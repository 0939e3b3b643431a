"""The plain reference of the ``afmoe`` architecture (Arcee's Trinity:
``models/block_decoder.py`` ``afmoe``): the whole sequence through every
layer in straightforward float32 ``jax.numpy``,
``jax.default_matmul_precision("highest")``, no kernel, no cache, no
batching, reading the weights it is given by the parameter names
``models/block_decoder.py`` defines, upcast to float32.

The equations (what is marked + is not fixed by the published
``config.json``: the afmoe model type's modelling code, listed under
``assumed`` in the benchmark's configuration file). rms(x; w) = x
rsqrt(mean(x^2) + eps) w.

    x_0 = E[token] * hidden_size^1/2 (+ mup_enabled)
    every layer, sandwich norms (+):
        h = x + rms(Attn(rms(x; w_in)); w_post_attn)
        x' = h + rms(F(rms(h; w_pre_ff)); w_post_ff)
    Attn(u), grouped-query, no biases:
        q = rms_head(u W_q; w_q) [heads of d], k = rms_head(u W_k; w_k),
        v = u W_v  [kv heads of d]; query head i reads kv head
        i // (heads / kv heads)
        a "sliding_attention" layer: rotary over the whole head (pairs (i,
        i + d/2), theta rope_theta), and query t attends
        t - sliding_window < s <= t
        a "full_attention" layer: no position encoding (+), s <= t
        p = softmax_s(q_t . k_s d^-1/2)
        Attn = (concat_h(sum_s p v_s) * sigmoid(u W_g)) W_o  (the gate +)
    dense F:  (silu(u W1) * (u W3)) W2
    expert F: shared(u) + sum_{i in top_k(s + b)} route_scale s_i /
        (sum_top s + 1e-6 +) FF_i(u), s = sigmoid(u W_r) in float32, b the
        expert bias (+ a learned buffer; from the seed here);
        ``experts_held``: only those experts' terms, the chip's share of an
        expert-parallel layer
    logits = rms(x; w_final) W_head^T

Top-k is discontinuous: a score within rounding of the k-th largest flips
an expert pick and moves the logits by far more than any rounding. So the
reference can be told the picks to follow (the system's own). It computes
its own scores all the same and reports, for every row, how far the least
of the followed picks lies below its own k-th largest score
(``pick_gaps``); the caller bounds it.

``precision="bfloat16"`` computes everything, the norms, the router, the
scores and the softmax included, in bfloat16: the nearest precision below
what the configuration states. ``full_window=True`` lets every window layer
attend its whole context. Both exist to be put in the system's place, to
show that a comparison's limits tell them apart.

At the published widths the pass goes in blocks so that it fits beside the
served model: a layer is two jitted calls (attention, then feed-forward),
each upcasting only its own weights, an expert at a time, and handing the
stream's buffer on (donated); the keys and values every position leaves
and the feed-forward are made ROW_BLOCK rows at a time, the attention
QUERY_BLOCK queries at a time (a window layer's against the window before
them alone); the head is applied HEAD_BLOCK rows of the vocabulary at a
time to the rows asked for.
"""

import json

EPS_TOPK = 1e-6
QUERY_BLOCK = 32
ROW_BLOCK = 2048
HEAD_BLOCK = 16384


def _rms(x, w, eps):
    import jax
    import jax.numpy as jnp

    y = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
    return y if w is None else y * w


def _rotary(x, pos, theta):
    """x [t, heads, d], the half-rotation form: pairs (i, i + d/2),
    theta^(-2i/d)."""
    import jax.numpy as jnp

    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos.astype(jnp.float32)[:, None] * inv
    cos = jnp.cos(ang)[:, None, :].astype(x.dtype)
    sin = jnp.sin(ang)[:, None, :].astype(x.dtype)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _blocks(y, n):
    """y [t, ...] padded to whole blocks of n and cut into them."""
    import jax.numpy as jnp

    pad = -y.shape[0] % n
    return jnp.pad(y, ((0, pad),) + ((0, 0),) * (y.ndim - 1)).reshape(
        (-1, n) + y.shape[1:])


def _unblock(y, t):
    return y.reshape((-1,) + y.shape[2:])[:t]


def swiglu_ff(u, w1, w3, w2):
    import jax

    return (jax.nn.silu(u @ w1) * (u @ w3)) @ w2


def moe_layer(u, router_w, router_b, w1, w3, w2, moe, follow=None):
    """(the routed sum [t, d] over the experts held, the reference's own
    picks [t, k], pick gaps [t]). w1, w3 [held, d, f] and w2 [held, f, d]
    hold the experts ``moe["experts_held"]`` (default: all) in that order
    and may be bfloat16: one expert is upcast at a time, inside the loop.
    ``follow`` [t, k]: picks to use in place of its own."""
    import jax
    import jax.numpy as jnp

    t, k_top, n_exp = u.shape[0], moe["k"], moe["num_experts"]
    held = list(moe.get("experts_held") or range(n_exp))
    s = jax.nn.sigmoid(u @ router_w)
    sel = s if router_b is None else s + router_b
    top, own = jax.lax.top_k(sel, k_top)
    if follow is None:
        use, gap = own, jnp.zeros((t,), u.dtype)
    else:
        use = jnp.asarray(follow, jnp.int32)
        gap = jnp.maximum(
            top[:, -1] - jnp.min(jnp.take_along_axis(sel, use, axis=1), axis=1),
            0.0)
    g = jnp.take_along_axis(s, use, axis=1)
    if moe.get("norm_topk", True):
        g = g / (jnp.sum(g, -1, keepdims=True) + EPS_TOPK)
    g = g * moe.get("scale", 1.0)
    dense_g = jnp.zeros((t, n_exp), u.dtype).at[
        jnp.arange(t)[:, None], use].add(g)
    ids = jnp.asarray(held, jnp.int32)

    def one(i, f):
        up = lambda m: m[i].astype(u.dtype)
        fe = swiglu_ff(u, up(w1), up(w3), up(w2))
        return f + jnp.take(dense_g, ids[i], axis=1)[:, None] * fe

    f = jax.lax.fori_loop(0, len(held), one, jnp.zeros_like(u))
    return f, own.astype(jnp.int32), gap


def _precision(precision):
    """(the dtype everything is computed in, the matmul precision)."""
    import jax.numpy as jnp

    if precision == "float32":
        return jnp.float32, "highest"
    if precision == "bfloat16":
        return jnp.bfloat16, "default"
    raise ValueError("precision must be 'float32' or 'bfloat16'")


_FNS = {}  # one jitted function a kind of sub-layer


def _attention_fn(window, rotary, spec, precision):
    """Attention of one layer as a jitted function of (x [t, d], its weights
    by short name): x + rms(Attn(rms(x))), QUERY_BLOCK queries at a time.
    `window` 0: the whole context."""
    import jax
    import jax.numpy as jnp

    key = json.dumps(["attn", window, rotary, spec, precision], sort_keys=True)
    if key in _FNS:
        return _FNS[key]
    eps, theta = spec["norm_eps"], spec["rope_theta"]
    h, hk, d = spec["n_head"], spec["n_kv_head"], spec["d_head"]
    group = h // hk
    dt, matmul = _precision(precision)

    def attention(x, w):
        with jax.default_matmul_precision(matmul):
            w = {k: v.astype(dt) for k, v in w.items()}
            t = x.shape[0]
            pos = jnp.arange(t)

            def kv(block):
                xb, pb = block
                u = _rms(xb, w["op_norm_w"], eps)
                k = _rms((u @ w["k_w"]).reshape(-1, hk, d), w["k_norm_w"], eps)
                if rotary:
                    k = _rotary(k, pb, theta)
                return k, (u @ w["v_w"]).reshape(-1, hk, d)

            k, v = (_unblock(y, t) for y in jax.lax.map(
                kv, (_blocks(x, ROW_BLOCK), _blocks(pos, ROW_BLOCK))))
            if window:
                # the window before a block's first query, then the block
                k = jnp.pad(k, ((window, QUERY_BLOCK), (0, 0), (0, 0)))
                v = jnp.pad(v, ((window, QUERY_BLOCK), (0, 0), (0, 0)))

            def one(block):
                xb, qpos = block
                b = xb.shape[0]
                u = _rms(xb, w["op_norm_w"], eps)
                q = _rms((u @ w["q_w"]).reshape(b, h, d), w["q_norm_w"], eps)
                if rotary:
                    q = _rotary(q, qpos, theta)
                if window:
                    first = qpos[0]
                    kb = jax.lax.dynamic_slice_in_dim(k, first, window + b)
                    vb = jax.lax.dynamic_slice_in_dim(v, first, window + b)
                    spos = first - window + jnp.arange(window + b)
                else:
                    kb, vb, spos = k, v, pos
                qg = q.reshape(b, hk, group, d)
                s = jnp.einsum("bkgd,skd->bkgs", qg, kb) * (d ** -0.5)
                live = spos[None, :] <= qpos[:, None]
                if window:
                    live = live & (spos[None, :] > qpos[:, None] - window) & (
                        spos[None, :] >= 0)
                s = jnp.where(live[:, None, None, :], s, -jnp.inf)
                p = jax.nn.softmax(s, -1)
                ctx = jnp.einsum("bkgs,skd->bkgd", p, vb).reshape(b, h * d)
                ctx = ctx * jax.nn.sigmoid(u @ w["g_w"])
                return xb + _rms(ctx @ w["o_w"], w["op_post_norm_w"], eps)

            out = jax.lax.map(one, (_blocks(x, QUERY_BLOCK),
                                    _blocks(pos, QUERY_BLOCK)))
            return _unblock(out, t)

    _FNS[key] = jax.jit(attention, donate_argnums=(0,))
    return _FNS[key]


def _ff_fn(ff, spec, precision):
    """The feed-forward sub-layer as a jitted function of (x [t, d], its
    weights by short name, the picks to follow or None): (x +
    rms(F(rms(x))), own picks, pick gaps), ROW_BLOCK rows at a time."""
    import jax
    import jax.numpy as jnp

    key = json.dumps(["ff", ff, spec, precision], sort_keys=True)
    if key in _FNS:
        return _FNS[key]
    eps, moe = spec["norm_eps"], spec["moe"]
    dt, matmul = _precision(precision)

    def feed_forward(x, w, follow):
        with jax.default_matmul_precision(matmul):
            small = {k: v.astype(dt) for k, v in w.items()
                     if not k.startswith("exp_")}
            t = x.shape[0]
            k_top = moe.get("k", 1) if ff == "moe" else 1

            def one(block):
                xb, fol = block
                u = _rms(xb, small["ffn_norm_w"], eps)
                if ff == "dense":
                    f = swiglu_ff(u, small["ff1_w"], small["ff3_w"], small["ff2_w"])
                    own, gap = fol, jnp.zeros((xb.shape[0],), dt)
                else:
                    f, own, gap = moe_layer(
                        u, small["router_w"], small.get("router_b"),
                        w["exp_w1"], w["exp_w3"], w["exp_w2"], moe,
                        None if follow is None else fol)
                    if moe.get("shared_width"):
                        f = f + swiglu_ff(u, small["sh_w1"], small["sh_w3"],
                                          small["sh_w2"])
                return xb + _rms(f, small["ffn_post_norm_w"], eps), own, gap

            fol = follow if follow is not None else jnp.zeros((t, k_top), jnp.int32)
            out, own, gap = jax.lax.map(one, (
                _blocks(x, ROW_BLOCK), _blocks(fol, ROW_BLOCK)))
            return _unblock(out, t), _unblock(own, t), _unblock(gap, t)

    _FNS[key] = jax.jit(feed_forward, donate_argnums=(0,))
    return _FNS[key]


def forward(weights, tokens, spec, picks=None, rows=None, precision="float32",
            full_window=False):
    """{"logits": [t or len(rows), vocab] float32, "picks": [n_moe, t, k]
    (its own), "pick_gaps": [n_moe, t]}. ``spec`` is ``spec_of(model)``;
    ``picks`` [n_moe, t, k], if given, are followed (module docstring)."""
    import jax
    import jax.numpy as jnp

    prefix = spec["prefix"]
    name = lambda *parts: "_".join((prefix,) + parts)
    tokens = jnp.asarray(tokens, jnp.int32)
    dt, matmul = _precision(precision)
    x = jnp.take(weights[name("tok_emb")], tokens, axis=0).astype(dt)
    x = x * jnp.asarray(spec["embed_scale"], dt)
    out = {"picks": [], "pick_gaps": []}
    windows = spec.get("windows") or [0] * len(spec["blocks"])
    positions = spec["positions"]
    for i, (op, ff) in enumerate(spec["blocks"]):
        if op != "attention":
            raise ValueError("the afmoe reference has attention layers only")
        lead = name("l%d" % i, "")
        w = {k[len(lead):]: v for k, v in weights.items() if k.startswith(lead)}
        attn_w = {k: v for k, v in w.items() if k.startswith(
            ("op_", "q_", "k_", "v_", "g_", "o_"))}
        window = 0 if full_window else windows[i]
        # a sub-layer at a time on the device: run ahead, the host would
        # hold every layer's temporaries at once beside the served model
        x = jax.block_until_ready(_attention_fn(
            window, positions[i] == "rotary", spec, precision)(x, attn_w))
        ff_w = {k: v for k, v in w.items() if k.startswith(
            ("ffn_", "ff", "exp_", "router_", "sh_"))}
        fol = None
        if ff == "moe" and picks is not None:
            fol = jnp.asarray(picks[len(out["picks"])], jnp.int32)
        x, own, pgap = jax.block_until_ready(
            _ff_fn(ff, spec, precision)(x, ff_w, fol))
        if ff == "moe":
            out["picks"].append(own)
            out["pick_gaps"].append(pgap.astype(jnp.float32))
    if rows is not None:
        x = x[jnp.asarray(rows)]
    head = weights[name("tok_emb" if spec["tied_head"] else "head_w")]
    with jax.default_matmul_precision(matmul):
        hx = _rms(x, weights[name("final_norm_w")].astype(dt), spec["norm_eps"])
        logits = jnp.concatenate([
            jax.block_until_ready(
                (hx @ head[v0:v0 + HEAD_BLOCK].astype(dt).T).astype(jnp.float32))
            for v0 in range(0, head.shape[0], HEAD_BLOCK)], -1)
    result = {"logits": logits}
    for k, v in out.items():
        result[k] = jnp.stack(v) if v else None
    return result


def spec_of(model):
    """What forward() needs to know of a BlockDecoder."""
    return dict(model.spec(), prefix=model.prefix, norm_eps=model.norm_eps,
                rope_theta=model.rope_theta, positions=model.positions,
                windows=model.windows, embed_scale=model.embed_scale)
