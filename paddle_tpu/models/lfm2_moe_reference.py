"""The plain reference of the ``lfm2_moe`` architecture (LFM2-8B-A1B): the
whole sequence through every layer in straightforward float32 ``jax.numpy``,
``jax.default_matmul_precision("highest")``, no cache, no kernel, no
batching, under a causal mask. It reads the weights it is given by the
parameter names ``models/block_decoder.py`` defines, upcast to float32.

The equations (ISSUE 27; the published modelling code's, with the
departures listed in ``chipbench/configs/lfm2_8b_a1b.json`` ``assumed``):

    rms(x; w)  = x * rsqrt(mean(x^2, -1) + eps) * w
    h  = x + OP_l(rms(x; w_op));  x' = h + FF_l(rms(h; w_ffn))
    logits = rms(x_L; w_final) @ E^T;  x_0 = E[token]

    conv:  [B, C, z] = split3(u @ W_in); v = B * z;
           c_t = sum_j k[:, j] * v_{t-(L-1)+j};  y = (C * c) @ W_out
    attention: 32 query heads over 8 KV heads (query head i reads KV head
           i // group), q and k RMS-normed per head before rotary (half
           rotation, theta), causal softmax at d_head^-0.5, no biases
    dense FF:  (silu(u @ W1) * (u @ W3)) @ W2
    router:    s = sigmoid(u @ W_g); I = top_k(s + b); g_i = s_i / (sum_I s
           + 1e-6) * scale;  MoE(u) = sum_{i in I} g_i FF_i(u)

Top-k is discontinuous: a score within rounding of the k-th largest flips a
pick and moves the logits by far more than any rounding. So the reference
can be told the picks to follow (``picks``, the system's own): it computes
its own scores all the same, reports for every row whose set of picks
differs how far each of the system's picks lies below the reference's k-th
largest selection score (``pick_gaps``), and then weighs the system's
picks with its own scores. The caller bounds the gaps and their count.

At the published widths the float32 copy of everything would not fit beside
the served model, so the pass goes in blocks: a layer is one jitted call
that upcasts that layer's weights only, the experts' matrices one expert at
a time inside a loop, the embedding only at the tokens' rows, and the head
is applied to the rows asked for.
"""

import json

EPS_TOPK = 1e-6


def _rms(x, w, eps):
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rotary(x, pos, theta):
    """x: [t, heads, d]; half-rotation form."""
    import jax.numpy as jnp

    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None, :].astype(x.dtype)
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None, :].astype(x.dtype)
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def conv_operator(u, w_in, kern, w_out):
    """The gated short convolution of one whole sequence from zeros."""
    import jax.numpy as jnp

    t, d = u.shape
    bcz = u @ w_in
    b, c, z = bcz[:, :d], bcz[:, d:2 * d], bcz[:, 2 * d:]
    taps = kern.shape[1]
    v = jnp.pad(b * z, ((taps - 1, 0), (0, 0)))
    conv = sum(v[j:j + t] * kern[:, j] for j in range(taps))
    return (c * conv) @ w_out


def attention_operator(u, w, n_head, n_kv, d_head, eps, theta):
    """Grouped-query causal attention of one whole sequence; w holds q_w,
    k_w, v_w, o_w, q_norm_w, k_norm_w."""
    import jax
    import jax.numpy as jnp

    t = u.shape[0]
    pos = jnp.arange(t)
    q = (u @ w["q_w"]).reshape(t, n_head, d_head)
    k = (u @ w["k_w"]).reshape(t, n_kv, d_head)
    v = (u @ w["v_w"]).reshape(t, n_kv, d_head)
    q = _rotary(_rms(q, w["q_norm_w"], eps), pos, theta)
    k = _rotary(_rms(k, w["k_norm_w"], eps), pos, theta)
    k = jnp.repeat(k, n_head // n_kv, axis=1)  # query head i: KV head i // group
    v = jnp.repeat(v, n_head // n_kv, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k) * d_head ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    ctx = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v)
    return ctx.reshape(t, n_head * d_head) @ w["o_w"]


def moe_layer(u, router_w, router_b, w1, w3, w2, moe, follow=None):
    """(MoE(u) [t, d], the reference's own picks [t, k], pick gaps [t]): the
    whole layer, every expert. w1, w3 [experts, d, f] and w2 [experts, f, d]
    may be bfloat16: one expert is upcast at a time, inside the loop.
    ``follow`` [t, k]: picks to use in place of its own."""
    import jax
    import jax.numpy as jnp

    t, k_top, n_exp = u.shape[0], moe["k"], moe["num_experts"]
    s = jax.nn.sigmoid(u @ router_w)
    sel = s if router_b is None else s + router_b
    top, own = jax.lax.top_k(sel, k_top)
    if follow is None:
        use, gap = own, jnp.zeros((t,), u.dtype)
    else:
        use = jnp.asarray(follow, jnp.int32)
        # how far the least of the followed picks lies below the
        # reference's own k-th largest selection score
        gap = jnp.maximum(
            top[:, -1] - jnp.min(jnp.take_along_axis(sel, use, axis=1), axis=1),
            0.0)
    g = jnp.take_along_axis(s, use, axis=1)
    if moe.get("norm_topk", True):
        g = g / (jnp.sum(g, -1, keepdims=True) + EPS_TOPK)
    g = g * moe.get("scale", 1.0)
    # dense over the experts with a 0/1 choice: plain, not fast
    dense_g = jnp.zeros((t, n_exp), u.dtype).at[
        jnp.arange(t)[:, None], use].add(g)

    def one(e, f):
        up = lambda m: m[e].astype(u.dtype)
        fe = (jax.nn.silu(u @ up(w1)) * (u @ up(w3))) @ up(w2)
        return f + jnp.take(dense_g, e, axis=1)[:, None] * fe

    f = jax.lax.fori_loop(0, n_exp, one, jnp.zeros_like(u))
    return f, own.astype(jnp.int32), gap


def _precision(precision):
    """(the dtype everything is computed in, the matmul precision).
    "float32" is the reference. "bfloat16" is the nearest precision below
    what the configuration states: the norms, the router, the softmax and
    the conv's sums in bfloat16 like everything else; it exists to show
    that the comparison's limits tell the two apart."""
    import jax.numpy as jnp

    if precision == "float32":
        return jnp.float32, "highest"
    if precision == "bfloat16":
        return jnp.bfloat16, "default"
    raise ValueError("precision must be 'float32' or 'bfloat16'")


_LAYERS = {}  # one jitted function a kind of layer


def _layer_fn(op, ff, sizes, moe, precision):
    """One layer as a jitted function of (x, its weights by short name, the
    picks to follow or None)."""
    import jax

    key = json.dumps([op, ff, sizes, moe, precision], sort_keys=True)
    if key in _LAYERS:
        return _LAYERS[key]
    n_head, n_kv, d_head, eps, theta = sizes
    dt, matmul = _precision(precision)

    def layer(x, w, follow):
        with jax.default_matmul_precision(matmul):
            w32 = {k: v.astype(dt) for k, v in w.items()
                   if not k.startswith("exp_")}
            u = _rms(x, w32["op_norm_w"], eps)
            if op == "conv":
                o = conv_operator(
                    u, w32["conv_in_w"], w32["conv_k"], w32["conv_out_w"])
            else:
                o = attention_operator(u, w32, n_head, n_kv, d_head, eps, theta)
            h = x + o
            u = _rms(h, w32["ffn_norm_w"], eps)
            if ff == "dense":
                f = (jax.nn.silu(u @ w32["ff1_w"]) * (u @ w32["ff3_w"])
                     ) @ w32["ff2_w"]
                return h + f, None, None
            f, own, gap = moe_layer(
                u, w32["router_w"], w32.get("router_b"), w["exp_w1"],
                w["exp_w3"], w["exp_w2"], moe, follow)
            return h + f, own, gap

    _LAYERS[key] = jax.jit(layer)
    return _LAYERS[key]


def forward(weights, tokens, spec, picks=None, rows=None,
            precision="float32"):
    """(logits [t, vocab] float32 — or of `rows` only —, picks [n_moe, t, k]
    int32, pick_gaps [n_moe, t] float32). ``spec`` is ``BlockDecoder.spec()``
    plus ``prefix``, ``norm_eps`` and ``rope_theta``; ``picks`` [n_moe, t,
    k], if given, are followed (module docstring)."""
    import jax
    import jax.numpy as jnp

    prefix = spec["prefix"]
    name = lambda *parts: "_".join((prefix,) + parts)
    sizes = [spec["n_head"], spec["n_kv_head"], spec["d_head"],
             spec["norm_eps"], spec["rope_theta"]]
    tokens = jnp.asarray(tokens, jnp.int32)
    dt, matmul = _precision(precision)
    x = jnp.take(weights[name("tok_emb")], tokens, axis=0).astype(dt)
    own_picks, gaps = [], []
    for i, (op, ff) in enumerate(spec["blocks"]):
        lead = name("l%d" % i, "")
        w = {k[len(lead):]: v for k, v in weights.items() if k.startswith(lead)}
        fn = _layer_fn(op, ff, sizes, spec["moe"], precision)
        follow = None
        if ff == "moe" and picks is not None:
            follow = jnp.asarray(picks[len(own_picks)], jnp.int32)
        x, own, gap = fn(x, w, follow)
        if ff == "moe":
            own_picks.append(own)
            gaps.append(gap)
    if rows is not None:
        x = x[jnp.asarray(rows)]
    with jax.default_matmul_precision(matmul):
        h = _rms(x, weights[name("final_norm_w")].astype(dt), spec["norm_eps"])
        logits = (h @ weights[name("tok_emb")].astype(dt).T).astype(jnp.float32)
    if own_picks:
        return logits, jnp.stack(own_picks), jnp.stack(gaps).astype(jnp.float32)
    return logits, None, None


def spec_of(model):
    """What forward() needs to know of a BlockDecoder."""
    return dict(model.spec(), prefix=model.prefix, norm_eps=model.norm_eps,
                rope_theta=model.rope_theta)
