"""The benchmark's own copy of the plain reference of the ``glm_moe_dsa``
architecture (paddle_tpu/models/glm_dsa_reference.py; it imports nothing
from the program, and tests/chipbench_suite/test_glm52_cell.py holds the two to
each other: identical from the equations on). GLM-5.2. The whole sequence
through every layer in straightforward float32 ``jax.numpy``,
``jax.default_matmul_precision("highest")``, no kernel, no cache, no
batching, reading the weights it is given by the parameter names
``models/block_decoder.py`` defines, upcast to float32.

The equations (what is marked + is not fixed by the published
``config.json`` and is listed under ``assumed`` in
``chipbench/configs/glm_5_2.json``). h_t is a layer's normed input at
position t; rms(x; w) = x rsqrt(mean(x^2) + eps) w.

    MLA (DeepSeek-V2/V3, no biases):
        c_q = rms(h W_qa; w_q);  q = c_q W_qb -> 64 heads of [q_nope | q_rope]
        [c_kv | k_r] = h W_kva;  c_kv = rms(c_kv; w_kv)
        q_rope, k_r rotated: interleaved pairs (2i, 2i + 1), theta from
        rope_parameters, no scaling
        score_h(t, s) = (q_nope_h . c_kv_s W_uk_h + q_rope_h . k_r_s) *
            (d_nope + d_rope)^-0.5, for s in S_t only; softmax over S_t;
        out = concat_h(sum_s p_h(t, s) c_kv_s W_uv_h) W_o
    indexer (a "full" layer; DeepSeek-V3.2's lightning indexer):
        q^I_{t,j} = rot(c_q W_qI)_j  (32 heads of 128, rotary on the first 64)
        k^I_s = rot(LayerNorm(h_s W_kI))  (one key every head shares; the
            LayerNorm with its weight and bias, eps 1e-6 +, in float32)
        w_{t,j} = (h_t W_w)_j 32^-1/2
        I(t, s) = 128^-1/2 sum_j w_{t,j} ReLU(q^I_{t,j} . k^I_s), s <= t
        S_t = the min(2048, t + 1) positions of largest I(t, .)
    a "shared" layer: S_t of the nearest full layer before it
    dense F:  (silu(h W1) * (h W3)) W2
    expert F: shared(h) + sum_{i in top_k(s + b)} scale s_i / (sum_top s +
        1e-6 +) FF_i(h), s = sigmoid(h W_r); ``experts_held``: only those
        experts' terms, the chip's share of an expert-parallel layer
    logits = rms(x; w_final) W_head^T

Departures: DeepSeek-V3.2's Hadamard rotation of the index queries and keys
is left out (orthogonal: q . k is unchanged), and so is their FP8 (float32
here). Attention is written in the absorbed form (the same products
reassociated): the plain form's expanded keys and values of a 33k-token
document, 3.8 GB in float32, do not fit beside the served model.

Top-k is discontinuous twice over here: a score within rounding of the k-th
largest flips an expert pick, or a selected position, and moves the logits
by far more than any rounding. So the reference can be told the picks and
the selections to follow (the system's own). It computes its own scores all
the same and reports, for every row, how far the least of the followed
picks lies below its own k-th largest router score (``pick_gaps``), and for
every full layer and row how many followed positions its own top-2048 does
not hold (``sel_outside``) and how far the least of them lies below its own
2048th score (``sel_gaps``). The caller bounds them.

At the published widths the pass goes in blocks so that it fits beside the
served model: a layer is two jitted calls (attention, then feed-forward),
each upcasting only its own weights, an expert at a time, and handing the
stream's buffer on (donated); the keys every position leaves and the
feed-forward are made ROW_BLOCK rows at a time, a query's indexer scores,
selection and attention QUERY_BLOCK positions at a time; the head is
applied HEAD_BLOCK rows of the vocabulary at a time to the rows asked for.
"""

import json

EPS_TOPK = 1e-6
INDEX_NORM_EPS = 1e-6
QUERY_BLOCK = 32
ROW_BLOCK = 2048
HEAD_BLOCK = 16384


def _rms(x, w, eps):
    import jax
    import jax.numpy as jnp

    y = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
    return y if w is None else y * w


def _layer_norm(x, w, b, eps):
    import jax
    import jax.numpy as jnp

    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w + b


def _rotary(x, pos, dim, theta):
    """x [t, ..., dim], interleaved pairs (2i, 2i + 1), theta^(-2i/dim)."""
    import jax.numpy as jnp

    inv = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    ang = pos.astype(jnp.float32)[:, None] * inv
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (ang.shape[-1],)
    cos = jnp.cos(ang).reshape(shape).astype(x.dtype)
    sin = jnp.sin(ang).reshape(shape).astype(x.dtype)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1).reshape(
        x.shape)


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


def index_scores(q_i, w_i, k_i, qpos):
    """I(t, s) [b, t_all] of b query rows (q_i [b, heads, d], w_i [b, heads],
    at positions qpos [b]) against every key k_i [t_all, d]; -inf where s >
    t."""
    import jax.numpy as jnp

    d = q_i.shape[-1]
    dots = jnp.maximum(jnp.einsum("bhd,sd->bhs", q_i, k_i), 0.0)
    s = jnp.einsum("bhs,bh->bs", dots, w_i) * (d ** -0.5)
    live = jnp.arange(k_i.shape[0])[None, :] <= qpos[:, None]
    return jnp.where(live, s, -jnp.inf)


def select(scores, topk, follow=None):
    """(the positions to attend [b, topk] (-1: none), how many of them the
    reference's own top-k does not hold [b], how far the least of them lies
    under its own k-th largest score [b]). ``follow`` [b, topk]: the
    selection to use in place of its own."""
    import jax
    import jax.numpy as jnp

    k = min(topk, scores.shape[1])
    top, own = jax.lax.top_k(scores, k)
    own = jnp.where(top > -jnp.inf, own, -1)
    if k < topk:
        own = jnp.pad(own, ((0, 0), (0, topk - k)), constant_values=-1)
    kth = top[:, -1]
    if follow is None:
        zero = jnp.zeros((scores.shape[0],), scores.dtype)
        return own.astype(jnp.int32), zero.astype(jnp.int32), zero
    use = jnp.asarray(follow, jnp.int32)
    valid = use >= 0
    got = jnp.take_along_axis(scores, jnp.maximum(use, 0), axis=1)
    below = valid & (got < kth[:, None])
    gap = jnp.max(jnp.where(below, kth[:, None] - got, 0.0), axis=1)
    return use, jnp.sum(below.astype(jnp.int32), axis=1), gap


def _precision(precision):
    """(the dtype everything is computed in, the matmul precision).
    "float32" is the reference. "bfloat16" is the nearest precision below
    what the configuration states: the indexer's scores, the selection, the
    norms, the router and the softmax in bfloat16 like everything else; it
    exists to show that the comparison's limits tell the two apart."""
    import jax.numpy as jnp

    if precision == "float32":
        return jnp.float32, "highest"
    if precision == "bfloat16":
        return jnp.bfloat16, "default"
    raise ValueError("precision must be 'float32' or 'bfloat16'")


_FNS = {}  # one jitted function a kind of sub-layer


def _attention_fn(role, spec, precision):
    """Attention of one layer as a jitted function of (x [t, d], its weights
    by short name, the selection of its group [t, topk] or None, the
    selection to follow [t, topk] or None): (x + attention, the selection
    attended, sel_outside [t], sel_gaps [t])."""
    import jax
    import jax.numpy as jnp

    key = json.dumps(["attn", role, spec, precision], sort_keys=True)
    if key in _FNS:
        return _FNS[key]
    eps, mla, dsa = spec["norm_eps"], spec["mla"], spec["dsa"]
    h, theta = spec["n_head"], spec["rope_theta"]
    dn, dr, rank = mla["d_nope"], mla["d_rope"], mla["kv_rank"]
    heads, d_i, topk = dsa["heads"], dsa["d_head"], dsa["topk"]
    dt, matmul = _precision(precision)

    def attention(x, w, group_sel, follow):
        with jax.default_matmul_precision(matmul):
            w = {k: v.astype(dt) for k, v in w.items()}
            t = x.shape[0]
            pos = jnp.arange(t)

            def keys(xb):  # what every position leaves: [c_kv | k_r], k^I, w
                u = _rms(xb, w["op_norm_w"], eps)
                kv = u @ w["kv_a_w"]
                if role != "full":
                    return (kv,)
                k_i = _layer_norm(
                    (u @ w["idx_k_w"]).astype(jnp.float32),
                    w["idx_k_norm_w"].astype(jnp.float32),
                    w["idx_k_norm_b"].astype(jnp.float32),
                    INDEX_NORM_EPS).astype(dt)
                return kv, k_i, (u @ w["idx_w_w"]) * (heads ** -0.5)

            kv, *index = (_unblock(y, t) for y in jax.lax.map(
                keys, _blocks(x, ROW_BLOCK)))
            c_kv = _rms(kv[:, :rank], w["kv_a_norm_w"], eps)
            k_r = _rotary(kv[:, rank:], pos, dr, theta)
            if role == "full":
                k_i, w_i = index
                k_i = jnp.concatenate(
                    [_rotary(k_i[:, :dr], pos, dr, theta), k_i[:, dr:]], -1)

            def one(block):
                xb, qpos, own_sel, fol = block
                c_q = _rms(_rms(xb, w["op_norm_w"], eps) @ w["q_a_w"],
                           w["q_a_norm_w"], eps)
                b = xb.shape[0]
                if role == "full":
                    q_i = (c_q @ w["idx_q_w"]).reshape(b, heads, d_i)
                    q_i = jnp.concatenate(
                        [_rotary(q_i[..., :dr], qpos, dr, theta),
                         q_i[..., dr:]], -1)
                    wb = jnp.take(w_i, qpos, axis=0)
                    sel, outside, gap = select(
                        index_scores(q_i, wb, k_i, qpos), topk,
                        None if follow is None else fol)
                else:
                    sel = own_sel
                    outside = jnp.zeros((b,), jnp.int32)
                    gap = jnp.zeros((b,), dt)
                q = (c_q @ w["q_b_w"]).reshape(b, h, dn + dr)
                q_nope = q[..., :dn]
                q_rope = _rotary(q[..., dn:], qpos, dr, theta)
                take = jnp.maximum(sel, 0)
                c_sel = jnp.take(c_kv, take, axis=0)  # [b, topk, rank]
                r_sel = jnp.take(k_r, take, axis=0)
                q_lat = jnp.einsum("bhn,hnr->bhr", q_nope, w["uk_w"])
                s = (jnp.einsum("bhr,bkr->bhk", q_lat, c_sel)
                     + jnp.einsum("bhe,bke->bhk", q_rope, r_sel)) * mla["sm_scale"]
                s = jnp.where((sel >= 0)[:, None, :], s, -jnp.inf)
                p = jax.nn.softmax(s, -1)
                ctx = jnp.einsum("bhk,bkr->bhr", p, c_sel)
                v = jnp.einsum("bhr,hrv->bhv", ctx, w["uv_w"]).reshape(b, -1)
                return xb + v @ w["o_w"], sel, outside, gap

            zeros = jnp.zeros((t, topk), jnp.int32)
            out, sel, outside, gap = jax.lax.map(one, (
                _blocks(x, QUERY_BLOCK), _blocks(pos, QUERY_BLOCK),
                _blocks(zeros if group_sel is None else group_sel, QUERY_BLOCK),
                _blocks(zeros if follow is None else follow, QUERY_BLOCK)))
            return (_unblock(out, t), _unblock(sel, t), _unblock(outside, t),
                    _unblock(gap, t))

    _FNS[key] = jax.jit(attention, donate_argnums=(0,))
    return _FNS[key]


def _ff_fn(ff, spec, precision):
    """The feed-forward sub-layer as a jitted function of (x [t, d], its
    weights by short name, the picks to follow or None): (x + F, own picks,
    pick gaps), ROW_BLOCK rows at a time."""
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
                    return xb + f, fol, jnp.zeros((xb.shape[0],), dt)
                f, own, gap = moe_layer(
                    u, small["router_w"], small.get("router_b"), w["exp_w1"],
                    w["exp_w3"], w["exp_w2"], moe,
                    None if follow is None else fol)
                if moe.get("shared_width"):
                    f = f + swiglu_ff(u, small["sh_w1"], small["sh_w3"], small["sh_w2"])
                return xb + f, own, gap

            fol = follow if follow is not None else jnp.zeros((t, k_top), jnp.int32)
            out, own, gap = jax.lax.map(one, (
                _blocks(x, ROW_BLOCK), _blocks(fol, ROW_BLOCK)))
            return _unblock(out, t), _unblock(own, t), _unblock(gap, t)

    _FNS[key] = jax.jit(feed_forward, donate_argnums=(0,))
    return _FNS[key]


def forward(weights, tokens, spec, picks=None, selections=None, rows=None,
            precision="float32"):
    """{"logits": [t or len(rows), vocab] float32, "picks": [n_moe, t, k]
    (its own), "pick_gaps": [n_moe, t], "selections": [n_full, t, topk] (the
    attended ones), "sel_outside": [n_full, t], "sel_gaps": [n_full, t]}.
    ``spec`` is ``spec_of(model)``; ``picks`` [n_moe, t, k] and
    ``selections`` [n_full, t, topk], if given, are followed (module
    docstring)."""
    import jax
    import jax.numpy as jnp

    prefix = spec["prefix"]
    name = lambda *parts: "_".join((prefix,) + parts)
    tokens = jnp.asarray(tokens, jnp.int32)
    dt, matmul = _precision(precision)
    x = jnp.take(weights[name("tok_emb")], tokens, axis=0).astype(dt)
    out = {k: [] for k in ("picks", "pick_gaps", "selections", "sel_outside",
                           "sel_gaps")}
    group = None
    for i, (op, ff) in enumerate(spec["blocks"]):
        if op != "dsa":
            raise ValueError("the glm_moe_dsa reference has dsa layers only")
        role = spec["dsa"]["roles"][i]
        lead = name("l%d" % i, "")
        w = {k[len(lead):]: v for k, v in weights.items() if k.startswith(lead)}
        attn_w = {k: v for k, v in w.items() if not k.startswith(
            ("ff", "exp_", "router_", "sh_"))}
        follow = None
        if role == "full" and selections is not None:
            follow = jnp.asarray(selections[len(out["selections"])], jnp.int32)
        # a sub-layer at a time on the device: run ahead, the host would
        # hold every layer's temporaries at once beside the served model
        x, sel, outside, gap = jax.block_until_ready(
            _attention_fn(role, spec, precision)(x, attn_w, group, follow))
        if role == "full":
            group = sel
            out["selections"].append(sel)
            out["sel_outside"].append(outside)
            out["sel_gaps"].append(gap.astype(jnp.float32))
        ff_w = {k: v for k, v in w.items() if k.startswith(
            ("ffn_norm", "ff", "exp_", "router_", "sh_"))}
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
                rope_theta=model.rope_theta)
