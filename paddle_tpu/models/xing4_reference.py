"""The plain reference of the ``xing4_0`` architecture (Xing4.0-29B-A4B),
beside the served model (models/block_decoder.py ``xing4_0``) and, copied
below the docstring, with the benchmark (chipbench/reference_xing4.py;
tests/chipbench_suite/test_xing4.py holds the two to each other). The
whole sequence through every layer in straightforward float32 ``jax.numpy``,
``jax.default_matmul_precision("highest")``, no cache, no kernel, no
batching, under a causal mask, attention in its PLAIN form (every head's key
and value expanded from the latent; the served path attends absorbed). It
reads the weights it is given by the parameter names
``models/block_decoder.py`` defines, upcast to float32.

The equations (ISSUE 31; everything marked + is not fixed by the published
``config.json`` and is listed in ``chipbench/configs/xing4_29b_a4b.json``
``assumed``). The residual stream is n = hc_mult copies a token, X in
R^{n x d}; X_0 = the token's embedding in every copy +; before the last norm
the copies are summed +. A layer is two sub-layers F (attention, then the
feed-forward), each inside its own manifold-constrained hyper-connection
(mHC, arXiv:2512.24880):

    x~ = vec(X) * rsqrt(mean(vec(X)^2) + rms_norm_eps)   no learned scale +
    [pre~ n | post~ n | res~ n x n] = (x~ P) * [a_pre | a_post | a_res] + b
    H_pre = sigmoid(pre~);  H_post = 2 sigmoid(post~)
    H_res = SK(clip(res~, clamp_min, clamp_max)): M = exp(.), then
        hc_sinkhorn_iters times: rows /= row sums + hc_eps, then
        columns /= column sums + hc_eps                     (the order +)
    u = H_pre X;  y = F(rms(u; w));  X' = H_res X + H_post^T y

    attention F (DeepSeek-V2/V3's MLA, no biases):
        c_q = rms(u W_dq; w_q);  q = c_q W_uq -> heads of [q_nope | q_rope]
        [c_kv | k_r] = u W_dkv;  c_kv = rms(c_kv; w_kv)
        q_rope, k_r rotated, interleaved pairs (2i, 2i + 1) +, YaRN's
        blended frequencies over the d_rope columns; cos and sin times
        mscale / mscale_all_dim
        k_h = [c_kv W_uk_h | k_r];  v_h = c_kv W_uv_h
        scores (q_h . k_h) (d_nope + d_rope)^-0.5 m^2, m = 0.1 mscale_all_dim
        ln(factor) + 1; causal softmax; concat_h(softmax v_h) W_o
    dense F:  (silu(u W1) * (u W3)) W2
    expert F: shared(u) + sum_{i in top_k(s + b)} scale s_i / (sum_top s +
        1e-6 +) FF_i(u), s = sigmoid(u W_r); shared and every FF_i a SwiGLU.
        ``experts_held``: only those experts' terms are computed, the
        chip's share of an expert-parallel layer; what the absent experts
        would add is left out here as in the served model.
    logits = rms(sum_j X_j; w_final) W_head^T   (the head is its own matrix)

Top-k is discontinuous: a score within rounding of the k-th largest flips a
pick and moves the logits by far more than any rounding. So the reference
can be told the picks to follow (``picks``, the system's own): it computes
its own scores all the same, reports for every row how far the least of the
followed picks lies below its own k-th largest selection score
(``pick_gaps``), and weighs the followed picks with its own scores. The
caller bounds the gaps and the share of rows that differ.

At the published widths a float32 copy of everything does not fit beside
the served model, so the pass goes in blocks: a layer is one jitted call
that upcasts that layer's weights only, an expert at a time inside a loop;
attention scores are made for ATTENTION_BLOCK query positions at a time;
the embedding is read at the tokens' rows and the head is applied, HEAD_BLOCK
rows of the vocabulary at a time, to the rows asked for.
"""

import json
import math

EPS_TOPK = 1e-6
ATTENTION_BLOCK = 256
HEAD_BLOCK = 16384


def _rms(x, w, eps):
    import jax
    import jax.numpy as jnp

    y = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
    return y if w is None else y * w


def yarn_inv_freq(dim, theta, yarn):
    """The dim // 2 rotary frequencies as plain floats; ``yarn`` = [factor,
    original positions, beta_fast, beta_slow] or None (theta^(-2i/dim))."""
    plain = [theta ** (-2.0 * i / dim) for i in range(dim // 2)]
    if not yarn or yarn[0] == 1:
        return plain
    factor, original, beta_fast, beta_slow = yarn

    def pair_of(turns):  # the pair that completes `turns` turns over `original`
        return dim * math.log(original / (turns * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(pair_of(beta_fast)), 0)
    high = min(math.ceil(pair_of(beta_slow)), dim - 1)
    span = (high - low) or 0.001
    out = []
    for i, f in enumerate(plain):
        ramp = min(max((i - low) / span, 0.0), 1.0)
        out.append(f / factor * ramp + f * (1.0 - ramp))
    return out


def _rotary(x, pos, inv_freq, mscale):
    """x [t, ..., d_rope], interleaved pairs (2i, 2i + 1)."""
    import jax.numpy as jnp

    ang = pos.astype(jnp.float32)[:, None] * jnp.asarray(inv_freq, jnp.float32)
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (ang.shape[-1],)
    cos = (jnp.cos(ang) * mscale).reshape(shape).astype(x.dtype)
    sin = (jnp.sin(ang) * mscale).reshape(shape).astype(x.dtype)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1).reshape(
        x.shape)


def hyper_maps(xs, p, alpha, bias, hyper, eps):
    """(H_pre [t, n], H_post [t, n], H_res [t, n, n]) of the stream xs
    [t, n, d]; p [n * d, 2n + n * n], alpha [3], bias [2n + n * n]."""
    import jax
    import jax.numpy as jnp

    t, n, _ = xs.shape
    gain = jnp.concatenate([
        jnp.broadcast_to(alpha[i], (width,))
        for i, width in enumerate((n, n, n * n))])
    raw = (_rms(xs.reshape(t, -1), None, eps) @ p) * gain + bias
    lo, hi = hyper["clamp"]
    m = jnp.exp(jnp.clip(raw[:, 2 * n:], lo, hi).reshape(t, n, n))
    for _ in range(hyper["iters"]):
        m = m / (jnp.sum(m, -1, keepdims=True) + hyper["eps"])
        m = m / (jnp.sum(m, -2, keepdims=True) + hyper["eps"])
    return (jax.nn.sigmoid(raw[:, :n]), 2.0 * jax.nn.sigmoid(raw[:, n:2 * n]),
            m)


def hyper_sublayer(xs, w, which, hyper, eps, fn):
    """X' = H_res X + H_post^T fn(rms(H_pre X; w_norm)); fn returns (y,
    extras)."""
    import jax.numpy as jnp

    h_pre, h_post, h_res = hyper_maps(
        xs, w[which + "_hc_w"], w[which + "_hc_a"], w[which + "_hc_b"],
        hyper, eps)
    u = jnp.einsum("tj,tjd->td", h_pre, xs)
    y, extras = fn(_rms(u, w[which + "_norm_w"], eps))
    return (jnp.einsum("tjk,tkd->tjd", h_res, xs)
            + h_post[:, :, None] * y[:, None, :]), extras


def mla_operator(u, w, n_head, mla, theta):
    """Latent attention of one whole sequence, plain form: every head's key
    [c_kv W_uk_h | k_r] and value c_kv W_uv_h expanded; w holds q_a_w,
    q_a_norm_w, q_b_w, kv_a_w, kv_a_norm_w, uk_w [h, d_nope, kv_rank], uv_w
    [h, kv_rank, d_value], o_w; eps under "eps"."""
    import jax
    import jax.numpy as jnp

    t = u.shape[0]
    dn, dr, rank = mla["d_nope"], mla["d_rope"], mla["kv_rank"]
    pos = jnp.arange(t)
    inv = yarn_inv_freq(dr, theta, mla.get("yarn"))
    mscale = mla.get("rope_mscale", 1.0)
    c_q = _rms(u @ w["q_a_w"], w["q_a_norm_w"], w["eps"])
    q = (c_q @ w["q_b_w"]).reshape(t, n_head, dn + dr)
    q_nope, q_rope = q[..., :dn], _rotary(q[..., dn:], pos, inv, mscale)
    kv = u @ w["kv_a_w"]
    c_kv = _rms(kv[:, :rank], w["kv_a_norm_w"], w["eps"])
    k_r = _rotary(kv[:, rank:], pos, inv, mscale)
    k_nope = jnp.einsum("tr,hnr->thn", c_kv, w["uk_w"])
    v = jnp.einsum("tr,hrv->thv", c_kv, w["uv_w"])
    # ATTENTION_BLOCK query positions at a time against every key, under the
    # causal mask: the scores of 4k+ positions whole would not fit
    pad = -t % ATTENTION_BLOCK
    blocks = lambda y: jnp.pad(y, ((0, pad),) + ((0, 0),) * (y.ndim - 1)).reshape(
        (-1, ATTENTION_BLOCK) + y.shape[1:])

    def one(block):
        qn, qr, qpos = block
        s = (jnp.einsum("qhn,khn->hqk", qn, k_nope)
             + jnp.einsum("qhr,kr->hqk", qr, k_r)) * mla["sm_scale"]
        s = jnp.where((pos[None, :] <= qpos[:, None])[None], s, -jnp.inf)
        return jnp.einsum("hqk,khv->qhv", jax.nn.softmax(s, -1), v)

    ctx = jax.lax.map(one, (blocks(q_nope), blocks(q_rope), blocks(pos)))
    return ctx.reshape(t + pad, -1)[:t] @ w["o_w"]


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
    ids = jnp.asarray(held, jnp.int32)

    def one(i, f):
        up = lambda m: m[i].astype(u.dtype)
        fe = swiglu_ff(u, up(w1), up(w3), up(w2))
        return f + jnp.take(dense_g, ids[i], axis=1)[:, None] * fe

    f = jax.lax.fori_loop(0, len(held), one, jnp.zeros_like(u))
    return f, own.astype(jnp.int32), gap


def _precision(precision):
    """(the dtype everything is computed in, the matmul precision).
    "float32" is the reference. "bfloat16" is the nearest precision below
    what the configuration states: the norms, the router, the softmax and
    the hyper-connections' maps, Sinkhorn included, in bfloat16 like
    everything else; it exists to show that the comparison's limits tell
    the two apart."""
    import jax.numpy as jnp

    if precision == "float32":
        return jnp.float32, "highest"
    if precision == "bfloat16":
        return jnp.bfloat16, "default"
    raise ValueError("precision must be 'float32' or 'bfloat16'")


_LAYERS = {}  # one jitted function a kind of layer


def _layer_fn(ff, spec, precision):
    """One layer as a jitted function of (the stream [t, n, d], its weights
    by short name, the picks to follow or None)."""
    import jax

    key = json.dumps([ff, spec, precision], sort_keys=True)
    if key in _LAYERS:
        return _LAYERS[key]
    eps, hyper, moe = spec["norm_eps"], spec["hyper"], spec["moe"]
    dt, matmul = _precision(precision)

    def sublayer(xs, w, which, fn):
        if hyper:
            return hyper_sublayer(xs, w, which, hyper, eps, fn)
        y, extras = fn(_rms(xs[:, 0], w[which + "_norm_w"], eps))
        return xs + y[:, None, :], extras

    def layer(xs, w, follow):
        with jax.default_matmul_precision(matmul):
            w32 = {k: v.astype(dt) for k, v in w.items()
                   if not k.startswith("exp_")}
            w32["eps"] = eps
            xs, _ = sublayer(xs, w32, "op", lambda u: (mla_operator(
                u, w32, spec["n_head"], spec["mla"], spec["rope_theta"]), None))

            def feed_forward(u):
                if ff == "dense":
                    return swiglu_ff(
                        u, w32["ff1_w"], w32["ff3_w"], w32["ff2_w"]), (None, None)
                f, own, gap = moe_layer(
                    u, w32["router_w"], w32.get("router_b"), w["exp_w1"],
                    w["exp_w3"], w["exp_w2"], moe, follow)
                if moe.get("shared_width"):
                    f = f + swiglu_ff(
                        u, w32["sh_w1"], w32["sh_w3"], w32["sh_w2"])
                return f, (own, gap)

            xs, (own, gap) = sublayer(xs, w32, "ffn", feed_forward)
            return xs, own, gap

    _LAYERS[key] = jax.jit(layer)
    return _LAYERS[key]


def forward(weights, tokens, spec, picks=None, rows=None,
            precision="float32"):
    """(logits [t, vocab] float32 — or of `rows` only —, picks [n_moe, t, k]
    int32, pick_gaps [n_moe, t] float32). ``spec`` is ``spec_of(model)``:
    ``BlockDecoder.spec()`` plus ``prefix``, ``norm_eps`` and
    ``rope_theta``; ``picks`` [n_moe, t, k], if given, are followed (module
    docstring)."""
    import jax
    import jax.numpy as jnp

    prefix = spec["prefix"]
    name = lambda *parts: "_".join((prefix,) + parts)
    tokens = jnp.asarray(tokens, jnp.int32)
    dt, matmul = _precision(precision)
    n = spec["hyper"]["streams"] if spec["hyper"] else 1
    x = jnp.take(weights[name("tok_emb")], tokens, axis=0).astype(dt)
    xs = jnp.repeat(x[:, None, :], n, axis=1)
    own_picks, gaps = [], []
    for i, (op, ff) in enumerate(spec["blocks"]):
        if op != "mla":
            raise ValueError("the xing4_0 reference has latent attention only")
        lead = name("l%d" % i, "")
        w = {k[len(lead):]: v for k, v in weights.items() if k.startswith(lead)}
        follow = None
        if ff == "moe" and picks is not None:
            follow = jnp.asarray(picks[len(own_picks)], jnp.int32)
        # a layer at a time on the device: run ahead, the host would hold
        # every layer's temporaries at once beside the served model
        xs, own, gap = jax.block_until_ready(
            _layer_fn(ff, spec, precision)(xs, w, follow))
        if ff == "moe":
            own_picks.append(own)
            gaps.append(gap)
    x = jnp.sum(xs, axis=1)
    if rows is not None:
        x = x[jnp.asarray(rows)]
    head = weights[name("tok_emb" if spec["tied_head"] else "head_w")]
    with jax.default_matmul_precision(matmul):
        h = _rms(x, weights[name("final_norm_w")].astype(dt), spec["norm_eps"])
        logits = jnp.concatenate([
            jax.block_until_ready(
                (h @ head[v0:v0 + HEAD_BLOCK].astype(dt).T).astype(jnp.float32))
            for v0 in range(0, head.shape[0], HEAD_BLOCK)], -1)
    if own_picks:
        return logits, jnp.stack(own_picks), jnp.stack(gaps).astype(jnp.float32)
    return logits, None, None


def spec_of(model):
    """What forward() needs to know of a BlockDecoder."""
    return dict(model.spec(), prefix=model.prefix, norm_eps=model.norm_eps,
                rope_theta=model.rope_theta)
