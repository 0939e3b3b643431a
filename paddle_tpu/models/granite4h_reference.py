"""The plain reference of the ``granitemoehybrid`` architecture as
``models/block_decoder.py`` ``granitemoehybrid`` builds it
(granite-4.0-h-micro: Mamba-2 layers with an attention layer among every
ten, a dense gated feed-forward in every layer). chipbench/
reference_granite4h.py is the benchmark's copy and a test holds the two to
each other: identical below this paragraph. The
whole sequence through every layer in straightforward float32 ``jax.numpy``,
``jax.default_matmul_precision("highest")``, no cache, no kernel, no
batching, no chunked form: the recurrence is a ``lax.scan`` over the
positions, its definition. It reads the weights it is given by the parameter
names ``models/block_decoder.py`` defines, upcast to float32.

The equations (ISSUE 33; what the published ``config.json`` does not fix is
marked + and listed in ``chipbench/configs/granite_4_0_h_micro.json``
``assumed``). Rows x of width d; every norm is RMS with a learned scale.

    stream:  h_0 = embedding_multiplier E[token]
             h = x + residual_multiplier OP(rms(x; w_op))
             x' = h + residual_multiplier FF(rms(h; w_ffn))
             logits = rms(x_last; w_final) E^T / logits_scaling  (tied head)
    FF:      (silu(u W1) * (u W3)) W2
    OP attention: q, k, v = u W_q, u W_k, u W_v (no bias, no q/k norm, no
             position of any kind); heads of d_head, query head i on KV head
             i // (n_head / n_kv_head); scores (q . k) attention_multiplier;
             causal softmax; concat_h(softmax v) W_o
    OP mamba (Mamba-2; H heads of P on a state of N, G groups of B and C):
             [z | xBC | dt] = u W_in                       (the order +)
             xBC_t <- silu(sum_j K[:, j] xBC_{t-(L-1)+j} + b_conv)
                      (depthwise, causal, zeros before the sequence)
             [x | B | C] = xBC                             (the order +)
             dt_t = softplus(dt_t + dt_bias)   (no upper clamp +)
             A = -exp(A_log)                   (a scalar a head)
             head j: S_t = exp(dt_t A_j) S_{t-1} + dt_t x_t^j (outer) B_t
                     y_t^j = S_t C_t + D_j x_t^j           (S is P x N)
             y <- rms(y * silu(z); w_norm) over all H * P;  OP = y W_out

``precision`` computes a part of the mamba operator one precision lower, for
the benchmark's two readings of what its limits must tell apart:
``"state_bfloat16"`` keeps the recurrent state S in bfloat16 (rounded after
every position's update, as a cache in the model's dtype would);
``"dt_bfloat16"`` computes dt + dt_bias, the softplus, dt A and its
exponential in bfloat16 (every operand and every result rounded to it).
``"float32"`` is the reference. The roundings are ``lax.reduce_precision``,
not casts: XLA may drop a float32 -> bfloat16 -> float32 round trip, and on
the chip it does.

At the published widths a float32 copy of everything does not fit beside the
served model, so the pass goes in blocks: a layer is one jitted call that
upcasts that layer's weights only (and the caller's queue is emptied a
layer, or every layer's temporaries are alive at once); the embedding is
read at the tokens' rows and the head is applied, HEAD_BLOCK rows of the
vocabulary at a time, to the rows asked for.
"""

import jax
import jax.numpy as jnp

F32 = jnp.float32
HEAD_BLOCK = 16384
PRECISIONS = ("float32", "state_bfloat16", "dt_bfloat16")


def _bf16(v):
    """v rounded to bfloat16's 8 exponent and 7 mantissa bits, in float32."""
    return jax.lax.reduce_precision(v, exponent_bits=8, mantissa_bits=7)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def swiglu_ff(u, w1, w3, w2):
    return (jax.nn.silu(u @ w1) * (u @ w3)) @ w2


def attention_operator(u, w, spec):
    """Causal grouped-query attention of one sequence u [t, d]."""
    t = u.shape[0]
    n_head, n_kv, d = spec["n_head"], spec["n_kv_head"], spec["d_head"]
    q = (u @ w["q_w"]).reshape(t, n_head, d)
    k = (u @ w["k_w"]).reshape(t, n_kv, d)
    v = (u @ w["v_w"]).reshape(t, n_kv, d)
    k, v = (jnp.repeat(m, n_head // n_kv, axis=1) for m in (k, v))
    scores = jnp.einsum("thd,shd->hts", q, k) * spec["attn_scale"]
    scores = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None], scores, -jnp.inf)
    ctx = jnp.einsum("hts,shd->thd", jax.nn.softmax(scores, -1), v)
    return ctx.reshape(t, n_head * d) @ w["o_w"]


def mamba_operator(u, w, spec, precision="float32"):
    """The Mamba-2 operator of one sequence u [t, d], position by position:
    (OP [t, d], the state S [H, P, N] after the last position)."""
    m, t = spec["ssm"], u.shape[0]
    n_head, p, n, groups = m["heads"], m["d_head"], m["d_state"], m["groups"]
    d_inner, taps = n_head * p, m["conv"]
    conv_dim = d_inner + 2 * groups * n
    zxbcdt = u @ w["ssm_in_w"]
    z, xbc, dt = (zxbcdt[:, :d_inner], zxbcdt[:, d_inner:d_inner + conv_dim],
                  zxbcdt[:, d_inner + conv_dim:])
    padded = jnp.pad(xbc, ((taps - 1, 0), (0, 0)))
    conv = sum(padded[j:j + t] * w["ssm_conv_k"][:, j] for j in range(taps))
    xbc = jax.nn.silu(conv + w["ssm_conv_b"])
    x = xbc[:, :d_inner].reshape(t, n_head, p)
    per_head = lambda g: jnp.repeat(
        g.reshape(t, groups, n), n_head // groups, axis=1)
    b = per_head(xbc[:, d_inner:d_inner + groups * n])
    c = per_head(xbc[:, d_inner + groups * n:])
    if precision == "dt_bfloat16":
        dt = _bf16(jax.nn.softplus(_bf16(_bf16(dt) + _bf16(w["ssm_dt_bias"]))))
        decay = _bf16(jnp.exp(_bf16(dt * _bf16(-jnp.exp(w["ssm_a_log"])))))
    else:
        dt = jax.nn.softplus(dt + w["ssm_dt_bias"])
        decay = jnp.exp(dt * -jnp.exp(w["ssm_a_log"]))
    keep = _bf16 if precision == "state_bfloat16" else (lambda s: s)

    def step(s, at):
        x_t, b_t, c_t, dt_t, decay_t = at
        s = keep(decay_t[:, None, None] * s
                 + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        return s, jnp.einsum("hpn,hn->hp", s, c_t) + w["ssm_d"][:, None] * x_t

    last, y = jax.lax.scan(
        step, jnp.zeros((n_head, p, n), F32), (x, b, c, dt, decay))
    y = _rms(y.reshape(t, d_inner) * jax.nn.silu(z), w["ssm_norm_w"],
             spec["norm_eps"])
    return y @ w["ssm_out_w"], last


_LAYERS = {}


def _layer_fn(op, spec, precision):
    key = (op, repr(sorted(spec.items(), key=lambda kv: kv[0])), precision)
    if key in _LAYERS:
        return _LAYERS[key]

    def layer(x, w):
        w = {k: v.astype(F32) for k, v in w.items()}
        with jax.default_matmul_precision("highest"):
            u = _rms(x, w["op_norm_w"], spec["norm_eps"])
            out, state = (
                mamba_operator(u, w, spec, precision) if op == "mamba"
                else (attention_operator(u, w, spec), jnp.zeros((0,), F32)))
            h = x + spec["residual_scale"] * out
            u = _rms(h, w["ffn_norm_w"], spec["norm_eps"])
            return h + spec["residual_scale"] * swiglu_ff(
                u, w["ff1_w"], w["ff3_w"], w["ff2_w"]), state

    _LAYERS[key] = jax.jit(layer)
    return _LAYERS[key]


def forward(weights, tokens, spec, rows=None, precision="float32",
            states=False):
    """Logits [len(rows), vocab] float32 of one sequence `tokens` at the
    positions `rows` (default: every position). `weights` is {parameter
    name: array} under block_decoder's names; `spec` is spec_of(model).
    With `states`: (logits, the mamba layers' recurrent states after the
    last token [mamba layers, H, P, N], in layer order)."""
    if precision not in PRECISIONS:
        raise ValueError("precision must be one of %s" % (PRECISIONS,))
    prefix = spec["prefix"]
    tokens = jnp.asarray(tokens, jnp.int32)
    rows = jnp.arange(tokens.shape[0]) if rows is None else jnp.asarray(rows)
    emb = weights[prefix + "_tok_emb"]
    x = spec["embed_scale"] * jnp.take(emb, tokens, axis=0).astype(F32)
    kept = []
    for i, op in enumerate(spec["operators"]):
        head = "%s_l%d_" % (prefix, i)
        w = {k[len(head):]: v for k, v in weights.items() if k.startswith(head)}
        x, state = jax.block_until_ready(_layer_fn(op, spec, precision)(x, w))
        if op == "mamba" and states:
            kept.append(state)
    with jax.default_matmul_precision("highest"):
        last = _rms(jnp.take(x, rows, axis=0),
                    weights[prefix + "_final_norm_w"].astype(F32),
                    spec["norm_eps"])
        parts = [
            jax.block_until_ready(last @ emb[s:s + HEAD_BLOCK].astype(F32).T)
            for s in range(0, emb.shape[0], HEAD_BLOCK)]
    logits = jnp.concatenate(parts, axis=1) / spec["logit_scale"]
    return (logits, jnp.stack(kept)) if states else logits


def spec_of(model):
    """What forward needs of a BlockDecoder built by granitemoehybrid, as
    plain data. A decoder with something this reference does not compute
    (rotary positions, a q/k norm, experts, another residual) is refused."""
    if (model.position != "none" or model.qk_norm or model.hyper
            or any(f != "dense" for _, f in model.blocks)
            or any(o not in ("mamba", "attention") for o, _ in model.blocks)):
        raise ValueError("not a granitemoehybrid decoder")
    ssm = dict(model.ssm)
    ssm.setdefault("groups", 1)
    return {
        "prefix": model.prefix, "operators": [o for o, _ in model.blocks],
        "n_head": model.n_head, "n_kv_head": model.n_kv_head,
        "d_head": model.d_head, "ssm": ssm, "norm_eps": model.norm_eps,
        "attn_scale": model.attn_scale, "embed_scale": model.embed_scale,
        "residual_scale": model.residual_scale,
        "logit_scale": model.logit_scale,
    }
