"""Ops of the block-specified decoder (models/block_decoder.py): RMS norm,
rotary position embedding (whole or partial, plain or YaRN frequencies), the
gated feed-forward's activation, the hyper-connection that reads and writes
a residual stream of several copies, the short
convolution (gated, or with bias and silu) with its fixed per-sequence
state, the Mamba-2 state-space scan with its float32 recurrent state, and
the mixture of experts (router, and a grouped expert product whose work
follows the routing).

Conventions:
  * Activations and weights may be bfloat16; every op here computes its
    reductions, its transcendental functions and its accumulations in
    float32 and rounds once, to its input's dtype. The router's scores,
    weights and the matrix that makes them are float32 throughout: a top-k
    over bf16 scores picks other experts.
  * ``ssm_scan`` keeps a sequence's recurrent state in a float32 pool
    ``[state_rows, d_state, heads * d_head]`` under the same row table; its
    step writes live rows only, in place (``pallas_kernels.ssm_state_step``
    on a TPU), because a row is megabytes where a convolution's is KB.
  * ``short_conv`` keeps a sequence's state — the last ``L - 1`` rows of
    what it convolves — in a persistable ``[state_rows, (L - 1) * d]`` pool that
    a fed row table indexes, as block tables index KV pages. Row 0 is a
    scratch row: idle and mid-prefill slots of a decode step write there
    and never to a live state. The op's ``StateOut`` IS the pool variable
    (the ``kv_cache_write`` idiom), so the serving lowering classifies it as
    written state and donates it.
  * ``moe_router`` routes over all ``num_experts``; rows that carry no live
    token (``Live`` == 0) get the pick ``num_experts`` — no expert — and
    weight 0. ``moe_experts`` computes the part of ``sum_i g_i FF_i(u)``
    that the experts it holds give (``experts_held``, default all): rows
    are sorted by expert and one Pallas kernel
    (``pallas_kernels.grouped_expert_ffn``) streams each expert that has rows
    through VMEM once, gating and down-projecting there, so the bytes and the
    FLOPs follow the routing and not ``num_experts``. Where the kernel
    declines (``grouped_ffn_path_taken``: a model or expert width that is not
    whole lane tiles, a dtype other than bf16 / f32, weights in another dtype
    than the rows) three ``jax.lax.ragged_dot`` calls do the same with the
    same roundings.
  * Every op has a shape rule of its own: deriving shapes from the lowering
    (registry.infer_shape) traces it once a layer a variant (PERF.md, PR 26).
"""

import jax
import jax.numpy as jnp
import numpy as np

from . import pallas_kernels as _pk
from .registry import register

__all__ = []

_F32 = jnp.float32


def _var(block, op, slot, outputs=False):
    names = (op.outputs if outputs else op.inputs).get(slot) or [None]
    return None if names[0] is None else block._var_recursive(names[0])


def _like(src_slot, out_slots=("Out",)):
    """Shape rule: every slot of out_slots is shaped and typed like the
    input src_slot."""

    def rule(op, block):
        src = _var(block, op, src_slot)
        for slot in out_slots:
            out = _var(block, op, slot, outputs=True)
            if out is not None:
                out.shape, out.dtype = src.shape, src.dtype

    return rule


# ---------------------------------------------------------------------------
# norms, positions, activation
# ---------------------------------------------------------------------------


@register("rms_norm", no_grad=True, infer_shape=_like("X"))
def _rms_norm(ctx, ins, attrs):
    """x * rsqrt(mean(x^2, -1) + eps) * w in f32. groups > 1 norms each of
    `groups` equal slices of the last axis on its own with the one [d] scale
    (the per-head q/k norm over [rows, n_head * d_head])."""
    (x,) = ins["X"]
    (w,) = ins["Scale"]
    eps = float(attrs.get("epsilon", 1e-5))
    groups = int(attrs.get("groups", 1))
    x32 = x.astype(_F32)
    shape = x32.shape
    if groups > 1:
        x32 = x32.reshape(shape[:-1] + (groups, shape[-1] // groups))
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    y = y * w.astype(_F32)
    return {"Out": [y.reshape(shape).astype(x.dtype)]}


def yarn_inv_freq(dim, theta, factor, original, beta_fast, beta_slow):
    """The `dim // 2` rotary frequencies under YaRN (arXiv:2309.00071, as the
    published DeepSeek-V2/V3 modelling code computes them): pair i turns at
    theta^(-2i/dim), divided by `factor` where it completes fewer than
    beta_slow turns over the `original` positions, as it is where it
    completes more than beta_fast, and a linear blend between the two
    pair indices. numpy float64: constants of the program."""
    extra = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)

    def pair_of(turns):
        return dim * np.log(original / (turns * 2 * np.pi)) / (2 * np.log(theta))

    low = max(int(np.floor(pair_of(beta_fast))), 0)
    high = min(int(np.ceil(pair_of(beta_slow))), dim - 1)
    span = float(high - low) or 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low) / span, 0, 1)
    return extra / float(factor) * ramp + extra * (1.0 - ramp)


@register("rotary_embedding", no_grad=True, infer_shape=_like("X"))
def _rotary_embedding(ctx, ins, attrs):
    """Rotary position embedding of the last `rotary_dim` features of every
    head (0: the whole head), the features before them passed through. The
    angle of feature pair i at position p is p * inv_freq_i, inv_freq =
    theta^(-2i/d), or YaRN's blend of it when `yarn` = [factor, original
    positions, beta_fast, beta_slow] is given (yarn_inv_freq); cos and sin
    are scaled by `mscale`. Form "half" (rotate_half): pair i is features
    (i, i + d/2), out = x * cos + concat(-x2, x1) * sin; form "interleaved":
    pair i is features (2i, 2i + 1)."""
    (x,) = ins["X"]  # [rows, n_head * d]
    (pos,) = ins["Pos"]
    n_head = int(attrs["n_head"])
    theta = float(attrs.get("theta", 10000.0))
    rows = x.shape[0]
    d_head = x.shape[-1] // n_head
    d = int(attrs.get("rotary_dim") or 0) or d_head
    yarn = attrs.get("yarn")
    inv = (jnp.asarray(yarn_inv_freq(d, theta, *yarn), _F32) if yarn
           else theta ** (-jnp.arange(0, d, 2, dtype=_F32) / d))
    ang = pos.reshape(rows, 1).astype(_F32) * inv[None, :]
    mscale = float(attrs.get("mscale", 1.0))
    cos = (jnp.cos(ang) * mscale)[:, None, :]
    sin = (jnp.sin(ang) * mscale)[:, None, :]
    xh = x.astype(_F32).reshape(rows, n_head, d_head)
    keep, xr = xh[..., : d_head - d], xh[..., d_head - d:]
    if attrs.get("form", "half") == "interleaved":
        x1, x2 = xr[..., 0::2], xr[..., 1::2]
        out = jnp.stack(
            [x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1
        ).reshape(rows, n_head, d)
    else:
        x1, x2 = xr[..., : d // 2], xr[..., d // 2:]
        out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    out = jnp.concatenate([keep, out], -1)
    return {"Out": [out.reshape(x.shape).astype(x.dtype)]}


@register("swiglu", no_grad=True, infer_shape=_like("X"))
def _swiglu(ctx, ins, attrs):
    """silu(X) * Y: the gate of the gated feed-forward, in f32."""
    (g,) = ins["X"]
    (u,) = ins["Y"]
    return {"Out": [(jax.nn.silu(g.astype(_F32)) * u.astype(_F32)).astype(g.dtype)]}


# ---------------------------------------------------------------------------
# hyper-connection: a residual stream of n parallel copies
# ---------------------------------------------------------------------------


def sinkhorn(logits, iters, eps):
    """exp(logits) [..., n, n] scaled to doubly stochastic: `iters` times,
    every row over its sum + eps, then every column over its sum + eps."""
    m = jnp.exp(logits)
    for _ in range(int(iters)):
        m = m / (jnp.sum(m, -1, keepdims=True) + eps)
        m = m / (jnp.sum(m, -2, keepdims=True) + eps)
    return m


def _hyper_connection_infer(op, block):
    x = _var(block, op, "X")
    n = int(op.attrs["streams"])
    u = _var(block, op, "Out", outputs=True)
    u.shape, u.dtype = tuple(x.shape[:-1]) + (x.shape[-1] // n,), x.dtype
    maps = _var(block, op, "Maps", outputs=True)
    maps.shape, maps.dtype = tuple(x.shape[:-1]) + (n + n * n,), "float32"


@register("hyper_connection", no_grad=True, infer_shape=_hyper_connection_infer)
def _hyper_connection(ctx, ins, attrs):
    """The read side of a manifold-constrained hyper-connection
    (arXiv:2512.24880) around one sub-layer. X is the residual stream
    [rows, n * d], stream j in columns j * d .. (j + 1) * d. All float32:

        x~ = X * rsqrt(mean(X^2 over all n * d) + norm_eps)   (no scale)
        [pre~ n | post~ n | res~ n * n] = x~ @ W * repeat(Alpha) + Bias
        H_pre = sigmoid(pre~); H_post = 2 sigmoid(post~)
        H_res = sinkhorn(clip(res~, clamp_min, clamp_max), iters, eps)

    Out = sum_j H_pre[j] X_j, rounded to X's dtype: what the sub-layer
    reads. Maps = [H_post | H_res row-major] float32 [rows, n + n * n], for
    hyper_connection_post."""
    (x,) = ins["X"]
    (w,) = ins["W"]  # [n * d, 2n + n * n] float32
    (alpha,) = ins["Alpha"]  # [3]: pre, post, res
    (bias,) = ins["Bias"]  # [2n + n * n]
    n = int(attrs["streams"])
    rows = x.shape[0]
    x32 = x.astype(_F32)
    xt = x32 * jax.lax.rsqrt(
        jnp.mean(x32 * x32, -1, keepdims=True)
        + float(attrs.get("norm_eps", 1e-6)))
    raw = jnp.dot(xt, w.astype(_F32), precision=jax.lax.Precision.HIGHEST)
    a32 = alpha.astype(_F32)
    gain = jnp.concatenate([
        jnp.broadcast_to(a32[i:i + 1], (width,))
        for i, width in enumerate((n, n, n * n))])
    raw = raw * gain[None, :] + bias.astype(_F32)[None, :]
    lo, hi = float(attrs["clamp_min"]), float(attrs["clamp_max"])
    iters, eps = int(attrs["iters"]), float(attrs["eps"])
    if _pk.hyper_maps_path_taken():
        # one kernel for the sigmoids and the rounds (its time reads
        # op:hyper_connection: no pallas_kernel= scope)
        maps = _pk.hyper_maps(raw, n, iters, eps, (lo, hi))
        h_pre, maps = maps[:, :n], maps[:, n:]
    else:
        h_pre = jax.nn.sigmoid(raw[:, :n])
        h_res = sinkhorn(
            jnp.clip(raw[:, 2 * n:], lo, hi).reshape(rows, n, n), iters, eps)
        maps = jnp.concatenate([
            2.0 * jax.nn.sigmoid(raw[:, n:2 * n]), h_res.reshape(rows, n * n)],
            -1)
    # n is small: broadcast products on the VPU, no batched 1 x n matmul
    xs = x32.reshape(rows, n, -1)
    u = sum(h_pre[:, j:j + 1] * xs[:, j] for j in range(n))
    return {"Out": [u.astype(x.dtype)], "Maps": [maps]}


@register("hyper_connection_post", no_grad=True, infer_shape=_like("X"))
def _hyper_connection_post(ctx, ins, attrs):
    """The write side: X'_j = sum_k H_res[j, k] X_k + H_post[j] * Y, in
    float32, rounded to X's dtype. Maps is hyper_connection's."""
    (x,) = ins["X"]  # [rows, n * d]
    (y,) = ins["Y"]  # [rows, d]
    (maps,) = ins["Maps"]
    n = int(attrs["streams"])
    rows = x.shape[0]
    h_post, h_res = maps[:, :n], maps[:, n:].reshape(rows, n, n)
    xs, y32 = x.astype(_F32).reshape(rows, n, -1), y.astype(_F32)
    out = jnp.stack([
        sum(h_res[:, j, k:k + 1] * xs[:, k] for k in range(n))
        + h_post[:, j:j + 1] * y32
        for j in range(n)], 1)
    return {"Out": [out.reshape(x.shape).astype(x.dtype)]}


# ---------------------------------------------------------------------------
# gated short convolution
# ---------------------------------------------------------------------------


def _short_conv_infer(op, block):
    x = _var(block, op, "X")
    out = _var(block, op, "Out", outputs=True)
    gated = op.attrs.get("form", "gated") == "gated"
    out.shape = tuple(x.shape[:-1]) + (x.shape[-1] // (3 if gated else 1),)
    out.dtype = x.dtype
    _state_out_like_state(op, block)


def _state_out_like_state(op, block):
    state = _var(block, op, "State")
    state_out = _var(block, op, "StateOut", outputs=True)
    if state is not None and state_out is not None:
        state_out.shape, state_out.dtype = state.shape, state.dtype


@register("short_conv", no_grad=True, infer_shape=_short_conv_infer)
def _short_conv(ctx, ins, attrs):
    """A depthwise causal convolution of `L` taps over the sequence,
    c_t = sum_j Kernel[:, j] * v_{t - (L-1) + j}, in one of two forms:

    form "gated" (the default): between its two projections. X is
    ``u @ W_in`` = [B, C, z] (in that order, [rows, 3 * d]); v = B * z;
    Out = C * c.
    form "silu": X is v itself ([rows, d]); Out = silu(c + Bias) (Bias [d]
    optional): the convolution in front of a state-space scan.

    mode "chunk": the rows are consecutive positions of ONE sequence from
    Start; the (L - 1) rows of v before them come from State[Rows[0]] (zeros
    when Start is 0), and the state after the last live row (Live counts
    them; padding follows) is written back. With no State the rows are whole
    sequences of seq_len positions each, every one from zeros. mode "step":
    one position of a sequence a row, each with its own state row Rows[r];
    the new state shifts v_t in.

    v is rounded to the state's dtype before it is used, in both modes, so
    what a later chunk or step reads back from the state is bit for bit what
    the same position saw inside a chunk: the result does not depend on
    where a prompt was cut."""
    (x,) = ins["X"]
    (kern,) = ins["Kernel"]  # [d, L]
    state = ins.get("State", [None])[0]
    d, taps = kern.shape
    keep = taps - 1
    rows = x.shape[0]
    store = state.dtype if state is not None else x.dtype
    if attrs.get("form", "gated") == "gated":
        b, c, z = (x[:, i * d:(i + 1) * d].astype(_F32) for i in range(3))
        v = (b * z).astype(store).astype(_F32)
        finish = lambda conv: (c * conv).astype(x.dtype)
    else:
        bias = ins.get("Bias", [None])[0]
        v = x.astype(store).astype(_F32)
        b32 = 0.0 if bias is None else bias.astype(_F32)
        finish = lambda conv: jax.nn.silu(conv + b32).astype(x.dtype)
    k32 = kern.astype(_F32)
    if attrs.get("mode", "chunk") == "step":
        idx = ins["Rows"][0].reshape(-1).astype(jnp.int32)
        prev = jnp.take(state, idx, axis=0).astype(_F32).reshape(rows, keep, d)
        conv = v * k32[:, keep]
        for j in range(keep):
            conv = conv + prev[:, j] * k32[:, j]
        new = jnp.concatenate([prev[:, 1:], v[:, None]], 1).reshape(rows, keep * d)
        return {"Out": [finish(conv)],
                "StateOut": [state.at[idx].set(new.astype(state.dtype))]}
    if state is None:
        # whole sequences of seq_len rows each (all the rows are one when 0)
        t = int(attrs.get("seq_len", 0)) or rows
        vv = jnp.pad(v.reshape(rows // t, t, d), ((0, 0), (keep, 0), (0, 0)))
        conv = sum(vv[:, j:j + t] * k32[:, j] for j in range(taps))
        return {"Out": [finish(conv.reshape(rows, d))]}
    row = ins["Rows"][0].reshape(-1)[0].astype(jnp.int32)
    start = ins["Start"][0].reshape(-1)[0]
    prev = jnp.where(start > 0, state[row].astype(_F32).reshape(keep, d), 0.0)
    vv = jnp.concatenate([prev, v], 0)  # v_t sits at row t + keep
    conv = sum(vv[j:j + rows] * k32[:, j] for j in range(taps))
    n_live = jnp.sum(ins["Live"][0].reshape(-1).astype(jnp.int32))
    new = jax.lax.dynamic_slice_in_dim(vv, n_live, keep, 0)
    return {
        "Out": [finish(conv)],
        "StateOut": [
            state.at[row].set(new.reshape(keep * d).astype(state.dtype))],
    }


# ---------------------------------------------------------------------------
# state-space scan (Mamba-2)
# ---------------------------------------------------------------------------

_HI = jax.lax.Precision.HIGHEST


def ssd_chunk(x, dt, a, b, c, h0):
    """One chunk of the state-space recurrence in its quadratic (SSD) form,
    all float32. x [L, H, P] the heads' inputs, dt [L, H] the step sizes
    (0 for a padding row: it decays nothing and adds nothing), a [H] the
    heads' (negative) rates, b and c [L, H, N] the input and output maps a
    head, h0 [H, P, N] the state before the chunk. Returns (y [L, H, P], h1
    [H, P, N]) with, a head, H_t = exp(dt_t a) H_{t-1} + dt_t x_t (outer)
    b_t and y_t = H_t c_t:

        y_t = sum_{s<=t} exp(cum_t - cum_s) (c_t . b_s) dt_s x_s
              + exp(cum_t) H_0 c_t,        cum_t = sum_{u<=t} dt_u a
        H_L = exp(cum_L) H_0 + sum_s exp(cum_L - cum_s) dt_s x_s (outer) b_s
    """
    n = x.shape[0]
    cum = jnp.cumsum(dt * a[None, :], axis=0)  # [L, H], falling
    seg = cum[:, None, :] - cum[None, :, :]  # [t, s, H]
    lower = jnp.tril(jnp.ones((n, n), bool))[:, :, None]
    decay = jnp.where(lower, jnp.exp(jnp.where(lower, seg, 0.0)), 0.0)
    cb = jnp.einsum("thn,shn->tsh", c, b, precision=_HI)
    xdt = x * dt[:, :, None]
    y = jnp.einsum("tsh,shp->thp", cb * decay, xdt, precision=_HI)
    y = y + jnp.exp(cum)[:, :, None] * jnp.einsum(
        "hpn,thn->thp", h0, c, precision=_HI)
    tail = jnp.exp(cum[-1][None, :] - cum)  # [s, H]
    h1 = jnp.exp(cum[-1])[:, None, None] * h0 + jnp.einsum(
        "shp,shn->hpn", xdt * tail[:, :, None], b, precision=_HI)
    return y, h1


def _ssm_scan_infer(op, block):
    z = _var(block, op, "Z")
    out = _var(block, op, "Out", outputs=True)
    out.shape, out.dtype = z.shape, z.dtype
    _state_out_like_state(op, block)


@register("ssm_scan", no_grad=True, infer_shape=_ssm_scan_infer)
def _ssm_scan(ctx, ins, attrs):
    """The Mamba-2 state-space operator between its convolution and its
    output projection. X is the convolved [x | B | C] ([rows, H * P + 2 * G
    * N]: `heads` H of `d_head` P, `groups` G of B and C, `d_state` N), Dt
    [rows, H] the raw step sizes, Z [rows, H * P] the gate. For head j (of
    group g) at position t, in float32 whatever the rows' dtype:

        dt_t = softplus(Dt_t + DtBias);   A = -exp(ALog)
        H_t = exp(dt_t A_j) H_{t-1} + dt_t x_t^j (outer) B_t^g   [P, N]
        y_t^j = H_t C_t^g + D_j x_t^j
        Out_t = rms(y_t * silu(Z_t); NormScale, eps) over all H * P

    rounded once, to Z's dtype. The state is float32, a row of the
    persistable pool State [state_rows, N, H * P] a sequence (the heads'
    entries on the lanes, so that a step's row update is whole-vreg work; a
    sequence's state its own tiles, so that a row is copied or updated
    without touching its neighbours'), row 0 scratch, indexed by Rows as
    short_conv's.

    mode "chunk": the rows are consecutive positions of ONE sequence from
    Start, at most `chunk` of them a quadratic form (ssd_chunk) and the
    state carried between; the state before them is State[Rows[0]], zeros
    when Start is 0 (a cold admission writes no zeros to the pool); rows
    with Live == 0 (padding, behind the live ones) take dt = 0 and leave
    the state alone. With no State the rows are whole sequences of seq_len
    positions each, every one from zeros. mode "step": one position of a
    sequence a row with its own state row Rows[r]; rows whose Rows[r] is 0
    (idle slots) are not computed and touch no state but the scratch row.
    On a TPU the step is pallas_kernels.ssm_state_step: the pool updated in
    place, live rows only."""
    (x,) = ins["X"]
    (dt_raw,) = ins["Dt"]
    (z,) = ins["Z"]
    state = ins.get("State", [None])[0]
    n_head, p = int(attrs["heads"]), int(attrs["d_head"])
    n, groups = int(attrs["d_state"]), int(attrs.get("groups", 1))
    chunk = int(attrs.get("chunk", 256))
    rows, hp = x.shape[0], n_head * p
    a = -jnp.exp(ins["ALog"][0].astype(_F32))
    d_skip = ins["D"][0].astype(_F32)
    dt = jax.nn.softplus(
        dt_raw.astype(_F32) + ins["DtBias"][0].astype(_F32)[None, :])
    x32 = x.astype(_F32)
    xs = x32[:, :hp].reshape(rows, n_head, p)
    per_head = lambda m: jnp.repeat(
        m.reshape(rows, groups, n), n_head // groups, axis=1)
    b, c = x32[:, hp:hp + groups * n], x32[:, hp + groups * n:]

    def finish(y):
        """y [rows, H, P] without the skip -> Out."""
        y = (y + d_skip[None, :, None] * xs).reshape(rows, hp)
        g = y * jax.nn.silu(z.astype(_F32))
        g = g * jax.lax.rsqrt(
            jnp.mean(g * g, -1, keepdims=True)
            + float(attrs.get("epsilon", 1e-5)))
        return (g * ins["NormScale"][0].astype(_F32)).astype(z.dtype)

    to_pool = lambda h: jnp.transpose(h, (2, 0, 1)).reshape(n, hp)
    from_pool = lambda r: jnp.transpose(r.reshape(n, n_head, p), (1, 2, 0))

    if attrs.get("mode", "chunk") == "step":
        idx = ins["Rows"][0].reshape(-1).astype(jnp.int32)
        decay = jnp.exp(dt * a[None, :])  # [rows, H]
        xdt = (xs * dt[:, :, None]).reshape(rows, hp)
        if _pk.ssm_step_path_taken(hp, n, groups):
            # no pallas_kernel= scope: its device time reads op:ssm_scan
            y, new_state = _pk.ssm_state_step(
                state, idx, xdt, jnp.repeat(decay, p, axis=1), b, c)
        else:
            # [rows, n, hp]: a group's B and C under each of its heads' lanes
            lanes = lambda m: jnp.repeat(
                jnp.swapaxes(m.reshape(rows, groups, n), 1, 2),
                hp // groups, axis=2)
            prev = jnp.take(state, idx, axis=0)
            new = (prev * jnp.repeat(decay, p, axis=1)[:, None, :]
                   + lanes(b) * xdt[:, None, :])
            y = jnp.sum(new * lanes(c), axis=1)
            # idle rows write nothing of theirs: the scratch row keeps what
            # it had (a zero-filled row is rewritten as it was read)
            keep = (idx != 0)[:, None, None]
            new_state = state.at[idx].set(jnp.where(keep, new, prev))
        return {"Out": [finish(y.reshape(rows, n_head, p))],
                "StateOut": [new_state]}

    def scan(xs_, dt_, b_, c_, h0):
        """One sequence, `chunk` positions a quadratic form."""
        ys, h = [], h0
        for s in range(0, xs_.shape[0], chunk):
            part = slice(s, s + chunk)
            y, h = ssd_chunk(xs_[part], dt_[part], a, b_[part], c_[part], h)
            ys.append(y)
        return jnp.concatenate(ys, 0), h

    b, c = per_head(b), per_head(c)
    zeros = jnp.zeros((n_head, p, n), _F32)
    if state is None:
        t = int(attrs.get("seq_len", 0)) or rows
        seqs = lambda m: m.reshape((rows // t, t) + m.shape[1:])
        y = jax.vmap(lambda *m: scan(*m, zeros)[0])(
            seqs(xs), seqs(dt), seqs(b), seqs(c))
        return {"Out": [finish(y.reshape(rows, n_head, p))]}
    row = ins["Rows"][0].reshape(-1)[0].astype(jnp.int32)
    start = ins["Start"][0].reshape(-1)[0]
    live = ins["Live"][0].reshape(-1, 1) != 0
    h0 = jnp.where(start > 0, from_pool(state[row]), zeros)
    y, h1 = scan(xs, jnp.where(live, dt, 0.0), b, c, h0)
    return {"Out": [finish(y)],
            "StateOut": [state.at[row].set(to_pool(h1))]}


# ---------------------------------------------------------------------------
# mixture of experts
# ---------------------------------------------------------------------------


def _moe_router_infer(op, block):
    x = _var(block, op, "X")
    k = int(op.attrs["k"])
    for slot, dtype in (("Picks", "int32"), ("Weights", "float32")):
        out = _var(block, op, slot, outputs=True)
        out.shape, out.dtype = (x.shape[0], k), dtype


@register("moe_router", no_grad=True, infer_shape=_moe_router_infer)
def _moe_router(ctx, ins, attrs):
    """s = sigmoid(x @ W) in f32; the k experts with the largest s + Bias
    (the bias picks, it does not weigh); Weights = s_i, over their sum + 1e-6
    when norm_topk, times `scale`. Rows with Live == 0 get the pick
    num_experts (no expert) and weight 0."""
    (x,) = ins["X"]
    (w,) = ins["W"]  # [d, num_experts] float32
    bias = ins.get("Bias", [None])[0]
    live = ins.get("Live", [None])[0]
    k = int(attrs["k"])
    n_exp = w.shape[-1]
    s = jax.nn.sigmoid(jnp.dot(
        x.astype(_F32), w.astype(_F32), precision=jax.lax.Precision.HIGHEST))
    sel = s if bias is None else s + bias.astype(_F32)[None, :]
    _, picks = jax.lax.top_k(sel, k)
    g = jnp.take_along_axis(s, picks, axis=1)
    if attrs.get("norm_topk", True):
        g = g / (jnp.sum(g, -1, keepdims=True) + 1e-6)
    g = g * float(attrs.get("scale", 1.0))
    picks = picks.astype(jnp.int32)
    if live is not None:
        on = live.reshape(-1, 1) != 0
        picks = jnp.where(on, picks, n_exp)
        g = jnp.where(on, g, 0.0)
    return {"Picks": [picks], "Weights": [g]}


@register("moe_experts", no_grad=True, infer_shape=_like("X"))
def _moe_experts(ctx, ins, attrs):
    """The held experts' part of sum_i Weights_i * FF_{Picks_i}(x), with
    FF_e(u) = (silu(u @ W1[e]) * (u @ W3[e])) @ W2[e]. The rows' picks are
    sorted by expert; each group meets its own expert's matrices, so an
    expert nobody picked costs nothing and a pick of an expert held
    elsewhere (or of no expert: a row without a live token) is not computed
    at all. The sort, the gather and the weighted scatter are XLA's; the
    groups' products are the Pallas kernel grouped_expert_ffn, or three
    ragged_dot calls for the widths and dtypes it declines. Either way every
    product accumulates in float32, h is rounded once to x's dtype, and the
    picks are weighed and summed in float32."""
    (x,) = ins["X"]
    (picks,) = ins["Picks"]
    (weights,) = ins["Weights"]
    (w1,) = ins["W1"]  # [held, d, f]
    (w3,) = ins["W3"]
    (w2,) = ins["W2"]  # [held, f, d]
    n_exp = int(attrs["num_experts"])
    n_held = w1.shape[0]
    # the experts held, as global ids: all of them on one chip by default
    held = [int(e) for e in (attrs.get("experts_held") or range(n_held))]
    rows, k = picks.shape
    where = np.full(n_exp + 1, n_held, np.int32)  # global id -> held index
    where[held] = np.arange(n_held, dtype=np.int32)
    local = jnp.asarray(where)[picks.reshape(-1)]
    order = jnp.argsort(local, stable=True)
    sorted_local = local[order]
    token = order // k
    sizes = jnp.bincount(local, length=n_held + 1)[:n_held].astype(jnp.int32)
    xs = jnp.take(x, token, axis=0)
    if _pk.grouped_ffn_path_taken(x.shape[-1], w1.shape[-1], x.dtype, w1.dtype):
        # no pallas_kernel= scope: its device time reads op:moe_experts
        y = _pk.grouped_expert_ffn(xs, w1, w3, w2, sizes)
    else:
        ragged = lambda a, b: jax.lax.ragged_dot(
            a, b, sizes, preferred_element_type=_F32)
        h = (jax.nn.silu(ragged(xs, w1)) * ragged(xs, w3)).astype(x.dtype)
        y = ragged(h, w2)
    g = weights.reshape(-1)[order].astype(_F32)
    y = jnp.where((sorted_local < n_held)[:, None], y * g[:, None], 0.0)
    out = jnp.zeros((rows, x.shape[-1]), _F32).at[token].add(y)
    return {"Out": [out.astype(x.dtype)]}
