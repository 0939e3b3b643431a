"""Pallas TPU kernels — the hand-tuned hot-op tier.

Reference analog: operators/math/jit_kernel.h:33-79 + jit_gen.h:41 — the
reference JIT-assembles x86 vector kernels (Xbyak) where the compiler's
codegen wasn't enough; on TPU that role belongs to Pallas kernels lowered
onto MXU/VPU tiles (SURVEY.md §7.9 perf closure).

Kernels: blockwise flash attention forward (online-softmax over KV blocks,
saving only the per-row logsumexp) and a fused flash-attention-2 style
backward — one kernel per K block computing dK, dV, and dQ partials, so the
score matrix and dO·Vᵀ are built once instead of twice (the classic
two-kernel split recomputes both; measured 2.4 -> 1.56 ms per fwd+grad at
t=1024 on chip). Both read q, k, v and dO as [b, t, h·d], the layout a
projection leaves the heads in, a 128-lane block of the last axis a program
(two 64-wide heads, or one of 128: docs/flash_attention.md); rank-4
(b, h, t, d) callers run the same bodies over the (b·h, t, d) view.
Long-context shapes stream the non-resident side through
the grid (separate dQ / dKV kernels there, where VMEM residency is the
binding constraint, not flop count). Ragged tile shapes fall back to the
dense form in both directions (a trace-time decision).

On non-TPU backends (the CPU test mesh) the kernel runs in Pallas interpret
mode — same code path, no Mosaic compile — keeping tests hermetic.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .registry import (
    bcast_y,
    gather_op_inputs,
    register,
    register_fused,
    scatter_op_outputs,
)

__all__ = [
    "flash_attention",
    "flash_tiles_ok",
    "flash_path_taken",
    "flash_tier",
    "gemm_bias_act",
    "gemm_path_taken",
    "gemm_dbuf_path_taken",
    "quant_gemm_bias_act",
    "quant_gemm_path_taken",
    "paged_flash_attention",
    "paged_flash_path_taken",
    "paged_positions_walked",
    "latent_paged_attention",
    "latent_paged_path_taken",
    "latent_positions_walked",
    "dsa_index_scores",
    "dsa_index_path_taken",
    "index_positions_walked",
    "hyper_maps",
    "hyper_maps_path_taken",
    "grouped_expert_ffn",
    "grouped_ffn_path_taken",
    "ssm_state_step",
    "ssm_step_path_taken",
    "KERNELS_REVISION",
    "fused_layer_norm",
    "fused_layer_norm_grad",
    "ln_path_taken",
    "KERNEL_DISPATCHES",
]

# trace-time dispatch telemetry: family -> number of times the fused lowering
# ACCEPTED a tagged run (i.e. the Pallas kernel was emitted, not the per-op
# fallback). Counted once per trace, so tests can clear() it, force a build,
# and assert the kernel path engaged (the path-assertion satellite: a ragged
# dense fallback must never silently eat the speedup).
KERNEL_DISPATCHES = {}


def _note_dispatch(family):
    KERNEL_DISPATCHES[family] = KERNEL_DISPATCHES.get(family, 0) + 1

_DEF_BLOCK_Q = 1024
_DEF_BLOCK_K = 1024
_DEF_BLOCK_Q_CAUSAL = 512
_DEF_BLOCK_K_CAUSAL = 512  # smaller K stream keeps the causal chunk-skip live
# streamed (long-context) tier optimum, swept at t=16384 on chip: (1024,1024)
# runs 100/124 TF/s eff fwd (causal/not) vs 51/63 at (512,512); same ranking
# for the backward (97/121 vs 67/90); 2048 tiles overflow VMEM
_DEF_STREAM_BLOCK = 1024
_LANES = 128  # Mosaic minimum tile width for the residual tensors


def _auto_block(t, target):
    """Largest power-of-two-scaled block ≤ target that divides t, else t
    itself when a single whole tile fits. Returns 0 for ragged shapes (the
    caller falls back to the dense form). Measured on chip (t=1024, d=128,
    b*h=128): (block_q, block_k) = (128,128) runs the forward at 21 TF/s,
    (512,1024) at 122 TF/s — the MXU needs the bigger s=(block_q, block_k)
    tiles to amortize; small defaults were the single biggest attention
    sink. Causal sweeps put (512,512) first (46→56 TF/s effective over
    (512,1024): one whole-t K block can't skip masked chunks); the backward
    shares the forward's optimum (fwd+bwd grad 2.31 ms = 104 TF/s at
    (512,1024) vs 2.68 at (512,512))."""
    c = target
    while c >= 128:
        if t % c == 0:
            return c
        c //= 2
    return t if t <= target else 0


def _resolve_blocks(block_q, block_k, causal):
    # r05 on-chip sweep (t=1024, d=128, bh=128, fused bwd): non-causal
    # (1024,1024) runs fwd+grad at 1.60 ms vs 1.77 at (512,1024); causal
    # keeps (512,512) (1.84 ms; one whole-t K block can't skip masked chunks)
    return (
        block_q or (_DEF_BLOCK_Q_CAUSAL if causal else _DEF_BLOCK_Q),
        block_k or (_DEF_BLOCK_K_CAUSAL if causal else _DEF_BLOCK_K),
    )


def _resident_ok(t, d, itemsize):
    """Whether a whole-(t, d) K and V (or q/do/lse/delta) residency fits the
    ~16 MiB VMEM budget with room for tiles and double-buffering. Calibrated
    on chip: t=8192, d=128, bf16 (4 MiB for K+V) compiles and runs; t=16384
    overflows ("Scoped allocation ... exceeded scoped vmem limit"). Beyond
    this the streamed kernels below tile the long side through the grid."""
    return t * d * itemsize * 2 <= 4 * 1024 * 1024


def _attention_reference(q, k, v, causal, sm_scale):
    """Dense XLA attention — the numerics contract and the vjp source."""
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * sm_scale
    if causal:
        tq, tk = logits.shape[-2], logits.shape[-1]
        mask = jnp.tril(jnp.ones((tq, tk), bool), k=tk - tq)
        logits = jnp.where(mask, logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v)


def _heads_a_block(n_head, d):
    """Heads that share one block of the last axis of a [b, t, h·d] operand:
    the one parameter the flash kernels adapt to. A block is one 128-lane
    vector wide, so two 64-wide heads stand in it side by side (four of 32);
    a head whose width is whole vectors is a block of its own, and so is the
    whole axis where there is one head (the (b·h, t, d) view the rank-4
    callers are run through). 0: the heads cannot be blocked (d_head 96, an
    odd head count at 64) and the caller splits them first."""
    if n_head == 1 or d % _LANES == 0:
        return 1
    per = _LANES // d
    return per if _LANES % d == 0 and n_head % per == 0 else 0


def _own_lanes(x, j, d_head, other=0):
    """(rows, W) x with every column outside head j's d_head set to `other`:
    how a head of a shared block reads and writes through full-width vectors.
    A contraction over W lanes of an operand so zeroed is the head's own
    d_head-wide one, at the MXU passes a d_head-wide tile padded to a lane
    costs anyway. The heads of a block are unrolled (j is static): their
    bodies are independent, and the scheduler runs one head's softmax under
    the other's products."""
    if x.shape[1] == d_head:
        return x
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    mine = (lane >= j * d_head) & (lane < (j + 1) * d_head)
    return jnp.where(mine, x, jnp.full_like(x, other))


def _sum_own_lanes(xs, d_head):
    """Head j's columns of xs[j], side by side: the block's result."""
    out = _own_lanes(xs[0], 0, d_head)
    for j in range(1, len(xs)):
        out = out + _own_lanes(xs[j], j, d_head)
    return out


def _scores(q, k_blk, sm_scale, causal, q_first, k_first, offset):
    """(block_q, block_k) f32 scores of one head, causal-masked with the
    bottom-right alignment of _attention_reference's tril(k=tk-tq): query
    row i may see keys up to i + offset."""
    s = jax.lax.dot_general(
        q, k_blk, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * sm_scale
    if causal:
        q_pos = q_first + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        k_pos = k_first + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(q_pos + offset >= k_pos, s, -jnp.inf)
    return s


def _softmax_step(s, m_prev, l_prev):
    """One online-softmax step over a block of scores: (p, alpha, m, l)."""
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    # rows fully masked so far (m still -inf) must not poison the step with
    # exp(-inf + inf): against 0 their scores and their m_prev give exp(-inf)
    m_safe = jnp.where(m_new == -jnp.inf, 0.0, m_new)
    alpha = jnp.exp(m_prev - m_safe)
    p = jnp.exp(s - m_safe[:, None])
    return p, alpha, m_new, l_prev * alpha + jnp.sum(p, axis=1)


def _saved_probs(s, lse):
    """The forward's probabilities again, from the saved logsumexp (finite
    in every row, see _lse_of: a masked score gives exp(-inf) = 0)."""
    return jnp.exp(s - lse[:, None])


def _lse_of(m, l):
    # fully-masked rows get a finite sentinel; their p = exp(-inf - lse) is 0
    return jnp.where(m == -jnp.inf, 0.0, m + jnp.log(jnp.maximum(l, 1e-20)))


def _store_lse(lse_ref, j, q_first, lse, t_q, packed):
    """The logsumexp residual of head j of the block. PACKED: one
    (1, heads · t_q) lane-major row a block, head j's t_q values from lane
    j · t_q — the earlier 128-lane broadcast layout cost ~67 MB of HBM
    write+read per bench attention layer where this is ~0.5 MB. A t_q that
    is not whole lanes keeps the broadcast layout: Mosaic cannot vector-store
    partial lanes."""
    if packed:
        # (block_q,) comes out of the row reductions one value a sublane row;
        # the row wants it one value a lane. Mosaic's own relayout is XLU
        # transposes, ~2.6 us a program here and in the open (measured on
        # chip, PR 37: the forward 0.45 -> 0.62 ms with the lse); picking the
        # diagonal of each 128 x 128 lane-broadcast square with a sublane sum
        # is a few hundred VPU operations, and exact (one term is not 0)
        n = lse.shape[0] // _LANES
        square = jnp.broadcast_to(
            lse[:, None], (lse.shape[0], _LANES)).reshape(n, _LANES, _LANES)
        diagonal = (jax.lax.broadcasted_iota(jnp.int32, square.shape, 1)
                    == jax.lax.broadcasted_iota(jnp.int32, square.shape, 2))
        rows = jnp.sum(jnp.where(diagonal, square, 0.0), axis=1)
        for r in range(n):
            start = pl.multiple_of(j * t_q + q_first + r * _LANES, _LANES)
            lse_ref[0, pl.ds(start, _LANES)] = rows[r].astype(lse_ref.dtype)
    else:
        lse_ref[j] = jnp.broadcast_to(
            lse[:, None], lse_ref.shape[1:]
        ).astype(lse_ref.dtype)


def _load_rows(ref, j, q_first, block_q, t_q, packed):
    """Head j's (block_q,) f32 rows of an lse-layout ref (see _store_lse);
    the unpacked layout is per-q-block in the streamed kernels (q_first
    None) and whole-t in the fused one."""
    if packed:
        return ref[0, pl.ds(j * t_q + q_first, block_q)].astype(jnp.float32)
    if q_first is None:
        return ref[j][..., 0].astype(jnp.float32)
    return ref[j, pl.ds(q_first, block_q), 0].astype(jnp.float32)


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref=None, *, d_head, block_k,
                  causal, sm_scale, t_q_total, lse_packed=True):
    """One (batch, head block, q block) program over blocks of [b, t, h·d]
    operands, the layout the projections leave them in: q and o are
    (block_q, W), k and v (t_k, W), W lanes holding W // d_head heads. Each
    head streams the KV blocks with the online softmax recurrence (m =
    running max, l = running sum, acc = running PV) and keeps its own columns
    of the result."""
    qi = pl.program_id(2)
    block_q, width = q_ref.shape
    heads = width // d_head
    t_k = k_ref.shape[0]
    nk = pl.cdiv(t_k, block_k)
    offset = t_k - t_q_total
    # operands stay in their native dtype (bf16 on the train path): the MXU
    # multiplies bf16 pairs at full rate and accumulates f32 via
    # preferred_element_type — upcasting to f32 FIRST forces the multi-pass
    # f32 MXU emulation at a fraction of peak (measured: the whole fwd kernel
    # 131 -> 178 TF/s from this change alone)
    q = q_ref[...]
    qs = [_own_lanes(q, j, d_head) for j in range(heads)]

    def body(ki, carry):
        k_blk = k_ref[pl.ds(ki * block_k, block_k), :]
        v_blk = v_ref[pl.ds(ki * block_k, block_k), :]
        new = []
        for q_j, (acc, m_prev, l_prev) in zip(qs, carry):
            s = _scores(q_j, k_blk, sm_scale, causal, qi * block_q,
                        ki * block_k, offset)
            p, alpha, m_new, l_new = _softmax_step(s, m_prev, l_prev)
            # p rounds to v's dtype for the PV dot — the same rounding the
            # dense XLA chain applies (probs.astype(q.dtype) in
            # _attention_reference)
            acc = acc * alpha[:, None] + jax.lax.dot_general(
                p.astype(v_blk.dtype), v_blk, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            new.append((acc, m_new, l_new))
        return tuple(new)

    init = (
        jnp.zeros((block_q, width), jnp.float32),
        jnp.full((block_q,), -jnp.inf, jnp.float32),
        jnp.zeros((block_q,), jnp.float32),
    )
    if causal:
        # only KV blocks reaching this q block's last visible key contribute
        last_key = qi * block_q + block_q - 1 + offset
        nk_needed = jnp.clip((last_key + block_k) // block_k, 0, nk)
    else:
        nk_needed = nk
    state = jax.lax.fori_loop(0, nk_needed, body, (init,) * heads)
    if lse_ref is not None:
        for j, (_, m, l) in enumerate(state):
            _store_lse(lse_ref, j, qi * block_q, _lse_of(m, l), t_q_total,
                       lse_packed)
    o_ref[...] = _sum_own_lanes(
        [acc / jnp.maximum(l, 1e-20)[:, None] for acc, _, l in state], d_head
    ).astype(o_ref.dtype)


def _flash_kernel_streamed(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref,
                           m_ref, l_ref, *, d_head, causal, sm_scale,
                           t_q_total, t_k_total, with_lse, lse_packed=True):
    """Long-context forward: grid (b, head blocks, q_blocks, k_blocks) with
    K/V streamed through the innermost grid dim, so VMEM holds one
    (block_q, W) query tile plus one (block_k, W) K/V tile regardless of t —
    the whole-KV-resident kernel above overflows VMEM past ~8k tokens (see
    _resident_ok). The online-softmax state lives in f32 VMEM scratch across
    the k-block sweep (acc for the whole block, m and l a head); the output
    tile is written on the last k step."""
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)
    block_q, width = q_ref.shape
    block_k = k_ref.shape[0]
    heads = width // d_head
    offset = t_k_total - t_q_total

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)

    if causal:
        needed = ki * block_k <= qi * block_q + block_q - 1 + offset
    else:
        needed = qi >= 0  # trivially true, keeps pl.when uniform

    @pl.when(needed)
    def _step():
        q = q_ref[...]
        k_blk = k_ref[...]
        v_blk = v_ref[...]
        for j in range(heads):
            s = _scores(_own_lanes(q, j, d_head), k_blk, sm_scale, causal,
                        qi * block_q, ki * block_k, offset)
            p, alpha, m_new, l_new = _softmax_step(
                s, m_ref[j][..., 0], l_ref[j][..., 0]
            )
            pv = jax.lax.dot_general(
                p.astype(v_blk.dtype), v_blk, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            # the other heads' columns of acc keep their own scale
            scale = jnp.broadcast_to(alpha[:, None], pv.shape)
            acc_ref[...] = acc_ref[...] * _own_lanes(
                scale, j, d_head, other=1
            ) + _own_lanes(pv, j, d_head)
            m_ref[j] = jnp.broadcast_to(m_new[:, None], m_ref.shape[1:])
            l_ref[j] = jnp.broadcast_to(l_new[:, None], l_ref.shape[1:])

    @pl.when(ki == nk - 1)
    def _finish():
        outs = []
        for j in range(heads):
            m = m_ref[j][..., 0]
            l = l_ref[j][..., 0]
            if with_lse:
                _store_lse(lse_ref, j, qi * block_q, _lse_of(m, l), t_q_total,
                       lse_packed)
            outs.append(acc_ref[...] / jnp.maximum(l, 1e-20)[:, None])
        o_ref[...] = _sum_own_lanes(outs, d_head).astype(o_ref.dtype)


def _no_lse_adapter(kernel, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref):
    kernel(q_ref, k_ref, v_ref, o_ref, None, acc_ref, m_ref, l_ref)


def _lse_shape_spec(b, n_blocks, heads, tq, block_q, q_index):
    """(shape, BlockSpec) of the kernels' logsumexp layout, see _store_lse.
    q_index(*grid ids) -> the q block, or None for the whole t_q."""
    if tq % _LANES == 0:
        return (b, n_blocks, 1, heads * tq), pl.BlockSpec(
            (None, None, 1, heads * tq), lambda bi, hi, *rest: (bi, hi, 0, 0))
    shape = (b, n_blocks, heads, tq, _LANES)
    if q_index is None:
        return shape, pl.BlockSpec(
            (None, None, heads, tq, _LANES),
            lambda bi, hi, *rest: (bi, hi, 0, 0, 0))
    return shape, pl.BlockSpec(
        (None, None, heads, block_q, _LANES),
        lambda bi, hi, *rest: (bi, hi, 0, q_index(*rest), 0),
    )


def _lse_to_kernel(x, heads):
    """(b, h, tq) f32 -> the kernels' layout (see _store_lse)."""
    b, h, tq = x.shape
    if tq % _LANES == 0:
        return x.reshape(b, h // heads, 1, heads * tq)
    return jnp.broadcast_to(
        x.reshape(b, h // heads, heads, tq, 1),
        (b, h // heads, heads, tq, _LANES),
    )


def _lse_from_kernel(x, tq):
    """The kernels' logsumexp layout -> (b, h, tq)."""
    if x.ndim == 4:  # packed: (b, blocks, 1, heads · tq)
        return x.reshape(x.shape[0], -1, tq)
    return x[..., 0].reshape(x.shape[0], -1, tq)


def _flash_fwd_call(q, k, v, n_head, heads, causal, sm_scale, blocks,
                    interpret, with_lse):
    """The forward kernels over [b, t, h·d] operands whose last axis blocks
    into `heads` heads a block (_heads_a_block > 0; blocks from
    _flash_blocks): out, or (out, lse (b, h, tq))."""
    block_q, block_k, streamed = blocks
    b, tq, f = q.shape
    tk = k.shape[1]
    d = f // n_head
    width = heads * d
    n_blocks = n_head // heads
    if streamed:
        # long-context tier: stream K/V through the grid instead of holding
        # them whole in VMEM
        grid = (b, n_blocks, tq // block_q, tk // block_k)
        q_spec = pl.BlockSpec(
            (None, block_q, width), lambda bi, hi, qi, ki: (bi, qi, hi))
        k_spec = pl.BlockSpec(
            (None, block_k, width), lambda bi, hi, qi, ki: (bi, ki, hi))
        kernel = functools.partial(
            _flash_kernel_streamed, d_head=d, causal=causal,
            sm_scale=sm_scale, t_q_total=tq, t_k_total=tk, with_lse=with_lse,
            lse_packed=tq % _LANES == 0,
        )
        if not with_lse:
            kernel = functools.partial(_no_lse_adapter, kernel)
        scratch = [
            pltpu.VMEM((block_q, width), jnp.float32),
            pltpu.VMEM((heads, block_q, _LANES), jnp.float32),
            pltpu.VMEM((heads, block_q, _LANES), jnp.float32),
        ]
    else:
        grid = (b, n_blocks, tq // block_q)
        q_spec = pl.BlockSpec(
            (None, block_q, width), lambda bi, hi, qi: (bi, qi, hi))
        k_spec = pl.BlockSpec((None, tk, width), lambda bi, hi, qi: (bi, 0, hi))
        kernel = functools.partial(
            _flash_kernel, d_head=d, block_k=block_k, causal=causal,
            sm_scale=sm_scale, t_q_total=tq, lse_packed=tq % _LANES == 0,
        )
        scratch = []
    out_shapes = [jax.ShapeDtypeStruct((b, tq, f), q.dtype)]
    out_specs = [q_spec]
    if with_lse:
        shape, spec = _lse_shape_spec(
            b, n_blocks, heads, tq, block_q, lambda qi, *ki: qi)
        out_shapes.append(jax.ShapeDtypeStruct(shape, jnp.float32))
        out_specs.append(spec)
    res = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[q_spec, k_spec, k_spec],
        out_specs=out_specs if with_lse else out_specs[0],
        out_shape=out_shapes if with_lse else out_shapes[0],
        scratch_shapes=scratch,
        interpret=interpret,
    )(q, k, v)
    if with_lse:
        return res[0], _lse_from_kernel(res[1], tq)
    return res


def flash_tiles_ok(t, block=None):
    """Conservative symmetric predicate for callers that REQUIRE the Pallas
    path on a square t (the flash ring, whose merge needs the lse the dense
    fallback doesn't produce). It gates on the TIGHTEST block target across
    causal/non-causal and q/k sides (the causal 512 targets) — if it passes,
    _flash_forward takes the Pallas path for both directions in either
    mode."""
    if t <= 0:
        return False
    tightest = min(_DEF_BLOCK_Q, _DEF_BLOCK_K,
                   _DEF_BLOCK_Q_CAUSAL, _DEF_BLOCK_K_CAUSAL)
    return _auto_block(t, block or tightest) > 0


def flash_path_taken(tq, tk, causal=False, block_q=None, block_k=None):
    """EXACT mirror of the flash forward's pallas-vs-dense decision, for code
    that must predict it from static shapes (layers.flash_attention decides
    whether to declare the Lse output with this — a mismatch would either
    dangle a declared var or silently drop the saved residual and force the
    dense recompute-vjp backward)."""
    if tq <= 0 or tk <= 0:
        return False
    bq, bk = _resolve_blocks(block_q, block_k, causal)
    return _auto_block(tq, bq) > 0 and _auto_block(tk, bk) > 0


def _flash_blocks(tq, tk, width, itemsize, causal, block_q, block_k,
                  backward=False):
    """(block_q, block_k, streamed) as the kernels take them; blocks of 0
    for ragged tiles. Streamed: a side is too long to sit whole in VMEM. The
    fused backward needs both sides resident (breaks past ~8k tokens) and
    materializes an (nk, tq, W) dQ-partials HBM temporary, bounded to <= 2x
    dQ by the nk cap here; everything bigger takes the grid-streamed tier
    (any t, O(t) memory), whose optimum is larger tiles (the gate passes on
    the resident targets, and the stream targets only widen it)."""
    bq, bk = _resolve_blocks(block_q, block_k, causal)
    bq, bk = _auto_block(tq, bq), _auto_block(tk, bk)
    if not (bq and bk):
        return 0, 0, False
    streamed = not _resident_ok(tk, width, itemsize)
    if backward:
        streamed = (streamed or tk // bk > 2
                    or not _resident_ok(tq, width, itemsize))
    if streamed:
        return (_auto_block(tq, block_q or _DEF_STREAM_BLOCK),
                _auto_block(tk, block_k or _DEF_STREAM_BLOCK), True)
    if max(tq, tk) >= 4096:
        # the (1024, block_k) f32 score/probability temporaries + resident
        # slabs (K/V with tk; q/do/o with tq in the fused backward) overflow
        # VMEM once EITHER side reaches t=4096 (compile-checked on chip,
        # including asymmetric tq=1024/tk=4096); 512 holds through 8192
        bq = min(bq, 512)
    return bq, bk, False


FLASH_DENSE = "flash_dense"
FLASH_SPLIT = "flash_split_heads"


def flash_tier(tq, tk, n_head, d, causal=False, block_q=None, block_k=None):
    """(KERNEL_DISPATCHES key, heads a block the kernels run with) of the
    tier a [b, t, h·d] flash call takes, from static shapes alone:
    "flash_heads_a_block:2" (64-wide heads, two a 128-lane block; ":4" at
    32), "flash_heads_a_block:1" (a head of whole lanes, or one head),
    "flash_split_heads" where the last axis cannot be blocked so (d_head 96,
    an odd head count at 64, a t_q under a lane): the heads are split into
    (b·h, t, d), run one a block and merged, the two transposes the packed
    tiers exist to avoid; "flash_dense" for ragged tiles."""
    if not flash_path_taken(tq, tk, causal, block_q, block_k):
        return FLASH_DENSE, 0
    heads = _heads_a_block(n_head, d)
    if not heads or (n_head > 1 and tq % _LANES):
        return FLASH_SPLIT, 1
    return "flash_heads_a_block:%d" % heads, heads


def _split_heads(x, n_head):
    """[b, t, h·d] -> (b·h, t, d), one head a row of the batch."""
    b, t, f = x.shape
    return x.reshape(b, t, n_head, f // n_head).transpose(0, 2, 1, 3).reshape(
        b * n_head, t, f // n_head)


def _merge_heads(x, n_head):
    """(b·h, t, d) -> [b, t, h·d]."""
    bh, t, d = x.shape
    return x.reshape(bh // n_head, n_head, t, d).transpose(0, 2, 1, 3).reshape(
        bh // n_head, t, n_head * d)


def _dense(q, k, v, n_head, causal, sm_scale):
    """_attention_reference over either layout: [b, t, h·d] operands with
    n_head, (b, h, t, d) without."""
    if n_head is None:
        return _attention_reference(q, k, v, causal, sm_scale)
    b = q.shape[0]

    def four(x):
        return _split_heads(x, n_head).reshape(b, n_head, x.shape[1], -1)

    out = _attention_reference(four(q), four(k), four(v), causal, sm_scale)
    return _merge_heads(out.reshape((-1,) + out.shape[2:]), n_head)


def _flash_forward(q, k, v, causal, sm_scale, block_q, block_k, interpret,
                   with_lse=False, n_head=None):
    """Flash forward. With n_head over [b, t, h·d] operands, the heads side
    by side on the last axis: out [b, tq, h·d], with_lse also lse (b, h, tq)
    (None on the dense fallback); picks the tier from the shapes and says
    which in KERNEL_DISPATCHES. Without, the rank-4 callers' entry (the flash
    ring, the attention-fusing pass, layers.flash_attention on (b, h, t, d)):
    the same kernels over the free (b·h, t, d) view, one head a block."""
    if n_head is None:
        b, h, tq, d = q.shape
        res = _flash_forward(
            q.reshape(b * h, tq, d), k.reshape(b * h, -1, d),
            v.reshape(b * h, -1, d), causal, sm_scale, block_q, block_k,
            interpret, with_lse, n_head=1,
        )
        if not with_lse:
            return res.reshape(b, h, tq, d)
        out, lse = res
        return out.reshape(b, h, tq, d), (
            None if lse is None else lse.reshape(b, h, tq))
    b, tq, f = q.shape
    tk = k.shape[1]
    d = f // n_head
    tier, heads = flash_tier(tq, tk, n_head, d, causal, block_q, block_k)
    _note_dispatch(tier)
    if tier == FLASH_DENSE:
        # ragged tails: fall back to the dense form (shapes are static, so
        # this is a trace-time decision, not a runtime branch)
        out = _dense(q, k, v, n_head, causal, sm_scale)
        return (out, None) if with_lse else out
    split = tier == FLASH_SPLIT
    if split:
        q, k, v = (_split_heads(x, n_head) for x in (q, k, v))
    blocks = _flash_blocks(tq, tk, heads * d, k.dtype.itemsize, causal,
                           block_q, block_k)
    res = _flash_fwd_call(q, k, v, 1 if split else n_head, heads, causal,
                          sm_scale, blocks, interpret, with_lse)
    if not split:
        return res
    if with_lse:
        return _merge_heads(res[0], n_head), res[1].reshape(b, n_head, tq)
    return _merge_heads(res, n_head)


# ---------------------------------------------------------------------------
# flash backward (flash-attention-2 style) against the saved logsumexp L and
# D = rowsum(dO * O): one fused kernel a K block where both sides sit in
# VMEM, a dQ kernel over q blocks and a dK/dV kernel over k blocks, each
# streaming the opposite side, where they do not
# ---------------------------------------------------------------------------


def _flash_bwd_fused_kernel(q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref,
                            dk_ref, dv_ref, dqp_ref, *, d_head, block_q,
                            causal, sm_scale, t_q_total, lse_packed=True):
    """Fused resident backward: one (b, head block, k_block) program computes
    dK and dV for its K block AND this K block's partial contribution to
    every dQ row (summed over k blocks by XLA outside). The two-kernel form
    recomputes the score matrix s and dp = dO·Vᵀ in BOTH kernels — 7
    matmul-units per backward vs the 5 this kernel executes (s, dp, dV, dK,
    dQ-partial), a 28%% flop cut on the exact tier the MFU bench runs
    (measured on chip: fwd+grad 2.42 -> 1.87 ms at t=1024 bh=128
    non-causal). A head of a shared block reads k and v with the other
    heads' columns zeroed (_own_lanes), so its scores, dP and dQ partial are
    its own, and keeps its own columns of dK and dV."""
    ki = pl.program_id(2)
    block_k, width = k_ref.shape
    heads = width // d_head
    t_k_total = pl.num_programs(2) * block_k
    offset = t_k_total - t_q_total  # bottom-right causal alignment
    t_q = q_ref.shape[0]
    nq = pl.cdiv(t_q, block_q)
    ks = [_own_lanes(k_ref[...], j, d_head) for j in range(heads)]
    vs = [_own_lanes(v_ref[...], j, d_head) for j in range(heads)]

    dqp_ref[...] = jnp.zeros_like(dqp_ref)  # skipped causal rows stay 0

    def body(qi, carry):
        rows = pl.ds(qi * block_q, block_q)
        q_blk = q_ref[rows, :]
        do_blk = do_ref[rows, :]
        # delta = rowsum(dO * O) computed here from the saved forward output
        # rather than as an XLA prologue: the prologue form writes + re-reads
        # a 128-lane-broadcast f32 tensor per layer (~134 MB of HBM traffic)
        # where this is a VPU rowsum over tiles already resident
        do_o = do_blk.astype(jnp.float32) * o_ref[rows, :].astype(jnp.float32)
        new, dqp = [], None
        for j, (dk, dv) in enumerate(carry):
            lse = _load_rows(lse_ref, j, qi * block_q, block_q, t_q_total,
                             lse_packed)
            delta = jnp.sum(_own_lanes(do_o, j, d_head), axis=1)
            s = _scores(q_blk, ks[j], sm_scale, causal, qi * block_q,
                        ki * block_k, offset)
            p = _saved_probs(s, lse)
            dv = dv + jax.lax.dot_general(
                p.astype(do_blk.dtype), do_blk, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            dp = jax.lax.dot_general(
                do_blk, vs[j], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            ds = (p * (dp - delta[:, None]) * sm_scale).astype(q_blk.dtype)
            dk = dk + jax.lax.dot_general(
                ds, q_blk, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            # zero outside the head's columns, as ks[j] is: the heads'
            # partials add into the shared rows exactly
            dqp_j = jax.lax.dot_general(
                ds, ks[j], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            dqp = dqp_j if dqp is None else dqp + dqp_j
            new.append((dk, dv))
        dqp_ref[rows, :] = dqp.astype(dqp_ref.dtype)
        return tuple(new)

    if causal:
        first_q_row = ki * block_k - offset
        q_start = jnp.clip(first_q_row // block_q, 0, nq)
    else:
        q_start = 0
    zero = jnp.zeros((block_k, width), jnp.float32)
    grads = jax.lax.fori_loop(q_start, nq, body, ((zero, zero),) * heads)
    dk_ref[...] = _sum_own_lanes([g[0] for g in grads], d_head).astype(
        dk_ref.dtype)
    dv_ref[...] = _sum_own_lanes([g[1] for g in grads], d_head).astype(
        dv_ref.dtype)


def _flash_bwd_dq_streamed(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                           dq_ref, dq_acc, *, d_head, causal, sm_scale,
                           t_q_total, t_k_total, lse_packed=True):
    """Streamed dQ: grid (b, head blocks, q_blocks, k_blocks); K/V tiles ride
    the inner grid dim, dQ accumulates in f32 scratch and lands on the last
    k step."""
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)
    block_q, width = q_ref.shape
    block_k = k_ref.shape[0]
    offset = t_k_total - t_q_total

    @pl.when(ki == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    if causal:
        needed = ki * block_k <= qi * block_q + block_q - 1 + offset
    else:
        needed = qi >= 0

    @pl.when(needed)
    def _step():
        q = q_ref[...]
        do = do_ref[...]
        q_first = qi * block_q if lse_packed else None
        for j in range(width // d_head):
            lse = _load_rows(lse_ref, j, q_first, block_q, t_q_total,
                             lse_packed)
            delta = _load_rows(delta_ref, j, q_first, block_q, t_q_total,
                               lse_packed)
            # zero outside the head's columns: so is the head's dQ
            k_blk = _own_lanes(k_ref[...], j, d_head)
            v_blk = _own_lanes(v_ref[...], j, d_head)
            s = _scores(q, k_blk, sm_scale, causal, qi * block_q,
                        ki * block_k, offset)
            p = _saved_probs(s, lse)
            dp = jax.lax.dot_general(
                do, v_blk, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            ds = (p * (dp - delta[:, None]) * sm_scale).astype(k_blk.dtype)
            dq_acc[...] = dq_acc[...] + jax.lax.dot_general(
                ds, k_blk, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )

    @pl.when(ki == nk - 1)
    def _finish():
        dq_ref[...] = dq_acc[...].astype(dq_ref.dtype)


def _flash_bwd_dkv_streamed(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                            dk_ref, dv_ref, dk_acc, dv_acc, *, d_head, causal,
                            sm_scale, t_q_total, t_k_total, lse_packed=True):
    """Streamed dK/dV: grid (b, head blocks, k_blocks, q_blocks); Q/dO/lse/
    delta tiles ride the inner grid dim, dK/dV accumulate in f32 scratch."""
    ki = pl.program_id(2)
    qi = pl.program_id(3)
    nq = pl.num_programs(3)
    block_q, width = q_ref.shape
    block_k = k_ref.shape[0]
    offset = t_k_total - t_q_total

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    if causal:
        # q rows before this k block's first key see nothing of it
        needed = qi * block_q + block_q - 1 + offset >= ki * block_k
    else:
        needed = qi >= 0

    @pl.when(needed)
    def _step():
        k_blk = k_ref[...]
        v_blk = v_ref[...]
        q_first = qi * block_q if lse_packed else None
        for j in range(width // d_head):
            lse = _load_rows(lse_ref, j, q_first, block_q, t_q_total,
                             lse_packed)
            delta = _load_rows(delta_ref, j, q_first, block_q, t_q_total,
                               lse_packed)
            # zero outside the head's columns, so the products below land in
            # the head's columns of the shared accumulators and nowhere else
            q_blk = _own_lanes(q_ref[...], j, d_head)
            do_blk = _own_lanes(do_ref[...], j, d_head)
            s = _scores(q_blk, k_blk, sm_scale, causal, qi * block_q,
                        ki * block_k, offset)
            p = _saved_probs(s, lse)
            dv_acc[...] = dv_acc[...] + jax.lax.dot_general(
                p.astype(do_blk.dtype), do_blk, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            dp = jax.lax.dot_general(
                do_blk, v_blk, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            ds = (p * (dp - delta[:, None]) * sm_scale).astype(q_blk.dtype)
            dk_acc[...] = dk_acc[...] + jax.lax.dot_general(
                ds, q_blk, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )

    @pl.when(qi == nq - 1)
    def _finish():
        dk_ref[...] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)


def _flash_bwd_call(q, k, v, out, lse, dout, n_head, heads, causal, sm_scale,
                    blocks, interpret):
    """The backward kernels over [b, t, h·d] operands (see _flash_fwd_call;
    lse (b, h, tq)): dq, dk, dv in the operands' layout."""
    b, tq, f = q.shape
    tk = k.shape[1]
    d = f // n_head
    width = heads * d
    n_blocks = n_head // heads
    block_q, block_k, streamed = blocks
    common = dict(d_head=d, causal=causal, sm_scale=sm_scale, t_q_total=tq,
                  lse_packed=tq % _LANES == 0)
    if not streamed:
        nk = tk // block_k
        q_spec = pl.BlockSpec((None, tq, width), lambda bi, hi, ki: (bi, 0, hi))
        k_spec = pl.BlockSpec(
            (None, block_k, width), lambda bi, hi, ki: (bi, ki, hi))
        lse_shape, lse_spec = _lse_shape_spec(
            b, n_blocks, heads, tq, block_q, None)
        dk, dv, dqp = pl.pallas_call(
            functools.partial(
                _flash_bwd_fused_kernel, block_q=block_q, **common),
            grid=(b, n_blocks, nk),
            in_specs=[q_spec, k_spec, k_spec, q_spec, q_spec, lse_spec],
            out_specs=[
                k_spec, k_spec,
                pl.BlockSpec((None, None, tq, width),
                             lambda bi, hi, ki: (bi, ki, 0, hi)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((b, tk, f), k.dtype),
                jax.ShapeDtypeStruct((b, tk, f), v.dtype),
                # dQ partials, one slab per k block, in q's dtype: each
                # partial is already f32-accumulated inside its dot; the
                # cross-block sum over nk<=2 terms (_flash_blocks routes
                # tk//block_k > 2 to the streamed tier) loses nothing the
                # final cast keeps
                jax.ShapeDtypeStruct((b, nk, tq, f), q.dtype),
            ],
            interpret=interpret,
        )(q, k, v, dout, out, _lse_to_kernel(lse, heads))
        dq = (
            dqp[:, 0]
            if nk == 1
            else jnp.sum(dqp, axis=1, dtype=jnp.float32).astype(q.dtype)
        )
        return dq, dk, dv

    delta = jnp.sum(
        (dout.astype(jnp.float32) * out.astype(jnp.float32)).reshape(
            b, tq, n_head, d),
        axis=-1,
    ).transpose(0, 2, 1)  # (b, h, tq), the lse's layout
    rows = (_lse_to_kernel(lse, heads), _lse_to_kernel(delta, heads))
    q_spec = pl.BlockSpec(
        (None, block_q, width), lambda bi, hi, qi, ki: (bi, qi, hi))
    k_spec = pl.BlockSpec(
        (None, block_k, width), lambda bi, hi, qi, ki: (bi, ki, hi))
    _, lane_q = _lse_shape_spec(
        b, n_blocks, heads, tq, block_q, lambda qi, ki: qi)
    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_streamed, t_k_total=tk, **common),
        grid=(b, n_blocks, tq // block_q, tk // block_k),
        in_specs=[q_spec, k_spec, k_spec, q_spec, lane_q, lane_q],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((b, tq, f), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, width), jnp.float32)],
        interpret=interpret,
    )(q, k, v, dout, *rows)

    kq_spec = pl.BlockSpec(
        (None, block_q, width), lambda bi, hi, ki, qi: (bi, qi, hi))
    kk_spec = pl.BlockSpec(
        (None, block_k, width), lambda bi, hi, ki, qi: (bi, ki, hi))
    _, klane_q = _lse_shape_spec(
        b, n_blocks, heads, tq, block_q, lambda ki, qi: qi)
    dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_streamed, t_k_total=tk, **common),
        grid=(b, n_blocks, tk // block_k, tq // block_q),
        in_specs=[kq_spec, kk_spec, kk_spec, kq_spec, klane_q, klane_q],
        out_specs=[kk_spec, kk_spec],
        out_shape=[
            jax.ShapeDtypeStruct((b, tk, f), k.dtype),
            jax.ShapeDtypeStruct((b, tk, f), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, width), jnp.float32),
            pltpu.VMEM((block_k, width), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v, dout, *rows)
    return dq, dk, dv


def _flash_backward(q, k, v, out, lse, dout, causal, sm_scale, block_q,
                    block_k, interpret, n_head=None):
    """Flash backward against the forward's saved out and lse (b, h, tq),
    through the tier the forward took (flash_tier reads the same shapes):
    dq, dk, dv in the operands' layout, [b, t, h·d] with n_head and
    (b, h, t, d) without (see _flash_forward)."""
    if n_head is None:
        b, h, tq, d = q.shape
        dq, dk, dv = _flash_backward(
            *(x.reshape(b * h, -1, d) for x in (q, k, v, out)),
            lse.reshape(b * h, 1, tq), dout.reshape(b * h, tq, d), causal,
            sm_scale, block_q, block_k, interpret, n_head=1,
        )
        return dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape)
    b, tq, f = q.shape
    tk = k.shape[1]
    d = f // n_head
    tier, heads = flash_tier(tq, tk, n_head, d, causal, block_q, block_k)
    split = tier == FLASH_SPLIT
    if split:
        q, k, v, out, dout = (
            _split_heads(x, n_head) for x in (q, k, v, out, dout))
        lse = lse.reshape(b * n_head, 1, tq)
    blocks = _flash_blocks(tq, tk, heads * d, k.dtype.itemsize, causal,
                           block_q, block_k, backward=True)
    grads = _flash_bwd_call(q, k, v, out, lse, dout, 1 if split else n_head,
                            heads, causal, sm_scale, blocks, interpret)
    return tuple(_merge_heads(g, n_head) for g in grads) if split else grads


def _resolve_defaults(q, sm_scale, interpret, n_head=None):
    """Single source of the defaulting rule: forward, _fwd and _bwd must
    agree or a custom_vjp would silently produce wrong gradients."""
    if sm_scale is None:
        sm_scale = (q.shape[-1] // (n_head or 1)) ** -0.5
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return sm_scale, interpret


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def flash_attention(
    q,
    k,
    v,
    causal=False,
    sm_scale=None,
    block_q=None,
    block_k=None,
    interpret=None,
    n_head=None,
):
    """softmax(QKᵀ·scale [causal-masked]) V. With n_head, over [b, t, h·d]
    tensors, the heads side by side on the last axis as a projection leaves
    them (no head split or merge around the call); without, over
    (b, h, t, d).

    block_q/block_k of None pick tuned per-direction defaults adapted to the
    sequence length (_auto_block); explicit values act as upper-bound targets.
    """
    sm_scale, interpret = _resolve_defaults(q, sm_scale, interpret, n_head)
    return _flash_forward(q, k, v, causal, sm_scale, block_q, block_k,
                          interpret, n_head=n_head)


def _fwd(q, k, v, causal, sm_scale, block_q, block_k, interpret, n_head):
    sm_scale, interpret = _resolve_defaults(q, sm_scale, interpret, n_head)
    out, lse = _flash_forward(
        q, k, v, causal, sm_scale, block_q, block_k, interpret,
        with_lse=True, n_head=n_head)
    return out, (q, k, v, out, lse)


def _dense_vjp(q, k, v, dout, n_head, causal, sm_scale):
    """The ragged-tile fallback's backward: the dense form's own vjp (the
    same trace-time decision as the forward fallback)."""
    return jax.vjp(
        lambda a, b, c: _dense(a, b, c, n_head, causal, sm_scale), q, k, v
    )[1](dout)


def _bwd(causal, sm_scale, block_q, block_k, interpret, n_head, res, dout):
    q, k, v, out, lse = res
    sm_scale, interpret = _resolve_defaults(q, sm_scale, interpret, n_head)
    if lse is None:
        return _dense_vjp(q, k, v, dout, n_head, causal, sm_scale)
    return _flash_backward(
        q, k, v, out, lse, dout, causal, sm_scale, block_q, block_k,
        interpret, n_head=n_head)


flash_attention.defvjp(_fwd, _bwd)


def _flash_on_mesh(ctx, fn, args, kinds, out_kinds, n_head):
    """fn(n_head, *args) as the flash ops run it: as is on one device, and
    under shard_map on an SPMD mesh, where GSPMD cannot partition a Mosaic
    kernel (see _mesh_declines). Attention is independent per (batch, head),
    so the batch splits over the data axes and the heads over tp with no
    collective; a dim the axes do not divide stays whole on every device.
    kinds / out_kinds name each operand's and result's layout: "x", an
    attention operand, [b, t, h·d] with n_head or (b, h, t, d) without, and
    "l", the (b, h, t) lse. On [b, t, h·d] the heads split over tp only where
    a device's share of the last axis is whole 128-lane blocks; fn is handed
    the heads a device holds."""
    mesh = ctx.mesh
    if mesh is None or mesh.size == 1:
        return fn(n_head, *args)
    from jax.sharding import PartitionSpec as P

    from ..parallel.collectives import shard_map

    x = args[kinds.index("x")]
    b = x.shape[0]
    h = n_head or x.shape[1]
    data = tuple(a for a in ("dp", "fsdp") if mesh.shape.get(a, 1) > 1)
    if not data or b % int(np.prod([mesh.shape[a] for a in data])):
        data = None
    n_tp = mesh.shape.get("tp", 1)
    tp = "tp" if n_tp > 1 and h % n_tp == 0 else None
    if tp and n_head and (x.shape[2] // n_tp) % _LANES:
        tp = None
    spec = {
        "x": P(data, None, tp) if n_head else P(data, tp, None, None),
        "l": P(data, tp, None),
    }
    local = n_head // n_tp if n_head and tp else n_head
    return shard_map(
        functools.partial(fn, local), mesh=mesh,
        in_specs=tuple(spec[c] for c in kinds),
        out_specs=tuple(spec[c] for c in out_kinds),
        check_vma=False,
    )(*args)


def _flash_op_operands(ins, attrs):
    """(q, k, v, n_head, causal, sm_scale, interpret, takes the kernels) of
    a flash op. n_head: the attribute, with Q/K/V [b, t, h·d]; None with the
    rank-4 (b, h, t, d) operands of the attention-fusing pass."""
    (q,) = ins["Q"]
    (k,) = ins["K"]
    (v,) = ins["V"]
    n_head = int(attrs["n_head"]) if q.ndim == 3 else None
    causal = bool(attrs.get("causal", False))
    sm_scale, interpret = _resolve_defaults(
        q, attrs.get("sm_scale"), None, n_head)
    t_axis = 1 if n_head else 2
    kernels = flash_path_taken(q.shape[t_axis], k.shape[t_axis], causal)
    return q, k, v, n_head, causal, sm_scale, interpret, kernels


@register("flash_attention")
def _flash_attention_op(ctx, ins, attrs):
    """Graph-op form: Q/K/V → Out (+ Lse residual, (b, h, t) f32), Out in
    Q's layout. With the attribute n_head the operands are [b, t, h·d], what
    a projection's mul leaves: the transformer layers emit this in place of
    reshape + transpose2 + matmul + softmax + matmul + transpose2 + reshape.
    Rank-4 operands are (b, h, t, d) (docs/flash_attention.md).

    The logsumexp residual is emitted as a side output so the explicit
    flash_attention_grad below can run the flash backward against the SAVED
    forward — without it, the generic vjp-derived grad re-traces the forward
    inside jax.vjp, and since the duplicate is a pallas custom-call with a
    different output arity, XLA CSE cannot deduplicate it (one extra forward
    kernel run per attention block per step, measured on chip)."""
    q, k, v, n_head, causal, sm_scale, interpret, kernels = (
        _flash_op_operands(ins, attrs))
    if not kernels:
        # ragged tiles: the dense form, plain XLA that GSPMD partitions
        _note_dispatch(FLASH_DENSE)
        return {"Out": [_dense(q, k, v, n_head, causal, sm_scale)]}

    def forward(n_head, q, k, v):
        return _flash_forward(q, k, v, causal, sm_scale, None, None,
                              interpret, with_lse=True, n_head=n_head)

    out, lse = _flash_on_mesh(ctx, forward, (q, k, v), "xxx", "xl", n_head)
    return {"Out": [out], "Lse": [lse]}


@register("flash_attention_grad", no_grad=True)
def _flash_attention_grad_op(ctx, ins, attrs):
    """Explicit grad: flash backward kernels against the saved Out/Lse.
    Falls back to the dense recompute-vjp when the forward took the dense
    path (no Lse in the program — ragged tiles)."""
    q, k, v, n_head, causal, sm_scale, interpret, _ = (
        _flash_op_operands(ins, attrs))
    (dout,) = ins["Out@GRAD"]
    lse = ins.get("Lse", [None])[0]
    if lse is None:
        dq, dk, dv = _dense_vjp(
            q, k, v, dout.astype(q.dtype), n_head, causal, sm_scale)
    else:
        (out,) = ins["Out"]

        def backward(n_head, q, k, v, out, lse, dout):
            return _flash_backward(q, k, v, out, lse, dout, causal, sm_scale,
                                   None, None, interpret, n_head=n_head)

        dq, dk, dv = _flash_on_mesh(
            ctx, backward, (q, k, v, out, lse, dout), "xxxxlx", "xxx", n_head)
    return {"Q@GRAD": [dq], "K@GRAD": [dk], "V@GRAD": [dv]}


# ---------------------------------------------------------------------------
# kernel-substitution tier: fused GEMM epilogue and fused
# layer_norm(+residual). Each is reached through a `fuse_*` pass
# (passes/builtin.py) that tags op runs with PALLAS_GROUP_ATTR /
# PALLAS_KERNEL_ATTR; registry.lower_ops hands a tagged run to the
# @register_fused lowering below, which validates shapes/attrs at TRACE time
# and declines (return False -> per-op fallback) anything the kernel can't
# take — so tagging is always semantics-preserving.
# ---------------------------------------------------------------------------

# r06 on-chip sweep (m=8192, n=k=2048, bf16): (512,512,512) tiles run the
# fused GEMM+bias+gelu at 168 TF/s vs 141 at (256,256,512) and 155 at
# (512,512,256) — the MXU wants the large accumulate tile, and k=512 keeps
# the x/w stream double-buffered under the ~16 MiB VMEM roof
_DEF_GEMM_BLOCK_M = 512
_DEF_GEMM_BLOCK_N = 512
_DEF_GEMM_BLOCK_K = 512

# epilogue activations the kernel computes on the f32 accumulator before the
# single rounding to the output dtype; must stay the exact functions
# core_ops registers or fused/unfused parity drifts beyond rounding. Exact
# gelu (the erf form core_ops registers) is absent: the Pallas TPU lowering
# has neither erf nor erfc, so fuse_gemm_epilogue leaves a gelu per-op and
# tags only the GEMM+bias in front of it.
_GEMM_ACT_F32 = {
    "relu": lambda z: jnp.maximum(z, 0.0),
    "tanh": jnp.tanh,
    "sigmoid": jax.nn.sigmoid,
}


def gemm_path_taken(m, n, k, block_m=None, block_n=None, block_k=None):
    """EXACT mirror of gemm_bias_act's pallas-vs-dense decision (the
    flash_path_taken idiom): tests assert it, and the fused lowering declines
    a tagged chain when it is False so the dense per-op path keeps parity."""
    if m <= 0 or n <= 0 or k <= 0:
        return False
    return (
        _auto_block(m, block_m or _DEF_GEMM_BLOCK_M) > 0
        and _auto_block(n, block_n or _DEF_GEMM_BLOCK_N) > 0
        and _auto_block(k, block_k or _DEF_GEMM_BLOCK_K) > 0
    )


def gemm_dbuf_path_taken(m, n, k, block_m=None, block_n=None, block_k=None):
    """Mirror of the double-buffered-GEMM dispatch: the manual k-loop DMA
    kernel runs exactly when the ordinary tiled kernel would (same tile
    feasibility — the accumulation order is identical, so outputs are
    bit-identical) AND the gemm_double_buffer flag takes it: "on" forces it
    everywhere (interpret-mode parity tests), "auto" takes it only on a real
    TPU (manual DMA emulation underperforms the pipelined form on the CPU
    interpreter), "off" keeps the grid-pipelined kernel."""
    from .. import flags as _flags

    mode = _flags.get_flags("gemm_double_buffer")["gemm_double_buffer"]
    if mode == "off":
        return False
    if not gemm_path_taken(m, n, k, block_m, block_n, block_k):
        return False
    if mode == "on":
        return True
    return jax.default_backend() == "tpu"


def _gemm_dbuf_kernel(x_hbm, w_hbm, b_ref, z_ref, y_ref, xb, wb, acc_ref,
                      sem, *, act, bm, bn, bk, nk):
    """One (m_block, n_block) output tile with an explicit double-buffered
    k loop: x/w stay HBM-resident (memory_space=ANY) and the kernel DMAs
    tile k+1 into the spare VMEM slot while the MXU contracts tile k — the
    overlap the grid-pipelined form leaves to the emitter, written out by
    hand so the k stream never stalls on the copy. Accumulation order and
    epilogue are identical to _gemm_epilogue_kernel (bit-identical parity
    is asserted by tests)."""
    mi = pl.program_id(0)
    ni = pl.program_id(1)

    def tile_in(ki, slot):
        cx = pltpu.make_async_copy(
            x_hbm.at[pl.ds(mi * bm, bm), pl.ds(ki * bk, bk)],
            xb.at[slot], sem.at[slot, 0],
        )
        cw = pltpu.make_async_copy(
            w_hbm.at[pl.ds(ki * bk, bk), pl.ds(ni * bn, bn)],
            wb.at[slot], sem.at[slot, 1],
        )
        return cx, cw

    acc_ref[...] = jnp.zeros_like(acc_ref)
    for c in tile_in(0, 0):
        c.start()

    def body(ki, _):
        slot = jax.lax.rem(ki, 2)

        @pl.when(ki + 1 < nk)
        def _prefetch():
            for c in tile_in(ki + 1, 1 - slot):
                c.start()

        for c in tile_in(ki, slot):
            c.wait()
        acc_ref[...] += jax.lax.dot_general(
            xb[slot], wb[slot], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return 0

    jax.lax.fori_loop(0, nk, body, 0)
    z = acc_ref[...] + b_ref[...].astype(jnp.float32)
    z_ref[...] = z.astype(z_ref.dtype)
    if y_ref is not None:
        y_ref[...] = _GEMM_ACT_F32[act](z).astype(y_ref.dtype)


def _gemm_dbuf_no_act_adapter(kernel, x_hbm, w_hbm, b_ref, z_ref, xb, wb,
                              acc_ref, sem):
    kernel(x_hbm, w_hbm, b_ref, z_ref, None, xb, wb, acc_ref, sem)


def _gemm_bias_act_dbuf(x2, w2, bias_row, act, bm, bn, bk, interpret):
    m, k = x2.shape
    n = w2.shape[1]
    grid = (m // bm, n // bn)
    nk = k // bk
    in_specs = [
        pl.BlockSpec(memory_space=pl.ANY),
        pl.BlockSpec(memory_space=pl.ANY),
        pl.BlockSpec((1, bn), lambda mi, ni: (0, ni)),
    ]
    out_spec = pl.BlockSpec((bm, bn), lambda mi, ni: (mi, ni))
    scratch = [
        pltpu.VMEM((2, bm, bk), x2.dtype),
        pltpu.VMEM((2, bk, bn), w2.dtype),
        pltpu.VMEM((bm, bn), jnp.float32),
        pltpu.SemaphoreType.DMA((2, 2)),
    ]
    kernel = functools.partial(
        _gemm_dbuf_kernel, act=act, bm=bm, bn=bn, bk=bk, nk=nk
    )
    cost = pl.CostEstimate(
        flops=2 * m * n * k,
        bytes_accessed=(x2.size + w2.size) * x2.dtype.itemsize
        + (2 if act else 1) * m * n * x2.dtype.itemsize,
        transcendentals=m * n if act in ("tanh", "sigmoid") else 0,
    )
    if act is None:
        z = pl.pallas_call(
            functools.partial(_gemm_dbuf_no_act_adapter, kernel),
            grid=grid,
            in_specs=in_specs,
            out_specs=out_spec,
            out_shape=jax.ShapeDtypeStruct((m, n), x2.dtype),
            scratch_shapes=scratch,
            cost_estimate=cost,
            interpret=interpret,
        )(x2, w2, bias_row)
        return z, None
    z, y = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[out_spec, out_spec],
        out_shape=[
            jax.ShapeDtypeStruct((m, n), x2.dtype),
            jax.ShapeDtypeStruct((m, n), x2.dtype),
        ],
        scratch_shapes=scratch,
        cost_estimate=cost,
        interpret=interpret,
    )(x2, w2, bias_row)
    return z, y


def _gemm_epilogue_kernel(x_ref, w_ref, b_ref, z_ref, y_ref, acc_ref, *, act):
    """One (m_block, n_block) output tile: stream k blocks through the
    innermost grid dim into an f32 VMEM accumulator; on the last k step add
    the bias row and apply the activation on the f32 value, rounding ONCE to
    the output dtype (the dense chain rounds after the matmul, the add, and
    the act — the documented fused-vs-unfused bf16 tolerance)."""
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(ki == nk - 1)
    def _finish():
        z = acc_ref[...] + b_ref[...].astype(jnp.float32)
        z_ref[...] = z.astype(z_ref.dtype)
        if y_ref is not None:
            y_ref[...] = _GEMM_ACT_F32[act](z).astype(y_ref.dtype)


def _gemm_no_act_adapter(kernel, x_ref, w_ref, b_ref, z_ref, acc_ref):
    kernel(x_ref, w_ref, b_ref, z_ref, None, acc_ref)


def gemm_bias_act(x2, w2, bias_row, act=None, *, block_m=None, block_n=None,
                  block_k=None, interpret=None):
    """act(x2 @ w2 + bias) over 2-D operands with the bias+activation fused
    into the GEMM epilogue. bias_row is (1, n) (or broadcastable to it).
    Returns (z, y): z the post-bias pre-activation value, y = act(z) (None
    when act is None). Ragged tiles fall back to a dense XLA form with the
    SAME f32-accumulate/round-once numerics (trace-time decision)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    m, k = x2.shape
    n = w2.shape[1]
    bias_row = jnp.broadcast_to(bias_row.reshape(1, -1), (1, n))
    if not gemm_path_taken(m, n, k, block_m, block_n, block_k):
        z32 = jax.lax.dot_general(
            x2, w2, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) + bias_row.astype(jnp.float32)
        z = z32.astype(x2.dtype)
        y = _GEMM_ACT_F32[act](z32).astype(x2.dtype) if act else None
        return z, y
    bm = _auto_block(m, block_m or _DEF_GEMM_BLOCK_M)
    bn = _auto_block(n, block_n or _DEF_GEMM_BLOCK_N)
    bk = _auto_block(k, block_k or _DEF_GEMM_BLOCK_K)
    if gemm_dbuf_path_taken(m, n, k, block_m, block_n, block_k):
        _note_dispatch("gemm_dbuf")
        return _gemm_bias_act_dbuf(x2, w2, bias_row, act, bm, bn, bk, interpret)
    grid = (m // bm, n // bn, k // bk)  # k innermost: acc carries across it
    in_specs = [
        pl.BlockSpec((bm, bk), lambda mi, ni, ki: (mi, ki)),
        pl.BlockSpec((bk, bn), lambda mi, ni, ki: (ki, ni)),
        pl.BlockSpec((1, bn), lambda mi, ni, ki: (0, ni)),
    ]
    out_spec = pl.BlockSpec((bm, bn), lambda mi, ni, ki: (mi, ni))
    kernel = functools.partial(_gemm_epilogue_kernel, act=act)
    cost = pl.CostEstimate(
        flops=2 * m * n * k,
        bytes_accessed=(x2.size + w2.size) * x2.dtype.itemsize
        + (2 if act else 1) * m * n * x2.dtype.itemsize,
        transcendentals=m * n if act in ("tanh", "sigmoid") else 0,
    )
    if act is None:
        z = pl.pallas_call(
            functools.partial(_gemm_no_act_adapter, kernel),
            grid=grid,
            in_specs=in_specs,
            out_specs=out_spec,
            out_shape=jax.ShapeDtypeStruct((m, n), x2.dtype),
            scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
            cost_estimate=cost,
            interpret=interpret,
        )(x2, w2, bias_row)
        return z, None
    z, y = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[out_spec, out_spec],
        out_shape=[
            jax.ShapeDtypeStruct((m, n), x2.dtype),
            jax.ShapeDtypeStruct((m, n), x2.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        cost_estimate=cost,
        interpret=interpret,
    )(x2, w2, bias_row)
    return z, y


# ---------------------------------------------------------------------------
# quantized GEMM tier — int8×int8→i32 and fp8(e4m3)×fp8→f32 tile paths over
# the same (m, n, k) grid as gemm_bias_act. The MXU contracts the low-
# precision operands natively (v5e: 383 int8 TOPS vs 192 bf16 TF/s — 2×) and
# the dequantize multiply rides the existing epilogue: acc → ·scale → +bias
# → act, rounding ONCE at the store exactly like the f32 path. Uncovered
# shapes/dtypes decline to a dense XLA form with the same
# low-precision-multiply / wide-accumulate / round-once numerics, so the
# dispatch decision never changes results (the PR 11 contract). int8's i32
# accumulation is exact regardless of tiling; kernel-vs-fallback parity is
# within one f32 ulp of the dequant epilogue (the compiler may or may not
# fuse ·scale+bias into an fma). fp8's f32 accumulation is tiled, so parity
# is bit-bounded like flash.
# ---------------------------------------------------------------------------

# int8/fp8 sublane minimum is 32 (vs 8 for f32, 16 for bf16) — a (32, 128)
# tile floor. _auto_block's 128 floor already clears it; the 512 target from
# the r06 f32 sweep carries over (the accumulate tile, not the operand dtype,
# is what the MXU wants large).
_QUANT_GEMM_ACC = {
    jnp.dtype(jnp.int8): jnp.int32,
    jnp.dtype(jnp.float8_e4m3fn): jnp.float32,
}


def quant_gemm_path_taken(m, n, k, dtype, block_m=None, block_n=None,
                          block_k=None):
    """EXACT mirror of quant_gemm_bias_act's pallas-vs-dense decision. The
    quantized_gemm flag picks the tier with the paged_flash semantics: "off"
    always dense, "on" forces the kernel (interpret mode off-TPU — parity
    tests), "auto" takes the kernel only on a real TPU (an interpreted
    int8 kernel is slower than the dense XLA dot on the CPU test mesh).
    dtype must be int8 or float8_e4m3fn and the f32-GEMM tile feasibility
    applies unchanged."""
    from .. import flags as _flags

    mode = _flags.get_flags("quantized_gemm")["quantized_gemm"]
    if mode == "off":
        return False
    if jnp.dtype(dtype) not in _QUANT_GEMM_ACC:
        return False
    if not gemm_path_taken(m, n, k, block_m, block_n, block_k):
        return False
    # low-precision Mosaic granule is (32, 128) — stricter than the f32
    # tier, which accepts a single whole ragged tile
    bm = _auto_block(m, block_m or _DEF_GEMM_BLOCK_M)
    bn = _auto_block(n, block_n or _DEF_GEMM_BLOCK_N)
    bk = _auto_block(k, block_k or _DEF_GEMM_BLOCK_K)
    if bm % 32 or bn % _LANES or bk % _LANES:
        return False
    if mode == "on":
        return True
    return jax.default_backend() == "tpu"


def _quant_gemm_kernel(s_ref, x_ref, w_ref, b_ref, z_ref, y_ref, acc_ref, *,
                       act):
    """One (m_block, n_block) tile: low-precision operands stream through the
    MXU into a wide VMEM accumulator (i32 for int8, f32 for fp8 — native-
    dtype operands with preferred_element_type, never upcast first); the last
    k step dequantizes with the combined per-tensor scale, adds bias, applies
    the activation, and rounds once to the output dtype. The scale rides in
    SMEM as a (1, 1) scalar."""
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=acc_ref.dtype,
    )

    @pl.when(ki == nk - 1)
    def _finish():
        z = acc_ref[...].astype(jnp.float32) * s_ref[0, 0] + b_ref[
            ...
        ].astype(jnp.float32)
        z_ref[...] = z.astype(z_ref.dtype)
        if y_ref is not None:
            y_ref[...] = _GEMM_ACT_F32[act](z).astype(y_ref.dtype)


def _quant_gemm_no_act_adapter(kernel, s_ref, x_ref, w_ref, b_ref, z_ref,
                               acc_ref):
    kernel(s_ref, x_ref, w_ref, b_ref, z_ref, None, acc_ref)


def quant_gemm_bias_act(x2, w2, scale, bias_row=None, act=None, *,
                        out_dtype=jnp.float32, block_m=None, block_n=None,
                        block_k=None, interpret=None):
    """act((x2 @ w2) * scale + bias) where x2/w2 are int8 levels or fp8
    values and scale is the combined per-tensor dequantize factor
    (x_scale * w_scale, a scalar). Accumulation is i32 (int8) or f32 (fp8);
    dequant/bias/act happen on the wide value with ONE rounding to out_dtype.
    Returns (z, y) like gemm_bias_act: z post-bias pre-activation, y = act(z)
    (None when act is None). Shapes/dtypes the kernel declines
    (quant_gemm_path_taken False) fall back to a dense XLA form with the
    same wide-accumulate/round-once numerics."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    m, k = x2.shape
    n = w2.shape[1]
    acc_dtype = _QUANT_GEMM_ACC.get(jnp.dtype(x2.dtype))
    scale = jnp.asarray(scale, jnp.float32).reshape(1, 1)
    if bias_row is None:
        bias_row = jnp.zeros((1, n), jnp.float32)
    bias_row = jnp.broadcast_to(bias_row.reshape(1, -1), (1, n))
    if acc_dtype is None or not quant_gemm_path_taken(
        m, n, k, x2.dtype, block_m, block_n, block_k
    ):
        wide = jax.lax.dot_general(
            x2, w2, (((1,), (0,)), ((), ())),
            preferred_element_type=acc_dtype or jnp.float32,
        )
        z32 = wide.astype(jnp.float32) * scale[0, 0] + bias_row.astype(
            jnp.float32
        )
        z = z32.astype(out_dtype)
        y = _GEMM_ACT_F32[act](z32).astype(out_dtype) if act else None
        return z, y
    family = "gemm_int8" if acc_dtype == jnp.int32 else "gemm_fp8"
    _note_dispatch(family)
    bm = _auto_block(m, block_m or _DEF_GEMM_BLOCK_M)
    bn = _auto_block(n, block_n or _DEF_GEMM_BLOCK_N)
    bk = _auto_block(k, block_k or _DEF_GEMM_BLOCK_K)
    grid = (m // bm, n // bn, k // bk)  # k innermost: acc carries across it
    in_specs = [
        pl.BlockSpec(
            (1, 1), lambda mi, ni, ki: (0, 0), memory_space=pltpu.SMEM
        ),
        pl.BlockSpec((bm, bk), lambda mi, ni, ki: (mi, ki)),
        pl.BlockSpec((bk, bn), lambda mi, ni, ki: (ki, ni)),
        pl.BlockSpec((1, bn), lambda mi, ni, ki: (0, ni)),
    ]
    out_spec = pl.BlockSpec((bm, bn), lambda mi, ni, ki: (mi, ni))
    kernel = functools.partial(_quant_gemm_kernel, act=act)
    cost = pl.CostEstimate(
        flops=2 * m * n * k,
        bytes_accessed=(x2.size + w2.size) * x2.dtype.itemsize
        + (2 if act else 1) * m * n * jnp.dtype(out_dtype).itemsize,
        transcendentals=m * n if act in ("tanh", "sigmoid") else 0,
    )
    scratch = [pltpu.VMEM((bm, bn), acc_dtype)]
    if act is None:
        z = pl.pallas_call(
            functools.partial(_quant_gemm_no_act_adapter, kernel),
            grid=grid,
            in_specs=in_specs,
            out_specs=out_spec,
            out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
            scratch_shapes=scratch,
            cost_estimate=cost,
            interpret=interpret,
        )(scale, x2, w2, bias_row)
        return z, None
    z, y = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[out_spec, out_spec],
        out_shape=[
            jax.ShapeDtypeStruct((m, n), out_dtype),
            jax.ShapeDtypeStruct((m, n), out_dtype),
        ],
        scratch_shapes=scratch,
        cost_estimate=cost,
        interpret=interpret,
    )(scale, x2, w2, bias_row)
    return z, y


# ---------------------------------------------------------------------------
# paged flash attention — the serving decode/chunk-prefill kernel. Walks only
# the pages a slot holds: an in-kernel loop whose trip count comes from the
# scalar-prefetched position, over compute blocks of several pages that the
# kernel copies out of the HBM pool itself (double-buffered async copies
# chasing the block table), with the online-softmax recurrence in VMEM
# accumulators and the mask by position inside the loop — the gathered
# [*, ctx, heads, d] context of the dense lowering is never materialized, and
# an idle slot or a short context costs a block, not a table's worth of grid
# steps.
# ---------------------------------------------------------------------------

# Pages in one compute block of the walk, by path. Swept on chip (v5e, jax
# 0.9.0, PR 26) at gpt2l_chat's geometry: table 128 wide, 20 heads of 64, page
# 8, f32; us a call, the parent's one-page grid first. Decode, 24 slots (10 at
# 200-600 positions, 14 idle): 814 -> 98 / 84 / 88 / 105 at 4 / 8 / 16 / 32
# pages; all 24 at 1023: 3031 -> 392 / 372 at 8 / 16. A 32-row chunk ending at
# position 223: 187 -> 54 / 34 / 19 / 13 at 4 / 8 / 16 / 32; at 1023: 810 ->
# 236 / 128 / 62 / 35 (its per-head MXU products cost the same whatever the
# block holds, so the largest block the buffers' budget allows).
_PAGED_PAGES_PER_BLOCK = 8  # decode: one query row a program
_PAGED_PAGES_PER_BLOCK_CHUNK = 32  # chunked prefill: one shared table
# what the walk's four page buffers (K and V, two blocks each) may take of the
# ~16 MiB of VMEM a kernel is given: wide heads or large pages shrink the block
_PAGED_BUFFER_BYTES = 8 * 1024 * 1024
# A chunk's block holds at most this many positions: the kernel's code is
# unrolled over a head's (rows, block) scores, and 32 pages of 64 (2,048
# positions) made a 256-row chunk program of 32 layers 215 MB of code (a
# described v5e); the accepted cells' 32 pages of 16 are 512
_PAGED_CHUNK_BLOCK_POSITIONS = 512
# A chunk whose heads are _PAGED_WIDE_HEAD wide or wider is given
# _PAGED_WIDE_VMEM_BYTES of VMEM: its carries and a head's K and V slices in
# float32 are twice what heads of 64 take, and a chunk of 256 rows of 32 heads
# of 128 needed 44.5 MiB of the 40.4 the compiler allows by default
_PAGED_WIDE_HEAD = 128
_PAGED_WIDE_VMEM_BYTES = 100 * 1024 * 1024

# Folded into serving/compile_cache.variant_key: an exported serving module
# embeds the Mosaic kernels it was lowered with, so a cached export must miss
# when a kernel's lowering changes. Bump it with every such change.
KERNELS_REVISION = "31-paged-latent"


def paged_flash_path_taken(n_q, n_pages, page_size, n_head, d_head):
    """EXACT mirror of the paged_attention lowering's kernel-vs-dense
    decision. The paged_flash flag picks the tier: "off" always takes the
    dense flat-gather reference; "on" always takes the kernel (interpret
    mode off-TPU — the hermetic parity tests force this — and on a TPU
    whatever Mosaic says about the geometry is raised, not hidden); "auto"
    (default) takes the kernel only on a real TPU, because an interpreted
    Pallas body in the decode hot loop is slower than the dense XLA gather
    on the CPU test mesh, and only when a page is a whole number of 8-row
    sublane tiles — the one geometry limit of the (page_size, n_head*d)
    page copies (compile-checked for v5e: 2x16 up to 16x128 heads, pages of
    8..128 rows, f32 and int8 pools). More than 128 heads decline in every
    mode: decode keeps its softmax carries a head a lane."""
    from .. import flags as _flags

    mode = _flags.get_flags("paged_flash")["paged_flash"]
    if mode == "off":
        return False
    if min(int(n_q), int(n_pages), int(page_size), int(n_head), int(d_head)) < 1:
        return False
    if int(n_head) > _LANES:  # decode keeps its softmax carries a head a lane
        return False
    if mode == "on":
        return True
    return jax.default_backend() == "tpu" and int(page_size) % 8 == 0


def _paged_block_pages(n_pages, page_bytes, shared, page_size=None):
    """Pages in a compute block: the module constant of the path, or the
    whole table where that is narrower, or what of it the two K and two V
    buffers' VMEM budget holds (page_bytes: one pool page, page_size *
    n_head*d * itemsize); a chunk's at most _PAGED_CHUNK_BLOCK_POSITIONS
    positions of `page_size`."""
    most = _PAGED_PAGES_PER_BLOCK_CHUNK if shared else _PAGED_PAGES_PER_BLOCK
    if shared and page_size:
        most = min(most, max(1, _PAGED_CHUNK_BLOCK_POSITIONS // int(page_size)))
    fits = _PAGED_BUFFER_BYTES // (4 * int(page_bytes))
    return max(1, min(most, int(n_pages), fits))


def paged_positions_walked(pos, page_size, n_pages, row_bytes, shared=False,
                           window=0):
    """EXACT mirror of the kernel's trip count, in positions: what one
    paged_flash_attention call walks for these positions over a table
    n_pages wide whose pool rows take row_bytes (n_head*d * itemsize).
    Each row (decode), or the one shared table at max(pos) (chunked
    prefill, shared=True), takes pos // block + 1 blocks, none for
    pos < 0, at most the table's worth. With a `window` the walk starts at
    the block of the row's first position in it, max(pos - window + 1, 0)
    (a chunk: of its first row's, min(pos)), and the blocks before are not
    walked."""
    block = int(page_size) * _paged_block_pages(
        n_pages, page_size * row_bytes, shared, page_size
    )
    pos = np.asarray(pos, dtype=np.int64).reshape(-1)
    low = pos.min(keepdims=True) if shared else pos
    if shared:
        pos = pos.max(keepdims=True)
    first = np.maximum(low - window + 1, 0) // block if window else 0
    most = -(-int(n_pages) * int(page_size) // block)
    return int(np.clip(
        np.clip(pos // block + 1, 0, most) - first, 0, None).sum()) * block


def _paged_flash_kernel(*refs, page_size, n_head, n_kv_head, sm_scale,
                        per_row, quant, n_pages, block_pages, window=0):
    """Every query row the program owns against the pages its table holds up
    to pos, a compute block of block_pages pages a loop trip. per_row
    (decode): grid (slot,), the slot's one query row, its own block-table
    row, pos a scalar out of the prefetched pos vector. Shared (chunked
    prefill): one program, every row of the chunk against the one shared
    table; per-row positions arrive as a (rows, 1) VMEM block and the
    scalar-prefetched max of them bounds the walk. The block tables ride in
    as scalar-prefetch operands; the pools stay in HBM and the kernel copies
    a block's pages into one of two VMEM buffers, block b + 1's copies in
    flight while block b is computed. A page wholly past pos is not copied
    (so neither is one past a table that is no whole number of blocks): the
    mask by pos drops what its buffer rows still hold, which the zeroing at
    the first program keeps finite. quant: K/V pages are int8 levels and a
    (1, block) row of f32 per-position scales comes with each block; they
    scale the scores and the probabilities, so no dequantized row exists
    anywhere. The online-softmax carries live in VMEM scratch.

    The block's products come in two forms, by what the program owns. One
    query row (decode) has nothing to feed the MXU per head, so q * K and
    p * V are whole-feature VPU products, positions on the sublanes, and
    two constant head-indicator matrices on the MXU sum q * K over each
    head's lanes and spread p back over them (m, l: (1, 128), a head a
    lane). A chunk of rows walks the heads over lane slices with two MXU
    products each (m, l per head, lane-broadcast like the flash kernels
    above). acc is whole-feature in both.

    Grouped-query attention (n_kv_head < n_head, group = their ratio): the
    pools' rows hold n_kv_head heads. Decode gets its query row as `group`
    rows of the pools' width, row g holding member g of every KV head's
    group, so each is one whole-feature product with the same K and V block
    and the indicator matrices of row g put its heads on lanes g * n_kv_head
    + j (with group 1 this is the one row and the one pair of matrices there
    was). A chunk's query head h reads the lane slice of KV head h // group.

    window > 0 (a sliding-window layer): query t attends t - window < s <= t
    alone. The walk starts at the block of the row's first such position
    (decode) or of the chunk's first row's (shared: the scalar-prefetched
    min of the positions), the pages before that position are not copied,
    and the mask drops what lies behind each row's own bound."""
    bt_ref, sp_ref = refs[:2]
    refs = refs[2:]
    if per_row:
        row = pl.program_id(0)
        pos = last = sp_ref[row]
        page_of = lambda j: bt_ref[row, j]
        low = jnp.maximum(pos - window + 1, 0) if window else 0
    else:
        posv_ref, refs = refs[0], refs[1:]
        pos = posv_ref[...]  # (rows, 1)
        last = sp_ref[0]
        page_of = lambda j: bt_ref[j]
        low = jnp.maximum(sp_ref[1] - window + 1, 0) if window else 0
    q_ref, refs = refs[0], refs[1:]
    if quant:
        ks_ref, vs_ref, refs = refs[0], refs[1], refs[2:]
    pools, o_ref, bufs = refs[:2], refs[2], refs[3:5]  # K, V
    sem, acc_ref, m_ref, l_ref = refs[5:9]
    if per_row:
        sum_ref, spread_ref = refs[9:]  # (group, feat, 128), (group, 128, feat)
    rows, feat = q_ref.shape  # decode: (group, the pools' width)
    group = n_head // n_kv_head
    d = (feat if per_row else bufs[0].shape[-1]) // n_kv_head
    block = block_pages * page_size
    n_blocks = jnp.clip(last // block + 1, 0, pl.cdiv(n_pages, block_pages))
    first_block = low // block

    def head_lanes(shape, head_axis, g):
        # 1.0 where the lane (the other axis) belongs to the KV head whose
        # group member g sits on this head lane (g * n_kv_head + the KV head)
        lane = jax.lax.broadcasted_iota(jnp.int32, shape, 1 - head_axis)
        first = (jax.lax.broadcasted_iota(jnp.int32, shape, head_axis)
                 - g * n_kv_head) * d
        return ((lane >= first) & (lane < first + d)).astype(jnp.float32)

    @pl.when(pl.program_id(0) == 0)
    def _once():
        for buf in bufs:
            buf[...] = jnp.zeros_like(buf)
        if per_row:
            for g in range(group):
                sum_ref[g] = head_lanes(sum_ref.shape[1:], 1, g)
                spread_ref[g] = head_lanes(spread_ref.shape[1:], 0, g)

    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
    l_ref[...] = jnp.zeros_like(l_ref)

    def page_copies(b, slot, start):
        # the block's pages that hold a position up to pos, and are in the
        # table, from the page of the window's first position on
        first = b * block_pages
        skip = (jnp.clip(low // page_size - first, 0, block_pages)
                if window else 0)
        n_live = jnp.clip(
            jnp.minimum(last // page_size + 1, n_pages) - first, 0, block_pages
        )

        def _page(i, _):
            row0 = pl.multiple_of(page_of(first + i) * page_size, page_size)
            for n in range(2):
                copy = pltpu.make_async_copy(
                    pools[n].at[pl.ds(row0, page_size)],
                    bufs[n].at[slot, i], sem.at[n, slot],
                )
                copy.start() if start else copy.wait()

        jax.lax.fori_loop(skip, n_live, _page, None)

    def dot(x, y, contract=((1,), (0,))):
        return jax.lax.dot_general(
            x, y, (contract, ((), ())), preferred_element_type=jnp.float32
        )

    def softmax_step(s, live, m_prev, l_prev, axis):
        s = jnp.where(live, s, -jnp.inf)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=axis, keepdims=True))
        # alpha rescales the old accumulator; exp(-inf - -inf) = nan, so
        # pin never-seen rows (m_prev = -inf) to alpha = 0 explicitly
        alpha = jnp.exp(
            jnp.where(m_prev == -jnp.inf, -jnp.inf, m_prev - m_new)
        )
        # the where kills the -inf - -inf nan on dead rows too
        p = jnp.where(live, jnp.exp(s - m_new), 0.0)
        l_new = l_prev * alpha + jnp.sum(p, axis=axis, keepdims=True)
        return p, alpha, m_new, l_new

    def column(scale_row):
        # (1, block) -> (block, 1) with no transpose: the diagonal's lane sum
        eye = (
            jax.lax.broadcasted_iota(jnp.int32, (block, block), 0)
            == jax.lax.broadcasted_iota(jnp.int32, (block, block), 1)
        )
        return jnp.sum(jnp.where(eye, scale_row, 0.0), axis=1, keepdims=True)

    def one_row_block(b, slot):
        k = bufs[0][slot].astype(jnp.float32).reshape(block, feat)
        v = bufs[1][slot].astype(jnp.float32).reshape(block, feat)
        s = sum(
            dot(k * q_ref[g:g + 1].astype(jnp.float32), sum_ref[g])
            for g in range(group)
        ) * sm_scale
        if quant:
            s = s * column(ks_ref[pl.ds(b, 1), :])
        at = b * block + jax.lax.broadcasted_iota(jnp.int32, (block, _LANES), 0)
        live = (at <= pos) & (at > pos - window) if window else at <= pos
        p, alpha, m_ref[...], l_ref[...] = softmax_step(
            s, live, m_ref[...], l_ref[...], 0
        )
        if quant:
            p = p * column(vs_ref[pl.ds(b, 1), :])
        p_alpha = jnp.concatenate([p, jnp.broadcast_to(alpha, (8, _LANES))])
        for g in range(group):
            spread = dot(p_alpha, spread_ref[g])  # (block + 8, feat)
            acc_ref[g:g + 1] = acc_ref[g:g + 1] * spread[block:block + 1] + (
                jnp.sum(spread[:block] * v, axis=0, keepdims=True)
            )

    def chunk_block(b, slot):
        at = b * block + jax.lax.broadcasted_iota(jnp.int32, (rows, block), 1)
        live = (at <= pos) & (at > pos - window) if window else at <= pos
        if quant:  # (1, block): this block's per-position scales
            k_scale = ks_ref[pl.ds(b, 1), :] * sm_scale
            v_scale = vs_ref[pl.ds(b, 1), :]
        for h in range(n_head):
            sl = slice(h * d, (h + 1) * d)
            kv = slice(h // group * d, (h // group + 1) * d)
            q2 = q_ref[:, sl].astype(jnp.float32)  # (rows, d)
            k2 = bufs[0][slot, :, :, kv].astype(jnp.float32).reshape(block, d)
            v2 = bufs[1][slot, :, :, kv].astype(jnp.float32).reshape(block, d)
            s = dot(q2, k2, ((1,), (1,))) * (k_scale if quant else sm_scale)
            p, alpha, m_new, l_new = softmax_step(
                s, live, m_ref[h][:, :1], l_ref[h][:, :1], 1
            )
            acc_ref[:, sl] = acc_ref[:, sl] * alpha + dot(
                p * v_scale if quant else p, v2
            )
            m_ref[h] = jnp.broadcast_to(m_new, m_ref.shape[1:])
            l_ref[h] = jnp.broadcast_to(l_new, l_ref.shape[1:])

    @pl.when(n_blocks > first_block)
    def _first():
        page_copies(first_block, first_block % 2, True)

    def _block(b, _):
        slot = b % 2

        @pl.when(b + 1 < n_blocks)
        def _next():
            page_copies(b + 1, 1 - slot, True)

        page_copies(b, slot, False)
        (one_row_block if per_row else chunk_block)(b, slot)

    jax.lax.fori_loop(first_block, n_blocks, _block, None)

    # safe softmax tail: a fully-masked row (pos < 0, nothing live) has
    # l = 0 and emits zeros instead of 0/0 nan — the where-mask contract
    # the dense reference shares
    if per_row:
        l8 = jnp.broadcast_to(l_ref[...], (8, _LANES))
        for g in range(group):
            l = dot(l8, spread_ref[g])[:1]
            o_ref[g:g + 1] = (
                acc_ref[g:g + 1] / jnp.where(l > 0.0, l, 1.0)
            ).astype(o_ref.dtype)
    else:
        for h in range(n_head):
            sl = slice(h * d, (h + 1) * d)
            l = l_ref[h][:, :1]
            o_ref[:, sl] = (
                acc_ref[:, sl] / jnp.where(l > 0.0, l, 1.0)
            ).astype(o_ref.dtype)


def paged_flash_attention(q, k_pool, v_pool, block_table, pos, *, n_head,
                          page_size, sm_scale=None, k_scales=None,
                          v_scales=None, interpret=None, n_kv_head=None,
                          window=0):
    """Paged attention over the KV pool without materializing the gathered
    context. q is [rows, n_head*d]; block_table is [rows, P] (decode — one
    page list per query row) or [P] (chunked prefill — one list shared by
    all rows); pos[r] bounds row r's live context (attends 0..pos
    inclusive; pos < 0 means fully masked and emits zeros). Returns
    [rows, n_head*d] in q's dtype with f32 accumulation — bit-bounded, not
    bit-identical, vs the dense reference (the online softmax reassociates
    the sum). The work follows pos, not the table's width:
    paged_positions_walked mirrors it.

    The pools are read in place as [pool_rows, n_kv_head*d]: one page is one
    (page_size, n_kv_head*d) copy, legal for Mosaic whenever page_size is a
    multiple of 8 (of 16 for a bf16 pool), with no relayout of the pool
    around the call. n_kv_head (default n_head) < n_head is grouped-query
    attention: query head i reads KV head i // (n_head // n_kv_head).

    k_scales/v_scales (both or neither): the pools hold int8 levels and
    [pool_rows] f32 per-row scales ride along, gathered through the table
    ahead of the call; the kernel scales the scores and the probabilities
    by them on the block-table walk, so no dequantized f32 row exists
    anywhere.

    window > 0: a sliding-window layer's rows, each attending the last
    `window` positions up to its own (pos - window < s <= pos); the walk
    starts at the first of them and paged_positions_walked(window=) mirrors
    it."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    rows, feat = q.shape
    d = feat // n_head
    n_kv = int(n_kv_head or n_head)
    group, kv_feat = n_head // n_kv, n_kv * d
    bt = block_table.astype(jnp.int32)
    pos_v = pos.reshape(-1).astype(jnp.int32)
    quant = k_scales is not None
    per_row = bt.ndim == 2
    n_pages = bt.shape[-1]
    block_pages = _paged_block_pages(
        n_pages, page_size * kv_feat * k_pool.dtype.itemsize, not per_row,
        page_size
    )
    wide = {}
    if not per_row and d >= _PAGED_WIDE_HEAD:
        wide["compiler_params"] = pltpu.CompilerParams(
            vmem_limit_bytes=_PAGED_WIDE_VMEM_BYTES)
    _note_dispatch("paged_flash_int8" if quant else "paged_flash")
    if per_row:
        grid = (rows,)
        q_spec = pl.BlockSpec(
            (None, group, kv_feat), lambda s, bt_r, pos_r: (s, 0, 0))
        prefetch = [bt, pos_v]
        # (rows, group, kv_feat): a slot's block is legal only as the whole
        # of the last two dimensions; row g holds member g of every KV
        # head's group of query heads
        operands = [q.reshape(rows, n_kv, group, d).transpose(0, 2, 1, 3)
                    .reshape(rows, group, kv_feat)]
        in_specs = [q_spec]
        out_shape = jax.ShapeDtypeStruct((rows, group, kv_feat), q.dtype)
        carries = [
            pltpu.VMEM((group, kv_feat), jnp.float32),  # acc
            pltpu.VMEM((1, _LANES), jnp.float32),  # m, a head a lane
            pltpu.VMEM((1, _LANES), jnp.float32),  # l
            pltpu.VMEM((group, kv_feat, _LANES), jnp.float32),  # lanes -> head sums
            pltpu.VMEM((group, _LANES, kv_feat), jnp.float32),  # head -> its lanes
        ]
    else:
        grid = (1,)
        whole = lambda i, bt_r, last_r: (0, 0)
        q_spec = pl.BlockSpec((rows, feat), whole)
        prefetch = [bt, (jnp.stack([jnp.max(pos_v), jnp.min(pos_v)])
                         if window else jnp.max(pos_v).reshape(1))]
        operands = [pos_v.reshape(rows, 1), q]
        in_specs = [pl.BlockSpec((rows, 1), whole), q_spec]
        out_shape = jax.ShapeDtypeStruct((rows, feat), q.dtype)
        carries = [
            pltpu.VMEM((rows, feat), jnp.float32),  # acc
            pltpu.VMEM((n_head, rows, _LANES), jnp.float32),  # m
            pltpu.VMEM((n_head, rows, _LANES), jnp.float32),  # l
        ]
    if quant:
        # Mosaic copies no (page_size, 1) slice out of a scale pool (PR 26:
        # "slice shape must be aligned to tiling (128)"), so the scales
        # alone are gathered through the table out here, a page a row
        # (1/feat of the K and V bytes; on chip 17 us of the call's 89),
        # and reach the kernel as one (1, block) row a block, the table
        # padded to whole blocks with its last page
        n_blocks = pl.cdiv(n_pages, block_pages)
        pages = bt[..., jnp.minimum(
            jnp.arange(n_blocks * block_pages), n_pages - 1
        )]
        shape = bt.shape[:-1] + (n_blocks, block_pages * page_size)
        operands += [
            jnp.take(
                scales.astype(jnp.float32).reshape(-1, page_size), pages,
                axis=0,
            ).reshape(shape)
            for scales in (k_scales, v_scales)
        ]
        if per_row:
            in_specs += [pl.BlockSpec(
                (None,) + shape[1:], lambda s, bt_r, pos_r: (s, 0, 0)
            )] * 2
        else:
            in_specs += [pl.BlockSpec(shape, whole)] * 2
    operands += [k_pool, v_pool]
    in_specs += [pl.BlockSpec(memory_space=pltpu.HBM)] * 2
    out = pl.pallas_call(
        functools.partial(
            _paged_flash_kernel, page_size=page_size, n_head=n_head,
            n_kv_head=n_kv, sm_scale=float(sm_scale or 0.0) or d**-0.5,
            per_row=per_row, quant=quant, n_pages=n_pages,
            block_pages=block_pages, window=int(window or 0),
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=in_specs,
            out_specs=q_spec,
            scratch_shapes=[
                pltpu.VMEM((2, block_pages, page_size, kv_feat), k_pool.dtype),
                pltpu.VMEM((2, block_pages, page_size, kv_feat), v_pool.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),  # [K | V, buffer]
            ] + carries,
        ),
        out_shape=out_shape,
        interpret=interpret,
        **wide,
    )(*prefetch, *operands)
    if per_row:
        out = out.reshape(rows, group, n_kv, d).transpose(0, 2, 1, 3)
    return out.reshape(rows, feat)


# ---------------------------------------------------------------------------
# latent paged attention (mla_attention): one pool whose row is the key of
# every query head and, its leading columns, their value
# ---------------------------------------------------------------------------

# Positions in one compute block of the latent walk, and the tokens of a
# prefill chunk one program owns (x n_head query rows: its MXU rows). A
# decode program owns one token: its n_head query rows are the MXU's rows,
# so the walk is two products a block whatever the heads. A chunk's programs
# each walk the prefix again: 16 tokens x 32 heads are 512 rows against 1024
# positions, 2 MiB of float32 scores.
_LATENT_BLOCK_POSITIONS = 512
_LATENT_BLOCK_POSITIONS_CHUNK = 1024
_LATENT_CHUNK_TOKENS = 16
_LATENT_VMEM_BYTES = 64 * 1024 * 1024


def latent_paged_path_taken(n_q, n_pages, page_size, n_head, width):
    """EXACT mirror of the mla_attention lowering's kernel-vs-dense decision,
    under the paged_flash flag as paged_flash_path_taken: "auto" takes the
    kernel on a TPU when a page is whole sublane tiles of bfloat16 and the
    pool's row whole lane tiles."""
    from .. import flags as _flags

    mode = _flags.get_flags("paged_flash")["paged_flash"]
    if mode == "off":
        return False
    if min(int(n_q), int(n_pages), int(page_size), int(n_head), int(width)) < 1:
        return False
    if mode == "on":
        return True
    return (jax.default_backend() == "tpu" and int(page_size) % 16 == 0
            and int(width) % _LANES == 0)


def _latent_block_pages(n_pages, page_size, shared):
    most = _LATENT_BLOCK_POSITIONS_CHUNK if shared else _LATENT_BLOCK_POSITIONS
    return max(1, min(most // int(page_size), int(n_pages)))


def latent_positions_walked(pos, page_size, n_pages, shared=False):
    """EXACT mirror of latent_paged_attention's trip count, in positions, as
    paged_positions_walked is of paged_flash_attention's: each row (decode),
    or each program's _LATENT_CHUNK_TOKENS rows at their largest position
    (a prefill chunk, shared=True), takes pos // block + 1 blocks."""
    block = int(page_size) * _latent_block_pages(n_pages, page_size, shared)
    pos = np.asarray(pos, dtype=np.int64).reshape(-1)
    if shared:
        tokens = min(pos.size, _LATENT_CHUNK_TOKENS)
        pad = -pos.size % tokens
        pos = np.concatenate([pos, np.full(pad, -1)]).reshape(-1, tokens).max(1)
    most = -(-int(n_pages) * int(page_size) // block)
    return int(np.clip(pos // block + 1, 0, most).sum()) * block


def _latent_paged_kernel(bt_ref, last_ref, pos_ref, q_ref, pool, o_ref, buf,
                         sem, acc_ref, m_ref, l_ref, *, page_size, d_value,
                         sm_scale, per_row, n_pages, block_pages):
    """One program's query rows (tokens x heads, every head of a token a row)
    against the pages one block table holds up to `last`, the program's
    largest position. Every row has the same key and value: a pooled row is
    the key, and its first d_value columns the value, of all of them, so a
    block is read once and met by two MXU products, rows x block and block x
    d_value. per_row (decode): the program is a slot, its table row
    bt[program]; shared (a prefill chunk): every program walks the one
    table. The walk, its double buffer and its safe softmax are
    _paged_flash_kernel's."""
    i = pl.program_id(0)
    last = last_ref[i]
    page_of = (lambda j: bt_ref[i, j]) if per_row else (lambda j: bt_ref[j])
    pos = pos_ref[...]  # (rows, 1)
    rows = q_ref.shape[0]
    block = block_pages * page_size
    n_blocks = jnp.clip(last // block + 1, 0, pl.cdiv(n_pages, block_pages))

    @pl.when(i == 0)
    def _once():  # what a page never copied leaves in a buffer stays finite
        buf[...] = jnp.zeros_like(buf)

    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
    l_ref[...] = jnp.zeros_like(l_ref)

    def page_copies(b, slot, start):
        first = b * block_pages
        n_live = jnp.clip(
            jnp.minimum(last // page_size + 1, n_pages) - first, 0, block_pages
        )

        def _page(j, _):
            row0 = pl.multiple_of(page_of(first + j) * page_size, page_size)
            copy = pltpu.make_async_copy(
                pool.at[pl.ds(row0, page_size)], buf.at[slot, j], sem.at[slot])
            copy.start() if start else copy.wait()

        jax.lax.fori_loop(0, n_live, _page, None)

    @pl.when(n_blocks > 0)
    def _first():
        page_copies(0, 0, True)

    def _block(b, _):
        slot = b % 2

        @pl.when(b + 1 < n_blocks)
        def _next():
            page_copies(b + 1, 1 - slot, True)

        page_copies(b, slot, False)
        k = buf[slot].reshape(block, buf.shape[-1])
        s = jax.lax.dot_general(
            q_ref[...], k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        live = b * block + jax.lax.broadcasted_iota(
            jnp.int32, (rows, block), 1) <= pos
        s = jnp.where(live, s, -jnp.inf)
        m_prev, l_prev = m_ref[:, :1], l_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        # exp(-inf - -inf) = nan: a row that has seen nothing keeps alpha 0
        alpha = jnp.exp(jnp.where(m_prev == -jnp.inf, -jnp.inf, m_prev - m_new))
        p = jnp.where(live, jnp.exp(s - m_new), 0.0)
        l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p.astype(k.dtype), k[:, :d_value],
            preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    jax.lax.fori_loop(0, n_blocks, _block, None)
    l = l_ref[:, :1]  # a fully-masked row (pos < 0) emits zeros, not 0 / 0
    o_ref[...] = (acc_ref[...] / jnp.where(l > 0.0, l, 1.0)).astype(o_ref.dtype)


def latent_paged_attention(q, pool, block_table, pos, *, n_head, page_size,
                           d_value, sm_scale, interpret=None):
    """Attention of n_head query heads over ONE paged pool whose row serves
    every head as its key (all of its columns) and as its value (the first
    d_value): latent attention in its absorbed form, where the pool holds
    [c_kv | k_rope (| zero padding to whole lane tiles)] a position and q is
    [rows, n_head * width], a head's [q_nope @ W_uk | q_rope (| zeros)].
    Returns [rows, n_head * d_value] in q's dtype: each head's softmax-
    weighted sum of c_kv, for the caller to take through W_uv. block_table,
    pos and the masking are paged_flash_attention's: [rows, P] (decode) or
    [P] (a prefill chunk); row r attends 0..pos[r]; pos < 0 emits zeros. A
    pooled row is read once a program whatever n_head: a decode program is
    one slot, a chunk's program _LATENT_CHUNK_TOKENS of its rows.
    latent_positions_walked mirrors the walk."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    rows, feat = q.shape
    width = feat // n_head
    bt = block_table.astype(jnp.int32)
    pos_v = pos.reshape(-1).astype(jnp.int32)
    per_row = bt.ndim == 2
    n_pages = bt.shape[-1]
    block_pages = _latent_block_pages(n_pages, page_size, not per_row)
    tokens = 1 if per_row else min(rows, _LATENT_CHUNK_TOKENS)
    n_prog = pl.cdiv(rows, tokens)
    if n_prog * tokens > rows:  # rows of no token: masked whole
        q = jnp.pad(q, ((0, n_prog * tokens - rows), (0, 0)))
        pos_v = jnp.pad(pos_v, (0, n_prog * tokens - rows), constant_values=-1)
    own = tokens * n_head  # a program's query rows
    _note_dispatch("paged_latent")
    mine = lambda i, bt_r, last_r: (i, 0)
    out = pl.pallas_call(
        functools.partial(
            _latent_paged_kernel, page_size=page_size, d_value=d_value,
            sm_scale=float(sm_scale), per_row=per_row, n_pages=n_pages,
            block_pages=block_pages,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n_prog,),
            in_specs=[
                pl.BlockSpec((own, 1), mine),
                pl.BlockSpec((own, width), mine),
                pl.BlockSpec(memory_space=pltpu.HBM),
            ],
            out_specs=pl.BlockSpec((own, d_value), mine),
            scratch_shapes=[
                pltpu.VMEM((2, block_pages, page_size, width), pool.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.VMEM((own, d_value), jnp.float32),  # acc
                pltpu.VMEM((own, _LANES), jnp.float32),  # m
                pltpu.VMEM((own, _LANES), jnp.float32),  # l
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((n_prog * own, d_value), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_LATENT_VMEM_BYTES,
        ),
        interpret=interpret,
    )(
        bt, pos_v.reshape(n_prog, tokens).max(axis=1),
        jnp.repeat(pos_v, n_head).reshape(-1, 1),
        q.astype(pool.dtype).reshape(n_prog * own, width), pool,
    )
    return out[: rows * n_head].reshape(rows, n_head * d_value)


# ---------------------------------------------------------------------------
# the lightning indexer's scan (dsa_index): every index key a row's block
# table holds up to its position, scored by the row's index heads
# ---------------------------------------------------------------------------

# Positions in one compute block of the scan, and the tokens of a prefill
# chunk one program owns (x heads query rows: 16 x 32 = 512 MXU rows against
# 512 positions). A decode program owns one token, as the latent walk's does.
_INDEX_BLOCK_POSITIONS = 512
_INDEX_CHUNK_TOKENS = 16
_INDEX_VMEM_BYTES = 64 * 1024 * 1024


def dsa_index_path_taken(n_q, n_pages, page_size, width):
    """EXACT mirror of the dsa_index lowering's kernel-vs-dense decision,
    under the paged_flash flag as latent_paged_path_taken: "auto" takes the
    kernel on a TPU when a page is whole sublane tiles of bfloat16 and an
    index key whole lane tiles."""
    from .. import flags as _flags

    mode = _flags.get_flags("paged_flash")["paged_flash"]
    if mode == "off":
        return False
    if min(int(n_q), int(n_pages), int(page_size), int(width)) < 1:
        return False
    if mode == "on":
        return True
    return (jax.default_backend() == "tpu" and int(page_size) % 16 == 0
            and int(width) % _LANES == 0)


def _index_block_pages(n_pages, page_size):
    return max(1, min(_INDEX_BLOCK_POSITIONS // int(page_size), int(n_pages)))


def index_positions_walked(pos, page_size, n_pages):
    """EXACT mirror of dsa_index_scores' trip count for decode rows, in
    positions, as latent_positions_walked is of the latent walk's: each row
    takes pos // block + 1 blocks, none for pos < 0."""
    block = int(page_size) * _index_block_pages(n_pages, page_size)
    pos = np.asarray(pos, dtype=np.int64).reshape(-1)
    most = -(-int(n_pages) * int(page_size) // block)
    return int(np.clip(pos // block + 1, 0, most).sum()) * block


def index_rows_scanned(tables, pos, page_size):
    """The distinct index-key pool rows a decode step's scan has to read:
    every position up to each row's own through its table row (tables
    [rows, pages], pos [rows]); a pool row that several rows' tables hold (a
    document's pages) counts once, the scratch page (0) and a row at pos < 0
    never. What the work requires, for serve.dsa_index_roofline, where
    index_positions_walked is what the kernel walks."""
    pos = np.asarray(pos, dtype=np.int64).reshape(-1)
    tables = np.asarray(tables, dtype=np.int64).reshape(pos.size, -1)
    first = np.arange(tables.shape[1], dtype=np.int64) * int(page_size)
    used = np.clip(pos[:, None] - first[None, :] + 1, 0, int(page_size))
    read = (tables > 0) & (used > 0)
    if not read.any():
        return 0
    most = np.zeros(int(tables.max()) + 1, np.int64)
    np.maximum.at(most, tables[read], used[read])
    return int(most.sum())


def _dsa_index_kernel(bt_ref, last_ref, pos_ref, q_ref, w_ref, pool, o_ref,
                      buf, sem, *, page_size, n_pages, block_pages, per_row,
                      heads):
    """One program's tokens (heads query rows each) against the index keys
    one block table holds up to `last`, the program's largest position: a
    block of keys is read once and met by one MXU product, rows x block; the
    ReLU, each head's weight and the sum over a token's heads stay on the
    VPU in float32, so no [tokens, heads, positions] array leaves the
    kernel. o_ref is (blocks, tokens, block): a block's scores where the
    position is at most the token's own, -inf everywhere else. The walk and
    its double buffer are _latent_paged_kernel's."""
    i = pl.program_id(0)
    last = last_ref[i]
    page_of = (lambda j: bt_ref[i, j]) if per_row else (lambda j: bt_ref[j])
    pos = pos_ref[...]  # (tokens, 1)
    tokens = pos.shape[0]
    block = block_pages * page_size
    n_blocks = jnp.clip(last // block + 1, 0, pl.cdiv(n_pages, block_pages))

    @pl.when(i == 0)
    def _once():  # what a page never copied leaves in a buffer stays finite
        buf[...] = jnp.zeros_like(buf)

    o_ref[...] = jnp.full(o_ref.shape, -jnp.inf, o_ref.dtype)

    def page_copies(b, slot, start):
        first = b * block_pages
        n_live = jnp.clip(
            jnp.minimum(last // page_size + 1, n_pages) - first, 0, block_pages
        )

        def _page(j, _):
            row0 = pl.multiple_of(page_of(first + j) * page_size, page_size)
            copy = pltpu.make_async_copy(
                pool.at[pl.ds(row0, page_size)], buf.at[slot, j], sem.at[slot])
            copy.start() if start else copy.wait()

        jax.lax.fori_loop(0, n_live, _page, None)

    @pl.when(n_blocks > 0)
    def _first():
        page_copies(0, 0, True)

    def _block(b, _):
        slot = b % 2

        @pl.when(b + 1 < n_blocks)
        def _next():
            page_copies(b + 1, 1 - slot, True)

        page_copies(b, slot, False)
        k = buf[slot].reshape(block, buf.shape[-1])
        s = jax.lax.dot_general(
            q_ref[...], k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        s = (jnp.maximum(s, 0.0) * w_ref[...]).reshape(tokens, heads, block)
        live = b * block + jax.lax.broadcasted_iota(
            jnp.int32, (tokens, block), 1) <= pos
        o_ref[b] = jnp.where(live, jnp.sum(s, axis=1), -jnp.inf)

    jax.lax.fori_loop(0, n_blocks, _block, None)


def dsa_index_scores(q, w, pool, block_table, pos, *, heads, page_size,
                     interpret=None):
    """The lightning indexer's scores of every position a row's table holds:
    q [rows, heads * width] (each head's query, rotated), w [rows, heads]
    float32 (each head's weight, its scales folded in), pool [pool_rows,
    width] (one index key a position, shared by every head). Returns
    [rows, n_pages * page_size] float32: at position c of row r, sum_j
    w[r, j] * ReLU(q[r, j] . key(c)) where c <= pos[r], -inf elsewhere (a
    row with pos < 0: -inf whole). block_table is [rows, P] (decode) or [P]
    (a prefill chunk), as latent_paged_attention's; a page wholly past a
    program's largest position is never copied."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    rows, feat = q.shape
    width = feat // heads
    bt = block_table.astype(jnp.int32)
    pos_v = pos.reshape(-1).astype(jnp.int32)
    w = w.astype(jnp.float32)
    per_row = bt.ndim == 2
    n_pages = bt.shape[-1]
    block_pages = _index_block_pages(n_pages, page_size)
    block = block_pages * page_size
    n_blk = pl.cdiv(n_pages, block_pages)
    tokens = 1 if per_row else min(rows, _INDEX_CHUNK_TOKENS)
    n_prog = pl.cdiv(rows, tokens)
    if n_prog * tokens > rows:  # rows of no token: masked whole
        pad = n_prog * tokens - rows
        q = jnp.pad(q, ((0, pad), (0, 0)))
        w = jnp.pad(w, ((0, pad), (0, 0)))
        pos_v = jnp.pad(pos_v, (0, pad), constant_values=-1)
    own = tokens * heads  # a program's query rows
    _note_dispatch("dsa_index")
    mine = lambda i, bt_r, last_r: (i, 0, 0)
    out = pl.pallas_call(
        functools.partial(
            _dsa_index_kernel, page_size=page_size, n_pages=n_pages,
            block_pages=block_pages, per_row=per_row, heads=heads,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n_prog,),
            in_specs=[
                pl.BlockSpec((None, tokens, 1), mine),
                pl.BlockSpec((None, own, width), mine),
                pl.BlockSpec((None, own, 1), mine),
                pl.BlockSpec(memory_space=pltpu.HBM),
            ],
            out_specs=pl.BlockSpec(
                (None, n_blk, tokens, block), lambda i, bt_r, last_r: (i, 0, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, block_pages, page_size, width), pool.dtype),
                pltpu.SemaphoreType.DMA((2,)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(
            (n_prog, n_blk, tokens, block), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_INDEX_VMEM_BYTES,
        ),
        interpret=interpret,
    )(
        bt, pos_v.reshape(n_prog, tokens).max(axis=1),
        pos_v.reshape(n_prog, tokens, 1),
        q.astype(pool.dtype).reshape(n_prog, own, width),
        w.reshape(n_prog, own, 1), pool,
    )
    out = out.transpose(0, 2, 1, 3).reshape(n_prog * tokens, n_blk * block)
    return out[:rows, : n_pages * page_size]


# ---------------------------------------------------------------------------
# hyper-connection maps (hyper_connection): the sigmoids and the Sinkhorn
# rounds of a residual stream's n copies, one kernel
# ---------------------------------------------------------------------------


def hyper_maps_path_taken():
    """EXACT mirror of hyper_connection's kernel-vs-XLA decision: the kernel
    on a TPU. The same rounds in jax.numpy are right everywhere and compact,
    but on the chip each round's two sums are two tiny kernels (1600 a
    decode step of 20 layers), and written out entry by entry so that XLA
    fuses them they are 2,500 instructions a connection: 100k a program,
    250 s of compilation a variant (my chip run, PR 31)."""
    return jax.default_backend() == "tpu"


def _hyper_maps_kernel(raw_ref, out_ref, *, n, iters, eps, lo, hi):
    """raw_ref, out_ref: (2n + n * n, rows) float32, a row of the block one
    entry of the maps for every token (tokens on the lanes): n sigmoids, n
    doubled sigmoids, then exp(clip(.)) of the n x n entries through `iters`
    Sinkhorn rounds. Row j of a token's matrix is the block's n rows from
    2n + j * n, its entries on the sublanes: a row's sum is a sublane
    reduction, the columns' sums the n blocks added."""
    out_ref[0:n, :] = jax.nn.sigmoid(raw_ref[0:n, :])
    out_ref[n:2 * n, :] = 2.0 * jax.nn.sigmoid(raw_ref[n:2 * n, :])
    at = lambda j: slice(2 * n + j * n, 2 * n + (j + 1) * n)
    m = tuple(jnp.exp(jnp.clip(raw_ref[at(j), :], lo, hi)) for j in range(n))

    def one_round(_, m):
        m = tuple(r / (jnp.sum(r, axis=0, keepdims=True) + eps) for r in m)
        cols = sum(m[1:], m[0]) + eps
        return tuple(r / cols for r in m)

    m = jax.lax.fori_loop(0, iters, one_round, m)
    for j in range(n):
        out_ref[at(j), :] = m[j]


def hyper_maps(raw, n, iters, eps, clamp, interpret=None):
    """The three maps of a hyper-connection from their pre-activations raw
    [rows, 2n + n * n] float32 ([pre | post | res row-major]): [sigmoid |
    2 sigmoid | Sinkhorn(exp(clip(res, *clamp)), iters, eps)], the same
    shape. One program holds every row: the arrays are a few KB."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    rows, cols = raw.shape
    pad = -rows % _LANES  # tokens on the lanes, whole tiles of them
    raw_t = jnp.pad(raw.astype(jnp.float32).T, ((0, 0), (0, pad)))
    _note_dispatch("hyper_maps")
    out = pl.pallas_call(
        functools.partial(
            _hyper_maps_kernel, n=int(n), iters=int(iters), eps=float(eps),
            lo=float(clamp[0]), hi=float(clamp[1])),
        out_shape=jax.ShapeDtypeStruct((cols, rows + pad), jnp.float32),
        interpret=interpret,
    )(raw_t)
    return out[:, :rows].T


# ---------------------------------------------------------------------------
# state-space decode step (ssm_scan, mode "step"): the live rows of the
# float32 state pool updated in place
# ---------------------------------------------------------------------------

# The lanes of a state row one program holds: 16 heads of 64 at the widths
# this was written for, [128, 1024] float32 = 512 KB a block, two blocks in
# and two out in VMEM. A row is [d_state, heads * d_head], the heads' entries
# on the lanes, so the decay and the input are lane-dense rows broadcast down
# the sublanes and the output's sum over d_state is adds of whole vregs.
_SSM_BLOCK_LANES = 1024
_SSM_VMEM_BYTES = 32 * 1024 * 1024


def ssm_step_path_taken(hp, n, groups):
    """EXACT mirror of ssm_scan's kernel-vs-XLA decision for its decode
    step: the kernel on a TPU when a state row is whole float32 tiles
    (heads * d_head a multiple of 128 lanes, d_state of 8 sublanes) and B
    and C are one group's. XLA's gather, update and scatter move every row
    of the step three times over; the kernel moves the live rows once each
    way."""
    return (jax.default_backend() == "tpu" and int(hp) % _LANES == 0
            and int(n) % 8 == 0 and int(groups) == 1)


def _ssm_step_kernel(rows_ref, order_ref, live_ref, xdt_ref, decay_ref, b_ref,
                     c_ref, h_ref, y_ref, o_ref):
    """One block of lanes of one slot's row: h' = decay * h + b (outer) xdt,
    y = sum over d_state of c * h'. The grid walks the slots live ones
    first; programs past them keep the last live block's indices, so the
    pipeline copies nothing for them and their body leaves the pool's block
    alone: it still holds that block's result when it is written back,
    once."""
    i = pl.program_id(0)
    n_live = live_ref[0]
    n = h_ref.shape[1]
    # a [1, n] row as an [n, 1] column: its diagonal summed over the lanes
    eye = (jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (n, n), 1))
    column = lambda row: jnp.sum(
        jnp.where(eye, jnp.broadcast_to(row, (n, n)), 0.0), axis=1,
        keepdims=True)

    @pl.when(i < n_live)
    def _update():
        new = h_ref[0] * decay_ref[0] + column(b_ref[0]) * xdt_ref[0]
        o_ref[0] = new
        y_ref[0] = jnp.sum(new * column(c_ref[0]), axis=0, keepdims=True)

    @pl.when(i >= n_live)
    def _idle():
        y_ref[...] = jnp.zeros_like(y_ref)

    @pl.when(jnp.logical_and(n_live == 0, i == 0))
    def _scratch():  # no live row: the scratch row is written as it was read
        o_ref[...] = h_ref[...]


def ssm_state_step(state, rows, xdt, decay, b, c, *, interpret=None):
    """One decode step of the state-space recurrence over a pool of state
    rows, in place. state [state_rows, n, hp] float32, a row the state of
    one sequence (n = d_state, hp = heads * d_head); rows [slots] int32 each
    slot's state row, 0 for a slot that is not live; xdt [slots, hp] = dt *
    x, decay [slots, hp] = exp(dt * A) a head, repeated over its d_head, b
    and c [slots, n], all float32. For every live slot s

        state[rows[s]] <- decay[s] * state[rows[s]] + b[s] (outer) xdt[s]
        y[s] = sum_n c[s, n] * state[rows[s]][n]          (the new state)

    and y[s] = 0 for the others. Returns (y [slots, hp], the pool): the
    pool is aliased to the input, so where the caller donates it (the
    serving variants do) nothing is copied, and only the live rows are read
    and written: the grid takes the live slots first (`order`, a scalar
    table like the rows') and the programs behind them stay on the last
    live block."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    slots, hp = xdt.shape
    n = b.shape[1]
    lanes = min(_SSM_BLOCK_LANES, hp)
    if hp % lanes:
        lanes = _LANES
    n_blocks = hp // lanes
    rows = rows.reshape(-1).astype(jnp.int32)
    on = rows != 0
    order = jnp.argsort(jnp.logical_not(on), stable=True).astype(jnp.int32)
    n_live = jnp.sum(on.astype(jnp.int32))
    compact = jnp.take(rows, order)
    # behind the live rows: the last live row again (the scratch row, with
    # none), so that its block is neither fetched nor written a second time
    compact = jnp.where(jnp.arange(slots) < n_live, compact,
                        compact[jnp.maximum(n_live - 1, 0)])
    f32 = lambda m: m.astype(jnp.float32)[:, None, :]

    def pooled(i, j, rows_r, order_r, live_r):
        return rows_r[i], 0, jnp.where(i >= live_r[0], n_blocks - 1, j)

    mine = lambda i, j, rows_r, order_r, live_r: (order_r[i], 0, j)
    whole = lambda i, j, rows_r, order_r, live_r: (order_r[i], 0, 0)
    _note_dispatch("ssm_step")
    y, pool = pl.pallas_call(
        _ssm_step_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(slots, n_blocks),
            in_specs=[
                pl.BlockSpec((1, 1, lanes), mine),  # xdt
                pl.BlockSpec((1, 1, lanes), mine),  # decay
                pl.BlockSpec((1, 1, n), whole),  # b
                pl.BlockSpec((1, 1, n), whole),  # c
                pl.BlockSpec((1, n, lanes), pooled),  # the pool
            ],
            out_specs=[
                pl.BlockSpec((1, 1, lanes), mine),
                pl.BlockSpec((1, n, lanes), pooled),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((slots, 1, hp), jnp.float32),
            jax.ShapeDtypeStruct(state.shape, jnp.float32),
        ],
        # operands: rows, order, n_live, xdt, decay, b, c, pool -> output 1
        input_output_aliases={7: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_SSM_VMEM_BYTES,
        ),
        interpret=interpret,
    )(compact, order, n_live.reshape(1), f32(xdt), f32(decay), f32(b), f32(c),
      state)
    return y[:, 0, :], pool


# ---------------------------------------------------------------------------
# grouped expert feed-forward (moe_experts): rows sorted by expert, each
# touched expert's three matrices streamed through VMEM once
# ---------------------------------------------------------------------------

# Swept on chip (v5e, jax 0.9.0, PR 28) at lfm2moe_chat's widths: 32 experts
# of 2048 x 1792, bf16; us a call, three ragged_dot calls first. A decode step
# of 19 live rows x 4 picks (30 experts touched): 1571 -> 907-922 at every
# block_f of 256 / 896 / 1792 and every window of 16 / 32 / 64 / 128 rows
# (725 GB/s of the touched experts' bytes, 88 % of 819: the copies bound it
# and the MXU hides behind them whatever the window); 32 live rows (31
# experts) 1618 -> 936-948; 8 (19 experts) 1003 -> 590-603. A 128-row chunk
# (512 sorted rows, 32 experts): 2798 -> 974 as one 512-row tile, 1009 as four
# of 128 (35 expert reads). So the smallest tiles that tie: less VMEM, less
# MXU work.
#
# The sorted rows one row tile holds in VMEM beside their f32 result (a
# decode step brings 128 of them, a prefill chunk 512): every expert whose
# group lies in the tile is read once for the whole tile.
_MOE_BLOCK_ROWS = 512
# The rows one product takes: a group's rows are met a window at a time,
# from the group's offset rounded down to the sublane tile, the other
# groups' rows in the window masked out of `h`.
_MOE_ROW_WINDOW = 32
_MOE_ROW_ALIGN = 16  # a bf16 sublane tile; whole f32 tiles too
# what a call's two buffers of the three weight tiles may take of VMEM (128
# MiB on a v5e core: 256 of lfm2moe_chat's 1792 columns a tile), and what
# the call asks Mosaic for beyond its blocks
_MOE_WEIGHT_BUFFER_BYTES = 16 * 1024 * 1024
_MOE_VMEM_SLACK_BYTES = 16 * 1024 * 1024


def grouped_ffn_path_taken(d, f, x_dtype, w_dtype):
    """EXACT mirror of moe_experts' kernel-vs-ragged_dot decision: the
    kernel takes bf16 or f32 rows against weights of the same dtype whose
    model and expert widths are whole lane tiles; jax.lax.ragged_dot is the
    lowering for everything else. Off-TPU the kernel runs in interpret
    mode."""
    x_dtype, w_dtype = jnp.dtype(x_dtype), jnp.dtype(w_dtype)
    return (
        x_dtype == w_dtype
        and x_dtype in (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32))
        and min(int(d), int(f)) > 0
        and int(d) % _LANES == 0
        and int(f) % _LANES == 0
    )


def _moe_block_f(d, f, itemsize):
    """The widest tile of f, in whole lane tiles that divide f, whose three
    weight tiles fit their VMEM budget twice over."""
    lanes = f // _LANES
    for parts in range(1, lanes + 1):
        if lanes % parts == 0 and (
            6 * d * (f // parts) * itemsize <= _MOE_WEIGHT_BUFFER_BYTES
        ):
            return f // parts
    return _LANES


def _moe_visits(sizes, n_tiles, block_rows):
    """The (row tile, expert) pairs a call computes, in the order it walks
    them, from the groups' sizes [held]: offsets [held + 1] of the groups in
    the sorted rows, and the expert and the row tile of each of the
    held + n_tiles - 1 grid steps, the count of real ones last. An expert
    nobody picked is in no pair; the steps past the count repeat the last
    real pair, whose blocks are resident, so they fetch nothing."""
    n_held = sizes.shape[0]
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first = starts // block_rows
    tiles = jnp.where(sizes > 0, (ends - 1) // block_rows - first + 1, 0)
    visit_end = jnp.cumsum(tiles)
    count = visit_end[-1]
    step = jnp.clip(
        jnp.arange(n_held + n_tiles - 1, dtype=jnp.int32), 0, count - 1)
    expert = jnp.minimum(
        jnp.searchsorted(visit_end, step, side="right"), n_held - 1
    ).astype(jnp.int32)
    tile = first[expert] + step - (visit_end - tiles)[expert]
    offsets = jnp.concatenate([jnp.zeros(1, jnp.int32), ends])
    return (offsets.astype(jnp.int32), expert,
            jnp.clip(tile, 0, n_tiles - 1).astype(jnp.int32),
            count.reshape(1).astype(jnp.int32))


def _grouped_ffn_kernel(offs_ref, expert_ref, tile_ref, count_ref, x_ref,
                        w1_ref, w3_ref, w2_ref, o_ref, *, block_rows, window):
    """Grid (visit, tile of f). One visit is one expert's rows inside one row
    tile; o_ref, the tile's f32 result, stays resident over the tile's
    visits and takes each f tile's partial down-projection."""
    v, j = pl.program_id(0), pl.program_id(1)

    @pl.when(v < count_ref[0])
    def _():
        e, t = expert_ref[v], tile_ref[v]
        base = t * block_rows
        lo = jnp.maximum(offs_ref[e], base) - base
        hi = jnp.minimum(offs_ref[e + 1], base + block_rows) - base

        @pl.when((j == 0) & ((v == 0) | (tile_ref[jnp.maximum(v - 1, 0)] != t)))
        def _():
            o_ref[...] = jnp.zeros_like(o_ref)

        start = lo // _MOE_ROW_ALIGN * _MOE_ROW_ALIGN

        def one_window(i, carry):
            want = start + i * window
            at = pl.multiple_of(
                jnp.minimum(want, block_rows - window), _MOE_ROW_ALIGN)
            row = at + jax.lax.broadcasted_iota(jnp.int32, (window, 1), 0)
            x = x_ref[pl.ds(at, window), :]
            up = lambda w_ref: jnp.dot(
                x, w_ref[...], preferred_element_type=jnp.float32)
            h = (jax.nn.silu(up(w1_ref)) * up(w3_ref)).astype(x.dtype)
            # the window's rows of other groups, and what a window pushed
            # back from the tile's end shares with the one before it
            h = jnp.where((row >= jnp.maximum(lo, want)) & (row < hi), h, 0)
            o_ref[pl.ds(at, window), :] += jnp.dot(
                h, w2_ref[...], preferred_element_type=jnp.float32)
            return carry

        jax.lax.fori_loop(0, pl.cdiv(hi - start, window), one_window, 0)


def grouped_expert_ffn(xs, w1, w3, w2, sizes, *, block_rows=None,
                       block_f=None, row_window=None, interpret=None):
    """y[r] = (silu(xs[r] @ W1[e]) * (xs[r] @ W3[e])) @ W2[e] in float32 for
    rows sorted by the expert e that takes them: xs [rows, d], w1 / w3
    [held, d, f], w2 [held, f, d], sizes [held] the groups' row counts in
    order. Rows past sum(sizes) belong to no expert: their result is
    unspecified (zero inside a row tile some group reaches, unwritten
    elsewhere) and the caller drops it. Every product accumulates in float32
    and h is rounded once, to xs' dtype, as three ragged_dot calls would.

    Each expert with rows is read from HBM once a row tile it reaches, in
    (d, block_f) and (block_f, d) tiles that the grid's pipeline double
    buffers; an expert without rows is never fetched (its grid steps name the
    blocks already resident). xs' tile and its f32 result stay in VMEM for
    the tile's visits and h never leaves it. The MXU work follows the groups:
    row_window rows a product."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    rows, d = xs.shape
    n_held, _, f = w1.shape
    itemsize = xs.dtype.itemsize
    tile_rows = -(-max(rows, 1) // _MOE_ROW_ALIGN) * _MOE_ROW_ALIGN
    block_rows = int(block_rows or min(_MOE_BLOCK_ROWS, tile_rows))
    window = min(int(row_window or _MOE_ROW_WINDOW), block_rows)
    block_f = int(block_f or _moe_block_f(d, f, itemsize))
    n_tiles, f_tiles = pl.cdiv(rows, block_rows), f // block_f
    if rows < n_tiles * block_rows:
        xs = jnp.pad(xs, ((0, n_tiles * block_rows - rows), (0, 0)))
    prefetch = _moe_visits(sizes.astype(jnp.int32), n_tiles, block_rows)
    _note_dispatch("moe_grouped")

    def f_tile(v, j, count_r):
        # a step past the last real visit keeps the last step's tile
        return jnp.where(v < count_r[0], j, f_tiles - 1)

    up_spec = pl.BlockSpec(
        (None, d, block_f),
        lambda v, j, offs_r, e_r, t_r, n_r: (e_r[v], 0, f_tile(v, j, n_r)))
    down_spec = pl.BlockSpec(
        (None, block_f, d),
        lambda v, j, offs_r, e_r, t_r, n_r: (e_r[v], f_tile(v, j, n_r), 0))
    row_spec = pl.BlockSpec(
        (block_rows, d), lambda v, j, offs_r, e_r, t_r, n_r: (t_r[v], 0))
    vmem = (
        6 * d * block_f * itemsize  # two buffers of the three weight tiles
        + 2 * block_rows * d * (itemsize + 4)  # xs and the f32 result
        + _MOE_VMEM_SLACK_BYTES
    )
    out = pl.pallas_call(
        functools.partial(
            _grouped_ffn_kernel, block_rows=block_rows, window=window),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(n_held + n_tiles - 1, f_tiles),
            in_specs=[row_spec, up_spec, up_spec, down_spec],
            out_specs=row_spec,
        ),
        out_shape=jax.ShapeDtypeStruct(
            (n_tiles * block_rows, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem,
        ),
        interpret=interpret,
    )(*prefetch, xs, w1, w3, w2)
    return out[:rows]


# ---------------------------------------------------------------------------
# fused layer_norm(+residual): forward with one-pass Welford stats, explicit
# backward against the saved Mean/Variance — both f32 math rounded once
# ---------------------------------------------------------------------------

# 128 rows/block swept on chip at d=2048 bf16: 128 rows runs fwd+bwd at
# 412 GB/s effective (the op is bandwidth-bound) vs 397 at 256 rows (VMEM
# pressure starts evicting the double buffer) and 361 at 64 (grid overhead)
_DEF_LN_BLOCK_ROWS = 128
_LN_COL_CHUNK = 512  # Welford merge chunk width (lanes)
# conservative working-set roof: x/r/s/y native tiles + f32 stats temps per
# block must leave room for double buffering in ~16 MiB VMEM
_LN_VMEM_BUDGET = 12 * 1024 * 1024


def _ln_blocks(rows, cols, itemsize):
    """Row-block size for the fused layer_norm kernels, or 0 for shapes the
    kernel declines: the packed (1, rows) stats layout needs rows % 128 == 0
    (the flash lse rule — Mosaic cannot vector-store partial lanes), the
    Welford chunk sweep needs cols % 128 == 0, and the whole (block, cols)
    slab must sit in VMEM."""
    if rows <= 0 or cols <= 0 or rows % _LANES or cols % _LANES:
        return 0
    br = _auto_block(rows, _DEF_LN_BLOCK_ROWS)
    while br > 8 and br * cols * (4 * itemsize + 16) > _LN_VMEM_BUDGET:
        br //= 2
    if not br or br * cols * (4 * itemsize + 16) > _LN_VMEM_BUDGET:
        return 0
    return br


def ln_path_taken(rows, cols, itemsize=4):
    """EXACT mirror of the fused layer_norm pallas-vs-dense decision over the
    (lead, prod(shape[begin_norm_axis:])) view — see gemm_path_taken."""
    return _ln_blocks(rows, cols, itemsize) > 0


def _welford_cols(s32, cols, col_chunk):
    """One-pass Welford over the column axis of an f32 (rows, chunk-multiple)
    value, merging per-chunk moments with the parallel combination — numerics
    match jnp.mean/jnp.var to f32 rounding without the naive sum-of-squares
    cancellation at large |mean|. The chunks are static lane-aligned slices
    (a Python range): Mosaic has no dynamic_slice on a value."""
    rows = s32.shape[0]
    mean = jnp.zeros((rows,), jnp.float32)
    m2 = jnp.zeros((rows,), jnp.float32)
    for ci in range(cols // col_chunk):
        blk = s32[:, ci * col_chunk:(ci + 1) * col_chunk]
        bmean = jnp.mean(blk, axis=1)
        bm2 = jnp.sum(jnp.square(blk - bmean[:, None]), axis=1)
        count = ci * col_chunk
        tot = count + col_chunk
        delta = bmean - mean
        mean = mean + delta * (col_chunk / tot)
        m2 = m2 + bm2 + jnp.square(delta) * (count * col_chunk / tot)
    return mean, m2 / cols  # biased variance — the layer_norm contract


def _ln_fwd_kernel(x_ref, r_ref, scale_ref, bias_ref, s_ref, y_ref, mean_ref,
                   var_ref, *, eps, col_chunk):
    """One row block: residual add in the INPUT dtype (bit-matching the dense
    elementwise_add it replaces), Welford stats and normalization in f32,
    packed lane-major (1, rows) Mean/Variance residuals (the flash lse
    layout)."""
    ri = pl.program_id(0)
    block_rows, cols = x_ref.shape
    if r_ref is not None:
        s = x_ref[...] + r_ref[...]
        s_ref[...] = s
    else:
        s = x_ref[...]
    s32 = s.astype(jnp.float32)
    mean, var = _welford_cols(s32, cols, col_chunk)
    y = (s32 - mean[:, None]) * jax.lax.rsqrt(var[:, None] + eps)
    y = y * scale_ref[...].astype(jnp.float32) + bias_ref[...].astype(
        jnp.float32
    )
    y_ref[...] = y.astype(y_ref.dtype)
    mean_ref[0, pl.ds(ri * block_rows, block_rows)] = mean
    var_ref[0, pl.ds(ri * block_rows, block_rows)] = var


def _ln_fwd_no_residual_adapter(kernel, x_ref, scale_ref, bias_ref, y_ref,
                                mean_ref, var_ref):
    kernel(x_ref, None, scale_ref, bias_ref, None, y_ref, mean_ref, var_ref)


def fused_layer_norm(x2, residual2, scale, bias, eps, *, interpret=None):
    """layer_norm(x2 [+ residual2]) over the (rows, cols) view. Returns
    (s, y, mean, var): s = x2 + residual2 in the input dtype (None when no
    residual), y the normalized output in the input dtype, mean/var the f32
    per-row stats (biased variance). scale/bias of None behave as ones/zeros.
    Shapes the kernel declines (ln_path_taken False) fall back to the dense
    f32 form with identical outputs."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    rows, cols = x2.shape
    scale_row = (
        jnp.ones((1, cols), jnp.float32)
        if scale is None
        else scale.reshape(1, cols)
    )
    bias_row = (
        jnp.zeros((1, cols), jnp.float32)
        if bias is None
        else bias.reshape(1, cols)
    )
    br = _ln_blocks(rows, cols, x2.dtype.itemsize)
    if not br:
        s = None if residual2 is None else x2 + residual2
        base = x2 if s is None else s
        b32 = base.astype(jnp.float32)
        mean = jnp.mean(b32, axis=1)
        var = jnp.var(b32, axis=1)
        y = (b32 - mean[:, None]) * jax.lax.rsqrt(var[:, None] + eps)
        y = y * scale_row.astype(jnp.float32) + bias_row.astype(jnp.float32)
        return s, y.astype(x2.dtype), mean, var
    col_chunk = _auto_block(cols, _LN_COL_CHUNK)
    kernel = functools.partial(
        _ln_fwd_kernel, eps=eps, col_chunk=col_chunk
    )
    row_spec = pl.BlockSpec((br, cols), lambda ri: (ri, 0))
    cvec_spec = pl.BlockSpec((1, cols), lambda ri: (0, 0))
    stat_spec = pl.BlockSpec((1, rows), lambda ri: (0, 0))
    stat_shape = jax.ShapeDtypeStruct((1, rows), jnp.float32)
    if residual2 is None:
        y, mean, var = pl.pallas_call(
            functools.partial(_ln_fwd_no_residual_adapter, kernel),
            grid=(rows // br,),
            in_specs=[row_spec, cvec_spec, cvec_spec],
            out_specs=[row_spec, stat_spec, stat_spec],
            out_shape=[
                jax.ShapeDtypeStruct((rows, cols), x2.dtype),
                stat_shape,
                stat_shape,
            ],
            interpret=interpret,
        )(x2, scale_row, bias_row)
        return None, y, mean.reshape(rows), var.reshape(rows)
    s, y, mean, var = pl.pallas_call(
        kernel,
        grid=(rows // br,),
        in_specs=[row_spec, row_spec, cvec_spec, cvec_spec],
        out_specs=[row_spec, row_spec, stat_spec, stat_spec],
        out_shape=[
            jax.ShapeDtypeStruct((rows, cols), x2.dtype),
            jax.ShapeDtypeStruct((rows, cols), x2.dtype),
            stat_shape,
            stat_shape,
        ],
        interpret=interpret,
    )(x2, residual2, scale_row, bias_row)
    return s, y, mean.reshape(rows), var.reshape(rows)


def _ln_bwd_kernel(x_ref, scale_ref, mean_ref, var_ref, dy_ref, dx_ref,
                   ds_ref, db_ref, *, eps):
    """One row block of the layer_norm backward against the SAVED stats:
    dx = rstd * (dxhat - mean(dxhat) - xhat * mean(dxhat * xhat)) in f32;
    dscale/dbias accumulate across the sequential grid into (1, cols) f32
    output blocks (constant index_map -> the block stays resident)."""
    ri = pl.program_id(0)
    block_rows = x_ref.shape[0]
    x = x_ref[...].astype(jnp.float32)
    dy = dy_ref[...].astype(jnp.float32)
    scale = scale_ref[...].astype(jnp.float32)
    mu = mean_ref[0, pl.ds(ri * block_rows, block_rows)]
    var = var_ref[0, pl.ds(ri * block_rows, block_rows)]
    rstd = jax.lax.rsqrt(var + eps)
    xhat = (x - mu[:, None]) * rstd[:, None]
    dxh = dy * scale
    c1 = jnp.mean(dxh, axis=1)
    c2 = jnp.mean(dxh * xhat, axis=1)
    dx_ref[...] = (
        rstd[:, None] * (dxh - c1[:, None] - xhat * c2[:, None])
    ).astype(dx_ref.dtype)

    @pl.when(ri == 0)
    def _init():
        ds_ref[...] = jnp.zeros_like(ds_ref)
        db_ref[...] = jnp.zeros_like(db_ref)

    ds_ref[...] += jnp.sum(dy * xhat, axis=0, keepdims=True)
    db_ref[...] += jnp.sum(dy, axis=0, keepdims=True)


def fused_layer_norm_grad(x2, scale, mean, var, dy2, eps, *, interpret=None):
    """Backward of the fused layer_norm over the (rows, cols) view. Returns
    (dx, dscale, dbias) with dx in x2's dtype and dscale/dbias as (cols,)
    f32 partials (caller casts to the param dtypes). scale of None behaves
    as ones. Declined shapes fall back to a dense f32 form with the same
    formula."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    rows, cols = x2.shape
    scale_row = (
        jnp.ones((1, cols), jnp.float32)
        if scale is None
        else scale.reshape(1, cols)
    )
    br = _ln_blocks(rows, cols, x2.dtype.itemsize)
    if not br:
        x32 = x2.astype(jnp.float32)
        dy32 = dy2.astype(jnp.float32)
        rstd = jax.lax.rsqrt(var + eps)
        xhat = (x32 - mean[:, None]) * rstd[:, None]
        dxh = dy32 * scale_row.astype(jnp.float32)
        c1 = jnp.mean(dxh, axis=1)
        c2 = jnp.mean(dxh * xhat, axis=1)
        dx = (rstd[:, None] * (dxh - c1[:, None] - xhat * c2[:, None])).astype(
            x2.dtype
        )
        return dx, jnp.sum(dy32 * xhat, axis=0), jnp.sum(dy32, axis=0)
    row_spec = pl.BlockSpec((br, cols), lambda ri: (ri, 0))
    cvec_spec = pl.BlockSpec((1, cols), lambda ri: (0, 0))
    stat_spec = pl.BlockSpec((1, rows), lambda ri: (0, 0))
    dx, ds, db = pl.pallas_call(
        functools.partial(_ln_bwd_kernel, eps=eps),
        grid=(rows // br,),
        in_specs=[row_spec, cvec_spec, stat_spec, stat_spec, row_spec],
        out_specs=[row_spec, cvec_spec, cvec_spec],
        out_shape=[
            jax.ShapeDtypeStruct((rows, cols), x2.dtype),
            jax.ShapeDtypeStruct((1, cols), jnp.float32),
            jax.ShapeDtypeStruct((1, cols), jnp.float32),
        ],
        interpret=interpret,
    )(x2, scale_row, mean.reshape(1, rows), var.reshape(1, rows), dy2)
    return dx, ds.reshape(cols), db.reshape(cols)


# ---------------------------------------------------------------------------
# fused lowerings: registry.lower_ops hands tagged runs here; every path that
# cannot reproduce the per-op semantics returns False (per-op fallback)
# ---------------------------------------------------------------------------


class _Shape2:
    __slots__ = ("shape", "ndim")

    def __init__(self, shape):
        self.shape = tuple(shape)
        self.ndim = len(self.shape)


def _mesh_declines(ctx, ops):
    """True when a tagged run must decline to per-op lowering, where GSPMD
    partitions op by op, because of the mesh it compiles for. Two reasons:

    - Mosaic kernels for a mesh of more than one device: GSPMD refuses them
      ("Mosaic kernels cannot be automatically partitioned. Please wrap the
      call in a shard_map"). Interpret mode — the CPU test mesh — lowers to
      plain XLA ops and is not affected. (The flash ops have no per-op form
      worth declining to and run under shard_map: _flash_shard_map.)
    - the declarative rule engine (ctx.sharding, a
      parallel.sharding_rules.Resolver) places any of the run's operands or
      results on this mesh. The tiled/flattened kernels assume whole local
      tensors — a tp-sharded weight or fsdp-sharded param would be gathered
      around an opaque pallas_call, defeating the placement."""
    mesh = getattr(ctx, "mesh", None)
    if mesh is not None and mesh.size > 1 and jax.default_backend() == "tpu":
        return True
    sharding = getattr(ctx, "sharding", None)
    if sharding is None:
        return False
    for op in ops:
        for name in list(op.input_arg_names) + list(op.output_arg_names):
            if name and sharding.rule_spec(name) is not None:
                return True
    return False


def _gemm_chain_views(prod, x, w):
    """2-D (m,k)/(k,n) views of the producer's operands plus the full output
    shape, or None when the op form is outside the kernel's contract."""
    if prod.type in ("mul", "int8_mul"):
        xnc = int(prod.attrs.get("x_num_col_dims", 1))
        ync = int(prod.attrs.get("y_num_col_dims", 1))
        m = int(np.prod(x.shape[:xnc], dtype=np.int64)) if xnc else 1
        kx = x.size // max(m, 1)
        kw = int(np.prod(w.shape[:ync], dtype=np.int64)) if ync else 1
        n = w.size // max(kw, 1)
        out_shape = tuple(x.shape[:xnc]) + tuple(w.shape[ync:])
        split = xnc
    else:  # matmul
        if prod.attrs.get("transpose_X", False) or prod.attrs.get(
            "transpose_Y", False
        ):
            return None
        if float(prod.attrs.get("alpha", 1.0)) != 1.0:
            return None
        if x.ndim != 2 or w.ndim != 2:
            return None
        m, kx = x.shape
        kw, n = w.shape
        out_shape = (m, n)
        split = 1
    if kx != kw or m <= 0 or n <= 0 or kx <= 0:
        return None
    return m, n, kx, out_shape, split


@register_fused("gemm_epilogue")
def _fused_gemm_epilogue(ctx, ops, env):
    """mul|matmul -> elementwise_add [-> act] through gemm_bias_act. The
    intermediate env entries stay live for OTHER consumers: the producer's
    Out is rebuilt as z - bias (grad ops list it as an input but the vjp
    replay never reads its value, so XLA DCEs the subtraction when unused)
    and the add's Out is the kernel's exact pre-activation z (gelu_grad's
    replay input)."""
    if len(ops) not in (2, 3) or ops[0].type not in ("mul", "matmul"):
        return False
    if _mesh_declines(ctx, ops):
        return False
    prod, add = ops[0], ops[1]
    act_op = ops[2] if len(ops) == 3 else None
    if add.type != "elementwise_add":
        return False
    if act_op is not None and act_op.type not in _GEMM_ACT_F32:
        return False
    if (
        add.input("X")[0] != prod.output("Out")[0]
        or (act_op is not None and act_op.input("X")[0] != add.output("Out")[0])
    ):
        return False
    x = env.get(prod.input("X")[0])
    w = env.get(prod.input("Y")[0])
    bias = env.get(add.input("Y")[0])
    if x is None or w is None or bias is None:
        return False
    if x.dtype != w.dtype or not jnp.issubdtype(x.dtype, jnp.floating):
        return False
    views = _gemm_chain_views(prod, x, w)
    if views is None:
        return False
    m, n, k, out_shape, split = views
    if not gemm_path_taken(m, n, k):
        return False
    bview = bcast_y(_Shape2(out_shape), bias, int(add.attrs.get("axis", -1)))
    if any(d != 1 for d in bview.shape[:split]):
        return False  # bias varying over GEMM rows is outside the epilogue
    brow = jnp.broadcast_to(
        bview, (1,) * split + tuple(out_shape[split:])
    ).reshape(1, n)
    z2, y2 = gemm_bias_act(
        x.reshape(m, k), w.reshape(k, n), brow,
        act=act_op.type if act_op is not None else None,
    )
    env[add.output("Out")[0]] = z2.reshape(out_shape)
    env[prod.output("Out")[0]] = (
        z2.astype(jnp.float32) - brow.astype(jnp.float32)
    ).astype(z2.dtype).reshape(out_shape)
    if act_op is not None:
        env[act_op.output("Out")[0]] = y2.reshape(out_shape)
    _note_dispatch("gemm_epilogue")
    return True


@register_fused("gemm_int8")
def _fused_quant_gemm(ctx, ops, env):
    """int8_mul -> fake_dequantize ×2 [-> elementwise_add [-> act]] through
    quant_gemm_bias_act: the two chained per-tensor dequant multiplies
    collapse into ONE combined scale applied to the i32 accumulator, and the
    bias/activation ride the same epilogue — the whole calibrated-int8 dense
    layer is one kernel with one rounding. Intermediate env entries are
    rebuilt algebraically from z (exact inverses of the epilogue, f32) so
    out-of-run consumers stay correct; XLA DCEs them when unused."""
    if len(ops) not in (3, 4, 5) or ops[0].type != "int8_mul":
        return False
    if _mesh_declines(ctx, ops):
        return False
    prod, d1, d2 = ops[0], ops[1], ops[2]
    if (
        d1.type != "fake_dequantize_max_abs"
        or d2.type != "fake_dequantize_max_abs"
        or d1.input("X") != [prod.output("Out")[0]]
        or d2.input("X") != [d1.output("Out")[0]]
    ):
        return False
    add_op = act_op = None
    if len(ops) >= 4:
        add_op = ops[3]
        if add_op.type != "elementwise_add" or add_op.input("X") != [
            d2.output("Out")[0]
        ]:
            return False
    if len(ops) == 5:
        act_op = ops[4]
        if act_op.type not in _GEMM_ACT_F32 or act_op.input("X") != [
            add_op.output("Out")[0]
        ]:
            return False
    x = env.get(prod.input("X")[0])
    w = env.get(prod.input("Y")[0])
    s1 = env.get(d1.input("Scale")[0])
    s2 = env.get(d2.input("Scale")[0])
    if x is None or w is None or s1 is None or s2 is None:
        return False
    if x.dtype != jnp.int8 or w.dtype != jnp.int8:
        return False
    views = _gemm_chain_views(prod, x, w)
    if views is None:
        return False
    m, n, k, out_shape, split = views
    if not quant_gemm_path_taken(m, n, k, x.dtype):
        return False
    r1 = float(d1.attrs.get("max_range", 127.0))
    r2 = float(d2.attrs.get("max_range", 127.0))
    combined = (jnp.reshape(s1, ()) / r1) * (jnp.reshape(s2, ()) / r2)
    brow = None
    if add_op is not None:
        bias = env.get(add_op.input("Y")[0])
        if bias is None:
            return False
        bview = bcast_y(_Shape2(out_shape), bias, int(add_op.attrs.get("axis", -1)))
        if any(d != 1 for d in bview.shape[:split]):
            return False
        brow = jnp.broadcast_to(
            bview, (1,) * split + tuple(out_shape[split:])
        ).reshape(1, n)
    z2, y2 = quant_gemm_bias_act(
        x.reshape(m, k), w.reshape(k, n), combined, brow,
        act=act_op.type if act_op is not None else None,
    )
    z32 = z2.astype(jnp.float32)
    pre = z32 if brow is None else z32 - brow.astype(jnp.float32)
    env[prod.output("Out")[0]] = (pre / combined).reshape(out_shape)
    env[d1.output("Out")[0]] = (
        pre / jnp.maximum(jnp.reshape(s2, ()) / r2, 1e-30)
    ).reshape(out_shape)
    env[d2.output("Out")[0]] = pre.astype(z2.dtype).reshape(out_shape)
    if add_op is not None:
        env[add_op.output("Out")[0]] = z2.reshape(out_shape)
    if act_op is not None:
        env[act_op.output("Out")[0]] = y2.reshape(out_shape)
    return True


def _ln_view(op, x):
    bna = int(op.attrs.get("begin_norm_axis", 1))
    rows = int(np.prod(x.shape[:bna], dtype=np.int64)) if bna else 1
    cols = x.size // max(rows, 1)
    return rows, cols


@register_fused("layer_norm")
def _fused_layer_norm(ctx, ops, env):
    """[elementwise_add ->] layer_norm through fused_layer_norm. The residual
    form requires strictly equal operand shapes (the pre_post_process "dan"
    chain); anything else declines to per-op."""
    ln = ops[-1]
    if ln.type != "layer_norm" or len(ops) > 2:
        return False
    if _mesh_declines(ctx, ops):
        return False
    add = ops[0] if len(ops) == 2 else None
    if add is not None:
        if (
            add.type != "elementwise_add"
            or add.output("Out")[0] != ln.input("X")[0]
        ):
            return False
        xa = env.get(add.input("X")[0])
        ra = env.get(add.input("Y")[0])
        if xa is None or ra is None or xa.shape != ra.shape or xa.dtype != ra.dtype:
            return False
        x_full = xa
        residual_full = ra
    else:
        x_full = env.get(ln.input("X")[0])
        residual_full = None
        if x_full is None:
            return False
    rows, cols = _ln_view(ln, x_full)
    if not ln_path_taken(rows, cols, x_full.dtype.itemsize):
        return False
    # NOT gather_op_inputs: in the residual form, ln's X is the add's Out,
    # which by design has no env entry yet (the fused kernel produces it)
    scale_names = ln.inputs.get("Scale") or []
    bias_names = ln.inputs.get("Bias") or []
    scale = env.get(scale_names[0]) if scale_names else None
    bias = env.get(bias_names[0]) if bias_names else None
    eps = ln.attrs.get("epsilon", 1e-5)
    s2, y2, mean, var = fused_layer_norm(
        x_full.reshape(rows, cols),
        None if residual_full is None else residual_full.reshape(rows, cols),
        scale, bias, eps,
    )
    if add is not None:
        env[add.output("Out")[0]] = s2.reshape(x_full.shape)
    outs = {"Y": [y2.reshape(x_full.shape)], "Mean": [mean], "Variance": [var]}
    scatter_op_outputs(ln, outs, env)
    _note_dispatch("layer_norm")
    return True


@register_fused("layer_norm_grad")
def _fused_layer_norm_grad(ctx, ops, env):
    """layer_norm_grad through the explicit backward kernel against the saved
    Mean/Variance. Declines when someone differentiates through the stats
    themselves (Mean@GRAD / Variance@GRAD cotangents) — the generic
    vjp-replay fallback handles that exotic case."""
    if len(ops) != 1 or ops[0].type != "layer_norm_grad":
        return False
    if _mesh_declines(ctx, ops):
        return False
    op = ops[0]
    ins = gather_op_inputs(op, env)
    if (
        ins.get("Mean@GRAD", [None])[0] is not None
        or ins.get("Variance@GRAD", [None])[0] is not None
    ):
        return False
    x = ins.get("X", [None])[0]
    dy = ins.get("Y@GRAD", [None])[0]
    mean = ins.get("Mean", [None])[0]
    var = ins.get("Variance", [None])[0]
    if x is None or dy is None or mean is None or var is None:
        return False
    rows, cols = _ln_view(op, x)
    if not ln_path_taken(rows, cols, x.dtype.itemsize):
        return False
    scale = ins.get("Scale", [None])[0]
    eps = op.attrs.get("epsilon", 1e-5)
    dx, ds, db = fused_layer_norm_grad(
        x.reshape(rows, cols), scale, mean, var,
        dy.reshape(rows, cols).astype(x.dtype), eps,
    )
    outs = {"X@GRAD": [dx.reshape(x.shape)]}
    if scale is not None and "Scale@GRAD" in op.outputs:
        outs["Scale@GRAD"] = [ds.reshape(scale.shape).astype(scale.dtype)]
    bias = ins.get("Bias", [None])[0]
    if bias is not None and "Bias@GRAD" in op.outputs:
        outs["Bias@GRAD"] = [db.reshape(bias.shape).astype(bias.dtype)]
    scatter_op_outputs(op, outs, env)
    _note_dispatch("layer_norm_grad")
    return True
