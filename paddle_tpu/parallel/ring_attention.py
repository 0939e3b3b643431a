"""Ring attention: exact attention over sequences sharded across the 'sp'
mesh axis (context parallelism over ICI).

The reference (2018-era) has NO sequence parallelism — its long-sequence
answer was LoD ragged batching (SURVEY.md §5.7); this is the new capability
the TPU build adds. Algorithm (Liu et al. ring attention; public pattern):
each rank holds a (b, h, t_local, d) shard of Q/K/V along the sequence; K/V
chunks rotate around the ring via ppermute while each rank accumulates its
queries' attention with an online (streaming) softmax — max/denominator
corrections per incoming chunk — so the result is EXACT full attention
without ever materializing the (t, t) score matrix on one chip, and the
K/V transfer overlaps compute around the ring.

Causal masking uses global positions derived from each chunk's rank of
origin (after i rotations a rank holds the chunk of rank (me - i) mod n).

Two per-chunk compute tiers:

- **flash** (default when tile shapes allow): each ring step runs the Pallas
  flash-attention kernels (ops/pallas_kernels.py). The whole ring is one
  custom_vjp: the forward merges per-chunk (out_i, lse_i) with the online
  rescale and saves only (q, k, v, out, lse) per rank — O(t_local) memory;
  the backward re-runs the ring with the flash dQ/dKV kernels against the
  GLOBAL logsumexp (the flash-2 decomposition is exact per KV block, so
  per-chunk backward with global lse sums to the full gradient) while dK/dV
  accumulators rotate with their chunks and arrive home after n hops.
  Causal chunk scheduling is static-per-step: step 0 is the diagonal
  (causal kernel); later steps are fully-visible or fully-masked, selected
  by one lax.cond (the masked branch does no FLOPs) — the causal ring does
  ~half the work of the full ring.
- **dense** fallback (ragged tiles): the original einsum online-softmax
  steps differentiated by plain autodiff.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from ..ops import pallas_kernels as pk
from .collectives import axis_size, shard_map

__all__ = ["ring_attention", "ring_attention_sharded"]

NEG_INF = -1e30


def _ring_attention_local(q, k, v, axis_name, causal, scale):
    """Runs inside shard_map: q,k,v are local (b, h, t_loc, d) shards."""
    n = axis_size(axis_name)
    me = lax.axis_index(axis_name)
    b, h, t_loc, d = q.shape

    q_pos = me * t_loc + jnp.arange(t_loc)  # global positions of my queries

    def step(i, carry):
        k_cur, v_cur, m, l, o = carry
        src = (me - i) % n  # rank of origin of the chunk I currently hold
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k_cur) * scale
        if causal:
            k_pos = src * t_loc + jnp.arange(t_loc)
            mask = q_pos[:, None] >= k_pos[None, :]
            s = jnp.where(mask[None, None], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        corr = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        l_new = l * corr + jnp.sum(p, axis=-1)
        o_new = o * corr[..., None] + jnp.einsum("bhqk,bhkd->bhqd", p, v_cur)
        perm = [(j, (j + 1) % n) for j in range(n)]
        k_next = lax.ppermute(k_cur, axis_name, perm)
        v_next = lax.ppermute(v_cur, axis_name, perm)
        return (k_next, v_next, m_new, l_new, o_new)

    m0 = jnp.full((b, h, t_loc), NEG_INF, q.dtype)
    l0 = jnp.zeros((b, h, t_loc), q.dtype)
    o0 = jnp.zeros((b, h, t_loc, d), q.dtype)
    carry = (k, v, m0, l0, o0)
    # unrolled python loop: n is a static mesh size, so XLA can pipeline the
    # ppermute of chunk i+1 behind the matmuls of chunk i
    for i in range(n):
        carry = step(i, carry)
    _, _, m, l, o = carry
    return o / jnp.maximum(l, 1e-20)[..., None]


# ---------------------------------------------------------------------------
# flash ring: whole-ring custom_vjp over the Pallas kernels
# ---------------------------------------------------------------------------


def _rot(x, axis_name, n):
    return lax.ppermute(x, axis_name, [(j, (j + 1) % n) for j in range(n)])


def _chunk_fwd(q, k_cur, v_cur, causal_diag, scale, interpret):
    """One ring step's flash forward on (b, h, t_loc, d): (out, lse)."""
    out, lse = pk._flash_forward(
        q, k_cur, v_cur, causal_diag, scale,
        None, None, interpret, with_lse=True,
    )
    return out, lse


def _chunk_bwd(q, k_cur, v_cur, out, lse, do, causal_diag, scale, interpret):
    """One ring step's flash backward against the GLOBAL lse."""
    return pk._flash_backward(
        q, k_cur, v_cur, out, lse, do, causal_diag, scale,
        None, None, interpret,
    )


def _merge(acc, m, l, o_i, lse_i):
    """Online merge of a normalized chunk (o_i, lse_i) into (acc, m, l)."""
    m_new = jnp.maximum(m, lse_i)
    alpha = jnp.exp(jnp.where(m == -jnp.inf, -jnp.inf, m - m_new))
    w = jnp.exp(lse_i - m_new)
    acc = acc * alpha[..., None] + o_i.astype(jnp.float32) * w[..., None]
    l = l * alpha + w
    return acc, m_new, l


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _ring_flash_local(q, k, v, axis_name, causal, scale, interpret):
    out, _lse = _ring_flash_fwd_pass(q, k, v, axis_name, causal, scale, interpret)
    return out


def _ring_flash_fwd_pass(q, k, v, axis_name, causal, scale, interpret):
    n = axis_size(axis_name)
    me = lax.axis_index(axis_name)
    b, h, t_loc, d = q.shape

    acc = jnp.zeros((b, h, t_loc, d), jnp.float32)
    m = jnp.full((b, h, t_loc), -jnp.inf, jnp.float32)
    l = jnp.zeros((b, h, t_loc), jnp.float32)
    k_cur, v_cur = k, v
    for i in range(n):
        if causal and i == 0:
            # diagonal chunk: the only step needing an intra-chunk mask
            o_i, lse_i = _chunk_fwd(q, k_cur, v_cur, True, scale, interpret)
            acc, m, l = _merge(acc, m, l, o_i, lse_i)
        elif causal:
            # src = (me - i) % n: fully visible iff src < me, else fully
            # masked — one cond, and the masked branch does no attention work
            src = (me - i) % n

            def _vis(args):
                acc, m, l = args
                o_i, lse_i = _chunk_fwd(q, k_cur, v_cur, False, scale, interpret)
                return _merge(acc, m, l, o_i, lse_i)

            acc, m, l = lax.cond(src < me, _vis, lambda args: args, (acc, m, l))
        else:
            o_i, lse_i = _chunk_fwd(q, k_cur, v_cur, False, scale, interpret)
            acc, m, l = _merge(acc, m, l, o_i, lse_i)
        if i + 1 < n:
            k_cur = _rot(k_cur, axis_name, n)
            v_cur = _rot(v_cur, axis_name, n)
    out = (acc / jnp.maximum(l, 1e-20)[..., None]).astype(q.dtype)
    lse = m + jnp.log(jnp.maximum(l, 1e-20))
    return out, lse


def _ring_flash_vjp_fwd(q, k, v, axis_name, causal, scale, interpret):
    out, lse = _ring_flash_fwd_pass(q, k, v, axis_name, causal, scale, interpret)
    # O(t_local) residuals — no per-step K/V chunks, no (t, t) scores
    return out, (q, k, v, out, lse)


def _ring_flash_vjp_bwd(axis_name, causal, scale, interpret, res, do):
    q, k, v, out, lse = res
    n = axis_size(axis_name)
    me = lax.axis_index(axis_name)

    dq = jnp.zeros(q.shape, jnp.float32)
    # dK/dV accumulators travel around the ring WITH their chunk and are
    # home again after the n-th hop
    dk_acc = jnp.zeros(k.shape, jnp.float32)
    dv_acc = jnp.zeros(v.shape, jnp.float32)
    k_cur, v_cur = k, v
    for i in range(n):
        if causal and i == 0:
            dq_i, dk_i, dv_i = _chunk_bwd(
                q, k_cur, v_cur, out, lse, do, True, scale, interpret
            )
            dq += dq_i
            dk_acc += dk_i
            dv_acc += dv_i
        elif causal:
            src = (me - i) % n

            def _vis(args):
                dq, dk_acc, dv_acc = args
                dq_i, dk_i, dv_i = _chunk_bwd(
                    q, k_cur, v_cur, out, lse, do, False, scale, interpret
                )
                return dq + dq_i, dk_acc + dk_i, dv_acc + dv_i

            dq, dk_acc, dv_acc = lax.cond(
                src < me, _vis, lambda args: args, (dq, dk_acc, dv_acc)
            )
        else:
            dq_i, dk_i, dv_i = _chunk_bwd(
                q, k_cur, v_cur, out, lse, do, False, scale, interpret
            )
            dq += dq_i
            dk_acc += dk_i
            dv_acc += dv_i
        # accumulators rotate every step (incl. the last) to complete the
        # full ring and land back on the owning rank; k/v are not needed
        # after the last compute
        if i + 1 < n:
            k_cur = _rot(k_cur, axis_name, n)
            v_cur = _rot(v_cur, axis_name, n)
        dk_acc = _rot(dk_acc, axis_name, n)
        dv_acc = _rot(dv_acc, axis_name, n)
    return dq.astype(q.dtype), dk_acc.astype(k.dtype), dv_acc.astype(v.dtype)


_ring_flash_local.defvjp(_ring_flash_vjp_fwd, _ring_flash_vjp_bwd)


# the Pallas path needs whole q/k tiles — otherwise _flash_forward would
# silently fall back to dense WITHOUT lse, which the ring merge needs; the
# rule lives in pallas_kernels.flash_tiles_ok. (Head dim needs no gate:
# Mosaic pads sub-lane dims, verified on-chip down to d=8.)
_flash_tiles_ok = pk.flash_tiles_ok


def ring_attention_sharded(
    q, k, v, mesh, axis_name="sp", causal=False, scale=None, use_flash=None
):
    """q,k,v: (b, h, t, d) GLOBAL arrays (sharded or shardable on t over
    `axis_name`). Returns attention output with the same sharding.

    use_flash: None = auto (Pallas ring when tile shapes allow), True/False
    to force. The dense tier remains for ragged shards."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    n_sp = mesh.shape[axis_name]
    if q.shape[2] % n_sp:
        raise ValueError(
            "sequence length %d not divisible by the %r axis size %d"
            % (q.shape[2], axis_name, n_sp)
        )
    t_loc = q.shape[2] // n_sp
    if use_flash is None:
        use_flash = _flash_tiles_ok(t_loc)
    elif use_flash and not _flash_tiles_ok(t_loc):
        raise ValueError(
            "flash ring needs t_local %% block == 0 (t_local=%d); "
            "pass use_flash=False for the dense ring" % t_loc
        )
    # batch rides the dp axis when the mesh has one (degrade gracefully on
    # sp-only meshes, matching sharded_embedding_lookup's guard)
    batch_axes = ("dp",) if "dp" in mesh.shape else None
    spec = P(batch_axes, None, (axis_name,), None)
    if use_flash:
        # shared defaulting rule with the flash kernels (fwd/bwd must agree)
        scale, interpret = pk._resolve_defaults(q, scale, None)

        # positional call: custom_vjp nondiff_argnums are position-based
        def local(q, k, v):
            return _ring_flash_local(
                q, k, v, axis_name, causal, scale, interpret
            )
    else:
        local = functools.partial(
            _ring_attention_local, axis_name=axis_name, causal=causal, scale=scale
        )
    fn = shard_map(
        local,
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        # flash tier only: pallas_call out_shapes carry no varying-mesh-axes
        # annotation, which the replication checker requires; collective
        # correctness there is covered by the ring-vs-dense forward/grad
        # tests. The dense tier keeps the checker.
        check_vma=not use_flash,
    )
    return fn(q, k, v)


def ring_attention(q, k, v, causal=False, scale=None, axis_name="sp", mesh=None):
    """Plain attention when no sp sharding is active; ring algorithm when a
    mesh with a >1 'sp' axis is supplied (or found on the inputs)."""
    if mesh is not None and mesh.shape.get(axis_name, 1) > 1:
        return ring_attention_sharded(q, k, v, mesh, axis_name, causal, scale)
    if scale is None:
        scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    if causal:
        t_q, t_k = s.shape[-2], s.shape[-1]
        mask = jnp.arange(t_q)[:, None] >= jnp.arange(t_k)[None, :]
        s = jnp.where(mask[None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)
