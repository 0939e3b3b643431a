"""Generation-serving ops: paged KV-cache attention and cache writes.

The decode hot loop of the generation engine (`serving/generation.py`) runs
one fixed-shape program per step: every live slot contributes exactly one
query token, and all past K/V live in a preallocated paged pool indexed
through per-slot block tables (the vLLM layout). Keeping the gather/scatter
inside registered ops means the decode program lowers through the same
`aot_serve_lowering` path as everything else — the pool tensors classify as
mutable state and can be donated, so pages update in place and the step
never retraces.

Conventions:
  * A pool is a persistable ``[n_pages * page_size, n_kv_head * d_head]``
    array (f32, bf16 or int8 levels; ``n_kv_head`` is ``n_head`` unless the
    op's attribute says otherwise: grouped-query attention). Row ``page_id * page_size + offset`` holds the K (or V) row for
    one token. Page 0 is a scratch page the allocator never hands out —
    writes landing there (padded prefill tail, idle decode slots) are
    masked out of every attention read.
  * ``kv_cache_write`` outputs the pool variable itself (the in-place
    idiom, like ``increment``), so the executor classifies the pool as
    written state and threads the new buffer to the next step.
  * ``paged_attention`` takes a block table of either shape: ``[S, P]``
    (decode — one page list per query row) or ``[P]`` (chunked prefill —
    one slot's list shared by every row of the chunk). Masking is a proper
    where-mask with a safe softmax: masked scores are dropped, never added
    as a large negative constant (the additive ``-1e9`` form leaks
    probability mass once scores live in bf16 at long context), and a
    fully-masked row (pos < 0) emits zeros instead of 0/0 NaN.
  * **int8 pool mode** — when ``kv_cache_write`` is given a ``Scales``
    input, the pool holds int8 levels (symmetric per-row absmax/127
    quantization on the scatter) and a ``[n_pages * page_size]`` f32 scale
    pool rides along as a second piece of written state. ``paged_attention``
    takes the matching ``KScales``/``VScales`` and dequantizes inline — in
    the dense path on the gathered rows; the Pallas kernel scales the
    scores and the probabilities on its block-table walk instead, so no
    dequantized row exists there at all. One HBM pool
    at ~¼ the bytes per row (int8 + one f32 scale per row) holds ≥2× the
    generation slots.
  * On TPU (or when FLAGS_paged_flash forces it) the lowering dispatches to
    the paged flash-attention Pallas kernel (ops/pallas_kernels.py), which
    walks only the pages each row's table holds up to its position — a
    loop bounded by pos over multi-page blocks the kernel copies out of the
    pool itself — with an online softmax, and never materializes the
    gathered context: its work follows the positions attended, where the
    dense form's follows the table's width. The dense form below stays as
    the decline target and the parity oracle (PR 11 contract).
"""

import jax
import jax.numpy as jnp

from .registry import register

__all__ = []


def _flat_rows(block_table, positions, page_size):
    """Pool row index for each (slot, position): block_table picks the page,
    position % page_size the offset. block_table may be [S, P] (decode, one
    row per slot) or [P] (prefill, one slot writing many positions). A
    position at or past the table's capacity (P * page_size — only the
    padded tail of a prefill chunk near the context bound can get there) is
    routed to the scratch page's rows instead of clamp-corrupting the last
    real page."""
    positions = positions.reshape(-1).astype(jnp.int32)
    page_idx = positions // page_size
    n_pages = block_table.shape[-1]
    safe_idx = jnp.minimum(page_idx, n_pages - 1)
    if block_table.ndim == 1:
        page_id = block_table.astype(jnp.int32)[safe_idx]
    else:
        page_id = jnp.take_along_axis(
            block_table.astype(jnp.int32), safe_idx[:, None], axis=1
        )[:, 0]
    page_id = jnp.where(page_idx < n_pages, page_id, 0)
    return page_id * page_size + positions % page_size


KV_QUANT_LEVELS = 127.0  # symmetric int8: round(x / scale), scale = absmax/127


@register("kv_cache_write", no_grad=True)
def _kv_cache_write(ctx, ins, attrs):
    """Scatter K/V rows into the pool. With a Scales input the pool holds
    int8 levels: each row quantizes symmetrically on the way in (scale =
    absmax/127 per row — a page's scale vector fills incrementally as its
    rows are written, so earlier rows are never re-scaled) and the f32
    per-row scale lands in the scale pool at the same flat index. Both the
    pool and the scale pool come back as written state (the in-place
    idiom), so decode steps donate both buffers."""
    (pool,) = ins["Pool"]
    (rows,) = ins["Rows"]
    (bt,) = ins["BlockTable"]
    (pos,) = ins["Pos"]
    page_size = int(attrs["page_size"])
    flat = _flat_rows(bt, pos, page_size)
    scales = ins.get("Scales", [None])[0]
    if scales is None:
        return {"Out": [pool.at[flat].set(rows.astype(pool.dtype))]}
    r32 = rows.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(r32), axis=-1)
    scale = jnp.maximum(absmax, 1e-8) / KV_QUANT_LEVELS
    q = jnp.clip(
        jnp.round(r32 / scale[:, None]), -KV_QUANT_LEVELS, KV_QUANT_LEVELS
    ).astype(pool.dtype)
    return {
        "Out": [pool.at[flat].set(q)],
        "OutScales": [scales.at[flat].set(scale.astype(scales.dtype))],
    }


def _paged_attention_infer(op, block):
    """Out is shaped and typed like Q. A rule of its own, because deriving it
    from the lowering (registry.infer_shape) traces the Pallas kernel once
    for every layer of every variant a server builds, and again in every
    start with a warm export cache: 216 traces and most of GPT-2 large's
    set-up on the chip's host (PERF.md, PR 26)."""
    q = block._var_recursive(op.inputs["Q"][0])
    out = block._var_recursive(op.outputs["Out"][0])
    out.shape, out.dtype = q.shape, q.dtype


@register("paged_attention", no_grad=True, infer_shape=_paged_attention_infer)
def _paged_attention(ctx, ins, attrs):
    (q,) = ins["Q"]  # [S, H*D] — one query token per row
    (kp,) = ins["KPool"]
    (vp,) = ins["VPool"]
    (bt,) = ins["BlockTable"]  # [S, P] or [P] int32 page ids (0 = scratch)
    (pos,) = ins["Pos"]  # [S] position of each query (attends 0..pos)
    n_head = int(attrs["n_head"])
    # grouped-query attention: the pools' rows hold n_kv_head heads and query
    # head i reads KV head i // (n_head // n_kv_head)
    n_kv = int(attrs.get("n_kv_head") or n_head)
    page_size = int(attrs["page_size"])
    s = q.shape[0]
    p = bt.shape[-1]
    ctx_len = p * page_size
    d = q.shape[-1] // n_head
    scale = float(attrs.get("sm_scale") or 0.0) or d**-0.5
    ks = ins.get("KScales", [None])[0]
    vs = ins.get("VScales", [None])[0]

    from . import pallas_kernels as _pk

    if _pk.paged_flash_path_taken(s, p, page_size, n_head, d):
        out = _pk.paged_flash_attention(
            q, kp, vp, bt, pos,
            n_head=n_head, page_size=page_size, sm_scale=scale,
            k_scales=ks, v_scales=vs, n_kv_head=n_kv,
        )
        return {"Out": [out]}

    def _deq(levels, row_scales, flat_idx):
        # int8-pool dequant in the dense decline path: per-row scales gather
        # through the same flat indices as their rows
        x = levels.astype(jnp.float32)
        if row_scales is None:
            return x
        sc = jnp.take(row_scales.reshape(-1), flat_idx.reshape(-1), axis=0)
        return x * sc.astype(jnp.float32).reshape(flat_idx.shape + (1, 1))

    qh = q.reshape(s, n_head, d).astype(jnp.float32)
    # every query head beside its KV head: a no-op when n_kv == n_head
    heads = lambda x: jnp.repeat(
        x.reshape(x.shape[:-1] + (n_kv, d)), n_head // n_kv, axis=-2)
    offsets = jnp.arange(page_size, dtype=jnp.int32)
    if bt.ndim == 1:
        # one shared page list: gather each context row once for all queries
        flat = (bt.astype(jnp.int32)[:, None] * page_size + offsets[None, :])
        flat = flat.reshape(ctx_len)
        k = heads(jnp.take(kp, flat, axis=0))
        v = heads(jnp.take(vp, flat, axis=0))
        k = _deq(k, ks, flat)
        v = _deq(v, vs, flat)
        scores = jnp.einsum("shd,chd->shc", qh, k.astype(jnp.float32)) * scale
    else:
        flat = (
            bt.astype(jnp.int32)[:, :, None] * page_size
            + offsets[None, None, :]
        ).reshape(s, ctx_len)
        k = heads(jnp.take(kp, flat.reshape(-1), axis=0).reshape(s, ctx_len, -1))
        v = heads(jnp.take(vp, flat.reshape(-1), axis=0).reshape(s, ctx_len, -1))
        k = _deq(k, ks, flat)
        v = _deq(v, vs, flat)
        scores = jnp.einsum("shd,schd->shc", qh, k.astype(jnp.float32)) * scale

    # causal-by-position where-mask + safe softmax: the query at position
    # pos sees context rows 0..pos inclusive (its own K/V row was written
    # earlier this step). Dead rows are EXCLUDED (weight exactly 0), not
    # additively depressed; a fully-masked row (pos < 0) emits zeros.
    live = (
        jnp.arange(ctx_len, dtype=jnp.int32)[None, :]
        <= pos.reshape(-1).astype(jnp.int32)[:, None]
    )[:, None, :]
    scores = jnp.where(live, scores, -jnp.inf)
    m = jnp.max(scores, axis=-1, keepdims=True)
    m = jnp.where(jnp.isfinite(m), m, 0.0)
    w = jnp.where(live, jnp.exp(scores - m), 0.0)
    denom = jnp.sum(w, axis=-1, keepdims=True)
    w = w / jnp.where(denom > 0.0, denom, 1.0)
    if bt.ndim == 1:
        out = jnp.einsum("shc,chd->shd", w, v.astype(jnp.float32))
    else:
        out = jnp.einsum("shc,schd->shd", w, v.astype(jnp.float32))
    return {"Out": [out.reshape(s, n_head * d).astype(q.dtype)]}


def _mla_attention_infer(op, block):
    q = block._var_recursive(op.inputs["Q"][0])
    pool = block._var_recursive(op.inputs["Pool"][0])
    out = block._var_recursive(op.outputs["Out"][0])
    heads = q.shape[-1] // pool.shape[-1]
    out.shape = tuple(q.shape[:-1]) + (heads * int(op.attrs["d_value"]),)
    out.dtype = q.dtype


@register("mla_attention", no_grad=True, infer_shape=_mla_attention_infer)
def _mla_attention(ctx, ins, attrs):
    """Latent attention in its absorbed form over ONE paged pool: a pooled
    row ``[c_kv | k_rope | zero padding]`` is the key of every query head
    and, its first ``d_value`` columns, their value. Q is ``[S, n_head *
    width]`` (``width`` the pool's: a head's ``[q_nope @ W_uk | q_rope |
    zeros]``), Out ``[S, n_head * d_value]``: each head's softmax-weighted
    sum of ``c_kv``. BlockTable, Pos and the where-mask with its safe
    softmax are paged_attention's. On TPU (or when FLAGS_paged_flash forces
    it) the lowering is the Pallas walk latent_paged_attention, which reads
    each pooled row once for all the heads; the dense form below is the
    decline target and the parity oracle."""
    (q,) = ins["Q"]
    (pool,) = ins["Pool"]
    (bt,) = ins["BlockTable"]
    (pos,) = ins["Pos"]
    page_size = int(attrs["page_size"])
    d_value = int(attrs["d_value"])
    scale = float(attrs["sm_scale"])
    s, width = q.shape[0], pool.shape[-1]
    n_head = q.shape[-1] // width
    p = bt.shape[-1]
    ctx_len = p * page_size

    from . import pallas_kernels as _pk

    if _pk.latent_paged_path_taken(s, p, page_size, n_head, width):
        return {"Out": [_pk.latent_paged_attention(
            q, pool, bt, pos, n_head=n_head, page_size=page_size,
            d_value=d_value, sm_scale=scale)]}

    qh = q.reshape(s, n_head, width).astype(jnp.float32)
    offsets = jnp.arange(page_size, dtype=jnp.int32)
    flat = (bt.astype(jnp.int32)[..., None] * page_size + offsets).reshape(
        bt.shape[:-1] + (ctx_len,))
    k = jnp.take(pool, flat.reshape(-1), axis=0).astype(jnp.float32).reshape(
        flat.shape + (width,))
    shared = bt.ndim == 1
    scores = jnp.einsum("shw,cw->shc" if shared else "shw,scw->shc", qh, k)
    live = (
        jnp.arange(ctx_len, dtype=jnp.int32)[None, :]
        <= pos.reshape(-1).astype(jnp.int32)[:, None]
    )[:, None, :]
    scores = jnp.where(live, scores * scale, -jnp.inf)
    m = jnp.max(scores, axis=-1, keepdims=True)
    m = jnp.where(jnp.isfinite(m), m, 0.0)
    w = jnp.where(live, jnp.exp(scores - m), 0.0)
    denom = jnp.sum(w, axis=-1, keepdims=True)
    w = w / jnp.where(denom > 0.0, denom, 1.0)
    out = jnp.einsum(
        "shc,cv->shv" if shared else "shc,scv->shv", w, k[..., :d_value])
    return {"Out": [out.reshape(s, n_head * d_value).astype(q.dtype)]}
