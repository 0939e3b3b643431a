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
from jax._src import source_info_util as _name_stack

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


# ---------------------------------------------------------------------------
# learned sparse attention (DeepSeek-V3.2's DSA): a lightning indexer scores
# every cached position, each query keeps its top `k`, and latent attention
# reads only those
# ---------------------------------------------------------------------------


def _table_rows(bt, page_size):
    """Pool row of every position a block table holds, [..., P * page_size]."""
    offsets = jnp.arange(page_size, dtype=jnp.int32)
    return (bt.astype(jnp.int32)[..., None] * page_size + offsets).reshape(
        bt.shape[:-1] + (bt.shape[-1] * page_size,))


def _positions_rows(bt, positions, page_size):
    """Pool row of each position in `positions` [S, k] (< 0: none, row 0)
    through the block table ([S, P], or [P] shared by every row)."""
    p = jnp.maximum(positions, 0)
    page = p // page_size
    if bt.ndim == 1:
        page_id = bt.astype(jnp.int32)[page]
    else:
        page_id = jnp.take_along_axis(bt.astype(jnp.int32), page, axis=1)
    return jnp.where(positions >= 0, page_id * page_size + p % page_size, 0)


def _dsa_index_infer(op, block):
    q = block._var_recursive(op.inputs["Q"][0])
    bt = block._var_recursive(op.inputs["BlockTable"][0])
    out = block._var_recursive(op.outputs["Out"][0])
    out.shape = (q.shape[0], bt.shape[-1] * int(op.attrs["page_size"]))
    out.dtype = "float32"


@register("dsa_index", no_grad=True, infer_shape=_dsa_index_infer)
def _dsa_index(ctx, ins, attrs):
    """The lightning indexer's scan over a paged pool of index keys (one
    key a position, shared by every index head). Q is ``[S, heads * width]``
    (each head's rotated query), W ``[S, heads]`` (each head's weight, its
    scales folded in), BlockTable and Pos paged_attention's. Out ``[S, P *
    page_size]`` float32: ``sum_j W[s, j] ReLU(Q[s, j] . key(c))`` at every
    position c <= Pos[s] of the row's table, -inf past it. On TPU (or when
    FLAGS_paged_flash forces it) the lowering is the Pallas scan
    dsa_index_scores, which walks only the pages up to each program's
    largest position and sums the heads inside the kernel; the dense form
    below is the decline target and the parity oracle."""
    (q,) = ins["Q"]
    (w,) = ins["W"]
    (pool,) = ins["Pool"]
    (bt,) = ins["BlockTable"]
    (pos,) = ins["Pos"]
    page_size = int(attrs["page_size"])
    s, width = q.shape[0], pool.shape[-1]
    heads = q.shape[-1] // width

    from . import pallas_kernels as _pk

    if _pk.dsa_index_path_taken(s, bt.shape[-1], page_size, width):
        return {"Out": [_pk.dsa_index_scores(
            q, w, pool, bt, pos, heads=heads, page_size=page_size)]}
    flat = _table_rows(bt, page_size)
    k = jnp.take(pool, flat.reshape(-1), axis=0).astype(jnp.float32).reshape(
        flat.shape + (width,))
    qh = q.reshape(s, heads, width).astype(jnp.float32)
    dots = jnp.einsum("shw,cw->shc" if bt.ndim == 1 else "shw,scw->shc", qh, k)
    scores = jnp.einsum("shc,sh->sc", jnp.maximum(dots, 0.0),
                        w.astype(jnp.float32))
    live = (jnp.arange(flat.shape[-1], dtype=jnp.int32)[None, :]
            <= pos.reshape(-1, 1).astype(jnp.int32))
    return {"Out": [jnp.where(live, scores, -jnp.inf)]}


def _ordered_keys(scores):
    """float32 -> uint32 in the same order (-0.0 and 0.0 apart, -inf
    least)."""
    u = jax.lax.bitcast_convert_type(scores.astype(jnp.float32), jnp.uint32)
    return jnp.where(u >> 31 == 1, ~u, u | jnp.uint32(0x80000000))


def topk_positions(scores, k):
    """The exact top-k of every row of scores [S, L] (-inf: no position):
    (positions [S, k] int32 in ascending order, -1 behind the row's last
    where it has fewer than k; the same selection as a mask [S, L] bool).
    Ties at the k-th score go to the lowest positions. No sort over L and no
    gather: the k-th largest key is found four bits at a time (8 passes,
    each counting the row against 15 candidates at once), the positions
    above it and enough of the ties are marked, and the j-th marked position
    is found by counting: the 128-wide block whose running count passes j
    by comparison, then the lane inside it through a one-hot product with
    the blocks' running counts (integers under 129: exact in bfloat16)."""
    rows, n = scores.shape
    key = _ordered_keys(scores)
    digits = jnp.arange(1, 16, dtype=jnp.uint32)

    def one_digit(i, t):
        shift = (28 - 4 * i).astype(jnp.uint32)
        cand = t[:, None] | (digits[None, :] << shift)  # [rows, 15]
        above = jnp.sum((key[:, None, :] >= cand[:, :, None]).astype(jnp.int32),
                        axis=2)
        # counts fall as the digit rises: how many pass is the digit
        return t | (jnp.sum((above >= k).astype(jnp.uint32), axis=1) << shift)

    t = jax.lax.fori_loop(0, 8, one_digit, jnp.zeros((rows,), jnp.uint32))
    gt = key > t[:, None]
    eq = key == t[:, None]
    room = k - jnp.sum(gt.astype(jnp.int32), axis=1, keepdims=True)
    take = gt | (eq & (jnp.cumsum(eq.astype(jnp.int32), axis=1) <= room))
    take = take & (scores > -jnp.inf)
    count = jnp.cumsum(take.astype(jnp.int32), axis=1)  # inclusive
    pad = -n % 128
    if pad:
        count = jnp.pad(count, ((0, 0), (0, pad)), mode="edge")
    blocks = count.reshape(rows, -1, 128)
    ends = blocks[:, :, -1]  # [rows, nb]: marked up to a block's end
    before = jnp.concatenate(
        [jnp.zeros((rows, 1), jnp.int32), ends[:, :-1]], axis=1)
    j = jnp.arange(k, dtype=jnp.int32)[None, :, None]
    hot = (before[:, None, :] <= j) & (ends[:, None, :] > j)  # [rows, k, nb]
    blk = jnp.sum(jnp.where(hot, jnp.arange(ends.shape[1], dtype=jnp.int32), 0),
                  axis=2)
    rank = j[:, :, 0] - jnp.sum(jnp.where(hot, before[:, None, :], 0), axis=2)
    lanes = jnp.einsum(
        "rkb,rbl->rkl", hot.astype(jnp.bfloat16),
        (blocks - before[:, :, None]).astype(jnp.bfloat16),
        preferred_element_type=jnp.float32)
    at = blk * 128 + jnp.sum((lanes <= rank[:, :, None]).astype(jnp.int32), axis=2)
    return jnp.where(j[:, :, 0] < count[:, -1:], at, -1).astype(jnp.int32), take


def _dsa_oracle():
    """The dsa_topk and mla_sparse_attention lowerings' choice, under the
    paged_flash flag: "off" takes their parity oracles (jax.lax.top_k, a
    sort on the chip; every position of the table, masked to the
    selection), anything else the served forms."""
    from .. import flags as _flags

    return _flags.get_flags("paged_flash")["paged_flash"] == "off"


def _dsa_topk_infer(op, block):
    x = block._var_recursive(op.inputs["X"][0])
    out = block._var_recursive(op.outputs["Out"][0])
    out.shape = (x.shape[0], int(op.attrs["k"]))
    out.dtype = "int32"
    mask = block._var_recursive(op.outputs["Mask"][0])
    mask.shape, mask.dtype = tuple(x.shape), "bool"


@register("dsa_topk", no_grad=True, infer_shape=_dsa_topk_infer)
def _dsa_topk(ctx, ins, attrs):
    """The positions of each row's k largest scores (X [S, L] float32, -inf
    where there is no position), ascending, -1 behind a row with fewer than
    k: Out [S, k] int32; Mask [S, L] bool, the same selection. Exact; ties go
    to the lowest positions."""
    (x,) = ins["X"]
    k, n = int(attrs["k"]), x.shape[1]
    if k > n:
        x = jnp.pad(x, ((0, 0), (0, k - n)), constant_values=-jnp.inf)
    if not _dsa_oracle():
        out, mask = topk_positions(x, k)
        return {"Out": [out], "Mask": [mask[:, :n]]}
    top, idx = jax.lax.top_k(x.astype(jnp.float32), k)
    idx = jnp.sort(jnp.where(top > -jnp.inf, idx, x.shape[1]), axis=1)
    rows = jnp.arange(x.shape[0])[:, None]
    mask = jnp.zeros((x.shape[0], x.shape[1] + 1), bool).at[rows, idx].set(True)
    return {"Out": [jnp.where(idx < x.shape[1], idx, -1).astype(jnp.int32)],
            "Mask": [mask[:, :n]]}


def _mla_sparse_infer(op, block):
    q = block._var_recursive(op.inputs["Q"][0])
    pool = block._var_recursive(op.inputs["Pool"][0])
    out = block._var_recursive(op.outputs["Out"][0])
    heads = q.shape[-1] // pool.shape[-1]
    out.shape = (q.shape[0], heads * int(op.attrs["d_value"]))
    out.dtype = q.dtype


SPARSE_WALK_BLOCK = 8  # query rows a trip: one float32 sublane tile


def sparse_walk_blocks(n_live, slots):
    """(rows a block, blocks) of mla_sparse_attention's walk over a decode
    step's per-row tables with `n_live` of its `slots` rows live (an int, or
    a traced int32 inside the op): min(slots, 8) rows a block, as many
    blocks as hold the live rows. The host's mirror of the op's trip count;
    their product is the rows the walk gathers for (gen_dsa_walk_rows)."""
    block = min(int(slots), SPARSE_WALK_BLOCK)
    return block, (n_live + block - 1) // block


def _attend_selected(scores, kv, live, scale, d_value):
    """Absorbed attention over latent rows kv [S, k, W] from their scores
    [S, H, k] float32, softmax over `live` [S, 1, k]: [S, H, d_value]
    float32."""
    scores = jnp.where(live, scores * scale, -jnp.inf)
    m = jnp.max(scores, axis=-1, keepdims=True)
    m = jnp.where(jnp.isfinite(m), m, 0.0)
    w = jnp.where(live, jnp.exp(scores - m), 0.0)
    denom = jnp.sum(w, axis=-1, keepdims=True)
    w = w / jnp.where(denom > 0.0, denom, 1.0)
    return jnp.einsum("shk,skv->shv", w.astype(kv.dtype), kv[..., :d_value],
                      preferred_element_type=jnp.float32)


def _gathered_walk(qh, pool, bt, sel, page_size, scale, d_value):
    """Each row gathers its selected rows through the block table, by
    (page, offset), and attends them: [S, H, d_value] float32."""
    rows = _positions_rows(bt, sel, page_size)
    kv = jnp.take(pool, rows.reshape(-1), axis=0).reshape(
        sel.shape[0], -1, pool.shape[-1])
    # the gathered rows as the left operand, contracted on their last axis:
    # the layout the gather leaves them in, so they are not copied to a
    # transposed one first
    scores = jnp.einsum("skw,shw->shk", kv, qh.astype(pool.dtype),
                        preferred_element_type=jnp.float32)
    return _attend_selected(scores, kv, (sel >= 0)[:, None, :], scale, d_value)


def _live_rows_walk(qh, pool, bt, sel, live, page_size, scale, d_value,
                    dtype):
    """_gathered_walk for the live rows of per-row tables alone: the rows
    in live-first order (a stable argsort), a block of them a trip, as many
    trips as sparse_walk_blocks counts; each block's results go back to its
    rows' slots, and the rows behind the live ones in the last block write
    nowhere. An idle row's output is 0."""
    s = qh.shape[0]
    on = live.reshape(-1) != 0
    order = jnp.argsort(jnp.logical_not(on), stable=True).astype(jnp.int32)
    n_live = jnp.sum(on.astype(jnp.int32))
    block, trips = sparse_walk_blocks(n_live, s)
    scope = _name_stack.current_name_stack()

    def trip(b, out):
        with _name_stack.set_name_stack(scope):
            at = b * block + jnp.arange(block, dtype=jnp.int32)
            rows = jnp.take(order, jnp.minimum(at, s - 1))
            ctx = _gathered_walk(jnp.take(qh, rows, axis=0),
                                 pool, jnp.take(bt, rows, axis=0),
                                 jnp.take(sel, rows, axis=0), page_size,
                                 scale, d_value)
            return out.at[jnp.where(at < n_live, rows, s)].set(
                ctx.astype(dtype), mode="drop")

    # a device trace gives the loop an event of its own around its body's:
    # the loop goes out under no op's name and its body under this op's, so
    # that the op's device time counts the walk once
    with _name_stack.reset_name_stack():
        return jax.lax.fori_loop(
            0, trips, trip,
            jnp.zeros((s,) + qh.shape[1:2] + (d_value,), dtype))


@register("mla_sparse_attention", no_grad=True, infer_shape=_mla_sparse_infer)
def _mla_sparse_attention(ctx, ins, attrs):
    """mla_attention over the positions Selected [S, k] (ascending, -1: none)
    alone: each row gathers its k pooled rows through the block table, by
    (page, offset), and attends them absorbed, softmax over the selection.
    Every query head of a row shares its selection. Live [S] int32 (1: a
    real row), where given with per-row tables, names the rows to walk: the
    others (idle and mid-prefill slots, whose scratch-page selections are
    not empty) gather nothing and give 0. A shared table (one sequence's
    chunk) walks every row. With FLAGS_paged_flash "off" the lowering is
    the parity oracle instead: every position of the table, masked to the
    selection."""
    (q,) = ins["Q"]
    (pool,) = ins["Pool"]
    (bt,) = ins["BlockTable"]
    (sel,) = ins["Selected"]
    live = ins.get("Live", [None])[0]
    if bt.ndim == 1:
        live = None
    page_size = int(attrs["page_size"])
    d_value = int(attrs["d_value"])
    scale = float(attrs["sm_scale"])
    s, width = q.shape[0], pool.shape[-1]
    n_head = q.shape[-1] // width
    sel = sel.astype(jnp.int32)
    qh = q.reshape(s, n_head, width)
    if not _dsa_oracle():
        if live is not None:
            out = _live_rows_walk(qh, pool, bt, sel, live, page_size, scale,
                                  d_value, q.dtype)
        else:
            out = _gathered_walk(qh, pool, bt, sel, page_size, scale, d_value)
        return {"Out": [out.reshape(s, n_head * d_value).astype(q.dtype)]}
    flat = _table_rows(bt, page_size)
    kv = jnp.take(pool, flat.reshape(-1), axis=0).reshape(flat.shape + (width,))
    n = flat.shape[-1]
    mask = jnp.any(jnp.arange(n)[None, None, :] == sel[:, :, None],
                   axis=1)[:, None, :]
    if bt.ndim == 1:
        kv = jnp.broadcast_to(kv, (s, n, width))
    scores = jnp.einsum("shw,skw->shk", qh.astype(pool.dtype), kv,
                        preferred_element_type=jnp.float32)
    out = _attend_selected(scores, kv, mask, scale, d_value)
    if live is not None:
        out = jnp.where((live.reshape(-1) != 0)[:, None, None], out, 0.0)
    return {"Out": [out.reshape(s, n_head * d_value).astype(q.dtype)]}


def _dsa_count_infer(op, block):
    out = block._var_recursive(op.outputs["Out"][0])
    out.shape, out.dtype = (1,), "int32"


@register("dsa_count", no_grad=True, infer_shape=_dsa_count_infer)
def _dsa_count(ctx, ins, attrs):
    """What a step's selection names, for serve.sparse_attn_roofline: Out
    int32 [1], the distinct pool rows the selection Mask [S, P * page_size]
    (dsa_topk's) names through BlockTable. A row that several query rows
    select (a document's pages) counts once; the scratch page, and a query
    row whose Pos is < 0, count never. No sort: a decode row counts a
    selected row unless a lower row's table holds its page and selects it
    too (one [S, S] product a page). What the index scan read follows from
    the tables and positions alone, and the host counts it
    (pallas_kernels.index_rows_scanned)."""
    (mask,) = ins["Mask"]
    (bt,) = ins["BlockTable"]
    (pos,) = ins["Pos"]
    ps = int(attrs["page_size"])
    pos = pos.reshape(-1).astype(jnp.int32)
    mask = mask & (pos[:, None] >= 0)
    if bt.ndim == 1:  # one sequence, one table
        real = jnp.repeat(bt.astype(jnp.int32) > 0, ps)
        picked = jnp.sum((jnp.any(mask, axis=0) & real).astype(jnp.int32))
        return {"Out": [picked.reshape(1).astype(jnp.int32)]}
    s, n_pages = bt.shape
    pages = bt.astype(jnp.int32)
    # same[p, r, r']: rows r and r' hold one page at table entry p, r' < r
    lower = jnp.arange(s)[:, None] > jnp.arange(s)[None, :]
    same = ((pages.T[:, :, None] == pages.T[:, None, :])
            & (pages.T[:, :, None] > 0) & lower[None])
    m = mask.reshape(s, n_pages, ps).transpose(1, 0, 2).astype(jnp.bfloat16)
    before = jnp.einsum("prq,pqo->pro", same.astype(jnp.bfloat16), m,
                        preferred_element_type=jnp.float32)
    picked = jnp.sum(((m > 0) & (before == 0)
                      & (pages.T[:, :, None] > 0)).astype(jnp.int32))
    return {"Out": [picked.reshape(1).astype(jnp.int32)]}
