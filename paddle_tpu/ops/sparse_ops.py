"""Sparse (SelectedRows) gradient + per-row optimizer ops.

Reference analog: the is_sparse=True path of lookup_table_grad_op
(lookup_table_op.h LookupTableGradKernel's SelectedRows branch), the sparse
functors in operators/optimizers (sgd_op.h SparseSGDFunctor, adam_op.h
SparseAdamFunctor lazy_mode, adagrad_op.h SparseAdagradFunctor), and
merge_add (math/selected_rows_functor.cc). On pservers these made the wire
and the update cost O(touched rows); here they make the HBM traffic of the
backward+update O(touched rows) — the dense path reads AND writes the whole
(rows, dim) table plus every moment each step, the sparse path touches
(ids_per_batch, dim) rows of each.

Three pieces:

- `lookup_table_grad_sparse`: emits the SelectedRows pair (embedding/
  selected_rows.py) — values in the cotangent's dtype + int32 global row ids
  (ROW_SENTINEL for masked/padding slots). No table-shaped tensor exists
  anywhere in its lowering.
- `{sgd,adagrad,adam}_sparse`: merge duplicate rows in f32, gather the
  touched param/moment rows, update in f32, scatter back in storage dtype.
  When the op carries an `axis_name` whose mesh extent is >1 the update runs
  under shard_map with the table and moments kept row-sharded — each rank
  updates only its own rows (ids/values are replicated, so every dp replica
  computes identical updates: no cross-replica divergence). This is the ZeRO
  composition for embeddings: moments shard along `ep` with the table
  (optimizer._add_accumulator copies the param's sharding_spec) instead of
  the dense ZeRO-1 `dp` sharding, and bf16 moments ride through unchanged.
- `selected_rows_to_dense`: densify fallback for optimizers without a sparse
  kernel (momentum, rmsprop, …), matching the reference's SelectedRows→
  LoDTensor merge before a dense update.

Adam here is the reference's lazy_mode: untouched rows' moments do not decay
that step (their params also don't move). SGD/Adagrad sparse updates are
exactly the dense math restricted to touched rows — untouched rows are
bit-identical either way.

The custom grad maker for lookup_table/embedding/distributed_lookup_table
lives here too: it chooses sparse vs dense per op instance (is_sparse attr,
and the table must have exactly ONE differentiable consumer — a twice-used
table would need a SelectedRows-aware grad summation, so it falls back to
the dense scatter-add instead).
"""

import functools

import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ..framework import OpRole, grad_var_name
from ..embedding.selected_rows import (
    ROW_SENTINEL,
    densify,
    mark_selected_rows,
    merge_rows,
    rows_var_name,
)
from .registry import OPS, register

__all__ = ["SPARSE_OPTIMIZER_TYPES"]

# optimizer op types with a per-row sparse lowering; everything else densifies
SPARSE_OPTIMIZER_TYPES = {
    "sgd": "sgd_sparse",
    "adagrad": "adagrad_sparse",
    "adam": "adam_sparse",
}


def _gauges(param, height, dim, cap, vbytes, tbytes, shards):
    """Trace-time embedding gauges (PR 4 registry). Set once per (re)compile;
    `cap` is the id-slot capacity of the step — the rows-touched upper bound
    (the exact unique count is data-dependent, invisible to a static trace)."""
    try:
        from ..observability.registry import default_registry

        reg = default_registry()
        lbl = {"table": str(param)}
        if cap is not None:
            reg.gauge(
                "embedding/rows_touched_per_step",
                help="id slots per step (upper bound on unique touched rows)",
            ).set(float(cap), **lbl)
            reg.gauge(
                "embedding/sparse_grad_bytes",
                help="bytes of the SelectedRows gradient per step",
            ).set(float(cap * dim * vbytes + cap * 4), **lbl)
            reg.gauge(
                "embedding/dense_grad_bytes",
                help="bytes a dense gradient of this table would be",
            ).set(float(height * dim * vbytes), **lbl)
        if tbytes is not None:
            reg.gauge(
                "embedding/table_bytes_per_shard",
                help="per-device HBM bytes of the table at the current ep",
            ).set(float(tbytes) / max(1, shards), **lbl)
    except Exception:
        pass  # observability must never break a trace


# --------------------------------------------------------------------------
# sparse gradient op
# --------------------------------------------------------------------------


def _sparse_grad_infer(op, block):
    """(capacity, dim) values + (capacity,) rows; capacity is ids.size, which
    is dynamic when the batch dim is (-1 stays -1 — the executor re-traces
    with concrete feed shapes)."""
    w = block._var_recursive(op.inputs["W"][0])
    ids = block._var_recursive(op.inputs["Ids"][0])
    dim = int(w.shape[1])
    n, dyn = 1, False
    for d in ids.shape:
        if d == -1:
            dyn = True
        else:
            n *= int(d)
    n = -1 if dyn else n
    gv = block._var_recursive(op.outputs["W@GRAD"][0])
    gv.shape = (n, dim)
    rv = block._var_recursive(op.outputs["Rows"][0])
    rv.shape = (n,)
    rv.dtype = "int32"


@register("lookup_table_grad_sparse", no_grad=True, infer_shape=_sparse_grad_infer)
def _lookup_table_grad_sparse(ctx, ins, attrs):
    """d(loss)/d(W) as SelectedRows: every id slot becomes one (row, value)
    pair; masked slots (negative ids, padding_idx) get ROW_SENTINEL so the
    optimizer's OOB-dropping scatter ignores them. W contributes shape only."""
    (w,) = ins["W"]
    (ids,) = ins["Ids"]
    (dout,) = ins["Out@GRAD"]
    dim = w.shape[1]
    flat = ids.reshape(-1).astype(jnp.int32)
    vals = dout.reshape(-1, dim)
    invalid = flat < 0
    padding_idx = int(attrs.get("padding_idx", -1))
    if padding_idx != -1:
        pad = padding_idx if padding_idx >= 0 else padding_idx + w.shape[0]
        invalid = invalid | (flat == pad)
    rows = jnp.where(invalid, jnp.int32(ROW_SENTINEL), flat)
    _gauges(
        attrs.get("param", "?"),
        int(w.shape[0]),
        int(dim),
        int(flat.shape[0]),
        jnp.dtype(vals.dtype).itemsize,
        None,
        1,
    )
    return {"W@GRAD": [vals], "Rows": [rows]}


@register("selected_rows_to_dense", no_grad=True)
def _selected_rows_to_dense(ctx, ins, attrs):
    (vals,) = ins["X"]
    (rows,) = ins["Rows"]
    height = int(attrs["height"])
    return {"Out": [densify(rows, vals, height)]}


# --------------------------------------------------------------------------
# per-row optimizer updates
# --------------------------------------------------------------------------


def _row_update(table, states, uniq, summed, height, compute, axis_name=None):
    """Gather touched rows of table+states, apply `compute` in f32, scatter
    back in storage dtype. Runs per-shard inside shard_map (axis_name set,
    rows offset by the shard's base) or on the full table otherwise. Rows that
    are invalid (sentinel → height) or live on another shard scatter out of
    bounds and are dropped."""
    rows_local = table.shape[0]
    local = uniq - (
        lax.axis_index(axis_name) * rows_local if axis_name else 0
    )
    valid = (uniq < height) & (local >= 0) & (local < rows_local)
    gidx = jnp.where(valid, local, 0)
    sidx = jnp.where(valid, local, rows_local)  # OOB → dropped on scatter
    p_rows = jnp.take(table, gidx, axis=0).astype(jnp.float32)
    s_rows = [jnp.take(s, gidx, axis=0).astype(jnp.float32) for s in states]
    new_p, new_s = compute(p_rows, s_rows, summed)
    # mask BEFORE the scatter so invalid slots can't even race valid ones
    table = table.at[sidx].set(new_p.astype(table.dtype), mode="drop")
    states = [
        s.at[sidx].set(ns.astype(s.dtype), mode="drop")
        for s, ns in zip(states, new_s)
    ]
    return (table, *states)


def _sparse_apply(ctx, ins, attrs, state_slots, make_compute):
    """Shared driver for the *_sparse optimizer ops. state_slots names the
    row-aligned moment inputs; make_compute(attrs, scalars) returns the f32
    per-row math. Scalar state (lr, beta pows) is replicated, like the dense
    ZeRO-1 tier."""
    (p,) = ins["Param"]
    (vals,) = ins["Grad"]
    (rows,) = ins["GradRows"]
    lr = ins["LearningRate"][0].reshape(()).astype(jnp.float32)
    states = [ins[s][0] for s in state_slots]
    height = int(p.shape[0])
    # merge duplicate ids once, in f32, on the replicated (cap, dim) pair —
    # O(cap) work vs the dense path's O(height) table-wide scatter
    uniq, summed = merge_rows(rows, vals, height)
    compute = make_compute(attrs, lr)

    axis = attrs.get("axis_name") or None
    mesh = ctx.mesh
    use_shard = bool(axis) and mesh is not None and mesh.shape.get(axis, 1) > 1
    _gauges(
        attrs.get("param", "?"),
        height,
        int(p.shape[1]),
        None,
        jnp.dtype(vals.dtype).itemsize,
        height * int(p.shape[1]) * jnp.dtype(p.dtype).itemsize,
        mesh.shape.get(axis, 1) if use_shard else 1,
    )
    if use_shard:
        from ..parallel.collectives import shard_map

        nshard = len(states) + 1
        shard_spec = tuple(P((axis,), None) for _ in range(nshard))
        fn = shard_map(
            functools.partial(
                _shard_body,
                nstates=len(states),
                height=height,
                compute=compute,
                axis_name=axis,
            ),
            mesh=mesh,
            in_specs=shard_spec + (P(), P()),
            # table+moments stay row-sharded; every dp replica computed the
            # same update from the replicated (uniq, summed), so disabling
            # the replication check is sound
            out_specs=shard_spec,
            check_vma=False,
        )
        outs = fn(p, *states, uniq, summed)
    else:
        outs = _row_update(p, states, uniq, summed, height, compute)
    return outs


def _shard_body(*args, nstates, height, compute, axis_name):
    table = args[0]
    states = list(args[1 : 1 + nstates])
    uniq, summed = args[1 + nstates], args[2 + nstates]
    return _row_update(
        table, states, uniq, summed, height, compute, axis_name=axis_name
    )


def _pack(outs, out_slots):
    return {slot: [v] for slot, v in zip(out_slots, outs)}


@register("sgd_sparse", no_grad=True, infer_shape=lambda op, block: None)
def _sgd_sparse(ctx, ins, attrs):
    """Per-row SGD — exactly the dense sgd math restricted to touched rows
    (untouched rows are unchanged in both), so sparse-vs-dense SGD training
    is bit-identical on f32 tables."""

    def make(attrs, lr):
        def compute(p_rows, s_rows, g):
            return p_rows - lr * g, []

        return compute

    outs = _sparse_apply(ctx, ins, attrs, (), make)
    return _pack(outs, ("ParamOut",))


@register("adagrad_sparse", no_grad=True, infer_shape=lambda op, block: None)
def _adagrad_sparse(ctx, ins, attrs):
    def make(attrs, lr):
        eps = attrs.get("epsilon", 1e-6)

        def compute(p_rows, s_rows, g):
            (mom,) = s_rows
            mom_out = mom + jnp.square(g)
            return p_rows - lr * g / (jnp.sqrt(mom_out) + eps), [mom_out]

        return compute

    outs = _sparse_apply(ctx, ins, attrs, ("Moment",), make)
    return _pack(outs, ("ParamOut", "MomentOut"))


def _adam_sparse_lower(ctx, ins, attrs):
    """Lazy Adam (reference adam_op.h SparseAdamFunctor, lazy_mode=True):
    moments of untouched rows are frozen, not decayed — the property the
    touched-rows-only test asserts bit-exactly. Beta pows advance globally
    via the optimizer's _finish_update scale ops, same as dense."""
    b1p = ins["Beta1Pow"][0].reshape(()).astype(jnp.float32)
    b2p = ins["Beta2Pow"][0].reshape(()).astype(jnp.float32)

    def make(attrs, lr):
        b1 = attrs.get("beta1", 0.9)
        b2 = attrs.get("beta2", 0.999)
        eps = attrs.get("epsilon", 1e-8)
        lr_t = lr * jnp.sqrt(1 - b2p) / (1 - b1p)

        def compute(p_rows, s_rows, g):
            m1, m2 = s_rows
            m1o = b1 * m1 + (1 - b1) * g
            m2o = b2 * m2 + (1 - b2) * jnp.square(g)
            p_out = p_rows - lr_t * m1o / (jnp.sqrt(m2o) + eps)
            return p_out, [m1o, m2o]

        return compute

    outs = _sparse_apply(ctx, ins, attrs, ("Moment1", "Moment2"), make)
    return _pack(outs, ("ParamOut", "Moment1Out", "Moment2Out"))


register("adam_sparse", no_grad=True, infer_shape=lambda op, block: None)(
    _adam_sparse_lower
)


# --------------------------------------------------------------------------
# custom grad maker: sparse vs dense per lookup instance
# --------------------------------------------------------------------------


def _forward_consumers(block, w_name):
    """Differentiable forward-role ops reading w_name (backward/optimize ops
    excluded by role bit — by maker time the block already holds the grad ops
    appended for later program positions)."""
    n = 0
    for o in block.ops:
        role = int(o.attrs.get(OpRole.OP_ROLE_KEY, 0) or 0)
        if role & (OpRole.Backward | OpRole.Optimize):
            continue
        if w_name in o.input_arg_names:
            n += 1
    return n


def _lookup_grad_maker(op, block, grad_map):
    """Grad for lookup_table/embedding/distributed_lookup_table.

    is_sparse=True AND the table has a single differentiable consumer →
    SelectedRows pair via lookup_table_grad_sparse. Otherwise the dense f32
    scatter-add (lookup_table_grad) — when the table is looked up twice its
    contributions must be summed, which backward.py only knows how to do
    densely (the reference merges multi-consumer SelectedRows the same way:
    merged to dense before apply)."""
    w_name = op.inputs["W"][0]
    ids_name = op.inputs["Ids"][0]
    out_name = op.outputs["Out"][0]
    g_out = grad_map.get(out_name)
    g_w = grad_map.get(w_name)
    if g_out is None or g_w is None:
        return []
    attrs = {
        "padding_idx": int(op.attrs.get("padding_idx", -1)),
        "param": w_name,
        OpRole.OP_ROLE_VAR_KEY: [w_name, g_w],
    }
    w_var = block._var_recursive(w_name)
    sparse_ok = (
        bool(op.attrs.get("is_sparse", False))
        and g_w == grad_var_name(w_name)
        and _forward_consumers(block, w_name) == 1
    )
    if not sparse_ok:
        return [
            {
                "type": "lookup_table_grad",
                "inputs": {
                    "W": [w_name],
                    "Ids": [ids_name],
                    "Out@GRAD": [g_out],
                },
                "outputs": {"W@GRAD": [g_w]},
                "attrs": attrs,
            }
        ]
    rows_name = rows_var_name(g_w)
    if not block.has_var(rows_name):
        rv = block.create_var(
            name=rows_name,
            shape=[-1],
            dtype="int32",
            persistable=False,
        )
        rv.stop_gradient = True
    g_var = block._var_recursive(g_w)
    mark_selected_rows(g_var, rows_name, int(w_var.shape[0]))
    return [
        {
            "type": "lookup_table_grad_sparse",
            "inputs": {"W": [w_name], "Ids": [ids_name], "Out@GRAD": [g_out]},
            "outputs": {"W@GRAD": [g_w], "Rows": [rows_name]},
            "attrs": attrs,
        }
    ]


# attach to the already-registered lookup ops (core_ops.py / parallel_ops.py
# own the forward lowerings; the maker is the backward policy layer)
for _t in ("lookup_table", "embedding", "distributed_lookup_table"):
    OPS[_t].grad = _lookup_grad_maker
