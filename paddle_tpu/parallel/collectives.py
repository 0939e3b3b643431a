"""Collective wrappers for shard_map code (reference: the NCCL op set —
all_reduce_op_handle.cc, reduce_op_handle.cc, broadcast_op_handle.cc —
and the legacy nccl ops). Inside shard_map these lower to XLA collectives
over ICI/DCN.

The second half are GSPMD-path equivalents: sharding CONSTRAINTS placed on
values inside a jit-over-Mesh trace (no shard_map region needed). GSPMD
materializes them as the matching collectives — constraining a cross-replica
partial sum to a sharded layout yields reduce-scatter, constraining a sharded
value back to replicated yields all-gather — which is how the ZeRO-1
optimizer tier (ReduceStrategy.Reduce, docs/parallelism.md) expresses
reduce-scatter(grad) → sharded update → all-gather(param) while leaving XLA
free to overlap the collectives with backward compute."""

import jax
import jax.numpy as jnp
from jax import lax, shard_map

__all__ = [
    "shard_map",
    "all_reduce",
    "all_gather",
    "reduce_scatter",
    "ppermute_shift",
    "broadcast",
    "axis_index",
    "axis_size",
    "constrain_sharded",
    "constrain_replicated",
    "zero1_shardable",
]


def all_reduce(x, axis_name, op="sum"):
    if op == "sum":
        return lax.psum(x, axis_name)
    if op == "max":
        return lax.pmax(x, axis_name)
    if op == "min":
        return lax.pmin(x, axis_name)
    if op == "mean":
        return lax.pmean(x, axis_name)
    raise ValueError("unknown reduce op %r" % op)


def all_gather(x, axis_name, axis=0, tiled=True):
    return lax.all_gather(x, axis_name, axis=axis, tiled=tiled)


def reduce_scatter(x, axis_name, axis=0):
    return lax.psum_scatter(x, axis_name, scatter_dimension=axis, tiled=True)


def ppermute_shift(x, axis_name, shift=1):
    """Rotate shards around the ring: each rank sends to rank+shift."""
    n = axis_size(axis_name)
    perm = [(i, (i + shift) % n) for i in range(n)]
    return lax.ppermute(x, axis_name, perm)


def broadcast(x, axis_name, root=0):
    idx = lax.axis_index(axis_name)
    masked = jnp.where(idx == root, x, jnp.zeros_like(x))
    return lax.psum(masked, axis_name)


def axis_index(axis_name):
    return lax.axis_index(axis_name)


def axis_size(axis_name):
    """Static extent of a bound mesh axis."""
    return lax.axis_size(axis_name)


# ---------------------------------------------------------------------------
# GSPMD-path constraints (jit-over-Mesh traces, no shard_map)
# ---------------------------------------------------------------------------


def zero1_shardable(shape, mesh, axis_name):
    """True iff an array of `shape` can hold a 1/axis shard per rank: the
    leading dim divides evenly over the axis extent. Scalars and the shape-[1]
    optimizer scalars (LearningRate, Beta*Pow) are excluded by construction —
    they stay replicated, which keeps their update math identical to the
    all-reduce path."""
    n = mesh.shape.get(axis_name, 1)
    return n > 1 and len(shape) >= 1 and shape[0] % n == 0


def constrain_sharded(x, mesh, axis_name, dim=0):
    """Constrain `x` to be sharded over `axis_name` along `dim`. Applied to a
    cross-replica gradient partial sum, GSPMD lowers the combine as
    reduce-scatter ((p-1)/p · bytes on the wire) instead of all-reduce
    (2(p-1)/p); applied to replicated state it is a local slice."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    spec = [None] * x.ndim
    spec[dim] = axis_name
    return lax.with_sharding_constraint(x, NamedSharding(mesh, P(*spec)))


def constrain_replicated(x, mesh):
    """Constrain `x` to be fully replicated. Applied to a sharded updated
    parameter, GSPMD materializes the all-gather back to every rank."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    return lax.with_sharding_constraint(x, NamedSharding(mesh, P()))
