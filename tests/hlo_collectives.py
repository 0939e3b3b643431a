"""The collectives of a compiled step, read from `compiled_hlo()` text: what
the wire-signature tests of test_parallel_executor.py and
test_sharding_rules.py compare with the analytic bytes. Bytes, not opcodes:
the CPU partitioner may spell a reduce-scatter as all-reduce + dynamic-slice,
and the combined tensor's bytes are the same under either spelling."""

import re

import numpy as np

_BYTES = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "s64": 8, "pred": 1}
_LINE = re.compile(
    r"= (.*?) (all-reduce|reduce-scatter|all-gather)(-start)?\(")
_IOTA = re.compile(r"replica_groups=\[(\d+),(\d+)\]<=\[([\d,]+)\](?:T\(([\d,]+)\))?")
_EXPLICIT = re.compile(r"replica_groups=\{(\{[\d,]+\}(?:,\{[\d,]+\})*)\}")


def _groups(line):
    """replica_groups -> a set of frozensets of device ids, from either the
    explicit {{0,1},{2,3}} or the iota [G,S]<=[dims]T(perm) spelling."""
    m = _EXPLICIT.search(line)
    if m:
        return {frozenset(int(x) for x in g.split(","))
                for g in m.group(1)[1:-1].split("},{")}
    m = _IOTA.search(line)
    if not m:
        return set()
    ids = np.arange(int(m.group(1)) * int(m.group(2))).reshape(
        [int(x) for x in m.group(3).split(",")])
    if m.group(4):
        ids = ids.transpose([int(x) for x in m.group(4).split(",")])
    return {frozenset(g) for g in ids.reshape(int(m.group(1)), -1).tolist()}


def collectives(hlo, mesh):
    """[(op, axis, shape, bytes)] for every all-reduce, reduce-scatter and
    all-gather result: `shape` and `bytes` are the whole combined tensor's
    (a reduce-scatter's result is one shard of it), `axis` the mesh axis whose
    groups the replica_groups are, or "mixed"."""
    sizes = [mesh.shape[a] for a in mesh.axis_names]
    ids = np.arange(int(np.prod(sizes))).reshape(sizes)
    by_axis = {
        a: {frozenset(g) for g in
            np.moveaxis(ids, k, -1).reshape(-1, sizes[k]).tolist()}
        for k, a in enumerate(mesh.axis_names)
    }
    out = []
    for line in hlo.splitlines():
        m = _LINE.search(line)
        if not m:
            continue
        groups = _groups(line)
        axis = next((a for a, g in by_axis.items() if groups and groups <= g),
                    "mixed")
        p = len(next(iter(groups))) if groups else mesh.size
        shapes = re.findall(r"(\w+)\[([\d,]*)\]", m.group(1))
        if m.group(3) and m.group(2) == "all-gather":
            shapes = shapes[len(shapes) // 2:]  # a -start holds (operands, results)
        for dtype, dims in shapes:
            dims = tuple(int(d) for d in dims.split(",") if d)
            n = int(np.prod(dims or (1,))) * _BYTES[dtype]
            out.append((m.group(2), axis, dims,
                        n * p if m.group(2) == "reduce-scatter" else n))
    return out


def reduced_bytes(rows, axis=None):
    return sum(b for op, a, _, b in rows
               if op != "all-gather" and axis in (None, a))


def gathered_bytes(rows, axis=None):
    return sum(b for op, a, _, b in rows
               if op == "all-gather" and axis in (None, a))


def within(got, want, tol=0.10):
    """The tolerance every wire signature is held to."""
    return abs(got - want) <= tol * want
