"""Declarative sharding rules: param/activation names -> PartitionSpecs.

The single source of truth for tensor placement over the dp×fsdp×tp×sp×ep×pp
mesh. Before this engine, sharding was hand-placed per feature — ZeRO-1
constraints inside core_ops._opt_f32, the embedding `ep` spec inside
embedding/engine.py — which could not express tensor parallelism or FSDP at
all. Rules replace both with one mechanism (exemplars: EasyLM's
match_partition_rules regex table and MaxText's SpecLayout canonical
per-role layouts):

- `ShardingRules` holds ordered (regex, spec) pairs. A name resolves by
  re.search against every rule, LAST match wins (append more-specific rules
  after catch-alls). Unmatched names stay replicated. Specs follow the
  sharding_spec tuple convention: one entry per dim, each None | axis name |
  tuple of axis names, e.g. ("fsdp", "tp") or (("fsdp", "tp"), None).
- `SpecLayout` names the canonical layouts for the transformer roles
  (embedding / column-parallel / row-parallel / vector) so model code asks
  for intents, not axis tuples.
- `Resolver` binds rules to a live mesh + lowered block: prunes axes the
  mesh doesn't have, degrades non-divisible dims to replication, aliases
  optimizer accumulators to their parameter's layout, and layers the legacy
  `Variable.sharding_spec` attribute (parallel.shard_parameter) and the
  ZeRO-1 state tier underneath explicit rules. The executor consults it at
  its one placement choke point (state in/out_shardings + op-output
  constraints), so the same program runs on ANY mesh — axes it lacks simply
  prune away.

Wire behavior falls out of GSPMD (docs/parallelism.md): an fsdp rule on a
parameter makes its use all-gather and its gradient combine reduce-scatter
(FSDP); a ("fsdp","tp")/("tp","fsdp") column/row pair on a matmul pair
makes the partitioner place the tp all-reduce after the second matmul
(Megatron TP).
"""

import re

import numpy as np

__all__ = [
    "MESH_AXES",
    "ShardingRules",
    "SpecLayout",
    "program_rules",
    "Resolver",
    "opt_constrain_ins",
    "opt_constrain_outs",
]

# the canonical mesh axes (parallel.mesh.MeshConfig order). Rules may only
# name these; anything else is a typo caught at add() time, not a silent
# replication at run time.
MESH_AXES = ("dp", "fsdp", "tp", "sp", "ep", "pp")


def _normalize_spec(spec):
    """Canonicalize one spec tuple: each dim entry None | axis | tuple of
    axes. Returns a hashable nested tuple; raises ValueError on unknown
    axis names or malformed entries."""
    if spec is None:
        return None
    out = []
    for entry in spec:
        if entry is None:
            out.append(None)
            continue
        axes = entry if isinstance(entry, (tuple, list)) else (entry,)
        for a in axes:
            if a not in MESH_AXES:
                raise ValueError(
                    "unknown mesh axis %r in sharding spec %r (valid: %s)"
                    % (a, tuple(spec), ", ".join(MESH_AXES))
                )
        if len(set(axes)) != len(axes):
            raise ValueError("repeated axis in sharding spec entry %r" % (entry,))
        out.append(tuple(axes) if len(axes) > 1 else axes[0])
    return tuple(out)


class ShardingRules:
    """Ordered (regex, PartitionSpec-tuple) rules, LAST match wins.

    Matching uses re.search (a bare parameter name matches anywhere in the
    var name — anchor with ^…$ when that is too loose; note an unanchored
    pattern also matches derived names like `<param>@GRAD` and the
    `<param>_<slot>_acc_<k>` accumulators, which is usually what you want
    for a storage layout). `add` validates axis names eagerly and returns
    self for chaining."""

    def __init__(self, rules=()):
        self._rules = []  # [(pattern str, compiled, spec)]
        for pattern, spec in rules:
            self.add(pattern, spec)

    def add(self, pattern, spec):
        self._rules.append((pattern, re.compile(pattern), _normalize_spec(spec)))
        return self

    def extend(self, other):
        """Append another rule set's rules after this one's (so `other`
        wins ties under last-match)."""
        if other is not None:
            for pattern, _, spec in other._rules:
                self._rules.append((pattern, re.compile(pattern), spec))
        return self

    def match(self, name):
        """Resolved spec tuple for `name`, or None (replicated) when no rule
        matches. A matching rule with spec None explicitly forces
        replication (useful to exempt names from an earlier catch-all)."""
        found = None
        for _, rx, spec in self._rules:
            if rx.search(name):
                found = (spec,)
        return found[0] if found is not None else None

    def fingerprint(self):
        """Hashable identity for executor compile-cache keys: rules are
        attached to live Program objects and may grow after a first run."""
        return tuple((p, s) for p, _, s in self._rules)

    def __len__(self):
        return len(self._rules)

    def __iter__(self):
        for pattern, _, spec in self._rules:
            yield pattern, spec

    def __repr__(self):
        return "ShardingRules(%r)" % (list(self),)


class SpecLayout:
    """Canonical per-role layouts over the standard axes — the MaxText-style
    vocabulary model code uses instead of hand-written axis tuples.

    Roles (2-D weights are [in_features, out_features], fluid convention):

    - embedding():        ((fsdp, tp), None) — vocab rows split over both
                          model axes, feature dim whole.
    - column_parallel():  (fsdp, tp)  — qkv / ffn-up: out-features over tp
                          (per-head shards), in-features over fsdp.
    - row_parallel():     (tp, fsdp)  — attn-out / ffn-down: in-features
                          over tp so the pair's reduce lands HERE (GSPMD
                          places one tp all-reduce after the second matmul).
    - vector():           (fsdp,)     — biases / norm scales: fsdp only
                          (tp-sharding rank-1 state buys nothing).
    """

    def __init__(self, fsdp_axis="fsdp", tp_axis="tp", ep_axis="ep"):
        self.fsdp_axis = fsdp_axis
        self.tp_axis = tp_axis
        self.ep_axis = ep_axis

    def embedding(self):
        return ((self.fsdp_axis, self.tp_axis), None)

    def column_parallel(self):
        return (self.fsdp_axis, self.tp_axis)

    def row_parallel(self):
        return (self.tp_axis, self.fsdp_axis)

    def vector(self):
        return (self.fsdp_axis,)

    def transformer_rules(self, column=(), row=(), vector=(), embedding=()):
        """Build a ShardingRules from name patterns per role (the common
        case: one call listing the model's weight-name regexes)."""
        rules = ShardingRules()
        for pat in embedding:
            rules.add(pat, self.embedding())
        for pat in column:
            rules.add(pat, self.column_parallel())
        for pat in row:
            rules.add(pat, self.row_parallel())
        for pat in vector:
            rules.add(pat, self.vector())
        return rules


def program_rules(program):
    """The ShardingRules attached to `program`, created on first use.
    Model-building code (embedding engine, user layers) registers storage
    layouts here; ParallelExecutor merges them with
    BuildStrategy.sharding_rules (build-strategy rules win ties) and the
    pass pipeline carries them across program rewrites."""
    rules = getattr(program, "_sharding_rules", None)
    if rules is None:
        rules = ShardingRules()
        program._sharding_rules = rules
    return rules


class Resolver:
    """Rules bound to a live mesh: name -> pruned spec / NamedSharding.

    Precedence per name (first hit wins):
      1. explicit rules (program rules + BuildStrategy rules, last match
         wins within the combined list);
      2. accumulator alias: optimizer-state tensors (ZERO1_STATE_SLOTS)
         resolve through their parameter's name, so moments always inherit
         the param's storage layout without name-pattern gymnastics;
      3. the legacy `Variable.sharding_spec` attribute
         (parallel.shard_parameter);
      4. ZeRO-1 state names (set by the executor) -> (zero1_axis,);
      5. replicated.

    Pruning makes any program runnable on any mesh: axes the mesh lacks (or
    has at extent 1) drop out; a dim whose size doesn't divide its axes'
    combined extent degrades to replication for that dim; a spec longer
    than the value's rank resolves to replicated. All-None specs collapse
    to None so callers can treat None as 'no placement opinion'."""

    def __init__(self, mesh, rules=None, var_lookup=None):
        self.mesh = mesh
        self.rules = rules if rules is not None and len(rules) else None
        self._var_lookup = var_lookup  # name -> Variable or None (legacy attr)
        self.aliases = {}  # state/accumulator name -> param name
        self.zero1_axis = None
        self.zero1_names = frozenset()
        # structured record of every divisibility degradation _prune applied
        # (was silent before the static analyzer landed): [(name, dim, axes,
        # dim_size, extent)], recorded once per (name, dim) and counted into
        # the observability registry (analysis/sharding_degraded). fluidlint's
        # sharding-rules checker reports the same condition statically.
        self.degraded = []
        self._degraded_seen = set()

    def set_zero1(self, axis, names):
        self.zero1_axis = axis
        self.zero1_names = frozenset(names)

    def add_aliases(self, ops):
        """Map every optimizer-state input (ZERO1_STATE_SLOTS) to its op's
        Param name so layer 2 can resolve accumulators."""
        from ..ops.core_ops import ZERO1_STATE_SLOTS

        for op in ops:
            slots = ZERO1_STATE_SLOTS.get(op.type)
            if not slots:
                continue
            params = op.inputs.get("Param", ())
            if not params:
                continue
            for slot in slots:
                for name in op.inputs.get(slot, ()):
                    self.aliases[name] = params[0]

    def _record_degraded(self, name, dim, axes, dim_size, extent):
        key = (name, dim)
        if name is None or key in self._degraded_seen:
            return
        self._degraded_seen.add(key)
        self.degraded.append((name, dim, axes, dim_size, extent))
        from ..observability import registry as _registry

        _registry.default_registry().counter(
            "analysis/sharding_degraded",
            "spec dims degraded to replication because the dim size did not "
            "divide the mesh-axes extent",
        ).inc(axes="+".join(axes))

    def _prune(self, spec, shape, name=None):
        if spec is None:
            return None
        shape = tuple(shape) if shape is not None else None
        if shape is not None and len(spec) > len(shape):
            return None
        out = []
        for dim, entry in enumerate(spec):
            axes = () if entry is None else (
                tuple(entry) if isinstance(entry, tuple) else (entry,)
            )
            kept = tuple(a for a in axes if self.mesh.shape.get(a, 1) > 1)
            if kept and shape is not None:
                extent = int(np.prod([self.mesh.shape[a] for a in kept]))
                if shape[dim] % extent != 0:
                    self._record_degraded(name, dim, kept, shape[dim], extent)
                    kept = ()
            out.append(kept if len(kept) > 1 else (kept[0] if kept else None))
        if all(e is None for e in out):
            return None
        return tuple(out)

    def rule_spec(self, name, shape=None):
        """Layers 1-3 only (explicit rules / alias / legacy attr), pruned to
        this mesh. The layer the ZeRO-1 tier defers to: a param whose rule
        survives pruning leaves the zero1 path entirely."""
        raw = None
        if self.rules is not None:
            raw = self.rules.match(name)
            if raw is None and name in self.aliases:
                raw = self.rules.match(self.aliases[name])
        if raw is None and self._var_lookup is not None:
            v = self._var_lookup(name)
            if v is None and name in self.aliases:
                v = self._var_lookup(self.aliases[name])
            spec = getattr(v, "sharding_spec", None)
            if spec is not None:
                raw = _normalize_spec(spec)
        return self._prune(raw, shape, name=name)

    def audit(self, names):
        """Dead-rule audit: patterns matching none of `names` (typically the
        lowered block's vars plus the scope's persistables) are typos or
        stale layouts silently replicating their target. Returns the dead
        pattern list and counts each into the observability registry
        (analysis/sharding_dead_rules); the executor runs this once per
        compile, fluidlint's sharding-rules checker statically."""
        if self.rules is None:
            return []
        names = list(names)
        dead = []
        for pattern, rx, _ in self.rules._rules:
            if not any(rx.search(n) for n in names):
                dead.append(pattern)
        if dead:
            from ..observability import registry as _registry

            c = _registry.default_registry().counter(
                "analysis/sharding_dead_rules",
                "sharding rules whose pattern matched no var at compile",
            )
            for pattern in dead:
                c.inc(pattern=pattern)
        return dead

    def spec(self, name, shape=None):
        """Full precedence chain -> pruned spec tuple or None (replicated)."""
        s = self.rule_spec(name, shape)
        if s is not None:
            return s
        if name in self.zero1_names:
            return (self.zero1_axis,)
        return None

    def named_sharding(self, name, shape=None):
        from jax.sharding import NamedSharding, PartitionSpec as P

        s = self.spec(name, shape)
        return NamedSharding(self.mesh, P() if s is None else P(*s))

    def constrain(self, x, spec):
        from jax import lax
        from jax.sharding import NamedSharding, PartitionSpec as P

        return lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, P(*spec))
        )

    def constrain_outputs(self, op, env):
        """The activation/placement hook _lower_one calls after binding an
        op's outputs: every output name with an explicit rule (layers 1-3)
        gets a with_sharding_constraint in its pruned layout. Idempotent on
        already-placed values; a no-op for unmatched names, so per-op cost
        is a few regex searches at trace time."""
        for name in op.output_arg_names:
            v = env.get(name)
            if v is None or not hasattr(v, "shape"):
                continue
            s = self.rule_spec(name, np.shape(v))
            if s is not None:
                env[name] = self.constrain(v, s)


# ---------------------------------------------------------------------------
# Optimizer-op constraints (the core_ops._opt_f32 seam)
# ---------------------------------------------------------------------------
# Both helpers are called by _opt_f32 around the f32 update math: ins BEFORE
# the upcast (the wire carries the grad's native dtype; the upcast then
# touches only the local shard), outs AFTER the downcast.


def _op_param_spec(ctx, ins):
    """The Param's storage spec from the rule engine (layers 1-3), or None.
    Identified via ctx.op (set by registry._lower_one); shape from the
    traced Param value, so pruning sees the real dims."""
    resolver = getattr(ctx, "sharding", None)
    op = getattr(ctx, "op", None)
    if resolver is None or op is None:
        return None
    params = op.inputs.get("Param", ())
    pvals = ins.get("Param", ())
    if not params or not pvals or pvals[0] is None:
        return None
    return resolver.rule_spec(params[0], np.shape(pvals[0]))


def _zero1_active(ctx):
    axis = getattr(ctx, "zero1_axis", None)
    mesh = getattr(ctx, "mesh", None)
    if axis and mesh is not None and mesh.shape.get(axis, 1) > 1:
        return mesh, axis
    return None, None


def opt_constrain_ins(ctx, ins):
    """Pin optimizer-op inputs to the parameter's storage layout.

    Rule-sharded param (FSDP / TP): every floating input WITH THE PARAM'S
    SHAPE (Param, Grad, moments) is constrained to the param's spec. On the
    gradient — still an unpositioned cross-replica partial sum here — GSPMD
    materializes the combine as reduce-scatter over the sharded dims (the
    FSDP grad path); on the param and moments it confirms the stored layout.
    Scalar state (LearningRate, Beta*Pow) never matches the shape and stays
    replicated.

    Otherwise, under the ZeRO-1 tier: every shardable floating input is
    pinned to a 1/dp shard along dim 0 — reduce-scatter on the grad, local
    slice on the replicated param, stored-layout no-op on the moments."""
    import jax.numpy as jnp

    from . import collectives as _coll

    pspec = _op_param_spec(ctx, ins)
    if pspec is not None:
        resolver = ctx.sharding
        pshape = np.shape(ins["Param"][0])
        out = {}
        for slot, vals in ins.items():
            cons = []
            for a in vals:
                if (
                    a is not None
                    and np.shape(a) == pshape
                    and jnp.issubdtype(jnp.asarray(a).dtype, jnp.floating)
                ):
                    a = resolver.constrain(a, pspec)
                cons.append(a)
            out[slot] = cons
        return out

    mesh, axis = _zero1_active(ctx)
    if mesh is None:
        return ins
    out = {}
    for slot, vals in ins.items():
        cons = []
        for a in vals:
            if (
                a is not None
                and jnp.issubdtype(jnp.asarray(a).dtype, jnp.floating)
                and _coll.zero1_shardable(jnp.shape(a), mesh, axis)
            ):
                a = _coll.constrain_sharded(a, mesh, axis)
            cons.append(a)
        out[slot] = cons
    return out


def opt_constrain_outs(ctx, res, ins):
    """Pin optimizer-op outputs to their storage layouts.

    Rule-sharded param: ParamOut and the moment outs stay IN the param's
    spec — under FSDP the param itself lives sharded (all-gather happens at
    next use, placed by GSPMD), so unlike ZeRO-1 there is no gather here.

    ZeRO-1 tier: ParamOut is constrained back to replicated (GSPMD -> the
    param all-gather, overlappable with the rest of the step) — but pinned
    to the sharded layout FIRST: without that the partitioner may push the
    replicated constraint through the update arithmetic and gather every
    operand separately (observed on the CPU partitioner: p and lr·v each
    all-gathered, 2x the wire bytes). Every other shardable state output
    (moments) stays sharded — the 1/dp state-memory and HBM-traffic win."""
    import jax.numpy as jnp

    from . import collectives as _coll

    pspec = _op_param_spec(ctx, ins)
    if pspec is not None:
        resolver = ctx.sharding
        pshape = np.shape(ins["Param"][0])
        out = {}
        for slot, vals in res.items():
            cons = []
            for v in vals:
                if (
                    v is not None
                    and np.shape(v) == pshape
                    and jnp.issubdtype(jnp.asarray(v).dtype, jnp.floating)
                ):
                    v = resolver.constrain(v, pspec)
                cons.append(v)
            out[slot] = cons
        return out

    mesh, axis = _zero1_active(ctx)
    if mesh is None:
        return res
    out = {}
    for slot, vals in res.items():
        cons = []
        for v in vals:
            if v is not None and jnp.issubdtype(
                jnp.asarray(v).dtype, jnp.floating
            ):
                if slot == "ParamOut":
                    if _coll.zero1_shardable(jnp.shape(v), mesh, axis):
                        v = _coll.constrain_sharded(v, mesh, axis)
                    v = _coll.constrain_replicated(v, mesh)
                elif _coll.zero1_shardable(jnp.shape(v), mesh, axis):
                    v = _coll.constrain_sharded(v, mesh, axis)
            cons.append(v)
        out[slot] = cons
    return out
