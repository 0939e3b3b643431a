"""Sharding-rule engine tests (parallel/sharding_rules.py): rule matching
semantics, Resolver pruning, and end-to-end parity of the two strategies the
engine adds — Megatron tensor parallelism (column/row pairs) and FSDP
(params + grads + moments sharded with all-gather-on-use) — against the
plain single-device Executor, plus composition with elastic checkpoints
(topology-changing resume), the fused Pallas passes (decline under tp), and
the embedding engine's migrated `ep` rule."""

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu import framework
from paddle_tpu.executor import Scope, global_scope, scope_guard
from paddle_tpu.parallel import MeshConfig, ShardingRules, SpecLayout, make_mesh
from paddle_tpu.parallel.sharding_rules import Resolver

_RTOL, _ATOL = 2e-3, 2e-4


# ---------------------------------------------------------------------------
# rule matching + resolver pruning (no executor)
# ---------------------------------------------------------------------------


def test_rules_last_match_wins():
    rules = ShardingRules([
        (r"\.w_0$", ("fsdp", None)),        # catch-all for weights
        (r"^fc_1\.w_0$", ("tp", None)),     # more specific, added later
    ])
    assert rules.match("fc_0.w_0") == ("fsdp", None)
    assert rules.match("fc_1.w_0") == ("tp", None)
    # unmatched -> None (replicated)
    assert rules.match("fc_0.b_0") is None
    # a later None spec explicitly exempts a name from the catch-all
    rules.add(r"^fc_2\.w_0$", None)
    assert rules.match("fc_2.w_0") is None


def test_rules_unanchored_covers_derived_names():
    """An unanchored param-name rule reaches the grad and accumulator names
    derived from it — the documented storage-layout behavior."""
    rules = ShardingRules([("emb_table", ("ep", None))])
    assert rules.match("emb_table") == ("ep", None)
    assert rules.match("emb_table_moment1_acc_0") == ("ep", None)


def test_rules_bad_axis_raises():
    with pytest.raises(ValueError):
        ShardingRules([("w", ("dp2",))])
    with pytest.raises(ValueError):
        ShardingRules().add("w", ("model",))
    with pytest.raises(ValueError):  # repeated axis within one dim entry
        ShardingRules().add("w", (("tp", "tp"), None))


def test_resolver_pruning():
    mesh = make_mesh(MeshConfig(dp=2, tp=2, sp=2))
    res = Resolver(mesh, rules=ShardingRules([
        ("a", ("fsdp", "tp")),
        ("b", ("tp", None)),
        ("c", ("tp", "dp")),
        ("d", ("tp",)),
    ]))
    # fsdp has extent 1 on this mesh -> that dim degrades to replicated
    assert res.rule_spec("a", (8, 8)) == (None, "tp")
    # dim 0 not divisible by tp=2 -> degrade; all-None collapses to None
    assert res.rule_spec("b", (3, 8)) is None
    # rank mismatch -> replicated
    assert res.rule_spec("c", (4,)) is None
    # scalar -> replicated
    assert res.rule_spec("d", ()) is None
    # unmatched -> replicated
    assert res.rule_spec("z", (8, 8)) is None


# ---------------------------------------------------------------------------
# end-to-end parity helpers
# ---------------------------------------------------------------------------


def _build_adam():
    x = fluid.layers.data(name="x", shape=[16], dtype="float32")
    y = fluid.layers.data(name="y", shape=[1], dtype="int64")
    h = fluid.layers.fc(x, size=32, act="relu")
    logits = fluid.layers.fc(h, size=4)
    loss = fluid.layers.mean(fluid.layers.softmax_with_cross_entropy(logits, y))
    fluid.optimizer.Adam(learning_rate=0.01).minimize(loss)
    return loss


def _make_data(rng, n):
    x = rng.randn(n, 16).astype("float32")
    y = (np.abs(x[:, :4]).argmax(1)).astype("int64").reshape(n, 1)
    return x, y


def _train(batches, mesh_cfg=None, rules=None, seed=3):
    """Loss trajectory (+ final scope, pe) for the MLP+Adam model: plain
    Executor when mesh_cfg is None, else ParallelExecutor under the given
    MeshConfig and BuildStrategy.sharding_rules."""
    from paddle_tpu.parallel_executor import BuildStrategy

    main, startup = framework.Program(), framework.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        loss = _build_adam()
    exe = fluid.Executor()
    losses = []
    scope = Scope(seed=seed)
    with scope_guard(scope):
        exe.run(startup)
        pe = None
        if mesh_cfg is not None:
            strat = BuildStrategy()
            strat.sharding_rules = rules
            pe = fluid.ParallelExecutor(
                loss_name=loss.name, main_program=main, build_strategy=strat,
                scope=scope, mesh_config=mesh_cfg,
            )
        for x, y in batches:
            if pe is not None:
                (l,) = pe.run(fetch_list=[loss.name], feed={"x": x, "y": y})
            else:
                (l,) = exe.run(main, feed={"x": x, "y": y},
                               fetch_list=[loss.name])
            losses.append(float(np.asarray(l).reshape(-1)[0]))
    return losses, scope, pe


def _spec_axes(scope, name):
    val = scope.vars[name]
    if not hasattr(val, "sharding"):
        return ()
    flat = []
    for entry in val.sharding.spec:
        if entry is None:
            continue
        flat.extend(entry if isinstance(entry, tuple) else (entry,))
    return tuple(flat)


# fc params: fc_0.w_0 (16,32), fc_0.b_0 (32,), fc_1.w_0 (32,4), fc_1.b_0 (4,)
_TP_RULES = [
    (r"^fc_0\.w_0$", (None, "tp")),
    (r"^fc_0\.b_0$", ("tp",)),
    (r"^fc_1\.w_0$", ("tp", None)),
]
_FSDP_RULES = [(r"^fc_\d+\.(w|b)_0$", ("fsdp",))]
_PARAMS = ("fc_0.w_0", "fc_0.b_0", "fc_1.w_0", "fc_1.b_0")


def test_tp_rules_match_single_device():
    """Megatron column/row pair over dp4 x tp2: same trajectory as the plain
    Executor, with the weights (and their Adam moments, via the resolver's
    accumulator alias) STORED tp-sharded."""
    rng = np.random.RandomState(0)
    batches = [_make_data(rng, 64) for _ in range(6)]
    single, _, _ = _train(batches)
    multi, scope, pe = _train(batches, MeshConfig(dp=4, tp=2), _TP_RULES)
    np.testing.assert_allclose(single, multi, rtol=_RTOL, atol=_ATOL)
    if pe.device_count > 1:
        assert _spec_axes(scope, "fc_0.w_0") == ("tp",)
        assert _spec_axes(scope, "fc_1.w_0") == ("tp",)
        assert _spec_axes(scope, "fc_1.b_0") == ()  # no rule -> replicated
        moments = [n for n in scope.vars
                   if n.startswith("fc_0.w_0_moment") and "_acc" in n]
        assert moments
        for n in moments:
            assert _spec_axes(scope, n) == ("tp",), n


def test_fsdp_rules_match_single_device():
    """FSDP over dp2 x fsdp4: params + moments live 1/4-sharded (all-gather
    at use), trajectory identical to the plain Executor."""
    rng = np.random.RandomState(1)
    batches = [_make_data(rng, 64) for _ in range(6)]
    single, _, _ = _train(batches)
    multi, scope, pe = _train(batches, MeshConfig(dp=2, fsdp=4), _FSDP_RULES)
    np.testing.assert_allclose(single, multi, rtol=_RTOL, atol=_ATOL)
    if pe.device_count > 1:
        for name in _PARAMS:
            assert _spec_axes(scope, name) == ("fsdp",), name
            # a quarter of the parameter lives on a chip
            val = scope.vars[name]
            assert val.addressable_shards[0].data.nbytes * 4 == val.nbytes
        moments = [n for n in scope.vars if "_moment" in n and "_acc" in n]
        assert moments
        for n in moments:
            assert _spec_axes(scope, n) == ("fsdp",), n


def _wire(mesh_cfg, rules):
    """One step under the rules: (collective rows of the compiled step,
    {param: (shape, bytes, bytes a chip stores)}), or None on one device."""
    from hlo_collectives import collectives

    _, scope, pe = _train([_make_data(np.random.RandomState(2), 64)],
                          mesh_cfg, rules)
    if pe.device_count < 2:
        return None
    stored = {
        n: (tuple(scope.vars[n].shape), scope.vars[n].nbytes,
            scope.vars[n].addressable_shards[0].data.nbytes)
        for n in _PARAMS
    }
    return collectives(pe.compiled_hlo(), pe._mesh), stored


def test_fsdp_gathers_each_parameter_once():
    """The wire signature of FSDP over dp2 x fsdp4, read from the compiled
    step: each rule-sharded parameter is all-gathered over `fsdp` once a step
    (weight streaming; a second gather of the same tensor doubles the bytes).
    Gathers are matched by shape, so the partitioner's own activation gathers
    do not count. The gradients are not combined twice either: everything
    reduced stays under 1.5x the gradient bytes (1.24x here: the CPU
    partitioner spells the reduce-scatter as all-reduce + dynamic-slice and
    adds a few activation partials; a second ring of the large gradient
    would make it 2x)."""
    from hlo_collectives import reduced_bytes, within

    wire = _wire(MeshConfig(dp=2, fsdp=4), _FSDP_RULES)
    if wire is None:
        return
    rows, stored = wire
    shapes = {shape for shape, _, _ in stored.values()}
    params = sum(nbytes for _, nbytes, _ in stored.values())
    gathered = sum(b for op, axis, shape, b in rows
                   if op == "all-gather" and axis == "fsdp" and shape in shapes)
    assert within(gathered, params), (gathered, params, rows)
    assert reduced_bytes(rows) <= 1.5 * params, (reduced_bytes(rows), rows)


def test_tp_reduces_stored_shards_and_one_activation():
    """The wire signature of the Megatron pair over dp4 x tp2: the dp ring
    carries each gradient at its parameter's stored shard size, and the only
    tp collective is the row-parallel product's partial sum, (batch / dp) x
    classes f32. The backward adds none: the first operand is the feed."""
    from hlo_collectives import gathered_bytes, reduced_bytes, within

    wire = _wire(MeshConfig(dp=4, tp=2), _TP_RULES)
    if wire is None:
        return
    rows, stored = wire
    shards = sum(chip for _, _, chip in stored.values())
    assert within(reduced_bytes(rows, "dp"), shards), (rows, shards)
    assert within(reduced_bytes(rows, "tp"), 64 // 4 * 4 * 4), rows
    assert gathered_bytes(rows) == 0, rows
    assert all(axis in ("dp", "tp") for _, axis, _, _ in rows), rows


def test_fsdp_checkpoint_roundtrip_topology_change():
    """Elastic composition: train 3 steps under dp2 x fsdp4, checkpoint
    (sharded params+moments gather to host), resume into a FRESH scope on a
    DIFFERENT topology (dp4 x fsdp2) — the continued trajectory equals the
    uninterrupted single-device run's."""
    import jax.numpy as jnp

    from paddle_tpu.resilience.checkpoint import snapshot_persistables

    rng = np.random.RandomState(11)
    batches = [_make_data(rng, 64) for _ in range(6)]
    full, _, _ = _train(batches)

    from paddle_tpu.parallel_executor import BuildStrategy

    def steps_on(mesh_cfg, scope, lo, hi):
        main, startup = framework.Program(), framework.Program()
        with fluid.unique_name.guard(), fluid.program_guard(main, startup):
            loss = _build_adam()
        exe = fluid.Executor()
        with scope_guard(scope):
            if lo == 0:
                exe.run(startup)
            else:
                exe.run(startup)  # fresh init, then overlay the checkpoint
                for name, arr in snap.items():
                    scope.set_var(name, jnp.asarray(arr))
            strat = BuildStrategy()
            strat.sharding_rules = _FSDP_RULES
            pe = fluid.ParallelExecutor(
                loss_name=loss.name, main_program=main, build_strategy=strat,
                scope=scope, mesh_config=mesh_cfg,
            )
            out = []
            for x, y in batches[lo:hi]:
                (l,) = pe.run(fetch_list=[loss.name], feed={"x": x, "y": y})
                out.append(float(np.asarray(l).reshape(-1)[0]))
            return out, main

    head_scope = Scope(seed=3)
    head, head_main = steps_on(MeshConfig(dp=2, fsdp=4), head_scope, 0, 3)
    with scope_guard(head_scope):
        snap = snapshot_persistables(head_main, scope=head_scope)
    tail, _ = steps_on(MeshConfig(dp=4, fsdp=2), Scope(seed=3), 3, 6)
    np.testing.assert_allclose(head + tail, full, rtol=_RTOL, atol=_ATOL)


def test_fused_kernels_decline_under_tp():
    """BuildStrategy.fuse_kernels + tp rules: the Pallas substitutions whose
    tile dims a rule shards must DECLINE (fall back to the reference per-op
    path) and the trajectory must still match the unfused run."""
    from paddle_tpu.ops import pallas_kernels as pk
    from paddle_tpu.parallel_executor import BuildStrategy

    rng = np.random.RandomState(5)
    batches = [_make_data(rng, 64) for _ in range(4)]

    def run(fuse):
        main, startup = framework.Program(), framework.Program()
        with fluid.unique_name.guard(), fluid.program_guard(main, startup):
            loss = _build_adam()
        exe = fluid.Executor()
        strat = BuildStrategy()
        strat.fuse_kernels = fuse
        strat.sharding_rules = _TP_RULES
        losses = []
        scope = Scope(seed=7)
        with scope_guard(scope):
            exe.run(startup)
            pe = fluid.ParallelExecutor(
                loss_name=loss.name, main_program=main, build_strategy=strat,
                scope=scope, mesh_config=MeshConfig(dp=4, tp=2),
            )
            for x, y in batches:
                (l,) = pe.run(fetch_list=[loss.name], feed={"x": x, "y": y})
                losses.append(float(np.asarray(l).reshape(-1)[0]))
        return losses, pe

    pk.KERNEL_DISPATCHES.clear()
    off, _ = run(False)
    on, pe = run(True)
    if pe.device_count > 1:
        # every fc weight is tp-sharded, so no gemm epilogue may substitute
        assert "gemm_epilogue" not in pk.KERNEL_DISPATCHES, pk.KERNEL_DISPATCHES
    np.testing.assert_allclose(on, off, rtol=_RTOL, atol=_ATOL)


def test_embedding_engine_rule_migration():
    """The embedding engine now registers its `ep` layout as a program rule
    (no bespoke sharding_spec path): the rule is present on the program, the
    table AND its Adam moments store row-sharded over ep, and training
    matches the plain Executor."""
    VOCAB, D, T = 64, 16, 8

    def build():
        tok = fluid.layers.data(
            name="tok", shape=[-1, T, 1], dtype="int64", append_batch_size=False
        )
        lbl = fluid.layers.data(
            name="lbl", shape=[-1, 1], dtype="int64", append_batch_size=False
        )
        emb = fluid.layers.distributed_embedding(tok, size=[VOCAB, D])
        pooled = fluid.layers.reduce_mean(emb, dim=[1])
        logits = fluid.layers.fc(pooled, size=4)
        loss = fluid.layers.mean(
            fluid.layers.softmax_with_cross_entropy(logits, lbl)
        )
        fluid.optimizer.Adam(0.01).minimize(loss)
        return loss

    rng = np.random.RandomState(2)
    batches = [
        (rng.randint(0, VOCAB, (8, T, 1)).astype("int64"),
         rng.randint(0, 4, (8, 1)).astype("int64"))
        for _ in range(4)
    ]

    def train(mesh_cfg):
        main, startup = framework.Program(), framework.Program()
        with fluid.unique_name.guard(), fluid.program_guard(main, startup):
            loss = build()
        table = next(
            p.name for p in main.global_block().all_parameters()
            if tuple(p.shape) == (VOCAB, D)
        )
        rules = getattr(main, "_sharding_rules", None)
        assert rules is not None and rules.match(table) == ("ep", None)
        exe = fluid.Executor()
        losses = []
        scope = Scope(seed=9)
        with scope_guard(scope):
            exe.run(startup)
            pe = (
                fluid.ParallelExecutor(
                    loss_name=loss.name, main_program=main, scope=scope,
                    mesh_config=mesh_cfg,
                )
                if mesh_cfg is not None
                else None
            )
            for tok, lbl in batches:
                feed = {"tok": tok, "lbl": lbl}
                if pe is not None:
                    (l,) = pe.run(fetch_list=[loss.name], feed=feed)
                else:
                    (l,) = exe.run(main, feed=feed, fetch_list=[loss.name])
                losses.append(float(np.asarray(l).reshape(-1)[0]))
        return losses, scope, table, pe

    single, _, _, _ = train(None)
    multi, scope, table, pe = train(MeshConfig(dp=4, ep=2))
    np.testing.assert_allclose(single, multi, rtol=5e-3, atol=5e-4)
    if pe.device_count > 1:
        assert _spec_axes(scope, table) == ("ep",)
        accs = [n for n in scope.vars
                if n.startswith(table + "_") and "_acc" in n
                and np.asarray(scope.vars[n]).shape == (VOCAB, D)]
        assert accs
        for n in accs:
            assert _spec_axes(scope, n) == ("ep",), n


def test_build_strategy_rules_and_spec_layout():
    """SpecLayout's canonical layouts and the BuildStrategy plumbing: rules
    passed as plain (pattern, spec) tuples are accepted, and transformer_rules
    builds the documented role layouts."""
    layout = SpecLayout()
    rules = layout.transformer_rules(
        column=[r"_up\.w$"], row=[r"_down\.w$"], vector=[r"\.b$"],
        embedding=[r"^embed"],
    )
    assert rules.match("blk0_up.w") == ("fsdp", "tp")
    assert rules.match("blk0_down.w") == ("tp", "fsdp")
    assert rules.match("blk0_up.b") == ("fsdp",)
    assert rules.match("embed_table") == (("fsdp", "tp"), None)
