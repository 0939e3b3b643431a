"""Program-level pipeline parallelism through ParallelExecutor.

The pp tier's correctness contract mirrors test_parallel_executor.py's:
training a fluid Program on a dp×pp mesh must reproduce the single-device
Executor's loss trajectory exactly (same data, same init seed), for both
the GPipe and 1F1B schedules, with ZeRO-1/checkpointing composing
unchanged. The homogeneous-stack engines keep their own coverage in
test_pipeline_parallel.py; this file exercises the heterogeneous Program
lowering (executor._PipelinedBlock + parallel/partition.py).
"""

import os
import tempfile

import numpy as np
import pytest

import jax

import paddle_tpu.fluid as fluid
from paddle_tpu import framework
from paddle_tpu.executor import Scope, global_scope, scope_guard
from paddle_tpu.parallel import MeshConfig
from paddle_tpu.parallel_executor import (
    BuildStrategy,
    ExecutionStrategy,
    ReduceStrategy,
)


def build_mlp(widths=(48, 32, 24)):
    """Heterogeneous stage material: every layer a different width, so the
    partitioner has to balance genuinely unequal costs."""
    x = fluid.layers.data(name="x", shape=[16], dtype="float32")
    y = fluid.layers.data(name="y", shape=[1], dtype="int64")
    h = x
    for w in widths:
        h = fluid.layers.fc(h, size=w, act="relu")
    logits = fluid.layers.fc(h, size=4)
    loss = fluid.layers.mean(
        fluid.layers.softmax_with_cross_entropy(logits, y)
    )
    return loss


def make_data(rng, n):
    x = rng.randn(n, 16).astype("float32")
    y = (np.abs(x[:, :4]).argmax(1)).astype("int64").reshape(n, 1)
    return x, y


def train(batches, pe_factory=None, seed=3, optimizer=None, build=build_mlp):
    """One trajectory: plain Executor when pe_factory is None, else the
    ParallelExecutor it returns (given loss, main)."""
    main = framework.Program()
    startup = framework.Program()
    with fluid.unique_name.guard():
        with fluid.program_guard(main, startup):
            loss = build()
            (optimizer or fluid.optimizer.SGD(learning_rate=0.05)).minimize(
                loss
            )
    exe = fluid.Executor(fluid.CPUPlace())
    losses = []
    with scope_guard(Scope(seed=seed)):
        exe.run(startup)
        pe = pe_factory(loss, main) if pe_factory else None
        for x, y in batches:
            if pe is not None:
                (l,) = pe.run(fetch_list=[loss.name], feed={"x": x, "y": y})
            else:
                (l,) = exe.run(
                    main, feed={"x": x, "y": y}, fetch_list=[loss.name]
                )
            losses.append(float(np.asarray(l).reshape(-1)[0]))
    return losses


def _pp_factory(schedule, n_micro=4, dp=2, pp=4, reduce_strategy=None):
    def factory(loss, main):
        es = ExecutionStrategy()
        es.pipeline_schedule = schedule
        es.num_microbatches = n_micro
        bs = BuildStrategy()
        if reduce_strategy is not None:
            bs.reduce_strategy = reduce_strategy
        return fluid.ParallelExecutor(
            loss_name=loss.name,
            main_program=main,
            mesh_config=MeshConfig(dp=dp, pp=pp),
            exec_strategy=es,
            build_strategy=bs,
        )

    return factory


@pytest.mark.parametrize("schedule", ["gpipe", "1f1b"])
def test_pp_program_matches_single_device(schedule):
    """dp2×pp4 training of a heterogeneous-width MLP reproduces the plain
    Executor loss-for-loss under both schedules (the ISSUE's acceptance
    bar: loss parity vs single-device)."""
    rng = np.random.RandomState(0)
    batches = [make_data(rng, 64) for _ in range(5)]
    batches.append(batches[0])  # "the loss fell" compares one batch with itself
    single = train(batches)
    pp = train(batches, _pp_factory(schedule))
    np.testing.assert_allclose(single, pp, rtol=2e-3, atol=2e-4)
    assert np.isfinite(pp).all()
    assert pp[-1] < pp[0]


def test_pp_zero1_composes():
    """ReduceStrategy.Reduce (ZeRO-1 over 'dp') under a pp mesh: the
    optimizer tier shards its state over dp while the forward/backward run
    the pipeline — trajectories still match single-device."""
    rng = np.random.RandomState(1)
    batches = [make_data(rng, 64) for _ in range(5)]
    opt = lambda: fluid.optimizer.Momentum(learning_rate=0.05, momentum=0.9)
    single = train(batches, optimizer=opt())
    pp = train(
        batches,
        _pp_factory("gpipe", reduce_strategy=ReduceStrategy.Reduce),
        optimizer=opt(),
    )
    # exact parity with the single-device trajectory IS the contract; loss
    # monotonicity over 5 random batches is not guaranteed with momentum
    np.testing.assert_allclose(single, pp, rtol=2e-3, atol=2e-4)
    assert np.isfinite(pp).all()


def test_pp_build_strategy_stages_knob():
    """BuildStrategy.pipeline_stages builds the dp×pp mesh without an
    explicit MeshConfig (dp fills the remaining devices)."""
    rng = np.random.RandomState(2)
    batches = [make_data(rng, 64) for _ in range(3)]

    def factory(loss, main):
        bs = BuildStrategy()
        bs.pipeline_stages = 4
        return fluid.ParallelExecutor(
            loss_name=loss.name, main_program=main, build_strategy=bs
        )

    single = train(batches)
    pp = train(batches, factory)
    np.testing.assert_allclose(single, pp, rtol=2e-3, atol=2e-4)


def test_device_guard_override_controls_partition():
    """Explicit device_guard("pp:k") annotations win over the analytic
    partitioner, and the resulting plan maps ops where pinned."""

    def build():
        x = fluid.layers.data(name="x", shape=[16], dtype="float32")
        y = fluid.layers.data(name="y", shape=[1], dtype="int64")
        with framework.device_guard("pp:0"):
            h = fluid.layers.fc(x, size=32, act="relu")
        with framework.device_guard("pp:1"):
            h = fluid.layers.fc(h, size=24, act="relu")
        with framework.device_guard("pp:2"):
            h = fluid.layers.fc(h, size=16, act="relu")
        with framework.device_guard("pp:3"):
            logits = fluid.layers.fc(h, size=4)
            loss = fluid.layers.mean(
                fluid.layers.softmax_with_cross_entropy(logits, y)
            )
        return loss

    rng = np.random.RandomState(3)
    batches = [make_data(rng, 64) for _ in range(3)]
    single = train(batches, build=build)
    factory = _pp_factory("gpipe")

    main = framework.Program()
    startup = framework.Program()
    with fluid.unique_name.guard():
        with fluid.program_guard(main, startup):
            loss = build()
            fluid.optimizer.SGD(learning_rate=0.05).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    losses = []
    with scope_guard(Scope(seed=3)):
        exe.run(startup)
        pe = factory(loss, main)
        for x, y in batches:
            (l,) = pe.run(fetch_list=[loss.name], feed={"x": x, "y": y})
            losses.append(float(np.asarray(l).reshape(-1)[0]))
        blk = pe._cache[next(iter(pe._cache))]
        plan = blk.stage_plan
    np.testing.assert_allclose(single, losses, rtol=2e-3, atol=2e-4)
    # the guard put exactly one fc block per stage: each stage owns its w+b
    assert [sorted(s) for s in plan["stage_params"]] == [
        sorted(["fc_%d.w_0" % k, "fc_%d.b_0" % k]) for k in range(4)
    ]


def test_pp_checkpoint_save_resume_roundtrip():
    """save_persistables mid-training under the pp lowering, reload into a
    fresh scope, and the resumed trajectory continues exactly — stage
    partitioning must not leak into the checkpoint layout."""
    rng = np.random.RandomState(4)
    batches = [make_data(rng, 64) for _ in range(6)]
    factory = _pp_factory("gpipe")

    main = framework.Program()
    startup = framework.Program()
    with fluid.unique_name.guard():
        with fluid.program_guard(main, startup):
            loss = build_mlp()
            fluid.optimizer.SGD(learning_rate=0.05).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())

    with tempfile.TemporaryDirectory() as d:
        with scope_guard(Scope(seed=3)):
            exe.run(startup)
            pe = factory(loss, main)
            for x, y in batches[:3]:
                pe.run(fetch_list=[loss.name], feed={"x": x, "y": y})
            fluid.io.save_persistables(exe, d, main)
            tail_expected = []
            for x, y in batches[3:]:
                (l,) = pe.run(fetch_list=[loss.name], feed={"x": x, "y": y})
                tail_expected.append(float(np.asarray(l).reshape(-1)[0]))

        with scope_guard(Scope(seed=99)):  # different seed: load must win
            exe.run(startup)
            fluid.io.load_persistables(exe, d, main)
            pe = factory(loss, main)
            tail = []
            for x, y in batches[3:]:
                (l,) = pe.run(fetch_list=[loss.name], feed={"x": x, "y": y})
                tail.append(float(np.asarray(l).reshape(-1)[0]))
    np.testing.assert_allclose(tail_expected, tail, rtol=2e-3, atol=2e-4)


def test_pp_rejects_non_last_stage_fetch_and_multistep():
    main = framework.Program()
    startup = framework.Program()
    with fluid.unique_name.guard():
        with fluid.program_guard(main, startup):
            x = fluid.layers.data(name="x", shape=[16], dtype="float32")
            y = fluid.layers.data(name="y", shape=[1], dtype="int64")
            h = fluid.layers.fc(x, size=32, act="relu")
            first = h
            h = fluid.layers.fc(h, size=24, act="relu")
            logits = fluid.layers.fc(h, size=4)
            loss = fluid.layers.mean(
                fluid.layers.softmax_with_cross_entropy(logits, y)
            )
            fluid.optimizer.SGD(learning_rate=0.05).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    rng = np.random.RandomState(5)
    x_np, y_np = make_data(rng, 64)
    with scope_guard(Scope(seed=3)):
        exe.run(startup)
        pe = _pp_factory("gpipe")(loss, main)
        with pytest.raises(ValueError, match="LAST pipeline stage|non-last"):
            pe.run(
                fetch_list=[loss.name, first.name],
                feed={"x": x_np, "y": y_np},
            )
        with pytest.raises(NotImplementedError, match="steps_per_run"):
            pe.run(
                fetch_list=[loss.name],
                feed={"x": np.stack([x_np, x_np]), "y": np.stack([y_np, y_np])},
                steps_per_run=2,
            )


# ---------------------------------------------------------------------------
# partition.py unit tests
# ---------------------------------------------------------------------------


def test_balanced_partition_minimizes_bottleneck():
    from paddle_tpu.parallel.partition import balanced_partition

    w = [1.0, 1.0, 10.0, 1.0, 1.0, 1.0]
    stages = balanced_partition(w, legal_cuts=range(5), n_stages=3)
    assert stages == sorted(stages) and set(stages) == {0, 1, 2}
    seg = [
        sum(wi for wi, s in zip(w, stages) if s == k) for k in range(3)
    ]
    assert max(seg) == 10.0  # the heavy op alone bounds the bottleneck


def test_balanced_partition_respects_legal_cuts():
    from paddle_tpu.parallel.partition import balanced_partition

    w = [5.0, 5.0, 5.0, 5.0]
    stages = balanced_partition(w, legal_cuts=[0, 2], n_stages=3)
    # cuts forced at 0 and 2: stages [0, 1, 1, 2]
    assert stages == [0, 1, 1, 2]
    with pytest.raises(ValueError, match="legal cut"):
        balanced_partition(w, legal_cuts=[1], n_stages=3)


def test_stages_from_attrs_validation():
    from paddle_tpu.framework import PIPELINE_STAGE_ATTR
    from paddle_tpu.parallel.partition import stages_from_attrs

    class FakeOp:
        def __init__(self, stage=None):
            self.type = "fake"
            self.attrs = (
                {} if stage is None else {PIPELINE_STAGE_ATTR: stage}
            )

    assert stages_from_attrs([FakeOp(), FakeOp()], 2) is None
    assert stages_from_attrs(
        [FakeOp(0), FakeOp(), FakeOp(1), FakeOp()], 2
    ) == [0, 0, 1, 1]
    with pytest.raises(ValueError, match="BACKWARD"):
        stages_from_attrs([FakeOp(1), FakeOp(0)], 2)
    with pytest.raises(ValueError, match=">= pipeline depth"):
        stages_from_attrs([FakeOp(5)], 2)


def test_analytic_op_time_matmul_dominates_bandwidth():
    from paddle_tpu.parallel.partition import analytic_op_time_us

    class A:
        def __init__(self, shape, dtype="float32"):
            self.shape = tuple(shape)
            self.dtype = np.dtype(dtype)

    # big matmul: flops-bound; its time must exceed an equal-bytes add
    t_mm = analytic_op_time_us(
        "mul",
        {"X": [A((1024, 4096))], "Y": [A((4096, 4096))]},
        {"Out": [A((1024, 4096))]},
    )
    t_add = analytic_op_time_us(
        "elementwise_add",
        {"X": [A((1024, 4096))], "Y": [A((1024, 4096))]},
        {"Out": [A((1024, 4096))]},
    )
    assert t_mm > t_add > 0


def test_device_guard_accepts_reference_spellings():
    with framework.device_guard("gpu:2"):
        assert framework._current_pipeline_stage() == 2
        with framework.device_guard("cpu"):
            assert framework._current_pipeline_stage() is None
        assert framework._current_pipeline_stage() == 2
    assert framework._current_pipeline_stage() is None
    with pytest.raises(ValueError):
        framework.device_guard("tpu").__enter__()
