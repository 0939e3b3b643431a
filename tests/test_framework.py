"""Framework-core unit tests (reference unittests/test_program.py,
test_operator_desc.py, test_protobuf_descs.py)."""

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu import framework
from paddle_tpu.framework import Program


def test_program_block_structure():
    p = Program()
    assert p.num_blocks == 1
    b = p._create_block()
    assert b.idx == 1 and b.parent_idx == 0
    p._rollback()
    assert p.current_block().idx == 0


def test_var_and_op_append():
    p = Program()
    blk = p.global_block()
    x = blk.create_var(name="x", shape=[2, 3], dtype="float32")
    y = blk.create_var(name="y", shape=[2, 3], dtype="float32")
    out = blk.create_var(name="out")
    op = blk.append_op(
        type="elementwise_add",
        inputs={"X": ["x"], "Y": ["y"]},
        outputs={"Out": ["out"]},
    )
    assert op.type == "elementwise_add"
    # infer_shape via eval_shape populated output metadata
    assert blk.var("out").shape == (2, 3)
    assert blk.var("out").dtype == "float32"


def test_dynamic_batch_dim_inference():
    p = Program()
    blk = p.global_block()
    blk.create_var(name="x", shape=[-1, 3], dtype="float32")
    blk.create_var(name="out")
    blk.append_op(type="relu", inputs={"X": ["x"]}, outputs={"Out": ["out"]})
    assert blk.var("out").shape == (-1, 3)


def test_clone_for_test_flips_is_test():
    main = Program()
    with fluid.program_guard(main, Program()):
        x = fluid.layers.data(name="x", shape=[4], dtype="float32")
        h = fluid.layers.dropout(x, dropout_prob=0.5)
    clone = main.clone(for_test=True)
    ops = [op for op in clone.global_block().ops if op.type == "dropout"]
    assert ops and ops[0].attrs["is_test"] is True
    # original untouched
    ops0 = [op for op in main.global_block().ops if op.type == "dropout"]
    assert ops0[0].attrs["is_test"] is False


def test_serialization_roundtrip():
    main = Program()
    with fluid.program_guard(main, Program()):
        x = fluid.layers.data(name="x", shape=[4], dtype="float32")
        y = fluid.layers.fc(x, size=3, act="relu")
    d = main.to_dict()
    restored = Program.from_dict(d)
    assert [op.type for op in restored.global_block().ops] == [
        op.type for op in main.global_block().ops
    ]
    assert restored.global_block().var(y.name).shape == y.shape
    params = restored.global_block().all_parameters()
    assert len(params) == 2  # weight + bias


def test_prune_keeps_needed_ops_only():
    main = Program()
    with fluid.program_guard(main, Program()):
        x = fluid.layers.data(name="x", shape=[4], dtype="float32")
        h = fluid.layers.fc(x, size=3)
        unrelated = fluid.layers.fc(x, size=7)
    pruned = main._prune([h])
    kept_types = [op.type for op in pruned.global_block().ops]
    # unrelated fc's mul must be gone
    assert len(kept_types) < len(main.global_block().ops)


def test_dtype_canonicalization():
    assert framework.convert_np_dtype("float64") == "float32"
    assert framework.convert_np_dtype("int64") == "int32"
    assert framework.convert_np_dtype(np.float32) == "float32"
    assert framework.convert_np_dtype(5) == "float32"  # proto enum FP32


def test_operator_overloading_builds_ops():
    main = Program()
    with fluid.program_guard(main, Program()):
        x = fluid.layers.data(name="x", shape=[4], dtype="float32")
        y = x * 2.0 + 1.0
        z = x + y
    types = [op.type for op in main.global_block().ops]
    assert "scale" in types and "elementwise_add" in types


def test_stop_gradient_blocks_backward():
    main = Program()
    with fluid.program_guard(main, Program()):
        x = fluid.layers.data(name="x", shape=[4], dtype="float32")
        h1 = fluid.layers.fc(x, size=4)
        h1.stop_gradient = True
        h2 = fluid.layers.fc(h1, size=1)
        loss = fluid.layers.mean(h2)
        pg = fluid.append_backward(loss)
    # only the second fc's params get grads
    grad_params = {p.name for p, g in pg}
    first_fc_w = main.global_block().all_parameters()[0].name
    assert all("fc_1" in n or "fc_0" not in n for n in grad_params) or (
        first_fc_w not in grad_params
    )


def test_profiler_context_and_timeline(tmp_path):
    """Reference test_profiler.py pattern: run a tiny train loop under the
    profiler context, assert events were aggregated and the dump converts to
    a chrome trace."""
    import json
    import os
    import sys

    from paddle_tpu.executor import Scope, scope_guard

    main, startup = Program(), Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="px", shape=[4], dtype="float32")
        y = fluid.layers.fc(input=x, size=3)
        loss = fluid.layers.mean(y)
        fluid.optimizer.SGD(learning_rate=0.01).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    with scope_guard(Scope()):
        exe.run(startup)
        path = str(tmp_path / "profile")
        with fluid.profiler.profiler("All", "total", path):
            for _ in range(3):
                exe.run(
                    main,
                    feed={"px": np.ones((2, 4), "float32")},
                    fetch_list=[loss.name],
                )
        assert not fluid.profiler.is_profiling()
        with open(path) as f:
            dump = json.load(f)
        names = {e["name"] for e in dump["events"]}
        assert any("run/block0" in n for n in names)
        sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))
        try:
            import timeline

            out = str(tmp_path / "timeline.json")
            n = timeline.convert(path, out)
            assert n > 0
            with open(out) as f:
                trace = json.load(f)
            assert "traceEvents" in trace
        finally:
            sys.path.pop(0)
    fluid.profiler.reset_profiler()


def test_flags_check_nan_inf():
    """FLAGS tier (reference SURVEY.md §5.6 + operator.cc:778
    FLAGS_check_nan_inf): a program producing NaN raises naming the var when
    the flag is on, runs silently when off."""
    from paddle_tpu.executor import Scope, scope_guard

    main = Program()
    blk = main.global_block()
    blk.create_var(name="nan_x", shape=[2], dtype="float32")
    blk.create_var(name="nan_y", shape=None, dtype=None)
    blk.append_op(
        type="log", inputs={"X": ["nan_x"]}, outputs={"Out": ["nan_y"]}, attrs={}
    )
    exe = fluid.Executor(fluid.CPUPlace())
    bad = np.array([-1.0, 1.0], "float32")  # log(-1) = nan
    with scope_guard(Scope()):
        exe.run(main, feed={"nan_x": bad}, fetch_list=["nan_y"])  # off: fine
    fluid.set_flags({"FLAGS_check_nan_inf": True})
    try:
        with scope_guard(Scope()):
            with pytest.raises(FloatingPointError, match="nan_y"):
                exe.run(main, feed={"nan_x": bad}, fetch_list=["nan_y"])
    finally:
        fluid.set_flags({"check_nan_inf": False})
    assert fluid.get_flags("check_nan_inf") == {"check_nan_inf": False}


def test_profile_ops_mode():
    """FLAGS_profile_ops: per-op eager execution under the profiler produces
    op-type-attributed events (reference per-op RecordEvent tables) and the
    same numerics as the jitted path."""
    from paddle_tpu.executor import Scope, scope_guard

    main, startup = Program(), Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="pox", shape=[4], dtype="float32")
        y = fluid.layers.fc(input=x, size=3, act="relu")
        loss = fluid.layers.mean(y)
    exe = fluid.Executor(fluid.CPUPlace())
    feed = {"pox": np.ones((2, 4), "float32")}
    with scope_guard(Scope(seed=1)):
        exe.run(startup)
        (jitted,) = exe.run(main, feed=feed, fetch_list=[loss.name])
    fluid.set_flags({"profile_ops": True})
    try:
        with scope_guard(Scope(seed=1)):
            exe.run(startup)
            with fluid.profiler.profiler("All", "total", None):
                (per_op,) = exe.run(main, feed=feed, fetch_list=[loss.name])
            import paddle_tpu.profiler as prof

            table, _ = prof._aggregate()
    finally:
        fluid.set_flags({"profile_ops": False})
        fluid.profiler.reset_profiler()
    np.testing.assert_allclose(per_op, jitted, rtol=1e-5)
    # events are "op/<type>:<output>" (display form) — assert the op TYPES
    # were attributed without pinning the instance suffix
    assert any("op/mul" in name for name in table), table.keys()
    assert any("op/relu" in name for name in table), table.keys()


def test_device_op_profile_correlation(tmp_path):
    """ROADMAP 10: Executor.compiled_hlo() carries op_name metadata naming
    each framework op's scope (registry.lower_ops named_scope), and
    profiler._hlo_op_map correlates HLO instruction names back to op types —
    the CUPTI-kernel→op correlation analog (reference device_tracer.cc).
    The xplane aggregation itself needs a real TPU/GPU plane, so on the CPU
    test backend device_op_profile degrades to an empty table."""
    import paddle_tpu.profiler as prof
    from paddle_tpu.executor import Scope, scope_guard

    main, startup = Program(), Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="dopx", shape=[4], dtype="float32")
        y = fluid.layers.fc(input=x, size=3, act="relu")
        loss = fluid.layers.mean(y)
    exe = fluid.Executor(fluid.CPUPlace())
    feed = {"dopx": np.ones((2, 4), "float32")}
    with scope_guard(Scope(seed=1)):
        exe.run(startup)
        tdir = str(tmp_path / "xla")
        with fluid.profiler.xla_trace(tdir):
            exe.run(main, feed=feed, fetch_list=[loss.name])
        hlo = exe.compiled_hlo()
        assert 'op_name="' in hlo
        mapping = prof._hlo_op_map(hlo)
        assert mapping, "no op_name metadata parsed"
        assert {"mul", "relu", "mean"} & set(mapping.values()), set(mapping.values())
        table = prof.device_op_profile(tdir, hlo, print_table=False)
        assert isinstance(table, dict)
