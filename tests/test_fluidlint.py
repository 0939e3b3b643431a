"""fluidlint checker-suite tests (paddle_tpu/analysis/checkers.py, verify.py,
tools/fluidlint.py).

Three contracts:
1. every registered checker catches its seeded defect, with check-id + op +
   var provenance on the finding;
2. the model zoo (tools/fluidlint.py ZOO — the same programs the CLI lints)
   is clean: zero findings, zero analyzer problems;
3. the FLAGS_static_verify compile gate is bit-transparent: Executor,
   ParallelExecutor, and aot_serve_lowering produce identical results with
   the flag on and off, and a defective program raises StaticVerifyError at
   compile instead of failing inside the trace.
"""

import os
import sys

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu import flags, framework
from paddle_tpu.analysis import StaticVerifyError, lint_program, maybe_static_verify
from paddle_tpu.analysis import verify as _verify_mod
from paddle_tpu.executor import Scope, aot_serve_lowering, scope_guard
from paddle_tpu.parallel import MeshConfig, ShardingRules, make_mesh
from paddle_tpu.parallel.sharding_rules import Resolver

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "..", "tools"))
import fluidlint  # noqa: E402  (the CLI + zoo registry under test)


@pytest.fixture(autouse=True)
def _gate_reset():
    """The verify gate memoizes per program uid — isolate every test."""
    _verify_mod._VERIFIED.clear()
    flags.set_flags({"static_verify": False})
    yield
    _verify_mod._VERIFIED.clear()
    flags.set_flags({"static_verify": False})


def _fresh():
    return framework.Program(), framework.Program()


def _only(findings, check):
    hits = [f for f in findings if f.check == check]
    assert hits, "expected a %r finding, got %r" % (check, findings)
    return hits


# ---------------------------------------------------------------------------
# seeded defects: one per checker, provenance asserted
# ---------------------------------------------------------------------------


def test_seeded_dead_write():
    main, startup = _fresh()
    with fluid.program_guard(main, startup):
        a = fluid.layers.fill_constant(shape=[2, 2], dtype="float32", value=1.0)
        b = fluid.layers.fill_constant(shape=[2, 2], dtype="float32", value=2.0)
        v = fluid.layers.fill_constant(shape=[2, 2], dtype="float32", value=0.0)
        fluid.layers.assign(a, output=v)  # shadowed: rebound before any read
        fluid.layers.assign(b, output=v)
    _, findings = lint_program(main, [], [v.name])
    hits = _only(findings, "dead-write")
    assert {f.severity for f in hits} == {"warning"}
    assert {f.var for f in hits} == {v.name}
    assert {f.op_type for f in hits} == {"fill_constant", "assign"}
    assert all(f.block_idx == 0 and f.op_index is not None for f in hits)


def test_seeded_write_never_read():
    main, startup = _fresh()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[4], dtype="float32")
        dead = fluid.layers.relu(x)  # never read, never fetched
        loss = fluid.layers.mean(x)
    _, findings = lint_program(main, ["x"], [loss.name])
    (f,) = _only(findings, "write-never-read")
    assert f.severity == "warning"
    assert f.var == dead.name and f.op_type == "relu"
    assert f.block_idx == 0


def test_seeded_dtype_boundary():
    main, startup = _fresh()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[4], dtype="float32")
        lo = fluid.layers.cast(x, "bfloat16")
        mixed = fluid.layers.elementwise_add(lo, x)  # bf16 + f32, no cast
    _, findings = lint_program(main, ["x"], [mixed.name])
    (f,) = _only(findings, "dtype-boundary")
    assert f.severity == "warning"
    assert f.op_type == "elementwise_add" and f.var == lo.name
    assert "mixed-precision" in f.message


def test_seeded_determinism():
    main, startup = _fresh()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[4], dtype="float32")
        d = fluid.layers.dropout(x, dropout_prob=0.5)
        loss = fluid.layers.mean(d)
    # fine as a training program ...
    _, train_findings = lint_program(main, ["x"], [loss.name])
    assert not [f for f in train_findings if f.check == "determinism"]
    # ... an exported-wrong inference program is an error
    _, findings = lint_program(main, ["x"], [loss.name], mode="inference")
    (f,) = _only(findings, "determinism")
    assert f.severity == "error" and f.op_type == "dropout"
    assert f.var == d.name


def test_seeded_fetch_unwritten():
    main, startup = _fresh()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[4], dtype="float32")
        loss = fluid.layers.mean(x)
    _, findings = lint_program(main, ["x"], [loss.name, "no_such_var"])
    (f,) = _only(findings, "fetch-unwritten")
    assert f.severity == "error" and f.var == "no_such_var"


def test_seeded_sharding_rules():
    main, startup = _fresh()
    # unique_name.guard: the rule pattern below hard-codes the param name
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[8], dtype="float32")
        h = fluid.layers.fc(x, size=4)
    # rank-3 spec on a rank-2 param (error) + a pattern matching nothing
    main._sharding_rules = ShardingRules([
        (r"^fc_0\.w_0$", ("tp", "fsdp", "ep")),
        (r"^nomatch_xyz$", ("tp",)),
    ])
    _, findings = lint_program(main, ["x"], [h.name])
    errors = [f for f in findings if f.check == "sharding-rules"
              and f.severity == "error"]
    (e,) = errors
    assert e.var == "fc_0.w_0" and "rank-3" in e.message
    warns = [f for f in findings if f.check == "sharding-rules"
             and f.severity == "warning"]
    (w,) = warns
    assert w.var == r"^nomatch_xyz$" and "dead rule" in w.message


def _build_while(defect=False):
    """Counting while loop; with defect=True, un-thread the loop bound from
    the while op's X inputs — the classic capture bug the functional
    lowering cannot see."""
    main, startup = _fresh()
    with fluid.program_guard(main, startup):
        i = fluid.layers.fill_constant(shape=[1], dtype="int64", value=0)
        n = fluid.layers.fill_constant(shape=[1], dtype="int64", value=4)
        acc = fluid.layers.fill_constant(shape=[2], dtype="float32", value=0.0)
        cond = fluid.layers.less_than(i, n)
        w = fluid.layers.While(cond)
        with w.block():
            a2 = fluid.layers.elementwise_add(
                acc, fluid.layers.fill_constant([2], "float32", 1.0)
            )
            fluid.layers.assign(a2, acc)
            fluid.layers.increment(i, value=1, in_place=True)
            fluid.layers.less_than(i, n, cond=cond)
    if defect:
        wop = next(
            op for op in main.global_block().ops if op.type == "while"
        )
        wop.inputs["X"].remove(n.name)
        wop.attrs["x_names"] = [
            x for x in wop.attrs["x_names"] if x != n.name
        ]
    return main, n.name, acc.name


def test_seeded_cf_capture():
    main, n_name, acc_name = _build_while(defect=True)
    _, findings = lint_program(main, [], [acc_name])
    hits = _only(findings, "cf-capture")
    assert any(
        f.severity == "error" and f.var == n_name and f.op_type == "while"
        for f in hits
    ), hits


def test_seeded_donation_alias():
    main, startup = _fresh()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[4], dtype="float32")
        w = fluid.layers.create_parameter([4, 3], "float32", name="W")
        y = fluid.layers.mul(x, w)
        loss = fluid.layers.mean(y)
    scope = Scope(seed=0)
    with scope_guard(scope):
        fluid.Executor().run(startup)
        # a corrupted plan donating read-only state: the forward-only
        # lowering never writes W, so donating it is use-after-donate
        main._donation_plan = {
            "feed": ["x"],
            "fetch": [loss.name],
            "mut": ["W"],
            "ro": [],
            "unknown": (),
            "scope_uid": scope._uid,
        }
        _, findings = lint_program(main, ["x"], [loss.name], scope=scope)
    (f,) = _only(findings, "donation-alias")
    assert f.severity == "error" and f.var == "W"
    assert "use-after-donate" in f.message


# ---------------------------------------------------------------------------
# the zoo is clean (same programs the CLI lints): asserted in
# tests/test_analysis.py::test_zoo_facts_agree_with_traced_metadata, which
# builds each zoo model once for both the lint-clean and the
# facts-vs-traced-metadata contracts; the CLI path runs in test_cli_smoke
# below.
# ---------------------------------------------------------------------------


def test_cli_smoke(capsys):
    assert fluidlint.main(["--model", "lenet", "--strict"]) == 0
    assert "lenet" in capsys.readouterr().out
    assert fluidlint.main(["--model", "lenet", "--json"]) == 0
    import json

    rec = json.loads(capsys.readouterr().out)
    assert rec["model"] == "lenet" and rec["findings"] == []
    assert rec["ops_analyzed"] > 10


# ---------------------------------------------------------------------------
# Resolver observability satellites: degradation records + dead-rule audit
# ---------------------------------------------------------------------------


def test_resolver_records_divisibility_degradation():
    mesh = make_mesh(MeshConfig(dp=2, tp=2, sp=2))
    res = Resolver(mesh, rules=ShardingRules([("b", ("tp", None))]))
    assert res.rule_spec("b", (3, 8)) is None  # 3 % tp=2 -> degrade
    assert res.degraded == [("b", 0, ("tp",), 3, 2)]
    # recorded once per (name, dim), not per resolve
    res.rule_spec("b", (3, 8))
    assert len(res.degraded) == 1


def test_resolver_dead_rule_audit():
    mesh = make_mesh(MeshConfig(dp=2, tp=2, sp=2))
    res = Resolver(mesh, rules=ShardingRules([
        ("fc_0", ("tp", None)),
        ("nomatch_xyz", ("tp",)),
    ]))
    dead = res.audit({"fc_0.w_0", "fc_0.b_0", "x"})
    assert dead == ["nomatch_xyz"]
    assert res.audit({"fc_0.w_0", "nomatch_xyz"}) == []


# ---------------------------------------------------------------------------
# the FLAGS_static_verify gate: bit-transparent on every compile seam
# ---------------------------------------------------------------------------


def _build_sgd_net():
    main, startup = _fresh()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[8], dtype="float32")
        h = fluid.layers.fc(x, size=4, act="relu")
        loss = fluid.layers.mean(h)
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    return main, startup, loss


def test_executor_gate_bit_parity():
    xv = np.random.RandomState(0).randn(6, 8).astype("float32")

    def run(verify_on):
        flags.set_flags({"static_verify": bool(verify_on)})
        main, startup, loss = _build_sgd_net()
        with scope_guard(Scope(seed=7)):
            exe = fluid.Executor()
            exe.run(startup)
            return [
                np.asarray(
                    exe.run(main, feed={"x": xv}, fetch_list=[loss.name])[0]
                )
                for _ in range(3)
            ]

    off = run(False)
    assert not _verify_mod._VERIFIED
    on = run(True)
    assert _verify_mod._VERIFIED, "gate never ran with the flag on"
    for a, b in zip(off, on):
        np.testing.assert_array_equal(a, b)


def test_parallel_executor_gate_bit_parity():
    xv = np.random.RandomState(1).randn(8, 8).astype("float32")

    def run(verify_on):
        flags.set_flags({"static_verify": bool(verify_on)})
        main, startup, loss = _build_sgd_net()
        with scope_guard(Scope(seed=5)):
            fluid.Executor().run(startup)
            pe = fluid.ParallelExecutor(
                use_cuda=False, loss_name=loss.name, main_program=main
            )
            return [
                np.asarray(pe.run(fetch_list=[loss.name], feed={"x": xv})[0])
                for _ in range(2)
            ]

    off = run(False)
    _verify_mod._VERIFIED.clear()
    on = run(True)
    assert any(
        k for k in _verify_mod._VERIFIED
    ), "ParallelExecutor never hit the gate"
    for a, b in zip(off, on):
        np.testing.assert_array_equal(a, b)


def test_aot_serve_gate_bit_parity():
    """The serving seam, driven by the NMT beam-search infer model — the
    analyzer's hardest program (while loop, tensor arrays, decode)."""
    from paddle_tpu.models import machine_translation as mt

    B, T, VOCAB = 2, 4, 10
    rng = np.random.RandomState(5)
    feed = {
        "src": rng.randint(2, VOCAB, (B, T, 1)).astype(np.int64),
        "src_len": np.array([T, T - 1], np.int64),
    }

    def run(verify_on):
        flags.set_flags({"static_verify": bool(verify_on)})
        main, startup = _fresh()
        with fluid.unique_name.guard(), fluid.program_guard(main, startup):
            src = fluid.layers.data(
                name="src", shape=[B, T, 1], dtype="int64",
                append_batch_size=False,
            )
            main.global_block().create_var(
                name="src_len", shape=(B,), dtype="int64"
            )
            src._len_name = "src_len"
            ids, scores = mt.infer_model(
                src, VOCAB, beam_size=2, max_out_len=T + 1, start_id=0,
                end_id=1,
            )
        with scope_guard(Scope(seed=0)):
            fluid.Executor().run(startup)
            serve, ro, mut = aot_serve_lowering(
                main, ["src", "src_len"], [ids.name, scores.name],
                fluid.executor.global_scope(),
            )
        return [np.asarray(v) for v in serve(feed, ro, mut)]

    off = run(False)
    _verify_mod._VERIFIED.clear()
    on = run(True)
    assert _verify_mod._VERIFIED, "aot_serve_lowering never hit the gate"
    for a, b in zip(off, on):
        np.testing.assert_array_equal(a, b)


def test_gpt_serving_programs_static_verify():
    """The gpt prefill/decode variants (serving/generation's programs) pass
    a serving-mode static verification."""
    flags.set_flags({"static_verify": True})
    for kind in ("gpt_prefill", "gpt_decode"):
        program, feeds, fetches = fluidlint.ZOO[kind]()
        findings = maybe_static_verify(
            program, feeds, fetches, mode="serving", where="test:%s" % kind
        )
        assert findings == [], (kind, findings)


def test_gate_off_is_free():
    main, _, acc_name = _build_while(defect=True)
    # flag off: the gate does nothing, even for a defective program
    assert maybe_static_verify(main, [], [acc_name]) is None
    assert not _verify_mod._VERIFIED


def test_defective_program_raises_at_compile():
    """With the flag on, a capture-broken while program is rejected BEFORE
    tracing, by check id — not with a KeyError from inside XLA."""
    flags.set_flags({"static_verify": True})
    main, n_name, acc_name = _build_while(defect=True)
    with scope_guard(Scope(seed=0)):
        exe = fluid.Executor()
        with pytest.raises(StaticVerifyError) as ei:
            exe.run(main, feed={}, fetch_list=[acc_name])
    assert "cf-capture" in str(ei.value)
    assert n_name in str(ei.value)
    assert ei.value.findings


def test_gate_memoizes_per_program():
    flags.set_flags({"static_verify": True})
    main, startup, loss = _build_sgd_net()
    xv = np.zeros((2, 8), "float32")
    with scope_guard(Scope(seed=0)):
        exe = fluid.Executor()
        exe.run(startup)
        exe.run(main, feed={"x": xv}, fetch_list=[loss.name])
        n = len(_verify_mod._VERIFIED)
        exe.run(main, feed={"x": xv}, fetch_list=[loss.name])
    assert len(_verify_mod._VERIFIED) == n  # second run: memo hit, no re-lint
