"""Kernel-substitution tier (docs/passes.md "Pallas kernel substitution"):
unit numerics for the fused GEMM-epilogue / layer_norm(+residual) kernels
against dense references, the path predicates that gate them, the per-op
Adam lowering every tier shares against its f32 chain, and fused-vs-unfused
pipeline parity through BOTH executors — ZeRO-1's sharded state included."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu.fluid as fluid
from paddle_tpu import flags, framework
from paddle_tpu.executor import Scope, scope_guard
from paddle_tpu.ops import pallas_kernels as pk
from paddle_tpu.ops import registry
from paddle_tpu.parallel_executor import BuildStrategy, ReduceStrategy

# fused chains round ONCE (f32 accumulate, single cast) where the unfused
# op sequence rounds at every op boundary — trajectories agree to fp noise,
# not bit-for-bit (the Adam state update, per op on every path, agrees with
# its f32 chain to a few ulps: test_adam_lowering_matches_f32_chain). Same
# bar as the PE convergence
# contract in test_parallel_executor.py.
_RTOL = 2e-3
_ATOL = 2e-4


# --------------------------------------------------------------------------
# path predicates — the same checks the lowerings consult before
# substituting, asserted directly so a silent fallback can't masquerade
# as coverage
# --------------------------------------------------------------------------


def test_gemm_path_predicate():
    assert pk.gemm_path_taken(128, 256, 256)
    assert pk.gemm_path_taken(512, 2048, 2048)
    assert pk.gemm_path_taken(100, 256, 256)  # one whole ragged tile is fine
    assert not pk.gemm_path_taken(1000, 256, 256)  # ragged m, multi-tile
    assert not pk.gemm_path_taken(128, 1030, 256)  # ragged n, multi-tile


def test_ln_path_predicate():
    assert pk.ln_path_taken(128, 256)
    assert pk.ln_path_taken(8192, 2048)
    assert not pk.ln_path_taken(100, 256)  # rows % 128
    assert not pk.ln_path_taken(128, 100)  # cols % 128


# --------------------------------------------------------------------------
# kernel unit numerics vs dense references
# --------------------------------------------------------------------------


@pytest.mark.parametrize("act", [None, "relu", "tanh"])
def test_gemm_bias_act_matches_dense(act):
    rng = np.random.RandomState(0)
    m, k, n = 128, 256, 384
    x = jnp.asarray(rng.randn(m, k).astype("float32"))
    w = jnp.asarray(rng.randn(k, n).astype("float32"))
    b = jnp.asarray(rng.randn(n).astype("float32"))
    assert pk.gemm_path_taken(m, n, k)
    z, y = pk.gemm_bias_act(x, w, b, act)
    z_ref = jnp.dot(x, w, preferred_element_type=jnp.float32) + b
    np.testing.assert_allclose(np.asarray(z), np.asarray(z_ref),
                               rtol=2e-5, atol=2e-5)
    if act is None:
        assert y is None  # callers reuse z; no second output to transfer
    else:
        y_ref = pk._GEMM_ACT_F32[act](z_ref)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   rtol=2e-5, atol=2e-5)


def test_gemm_double_buffer_predicate_and_bit_identity():
    """The manual double-buffered k-loop DMA variant must be a pure
    scheduling change: same tiles, same accumulation order, same epilogue —
    so its outputs are BIT-identical to the grid-pipelined kernel, not
    merely close."""
    saved = flags.get_flags("gemm_double_buffer")
    rng = np.random.RandomState(7)
    m, k, n = 256, 512, 256
    x = jnp.asarray(rng.randn(m, k).astype("float32"))
    w = jnp.asarray(rng.randn(k, n).astype("float32"))
    b = jnp.asarray(rng.randn(n).astype("float32"))
    try:
        flags.set_flags({"gemm_double_buffer": "off"})
        assert not pk.gemm_dbuf_path_taken(m, n, k, None, None, 128)
        z0, y0 = pk.gemm_bias_act(x, w, b, "tanh", block_k=128)
        z0n, _ = pk.gemm_bias_act(x, w, b, None, block_k=128)
        flags.set_flags({"gemm_double_buffer": "on"})
        assert pk.gemm_dbuf_path_taken(m, n, k, None, None, 128)
        before = pk.KERNEL_DISPATCHES.get("gemm_dbuf", 0)
        z1, y1 = pk.gemm_bias_act(x, w, b, "tanh", block_k=128)  # nk = 4
        z1n, y1n = pk.gemm_bias_act(x, w, b, None, block_k=128)
        assert pk.KERNEL_DISPATCHES.get("gemm_dbuf", 0) == before + 2
    finally:
        flags.set_flags(saved)
    np.testing.assert_array_equal(np.asarray(z0), np.asarray(z1))
    np.testing.assert_array_equal(np.asarray(y0), np.asarray(y1))
    np.testing.assert_array_equal(np.asarray(z0n), np.asarray(z1n))
    assert y1n is None


def test_gemm_ragged_falls_back_dense():
    rng = np.random.RandomState(1)
    # 1000 rows: > one tile and no 128-multiple divisor -> dense fallback
    x = jnp.asarray(rng.randn(1000, 256).astype("float32"))
    w = jnp.asarray(rng.randn(256, 256).astype("float32"))
    b = jnp.asarray(rng.randn(256).astype("float32"))
    assert not pk.gemm_path_taken(1000, 256, 256)
    z, y = pk.gemm_bias_act(x, w, b, "relu")
    z_ref = jnp.dot(x, w, preferred_element_type=jnp.float32) + b
    np.testing.assert_allclose(np.asarray(z), np.asarray(z_ref),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(y),
                               np.maximum(np.asarray(z_ref), 0.0),
                               rtol=2e-5, atol=2e-5)


def _ln_reference(x, r, scale, bias, eps):
    s = x if r is None else x + r
    s32 = s.astype(jnp.float32)
    mean = s32.mean(axis=1, keepdims=True)
    var = s32.var(axis=1, keepdims=True)
    xhat = (s32 - mean) * jax.lax.rsqrt(var + eps)
    y = xhat * scale.astype(jnp.float32) + bias.astype(jnp.float32)
    return s, y.astype(x.dtype), mean[:, 0], var[:, 0]


@pytest.mark.parametrize("residual", [False, True])
def test_fused_layer_norm_matches_reference(residual):
    rng = np.random.RandomState(2)
    rows, cols = 128, 256
    x = jnp.asarray(rng.randn(rows, cols).astype("float32"))
    r = jnp.asarray(rng.randn(rows, cols).astype("float32")) if residual else None
    scale = jnp.asarray(rng.rand(cols).astype("float32") + 0.5)
    bias = jnp.asarray(rng.randn(cols).astype("float32"))
    assert pk.ln_path_taken(rows, cols)
    s, y, mean, var = pk.fused_layer_norm(x, r, scale, bias, 1e-5)
    s_ref, y_ref, mean_ref, var_ref = _ln_reference(x, r, scale, bias, 1e-5)
    if residual:
        # the residual sum is the graph value grads replay from: bit-exact
        np.testing.assert_array_equal(np.asarray(s), np.asarray(s_ref))
    else:
        assert s is None
    np.testing.assert_allclose(np.asarray(mean), np.asarray(mean_ref),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(var), np.asarray(var_ref),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-5, atol=1e-5)


def test_fused_layer_norm_grad_matches_vjp():
    rng = np.random.RandomState(3)
    rows, cols = 128, 256
    x = jnp.asarray(rng.randn(rows, cols).astype("float32"))
    scale = jnp.asarray(rng.rand(cols).astype("float32") + 0.5)
    bias = jnp.asarray(rng.randn(cols).astype("float32"))
    dy = jnp.asarray(rng.randn(rows, cols).astype("float32"))

    def f(x, scale, bias):
        return _ln_reference(x, None, scale, bias, 1e-5)[1]

    _, vjp = jax.vjp(f, x, scale, bias)
    dx_ref, ds_ref, db_ref = vjp(dy)
    _, _, mean, var = pk.fused_layer_norm(x, None, scale, bias, 1e-5)
    dx, ds, db = pk.fused_layer_norm_grad(x, scale, mean, var, dy, 1e-5)
    np.testing.assert_allclose(np.asarray(dx), np.asarray(dx_ref),
                               rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(np.asarray(ds), np.asarray(ds_ref),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(db), np.asarray(db_ref),
                               rtol=2e-4, atol=2e-4)


def _assert_within_ulps(got, want, ulps=4):
    """Equal to a few units in the last place of the storage dtype, on
    operands of magnitude ~1. Two separately compiled forms of the same f32
    expressions are not bit-equal as a contract: where XLA contracts a
    multiply-add into an FMA differs between the compiled kernel body and
    the jitted reference, and between XLA versions."""
    got, want = np.asarray(got), np.asarray(want)
    assert got.dtype == want.dtype, (got.dtype, want.dtype)
    tol = ulps * float(jnp.finfo(got.dtype).eps)
    np.testing.assert_allclose(got.astype(np.float32), want.astype(np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("grad_dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("moment_dtype", ["float32", "bfloat16"])
def test_adam_lowering_matches_f32_chain(moment_dtype, grad_dtype):
    """The one optimizer lowering of every tier (core_ops._adam under
    _opt_f32): f32 math whatever the gradient's and the moments' dtypes,
    every <Slot>Out rounded to its slot's storage dtype — within a few ulps
    of a jitted reference of the same expressions, ragged shapes included."""
    rng = np.random.RandomState(4)
    b1, b2, eps = 0.9, 0.999, 1e-8
    shapes = [(256, 384), (384,), (128, 128), (7, 13)]  # incl. ragged tail
    lr = jnp.asarray([1e-3], jnp.float32)
    b1p = jnp.asarray([b1 ** 3], jnp.float32)
    b2p = jnp.asarray([b2 ** 3], jnp.float32)
    lower = registry.get("adam").lower

    @jax.jit
    def got(p, g, m1, m2):
        return lower(
            registry.LowerCtx(jax.random.key(0)),
            {"Param": [p], "Grad": [g], "Moment1": [m1], "Moment2": [m2],
             "LearningRate": [lr], "Beta1Pow": [b1p], "Beta2Pow": [b2p]},
            {"beta1": b1, "beta2": b2, "epsilon": eps},
        )

    @jax.jit
    def ref(p, g, m1, m2):
        gf = g.astype(jnp.float32)
        lr_t = lr[0] * jnp.sqrt(1 - b2p[0]) / (1 - b1p[0])
        m1o = b1 * m1.astype(jnp.float32) + (1 - b1) * gf
        m2o = b2 * m2.astype(jnp.float32) + (1 - b2) * jnp.square(gf)
        po = p.astype(jnp.float32) - lr_t * m1o / (jnp.sqrt(m2o) + eps)
        return po.astype(p.dtype), m1o.astype(m1.dtype), m2o.astype(m2.dtype)

    for s in shapes:
        p = jnp.asarray(rng.randn(*s).astype("float32"))
        g = jnp.asarray(rng.randn(*s), grad_dtype)
        m1 = jnp.asarray(rng.randn(*s), moment_dtype)
        m2 = jnp.asarray(np.abs(rng.randn(*s)), moment_dtype)
        outs = got(p, g, m1, m2)
        po_r, m1o_r, m2o_r = ref(p, g, m1, m2)
        _assert_within_ulps(outs["ParamOut"][0], po_r)
        _assert_within_ulps(outs["Moment1Out"][0], m1o_r)
        _assert_within_ulps(outs["Moment2Out"][0], m2o_r)
        assert str(outs["Moment1Out"][0].dtype) == moment_dtype
        assert outs["ParamOut"][0].dtype == jnp.float32


# --------------------------------------------------------------------------
# pipeline parity through the executors — shapes chosen so every path
# predicate holds (batch 128, width 256): mul+add+gelu and mul+add hit the
# GEMM epilogue, the residual add + layer_norm pair hits the LN kernel;
# Adam's 8 params update per op on both sides
# --------------------------------------------------------------------------


def _build_residual_ln_model(moment_dtype=None):
    main, startup = framework.Program(), framework.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[256], dtype="float32")
        y = fluid.layers.data(name="y", shape=[1], dtype="float32")
        h = fluid.layers.fc(x, size=256, act="gelu")
        h2 = fluid.layers.fc(h, size=256)
        r = fluid.layers.elementwise_add(h2, h)
        ln = fluid.layers.layer_norm(r, begin_norm_axis=1)
        pred = fluid.layers.fc(ln, size=1)
        loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
        fluid.optimizer.Adam(
            learning_rate=1e-3, moment_dtype=moment_dtype
        ).minimize(loss)
    return main, startup, loss


def _build_plain_model(moment_dtype=None):
    """Two fc layers and no layer_norm: at a batch the GEMM tiling declines
    (ragged, more than one tile) no kernel family substitutes."""
    main, startup = framework.Program(), framework.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[256], dtype="float32")
        y = fluid.layers.data(name="y", shape=[1], dtype="float32")
        pred = fluid.layers.fc(fluid.layers.fc(x, size=40, act="tanh"), size=1)
        loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
        fluid.optimizer.Adam(
            learning_rate=1e-3, moment_dtype=moment_dtype
        ).minimize(loss)
    return main, startup, loss


def _executor_run(pipeline, moment_dtype=None, steps=4,
                  build=_build_residual_ln_model, batch=128):
    """(losses, first-fc weight grads, final param values) under the given
    FLAGS_pass_pipeline through the plain Executor."""
    flags.set_flags({"pass_pipeline": pipeline})
    try:
        main, startup, loss = build(moment_dtype)
        pnames = [v.name for v in main.global_block().all_parameters()]
        exe = fluid.Executor()
        rng = np.random.RandomState(3)
        W = rng.randn(256, 1).astype("float32")
        losses, grads = [], []
        scope = Scope(seed=11)
        with scope_guard(scope):
            exe.run(startup)
            for _ in range(steps):
                xs = rng.randn(batch, 256).astype("float32")
                lv, gv = exe.run(
                    main, feed={"x": xs, "y": xs @ W},
                    fetch_list=[loss.name, pnames[0] + "@GRAD"],
                )
                losses.append(np.asarray(lv).copy())
                grads.append(np.asarray(gv).copy())
            finals = {n: np.asarray(scope.vars[n]).copy() for n in pnames}
        return np.stack(losses), np.stack(grads), finals
    finally:
        flags.set_flags({"pass_pipeline": ""})


def test_fused_pipeline_parity_executor():
    """training_fused on vs off through Executor: losses, fetched grads, and
    the trained params all agree — and the dispatch counters prove every
    kernel family actually substituted (no silent per-op fallback)."""
    pk.KERNEL_DISPATCHES.clear()
    off_l, off_g, off_p = _executor_run("")
    assert not pk.KERNEL_DISPATCHES, pk.KERNEL_DISPATCHES
    on_l, on_g, on_p = _executor_run("training_fused")
    for family in ("gemm_epilogue", "layer_norm", "layer_norm_grad"):
        assert pk.KERNEL_DISPATCHES.get(family, 0) > 0, (
            family, pk.KERNEL_DISPATCHES)
    np.testing.assert_allclose(on_l, off_l, rtol=_RTOL, atol=_ATOL)
    np.testing.assert_allclose(on_g, off_g, rtol=_RTOL, atol=_ATOL)
    for n in off_p:
        np.testing.assert_allclose(on_p[n], off_p[n], rtol=_RTOL, atol=_ATOL,
                                   err_msg=n)


def test_fused_pipeline_parity_executor_bf16_moments():
    """The benchmark's default (bf16 Adam moments) under the fused forward
    and backward kernels: the trajectory bar is unchanged."""
    off_l, _, off_p = _executor_run("", moment_dtype="bfloat16")
    on_l, _, on_p = _executor_run("training_fused", moment_dtype="bfloat16")
    np.testing.assert_allclose(on_l, off_l, rtol=_RTOL, atol=_ATOL)
    for n in off_p:
        np.testing.assert_allclose(on_p[n], off_p[n], rtol=_RTOL, atol=_ATOL,
                                   err_msg=n)


def _pe_run(fuse_kernels, zero1=False, steps=4):
    bs = BuildStrategy()
    bs.fuse_kernels = fuse_kernels
    if zero1:
        bs.reduce_strategy = ReduceStrategy.Reduce
    main, startup, loss = _build_residual_ln_model()
    exe = fluid.Executor()
    rng = np.random.RandomState(3)
    W = rng.randn(256, 1).astype("float32")
    losses = []
    scope = Scope(seed=2)
    with scope_guard(scope):
        exe.run(startup)
        pe = fluid.ParallelExecutor(
            loss_name=loss.name, main_program=main, build_strategy=bs,
            scope=scope,
        )
        for _ in range(steps):
            xs = rng.randn(128, 256).astype("float32")
            (l,) = pe.run([loss.name], feed={"x": xs, "y": xs @ W})
            losses.append(float(np.asarray(l).reshape(-1)[0]))
    return losses, pe


def test_fused_pipeline_parity_parallel_executor():
    """BuildStrategy.fuse_kernels resolves to the training_fused preset and
    the SPMD lowering matches the unfused run over the 8-device mesh."""
    pk.KERNEL_DISPATCHES.clear()
    off, _ = _pe_run(False)
    assert not pk.KERNEL_DISPATCHES, pk.KERNEL_DISPATCHES
    on, _ = _pe_run(True)
    for family in ("gemm_epilogue", "layer_norm", "layer_norm_grad"):
        assert pk.KERNEL_DISPATCHES.get(family, 0) > 0, (
            family, pk.KERNEL_DISPATCHES)
    np.testing.assert_allclose(on, off, rtol=_RTOL, atol=_ATOL)


def test_zero1_under_training_fused_matches_unfused_with_sharded_state():
    """Under ReduceStrategy.Reduce the forward/backward kernels substitute,
    the per-param update keeps the moment sharding GSPMD plans around, and
    the trajectory matches the unfused ZeRO-1 run."""
    pk.KERNEL_DISPATCHES.clear()
    z_off, _ = _pe_run(False, zero1=True)
    z_on, zpe = _pe_run(True, zero1=True)
    assert pk.KERNEL_DISPATCHES.get("gemm_epilogue", 0) > 0
    if zpe.device_count > 1:
        assert zpe._last_run[0].zero1_state_names
    np.testing.assert_allclose(z_on, z_off, rtol=_RTOL, atol=_ATOL)


def test_training_fused_tags_no_optimizer_family():
    """One optimizer lowering for every tier: training_fused tags the GEMM
    and layer-norm runs and leaves the run of adam ops to core_ops._adam;
    no pass and no fused lowering is registered for an optimizer."""
    from paddle_tpu import passes

    assert "fuse_optimizer" not in passes.registered_passes()
    assert not [f for f in registry.FUSED_LOWERINGS if "adam" in f]
    main, _, loss = _build_residual_ln_model()
    fused = passes.PassManager("training_fused").apply(
        main, feed_names=("x", "y"), fetch_names=(loss.name,))
    ops = fused.global_block().ops
    adams = [op for op in ops if op.type == "adam"]
    assert len(adams) == 8
    for op in adams:
        assert registry.PALLAS_GROUP_ATTR not in op.attrs
        assert registry.PALLAS_KERNEL_ATTR not in op.attrs
    families = {op.attrs.get(registry.PALLAS_KERNEL_ATTR) for op in ops}
    assert families == {None, "gemm_epilogue", "layer_norm", "layer_norm_grad"}


def test_training_fused_without_a_kernel_chain_is_the_default_bit_for_bit():
    """Where no GEMM or layer-norm run substitutes, training_fused adds
    nothing: the update is the per-op Adam of training_default, bit for
    bit."""
    pk.KERNEL_DISPATCHES.clear()
    runs = [
        _executor_run(pipeline, moment_dtype="bfloat16", steps=3,
                      build=_build_plain_model, batch=1000)
        for pipeline in ("training_fused", "training_default")
    ]
    assert not pk.KERNEL_DISPATCHES, pk.KERNEL_DISPATCHES
    (fused_l, _, fused_p), (default_l, _, default_p) = runs
    np.testing.assert_array_equal(fused_l, default_l)
    for n in default_p:
        np.testing.assert_array_equal(fused_p[n], default_p[n], err_msg=n)


def test_build_strategy_pipeline_resolution():
    bs = BuildStrategy()
    assert bs.resolved_pass_pipeline() is None
    bs.fuse_kernels = True
    assert bs.resolved_pass_pipeline() == "training_fused"
    bs.pass_pipeline = "training_default"  # explicit pipeline wins
    assert bs.resolved_pass_pipeline() == "training_default"
