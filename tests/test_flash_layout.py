"""Flash attention over [b, t, h·d] operands, the layout the projections
leave the heads in (docs/flash_attention.md): the kernels against
_attention_reference tier by tier, the tier each shape takes and the
KERNEL_DISPATCHES key that says so, the Program multi_head_attention emits,
and the op under shard_map on the four-device CPU mesh."""

import collections

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu.fluid as fluid
from paddle_tpu import framework
from paddle_tpu.executor import Scope, scope_guard
from paddle_tpu.ops import pallas_kernels as pk

TWO, ONE = "flash_heads_a_block:2", "flash_heads_a_block:1"
SPLIT, DENSE = pk.FLASH_SPLIT, pk.FLASH_DENSE


def _split(x, h):
    b, t, f = x.shape
    return x.reshape(b, t, h, f // h).transpose(0, 2, 1, 3)


def _merge(x):
    b, h, t, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b, t, h * d)


def _reference(q, k, v, h, causal):
    d = q.shape[-1] // h
    return _merge(pk._attention_reference(
        _split(q, h), _split(k, h), _split(v, h), causal, d ** -0.5))


def _operands(b, tq, tk, h, d, dtype=jnp.float32, seed=0):
    rng = np.random.RandomState(seed)
    return tuple(
        jnp.asarray(rng.randn(b, t, h * d) * 0.5, dtype)
        for t in (tq, tk, tk, tq)
    )


# name -> (b, tq, tk, heads, d_head, causal, blocks, streamed, tier)
CASES = {
    "two a block": (2, 256, 256, 4, 64, False, None, False, TWO),
    "two a block causal": (2, 256, 256, 4, 64, True, (128, 128), False, TWO),
    "two a block, two k blocks": (1, 256, 256, 2, 64, False, (256, 128), False, TWO),
    "one a block": (1, 256, 256, 2, 128, False, None, False, ONE),
    "one a block causal": (1, 256, 256, 2, 128, True, (128, 128), False, ONE),
    "four a block causal": (1, 256, 256, 8, 32, True, (128, 128), False,
                            "flash_heads_a_block:4"),
    "cross attention": (2, 128, 384, 2, 64, False, None, False, TWO),
    "cross attention causal": (1, 128, 256, 2, 64, True, None, False, TWO),
    "cross attention one a block": (1, 256, 128, 1, 128, False, None, False, ONE),
    "streamed two a block": (2, 256, 256, 4, 64, False, (128, 128), True, TWO),
    "streamed two a block causal": (1, 256, 512, 4, 64, True, (128, 128), True, TWO),
    "streamed one a block causal": (1, 256, 256, 2, 128, True, (128, 128), True, ONE),
    "declined: odd head count at 64": (1, 256, 256, 3, 64, True, None, False, SPLIT),
    "declined: d_head 96": (1, 128, 128, 2, 96, False, None, False, SPLIT),
    "declined: t under a lane": (2, 64, 64, 2, 64, True, None, False, SPLIT),
    "declined: t not whole lanes": (1, 200, 200, 2, 64, False, None, False, SPLIT),
    "declined, streamed": (1, 64, 64, 3, 64, True, None, True, SPLIT),
    "ragged tiles": (1, 600, 600, 2, 64, True, None, False, DENSE),
}


def _run_case(name, monkeypatch, grads):
    b, tq, tk, h, d, causal, blocks, streamed, tier = CASES[name]
    if streamed:
        monkeypatch.setattr(pk, "_resident_ok", lambda *a: False)
    bq, bk = blocks or (None, None)
    q, k, v, do = _operands(b, tq, tk, h, d)

    def flash(q, k, v):
        return pk.flash_attention(q, k, v, causal, None, bq, bk, None, h)

    def reference(q, k, v):
        return _reference(q, k, v, h, causal)

    pk.KERNEL_DISPATCHES.clear()
    if grads:
        got = jax.grad(lambda *a: (flash(*a) * do).sum(), (0, 1, 2))(q, k, v)
        want = jax.grad(lambda *a: (reference(*a) * do).sum(), (0, 1, 2))(q, k, v)
    else:
        got, want = [flash(q, k, v)], [reference(q, k, v)]
    taken = {n: c for n, c in pk.KERNEL_DISPATCHES.items() if n.startswith("flash")}
    assert list(taken) == [tier], taken
    assert pk.flash_tier(tq, tk, h, d, causal, bq, bk)[0] == tier
    for g, w in zip(got, want):
        scale = max(1.0, float(jnp.max(jnp.abs(w))))
        np.testing.assert_allclose(
            np.asarray(g) / scale, np.asarray(w) / scale, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("name", sorted(CASES))
def test_forward_matches_reference(name, monkeypatch):
    _run_case(name, monkeypatch, grads=False)


@pytest.mark.parametrize("name", sorted(CASES))
def test_gradients_match_reference(name, monkeypatch):
    _run_case(name, monkeypatch, grads=True)


@pytest.mark.parametrize("causal", [False, True])
def test_bf16_operands_round_as_the_reference_does(causal):
    """bf16 in, f32 accumulation, the probabilities rounded to v's dtype
    before P·V: the same roundings as the dense chain, so the two agree to a
    bf16 last bit of the largest value."""
    h = 4
    q, k, v, do = _operands(2, 256, 256, h, 64, jnp.bfloat16, seed=3)
    f32 = jnp.float32

    def loss(fn):
        return lambda *a: (fn(*a).astype(f32) * do.astype(f32)).sum()

    flash = lambda q, k, v: pk.flash_attention(q, k, v, causal, None, None, None, None, h)
    reference = lambda q, k, v: _reference(q, k, v, h, causal)
    got = [flash(q, k, v)] + list(jax.grad(loss(flash), (0, 1, 2))(q, k, v))
    want = [reference(q, k, v)] + list(jax.grad(loss(reference), (0, 1, 2))(q, k, v))
    for g, w in zip(got, want):
        assert g.dtype == jnp.bfloat16
        top = float(jnp.max(jnp.abs(w.astype(f32))))
        err = float(jnp.max(jnp.abs(g.astype(f32) - w.astype(f32))))
        assert err <= 2 ** -7 * top, (err, top)


def test_fully_masked_rows_read_zero():
    """Causal with more queries than keys: rows that see no key give 0, not
    the reference's nan, and leave a finite lse and zero gradients."""
    h = 2
    q, k, v, do = _operands(1, 256, 128, h, 64, seed=5)
    flash = lambda q, k, v: pk.flash_attention(q, k, v, True, None, None, None, None, h)
    out = flash(q, k, v)
    assert np.isfinite(np.asarray(out)).all()
    np.testing.assert_array_equal(np.asarray(out[:, :128]), 0.0)
    for g in jax.grad(lambda *a: (flash(*a) * do).sum(), (0, 1, 2))(q, k, v):
        assert np.isfinite(np.asarray(g)).all()


@pytest.mark.parametrize("n_head,d,want", [
    (16, 64, 2), (8, 128, 1), (4, 256, 1), (1, 40, 1), (8, 32, 4), (16, 8, 16),
    (3, 64, 0), (2, 96, 0), (4, 8, 0), (6, 48, 0),
])
def test_heads_a_block(n_head, d, want):
    """The one parameter: heads that share a 128-lane block of the last
    axis; 0 where the heads cannot be blocked and are split first."""
    assert pk._heads_a_block(n_head, d) == want


def test_rank4_callers_reach_the_same_kernels():
    """(b, h, t, d) operands (the flash ring, the fusing pass) run the same
    kernel bodies over the (b·h, t, d) view, one head a block."""
    rng = np.random.RandomState(1)
    q, k, v = (jnp.asarray(rng.randn(2, 3, 256, 32), jnp.float32) for _ in range(3))
    pk.KERNEL_DISPATCHES.clear()
    out, lse = pk._flash_forward(q, k, v, True, 32 ** -0.5, 128, 128, True,
                                 with_lse=True)
    assert pk.KERNEL_DISPATCHES == {ONE: 1}
    ref = pk._attention_reference(q, k, v, True, 32 ** -0.5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)
    assert lse.shape == (2, 3, 256)
    three = pk.flash_attention(_merge(q), _merge(k), _merge(v), True, None,
                               128, 128, None, 3)
    np.testing.assert_allclose(
        np.asarray(_split(three, 3)), np.asarray(out), rtol=2e-5, atol=2e-5)


def _mha_program(t=128, d_model=128, n_head=2, causal=True, use_flash=True):
    from paddle_tpu.models.transformer import multi_head_attention

    main, startup = framework.Program(), framework.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[t, d_model], dtype="float32")
        y = fluid.layers.data(name="y", shape=[t, d_model], dtype="float32")
        out = multi_head_attention(
            x, x, x, None, d_key=d_model // n_head, d_value=d_model // n_head,
            d_model=d_model, n_head=n_head, dropout_rate=0.0,
            use_flash=use_flash, causal=causal,
        )
        loss = fluid.layers.mean(fluid.layers.square_error_cost(out, y))
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    return main, startup, loss


def test_mha_emits_flash_on_the_projections_outputs():
    """No reshape or transpose2 between the projections and flash_attention,
    and none between flash_attention_grad and the projections' gradients:
    q, k, v come from mul, the context goes to mul, and so do the grads."""
    main, _, _ = _mha_program()
    ops = main.global_block().ops
    types = collections.Counter(op.type for op in ops)
    assert types["flash_attention"] == types["flash_attention_grad"] == 1
    assert not types["transpose2"] and not types["transpose2_grad"], types
    assert not types["reshape2"] and not types["reshape2_grad"], types
    producer = {n: op.type for op in ops for n in op.output_arg_names}
    consumers = collections.defaultdict(set)
    for op in ops:
        for n in op.input_arg_names:
            consumers[n].add(op.type)
    fa = next(op for op in ops if op.type == "flash_attention")
    assert fa.attrs["n_head"] == 2
    assert [producer[fa.input(s)[0]] for s in "QKV"] == ["mul"] * 3
    assert consumers[fa.output("Out")[0]] == {
        "mul", "mul_grad", "flash_attention_grad"}
    fg = next(op for op in ops if op.type == "flash_attention_grad")
    assert fg.attrs["n_head"] == 2 and "Lse" in fg.inputs
    assert producer[fg.input("Out@GRAD")[0]] == "mul_grad"
    for slot in ("Q@GRAD", "K@GRAD", "V@GRAD"):
        assert consumers[fg.output(slot)[0]] <= {"mul_grad", "sum"}


def test_mha_without_flash_keeps_the_head_split():
    """The dense chain still splits and merges the heads (four transposes)."""
    main, _, _ = _mha_program(use_flash=False)
    types = collections.Counter(op.type for op in main.global_block().ops)
    assert types["transpose2"] == 4 and not types["flash_attention"]


def _train(main, startup, loss, feed, steps=2, executor=None):
    """(losses, the parameters after the steps) from seed 0."""
    scope = Scope(seed=0)
    with scope_guard(scope):
        fluid.Executor(fluid.CPUPlace()).run(startup)
        run = executor(main, loss, scope) if executor else (
            lambda: fluid.Executor(fluid.CPUPlace()).run(
                main, feed=feed, fetch_list=[loss.name])[0])
        losses = [float(np.asarray(run()).reshape(-1)[0]) for _ in range(steps)]
        params = {p.name: np.asarray(scope.vars[p.name])
                  for p in main.global_block().all_parameters()}
    return losses, params


def _feed(t=128, d_model=128, b=4):
    rng = np.random.RandomState(2)
    return {"x": rng.randn(b, t, d_model).astype("float32"),
            "y": rng.randn(b, t, d_model).astype("float32")}


def test_mha_flash_trains_as_the_dense_chain_does():
    """The same seed through the flash Program and the dense one: the same
    losses and the same parameters after two steps (the gradients, through
    SGD), to an online softmax's rounding."""
    feed = _feed()
    dense = _train(*_mha_program(use_flash=False), feed)
    pk.KERNEL_DISPATCHES.clear()
    flash = _train(*_mha_program(), feed)
    assert list(pk.KERNEL_DISPATCHES) == [TWO], pk.KERNEL_DISPATCHES
    np.testing.assert_allclose(flash[0], dense[0], rtol=1e-5)
    for name, want in dense[1].items():
        np.testing.assert_allclose(flash[1][name], want, rtol=1e-4, atol=1e-6)


MESHES = {
    "dp 4": (dict(dp=4), 2, TWO),
    "dp 2 x tp 2": (dict(dp=2, tp=2), 4, TWO),
    # a device's share of the last axis is one 64-wide head, half a lane
    # block: the heads stay whole on every device, the batch still splits
    "dp 2 x tp 2, heads kept whole": (dict(dp=2, tp=2), 2, TWO),
    "fsdp 2 x tp 2, one 128-wide head a device": (dict(fsdp=2, tp=2), 2, ONE),
}


@pytest.mark.parametrize("name", sorted(MESHES))
def test_parallel_executor_gives_the_one_device_step(name):
    """The flash ops under shard_map on the four-device CPU mesh: losses and
    parameters after two steps as on one device."""
    from paddle_tpu.parallel import MeshConfig

    mesh, n_head, tier = MESHES[name]
    d_model = 256 if tier == ONE else 64 * n_head
    feed = _feed(d_model=d_model)
    one = _train(*_mha_program(d_model=d_model, n_head=n_head), feed)

    def executor(main, loss, scope):
        pe = fluid.ParallelExecutor(
            loss_name=loss.name, main_program=main, scope=scope,
            mesh_config=MeshConfig(**mesh), devices=jax.devices()[:4])
        return lambda: pe.run(fetch_list=[loss.name], feed=feed)[0]

    pk.KERNEL_DISPATCHES.clear()
    many = _train(*_mha_program(d_model=d_model, n_head=n_head), feed,
                  executor=executor)
    assert list(pk.KERNEL_DISPATCHES) == [tier], pk.KERNEL_DISPATCHES
    np.testing.assert_allclose(many[0], one[0], rtol=1e-5)
    for pname, want in one[1].items():
        np.testing.assert_allclose(many[1][pname], want, rtol=1e-4, atol=1e-6)


@pytest.mark.parametrize("mesh,n_head,d,heads_local", [
    (dict(dp=2, tp=2), 4, 64, 2),
    (dict(dp=2, tp=2), 2, 64, 2),
    (dict(dp=2, tp=2), 2, 128, 1),
    (dict(dp=4), 4, 64, 4),
])
def test_heads_split_over_tp_only_in_whole_lane_blocks(mesh, n_head, d, heads_local):
    """_flash_on_mesh hands the kernels the heads a device holds: h / tp
    where that share of the last axis is whole 128-lane blocks, else all."""
    from paddle_tpu.ops.registry import LowerCtx
    from paddle_tpu.parallel import MeshConfig, make_mesh

    ctx = LowerCtx(None, mesh=make_mesh(MeshConfig(**mesh), jax.devices()[:4]))
    seen = []

    def fn(n_head, q):
        seen.append((n_head, q.shape))
        return (q,)

    q = jnp.zeros((4, 128, n_head * d), jnp.float32)
    pk._flash_on_mesh(ctx, fn, (q,), "x", "x", n_head)
    (local, shape), = seen
    assert local == heads_local
    assert shape[2] == heads_local * d and shape[0] == 4 // (
        mesh.get("dp", 1) * mesh.get("fsdp", 1))


def test_parallel_executor_traces_and_compiles_its_step_once():
    """What PR 37 found behind tbig_train_dp4's set-up: a plain Executor's
    startup leaves the state on one device with no mesh in its type, the
    step's outputs carry the mesh, and jit keys its traces on that, so the
    step was traced and compiled for the first call and again for the second:
    four 46 MB executables a run where two do, and the compile cache of the
    chip's machine (192 MiB) could not hold four of the new kernels' 53 MB.
    ParallelExecutor now places the state before the first call: nothing is
    traced or compiled after it."""
    import jax.monitoring

    from paddle_tpu.parallel import MeshConfig

    seen = []

    def listen(event, duration, **_):
        if event.startswith("/jax/core/compile/"):
            seen.append(event)

    # jax.monitoring has no public way to drop one listener: it stays,
    # appending to a list nothing reads
    jax.monitoring.register_event_duration_secs_listener(listen)
    main, startup, loss = _mha_program()
    feed = _feed()
    scope = Scope(seed=0)
    with scope_guard(scope):
        fluid.Executor(fluid.CPUPlace()).run(startup)
        pe = fluid.ParallelExecutor(
            loss_name=loss.name, main_program=main, scope=scope,
            mesh_config=MeshConfig(dp=4), devices=jax.devices()[:4])
        pe.run(fetch_list=[loss.name], feed=feed)
        first = len(seen)
        pk.KERNEL_DISPATCHES.clear()
        for _ in range(2):
            pe.run(fetch_list=[loss.name], feed=feed)
    again = [e for e in seen[first:] if not e.endswith("jaxpr_trace_duration")]
    assert first and again == [], again  # nothing lowered, nothing compiled
    assert pk.KERNEL_DISPATCHES == {}, pk.KERNEL_DISPATCHES
