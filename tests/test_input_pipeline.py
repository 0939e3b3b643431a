"""Input-pipeline overlap evidence.

The PyReader feeder thread must stage batch N+1 (host assembly +
device_put) WHILE step N computes — the reference's double-buffer reader
contract (operators/reader/buffered_reader.h:48). PyReader throughput
against a chip is a device measurement and is not taken here; the overlap
property itself is asserted on the CPU backend, where transfers are
memcpy-fast and the compute/feed times are controlled.
"""

import time

import numpy as np

import paddle_tpu.fluid as fluid
from paddle_tpu import framework
from paddle_tpu.executor import Scope, scope_guard
from paddle_tpu.py_reader import PyReader

FEED_DELAY = 0.08  # synthetic host-side cost per batch (parse/augment)
STEPS = 6


def _build(n=512):
    main, startup = framework.Program(), framework.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[n], dtype="float32")
        h = x
        for _ in range(4):  # enough matmul work to overlap against
            h = fluid.layers.fc(h, size=n)
        loss = fluid.layers.mean(h)
    return main, startup, loss


def test_pyreader_overlaps_feed_with_compute():
    main, startup, loss = _build()
    exe = fluid.Executor(fluid.CPUPlace())
    rng = np.random.RandomState(0)
    batch = {"x": rng.rand(64, 512).astype("float32")}

    def slow_reader():
        for _ in range(STEPS):
            time.sleep(FEED_DELAY)  # host-side work the pipeline must hide
            yield dict(batch)

    with scope_guard(Scope(seed=0)):
        exe.run(startup)
        # warm the compile cache and time one compute step
        (l,) = exe.run(main, feed=batch, fetch_list=[loss.name], return_numpy=False)
        np.asarray(l)
        t0 = time.perf_counter()
        for _ in range(3):
            (l,) = exe.run(main, feed=batch, fetch_list=[loss.name], return_numpy=False)
        np.asarray(l)
        step_time = (time.perf_counter() - t0) / 3

        reader = PyReader(["x"], capacity=2)
        reader.decorate_tensor_provider(slow_reader)
        reader.start()
        t0 = time.perf_counter()
        n_batches = 0
        for feed in reader():
            (l,) = exe.run(main, feed=feed, fetch_list=[loss.name], return_numpy=False)
            n_batches += 1
        np.asarray(l)
        wall = time.perf_counter() - t0

    assert n_batches == STEPS
    sequential = STEPS * (FEED_DELAY + step_time)
    overlapped = STEPS * max(FEED_DELAY, step_time)
    # the pipeline must land meaningfully below the no-overlap time; the
    # margin absorbs CI timer noise (sequential/overlapped differ by the
    # smaller of feed/compute per step)
    budget = overlapped + 0.6 * (sequential - overlapped) + 0.15
    assert wall < budget, (
        "no feed/compute overlap: wall=%.3fs sequential=%.3fs overlapped=%.3fs"
        % (wall, sequential, overlapped)
    )


def test_pyreader_compact_wire_uint8():
    """wire_dtypes stages the batch in the compact dtype (uint8 pixels: 4x
    fewer bytes over the link) and the executor's declared-dtype cast
    converts on device — results must equal feeding the f32 directly."""
    import jax

    main, startup, loss = _build(n=64)
    exe = fluid.Executor(fluid.CPUPlace())
    rng = np.random.RandomState(1)
    img_u8 = rng.randint(0, 256, (8, 64)).astype("uint8")

    def reader():
        yield {"x": img_u8}

    wire = PyReader(["x"], capacity=2, wire_dtypes={"x": "uint8"})
    wire.decorate_tensor_provider(reader)
    with scope_guard(Scope(seed=0)):
        exe.run(startup)
        wire.start()
        try:
            batch = wire.next_batch()
            # the staged array really is the compact wire dtype on device
            assert isinstance(batch["x"], jax.Array)
            assert str(batch["x"].dtype) == "uint8"
            (got,) = exe.run(main, feed=batch, fetch_list=[loss.name])
        finally:
            wire.reset()
        (want,) = exe.run(
            main,
            feed={"x": img_u8.astype("float32")},
            fetch_list=[loss.name],
        )
    np.testing.assert_allclose(got, want, rtol=1e-6)


def test_pyreader_compact_wire_bf16():
    """bf16 wire: half the bytes of f32; device cast back to the declared
    f32 var dtype keeps the program's compute precision unchanged."""
    import jax

    main, startup, loss = _build(n=64)
    exe = fluid.Executor(fluid.CPUPlace())
    rng = np.random.RandomState(2)
    x = rng.rand(8, 64).astype("float32")

    wire = PyReader(["x"], capacity=2, wire_dtypes={"x": "bfloat16"})
    wire.decorate_tensor_provider(lambda: iter([{"x": x}]))
    with scope_guard(Scope(seed=0)):
        exe.run(startup)
        wire.start()
        try:
            batch = wire.next_batch()
            assert str(batch["x"].dtype) == "bfloat16"
            assert batch["x"].nbytes == x.nbytes // 2
            (got,) = exe.run(main, feed=batch, fetch_list=[loss.name])
        finally:
            wire.reset()
        (want,) = exe.run(main, feed={"x": x}, fetch_list=[loss.name])
    # bf16 quantization of the input is the only difference
    np.testing.assert_allclose(got, want, rtol=2e-2, atol=1e-3)


def test_multireader_midstep_eof_pushback_and_group_eof():
    """When one reader of a program's reader group ends mid-step during a
    multi-step pull, sibling batches already pulled for the incomplete step
    are pushed back (not dropped), and the NEXT pull raises EOF for the
    whole group instead of proceeding with feeds missing the exhausted
    reader's slots."""
    import pytest

    from paddle_tpu.executor import _pull_reader_steps, _started_readers
    from paddle_tpu.py_reader import EOFException, PyReader

    def make(name, n):
        rd = PyReader([name], capacity=8, return_device_arrays=False)
        rd.decorate_tensor_provider(
            lambda n=n, name=name: (
                {name: np.full((2, 3), i, "float32")} for i in range(n)
            )
        )
        rd.start()
        return rd

    ra, rb = make("a", 5), make("b", 3)  # b exhausts first
    feed, k = _pull_reader_steps([ra, rb], 2)
    assert k == 2 and feed["a"].shape == (2, 2, 3)
    # second pull: step 0 ok (a=2,b=2); step 1: a=3 pulled, then b EOFs ->
    # a's batch 3 must be pushed back, k=1 tail returned, EOF deferred
    feed, k = _pull_reader_steps([ra, rb], 2)
    assert k == 1
    assert float(np.asarray(feed["a"])[0, 0, 0]) == 2.0

    class P:  # program stub carrying the reader group
        _py_readers = [ra, rb]

    with pytest.raises(EOFException):
        _started_readers(P())
    # the pushed-back batch survives for the next epoch's consumer
    assert float(np.asarray(ra.next_batch()["a"])[0, 0]) == 3.0
