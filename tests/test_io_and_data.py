"""Checkpoint I/O + data-layer tests (reference unittests: test_io_save_load*,
test_py_reader_using_executor.py, reader decorator tests)."""

import os
import tempfile

import numpy as np

import paddle_tpu as paddle
import paddle_tpu.fluid as fluid
from paddle_tpu import framework
from paddle_tpu.executor import Scope, scope_guard


def _build_linear():
    x = fluid.layers.data(name="x", shape=[4], dtype="float32")
    y = fluid.layers.fc(x, size=2, act=None)
    return x, y


def test_save_load_persistables_roundtrip():
    main, startup = framework.Program(), framework.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x, y = _build_linear()
    exe = fluid.Executor()
    with tempfile.TemporaryDirectory() as d:
        with scope_guard(Scope(seed=1)):
            exe.run(startup)
            xv = np.ones((3, 4), "float32")
            (before,) = exe.run(main, feed={"x": xv}, fetch_list=[y.name])
            fluid.io.save_persistables(exe, d, main)
        # fresh scope: load and verify identical output
        with scope_guard(Scope(seed=99)):
            fluid.io.load_persistables(exe, d, main)
            (after,) = exe.run(main, feed={"x": xv}, fetch_list=[y.name])
        np.testing.assert_allclose(before, after)


def test_save_load_inference_model():
    main, startup = framework.Program(), framework.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x, y = _build_linear()
        # extra head that must be pruned away
        z = fluid.layers.fc(x, size=9)
    exe = fluid.Executor()
    with tempfile.TemporaryDirectory() as d:
        with scope_guard(Scope(seed=2)):
            exe.run(startup)
            xv = np.random.RandomState(0).randn(5, 4).astype("float32")
            (before,) = exe.run(main, feed={"x": xv}, fetch_list=[y.name])
            fluid.io.save_inference_model(d, ["x"], [y], exe, main)
        with scope_guard(Scope(seed=3)):
            prog, feed_names, fetch_vars = fluid.io.load_inference_model(d, exe)
            assert feed_names == ["x"]
            (after,) = exe.run(
                prog, feed={"x": xv}, fetch_list=[fetch_vars[0].name]
            )
        np.testing.assert_allclose(before, after, rtol=1e-6)
        # pruning dropped the unrelated head's params from disk
        files = set(os.listdir(d))
        assert not any("fc_1" in f for f in files), files


def test_reader_decorators():
    def r():
        return iter(range(10))

    assert list(paddle.reader.firstn(r, 3)()) == [0, 1, 2]
    assert sorted(paddle.reader.shuffle(r, 5)()) == list(range(10))
    assert list(paddle.reader.map_readers(lambda a: a * 2, r)()) == [
        2 * i for i in range(10)
    ]
    assert list(paddle.reader.buffered(r, 2)()) == list(range(10))
    chained = paddle.reader.chain(r, r)
    assert len(list(chained())) == 20
    batches = list(paddle.batch(r, 4)())
    assert batches[0] == [0, 1, 2, 3] and batches[-1] == [8, 9]


def test_data_feeder_pads_lod_fields():
    main = framework.Program()
    with fluid.program_guard(main, framework.Program()):
        words = fluid.layers.data(name="words", shape=[1], dtype="int64", lod_level=1)
        label = fluid.layers.data(name="label", shape=[1], dtype="int64")
    feeder = fluid.DataFeeder([words, label], program=main)
    feed = feeder.feed([([1, 2, 3], 0), ([4, 5], 1)])
    assert feed["words"].shape == (2, 3, 1)
    np.testing.assert_array_equal(feed["words@LEN"], [3, 2])
    assert feed["label"].shape == (2, 1)


def test_py_reader_trains_and_raises_eof():
    main, startup = framework.Program(), framework.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        reader = fluid.layers.py_reader(
            capacity=4, shapes=[(-1, 4), (-1, 1)], dtypes=["float32", "int64"]
        )
        x, label = fluid.layers.read_file(reader)
        logits = fluid.layers.fc(x, size=2)
        loss = fluid.layers.mean(
            fluid.layers.softmax_with_cross_entropy(logits, label)
        )
        fluid.optimizer.SGD(0.1).minimize(loss)

    rng = np.random.RandomState(0)

    def gen():
        for _ in range(6):
            xs = rng.randn(8, 4).astype("float32")
            ys = (xs.sum(1) > 0).astype("int64").reshape(8, 1)
            yield {"x": xs, "label": ys}

    # decorate with dict provider using real var names
    def provider():
        for batch in gen():
            yield {x.name: batch["x"], label.name: batch["label"]}

    reader.decorate_tensor_provider(provider)
    exe = fluid.Executor()
    with scope_guard(Scope(seed=0)):
        exe.run(startup)
        reader.start()
        seen = 0
        try:
            while True:
                exe.run(main, fetch_list=[loss.name])
                seen += 1
        except fluid.EOFException:
            pass
        assert seen == 6
        # second epoch works after restart
        reader.start()
        (l,) = exe.run(main, fetch_list=[loss.name])
        assert np.isfinite(l).all()
        reader.reset()


def test_standalone_pyreader_batched_tuples():
    from paddle_tpu.py_reader import PyReader

    r = PyReader(["img", "label"], return_device_arrays=False)
    data = [
        [(np.ones(4, "float32") * i, i) for i in range(3)]
        for _ in range(2)
    ]
    r.decorate_paddle_reader(lambda: iter(data))
    r.start()
    b = r.next_batch()
    assert b["img"].shape == (3, 4)
    np.testing.assert_array_equal(b["label"], [0, 1, 2])
    r.reset()
    assert r._thread is None


def test_pyreader_feeder_exception_propagates():
    """A crashing reader must surface in the consumer — NOT read as a clean
    EOF that silently truncates the epoch (round-2 advisor finding on the
    AsyncExecutor staging path)."""
    from paddle_tpu.py_reader import PyReader

    def bad_src():
        yield {"x": np.asarray([1.0], "float32")}
        raise RuntimeError("corrupt sample")

    r = PyReader(["x"], capacity=2, return_device_arrays=False)
    r.decorate_tensor_provider(bad_src)
    r.start()
    assert r.next_batch()["x"][0] == 1.0
    try:
        r.next_batch()
        raise AssertionError("expected the feeder RuntimeError")
    except RuntimeError as e:
        assert "corrupt sample" in str(e)


def test_pyreader_reset_mid_epoch_stops_thread():
    from paddle_tpu.py_reader import PyReader

    produced = []

    def src():
        for i in range(1000):
            produced.append(i)
            yield {"x": np.asarray([i])}

    r = PyReader(["x"], capacity=2, return_device_arrays=False)
    r.decorate_tensor_provider(src)
    r.start()
    r.next_batch()
    thread = r._thread
    r.reset()
    assert not thread.is_alive()
    assert len(produced) < 1000  # source was not drained


def test_pyreader_epoch_cache_replays_without_reader():
    """cache_epoch=True: epoch 1 pulls from the source; epoch 2+ replays the
    staged batches device-resident — the source, host assembly, and wire are
    out of the loop."""
    from paddle_tpu.py_reader import PyReader, EOFException

    pulls = []

    def src():
        pulls.append(1)
        for i in range(4):
            yield {"x": np.full((2, 3), i, "float32")}

    r = PyReader(["x"], capacity=2, cache_epoch=True)
    r.decorate_tensor_provider(src)

    def epoch():
        r.start()
        got = []
        try:
            while True:
                got.append(np.asarray(r.next_batch()["x"]))
        except EOFException:
            return got

    e1 = epoch()
    e2 = epoch()
    e3 = epoch()
    assert len(pulls) == 1  # source consulted once, epochs 2-3 cached
    assert len(e1) == len(e2) == len(e3) == 4
    for a, b in zip(e1, e3):
        np.testing.assert_array_equal(a, b)
    # a new dataset invalidates the cache
    r.decorate_tensor_provider(src)
    epoch()
    assert len(pulls) == 2


def test_pyreader_partial_epoch_does_not_poison_cache():
    from paddle_tpu.py_reader import PyReader, EOFException

    def src():
        for i in range(6):
            yield {"x": np.asarray([i], "float32")}

    r = PyReader(["x"], capacity=2, cache_epoch=True)
    r.decorate_tensor_provider(src)
    r.start()
    r.next_batch()
    r.reset()  # mid-epoch abort: the partial epoch must NOT become the cache
    assert r._cache is None
    r.start()
    seen = []
    try:
        while True:
            seen.append(int(np.asarray(r.next_batch()["x"])[0]))
    except EOFException:
        pass
    assert seen == [0, 1, 2, 3, 4, 5]
    assert r._cache is not None and len(r._cache) == 6


def test_xmap_readers_order_preserved():
    def src():
        return iter(range(50))

    mapped = paddle.reader.xmap_readers(
        lambda x: x * 2, src, process_num=4, buffer_size=8, order=True
    )
    assert list(mapped()) == [2 * i for i in range(50)]


def test_pe_pulls_from_py_reader():
    main, startup = framework.Program(), framework.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        reader = fluid.layers.py_reader(
            capacity=4, shapes=[(-1, 4), (-1, 1)], dtypes=["float32", "int64"]
        )
        x, label = fluid.layers.read_file(reader)
        logits = fluid.layers.fc(x, size=2)
        loss = fluid.layers.mean(
            fluid.layers.softmax_with_cross_entropy(logits, label)
        )
        fluid.optimizer.SGD(0.1).minimize(loss)

    def provider():
        rng = np.random.RandomState(0)
        for _ in range(3):
            yield {
                x.name: rng.randn(16, 4).astype("float32"),
                label.name: rng.randint(0, 2, (16, 1)).astype("int64"),
            }

    reader.decorate_tensor_provider(provider)
    exe = fluid.Executor()
    with scope_guard(Scope(seed=0)):
        exe.run(startup)
        pe = fluid.ParallelExecutor(loss_name=loss.name, main_program=main)
        reader.start()
        n = 0
        try:
            while True:
                pe.run(fetch_list=[loss.name])
                n += 1
        except fluid.EOFException:
            pass
        assert n == 3


def test_dataset_shims():
    sample = next(paddle.dataset.mnist.train()())
    assert sample[0].shape == (784,) and 0 <= sample[1] < 10
    x, y = next(paddle.dataset.uci_housing.train()())
    assert x.shape == (13,) and y.shape == (1,)
    seq, lbl = next(paddle.dataset.imdb.train()())
    assert isinstance(seq, list) and lbl in (0, 1)


def test_predictor_and_compiled_export(tmp_path):
    """Inference deployment tier (reference AnalysisPredictor +
    fluid_lib_dist): save_inference_model -> Predictor.run, then AOT
    export_compiled -> load_compiled serves identically from the artifact
    alone."""
    import numpy as np

    import paddle_tpu.fluid as fluid
    from paddle_tpu import framework, inference
    from paddle_tpu.executor import Scope, scope_guard

    main, startup = framework.Program(), framework.Program()
    with fluid.unique_name.guard():
        with fluid.program_guard(main, startup):
            x = fluid.layers.data(name="inf_x", shape=[6], dtype="float32")
            h = fluid.layers.fc(input=x, size=8, act="relu")
            y = fluid.layers.fc(input=h, size=3, act="softmax")
    exe = fluid.Executor(fluid.CPUPlace())
    model_dir = str(tmp_path / "model")
    with scope_guard(Scope(seed=3)):
        exe.run(startup)
        fluid.io.save_inference_model(model_dir, ["inf_x"], [y], exe, main_program=main)

    pred = inference.Predictor(model_dir)
    assert pred.get_input_names() == ["inf_x"]
    feed = np.random.RandomState(0).rand(4, 6).astype("float32")
    (out1,) = pred.run({"inf_x": feed})
    assert out1.shape == (4, 3)
    np.testing.assert_allclose(out1.sum(axis=1), 1.0, rtol=1e-5)

    artifact = str(tmp_path / "compiled.npz")
    inference.export_compiled(model_dir, {"inf_x": feed}, artifact)
    served = inference.load_compiled(artifact)
    (out2,) = served.run({"inf_x": feed})
    np.testing.assert_allclose(out2, out1, rtol=1e-5, atol=1e-6)


def test_dlpack_interop_with_torch():
    """DLPack tensor interop (reference framework/dlpack_tensor.cc):
    framework tensors exchange with torch in both directions without a
    host copy when on the same device."""
    import torch

    from paddle_tpu.lod_tensor import from_dlpack, to_dlpack

    x = np.arange(12, dtype="float32").reshape(3, 4)
    jx = from_dlpack(torch.tensor(x))  # torch -> framework
    np.testing.assert_array_equal(np.asarray(jx), x)
    t = torch.utils.dlpack.from_dlpack(to_dlpack(jx * 2))  # framework -> torch
    np.testing.assert_array_equal(t.numpy(), x * 2)
    # TPU-resident (or any non-DLPack-device) values stage via host
    t2 = torch.utils.dlpack.from_dlpack(to_dlpack(np.float32([1, 2])))
    np.testing.assert_array_equal(t2.numpy(), [1, 2])


def test_resaved_f32_var_not_downcast_by_stale_dtype_meta():
    """A directory reused across runs must not resurrect an earlier run's
    bf16 dtype record: run A saves var as bf16, run B (different writer)
    re-saves the same var as f32 — the restore must be exact f32, not a
    silent bf16 round-trip (the r04 advisor repro: 1.001 restored as 1.0).
    Simulated by writing a legacy per-PID meta naming the var, as a
    different-PID writer would have left behind."""
    import json

    from paddle_tpu.io import load_arrays, save_arrays

    with tempfile.TemporaryDirectory() as d:
        # run A: var saved as bf16 (sidecar + a legacy meta another writer
        # could have left)
        import jax.numpy as jnp

        save_arrays(d, {"w": jnp.asarray([1.0009765625], jnp.bfloat16)})
        with open(os.path.join(d, "__dtypes__.12345.json"), "w") as f:
            json.dump({"w": "bfloat16"}, f)
        # run B: same var re-saved as f32
        val = np.asarray([1.001], "float32")
        save_arrays(d, {"w": val})
        got = load_arrays(d)["w"]
        assert np.asarray(got).dtype == np.float32
        np.testing.assert_array_equal(np.asarray(got), val)


def test_sidecar_dtype_round_trips_bf16():
    """bf16 vars still restore as bf16 through the sidecar records, and a
    legacy directory (meta only, no sidecar) stays readable."""
    import json

    import jax.numpy as jnp

    from paddle_tpu.io import load_arrays, save_arrays

    with tempfile.TemporaryDirectory() as d:
        save_arrays(d, {"a/b": jnp.asarray([2.5, 3.5], jnp.bfloat16)})
        assert os.path.exists(os.path.join(d, "a", "b.npy.dtype"))
        got = load_arrays(d)["a/b"]
        assert "bfloat16" in str(np.asarray(got).dtype) or got.dtype == jnp.bfloat16
    with tempfile.TemporaryDirectory() as d:
        np.save(os.path.join(d, "w.npy"), np.asarray([1.5], "float32"))
        with open(os.path.join(d, "__dtypes__.json"), "w") as f:
            json.dump({"w": "bfloat16"}, f)
        got = load_arrays(d)["w"]
        assert got.dtype == jnp.bfloat16


def test_torn_dtype_meta_degrades_gracefully():
    """A writer that died mid-json.dump leaves a torn `__dtypes__.json`.
    Loads must not fail over the sidecar: the per-var path skips the torn
    meta (per-array .dtype sidecars still apply), and the combined-file path
    degrades to no dtype records — vars restore as their f32 payloads."""
    import json

    import jax.numpy as jnp

    from paddle_tpu.io import load_arrays

    # per-var layout: torn legacy meta + healthy sidecar
    with tempfile.TemporaryDirectory() as d:
        from paddle_tpu.io import save_arrays

        save_arrays(d, {"w": jnp.asarray([2.5], jnp.bfloat16)})
        with open(os.path.join(d, "__dtypes__.json"), "w") as f:
            f.write('{"w": "bfl')  # truncated mid-dump
        got = load_arrays(d)["w"]
        assert got.dtype == jnp.bfloat16  # sidecar still wins

    # combined layout: torn meta beside the .npz
    main, startup = framework.Program(), framework.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x, y = _build_linear()
    exe = fluid.Executor()
    with tempfile.TemporaryDirectory() as d:
        with scope_guard(Scope(seed=1)):
            exe.run(startup)
            xv = np.ones((3, 4), "float32")
            (before,) = exe.run(main, feed={"x": xv}, fetch_list=[y.name])
            fluid.io.save_persistables(exe, d, main, filename="all.npz")
            # the save's own meta must have committed atomically (no temps)
            assert os.path.exists(os.path.join(d, "__dtypes__.json"))
            assert not [n for n in os.listdir(d) if ".tmp." in n]
        with open(os.path.join(d, "__dtypes__.json"), "w") as f:
            f.write('{"fc_0.w_0": "bfloat1')  # torn
        with scope_guard(Scope(seed=99)):
            fluid.io.load_persistables(exe, d, main, filename="all.npz")
            (after,) = exe.run(main, feed={"x": xv}, fetch_list=[y.name])
        np.testing.assert_allclose(before, after)
