"""Benchmark: ResNet-50 training throughput on one TPU chip.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": "images/sec", "vs_baseline": N}

Baseline: the reference's best published ResNet-50 TRAIN throughput —
84.08 images/sec (bs=256, MKL-DNN, 2-socket Xeon 6148; BASELINE.md /
reference benchmark/IntelOptimizedPaddle.md:38-46). Its GPU tables ship no
ResNet-50 training number, so the CPU MKL-DNN figure is the reference's
headline for this model.
"""

import json
import os
import sys
import time
import traceback

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))

BASELINE_IMAGES_PER_SEC = 84.08

# Published peaks of one chip, keyed by the device_kind JAX reports. Source:
# Google Cloud documentation, "TPU v5e" (cloud.google.com/tpu/docs/v5e):
# 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s. A kind that is not
# here is an error, not a default.
DEVICE_PEAKS = {
    "TPU v5 lite": {"bf16_tflops": 197.0, "int8_tops": 393.0, "hbm_gbs": 819.0},
}


def device_peaks(device_kind):
    if device_kind not in DEVICE_PEAKS:
        raise RuntimeError(
            "no published peaks for device kind %r (bench.DEVICE_PEAKS holds %s)"
            % (device_kind, sorted(DEVICE_PEAKS))
        )
    return DEVICE_PEAKS[device_kind]


def device_record():
    """The device and the installation a record was taken on, as JAX reports
    them."""
    from importlib import metadata

    import jax
    import jaxlib

    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = None
    dev = jax.devices()[0]
    return {
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "device_count": len(jax.devices()),
        "versions": {
            "jax": jax.__version__, "jaxlib": jaxlib.__version__,
            "libtpu": libtpu,
        },
    }


def _attempt(failed, name, fn, *args, **kwargs):
    """Run one bench pass. A failure is printed with its traceback and its
    name lands in `failed` — main() puts that list in the record and exits
    1 — so a pass that broke is never a key that is quietly missing. With
    failed=None the exception propagates."""
    try:
        return fn(*args, **kwargs)
    except Exception:
        if failed is None:
            raise
        traceback.print_exc()
        failed.append(name)
        return None


def build(batch_size):
    import paddle_tpu.fluid as fluid
    from paddle_tpu import framework
    from paddle_tpu.models import resnet

    main = framework.Program()
    startup = framework.Program()
    with fluid.unique_name.guard():
        with fluid.program_guard(main, startup):
            img = fluid.layers.data(name="img", shape=[3, 224, 224], dtype="float32")
            label = fluid.layers.data(name="label", shape=[1], dtype="int64")
            loss, acc, _ = resnet.resnet50(img, label)
            fluid.optimizer.Momentum(learning_rate=0.1, momentum=0.9).minimize(loss)
    return main, startup, loss


def run(batch_size=256, steps=32, warmup=3, n_staged=4, bf16=True,
        measure_pipeline=True, failed=None):
    """Synthetic-data throughput, like the reference harness's fake-data mode
    (benchmark/fluid/fluid_benchmark.py): batches are staged on device once and
    cycled, so the headline measures the training step and not the input
    path.

    With measure_pipeline, a second pass feeds through PyReader — host batches
    staged to device by the feeder thread overlapping compute (the real train-
    loop input path, reference operators/reader/buffered_reader.h:48) — and
    the pyreader/staged throughput ratio is reported as pipeline evidence.

    Each timed window ends in one fetch to the host, the reference harness's
    steady-state methodology (fluid_benchmark.py:256-291). The multi-step and
    PyReader passes run through _attempt(failed, ...): a failure there is
    named in `failed` and its number is None."""
    import jax
    import paddle_tpu.fluid as fluid
    from paddle_tpu.executor import Scope, scope_guard
    from paddle_tpu.py_reader import PyReader

    main, startup, loss = build(batch_size)
    exe = fluid.Executor(fluid.TPUPlace())
    rng = np.random.RandomState(0)
    batches = [
        {
            "img": jax.device_put(
                rng.randn(batch_size, 3, 224, 224).astype("float32")
            ),
            "label": jax.device_put(
                rng.randint(0, 1000, (batch_size, 1)).astype("int32")
            ),
        }
        for _ in range(n_staged)
    ]

    with scope_guard(Scope(seed=0)):
        exe.run(startup)
        if bf16:
            # bfloat16 is the TPU-native training precision (MXU natively
            # multiplies bf16; measured +70%% over f32 on this model). The
            # reference's analog is its float16_transpiler benchmark mode
            # (paddle/contrib/float16).
            from paddle_tpu.transpiler.bf16_transpiler import Bf16Transpiler

            Bf16Transpiler().transpile(main)
        for i in range(warmup):
            (l,) = exe.run(
                main, feed=batches[i % n_staged], fetch_list=[loss.name],
                return_numpy=False,
            )
        np.asarray(l)  # sync
        t0 = time.perf_counter()
        for i in range(steps):
            (l,) = exe.run(
                main, feed=batches[i % n_staged], fetch_list=[loss.name],
                return_numpy=False,
            )
        np.asarray(l)  # sync
        dt = time.perf_counter() - t0
        single_ips = batch_size * steps / dt

        # multi-step dispatch: k = 2*n_staged iterations per XLA call
        # (Executor steps_per_run -> lax.scan with donated state), so the
        # per-call host dispatch cost (~480 state buffers) and the fetch
        # are paid once per k steps; the stacked feed is ~1.2 GB (k x 154 MB
        # for bs=256)
        import jax.numpy as jnp

        def multistep():
            nonlocal batches
            stacked = {
                n: jnp.stack([b[n] for b in batches] * 2) for n in batches[0]
            }
            batches = None  # free per-step staged copies before the stacked pass
            k = 2 * n_staged
            calls = max(4, steps // k)
            (l,) = exe.run(
                main, feed=stacked, fetch_list=[loss.name],
                return_numpy=False, steps_per_run=k,
            )  # compile + warm
            np.asarray(l)
            t0 = time.perf_counter()
            for _ in range(calls):
                (l,) = exe.run(
                    main, feed=stacked, fetch_list=[loss.name],
                    return_numpy=False, steps_per_run=k,
                )
            np.asarray(l)  # sync
            return batch_size * k * calls / (time.perf_counter() - t0)

        staged_ips = _attempt(failed, "resnet50_multistep", multistep)
        batches = None  # free device buffers before the pipeline passes stage
        if not measure_pipeline:
            return staged_ips, single_ips, None, None
        pyreader_ips = _attempt(
            failed, "resnet50_pyreader", _run_pyreader_pass,
            exe, main, loss, batch_size, steps, warmup, n_staged, rng,
        )
        # compact wire format (VERDICT-4b): uint8 pixels over the link
        # (38.5 MB/step at bs=256 instead of 154 MB), cast to the declared
        # f32/bf16 var dtype ON device, fused into the step
        pyreader_u8_ips = _attempt(
            failed, "resnet50_pyreader_uint8", _run_pyreader_pass,
            exe, main, loss, batch_size, steps, warmup, n_staged, rng,
            wire="uint8",
        )
    return staged_ips, single_ips, pyreader_ips, pyreader_u8_ips


def _run_pyreader_pass(exe, main, loss, batch_size, steps, warmup, n_staged,
                       rng, wire="float32"):
    """PyReader-fed pass: fresh host batches each step, async staging.
    wire="uint8" feeds raw pixel bytes (4x fewer bytes over the
    host->device link); the executor casts to the declared var dtype on
    device at trace time, fused into the compiled step."""
    from paddle_tpu.py_reader import PyReader

    if wire == "uint8":
        host_batches = [
            {
                "img": rng.randint(
                    0, 256, (batch_size, 3, 224, 224)
                ).astype("uint8"),
                "label": rng.randint(0, 1000, (batch_size, 1)).astype("int32"),
            }
            for _ in range(n_staged)
        ]
    else:
        host_batches = [
            {
                "img": rng.randn(batch_size, 3, 224, 224).astype("float32"),
                "label": rng.randint(0, 1000, (batch_size, 1)).astype("int32"),
            }
            for _ in range(n_staged)
        ]

    def gen():
        for i in range(steps + warmup):
            yield host_batches[i % n_staged]

    reader = PyReader(["img", "label"], capacity=2)
    reader.decorate_tensor_provider(gen)
    reader.start()
    try:
        it = reader()
        for _ in range(warmup):
            (l,) = exe.run(
                main, feed=next(it), fetch_list=[loss.name], return_numpy=False
            )
        np.asarray(l)
        t0 = time.perf_counter()
        for _ in range(steps):
            (l,) = exe.run(
                main, feed=next(it), fetch_list=[loss.name], return_numpy=False
            )
        np.asarray(l)
        dt = time.perf_counter() - t0
    finally:
        reader.reset()
    return batch_size * steps / dt


# reference's published RNN train number nearest our stacked-LSTM config:
# 2-layer LSTM text-clf, bs=64, hidden=512, t=100, dict=30k → 184 ms/batch
# on K40m (reference benchmark/README.md:113-121)
BASELINE_LSTM_MS_PER_BATCH = 184.0

# reference's best published VGG-19 TRAIN throughput: 30.44 img/s (bs=256,
# MKL-DNN; benchmark/IntelOptimizedPaddle.md:29-37)
BASELINE_VGG19_IMAGES_PER_SEC = 30.44


def run_vgg19(bs=64, steps=30, warmup=3):
    """Tertiary metric: VGG-19 bf16 train (the second model the reference
    publishes a train baseline for)."""
    import jax
    import paddle_tpu.fluid as fluid
    from paddle_tpu import framework
    from paddle_tpu.executor import Scope, scope_guard
    from paddle_tpu.models import vgg

    main, startup = framework.Program(), framework.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        img = fluid.layers.data(name="img", shape=[3, 224, 224], dtype="float32")
        label = fluid.layers.data(name="label", shape=[1], dtype="int64")
        loss, _, _ = vgg.vgg19(img, label)
        fluid.optimizer.Momentum(learning_rate=0.01, momentum=0.9).minimize(loss)
    exe = fluid.Executor(fluid.TPUPlace())
    rng = np.random.RandomState(0)
    feed = {
        "img": jax.device_put(rng.randn(bs, 3, 224, 224).astype("float32")),
        "label": jax.device_put(rng.randint(0, 1000, (bs, 1)).astype("int64")),
    }
    with scope_guard(Scope(seed=0)):
        exe.run(startup)
        from paddle_tpu.transpiler.bf16_transpiler import Bf16Transpiler

        Bf16Transpiler().transpile(main)
        for _ in range(warmup):
            (l,) = exe.run(main, feed=feed, fetch_list=[loss.name], return_numpy=False)
        np.asarray(l)
        t0 = time.perf_counter()
        for _ in range(steps):
            (l,) = exe.run(main, feed=feed, fetch_list=[loss.name], return_numpy=False)
        np.asarray(l)
        return bs * steps / (time.perf_counter() - t0)


def run_lstm(hid=512, bs=64, t=100, dict_dim=30000, steps=20, warmup=3,
             measure_pipeline=False, failed=None):
    """Tertiary metric: BASELINE config 5 (stacked dynamic-LSTM text model,
    models/stacked_lstm.py) at the reference's published RNN benchmark shape.
    Full-length sequences (the reference pads to t=100 for its comparison
    too, benchmark/README.md:104). Returns (staged ms/batch, PyReader keep-up
    fraction); the PyReader pass runs through _attempt(failed, ...)."""
    import jax
    import paddle_tpu.fluid as fluid
    from paddle_tpu import framework
    from paddle_tpu.executor import Scope, scope_guard
    from paddle_tpu.models.stacked_lstm import stacked_lstm_net

    main, startup = framework.Program(), framework.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        words = fluid.layers.data(name="words", shape=[1], dtype="int64", lod_level=1)
        label = fluid.layers.data(name="label", shape=[1], dtype="int64")
        loss, _, _ = stacked_lstm_net(
            words, label, dict_dim=dict_dim, emb_dim=512, hid_dim=hid,
            stacked_num=2,
        )
        fluid.optimizer.Adam(learning_rate=2e-3).minimize(loss)
    rng = np.random.RandomState(0)
    feed = {
        "words": jax.device_put(rng.randint(0, dict_dim, (bs, t, 1)).astype("int64")),
        "words@LEN": jax.device_put(np.full((bs,), t, "int32")),
        "label": jax.device_put(rng.randint(0, 2, (bs, 1)).astype("int64")),
    }
    exe = fluid.Executor(fluid.TPUPlace())
    with scope_guard(Scope(seed=0)):
        exe.run(startup)
        from paddle_tpu.transpiler.bf16_transpiler import Bf16Transpiler

        Bf16Transpiler().transpile(main)
        # multi-step dispatch: one scan call runs all `steps` batches, so
        # the per-call dispatch is paid once (token feeds are ~50 KB,
        # stacking is free)
        import jax.numpy as jnp

        stacked = {n: jnp.stack([v] * steps) for n, v in feed.items()}
        for _ in range(warmup // 2 + 1):
            (l,) = exe.run(
                main, feed=stacked, fetch_list=[loss.name],
                return_numpy=False, steps_per_run=steps,
            )
        np.asarray(l)

        # >=5 steady-state supercalls, same methodology as the pyreader pass
        # below: round 4 timed a SINGLE supercall here and a one-off stall in
        # it produced a 93 ms/batch artifact against a ~6 ms steady state
        # (the same run's own pyreader pass proved the skew)
        def _time_staged(timed_calls=5):
            t0 = time.perf_counter()
            for _ in range(timed_calls):
                (l,) = exe.run(
                    main, feed=stacked, fetch_list=[loss.name],
                    return_numpy=False, steps_per_run=steps,
                )
            np.asarray(l)
            return (time.perf_counter() - t0) / (timed_calls * steps) * 1e3

        staged_ms = _time_staged()
        if not measure_pipeline:
            return staged_ms, None

        # Input-pipeline keep-up on a byte-light feed (the VERDICT-4a
        # evidence): ~51.5 KB/step (64x100 int64 words + lens + labels) in
        # three arrays. The reader yields SUPER-batches at the steps_per_run
        # granularity (3 transfers per k steps — the reference's
        # double_buffer over paddle.batch batches is the same
        # batching-of-transfers pattern), and next_batch() returns the
        # stacked [k, ...] feed the multi-step call consumes directly.
        from paddle_tpu.py_reader import PyReader

        def pyreader_frac():
            nonlocal staged_ms
            host = {
                n: np.stack([np.asarray(v)] * steps) for n, v in feed.items()
            }
            timed_supers = 5

            def gen():
                for _ in range(2 + timed_supers):
                    yield host

            # capacity 2 < timed_supers: the timed window MUST be fed by
            # the producer in steady state (a prestaged-backlog-only pass
            # would be structurally incapable of failing the keep-up bar)
            reader = PyReader(list(feed), capacity=2)
            reader.decorate_tensor_provider(gen)
            reader.start()
            try:
                (l,) = exe.run(
                    main, feed=reader.next_batch(), fetch_list=[loss.name],
                    return_numpy=False, steps_per_run=steps,
                )
                np.asarray(l)
                t0 = time.perf_counter()
                for _ in range(timed_supers):
                    (l,) = exe.run(
                        main, feed=reader.next_batch(),
                        fetch_list=[loss.name],
                        return_numpy=False, steps_per_run=steps,
                    )
                np.asarray(l)
                pyreader_ms = (
                    (time.perf_counter() - t0) / (timed_supers * steps) * 1e3
                )
            finally:
                reader.reset()
            if staged_ms > 1.1 * pyreader_ms:
                # staged (the frac denominator) must sit at or below the
                # producer-fed steady state; a skew here means the staged
                # window caught a stall — remeasure once, then fail loudly
                # rather than emit a nonsense frac (round-4's 14.88)
                print(
                    "lstm staged/pyreader skew %.1f/%.1f ms — remeasuring"
                    % (staged_ms, pyreader_ms), file=sys.stderr,
                )
                staged_ms = min(staged_ms, _time_staged())
            frac = staged_ms / pyreader_ms
            if not 0.0 < frac <= 1.1:
                raise RuntimeError(
                    "lstm keep-up frac %.2f outside (0, 1.1]: staged %.1f ms "
                    "vs pyreader %.1f ms" % (frac, staged_ms, pyreader_ms)
                )
            return frac

        frac = _attempt(failed, "lstm_pyreader", pyreader_frac)
        return staged_ms, frac


def build_transformer(b=8, t=1024, d=2048, n_layer=4, vocab=32000,
                      moment_dtype=None):
    """Build the MFU-bench Transformer train step. Returns
    (main, startup, feed, loss, flops_per_step) with the feed already staged
    on device. Shared by run_transformer_mfu and tools/mfu_audit.py."""
    import jax
    import paddle_tpu.fluid as fluid
    from paddle_tpu import framework
    from paddle_tpu.models import transformer as T

    n_head, d_inner = 16, 4 * d
    main, startup = framework.Program(), framework.Program()
    with fluid.unique_name.guard():
        with fluid.program_guard(main, startup):
            feeds = {}
            for name, shape, dtype in [
                ("src_word", [t], "int64"), ("src_pos", [t], "int64"),
                ("trg_word", [t], "int64"), ("trg_pos", [t], "int64"),
                ("label", [t], "int64"), ("label_weight", [t, 1], "float32"),
            ]:
                feeds[name] = fluid.layers.data(name=name, shape=shape, dtype=dtype)
            loss, _ = T.transformer(
                feeds["src_word"], feeds["src_pos"], feeds["trg_word"],
                feeds["trg_pos"], None, None, None,
                feeds["label"], feeds["label_weight"],
                src_vocab_size=vocab, trg_vocab_size=vocab,
                n_layer=n_layer, n_head=n_head, d_model=d, d_inner=d_inner,
                d_key=d // n_head, d_value=d // n_head,
                dropout=0.0, max_length=t + 1, use_flash=True, padded=False,
            )
            fluid.optimizer.Adam(
                learning_rate=1e-4, moment_dtype=moment_dtype
            ).minimize(loss)

    rng = np.random.RandomState(0)
    pos = np.tile(np.arange(t), (b, 1)).astype("int64")
    feed = {
        "src_word": jax.device_put(rng.randint(0, vocab, (b, t)).astype("int64")),
        "src_pos": jax.device_put(pos),
        "trg_word": jax.device_put(rng.randint(0, vocab, (b, t)).astype("int64")),
        "trg_pos": jax.device_put(pos.copy()),
        "label": jax.device_put(rng.randint(0, vocab, (b, t)).astype("int64")),
        "label_weight": jax.device_put(np.ones((b, t, 1), "float32")),
    }
    enc_mm = n_layer * (4 * d * d + 2 * d * d_inner)
    dec_mm = n_layer * (8 * d * d + 2 * d * d_inner)
    mm = 2 * b * t * (enc_mm + dec_mm) + 2 * b * t * d * vocab
    attn = 4 * b * t * t * d * (3 * n_layer)
    flops = 3 * (mm + attn)
    return main, startup, feed, loss, flops


def run_transformer_mfu(b=8, t=1024, d=2048, n_layer=4, vocab=32000,
                        moment_dtype="bfloat16", pass_pipeline=None):
    """Secondary metric: MFU on a compute-dense Transformer train step (the
    north-star metric is MFU, BASELINE.md — ResNet-50 on one v5e chip is
    HBM-bound by its BN/elementwise tier (PROFILE.md), so a matmul-dominated
    model is the honest vehicle for demonstrating MXU utilization). Model:
    enc-dec Transformer (models/transformer.py) with Pallas flash attention,
    bf16, Adam. FLOPs counted as fwd + 2x bwd over the matmul/attention
    terms only (embedding gathers, softmax, norms uncounted)."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.executor import Scope, scope_guard

    main, startup, feed, loss, flops = build_transformer(
        b, t, d, n_layer, vocab, moment_dtype=moment_dtype
    )
    import jax.numpy as jnp

    # pass_pipeline (e.g. "training_fused" for the Pallas kernel-substitution
    # tier) applies only to this bench step and is restored on exit
    from contextlib import ExitStack

    from paddle_tpu import flags as _flags

    stack = ExitStack()
    if pass_pipeline is not None:
        prev = _flags.get_flags("pass_pipeline")["pass_pipeline"]
        _flags.set_flags({"pass_pipeline": pass_pipeline})
        stack.callback(lambda: _flags.set_flags({"pass_pipeline": prev}))

    exe = fluid.Executor(fluid.TPUPlace())
    with stack, scope_guard(Scope(seed=0)):
        exe.run(startup)
        from paddle_tpu.transpiler.bf16_transpiler import Bf16Transpiler

        Bf16Transpiler().transpile(main)
        # multi-step dispatch (steps_per_run=32) amortizes per-call dispatch
        # and the end-of-window fetch the same way the ResNet/LSTM passes
        # do; each timed window covers 64 steps. The HEADLINE estimator is
        # min-over-windows, on the argument that contention and stalls only
        # ever ADD time to a window (a one-off host stall once produced a
        # 25%% artifact against the same run's own steady state). To make
        # that choice AUDITABLE rather than asserted, >=5 windows are timed
        # and every per-window time plus the median ride along in the JSON
        # record: a min far below the median flags a run whose headline
        # deserves suspicion (r05 advisor). A failure here fails the pass:
        # there is no per-step fallback.
        k = 32
        calls = 2
        windows = 5
        stacked = {n: jnp.stack([v] * k) for n, v in feed.items()}
        window_dts = []
        (l,) = exe.run(
            main, feed=stacked, fetch_list=[loss.name],
            return_numpy=False, steps_per_run=k,
        )
        np.asarray(l)
        for _ in range(windows):
            t0 = time.perf_counter()
            for _ in range(calls):
                (l,) = exe.run(
                    main, feed=stacked, fetch_list=[loss.name],
                    return_numpy=False, steps_per_run=k,
                )
            np.asarray(l)
            window_dts.append((time.perf_counter() - t0) / (calls * k))
    best_dt = min(window_dts)
    median_dt = sorted(window_dts)[len(window_dts) // 2]
    return {
        "tflops_min_window": flops / best_dt / 1e12,
        "tflops_median_window": flops / median_dt / 1e12,
        "window_ms_per_step": [round(dt * 1e3, 2) for dt in window_dts],
    }


def run_zero1_bench(d=512, depth=4, bs_per_dev=16, steps=12, warmup=3):
    """ZeRO-1 vs replicated data parallelism over the local device mesh:
    same MLP+Adam train step under ReduceStrategy.AllReduce (replicated
    optimizer state, gradient all-reduce) and ReduceStrategy.Reduce (ZeRO-1:
    reduce-scatter grad, sharded moments, param all-gather). Reports step
    time for both and the measured PER-CHIP optimizer-state bytes — the
    sharded path's state bytes drop ~dp× (the ZeRO-1 memory claim, measured
    not asserted). Returns None on a single-device harness (the bench chip):
    there is no dp axis to shard over. Wire-volume evidence for the same
    pair of paths comes from tools/comm_audit.py."""
    import jax

    if jax.device_count() < 2:
        return None
    import paddle_tpu.fluid as fluid
    from paddle_tpu import framework
    from paddle_tpu.executor import Scope, scope_guard
    from paddle_tpu.parallel_executor import BuildStrategy, ReduceStrategy

    n_dev = jax.device_count()
    bs = bs_per_dev * n_dev
    rng = np.random.RandomState(0)
    x = rng.randn(bs, d).astype("float32")
    y = rng.randint(0, 10, (bs, 1)).astype("int64")

    def one(strategy):
        main_p, startup = framework.Program(), framework.Program()
        with fluid.unique_name.guard(), fluid.program_guard(main_p, startup):
            xv = fluid.layers.data(name="x", shape=[d], dtype="float32")
            yv = fluid.layers.data(name="y", shape=[1], dtype="int64")
            h = xv
            for _ in range(depth):
                h = fluid.layers.fc(h, size=d, act="relu")
            logits = fluid.layers.fc(h, size=10)
            loss = fluid.layers.mean(
                fluid.layers.softmax_with_cross_entropy(logits, yv)
            )
            fluid.optimizer.Adam(learning_rate=1e-3).minimize(loss)
        strat = BuildStrategy()
        strat.reduce_strategy = strategy
        scope = Scope(seed=0)
        with scope_guard(scope):
            fluid.Executor().run(startup)
            pe = fluid.ParallelExecutor(
                loss_name=loss.name, main_program=main_p, build_strategy=strat,
                scope=scope,
            )
            for _ in range(warmup):
                (l,) = pe.run(fetch_list=[loss.name], feed={"x": x, "y": y},
                              return_numpy=False)
            np.asarray(l)
            t0 = time.perf_counter()
            for _ in range(steps):
                (l,) = pe.run(fetch_list=[loss.name], feed={"x": x, "y": y},
                              return_numpy=False)
            np.asarray(l)
            ms = (time.perf_counter() - t0) / steps * 1e3
            # optimizer accumulators carry the unique_name "_acc" suffix
            # (optimizer._add_accumulator); per-chip bytes = device 0's shard
            state_bytes = 0
            for name, val in scope.vars.items():
                if "_acc" in name and hasattr(val, "addressable_shards"):
                    state_bytes += val.addressable_shards[0].data.nbytes
            final_loss = float(np.asarray(l).reshape(-1)[0])
        return ms, state_bytes, final_loss

    ar_ms, ar_bytes, ar_loss = one(ReduceStrategy.AllReduce)
    z1_ms, z1_bytes, z1_loss = one(ReduceStrategy.Reduce)
    assert np.isfinite(z1_loss) and abs(z1_loss - ar_loss) < 5e-2, (
        "zero1 trajectory diverged from replicated: %.4f vs %.4f"
        % (z1_loss, ar_loss)
    )
    return {
        "zero1_devices": n_dev,
        "zero1_step_ms": round(z1_ms, 2),
        "allreduce_step_ms": round(ar_ms, 2),
        "zero1_opt_state_bytes_per_chip": z1_bytes,
        "allreduce_opt_state_bytes_per_chip": ar_bytes,
        "zero1_state_reduction_x": round(ar_bytes / z1_bytes, 2)
        if z1_bytes
        else None,
    }


def run_sharding_bench(d=256, ffn=1024, depth=4, classes=16, bs_per_dev=8,
                       steps=10, warmup=3, smoke=False):
    """Declarative sharding rules (PR 13) evidence pass: the same FFN-block
    transformer stack + Adam trained (a) dp-replicated over all devices and
    (b) under BuildStrategy.sharding_rules on a dp2 x fsdp2 x tp2 mesh —
    Megatron column/row pairs on each block (SpecLayout) with fsdp sharding
    the remaining dims. Measures step time, loss parity, and the PER-CHIP
    param + optimizer-state bytes, asserting the sharded path's resident
    bytes come in at or under 1/fsdp of replicated (the FSDP memory claim;
    with tp2 also splitting the weights the measured factor is ~tp x fsdp).

    Step-time is checked against the analytic projection from the
    comm-audit wire model: at equal global batch the two meshes do the SAME
    per-chip matmul flops ((batch/4) x (params/2) vs (batch/8) x params),
    and on the in-process virtual-device harness wire is memcpy, so the
    projection is the replicated step time itself; the measured ratio is
    recorded and asserted within tolerance.

    Also writes the paper-size analytic HBM projection: the config scaled
    to d=4096/ffn=16384/L=24/vocab=32k whose replicated param+state bytes
    exceed one v5e chip's 16 GB HBM while the tp2 x fsdp2 sharded footprint
    fits — the 'train a model bigger than one chip' claim, with every
    input recorded. Returns None below 8 devices."""
    import jax

    if jax.device_count() < 8:
        return None
    import paddle_tpu.fluid as fluid
    from paddle_tpu import framework
    from paddle_tpu.executor import Scope, scope_guard
    from paddle_tpu.parallel import MeshConfig, SpecLayout
    from paddle_tpu.parallel_executor import BuildStrategy

    if smoke:
        d, ffn, depth, steps = 128, 256, 2, 6
    n_dev = jax.device_count()
    bs = bs_per_dev * n_dev
    rng = np.random.RandomState(0)
    x = rng.randn(bs, d).astype("float32")
    y = rng.randint(0, classes, (bs, 1)).astype("int64")

    def build():
        main_p, startup = framework.Program(), framework.Program()
        with fluid.unique_name.guard(), fluid.program_guard(main_p, startup):
            xv = fluid.layers.data(name="x", shape=[d], dtype="float32")
            yv = fluid.layers.data(name="y", shape=[1], dtype="int64")
            h = xv
            for k in range(depth):
                up = fluid.layers.fc(
                    h, size=ffn, act="relu",
                    param_attr=fluid.ParamAttr(name="blk%d_up.w" % k),
                    bias_attr=fluid.ParamAttr(name="blk%d_up.b" % k),
                )
                down = fluid.layers.fc(
                    up, size=d,
                    param_attr=fluid.ParamAttr(name="blk%d_down.w" % k),
                    bias_attr=fluid.ParamAttr(name="blk%d_down.b" % k),
                )
                h = fluid.layers.elementwise_add(h, down)
            logits = fluid.layers.fc(h, size=classes)
            loss = fluid.layers.mean(
                fluid.layers.softmax_with_cross_entropy(logits, yv)
            )
            fluid.optimizer.Adam(learning_rate=1e-3).minimize(loss)
        return main_p, startup, loss

    rules = SpecLayout().transformer_rules(
        column=[r"^blk\d+_up\.w$"],
        row=[r"^blk\d+_down\.w$"],
        vector=[r"^blk\d+_(up|down)\.b$"],
    )

    def one(mesh_cfg, use_rules):
        main_p, startup, loss = build()
        strat = BuildStrategy()
        if use_rules:
            strat.sharding_rules = rules
        scope = Scope(seed=0)
        with scope_guard(scope):
            fluid.Executor().run(startup)
            pe = fluid.ParallelExecutor(
                loss_name=loss.name, main_program=main_p, build_strategy=strat,
                scope=scope, mesh_config=mesh_cfg,
            )
            for _ in range(warmup):
                (l,) = pe.run(fetch_list=[loss.name], feed={"x": x, "y": y},
                              return_numpy=False)
            np.asarray(l)
            t0 = time.perf_counter()
            for _ in range(steps):
                (l,) = pe.run(fetch_list=[loss.name], feed={"x": x, "y": y},
                              return_numpy=False)
            np.asarray(l)
            ms = (time.perf_counter() - t0) / steps * 1e3
            # resident bytes = device 0's shard of every param + accumulator
            param_names = {
                p.name for p in main_p.global_block().all_parameters()
            }
            resident = 0
            for name, val in scope.vars.items():
                if (name in param_names or "_acc" in name) and hasattr(
                    val, "addressable_shards"
                ):
                    resident += val.addressable_shards[0].data.nbytes
            final_loss = float(np.asarray(l).reshape(-1)[0])
        return ms, resident, final_loss

    rep_ms, rep_bytes, rep_loss = one(None, False)  # default: dp over all 8
    shd_ms, shd_bytes, shd_loss = one(
        MeshConfig(dp=2, fsdp=2, tp=2), True
    )
    assert np.isfinite(shd_loss) and abs(shd_loss - rep_loss) < 5e-2, (
        "sharded trajectory diverged from replicated: %.4f vs %.4f"
        % (shd_loss, rep_loss)
    )
    fsdp_size = 2
    assert shd_bytes <= rep_bytes / fsdp_size * 1.1, (
        "sharded per-chip bytes %d exceed replicated/fsdp %d x 1.1"
        % (shd_bytes, rep_bytes // fsdp_size)
    )
    # equal per-chip flops => the projection is the replicated step time;
    # one-sided (faster than projection is fine, CPU timing is noisy)
    assert shd_ms <= rep_ms * 1.15, (
        "sharded step %.2f ms is >15%% over the analytic projection %.2f ms"
        % (shd_ms, rep_ms)
    )

    # paper-size analytic HBM projection (all inputs recorded in the JSON)
    P = dict(d=4096, ffn=16384, depth=24, vocab=32000)
    n_params = (
        P["depth"] * (P["d"] * P["ffn"] * 2 + P["ffn"] + P["d"])
        + P["vocab"] * P["d"]
    )
    # f32 resident training bytes/param: param 4 + two Adam moments 8
    resident_per_param = 12
    hbm_gb = 16.0  # v5e HBM per chip
    replicated_gb = n_params * resident_per_param / 1e9
    sharded_gb = replicated_gb / 4  # tp2 x fsdp2 shards params + state 4x
    assert replicated_gb > hbm_gb > sharded_gb, (
        "paper-size projection no longer straddles one chip's HBM: "
        "replicated %.1f GB, sharded %.1f GB, HBM %.1f GB"
        % (replicated_gb, sharded_gb, hbm_gb)
    )

    return {
        "devices": n_dev,
        "mesh": "dp2 x fsdp2 x tp2 (vs dp%d replicated)" % n_dev,
        "model": "FFN stack d=%d ffn=%d depth=%d, Adam" % (d, ffn, depth),
        "replicated_step_ms": round(rep_ms, 2),
        "sharded_step_ms": round(shd_ms, 2),
        "step_ms_ratio_vs_projection": round(shd_ms / rep_ms, 3),
        "replicated_param_state_bytes_per_chip": rep_bytes,
        "sharded_param_state_bytes_per_chip": shd_bytes,
        "state_reduction_x": round(rep_bytes / shd_bytes, 2) if shd_bytes
        else None,
        "loss_replicated": round(rep_loss, 6),
        "loss_sharded": round(shd_loss, 6),
        "paper_size_projection": {
            "config": P,
            "n_params": n_params,
            "resident_bytes_per_param_f32_adam": resident_per_param,
            "hbm_gb_per_chip_v5e": hbm_gb,
            "replicated_param_state_gb_per_chip": round(replicated_gb, 1),
            "tp2_fsdp2_param_state_gb_per_chip": round(sharded_gb, 1),
            "fits": "sharded only",
        },
    }


def run_pp_bench(dp=2, pp=4, m1=4, m2=16, mb=8, steps=8, warmup=2):
    """Program-level pipeline parallelism (ParallelExecutor + MeshConfig(pp))
    on a dp2×pp4 mesh: an encoder-only Transformer stack pinned one layer
    per stage (framework.device_guard), trained through the GPipe schedule
    at two microbatch counts m1 < m2 with the PER-MICROBATCH size fixed.

    The bubble is MEASURED, not asserted: with t(m) = c + (m+p-1)·τ the
    slope τ = (t(m2)-t(m1))/(m2-m1) is the steady-state per-tick time, so
    bubble(m1) = 1 - m1·τ/t(m1), compared against the analytic GPipe bound
    (p-1)/(m1+p-1) (docs/parallelism.md). A measured/analytic ratio far
    from 1 means the schedule is losing time to something other than
    pipeline fill/drain. Returns None below dp×pp devices."""
    import jax

    if jax.device_count() < dp * pp:
        return None
    import paddle_tpu.fluid as fluid
    from paddle_tpu import framework
    from paddle_tpu.executor import Scope, scope_guard
    from paddle_tpu.models.transformer import encoder_layer
    from paddle_tpu.parallel import MeshConfig
    from paddle_tpu.parallel_executor import BuildStrategy, ExecutionStrategy

    vocab, t_len, d, n_head, d_inner = 512, 16, 64, 4, 256
    n_layer = pp  # one encoder layer per stage

    def build():
        cfg = {"d_key": d // n_head, "d_value": d // n_head, "d_model": d,
               "n_head": n_head, "d_inner": d_inner, "dropout": 0.0}
        word = fluid.layers.data(name="word", shape=[-1, t_len, 1],
                                 dtype="int64", append_batch_size=False)
        pos = fluid.layers.data(name="pos", shape=[-1, t_len, 1],
                                dtype="int64", append_batch_size=False)
        label = fluid.layers.data(name="label", shape=[-1, 1],
                                  dtype="int64", append_batch_size=False)
        with framework.device_guard("pp:0"):
            h = fluid.layers.elementwise_add(
                fluid.layers.embedding(word, size=[vocab, d]),
                fluid.layers.embedding(pos, size=[t_len, d]),
            )
            h = encoder_layer(h, None, cfg)
        for k in range(1, n_layer):
            with framework.device_guard("pp:%d" % k):
                h = encoder_layer(h, None, cfg)
        with framework.device_guard("pp:%d" % (n_layer - 1)):
            pooled = fluid.layers.reduce_mean(h, dim=1)
            logits = fluid.layers.fc(pooled, size=16)
            loss = fluid.layers.mean(
                fluid.layers.softmax_with_cross_entropy(
                    label=label, logits=logits
                )
            )
        return loss

    def one(m, schedule):
        b = dp * m * mb
        rng = np.random.RandomState(0)
        feed = {
            "word": rng.randint(0, vocab, (b, t_len, 1)).astype("int64"),
            "pos": np.tile(
                np.arange(t_len)[None, :, None], (b, 1, 1)
            ).astype("int64"),
            "label": rng.randint(0, 16, (b, 1)).astype("int64"),
        }
        main_p, startup = framework.Program(), framework.Program()
        with fluid.unique_name.guard(), fluid.program_guard(main_p, startup):
            loss = build()
            fluid.optimizer.SGD(learning_rate=0.01).minimize(loss)
        es = ExecutionStrategy()
        es.pipeline_schedule = schedule
        es.num_microbatches = m
        exe = fluid.Executor()
        with scope_guard(Scope(seed=0)):
            exe.run(startup)
            pe = fluid.ParallelExecutor(
                loss_name=loss.name, main_program=main_p,
                mesh_config=MeshConfig(dp=dp, pp=pp),
                exec_strategy=es, build_strategy=BuildStrategy(),
            )
            for _ in range(warmup):
                (l,) = pe.run(fetch_list=[loss.name], feed=feed)
            np.asarray(l)
            # min-over-windows: harness noise only ever ADDS time
            best = float("inf")
            for _ in range(3):
                t0 = time.perf_counter()
                for _ in range(steps):
                    (l,) = pe.run(fetch_list=[loss.name], feed=feed)
                np.asarray(l)
                best = min(best, (time.perf_counter() - t0) / steps)
        return best

    t1 = one(m1, "gpipe")
    t2 = one(m2, "gpipe")
    tau = (t2 - t1) / (m2 - m1)
    measured = 1 - m1 * tau / t1
    analytic = (pp - 1) / (m1 + pp - 1)
    t1_1f1b = one(m1, "1f1b")
    return {
        "pp_mesh": "dp%d x pp%d" % (dp, pp),
        "pp_schedule": "gpipe",
        "pp_microbatch_rows_per_shard": mb,
        "pp_step_ms_m%d" % m1: round(t1 * 1e3, 2),
        "pp_step_ms_m%d" % m2: round(t2 * 1e3, 2),
        "pp_step_ms_m%d_1f1b" % m1: round(t1_1f1b * 1e3, 2),
        "pp_tick_ms": round(tau * 1e3, 3),
        "pp_bubble_measured_m%d" % m1: round(measured, 3),
        "pp_bubble_analytic_m%d" % m1: round(analytic, 3),
        "pp_bubble_measured_over_analytic": round(measured / analytic, 2),
    }


def _save_lenet_inference(model_dir, seed=11):
    """LeNet-class MNIST model -> save_inference_model(model_dir); the
    SERVING bench workload (the serving analog of the book's
    recognize_digits chapter)."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu import framework
    from paddle_tpu.executor import Scope, scope_guard

    main, startup = framework.Program(), framework.Program()
    with fluid.unique_name.guard():
        with fluid.program_guard(main, startup):
            img = fluid.layers.data(name="img", shape=[1, 28, 28], dtype="float32")
            conv1 = fluid.layers.conv2d(img, num_filters=6, filter_size=5, padding=2, act="relu")
            pool1 = fluid.layers.pool2d(conv1, pool_size=2, pool_stride=2)
            conv2 = fluid.layers.conv2d(pool1, num_filters=16, filter_size=5, act="relu")
            pool2 = fluid.layers.pool2d(conv2, pool_size=2, pool_stride=2)
            fc1 = fluid.layers.fc(pool2, size=120, act="relu")
            probs = fluid.layers.fc(fc1, size=10, act="softmax")
    exe = fluid.Executor(fluid.CPUPlace())
    with scope_guard(Scope(seed=seed)):
        exe.run(startup)
        fluid.io.save_inference_model(
            model_dir, ["img"], [probs], exe, main_program=main
        )


_COLD_START_CHILD = r"""
import sys, time
model_dir, cache_dir, buckets = sys.argv[1], sys.argv[2], sys.argv[3]
from paddle_tpu.serving import ServingEngine
# timed region: engine build (model load + lowering) + every bucket variant
# acquired and executable — the serving layer's boot-to-warm. Imports are
# identical on both boots and excluded so the ratio measures the cache.
t0 = time.perf_counter()
eng = ServingEngine(model_dir, name="lenet", cache_dir=cache_dir,
                    batch_buckets=tuple(int(b) for b in buckets.split(",")))
eng.warmup()
print("COLD %.4f TRACES %d HITS %d"
      % (time.perf_counter() - t0, eng.traces, eng.cache_hits))
"""


def _cold_start(model_dir, cache_dir, buckets):
    """Boot-to-warm seconds in a FRESH process (in-process jit caches would
    flatter the second boot; a real replica restart pays imports + engine
    build + per-bucket variant acquisition, which is what this times). The
    child's XLA cache is placed from outside, beside the artifact cache, so
    the first boot is cold in both layers and the second warm in both."""
    import subprocess

    out = subprocess.run(
        [sys.executable, "-c", _COLD_START_CHILD, model_dir, cache_dir,
         ",".join(str(b) for b in buckets)],
        capture_output=True, text=True, timeout=600, cwd=_HERE,
        env=dict(os.environ,
                 JAX_COMPILATION_CACHE_DIR=os.path.join(cache_dir, "xla")),
    )
    for line in out.stdout.splitlines():
        if line.startswith("COLD "):
            parts = line.split()
            return float(parts[1]), int(parts[3]), int(parts[5])
    raise RuntimeError(
        "cold-start child failed:\n%s\n%s" % (out.stdout, out.stderr)
    )


def run_serving_bench(duration_s=8.0, clients=4, max_rows=4,
                      offered_interval_ms=4.0):
    """The serving runtime's evidence pass (ISSUE 6 acceptance): sustained
    concurrent load on a LeNet/MNIST-class model through ServingEngine +
    ContinuousBatcher, plus cold-start-from-trace vs cold-start-from-cache
    in fresh subprocesses. Returns the SERVING.json record."""
    import shutil
    import tempfile
    import threading

    from paddle_tpu.observability import registry as _registry
    from paddle_tpu.serving import (
        ContinuousBatcher, QueueFullError, RequestTimeout, ServingEngine,
    )

    import subprocess

    tmp = tempfile.mkdtemp(prefix="serving-bench-")
    model_dir = os.path.join(tmp, "lenet")
    cache_dir = os.path.join(tmp, "cache")
    buckets = (1, 2, 4, 8, 16, 32, 64, 128, 256)
    try:
        # One process per device. The cold-start children need the device
        # this parent will serve from, so the parent stays off JAX until the
        # last of them has exited: the LeNet is saved by a child too, and
        # the children run one after another.
        subprocess.run(
            [sys.executable, "-c",
             "import sys; from bench import _save_lenet_inference; "
             "_save_lenet_inference(sys.argv[1])", model_dir],
            check=True, timeout=600, cwd=_HERE,
        )

        # ---- cold start: trace vs cache, each in a fresh process ----------
        cold_trace, traces1, hits1 = _cold_start(model_dir, cache_dir, buckets)
        assert traces1 == len(buckets) and hits1 == 0, (traces1, hits1)
        cold_cache, traces2, hits2 = _cold_start(model_dir, cache_dir, buckets)
        assert traces2 == 0, "second boot traced %d variants" % traces2

        # ---- sustained concurrent load ------------------------------------
        eng = ServingEngine(
            model_dir, name="lenet", cache_dir=cache_dir, batch_buckets=buckets
        )
        eng.warmup()
        traces_after_warmup = eng.traces
        batcher = ContinuousBatcher(
            eng, max_queue_rows=256, max_batch_delay_ms=2.0, timeout_ms=5000.0
        )
        counts = {"ok": 0, "rejected": 0, "timeout": 0, "error": 0}
        lock = threading.Lock()
        stop_at = time.perf_counter() + duration_s
        rng0 = np.random.RandomState(0)
        payloads = [
            rng0.randn(r, 1, 28, 28).astype("float32")
            for r in range(1, max_rows + 1)
        ]

        def client(k):
            i = 0
            while time.perf_counter() < stop_at:
                feed = {"img": payloads[(k + i) % len(payloads)]}
                i += 1
                try:
                    batcher.run(feed, timeout=30.0)
                    outcome = "ok"
                except QueueFullError:
                    outcome = "rejected"
                except RequestTimeout:
                    outcome = "timeout"
                except Exception:
                    outcome = "error"
                with lock:
                    counts[outcome] += 1
                time.sleep(offered_interval_ms / 1e3)

        t0 = time.perf_counter()
        threads = [
            threading.Thread(target=client, args=(k,)) for k in range(clients)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        batcher.close(drain=True)

        reg = _registry.default_registry()
        lat = reg.get("serving/lenet/latency_ms")
        queue = reg.get("serving/lenet/queue_ms")
        device = reg.get("serving/lenet/device_ms")
        fill = reg.get("serving/lenet/batch_fill")
        rows = reg.get("serving/lenet/rows")
        padded = reg.get("serving/lenet/padded_rows")
        offered = sum(counts.values())
        served_fraction = counts["ok"] / float(offered) if offered else 0.0
        real_rows = rows.value() if rows else 0
        pad_rows = padded.value() if padded else 0
        record = {
            "metric": "serving_lenet",
            "requests_offered": offered,
            "requests_ok": counts["ok"],
            "requests_rejected": counts["rejected"],
            "requests_timeout": counts["timeout"],
            "requests_error": counts["error"],
            "served_fraction": round(served_fraction, 4),
            "requests_per_sec": round(counts["ok"] / wall, 1),
            "concurrent_clients": clients,
            "offered_interval_ms": offered_interval_ms,
            "p50_latency_ms": round(lat.percentile(50), 3) if lat else None,
            "p99_latency_ms": round(lat.percentile(99), 3) if lat else None,
            "p50_queue_ms": round(queue.percentile(50), 3) if queue else None,
            "p50_device_ms": round(device.percentile(50), 3) if device else None,
            "batch_fill_mean": round(fill._sum / fill.count, 3)
            if fill and fill.count else None,
            "padding_waste_frac": round(
                pad_rows / float(real_rows + pad_rows), 3
            ) if real_rows + pad_rows else None,
            "traces_after_warmup": eng.traces - traces_after_warmup,
            "compile_cache": eng.cache.stats() if eng.cache else None,
            "batch_buckets": list(buckets),
            "cold_start_from_trace_s": round(cold_trace, 3),
            "cold_start_from_cache_s": round(cold_cache, 3),
            "cold_start_speedup_x": round(cold_trace / cold_cache, 2),
        }
        return record
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def run_generation_bench(smoke=False):
    """Autoregressive serving evidence pass (ISSUE 12 acceptance): Poisson
    arrivals of mixed-length greedy generation requests through
    GenerationEngine + GenerationScheduler (prefill/decode split, paged KV
    pool, token-level continuous batching), against a naive whole-sequence
    ablation server that re-runs the dense forward over the entire padded
    sequence for every generated token with one request in flight — the
    PR 6 single-shot serving answer to autoregression. Both paths are
    greedy off the same params, so the ablation is token-identical and the
    ratio isolates the serving strategy. Returns the GENSERVE.json record."""
    import threading

    from paddle_tpu.executor import aot_serve_lowering, scope_guard
    from paddle_tpu.models.gpt_decoder import GPTDecoder
    from paddle_tpu.observability import registry as _registry
    from paddle_tpu.serving import GenerationEngine, GenerationScheduler

    if smoke:
        model_kw = dict(vocab_size=64, n_layer=2, n_head=2, d_model=32,
                        d_inner=64, max_context=32)
        n_requests, max_slots, rate_req_s = 24, 4, 200.0
        naive_requests = 6
    else:
        model_kw = dict(vocab_size=256, n_layer=4, n_head=4, d_model=128,
                        d_inner=256, max_context=64)
        n_requests, max_slots, rate_req_s = 64, 8, 100.0
        naive_requests = 12
    name = "genbench"
    model = GPTDecoder(**model_kw)
    eng = GenerationEngine(model, name=name, max_slots=max_slots,
                           page_size=8, cache_dir=None)
    n_variants = eng.warmup()
    traces0 = eng.traces
    no_eos = model_kw["vocab_size"]  # out of range: every finish is "length"

    # shared-prefix workload: a couple of page-aligned "system prompts"
    # reused by 3/4 of the requests (prefix KV cache hits; the long shared
    # head also pushes those prompts past prefill_chunk, exercising chunked
    # prefill), plus a cold 1/4 sweeping the full prompt-length range
    rng = np.random.RandomState(0)
    ctx = eng.max_context
    ps = eng.page_size
    vocab = model_kw["vocab_size"]
    sys_len = min(4 * ps, (eng.max_prompt_len - 1) // ps * ps)
    sys_prompts = [
        [int(t) for t in rng.randint(0, vocab, size=sys_len)]
        for _ in range(2)
    ]
    reqs = []
    for i in range(n_requests):
        max_new = int(rng.randint(4, max(5, ctx // 2)))
        if i % 4 == 3:
            L = int(rng.randint(1, eng.max_prompt_len + 1))
            prompt = [int(t) for t in rng.randint(0, vocab, size=L)]
        else:
            tail = int(rng.randint(1, eng.max_prompt_len - sys_len + 1))
            prompt = sys_prompts[i % len(sys_prompts)] + [
                int(t) for t in rng.randint(0, vocab, size=tail)
            ]
        reqs.append((prompt, max_new))

    # ---- continuous batching under Poisson arrivals -----------------------
    sched = GenerationScheduler(eng, max_queue_requests=n_requests,
                                timeout_ms=120000.0)
    futures = []
    t0 = time.perf_counter()
    for prompt, max_new in reqs:
        futures.append(
            sched.submit(prompt, max_new_tokens=max_new, eos_id=no_eos)
        )
        time.sleep(rng.exponential(1.0 / rate_req_s))
    results = [f.result(300.0) for f in futures]
    wall = time.perf_counter() - t0
    sched.close(drain=True)
    cont_tokens = sum(len(r.tokens) for r in results)
    cont_tps = cont_tokens / wall
    traces_after = eng.traces - traces0

    reg = _registry.default_registry()
    ttft = reg.get("serving/%s/gen_ttft_ms" % name)
    tok = reg.get("serving/%s/gen_token_ms" % name)
    steps = reg.get("serving/%s/gen_steps" % name)
    n_steps = steps.value() if steps else 0

    # ---- naive whole-sequence ablation ------------------------------------
    # one dense forward over the full padded context per generated token,
    # requests strictly serial (prefix subset of the same workload, same
    # greedy math -> token parity is asserted, throughput is scaled per
    # token so the subset is fair)
    fwd_main, _, fwd_feeds, fwd_fetches = model.build_forward(1, ctx)
    with scope_guard(eng.scope):
        fwd, fwd_ro, _ = aot_serve_lowering(
            fwd_main, fwd_feeds, fwd_fetches, eng.scope
        )

    def naive_generate(prompt, max_new):
        toks = list(prompt)
        out = []
        budget = min(max_new, ctx - len(prompt))
        while len(out) < budget:
            buf = np.zeros((1, ctx, 1), np.int64)
            buf[0, :len(toks), 0] = toks
            (lg,) = fwd({"fwd_tokens": buf}, fwd_ro, {})
            nxt = int(np.asarray(lg)[0, len(toks) - 1].argmax())
            out.append(nxt)
            toks.append(nxt)
        return out

    naive_generate(*reqs[0])  # warm the jit before timing
    t0 = time.perf_counter()
    naive_out = [naive_generate(p, m) for p, m in reqs[:naive_requests]]
    naive_wall = time.perf_counter() - t0
    naive_tokens = sum(len(o) for o in naive_out)
    naive_tps = naive_tokens / naive_wall
    parity_ok = all(
        o == results[i].tokens for i, o in enumerate(naive_out)
    )

    # ---- head-of-line ablation (full mode): TTFT of short prompts that
    # arrive while a max-length prompt is streaming, chunked prefill vs
    # whole-prompt prefill (prefill_chunk = max_context) on identical
    # geometry — the number chunking exists to improve. Uses a 256-token
    # context so the whole-prompt prefill call is genuinely expensive
    # relative to one chunk; the first two rounds warm the host path and
    # are dropped.
    hol = None
    if not smoke:
        hol_kw = dict(model_kw, max_context=256)

        def _hol_short_ttft(chunk, tag):
            m2 = GPTDecoder(**hol_kw)
            e2 = GenerationEngine(m2, name="%s_%s" % (name, tag),
                                  max_slots=max_slots, page_size=8,
                                  prefill_chunk=chunk, prefix_cache=False,
                                  cache_dir=None)
            e2.warmup()
            s2 = GenerationScheduler(e2, max_queue_requests=64,
                                     timeout_ms=120000.0)
            long_p = [int(t) for t in
                      rng.randint(0, vocab, size=e2.max_prompt_len)]
            short_p = [int(t) for t in rng.randint(0, vocab, size=2)]
            lat = []
            for r in range(14):
                fl = s2.submit(long_p, max_new_tokens=8, eos_id=no_eos)
                for _ in range(3):
                    t0 = time.perf_counter()
                    s2.submit(short_p, max_new_tokens=1,
                              eos_id=no_eos).result(60.0)
                    if r >= 2:
                        lat.append((time.perf_counter() - t0) * 1e3)
                fl.result(60.0)
            s2.close(drain=True)
            lat.sort()
            return {
                "p50_ms": round(lat[len(lat) // 2], 3),
                "p99_ms": round(lat[min(len(lat) - 1,
                                        int(len(lat) * 0.99))], 3),
            }

        hol = {
            "long_prompt_tokens": hol_kw["max_context"] - 1,
            "chunked": _hol_short_ttft(None, "holc"),
            "whole_prompt": _hol_short_ttft(hol_kw["max_context"], "holw"),
        }

    pool = eng.pool.stats()
    est = eng.stats()
    pc = est.get("prefix_cache") or {}
    return {
        "metric": "generation_tokens_per_sec_per_chip",
        "value": round(cont_tps, 1),
        "unit": "tokens/sec",
        "requests": n_requests,
        "requests_ok": sum(1 for r in results if r.finish_reason),
        "served_fraction": round(len(results) / float(n_requests), 4),
        "tokens_generated": cont_tokens,
        "poisson_rate_req_s": rate_req_s,
        "mean_tokens_per_step": round(cont_tokens / n_steps, 2)
        if n_steps else None,
        "p50_ttft_ms": round(ttft.percentile(50), 3) if ttft else None,
        "p99_ttft_ms": round(ttft.percentile(99), 3) if ttft else None,
        "p50_token_ms": round(tok.percentile(50), 3) if tok else None,
        "p99_token_ms": round(tok.percentile(99), 3) if tok else None,
        "traces_after_warmup": traces_after,
        "variants": n_variants,
        "prefill_buckets": list(eng.prefill_buckets),
        "prefill_chunk": eng.prefill_chunk,
        "prefill_chunks": est["prefill_chunks"],
        "prefix_hit_rate": round(pc.get("hit_rate", 0.0), 4),
        "prefix_cache": pc,
        "kernel_dispatches": est["kernel_dispatches"],
        "hol_short_ttft_ms": hol,
        "geometry": eng.geometry(),
        "pool": pool,
        "naive_whole_sequence_tokens_per_sec": round(naive_tps, 1),
        "naive_ablation_requests": naive_requests,
        "naive_token_parity_ok": parity_ok,
        "continuous_vs_naive_x": round(cont_tps / naive_tps, 2),
        "model": {k: v for k, v in sorted(model_kw.items())},
        "max_slots": max_slots,
    }


class _ImgShardDecode:
    """Shard factory for the reader bench: deterministic synthetic uint8
    image batches with a real per-batch CPU decode cost (generate +
    augmentation passes) — the uncached path that cache_epoch cannot hide.
    Module-level and numpy-only so it runs inside data-runtime worker
    processes under fork or spawn."""

    def __init__(self, bs, hw, batches_per_shard, passes, classes=100,
                 seed=0):
        self.bs, self.hw = int(bs), int(hw)
        self.batches_per_shard = int(batches_per_shard)
        self.passes = int(passes)
        self.classes = int(classes)
        self.seed = int(seed)

    def __call__(self, shard_id, num_shards):
        rng = np.random.RandomState(self.seed * 100003 + shard_id)
        for _ in range(self.batches_per_shard):
            raw = rng.randint(
                0, 256, (self.bs, 3, self.hw, self.hw)
            ).astype(np.uint8)
            img = raw.astype(np.float32)
            for _ in range(self.passes):  # flip/jitter/clip: decode cost
                img = img[:, :, ::-1, :] * 1.01 + 0.5
                np.clip(img, 0.0, 255.0, out=img)
            yield {
                "img": img.astype(np.uint8),  # compact wire: bytes over PCIe
                "label": rng.randint(
                    0, self.classes, (self.bs, 1)
                ).astype(np.int64),
            }


class _TokShardDecode:
    """Shard factory for the token path: int64 id batches with a
    tokenizer-like CPU cost (sort/cumsum passes over the ids)."""

    def __init__(self, bs, tlen, batches_per_shard, passes, vocab, seed=0):
        self.bs, self.tlen = int(bs), int(tlen)
        self.batches_per_shard = int(batches_per_shard)
        self.passes = int(passes)
        self.vocab = int(vocab)
        self.seed = int(seed)

    def __call__(self, shard_id, num_shards):
        rng = np.random.RandomState(self.seed * 100003 + shard_id)
        for _ in range(self.batches_per_shard):
            toks = rng.randint(
                1, self.vocab, (self.bs, self.tlen, 1)
            ).astype(np.int64)
            for _ in range(self.passes):
                np.cumsum(np.sort(toks, axis=1), axis=1)
            yield {
                "words": toks,
                "label": rng.randint(0, 2, (self.bs, 1)).astype(np.int64),
            }


def _reader_feed_pass(exe, main, loss, factory, feed_names, num_shards,
                      num_workers):
    """One warm epoch (worker spin-up + XLA compile), then a timed epoch.
    Returns (batches, wall_s, stall_s): stall is the time next_batch spent
    BLOCKED waiting for data — the end-to-end feed-stall the PR 4 StepStats
    hook measures on the same call path — so frac = stall/wall is the
    fraction of the epoch the device would have idled on input."""
    from paddle_tpu.py_reader import EOFException, PyReader

    reader = PyReader(feed_names, capacity=4)
    if num_workers > 0:
        reader.decorate_tensor_provider(
            factory, num_workers=num_workers, num_shards=num_shards
        )
    else:
        def seq():  # identical decode work, single in-process feeder thread
            for s in range(num_shards):
                for feed in factory(s, num_shards):
                    yield feed

        reader.decorate_tensor_provider(seq, num_workers=0)
    l = None
    try:
        reader.start()
        for feed in reader():
            (l,) = exe.run(main, feed=feed, fetch_list=[loss.name],
                           return_numpy=False)
        np.asarray(l)
        reader.start()
        batches, stall = 0, 0.0
        t0 = time.perf_counter()
        while True:
            tf = time.perf_counter()
            try:
                feed = reader.next_batch()
            except EOFException:
                break
            stall += time.perf_counter() - tf
            (l,) = exe.run(main, feed=feed, fetch_list=[loss.name],
                           return_numpy=False)
            batches += 1
        np.asarray(l)  # sync before stopping the clock
        wall = time.perf_counter() - t0
    finally:
        reader.close()
    return batches, wall, stall


def _staged_ceiling(exe, main, loss, feed, steps):
    """Device-prestaged throughput: the compute ceiling the feed passes are
    measured against (batches/sec)."""
    import jax

    dev = {k: jax.device_put(v) for k, v in feed.items()}
    for _ in range(2):
        (l,) = exe.run(main, feed=dev, fetch_list=[loss.name],
                       return_numpy=False)
    np.asarray(l)
    t0 = time.perf_counter()
    for _ in range(steps):
        (l,) = exe.run(main, feed=dev, fetch_list=[loss.name],
                       return_numpy=False)
    np.asarray(l)
    return steps / (time.perf_counter() - t0)


def run_reader_bench(smoke=False, num_workers=None):
    """ISSUE 7 evidence pass → BENCH_reader.json: the uncached uint8-image
    and token feed paths, each measured three ways — device-prestaged
    ceiling, single-threaded PyReader (num_workers=0, the pre-runtime hot
    path), and the native data runtime (multiprocess decode + shm ring +
    async device staging, docs/data.md). `pyreader_frac` here is the
    FEED-STALL FRACTION of epoch wall time (time next_batch blocked on
    input / total), the acceptance metric: < 0.05 with the runtime on the
    bench chip, < 0.2 in the CPU CI smoke."""
    import jax
    import paddle_tpu.fluid as fluid
    from paddle_tpu import framework
    from paddle_tpu.executor import Scope, scope_guard

    if smoke:
        nw = int(num_workers or 2)
        img_cfg = dict(bs=16, hw=32, batches_per_shard=6, passes=4)
        tok_cfg = dict(bs=16, tlen=64, batches_per_shard=6, passes=4,
                       vocab=1024)
        shards = 8
    else:
        nw = int(num_workers or 4)
        img_cfg = dict(bs=64, hw=96, batches_per_shard=4, passes=8)
        tok_cfg = dict(bs=64, tlen=256, batches_per_shard=4, passes=24,
                       vocab=8192)
        shards = 16

    hw, tlen, vocab = img_cfg["hw"], tok_cfg["tlen"], tok_cfg["vocab"]

    def build_image():
        main_p, startup = framework.Program(), framework.Program()
        with fluid.unique_name.guard(), fluid.program_guard(main_p, startup):
            img = fluid.layers.data(name="img", shape=[3, hw, hw],
                                    dtype="float32")
            label = fluid.layers.data(name="label", shape=[1], dtype="int64")
            h = fluid.layers.conv2d(img, num_filters=16, filter_size=3,
                                    stride=2, act="relu")
            h = fluid.layers.conv2d(h, num_filters=32, filter_size=3,
                                    stride=2, act="relu")
            h = fluid.layers.pool2d(h, pool_size=2, pool_stride=2)
            logits = fluid.layers.fc(h, size=100)
            loss = fluid.layers.mean(
                fluid.layers.softmax_with_cross_entropy(logits, label)
            )
            fluid.optimizer.SGD(learning_rate=0.01).minimize(loss)
        return main_p, startup, loss

    def build_tokens():
        main_p, startup = framework.Program(), framework.Program()
        with fluid.unique_name.guard(), fluid.program_guard(main_p, startup):
            words = fluid.layers.data(name="words", shape=[tlen, 1],
                                      dtype="int64")
            label = fluid.layers.data(name="label", shape=[1], dtype="int64")
            emb = fluid.layers.embedding(words, size=[vocab, 128])
            h = fluid.layers.reduce_mean(emb, dim=1)
            h = fluid.layers.fc(h, size=256, act="relu")
            logits = fluid.layers.fc(h, size=2)
            loss = fluid.layers.mean(
                fluid.layers.softmax_with_cross_entropy(logits, label)
            )
            fluid.optimizer.SGD(learning_rate=0.01).minimize(loss)
        return main_p, startup, loss

    # no TPUPlace: CI runs this mode's smoke on the CPU; the record says
    # where it ran
    exe = fluid.Executor()
    record = {"metric": "reader_pipeline", "mode": "smoke" if smoke else
              "full", "platform": jax.default_backend(),
              "num_workers": nw, "num_shards": shards}
    for key, build, dec, unit in (
        ("image", build_image, _ImgShardDecode(**img_cfg), img_cfg["bs"]),
        ("tokens", build_tokens, _TokShardDecode(**tok_cfg),
         tok_cfg["bs"] * tok_cfg["tlen"]),
    ):
        main_p, startup, loss = build()
        with scope_guard(Scope(seed=0)):  # fresh scope: no param collisions
            exe.run(startup)
            probe = next(dec(0, shards))
            ceiling = _staged_ceiling(exe, main_p, loss, probe,
                                      steps=shards * 3) * unit
            b0, w0, s0 = _reader_feed_pass(
                exe, main_p, loss, dec,
                list(probe), shards, num_workers=0,
            )
            b1, w1, s1 = _reader_feed_pass(
                exe, main_p, loss, dec,
                list(probe), shards, num_workers=nw,
            )
            thread_ips, rt_ips = b0 * unit / w0, b1 * unit / w1
            if key == "image":
                path = {
                    "staged_images_per_sec": round(ceiling, 2),
                    "pyreader_images_per_sec": round(thread_ips, 2),
                    "pyreader_frac": round(s0 / w0, 3),
                    "pyreader_images_per_sec_runtime": round(rt_ips, 2),
                    "pyreader_frac_runtime": round(s1 / w1, 3),
                }
            else:
                path = {
                    "staged_tokens_per_sec": round(ceiling, 1),
                    "tokens_per_sec": round(thread_ips, 1),
                    "pyreader_frac_tokens": round(s0 / w0, 3),
                    "tokens_per_sec_runtime": round(rt_ips, 1),
                    "pyreader_frac_tokens_runtime": round(s1 / w1, 3),
                }
            path["runtime_speedup_x"] = round(rt_ips / thread_ips, 2)
            path["batches_per_epoch"] = b1
            record[key] = path
    return record


def _recsys_build(rows, fields, dim, is_sparse, use_distributed,
                  optimizer="adam", layer_sizes=(32, 16)):
    import paddle_tpu.fluid as fluid
    from paddle_tpu import framework
    from paddle_tpu.models.deepfm import deepfm

    main_p, startup = framework.Program(), framework.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main_p, startup):
        ids = fluid.layers.data(name="ids", shape=[fields, 1], dtype="int64")
        label = fluid.layers.data(name="label", shape=[1], dtype="float32")
        loss, pred, _ = deepfm(
            ids, label, num_features=rows, num_fields=fields,
            embedding_size=dim, layer_sizes=layer_sizes,
            is_sparse=is_sparse, use_distributed=use_distributed,
        )
        if optimizer == "adam":
            # bf16-stored moments: the TPU-native state precision; per-row
            # sparse updates gather/cast/scatter them alongside the table
            fluid.optimizer.Adam(
                learning_rate=1e-3, moment_dtype="bfloat16"
            ).minimize(loss)
        else:
            fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    return main_p, startup, loss


def _recsys_batches(rng, rows, fields, batch, n):
    out = []
    for _ in range(n):
        ids = rng.randint(0, rows, (batch, fields, 1)).astype("int64")
        label = (rng.rand(batch, 1) < 0.5).astype("float32")
        out.append({"ids": ids, "label": label})
    return out


def _recsys_time(run_step, batches, warmup=2, windows=2, steps=6):
    """min-over-windows ms/step (harness noise only ever adds time)."""
    for i in range(warmup):
        l = run_step(batches[i % len(batches)])
    np.asarray(l)
    best = float("inf")
    for _ in range(windows):
        t0 = time.perf_counter()
        for i in range(steps):
            l = run_step(batches[i % len(batches)])
        np.asarray(l)
        best = min(best, (time.perf_counter() - t0) / steps)
    return best * 1e3


def run_recsys_bench(smoke=False):
    """Sparse embedding engine evidence pass (PR 8) → BENCH_recsys.json.

    Three legs over the DeepFM CTR model (models/deepfm.py, two shared-id
    tables fm_first[rows,1] + fm_emb[rows,dim]):

    1. update-cost: dense Adam (full-table moment decay each step) vs
       is_sparse=True (SelectedRows grads + per-row lazy-Adam updates) on one
       device at <=1% rows touched per step — the sparse step must be
       measurably faster since its optimizer cost is O(touched rows);
    2. ep-sharded throughput: the same sparse model row-sharded over every
       local device via ParallelExecutor + MeshConfig(ep=n) — headline
       `embedding_rows_per_sec` (table rows gathered+updated per second,
       batch*fields*2 tables per step);
    3. parity: sparse ep-sharded SGD vs dense single-device SGD on identical
       batches (the engine changes data layout, not math — SGD is
       bit-exact; see tests/test_deepfm.py for the assertion-grade version).

    Size accounting rides along: table + dense f32 Adam state vs the
    per-chip share when row-sharded with bf16 moments (the "giant table"
    claim — the table's dense state does not fit one chip's fair share)."""
    import jax
    import paddle_tpu.fluid as fluid
    from paddle_tpu.executor import Scope, scope_guard
    from paddle_tpu.parallel import MeshConfig

    n_dev = jax.device_count()
    if smoke:
        rows, dim, fields, batch = 4096, 8, 6, 128
        steps, layer_sizes = 4, (16,)
    else:
        rows, dim, fields, batch = 1 << 20, 32, 16, 512
        steps, layer_sizes = 6, (32, 16)
    if rows % max(n_dev, 1):
        rows -= rows % n_dev  # row-sharding needs divisibility
    rng = np.random.RandomState(0)
    batches = _recsys_batches(rng, rows, fields, batch, 4)
    record = {
        "metric": "recsys_deepfm",
        "mode": "smoke" if smoke else "full",
        "table_rows": rows,
        "embedding_dim": dim,
        "num_fields": fields,
        "batch_size": batch,
        "devices": n_dev,
        "rows_touched_frac": round(batch * fields / float(rows), 5),
        "platform": jax.default_backend(),
    }

    exe = fluid.Executor()  # CI runs this mode's smoke on the CPU

    # ---- leg 1: dense vs sparse update cost, single device ----------------
    for key, sparse in (("dense", False), ("sparse", True)):
        main_p, startup, loss = _recsys_build(
            rows, fields, dim, is_sparse=sparse, use_distributed=False,
            layer_sizes=layer_sizes,
        )
        with scope_guard(Scope(seed=0)):
            exe.run(startup)
            ms = _recsys_time(
                lambda feed: exe.run(main_p, feed=feed,
                                     fetch_list=[loss.name],
                                     return_numpy=False)[0],
                batches, steps=steps,
            )
        record["%s_step_ms_1dev" % key] = round(ms, 2)
    record["sparse_vs_dense_update_speedup_x"] = round(
        record["dense_step_ms_1dev"] / record["sparse_step_ms_1dev"], 2
    )

    # ---- leg 2: ep-sharded sparse throughput ------------------------------
    sharded_ms = None
    if n_dev > 1:
        main_p, startup, loss = _recsys_build(
            rows, fields, dim, is_sparse=True, use_distributed=True,
            layer_sizes=layer_sizes,
        )
        with scope_guard(Scope(seed=0)):
            exe.run(startup)
            pe = fluid.ParallelExecutor(
                use_cuda=False, loss_name=loss.name, main_program=main_p,
                mesh_config=MeshConfig(dp=1, ep=n_dev),
            )
            sharded_ms = _recsys_time(
                lambda feed: pe.run([loss.name], feed=feed,
                                    return_numpy=False)[0],
                batches, steps=steps,
            )
        record["sharded_step_ms_ep%d" % n_dev] = round(sharded_ms, 2)
    rows_per_step = batch * fields * 2  # both tables gather+update per id
    best_ms = min(
        m for m in (sharded_ms, record["sparse_step_ms_1dev"]) if m
    )
    record["embedding_rows_per_sec"] = round(rows_per_step / best_ms * 1e3, 1)

    # ---- size accounting: the giant-table claim ---------------------------
    fbytes = rows * dim * 4
    table_bytes = fbytes + rows * 1 * 4  # fm_emb + fm_first
    dense_state = 2 * (fbytes + rows * 4)  # two f32 moment sets, both tables
    sharded_per_chip = (table_bytes + (fbytes + rows * 4)) // max(n_dev, 1)
    # table f32 + 2x bf16 moments, row-sharded over the mesh
    record["table_bytes"] = table_bytes
    record["dense_opt_state_bytes"] = dense_state
    record["sharded_table_plus_state_bytes_per_chip"] = sharded_per_chip
    record["table_over_chip_state_share_x"] = round(
        (table_bytes + dense_state) / float(sharded_per_chip), 2
    )

    # ---- leg 3: sparse ep-sharded vs dense 1-dev loss parity (SGD) --------
    prows, pfields, pdim, pbatch = 2048, 4, 8, 64
    if prows % max(n_dev, 1):
        prows -= prows % n_dev
    prng = np.random.RandomState(7)
    pbatches = _recsys_batches(prng, prows, pfields, pbatch, 6)

    def parity_losses(distributed):
        main_p, startup, loss = _recsys_build(
            prows, pfields, pdim, is_sparse=distributed,
            use_distributed=distributed, optimizer="sgd", layer_sizes=(16,),
        )
        losses = []
        with scope_guard(Scope(seed=3)):
            exe.run(startup)
            if distributed and n_dev > 1:
                pe = fluid.ParallelExecutor(
                    use_cuda=False, loss_name=loss.name, main_program=main_p,
                    mesh_config=MeshConfig(dp=1, ep=n_dev),
                )
                step = lambda feed: pe.run([loss.name], feed=feed)[0]
            else:
                step = lambda feed: exe.run(
                    main_p, feed=feed, fetch_list=[loss.name]
                )[0]
            for feed in pbatches:
                losses.append(float(np.asarray(step(feed)).reshape(-1)[0]))
        return losses

    dense_l = parity_losses(False)
    sparse_l = parity_losses(True)
    diff = max(abs(a - b) for a, b in zip(dense_l, sparse_l))
    record["parity_max_loss_diff"] = round(diff, 6)
    record["parity_steps"] = len(pbatches)
    return record


def _passes_build_lenet():
    import paddle_tpu.fluid as fluid
    from paddle_tpu import framework
    from paddle_tpu.models import lenet5

    main = framework.Program()
    startup = framework.Program()
    with fluid.unique_name.guard():
        with fluid.program_guard(main, startup):
            img = fluid.layers.data(name="img", shape=[1, 28, 28],
                                    dtype="float32")
            label = fluid.layers.data(name="label", shape=[1], dtype="int64")
            out = lenet5(img, label)
            loss = out[0] if isinstance(out, tuple) else out
            fluid.optimizer.Adam(learning_rate=1e-3).minimize(loss)
    return main, startup, loss


def _passes_build_transformer():
    import paddle_tpu.fluid as fluid
    from paddle_tpu import framework
    from paddle_tpu.models.transformer import build_tiny_flash_transformer

    main = framework.Program()
    startup = framework.Program()
    with fluid.unique_name.guard():
        with fluid.program_guard(main, startup):
            _feeds, loss = build_tiny_flash_transformer()
            fluid.optimizer.Adam(learning_rate=1e-3).minimize(loss)
    return main, startup, loss


def _passes_feed(model, rng, batch):
    if model == "lenet":
        return {
            "img": rng.randn(batch, 1, 28, 28).astype("float32"),
            "label": rng.randint(0, 10, (batch, 1)).astype("int64"),
        }
    from paddle_tpu.models.transformer import tiny_flash_transformer_feed

    return tiny_flash_transformer_feed(batch, seed=int(rng.randint(1 << 30)))


def run_passes_bench(smoke=False):
    """Pass-framework evidence (ISSUE 10 -> PASSES.json): for LeNet and the
    tiny flash transformer, pipeline off vs the training_default preset —
    steady-state step time, program op count before/after, compiled HLO
    instruction count, per-pass payloads (folded/removed/fusion groups), and
    the max loss delta over lockstep training (must be < 1e-6: the pipeline
    preserves the RNG stream, so training is bit-identical)."""
    from paddle_tpu import flags, passes
    from paddle_tpu.executor import Executor, Scope, scope_guard

    steps = 4 if smoke else 10
    warmup = 2
    record = {"metric": "graph_passes", "mode": "smoke" if smoke else "full",
              "preset": "training_default",
              "pipeline": list(passes.PRESETS["training_default"]),
              "models": {}}

    for model, builder, batch in (
        ("lenet", _passes_build_lenet, 32),
        ("transformer", _passes_build_transformer, 8),
    ):
        entry = {}
        losses = {}
        for pipeline in ("off", "training_default"):
            flags.set_flags({"pass_pipeline":
                             "" if pipeline == "off" else pipeline})
            try:
                main_p, startup, loss = builder()
                exe = Executor()
                rng = np.random.RandomState(0)
                with scope_guard(Scope(seed=7)):
                    from paddle_tpu.executor import global_scope

                    exe.run(startup)
                    ls = []
                    feed_names = None
                    for _ in range(warmup):
                        feed = _passes_feed(model, rng, batch)
                        feed_names = sorted(feed)
                        ls.append(float(np.asarray(exe.run(
                            main_p, feed=feed, fetch_list=[loss.name],
                        )[0]).reshape(-1)[0]))
                    t0 = time.perf_counter()
                    for _ in range(steps):
                        ls.append(float(np.asarray(exe.run(
                            main_p, feed=_passes_feed(model, rng, batch),
                            fetch_list=[loss.name],
                        )[0]).reshape(-1)[0]))
                    step_ms = (time.perf_counter() - t0) / steps * 1e3
                    hlo = exe.compiled_hlo()
                    if pipeline != "off":
                        # the memoized transformed program the executor just
                        # ran (same key: program, pipeline, scope, feed/fetch
                        # -> cache hit, not a re-application)
                        transformed = passes.apply_cached(
                            main_p, pipeline, scope=global_scope(),
                            feed_names=feed_names,
                            fetch_names=[loss.name],
                        )
                        entry["ops_after"] = sum(
                            len(b.ops) for b in transformed.blocks
                        )
                        results = transformed._pass_results
                        entry["folded"] = results.get(
                            "constant_fold", {}).get("folded", 0)
                        entry["dce_removed"] = results.get(
                            "dead_op_eliminate", {}).get("removed", 0)
                        entry["fusion_groups"] = results.get(
                            "fuse_elemwise_act", {}).get("groups", 0)
                losses[pipeline] = ls
                key = "off" if pipeline == "off" else "on"
                entry["step_ms_%s" % key] = round(step_ms, 3)
                entry["hlo_instructions_%s" % key] = hlo.count(" = ")
                if pipeline == "off":
                    entry["ops_before"] = sum(
                        len(b.ops) for b in main_p.blocks
                    )
            finally:
                flags.set_flags({"pass_pipeline": ""})
        entry["op_reduction"] = entry["ops_before"] - entry["ops_after"]
        entry["max_loss_delta"] = max(
            abs(a - b)
            for a, b in zip(losses["off"], losses["training_default"])
        )
        record["models"][model] = entry
    record["parity_ok"] = all(
        m["max_loss_delta"] < 1e-6 for m in record["models"].values()
    )
    return record


# v5e chip conventions for the quant bench's roofline projections (the
# int8/fp8 MXU rate claims cannot be measured on the CPU CI host: XLA-CPU
# lowers the int8 dot through a slow emulation path, so the CPU-measured
# int8/native ratio measures that emulation, not the chip — both numbers
# ride the record, clearly labeled)
_V5E = DEVICE_PEAKS["TPU v5 lite"]


def _quant_fit_classifier(model_dir, build_net, feed_shape, feed_dtype,
                          batch_fn, steps, bs, seed=11):
    """Fit a zoo classifier on synthetic clustered batches (Adam) and
    save_inference_model(model_dir). The int8 accuracy gate needs an fp32
    oracle with real decision margins: a random-init deep net's top-1 sits
    at ~zero logit margin, so int8-vs-fp32 'disagreement' there measures
    logit degeneracy, not quantization fidelity. Returns the fp32 training
    loss curve endpoints (first, last) as a fit sanity check."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu import framework
    from paddle_tpu.executor import Scope, scope_guard

    main, startup = framework.Program(), framework.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        img = fluid.layers.data(name="img", shape=feed_shape,
                                dtype=feed_dtype)
        label = fluid.layers.data(name="label", shape=[1], dtype="int64")
        loss, logits = build_net(img, label)
        fluid.optimizer.Adam(learning_rate=1e-3).minimize(loss)
    exe = fluid.Executor()
    rng = np.random.RandomState(seed)
    first = last = None
    with scope_guard(Scope(seed=seed)):
        exe.run(startup)
        for i in range(steps):
            x, y = batch_fn(rng, bs)
            (lv,) = exe.run(main, feed={"img": x, "label": y},
                            fetch_list=[loss.name])
            last = float(np.asarray(lv).reshape(()))
            if first is None:
                first = last
        fluid.io.save_inference_model(
            model_dir, ["img"], [logits], exe, main_program=main
        )
    return first, last


def _quant_eval_classifier(model_dir, name, batch_fn, calib_batches,
                           eval_batches, eval_bs, seed=3):
    """fp32-vs-int8 evidence for one saved classifier: top-1 accuracy of
    each engine against the synthetic labels, per-example agreement, logit
    drift, and the CPU rows/s of both engines."""
    from paddle_tpu.serving import ServingEngine

    rng = np.random.RandomState(seed)
    calib = [{"img": batch_fn(rng, 16)[0]} for _ in range(calib_batches)]
    e_f32 = ServingEngine(model_dir, name=name + "_f32", cache_dir=None)
    e_i8 = ServingEngine(model_dir, name=name + "_i8", cache_dir=None,
                         precision="int8", calibration_feeds=calib)
    ok32 = ok8 = agree = tot = 0
    drift = 0.0
    t32 = t8 = 0.0
    for _ in range(eval_batches):
        x, y = batch_fn(rng, eval_bs)
        t0 = time.perf_counter()
        (a,) = e_f32.run({"img": x})
        t32 += time.perf_counter() - t0
        t0 = time.perf_counter()
        (b,) = e_i8.run({"img": x})
        t8 += time.perf_counter() - t0
        pa, pb = np.argmax(a, -1), np.argmax(b, -1)
        yy = y.reshape(-1)
        ok32 += int((pa == yy).sum())
        ok8 += int((pb == yy).sum())
        agree += int((pa == pb).sum())
        tot += x.shape[0]
        drift = max(drift, float(
            np.abs(a - b).max() / (np.abs(a).max() + 1e-9)
        ))
    q = e_i8.stats()["quant"]
    return {
        "top1_fp32": round(ok32 / tot, 4),
        "top1_int8": round(ok8 / tot, 4),
        "top1_delta": round(abs(ok32 - ok8) / tot, 4),
        "agreement": round(agree / tot, 4),
        "eval_examples": tot,
        "max_rel_logit_err": round(drift, 4),
        "quantized_muls": q["quantized_muls"],
        "calibrated_ranges": q["calibrated_ranges"],
        "fused_groups": q["fused_groups"],
        "cpu_fp32_rows_per_sec": round(tot / t32, 1),
        "cpu_int8_rows_per_sec": round(tot / t8, 1),
    }, e_i8


def _quant_v5e_roofline(mm_flops, w_elems, act_elems):
    """Single-shot serving time on one v5e, bf16 weights vs the calibrated
    int8 path, under the MXU-rate/HBM-bandwidth roofline. bf16 reads
    2B/elem weights+activations; int8 reads 1B/elem weights but pays the
    quantize_static activation pass (4B f32 read + 1B write + 1B GEMM
    re-read). Epilogue dequant is folded into the kernel (free)."""
    bw = _V5E["hbm_gbs"] * 1e9
    t_bf16 = max(mm_flops / (_V5E["bf16_tflops"] * 1e12),
                 (2.0 * w_elems + 2.0 * act_elems) / bw)
    t_int8 = max(mm_flops / (_V5E["int8_tops"] * 1e12),
                 (1.0 * w_elems + 6.0 * act_elems) / bw)
    return {
        "mm_gflops": round(mm_flops / 1e9, 2),
        "weight_melems": round(w_elems / 1e6, 2),
        "act_melems": round(act_elems / 1e6, 2),
        "peak_bf16_tflops": _V5E["bf16_tflops"],
        "peak_int8_tops": _V5E["int8_tops"],
        "hbm_gbs": _V5E["hbm_gbs"],
        "t_bf16_us": round(t_bf16 * 1e6, 2),
        "t_int8_us": round(t_int8 * 1e6, 2),
        "speedup_x": round(t_bf16 / t_int8, 2),
    }


def run_quant_bench(smoke=False):
    """Quantization evidence pass (ISSUE 18 acceptance) -> QUANT.json.

    Four sections: (1) zoo classifiers briefly fit on synthetic clusters,
    fp32 oracle vs calibrated-int8 ServingEngine top-1 (the <0.5% accuracy
    gate); (2) the fc-stack serving head — the matmul-dominated honest
    vehicle for the int8 MXU rate, same reasoning as run_transformer_mfu —
    with the v5e roofline projection carrying the chip-rate claim and the
    CPU-measured ratio riding alongside; (3) the kv-int8 GenerationEngine
    at 2x max_slots in fewer pool bytes, with greedy-token agreement and
    the last-step logit-drift bound; (4) the FLAGS_fp8_matmul training
    step-time entry."""
    import shutil
    import tempfile

    import paddle_tpu.fluid as fluid
    from paddle_tpu.executor import Scope
    from paddle_tpu.models.gpt_decoder import GPTDecoder
    from paddle_tpu.models.lenet import lenet5
    from paddle_tpu.serving import GenerationEngine, GenerationScheduler

    record = {"metric": "quant_serving", "smoke": bool(smoke)}
    tmp = tempfile.mkdtemp(prefix="quant-bench-")
    try:
        # ---- (1) zoo classifiers: int8 top-1 within 0.5% of fp32 ----------
        fit_steps, eval_batches, eval_bs = (
            (10, 2, 128) if smoke else (30, 8, 250)
        )

        lenet_means = np.random.RandomState(100).rand(10, 1, 28, 28)

        def lenet_batch(rng, bs):
            y = rng.randint(0, 10, (bs, 1)).astype("int64")
            x = (0.7 * lenet_means[y.reshape(-1)]
                 + 0.3 * rng.rand(bs, 1, 28, 28)).astype("float32")
            return x, y

        def lenet_net(img, label):
            loss, _acc, logits = lenet5(img, label)
            return loss, logits

        d1 = os.path.join(tmp, "lenet")
        l0, l1 = _quant_fit_classifier(
            d1, lenet_net, [1, 28, 28], "float32", lenet_batch,
            steps=fit_steps, bs=64,
        )
        zoo_lenet, _ = _quant_eval_classifier(
            d1, "q_lenet", lenet_batch, calib_batches=8,
            eval_batches=eval_batches, eval_bs=eval_bs,
        )
        zoo_lenet["fit_loss_first_last"] = [round(l0, 3), round(l1, 3)]

        # fc-stack classifier head (the deep&wide serving shape: every mul
        # quantizes, so this model also vehicles the throughput section)
        d_model, classes, depth = (256, 16, 2) if smoke else (2048, 16, 3)
        head_means = np.random.RandomState(101).randn(classes, d_model)

        def head_batch(rng, bs):
            y = rng.randint(0, classes, (bs, 1)).astype("int64")
            x = (head_means[y.reshape(-1)]
                 + 0.7 * rng.randn(bs, d_model)).astype("float32")
            return x, y

        def head_net(img, label):
            h = img
            for _ in range(depth):
                h = fluid.layers.fc(h, size=d_model, act="relu")
            logits = fluid.layers.fc(h, size=classes)
            loss = fluid.layers.mean(
                fluid.layers.softmax_with_cross_entropy(logits, label)
            )
            return loss, logits

        d2 = os.path.join(tmp, "fc_head")
        h0, h1 = _quant_fit_classifier(
            d2, head_net, [d_model], "float32", head_batch,
            steps=fit_steps, bs=64,
        )
        zoo_head, e_head_i8 = _quant_eval_classifier(
            d2, "q_head", head_batch, calib_batches=8,
            eval_batches=eval_batches, eval_bs=eval_bs,
        )
        zoo_head["fit_loss_first_last"] = [round(h0, 3), round(h1, 3)]
        record["zoo"] = {"lenet5": zoo_lenet, "fc_head": zoo_head}
        record["top1_delta_max"] = max(
            zoo_lenet["top1_delta"], zoo_head["top1_delta"]
        )

        # ---- (2) single-shot throughput: v5e roofline + CPU measured ------
        # op mix counted from what quantize_serving actually froze
        B = 128 if smoke else 1024
        scope = e_head_i8.scope
        frozen = e_head_i8.quant_results["quantize_serving"]["weights_frozen"]
        mm_flops = w_elems = act_elems = 0
        for wname in frozen:
            k, n = np.asarray(scope.find_var(wname)).shape
            mm_flops += 2.0 * B * k * n
            w_elems += k * n
            act_elems += B * k
        roof = _quant_v5e_roofline(mm_flops, w_elems, act_elems)
        record["single_shot"] = {
            "model": {"d_model": d_model, "depth": depth, "classes": classes},
            "batch_rows": B,
            "v5e_roofline": roof,
            "int8_vs_bf16_x_v5e": roof["speedup_x"],
            # CPU ratio measures XLA-CPU's int8-dot emulation, not the MXU
            "cpu_measured_x": round(
                zoo_head["cpu_int8_rows_per_sec"]
                / zoo_head["cpu_fp32_rows_per_sec"], 3,
            ),
        }

        # ---- (3) kv-int8 generation: 2x slots in fewer pool bytes ---------
        if smoke:
            kv_kw = dict(vocab_size=64, n_layer=2, n_head=2, d_model=32,
                         d_inner=64, max_context=32)
            base_slots, n_parity, n_sched = 4, 3, 8
        else:
            kv_kw = dict(vocab_size=256, n_layer=4, n_head=4, d_model=128,
                         d_inner=256, max_context=64)
            base_slots, n_parity, n_sched = 8, 8, 32
        no_eos = kv_kw["vocab_size"]
        e_f32 = GenerationEngine(
            GPTDecoder(**kv_kw), name="qkv_f32", max_slots=base_slots,
            page_size=8, cache_dir=None, scope=Scope(seed=11),
        )
        e_i8 = GenerationEngine(
            GPTDecoder(kv_dtype="int8", **kv_kw), name="qkv_i8",
            max_slots=2 * base_slots, page_size=8, cache_dir=None,
            scope=Scope(seed=11),
        )
        rng = np.random.RandomState(0)
        vocab = kv_kw["vocab_size"]
        drift = 0.0
        tok_same = tok_all = 0
        for _ in range(n_parity):
            L = int(rng.randint(4, e_f32.max_prompt_len - 8))
            p = [int(t) for t in rng.randint(0, vocab, size=L)]
            r32 = e_f32.generate(p, max_new_tokens=8, eos_id=no_eos)
            l32 = e_f32.last_logits[0].copy()
            ri8 = e_i8.generate(p, max_new_tokens=8, eos_id=no_eos)
            li8 = e_i8.last_logits[0].copy()
            tok_same += sum(a == b for a, b in zip(r32.tokens, ri8.tokens))
            tok_all += len(r32.tokens)
            drift = max(drift, float(
                np.abs(l32 - li8).max() / (np.abs(l32).max() + 1e-9)
            ))

        # GENSERVE-style continuous-batching load on the int8-kv engine
        sched = GenerationScheduler(e_i8, max_queue_requests=n_sched,
                                    timeout_ms=120000.0)
        futures = []
        t0 = time.perf_counter()
        for _ in range(n_sched):
            L = int(rng.randint(1, e_i8.max_prompt_len + 1))
            p = [int(t) for t in rng.randint(0, vocab, size=L)]
            mx = int(rng.randint(4, max(5, e_i8.max_context // 2)))
            futures.append(sched.submit(p, max_new_tokens=mx, eos_id=no_eos))
            time.sleep(rng.exponential(1.0 / 100.0))
        results = [f.result(300.0) for f in futures]
        wall = time.perf_counter() - t0
        sched.close(drain=True)
        toks = sum(len(r.tokens) for r in results)

        p32, p8 = e_f32.pool.stats(), e_i8.pool.stats()
        record["kv_int8"] = {
            "baseline_max_slots": base_slots,
            "max_slots": 2 * base_slots,
            "max_slots_x": 2.0,
            "pool_bytes_f32": p32["resident_bytes"],
            "pool_bytes_int8_2x_slots": p8["resident_bytes"],
            "pool_bytes_x": round(
                p8["resident_bytes"] / p32["resident_bytes"], 3
            ),
            "storage_dtype": p8["storage_dtype"],
            "token_agreement": round(tok_same / tok_all, 4),
            "max_rel_logit_drift": round(drift, 4),
            "tokens_per_sec": round(toks / wall, 1),
            "requests": n_sched,
            "requests_ok": sum(1 for r in results if r.finish_reason),
            "geometry": e_i8.geometry(),
            "model": {k: v for k, v in sorted(kv_kw.items())},
        }

        # ---- (4) fp8 training-matmul step time ----------------------------
        from paddle_tpu import flags as _flags
        from paddle_tpu.executor import scope_guard
        from paddle_tpu.ops.pallas_kernels import KERNEL_DISPATCHES

        t_kw = (dict(b=2, t=32, d=64, n_layer=1, vocab=256) if smoke
                else dict(b=2, t=64, d=128, n_layer=2, vocab=512))
        t_steps = 3 if smoke else 6

        def fp8_step(fp8_on):
            _flags.set_flags({"fp8_matmul": bool(fp8_on)})
            try:
                main, startup, feed, loss, flops = build_transformer(**t_kw)
                exe = fluid.Executor()
                with scope_guard(Scope(seed=0)):
                    exe.run(startup)
                    before = KERNEL_DISPATCHES.get("matmul_fp8", 0)
                    for _ in range(2):
                        (lv,) = exe.run(main, feed=feed,
                                        fetch_list=[loss.name],
                                        return_numpy=False)
                    np.asarray(lv)
                    t0 = time.perf_counter()
                    for _ in range(t_steps):
                        (lv,) = exe.run(main, feed=feed,
                                        fetch_list=[loss.name],
                                        return_numpy=False)
                    lf = float(np.asarray(lv).reshape(()))
                    dt = (time.perf_counter() - t0) / t_steps
                return dt, lf, KERNEL_DISPATCHES.get("matmul_fp8", 0) - before
            finally:
                _flags.set_flags({"fp8_matmul": False})

        dt_base, loss_base, _ = fp8_step(False)
        dt_fp8, loss_fp8, n_disp = fp8_step(True)
        record["fp8_transformer"] = {
            "model": t_kw,
            "cpu_step_ms_baseline": round(dt_base * 1e3, 2),
            "cpu_step_ms_fp8": round(dt_fp8 * 1e3, 2),
            "matmul_fp8_dispatches_per_step": n_disp // (t_steps + 2),
            "loss_baseline": round(loss_base, 4),
            "loss_fp8": round(loss_fp8, 4),
            # e4m3 pairs run the MXU at the int8 rate (ops/pallas_kernels.py)
            "nominal_v5e_matmul_speedup_x": round(
                _V5E["int8_tops"] / _V5E["bf16_tflops"], 2
            ),
        }
        return record
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def run_online_bench(smoke=False):
    """Online-learning evidence pass (PR 15 -> ONLINE.json; docs/online.md).

    One process, the full loop: a DeepFM CTR model trains on a synthetic
    clickstream (OnlineTrainer over the elastic Supervisor), publishing a
    base + delta chain into a model repository every `interval` steps, while
    a ModelServer serves the SAME model to concurrent HTTP clients and a
    HotReloader lands each published version in the live engine. Proves:

      - zero 5xx across >= `swaps_target` hot swaps under load;
      - every response names the version that computed it, and each
        client's observed version sequence is monotone;
      - staleness stays under the contract bound (gauge sampled all run);
      - bit-parity: for sampled versions k, an OFFLINE engine restored from
        base+deltas(<=k) reproduces the served prediction exactly;
      - sustained trainer rows/sec while serving.
    """
    import io as stdio  # noqa: F401  (kept for parity with serving bench)
    import shutil
    import tempfile
    import threading
    import urllib.error
    import urllib.request

    import paddle_tpu.fluid as fluid
    from paddle_tpu import framework
    from paddle_tpu.executor import Scope, scope_guard
    from paddle_tpu.models.deepfm import deepfm
    from paddle_tpu.observability import registry as _registry
    from paddle_tpu.online import (
        HotReloader,
        ModelPublisher,
        OnlineTrainer,
        StalenessContract,
        read_latest,
    )
    from paddle_tpu.resilience import async_ckpt as ac
    from paddle_tpu.serving import ModelServer, ServingEngine

    rows = 512 if smoke else 4096
    fields, dim, batch = 4, 8, 32
    interval = 5
    swaps_target = 3 if smoke else 10
    steps = interval * (swaps_target + 2)
    contract = StalenessContract(max_staleness_steps=10 * interval)

    work = tempfile.mkdtemp(prefix="online-bench-")
    repo = os.path.join(work, "repo")
    record = {
        "metric": "online_learning",
        "mode": "smoke" if smoke else "full",
        "table_rows": rows,
        "num_fields": fields,
        "batch_size": batch,
        "publish_interval": interval,
        "max_staleness_steps": contract.max_staleness_steps,
    }
    try:
        main_p, startup = framework.Program(), framework.Program()
        with fluid.unique_name.guard(), fluid.program_guard(main_p, startup):
            ids = fluid.layers.data(
                name="ids", shape=[fields, 1], dtype="int64"
            )
            label = fluid.layers.data(
                name="label", shape=[1], dtype="float32"
            )
            loss, pred, _ = deepfm(
                ids, label, num_features=rows, num_fields=fields,
                embedding_size=dim, layer_sizes=(16,),
                is_sparse=True, use_distributed=True,
            )
            fluid.optimizer.SGD(learning_rate=0.05).minimize(loss)

        exe = fluid.Executor()
        scope = Scope(seed=0)
        model_dir = os.path.join(work, "model")
        with scope_guard(scope):
            exe.run(startup)
            fluid.io.save_inference_model(
                model_dir, ["ids"], [pred], exe, main_program=main_p
            )

        srv = ModelServer(port=0)
        eng = srv.add_model(
            "ctr", model_dir, batch_buckets=(1, 2, 4),
            batcher_opts={"max_batch_delay_ms": 1.0},
        )
        serve_names = eng.param_names()
        port = srv.start()
        base_url = "http://127.0.0.1:%d" % port

        trainer = OnlineTrainer(
            exe, main_p, repo, serve_names,
            publisher=ModelPublisher(
                repo, max_chain=steps, contract=contract
            ),
            publish_interval=interval, scope=scope,
        )
        reloader = HotReloader(
            repo, {"ctr": eng}, consumer="bench", poll_interval_s=0.02,
            contract=contract,
        ).start()

        def stream():
            rng = np.random.RandomState(11)
            for _ in range(steps):
                yield {
                    "ids": rng.randint(
                        0, rows, (batch, fields, 1)
                    ).astype(np.int64),
                    "label": (
                        rng.rand(batch, 1) < 0.5
                    ).astype(np.float32),
                }

        train_curve = []
        train_wall = []

        def train():
            t0 = time.perf_counter()
            train_curve.extend(
                trainer.run(stream(), fetch_list=[loss.name])
            )
            train_wall.append(time.perf_counter() - t0)

        payload = json.dumps({
            "inputs": {
                "ids": np.random.RandomState(5).randint(
                    0, rows, (2, fields, 1)
                ).tolist()
            }
        }).encode()
        stop = threading.Event()
        n_clients = 3
        per_client = [[] for _ in range(n_clients)]  # (version, outputs)
        errors_5xx, errors_other = [], []
        staleness_seen = []

        def client(i):
            while not stop.is_set():
                try:
                    req = urllib.request.Request(
                        base_url + "/v1/models/ctr:predict", data=payload,
                        headers={"Content-Type": "application/json"},
                    )
                    doc = json.load(urllib.request.urlopen(req, timeout=30))
                    out = np.asarray(
                        list(doc["outputs"].values())[0], np.float32
                    )
                    per_client[i].append((int(doc["model_version"]), out))
                except urllib.error.HTTPError as e:
                    (errors_5xx if e.code >= 500 else errors_other).append(e)
                except Exception as e:
                    errors_other.append(e)

        def sample_staleness():
            snap = _registry.default_registry().snapshot()
            vals = snap.get("online/serving_staleness_steps", {})
            for v in (vals.get("values") or {}).values():
                staleness_seen.append(float(v))

        tthread = threading.Thread(target=train, daemon=True)
        cthreads = [
            threading.Thread(target=client, args=(i,), daemon=True)
            for i in range(n_clients)
        ]
        tthread.start()
        for t in cthreads:
            t.start()
        while tthread.is_alive():
            tthread.join(0.05)
            sample_staleness()
        # let the reloader land the final version, then stop the load
        deadline = time.perf_counter() + 30
        while time.perf_counter() < deadline:
            latest = read_latest(repo)
            if latest and reloader.applied_version == latest["version"]:
                break
            time.sleep(0.05)
            sample_staleness()
        time.sleep(0.2)  # a few requests against the final version
        stop.set()
        for t in cthreads:
            t.join(30)
        reloader.stop()
        srv.stop(drain=True)

        samples = [s for cs in per_client for s in cs]
        versions = sorted({v for v, _ in samples})
        for cs in per_client:  # served version is monotone per client
            vs = [v for v, _ in cs]
            assert vs == sorted(vs), "served version went backwards"
        assert not errors_5xx, errors_5xx[:3]
        assert reloader.reloads >= swaps_target, (
            "only %d hot swaps" % reloader.reloads
        )
        latest = read_latest(repo)
        assert latest and reloader.applied_version == latest["version"]
        assert max(staleness_seen or [0.0]) <= contract.max_staleness_steps

        # bit-parity: offline engine from base+deltas(<=k) == served output
        by_version = {}
        for v, out in samples:
            by_version.setdefault(v, out)
        check = [v for v in versions if v > 0][-4:]
        feed = {
            "ids": np.asarray(
                json.loads(payload)["inputs"]["ids"], np.int64
            )
        }
        parity = True
        for k in check:
            step_k, arrays, _info = ac.load_with_deltas(repo, upto_step=k)
            assert step_k == k
            off = ServingEngine(
                model_dir, name="off%d" % k, batch_buckets=(1, 2, 4)
            )
            off.set_params(arrays, version=k)
            (out_k,) = off.run(feed)
            parity = parity and np.array_equal(
                np.asarray(out_k, np.float32), by_version[k]
            )
        assert parity, "served prediction != offline base+delta replay"

        wall = train_wall[0] if train_wall else float("nan")
        pub = trainer.publisher.stats()
        record.update({
            "train_steps": trainer.steps,
            "train_wall_s": round(wall, 3),
            "rows_per_sec": round(trainer.steps * batch / wall, 1),
            "loss_first": round(train_curve[0], 5) if train_curve else None,
            "loss_last": round(train_curve[-1], 5) if train_curve else None,
            "publishes": pub["published"],
            "publish_throttled": pub["throttled"],
            "delta_chain_len": pub["chain_len"],
            "hot_swaps": reloader.reloads,
            "reload_errors": reloader.errors,
            "requests_total": len(samples),
            "errors_5xx": len(errors_5xx),
            "errors_other": len(errors_other),
            "versions_served": versions,
            "max_staleness_steps_observed": max(staleness_seen or [0.0]),
            "parity_versions_checked": check,
            "parity_bit_exact": bool(parity),
        })
    finally:
        shutil.rmtree(work, ignore_errors=True)
    return record


def _fleet_on_cpu():
    """bench.py fleet|tracing|slo run their parent and every replica child
    on the CPU, and say so in their records, until ROADMAP R6 decides how
    replica processes hold chips: a chip belongs to one process, and these
    parents touch JAX before they spawn. Pins this process and returns the
    env the ReplicaProcess children get."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    if jax.default_backend() != "cpu":
        raise RuntimeError(
            "this mode runs on the CPU but JAX already initialised %r"
            % jax.default_backend()
        )
    return {"JAX_PLATFORMS": "cpu"}


def run_fleet_bench(smoke=False):
    """Fleet chaos soak (PR 16 -> FLEET.json; docs/fleet.md).

    Three replica ModelServer SUBPROCESSES (predict MLP + a tiny GPTDecoder
    :generate model, all replicas seeded identically) behind one Router,
    under live mixed predict/generate client traffic. Mid-run, one replica
    is SIGKILLed and later restarted; it may rejoin the routable pool only
    after its HotReloader lands AND acks the repository's published model
    version (the PR 15 staleness gate). Then two targeted chaos rounds —
    PADDLE_TPU_FAULTS=conn_reset and slow_response armed on ONE replica —
    must show that replica's circuit breaker opening and re-closing while
    the router absorbs everything. Acceptance, asserted here:

      - zero 5xx across the whole soak; served_fraction == 1.0;
      - failover-window p99 <= 5x steady-state p99;
      - the killed replica rejoins only at/after the acked target version;
      - breaker opened >= 1x and re-closed in each targeted chaos round,
        with zero client-visible errors.
    """
    import shutil
    import tempfile
    import threading
    import urllib.error
    import urllib.request

    import paddle_tpu.fluid as fluid
    from paddle_tpu import framework
    from paddle_tpu.executor import Scope, scope_guard
    from paddle_tpu.fleet import CLOSED, ReplicaProcess, Router
    from paddle_tpu.online import ModelPublisher, read_latest
    from paddle_tpu.serving import ServingEngine

    steady_s = 2.0 if smoke else 6.0
    chaos_s = 2.0 if smoke else 5.0
    n_predict_clients = 3
    n_generate_clients = 2

    cpu_env = _fleet_on_cpu()
    work = tempfile.mkdtemp(prefix="fleet-bench-")
    repo = os.path.join(work, "repo")
    record = {
        "metric": "fleet_chaos",
        "mode": "smoke" if smoke else "full",
        "platform": "cpu",
        "replicas": 3,
    }
    gen_kw = dict(vocab_size=24, n_layer=2, n_head=2, d_model=16,
                  d_inner=32, max_context=16)

    def _save_mlp_inference(model_dir):
        main_p, startup = framework.Program(), framework.Program()
        with fluid.unique_name.guard(), fluid.program_guard(main_p, startup):
            x = fluid.layers.data(name="fx", shape=[6], dtype="float32")
            h = fluid.layers.fc(input=x, size=8, act="relu")
            y = fluid.layers.fc(input=h, size=3, act="softmax")
        exe = fluid.Executor(fluid.CPUPlace())
        with scope_guard(Scope(seed=3)):
            exe.run(startup)
            fluid.io.save_inference_model(
                model_dir, ["fx"], [y], exe, main_program=main_p
            )

    def _spec(name):
        return {
            "name": name,
            "request_timeout_ms": 10000.0,
            "predict": {"model": "m", "model_dir": model_dir},
            "generate": {"model": "g", "model_kw": gen_kw, "seed": 0,
                         "max_slots": 3, "page_size": 4, "max_context": 16},
            "repo": repo,
            "poll_interval_s": 0.1,
        }

    p_doc = json.dumps({
        "inputs": {"fx": np.random.RandomState(9).rand(2, 6).tolist()}
    }).encode()
    g_doc = json.dumps({
        "prompt": [1, 2, 3], "max_new_tokens": 4, "eos_id": 999
    }).encode()

    def _post(url, body, timeout=30.0):
        req = urllib.request.Request(
            url, data=body, headers={"Content-Type": "application/json"}
        )
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, json.loads(resp.read().decode())

    try:
        model_dir = os.path.join(work, "model")
        _save_mlp_inference(model_dir)
        # publish v1 into the repo: replicas must land+ack it to be routable
        eng = ServingEngine(model_dir, name="m", batch_buckets=(1, 2, 4))
        params = {n: np.asarray(eng.scope.vars[n]).copy()
                  for n in eng.param_names()}
        ModelPublisher(repo).publish(params, 1)
        target_version = read_latest(repo)["version"]

        reps = [ReplicaProcess(_spec("fr%d" % i), work, env=cpu_env)
                for i in range(3)]
        router = Router(
            port=0, hedge=True, hedge_delay_ms=80.0, probe_interval_s=0.2,
            down_after=2, total_deadline_s=20.0, attempt_timeout_s=8.0,
            repo=repo, repo_model="m", seed=0,
        )
        rport = router.start()
        base = "http://127.0.0.1:%d" % rport
        for r in reps:
            r.start()
        for r in reps:
            r.wait_ready(timeout=300.0)
            router.register(r.name, r.url)
        router.probe_once()
        assert len(router.stats()["routable"]) == 3, router.stats()

        phase = ["steady"]
        samples = []  # (phase, kind, latency_s, code)
        errors_5xx, errors_other = [], []
        gen_tokens = set()
        stop = threading.Event()

        def client(kind):
            url = base + ("/v1/models/m:predict" if kind == "predict"
                          else "/v1/models/g:generate")
            body = p_doc if kind == "predict" else g_doc
            while not stop.is_set():
                t0 = time.perf_counter()
                try:
                    code, doc = _post(url, body)
                    samples.append(
                        (phase[0], kind, time.perf_counter() - t0, code)
                    )
                    if kind == "generate":
                        gen_tokens.add(tuple(doc["tokens"]))
                except urllib.error.HTTPError as e:
                    (errors_5xx if e.code >= 500 else errors_other).append(
                        (phase[0], kind, e.code)
                    )
                except Exception as e:
                    errors_5xx.append((phase[0], kind, repr(e)))

        threads = [threading.Thread(target=client, args=("predict",),
                                    daemon=True)
                   for _ in range(n_predict_clients)]
        threads += [threading.Thread(target=client, args=("generate",),
                                     daemon=True)
                    for _ in range(n_generate_clients)]
        for t in threads:
            t.start()

        time.sleep(steady_s)
        # ------------------------------------------------ SIGKILL + restart
        phase[0] = "failover"
        reps[0].kill()
        t_kill = time.perf_counter()
        time.sleep(chaos_s)
        reps[0].restart()
        reps[0].wait_ready(timeout=300.0)
        router.register(reps[0].name, reps[0].url)  # fresh port
        # the staleness gate: routable again only once the restarted
        # process's HotReloader has ACKED the published version
        rejoin_deadline = time.monotonic() + 120.0
        rejoined_at_version = None
        while time.monotonic() < rejoin_deadline:
            router.probe_once()
            if reps[0].name in router.stats()["routable"]:
                rejoined_at_version = router.replicas()[
                    reps[0].name
                ].version_for_gate("m")
                break
            time.sleep(0.1)
        assert rejoined_at_version is not None, "killed replica never rejoined"
        assert rejoined_at_version >= target_version
        phase[0] = "recovered"
        time.sleep(steady_s / 2)
        stop.set()
        for t in threads:
            t.join(30.0)

        total = len(samples) + len(errors_5xx) + len(errors_other)
        served = len(samples)
        lat = {ph: sorted(s[2] for s in samples if s[0] == ph)
               for ph in ("steady", "failover", "recovered")}
        p99 = {
            ph: (xs[min(int(len(xs) * 0.99), len(xs) - 1)] * 1e3
                 if xs else None)
            for ph, xs in lat.items()
        }
        assert not errors_5xx, errors_5xx[:5]
        assert served == total and total > 0
        assert len(gen_tokens) == 1, (
            "generate replicas disagreed: %s" % gen_tokens
        )
        failover_ratio = (
            p99["failover"] / p99["steady"]
            if p99["failover"] and p99["steady"] else None
        )
        assert failover_ratio is None or failover_ratio <= 5.0, (
            "failover p99 %.1fms > 5x steady p99 %.1fms"
            % (p99["failover"], p99["steady"])
        )
        record.update({
            "requests_total": total,
            "served_fraction": round(served / total, 4),
            "errors_5xx": len(errors_5xx),
            "errors_other": len(errors_other),
            "steady_p99_ms": round(p99["steady"], 2) if p99["steady"] else None,
            "failover_p99_ms": (
                round(p99["failover"], 2) if p99["failover"] else None
            ),
            "failover_p99_over_steady": (
                round(failover_ratio, 2) if failover_ratio else None
            ),
            "recovered_p99_ms": (
                round(p99["recovered"], 2) if p99["recovered"] else None
            ),
            "target_model_version": target_version,
            "rejoined_at_version": rejoined_at_version,
            "kill_to_stop_s": round(time.perf_counter() - t_kill, 2),
            "retries": router._m_retries.value(kind="predict")
            + router._m_retries.value(kind="generate"),
            "hedges_launched": router._m_hedges.value(event="launched"),
            "hedges_won": router._m_hedges.value(event="won"),
            "generate_parity": len(gen_tokens) == 1,
        })
        router.stop()
        for r in reps:
            r.terminate()

        # ---------------------------------------- targeted breaker rounds
        # one replica armed with a deterministic fault plan, one clean: the
        # armed replica's breaker must open AND re-close while every client
        # request still succeeds through failover
        for fault_kind, fault_spec in (
            ("conn_reset", "conn_reset:every=2"),
            ("slow_response", "slow_response:every=2@ms=1200"),
        ):
            cr = [
                ReplicaProcess(_spec("%s0" % fault_kind[:2]), work,
                               env=cpu_env, faults=fault_spec),
                ReplicaProcess(_spec("%s1" % fault_kind[:2]), work,
                               env=cpu_env),
            ]
            crouter = Router(
                port=0, hedge=False, probe_interval_s=0.2,
                total_deadline_s=20.0, attempt_timeout_s=0.4,
                repo=repo, repo_model="m", seed=1,
                breaker_opts=dict(
                    failure_threshold=3, error_rate_threshold=0.5,
                    min_requests=4, open_for_s=0.3, success_threshold=1,
                ),
            )
            cport = crouter.start()
            armed = cr[0].spec["name"]
            try:
                for r in cr:
                    r.start()
                for r in cr:
                    r.wait_ready(timeout=300.0)
                    crouter.register(r.name, r.url)
                crouter.probe_once()
                url = "http://127.0.0.1:%d/v1/models/m:predict" % cport
                codes = []
                opened = closed_again = False
                deadline = time.monotonic() + (20.0 if smoke else 40.0)
                while time.monotonic() < deadline:
                    codes.append(_post(url, p_doc)[0])
                    br = crouter.replicas()[armed].breaker
                    if br.stats()["opens"] >= 1:
                        opened = True
                        if br.state == CLOSED:
                            closed_again = True
                            break
                    time.sleep(0.01)
                assert codes and all(c == 200 for c in codes), (
                    fault_kind, codes[-5:]
                )
                assert opened, "%s never tripped the breaker" % fault_kind
                assert closed_again, (
                    "%s breaker never re-closed" % fault_kind
                )
                record["%s_requests" % fault_kind] = len(codes)
                record["%s_breaker_opens" % fault_kind] = (
                    crouter.replicas()[armed].breaker.stats()["opens"]
                )
                record["%s_client_errors" % fault_kind] = 0
                record["%s_breaker_reclosed" % fault_kind] = True
            finally:
                crouter.stop()
                for r in cr:
                    try:
                        r.kill()
                    except Exception:
                        pass
    finally:
        shutil.rmtree(work, ignore_errors=True)
    return record


def run_tracing_bench(smoke=False):
    """Tracing + flight-recorder evidence pass (ISSUE 19 -> TRACING.json).

    Two measurements:

    1. **Overhead**: sustained single-client load on an MLP through
       ServingEngine + ContinuousBatcher, tracing OFF vs ON (sample 1.0 —
       the worst case: every span exported). Acceptance: p99 with tracing
       on regresses <= 5% vs off (asserted in full mode; best-of-N rounds
       per config damp CPU scheduling noise).

    2. **Chaos propagation**: three replica ModelServer subprocesses behind
       the Router, all four processes tracing into ONE shared trace dir.
       One replica is armed with PADDLE_TPU_FAULTS=conn_reset (failed
       attempts + failover) and later SIGKILLed (breaker opens). Acceptance:
       served_fraction == 1.0; flight-recorder bundles exist whose span
       ring shows a failed router.attempt AND the successful failover
       under the SAME trace id; at least one trace's spans come from >= 3
       distinct OS processes (router + failed replica + winning replica);
       tools/timeline.py --trace_path and tools/trace_view.py both render
       the shards.
    """
    import shutil
    import tempfile
    import threading

    from paddle_tpu import flags as _flags
    from paddle_tpu import fluid
    from paddle_tpu import framework
    from paddle_tpu.executor import Scope, scope_guard
    from paddle_tpu.fleet import ReplicaProcess, Router
    from paddle_tpu.observability import flightrec as _flightrec
    from paddle_tpu.observability import tracing as _tracing
    from paddle_tpu.serving import ContinuousBatcher, ServingEngine

    work = tempfile.mkdtemp(prefix="tracing-bench-")
    cpu_env = _fleet_on_cpu()
    record = {"metric": "tracing", "mode": "smoke" if smoke else "full",
              "platform": "cpu"}
    old_flags = _flags.get_flags([
        "trace_dir", "flightrec_dir", "trace_sample", "flightrec_min_interval_s",
    ])

    def _save_mlp_inference(model_dir):
        # wide enough that a request carries real engine compute (~ms):
        # against a micro-model the bound would measure interpreter call
        # overhead per span vs a degenerate denominator no deployment has
        main_p, startup = framework.Program(), framework.Program()
        with fluid.unique_name.guard(), fluid.program_guard(main_p, startup):
            x = fluid.layers.data(name="tx", shape=[6], dtype="float32")
            h = fluid.layers.fc(input=x, size=64, act="relu")
            h = fluid.layers.fc(input=h, size=64, act="relu")
            y = fluid.layers.fc(input=h, size=3, act="softmax")
        exe = fluid.Executor(fluid.CPUPlace())
        with scope_guard(Scope(seed=3)):
            exe.run(startup)
            fluid.io.save_inference_model(
                model_dir, ["tx"], [y], exe, main_program=main_p
            )

    def _set_tracing(trace_dir, flightrec_dir=""):
        _flags.set_flags({
            "trace_dir": trace_dir, "flightrec_dir": flightrec_dir,
            "trace_sample": 1.0, "flightrec_min_interval_s": 0.1,
        })
        _tracing.reset()
        _flightrec.reset()

    try:
        model_dir = os.path.join(work, "model")
        _save_mlp_inference(model_dir)

        # ---- 1. overhead: p99 with tracing off vs on ----------------------
        n_requests = 200 if smoke else 800
        rounds = 1 if smoke else 5
        feed = {"tx": np.random.RandomState(7).rand(2, 6).astype("float32")}

        n_clients = 8

        def measure(trace_dir):
            # closed-loop concurrent clients — the shape the fleet actually
            # serves: per-batch spans (serving.batch, engine.execute) and
            # the segment serialization amortize across the co-batched
            # requests, exactly as they do behind the router
            _set_tracing(trace_dir)
            eng = ServingEngine(model_dir, name="tb",
                                batch_buckets=(1, 2, 4, 8, 16))
            b = ContinuousBatcher(eng, max_queue_rows=256,
                                  max_batch_delay_ms=1.0)
            lats = []
            lats_lock = threading.Lock()

            def client(n):
                mine = []
                for _ in range(n):
                    t0 = time.perf_counter()
                    b.run(dict(feed), timeout=30.0)
                    mine.append(time.perf_counter() - t0)
                with lats_lock:
                    lats.extend(mine)

            try:
                b.run(dict(feed), timeout=30.0)  # warmup/trace
                threads = [
                    threading.Thread(
                        target=client, args=(n_requests // n_clients,)
                    )
                    for _ in range(n_clients)
                ]
                for t in threads:
                    t.start()
                for t in threads:
                    t.join()
            finally:
                b.close()
            return lats

        def _p99(lats):
            lats = sorted(lats)
            return lats[min(int(len(lats) * 0.99), len(lats) - 1)] * 1e3

        # interleave configs so machine-load drift penalizes both equally
        # (all-off-then-all-on attributes any slow patch to "on"), and POOL
        # the rounds before taking p99: both configs then see the same noise
        # environment, instead of min-of-rounds rewarding one lucky round
        # interleaved off/on rounds so machine-load drift penalizes both
        # equally, gated on the MEDIAN of per-round p99s: robust to one
        # noisy round on either side (a single scheduler hiccup routinely
        # moves a round's p99 by 50% on a shared host), still a p99 bound
        # one discarded warmup pass per config: the first tracing-enabled
        # round pays one-time costs (shard dir, writer thread, code paths)
        # that are not steady-state overhead
        measure("")
        measure(os.path.join(work, "ovh-traces-warm"))
        rounds_off, rounds_on = [], []
        for i in range(rounds):
            rounds_off.append(round(_p99(measure("")), 3))
            rounds_on.append(round(
                _p99(measure(os.path.join(work, "ovh-traces-%d" % i))), 3
            ))
            print("  overhead round %d: p99 off=%.3fms on=%.3fms"
                  % (i, rounds_off[-1], rounds_on[-1]))
        p99_off = sorted(rounds_off)[len(rounds_off) // 2]
        p99_on = sorted(rounds_on)[len(rounds_on) // 2]
        record["p99_rounds_off"] = rounds_off
        record["p99_rounds_on"] = rounds_on
        overhead_pct = 100.0 * (p99_on - p99_off) / p99_off
        record.update({
            "p99_ms_tracing_off": round(p99_off, 3),
            "p99_ms_tracing_on": round(p99_on, 3),
            "overhead_pct": round(overhead_pct, 2),
        })
        if not smoke:
            assert p99_on <= p99_off * 1.05, (
                "tracing-on p99 %.3fms > 1.05x off p99 %.3fms"
                % (p99_on, p99_off)
            )

        # ---- 2. chaos propagation across real processes -------------------
        tdir = os.path.join(work, "traces")
        fdir = os.path.join(work, "flightrec")
        trace_env = {
            **cpu_env,
            "FLAGS_trace_dir": tdir,
            "FLAGS_flightrec_dir": fdir,
            "FLAGS_trace_sample": "1.0",
        }
        spec = lambda name: {
            "name": name,
            "request_timeout_ms": 10000.0,
            "predict": {"model": "m", "model_dir": model_dir},
        }
        reps = [
            ReplicaProcess(spec("tr0"), work, env=dict(trace_env),
                           faults="conn_reset:every=3"),
            ReplicaProcess(spec("tr1"), work, env=dict(trace_env)),
            ReplicaProcess(spec("tr2"), work, env=dict(trace_env)),
        ]
        _set_tracing(tdir, fdir)  # router traces + records in-process
        router = Router(
            port=0, hedge=False, probe_interval_s=0.2, down_after=2,
            total_deadline_s=20.0, attempt_timeout_s=8.0, seed=0,
            breaker_opts=dict(failure_threshold=2, error_rate_threshold=0.5,
                              min_requests=2, open_for_s=0.3,
                              success_threshold=1),
        )
        rport = router.start()
        codes = []
        try:
            for r in reps:
                r.start()
            for r in reps:
                r.wait_ready(timeout=300.0)
                router.register(r.name, r.url)
            router.probe_once()
            assert len(router.stats()["routable"]) == 3, router.stats()

            url = "http://127.0.0.1:%d/v1/models/m:predict" % rport
            doc = json.dumps({
                "inputs": {"tx": np.random.RandomState(1).rand(2, 6).tolist()}
            }).encode()

            import urllib.request

            def post():
                req = urllib.request.Request(
                    url, data=doc,
                    headers={"Content-Type": "application/json"},
                )
                with urllib.request.urlopen(req, timeout=30.0) as resp:
                    resp.read()
                    return resp.status

            n_round1 = 30 if smoke else 120
            n_round2 = 20 if smoke else 60
            for _ in range(n_round1):  # conn_reset round: failovers + breaker
                codes.append(post())
            reps[0].kill()             # SIGKILL round: DOWN + more failovers
            for _ in range(n_round2):
                codes.append(post())
        finally:
            router.stop()
            for r in reps:
                try:
                    r.kill()
                except Exception:
                    pass
            _tracing.reset()   # flush the router's shard
            _flightrec.reset()
            _flags.set_flags(old_flags)
            _tracing.reset()
            _flightrec.reset()

        served_fraction = sum(c == 200 for c in codes) / float(len(codes))
        assert served_fraction == 1.0, (
            "%d/%d served" % (sum(c == 200 for c in codes), len(codes))
        )

        spans = _tracing.load_spans(tdir)
        by_trace = {}
        for s in spans:
            by_trace.setdefault(s["trace"], []).append(s)
        # a failover trace: failed attempt + ok attempt, spans from >= 3 pids
        multi = None
        for tid, sp in by_trace.items():
            names = [s["name"] for s in sp]
            att = [s for s in sp if s["name"] == "router.attempt"]
            pids = {(s.get("host"), s.get("pid")) for s in sp}
            if (len(pids) >= 3 and "server.request" in names
                    and any(a["status"] == "error" for a in att)
                    and any(a["status"] == "ok" for a in att)):
                multi = (tid, sorted(str(p) for p in pids), len(sp))
                break
        assert multi is not None, (
            "no failover trace spanning >= 3 processes found "
            "(%d traces, %d spans)" % (len(by_trace), len(spans))
        )

        bundles = sorted(
            d for d in os.listdir(fdir) if d.startswith("bundle-")
        )
        assert bundles, "chaos run produced no flight-recorder bundles"
        reasons = {b.split("-")[2] for b in bundles}
        # a bundle whose span ring shows failed attempt + failover, same trace
        bundle_failover = False
        for b in bundles:
            ring = _tracing.load_spans(os.path.join(fdir, b, "spans.jsonl"))
            ring_tr = {}
            for s in ring:
                ring_tr.setdefault(s["trace"], []).append(s)
            for sp in ring_tr.values():
                att = [s for s in sp if s["name"] == "router.attempt"]
                if (any(a["status"] == "error" for a in att)
                        and any(a["status"] == "ok" for a in att)):
                    bundle_failover = True
                    break
            if bundle_failover:
                break
        assert bundle_failover, (
            "no bundle's span ring shows failed attempt + failover: %s"
            % bundles
        )

        # ---- render checks: timeline + trace_view over the shards ---------
        sys.path.insert(0, os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "tools"))
        import timeline as _timeline
        import trace_view as _trace_view

        tl_path = os.path.join(work, "timeline.json")
        n_events = _timeline.convert(
            "", tl_path, trace_path=tdir
        )
        assert n_events >= len(spans)
        assert _trace_view.main([tdir, "--top", "5"]) == 0
        assert _trace_view.main([tdir, "--trace", multi[0]]) == 0

        record.update({
            "requests": len(codes),
            "served_fraction": served_fraction,
            "traces": len(by_trace),
            "spans": len(spans),
            "failover_trace": multi[0],
            "failover_trace_processes": len(multi[1]),
            "failover_trace_spans": multi[2],
            "bundles": len(bundles),
            "bundle_reasons": sorted(reasons),
            "bundle_shows_failover": bundle_failover,
            "timeline_events": n_events,
        })
    finally:
        _flags.set_flags(old_flags)
        _tracing.reset()
        _flightrec.reset()
        shutil.rmtree(work, ignore_errors=True)
    return record


def run_slo_bench(smoke=False):
    """Fleet SLO-engine evidence pass (ISSUE 20 -> SLO.json).

    Five measurements:

    1. **Exactness**: ``promparse.parse(registry.to_prometheus()) ==
       registry.snapshot()`` for populated registries, and fleet p50/p99
       computed from the bucket-wise merge of three replicas' expositions
       are BIT-EQUAL to the percentiles of one pooled histogram that saw
       every raw observation (same grid, same interpolation arithmetic).
    2. **Steady state**: two clean replica subprocesses behind
       Router(fleet_metrics=True) with availability + latency SLOs on
       compressed burn-rate windows and all three sentinel kinds armed —
       ZERO alerts may fire, and the goodput gauge tracks the roofline
       measured during warmup (MFU-online ~ 1.0).
    3. **Chaos**: a pre-booted replica armed with
       PADDLE_TPU_FAULTS=slow_response (+400 ms per request, below the
       attempt timeout so availability stays clean while latency burns)
       is swapped IN for the clean pair — the "bad deploy rolled out"
       shape. The fast-burn page alert on the latency SLO must fire
       < 60 s after the swap, a matching ``slo_alert`` flight-recorder
       bundle (carrying the offending window's merged series) must land
       on disk, and the alert must RESOLVE after the clean pair is
       swapped back. tools/timeline.py renders the alert track.
    4. **Hot-swap drift**: an in-process LocalSampler + DriftSentinel over
       a serving latency histogram — a stationary phase fires nothing,
       then the engine is swapped for a much heavier model and the EWMA
       sentinel catches the regression no static threshold would.
    5. **Overhead**: router client p99 with the scrape+eval loop ON
       (aggressive 0.25 s interval, SLOs + sentinels evaluated every
       scrape) vs OFF — interleaved rounds, gated on the median of
       per-round p99s: on <= 1.05x off (asserted in full mode).
    """
    import glob
    import shutil
    import tempfile
    import threading
    import urllib.error
    import urllib.request

    from paddle_tpu import flags as _flags
    from paddle_tpu import fluid
    from paddle_tpu import framework
    from paddle_tpu.executor import Scope, scope_guard
    from paddle_tpu.fleet import ReplicaProcess, Router
    from paddle_tpu.observability import flightrec as _flightrec
    from paddle_tpu.observability import promparse
    from paddle_tpu.observability import registry as _obsreg
    from paddle_tpu.observability.aggregate import (
        hist_percentile,
        merge_snapshots,
    )
    from paddle_tpu.observability.slo import (
        SLO,
        AlertEngine,
        BurnRateRule,
        DriftSentinel,
        GoodputSentinel,
        LocalSampler,
        RetraceSentinel,
    )
    from paddle_tpu.serving import ServingEngine

    work = tempfile.mkdtemp(prefix="slo-bench-")
    cpu_env = _fleet_on_cpu()
    record = {"metric": "slo", "mode": "smoke" if smoke else "full",
              "platform": "cpu"}
    old_flags = _flags.get_flags([
        "flightrec_dir", "flightrec_min_interval_s",
    ])

    # ---- 1. exposition round trip + merged-percentile bit-equality --------
    rng = np.random.RandomState(11)
    regs = [_obsreg.MetricRegistry() for _ in range(3)]
    pooled = _obsreg.MetricRegistry().histogram(
        "serving/latency_ms", "pooled reference: every raw observation"
    )
    for i, reg in enumerate(regs):
        reg.counter("fleet/requests", "routed").inc(
            7 * (i + 1), kind="predict", code="200"
        )
        h = reg.histogram("serving/latency_ms", "per-replica latency")
        for v in rng.gamma(2.0, 30.0, size=300 + 131 * i):
            h.observe(float(v))
            pooled.observe(float(v))
    parsed = [("rep%d" % i, promparse.parse(reg.to_prometheus()))
              for i, reg in enumerate(regs)]
    roundtrip = all(
        snap == regs[i].snapshot() for i, (_, snap) in enumerate(parsed)
    )
    fleet_rec = merge_snapshots(parsed)["serving/latency_ms"]
    pcts = {
        "p50": (hist_percentile(fleet_rec, 50), pooled.percentile(50)),
        "p99": (hist_percentile(fleet_rec, 99), pooled.percentile(99)),
    }
    merge_exact = all(a == b for a, b in pcts.values())
    record["roundtrip_exact"] = bool(roundtrip)
    record["merged_p99_bit_equal"] = bool(merge_exact)
    record["merged_vs_pooled"] = {
        k: {"merged": a, "pooled": b} for k, (a, b) in pcts.items()
    }
    # pure arithmetic, deterministic: asserted in smoke too
    assert roundtrip, "parse(to_prometheus()) != snapshot()"
    assert merge_exact, "merged percentiles not bit-equal: %r" % pcts

    def _save_mlp_inference(model_dir):
        main_p, startup = framework.Program(), framework.Program()
        with fluid.unique_name.guard(), fluid.program_guard(main_p, startup):
            x = fluid.layers.data(name="fx", shape=[6], dtype="float32")
            h = fluid.layers.fc(input=x, size=8, act="relu")
            y = fluid.layers.fc(input=h, size=3, act="softmax")
        exe = fluid.Executor(fluid.CPUPlace())
        with scope_guard(Scope(seed=3)):
            exe.run(startup)
            fluid.io.save_inference_model(
                model_dir, ["fx"], [y], exe, main_program=main_p
            )

    model_dir = os.path.join(work, "model")
    _save_mlp_inference(model_dir)

    # predict-only replicas: the SLO rounds exercise the scrape/alert
    # plane, not the engines, so the smallest servable model does
    def _spec(name):
        return {
            "name": name,
            "request_timeout_ms": 10000.0,
            "predict": {"model": "m", "model_dir": model_dir},
            "poll_interval_s": 0.1,
        }

    p_doc = json.dumps({
        "inputs": {"fx": np.random.RandomState(9).rand(2, 6).tolist()}
    }).encode()

    def _post(url, body, timeout=30.0):
        req = urllib.request.Request(
            url, data=body, headers={"Content-Type": "application/json"}
        )
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, json.loads(resp.read().decode())

    def _p99(vals):
        vals = sorted(vals)
        return vals[min(int(len(vals) * 0.99), len(vals) - 1)] * 1e3

    # compressed SRE-workbook rules: same two-window/two-burn structure,
    # seconds instead of hours, so one bench round watches a full
    # fire -> resolve cycle (DEFAULT_RULES would need 5m of history)
    def _rules():
        return [
            BurnRateRule("page", 4.0, 12.0, 8.0),
            BurnRateRule("ticket", 8.0, 24.0, 4.0),
        ]

    def _slos():
        return [
            SLO("availability", 0.999, counter="fleet/requests",
                bad={"code": "5"}, min_events=8,
                description="non-5xx fraction of routed requests"),
            SLO("latency", 0.99, histogram="fleet/request_ms",
                threshold_ms=100.0, min_events=8,
                description="routed requests under 100 ms"),
        ]

    def _sentinels():
        return [
            DriftSentinel("fleet_latency_drift", "fleet/request_ms",
                          warmup=10, rel_threshold=2.0),
            RetraceSentinel(steady_ticks=8),
        ]

    warm_s = 3.0 if smoke else 5.0
    steady_s = 6.0 if smoke else 15.0
    fdir = os.path.join(work, "flightrec")
    alerts_path = os.path.join(work, "alerts.jsonl")
    _flags.set_flags({
        # min_interval 0: drift + page alerts can fire on the SAME
        # evaluate tick and each must still get its bundle
        "flightrec_dir": fdir, "flightrec_min_interval_s": 0.0,
    })
    _flightrec.reset()

    reps = []
    router = None
    stop = threading.Event()
    threads = []
    try:
        # ---- 2+3. live fleet: steady state, then slow_response chaos ------
        clean = [ReplicaProcess(_spec("sr%d" % i), work, env=cpu_env)
                 for i in range(2)]
        slow = ReplicaProcess(
            _spec("sr_slow"), work, env=cpu_env,
            faults="slow_response:every=1@ms=400",
        )
        reps = clean + [slow]
        for r in reps:  # the slow one boots NOW so the chaos swap is instant
            r.start()
        router = Router(
            port=0, hedge=False, probe_interval_s=0.2, down_after=2,
            total_deadline_s=20.0, attempt_timeout_s=8.0, seed=0,
            fleet_metrics=True, scrape_interval_s=0.4,
            slos=_slos(), sentinels=_sentinels(), alert_rules=_rules(),
            alerts_path=alerts_path,
        )
        base = "http://127.0.0.1:%d" % router.start()
        engine = router.alert_engine
        for r in clean:
            r.wait_ready(timeout=300.0)
            router.register(r.name, r.url)
        router.probe_once()
        assert len(router.stats()["routable"]) == 2, router.stats()

        phase = ["warmup"]
        samples = []  # (phase, latency_s, code-or-repr)
        lock = threading.Lock()

        def client():
            url = base + "/v1/models/m:predict"
            while not stop.is_set():
                t0 = time.perf_counter()
                try:
                    code, _ = _post(url, p_doc)
                except Exception as e:  # noqa: BLE001 - tallied, not fatal
                    code = repr(e)
                with lock:
                    samples.append((phase[0], time.perf_counter() - t0, code))

        threads = [threading.Thread(target=client, daemon=True)
                   for _ in range(6)]
        for t in threads:
            t.start()

        t0 = time.time()
        time.sleep(warm_s)
        with lock:
            n_warm = sum(1 for p, _, _ in samples if p == "warmup")
        roofline_rps = n_warm / (time.time() - t0)
        # each predict carries 2 rows -> roofline in rows/s, fed live into
        # slo/goodput_per_s + slo/goodput_vs_roofline (MFU-online)
        goodput = engine.add_sentinel(GoodputSentinel(
            "fleet_goodput", "fleet/requests",
            roofline_per_s=roofline_rps * 2.0, unit="rows", scale=2.0,
        ))

        phase[0] = "steady"
        ev_mark = len(engine.events)
        time.sleep(steady_s)
        steady_fired = [
            e for e in engine.events[ev_mark:] if e.state == "firing"
        ]
        with lock:
            n_steady = sum(1 for p, _, _ in samples if p == "steady")
        record["steady"] = {
            "duration_s": steady_s,
            "requests": n_steady,
            "alerts_fired": len(steady_fired),
            "roofline_rows_per_s": round(roofline_rps * 2.0, 1),
            "goodput_rows_per_s": goodput.last_per_s,
            "goodput_vs_roofline": goodput.last_frac,
        }
        assert not steady_fired, (
            "false alert(s) in steady state: %s"
            % [e.to_dict() for e in steady_fired]
        )

        # chaos: swap the slow replica IN for the clean pair — every
        # request now pays +400 ms (still < attempt timeout: no failover,
        # no 5xx — availability holds while the latency SLO burns)
        slow.wait_ready(timeout=300.0)
        phase[0] = "chaos"
        router.register(slow.name, slow.url)
        router.probe_once()
        for r in clean:
            router.deregister(r.name)
        t_chaos = time.time()

        fired_ev = None
        deadline = t_chaos + 60.0
        while time.time() < deadline and fired_ev is None:
            fired_ev = next(
                (e for e in list(engine.events)
                 if e.name == "latency" and e.severity == "page"
                 and e.state == "firing" and e.ts >= t_chaos), None)
            time.sleep(0.2)
        fired_after = None if fired_ev is None else fired_ev.ts - t_chaos
        goodput_chaos = goodput.last_frac  # read mid-chaos, before recovery

        # clear the fault: clean pair back in, slow replica out
        phase[0] = "clear"
        for r in clean:
            router.register(r.name, r.url)
        router.probe_once()
        router.deregister(slow.name)
        t_clear = time.time()
        resolved_ev = None
        deadline = t_clear + 90.0
        while time.time() < deadline and resolved_ev is None:
            resolved_ev = next(
                (e for e in list(engine.events)
                 if e.name == "latency" and e.severity == "page"
                 and e.state == "resolved" and e.ts >= t_clear), None)
            time.sleep(0.2)

        stop.set()
        for t in threads:
            t.join(timeout=30.0)

        bundles = sorted(glob.glob(os.path.join(fdir, "bundle-*")))
        page_bundle = None
        for b in bundles:
            if "-slo_alert-" not in os.path.basename(b):
                continue
            with open(os.path.join(b, "event.json")) as f:
                ev = json.load(f)
            info = ev.get("info", {})
            if info.get("name") == "latency" and info.get("series"):
                page_bundle = os.path.basename(b)
        drift_fired = any(
            e.name == "fleet_latency_drift" and e.state == "firing"
            for e in engine.events
        )
        with lock:
            chaos_lat = [s for p, s, c in samples if p == "chaos" and c == 200]
            err_5xx = sum(
                1 for _, _, c in samples
                if (isinstance(c, int) and c >= 500)
                or (not isinstance(c, int))
            )
        record["chaos"] = {
            "fired": fired_ev is not None,
            "fired_after_s": None if fired_after is None
            else round(fired_after, 2),
            "resolved": resolved_ev is not None,
            "resolved_after_s": None if resolved_ev is None
            else round(resolved_ev.ts - t_clear, 2),
            "chaos_p99_ms": round(_p99(chaos_lat), 1) if chaos_lat else None,
            "errors_5xx": err_5xx,
            "drift_sentinel_also_fired": drift_fired,
            "goodput_vs_roofline_during_chaos": goodput_chaos,
            "slo_alert_bundle": page_bundle,
            "alert_log_lines": sum(1 for _ in open(alerts_path))
            if os.path.exists(alerts_path) else 0,
        }
        assert fired_ev is not None and fired_after < 60.0, (
            "fast-burn latency page did not fire within 60s: %s"
            % record["chaos"]
        )
        assert resolved_ev is not None, (
            "latency page never resolved after the fault cleared"
        )
        assert page_bundle is not None, (
            "no slo_alert flight-recorder bundle with the merged series: %s"
            % bundles
        )

        # render check: the alert fire/resolve pairs become a chrome-trace
        # track (satellite: tools/timeline.py --alerts_path)
        sys.path.insert(0, os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "tools"))
        import timeline as _timeline

        tl_path = os.path.join(work, "timeline.json")
        n_tl = _timeline.convert("", tl_path, alerts_path=alerts_path)
        record["chaos"]["timeline_events"] = n_tl
        assert n_tl >= 1, "timeline rendered no alert events"

        router.stop()
        router = None
        reps.pop().terminate()  # the clean pair stays up for round 5

        # ---- 4. hot-swap latency drift (in-process) -----------------------
        sreg = _obsreg.MetricRegistry()
        shist = sreg.histogram("serving/swap_latency_ms", "client latency")
        sampler = LocalSampler(sreg)
        deng = AlertEngine(slos=(), history=sampler, rules=(),
                           registry=sreg, log_stderr=False, flightrec=False)
        # detection threshold 2x (rel 1.0): the swapped-in model is ~5x
        # heavier, so detection keeps headroom, while a scheduler hiccup
        # inside a 12-sample tick mean can't move the fast EWMA past 2x
        # the baseline (the 0.6 threshold false-fired on a shared host)
        drift = deng.add_sentinel(DriftSentinel(
            "hot_swap_drift", "serving/swap_latency_ms",
            warmup=6, rel_threshold=1.0, min_count=2,
        ))

        def _save_wide(out_dir, layers, width):
            main_p, startup = framework.Program(), framework.Program()
            with fluid.unique_name.guard(), \
                    fluid.program_guard(main_p, startup):
                x = fluid.layers.data(name="fx", shape=[6], dtype="float32")
                hh = x
                for _ in range(layers):
                    hh = fluid.layers.fc(input=hh, size=width, act="relu")
                y = fluid.layers.fc(input=hh, size=3, act="softmax")
            exe = fluid.Executor(fluid.CPUPlace())
            with scope_guard(Scope(seed=4)):
                exe.run(startup)
                fluid.io.save_inference_model(
                    out_dir, ["fx"], [y], exe, main_program=main_p
                )

        # baseline ~1.3 ms/call (6x1024): heavy enough that tick means on
        # a shared host stay within ~1.5x (a 2x64 micro-model's means
        # swing 5x on dispatch noise alone and false-fire any threshold
        # that could still catch a real swap); the "bad hot swap" lands an
        # 8x2048 stack in its place, ~5x slower per call
        small_dir = os.path.join(work, "model_small")
        big_dir = os.path.join(work, "model_big")
        _save_wide(small_dir, 6, 1024)
        _save_wide(big_dir, 8, 2048)
        small = ServingEngine(small_dir, name="dm", batch_buckets=(2,))
        big = ServingEngine(big_dir, name="dm_big", batch_buckets=(2,))
        feed = {"fx": np.random.RandomState(5).rand(2, 6).astype("float32")}
        for eng in (small, big):  # compile outside the measured stream
            eng.run(dict(feed))

        n_ticks = 20 if smoke else 40
        false_pos = 0

        def _tick(eng):
            for _ in range(12):
                tq = time.perf_counter()
                eng.run(dict(feed))
                shist.observe((time.perf_counter() - tq) * 1e3)
            sampler.sample()
            return deng.evaluate()

        for _ in range(n_ticks):  # stationary: must stay quiet
            false_pos += sum(1 for e in _tick(small) if e.state == "firing")
        detect_tick = None
        for i in range(n_ticks):  # hot swap to the heavier engine
            if any(e.state == "firing" for e in _tick(big)):
                detect_tick = i + 1
                break
        record["drift"] = {
            "stationary_false_positives": false_pos,
            "detected": detect_tick is not None,
            "ticks_to_detect": detect_tick,
            "fast_over_slow": None if drift._fast is None or not drift._slow
            else round(drift._fast / drift._slow, 2),
        }
        assert false_pos == 0, "drift sentinel fired on a stationary stream"
        if not smoke:
            assert detect_tick is not None, "hot-swap regression undetected"

        # ---- 5. scrape+eval overhead on router p99 ------------------------
        n_requests = 240 if smoke else 720
        rounds = 1 if smoke else 5
        n_clients = 6

        def measure(slo_on):
            kw = {}
            if slo_on:
                kw = dict(fleet_metrics=True, scrape_interval_s=0.25,
                          slos=_slos(), sentinels=_sentinels(),
                          alert_rules=_rules())
            r2 = Router(port=0, hedge=False, probe_interval_s=0.5,
                        total_deadline_s=20.0, attempt_timeout_s=8.0,
                        seed=0, **kw)
            b2 = "http://127.0.0.1:%d" % r2.start()
            for r in reps:
                r2.register(r.name, r.url)
            r2.probe_once()
            lats = []
            llock = threading.Lock()

            def cl(n):
                mine = []
                for _ in range(n):
                    tq = time.perf_counter()
                    _post(b2 + "/v1/models/m:predict", p_doc)
                    mine.append(time.perf_counter() - tq)
                with llock:
                    lats.extend(mine)

            try:
                _post(b2 + "/v1/models/m:predict", p_doc)  # warm the path
                ts = [threading.Thread(target=cl,
                                       args=(n_requests // n_clients,))
                      for _ in range(n_clients)]
                for t in ts:
                    t.start()
                for t in ts:
                    t.join()
            finally:
                r2.stop()
            return lats

        # one discarded pass per config, then interleaved rounds gated on
        # the MEDIAN of per-round p99s (same rationale as the tracing
        # bench: drift penalizes both configs equally, one noisy round on
        # a shared host can't decide the gate)
        measure(False)
        measure(True)
        rounds_off, rounds_on = [], []
        for i in range(rounds):
            rounds_off.append(round(_p99(measure(False)), 3))
            rounds_on.append(round(_p99(measure(True)), 3))
            print("  slo overhead round %d: p99 off=%.3fms on=%.3fms"
                  % (i, rounds_off[-1], rounds_on[-1]))
        p99_off = sorted(rounds_off)[len(rounds_off) // 2]
        p99_on = sorted(rounds_on)[len(rounds_on) // 2]
        record.update({
            "p99_rounds_off": rounds_off,
            "p99_rounds_on": rounds_on,
            "p99_ms_slo_off": round(p99_off, 3),
            "p99_ms_slo_on": round(p99_on, 3),
            "overhead_pct": round(100.0 * (p99_on - p99_off) / p99_off, 2),
        })
        if not smoke:
            assert p99_on <= p99_off * 1.05, (
                "scrape+eval p99 %.3fms > 1.05x off p99 %.3fms"
                % (p99_on, p99_off)
            )
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=10.0)
        if router is not None:
            router.stop()
        for r in reps:
            try:
                r.terminate()
            except Exception:
                pass
        _flags.set_flags(old_flags)
        _flightrec.reset()
        shutil.rmtree(work, ignore_errors=True)
    return record


def run_recovery_bench(smoke=False):
    """Elastic-recovery evidence pass (ISSUE 9 -> RECOVERY.json).

    Three measurements on one machine:
      1. checkpoint step stall, sync vs async, at EQUAL state size: a
         synchronous `checkpoint.save_checkpoint` stalls the step for the
         full serialize+hash+fsync; `AsyncCheckpointer.save` stalls only for
         the device->host snapshot. Acceptance: async <= 20% of sync.
      2. time-to-recover: wall time of `Supervisor.resume_or_init` on a cold
         scope (startup + manifest read + shard reassembly + overlay).
      3. steps lost to a simulated preemption at `killed_at_step` with
         `ckpt_every` checkpoint cadence, plus a bit-exactness check that
         the resumed trajectory equals the uninterrupted one.
    """
    import shutil
    import tempfile

    import jax.numpy as jnp

    import paddle_tpu.fluid as fluid
    from paddle_tpu import framework
    from paddle_tpu.executor import Scope, scope_guard
    from paddle_tpu.resilience import (
        AsyncCheckpointer, Supervisor, checkpoint as rckpt,
    )

    # --- 1. stall comparison at equal state size -------------------------
    state_mb = 8 if smoke else 64
    n_arrays = 8
    rows = (state_mb << 20) // n_arrays // (64 * 4)
    rng = np.random.RandomState(0)
    # device arrays: the async save's stall IS the device->host copy
    state = {
        "p%02d" % i: jnp.asarray(rng.randn(rows, 64).astype(np.float32))
        for i in range(n_arrays)
    }
    repeats = 3 if smoke else 5
    tmp = tempfile.mkdtemp(prefix="recovery-bench-")
    sync_ms, async_ms, commit_ms = [], [], []
    try:
        cp = AsyncCheckpointer(os.path.join(tmp, "async"), keep_last=2)
        for r in range(repeats):
            t0 = time.perf_counter()
            rckpt.save_checkpoint(os.path.join(tmp, "sync"), state, r,
                                  keep_last=2)
            sync_ms.append((time.perf_counter() - t0) * 1e3)
            t0 = time.perf_counter()
            stall = cp.save(state, r)
            async_ms.append(stall * 1e3)
            cp.wait()  # commit latency is background, measured separately
            commit_ms.append((time.perf_counter() - t0) * 1e3)
        cp.close()
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    med = lambda xs: sorted(xs)[len(xs) // 2]  # noqa: E731

    # --- 2+3. preemption -> resume on a tiny supervised trainer ----------
    def _mlp():
        main, startup = framework.Program(), framework.Program()
        with fluid.unique_name.guard(), fluid.program_guard(main, startup):
            x = fluid.layers.data(name="x", shape=[8], dtype="float32")
            y = fluid.layers.data(name="y", shape=[1], dtype="float32")
            h = fluid.layers.fc(input=x, size=16, act="relu")
            p = fluid.layers.fc(input=h, size=1)
            loss = fluid.layers.mean(fluid.layers.square_error_cost(p, y))
            fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
        return main, startup, loss

    def _feed(step):
        r = np.random.RandomState(step)
        x = r.randn(16, 8).astype(np.float32)
        return {"x": x,
                "y": np.abs(x).sum(axis=1, keepdims=True).astype(np.float32)}

    ckpt_every, killed_at, total = 5, 17, 20
    root = tempfile.mkdtemp(prefix="recovery-train-")
    try:
        def train(ckpt_root, upto, every):
            main, startup, loss = _mlp()
            with scope_guard(Scope(seed=1)):
                exe = fluid.Executor()
                sup = Supervisor(exe, ckpt_root, program=main,
                                 ckpt_every=every)
                start, _ = sup.resume_or_init(startup)
                out = {}
                with sup:
                    for s in range(start, upto):
                        (lv,) = sup.run_step(program=main, feed=_feed(s),
                                             fetch_list=[loss])
                        out[s] = float(np.asarray(lv).ravel()[0])
                    sup.checkpointer.wait()
                return out, start

        golden, _ = train(os.path.join(root, "golden"), total, 0)
        eroot = os.path.join(root, "elastic")
        train(eroot, killed_at, ckpt_every)  # "preempted" here: no final save

        main, startup, loss = _mlp()
        with scope_guard(Scope(seed=2)):
            exe = fluid.Executor()
            sup = Supervisor(exe, eroot, program=main, ckpt_every=0)
            t0 = time.perf_counter()
            resumed_step, _cursor = sup.resume_or_init(startup)
            recover_s = time.perf_counter() - t0
        cont, start = train(eroot, total, 0)
        bit_exact = all(cont[s] == golden[s] for s in range(start, total))
    finally:
        shutil.rmtree(root, ignore_errors=True)

    return {
        "metric": "elastic_recovery",
        "mode": "smoke" if smoke else "full",
        "state_mb": state_mb,
        "repeats": repeats,
        "sync_save_stall_ms": round(med(sync_ms), 2),
        "async_save_stall_ms": round(med(async_ms), 2),
        "async_commit_ms": round(med(commit_ms), 2),
        # the acceptance ratio: step-visible stall, async vs sync
        "async_stall_frac_of_sync": round(med(async_ms) / med(sync_ms), 4),
        "ckpt_every": ckpt_every,
        "killed_at_step": killed_at,
        "resumed_step": resumed_step,
        "steps_lost": killed_at - resumed_step,
        "time_to_recover_s": round(recover_s, 3),
        "resume_bit_exact": bool(bit_exact),
    }


def main():
    if len(sys.argv) > 1 and sys.argv[1] == "fleet":
        # fleet chaos soak (PR 16): 3 replica subprocesses behind the
        # health-aware Router under mixed predict/generate load — SIGKILL +
        # ack-gated rejoin mid-run, then conn_reset and slow_response rounds
        # proving the breaker opens and re-closes with zero client-visible
        # errors; writes FLEET.json next to this file ("smoke" shrinks the
        # soak, skips the tracked file)
        smoke = "smoke" in sys.argv[2:]
        rec = run_fleet_bench(smoke=smoke)
        if not smoke:
            out = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "FLEET.json")
            with open(out, "w") as f:
                json.dump(rec, f, indent=1)
        print(json.dumps(rec, indent=1))
        return
    if len(sys.argv) > 1 and sys.argv[1] == "tracing":
        # tracing + flight-recorder evidence pass (ISSUE 19): serving p99
        # with tracing on <= 1.05x off; a 3-replica chaos run (conn_reset +
        # SIGKILL) with served_fraction 1.0 whose trace shards carry one
        # failover trace across >= 3 OS processes and whose bundles show
        # the failed attempt + retry; writes TRACING.json next to this
        # file ("smoke" shrinks the run, skips the tracked file)
        smoke = "smoke" in sys.argv[2:]
        rec = run_tracing_bench(smoke=smoke)
        if not smoke:
            out = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "TRACING.json")
            with open(out, "w") as f:
                json.dump(rec, f, indent=1)
        print(json.dumps(rec, indent=1))
        return
    if len(sys.argv) > 1 and sys.argv[1] == "slo":
        # fleet SLO-engine evidence pass (ISSUE 20): exposition round-trip
        # + merged-percentile bit-equality, a steady-state round with zero
        # false alerts, a slow_response chaos round whose fast-burn latency
        # page fires < 60s and resolves after the fault clears (with the
        # slo_alert flight-recorder bundle), hot-swap drift detection, and
        # the scrape+eval overhead gate on router p99; writes SLO.json next
        # to this file ("smoke" shrinks the rounds, skips the tracked file)
        smoke = "smoke" in sys.argv[2:]
        rec = run_slo_bench(smoke=smoke)
        if not smoke:
            out = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "SLO.json")
            with open(out, "w") as f:
                json.dump(rec, f, indent=1)
        print(json.dumps(rec, indent=1))
        return
    if len(sys.argv) > 1 and sys.argv[1] == "recovery":
        # elastic-recovery evidence pass (ISSUE 9): async-checkpoint stall
        # vs synchronous save at equal state size (target <= 0.20),
        # time-to-recover, steps lost to a preemption; writes RECOVERY.json
        # next to this file ("smoke" shrinks sizes, skips the tracked file)
        smoke = "smoke" in sys.argv[2:]
        rec = run_recovery_bench(smoke=smoke)
        if not smoke:
            out = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "RECOVERY.json")
            with open(out, "w") as f:
                json.dump(rec, f, indent=1)
        print(json.dumps(rec, indent=1))
        return
    if len(sys.argv) > 1 and sys.argv[1] == "online":
        # online-learning evidence pass (ISSUE 15): streaming DeepFM trainer
        # publishing base+delta versions while a ModelServer hot-swaps them
        # under client load — zero 5xx, bounded staleness, offline bit-
        # parity; writes ONLINE.json next to this file ("smoke" shrinks the
        # run, skips the tracked file)
        smoke = "smoke" in sys.argv[2:]
        rec = run_online_bench(smoke=smoke)
        if not smoke:
            out = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "ONLINE.json")
            with open(out, "w") as f:
                json.dump(rec, f, indent=1)
        print(json.dumps(rec, indent=1))
        return
    if len(sys.argv) > 1 and sys.argv[1] == "recsys":
        # sparse-embedding-engine evidence pass (PR 8): writes
        # BENCH_recsys.json next to this file; "smoke" keeps sizes CPU-CI
        # friendly and skips the tracked-metric file
        smoke = "smoke" in sys.argv[2:]
        rec = run_recsys_bench(smoke=smoke)
        if not smoke:
            out = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "BENCH_recsys.json")
            with open(out, "w") as f:
                json.dump(rec, f, indent=1)
        print(json.dumps(rec, indent=1))
        return
    if len(sys.argv) > 1 and sys.argv[1] == "reader":
        # reader-pipeline evidence pass (ISSUE 7): uncached uint8-image and
        # token paths with and without the native data runtime; "smoke"
        # keeps sizes CPU-CI friendly and skips the tracked-metric file
        smoke = "smoke" in sys.argv[2:]
        rec = run_reader_bench(smoke=smoke)
        if not smoke:
            out = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "BENCH_reader.json")
            with open(out, "w") as f:
                json.dump(rec, f, indent=1)
        print(json.dumps(rec))
        return
    if len(sys.argv) > 1 and sys.argv[1] == "passes":
        # pass-framework evidence (ISSUE 10): pipeline off vs the
        # training_default preset on LeNet + tiny transformer — step time,
        # op/HLO counts, fold/DCE/fusion payloads, loss-parity delta; writes
        # PASSES.json next to this file ("smoke" shrinks steps, skips the
        # tracked file)
        smoke = "smoke" in sys.argv[2:]
        rec = run_passes_bench(smoke=smoke)
        if not smoke:
            out = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "PASSES.json")
            with open(out, "w") as f:
                json.dump(rec, f, indent=1)
        print(json.dumps(rec, indent=1))
        return
    if len(sys.argv) > 1 and sys.argv[1] == "quant":
        # quantization evidence pass (ISSUE 18): calibrated-int8 zoo top-1
        # vs fp32, v5e-roofline single-shot speedup, kv-int8 2x-slots
        # generation entry, fp8 training-matmul step time; writes QUANT.json
        # next to this file ("smoke" shrinks sizes, skips the tracked file)
        smoke = "smoke" in sys.argv[2:]
        rec = run_quant_bench(smoke=smoke)
        if not smoke:
            out = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "QUANT.json")
            with open(out, "w") as f:
                json.dump(rec, f, indent=1)
        print(json.dumps(rec, indent=1))
        return
    if len(sys.argv) > 1 and sys.argv[1] == "mfu_audit":
        # per-HLO MFU gap audit with the HBM memcpy microbench grounding the
        # memory roofline in measured bandwidth (tools/mfu_audit.py; ISSUE
        # 11 satellite). All trailing args pass through, e.g.:
        #   python bench.py mfu_audit transformer --pass-pipeline
        #   training_fused --probe
        import importlib.util as _ilu

        spec = _ilu.spec_from_file_location(
            "mfu_audit",
            os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "tools", "mfu_audit.py"),
        )
        mod = _ilu.module_from_spec(spec)
        spec.loader.exec_module(mod)
        mod.main(sys.argv[2:])
        return
    if len(sys.argv) > 1 and sys.argv[1] == "generation":
        # autoregressive-serving evidence pass (ISSUE 12): Poisson
        # mixed-length load through the token-level continuous scheduler vs
        # the naive whole-sequence ablation; writes GENSERVE.json next to
        # this file ("smoke" shrinks the model/load, skips the tracked file)
        smoke = "smoke" in sys.argv[2:]
        rec = run_generation_bench(smoke=smoke)
        if not smoke:
            out = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "GENSERVE.json")
            with open(out, "w") as f:
                json.dump(rec, f, indent=1)
        print(json.dumps(rec, indent=1))
        return
    if len(sys.argv) > 1 and sys.argv[1] == "serving":
        # serving-runtime evidence pass (scripts/build_and_test.sh): writes
        # SERVING.json next to this file
        rec = run_serving_bench()
        out = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "SERVING.json")
        with open(out, "w") as f:
            json.dump(rec, f, indent=1)
        print(json.dumps(rec, indent=1))
        return
    if len(sys.argv) > 1 and sys.argv[1] == "sharding":
        # sharding-rule engine evidence pass (PR 13): tp x fsdp vs
        # dp-replicated — per-chip param+state bytes, step time, loss
        # parity, paper-size HBM projection; writes MULTICHIP_SHARDING.json
        # next to this file ("smoke" shrinks sizes, skips the tracked file)
        smoke = "smoke" in sys.argv[2:]
        rec = run_sharding_bench(smoke=smoke)
        if rec is None:
            raise SystemExit("sharding bench needs an 8-device mesh")
        if not smoke:
            out = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "MULTICHIP_SHARDING.json")
            with open(out, "w") as f:
                json.dump(rec, f, indent=1)
        print(json.dumps(rec, indent=1))
        return
    if len(sys.argv) > 1 and sys.argv[1] == "pp":
        # standalone pp-bubble evidence pass (scripts/build_and_test.sh):
        # writes MULTICHIP_PP.json next to this file
        rec = run_pp_bench()
        if rec is None:
            raise SystemExit("pp bench needs a dp*pp-device mesh")
        out = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "MULTICHIP_PP.json")
        with open(out, "w") as f:
            json.dump(rec, f, indent=1)
        print(json.dumps(rec, indent=1))
        return
    # default mode: the training passes, measured on the chip. A run on any
    # other backend measures XLA's CPU compiler and the Pallas interpreter,
    # so it is refused here — in main(), because scripts/build_and_test.sh
    # and tools/ import this module's functions on the CPU.
    dev = device_record()
    if dev["platform"] != "tpu":
        raise SystemExit(
            "bench.py measures on a TPU and JAX found none: its default "
            "backend here is %r (%s)" % (dev["platform"], dev["device_kind"])
        )
    peak_tflops = device_peaks(dev["device_kind"])["bf16_tflops"]
    batch_size = int(sys.argv[1]) if len(sys.argv) > 1 else 256
    failed = []
    record = {
        "metric": "resnet50_train_images_per_sec_per_chip",
        "value": None,
        "unit": "images/sec",
    }
    record.update(dev)
    record["peak_bf16_tflops"] = peak_tflops

    res = _attempt(failed, "resnet50", run, batch_size=batch_size, failed=failed)
    if res is not None:
        ips, single_ips, pyreader_ips, pyreader_u8_ips = res
        # headline = the faster of single-dispatch and multi-step: round 4
        # showed the unconditional multi-step headline can sit BELOW the
        # same run's single-dispatch measurement
        headline = max(ips or 0.0, single_ips)
        record["value"] = round(headline, 2)
        record["vs_baseline"] = round(headline / BASELINE_IMAGES_PER_SEC, 2)
        # one dispatch per step vs the k-step scan (the delta IS the
        # measured per-call dispatch cost, either sign)
        record["resnet50_singledispatch_images_per_sec"] = round(single_ips, 2)
        if ips:
            record["resnet50_multistep_images_per_sec"] = round(ips, 2)
        # input-pipeline evidence: PyReader-fed throughput as a fraction of
        # the SINGLE-dispatch staged ceiling (target >=0.95 — async staging
        # overlaps the host->device transfer with compute). The pyreader
        # passes run one dispatch per step, so dividing by the multi-step
        # number would misattribute dispatch overhead to the pipeline. An
        # f32 bs=256 image batch is 154 MB a step; uint8 wire cuts it 4x.
        if pyreader_ips:
            record["pyreader_images_per_sec"] = round(pyreader_ips, 2)
            record["pyreader_frac"] = round(pyreader_ips / single_ips, 3)
        if pyreader_u8_ips:
            record["pyreader_uint8_images_per_sec"] = round(pyreader_u8_ips, 2)
            record["pyreader_frac_uint8"] = round(pyreader_u8_ips / single_ips, 3)

    # headline MFU config: bf16-stored Adam moments (f32 compute) — the
    # TPU-native training configuration (convergence-tested,
    # tests/test_ops_optimizers.py) which halves optimizer-state memory
    # and its share of the dW-fusion HBM traffic (PROFILE.md audit) —
    # under the training_fused preset (Pallas GEMM-epilogue /
    # layer_norm / multi-tensor-Adam substitution, docs/passes.md)
    mfu = _attempt(failed, "transformer_fused", run_transformer_mfu,
                   pass_pipeline="training_fused")
    if mfu is not None:
        tfs = mfu["tflops_min_window"]
        record["transformer_tflops_per_sec"] = round(tfs, 1)
        record["transformer_mfu_vs_nominal_peak"] = round(tfs / peak_tflops, 3)
        # estimator audit trail: the median and every window time (min far
        # below median = suspect headline; see run_transformer_mfu)
        record["transformer_tflops_median_window"] = round(
            mfu["tflops_median_window"], 1
        )
        record["transformer_window_ms_per_step"] = mfu["window_ms_per_step"]
    # kernel-substitution ablation: the SAME step with the fuse_* passes
    # off — the delta against the headline is the Pallas tier's win
    mfu_unfused = _attempt(failed, "transformer_unfused", run_transformer_mfu,
                           pass_pipeline="")
    if mfu_unfused is not None:
        tfs_u = mfu_unfused["tflops_min_window"]
        record["transformer_tflops_unfused"] = round(tfs_u, 1)
        record["transformer_mfu_unfused"] = round(tfs_u / peak_tflops, 3)
        record["transformer_unfused_window_ms_per_step"] = mfu_unfused[
            "window_ms_per_step"
        ]
    # reference-comparable variant: full-f32 Adam state
    mfu_f32 = _attempt(failed, "transformer_f32_state", run_transformer_mfu,
                       moment_dtype=None)
    if mfu_f32 is not None:
        tfs_f32 = mfu_f32["tflops_min_window"]
        record["transformer_tflops_f32_state"] = round(tfs_f32, 1)
        record["transformer_mfu_f32_state"] = round(tfs_f32 / peak_tflops, 3)
        record["transformer_f32_state_window_ms_per_step"] = mfu_f32[
            "window_ms_per_step"
        ]
    # ZeRO-1 evidence (multi-device meshes only; returns None on one chip):
    # step time + per-chip optimizer-state bytes, Reduce(ZeRO-1) vs
    # AllReduce(replicated) — docs/parallelism.md
    z1 = _attempt(failed, "zero1", run_zero1_bench)
    if z1:
        record.update(z1)
    lstm = _attempt(failed, "lstm", run_lstm, measure_pipeline=True,
                    failed=failed)
    if lstm is not None:
        lstm_ms, token_frac = lstm
        record["lstm_ms_per_batch"] = round(lstm_ms, 1)
        record["lstm_vs_baseline"] = round(BASELINE_LSTM_MS_PER_BATCH / lstm_ms, 2)
        if token_frac:
            # byte-light keep-up proof: ~51.5 KB/step token feed (target
            # >= 0.95)
            record["pyreader_frac_tokens"] = round(token_frac, 3)
    vgg_ips = _attempt(failed, "vgg19", run_vgg19)
    if vgg_ips is not None:
        record["vgg19_images_per_sec"] = round(vgg_ips, 1)
        record["vgg19_vs_baseline"] = round(vgg_ips / BASELINE_VGG19_IMAGES_PER_SEC, 2)
    record["failed"] = failed
    print(json.dumps(record))
    if failed:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
