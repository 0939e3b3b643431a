"""Model-zoo benchmark launcher.

Reference analog: benchmark/fluid/fluid_benchmark.py + args.py — a CLI that
builds one of the zoo models, optionally dist-transpiles by env role, trains
`--pass_num` passes of `--iterations` minibatches, and prints per-pass
throughput. The TPU-native edition keeps the surface (models, fake-data mode,
infer_only, memory_optimize, profile, pserver env-role mode) and replaces the
nccl2 update method with `spmd` (ParallelExecutor over the device mesh).

Usage:
    python benchmark/fluid_benchmark.py --model resnet --device TPU \
        --batch_size 64 --iterations 20 --pass_num 2 --use_bf16
Env-role pserver mode (reference dist env contract):
    PADDLE_TRAINING_ROLE=PSERVER|TRAINER PADDLE_PSERVER_IPS=... \
    PADDLE_TRAINERS=2 PADDLE_TRAINER_ID=0 python benchmark/fluid_benchmark.py \
        --model mnist --update_method pserver
"""

import argparse
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

import paddle_tpu.fluid as fluid  # noqa: E402
from paddle_tpu import framework  # noqa: E402
from paddle_tpu.executor import Scope, scope_guard  # noqa: E402


def parse_args(argv=None):
    p = argparse.ArgumentParser("fluid-style model benchmark")
    p.add_argument("--model", default="mnist",
                   choices=["mnist", "resnet", "vgg", "stacked_dynamic_lstm",
                            "machine_translation", "transformer"])
    p.add_argument("--batch_size", type=int, default=32)
    p.add_argument("--learning_rate", type=float, default=0.001)
    p.add_argument("--skip_batch_num", type=int, default=5)
    p.add_argument("--iterations", type=int, default=30)
    p.add_argument("--pass_num", type=int, default=1)
    p.add_argument(
        "--device", default="TPU", choices=["CPU", "TPU"],
        help="TPU: fluid.TPUPlace(), an error where JAX finds no TPU. CPU: "
        "fluid.CPUPlace(), which pins nothing (JAX's default backend; run "
        "under JAX_PLATFORMS=cpu to mean the host)",
    )
    p.add_argument("--data_set", default="flowers",
                   choices=["cifar10", "flowers", "imagenet"])
    p.add_argument("--infer_only", action="store_true")
    p.add_argument("--use_fake_data", action="store_true", default=True,
                   help="synthetic batches staged once (reference fake-data mode)")
    p.add_argument("--memory_optimize", action="store_true")
    p.add_argument("--use_bf16", action="store_true",
                   help="bf16 training (the fp16/data_format analog on TPU)")
    p.add_argument("--profile", action="store_true")
    p.add_argument("--update_method", default="local",
                   choices=["local", "pserver", "spmd"])
    p.add_argument("--async_mode", action="store_true")
    p.add_argument("--no_split_var", action="store_true")
    return p.parse_args(argv)


# --------------------------------------------------------------------------
# model adapters: build(main, startup, args) -> (loss, feed_fn)
# --------------------------------------------------------------------------


def _img_label(shape, classes):
    img = fluid.layers.data(name="img", shape=list(shape), dtype="float32")
    label = fluid.layers.data(name="label", shape=[1], dtype="int64")
    return img, label


def _img_feed(args, shape, classes):
    rng = np.random.RandomState(0)
    return {
        "img": rng.randn(args.batch_size, *shape).astype("float32"),
        "label": rng.randint(0, classes, (args.batch_size, 1)).astype("int64"),
    }


def build_mnist(args):
    from paddle_tpu.models import lenet

    img, label = _img_label((1, 28, 28), 10)
    loss, acc, _ = lenet.lenet5(img, label)
    return loss, lambda: _img_feed(args, (1, 28, 28), 10)


def build_resnet(args):
    from paddle_tpu.models import resnet

    if args.data_set == "cifar10":
        img, label = _img_label((3, 32, 32), 10)
        loss, acc, _ = resnet.resnet_cifar10(img, label)
        return loss, lambda: _img_feed(args, (3, 32, 32), 10)
    img, label = _img_label((3, 224, 224), 1000)
    loss, acc, _ = resnet.resnet50(img, label)
    return loss, lambda: _img_feed(args, (3, 224, 224), 1000)


def build_vgg(args):
    from paddle_tpu.models import vgg

    shape, classes = ((3, 32, 32), 10) if args.data_set == "cifar10" else (
        (3, 224, 224), 1000)
    img, label = _img_label(shape, classes)
    loss, acc, _ = vgg.vgg16(img, label, class_num=classes)
    return loss, lambda: _img_feed(args, shape, classes)


def build_stacked_dynamic_lstm(args, dict_dim=30000, t=100):
    from paddle_tpu.models.stacked_lstm import stacked_lstm_net

    words = fluid.layers.data(name="words", shape=[1], dtype="int64", lod_level=1)
    label = fluid.layers.data(name="label", shape=[1], dtype="int64")
    loss, acc, _ = stacked_lstm_net(
        words, label, dict_dim=dict_dim, emb_dim=512, hid_dim=512, stacked_num=2
    )
    rng = np.random.RandomState(0)

    def feed():
        return {
            "words": rng.randint(0, dict_dim, (args.batch_size, t, 1)).astype("int64"),
            "words@LEN": np.full((args.batch_size,), t, "int32"),
            "label": rng.randint(0, 2, (args.batch_size, 1)).astype("int64"),
        }

    return loss, feed


def build_machine_translation(args, dict_size=10000, t=16):
    from paddle_tpu.models import machine_translation as mt

    b = args.batch_size
    # the attention mask needs static (B, T) shapes; lengths ride explicit
    # companion vars (the tests/test_machine_translation.py declaration)
    src = fluid.layers.data(name="src_word", shape=[b, t, 1], dtype="int64",
                            append_batch_size=False)
    fluid.framework.default_main_program().global_block().create_var(
        name="src_len", shape=(b,), dtype="int64")
    src._len_name = "src_len"
    trg = fluid.layers.data(name="trg_word", shape=[b, t + 1, 1], dtype="int64",
                            append_batch_size=False)
    lbl = fluid.layers.data(name="label", shape=[b, t + 1, 1], dtype="int64",
                            append_batch_size=False)
    tlen = fluid.layers.data(name="trg_len", shape=[b], dtype="int64",
                             append_batch_size=False)
    loss = mt.train_model(src, trg, lbl, tlen, dict_size)
    rng = np.random.RandomState(0)

    def feed():
        return {
            "src_word": rng.randint(0, dict_size, (b, t, 1)).astype("int64"),
            "src_len": np.full((b,), t, "int64"),
            "trg_word": rng.randint(0, dict_size, (b, t + 1, 1)).astype("int64"),
            "label": rng.randint(0, dict_size, (b, t + 1, 1)).astype("int64"),
            "trg_len": np.full((b,), t + 1, "int64"),
        }

    return loss, feed


def build_transformer(args, vocab=1000, t=64):
    from paddle_tpu.models import transformer as T

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
        n_layer=2, n_head=8, d_model=256, d_inner=1024, d_key=32, d_value=32,
        dropout=0.0, max_length=t + 1, use_flash=True, padded=False,
    )
    rng = np.random.RandomState(0)
    pos = np.tile(np.arange(t), (args.batch_size, 1)).astype("int64")

    def feed():
        b = args.batch_size
        return {
            "src_word": rng.randint(0, vocab, (b, t)).astype("int64"),
            "src_pos": pos,
            "trg_word": rng.randint(0, vocab, (b, t)).astype("int64"),
            "trg_pos": pos.copy(),
            "label": rng.randint(0, vocab, (b, t)).astype("int64"),
            "label_weight": np.ones((b, t, 1), "float32"),
        }

    return loss, feed


_BUILDERS = {
    "mnist": build_mnist,
    "resnet": build_resnet,
    "vgg": build_vgg,
    "stacked_dynamic_lstm": build_stacked_dynamic_lstm,
    "machine_translation": build_machine_translation,
    "transformer": build_transformer,
}


def dist_transpile(args, train_prog, startup_prog):
    """Env-role pserver transpile (reference fluid_benchmark.py:63 contract:
    PADDLE_PSERVER_IPS/PADDLE_PSERVER_PORT/PADDLE_TRAINERS/PADDLE_TRAINER_ID/
    PADDLE_CURRENT_IP/PADDLE_TRAINING_ROLE)."""
    from paddle_tpu.transpiler import DistributeTranspiler, DistributeTranspilerConfig

    port = os.getenv("PADDLE_PSERVER_PORT", "6174")
    pserver_ips = os.getenv("PADDLE_PSERVER_IPS", "")
    eplist = [":".join([ip, port]) for ip in pserver_ips.split(",") if ip]
    pserver_endpoints = ",".join(eplist)
    trainers = int(os.getenv("PADDLE_TRAINERS", "1"))
    trainer_id = int(os.getenv("PADDLE_TRAINER_ID", "0"))
    current_endpoint = os.getenv("PADDLE_CURRENT_IP", "127.0.0.1") + ":" + port
    role = os.getenv("PADDLE_TRAINING_ROLE", "TRAINER")

    config = DistributeTranspilerConfig()
    config.slice_var_up = not args.no_split_var
    t = DistributeTranspiler(config=config)
    t.transpile(
        trainer_id, program=train_prog, pservers=pserver_endpoints,
        trainers=trainers, sync_mode=not args.async_mode,
        startup_program=startup_prog,
    )
    if role == "PSERVER":
        pserver_program = t.get_pserver_program(current_endpoint)
        pserver_startup = t.get_startup_program(
            current_endpoint, pserver_program, startup_program=startup_prog
        )
        return "pserver", pserver_program, pserver_startup
    return "trainer", t.get_trainer_program(), startup_prog


def main(argv=None):
    args = parse_args(argv)
    main_prog, startup_prog = framework.Program(), framework.Program()
    with fluid.unique_name.guard():
        with fluid.program_guard(main_prog, startup_prog):
            loss, feed_fn = _BUILDERS[args.model](args)
            if not args.infer_only:
                fluid.optimizer.Adam(learning_rate=args.learning_rate).minimize(loss)
            elif hasattr(main_prog, "clone"):
                main_prog = main_prog.clone(for_test=True)

    if args.memory_optimize:
        fluid.memory_optimize(main_prog)

    place = fluid.CPUPlace() if args.device == "CPU" else fluid.TPUPlace()
    exe = fluid.Executor(place)

    if args.update_method == "pserver":
        role, prog, startup = dist_transpile(args, main_prog, startup_prog)
        if role == "pserver":
            with scope_guard(Scope(seed=0)):
                exe.run(startup)
                exe.run(prog)  # serves until trainers disconnect
            return []
        main_prog = prog

    results = []
    with scope_guard(Scope(seed=0)):
        exe.run(startup_prog)
        if args.use_bf16:
            from paddle_tpu.transpiler.bf16_transpiler import Bf16Transpiler

            Bf16Transpiler().transpile(main_prog)

        runner = exe
        run_kw = {}
        if args.update_method == "spmd":
            pe = fluid.ParallelExecutor(
                use_cuda=False, loss_name=loss.name, main_program=main_prog
            )
            runner = pe

        feed = feed_fn()
        import jax

        feed = {k: jax.device_put(v) for k, v in feed.items()}
        fetch = [loss.name]

        def run_once():
            if runner is exe:
                return exe.run(main_prog, feed=feed, fetch_list=fetch,
                               return_numpy=False)
            return runner.run(fetch_list=fetch, feed=feed)

        def one_pass(profiling=False):
            out = None
            t_start = None
            n = 0
            maybe_prof = (
                fluid.profiler.profiler("All", "total")
                if profiling
                else _null_ctx()
            )
            with maybe_prof:
                for it in range(args.iterations):
                    if it == args.skip_batch_num:
                        if out is not None:
                            np.asarray(out[0])  # sync warmup before timing
                        t_start = time.time()
                        n = 0
                    out = run_once()
                    n += args.batch_size
            last = float(np.asarray(out[0]).reshape(-1)[0])  # syncs the pass
            dt = time.time() - (t_start or time.time())
            return (n / dt if dt > 0 else float("nan")), last

        for pass_id in range(args.pass_num):
            ips, last_loss = one_pass(profiling=args.profile and pass_id == 0)
            results.append(ips)
            print("Pass: %d, Throughput: %.2f samples/s, Loss: %s"
                  % (pass_id, ips, last_loss))
    return results


class _null_ctx(object):
    def __enter__(self):
        return self

    def __exit__(self, *a):
        return False


if __name__ == "__main__":
    main()
