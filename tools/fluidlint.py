#!/usr/bin/env python
"""fluidlint: run the whole-program static analyzer + checker suite
(paddle_tpu/analysis, docs/static_analysis.md) over a model and print every
finding with op/var provenance.

Usage:
  python tools/fluidlint.py --zoo                 # lint every zoo model
  python tools/fluidlint.py --model lenet         # one model
  python tools/fluidlint.py --model-dir DIR       # a saved inference model
  python tools/fluidlint.py --zoo --json          # machine-readable output
  python tools/fluidlint.py --zoo --strict        # exit 1 on warnings too

Exit code: 0 clean, 1 any error finding (or, with --strict, any finding at
all), 2 usage/build failure. The zoo linting clean is an invariant
(tests/test_analysis.py::test_zoo_facts_agree_with_traced_metadata).

The ZOO registry of `name -> build() -> (program, feed_names, fetch_names)`
is also imported by tests/test_fluidlint.py — the clean-zoo test and this
CLI lint the exact same programs.
"""

import argparse
import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def _fresh():
    from paddle_tpu import framework

    return framework.Program(), framework.Program()


def _guard(main, startup):
    import paddle_tpu.fluid as fluid

    class _G:
        def __enter__(self):
            self._u = fluid.unique_name.guard()
            self._p = fluid.program_guard(main, startup)
            self._u.__enter__()
            self._p.__enter__()
            return self

        def __exit__(self, *exc):
            self._p.__exit__(*exc)
            self._u.__exit__(*exc)

    return _G()


def _cv_model(model_fn, img_shape, minimize=False, **kw):
    import paddle_tpu.fluid as fluid

    main, startup = _fresh()
    with _guard(main, startup):
        img = fluid.layers.data(name="img", shape=img_shape, dtype="float32")
        label = fluid.layers.data(name="label", shape=[1], dtype="int64")
        loss, acc = model_fn(img, label, **kw)[:2]
        if minimize:
            fluid.optimizer.Adam(learning_rate=1e-3).minimize(loss)
    # fetch accuracy too: computed-but-unfetched outputs are exactly what
    # the write-never-read checker flags
    return main, ["img", "label"], [loss.name, acc.name]


def build_lenet():
    from paddle_tpu.models import lenet5

    return _cv_model(lenet5, [1, 28, 28], minimize=True)


def build_resnet_cifar10():
    from paddle_tpu.models.resnet import resnet_cifar10

    return _cv_model(resnet_cifar10, [3, 32, 32], depth=20)


def build_vgg16():
    from paddle_tpu.models.vgg import vgg16

    return _cv_model(vgg16, [3, 32, 32], class_num=10)


def build_alexnet():
    from paddle_tpu.models.alexnet import alexnet

    return _cv_model(alexnet, [3, 224, 224], class_dim=10)


def build_googlenet():
    from paddle_tpu.models.googlenet import googlenet

    return _cv_model(googlenet, [3, 224, 224], class_dim=10)


def build_se_resnext50():
    from paddle_tpu.models import se_resnext

    return _cv_model(
        se_resnext.se_resnext50, [3, 64, 64], class_dim=10,
        depth_override=[1, 1, 1, 1], filters_override=[32, 64, 128, 256],
    )


def build_transformer():
    import paddle_tpu.fluid as fluid
    from paddle_tpu.models.transformer import build_tiny_flash_transformer

    main, startup = _fresh()
    with _guard(main, startup):
        feeds, loss = build_tiny_flash_transformer()
        fluid.optimizer.Adam(learning_rate=1e-3).minimize(loss)
    return main, sorted(feeds), [loss.name]


def build_deepfm():
    import paddle_tpu.fluid as fluid
    from paddle_tpu.models.deepfm import deepfm

    main, startup = _fresh()
    with _guard(main, startup):
        ids = fluid.layers.data(name="ids", shape=[4, 1], dtype="int64")
        label = fluid.layers.data(name="label", shape=[1], dtype="float32")
        loss, pred, _ = deepfm(ids, label, num_features=1000, num_fields=4)
        fluid.optimizer.Adam(learning_rate=5e-3).minimize(loss)
    return main, ["ids", "label"], [loss.name, pred.name]


def build_stacked_lstm():
    import paddle_tpu.fluid as fluid
    from paddle_tpu.models.stacked_lstm import stacked_lstm_net

    main, startup = _fresh()
    with _guard(main, startup):
        words = fluid.layers.data(
            name="words", shape=[1], dtype="int64", lod_level=1
        )
        label = fluid.layers.data(name="label", shape=[1], dtype="int64")
        loss, acc, _ = stacked_lstm_net(
            words, label, dict_dim=200, emb_dim=16, hid_dim=16, stacked_num=2
        )
    return main, ["words", "label"], [loss.name, acc.name]


def build_machine_translation():
    """NMT training net: recurrent (scan) encoder/decoder sub-blocks."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.models import machine_translation as mt

    B, T, VOCAB = 4, 6, 50
    main, startup = _fresh()
    with _guard(main, startup):
        src = fluid.layers.data(
            name="src", shape=[B, T, 1], dtype="int64", append_batch_size=False
        )
        main.global_block().create_var(
            name="src_len", shape=(B,), dtype="int64"
        )
        src._len_name = "src_len"
        trg = fluid.layers.data(
            name="trg", shape=[B, T + 1, 1], dtype="int64",
            append_batch_size=False,
        )
        lab = fluid.layers.data(
            name="lab", shape=[B, T + 1, 1], dtype="int64",
            append_batch_size=False,
        )
        trg_len = fluid.layers.data(
            name="trg_len", shape=[B], dtype="int64", append_batch_size=False
        )
        loss = mt.train_model(src, trg, lab, trg_len, VOCAB)
        fluid.optimizer.Adam(1e-2).minimize(loss)
    return main, ["src", "src_len", "trg", "lab", "trg_len"], [loss.name]


def build_machine_translation_infer():
    """NMT beam-search decode: while loop, tensor arrays, beam_search_decode
    — the analyzer's hardest control-flow case."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.models import machine_translation as mt

    B, T, VOCAB = 4, 6, 50
    main, startup = _fresh()
    with _guard(main, startup):
        src = fluid.layers.data(
            name="src", shape=[B, T, 1], dtype="int64", append_batch_size=False
        )
        main.global_block().create_var(
            name="src_len", shape=(B,), dtype="int64"
        )
        src._len_name = "src_len"
        ids, scores = mt.infer_model(src, VOCAB)
    return main, ["src", "src_len"], [ids.name, scores.name]


def _gpt():
    from paddle_tpu.models.gpt_decoder import GPTDecoder

    return GPTDecoder(vocab_size=64, n_layer=2, n_head=2, d_model=32,
                      max_context=32)


def build_gpt_forward():
    main, _, feeds, fetches = _gpt().build_forward(2, 8)
    return main, feeds, fetches


def build_gpt_prefill():
    main, _, feeds, fetches = _gpt().build_prefill(8, 4, 8, 32)
    return main, feeds, fetches


def build_gpt_decode():
    main, _, feeds, fetches = _gpt().build_decode(4, 4, 8, 32)
    return main, feeds, fetches


ZOO = {
    "lenet": build_lenet,
    "resnet_cifar10": build_resnet_cifar10,
    "vgg16": build_vgg16,
    "alexnet": build_alexnet,
    "googlenet": build_googlenet,
    "se_resnext50": build_se_resnext50,
    "transformer": build_transformer,
    "deepfm": build_deepfm,
    "stacked_lstm": build_stacked_lstm,
    "machine_translation": build_machine_translation,
    "machine_translation_infer": build_machine_translation_infer,
    "gpt_forward": build_gpt_forward,
    "gpt_prefill": build_gpt_prefill,
    "gpt_decode": build_gpt_decode,
}


def lint_one(name, program, feed_names, fetch_names, as_json=False):
    """Lint one program; returns (analysis, findings) and prints them."""
    from paddle_tpu.analysis import lint_program

    analysis, findings = lint_program(program, feed_names, fetch_names)
    if as_json:
        print(json.dumps({
            "model": name,
            "findings": [
                {
                    "check": f.check, "severity": f.severity,
                    "message": f.message, "var": f.var,
                    "block": f.block_idx, "op_index": f.op_index,
                    "op_type": f.op_type, "op": f.op_display,
                }
                for f in findings
            ],
            "problems": list(analysis.problems),
            "ops_analyzed": len(analysis.records),
        }))
    else:
        tag = "clean" if not findings else "%d finding(s)" % len(findings)
        print("%-28s %s" % (name, tag))
        for f in findings:
            print("  " + f.format())
        for p in analysis.problems:
            print("  note: %s" % (p,))
    return analysis, findings


def main(argv=None):
    ap = argparse.ArgumentParser(
        prog="fluidlint", description=__doc__.splitlines()[0]
    )
    ap.add_argument("--model", choices=sorted(ZOO), help="zoo model to lint")
    ap.add_argument("--zoo", action="store_true", help="lint every zoo model")
    ap.add_argument(
        "--model-dir", help="saved inference-model directory to lint"
    )
    ap.add_argument(
        "--strict", action="store_true", help="exit 1 on warnings too"
    )
    ap.add_argument("--json", action="store_true", help="JSONL output")
    args = ap.parse_args(argv)

    targets = []
    if args.zoo:
        targets = sorted(ZOO)
    elif args.model:
        targets = [args.model]
    elif not args.model_dir:
        ap.error("one of --zoo, --model, or --model-dir is required")

    worst = 0
    for name in targets:
        program, feeds, fetches = ZOO[name]()
        _, findings = lint_one(name, program, feeds, fetches, args.json)
        if any(f.severity == "error" for f in findings):
            worst = max(worst, 1)
        elif findings and args.strict:
            worst = max(worst, 1)

    if args.model_dir:
        import paddle_tpu.fluid as fluid
        from paddle_tpu import io as _io
        from paddle_tpu.executor import Executor, Scope, scope_guard

        scope = Scope()
        with scope_guard(scope):
            program, feed_names, fetch_vars = _io.load_inference_model(
                args.model_dir, Executor()
            )
        _, findings = lint_one(
            args.model_dir, program, feed_names,
            [v.name for v in fetch_vars], args.json,
        )
        if any(f.severity == "error" for f in findings):
            worst = max(worst, 1)
        elif findings and args.strict:
            worst = max(worst, 1)

    return worst


if __name__ == "__main__":
    sys.exit(main())
