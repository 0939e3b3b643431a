"""Pass framework tests (paddle_tpu/passes, docs/passes.md): lossless
Graph round-trip across the whole model zoo, per-pass unit behavior
(fetched constants must NOT fold, DCE keeps fetch/persistable/stochastic
roots), pipeline on/off bit-parity through both executors, serving parity
with the `inference` preset, debug dumps, and the donation-plan
cross-check at the lowering seam."""

import json
import os

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu import framework, passes
from paddle_tpu.executor import Scope, aot_serve_lowering, scope_guard


def _fresh():
    return framework.Program(), framework.Program()


def _program_fingerprint(program):
    return json.dumps(program.to_dict(), sort_keys=True)


# --------------------------------------------------------------------------
# round-trip identity across the model zoo
# --------------------------------------------------------------------------


def _build_lenet_trained():
    from paddle_tpu.models import lenet5

    main, startup = _fresh()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        img = fluid.layers.data(name="img", shape=[1, 28, 28], dtype="float32")
        label = fluid.layers.data(name="label", shape=[1], dtype="int64")
        out = lenet5(img, label)
        loss = out[0] if isinstance(out, tuple) else out
        fluid.optimizer.Adam(learning_rate=1e-3).minimize(loss)
    return main


def _build_resnet_cifar():
    from paddle_tpu.models.resnet import resnet_cifar10

    main, startup = _fresh()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        img = fluid.layers.data(name="img", shape=[3, 32, 32], dtype="float32")
        label = fluid.layers.data(name="label", shape=[1], dtype="int64")
        resnet_cifar10(img, label, depth=20)
    return main


def _build_vgg16():
    from paddle_tpu.models.vgg import vgg16

    main, startup = _fresh()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        img = fluid.layers.data(name="img", shape=[3, 32, 32], dtype="float32")
        label = fluid.layers.data(name="label", shape=[1], dtype="int64")
        vgg16(img, label, class_num=10)
    return main


def _build_alexnet():
    from paddle_tpu.models.alexnet import alexnet

    main, startup = _fresh()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        img = fluid.layers.data(name="img", shape=[3, 224, 224],
                                dtype="float32")
        label = fluid.layers.data(name="label", shape=[1], dtype="int64")
        alexnet(img, label, class_dim=10)
    return main


def _build_googlenet():
    from paddle_tpu.models.googlenet import googlenet

    main, startup = _fresh()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        img = fluid.layers.data(name="img", shape=[3, 224, 224],
                                dtype="float32")
        label = fluid.layers.data(name="label", shape=[1], dtype="int64")
        googlenet(img, label, class_dim=10)
    return main


def _build_se_resnext():
    from paddle_tpu.models import se_resnext

    main, startup = _fresh()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        img = fluid.layers.data(name="img", shape=[3, 64, 64], dtype="float32")
        label = fluid.layers.data(name="label", shape=[1], dtype="int64")
        se_resnext.se_resnext50(
            img, label, class_dim=10,
            depth_override=[1, 1, 1, 1], filters_override=[32, 64, 128, 256],
        )
    return main


def _build_transformer():
    from paddle_tpu.models.transformer import build_tiny_flash_transformer

    main, startup = _fresh()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        _feeds, loss = build_tiny_flash_transformer()
        fluid.optimizer.Adam(learning_rate=1e-3).minimize(loss)
    return main


def _build_deepfm():
    from paddle_tpu.models.deepfm import deepfm

    main, startup = _fresh()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        ids = fluid.layers.data(name="ids", shape=[4, 1], dtype="int64")
        label = fluid.layers.data(name="label", shape=[1], dtype="float32")
        loss, _, _ = deepfm(ids, label, num_features=1000, num_fields=4)
        fluid.optimizer.Adam(learning_rate=5e-3).minimize(loss)
    return main


def _build_stacked_lstm():
    from paddle_tpu.models.stacked_lstm import stacked_lstm_net

    main, startup = _fresh()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        words = fluid.layers.data(name="words", shape=[1], dtype="int64",
                                  lod_level=1)
        label = fluid.layers.data(name="label", shape=[1], dtype="int64")
        stacked_lstm_net(words, label, dict_dim=200, emb_dim=16, hid_dim=16,
                         stacked_num=2)
    return main


def _build_machine_translation():
    from paddle_tpu.models import machine_translation as mt

    B, T, VOCAB = 4, 6, 50
    main, startup = _fresh()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        src = fluid.layers.data(name="src", shape=[B, T, 1], dtype="int64",
                                append_batch_size=False)
        main.global_block().create_var(name="src_len", shape=(B,),
                                       dtype="int64")
        src._len_name = "src_len"
        trg = fluid.layers.data(name="trg", shape=[B, T + 1, 1],
                                dtype="int64", append_batch_size=False)
        lab = fluid.layers.data(name="lab", shape=[B, T + 1, 1],
                                dtype="int64", append_batch_size=False)
        trg_len = fluid.layers.data(name="trg_len", shape=[B], dtype="int64",
                                    append_batch_size=False)
        loss = mt.train_model(src, trg, lab, trg_len, VOCAB)
        fluid.optimizer.Adam(1e-2).minimize(loss)
    return main


_MODEL_BUILDERS = {
    "lenet": _build_lenet_trained,
    "resnet_cifar10": _build_resnet_cifar,
    "vgg16": _build_vgg16,
    "alexnet": _build_alexnet,
    "googlenet": _build_googlenet,
    "se_resnext50": _build_se_resnext,
    "transformer": _build_transformer,
    "deepfm": _build_deepfm,
    "stacked_lstm": _build_stacked_lstm,
    "machine_translation": _build_machine_translation,
}


@pytest.mark.parametrize("model", sorted(_MODEL_BUILDERS))
def test_roundtrip_identity(model):
    """Program -> Graph -> Program must be bit-identical (the ISSUE's
    lossless round-trip criterion), for every model in the zoo — including
    sub-block control flow (machine_translation's while loop)."""
    program = _MODEL_BUILDERS[model]()
    before = _program_fingerprint(program)
    graph = passes.Graph(program)
    graph.verify()
    after = _program_fingerprint(graph.to_program())
    assert before == after
    # the source program itself must be untouched by graph construction
    assert _program_fingerprint(program) == before


def test_registered_pass_battery():
    names = passes.registered_passes()
    for required in ("constant_fold", "dead_op_eliminate",
                     "fuse_elemwise_act", "inplace_donation_plan",
                     "fold_batch_norm", "memory_optimize",
                     "quantize_training"):
        assert required in names
    assert len(names) >= 5
    assert set(passes.PRESETS) == {
        "training_default", "inference", "training_fused",
        "inference_int8",
    }
    for pname in ("fuse_gemm_epilogue", "fuse_layer_norm"):
        assert pname in names
        assert pname in passes.PRESETS["training_fused"]
    for pname in ("calibrate", "quantize_serving", "fuse_quant_gemm"):
        assert pname in names
        assert pname in passes.PRESETS["inference_int8"]


# --------------------------------------------------------------------------
# per-pass unit tests
# --------------------------------------------------------------------------


def _scale_chain_program():
    """fill_constant -> scale -> elementwise_add(fed) : the fill+scale prefix
    is foldable, the add is not (fed input)."""
    main, startup = _fresh()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[4], dtype="float32")
        c = fluid.layers.fill_constant(shape=[4], dtype="float32", value=2.0)
        s = fluid.layers.scale(c, scale=3.0)
        out = fluid.layers.elementwise_add(x, s)
    return main, out


def test_constant_fold_folds_prefix():
    main, out = _scale_chain_program()
    scope = Scope(seed=0)
    n_before = len(main.global_block().ops)
    results = passes.apply_inplace(
        main, ["constant_fold"], scope=scope,
        feed_names=["x"], fetch_names=[out.name],
    )
    assert results["constant_fold"]["folded"] == 2
    assert len(main.global_block().ops) == n_before - 2
    # the folded chain's value the surviving add still reads is in the scope
    folded = scope.find_var(results["constant_fold"]["stored"][0])
    np.testing.assert_allclose(np.asarray(folded), np.full(4, 6.0), rtol=0)
    # and the program still computes the same thing
    from paddle_tpu.executor import Executor

    exe = fluid.Executor()
    with scope_guard(scope):
        (val,) = exe.run(main, feed={"x": np.ones(4, "float32")},
                         fetch_list=[out.name])
    np.testing.assert_allclose(np.asarray(val), np.full(4, 7.0), rtol=0)


def test_constant_fold_keeps_fetched_op():
    """An op whose output is FETCHED must never fold away (ISSUE'd
    explicitly)."""
    main, startup = _fresh()
    with fluid.program_guard(main, startup):
        c = fluid.layers.fill_constant(shape=[2], dtype="float32", value=1.5)
    results = passes.apply_inplace(
        main, ["constant_fold"], scope=Scope(), fetch_names=[c.name],
    )
    assert results["constant_fold"]["folded"] == 0
    assert [op.type for op in main.global_block().ops] == ["fill_constant"]


def test_constant_fold_needs_scope():
    main, _ = _scale_chain_program()
    n = len(main.global_block().ops)
    results = passes.apply_inplace(main, ["constant_fold"])
    assert results["constant_fold"]["folded"] == 0
    assert len(main.global_block().ops) == n


def test_dead_op_eliminate_roots():
    """DCE removes the unconsumed branch but keeps (a) ops feeding the fetch,
    (b) ops writing persistable vars, (c) stochastic ops — the RNG-stream
    rule."""
    main, startup = _fresh()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[4], dtype="float32")
        kept = fluid.layers.scale(x, scale=2.0)
        dead = fluid.layers.scale(x, scale=5.0)  # never fetched or consumed
        dropped = fluid.layers.dropout(x, dropout_prob=0.5)  # stochastic
        p = fluid.layers.create_parameter([4], "float32", name="p0")
        assign = fluid.layers.assign(kept)  # -> non-persistable, dead
    types_before = [op.type for op in main.global_block().ops]
    assert "dropout" in types_before
    results = passes.apply_inplace(
        main, ["dead_op_eliminate"],
        feed_names=["x"], fetch_names=[kept.name],
    )
    types = [op.type for op in main.global_block().ops]
    assert results["dead_op_eliminate"]["removed"] >= 2
    assert "dropout" in types  # stochastic root survives
    assert "scale" in types  # the fetched chain survives
    # both dead scale ops gone: only the fetched one remains
    assert types.count("scale") == 1
    assert dead.name not in {
        n for op in main.global_block().ops for n in op.output_arg_names
    }
    assert assign.name not in {
        n for op in main.global_block().ops for n in op.output_arg_names
    }


def test_dead_op_eliminate_keeps_persistable_writes():
    """An optimizer-style write to a persistable var is a root even when
    nothing fetches it."""
    main = _build_lenet_trained()
    types_before = [op.type for op in main.global_block().ops]
    loss_name = "mean_0.tmp_0"
    assert loss_name in {
        n for op in main.global_block().ops for n in op.output_arg_names
    }
    passes.apply_inplace(
        main, ["dead_op_eliminate"],
        feed_names=["img", "label"], fetch_names=[loss_name],
    )
    types = [op.type for op in main.global_block().ops]
    assert types.count("adam") == types_before.count("adam")


def test_fuse_elemwise_act_tags_chains():
    main = _build_lenet_trained()
    from paddle_tpu.ops.registry import FUSION_GROUP_ATTR

    n_before = len(main.global_block().ops)
    results = passes.apply_inplace(main, ["fuse_elemwise_act"])
    r = results["fuse_elemwise_act"]
    assert r["groups"] >= 3  # two convs + three fcs carry add(+act) chains
    assert r["ops_tagged"] >= 2 * r["groups"]
    assert len(main.global_block().ops) == n_before  # purely additive
    tags = [
        op.attrs[FUSION_GROUP_ATTR]
        for op in main.global_block().ops
        if FUSION_GROUP_ATTR in op.attrs
    ]
    assert len(tags) == r["ops_tagged"]
    assert len(set(tags)) == r["groups"]


def test_graph_verify_catches_reorder():
    """Moving a consumer before its producer must fail verification — the
    per-pass invariant the manager re-checks."""
    main, out = _scale_chain_program()
    graph = passes.Graph(main)
    block = graph.program.global_block()
    block.ops.append(block.ops.pop(0))  # rotate: fill_constant now last
    graph.refresh()
    with pytest.raises(passes.GraphVerifyError):
        graph.verify()


def test_pass_debug_dumps(tmp_path):
    from paddle_tpu import flags

    main = _build_lenet_trained()
    flags.set_flags({"pass_debug_dir": str(tmp_path)})
    try:
        passes.PassManager("training_default").apply(
            main, scope=Scope(), feed_names=["img", "label"],
            fetch_names=["mean_0.tmp_0"],
        )
    finally:
        flags.set_flags({"pass_debug_dir": ""})
    names = sorted(os.listdir(str(tmp_path)))
    for i, pname in enumerate(passes.PRESETS["training_default"]):
        assert "%02d_%s_before.dot" % (i, pname) in names
        assert "%02d_%s_after.dot" % (i, pname) in names
        assert "%02d_%s_ops.diff" % (i, pname) in names
    # the dot files are real graphviz, not error stubs
    head = open(os.path.join(str(tmp_path), names[0])).read(100)
    assert head.startswith("digraph")


# --------------------------------------------------------------------------
# pipeline parity through both executors
# --------------------------------------------------------------------------


def _lenet_losses_executor(pipeline, steps=4):
    from paddle_tpu import flags

    flags.set_flags({"pass_pipeline": pipeline})
    try:
        from paddle_tpu.models import lenet5

        main, startup = _fresh()
        with fluid.unique_name.guard(), fluid.program_guard(main, startup):
            img = fluid.layers.data(name="img", shape=[1, 28, 28],
                                    dtype="float32")
            label = fluid.layers.data(name="label", shape=[1], dtype="int64")
            out = lenet5(img, label)
            loss = out[0] if isinstance(out, tuple) else out
            fluid.optimizer.Adam(learning_rate=1e-3).minimize(loss)
        exe = fluid.Executor()
        rng = np.random.RandomState(3)
        losses = []
        with scope_guard(Scope(seed=11)):
            exe.run(startup)
            for _ in range(steps):
                feed = {
                    "img": rng.randn(16, 1, 28, 28).astype("float32"),
                    "label": rng.randint(0, 10, (16, 1)).astype("int64"),
                }
                (lv,) = exe.run(main, feed=feed, fetch_list=[loss.name])
                losses.append(np.asarray(lv).copy())
        return np.stack(losses)
    finally:
        flags.set_flags({"pass_pipeline": ""})


def test_pipeline_parity_executor():
    """training_default on vs off through Executor must be BIT-identical:
    every pass preserves the lowered op sequence's RNG stream and math."""
    off = _lenet_losses_executor("")
    on = _lenet_losses_executor("training_default")
    np.testing.assert_array_equal(off, on)


def _fc_losses_parallel_executor(pipeline, steps=4):
    main, startup = _fresh()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[8], dtype="float32")
        y = fluid.layers.data(name="y", shape=[1], dtype="float32")
        h = fluid.layers.fc(x, size=16, act="relu")
        pred = fluid.layers.fc(h, size=1)
        loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
        fluid.optimizer.Adam(learning_rate=1e-2).minimize(loss)
    bs = fluid.BuildStrategy()
    bs.pass_pipeline = pipeline
    exe = fluid.Executor()
    rng = np.random.RandomState(5)
    W = rng.randn(8, 1).astype("float32")
    losses = []
    with scope_guard(Scope(seed=2)):
        exe.run(startup)
        pe = fluid.ParallelExecutor(
            use_cuda=False, loss_name=loss.name, main_program=main,
            build_strategy=bs,
        )
        for _ in range(steps):
            xs = rng.randn(16, 8).astype("float32")
            ys = xs @ W
            (lv,) = pe.run([loss.name], feed={"x": xs, "y": ys})
        losses.append(np.asarray(lv).copy())
    return np.stack(losses)


def test_pipeline_parity_parallel_executor():
    """BuildStrategy.pass_pipeline on vs off through ParallelExecutor (SPMD
    over the test mesh) must match bit-for-bit."""
    off = _fc_losses_parallel_executor("")
    on = _fc_losses_parallel_executor("training_default")
    np.testing.assert_array_equal(off, on)


# --------------------------------------------------------------------------
# serving: aot_serve_lowering's inference preset
# --------------------------------------------------------------------------


def test_serving_inference_preset_parity():
    from paddle_tpu.models import lenet5

    main, startup = _fresh()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        img = fluid.layers.data(name="img", shape=[1, 28, 28],
                                dtype="float32")
        label = fluid.layers.data(name="label", shape=[1], dtype="int64")
        out = lenet5(img, label)
        loss = out[0] if isinstance(out, tuple) else out
    infer = main.clone(for_test=True)

    exe = fluid.Executor()
    rng = np.random.RandomState(0)
    feed = {
        "img": rng.randn(4, 1, 28, 28).astype("float32"),
        "label": rng.randint(0, 10, (4, 1)).astype("int64"),
    }
    import jax.numpy as jnp

    feeds = {k: jnp.asarray(v) for k, v in feed.items()}
    scope = Scope(seed=0)
    with scope_guard(scope):
        exe.run(startup)
        serve_on, ro_on, mut_on = aot_serve_lowering(
            infer, ["img", "label"], [loss.name], scope,
        )  # default pass_pipeline="inference"
        serve_off, ro_off, mut_off = aot_serve_lowering(
            infer, ["img", "label"], [loss.name], scope, pass_pipeline="",
        )
    out_on = np.asarray(serve_on(feeds, ro_on, mut_on)[0])
    out_off = np.asarray(serve_off(feeds, ro_off, mut_off)[0])
    np.testing.assert_array_equal(out_on, out_off)


# --------------------------------------------------------------------------
# donation plan cross-check at the lowering seam
# --------------------------------------------------------------------------


def test_donation_plan_rides_program_and_crosscheck_raises():
    from paddle_tpu.models import lenet5

    main, startup = _fresh()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        img = fluid.layers.data(name="img", shape=[1, 28, 28],
                                dtype="float32")
        label = fluid.layers.data(name="label", shape=[1], dtype="int64")
        out = lenet5(img, label)
        loss = out[0] if isinstance(out, tuple) else out
        fluid.optimizer.Adam(learning_rate=1e-3).minimize(loss)

    exe = fluid.Executor()
    rng = np.random.RandomState(0)
    feed = {
        "img": rng.randn(4, 1, 28, 28).astype("float32"),
        "label": rng.randint(0, 10, (4, 1)).astype("int64"),
    }
    scope = Scope(seed=0)
    with scope_guard(scope):
        exe.run(startup)
        transformed = passes.apply_cached(
            main, "training_default", scope=scope,
            feed_names=sorted(feed), fetch_names=[loss.name],
        )
        plan = transformed._donation_plan
        assert not plan["unknown"]
        assert plan["scope_uid"] == scope._uid
        assert plan["mut"]  # Adam rewrites params + moments in place
        # the healthy plan lowers fine
        (lv,) = exe.run(transformed, feed=feed, fetch_list=[loss.name])
        assert np.isfinite(np.asarray(lv)).all()
        # a corrupted plan must be caught at the lowering seam
        bad = dict(plan)
        bad["mut"] = list(plan["mut"][1:])  # drop one donated tensor
        transformed._donation_plan = bad
        exe2 = fluid.Executor()
        with pytest.raises(RuntimeError, match="donation"):
            exe2.run(transformed, feed=feed, fetch_list=[loss.name])
        transformed._donation_plan = plan
