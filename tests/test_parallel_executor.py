"""ParallelExecutor correctness = convergence equivalence with the plain
Executor (reference unittests/parallel_executor_test_base.py
check_network_convergence), run on the 8-device virtual CPU mesh.

Instantiated across the reference's model family (SURVEY.md §4.3):
MLP (test_parallel_executor_mnist analog), SE-ResNeXt
(_seresnext), Transformer (_transformer), CRF (_crf), and a
bounded-While training case (test_parallel_executor_test_while_train)."""


import numpy as np

import paddle_tpu.fluid as fluid
from paddle_tpu import framework
from paddle_tpu.executor import Scope, global_scope, scope_guard


def _check_convergence(build_fn, batches, optimizer_fn, rtol=2e-3, atol=2e-4,
                       seed=3, require_decrease=True):
    """Train the same model+data twice — plain Executor vs ParallelExecutor
    over the 8-device mesh — and require identical loss trajectories
    (reference check_network_convergence contract)."""

    def train(use_pe):
        main = framework.Program()
        startup = framework.Program()
        with fluid.unique_name.guard():
            with fluid.program_guard(main, startup):
                loss, feed_names = build_fn()
                optimizer_fn().minimize(loss)
        exe = fluid.Executor(fluid.CPUPlace())
        losses = []
        with scope_guard(Scope(seed=seed)):
            exe.run(startup)
            pe = (
                fluid.ParallelExecutor(
                    use_cuda=False, loss_name=loss.name, main_program=main
                )
                if use_pe
                else None
            )
            for batch in batches:
                feed = dict(zip(feed_names, batch))
                if use_pe:
                    (l,) = pe.run(fetch_list=[loss.name], feed=feed)
                else:
                    (l,) = exe.run(main, feed=feed, fetch_list=[loss.name])
                losses.append(float(np.asarray(l).reshape(-1)[0]))
        return losses

    single = train(False)
    multi = train(True)
    np.testing.assert_allclose(single, multi, rtol=rtol, atol=atol)
    assert np.isfinite(multi).all()
    if require_decrease:
        assert multi[-1] < multi[0], multi
    return multi


def build_model():
    x = fluid.layers.data(name="x", shape=[16], dtype="float32")
    y = fluid.layers.data(name="y", shape=[1], dtype="int64")
    h = fluid.layers.fc(x, size=32, act="relu")
    logits = fluid.layers.fc(h, size=4)
    loss = fluid.layers.mean(
        fluid.layers.softmax_with_cross_entropy(logits, y)
    )
    return loss


def make_data(rng, n):
    x = rng.randn(n, 16).astype("float32")
    y = (np.abs(x[:, :4]).argmax(1)).astype("int64").reshape(n, 1)
    return x, y


def train(use_pe, batches, seed=3):
    main = framework.Program()
    startup = framework.Program()
    with fluid.unique_name.guard():
        with fluid.program_guard(main, startup):
            loss = build_model()
            fluid.optimizer.SGD(learning_rate=0.05).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    losses = []
    with scope_guard(Scope(seed=seed)):
        exe.run(startup)
        runner = (
            fluid.ParallelExecutor(use_cuda=False, loss_name=loss.name, main_program=main)
            if use_pe
            else None
        )
        for x, y in batches:
            if use_pe:
                (l,) = runner.run(fetch_list=[loss.name], feed={"x": x, "y": y})
            else:
                (l,) = exe.run(main, feed={"x": x, "y": y}, fetch_list=[loss.name])
            losses.append(float(np.asarray(l).reshape(-1)[0]))
    return losses


def test_pe_matches_single_device_convergence():
    rng = np.random.RandomState(0)
    batches = [make_data(rng, 64) for _ in range(20)]
    single = train(False, batches)
    multi = train(True, batches)
    # same data, same init seed → identical trajectories up to fp reduction order
    np.testing.assert_allclose(single, multi, rtol=2e-3, atol=2e-4)
    assert multi[-1] < multi[0] * 0.9


def test_pe_rejects_indivisible_batch():
    import jax

    main = framework.Program()
    startup = framework.Program()
    with fluid.unique_name.guard():
        with fluid.program_guard(main, startup):
            loss = build_model()
            fluid.optimizer.SGD(learning_rate=0.05).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    with scope_guard(Scope()):
        exe.run(startup)
        pe = fluid.ParallelExecutor(main_program=main, loss_name=loss.name)
        if pe.device_count > 1:
            rng = np.random.RandomState(0)
            x, y = make_data(rng, pe.device_count + 1)
            try:
                pe.run(fetch_list=[loss.name], feed={"x": x, "y": y})
                raise AssertionError("expected ValueError for indivisible batch")
            except ValueError:
                pass


def _zero1_strategy():
    from paddle_tpu.parallel_executor import BuildStrategy, ReduceStrategy

    s = BuildStrategy()
    s.reduce_strategy = ReduceStrategy.Reduce
    return s


def _build_adam_program(moment_dtype=None):
    main = framework.Program()
    startup = framework.Program()
    with fluid.unique_name.guard():
        with fluid.program_guard(main, startup):
            loss = build_model()
            fluid.optimizer.Adam(
                learning_rate=0.01, moment_dtype=moment_dtype
            ).minimize(loss)
    return main, startup, loss


def test_pe_zero1_matches_allreduce_convergence():
    """ReduceStrategy.Reduce (ZeRO-1: reduce-scatter grads, sharded moments,
    all-gather params) must produce the same loss trajectory as the
    replicated all-reduce path — the update math is identical, only its
    placement changes. Run with the bench default bf16 moments so the
    sharding constraints compose with the moment down-cast."""
    rng = np.random.RandomState(7)
    batches = [make_data(rng, 64) for _ in range(6)]

    def run(strategy):
        main, startup, loss = _build_adam_program(moment_dtype="bfloat16")
        exe = fluid.Executor(fluid.CPUPlace())
        losses = []
        scope = Scope(seed=3)
        with scope_guard(scope):
            exe.run(startup)
            pe = fluid.ParallelExecutor(
                loss_name=loss.name, main_program=main, build_strategy=strategy,
                scope=scope,
            )
            for x, y in batches:
                (l,) = pe.run(fetch_list=[loss.name], feed={"x": x, "y": y})
                losses.append(float(np.asarray(l).reshape(-1)[0]))
        return losses, pe, scope

    base, base_pe, _ = run(None)  # default BuildStrategy = AllReduce
    z1, z1_pe, z1_scope = run(_zero1_strategy())
    np.testing.assert_allclose(base, z1, rtol=2e-3, atol=2e-4)
    assert z1[-1] < z1[0], z1

    if z1_pe.device_count > 1:
        compiled = z1_pe._last_run[0]
        names = compiled.zero1_state_names
        # the fc weights' moments are divisible by dp and must be sharded;
        # bf16 moment storage must survive the constraint plumbing
        assert names, "zero1 run sharded no optimizer state"
        for n in names:
            val = z1_scope.vars[n]
            assert "dp" in val.sharding.spec, (n, val.sharding)
            assert str(val.dtype) == "bfloat16", (n, val.dtype)
        # replicated path keeps all state whole on every chip
        assert not base_pe._last_run[0].zero1_state_names


def _wire(strategy):
    """One Adam step of the MLP under `strategy`: (pe, program, collective
    rows of the compiled step, optimizer-state bytes a chip holds)."""
    from hlo_collectives import collectives

    rng = np.random.RandomState(7)
    x, y = make_data(rng, 64)
    main, startup, loss = _build_adam_program()
    scope = Scope(seed=3)
    with scope_guard(scope):
        fluid.Executor(fluid.CPUPlace()).run(startup)
        pe = fluid.ParallelExecutor(
            loss_name=loss.name, main_program=main,
            build_strategy=strategy, scope=scope,
        )
        pe.run(fetch_list=[loss.name], feed={"x": x, "y": y})
        rows = collectives(pe.compiled_hlo(), pe._mesh)
        state = sum(
            v.addressable_shards[0].data.nbytes
            for n, v in scope.vars.items()
            if "_acc" in n and hasattr(v, "addressable_shards")
        )
    return pe, main, rows, state


def test_pe_allreduce_reduces_the_gradients_and_gathers_nothing():
    """The wire signature of the replicated dp step, read from the compiled
    step: it reduce-combines the gradient bytes (+ the scalar loss) once and
    all-gathers nothing. Bytes, not opcodes: see hlo_collectives."""
    from hlo_collectives import gathered_bytes, reduced_bytes, within

    pe, main, rows, _ = _wire(None)
    if pe.device_count < 2:
        return
    grads = sum(int(np.prod(p.shape)) * 4
                for p in main.global_block().all_parameters())
    assert within(reduced_bytes(rows), grads), (reduced_bytes(rows), grads)
    assert gathered_bytes(rows) == 0, rows


def test_pe_zero1_gathers_what_it_shards():
    """ZeRO-1's: it reduce-combines the same gradient bytes, all-gathers each
    dp-shardable parameter once (the updated shards return to every rank) and
    keeps less optimizer state a chip than the replicated step."""
    from hlo_collectives import gathered_bytes, reduced_bytes, within

    base_pe, _, _, base_state = _wire(None)
    if base_pe.device_count < 2:
        return
    z1_pe, main, rows, z1_state = _wire(_zero1_strategy())
    params = main.global_block().all_parameters()
    grads = sum(int(np.prod(p.shape)) * 4 for p in params)
    shardable = sum(int(np.prod(p.shape)) * 4 for p in params
                    if p.shape[0] % z1_pe.device_count == 0)
    assert within(reduced_bytes(rows), grads), (reduced_bytes(rows), grads)
    assert within(gathered_bytes(rows), shardable), (
        gathered_bytes(rows), shardable)
    assert z1_state < base_state, (z1_state, base_state)


def test_pe_zero1_checkpoint_roundtrip(tmp_path):
    """Crash-safe checkpointing of a ZeRO-1 run: moments live sharded over
    'dp', the snapshot gathers them to host, and a resume into a FRESH scope
    continues the trajectory exactly (steps 4-6 equal the uninterrupted
    run's)."""
    import jax.numpy as jnp

    from paddle_tpu.resilience.checkpoint import (
        load_latest_valid,
        save_checkpoint,
        snapshot_persistables,
    )

    rng = np.random.RandomState(11)
    batches = [make_data(rng, 64) for _ in range(6)]
    root = str(tmp_path / "z1ckpt")

    def step_range(scope, main, loss, lo, hi):
        pe = fluid.ParallelExecutor(
            loss_name=loss.name, main_program=main,
            build_strategy=_zero1_strategy(), scope=scope,
        )
        out = []
        for x, y in batches[lo:hi]:
            (l,) = pe.run(fetch_list=[loss.name], feed={"x": x, "y": y})
            out.append(float(np.asarray(l).reshape(-1)[0]))
        return out

    # uninterrupted reference run
    main, startup, loss = _build_adam_program()
    exe = fluid.Executor(fluid.CPUPlace())
    with scope_guard(Scope(seed=3)):
        exe.run(startup)
        full = step_range(global_scope(), main, loss, 0, 6)

    # run 3 steps, checkpoint (sharded moments gather to host here), crash
    main, startup, loss = _build_adam_program()
    with scope_guard(Scope(seed=3)):
        exe.run(startup)
        head = step_range(global_scope(), main, loss, 0, 3)
        save_checkpoint(root, snapshot_persistables(main), step=3)

    # fresh scope + startup, overlay the checkpoint, continue
    main, startup, loss = _build_adam_program()
    with scope_guard(Scope(seed=3)):
        exe.run(startup)
        step, arrays = load_latest_valid(root)
        assert step == 3
        sc = global_scope()
        for name, arr in arrays.items():
            sc.set_var(name, jnp.asarray(arr))
        tail = step_range(sc, main, loss, 3, 6)

    np.testing.assert_allclose(head + tail, full, rtol=2e-3, atol=2e-4)


def test_pe_se_resnext_convergence():
    """reference test_parallel_executor_seresnext.py: tiny structurally-exact
    SE-ResNeXt instance (conv/bn/group-conv/SE blocks) under PE."""
    from paddle_tpu.models.se_resnext import SE_ResNeXt

    def build():
        img = fluid.layers.data(name="img", shape=[3, 32, 32], dtype="float32")
        label = fluid.layers.data(name="label", shape=[1], dtype="int64")
        net = SE_ResNeXt(
            depth_override=[1, 1, 1, 1], filters_override=[32, 32, 32, 32]
        )
        logits = net.net(img, class_dim=4)
        loss = fluid.layers.mean(
            fluid.layers.softmax_with_cross_entropy(logits, label)
        )
        return loss, ["img", "label"]

    rng = np.random.RandomState(1)
    batches = [
        (
            rng.randn(8, 3, 32, 32).astype("float32"),
            rng.randint(0, 4, (8, 1)).astype("int64"),
        )
        for _ in range(3)
    ]
    _check_convergence(
        build,
        batches,
        lambda: fluid.optimizer.Momentum(learning_rate=0.05, momentum=0.9),
        rtol=5e-3,
        atol=5e-4,
        require_decrease=False,  # 3 steps: equivalence is the contract here
    )


def test_pe_transformer_convergence():
    """reference test_parallel_executor_transformer.py: the dense transformer
    (encoder+decoder stacks) trains identically under PE."""
    from paddle_tpu.models.transformer import transformer

    t, vocab = 8, 32

    def build():
        feeds = {}
        for name, shape, dtype in [
            ("src_word", [t], "int64"),
            ("src_pos", [t], "int64"),
            ("trg_word", [t], "int64"),
            ("trg_pos", [t], "int64"),
            ("lbl", [t], "int64"),
            ("lbl_w", [t, 1], "float32"),
        ]:
            feeds[name] = fluid.layers.data(name=name, shape=shape, dtype=dtype)
        loss, _logits = transformer(
            feeds["src_word"], feeds["src_pos"], feeds["trg_word"],
            feeds["trg_pos"], None, None, None, feeds["lbl"], feeds["lbl_w"],
            src_vocab_size=vocab, trg_vocab_size=vocab,
            n_layer=1, n_head=2, d_model=16, d_inner=32, d_key=8, d_value=8,
            dropout=0.0, max_length=t + 1,
        )
        return loss, ["src_word", "src_pos", "trg_word", "trg_pos", "lbl", "lbl_w"]

    rng = np.random.RandomState(2)
    pos = np.tile(np.arange(t), (8, 1)).astype("int64")
    batches = [
        (
            rng.randint(0, vocab, (8, t)).astype("int64"), pos,
            rng.randint(0, vocab, (8, t)).astype("int64"), pos,
            rng.randint(0, vocab, (8, t)).astype("int64"),
            np.ones((8, t, 1), "float32"),
        )
        for _ in range(5)
    ]
    _check_convergence(
        build, batches, lambda: fluid.optimizer.Adam(learning_rate=0.01),
        rtol=5e-3, atol=5e-4,
    )


def test_pe_crf_convergence():
    """reference test_parallel_executor_crf.py: embedding + GRU + linear-chain
    CRF (the label-semantic-roles shape) trains identically under PE."""
    V, TAGS, T = 24, 4, 6

    def build():
        words = fluid.layers.data(
            name="words", shape=[-1, T, 1], dtype="int64", append_batch_size=False
        )
        tags = fluid.layers.data(
            name="tags", shape=[-1, T, 1], dtype="int64", append_batch_size=False
        )
        wlen = fluid.layers.data(
            name="wlen", shape=[-1], dtype="int64", append_batch_size=False
        )
        emb = fluid.layers.embedding(words, size=[V, 8])
        emb._len_name = "wlen"
        proj = fluid.layers.fc(emb, size=12 * 3, num_flatten_dims=2)
        proj._len_name = "wlen"
        gru = fluid.layers.dynamic_gru(proj, size=12)
        emission = fluid.layers.fc(gru, size=TAGS, num_flatten_dims=2)
        emission._len_name = "wlen"
        crf_cost = fluid.layers.linear_chain_crf(
            emission, tags, param_attr=fluid.ParamAttr(name="crfw")
        )
        loss = fluid.layers.mean(crf_cost)
        return loss, ["words", "tags", "wlen"]

    rng = np.random.RandomState(3)
    batches = []
    for _ in range(5):
        ws = rng.randint(0, V, (8, T, 1)).astype("int64")
        batches.append(
            (ws, (ws % TAGS).astype("int64"),
             rng.randint(2, T + 1, (8,)).astype("int64"))
        )
    _check_convergence(
        build, batches, lambda: fluid.optimizer.Adam(learning_rate=0.02),
        rtol=5e-3, atol=5e-4,
    )


def test_pe_while_train_convergence():
    """reference test_parallel_executor_test_while_train: the forward pass
    contains a bounded While (lowered to the differentiable masked scan), and
    training through it matches single-device under PE."""
    T = 3

    def build():
        x = fluid.layers.data(name="x", shape=[8], dtype="float32")
        y = fluid.layers.data(name="y", shape=[1], dtype="float32")
        h = fluid.layers.fc(x, size=8, act="relu")
        i = fluid.layers.fill_constant([1], "int64", 0)
        n = fluid.layers.fill_constant([1], "int64", T)
        acc = fluid.layers.fc(h, size=8)
        cond = fluid.layers.less_than(i, n)
        w = fluid.layers.While(cond, maximum_iterations=T)
        with w.block():
            nxt = fluid.layers.scale(acc, scale=0.5)
            fluid.layers.assign(nxt, acc)
            fluid.layers.increment(i, value=1, in_place=True)
            fluid.layers.less_than(i, n, cond=cond)
        pred = fluid.layers.fc(acc, size=1)
        loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
        return loss, ["x", "y"]

    rng = np.random.RandomState(4)
    W = rng.rand(8, 1).astype("float32")
    batches = []
    for _ in range(8):
        xb = rng.rand(16, 8).astype("float32")
        batches.append((xb, xb @ W))
    _check_convergence(
        build, batches, lambda: fluid.optimizer.SGD(learning_rate=0.1)
    )
