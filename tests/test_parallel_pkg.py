"""parallel/ package tests on the 8-device CPU mesh: ring attention exactness
vs plain attention, sharded embedding vs dense lookup, mesh config, and
collective wrappers."""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from paddle_tpu.parallel import MeshConfig, collectives, make_mesh
from paddle_tpu.parallel.ring_attention import ring_attention, ring_attention_sharded
from paddle_tpu.parallel.sharded_embedding import sharded_embedding_lookup


def test_mesh_config_resolution():
    cfg = MeshConfig(dp=-1, sp=4)
    assert cfg.resolve(8) == {
        "dp": 2, "fsdp": 1, "tp": 1, "sp": 4, "ep": 1, "pp": 1
    }
    with pytest.raises(ValueError):
        MeshConfig(dp=3).resolve(8)
    with pytest.raises(ValueError):
        MeshConfig(dp=-1, tp=-1).resolve(8)


def _qkv(rng, b=2, h=2, t=16, d=8):
    return (
        rng.randn(b, h, t, d).astype("float32"),
        rng.randn(b, h, t, d).astype("float32"),
        rng.randn(b, h, t, d).astype("float32"),
    )


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_plain(causal):
    rng = np.random.RandomState(0)
    q, k, v = _qkv(rng)
    mesh = make_mesh(MeshConfig(dp=2, sp=4))
    ref = ring_attention(q, k, v, causal=causal)  # plain path
    out = ring_attention_sharded(q, k, v, mesh, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_ring_attention_grads_match():
    rng = np.random.RandomState(1)
    q, k, v = _qkv(rng, t=8)
    mesh = make_mesh(MeshConfig(dp=2, sp=4))

    def loss_ring(q, k, v):
        return jnp.sum(ring_attention_sharded(q, k, v, mesh, causal=True) ** 2)

    def loss_plain(q, k, v):
        return jnp.sum(ring_attention(q, k, v, causal=True) ** 2)

    g_ring = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
    g_plain = jax.grad(loss_plain, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ring, g_plain):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=5e-4, atol=5e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_flash_matches_dense_ring_and_plain(causal):
    """The Pallas flash ring (use_flash=True) agrees with both the dense
    einsum ring and single-device attention — forward AND gradients
    (ring-vs-dense gradients on a >1 sp mesh)."""
    rng = np.random.RandomState(3)
    q, k, v = _qkv(rng, b=2, h=2, t=32, d=8)
    mesh = make_mesh(MeshConfig(dp=2, sp=4))

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) ** 2)

    flash = functools.partial(
        ring_attention_sharded, mesh=mesh, causal=causal, use_flash=True
    )
    dense = functools.partial(
        ring_attention_sharded, mesh=mesh, causal=causal, use_flash=False
    )
    plain = functools.partial(ring_attention, causal=causal)

    np.testing.assert_allclose(
        np.asarray(flash(q, k, v)), np.asarray(plain(q, k, v)),
        rtol=2e-5, atol=2e-5,
    )
    g_flash = jax.grad(loss(flash), argnums=(0, 1, 2))(q, k, v)
    g_dense = jax.grad(loss(dense), argnums=(0, 1, 2))(q, k, v)
    g_plain = jax.grad(loss(plain), argnums=(0, 1, 2))(q, k, v)
    for a, b_, c in zip(g_flash, g_dense, g_plain):
        np.testing.assert_allclose(np.asarray(a), np.asarray(c), rtol=5e-4, atol=5e-5)
        np.testing.assert_allclose(np.asarray(b_), np.asarray(c), rtol=5e-4, atol=5e-5)


def test_ring_flash_ragged_falls_back():
    """Ragged t_local (flash tiles impossible) auto-selects the dense ring;
    forcing use_flash=True raises."""
    from paddle_tpu.parallel.ring_attention import _flash_tiles_ok

    rng = np.random.RandomState(4)
    # _auto_block admits any t_loc <= the block target as one whole tile, so
    # ragged means: above the conservative 512 target AND not a multiple of
    # 128 (t_loc=520 -> no 128*2^k divisor, too big for a single 512-tile;
    # the causal ring's diagonal chunk resolves causal (512,512) blocks, so
    # the predicate MUST stay gated on the tightest target or the ring
    # auto-selects flash and the dense-fallback chunk returns no lse)
    assert _flash_tiles_ok(130)  # small non-multiples ride one whole tile
    assert not _flash_tiles_ok(520)
    q, k, v = _qkv(rng, b=2, h=1, t=4 * 520, d=8)
    mesh = make_mesh(MeshConfig(dp=2, sp=4))
    out = ring_attention_sharded(q, k, v, mesh, causal=True)  # auto -> dense
    ref = ring_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)
    with pytest.raises(ValueError):
        ring_attention_sharded(q, k, v, mesh, causal=True, use_flash=True)


def test_sharded_embedding_matches_dense():
    rng = np.random.RandomState(2)
    table = rng.randn(64, 16).astype("float32")
    ids = rng.randint(0, 64, (4, 7)).astype("int32")
    mesh = make_mesh(MeshConfig(dp=1, ep=8))
    out = sharded_embedding_lookup(table, ids, mesh, axis_name="ep")
    np.testing.assert_allclose(np.asarray(out), table[ids], rtol=1e-6)


def test_full_mesh_training_matches_single_device():
    """A model using every parallelism kind — dp (batch), tp (sharded fc
    weight), sp (ring attention), ep (sharded embedding) — trains under
    ParallelExecutor on a dp2×sp2×ep2 mesh and matches single-device losses."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu import framework
    from paddle_tpu.executor import Scope, scope_guard
    from paddle_tpu.parallel import MeshConfig, shard_parameter

    VOCAB, D, HEADS, T = 64, 16, 2, 8

    def build():
        main, startup = framework.Program(), framework.Program()
        with fluid.unique_name.guard(), fluid.program_guard(main, startup):
            tok = fluid.layers.data(
                name="tok", shape=[-1, T, 1], dtype="int64", append_batch_size=False
            )
            label = fluid.layers.data(
                name="label", shape=[-1, 1], dtype="int64", append_batch_size=False
            )
            emb = fluid.layers.distributed_embedding(tok, size=[VOCAB, D])
            qkv = fluid.layers.fc(emb, size=3 * D, num_flatten_dims=2, bias_attr=False)
            # tp-shard the qkv projection's weight columns
            params = main.global_block().all_parameters()
            for p in params:
                if p.shape == (D, 3 * D):
                    shard_parameter(p, (None, "tp"))
            q, k, v = fluid.layers.split(qkv, 3, dim=2)

            def heads(x):
                r = fluid.layers.reshape(x, [0, 0, HEADS, D // HEADS])
                return fluid.layers.transpose(r, [0, 2, 1, 3])

            att = fluid.layers.ring_attention(heads(q), heads(k), heads(v), causal=True)
            att = fluid.layers.transpose(att, [0, 2, 1, 3])
            att = fluid.layers.reshape(att, [0, 0, D])
            pooled = fluid.layers.reduce_mean(att, dim=[1])
            logits = fluid.layers.fc(pooled, size=4)
            loss = fluid.layers.mean(
                fluid.layers.softmax_with_cross_entropy(logits, label)
            )
            fluid.optimizer.SGD(0.1).minimize(loss)
        return main, startup, loss

    rng = np.random.RandomState(0)
    batches = [
        (
            rng.randint(0, VOCAB, (8, T, 1)).astype("int64"),
            rng.randint(0, 4, (8, 1)).astype("int64"),
        )
        for _ in range(4)
    ]

    def train(use_pe):
        main, startup, loss = build()
        exe = fluid.Executor()
        out = []
        with scope_guard(Scope(seed=3)):
            exe.run(startup)
            pe = (
                fluid.ParallelExecutor(
                    main_program=main,
                    loss_name=loss.name,
                    mesh_config=MeshConfig(dp=2, tp=1, sp=2, ep=2),
                )
                if use_pe
                else None
            )
            for tok, lbl in batches:
                feed = {"tok": tok, "label": lbl}
                if use_pe:
                    (l,) = pe.run(fetch_list=[loss.name], feed=feed)
                else:
                    (l,) = exe.run(main, feed=feed, fetch_list=[loss.name])
                out.append(float(np.asarray(l).reshape(-1)[0]))
        return out

    single = train(False)
    multi = train(True)
    np.testing.assert_allclose(single, multi, rtol=5e-3, atol=5e-4)


def test_collective_wrappers():
    mesh = make_mesh(MeshConfig(dp=8))
    x = np.arange(8, dtype="float32").reshape(8, 1)

    def body(x):
        s = collectives.all_reduce(x, "dp")
        idx = collectives.axis_index("dp").astype(jnp.float32)
        rot = collectives.ppermute_shift(x, "dp", 1)
        b = collectives.broadcast(x, "dp", root=3)
        return s, idx.reshape(1, 1), rot, b

    fn = collectives.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(("dp",), None),),
        out_specs=(P(("dp",), None),) * 4,
    )
    s, idx, rot, b = fn(x)
    np.testing.assert_allclose(np.asarray(s).reshape(-1), [28.0] * 8)
    np.testing.assert_allclose(np.asarray(idx).reshape(-1), np.arange(8))
    np.testing.assert_allclose(np.asarray(rot).reshape(-1), np.roll(np.arange(8), 1))
    np.testing.assert_allclose(np.asarray(b).reshape(-1), [3.0] * 8)


def test_ring_flash_composes_with_streamed_kernels():
    """The flash ring calls pk._flash_forward/_flash_backward per ring step;
    when t_local exceeds the VMEM residency threshold those take the
    streamed long-context tier. Force the streamed tier on small shapes and
    check ring-vs-dense forward and gradient agreement still holds."""
    from paddle_tpu.ops import pallas_kernels as pk
    from paddle_tpu.parallel.ring_attention import ring_attention_sharded

    orig = pk._resident_ok
    pk._resident_ok = lambda *a: False
    try:
        rng = np.random.RandomState(11)
        q, k, v = _qkv(rng, b=2, h=2, t=512, d=16)
        mesh = make_mesh(MeshConfig(dp=2, sp=4))
        out = ring_attention_sharded(q, k, v, mesh, causal=True, use_flash=True)
        ref = ring_attention(q, k, v, causal=True)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=2e-3, atol=2e-3
        )

        def loss_ring(q, k, v):
            return jnp.sum(
                ring_attention_sharded(q, k, v, mesh, causal=True, use_flash=True)
                ** 2
            )

        def loss_dense(q, k, v):
            return jnp.sum(ring_attention(q, k, v, causal=True) ** 2)

        g1 = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            scale = max(1.0, float(jnp.max(jnp.abs(b))))
            np.testing.assert_allclose(
                np.asarray(a) / scale, np.asarray(b) / scale, rtol=2e-3, atol=2e-3
            )
    finally:
        pk._resident_ok = orig
