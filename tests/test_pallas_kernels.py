"""Flash-attention Pallas kernel vs the dense XLA reference (the OpTest
numerics contract for the hand-tuned kernel tier, SURVEY.md §7.9)."""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops.pallas_kernels import _attention_reference, flash_attention

# On the real chip (scripts/optest_tpu.py lane) the f32-input comparisons
# against the numpy/dense reference need the MXU-noise bar: default-precision
# f32 dots execute as fast bf16 passes (~2^-9 relative per product, sqrt(K)
# absolute cancellation noise) — the same policy as op_test.py's
# MXU-crossing tolerance scale. CPU interpret mode keeps the tight bar.
_ON_TPU = os.environ.get("PADDLE_OPTEST_PLACE", "cpu").lower() == "tpu"
_RTOL = 2e-2 if _ON_TPU else 2e-4
_ATOL = 2e-2 if _ON_TPU else 2e-5


@pytest.mark.parametrize("causal", [False, True])
def test_flash_matches_dense(causal):
    rng = np.random.RandomState(0)
    b, h, t, d = 2, 3, 256, 32
    q = jnp.asarray(rng.randn(b, h, t, d).astype("float32"))
    k = jnp.asarray(rng.randn(b, h, t, d).astype("float32"))
    v = jnp.asarray(rng.randn(b, h, t, d).astype("float32"))
    out = flash_attention(q, k, v, causal, None, 128, 128)
    ref = _attention_reference(q, k, v, causal, d**-0.5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=_RTOL, atol=_ATOL)


def test_flash_grads_match_dense():
    rng = np.random.RandomState(1)
    b, h, t, d = 1, 2, 128, 16
    q = jnp.asarray(rng.randn(b, h, t, d).astype("float32"))
    k = jnp.asarray(rng.randn(b, h, t, d).astype("float32"))
    v = jnp.asarray(rng.randn(b, h, t, d).astype("float32"))

    def loss_flash(q, k, v):
        return flash_attention(q, k, v, True).sum()

    def loss_dense(q, k, v):
        return _attention_reference(q, k, v, True, d**-0.5).sum()

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_dense = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for gf, gd in zip(g_flash, g_dense):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gd), rtol=_RTOL, atol=_ATOL)


def test_ragged_tail_falls_back():
    rng = np.random.RandomState(2)
    q = jnp.asarray(rng.randn(1, 1, 100, 16).astype("float32"))  # 100 % 128 != 0
    out = flash_attention(q, q, q, False)
    ref = _attention_reference(q, q, q, False, 16**-0.5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=_RTOL, atol=_ATOL)


def test_flash_attention_graph_op():
    import paddle_tpu.fluid as fluid
    from paddle_tpu import framework
    from paddle_tpu.executor import Executor, Scope, scope_guard

    rng = np.random.RandomState(3)
    qkv = rng.randn(3, 1, 2, 128, 16).astype("float32")
    main = framework.Program()
    blk = main.global_block()
    for name, arr in zip("qkv", qkv):
        blk.create_var(name=name, shape=arr.shape, dtype="float32")
    blk.create_var(name="att_out", shape=None, dtype=None)
    blk.append_op(
        type="flash_attention",
        inputs={"Q": ["q"], "K": ["k"], "V": ["v"]},
        outputs={"Out": ["att_out"]},
        attrs={"causal": True},
    )
    exe = Executor(fluid.CPUPlace())
    with scope_guard(Scope()):
        (got,) = exe.run(
            main,
            feed={"q": qkv[0], "k": qkv[1], "v": qkv[2]},
            fetch_list=["att_out"],
        )
    ref = _attention_reference(
        jnp.asarray(qkv[0]), jnp.asarray(qkv[1]), jnp.asarray(qkv[2]), True, 16**-0.5
    )
    np.testing.assert_allclose(got, np.asarray(ref), rtol=_RTOL, atol=_ATOL)


def test_multi_head_attention_flash_path_trains():
    """use_flash=True in the transformer attention emits the Pallas op and
    the model still trains (grads flow through the custom vjp)."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu import framework
    from paddle_tpu.executor import Scope, scope_guard
    from paddle_tpu.models.transformer import multi_head_attention

    main, startup = framework.Program(), framework.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.layers.data(name="mha_x", shape=[128, 32], dtype="float32")
        label = fluid.layers.data(name="mha_y", shape=[128, 32], dtype="float32")
        out = multi_head_attention(
            x, x, x, None, d_key=8, d_value=8, d_model=32, n_head=4,
            dropout_rate=0.0, use_flash=True, causal=True,
        )
        loss = fluid.layers.mean(fluid.layers.square_error_cost(out, label))
        fluid.optimizer.Adam(learning_rate=0.01).minimize(loss)
    assert any(op.type == "flash_attention" for op in main.global_block().ops)

    rng = np.random.RandomState(4)
    xs = rng.randn(2, 128, 32).astype("float32")
    ys = rng.randn(2, 128, 32).astype("float32")
    exe = fluid.Executor(fluid.CPUPlace())
    losses = []
    with scope_guard(Scope(seed=0)):
        exe.run(startup)
        for _ in range(5):
            (lv,) = exe.run(
                main, feed={"mha_x": xs, "mha_y": ys}, fetch_list=[loss.name]
            )
            losses.append(float(np.asarray(lv).reshape(-1)[0]))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0]


def test_transformer_use_flash_end_to_end():
    """transformer(use_flash=True) emits flash_attention ops in the decoder
    self-attention and trains (unpadded batch)."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu import framework
    from paddle_tpu.executor import Scope, scope_guard
    from paddle_tpu.models.transformer import (
        build_tiny_flash_transformer,
        tiny_flash_transformer_feed,
    )

    main, startup = framework.Program(), framework.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        feeds, loss = build_tiny_flash_transformer()
        fluid.optimizer.Adam(learning_rate=0.01).minimize(loss)
    assert any(
        op.type == "flash_attention" for op in main.global_block().ops
    ), "flash op not emitted"

    feed = tiny_flash_transformer_feed(b=2)
    exe = fluid.Executor(fluid.CPUPlace())
    losses = []
    with scope_guard(Scope(seed=0)):
        exe.run(startup)
        for _ in range(4):
            (lv,) = exe.run(main, feed=feed, fetch_list=[loss.name])
            losses.append(float(np.asarray(lv).reshape(-1)[0]))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0]


def test_flash_streamed_long_context_tier():
    """The long-context streamed kernels (grid-tiled K/V with VMEM scratch
    accumulators — used when whole-side residency would overflow VMEM past
    ~8k tokens, pallas_kernels._resident_ok) match the dense reference for
    forward and gradients, causal and not. Forced on small shapes by
    patching the residency predicate; chip_smoke.py's kernels phase compiles
    the streamed tier on the chip."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops import pallas_kernels as pk

    orig = pk._resident_ok
    pk._resident_ok = lambda *a: False
    try:
        rng = np.random.RandomState(3)
        b, h, t, dh = 2, 2, 256, 32
        q = jnp.array(rng.randn(b, h, t, dh), jnp.float32)
        k = jnp.array(rng.randn(b, h, t, dh), jnp.float32)
        v = jnp.array(rng.randn(b, h, t, dh), jnp.float32)
        for causal in (False, True):
            out = pk.flash_attention(q, k, v, causal, None)
            ref = pk._attention_reference(q, k, v, causal, dh ** -0.5)
            np.testing.assert_allclose(
                np.asarray(out), np.asarray(ref), rtol=2e-2, atol=2e-2
            )
            g = jax.grad(
                lambda a, bb, c: jnp.sum(
                    pk.flash_attention(a, bb, c, causal, None) ** 2
                ),
                argnums=(0, 1, 2),
            )(q, k, v)
            gr = jax.grad(
                lambda a, bb, c: jnp.sum(
                    pk._attention_reference(a, bb, c, causal, dh ** -0.5) ** 2
                ),
                argnums=(0, 1, 2),
            )(q, k, v)
            for got, want in zip(g, gr):
                scale = max(1.0, float(jnp.max(jnp.abs(want))))
                np.testing.assert_allclose(
                    np.asarray(got) / scale, np.asarray(want) / scale,
                    rtol=2e-2, atol=2e-2,
                )
    finally:
        pk._resident_ok = orig


def test_lse_declaration_mirrors_lowering_decision():
    """layers.flash_attention must declare Lse exactly when the lowering
    takes the Pallas path (flash_path_taken), including the asymmetric case
    tq=512/tk=600 non-causal where the non-causal 1024 k target admits a
    whole 600-tile while the conservative symmetric predicate does not — a
    mismatch would silently drop the saved residual and fall back to the
    dense recompute-vjp backward."""
    import jax.numpy as jnp

    import paddle_tpu.fluid as fluid
    from paddle_tpu import framework
    from paddle_tpu.executor import Scope, scope_guard
    from paddle_tpu.ops import pallas_kernels as pk

    assert pk.flash_path_taken(512, 600, causal=False)
    # flash_tiles_ok gates on the TIGHTEST (causal 512) target so ring
    # callers can rely on it in either mode; 600 passes non-causal
    # flash_path_taken (1024 k target) but not the conservative predicate
    assert not pk.flash_tiles_ok(600)
    assert not pk.flash_tiles_ok(1200)
    assert not pk.flash_path_taken(512, 600, causal=True)  # causal k target 512

    main, startup = framework.Program(), framework.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        q = fluid.layers.data(name="fq", shape=[2, 512, 8], dtype="float32")
        k = fluid.layers.data(name="fk", shape=[2, 600, 8], dtype="float32")
        v = fluid.layers.data(name="fv", shape=[2, 600, 8], dtype="float32")
        out = fluid.layers.flash_attention(q, k, v, causal=False)
    op = next(o for o in main.global_block().ops if o.type == "flash_attention")
    assert "Lse" in op.outputs, "Lse must be declared for the pallas path"

    rng = np.random.RandomState(0)
    feed = {
        "fq": rng.randn(1, 2, 512, 8).astype("float32"),
        "fk": rng.randn(1, 2, 600, 8).astype("float32"),
        "fv": rng.randn(1, 2, 600, 8).astype("float32"),
    }
    exe = fluid.Executor(fluid.CPUPlace())
    with scope_guard(Scope(seed=0)):
        (got,) = exe.run(main, feed=feed, fetch_list=[out.name])
    want = pk._attention_reference(
        jnp.asarray(feed["fq"]), jnp.asarray(feed["fk"]), jnp.asarray(feed["fv"]),
        False, 8 ** -0.5,
    )
    np.testing.assert_allclose(
        got, np.asarray(want), rtol=max(_RTOL, 2e-3), atol=max(_ATOL, 2e-3)
    )


# --------------------------------------------------------------------------
# paged flash-attention decode kernel (serving fast path)
# --------------------------------------------------------------------------


def _paged_dense_ref(q, kp, vp, bt, pos, n_head, page_size):
    """The generation_ops dense lowering's math, in numpy f32 — the decline
    target the kernel must stay bit-bounded against."""
    s, feat = q.shape
    d = feat // n_head
    if bt.ndim == 1:
        bt = np.broadcast_to(bt, (s, bt.shape[0]))
    ctx = bt.shape[1] * page_size
    flat = (
        bt.astype(np.int64)[:, :, None] * page_size
        + np.arange(page_size)[None, None, :]
    ).reshape(s, ctx)
    k = kp[flat.reshape(-1)].reshape(s, ctx, n_head, d)
    v = vp[flat.reshape(-1)].reshape(s, ctx, n_head, d)
    qh = q.reshape(s, n_head, d)
    sc = np.einsum("shd,schd->shc", qh, k) * (d ** -0.5)
    live = (np.arange(ctx)[None, :] <= pos[:, None])[:, None, :]
    sc = np.where(live, sc, -np.inf)
    m = sc.max(-1, keepdims=True)
    m = np.where(np.isfinite(m), m, 0.0)
    w = np.where(live, np.exp(sc - m), 0.0)
    den = w.sum(-1, keepdims=True)
    w = w / np.where(den > 0.0, den, 1.0)
    return np.einsum("shc,schd->shd", w, v).reshape(s, feat).astype("float32")


def _paged_case(rng, slots, n_pages, pages_per_slot, n_head, d, page_size):
    feat = n_head * d
    q = rng.randn(slots, feat).astype("float32")
    kp = rng.randn(n_pages * page_size, feat).astype("float32")
    vp = rng.randn(n_pages * page_size, feat).astype("float32")
    bt = np.zeros((slots, pages_per_slot), np.int32)
    for s in range(slots):
        bt[s] = rng.choice(np.arange(1, n_pages), pages_per_slot, replace=False)
    return q, kp, vp, bt


def test_paged_flash_path_predicate():
    from paddle_tpu import flags
    from paddle_tpu.ops import pallas_kernels as pk

    saved = flags.get_flags("paged_flash")
    try:
        flags.set_flags({"paged_flash": "on"})
        assert pk.paged_flash_path_taken(4, 4, 8, 2, 8)
        flags.set_flags({"paged_flash": "off"})
        assert not pk.paged_flash_path_taken(4, 4, 8, 2, 8)
        flags.set_flags({"paged_flash": "auto"})
        import jax

        assert pk.paged_flash_path_taken(4, 4, 8, 2, 8) == (
            jax.default_backend() == "tpu"
        )
    finally:
        flags.set_flags(saved)


def test_paged_flash_decode_matches_dense_across_page_boundaries():
    """Per-slot block tables, ragged positions: mid-page, exactly on a page
    boundary, last row of the table, and a fully-masked (pos = -1) idle
    slot that must emit zeros."""
    from paddle_tpu.ops import pallas_kernels as pk

    rng = np.random.RandomState(11)
    n_head, d, ps = 2, 8, 4
    q, kp, vp, bt = _paged_case(rng, 5, 12, 3, n_head, d, ps)
    pos = np.array([2, 3, 4, 11, -1], dtype=np.int32)
    before = pk.KERNEL_DISPATCHES.get("paged_flash", 0)
    out = pk.paged_flash_attention(
        jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
        jnp.asarray(bt), jnp.asarray(pos),
        n_head=n_head, page_size=ps, interpret=True,
    )
    assert pk.KERNEL_DISPATCHES.get("paged_flash", 0) == before + 1
    ref = _paged_dense_ref(q, kp, vp, bt, pos, n_head, ps)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=_RTOL, atol=_ATOL)
    assert np.abs(np.asarray(out)[4]).max() == 0.0  # fully-masked row


def test_paged_flash_shared_table_matches_dense():
    """Chunked-prefill shape: one [P] page list shared by every chunk row,
    consecutive positions."""
    from paddle_tpu.ops import pallas_kernels as pk

    rng = np.random.RandomState(12)
    n_head, d, ps = 2, 8, 4
    q, kp, vp, _ = _paged_case(rng, 6, 10, 3, n_head, d, ps)
    bt1 = np.array([2, 7, 4], dtype=np.int32)
    pos = np.arange(5, 11, dtype=np.int32)  # chunk starting mid-page
    out = pk.paged_flash_attention(
        jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
        jnp.asarray(bt1), jnp.asarray(pos),
        n_head=n_head, page_size=ps, interpret=True,
    )
    ref = _paged_dense_ref(q, kp, vp, bt1, pos, n_head, ps)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=_RTOL, atol=_ATOL)


# The walk's own cases: a compute block is 2 pages of 4 rows here (the module
# constants are set to 2), so positions sit on both sides of a block's edge.
# name -> (positions, table width in pages, shared table?)
_WALK_CASES = {
    "idle slots on the scratch page": ([0, 0, 5], 6, False),
    "block - 1, block, block + 1": ([7, 8, 9], 6, False),
    "full context": ([23, 23, 0], 6, False),
    "fully masked rows emit zeros": ([-1, 12, -1], 6, False),
    "all rows masked": ([-1, -1, -1], 6, False),
    "table no whole number of blocks": ([19, 4, 16], 5, False),
    "table narrower than a block": ([3, 0, 2], 1, False),
    "shared and non-contiguous pages": ([21, 13, 17], 6, False),
    "shared table, rows on both sides of a block": ([5, 6, 7, 8, 9], 6, True),
    "shared table, full context": ([20, 21, 22, 23], 6, True),
    "shared table, odd width": ([15, 16, 17, 18, 19], 5, True),
    "shared table, all rows masked": ([-1, -1], 6, True),
}


def _int8_rows(x):
    """Symmetric per-row int8 levels and f32 scales, as kv_cache_write."""
    from paddle_tpu.ops.generation_ops import KV_QUANT_LEVELS as top

    scale = np.maximum(np.abs(x).max(axis=1), 1e-8) / top
    levels = np.clip(np.round(x / scale[:, None]), -top, top).astype("int8")
    return levels, scale.astype("float32")


@pytest.mark.parametrize("pool", ["f32", "int8"])
@pytest.mark.parametrize("case", sorted(_WALK_CASES))
def test_paged_flash_walk_matches_dense(case, pool, monkeypatch):
    """The walk bounded by pos over multi-page blocks, against the dense
    gather: every case on the f32 and the int8 pool."""
    from paddle_tpu.ops import pallas_kernels as pk

    monkeypatch.setattr(pk, "_PAGED_PAGES_PER_BLOCK", 2)
    monkeypatch.setattr(pk, "_PAGED_PAGES_PER_BLOCK_CHUNK", 2)
    pos, width, shared = _WALK_CASES[case]
    pos = np.array(pos, dtype=np.int32)
    rng = np.random.RandomState(len(case))
    n_head, d, ps = 2, 8, 4
    q, kp, vp, bt = _paged_case(rng, len(pos), 16, width, n_head, d, ps)
    if case == "idle slots on the scratch page":
        bt[:2] = 0
    if case == "shared and non-contiguous pages":
        bt[1, :3] = bt[0, :3]  # a shared prefix
        bt[2] = np.sort(bt[2])[::-1]
    if shared:
        bt = bt[0]
    scales = {}
    if pool == "int8":
        kp, ks = _int8_rows(kp)
        vp, vs = _int8_rows(vp)
        scales = dict(k_scales=jnp.asarray(ks), v_scales=jnp.asarray(vs))
    out = np.asarray(pk.paged_flash_attention(
        jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(bt),
        jnp.asarray(pos), n_head=n_head, page_size=ps, interpret=True,
        **scales,
    ))
    row_bytes = n_head * d * kp.dtype.itemsize
    if pool == "int8":  # the reference reads the dequantized rows
        kp = kp.astype("float32") * ks[:, None]
        vp = vp.astype("float32") * vs[:, None]
    ref = _paged_dense_ref(q, kp, vp, bt, pos, n_head, ps)
    np.testing.assert_allclose(out, ref, rtol=_RTOL, atol=_ATOL)
    assert np.abs(out[pos < 0]).max(initial=0.0) == 0.0
    # the counter mirrors the trips: blocks of 8 positions, capped at the table
    block = min(2, width) * ps
    trips = [0 if p < 0 else min(p // block + 1, -(-width * ps // block))
             for p in ([pos.max()] if shared else pos)]
    assert pk.paged_positions_walked(
        pos, ps, width, row_bytes, shared=shared
    ) == sum(trips) * block


def test_paged_block_follows_the_buffers_budget():
    """Wide rows or large pages shrink the block to what the four page
    buffers may take of VMEM; a narrow table bounds it too."""
    from paddle_tpu.ops import pallas_kernels as pk

    cell = 8 * 1280 * 4  # gpt2-large: page 8, 20 heads of 64, f32
    assert pk._paged_block_pages(128, cell, False) == pk._PAGED_PAGES_PER_BLOCK
    assert pk._paged_block_pages(128, cell, True) == (
        pk._PAGED_PAGES_PER_BLOCK_CHUNK)
    assert pk._paged_block_pages(3, cell, False) == 3
    assert pk._paged_block_pages(8, 128 * 2048 * 4, True) == 2
    assert pk._paged_block_pages(8, 1 << 30, True) == 1
    # what the engine reports as the most a step walks is the kernel's cap
    assert pk.paged_positions_walked(
        [1023] * 24, 8, 128, 1280 * 4) == 24 * 128 * 8
    assert pk.paged_positions_walked([0] * 24, 8, 128, 1280 * 4) == 24 * 64
