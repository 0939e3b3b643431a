"""The afmoe architecture (Arcee's Trinity) at a tiny size: three
sliding-window layers and a full one (window 16 positions, pages of 8),
rotary positions on the window layers and none on the full one, a sigmoid
gate on attention, sandwich norms, 8 experts top 2 beside a shared one,
through GenerationEngine's caches (the window layers in a page space of
their own whose pages go back to the pool as rows pass them), against the
plain reference (models/afmoe_reference.py) on seeded random weights:
logits, not tokens."""

import numpy as np
import pytest

from op_test import OpTest
from paddle_tpu import flags
from paddle_tpu.executor import Executor, Scope, scope_guard
from paddle_tpu.models import afmoe_reference as ref
from paddle_tpu.models.block_decoder import afmoe
from paddle_tpu.ops import registry
from paddle_tpu.serving.generation import GenerationEngine, GenRequest
from paddle_tpu.serving.kv_cache import PoolExhausted, WindowPages

# the published pattern in miniature: one leading dense layer, then
# [sliding, sliding, full] expert layers after the first window layer (the
# 3:1 period), 4 query heads on 2 KV heads of 8
AF = dict(
    model_type="afmoe", vocab_size=101, hidden_size=32, intermediate_size=48,
    moe_intermediate_size=16, num_attention_heads=4, num_key_value_heads=2,
    head_dim=8, num_hidden_layers=4,
    layer_types=["sliding_attention"] * 3 + ["full_attention"],
    sliding_window=16, num_dense_layers=1, num_experts=8,
    num_experts_per_tok=2, num_shared_experts=1, route_norm=True,
    route_scale=2.826, score_func="sigmoid", n_group=1, topk_group=1,
    num_expert_groups=1, num_limited_groups=1, mup_enabled=True,
    rms_norm_eps=1e-5, rope_theta=10000, rope_scaling=None,
    tie_word_embeddings=False, max_position_embeddings=128, hidden_act="silu",
    global_attn_every_n_layers=4, load_balance_coeff=0.001)
F32_TOL = 2e-5  # float32 both sides; the products differ in summation order


def _engine(dtype="float32", seed=5, held=None, **kw):
    model = afmoe(AF, max_context=96, dtype=dtype, experts_held=held)
    kw = dict(dict(max_slots=3, page_size=8, prefill_chunk=8, pool_pages=40),
              **kw)
    _engine.made += 1  # a name of its own: the registry counts by name
    eng = GenerationEngine(
        model, name="tafmoe%d" % _engine.made, scope=Scope(seed=seed), **kw)
    eng.record_picks = True
    return model, eng


_engine.made = 0


@pytest.fixture
def paged_flash(request):
    prev = flags.get_flags("paged_flash")
    flags.set_flags({"paged_flash": request.param})
    yield request.param
    flags.set_flags(prev)


def _weights(model, eng):
    return {n: eng.scope.vars[n] for n in model.param_names()}


def _prompt(n, seed=0):
    return [int(v) for v in np.random.default_rng(seed).integers(2, 101, n)]


def _by_position(parts, n):
    parts = [p for _, p in sorted(parts, key=lambda sp: sp[0])]
    return np.concatenate(parts, axis=1)[:, :n]


def _reference(model, eng, tokens, picks, **kw):
    """Reference logits following the system's picks; every pick the
    reference would not have made itself has to be a near tie."""
    out = ref.forward(_weights(model, eng), tokens, ref.spec_of(model),
                      picks=picks, **kw)
    assert float(np.max(out["pick_gaps"])) < 1e-4
    return np.asarray(out["logits"])


def test_afmoe_is_built_from_the_source_s_keys():
    model = afmoe(AF, experts_held=[0, 1, 2, 3])
    assert model.windows == [16, 16, 16, 0]
    assert model.positions == ["rotary", "rotary", "rotary", "none"]
    assert model.window() == 16 and model.attn_gate and model.sandwich_norm
    assert model.embed_scale == pytest.approx(32 ** 0.5)
    assert model.blocks == [("attention", "dense")] + [("attention", "moe")] * 3
    assert model.moe["scale"] == 2.826 and model.moe["shared_width"] == 16
    assert model.moe["experts_held"] == [0, 1, 2, 3] and not model.tied_head
    names = model.param_names()
    for part in ("g_w", "op_post_norm_w", "ffn_post_norm_w", "q_norm_w"):
        assert "afmoe_l3_" + part in names
    specs = model.cache_specs()
    assert [s.get("window") for s in specs] == [16] * 6 + [None] * 2


@pytest.mark.parametrize("key,value", [
    ("n_group", 2), ("score_func", "softmax"),
    ("layer_types", ["sliding_attention"] * 4 + ["full_attention"])])
def test_afmoe_refuses_a_form_it_does_not_build(key, value):
    with pytest.raises(ValueError):
        afmoe(dict(AF, **{key: value}))


def test_afmoe_forward_program_matches_the_reference():
    """Two sequences of 40 positions, past the 16-position window, through
    build_forward's dense program, against the reference's pass."""
    model, eng = _engine()
    batch, t = 2, 40
    tokens = np.array([_prompt(t, 1), _prompt(t, 2)], np.int64)
    main, _, _, fetches = model.build_forward(batch, t)
    with scope_guard(eng.scope):
        logits, picks = Executor().run(
            main, feed={"fwd_tokens": tokens[:, :, None]}, fetch_list=fetches)
    picks = np.asarray(picks).reshape(len(model.moe_layers()), batch, t, -1)
    for b in range(batch):
        want = _reference(model, eng, tokens[b], picks[:, b])
        np.testing.assert_allclose(np.asarray(logits)[b], want, atol=F32_TOL)


@pytest.mark.parametrize("paged_flash", ["off", "on"], ids=["dense", "kernel"],
                         indirect=True)
def test_afmoe_chunked_prefill_then_decode_matches_the_full_forward_pass(
        paged_flash):
    """37 prompt tokens in chunks of 8 (the chunk at 16 straddles the
    window's edge: its first row attends positions 1-16, its last 8-23),
    then eight decode steps to position 44, all through the window layers'
    own pages and the full layers' pages: the dense lowerings, and the
    Pallas kernel in interpret mode; first-token logits and every step's
    against the reference's one pass. A short prompt (under the window)
    decodes beside it in slot 2, the slot between them idle."""
    from paddle_tpu.ops import pallas_kernels as pk

    before = pk.KERNEL_DISPATCHES.get("paged_flash", 0)
    model, eng = _engine()
    prompt, other = _prompt(37), _prompt(11, 5)
    run = eng.start(GenRequest(prompt, max_new_tokens=9))
    got = [np.array(eng.last_prefill_logits)]
    gone = eng.start(GenRequest(_prompt(9, 6), max_new_tokens=2))
    run2 = eng.start(GenRequest(other, max_new_tokens=9))
    got2 = [np.array(eng.last_prefill_logits)]
    eng.finish(gone)
    assert (run.slot, gone.slot, run2.slot) == (0, 1, 2)
    for _ in range(8):
        eng.decode_step([run, run2])
        got.append(np.array(eng.last_logits[run.slot]))
        got2.append(np.array(eng.last_logits[run2.slot]))
    # the long row holds only its window's pages: positions 29-44
    held = np.flatnonzero(eng.window_pool.block_table(run.slot))
    assert held.tolist() == [3, 4, 5]
    for r in (run, run2):
        eng.finish(r)
    calls = eng.stats()["kernel_dispatches"].get("paged_flash", 0) - before
    assert (calls > 0) == (paged_flash == "on")
    for r, p, g in ((run, prompt, got), (run2, other, got2)):
        tokens = p + r.tokens[:8]
        want = _reference(model, eng, tokens, _by_position(r.picks, len(tokens)))
        for i, row in enumerate(g):
            np.testing.assert_allclose(row, want[len(p) - 1 + i], atol=F32_TOL)


def test_a_window_layer_reads_nothing_behind_its_window():
    """The reference with every window layer attending its whole context,
    put in the system's place, is far from the system: past the window the
    bound is not a rounding."""
    model, eng = _engine()
    prompt = _prompt(40, 3)
    run = eng.start(GenRequest(prompt, max_new_tokens=1))
    got = np.array(eng.last_prefill_logits)
    eng.finish(run)
    picks = _by_position(run.picks, len(prompt))
    want = _reference(model, eng, prompt, picks)[-1]
    far = ref.forward(_weights(model, eng), prompt, ref.spec_of(model),
                      picks=picks, full_window=True)
    np.testing.assert_allclose(got, want, atol=F32_TOL)
    assert np.abs(np.asarray(far["logits"])[-1] - got).max() > 100 * F32_TOL


def test_afmoe_prefix_hit_past_the_window_equals_a_cold_admission():
    """A document of five whole pages (40 positions, past the 16-position
    window), prefilled once, then a second prompt on it admitted through
    the prefix hit: all five full-layer pages and the window layers' last
    two come back (the pages behind stay the scratch page), and every logit
    is the cold engine's."""
    model, warm = _engine()
    _, cold = _engine(prefix_cache=False)
    doc = _prompt(40, 7)
    first, second = doc + _prompt(5, 8), doc + _prompt(7, 9)
    warm.finish(warm.start(GenRequest(first, max_new_tokens=2)))
    st = warm.prefix_cache.stats()
    assert st["cached_pages"] == 5 and st["cached_window_pages"] == 2
    run = warm.admit(GenRequest(second, max_new_tokens=4))
    assert run.pf_pos == 40 and warm.prefix_cache.stats()["pages_hit"] == 5
    assert np.flatnonzero(run.wtable).tolist() == [3, 4]
    while not warm.prefill_step(run):
        pass
    run_cold = cold.start(GenRequest(second, max_new_tokens=4))
    np.testing.assert_allclose(
        warm.last_prefill_logits, cold.last_prefill_logits, atol=F32_TOL)
    for _ in range(3):
        warm.decode_step([run])
        cold.decode_step([run_cold])
        np.testing.assert_allclose(
            warm.last_logits[run.slot], cold.last_logits[run_cold.slot],
            atol=F32_TOL)
    assert run.tokens == run_cold.tokens


def test_a_hit_stops_where_the_window_pages_end():
    """Nodes whose window pages were not kept cannot be hit through: the
    lookup cuts back to the deepest depth whose window is whole, here none
    (the document's window pages evicted), and the prompt prefills from 0."""
    model, eng = _engine()
    doc = _prompt(40, 7)
    eng.finish(eng.start(GenRequest(doc + _prompt(5, 8), max_new_tokens=2)))
    assert eng.prefix_cache.evict_window(100) == 2
    run = eng.admit(GenRequest(doc + _prompt(7, 9), max_new_tokens=2))
    assert run.pf_pos == 0
    eng.finish(run)


def test_a_row_short_of_window_pages_takes_those_only_the_cache_holds():
    """A window pool of four pages, two of them a cached document's window
    tail: a new row reserves four (the free two and the cache's two), and
    when its prefill finds the free list empty the engine evicts a cached
    window page for it; its tokens are those of a roomy engine."""
    model, eng = _engine(window_pool_pages=5)
    _, roomy = _engine()
    eng.finish(eng.start(GenRequest(_prompt(45, 7), max_new_tokens=2)))
    assert eng.prefix_cache.stats()["cached_window_pages"] == 2
    assert eng.window_pool.available() == 2
    assert eng._window_spare() == 2
    evicted, evict = [], eng.prefix_cache.evict_window
    eng.prefix_cache.evict_window = lambda n: evicted.append(evict(n)) or (
        evicted[-1])
    req = lambda: GenRequest(_prompt(40, 8), max_new_tokens=10)
    run, run_roomy = eng.start(req()), roomy.start(req())
    assert evicted and all(evicted)
    while not run.done:
        eng.decode_step([run])
        roomy.decode_step([run_roomy])
    assert run.tokens == run_roomy.tokens
    eng.finish(run)
    roomy.finish(run_roomy)


def test_window_pages_go_back_to_the_pool_as_a_row_passes_them():
    """A row decoding from position 10 to 60: its window pages are drawn
    as it reaches them and returned once the window has passed them, the
    pool's own count agreeing at every step with the pages in the row's
    table and the prefix cache's; what it holds never passes W + a page."""
    model, eng = _engine(prefix_cache=False)
    wp = eng.window_pool
    run = eng.start(GenRequest(_prompt(10, 4), max_new_tokens=50))
    held_most = 0
    while not run.done:
        eng.decode_step([run])
        table = wp.block_table(run.slot)
        held = np.flatnonzero(table)
        held_most = max(held_most, len(held))
        pos = run.next_pos - 1
        assert held.min() == max(0, pos - 16 + 1) // 8
        assert wp.stats()["pages_in_use"] == len(held)
    assert held_most <= 16 // 8 + 1
    assert wp.stats()["pages_returned"] == 60 // 8 - 2
    eng.finish(run)
    assert wp.stats()["pages_in_use"] == 0


def test_the_window_reservation_and_what_a_pool_admits():
    """A row reserves min(its pages, W + a chunk + a page) less a hit's
    pages; the pool admits it only while its free and reclaimable pages
    cover every live row's reserve."""
    wp = WindowPages(9, 8, 3, 12, 16, 8)
    assert wp.row_pages == 4
    assert wp.reservation(20) == 3 and wp.reservation(90) == 4
    assert wp.reservation(90, hit_pages=10) == 2
    wp.acquire(0, 90)
    wp.acquire(1, 90)
    assert wp.available() == 0
    with pytest.raises(PoolExhausted):
        wp.acquire(2, 8)
    wp.acquire(2, 8, spare=1)
    wp.advance(0, 0, 8)
    assert wp.stats()["pages_in_use"] == 1
    wp.release(0)
    assert wp.stats()["pages_in_use"] == 0


def test_the_engine_counts_what_the_window_layers_read():
    """gen_window_ctx_tokens: a decode step of rows at positions 30 and 5
    attends 16 + 6 positions on each window layer; gen_chunk_window_pairs
    and gen_chunk_window_tokens of a chunk of 8 at position 16."""
    from paddle_tpu.observability import registry as reg

    model, eng = _engine(prefix_cache=False)
    snap = lambda h: reg.default_registry().snapshot()[
        "serving/%s/gen_%s" % (eng.name, h)]["sum"]
    pairs0, tokens0 = snap("chunk_window_pairs"), snap("chunk_window_tokens")
    a = eng.start(GenRequest(_prompt(30, 1), max_new_tokens=3))
    assert snap("chunk_window_tokens") - tokens0 == 8 + 16 + 23 + 21
    # rows 0-15 attend 1-16 positions, rows 16-29 the window
    assert snap("chunk_window_pairs") - pairs0 == sum(range(1, 17)) + 14 * 16
    b = eng.start(GenRequest(_prompt(5, 2), max_new_tokens=3))
    before, held0 = snap("window_ctx_tokens"), snap("window_pages_held")
    eng.decode_step([a, b])
    assert snap("window_ctx_tokens") - before == 16 + 6
    assert snap("window_pages_held") - held0 == 3 + 1
    for r in (a, b):
        eng.finish(r)


def test_afmoe_expert_shares_and_the_shared_expert_add_up_to_the_whole_layer():
    """The two shares of an expert-parallel-2 layer in miniature (8
    experts top 2, four a share, as the chip holds 16 of 128): each share's
    routed part, plus the shared expert counted once, add up to the uncut
    reference's whole layer."""
    import jax.numpy as jnp

    rng = np.random.default_rng(4)
    rows, d, f, n_exp, k = 24, 16, 12, 8, 2
    x = rng.standard_normal((rows, d)).astype("float32")
    router_w = rng.standard_normal((d, n_exp)).astype("float32") * 0.5
    router_b = rng.standard_normal(n_exp).astype("float32") * 0.3
    w1, w3 = (rng.standard_normal((n_exp, d, f)).astype("float32") * 0.3
              for _ in range(2))
    w2 = rng.standard_normal((n_exp, f, d)).astype("float32") * 0.3
    s1, s3 = (rng.standard_normal((d, f)).astype("float32") * 0.3 for _ in range(2))
    s2 = rng.standard_normal((f, d)).astype("float32") * 0.3
    moe = {"num_experts": n_exp, "k": k, "norm_topk": True, "scale": 2.826}
    J = jnp.asarray
    whole, _, _ = ref.moe_layer(
        J(x), J(router_w), J(router_b), J(w1), J(w3), J(w2), moe)
    whole = np.asarray(whole + ref.swiglu_ff(J(x), J(s1), J(s3), J(s2)))
    lower = lambda t, ins, attrs: registry.get(t).lower(None, ins, attrs)
    routed = lower("moe_router", {"X": [J(x)], "W": [J(router_w)],
                                  "Bias": [J(router_b)]},
                   {"k": k, "norm_topk": True, "scale": 2.826})
    total = np.asarray(ref.swiglu_ff(J(x), J(s1), J(s3), J(s2)), np.float64)
    for share in range(2):
        held = list(range(4 * share, 4 * share + 4))
        part = lower("moe_experts", {
            "X": [J(x)], "Picks": routed["Picks"], "Weights": routed["Weights"],
            "W1": [J(w1[held])], "W3": [J(w3[held])], "W2": [J(w2[held])]},
            {"num_experts": n_exp, "experts_held": held})["Out"][0]
        total += np.asarray(part, np.float64)
        mine, _, _ = ref.moe_layer(
            J(x), J(router_w), J(router_b), J(w1[held]), J(w3[held]),
            J(w2[held]), dict(moe, experts_held=held))
        np.testing.assert_allclose(np.asarray(part), np.asarray(mine), atol=2e-4)
    np.testing.assert_allclose(total, whole, atol=5e-4)
    assert np.abs(whole).max() > 0.1


def _window_attention_np(q, kp, vp, bt, pos, n_head, n_kv, page_size, window):
    """float64 numpy: each row attends pos - window < s <= pos through its
    table (or the one shared table), query head i on KV head i // group."""
    rows, feat = q.shape
    d = feat // n_head
    tables = bt if bt.ndim == 2 else np.broadcast_to(bt, (rows,) + bt.shape)
    out = np.zeros((rows, n_head, d))
    for r in range(rows):
        flat = (tables[r][:, None] * page_size
                + np.arange(page_size)[None, :]).reshape(-1)
        k = kp[flat].reshape(-1, n_kv, d).astype(np.float64)
        v = vp[flat].reshape(-1, n_kv, d).astype(np.float64)
        at = np.arange(flat.size)
        live = (at <= pos[r]) & (at > pos[r] - window)
        for h in range(n_head):
            kv = h // (n_head // n_kv)
            sc = k[:, kv] @ q[r].reshape(n_head, d)[h] * d ** -0.5
            w = np.where(live, np.exp(sc - sc[live].max()), 0.0)
            out[r, h] = (w / w.sum()) @ v[:, kv]
    return out.reshape(rows, feat).astype("float32")


class TestWindowAttention(OpTest):
    """Decode rows, each its own table whose entries behind the window are
    the scratch page (0): a row near the start, one past several windows."""

    def setUp(self):
        self.op_type = "window_attention"
        rng = np.random.default_rng(3)
        n_head, n_kv, d, page_size, window = 4, 2, 4, 4, 6
        kp = rng.random((20 * page_size, n_kv * d)).astype("float32") - 0.5
        vp = rng.random((20 * page_size, n_kv * d)).astype("float32") - 0.5
        q = rng.random((3, n_head * d)).astype("float32") - 0.5
        bt = np.array([[1, 2, 0, 0, 0], [0, 0, 3, 4, 5], [0, 0, 0, 6, 7]],
                      dtype="int32")
        pos = np.array([4, 17, 13], dtype="int32")
        self.inputs = {"Q": q, "KPool": kp, "VPool": vp, "BlockTable": bt,
                       "Pos": pos}
        self.attrs = {"n_head": n_head, "page_size": page_size,
                      "n_kv_head": n_kv, "window": window}
        self.outputs = {"Out": _window_attention_np(
            q, kp, vp, bt, pos, n_head, n_kv, page_size, window)}

    def test_check_output(self):
        self.check_output()


class TestWindowAttentionSharedTable(OpTest):
    """A prefill chunk: one table shared by its rows, the first row's window
    reaching back past the entries already returned."""

    def setUp(self):
        self.op_type = "window_attention"
        rng = np.random.default_rng(4)
        n_head, n_kv, d, page_size, window = 4, 2, 4, 4, 6
        kp = rng.random((20 * page_size, n_kv * d)).astype("float32") - 0.5
        vp = rng.random((20 * page_size, n_kv * d)).astype("float32") - 0.5
        q = rng.random((5, n_head * d)).astype("float32") - 0.5
        bt = np.array([0, 0, 8, 9, 10, 11], dtype="int32")
        pos = 13 + np.arange(5).astype("int32")
        self.inputs = {"Q": q, "KPool": kp, "VPool": vp, "BlockTable": bt,
                       "Pos": pos}
        self.attrs = {"n_head": n_head, "page_size": page_size,
                      "n_kv_head": n_kv, "window": window}
        self.outputs = {"Out": _window_attention_np(
            q, kp, vp, bt, pos, n_head, n_kv, page_size, window)}

    def test_check_output(self):
        self.check_output()


@pytest.mark.parametrize("per_row", [True, False], ids=["decode", "chunk"])
def test_the_window_kernel_matches_its_dense_lowering_and_walks_the_window(
        per_row):
    """The Pallas kernel (interpret mode) against the dense lowering, with
    windows of one to several compute blocks; the walk the mirror counts
    starts at the window's first block."""
    import jax.numpy as jnp
    from paddle_tpu.ops import pallas_kernels as pk

    rng = np.random.default_rng(5)
    page_size, n_pages, feat = 8, 24, 16
    pool = lambda: jnp.asarray(rng.standard_normal((200 * page_size, feat)),
                               jnp.float32)
    kp, vp = pool(), pool()
    rows = 5
    if per_row:
        bt = rng.permutation(np.arange(1, 200))[:rows * n_pages].reshape(
            rows, n_pages)
        pos = np.array([-1, 3, 70, 130, 190])
    else:
        bt = rng.permutation(np.arange(1, 200))[:n_pages]
        pos = 150 + np.arange(rows)
    q = jnp.asarray(rng.standard_normal((rows, 4 * 8)), jnp.float32)
    for window in (8, 40, 100):
        ins = {"Q": [q], "KPool": [kp], "VPool": [vp],
               "BlockTable": [jnp.asarray(bt, jnp.int32)],
               "Pos": [jnp.asarray(pos, jnp.int32)]}
        attrs = {"n_head": 4, "page_size": page_size, "n_kv_head": 2,
                 "window": window}
        got, want = (_lower_window(ins, attrs, m) for m in ("on", "off"))
        np.testing.assert_allclose(got, want, atol=2e-5)
        block = page_size * pk._paged_block_pages(
            n_pages, page_size * feat * 4, not per_row)
        first = np.maximum((pos.min(keepdims=True) if not per_row else pos)
                           - window + 1, 0) // block
        last = np.clip((pos.max(keepdims=True) if not per_row else pos)
                       // block + 1, 0, None)
        assert pk.paged_positions_walked(
            pos, page_size, n_pages, feat * 4, shared=not per_row,
            window=window) == int(np.clip(last - first, 0, None).sum()) * block


def _lower_window(ins, attrs, mode):
    prev = flags.get_flags("paged_flash")
    flags.set_flags({"paged_flash": mode})
    try:
        return np.asarray(registry.get("window_attention").lower(
            None, ins, attrs)["Out"][0])
    finally:
        flags.set_flags(prev)


@pytest.mark.parametrize("geometry", [
    (16, 64, 32, False), (16, 128, 32, True), (64, 128, 8, True),
    (64, 64, 8, False)],
    ids=["accepted-cells", "wide-heads", "trinity", "pages-of-64"])
def test_a_chunk_s_block_and_its_vmem(geometry):
    """A chunk's block holds at most 512 positions (the accepted cells' 32
    pages of 16 as before; 8 pages of 64), and a chunk of heads of 128 asks
    the compiler for more VMEM while heads of 64 keep its own limit."""
    from paddle_tpu.ops import pallas_kernels as pk

    page_size, d, pages, wide = geometry
    page_bytes = page_size * 8 * 64 * 2
    assert pk._paged_block_pages(426, page_bytes, True, page_size) == pages
    assert pk._paged_block_pages(426, page_bytes, False, page_size) == (
        pk._paged_block_pages(426, page_bytes, False))
    assert (d >= pk._PAGED_WIDE_HEAD) == wide


def test_the_other_serving_configurations_build_no_window():
    """A model without window layers builds what it built before: no
    window_attention op, no window attribute, no window feed or cache spec,
    no window in the engine's geometry (its compile-cache key)."""
    import test_block_decoder as tb
    import test_glm52 as tg
    from paddle_tpu.models import block_decoder as bd

    models = [bd.lfm2_moe(tb.TINY, max_context=64, dtype="float32"),
              bd.granitemoehybrid(tb.GRANITE, max_context=64, dtype="float32"),
              bd.xing4_0(tb.XING, max_context=64, dtype="float32"),
              bd.glm_moe_dsa(tg.GLM, max_context=64, dtype="float32")]
    for m in models:
        assert m.window() == 0 and "windows" not in m.spec()
        assert not any("window" in s for s in m.cache_specs())
        kw = {"state_rows": 4} if any(
            s["kind"] == "state" for s in m.cache_specs()) else {}
        for main, _, feeds, _ in (m.build_decode(3, 8, 8, 320, **kw),
                                  m.build_prefill(8, 8, 8, 320, **kw)):
            ops = main.global_block().ops
            assert not any(op.type == "window_attention" or "window" in op.attrs
                           for op in ops)
            assert not any("window" in f for f in feeds)
    _, eng = _engine()
    assert eng.geometry()["window"] == 16
    model = bd.lfm2_moe(tb.TINY, max_context=64, dtype="float32")
    plain = GenerationEngine(model, name="tafmoe_plain", max_slots=2,
                             page_size=4, prefill_chunk=8)
    assert "window" not in plain.geometry() and plain.window_pool is None
    assert set(plain._dec_feeds) == {"dec_tokens", "dec_positions",
                                     "dec_block_table", "dec_live",
                                     "dec_state_rows"}
