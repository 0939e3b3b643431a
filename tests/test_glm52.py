"""The glm_moe_dsa architecture (GLM-5.2) at a tiny size: latent attention
over a learned sparse selection (a lightning indexer on the "full" layers,
its selection reused by the "shared" ones behind it), a paged pool of index
keys beside the latent pools, 8 experts top 2 beside a shared one, through
GenerationEngine's caches, against the plain reference
(models/glm_dsa_reference.py) on seeded random weights: logits, not tokens."""

import numpy as np
import pytest

from op_test import OpTest
from paddle_tpu import flags
from paddle_tpu.executor import Executor, Scope, scope_guard
from paddle_tpu.models import glm_dsa_reference as ref
from paddle_tpu.models.block_decoder import glm_moe_dsa
from paddle_tpu.ops import registry
from paddle_tpu.serving.generation import GenerationEngine, GenRequest

# the published pattern in miniature: published layers 2-6 (the last leading
# dense layer, full; three shared expert layers; a full expert layer), 4
# heads of latent attention, 2 index heads of 16 (rotary on the first 8), 8
# positions a query, 8 experts top 2 beside a shared one
GLM = dict(
    model_type="glm_moe_dsa", vocab_size=101, hidden_size=64,
    intermediate_size=96, moe_intermediate_size=32, num_attention_heads=4,
    num_key_value_heads=4, q_lora_rank=24, kv_lora_rank=32,
    qk_nope_head_dim=16, qk_rope_head_dim=8, qk_head_dim=24, v_head_dim=16,
    num_hidden_layers=5, first_k_dense_replace=1,
    mlp_layer_types=["dense", "sparse", "sparse", "sparse", "sparse"],
    indexer_types=["full", "shared", "shared", "shared", "full"],
    index_n_heads=2, index_head_dim=16, index_topk=8,
    indexer_rope_interleave=True, index_topk_pattern=None,
    n_routed_experts=8, n_shared_experts=1, num_experts_per_tok=2, n_group=1,
    topk_group=1, topk_method="noaux_tc", scoring_func="sigmoid",
    norm_topk_prob=True, routed_scaling_factor=2.5, rms_norm_eps=1e-5,
    rope_interleave=True,
    rope_parameters={"rope_theta": 8000000, "rope_type": "default"},
    tie_word_embeddings=False, max_position_embeddings=128,
    num_nextn_predict_layers=1, hidden_act="silu", attention_bias=False)
F32_TOL = 2e-5  # float32 both sides; the products differ in summation order


def _engine(dtype="float32", seed=5, held=None, **kw):
    model = glm_moe_dsa(GLM, max_context=128, dtype=dtype, experts_held=held)
    kw = dict(dict(max_slots=3, page_size=4, prefill_chunk=16, pool_pages=70), **kw)
    _engine.made += 1  # a name of its own: the registry counts by name
    eng = GenerationEngine(
        model, name="tglm%d" % _engine.made, scope=Scope(seed=seed), **kw)
    eng.record_picks = True
    return model, eng


_engine.made = 0


@pytest.fixture(scope="module")
def glm():
    return _engine()


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


def _reference(model, eng, tokens, picks, selections):
    """Reference logits following the system's picks and selections; every
    pick and selected position the reference would not have made itself
    has to be a near tie."""
    out = ref.forward(_weights(model, eng), tokens, ref.spec_of(model),
                      picks=picks, selections=selections)
    assert float(np.max(out["pick_gaps"])) < 1e-4
    assert float(np.max(out["sel_gaps"])) < 1e-4
    return np.asarray(out["logits"])


def _forward(model, eng, tokens):
    batch, t = tokens.shape
    main, _, _, fetches = model.build_forward(batch, t)
    with scope_guard(eng.scope):
        logits, picks, sels, _ = Executor().run(
            main, feed={"fwd_tokens": tokens[:, :, None]}, fetch_list=fetches)
    n_full = len(model.dsa_groups())
    return (np.asarray(logits),
            np.asarray(picks).reshape(len(model.moe_layers()), batch, t, -1),
            np.asarray(sels).reshape(n_full, batch, t, -1))


def test_glm_moe_dsa_is_built_from_the_source_s_keys(glm):
    model, eng = glm
    assert model.blocks == [("dsa", "dense")] + [("dsa", "moe")] * 4
    assert model.dsa_groups() == [(0, [0, 1, 2, 3]), (4, [4])]
    assert model.dsa == {"heads": 2, "d_head": 16, "topk": 8, "rope_dim": 8,
                         "norm_eps": 1e-6, "roles": GLM["indexer_types"]}
    assert model.mla["sm_scale"] == pytest.approx(24 ** -0.5)
    assert model.rope_theta == 8e6 and not model.tied_head
    assert model.moe["scale"] == 2.5 and model.moe["shared_width"] == 32
    # a latent pool a layer, an index-key pool a full layer, on one page set
    specs = model.cache_specs()
    assert [(s["layer"], s["form"], s["width"]) for s in specs] == [
        (0, "latent", 128), (0, "index", 16), (1, "latent", 128),
        (2, "latent", 128), (3, "latent", 128), (4, "latent", 128),
        (4, "index", 16)]
    assert model.kv_pool_names() == []
    assert eng.pool_row_bytes == {"latent": 512, "index": 64}
    rows = eng.pool_pages * eng.page_size
    assert eng.stats()["kv"]["resident_bytes"] == rows * (5 * 512 + 2 * 64)
    assert set(model.param_names()) == {
        n for n in eng.scope.vars if n.startswith("glm5_")
        and "kv_latent" not in n and "index_k" not in n}
    shared = [n for n in model.param_names() if n.startswith("glm5_l1_")]
    assert not any("idx_" in n for n in shared)


@pytest.mark.parametrize("key,value", [
    ("indexer_types", ["shared", "shared", "shared", "shared", "full"]),
    ("indexer_types", ["full"] * 4),
    ("mlp_layer_types", ["dense"] * 4 + ["mixed"]),
    ("index_topk_pattern", "every-other"),
    ("indexer_rope_interleave", False),
    ("rope_parameters", {"rope_theta": 8e6, "rope_type": "yarn"}),
    ("n_group", 8),
    ("scoring_func", "softmax"),
    ("num_key_value_heads", 1),
], ids=lambda v: str(v)[:24])
def test_glm_moe_dsa_refuses_a_form_it_does_not_build(key, value):
    with pytest.raises(ValueError, match=key):
        glm_moe_dsa(dict(GLM, **{key: value}))


def test_glm52_forward_program_matches_the_reference(glm):
    model, eng = glm
    tokens = np.array([_prompt(19, 1), _prompt(19, 2)], np.int64)
    logits, picks, sels = _forward(model, eng, tokens)
    for b in range(2):
        want = _reference(model, eng, tokens[b], picks[:, b], sels[:, b])
        np.testing.assert_allclose(logits[b], want, atol=F32_TOL)
        # and the program's selections are the reference's own
        own = ref.forward(_weights(model, eng), tokens[b], ref.spec_of(model))
        assert [sorted(set(r) - {-1}) for r in np.asarray(own["selections"])[0]] == [
            sorted(set(r) - {-1}) for r in sels[0, b]]


@pytest.mark.parametrize("paged_flash", ["off", "on"], ids=["dense", "kernel"],
                         indirect=True)
def test_glm52_chunked_prefill_then_decode_matches_the_full_forward_pass(
        paged_flash):
    """37 prompt tokens in chunks of 16, 16 and 5, then six decode steps,
    all through the latent and index-key pages: the dense lowerings, and the
    Pallas scan in interpret mode with the threshold selection and the
    gathered walk; the first-token logits and every step's against the
    reference's one pass over the whole sequence. A second prompt decodes
    beside it in slot 2, the slot between them idle (its request retired),
    so that the walk over live rows leaves a gap and puts each result back
    in its own slot."""
    model, eng = _engine()
    prompt, other = _prompt(37), _prompt(21, 5)
    run = eng.start(GenRequest(prompt, max_new_tokens=8))
    got = [np.array(eng.last_prefill_logits)]
    gone = eng.start(GenRequest(_prompt(9, 6), max_new_tokens=2))
    run2 = eng.start(GenRequest(other, max_new_tokens=8))
    got2 = [np.array(eng.last_prefill_logits)]
    eng.finish(gone)
    assert (run.slot, gone.slot, run2.slot) == (0, 1, 2)
    for _ in range(6):
        eng.decode_step([run, run2])
        got.append(np.array(eng.last_logits[run.slot]))
        got2.append(np.array(eng.last_logits[run2.slot]))
    for r in (run, run2):
        eng.finish(r)
    assert [s for s, _ in run.selections[:3]] == [0, 16, 32]
    scans = eng.stats()["kernel_dispatches"].get("dsa_index", 0)
    assert (scans > 0) == (paged_flash == "on")
    for r, p, g in ((run, prompt, got), (run2, other, got2)):
        tokens = p + r.tokens[:6]
        want = _reference(model, eng, tokens, _by_position(r.picks, len(tokens)),
                          _by_position(r.selections, len(tokens)))
        for i, row in enumerate(g):
            np.testing.assert_allclose(row, want[len(p) - 1 + i], atol=F32_TOL)


def _lower(op, ins, attrs, mode):
    prev = flags.get_flags("paged_flash")
    flags.set_flags({"paged_flash": mode})
    try:
        return np.asarray(registry.get(op).lower(None, ins, attrs)["Out"][0])
    finally:
        flags.set_flags(prev)


def _paged_case(rng, rows, per_row, page_size=4, n_pages=6, width=16):
    """A pool of 40 pages, a table of n_pages a row (or one shared), and
    positions, some short of a page, one row with nothing (-1) or idle."""
    import jax.numpy as jnp

    pool = jnp.asarray(rng.standard_normal((40 * page_size, width)), jnp.float32)
    if per_row:
        table = rng.permutation(np.arange(1, 40))[:rows * n_pages].reshape(
            rows, n_pages)
        pos = rng.integers(0, n_pages * page_size, rows)
        pos[0] = -1
    else:
        table = rng.permutation(np.arange(1, 40))[:n_pages]
        pos = 3 + np.arange(rows)
    return pool, jnp.asarray(table, jnp.int32), jnp.asarray(pos, jnp.int32)


@pytest.mark.parametrize("per_row", [True, False], ids=["decode", "chunk"])
def test_the_index_scan_kernel_matches_its_dense_lowering(per_row):
    import jax.numpy as jnp

    rng = np.random.default_rng(11)
    rows, heads = 5, 3
    pool, table, pos = _paged_case(rng, rows, per_row)
    q = jnp.asarray(rng.standard_normal((rows, heads * 16)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((rows, heads)), jnp.float32)
    ins = {"Q": [q], "W": [w], "Pool": [pool], "BlockTable": [table],
           "Pos": [pos]}
    got = _lower("dsa_index", ins, {"page_size": 4}, "on")
    want = _lower("dsa_index", ins, {"page_size": 4}, "off")
    assert np.array_equal(np.isinf(got), np.isinf(want))
    live = np.isfinite(want)
    np.testing.assert_allclose(got[live], want[live], rtol=1e-5, atol=1e-5)
    assert live.sum() == sum(max(int(p) + 1, 0) for p in pos)


@pytest.mark.parametrize("n,k", [(300, 8), (1000, 64), (5, 8)],
                         ids=["two-blocks", "eight-blocks", "fewer-than-k"])
def test_the_threshold_selection_matches_a_sort(n, k):
    """Exact: on scores without ties (and -inf past each row's position),
    the same positions as jax.lax.top_k, ascending, -1 behind a short row;
    negative scores and -0.0 in the mix."""
    import jax.numpy as jnp

    rng = np.random.default_rng(n)
    scores = rng.standard_normal((6, n)).astype(np.float32)
    scores[1] = -np.abs(scores[1])
    scores[2, 0] = -0.0
    for r, p in enumerate([n - 1, 0, n // 2, 3, n - 1, 1]):
        scores[r, p + 1:] = -np.inf
    ins = {"X": [jnp.asarray(scores)]}
    got = _lower("dsa_topk", ins, {"k": k}, "on")
    want = _lower("dsa_topk", ins, {"k": k}, "off")
    np.testing.assert_array_equal(got, want)
    for r in range(6):
        live = np.isfinite(scores[r])
        want_r = np.sort(np.argsort(-scores[r])[:min(k, live.sum())])
        assert list(got[r][got[r] >= 0]) == list(want_r)
    # ties at the k-th score go to the lowest positions
    flat = np.zeros((1, 40), np.float32)
    flat[0, 30:] = 1.0
    assert list(_lower("dsa_topk", {"X": [jnp.asarray(flat)]}, {"k": 12}, "on")[0]) == (
        [0, 1] + list(range(30, 40)))


@pytest.mark.parametrize("per_row", [True, False], ids=["decode", "chunk"])
def test_the_sparse_walk_matches_its_dense_lowering(per_row):
    import jax.numpy as jnp

    rng = np.random.default_rng(5)
    rows, heads, width, k = 5, 3, 16, 6
    pool, table, pos = _paged_case(rng, rows, per_row, width=width)
    q = jnp.asarray(rng.standard_normal((rows, heads * width)), jnp.float32)
    sel = np.full((rows, k), -1, np.int32)
    for r, p in enumerate(np.asarray(pos)):
        if p >= 0:
            pick = np.sort(rng.permutation(int(p) + 1)[:k])
            sel[r, :pick.size] = pick
    ins = {"Q": [q], "Pool": [pool], "BlockTable": [table],
           "Selected": [jnp.asarray(sel)]}
    attrs = {"page_size": 4, "d_value": 12, "sm_scale": 0.3}
    got = _lower("mla_sparse_attention", ins, attrs, "on")
    want = _lower("mla_sparse_attention", ins, attrs, "off")
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    assert not got[np.asarray(pos) < 0].any()


def _todays_walk(q, pool, table, sel, page_size, d_value, sm_scale):
    """The served walk before it took Live: every row gathers its selected
    rows, idle or not."""
    import jax.numpy as jnp

    from paddle_tpu.ops.generation_ops import _positions_rows

    s, width = q.shape[0], pool.shape[-1]
    qh = q.reshape(s, -1, width)
    rows = _positions_rows(table, sel, page_size)
    kv = jnp.take(pool, rows.reshape(-1), axis=0).reshape(s, -1, width)
    live = (sel >= 0)[:, None, :]
    scores = jnp.einsum("skw,shw->shk", kv, qh.astype(pool.dtype),
                        preferred_element_type=jnp.float32)
    scores = jnp.where(live, scores * sm_scale, -jnp.inf)
    m = jnp.max(scores, axis=-1, keepdims=True)
    m = jnp.where(jnp.isfinite(m), m, 0.0)
    w = jnp.where(live, jnp.exp(scores - m), 0.0)
    denom = jnp.sum(w, axis=-1, keepdims=True)
    w = w / jnp.where(denom > 0.0, denom, 1.0)
    out = jnp.einsum("shk,skv->shv", w.astype(pool.dtype), kv[..., :d_value],
                     preferred_element_type=jnp.float32)
    return np.asarray(out.reshape(s, -1).astype(q.dtype))


@pytest.mark.parametrize("case", [
    "live-first", "live-middle", "live-last", "none-live", "all-live",
    "two-trips", "no-live-input", "shared-table"])
def test_the_walk_over_live_rows(case, monkeypatch):
    """With Live and per-row tables the walk gathers for the live rows
    alone, a block of min(S, 8) a trip: on the live rows it is the walk of
    every row (and the dense oracle) within 1e-5 in float32, on the idle
    ones 0, and it runs as many trips as the host's mirror counts. Without
    Live, and on a shared table (Live ignored), it is bit for bit the walk
    of every row. An idle row's selection is the scratch page's, not empty,
    as an idle slot's is."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops import generation_ops as go

    rows = 20 if case == "two-trips" else 5
    live = {"live-first": [0], "live-middle": [2], "live-last": [4],
            "none-live": [], "all-live": range(5),
            "two-trips": [0, 2, 3, 5, 8, 9, 11, 14, 15, 17, 19]}.get(case, range(5))
    on = np.isin(np.arange(rows), list(live)).astype(np.int32)
    rng = np.random.default_rng(rows + on.sum())
    per_row = case != "shared-table"
    heads, width, k, ps = 3, 16, 6, 4
    n_pages = 6 if per_row else 12
    pool = jnp.asarray(rng.standard_normal((80 * ps, width)), jnp.float32)
    if per_row:
        table = rng.integers(1, 80, (rows, n_pages)).astype(np.int32)
        pos = rng.integers(0, n_pages * ps, rows).astype(np.int32)
    else:
        table = rng.permutation(np.arange(1, 80))[:n_pages].astype(np.int32)
        pos = (20 + np.arange(rows)).astype(np.int32)
    sel = np.full((rows, k), -1, np.int32)
    for r, p in enumerate(pos):
        pick = np.sort(rng.permutation(int(p) + 1)[:k])
        sel[r, :pick.size] = pick
    if per_row:  # idle slots: table 0 and position 0, selection [0, -1, ...]
        table[on == 0] = 0
        sel[on == 0] = [0] + [-1] * (k - 1)
    q = jnp.asarray(rng.standard_normal((rows, heads * width)), jnp.float32)
    ins = {"Q": [q], "Pool": [pool], "BlockTable": [jnp.asarray(table)],
           "Selected": [jnp.asarray(sel)]}
    if case != "no-live-input":
        ins["Live"] = [jnp.asarray(on)]
    attrs = {"page_size": ps, "d_value": 12, "sm_scale": 0.3}
    today = _todays_walk(q, pool, jnp.asarray(table), jnp.asarray(sel), ps,
                         12, 0.3)
    got = _lower("mla_sparse_attention", ins, attrs, "on")
    if "Live" not in ins or not per_row:
        assert np.array_equal(got, today)
        return
    idle = on == 0
    assert not got[idle].any()
    np.testing.assert_allclose(got[~idle], today[~idle], rtol=1e-5, atol=1e-5)
    dense = _lower("mla_sparse_attention", ins, attrs, "off")
    assert not dense[idle].any()
    np.testing.assert_allclose(got[~idle], dense[~idle], rtol=1e-5, atol=1e-5)
    # the trips, counted with the loop run in Python
    walked = []
    inner = go._gathered_walk
    monkeypatch.setattr(go, "_gathered_walk", lambda qh, *a: (
        walked.append(qh.shape[0]), inner(qh, *a))[1])
    with jax.disable_jit():
        _lower("mla_sparse_attention", ins, attrs, "on")
    block, trips = go.sparse_walk_blocks(int(on.sum()), rows)
    assert walked == [block] * trips and block == min(rows, 8)
    assert sum(walked) == {"none-live": 0, "two-trips": 16}.get(case, 5)


def test_the_walk_s_loop_goes_out_under_no_op_s_name():
    """A device trace gives a loop an event of its own around its body's
    events: the loop's instruction carries no op's name and every gather
    of its body carries mla_sparse_attention's, so the op's device time
    counts the walk once."""
    import re

    import jax
    import jax.numpy as jnp

    def f(q, pool, bt, sel, live):
        ins = {"Q": [q], "Pool": [pool], "BlockTable": [bt], "Selected": [sel],
               "Live": [live]}
        with jax.named_scope("mla_sparse_attention"), jax.named_scope("out=o"):
            return registry.get("mla_sparse_attention").lower(
                None, ins, {"page_size": 4, "d_value": 12, "sm_scale": 0.3})["Out"][0]

    hlo = jax.jit(f).lower(
        jnp.zeros((10, 48)), jnp.zeros((160, 16)), jnp.zeros((10, 6), jnp.int32),
        jnp.zeros((10, 6), jnp.int32), jnp.ones((10,), jnp.int32)).compile().as_text()
    names = lambda opcode: [
        re.search(r'op_name="([^"]*)"', line).group(1)
        for line in hlo.splitlines() if " %s(" % opcode in line]
    loops, gathers = names("while"), names("gather")
    assert loops and not any("mla_sparse_attention" in n for n in loops)
    assert gathers and all("mla_sparse_attention/out=o" in n for n in gathers)


def test_rows_short_of_topk_attend_every_position_as_dense_mla():
    """A row at position < topk selects all of 0..pos, and the sparse walk
    over that selection is mla_attention's dense walk."""
    import jax.numpy as jnp

    rng = np.random.default_rng(2)
    rows, heads, width = 4, 3, 16
    pool, table, pos = _paged_case(rng, rows, True, width=width)
    pos = jnp.asarray([2, 5, 7, 0], jnp.int32)
    scores = jnp.where(jnp.arange(24)[None, :] <= pos[:, None],
                       jnp.asarray(rng.standard_normal((rows, 24)), jnp.float32),
                       -jnp.inf)
    sel = _lower("dsa_topk", {"X": [scores]}, {"k": 8}, "on")
    for r, p in enumerate(np.asarray(pos)):
        assert list(sel[r]) == list(range(p + 1)) + [-1] * (7 - p)
    q = jnp.asarray(rng.standard_normal((rows, heads * width)), jnp.float32)
    attrs = {"page_size": 4, "d_value": 12, "sm_scale": 0.3}
    got = _lower("mla_sparse_attention", {
        "Q": [q], "Pool": [pool], "BlockTable": [table],
        "Selected": [jnp.asarray(sel)]}, attrs, "auto")
    want = _lower("mla_attention", {
        "Q": [q], "Pool": [pool], "BlockTable": [table], "Pos": [pos]},
        attrs, "off")
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("per_row", [True, False], ids=["decode", "chunk"])
def test_the_count_of_distinct_rows_is_a_brute_force_s(per_row):
    """dsa_count (the selected rows, on the device) and the host's count of
    the rows a decode step's scan reads, against sets of (page, offset):
    rows that share pages (a document under several questions) count a
    shared row once, the scratch page and a row at Pos < 0 never."""
    import jax.numpy as jnp

    from paddle_tpu.ops import pallas_kernels as pk

    rng = np.random.default_rng(3)
    rows, ps, n_pages = 6, 4, 8
    if per_row:
        doc = rng.permutation(np.arange(1, 30))[:5]
        table = np.zeros((rows, n_pages), np.int32)
        for r in range(rows):  # rows 0-3 on one document, each its own tail
            own = rng.permutation(np.arange(30, 90))[:3] + 100 * r
            table[r] = np.concatenate([doc, own]) if r < 4 else rng.permutation(
                np.arange(200, 300))[:n_pages]
        pos = rng.integers(17, n_pages * ps, rows)
        pos[5] = -1
        tables = table
    else:
        table = rng.permutation(np.arange(1, 40))[:n_pages].astype(np.int32)
        table[-1] = 0  # a table entry past the reservation: the scratch page
        pos = 5 + np.arange(rows)
        pos[2] = -1
        tables = np.broadcast_to(table, (rows, n_pages))
    mask = rng.random((rows, n_pages * ps)) < 0.3
    mask &= np.arange(n_pages * ps)[None, :] <= pos[:, None]
    got = _lower("dsa_count", {"Mask": [jnp.asarray(mask)],
                               "BlockTable": [jnp.asarray(table)],
                               "Pos": [jnp.asarray(pos, np.int32)]},
                 {"page_size": ps}, "auto")
    scanned = {(tables[r, c // ps], c % ps) for r in range(rows) if pos[r] >= 0
               for c in range(pos[r] + 1) if tables[r, c // ps] > 0}
    picked = {(tables[r, c // ps], c % ps) for r in range(rows) if pos[r] >= 0
              for c in np.flatnonzero(mask[r]) if tables[r, c // ps] > 0}
    assert list(got) == [len(picked)]
    if per_row:
        assert pk.index_rows_scanned(table, pos, ps) == len(scanned)


# ---- each op through a program (its shape rule too), against numpy


def _op_case(seed=0, rows=4, heads=2, width=16, ps=4, n_pages=5):
    rng = np.random.default_rng(seed)
    pool = rng.standard_normal((40 * ps, width)).astype(np.float32)
    bt = rng.permutation(np.arange(1, 40))[:rows * n_pages].reshape(
        rows, n_pages).astype(np.int32)
    pos = np.array([0, 7, 19, -1], np.int32)[:rows]
    q = (rng.standard_normal((rows, heads * width)) * 0.5).astype(np.float32)
    return rng, pool, bt, pos, q


def _pool_rows(bt, r, ps):
    return np.concatenate([np.arange(p * ps, (p + 1) * ps) for p in bt[r]])


class TestDsaIndexOp(OpTest):
    def setUp(self):
        self.op_type = "dsa_index"
        rng, pool, bt, pos, q = _op_case()
        w = rng.standard_normal((4, 2)).astype(np.float32)
        out = np.full((4, 20), -np.inf, np.float32)
        for r in range(4):
            keys = pool[_pool_rows(bt, r, 4)]
            for c in range(pos[r] + 1):
                dots = np.maximum(q[r].reshape(2, 16) @ keys[c], 0.0)
                out[r, c] = dots @ w[r]
        self.inputs = {"Q": q, "W": w, "Pool": pool, "BlockTable": bt, "Pos": pos}
        self.attrs = {"page_size": 4}
        self.outputs = {"Out": out}

    def test_check_output(self):
        self.check_output(atol=1e-4)


class TestDsaTopkOp(OpTest):
    def setUp(self):
        self.op_type = "dsa_topk"
        x = np.random.default_rng(1).standard_normal((3, 40)).astype(np.float32)
        x[1, 5:] = -np.inf  # six positions: fewer than k
        out = np.full((3, 8), -1, np.int32)
        mask = np.zeros((3, 40), bool)
        for r in range(3):
            top = np.sort(np.argsort(-x[r])[:min(8, np.isfinite(x[r]).sum())])
            out[r, :top.size] = top
            mask[r, top] = True
        self.inputs = {"X": x}
        self.attrs = {"k": 8}
        self.outputs = {"Out": out, "Mask": mask}

    def test_check_output(self):
        self.check_output(atol=0)


class TestMlaSparseAttentionOp(OpTest):
    def setUp(self):
        self.op_type = "mla_sparse_attention"
        rng, pool, bt, pos, q = _op_case(seed=2)
        sel = np.full((4, 3), -1, np.int32)
        out = np.zeros((4, 2 * 12), np.float32)
        for r in range(4):
            if pos[r] < 0:
                continue
            pick = np.sort(rng.permutation(pos[r] + 1)[:3])
            sel[r, :pick.size] = pick
            keys = pool[_pool_rows(bt, r, 4)][pick]
            for h in range(2):
                sc = keys @ q[r, h * 16:(h + 1) * 16] * 0.3
                p_ = np.exp(sc - sc.max())
                out[r, h * 12:(h + 1) * 12] = (p_ / p_.sum()) @ keys[:, :12]
        self.inputs = {"Q": q, "Pool": pool, "BlockTable": bt, "Selected": sel}
        self.attrs = {"page_size": 4, "d_value": 12, "sm_scale": 0.3}
        self.outputs = {"Out": out}

    def test_check_output(self):
        self.check_output(atol=1e-5)


class TestDsaCountOp(OpTest):
    def setUp(self):
        self.op_type = "dsa_count"
        _, _, bt, pos, _ = _op_case(seed=3)
        bt[1, :2] = bt[2, :2]  # rows 1 and 2 share their first two pages
        mask = np.random.default_rng(4).random((4, 20)) < 0.5
        mask &= np.arange(20)[None, :] <= pos[:, None]
        picked = {(bt[r, c // 4], c % 4) for r in range(4)
                  for c in np.flatnonzero(mask[r])}
        self.inputs = {"Mask": mask, "BlockTable": bt, "Pos": pos}
        self.attrs = {"page_size": 4}
        self.outputs = {"Out": np.array([len(picked)], np.int32)}

    def test_check_output(self):
        self.check_output(atol=0)


def test_a_shared_layer_attends_its_full_layer_s_selection(glm):
    """The three shared layers attend layer 0's selection: perturbing the
    index keys of the later full layer (4) moves its own selection and the
    logits, and leaves layer 0's selection, everything before layer 4,
    as it was; perturbing layer 0's moves both."""
    model, eng = glm
    tokens = np.array([_prompt(23, 4)], np.int64)
    logits, _, sels = _forward(model, eng, tokens)
    for layer, moved in ((4, [False, True]), (0, [True, True])):
        name = "glm5_l%d_idx_k_w" % layer
        kept = eng.scope.vars[name]
        eng.scope.vars[name] = kept * -3.0
        try:
            logits2, _, sels2 = _forward(model, eng, tokens)
        finally:
            eng.scope.vars[name] = kept
        assert [not np.array_equal(sels[f], sels2[f]) for f in (0, 1)] == moved
        assert np.abs(logits2 - logits).max() > 1e-3


def test_glm52_prefix_hit_brings_the_index_keys_and_equals_a_cold_admission():
    """A document of three whole pages, prefilled once, then a second
    prompt on it admitted through the prefix hit: the index scan of the
    second prompt's tail reads the document's index keys off the shared
    pages, and every logit is the cold engine's."""
    model, warm = _engine()
    _, cold = _engine(prefix_cache=False)
    doc = _prompt(24, 7)
    first, second = doc + _prompt(9, 8), doc + _prompt(11, 9)
    warm.finish(warm.start(GenRequest(first, max_new_tokens=2)))
    run = warm.start(GenRequest(second, max_new_tokens=4))
    assert run.selections[0][0] == 24  # prefill began behind the document
    assert warm.prefix_cache.stats()["pages_hit"] == 6
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
    # the selections of the tail name document positions, so a hit without
    # the document's index keys would have moved them
    assert (np.asarray(run.selections[0][1]) < 24).any()


def test_glm52_engine_counts_what_the_selection_path_read():
    """gen_dsa_*: a decode step of two rows on one document counts the
    document's index rows and its selected rows once, the pairs for each
    row; the step's share is selected over attended; the walk gathers for
    the two live rows in a block of min(3 slots, 8)."""
    from paddle_tpu.observability import registry as reg

    model, eng = _engine()
    snap = lambda h: reg.default_registry().snapshot()["serving/%s/%s" % (eng.name, h)]
    doc = _prompt(24, 3)
    runs = [eng.start(GenRequest(doc + _prompt(2, 10 + i), max_new_tokens=3))
            for i in range(2)]
    before = {h: snap("gen_dsa_" + h)["sum"] for h in (
        "index_positions", "index_pairs", "selected_positions", "selected_pairs",
        "walk_rows")}
    eng.decode_step(runs)
    got = {h: snap("gen_dsa_" + h)["sum"] - before[h] for h in before}
    for r in runs:
        eng.finish(r)
    # each row at position 26 attends 27 positions and keeps 8; the two
    # share the document's 24 rows (its six pages)
    assert got["index_pairs"] == 2 * 27 * 2  # two full layers
    assert got["index_positions"] == 2 * (24 + 3 + 3)
    assert got["selected_pairs"] == 2 * 8 * 5  # five dsa layers
    sels = [np.asarray(r.selections[-1][1])[:, 0] for r in runs]
    distinct = [len({(int(s) if s < 24 else (i, int(s))) for i in range(2)
                     for s in sels[i][f]}) for f in range(2)]
    assert got["selected_positions"] == distinct[0] * 4 + distinct[1]
    block = min(eng.max_slots, 8)
    assert got["walk_rows"] == block * -(-2 // block) == 3
    share = snap("gen_dsa_selected_share")
    assert share["sum"] / share["count"] <= 100.0


def test_glm52_expert_shares_and_the_shared_expert_add_up_to_the_whole_layer():
    """The sixteen shares of an expert-parallel-16 layer in miniature (32
    experts top 8, two a share): each share's routed part, plus the shared
    expert counted once, add up to the uncut reference's whole layer."""
    import jax.numpy as jnp

    rng = np.random.default_rng(4)
    rows, d, f, n_exp, k = 24, 16, 12, 32, 8
    x = rng.standard_normal((rows, d)).astype("float32")
    router_w = rng.standard_normal((d, n_exp)).astype("float32") * 0.5
    router_b = rng.standard_normal(n_exp).astype("float32") * 0.3
    w1, w3 = (rng.standard_normal((n_exp, d, f)).astype("float32") * 0.3
              for _ in range(2))
    w2 = rng.standard_normal((n_exp, f, d)).astype("float32") * 0.3
    s1, s3 = (rng.standard_normal((d, f)).astype("float32") * 0.3 for _ in range(2))
    s2 = rng.standard_normal((f, d)).astype("float32") * 0.3
    moe = {"num_experts": n_exp, "k": k, "norm_topk": True, "scale": 2.5}
    J = jnp.asarray
    whole, _, _ = ref.moe_layer(
        J(x), J(router_w), J(router_b), J(w1), J(w3), J(w2), moe)
    whole = np.asarray(whole + ref.swiglu_ff(J(x), J(s1), J(s3), J(s2)))
    lower = lambda t, ins, attrs: registry.get(t).lower(None, ins, attrs)
    routed = lower("moe_router", {"X": [J(x)], "W": [J(router_w)],
                                  "Bias": [J(router_b)]},
                   {"k": k, "norm_topk": True, "scale": 2.5})
    total = np.asarray(ref.swiglu_ff(J(x), J(s1), J(s3), J(s2)), np.float64)
    for share in range(16):
        held = [2 * share, 2 * share + 1]
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
