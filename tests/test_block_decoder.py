"""The block-specified decoder (models/block_decoder.py) as the lfm2_moe
architecture at a tiny size, through GenerationEngine's caches, against the
plain reference (models/lfm2_moe_reference.py) on seeded random weights:
logits, not tokens."""

import numpy as np
import pytest

from paddle_tpu.executor import Executor, Scope, scope_guard
from paddle_tpu.models import lfm2_moe_reference as ref
from paddle_tpu.models.block_decoder import BlockDecoder, lfm2_moe
from paddle_tpu.serving.generation import (
    GenerationEngine, GenerationScheduler, GenRequest)

# the published layer pattern in miniature: a leading dense conv layer, then
# [full_attention, conv, conv] periods; 4 query heads on 2 KV heads; 8
# experts, 2 a token
TINY = dict(
    vocab_size=97, hidden_size=32, intermediate_size=48,
    moe_intermediate_size=24, num_attention_heads=4, num_key_value_heads=2,
    num_hidden_layers=5, num_dense_layers=1,
    layer_types=["conv", "full_attention", "conv", "conv", "full_attention"],
    num_experts=8, num_experts_per_tok=2, use_expert_bias=True,
    norm_topk_prob=True, routed_scaling_factor=1.0, conv_L_cache=3,
    norm_eps=1e-5, rope_theta=1e6, max_position_embeddings=64)
F32_TOL = 2e-5  # float32 both sides; the products differ in summation order
# bfloat16 weights, activations and caches against float32 of the same
# weights: ~2^-9 relative a rounding, a few dozen roundings deep, on logits
# of about 0.7
BF16_BAND = 2e-2


def _engine(dtype="float32", seed=3, **kw):
    model = lfm2_moe(TINY, max_context=64, dtype=dtype)
    kw = dict(dict(max_slots=3, page_size=4, prefill_chunk=8), **kw)
    eng = GenerationEngine(model, scope=Scope(seed=seed), **kw)
    eng.record_picks = True
    return model, eng


@pytest.fixture(scope="module")
def served():
    return _engine()


def _weights(model, eng):
    return {n: eng.scope.vars[n] for n in model.param_names()}


def _picks(run, n):
    """The run's picks by position, [n_moe, n, k]."""
    parts = [p for _, p in sorted(run.picks, key=lambda sp: sp[0])]
    return np.concatenate(parts, axis=1)[:, :n]


def _prompt(n, seed=0):
    return [int(v) for v in np.random.default_rng(seed).integers(2, 97, n)]


def _reference(model, eng, tokens, picks):
    """Reference logits [t, vocab] following the system's picks; every pick
    the reference would not have made itself has to be a near tie."""
    logits, own, gaps = ref.forward(
        _weights(model, eng), tokens, ref.spec_of(model), picks=picks)
    assert float(np.max(gaps)) < 1e-4, "a pick the reference's scores do not bear out"
    return np.asarray(logits)


def test_forward_program_matches_the_reference(served):
    model, eng = served
    tokens = np.array([_prompt(13, 1), _prompt(13, 2)], np.int64)
    main, _, feeds, fetches = model.build_forward(2, 13)
    with scope_guard(eng.scope):
        logits, picks = Executor().run(
            main, feed={"fwd_tokens": tokens[:, :, None]}, fetch_list=fetches)
    picks = np.asarray(picks).reshape(len(model.moe_layers()), 2, 13, -1)
    for b in range(2):
        want = _reference(model, eng, tokens[b], picks[:, b])
        np.testing.assert_allclose(np.asarray(logits)[b], want, atol=F32_TOL)


def test_chunked_prefill_then_decode_matches_the_full_forward_pass(served):
    """21 prompt tokens in chunks of 8, 8 and 5 (the last padded to 8), then
    six decode steps, all through the pages and the conv state rows: the
    first-token logits and every step's against the reference's one pass
    over the whole sequence."""
    model, eng = served
    prompt = _prompt(21)
    run = eng.start(GenRequest(prompt, max_new_tokens=8))
    got = [np.array(eng.last_prefill_logits)]
    for _ in range(6):
        eng.decode_step([run])
        got.append(np.array(eng.last_logits[run.slot]))
    tokens = prompt + run.tokens[:6]
    eng.finish(run)
    assert [s for s, _ in run.picks[:3]] == [0, 8, 16]
    want = _reference(model, eng, tokens, _picks(run, len(tokens)))
    for i, g in enumerate(got):
        np.testing.assert_allclose(g, want[len(prompt) - 1 + i], atol=F32_TOL)


def test_prefix_hit_restores_the_state_and_equals_a_cold_admission():
    """A prompt admitted through a prefix hit (pages shared, conv state
    restored from the boundary's snapshot) against the same prompt in an
    engine that has seen nothing: the same logits to float32 rounding (the
    hit cuts the chunks elsewhere, so the products sum in another order)."""
    model, warm = _engine()
    _, cold = _engine(prefix_cache=False)
    shared = _prompt(12, 7)
    first = shared + _prompt(9, 8)
    second = shared + _prompt(10, 9)
    third = shared + _prompt(11, 10)
    for p in (first, second):  # the second leaves the snapshot at 12
        warm.finish(warm.start(GenRequest(p, max_new_tokens=2)))
    st = warm.prefix_cache.stats()
    # the one snapshot: at the boundary the second prompt shared
    assert st["states_taken"] == st["cached_states"] == 1
    assert st["state_bytes"] == warm.state_row_bytes
    run = warm.start(GenRequest(third, max_new_tokens=4))
    assert run.picks[0][0] == 12  # prefill began at the shared boundary
    assert warm.prefix_cache.stats()["states_hit"] == st["states_hit"] + 1
    run_cold = cold.start(GenRequest(third, max_new_tokens=4))
    np.testing.assert_allclose(
        warm.last_prefill_logits, cold.last_prefill_logits, atol=F32_TOL)
    for _ in range(3):
        warm.decode_step([run])
        cold.decode_step([run_cold])
        np.testing.assert_allclose(
            warm.last_logits[run.slot], cold.last_logits[run_cold.slot],
            atol=F32_TOL)
    assert run.tokens == run_cold.tokens


def test_lookup_returns_only_a_boundary_that_has_a_state():
    """Pages alone are no hit for a model with a state: the first repeat of
    a shared prefix matches pages, gets none, and cuts its prefill there."""
    model, eng = _engine()
    shared = _prompt(12, 7)
    eng.finish(eng.start(GenRequest(shared + _prompt(9, 8), max_new_tokens=2)))
    pc = eng.prefix_cache
    pages, state, matched = pc.lookup_state(shared + _prompt(10, 9))
    pc.pool.unpin_pages(pages)
    # a cold prompt leaves no snapshot (nobody was seen to share any of it):
    # the three shared pages match and none is usable
    assert (len(pages), matched) == (0, 3) and state is None
    run = eng.admit(GenRequest(shared + _prompt(10, 9), max_new_tokens=2))
    assert (run.pf_pos, run.cut) == (0, 12)
    while not eng.prefill_step(run):
        pass
    eng.finish(run)
    pages, state, matched = pc.lookup_state(shared + _prompt(11, 10))
    pc.pool.unpin_pages(pages)
    assert (len(pages), matched) == (3, 3) and state is not None


def test_state_snapshots_are_capped_evicted_and_counted():
    model, eng = _engine()
    pc = eng.prefix_cache
    # room for two and a half snapshots: two fit
    pc.snapshot_bytes = 5 * eng.state_row_bytes // 2
    for seed in range(4):  # each head twice: the second leaves its snapshot
        for tail in (1, 2):
            eng.finish(eng.start(GenRequest(
                _prompt(8, 20 + seed) + _prompt(9, 40 + seed + tail),
                max_new_tokens=1)))
    st = pc.stats()
    assert st["cached_states"] == 2 and st["states_taken"] == 4
    assert st["states_evicted"] == 2
    assert st["state_bytes"] == 2 * eng.state_row_bytes
    assert st["state_bytes_reserved"] == 5 * eng.state_row_bytes // 2
    pc.evict_for(10 ** 6)
    st = pc.stats()
    assert st["cached_states"] == 0 and st["states_evicted"] == 4
    assert eng.stats()["kv"]["state_bytes"] == eng.seq_state_bytes > 0
    assert eng.pool.stats()["state_bytes"] == eng.seq_state_bytes


def test_decode_step_leaves_idle_and_mid_prefill_states_untouched(served):
    """A decode step advances one run while a second slot is mid-prefill and
    a third is idle: neither of their state rows changes, and the
    mid-prefill run finishes to the same logits as alone."""
    model, eng = served
    a = eng.start(GenRequest(_prompt(9, 30), max_new_tokens=8))
    b = eng.admit(GenRequest(_prompt(19, 31), max_new_tokens=4))
    assert not eng.prefill_step(b)  # one chunk of three
    rows = lambda: {n: np.array(eng._state[n]) for n in eng._state_names}
    before = rows()
    eng.decode_step([a])
    after = rows()
    idle = ({0, 1, 2} - {a.slot, b.slot}).pop()
    for n in before:
        for slot in (b.slot, idle):
            np.testing.assert_array_equal(before[n][slot + 1], after[n][slot + 1])
        assert (before[n][a.slot + 1] != after[n][a.slot + 1]).any()
    while not eng.prefill_step(b):
        pass
    got = np.array(eng.last_prefill_logits)
    eng.finish(a)
    eng.finish(b)
    alone = eng.start(GenRequest(_prompt(19, 31), max_new_tokens=4))
    np.testing.assert_allclose(got, eng.last_prefill_logits, atol=F32_TOL)
    eng.finish(alone)


def test_rows_without_a_live_token_are_not_routed(served):
    """The picks of a decode step: live rows pick experts, every other row
    the pick `num_experts`; the histograms count live rows only."""
    model, eng = served
    a = eng.start(GenRequest(_prompt(5, 40), max_new_tokens=4))
    eng.decode_step([a])
    picks = eng.last_picks
    n_exp = TINY["num_experts"]
    assert (picks[:, a.slot] < n_exp).all()
    others = [s for s in range(3) if s != a.slot]
    assert (picks[:, others] == n_exp).all()
    eng.finish(a)


def test_bf16_stays_in_its_band_of_float32():
    """bfloat16 weights, activations, pages and conv state against float32
    of the same weights, following the system's picks."""
    model, eng = _engine("bfloat16")
    prompt = _prompt(21)
    run = eng.start(GenRequest(prompt, max_new_tokens=6))
    first = np.array(eng.last_prefill_logits)
    for _ in range(4):
        eng.decode_step([run])
    last = np.array(eng.last_logits[run.slot])
    tokens = prompt + run.tokens[:4]
    eng.finish(run)
    assert first.dtype == np.float32
    assert str(eng._state[eng._state_names[0]].dtype) == "bfloat16"
    logits, _, gaps = ref.forward(
        _weights(model, eng), tokens, ref.spec_of(model),
        picks=_picks(run, len(tokens)))
    want = np.asarray(logits)
    # a pick may differ where bf16 activations moved a score past its
    # neighbour: such a score is within a few 1e-3 of the k-th largest
    assert float(np.max(gaps)) < 2e-2
    for got, row in ((first, len(prompt) - 1), (last, len(tokens) - 1)):
        err = float(np.abs(got - want[row]).max())
        assert 1e-5 < err < BF16_BAND, err


def test_scheduler_serves_concurrent_requests_as_the_serial_engine(served):
    """Through GenerationScheduler: continuous batching, chunked prefill and
    the prefix cache give each request the tokens a serial run gives it."""
    model, eng = served
    prompts = [_prompt(12, 7) + _prompt(3 + i, 50 + i) for i in range(5)]
    serial = [eng.generate(p, max_new_tokens=5).tokens for p in prompts]
    sched = GenerationScheduler(eng)
    try:
        futures = [sched.submit(p, max_new_tokens=5) for p in prompts]
        got = [f.result(120).tokens for f in futures]
    finally:
        sched.close()
    assert got == serial
    assert eng.prefix_cache.stats()["states_hit"] >= 4


def test_the_block_specification_is_part_of_the_cache_key(served):
    model, eng = served
    geo = eng.geometry()
    assert geo["block_spec"]["blocks"][0] == ["conv", "dense"]
    assert geo["block_spec"]["moe"]["num_experts"] == 8
    other = BlockDecoder(
        97, 32, [("attention", "dense")], 4, 2, 8, 48, max_context=64,
        dtype="float32")
    assert other.spec() != model.spec()
    assert [s["kind"] for s in other.cache_specs()] == ["paged", "paged"]
    kinds = [s["kind"] for s in model.cache_specs()]
    assert kinds.count("state") == 3 and kinds.count("paged") == 4
    assert {s["width"] for s in model.cache_specs() if s["kind"] == "paged"} == {16}


# a width the grouped kernel takes (whole lane tiles): two expert layers
WIDE = dict(
    TINY, hidden_size=128, intermediate_size=128, moe_intermediate_size=128,
    num_hidden_layers=3, layer_types=["conv", "full_attention", "conv"])


@pytest.mark.parametrize("config, layers", [(WIDE, 2), (TINY, 0)],
                         ids=["whole lane tiles", "declined"])
def test_decode_step_observes_the_expert_layers_on_the_grouped_kernel(
        config, layers):
    """gen_moe_kernel_layers: the decode variant's moe_experts ops that lower
    to ops.pallas_kernels.grouped_expert_ffn, observed a decode step: every
    expert layer where the widths are whole lane tiles, none where the op
    declines to jax.lax.ragged_dot; the engine's mirror agrees with what
    the trace dispatched, and stats() names the family."""
    from paddle_tpu.observability import registry
    from paddle_tpu.ops import pallas_kernels as pk

    name = "moegen%d" % layers
    model = lfm2_moe(config, max_context=64, dtype="float32")
    assert len(model.moe_layers()) == (layers or 4)
    eng = GenerationEngine(model, name=name, scope=Scope(seed=5), max_slots=3,
                           page_size=4, prefill_chunk=8)
    run = eng.start(GenRequest(_prompt(5, 7), max_new_tokens=4))
    # the prompt's chunk traced its own; the decode variant is traced next
    pk.KERNEL_DISPATCHES.pop("moe_grouped", None)
    for _ in range(2):
        eng.decode_step([run])
    eng.finish(run)
    assert pk.KERNEL_DISPATCHES.get("moe_grouped", 0) == layers
    assert eng.stats()["kernel_dispatches"].get("moe_grouped", 0) == layers
    h = registry.default_registry().snapshot()[
        "serving/%s/gen_moe_kernel_layers" % name]
    assert (h["count"], h["sum"]) == (2, 2 * layers)
    # the wide decoder through the kernel is the reference's decoder
    if layers:
        tokens = _prompt(5, 7) + run.tokens[:2]
        eng.record_picks = True
        again = eng.start(GenRequest(tokens, max_new_tokens=2))
        want = _reference(model, eng, tokens, _picks(again, len(tokens)))
        np.testing.assert_allclose(
            eng.last_prefill_logits, want[len(tokens) - 1], atol=1e-4)
        eng.finish(again)


def _host_sample(logits, temperature, top_k, rng):
    """The host sampler as it stood before the ids moved to the device: the
    contract of a seeded request's stream."""
    z = np.asarray(logits, np.float64) / temperature
    if top_k and top_k < z.size:
        kth = np.partition(z, -top_k)[-top_k]
        z = np.where(z < kth, -np.inf, z)
    p = np.exp(z - z.max())
    p /= p.sum()
    return int(rng.choice(z.size, p=p))


def test_the_step_s_ids_are_the_argmax_of_its_logits_and_its_fetch_is_small():
    """Through the block decoder's variant, as through GPTDecoder's: a
    greedy row's token is argmax over its row of last_logits, a row with a
    temperature draws what the host sampler draws for its seed from that
    row; 17 steps with a row joining and one leaving. The step copies its
    ids and the routers' picks, and the logits of each sampled row only."""
    from paddle_tpu.observability import registry

    model = lfm2_moe(TINY, max_context=64, dtype="float32")
    eng = GenerationEngine(model, name="idsblk", scope=Scope(seed=4),
                           max_slots=3, page_size=4, prefill_chunk=8)
    eng.warmup()
    n_moe, k, vocab = len(model.moe_layers()), 2, 97
    small = 3 * 4 + n_moe * 3 * k * 4  # ids + picks [n_moe, slots, k]
    reqs = [GenRequest(_prompt(9, 1), max_new_tokens=20),
            GenRequest(_prompt(5, 2), max_new_tokens=9, temperature=0.7,
                       top_k=6, seed=21),
            GenRequest(_prompt(11, 3), max_new_tokens=12)]
    rngs = [None, np.random.default_rng(21), None]

    def want(i, row):
        if rngs[i] is None:
            return int(np.argmax(row))
        return _host_sample(row, reqs[i].temperature, reqs[i].top_k, rngs[i])

    def start(i):
        run = eng.start(reqs[i])
        assert run.tokens == [want(i, eng.last_prefill_logits)]
        return run

    live = {0: start(0), 1: start(1)}
    steps = sampled_steps = 0
    try:
        for step in range(17):
            if step == 4:
                live[2] = start(2)
            eng.decode_step(list(live.values()))
            steps += 1
            sampled_steps += 1 in live
            assert eng.last_picks.shape == (n_moe, 3, k)
            assert eng.stats()["decode_fetch_bytes"] == (
                small + (1 in live) * vocab * 4)
            rows = eng.last_logits
            for i, run in list(live.items()):
                assert run.tokens[-1] == want(i, rows[run.slot]), (step, i)
                if run.done:
                    eng.finish(live.pop(i))
    finally:
        for run in live.values():
            eng.finish(run)
    assert sorted(live) == [0] and 0 < sampled_steps < steps
    h = registry.default_registry().snapshot()["serving/idsblk/gen_fetch_bytes"]
    assert (h["count"], h["sum"]) == (
        steps, steps * small + sampled_steps * vocab * 4)


def test_a_tie_over_the_whole_vocabulary_goes_to_the_first_index():
    """The last norm's weight at 0 makes every logit exactly 0: the id the
    device chooses is numpy's argmax of that row, index 0."""
    model, eng = _engine(seed=6)
    norm = model._p("final_norm_w")
    eng.set_params({norm: np.zeros_like(np.asarray(eng.scope.vars[norm]))})
    run = eng.start(GenRequest(_prompt(6, 5), max_new_tokens=4))
    try:
        assert not eng.last_prefill_logits.any() and run.tokens == [0]
        eng.decode_step([run])
        row = eng.last_logits[run.slot]
        assert not row.any() and run.tokens[-1] == int(np.argmax(row)) == 0
    finally:
        eng.finish(run)


# ---------------------------------------------------------------------------
# the xing4_0 architecture at a tiny size: latent attention through one
# paged pool a layer, the hyper-connection residual, a shared expert beside
# the routed ones, an untied head, partial YaRN rotary
# ---------------------------------------------------------------------------

from paddle_tpu.models import xing4_reference as xref  # noqa: E402
from paddle_tpu.models.block_decoder import xing4_0  # noqa: E402

XING = dict(
    model_type="xing4_0", vocab_size=101, hidden_size=64, intermediate_size=96,
    moe_intermediate_size=32, num_attention_heads=4, num_key_value_heads=4,
    q_lora_rank=24, kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
    v_head_dim=16, num_hidden_layers=3, first_k_dense_replace=1,
    n_routed_experts=8, n_shared_experts=1, num_experts_per_tok=2,
    n_group=1, topk_group=1, topk_method="noaux_tc", scoring_func="sigmoid",
    norm_topk_prob=True, routed_scaling_factor=2, hc_mult=4,
    hc_sinkhorn_iters=20, hc_eps=1e-6, mhc_h_res_clamp_min=-30,
    mhc_h_res_clamp_max=30, rms_norm_eps=1e-6, rope_theta=10000,
    rope_scaling={"type": "yarn", "factor": 4, "beta_fast": 32, "beta_slow": 1,
                  "mscale": 1, "mscale_all_dim": 1,
                  "original_max_position_embeddings": 32},
    tie_word_embeddings=False, max_position_embeddings=128,
    num_nextn_predict_layers=1)


def _xing_engine(dtype="float32", seed=5, held=None, **kw):
    model = xing4_0(XING, max_context=128, dtype=dtype, experts_held=held)
    kw = dict(dict(max_slots=3, page_size=8, prefill_chunk=16, pool_pages=40), **kw)
    eng = GenerationEngine(model, scope=Scope(seed=seed), **kw)
    eng.record_picks = True
    return model, eng


@pytest.fixture(scope="module")
def xing():
    return _xing_engine()


def _xing_reference(model, eng, tokens, picks):
    logits, own, gaps = xref.forward(
        _weights(model, eng), tokens, xref.spec_of(model), picks=picks)
    assert float(np.max(gaps)) < 1e-4, "a pick the reference's scores do not bear out"
    return np.asarray(logits)


def test_xing4_is_built_from_the_source_s_keys(xing):
    model, eng = xing
    assert model.blocks == [("mla", "dense"), ("mla", "moe"), ("mla", "moe")]
    assert model.hyper == {"streams": 4, "iters": 20, "eps": 1e-6,
                           "clamp": [-30.0, 30.0]}
    assert not model.tied_head and model.moe["shared_width"] == 32
    m = 0.1 * np.log(4) + 1.0
    assert model.mla["sm_scale"] == pytest.approx(24 ** -0.5 * m * m)
    assert model.mla["yarn"] == [4.0, 32.0, 32.0, 1.0]
    # one paged pool a layer, [c_kv 32 | k_r 8] in a 128-wide row, no K/V pair
    specs = model.cache_specs()
    assert [s["kind"] for s in specs] == ["paged"] * 3
    assert {(s["width"], s["values"], s["form"]) for s in specs} == {(128, 40, "latent")}
    assert model.kv_pool_names() == []
    rows = eng.pool_pages * eng.page_size
    assert eng.stats()["kv"]["resident_bytes"] == rows * 3 * 128 * 4
    assert set(model.param_names()) == {
        n for n in eng.scope.vars if n.startswith("xing4_") and "kv_latent" not in n}
    with pytest.raises(ValueError):
        xing4_0(dict(XING, n_group=2))


def test_xing4_forward_program_matches_the_reference(xing):
    model, eng = xing
    tokens = np.array([_prompt(19, 1), _prompt(19, 2)], np.int64)
    main, _, feeds, fetches = model.build_forward(2, 19)
    with scope_guard(eng.scope):
        logits, picks = Executor().run(
            main, feed={"fwd_tokens": tokens[:, :, None]}, fetch_list=fetches)
    picks = np.asarray(picks).reshape(len(model.moe_layers()), 2, 19, -1)
    for b in range(2):
        want = _xing_reference(model, eng, tokens[b], picks[:, b])
        np.testing.assert_allclose(np.asarray(logits)[b], want, atol=F32_TOL)


@pytest.mark.parametrize("paged_flash", ["off", "on"], ids=["dense", "kernel"])
def test_xing4_chunked_prefill_then_decode_matches_the_full_forward_pass(paged_flash):
    """37 prompt tokens in chunks of 16, 16 and 5, then six decode steps,
    all through the latent pages in the absorbed form (the dense lowering,
    and the Pallas walk in interpret mode): the first-token logits and every
    step's against the reference's one plain-form pass over the sequence."""
    from paddle_tpu import flags

    prev = flags.get_flags("paged_flash")
    flags.set_flags({"paged_flash": paged_flash})
    try:
        model, eng = _xing_engine()
        prompt = _prompt(37)
        run = eng.start(GenRequest(prompt, max_new_tokens=8))
        got = [np.array(eng.last_prefill_logits)]
        for _ in range(6):
            eng.decode_step([run])
            got.append(np.array(eng.last_logits[run.slot]))
        tokens = prompt + run.tokens[:6]
        eng.finish(run)
    finally:
        flags.set_flags(prev)
    assert [s for s, _ in run.picks[:3]] == [0, 16, 32]
    walks = eng.stats()["kernel_dispatches"].get("paged_latent", 0)
    assert (walks > 0) == (paged_flash == "on")
    want = _xing_reference(model, eng, tokens, _picks(run, len(tokens)))
    for i, g in enumerate(got):
        np.testing.assert_allclose(g, want[len(prompt) - 1 + i], atol=F32_TOL)


def test_xing4_prefix_hit_shares_the_latent_pages_and_equals_a_cold_admission():
    model, warm = _xing_engine()
    _, cold = _xing_engine(prefix_cache=False)
    doc = _prompt(24, 7)  # three whole pages
    first, second = doc + _prompt(9, 8), doc + _prompt(11, 9)
    warm.finish(warm.start(GenRequest(first, max_new_tokens=2)))
    run = warm.start(GenRequest(second, max_new_tokens=4))
    assert run.picks[0][0] == 24  # prefill began behind the document
    assert warm.prefix_cache.stats()["pages_hit"] == 3
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


def test_xing4_scheduler_serves_concurrent_requests_as_the_serial_engine(xing):
    model, eng = xing
    prompts = [_prompt(16, 7) + _prompt(3 + i, 50 + i) for i in range(5)]
    serial = [eng.generate(p, max_new_tokens=5).tokens for p in prompts]
    sched = GenerationScheduler(eng)
    try:
        futures = [sched.submit(p, max_new_tokens=5) for p in prompts]
        got = [f.result(120).tokens for f in futures]
    finally:
        sched.close()
    assert got == serial


def test_xing4_engine_counts_the_held_experts_and_the_chunks_context():
    """experts_held: the touched-experts histograms count only experts this
    chip holds (the mirror of moe_experts' visits); a chunk observes the
    positions and the pairs it attends, a decode step the bytes it feeds."""
    from paddle_tpu.observability import registry
    from paddle_tpu.serving.generation import _experts_touched

    picks = np.array([[[0, 5], [5, 7]], [[1, 2], [2, 6]]])
    assert _experts_touched(picks) == 6
    assert _experts_touched(picks, held=[0, 1, 2, 3]) == 3
    assert _experts_touched(picks, held=[4]) == 0
    model, eng = _xing_engine(held=[0, 1, 2, 5], name="xing_held")
    snap = lambda h: registry.default_registry().snapshot()["serving/xing_held/" + h]
    run = eng.start(GenRequest(_prompt(21), max_new_tokens=4))
    eng.decode_step([run])
    all_picked = _experts_touched(eng.last_picks[:, [run.slot], :])
    held_picked = _experts_touched(eng.last_picks[:, [run.slot], :], [0, 1, 2, 5])
    assert snap("gen_experts_touched")["sum"] == held_picked <= all_picked
    eng.finish(run)
    # chunks of 16 and 5 rows: 16 + 21 positions; 16 * 17 / 2 + (5 * 16 + 15) pairs
    assert snap("gen_chunk_ctx_tokens")["sum"] == 16 + 21
    assert snap("gen_chunk_ctx_pairs")["sum"] == 136 + 95
    # int32 on the device: tokens, positions, the table, live, state rows
    feeds = eng.max_slots * (4 + 4 + 4 * eng.max_pages + 4 + 4)
    assert snap("gen_feed_bytes")["sum"] == feeds
    # the same logits as the reference given the same share
    tokens = _prompt(21) + run.tokens[:1]
    want = _xing_reference(model, eng, tokens, _picks(run, len(tokens)))
    np.testing.assert_allclose(
        np.array(eng.last_logits[run.slot]), want[-1], atol=F32_TOL)


def test_xing4_expert_shares_and_the_shared_expert_add_up_to_the_whole_layer():
    """The eight shares of an expert-parallel-8 layer in miniature (16
    experts, two a share): each share's routed part, plus the shared expert
    counted once, add up to the uncut reference's whole expert layer."""
    import jax.numpy as jnp

    from paddle_tpu.ops import registry

    rng = np.random.default_rng(4)
    rows, d, f, n_exp, k = 24, 16, 12, 16, 4
    x = rng.standard_normal((rows, d)).astype("float32")
    router_w = rng.standard_normal((d, n_exp)).astype("float32") * 0.5
    router_b = rng.standard_normal(n_exp).astype("float32") * 0.3
    w1, w3 = (rng.standard_normal((n_exp, d, f)).astype("float32") * 0.3
              for _ in range(2))
    w2 = rng.standard_normal((n_exp, f, d)).astype("float32") * 0.3
    s1, s3 = (rng.standard_normal((d, f)).astype("float32") * 0.3 for _ in range(2))
    s2 = rng.standard_normal((f, d)).astype("float32") * 0.3
    moe = {"num_experts": n_exp, "k": k, "norm_topk": True, "scale": 2.0}
    J = jnp.asarray
    whole, _, _ = xref.moe_layer(
        J(x), J(router_w), J(router_b), J(w1), J(w3), J(w2), moe)
    whole = np.asarray(whole + xref.swiglu_ff(J(x), J(s1), J(s3), J(s2)))
    lower = lambda t, ins, attrs: registry.get(t).lower(None, ins, attrs)
    routed = lower("moe_router", {"X": [J(x)], "W": [J(router_w)],
                                  "Bias": [J(router_b)]},
                   {"k": k, "norm_topk": True, "scale": 2.0})
    total = np.asarray(xref.swiglu_ff(J(x), J(s1), J(s3), J(s2)), np.float64)
    for share in range(8):
        held = [2 * share, 2 * share + 1]
        part = lower("moe_experts", {
            "X": [J(x)], "Picks": routed["Picks"], "Weights": routed["Weights"],
            "W1": [J(w1[held])], "W3": [J(w3[held])], "W2": [J(w2[held])]},
            {"num_experts": n_exp, "experts_held": held})["Out"][0]
        total += np.asarray(part, np.float64)
        # the reference given that share computes the same part
        mine, _, _ = xref.moe_layer(
            J(x), J(router_w), J(router_b), J(w1[held]), J(w3[held]),
            J(w2[held]), dict(moe, experts_held=held))
        np.testing.assert_allclose(np.asarray(part), np.asarray(mine), atol=2e-4)
    np.testing.assert_allclose(total, whole, atol=5e-4)
    assert np.abs(whole).max() > 0.1


def test_xing4_bf16_stays_in_its_band_of_float32():
    model, eng = _xing_engine(dtype="bfloat16", seed=9)
    prompt = _prompt(29, 3)
    run = eng.start(GenRequest(prompt, max_new_tokens=4))
    got = np.array(eng.last_prefill_logits)
    eng.finish(run)
    want, own, gaps = xref.forward(
        _weights(model, eng), prompt, xref.spec_of(model),
        picks=_picks(run, len(prompt)))
    assert float(np.max(gaps)) < 5e-2
    assert np.abs(got - np.asarray(want)[-1]).max() < BF16_BAND


# ---------------------------------------------------------------------------
# the granitemoehybrid architecture at a tiny size: Mamba-2 state-space
# layers with an attention layer among them (no positions, no q/k norm, its
# own score scale), a dense feed-forward in every layer, four multipliers
# ---------------------------------------------------------------------------

from paddle_tpu.models import granite4h_reference as gref  # noqa: E402
from paddle_tpu.models.block_decoder import granitemoehybrid  # noqa: E402

GRANITE = dict(
    model_type="granitemoehybrid", vocab_size=97, hidden_size=64,
    intermediate_size=96, shared_intermediate_size=96, num_hidden_layers=4,
    layer_types=["mamba", "mamba", "attention", "mamba"],
    num_attention_heads=4, num_key_value_heads=2, mamba_n_heads=8,
    mamba_d_head=16, mamba_d_state=16, mamba_n_groups=1, mamba_d_conv=4,
    mamba_expand=2, mamba_chunk_size=8, mamba_conv_bias=True,
    mamba_proj_bias=False, attention_bias=False, attention_multiplier=0.0625,
    embedding_multiplier=12, residual_multiplier=0.22, logits_scaling=8,
    rms_norm_eps=1e-5, rope_theta=10000, position_embedding_type="nope",
    num_local_experts=0, num_experts_per_tok=0, tie_word_embeddings=True,
    max_position_embeddings=128)
# float32 both sides, on logits of ~0.2: the chunked form and the
# recurrence sum in another order
GRANITE_TOL = 2e-6


def _granite_engine(dtype="float32", seed=3, **kw):
    model = granitemoehybrid(GRANITE, max_context=128, dtype=dtype)
    kw = dict(dict(max_slots=3, page_size=4, prefill_chunk=8), **kw)
    _granite_engine.made += 1  # a name of its own: the registry counts by name
    return model, GenerationEngine(
        model, name="tgranite%d" % _granite_engine.made, scope=Scope(seed=seed),
        **kw)


_granite_engine.made = 0


@pytest.fixture(scope="module")
def granite():
    return _granite_engine()


def _granite_reference(model, eng, tokens):
    return np.asarray(gref.forward(
        _weights(model, eng), tokens, gref.spec_of(model)))


def test_granitemoehybrid_is_built_from_the_source_s_keys(granite):
    model, eng = granite
    assert model.blocks == [("mamba", "dense"), ("mamba", "dense"),
                            ("attention", "dense"), ("mamba", "dense")]
    assert model.ssm == {"heads": 8, "d_head": 16, "d_state": 16, "groups": 1,
                         "conv": 4, "chunk": 8}
    assert (model.position, model.qk_norm, model.attn_scale) == ("none", False, 0.0625)
    assert (model.embed_scale, model.residual_scale, model.logit_scale) == (12.0, 0.22, 8.0)
    assert model.d_ffn == 96 and model.tied_head and model.ssm_widths() == (128, 160)
    # a mamba layer: two state pools of different dtype and width; the
    # attention layer its K and V pages
    specs = model.cache_specs()
    assert [(s["kind"], s["layer"]) for s in specs] == [
        ("state", 0), ("state", 0), ("state", 1), ("state", 1),
        ("paged", 2), ("paged", 2), ("state", 3), ("state", 3)]
    conv, rec = specs[0], specs[1]
    assert (conv["width"], conv["dtype"], conv.get("form")) == (3 * 160, "float32", None)
    assert (rec["width"], rec["shape"], rec["dtype"], rec["form"]) == (
        8 * 16 * 16, [16, 128], "float32", "recurrent")
    bf16 = granitemoehybrid(GRANITE, max_context=128).cache_specs()
    assert (bf16[0]["dtype"], bf16[1]["dtype"]) == ("bfloat16", "float32")
    st = eng.stats()["kv"]
    assert st["recurrent_row_bytes"] == 3 * 8 * 16 * 16 * 4
    assert st["state_row_bytes"] == 3 * (8 * 16 * 16 + 3 * 160) * 4
    assert st["state_bytes"] == 4 * st["state_row_bytes"]  # 3 slots + scratch
    assert set(model.param_names()) == {
        n for n in eng.scope.vars
        if n.startswith("granite4h_") and "kv_" not in n and "state" not in n}
    assert not any("q_norm" in n for n in model.param_names())
    # Mamba-2's own initialisation: A in [1, 16], dt in [1e-3, 1e-1], D 1
    w = _weights(model, eng)
    a = np.exp(np.asarray(w["granite4h_l0_ssm_a_log"]))
    dt = np.logaddexp(0, np.asarray(w["granite4h_l0_ssm_dt_bias"]))
    assert a.min() >= 1 and a.max() <= 16 and a.std() > 1
    assert dt.min() >= 0.999e-3 and dt.max() <= 1.001e-1
    assert (np.asarray(w["granite4h_l0_ssm_d"]) == 1).all()
    for key, value in (("num_local_experts", 4), ("attention_bias", True),
                       ("mamba_proj_bias", True), ("mamba_conv_bias", False),
                       ("tie_word_embeddings", False), ("mamba_n_heads", 4)):
        with pytest.raises(ValueError):
            granitemoehybrid(dict(GRANITE, **{key: value}))


def test_granite_forward_program_matches_the_reference(granite):
    model, eng = granite
    tokens = np.array([_prompt(21, 1), _prompt(21, 2)], np.int64)
    main, _, feeds, fetches = model.build_forward(2, 21)
    with scope_guard(eng.scope):
        (logits,) = Executor().run(
            main, feed={"fwd_tokens": tokens[:, :, None]}, fetch_list=fetches)
    for b in range(2):
        want = _granite_reference(model, eng, tokens[b])
        assert np.abs(want).max() > 0.05
        np.testing.assert_allclose(np.asarray(logits)[b], want, atol=GRANITE_TOL)


def test_granite_chunked_prefill_then_decode_matches_the_full_forward_pass():
    """29 prompt tokens in chunks of 8, 8, 8 and 5, then six decode steps
    through the conv and recurrent state rows and the attention layer's
    pages: the first-token logits and every step's against the reference's
    one pass over the sequence, position by position."""
    model, eng = _granite_engine()
    prompt = _prompt(29)
    run = eng.start(GenRequest(prompt, max_new_tokens=8))
    got = [np.array(eng.last_prefill_logits)]
    for _ in range(6):
        eng.decode_step([run])
        got.append(np.array(eng.last_logits[run.slot]))
    tokens = prompt + run.tokens[:6]
    state = eng.state_rows(run.slot)
    eng.finish(run)
    want = _granite_reference(model, eng, tokens)
    for i, g in enumerate(got):
        np.testing.assert_allclose(g, want[len(prompt) - 1 + i], atol=GRANITE_TOL)
    # the slot's recurrent state of the first layer is the reference's
    _, last = gref.forward(_weights(model, eng), tokens, gref.spec_of(model),
                           states=True)
    assert last.shape == (3, 8, 16, 16)  # the mamba layers', in layer order
    for i, layer in enumerate((0, 1, 3)):
        mine = np.asarray(state["granite4h_l%d_ssm_state" % layer])
        np.testing.assert_allclose(
            mine.reshape(16, 8, 16).transpose(1, 2, 0), np.asarray(last[i]),
            atol=1e-6)
    from paddle_tpu.observability import registry

    hist = {n.rsplit("/", 1)[1]: (m["sum"], m["count"]) for n, m in
            registry.default_registry().snapshot().items()
            if n.startswith("serving/%s/gen_" % eng.name) and "ssm" in n}
    assert hist["gen_ssm_rows"] == (6, 6)  # six steps of one live row
    assert hist["gen_ssm_state_bytes"] == (6 * 2 * 3 * 8 * 16 * 16 * 4, 6)
    assert hist["gen_chunk_ssm_tokens"] == (29, 4)


def test_granite_prefix_hit_restores_both_states_and_equals_a_cold_admission():
    """A prompt admitted through a prefix hit (pages shared, conv and
    recurrent state restored from the boundary's snapshot) against the same
    prompt in an engine that has seen nothing, and against the reference."""
    model, warm = _granite_engine()
    _, cold = _granite_engine(prefix_cache=False)
    shared = _prompt(12, 7)
    first, second, third = (shared + _prompt(n, n) for n in (9, 10, 11))
    for p in (first, second):  # the second leaves the snapshot at 12
        warm.finish(warm.start(GenRequest(p, max_new_tokens=2)))
    st = warm.prefix_cache.stats()
    assert st["states_taken"] == st["cached_states"] == 1
    assert st["state_bytes"] == warm.state_row_bytes
    copied = warm._m_snap_copy_bytes
    assert copied.value(event="taken") == warm.state_row_bytes
    assert copied.value(event="restored") == 0
    run = warm.admit(GenRequest(third, max_new_tokens=4))
    assert (run.pf_pos, run.cut) == (12, 0)
    assert copied.value(event="restored") == warm.state_row_bytes
    while not warm.prefill_step(run):
        pass
    run_cold = cold.start(GenRequest(third, max_new_tokens=4))
    want = _granite_reference(model, warm, third)[-1]
    np.testing.assert_allclose(warm.last_prefill_logits, want, atol=GRANITE_TOL)
    np.testing.assert_allclose(
        warm.last_prefill_logits, cold.last_prefill_logits, atol=GRANITE_TOL)
    for _ in range(3):
        warm.decode_step([run])
        cold.decode_step([run_cold])
        np.testing.assert_allclose(
            warm.last_logits[run.slot], cold.last_logits[run_cold.slot],
            atol=GRANITE_TOL)
    assert run.tokens == run_cold.tokens


def test_granite_decode_step_leaves_idle_and_mid_prefill_states_untouched():
    """A decode step for one run: the other slots' state rows (one idle, one
    in the middle of its prefill) keep their bits, the recurrent ones too."""
    model, eng = _granite_engine()
    a = eng.start(GenRequest(_prompt(9, 1), max_new_tokens=4))
    b = eng.admit(GenRequest(_prompt(20, 2), max_new_tokens=4))
    eng.prefill_step(b)  # one chunk of three
    before = {s: eng.state_rows(s) for s in range(3)}
    eng.decode_step([a])
    after = {s: eng.state_rows(s) for s in range(3)}
    for s in set(range(3)) - {a.slot}:
        for name in before[s]:
            np.testing.assert_array_equal(
                np.asarray(before[s][name]), np.asarray(after[s][name]))
    changed = [name for name in before[a.slot] if not np.array_equal(
        np.asarray(before[a.slot][name]), np.asarray(after[a.slot][name]))]
    assert len(changed) == 6  # three layers' conv and recurrent rows
    eng.finish(a)
    eng.finish(b)


DEPARTURES = {
    "embedding_multiplier": dict(embedding_multiplier=1),
    "residual_multiplier": dict(residual_multiplier=1.0),
    "logits_scaling": dict(logits_scaling=1),
    "attention_multiplier": dict(attention_multiplier=16 ** -0.5),
    "rotary_added": dict(position_embedding_type="rope"),
}


@pytest.mark.parametrize("which", sorted(DEPARTURES) + ["qk_norm_added"])
def test_granite_multipliers_positions_and_norms_are_the_configuration_s(which):
    """Each of the four multipliers, the missing rotary and the missing q/k
    norm is in the decoder because the configuration says so: a decoder
    built with that one dropped (or added) over the same weights leaves the
    reference by a thousand times what the true one does. Weights ten times
    the published spread, so that the attention layer's scores are not all
    alike and its scale and positions show."""
    build = lambda config: granitemoehybrid(
        config, max_context=128, dtype="float32", init_std=0.2)
    model = build(GRANITE)
    if which == "qk_norm_added":
        other = build(GRANITE)
        other.qk_norm = True  # the source has no key for it: lfm2's norm
    else:
        other = build(dict(GRANITE, **DEPARTURES[which]))
    scope = Scope(seed=3)
    other.ensure_params(scope)  # the true decoder's, and the norm's scales
    tokens = np.array([_prompt(21, 1)], np.int64)
    want = np.asarray(gref.forward(
        {n: scope.vars[n] for n in model.param_names()}, tokens[0],
        gref.spec_of(model)))

    def off(decoder):
        main, _, _, fetches = decoder.build_forward(1, 21)
        with scope_guard(scope):
            (logits,) = Executor().run(
                main, feed={"fwd_tokens": tokens[:, :, None]}, fetch_list=fetches)
        return float(np.abs(np.asarray(logits)[0] - want).max())

    true = off(model)
    assert true < 1e-4 * np.abs(want).max(), true
    assert off(other) > 1000 * true, (which, off(other), true)
