"""The host reads a decode step's results one step late
(GenerationEngine.decode_step(ahead=True), GenerationScheduler._pass): step
n+1 is dispatched from step n's device-born ids before the host has read
them. Every reply is token for token what the synchronous path gives; an eos
is seen one step late and costs one row-step inside the row's own pages; a
length end is never dispatched past max_new; a step with a temperature row is
synchronous; nothing compiles after warm-up; close(drain=True) returns every
reply; a failing dispatch or read fails the runs in flight and releases their
slots; a step in flight owns its feed buffers. On a GPTDecoder and on a
small block decoder with a conv state and experts."""

import time

import numpy as np
import pytest

from paddle_tpu.executor import Scope
from paddle_tpu.models.block_decoder import lfm2_moe
from paddle_tpu.models.gpt_decoder import GPTDecoder
from paddle_tpu.observability import registry
from paddle_tpu.serving import GenerationEngine, GenerationScheduler, GenRequest
from paddle_tpu.serving.generation import FROM_DEVICE

NO_EOS = 10 ** 6  # no vocabulary holds it: every reply ends by length
GPT = dict(vocab_size=48, n_layer=2, n_head=2, d_model=16, d_inner=32,
           max_context=64)
# tests/test_block_decoder.py's miniature of lfm2_moe: conv layers beside
# attention over 2 KV heads, 8 experts, 2 a token
BLOCKS = dict(
    vocab_size=97, hidden_size=32, intermediate_size=48,
    moe_intermediate_size=24, num_attention_heads=4, num_key_value_heads=2,
    num_hidden_layers=5, num_dense_layers=1,
    layer_types=["conv", "full_attention", "conv", "conv", "full_attention"],
    num_experts=8, num_experts_per_tok=2, use_expert_bias=True,
    norm_topk_prob=True, routed_scaling_factor=1.0, conv_L_cache=3,
    norm_eps=1e-5, rope_theta=1e6, max_position_embeddings=64)
KINDS = ("gpt", "blocks")


def build(kind, name=None, **kw):
    # the block decoder: an untied head and wide weights, so that a reply
    # is not its prompt's last token over and over
    model = (GPTDecoder(**GPT) if kind == "gpt"
             else lfm2_moe(BLOCKS, max_context=64, dtype="float32",
                           tied_head=False, init_std=0.25))
    kw = dict(dict(max_slots=3, page_size=4, prefill_chunk=8, max_context=64,
                   cache_dir=None), **kw)
    eng = GenerationEngine(
        model, name=name or "pipe_" + kind, scope=Scope(seed=11), **kw)
    eng.warmup()
    return eng


@pytest.fixture(scope="module")
def engines():
    built = {}

    def get(kind):
        if kind not in built:
            built[kind] = build(kind)
        return built[kind]
    return get


def prompt(n, seed):
    return [int(v) for v in np.random.default_rng(seed).integers(2, 40, n)]


def hist(eng, name):
    """(sum, count) of one of the engine's histograms."""
    h = registry.default_registry().snapshot()[
        "serving/%s/%s" % (eng.name, name)]
    return np.array([h["sum"], h["count"]])


def drains(eng, reason):
    return registry.default_registry().snapshot()[
        "serving/%s/gen_pipeline_drains" % eng.name]["values"].get(
            "reason=%s" % reason, 0)


def an_eos(eng, prompt_, max_new, at):
    """An eos id that the greedy reply to `prompt_` first holds at index
    `at`, and that reply cut there (None where this prompt has none)."""
    tokens = eng.generate(prompt_, max_new_tokens=max_new, eos_id=NO_EOS).tokens
    if tokens[at] in tokens[:at]:
        return None, None
    return tokens[at], tokens[:at + 1]


def prompts_with_an_eos(eng, n, max_new, seeds, want=1):
    """[(prompt of n tokens, eos id, the reply cut at it)], the eos past the
    reply's second token, so that decode steps reach it."""
    found = []
    for seed in seeds:
        p = prompt(n, seed)
        for at in range(2, max_new - 1):
            eos, reply = an_eos(eng, p, max_new, at)
            if eos is not None:
                found.append((p, eos, reply))
                break
        if len(found) == want:
            break
    assert found, "no reply of these seeds has a token to end on"
    return found


def drive(eng, live, on_pass=None):
    """What the scheduler's pass does with the engine, by hand: dispatch
    the next step, read the one in flight, retire what the read ended."""
    live = list(live)
    while live or eng.steps_in_flight:
        if on_pass is not None:
            on_pass(live)
        for run in eng.decode_step(list(live), ahead=True):
            if run.done and run in live:
                live.remove(run)
                eng.finish(run)


# ---------------------------------------------------------------- the replies


@pytest.mark.parametrize("kind", KINDS)
def test_requests_admitted_mid_stream_get_the_tokens_generate_gives(
        engines, kind):
    """Greedy and seeded sampled requests, with an eos and without, some
    submitted while others decode: each reply is engine.generate()'s."""
    eng = engines(kind)
    shared = prompt(8, 1)
    asks = []
    for i in range(8):
        kw = dict(max_new_tokens=4 + 3 * (i % 4), eos_id=NO_EOS)
        if i % 4 == 3:
            kw.update(temperature=0.7, top_k=5, seed=100 + i)
        asks.append((shared + prompt(2 + i, 20 + i), kw))
    eos, _ = an_eos(eng, asks[1][0], asks[1][1]["max_new_tokens"], 3)
    if eos is not None:
        asks[1][1]["eos_id"] = eos
    serial = [eng.generate(p, **kw) for p, kw in asks]
    before = hist(eng, "gen_steps_in_flight")
    sched = GenerationScheduler(eng, timeout_ms=120000.0)
    try:
        futures = []
        for i, (p, kw) in enumerate(asks):
            futures.append(sched.submit(p, **kw))
            if i % 3 == 2:
                futures[0].result(120)  # the rest join a stream under way
                time.sleep(0.01)
        got = [f.result(120) for f in futures]
    finally:
        assert sched.close()
    assert [g.tokens for g in got] == [s.tokens for s in serial]
    assert [g.finish_reason for g in got] == [s.finish_reason for s in serial]
    if eos is not None:
        assert got[1].finish_reason == "eos" and len(got[1].tokens) == 4
    total, count = hist(eng, "gen_steps_in_flight") - before
    assert count > 0 and total > count  # some step went out one ahead
    assert eng.steps_in_flight == 0
    assert eng.pool.stats()["slots_in_use"] == 0


# ------------------------------------------------------------------ a late eos


@pytest.mark.parametrize("kind", KINDS)
def test_an_eos_costs_one_row_step_in_its_own_pages_and_the_next_tenant_is_right(
        engines, kind):
    eng = build(kind, name="pipe_eos_" + kind, max_slots=2)
    p_b, p_c = prompt(6, 4), prompt(11, 5)
    ((p_a, eos, want_a),) = prompts_with_an_eos(eng, 9, 10, range(300, 340))
    want_b = eng.generate(p_b, max_new_tokens=12, eos_id=NO_EOS).tokens
    want_c = eng.generate(p_c, max_new_tokens=6, eos_id=NO_EOS).tokens

    a = eng.start(GenRequest(p_a, max_new_tokens=10, eos_id=eos))
    b = eng.start(GenRequest(p_b, max_new_tokens=12, eos_id=NO_EOS))
    pools = [n for n in eng._state if n not in eng._state_names]
    own = {int(page) * eng.page_size + off
           for run in (a, b) for page in run.table if page
           for off in range(eng.page_size)}
    live = [a, b]
    before = {n: np.array(eng._state[n]) for n in pools}
    while not a.done:
        eng.decode_step(live, ahead=True)
    assert a.tokens == want_a and a.finish_reason == "eos"
    wasted = eng._in_flight
    assert wasted is not None and wasted.rides(a)  # one row-step for nothing
    slot = a.slot
    live.remove(a)
    eng.finish(a)
    # the slot's next tenant arrives while that step is unread
    c = eng.start(GenRequest(p_c, max_new_tokens=6, eos_id=NO_EOS))
    assert c.slot == slot
    live.append(c)
    drive(eng, live)
    after = {n: np.array(eng._state[n]) for n in pools}
    assert len(a.tokens) == len(want_a)  # the wasted id was thrown away
    assert b.tokens == want_b
    assert c.tokens == want_c
    own |= {int(page) * eng.page_size + off
            for page in c.table if page for off in range(eng.page_size)}
    own |= set(range(eng.page_size))  # page 0: the scratch page, idle rows'
    for n in pools:
        rows = np.flatnonzero(
            (before[n] != after[n]).reshape(len(before[n]), -1).any(axis=1))
        assert set(rows.tolist()) <= own, n


@pytest.mark.parametrize("kind", KINDS)
def test_a_late_eos_through_the_scheduler_gives_the_same_reply(engines, kind):
    eng = engines(kind)
    found = prompts_with_an_eos(eng, 7, 9, range(40, 80), want=3)
    others = [prompt(5 + i, 70 + i) for i in range(3)]
    want_others = [eng.generate(p, max_new_tokens=14, eos_id=NO_EOS).tokens
                   for p in others]
    sched = GenerationScheduler(eng, timeout_ms=120000.0)
    try:
        long = [sched.submit(p, max_new_tokens=14, eos_id=NO_EOS)
                for p in others[:2]]
        cut = [sched.submit(p, max_new_tokens=9, eos_id=eos)
               for p, eos, _ in found]
        long.append(sched.submit(others[2], max_new_tokens=14, eos_id=NO_EOS))
        got_cut = [f.result(120) for f in cut]
        got_long = [f.result(120).tokens for f in long]
    finally:
        assert sched.close()
    assert [g.tokens for g in got_cut] == [want for _, _, want in found]
    assert {g.finish_reason for g in got_cut} == {"eos"}
    assert got_long == want_others


# ---------------------------------------------------------------- a length end


@pytest.mark.parametrize("kind", KINDS)
def test_a_length_end_is_never_dispatched_past_max_new(
        engines, kind, monkeypatch):
    eng = engines(kind)
    fed = {}  # run -> the positions its decode steps were fed
    real = eng._dispatch_step

    def spy(runs, prev):
        step = real(runs, prev)
        for run, pos in zip(step.runs, step.positions):
            fed.setdefault(run, []).append(pos)
        return step
    monkeypatch.setattr(eng, "_dispatch_step", spy)
    asks = [(prompt(5 + 2 * i, 80 + i), 3 + 2 * i) for i in range(4)]
    runs = [eng.start(GenRequest(p, max_new_tokens=n, eos_id=NO_EOS))
            for p, n in asks[:3]]
    before = hist(eng, "gen_steps_in_flight")
    drive(eng, runs)
    total, count = hist(eng, "gen_steps_in_flight") - before
    assert total == 2 * count - 1  # every step but the first went out ahead
    for run, (p, n) in zip(runs, asks):
        assert len(run.tokens) == n and run.finish_reason == "length"
        # token i of n is fed at position len(p) + i - 1; the last is not fed
        assert fed[run] == list(range(len(p), len(p) + n - 1))
        assert max(fed[run]) < eng.pool.pages_for(len(p) + n) * eng.page_size
    assert drains(eng, "idle") >= 1  # the last step was read with none behind


# ----------------------------------------------------------- a temperature row


@pytest.mark.parametrize("kind", KINDS)
def test_a_step_with_a_temperature_row_is_synchronous(engines, kind):
    eng = engines(kind)
    p_g, p_s = prompt(6, 90), prompt(7, 91)
    sampled = dict(max_new_tokens=5, eos_id=NO_EOS, temperature=0.9, top_k=6,
                   seed=7)
    want_g = eng.generate(p_g, max_new_tokens=11, eos_id=NO_EOS).tokens
    want_s = eng.generate(p_s, **sampled).tokens
    g = eng.start(GenRequest(p_g, max_new_tokens=11, eos_id=NO_EOS))
    s = eng.start(GenRequest(p_s, **sampled))
    marks = []

    def mark(live):
        marks.append((s in live, hist(eng, "gen_steps_in_flight"),
                      drains(eng, "temperature")))
    drive(eng, [g, s], on_pass=mark)
    marks.append((False, hist(eng, "gen_steps_in_flight"),
                  drains(eng, "temperature")))
    assert g.tokens == want_g  # the greedy neighbour's tokens are unchanged
    assert s.tokens == want_s  # the seeded stream drew the same numbers
    with_s = [m for m in marks if m[0]]
    (_, first, d0), (_, last, d1) = with_s[0], marks[len(with_s)]
    total, count = last - first
    assert count == 4 and total == count  # each observed 1: synchronous
    assert d1 - d0 == count  # and was counted as a drain
    total, count = marks[-1][1] - last
    assert count > 1 and total == 2 * count - 1  # then greedy steps run ahead


# ----------------------------------------------------- warm-up, close, failure


@pytest.mark.parametrize("kind", KINDS)
def test_nothing_is_traced_after_warmup_and_close_returns_every_reply(
        engines, kind):
    eng = engines(kind)
    asks = [(prompt(4 + i, 110 + i),
             dict(max_new_tokens=5 + i, eos_id=NO_EOS,
                  **(dict(temperature=0.8, seed=i) if i == 2 else {})))
            for i in range(7)]
    serial = [eng.generate(p, **kw).tokens for p, kw in asks]
    traces, variants = eng.traces, len(eng._variants)
    sched = GenerationScheduler(eng, timeout_ms=120000.0)
    futures = [sched.submit(p, **kw) for p, kw in asks]
    assert sched.close(drain=True, timeout=120.0)
    assert all(f.done() for f in futures)  # no reply left behind a step
    assert [f.result(0).tokens for f in futures] == serial
    assert (eng.traces, len(eng._variants)) == (traces, variants)
    assert eng.steps_in_flight == 0
    assert eng.pool.stats()["slots_in_use"] == 0


@pytest.mark.parametrize("fails", ("dispatch", "read"))
@pytest.mark.parametrize("kind", KINDS)
def test_a_failing_step_fails_the_runs_in_flight_and_releases_their_slots(
        engines, kind, fails, monkeypatch):
    eng = engines(kind)
    p = [prompt(6, 130), prompt(9, 131)]
    want = eng.generate(p[0], max_new_tokens=6, eos_id=NO_EOS).tokens
    name = "_dispatch_step" if fails == "dispatch" else "_read_step"
    real, calls = getattr(eng, name), []

    def breaks(*args):
        calls.append(args)
        if len(calls) == 3:
            raise RuntimeError("injected %s failure" % fails)
        return real(*args)
    monkeypatch.setattr(eng, name, breaks)
    sched = GenerationScheduler(eng, timeout_ms=120000.0)
    try:
        futures = [sched.submit(q, max_new_tokens=12, eos_id=NO_EOS) for q in p]
        for f in futures:
            with pytest.raises(RuntimeError, match="decode failed"):
                f.result(120)
        assert eng.steps_in_flight == 0
        assert eng.pool.stats()["slots_in_use"] == 0
        # the engine goes on serving
        assert sched.submit(p[0], max_new_tokens=6,
                            eos_id=NO_EOS).result(120).tokens == want
    finally:
        assert sched.close()


# ------------------------------------------------------------ the feed buffers


@pytest.mark.parametrize("kind", KINDS)
def test_a_step_in_flight_owns_its_feed_buffers(engines, kind, monkeypatch):
    """The persistent _dec_feeds rows are written again for step n+1 while
    step n may not have taken its feeds yet: what a step is called with is
    its own copy, and stays as it was fed."""
    eng = engines(kind)
    taken = []  # (the feeds a decode call got, their values then)
    real = eng._dispatch

    def spy(variant, feeds, mut_in):
        if "dec_tokens" in feeds:
            host = {n: a for n, a in feeds.items() if isinstance(a, np.ndarray)}
            taken.append((host, {n: a.copy() for n, a in host.items()}))
        return real(variant, feeds, mut_in)
    monkeypatch.setattr(eng, "_dispatch", spy)
    runs = [eng.start(GenRequest(prompt(5 + i, 140 + i), max_new_tokens=6,
                                 eos_id=NO_EOS)) for i in range(2)]
    drive(eng, runs)
    assert len(taken) >= 5
    for host, then in taken:
        assert set(host) == set(eng._variant("decode").feed_names)
        for n, arr in host.items():
            assert not np.shares_memory(arr, eng._dec_feeds[n]), n
            np.testing.assert_array_equal(arr, then[n])
    # and the steps did differ: the buffers were rewritten under them
    assert any((a[1]["dec_positions"] != b[1]["dec_positions"]).any()
               for a, b in zip(taken, taken[1:]))
    # the first step's rows took the host's tokens, the others the device's
    slots = [run.slot for run in runs]
    assert (taken[0][1]["dec_tokens"][slots, 0] >= 0).all()
    assert (taken[1][1]["dec_tokens"][slots, 0] == FROM_DEVICE).all()
