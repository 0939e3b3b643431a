"""Generation serving tier (paddle_tpu/serving/generation.py, kv_cache.py,
models/gpt_decoder.py): continuous-batch vs serial token parity, mid-stream
admission bit-parity, NMT beam-search round-trip through aot_serve_lowering,
decode-state donation aliasing vs single-shot, compile-cache geometry
keying across fresh processes, scheduler lifecycle, and the HTTP :generate
route."""

import json
import os
import subprocess
import sys
import urllib.error
import urllib.request

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu import framework
from paddle_tpu.executor import Scope, aot_serve_lowering, scope_guard
from paddle_tpu.models.gpt_decoder import GPTDecoder
from paddle_tpu.serving import (
    GenerationEngine,
    GenerationScheduler,
    GenRequest,
    ModelServer,
    PagedKVPool,
    PoolExhausted,
    QueueFullError,
    ServingEngine,
    ShutdownError,
)

MODEL_KW = dict(
    vocab_size=24, n_layer=2, n_head=2, d_model=16, d_inner=32, max_context=16
)
NO_EOS = 999  # never sampled: forces "length" finishes in timing-sensitive tests


@pytest.fixture(scope="module")
def gen_engine():
    model = GPTDecoder(**MODEL_KW)
    eng = GenerationEngine(
        model, name="tgen", max_slots=3, page_size=4, max_context=16,
        cache_dir=None,
    )
    eng.warmup()
    return eng


# ---------------------------------------------------------------- allocator


def test_paged_pool_reuse_and_exhaustion():
    pool = PagedKVPool(n_pages=5, page_size=4, max_slots=2, max_pages_per_slot=2)
    s0, t0 = pool.acquire(8)   # 2 pages
    s1, t1 = pool.acquire(5)   # 2 pages
    assert s0 != s1
    assert 0 not in set(t0[t0 != 0]) and 0 not in set(t1[t1 != 0])
    assert pool.stats()["pages_in_use"] == 4
    with pytest.raises(PoolExhausted):
        pool.acquire(1)  # no slot left
    pool.release(s0)
    used = set(int(p) for p in t0 if p != 0)
    s2, t2 = pool.acquire(8)  # page reuse on retirement
    assert set(int(p) for p in t2 if p != 0) == used
    pool.release(s1)
    pool.release(s2)
    assert pool.stats() == {
        "pages_total": 4, "pages_in_use": 0, "pages_shared": 0,
        "slots_total": 2, "slots_in_use": 0, "slot_occupancy": 0.0,
        "resident_bytes": 0, "storage_dtype": "float32", "state_bytes": 0,
    }


# ------------------------------------------------------------ token parity


def test_continuous_batch_matches_serial_decode(gen_engine):
    """(a) token-for-token parity: mixed prompt/output lengths through the
    continuous scheduler == one-request-at-a-time decode."""
    eng = gen_engine
    cases = [
        ([3, 7, 11, 2, 9], 3),
        ([1, 2], 6),
        ([5, 6, 7], 5),
        ([9, 8, 7, 6, 5, 4, 3], 7),
        ([2, 4], 4),
        ([13, 12, 11, 10], 5),
    ]
    serial = [eng.generate(p, max_new_tokens=m) for p, m in cases]
    sched = GenerationScheduler(eng, timeout_ms=60000.0)
    try:
        futs = [sched.submit(p, max_new_tokens=m) for p, m in cases]
        results = [f.result(60) for f in futs]
    finally:
        assert sched.close(drain=True)
    for (p, m), want, got in zip(cases, serial, results):
        assert got.tokens == want.tokens, (p, got.tokens, want.tokens)
        assert got.finish_reason == want.finish_reason
    st = eng.pool.stats()
    assert st["slots_in_use"] == 0
    # every slot reference is gone; the only pages still in use are full
    # prompt pages the prefix cache pinned — all reclaimable on demand
    cached = eng.prefix_cache.stats()["cached_pages"]
    assert st["pages_in_use"] == cached == eng.prefix_cache.reclaimable()
    assert eng.traces == len(eng._variants), "hot loop retraced"


def test_paged_decode_matches_dense_forward(gen_engine):
    """The paged decode path reproduces the whole-sequence dense program's
    logits (same params, same math, different cache plumbing) to f32
    rounding: the two programs reduce over differently shaped operands, so
    XLA may order the sums differently — the contract is numerical, a few
    ulps on logits of magnitude ~1, not bit equality."""
    eng = gen_engine
    model, T = eng.model, 16
    main, _, feeds, fetches = model.build_forward(1, T)
    with scope_guard(eng.scope):
        serve, ro, mut = aot_serve_lowering(main, feeds, fetches, eng.scope)
    assert not mut

    prompt, n_new = [3, 7, 11, 2, 9], 4

    def oracle_row(tokens):
        buf = np.zeros((1, T, 1), np.int64)
        buf[0, :len(tokens), 0] = tokens
        (lg,) = serve({"fwd_tokens": buf}, ro, {})
        return np.asarray(lg)[0, len(tokens) - 1]

    req = GenRequest(prompt, max_new_tokens=n_new, eos_id=NO_EOS)
    run = eng.start(req)
    toks = list(prompt) + [run.tokens[-1]]
    close = dict(rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(eng.last_prefill_logits, oracle_row(prompt), **close)
    try:
        while not run.done:
            eng.decode_step([run])
            np.testing.assert_allclose(
                eng.last_logits[run.slot], oracle_row(toks), **close
            )
            toks.append(run.tokens[-1])
    finally:
        eng.finish(run)


def test_mid_stream_admit_does_not_perturb_other_slots(gen_engine):
    """(b) admitting a request mid-batch never changes another live slot's
    logits — bit-parity against a solo run of the same request."""
    eng = gen_engine
    req_a = dict(prompt=[3, 1, 4, 1, 5], max_new_tokens=8, eos_id=NO_EOS)

    def drive(mid_admit):
        run = eng.start(GenRequest(**req_a))
        rows = [eng.last_prefill_logits.copy()]
        other = None
        try:
            for step in range(7):
                live = [run]
                if mid_admit and step == 3:
                    other = eng.start(
                        GenRequest([9, 2, 6], max_new_tokens=12, eos_id=NO_EOS)
                    )
                if other is not None and not other.done:
                    live.append(other)
                eng.decode_step(live)
                rows.append(eng.last_logits[run.slot].copy())
        finally:
            eng.finish(run)
            if other is not None:
                eng.finish(other)
        return rows

    solo = drive(mid_admit=False)
    shared = drive(mid_admit=True)
    assert len(solo) == len(shared)
    for i, (a, b) in enumerate(zip(solo, shared)):
        np.testing.assert_array_equal(a, b, err_msg="step %d" % i)


def test_sampling_deterministic_per_seed(gen_engine):
    eng = gen_engine
    kw = dict(max_new_tokens=6, temperature=0.7, top_k=4, eos_id=NO_EOS)
    a = eng.generate([2, 3, 5], seed=11, **kw)
    b = eng.generate([2, 3, 5], seed=11, **kw)
    c = eng.generate([2, 3, 5], seed=12, **kw)
    assert a.tokens == b.tokens
    assert max(a.tokens) < MODEL_KW["vocab_size"]
    assert a.tokens != c.tokens or True  # different seed may still collide


# ------------------------------------------------- NMT infer path round-trip


def test_nmt_infer_roundtrips_through_aot_serve_lowering():
    """(c) the beam-search XLA-While infer model still lowers through
    aot_serve_lowering and matches the Executor bit-for-bit."""
    from paddle_tpu.models import machine_translation as mt

    B, T, VOCAB = 2, 4, 10
    main, startup = framework.Program(), framework.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        src = fluid.layers.data(
            name="src", shape=[B, T, 1], dtype="int64", append_batch_size=False
        )
        main.global_block().create_var(name="src_len", shape=(B,), dtype="int64")
        src._len_name = "src_len"
        ids, scores = mt.infer_model(
            src, VOCAB, beam_size=2, max_out_len=T + 1, start_id=0, end_id=1
        )
    fetch = [ids.name, scores.name, ids._hyp_len.name]
    rng = np.random.RandomState(5)
    feed = {
        "src": rng.randint(2, VOCAB, (B, T, 1)).astype(np.int64),
        "src_len": np.array([T, T - 1], np.int64),
    }
    scope = Scope(seed=0)
    with scope_guard(scope):
        exe = fluid.Executor()
        exe.run(startup)
        ref = exe.run(main, feed=feed, fetch_list=fetch)
        serve, ro, mut = aot_serve_lowering(
            main, ["src", "src_len"], fetch, scope
        )
    got = serve(feed, ro, mut)
    assert len(got) == len(ref) == 3
    for g, r in zip(got, ref):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(r))


# --------------------------------------------------------- donation aliasing


def _save_mlp(tmp_path):
    main, startup = framework.Program(), framework.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.layers.data(name="alias_x", shape=[4], dtype="float32")
        y = fluid.layers.fc(input=x, size=3, act="softmax")
    exe = fluid.Executor(fluid.CPUPlace())
    model_dir = str(tmp_path / "alias_mlp")
    with scope_guard(Scope(seed=1)):
        exe.run(startup)
        fluid.io.save_inference_model(model_dir, ["alias_x"], [y], exe,
                                      main_program=main)
    return model_dir


def test_decode_state_donated_single_shot_not(gen_engine, tmp_path):
    """Donation is a property of the compiled executable, not convention:
    the decode/prefill variants alias their KV-pool args in place; the
    single-shot ServingEngine variants must not alias anything."""
    dec = gen_engine._variant("decode")
    assert "input_output_alias" in dec.fn.as_text()
    pre = gen_engine._variant("prefill:%d" % gen_engine.prefill_buckets[0])
    assert "input_output_alias" in pre.fn.as_text()

    sse = ServingEngine(
        _save_mlp(tmp_path), name="alias_mlp", batch_buckets=(1, 2),
        cache_dir=None,
    )
    sse.warmup()
    assert sse._variants
    for fn in sse._variants.values():
        assert "input_output_alias" not in fn.as_text()


# ----------------------------------------------- compile-cache geometry keys

_CACHE_BOOT = r"""
import os, json, sys
os.environ.setdefault("JAX_PLATFORMS", "cpu")
from paddle_tpu.models.gpt_decoder import GPTDecoder
from paddle_tpu.serving.generation import GenerationEngine
cache_dir, page_size = sys.argv[1], int(sys.argv[2])
m = GPTDecoder(vocab_size=16, n_layer=1, n_head=2, d_model=8, d_inner=16,
               max_context=8)
e = GenerationEngine(m, name="cgeom", max_slots=2, page_size=page_size,
                     max_context=8, prefill_buckets=(4,), cache_dir=cache_dir)
e.warmup()
print(json.dumps({"traces": e.traces, "cache_hits": e.cache_hits,
                  "variants": len(e._variants)}))
"""


@pytest.mark.slow
def test_cache_geometry_misses_in_fresh_process(tmp_path):
    """Satellite: same geometry second boot = all cache hits, zero traces;
    flipping page size in a fresh process must MISS (never replay a stale
    executable against a differently-shaped pool)."""
    cache = str(tmp_path / "gen_cache")
    env = dict(os.environ, JAX_PLATFORMS="cpu")

    def boot(page_size):
        out = subprocess.run(
            [sys.executable, "-c", _CACHE_BOOT, cache, str(page_size)],
            capture_output=True, text=True, env=env, timeout=600,
        )
        assert out.returncode == 0, out.stderr[-2000:]
        return json.loads(out.stdout.strip().splitlines()[-1])

    first = boot(4)
    assert first["traces"] == first["variants"] == 2
    warm = boot(4)
    assert warm["traces"] == 0
    assert warm["cache_hits"] == warm["variants"] == 2
    flipped = boot(2)
    assert flipped["traces"] == flipped["variants"] == 2, flipped


# ------------------------------------------------------- scheduler lifecycle


def test_scheduler_backpressure_and_shutdown():
    model = GPTDecoder(**MODEL_KW)
    eng = GenerationEngine(
        model, name="tgen_bp", max_slots=1, page_size=4, max_context=16,
        prefill_buckets=(4,), cache_dir=None,
    )
    eng.warmup()
    sched = GenerationScheduler(eng, max_queue_requests=1, timeout_ms=60000.0)
    futs = [sched.submit([2, 3], max_new_tokens=14, eos_id=NO_EOS)]
    with pytest.raises(QueueFullError):
        # the single slot drains at one request per 14 decode steps; flooding
        # submits must hit the bounded queue (limit 1) and fast-fail
        for _ in range(200):
            futs.append(sched.submit([4, 5], max_new_tokens=14, eos_id=NO_EOS))
    assert sched.close(drain=False)  # fail-fast close joins the worker
    for f in futs:
        try:
            f.result(5)  # completed before close, or failed at shutdown
        except (ShutdownError, RuntimeError):
            pass
    st = eng.pool.stats()
    assert st["slots_in_use"] == 0 and st["pages_in_use"] == 0

    with pytest.raises(ShutdownError):
        sched.submit([1, 2])

    with pytest.raises(ValueError):
        GenRequest([], max_new_tokens=1)
    with pytest.raises(ValueError):
        GenRequest([1], max_new_tokens=0)


# ------------------------------------------------------------- HTTP :generate


def test_http_generate_route(gen_engine):
    want = gen_engine.generate([5, 4, 3], max_new_tokens=5)
    server = ModelServer(request_timeout_ms=60000.0)
    server.add_generation_model("tgen", engine=gen_engine)
    port = server.start()
    try:
        body = json.dumps(
            {"prompt": [5, 4, 3], "max_new_tokens": 5}
        ).encode()
        req = urllib.request.Request(
            "http://127.0.0.1:%d/v1/models/tgen:generate" % port,
            data=body, headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=60) as resp:
            doc = json.loads(resp.read())
        assert doc["tokens"] == want.tokens
        assert doc["finish_reason"] == want.finish_reason
        assert doc["prompt_len"] == 3

        # wrong verb on a generation model -> 400
        req = urllib.request.Request(
            "http://127.0.0.1:%d/v1/models/tgen:predict" % port,
            data=b"{}", headers={"Content-Type": "application/json"},
        )
        try:
            urllib.request.urlopen(req, timeout=10)
            assert False, "expected 400"
        except urllib.error.HTTPError as e:
            assert e.code == 400

        # bad payload -> 400; unknown model -> 404
        req = urllib.request.Request(
            "http://127.0.0.1:%d/v1/models/tgen:generate" % port,
            data=b'{"prompt": []}',
            headers={"Content-Type": "application/json"},
        )
        try:
            urllib.request.urlopen(req, timeout=10)
            assert False, "expected 400"
        except urllib.error.HTTPError as e:
            assert e.code == 400
        req = urllib.request.Request(
            "http://127.0.0.1:%d/v1/models/nope:generate" % port,
            data=b'{"prompt": [1]}',
            headers={"Content-Type": "application/json"},
        )
        try:
            urllib.request.urlopen(req, timeout=10)
            assert False, "expected 404"
        except urllib.error.HTTPError as e:
            assert e.code == 404

        # health/describe include the generation model
        with urllib.request.urlopen(
            "http://127.0.0.1:%d/v1/models" % port, timeout=10
        ) as resp:
            desc = json.loads(resp.read())
        assert desc["tgen"]["kind"] == "generate"
        assert desc["tgen"]["stats"]["traces"] == desc["tgen"]["stats"]["variants"]
    finally:
        server.stop(drain=True)


# ------------------------------------------- chunked prefill / prefix cache


def test_chunked_prefill_matches_whole_prompt(gen_engine):
    """Prefilling a long prompt in small chunks must be bit-identical to
    covering it with one big bucket — same first-token logits, same tokens."""
    eng_a = gen_engine  # buckets (2,4,8,16): this prompt runs as ONE chunk
    eng_b = GenerationEngine(
        eng_a.model, name="tgen_chunk", scope=eng_a.scope, max_slots=3,
        page_size=4, max_context=16, prefill_chunk=4, cache_dir=None,
        prefix_cache=False,
    )
    eng_b.warmup()
    assert eng_b.prefill_buckets == (2, 4)
    prompt = [3, 7, 11, 2, 9, 4, 1, 8, 6, 5, 10, 12, 2]  # 13 -> 4+4+4+1
    ra = eng_a.generate(prompt, max_new_tokens=3, eos_id=NO_EOS)
    la = eng_a.last_prefill_logits.copy()
    rb = eng_b.generate(prompt, max_new_tokens=3, eos_id=NO_EOS)
    assert eng_b._m_chunks.value() == 4
    np.testing.assert_array_equal(la, eng_b.last_prefill_logits)
    assert rb.tokens == ra.tokens
    assert rb.finish_reason == ra.finish_reason


def test_scheduler_interleaves_chunks_token_parity():
    """Long prompts streamed through the chunking scheduler produce the
    same tokens as serial whole-prompt decode, while short requests keep
    streaming (the scheduler runs decode steps between chunks)."""
    model = GPTDecoder(**MODEL_KW)
    eng = GenerationEngine(
        model, name="tgen_il", max_slots=3, page_size=4, max_context=16,
        prefill_chunk=4, cache_dir=None, prefix_cache=False,
    )
    eng.warmup()
    cases = [
        ([9, 8, 7, 6, 5, 4, 3, 2, 1, 10, 11, 12], 3),  # 3 chunks
        ([1, 2], 8),
        ([5, 6, 7, 8, 9, 10, 11, 12, 13], 4),  # 3 chunks
        ([2, 4], 6),
    ]
    serial = [eng.generate(p, max_new_tokens=m) for p, m in cases]
    sched = GenerationScheduler(eng, timeout_ms=60000.0)
    try:
        futs = [sched.submit(p, max_new_tokens=m) for p, m in cases]
        results = [f.result(60) for f in futs]
    finally:
        assert sched.close(drain=True)
    for (p, m), want, got in zip(cases, serial, results):
        assert got.tokens == want.tokens, (p, got.tokens, want.tokens)
    assert eng.traces == len(eng._variants), "hot loop retraced"


def test_prefix_cache_sharing_refcounts_and_parity():
    """A shared system prompt prefills once: the second request's leading
    pages come from the trie (refcounted, never copied), its prefill starts
    past them, and its logits/tokens are bit-identical to a no-cache run."""
    model = GPTDecoder(**MODEL_KW)
    eng = GenerationEngine(
        model, name="tgen_px", max_slots=2, page_size=4, max_context=16,
        cache_dir=None,
    )
    eng.warmup()
    ref = GenerationEngine(
        model, name="tgen_px_ref", scope=eng.scope, max_slots=2, page_size=4,
        max_context=16, cache_dir=None, prefix_cache=False,
    )
    ref.warmup()
    sys_prompt = [7, 3, 9, 1, 2, 8, 4, 6]  # two full pages

    first = eng.generate(sys_prompt + [5], max_new_tokens=2, eos_id=NO_EOS)
    st = eng.prefix_cache.stats()
    assert st["cached_pages"] == 2 and st["pages_hit"] == 0

    want = ref.generate(sys_prompt + [5, 11], max_new_tokens=4, eos_id=NO_EOS)
    lref = ref.last_prefill_logits.copy()
    got = eng.generate(sys_prompt + [5, 11], max_new_tokens=4, eos_id=NO_EOS)
    st = eng.prefix_cache.stats()
    assert st["pages_hit"] == 2 and st["lookups_hit"] == 1
    assert got.tokens == want.tokens
    np.testing.assert_array_equal(eng.last_prefill_logits, lref)
    assert first.tokens[0] == want.tokens[0] or True  # prompts differ past prefix

    # mid-run refcounts: trie + slot share the pages; decode never writes
    # through them (positions >= prompt len land in private pages)
    run = eng.admit(GenRequest(sys_prompt + [9, 9], max_new_tokens=2,
                               eos_id=NO_EOS))
    assert run.pf_pos == 8, "prefill must start past the two shared pages"
    shared = [int(p) for p in run.table[:2]]
    assert all(eng.pool.page_refcount(p) == 2 for p in shared)
    assert eng.pool.stats()["pages_shared"] == 2
    while not eng.prefill_step(run):
        pass
    while not run.done:
        eng.decode_step([run])
    eng.finish(run)
    assert all(eng.pool.page_refcount(p) == 1 for p in shared)
    assert eng.pool.stats()["pages_shared"] == 0
    assert eng.prefix_cache.reclaimable() == eng.prefix_cache.stats()["cached_pages"]


def test_prefix_cache_trie_lru_and_guarded_eviction():
    pool = PagedKVPool(n_pages=8, page_size=2, max_slots=2,
                       max_pages_per_slot=4)
    from paddle_tpu.serving import PrefixCache

    cache = PrefixCache(pool, capacity_pages=2)
    s, t = pool.acquire(4)  # 2 pages
    assert cache.insert([1, 2, 3, 4], t) == 2
    pool.release(s)
    # lookup pins; the final prompt token is never eligible
    got = cache.lookup([1, 2, 3, 4])
    assert got == [int(t[0])] and pool.page_refcount(t[0]) == 2
    pool.unpin_pages(got)
    got = cache.lookup([1, 2, 3, 4, 9])
    assert got == [int(t[0]), int(t[1])]
    pool.unpin_pages(got)
    got = cache.lookup([1, 2, 9])  # page 1 matches; divergence is at token 3
    assert got == [int(t[0])]
    pool.unpin_pages(got)
    assert cache.lookup([1, 9, 9]) == []  # diverges inside the first page

    # at capacity, inserting a new prompt LRU-evicts the older chain
    s2, t2 = pool.acquire(4)
    assert cache.insert([5, 6, 7, 8], t2) == 2
    pool.release(s2)
    assert cache.lookup([1, 2, 3, 4, 9]) == []
    got = cache.lookup([5, 6, 7, 8, 9])
    assert got == [int(t2[0]), int(t2[1])]
    pool.unpin_pages(got)

    # eviction never touches a page a slot still reads
    s3, t3 = pool.acquire(3, shared_pages=[int(t2[0])])
    assert cache.evict_for(2) == 1  # only the unshared deep page went
    assert cache.lookup([5, 6, 9]) == [int(t2[0])]
    pool.unpin_pages([int(t2[0])])
    pool.release(s3)
    assert cache.clear() == 1
    assert pool.stats()["pages_in_use"] == 0


def test_prefix_trie_keys_a_page_by_its_parent_and_evicts_leaves_first():
    """Two prompts that share a first page branch under it; the shared page
    has two children and goes only after both; the same page's tokens under
    another parent are another node; at capacity an insert never hangs a
    page from a node the eviction just took; and a long prompt costs its
    tokens once (its keys are one page each, not whole prefixes)."""
    from paddle_tpu.serving import PrefixCache

    pool = PagedKVPool(n_pages=16, page_size=2, max_slots=3,
                       max_pages_per_slot=5)
    cache = PrefixCache(pool, capacity_pages=5)
    sa, ta = pool.acquire(6)
    assert cache.insert([1, 2, 3, 4, 5, 6], ta) == 3
    sb, tb = pool.acquire(6, shared_pages=[int(ta[0])])
    assert cache.insert([1, 2, 7, 8, 3, 4], tb) == 2  # the first page is there
    assert all(len(key[1]) == 2 for key in cache._nodes)  # one page a key
    # [3, 4] under [1, 2, 7, 8] is not [3, 4] under [1, 2]
    got = cache.lookup([1, 2, 7, 8, 3, 4, 0])
    assert got == [int(ta[0]), int(tb[1]), int(tb[2])]
    pool.unpin_pages(got)
    pool.release(sa)
    pool.release(sb)
    # leaves first, coldest first: the first prompt's chain from its end,
    # the shared first page only after both branches under it
    assert cache.evict_for(2) == 2
    assert cache.lookup([1, 2, 3, 4, 5, 6, 0]) == [int(ta[0])]
    pool.unpin_pages([int(ta[0])])
    assert cache.evict_for(9) == 3 and cache.stats()["cached_pages"] == 0
    assert pool.stats()["pages_in_use"] == 0
    # at capacity: a third prompt's pages push the coldest leaves out and
    # every page left in the trie can still be reached from the top
    cache = PrefixCache(pool, capacity_pages=3)
    for first in (10, 20, 30):
        s, t = pool.acquire(6)
        cache.insert([first, 1, first, 2, first, 3], t)
        pool.release(s)
    assert cache.stats()["cached_pages"] == 3
    got = cache.lookup([30, 1, 30, 2, 30, 3, 0])
    assert len(got) == 3
    pool.unpin_pages(got)
    assert cache.lookup([10, 1, 10, 2, 0]) == []
    assert cache.clear() == 3 and pool.stats()["pages_in_use"] == 0


# ------------------------------------------- the ids are born on the device


IDS_KW = dict(MODEL_KW, max_context=48)


def _ids_engine(name, **kw):
    eng = GenerationEngine(
        GPTDecoder(**IDS_KW), name=name, max_slots=3, page_size=4,
        max_context=48, **dict(dict(cache_dir=None), **kw))
    eng.warmup()
    return eng


@pytest.fixture(scope="module")
def ids_engine():
    return _ids_engine("tgen_ids")


def _host_sample(logits, temperature, top_k, rng):
    """The host sampler as it stood before the ids moved to the device
    (numpy, float64, one rng.choice a token): the contract of a seeded
    request's stream."""
    z = np.asarray(logits, np.float64) / temperature
    if top_k and top_k < z.size:
        kth = np.partition(z, -top_k)[-top_k]
        z = np.where(z < kth, -np.inf, z)
    p = np.exp(z - z.max())
    p /= p.sum()
    return int(rng.choice(z.size, p=p))


def _fetch_hist(name):
    from paddle_tpu.observability import registry

    h = registry.default_registry().snapshot()[
        "serving/%s/gen_fetch_bytes" % name]
    return h["count"], h["sum"]


def test_greedy_ids_are_the_argmax_of_the_step_s_logits_rows_joining_and_leaving(
        ids_engine):
    """A decode step's ids equal argmax over the rows of last_logits, which
    is what the host sampler chose: 18 steps, a second row joining at step 3
    and leaving at its length, a third joining at step 8; the first token
    likewise from the prompt's logits. An all-greedy step fetches its ids
    and nothing else (this decoder has no routers)."""
    eng = ids_engine
    a = eng.start(GenRequest([3, 1, 4, 1, 5], max_new_tokens=19, eos_id=NO_EOS))
    assert a.tokens == [int(np.argmax(eng.last_prefill_logits))]
    joins = {3: GenRequest([9, 2, 6], max_new_tokens=6, eos_id=NO_EOS),
             8: GenRequest([7, 7, 1, 2], max_new_tokens=12, eos_id=NO_EOS)}
    live, seen = [a], [a]
    count0, sum0 = _fetch_hist("tgen_ids")
    try:
        for step in range(18):
            if step in joins:
                run = eng.start(joins[step])
                assert run.tokens == [int(np.argmax(eng.last_prefill_logits))]
                live.append(run)
                seen.append(run)
            eng.decode_step(live)
            assert eng._last_logits._host is None  # the step copied no logits
            rows = eng.last_logits
            for run in live:
                assert run.tokens[-1] == int(np.argmax(rows[run.slot])), step
            assert eng.stats()["decode_fetch_bytes"] == 3 * 4
            for run in [r for r in live if r.done]:
                eng.finish(run)
                live.remove(run)
    finally:
        for run in live:
            eng.finish(run)
    assert [len(r.tokens) for r in seen] == [19, 6, 11]
    assert [r.finish_reason for r in seen] == ["length", "length", None]
    count1, sum1 = _fetch_hist("tgen_ids")
    assert (count1 - count0, sum1 - sum0) == (18, 18 * 12)
    # the serial reference path replies with the same tokens
    again = eng.generate([3, 1, 4, 1, 5], max_new_tokens=19, eos_id=NO_EOS)
    assert again.tokens == seen[0].tokens


def test_a_tie_goes_to_the_first_index_as_numpy_s_argmax():
    """The last norm's scale at 0 and its shift at b make every row's hidden
    state b; head columns 3 and 7 equal b and the others 0: logits 3 and 7
    tie at |b|^2 above the rest, and the device's id is 3, the first."""
    eng = _ids_engine("tgen_tie")
    model = eng.model
    b = np.linspace(0.5, 1.5, IDS_KW["d_model"]).astype(np.float32)
    head = np.zeros((IDS_KW["d_model"], IDS_KW["vocab_size"]), np.float32)
    head[:, 3] = head[:, 7] = b
    eng.set_params({
        model._p("lnf_w"): np.zeros_like(b), model._p("lnf_b"): b,
        model._p("head_w"): head})
    runs = [eng.start(GenRequest(p, max_new_tokens=4, eos_id=NO_EOS))
            for p in ([2, 5, 8], [11, 4])]
    try:
        row = eng.last_prefill_logits
        assert row[3] == row[7] == row.max() > 0
        assert [r.tokens for r in runs] == [[3], [3]]
        eng.decode_step(runs)
        rows = eng.last_logits
        for run in runs:
            row = rows[run.slot]
            assert row[3] == row[7] == row.max() > 0
            assert run.tokens[-1] == int(np.argmax(row)) == 3
    finally:
        for run in runs:
            eng.finish(run)


def test_a_step_mixing_greedy_and_sampled_rows_keeps_the_seeded_stream(
        ids_engine):
    """Rows with a temperature get the tokens the host sampler gives for the
    same seed from that row's logits, the greedy row its argmax; the step
    fetches the ids and one row of logits for each sampled row."""
    eng = ids_engine
    vocab = IDS_KW["vocab_size"]
    reqs = [
        GenRequest([3, 1, 4], max_new_tokens=12, eos_id=NO_EOS),
        GenRequest([2, 7, 1, 8], max_new_tokens=12, eos_id=NO_EOS,
                   temperature=0.8, top_k=5, seed=7),
        GenRequest([6, 6], max_new_tokens=12, eos_id=NO_EOS,
                   temperature=1.3, seed=9),
    ]
    rngs = [None, np.random.default_rng(7), np.random.default_rng(9)]

    def want(req, rng, row):
        if rng is None:
            return int(np.argmax(row))
        return _host_sample(row, req.temperature, req.top_k, rng)

    runs = []
    try:
        for req, rng in zip(reqs, rngs):
            runs.append(eng.start(req))
            assert runs[-1].tokens == [want(req, rng, eng.last_prefill_logits)]
        for step in range(10):
            # the last steps leave the second sampled row out
            live = runs if step < 6 else runs[:2]
            count0, sum0 = _fetch_hist("tgen_ids")
            eng.decode_step(live)
            fetched = 3 * 4 + (len(live) - 1) * vocab * 4
            assert eng.stats()["decode_fetch_bytes"] == fetched
            count1, sum1 = _fetch_hist("tgen_ids")
            assert (count1 - count0, sum1 - sum0) == (1, fetched)
            rows = eng.last_logits
            for run, req, rng in list(zip(runs, reqs, rngs))[:len(live)]:
                assert run.tokens[-1] == want(req, rng, rows[run.slot]), step
    finally:
        for run in runs:
            eng.finish(run)
    assert len({tuple(r.tokens) for r in runs}) == 3


def test_last_logits_is_read_on_demand_from_the_step_s_own_output(ids_engine):
    eng = ids_engine
    run = eng.start(GenRequest([5, 3, 2], max_new_tokens=4, eos_id=NO_EOS))
    try:
        assert eng._last_prefill_logits._host is None
        first = eng.last_prefill_logits
        np.testing.assert_array_equal(
            first, np.asarray(eng._last_prefill_logits.dev)[0])
        eng.decode_step([run])
        held = eng._last_logits
        assert held._host is None  # nothing copied until someone asks
        eager = np.asarray(held.dev)
        rows = eng.last_logits
        assert rows.shape == (3, IDS_KW["vocab_size"])
        assert rows.dtype == np.float32
        np.testing.assert_array_equal(rows, eager)
        assert eng.last_logits is rows  # the second read copies nothing
        assert eng.last_prefill_logits.base is first.base
        eng.decode_step([run])
        assert eng._last_logits is not held  # the next step's own
    finally:
        eng.finish(run)


def test_the_decode_variant_without_the_ids_is_another_cache_key(
        tmp_path, monkeypatch):
    """A cache directory filled by an engine whose variants fetch no ids
    (the key and the module of every engine before the ids) misses: the
    variants are traced again, never replayed, and then hit their own."""
    from paddle_tpu.serving import generation

    cache = str(tmp_path / "gen_cache")

    def make():
        return GenerationEngine(
            GPTDecoder(**IDS_KW), name="tgen_key", max_slots=3, page_size=4,
            max_context=48, prefill_buckets=(4,), cache_dir=cache)

    with monkeypatch.context() as m:
        m.setattr(generation, "_emit_ids", lambda main, fetches: list(fetches))
        old = make()
        old._variant("decode")
        old._variant("prefill:4")
        assert (old.traces, old.cache_hits) == (2, 0)
        old_keys = set(os.listdir(cache))

    def boot():
        eng = make()
        eng.warmup()
        return eng

    new = boot()
    assert (new.traces, new.cache_hits) == (2, 0)
    assert old_keys < set(os.listdir(cache))
    warm = boot()
    assert (warm.traces, warm.cache_hits) == (0, 2)
    assert warm.generate([1, 2, 3], max_new_tokens=3).tokens == new.generate(
        [1, 2, 3], max_new_tokens=3).tokens


def test_warmup_leaves_nothing_to_compile_for_a_sampled_row():
    import jax.monitoring

    eng = _ids_engine("tgen_warm")
    compiles = []
    listening = [True]

    def listen(event, duration, **_):
        if listening[0] and event == "/jax/core/compile/backend_compile_duration":
            compiles.append(event)

    jax.monitoring.register_event_duration_secs_listener(listen)
    traces = eng.traces
    runs = [
        eng.start(GenRequest([3, 1, 4], max_new_tokens=4, eos_id=NO_EOS)),
        eng.start(GenRequest([2, 7], max_new_tokens=4, eos_id=NO_EOS,
                             temperature=0.9, top_k=3, seed=1)),
    ]
    try:
        eng.decode_step(runs)
        eng.decode_step(runs[1:])
    finally:
        listening[0] = False
        for run in runs:
            eng.finish(run)
    assert compiles == [] and eng.traces == traces


# ---------------------------------------------------------------------------
# snapshots of a per-slot sequence state: a reserve in bytes, and when one is
# taken (a model with a conv or recurrent state: test_block_decoder's tiny
# lfm2_moe, whose slot row is 384 B)
# ---------------------------------------------------------------------------


def _state_engine(**kw):
    from paddle_tpu.models.block_decoder import lfm2_moe
    from test_block_decoder import TINY

    model = lfm2_moe(TINY, max_context=64, dtype="float32")
    kw = dict(dict(max_slots=3, page_size=4, prefill_chunk=8, cache_dir=None), **kw)
    _state_engine.made += 1  # a name of its own: the registry's counters are by name
    return GenerationEngine(
        model, name="tstate%d" % _state_engine.made, scope=Scope(seed=3), **kw)


_state_engine.made = 0


def _tokens(n, seed):
    return [int(v) for v in np.random.default_rng(seed).integers(2, 97, n)]


def test_snapshots_are_evicted_by_bytes_under_the_reserve():
    """The reserve is device bytes, whatever a state is wide: by default
    four snapshots a slot; with `snapshot_bytes` as many as fit, the coldest
    going first; stats() and the gauge report held and reserved bytes."""
    eng = _state_engine()
    row = eng.state_row_bytes
    assert row == 3 * 2 * 32 * 4  # three conv layers' two rows of 32 float32
    assert eng.prefix_cache.stats()["state_bytes_reserved"] == 4 * 3 * row
    eng = _state_engine(snapshot_bytes=3 * row + row // 2)
    pc = eng.prefix_cache
    heads = [_tokens(8, 50 + i) for i in range(5)]
    for i, head in enumerate(heads):  # each head twice: the second snapshots it
        for tail in (1, 2):
            eng.finish(eng.start(GenRequest(
                head + _tokens(7, 70 + 2 * i + tail), max_new_tokens=1)))
    st = pc.stats()
    assert (st["states_taken"], st["states_evicted"], st["cached_states"]) == (5, 2, 3)
    assert st["state_bytes"] == 3 * row <= st["state_bytes_reserved"] == 3 * row + row // 2
    assert eng._m_snap_bytes.value(what="held") == 3 * row
    assert eng._m_snap_bytes.value(what="reserved") == 3 * row + row // 2
    assert eng._m_snap_copy_bytes.value(event="taken") == 5 * row
    # the coldest went: the first two heads match pages and restore nothing,
    # the last three restore
    for i, head in enumerate(heads):
        pages, state, matched = pc.lookup_state(head + _tokens(5, 90 + i))
        pc.pool.unpin_pages(pages)
        assert matched == 2 and (state is not None) == (i >= 2), i
    # a reserve smaller than one snapshot holds none; the pages stay shared
    eng = _state_engine(snapshot_bytes=row - 1)
    for tail in (1, 2, 3):
        eng.finish(eng.start(GenRequest(heads[0] + _tokens(7, tail), max_new_tokens=1)))
    st = eng.prefix_cache.stats()
    assert st["cached_states"] == 0 and st["states_taken"] == st["states_evicted"] == 2
    assert st["states_hit"] == 0 and st["cached_pages"] >= 2


def test_prompts_of_one_shared_head_leave_the_one_snapshot_the_policy_says():
    """Six prompts of one shared 12-token head and unique tails (up to five
    chunks of 8): a snapshot is taken only at a boundary another prompt was
    seen to share. The first prompt leaves none, the second one at the
    head's end, and every later one restores it and copies nothing more:
    not one at each of the tails' page-aligned chunk ends."""
    eng = _state_engine()
    row = eng.state_row_bytes
    head = _tokens(12, 5)
    taken, hit = [], []
    for i, n in enumerate((9, 30, 17, 26, 5, 38)):
        eng.finish(eng.start(GenRequest(head + _tokens(n, 10 + i), max_new_tokens=2)))
        st = eng.prefix_cache.stats()
        taken.append(st["states_taken"])
        hit.append(st["states_hit"])
    assert taken == [0, 1, 1, 1, 1, 1] and hit == [0, 0, 1, 2, 3, 4]
    assert st["cached_states"] == 1 and st["state_bytes"] == row
    assert eng._m_snap_copy_bytes.value(event="taken") == row
    assert eng._m_snap_copy_bytes.value(event="restored") == 4 * row
    # two of them share 20 tokens more: the next of those leaves one there
    deeper = head + _tokens(30, 11)[:20]
    eng.finish(eng.start(GenRequest(deeper + _tokens(6, 30), max_new_tokens=2)))
    assert eng.prefix_cache.stats()["states_taken"] == 2
    pages, state, matched = eng.prefix_cache.lookup_state(deeper + _tokens(3, 31))
    eng.prefix_cache.pool.unpin_pages(pages)
    assert (len(pages), matched) == (8, 8) and state is not None


def _program_digest(model):
    """sha256 over every op (type, attrs, input and output names by slot) of
    the decode, a prefill and the forward program, and their startups."""
    import hashlib

    h = hashlib.sha256()
    builds = (model.build_decode(3, 4, 16, 68, state_rows=4),
              model.build_prefill(8, 4, 16, 68, state_rows=4),
              model.build_forward(2, 8))
    for main, startup, feeds, fetches in builds:
        for prog in (main, startup):
            for op in prog.global_block().ops:
                attrs = {k: (v.tolist() if isinstance(v, np.ndarray) else v)
                         for k, v in op.attrs.items()}
                h.update(json.dumps(
                    [op.type, sorted(attrs.items(), key=lambda kv: kv[0]),
                     sorted(op.inputs.items()), sorted(op.outputs.items())],
                    sort_keys=True, default=str).encode())
        h.update(json.dumps([feeds, fetches]).encode())
    return h.hexdigest()


@pytest.mark.parametrize("which", ["lfm2_moe", "xing4_0"])
def test_block_decoders_built_before_the_state_space_operator_keep_their_programs(which):
    """What BlockDecoder gained for granitemoehybrid (the mamba operator, the
    position / q-k norm / score scale choices, the three multipliers, the
    convolution's second form) defaults to what lfm2_moe and xing4_0 built
    before: their programs' op lists, attrs and variable names, and the
    spec the compile cache folds in, are PR 32's (digests taken there with
    this function). A PR that changes them on purpose takes them anew."""
    from paddle_tpu.models.block_decoder import lfm2_moe, xing4_0
    from test_block_decoder import TINY, XING

    model, digest = {
        "lfm2_moe": (lambda: lfm2_moe(TINY, max_context=64, dtype="float32"),
                     "359183df59cf841822f1e10a0dd7c7d5e73d3264e786d207d05e20c5733e7583"),
        "xing4_0": (lambda: xing4_0(XING, max_context=64, dtype="float32",
                                    experts_held=None),
                    "71074496b20431f033e3f0162ad7d862333d6b922bbdbd8ba729cf3b5eab39b3"),
    }[which]
    model = model()
    assert _program_digest(model) == digest
    assert set(model.spec()) == {
        "blocks", "dtype", "n_head", "n_kv_head", "d_head", "d_model", "d_ffn",
        "conv_taps", "moe", "mla", "hyper", "tied_head"}
