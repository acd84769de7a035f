"""Inference-engine tests: paged cache, decode parity, continuous
batching invariants, sampling independence, compile-cache counters."""

import numpy as np
import pytest


# --------------------------------------------------------------- fixtures
@pytest.fixture(scope="module")
def tiny_f32():
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.gpt import GPTConfig, init_params
    cfg = GPTConfig.tiny(dtype=jnp.float32)
    params = init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


@pytest.fixture(scope="module")
def tiny_bf16():
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.gpt import GPTConfig, init_params
    cfg = GPTConfig.tiny(dtype=jnp.bfloat16)
    params = init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


# AOT executables depend on (cfg, geometry) only — share them across
# the many tiny engines below so each test doesn't re-pay the compile
_EXEC_CACHE = {}


def _make_engine(cfg, params, **kw):
    from ray_tpu.inference import InferenceEngine
    kw.setdefault("slots", 2)
    kw.setdefault("page_size", 16)
    kw.setdefault("buckets", (16, 32, 64))
    kw.setdefault("telemetry", False)
    kw.setdefault("executable_cache", _EXEC_CACHE)
    return InferenceEngine(cfg, params, **kw)


def _prompt(n, vocab, seed=0):
    return list(np.random.RandomState(seed).randint(0, vocab, size=n))


def _teacher_forced_rows(cfg, params, prompt, generated):
    """One full-context ``forward`` over the engine's own trajectory:
    row i is the teacher-forced distribution the i-th generated token
    was (supposedly) sampled from.  A single compile, versus one per
    growing length for the naive step-by-step reference."""
    import jax.numpy as jnp

    from ray_tpu.models.gpt import forward
    full = list(prompt) + list(generated[:-1])
    logits, _ = forward(params, jnp.array(full, jnp.int32)[None], cfg)
    lo = len(prompt) - 1
    return np.asarray(logits[0, lo:lo + len(generated)])


# ---------------------------------------------------------- page allocator
def test_page_allocator_invariants():
    from ray_tpu.inference import PageAllocator
    alloc = PageAllocator(8)            # pages 1..7 usable
    assert alloc.free_count == 7
    a = alloc.alloc(3)
    b = alloc.alloc(4)
    assert alloc.free_count == 0 and 0 not in a + b
    assert alloc.alloc(1) is None       # exhausted -> None, not raise
    alloc.free(a)
    assert alloc.free_count == 3
    with pytest.raises(ValueError):
        alloc.free(a)                   # double free
    with pytest.raises(ValueError):
        alloc.free([0])                 # the reserved garbage page
    alloc.free(b)
    assert alloc.free_count == 7


# ------------------------------------------------------------ decode parity
def test_decode_matches_forward_fp32(tiny_f32):
    cfg, params = tiny_f32
    engine = _make_engine(cfg, params, debug_logits=True)
    prompt = _prompt(9, cfg.vocab_size)
    rid = engine.submit(prompt, max_new_tokens=6)
    got_tokens = []
    while engine.has_work():
        for r, tok, _ in engine.step():
            got_tokens.append(tok)
    got_logits = engine.logits_trace[rid]
    ref = _teacher_forced_rows(cfg, params, prompt, got_tokens)
    # cached decode logits match teacher-forced forward step-by-step,
    # and the greedy tokens are the argmax of the reference rows (so
    # the trajectory itself is the teacher-forced one, not just
    # self-consistent)
    assert got_tokens == list(ref.argmax(-1))
    np.testing.assert_allclose(np.stack(got_logits), ref, rtol=2e-4,
                               atol=2e-4)


@pytest.mark.slow   # >5s: pays the bf16 engine compiles (fp32 parity
                    # covers this path in tier-1)
def test_decode_matches_forward_bf16(tiny_bf16):
    cfg, params = tiny_bf16
    engine = _make_engine(cfg, params, debug_logits=True)
    prompt = _prompt(13, cfg.vocab_size, seed=3)
    rid = engine.submit(prompt, max_new_tokens=4)
    while engine.has_work():
        engine.step()
    got = engine.logits_trace[rid]
    # teacher-forced reference along the engine's own trajectory
    # (greedy ties can legitimately flip under bf16, so compare logits,
    # not tokens)
    req = engine._requests[rid]
    ref = _teacher_forced_rows(cfg, params, prompt, req.generated)
    np.testing.assert_allclose(np.stack(got), ref, rtol=0.1, atol=0.15)


def test_ragged_join_leave_matches_solo(tiny_f32):
    """Continuous batching must be invisible: sequences joining and
    leaving mid-stream produce the same tokens as solo runs, and their
    cached-decode logits still match teacher-forced ``forward``."""
    cfg, params = tiny_f32
    p1 = _prompt(7, cfg.vocab_size, seed=1)
    p2 = _prompt(11, cfg.vocab_size, seed=2)
    solo1 = _make_engine(cfg, params).generate([p1], max_new_tokens=8)[0]
    solo2 = _make_engine(cfg, params).generate([p2], max_new_tokens=5)[0]

    engine = _make_engine(cfg, params, debug_logits=True)
    r1 = engine.submit(p1, max_new_tokens=8)
    out = {r1: []}
    for _ in range(3):                       # r1 decodes alone a while
        for r, tok, _ in engine.step():
            out[r].append(tok)
    r2 = engine.submit(p2, max_new_tokens=5)  # joins mid-stream
    out[r2] = []
    while engine.has_work():
        for r, tok, _ in engine.step():
            out[r].append(tok)
    assert out[r1] == solo1
    assert out[r2] == solo2
    # logits parity holds through the join (r1's later rows were
    # computed co-batched with r2) and past r2's retirement
    for rid, prompt in ((r1, p1), (r2, p2)):
        ref = _teacher_forced_rows(cfg, params, prompt, out[rid])
        np.testing.assert_allclose(np.stack(engine.logits_trace[rid]),
                                   ref, rtol=2e-4, atol=2e-4)


def test_int8_kv_cache_parity_and_bytes(tiny_f32):
    """r11 int8 KV cache: ~2x+ lower ``KVCache.bytes`` at fixed pages
    (codes + scale arrays vs f32 here — 3.2x; vs a bf16 cache the same
    geometry gives 1.88x), step-by-step decode-logits parity against
    the model-dtype cache within the int8 budget, and the
    zero-steady-state-recompile counters still hold with the doubled
    state tuple."""
    cfg, params = tiny_f32
    base = _make_engine(cfg, params, debug_logits=True)
    q8 = _make_engine(cfg, params, debug_logits=True, kv_dtype="int8",
                      executable_cache={})
    # fixed pages, same geometry: the footprint claim (f32 model dtype:
    # 2*D*4 bytes -> D + 4 per vector)
    assert base.cache.bytes / q8.cache.bytes > 2.0
    assert q8.stats()["kv_dtype"] == "int8"
    assert (q8.stats()["kv_bytes_per_slot"]
            < base.stats()["kv_bytes_per_slot"] / 2)

    prompt = _prompt(9, cfg.vocab_size, seed=11)
    outs = {}
    for eng in (base, q8):
        rid = eng.submit(prompt, max_new_tokens=6)
        toks = []
        while eng.has_work():
            for _r, tok, _d in eng.step():
                toks.append(tok)
        outs[eng] = (rid, toks)
    # per-step logits within the documented budget: K/V codes carry
    # <= amax/254 per-element error -> O(1%) decode-logits drift on
    # the tiny model (measured 0.006 at logit scale 0.5)
    l_base = np.stack(base.logits_trace[outs[base][0]])
    l_q8 = np.stack(q8.logits_trace[outs[q8][0]])
    np.testing.assert_allclose(l_q8, l_base, rtol=0.05, atol=0.05)
    # greedy trajectories agree on the tiny model (not guaranteed at
    # scale — the logits assertion above is the real contract)
    assert outs[q8][1] == outs[base][1]
    assert q8.stats()["compiles"] == {"prefill": 1,
                                      "prefill_cached": 0,
                                      "decode": 1}

    # ragged co-batching stays invisible under quantization too
    p2 = _prompt(14, cfg.vocab_size, seed=12)
    solo = _make_engine(cfg, params, kv_dtype="int8",
                        executable_cache={}).generate(
        [p2], max_new_tokens=4)[0]
    both = _make_engine(cfg, params, kv_dtype="int8",
                        executable_cache={}).generate(
        [prompt, p2], max_new_tokens=4)
    assert both[1] == solo


def test_kv_dtype_env_knob(tiny_f32, monkeypatch):
    """RAY_TPU_KV_DTYPE resolves through infer_config; unknown values
    fall back loudly to the model dtype."""
    from ray_tpu.inference.config import infer_config
    cfg, params = tiny_f32
    monkeypatch.setenv("RAY_TPU_KV_DTYPE", "int8")
    infer_config(refresh=True)
    try:
        eng = _make_engine(cfg, params, executable_cache={})
        assert eng.kv_dtype == "int8" and eng.cache.quantized
        monkeypatch.setenv("RAY_TPU_KV_DTYPE", "fp4")
        assert infer_config(refresh=True).kv_dtype == "model"
    finally:
        monkeypatch.delenv("RAY_TPU_KV_DTYPE")
        infer_config(refresh=True)


# ---------------------------------------------------------- prefix cache
def test_page_allocator_refcount_and_eviction():
    """r12 refcounted allocator: shared pages free only at refcount 0,
    registered refcount-0 pages park in an LRU idle pool, and alloc
    evicts idle pages LRU-first (unregistering them) before failing."""
    from ray_tpu.inference import PageAllocator, PrefixIndex
    idx = PrefixIndex()
    alloc = PageAllocator(6, index=idx)        # pages 1..5 usable
    a = alloc.alloc(2)
    h = PrefixIndex.chain(PrefixIndex.ROOT, [1, 2, 3])
    assert idx.register(h, a[0])
    # shared reference: releasing one of two refs keeps the page live
    alloc.acquire(a[0])
    assert alloc.refcount(a[0]) == 2
    alloc.release([a[0]])
    assert alloc.refcount(a[0]) == 1 and alloc.free_count == 3
    # refcount 0: registered page idles (still a lookup hit),
    # unregistered page goes back to the free list
    alloc.release(a)
    assert alloc.refcount(a[0]) == 0
    assert alloc.idle_count == 1 and alloc.free_count == 5
    assert idx.lookup(h) == a[0]
    # a hit revives the idle page
    alloc.acquire(a[0])
    assert alloc.idle_count == 0 and alloc.refcount(a[0]) == 1
    alloc.release([a[0]])
    # exhausting the free list evicts the idle page and forgets it
    b = alloc.alloc(5)
    assert b is not None and len(set(b)) == 5
    assert alloc.evictions == 1 and idx.lookup(h) is None
    assert alloc.alloc(1) is None              # truly exhausted
    with pytest.raises(ValueError):
        alloc.acquire(0)                       # the garbage page
    alloc.release(b)
    with pytest.raises(ValueError):
        alloc.release([b[0]])                  # double free stays O(1)


def test_scheduler_refcount_fuzz():
    """Fuzz admit/hit/retire/evict interleavings at the scheduler
    level (no compiled steps — register_prefix is called as the engine
    would, after 'prefill'): no page freed while referenced, refcounts
    exactly match the active references, every page always in exactly
    one of {free, idle, allocated}, and nothing leaks at drain."""
    import collections

    from ray_tpu.inference import (Request, SamplingParams,
                                   SlotScheduler)
    rng = np.random.RandomState(42)
    ps = 8
    sched = SlotScheduler(slots=3, page_size=ps, num_pages=24,
                          max_pages_per_slot=8, prefix=True)
    alloc = sched.allocator
    # a small pool of shared prefixes drives real hit/shared-page load
    prefixes = [list(rng.randint(0, 97, 2 * ps)) for _ in range(3)]
    rid = 0
    for step in range(300):
        op = rng.rand()
        if op < 0.5 and len(sched.waiting) < 4:
            prompt = list(prefixes[rng.randint(3)]) if rng.rand() < 0.7 \
                else list(rng.randint(0, 97, 2 * ps))
            prompt = prompt + list(
                rng.randint(0, 97, int(rng.randint(1, 2 * ps))))
            sched.submit(Request(rid=rid, prompt=prompt,
                                 max_new_tokens=int(rng.randint(1, 8)),
                                 sampling=SamplingParams()))
            rid += 1
        elif op < 0.8:
            req = sched.try_admit()
            if req is not None:
                sched.register_prefix(req)     # "prefill finished"
        elif sched.active:
            slot = list(sched.active)[rng.randint(len(sched.active))]
            sched.retire(slot)
        # --- invariants, every step ---
        expected = collections.Counter()
        for req in sched.active.values():
            for p in req.pages:
                expected[p] += 1
        # refcounts exactly track active references...
        assert dict(expected) == {p: c for p, c in
                                  alloc._refcount.items()}, step
        # ...no referenced page is free/idle, and the three pools
        # partition the usable pages
        free = alloc._free_set
        idle = set(alloc._idle)
        held = set(alloc._refcount)
        assert len(alloc._free) == len(free)
        assert not (free & idle) and not (free & held) \
            and not (idle & held)
        assert free | idle | held == set(range(1, 24))
        # idle pages are exactly the registered refcount-0 pages
        for p in idle:
            assert sched.prefix_index.has(p)
    while sched.active:
        sched.retire(next(iter(sched.active)))
    assert not alloc._refcount
    assert alloc.free_count == 23              # nothing leaked
    assert alloc.evictions > 0      # pressure reached the idle pool


def test_prefix_hit_decode_parity(tiny_f32):
    """The tentpole contract: a prefix-hit request (suffix-only
    prefill over shared cached pages) produces the same trajectory and
    step-by-step decode logits as the identical request running cold —
    including a prompt whose length is an exact page multiple (the
    final prompt token must still prefill)."""
    cfg, params = tiny_f32
    for plen, seed in ((37, 21), (48, 22)):    # 48 = 3 full pages
        engine = _make_engine(cfg, params, debug_logits=True)
        prompt = _prompt(plen, cfg.vocab_size, seed=seed)
        r_cold = engine.submit(prompt, max_new_tokens=5)
        while engine.has_work():
            engine.step()
        r_hit = engine.submit(prompt, max_new_tokens=5)
        while engine.has_work():
            engine.step()
        st = engine.stats()
        # the hit skipped every full page strictly before the last
        # prompt token, at zero prefill compute
        assert st["prefix"]["hit_tokens"] == 16 * ((plen - 1) // 16)
        assert st["prefix"]["requests_hit"] == 1
        assert engine._requests[r_hit].generated == \
            engine._requests[r_cold].generated
        np.testing.assert_allclose(
            np.stack(engine.logits_trace[r_hit]),
            np.stack(engine.logits_trace[r_cold]),
            rtol=2e-4, atol=2e-4)


def test_prefix_hit_decode_parity_int8(tiny_f32):
    """Prefix hits under ``kv_dtype="int8"``: deterministic rounding
    makes shared pages bit-identical, so a hit request's logits stay
    within the int8 budget of its own cold run (the cached prefix is
    read back quantized where the cold prefill read full precision)."""
    cfg, params = tiny_f32
    engine = _make_engine(cfg, params, debug_logits=True,
                          kv_dtype="int8")
    prompt = _prompt(37, cfg.vocab_size, seed=23)
    r_cold = engine.submit(prompt, max_new_tokens=5)
    while engine.has_work():
        engine.step()
    r_hit = engine.submit(prompt, max_new_tokens=5)
    while engine.has_work():
        engine.step()
    assert engine.stats()["prefix"]["hit_tokens"] == 32
    np.testing.assert_allclose(
        np.stack(engine.logits_trace[r_hit]),
        np.stack(engine.logits_trace[r_cold]),
        rtol=0.05, atol=0.05)


def test_prefix_mixed_traffic_zero_recompiles(tiny_f32):
    """Mixed hit/miss traffic: varying cached lengths ride ONE cached-
    prefill executable per suffix bucket (cached_len is a traced
    scalar), so the compile counters stay flat — and a hit request
    co-batched with strangers still matches its solo cold run."""
    cfg, params = tiny_f32
    engine = _make_engine(cfg, params, executable_cache={})
    shared = _prompt(32, cfg.vocab_size, seed=31)       # 2 full pages
    mkreq = lambda n, s: shared + _prompt(n, cfg.vocab_size, seed=s)
    solo = _make_engine(cfg, params).generate(
        [mkreq(7, 33)], max_new_tokens=4)[0]
    out = {}
    # cold registrant, then hits with different suffix lengths, plus a
    # no-share stranger co-batched between them
    rids = [engine.submit(mkreq(5, 32), max_new_tokens=4),
            engine.submit(mkreq(7, 33), max_new_tokens=4),
            engine.submit(_prompt(40, cfg.vocab_size, seed=34),
                          max_new_tokens=4),   # same 64 bucket, no share
            engine.submit(mkreq(12, 35), max_new_tokens=4)]
    for r in rids:
        out[r] = []
    while engine.has_work():
        for r, tok, _d in engine.step():
            out[r].append(tok)
    st = engine.stats()
    assert st["compiles"] == {"prefill": 1, "prefill_cached": 1,
                              "decode": 1}
    assert st["prefix"]["requests_hit"] == 2
    assert st["prefix"]["hit_tokens"] == 2 * 32
    assert out[rids[1]] == solo


def test_prefix_shared_pages_refcounted_concurrently(tiny_f32):
    """Two live requests sharing prefix pages: the shared pages carry
    refcount 2 while both decode, survive the first retire, and only
    return to the idle pool after the second — then a third request
    revives them from idle."""
    cfg, params = tiny_f32
    engine = _make_engine(cfg, params)
    sched = engine.scheduler
    free0 = sched.allocator.free_count
    shared = _prompt(32, cfg.vocab_size, seed=41)
    r1 = engine.submit(shared + _prompt(3, cfg.vocab_size, seed=42),
                       max_new_tokens=8)
    engine.step()        # r1 prefilled + registered
    r2 = engine.submit(shared + _prompt(5, cfg.vocab_size, seed=43),
                       max_new_tokens=3)
    engine.step()        # r2 admitted as a hit, both now active
    reqs = {r.rid: r for r in sched.active.values()}
    shared_pages = reqs[r1].pages[:2]
    assert reqs[r2].pages[:2] == shared_pages      # same storage
    assert reqs[r2].cached_tokens == 32
    for p in shared_pages:
        assert sched.allocator.refcount(p) == 2
    while engine.has_work():
        engine.step()    # r2 retires first (max_new 3), then r1
    assert sched.allocator.free_count == free0     # idle counts as free
    assert sched.allocator.idle_count > 0
    r3 = engine.submit(shared + _prompt(4, cfg.vocab_size, seed=44),
                       max_new_tokens=3)
    engine.step()
    (req3,) = sched.active.values()
    assert req3.rid == r3 and req3.cached_tokens == 32
    while engine.has_work():
        engine.step()
    assert sched.allocator.free_count == free0
    assert engine.stats()["prefix"]["requests_hit"] == 2


def test_prefix_disabled_knob(tiny_f32):
    """prefix=False (RAY_TPU_INFER_PREFIX=0): identical prompts never
    share — no index, no hits, no cached-prefill compiles."""
    cfg, params = tiny_f32
    engine = _make_engine(cfg, params, prefix=False,
                          executable_cache={})
    prompt = _prompt(37, cfg.vocab_size, seed=51)
    engine.generate([prompt], max_new_tokens=2)
    engine.generate([prompt], max_new_tokens=2)
    st = engine.stats()
    assert st["prefix"] == {
        "enabled": False, "hit_pages": 0, "hit_tokens": 0,
        "requests_hit": 0, "registered_pages": 0, "idle_pages": 0,
        "evictions": 0}
    assert st["compiles"]["prefill_cached"] == 0
    assert st["hits"]["prefill"] == 1          # second run = pure hit


# ----------------------------------------------------------- load shedding
def test_max_queue_load_shedding(tiny_f32):
    """RAY_TPU_INFER_MAX_QUEUE: over-cap submits raise the typed
    QueueFullError instead of queueing unboundedly, and draining the
    queue re-opens admission."""
    from ray_tpu.inference import QueueFullError
    cfg, params = tiny_f32
    engine = _make_engine(cfg, params, slots=1, max_queue=2)
    engine.submit(_prompt(5, cfg.vocab_size), max_new_tokens=2)
    engine.submit(_prompt(6, cfg.vocab_size), max_new_tokens=2)
    assert engine.stats()["waiting"] == 2      # head admits at step()
    with pytest.raises(QueueFullError, match="MAX_QUEUE"):
        engine.submit(_prompt(7, cfg.vocab_size), max_new_tokens=2)
    assert len(engine._requests) == 2          # rejected leaves no trace
    engine.step()                              # head takes the slot
    assert engine.stats()["waiting"] == 1      # cap re-opens
    engine.submit(_prompt(8, cfg.vocab_size), max_new_tokens=2)
    while engine.has_work():
        engine.step()
    assert not engine._requests


def test_gpt_deployment_queue_full_is_stream_error(tiny_f32):
    """The serve deployment surfaces the typed rejection as the
    stream's error (consumer sees QueueFullError at first iteration),
    not a silently parked request."""
    import asyncio

    import jax.numpy as jnp

    from ray_tpu.inference import QueueFullError
    from ray_tpu.inference.serve_gpt import GPTDeployment

    dep = GPTDeployment.func_or_class(
        model="tiny", model_config={"dtype": jnp.float32},
        engine_config={"slots": 1, "page_size": 16, "buckets": (32,),
                       "max_queue": 1, "telemetry": False,
                       "executable_cache": _EXEC_CACHE})
    dep.engine.submit([1, 2, 3], max_new_tokens=4)   # fills the queue

    async def run():
        agen = dep({"tokens": [7, 8, 9], "max_new_tokens": 4})
        return [tok async for tok in agen]

    with pytest.raises(QueueFullError):
        asyncio.run(asyncio.wait_for(run(), timeout=30))
    assert not dep._queues


# --------------------------------------------------------------- batching
def test_scheduler_no_slot_or_page_leaks(tiny_f32):
    """Fuzz admissions/retirements through the real engine: tight page
    pool forces queueing; afterwards every slot and page is free."""
    cfg, params = tiny_f32
    # 2 slots, 5 usable pages of 16 -> at most ~2 small requests resident
    engine = _make_engine(cfg, params, num_pages=6)
    free_pages0 = engine.scheduler.allocator.free_count
    rng = np.random.RandomState(7)
    rids, max_new = [], {}
    for i in range(12):
        n = int(rng.randint(1, 30))
        mn = int(rng.randint(1, 5))
        rid = engine.submit(_prompt(n, cfg.vocab_size, seed=i),
                            max_new_tokens=mn)
        rids.append(rid)
        max_new[rid] = mn
    counts = {r: 0 for r in rids}
    done = set()
    while engine.has_work():
        sched = engine.scheduler
        in_use = sum(len(r.pages) for r in sched.active.values())
        assert in_use + sched.allocator.free_count == free_pages0
        for r, _tok, fin in engine.step():
            counts[r] += 1
            if fin:
                done.add(r)
    assert done == set(rids)
    assert engine.scheduler.allocator.free_count == free_pages0
    assert sorted(engine.scheduler.free_slots) == [0, 1]
    assert not engine.scheduler.active and not engine.scheduler.waiting
    assert not engine._requests      # finished requests are pruned
    for r in rids:
        assert 1 <= counts[r] <= max_new[r]


def test_zero_steady_state_recompiles(tiny_f32):
    """Varying request lengths within one bucket: exactly one prefill
    compile (the bucket) and one decode compile ever; everything else
    is a compile-cache hit."""
    cfg, params = tiny_f32
    # private executable cache: this test is *about* the counters
    engine = _make_engine(cfg, params, buckets=(64,),
                          executable_cache={})
    for i, n in enumerate((5, 20, 33, 48)):
        engine.submit(_prompt(n, cfg.vocab_size, seed=i),
                      max_new_tokens=4)
    while engine.has_work():
        engine.step()
    stats = engine.stats()
    assert stats["compiles"] == {"prefill": 1, "prefill_cached": 0,
                                 "decode": 1}
    assert stats["hits"]["prefill"] == 3
    assert stats["hits"]["decode"] > 0


def test_cancel_frees_slot_and_stops_tokens(tiny_f32):
    """cancel() retires an active sequence at the next tick (freeing
    its slot and pages) without touching co-batched neighbors, and
    drops a still-waiting request before it ever runs."""
    cfg, params = tiny_f32
    engine = _make_engine(cfg, params)
    free0 = engine.scheduler.allocator.free_count
    p2 = _prompt(6, cfg.vocab_size, seed=1)
    r1 = engine.submit(_prompt(5, cfg.vocab_size), max_new_tokens=50)
    r2 = engine.submit(p2, max_new_tokens=6)
    r3 = engine.submit(_prompt(4, cfg.vocab_size, seed=2),
                       max_new_tokens=3)     # waits: both slots taken
    out = {r1: [], r2: [], r3: []}
    for _ in range(2):
        for r, tok, _d in engine.step():
            out[r].append(tok)
    n1 = len(out[r1])
    assert 0 < n1 < 50                # mid-stream, not finished
    engine.cancel(r1)
    engine.cancel(r3)
    while engine.has_work():
        for r, tok, _d in engine.step():
            out[r].append(tok)
    assert len(out[r1]) == n1         # nothing after the cancel tick
    assert out[r3] == []              # cancelled while waiting
    assert engine.scheduler.allocator.free_count == free0
    assert not engine.scheduler.active and not engine.scheduler.waiting
    assert not engine._requests
    # the surviving neighbor is byte-identical to a solo run
    solo2 = _make_engine(cfg, params).generate([p2],
                                               max_new_tokens=6)[0]
    assert out[r2] == solo2


def test_eos_retires_early(tiny_f32):
    cfg, params = tiny_f32
    engine = _make_engine(cfg, params, debug_logits=True)
    prompt = _prompt(6, cfg.vocab_size)
    # find the greedy first token, then rerun with it as the EOS token
    probe = _make_engine(cfg, params)
    first = probe.generate([prompt], max_new_tokens=1)[0][0]
    rid = engine.submit(prompt, max_new_tokens=10, eos_token=first)
    events = []
    while engine.has_work():
        events.extend(engine.step())
    assert events == [(rid, first, True)]
    assert engine.scheduler.allocator.free_count == \
        probe.scheduler.allocator.free_count


# --------------------------------------------------------------- logprobs
def test_logprobs_match_teacher_forced(tiny_f32):
    """r14 satellite: the sampler's chosen-token logprobs — threaded
    through step events and ``generate(return_logprobs=True)`` — match
    a ``log_softmax`` teacher-forced ``forward`` recompute step by
    step, for greedy AND temperature sampling (the logprob is always
    the model distribution's, independent of sampling shaping)."""
    import jax

    from ray_tpu.inference import SamplingParams
    cfg, params = tiny_f32
    for sp in (None, SamplingParams(temperature=0.9, top_k=50,
                                    seed=7)):
        engine = _make_engine(cfg, params)
        prompt = _prompt(11, cfg.vocab_size, seed=61)
        (toks,), (lps,) = engine.generate([prompt], max_new_tokens=6,
                                          sampling=sp,
                                          return_logprobs=True)
        ref_rows = _teacher_forced_rows(cfg, params, prompt, toks)
        ref_lp = jax.nn.log_softmax(ref_rows, axis=-1)
        want = [float(ref_lp[i, t]) for i, t in enumerate(toks)]
        np.testing.assert_allclose(lps, want, rtol=2e-4, atol=2e-4)
        # logprobs ride the events too (the serve stream's source)
        engine2 = _make_engine(cfg, params)
        engine2.submit(prompt, max_new_tokens=6, sampling=sp)
        ev_lps = []
        while engine2.has_work():
            for ev in engine2.step():
                assert ev == (ev[0], ev[1], ev[2])   # 3-tuple compat
                ev_lps.append(ev.logprob)
        np.testing.assert_allclose(ev_lps, want, rtol=2e-4, atol=2e-4)


def test_gpt_deployment_streams_logprobs(tiny_f32):
    """The serve deployment's ``"logprobs": True`` option: stream
    items become {token, logprob} dicts whose logprobs match the
    offline engine's (drives the class directly — no serve runtime)."""
    import asyncio

    import jax.numpy as jnp

    from ray_tpu.inference.serve_gpt import GPTDeployment
    cfg, params = tiny_f32
    dep = GPTDeployment.func_or_class(
        model="tiny", model_config={"dtype": jnp.float32},
        engine_config={"slots": 2, "page_size": 16, "buckets": (32,),
                       "telemetry": False,
                       "executable_cache": _EXEC_CACHE})
    prompt = [3, 1, 4, 1, 5]

    async def run():
        agen = dep({"tokens": prompt, "max_new_tokens": 4,
                    "logprobs": True})
        return [item async for item in agen]

    items = asyncio.run(asyncio.wait_for(run(), timeout=60))
    assert all(set(i) == {"token", "logprob"} for i in items)
    want_toks, want_lps = _make_engine(cfg, params).generate(
        [prompt], max_new_tokens=4, return_logprobs=True)
    assert [i["token"] for i in items] == want_toks[0]
    np.testing.assert_allclose([i["logprob"] for i in items],
                               want_lps[0], rtol=1e-6)


# --------------------------------------------------------------- sampling
def test_sampling_modes():
    import jax.numpy as jnp

    from ray_tpu.inference.sampling import sample_tokens
    rng = np.random.RandomState(0)
    logits = jnp.array(rng.randn(4, 64), jnp.float32)
    seeds = jnp.arange(4, dtype=jnp.int32)
    counts = jnp.zeros(4, jnp.int32)
    zeros = jnp.zeros(4, jnp.float32)
    ones = jnp.ones(4, jnp.float32)
    ik = jnp.zeros(4, jnp.int32)
    # greedy == argmax
    greedy = np.asarray(sample_tokens(logits, seeds, counts, zeros, ik,
                                      ones))
    assert (greedy == np.asarray(logits).argmax(-1)).all()
    # top_k=1 forces the argmax even at high temperature
    topk1 = np.asarray(sample_tokens(logits, seeds, counts, 5 * ones,
                                     jnp.ones(4, jnp.int32), ones))
    assert (topk1 == greedy).all()
    # same (seed, count) reproduces; different count varies
    a = np.asarray(sample_tokens(logits, seeds, counts, ones, ik, ones))
    b = np.asarray(sample_tokens(logits, seeds, counts, ones, ik, ones))
    assert (a == b).all()
    c = np.asarray(sample_tokens(logits, seeds, counts + 1, ones, ik,
                                 ones))
    assert (a != c).any()
    # tiny top_p collapses to the mode
    tp = np.asarray(sample_tokens(logits, seeds, counts, ones, ik,
                                  1e-6 * ones))
    assert (tp == greedy).all()


def _reference_sample_one(logits, seed, count, temp, top_k, top_p):
    """The sampler as it stood before it chose its work by what the
    call's rows ask for: one body for every row, both sorts always
    (``top_k == 0`` and ``top_p >= 1`` read as "off").  Kept here as the
    plain reference the three bodies are held to."""
    import jax
    import jax.numpy as jnp
    V = logits.shape[-1]
    l = logits.astype(jnp.float32)
    greedy = jnp.argmax(l, -1).astype(jnp.int32)
    model_logp = jax.nn.log_softmax(l)
    z = l / jnp.maximum(temp, 1e-6)
    kth = jnp.sort(z)[::-1][jnp.clip(top_k - 1, 0, V - 1)]
    z = jnp.where((top_k > 0) & (z < kth), -jnp.inf, z)
    probs = jax.nn.softmax(z)
    sp = jnp.sort(probs)[::-1]
    cum = jnp.cumsum(sp)
    keep = (cum - sp) < top_p
    thresh = jnp.min(jnp.where(keep, sp, jnp.inf))
    z = jnp.where((top_p >= 1.0) | (probs >= thresh), z, -jnp.inf)
    key = jax.random.fold_in(jax.random.PRNGKey(seed), count)
    g = -jnp.log(-jnp.log(
        jax.random.uniform(key, (V,), minval=1e-20, maxval=1.0)))
    sampled = jnp.argmax(z + g, -1).astype(jnp.int32)
    tok = jnp.where(temp <= 0.0, greedy, sampled)
    return tok, model_logp[tok]


# per-row (temperature, top_k, top_p) of each case, and the body the
# call has to select; the mixed batch ends in an inactive row, which
# carries the null (greedy) parameters as the engine gives them
_SAMPLER_CASES = {
    "all_greedy": ([(0.0, 0, 1.0)] * 5, "plain"),
    "all_temperature_only": ([(0.7, 0, 1.0), (1.0, 0, 1.0),
                              (1.3, 0, 1.0), (0.2, 0, 1.0),
                              (2.0, 0, 1.0)], "draw"),
    "all_top_k": ([(0.8, 1, 1.0), (1.0, 5, 1.0), (1.0, 20, 1.0),
                   (1.5, 63, 1.0), (0.5, 500, 1.0)], "filter"),
    "all_top_p": ([(0.8, 0, 0.9), (1.0, 0, 0.5), (1.0, 0, 1e-6),
                   (1.5, 0, 0.99), (0.5, 0, 0.3)], "filter"),
    "mixed": ([(0.0, 0, 1.0), (0.9, 0, 1.0), (1.0, 8, 1.0),
               (1.1, 0, 0.8), (0.0, 0, 1.0)], "filter"),
}


@pytest.mark.parametrize("case", sorted(_SAMPLER_CASES))
def test_sampler_bodies_match_the_one_body_reference(case):
    """Whichever body a call selects, every row gets the token and the
    logprob the old one-body sampler gives that row."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.inference.sampling import (sample_path, sample_tokens,
                                            sample_tokens_logprobs)
    rows, path = _SAMPLER_CASES[case]
    temps = jnp.array([r[0] for r in rows], jnp.float32)
    top_ks = jnp.array([r[1] for r in rows], jnp.int32)
    top_ps = jnp.array([r[2] for r in rows], jnp.float32)
    assert sample_path(temps, top_ks, top_ps) == path
    reference = jax.jit(jax.vmap(_reference_sample_one))
    n = len(rows)
    for trial, count0 in enumerate((0, 1, 17, 400)):
        logits = 3.0 * jnp.array(
            np.random.RandomState(trial).randn(n, 64), jnp.float32)
        seeds = jnp.arange(n, dtype=jnp.int32) * 7919 + trial
        counts = count0 + jnp.arange(n, dtype=jnp.int32)
        args = (logits, seeds, counts, temps, top_ks, top_ps)
        want_tok, want_lp = reference(*args)
        tok, lp = sample_tokens_logprobs(*args)
        np.testing.assert_array_equal(np.asarray(tok),
                                      np.asarray(want_tok))
        np.testing.assert_allclose(np.asarray(lp), np.asarray(want_lp),
                                   rtol=0, atol=1e-6)
        np.testing.assert_array_equal(np.asarray(sample_tokens(*args)),
                                      np.asarray(want_tok))


def test_sample_path_counter(tiny_f32, monkeypatch):
    """``infer/sample`` names the body its call selected and
    ``InferTelemetry`` counts calls by it: all ``plain`` for default
    ``SamplingParams()``, ``draw`` / ``filter`` for the other two."""
    from ray_tpu.inference import SamplingParams
    from ray_tpu.inference import engine as engine_mod
    from ray_tpu.util import tracing
    cfg, params = tiny_f32
    prompt = _prompt(7, cfg.vocab_size, seed=2)
    for sp, path in ((SamplingParams(), "plain"),
                     (SamplingParams(temperature=1.0, seed=3), "draw"),
                     (SamplingParams(temperature=1.0, top_p=0.9, seed=3),
                      "filter")):
        engine = _make_engine(cfg, params, telemetry=True)
        tracing.clear_recorded()
        tracing.enable_tracing()
        try:
            engine.generate([prompt], max_new_tokens=3, sampling=sp)
        finally:
            tracing.disable_tracing()
        spans = [r for r in tracing.recorded_spans()
                 if r["name"] == "infer/sample"]
        assert len(spans) == 3
        assert {r["attributes"]["path"] for r in spans} == {path}
        assert engine.telemetry.summary()["sample"] == {
            "calls": 3, "path_share": {path: 1.0}}
    # a greedy and a top-p request in one decode batch: the prefills
    # (one row each) take their own row's path, the shared ticks filter
    engine = _make_engine(cfg, params, telemetry=True)
    engine.submit(prompt, 3, SamplingParams())
    engine.submit(prompt, 3, SamplingParams(temperature=1.0, top_p=0.9))
    while engine.has_work():
        engine.step()
    assert engine.telemetry.sample_paths == {"plain": 1, "filter": 3}
    # nobody to keep it, nothing computed: no counter, no trace
    engine = _make_engine(cfg, params, telemetry=False)
    seen = []
    monkeypatch.setattr(engine_mod, "sample_path",
                        lambda *a: seen.append(a) or "plain")
    engine.generate([prompt], max_new_tokens=2)
    assert seen == [] and engine.telemetry.sample_paths == {}
    tracing.enable_tracing()
    try:
        engine.generate([prompt], max_new_tokens=2)
    finally:
        tracing.disable_tracing()
    assert len(seen) == 2 and engine.telemetry.sample_paths == {}


_COBATCH_KINDS = {
    "greedy": dict(),
    "temperature_only": dict(temperature=0.8, seed=123),
    "top_k": dict(temperature=0.8, top_k=20, seed=123),
    "top_p": dict(temperature=0.8, top_p=0.7, seed=123),
}


@pytest.fixture(scope="module")
def cobatched(tiny_f32):
    """One engine run with a request of every kind in its decode batch
    (so every tick runs the ``filter`` body): kind -> (tokens,
    logprobs)."""
    from ray_tpu.inference import SamplingParams
    cfg, params = tiny_f32
    engine = _make_engine(cfg, params, slots=4)
    rids = {engine.submit(_prompt(8 + i, cfg.vocab_size, seed=4 + i), 6,
                          SamplingParams(**kw)): kind
            for i, (kind, kw) in enumerate(_COBATCH_KINDS.items())}
    out = {kind: ([], []) for kind in _COBATCH_KINDS}
    while engine.has_work():
        for ev in engine.step():
            rid, tok, _done = ev
            out[rids[rid]][0].append(tok)
            out[rids[rid]][1].append(ev.logprob)
    return out


@pytest.mark.parametrize("kind", list(_COBATCH_KINDS))
def test_sampled_sequence_independent_of_cobatch(tiny_f32, cobatched,
                                                 kind):
    """Per-sequence PRNG, and one function of a row's own parameters in
    all three sampler bodies: a greedy, a temperature-only, a top-k and
    a top-p request each produce alone (the ``plain``, ``draw``,
    ``filter``, ``filter`` body) the tokens they produce co-batched
    with the others (the ``filter`` body for all)."""
    from ray_tpu.inference import SamplingParams
    cfg, params = tiny_f32
    i = list(_COBATCH_KINDS).index(kind)
    sp = SamplingParams(**_COBATCH_KINDS[kind])
    p1 = _prompt(8 + i, cfg.vocab_size, seed=4 + i)
    solo, solo_lp = _make_engine(cfg, params).generate(
        [p1], max_new_tokens=6, sampling=sp, return_logprobs=True)
    assert cobatched[kind][0] == solo[0]
    np.testing.assert_allclose(cobatched[kind][1], solo_lp[0],
                               rtol=1e-5, atol=1e-6)
    # and beside a stranger under the same parameters, as before
    p2 = _prompt(15, cfg.vocab_size, seed=5)
    both = _make_engine(cfg, params).generate([p1, p2],
                                              max_new_tokens=6,
                                              sampling=sp)
    assert both[0] == solo[0]


# ------------------------------------------------------- config / telemetry
def test_infer_config_env_knobs(monkeypatch):
    from ray_tpu.inference.config import infer_config
    monkeypatch.setenv("RAY_TPU_INFER_SLOTS", "3")
    monkeypatch.setenv("RAY_TPU_INFER_PAGE_SIZE", "32")
    monkeypatch.setenv("RAY_TPU_INFER_PAGES", "11")
    monkeypatch.setenv("RAY_TPU_INFER_BUCKETS", "64,256,128")
    cfg = infer_config(refresh=True)
    assert (cfg.slots, cfg.page_size, cfg.pages) == (3, 32, 11)
    assert cfg.buckets == (64, 128, 256)
    # r12 knobs: prefix cache + load-shedding queue cap
    assert infer_config().prefix and infer_config().max_queue == 0
    monkeypatch.setenv("RAY_TPU_INFER_PREFIX", "0")
    monkeypatch.setenv("RAY_TPU_INFER_MAX_QUEUE", "7")
    cfg = infer_config(refresh=True)
    assert not cfg.prefix and cfg.max_queue == 7
    monkeypatch.setenv("RAY_TPU_INFER_MAX_QUEUE", "-3")
    assert infer_config(refresh=True).max_queue == 0   # loud fallback
    monkeypatch.delenv("RAY_TPU_INFER_SLOTS")
    monkeypatch.delenv("RAY_TPU_INFER_PAGE_SIZE")
    monkeypatch.delenv("RAY_TPU_INFER_PAGES")
    monkeypatch.delenv("RAY_TPU_INFER_BUCKETS")
    monkeypatch.delenv("RAY_TPU_INFER_PREFIX")
    monkeypatch.delenv("RAY_TPU_INFER_MAX_QUEUE")
    infer_config(refresh=True)


def test_infer_telemetry_summary(tiny_f32):
    cfg, params = tiny_f32
    engine = _make_engine(cfg, params, telemetry=True)
    engine.generate([_prompt(5, cfg.vocab_size)], max_new_tokens=3)
    out = engine.telemetry.summary()
    assert out["enabled"] and out["requests_done"] == 1
    assert out["prefills"] == 1 and out["decode_steps"] == 2
    assert out["ttft_s"] > 0 and out["decode_step_s"] > 0
    assert out["decode_tokens_per_sec"] > 0
    # r12: prefix-hit accounting, TTFT split and queue-wait series
    assert out["prompt_tokens"] == 5
    assert out["prefill_tokens_skipped"] == 0
    assert out["prefix_hit_rate"] == 0.0
    assert out["ttft_mean_s"] > 0
    assert out["ttft_prefix_miss_s"] > 0 and "ttft_prefix_hit_s" not in out
    assert out["queue_wait_s"] >= 0
    # a second identical request: skipped tokens and the hit-side TTFT
    # series appear (prompt has no full page at len 5 -> use a long one)
    long = _prompt(37, cfg.vocab_size, seed=9)
    engine.generate([long], max_new_tokens=2)
    engine.generate([long], max_new_tokens=2)
    out = engine.telemetry.summary()
    assert out["prefill_tokens_skipped"] == 32
    assert out["ttft_prefix_hit_s"] > 0
    # r11: the true cache footprint rides the summary block
    assert out["kv_dtype"] == "model"
    assert out["kv_bytes_per_slot"] > 0
    assert out["kv_cache_bytes"] == engine.cache.bytes
    # disabled recorder is a no-op block
    off = _make_engine(cfg, params, telemetry=False)
    off.generate([_prompt(5, cfg.vocab_size)], max_new_tokens=2)
    assert off.telemetry.summary() == {"enabled": False}


def test_submit_validation(tiny_f32):
    cfg, params = tiny_f32
    engine = _make_engine(cfg, params)
    with pytest.raises(ValueError):
        engine.submit([], max_new_tokens=2)
    with pytest.raises(ValueError):
        engine.submit([1], max_new_tokens=0)
    with pytest.raises(ValueError):          # beyond max_seq
        engine.submit(_prompt(100, cfg.vocab_size),
                      max_new_tokens=100)
    with pytest.raises(ValueError):          # beyond largest bucket
        engine.submit(_prompt(65, cfg.vocab_size), max_new_tokens=2)
    # needs more pages than the whole pool owns: must raise at submit,
    # not queue forever (FIFO admission would spin on it)
    tight = _make_engine(cfg, params, num_pages=3)   # pool = 2 pages
    with pytest.raises(ValueError, match="pool"):
        tight.submit(_prompt(20, cfg.vocab_size), max_new_tokens=20)
    assert not tight._requests       # rejected submits leave no trace


def test_layer_apply_cache_rejects_fused_rope(tiny_f32):
    """The cache hook's contract is post-RoPE keys; a fused-RoPE
    attn_fn would receive (and cache) un-rotated ones — must fail
    loudly, not decode garbage."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import gpt as G
    cfg, params = tiny_f32
    lp = jax.tree.map(lambda a: a[0], params["layers"])
    x = jnp.zeros((1, 4, cfg.d_model), cfg.dtype)

    def attn(q, k, v, **kw):
        return q

    attn.fused_rope = True
    assert cfg.pos == "rope"
    with pytest.raises(ValueError, match="fused RoPE"):
        G.layer_apply(lp, x, cfg, positions=jnp.arange(4),
                      attn_fn=attn, cache=(None, None))


def test_engine_rejects_zero_slots(tiny_f32, monkeypatch):
    """RAY_TPU_INFER_SLOTS=0 must fail at construction, not hang every
    generate() in a no-admission busy loop."""
    from ray_tpu.inference.config import infer_config
    cfg, params = tiny_f32
    monkeypatch.setenv("RAY_TPU_INFER_SLOTS", "0")
    infer_config(refresh=True)
    try:
        with pytest.raises(ValueError, match="decode slot"):
            _make_engine(cfg, params, slots=None)
    finally:
        monkeypatch.delenv("RAY_TPU_INFER_SLOTS")
        infer_config(refresh=True)


# ------------------------------------------------------------------ serve
def test_gpt_deployment_pump_failure_propagates(tiny_f32):
    """A step failure inside the replica's pump task must surface to
    every streaming consumer, not leave them awaiting a queue forever
    (drives the underlying class directly — no serve runtime)."""
    import asyncio

    import jax.numpy as jnp

    from ray_tpu.inference.serve_gpt import GPTDeployment

    dep = GPTDeployment.func_or_class(
        model="tiny", model_config={"dtype": jnp.float32},
        engine_config={"slots": 2, "page_size": 16, "buckets": (32,),
                       "telemetry": False,
                       "executable_cache": _EXEC_CACHE})

    def boom():
        raise RuntimeError("step exploded")
    dep.engine.step = boom

    async def run():
        agen = dep({"tokens": [1, 2, 3], "max_new_tokens": 4})
        return [tok async for tok in agen]

    with pytest.raises(RuntimeError, match="step exploded"):
        asyncio.run(asyncio.wait_for(run(), timeout=30))
    assert not dep._queues            # consumer cleaned up its queue


def test_gpt_deployment_abandoned_stream_cancels(tiny_f32):
    """A consumer that stops iterating (client disconnect) must not
    leave its sequence decoding to max_new_tokens in a slot nobody
    reads: the generator's cleanup cancels it and the engine frees the
    slot within a tick."""
    import asyncio

    import jax.numpy as jnp

    from ray_tpu.inference.serve_gpt import GPTDeployment

    dep = GPTDeployment.func_or_class(
        model="tiny", model_config={"dtype": jnp.float32},
        engine_config={"slots": 2, "page_size": 16, "buckets": (32,),
                       "telemetry": False,
                       "executable_cache": _EXEC_CACHE})

    async def run():
        agen = dep({"tokens": [1, 2, 3], "max_new_tokens": 60})
        async for _tok in agen:
            break                     # consumer walks away
        await agen.aclose()           # triggers the finally -> cancel
        await dep._pump_task          # pump drains the cancel and exits

    asyncio.run(asyncio.wait_for(run(), timeout=60))
    assert not dep.engine.scheduler.active
    assert not dep.engine.scheduler.waiting
    assert not dep.engine._requests
    # far fewer decode ticks than the 59 an unread request would burn
    assert dep.engine.hit_counts["decode"] \
        + dep.engine.compile_counts["decode"] <= 3


@pytest.mark.slow   # replica subprocess pays its own engine compiles
def test_gpt_deployment_streams_tokens(ray_start_regular):
    import jax
    import jax.numpy as jnp

    import ray_tpu.serve as serve
    from ray_tpu.inference import InferenceEngine
    from ray_tpu.inference.serve_gpt import GPTDeployment
    from ray_tpu.models.gpt import GPTConfig, init_params

    app = GPTDeployment.bind(
        model="tiny", model_config={"dtype": jnp.float32},
        engine_config={"slots": 2, "page_size": 16,
                       "buckets": (32,), "telemetry": False})
    handle = serve.run(app, name="gpt")
    prompt = _prompt(6, 512)
    stream = handle.options(stream=True).remote(
        {"tokens": prompt, "max_new_tokens": 5})
    got = list(stream)
    # the replica runs the same preset/seed: offline engine must agree
    cfg = GPTConfig.tiny(dtype=jnp.float32)
    params = init_params(cfg, jax.random.PRNGKey(0))
    want = _make_engine(cfg, params, buckets=(32,)).generate(
        [prompt], max_new_tokens=5)[0]
    assert got == want
    serve.delete("gpt")


# ------------------------------------------------- deadlines & resilience
def test_ttft_deadline_expires_waiting_request(tiny_f32):
    """A request still waiting past its TTFT deadline is shed: typed
    terminal error event, nothing ever held (r15 — over-deadline work
    is shed, not queued)."""
    import time

    from ray_tpu.inference import DeadlineExceededError
    cfg, params = tiny_f32
    engine = _make_engine(cfg, params, slots=1, telemetry=True)
    p = _prompt(8, cfg.vocab_size)
    r1 = engine.submit(p, max_new_tokens=4)
    r2 = engine.submit(p, max_new_tokens=4, ttft_deadline_s=1e-4)
    time.sleep(0.005)                   # r2 is queued behind r1's slot
    errs, toks = {}, {r1: 0, r2: 0}
    while engine.has_work():
        for ev in engine.step():
            if ev.error is not None:
                errs[ev[0]] = ev
            else:
                toks[ev[0]] += 1
    assert toks[r1] == 4 and toks[r2] == 0
    ev = errs[r2]
    assert ev == (r2, -1, True)          # 3-tuple-compatible terminal
    assert isinstance(ev.error, DeadlineExceededError)
    assert ev.error.kind == "ttft" and ev.error.rid == r2
    # the error rides serve streams across the object store: pickling
    # must rebuild it from its constructor args (not the message)
    import pickle
    back = pickle.loads(pickle.dumps(ev.error))
    assert (back.rid, back.kind) == (r2, "ttft")
    assert str(back) == str(ev.error)
    assert engine.deadline_exceeded == 1
    assert engine.stats()["deadline_exceeded"] == 1
    assert engine.telemetry.summary()["deadline_exceeded"] == \
        {"ttft": 1}
    assert not engine._requests          # expired requests are pruned


def test_total_deadline_retires_mid_decode_and_releases_all(tiny_f32):
    """Total-deadline expiry mid-decode retires the sequence with its
    slot, pages and prefix refcounts released — the allocator
    partition is exact afterwards."""
    import time

    from ray_tpu.inference import DeadlineExceededError
    cfg, params = tiny_f32
    engine = _make_engine(cfg, params, prefix=True)
    alloc = engine.scheduler.allocator
    free0 = alloc.free_count
    rid = engine.submit(_prompt(8, cfg.vocab_size), max_new_tokens=20,
                        deadline_s=0.05)
    got, err = 0, None
    engine.step()                        # prefill tick: first token
    got += 1
    time.sleep(0.06)                     # blow the budget mid-decode
    while engine.has_work():
        for ev in engine.step():
            if ev.error is not None:
                err = ev.error
            else:
                got += 1
    assert isinstance(err, DeadlineExceededError)
    assert err.kind == "total" and 1 <= got < 20
    assert len(engine.scheduler.free_slots) == engine.slots
    assert alloc.free_count == free0
    # generate() surfaces the typed error instead of hanging (1ns
    # budget: the first tick's sweep always sees it expired)
    with pytest.raises(DeadlineExceededError):
        engine.generate([_prompt(8, cfg.vocab_size, seed=1)],
                        max_new_tokens=4, deadline_s=1e-9)


def test_cancel_before_prefill_releases_prefix_refcounts(tiny_f32):
    """r15 satellite regression: cancelling a request that was
    admitted with prefix-cache hits but NOT yet prefilled must release
    the refcounts admission acquired — the free/idle/held partition
    stays exact and no page keeps a stray reference."""
    cfg, params = tiny_f32
    engine = _make_engine(cfg, params, slots=4, page_size=8,
                          buckets=(32,), prefix=True)
    alloc = engine.scheduler.allocator
    pp = _prompt(17, cfg.vocab_size, seed=3)   # 2 full pages + tail
    engine.generate([pp], max_new_tokens=2)    # registers the 2 pages
    base_idle, base_free = alloc.idle_count, alloc.free_count
    assert base_idle == 2
    rid = engine.submit(pp, max_new_tokens=2)
    # drive admission by hand: the request now holds 2 prefix-hit
    # refcounts + fresh pages, but its prefill has not run
    req = engine.scheduler.try_admit()
    assert req is not None and req.rid == rid and req.n_hit_pages == 2
    assert alloc.refcount(req.pages[0]) == 1   # revived idle hit
    engine.cancel(rid)
    engine.step()                              # cancel processed first
    assert not engine.has_work()
    assert alloc.idle_count == base_idle
    assert alloc.free_count == base_free
    assert len(engine.scheduler.free_slots) == 4
    for page in range(1, alloc.num_pages):
        assert alloc.refcount(page) == 0
    # the shared pages survived the cancel: a fresh request still hits
    rid2 = engine.submit(pp, max_new_tokens=2)
    engine.step()
    assert engine.scheduler.prefix_requests_hit >= 2
    while engine.has_work():
        engine.step()


def test_decode_fault_leaves_engine_drainable(tiny_f32):
    """An injected ``infer.decode`` fault fires before the donated
    executable dispatches: the engine state stays consistent, cancels
    drain it clean (the supervisor's actor-replacement contract)."""
    from ray_tpu.util import chaos
    cfg, params = tiny_f32
    engine = _make_engine(cfg, params)
    alloc = engine.scheduler.allocator
    free0 = alloc.free_count
    chaos.install_faults("infer.decode@1")
    try:
        rid = engine.submit(_prompt(8, cfg.vocab_size),
                            max_new_tokens=4)
        with pytest.raises(chaos.InjectedFault):
            while engine.has_work():
                engine.step()
        engine.cancel(rid)
        engine.step()                   # fault fired once; tick works
        assert not engine.has_work()
        assert alloc.free_count + alloc.idle_count == free0
        assert len(engine.scheduler.free_slots) == engine.slots
    finally:
        chaos.clear_faults()


def test_gpt_deployment_deadline_is_stream_error(tiny_f32):
    """The serve deployment surfaces a deadline expiry as the typed
    stream error (the client's shed-load signal), and the payload's
    deadline keys reach the engine."""
    import asyncio

    import jax.numpy as jnp

    from ray_tpu.inference import DeadlineExceededError
    from ray_tpu.inference.serve_gpt import GPTDeployment

    dep = GPTDeployment.func_or_class(
        model="tiny", model_config={"dtype": jnp.float32},
        engine_config={"slots": 1, "page_size": 16, "buckets": (32,),
                       "telemetry": False,
                       "executable_cache": _EXEC_CACHE})
    # slot 1 is busy; the deadlined request queues behind it and blows
    # its TTFT budget on the first pump tick
    dep.engine.submit(_prompt(6, 512), max_new_tokens=8)

    async def run():
        agen = dep({"tokens": _prompt(6, 512, seed=2),
                    "max_new_tokens": 4, "ttft_deadline_s": 1e-4})
        await asyncio.sleep(0.01)
        return [tok async for tok in agen]

    with pytest.raises(DeadlineExceededError):
        asyncio.run(asyncio.wait_for(run(), timeout=60))
    assert not dep._queues
    assert dep.engine.deadline_exceeded == 1


def test_gpt_deployment_graceful_drain(tiny_f32):
    """``drain()``: admission stops with a typed error, in-flight
    streams finish, the engine ends idle (r15 — a scale-down or
    preemption notice costs zero dropped streams)."""
    import asyncio

    import jax.numpy as jnp

    from ray_tpu.inference.serve_gpt import (GPTDeployment,
                                             ReplicaDrainingError)

    dep = GPTDeployment.func_or_class(
        model="tiny", model_config={"dtype": jnp.float32},
        engine_config={"slots": 2, "page_size": 16, "buckets": (32,),
                       "telemetry": False,
                       "executable_cache": _EXEC_CACHE})

    async def run():
        agen = dep({"tokens": _prompt(6, 512), "max_new_tokens": 6})
        first = await agen.__anext__()          # stream is in flight
        drain_task = asyncio.create_task(dep.drain())
        await asyncio.sleep(0.01)
        # draining: new admissions are rejected with the typed error
        with pytest.raises(ReplicaDrainingError):
            async for _ in dep({"tokens": [1, 2], "max_new_tokens": 2}):
                pass
        # ... but the in-flight stream runs to completion
        rest = [tok async for tok in agen]
        report = await drain_task
        return first, rest, report

    first, rest, report = asyncio.run(
        asyncio.wait_for(run(), timeout=60))
    assert len([first] + rest) == 6
    assert report["drained"] is True
    assert report["active"] == 0 and report["waiting"] == 0
    assert report["free_slots"] == 2
    assert not dep.engine.has_work()
    assert dep.telemetry_summary()["draining"] is True


@pytest.mark.slow   # the healthy-path drain test stays tier-1; this
                    # variant re-pays a deployment engine build
def test_gpt_deployment_drain_survives_dead_pump(tiny_f32):
    """r15 review hardening: ``drain()`` must not hang when the pump
    died with work still in the engine (nothing will ever tick it
    again) — it retires the leftovers host-side and reports idle."""
    import asyncio

    import jax.numpy as jnp

    from ray_tpu.inference.serve_gpt import GPTDeployment
    from ray_tpu.util import chaos

    dep = GPTDeployment.func_or_class(
        model="tiny", model_config={"dtype": jnp.float32},
        engine_config={"slots": 2, "page_size": 16, "buckets": (32,),
                       "telemetry": False,
                       "executable_cache": _EXEC_CACHE})
    chaos.install_faults("infer.decode@1")
    try:
        async def run():
            agen = dep({"tokens": _prompt(6, 512),
                        "max_new_tokens": 6})
            with pytest.raises(chaos.InjectedFault):
                async for _ in agen:
                    pass                 # pump dies on the decode tick
            return await asyncio.wait_for(dep.drain(), timeout=30)

        report = asyncio.run(asyncio.wait_for(run(), timeout=60))
    finally:
        chaos.clear_faults()
    assert report["drained"] is True
    assert report["active"] == 0 and report["waiting"] == 0
    assert not dep.engine.has_work()
    assert dep.engine.scheduler.allocator.free_count == \
        dep.engine.scheduler.allocator.num_pages - 1


def test_gpt_deployment_drain_timeout_on_wedged_pump(tiny_f32):
    """r15 review hardening: ``drain(timeout_s=...)`` must not hang on
    a pump that is alive but never finishing (a wedged step) — it
    reports ``drained: False`` without touching engine state, so the
    preemption handler can escalate."""
    import asyncio

    import jax.numpy as jnp

    from ray_tpu.inference.serve_gpt import GPTDeployment

    dep = GPTDeployment.func_or_class(
        model="tiny", model_config={"dtype": jnp.float32},
        engine_config={"slots": 2, "page_size": 16, "buckets": (32,),
                       "telemetry": False,
                       "executable_cache": _EXEC_CACHE})

    async def run():
        dep.engine.submit(_prompt(6, 512), max_new_tokens=4)
        # a "pump" that never finishes stands in for a wedged step
        dep._pump_task = asyncio.get_running_loop().create_task(
            asyncio.sleep(60))
        report = await asyncio.wait_for(
            dep.drain(poll_s=0.01, timeout_s=0.1), timeout=10)
        dep._pump_task.cancel()
        return report

    report = asyncio.run(asyncio.wait_for(run(), timeout=60))
    assert report["drained"] is False
    assert "wedged" in report["reason"]
    assert report["active"] + report["waiting"] == 1  # state untouched
    dep.engine.drain_requests()            # test cleanup
    assert not dep.engine.has_work()


# ------------------------------------------ no layer's pool is materialised
def _step_executable(engine, kind):
    """``(jitted step, example args)`` of one of the engine's four
    serve executables at the engine's own geometry."""
    import jax.numpy as jnp
    i32 = jnp.int32
    mp = engine.max_pages_per_slot
    head = (engine.params,) + tuple(engine.cache.state)
    fn = engine._build_step(kind)
    if kind == "decode":
        return fn, head + (
            jnp.zeros((engine.slots,), i32),
            jnp.zeros((engine.slots,), i32),
            jnp.zeros((engine.slots, mp), i32))
    if kind == "prefill":
        return fn, head + (
            jnp.zeros((1, 32), i32), jnp.int32(20), jnp.zeros((mp,), i32))
    return fn, head + (
        jnp.zeros((1, 16), i32), jnp.int32(32), jnp.int32(5),
        jnp.zeros((mp,), i32))


@pytest.mark.parametrize("kv_dtype", ["model", "int8"])
@pytest.mark.parametrize("kind", ["decode", "prefill", "prefill_cached"])
def test_step_never_materialises_a_layers_pool(tiny_f32, kind, kv_dtype):
    """The structure that keeps the per-layer pool copy from coming
    back: in every serve executable the stacked cache arrays are
    donated in and come out; nothing slices a layer's
    ``[pages, page, H(, D)]`` pool out of them or updates it back in
    (StableHLO, as lowered); and the CPU's optimised module holds no
    ``copy`` of a whole or per-layer cache array — the writes are
    in-place scatters of the touched pages on the scan's carry."""
    import re
    cfg, params = tiny_f32
    engine = _make_engine(cfg, params, kv_dtype=kv_dtype,
                          executable_cache={})
    fn, args = _step_executable(engine, kind)
    lowered = fn.lower(*args)
    text = lowered.as_text()
    state = engine.cache.state

    def dims(shape):
        return "x".join(map(str, shape))

    # every cache array: a donated input that aliases an output
    (sig,) = [ln for ln in text.splitlines()
              if "func.func public @main" in ln]
    donated = re.findall(r"tensor<([0-9x]+)x\w+> \{tf\.aliasing_output",
                         sig)
    assert sorted(donated) == sorted(dims(a.shape) for a in state)
    results = sig.split("->", 1)[1]
    for a in state:
        assert f"tensor<{dims(a.shape)}x" in results

    # no dynamic_slice / dynamic_update_slice touches a per-layer pool
    stacked = {dims(a.shape) for a in state}
    per_layer = {dims(a.shape[1:]) for a in state}
    for ln in text.splitlines():
        if "dynamic_slice" not in ln and "dynamic_update_slice" not in ln:
            continue
        for shape in re.findall(r"tensor<([0-9x]+)x\w+>", ln):
            assert shape not in stacked, ln
            assert shape not in per_layer, ln
            assert shape not in {"1x" + s for s in per_layer}, ln

    # and the optimised module copies none of them
    hlo = lowered.compile().as_text()
    big = {str(list(s)).replace(" ", "")
           for a in state
           for s in (a.shape, a.shape[1:], (1,) + a.shape[1:])}
    for ln in hlo.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = \w+(\[[0-9,]*\])\S* "
                     r"copy\(", ln)
        assert not (m and m.group(1) in big), ln


# ------------------------------------------------- cache contents, by hand
def _reference_kv(cfg, params, tokens):
    """Post-RoPE K and V of every layer for one sequence, ``[L, T, H,
    D]`` each, from a plain layer-by-layer forward (no cache, no
    engine): what the engine must have put at the sequence's
    ``[layer, page, offset]``."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import gpt as G
    from ray_tpu.parallel.ring_attention import local_attention
    T = len(tokens)
    x = params["embed"].astype(cfg.dtype)[jnp.array(tokens)][None]
    ks, vs = [], []

    def attn(q, k, v, cache):
        ks.append(np.asarray(k[0]))
        vs.append(np.asarray(v[0]))
        return local_attention(q, k, v, causal=True), cache

    for i in range(cfg.n_layers):
        lp = jax.tree.map(lambda a: a[i], params["layers"])
        x, _aux, _ = G.layer_apply(lp, x, cfg, positions=jnp.arange(T),
                                   attn_fn=attn, cache=())
    return np.stack(ks), np.stack(vs)


def _quantize_rows(x):
    """The plain writer's int8 rows: per-vector symmetric codes,
    ``scale = amax / 127`` over the last axis, rounded to nearest."""
    x = np.asarray(x, np.float32)
    scale = (np.abs(x).max(axis=-1) / 127.0).astype(np.float32)
    safe = np.where(scale == 0.0, 1.0, scale)
    codes = np.rint(x / safe[..., None]).clip(-127, 127)
    return codes.astype(np.int8), scale


@pytest.mark.parametrize("lora", [False, True], ids=["base", "lora"])
@pytest.mark.parametrize("kv_dtype", ["model", "int8"])
def test_cache_contents_match_plain_writer(tiny_f32, kv_dtype, lora):
    """Every cache array, element for element, against a numpy writer
    that places each cached token's post-RoPE K and V (int8: codes and
    scales) at ``[layer, page, ..., position % page_size]`` (the pool's
    page offset is its minor dimension) — after a cold
    prefill, a prefix hit with a cached-suffix prefill and decode ticks
    beside an inactive slot; pages no request owns stay untouched; and
    the emitted tokens are the teacher-forced forward's."""
    import jax

    from ray_tpu.inference import SamplingParams
    from ray_tpu.inference.kv_cache import GARBAGE_PAGE
    cfg, params = tiny_f32
    kw, model_id, ref_params = {}, None, params
    if lora:
        from ray_tpu.adapters import (LoraConfig, init_adapter,
                                      merge_adapter)
        lcfg = LoraConfig(enabled=True, rank=4, scale=0.5, cache_slots=3)
        adapter = init_adapter(cfg, lcfg, jax.random.PRNGKey(11),
                               random_b=True)
        kw, model_id = {"lora": lcfg}, "t1"
        ref_params = merge_adapter(params, adapter, cfg, scale=0.5)
    engine = _make_engine(cfg, params, slots=4, kv_dtype=kv_dtype,
                          executable_cache={}, **kw)
    if lora:
        engine.load_adapter("t1", adapter, scale=0.5)
    greedy = SamplingParams(temperature=0.0, model_id=model_id)
    ps = engine.page_size

    # the script: A prefills cold; B shares A's first two pages (a
    # prefix hit, so only its suffix prefills); C decodes beside
    # them; the fourth slot stays inactive throughout
    prompt_a = _prompt(37, cfg.vocab_size, seed=31)
    prompt_b = prompt_a[:32] + _prompt(9, cfg.vocab_size, seed=32)
    prompt_c = _prompt(6, cfg.vocab_size, seed=33) * 3
    rids = [engine.submit(prompt_a, max_new_tokens=24, sampling=greedy)]
    engine.step()
    rids.append(engine.submit(prompt_b, max_new_tokens=24,
                              sampling=greedy))
    rids.append(engine.submit(prompt_c, max_new_tokens=24,
                              sampling=greedy))
    for _ in range(6):
        engine.step()
    engine._level()     # the decode in flight has written its rows
    reqs = [engine._requests[r] for r in rids]
    assert not any(r.done for r in reqs)
    st = engine.stats()
    assert st["prefix"]["hit_tokens"] == 32
    assert st["compiles"]["prefill_cached"] == 1
    assert st["free_slots"] == 1                            # an idle slot

    names = ("k", "v", "k_scale", "v_scale")[:len(engine.cache.state)]
    got = dict(zip(names, map(np.asarray, engine.cache.state)))
    want = {n: np.zeros_like(a) for n, a in got.items()}
    live = np.zeros((got["k"].shape[1], ps), bool)  # [page, offset]
    owned = {GARBAGE_PAGE}
    for req in reqs:
        tokens = list(req.prompt) + list(req.generated[:-1])
        assert engine.scheduler.lengths[req.slot] == len(tokens)
        owned.update(req.pages)
        # the emitted tokens are the teacher-forced ones
        rows = _teacher_forced_rows(cfg, ref_params, req.prompt,
                                    req.generated)
        picked = rows[np.arange(len(req.generated)), req.generated]
        if kv_dtype == "model":
            assert list(req.generated) == list(rows.argmax(-1))
        else:       # int8 context: within its budget of the best logit
            assert (picked >= rows.max(-1) - 0.05).all()
        k, v = _reference_kv(cfg, ref_params, tokens)
        for t in range(len(tokens)):
            page, off = req.pages[t // ps], t % ps
            live[page, off] = True
            if kv_dtype == "model":
                want["k"][:, page, ..., off] = k[:, t]
                want["v"][:, page, ..., off] = v[:, t]
            else:
                (want["k"][:, page, ..., off],
                 want["k_scale"][:, page, ..., off]) = _quantize_rows(k[:, t])
                (want["v"][:, page, ..., off],
                 want["v_scale"][:, page, ..., off]) = _quantize_rows(v[:, t])
    assert live.sum() == sum(engine.scheduler.lengths) - 32   # shared pages
    # [L, page, offset, ...] views, for the [page, offset] mask
    got, want = ({n: np.moveaxis(a, -1, 2) for n, a in d.items()}
                 for d in (got, want))
    if kv_dtype == "int8":
        # layer 0 sees no cached context, so its codes are the plain
        # writer's (a rounding tie may flip one); deeper layers read the
        # quantized context back and carry its budget, so they are
        # held as dequantized values
        for n in ("k", "v"):
            diff = np.abs(got[n][0][live].astype(np.int32)
                          - want[n][0][live])
            assert diff.max() <= 1 and (diff > 0).mean() < 0.01
            np.testing.assert_allclose(got[n + "_scale"][0][live],
                                       want[n + "_scale"][0][live],
                                       rtol=1e-5)
            deq = [a[n].astype(np.float32) * a[n + "_scale"][..., None]
                   for a in (got, want)]
            # (measured drift 0.04-0.08 on values up to 4.3)
            np.testing.assert_allclose(deq[0][:, live], deq[1][:, live],
                                       rtol=0.05, atol=0.1)
    for n in names:
        if kv_dtype == "model":
            np.testing.assert_allclose(got[n][:, live], want[n][:, live],
                                       rtol=2e-4, atol=2e-5)
        # pages no request holds (and that are not the garbage page)
        # were never written
        free = [p for p in range(got[n].shape[1]) if p not in owned]
        assert not got[n][:, free].any()


# --------------------------------------------- the cache's seam, on its own
@pytest.mark.parametrize("kv_dtype", ["model", "int8"])
@pytest.mark.parametrize("writer", ["prefill", "suffix", "decode"])
def test_cache_append_reads_back_through_context(writer, kv_dtype):
    """What a step does to the cache, without a step: ``append`` one
    layer's new rows with each writer, read them back through
    ``context_dense`` — equal to what was written (int8: to the quantiser's
    bound of half a scale step), every row not written as it was, the
    other layer and the garbage page untouched."""
    import jax.numpy as jnp

    from ray_tpu.inference import kv_cache as kvc

    L, P, ps, H, D = 2, 6, 4, 2, 8
    rng = np.random.default_rng(0)
    cache = kvc.KVCache(n_layers=L, num_pages=P, page_size=ps, n_heads=H,
                        head_dim=D, dtype=jnp.float32, kv_dtype=kv_dtype)
    assert (cache.num_pages, cache.page_size) == (P, ps)
    assert cache.dtype == (jnp.int8 if kv_dtype == "int8" else jnp.float32)
    # the page offset is the pool's minor dimension
    assert [a.shape for a in cache.state] == (
        [(L, P, H, D, ps)] * 2
        + [(L, P, H, ps)] * (2 if kv_dtype == "int8" else 0))
    # nothing starts at zero, so "untouched" is a real claim
    cache.state = tuple(
        jnp.asarray(rng.integers(-127, 128, a.shape), a.dtype)
        if a.dtype == jnp.int8
        else jnp.asarray(rng.uniform(0.5, 1.5, a.shape), a.dtype)
        for a in cache.state)
    before = [np.asarray(a) for a in cache.state]
    layer = jnp.int32(1)

    def rows(*shape):
        return jnp.asarray(rng.normal(size=shape + (H, D)), jnp.float32)

    if writer == "prefill":            # a whole 8-token bucket: 2 pages
        table = np.array([[3, 1, 0]], np.int32)
        k, v = rows(8), rows(8)
        where = (kvc.write_prefill, table[0])
        slots, pos = np.zeros(8, int), np.arange(8)
    elif writer == "suffix":           # 4 valid rows of 8 from position 3
        table = np.array([[3, 1, 0]], np.int32)
        k, v = rows(8), rows(8)
        where = (kvc.write_prefill_at, table[0], jnp.int32(3),
                 jnp.int32(4))
        slots, pos = np.zeros(4, int), 3 + np.arange(4)
        k_put, v_put = k[:4], v[:4]
    else:                              # one row per slot
        table = np.array([[3, 1], [2, 4]], np.int32)
        k, v = rows(2), rows(2)
        lengths = np.array([5, 2], np.int32)
        where = (kvc.write_decode, table, lengths)
        slots, pos = np.arange(2), lengths
    if writer != "suffix":
        k_put, v_put = k, v

    write, *at = where
    got_layer, arrays = kvc.append(write, (layer, cache.state), k, v, *at)
    assert int(got_layer) == 1 and len(arrays) == len(before)
    after = [np.asarray(a) for a in arrays]
    for a, b in zip(after, before):
        np.testing.assert_array_equal(a[0], b[0])       # the other layer
        np.testing.assert_array_equal(a[1, kvc.GARBAGE_PAGE],
                                      b[1, kvc.GARBAGE_PAGE])
        np.testing.assert_array_equal(a[1, 5], b[1, 5])  # in no table

    kd, vd = kvc.context_dense((layer, arrays), table, jnp.float32)
    assert kd.shape == vd.shape == (len(table), table.shape[1] * ps, H, D)
    old_k, old_v = kvc.context_dense((layer, cache.state), table,
                                     jnp.float32)
    written = np.zeros(kd.shape[:2], bool)
    written[slots, pos] = True
    for dense, old, put in ((kd, old_k, k_put), (vd, old_v, v_put)):
        dense, old, put = map(np.asarray, (dense, old, put))
        np.testing.assert_array_equal(dense[~written], old[~written])
        if kv_dtype == "model":
            np.testing.assert_array_equal(dense[slots, pos], put)
        else:
            step = np.abs(put).max(-1, keepdims=True) / 127.0
            assert (np.abs(dense[slots, pos] - put)
                    <= step / 2 + 1e-6).all()
    # the dense context is the pool's own pages, offset-minor as
    # stored (int8: the codes times their scales), turned row-major
    for dense, i in ((kd, 0), (vd, 1)):
        pages = after[i][1][table].astype(np.float32)
        if kv_dtype == "int8":
            assert arrays[i].dtype == jnp.int8
            pages = pages * after[i + 2][1][table][:, :, :, None]
        np.testing.assert_array_equal(
            np.asarray(dense),
            np.moveaxis(pages, -1, 2).reshape(dense.shape))


@pytest.mark.parametrize("kv_dtype", ["model", "int8"])
def test_cache_attend_matches_the_dense_context(kv_dtype):
    """``attend`` reads the pool in place: one query row per slot over
    the first ``lengths`` positions of its pages equals a plain softmax
    over the same rows of ``context_dense``, in the layer asked for,
    with a shuffled table, a slot whose tail pages are garbage and a
    row at the garbage page (a free slot), which reads as zeros."""
    import jax.numpy as jnp

    from ray_tpu.inference import kv_cache as kvc

    L, P, ps, H, D = 2, 7, 4, 2, 8
    rng = np.random.default_rng(1)
    cache = kvc.KVCache(n_layers=L, num_pages=P, page_size=ps, n_heads=H,
                        head_dim=D, dtype=jnp.float32, kv_dtype=kv_dtype)
    cache.state = tuple(
        jnp.asarray(rng.integers(-127, 128, a.shape), a.dtype)
        if a.dtype == jnp.int8
        else jnp.asarray(rng.uniform(0.1, 1.0, a.shape), a.dtype)
        for a in cache.state)
    table = np.array([[5, 2, 6], [3, 0, 0], [1, 4, 0], [0, 0, 0]], np.int32)
    lengths = np.array([12, 1, 5, 3], np.int32)
    q = jnp.asarray(rng.normal(size=(4, H, D)), jnp.float32)
    for layer in range(L):
        c = (jnp.int32(layer), cache.state)
        got = np.asarray(kvc.attend(q, c, table, lengths))
        kd, vd = map(np.asarray, kvc.context_dense(c, table, jnp.float32))
        # a row whose table starts at the garbage page is no sequence,
        # whatever its length says: nothing is read for it
        assert not got[3].any()
        for b, n in enumerate(lengths[:3]):
            for h in range(H):
                s = kd[b, :n, h] @ np.asarray(q)[b, h] * D ** -0.5
                p = np.exp(s - s.max())
                np.testing.assert_allclose(
                    got[b, h], (p / p.sum()) @ vd[b, :n, h],
                    rtol=2e-5, atol=2e-5)


# ------------------------------------------- one decode in flight (PR 32)
# The engine dispatches a tick's decode on the device's own tokens and
# only then fetches the previous decode's.  Each case below drives the
# same script through two engines: one as it runs (ahead), one brought
# level with the device after every tick (``_level``: every token is on
# the host before the next dispatch, which is the synchronous tick the
# engine ran before).  What a caller sees per request must be equal.
def _pump(engine, level, script, limit=400):
    """Run ``script(engine, tick, streams)`` before every tick and step
    to the end -> ({rid: [(token, done, logprob, error type)]}, how
    many ticks left a decode in flight)."""
    from ray_tpu.util import chaos
    streams, left_in_flight, tick = {}, 0, 0
    while tick == 0 or engine.has_work():
        script(engine, tick, streams)
        try:
            events = engine.step()
        except chaos.InjectedFault:
            events = []     # the tick is lost; what it dispatched is not
        if level:           # the tick returns its own decode's tokens
            engine._level()
            events, engine._backlog = events + engine._backlog, []
        for ev in events:
            streams.setdefault(ev[0], []).append(
                (ev[1], ev[2], ev.logprob,
                 type(ev.error).__name__ if ev.error is not None
                 else None))
        left_in_flight += any(f.kind == "decode" for f in engine._flight)
        tick += 1
        assert tick < limit, "the engine never ran dry"
    return streams, left_in_flight


def _at_start(submit):
    """A script that submits everything before the first tick."""
    def script(engine, tick, streams):
        if tick == 0:
            submit(engine)
    return script


def _ahead_plain(cfg, sampling=None, **submit_kw):
    """Three requests over two slots: the third takes the slot of
    whichever finishes first, while a decode is in flight."""
    def submit(engine):
        engine.submit(_prompt(9, cfg.vocab_size), max_new_tokens=7,
                      sampling=sampling, **submit_kw)
        engine.submit(_prompt(20, cfg.vocab_size, seed=1),
                      max_new_tokens=4, sampling=sampling, **submit_kw)
        engine.submit(_prompt(5, cfg.vocab_size, seed=2),
                      max_new_tokens=5, sampling=sampling, **submit_kw)
    return _at_start(submit)


def _ahead_case(name, cfg, params):
    """-> (engine kwargs, script, a check of the two engines or
    None)."""
    from ray_tpu.inference import SamplingParams
    kwargs, check = {}, None
    if name == "greedy":
        script = _ahead_plain(cfg)
    elif name == "temperature":
        script = _ahead_plain(cfg, SamplingParams(temperature=0.9,
                                                  seed=11))
    elif name == "top_k_top_p":
        script = _ahead_plain(cfg, SamplingParams(
            temperature=1.1, top_k=20, top_p=0.8, seed=5))
    elif name == "eos_mid_stream":
        # the third greedy token of the first prompt ends its stream:
        # no count foresees it, so one more row of it is in flight
        probe = _make_engine(cfg, params).generate(
            [_prompt(9, cfg.vocab_size)], max_new_tokens=3)[0]
        assert probe[2] not in probe[:2]
        script = _ahead_plain(cfg, eos_token=probe[2])

        def check(ahead, level, streams):
            assert [t for t, *_ in streams[0]] == probe
            assert streams[0][-1][1]            # done at the eos token
    elif name in ("max_new_1", "max_new_2"):
        n = int(name[-1])

        def submit(engine):
            for seed in range(3):
                engine.submit(_prompt(6 + seed, cfg.vocab_size, seed),
                              max_new_tokens=n)
        script = _at_start(submit)

        def check(ahead, level, streams):
            assert all(len(s) == n and s[-1][1] for s in streams.values())
    elif name in ("cancel_in_flight", "deadline_in_flight"):
        state = {}

        def script(engine, tick, streams):
            if tick == 0:
                engine.submit(_prompt(9, cfg.vocab_size),
                              max_new_tokens=12, deadline_s=3600.0)
                engine.submit(_prompt(7, cfg.vocab_size, seed=1),
                              max_new_tokens=8)
            if len(streams.get(0, ())) == 3 and not state.get(engine):
                state[engine] = True
                req = engine._requests[0]
                # the engine as it runs has the fourth token in flight
                state["in_flight", bool(engine._flight)] = req.in_flight
                if name == "cancel_in_flight":
                    engine.cancel(0)
                else:
                    req.submitted_ts -= 7200.0

        def check(ahead, level, streams):
            assert state["in_flight", True] == 1
            assert state["in_flight", False] == 0
            tail = streams[0][3:]
            if name == "cancel_in_flight":
                assert tail == []
            else:
                assert [(t, d, e) for t, d, _lp, e in tail] == [
                    (-1, True, "DeadlineExceededError")]
            assert len(streams[1]) == 8
    elif name in ("prefix_hits", "prefix_hits_int8", "latent_row"):
        kwargs = {"prefix": True, "slots": 3}
        if name.endswith("int8"):
            kwargs["kv_dtype"] = "int8"
        if name == "latent_row":
            # the other model: a latent cache row, two cache layers a
            # block, an expert layer whose counts ride on the fetch
            import jax
            import jax.numpy as jnp
            from ray_tpu.models import longcat
            lc = longcat.LongcatConfig.longcat_tiny(dtype=jnp.float32)
            kwargs["model"] = (lc, longcat.init_params(
                lc, jax.random.PRNGKey(0)))
        shared = _prompt(32, cfg.vocab_size, seed=4)

        def script(engine, tick, streams):
            if tick == 0:
                engine.submit(shared + [3, 1, 4], max_new_tokens=5)
            if tick == 2:       # hits the two pages registered above,
                # and two admitted in one tick share them as well
                engine.submit(shared + [1, 5, 9, 2], max_new_tokens=6)
                engine.submit(shared + [6, 5], max_new_tokens=4)

        def check(ahead, level, streams):
            for engine in (ahead, level):
                assert engine.scheduler.prefix_requests_hit == 2
            if name == "latent_row":
                # the same steps, so the same counts
                assert (ahead.telemetry.summary()["moe"]
                        == level.telemetry.summary()["moe"])
    elif name == "int8_cache":
        kwargs = {"kv_dtype": "int8"}
        script = _ahead_plain(cfg)
    elif name == "lora_bank":
        import jax
        from ray_tpu.adapters import LoraConfig, init_adapter
        lcfg = LoraConfig(enabled=True, rank=4, scale=0.5, cache_slots=3)
        kwargs = {"lora": lcfg, "slots": 3}
        adapter = init_adapter(cfg, lcfg, jax.random.PRNGKey(11),
                               random_b=True)

        def submit(engine):
            engine.load_adapter("t1", adapter, scale=0.5)
            engine.submit(_prompt(9, cfg.vocab_size), max_new_tokens=6,
                          sampling=SamplingParams(model_id="t1"))
            engine.submit(_prompt(9, cfg.vocab_size), max_new_tokens=6)
            engine.submit(_prompt(12, cfg.vocab_size, seed=2),
                          max_new_tokens=4,
                          sampling=SamplingParams(model_id="t1"))
        script = _at_start(submit)

        def check(ahead, level, streams):
            # the tenant's tokens are its own, not the base model's
            assert ([t for t, *_ in streams[0]]
                    != [t for t, *_ in streams[1]])
    elif name == "hold_pages_export":
        handoffs = {}

        def script(engine, tick, streams):
            if tick == 0:
                engine.submit(_prompt(20, cfg.vocab_size),
                              max_new_tokens=1, hold_pages=True)
                engine.submit(_prompt(9, cfg.vocab_size, seed=1),
                              max_new_tokens=3, hold_pages=True)
                engine.submit(_prompt(7, cfg.vocab_size, seed=2),
                              max_new_tokens=6)
            for rid in (0, 1):
                if rid in engine._held:     # as soon as it has retired
                    handoffs[engine, rid] = engine.export_request(rid)

        def check(ahead, level, streams):
            for rid in (0, 1):
                a, b = handoffs[ahead, rid], handoffs[level, rid]
                assert (a.context, a.next_token, a.next_logprob) == (
                    b.context, b.next_token, b.next_logprob)
                np.testing.assert_array_equal(a.k, b.k)
                np.testing.assert_array_equal(a.v, b.v)
            assert ahead.stats()["exports"] == 2
    elif name == "set_params_between_ticks":
        import jax
        new = jax.tree.map(lambda a: np.asarray(a) * 1.05, params)

        def script(engine, tick, streams):
            if tick == 0:
                engine.submit(_prompt(9, cfg.vocab_size),
                              max_new_tokens=8)
                engine.submit(_prompt(5, cfg.vocab_size, seed=1),
                              max_new_tokens=6)
            if tick == 3:       # the decode in flight ran under the old
                engine.set_params(new, version=7)

        def check(ahead, level, streams):
            unswapped, _ = _pump(_make_engine(cfg, params), False,
                                 lambda e, t, s: script(e, t, s)
                                 if t == 0 else None)
            assert streams[0][:4] == unswapped[0][:4]
            assert streams[0] != unswapped[0]
            assert ahead.param_version == 7
    elif name == "decode_fault_then_resume":
        # the first decode's dispatch fails after the prefills of its
        # tick were dispatched: their first tokens, never fetched, are
        # still the next decode's input
        from ray_tpu.util import chaos
        plain = _ahead_plain(cfg)

        def script(engine, tick, streams):
            if tick == 0:
                chaos.install_faults("infer.decode@1")
            plain(engine, tick, streams)

        def check(ahead, level, streams):
            solo = _make_engine(cfg, params).generate(
                [_prompt(9, cfg.vocab_size)], max_new_tokens=7)[0]
            assert [t for t, *_ in streams[0]] == solo
    elif name == "debug_logits":
        kwargs = {"debug_logits": True}
        script = _ahead_plain(cfg)

        def check(ahead, level, streams):
            # one row per generated token, the row that produced it
            for rid, stream in streams.items():
                rows = np.stack(ahead.logits_trace[rid])
                assert len(rows) == len(stream)
                np.testing.assert_array_equal(
                    rows, np.stack(level.logits_trace[rid]))
                assert list(rows.argmax(-1)) == [t for t, *_ in stream]
            prompt = _prompt(9, cfg.vocab_size)
            np.testing.assert_allclose(
                np.stack(ahead.logits_trace[0]),
                _teacher_forced_rows(cfg, params, prompt,
                                     [t for t, *_ in streams[0]]),
                rtol=2e-4, atol=2e-4)
    else:
        raise KeyError(name)
    return kwargs, script, check


_AHEAD_CASES = (
    "greedy", "temperature", "top_k_top_p", "eos_mid_stream",
    "max_new_1", "max_new_2", "cancel_in_flight", "deadline_in_flight",
    "prefix_hits", "prefix_hits_int8", "int8_cache", "lora_bank",
    "hold_pages_export", "set_params_between_ticks",
    "decode_fault_then_resume", "debug_logits", "latent_row")


@pytest.mark.parametrize("case", _AHEAD_CASES)
def test_running_ahead_matches_the_synchronous_tick(tiny_f32, case):
    cfg, params = tiny_f32
    kwargs, script, check = _ahead_case(case, cfg, params)
    cfg, params = kwargs.pop("model", (cfg, params))
    ahead = _make_engine(cfg, params, telemetry=True, **kwargs)
    level = _make_engine(cfg, params, telemetry=True, **kwargs)
    try:
        got, in_flight = _pump(ahead, False, script)
        want, never = _pump(level, True, script)
    finally:
        from ray_tpu.util import chaos
        chaos.clear_faults()
    assert never == 0 and (in_flight > 0 or case == "max_new_1")
    assert got and got == want
    if check is not None:
        check(ahead, level, got)
    for engine in (ahead, level):
        assert engine.leak_free() and not engine.has_work()
        assert not engine._flight and not engine._backlog
        sched = engine.scheduler
        assert not sched.active and not sched.waiting
        assert len(sched.free_slots) == engine.slots
        assert (sched.allocator.free_count      # idle pages count
                == sched.allocator.num_pages - 1)
        assert all(r.in_flight == 0 for r in engine._requests.values())
    decode = ahead.telemetry.summary().get("decode")
    if decode is not None:      # every decode was left in flight
        assert decode["ahead_share"] == 1.0
        assert decode["dispatches"] == in_flight


# ------------------------------ the pool's layout stays inside kv_cache.py
def test_handoff_in_the_row_major_wire_format_installs_and_decodes(tiny_f32):
    """A handoff as replicas wrote it before the pool turned its page
    offset minor — contents ``[L, pages, page, H, D]``, here laid down
    by a plain numpy writer from a cache-free forward — installs into
    the pool and decodes to the tokens of an engine that prefilled the
    prompt itself; and what this engine exports is still that format."""
    from ray_tpu.inference import kv_cache as kvc
    cfg, params = tiny_f32
    ps, n_new = 16, 6
    prompt = _prompt(37, cfg.vocab_size, seed=5)
    solo = _make_engine(cfg, params, page_size=ps)
    rid = solo.submit(prompt, max_new_tokens=1, hold_pages=True)
    while solo.has_work():
        solo.step()
    exported = solo.export_request(rid)
    want = _make_engine(cfg, params, page_size=ps).generate(
        [prompt], max_new_tokens=1 + n_new)[0]

    k, v = _reference_kv(cfg, params, prompt)          # [L, T, H, D]
    n_pages = kvc.pages_needed(len(prompt), ps)
    old = {}
    for name, rows in (("k", k), ("v", v)):
        pages = np.zeros((cfg.n_layers, n_pages * ps) + rows.shape[2:],
                         rows.dtype)
        pages[:, :len(prompt)] = rows
        old[name] = pages.reshape((cfg.n_layers, n_pages, ps)
                                  + rows.shape[2:])
        got = getattr(exported, name)
        assert got.shape == old[name].shape and got.flags.c_contiguous
        np.testing.assert_allclose(
            got.reshape(pages.shape)[:, :len(prompt)], rows,
            rtol=2e-4, atol=2e-5)
    assert exported.next_token == want[0]
    handoff = kvc.KVHandoff(
        context=list(prompt), page_size=ps, kv_dtype="model",
        dtype=str(solo.cache.dtype),
        chain_hashes=kvc.PrefixIndex.chain_hashes(prompt, ps),
        next_token=want[0], next_logprob=0.0, **old)

    engine = _make_engine(cfg, params, page_size=ps)
    rid = engine.import_submit(handoff, max_new_tokens=n_new)
    got = []
    while engine.has_work():
        got += [ev[1] for ev in engine.step() if ev[0] == rid]
    assert got == want[1:]
    assert engine.stats()["imports"] == 1 and engine.leak_free()


@pytest.mark.parametrize("kv_dtype", ["model", "int8"])
def test_append_decode_in_place_matches_the_blend(kv_dtype, monkeypatch):
    """``append_decode`` where its one decision says the pools block:
    K and V (an int8 cache's codes) go through the write kernel, the
    int8 scales through the whole-page blend, and every array comes out
    equal to the bit to ``append(write_decode, ...)``'s but for the
    garbage page, which only the blend's dead slot rewrites."""
    import jax.numpy as jnp

    from ray_tpu.inference import kv_cache as kvc
    from ray_tpu.ops import attention
    L, P, ps, H, D = 2, 6, 128, 2, 32
    rng = np.random.default_rng(1)
    cache = kvc.KVCache(n_layers=L, num_pages=P, page_size=ps, n_heads=H,
                        head_dim=D, dtype=jnp.bfloat16, kv_dtype=kv_dtype)
    cache.state = tuple(
        jnp.asarray(rng.integers(-127, 128, a.shape), a.dtype)
        if a.dtype == jnp.int8
        else jnp.asarray(rng.uniform(0.5, 1.5, a.shape), a.dtype)
        for a in cache.state)
    table = np.array([[3, 1], [0, 0], [2, 4]], np.int32)   # slot 1 is free
    lengths = np.array([ps + 7, 0, ps - 1], np.int32)
    k, v = (jnp.asarray(rng.normal(size=(3, H, D)), jnp.bfloat16)
            for _ in range(2))
    at = ((jnp.int32(1), cache.state), k, v, table, lengths)
    _layer, want = kvc.append(kvc.write_decode, *at)
    monkeypatch.setattr(attention, "decode_write_uses_pallas",
                        lambda D, page, dtype: True)
    layer, got = kvc.append_decode(*at)
    assert int(layer) == 1 and len(got) == len(want) == len(cache.state)
    for a, b, before in zip(got, want, cache.state):
        a, b = np.array(a), np.array(b)
        assert a.dtype == b.dtype
        if a.ndim == 5:         # the kernel's: the garbage page as it was
            np.testing.assert_array_equal(a[1, 0], np.asarray(before)[1, 0])
            b[1, 0] = a[1, 0]
        np.testing.assert_array_equal(a, b)
    assert not np.array_equal(np.asarray(got[0]), np.asarray(cache.state[0]))


def test_decode_counts_the_pages_it_reads_of_those_its_table_names(tiny_f32):
    """``decode.pages_read`` over ``decode.pages_table``: each plain
    decode adds its dispatched rows' live pages (the context with the
    token being written) and the whole table's room, by the host's own
    lengths; nothing is counted with telemetry off."""
    from ray_tpu.inference.kv_cache import pages_needed
    cfg, params = tiny_f32
    ps, slots = 16, 3
    jobs = [(5, 4), (30, 9), (16, 2)]      # (prompt tokens, new tokens)
    engines = [_make_engine(cfg, params, page_size=ps, slots=slots,
                            telemetry=on) for on in (True, False)]
    for engine in engines:
        for i, (n, m) in enumerate(jobs):
            engine.submit(_prompt(n, cfg.vocab_size, seed=i),
                          max_new_tokens=m)
        while engine.has_work():
            engine.step()
    on, off = engines
    decode = on.telemetry.summary()["decode"]
    # the k-th decoded token of a request is written at position n+k-1
    read = sum(pages_needed(n + k, ps) for n, m in jobs
               for k in range(1, m))
    table = decode["dispatches"] * slots * on.max_pages_per_slot
    assert decode["dispatches"] == max(m for _n, m in jobs) - 1
    assert (decode["pages_read"], decode["pages_table"]) == (read, table)
    assert decode["pages_read"] / decode["pages_table"] == read / table
    assert 0 < read < table
    assert off.telemetry.summary() == {"enabled": False}
    assert off.telemetry.decode_pages == [0, 0]


@pytest.mark.parametrize("in_place", [False, True], ids=["blend", "kernel"])
def test_decode_counts_the_rows_it_lays_and_the_pages_it_moves(
        tiny_f32, monkeypatch, in_place):
    """``decode.rows_written`` and ``decode.tail_pages_rewritten``, two
    counts: every dispatched row lays one row; the whole-page blend
    (the CPU's path) moves every slot's tail page to do it, the write
    kernel the live slots' alone.  The engine reports the one decision
    (``ops/attention.py:decode_write_uses_pallas``) beside the
    attention's, and decodes the same tokens either way: the kernel arm
    runs the real kernel, in interpret mode, where the decision is made
    to say yes."""
    from ray_tpu.ops import attention
    cfg, params = tiny_f32
    slots, jobs = 3, [(5, 4), (30, 6)]     # (prompt tokens, new tokens)

    def run():
        # a 128-token page and head_dim 16 block; executables of its own
        engine = _make_engine(cfg, params, page_size=128, slots=slots,
                              telemetry=True, executable_cache={})
        tokens = {engine.submit(_prompt(n, cfg.vocab_size, seed=i),
                                max_new_tokens=m): []
                  for i, (n, m) in enumerate(jobs)}
        while engine.has_work():
            for rid, token, _done in engine.step():
                tokens[rid].append(token)
        return (list(tokens.values()), engine.stats()["decode_write_impl"],
                engine.telemetry.summary()["decode"])

    rows = sum(m - 1 for _n, m in jobs)
    tokens, impl, decode = run()
    assert [len(t) for t in tokens] == [m for _n, m in jobs]
    assert impl == "blend" and decode["rows_written"] == rows
    assert decode["tail_pages_rewritten"] == decode["dispatches"] * slots
    if in_place:
        # the one decision, read where it is taken (the cache's writer
        # asks it, the engine reports the cache's answer)
        monkeypatch.setattr(attention, "decode_write_uses_pallas",
                            lambda D, page, dtype: True)
        assert run() == (tokens, "pallas", {**decode,
                                            "tail_pages_rewritten": rows})
