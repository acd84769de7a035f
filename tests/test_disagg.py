"""Disaggregated prefill/decode serving (r20): KV-page export/import
round trips (fp32 + int8 bit-identical), digest-match skip-transfer,
eviction-pressure imports, the two-pool acceptance run (exact parity
with co-located, zero recompiles, fleet-wide leak audit incl. in-flight
handoff objects), and chaos failover on every handoff leg."""

import time

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


@pytest.fixture(autouse=True)
def _no_faults():
    from ray_tpu.util import chaos
    chaos.clear_faults()
    yield
    chaos.clear_faults()


# the tier-1 budget rule: one tiny-f32 engine compile per process.
# test_disagg collects first alphabetically, so IT pays the shared
# (GPTConfig.tiny f32, slots 2, page 16, buckets (16,32,64)) compile
# into test_inference.py's cache and test_fleet/test_inference ride it
# (safe under the tier-1 invocation: xdist and random order disabled).
import test_inference as _ti  # noqa: E402

_EXEC_CACHE = _ti._EXEC_CACHE
_EXEC_CACHE_INT8 = {}           # int8 executables, shared within this file
_ENGINE_KW = {"slots": 2, "page_size": 16, "buckets": (16, 32, 64),
              "telemetry": False, "executable_cache": _EXEC_CACHE}


def _make_engine(tiny, **over):
    from ray_tpu.inference import InferenceEngine
    cfg, params = tiny
    kw = dict(_ENGINE_KW)
    kw.update(over)
    if kw.get("kv_dtype") == "int8":
        kw.setdefault("executable_cache", _EXEC_CACHE_INT8)
        if kw["executable_cache"] is _EXEC_CACHE:
            kw["executable_cache"] = _EXEC_CACHE_INT8
    return InferenceEngine(cfg, params, **kw)


def _make_replica(tiny, rid, *, watchdog_s=0.0, **over):
    from ray_tpu.fleet import EngineReplica
    return EngineReplica(rid, _make_engine(tiny, **over),
                         watchdog_s=watchdog_s)


def _fcfg(**over):
    from ray_tpu.fleet import FleetConfig
    base = dict(retries=2, affinity=True, affinity_cap=8,
                up_depth=4.0, ttft_slo=0.0, dwell=1.0, backoff=0.0,
                backoff_max=8.0, slow_factor=0.0, hedge=False)
    base.update(over)
    return FleetConfig(**base)


def _tel():
    from ray_tpu.telemetry.config import TelemetryConfig
    from ray_tpu.telemetry.fleet import FleetTelemetry
    return FleetTelemetry(config=TelemetryConfig(enabled=True))


def _prompt(n, vocab, seed=0):
    return list(np.random.RandomState(seed).randint(0, vocab, size=n))


def _first_token(engine, prompt, **kw):
    """Run one first-token-stop (max_new=1 + hold) submission to
    completion; returns ``(rid, token)``."""
    rid = engine.submit(prompt, max_new_tokens=1, hold_pages=True, **kw)
    toks = []
    while engine.has_work():
        for _r, tok, _d in engine.step():
            toks.append(tok)
    assert len(toks) == 1
    return rid, toks[0]


def _drain(engine, out):
    while engine.has_work():
        for _r, tok, _d in engine.step():
            out.append(tok)
    return out


# ------------------------------------------------- export/import round trip
@pytest.mark.parametrize("plen", [40, 48, 9])
def test_export_import_roundtrip_fp32(tiny_f32, plen):
    """Export after the first token, import into a second engine, and
    the continuation is token-exact vs a co-located run — across a
    partial-tail prompt (40 = 2.5 pages), an exact-page-multiple one
    (48 = 3 pages: every context page full and shareable), and a
    sub-page one (9).  The importer compiles NOTHING (the decode step
    over a seeded slot is the one executable it already has), payload
    contents match the exporter's cache bit-for-bit, and both
    allocators audit clean."""
    from ray_tpu.inference import kv_cache as kvc
    cfg, _ = tiny_f32
    prompt = _prompt(plen, cfg.vocab_size, seed=plen)
    ref = _make_engine(tiny_f32)
    (want,) = ref.generate([prompt], max_new_tokens=6)

    pre = _make_engine(tiny_f32)
    dec = _make_engine(tiny_f32)
    rid, t0 = _first_token(pre, prompt)
    assert t0 == want[0]
    assert pre.stats()["held"] == 1          # pages survive retirement
    handoff = pre.export_request(rid)
    assert pre.stats()["held"] == 0 and pre.stats()["exports"] == 1
    assert handoff.context == prompt
    assert handoff.n_pages == -(-plen // 16)
    assert handoff.n_full_pages == plen // 16
    assert len(handoff.chain_hashes) == handoff.n_full_pages
    # analytic byte math: K+V across layers per page
    per_page = kvc.handoff_page_bytes(
        n_layers=cfg.n_layers, page_size=16, n_heads=cfg.n_heads,
        head_dim=cfg.head_dim, itemsize=4, quantized=False)
    assert handoff.nbytes == per_page * handoff.n_pages

    rid2 = dec.import_submit(handoff, max_new_tokens=5)
    # the installed pages are bit-identical to the payload (the first
    # step's decode tick already appended ONE token at position plen,
    # which lands inside the tail page when the context has one — so
    # the tail compares only its context positions)
    # (the engine runs one decode ahead: that decode is dispatched and
    # its write is in the cache, its token comes out of the next step)
    assert dec.step() == [] and dec.has_work()
    (req,) = dec.scheduler.active.values()
    assert req.in_flight == 1 and len(req.generated) == 1
    arrays = kvc.export_pages(dec.cache, req.pages[:handoff.n_pages])
    tail = plen % 16
    for got, sent in ((arrays["k"], handoff.k),
                      (arrays["v"], handoff.v)):
        np.testing.assert_array_equal(got[:, :plen // 16],
                                      sent[:, :plen // 16])
        if tail:
            np.testing.assert_array_equal(got[:, -1, :tail],
                                          sent[:, -1, :tail])
    out = [t0]
    assert _drain(dec, out) == want
    assert dec.stats()["imports"] == 1
    assert dec.stats()["compiles"] == {"prefill": 0,
                                       "prefill_cached": 0,
                                       "decode": 0}
    for eng in (pre, dec):
        sched = eng.scheduler
        assert not sched.active and not sched.waiting
        assert sched.allocator.free_count == sched.allocator.num_pages - 1


def test_export_import_roundtrip_int8(tiny_f32):
    """int8 handoffs move codes + scales on the same path,
    bit-identically: the importer's cache pages equal the payload's
    arrays exactly, the continuation equals an int8 co-located run,
    and the per-page byte math shows the wire saving (head_dim + 4
    bytes per cached vector vs head_dim * 4 for this f32 model — on a
    bf16 fleet the same arithmetic gives the ~2x claim)."""
    from ray_tpu.inference import kv_cache as kvc
    cfg, _ = tiny_f32
    prompt = _prompt(48, cfg.vocab_size, seed=8)
    ref = _make_engine(tiny_f32, kv_dtype="int8")
    (want,) = ref.generate([prompt], max_new_tokens=6)

    pre = _make_engine(tiny_f32, kv_dtype="int8")
    dec = _make_engine(tiny_f32, kv_dtype="int8")
    rid, t0 = _first_token(pre, prompt)
    h8 = pre.export_request(rid)
    assert h8.kv_dtype == "int8"
    assert h8.k.dtype == np.int8 and h8.k_scale.dtype == np.float32
    per_page8 = kvc.handoff_page_bytes(
        n_layers=cfg.n_layers, page_size=16, n_heads=cfg.n_heads,
        head_dim=cfg.head_dim, itemsize=1, quantized=True)
    per_page32 = kvc.handoff_page_bytes(
        n_layers=cfg.n_layers, page_size=16, n_heads=cfg.n_heads,
        head_dim=cfg.head_dim, itemsize=4, quantized=False)
    assert h8.nbytes == per_page8 * h8.n_pages
    assert per_page8 / per_page32 == pytest.approx(
        (cfg.head_dim + 4) / (cfg.head_dim * 4))

    rid2 = dec.import_submit(h8, max_new_tokens=5)
    assert dec.step() == []          # the first decode is in flight
    (req,) = dec.scheduler.active.values()
    arrays = kvc.export_pages(dec.cache, req.pages[:h8.n_pages])
    np.testing.assert_array_equal(arrays["k"], h8.k)
    np.testing.assert_array_equal(arrays["v"], h8.v)
    np.testing.assert_array_equal(arrays["k_scale"], h8.k_scale)
    np.testing.assert_array_equal(arrays["v_scale"], h8.v_scale)
    out = [t0]
    assert _drain(dec, out) == want
    assert dec.stats()["compiles"] == {"prefill": 0,
                                       "prefill_cached": 0,
                                       "decode": 0}
    # dtype mismatch is refused loudly — the contents would be
    # reinterpreted, not converted
    with pytest.raises(ValueError, match="kv_dtype"):
        _make_engine(tiny_f32).import_submit(h8, max_new_tokens=2)
    for eng in (pre, dec):
        assert eng.scheduler.allocator.free_count \
            == eng.scheduler.allocator.num_pages - 1


def test_import_digest_match_skips_transfer(tiny_f32):
    """The skip-transfer path: once an exact-page-multiple context is
    resident (first import registered its pages), a metadata-only
    handoff installs as pure prefix hits — zero content bytes, zero
    writes — and still continues token-exactly.  If the resident pages
    were evicted meanwhile, admission surfaces the typed
    HandoffContentMissing instead of decoding over garbage."""
    from ray_tpu.inference import HandoffContentMissing
    cfg, _ = tiny_f32
    prompt = _prompt(48, cfg.vocab_size, seed=5)      # 3 full pages
    ref = _make_engine(tiny_f32)
    (want,) = ref.generate([prompt], max_new_tokens=4)

    pre = _make_engine(tiny_f32)
    dec = _make_engine(tiny_f32)
    rid, t0 = _first_token(pre, prompt)
    h = pre.export_request(rid)
    dec.import_submit(h, max_new_tokens=3)
    assert _drain(dec, [t0]) == want
    digest = dec.prefix_digest()
    assert all(hh in digest for hh in h.chain_hashes)

    # warm: same prompt again, metadata only (strip_contents is the
    # wire form the router ships when the digest covers everything)
    rid, t0 = _first_token(pre, prompt)     # prefill-side prefix hit
    warm = pre.export_request(rid).strip_contents()
    assert warm.nbytes == 0 and warm.k is None
    hit_pages_before = dec.scheduler.prefix_hit_pages
    dec.import_submit(warm, max_new_tokens=3)
    assert _drain(dec, [t0]) == want
    # all three context pages installed as hits — zero writes
    assert dec.scheduler.prefix_hit_pages == hit_pages_before + 3

    # miss: flush the prefix cache between digest check and admission
    rid, t0 = _first_token(pre, prompt)
    gone = pre.export_request(rid).strip_contents()
    dec.scheduler.flush_prefix()
    dec.import_submit(gone, max_new_tokens=3)
    errs = []
    while dec.has_work():
        for ev in dec.step():
            if ev.error is not None:
                errs.append(ev.error)
    assert len(errs) == 1 and isinstance(errs[0], HandoffContentMissing)
    assert errs[0].missing_pages == 3
    for eng in (pre, dec):
        assert eng.scheduler.allocator.free_count \
            == eng.scheduler.allocator.num_pages - 1


def test_import_into_occupied_allocator_evicts(tiny_f32):
    """Import under page pressure: a decode engine whose pool is
    mostly idle registered pages evicts LRU-first to take the handoffs
    (exactly like a cold admission would), a handoff that cannot get a
    slot NOW waits in the queue — the slot-occupancy backlog the
    decode pool scales on — and every continuation stays exact."""
    cfg, _ = tiny_f32
    # tight pool: 8 usable pages; each 33-token request reserves 3
    cache9 = {}
    dec = _make_engine(tiny_f32, num_pages=9, executable_cache=cache9)
    pre = _make_engine(tiny_f32, num_pages=9, executable_cache=cache9)
    ref = _make_engine(tiny_f32, num_pages=9, executable_cache=cache9)
    fills = [_prompt(33, cfg.vocab_size, seed=60 + i) for i in range(2)]
    targets = [_prompt(33, cfg.vocab_size, seed=80 + i)
               for i in range(3)]
    expected = [ref.generate([t], max_new_tokens=4)[0]
                for t in targets]
    # occupy: run two requests to completion so their 2 full prompt
    # pages each park idle in the prefix pool (refcount 0, registered
    # — evictable), leaving only 4 truly-free pages for 3 imports
    ref.generate(fills, max_new_tokens=4)  # warm compiles only
    dec.generate(fills, max_new_tokens=4)
    assert dec.scheduler.allocator.idle_count == 4
    assert len(dec.scheduler.allocator._free) == 4

    outs = {}
    for target in targets:
        rid, t0 = _first_token(pre, target)
        h = pre.export_request(rid)
        outs[dec.import_submit(h, max_new_tokens=3)] = [t0]
    # 3 imports, 2 slots: at least one waits for a slot (occupancy)
    assert len(dec.scheduler.waiting) >= 1
    while dec.has_work():
        for ev in dec.step():
            if ev[0] in outs and ev.error is None:
                outs[ev[0]].append(ev[1])
    # 3 * 3 = 9 pages needed against 4 free: idle pages were evicted
    assert dec.scheduler.allocator.evictions > 0
    for out, want in zip(outs.values(), expected):
        assert out == want
    for eng in (pre, dec):
        assert eng.scheduler.allocator.free_count \
            == eng.scheduler.allocator.num_pages - 1


# --------------------------------------------------------- the two pools
def test_disagg_acceptance(tiny_f32):
    """THE r20 acceptance test: mixed-length traffic (shared-prefix
    groups + singletons) through a 1-prefill + 2-decode fleet completes
    with token sequences exactly equal to the co-located run (greedy),
    compile counters identical to a warmed single-pool engine — zero
    steady-state recompiles on BOTH pools — and the fleet-wide leak
    audit green including in-flight handoff objects.  Warm handoffs
    (exact-page-multiple repeats resident by digest) move zero bytes."""
    from ray_tpu.fleet import DisaggRouter
    cfg, _ = tiny_f32
    shared = _prompt(32, cfg.vocab_size, seed=11)     # 2 full pages
    exact = _prompt(48, cfg.vocab_size, seed=31)      # 3 full, no tail
    # the exact-multiple prompt repeats in a SECOND traffic wave: by
    # then its pages are registered on a decode replica and digest
    # affinity makes the repeat handoff warm (within one wave a
    # first-token-stop tick prefills the whole queue, so every handoff
    # dispatches before any import installs — warmth is cross-wave by
    # construction)
    prompts = ([exact]
               + [shared + _prompt(5 + i, cfg.vocab_size, seed=20 + i)
                  for i in range(5)]
               + [_prompt(9, cfg.vocab_size, seed=32)]
               + [exact])
    ref = _make_replica(tiny_f32, "ref")
    expected = ref.engine.generate(prompts, max_new_tokens=4)

    pre = [_make_replica(tiny_f32, "p0")]
    dec = [_make_replica(tiny_f32, f"d{i}") for i in range(2)]
    tel = _tel()
    router = DisaggRouter(pre, dec, cfg=_fcfg(), rng_seed=0,
                          telemetry=tel)
    streams = [router.remote({"tokens": p, "max_new_tokens": 4})
               for p in prompts[:-1]]
    outs = [list(s) for s in streams]
    streams.append(router.remote({"tokens": prompts[-1],
                                  "max_new_tokens": 4}))
    outs.append(list(streams[-1]))
    for out, want in zip(outs, expected):
        assert out == want
    assert all(s.done and s.error is None and s.retries == 0
               for s in streams)
    assert router.quiesce()
    # zero steady-state recompiles on both pools (shared cache warmed
    # by the reference replica)
    for r in router.replicas():
        assert r.engine.stats()["compiles"] == {
            "prefill": 0, "prefill_cached": 0, "decode": 0}
    # fleet-wide leak audit, including the handoff store
    assert router.leak_free()
    assert router.store.in_flight == 0
    # every stream's pages moved exactly once (no failovers)
    summ = tel.summary()
    assert summ["handoffs"] == len(prompts)
    # the warm pair's second handoff shipped metadata only
    assert summ["handoffs_skipped"] >= 1
    assert summ["handoff_bytes_total"] > 0
    assert summ["ttft_s_by_mode"]["disagg"]["count"] == len(prompts)
    assert set(summ["pool_queue_depth"]) == {"prefill", "decode"}
    # pool split is visible in the engine counters: prefill replicas
    # exported everything, decode replicas imported everything and
    # never ran a prefill
    assert sum(r.engine.stats()["exports"]
               for r in router.replicas("prefill")) == len(prompts)
    assert sum(r.engine.stats()["imports"]
               for r in router.replicas("decode")) == len(prompts)
    assert all(r.engine.stats()["hits"]["prefill"] == 0
               and r.engine.stats()["hits"]["prefill_cached"] == 0
               for r in router.replicas("decode"))


def test_disagg_stream_logprobs_and_geometry(tiny_f32):
    """The stream honors the deployment payload contract
    ({"logprobs": True} yields {"token", "logprob"} dicts matching a
    direct engine run), and mixed-geometry pools are refused up
    front — handoffs move raw page bytes, one fleet geometry."""
    from ray_tpu.fleet import DisaggRouter
    cfg, _ = tiny_f32
    prompt = _prompt(19, cfg.vocab_size, seed=42)
    ref = _make_replica(tiny_f32, "lp-ref")
    toks_ref, lps_ref = ref.engine.generate([prompt], max_new_tokens=4,
                                            return_logprobs=True)
    router = DisaggRouter([_make_replica(tiny_f32, "lp-p")],
                          [_make_replica(tiny_f32, "lp-d")],
                          cfg=_fcfg(), telemetry=_tel())
    out = list(router.remote({"tokens": prompt, "max_new_tokens": 4,
                              "logprobs": True}))
    assert [o["token"] for o in out] == toks_ref[0]
    assert [o["logprob"] for o in out] == pytest.approx(lps_ref[0])
    assert router.quiesce() and router.leak_free()
    with pytest.raises(ValueError, match="geometry"):
        DisaggRouter([_make_replica(tiny_f32, "g-p")],
                     [_make_replica(tiny_f32, "g-d", page_size=8,
                                    executable_cache={})],
                     cfg=_fcfg(), telemetry=_tel())
    with pytest.raises(ValueError, match="BOTH pools"):
        DisaggRouter([_make_replica(tiny_f32, "g2-p")], [],
                     cfg=_fcfg(), telemetry=_tel())


# ------------------------------------------------------- chaos failover
def test_handoff_chaos_all_legs_reprefill_exactly(tiny_f32):
    """Chaos acceptance, transfer legs: a ``serve.handoff`` fault on
    the export leg (hit 1) and on a later import leg (hit 4) each
    degrade to re-prefill-from-prompt failover — every stream completes
    with the exact greedy continuation, at-most-once delivery holds
    structurally, and zero pages/refs/handoff objects leak."""
    from ray_tpu.fleet import DisaggRouter
    from ray_tpu.util import chaos
    cfg, _ = tiny_f32
    prompts = [_prompt(20 + 3 * i, cfg.vocab_size, seed=i)
               for i in range(5)]
    ref = _make_replica(tiny_f32, "hc-ref")
    expected = ref.engine.generate(prompts, max_new_tokens=4)
    for spec in ("serve.handoff@1", "serve.handoff@4",
                 "serve.handoff@1,serve.handoff@4"):
        tel = _tel()
        router = DisaggRouter(
            [_make_replica(tiny_f32, f"hp-{spec}")],
            [_make_replica(tiny_f32, f"hd0-{spec}"),
             _make_replica(tiny_f32, f"hd1-{spec}")],
            cfg=_fcfg(), rng_seed=0, telemetry=tel)
        plan = chaos.install_faults(spec)
        streams = [router.remote({"tokens": p, "max_new_tokens": 4})
                   for p in prompts]
        outs = [list(s) for s in streams]
        chaos.clear_faults()
        assert len(plan.fired) == spec.count("serve.handoff")
        for out, want in zip(outs, expected):
            assert out == want
        assert all(s.done and s.error is None for s in streams)
        assert any(s.retries > 0 for s in streams)
        assert tel.retries.get("handoff", 0) >= 1
        assert router.quiesce() and router.leak_free()
        assert router.store.in_flight == 0


def test_handoff_slowdown_delay_supported(tiny_f32):
    """``serve.handoff:delay=`` stretches the transfer instead of
    killing it — the handoff-seconds histogram shows the injected
    wall, nothing fails over, and the output stays exact."""
    from ray_tpu.fleet import DisaggRouter
    from ray_tpu.util import chaos
    cfg, _ = tiny_f32
    prompt = _prompt(20, cfg.vocab_size, seed=3)
    ref = _make_replica(tiny_f32, "sd-ref")
    (want,) = ref.engine.generate([prompt], max_new_tokens=3)
    tel = _tel()
    router = DisaggRouter([_make_replica(tiny_f32, "sd-p")],
                          [_make_replica(tiny_f32, "sd-d")],
                          cfg=_fcfg(), telemetry=tel)
    plan = chaos.install_faults("serve.handoff@1..2:delay=0.05")
    out = list(router.remote({"tokens": prompt, "max_new_tokens": 3}))
    chaos.clear_faults()
    assert out == want
    assert plan.slowdown_s("serve.handoff") == pytest.approx(0.1)
    assert tel.summary()["handoff_s_max"] >= 0.1
    assert router.quiesce() and router.leak_free()


def test_prefill_death_after_export_acceptance(tiny_f32):
    """Chaos acceptance, prefill side: the prefill replica dies on its
    SECOND tick — after its first tick's requests were exported and
    handed off.  Already-handed-off streams keep decoding untouched
    (the ownership transferred — no retry burned); streams still bound
    to the corpse re-prefill on the surviving prefill replica; held
    exports are reaped with the corpse; the prefill reconciler
    restores the pool with zero recompiles."""
    from ray_tpu.fleet import DisaggRouter, Reconciler, RUNNING
    from ray_tpu.util import chaos
    from ray_tpu.inference import PrefixIndex
    cfg, _ = tiny_f32
    prompts1 = [_prompt(18 + 4 * i, cfg.vocab_size, seed=40 + i)
                for i in range(4)]
    ref = _make_replica(tiny_f32, "pk-ref")
    expected1 = ref.engine.generate(prompts1, max_new_tokens=4)

    fcfg = _fcfg(retries=2)
    router = DisaggRouter(
        [_make_replica(tiny_f32, "pk-p0"),
         _make_replica(tiny_f32, "pk-p1")],
        [_make_replica(tiny_f32, "pk-d0"),
         _make_replica(tiny_f32, "pk-d1")],
        cfg=fcfg, rng_seed=0, telemetry=_tel())
    rec = Reconciler(router.pool_view("prefill"),
                     lambda rid: _make_replica(tiny_f32, f"pk-f{rid}"),
                     target=2, cfg=fcfg)
    # wave 1: submit and poll once — a first-token-stop tick prefills
    # and exports EVERYTHING waiting, so after one poll every wave-1
    # stream has been handed off and is mid-decode on the decode pool
    wave1 = [router.remote({"tokens": p, "max_new_tokens": 4})
             for p in prompts1]
    router.poll()
    assert all(s.phase == "decode" and not s.done for s in wave1)
    # wave 2 extends prompts the victim itself prefilled (their prefix
    # pages are registered only in ITS cache), so prefix affinity
    # routes every wave-2 stream to pk-p0 deterministically
    victim = router.replicas("prefill")[0]
    assert victim.id == "pk-p0"
    mine = [p for p in prompts1
            if all(h in victim.prefix_digest()
                   for h in PrefixIndex.chain_hashes(p, 16))]
    assert mine            # pow-2 over 4 streams reached both replicas
    prompts2 = [list(p) + _prompt(3, cfg.vocab_size, seed=90 + j)
                for j, p in enumerate(mine)]
    expected2 = ref.engine.generate(prompts2, max_new_tokens=4)
    # targeted kill: an armed FAULT on the per-replica tick site kills
    # exactly pk-p0 on its next tick — i.e. after its wave-1 exports
    # left (hit counters start at the install, so @1 IS that tick,
    # which wave 2's arrival brings)
    assert victim.engine.ticks >= 1      # its exports already happened
    plan = chaos.install_faults("serve.tick[pk-p0]@1")
    wave2 = [router.remote({"tokens": p, "max_new_tokens": 4})
             for p in prompts2]
    assert all(s.replica_id == "pk-p0" for s in wave2)
    streams = wave1 + wave2
    outs = [list(s) for s in streams]
    chaos.clear_faults()
    assert plan.fired and plan.fired[0][0] == "serve.tick[pk-p0]"
    for out, want in zip(outs, expected1 + expected2):
        assert out == want
    assert all(s.done and s.error is None for s in streams)
    # ownership transferred before death: every handed-off wave-1
    # stream finished WITHOUT a failover — the corpse's death only
    # re-routed the streams still bound to it
    assert all(s.retries == 0 for s in wave1)
    assert any(s.retries > 0 for s in wave2)
    (corpse,) = [r for r in router.replicas() if not r.alive]
    assert corpse.id == "pk-p0" and corpse.reaped
    assert corpse.engine.stats()["held"] == 0    # exports not orphaned
    assert corpse.leak_free()
    deadline = time.monotonic() + 5
    while time.monotonic() < deadline:
        rec.reconcile()
        if sorted(rec.states().values()).count(RUNNING) == 2:
            break
        time.sleep(0.01)
    assert sorted(rec.states().values()).count(RUNNING) == 2
    assert len(router.replicas("prefill")) == 2
    for r in router.replicas():
        assert r.engine.stats()["compiles"] == {
            "prefill": 0, "prefill_cached": 0, "decode": 0}
    assert router.quiesce() and router.leak_free()


def test_decode_death_after_import_acceptance(tiny_f32):
    """Chaos acceptance, decode side: a decode replica dies AFTER
    imports installed and began decoding (2nd tick).  Its streams
    re-prefill from prompt + every emitted token on the prefill pool
    and hand off again — continuations exactly equal the unfailed run
    (at-most-once structural), the corpse reaps clean, nothing
    leaks."""
    from ray_tpu.fleet import DisaggRouter
    from ray_tpu.util import chaos
    cfg, _ = tiny_f32
    prompts = [_prompt(18 + 4 * i, cfg.vocab_size, seed=50 + i)
               for i in range(5)]
    ref = _make_replica(tiny_f32, "dd-ref")
    expected = ref.engine.generate(prompts, max_new_tokens=5)

    tel = _tel()
    router = DisaggRouter(
        [_make_replica(tiny_f32, "dd-p0")],
        [_make_replica(tiny_f32, "dd-d0"),
         _make_replica(tiny_f32, "dd-d1")],
        cfg=_fcfg(retries=2), rng_seed=0, telemetry=tel)
    plan = chaos.install_faults("serve.tick[dd-d0]@2")
    streams = [router.remote({"tokens": p, "max_new_tokens": 5})
               for p in prompts]
    outs = [list(s) for s in streams]
    chaos.clear_faults()
    assert plan.fired == [("serve.tick[dd-d0]", 2)]
    for out, want in zip(outs, expected):
        assert out == want
    assert all(s.done and s.error is None for s in streams)
    assert any(s.retries > 0 for s in streams)
    (corpse,) = [r for r in router.replicas() if not r.alive]
    assert corpse.id == "dd-d0" and corpse.reaped and corpse.leak_free()
    # the failed-over streams re-prefilled AND re-handed-off: more
    # handoffs than streams
    assert tel.summary()["handoffs"] > len(prompts)
    assert router.quiesce() and router.leak_free()


def test_failover_budget_and_empty_pools_typed(tiny_f32):
    """Exhausted failover budget and an empty healthy pool both
    surface the typed ReplicaUnavailableError on the stream — never a
    hang (the zero-hung-streams contract, disagg edition)."""
    from ray_tpu.fleet import DisaggRouter, ReplicaUnavailableError
    from ray_tpu.util import chaos
    cfg, _ = tiny_f32
    router = DisaggRouter([_make_replica(tiny_f32, "fb-p")],
                          [_make_replica(tiny_f32, "fb-d")],
                          cfg=_fcfg(retries=1), rng_seed=0,
                          telemetry=_tel())
    s = router.remote({"tokens": _prompt(8, cfg.vocab_size),
                       "max_new_tokens": 4})
    chaos.install_faults("serve.replica@1,serve.replica@2")
    with pytest.raises(ReplicaUnavailableError):
        list(s)
    chaos.clear_faults()
    assert s.done
    assert all(r.leak_free() for r in router.replicas()
               if not r.alive)


def test_partial_residency_strips_resident_pages(tiny_f32):
    """A handoff to a target already holding a leading run of the
    context pages ships ONLY what is missing: the second wave's
    shared-prefix handoff moves just the private tail page, not the
    resident prefix — the wire form of the r12 prefix cache — and the
    continuation stays exact."""
    from ray_tpu.fleet import DisaggRouter
    cfg, _ = tiny_f32
    shared = _prompt(32, cfg.vocab_size, seed=13)      # 2 full pages
    p1 = shared + _prompt(8, cfg.vocab_size, seed=70)  # 3 pages total
    p2 = shared + _prompt(9, cfg.vocab_size, seed=71)  # 3 pages total
    ref = _make_replica(tiny_f32, "ps-ref")
    expected = ref.engine.generate([p1, p2], max_new_tokens=4)

    tel = _tel()
    router = DisaggRouter([_make_replica(tiny_f32, "ps-p")],
                          [_make_replica(tiny_f32, "ps-d")],
                          cfg=_fcfg(), rng_seed=0, telemetry=tel)
    out1 = list(router.remote({"tokens": p1, "max_new_tokens": 4}))
    out2 = list(router.remote({"tokens": p2, "max_new_tokens": 4}))
    assert [out1, out2] == expected
    summ = tel.summary()
    # wave 1 shipped all 3 pages cold; wave 2 found the 2 shared
    # prefix pages resident and shipped only its private tail page
    assert summ["handoffs"] == 2 and summ["handoffs_skipped"] == 0
    assert summ["handoff_pages_total"] == 3 + 1
    per_page = summ["handoff_bytes_total"] // 4
    assert summ["handoff_bytes_total"] == per_page * 4
    assert router.quiesce() and router.leak_free()
    assert router.store.in_flight == 0


def test_disagg_deadline_is_one_budget_across_legs(tiny_f32):
    """The stream's total deadline is ONE budget spanning legs: the
    decode-side request receives the remaining budget (not a fresh
    clock — a disagg request must not get ~2x the co-located budget),
    and a failover re-admission disables the engine-side TTFT deadline
    outright (the stream's real first token was already delivered; the
    engine DEFAULT must not re-arm and shed it)."""
    from ray_tpu.fleet import DisaggRouter
    cfg, _ = tiny_f32
    prompt = _prompt(20, cfg.vocab_size, seed=6)
    pre = _make_replica(tiny_f32, "bd-p", ttft_deadline=30.0)
    dec = [_make_replica(tiny_f32, "bd-d0"),
           _make_replica(tiny_f32, "bd-d1")]
    router = DisaggRouter([pre], dec, cfg=_fcfg(), rng_seed=0,
                          telemetry=_tel())
    s = router.remote({"tokens": prompt, "max_new_tokens": 6,
                       "deadline_s": 100.0})
    s.submitted_ts -= 60.0               # 60 s already "spent"
    router.poll()                        # prefill + handoff + install
    assert s.phase == "decode"
    drep = next(r for r in dec if r.id == s.replica_id)
    req = drep.engine._requests[s.rid]
    assert req.deadline_s == pytest.approx(40.0, abs=2.0)
    # decode replica dies: the failover re-admission on the prefill
    # pool must carry ttft_deadline_s=None (engine default DISABLED,
    # despite the replica's 30 s default) and the still-shrinking
    # total budget
    drep.alive = False
    router.poll()
    assert s.phase == "prefill" and s.retries == 1
    req2 = pre.engine._requests[s.rid]
    assert req2.ttft_deadline_s is None
    assert req2.deadline_s == pytest.approx(40.0, abs=2.0)
    ref = _make_replica(tiny_f32, "bd-ref")
    (want,) = ref.engine.generate([prompt], max_new_tokens=6)
    assert list(s) == want
    assert router.quiesce() and router.leak_free()


def test_handoff_store_accounting(tiny_f32):
    """The in-process HandoffStore tracks in-flight objects and put
    bytes (the leak-audit half of 'orphaned exports cannot leak'), and
    drop is idempotent."""
    from ray_tpu.fleet import HandoffStore
    from ray_tpu.inference import KVHandoff
    store = HandoffStore(use_object_store=False)
    h = KVHandoff(context=[1, 2, 3], page_size=16, kv_dtype="model",
                  dtype="float32", chain_hashes=[], next_token=7,
                  next_logprob=-0.5, k=np.zeros((2, 1, 16, 4, 8),
                                                np.float32),
                  v=np.zeros((2, 1, 16, 4, 8), np.float32))
    handle = store.put(h)
    assert store.in_flight == 1 and store.bytes_put == h.nbytes
    assert store.get(handle) is h
    store.drop(handle)
    store.drop(handle)
    assert store.in_flight == 0 and store.puts == 1
