"""Multi-tenant LoRA serving (r25): factor math and the merged-weights
oracle, the versioned AdapterStore and per-engine LRU registry, the
engine parity battery (adapter-on output == merged weights, across
int8 KV, prefix hits and mixed co-batching), compile
counters frozen across hot-load and republish, chaos on the load path,
adapter-only RL publish round-trip, and the two-replica fleet
acceptance run."""

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


def _lcfg(**over):
    from ray_tpu.adapters import LoraConfig
    base = dict(enabled=True, rank=4, scale=0.5, cache_slots=3)
    base.update(over)
    return LoraConfig(**base)


@pytest.fixture(scope="module")
def adapters(tiny_f32):
    """Two deliberately non-identity adapters (random B)."""
    import jax

    from ray_tpu.adapters import init_adapter
    cfg, _ = tiny_f32
    lcfg = _lcfg()
    return {
        "t1": init_adapter(cfg, lcfg, jax.random.PRNGKey(11),
                           random_b=True),
        "t2": init_adapter(cfg, lcfg, jax.random.PRNGKey(22),
                           random_b=True),
    }


def _store_with(adapters, ids=("t1", "t2")):
    from ray_tpu.adapters import AdapterStore
    store = AdapterStore(use_object_store=False)
    for mid in ids:
        store.put(mid, adapters[mid], scale=0.5)
    return store


# engines here share one executable cache (same tiny-f32 geometry ->
# same AOT executables across tests; lora engines key separately via
# the exec key's lora component but still share among themselves)
import test_inference as _ti  # noqa: E402

_EXEC_CACHE = _ti._EXEC_CACHE
_KW = {"slots": 3, "page_size": 16, "buckets": (16, 32, 64),
       "telemetry": False, "executable_cache": _EXEC_CACHE}


def _engine(tiny, **over):
    from ray_tpu.inference import InferenceEngine
    cfg, params = tiny
    kw = dict(_KW)
    kw.update(over)
    params = kw.pop("params", params)
    return InferenceEngine(cfg, params, **kw)


def _merged(tiny, adapter, scale=0.5):
    from ray_tpu.adapters import merge_adapter
    cfg, params = tiny
    return merge_adapter(params, adapter, cfg, scale=scale)


def _greedy(model_id=None, **over):
    from ray_tpu.inference import SamplingParams
    return SamplingParams(temperature=0.0, model_id=model_id, **over)


def _prompt(n, vocab, seed=0):
    return list(np.random.RandomState(seed).randint(1, vocab, size=n))


# ------------------------------------------------------------ factor math
def test_fresh_adapter_is_identity_and_merge_oracle(tiny_f32):
    """Standard LoRA init (B = 0) is an exact no-op: merged weights
    equal base weights, and the single-adapter forward equals the
    plain forward.  A random-B adapter changes the output."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.adapters import init_adapter, merge_adapter
    from ray_tpu.models import gpt as gpt_mod
    cfg, params = tiny_f32
    lcfg = _lcfg()
    fresh = init_adapter(cfg, lcfg, jax.random.PRNGKey(1))
    merged = merge_adapter(params, fresh, cfg, scale=0.5)
    for k in params["layers"]:
        np.testing.assert_array_equal(np.asarray(merged["layers"][k]),
                                      np.asarray(params["layers"][k]))
    tokens = jnp.asarray([_prompt(12, cfg.vocab_size)], jnp.int32)
    base_out, _ = gpt_mod.forward(params, tokens, cfg)
    lora_out, _ = gpt_mod.forward(
        params, tokens, cfg, lora={**fresh, "scale": 0.5})
    np.testing.assert_array_equal(np.asarray(base_out),
                                  np.asarray(lora_out))

    hot = init_adapter(cfg, lcfg, jax.random.PRNGKey(2), random_b=True)
    hot_merged = merge_adapter(params, hot, cfg, scale=0.5)
    ref, _ = gpt_mod.forward(hot_merged, tokens, cfg)
    via_lora, _ = gpt_mod.forward(params, tokens, cfg,
                                  lora={**hot, "scale": 0.5})
    assert not np.allclose(np.asarray(ref), np.asarray(base_out))
    np.testing.assert_allclose(np.asarray(via_lora), np.asarray(ref),
                               atol=2e-4, rtol=2e-4)


def test_bank_install_clear_and_slot_zero_guard(tiny_f32, adapters):
    from ray_tpu.adapters import bank_install, bank_zeros
    from ray_tpu.adapters.lora import bank_clear
    cfg, _ = tiny_f32
    lcfg = _lcfg()
    bank = bank_zeros(cfg, lcfg)
    assert bank["scale"].shape == (lcfg.bank_slots,)
    bank = bank_install(bank, 1, adapters["t1"], scale=0.5)
    assert float(bank["scale"][1]) == 0.5
    assert float(np.abs(np.asarray(bank["wq_a"][1])).max()) > 0
    # slot 0 stays the identity
    assert float(np.abs(np.asarray(bank["wq_a"][0])).max()) == 0
    assert float(bank["scale"][0]) == 0.0
    with pytest.raises(ValueError, match="identity"):
        bank_install(bank, 0, adapters["t1"])
    bank = bank_clear(bank, 1)
    assert float(np.abs(np.asarray(bank["wq_a"][1])).max()) == 0


def test_salt_bytes_distinct_per_tenant_and_version():
    from ray_tpu.adapters import salt_bytes
    assert salt_bytes(None, 1) == b"" and salt_bytes("", 1) == b""
    s11, s12 = salt_bytes("t1", 1), salt_bytes("t1", 2)
    s21 = salt_bytes("t2", 1)
    assert len({s11, s12, s21}) == 3


def test_moe_configs_are_rejected(tiny_f32):
    import dataclasses

    import jax

    from ray_tpu.adapters import init_adapter
    cfg, _ = tiny_f32
    moe_cfg = dataclasses.replace(cfg, n_experts=4)
    with pytest.raises(ValueError, match="MoE|expert"):
        init_adapter(moe_cfg, _lcfg(), jax.random.PRNGKey(0))


# --------------------------------------------------------- store/registry
def test_adapter_store_versions_checkout_and_misses(adapters):
    from ray_tpu.adapters import AdapterStore, AdapterUnavailableError
    store = AdapterStore(use_object_store=False)
    assert "t1" not in store
    v1 = store.put("t1", adapters["t1"], scale=0.5)
    v2 = store.put("t1", adapters["t1"], scale=0.7)
    assert (v1, v2) == (1, 2)
    assert store.latest_version("t1") == 2
    got, payload, scale = store.checkout("t1")
    assert got == 2 and scale == 0.7
    assert store.in_flight == 1
    store.checkin()
    assert store.in_flight == 0
    got, _, scale = store.get("t1", version=1)     # pinned fetch
    assert got == 1 and scale == 0.5
    with pytest.raises(AdapterUnavailableError, match="never published"):
        store.get("nope")
    with pytest.raises(AdapterUnavailableError, match="not in store"):
        store.get("t1", version=9)
    assert store.salt_for("t1") != store.salt_for("t1", version=1)
    assert store.salt_for("nope") == b""
    s = store.stats()
    assert s["in_flight"] == 0 and s["bytes_published"] > 0


def test_adapter_registry_lru_eviction_and_pin_discipline():
    from ray_tpu.adapters import AdapterRegistry, AdapterUnavailableError
    reg = AdapterRegistry(cache_slots=2)
    s1, ev = reg.place("a", 1)
    s2, _ = reg.place("b", 1)
    assert {s1, s2} == {1, 2} and ev is None
    reg.touch("a", 1)                    # LRU order is now b, a
    s3, evicted = reg.place("c", 1)
    assert evicted == "b" and s3 == s2   # b's row is recycled
    assert set(reg.resident_ids) == {"a", "c"}
    # an unpinned version bump retires the stale row and recycles it
    slot_a = reg.lookup("a")[0]
    same, ev = reg.place("a", 2)
    assert same == slot_a and ev is None and reg.lookup("a") == (slot_a, 2)
    assert reg.lookup("a", 1) is None    # v1 retired with the bump
    # everything pinned -> typed error, never a hang
    reg.pin("a", 2)
    reg.pin("c", 1)
    with pytest.raises(AdapterUnavailableError, match="pinned"):
        reg.place("d", 1)
    reg.unpin("a", 2)
    slot_d, evicted = reg.place("d", 1)
    assert evicted == "a" and slot_d == slot_a
    reg.unpin("c", 1)
    assert reg.pinned_total == 0
    with pytest.raises(RuntimeError, match="without a pin"):
        reg.unpin("c", 1)


def test_adapter_registry_pinned_republish_gets_fresh_slot():
    """A version republish while the old version is pinned by
    in-flight requests must NOT rewrite the pinned row: the new
    version lands in a different slot, both stay addressable by exact
    version, and the stale row only becomes evictable once its pins
    drain."""
    from ray_tpu.adapters import AdapterRegistry, AdapterUnavailableError
    reg = AdapterRegistry(cache_slots=2)
    s_old, _ = reg.place("a", 1)
    reg.pin("a", 1)
    s_new, ev = reg.place("a", 2)
    assert s_new != s_old and ev is None
    assert reg.lookup("a", 1) == (s_old, 1)   # pinned factors intact
    assert reg.lookup("a", 2) == (s_new, 2)
    assert reg.lookup("a") == (s_new, 2)      # unversioned -> newest
    # the pinned row can never be re-placed in place either
    with pytest.raises(AdapterUnavailableError, match="pinned"):
        reg.place("a", 1)
    # pins drained: v1 is ordinary LRU prey, v2 survives
    reg.unpin("a", 1)
    s_b, evicted = reg.place("b", 1)
    assert s_b == s_old and evicted is None   # "a" still resident (v2)
    assert reg.lookup("a") == (s_new, 2)
    assert reg.pinned_total == 0


# --------------------------------------------------- engine parity battery
def test_engine_adapter_parity_vs_merged_weights(tiny_f32, adapters):
    """THE serving oracle: an engine decoding under a banked adapter
    must emit the exact tokens of an engine serving the merged
    weights — greedy and sampled — while base traffic on the same
    engine stays bit-identical to a plain engine."""
    from ray_tpu.inference import SamplingParams
    eng = _engine(tiny_f32, lora=_lcfg())
    eng.load_adapter("t1", adapters["t1"], scale=0.5)
    merged_eng = _engine(tiny_f32, params=_merged(tiny_f32,
                                                  adapters["t1"]))
    plain_eng = _engine(tiny_f32)
    cfg, _ = tiny_f32
    p = _prompt(9, cfg.vocab_size, seed=1)

    assert (eng.generate([p], 10, _greedy("t1"))
            == merged_eng.generate([p], 10, _greedy()))
    # sampled path: same (seed, step) chain -> same tokens
    sp = SamplingParams(temperature=0.7, seed=5, model_id="t1")
    sp_ref = SamplingParams(temperature=0.7, seed=5)
    assert eng.generate([p], 10, sp) == merged_eng.generate([p], 10,
                                                            sp_ref)
    # the zero-adapter identity path
    assert (eng.generate([p], 10, _greedy())
            == plain_eng.generate([p], 10, _greedy()))
    assert eng.leak_free() and merged_eng.leak_free()


def test_mixed_cobatch_solo_equals_batched(tiny_f32, adapters):
    """Three tenants (two adapters + base) co-batched on ONE engine:
    every stream equals its solo merged-weights run — the grouped
    gather keeps co-batched tenants from contaminating each other."""
    eng = _engine(tiny_f32, lora=_lcfg())
    eng.load_adapter("t1", adapters["t1"], scale=0.5)
    eng.load_adapter("t2", adapters["t2"], scale=0.5)
    cfg, _ = tiny_f32
    prompts = [_prompt(7, cfg.vocab_size, seed=s) for s in (1, 2, 3)]
    tenants = ["t1", "t2", None]

    solo = [_engine(tiny_f32, params=_merged(tiny_f32, adapters[t])
                    if t else tiny_f32[1]).generate([p], 8, _greedy())[0]
            for p, t in zip(prompts, tenants)]

    rids = [eng.submit(p, 8, _greedy(t))
            for p, t in zip(prompts, tenants)]
    out = {r: [] for r in rids}
    while eng.has_work():
        for (rid, tok, _d) in eng.step():
            out[rid].append(tok)
    assert [out[r] for r in rids] == solo
    assert eng.leak_free()


def test_adapter_parity_int8_kv(tiny_f32, adapters):
    eng = _engine(tiny_f32, lora=_lcfg(), kv_dtype="int8")
    eng.load_adapter("t1", adapters["t1"], scale=0.5)
    ref = _engine(tiny_f32, params=_merged(tiny_f32, adapters["t1"]),
                  kv_dtype="int8")
    cfg, _ = tiny_f32
    p = _prompt(8, cfg.vocab_size, seed=4)
    assert eng.generate([p], 8, _greedy("t1")) == ref.generate(
        [p], 8, _greedy())


def test_adapter_prefix_reuse_and_salt_non_aliasing(tiny_f32, adapters):
    """Same (tenant, prompt) twice -> the second run prefix-hits the
    salted chain AND still equals the merged oracle; base traffic over
    the identical tokens must not alias the tenant's entries (the
    chain roots differ by salt)."""
    store = _store_with(adapters)
    eng = _engine(tiny_f32, lora=_lcfg(), adapter_store=store,
                  prefix=True)
    cfg, _ = tiny_f32
    p = _prompt(37, cfg.vocab_size, seed=6)     # 2 hit-eligible pages
    ref = _engine(tiny_f32, params=_merged(tiny_f32, adapters["t1"]),
                  prefix=True)
    expect = ref.generate([p], 6, _greedy())
    assert eng.generate([p], 6, _greedy("t1")) == expect
    hits0 = eng.stats()["prefix"]["hit_pages"]
    assert eng.generate([p], 6, _greedy("t1")) == expect
    hits1 = eng.stats()["prefix"]["hit_pages"]
    assert hits1 >= hits0 + 2        # the tenant's own chain hit
    # base traffic on the same tokens: no cross-tenant prefix reuse
    # (salted chains can't match the unsalted root), same base output
    plain = _engine(tiny_f32, prefix=True)
    assert (eng.generate([p], 6, _greedy())
            == plain.generate([p], 6, _greedy()))
    assert eng.stats()["prefix"]["hit_pages"] == hits1
    assert eng.leak_free()


def test_hot_load_and_republish_keep_compiles_frozen(tiny_f32, adapters):
    """The tentpole invariant: adapters are call args, so tenant
    hot-load, version republish and eviction never touch the compile
    cache."""
    store = _store_with(adapters, ids=("t1",))
    eng = _engine(tiny_f32, lora=_lcfg(), adapter_store=store)
    cfg, _ = tiny_f32
    p = _prompt(8, cfg.vocab_size, seed=7)
    eng.generate([p], 6, _greedy("t1"))
    frozen = dict(eng.compile_counts)
    # hot-load a second tenant mid-traffic
    rid_live = eng.submit(p, 12, _greedy("t1"))
    store.put("t2", adapters["t2"], scale=0.5)
    out2 = []
    rid2 = eng.submit(_prompt(8, cfg.vocab_size, seed=8), 6,
                      _greedy("t2"))
    while eng.has_work():
        for (rid, tok, _d) in eng.step():
            if rid == rid2:
                out2.append(tok)
    assert len(out2) == 6
    # republish t1 -> new version resolves on the next request
    store.put("t1", adapters["t2"], scale=0.5)   # v2 = t2's factors
    ref = _engine(tiny_f32, params=_merged(tiny_f32, adapters["t2"]))
    assert eng.generate([p], 6, _greedy("t1")) == ref.generate(
        [p], 6, _greedy())
    assert dict(eng.compile_counts) == frozen, (
        "adapter lifecycle must never recompile")
    assert eng.leak_free()
    del rid_live


def test_republish_mid_decode_keeps_pinned_version_factors(tiny_f32,
                                                           adapters):
    """A request decoding under v1 when the tenant republishes v2 —
    with a co-batched latest-tracking request resolving v2 while the
    v1 pin is live — must finish under v1's EXACT factors: the new
    version lands in a fresh bank row, never over the pinned one."""
    store = _store_with(adapters, ids=("t1",))   # v1 = t1's factors
    eng = _engine(tiny_f32, lora=_lcfg(), adapter_store=store)
    cfg, _ = tiny_f32
    p1 = _prompt(8, cfg.vocab_size, seed=13)
    p2 = _prompt(8, cfg.vocab_size, seed=14)
    expect_v1 = _engine(tiny_f32, params=_merged(
        tiny_f32, adapters["t1"])).generate([p1], 8, _greedy())[0]
    expect_v2 = _engine(tiny_f32, params=_merged(
        tiny_f32, adapters["t2"])).generate([p2], 4, _greedy())[0]

    rid1 = eng.submit(p1, 8, _greedy("t1"))
    out = {rid1: []}
    republished = False
    while eng.has_work():
        for (rid, tok, _d) in eng.step():
            out[rid].append(tok)
        if not republished:
            republished = True
            store.put("t1", adapters["t2"], scale=0.5)  # v2 factors
            rid2 = eng.submit(p2, 4, _greedy("t1"))     # tracks v2
            out[rid2] = []
    assert out[rid1] == expect_v1     # v1 pin survived the republish
    assert out[rid2] == expect_v2     # v2 resolved alongside, fresh row
    assert eng.leak_free()


def test_bad_geometry_publish_is_typed_not_fatal(tiny_f32, adapters):
    """A tenant publishing factors of the wrong rank/targets must
    retire only that tenant's request with the typed error — the
    replica's step loop and its other tenants keep serving."""
    import jax

    from ray_tpu.adapters import AdapterUnavailableError, init_adapter
    cfg, _ = tiny_f32
    store = _store_with(adapters, ids=("t1",))
    store.put("bad", init_adapter(cfg, _lcfg(rank=7),
                                  jax.random.PRNGKey(9), random_b=True))
    eng = _engine(tiny_f32, lora=_lcfg(), adapter_store=store)
    p = _prompt(8, cfg.vocab_size, seed=15)
    rid_bad = eng.submit(p, 4, _greedy("bad"))
    rid_ok = eng.submit(p, 4, _greedy("t1"))
    got_ok, bad_err = [], None
    while eng.has_work():
        for ev in eng.step():
            rid, tok, _d = ev
            if rid == rid_bad and ev.error is not None:
                bad_err = ev.error
            elif rid == rid_ok and ev.error is None:
                got_ok.append(tok)
    assert isinstance(bad_err, AdapterUnavailableError)
    assert "do not fit" in str(bad_err)
    assert len(got_ok) == 4
    assert eng.leak_free()
    assert store.stats()["in_flight"] == 0
    # the direct-install path is gated by the same check
    with pytest.raises(AdapterUnavailableError, match="do not fit"):
        eng.load_adapter("bad2", init_adapter(
            cfg, _lcfg(rank=7), jax.random.PRNGKey(10)))


def test_submit_rejections_are_typed(tiny_f32, adapters):
    from ray_tpu.adapters import AdapterUnavailableError
    cfg, _ = tiny_f32
    p = _prompt(6, cfg.vocab_size)
    plain = _engine(tiny_f32)
    with pytest.raises(AdapterUnavailableError, match="without adapter"):
        plain.submit(p, 4, _greedy("t1"))
    eng = _engine(tiny_f32, lora=_lcfg(),
                  adapter_store=_store_with(adapters, ids=("t1",)))
    with pytest.raises(AdapterUnavailableError, match="never published"):
        eng.submit(p, 4, _greedy("ghost"))
    assert not eng.has_work() and eng.leak_free()


def test_chaos_adapter_load_fault_and_delay(tiny_f32, adapters):
    """An injected ``serve.adapter_load`` fault retires the waiting
    request with the typed error — resident tenants keep decoding,
    nothing hangs or leaks; the ``:delay=`` flavor completes."""
    from ray_tpu.adapters import AdapterUnavailableError
    from ray_tpu.util import chaos
    store = _store_with(adapters)
    eng = _engine(tiny_f32, lora=_lcfg(), adapter_store=store)
    cfg, _ = tiny_f32
    p = _prompt(8, cfg.vocab_size, seed=9)
    eng.generate([p], 4, _greedy("t1"))          # t1 now resident
    plan = chaos.install_faults("serve.adapter_load@1")
    rid_ok = eng.submit(p, 5, _greedy("t1"))     # cache hit: no fault leg
    rid_bad = eng.submit(_prompt(8, cfg.vocab_size, seed=10), 5,
                         _greedy("t2"))          # cold load -> fault
    got_ok, bad_err = [], None
    while eng.has_work():
        for ev in eng.step():
            rid, tok, _d = ev
            if rid == rid_bad and ev.error is not None:
                bad_err = ev.error
            elif rid == rid_ok and ev.error is None:
                got_ok.append(tok)
    assert isinstance(bad_err, AdapterUnavailableError)
    assert len(got_ok) == 5                      # the resident tenant fed
    assert plan.fired == [("serve.adapter_load", 1)]
    chaos.clear_faults()
    # delay flavor: slow load, not a failure
    chaos.install_faults("serve.adapter_load@1:delay=0.05")
    assert eng.generate([p], 4, _greedy("t2"))   # completes
    chaos.clear_faults()
    assert eng.leak_free()
    assert store.stats()["in_flight"] == 0
    assert eng.adapters.pinned_total == 0


def test_leak_audit_covers_adapter_pins_and_store(tiny_f32, adapters):
    """leak_free() must catch a pin/in_flight imbalance, not just
    slot/page leaks."""
    store = _store_with(adapters, ids=("t1",))
    eng = _engine(tiny_f32, lora=_lcfg(), adapter_store=store)
    cfg, _ = tiny_f32
    eng.generate([_prompt(6, cfg.vocab_size)], 4, _greedy("t1"))
    assert eng.leak_free()
    eng.adapters.pin("t1", 1)                # orphan pin
    assert not eng.leak_free()
    eng.adapters.unpin("t1", 1)
    assert eng.leak_free()
    store.checkout("t1")                     # un-checked-in fetch
    assert not eng.leak_free()
    store.checkin()
    assert eng.leak_free()


# --------------------------------------------------------- adapter-only RL
@pytest.mark.slow   # r25 --durations: ~11s — two supervised builders
                    # plus an RL builder jit at the tiny shape; the
                    # publish->serve seam stays tier-1 in
                    # test_rl_published_adapter_serves_merged_parity
def test_adapter_only_training_identity_grads_and_publish(tiny_f32):
    """build_gpt_train(lora=...): step 0 is exactly the base model,
    training moves only adapter params, and the RL learner's publish
    payload is adapter-sized."""
    import jax

    from ray_tpu.adapters import AdapterStore, adapter_nbytes
    from ray_tpu.models import training
    from ray_tpu.parallel.mesh import make_mesh
    from ray_tpu.rl.learner import InProcessLearner
    cfg, base = tiny_f32
    mesh = make_mesh(dp=1, devices=jax.devices()[:1])
    lcfg = _lcfg()
    fns = training.build_gpt_train(cfg, mesh, lora=lcfg,
                                   base_params=base, telemetry=False)
    full = training.build_gpt_train(cfg, mesh, telemetry=False)
    st = fns["init_fn"](jax.random.PRNGKey(1))
    assert all(k.endswith(("_a", "_b")) for k in st.params)
    rng = np.random.default_rng(0)
    batch = {
        "tokens": rng.integers(0, cfg.vocab_size, (4, 32), dtype=np.int64)
        .astype(np.int32),
        "targets": rng.integers(0, cfg.vocab_size, (4, 32),
                                dtype=np.int64).astype(np.int32)}
    assert np.isclose(float(fns["loss_fn"](st.params, batch)),
                      float(full["loss_fn"](base, batch)), atol=1e-5)
    losses = []
    for _ in range(4):
        st, m = fns["step_fn"](st, batch)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0]

    # base_params is mandatory in adapter mode
    with pytest.raises(ValueError, match="base_params"):
        training.build_gpt_train(cfg, mesh, lora=lcfg, telemetry=False)

    # RL learner round-trip: publish is adapter-sized and versioned
    learner = InProcessLearner(cfg, lora=lcfg, base_params=base, seed=3)
    rl_batch = {
        "tokens": rng.integers(0, cfg.vocab_size, (4, 24), dtype=np.int64)
        .astype(np.int32),
        "targets": rng.integers(0, cfg.vocab_size, (4, 24),
                                dtype=np.int64).astype(np.int32),
        "rewards": rng.standard_normal(4).astype(np.float32)}
    learner.update(rl_batch)
    store = AdapterStore(use_object_store=False)
    assert learner.publish_adapter(store, "tenant-rl") == 1
    assert learner.publish_adapter(store, "tenant-rl") == 2
    nbytes = adapter_nbytes(learner.params_host())
    assert store.stats()["bytes_published"] == 2 * nbytes
    full_bytes = sum(np.asarray(v).nbytes
                     for v in jax.tree.leaves(base))
    assert nbytes < full_bytes / 10      # the publish-bytes win

    # a full-weights learner refuses adapter publication, typed
    plain = InProcessLearner(cfg, fns=training.build_gpt_rl_train(
        cfg, mesh))
    with pytest.raises(ValueError, match="WeightStore"):
        plain.publish_adapter(store, "x")


def test_rl_published_adapter_serves_merged_parity(tiny_f32):
    """The RL -> serve seam end-to-end: train adapter-only, publish to
    the store, decode under the tenant, match merged weights."""
    from ray_tpu.adapters import AdapterStore, merge_adapter
    from ray_tpu.rl.learner import InProcessLearner
    cfg, base = tiny_f32
    lcfg = _lcfg(scale=1.0)
    learner = InProcessLearner(cfg, lora=lcfg, base_params=base, seed=4)
    rng = np.random.default_rng(1)
    batch = {
        "tokens": rng.integers(0, cfg.vocab_size, (4, 24), dtype=np.int64)
        .astype(np.int32),
        "targets": rng.integers(0, cfg.vocab_size, (4, 24),
                                dtype=np.int64).astype(np.int32),
        "rewards": rng.standard_normal(4).astype(np.float32)}
    for _ in range(2):
        learner.update(batch)
    store = AdapterStore(use_object_store=False)
    learner.publish_adapter(store, "tenant-rl")

    eng = _engine(tiny_f32, lora=lcfg, adapter_store=store)
    p = _prompt(8, cfg.vocab_size, seed=12)
    out = eng.generate([p], 8, _greedy("tenant-rl"))
    _, host, scale = store.get("tenant-rl")
    ref = _engine(tiny_f32,
                  params=merge_adapter(base, host, cfg, scale=scale))
    assert out == ref.generate([p], 8, _greedy())
    assert eng.leak_free()


# --------------------------------------------------------------- fleet
def _fcfg(**over):
    from ray_tpu.fleet import FleetConfig
    base = dict(retries=2, affinity=True, affinity_cap=8,
                adapter_affinity=True, up_depth=4.0, ttft_slo=0.0,
                dwell=1.0, backoff=1.0, backoff_max=8.0, hedge=False)
    base.update(over)
    return FleetConfig(**base)


def _tel():
    from ray_tpu.telemetry.config import TelemetryConfig
    from ray_tpu.telemetry.fleet import FleetTelemetry
    return FleetTelemetry(config=TelemetryConfig(enabled=True))


def test_fleet_multitenant_acceptance(tiny_f32, adapters):
    """THE acceptance run: a two-replica fleet serving three tenants
    (two adapters + base) concurrently — per-tenant streams equal
    their solo merged-weights runs, a mid-traffic republish resolves
    without a recompile, and the full leak audit (slots, pages, pins,
    store in_flight) is clean after the drain."""
    from ray_tpu.fleet import EngineReplica, FleetRouter
    store = _store_with(adapters)
    reps = [EngineReplica(f"r{i}",
                          _engine(tiny_f32, lora=_lcfg(),
                                  adapter_store=store))
            for i in range(2)]
    router = FleetRouter(reps, cfg=_fcfg(), rng_seed=0,
                         telemetry=_tel())
    cfg, _ = tiny_f32
    prompts = [_prompt(8, cfg.vocab_size, seed=s) for s in (1, 2, 3)]
    tenants = ["t1", "t2", None]
    solo = [_engine(tiny_f32, params=_merged(tiny_f32, adapters[t])
                    if t else tiny_f32[1]).generate([p], 6, _greedy())[0]
            for p, t in zip(prompts, tenants)]

    streams = [router.remote({"tokens": p, "max_new_tokens": 6,
                              "model_id": t})
               for p, t in zip(prompts, tenants)]
    assert [s.result() for s in streams] == solo
    frozen = [dict(r.engine.compile_counts) for r in reps]

    # mid-traffic republish: new version, same compiled executables
    live = [router.remote({"tokens": p, "max_new_tokens": 6,
                           "model_id": t})
            for p, t in zip(prompts, tenants)]
    router.poll()        # live requests resolve + pin version 1
    store.put("t1", adapters["t2"], scale=0.5)
    assert [s.result() for s in live] == solo     # resolved pre-publish
    ref2 = _engine(tiny_f32, params=_merged(tiny_f32, adapters["t2"]))
    s = router.remote({"tokens": prompts[0], "max_new_tokens": 6,
                       "model_id": "t1"})
    assert s.result() == ref2.generate([prompts[0]], 6, _greedy())[0]
    assert [dict(r.engine.compile_counts) for r in reps] == frozen

    # drain: every audit clean
    for r in reps:
        while r.engine.has_work():
            r.step()
        assert r.leak_free()
        assert r.engine.adapters.pinned_total == 0
    assert store.stats()["in_flight"] == 0
    fstats = router.stats()
    assert fstats["adapter_store"]["models"] == 2


def test_router_adapter_affinity_vs_residency_blind(tiny_f32, adapters):
    """A tenant's request prefers the replica whose bank already holds
    its adapter (no store fetch, no install); the residency-blind arm
    (adapter_affinity=False) ignores residency entirely."""
    from ray_tpu.fleet import EngineReplica, FleetRouter
    store = _store_with(adapters)
    cold = EngineReplica("cold", _engine(tiny_f32, lora=_lcfg(),
                                         adapter_store=store))
    warm = EngineReplica("warm", _engine(tiny_f32, lora=_lcfg(),
                                         adapter_store=store))
    cfg, _ = tiny_f32
    p = _prompt(8, cfg.vocab_size, seed=3)
    # make t1 resident on warm only
    warm.engine.generate([p], 2, _greedy("t1"))
    assert "t1" in warm.adapter_digest()
    assert "t1" not in cold.adapter_digest()

    loads_before = warm.engine.adapters.loads
    router = FleetRouter([cold, warm], cfg=_fcfg(), rng_seed=0,
                         telemetry=_tel())
    for seed in range(4):
        s = router.remote({"tokens": _prompt(8, cfg.vocab_size,
                                             seed=seed),
                           "max_new_tokens": 2, "model_id": "t1"})
        s.result()
        assert s.replica_id == "warm"
    assert warm.engine.adapters.loads == loads_before  # zero refetches

    # blind arm: routing falls back to pow-2, cold gets traffic too
    blind = FleetRouter([cold, warm],
                        cfg=_fcfg(adapter_affinity=False),
                        rng_seed=0, telemetry=_tel())
    picks = set()
    for seed in range(6):
        s = blind.remote({"tokens": _prompt(8, cfg.vocab_size,
                                            seed=10 + seed),
                          "max_new_tokens": 2, "model_id": "t1"})
        s.result()
        picks.add(s.replica_id)
    assert "cold" in picks


def test_fleet_reroute_on_adapter_unavailable(tiny_f32, adapters):
    """A replica that rejects a tenant at submit (e.g. its bank is
    pinned full) is excluded for that request and the stream lands on
    a sibling — typed, never a hang."""
    from ray_tpu.adapters import AdapterUnavailableError
    from ray_tpu.fleet import EngineReplica, FleetRouter
    store = _store_with(adapters)
    good = EngineReplica("good", _engine(tiny_f32, lora=_lcfg(),
                                         adapter_store=store))
    bad = EngineReplica("bad", _engine(tiny_f32, lora=_lcfg(),
                                       adapter_store=store))
    orig = bad.submit

    def reject(prompt, **kw):
        sampling = kw.get("sampling")
        if sampling is not None and sampling.model_id:
            raise AdapterUnavailableError(sampling.model_id,
                                          "bank pinned full")
        return orig(prompt, **kw)

    bad.submit = reject
    tel = _tel()
    router = FleetRouter([bad, good], cfg=_fcfg(adapter_affinity=False),
                         rng_seed=0, telemetry=tel)
    cfg, _ = tiny_f32
    outs = []
    for seed in range(4):
        s = router.remote({"tokens": _prompt(8, cfg.vocab_size,
                                             seed=seed),
                           "max_new_tokens": 2, "model_id": "t1"})
        outs.append(s.result())
        assert s.replica_id == "good"
    assert all(len(o) == 2 for o in outs)
    assert tel.retries.get("adapter", 0) >= 1
