"""The latent-cache, routed model (``models/longcat.py``) on the serve
path, at a small size (hidden 64, 2 blocks, 4 heads, 16 routed + 8
identity experts, top 3), against the benchmark's plain reference
(``benchmark/reference/longcat.py``, which imports nothing of the
program) on seeded float32 weights."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import longcat as ref
from ray_tpu.inference import InferenceEngine, kv_cache as kvc
from ray_tpu.models import longcat
from ray_tpu.parallel import moe

PAGE = 16


def _config(cfg):
    """What the reference is told that no weight's shape says."""
    return {"moe_topk": cfg.moe_top_k,
            "routed_scaling_factor": cfg.routed_scale,
            "zero_expert_num": cfg.n_identity_experts,
            "rms_norm_eps": cfg.norm_eps, "rope_theta": cfg.rope_theta,
            "mla_scale_q_lora": cfg.mla_scale_q_lora,
            "mla_scale_kv_lora": cfg.mla_scale_kv_lora,
            "qk_rope_head_dim": cfg.qk_rope_head_dim,
            "model": {"kwargs": {"held_experts": list(cfg.held_experts)}}}


@pytest.fixture(scope="module")
def tiny():
    cfg = longcat.LongcatConfig.longcat_tiny(dtype=jnp.float32)
    return cfg, longcat.init_params(cfg, jax.random.PRNGKey(0))


def _engine(cfg, params, **kw):
    kw.setdefault("slots", 2)
    kw.setdefault("page_size", PAGE)
    kw.setdefault("buckets", (16, 32, 64))
    kw.setdefault("telemetry", True)
    return InferenceEngine(cfg, params, debug_logits=True, **kw)


def _rows(engine, prompt, n_new):
    """(the logits rows that produced each generated token, tokens)."""
    rid = engine.submit(prompt, max_new_tokens=n_new)
    generated = []
    while engine.has_work():
        generated += [int(ev[1]) for ev in engine.step() if ev[0] == rid]
    return np.stack(engine.logits_trace.pop(rid)), generated


def _prompt(n, seed):
    return np.random.RandomState(seed).randint(0, 512, size=n).tolist()


def test_prefill_then_decode_matches_the_reference_cold_and_on_a_hit(tiny):
    """Prefill, then decode through the latent pages, gives the logits of
    the reference's full forward over the same tokens: cold, and again
    behind pages another request registered (shared, never rewritten)."""
    cfg, params = tiny
    engine = _engine(cfg, params)
    shared = _prompt(2 * PAGE, seed=1)
    for k, tail in enumerate((_prompt(7, 2), _prompt(11, 3))):
        hits = engine.scheduler.prefix_hit_pages
        got, generated = _rows(engine, shared + tail, 6)
        full = np.asarray([shared + tail + generated[:-1]], np.int32)
        want = np.asarray(ref.logits_last(params, full, 6, _config(cfg))[0])
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
        assert engine.scheduler.prefix_hit_pages - hits == (2 if k else 0)
    assert engine.leak_free()
    counts = engine.telemetry.summary()["moe"]
    # two counts a pair, never a ratio: every row makes top_k picks
    assert counts["picks"] == cfg.moe_top_k * counts["rows"]
    assert counts["calls"] == counts["decode_calls"] + 2 * cfg.n_layers
    assert 0 < counts["held_picks"] < counts["picks"]
    assert 0 < counts["decode_experts_hit"] <= (
        len(cfg.held_experts) * counts["decode_calls"])
    # a decode's few rows fill one tile an expert they hit
    assert counts["decode_loop_trips"] == counts["decode_experts_hit"]
    assert counts["loop_trips"] >= counts["experts_hit"]


@pytest.mark.parametrize("fault", ["no_held", "wrong_held", "no_identity"])
def test_the_check_sees_a_fault_of_the_expert_layer(tiny, fault):
    """The comparison that decides ``correct`` (``harness/check.py``, at
    the family's own limit) against a reference that leaves the held
    experts' part out, sends a held pick to the wrong expert, or leaves
    the identity experts' part out (``reference/longcat.py:FAULTS``;
    ``benchmark/controls/longcat_check.py`` reads the same at the
    published widths on the chip): not correct, where the clean
    reference is."""
    from benchmark.harness import check
    cfg, params = tiny
    engine = _engine(cfg, params)
    prompt = _prompt(40, seed=5)
    got, generated = _rows(engine, prompt, 8)
    full = np.asarray([prompt + generated[:-1]], np.int32)
    config = _config(cfg)
    clean = np.asarray(ref.logits_last(params, full, 8, config)[0])
    assert check.compare(got, clean, None, ref.LOGITS_TOL)["ok"]
    faulty = np.asarray(ref.logits_last(
        params, full, 8, dict(config, _fault=fault))[0])
    row = check.compare(got, faulty, None, ref.LOGITS_TOL)
    assert not row["ok"] and row["rel_err"] > 2 * ref.LOGITS_TOL


def test_a_gpt_model_fetches_no_expert_counts():
    from ray_tpu.models.gpt import GPTConfig, init_params
    cfg = GPTConfig.tiny(dtype=jnp.float32)
    engine = _engine(cfg, init_params(cfg, jax.random.PRNGKey(0)))
    engine.submit(_prompt(9, 0), max_new_tokens=3)
    while engine.has_work():
        assert all(rec.moe is None for rec in engine._flight)
        engine.step()
    assert "moe" not in engine.telemetry.summary()


def _layer(cfg, key, held, T=40):
    """One expert layer's weights for ``held``, cut from a full set."""
    d, E = cfg.d_model, cfg.n_routed_experts + cfg.n_identity_experts
    ks = jax.random.split(key, 5)
    full = {
        "router": jax.random.normal(ks[0], (d, E)) * d ** -0.5,
        "e_gate": jax.random.normal(
            ks[1], (cfg.n_routed_experts, d, cfg.expert_ff)) * d ** -0.5,
        "e_up": jax.random.normal(
            ks[2], (cfg.n_routed_experts, d, cfg.expert_ff)) * d ** -0.5,
        "e_down": jax.random.normal(
            ks[3], (cfg.n_routed_experts, cfg.expert_ff, d))
        * cfg.expert_ff ** -0.5}
    x = jax.random.normal(ks[4], (T, d))
    held = list(held)
    return x, full, lambda: moe.dropless_moe(
        x, full["router"], jnp.zeros((E,)), full["e_gate"][jnp.array(held)],
        full["e_up"][jnp.array(held)], full["e_down"][jnp.array(held)],
        held=held, n_routed=cfg.n_routed_experts, top_k=cfg.moe_top_k,
        scale=cfg.routed_scale)


def _uncut_reference(cfg, x, full, routed=True):
    """The whole expert layer in plain numpy: every routed expert
    (``routed=False``: the identity experts' part alone)."""
    x = np.asarray(x, np.float64)
    logits = x @ np.asarray(full["router"], np.float64)
    p = np.exp(logits - logits.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    out = np.zeros_like(x)
    silu = lambda a: a / (1.0 + np.exp(-a))               # noqa: E731
    for t in range(x.shape[0]):
        for e in np.argsort(-p[t])[:cfg.moe_top_k]:
            if e >= cfg.n_routed_experts:
                y = x[t]
            elif not routed:
                continue
            else:
                g, u, dn = (np.asarray(full[k][e], np.float64)
                            for k in ("e_gate", "e_up", "e_down"))
                y = (silu(x[t] @ g) * (x[t] @ u)) @ dn
            out[t] += p[t, e] * y
    return cfg.routed_scale * out


def test_the_shares_add_up(tiny):
    """What every share of the experts gives, the identity experts' part
    counted once, is the uncut expert layer."""
    cfg, _ = tiny
    shares = [range(0, 4), range(4, 8), range(8, 16)]
    x, full, _ = _layer(cfg, jax.random.PRNGKey(5), shares[0])
    identity_only = _uncut_reference(cfg, x, full, routed=False)
    total = identity_only.copy()
    for held in shares:
        out, counts = _layer(cfg, jax.random.PRNGKey(5), held)[2]()
        total += np.asarray(out, np.float64) - identity_only
        assert int(counts[0]) == x.shape[0] and int(counts[5]) == 1
    np.testing.assert_allclose(total, _uncut_reference(cfg, x, full),
                               rtol=2e-4, atol=2e-4)


def test_dropless_when_every_row_picks_one_held_expert(tiny):
    """A routing that sends every row to one held expert: a capacity
    would drop most of them; here each gets that expert's output."""
    cfg, _ = tiny
    d, E, T = cfg.d_model, 24, 300            # more rows than one tile
    ks = jax.random.split(jax.random.PRNGKey(7), 4)
    x = jnp.abs(jax.random.normal(ks[0], (T, d))) + 0.1
    router = jnp.zeros((d, E)).at[:, 2].set(1.0)      # all rows pick 2
    e_gate, e_up = (jax.random.normal(k, (4, d, 32)) * d ** -0.5
                    for k in ks[1:3])
    e_down = jax.random.normal(ks[3], (4, 32, d)) * 32 ** -0.5
    out, counts = moe.dropless_moe(
        x, router, jnp.zeros((E,)), e_gate, e_up, e_down, held=[0, 1, 2, 3],
        n_routed=16, top_k=1, scale=1.0)
    logits = x @ router
    p = jax.nn.softmax(logits, -1)[:, 2:3]
    want = p * ((jax.nn.silu(x @ e_gate[2]) * (x @ e_up[2])) @ e_down[2])
    np.testing.assert_allclose(out, want, rtol=2e-4, atol=2e-5)
    # 300 rows of one expert: three tiles of 128, three trips
    assert [int(c) for c in counts] == [T, T, 0, T, 1, 1, 3]


def test_rows_of_no_sequence_pick_nothing(tiny):
    cfg, _ = tiny
    x, full, _ = _layer(cfg, jax.random.PRNGKey(9), range(4), T=8)
    valid = jnp.arange(8) < 5
    args = (x, full["router"], jnp.zeros((24,)), full["e_gate"][:4],
            full["e_up"][:4], full["e_down"][:4])
    kw = dict(held=range(4), n_routed=16, top_k=3, scale=6.0)
    out, counts = moe.dropless_moe(*args, valid=valid, **kw)
    alone, fewer = moe.dropless_moe(*(a[:5] if a is x else a for a in args),
                                    **kw)
    np.testing.assert_allclose(out[:5], alone, rtol=1e-5, atol=1e-6)
    assert not np.asarray(out[5:]).any()
    assert [int(c) for c in counts] == [int(c) for c in fewer]


@pytest.mark.parametrize("feature, kwargs", [
    ("int8", {"kv_dtype": "int8"}),
    ("LoRA", {"lora": True}),
])
def test_what_is_written_over_k_and_v_refuses_a_latent_row(tiny, feature,
                                                           kwargs):
    cfg, params = tiny
    with pytest.raises(NotImplementedError, match=feature):
        _engine(cfg, params, **kwargs)


def test_handoff_refuses_a_latent_row(tiny):
    cfg, params = tiny
    engine = _engine(cfg, params)
    with pytest.raises(NotImplementedError, match="KVHandoff"):
        engine.export_request(0)
    with pytest.raises(NotImplementedError, match="KVHandoff"):
        engine.import_submit(None, max_new_tokens=1)
    with pytest.raises(NotImplementedError, match="export_pages"):
        kvc.export_pages(engine.cache, [1])


def test_a_routed_gpt_config_keeps_a_refusal_that_says_why():
    from ray_tpu.models.gpt import GPTConfig
    with pytest.raises(NotImplementedError, match="static capacity"):
        InferenceEngine(GPTConfig.tiny(n_experts=4), {})


def test_the_deployment_builds_the_preset_by_name():
    from ray_tpu.inference.serve_gpt import _build_engine
    cfg, engine = _build_engine(
        "longcat_tiny", {"dtype": jnp.float32, "held_experts": [0, 5]},
        {"slots": 2, "page_size": PAGE, "buckets": (16, 32)}, seed=3)
    assert cfg.held_experts == (0, 5) and engine.cache.latent == (32, 16)
    assert engine.cache.k.shape == (4, 2 * 8 + 1, 48, PAGE)
    assert engine.generate([_prompt(9, 0)], max_new_tokens=3)[0]
    with pytest.raises(ValueError, match="longcat_flash_omni"):
        _build_engine("no_such_preset", None, None, seed=0)


def test_latent_kernels_match_the_gathered_formulation():
    """The decode kernel and the write kernel over a latent pool, in
    interpret mode, against the masked einsum and the whole-page blend
    the CPU path runs."""
    from ray_tpu.ops import attention as ops
    L, P, R, page, B, H, rank = 2, 9, 48, 128, 3, 4, 32
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    pool = jax.random.normal(ks[0], (L, P, R, page)).astype(jnp.bfloat16)
    q = jax.random.normal(ks[1], (B, H, R)).astype(jnp.bfloat16)
    # six pages a slot: two of the kernel's groups of four, the second
    # filled up with the garbage page
    table = jnp.array([[1, 2, 3, 5, 6, 7], [4, 0, 0, 0, 0, 0],
                       [0, 0, 0, 0, 0, 0]], jnp.int32)
    lengths = jnp.array([700, 17, 0], jnp.int32)
    want = ops.latent_decode_attention(q, pool, lengths, table, 1,
                                       scale=0.2, value_dim=rank)
    new = jax.random.normal(ks[2], (B, R)).astype(jnp.bfloat16)
    blend = kvc.write_decode(pool, new, 1, table, lengths)
    real = ops._use_interpret
    try:        # the kernels themselves, interpreted
        ops._use_interpret = lambda: True
        gate = ops.latent_decode_uses_pallas
        ops.latent_decode_uses_pallas = lambda *a: True
        got = ops.latent_decode_attention(q, pool, lengths, table, 1,
                                          scale=0.2, value_dim=rank)
        wrote = ops.latent_decode_write(pool, new, lengths, table, 1,
                                        skip_page=0)
    finally:
        ops._use_interpret, ops.latent_decode_uses_pallas = real, gate
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=2e-2, atol=2e-2)
    assert not np.asarray(got[2], np.float32).any()
    # slot 2 holds nothing (its tail is the skipped page): the blend
    # writes the garbage page, the kernel writes nothing there
    np.testing.assert_array_equal(np.asarray(wrote[:, 1:], np.float32),
                                  np.asarray(blend[:, 1:], np.float32))
    np.testing.assert_array_equal(np.asarray(wrote[:, 0], np.float32),
                                  np.asarray(pool[:, 0], np.float32))


def test_prefill_kernel_matches_the_chunked_einsum():
    """The flash forward with the causal edge moved by ``start``, in
    interpret mode, against the masked einsum the CPU path runs: a cold
    bucket, and two cached lengths that put the edge inside a key block
    and past one."""
    from ray_tpu.ops import attention as ops
    H, S, C, nope, rope, dv = 2, 256, 1024, 32, 16, 32
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    bf = jnp.bfloat16
    qn, qr, kn, kr, v = (
        jax.random.normal(k, shape).astype(bf) for k, shape in zip(ks, (
            (H, S, nope), (H, S, rope), (H, C, nope), (C, rope),
            (H, C, dv))))
    for start in (0, 300, 700):
        want = ops.latent_prefill_attention(qn, qr, kn, kr, v, start,
                                            scale=0.15)
        real, gate = ops._use_interpret, ops.latent_prefill_uses_pallas
        try:
            ops._use_interpret = lambda: True
            ops.latent_prefill_uses_pallas = lambda *a: True
            got = ops.latent_prefill_attention(qn, qr, kn, kr, v,
                                               jnp.int32(start), scale=0.15)
        finally:
            ops._use_interpret, ops.latent_prefill_uses_pallas = real, gate
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=2e-2, atol=2e-2)


def test_reference_loss_and_gradients_are_finite(tiny):
    cfg, params = tiny
    tokens = np.random.RandomState(0).randint(0, 512, size=(2, 24))
    total, count, grads = ref.loss_and_grad_sums(
        params, jnp.asarray(tokens[:, :-1]), jnp.asarray(tokens[:, 1:]), 1,
        _config(cfg))
    assert float(count) == 2 * 23 and np.isfinite(float(total))
    assert float(jnp.abs(grads["layers"]["e_gate"]).sum()) > 0
