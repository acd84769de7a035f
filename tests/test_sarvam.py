"""sarvam-105b's stack (``models/sarvam.py``) on the serve path, at a
small size (hidden 64, one dense and two routed layers, 4 heads, 8 of 32
experts held, top 4, a shared expert), against the benchmark's plain
reference (``benchmark/reference/sarvam.py``, which imports nothing of
the program) on seeded float32 weights."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import sarvam as ref
from ray_tpu.inference import InferenceEngine, kv_cache as kvc
from ray_tpu.models import latent, sarvam
from ray_tpu.parallel import moe

PAGE = 16
# float32 on both sides: what is left is the order of the sums (the
# engine absorbs W_kvb and reads the cache, the reference materialises
# K and V), 1e-6 of logits of order one.  The next precision down
# (every matrix and every layer's input of the reference in bfloat16)
# reads 8e-3 and more: 40 x over the limit
TOL = 2e-4


def _config(cfg, **more):
    """What the reference is told that no weight's shape says."""
    return dict({
        "num_experts_per_tok": cfg.moe_top_k,
        "routed_scaling_factor": cfg.routed_scale,
        "rms_norm_eps": cfg.norm_eps, "rope_theta": cfg.rope_theta,
        "qk_rope_head_dim": cfg.qk_rope_head_dim,
        "rope_scaling": {
            "factor": cfg.rope_factor,
            "original_max_position_embeddings": cfg.rope_original_max,
            "beta_fast": cfg.rope_beta_fast, "beta_slow": cfg.rope_beta_slow,
            "mscale": cfg.rope_mscale,
            "mscale_all_dim": cfg.rope_mscale},
        "model": {"kwargs": {"held_experts": list(cfg.held_experts)}}},
        **more)


@pytest.fixture(scope="module")
def tiny():
    cfg = sarvam.SarvamConfig.sarvam_tiny(dtype=jnp.float32)
    params = sarvam.init_params(cfg, jax.random.PRNGKey(0))
    # a bias that moves picks: the scores' gaps at the edge are ~1e-2
    bias = 0.05 * jax.random.normal(
        jax.random.PRNGKey(5), params["layers"]["router_bias"].shape)
    params["layers"]["router_bias"] = bias
    return cfg, params


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


@pytest.fixture(scope="module")
def served(tiny):
    """One request through the engine: its rows and its tokens."""
    cfg, params = tiny
    prompt = _prompt(40, seed=5)
    got, generated = _rows(_engine(cfg, params), prompt, 8)
    return got, np.asarray([prompt + generated[:-1]], np.int32)


def test_prefill_then_decode_matches_the_reference_cold_and_on_a_hit(tiny):
    """Prefill, then decode through the latent pages, gives the logits of
    the reference's full forward over the same tokens: cold, and again
    behind pages another request registered."""
    cfg, params = tiny
    engine = _engine(cfg, params)
    shared = _prompt(2 * PAGE, seed=1)
    for k, tail in enumerate((_prompt(7, 2), _prompt(11, 3))):
        hits = engine.scheduler.prefix_hit_pages
        got, generated = _rows(engine, shared + tail, 6)
        full = np.asarray([shared + tail + generated[:-1]], np.int32)
        want = np.asarray(ref.logits_last(params, full, 6, _config(cfg))[0])
        np.testing.assert_allclose(got, want, rtol=TOL, atol=TOL)
        assert engine.scheduler.prefix_hit_pages - hits == (2 if k else 0)
    assert engine.leak_free()
    counts = engine.telemetry.summary()["moe"]
    routed = cfg.n_layers - cfg.n_dense_layers
    assert counts["picks"] == cfg.moe_top_k * counts["rows"]
    assert counts["calls"] == counts["decode_calls"] + 2 * routed
    assert 0 < counts["held_picks"] < counts["picks"]
    assert counts["identity_picks"] == 0
    # the loop's trips: a tile an expert hit at a decode's few rows
    assert counts["decode_loop_trips"] == counts["decode_experts_hit"] > 0
    assert counts["loop_trips"] >= counts["experts_hit"]


@pytest.mark.parametrize("fault", [
    "no_shared", "softmax", "no_renorm", "no_yarn_scale", "no_q_norm",
    "wrong_held", "bfloat16"])
def test_the_check_sees_a_planted_fault(tiny, served, fault):
    """The comparison that decides ``correct`` (``harness/check.py``)
    against a reference with one planted fault
    (``reference/sarvam.py:FAULTS``; ``benchmark/controls/
    sarvam_check.py`` reads the same at the published widths on the
    chip), and against the reference in the next precision down: not
    correct, where the clean reference is.  In float32 the limit is this
    file's."""
    from benchmark.harness import check
    cfg, params = tiny
    got, full = served
    clean = np.asarray(ref.logits_last(params, full, 8, _config(cfg))[0])
    assert check.compare(got, clean, None, TOL)["ok"]
    planted = ({"_round": fault} if fault == "bfloat16"
               else {"_fault": fault})
    faulty = np.asarray(ref.logits_last(params, full, 8,
                                        _config(cfg, **planted))[0])
    row = check.compare(got, faulty, None, TOL)
    assert not row["ok"] and row["rel_err"] > 20 * TOL


def test_the_routers_logits_in_bfloat16_flip_picks_at_the_edge_alone(
        tiny, served):
    """The one planted fault the check is not asked to see: rows it moves
    at all are rows a pick flipped in, and the margin names them."""
    cfg, params = tiny
    got, full = served
    config = _config(cfg, _fault="router_bf16")
    faulty = np.asarray(ref.logits_last(params, full, 8, config)[0])
    moved = np.abs(got - faulty).max(-1) / np.abs(faulty).max() > TOL
    margin = np.asarray(ref._last_rows(params, jnp.asarray(full), 8,
                                       config)[1][0])
    assert np.all(margin[moved] < 0.05)


# ------------------------------------------------- the expert layer ----
E, K, D, FE = 128, 8, 64, 32


def _layer(key, T=48):
    ks = jax.random.split(key, 9)
    draw = lambda k, shape, s: jax.random.normal(k, shape) * s  # noqa: E731
    return {
        "x": draw(ks[0], (T, D), 1.0),
        "router": draw(ks[1], (D, E), 3.0 * D ** -0.5),
        "bias": draw(ks[2], (E,), 0.05),
        "e_gate": draw(ks[3], (E, D, FE), D ** -0.5),
        "e_up": draw(ks[4], (E, D, FE), D ** -0.5),
        "e_down": draw(ks[5], (E, FE, D), FE ** -0.5),
        "s_gate": draw(ks[6], (D, FE), D ** -0.5),
        "s_up": draw(ks[7], (D, FE), D ** -0.5),
        "s_down": draw(ks[8], (FE, D), FE ** -0.5)}


def _share(w, held, x=None, **kw):
    """One chip's routed part of the layer ``w`` for ``held``."""
    held = list(held)
    at = jnp.array(held)
    kw.setdefault("scoring", "sigmoid")
    kw.setdefault("renormalise", True)
    bias = kw.pop("bias", w["bias"])
    return moe.dropless_moe(
        w["x"] if x is None else x, w["router"], bias, w["e_gate"][at],
        w["e_up"][at], w["e_down"][at], held=held, n_routed=E, top_k=K,
        scale=2.5, **kw)


def test_the_four_shares_add_up():
    """What the four chips of the deployment give (held 0-31, 32-63,
    64-95, 96-127 of a 128-wide router), the shared expert counted once,
    is the uncut layer as the reference computes it."""
    w = _layer(jax.random.PRNGKey(5))
    total = np.asarray(latent.swiglu(w["x"][None], w["s_gate"], w["s_up"],
                                     w["s_down"])[0], np.float64)
    picks = 0
    for chip in range(4):
        out, counts = _share(w, range(32 * chip, 32 * chip + 32))
        total += np.asarray(out, np.float64)
        picks += int(counts[1])
        assert int(counts[0]) == w["x"].shape[0] and int(counts[5]) == 1
    assert picks == K * w["x"].shape[0]       # every pick lives somewhere
    layers = {k: w[k][None] for k in w if k not in ("x", "bias")}
    layers["router_bias"] = w["bias"][None]
    st = (K, 2.5, tuple(range(E)))
    with jax.default_matmul_precision("highest"):
        want, _ = ref.moe(w["x"], ref._reader(layers, 0), st,
                          lambda a: a, jnp.asarray(ref.FAULTS[""],
                                                   jnp.float32))
    np.testing.assert_allclose(total, np.asarray(want), rtol=2e-4,
                               atol=2e-4)


def _oracle(w, held, x, *, scoring="sigmoid", renormalise=True, bias=None):
    """The routed part in ten dense lines: every expert computed for
    every row, the picks a mask."""
    logits = x @ w["router"]
    s = jax.nn.sigmoid(logits) if scoring == "sigmoid" \
        else jax.nn.softmax(logits, -1)
    biased = s + (w["bias"] if bias is None else bias)
    kth = jnp.sort(biased, -1)[:, -K][:, None]
    weight = jnp.where(biased >= kth, s, 0.0)
    if renormalise:
        weight = weight / jnp.sum(weight, -1, keepdims=True)
    h = jax.nn.silu(jnp.einsum("td,edf->tef", x, w["e_gate"])) \
        * jnp.einsum("td,edf->tef", x, w["e_up"])
    y = jnp.einsum("tef,efd->ted", h, w["e_down"])
    ours = jnp.zeros((E,)).at[jnp.array(list(held))].set(1.0)
    return 2.5 * jnp.einsum("te,ted->td", weight * ours, y)


@pytest.mark.parametrize("lowering", ["primal", "differentiated"])
@pytest.mark.parametrize("what, kw", [
    ("sigmoid", {}),
    ("softmax", {"scoring": "softmax"}),
    ("no_bias", {"bias": jnp.zeros((E,))}),
    ("not_renormalised", {"renormalise": False}),
])
def test_scoring_bias_and_renormalisation_against_the_oracle(lowering, what,
                                                             kw):
    """Sigmoid scoring, selection by a non-zero bias (the picks differ
    from the unbiased ones; the weights are the bare scores) and the
    renormalisation over all eight picks wherever they live, each
    against the dense oracle: the loop a serve step takes, and the
    grouped products with their own backward a differentiated call
    takes, gradients in x included."""
    w = _layer(jax.random.PRNGKey(11))
    held = range(16, 48)
    with jax.default_matmul_precision("highest"):
        want = _oracle(w, held, w["x"], **kw)
        if lowering == "primal":
            got, _ = _share(w, held, **kw)
        else:
            probe = jax.random.normal(jax.random.PRNGKey(1), w["x"].shape)
            got, back = jax.vjp(lambda x: _share(w, held, x, **kw)[0],
                                w["x"])
            _, want_back = jax.vjp(lambda x: _oracle(w, held, x, **kw),
                                   w["x"])
            np.testing.assert_allclose(back(probe)[0], want_back(probe)[0],
                                       rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
    if what == "sigmoid":
        # the bias moved picks, or this test shows nothing of it
        plain = _oracle(w, held, w["x"], bias=jnp.zeros((E,)))
        assert float(jnp.abs(plain - want).max()) > 1e-2


def test_an_unknown_scoring_is_refused_by_name():
    w = _layer(jax.random.PRNGKey(0), T=4)
    with pytest.raises(KeyError, match="tanh"):
        _share(w, range(4), scoring="tanh")


# ------------------------------------------------------- the rotation --
def test_yarn_tables_and_the_softmax_factor_against_the_closed_form():
    """``deepseek_yarn`` at the published numbers: the frequencies blend
    from the plain ones (a channel that turns more than 32 times over
    4096 positions) into the plain ones over 40 (fewer than once), cos
    and sin unscaled, and the softmax's scale times (0.1 ln 40 + 1)^2."""
    cfg = sarvam.SarvamConfig.sarvam_105b(
        n_layers=2, vocab_size=8, held_experts=(0,))
    rope = cfg.rope
    assert rope.attention_factor == 1.0
    got = rope.inv_freq(64)
    c = np.arange(32)
    plain = 10000.0 ** (-c / 32)
    # the channel that turns r times over 4096 positions
    turns = lambda r: 64 * math.log(4096 / (r * 2 * math.pi)) / (  # noqa
        2 * math.log(10000.0))
    low, high = math.floor(turns(32)), math.ceil(turns(1))
    assert (low, high) == (10, 23)
    ramp = np.clip((c - low) / (high - low), 0, 1)
    np.testing.assert_allclose(got, plain * (1 - ramp) + plain / 40 * ramp,
                               rtol=1e-6)
    np.testing.assert_allclose(got[:11], plain[:11], rtol=1e-6)
    np.testing.assert_allclose(got[23:], plain[23:] / 40, rtol=1e-6)
    np.testing.assert_allclose(
        got, ref.yarn_inv_freq(64, 10000.0, 40.0, 4096, 32.0, 1.0),
        rtol=1e-6)
    factor = (0.1 * math.log(40) + 1) ** 2
    assert factor == pytest.approx(1.8739, abs=1e-4)
    assert cfg.softmax_scale == pytest.approx(192 ** -0.5 * factor)


def test_the_published_widths_are_the_presets_defaults():
    cfg = sarvam.SarvamConfig.sarvam_105b()
    assert (cfg.d_model, cfg.n_layers, cfg.n_dense_layers, cfg.n_heads,
            cfg.qk_head_dim, cfg.v_head_dim, cfg.latent_row, cfg.d_ff,
            cfg.expert_ff, cfg.n_routed_experts,
            cfg.moe_top_k, cfg.routed_scale, cfg.vocab_size) == (
        4096, 32, 1, 64, 192, 128, (512, 64), 16384, 2048, 128, 8,
        2.5, 262144)
    assert cfg.cache_layers == 32 and cfg.step_counts == moe.MOE_COUNTS
    shapes = jax.eval_shape(lambda: sarvam.init_params(
        sarvam.SarvamConfig.sarvam_105b(n_layers=6, vocab_size=65536,
                                        held_experts=tuple(range(32))),
        jax.random.PRNGKey(0)))
    # the cell's share: 10.92 GB of bfloat16 weights
    assert sum(math.prod(a.shape) * a.dtype.itemsize
               for a in jax.tree.leaves(shapes)) == 10_922_085_120
    with pytest.raises(ValueError, match="held_experts"):
        sarvam.SarvamConfig.sarvam_tiny(held_experts=(1, 1))
    with pytest.raises(ValueError, match="n_dense_layers"):
        sarvam.SarvamConfig.sarvam_tiny(n_dense_layers=3)


# ------------------------------------------------------ the refusals --
@pytest.mark.parametrize("feature, kwargs", [
    ("int8", {"kv_dtype": "int8"}),
    ("LoRA", {"lora": True}),
])
def test_what_is_written_over_k_and_v_refuses_this_models_row(tiny, feature,
                                                              kwargs):
    cfg, params = tiny
    with pytest.raises(NotImplementedError, match=feature):
        _engine(cfg, params, **kwargs)


def test_handoff_refuses_this_models_row(tiny):
    cfg, params = tiny
    engine = _engine(cfg, params)
    with pytest.raises(NotImplementedError, match="KVHandoff"):
        engine.export_request(0)
    with pytest.raises(NotImplementedError, match="KVHandoff"):
        engine.import_submit(None, max_new_tokens=1)
    with pytest.raises(NotImplementedError, match="export_pages"):
        kvc.export_pages(engine.cache, [1])


# ------------------------------------------------- the other models ----
def test_the_deployment_finds_every_modules_presets_by_name():
    from ray_tpu.inference.serve_gpt import _build_engine
    from ray_tpu.models import gpt, longcat
    cfg, engine = _build_engine(
        "sarvam_tiny", {"dtype": jnp.float32, "held_experts": [0, 9]},
        {"slots": 2, "page_size": PAGE, "buckets": (16, 32)}, seed=3)
    assert cfg.held_experts == (0, 9) and engine.cache.latent == (32, 16)
    # one latent row a token a layer, the dense layer's too
    assert engine.cache.k.shape == (3, 2 * 8 + 1, 48, PAGE)
    assert engine.generate([_prompt(9, 0)], max_new_tokens=3)[0]
    for module in (gpt, longcat, sarvam):
        assert all(hasattr(module.CONFIG, name) for name in module.PRESETS)
    with pytest.raises(ValueError) as e:
        _build_engine("no_such_preset", None, None, seed=0)
    for name in gpt.PRESETS + longcat.PRESETS + sarvam.PRESETS:
        assert name in str(e.value)
    # a preset the serve path lacks is refused with the reason
    with pytest.raises(NotImplementedError, match="held_experts"):
        _build_engine("mellum_tiny", None, None, seed=0)


@pytest.mark.parametrize("model", ["gpt", "longcat"])
def test_the_other_models_fetch_what_they_fetched(model):
    """A GPT model fetches no counts; a LongCat preset its counts, the
    loop's trips among them, through the engine it always had (the
    softmax's scale the one its head size gives)."""
    from ray_tpu.models import gpt, longcat
    if model == "gpt":
        cfg = gpt.GPTConfig.tiny(dtype=jnp.float32)
        params = gpt.init_params(cfg, jax.random.PRNGKey(0))
    else:
        cfg = longcat.LongcatConfig.longcat_tiny(dtype=jnp.float32)
        params = longcat.init_params(cfg, jax.random.PRNGKey(0))
        assert cfg.softmax_scale == cfg.qk_head_dim ** -0.5
    engine = _engine(cfg, params)
    engine.submit(_prompt(9, 0), max_new_tokens=3)
    fetched = []
    while engine.has_work():
        fetched += [rec.moe for rec in engine._flight]
        engine.step()
    summary = engine.telemetry.summary()
    if model == "gpt":
        assert all(m is None for m in fetched) and "moe" not in summary
    else:
        assert all(m.shape == (len(moe.MOE_COUNTS),) for m in fetched)
        assert set(moe.MOE_COUNTS) <= set(summary["moe"])
        assert summary["moe"]["identity_picks"] > 0


def test_the_fetchs_span_carries_the_steps_kind_and_counts(tiny, tmp_path):
    """``infer/sample`` of a routed model: the step's kind, the held
    picks, the experts hit and the loop's trips, as the benchmark's
    reader finds them in a profile."""
    from benchmark.reduce import spans
    from benchmark.reduce.xplane import find_xplane
    cfg, params = tiny
    engine = _engine(cfg, params)
    jax.profiler.start_trace(str(tmp_path))
    try:
        engine.generate([_prompt(20, 4)], max_new_tokens=4)
    finally:
        jax.profiler.stop_trace()
    fetches = spans.load(find_xplane(str(tmp_path))).named("infer/sample")
    kinds = [s.stats["kind"] for s in fetches]
    assert kinds[0] == "prefill" and set(kinds[1:]) == {"decode"}
    assert fetches[0].stats["moe_hit"] > 0
    for s in fetches:       # (a decode's one row may hit no held expert)
        assert 0 <= s.stats["moe_hit"] <= s.stats["moe_trips"]
        assert s.stats["moe_hit"] <= s.stats["moe_held"]
    counts = engine.telemetry.summary()["moe"]
    assert sum(s.stats["moe_trips"] for s in fetches) == counts["loop_trips"]
