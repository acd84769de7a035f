"""Step-level training telemetry (``ray_tpu/telemetry/``).

Everything runs on the CPU backend (conftest pins an 8-device host-sim
world): record schema + compile-vs-steady split, MFU arithmetic against
a hand-computed GPT FLOPs count, chrome-trace JSON validity, dashboard
``/api/timeline`` + ``/metrics`` carrying train-step data, and the
disabled-mode no-op / <1%-overhead budget.
"""

import json
import time

import pytest


def _tiny_cfg():
    import jax.numpy as jnp

    from ray_tpu.models.gpt import GPTConfig
    return GPTConfig(vocab_size=512, d_model=64, n_layers=2, n_heads=4,
                     max_seq=64, dtype=jnp.float32)


_PEAK = 197.0


def _single_dev_mesh():
    import jax

    from ray_tpu.parallel.mesh import make_mesh
    return make_mesh(dp=1, devices=jax.devices()[:1])


@pytest.fixture(scope="module")
def aot_run():
    """One instrumented AOT run shared by the schema/MFU/trace tests."""
    import jax

    from ray_tpu.models import training
    from ray_tpu.telemetry import StepTelemetry

    cfg = _tiny_cfg()
    mesh = _single_dev_mesh()
    fns = training.build_gpt_train(cfg, mesh, telemetry=False)
    # an explicit peak: on the CPU there is no chip to price MFU
    # against (chip_peak_tflops refuses unknown devices), and the
    # arithmetic under test does not care whose peak it is
    tel = StepTelemetry(cfg, mesh, comm_mode=fns["comm_mode"],
                        label="t9", aot=True, chip_peak_tflops=_PEAK)
    step = tel.wrap(fns["step_fn"])
    state = fns["init_fn"](jax.random.PRNGKey(0))
    batch = training.synthetic_lm_batch(jax.random.PRNGKey(1), 4, 32,
                                        cfg.vocab_size)
    for _ in range(4):
        state, metrics = step(state, batch)
    return {"cfg": cfg, "mesh": mesh, "tel": tel, "batch": batch,
            "loss": float(metrics["loss"])}


def test_step_record_schema_and_compile_split(aot_run):
    tel = aot_run["tel"]
    assert len(tel.records) == 4
    for rec in tel.records:
        for key in ("step", "ts", "wall_s", "dispatch_s", "sync_s",
                    "tokens", "loss"):
            assert key in rec, (key, rec)
        assert rec["wall_s"] > 0
        assert rec["wall_s"] >= rec["dispatch_s"] > 0
        assert rec["tokens"] == 4 * 32
    # throughput/MFU only on steady steps: step 0's wall includes the
    # compile, so a rate derived from it would be garbage
    assert "tokens_per_sec" not in tel.records[0]
    for rec in tel.records[1:]:
        assert rec["tokens_per_sec"] > 0 and "mfu" in rec
    # compile time is split out of steady state: only step 0 carries
    # it, and the steady median must not include the compile
    assert tel.records[0]["compile_s"] > 0
    assert "compile_s" not in tel.records[1]
    s = tel.summary()
    assert s["enabled"] and s["steps"] == 4
    assert s["compile_s"] == tel.records[0]["compile_s"]
    assert s["first_step_s"] >= s["compile_s"]
    assert s["steady_step_s"] < s["first_step_s"]
    # HBM footprint from jit(...).lower().compile().memory_analysis()
    assert s["hbm"] is not None
    assert s["hbm"]["argument_bytes"] > 0
    assert s["hbm"]["total_bytes"] > 0
    # logical collective accounting is present (single-device: zeros)
    assert s["collective_bytes_per_step"]["total"] == 0
    assert s["comm_mode"] == "gspmd"


def test_mfu_arithmetic_vs_hand_computed_flops(aot_run):
    """The analytic FLOPs/token matches an independently hand-computed
    count for the tiny GPT, and the recorded MFU is exactly
    tokens/s/device * flops_per_token / peak."""
    from ray_tpu.telemetry import gpt_train_flops_per_token

    cfg, tel = aot_run["cfg"], aot_run["tel"]
    seq = 32
    d, H, hd, f = cfg.d_model, cfg.n_heads, cfg.head_dim, cfg.ff_dim
    L, V = cfg.n_layers, cfg.vocab_size
    # hand count (2 FLOPs/MAC): qkv + causal attention (half of the
    # 2 * 2*seq*H*hd score/value matmuls) + out-proj + swiglu FFN
    per_layer = (3 * 2 * d * H * hd          # q, k, v projections
                 + 2 * seq * H * hd          # QK^T + AV, causal-halved
                 + 2 * H * hd * d            # output projection
                 + 3 * 2 * d * f)            # w1, w3, w2
    fwd = L * per_layer + 2 * d * V          # + lm head
    want = 3 * fwd                           # fwd + 2x bwd
    # default ce_chunk=4096 >= 0 rematerializes the head matmul once
    want += 2 * d * V
    got = gpt_train_flops_per_token(cfg, seq)
    assert got == pytest.approx(want, rel=1e-9), (got, want)

    rec = tel.records[2]
    expect_mfu = (rec["tokens_per_sec"] * got
                  / (_PEAK * 1e12))
    assert rec["mfu"] == pytest.approx(expect_mfu, rel=1e-6)


@pytest.mark.parametrize("ce_chunk, n_dev, ce_mode, path, vocab_matmuls", [
    (-1, 1, None, "xla_saved", 3),       # both train cells' recipe
    (4096, 1, None, "flash", 4),         # the default GPTConfig
    (0, 1, None, "flash", 4),
    (-1, 4, None, "xla_saved", 3),
    (4096, 4, None, "xla_chunked", 4),   # sharded: the kernel declines
    (-1, 1, "flash", "flash", 4),        # an A/B driver's pins
    (4096, 1, "xla", "xla_chunked", 4),
])
def test_step_record_names_the_loss_head_and_prices_it(
        ce_chunk, n_dev, ce_mode, path, vocab_matmuls):
    """The run's ``ce_path`` (its first record and the summary carry
    it) and the FLOPs a token follow the head the model's dispatch
    names (``models.gpt.ce_path``, over ``flash_ce.uses_flash_ce``): three
    vocabulary matmuls where the recipe keeps its logits, four wherever
    they are recomputed, in flash-CE or in chunks."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.gpt import GPTConfig
    from ray_tpu.parallel.mesh import make_mesh
    from ray_tpu.telemetry import StepTelemetry
    from ray_tpu.telemetry.flops import gpt_fwd_flops_per_token

    cfg = GPTConfig(vocab_size=512, d_model=128, n_layers=2, n_heads=2,
                    max_seq=64, dtype=jnp.float32, ce_chunk=ce_chunk)
    mesh = make_mesh(dp=n_dev, devices=jax.devices()[:n_dev])
    tel = StepTelemetry(cfg, mesh, ce_mode=ce_mode,
                        chip_peak_tflops=_PEAK)
    step = tel.wrap(lambda state, batch: (state, {"loss": 0.0}))
    batch = {"tokens": jnp.zeros((4, 32), jnp.int32)}
    for _ in range(2):
        step(None, batch)
    assert [r.get("ce_path") for r in tel.records] == [path, None]
    assert tel.summary()["ce_path"] == path
    head = 2 * cfg.d_model * cfg.vocab_size
    layers = 3 * (gpt_fwd_flops_per_token(cfg, 32) - head)
    assert (tel.flops_per_token() - layers) / head == vocab_matmuls
    # before a batch has shown its shape there is nothing to name
    assert StepTelemetry(cfg, mesh, ce_mode=ce_mode).ce_path() is None


@pytest.mark.parametrize("n_dev", [1, 4])
def test_step_record_carries_the_attention_schedules_coverage(n_dev):
    """``causal_coverage`` (the run's first record and the summary carry
    it beside ``ce_path``) is what the attention fn the step was built
    with counts for its schedule, on one device and under ``shard_map``;
    a step whose attention says nothing of the kind reports none."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.gpt import GPTConfig
    from ray_tpu.ops import attention as A
    from ray_tpu.parallel.mesh import make_mesh
    from ray_tpu.telemetry import StepTelemetry

    cfg = GPTConfig(vocab_size=512, d_model=128, n_layers=2, n_heads=2,
                    max_seq=1024, dtype=jnp.float32)
    mesh = make_mesh(dp=n_dev, devices=jax.devices()[:n_dev])
    attn_fn = A.make_flash_attention_fn(mesh, rope_theta=cfg.rope_theta)
    batch = {"tokens": jnp.zeros((n_dev, 1024), jnp.int32)}
    for fn, want in ((attn_fn, A.train_causal_coverage(1024, 2, 64)),
                     (None, None)):
        tel = StepTelemetry(cfg, mesh, attn_fn=fn, chip_peak_tflops=_PEAK)
        assert tel.causal_coverage() is None    # no batch seen yet
        step = tel.wrap(lambda state, batch: (state, {"loss": 0.0}))
        for _ in range(2):
            step(None, batch)
        assert [r.get("causal_coverage") for r in tel.records] == \
            [want, None]
        assert tel.summary().get("causal_coverage") == want
    # the train cells' schedule: under 0.65 where the parent's ran 0.75
    assert A.train_causal_coverage(1024, 2, 64) < 0.65


def test_chrome_trace_export_valid(aot_run):
    """The exporter emits Perfetto-loadable JSON: a ``traceEvents``
    list of complete events carrying both host spans and step
    annotations."""
    from ray_tpu.telemetry import chrome_trace
    from ray_tpu.util import tracing

    tracing.clear_recorded()
    tracing.enable_tracing()
    try:
        with tracing.span("host-side-work", kind="test"):
            time.sleep(0.01)
    finally:
        tracing.disable_tracing()

    trace = json.loads(chrome_trace.export())
    evs = trace["traceEvents"]
    assert isinstance(evs, list) and evs
    for ev in evs:
        assert {"name", "ph", "ts", "pid", "tid"} <= set(ev)
        if ev["ph"] == "X":
            assert ev["dur"] >= 0
    # host span from the tracing fallback recorder ...
    host = [e for e in evs if e["name"] == "host-side-work"]
    assert host and host[0]["pid"] == "host"
    assert host[0]["dur"] >= 0.01 * 1e6
    # ... merged with the train-step records (step + phases + compile)
    steps = [e for e in evs if e.get("cat") == "train_step"]
    assert len(steps) >= 4
    assert any("compile" in e["name"] for e in evs)
    assert any(e["name"].endswith("/sync") for e in evs)
    # events are time-sorted, as trace viewers expect
    ts = [e["ts"] for e in evs]
    assert ts == sorted(ts)


def test_tracing_spans_use_monotonic_durations():
    """Fallback-recorder spans carry a monotonic ``dur`` (NTP-safe)
    plus the epoch placement keys."""
    from ray_tpu.util import tracing

    tracing.clear_recorded()
    tracing.enable_tracing()
    try:
        with tracing.span("mono"):
            time.sleep(0.02)
    finally:
        tracing.disable_tracing()
    (rec,) = [s for s in tracing.recorded_spans()
              if s["name"] == "mono"]
    assert rec["dur"] >= 0.02
    assert rec["end"] == pytest.approx(rec["start"] + rec["dur"])
    assert "tid" in rec


def test_disabled_mode_noop(monkeypatch):
    """RAY_TPU_TELEMETRY=0: the wrapper is identity, instrument() adds
    nothing, and the builders return unwrapped steps."""
    import ray_tpu.telemetry.config as tcfg_mod
    from ray_tpu.telemetry import StepTelemetry, instrument, \
        telemetry_config

    monkeypatch.setenv("RAY_TPU_TELEMETRY", "0")
    try:
        cfg = telemetry_config(refresh=True)
        assert not cfg.enabled
        tel = StepTelemetry(label="off")
        assert not tel.enabled

        def step(x):
            return x

        assert tel.wrap(step) is step
        fns = {"step_fn": step}
        out = instrument(fns)
        assert out is fns and "telemetry" not in out
        assert tel.summary() == {"enabled": False}
    finally:
        monkeypatch.delenv("RAY_TPU_TELEMETRY")
        telemetry_config(refresh=True)
    assert tcfg_mod.telemetry_config().enabled


def test_rl_telemetry_summary():
    """r14: the RL-loop recorder's summary block — rollout tokens/s,
    learner steps/s (steady: first step's compile excluded), publish
    latency, the param_version_lag series and the queue drop
    accounting — plus the disabled no-op."""
    from ray_tpu.telemetry import RLTelemetry
    from ray_tpu.telemetry.config import TelemetryConfig

    tel = RLTelemetry(config=TelemetryConfig(enabled=True))
    for i in range(3):
        tel.record_rollout(0.1, tokens=50, param_version=i + 1)
    tel.record_learner_step(1.0, version_lag=0)      # cold: compile
    tel.record_learner_step(0.01, version_lag=0)
    tel.record_learner_step(0.01, version_lag=2)
    for v in (1, 2, 3, 4):
        tel.record_publish(0.002, version=v)
    tel.record_backpressure()
    tel.record_actor_restart()      # r15: supervisor counters
    tel.record_actor_restart()
    tel.record_learner_restart()
    tel.record_queue_counters(drops_stale=5, drops_overflow=1)
    out = tel.summary()
    assert out["enabled"] and out["label"] == "rl"
    assert out["actor_restarts"] == 2
    assert out["learner_restarts"] == 1
    assert out["rollouts"] == 3 and out["rollout_tokens"] == 150
    assert out["rollout_tokens_per_sec"] == pytest.approx(500.0)
    assert out["learner_steps"] == 3
    # steady rate: the 1s compile step is excluded
    assert out["learner_steps_per_sec"] == pytest.approx(100.0)
    assert out["publishes"] == 4 and out["param_version"] == 4
    assert out["publish_s"] == pytest.approx(0.002)
    assert out["version_lag_mean"] == pytest.approx(2 / 3)
    assert out["version_lag_max"] == 2
    assert out["drops"] == {"stale": 5, "overflow": 1}
    assert out["backpressure_rejections"] == 1
    off = RLTelemetry(config=TelemetryConfig(enabled=False))
    off.record_rollout(0.1, tokens=1, param_version=1)
    off.record_actor_restart()
    assert off.summary() == {"enabled": False}


def test_ckpt_telemetry_summary():
    """r15: the checkpoint recorder's summary block — write counts,
    failure counter (a failed write must never kill the run, so it has
    to be observable instead), write-latency stats and the
    last-persisted-step gauge value — plus the disabled no-op."""
    from ray_tpu.telemetry import CkptTelemetry
    from ray_tpu.telemetry.config import TelemetryConfig

    tel = CkptTelemetry(config=TelemetryConfig(enabled=True))
    assert tel.summary()["last_checkpoint_step"] == -1
    tel.record_write(0.2, step=50)
    tel.record_write(0.4, step=100)
    tel.record_failure()
    out = tel.summary()
    assert out["enabled"] and out["label"] == "train"
    assert out["checkpoints"] == 2 and out["failed"] == 1
    assert out["last_checkpoint_step"] == 100
    assert out["write_s"] == pytest.approx(0.3)
    assert out["write_max_s"] == pytest.approx(0.4)
    off = CkptTelemetry(config=TelemetryConfig(enabled=False))
    off.record_write(0.2, step=1)
    off.record_failure()
    assert off.summary() == {"enabled": False}


def test_data_telemetry_summary():
    """r17: the input-pipeline recorder's summary block — produced
    batches with packed-token counts and input tok/s, trainer-blocked
    stall accounting, reader-restart and pack-retry counters — plus
    the disabled no-op."""
    from ray_tpu.telemetry import DataTelemetry
    from ray_tpu.telemetry.config import TelemetryConfig

    tel = DataTelemetry(config=TelemetryConfig(enabled=True))
    assert tel.summary()["batches"] == 0
    tel.record_batch(100, 0.5, queue_depth=2)
    tel.record_batch(60, 0.3, queue_depth=1)
    tel.record_stall(0.01)
    tel.record_stall(0.05)
    tel.record_reader_restart()
    tel.record_pack_retry()
    tel.record_read_hedge(won=True)
    tel.record_read_hedge(won=False)
    out = tel.summary()
    assert out["enabled"] and out["label"] == "train"
    assert out["batches"] == 2 and out["input_tokens"] == 160
    assert out["input_tok_s"] == pytest.approx(200.0)
    assert out["packed_tokens_per_batch"] == pytest.approx(80.0)
    assert out["prefetch_depth_mean"] == pytest.approx(1.5)
    assert out["stall_s_total"] == pytest.approx(0.06)
    assert out["stall_s_max"] == pytest.approx(0.05)
    assert out["reader_restarts"] == 1 and out["pack_retries"] == 1
    assert out["read_hedges"] == 2 and out["read_hedges_won"] == 1
    off = DataTelemetry(config=TelemetryConfig(enabled=False))
    off.record_batch(10, 0.1)
    off.record_stall(1.0)
    off.record_reader_restart()
    off.record_read_hedge(won=True)
    assert off.summary() == {"enabled": False}


def test_elastic_telemetry_summary():
    """r18: the elastic-loop recorder's summary block — live mesh
    size, transitions split by kind, reshard-latency stats — plus the
    disabled no-op and the unknown-kind guard."""
    from ray_tpu.telemetry import ElasticTelemetry
    from ray_tpu.telemetry.config import TelemetryConfig

    tel = ElasticTelemetry(config=TelemetryConfig(enabled=True))
    tel.record_mesh(8)
    assert tel.summary()["mesh_devices"] == 8
    assert tel.summary()["transitions_total"] == 0
    tel.record_transition("shrink", 0.2, n_devices=4)
    tel.record_transition("expand", 0.4, n_devices=8)
    tel.record_transition("shrink", 0.1, n_devices=4)
    out = tel.summary()
    assert out["enabled"] and out["label"] == "train"
    assert out["mesh_devices"] == 4
    assert out["transitions"] == {"shrink": 2, "expand": 1}
    assert out["transitions_total"] == 3
    assert out["reshard_s"] == pytest.approx(0.2)
    assert out["reshard_max_s"] == pytest.approx(0.4)
    # r19: sustained-straggle events ride the same recorder
    assert out["straggler_events"] == 0
    tel.record_straggler()
    tel.record_straggler()
    assert tel.summary()["straggler_events"] == 2
    with pytest.raises(ValueError, match="shrink"):
        tel.record_transition("sideways", 0.1, n_devices=4)
    off = ElasticTelemetry(config=TelemetryConfig(enabled=False))
    off.record_mesh(8)
    off.record_transition("shrink", 0.1, n_devices=4)
    off.record_straggler()
    assert off.summary() == {"enabled": False}


def test_fleet_telemetry_summary():
    """r16: the fleet recorder's summary block — router retries split
    by cause, replica restarts, affinity hit rate and the per-replica
    queue-depth snapshot — plus the disabled no-op."""
    from ray_tpu.telemetry import FleetTelemetry
    from ray_tpu.telemetry.config import TelemetryConfig

    tel = FleetTelemetry(config=TelemetryConfig(enabled=True))
    tel.record_retry("dead")
    tel.record_retry("dead")
    tel.record_retry("draining")
    tel.record_retry("queue_full")
    tel.record_restart()
    for hit in (True, False, True, True):
        tel.record_affinity(hit=hit)
    tel.record_queue_depth("r0", 3)
    tel.record_queue_depth("r1", 0)
    # r19 gray-failure series: hedges by outcome, demotion episodes,
    # per-replica latency-score gauge
    tel.record_hedge("issued")
    tel.record_hedge("issued")
    tel.record_hedge("won")
    tel.record_hedge("wasted")
    tel.record_demotion("r1")
    tel.record_latency_score("r0", 0.002)
    tel.record_latency_score("r1", 0.31)
    with pytest.raises(ValueError, match="issued"):
        tel.record_hedge("lost")
    # r20 disaggregation series: handoff bytes/seconds/pages (+ warm
    # skips), per-pool depth gauges, TTFT split by pool mode
    tel.record_handoff(n_bytes=4096, seconds=0.002, pages=2)
    tel.record_handoff(n_bytes=0, seconds=0.001, pages=0, skipped=True)
    tel.record_pool_depth("prefill", 3)
    tel.record_pool_depth("decode", 1)
    tel.record_ttft(0.02, mode="disagg")
    tel.record_ttft(0.04, mode="disagg")
    tel.record_ttft(0.05, mode="colocated")
    out = tel.summary()
    assert out["enabled"] and out["label"] == "fleet"
    assert out["router_retries"] == {"dead": 2, "draining": 1,
                                     "queue_full": 1}
    assert out["router_retries_total"] == 4
    assert out["replica_restarts"] == 1
    assert out["affinity_decisions"] == 4
    assert out["affinity_hit_rate"] == pytest.approx(0.75)
    assert out["replica_queue_depth"] == {"r0": 3, "r1": 0}
    assert out["hedges"] == {"issued": 2, "won": 1, "wasted": 1}
    assert out["replica_demotions"] == 1
    assert out["replica_latency_score"] == {"r0": 0.002, "r1": 0.31}
    assert out["handoffs"] == 2 and out["handoffs_skipped"] == 1
    assert out["handoff_bytes_total"] == 4096
    assert out["handoff_pages_total"] == 2
    assert out["handoff_s_mean"] == pytest.approx(0.0015)
    assert out["handoff_s_max"] == pytest.approx(0.002)
    assert out["pool_queue_depth"] == {"prefill": 3, "decode": 1}
    assert out["ttft_s_by_mode"]["disagg"]["count"] == 2
    assert out["ttft_s_by_mode"]["disagg"]["mean_s"] == \
        pytest.approx(0.03)
    assert out["ttft_s_by_mode"]["disagg"]["p99_s"] == \
        pytest.approx(0.04)
    assert out["ttft_s_by_mode"]["colocated"]["count"] == 1
    # a stopped replica's gauge state drops out of the snapshot
    tel.forget_replica("r1")
    assert tel.summary()["replica_queue_depth"] == {"r0": 3}
    assert tel.summary()["replica_latency_score"] == {"r0": 0.002}
    off = FleetTelemetry(config=TelemetryConfig(enabled=False))
    off.record_retry("dead")
    off.record_restart()
    off.record_affinity(hit=True)
    off.record_hedge("issued")
    off.record_demotion("r0")
    off.record_latency_score("r0", 1.0)
    off.record_handoff(n_bytes=1, seconds=0.1, pages=1)
    off.record_pool_depth("prefill", 1)
    off.record_ttft(0.1, mode="disagg")
    assert off.summary() == {"enabled": False}


def test_infer_telemetry_deadline_counter():
    """r15: ``infer_deadline_exceeded_total`` rides the infer
    recorder, split by kind in the summary block."""
    from ray_tpu.telemetry import InferTelemetry
    from ray_tpu.telemetry.config import TelemetryConfig

    tel = InferTelemetry(config=TelemetryConfig(enabled=True))
    tel.record_deadline_exceeded(kind="ttft")
    tel.record_deadline_exceeded(kind="ttft")
    tel.record_deadline_exceeded(kind="total")
    assert tel.summary()["deadline_exceeded"] == \
        {"ttft": 2, "total": 1}
    off = InferTelemetry(config=TelemetryConfig(enabled=False))
    off.record_deadline_exceeded(kind="ttft")
    assert off.summary() == {"enabled": False}


def test_infer_telemetry_adapter_summary():
    """r25: adapter-cache lookups and load walls fold into an
    ``adapters`` summary block — absent when no tenant ever looked
    one up."""
    from ray_tpu.telemetry import InferTelemetry
    from ray_tpu.telemetry.config import TelemetryConfig

    tel = InferTelemetry(config=TelemetryConfig(enabled=True))
    assert "adapters" not in tel.summary()
    tel.record_adapter_cache(hit=True)
    tel.record_adapter_cache(hit=True)
    tel.record_adapter_cache(hit=False)
    tel.record_adapter_load(0.01, resident=2)
    out = tel.summary()["adapters"]
    assert out["cache_hits"] == 2
    assert out["cache_misses"] == 1
    assert abs(out["cache_hit_rate"] - 2 / 3) < 1e-9
    assert out["loads"] == 1
    assert abs(out["load_seconds"] - 0.01) < 1e-9
    off = InferTelemetry(config=TelemetryConfig(enabled=False))
    off.record_adapter_cache(hit=True)
    off.record_adapter_load(0.01, resident=1)
    assert off.summary() == {"enabled": False}


@pytest.mark.slow
def test_telemetry_overhead_under_one_percent():
    """Acceptance budget: telemetry-on steady-state step time exceeds
    telemetry-off by <1%.

    A direct A/B on the real train step cannot resolve 1% on this
    1-core CI box — its per-step variance is ±30% between runs, two
    orders of magnitude above the wrapper's actual bookkeeping cost.
    So the budget is checked by decomposition: (1) the wrapper's
    absolute per-call cost, measured as the mean delta over many
    calls of a near-free jitted step (identical code path through the
    recorder: spans, sync, record build, emit check); (2) the real
    GPT step's steady wall time; assert (1) < 1% of (2)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import training
    from ray_tpu.models.gpt import GPTConfig
    from ray_tpu.telemetry import StepTelemetry

    # (1) absolute bookkeeping cost around a near-free step
    @jax.jit
    def fake_step(state, batch):
        s = state + 1.0
        return s, {"loss": jnp.sum(s)}

    cfg = GPTConfig(vocab_size=2048, d_model=128, n_layers=2,
                    n_heads=4, max_seq=256, dtype=jnp.float32)
    mesh = _single_dev_mesh()
    tel = StepTelemetry(cfg, mesh, comm_mode="gspmd",
                        label="overhead")
    wrapped = tel.wrap(fake_step)
    s = jnp.zeros((8, 128))
    batch = {"tokens": jnp.zeros((4, 128), jnp.int32)}
    s, _ = fake_step(s, batch)
    s, _ = wrapped(s, batch)       # step 0 (jit warm) out of the way
    n = 800
    t0 = time.monotonic()
    for _ in range(n):
        out = fake_step(s, batch)
        jax.block_until_ready(out)
    t_raw = time.monotonic() - t0
    t0 = time.monotonic()
    for _ in range(n):
        wrapped(s, batch)          # blocks internally
    t_wrapped = time.monotonic() - t0
    per_call = max((t_wrapped - t_raw) / n, 0.0)
    assert len(tel.records) == n + 1

    # (2) the real step's steady wall time (median of a few)
    fns = training.build_gpt_train(cfg, mesh, telemetry=False)
    state = fns["init_fn"](jax.random.PRNGKey(0))
    gbatch = training.synthetic_lm_batch(jax.random.PRNGKey(1), 4,
                                         128, cfg.vocab_size)
    walls = []
    for i in range(6):
        t0 = time.monotonic()
        state, m = fns["step_fn"](state, gbatch)
        jax.block_until_ready((state, m))
        if i > 0:
            walls.append(time.monotonic() - t0)
    walls.sort()
    steady = walls[len(walls) // 2]

    overhead = per_call / steady
    assert overhead < 0.01, (
        f"telemetry bookkeeping {per_call*1e6:.0f}µs/step is "
        f"{overhead:.2%} of the {steady*1e3:.1f}ms steady step — "
        "exceeds the 1% budget")


@pytest.mark.slow
def test_dashboard_timeline_and_metrics_show_train_steps(
        ray_start_regular):
    """The unified timeline reaches ``/api/timeline`` and the per-step
    Prometheus series reach ``/metrics`` through the control plane."""
    import jax
    import requests

    from ray_tpu.dashboard.app import Dashboard
    from ray_tpu.models import training

    cfg = _tiny_cfg()
    mesh = _single_dev_mesh()
    fns = training.build_gpt_train(cfg, mesh)   # default-on telemetry
    assert "telemetry" in fns
    state = fns["init_fn"](jax.random.PRNGKey(0))
    batch = training.synthetic_lm_batch(jax.random.PRNGKey(1), 2, 32,
                                        cfg.vocab_size)
    for _ in range(2):
        state, _ = fns["step_fn"](state, batch)

    port = Dashboard(18311).start()
    timeline = requests.get(
        f"http://127.0.0.1:{port}/api/timeline", timeout=10).json()
    steps = [ev for ev in timeline
             if ev.get("cat") == "train_step"]
    assert steps, [ev.get("name") for ev in timeline][:20]
    assert all(ev["ph"] == "X" and ev["dur"] > 0 for ev in steps)

    # r15 resilience + r16 fleet + r17 data-plane + r18 elastic series
    # ride the same control plane
    from ray_tpu.telemetry import (CkptTelemetry, DataTelemetry,
                                   ElasticTelemetry, FleetTelemetry,
                                   InferTelemetry, RLTelemetry)
    from ray_tpu.telemetry.config import TelemetryConfig
    on = TelemetryConfig(enabled=True)
    CkptTelemetry(config=on).record_write(0.1, step=2)
    elastic = ElasticTelemetry(config=on)
    elastic.record_mesh(8)
    elastic.record_transition("shrink", 0.05, n_devices=4)
    elastic.record_straggler()
    RLTelemetry(config=on).record_actor_restart()
    infer = InferTelemetry(config=on)
    infer.record_deadline_exceeded(kind="ttft")
    infer.record_adapter_cache(hit=True)
    infer.record_adapter_cache(hit=False)
    infer.record_adapter_load(0.01, resident=2)
    data = DataTelemetry(config=on)
    data.record_batch(128, 0.2, queue_depth=2)
    data.record_stall(0.003)
    data.record_reader_restart()
    fleet = FleetTelemetry(config=on)
    fleet.record_retry("dead")
    fleet.record_restart()
    fleet.record_affinity(hit=True)
    fleet.record_queue_depth("r0", 2)
    fleet.record_hedge("issued")
    fleet.record_hedge("won")
    fleet.record_demotion("r0")
    fleet.record_latency_score("r0", 0.25)
    fleet.record_handoff(n_bytes=2048, seconds=0.003, pages=2)
    fleet.record_pool_depth("prefill", 2)
    fleet.record_pool_depth("decode", 0)
    fleet.record_ttft(0.02, mode="disagg")

    text = requests.get(f"http://127.0.0.1:{port}/metrics",
                        timeout=10).text
    assert "train_step_seconds" in text, text[:2000]
    assert "user_histogram_train_step_seconds_bucket" in text
    assert "train_mfu" in text
    assert "train_collective_bytes" in text
    assert "train_checkpoint_seconds" in text
    assert "train_last_checkpoint_step" in text
    assert "rl_actor_restarts_total" in text
    assert "infer_deadline_exceeded_total" in text
    assert "serve_router_retries_total" in text
    # counters mangle tags into the series name; the cause split must
    # still be distinguishable per-series
    assert "cause" in text and "dead" in text
    assert "serve_replica_restarts_total" in text
    assert "serve_replica_queue_depth" in text
    assert 'replica="r0"' in text        # gauges carry real labels
    assert "serve_fleet_affinity_hit_rate" in text
    # r17 input-pipeline series
    assert "data_input_tokens_per_sec" in text
    assert "data_prefetch_depth" in text
    assert "data_stall_seconds" in text
    assert "data_reader_restarts_total" in text
    # r18 elastic series: gauge, reshard histogram, kind-split counter
    assert "train_mesh_devices" in text
    assert "user_histogram_train_reshard_seconds_bucket" in text
    assert "train_elastic_transitions_total" in text
    assert "shrink" in text
    # r19 gray-failure series: hedges by outcome, demotions, the
    # per-replica latency-score gauge, train straggle events
    assert "serve_hedges_total" in text
    assert "outcome" in text and "issued" in text
    assert "serve_replica_demotions_total" in text
    assert "serve_replica_latency_score" in text
    assert "train_straggler_events_total" in text
    # r20 disaggregation series: handoff bytes counter + seconds
    # histogram, per-pool depth gauges, TTFT-by-pool-mode histogram
    assert "serve_handoff_bytes_total" in text
    assert "user_histogram_serve_handoff_seconds_bucket" in text
    assert "serve_pool_queue_depth" in text
    assert 'pool="prefill"' in text and 'pool="decode"' in text
    assert "user_histogram_serve_ttft_seconds_bucket" in text
    assert 'mode="disagg"' in text
    # r25 multi-tenant adapter series: cache hit/miss counters, the
    # load-wall histogram, the resident-adapter gauge
    assert "serve_adapter_cache_hits_total" in text
    assert "serve_adapter_cache_misses_total" in text
    assert "user_histogram_serve_adapter_load_seconds_bucket" in text
    assert "serve_adapter_resident" in text
