"""Headline benchmark: GPT-2 (124M) training throughput per chip.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.

The reference's north-star (BASELINE.json) is per-device training
throughput matching H100+NCCL.  Baseline constant below is the per-H100
GPT-2-small bf16 DDP throughput (~255k tokens/s/GPU ≈ 190 TFLOP/s
effective at 6*N FLOPs/token); vs_baseline = ours / that.

Every mode measures a TPU and fails without one.  ``--quick`` runs the
same code path at toy shapes on whatever platform jax selects (the CPU
rehearsal before a chip run): it proves the path executes and prints
counts and losses under ``quick_check`` — never a rate, a latency or a
utilization, and never under the device metric's name.
"""

from __future__ import annotations

import json
import os
import sys
import time

H100_GPT2_TOKENS_PER_SEC = 255_000.0


def _target():
    """``(devices, platform, quick)`` for a bench mode: the TPU, or
    whatever jax selects under ``--quick``.  Anything else exits
    non-zero — a measurement path that finds no chip fails, it does not
    fall back to the CPU and print under the device metric's name."""
    import jax
    devices = jax.devices()
    platform = devices[0].platform
    quick = "--quick" in sys.argv
    if platform != "tpu" and not quick:
        raise SystemExit(
            f"bench.py measures a TPU but jax found platform={platform!r} "
            f"({devices[0].device_kind}); pass --quick for the toy-shape "
            "path check (it prints no rates)")
    return devices, platform, quick


# what a --quick line may carry: identity, counts and correctness —
# nothing derived from a clock
_QUICK_FIELDS = (
    "platform", "n_devices", "mesh", "model_params", "replicas",
    "requests", "generated_tokens", "errors", "final_loss", "loss",
    "attn_pack2", "ce", "fuse_norm", "comm_mode", "comm_quant",
    "compiles", "compile_counts", "leak_free",
)


def _emit(record: dict, quick: bool) -> None:
    if quick:
        record = {"quick_check": record["metric"],
                  **{k: record[k] for k in _QUICK_FIELDS if k in record}}
    print(json.dumps(record))


def _collective_bytes(cfg, mesh, batch, seq, comm_mode, quant="none"):
    from ray_tpu.parallel import overlap as ovl
    return ovl.collective_bytes_per_step(cfg, mesh, batch=batch,
                                         seq=seq, comm_mode=comm_mode,
                                         quant=quant)


def _mesh_arg():
    if "--mesh" not in sys.argv:
        return None
    idx = sys.argv.index("--mesh")
    if idx + 1 >= len(sys.argv):
        raise SystemExit("--mesh needs an argument, e.g. "
                         "--mesh fsdp=4,tp=2")
    return sys.argv[idx + 1]


def bench_mesh(arg: str):
    """Multichip bench: the sharded GPT step on an explicit mesh, one
    JSON line per comm schedule (gspmd vs overlap) with the logical
    collective bytes/step.

    ``python bench.py --mesh fsdp=4``.  The mesh must fit the devices
    this process sees; a mesh that does not fit fails (no re-exec on a
    host-simulated mesh: a parent that has touched jax holds the chips
    its child would need, and a simulated mesh times nothing real).
    """
    import math

    import jax
    import jax.numpy as jnp

    from ray_tpu.models import training
    from ray_tpu.models.gpt import GPTConfig
    from ray_tpu.parallel import overlap as ovl
    from ray_tpu.parallel.mesh import (MeshSpec, make_mesh,
                                       parse_mesh_axes)

    devices, platform, quick = _target()
    axes = dict(MeshSpec.create(**parse_mesh_axes(arg))
                .resolve(len(devices)).axes)
    need = math.prod(axes.values())
    if need > len(devices):
        raise SystemExit(f"--mesh {arg!r} needs {need} devices; "
                         f"{len(devices)} {platform} device(s) visible")
    mesh = make_mesh(devices=devices, **axes)
    data_par = (mesh.shape.get("dcn", 1) * mesh.shape.get("dp", 1)
                * mesh.shape.get("fsdp", 1))
    if quick:
        cfg = GPTConfig(vocab_size=512, d_model=128, n_layers=4,
                        n_heads=4, max_seq=128, dtype=jnp.float32)
        batch, seq, steps = 4 * data_par, 128, 4
    else:
        cfg = GPTConfig.gpt2(vocab_size=50304, max_seq=1024,
                             dtype=jnp.bfloat16, remat=True)
        batch, seq, steps = 8 * data_par, 1024, 20

    batch_data = training.synthetic_lm_batch(
        jax.random.PRNGKey(1), batch, seq, cfg.vocab_size)
    # three rows per mesh: the two schedules plus the int8-wire overlap
    # arm, with per-collective wire dtypes side by side; a dcn mesh adds
    # the dcn-only-quant arm (the recommended multi-pod wire)
    arms = [("gspmd", "none"), ("overlap", "none"), ("overlap", "int8")]
    if mesh.shape.get("dcn", 1) > 1:
        arms.append(("overlap", "dcn"))
    for want, want_quant in arms:
        fns = training.build_gpt_train(cfg, mesh, comm_mode=want,
                                       comm_quant=want_quant)
        mode = fns["comm_mode"]
        if want_quant != "none" and mode != "overlap":
            continue     # overlap declined: no distinct quantized arm
        state = fns["init_fn"](jax.random.PRNGKey(0))
        for _ in range(2):
            state, metrics = fns["step_fn"](state, batch_data)
            float(metrics["loss"])
        # raw jit step for the timed loop (same executable the wrapped
        # warmup compiled — the light wrapper delegates to it), then a
        # short instrumented window for the telemetry steady stats
        raw_step = fns.get("raw_step_fn", fns["step_fn"])
        t0 = time.perf_counter()
        for _ in range(steps):
            state, metrics = raw_step(state, batch_data)
        float(metrics["loss"])
        dt = time.perf_counter() - t0
        if "telemetry" in fns:
            for _ in range(3):
                state, metrics = fns["step_fn"](state, batch_data)
        tok_s = steps * batch * seq / dt
        cb = ovl.collective_bytes_per_step(
            cfg, mesh, batch=batch, seq=seq, comm_mode=mode,
            quant=fns.get("comm_quant", "none"))
        record = {
            "metric": "gpt2_train_tokens_per_sec_multichip",
            "value": round(tok_s, 1),
            "unit": "tokens/s",
            "tokens_per_sec_per_chip": round(tok_s / mesh.size, 1),
            "platform": platform,
            "mesh": dict(mesh.shape),
            "comm_mode": mode,
            "requested_comm_mode": want,
            "requested_comm_quant": want_quant,
            "comm_quant": fns.get("comm_quant", "none"),
            "collective_bytes_per_step": cb,
            # flattened per-tier rows: bytes and the analytic seconds
            # at the TIER_BANDWIDTH_GBPS price — the ~30x ICI-vs-DCN
            # gap is what makes the hierarchy's DCN reduction matter
            "collective_bytes_ici": cb["ici"]["total"],
            "collective_bytes_dcn": cb["dcn"]["total"],
            "collective_seconds_ici": cb["ici"]["seconds"],
            "collective_seconds_dcn": cb["dcn"]["seconds"],
            "final_loss": round(float(metrics["loss"]), 4),
        }
        if "reduction_vs_flat" in cb["dcn"]:
            record["dcn_reduction_vs_flat"] = \
                cb["dcn"]["reduction_vs_flat"]
        if "telemetry" in fns:
            record["telemetry"] = fns["telemetry"].summary()
        _emit(record, quick)


def _bench_fleet_arm(cfg, params, replicas_n, slots, page, affinity,
                     executables, payloads, gap_s):
    """One measured fleet arm, scoped so the whole fleet (N engines
    with full KV caches) frees before the next arm allocates its own
    — the two arms must never be resident together on a real device."""
    from ray_tpu.fleet import EngineReplica, FleetRouter, fleet_config
    from ray_tpu.inference import InferenceEngine
    from ray_tpu.telemetry.config import TelemetryConfig
    from ray_tpu.telemetry.fleet import FleetTelemetry

    engines = [InferenceEngine(cfg, params, slots=slots,
                               page_size=page, telemetry=True,
                               max_queue=0,
                               executable_cache=executables)
               for _ in range(replicas_n)]
    router = FleetRouter(
        [EngineReplica(f"r{i}", e) for i, e in enumerate(engines)],
        cfg=fleet_config(), affinity=affinity, rng_seed=0,
        telemetry=FleetTelemetry(config=TelemetryConfig(enabled=True)))
    dt, streams = _run_fleet_open_loop(router, payloads, gap_s)
    return {
        "wall_s": dt,
        "generated_tokens": sum(len(s.generated) for s in streams),
        "errors": sum(1 for s in streams if s.error is not None),
        "ttfts": sorted(router.recent_ttfts()),
        "telemetries": [e.telemetry.summary() for e in engines],
        "compiles": [e.stats()["compiles"] for e in engines],
        "fleet": router.telemetry.summary(),
    }


def _infer_trace(cfg, page, requests, rng_seed=1, shared_pages=3,
                 suffix_lens=None):
    """Open-loop request trace with a shared system prompt: every
    request is ``shared_pages`` full pages of identical system-prompt
    tokens plus a unique suffix — the fleet-traffic shape the prefix
    cache targets (>= 50% of prompt tokens shared).  Returns
    ``(prompts, shared_len)``."""
    import jax

    shared_len = shared_pages * page
    rng = jax.random.PRNGKey(rng_seed)
    rng, sub = jax.random.split(rng)
    # .tolist() materializes plain ints once — a list of 0-d device
    # arrays would pay a conversion per token in submit() and the
    # prefix walk, inside the measured TTFT window
    shared = jax.random.randint(sub, (shared_len,), 0,
                                cfg.vocab_size).tolist()
    prompts = []
    for i in range(requests):
        rng, sub = jax.random.split(rng)
        n = suffix_lens[i % len(suffix_lens)]
        prompts.append(shared + jax.random.randint(
            sub, (n,), 0, cfg.vocab_size).tolist())
    return prompts, shared_len


def _run_open_loop(engine, prompts, max_new, gap_s):
    """Submit on a fixed arrival schedule (open loop: arrivals do not
    wait for completions) while pumping ``engine.step()``; returns
    wall seconds and generated-token count."""
    import time as _time

    from ray_tpu.inference import SamplingParams
    total = 0
    t0 = _time.perf_counter()
    submitted = 0
    while submitted < len(prompts) or engine.has_work():
        now = _time.perf_counter() - t0
        while (submitted < len(prompts)
               and submitted * gap_s <= now):
            engine.submit(prompts[submitted], max_new_tokens=max_new,
                          sampling=SamplingParams())
            submitted += 1
        if engine.has_work():
            total += len(engine.step())
        else:
            _time.sleep(min(gap_s, 0.002))
    return _time.perf_counter() - t0, total


def _fleet_disagg_env() -> bool:
    """``RAY_TPU_FLEET_DISAGG=1`` selects the disagg A/B without the
    ``--disagg`` flag (resolved through fleet_config so the knob has
    one parser)."""
    from ray_tpu.fleet import fleet_config
    return fleet_config().disagg


def _replicas_arg() -> int:
    if "--replicas" not in sys.argv:
        return 1
    idx = sys.argv.index("--replicas")
    if idx + 1 >= len(sys.argv):
        raise SystemExit("--replicas needs an argument, e.g. "
                         "--replicas 4")
    n = int(sys.argv[idx + 1])
    if n < 1:
        raise SystemExit(f"--replicas must be >= 1, got {n}")
    return n


def _run_fleet_open_loop(router, payloads, gap_s):
    """Submit on a fixed arrival schedule through the router while
    pumping the fleet; returns (wall seconds, streams)."""
    import time as _time
    streams = []
    submitted = 0
    t0 = _time.perf_counter()
    while submitted < len(payloads) or any(not s.done for s in streams):
        now = _time.perf_counter() - t0
        while (submitted < len(payloads)
               and submitted * gap_s <= now):
            streams.append(router.remote(payloads[submitted]))
            submitted += 1
        if not router.poll():
            _time.sleep(min(gap_s, 0.001))
    return _time.perf_counter() - t0, streams


def bench_infer_fleet(replicas_n: int):
    """Multi-replica inference arm: ``python bench.py --infer
    --replicas N`` — a mixed open-loop trace (N shared-prefix groups
    interleaved) over N in-process replicas behind the fleet router,
    run twice: affinity routing vs pure pow-2.  Two JSON lines, one
    per arm, each carrying aggregate tokens/s, p50/p99 TTFT, and the
    fleet-wide prefix hit rate — the A/B the ROADMAP item 1 asks for:
    with affinity every group's requests land where its prefix pages
    live; without, each replica pays a cold prefill per group.  All
    replicas share one executable cache, so the measured arms show
    zero compiles (warmed separately)."""
    import statistics

    import jax
    import jax.numpy as jnp

    from ray_tpu.inference import InferenceEngine
    from ray_tpu.inference.config import infer_config
    from ray_tpu.models.gpt import GPTConfig, init_params

    devices, platform, quick = _target()
    if quick:
        cfg = GPTConfig(vocab_size=2048, d_model=128, n_layers=2,
                        n_heads=4, max_seq=256, dtype=jnp.float32)
        slots, page, max_new = 4, 16, 8
        shared_pages, gap_s = 3, 0.005
        requests = 8 * replicas_n
        suffix_lens = [9, 17, 5, 23, 12, 30, 7, 14]
    else:
        cfg = GPTConfig.gpt2(vocab_size=50304, max_seq=1024,
                             dtype=jnp.bfloat16)
        icfg = infer_config()
        slots, page, max_new = icfg.slots, icfg.page_size, 64
        shared_pages, gap_s = 3, 0.01
        requests = 16 * replicas_n
        suffix_lens = [32 + 23 * i % 224 for i in range(requests)]

    params = init_params(cfg, jax.random.PRNGKey(0))
    # N prefix groups, requests interleaved round-robin: the mixed
    # fleet-traffic shape (distinct tenants, each with its own shared
    # system prompt)
    groups = [
        _infer_trace(cfg, page, requests // replicas_n, rng_seed=g + 1,
                     shared_pages=shared_pages,
                     suffix_lens=suffix_lens)[0]
        for g in range(replicas_n)]
    prompts = [groups[i % replicas_n][i // replicas_n]
               for i in range(requests)]
    shared_len = shared_pages * page

    executables = {}
    # warm BOTH prefill flavors: with the prefix cache off every full
    # prompt bucket compiles (the spread-traffic cold prefills the
    # no-affinity arm pays), with it on the cached-suffix buckets do —
    # the measured fleet then shows zero compiles in either arm
    for warm_prefix in (False, True):
        warm = InferenceEngine(cfg, params, slots=slots,
                               page_size=page, telemetry=False,
                               max_queue=0, prefix=warm_prefix,
                               executable_cache=executables)
        _run_open_loop(warm, prompts, max_new, gap_s=0.0)
        del warm

    payloads = [{"tokens": p, "max_new_tokens": max_new}
                for p in prompts]
    for affinity in (True, False):
        arm = _bench_fleet_arm(cfg, params, replicas_n, slots, page,
                               affinity, executables, payloads, gap_s)
        dt, ttfts = arm["wall_s"], arm["ttfts"]
        tels = arm["telemetries"]
        prompt_tokens = sum(t.get("prompt_tokens", 0) for t in tels)
        skipped = sum(t.get("prefill_tokens_skipped", 0) for t in tels)
        record = {
            "metric": "gpt_infer_fleet_tokens_per_sec",
            "value": round(arm["generated_tokens"] / dt, 1)
            if dt > 0 else 0.0,
            "unit": "tokens/s",
            "platform": platform,
            "model_params": None if quick else 124_000_000,
            "replicas": replicas_n,
            "affinity": affinity,
            "requests": requests,
            "generated_tokens": arm["generated_tokens"],
            "errors": arm["errors"],
            "wall_s": round(dt, 3),
            "slots": slots,
            "page_size": page,
            "open_loop_gap_s": gap_s,
            "prefix_groups": replicas_n,
            "shared_prompt_tokens": shared_len,
            "fleet_prefix_hit_rate": round(
                skipped / prompt_tokens, 4) if prompt_tokens else 0.0,
            "ttft_p50_s": round(
                statistics.median(ttfts), 4) if ttfts else 0.0,
            "ttft_p99_s": round(
                ttfts[min(len(ttfts) - 1,
                          int(0.99 * len(ttfts)))], 4)
                if ttfts else 0.0,
            # zero steady-state recompiles across the whole fleet: the
            # measured replicas ride the warmup's shared executables
            "compiles": arm["compiles"],
            "fleet": arm["fleet"],
        }
        _emit(record, quick)


def _bench_gray_arm(cfg, params, replicas_n, slots, page, fcfg,
                    executables, payloads, gap_s, fault_spec):
    """One measured gray-failure arm (scoped so each arm's fleet frees
    before the next allocates): builds the fleet, arms the slowdown
    plan, runs the open-loop trace, returns the stream-level numbers."""
    from ray_tpu.fleet import EngineReplica, FleetRouter
    from ray_tpu.inference import InferenceEngine
    from ray_tpu.telemetry.config import TelemetryConfig
    from ray_tpu.telemetry.fleet import FleetTelemetry
    from ray_tpu.util import chaos

    engines = [InferenceEngine(cfg, params, slots=slots,
                               page_size=page, telemetry=False,
                               max_queue=0,
                               executable_cache=executables)
               for _ in range(replicas_n)]
    router = FleetRouter(
        [EngineReplica(f"r{i}", e) for i, e in enumerate(engines)],
        cfg=fcfg, affinity=False, rng_seed=0, concurrent_steps=True,
        telemetry=FleetTelemetry(config=TelemetryConfig(enabled=True)))
    chaos.install_faults(fault_spec)
    try:
        dt, streams = _run_fleet_open_loop(router, payloads, gap_s)
    finally:
        chaos.clear_faults()
    router.quiesce()
    inter = [b - a for s in streams
             for a, b in zip(s.token_ts, s.token_ts[1:])]
    out = {
        "wall_s": dt,
        "generated_tokens": sum(len(s.generated) for s in streams),
        "errors": sum(1 for s in streams if s.error is not None),
        "ttfts": sorted(router.recent_ttfts()),
        "inter_token": sorted(inter),
        "compiles": [e.stats()["compiles"] for e in engines],
        "fleet": router.telemetry.summary(),
        "leak_free": router.leak_free(),
    }
    router.close()
    return out


def bench_infer_gray(replicas_n: int):
    """Gray-failure A/B: ``python bench.py --infer --replicas N
    --gray`` — the same open-loop trace twice over an N-replica fleet
    whose replica r0 runs under a sustained ``serve.tick[r0]`` delay
    window (slow, never dead), once with hedging + latency demotion ON
    and once OFF.  Two JSON lines, one per arm, each carrying p50/p99
    TTFT, inter-token p99, hedges issued/won/wasted and demotions —
    the r19 acceptance A/B: with mitigation on, the fleet's tail must
    stop tracking the straggler."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.fleet import FleetConfig
    from ray_tpu.inference import InferenceEngine
    from ray_tpu.inference.config import infer_config
    from ray_tpu.models.gpt import GPTConfig, init_params

    devices, platform, quick = _target()
    if quick:
        cfg = GPTConfig(vocab_size=2048, d_model=128, n_layers=2,
                        n_heads=4, max_seq=256, dtype=jnp.float32)
        slots, page, max_new = 4, 16, 8
        # the delay dwarfs a healthy tick (a few ms) so the injected
        # gray failure dominates the tails; arrivals stretch past the
        # straggler's first slow tick (the EWMA needs one completed
        # tick before demotion can protect later arrivals), and N-1
        # healthy replicas can absorb the whole trace without deep
        # queues: the A/B isolates the gray failure, not generic
        # overload (where no routing policy wins)
        gap_s, delay_s = 0.03, 0.4
        requests = 8 * replicas_n
        suffix_lens = [9, 17, 5, 23, 12, 30, 7, 14]
    else:
        cfg = GPTConfig.gpt2(vocab_size=50304, max_seq=1024,
                             dtype=jnp.bfloat16)
        icfg = infer_config()
        slots, page, max_new = icfg.slots, icfg.page_size, 32
        gap_s, delay_s = 0.02, 0.5
        requests = 8 * replicas_n
        suffix_lens = [32 + 23 * i % 224 for i in range(requests)]

    params = init_params(cfg, jax.random.PRNGKey(0))
    prompts, _ = _infer_trace(cfg, page, requests, rng_seed=1,
                              shared_pages=1, suffix_lens=suffix_lens)
    executables = {}
    # warm both prefill flavors (the r16 fleet-bench precedent): the
    # first measured arm must not pay a compile the second one rides
    for warm_prefix in (False, True):
        warm = InferenceEngine(cfg, params, slots=slots,
                               page_size=page, telemetry=False,
                               max_queue=0, prefix=warm_prefix,
                               executable_cache=executables)
        _run_open_loop(warm, prompts, max_new, gap_s=0.0)
        del warm

    payloads = [{"tokens": p, "max_new_tokens": max_new}
                for p in prompts]
    # the slow window covers every r0 tick the trace can reach
    fault_spec = f"serve.tick[r0]@1..100000:delay={delay_s}"
    arms = {
        "on": FleetConfig(slow_factor=3.0, hedge=True,
                          hedge_factor=2.0, hedge_min=2 * gap_s),
        "off": FleetConfig(slow_factor=0.0, hedge=False),
    }
    for name, fcfg in arms.items():
        arm = _bench_gray_arm(cfg, params, replicas_n, slots, page,
                              fcfg, executables, payloads, gap_s,
                              fault_spec)
        ttfts, inter = arm["ttfts"], arm["inter_token"]

        def pct(xs, q):
            if not xs:
                return 0.0
            return round(xs[min(len(xs) - 1, int(q * len(xs)))], 4)

        fleet = arm["fleet"]
        record = {
            "metric": "gpt_infer_gray_ttft_p99_s",
            "value": pct(ttfts, 0.99),
            "unit": "s",
            "platform": platform,
            "mitigation": name,
            "replicas": replicas_n,
            "requests": requests,
            "slow_replica": "r0",
            "slow_delay_s": delay_s,
            "generated_tokens": arm["generated_tokens"],
            "errors": arm["errors"],
            "wall_s": round(arm["wall_s"], 3),
            "tokens_per_sec": round(
                arm["generated_tokens"] / arm["wall_s"], 1)
            if arm["wall_s"] > 0 else 0.0,
            "ttft_p50_s": pct(ttfts, 0.50),
            "ttft_p99_s": pct(ttfts, 0.99),
            "inter_token_p99_s": pct(inter, 0.99),
            "hedges": fleet.get("hedges", {}),
            "demotions": fleet.get("replica_demotions", 0),
            "compiles": arm["compiles"],
            "leak_free": arm["leak_free"],
            "open_loop_gap_s": gap_s,
        }
        _emit(record, quick)


def _bench_disagg_arm(cfg, params, mode, replicas_n, prefill_n, slots,
                      page, kv_dtype, executables, payloads, gap_s):
    """One measured arm of the disagg A/B (scoped so each arm's fleet
    frees before the next allocates).  ``mode``: "colocated" runs N
    replicas behind the FleetRouter; "disagg" splits the SAME N chips
    into prefill_n prefill + (N - prefill_n) decode replicas behind
    the DisaggRouter — equal chip count, different topology."""
    from ray_tpu.fleet import (DisaggRouter, EngineReplica, FleetRouter,
                               fleet_config)
    from ray_tpu.inference import InferenceEngine
    from ray_tpu.telemetry.config import TelemetryConfig
    from ray_tpu.telemetry.fleet import FleetTelemetry

    def mk(rid):
        return EngineReplica(rid, InferenceEngine(
            cfg, params, slots=slots, page_size=page, telemetry=False,
            max_queue=0, kv_dtype=kv_dtype,
            executable_cache=executables))

    tel = FleetTelemetry(config=TelemetryConfig(enabled=True))
    if mode == "colocated":
        router = FleetRouter([mk(f"r{i}") for i in range(replicas_n)],
                             cfg=fleet_config(), affinity=True,
                             rng_seed=0, telemetry=tel)
    else:
        router = DisaggRouter(
            [mk(f"p{i}") for i in range(prefill_n)],
            [mk(f"d{i}") for i in range(replicas_n - prefill_n)],
            cfg=fleet_config(), rng_seed=0, telemetry=tel)
    dt, streams = _run_fleet_open_loop(router, payloads, gap_s)
    router.quiesce()
    inter = [b - a for s in streams
             for a, b in zip(s.token_ts, s.token_ts[1:])]
    return {
        "wall_s": dt,
        "generated_tokens": sum(len(s.generated) for s in streams),
        "errors": sum(1 for s in streams if s.error is not None),
        "ttfts": sorted(router.recent_ttfts()),
        "inter_token": sorted(inter),
        "compiles": [r.engine.stats()["compiles"]
                     for r in router.replicas()],
        "fleet": tel.summary(),
        "leak_free": router.leak_free(),
    }


def bench_infer_disagg(replicas_n: int):
    """Disaggregation A/B: ``python bench.py --infer --replicas N
    --disagg`` (or ``RAY_TPU_FLEET_DISAGG=1``) — the same open-loop
    shared-prefix trace over equal chip counts, three ways: N
    co-located replicas (FleetRouter), 1 prefill + N-1 decode behind
    the DisaggRouter (``RAY_TPU_FLEET_PREFILL_REPLICAS`` resizes the
    split), and the disagg arm again on an int8 KV cache.  One JSON
    line per arm carrying p50/p99 TTFT, decode inter-token p99,
    aggregate tok/s, and the handoff byte accounting checked against
    the analytic page-size math — the int8 arm's bytes/page are
    ``(head_dim + 4) / (head_dim * itemsize)`` of the model-dtype
    arm's (~half on a bf16 fleet).  All arms ride pre-warmed shared
    executables: the compile counters in every record must be
    all-zero."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.fleet import fleet_config
    from ray_tpu.inference import InferenceEngine
    from ray_tpu.inference.config import infer_config
    from ray_tpu.inference.kv_cache import handoff_page_bytes
    from ray_tpu.models.gpt import GPTConfig, init_params

    devices, platform, quick = _target()
    if quick:
        cfg = GPTConfig(vocab_size=2048, d_model=128, n_layers=2,
                        n_heads=4, max_seq=256, dtype=jnp.float32)
        slots, page, max_new = 4, 16, 8
        shared_pages, gap_s = 2, 0.005
        requests = 8 * replicas_n
        suffix_lens = [9, 17, 5, 23, 12, 30, 7, 14]
    else:
        cfg = GPTConfig.gpt2(vocab_size=50304, max_seq=1024,
                             dtype=jnp.bfloat16)
        icfg = infer_config()
        slots, page, max_new = icfg.slots, icfg.page_size, 32
        shared_pages, gap_s = 3, 0.01
        requests = 8 * replicas_n
        suffix_lens = [32 + 23 * i % 224 for i in range(requests)]

    params = init_params(cfg, jax.random.PRNGKey(0))
    prompts, shared_len = _infer_trace(cfg, page, requests, rng_seed=1,
                                       shared_pages=shared_pages,
                                       suffix_lens=suffix_lens)
    prefill_n = min(max(fleet_config().prefill_replicas, 1),
                    replicas_n - 1)
    itemsize = jnp.dtype(cfg.dtype).itemsize
    page_bytes = {
        "model": handoff_page_bytes(
            n_layers=cfg.n_layers, page_size=page, n_heads=cfg.n_heads,
            head_dim=cfg.head_dim, itemsize=itemsize, quantized=False),
        "int8": handoff_page_bytes(
            n_layers=cfg.n_layers, page_size=page, n_heads=cfg.n_heads,
            head_dim=cfg.head_dim, itemsize=1, quantized=True),
    }
    payloads = [{"tokens": p, "max_new_tokens": max_new}
                for p in prompts]
    arms = (("colocated", "model"), ("disagg", "model"),
            ("disagg", "int8"))
    executables = {}
    # warm every executable family the arms touch (cold + cached
    # prefill flavors, both kv dtypes): the measured fleets must show
    # all-zero compiles, and no arm may ride a compile another paid
    for kv_dtype in ("model", "int8"):
        for warm_prefix in (False, True):
            warm = InferenceEngine(cfg, params, slots=slots,
                                   page_size=page, telemetry=False,
                                   max_queue=0, prefix=warm_prefix,
                                   kv_dtype=kv_dtype,
                                   executable_cache=executables)
            _run_open_loop(warm, prompts, max_new, gap_s=0.0)
            del warm

    for mode, kv_dtype in arms:
        arm = _bench_disagg_arm(cfg, params, mode, replicas_n,
                                prefill_n, slots, page, kv_dtype,
                                executables, payloads, gap_s)
        ttfts, inter = arm["ttfts"], arm["inter_token"]

        def pct(xs, q):
            if not xs:
                return 0.0
            return round(xs[min(len(xs) - 1, int(q * len(xs)))], 4)

        fleet = arm["fleet"]
        analytic = fleet.get("handoff_pages_total", 0) \
            * page_bytes[kv_dtype]
        record = {
            "metric": "gpt_infer_disagg_tokens_per_sec",
            "value": round(arm["generated_tokens"] / arm["wall_s"], 1)
            if arm["wall_s"] > 0 else 0.0,
            "unit": "tokens/s",
            "platform": platform,
            "mode": mode,
            "kv_dtype": kv_dtype,
            "replicas": replicas_n,
            "prefill_replicas": prefill_n if mode == "disagg" else 0,
            "decode_replicas": (replicas_n - prefill_n
                                if mode == "disagg" else 0),
            "requests": requests,
            "shared_prompt_tokens": shared_len,
            "generated_tokens": arm["generated_tokens"],
            "errors": arm["errors"],
            "wall_s": round(arm["wall_s"], 3),
            "ttft_p50_s": pct(ttfts, 0.50),
            "ttft_p99_s": pct(ttfts, 0.99),
            "inter_token_p99_s": pct(inter, 0.99),
            "handoffs": fleet.get("handoffs", 0),
            "handoffs_skipped": fleet.get("handoffs_skipped", 0),
            "handoff_bytes": fleet.get("handoff_bytes_total", 0),
            # measured == analytic is the byte-math check: pages moved
            # times the per-page K/V (+scale) footprint
            "handoff_bytes_analytic": analytic,
            "handoff_bytes_match":
                fleet.get("handoff_bytes_total", 0) == analytic,
            "handoff_page_bytes": page_bytes[kv_dtype],
            "handoff_page_bytes_vs_model": round(
                page_bytes[kv_dtype] / page_bytes["model"], 4),
            "open_loop_gap_s": gap_s,
            "compiles": arm["compiles"],
            "leak_free": arm["leak_free"],
        }
        _emit(record, quick)


def bench_infer_trace(replicas_n: int):
    """p99 TTFT attribution over the traced disagg fleet: ``python
    bench.py --infer --trace``.

    Runs the shared-prefix open-loop trace through a DisaggRouter
    (tiers on: host-DRAM pool + fleet-shared page store) with
    per-request tracing forced to sample=1, then decomposes every
    request's TTFT from its span tree: ``queue`` (submit -> admit),
    ``route`` (the router's pick loop), ``prefix_walk`` (the
    scheduler's per-tier walk), ``tier_fetch`` (host/store page
    fetches), ``handoff`` (export + import + install legs),
    ``prefill`` (the compiled bucket run), ``first_decode`` (decode
    ticks inside the TTFT window) and ``unattributed`` (dispatch gaps
    between spans).  Prints ONE JSON line with per-component p50/p99
    milliseconds; the component p50s must sum to the measured p50 TTFT
    within 10% (``attribution_ratio`` — the spans tile the window, so
    a miss means a hole in the instrumentation).  The slowest
    request's full span tree rides the record (``slowest_tree``) and
    echoes to stderr for humans."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.fleet import DisaggRouter, EngineReplica, fleet_config
    from ray_tpu.inference import InferenceEngine
    from ray_tpu.inference.config import infer_config
    from ray_tpu.inference.kv_cache import KVPageStore
    from ray_tpu.models.gpt import GPTConfig, init_params
    from ray_tpu.telemetry import trace
    from ray_tpu.telemetry.config import TelemetryConfig
    from ray_tpu.telemetry.fleet import FleetTelemetry

    devices, platform, quick = _target()
    # attribution needs every request traced and a ring big enough to
    # hold the whole run (the report reads the ring after quiesce)
    os.environ["RAY_TPU_TRACE_SAMPLE"] = "1"
    os.environ.setdefault("RAY_TPU_TRACE_RING", "65536")
    trace.trace_config(refresh=True)
    trace.reset()
    if quick:
        cfg = GPTConfig(vocab_size=2048, d_model=128, n_layers=2,
                        n_heads=4, max_seq=256, dtype=jnp.float32)
        slots, page, max_new = 4, 16, 8
        shared_pages, gap_s = 2, 0.005
        requests = 8 * replicas_n
        suffix_lens = [9, 17, 5, 23, 12, 30, 7, 14]
    else:
        cfg = GPTConfig.gpt2(vocab_size=50304, max_seq=1024,
                             dtype=jnp.bfloat16)
        icfg = infer_config()
        slots, page, max_new = icfg.slots, icfg.page_size, 32
        shared_pages, gap_s = 3, 0.01
        requests = 8 * replicas_n
        suffix_lens = [32 + 23 * i % 224 for i in range(requests)]

    params = init_params(cfg, jax.random.PRNGKey(0))
    prompts, shared_len = _infer_trace(cfg, page, requests, rng_seed=1,
                                       shared_pages=shared_pages,
                                       suffix_lens=suffix_lens)
    payloads = [{"tokens": p, "max_new_tokens": max_new}
                for p in prompts]
    executables = {}
    for warm_prefix in (False, True):
        warm = InferenceEngine(cfg, params, slots=slots,
                               page_size=page, telemetry=False,
                               max_queue=0, prefix=warm_prefix,
                               executable_cache=executables)
        _run_open_loop(warm, prompts, max_new, gap_s=0.0)
        del warm

    prefill_n = min(max(fleet_config().prefill_replicas, 1),
                    replicas_n - 1)
    store = KVPageStore(use_object_store=False)

    def mk(rid):
        return EngineReplica(rid, InferenceEngine(
            cfg, params, slots=slots, page_size=page, telemetry=False,
            max_queue=0, host_pages=4, store=store,
            executable_cache=executables))

    router = DisaggRouter(
        [mk(f"p{i}") for i in range(prefill_n)],
        [mk(f"d{i}") for i in range(replicas_n - prefill_n)],
        cfg=fleet_config(), rng_seed=0,
        telemetry=FleetTelemetry(config=TelemetryConfig(enabled=True)))
    dt, streams = _run_fleet_open_loop(router, payloads, gap_s)
    router.quiesce()

    # ----------------------------------------------- TTFT decomposition
    # each span's contribution is its overlap with the request's TTFT
    # window [root start, first token] — spans past the first token
    # (decode, the install leg on the decode replica) attribute 0, so
    # the components tile the TTFT and their sum must reproduce it
    direct = ("queue", "route", "prefix_walk", "tier_fetch", "prefill")
    handoff_names = {"handoff.export", "handoff.import",
                     "handoff.install"}
    comp_names = direct + ("handoff", "first_decode", "delivery",
                           "unattributed")
    per_comp = {c: [] for c in comp_names}
    decode_ticks = [r for r in trace.recorder().spans()
                    if r["name"] == "decode_tick"]
    ttfts, ranked = [], []
    for s in streams:
        if s.error is not None or s.first_token_ts is None:
            continue
        tid = s.trace.trace_id
        spans = trace.spans_for(tid)
        root = next((r for r in spans if r["name"] == "request"), None)
        if root is None:
            continue
        ttft = s.first_token_ts - s.submitted_ts
        w0, w1 = root["start"], root["start"] + ttft

        def clipped(rec):
            a = max(rec["start"], w0)
            b = min(rec["start"] + rec.get("dur", 0.0), w1)
            return max(b - a, 0.0)

        acc = {c: 0.0 for c in comp_names}
        for rec in spans:
            name = rec["name"]
            comp = ("handoff" if name in handoff_names
                    else name if name in direct else None)
            if comp is not None:
                acc[comp] += clipped(rec)
        for rec in decode_ticks:
            if tid in (rec.get("attributes") or {}).get("trace_ids",
                                                        ()):
                acc["first_decode"] += clipped(rec)
        # delivery: the host-driven dispatch gap between the engine
        # recording the first token (inside its step — the rid-tagged
        # first_token event) and the stream observing it (the window
        # end).  In the host-sim fleet every replica steps in one
        # process, so this is the poll loop's serialization cost.
        eng_ft = min((rec["start"] for rec in spans
                      if rec["name"] == "first_token"
                      and "rid" in (rec.get("attributes") or {})),
                     default=None)
        if eng_ft is not None:
            acc["delivery"] = max(w1 - max(eng_ft, w0), 0.0)
        known = sum(acc.values())
        acc["unattributed"] = max(ttft - known, 0.0)
        for c in comp_names:
            per_comp[c].append(acc[c])
        ttfts.append(ttft)
        ranked.append((ttft, tid))
    ttfts.sort()

    def pct(xs, q):
        if not xs:
            return 0.0
        xs = sorted(xs)
        return xs[min(len(xs) - 1, int(q * len(xs)))]

    p50 = pct(ttfts, 0.50)
    sum_p50 = sum(pct(v, 0.50) for v in per_comp.values())
    slowest = max(ranked) if ranked else (0.0, None)
    tree = trace.format_tree(slowest[1]) if slowest[1] else ""
    record = {
        "metric": "gpt_infer_ttft_p50_attribution",
        "value": round(p50, 4),
        "unit": "s",
        "platform": platform,
        "mode": "disagg",
        "replicas": replicas_n,
        "prefill_replicas": prefill_n,
        "requests": requests,
        "attributed": len(ttfts),
        "errors": sum(1 for s in streams if s.error is not None),
        "shared_prompt_tokens": shared_len,
        "wall_s": round(dt, 3),
        "ttft_p50_s": round(p50, 4),
        "ttft_p99_s": round(pct(ttfts, 0.99), 4),
        "components": {c: {"p50_ms": round(pct(v, 0.50) * 1e3, 3),
                           "p99_ms": round(pct(v, 0.99) * 1e3, 3)}
                       for c, v in per_comp.items()},
        "component_p50_sum_s": round(sum_p50, 4),
        # the acceptance gate: component p50s reproduce the p50 TTFT
        "attribution_ratio": round(sum_p50 / p50, 4) if p50 > 0
        else 0.0,
        "spans_recorded": trace.recorder().recorded,
        "spans_dropped": trace.recorder().dropped,
        "slowest_trace_id": slowest[1],
        "slowest_ttft_s": round(slowest[0], 4),
        "slowest_tree": tree,
        "leak_free": router.leak_free(),
    }
    _emit(record, quick)
    if tree:
        print(f"slowest request ({slowest[0] * 1e3:.1f} ms TTFT):",
              file=sys.stderr)
        print(tree, file=sys.stderr)


def bench_infer():
    """Inference headline: continuous-batching decode throughput.

    ``python bench.py --infer``.  Runs an open-loop trace whose
    requests share a system-prompt prefix (>= 50% of prompt tokens)
    and prints ONE JSON line — decode tokens/s as the headline value,
    TTFT (mean + split by prefix-cache outcome), prefill tokens
    skipped by prefix hits vs the trace's analytic hit count, the
    engine compile-cache counters (zero steady-state recompiles: the
    measured engine shares a warmed executable cache, so it must show
    zero compiles and only hits) and the full ``InferTelemetry``
    block.  The prefix-cache A/B is the env knob: run once with
    ``RAY_TPU_INFER_PREFIX=1`` and once with ``=0``
    (``scratch/r12_prefix.py`` automates both arms).  On CPU the model
    shrinks to a smoke configuration (numbers exercise the engine, not
    the hardware).
    """
    import jax
    import jax.numpy as jnp

    from ray_tpu.inference import InferenceEngine
    from ray_tpu.inference.config import infer_config
    from ray_tpu.models.gpt import GPTConfig, init_params

    devices, platform, quick = _target()
    if quick:
        cfg = GPTConfig(vocab_size=2048, d_model=128, n_layers=2,
                        n_heads=4, max_seq=256, dtype=jnp.float32)
        slots, page, requests, max_new = 4, 16, 8, 8
        shared_pages = 3                      # 48-token system prompt
        suffix_lens = [9, 17, 5, 23, 12, 30, 7, 14]
        gap_s = 0.01
    else:
        cfg = GPTConfig.gpt2(vocab_size=50304, max_seq=1024,
                             dtype=jnp.bfloat16)
        icfg = infer_config()
        slots, page = icfg.slots, icfg.page_size
        requests, max_new = 32, 64
        shared_pages = 3                      # e.g. 384 @ page 128
        suffix_lens = [32 + 23 * i % 224 for i in range(requests)]
        gap_s = 0.01

    params = init_params(cfg, jax.random.PRNGKey(0))
    prompts, shared_len = _infer_trace(cfg, page, requests,
                                       shared_pages=shared_pages,
                                       suffix_lens=suffix_lens)
    # warmup engine compiles every executable the trace touches into a
    # shared cache; the measured engine then shows pure steady state —
    # zero compiles, all hits — and TTFT carries no compile time
    executables = {}
    # max_queue pinned off (like telemetry below): a stray
    # RAY_TPU_INFER_MAX_QUEUE from a serving experiment would make the
    # burst-submitting warmup raise QueueFullError and kill the bench
    warm = InferenceEngine(cfg, params, slots=slots, page_size=page,
                           telemetry=False, max_queue=0,
                           executable_cache=executables)
    _run_open_loop(warm, prompts, max_new, gap_s=0.0)
    warmup_compiles = dict(warm.compile_counts)
    del warm    # frees the warmup engine's KV cache before measuring
    # telemetry pinned on: the numbers ARE this entry's output (a
    # stray RAY_TPU_TELEMETRY=0 would otherwise zero the headline)
    engine = InferenceEngine(cfg, params, slots=slots, page_size=page,
                             telemetry=True, max_queue=0,
                             executable_cache=executables)
    dt, total_tokens = _run_open_loop(engine, prompts, max_new, gap_s)
    tel = engine.telemetry.summary()
    stats = engine.stats()
    # trace-analytic hit count: every request after the first hits the
    # shared pages (admissions are sequential, so request 0 registers
    # before request 1 walks the index) — the measured counter must
    # agree when the prefix cache is on
    analytic = (requests - 1) * shared_len if engine.prefix else 0
    result = {
        "metric": "gpt2_infer_decode_tokens_per_sec",
        "value": round(tel.get("decode_tokens_per_sec", 0.0), 1),
        "unit": "tokens/s",
        "platform": platform,
        "model_params": None if quick else 124_000_000,
        "requests": len(prompts),
        "generated_tokens": total_tokens,
        "wall_s": round(dt, 3),
        "slots": slots,
        "page_size": page,
        "open_loop_gap_s": gap_s,
        # prefix-cache headline: the shared-prefix trace's measured
        # vs analytic skipped-prefill tokens and the TTFT split
        "prefix": engine.prefix,
        "shared_prompt_tokens": shared_len,
        "prompt_tokens": tel.get("prompt_tokens", 0),
        "prefill_tokens_skipped": tel.get("prefill_tokens_skipped", 0),
        "prefill_tokens_skipped_analytic": analytic,
        "prefix_hit_rate": round(tel.get("prefix_hit_rate", 0.0), 4),
        "ttft_s": round(tel.get("ttft_s", 0.0), 4),
        "ttft_mean_s": round(tel.get("ttft_mean_s", 0.0), 4),
        "ttft_max_s": round(tel.get("ttft_max_s", 0.0), 4),
        "decode_step_ms": round(
            tel.get("decode_step_s", 0.0) * 1e3, 3),
        # the zero-steady-state-recompile claim, in the artifact: the
        # measured engine rides the warmup's executables — all hits
        "compiles": stats["compiles"],
        "compile_cache_hits": stats["hits"],
        "warmup_compiles": warmup_compiles,
        # true per-slot cache footprint (codes + scale arrays when the
        # cache stores int8) — the capacity-per-HBM-byte headline
        "kv_dtype": stats["kv_dtype"],
        "kv_bytes_per_slot": stats["kv_bytes_per_slot"],
        "telemetry": tel,
    }
    _emit(result, quick)


def bench_infer_tiers():
    """Tiered-KV-cache A/B: ``python bench.py --infer --tiers``.

    Three arms over the same trace — a shared system prefix warmed
    once, eviction pressure that forces it out of HBM, then a
    re-admission wave: ``flat`` (no spill tiers — every evicted page
    is re-prefilled), ``tiered_int8`` (host-DRAM pool + object store,
    int8 spill — the default wire format) and ``tiered_f32``
    (``spill_dtype=model`` — exact but ~``itemsize x`` the bytes).
    Prints ONE JSON line: per-arm per-tier hit counts and rates, the
    re-admission wave's TTFT split by the tier that served it,
    measured spill/fetch bytes+seconds against the analytic per-page
    pricing (int8 moves ``head_dim + 4`` bytes per cached vector vs
    ``head_dim * itemsize``), and the compile counters (tier installs
    scatter between ticks — a tiered arm must compile NOTHING beyond
    the flat arm's executables).  The model is a toy configuration on
    every platform, so the line is a ``quick_check`` (counts only).
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.inference import InferenceEngine, KVPageStore
    from ray_tpu.inference.kv_cache import handoff_page_bytes
    from ray_tpu.models.gpt import GPTConfig, init_params

    # toy shapes on every platform: a path check, never a measurement
    _, platform, _ = _target()
    quick = True
    cfg = GPTConfig(vocab_size=2048, d_model=128, n_layers=2,
                    n_heads=4, max_seq=256, dtype=jnp.float32)
    slots, page, max_new = 2, 16, 4
    buckets = (16, 32, 64, 128)
    num_pages, host_pages = 12, 4
    params = init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.RandomState(23)
    shared = list(rng.randint(0, cfg.vocab_size, size=40))  # 2 pages
    warm_wave = [shared + list(rng.randint(0, cfg.vocab_size, size=3))
                 for _ in range(2)]
    pressure = [list(rng.randint(0, cfg.vocab_size, size=90))
                for _ in range(3)]
    readmit = [shared + list(rng.randint(0, cfg.vocab_size,
                                         size=4 + i))
               for i in range(4)]

    def build(**tiers):
        return InferenceEngine(
            cfg, params, slots=slots, page_size=page, buckets=buckets,
            num_pages=num_pages, telemetry=True, max_queue=0,
            executable_cache=executables, **tiers)

    executables = {}
    warmup = build()
    for p in warm_wave + pressure + readmit:
        warmup.generate([p], max_new_tokens=max_new)
    warmup_compiles = dict(warmup.compile_counts)
    del warmup

    arms = []
    for name, tiers in (
            ("flat", {}),
            ("tiered_int8",
             {"host_pages": host_pages, "spill_dtype": "int8",
              "store": KVPageStore(use_object_store=False)}),
            ("tiered_f32",
             {"host_pages": host_pages, "spill_dtype": "model",
              "store": KVPageStore(use_object_store=False)})):
        engine = build(**tiers)
        for p in warm_wave:
            engine.generate([p], max_new_tokens=max_new)
        for p in pressure:
            engine.generate([p], max_new_tokens=max_new)
        # re-admission: classify each request by the warmest tier
        # that served its prefix, TTFT split accordingly
        ttft_by = {"hbm": [], "dram": [], "store": [], "miss": []}
        for p in readmit:
            before = dict(engine.tier_hits) if engine.tiered else {
                "hbm": engine.stats()["prefix"]["hit_pages"]}
            t0 = time.monotonic()
            engine.generate([p], max_new_tokens=max_new)
            wall = time.monotonic() - t0
            served = "miss"
            if engine.tiered:
                delta = {t: engine.tier_hits[t] - before.get(t, 0)
                         for t in engine.tier_hits}
            else:
                delta = {"hbm": engine.stats()["prefix"]["hit_pages"]
                         - before["hbm"]}
            for t in ("hbm", "dram", "store"):
                if delta.get(t):
                    served = t          # deepest tier touched wins
            ttft_by[served].append(wall)
        st = engine.stats()
        tiers_st = st["tiers"]
        eligible = len(readmit) * (len(shared) // page)
        hits = dict(tiers_st["hits"]) if tiers_st["enabled"] else {
            "hbm": st["prefix"]["hit_pages"], "dram": 0, "store": 0}
        arms.append({
            "arm": name,
            "tiered": tiers_st["enabled"],
            "spill_dtype": tiers_st["spill_dtype"],
            "tier_hits": hits,
            "readmit_hit_rate": round(
                min(sum(hits.values()), eligible) / eligible, 4),
            "ttft_by_tier_ms": {
                t: round(1e3 * sum(v) / len(v), 3)
                for t, v in ttft_by.items() if v},
            "spill_bytes": tiers_st["spill_bytes"],
            "fetches": tiers_st["fetches"],
            "fetch_seconds": round(tiers_st["fetch_seconds"], 6),
            "evictions": st["prefix"]["evictions"],
            "host": tiers_st["host"],
            "store": tiers_st["store"],
            # steady state: every arm rides the warmup's executables
            "compiles": st["compiles"],
        })
        assert sum(st["compiles"].values()) == 0, (name,
                                                   st["compiles"])
        assert engine.leak_free(), name

    head_dim = cfg.d_model // cfg.n_heads
    kw = dict(n_layers=cfg.n_layers, page_size=page,
              n_heads=cfg.n_heads, head_dim=head_dim)
    result = {
        "metric": "infer_tiered_kv_ab",
        "platform": platform,
        "page_size": page,
        "num_pages": num_pages,
        "host_pages": host_pages,
        "shared_prompt_tokens": len(shared),
        # analytic per-page spill pricing: what one demoted page costs
        # on the host-DRAM/object-store legs per format
        "page_bytes_analytic": {
            "int8": handoff_page_bytes(itemsize=1, quantized=True,
                                       **kw),
            "f32": handoff_page_bytes(itemsize=4, quantized=False,
                                      **kw),
        },
        "warmup_compiles": warmup_compiles,
        "arms": arms,
    }
    _emit(result, quick)


def bench_infer_spec():
    """Speculative-decoding headline: self-drafting draft-and-verify.

    ``python bench.py --infer --spec``.  Runs the latency-bound
    sequential-decode regime (one request in flight — the decode-tier
    shape the disagg split carves out, where every emitted token costs
    a full dispatch) over two traffic mixes: ``templated`` (shared
    system prefix plus a per-request motif repeated verbatim — the
    structured traffic self-drafting targets) and ``random`` (i.i.d.
    prompt tokens — the adversarial mix where drafts mostly miss and
    speculation must not lose much).  Arms: speculation off and
    ``k`` in {2, 4, 8}, greedy sampling throughout.  Prints ONE JSON
    line — per-arm decode tokens/s and speedup vs the off arm, accept
    rate and per-verify accepted-token histogram, p99 inter-token gap
    (accepted bursts land together, so the spec arms' gap distribution
    collapses toward zero between dispatch walls), bit-exact output
    parity vs the off arm (the exactness claim, in the artifact), the
    compile counters (measured engines ride a warmed executable cache:
    zero compiles, verify buckets included) and the leak audit (pages,
    slots and drafter states all released after every arm).  On CPU
    the model shrinks to a smoke configuration whose greedy
    trajectories collapse into repetition loops — the drafter's
    high-accept regime; real structured traffic reaches it through
    template/quote copying instead.
    """
    import time as _time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.inference import InferenceEngine, SamplingParams
    from ray_tpu.models.gpt import GPTConfig, init_params

    devices, platform, quick = _target()
    if quick:
        cfg = GPTConfig(vocab_size=256, d_model=64, n_layers=2,
                        n_heads=4, max_seq=512, dtype=jnp.float32)
        requests, max_new = 4, 384
    else:
        cfg = GPTConfig.gpt2(vocab_size=50304, max_seq=1024,
                             dtype=jnp.bfloat16)
        requests, max_new = 4, 512
    slots, page = 2, 16
    params = init_params(cfg, jax.random.PRNGKey(0))

    rng = np.random.RandomState(1)
    shared = rng.randint(0, cfg.vocab_size, 48).tolist()
    mixes = {
        # shared system prefix + a per-request 6-token motif repeated
        # 4x: the trailing-n-gram index locks onto the motif period
        # immediately, and the tiny greedy model's own repetition
        # loops extend the high-accept stretch through the generation
        "templated": [shared + rng.randint(0, cfg.vocab_size, 6)
                      .tolist() * 4 for _ in range(requests)],
        "random": [rng.randint(0, cfg.vocab_size, 72).tolist()
                   for _ in range(requests)],
    }

    def pct(xs, q):
        return round(sorted(xs)[int(q * (len(xs) - 1))], 6) if xs \
            else None

    def run_arm(prompts, k, executables, measure):
        sp = SamplingParams(spec=k > 0, spec_k=k if k else None)
        eng = InferenceEngine(cfg, params, slots=slots,
                              page_size=page, telemetry=measure,
                              max_queue=0, executable_cache=executables)
        free0 = eng.stats()["free_pages"]
        outs, gaps = [], []
        t0 = _time.perf_counter()
        for p in prompts:
            eng.submit(p, max_new_tokens=max_new, sampling=sp)
            toks, first = [], True
            last = _time.perf_counter()
            while eng.has_work():
                for ev in eng.step():
                    now = _time.perf_counter()
                    if first:
                        first = False       # prefill TTFT, not a gap
                    else:
                        gaps.append(now - last)
                    last = now
                    toks.append(ev[1])
            outs.append(toks)
        dt = _time.perf_counter() - t0
        st = eng.stats()
        tel = eng.telemetry.summary() if measure else {}
        leak_free = (st["free_pages"] == free0
                     and st["free_slots"] == slots
                     and st["spec"]["drafts"] == 0)
        return {"outs": outs, "wall_s": dt, "gaps": gaps, "stats": st,
                "telemetry": tel, "leak_free": leak_free}

    # one warmup engine per arm shape is wasteful — a single shared
    # executable cache covers every arm (prefill bucket, cached-
    # context prefill for the shared-prefix hit — hence two warmup
    # prompts — decode, and one verify executable per power-of-two k
    # bucket), so the first pass compiles and every measured engine
    # below shows zero
    executables = {}
    for k in (0, 2, 4, 8):
        run_arm(mixes["templated"][:2], k, executables, measure=False)

    arms = {}
    for mix, prompts in mixes.items():
        base = None
        for k in (0, 2, 4, 8):
            a = run_arm(prompts, k, executables, measure=True)
            tps = a["telemetry"].get("decode_tokens_per_sec", 0.0)
            if k == 0:
                base = {"tps": tps, "outs": a["outs"]}
            spec = a["stats"]["spec"]
            arms[f"{mix}_k{k}"] = {
                "decode_tokens_per_sec": round(tps, 1),
                "speedup_vs_off": round(tps / base["tps"], 3)
                if base["tps"] else None,
                "accept_rate": round(spec["accept_rate"], 4),
                "accepted_hist": spec["k_hist"],
                "inter_token_p50_s": pct(a["gaps"], 0.50),
                "inter_token_p99_s": pct(a["gaps"], 0.99),
                "greedy_parity": a["outs"] == base["outs"],
                "compiles": a["stats"]["compiles"],
                "leak_free": a["leak_free"],
                "wall_s": round(a["wall_s"], 3),
            }

    result = {
        "metric": "gpt2_infer_spec_decode_speedup",
        # headline: the templated mix at the default draft budget
        "value": arms["templated_k4"]["speedup_vs_off"],
        "unit": "decode tok/s at spec_k=4 vs non-speculative "
                "(templated mix, sequential requests)",
        "platform": platform,
        "model_params": None if quick else 124_000_000,
        "requests": requests,
        "max_new_tokens": max_new,
        "slots": slots,
        "page_size": page,
        "arms": arms,
    }
    _emit(result, quick)


def bench_infer_lora():
    """Multi-tenant LoRA A/B: ``python bench.py --infer --lora``.

    Two experiments over one warmed executable cache.  (1) Tenant-count
    sweep on a single engine: decode tokens/s under 0 (base), 1, 8 and
    64 distinct tenants round-robined through a bank with 8 cache
    slots — 1 and 8 are steady-state resident (every request a cache
    hit), 64 is the churn regime (evictions + store reloads on the
    request path).  The grouped-gather decode applies per-slot factors,
    so the per-token cost is flat in resident tenant count; churn pays
    only the eager bank installs.  (2) Router A/B: a two-replica fleet
    serving 6 tenants with adapter affinity on vs residency-blind
    (``adapter_affinity=False``) — reports per-arm adapter cache hit
    rate and store loads (the affinity arm pins tenants to the replica
    whose bank already holds them, so its miss/load count collapses).
    Prints ONE JSON line; compile counters must stay frozen across
    every arm (adapters are call args, never exec-key material), and
    every engine must pass the leak audit (slots, pages, pins, store
    ``in_flight``).  The model is a toy configuration on every platform,
    so the line is a ``quick_check`` (counts only).
    """
    import time as _time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.adapters import AdapterStore, LoraConfig, init_adapter
    from ray_tpu.adapters import adapter_nbytes
    from ray_tpu.fleet import EngineReplica, FleetConfig, FleetRouter
    from ray_tpu.inference import InferenceEngine, SamplingParams
    from ray_tpu.models.gpt import GPTConfig, init_params

    # toy shapes on every platform: a path check, never a measurement
    _, platform, _ = _target()
    quick = True
    cfg = GPTConfig(vocab_size=2048, d_model=128, n_layers=2,
                    n_heads=4, max_seq=256, dtype=jnp.float32)
    slots, page, max_new = 2, 16, 8
    buckets = (16, 32)
    lcfg = LoraConfig(enabled=True, rank=8, scale=0.5, cache_slots=8)
    params = init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.RandomState(25)
    store = AdapterStore(use_object_store=False)
    tenants = [f"tenant-{i:02d}" for i in range(64)]
    for i, mid in enumerate(tenants):
        store.put(mid, init_adapter(cfg, lcfg, jax.random.PRNGKey(i),
                                    random_b=True), scale=0.5)
    publish_bytes = store.stats()["bytes_published"] // len(tenants)
    full_bytes = sum(np.asarray(v).nbytes
                     for v in jax.tree.leaves(params))

    executables = {}

    def build():
        return InferenceEngine(
            cfg, params, slots=slots, page_size=page, buckets=buckets,
            telemetry=False, max_queue=0, lora=lcfg,
            adapter_store=store, executable_cache=executables)

    prompts = [list(rng.randint(1, cfg.vocab_size, size=9))
               for _ in range(16)]
    warmup = build()
    warmup.generate([prompts[0]], max_new_tokens=max_new)
    warmup.generate(
        [prompts[1]], max_new_tokens=max_new,
        sampling=SamplingParams(temperature=0.0, model_id=tenants[0]))
    warmup_compiles = dict(warmup.compile_counts)
    del warmup

    # ---- (1) tenant-count sweep + churn on one engine ----
    arms = []
    for n_tenants in (0, 1, 8, 64):
        engine = build()
        reqs = 32
        t0 = _time.monotonic()
        emitted = 0
        for i in range(reqs):
            mid = (tenants[i % n_tenants] if n_tenants else None)
            out = engine.generate(
                [prompts[i % len(prompts)]], max_new_tokens=max_new,
                sampling=SamplingParams(temperature=0.0, model_id=mid))
            emitted += len(out[0])
        wall = _time.monotonic() - t0
        st = engine.stats()
        ad = st["adapters"] if n_tenants else {}
        arms.append({
            "tenants": n_tenants,
            "decode_tok_s": round(emitted / wall, 2),
            "requests": reqs,
            "cache_hits": ad.get("hits", 0),
            "loads": ad.get("loads", 0),
            "evictions": ad.get("evictions", 0),
            "load_seconds": ad.get("load_seconds", 0.0),
            "compiles": st["compiles"],
        })
        assert sum(st["compiles"].values()) == 0, (n_tenants,
                                                   st["compiles"])
        assert engine.leak_free(), n_tenants
    base_tok_s = arms[0]["decode_tok_s"]
    for arm in arms:
        arm["vs_base"] = round(arm["decode_tok_s"] / base_tok_s, 4)

    # ---- (2) adapter-affinity vs residency-blind routing ----
    ab = []
    for affinity_on in (True, False):
        replicas = [EngineReplica(f"r{i}", build()) for i in range(2)]
        fcfg = FleetConfig(retries=2, affinity=True,
                           adapter_affinity=affinity_on, hedge=False,
                           dwell=1.0, backoff=1.0)
        router = FleetRouter(replicas, cfg=fcfg, rng_seed=7)
        mix = tenants[:6]
        streams = []
        for i in range(36):
            streams.append(router.remote({
                "tokens": prompts[i % len(prompts)],
                "max_new_tokens": max_new,
                "model_id": mix[i % len(mix)]}))
            if len(streams) >= 4:
                streams.pop(0).result()
        for s in streams:
            s.result()
        hits = misses = loads = 0
        for r in replicas:
            ad = r.engine.stats()["adapters"]
            hits += ad["hits"]
            misses += ad["misses"]
            loads += ad["loads"]
            assert r.leak_free(), r.id
        ab.append({
            "arm": ("adapter_affinity" if affinity_on
                    else "residency_blind"),
            "adapter_cache_hit_rate": round(hits / (hits + misses), 4),
            "loads": loads,
            "evictions": sum(
                r.engine.stats()["adapters"]["evictions"]
                for r in replicas),
        })
    assert store.stats()["in_flight"] == 0

    result = {
        "metric": "infer_lora_ab",
        "platform": platform,
        "rank": lcfg.rank,
        "cache_slots": lcfg.cache_slots,
        "published_tenants": len(tenants),
        # the adapter-only publish win: bytes per republish vs the
        # full-weights payload the store replaces
        "publish_bytes_per_adapter": int(publish_bytes),
        "full_params_bytes": int(full_bytes),
        "publish_shrink_x": round(full_bytes / publish_bytes, 1),
        "warmup_compiles": warmup_compiles,
        "tenant_sweep": arms,
        "router_ab": ab,
    }
    _emit(result, quick)


def bench_rl():
    """RL-loop headline: open-loop actor/learner co-run.

    ``python bench.py --rl``.  Runs the closed train<->infer loop
    (``ray_tpu.rl.run_rl_loop``: rollout actors over the inference
    engine, a REINFORCE/RLOO learner derived from
    ``build_gpt_rl_train``, versioned weight publications, bounded
    staleness) and prints ONE JSON line — rollout tokens/s as the
    headline value, learner steps/s, weight-publish latency, mean/max
    param-version lag, the end-to-end reward curve over the run (the
    policy-improvement proof riding the artifact), and the actors'
    compile counters (weight publication must show zero steady-state
    recompiles).  Knobs come from ``RAY_TPU_RL_*`` (``rl_config``);
    ``scratch/r14_rl.py`` automates the on-chip A/B arms.  On CPU the
    model shrinks to a smoke configuration (numbers exercise the loop,
    not the hardware).
    """
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.gpt import GPTConfig
    from ray_tpu.rl import rl_config, run_rl_loop

    devices, platform, quick = _target()
    rlcfg = rl_config()
    if quick:
        cfg = GPTConfig(vocab_size=512, d_model=128, n_layers=2,
                        n_heads=4, max_seq=128, dtype=jnp.float32)
        steps, lr = 10, 2e-2
        engine_kwargs = {"slots": max(rlcfg.batch, 2), "page_size": 16,
                         "buckets": (32,)}
    else:
        cfg = GPTConfig.gpt2(vocab_size=50304, max_seq=1024,
                             dtype=jnp.bfloat16)
        steps, lr = 30, 1e-4
        engine_kwargs = {}
    result = run_rl_loop(cfg, steps=steps, rlcfg=rlcfg, seed=1, lr=lr,
                         engine_kwargs=engine_kwargs)
    tel = result["telemetry"]
    curve = result["reward_curve"]
    third = max(len(curve) // 3, 1)
    record = {
        "metric": "gpt_rl_rollout_tokens_per_sec",
        "value": round(tel.get("rollout_tokens_per_sec", 0.0), 1),
        "unit": "tokens/s",
        "platform": platform,
        "model_params": None if quick else 124_000_000,
        "learner_steps": result["steps"],
        "learner_steps_per_sec": round(
            tel.get("learner_steps_per_sec", 0.0), 3),
        "publish_s": round(tel.get("publish_s", 0.0), 5),
        "version_lag_mean": tel.get("version_lag_mean", 0.0),
        "version_lag_max": tel.get("version_lag_max", 0),
        "drops_stale": result["drops_stale"],
        "drops_overflow": result["drops_overflow"],
        "actors": rlcfg.actors,
        "rollout_batch": rlcfg.batch,
        "horizon": rlcfg.horizon,
        "baseline": rlcfg.baseline,
        "publish_every": rlcfg.publish_every,
        "param_version": result["param_version"],
        "reward_curve": [round(float(r), 4) for r in curve],
        "reward_first_third": round(float(
            sum(curve[:third]) / third), 4),
        "reward_last_third": round(float(
            sum(curve[-third:]) / third), 4),
        # the zero-recompile claim across every weight publication, in
        # the artifact: each actor compiled at most once per bucket +
        # once for decode, replicas after the first compiled nothing
        "engine_compiles": [s["compiles"]
                            for s in result["engine_stats"]],
        "telemetry": tel,
    }
    _emit(record, quick)


def bench_data():
    """Input-pipeline A/B: streamed packed batches vs preloaded arrays.

    ``python bench.py --data``.  Runs the same compiled GPT train step
    through two feeds — (a) one preloaded host-array batch (the
    r01-r16 harness: the input pipeline costs nothing by construction)
    and (b) the r17 streaming data plane (shard readers -> sample
    packer -> bounded prefetch -> double-buffered ``device_put``) —
    and prints ONE JSON line.  The acceptance target is
    ``step_delta_frac ~ 0`` (all host work hides under the step) while
    ``packed_tokens_per_batch`` beats the unpacked arm at equal
    ``[B, S]`` (the padding FLOPs the packer reclaims).  Input tok/s
    (producer side) vs trainer consumption tok/s says which side has
    headroom.  On CPU the model shrinks to a smoke configuration
    (numbers exercise the pipeline, not the hardware).
    """
    import jax
    import jax.numpy as jnp

    from ray_tpu.data import SyntheticDocs, StreamingLoader
    from ray_tpu.models import training
    from ray_tpu.models.gpt import GPTConfig
    from ray_tpu.parallel.mesh import make_mesh

    devices, platform, quick = _target()
    if quick:
        cfg = GPTConfig(vocab_size=2048, d_model=128, n_layers=2,
                        n_heads=4, max_seq=256, dtype=jnp.float32)
        batch, seq, steps = 4, 128, 8
    else:
        cfg = GPTConfig.gpt2(vocab_size=50304, max_seq=1024,
                             dtype=jnp.bfloat16, remat=False,
                             unroll_layers=True, ce_chunk=-1)
        batch, seq, steps = 24, 1024, 20
    mesh = make_mesh(dp=len(devices), devices=devices)
    fns = training.build_gpt_train(cfg, mesh, telemetry=False)
    source = SyntheticDocs(3, num_shards=8,
                           docs_per_shard=1 << 16,
                           vocab=cfg.vocab_size,
                           min_len=max(8, seq // 8),
                           max_len=max(12, (3 * seq) // 4))

    def timed(step_fn, feed, n, on_warm=None):
        state = fns["init_fn"](jax.random.PRNGKey(0))
        for _ in range(2):                      # warmup/compile
            state, metrics = step_fn(state, feed())
            float(metrics["loss"])
        if on_warm is not None:                 # steady state begins
            on_warm()
        t0 = time.perf_counter()
        for _ in range(n):
            state, metrics = step_fn(state, feed())
        float(metrics["loss"])
        return (time.perf_counter() - t0) / n, float(metrics["loss"])

    # arm A: ONE preloaded packed batch — same pytree, same
    # segment-masked attention path, same compiled step as the
    # streaming arm, so the delta isolates the FEED (reads, packing,
    # queue, transfer), not a different computation
    with StreamingLoader(source, batch_size=batch, seq_len=seq,
                         seed=0, pack=True, device_put=False) as warm:
        pre = jax.device_put(warm.next().batch, fns["batch_sharding"])
    pre_step_s, _ = timed(fns["step_fn"], lambda: pre, steps)

    # arm B: the streaming plane (packed, segment-masked); the
    # consumption-rate clock and token counter start AFTER warmup so
    # trainer_tok_s is steady-state, not diluted by the jit compile
    packed_consumed, t_run0 = [0], [0.0]
    with StreamingLoader(source, batch_size=batch, seq_len=seq,
                         seed=0, pack=True,
                         sharding=fns["batch_sharding"]) as loader:
        def feed():
            sb = loader.next()
            packed_consumed[0] += sb.packed_tokens
            return sb.batch

        def on_warm():
            packed_consumed[0] = 0
            t_run0[0] = time.perf_counter()
        stream_step_s, _ = timed(fns["step_fn"], feed, steps, on_warm)
        run_wall = time.perf_counter() - t_run0[0]
        data_summary = loader.telemetry.summary()

    # unpacked control at equal [B, S]: tokens per batch without the
    # packer (each document pads its own row)
    with StreamingLoader(source, batch_size=batch, seq_len=seq,
                         seed=0, pack=False,
                         device_put=False) as unpacked:
        un_tokens = [unpacked.next().packed_tokens for _ in range(4)]

    trainer_tok_s = packed_consumed[0] / run_wall if run_wall else 0.0
    result = {
        "metric": "data_plane_step_delta",
        "value": round((stream_step_s - pre_step_s) / pre_step_s, 4)
        if pre_step_s else 0.0,
        "unit": "frac vs preloaded",
        "platform": platform,
        "n_devices": len(devices),
        "batch": batch, "seq": seq, "steps": steps,
        "preloaded_step_s": round(pre_step_s, 6),
        "stream_step_s": round(stream_step_s, 6),
        "input_tok_s": data_summary.get("input_tok_s", 0.0),
        "trainer_tok_s": round(trainer_tok_s, 1),
        "packed_tokens_per_batch": data_summary.get(
            "packed_tokens_per_batch", 0.0),
        "unpacked_tokens_per_batch": round(
            sum(un_tokens) / len(un_tokens), 1),
        "grid_tokens_per_batch": batch * seq,
        "stall_s_total": data_summary.get("stall_s_total", 0.0),
        "prefetch_depth_mean": data_summary.get(
            "prefetch_depth_mean", 0.0),
        "telemetry": {"data": data_summary},
    }
    _emit(result, quick)


def bench_elastic():
    """Elastic-training A/B: gradient-accumulation overhead + the
    cross-mesh reshard cost.

    ``python bench.py --elastic``.  Two questions, one JSON line:

    (a) What does global-batch invariance cost?  The same global batch
    runs through ``build_gpt_train(accum_steps=k)`` for k in {1, 2, 4}
    — identical arithmetic, k sequential microbatches — so the step
    delta vs k=1 is pure accumulation overhead (per-microbatch
    dispatch + the f32 grad-accumulator traffic).  Acceptance target:
    the added cost per extra microbatch stays ~ the per-microbatch
    dispatch cost, not a step-shaped constant.

    (b) What does a topology transition cost?  ``reshard_state`` moves
    the full TrainState host->new-mesh for an 8->4 shrink and the 4->8
    expand (the window in which no step runs — the elastic loop's
    ``train_reshard_seconds``).

    Needs 8 visible devices for (b) and fails with fewer (no re-exec
    on a host-simulated mesh: it would time nothing real, and a parent
    that has touched jax holds the chips a child would need).
    """
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import training
    from ray_tpu.models.gpt import GPTConfig
    from ray_tpu.parallel.mesh import make_mesh
    from ray_tpu.resilience.elastic import host_state, reshard_state

    devices, platform, quick = _target()
    if len(devices) < 8:
        raise SystemExit(f"--elastic needs 8 devices; {len(devices)} "
                         f"{platform} device(s) visible")
    # global batch 32: divisible by fsdp=8 x accum 4, so every arm
    # shards whole microbatches (validate_divisibility would name the
    # fix otherwise)
    if quick:
        cfg = GPTConfig(vocab_size=2048, d_model=128, n_layers=2,
                        n_heads=4, max_seq=256, dtype=jnp.float32)
        batch, seq, steps = 32, 128, 6
    else:
        cfg = GPTConfig.gpt2(vocab_size=50304, max_seq=1024,
                             dtype=jnp.bfloat16, remat=False,
                             unroll_layers=True, ce_chunk=-1)
        batch, seq, steps = 32, 1024, 12
    mesh = make_mesh(fsdp=8, devices=devices[:8])
    from ray_tpu.parallel.mesh import validate_divisibility
    validate_divisibility(mesh, batch=batch, accum_steps=4)
    batch_data = training.synthetic_lm_batch(
        jax.random.PRNGKey(1), batch, seq, cfg.vocab_size)

    # (a) accumulation overhead at fixed global batch
    arms = []
    for k in (1, 2, 4):
        fns = training.build_gpt_train(cfg, mesh, accum_steps=k,
                                       telemetry=False)
        state = fns["init_fn"](jax.random.PRNGKey(0))
        for _ in range(2):                       # warmup/compile
            state, metrics = fns["step_fn"](state, batch_data)
            float(metrics["loss"])
        t0 = time.perf_counter()
        for _ in range(steps):
            state, metrics = fns["step_fn"](state, batch_data)
        final_loss = float(metrics["loss"])       # forces the chain
        step_s = (time.perf_counter() - t0) / steps
        arms.append({"accum_steps": k, "step_s": round(step_s, 6),
                     "loss": round(final_loss, 4)})
        del state, fns
    base_s = arms[0]["step_s"]
    for a in arms:
        a["overhead_frac"] = round((a["step_s"] - base_s) / base_s, 4) \
            if base_s else 0.0
        if a["accum_steps"] > 1:
            a["overhead_per_microbatch_s"] = round(
                (a["step_s"] - base_s) / (a["accum_steps"] - 1), 6)

    # (b) reshard cost: 8 -> 4 (accum doubles) and back
    full = training.build_gpt_train(cfg, mesh, accum_steps=1,
                                    telemetry=False)
    half_mesh = make_mesh(fsdp=4, devices=devices[:4])
    half = training.build_gpt_train(cfg, half_mesh, accum_steps=2,
                                    telemetry=False)
    state = full["init_fn"](jax.random.PRNGKey(0))
    t0 = time.perf_counter()
    snap = host_state(state)
    state4 = reshard_state(snap, half["state_shardings"])
    jax.block_until_ready(state4)
    shrink_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    state8 = reshard_state(state4, full["state_shardings"])
    jax.block_until_ready(state8)
    expand_s = time.perf_counter() - t0

    result = {
        "metric": "elastic_accum_overhead",
        "value": arms[1]["overhead_frac"],
        "unit": "frac step time at accum_steps=2 vs 1 (global batch "
                "fixed)",
        "platform": platform,
        "n_devices": len(devices),
        "batch": batch, "seq": seq, "steps": steps,
        "mesh": dict(mesh.shape),
        "accum_arms": arms,
        "reshard": {"shrink_8_to_4_s": round(shrink_s, 6),
                    "expand_4_to_8_s": round(expand_s, 6)},
    }
    _emit(result, quick)


def main():
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import training
    from ray_tpu.models.gpt import GPTConfig
    from ray_tpu.parallel.mesh import make_mesh

    if "--elastic" in sys.argv:
        bench_elastic()
        return
    if "--data" in sys.argv:
        bench_data()
        return
    if "--infer" in sys.argv:
        n = _replicas_arg()
        if "--tiers" in sys.argv:
            bench_infer_tiers()
        elif "--lora" in sys.argv:
            bench_infer_lora()
        elif "--spec" in sys.argv:
            bench_infer_spec()
        elif "--trace" in sys.argv:
            # the attribution report wants the full disagg + tiers
            # path in frame: >= 1 prefill + >= 2 decode replicas
            bench_infer_trace(n if n > 1 else 3)
        elif "--gray" in sys.argv:
            # the demotion median wants an odd-one-out: 3+ replicas
            bench_infer_gray(n if n > 1 else 3)
        elif "--disagg" in sys.argv or _fleet_disagg_env():
            # the split needs >= 1 prefill + >= 2 decode to show the
            # interference delta: 3+ replicas
            bench_infer_disagg(n if n > 1 else 3)
        elif n > 1:
            bench_infer_fleet(n)
        else:
            bench_infer()
        return
    if "--rl" in sys.argv:
        bench_rl()
        return
    mesh_arg = _mesh_arg()
    if mesh_arg is not None:
        bench_mesh(mesh_arg)
        return

    devices, platform, quick = _target()

    if quick:
        cfg = GPTConfig(vocab_size=2048, d_model=128, n_layers=2,
                        n_heads=4, max_seq=256, dtype=jnp.float32)
        batch, seq, steps = 4, 128, 4
    else:
        # Tuned single-chip recipe (profiled on v5e): unrolled layer
        # loop (scan residual stashing costs ~20%/step), no-remat CE
        # (backward reuses saved logits: one fewer full vocab matmul),
        # fused-backward 1024x1024 flash blocks, bf16 rope rotation,
        # batch 24 un-rematerialized.
        cfg = GPTConfig.gpt2(vocab_size=50304, max_seq=1024,
                             dtype=jnp.bfloat16, remat=False,
                             unroll_layers=True, ce_chunk=-1)
        batch, seq, steps = 24, 1024, 40

    from ray_tpu.ops.attention import uses_pack2
    from ray_tpu.ops.flash_ce import ce_config, uses_flash_ce
    from ray_tpu.ops.fused_norm import out_proj_norm_plan
    mesh = make_mesh(dp=len(devices), devices=devices)
    # the kernels' own dispatch gates (head_dim/even heads/tileability
    # for pack2; mode/model-dim for flash-CE; norm/bias/shape for the
    # fused norm epilogues), so the reported fields are what runs: the
    # step is built with no pins, and a kernel Mosaic refuses fails the
    # run instead of being traded for another behind a printed number
    attn_pack2 = uses_pack2(seq, seq, cfg.n_heads, cfg.head_dim)
    fuse_norm = bool(out_proj_norm_plan(
        batch * seq, cfg.n_heads * cfg.head_dim, cfg.d_model,
        norm=cfg.norm, has_bias=cfg.use_bias, n_devices=len(devices),
        seq=seq))
    if uses_flash_ce(batch * seq, cfg.d_model, cfg.vocab_size,
                     n_devices=len(devices)):
        ce = "flash"
    elif ce_config().mode == "fused" and cfg.ce_chunk < 0:
        ce = "fused"     # plain XLA: dispatches on any mesh
    else:
        ce = "noremat" if cfg.ce_chunk < 0 else "chunked"

    # bench owns its recorder (AOT mode: exact compile split + HBM
    # memory_analysis) instead of the builders' default light wrap.
    # profile_dir is forced off: the xplane capture starts at warmup
    # step 1 and would still be running through the timed headline loop
    # (use scratch/r9_telemetry.py for captures).
    import ray_tpu.telemetry as tel_mod
    fns = training.build_gpt_train(cfg, mesh, telemetry=False)
    fns = tel_mod.instrument(
        fns, cfg, mesh, comm_mode=fns["comm_mode"], label="bench",
        aot=True, config=tel_mod.TelemetryConfig(
            enabled=tel_mod.telemetry_config().enabled))
    state = fns["init_fn"](jax.random.PRNGKey(0))
    batch_data = training.synthetic_lm_batch(
        jax.random.PRNGKey(1), batch, seq, cfg.vocab_size)
    for _ in range(2):                           # warmup/compile
        state, metrics = fns["step_fn"](state, batch_data)
        float(metrics["loss"])

    # the timed headline loop must NOT run through the telemetry
    # wrapper: its per-step blocking sync would serialize host dispatch
    # into the figure and break comparability with r05-r08 JSON.  The
    # AOT executable is the same compiled program the wrapped warmup
    # ran (no recompile); with telemetry off, raw_step is the jit call.
    tel = fns.get("telemetry")
    raw_step = ((tel.compiled_step() if tel else None)
                or fns.get("raw_step_fn", fns["step_fn"]))
    t0 = time.perf_counter()
    for _ in range(steps):
        state, metrics = raw_step(state, batch_data)
    # fetching the last loss forces the whole state-dependency chain
    float(metrics["loss"])
    dt = time.perf_counter() - t0
    if tel:
        # short instrumented window AFTER the measurement:
        # steady-state telemetry stats come from per-step blocking
        # syncs outside the timed loop
        for _ in range(3):
            state, metrics = fns["step_fn"](state, batch_data)

    tokens_per_step = batch * seq
    tok_s = steps * tokens_per_step / dt
    tok_s_chip = tok_s / len(devices)

    from ray_tpu.models.gpt import num_params
    n_params = num_params(state.params)
    flops_per_token = 6 * n_params
    tflops = tok_s_chip * flops_per_token / 1e12
    result = {
        "metric": "gpt2_train_tokens_per_sec_per_chip",
        "value": round(tok_s_chip, 1),
        "unit": "tokens/s/chip",
        "vs_baseline": round(tok_s_chip / H100_GPT2_TOKENS_PER_SEC, 4),
        "platform": platform,
        "n_devices": len(devices),
        "model_params": n_params,
        "achieved_tflops_per_chip": round(tflops, 2),
        "final_loss": round(float(metrics["loss"]), 4),
        # which schedules the dispatch gates chose: two-head
        # lane-packed attention, the CE path (flash/fused/noremat/
        # chunked), and the fused norm epilogues (out-proj +
        # ln_f-in-flash-CE)
        "attn_pack2": attn_pack2,
        "ce": ce,
        "fuse_norm": fuse_norm,
        # comm-schedule fields, so headline and --mesh records stay
        # comparable (headline is a dp-mesh GSPMD run; the overlap
        # schedule is --mesh territory)
        "mesh": dict(mesh.shape),
        "comm_mode": fns["comm_mode"],
        "comm_quant": fns.get("comm_quant", "none"),
        "collective_bytes_per_step": _collective_bytes(
            cfg, mesh, batch, seq, fns["comm_mode"],
            fns.get("comm_quant", "none")),
        # per-step telemetry (compile split, blocking-sync step time,
        # analytic-FLOPs MFU, HBM memory_analysis, collective bytes);
        # {"enabled": False} under RAY_TPU_TELEMETRY=0
        "telemetry": tel.summary() if tel else {"enabled": False},
    }
    if not quick:
        # toy shapes on a CPU have no chip to price MFU against
        # (chip_peak_tflops refuses devices outside its table)
        from ray_tpu.telemetry.flops import chip_peak_tflops
        result["chip_peak_tflops"] = chip_peak_tflops(devices[0])
        result["mfu"] = round(tflops / result["chip_peak_tflops"], 4)
    if tel:
        tel.stop()
    _emit(result, quick)

    if "--components" in sys.argv and not quick:
        # step-component view: attention fwd+bwd and the CE loss head
        # in isolation, custom schedule vs control, so a kernel A/B
        # needs no xplane trace
        from ray_tpu._private.ray_perf import (attention_perf, ce_perf,
                                               fused_norm_perf)
        arms = (True, False) if attn_pack2 else (False,)
        for pack2 in arms:
            comp = attention_perf(batch=batch, seq=seq,
                                  heads=cfg.n_heads,
                                  head_dim=cfg.head_dim, pack2=pack2)
            comp["metric"] = "attention_fwd_bwd"
            print(json.dumps(comp))
        ce_arms = ("flash", "noremat") if ce == "flash" \
            else ("noremat",)
        for mode in ce_arms:
            comp = ce_perf(n_tokens=batch * seq, d_model=cfg.d_model,
                           vocab=cfg.vocab_size, mode=mode)
            comp["metric"] = "ce_fwd_bwd"
            print(json.dumps(comp))
        norm_arms = (True, False) if fuse_norm else (False,)
        for fused in norm_arms:
            comp = fused_norm_perf(n_tokens=batch * seq,
                                   heads=cfg.n_heads,
                                   head_dim=cfg.head_dim,
                                   d_model=cfg.d_model, fused=fused)
            comp["metric"] = "fused_norm_epilogue"
            comp["fuse_norm"] = fused
            print(json.dumps(comp))


if __name__ == "__main__":
    main()
