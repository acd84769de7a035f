"""A serve cell: ``traffic.kind`` ``open_loop`` (arrivals on a schedule,
tails judged) or ``closed_loop`` (callers that wait for their reply,
tokens per second judged).

The parent is the client.  It deploys the replica through ``serve.run``
as a user would, sends requests through the handle's streaming path and
times tokens as they arrive.  It never touches jax."""

from __future__ import annotations

import argparse
import json
import os
import shutil
import threading
import time
from typing import Any, Dict, List

from benchmark.harness import client, common
from benchmark.harness import traffic as traffic_mod

TRACE_SECONDS = 5.0          # a short traced piece in the window's middle
CALL_TIMEOUT_S = 600


def _deploy(config: Dict[str, Any], engine: Dict[str, Any], seed: int,
            clients: int):
    import jax.numpy as jnp

    import ray_tpu.serve as serve
    from benchmark.harness.replica import BenchReplica
    from ray_tpu.inference.serve_gpt import GPTDeployment
    model = config.get("serve_model", config["model"])
    kwargs = dict(model["kwargs"])
    kwargs["dtype"] = getattr(jnp, kwargs["dtype"])
    deployment = serve.deployment(
        BenchReplica, name="gpt",
        max_ongoing_requests=clients + 16,
        ray_actor_options=GPTDeployment.ray_actor_options)
    return serve.run(deployment.bind(
        model=model["preset"], model_config=kwargs,
        engine_config=dict(engine),
        seed=seed & 0x7FFFFFFF), name="bench")


def _call(handle, method: str, *args):
    return getattr(handle, method).remote(*args).result(
        timeout_s=CALL_TIMEOUT_S)


def _run_all(handle, requests, concurrency: int) -> List[client.Outcome]:
    """Set-up traffic: every request once, ``concurrency`` at a time."""
    outcomes = [client.Outcome(index=i, due=0.0)
                for i in range(len(requests))]
    todo = list(range(len(requests)))
    lock = threading.Lock()

    def worker():
        while True:
            with lock:
                if not todo:
                    return
                i = todo.pop(0)
            outcomes[i].due = time.monotonic()
            client.stream(handle, requests[i], outcomes[i])

    threads = [threading.Thread(target=worker, daemon=True)
               for _ in range(min(concurrency, len(requests)))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(CALL_TIMEOUT_S)
    bad = [o for o in outcomes if not o.ok]
    if bad:
        raise RuntimeError(f"set-up request failed: {bad[0]}")
    return outcomes


def _stand_ins(shapes, prefix: List[int], info, seed: int):
    """Warm-up requests: the cached prefix every request shares, then
    fresh tokens to the same fill length as the request they stand for,
    two new tokens (so the decode executable is warm too)."""
    rng = traffic_mod.rng_for(seed, "warmup")
    page = info["page_size"]
    out = []
    for r in shapes:
        cached = (min(r.cached_prefix, len(r.prompt) - 1) // page) * page
        fill = len(r.prompt) - cached
        shared = prefix[:(min(len(prefix), cached) // page) * page]
        if not shared:
            fill = len(r.prompt)
        body = rng.integers(0, info["vocab_size"], size=fill).tolist()
        out.append(traffic_mod.Request(index=-1, prompt=shared + body,
                                       max_new_tokens=2))
    return out


class _Tracer:
    """Starts and stops the replica's profiler around a short piece in
    the middle of the window, from a thread of its own."""

    def __init__(self, handle, trace_dir: str, start_after_s: float):
        self.dir = trace_dir
        self._thread = threading.Thread(
            target=self._run, args=(handle, start_after_s), daemon=True)
        self.error = None

    def _run(self, handle, start_after_s):
        try:
            time.sleep(start_after_s)
            _call(handle, "bench_start_trace", self.dir)
            time.sleep(TRACE_SECONDS)
            _call(handle, "bench_stop_trace")
        except BaseException as e:  # noqa: BLE001 — reported by join
            self.error = e

    def start(self):
        self._thread.start()

    def join(self):
        self._thread.join(CALL_TIMEOUT_S)
        if self.error is not None:
            raise self.error


def _engine_facts(rec: Dict[str, Any]) -> Dict[str, Any]:
    facts: Dict[str, Any] = {
        "compiles_in_window": rec["compiles"],
        "engine_compiles_in_window": rec["engine_compiles"],
        "waiting_at_end": rec["waiting"],
    }
    if rec["tick_s"]:
        facts["engine_tick_ms"] = 1e3 * common.median(rec["tick_s"])
    live = [c for c in rec["context_tokens"] if c]
    if live:
        facts["decode_context_tokens"] = sum(live) / len(live)
    if rec["decode_active"]:
        facts["decode_tok_per_step"] = (sum(rec["decode_active"])
                                        / len(rec["decode_active"]))
        facts["decode_wall_ms"] = 1e3 * common.median(rec["decode_wall_s"])
    if rec["queue_wait_s"]:
        facts["queue_wait_p50_ms"] = 1e3 * common.median(rec["queue_wait_s"])
    if rec["prompt_tokens"]:
        facts["prefix_hit_share"] = (100.0 * rec["hit_tokens"]
                                     / rec["prompt_tokens"])
    if rec["ttft_s"]:
        facts["replica_ttft_p50_ms"] = 1e3 * common.median(rec["ttft_s"])
    if rec["prefill_wall_s"]:
        facts["prefill_wall_ms"] = 1e3 * common.median(rec["prefill_wall_s"])
    return facts


def _phase_seconds(t_start: float, phases: Dict[str, float]
                   ) -> Dict[str, float]:
    """Seconds each set-up phase took, in order, from process start."""
    out, last = {}, t_start
    for name, t in phases.items():
        out[name], last = t - last, t
    return out


def _open_loop(handle, info, mix, args, shared) -> Dict[str, Any]:
    seed, seconds = args.seed, args.seconds
    plan = traffic_mod.open_loop_requests(mix, seed, seconds,
                                          info["vocab_size"],
                                          rate_rps=shared.get("rate_rps"))
    requests = plan["requests"]
    rng = traffic_mod.rng_for(seed, "check")
    # correct: a cold request (registers the system prompt's pages), then
    # one that hits them
    samples = [{"tokens": plan["system_prompt"]
                + rng.integers(0, info["vocab_size"], size=n).tolist(),
                "max_new_tokens": 8}
               for n in (200, 333)][:int(mix["correct_sample"])]
    phases = {"deployed": time.time()}
    check = _call(handle, "bench_check", samples)
    phases["checked"] = time.time()
    shapes = traffic_mod.warmup_shapes(requests, info["page_size"],
                                       info["buckets"])
    _run_all(handle, _stand_ins(shapes, plan["system_prompt"], info, seed),
             concurrency=1)
    phases["warm"] = time.time()
    # returning users: their conversations so far are in the cache
    history = [traffic_mod.Request(index=-1, prompt=h, max_new_tokens=1)
               for h in plan["histories"]]
    _run_all(handle, history, concurrency=4)
    phases["histories"] = time.time()
    mark = _call(handle, "bench_mark")

    outcomes = [client.Outcome(index=r.index, due=0.0,
                               returning=r.returning) for r in requests]
    tracer = None
    if args.trace:
        tracer = _Tracer(handle, shared["trace_dir"], 0.35 * seconds)
    window_epoch = time.time()
    t0 = time.monotonic()

    def one(i):
        o, r = outcomes[i], requests[i]
        o.due = t0 + r.due_s
        delay = o.due - time.monotonic()
        if delay > 0:
            time.sleep(delay)
        client.stream(handle, r, o)

    threads = [threading.Thread(target=one, args=(i,), daemon=True)
               for i in range(len(requests))]
    if tracer:
        tracer.start()
    for t in threads:
        t.start()
    # the engine's records are read when the window closes, so that the
    # drain (fewer and fewer sequences a tick) does not dilute them
    time.sleep(max(0.0, t0 + seconds - time.monotonic()))
    rec = _call(handle, "bench_since", mark)
    deadline = t0 + seconds + float(mix["drain_limit_s"])
    for t in threads:
        t.join(max(0.0, deadline - time.monotonic()))
    drained_s = time.monotonic() - (t0 + seconds)
    if tracer:
        tracer.join()
    final = _call(handle, "bench_since", mark)
    for key in ("compiles", "engine_compiles", "device"):
        rec[key] = final[key]

    def backlog(at_s):
        """Requests due by ``at_s`` into the window with no token yet."""
        t = t0 + at_s
        return sum(1 for o in outcomes if o.due <= t and not
                   (o.token_times and o.token_times[0] <= t))

    lat = client.latency_facts(outcomes)
    done = sum(o.ok for o in outcomes)
    facts = _engine_facts(rec)
    facts.update({
        "setup_s": window_epoch - shared["t_start"],
        "offered_vs_done": 100.0 * done / len(outcomes),
        "offered_rps": plan["rate_rps"],
        "requests": len(outcomes),
        "drain_s": drained_s,
        "backlog_mid_window": backlog(seconds / 2),
        "backlog_at_window_end": backlog(seconds),
        "active_at_window_end": rec["active"],
        "serve_out_tok_s": sum(
            1 for o in outcomes for t in o.token_times
            if t <= t0 + seconds) / seconds,
    })
    if lat["lateness_ms"]:
        facts["gen_lateness_p99_ms"] = common.percentile(
            lat["lateness_ms"], 99)
    for name in shared["wanted"]:
        v = client.named_percentile(name, lat)
        if v is not None:
            facts[name] = v
    if lat["ttft_ms"] and "replica_ttft_p50_ms" in facts:
        facts["stream_overhead_ms"] = (common.median(lat["ttft_ms"])
                                       - facts["replica_ttft_p50_ms"])
    for label, flag in (("returning", True), ("new", False)):
        part = [1e3 * (o.token_times[0] - o.due) for o in outcomes
                if o.ok and o.returning == flag]
        if part:
            facts[f"ttft_p50_ms_{label}"] = common.median(part)
    detail = {"check": check, "setup_phases_s": _phase_seconds(
        shared["t_start"], phases), "plan": {
        "requests": len(requests), "returning": len(plan["histories"]),
        "offered_prompt_tokens": plan["offered_prompt_tokens"],
        "offered_output_tokens": plan["offered_output_tokens"],
        "warmup_shapes": len(shapes)},
        "errors": [o.error for o in outcomes if o.error][:5],
        "engine": {k: final[k] for k in ("waiting", "active", "free_pages",
                                         "prefix", "requests_done")}}
    return {"facts": facts, "device": rec["device"],
            "correct": bool(check["ok"]) and done == len(outcomes),
            "attempted": len(outcomes), "failed": len(outcomes) - done,
            "detail": detail}


def _closed_loop(handle, info, mix, args, shared) -> Dict[str, Any]:
    seed, seconds = args.seed, args.seconds
    clients = int(mix["clients_per_slot"]) * info["slots"]
    plan = traffic_mod.closed_loop_requests(mix, seed, clients,
                                            info["vocab_size"])
    rng = traffic_mod.rng_for(seed, "check")
    samples = [{"tokens": rng.integers(0, info["vocab_size"],
                                       size=n).tolist(),
                "max_new_tokens": 8}
               for n in (200, 333)][:int(mix["correct_sample"])]
    phases = {"deployed": time.time()}
    check = _call(handle, "bench_check", samples)
    phases["checked"] = time.time()
    shapes = traffic_mod.warmup_shapes(plan["requests"], info["page_size"],
                                       info["buckets"])
    _run_all(handle, _stand_ins(shapes, [], info, seed), concurrency=1)
    phases["warm"] = time.time()

    stop = threading.Event()
    finished = threading.Semaphore(0)
    outcomes: List[List[client.Outcome]] = [[] for _ in range(clients)]

    def caller(c):
        for r in plan["by_client"][c]:
            if stop.is_set():
                return
            o = client.Outcome(index=r.index, due=time.monotonic())
            outcomes[c].append(o)
            client.stream(handle, r, o, stop)
            if o.finished:
                finished.release()

    threads = [threading.Thread(target=caller, args=(c,), daemon=True)
               for c in range(clients)]
    for t in threads:
        t.start()
    # The ramp is a state and not a time: the window opens when this many
    # requests have completed.  None can before the first wave of prefills
    # (every slot admitted in one tick) is over, so an engine of any speed
    # is met in the same phase: slots full, the first sequences replaced.
    ramp_deadline = time.monotonic() + float(mix["ramp_limit_s"])
    for _ in range(int(mix["ramp_until_finished"])):
        if not finished.acquire(
                timeout=max(0.0, ramp_deadline - time.monotonic())):
            stop.set()
            raise RuntimeError(
                f"ramp: fewer than {mix['ramp_until_finished']} requests "
                f"completed in {mix['ramp_limit_s']} s")
    mark = _call(handle, "bench_mark")
    tracer = None
    if args.trace:
        tracer = _Tracer(handle, shared["trace_dir"], 0.35 * seconds)
        tracer.start()
    window_epoch = time.time()
    t0 = time.monotonic()
    time.sleep(seconds)
    t1 = time.monotonic()
    rec = _call(handle, "bench_since", mark)
    stop.set()
    if tracer:
        tracer.join()

    flat = [o for per in outcomes for o in per]
    tokens = sum(1 for o in flat for t in o.token_times if t0 <= t <= t1)
    failed = [o for o in flat if o.error]
    exhausted = sum(1 for c in range(clients)
                    if len(outcomes[c]) == len(plan["by_client"][c])
                    and outcomes[c][-1].finished)
    facts = _engine_facts(rec)
    facts.update({
        "setup_s": window_epoch - shared["t_start"],
        "serve_out_tok_s": tokens / (t1 - t0),
        "clients": clients,
        "requests_started": len(flat),
        "requests_finished": sum(o.finished for o in flat),
        "clients_out_of_requests": exhausted,
    })
    phases["ramped"] = window_epoch
    detail = {"check": check,
              "setup_phases_s": _phase_seconds(shared["t_start"], phases),
              "plan": {"clients": clients,
                       "requests": len(plan["requests"]),
                       "warmup_shapes": len(shapes)},
              "errors": [o.error for o in failed][:5],
              "engine": {k: rec[k] for k in ("waiting", "active",
                                             "free_pages", "prefix",
                                             "requests_done")}}
    return {"facts": facts, "device": rec["device"],
            "correct": bool(check["ok"]) and not failed and not exhausted,
            "attempted": len(flat), "failed": len(failed),
            "detail": detail}


def run(files: Dict[str, Any], args, t_start: float) -> Dict[str, Any]:
    import ray_tpu

    cell, config, mix = files["cell"], files["config"], files["traffic"]
    engine = files["sizing"]["engine"]
    rehearsal = args.rehearse_on_cpu
    if rehearsal:
        mix = dict(mix, ramp_until_finished=2, rate_rps=2.0,
                   drain_limit_s=60, requests_per_client=400)
        for key in ("history_tokens", "message_tokens", "output_tokens",
                    "prompt_tokens"):
            if key in mix:
                mix[key] = {"dist": "lognormal", "median": 24, "sigma": 0.5,
                            "min": 8, "max": 60}
        if "system_prompt_tokens" in mix and mix["system_prompt_tokens"]:
            mix["system_prompt_tokens"] = 128
    trace_dir = os.path.join(common.OUT_DIR, "trace", cell["name"])
    shutil.rmtree(trace_dir, ignore_errors=True)
    init_epoch = time.time()
    ray_tpu.init(num_tpus=1 if rehearsal else None)
    clients = max(int(mix.get("clients_per_slot", 2)) * engine["slots"], 64)
    handle = _deploy(config, engine, args.seed, clients)
    info = _call(handle, "bench_info")
    shared = {"t_start": t_start, "trace_dir": trace_dir,
              "wanted": [m["name"] for m in files["end_to_end"]
                         + files["per_layer"]]}
    runner = _open_loop if mix["kind"] == "open_loop" else _closed_loop
    rates = [float(r) for r in (args.rate_rps or "").split(",") if r]
    if len(rates) > 1:
        # the knee sweep: one deployment, one window per rate, each with
        # conversations of its own (another seed, so nothing is cached)
        for k, rate in enumerate(rates):
            sub = argparse.Namespace(**dict(vars(args), seed=args.seed + k,
                                            trace=0))
            res = _open_loop(handle, info, mix, sub,
                             dict(shared, rate_rps=rate,
                                  t_start=time.time()))
            facts = res["facts"] if not rehearsal else {
                k: v for k, v in res["facts"].items()
                if isinstance(v, int)}     # a CPU run gives counts only
            print(json.dumps({"sweep_rate_rps": rate, "facts": facts,
                              "failed": res["failed"]}), flush=True)
        raise SystemExit(4)        # a sweep is not a run: no result line
    if rates:
        shared["rate_rps"] = rates[0]
    out = runner(handle, info, mix, args, shared)
    out["facts"]["worker_ready_s"] = info["ready_epoch"] - init_epoch
    out["facts"]["slots"] = info["slots"]
    out["detail"]["replica"] = {k: info[k] for k in (
        "slots", "page_size", "num_pages", "cache_bytes", "param_bytes")}
    out["trace_dir"] = trace_dir if args.trace else None
    out["model_config"] = config
    return out
