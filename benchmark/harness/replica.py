"""The deployment the serve cells run: ``GPTDeployment`` unchanged, with
the few methods only the process that holds the chip can answer.

Only that process can trace the device, read its own compile counters or
see inside its engine, so the benchmark deploys this subclass.  It adds
nothing to the request path except one ``TraceAnnotation`` and two clock
reads around each ``engine.step()`` (the tick the per-layer metrics
count), and it never changes what the engine computes.  Spans the
program would have to emit itself (the phases inside a tick) are listed
in PERF.md for the ``tracing`` issue.
"""

from __future__ import annotations

import time
from typing import Any, Dict, List

from ray_tpu.inference.serve_gpt import GPTDeployment

from benchmark.harness import common
from benchmark.reduce.trace import TICK

# Engine logits against the float32 reference, as max|delta| / max|ref|
# over a request's rows.  The engine computes in bfloat16 (2^-8 per
# rounding) through up to 36 layers and a bf16 KV cache; a correct path
# shows about 1e-2.  A wrong page, position or mask moves whole rows
# (order 1); int8 or fp8 arithmetic shows 5e-2 and more.
LOGITS_TOL = 4e-2


class BenchReplica(GPTDeployment.func_or_class):

    def __init__(self, *args, **kwargs):
        import jax
        self._bench_devices = jax.devices()
        self._bench_ready_epoch = time.time()
        super().__init__(*args, **kwargs)
        self._bench_ticks: List[float] = []
        self._bench_context: List[int] = []     # live context tokens
        inner = self.engine.step

        def step():
            t0 = time.monotonic()
            with jax.profiler.TraceAnnotation(TICK):
                events = inner()
            self._bench_ticks.append(time.monotonic() - t0)
            sched = self.engine.scheduler
            self._bench_context.append(int(sum(
                sched.lengths[slot] for slot in sched.active)))
            return events

        self.engine.step = step

    # ------------------------------------------------------------ facts
    def bench_info(self) -> Dict[str, Any]:
        import jax
        eng = self.engine
        return {"ready_epoch": self._bench_ready_epoch,
                "device": common.device_block(self._bench_devices),
                "slots": eng.slots, "page_size": eng.page_size,
                "buckets": list(eng.buckets),
                "num_pages": int(eng.cache.k.shape[1]),
                "cache_bytes": int(eng.cache.bytes),
                "param_bytes": int(sum(
                    p.nbytes for p in jax.tree.leaves(eng.params))),
                "vocab_size": eng.cfg.vocab_size}

    def bench_mark(self) -> Dict[str, Any]:
        from ray_tpu._private.compile_cache import compile_stats
        tel, eng = self.engine.telemetry, self.engine
        return {"ticks": len(self._bench_ticks),
                "decodes": tel.decode_count, "prefills": tel.prefill_count,
                "ttfts": len(tel.ttfts), "queue_waits": len(tel.queue_waits),
                "prompt_tokens": tel.prompt_tokens,
                "hit_tokens": tel.prefix_hit_tokens,
                "compiles": compile_stats()["compiles"],
                "engine_compiles": sum(eng.compile_counts.values()),
                "requests_done": tel.requests_done}

    def bench_since(self, mark: Dict[str, Any]) -> Dict[str, Any]:
        """What the engine recorded since ``mark``."""
        now = self.bench_mark()
        tel, eng = self.engine.telemetry, self.engine

        def tail(records, key):
            n = now[key] - mark[key]
            return list(records[-n:]) if n > 0 else []

        decodes = tail(tel.decodes, "decodes")
        prefills = tail(tel.prefills, "prefills")
        stats = eng.stats()
        return {
            "tick_s": self._bench_ticks[mark["ticks"]:],
            "context_tokens": self._bench_context[mark["ticks"]:],
            "decode_wall_s": [d["wall_s"] for d in decodes],
            "decode_active": [d["active"] for d in decodes],
            "prefill_wall_s": [p["wall_s"] for p in prefills],
            "prefill_cached_tokens": [p["cached_tokens"] for p in prefills],
            "prefill_prompt_tokens": [p["prompt_tokens"] for p in prefills],
            "ttft_s": tail(tel.ttfts, "ttfts"),
            "queue_wait_s": tail(tel.queue_waits, "queue_waits"),
            "prompt_tokens": now["prompt_tokens"] - mark["prompt_tokens"],
            "hit_tokens": now["hit_tokens"] - mark["hit_tokens"],
            "compiles": now["compiles"] - mark["compiles"],
            "engine_compiles": now["engine_compiles"]
            - mark["engine_compiles"],
            "requests_done": now["requests_done"] - mark["requests_done"],
            "waiting": stats["waiting"], "active": stats["active"],
            "free_pages": stats["free_pages"],
            "prefix": stats["prefix"],
            "device": common.device_block(self._bench_devices),
        }

    # ------------------------------------------------------------ trace
    def bench_start_trace(self, trace_dir: str) -> float:
        import jax
        jax.profiler.start_trace(trace_dir)
        return time.time()

    def bench_stop_trace(self) -> float:
        import jax
        jax.profiler.stop_trace()
        return time.time()

    # ---------------------------------------------------------- correct
    def bench_check(self, samples: List[Dict[str, Any]]) -> Dict[str, Any]:
        """Prefill, then decode through the cache, against the
        reference's full forward over the same tokens: the logits row
        that produced each generated token.  Called in set-up, with no
        request in flight.  Samples run one after the other, so a later
        one can hit pages an earlier one registered."""
        import numpy as np

        from benchmark.reference import gpt as reference
        eng = self.engine
        rows = []
        eng.debug_logits = True
        try:
            for sample in samples:
                hits_before = eng.scheduler.prefix_hit_pages
                rid = eng.submit(sample["tokens"],
                                 max_new_tokens=sample["max_new_tokens"])
                generated = []
                while eng.has_work():
                    for ev in eng.step():
                        if ev[0] == rid and ev.error is None:
                            generated.append(int(ev[1]))
                got = np.stack(eng.logits_trace.pop(rid))
                eng._requests.pop(rid, None)
                full = np.asarray(
                    [sample["tokens"] + generated[:-1]], np.int32)
                want = np.asarray(reference.logits_last(
                    eng.params, full, len(generated))[0])
                err = float(np.max(np.abs(got - want))
                            / np.max(np.abs(want)))
                rows.append({
                    "prompt_tokens": len(sample["tokens"]),
                    "new_tokens": len(generated),
                    "hit_pages": eng.scheduler.prefix_hit_pages
                    - hits_before,
                    "rel_err": err,
                    "argmax_agree": float(np.mean(
                        got.argmax(-1) == want.argmax(-1))),
                    "ok": bool(err <= LOGITS_TOL
                               and len(generated)
                               == sample["max_new_tokens"])})
        finally:
            eng.debug_logits = False
            eng.logits_trace.clear()
        return {"rows": rows, "tolerance": LOGITS_TOL,
                "ok": all(r["ok"] for r in rows)}
