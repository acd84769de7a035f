"""Inference telemetry: TTFT / per-token latency / decode throughput.

The serving-side sibling of :class:`ray_tpu.telemetry.step.StepTelemetry`
— the engine records one entry per prefill and per decode step (wall
time measured to the host-materialized sampled tokens, so it is the
honest blocking figure), plus per-request TTFT at first-token time.
Sinks mirror r09:

- the engine wraps each step in ``ray_tpu.util.tracing`` spans
  (``infer/prefill`` / ``infer/decode``), which the chrome-trace
  exporter already merges into the unified host timeline;
- Prometheus series through the control-plane metrics when a ray_tpu
  session is up (``infer_ttft_seconds`` / ``infer_decode_step_seconds``
  / ``infer_queue_wait_seconds`` histograms,
  ``infer_decode_tokens_per_sec`` / ``infer_queue_depth`` gauges),
  throttled and dead-on-first-failure exactly like the train recorder;
- :meth:`summary` is the block a driver reports.

``RAY_TPU_TELEMETRY=0`` disables recording entirely (the engine checks
``enabled`` before touching the recorder).
"""

from __future__ import annotations

import statistics
import time
from typing import Any, Dict, List, Optional

from ray_tpu.telemetry.config import telemetry_config

_TTFT_BOUNDARIES = [0.001, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
                    1.0, 2.5, 5.0, 10.0, 30.0]
_STEP_BOUNDARIES = [0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005,
                    0.01, 0.025, 0.05, 0.1, 0.25, 1.0]


class InferTelemetry:
    """Per-engine recorder for prefill/decode/TTFT records."""

    _MAX_RECORDS = 10_000
    _MAX_EXEMPLARS = 64
    _EMIT_INTERVAL_S = 0.5

    def __init__(self, *, label: str = "infer", config=None):
        tcfg = config or telemetry_config()
        self.enabled: bool = tcfg.enabled
        self.label = label
        self.prefills: List[Dict[str, Any]] = []
        self.decodes: List[Dict[str, Any]] = []
        self.ttfts: List[float] = []
        # (ttft_s, trace_id) exemplars — the histogram-to-trace bridge
        self.ttft_exemplars: List[Any] = []
        # TTFT split by prefix-cache outcome: a hit request's first
        # token only pays the suffix prefill, so the two populations
        # have different distributions worth reporting separately
        self.ttfts_hit: List[float] = []
        self.ttfts_miss: List[float] = []
        self.queue_waits: List[float] = []
        self.prefill_count = 0
        self.decode_count = 0
        self.requests_done = 0
        self.decode_tokens = 0
        self.prompt_tokens = 0
        self.prefix_hit_tokens = 0
        self.deadline_exceeded: Dict[str, int] = {}
        # sampler calls by the body their rows selected (``plain`` /
        # ``draw`` / ``filter``, ``inference/sampling.py``): only
        # ``filter`` pays the full-vocabulary sorts
        self.sample_paths: Dict[str, int] = {}
        # decodes by how they were dispatched: [fetched at once, left
        # in flight ahead of the host]
        self.decode_dispatches = [0, 0]
        # pages of the KV pool the decodes' attention read (each
        # dispatched row's live pages) and pages their tables could
        # name (slots x max pages a slot): what reading the pool in
        # place saves over a padded context
        self.decode_pages = [0, 0]
        # rows of K/V the decodes laid into the pool (one a
        # dispatched row) and tail pages they moved to lay them: the
        # same count where the write is in place, every slot's page
        # where whole pages are blended
        self.decode_writes = [0, 0]
        # a routed model's expert layers (``parallel/moe.py:MOE_COUNTS``,
        # summed over every step's layers), and the same of its decodes
        # alone: a prefill's hundreds of rows hit every held expert, a
        # decode's few rows hit few, and a ratio over both hides it
        self.moe: Dict[str, int] = {}
        # multi-tenant LoRA (r25): per-replica adapter-cache outcomes
        # and load latency — the hit rate is what the router's
        # adapter-affinity scoring is supposed to move
        self.adapter_cache_hits = 0
        self.adapter_cache_misses = 0
        self.adapter_loads = 0
        self.adapter_load_seconds = 0.0
        self.cache_info: Dict[str, Any] = {}
        self._metrics = None
        self._metrics_dead = False
        self._metrics_last = 0.0
        self._queue_last = 0.0

    # ---------------------------------------------------------- records
    def record_prefill(self, wall_s: float, *, prompt_tokens: int,
                       bucket: int, cached_tokens: int = 0) -> None:
        if not self.enabled:
            return
        self.prefill_count += 1
        self.prompt_tokens += prompt_tokens
        self.prefix_hit_tokens += cached_tokens
        self.prefills.append({"wall_s": wall_s,
                              "prompt_tokens": prompt_tokens,
                              "cached_tokens": cached_tokens,
                              "bucket": bucket})
        del self.prefills[:-self._MAX_RECORDS]

    def record_decode(self, wall_s: float, *, active: int,
                      ahead: bool = False, pages_read: int = 0,
                      pages_table: int = 0, rows_written: int = 0,
                      tail_pages_rewritten: int = 0) -> None:
        """One decode, recorded when its tokens are on the host.
        ``ahead``: it was dispatched on the device's own tokens, ahead
        of the host (``inference/engine.py``).  ``pages_read`` of
        ``pages_table``: the pages its rows held at the dispatch, of
        those its page table has room for.  ``rows_written``: the rows
        it laid into the pool; ``tail_pages_rewritten``: the pages it
        moved to lay them."""
        if not self.enabled:
            return
        self.decode_count += 1
        self.decode_dispatches[bool(ahead)] += 1
        self.decode_pages[0] += pages_read
        self.decode_pages[1] += pages_table
        self.decode_writes[0] += rows_written
        self.decode_writes[1] += tail_pages_rewritten
        self.decode_tokens += active
        self.decodes.append({"wall_s": wall_s, "active": active})
        del self.decodes[:-self._MAX_RECORDS]
        self._emit_decode(wall_s, active)

    def record_moe(self, *, decode: bool, **counts: int) -> None:
        """One step's expert-layer counts (rows through an expert
        layer, picks on held experts, picks on identity experts, all
        picks, experts hit, expert-layer calls, the trips of the
        experts' loop: the tiles the picks filled), fetched with the
        step's tokens; ``decode``: the step was a decode."""
        if not self.enabled:
            return
        for name, n in counts.items():
            self.moe[name] = self.moe.get(name, 0) + n
            if decode:
                key = "decode_" + name
                self.moe[key] = self.moe.get(key, 0) + n

    def record_sample(self, path: str) -> None:
        """One sampler call that ran the body ``path`` (the engine has
        checked ``enabled``)."""
        self.sample_paths[path] = self.sample_paths.get(path, 0) + 1

    def record_ttft(self, ttft_s: float, *, prefix_hit: bool = False,
                    trace_id: Optional[str] = None) -> None:
        """``trace_id`` (when the request was trace-sampled) rides the
        Prometheus histogram as an exemplar — the jump from a p99
        bucket to the one request's flight-recorder span tree."""
        if not self.enabled:
            return
        self.ttfts.append(ttft_s)
        del self.ttfts[:-self._MAX_RECORDS]
        split = self.ttfts_hit if prefix_hit else self.ttfts_miss
        split.append(ttft_s)
        del split[:-self._MAX_RECORDS]
        if trace_id:
            self.ttft_exemplars.append((ttft_s, trace_id))
            del self.ttft_exemplars[:-self._MAX_EXEMPLARS]
        self._emit_ttft(ttft_s, trace_id)

    def record_queue(self, wait_s: float, *, depth: int) -> None:
        """Admission-time record: how long the request waited in the
        queue and how deep the queue stands behind it (the load-
        shedding signals: ``RAY_TPU_INFER_MAX_QUEUE`` caps the depth,
        these series say how close traffic runs to the cap)."""
        if not self.enabled:
            return
        self.queue_waits.append(wait_s)
        del self.queue_waits[:-self._MAX_RECORDS]
        self._emit_queue(wait_s, depth)

    def record_queue_depth(self, depth: int) -> None:
        """Submit-time gauge update: admissions stall exactly when the
        queue is backing up, so the depth gauge must also move on
        enqueue or it reads 0 through the whole overload.  Throttled
        like the decode emitter — high-QPS submits must not pay a
        metric emission each."""
        if not self.enabled or self._metrics_dead:
            return
        now = time.monotonic()
        if now - self._queue_last < self._EMIT_INTERVAL_S:
            return
        self._queue_last = now
        self._emit_queue(None, depth)

    def record_request_done(self) -> None:
        if self.enabled:
            self.requests_done += 1

    def record_deadline_exceeded(self, *, kind: str) -> None:
        """One request retired past its deadline (``kind`` = ``ttft``
        — never admitted in time — or ``total`` — expired mid-flight).
        Shed work is the load-limit signal, so it gets a Prometheus
        counter (``infer_deadline_exceeded_total``) operators can rate
        and alarm on."""
        if not self.enabled:
            return
        self.deadline_exceeded[kind] = \
            self.deadline_exceeded.get(kind, 0) + 1
        self._emit_deadline(kind)

    def record_adapter_cache(self, *, hit: bool) -> None:
        """One adapter-resolution outcome: ``hit`` means the tenant's
        factors were already resident in the engine's bank (zero-cost
        resolution); a miss pays a store fetch + bank install before
        the request can admit."""
        if not self.enabled:
            return
        if hit:
            self.adapter_cache_hits += 1
        else:
            self.adapter_cache_misses += 1
        self._emit_adapter_cache(hit)

    def record_adapter_load(self, wall_s: float, *,
                            resident: int) -> None:
        """One adapter fetched from the store and installed into the
        bank (``wall_s`` = checkout + host ``.at[].set``), plus the
        resident-tenant count after the install (the gauge operators
        watch against ``RAY_TPU_ADAPTER_CACHE``)."""
        if not self.enabled:
            return
        self.adapter_loads += 1
        self.adapter_load_seconds += wall_s
        self._emit_adapter_load(wall_s, resident)

    def record_cache_info(self, *, kv_dtype: str, cache_bytes: int,
                          kv_bytes_per_slot: int) -> None:
        """Static KV-cache geometry the engine reports once at
        construction: the storage dtype and the *true* per-slot
        footprint (codes + scale arrays for int8 caches)."""
        if self.enabled:
            self.cache_info = {"kv_dtype": kv_dtype,
                               "kv_cache_bytes": int(cache_bytes),
                               "kv_bytes_per_slot":
                                   int(kv_bytes_per_slot)}

    # ---------------------------------------------------------- summary
    def summary(self) -> Dict[str, Any]:
        """The block a driver reports."""
        if not self.enabled:
            return {"enabled": False}
        out: Dict[str, Any] = {
            "enabled": True, "label": self.label,
            "requests_done": self.requests_done,
            "prefills": self.prefill_count,
            "decode_steps": self.decode_count,
            "decode_tokens": self.decode_tokens,
            **self.cache_info,
        }
        out["prompt_tokens"] = self.prompt_tokens
        out["prefill_tokens_skipped"] = self.prefix_hit_tokens
        out["deadline_exceeded"] = dict(self.deadline_exceeded)
        if self.sample_paths:
            calls = sum(self.sample_paths.values())
            out["sample"] = {
                "calls": calls,
                "path_share": {path: n / calls for path, n
                               in sorted(self.sample_paths.items())},
            }
        if any(self.decode_dispatches):
            sync, ahead = self.decode_dispatches
            out["decode"] = {"dispatches": sync + ahead,
                             "dispatches_ahead": ahead,
                             "ahead_share": ahead / (sync + ahead),
                             "pages_read": self.decode_pages[0],
                             "pages_table": self.decode_pages[1],
                             "rows_written": self.decode_writes[0],
                             "tail_pages_rewritten": self.decode_writes[1]}
        if self.moe:
            # counts only: a share is the ratio of two of them
            out["moe"] = dict(self.moe)
        if self.prompt_tokens:
            out["prefix_hit_rate"] = (self.prefix_hit_tokens
                                      / self.prompt_tokens)
        if self.adapter_cache_hits or self.adapter_cache_misses:
            looked = self.adapter_cache_hits + self.adapter_cache_misses
            out["adapters"] = {
                "cache_hits": self.adapter_cache_hits,
                "cache_misses": self.adapter_cache_misses,
                "cache_hit_rate": self.adapter_cache_hits / looked,
                "loads": self.adapter_loads,
                "load_seconds": self.adapter_load_seconds,
            }
        if self.ttfts:
            out["ttft_s"] = statistics.median(self.ttfts)
            out["ttft_mean_s"] = statistics.fmean(self.ttfts)
            out["ttft_max_s"] = max(self.ttfts)
        if self.ttft_exemplars:
            # the worst traced request — where tail diagnosis starts
            worst = max(self.ttft_exemplars, key=lambda e: e[0])
            out["ttft_worst_trace"] = {"ttft_s": worst[0],
                                       "trace_id": worst[1]}
        if self.ttfts_hit:
            out["ttft_prefix_hit_s"] = statistics.median(self.ttfts_hit)
        if self.ttfts_miss:
            out["ttft_prefix_miss_s"] = statistics.median(
                self.ttfts_miss)
        if self.queue_waits:
            out["queue_wait_s"] = statistics.median(self.queue_waits)
            out["queue_wait_max_s"] = max(self.queue_waits)
        if self.prefills:
            out["prefill_s"] = statistics.median(
                r["wall_s"] for r in self.prefills)
        if self.decodes:
            # steady decode: drop the first step (carries the compile
            # on cold engines), same policy as StepTelemetry step 0
            steady = self.decodes[1:] or self.decodes
            step_s = statistics.median(r["wall_s"] for r in steady)
            out["decode_step_s"] = step_s
            tok = sum(r["active"] for r in steady)
            wall = sum(r["wall_s"] for r in steady)
            if wall > 0:
                out["decode_tokens_per_sec"] = tok / wall
        return out

    # ------------------------------------------------------- prometheus
    def _metric_objects(self):
        from ray_tpu._private.worker import is_initialized
        if not is_initialized():
            return None
        if self._metrics is None:
            from ray_tpu.util.metrics import Counter, Gauge, Histogram
            tags = ("label",)
            self._metrics = {
                "ttft": Histogram(
                    "infer_ttft_seconds",
                    "time from request submit to first token",
                    boundaries=_TTFT_BOUNDARIES, tag_keys=tags),
                "step": Histogram(
                    "infer_decode_step_seconds",
                    "decode step wall seconds (to sampled tokens)",
                    boundaries=_STEP_BOUNDARIES, tag_keys=tags),
                "tok": Gauge("infer_decode_tokens_per_sec",
                             "decode throughput", tag_keys=tags),
                "queue_wait": Histogram(
                    "infer_queue_wait_seconds",
                    "time from request submit to slot admission",
                    boundaries=_TTFT_BOUNDARIES, tag_keys=tags),
                "queue_depth": Gauge(
                    "infer_queue_depth",
                    "requests waiting for a decode slot",
                    tag_keys=tags),
                "deadline": Counter(
                    "infer_deadline_exceeded_total",
                    "requests retired past their TTFT/total deadline",
                    tag_keys=("label", "kind")),
                "adapter_hits": Counter(
                    "serve_adapter_cache_hits_total",
                    "adapter resolutions served from the resident bank",
                    tag_keys=tags),
                "adapter_misses": Counter(
                    "serve_adapter_cache_misses_total",
                    "adapter resolutions that paid a store fetch",
                    tag_keys=tags),
                "adapter_load": Histogram(
                    "serve_adapter_load_seconds",
                    "adapter store-fetch + bank-install latency",
                    boundaries=_TTFT_BOUNDARIES, tag_keys=tags),
                "adapter_resident": Gauge(
                    "serve_adapter_resident",
                    "tenant adapters resident in the bank",
                    tag_keys=tags),
            }
        return self._metrics

    def _emit_ttft(self, ttft_s: float,
                   trace_id: Optional[str] = None):
        if self._metrics_dead:
            return
        try:
            metrics = self._metric_objects()
            if metrics is not None:
                metrics["ttft"].observe(
                    ttft_s, tags={"label": self.label},
                    exemplar=({"trace_id": trace_id}
                              if trace_id else None))
        except Exception:  # noqa: BLE001 — never tax the serve loop
            self._metrics_dead = True

    def _emit_queue(self, wait_s, depth: int):
        if self._metrics_dead:
            return
        try:
            metrics = self._metric_objects()
            if metrics is not None:
                tags = {"label": self.label}
                if wait_s is not None:
                    metrics["queue_wait"].observe(wait_s, tags=tags)
                metrics["queue_depth"].set(depth, tags=tags)
        except Exception:  # noqa: BLE001 — never tax the serve loop
            self._metrics_dead = True

    def _emit_deadline(self, kind: str):
        if self._metrics_dead:
            return
        try:
            metrics = self._metric_objects()
            if metrics is not None:
                metrics["deadline"].inc(
                    1.0, tags={"label": self.label, "kind": kind})
        except Exception:  # noqa: BLE001 — never tax the serve loop
            self._metrics_dead = True

    def _emit_adapter_cache(self, hit: bool):
        if self._metrics_dead:
            return
        try:
            metrics = self._metric_objects()
            if metrics is not None:
                key = "adapter_hits" if hit else "adapter_misses"
                metrics[key].inc(1.0, tags={"label": self.label})
        except Exception:  # noqa: BLE001 — never tax the serve loop
            self._metrics_dead = True

    def _emit_adapter_load(self, wall_s: float, resident: int):
        if self._metrics_dead:
            return
        try:
            metrics = self._metric_objects()
            if metrics is not None:
                tags = {"label": self.label}
                metrics["adapter_load"].observe(wall_s, tags=tags)
                metrics["adapter_resident"].set(resident, tags=tags)
        except Exception:  # noqa: BLE001 — never tax the serve loop
            self._metrics_dead = True

    def _emit_decode(self, wall_s: float, active: int):
        if self._metrics_dead:
            return
        now = time.monotonic()
        if (self.decode_count > 1
                and now - self._metrics_last < self._EMIT_INTERVAL_S):
            return
        self._metrics_last = now
        try:
            metrics = self._metric_objects()
            if metrics is None:
                return
            tags = {"label": self.label}
            metrics["step"].observe(wall_s, tags=tags)
            if wall_s > 0:
                metrics["tok"].set(active / wall_s, tags=tags)
        except Exception:  # noqa: BLE001 — never tax the serve loop
            self._metrics_dead = True
