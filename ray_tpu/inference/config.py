"""Inference-engine env knobs — the single home for serving config.

Follows the ``attention_config()`` / ``ce_config()`` / ``comm_config()``
/ ``telemetry_config()`` precedent: one frozen dataclass resolved from
the environment once, ``refresh=True`` for tests and A/B drivers that
flip flags after import.
"""

from __future__ import annotations

import dataclasses
import os
import sys
from typing import Optional, Tuple


@dataclasses.dataclass(frozen=True)
class InferConfig:
    """Inference-engine knobs, resolved once from the environment.

    - ``RAY_TPU_INFER_SLOTS`` (default ``8``): decode batch slots — the
      fixed batch dimension of the compiled decode step.  Continuous
      batching admits/retires sequences into these slots without
      changing the compiled shape.
    - ``RAY_TPU_INFER_PAGE_SIZE`` (default ``128``): tokens per KV-cache
      page.  128 keeps a slot's gathered context a multiple of the
      decode kernel's 128-lane strip.
    - ``RAY_TPU_INFER_PAGES`` (default ``0`` = auto): total pages in the
      preallocated cache.  Auto sizes for every slot at full context
      (``slots * ceil(max_seq / page_size)``) plus the reserved garbage
      page; set lower to trade admission concurrency for HBM.
    - ``RAY_TPU_INFER_BUCKETS`` (default unset = powers of two from 32
      up to the model's ``max_seq``): comma-separated prefill length
      buckets.  Prompts are padded up to the smallest bucket that fits,
      so arbitrary request lengths hit at most ``len(buckets)`` prefill
      compiles and the decode step exactly one.
    - ``RAY_TPU_KV_DTYPE`` (default ``model``): KV-cache storage dtype
      — ``model`` (the model's ``cfg.dtype``) or ``int8``
      (block-scaled int8, one f32 scale per (position, head) lane
      vector stored in per-page scale arrays; keys/values quantize
      post-RoPE on write and dequantize inside the decode-attention
      page blocks).  ``int8`` roughly halves ``KVCache.bytes`` per
      page — i.e. ~2x the decode slots per HBM byte — at a bounded
      logits error (parity-tested against the ``model``-dtype cache).
      Default stays ``model`` until an on-chip A/B.
    - ``RAY_TPU_INFER_PREFIX`` (default ``1``): content-addressed
      prefix caching — full prompt pages register in a host-side
      chained-hash index and later requests sharing the prefix install
      the hit pages with refcount bumps, prefilling only the uncached
      suffix (one cached-context prefill executable per suffix bucket;
      zero steady-state recompiles still hold).  Pure host-side page-
      table metadata plus an XLA masked-einsum attention path — exact
      in model dtype (parity-tested), so it defaults on; ``0`` reverts
      to full-prompt prefill for every request.
    - ``RAY_TPU_INFER_MAX_QUEUE`` (default ``0`` = unbounded): cap on
      the scheduler's waiting queue.  Over-cap submits raise a typed
      :class:`~ray_tpu.inference.scheduler.QueueFullError` (load
      shedding) that the serve deployment surfaces as the stream's
      error instead of queueing unboundedly.
    - ``RAY_TPU_INFER_TTFT_DEADLINE`` (default ``0`` = none): default
      per-request time-to-first-token deadline in seconds.  A request
      still waiting past it is retired with a typed
      :class:`~ray_tpu.inference.scheduler.DeadlineExceededError`
      surfaced on its stream — over-deadline work is shed, not queued.
    - ``RAY_TPU_INFER_DEADLINE`` (default ``0`` = none): default
      per-request *total* deadline in seconds (submit to last token);
      expiry mid-decode retires the sequence, releasing its slot,
      pages and prefix refcounts.
    - ``RAY_TPU_INFER_WATCHDOG`` (default ``0`` = off): engine
      watchdog timeout in seconds — with work pending and no engine
      tick completing for this long, the serve replica's
      :class:`~ray_tpu.resilience.watchdog.EngineWatchdog` declares
      the step loop wedged (stderr + ``wedges`` counter; the drain /
      restart decision is the operator's).
    - ``RAY_TPU_INFER_STREAM_IDLE`` (default ``0`` = off): idle-
      consumer timeout in seconds for serve streams.  A consumer that
      silently drops its response generator is undetectable through
      the object-ref streaming protocol (no liveness signal); with
      this set, the deployment cancels any request whose stream has
      tokens waiting but has not been pumped for the budget —
      releasing its slot/pages/prefix refcounts instead of decoding
      to ``max_new_tokens`` for a reader that is gone.
    """
    slots: int = 8
    page_size: int = 128
    pages: int = 0
    buckets: Tuple[int, ...] = ()
    kv_dtype: str = "model"
    prefix: bool = True
    max_queue: int = 0
    ttft_deadline: float = 0.0
    deadline: float = 0.0
    watchdog: float = 0.0
    stream_idle: float = 0.0


_CONFIG: Optional[InferConfig] = None


def infer_config(refresh: bool = False) -> InferConfig:
    """The process-wide :class:`InferConfig` (env read once, cached)."""
    global _CONFIG
    if _CONFIG is None or refresh:
        env = os.environ.get
        raw_buckets = env("RAY_TPU_INFER_BUCKETS", "")
        buckets = tuple(sorted(int(b) for b in raw_buckets.split(",")
                               if b.strip())) if raw_buckets else ()
        kv_dtype = env("RAY_TPU_KV_DTYPE", "model")
        if kv_dtype not in ("model", "int8"):
            print(f"RAY_TPU_KV_DTYPE={kv_dtype!r} unknown; "
                  "using 'model'", file=sys.stderr)
            kv_dtype = "model"
        max_queue = int(env("RAY_TPU_INFER_MAX_QUEUE", "0"))
        if max_queue < 0:
            print(f"RAY_TPU_INFER_MAX_QUEUE={max_queue} negative; "
                  "using 0 (unbounded)", file=sys.stderr)
            max_queue = 0

        def nonneg_float(name, off_meaning):
            val = float(env(name, "0"))
            if val < 0:
                print(f"{name}={val} negative; using 0 "
                      f"({off_meaning})", file=sys.stderr)
                return 0.0
            return val

        ttft_deadline = nonneg_float("RAY_TPU_INFER_TTFT_DEADLINE",
                                     "no TTFT deadline")
        deadline = nonneg_float("RAY_TPU_INFER_DEADLINE",
                                "no total deadline")
        watchdog = nonneg_float("RAY_TPU_INFER_WATCHDOG",
                                "watchdog off")
        stream_idle = nonneg_float("RAY_TPU_INFER_STREAM_IDLE",
                                   "idle-stream reaper off")
        _CONFIG = InferConfig(
            slots=int(env("RAY_TPU_INFER_SLOTS", "8")),
            page_size=int(env("RAY_TPU_INFER_PAGE_SIZE", "128")),
            pages=int(env("RAY_TPU_INFER_PAGES", "0")),
            buckets=buckets,
            kv_dtype=kv_dtype,
            prefix=env("RAY_TPU_INFER_PREFIX", "1") != "0",
            max_queue=max_queue,
            ttft_deadline=ttft_deadline,
            deadline=deadline,
            watchdog=watchdog,
            stream_idle=stream_idle,
        )
    return _CONFIG


def default_buckets(max_seq: int, smallest: int = 32) -> Tuple[int, ...]:
    """Powers of two from ``smallest`` up to (and including) ``max_seq``."""
    out = []
    b = min(smallest, max_seq)
    while b < max_seq:
        out.append(b)
        b *= 2
    out.append(max_seq)
    return tuple(out)
