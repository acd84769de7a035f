"""Continuous-batching scheduler: slots, pages, request lifecycle.

Host-side state machine beside the compiled steps (the Podracer
pattern: a python scheduler colocated with AOT-compiled device step
functions).  Requests move ``waiting -> active(slot) -> finished``:

- **admit**: the head of the waiting queue takes a free decode slot and
  reserves ``ceil((prompt + max_new) / page_size)`` pages up front —
  reservation-at-admission means a running sequence can never run out
  of cache mid-decode, so there is no preemption path to get wrong.
  Admission blocks (request stays queued) until both a slot and the
  pages are free.  With prefix caching on, admission first walks the
  prompt's full pages through the :class:`~.kv_cache.PrefixIndex`:
  hits are installed into the page-table row with refcount bumps and
  **zero prefill compute**; only the pages past the last hit are
  freshly allocated, and the engine prefills only the uncached suffix.
- **retire** (EOS / max-new-tokens): the request's page references are
  released — shared pages survive under their other owners' refcounts,
  registered refcount-0 pages park in the allocator's idle pool, the
  rest return to the free list; the page-table row resets to the
  garbage page and the slot frees.

Decode writes only ever land in pages the slot *exclusively* owns (the
private tail past the prompt), so copy-on-write reduces to a
never-write-shared invariant: a hit page is always a full prompt page
strictly before the final prompt token, and the suffix prefill's first
write position is ``cached_tokens`` — on a page boundary past every
shared page.

**Load shedding**: ``max_queue`` (``RAY_TPU_INFER_MAX_QUEUE``) caps the
waiting queue; over-cap submits raise :class:`QueueFullError` — a typed
rejection the serve deployment surfaces as the stream's error — instead
of queueing unboundedly.

The page table and per-slot lengths live here as numpy arrays and are
passed into the fixed-shape compiled steps each call; the engine owns
the device-side cache arrays.  Invariants (no slot/page leaks, no page
freed while referenced, across any admit/hit/retire/evict interleaving)
are fuzzed in ``tests/test_inference.py``.
"""

from __future__ import annotations

import collections
import dataclasses
import time
from typing import Any, Deque, Dict, List, Optional

import numpy as np

from ray_tpu.inference.kv_cache import (GARBAGE_PAGE, PageAllocator,
                                        PrefixIndex, pages_needed)
from ray_tpu.inference.sampling import SamplingParams


class QueueFullError(RuntimeError):
    """Typed admission rejection: the waiting queue is at
    ``RAY_TPU_INFER_MAX_QUEUE`` — shed load (retry later / another
    replica) instead of queueing unboundedly."""


class DeadlineExceededError(RuntimeError):
    """Typed per-request deadline expiry (``RAY_TPU_INFER_TTFT_DEADLINE``
    / ``RAY_TPU_INFER_DEADLINE`` or per-request overrides): the request
    was retired — slot, pages and prefix refcounts released — because
    it blew its time-to-first-token or total budget.  Surfaced as the
    stream's error; wedged or over-deadline work is shed, not queued
    (the arXiv:2011.03641 concurrency-limits argument in seconds)."""

    def __init__(self, rid: int, kind: str, budget_s: float,
                 waited_s: float):
        super().__init__(
            f"request {rid}: {kind} deadline of {budget_s:.3f}s "
            f"exceeded ({waited_s:.3f}s elapsed)")
        self.rid = rid
        self.kind = kind            # "ttft" | "total"
        self.budget_s = budget_s
        self.waited_s = waited_s

    def __reduce__(self):
        # default exception pickling replays __init__ with self.args
        # (the message) — this error crosses the object store on serve
        # streams, so it must rebuild from its real constructor args
        return (DeadlineExceededError,
                (self.rid, self.kind, self.budget_s, self.waited_s))


@dataclasses.dataclass
class Request:
    rid: int
    prompt: List[int]
    max_new_tokens: int
    sampling: SamplingParams
    eos_token: Optional[int] = None
    # lifecycle state (owned by the scheduler/engine)
    generated: List[int] = dataclasses.field(default_factory=list)
    # chosen-token model logprobs, one per generated token (see
    # ``sampling``: log_softmax of the raw f32 logits at the sampled
    # id — the quantity the RL actors and the serve logprobs option
    # consume)
    logprobs: List[float] = dataclasses.field(default_factory=list)
    slot: Optional[int] = None
    pages: Optional[List[int]] = None
    submitted_ts: float = dataclasses.field(default_factory=time.monotonic)
    admitted_ts: Optional[float] = None
    done: bool = False
    # tokens dispatched for this request (its prefill, a decode row)
    # whose values the host has not fetched yet: the engine runs one
    # decode ahead of what it has seen, so everything the next dispatch
    # needs is reckoned as ``len(generated) + in_flight``
    in_flight: int = 0
    # deadlines (seconds from submit; None = none): ``ttft_deadline_s``
    # bounds time-to-first-token — it can only expire while the request
    # is still waiting, because admission delivers the first token in
    # the same tick — and ``deadline_s`` bounds the whole request.  An
    # expired request is retired with everything released and carries
    # the typed error here for the stream to surface.
    ttft_deadline_s: Optional[float] = None
    deadline_s: Optional[float] = None
    error: Optional[BaseException] = None
    # prefix-cache state: chained hashes of the prompt's full pages
    # (None until the first admission attempt computes them — they are
    # immutable per request, so retries reuse them), how many were
    # index hits, and the token count the hits cover (skipped prefill)
    chain_hashes: Optional[List[bytes]] = None
    n_hit_pages: int = 0
    cached_tokens: int = 0
    # disaggregated serving (r20): ``hold_pages`` keeps the request's
    # page references refcounted past retirement (the prefill-side
    # export seam — released by export_request/release_held); a
    # non-None ``import_payload`` (a kv_cache.KVHandoff) marks a
    # decode-side import, admitted like any request but installed from
    # the payload instead of prefilled
    hold_pages: bool = False
    import_payload: Optional[Any] = None
    # distributed tracing (r24): the request's TraceContext (a
    # telemetry.trace.TraceContext, None = untraced) — minted at the
    # router/serve boundary, carried here so every lifecycle stage can
    # hang spans off the same trace_id
    trace: Optional[Any] = None
    # multi-tenant serving (r25): the adapter this request decodes
    # under (None = base).  ``adapter_slot`` is the engine's bank row:
    # 0 = the identity slot, -1 = not yet resolved (the engine loads
    # the adapter and pins it before this request's first admission
    # attempt); ``adapter_version`` pins the store version (0 = latest,
    # resolved in place).  ``hash_salt`` overrides the prefix-chain
    # root so adapter K/V never aliases base K/V in the index — it
    # MUST be set before the first ``_prefix_walk`` computes
    # ``chain_hashes``.
    model_id: Optional[str] = None
    adapter_slot: int = 0
    adapter_version: int = 0
    hash_salt: bytes = b""


class SlotScheduler:
    def __init__(self, *, slots: int, page_size: int, num_pages: int,
                 max_pages_per_slot: int, prefix: bool = False,
                 max_queue: int = 0):
        self.slots = slots
        self.page_size = page_size
        self.max_pages_per_slot = max_pages_per_slot
        self.prefix_index = PrefixIndex() if prefix else None
        self.allocator = PageAllocator(num_pages,
                                       index=self.prefix_index)
        self.max_queue = max_queue
        self.page_table = np.full((slots, max_pages_per_slot),
                                  GARBAGE_PAGE, np.int32)
        self.lengths = np.zeros((slots,), np.int32)   # tokens in cache
        self.free_slots: List[int] = list(range(slots - 1, -1, -1))
        self.active: Dict[int, Request] = {}          # slot -> request
        self.waiting: Deque[Request] = collections.deque()
        # prefix-hit accounting (tokens = pages * page_size: the
        # prefill compute the hits skipped)
        self.prefix_hit_pages = 0
        self.prefix_hit_tokens = 0
        self.prefix_requests_hit = 0

    # ------------------------------------------------------------ admit
    def submit(self, req: Request) -> None:
        need = pages_needed(len(req.prompt) + req.max_new_tokens,
                            self.page_size)
        if need > self.max_pages_per_slot:
            raise ValueError(
                f"request {req.rid}: prompt+max_new = "
                f"{len(req.prompt) + req.max_new_tokens} tokens needs "
                f"{need} pages > {self.max_pages_per_slot} per slot")
        # an unsatisfiable-even-when-idle request must raise, not queue:
        # FIFO admission would otherwise spin on it forever (page 0 is
        # reserved, so the whole pool is num_pages - 1)
        if need > self.allocator.num_pages - 1:
            raise ValueError(
                f"request {req.rid}: needs {need} pages but the pool "
                f"only has {self.allocator.num_pages - 1} "
                f"(raise RAY_TPU_INFER_PAGES or shrink the request)")
        if self.max_queue and len(self.waiting) >= self.max_queue:
            raise QueueFullError(
                f"request {req.rid}: waiting queue at its cap of "
                f"{self.max_queue} (RAY_TPU_INFER_MAX_QUEUE) — "
                "shedding load instead of queueing unboundedly")
        self.waiting.append(req)

    def _prefix_walk(self, req: Request) -> List[int]:
        """Walk the prompt's full pages through the index and return
        the hit pages — a prefix of the full pages, stopped at the
        first miss.  The chained hashes are immutable per request, so
        the first attempt computes and caches them on the request and
        pool-pressure retries only re-do the (cheap) lookups — which
        *must* re-run: pages registered since the last attempt can
        turn misses into hits.

        Registrable pages are those fully covered by the prompt
        (boundary <= prompt length: decode writes start at position
        ``plen``, so they are immutable).  *Hit-eligible* pages stop
        one token earlier — the page holding the final prompt token is
        never taken as a hit even when full, because that token's
        logits seed the first sampled token, so at least one suffix
        token must always prefill."""
        if self.prefix_index is None:
            req.chain_hashes = req.chain_hashes or []
            return []
        if req.chain_hashes is None:
            req.chain_hashes = PrefixIndex.chain_hashes(
                req.prompt, self.page_size, salt=req.hash_salt)
        hits: List[int] = []
        # an imported request (r20 disagg) never prefills: EVERY full
        # context page is hit-eligible, including the one holding the
        # final context token — its logits were already consumed on the
        # prefill side, so nothing here needs to re-run
        eligible = (len(req.chain_hashes)
                    if req.import_payload is not None
                    else PrefixIndex.hit_eligible(len(req.prompt),
                                                  self.page_size))
        for h_i in req.chain_hashes[:eligible]:
            page = self.prefix_index.lookup(h_i)
            if page is None:
                break
            hits.append(page)
        return hits

    def try_admit(self) -> Optional[Request]:
        """Move the queue head into a free slot, or None (FIFO: a large
        stuck head does not get bypassed by smaller requests — simple
        and starvation-free)."""
        if not self.waiting or not self.free_slots:
            return None
        req = self.waiting[0]
        need = pages_needed(len(req.prompt) + req.max_new_tokens,
                            self.page_size)
        walk_t0 = time.monotonic()
        hits = self._prefix_walk(req)
        walk_dur = time.monotonic() - walk_t0
        # exact feasibility check before touching any state: acquiring
        # the hits removes the idle ones from the allocatable pool, so
        # the fresh allocation needs that much headroom beyond them —
        # failing here keeps a blocked head from churning refcounts
        # and idle-LRU order on every tick
        idle_hits = sum(1 for p in hits if self.allocator.is_idle(p))
        if need - len(hits) > self.allocator.free_count - idle_hits:
            return None
        # acquire hits BEFORE allocating fresh pages: an idle hit must
        # not be evicted by our own allocation's LRU sweep
        for p in hits:
            self.allocator.acquire(p)
        fresh = self.allocator.alloc(need - len(hits))
        assert fresh is not None        # guaranteed by the check above
        self.waiting.popleft()
        slot = self.free_slots.pop()
        pages = hits + fresh
        req.slot, req.pages = slot, pages
        req.n_hit_pages = len(hits)
        req.cached_tokens = len(hits) * self.page_size
        req.admitted_ts = time.monotonic()
        self.page_table[slot, :] = GARBAGE_PAGE
        self.page_table[slot, :len(pages)] = pages
        self.lengths[slot] = 0
        self.active[slot] = req
        if hits:
            self.prefix_hit_pages += len(hits)
            self.prefix_hit_tokens += req.cached_tokens
            self.prefix_requests_hit += 1
        if req.trace is not None and req.trace.sampled:
            # only the admitting walk is recorded — blocked attempts
            # re-walk but never admit, and a span per blocked tick
            # would drown the ring
            from ray_tpu.telemetry import trace as _trace
            _trace.record_span(
                "prefix_walk", req.trace,
                start=_trace.epoch_of(walk_t0), dur=walk_dur,
                hits=len(hits), eligible=len(req.chain_hashes or []))
        return req

    def register_prefix(self, req: Request) -> None:
        """Register the request's freshly-prefilled full prompt pages
        in the index (the engine calls this *after* the prefill
        executable has written their K/V — content must be in cache
        before a hash can hand the page to another request)."""
        if self.prefix_index is None:
            return
        for i in range(req.n_hit_pages, len(req.chain_hashes)):
            self.prefix_index.register(req.chain_hashes[i],
                                       req.pages[i])

    def flush_prefix(self) -> None:
        """Invalidate the whole prefix cache (weight swap: every
        cached K/V page was computed under the OLD params, and the
        index is keyed by token content alone, so a post-swap lookup
        would happily serve stale attention context).  Idle pages go
        back to the free list; pages still referenced by active
        sequences stay allocated (those sequences are mid-flight under
        the old weights by the caller's choice) but are unregistered,
        so no *new* request can share them — they free normally at
        retire.  Queued requests re-run their (now-missing) lookups at
        the next admission attempt."""
        if self.prefix_index is None:
            return
        self.allocator.flush_idle()
        self.prefix_index.clear()

    # ----------------------------------------------------------- retire
    def retire(self, slot: int) -> Request:
        req = self.active.pop(slot)
        self.allocator.release(req.pages)
        req.pages = None
        req.slot = None
        req.done = True
        self.page_table[slot, :] = GARBAGE_PAGE
        self.lengths[slot] = 0
        self.free_slots.append(slot)
        return req

    def retire_hold(self, slot: int) -> Request:
        """Retire like :meth:`retire` but KEEP the request's page
        references (``req.pages`` stays set, refcounts unmoved) — the
        disaggregation export seam: the slot frees for the next
        admission while the cached K/V survives for
        ``export_request``.  The engine owns the held request from
        here; the leak audit stays red until the pages are released
        (export or the failure path), which is exactly how orphaned
        exports are caught."""
        req = self.active.pop(slot)
        req.slot = None
        req.done = True
        self.page_table[slot, :] = GARBAGE_PAGE
        self.lengths[slot] = 0
        self.free_slots.append(slot)
        return req

    # ------------------------------------------------------------ views
    @property
    def has_work(self) -> bool:
        return bool(self.waiting or self.active)

    def prefix_stats(self) -> Dict[str, Any]:
        return {
            "enabled": self.prefix_index is not None,
            "hit_pages": self.prefix_hit_pages,
            "hit_tokens": self.prefix_hit_tokens,
            "requests_hit": self.prefix_requests_hit,
            "registered_pages": (len(self.prefix_index)
                                 if self.prefix_index is not None
                                 else 0),
            "idle_pages": self.allocator.idle_count,
            "evictions": self.allocator.evictions,
        }

    def prefix_digest(self) -> frozenset:
        """Registered-chain-hash snapshot for fleet prefix-affinity
        routing (empty when the prefix cache is off)."""
        if self.prefix_index is None:
            return frozenset()
        return self.prefix_index.digest()
