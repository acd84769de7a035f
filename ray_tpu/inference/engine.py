"""TPU-native continuous-batching inference engine for the GPT family.

The training path compiles one step function and reuses it forever; the
serving path has to survive arbitrary request shapes without paying XLA
compiles on the hot path.  Two mechanisms bound the compile surface
(the arXiv:2011.03641 lesson — steady-state recompiles are the TPU
serving killer):

- **shape buckets**: prompts pad to the smallest configured prefill
  bucket that fits, so prefill compiles at most once per bucket;
- **fixed decode slots**: the decode step is compiled exactly once for
  ``[slots]``-shaped inputs; continuous batching admits/retires
  sequences into those slots (host-side scheduler, Podracer-style
  colocated with the compiled steps) without changing the shape.

**Prefix caching** (``RAY_TPU_INFER_PREFIX``, r12) removes the shared
part of the prefill itself: full prompt pages register in a host-side
content-addressed index, admission installs hits into the page-table
row with refcount bumps, and only the uncached suffix runs through a
*cached-context prefill* executable — suffix self-attention plus
attention over the gathered cached pages, one executable per suffix
bucket with the cached length as a traced scalar, so the compile
surface does not grow with traffic.  Sharing is host metadata plus one
more bucketed step; the compiled prefill/decode steps above never
change shape.

Both step functions are AOT-compiled (``jit(...).lower().compile()``)
into an explicit compile cache with hit/miss counters — an unexpected
shape *raises* instead of silently recompiling, and the zero-recompile
acceptance test asserts on the counters.

**Disaggregated serving seams (r20).**  A prefill-pool engine runs
*first-token-stop* submissions — ``submit(max_new_tokens=1,
hold_pages=True)`` — whose pages survive retirement for
:meth:`export_request` (the KV handoff payload); a decode-pool engine
takes the payload through :meth:`import_submit`, which admits like any
request but installs the pages (resident ones as prefix hits, the
rest written host-side between ticks) and seeds the slot at the
absolute context offset, so the ordinary fixed-slot decode step
continues the sequence — neither seam adds an executable.

The steps themselves derive from the training model: ``embed`` +
``layer_apply`` with a KV-cache hook threaded through (post-RoPE keys
written to the paged cache, decode attention over the live pages where
they lie via ``ops/attention.py:decode_attention``), plus the model's
own final norm / tied head so cached decode logits match teacher-forced
``forward`` logits bit-for-bit-modulo-dtype (parity-tested in
``tests/test_inference.py``).  The stacked cache arrays (their format
is ``kv_cache.py``'s) are donated through every step and carried whole
through the layer scan: the hook rewrites only the pages the new tokens
land in, at ``(layer, page)``, and reads only pages named by ``(layer,
page)``, so no step materialises a layer's pool and steady-state decode
allocates nothing (the lowered structure is asserted in
``tests/test_inference.py``, the TPU compiler's in
``tests/test_tpu_aot.py``).

Single-device by design for now: ``pallas_call`` has no SPMD rule and
a serving replica owns one chip; sharded multi-chip decode is an open
ROADMAP item.
"""

from __future__ import annotations

import contextlib
import dataclasses
import threading
import time
from typing import Any, Dict, List, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ray_tpu.adapters import (AdapterRegistry, AdapterStore,
                              AdapterUnavailableError, LoraConfig,
                              lora_config, salt_bytes)
from ray_tpu.adapters import lora as lora_mod
from ray_tpu.inference import kv_cache as kvc
from ray_tpu.inference.config import default_buckets, infer_config
from ray_tpu.inference.sampling import (SamplingParams, sample_path,
                                        sample_tokens_logprobs)
from ray_tpu.inference.scheduler import (DeadlineExceededError,
                                         Request, SlotScheduler)
from ray_tpu.models import gpt as gpt_mod
from ray_tpu.ops.attention import (_NEG_INF, absorb_query, expand_output,
                                   latent_kv, latent_prefill_attention)
from ray_tpu.util import tracing


class StepEvent(tuple):
    """One ``step()`` event: unpacks and compares as the classic
    ``(rid, token, done)`` 3-tuple, with the sampled token's model
    logprob riding along as an attribute (``ev.logprob``) so logprob
    consumers (the serve stream's ``logprobs`` option, the RL rollout
    actors) don't force a tuple-shape change on every caller.

    ``ev.error`` (default None) is the failure channel: a request
    retired by deadline expiry emits one final event with
    ``done=True``, ``token=-1`` and the typed exception here — the
    serve pump raises it into the request's stream, ``generate()``
    re-raises it, and tuple consumers that ignore the attribute still
    see a clean terminal event."""

    def __new__(cls, rid: int, token: int, done: bool, logprob: float,
                error: Optional[BaseException] = None):
        self = super().__new__(cls, (rid, token, done))
        self.logprob = logprob
        self.error = error
        return self

    def __getnewargs__(self):
        # tuple's default reduce would replay __new__ with the bare
        # 3-tuple; events cross process boundaries here (object store,
        # remote rollout actors), so pickle must carry all five args
        return (self[0], self[1], self[2], self.logprob, self.error)


@dataclasses.dataclass(eq=False)
class _Flight:
    """One dispatched step whose sampled tokens are still on the device.

    The engine fetches and delivers these oldest first (``_land``), a
    decode only after the *next* decode is dispatched, so the fetch, the
    per-row delivery and the serve front's fan-out happen while the
    chip works.  ``rows`` pairs a row of the sampler's output with the
    request it was dispatched for: a request that ended meanwhile
    (``eos_token``, cancel, deadline) is recognised by identity and its
    row dropped, whoever holds the slot by then."""
    kind: str                       # "prefill" | "prefill_cached" | "decode"
    rows: List[Tuple[int, Request]]
    out: Any                        # (tokens, logprobs), device arrays
    span: "tracing.Span"            # the dispatch: infer/<kind>, with
    #                                 its attributes (bucket, ahead)
    path: Optional[str] = None      # the sampler body, where counted
    logits: Any = None              # device logits, under debug_logits
    moe: Any = None                 # the step's expert-layer counts
    #                                 (device array), for a routed model


@jax.jit
def _lay_token(tokens, slot, token):
    """``tokens`` [slots] with ``token`` [1] at ``slot``: a first token
    (a prefill's sample, still on the device) laid over the previous
    decode's sampled tokens, which are the next decode's input."""
    return tokens.at[slot].set(token[0])


def _token_rows(kind: str, args):
    """bool [B, S]: the rows of a step of ``kind`` that are tokens of a
    sequence, from the step's own arguments (``InferenceEngine.
    _build_step``): not a free slot's row of a decode (its table starts
    at the garbage page), not a bucket's padding.  For a stack that
    treats rows unequally (an expert layer computes and counts the
    tokens alone)."""
    if kind == "decode":
        return args[2][:, :1] != kvc.GARBAGE_PAGE
    tokens, last = args[0], args[-2]
    return (jnp.arange(tokens.shape[1]) < last)[None]


def _cached_context_attention(q, kctx, vctx, ks, vs, cached_len,
                              scale: Optional[float] = None):
    """Suffix queries over (cached prefix pages + causal suffix self).

    q/ks/vs: [1, S, H, D] — the suffix's (post-RoPE) queries and its
    own full-precision keys/values; kctx/vctx: [1, C, H, D] — the
    slot's gathered page context (only positions < ``cached_len`` are
    the shared prefix; everything else, including the just-written
    suffix copy and garbage pages, is masked out).  One softmax over
    the concatenated [ctx | self] score axis keeps the math identical
    to attention over the full ``cached + suffix`` sequence.  Masked-
    einsum XLA path — runs anywhere, shards nowhere special; the
    Pallas strip-mined variant is the on-chip follow-up.
    """
    B, S, H, D = q.shape
    C = kctx.shape[1]
    if scale is None:
        scale = D ** -0.5
    sc = jnp.einsum("bqhd,bkhd->bhqk", q, kctx,
                    preferred_element_type=jnp.float32) * scale
    ss = jnp.einsum("bqhd,bkhd->bhqk", q, ks,
                    preferred_element_type=jnp.float32) * scale
    ctx_mask = (jnp.arange(C) < cached_len)[None, None, None, :]
    causal = (jnp.arange(S)[:, None]
              >= jnp.arange(S)[None, :])[None, None]
    s = jnp.concatenate([jnp.where(ctx_mask, sc, _NEG_INF),
                         jnp.where(causal, ss, _NEG_INF)], axis=-1)
    m = jnp.max(s, -1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, -1, keepdims=True)                  # [B, H, S, 1]
    o = (jnp.einsum("bhqk,bkhd->bqhd", p[..., :C].astype(vctx.dtype),
                    vctx, preferred_element_type=jnp.float32)
         + jnp.einsum("bhqk,bkhd->bqhd", p[..., C:].astype(vs.dtype),
                      vs, preferred_element_type=jnp.float32))
    l = jnp.swapaxes(l, 1, 2)                          # [B, S, H, 1]
    return (o / jnp.maximum(l, 1e-30)).astype(q.dtype)


def refuse_unserved(cfg) -> None:
    """Raise, by name, for what a ``GPTConfig`` may ask that the serve
    path does not have (the train path has it): window layers, grouped
    K/V heads, the dropless expert layer.  A config that runs its own
    stack (``serve_hidden``: ``models/longcat.py``) says what it holds
    to that stack, not to this block."""
    if hasattr(cfg, "serve_hidden"):
        return
    unserved = [what for what, there in (
        (f"window layers (layer_types={getattr(cfg, 'layer_types', None)}"
         f", window={getattr(cfg, 'window', None)})",
         getattr(cfg, "layer_types", None) is not None),
        (f"grouped K/V heads (n_kv_heads={getattr(cfg, 'n_kv_heads', None)}"
         f" under n_heads={getattr(cfg, 'n_heads', None)})",
         getattr(cfg, "n_kv_heads", None) not in (
             None, getattr(cfg, "n_heads", None))),
        ("held_experts (the dropless expert layer of the train path)",
         getattr(cfg, "held_experts", None) is not None)) if there]
    if unserved:
        raise NotImplementedError(
            "GPTConfig with " + "; ".join(unserved) + " is not served: "
            "inference/kv_cache.py keeps one page population and one "
            "row size [H, D] for every layer (a window layer would "
            "release pages behind its window, a grouped layer's row "
            "has n_kv_heads heads), ops/attention.py:decode_attention "
            "reads one K/V head a query head and every live page, and "
            "the engine's step runs models/gpt.py's dense FFN; such a "
            "config runs on the train path (models/training.py:"
            "build_gpt_train)")


class InferenceEngine:
    """Continuous-batching decode engine over one GPT parameter set.

    ``submit()`` enqueues a request and returns its id; ``step()``
    advances the world by one engine tick — admit waiting sequences
    into free slots (one bucketed prefill each), then one batched
    decode for every active slot — and returns ``(rid, token, done)``
    events.  ``generate()`` is the run-to-completion convenience;
    streaming callers (the serve deployment) pump ``step()`` and fan
    events out per request.

    **One decode in flight.**  The engine runs one decode ahead of what
    the host has seen: a tick dispatches its decode on the device's own
    tokens (the previous sampler call's output, the first token of a
    request prefilled in between laid over its slot), and only then
    fetches and delivers the *previous* decode's tokens — so a tick
    returns the first tokens of the requests it admitted and the events
    of the decode dispatched one tick earlier, and the host's fetch,
    delivery and fan-out run while the chip works.  What the next
    dispatch needs is reckoned by count at dispatch (``lengths``, the
    sampler's ``counts``, "this row is the request's last":
    ``len(generated) + in_flight``); what the caller sees (the event,
    retiring, telemetry) happens at delivery.  A request ended by what
    no count foresees (``eos_token``, a cancel, a deadline) may meet
    one row of its own still in flight: the row's cache write lands
    beyond the request's context, ordered before any later writer of
    the same page by the donated cache state every step threads, and
    its sampled token is dropped.  ``has_work()`` stays true while a
    token is in flight.

    Knobs default to :func:`ray_tpu.inference.config.infer_config`
    (``RAY_TPU_INFER_*``); constructor arguments pin them for tests and
    A/B drivers.  ``debug_logits`` stashes each request's logits rows
    in ``logits_trace[rid]`` for the parity tests.

    ``executable_cache``: params are *call arguments* of the compiled
    steps, not baked constants, so executables only depend on (config,
    geometry).  Callers building several engines over the same model
    shape (re-deploys, A/B drivers, tests) can pass a shared dict to
    compile once per process; the per-engine compile/hit counters still
    count this engine's cache misses/hits.
    """

    def __init__(self, cfg: "gpt_mod.GPTConfig", params, *,
                 slots: Optional[int] = None,
                 page_size: Optional[int] = None,
                 num_pages: Optional[int] = None,
                 buckets: Optional[Tuple[int, ...]] = None,
                 kv_dtype: Optional[str] = None,
                 prefix: Optional[bool] = None,
                 max_queue: Optional[int] = None,
                 ttft_deadline: Optional[float] = None,
                 deadline: Optional[float] = None,
                 telemetry: Optional[bool] = None,
                 debug_logits: bool = False,
                 executable_cache: Optional[Dict[Any, Any]] = None,
                 lora: Union["LoraConfig", bool, None] = None,
                 adapter_store: Optional["AdapterStore"] = None):
        if getattr(cfg, "n_experts", 0) > 0:
            # a routed model is served through models/longcat.py's
            # block, whose expert layer is dropless
            raise NotImplementedError(
                "GPTConfig(n_experts > 0) is not served: its expert layer "
                "(parallel/moe.py:moe_layer) drops the picks past a "
                "static capacity computed from the step's row count, so "
                "a decode of a few rows through the cache cannot "
                "reproduce a prefill's output row by row; the serve "
                "path's routed layer is parallel/moe.py:dropless_moe")
        refuse_unserved(cfg)
        # in the start-up record (``util/tracing.py``): the weights put
        # on the device, the cache pool made, the bank
        with tracing.span("setup/engine") as sp:
            from ray_tpu._private.compile_cache import enable_compile_cache
            enable_compile_cache()
            icfg = infer_config()
            self.cfg = cfg
            self.params = jax.device_put(params)
            self.slots = slots if slots is not None else icfg.slots
            self.page_size = (page_size if page_size is not None
                              else icfg.page_size)
            self.kv_dtype = kv_dtype or icfg.kv_dtype
            self.prefix = icfg.prefix if prefix is None else bool(prefix)
            self.max_queue = (icfg.max_queue if max_queue is None
                              else max_queue)
            # default per-request deadlines (0/None = none); per-submit
            # overrides win.  Stored as None-or-positive so the expiry
            # sweep can skip requests without budgets cheaply.
            self.ttft_deadline = (icfg.ttft_deadline if ttft_deadline
                                  is None else float(ttft_deadline)) or None
            self.deadline = (icfg.deadline if deadline is None
                             else float(deadline)) or None
            if self.kv_dtype not in ("model", "int8"):
                raise ValueError(f"unknown kv_dtype {self.kv_dtype!r} "
                                 "(check RAY_TPU_KV_DTYPE)")
            if self.slots < 1:
                raise ValueError(f"need >= 1 decode slot, got {self.slots} "
                                 "(check RAY_TPU_INFER_SLOTS)")
            if self.page_size < 1:
                raise ValueError(f"page_size must be >= 1, got "
                                 f"{self.page_size}")
            if self.max_queue < 0:
                raise ValueError(f"max_queue must be >= 0, got "
                                 f"{self.max_queue} "
                                 "(check RAY_TPU_INFER_MAX_QUEUE)")
            self.buckets = tuple(sorted(
                b for b in (buckets or icfg.buckets
                            or default_buckets(cfg.max_seq))
                if b <= cfg.max_seq)) or (cfg.max_seq,)
            max_pages_per_slot = kvc.pages_needed(cfg.max_seq, self.page_size)
            num_pages = num_pages or icfg.pages or (
                self.slots * max_pages_per_slot + 1)
            self.max_pages_per_slot = max_pages_per_slot
            self.scheduler = SlotScheduler(
                slots=self.slots, page_size=self.page_size,
                num_pages=num_pages, max_pages_per_slot=max_pages_per_slot,
                prefix=self.prefix, max_queue=self.max_queue)
            if self._latent:
                self.cache = kvc.KVCache(
                    n_layers=cfg.cache_layers, num_pages=num_pages,
                    page_size=self.page_size, dtype=cfg.dtype,
                    kv_dtype=self.kv_dtype, latent=self._latent)
            else:
                self.cache = kvc.KVCache(
                    n_layers=cfg.n_layers, num_pages=num_pages,
                    page_size=self.page_size, n_heads=cfg.n_heads,
                    head_dim=cfg.head_dim, dtype=cfg.dtype,
                    kv_dtype=self.kv_dtype)
            # multi-tenant LoRA serving (r25): ``lora`` takes a LoraConfig
            # (explicit geometry), True (env defaults, forced on), or
            # None/False (follow RAY_TPU_LORA).  When on, the engine holds
            # an adapter **bank** — stacked [N, L, in, r]/[N, L, r, out]
            # factors, slot 0 the all-zeros identity — that rides every
            # compiled step as a call argument, plus the per-engine LRU
            # registry mapping model_id -> bank slot.  ``adapter_store``
            # shares the fleet's publication point; lora-on engines
            # default to a private store so direct put()/load flows work.
            if isinstance(lora, LoraConfig):
                self.lora_cfg: Optional[LoraConfig] = lora
            elif lora is True:
                self.lora_cfg = lora_config()
            elif lora is None and lora_config().enabled:
                self.lora_cfg = lora_config()
            else:
                self.lora_cfg = None
            if self._latent and self.lora_cfg is not None:
                kvc.refuse_latent("LoRA adapters (lora)")
            if self.lora_cfg is not None:
                self._lora_targets = lora_mod.effective_targets(
                    cfg, self.lora_cfg)
                self.lora_bank = lora_mod.bank_zeros(cfg, self.lora_cfg)
                self.adapters: Optional[AdapterRegistry] = AdapterRegistry(
                    self.lora_cfg.cache_slots)
                self.adapter_store: Optional[AdapterStore] = (
                    adapter_store if adapter_store is not None
                    else AdapterStore())
                lora_key = ("lora", self.lora_cfg.rank,
                            self.lora_cfg.bank_slots, self._lora_targets)
            else:
                self._lora_targets = ()
                self.lora_bank = None
                self.adapters = None
                self.adapter_store = adapter_store
                lora_key = None
            # compile cache: key -> AOT executable; an executable raises on
            # shape drift, so the counters below are honest.  Keys carry
            # the full (cfg, geometry) so a shared cache cannot alias
            # engines of different shapes.
            self._compiled: Dict[Any, Any] = (
                executable_cache if executable_cache is not None else {})
            self._exec_key = (cfg, self.slots, self.page_size, num_pages,
                              max_pages_per_slot, self.kv_dtype, lora_key)
            self.compile_counts: Dict[str, int] = {
                "prefill": 0, "prefill_cached": 0, "decode": 0}
            self.hit_counts: Dict[str, int] = {
                "prefill": 0, "prefill_cached": 0, "decode": 0}
            self._requests: Dict[int, Request] = {}
            # retired-but-held requests (r20 disagg export seam): pages
            # stay refcounted until export_request/release_held — the leak
            # audit counts them, so an orphaned export is visible
            self._held: Dict[int, Request] = {}
            self.exports = 0
            self.imports = 0
            # r24 tracing: the replica id spans carry (set by
            # fleet.replica.EngineReplica so cross-replica trace trees can
            # attribute work; None = a bare engine)
            self.trace_label: Optional[str] = None
            # dispatched steps whose tokens the host has not fetched,
            # oldest first (see _Flight), and events delivered outside a
            # tick (``_level``), which the next tick returns
            self._flight: List[_Flight] = []
            self._backlog: List[StepEvent] = []
            self._next_rid = 0
            self._cancelled: set = set()
            self._lock = threading.Lock()   # submit() vs step() admissions
            # liveness bookkeeping for the resilience watchdog: ``ticks``
            # counts completed step() calls, ``last_tick_ts`` their wall
            # time — a wedged step loop is has_work + neither moving
            self.ticks = 0
            self.last_tick_ts = time.monotonic()
            self.deadline_exceeded = 0
            # versioned params (the RL weight-publication contract): the
            # construction snapshot is version 0 and may alias caller-held
            # arrays, so the first set_params() does not delete it
            self.param_version = 0
            self._owns_params = False
            self.debug_logits = debug_logits
            # rid -> [logits row per generated token], appended in event
            # order (parity tests only; off by default)
            self.logits_trace: Dict[int, List[np.ndarray]] = {}
            from ray_tpu.telemetry.infer import InferTelemetry
            from ray_tpu.telemetry.config import TelemetryConfig
            config = (TelemetryConfig(enabled=True) if telemetry is True
                      else TelemetryConfig(enabled=False)
                      if telemetry is False else None)
            self.telemetry = InferTelemetry(config=config)
            self.telemetry.record_cache_info(
                kv_dtype=self.kv_dtype, cache_bytes=self.cache.bytes,
                kv_bytes_per_slot=self.cache.bytes_per_slot(
                    max_pages_per_slot))
            sp.set(slots=self.slots, pages=int(self.cache.num_pages),
                   buckets=len(self.buckets))

    # ---------------------------------------- multi-tenant LoRA (r25)
    def _adapter_release(self, req: Request) -> None:
        """Drop a retiring request's pin on its exact (tenant,
        version) (idempotent: the slot resets so double-retire paths
        can't double-unpin)."""
        if req.adapter_slot > 0 and self.adapters is not None:
            self.adapters.unpin(req.model_id, req.adapter_version)
        req.adapter_slot = 0

    def _check_adapter(self, model_id: str, adapter) -> None:
        """Gate factors against the bank geometry BEFORE the install:
        a tenant publishing a different rank/target set/dims must
        surface as the typed per-request error, never as a jax shape
        error escaping step() and killing the replica for everyone."""
        why = lora_mod.bank_mismatch(self.lora_bank, adapter)
        if why is not None:
            raise AdapterUnavailableError(
                model_id, "published factors do not fit the serving "
                f"bank: {why}")

    def _load_adapter(self, model_id: str,
                      version: Optional[int] = None, *,
                      pin: bool = False) -> Tuple[int, int]:
        """Resolve ``model_id`` to a resident bank slot -> ``(slot,
        installed version)``, loading through the adapter store on a
        miss (``version=None`` tracks the store's latest; a republish
        lands in a *fresh* row, never over a pinned one).  The install
        is an eager ``.at[].set`` over the bank call-arg — compile
        counters never move.  ``pin=True`` pins the resolved (tenant,
        version) under the same lock acquisition that resolves it, so
        the row cannot vanish between resolution and admission.  Fault
        site ``serve.adapter_load`` fires on the load leg only (cache
        hits are unaffected) and surfaces as the typed
        :class:`AdapterUnavailableError`.  Takes ``self._lock``
        internally — callers must NOT hold it: the store checkout can
        block on an object-store fetch, and submit()/cancel()/stats()
        must not stall behind it."""
        reg = self.adapters
        want = version
        if want is None and self.adapter_store is not None:
            want = self.adapter_store.latest_version(model_id)
        with self._lock:
            ent = reg.lookup(model_id, want)
            if ent is not None:
                reg.touch(model_id, ent[1])
                if pin:
                    reg.pin(model_id, ent[1])
                reg.hits += 1
            else:
                reg.misses += 1
        if self.telemetry.enabled:
            self.telemetry.record_adapter_cache(hit=ent is not None)
        if ent is not None:
            return ent
        from ray_tpu.util import chaos
        try:
            chaos.maybe_fail("serve.adapter_load")
        except chaos.InjectedFault as fault:
            raise AdapterUnavailableError(
                model_id, f"load failed: {fault}") from fault
        if self.adapter_store is None:
            raise AdapterUnavailableError(
                model_id, "not resident and the engine has no "
                "adapter store to fetch through")
        t0 = time.monotonic()
        got, adapter, scale = self.adapter_store.checkout(model_id, want)
        try:
            self._check_adapter(model_id, adapter)
            with self._lock:
                slot, _evicted = reg.place(model_id, got)
                self.lora_bank = lora_mod.bank_install(
                    self.lora_bank, slot, adapter, scale=scale)
                if pin:
                    reg.pin(model_id, got)
        finally:
            self.adapter_store.checkin()
        wall = time.monotonic() - t0
        reg.loads += 1
        reg.load_seconds += wall
        if self.telemetry.enabled:
            self.telemetry.record_adapter_load(
                wall, resident=len(reg.resident_ids))
        return slot, got

    def load_adapter(self, model_id: str, adapter, *,
                     scale: float = 1.0, version: int = 1) -> int:
        """Install an adapter's host factors directly into the bank
        (the storeless path: tests, a colocated learner).  Returns the
        bank slot.  Requests referencing ``model_id`` resolve to it
        without touching any store."""
        if self.lora_cfg is None:
            raise AdapterUnavailableError(
                model_id, "engine built without adapter support "
                "(RAY_TPU_LORA / lora=)")
        self._check_adapter(model_id, adapter)
        with self._lock:
            slot, _evicted = self.adapters.place(model_id, int(version))
            self.lora_bank = lora_mod.bank_install(
                self.lora_bank, slot, adapter, scale=scale)
            self.adapters.loads += 1
        return slot

    def _resolve_adapters(self, events: List["StepEvent"]) -> None:
        """Give every waiting multi-tenant request a resident, pinned
        bank slot before admission (step()-only, like every bank
        mutation).  Resolution sets the prefix-chain salt — it MUST
        land before ``_prefix_walk`` first hashes the prompt, so
        adapter K/V never aliases base K/V.  A failed load retires the
        request with the typed error — degraded, never hung.  The
        engine lock is held only around registry/scheduler mutations,
        NOT across the store fetch (``_load_adapter`` takes it at the
        right points itself)."""
        if self.lora_cfg is None:
            return
        with self._lock:
            pending = [r for r in self.scheduler.waiting
                       if r.adapter_slot == -1]
        for req in pending:
            try:
                slot, got = self._load_adapter(
                    req.model_id, req.adapter_version or None,
                    pin=True)
            except AdapterUnavailableError as err:
                with self._lock:
                    if req in self.scheduler.waiting:
                        self.scheduler.waiting.remove(req)
                    self._requests.pop(req.rid, None)
                req.error = err
                req.done = True
                events.append(StepEvent(req.rid, -1, True, 0.0,
                                        error=err))
                continue
            # req fields are read by this (the step) thread only
            req.adapter_slot = slot
            req.adapter_version = got
            req.hash_salt = salt_bytes(req.model_id, got)

    def adapter_digest(self) -> frozenset:
        """Resident tenant model_ids — the router composes this into
        its affinity score beside the prefix digest."""
        if self.adapters is None:
            return frozenset()
        with self._lock:
            return self.adapters.digest()

    # --------------------------------------------------------- requests
    def submit(self, prompt, max_new_tokens: int = 16,
               sampling: Optional[SamplingParams] = None,
               eos_token: Optional[int] = None,
               ttft_deadline_s: Optional[float] = None,
               deadline_s: Optional[float] = None,
               hold_pages: bool = False,
               trace_ctx=None) -> int:
        """Enqueue one request.  ``hold_pages`` is the disaggregation
        seam (first-token-stop mode is just ``max_new_tokens=1`` with
        it set): when the request retires, its page references survive
        for :meth:`export_request` instead of releasing — the prefill
        side of a prefill/decode split.  ``trace_ctx`` (r24, a
        :class:`~ray_tpu.telemetry.trace.TraceContext`) attaches the
        request to a distributed trace: queue / prefix-walk / prefill
        spans all hang off its id."""
        prompt = [int(t) for t in prompt]
        if not prompt:
            raise ValueError("empty prompt")
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if len(prompt) + max_new_tokens > self.cfg.max_seq:
            raise ValueError(
                f"prompt ({len(prompt)}) + max_new_tokens "
                f"({max_new_tokens}) exceeds max_seq {self.cfg.max_seq}")
        if len(prompt) > self.buckets[-1]:
            raise ValueError(f"prompt length {len(prompt)} exceeds the "
                             f"largest prefill bucket {self.buckets[-1]}")
        model_id = sampling.model_id if sampling is not None else None
        with self._lock:
            # multi-tenant (r25): validate the tenant up front — a
            # typed submit-time rejection the router can re-route —
            # but defer the actual bank load to step()
            # (``_resolve_adapters``), the only thread that may mutate
            # the bank.  Under the lock so the residency probe can't
            # race a concurrent step()'s eviction/install.
            if model_id:
                if self.lora_cfg is None:
                    raise AdapterUnavailableError(
                        model_id, "engine built without adapter "
                        "support (RAY_TPU_LORA / lora=)")
                if (self.adapters.lookup(model_id) is None
                        and (self.adapter_store is None
                             or model_id not in self.adapter_store)):
                    raise AdapterUnavailableError(
                        model_id, "never published to the adapter "
                        "store")
            rid = self._next_rid
            self._next_rid += 1
            req = Request(rid=rid, prompt=prompt,
                          max_new_tokens=max_new_tokens,
                          sampling=sampling or SamplingParams(),
                          eos_token=eos_token,
                          ttft_deadline_s=(self.ttft_deadline
                                           if ttft_deadline_s is None
                                           else ttft_deadline_s
                                           or None),
                          deadline_s=(self.deadline if deadline_s
                                      is None else deadline_s or None),
                          hold_pages=bool(hold_pages),
                          trace=trace_ctx,
                          model_id=model_id or None,
                          adapter_slot=-1 if model_id else 0)
            self.scheduler.submit(req)    # validates; may raise —
            self._requests[rid] = req     # register only if accepted
            depth = len(self.scheduler.waiting)
        if self.telemetry.enabled:
            # gauge moves on enqueue too (outside the lock — metric
            # I/O must not serialize against step()'s admissions):
            # under overload there ARE no admissions, so an
            # admission-only gauge would read 0 through the backlog
            self.telemetry.record_queue_depth(depth)
        return rid

    def cancel(self, rid: int) -> None:
        """Retire ``rid`` early (abandoned stream / client disconnect).

        Processed at the start of the next :meth:`step` tick — the only
        place scheduler state mutates besides admission and delivery.
        The slot and pages free there and then; a row of the request
        still in flight on the device is dropped when it is fetched
        (its cache write precedes any later writer of the page through
        the cache state every step threads).  A no-op for
        finished/unknown rids."""
        with self._lock:
            if rid in self._requests:
                self._cancelled.add(rid)

    def drain_requests(self) -> int:
        """Retire every known request NOW, host-side (no device step):
        the teardown path for a replica whose pump died or a supervisor
        replacing a dead rollout engine — nothing may be left holding
        slots/pages/refcounts.  Safe only when no concurrent
        :meth:`step` is running (the callers' situation by
        construction: the stepping thread is gone).  Held exports are
        released too — a reaped corpse must audit clean even when it
        died between first token and handoff.  Returns how many
        requests were retired."""
        with self._lock:
            rids = list(self._requests)
        for rid in rids:
            self.cancel(rid)
        self._process_cancels()
        # tokens in flight belonged to those requests: dropped unfetched
        # (the device may be the reason for the teardown)
        self._flight.clear()
        self._backlog.clear()
        held = list(self._held)
        for rid in held:
            self.release_held(rid)
        return len(rids) + len(held)

    # --------------------------------------------- disagg handoff (r20)
    def export_request(self, rid: int) -> "kvc.KVHandoff":
        """Export a retired-but-held request's cached K/V as a
        :class:`~ray_tpu.inference.kv_cache.KVHandoff` and release its
        pages — the prefill side of the prefill/decode split.  The
        payload covers every cached context token (``prompt +
        generated[:-1]``; with first-token-stop submissions that is
        exactly the prompt) plus the next input token the decode side
        seeds its slot with.  Registered full pages park idle in the
        prefix pool on release, so a later handoff of the same prefix
        still prefills nothing here."""
        if self._latent:
            kvc.refuse_latent("export_request (a KVHandoff)")
        req = self._held.pop(rid)
        context = list(req.prompt) + list(req.generated[:-1])
        n_pages = kvc.pages_needed(len(context), self.page_size)
        arrays = kvc.export_pages(self.cache, req.pages[:n_pages])
        handoff = kvc.KVHandoff(
            context=context, page_size=self.page_size,
            kv_dtype=self.kv_dtype, dtype=str(self.cache.dtype),
            chain_hashes=kvc.PrefixIndex.chain_hashes(
                context, self.page_size, salt=req.hash_salt),
            next_token=int(req.generated[-1]),
            next_logprob=float(req.logprobs[-1]),
            trace=(req.trace.to_wire() if req.trace is not None
                   else None),
            model_id=req.model_id,
            adapter_version=req.adapter_version, **arrays)
        self.scheduler.allocator.release(req.pages)
        req.pages = None
        self.exports += 1
        return handoff

    def release_held(self, rid: int) -> bool:
        """Release a held export without reading it (the failure path:
        the handoff faulted, the stream finished at its first token, or
        the replica is being reaped).  True if ``rid`` was held."""
        req = self._held.pop(rid, None)
        if req is None:
            return False
        self.scheduler.allocator.release(req.pages)
        req.pages = None
        return True

    def import_submit(self, handoff: "kvc.KVHandoff", *,
                      max_new_tokens: int,
                      sampling: Optional[SamplingParams] = None,
                      eos_token: Optional[int] = None,
                      deadline_s: Optional[float] = None) -> int:
        """Enqueue a KV handoff on the decode side of the split.

        The request admits through the ordinary scheduler (slot +
        pages reserved up front; under pressure it waits — queued
        imports ARE the slot-occupancy backlog the decode pool scales
        on), but instead of a prefill the admission installs the
        payload: hit pages (already resident by chain hash) are
        acquired with zero writes, missing pages get the handoff's
        contents, the slot seeds at the absolute context offset, and
        the next decode tick continues the sequence through the one
        compiled decode executable — nothing new ever compiles here.
        ``max_new_tokens`` counts the tokens still to generate (the
        prefill side's first token is already delivered and seeds the
        sampling counts, so sampled continuations stay
        trajectory-exact, not just greedy ones)."""
        if self._latent:
            kvc.refuse_latent("import_submit (a KVHandoff)")
        if handoff.page_size != self.page_size:
            raise ValueError(
                f"handoff page_size {handoff.page_size} != engine "
                f"page_size {self.page_size} — one fleet geometry")
        if handoff.kv_dtype != self.kv_dtype \
                or (handoff.k is not None
                    and str(handoff.k.dtype) != str(self.cache.dtype)):
            raise ValueError(
                f"handoff kv_dtype {handoff.kv_dtype!r} "
                f"(storage {handoff.dtype}) != engine "
                f"{self.kv_dtype!r} ({self.cache.dtype}) — the "
                "contents would be reinterpreted, not converted")
        if max_new_tokens < 1:
            raise ValueError("a handoff needs >= 1 token left to "
                             "decode — a finished stream has nothing "
                             "to hand off")
        context = [int(t) for t in handoff.context]
        if len(context) + 1 + max_new_tokens > self.cfg.max_seq:
            raise ValueError(
                f"context ({len(context)}) + remaining tokens "
                f"({1 + max_new_tokens}) exceeds max_seq "
                f"{self.cfg.max_seq}")
        model_id = getattr(handoff, "model_id", None)
        if model_id and self.lora_cfg is None:
            raise AdapterUnavailableError(
                model_id, "decode-side engine built without adapter "
                "support (RAY_TPU_LORA / lora=)")
        trace_ctx = None
        if handoff.trace:
            # the trace context rode the payload across replicas:
            # importer-side spans join the exporter's tree
            from ray_tpu.telemetry import trace as trace_mod
            trace_ctx = trace_mod.TraceContext.from_wire(handoff.trace)
        with self._lock:
            rid = self._next_rid
            self._next_rid += 1
            # prompt = context, generated seeded at install: the
            # +1 on max_new counts the prefill-side token as this
            # request's first, keeping retire/eos/sampling-count
            # arithmetic identical to a co-located run
            req = Request(rid=rid, prompt=context,
                          max_new_tokens=max_new_tokens + 1,
                          sampling=sampling or SamplingParams(),
                          eos_token=eos_token,
                          ttft_deadline_s=None,
                          deadline_s=(self.deadline if deadline_s
                                      is None else deadline_s or None),
                          chain_hashes=list(handoff.chain_hashes),
                          import_payload=handoff,
                          trace=trace_ctx,
                          # the importer must decode under the EXACT
                          # factors the prefill used: the version pins
                          # the store fetch across republishes, and the
                          # handoff's chain hashes are already salted
                          model_id=model_id or None,
                          adapter_slot=-1 if model_id else 0,
                          adapter_version=getattr(
                              handoff, "adapter_version", 0),
                          hash_salt=salt_bytes(
                              model_id, getattr(handoff,
                                                "adapter_version", 0)))
            self.scheduler.submit(req)    # validates; may raise
            self._requests[rid] = req
            depth = len(self.scheduler.waiting)
        if self.telemetry.enabled:
            self.telemetry.record_queue_depth(depth)
        return rid

    def _process_cancels(self) -> None:
        with self._lock:
            cancelled, self._cancelled = self._cancelled, set()
            if not cancelled:
                return
            sched = self.scheduler
            for slot, req in list(sched.active.items()):
                if req.rid in cancelled:
                    sched.retire(slot)
                    self._requests.pop(req.rid, None)
                    self._adapter_release(req)
            for req in [r for r in sched.waiting
                        if r.rid in cancelled]:
                sched.waiting.remove(req)
                req.done = True
                self._requests.pop(req.rid, None)
                self._adapter_release(req)

    def _expire_deadlines(self, events: List["StepEvent"]) -> None:
        """Retire every request past its deadline, at the same safe
        point cancels process (tick start; a row still in flight for
        an expired request is dropped at its fetch, like a cancelled
        one's).  A waiting request can blow either budget (TTFT is
        total-bounded too: ``ttft <= total``); an active request only
        the total one, since admission delivered its first token in
        its admission tick.  Retirement releases everything — slot,
        pages, prefix refcounts — and emits a terminal error event the
        stream surfaces as :class:`DeadlineExceededError`."""
        now = time.monotonic()

        def expiry(req: Request, waiting: bool):
            waited = now - req.submitted_ts
            if waiting and req.ttft_deadline_s is not None \
                    and waited > req.ttft_deadline_s:
                return DeadlineExceededError(req.rid, "ttft",
                                             req.ttft_deadline_s,
                                             waited)
            if req.deadline_s is not None and waited > req.deadline_s:
                return DeadlineExceededError(req.rid, "total",
                                             req.deadline_s, waited)
            return None

        expired: List[Request] = []
        with self._lock:
            sched = self.scheduler
            for req, err in [(r, e) for r in sched.waiting
                             if (e := expiry(r, True)) is not None]:
                sched.waiting.remove(req)
                req.error = err
                req.done = True
                self._requests.pop(req.rid, None)
                self._adapter_release(req)
                expired.append(req)
            for slot, req in list(sched.active.items()):
                err = expiry(req, False)
                if err is not None:
                    sched.retire(slot)
                    req.error = err
                    self._requests.pop(req.rid, None)
                    self._adapter_release(req)
                    expired.append(req)
        for req in expired:
            self.deadline_exceeded += 1
            if self.telemetry.enabled:
                self.telemetry.record_deadline_exceeded(
                    kind=req.error.kind)
            from ray_tpu.telemetry import trace as trace_mod
            trace_mod.anomaly("deadline", trace=req.trace,
                              rid=req.rid, budget=req.error.kind,
                              budget_s=req.error.budget_s,
                              waited_s=req.error.waited_s,
                              replica=self.trace_label)
            events.append(StepEvent(req.rid, -1, True, 0.0,
                                    error=req.error))

    def set_params(self, params, *, version: Optional[int] = None) -> int:
        """Hot-swap the engine's parameters to a new snapshot.

        ``params`` is a *host-side* pytree (the object-store snapshot
        form the RL learner publishes — numpy leaves); it is copied to
        the device and the **previous** snapshot's buffers are deleted
        eagerly (the donated-buffer swap: steady-state weight
        publication holds one resident copy plus the in-flight
        transfer, never an unbounded trail of dead snapshots waiting
        for GC).  Params are call arguments of the AOT executables, so
        a swap at unchanged shapes/dtypes costs **zero recompiles** —
        the compile counters are the acceptance test.

        Like :meth:`cancel`'s contract, the swap must not race a
        concurrent :meth:`step`: call it between engine ticks (the RL
        rollout actors swap between ``generate()`` calls; a serve
        replica would route it through the pump's executor thread).

        The swap also **invalidates the prefix cache**: registered
        pages hold K/V computed under the old params, and the index
        is keyed by token content alone — without the flush, a
        post-swap request sharing a cached prefix would attend over
        stale context and its logprobs would silently stop matching
        ``forward(new_params)`` (the on-policy contract).

        Returns the new ``param_version`` (monotonic; explicit
        ``version`` pins it — publications carry the learner's own
        counter so actor-side lag is measured in learner versions)."""
        # a decode in flight ran under the snapshot about to be deleted:
        # its tokens are fetched first, and come out of the next step()
        self._level()
        self.scheduler.flush_prefix()
        new = jax.device_put(params)
        jax.block_until_ready(new)
        old, self.params = self.params, new
        if self._owns_params:
            new_ids = {id(leaf) for leaf in jax.tree.leaves(new)}
            for leaf in jax.tree.leaves(old):
                if (isinstance(leaf, jax.Array)
                        and id(leaf) not in new_ids
                        and not leaf.is_deleted()):
                    leaf.delete()
        self._owns_params = True
        self.param_version = (self.param_version + 1 if version is None
                              else int(version))
        return self.param_version

    def has_work(self) -> bool:
        """Something is queued, active, in flight on the device, or
        delivered and not yet returned: ``step()`` has events to come."""
        with self._lock:
            return bool(self.scheduler.has_work or self._flight
                        or self._backlog)

    def prefix_digest(self) -> frozenset:
        """Registered prefix chain hashes (the ``stats()["prefix"]``
        accounting's underlying index, snapshotted) — the fleet
        router matches a prompt's chained page hashes against this to
        route it to the replica whose cache already holds the prefix."""
        with self._lock:
            return self.scheduler.prefix_digest()

    def stats(self) -> Dict[str, Any]:
        """The host's view, safe to read beside a running tick: a slot
        whose request has a token in flight counts as active, and
        nothing here waits for the device."""
        return {
            "compiles": dict(self.compile_counts),
            "hits": dict(self.hit_counts),
            "free_slots": len(self.scheduler.free_slots),
            "free_pages": self.scheduler.allocator.free_count,
            "waiting": len(self.scheduler.waiting),
            "active": len(self.scheduler.active),
            "cache_bytes": self.cache.bytes,
            "kv_dtype": self.kv_dtype,
            # what decode attention dispatches to at this geometry
            "decode_impl": ("pallas" if self.cache.reads_in_place
                            else "xla"),
            # and what lays a decode's new rows into the pool
            "decode_write_impl": ("pallas" if self.cache.writes_in_place
                                  else "blend"),
            "kv_bytes_per_slot": self.cache.bytes_per_slot(
                self.max_pages_per_slot),
            "max_queue": self.max_queue,
            "param_version": self.param_version,
            "prefix": self.scheduler.prefix_stats(),
            "deadline_exceeded": self.deadline_exceeded,
            "ticks": self.ticks,
            # disagg handoff accounting (r20): exports/imports served,
            # and how many retired requests still hold pages for export
            "exports": self.exports,
            "imports": self.imports,
            "held": len(self._held),
            # multi-tenant LoRA (r25): registry residency/hit counters
            # plus the shared store's publish/fetch accounting
            "adapters": {
                "enabled": self.lora_cfg is not None,
                **(self.adapters.stats()
                   if self.adapters is not None else {}),
                "store": (self.adapter_store.stats()
                          if self.adapter_store is not None else None),
            },
        }

    # ------------------------------------------------------ engine tick
    def step(self) -> List[StepEvent]:
        """One engine tick -> [(rid, token, done), ...] events (each a
        :class:`StepEvent`: 3-tuple-compatible, ``.logprob`` rides
        along).

        A tick admits (dispatching each admitted request's prefill),
        dispatches the decode of every active row, and only then
        fetches and delivers what was dispatched before that decode: so
        it returns the first tokens of the requests it admitted and the
        tokens of the decode the *previous* tick dispatched, while the
        chip is already at work on this tick's."""
        events, self._backlog = self._backlog, []
        with tracing.span("infer/step", tick=self.ticks) as tick:
            admitted = self._admit(events)
            sent = self._decode()
            self._land(events, keep=sent)
            self.ticks += 1
            self.last_tick_ts = time.monotonic()
            tick.set(admitted=admitted, events=len(events),
                     active=len(self.scheduler.active))
        return events

    def _admit(self, events: List[StepEvent]) -> int:
        """The scheduler's part of a tick, an ``infer/admit`` span per
        pass: cancels, deadlines and adapters first, then requests taken
        off the queue one at a time (prefix walk, page allocation, an
        import's install), each one's prefill dispatched before the
        next is looked at (its first token is fetched with the rest of
        the tick's, ``_land``).  Returns how many were admitted."""
        admitted = 0
        while True:
            with tracing.span("infer/admit",
                              waiting=len(self.scheduler.waiting)) as sp:
                if not admitted:
                    self._process_cancels()
                    self._expire_deadlines(events)
                    self._resolve_adapters(events)
                with self._lock:
                    req = self.scheduler.try_admit()
                if req is None:
                    return admitted
                admitted += 1
                sp.set(hit_pages=req.n_hit_pages)
                if req.import_payload is not None:
                    self._install_import(req, events)
                    continue
            self._prefill(req)

    def generate(self, prompts, max_new_tokens: int = 16,
                 sampling: Optional[SamplingParams] = None,
                 eos_token: Optional[int] = None,
                 return_logprobs: bool = False,
                 ttft_deadline_s: Optional[float] = None,
                 deadline_s: Optional[float] = None
                 ) -> Union[List[List[int]],
                            Tuple[List[List[int]], List[List[float]]]]:
        """Run-to-completion over a batch of prompts (ordered results).

        With ``return_logprobs`` the result is ``(token lists, logprob
        lists)`` — each generated token's model logprob, aligned with
        the token lists (the RL rollout form).  A deadline expiry
        raises its :class:`DeadlineExceededError` (streaming callers
        get it per-request via the event's ``error`` instead)."""
        rids = [self.submit(p, max_new_tokens, sampling, eos_token,
                            ttft_deadline_s=ttft_deadline_s,
                            deadline_s=deadline_s)
                for p in prompts]
        out: Dict[int, List[int]] = {r: [] for r in rids}
        lps: Dict[int, List[float]] = {r: [] for r in rids}
        err: Optional[BaseException] = None
        while err is None and self.has_work():
            for ev in self.step():
                rid, tok, _done = ev
                if ev.error is not None:
                    if err is None and rid in out:
                        err = ev.error
                    continue
                if rid in out:          # not a stale leftover rid
                    out[rid].append(tok)
                    lps[rid].append(ev.logprob)
        if err is not None:
            # don't abandon the surviving siblings mid-decode: their
            # slots/pages would stay held and poison the next call
            for r in rids:
                self.cancel(r)
            self._process_cancels()
            self._level()       # nor a row of theirs in flight
            raise err
        if return_logprobs:
            return ([out[r] for r in rids], [lps[r] for r in rids])
        return [out[r] for r in rids]

    # ---------------------------------------------------------- prefill
    def _bucket_for(self, n: int) -> int:
        for b in self.buckets:
            if n <= b:
                return b
        raise ValueError(f"no prefill bucket fits length {n}")

    def _prefill(self, req: Request) -> None:
        """Dispatch ``req``'s prefill and the sampling of its first
        token; nothing is fetched here.  What the rest of the tick
        needs is settled now: the slot's length, and the prompt's pages
        in the prefix index — every later reader of those pages takes
        the cache state this dispatch returns, so it runs after it.
        The first token stays on the device, where the tick's decode
        takes it as its input (``_token_input``)."""
        sched = self.scheduler
        slot = req.slot
        plen = len(req.prompt)
        cached = req.cached_tokens
        # the two prefill flavors differ only in executable + scalar
        # args: cold runs the whole prompt, a prefix hit runs just the
        # suffix (attending over the already-cached pages — zero
        # compute for the shared prefix)
        if cached:
            fill = req.prompt[cached:]
            kind = "prefill_cached"
            scalars = (np.int32(cached), np.int32(len(fill)))
        else:
            fill = req.prompt
            kind = "prefill"
            scalars = (np.int32(plen),)
        bucket = self._bucket_for(len(fill))
        tokens = np.zeros((1, bucket), np.int32)
        tokens[0, :len(fill)] = fill
        tr = req.trace if req.trace is not None and req.trace.sampled \
            else None
        ids = {"trace_id": tr.trace_id} if tr is not None else {}
        with tracing.span(f"infer/{kind}", rid=req.rid, bucket=bucket,
                          cached=cached, **ids) as sp:
            logits, moe = self._run_step((kind, bucket), [req], tokens,
                                         *scalars, sched.page_table[slot])
            out, path = self._sample_slots(sp, logits, [req])
        req.in_flight += 1
        self.scheduler.register_prefix(req)
        sched.lengths[slot] = plen
        self._flight.append(_Flight(
            kind, [(0, req)], out, sp, path=path,
            logits=logits if self.debug_logits else None, moe=moe))

    def _land_prefill(self, rec: _Flight, ssp) -> None:
        """The records of a prefill whose first token has reached the
        host (the fetch span ``ssp`` has just ended).  Its wall time is
        what the step thread spent on it: the dispatch and the wait of
        the fetch, not what was dispatched in between."""
        sp = rec.span
        req = rec.rows[0][1]
        plen, cached = len(req.prompt), req.cached_tokens
        bucket = sp.attributes["bucket"]
        wall = sp.dur + ssp.dur
        ttft = ssp.end - req.submitted_ts
        if req.trace is not None and req.trace.sampled:
            from ray_tpu.telemetry import trace as trace_mod
            tr = req.trace
            trace_mod.record_span(
                "queue", tr,
                start=trace_mod.epoch_of(req.submitted_ts),
                dur=req.admitted_ts - req.submitted_ts, rid=req.rid,
                replica=self.trace_label)
            trace_mod.record_span(
                "prefill", tr, start=trace_mod.epoch_of(sp.start),
                dur=wall, rid=req.rid, bucket=bucket,
                cached=cached, kind=rec.kind, replica=self.trace_label)
            trace_mod.event("first_token", tr, rid=req.rid,
                            ttft_s=ttft, replica=self.trace_label)
        if self.telemetry.enabled:
            self.telemetry.record_queue(
                req.admitted_ts - req.submitted_ts,
                depth=len(self.scheduler.waiting))
            self.telemetry.record_prefill(
                wall, prompt_tokens=plen, bucket=bucket,
                cached_tokens=cached)
            self.telemetry.record_ttft(
                ttft, prefix_hit=cached > 0,
                trace_id=req.trace.trace_id
                if req.trace is not None else None)

    def _install_import(self, req: Request, events) -> None:
        """Seed an admitted import's slot from its handoff payload —
        the decode side of the split, with ZERO compiled steps: hit
        pages are already resident, missing pages are written host-side
        between ticks, and the next batched decode picks the slot up
        like any mid-sequence request (input token = the prefill
        side's sampled token, position = the absolute context
        offset)."""
        handoff = req.import_payload
        sched = self.scheduler
        slot = req.slot
        t0 = time.monotonic()
        n_ctx = len(req.prompt)
        n_pages = kvc.pages_needed(n_ctx, self.page_size)
        present = handoff.page_list
        needed = [i for i in range(req.n_hit_pages, n_pages)]
        missing = [i for i in needed if i not in present]
        if missing:
            # a stripped (warm/partial) handoff whose resident pages
            # were evicted between the router's digest check and this
            # admission: release everything and surface the typed
            # re-prefill signal — never decode over garbage pages
            sched.retire(slot)
            req.error = kvc.HandoffContentMissing(req.rid, len(missing))
            self._requests.pop(req.rid, None)
            self._adapter_release(req)
            events.append(StepEvent(req.rid, -1, True, 0.0,
                                    error=req.error))
            return
        if needed:
            kvc.import_pages(self.cache,
                             [req.pages[i] for i in needed], handoff,
                             [present.index(i) for i in needed])
        # contents are in cache: the imported full pages are immutable
        # from here on and registrable for later handoffs/prompts
        self.scheduler.register_prefix(req)
        sched.lengths[slot] = n_ctx
        req.generated = [int(handoff.next_token)]
        req.logprobs = [float(handoff.next_logprob)]
        req.cached_tokens = n_ctx
        req.import_payload = None      # drop the content reference
        self.imports += 1
        if req.trace is not None and req.trace.sampled:
            from ray_tpu.telemetry import trace as trace_mod
            trace_mod.record_span(
                "handoff.install", req.trace,
                start=trace_mod.epoch_of(t0),
                dur=time.monotonic() - t0, rid=req.rid,
                pages_written=len(needed), hit_pages=req.n_hit_pages,
                replica=self.trace_label)

    def leak_free(self) -> bool:
        """Inventory audit: the usable pages partition exactly into
        free / idle / held, every adapter pin belongs to a live request
        and no adapter checkout is left in flight.  The fleet replicas'
        audits call through here."""
        alloc = self.scheduler.allocator
        free = set(alloc._free)
        idle = set(alloc._idle)
        held = set(alloc._refcount)
        usable = set(range(1, alloc.num_pages))
        if (free | idle | held != usable or (free & idle)
                or (free & held) or (idle & held)):
            return False
        if len(alloc._free) != len(alloc._free_set):
            return False
        if self.adapters is not None:
            # every live pin must belong to a live multi-tenant
            # request, and store checkouts must have been checked in
            live = sum(1 for r in self._requests.values()
                       if r.adapter_slot > 0)
            if self.adapters.pinned_total != live:
                return False
        if (self.adapter_store is not None
                and self.adapter_store.in_flight != 0):
            return False
        return True

    # ----------------------------------------------------------- decode
    def _decode(self) -> Optional[_Flight]:
        """Dispatch one decode over every active row that has a token
        to come -> its record in ``_flight``, left in flight (None if
        there is no such row).  Nothing is fetched: the token
        input is the previous decode's sampled tokens where that decode
        is still in flight, and what delivery used to settle for the
        next dispatch is settled here, by count."""
        from ray_tpu.util import chaos

        # fault site BEFORE any cache/scheduler mutation and before the
        # donated executable dispatches: an injected decode failure
        # leaves the engine state consistent (slots/pages still held,
        # cache arrays live), so supervisors can cancel/drain cleanly
        chaos.maybe_fail("infer.decode")
        sched = self.scheduler
        reqs: List[Optional[Request]] = [None] * self.slots
        dead = []
        for slot, req in sched.active.items():
            # a request whose last token is in flight holds its slot
            # until that token is delivered, and is not decoded again
            if len(req.generated) + req.in_flight < req.max_new_tokens:
                reqs[slot] = req
            else:
                dead.append(slot)
        rows = [(slot, r) for slot, r in enumerate(reqs) if r is not None]
        if not rows:
            return None
        # fresh copies: the scheduler's arrays change below, while the
        # dispatched step may still be reading its arguments
        lengths = sched.lengths.copy()
        page_table = sched.page_table.copy()
        if dead:
            # held slots that are done but for delivery ride this
            # decode as dead rows (its shape is fixed): their page rows
            # mask to the garbage page, so nothing of theirs is read,
            # written or counted as a token, and their sampled outputs
            # are never delivered
            page_table[dead, :] = kvc.GARBAGE_PAGE
        # the pages this decode's attention reads: each row's context
        # with the token it writes, by the host's own count
        live = lengths[[slot for slot, _req in rows]]
        pages = int((live // self.page_size + 1).sum())
        with tracing.span("infer/decode", active=len(rows),
                          ahead=1, pages=pages,
                          tokens=int(live.sum()) + len(rows)) as sp:
            logits, moe = self._run_step(("decode",), reqs,
                                         self._token_input(rows), lengths,
                                         page_table)
            out, path = self._sample_slots(sp, logits, reqs)
        for slot, req in rows:
            sched.lengths[slot] += 1    # the input token is cached
            req.in_flight += 1
        rec = _Flight("decode", rows, out, sp, path=path,
                      logits=logits if self.debug_logits else None,
                      moe=moe)
        self._flight.append(rec)
        return rec

    def _token_input(self, rows: List[Tuple[int, Request]]):
        """Each row's next input token, [slots].  Where a decode is in
        flight its sampled tokens are the input and never leave the
        device; otherwise the host's own last tokens are.  Laid over
        either: the first token of a request whose prefill is in flight
        (still on the device), and the host's token of a row the decode
        in flight did not hold (an import seeded at its install)."""
        prev, first = None, {}
        for rec in self._flight:
            if rec.kind == "decode":
                prev = rec
            else:
                first[rec.rows[0][1].rid] = rec.out[0]
        if prev is not None:
            tokens = prev.out[0]
            held = {req.rid for _row, req in prev.rows}
        else:
            tokens = np.zeros((self.slots,), np.int32)
            for slot, req in rows:
                if req.generated:
                    tokens[slot] = req.generated[-1]
        for slot, req in rows:
            token = first.get(req.rid)
            if token is None and prev is not None and req.rid not in held:
                token = np.array(req.generated[-1:], np.int32)
            if token is not None:
                tokens = _lay_token(tokens, np.int32(slot), token)
        return tokens

    # ------------------------------------------------- fetch and deliver
    def _land(self, events, keep: Optional[_Flight] = None) -> None:
        """Fetch and deliver every dispatched step but ``keep`` (the
        decode a tick leaves in flight), oldest first: an
        ``infer/sample`` span around each fetch — there the host waits
        for the device — and an ``infer/deliver`` span around what the
        tokens mean for the caller."""
        landing = [rec for rec in self._flight if rec is not keep]
        self._flight = [] if keep is None else [keep]
        for rec in landing:
            with tracing.span("infer/sample", rows=len(rec.rows)) as ssp:
                if rec.path is not None:
                    ssp.set(path=rec.path)
                # a routed model's counts ride on the same fetch (None,
                # and nothing more to fetch, for any other)
                (toks, logps), moe = jax.device_get((rec.out, rec.moe))
                if moe is not None:
                    self._land_moe(rec, ssp, moe)
            with self._deliver_span(events):
                for _row, req in rec.rows:
                    req.in_flight -= 1
                # a request that ended with this row in flight (eos,
                # cancel, deadline): the row's token is dropped
                live = [(row, req) for row, req in rec.rows
                        if not req.done]
                if rec.kind == "decode":
                    self._land_decode(rec, ssp, len(live))
                elif live:
                    self._land_prefill(rec, ssp)
                host_logits = (np.asarray(rec.logits) if live
                               and rec.logits is not None else None)
                for row, req in live:
                    if host_logits is not None:
                        self.logits_trace.setdefault(req.rid, []).append(
                            host_logits[row])
                    self._deliver(req, int(toks[row]), float(logps[row]),
                                  events)

    def _land_moe(self, rec: _Flight, ssp, moe) -> None:
        """A routed model's expert-layer counts of one step
        (``parallel/moe.py:MOE_COUNTS``, summed over its layers), on
        the host: the fetch's span carries the step's kind, the picks
        this chip computed, the experts they hit and the trips of the
        experts' loop, and the telemetry adds them up."""
        counts = dict(zip(self.cfg.step_counts, (int(c) for c in moe)))
        ssp.set(kind=rec.kind, moe_held=counts["held_picks"],
                moe_hit=counts["experts_hit"],
                moe_trips=counts["loop_trips"])
        if self.telemetry.enabled:
            self.telemetry.record_moe(decode=rec.kind == "decode",
                                      **counts)

    def _land_decode(self, rec: _Flight, ssp, delivered: int) -> None:
        """The records of a decode whose tokens have reached the host:
        its wall time is what the step thread spent on it, the dispatch
        and the wait of the fetch."""
        sp = rec.span
        wall = sp.dur + ssp.dur
        traced = [r.trace.trace_id for _row, r in rec.rows
                  if r.trace is not None and r.trace.sampled]
        if traced:
            # ONE coalesced span per tick (trace_id=None: a global
            # span), carrying the sampled trace ids it served — a
            # span per (tick, request) would swamp the ring at
            # decode rate
            from ray_tpu.telemetry import trace as trace_mod
            trace_mod.record_span(
                "decode_tick", None,
                start=trace_mod.epoch_of(sp.start), dur=wall,
                active=delivered,
                trace_ids=traced, replica=self.trace_label)
        if self.telemetry.enabled:
            self.telemetry.record_decode(
                wall, active=delivered,
                ahead=bool(sp.attributes["ahead"]),
                pages_read=sp.attributes["pages"],
                pages_table=self.slots * self.max_pages_per_slot,
                rows_written=sp.attributes["active"],
                tail_pages_rewritten=(sp.attributes["active"]
                                      if self.cache.writes_in_place
                                      else self.slots))

    def _level(self) -> None:
        """Bring the host level with the device from outside a tick:
        whatever is in flight is fetched and delivered, and the events
        wait for the next :meth:`step` to return them."""
        self._land(self._backlog)

    @contextlib.contextmanager
    def _deliver_span(self, events):
        """``infer/deliver``: what a tick does with tokens once they are
        on the host (records, the caller's events, retiring), closed
        with how many events it appended and how many of them finished
        a request."""
        n0 = len(events)
        with tracing.span("infer/deliver") as sp:
            yield sp
            sp.set(events=len(events) - n0,
                   done=sum(1 for ev in events[n0:] if ev[2]))

    def _deliver(self, req: Request, tok: int, logp: float,
                 events) -> None:
        req.generated.append(tok)
        req.logprobs.append(logp)
        done = (len(req.generated) >= req.max_new_tokens
                or (req.eos_token is not None and tok == req.eos_token))
        if done:
            if req.hold_pages:
                # disagg export seam: the slot frees but the pages stay
                # refcounted for export_request/release_held
                self.scheduler.retire_hold(req.slot)
                self._held[req.rid] = req
            else:
                self.scheduler.retire(req.slot)
            # the adapter unpins with the slot either way: a held
            # export only needs pages — the importer re-pins the
            # adapter on its own replica through the handoff metadata
            self._adapter_release(req)
            if self.telemetry.enabled:
                self.telemetry.record_request_done()
            if not self.debug_logits:
                # a serve replica lives for the deployment's lifetime:
                # finished requests must not accumulate (debug engines
                # keep them so parity tests can read trajectories)
                self._requests.pop(req.rid, None)
        events.append(StepEvent(req.rid, tok, done, logp))

    # --------------------------------------------------------- sampling
    def _sample_slots(self, sp, logits, reqs: List[Optional[Request]]
                      ) -> Tuple[Tuple[Any, Any], Optional[str]]:
        """Dispatch the sampler over one token per logits row — the
        full [slots, V] decode batch (None rows are inactive, result
        discarded) or a prefill's single [1, V] row — inside the
        dispatch span ``sp``.  Returns ``((tokens, model logprobs),
        path)``, the arrays still on the device: ``_land`` fetches
        them.  A row's key is folded with the count of tokens sampled
        for its request before this one, seen by the host or not."""
        null = SamplingParams()
        seeds = np.array([(r.sampling.seed if r else 0)
                          for r in reqs], np.int32)
        counts = np.array([(len(r.generated) + r.in_flight if r else 0)
                           for r in reqs], np.int32)
        temps = np.array(
            [(r.sampling.temperature if r else null.temperature)
             for r in reqs], np.float32)
        top_ks = np.array([(r.sampling.top_k if r else 0)
                           for r in reqs], np.int32)
        top_ps = np.array([(r.sampling.top_p if r else 1.0)
                           for r in reqs], np.float32)
        path = self._sample_path(sp, temps, top_ks, top_ps)
        return sample_tokens_logprobs(logits, seeds, counts, temps,
                                      top_ks, top_ps), path

    def _sample_path(self, sp, temps, top_ks, top_ps) -> Optional[str]:
        """Where the counter or a trace will keep it (``sp`` is the
        open span around the call), the body this sampler call's rows
        select (``sampling.sample_path``, the executable's own rule on
        the same arrays): counted here, and the ``path`` of the
        ``infer/sample`` span around the call's fetch."""
        counted = self.telemetry.enabled
        if not (counted or sp.recording):
            return None
        path = sample_path(temps, top_ks, top_ps)
        if counted:
            self.telemetry.record_sample(path)
        return path

    # ---------------------------------------------------- compile cache
    def _run_step(self, key, reqs, *step_args):
        """Run the serve executable ``key`` = ``(kind[, bucket])`` over
        the cache -> (its logits, a routed model's expert-layer counts
        or None).  The one place an executable's
        arguments are assembled: ``(params, [lora_bank,] *cache.state,
        *step_args[, adapter ids])``, the ids one per row of ``reqs``
        (co-batched tenants share a tick: the bank gather routes each
        row through its own A/B factors; a dead (``None``) or base row
        rides slot 0, the identity).  The donated state that comes back
        is the cache's from here on."""
        bank = aids = ()
        if self.lora_cfg is not None:
            bank = (self.lora_bank,)
            aids = (np.array([0 if r is None else max(r.adapter_slot, 0)
                              for r in reqs], np.int32),)
        args = (self.params, *bank, *self.cache.state, *step_args, *aids)
        logits, *state = self._get_compiled(key, args)(*args)
        # a model that declares ``step_counts`` returns them here
        moe = (state.pop(0) if getattr(self.cfg, "step_counts", ())
               else None)
        self.cache.state = tuple(state)
        return logits, moe

    def _get_compiled(self, key, example_args):
        kind = key[0]
        fn = self._compiled.get(self._exec_key + key)
        if fn is not None:
            self.hit_counts[kind] += 1
            return fn
        self.compile_counts[kind] += 1
        # a miss only: which step compiled (or loaded from the
        # persistent cache), and for how long
        with tracing.span("infer/compile", kind=kind,
                          bucket=key[1] if len(key) > 1 else 0):
            fn = self._build_step(kind).lower(*example_args).compile()
        self._compiled[self._exec_key + key] = fn
        return fn

    # ------------------------------------------------------- step fns --
    @property
    def _latent(self) -> Optional[Tuple[int, int]]:
        """What the model keeps in the cache: None for K and V rows
        ``[H, D]`` a layer (a ``GPTConfig``), ``(rank, rope)`` for one
        latent row a sublayer (a config that declares ``latent_row``
        and ``cache_layers``: ``models/longcat.py``)."""
        return getattr(self.cfg, "latent_row", None)

    def _embed(self, params, tokens, positions):
        """tokens [B, S], positions [S] or [B, S] -> hidden [B, S, d].

        ``embed_tokens`` assumes positions 0..S-1 for learned tables;
        prefill/decode index the table by absolute position instead."""
        cfg = self.cfg
        x = params["embed"].astype(cfg.dtype)[tokens]
        if cfg.pos == "learned":
            pe = params["pos_embed"].astype(cfg.dtype)[positions]
            x = x + (pe if positions.ndim == 2 else pe[None])
        return x

    def _layer_scan(self, params, x, caches, positions, attn_hook,
                    lora_bank=None, lora_ids=None):
        """Run the layer stack with the whole stacked cache arrays in
        the scan carry -> (final normed hidden, caches).

        ``caches`` is the cache's state tuple of stacked ``[L, ...]``
        arrays, whose format only ``kv_cache.py`` knows.  No layer's
        pool is ever sliced out or put back:
        each layer hands ``layer_apply`` the opaque ``cache = (layer
        index, caches)``, which round-trips to ``attn_hook``; the hook
        writes the new tokens into their pages at ``(layer, page)``
        (``kv_cache.append``; a decode's rows in place,
        ``kv_cache.append_decode``), attends over the pool in place
        (``kv_cache.attend``; a cached-suffix prefill over one slot's
        gathered pages, ``kv_cache.context_dense``), and returns the
        updated stacked arrays for the carry — so only the touched
        and the live pages cross HBM.

        ``lora_bank``/``lora_ids`` (r25 multi-tenant): bank factors are
        stacked ``[N, L, ...]`` — layer axis 1 — sliced per scan step;
        ``lora_ids`` [B] routes each batch row through its tenant's
        slot (slot 0 is the all-zeros identity, so base rows cost one
        fused-zero gather, never a branch)."""
        cfg = self.cfg

        def body(carry, i):
            x, caches = carry
            lp = jax.tree.map(
                lambda a: lax.dynamic_index_in_dim(a, i, 0,
                                                   keepdims=False),
                params["layers"])
            lora = None
            if lora_bank is not None:
                lora = {k: lax.dynamic_index_in_dim(v, i, 1,
                                                    keepdims=False)
                        for k, v in lora_bank.items() if k != "scale"}
                lora["scale"] = lora_bank["scale"]
                lora["ids"] = lora_ids
            x, _aux, caches = gpt_mod.layer_apply(
                lp, x, cfg, positions=positions, attn_fn=attn_hook,
                cache=(i, caches), lora=lora)
            return (x, caches), None

        (x, caches), _ = lax.scan(
            body, (x, caches), jnp.arange(cfg.n_layers))
        x = gpt_mod._norm(x, params["ln_f"], cfg.norm,
                          bias=params.get("ln_f_b"),
                          eps=gpt_mod.norm_eps(cfg))
        return x, caches

    def _prefill_attention(self, q, k, v):
        """Causal self-attention over the bucket (no cache read — the
        prompt is the whole context).  Flash kernel on a TPU; einsum
        where the CPU was asked for (interpret-mode Pallas is only paid
        for in the dedicated kernel tests, not every engine test); any
        other backend is refused by ``use_interpret``."""
        from ray_tpu.ops.substrate import use_interpret
        if use_interpret():
            from ray_tpu.parallel.ring_attention import local_attention
            return local_attention(q, k, v, causal=True)
        from ray_tpu.ops.attention import flash_attention
        return flash_attention(q, k, v, causal=True)

    def _build_step(self, kind: str):
        """The jitted serve step of ``kind``: ``(params, [lora_bank,]
        *cache_state, <kind's arguments>[, adapter_ids]) -> (logits
        f32, [counts,] *cache_state)``, the cache state donated
        (``counts``: one int32 vector, only from a model whose config
        names them in ``step_counts``).  Every kind
        embeds, runs the layer stack with its own attention hook
        (append the new rows to the cache, read the context back,
        attend: :meth:`_kv_hooks`, or :meth:`_latent_hooks` where the
        cache keeps a latent row) and applies the head to the rows it
        answers for:

        - ``"prefill"`` (tokens [1, S_bucket], length, page_row
          [max_pages]): a cold prompt.  Attention is causal over the
          bucket itself at full precision (the prompt IS the whole
          context; quantization only affects what later steps read
          back).  Logits [1, V] of the last valid row.
        - ``"prefill_cached"`` (tokens [1, S_bucket] (suffix, padded),
          cached_len, suffix_len, page_row): the prompt's first
          ``cached_len`` tokens are already in the slot's pages (prefix
          hits — byte-identical content, bit-identical codes for int8
          because cache writes round deterministically).  The suffix's
          queries attend over the gathered cached pages (length-masked)
          *plus* causally over the suffix itself at full precision,
          merged in one softmax.  ``cached_len`` / ``suffix_len`` are
          traced scalars, so one executable per *suffix bucket* serves
          every cached length.  Logits [1, V] of the last valid row.
        - ``"decode"`` (tokens [slots] (each slot's next input token),
          lengths [slots] (tokens already cached = the new token's
          absolute position), page_table [slots, max_pages]): one row
          per slot.  Logits [slots, V].

        The benchmark finds these executables and their operations by
        name (``jit_prefill*``, ``jit_decode``, ``gpt/attn/gather``,
        ``attn/decode_pallas``, ``attn/write_pallas``): the traced
        function carries the kind's name and no scope is added here."""
        cfg = self.cfg
        lora_on = self.lora_cfg is not None
        n_state = len(self.cache.state)
        # the hook follows what the cache keeps, the stack whose model
        # it is: a config that runs its own (``models/longcat.py``)
        # offers ``serve_hidden`` and ``lm_head``, and names in
        # ``step_counts`` what its step returns beside the logits
        hooks = self._latent_hooks if self._latent else self._kv_hooks
        own_stack = getattr(cfg, "serve_hidden", None)
        counted = bool(getattr(cfg, "step_counts", ()))

        def step(params, *args):
            bank = aids = None
            if lora_on:
                bank, *args = args
                *args, aids = args
            cache_state, args = tuple(args[:n_state]), args[n_state:]
            tokens, positions, last, attn_hook = hooks(kind, args)
            counts = ()
            if own_stack is None:
                x = self._embed(params, tokens, positions)
                x, cache_state = self._layer_scan(params, x, cache_state,
                                                  positions, attn_hook,
                                                  lora_bank=bank,
                                                  lora_ids=aids)
            else:
                x, cache_state, *counts = own_stack(
                    params, tokens, positions, cache_state, attn_hook,
                    _token_rows(kind, args))
            if kind != "decode":
                x = jnp.take(x[0], last - 1, axis=0)[None, None]  # [1,1,d]
            logits = jnp.einsum("bsd,dv->bsv", x,
                                gpt_mod.lm_head(params, cfg)
                                if own_stack is None
                                else cfg.lm_head(params))[:, 0]
            return ((logits.astype(jnp.float32),)
                    + (tuple(counts) if counted else ())
                    + tuple(cache_state))

        step.__name__ = kind
        first = 2 if lora_on else 1      # cache state shifts past bank
        return jax.jit(step,
                       donate_argnums=tuple(range(first,
                                                  first + n_state)))

    def _kv_hooks(self, kind: str, args):
        """A step's ``(tokens [B, S], positions, last, attn_hook)`` over
        K and V rows ``[H, D]`` (:meth:`_build_step` has the kinds and
        their arguments)."""
        last = None
        if kind == "decode":
            tokens, lengths, page_table = args
            positions = lengths[:, None]                   # [B, 1]
            tokens = tokens[:, None]

            def attn_hook(q, k, v, cache):
                cache = kvc.append_decode(cache, k[:, 0], v[:, 0],
                                          page_table, lengths)
                o = kvc.attend(q[:, 0], cache, page_table, lengths + 1)
                return o[:, None], cache[1]
        elif kind == "prefill":
            tokens, last, page_row = args
            positions = jnp.arange(tokens.shape[1])

            def attn_hook(q, k, v, cache):
                cache = kvc.append(kvc.write_prefill, cache, k[0],
                                   v[0], page_row)
                return self._prefill_attention(q, k, v), cache[1]
        else:
            tokens, cached_len, last, page_row = args
            positions = cached_len + jnp.arange(tokens.shape[1])

            def attn_hook(q, k, v, cache):
                cache = kvc.append(kvc.write_prefill_at, cache, k[0],
                                   v[0], page_row, cached_len, last)
                kctx, vctx = kvc.context_dense(cache, page_row[None],
                                               q.dtype)
                o = _cached_context_attention(q, kctx, vctx, k, v,
                                              cached_len)
                return o, cache[1]
        return tokens, positions, last, attn_hook

    def _latent_hooks(self, kind: str, args):
        """:meth:`_kv_hooks` over one latent row a token (the cache's
        ``(rank, rope)``).  The block hands the hook a sublayer's query
        parts, the new rows' parts and its ``W_kvb``
        (``ops/attention.py`` has the algebra):

        - ``"decode"``: the rows are laid in place, the query is
          absorbed and attends over the latent pages where they lie
          (``kv_cache.attend``), and the output goes through the value
          half of ``W_kvb``;
        - ``"prefill"`` / ``"prefill_cached"``: the rows are written,
          the slot's pages gathered (``kv_cache.context_dense``), K and
          V materialised from them, and the bucket's queries attend
          over positions up to their own
          (``ops/attention.py:latent_prefill_attention``): a cold
          prefill is the cached one at ``cached_len`` 0."""
        rank = self._latent[0]
        scale = self.cfg.softmax_scale
        last = None
        if kind == "decode":
            tokens, lengths, page_table = args
            positions = lengths[:, None]                   # [B, 1]
            tokens = tokens[:, None]

            def attn_hook(q_nope, q_rot, c, k_rot, w_kvb, cache):
                cache = kvc.append_decode(cache, c[:, 0], k_rot[:, 0],
                                          page_table, lengths)
                with jax.named_scope("attn/decode_pallas/absorb"):
                    q = absorb_query(q_nope[:, 0], q_rot[:, 0], w_kvb)
                o = kvc.attend(q, cache, page_table, lengths + 1,
                               scale=scale, value_dim=rank)
                with jax.named_scope("attn/decode_pallas/expand"):
                    o = expand_output(o, w_kvb)
                return o[:, None], cache[1]
        else:
            if kind == "prefill":
                tokens, last, page_row = args
                cached_len = jnp.int32(0)
            else:
                tokens, cached_len, last, page_row = args
            positions = cached_len + jnp.arange(tokens.shape[1])

            def attn_hook(q_nope, q_rot, c, k_rot, w_kvb, cache):
                cache = kvc.append(kvc.write_prefill_at, cache, c[0],
                                   k_rot[0], page_row, cached_len, last)
                ctx, k_rot = kvc.context_dense(cache, page_row[None],
                                               c.dtype, value_dim=rank)
                k_nope, v = latent_kv(ctx[0], w_kvb)
                o = latent_prefill_attention(
                    jnp.swapaxes(q_nope[0], 0, 1),
                    jnp.swapaxes(q_rot[0], 0, 1), k_nope, k_rot[0], v,
                    cached_len, scale=scale)
                return jnp.swapaxes(o, 0, 1)[None], cache[1]
        return tokens, positions, last, attn_hook
