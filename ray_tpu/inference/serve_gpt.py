"""GPT serving: the inference engine behind a ``serve`` deployment.

One replica owns one :class:`~ray_tpu.inference.engine.InferenceEngine`
and a single *pump* task that advances ``engine.step()`` in an executor
thread (the compiled step blocks; the event loop must keep accepting
requests while it runs) and fans the ``(rid, token, done)`` events out
to per-request asyncio queues.  Each HTTP/handle request is an async
generator that drains its queue — tokens flow through the existing
``ServeReplica.handle_request_streaming`` path, one object-ref slot per
token, and the handle-side ``DeploymentResponseGenerator`` yields them
as they land.  Continuous batching happens inside the engine: requests
arriving mid-stream join free decode slots without disturbing running
sequences.  The engine keeps one decode in flight: a tick returns the
first tokens of the requests it admitted and the events of the decode
the *previous* tick dispatched, so this fan-out (and the executor hop
back into ``step()``) runs while the chip is at work on the next
decode, and ``has_work()`` stays true until the last token in flight
has been returned.

Abandoned streams: closing the request's (replica-side) generator —
asyncio cancellation, ``aclose()``, the proxy tearing down a
disconnected HTTP response — cancels the sequence in the engine so its
decode slot frees within a tick.  A *handle* consumer that silently
drops its ``DeploymentResponseGenerator`` does **not** close the
replica-side generator (the object-ref streaming protocol carries no
consumer-liveness signal today); the **idle-stream reaper**
(``RAY_TPU_INFER_STREAM_IDLE``, default off) covers that hole: a
request whose stream has tokens waiting but has not been pumped for
the budget is cancelled — slot/pages/prefix refcounts released, a
typed :class:`StreamIdleError` left on the queue for any late reader
— instead of decoding to ``max_new_tokens`` for a reader that is
gone.  A consumer merely *waiting* on a slow engine (empty queue) is
never reaped.

Usage (see the README serving quickstart)::

    import ray_tpu, ray_tpu.serve as serve
    from ray_tpu.inference.serve_gpt import GPTDeployment

    ray_tpu.init()
    handle = serve.run(GPTDeployment.bind(model="tiny"), name="gpt")
    stream = handle.options(stream=True).remote(
        {"tokens": [1, 2, 3], "max_new_tokens": 8})
    for token in stream:
        ...
"""

from __future__ import annotations

import asyncio
import time
from typing import Any, Dict, Optional

import ray_tpu.serve as serve
from ray_tpu.inference.sampling import SamplingParams
from ray_tpu.util import tracing


class ReplicaDrainingError(RuntimeError):
    """Typed admission rejection while the replica drains: new
    requests must go to another replica (the router's retry signal);
    in-flight streams keep decoding to completion."""


class StreamIdleError(RuntimeError):
    """Typed cancellation of an abandoned stream: tokens sat unread
    past ``RAY_TPU_INFER_STREAM_IDLE``, so the request was retired
    (everything released).  A late consumer sees this instead of a
    silent hang on a queue nothing feeds anymore."""


def parse_request(request: Dict[str, Any]) -> Dict[str, Any]:
    """The one parser for the serving payload dict — the deployment
    and the fleet router both route requests through it, so a field
    added to the payload can never silently exist in one path and not
    the other."""
    return {
        "max_new_tokens": int(request.get("max_new_tokens", 16)),
        "sampling": SamplingParams(
            temperature=float(request.get("temperature", 0.0)),
            top_k=int(request.get("top_k", 0)),
            top_p=float(request.get("top_p", 1.0)),
            seed=int(request.get("seed", 0)),
            # multi-tenant (r25): which LoRA adapter this request
            # decodes under; absent/None = the base model
            model_id=request.get("model_id")),
        "want_logprobs": bool(request.get("logprobs", False)),
        "eos_token": request.get("eos_token"),
        "ttft_deadline_s": request.get("ttft_deadline_s"),
        "deadline_s": request.get("deadline_s"),
    }


def _build_engine(model: str, model_config: Optional[Dict[str, Any]],
                  engine_config: Optional[Dict[str, Any]], seed: int):
    import jax

    from ray_tpu.inference.engine import InferenceEngine, refuse_unserved
    from ray_tpu.models import gpt, longcat, sarvam

    # a preset is a classmethod of its model's config (``CONFIG``), and
    # the model's module names its own (``PRESETS``) and draws the
    # weights (``init_params``)
    models = (gpt, longcat, sarvam)
    module = next((m for m in models if model in m.PRESETS), None)
    if module is None:
        raise ValueError(
            f"unknown model preset {model!r}; expected one of "
            f"{sum((m.PRESETS for m in models), ())}")
    cfg = getattr(module.CONFIG, model)(**(model_config or {}))
    # before any weight is drawn (a config that runs its own stack
    # passes: it says what it holds to that stack)
    refuse_unserved(cfg)
    # dispatch only: nothing waits for the device here
    with tracing.span("setup/weights") as sp:
        params = module.init_params(cfg, jax.random.PRNGKey(seed))
        leaves = jax.tree.leaves(params)
        sp.set(leaves=len(leaves), bytes=sum(x.nbytes for x in leaves))
    return cfg, InferenceEngine(cfg, params, **(engine_config or {}))


def _replica_resources() -> Dict[str, Any]:
    """One chip per replica wherever the cluster has chips (resolved by
    ``serve.run``): a replica without the TPU resource lands on a worker
    its node manager pinned to the CPU, and would serve from there
    without a word.  ``.options(ray_actor_options=...)`` overrides."""
    import ray_tpu
    return ({"num_tpus": 1}
            if ray_tpu.cluster_resources().get("TPU", 0) >= 1 else {})


@serve.deployment(max_ongoing_requests=32,
                  ray_actor_options=_replica_resources)
class GPTDeployment:
    """Streaming GPT deployment over the continuous-batching engine.

    ``model``: a ``GPTConfig`` preset name (random-init weights —
    checkpoint loading rides ``train.checkpoint.load_pytree`` via
    ``params`` plumbing once a serving checkpoint format lands);
    ``model_config`` / ``engine_config``: kwargs forwarded to
    ``GPTConfig.<preset>()`` / :class:`InferenceEngine`.

    Request payload (one dict): ``{"tokens": [...], "max_new_tokens":
    int, "temperature": float, "top_k": int, "top_p": float, "seed":
    int, "eos_token": int | None, "logprobs": bool,
    "ttft_deadline_s": float | None, "deadline_s": float | None,
    "model_id": str | None}`` —
    yields generated token ids; with ``"logprobs": True`` each item is
    ``{"token": int, "logprob": float}`` instead (the sampled token's
    model logprob — ``log_softmax`` of the raw logits, parity-tested
    against a teacher-forced recompute in ``tests/test_inference.py``).
    The deadline keys override the ``RAY_TPU_INFER_TTFT_DEADLINE`` /
    ``RAY_TPU_INFER_DEADLINE`` defaults per request; an expired
    request is retired (slot/pages/prefix refcounts released) and its
    stream raises the typed
    :class:`~ray_tpu.inference.scheduler.DeadlineExceededError`.

    **Load shedding**: with ``RAY_TPU_INFER_MAX_QUEUE`` set, an
    over-cap submit raises
    :class:`~ray_tpu.inference.scheduler.QueueFullError` from the
    request's async generator — the streaming path delivers it as the
    stream's error at first iteration, so the client sees an
    immediate typed rejection (retry / another replica) instead of a
    request parked in an unbounded queue.
    """

    def __init__(self, model: str = "tiny",
                 model_config: Optional[Dict[str, Any]] = None,
                 engine_config: Optional[Dict[str, Any]] = None,
                 seed: int = 0,
                 watchdog_s: Optional[float] = None,
                 stream_idle_s: Optional[float] = None):
        self.cfg, self.engine = _build_engine(model, model_config,
                                              engine_config, seed)
        self._queues: Dict[int, asyncio.Queue] = {}
        self._pump_task: Optional[asyncio.Task] = None
        self._draining = False
        from ray_tpu.inference.config import infer_config
        icfg = infer_config()
        watchdog_s = (icfg.watchdog if watchdog_s is None
                      else watchdog_s)
        # idle-stream reaper: rid -> when the consumer last took an
        # item (or the request was submitted); swept by the pump
        self.stream_idle_s = (icfg.stream_idle if stream_idle_s is None
                              else stream_idle_s) or None
        self._last_pumped: Dict[int, float] = {}
        self.streams_reaped = 0
        self._watchdog = None
        if watchdog_s:
            from ray_tpu.resilience.watchdog import EngineWatchdog
            self._watchdog = EngineWatchdog(
                self.engine, timeout_s=watchdog_s).start()

    async def __call__(self, request: Dict[str, Any]):
        if self._draining:
            raise ReplicaDrainingError(
                "replica is draining: admission stopped, in-flight "
                "requests finishing — retry on another replica")
        parsed = parse_request(request)
        want_logprobs = parsed["want_logprobs"]
        # r24: a bare deployment request (no fleet router in front)
        # mints its own trace here — the engine's spans still land in
        # the flight recorder and the dashboard timeline
        from ray_tpu.telemetry import trace as trace_mod
        ctx = trace_mod.mint()
        root_id = trace_mod.record_span(
            "request", ctx, start=time.time(), dur=0.0,
            prompt_tokens=len(request["tokens"]),
            max_new=parsed["max_new_tokens"])
        trace_ctx = ctx.child(root_id) if root_id is not None else ctx
        rid = self.engine.submit(
            request["tokens"],
            max_new_tokens=parsed["max_new_tokens"],
            sampling=parsed["sampling"],
            eos_token=parsed["eos_token"],
            ttft_deadline_s=parsed["ttft_deadline_s"],
            deadline_s=parsed["deadline_s"],
            trace_ctx=trace_ctx)
        queue: asyncio.Queue = asyncio.Queue()
        self._queues[rid] = queue
        self._last_pumped[rid] = time.monotonic()
        self._ensure_pump()
        try:
            while True:
                item = await queue.get()
                # the consumer is live: it just took an item (a
                # consumer *waiting* on an empty queue is tracked by
                # the queue being empty, not by this stamp)
                self._last_pumped[rid] = time.monotonic()
                if isinstance(item, BaseException):
                    raise item       # pump died: surface, don't hang
                token, done, logprob = item
                # the yield returns when the consumer asks for the next
                # item, and the consumers here (the replica's re-yield,
                # the worker's commit of the item to the object store)
                # await nothing in between: the span is what one
                # streamed token holds the event loop for
                with tracing.span("serve/emit", rid=rid, done=int(done)):
                    yield ({"token": token, "logprob": logprob}
                           if want_logprobs else token)
                if done:
                    return
        finally:
            self._queues.pop(rid, None)
            self._last_pumped.pop(rid, None)
            # abandoned mid-stream (client disconnect): retire the
            # sequence instead of decoding to max_new_tokens in a slot
            # nobody is reading (no-op for normal completion)
            self.engine.cancel(rid)

    def _ensure_pump(self) -> None:
        if self._pump_task is None or self._pump_task.done():
            self._pump_task = asyncio.get_running_loop().create_task(
                self._pump())

    async def _pump(self) -> None:
        """Advance the engine while any request is in flight; the
        compiled step runs in an executor thread so the event loop
        keeps admitting new requests mid-stream.  A step failure fans
        out to every waiting consumer — a hung stream is worse than a
        failed one."""
        loop = asyncio.get_running_loop()
        try:
            while True:
                await self._pump_engine(loop)
                if not (self.stream_idle_s and self._queues):
                    return
                # engine idle but unread streams remain (abandoned
                # consumers): keep the reaper alive until they drain
                # or age out — otherwise their queues would persist on
                # a quiescent replica until new traffic revives the
                # pump.  New work re-enters the step loop above.
                self._reap_idle_streams()
                await asyncio.sleep(min(self.stream_idle_s / 4, 0.02))
        except BaseException as e:  # noqa: BLE001 — deliver, then die
            for queue in self._queues.values():
                queue.put_nowait(e)
            raise

    async def _pump_engine(self, loop) -> None:
        more = self.engine.has_work()
        while more:
            events = await loop.run_in_executor(None,
                                                self.engine.step)
            # from the tick's return to the last queue fed: the time
            # between two ticks is the serve front's, and this is the
            # part of it the pump itself spends.  ``more=0``: the pump
            # stops here, and until the next tick the replica has
            # nothing to serve
            with tracing.span("serve/fanout", events=len(events),
                              streams=len(self._queues)) as sp:
                self._fan_out(events)
                self._reap_idle_streams()
                more = self.engine.has_work()
                sp.set(more=int(more))

    def _fan_out(self, events) -> None:
        for ev in events:
            rid, token, done = ev
            queue = self._queues.get(rid)
            if queue is None:
                continue
            if queue.qsize() == 0:
                # empty -> nonempty: the idle clock measures how
                # long tokens sit UNREAD, so it starts when the
                # first unread token lands — not at the last
                # consumer read (a consumer blocked in get()
                # through a slow step would otherwise look idle
                # the moment the token arrives)
                self._last_pumped[rid] = time.monotonic()
            if ev.error is not None:
                # deadline expiry: the engine already released
                # the slot/pages; surface the typed error as the
                # stream's failure
                queue.put_nowait(ev.error)
            else:
                queue.put_nowait((token, done, ev.logprob))

    def _reap_idle_streams(self) -> None:
        """Cancel requests whose stream has tokens waiting but whose
        consumer has not taken one for ``stream_idle_s`` — the r10
        silently-dropped-generator hole.  An empty queue (consumer
        blocked on a slow engine) never reaps; only unread tokens
        aging out do."""
        if self.stream_idle_s is None:
            return
        now = time.monotonic()
        for rid, queue in list(self._queues.items()):
            if queue.qsize() == 0:
                continue
            if now - self._last_pumped.get(rid, now) \
                    <= self.stream_idle_s:
                continue
            if rid in self.engine._requests:
                self.engine.cancel(rid)
                # a late reader must raise, not hang on a queue the
                # pump no longer feeds
                queue.put_nowait(StreamIdleError(
                    f"request {rid}: stream not pumped for "
                    f"{self.stream_idle_s:.3f}s with tokens waiting "
                    "(RAY_TPU_INFER_STREAM_IDLE) — request "
                    "cancelled, slot/pages released"))
                self.streams_reaped += 1
            # else: the engine already finished it — nothing held and
            # nothing to count; just stop tracking the unread queue
            # (a late reader still drains its buffered tokens to done)
            self._queues.pop(rid, None)
            self._last_pumped.pop(rid, None)

    # ------------------------------------------------------------ drain
    async def drain(self, poll_s: float = 0.05,
                    timeout_s: Optional[float] = None) -> Dict[str, Any]:
        """Graceful shutdown: stop admission (``__call__`` raises a
        typed :class:`ReplicaDrainingError` from now on), let every
        in-flight request decode to completion, then report.  The
        autoscaler's scale-down / a preemption notice calls this so a
        replica exits with zero dropped streams; the engine's own
        clean-idle invariants (no held slots/pages) are what "finished"
        means.

        ``timeout_s`` bounds the wait on a pump that is alive but not
        finishing (a wedged step — the watchdog's scenario): past it,
        drain gives up WITHOUT touching engine state (the stuck step
        may still hold it) and reports ``drained: False`` so the
        preemption handler can escalate instead of hanging forever."""
        self._draining = True
        deadline = (None if timeout_s is None
                    else time.monotonic() + timeout_s)
        while True:
            pump_alive = (self._pump_task is not None
                          and not self._pump_task.done())
            if pump_alive:
                if deadline is not None and \
                        time.monotonic() > deadline:
                    # the watchdog stays ARMED: the replica is still
                    # running with a possibly wedged engine — this is
                    # exactly the scenario it reports on
                    stats = self.engine.stats()
                    return {"drained": False,
                            "reason": "pump still running past the "
                                      "drain timeout (wedged step?)",
                            "free_slots": stats["free_slots"],
                            "active": stats["active"],
                            "waiting": stats["waiting"]}
                await asyncio.sleep(poll_s)
                continue
            if self.engine.has_work():
                # the pump is dead (step failure) or never ran, so
                # nothing will tick the engine again: retire every
                # leftover request host-side — the replica must exit
                # with slots/pages/refcounts released, not hang
                # waiting for a tick that cannot come (consumers
                # already got the pump's error fan-out)
                self.engine.drain_requests()
            break
        if self._watchdog is not None:
            self._watchdog.stop()
        stats = self.engine.stats()
        return {"drained": True,
                "requests_done":
                    self.engine.telemetry.summary().get(
                        "requests_done", 0)
                    if self.engine.telemetry.enabled else None,
                "free_slots": stats["free_slots"],
                "active": stats["active"],
                "waiting": stats["waiting"]}

    def telemetry_summary(self) -> Dict[str, Any]:
        import jax

        from ray_tpu._private.compile_cache import compile_stats
        summary = self.engine.telemetry.summary()
        summary["stats"] = self.engine.stats()
        # only this process can say where its engine runs and what it
        # compiled: the device as jax reports it, its memory counters,
        # and the process-wide compile/persistent-cache counts
        devices = jax.devices()
        summary["device"] = {"platform": devices[0].platform,
                             "kind": devices[0].device_kind,
                             "count": len(devices),
                             "memory": devices[0].memory_stats()}
        summary["jax_compiles"] = compile_stats()
        summary["draining"] = self._draining
        summary["streams_reaped"] = self.streams_reaped
        if self._watchdog is not None:
            summary["watchdog_wedges"] = self._watchdog.wedges
        return summary
