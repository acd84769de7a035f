"""Step-level training telemetry: the :class:`StepTelemetry` recorder.

Wraps a jitted train step (``build_gpt_train``/``build_gpt_train_pp``
``step_fn``) and emits one structured record per step:

- wall time with an explicit blocking ``jax.block_until_ready`` sync,
  split into dispatch (host returns) and sync (device drains),
- first-step compile time split from steady state — in AOT mode
  (``aot=True``) via an explicit ``lower().compile()`` whose compiled
  executable also yields the HBM footprint from ``memory_analysis()``,
- tokens/sec and an analytic-FLOPs MFU estimate
  (:mod:`ray_tpu.telemetry.flops`) against the chip peak,
- logical collective bytes/step per comm_mode
  (``ray_tpu.parallel.overlap.collective_bytes_per_step``),
- for a step that returns its expert layers' counts (``moe_counts``, a
  config with ``held_experts``): ``moe.rows``, ``moe.held_picks``,
  ``moe.experts_hit``, ``moe.imbalance`` (the busiest held expert's
  rows over the mean) and ``moe.combine_windows`` (the windows of sorted
  rows the layers' combines bring in one direction, 0 where they are
  gathers), read with the loss in the step's one fetch; and
  on the first record the attention coverage of each layer kind
  (``attn_coverage``: the share of the score square the kind's schedule
  executes beside the share it needs) and the form of the layers'
  grouped products and of their combines (``moe_product``: ``pallas`` /
  ``ragged_dot``, ``moe_combine``: ``pallas`` / ``xla``).

Records flow to three sinks: the Chrome-trace exporter
(:mod:`ray_tpu.telemetry.chrome_trace`, merged into the dashboard
``/api/timeline``), Prometheus gauges/histograms through the
control-plane metrics (``train_step_seconds`` / ``train_mfu`` /
``train_collective_bytes`` on ``/metrics``), and
:meth:`StepTelemetry.summary` (``benchmark/run.py`` reads
``train_step_ms`` from its records).  ``RAY_TPU_TELEMETRY=0``
turns the whole wrapper into identity; ``RAY_TPU_PROFILE=<dir>``
additionally captures a ``jax.profiler`` xplane trace of the first
steady steps (see :mod:`ray_tpu.telemetry.config`).
"""

from __future__ import annotations

import statistics
import sys
import time
import weakref
from typing import Any, Dict, List, Optional

from ray_tpu.telemetry import flops as flops_mod
from ray_tpu.telemetry.config import telemetry_config

# live recorders, so the chrome-trace exporter / dashboard timeline can
# merge every in-process training loop without explicit plumbing
_RECORDERS: "weakref.WeakSet[StepTelemetry]" = weakref.WeakSet()


def recorders() -> List["StepTelemetry"]:
    return list(_RECORDERS)


def _memory_dict(compiled) -> Optional[Dict[str, int]]:
    """``memory_analysis()`` of an AOT-compiled step as plain ints."""
    try:
        ma = compiled.memory_analysis()
    except Exception:  # noqa: BLE001 — backend may not implement it
        return None
    if ma is None:
        return None
    out: Dict[str, int] = {}
    for field, key in (("argument_size_in_bytes", "argument_bytes"),
                       ("output_size_in_bytes", "output_bytes"),
                       ("temp_size_in_bytes", "temp_bytes"),
                       ("alias_size_in_bytes", "alias_bytes"),
                       ("generated_code_size_in_bytes",
                        "generated_code_bytes")):
        val = getattr(ma, field, None)
        if val is not None:
            out[key] = int(val)
    if not out:
        return None
    # arguments alias outputs for donated buffers; the liveness-ish
    # total charges each once
    out["total_bytes"] = (out.get("argument_bytes", 0)
                          + out.get("output_bytes", 0)
                          + out.get("temp_bytes", 0)
                          + out.get("generated_code_bytes", 0)
                          - out.get("alias_bytes", 0))
    return out


def _arg_signature(args):
    import jax
    return tuple(
        (getattr(leaf, "shape", None), str(getattr(leaf, "dtype", "")))
        for leaf in jax.tree.leaves(args))


def _find_tokens(args, kwargs):
    """The [B, S] token array of a step call, if one is recognizable."""
    for a in list(args) + list(kwargs.values()):
        if isinstance(a, dict) and "tokens" in a:
            tok = a["tokens"]
            if hasattr(tok, "shape") and len(tok.shape) == 2:
                return tok
    return None


class StepTelemetry:
    """Per-step telemetry recorder around one jitted train step.

    ``aot=True`` routes the first call through
    ``step_fn.lower(...).compile()`` — one compile total, an exact
    compile/steady split, and ``memory_analysis()`` HBM numbers; a
    step that does not compile raises there.
    ``aot=False`` (the default the train-step builders use) never
    re-routes compilation: the first step's wall time simply includes
    the jit compile and is reported as ``first_step_s``.
    """

    _MAX_RECORDS = 10_000

    def __init__(self, cfg=None, mesh=None, *,
                 comm_mode: Optional[str] = None,
                 comm_quant: Optional[str] = None,
                 ce_mode: Optional[str] = None,
                 attn_fn=None,
                 label: str = "train",
                 aot: bool = False,
                 chip_peak_tflops: Optional[float] = None,
                 config=None):
        tcfg = config or telemetry_config()
        self.enabled: bool = tcfg.enabled
        self.cfg = cfg
        self.mesh = mesh
        self.comm_mode = comm_mode
        self.comm_quant = comm_quant
        self.ce_mode = ce_mode
        self.attn_fn = attn_fn
        self.label = label
        self.records: List[Dict[str, Any]] = []
        self.step_count = 0      # total steps seen (survives trimming)
        self.compile_s: Optional[float] = None
        self.first_step_s: Optional[float] = None
        self.memory: Optional[Dict[str, int]] = None
        self._aot = aot
        self._cfgobj = tcfg
        self._compiled = None
        self._signature = None
        self._tokens_per_step: Optional[int] = None
        self._seq: Optional[int] = None
        self._batch: Optional[int] = None
        self._peak = chip_peak_tflops
        self._fpt: Optional[float] = None   # cached; -1 = unavailable
        self._ce_path: Optional[str] = None  # cached once the shape is known
        self._coverage: Optional[float] = None   # likewise
        self._metrics = None          # lazily-created metric objects
        self._metrics_dead = False    # no cluster / emission failed
        self._metrics_last = 0.0      # last emission (monotonic)
        self._bytes_emitted = False
        self._profile_started = False
        self._profile_stopped = False
        if self.enabled:
            _RECORDERS.add(self)

    # ------------------------------------------------------------- wrap --

    def wrap(self, step_fn):
        """``step_fn -> step_fn`` (identity when telemetry is off)."""
        if not self.enabled:
            return step_fn
        import functools

        @functools.wraps(step_fn)
        def wrapped(*args, **kwargs):
            return self._call(step_fn, args, kwargs)

        wrapped.telemetry = self
        return wrapped

    def _call(self, step_fn, args, kwargs):
        import jax

        from ray_tpu.util import tracing
        i = self.step_count
        self.step_count += 1
        self._note_tokens(args, kwargs)
        self._profile(i, before=True)
        ts = time.time()
        # the step annotation is what the profiler's step view reads;
        # the phases inside it are the program's spans, and the record's
        # times are theirs (wall = dispatch start to sync end)
        with jax.profiler.StepTraceAnnotation(self.label, step_num=i):
            with tracing.span(f"{self.label}/dispatch", step=i) as disp:
                out = self._dispatch(step_fn, args, kwargs, i)
            with tracing.span(f"{self.label}/sync", step=i) as sync:
                jax.block_until_ready(out)
            with tracing.span(f"{self.label}/loss_read", step=i):
                loss = self._maybe_loss(out)
                moe = self._maybe_moe(out)
            with tracing.span(f"{self.label}/record", step=i):
                self._record(i, ts, disp, sync, loss, moe)
        self._profile(i, before=False)
        return out

    def _record(self, i, ts, disp, sync, loss, moe=None):
        rec: Dict[str, Any] = {
            "step": i,
            "ts": ts,
            "wall_s": sync.end - disp.start,
            "dispatch_s": sync.start - disp.start,
            "sync_s": sync.end - sync.start,
        }
        if i == 0 and self.compile_s is not None:
            rec["compile_s"] = self.compile_s
        if i == 0:
            self.first_step_s = rec["wall_s"]
            # into the start-up record, from the stamps this record
            # takes anyway: the step's trace, lowering and executable
            # are jax's own records inside it (``util/tracing.py``)
            from ray_tpu.util import tracing
            tracing.keep("setup/first_step", ts, rec["wall_s"],
                         label=self.label)
        if self._tokens_per_step:
            rec["tokens"] = self._tokens_per_step
            # step 0's wall includes the (jit or AOT) compile — a
            # throughput/MFU derived from it would be garbage, and step
            # 0 is the one record always emitted to Prometheus
            if i > 0:
                rec["tokens_per_sec"] = (self._tokens_per_step
                                         / rec["wall_s"])
                fpt, peak = self.flops_per_token(), self.chip_peak()
                if fpt is not None and peak is not None:
                    rec["mfu"] = flops_mod.mfu(
                        rec["tokens_per_sec"] / self.n_devices(), fpt,
                        peak)
        if loss is not None:
            rec["loss"] = loss
        if moe is not None:
            rec["moe"] = moe
        if i == 0 and self.attn_coverage() is not None:
            rec["attn_coverage"] = self.attn_coverage()
        if i == 0 and self.ce_path() is not None:
            rec["ce_path"] = self.ce_path()   # fixed for the run
        if i == 0 and self.causal_coverage() is not None:
            rec["causal_coverage"] = self.causal_coverage()
        if i == 0 and self.moe_product() is not None:
            rec["moe_product"] = self.moe_product()
            rec["moe_combine"] = self.moe_combine()
        self.records.append(rec)
        if len(self.records) > self._MAX_RECORDS:
            # bounded like the control plane's task-event buffer: a
            # 100k-step run must not grow host memory (or the exported
            # timeline) without limit.  first_step_s/compile_s live as
            # attributes, so trimming the head loses nothing summary()
            # reports.
            del self.records[:len(self.records) - self._MAX_RECORDS]
        self._emit(rec)

    def _dispatch(self, step_fn, args, kwargs, i):
        if not self._aot:
            return step_fn(*args, **kwargs)
        if i == 0:
            # a step that does not compile fails here: no catch, no
            # second attempt through plain jit
            t0 = time.monotonic()
            compiled = step_fn.lower(*args, **kwargs).compile()
            self.compile_s = time.monotonic() - t0
            self.memory = _memory_dict(compiled)
            self._compiled = compiled
            self._signature = _arg_signature((args, kwargs))
        if _arg_signature((args, kwargs)) == self._signature:
            return self._compiled(*args, **kwargs)
        return step_fn(*args, **kwargs)     # other shapes: plain jit

    # ------------------------------------------------------- accounting --

    def _note_tokens(self, args, kwargs):
        if self._tokens_per_step is not None:
            return
        tok = _find_tokens(args, kwargs)
        if tok is not None:
            self._tokens_per_step = int(tok.shape[0]) * int(tok.shape[1])
            self._seq = int(tok.shape[1])
            self._batch = int(tok.shape[0])

    def _maybe_loss(self, out) -> Optional[float]:
        try:
            if (isinstance(out, tuple) and len(out) == 2
                    and isinstance(out[1], dict) and "loss" in out[1]):
                return float(out[1]["loss"])
        except Exception:  # noqa: BLE001 — loss stays optional
            pass
        return None

    def _maybe_moe(self, out) -> Optional[Dict[str, float]]:
        """The expert layers' counts of a step that returns them
        (``metrics["moe_counts"]``: ``parallel/moe.py:MOE_COUNTS`` summed
        over layers, then the rows each held expert took, then the
        windows the combines bring)."""
        if not (isinstance(out, tuple) and len(out) == 2
                and isinstance(out[1], dict) and "moe_counts" in out[1]):
            return None
        import numpy as np

        from ray_tpu.parallel.moe import MOE_COUNTS
        vec = np.asarray(out[1]["moe_counts"])
        named = dict(zip(MOE_COUNTS, (int(v) for v in vec)))
        load = vec[len(MOE_COUNTS):-1]
        moe = {"rows": named["rows"], "held_picks": named["held_picks"],
               "experts_hit": named["experts_hit"],
               "calls": named["calls"], "combine_windows": int(vec[-1])}
        if load.size and load.sum() > 0:
            moe["imbalance"] = float(load.max() / load.mean())
        return moe

    def compiled_step(self):
        """The AOT-compiled executable (``aot=True`` after the first
        wrapped call), or None.  Benchmark loops that must stay free of
        the wrapper's per-step blocking sync call this directly — same
        executable, no recompile, no recording."""
        return self._compiled

    def n_devices(self) -> int:
        return getattr(self.mesh, "size", None) or 1

    def chip_peak(self) -> Optional[float]:
        """bf16 peak of the chip the step runs on; ``None`` on the CPU,
        where MFU is not a quantity (records then carry no ``mfu``)."""
        if self._peak is None:
            import jax
            device = (self.mesh.devices.flat[0] if self.mesh is not None
                      else jax.devices()[0])
            if device.platform == "cpu":
                return None
            self._peak = flops_mod.chip_peak_tflops(device)
        return self._peak

    def ce_path(self) -> Optional[str]:
        """The loss head this step runs — ``flash``, ``xla_saved`` or
        ``xla_chunked`` — as the model's dispatch names it
        (``models.gpt.ce_path``, over the gate
        ``ops.flash_ce.uses_flash_ce``: the recipe's ``ce_chunk``, the
        shapes, the mesh size, the ``ce_mode`` pin).  ``None`` until a
        batch has shown its shape, or for a config with no such recipe;
        constant from then on, so asked once."""
        cfg = self.cfg
        if self._seq is None or not hasattr(cfg, "ce_chunk"):
            return None
        if self._ce_path is None:
            from ray_tpu.models.gpt import ce_path
            self._ce_path = ce_path(
                self._batch * self._seq, cfg.d_model, cfg.vocab_size,
                ce_chunk=cfg.ce_chunk, n_devices=self.n_devices(),
                mode=self.ce_mode)
        return self._ce_path

    def moe_product(self) -> Optional[str]:
        """The form a routed config's differentiated expert layers take
        their grouped products in — ``pallas``
        (``ops/grouped_matmul.py``) or ``ragged_dot`` — as the layer
        decides it from the step's shapes
        (``parallel.moe.product_path``, over the gate
        ``grouped_matmul.uses_kernel``).  ``None`` until a batch has
        shown its shape, and for a config with no such layer."""
        from ray_tpu.parallel.moe import product_path
        shapes = self._moe_shapes()
        return shapes and product_path(*shapes)

    def _moe_shapes(self):
        cfg = self.cfg
        if self._seq is None or not getattr(cfg, "held_experts", None):
            return None
        return (self._batch * self._seq, cfg.moe_top_k,
                len(cfg.held_experts), cfg.n_routed_experts, cfg.d_model,
                cfg.ff_dim)

    def moe_combine(self) -> Optional[str]:
        """The form those layers sum their rows into tokens in:
        ``pallas`` (``grouped_matmul.combine``) with the products'
        kernels, ``xla`` (a gather) with ``ragged_dot`` — a layer's one
        decision (``parallel.moe.combine_path``)."""
        from ray_tpu.parallel.moe import combine_path
        shapes = self._moe_shapes()
        return shapes and combine_path(*shapes)

    def causal_coverage(self) -> Optional[float]:
        """The share of the causal score square the step's attention
        schedule executes, as the attention fn the step was built with
        counts it (``ops.attention.make_flash_attention_fn``: 0.75 where
        whole blocks of 512 are masked at 1024 tokens, 0.5005 needed).
        ``None`` until a batch has shown its shape, and for a step whose
        attention says nothing of the kind (ring, ulysses, the einsum)."""
        count = getattr(self.attn_fn, "causal_coverage", None)
        if (count is None or self._seq is None
                or not hasattr(self.cfg, "n_heads")):
            return None
        if self._coverage is None:
            self._coverage = count(self._seq, self.cfg.n_heads,
                                   self.cfg.head_dim)
        return self._coverage

    def attn_coverage(self) -> Optional[Dict[str, Dict[str, float]]]:
        """For a step built with one attention hook a layer kind
        (``models.gpt.attention_fns``): each kind's share of the score
        square executed and needed, as its hook counts them."""
        fns = self.attn_fn
        if (not isinstance(fns, dict) or self._seq is None
                or not hasattr(self.cfg, "n_heads")):
            return None
        return {kind: fn.coverage(self._seq, self.cfg.n_heads,
                                  self.cfg.head_dim)
                for kind, fn in fns.items() if hasattr(fn, "coverage")}

    def flops_per_token(self, held_picks_per_token: Optional[float] = None
                        ) -> Optional[float]:
        """Analytic train FLOPs a token; a routed config's expert FLOPs
        at ``held_picks_per_token`` where given (the measured count),
        else at the uniform expectation (which is what is cached)."""
        if self.cfg is None or self._seq is None:
            return None
        if held_picks_per_token is not None:
            return flops_mod.gpt_train_flops_per_token(
                self.cfg, self._seq,
                ce_recompute=self.ce_path() != "xla_saved",
                held_picks_per_token=held_picks_per_token)
        if self._fpt is None:     # constant once the batch shape is known
            try:
                self._fpt = flops_mod.gpt_train_flops_per_token(
                    self.cfg, self._seq,
                    # only the saved-logits head runs three
                    # vocabulary matmuls; the other two recompute one
                    ce_recompute=self.ce_path() != "xla_saved")
            except Exception:  # noqa: BLE001 — non-GPT cfg
                self._fpt = -1.0
        return None if self._fpt < 0 else self._fpt

    def collective_bytes(self) -> Optional[Dict[str, Any]]:
        if (self.cfg is None or self.mesh is None
                or self._seq is None):
            return None
        try:
            from ray_tpu.parallel import overlap as ovl
            return ovl.collective_bytes_per_step(
                self.cfg, self.mesh, batch=self._batch, seq=self._seq,
                comm_mode=self.comm_mode or "gspmd",
                quant=self.comm_quant or "none")
        except Exception:  # noqa: BLE001 — non-GPT cfg / odd mesh
            return None

    # ---------------------------------------------------------- summary --

    def summary(self) -> Dict[str, Any]:
        """The aggregate ``telemetry`` block for bench/perf JSON."""
        if not self.enabled:
            return {"enabled": False}
        out: Dict[str, Any] = {"enabled": True, "label": self.label,
                               "steps": self.step_count}
        if not self.records:
            return out
        out["compile_s"] = self.compile_s
        out["first_step_s"] = self.first_step_s
        # a single (compile-inclusive) step has no steady state to
        # report — mislabeling it would be off by orders of magnitude
        steady = [r for r in self.records if r["step"] > 0]
        if steady:
            wall = statistics.median(r["wall_s"] for r in steady)
            out.update({
                "steady_step_s": wall,
                "steady_dispatch_s": statistics.median(
                    r["dispatch_s"] for r in steady),
                "steady_sync_s": statistics.median(
                    r["sync_s"] for r in steady),
            })
            if self._tokens_per_step:
                tok_s = self._tokens_per_step / wall
                out["tokens_per_step"] = self._tokens_per_step
                out["tokens_per_sec"] = tok_s
                out["tokens_per_sec_per_device"] = \
                    tok_s / self.n_devices()
                fpt, peak = self.flops_per_token(), self.chip_peak()
                routed = [r["moe"] for r in steady if "moe" in r]
                if routed:
                    # mean over the steady steps; the layers' calls are
                    # summed, so per layer where a layer is meant
                    n = len(routed)
                    layers = max(1, routed[0]["calls"])
                    moe = {key: sum(m[key] for m in routed) / n
                           for key in ("rows", "held_picks", "experts_hit",
                                       "combine_windows")}
                    moe["held_picks_per_token"] = (
                        moe["held_picks"] / max(1.0, moe["rows"]))
                    moe["experts_hit_per_layer"] = (
                        moe["experts_hit"] / layers)
                    spread = [m["imbalance"] for m in routed
                              if "imbalance" in m]
                    if spread:
                        moe["imbalance"] = sum(spread) / len(spread)
                    out["moe"] = moe
                    # the model's FLOPs at the picks that were computed
                    fpt = self.flops_per_token(moe["held_picks_per_token"])
                if fpt is not None:
                    out["flops_per_token"] = fpt
                cover = self.attn_coverage()
                if cover is not None:
                    out["attn_coverage"] = cover
                path = self.ce_path()
                if path is not None:
                    out["ce_path"] = path
                coverage = self.causal_coverage()
                if coverage is not None:
                    out["causal_coverage"] = coverage
                product = self.moe_product()
                if product is not None:
                    out["moe_product"] = product
                    out["moe_combine"] = self.moe_combine()
                if fpt is not None and peak is not None:
                    out["chip_peak_tflops"] = peak
                    out["mfu"] = flops_mod.mfu(
                        tok_s / self.n_devices(), fpt, peak)
        out["hbm"] = self.memory
        cb = self.collective_bytes()
        out["collective_bytes_per_step"] = cb
        if cb is not None:
            # flattened per-tier rows so perf JSON / dashboards can
            # plot the tier split without digging into the nested dict
            for tier in ("ici", "dcn"):
                t = cb.get(tier) or {}
                out[f"collective_bytes_{tier}"] = t.get("total", 0)
                out[f"collective_seconds_{tier}"] = t.get("seconds",
                                                          0.0)
            red = (cb.get("dcn") or {}).get("reduction_vs_flat")
            if red is not None:
                out["dcn_reduction_vs_flat"] = red
        if self.comm_mode is not None:
            out["comm_mode"] = self.comm_mode
        if self.comm_quant is not None:
            out["comm_quant"] = self.comm_quant
        return out

    # ------------------------------------------------------ chrome trace --

    def chrome_events(self) -> List[Dict[str, Any]]:
        """This recorder's steps as Chrome-trace complete events."""
        evs: List[Dict[str, Any]] = []
        pid, tid = "train", self.label
        for r in self.records:
            args = {k: r[k] for k in ("loss", "tokens_per_sec", "mfu")
                    if k in r}
            args["sync_ms"] = r["sync_s"] * 1e3
            evs.append({"name": f"{self.label}/step {r['step']}",
                        "cat": "train_step", "ph": "X",
                        "ts": r["ts"] * 1e6, "dur": r["wall_s"] * 1e6,
                        "pid": pid, "tid": tid, "args": args})
            evs.append({"name": f"{self.label}/dispatch", "cat": "train",
                        "ph": "X", "ts": r["ts"] * 1e6,
                        "dur": r["dispatch_s"] * 1e6,
                        "pid": pid, "tid": f"{tid}/phases", "args": {}})
            evs.append({"name": f"{self.label}/sync", "cat": "train",
                        "ph": "X",
                        "ts": (r["ts"] + r["dispatch_s"]) * 1e6,
                        "dur": r["sync_s"] * 1e6,
                        "pid": pid, "tid": f"{tid}/phases", "args": {}})
        return evs

    # --------------------------------------------------------- profiler --

    def _profile(self, i: int, *, before: bool):
        pdir = self._cfgobj.profile_dir
        if not pdir:
            return
        first = self._cfgobj.profile_first
        last = first + self._cfgobj.profile_steps - 1
        try:
            import jax
            if (before and not self._profile_started and i >= first):
                jax.profiler.start_trace(pdir)
                self._profile_started = True
            elif (not before and self._profile_started
                    and not self._profile_stopped and i >= last):
                jax.profiler.stop_trace()
                self._profile_stopped = True
        except Exception as e:  # noqa: BLE001 — profiling is best-effort
            print(f"telemetry: xplane capture failed ({e!r})",
                  file=sys.stderr)
            self._profile_stopped = True
            self._profile_started = True

    def stop(self):
        """Finalize: stop a still-running xplane capture."""
        if self._profile_started and not self._profile_stopped:
            try:
                import jax
                jax.profiler.stop_trace()
            except Exception:  # noqa: BLE001
                pass
            self._profile_stopped = True

    # ------------------------------------------------------- prometheus --

    _STEP_BOUNDARIES = [0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
                        0.25, 0.5, 1.0, 2.5, 5.0, 10.0]

    _EMIT_INTERVAL_S = 0.5

    def _emit(self, rec):
        """Per-step Prometheus gauges/histograms (control-plane metrics).

        Only when a ray_tpu session is up; the first failure disables
        emission for the rest of the run so a dead control plane cannot
        tax the step loop.  Emission is throttled to one batch per
        ``_EMIT_INTERVAL_S`` (step 0 always emits): the control plane is
        an RPC away, and a per-step RPC burst would tax fast steps for a
        scrape Prometheus only reads every few seconds anyway."""
        if self._metrics_dead:
            return
        now = time.monotonic()
        # steps 0 (compile + collective bytes) and 1 (first real
        # throughput/MFU) always emit; after that, the interval gates
        if rec["step"] > 1 and now - self._metrics_last \
                < self._EMIT_INTERVAL_S:
            return
        self._metrics_last = now
        try:
            from ray_tpu._private.worker import is_initialized
            if not is_initialized():
                return            # cluster may start later; retry then
            if self._metrics is None:
                from ray_tpu.util.metrics import Gauge, Histogram
                tags = ("label",)
                self._metrics = {
                    "step_s": Histogram(
                        "train_step_seconds",
                        "train step wall seconds (blocking sync)",
                        boundaries=self._STEP_BOUNDARIES,
                        tag_keys=tags),
                    "mfu": Gauge("train_mfu",
                                 "analytic-FLOPs model FLOPs utilization",
                                 tag_keys=tags),
                    "tok": Gauge("train_tokens_per_sec",
                                 "training throughput", tag_keys=tags),
                    "bytes": Gauge(
                        "train_collective_bytes",
                        "logical collective bytes/device/step",
                        tag_keys=tags),
                }
            tags = {"label": self.label}
            # step 0's wall includes the compile — keep the 30s-vs-50ms
            # outlier out of the step-seconds distribution, same policy
            # as the skipped step-0 throughput/MFU above
            if rec["step"] > 0:
                self._metrics["step_s"].observe(rec["wall_s"],
                                                tags=tags)
            if "mfu" in rec:
                self._metrics["mfu"].set(rec["mfu"], tags=tags)
            if "tokens_per_sec" in rec:
                self._metrics["tok"].set(rec["tokens_per_sec"],
                                         tags=tags)
            if not self._bytes_emitted:
                # once per run, on the first emission that actually
                # reaches the control plane (the cluster may have come
                # up after step 0)
                cb = self.collective_bytes()
                if cb is not None:
                    self._metrics["bytes"].set(cb["total"], tags=tags)
                self._bytes_emitted = True
        except Exception:  # noqa: BLE001 — never tax the step loop
            self._metrics_dead = True


def instrument(fns: Dict[str, Any], cfg=None, mesh=None, *,
               comm_mode: Optional[str] = None,
               comm_quant: Optional[str] = None,
               ce_mode: Optional[str] = None, label: str = "train",
               aot: bool = False,
               config=None) -> Dict[str, Any]:
    """Wrap the ``step_fn`` of a train-fns dict with a fresh recorder.

    Returns the same dict with ``step_fn`` wrapped and two extra keys:
    ``telemetry`` (the :class:`StepTelemetry`) and ``raw_step_fn`` (the
    unwrapped jitted step).  No-op (no extra keys) when telemetry is
    disabled."""
    rec = StepTelemetry(cfg, mesh, comm_mode=comm_mode,
                        comm_quant=comm_quant, ce_mode=ce_mode,
                        attn_fn=fns.get("attn_fn"),
                        label=label, aot=aot, config=config)
    if not rec.enabled:
        return fns
    fns["raw_step_fn"] = fns["step_fn"]
    fns["step_fn"] = rec.wrap(fns["step_fn"])
    fns["telemetry"] = rec
    return fns
