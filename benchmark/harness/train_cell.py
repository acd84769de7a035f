"""A train cell: ``traffic.kind == "train_steps"``.

The parent (``run.py``) never touches jax.  It starts the runtime and
hands :func:`train_loop` to ``JaxTrainer``; the loop runs in the one
worker that owns the cell's chips, through ``make_mesh`` and
``build_gpt_train`` as a user's loop would, and reports one summary."""

from __future__ import annotations

import os
import time
from typing import Any, Dict

from benchmark.harness import common

# Step 0 against the float32 reference on the same weights and batch.
# The program computes in bfloat16 with float32 statistics and float32
# loss accumulation: on the chip the mean NLL over 24,576 tokens agreed
# to 1.3e-6 relative (rounding errors average out) and the gradient
# norm, which the step returns in bfloat16 (2^-8 = 4e-3), to 2.7e-3 and
# 4.5e-3 (my chip runs, PR 23).  A wrong mask, a missing term or fp8
# arithmetic moves either by far more than the tolerances below.
LOSS_RTOL = 1e-3
GRAD_NORM_RTOL = 2e-2


def train_loop(config: Dict[str, Any]) -> None:
    import jax
    import numpy as np

    from benchmark.harness import traffic as traffic_mod
    from benchmark.reduce.trace import TICK
    from benchmark.reference import gpt as reference
    from ray_tpu import train
    from ray_tpu._private.compile_cache import (compile_stats,
                                                enable_compile_cache)
    from ray_tpu.models import training
    from ray_tpu.models.gpt import GPTConfig
    from ray_tpu.parallel.mesh import make_mesh
    import jax.numpy as jnp

    enable_compile_cache()
    devices = jax.devices()
    ready_epoch = time.time()
    rehearsal = config["rehearsal"]
    if devices[0].platform != "tpu" and not rehearsal:
        raise RuntimeError(f"worker is on {devices[0].platform}, not a TPU")
    if len(devices) != config["chips"]:
        raise RuntimeError(f"worker sees {len(devices)} devices, the cell "
                           f"asks for {config['chips']}")
    mix, model = config["traffic"], config["model"]
    kwargs = dict(model["kwargs"], **config["train_kwargs"])
    kwargs["dtype"] = getattr(jnp, kwargs["dtype"])
    cfg = (GPTConfig(**kwargs) if model["preset"] is None
           else getattr(GPTConfig, model["preset"])(**kwargs))
    mesh = make_mesh(dp=-1)
    fns = training.build_gpt_train(cfg, mesh)
    seed = int(config["seed"])
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                             seed >> 31)
    state = fns["init_fn"](key)               # one jitted call, on device
    batches = traffic_mod.train_batches(mix, seed, len(devices),
                                        cfg.vocab_size)
    tel = fns["telemetry"]
    seq = int(mix["seq"])
    tokens_per_step = batches[0]["tokens"].size

    # the reference's loss and gradient norm for step 0, before the step
    # donates the state: each chip takes its share of the global batch
    # with its own copy of the (replicated) weights, plain single-device
    # calls side by side, and the sums meet on the first chip
    share = batches[0]["tokens"].shape[0] // len(devices)
    parts = []
    for d, dev in enumerate(devices):
        weights = jax.tree.map(
            lambda a: next(s.data for s in a.addressable_shards
                           if s.device == dev), state.params)
        rows = slice(d * share, (d + 1) * share)
        parts.append(reference.loss_and_grad_sums(
            weights, jax.device_put(batches[0]["tokens"][rows], dev),
            jax.device_put(batches[0]["targets"][rows], dev),
            chunk=config["reference_chunk_per_chip"]))
    parts = jax.device_put(parts, devices[0])
    nll, count, grads = jax.tree.map(lambda *xs: sum(xs), *parts)
    ref_loss = float(nll / count)
    ref_gnorm = float(jnp.sqrt(sum(jnp.sum(jnp.square(g / count))
                                   for g in jax.tree.leaves(grads))))
    del parts, grads, weights

    losses = []
    step_index = 0

    def one_step():
        nonlocal state, step_index
        with jax.profiler.TraceAnnotation(TICK):
            batch = jax.device_put(batches[step_index % len(batches)],
                                   fns["batch_sharding"])
            state, metrics = fns["step_fn"](state, batch)
            losses.append(float(metrics["loss"]))
        step_index += 1
        return metrics

    m0 = one_step()
    got_loss, got_gnorm = losses[0], float(m0["grad_norm"])
    for _ in range(int(mix["warmup_steps"]) - 1):
        one_step()

    compiles_before = compile_stats()
    n_before = step_index
    window_epoch = time.time()
    t0 = time.monotonic()
    while True:
        one_step()
        t1 = time.monotonic()
        if t1 - t0 >= config["seconds"]:
            break
    steps = step_index - n_before
    compiles_after = compile_stats()
    walls = [r["wall_s"] for r in tel.records[-steps:]]
    window_losses = losses[-steps:]

    trace_dir = None
    if config["trace"]:
        trace_dir = config["trace_dir"]
        jax.profiler.start_trace(trace_dir)
        for _ in range(int(mix["traced_steps"])):
            one_step()
        jax.profiler.stop_trace()

    k = max(1, min(10, steps // 4))
    head = sum(window_losses[:k]) / k
    tail = sum(window_losses[-k:]) / k
    checks = {
        "loss_vs_reference": abs(got_loss - ref_loss) <= LOSS_RTOL * abs(ref_loss),
        "grad_norm_vs_reference": abs(got_gnorm - ref_gnorm)
        <= GRAD_NORM_RTOL * abs(ref_gnorm),
        "loss_finite": bool(np.all(np.isfinite(losses))),
        "loss_falling": tail < head,
    }
    train.report({"summary": {
        "device": common.device_block(devices),
        "ready_epoch": ready_epoch,
        "window_epoch": window_epoch,
        "window_s": t1 - t0,
        "steps": steps,
        "tokens_per_step": tokens_per_step,
        "seq": seq,
        "step_walls_s": walls,
        "compiles_in_window": compiles_after["compiles"]
        - compiles_before["compiles"],
        "compile_stats": compiles_after,
        "reference": {"loss": ref_loss, "grad_norm": ref_gnorm,
                      "got_loss": got_loss, "got_grad_norm": got_gnorm,
                      "loss_rtol": LOSS_RTOL,
                      "grad_norm_rtol": GRAD_NORM_RTOL},
        "loss_window": [head, tail],
        "checks": checks,
        "trace_dir": trace_dir,
    }})


def run(files: Dict[str, Any], args, t_start: float) -> Dict[str, Any]:
    """Parent side: start the trainer, wait, turn the worker's summary
    into the facts the metrics read."""
    import shutil
    import tempfile

    import ray_tpu
    from ray_tpu.train import RunConfig, ScalingConfig
    from ray_tpu.train.jax import JaxTrainer

    cell, config, mix = files["cell"], files["config"], files["traffic"]
    chips = cell["chips"]
    rehearsal = args.rehearse_on_cpu
    if rehearsal:
        mix = dict(mix, batch_per_chip=2, seq=256, warmup_steps=2,
                   traced_steps=2, distinct_batches=4)
    trace_dir = os.path.join(common.OUT_DIR, "trace", cell["name"])
    shutil.rmtree(trace_dir, ignore_errors=True)
    init_epoch = time.time()
    ray_tpu.init(num_tpus=chips if rehearsal else None)
    storage = tempfile.mkdtemp(prefix="bench_train_")
    try:
        result = JaxTrainer(
            train_loop,
            train_loop_config={
                "traffic": mix, "model": config["model"],
                "train_kwargs": files["sizing"]["train"]["kwargs"],
                "seed": args.seed, "seconds": args.seconds,
                "trace": bool(args.trace), "trace_dir": trace_dir,
                "chips": chips, "rehearsal": rehearsal,
                "reference_chunk_per_chip": 2},
            scaling_config=ScalingConfig(
                num_workers=1, use_tpu=True,
                resources_per_worker={"TPU": chips}),
            run_config=RunConfig(name="bench_" + cell["name"],
                                 storage_path=storage)).fit()
    finally:
        shutil.rmtree(storage, ignore_errors=True)
    if result.error is not None:
        raise result.error
    s = result.metrics["summary"]
    walls = s["step_walls_s"]
    tok_s_chip = s["steps"] * s["tokens_per_step"] / s["window_s"] / chips
    facts = {
        "train_tok_s_chip": tok_s_chip,
        "setup_s": s["window_epoch"] - t_start,
        "worker_ready_s": s["ready_epoch"] - init_epoch,
        "train_step_ms": 1e3 * common.median(walls),
        "compiles_in_window": s["compiles_in_window"],
        "steps": s["steps"],
        "seq": s["seq"],
        "batch_per_chip": s["tokens_per_step"] // s["seq"] // chips,
    }
    detail = {k: s[k] for k in ("reference", "loss_window", "checks",
                                "compile_stats", "window_s", "steps")}
    detail["step_ms"] = {
        "min": 1e3 * min(walls), "p50": 1e3 * common.median(walls),
        "p95": 1e3 * common.percentile(walls, 95), "max": 1e3 * max(walls),
        "over_1.05x_median": sum(w > 1.05 * common.median(walls)
                                 for w in walls)}
    detail["step_walls_ms"] = [round(1e3 * w, 3) for w in walls]
    return {"facts": facts, "device": s["device"],
            "correct": all(s["checks"].values()),
            "attempted": s["steps"], "failed": 0,
            "trace_dir": s["trace_dir"], "detail": detail,
            "model_config": config}
