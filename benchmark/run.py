"""Run one cell of the benchmark once.

    python3 benchmark/run.py --workload <name> --seed <n> \\
        --seconds <run_seconds> --trace <0|1>

A new process per run.  It brings the runtime up (``ray_tpu.init``),
lets the cell's runner build weights on the device from the seed, warm
the cell's own shapes and measure for ``--seconds``, prints what it
likes on earlier lines and, last, one JSON object with ``correct``,
``attempted``, ``failed``, ``metrics`` and ``device`` (and ``breakdown``
in a traced run).  With ``--trace 0`` the metrics are the cell's
end-to-end metrics, with ``--trace 1`` its per-layer metrics.

This process never touches jax: the chip belongs to the worker the
runtime starts.  Before the runner is called it waits, outside the
clock, until no ``/dev/vfio/<n>`` is still held by whoever ran before,
and after its shutdown until its own are let go of
(``harness/chips.py``).  Without a TPU it exits non-zero and prints no
result;
``--rehearse-on-cpu`` walks the same code at toy shapes on the CPU,
prints counts and no rates, and exits 3.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time
import traceback

T_START = time.time()
SHUTDOWN_LIMIT_S = 50.0
# the most a run waits for the chips before its clock starts, and holds
# its exit for them after its shutdown: a four-chip holder's groups come
# free 13-20 s after it was reaped (builder, PR 42)
CHIPS_TAKE_LIMIT_S = 90.0
CHIPS_RELEASE_LIMIT_S = 60.0
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))


def _say_why(e: BaseException) -> None:
    print("benchmark: failed: %s: %s" % (
        type(e).__name__, " ".join(str(e).split())[:2000]),
        file=sys.stderr, flush=True)


def _wait_for_chips(limit_s: float, freed: str, still_held: str) -> float:
    """Wait, outside the clock, until no ``/dev/vfio/<n>`` is held by a
    process that has exited (``harness/chips.py``); the seconds waited,
    0.0 and no word where the chips were free.  One line on standard
    error otherwise: ``freed`` over the nodes that were seen busy, or
    ``still_held`` over those busy at the limit, after which the run goes
    on and libtpu says what it says."""
    from benchmark.harness import chips
    waited, seen, still = chips.wait_free(limit_s)
    if still or waited > 0:
        print("benchmark: " + (still_held if still else freed).format(
            s=waited, nodes=", ".join(still or seen) or "the chips"),
            file=sys.stderr, flush=True)
    return waited


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--rehearse-on-cpu", action="store_true")
    ap.add_argument("--rate-rps", default=None,
                    help="open-loop cells: offer this rate instead of the "
                         "traffic file's; several, comma-separated, sweep "
                         "them in one deployment and print no result")
    args = ap.parse_args()

    from benchmark.harness import common, family, metrics
    files = common.cell_files(args.workload, args.rehearse_on_cpu)
    cell = files["cell"]
    try:
        # before the runtime is up or a chip is taken: a family whose
        # reference file is not there
        family.require(files["config"])
    except FileNotFoundError as e:
        _say_why(e)
        return 1
    if args.seconds is None:
        args.seconds = float(common.manifest()["run_seconds"])
    if args.rehearse_on_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            "--xla_force_host_platform_device_count=%d" % cell["chips"])
        args.seconds = min(args.seconds, 4.0)
    else:
        common.use_compile_cache()

    from ray_tpu.accelerators.tpu import detect_num_tpus
    chips = cell["chips"] if args.rehearse_on_cpu else detect_num_tpus()
    if chips < cell["chips"]:
        print(f"benchmark: {args.workload} needs {cell['chips']} TPU "
              f"chip(s), this host has {chips}", file=sys.stderr)
        return 2

    kind = files["traffic"]["kind"]
    if kind == "train_steps":
        from benchmark.harness import train_cell as runner
    elif kind in ("open_loop", "closed_loop"):
        from benchmark.harness import serve_cell as runner
    else:
        raise SystemExit(f"traffic kind {kind!r} has no runner")

    # the previous holder's release is no part of this system's set-up:
    # ``setup_s`` is counted from ``T_START`` plus this wait
    chip_wait_s = 0.0 if args.rehearse_on_cpu else _wait_for_chips(
        CHIPS_TAKE_LIMIT_S, "waited {s:.2f} s for {nodes} to be free",
        "{nodes} still busy after {s:.2f} s; going on")
    import ray_tpu
    try:
        out = runner.run(files, args, T_START + chip_wait_s)
    except Exception as e:      # noqa: BLE001 — the boundary that reports
        # the worker's traceback travels in the exception re-raised from
        # ``result.error`` (or chained to it); then the line that says
        # why, and the exit code 1 the driver reports
        traceback.print_exc()
        _say_why(e)
        # a shutdown that hangs on what the failure left behind may not
        # hold the exit for more than a minute
        watchdog = threading.Timer(SHUTDOWN_LIMIT_S, os._exit, (1,))
        watchdog.daemon = True
        watchdog.start()
        return 1
    finally:
        if ray_tpu.is_initialized():
            import ray_tpu.serve as serve
            serve.shutdown()
            ray_tpu.shutdown()
        if not args.rehearse_on_cpu:
            # the next run, of whatever checkout, finds the chips free
            _wait_for_chips(
                CHIPS_RELEASE_LIMIT_S,
                "held the exit {s:.2f} s until the chips were free",
                "{nodes} still busy {s:.2f} s after the shutdown")

    device = out["device"]
    if device["platform"] != "tpu" and not args.rehearse_on_cpu:
        print(f"benchmark: the cell ran on {device}, not a TPU",
              file=sys.stderr)
        return 2
    if device["count"] != cell["chips"]:
        print(f"benchmark: the cell ran on {device['count']} devices, "
              f"not {cell['chips']}", file=sys.stderr)
        return 2

    facts = out["facts"]
    line = {"correct": bool(out["correct"]), "attempted": out["attempted"],
            "failed": out["failed"], "metrics": {}, "device": device}
    if args.trace:
        reduced = None
        if out.get("trace_dir"):
            from benchmark.reduce import trace as trace_mod
            from benchmark.reduce.xplane import find_xplane
            # an open-loop run has reduced its trace already, to see
            # that every reader finds what it reads
            reduced = out.get("reduced") or trace_mod.reduce_trace(
                find_xplane(out["trace_dir"]))
            if reduced:
                device["busy_s"] = reduced["busy_s"]
                device["window_s"] = reduced["window_s"]
                line["breakdown"] = trace_mod.breakdown(reduced)
            elif not args.rehearse_on_cpu:
                print("benchmark: the trace holds no device operation",
                      file=sys.stderr)
                return 2
        ctx = {"facts": facts, "trace": reduced,
               "config": out["model_config"], "traffic": files["traffic"],
               "device_kind": device["kind"]}
        for m in files["per_layer"]:
            try:
                value = metrics.read_layer_metric(m["name"], ctx)
            except ValueError:
                # the CPU has no peaks on record, and never will
                if not args.rehearse_on_cpu:
                    raise
                value = None
            if value is not None:
                line["metrics"][m["name"]] = {"value": value,
                                              "unit": m["unit"]}
    else:
        for m in files["end_to_end"]:
            line["metrics"][m["name"]] = {"value": float(facts[m["name"]]),
                                          "unit": m["unit"]}

    # what decided ``correct``, each number beside its limit: the result
    # line's last key, and the run's last lines on standard error
    line["compared"] = {name: {"value": value, "limit": limit}
                        for name, value, limit in out["compared"]}
    detail = dict(out.get("detail", {}), workload=args.workload,
                  seed=args.seed, seconds=args.seconds, trace=args.trace,
                  chip_wait_s=chip_wait_s, facts=facts)
    os.makedirs(common.OUT_DIR, exist_ok=True)
    with open(os.path.join(
            common.OUT_DIR, f"{args.workload}.seed{args.seed}."
            f"trace{args.trace}.json"), "w") as f:
        json.dump(dict(detail, result=line), f)
    if args.rehearse_on_cpu:
        # counts only: a number from a CPU run is never a rate or a time
        print(json.dumps({
            "rehearsal": True, "correct": line["correct"],
            "attempted": line["attempted"], "failed": line["failed"],
            "metric_names": sorted(line["metrics"]),
            "checks": detail.get("checks") or detail.get("check"),
            "counts": {k: v for k, v in facts.items()
                       if isinstance(v, int)}}), flush=True)
        print("benchmark: rehearsal on the CPU complete: not a chip run, "
              "no result", file=sys.stderr)
        return 3
    detail.pop("step_walls_ms", None)
    print(json.dumps({"detail": detail}), flush=True)
    print(json.dumps(line), flush=True)
    for name, got in line["compared"].items():
        print(f"benchmark: compared {name}: {got['value']} "
              f"(limit {got['limit']})", file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
