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
runtime starts.  Without a TPU it exits non-zero and prints no result;
``--rehearse-on-cpu`` walks the same code at toy shapes on the CPU,
prints counts and no rates, and exits 3.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

T_START = time.time()
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))


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

    from benchmark.harness import common, metrics
    files = common.cell_files(args.workload, args.rehearse_on_cpu)
    cell = files["cell"]
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

    import ray_tpu
    try:
        out = runner.run(files, args, T_START)
    finally:
        if ray_tpu.is_initialized():
            import ray_tpu.serve as serve
            serve.shutdown()
            ray_tpu.shutdown()

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
            reduced = trace_mod.reduce_trace(find_xplane(out["trace_dir"]))
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

    detail = dict(out.get("detail", {}), workload=args.workload,
                  seed=args.seed, seconds=args.seconds, trace=args.trace,
                  facts=facts)
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
    return 0


if __name__ == "__main__":
    sys.exit(main())
