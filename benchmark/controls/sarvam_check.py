"""What the ``sarvam`` family's check has to see, read through the
harness's own comparison.

    python3 benchmark/controls/sarvam_check.py --seed <n> [--seed <m> ...]

For each seed it builds the replica of ``serve-sarvam-105b-ep4-docs`` in
this process (``BenchReplica``: the cell's configuration, sizing and
weights from the seed, as ``serve_cell._deploy`` hands them over), makes
the cell's own check samples (``serve_cell._check``: fresh tokens from
the seed, no shared prefix) and calls ``bench_check`` once as the cell
does and once per control.  A control hands the *reference* a fault, so
the engine is compared with a model that differs from it by exactly
that: a fault of the same size in the engine reads the same error.

- ``reference/sarvam.py:FAULTS``: the shared expert left out
  (``no_shared``), softmax for sigmoid (``softmax``), the
  renormalisation left out (``no_renorm``), the yarn factor on the
  softmax's scale left out (``no_yarn_scale``), the query heads' norm
  left out (``no_q_norm``), a held pick sent to the next held expert
  (``wrong_held``), the router's logits in bfloat16 (``router_bf16``).
- ``float8_e4m3fn``: the next precision down, every matrix and every
  layer's input of the reference rounded to it (``config["_round"]``).

The clean check has to hold and every judged control has to come out
not correct: exit 0 only then, 1 otherwise.  One planted fault is read
and printed and is no part of the verdict (``NOT_SEEN``): the router's
logits in bfloat16 move a logit by ~2^-9 of itself, as the bfloat16
activations the engine's float32 router is handed already do, so it
flips picks at the top-k's edge alone, which is what ``CHOICE_MARGIN``
leaves out of the judged rows.  One JSON line a check: the sample's
``rel_err`` over its decided rows, what it decided, and every row's
error beside its margin (``rows``), which is what ``LOGITS_TOL`` and
``CHOICE_MARGIN`` were set from.  ``--only <control>`` (repeatable)
runs those controls alone beside the clean check.
``--rehearse-on-cpu`` walks the same code at the family's toy shapes
(no reading means anything there; exit 3).
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

CELL = "serve-sarvam-105b-ep4-docs"
CONTROLS = [(name, {"_fault": name}) for name in (
    "no_shared", "softmax", "no_renorm", "no_yarn_scale", "no_q_norm",
    "wrong_held", "router_bf16")] + [
        ("float8_e4m3fn", {"_round": "float8_e4m3fn"})]
# read, printed, and no part of the verdict (the module's docstring)
NOT_SEEN = ("router_bf16",)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, action="append", required=True)
    ap.add_argument("--rehearse-on-cpu", action="store_true")
    ap.add_argument("--only", action="append",
                    help="run only these controls (clean always runs)")
    args = ap.parse_args()
    if args.rehearse_on_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"

    import jax.numpy as jnp

    from benchmark.harness import (check as check_mod, common, family,
                                   serve_cell)
    from benchmark.controls.longcat_check import _Here, _per_row
    from benchmark.harness.replica import BenchReplica
    if not args.rehearse_on_cpu:
        common.use_compile_cache()
    files = common.cell_files(CELL, args.rehearse_on_cpu)
    config, mix = files["config"], files["traffic"]
    reference = family.reference(family.family_of(config))
    sink = {"errs": [], "margins": []}
    _per_row(check_mod, reference, sink)
    kwargs = dict(config["model"]["kwargs"])
    kwargs["dtype"] = getattr(jnp, kwargs["dtype"])
    good = True
    for seed in args.seed:
        replica = BenchReplica(model=config["model"]["preset"],
                               model_config=kwargs,
                               engine_config=dict(files["sizing"]["engine"]),
                               seed=seed & 0x7FFFFFFF)
        info = replica.bench_info()
        for name, fault in [("clean", {})] + [
                c for c in CONTROLS
                if not args.only or c[0] in args.only]:
            sink["errs"].clear(), sink["margins"].clear()
            check = serve_cell._check(
                _Here(replica), info, mix,
                {"family": family.family_of(config),
                 "config": dict(config, **fault)}, seed, [])
            held = check["ok"] if name == "clean" else not check["ok"]
            good = good and (held or name in NOT_SEEN)
            print(json.dumps({
                "seed": seed, "check": name, "correct": check["ok"],
                "as_it_has_to_be": held, "judged": name not in NOT_SEEN,
                "tolerance": check["tolerance"],
                "decided_share": check["decided_share"],
                "samples": [{k: r[k] for k in (
                    "rel_err", "rel_err_all", "decided", "rows",
                    "hit_pages", "argmax_agree")} for r in check["rows"]],
                "rows": [[[round(e, 5), round(m, 5)]
                          for e, m in zip(errs, margins)]
                         for errs, margins in zip(sink["errs"],
                                                  sink["margins"])],
            }), flush=True)
        del replica         # the next seed's weights need its room
        gc.collect()
    print(json.dumps({"ok": good, "device": info["device"]}), flush=True)
    if args.rehearse_on_cpu:
        return 3
    return 0 if good else 1


if __name__ == "__main__":
    sys.exit(main())
