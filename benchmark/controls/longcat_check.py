"""What the ``longcat`` family's check has to see, read through the
harness's own comparison.

    python3 benchmark/controls/longcat_check.py --seed <n> [--seed <m> ...]

For each seed it builds the replica of ``serve-longcat-flash-omni-agent``
in this process (``BenchReplica``: the cell's configuration, sizing and
weights from the seed, as ``serve_cell._deploy`` hands them over), makes
the cell's own check samples (``serve_cell._check``: fresh tokens behind
the seed's system prompt) and calls ``bench_check`` once as the cell
does and once per control.  A control hands the *reference* a fault, so
the engine is compared with a model that differs from it by exactly
that: a fault of the same size in the engine reads the same error.

- ``no_held``, ``wrong_held``, ``no_identity``
  (``reference/longcat.py:FAULTS``): the held experts' part left out, a
  held pick sent to the next held expert, the identity experts' part
  left out.
- ``float8_e4m3fn``: the next precision down, every matrix and every
  block's input of the reference rounded to it (``config["_round"]``).

The clean check has to hold and every control has to come out not
correct: exit 0 only then, 1 otherwise.  One JSON line a check: the
sample's ``rel_err`` over its decided rows, what it decided, and every
row's error beside its margin (``rows``), which is what ``LOGITS_TOL``
and ``CHOICE_MARGIN`` were set from.  ``--rehearse-on-cpu`` walks the
same code at the family's toy shapes (no reading means anything there;
exit 3).
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

CELL = "serve-longcat-flash-omni-agent"
CONTROLS = [("no_held", {"_fault": "no_held"}),
            ("wrong_held", {"_fault": "wrong_held"}),
            ("no_identity", {"_fault": "no_identity"}),
            ("float8_e4m3fn", {"_round": "float8_e4m3fn"})]


class _Here:
    """The replica in this process behind the two calls of a serve
    handle that ``serve_cell._check`` makes."""

    def __init__(self, replica):
        self._replica = replica

    def __getattr__(self, method):
        bound = getattr(self._replica, method)

        class _Call:
            @staticmethod
            def remote(*args):
                out = bound(*args)
                return type("_Done", (), {
                    "result": staticmethod(lambda timeout_s=None: out)})
        return _Call


def _per_row(check_mod, reference, sink):
    """Keep every row's error and margin of each ``compare``: the
    harness reports a sample's largest."""
    import numpy as np
    compare, decided_rows = check_mod.compare, reference.decided_rows

    def keeping(got, want, decided, tol):
        diff = np.abs(np.asarray(got) - np.asarray(want)).max(-1)
        sink["errs"].append((diff / np.abs(want).max()).tolist())
        return compare(got, want, decided, tol)

    def margins(params, tokens, last, config):
        margin = reference._last_rows(params, tokens, last, config)[1][0]
        sink["margins"].append(np.asarray(margin).tolist())
        return decided_rows(params, tokens, last, config)

    check_mod.compare, reference.decided_rows = keeping, margins


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, action="append", required=True)
    ap.add_argument("--rehearse-on-cpu", action="store_true")
    args = ap.parse_args()
    if args.rehearse_on_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"

    import jax.numpy as jnp

    from benchmark.harness import (check as check_mod, common, family,
                                   serve_cell, traffic as traffic_mod)
    from benchmark.harness.replica import BenchReplica
    if not args.rehearse_on_cpu:
        common.use_compile_cache()
    files = common.cell_files(CELL, args.rehearse_on_cpu)
    config, mix = files["config"], files["traffic"]
    if args.rehearse_on_cpu:
        mix = dict(mix, system_prompt_tokens=128)
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
        system = traffic_mod.open_loop_requests(
            mix, seed, 1.0, info["vocab_size"])["system_prompt"]
        for name, fault in [("clean", {})] + CONTROLS:
            sink["errs"].clear(), sink["margins"].clear()
            check = serve_cell._check(
                _Here(replica), info, mix,
                {"family": family.family_of(config),
                 "config": dict(config, **fault)}, seed, system)
            held = check["ok"] if name == "clean" else not check["ok"]
            good = good and held
            print(json.dumps({
                "seed": seed, "check": name, "correct": check["ok"],
                "as_it_has_to_be": held, "tolerance": check["tolerance"],
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
