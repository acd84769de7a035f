"""Counts ``benchmark/tests/test_mellum_family.py`` in tier-1, which
collects ``tests/`` alone, so that weakening the benchmark's own checks
costs passes (ROADMAP D13)."""

from benchmark.harness import common
from benchmark.tests import test_mellum_family as theirs
from benchmark.tests.test_mellum_family import *  # noqa: F401,F403


def test_cell_finds_its_files_and_its_rehearsal(monkeypatch):  # noqa: F811
    """The benchmark's own test, every assertion of it and unedited, on
    the manifest as far as its cell.  It asserts that its cell is the
    manifest's last, which held until a later cell came behind it (new
    entries go to the end of their lists, and the file is a `benchmark`
    PR's to edit), and it lists the metrics that read in its cell alone,
    which held until PR 62 put ``moe_gather_ms_per_step`` behind them
    (PR 63 the ``setup_*`` metrics of every cell behind that, and PR 64
    ``attn_glue_ms_per_step``, the three train cells', last):
    the per-layer metrics are cut behind the last it knew, after an
    assertion of what stands behind; everything else it reads is the
    manifest as it is."""
    man = common.manifest()
    names = [w["name"] for w in man["workloads"]]
    at = names.index(theirs.CELL)
    assert at == 6 and len(set(names)) == len(names)
    layer = [m["name"] for m in man["per_layer"]]
    known = layer.index("moe_decode_roofline.batch") + 1
    behind = man["per_layer"][known:]
    assert (behind[0]["name"], behind[0]["workloads"]) \
        == ("moe_gather_ms_per_step", [theirs.CELL])
    # and since PR 63 the set-up's parts, since PR 64 the attention's
    # glue, none of them this cell's alone
    assert all(m["name"].startswith("setup_")
               and m.get("workloads") != [theirs.CELL] for m in behind[1:-1])
    assert behind[-1]["name"] == "attn_glue_ms_per_step" \
        and len(behind[-1]["workloads"]) == 3
    as_far = dict(man, workloads=man["workloads"][:at + 1],
                  per_layer=man["per_layer"][:known])
    monkeypatch.setattr(common, "manifest", lambda: as_far)
    theirs.test_cell_finds_its_files_and_its_rehearsal()


def test_gather_reader_sums_what_a_moe_scope_names_gather_or_scatter():
    """``moe_gather_ms_per_step`` (PR 62) on a made-up trace: the fetches
    and the scalar gathers under ``moe/``, forward and backward, a step;
    nothing of the embedding's or the loss head's, and 0.0 where a step
    ran and holds none."""
    from benchmark.harness import metrics
    moe = "jit(step)/transpose(jvp(gpt/ffn))/moe/"
    trace = {"modules": {"jit_step": {"calls": 4, "seconds": 1.2}},
             "op_seconds": {
                 moe + "experts/jit(_piece_bwd)/gather": 0.032,
                 "jit(step)/jvp(gpt/ffn)/moe/route/jit(take_along_axis)"
                 "/gather": 0.020,
                 moe + "route/jit(take_along_axis)/scatter-add": 0.004,
                 moe + "experts/jit(_piece_bwd)/jit(_gmm)/gmm_t/pallas_call":
                     0.05,
                 "jit(step)/jvp(gpt/embed)/gather": 0.01,
                 "jit(step)/jvp(gpt/ce)/jit(take_along_axis)/gather": 0.01}}
    read = metrics.read_layer_metric
    assert read("moe_gather_ms_per_step", {"trace": trace, "facts": {}}) \
        == theirs.pytest.approx(14.0)
    bare = dict(trace, op_seconds={"jit(step)/jvp(gpt/embed)/gather": 0.01})
    assert read("moe_gather_ms_per_step", {"trace": bare, "facts": {}}) == 0.0
