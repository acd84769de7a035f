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
    PR's to edit); everything else it reads is the manifest as it is."""
    man = common.manifest()
    names = [w["name"] for w in man["workloads"]]
    at = names.index(theirs.CELL)
    assert at == 6 and len(set(names)) == len(names)
    as_far = dict(man, workloads=man["workloads"][:at + 1])
    monkeypatch.setattr(common, "manifest", lambda: as_far)
    theirs.test_cell_finds_its_files_and_its_rehearsal()
