"""Counts ``benchmark/tests/test_manifest.py`` in tier-1, which collects
``tests/`` alone, so that weakening the benchmark's own checks costs
passes (ROADMAP D13)."""

from benchmark.tests.test_manifest import *  # noqa: F401,F403

# failing since PR 48 for the benchmark's own reasons (five cells and
# the toy's rehearsal asserted, six cells now; ROADMAP D13): left out
# until the `benchmark` PR that repairs them, which is followed by the
# removal of these lines
del test_every_cell_finds_its_files  # noqa: F821
del test_the_fifth_cell_is_the_chat_cell_with_clumped_arrivals  # noqa: F821
