"""Counts ``benchmark/tests/test_family.py`` in tier-1, which collects
``tests/`` alone, so that weakening the benchmark's own checks costs
passes (ROADMAP D13)."""

from benchmark.tests.test_family import *  # noqa: F401,F403

# failing since PR 48 for the benchmark's own reasons (five cells and
# the toy's rehearsal asserted, six cells now; ROADMAP D13): left out
# until the `benchmark` PR that repairs them, which is followed by the
# removal of these lines
del test_a_rehearsal_walks_the_cells_own_family_where_it_brings_a_file  # noqa: F821
