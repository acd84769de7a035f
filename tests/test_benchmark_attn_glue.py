"""Counts ``benchmark/tests/test_attn_glue.py`` in tier-1, which collects
``tests/`` alone, so that weakening the benchmark's own checks costs
passes (ROADMAP D13)."""

from benchmark.tests.test_attn_glue import *  # noqa: F401,F403
