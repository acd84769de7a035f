"""Counts ``benchmark/tests/test_startup_readers.py`` in tier-1, which
collects ``tests/`` alone, so that weakening the benchmark's own checks
costs passes (ROADMAP D13)."""

from benchmark.tests.test_startup_readers import *  # noqa: F401,F403
