"""The harness's look at the chips' device nodes (``harness/chips.py``)
and what ``run.py`` does with it: a run waits, outside its clock, for
nodes a process that has exited still holds, and holds its own exit
until they are free.  No chip here: the nodes are made up and ``os.open``
answers for them as the kernel would."""

import errno
import os
import sys
import time

import pytest

from benchmark import run as run_mod
from benchmark.harness import chips


class FakeNodes:
    """``answers[path]`` is what each successive ``os.open`` of the node
    meets: an errno, or ``None`` for a descriptor; the last answer
    repeats.  Any other path goes to the real ``os.open``."""

    def __init__(self, monkeypatch, answers):
        self.answers = {p: list(a) for p, a in answers.items()}
        self.opened, self.closed, self.flags = [], [], set()
        self._open, self._close = os.open, os.close
        monkeypatch.setattr(chips, "node_paths", lambda: sorted(answers))
        monkeypatch.setattr(chips.os, "open", self.open)
        monkeypatch.setattr(chips.os, "close", self.close)

    def open(self, path, flags, *rest, **kw):
        if path not in self.answers:
            return self._open(path, flags, *rest, **kw)
        self.flags.add(flags)
        left = self.answers[path]
        answer = left.pop(0) if len(left) > 1 else left[0]
        if answer is not None:
            raise OSError(answer, os.strerror(answer), path)
        fd = -1000 - len(self.opened)
        self.opened.append(fd)
        return fd

    def close(self, fd):
        if fd >= 0:
            return self._close(fd)
        self.closed.append(fd)


def test_a_node_busy_three_looks_long_is_waited_for(monkeypatch):
    nodes = FakeNodes(monkeypatch, {
        "/dev/vfio/0": [None],
        "/dev/vfio/1": [errno.EBUSY] * 3 + [None]})
    t0 = time.monotonic()
    waited, seen, still = chips.wait_free(10.0, poll_s=0.25)
    wall = time.monotonic() - t0
    assert (seen, still) == (["/dev/vfio/1"], [])
    assert 0.5 < waited < 1.0                  # three sleeps: 0.75 s
    assert abs(wall - waited) < 0.1
    assert chips.busy_nodes() == [] and chips.busy_nodes() == []
    assert nodes.opened and nodes.closed == nodes.opened
    assert nodes.flags == {os.O_RDWR}


def test_a_node_that_never_opens_is_named_at_the_limit(monkeypatch):
    nodes = FakeNodes(monkeypatch, {
        "/dev/vfio/0": [None], "/dev/vfio/3": [errno.EBUSY]})
    waited, seen, still = chips.wait_free(0.6, poll_s=0.25)
    assert seen == still == ["/dev/vfio/3"]
    assert 0.6 <= waited < 0.9
    assert nodes.closed == nodes.opened        # node 0's, every look


@pytest.mark.parametrize("err", [errno.EACCES, errno.ENOENT, errno.ENODEV])
def test_an_error_that_is_not_ebusy_counts_as_free(monkeypatch, err):
    FakeNodes(monkeypatch, {"/dev/vfio/0": [err], "/dev/vfio/1": [None]})
    monkeypatch.setattr(chips.time, "sleep", _no_sleep)
    assert chips.busy_nodes() == []
    assert chips.wait_free(5.0) == (0.0, [], [])


def test_no_nodes_no_wait_no_sleep(monkeypatch):
    FakeNodes(monkeypatch, {})
    monkeypatch.setattr(chips.time, "sleep", _no_sleep)
    assert chips.busy_nodes() == []
    assert chips.wait_free(90.0) == (0.0, [], [])


def test_a_look_that_blocks_in_the_kernel_is_counted_as_a_wait(monkeypatch):
    """An ``open`` that meets a release in progress answers seconds later,
    and with a descriptor: no ``EBUSY`` is seen, and the time is a wait
    all the same.  The millisecond four free nodes take reads 0.0."""
    FakeNodes(monkeypatch, {"/dev/vfio/0": [None]})
    monkeypatch.setattr(chips.time, "sleep", _no_sleep)
    clock = iter([100.0, 103.2004, 200.0, 200.0014])
    monkeypatch.setattr(chips.time, "monotonic", lambda: next(clock))
    assert chips.wait_free(90.0) == (3.2, [], [])
    assert chips.wait_free(90.0) == (0.0, [], [])


def test_only_numbered_vfio_groups_are_nodes(monkeypatch):
    monkeypatch.setattr(chips.glob, "glob", lambda pattern: [
        "/dev/vfio/vfio", "/dev/vfio/10", "/dev/vfio/2", "/dev/vfio/devices"])
    assert chips.node_paths() == ["/dev/vfio/10", "/dev/vfio/2"]
    monkeypatch.setattr(chips.glob, "glob", lambda pattern: [])
    assert chips.node_paths() == []            # /dev/accel* hosts, or none


def _no_sleep(seconds):
    raise AssertionError(f"slept {seconds} s with nothing busy")


def _drive_main(monkeypatch, capsys, argv, answers, fails=False):
    """``run.main`` up to its runner and out again: the runner is a stub
    that notes the clock's start it is handed and reports a device count
    no cell has, so ``main`` returns 2 straight after its ``finally``
    (or raises, where ``fails``).  ``answers`` are what
    ``chips.wait_free`` returns, in turn."""
    from benchmark.harness import train_cell
    from ray_tpu.accelerators import tpu

    handed, calls = [], []

    def stub_run(files, args, t_start):
        handed.append(t_start)
        if fails:
            raise RuntimeError("Unable to initialize backend 'tpu'")
        return {"device": {"platform": "tpu", "count": -1}}

    def wait_free(limit_s, poll_s=0.25):
        calls.append(limit_s)
        return answers.pop(0)

    class NoWatchdog:                  # the real one ends the process
        def __init__(self, *args):
            calls.append("watchdog")

        def start(self):
            pass

    monkeypatch.setattr(run_mod.threading, "Timer", NoWatchdog)
    monkeypatch.setattr(train_cell, "run", stub_run)
    monkeypatch.setattr(tpu, "detect_num_tpus", lambda: 1)
    monkeypatch.setattr(chips, "wait_free", wait_free)
    for name in ("JAX_PLATFORMS", "XLA_FLAGS", "JAX_COMPILATION_CACHE_DIR"):
        monkeypatch.setenv(name, os.environ.get(name, ""))   # put back after
    monkeypatch.setattr(sys, "argv", ["run.py"] + argv)
    code = run_mod.main()
    return code, handed, calls, capsys.readouterr().err


CELL = ["--workload", "train-gpt2-124m-b24x1024", "--seed", "7"]


FREE = (0.0, [], [])
BOTH_LIMITS = [run_mod.CHIPS_TAKE_LIMIT_S, run_mod.CHIPS_RELEASE_LIMIT_S]


def test_the_runner_is_handed_the_start_plus_the_wait(monkeypatch, capsys):
    code, handed, calls, err = _drive_main(monkeypatch, capsys, CELL, [
        (11.5, ["/dev/vfio/1", "/dev/vfio/2"], []),
        (13.25, ["/dev/vfio/0"], [])])
    assert code == 2                           # the stub's device count
    assert handed == [run_mod.T_START + 11.5]
    assert calls == BOTH_LIMITS
    assert ("benchmark: waited 11.50 s for /dev/vfio/1, /dev/vfio/2 to be "
            "free") in err
    assert ("benchmark: held the exit 13.25 s until the chips were "
            "free") in err


def test_a_wait_inside_the_looks_alone_is_a_wait(monkeypatch, capsys):
    code, handed, calls, err = _drive_main(monkeypatch, capsys, CELL, [
        (6.7, [], []), FREE])
    assert handed == [run_mod.T_START + 6.7]
    assert "benchmark: waited 6.70 s for the chips to be free" in err


def test_nodes_busy_at_the_limit_are_named_and_the_run_goes_on(
        monkeypatch, capsys):
    code, handed, calls, err = _drive_main(monkeypatch, capsys, CELL, [
        (90.0, ["/dev/vfio/1", "/dev/vfio/2"], ["/dev/vfio/2"]),
        (60.0, ["/dev/vfio/2"], ["/dev/vfio/2"])])
    assert code == 2 and handed == [run_mod.T_START + 90.0]
    assert "benchmark: /dev/vfio/2 still busy after 90.00 s; going on" in err
    assert ("benchmark: /dev/vfio/2 still busy 60.00 s after the "
            "shutdown") in err


def test_a_run_that_fails_holds_its_exit_too(monkeypatch, capsys):
    code, handed, calls, err = _drive_main(monkeypatch, capsys, CELL, [
        FREE, (12.4, ["/dev/vfio/3"], [])], fails=True)
    assert code == 1 and handed == [run_mod.T_START]
    assert calls == [BOTH_LIMITS[0], "watchdog", BOTH_LIMITS[1]]
    assert err.index("benchmark: failed: RuntimeError") < err.index(
        "benchmark: held the exit 12.40 s until the chips were free")


def test_on_a_free_host_the_clock_starts_where_it_did(monkeypatch, capsys):
    code, handed, calls, err = _drive_main(monkeypatch, capsys, CELL,
                                           [FREE, FREE])
    assert code == 2
    assert handed == [run_mod.T_START] and calls == BOTH_LIMITS
    assert "waited" not in err and "held the exit" not in err


def test_a_rehearsal_does_not_look_at_the_chips(monkeypatch, capsys):
    code, handed, calls, err = _drive_main(
        monkeypatch, capsys, CELL + ["--rehearse-on-cpu"], [])
    assert code == 2
    assert handed == [run_mod.T_START] and calls == []
