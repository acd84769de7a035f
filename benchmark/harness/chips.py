"""Are this host's TPU chips free to be taken?

A VFIO group (``/dev/vfio/<n>``, one a chip) opens for one process at a
time, and on some hosts the kernel lets go of a four-chip holder's groups
one after another, 13-20 s after the holder was reaped (builder, PR 42): a
run started inside that time dies in libtpu on ``open(/dev/vfio/<n>):
Device or resource busy``.  ``run.py`` looks here before it starts its
clock and after its shutdown, so that a run takes the chips only when
they are free and returns only when they are free again.

Nothing but the standard library, and nothing of the program: the look
may not change how the program under test behaves.  Opening a group node
and closing it at once takes nothing from the next opener; an open that
meets a release in progress waits for it in the kernel, which is as good
as a sleep and is counted as one (``wait_free``).
"""

from __future__ import annotations

import errno
import glob
import os
import time
from typing import List, Tuple


def node_paths() -> List[str]:
    """Every ``/dev/vfio/<digits>``; a host whose chips are ``/dev/accel*``
    nodes, or that has none, gives ``[]``."""
    return sorted(p for p in glob.glob("/dev/vfio/*")
                  if os.path.basename(p).isdigit())


def busy_nodes() -> List[str]:
    """The group nodes that answer an open with ``EBUSY``.  Any other
    error (``EACCES``, ``ENOENT``, ``ENODEV``) is not the harness's to
    judge and counts as free: libtpu reports it as it always has."""
    busy = []
    for path in node_paths():
        try:
            fd = os.open(path, os.O_RDWR)
        except OSError as e:
            if e.errno == errno.EBUSY:
                busy.append(path)
        else:
            os.close(fd)
    return busy


def wait_free(limit_s: float, poll_s: float = 0.25
              ) -> Tuple[float, List[str], List[str]]:
    """Look until no node is busy or ``limit_s`` have passed: the seconds
    waited, every node seen busy, and those still busy.  No sleep where
    the first look finds none busy.

    The wait counts the looks themselves.  While the kernel is letting go
    of a chip an ``open`` of its node does not answer at once: it blocks
    until that chip is released, for seconds, and then succeeds (two looks
    at a four-chip holder's nodes took 6-7 s, and a run whose looks met no
    ``EBUSY`` at all started 6.7 s late; my chip run, PR 43).  It is
    rounded to a hundredth of a second, so that a look at free nodes, a
    millisecond for four of them, reads 0.0."""
    t0 = time.monotonic()
    seen = set()
    while True:
        busy = busy_nodes()
        seen.update(busy)
        waited = round(time.monotonic() - t0, 2)
        if not busy or waited >= limit_s:
            return waited, sorted(seen), busy
        time.sleep(min(poll_s, limit_s - waited))
