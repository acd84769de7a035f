"""Client-side timing: what a user of the serve handle sees.

One thread per request in flight, each iterating its stream and reading
the clock as every token lands.  Time to first token is taken from the
moment the request was *due* (open loop) or sent (closed loop), so a
generator or a front end that runs late is charged to the system, and
the generator's own lateness is reported beside it."""

from __future__ import annotations

import dataclasses
import re
import threading
import time
from typing import Any, Dict, List, Optional

from benchmark.harness import common


@dataclasses.dataclass
class Outcome:
    index: int
    due: float                         # monotonic
    sent: float = 0.0
    token_times: List[float] = dataclasses.field(default_factory=list)
    wanted: int = 0
    error: Optional[str] = None
    finished: bool = False
    returning: bool = False

    @property
    def ok(self) -> bool:
        return (self.finished and self.error is None
                and len(self.token_times) == self.wanted)


def stream(handle, request, outcome: Outcome,
           stop: Optional[threading.Event] = None) -> None:
    """Send one request and record when each token arrives."""
    outcome.wanted = request.max_new_tokens
    outcome.sent = time.monotonic()
    try:
        for _tok in handle.options(stream=True).remote(request.payload):
            outcome.token_times.append(time.monotonic())
            if stop is not None and stop.is_set():
                return
        outcome.finished = True
    except BaseException as e:  # noqa: BLE001 — a failed request is data
        if stop is None or not stop.is_set():
            outcome.error = repr(e)[:300]


def latency_facts(outcomes: List[Outcome]) -> Dict[str, Any]:
    """TTFT (from the due time; a request that did not finish well has
    none and counts as failed) and the gaps between consecutive tokens of
    every stream, pooled."""
    ttft = [1e3 * (o.token_times[0] - o.due) for o in outcomes if o.ok]
    gaps = [1e3 * (b - a) for o in outcomes if o.ok
            for a, b in zip(o.token_times, o.token_times[1:])]
    lateness = [1e3 * (o.sent - o.due) for o in outcomes if o.sent]
    return {"ttft_ms": ttft, "itl_ms": gaps, "lateness_ms": lateness}


def named_percentile(name: str, facts: Dict[str, Any]) -> Optional[float]:
    """``ttft_p50_ms`` / ``itl_p95_ms`` ...: any percentile of either
    series, by name, so a later cell can take another tail as data."""
    m = re.fullmatch(r"(ttft|itl)_p(\d+(?:\.\d+)?)_ms", name)
    if not m or not facts[m.group(1) + "_ms"]:
        return None
    return common.percentile(facts[m.group(1) + "_ms"], float(m.group(2)))
