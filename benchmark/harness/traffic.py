"""The one general traffic generator: every mix is a data file of
parameters under ``benchmark/traffic/`` and this module turns it, with a
seed, into the inputs a runner feeds to the system.

The seed permutes and does not resample.  A length distribution is
evaluated at as many evenly spaced quantiles as there are requests, so
every seed offers the same multiset of lengths (the same tokens of
work); the seed decides which request gets which length, the token ids,
and the arrival times.  Clumped arrivals (``gamma``) are a fixed set of
gaps too, in one order that the seed turns: every seed offers the same
clumps (PR 43: a decode's time follows the live pages since PR 37, so
gaps drawn afresh made each seed another amount of work).
"""

from __future__ import annotations

import dataclasses
import math
import statistics
from typing import Any, Dict, List, Optional

import numpy as np

_NORMAL = statistics.NormalDist()


def rng_for(seed: int, stream: str) -> np.random.Generator:
    """An independent generator per named stream of one seed (any whole
    number; the driver's seeds pass 2**31)."""
    return np.random.default_rng(
        [int(seed) & 0xFFFFFFFF, int(seed) >> 32,
         *(ord(c) for c in stream)])


def quantile_lengths(spec: Dict[str, Any], n: int) -> List[int]:
    """``n`` lengths: the distribution's quantiles at (i + 0.5) / n,
    clipped to [min, max].  Sorted; the caller permutes."""
    if n <= 0:
        return []
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    mu, sigma = math.log(spec["median"]), float(spec["sigma"])
    out = []
    for i in range(n):
        z = _NORMAL.inv_cdf((i + 0.5) / n)
        out.append(int(min(max(round(math.exp(mu + sigma * z)),
                               spec["min"]), spec["max"])))
    return out


def permuted(values: List[int], rng: np.random.Generator) -> List[int]:
    return [values[i] for i in rng.permutation(len(values))]


@dataclasses.dataclass
class Request:
    index: int
    prompt: List[int]
    max_new_tokens: int
    due_s: float = 0.0            # open loop: offset from window start
    cached_prefix: int = 0        # tokens prefilled into the cache in set-up
    returning: bool = False
    client: int = 0               # closed loop: which client sends it

    @property
    def payload(self) -> Dict[str, Any]:
        # greedy decoding, forced length (no EOS with random weights)
        return {"tokens": self.prompt,
                "max_new_tokens": self.max_new_tokens}


def _tokens(rng: np.random.Generator, n: int, vocab: int) -> List[int]:
    return rng.integers(0, vocab, size=n).tolist()


def _poisson(n: int, seconds: float, mix: Dict[str, Any],
             rng: np.random.Generator) -> List[float]:
    """A Poisson process conditioned on its count: sorted uniforms."""
    return sorted(rng.uniform(0.0, seconds, size=n).tolist())


def _gamma_p(k: float, x: np.ndarray) -> np.ndarray:
    """The regularised lower incomplete gamma function P(k, x) by its
    series, x^k e^-x sum_m x^m / Gamma(k + m + 1): numpy alone, good for
    the x a gap's quantile reaches (under 100)."""
    x = np.asarray(x, dtype=np.float64)
    term = np.full_like(x, 1.0 / math.gamma(k + 1.0))
    total = term.copy()
    for m in range(1, 400):
        term = term * x / (k + m)
        total += term
    with np.errstate(under="ignore"):
        return np.clip(np.exp(k * np.log(x) - x) * total, 0.0, 1.0)


def gamma_strata(k: float, n: int) -> np.ndarray:
    """A gamma distribution of shape ``k`` cut into ``n`` strata of equal
    probability, each at its mean: ascending, as shares of their sum.
    The edges are the quantiles at i / n, found by bisection on log x;
    the mean of a stratum is k (P(k + 1, b) - P(k + 1, a)) n."""
    lo = np.full(n - 1, -700.0)
    hi = np.full(n - 1, math.log(k + 40.0 * math.sqrt(k) + 40.0))
    want = np.arange(1, n) / n
    for _ in range(100):
        mid = 0.5 * (lo + hi)
        below = _gamma_p(k, np.exp(mid)) < want
        lo, hi = np.where(below, mid, lo), np.where(below, hi, mid)
    edges = np.exp(0.5 * (lo + hi))
    return np.diff(np.concatenate(
        [[0.0], _gamma_p(k + 1.0, edges), [1.0]]))


def _gamma(n: int, seconds: float, mix: Dict[str, Any],
           rng: np.random.Generator) -> List[float]:
    """A renewal process whose gaps are gamma-distributed with
    coefficient of variation ``arrival_cv`` (shape 1 / cv^2: 1 is
    Poisson, above it arrivals clump), conditioned on its count as the
    Poisson one is: ``n + 1`` gaps that fill [0, seconds).  The gaps are
    not drawn: they are the distribution's ``n + 1`` strata of equal
    probability, each at its mean, in one order fixed by the count and
    the coefficient alone, and the seed turns that cycle to where the
    window starts.  So every seed offers the same gaps and the same
    clumps, as it offers the same lengths, and only which request meets
    which clump differs."""
    cv = float(mix["arrival_cv"])
    if not cv > 0:
        raise ValueError(f"arrival_cv must be positive, not {cv!r}")
    gaps = gamma_strata(1.0 / cv ** 2, n + 1)
    order = np.random.default_rng(
        [n + 1, int(round(1000 * cv))]).permutation(n + 1)
    gaps = np.roll(gaps[order], int(rng.integers(0, n + 1)))
    # the least strata are 0 to rounding: ties in due time are fine, and
    # where the last gap is such a one the last request is due just
    # inside the window
    due = np.cumsum(gaps)[:n] * (seconds / gaps.sum())
    return np.minimum(due, np.nextafter(seconds, 0.0)).tolist()


# arrival processes by the name a mix gives under ``arrivals``
ARRIVALS = {"poisson": _poisson, "gamma": _gamma}


def arrival_times(n: int, seconds: float, mix: Dict[str, Any],
                  rng: np.random.Generator) -> List[float]:
    """``n`` due times in [0, seconds), ascending, by the process the
    mix names: every seed offers the same number of requests at the same
    mean rate."""
    process = ARRIVALS.get(mix["arrivals"])
    if process is None:
        raise ValueError(f"unknown arrival process {mix['arrivals']!r} "
                         f"(known: {sorted(ARRIVALS)})")
    return process(n, seconds, mix, rng)


def open_loop_requests(mix: Dict[str, Any], seed: int, seconds: float,
                       vocab: int, rate_rps: Optional[float] = None,
                       part: str = "",
                       returning_from: Optional[Dict[str, Any]] = None
                       ) -> Dict[str, Any]:
    """The chat mix: one shared system prompt, each request the next turn
    of its own conversation.  Returns the requests in due order, the
    histories to prefill in set-up (returning users), and the offered
    totals.  ``part`` names a further stretch of the same seed's traffic
    (the load a traced run keeps up after its window): messages, outputs,
    arrivals and new users of its own behind the same system prompt.
    With ``returning_from``, an earlier stretch's plan, its returning
    users are that stretch's, back with another message on the history
    set-up already cached for them (in a seeded order, round and round):
    it asks nothing of set-up, so ``histories`` is empty and the stretch
    can be as long as it has to be."""
    rate = float(rate_rps if rate_rps is not None else mix["rate_rps"])
    n = max(1, int(round(rate * seconds)))
    sys_len = int(mix["system_prompt_tokens"])
    cap = int(mix["max_total_tokens"])
    hist = permuted(quantile_lengths(mix["history_tokens"], n),
                    rng_for(seed, "history" + part))
    msg = permuted(quantile_lengths(mix["message_tokens"], n),
                   rng_for(seed, "message" + part))
    out = permuted(quantile_lengths(mix["output_tokens"], n),
                   rng_for(seed, "output" + part))
    n_ret = int(round(float(mix["returning_share"]) * n))
    returning = permuted([True] * n_ret + [False] * (n - n_ret),
                         rng_for(seed, "returning" + part))
    due = arrival_times(n, seconds, mix, rng_for(seed, "arrivals" + part))
    tok = rng_for(seed, "tokens")
    system = _tokens(tok, sys_len, vocab)
    if part:
        tok = rng_for(seed, "tokens" + part)
    back = []
    if returning_from is not None and returning_from["histories"]:
        back = permuted(returning_from["histories"],
                        rng_for(seed, "back" + part))
    requests, histories = [], []
    for i in range(n):
        if returning[i] and back:
            context = back[sum(r.returning for r in requests) % len(back)]
        else:
            context = system + _tokens(tok, hist[i], vocab)
        new = min(out[i], cap - (len(context) + msg[i]))
        prompt = context + _tokens(tok, msg[i], vocab)
        requests.append(Request(
            index=i, prompt=prompt, max_new_tokens=new, due_s=due[i],
            cached_prefix=len(context) if returning[i] else sys_len,
            returning=returning[i]))
        if returning[i] and not back:
            histories.append(context)
    return {"requests": requests, "system_prompt": system,
            "histories": histories, "rate_rps": rate,
            "offered_prompt_tokens": sum(len(r.prompt) for r in requests),
            "offered_output_tokens": sum(r.max_new_tokens
                                         for r in requests)}


def closed_loop_requests(mix: Dict[str, Any], seed: int, clients: int,
                         vocab: int) -> Dict[str, Any]:
    """The batch mix: ``clients`` callers, each with its own list of
    requests sent one after the other.  No two prompts share a first
    page: the first token of request i is a distinct id."""
    per = int(mix["requests_per_client"])
    n = clients * per
    cap = int(mix["max_total_tokens"])
    # every round (the k-th request of all clients) is dealt in groups of
    # as many clients as the engine has slots, and each group is the whole
    # set of quantiles, permuted: the wave that fills the slots, and every
    # wave after it, is the same multiset of work for every seed
    group = max(1, clients // int(mix["clients_per_slot"]))
    plen, out = [], []
    for k in range(per):
        for g in range(0, clients, group):
            size = min(group, clients - g)
            plen += permuted(quantile_lengths(mix["prompt_tokens"], size),
                             rng_for(seed, f"prompt{k}.{g}"))
            out += permuted(quantile_lengths(mix["output_tokens"], size),
                            rng_for(seed, f"output{k}.{g}"))
    tok = rng_for(seed, "tokens")
    first = rng_for(seed, "first").permutation(vocab)[:n].tolist() \
        if n <= vocab else None
    requests = []
    for i in range(n):
        prompt = _tokens(tok, plen[i], vocab)
        if first is not None:
            prompt[0] = int(first[i])
        requests.append(Request(
            index=i, prompt=prompt,
            max_new_tokens=min(out[i], cap - plen[i]), client=i % clients))
    return {"requests": requests,
            "by_client": [[r for r in requests if r.client == c]
                          for c in range(clients)]}


def train_batches(mix: Dict[str, Any], seed: int, chips: int,
                  vocab: int) -> List[Dict[str, np.ndarray]]:
    """The seeded cycle of distinct host batches a train cell feeds, one
    per step: int32 ``tokens`` / ``targets`` of [batch_per_chip * chips,
    seq]."""
    B, S = int(mix["batch_per_chip"]) * chips, int(mix["seq"])
    rng = rng_for(seed, "train")
    out = []
    for _ in range(int(mix["distinct_batches"])):
        t = rng.integers(0, vocab, size=(B, S + 1), dtype=np.int32)
        out.append({"tokens": np.ascontiguousarray(t[:, :-1]),
                    "targets": np.ascontiguousarray(t[:, 1:])})
    return out


def warmup_shapes(requests: List[Request], page_size: int,
                  buckets: List[int]) -> List[Request]:
    """One stand-in per distinct (prefill kind, bucket) the requests will
    use, so that warming up touches every executable of the window and no
    other.  A stand-in has the same prompt length and cached prefix as a
    real request of that shape; the runner gives it fresh token ids."""
    seen, out = set(), []
    for r in requests:
        cached = (min(r.cached_prefix, len(r.prompt) - 1)
                  // page_size) * page_size
        fill = len(r.prompt) - cached
        bucket = next(b for b in buckets if fill <= b)
        key = ("cached" if cached else "cold", bucket)
        if key not in seen:
            seen.add(key)
            out.append(r)
    return out
