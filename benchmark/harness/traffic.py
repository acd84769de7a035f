"""The one general traffic generator: every mix is a data file of
parameters under ``benchmark/traffic/`` and this module turns it, with a
seed, into the inputs a runner feeds to the system.

The seed permutes and does not resample.  A length distribution is
evaluated at as many evenly spaced quantiles as there are requests, so
every seed offers the same multiset of lengths (the same tokens of
work); the seed decides which request gets which length, the token ids,
and the arrival times.
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


def arrival_times(n: int, seconds: float, kind: str,
                  rng: np.random.Generator) -> List[float]:
    """``n`` due times in [0, seconds).  ``poisson``: a Poisson process
    conditioned on its count, i.e. sorted uniforms, so every seed offers
    the same number of requests."""
    if kind == "poisson":
        return sorted(rng.uniform(0.0, seconds, size=n).tolist())
    raise ValueError(f"unknown arrival process {kind!r}")


def open_loop_requests(mix: Dict[str, Any], seed: int, seconds: float,
                       vocab: int, rate_rps: Optional[float] = None
                       ) -> Dict[str, Any]:
    """The chat mix: one shared system prompt, each request the next turn
    of its own conversation.  Returns the requests in due order, the
    histories to prefill in set-up (returning users), and the offered
    totals."""
    rate = float(rate_rps if rate_rps is not None else mix["rate_rps"])
    n = max(1, int(round(rate * seconds)))
    sys_len = int(mix["system_prompt_tokens"])
    cap = int(mix["max_total_tokens"])
    hist = permuted(quantile_lengths(mix["history_tokens"], n),
                    rng_for(seed, "history"))
    msg = permuted(quantile_lengths(mix["message_tokens"], n),
                   rng_for(seed, "message"))
    out = permuted(quantile_lengths(mix["output_tokens"], n),
                   rng_for(seed, "output"))
    n_ret = int(round(float(mix["returning_share"]) * n))
    returning = permuted([True] * n_ret + [False] * (n - n_ret),
                         rng_for(seed, "returning"))
    due = arrival_times(n, seconds, mix["arrivals"],
                        rng_for(seed, "arrivals"))
    tok = rng_for(seed, "tokens")
    system = _tokens(tok, sys_len, vocab)
    requests, histories = [], []
    for i in range(n):
        new = min(out[i], cap - (sys_len + hist[i] + msg[i]))
        context = system + _tokens(tok, hist[i], vocab)
        prompt = context + _tokens(tok, msg[i], vocab)
        requests.append(Request(
            index=i, prompt=prompt, max_new_tokens=new, due_s=due[i],
            cached_prefix=len(context) if returning[i] else sys_len,
            returning=returning[i]))
        if returning[i]:
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
