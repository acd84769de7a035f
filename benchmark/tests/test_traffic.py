"""The generator: every seed offers the same work, in another order."""

import collections
import math

import numpy as np
import pytest

from benchmark.harness import client, common, traffic

CHAT = common.load_json(common.BENCH_DIR + "/traffic/chat-returning.json")
BATCH = common.load_json(common.BENCH_DIR + "/traffic/offline-batch.json")
STEPS = common.load_json(common.BENCH_DIR + "/traffic/steps-b24x1024.json")
BIG = 2 ** 31 + 12345          # the driver's seeds pass 32 signed bits


def _chat(seed, seconds=40.0, rate=1.5):
    return traffic.open_loop_requests(CHAT, seed, seconds, 50304,
                                      rate_rps=rate)


def test_quantile_lengths_are_clipped_and_centred():
    xs = traffic.quantile_lengths(CHAT["history_tokens"], 101)
    assert xs == sorted(xs) and xs[0] >= 128 and xs[-1] <= 512
    assert xs[50] == 256                      # the median quantile


@pytest.mark.parametrize("seeds", [(1, 2), (7, BIG)])
def test_open_loop_same_multiset_other_order(seeds):
    a, b = (_chat(s) for s in seeds)
    for key in ("offered_prompt_tokens", "offered_output_tokens"):
        assert a[key] == b[key]
    assert sorted(len(r.prompt) for r in a["requests"]) != \
        [len(r.prompt) for r in a["requests"]]
    assert len(a["requests"]) == len(b["requests"]) == 60
    assert sum(r.returning for r in a["requests"]) == 36 == \
        len(a["histories"])
    assert [len(r.prompt) for r in a["requests"]] != \
        [len(r.prompt) for r in b["requests"]]
    # marginals are the same multisets even though pairings differ
    for f in (lambda r: r.max_new_tokens, lambda r: r.returning):
        assert collections.Counter(map(f, a["requests"])) == \
            collections.Counter(map(f, b["requests"]))


def test_open_loop_same_seed_same_inputs():
    a, b = _chat(BIG), _chat(BIG)
    assert [r.prompt for r in a["requests"]] == \
        [r.prompt for r in b["requests"]]
    assert [r.due_s for r in a["requests"]] == \
        [r.due_s for r in b["requests"]]


def test_open_loop_due_times_and_limits():
    plan = _chat(3)
    due = [r.due_s for r in plan["requests"]]
    assert due == sorted(due) and 0 <= due[0] and due[-1] < 40.0
    system = plan["system_prompt"]
    for r in plan["requests"]:
        assert r.prompt[:256] == system
        assert len(r.prompt) + r.max_new_tokens <= 1024
        assert r.cached_prefix == (len(r.prompt) - _msg_len(r, plan)
                                   if r.returning else 256)


def _msg_len(r, plan):
    # a returning user's history is one of the prefilled contexts
    ctx = next(h for h in plan["histories"]
               if r.prompt[:len(h)] == h and len(h) > 256)
    return len(r.prompt) - len(ctx)


def test_closed_loop_rounds_and_unshared_first_page():
    a = traffic.closed_loop_requests(BATCH, 1, 16, 50304)
    b = traffic.closed_loop_requests(BATCH, BIG, 16, 50304)
    assert len(a["by_client"]) == 16
    for k in range(int(BATCH["requests_per_client"])):
        for group in (slice(0, 8), slice(8, 16)):   # 8 slots, 2 a slot
            wave_a = sorted(len(c[k].prompt) for c in a["by_client"][group])
            wave_b = sorted(len(c[k].prompt) for c in b["by_client"][group])
            assert wave_a == wave_b             # each wave: same multiset
            assert sorted(c[k].max_new_tokens for c in a["by_client"][group]) \
                == sorted(c[k].max_new_tokens for c in b["by_client"][group])
    firsts = [r.prompt[0] for r in a["requests"]]
    assert len(set(firsts)) == len(firsts)      # no shared first page
    assert all(32 <= len(r.prompt) <= 512 and 32 <= r.max_new_tokens <= 256
               for r in a["requests"])


def test_train_batches_cycle():
    bs = traffic.train_batches(STEPS, BIG, 1, 50304)
    assert len(bs) == 16 and bs[0]["tokens"].shape == (24, 1024)
    assert (bs[0]["tokens"][:, 1:] == bs[0]["targets"][:, :-1]).all()
    assert not (bs[0]["tokens"] == bs[1]["tokens"]).all()
    again = traffic.train_batches(STEPS, BIG, 1, 50304)
    assert (again[3]["tokens"] == bs[3]["tokens"]).all()


def test_warmup_shapes_cover_every_bucket_once():
    plan = _chat(5)
    buckets = [32, 64, 128, 256, 512, 1024]
    shapes = traffic.warmup_shapes(plan["requests"], 128, buckets)
    keys = set()
    for r in plan["requests"]:
        cached = (min(r.cached_prefix, len(r.prompt) - 1) // 128) * 128
        fill = len(r.prompt) - cached
        keys.add(next(b for b in buckets if fill <= b))
    assert len(shapes) == len(keys)


def test_lateness_and_percentiles():
    o = client.Outcome(index=0, due=10.0, sent=10.004, wanted=3,
                       token_times=[10.5, 10.7, 11.0], finished=True)
    late = client.Outcome(index=1, due=11.0, sent=11.050, wanted=2,
                          token_times=[12.0, 12.1], finished=True)
    failed = client.Outcome(index=2, due=12.0, sent=12.0, wanted=2,
                            token_times=[12.5], error="boom")
    facts = client.latency_facts([o, late, failed])
    assert facts["ttft_ms"] == pytest.approx([500.0, 1000.0])
    assert sorted(facts["itl_ms"]) == pytest.approx([100.0, 200.0, 300.0])
    assert max(facts["lateness_ms"]) == pytest.approx(50.0)
    assert client.named_percentile("ttft_p50_ms", facts) == \
        pytest.approx(750.0)
    assert client.named_percentile("itl_p100_ms", facts) == \
        pytest.approx(300.0)
    assert client.named_percentile("first16_mean_ms", facts) is None
    assert not failed.ok and o.ok


BURST = common.load_traffic("chat-burst")


@pytest.mark.parametrize("seed", [1, 7, BIG])
def test_gamma_arrivals_same_work_as_poisson_only_clumped(seed):
    a = traffic.open_loop_requests(CHAT, seed, 40.0, 50304, rate_rps=1.5)
    b = traffic.open_loop_requests(BURST, seed, 40.0, 50304, rate_rps=1.5)
    # the same requests (count, lengths, tokens, who returns) ...
    assert [r.prompt for r in a["requests"]] == \
        [r.prompt for r in b["requests"]]
    assert [r.max_new_tokens for r in a["requests"]] == \
        [r.max_new_tokens for r in b["requests"]]
    # ... due at other times, inside the same window
    due = [r.due_s for r in b["requests"]]
    assert due == sorted(due) and 0 <= due[0] and due[-1] < 40.0
    assert due != [r.due_s for r in a["requests"]]
    again = traffic.open_loop_requests(BURST, seed, 40.0, 50304,
                                       rate_rps=1.5)
    assert due == [r.due_s for r in again["requests"]]


@pytest.mark.parametrize("seed", [3, 11, BIG + 1])
@pytest.mark.parametrize("cv", [2.0, 2.5])
def test_gamma_gaps_have_the_coefficient_of_variation_asked_for(seed, cv):
    n, seconds = 2000, 800.0
    mix = {"arrivals": "gamma", "arrival_cv": cv}
    due = traffic.arrival_times(n, seconds, mix, traffic.rng_for(seed, "a"))
    assert len(due) == n and due == sorted(due) and due[-1] < seconds
    gaps = np.diff([0.0] + due)
    # mean rate: n + 1 gaps fill the window, so n of them all but one
    assert n / seconds == pytest.approx(1.0 / gaps.mean(), rel=0.01)
    assert gaps.std() / gaps.mean() == pytest.approx(cv, rel=0.15)
    # poisson at the same count, for comparison: CV 1
    flat = np.diff([0.0] + traffic.arrival_times(
        n, seconds, {"arrivals": "poisson"}, traffic.rng_for(seed, "a")))
    assert flat.std() / flat.mean() == pytest.approx(1.0, rel=0.1)


def test_every_seed_offers_the_same_gaps_in_one_cycle_turned():
    # the seed does not resample the clumps: the n + 1 gaps are one fixed
    # cycle (the gamma's strata of equal probability, each at its mean)
    # and the seed decides where in it the window starts
    n, seconds = 130, 45.0
    mix = {"arrivals": "gamma", "arrival_cv": 2.5}

    def gaps(seed):
        due = traffic.arrival_times(n, seconds, mix,
                                    traffic.rng_for(seed, "arrivals"))
        assert due == sorted(due) and 0 <= due[0] and due[-1] < seconds
        return np.diff([0.0] + due + [seconds])

    first = gaps(1)
    assert first.sum() == pytest.approx(seconds)
    turns = set()
    for seed in (2, 7, 4300000601, BIG):
        g = gaps(seed)
        np.testing.assert_allclose(np.sort(g), np.sort(first), atol=1e-9)
        turn = [k for k in range(n + 1)
                if np.allclose(np.roll(first, k), g, atol=1e-9)]
        assert turn
        turns.add(turn[0])
    assert len(turns) > 1
    # the set is the distribution's: its strata at their means keep the
    # mean exactly and nearly all of the variance (CV 2.455 of 2.5 at 131)
    strata = traffic.gamma_strata(1 / 2.5 ** 2, n + 1)
    assert strata.sum() == pytest.approx(1.0)
    assert (np.diff(strata) >= 0).all()
    np.testing.assert_allclose(np.sort(first) / seconds, strata, atol=1e-9)
    assert strata.std() / strata.mean() == pytest.approx(2.455, abs=0.01)
    # another count or coefficient is another cycle
    other = traffic.arrival_times(n, seconds, {"arrivals": "gamma",
                                               "arrival_cv": 2.0},
                                  traffic.rng_for(1, "arrivals"))
    assert not np.allclose(np.diff([0.0] + other + [seconds]), first)


@pytest.mark.parametrize("k", [0.16, 0.25, 1.0, 4.0])
def test_the_incomplete_gamma_series_against_closed_forms(k):
    x = np.array([1e-30, 1e-9, 1e-3, 0.1, 1.0, 5.0, 20.0, 60.0])
    if k == 1.0:
        np.testing.assert_allclose(traffic._gamma_p(k, x), -np.expm1(-x),
                                   rtol=1e-12)
    # P(k + 1, x) = P(k, x) - x^k e^-x / Gamma(k + 1)
    np.testing.assert_allclose(
        traffic._gamma_p(k + 1, x),
        traffic._gamma_p(k, x) - np.exp(k * np.log(x) - x) / math.gamma(k + 1),
        atol=1e-13)
    # strata of equal probability: P at the edges is i / n
    n = 50
    s = traffic.gamma_strata(k, n)
    assert len(s) == n and s.sum() == pytest.approx(1.0)
    assert s.mean() == pytest.approx(1.0 / n)
    # the exponential's strata have a closed form: the integral of
    # x e^-x between two edges is u (1 - ln u) taken between their
    # survival shares u
    if k == 1.0:
        u = 1.0 - np.arange(n + 1) / n
        safe = np.where(u > 0, u, 1.0)
        np.testing.assert_allclose(
            s, np.diff(-(u * (1 - np.log(safe)))), atol=1e-12)


def test_the_tail_of_a_traced_run_is_other_conversations():
    a = traffic.open_loop_requests(CHAT, 5, 40.0, 50304, rate_rps=1.5)
    t = traffic.open_loop_requests(CHAT, 5, 20.0, 50304, rate_rps=1.5,
                                   part=".tail")
    assert t["system_prompt"] == a["system_prompt"]
    assert len(t["requests"]) == 30
    assert not {tuple(r.prompt) for r in a["requests"]} & \
        {tuple(r.prompt) for r in t["requests"]}


def test_a_tail_that_brings_the_windows_users_back_asks_nothing_of_setup():
    a = traffic.open_loop_requests(CHAT, BIG, 40.0, 50304, rate_rps=1.5)
    t = traffic.open_loop_requests(CHAT, BIG, 120.0, 50304, rate_rps=1.5,
                                   part=".tail", returning_from=a)
    # the window's plan is what it is without a tail
    assert [r.prompt for r in a["requests"]] == \
        [r.prompt for r in _chat(BIG)["requests"]]
    assert t["histories"] == [] and len(t["requests"]) == 180
    cached = {tuple(h) for h in a["histories"]}
    back = [r for r in t["requests"] if r.returning]
    assert len(back) == 108
    # every returning user of the tail is one of the window's, on the
    # history set-up cached for them, each about as often as the others
    used = collections.Counter(tuple(r.prompt[:r.cached_prefix])
                               for r in back)
    assert set(used) == cached
    assert max(used.values()) - min(used.values()) <= 1
    for r in t["requests"]:
        assert len(r.prompt) + r.max_new_tokens <= CHAT["max_total_tokens"]
        if not r.returning:
            assert r.cached_prefix == CHAT["system_prompt_tokens"]
    assert not {tuple(r.prompt) for r in a["requests"]} & \
        {tuple(r.prompt) for r in t["requests"]}
    again = traffic.open_loop_requests(CHAT, BIG, 120.0, 50304, rate_rps=1.5,
                                       part=".tail", returning_from=a)
    assert [r.prompt for r in again["requests"]] == \
        [r.prompt for r in t["requests"]]


def test_unknown_kinds_are_errors():
    # a new arrival process or length distribution is generator code,
    # which only a benchmark PR adds: a mix cannot name one that is not
    with pytest.raises(ValueError, match="weibull"):
        traffic.arrival_times(4, 8.0, {"arrivals": "weibull"},
                              traffic.rng_for(1, "a"))
    with pytest.raises(ValueError):
        traffic.arrival_times(4, 8.0, {"arrivals": "gamma",
                                       "arrival_cv": 0},
                              traffic.rng_for(1, "a"))
    with pytest.raises(ValueError):
        traffic.quantile_lengths({"dist": "fixed", "value": 64}, 3)


def test_a_mix_that_extends_another_takes_its_parameters():
    dp4 = common.load_traffic("steps-b96x1024-dp4")
    assert "extends" not in dp4 and dp4["who"] != STEPS["who"]
    assert {k: v for k, v in dp4.items() if k not in ("who", "why")} == \
        {k: v for k, v in STEPS.items() if k not in ("who", "why")}
