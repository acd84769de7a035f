"""The generator: every seed offers the same work, in another order."""

import collections

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


def test_unknown_kinds_are_errors():
    # a new arrival process or length distribution is generator code,
    # which only a benchmark PR adds: a mix cannot name one that is not
    with pytest.raises(ValueError):
        traffic.arrival_times(4, 8.0, "gamma", traffic.rng_for(1, "a"))
    with pytest.raises(ValueError):
        traffic.quantile_lengths({"dist": "fixed", "value": 64}, 3)


def test_a_mix_that_extends_another_takes_its_parameters():
    dp4 = common.load_traffic("steps-b96x1024-dp4")
    assert "extends" not in dp4 and dp4["who"] != STEPS["who"]
    assert {k: v for k, v in dp4.items() if k not in ("who", "why")} == \
        {k: v for k, v in STEPS.items() if k not in ("who", "why")}
