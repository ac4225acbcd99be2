"""The arrival schedule and lengths are a pure function of the traffic
file; --seed draws the token ids: every seed gets the same work."""

import json

import numpy as np
import pytest

from benchmark import manifest, serve_cell, stats, traffic_gen

CHAT = json.load(open(manifest.traffic_file("chat-open")))
BATCH = json.load(open(manifest.traffic_file("batch-closed")))
BIG_SEED = 2**31 + 4242


def _shape(reqs):
    return [(r.due_s, len(r.prompt), r.max_new, r.measured) for r in reqs]


def test_open_loop_is_a_pure_function_of_the_seed():
    a = traffic_gen.open_loop_schedule(CHAT, BIG_SEED, 20, 50304)
    b = traffic_gen.open_loop_schedule(CHAT, BIG_SEED, 20, 50304)
    assert _shape(a) == _shape(b)
    assert [r.prompt for r in a] == [r.prompt for r in b]


def test_every_seed_gets_the_same_schedule_and_other_tokens():
    a = traffic_gen.open_loop_schedule(CHAT, 1, 30, 50304)
    b = traffic_gen.open_loop_schedule(CHAT, BIG_SEED, 30, 50304)
    assert _shape(a) == _shape(b)  # due times, lengths, budgets, order
    assert [r.prompt for r in a] != [r.prompt for r in b]
    longer = traffic_gen.open_loop_schedule(CHAT, 1, 40, 50304)
    assert len(longer) > len(a)


def test_open_loop_rate_window_and_limits():
    rate, pre = CHAT["arrivals"]["rate_per_s"], CHAT["preroll_s"]
    reqs = traffic_gen.open_loop_schedule(CHAT, 3, 40, 50304)
    measured = [r for r in reqs if r.measured]
    assert len(measured) == round(rate * 40)
    assert all(pre <= r.due_s < pre + 40 for r in measured)
    assert all(r.due_s < pre for r in reqs if not r.measured)
    dues = [r.due_s for r in reqs]
    assert dues == sorted(dues)
    p, o = CHAT["prompt_tokens"], CHAT["output_tokens"]
    assert all(p["min"] <= len(r.prompt) <= p["max"] for r in reqs)
    assert all(o["min"] <= r.max_new <= o["max"] for r in reqs)
    assert all(3 <= t < 50304 for r in reqs for t in r.prompt)


def test_closed_loop_clients_share_one_multiset():
    a = traffic_gen.closed_loop_clients(BATCH, 5, 8, 4, 50304)
    b = traffic_gen.closed_loop_clients(BATCH, BIG_SEED, 8, 4, 50304)
    assert len(a) == 8 and all(len(q) == 4 for q in a)
    flat = lambda qs: [len(r.prompt) for q in qs for r in q]  # noqa: E731
    assert flat(a) == flat(b)
    assert a[0][0].prompt != b[0][0].prompt
    assert {r.max_new for q in a for r in q} == {256}


def test_gamma_arrivals_and_shared_prefixes_need_only_data():
    mix = dict(CHAT, arrivals={"process": "gamma", "cv": 3.0,
                               "rate_per_s": 5.0},
               shared_prefix={"pool": 2, "tokens": [16, 16]})
    reqs = traffic_gen.open_loop_schedule(mix, 9, 20, 1000)
    heads = {tuple(r.prompt[:16]) for r in reqs}
    assert len(heads) == 2
    gaps = np.diff([r.due_s for r in reqs if r.measured])
    assert gaps.std() / gaps.mean() > 1.5  # burstier than Poisson's 1


def test_lateness_and_latency_accounting():
    """TTFT runs from when a request was DUE; lateness is sent - due."""
    req = traffic_gen.Request(0, 0.0, [3, 4], 3, True)
    rec = serve_cell.Record(req, t_due=100.0)
    rec.t_sent = 100.25
    rec.stamps = [101.0, 101.5, 101.75]
    rec.done = True
    ttft, gaps, late = serve_cell._latencies([rec])
    assert ttft == [1.0] and gaps == [0.5, 0.25] and late == [0.25]
    assert not serve_cell._failed(rec)
    rec.stamps = rec.stamps[:2]
    assert serve_cell._failed(rec)  # ended short of its budget


@pytest.mark.parametrize("n,ok", [(199, False), (200, True)])
def test_percentile_sample_count_rule(n, ok):
    xs = list(range(n))
    assert stats.samples_needed(95) == 200
    assert stats.supported(xs, 95) is ok
    assert stats.pctl(xs, 95) == xs[int(round(0.95 * (n - 1)))]
    assert stats.pctl([], 95) is None
    assert stats.pctl([7.0], 50) == 7.0


def test_ttft_statistics_are_over_all_requests():
    ttft = [0.001 * k for k in range(1, 101)]  # 1..100 ms
    got = serve_cell._ttft_stats(ttft[::-1])
    assert got["ttft_mean_ms"] == pytest.approx(50.5)
    assert got["ttft_p90_ms"] == pytest.approx(90.0)
    assert got["ttft_tail10_mean_ms"] == pytest.approx(95.5)  # 91..100
    assert serve_cell._ttft_stats([]) == {}
