"""ISSUE 40's reckoning, where the next writer can rerun it: the bursty
state-space cell's own schedule (`jamba2-3b-serve-burst`'s traffic
file: the same sizes and gaps for every seed) through a model of the
scheduler's prefill order, on the host, in well under a second.

The model: a tick every 7.87 ms (PERF.md section 5: 629 ticks in
4.952 s), ONE chunk of 64 rows a tick, a prompt admitted at the first
tick at or after it is due (a lane is always free at the cell's rate)
and runnable in that tick, every prompt through the chunk rows (one of
at most a chunk as one chunk: its whole-prompt program takes the device
about a tick all the same). It reproduced the ledger under the parent's
order before it was asked about another: due to last chunk 435.5 ms
where PR 39's stages read queue 5.3 + wait 66.5 + ride 371.6 = 443.4.
That order ("ring": every admission a chunk in turn, round the ring)
lives on here alone, as the yardstick of the one that replaced it."""

import json
import os
import statistics

import pytest

from benchmark import traffic_gen
from luminaai_tpu.serving.server import pick_prefill

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRAFFIC = os.path.join(REPO, "benchmark", "traffic",
                       "chat-burst-jamba2-3b.json")
TICK_S, CHUNK, WINDOW_S = 0.00787, 64, 51.0
# Last chunk dispatched -> the harness's first token: the tick that
# carries it, the read after the next dispatch, the consumer's wake.
LAG_TICKS = 2.8


def _replay(order, rate_per_s):
    """Milliseconds from due to the last chunk's tick, for the requests
    due in the window, under `order`: "ring" (the parent's round-robin)
    or "turns" (pick_prefill). The gaps are scaled to `rate_per_s`."""
    with open(TRAFFIC) as f:
        mix = json.load(f)
    reqs = traffic_gen.open_loop_schedule(mix, 0, WINDOW_S, vocab=8)
    scale = float(mix["arrivals"]["rate_per_s"]) / rate_per_s
    due = [r.due_s * scale for r in reqs]
    ring = []  # [request, chunks left]: admission order ("ring": the ring's)
    last, admitted, tick = {}, 0, 0
    turn = 0
    while admitted < len(reqs) or ring:
        now = tick * TICK_S
        while admitted < len(reqs) and due[admitted] <= now:
            n_chunks = -(-len(reqs[admitted].prompt) // CHUNK)
            ring.append([admitted, n_chunks])
            admitted += 1
        tick += 1
        if not ring:
            continue
        if order == "ring":
            entry = ring.pop(0)  # the head, then to the tail
            if entry[1] > 1:
                ring.append(entry)
        else:
            at, _ = pick_prefill([left for _, left in ring], turn)
            entry = ring[at]
            if entry[1] == 1:
                del ring[at]
        turn += 1
        entry[1] -= 1
        if not entry[1]:
            last[entry[0]] = now
    return sorted(1e3 * (last[i] - due[i])
                  for i, r in enumerate(reqs) if r.measured)


def _summary(ms):
    """What the cell reports of TTFT, from due -> last chunk + the lag."""
    ttft = [x + 1e3 * LAG_TICKS * TICK_S for x in ms]
    return {
        "mean": statistics.fmean(ttft),
        "p90": ttft[int(0.9 * (len(ttft) - 1))],
        "tail10": statistics.fmean(ttft[-(len(ttft) // 10):]),
        "worst": ttft[-1],
    }


def test_the_model_reproduces_the_ledger_under_the_parents_order():
    ms = _replay("ring", 16.2)
    assert len(ms) == 826
    # PR 39's stages on the chip: queue 5.3 + wait 66.5 + ride 371.6.
    assert statistics.fmean(ms) == pytest.approx(443.4, rel=0.05)
    assert statistics.fmean(ms) == pytest.approx(435.5, abs=0.5)
    # ... and the harness's ttft_mean_ms 456.6-458.7, p90 1,069-1,075.
    got = _summary(ms)
    assert got["mean"] == pytest.approx(457.5, abs=0.5)
    assert got["p90"] == pytest.approx(1075.0, rel=0.01)


@pytest.mark.parametrize("rate,ring_mean,turns_mean", [
    (16.2, 457.5, 295.5),   # the cell: 0.7 x the knee
    (23.1, 2012.0, 1104.0),  # the knee
])
def test_taking_turns_beats_the_ring_on_mean_and_tail(rate, ring_mean,
                                                      turns_mean):
    ring, turns = (_summary(_replay(order, rate))
                   for order in ("ring", "turns"))
    assert ring["mean"] == pytest.approx(ring_mean, rel=0.005)
    assert turns["mean"] == pytest.approx(turns_mean, rel=0.005)
    for name in ("mean", "p90", "tail10", "worst"):
        assert turns[name] < ring[name], name
    assert turns["mean"] <= 0.70 * ring["mean"]
