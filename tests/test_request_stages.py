"""A request's time to its first token by stage (ISSUE 39): lifecycle
stamps on one clock, the stage histograms and the turns-waited counter,
the request-scoped spans written from the stamps, and the flight
recorder's new fields. And the order in which admissions mid-prefill get
the tick's one chunk (ISSUE 40): by turns the oldest and the one with the
fewest chunks left, the pure pick and its counters. On the clocked fake
decoder of test_capture.py, stepped through the scheduler's split-step
API, so every expected number is exact."""

import json
import threading
import time
from types import SimpleNamespace

import pytest

from luminaai_tpu.monitoring import tracing
from luminaai_tpu.monitoring.events import FlightRecorder, format_event
from luminaai_tpu.monitoring.telemetry import MetricsRegistry
from luminaai_tpu.monitoring.tracing import SpanTracer
from luminaai_tpu.serving.server import (
    PICK_RULES,
    ContinuousScheduler,
    RequestTimeout,
    pick_prefill,
)
from tests.test_capture import (
    ClockedStepper,
    FakeClock,
    first_admission_held,
    until as _until,
)
from tests.test_serving import FakeEngine

QUEUE, WAIT, RIDE, LAG, PREFILL, TTFT, TURNS = (
    "serve_queue_wait_seconds", "serve_prefill_wait_seconds",
    "serve_prefill_ride_seconds", "serve_first_token_lag_seconds",
    "serve_prefill_seconds", "serve_ttft_seconds",
    "serve_prefill_turns_waited_total",
)
CHUNKS, PICKS, NOT_OLDEST = (
    "serving_prefill_chunks_total", "serve_prefill_picks_total",
    "serve_prefill_picks_not_oldest_total",
)
STAGE_SPANS = ("req.queued", "req.prefill_wait", "req.prefill_ride",
               "req.first_token", "req.decode")


class StageStepper(ClockedStepper):
    """ClockedStepper with the rest of the admission paths: a prompt of
    at most one chunk takes the whole-prompt path (unless `chunk_all`:
    a one-chunk admission is what a prefix hit leaves of a long
    prompt), a prompt identical to one mid-prefill parks behind it (the
    decoder's dedup `waiting`), and a test can hook a chunk."""

    WHOLE = 0.030

    def __init__(self, clock, **kw):
        super().__init__(clock, **kw)
        self.on_chunk = None  # callable(st), before a chunk runs
        self.chunk_all = False
        self._leaders = {}

    def start_prefill(self, slot, prompt, **kw):
        if len(prompt) <= self.prefill_chunk and not self.chunk_all:
            return None
        st = super().start_prefill(slot, prompt, **kw)
        leader = self._leaders.get(tuple(prompt))
        if leader is not None and "done" not in leader:
            st["waiting"], st["leader"] = True, leader
        else:
            self._leaders[tuple(prompt)] = st
        return st

    def prefill_ready(self, st):
        if st.get("waiting"):
            if "done" not in st["leader"]:
                return False
            del st["waiting"]
        return st["next"] < st["n_chunks"]

    def advance_prefill(self, st):
        if self.on_chunk is not None:
            self.on_chunk(st)
        info = super().advance_prefill(st)
        if info is not None:
            st["done"] = True
        return info

    def prefill_into_slot(self, slot, prompt, **kw):
        if len(prompt) <= self.prefill_chunk:
            self.clock.advance(self.WHOLE)  # a forward pass of its own
        return super().prefill_into_slot(slot, prompt, **kw)


class RealClock:
    """The scheduler's default clock behind ClockedStepper's interface:
    a phase takes real time, so wall and monotonic stamps can be laid
    side by side."""

    def __call__(self):
        return time.monotonic()

    def advance(self, s):
        time.sleep(s)


def _world(clock=None, tracer=None, num_slots=3, **kw):
    clock = clock or FakeClock()
    registry, recorder = MetricsRegistry(), FlightRecorder()
    stepper = StageStepper(clock, num_slots=num_slots)
    sched = ContinuousScheduler(
        FakeEngine(), decoder=stepper, registry=registry,
        recorder=recorder, clock=clock, tracer=tracer, **kw)
    return SimpleNamespace(sched=sched, stepper=stepper, clock=clock,
                           registry=registry, recorder=recorder)


def _prompt(first, chunks, tail=0):
    """`chunks` whole chunks (+ `tail` tokens) whose first id is `first`."""
    return [first] * (chunks * StageStepper.prefill_chunk + tail)


def _ask(w, prompt, **gen):
    """submit() on a thread of its own: (thread, outcome holder)."""
    out = {}

    def run():
        try:
            out["tokens"] = w.sched.submit(
                prompt, {"max_new_tokens": 2, **gen})[0]
        except Exception as e:  # the outcome of a deadline
            out["error"] = e

    th = threading.Thread(target=run, daemon=True)
    th.start()
    return th, out


def _settle(w):
    _until(w.sched.idle, "scheduler idle")


def _together(w, *chunks, first=100):
    """Admissions of these chunk counts in a known order
    (first_admission_held), all mid-prefill from the first chunk on:
    the ids of the ticks' chunks, in the order they rode."""
    order = []
    w.stepper.on_chunk = lambda st: order.append(st["prompt"][0])
    asked = []
    with first_admission_held(w.sched):
        for i, n in enumerate(chunks):
            asked.append(_ask(w, _prompt(first + 10 * i, n)))
            if i == 0:
                _until(lambda: w.stepper.pool.stats()["in_use"] == 1,
                       "the first acquired")
            else:
                _until(lambda: w.sched.queue_depth() == i, "queued in order")
    for th, _ in asked:
        th.join(20)
    _settle(w)
    w.stepper.on_chunk = None
    for i, (_, out) in enumerate(asked):
        assert out["tokens"] == [first + 10 * i, first + 10 * i + 1], out
    return order


def _order_of_picks(*chunks, turn=0):
    """The order in which admissions of these chunk counts (admission
    order, all runnable from the first tick, the scheduler's turn at
    `turn`) get the ticks' chunks, by index, and what the turns-waited
    counter must read for them: a tick adds the others still runnable."""
    left = {i: n for i, n in enumerate(chunks) if n}
    order, waited = [], 0
    while left:
        at, _ = pick_prefill(list(left.values()), turn)
        turn += 1
        i = list(left)[at]
        order.append(i)
        waited += len(left) - 1
        left[i] -= 1
        if not left[i]:
            del left[i]
    return order, waited


def _turns_of_ring(*chunks, turn=0):
    return _order_of_picks(*chunks, turn=turn)[1]


def _picks(w):
    fam = w.registry.get(PICKS)
    return {rule: fam.labels(rule=rule).value for rule in PICK_RULES}


def _sum(w, name):
    return w.registry.get(name).sum


def _count(w, name):
    return w.registry.get(name).count


# -- the scenarios the stages must add up in ----------------------------------
def _chunked(w):
    th, out = _ask(w, _prompt(10, 3, tail=1))  # 4 chunks
    th.join(20)
    assert out["tokens"] == [10, 11]
    return 1


def _whole_prompt(w):
    th, out = _ask(w, _prompt(20, 1))  # one chunk: no chunked admission
    th.join(20)
    assert out["tokens"] == [20, 21]
    return 1


def _dedup_follower(w):
    """B, identical to A, is admitted while A is mid-prefill and parks
    until A's first token is booked."""
    prompt = _prompt(30, 3)
    started = []

    def on_chunk(st):
        if not started:
            started.append(_ask(w, prompt))
            _until(lambda: w.sched.queue_depth() == 1, "B queued")

    w.stepper.on_chunk = on_chunk
    ta, oa = _ask(w, prompt)
    ta.join(20)
    tb, ob = started[0]
    tb.join(20)
    assert oa["tokens"] == ob["tokens"] == [30, 31]
    return 2


def _admitted_mid_prefill(w):
    """B arrives while A's second chunk runs and shares the ring with
    A's remaining chunks."""
    started = []

    def on_chunk(st):
        if st["next"] == 1 and not started:
            started.append(_ask(w, _prompt(60, 2)))
            _until(lambda: w.sched.queue_depth() == 1, "B queued")

    w.stepper.on_chunk = on_chunk
    ta, oa = _ask(w, _prompt(40, 4))
    ta.join(20)
    started[0][0].join(20)
    assert oa["tokens"] == [40, 41] and started[0][1]["tokens"] == [60, 61]
    return 2


@pytest.mark.parametrize("scenario", [
    _chunked, _whole_prompt, _dedup_follower, _admitted_mid_prefill,
], ids=lambda f: f.__name__.strip("_"))
def test_the_four_stages_sum_to_ttft_for_every_request(scenario):
    w = _world()
    n = scenario(w)
    _settle(w)
    for name in (QUEUE, WAIT, RIDE, LAG, PREFILL, TTFT):
        assert _count(w, name) == n, name
    parts = sum(_sum(w, name) for name in (WAIT, RIDE, LAG))
    assert parts == pytest.approx(_sum(w, PREFILL), abs=1e-9)
    assert _sum(w, QUEUE) + parts == pytest.approx(_sum(w, TTFT), abs=1e-9)
    assert _sum(w, TTFT) > 0
    # Per request: the events carry the same stages (4 decimals each).
    admitted = {e["request_id"]: e["queue_wait_s"]
                for e in w.recorder.snapshot(type="request_admitted")}
    firsts = w.recorder.snapshot(type="request_first_token")
    assert len(firsts) == n
    for e in firsts:
        stages = (admitted[e["request_id"]] + e["prefill_wait_s"]
                  + e["prefill_ride_s"] + e["first_token_lag_s"])
        assert stages == pytest.approx(e["ttft_s"], abs=2.5e-4)
        assert e["turns_waited"] >= 0 and e["chunks"] >= 0
        line = format_event(e)  # what `lumina events` prints
        for field in ("prefill_wait_s=", "prefill_ride_s=",
                      "first_token_lag_s=", "chunks=", "turns_waited="):
            assert field in line
    assert not w.sched._prefilling
    assert sum(_picks(w).values()) == w.registry.get(CHUNKS).value


def test_the_stages_of_a_chunked_prompt_are_what_the_decoder_spent():
    """Four chunks alone on the injected clock: the wait is the
    admission's Python and the first chunk's dispatch, the ride the
    other three (the fake's last chunk syncs inside its dispatch)."""
    w = _world()
    _chunked(w)
    _settle(w)
    s = w.stepper
    a_chunk = s.PUT + s.DISPATCH
    assert _sum(w, QUEUE) == pytest.approx(0.0, abs=1e-9)
    assert _sum(w, WAIT) == pytest.approx(s.ADMIT + a_chunk, abs=1e-9)
    assert _sum(w, RIDE) == pytest.approx(3 * a_chunk + s.WAIT, abs=1e-9)
    assert _sum(w, LAG) == pytest.approx(0.0, abs=1e-9)
    (e,) = w.recorder.snapshot(type="request_first_token")
    assert e["chunks"] == 4 and e["turns_waited"] == 0
    # A ring of one: both rules name it, by turns, and it is the oldest.
    assert _picks(w) == {"oldest": 2, "shortest": 2}
    assert w.registry.get(NOT_OLDEST).value == 0
    assert w.registry.get(TURNS).value == 0


def test_the_whole_prompt_path_is_all_lag():
    w = _world()
    _whole_prompt(w)
    _settle(w)
    assert _sum(w, WAIT) == _sum(w, RIDE) == 0.0
    assert _sum(w, LAG) == pytest.approx(w.stepper.WHOLE, abs=1e-9)
    assert _sum(w, PREFILL) == pytest.approx(w.stepper.WHOLE, abs=1e-9)
    (e,) = w.recorder.snapshot(type="request_first_token")
    assert e["chunks"] == 0 and e["turns_waited"] == 0
    assert w.registry.get(TURNS).value == 0
    assert _picks(w) == {"oldest": 0, "shortest": 0}  # no chunk, no pick
    assert w.sched._chunk_turn == 0


def test_a_parked_follower_waits_for_a_turn_and_is_not_counted():
    """A dedup follower is in `prefill_wait` until prefill_ready lets it
    run, and no tick counts it as an admission that lost a turn."""
    w = _world()
    _dedup_follower(w)
    _settle(w)
    assert w.registry.get(TURNS).value == 0
    firsts = sorted(w.recorder.snapshot(type="request_first_token"),
                    key=lambda e: e["seq"])
    leader, follower = firsts
    # The follower's wait covers the leader's remaining chunks.
    assert follower["prefill_wait_s"] > leader["prefill_ride_s"]
    assert follower["chunks"] == leader["chunks"] == 3


@pytest.mark.parametrize("a,b,turns,not_oldest", [
    # A is the oldest and never the longer: every chunk of A's first.
    (3, 2, 3, 0), (2, 5, 2, 0), (4, 4, 4, 0),
    # A, B, A, B, then A alone: B is the shorter on the odd turns.
    (5, 2, 4, 2),
])
def test_turns_waited_is_exact_for_two_interleaved_admissions(
        a, b, turns, not_oldest):
    w = _world()
    _together(w, a, b)
    assert _turns_of_ring(a, b) == turns
    assert w.registry.get(TURNS).value == turns
    assert w.registry.get(CHUNKS).value == a + b
    assert sum(_picks(w).values()) == a + b
    assert w.registry.get(NOT_OLDEST).value == not_oldest
    assert not w.sched._prefilling


# -- the order of service: by turns the oldest and the fewest left -------------
@pytest.mark.parametrize("remaining,turn,expected", [
    ([8, 1, 1], 0, (0, "oldest")),
    ([8, 1, 1], 1, (1, "shortest")),    # ties to the oldest of them
    ([8, 3, 1], 3, (2, "shortest")),
    ([2, 2], 1, (0, "shortest")),       # the oldest is as short as any
    ([1, 8], 2, (0, "oldest")),
    ([5], 0, (0, "oldest")),            # a ring of one: both name it
    ([5], 1, (0, "shortest")),
])
def test_pick_prefill_is_a_pure_function_of_the_ring_and_the_turn(
        remaining, turn, expected):
    before = list(remaining)
    assert pick_prefill(remaining, turn) == expected
    assert remaining == before


@pytest.mark.parametrize("turn", [0, 1])
def test_short_arrivals_every_tick_hold_the_oldest_to_twice_its_chunks(turn):
    """A one-chunk admission arrives with every tick: fewest-left-first
    alone would never reach the 6-chunk admission; by turns it has its
    last chunk within 12 chunk-carrying ticks, whichever rule starts
    (sooner here: its last chunk ties with the short ones, and a tie
    goes to the oldest)."""
    left, ticks = [6], 0
    while left[0]:
        at, _ = pick_prefill(left, turn + ticks)
        ticks += 1
        left[at] -= 1
        if at:
            del left[at]
        left.append(1)
    assert ticks == 10 - turn <= 12


@pytest.mark.parametrize("seed", range(8))
def test_no_admission_waits_past_twice_the_chunks_ahead_of_it(seed):
    """The bound the alternation buys, on random rings: from its
    admission, an admission has its last chunk within 2 x (the chunks
    left of the admissions ahead of it + its own) chunk-carrying ticks,
    whatever arrives behind it."""
    import random

    rng = random.Random(seed)
    ring, due_by, turn, tick = {}, {}, rng.randrange(2), -1
    while ring or tick < 400:
        tick += 1
        if tick < 400 and rng.random() < 0.15:
            name = len(due_by)
            ring[name] = rng.choice((1, 1, 2, 5, 12, 16))
            due_by[name] = tick + 2 * sum(ring.values())
        if not ring:
            continue  # an idle tick: no turn is used up
        at, _ = pick_prefill(list(ring.values()), turn)
        turn += 1
        name = list(ring)[at]
        ring[name] -= 1
        if not ring[name]:
            del ring[name]
            assert tick < due_by[name], (name, tick, due_by[name])
    assert len(due_by) > 40


def test_three_admissions_get_the_ticks_in_the_order_the_rule_says():
    """8 / 1 / 1 chunks, all mid-prefill from the first tick: A (the
    oldest), B (the shortest; ties to the older), A, C, then A alone.
    Round-robin gave A, B, C, A...: the same first three, but on 8 / 2
    / 2 it would have served B and C twice each before A's third."""
    tracer = SpanTracer(enabled=True)
    w = _world(tracer=tracer, num_slots=4)
    w.stepper.chunk_all = True
    order = _together(w, 8, 1, 1)
    a, b, c = 100, 110, 120
    assert order == [a, b, a, c] + [a] * 6
    assert order == [(a, b, c)[i] for i in _order_of_picks(8, 1, 1)[0]]
    assert _picks(w) == {"oldest": 5, "shortest": 5}
    assert w.registry.get(NOT_OLDEST).value == 2
    assert w.registry.get(TURNS).value == 2 + 2 + 1 + 1
    assert _turns_of_ring(8, 1, 1) == 6
    assert w.registry.get(CHUNKS).value == 10 == w.sched._chunk_turn
    # The span of the tick says which rule chose its chunk.
    picks = [s.attrs["chunk_pick"] for s in tracer.recent("decode_step")
             if "chunk_pick" in s.attrs]
    assert picks == ["oldest", "shortest"] * 5


def test_a_short_admission_arriving_every_tick_does_not_starve_the_oldest():
    """Through the scheduler: while A (6 chunks) prefills, every tick
    that carries a chunk brings a new one-chunk admission. A has its
    last chunk on the 10th chunk-carrying tick (at most 12 by the
    bound; its last chunk ties with the short ones and a tie goes to
    the oldest)."""
    w = _world(num_slots=16)
    w.stepper.chunk_all = True
    order, asked = [], []

    def on_chunk(st):
        order.append(st["prompt"][0])
        if len(asked) < 14:
            queued = w.sched.queue_depth()  # nobody admits meanwhile
            asked.append(_ask(w, _prompt(10 + len(asked), 1)))
            _until(lambda: w.sched.queue_depth() == queued + 1,
                   "a short one queued")

    w.stepper.on_chunk = on_chunk
    th, out = _ask(w, _prompt(200, 6))
    th.join(20)
    for t, _ in asked:
        t.join(20)
    _settle(w)
    assert out["tokens"] == [200, 201]
    mine = [i for i, first in enumerate(order) if first == 200]
    assert mine == [0, 2, 4, 6, 8, 9]
    assert len(order) == 6 + 14
    for n, (_, o) in enumerate(asked):
        assert o["tokens"] == [10 + n, 11 + n]
    picks = _picks(w)
    assert sum(picks.values()) == w.registry.get(CHUNKS).value == 20
    # While A was the oldest, every odd turn went past it.
    assert w.registry.get(NOT_OLDEST).value == 4


def test_a_parked_follower_takes_no_pick_and_uses_up_no_turn():
    """L (5 chunks), F identical to L and parked behind it, C (2
    chunks), admitted in that order. F is older than C and gets
    nothing while parked: L, C, L, C, L, L, L, then F alone. The turns
    waited count L and C alone, and the turn stands at the chunks
    dispatched."""
    w = _world(num_slots=4)
    order = []
    w.stepper.on_chunk = lambda st: order.append(
        (st["prompt"][0], st["slot"]))
    leader = _prompt(30, 5)
    with first_admission_held(w.sched):
        asked = [_ask(w, leader)]
        _until(lambda: w.stepper.pool.stats()["in_use"] == 1, "L acquired")
        asked.append(_ask(w, leader))
        _until(lambda: w.sched.queue_depth() == 1, "F queued")
        asked.append(_ask(w, _prompt(60, 2)))
        _until(lambda: w.sched.queue_depth() == 2, "C queued")
    for th, _ in asked:
        th.join(20)
    _settle(w)
    assert [o["tokens"] for _, o in asked] == [[30, 31], [30, 31], [60, 61]]
    (l_slot, f_slot) = dict.fromkeys(s for first, s in order if first == 30)
    by = {l_slot: "L", f_slot: "F"}
    assert [by.get(s, "C") for _, s in order] == (
        list("LCLCLLL") + ["F"] * 5)
    assert w.registry.get(TURNS).value == 4  # L and C, four ticks
    assert w.registry.get(NOT_OLDEST).value == 2  # C's two, past L
    assert w.sched._chunk_turn == 12 == w.registry.get(CHUNKS).value
    assert _picks(w) == {"oldest": 6, "shortest": 6}


def test_a_tick_without_a_chunk_does_not_shift_the_phase():
    """A prompt of three chunks leaves the turn odd; the decode ticks
    that follow carry no chunk and leave it there, so the next two
    admissions start on the fewest-left turn: B, A, B, then A alone
    (from an even turn it is A, B, A, B)."""
    w = _world()
    th, out = _ask(w, _prompt(10, 3), max_new_tokens=6)
    th.join(20)
    _settle(w)
    assert out["tokens"] == [10, 11, 12, 13, 14, 15]
    assert w.stepper.steps >= 5  # ticks that carried no chunk
    assert w.sched._chunk_turn == 3
    order = _together(w, 5, 2, first=100)
    assert order == [110, 100, 110, 100, 100, 100, 100]
    assert _order_of_picks(5, 2, turn=3) == ([1, 0, 1, 0, 0, 0, 0], 3)
    assert _order_of_picks(5, 2, turn=0) == ([0, 1, 0, 1, 0, 0, 0], 4)
    assert w.registry.get(TURNS).value == 3
    assert w.registry.get(NOT_OLDEST).value == 2
    assert _picks(w) == {"oldest": 5, "shortest": 5}


@pytest.mark.parametrize("how", ["cancel", "deadline"])
def test_a_request_ended_mid_prefill_observes_no_stage(how):
    """Ended on its second chunk: the stage it was in closes, nothing
    is observed for it, and the runnable count is right afterwards: two
    more admissions read the exact number of turns."""
    tracer = SpanTracer(enabled=True)
    w = _world(tracer=tracer)

    def on_chunk(st):
        if st["next"] == 1:
            ((req, _),) = w.sched._prefilling.values()
            if how == "cancel":
                req.cancelled = True
            else:
                req.deadline = time.time() - 1.0
            w.stepper.on_chunk = None

    w.stepper.on_chunk = on_chunk
    th, out = _ask(w, _prompt(70, 4))
    th.join(20)
    _settle(w)
    if how == "cancel":
        assert out["tokens"] == []
    else:
        assert isinstance(out["error"], RequestTimeout)
    assert _count(w, QUEUE) == 1  # observed at admission
    for name in (WAIT, RIDE, LAG, PREFILL, TTFT):
        assert _count(w, name) == 0, name
    assert not w.recorder.snapshot(type="request_first_token")
    assert not w.sched._prefilling
    # Its spans stop at the stage it was in: two chunks had ridden.
    (root,) = tracer.recent("request")
    assert root.attrs["stopped"] == (
        "cancelled" if how == "cancel" else "timeout")
    mine = [s for s in tracer.recent() if s.trace_id == root.trace_id]
    assert [s.name for s in mine] == [
        "request", "req.queued", "req.prefill_wait", "req.prefill_ride"]
    ride = mine[-1]
    assert ride.attrs["chunks"] == 2
    assert ride.t0 + ride.duration_s == pytest.approx(
        root.t0 + root.duration_s, abs=1e-6)
    assert w.sched._chunk_turn == 2  # its two chunks, and no more
    _together(w, 5, 2, first=120)
    assert w.registry.get(TURNS).value == _turns_of_ring(5, 2, turn=2) == 4
    assert not w.sched._prefilling


def test_tracer_off_makes_no_span_and_takes_no_lock(monkeypatch):
    """Off, all of it is one attribute check: no Span is built, record()
    is not called, the tracer's lock is not taken."""
    w = _world()
    tracer = w.sched.tracer
    assert not tracer.enabled
    null = tracing.NULL_TRACER.span("x")
    assert tracer.span("decode_step") is null
    assert tracer.record("request", 0.0, 1.0) is None

    def boom(*a, **k):
        raise AssertionError("a span was made with the tracer off")

    class NoLock:
        __enter__ = __exit__ = boom

    monkeypatch.setattr(tracing.Span, "__init__", boom)
    monkeypatch.setattr(tracer, "record", boom)
    monkeypatch.setattr(tracer, "_write_lock", NoLock())
    _together(w, 3, 2)
    _whole_prompt(w)
    _settle(w)
    assert _count(w, TTFT) == 3 and tracer.spans_recorded == 0


def test_telemetry_off_observes_nothing_and_still_stamps():
    w = _world(telemetry=False, tracer=SpanTracer(enabled=True))
    _chunked(w)
    _settle(w)
    for name in (QUEUE, WAIT, RIDE, LAG, PREFILL, TTFT):
        assert _count(w, name) == 0, name
    assert w.registry.get(TURNS).value == 0
    (ride,) = w.sched.tracer.recent("req.prefill_ride")
    assert ride.attrs["chunks"] == 4


def test_a_capture_switched_on_mid_request_has_the_true_starts(tmp_path):
    """`--trace 2`'s switch: the tracer goes on while a request is on
    its second chunk. Its spans are written when it ends, from the
    stamps: they start before the capture does, share one trace id, lie
    inside their root end to end, and their `ts` is wall time."""
    path = tmp_path / "spans.jsonl"
    tracer = SpanTracer(jsonl_path=str(path), enabled=False)
    w = _world(clock=RealClock(), tracer=tracer)
    seen = {}

    def on_chunk(st):
        if st["next"] == 1 and not seen:
            ((req, _),) = w.sched._prefilling.values()
            seen["req"] = req
            assert tracer.start_capture(str(tmp_path / "trace"))

    w.stepper.on_chunk = on_chunk
    try:
        th, out = _ask(w, _prompt(90, 4), max_new_tokens=3)
        th.join(30)
        _settle(w)
    finally:
        tracer.stop_capture()
    assert out["tokens"] == [90, 91, 92]
    lines = [json.loads(x) for x in path.read_text().splitlines()]
    by_name = {}
    for line in lines:
        by_name.setdefault(line["name"], []).append(line)
    (clock_mark,) = by_name["capture_clock"]
    (root,) = by_name["request"]
    req = seen["req"]
    assert root["attrs"]["request_id"] == req.request_id
    assert root["parent"] is None and root["ts"] < clock_mark["ts"]
    assert abs(root["ts"] - req.t0) < 1e-3
    kids = [by_name[name][0] for name in STAGE_SPANS]
    assert all(len(by_name[name]) == 1 for name in STAGE_SPANS)
    end = root["ts"] + root["duration_s"]
    at = root["ts"]
    for kid in kids:
        assert kid["trace"] == root["trace"]
        assert kid["parent"] == root["span"]
        assert abs(kid["ts"] - at) < 1e-5  # each starts where the last ended
        at = kid["ts"] + kid["duration_s"]
    assert abs(at - end) < 1e-5
    wait = by_name["req.prefill_wait"][0]
    assert abs(wait["ts"] - (req.t0 + req.t_admit - req.t_submit)) < 1e-3
    assert wait["ts"] < clock_mark["ts"]  # a stage over before the switch
    ride = by_name["req.prefill_ride"][0]
    assert ride["attrs"]["chunks"] == 4
    # The ticks that did its work, by its id: those after the switch.
    carried = [s for s in by_name["decode_step"]
               if s.get("attrs", {}).get("chunk_request_id")
               == req.request_id]
    assert len(carried) == 2
    assert all(s["trace"] != root["trace"] for s in carried)
    assert "prefill_chunk" not in by_name
    tracer.close()
