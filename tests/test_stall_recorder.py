"""A stalled tick is booked with its cause (monitoring/watchdog.py:
ProcessPauses, TickStalls, stall_cause; serving/server.py::_step).

Pauses are hand-fed wherever a real one would make a test slow or
unsteady: the heartbeat's `_beat(dt, now)` and the collector hook's
`_on_gc(phase, info)` are called directly on an instance that has no
thread and no hook, over an injected clock. The tests that start the
process-wide instance close what they start, so no test after this
file inherits the hook; what this file inherits (other files' schedulers
are never closed) `nothing_inherited` takes down first."""

import gc
import json
import threading
import time

import pytest

from benchmark import common, layer_readers, manifest
from luminaai_tpu.monitoring import telemetry, tracing
from luminaai_tpu.monitoring.events import FlightRecorder
from luminaai_tpu.monitoring.goodput import (
    SERVE_TICK_PHASES,
    ThreadPhaseLedger,
)
from luminaai_tpu.monitoring.telemetry import MetricsRegistry
from luminaai_tpu.monitoring.tracing import SpanTracer
from luminaai_tpu.monitoring.watchdog import (
    STALL_CAUSES,
    ProcessPauses,
    RobustStats,
    StepTimeSentinel,
    TickStalls,
    stall_cause,
)
from luminaai_tpu.serving.server import ContinuousScheduler
from tests.test_serving import FakeEngine

NEW_READERS = {
    "tick_stall_pct": "serve_tick_stall_seconds_total",
    "tick_stall_gc_pct": "serve_tick_stall_gc_seconds_total",
    "tick_stall_process_pct": "serve_tick_stall_process_seconds_total",
    "tick_stall_device_pct": "serve_tick_stall_device_seconds_total",
    "gc_pause_pct": "process_gc_pause_seconds_total",
    "process_pause_pct": "process_pause_seconds_total",
}


class Clock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


class FedPauses:
    """ProcessPauses' two reads, set by hand."""

    def __init__(self):
        self.gc_seconds = 0.0
        self.pause_seconds = 0.0

    def gc_seconds_now(self):
        return self.gc_seconds

    def pause_seconds_now(self):
        return self.pause_seconds


def fed_stalls(registry=None):
    """TickStalls over a phase ledger on an injected clock and hand-fed
    pauses: (stalls, registry, clock, phases, pauses)."""
    registry = registry or MetricsRegistry()
    clock, pauses = Clock(), FedPauses()
    phases = ThreadPhaseLedger(
        SERVE_TICK_PHASES, "serve_tick_{cause}_seconds_total",
        registry=registry, clock=clock,
    )
    phases.start("sched")
    stalls = TickStalls(registry, phases, pauses)
    return stalls, registry, clock, phases, pauses


def spend(clock, phases, **seconds):
    """The owning thread spends `seconds` in each named phase and is
    back in `sched`."""
    for phase, s in seconds.items():
        phases.switch(phase)
        clock.t += s
    phases.switch("sched")


def counters(registry):
    return {k[len("counter:"):]: v
            for k, v in layer_readers.registry_view(registry).items()
            if k.startswith("counter:")}


def beat(pauses, dt):
    """One hand-fed wake of the heartbeat, `dt` seconds after the last,
    at the instant the instance's clock shows."""
    pauses._beat(dt, pauses._clock())


def heartbeats():
    return [t for t in threading.enumerate() if t.name == "process-heartbeat"]


def gc_hooks():
    return [cb for cb in gc.callbacks
            if getattr(cb, "__func__", None) is ProcessPauses._on_gc]


@pytest.fixture(autouse=True)
def nothing_inherited():
    """A scheduler that an earlier file of this worker made and never
    closed keeps the process-wide instance alive, with its hook, its
    thread and its tracers: each test here starts from none."""
    ProcessPauses.shutdown()
    assert not gc_hooks() and not heartbeats()


# -- the rule -----------------------------------------------------------
@pytest.mark.parametrize("gc_s, pause_s, phases, want", [
    # the collector took at least half the excess, whatever else did
    (0.5, 0.0, {"device_wait": 1.0, "sched": 0.1}, "gc"),
    (0.6, 0.9, {"sched": 1.0, "device_wait": 0.1}, "gc"),
    # the heartbeat was that late and the collector was not at work
    (0.49, 0.5, {"device_wait": 1.0, "sched": 0.1}, "process"),
    (0.0, 1.0, {"sched": 1.0, "device_wait": 0.1}, "process"),
    # neither, and the interval was spent blocked on the device
    (0.1, 0.49, {"device_wait": 1.0, "sched": 0.1, "put": 0.01}, "device"),
    # neither, and it was the thread's own Python
    (0.0, 0.0, {"device_wait": 0.1, "sched": 1.0}, "host"),
    (0.0, 0.02, {"device_wait": 0.1, "put": 0.9, "sched": 0.1}, "host"),
])
def test_cause_is_chosen_by_the_rule(gc_s, pause_s, phases, want):
    cause, phase = stall_cause(1.0, gc_s, pause_s, phases)
    assert cause == want
    assert phase == max(phases, key=phases.get)


def test_the_four_causes_add_up_to_the_total():
    stalls, registry, clock, phases, pauses = fed_stalls()
    fed = [  # excess, collector, lateness, where the thread was
        (0.18, 0.17, 0.17, {"sched": 0.19}),
        (1.02, 0.0, 1.0, {"device_wait": 1.03}),
        (2.7, 0.001, 0.0, {"device_wait": 2.71, "sched": 0.002}),
        (0.11, 0.0, 0.0, {"sched": 0.1, "device_wait": 0.02}),
        (0.3, 0.2, 0.0, {"put": 0.31}),
        (0.05, 0.0, 0.03, {"dispatch": 0.06}),
    ]
    booked = []
    for excess, gc_s, late_s, where in fed:
        spend(clock, phases, **where)
        pauses.gc_seconds += gc_s
        pauses.pause_seconds += late_s
        booked.append(stalls.book(excess))
        stalls.mark()
    assert [b["stall_cause"] for b in booked] == [
        "gc", "process", "device", "host", "gc", "process"]
    assert [b["stall_phase"] for b in booked] == [
        "sched", "device_wait", "device_wait", "sched", "put", "dispatch"]
    got = counters(registry)
    by_cause = {c: got[f"serve_tick_stall_{c}_seconds_total"]
                for c in STALL_CAUSES}
    assert by_cause == pytest.approx(
        {"gc": 0.48, "process": 1.07, "device": 2.7, "host": 0.11})
    assert sum(by_cause.values()) == pytest.approx(
        got["serve_tick_stall_seconds_total"])
    assert got["serve_tick_stalls_total"] == len(fed)
    # What the event carries is the interval's own, not the run's.
    assert booked[2]["gc_s"] == pytest.approx(0.001)
    assert booked[2]["device_wait_s"] == pytest.approx(2.71)
    assert booked[3]["pause_s"] == 0.0
    assert set(booked[0]) == {
        "stall_s", "stall_cause", "stall_phase", "gc_s", "pause_s",
        *(f"{p}_s" for p in SERVE_TICK_PHASES)}


def test_a_pause_inside_the_sentinels_own_time_is_the_next_ticks():
    """The collect reads the measurements once, beside its clock: what
    happens after that reading (a pause while the sentinel sorts) is in
    the NEXT tick's seconds, so it has to be in the next tick's
    measurements too, not lost between a late mark and the clock."""
    stalls, registry, clock, phases, pauses = fed_stalls()
    spend(clock, phases, device_wait=0.010)
    here = stalls.read()             # tick N's collect
    clock.t += 0.11                  # the process stands still ...
    pauses.pause_seconds += 0.11     # ... while tick N is being judged
    stalls.mark(here)
    spend(clock, phases, device_wait=0.008)
    booked = stalls.book(0.11, stalls.read())   # tick N + 1: 0.118 s
    assert booked["stall_cause"] == "process"
    assert booked["pause_s"] == pytest.approx(0.11)
    assert sum(booked[f"{p}_s"] for p in SERVE_TICK_PHASES) == (
        pytest.approx(0.118))


def test_clean_ticks_book_nothing_and_call_no_metric(monkeypatch):
    stalls, registry, clock, phases, pauses = fed_stalls()
    sentinel = StepTimeSentinel(prefix="serve_decode_step_seconds",
                                program="serve")
    calls = []
    monkeypatch.setattr(
        telemetry.Counter, "inc",
        lambda self, amount=1.0: calls.append(amount))
    for i in range(200):
        dt = 0.010 + 0.0002 * (i % 5)
        spend(clock, phases, device_wait=dt - 0.002, sched=0.002)
        pauses.gc_seconds += 0.0004  # short passes every tick flag nothing
        assert not sentinel.observe(dt, explain=stalls.book)
        stalls.mark()
    assert calls == []
    monkeypatch.undo()
    got = counters(registry)
    assert all(got[f"serve_tick_stall_{c}_seconds_total"] == 0
               for c in STALL_CAUSES)
    # The same sentinel, one tick held: the excess over the median.
    spend(clock, phases, device_wait=2.0)
    assert sentinel.observe(2.0, explain=stalls.book)
    got = counters(registry)
    assert got["serve_tick_stall_device_seconds_total"] == pytest.approx(
        2.0 - 0.0104, abs=1e-3)
    assert got["serve_tick_stall_seconds_total"] == (
        got["serve_tick_stall_device_seconds_total"])


def test_a_booking_that_raises_leaves_the_flag_the_count_and_the_event():
    """`explain` is the caller's: the scheduler calls the sentinel inside
    the `try` whose handler fails every lane, so a fault in the booking
    must stay in the sentinel."""
    registry, recorder = MetricsRegistry(), FlightRecorder()
    sentinel = StepTimeSentinel(registry=registry, recorder=recorder,
                                prefix="serve_decode_step_seconds",
                                program="serve")
    for i in range(100):
        assert not sentinel.observe(0.010 + 0.0002 * (i % 5))

    def explain(excess_s):
        raise KeyError("a phase the ledger does not know")

    assert sentinel.observe(2.0, step=7, explain=explain)
    assert registry.snapshot()["step_time_anomalies_total"][
        "program=serve"] == 1
    (event,) = recorder.snapshot(type="step_anomaly")
    assert event["seconds"] == 2.0 and event["step"] == 7
    assert "stall_cause" not in event


def test_sentinel_sorts_the_window_twice_an_observation(monkeypatch):
    registry = MetricsRegistry()
    sentinel = StepTimeSentinel(registry=registry, prefix="x_seconds",
                                program="serve")
    sorts = []
    from luminaai_tpu.monitoring import watchdog

    real = watchdog._median
    monkeypatch.setattr(watchdog, "_median",
                        lambda values: sorts.append(1) or real(values))
    window = RobustStats(64)
    for i in range(100):
        x = 0.01 + 0.001 * ((7 * i) % 11)
        sentinel.observe(x)
        window.add(x)
    assert len(sorts) == 2 * 100
    monkeypatch.undo()
    snap = registry.snapshot()
    # The gauges hold the window as it stands, newest value included.
    assert snap["x_seconds_median"] == window.median()
    assert snap["x_seconds_mad"] == window.mad()


# -- the process-wide measurements, hand-fed -----------------------------
def test_heartbeat_books_lateness_beyond_twenty_ms_in_full():
    registry = MetricsRegistry()
    pauses = ProcessPauses(clock=Clock())
    pauses.attach(registry)
    due = ProcessPauses.interval_s
    on_time = (due, due + 0.0001, due + 0.019, due + 0.0199)  # or nearly
    for dt in on_time:
        beat(pauses, dt)
    assert pauses.pause_seconds == 0.0
    beat(pauses, due + 1.0)     # stopped for a second
    beat(pauses, due + 0.040)   # a 40 ms hiccup
    assert pauses.pause_seconds == pytest.approx(1.0 + 0.040)
    got = counters(registry)
    assert got["process_pause_seconds_total"] == pytest.approx(1.040)
    assert got["process_wall_seconds_total"] == pytest.approx(
        sum(on_time) + 2 * due + 1.040)
    assert got["process_gc_pause_seconds_total"] == 0.0


def test_a_reader_that_runs_before_the_heartbeat_sees_the_pause():
    """After a pause both threads are due; the tick that books first
    must not find the heartbeat's lateness missing, nor find it twice
    once the heartbeat has run."""
    clock = Clock()
    pauses = ProcessPauses(clock=clock)
    due = ProcessPauses.interval_s
    assert pauses.pause_seconds_now() == 0.0  # no thread, no wake due
    pauses._beat_at = (clock.t, 0.0)          # as _run() leaves it
    clock.t += due + 0.015                    # a wake 15 ms overdue
    assert pauses.pause_seconds_now() == 0.0
    clock.t += 1.0                            # stopped for a second
    assert pauses.pause_seconds_now() == pytest.approx(1.015)
    pauses._beat(due + 1.015, clock.t)    # the heartbeat's turn
    assert pauses.pause_seconds == pytest.approx(1.015)
    assert pauses.pause_seconds_now() == pytest.approx(1.015)


def test_a_wake_and_its_lateness_are_published_together(monkeypatch):
    """`pause_seconds_now()` adds an overdue wake's lateness to the
    pauses booked so far: a reader that saw the new wake's time beside
    the old total would miss the pause, one that saw the new total
    beside the old wake would count it twice. Every state a reader can
    see while `_beat` runs gives the pause once."""
    clock = Clock()
    pauses = ProcessPauses(clock=clock)
    due = ProcessPauses.interval_s
    pauses._beat_at = (clock.t, 0.0)
    clock.t += due + 1.0
    seen = []

    class Watched(ProcessPauses):
        def __setattr__(self, name, value):
            object.__setattr__(self, name, value)
            seen.append((name, self.pause_seconds_now()))

    pauses.__class__ = Watched
    pauses._beat(due + 1.0, clock.t)
    assert {name for name, _ in seen} >= {"pause_seconds", "_beat_at"}
    assert [now for _, now in seen] == [pytest.approx(1.0)] * len(seen)


def test_a_reader_that_runs_before_the_stop_hook_sees_the_pass():
    """The collector's `stop` callback is Python: the interpreter may
    hand the turn to the thread the pass held up as it enters the hook,
    and that thread books its tick before the pass is added. It finds
    the pass open and counts it from its start; once the hook has run
    the pass is counted once."""
    clock = Clock()
    pauses = ProcessPauses(clock=clock)
    pauses._on_gc("start", {"generation": 2})
    clock.t += 0.34
    assert pauses.gc_seconds == 0.0
    assert pauses.gc_seconds_now() == pytest.approx(0.34)
    registry = MetricsRegistry()
    phases = ThreadPhaseLedger(
        SERVE_TICK_PHASES, "serve_tick_{cause}_seconds_total",
        registry=registry, clock=clock)
    phases.start("device_wait")
    stalls = TickStalls(registry, phases, pauses)
    stalls._at = (stalls._at[0], 0.0, 0.0)  # marked before the pass
    clock.t += 0.001
    booked = stalls.book(0.33)  # the held-up thread's turn comes first
    assert booked["stall_cause"] == "gc"
    assert booked["gc_s"] == pytest.approx(0.341)
    stalls.mark()
    clock.t += 0.002
    pauses._on_gc("stop", {"generation": 2, "collected": 0})
    assert pauses.gc_seconds == pytest.approx(0.343)
    assert pauses.gc_seconds_now() == pytest.approx(0.343)
    # the next tick finds what ran on after its mark, not the pass again
    assert stalls.book(0.2)["gc_s"] == pytest.approx(0.002)


def test_a_late_wake_is_written_as_a_back_dated_span():
    """A stopped process has no live Python span over its gap: the
    heartbeat writes one when it wakes, on the wall clock, ending at the
    wake. Off, the tracer is asked nothing."""
    clock, wall = Clock(), Clock()
    wall.t = 1_700_000_000.0
    tracer, off = SpanTracer(enabled=True), SpanTracer(enabled=False)
    pauses = ProcessPauses(clock=clock, wall=wall)
    pauses.attach(MetricsRegistry(), tracer)
    pauses.attach(None, off)
    due = ProcessPauses.interval_s
    beat(pauses, due + 0.019)  # on time
    assert tracer.recent("process.pause") == []
    wall.t += 7.0
    beat(pauses, due + 1.1)    # stood still for 1.1 s
    (span,) = tracer.recent("process.pause")
    assert span.duration_s == pytest.approx(1.1)
    assert span.t0 == pytest.approx(wall.t - 1.1)
    assert span.parent_id is None
    assert off.recent() == [] and off.spans_recorded == 0
    tracer.enabled = False     # a capture that ended
    beat(pauses, due + 0.5)
    assert len(tracer.recent("process.pause")) == 1
    assert pauses.pause_seconds == pytest.approx(1.6)


def test_the_change_reads_no_file_of_the_machine():
    """The chip machine is a gVisor sandbox without `cpu.stat`,
    `schedstat` or a pressure file (PERF.md section 7, From PRs 59-61):
    nothing here reads `/proc` or `/sys`, and a `process` tick's event
    carries what the process itself measured and no more."""
    import inspect

    from luminaai_tpu.monitoring import watchdog

    for piece in (ProcessPauses, TickStalls, stall_cause,
                  watchdog._PauseSink, watchdog._profiler_annotation):
        source = inspect.getsource(piece)
        assert "/proc" not in source and "/sys" not in source
        assert "open(" not in source
    assert not hasattr(watchdog, "cpu_pressure")
    stalls, registry, clock, phases, pauses = fed_stalls()
    spend(clock, phases, device_wait=0.12)
    pauses.pause_seconds += 0.11
    booked = stalls.book(0.11)
    assert booked["stall_cause"] == "process"
    assert "throttled_s" not in booked and "run_delay_s" not in booked


def test_collector_hook_adds_every_pass_to_one_float(monkeypatch):
    registry, clock = MetricsRegistry(), Clock()
    pauses = ProcessPauses(clock=clock)
    pauses.attach(registry)
    pauses._on_gc("stop", {"generation": 2, "collected": 0})  # no start
    calls = []
    monkeypatch.setattr(
        telemetry.Counter, "inc",
        lambda self, amount=1.0: calls.append(amount))
    for gen, seconds in ((0, 0.0004), (2, 0.18), (0, 0.0003), (1, 0.002)):
        pauses._on_gc("start", {"generation": gen})
        clock.t += seconds
        pauses._on_gc("stop", {"generation": gen, "collected": 5})
    assert calls == []  # the hook calls no metric
    monkeypatch.undo()
    assert pauses.gc_seconds == pytest.approx(0.1827)
    assert counters(registry)["process_gc_pause_seconds_total"] == 0.0
    beat(pauses, 0.010)  # the heartbeat publishes, not the hook
    assert counters(registry)[
        "process_gc_pause_seconds_total"] == pytest.approx(0.1827)
    # Nothing read a count of passes by generation (PR 59 had one): the
    # count rides the gc.collect span.
    assert registry.get("process_gc_collections_total") is None


def test_a_registry_that_joins_late_counts_from_there():
    first, second = MetricsRegistry(), MetricsRegistry()
    pauses = ProcessPauses(clock=Clock())
    pauses.attach(first)
    pauses.attach(first)  # a trainer and a scheduler on one registry
    for _ in range(100):
        beat(pauses, 0.010)
    pauses.attach(second)
    for _ in range(50):
        beat(pauses, 0.010)
    assert counters(first)["process_wall_seconds_total"] == pytest.approx(1.5)
    assert counters(second)["process_wall_seconds_total"] == pytest.approx(0.5)
    pauses.detach(first)
    beat(pauses, 0.010)  # one of first's two starters is still there
    assert counters(first)["process_wall_seconds_total"] == pytest.approx(1.51)
    pauses.detach(first)
    beat(pauses, 0.010)
    assert counters(first)["process_wall_seconds_total"] == pytest.approx(1.51)
    assert counters(second)["process_wall_seconds_total"] == pytest.approx(0.52)


# -- the process-wide instance, for real ---------------------------------
def wait_for(what, seconds=5.0):
    deadline = time.monotonic() + seconds
    while not what() and time.monotonic() < deadline:
        time.sleep(0.01)
    return what()


def test_a_real_collection_grows_the_counter_and_writes_one_span():
    registry = MetricsRegistry()
    tracer = SpanTracer(enabled=True)
    graph = [[] for _ in range(20000)]
    for i, node in enumerate(graph):  # cycles: the pass has work
        node.append(graph[i - 1])
    gc.disable()  # so that the one pass below is the only one hooked
    pauses = ProcessPauses.start(registry, tracer)
    try:
        del graph, node
        before = time.time()
        gc.collect()
        # The heartbeat publishes the counters and writes the span, in
        # that order: a pass that ends between the two is published by
        # the next wake.
        assert wait_for(lambda: tracer.recent("gc.collect") and counters(
            registry)["process_gc_pause_seconds_total"] > 0)
    finally:
        pauses.close(registry, tracer)
        gc.enable()
    got = counters(registry)
    assert got["process_gc_pause_seconds_total"] > 0
    assert got["process_wall_seconds_total"] > 0
    spans = tracer.recent("gc.collect")
    assert len(spans) == 1
    assert spans[0].attrs["generation"] == 2
    assert spans[0].attrs["collected"] >= 20000
    assert spans[0].duration_s == pytest.approx(
        got["process_gc_pause_seconds_total"], rel=0.5)
    assert before <= spans[0].t0 <= before + spans[0].duration_s + 1.0


def test_a_pass_that_starts_inside_the_tracer_does_not_wait_for_it(
        tmp_path, monkeypatch):
    """`SpanTracer._record` allocates under its write lock when it has
    a JSONL sink, so a pass can start on a thread that holds the lock:
    the hook must not ask for it (a non-reentrant lock: the thread
    would wait for itself for ever)."""
    registry = MetricsRegistry()
    tracer = SpanTracer(jsonl_path=str(tmp_path / "spans.jsonl"))
    to_dict = tracing.Span.to_dict

    def collecting(span):
        if span.name == "decode.pack":
            gc.collect()  # as an allocation inside json.dumps may
        return to_dict(span)

    monkeypatch.setattr(tracing.Span, "to_dict", collecting)
    pauses = ProcessPauses.start(registry, tracer)

    def one_span():
        with tracer.span("decode.pack"):
            pass

    worker = threading.Thread(target=one_span, daemon=True)
    try:
        worker.start()
        worker.join(timeout=10.0)
        assert not worker.is_alive()
        assert wait_for(lambda: tracer.recent("gc.collect"))
    finally:
        if worker.is_alive():  # let the stuck thread go, then fail above
            tracer._write_lock.release()
        pauses.close(registry, tracer)
    tracer.flush()
    names = [json.loads(line)["name"]
             for line in open(tmp_path / "spans.jsonl")]
    assert names.count("decode.pack") == 1
    assert names.count("gc.collect") >= 1


def test_a_pass_is_stamped_while_another_thread_holds_the_tracers_lock():
    """The hook runs at every pass on whatever thread allocates: with
    the tracer's write lock held elsewhere it still returns at once, and
    the span is written (one, by the heartbeat) when the lock is free."""
    registry = MetricsRegistry()
    tracer = SpanTracer(enabled=True)
    gc.disable()
    pauses = ProcessPauses.start(registry, tracer)
    held, release = threading.Event(), threading.Event()

    def hold():
        with tracer._write_lock:
            held.set()
            release.wait(10.0)

    holder = threading.Thread(target=hold, daemon=True)
    holder.start()
    try:
        assert held.wait(5.0)
        t0 = time.monotonic()
        gc.collect()  # the hook's start and stop, on this thread
        assert time.monotonic() - t0 < 2.0
        assert pauses.gc_seconds > 0
        # stamped: it is the heartbeat that waits for the lock (its next
        # wake stands at the write until the holder lets go), never the
        # thread the collector ran on
        assert len(pauses._gc_done) == 1 or wait_for(
            lambda: not pauses._gc_done)
        assert tracer.spans_recorded == 0
        release.set()
        holder.join(5.0)
        assert wait_for(lambda: tracer.recent("gc.collect") and counters(
            registry)["process_gc_pause_seconds_total"] > 0)
    finally:
        release.set()
        pauses.close(registry, tracer)
        gc.enable()
    assert len(tracer.recent("gc.collect")) == 1


def test_the_heartbeat_outlives_a_tracer_that_raises(monkeypatch):
    """The thread is the process's clock for every starter: one whose
    tracer raises (a sink gone, a patched Span) does not end it."""
    registry = MetricsRegistry()
    tracer = SpanTracer(enabled=True)

    def boom(*a, **kw):
        raise OSError("the sink is gone")

    monkeypatch.setattr(tracer, "record", boom)
    pauses = ProcessPauses.start(registry, tracer)
    try:
        gc.collect()  # stamped: the next wake's write raises
        assert wait_for(lambda: not pauses._gc_done)
        wall = pauses.wall_seconds
        assert wait_for(lambda: pauses.wall_seconds > wall)
        assert len(heartbeats()) == 1
    finally:
        pauses.close(registry, tracer)
    assert not heartbeats() and not gc_hooks()


def test_a_capture_holds_a_profiler_annotation_over_the_pass(monkeypatch):
    """During a capture (`use_jax_profiler`) the hook itself enters and
    leaves a profiler annotation named `gc.collect` on the thread that
    runs the pass: that, not the span written later, is what lies on
    the device trace's clock."""
    from luminaai_tpu.monitoring import watchdog

    notes = []

    class Annotation:
        def __init__(self, name):
            self.name = name

        def __exit__(self, *exc):
            notes.append((self.name, "exit", threading.get_ident()))

    def annotate(name):
        notes.append((name, "enter", threading.get_ident()))
        return Annotation(name)

    monkeypatch.setattr(watchdog, "_profiler_annotation", annotate)
    tracer = SpanTracer(enabled=True)
    pauses = ProcessPauses(clock=Clock())
    pauses.attach(None, tracer)
    pauses._on_gc("start", {"generation": 0})
    pauses._on_gc("stop", {"generation": 0, "collected": 1})
    assert notes == []  # a tracer that is on, and no capture
    tracer.use_jax_profiler = True
    pauses._on_gc("start", {"generation": 2})
    pauses._on_gc("stop", {"generation": 2, "collected": 7})
    me = threading.get_ident()
    assert notes == [("gc.collect", "enter", me), ("gc.collect", "exit", me)]
    tracer.enabled = False
    pauses._on_gc("start", {"generation": 2})
    pauses._on_gc("stop", {"generation": 2, "collected": 7})
    assert len(notes) == 2 and len(pauses._gc_done) == 2
    tracer.enabled = True
    beat(pauses, ProcessPauses.interval_s)  # the heartbeat writes them
    assert [s.attrs for s in tracer.recent("gc.collect")] == [
        {"generation": 0, "collected": 1}, {"generation": 2, "collected": 7}]
    assert not pauses._gc_done


def test_two_schedulers_share_one_hook_and_one_thread():
    hooks_before, threads_before = list(gc.callbacks), heartbeats()
    shared_before = ProcessPauses._shared
    scheds = [
        ContinuousScheduler(eng, decoder=eng.stepper,
                            registry=MetricsRegistry(),
                            recorder=FlightRecorder())
        for eng in (FakeEngine(), FakeEngine())
    ]
    try:
        assert scheds[0]._pauses is scheds[1]._pauses
        assert len(heartbeats()) == 1 and len(gc_hooks()) == 1
        quiet = ContinuousScheduler(
            FakeEngine(), registry=MetricsRegistry(), telemetry=False)
        assert quiet._pauses is None and quiet._stalls is None
        scheds[0].close()
        scheds[0].close()  # idempotent
        assert len(heartbeats()) == 1 and len(gc_hooks()) == 1
        toks, _ = scheds[1].submit([40, 41, 42], {"max_new_tokens": 3})
        assert toks == [40, 41, 42]
        for name in ("process_wall_seconds_total",
                     "serve_tick_stall_seconds_total"):
            assert name in counters(scheds[1].registry)
    finally:
        for s in scheds:
            s.close()
    assert list(gc.callbacks) == hooks_before
    assert heartbeats() == threads_before
    assert ProcessPauses._shared is shared_before


def test_the_heartbeat_thread_is_started_after_the_worker(monkeypatch):
    """On the TPU host a thread started between the runtime's start and
    the scheduler's worker made the worker's loads of compiled programs
    three times slower (PERF.md section 6, PRs 59-61): the scheduler joins
    ProcessPauses only once its worker thread runs."""
    seen = []
    start = ProcessPauses.start.__func__

    def spy(cls, registry, tracer=None):
        seen.append([t.name for t in threading.enumerate()
                     if t.name.endswith("(_loop)")])
        return start(cls, registry, tracer)

    monkeypatch.setattr(ProcessPauses, "start", classmethod(spy))
    before = {t.name for t in threading.enumerate()}
    eng = FakeEngine()
    sched = ContinuousScheduler(eng, decoder=eng.stepper,
                                registry=MetricsRegistry())
    sched.close()
    (workers,) = seen
    assert len(set(workers) - before) == 1


def test_a_held_tick_of_a_scheduler_is_booked_and_named():
    """Through ContinuousScheduler._step: the sentinel flags one slow
    step of a FakeStepper (whose sleep is the scheduler thread's own
    time: `sched`), with the process's measurements fed as silent."""
    registry, recorder = MetricsRegistry(), FlightRecorder()
    tracer = SpanTracer(enabled=True)
    eng = FakeEngine()
    eng.lane_tokens = lambda prompt: range(40, 60)
    eng.stepper.lane_tokens = eng.lane_tokens
    sched = ContinuousScheduler(eng, decoder=eng.stepper, registry=registry,
                                recorder=recorder, tracer=tracer)
    sched._stalls._pauses = FedPauses()
    step, calls = eng.stepper.decode_step, []

    def held_once(sample_key=None):
        calls.append(1)
        if len(calls) == 12:
            time.sleep(0.25)
        return step(sample_key)

    eng.stepper.decode_step = held_once
    try:
        toks, _ = sched.submit([40], {"max_new_tokens": 20})
    finally:
        sched.close()
    assert toks == list(range(40, 60))
    got = counters(registry)
    # One held tick; a loaded machine may stretch another past 4 x the
    # 10 ms median, and that one is booked by the same rule.
    events = recorder.snapshot(type="step_anomaly")
    assert registry.snapshot()["step_time_anomalies_total"][
        "program=serve"] == len(events) >= 1
    assert got["serve_tick_stall_seconds_total"] == pytest.approx(
        sum(got[f"serve_tick_stall_{c}_seconds_total"]
            for c in STALL_CAUSES))
    assert got["serve_tick_stall_seconds_total"] == pytest.approx(
        sum(e["stall_s"] for e in events), abs=1e-5)
    event = max(events, key=lambda e: e["stall_s"])
    assert event["program"] == "serve" and event["seconds"] > 0.25
    assert (event["stall_cause"], event["stall_phase"]) == ("host", "sched")
    assert event["stall_s"] == pytest.approx(0.25, abs=0.05)
    assert event["sched_s"] == pytest.approx(event["seconds"], abs=0.02)
    assert event["gc_s"] == 0.0 and event["pause_s"] == 0.0
    assert event["lanes"] == 1 and event["chunk_rows"] == 0
    assert set(f"{p}_s" for p in SERVE_TICK_PHASES) <= set(event)
    assert got["serve_tick_stalls_total"] == len(events)
    named = {s.attrs["stall_s"]: s for s in tracer.recent("decode_step")
             if "stall_cause" in s.attrs}
    assert len(named) == len(events)
    span = named[event["stall_s"]]
    assert span.attrs["stall_cause"] == "host"
    assert span.attrs["stall_phase"] == "sched"


def test_a_booking_fault_fails_no_request():
    """The sentinel's turn is inside `_step`'s `try`, whose handler
    fails every lane: a fault in the booking of a flagged tick stays
    with the sentinel, and the request ends as it would have."""
    registry, recorder = MetricsRegistry(), FlightRecorder()
    eng = FakeEngine()
    eng.lane_tokens = lambda prompt: range(40, 60)
    eng.stepper.lane_tokens = eng.lane_tokens
    sched = ContinuousScheduler(eng, decoder=eng.stepper, registry=registry,
                                recorder=recorder)
    step, calls = eng.stepper.decode_step, []

    def held_once(sample_key=None):
        calls.append(1)
        if len(calls) == 12:
            time.sleep(0.25)
        return step(sample_key)

    def book(excess_s, upto=None):
        raise RuntimeError("a counter that refuses")

    eng.stepper.decode_step = held_once
    sched._stalls.book = book
    try:
        toks, _ = sched.submit([40], {"max_new_tokens": 20})
    finally:
        sched.close()
    assert toks == list(range(40, 60))
    events = recorder.snapshot(type="step_anomaly")
    assert events and all("stall_cause" not in e for e in events)
    assert counters(registry)["serve_tick_stall_seconds_total"] == 0.0


# -- the benchmark's fourteen entries -------------------------------------
def new_entries():
    bench = manifest.load_benchmark()
    return [m for m in bench["per_layer"]
            if manifest.metric_base(m["name"]) in NEW_READERS]


def test_the_fourteen_entries_resolve():
    bench = manifest.load_benchmark()
    assert manifest.check(bench) == []
    entries = new_entries()
    assert len(entries) == 14
    # appended in one piece (later PRs append behind them: PR 63's two)
    at = bench["per_layer"].index(entries[0])
    assert bench["per_layer"][at:at + 14] == entries
    chat = next(m for m in bench["per_layer"]
                if m["name"] == "host_sched_ms_step.chat")["workloads"]
    for m in entries:
        base, kind = m["name"].split(".")
        assert m["unit"] == "%" and m["better"] == "lower"
        assert m["source"] == "program_counter"
        assert (m["moves"], m["workloads"]) == {
            "chat": ("ttft_mean_ms", chat),
            "batch": ("serve_tok_s", ["olmoe-serve-batch"]),
            "train": ("train_tok_s_chip",
                      ["mistral-7b-train-4k", "kimi-linear-train-8k"]),
        }[kind]
        with open(manifest.layer_metric_file(m["name"])) as f:
            spec = json.load(f)
        assert spec["num"] == {"counter": NEW_READERS[base]}
        assert spec["den"] == {"counter": "process_wall_seconds_total"}
        assert (spec["from"], spec["reduce"], spec["scale"]) == (
            "registry", "ratio", 100.0)


@pytest.fixture(scope="module")
def window_deltas():
    """The registry's delta over a window that met no stall, and over
    one that lost 2.5 s of 51 to a held step."""
    stalls, registry, clock, phases, _ = fed_stalls()
    pauses = ProcessPauses(clock=clock)
    pauses.attach(registry)
    beat(pauses, 3.0)  # set-up, before the window opens
    opened = layer_readers.registry_view(registry)
    for _ in range(5100):
        beat(pauses, 0.010)
    clean = layer_readers.delta(layer_readers.registry_view(registry), opened)
    spend(clock, phases, device_wait=2.5)
    stalls.book(2.5)
    held = layer_readers.delta(layer_readers.registry_view(registry), opened)
    return clean, held


@pytest.mark.parametrize("name", [
    f"{base}.{kind}" for base in NEW_READERS
    for kind in (("chat", "batch", "train") if base.startswith(
        ("gc_", "process_")) else ("chat", "batch"))
])
def test_each_reader_reads_zero_on_a_clean_window(name, window_deltas):
    clean, held = window_deltas
    assert name in {m["name"] for m in new_entries()}
    with open(manifest.layer_metric_file(name)) as f:
        spec = json.load(f)
    read = layer_readers.read
    value = read(name, spec, layer_readers.Context(registry_delta=clean))
    assert value == 0.0 and value is not None
    # The parent's registry has no such counter: nothing, not a fault.
    assert read(name, spec, layer_readers.Context(registry_delta={
        "counter:serve_decode_steps_total": 3500.0})) is None
    want = 100 * 2.5 / 51 if name.startswith(
        ("tick_stall_pct", "tick_stall_device_pct")) else 0.0
    assert read(name, spec, layer_readers.Context(
        registry_delta=held)) == pytest.approx(want)


# -- the refusal's own case (PR 59: benchmark_breaks_parent) ---------------
def parents_delta():
    """The registry's delta over a window of a tree without this PR: a
    live scheduler's counters with every one this PR adds taken out."""
    stalls, registry, clock, phases, _ = fed_stalls()
    pauses = ProcessPauses(clock=clock)
    pauses.attach(registry)
    for _ in range(1020):
        beat(pauses, ProcessPauses.interval_s)  # 51 s, every wake on time
    registry.counter("serve_decode_steps_total", "").inc(3500)
    view = layer_readers.registry_view(registry)
    added = {f"counter:{c}" for c in NEW_READERS.values()} | {
        "counter:process_wall_seconds_total",
        "counter:serve_tick_stall_host_seconds_total"}
    assert added <= set(view)
    return view, {k: v for k, v in view.items() if k not in added}


@pytest.mark.parametrize("base", sorted(NEW_READERS))
def test_a_program_without_the_counters_gives_the_reader_nothing(base):
    view, parent = parents_delta()
    with open(manifest.layer_metric_file(f"{base}.chat")) as f:
        spec = json.load(f)
    assert "without these counters" in spec["_note"]
    ctx = layer_readers.Context(registry_delta=parent)
    assert layer_readers.read(f"{base}.chat", spec, ctx) is None
    # half of it is no better: the numerator without the wall seconds,
    # or the wall seconds without the numerator
    num, den = f"counter:{NEW_READERS[base]}", (
        "counter:process_wall_seconds_total")
    for half in ({**parent, num: 0.0}, {**parent, den: 51.0},
                 {**parent, num: 0.0, den: 0.0}):
        assert layer_readers.read(f"{base}.chat", spec, layer_readers.Context(
            registry_delta=half)) is None
    # and this PR's program on a window that met nothing reads 0.0
    assert view[den] > 0
    assert layer_readers.read(f"{base}.chat", spec, layer_readers.Context(
        registry_delta=view)) == 0.0


@pytest.mark.parametrize("cell", [
    "olmoe-serve-chat", "olmoe-serve-batch", "jamba2-3b-serve-burst",
    "command-a-plus-serve-mixed", "kimi-k2-7-code-serve-longctx",
    "mimo-v2-flash-serve-reason", "mistral-7b-train-4k",
    "kimi-linear-train-8k"])
def test_the_parents_line_is_whole_and_leaves_the_names_out(cell):
    """What `reduce_traced_run` and `metric_values` make of a cell's
    registry readers on the parent's delta and on this tree's: the
    parent's line holds what it held (host_sched_ms_step on a serving
    cell) and none of the new names; this tree's holds every one of the
    cell's new entries, 0.0 and never null."""
    bench = manifest.load_benchmark()
    mine = [m for m in bench["per_layer"]
            if cell in m.get("workloads", [cell])]
    new = {m["name"] for m in mine
           if manifest.metric_base(m["name"]) in NEW_READERS}
    assert len(new) == (2 if "train" in cell else 6)
    view, parent = parents_delta()

    def line(delta):
        values = {}
        for m in mine:
            with open(manifest.layer_metric_file(m["name"])) as f:
                spec = json.load(f)
            if spec["from"] == "registry":
                values[m["name"]] = layer_readers.read(
                    m["name"], spec, layer_readers.Context(
                        registry_delta=delta))
        return common.metric_values(mine, values)

    before, after = line(parent), line(view)
    assert not new & set(before)
    assert new <= set(after)
    assert all(after[name] == {"value": 0.0, "unit": "%"} for name in new)
    assert {k: v for k, v in after.items() if k not in new} == before
    if "serve" in cell:
        assert any(k.startswith("host_sched_ms_step") for k in before)
    json.dumps(before), json.dumps(after)  # a result line's worth


# -- training ---------------------------------------------------------------
def test_a_trainer_starts_the_recorder_last_and_closes_it(tmp_path,
                                                          monkeypatch):
    """Trainer joins the same process-wide instance with its registry,
    after every thread its constructor starts (the history sampler),
    and close() leaves gc.callbacks and the threads as found."""
    import numpy as np

    from luminaai_tpu.config import Config
    from luminaai_tpu.training.trainer import Trainer

    seen = []
    start = ProcessPauses.start.__func__

    def spy(cls, registry, tracer=None):
        seen.append({t.name for t in threading.enumerate()})
        return start(cls, registry, tracer)

    monkeypatch.setattr(ProcessPauses, "start", classmethod(spy))
    cfg = Config(
        vocab_size=64, hidden_size=32, num_layers=1, num_heads=2,
        num_kv_heads=1, seq_length=16, batch_size=8,
        use_flash_attention=False, gradient_checkpointing=False,
        precision="fp32", max_steps=2, output_dir=str(tmp_path),
    )
    data = [{"input_ids": np.ones((8, 16), np.int32)}]
    hooks_before, threads_before = list(gc.callbacks), set(
        threading.enumerate())
    registry = MetricsRegistry()
    trainer = Trainer(cfg, train_data=data, registry=registry,
                      checkpoint_dir=str(tmp_path / "ckpt"),
                      recorder=FlightRecorder())
    try:
        (at_start,) = seen
        started = {t.name for t in threading.enumerate()} - {
            "process-heartbeat"}
        assert at_start >= started  # nothing of the trainer's came later
        assert len(heartbeats()) == 1 and len(gc_hooks()) == 1
        assert wait_for(lambda: counters(registry).get(
            "process_wall_seconds_total", 0) > 0)
        for name in ("process_gc_pause_seconds_total",
                     "process_pause_seconds_total"):
            assert name in counters(registry)
        assert not any(n.startswith("serve_tick_stall")
                       for n in counters(registry))
    finally:
        trainer.close()
        trainer.close()  # idempotent
    assert list(gc.callbacks) == hooks_before
    assert not heartbeats()
    assert not any(t.name == "process-heartbeat"
                   for t in set(threading.enumerate()) - threads_before)
