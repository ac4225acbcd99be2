"""Capture on demand (monitoring/tracing.py), the scheduler thread's span
tree and phase ledger, Trainer.request_profile, and the names of the
jitted steps (ISSUE 25)."""

import contextlib
import glob
import json
import os
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from luminaai_tpu.monitoring.goodput import (
    CAUSES,
    SERVE_TICK_PHASES,
    GoodputLedger,
    ThreadPhaseLedger,
)
from luminaai_tpu.monitoring.telemetry import MetricsRegistry
from luminaai_tpu.monitoring.tracing import (
    FLUSH_EVERY_SPANS,
    NULL_TRACER,
    SpanTracer,
)
from tests.test_serving import FakeEngine, FakeStepper


def _host_event_names(trace_dir):
    from jax.profiler import ProfileData

    (path,) = glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    )
    data = ProfileData.from_file(path)
    names = set()
    for plane in data.planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                names.update(e.name for e in line.events)
    return names


def until(cond, what=""):
    """Poll `cond` (another thread makes it true) for at most 10 s."""
    for _ in range(2000):
        if cond():
            return
        threading.Event().wait(0.005)
    raise AssertionError(f"timed out waiting: {what}")


@contextlib.contextmanager
def first_admission_held(sched):
    """Admissions in a known order: whatever the scheduler admits inside
    the block waits in `start_prefill` until the block ends, so a
    request queued meanwhile is admitted on the generation's first tick
    and both are mid-prefill from the first chunk on."""
    dec, gate = sched.decoder, threading.Event()
    start = dec.start_prefill

    def held(*args, **kw):
        assert gate.wait(10)
        return start(*args, **kw)

    dec.start_prefill = held
    try:
        yield
    finally:
        gate.set()
        dec.start_prefill = start


# -- the capture control ---------------------------------------------------
@pytest.mark.parametrize("enabled", [False, True])
@pytest.mark.parametrize("mirror", [False, True])
def test_capture_switches_on_and_restores_the_flags(tmp_path, enabled, mirror):
    tracer = SpanTracer(enabled=enabled, use_jax_profiler=mirror)
    assert not tracer.capturing
    assert tracer.start_capture(str(tmp_path / "trace"))
    try:
        assert tracer.capturing
        assert tracer.enabled and tracer.use_jax_profiler
        with tracer.span("inside") as s:
            s.set(x=1)
    finally:
        assert tracer.stop_capture() == str(tmp_path / "trace")
    assert (tracer.enabled, tracer.use_jax_profiler) == (enabled, mirror)
    assert not tracer.capturing
    assert [s.name for s in tracer.recent()] == ["capture_clock", "inside"]
    assert any((tmp_path / "trace").rglob("*.xplane.pb"))


def test_capture_is_idempotent_and_one_a_process(tmp_path):
    a, b = SpanTracer(enabled=False), SpanTracer(enabled=False)
    assert a.stop_capture() is None  # nothing open: a no-op
    assert a.start_capture(str(tmp_path / "a"))
    try:
        assert not a.start_capture(str(tmp_path / "a2"))  # already on
        assert not b.start_capture(str(tmp_path / "b"))   # one a process
        assert b.stop_capture() is None  # b may not stop a's capture
        assert a.capturing and not b.capturing and not b.enabled
    finally:
        assert a.stop_capture() == str(tmp_path / "a")
    assert a.stop_capture() is None
    assert b.start_capture(str(tmp_path / "b"))  # free again
    assert b.stop_capture() == str(tmp_path / "b")


def test_the_shared_null_tracer_is_never_switched_on(tmp_path):
    assert not NULL_TRACER.start_capture(str(tmp_path / "never"))
    assert not NULL_TRACER.enabled and not NULL_TRACER.capturing
    assert not (tmp_path / "never").exists()


def test_a_switched_off_tracer_hands_out_the_shared_null_span(tmp_path):
    tracer = SpanTracer(enabled=False)
    null = NULL_TRACER.span("x")
    assert tracer.span("a", k=1) is null
    assert tracer.start_capture(str(tmp_path / "t"))
    assert tracer.span("b") is not null
    tracer.stop_capture()
    assert tracer.span("c") is null
    assert tracer.spans_recorded == 1  # the capture_clock mark alone


@pytest.mark.parametrize("opened", ["before_start", "during"])
def test_a_span_across_the_switch_is_harmless(tmp_path, opened):
    """The scheduler thread opens spans while the harness thread flips
    the switch: a span opened on one side and closed on the other must
    neither raise nor leave the thread's stack dirty."""
    tracer = SpanTracer(enabled=(opened == "before_start"))
    if opened == "before_start":
        cm = tracer.span("crossing")  # enabled, not mirrored
        cm.__enter__()
        assert tracer.start_capture(str(tmp_path / "t"))
        cm.__exit__(None, None, None)
        tracer.stop_capture()
    else:
        assert tracer.start_capture(str(tmp_path / "t"))
        cm = tracer.span("crossing")  # mirrored into the profiler
        cm.__enter__()
        tracer.stop_capture()
        cm.__exit__(None, None, None)
    assert tracer._stack() == []
    assert [s.name for s in tracer.recent("crossing")] == ["crossing"]
    with tracer.span("after") as s:
        assert getattr(s, "parent_id", None) is None


def test_capture_clock_ties_the_jsonl_to_the_profiler(tmp_path):
    path = tmp_path / "spans.jsonl"
    tracer = SpanTracer(jsonl_path=str(path), enabled=False)
    assert tracer.start_capture(str(tmp_path / "t"))
    tracer.stop_capture()  # flushes the sink
    (line,) = [json.loads(x) for x in path.read_text().splitlines()]
    assert line["name"] == "capture_clock" and line["duration_s"] > 0
    unix_ns = line["attrs"]["unix_ns"]
    assert abs(line["ts"] - unix_ns / 1e9) < 1e-3
    assert f"capture_clock unix_ns={unix_ns}" in _host_event_names(
        str(tmp_path / "t"))
    tracer.close()


def test_jsonl_is_not_flushed_on_every_span(tmp_path):
    path = tmp_path / "spans.jsonl"
    tracer = SpanTracer(jsonl_path=str(path))
    with tracer.span("one"):
        pass
    assert path.read_text() == ""  # buffered, the ring has it
    assert [s.name for s in tracer.recent()] == ["one"]
    tracer.flush()
    assert len(path.read_text().splitlines()) == 1
    for _ in range(FLUSH_EVERY_SPANS):
        with tracer.span("many"):
            pass
    assert len(path.read_text().splitlines()) >= FLUSH_EVERY_SPANS
    tracer.close()
    assert len(path.read_text().splitlines()) == FLUSH_EVERY_SPANS + 1


# -- the ledger names its own causes ---------------------------------------
@pytest.mark.parametrize("causes,fmt", [
    (CAUSES, None),
    (SERVE_TICK_PHASES, "serve_tick_{cause}_seconds_total"),
], ids=["run", "scheduler_thread"])
def test_ledger_partitions_elapsed_for_any_causes(causes, fmt):
    t = [100.0]
    registry = MetricsRegistry()
    if fmt:
        ledger = ThreadPhaseLedger(causes, fmt, registry=registry,
                                   clock=lambda: t[0])
    else:
        ledger = GoodputLedger(registry=registry, clock=lambda: t[0],
                               kind="x", causes=causes)
    ledger.start(causes[0])
    for i, cause in enumerate(causes):
        ledger.switch(cause)
        t[0] += 0.25 * (i + 1)
    with ledger.region(causes[1]):
        t[0] += 1.0
    ledger.stop()
    t[0] += 5.0  # stopped gap: booked to the last cause on restart
    ledger.start(causes[0])
    secs = ledger.seconds()
    assert set(secs) == set(causes)
    assert abs(sum(secs.values()) - ledger.elapsed()) < 1e-9
    assert secs[causes[-1]] == pytest.approx(0.25 * len(causes) + 5.0)
    assert secs[causes[1]] == pytest.approx(0.5 + 1.0)
    with pytest.raises(ValueError):
        ledger.switch("no_such_cause")
    names = {f.name for f in registry.families()}
    if fmt:
        assert {fmt.format(cause=c) for c in causes} <= names
        assert "x_time_seconds_total" not in names
        # Nothing reaches the counters before the owner publishes.
        counters = [registry.counter(fmt.format(cause=c), "") for c in causes]
        assert sum(c.value for c in counters) == 0.0
        ledger.publish()
        ledger.publish()  # idempotent: only what accrued since
        assert sum(c.value for c in counters) == pytest.approx(
            sum(secs.values()), abs=1e-9)
    else:
        assert "x_time_seconds_total" in names


# -- the scheduler thread --------------------------------------------------
class FakeClock:
    def __init__(self):
        self.t = 50.0

    def __call__(self):
        return self.t

    def advance(self, s):
        self.t += s


class ClockedStepper(FakeStepper):
    """FakeStepper with the chunked admission path and the decoder's side
    of the phase ledger, every phase advancing an injected clock by a
    known amount (so the expected totals are exact)."""

    PUT, DISPATCH, WAIT, ADMIT = 0.001, 0.002, 0.020, 0.0005
    prefill_chunk = 4

    def __init__(self, clock, **kw):
        super().__init__(**kw)
        self.clock = clock
        self.tracer = NULL_TRACER
        self.phases = GoodputLedger(enabled=False, causes=SERVE_TICK_PHASES)
        self.calls = {"put": 0, "dispatch": 0, "device_wait": 0, "admit": 0}

    def _phase(self, cause, seconds, span):
        self.calls[cause] += 1
        with self.phases.region(cause), self.tracer.span(span):
            self.clock.advance(seconds)

    def start_prefill(self, slot, prompt, max_new_tokens=1, sample_key=None,
                      seed=None):
        self.calls["admit"] += 1
        self.clock.advance(self.ADMIT)  # the scheduler's own Python
        n = -(-len(prompt) // self.prefill_chunk)
        return {"slot": slot, "prompt": list(prompt), "next": 0,
                "n_chunks": n, "chunk": self.prefill_chunk,
                "length": len(prompt), "max_new": max_new_tokens}

    def advance_prefill(self, st):
        self._phase("put", self.PUT, "prefill.put")
        self._phase("dispatch", self.DISPATCH, "prefill.dispatch")
        st["next"] += 1
        if st["next"] < st["n_chunks"]:
            return None
        with self.tracer.span("prefill.sample"):
            self._phase("device_wait", self.WAIT, "sync")
        return self.prefill_into_slot(
            st["slot"], st["prompt"], max_new_tokens=st["max_new"])

    def decode_step(self, sample_key=None):
        self._phase("put", self.PUT, "decode.put")
        self._phase("dispatch", self.DISPATCH, "decode.dispatch")
        self._phase("device_wait", self.WAIT, "decode.fetch")
        return super().decode_step(sample_key)


def _phase_counters(registry):
    return {
        c: registry.counter(f"serve_tick_{c}_seconds_total", "").value
        for c in SERVE_TICK_PHASES
    }


def test_tick_phase_counters_partition_the_scheduler_threads_time():
    """One generation with an admission, a chunk, a final chunk (its
    first-token sync) and decode steps, on an injected clock: the five
    serve_tick_* counters sum to the thread's elapsed time, and the
    engine's three read exactly what the decoder spent."""
    from luminaai_tpu.serving.server import ContinuousScheduler

    clock = FakeClock()
    registry = MetricsRegistry()
    stepper = ClockedStepper(clock, num_slots=2)
    sched = ContinuousScheduler(
        FakeEngine(), decoder=stepper, registry=registry,
        clock=clock,
    )
    assert stepper.phases is sched._phases  # the decoder got the ledger
    assert stepper.tracer is sched.tracer
    clock.advance(1.0)  # idle before the first request
    toks, _ = sched.submit([100] * 7, {"max_new_tokens": 3})  # 2 chunks
    assert toks == [100, 101, 102]
    # Back on q.get(): the counters are published once a tick and
    # before the thread blocks on the queue.
    deadline = 200
    while sched._phases.current_cause() != "queue_idle" and deadline:
        deadline -= 1
        threading.Event().wait(0.01)
    assert sched._phases.current_cause() == "queue_idle"
    got = _phase_counters(registry)
    calls = stepper.calls
    assert calls["admit"] == 1 and calls["device_wait"] == 1 + 2
    assert got["put"] == pytest.approx(calls["put"] * stepper.PUT, abs=1e-9)
    assert got["dispatch"] == pytest.approx(
        calls["dispatch"] * stepper.DISPATCH, abs=1e-9)
    assert got["device_wait"] == pytest.approx(
        calls["device_wait"] * stepper.WAIT, abs=1e-9)
    # The rest of the elapsed time is the scheduler's own and the queue:
    # the admission's Python and the second before the request.
    assert got["sched"] + got["queue_idle"] == pytest.approx(
        stepper.ADMIT + 1.0, abs=1e-9)
    elapsed = sched._phases.elapsed()
    assert abs(sum(got.values()) - elapsed) < 1e-6
    assert abs(sum(sched._phases.seconds().values()) - elapsed) < 1e-6


def test_telemetry_off_switches_the_phase_ledger_off():
    from luminaai_tpu.serving.server import ContinuousScheduler

    registry = MetricsRegistry()
    sched = ContinuousScheduler(
        FakeEngine(), decoder=FakeStepper(num_slots=2),
        registry=registry, telemetry=False,
    )
    toks, _ = sched.submit([7], {"max_new_tokens": 2})
    assert toks == [7, 8]
    assert not sched._phases.enabled
    assert not any(f.name.startswith("serve_tick_")
                   for f in registry.families())


@pytest.fixture(scope="module")
def tiny_engine():
    from flax import linen as nn

    from luminaai_tpu.config import Config
    from luminaai_tpu.data.tokenizer import ConversationTokenizer
    from luminaai_tpu.inference.generate import GenerationEngine
    from luminaai_tpu.models.transformer import LuminaTransformer

    tok = ConversationTokenizer()
    cfg = Config(
        vocab_size=tok.vocab_size, hidden_size=64, num_layers=1,
        num_heads=4, num_kv_heads=2, seq_length=128,
        use_flash_attention=False, precision="fp32",
        gradient_checkpointing=False, max_new_tokens=8,
        prefill_chunk_size=16,
    )
    model = LuminaTransformer(cfg)
    params = jax.jit(model.init)(
        jax.random.key(0), jnp.ones((1, 8), jnp.int32))["params"]
    params = jax.tree.map(
        lambda x: x.unbox() if isinstance(x, nn.meta.AxisMetadata) else x,
        params, is_leaf=lambda x: isinstance(x, nn.meta.AxisMetadata),
    )
    return GenerationEngine(model, params, tok, cfg)


def test_a_capture_holds_the_scheduler_threads_span_tree(tiny_engine,
                                                         tmp_path):
    """A scheduler that served untraced is switched on mid-process: the
    profiler's host plane then holds the tick's spans and the clock
    mark, the JSONL-side spans nest under sched.tick, and the real
    decoder feeds the phase ledger."""
    from luminaai_tpu.serving.server import ContinuousScheduler

    registry = MetricsRegistry()
    tracer = SpanTracer(enabled=False)
    sched = ContinuousScheduler(
        tiny_engine, num_slots=2, page_size=16, max_slot_tokens=64,
        registry=registry, tracer=tracer,
    )
    assert sched.decoder.tracer is tracer
    prompt = list(range(5, 5 + 40))  # 3 chunks of 16
    warm, _ = sched.submit(prompt, {"max_new_tokens": 4, "temperature": 0.0})
    assert tracer.spans_recorded == 0  # off: nothing was recorded
    # The tick that emitted the last token still admits and publishes
    # after it: a capture switched on under it would have no tick span.
    for _ in range(1000):
        if sched.idle():
            break
        threading.Event().wait(0.005)
    assert tracer.start_capture(str(tmp_path / "trace"))
    try:
        toks, _ = sched.submit(
            prompt, {"max_new_tokens": 4, "temperature": 0.0})
    finally:
        tracer.stop_capture()
    assert toks == warm
    want = {"sched.tick", "sched.admit", "decode_step", "decode.pack",
            "decode.put", "decode.dispatch", "decode.fetch", "decode.book",
            "sched.emit"}
    names = _host_event_names(str(tmp_path / "trace"))
    assert want <= names, want - names
    # The request's own spans are written from its stamps when it ends:
    # the JSONL sink's alone (an annotation cannot be back-dated), and
    # the host-only span around the chunk's choice is gone.
    request_spans = {"request", "req.queued", "req.prefill_wait",
                     "req.prefill_ride", "req.first_token", "req.decode"}
    assert not names & (request_spans | {"prefill_chunk"})
    # A chunk rides the decode step: no transfer, program call or
    # first-token sync of its own is left on the chunked path.
    assert not names & {"prefill.put", "prefill.dispatch", "prefill.sample"}
    assert any(n.startswith("capture_clock unix_ns=") for n in names)
    spans = tracer.recent()
    assert request_spans <= {s.name for s in spans}
    by_id = {s.span_id: s for s in spans}
    ticks = {s.span_id for s in spans if s.name == "sched.tick"}
    assert ticks
    for s in spans:
        if s.name in ("decode_step", "sched.emit", "sched.admit"):
            assert s.parent_id in ticks or s.name == "sched.admit", s.name
        if s.name in ("decode.pack", "decode.put", "decode.dispatch",
                      "decode.fetch", "decode.book"):
            assert by_id[s.parent_id].name == "decode_step"
        if s.name == "decode_step" and "chunk_slot" in s.attrs:
            assert 0 < s.attrs["chunk_rows"] <= 16
    carried = [s for s in spans
               if s.name == "decode_step" and "chunk_slot" in s.attrs]
    assert [s.attrs["chunk_rows"] for s in carried] == [16, 16, 8]
    admit = [s for s in spans if s.name == "sched.admit"]
    assert admit and admit[0].attrs["prompt_tokens"] == 40
    rid = admit[0].attrs["request_id"]
    # One request's stages and the ticks that did its work, by its id.
    assert {s.attrs["chunk_request_id"] for s in carried} == {rid}
    (root,) = [s for s in spans if s.name == "request"]
    assert root.attrs["request_id"] == rid and root.parent_id is None
    assert root.attrs["tokens"] == 4 and root.attrs["stopped"] == "length"
    (ride,) = [s for s in spans if s.name == "req.prefill_ride"]
    assert ride.trace_id == root.trace_id
    assert ride.attrs == {"chunks": 3, "ticks": 3, "turns_waited": 0}
    got = _phase_counters(registry)
    assert all(got[c] > 0 for c in ("put", "dispatch", "device_wait",
                                    "sched"))


def test_turns_waited_by_request_add_up_to_the_counter(tiny_engine):
    """Two prompts of three and two chunks share the ticks' chunk rows
    on the real decoder, one step ahead: each one's `turns_waited` is
    the steps dispatched from its admission through its last chunk less
    its own chunks, and together they are
    serve_prefill_turns_waited_total. A is the oldest and never the
    longer, so its three chunks ride first and B waits three turns."""
    from luminaai_tpu.monitoring.events import FlightRecorder
    from luminaai_tpu.serving.server import ContinuousScheduler

    registry, recorder = MetricsRegistry(), FlightRecorder()
    sched = ContinuousScheduler(
        tiny_engine, num_slots=2, page_size=16, max_slot_tokens=64,
        registry=registry, recorder=recorder,
    )
    gen = {"max_new_tokens": 2, "temperature": 0.0}
    threads = []
    with first_admission_held(sched):  # the ticks' chunks: A, A, A, B, B
        for n_tokens in (40, 24):
            threads.append(threading.Thread(
                target=sched.submit,
                args=(list(range(5, 5 + n_tokens)), gen), daemon=True))
            threads[-1].start()
            until(lambda: (sched.decoder.pool.stats()["in_use"],
                           sched.queue_depth()) == (1, len(threads) - 1),
                  "A holds its slot, then B is queued")
    for th in threads:
        th.join(60)
    firsts = sorted(recorder.snapshot(type="request_first_token"),
                    key=lambda e: e["chunks"])
    assert [(e["chunks"], e["turns_waited"]) for e in firsts] == [
        (2, 3), (3, 0)]
    assert registry.counter(
        "serve_prefill_turns_waited_total", "").value == 3
    assert registry.counter("serving_prefill_chunks_total", "").value == 5
    picks = registry.get("serve_prefill_picks_total")
    assert [picks.labels(rule=r).value for r in ("oldest", "shortest")] == [
        3, 2]
    assert registry.counter(
        "serve_prefill_picks_not_oldest_total", "").value == 0
    assert not sched._prefilling


# -- the trainer -----------------------------------------------------------
@pytest.fixture(scope="module")
def tiny_trainer(tmp_path_factory):
    from luminaai_tpu.training.trainer import Trainer
    from tests.test_orchestrator import patterned_data, tiny_config

    tmp = tmp_path_factory.mktemp("capture_trainer")
    cfg = tiny_config(tmp, max_steps=12, save_every_n_batches=10**6,
                      health_check_interval=20)  # a log sync every 2 steps
    trainer = Trainer(cfg, train_data=patterned_data(cfg),
                      checkpoint_dir=str(tmp / "ckpt"),
                      registry=MetricsRegistry())
    yield trainer, tmp
    trainer.close()


def test_request_profile_starts_and_stops_at_step_boundaries(tiny_trainer):
    trainer, tmp = tiny_trainer
    trace_dir = str(tmp / "asked")
    seen = []

    def hook(step, metrics):
        seen.append((step, trainer.profiling, trainer.tracer.enabled))
        if step == 4:
            trainer.request_profile(4, trace_dir)

    trainer.step_callback = hook
    assert not trainer.tracer.enabled  # its own tracer, switched off
    assert trainer.tracer is not NULL_TRACER
    trainer.train()
    # Armed at the sync after step 4: on from the boundary before step 5
    # to the boundary after step 8, off again afterwards.
    assert seen == [(2, False, False), (4, False, False), (6, True, True),
                    (8, True, True), (10, False, False), (12, False, False)]
    names = _host_event_names(trace_dir)
    assert {"train.log_sync", "train.data_wait"} <= names
    assert any(n.startswith("train_step") for n in names)  # step markers
    assert any(n.startswith("capture_clock unix_ns=") for n in names)
    assert trainer.stop_profile() is None  # idempotent
    assert not trainer.tracer.capturing


@pytest.mark.parametrize("which", ["train_step", "eval_step"])
def test_the_jitted_steps_are_named(tiny_trainer, which):
    """A device trace's module line shows `jit_<name>(`: the benchmark's
    train_step_device_ms selects `^jit_train_step\\(`."""
    trainer, _ = tiny_trainer
    fn = getattr(trainer, which).jitted
    assert fn.__name__ == which
    batch = trainer._put({"input_ids": np.ones(
        (trainer.config.batch_size, trainer.config.seq_length), np.int32)})
    with trainer.mesh:
        text = fn.lower(trainer.state, batch).as_text()
    assert f"module @jit_{which}" in text
