"""Hang watchdog and step-time anomaly sentinel.

A stuck DCN collective, a wedged compile helper, or a straggling host
hangs a run SILENTLY: the loop blocks inside a jax sync, no exception is
raised, and the reservation burns until a human notices. This module is
the runtime tripwire:

  - `HangWatchdog`: a heartbeat armed by the training loop and the
    serving scheduler. Producers `beat()` at their synced boundaries
    (the trainer at log cadence, right after the float() window sync;
    the scheduler after each decode step). A daemon thread watches the
    gap since the last beat against a ROBUST threshold — k x rolling
    median (+MAD guard) of recent beat intervals, floored — and when it
    trips: emits a `hang_suspected` flight event, writes ALL-thread
    stacks plus the flight ring next to the checkpoints, bumps
    `{training,serving}_hangs_total`, reattributes the stalled seconds
    to the goodput ledger's `hang` cause, and (opt-in `abort=True`,
    `--watchdog-abort`) exits RESUMABLE_EXIT=75 so the orchestrator
    restarts the job instead of burning the reservation. Warmup-aware
    by construction: the trainer arms AFTER the first-compile sync and
    nothing fires until `warmup` intervals exist, so a first compile
    (minutes on flagship shapes) can never trip it.

  - `StepTimeSentinel`: online robust stats over step durations. Each
    observation is checked against the rolling median/MAD BEFORE it
    joins the window (a spike must not defend itself), emitting
    `step_anomaly` events and `<prefix>_{median,mad}` gauges. Reset on
    recompile — a new executable is a new timing regime.

  - `ProcessPauses` and `TickStalls`: what a flagged step is booked
    under. The first is process-wide (ONE `gc.callbacks` hook and ONE
    heartbeat thread however many schedulers and trainers start it):
    the collector's passes and the seconds in which no Python thread of
    the process ran. The second books a flagged serving tick's excess
    over the rolling median under exactly one of `STALL_CAUSES`, from
    those two and the owning thread's phase ledger (`stall_cause` is
    the rule).

  - `host_step_skew()`: per-host step-completion skew, gathered at the
    caller's EXISTING multihost sync point (the trainer's log-window
    float() conversion) — max-min of per-host wall clocks, the
    straggler signal. Single-host returns 0.0 with no device work.

Everything here is host-side wall clock: zero new syncs enter the step
path (LX002 stays clean), and the monitor thread holds no jax state.
"""

from __future__ import annotations

import contextlib
import gc
import logging
import os
import sys
import threading
import time
import traceback
from collections import deque
from typing import Any, Callable, Dict, List, Optional, Tuple

logger = logging.getLogger(__name__)

__all__ = [
    "RESUMABLE_EXIT",
    "RobustStats",
    "HangWatchdog",
    "StepTimeSentinel",
    "STALL_CAUSES",
    "stall_cause",
    "ProcessPauses",
    "TickStalls",
    "host_step_skew",
    "dump_all_stacks",
]

# Mirrors cli.RESUMABLE_EXIT: orchestrators treat 75 (EX_TEMPFAIL) as
# "restart me", distinct from a real failure.
RESUMABLE_EXIT = 75

# MAD -> sigma for a normal distribution; used to turn the MAD guard
# into comparable units with the median.
_MAD_SIGMA = 1.4826


def _median(values) -> float:
    s = sorted(values)
    n = len(s)
    if not n:
        return 0.0
    mid = n // 2
    return s[mid] if n % 2 else 0.5 * (s[mid - 1] + s[mid])


class RobustStats:
    """Rolling median/MAD over the last `window` observations. Sorting a
    <=128-element window at beat/log cadence is microseconds — robust
    beats clever here."""

    def __init__(self, window: int = 64):
        self._buf: "deque[float]" = deque(maxlen=max(2, int(window)))

    def add(self, x: float) -> None:
        self._buf.append(float(x))

    def __len__(self) -> int:
        return len(self._buf)

    def median(self) -> float:
        return _median(self._buf)

    def mad(self) -> float:
        """Median absolute deviation (raw, not sigma-scaled)."""
        return self.median_mad()[1]

    def median_mad(self) -> Tuple[float, float]:
        """(median, raw MAD): the window sorted twice."""
        med = _median(self._buf)
        return med, _median([abs(x - med) for x in self._buf])

    def clear(self) -> None:
        self._buf.clear()


def dump_all_stacks(path: str) -> Optional[str]:
    """Write every live thread's Python stack to `path` (the hang
    forensics a restart would otherwise destroy). Never raises — it
    rides the watchdog's firing path."""
    try:
        names = {t.ident: t.name for t in threading.enumerate()}
        with open(path, "w", encoding="utf-8") as fh:
            for tid, frame in sys._current_frames().items():
                fh.write(
                    f"--- thread {names.get(tid, '?')} (ident={tid}) ---\n"
                )
                fh.write("".join(traceback.format_stack(frame)))
                fh.write("\n")
        return path
    except Exception as e:  # pragma: no cover - filesystem failures
        logger.warning("all-thread stack dump failed: %s", e)
        return None


class HangWatchdog:
    """Heartbeat monitor: detect -> dump -> (abort | keep watching).

    Producers call `beat()` at synced boundaries; `arm()`/`disarm()`
    bracket the active region (an idle scheduler or a finished trainer
    must never trip); `pause()` brackets legitimately-slow host work
    (eval, blocking checkpoint saves) — the interval spanning a pause is
    excluded from the stats and cannot fire.

    Threshold: k * (median + MAD_sigma) of the rolling beat intervals,
    floored at `floor_s` — k x rolling median with the MAD term guarding
    noisy windows, armed only once `warmup` intervals exist.
    """

    def __init__(
        self,
        kind: str = "training",
        registry=None,
        recorder=None,
        dump_dir: Optional[str] = None,
        k: float = 10.0,
        floor_s: float = 30.0,
        warmup: int = 3,
        window: int = 64,
        poll_s: float = 1.0,
        abort: bool = False,
        ledger=None,
        clock=time.monotonic,
        exit_fn=os._exit,
    ):
        self.kind = str(kind)
        self.dump_dir = dump_dir
        self.k = float(k)
        self.floor_s = float(floor_s)
        self.warmup = max(1, int(warmup))
        self.poll_s = max(0.01, float(poll_s))
        self.abort = bool(abort)
        self.ledger = ledger
        self._clock = clock
        self._exit_fn = exit_fn
        self._lock = threading.Lock()
        self._stats = RobustStats(window)
        self._armed = False
        self._paused = 0
        self._skip_next = False
        self._fired = False
        self._last_beat: Optional[float] = None
        self._thread: Optional[threading.Thread] = None
        self._stop = threading.Event()
        self.fires = 0  # lifetime hang_suspected count (tests, /stats)
        if recorder is None:
            from luminaai_tpu.monitoring.events import get_recorder

            recorder = get_recorder()
        self.recorder = recorder
        self._m_hangs = None
        if registry is not None:
            self._m_hangs = registry.counter(
                f"{self.kind}_hangs_total",
                "Suspected hangs: a step/tick exceeded the robust "
                "k x rolling-median threshold (docs/observability.md)",
            )

    # -- producer API -----------------------------------------------------
    def arm(self) -> None:
        """Start watching from NOW (the first interval begins here).
        Lazily spawns the monitor thread — an unarmed watchdog costs
        nothing."""
        with self._lock:
            self._armed = True
            self._last_beat = self._clock()
            self._fired = False
            self._skip_next = False
            if self._thread is None:
                self._stop.clear()
                self._thread = threading.Thread(
                    target=self._monitor,
                    name=f"{self.kind}-watchdog",
                    daemon=True,
                )
                self._thread.start()

    def disarm(self) -> None:
        with self._lock:
            self._armed = False
            self._last_beat = None

    def beat(self) -> None:
        """One synced boundary passed. Records the interval into the
        rolling stats (unless flagged skip: pause exits, recompiles) and
        re-enables firing for the next stall."""
        now = self._clock()
        with self._lock:
            if not self._armed:
                return
            if self._last_beat is not None and not self._skip_next:
                self._stats.add(now - self._last_beat)
            self._last_beat = now
            self._skip_next = False
            self._fired = False

    def skip_next(self) -> None:
        """Exclude the in-flight interval from the stats and from firing
        (recompile boundaries: a rebuild is a new timing regime, and its
        one long step is expected). Also clears the rolling window."""
        with self._lock:
            self._skip_next = True
            self._stats.clear()
            self._last_beat = self._clock()

    @contextlib.contextmanager
    def pause(self):
        """Suspend firing across legitimately-slow host work (eval,
        blocking checkpoint saves). The spanning interval is excluded
        from the stats on exit."""
        with self._lock:
            self._paused += 1
        try:
            yield
        finally:
            with self._lock:
                self._paused -= 1
                self._skip_next = True
                self._last_beat = self._clock()

    def close(self) -> None:
        self.disarm()
        self._stop.set()
        t = self._thread
        if t is not None:
            t.join(timeout=2.0)
            self._thread = None

    # -- reads ------------------------------------------------------------
    def threshold_s(self) -> Optional[float]:
        """Current firing threshold, or None while warming up."""
        with self._lock:
            return self._threshold_locked()

    def _threshold_locked(self) -> Optional[float]:
        if len(self._stats) < self.warmup:
            return None
        med = self._stats.median()
        mad = self._stats.mad() * _MAD_SIGMA
        return max(self.floor_s, self.k * (med + mad))

    def stats(self) -> Dict[str, Any]:
        with self._lock:
            return {
                "kind": self.kind,
                "armed": self._armed,
                "intervals": len(self._stats),
                "median_s": round(self._stats.median(), 6),
                "mad_s": round(self._stats.mad(), 6),
                "threshold_s": self._threshold_locked(),
                "fires": self.fires,
                "abort": self.abort,
            }

    # -- monitor thread ---------------------------------------------------
    def _monitor(self) -> None:
        while not self._stop.wait(self.poll_s):
            with self._lock:
                if (
                    not self._armed
                    or self._paused
                    or self._fired
                    or self._last_beat is None
                ):
                    continue
                thr = self._threshold_locked()
                if thr is None:
                    continue  # warmup: first compile can never trip
                stalled = self._clock() - self._last_beat
                if stalled <= thr:
                    continue
                self._fired = True
                self.fires += 1
                med = self._stats.median()
                mad = self._stats.mad()
            self._fire(stalled, thr, med, mad)

    def _fire(self, stalled: float, thr: float, med: float, mad: float):
        """Detect -> record -> dump -> (abort | continue). Never raises:
        a broken dump path must not kill the monitor."""
        logger.critical(
            "%s hang suspected: %.1fs since last heartbeat "
            "(threshold %.1fs = k=%.1f x rolling median %.3fs, MAD %.3fs)",
            self.kind, stalled, thr, self.k, med, mad,
        )
        if self._m_hangs is not None:
            self._m_hangs.inc()
        if self.ledger is not None:
            try:
                # The stall was accruing to whatever cause is open
                # (usually productive); move it where it belongs.
                self.ledger.reattribute("hang", stalled)
            except Exception:  # pragma: no cover - ledger must not kill us
                pass
        stacks_path = None
        dump_path = None
        if self.dump_dir:
            try:
                os.makedirs(self.dump_dir, exist_ok=True)
                stamp = time.strftime("%Y%m%dT%H%M%SZ", time.gmtime())
                stacks_path = dump_all_stacks(
                    os.path.join(
                        self.dump_dir,
                        f"stacks-{stamp}-{os.getpid()}-hang.txt",
                    )
                )
            except Exception as e:  # pragma: no cover
                logger.warning("stack dump failed: %s", e)
        self.recorder.emit(
            "hang_suspected",
            kind=self.kind,
            stalled_s=round(stalled, 3),
            threshold_s=round(thr, 3),
            median_s=round(med, 6),
            mad_s=round(mad, 6),
            k=self.k,
            stacks=stacks_path,
            abort=self.abort,
        )
        if self.dump_dir:
            dump_path = self.recorder.dump_to_dir(
                self.dump_dir, reason=f"{self.kind}_hang_suspected"
            )
        if self.abort:
            logger.critical(
                "--watchdog-abort: exiting %d (resumable) so the "
                "orchestrator restarts instead of burning the "
                "reservation; forensics: %s / %s",
                RESUMABLE_EXIT, stacks_path, dump_path,
            )
            # The run is WEDGED inside a sync — a graceful save cannot
            # land. os._exit skips atexit/finally by design: the last
            # periodic checkpoint plus the dumps above are the record.
            self._exit_fn(RESUMABLE_EXIT)


class StepTimeSentinel:
    """Online step-time anomaly detection over robust rolling stats.

    `observe(seconds)` checks the value against the PRIOR window
    (median/MAD) before adding it: anomalous when it exceeds BOTH
    `k x median` (ratio: it is many steps' worth of time) and
    `median + guard_sigmas x MAD_sigma` (significance: the window is not
    just noisy). Emits one `step_anomaly` event per anomaly, keeps
    `<prefix>_median` / `<prefix>_mad` gauges fresh, and counts into
    `step_time_anomalies_total{program}`.
    """

    def __init__(
        self,
        registry=None,
        recorder=None,
        prefix: str = "train_step_seconds",
        program: str = "train",
        k: float = 4.0,
        guard_sigmas: float = 6.0,
        window: int = 64,
        warmup: int = 5,
        enabled: bool = True,
    ):
        self.enabled = bool(enabled)
        if not self.enabled:
            registry = recorder = None  # no gauges, no events, no cost
        self.program = str(program)
        self.k = float(k)
        self.guard_sigmas = float(guard_sigmas)
        self.warmup = max(2, int(warmup))
        self._stats = RobustStats(window)
        self._med = self._mad = 0.0  # of the window as it stands
        self._lock = threading.Lock()
        self.anomalies = 0
        self.recorder = recorder
        self._g_median = self._g_mad = self._m_anomalies = None
        if registry is not None:
            self._g_median = registry.gauge(
                f"{prefix}_median",
                f"Rolling median of observed {self.program} step seconds",
            )
            self._g_mad = registry.gauge(
                f"{prefix}_mad",
                f"Rolling MAD of observed {self.program} step seconds",
            )
            self._m_anomalies = registry.counter(
                "step_time_anomalies_total",
                "Step durations flagged anomalous vs the rolling "
                "median/MAD, by program",
                labelnames=("program",),
            )

    def observe(
        self,
        seconds: float,
        step: Optional[int] = None,
        explain: Optional[Callable[[float], Dict[str, Any]]] = None,
    ) -> bool:
        """Feed one step duration; returns True when flagged anomalous.
        `explain(excess_s)` is called for a flagged observation alone,
        with its seconds over the rolling median: the caller books what
        it knows of the cause there and returns further fields for the
        `step_anomaly` event."""
        if not self.enabled:
            return False
        seconds = float(seconds)
        with self._lock:
            # The prior window's median and MAD were computed when its
            # last value joined it: two sorts an observation, not six.
            med, mad = self._med, self._mad
            anomalous = (
                len(self._stats) >= self.warmup
                and med > 0
                and seconds > self.k * med
                and seconds > med + self.guard_sigmas * mad * _MAD_SIGMA
            )
            self._stats.add(seconds)
            new_med, new_mad = self._med, self._mad = (
                self._stats.median_mad()
            )
            if anomalous:
                self.anomalies += 1
        if self._g_median is not None:
            self._g_median.set(new_med)
            self._g_mad.set(new_mad)
        if anomalous:
            more: Dict[str, Any] = {}
            if explain is not None:
                try:
                    more = explain(seconds - med)
                except Exception as e:  # the caller's booking: the flag,
                    # the count and the event stand without its fields
                    logger.debug("step sentinel: explain raised %r", e)
            if self._m_anomalies is not None:
                self._m_anomalies.labels(program=self.program).inc()
            if self.recorder is not None:
                self.recorder.emit(
                    "step_anomaly",
                    program=self.program,
                    seconds=round(seconds, 6),
                    median_s=round(med, 6),
                    mad_s=round(mad, 6),
                    k=self.k,
                    **({"step": step} if step is not None else {}),
                    **more,
                )
        return anomalous

    def reset(self) -> None:
        """New timing regime (recompile): forget the old distribution."""
        with self._lock:
            self._stats.clear()
            self._med = self._mad = 0.0


# What a flagged tick's excess is booked under, in the order the rule
# asks (stall_cause).
STALL_CAUSES = ("gc", "process", "device", "host")


def stall_cause(
    excess_s: float, gc_s: float, pause_s: float, phases_s: Dict[str, float]
) -> Tuple[str, str]:
    """(cause, phase) of a flagged tick from what was measured inside
    its interval: the collector's seconds, the heartbeat's lateness and
    the owning thread's seconds by phase. `phase` is the one that holds
    most of the interval. `gc` where the collector took at least half
    the excess; `process` where the heartbeat was that late and the
    collector was not (no Python thread ran: the machine took the cores
    or the process was stopped); `device` where neither was and the
    phase is `device_wait` (every Python thread ran on time and the
    step was still held: below Python, in the runtime or on the chip);
    `host` otherwise (the thread's own Python)."""
    phase = max(phases_s, key=phases_s.get)
    half = 0.5 * excess_s
    if gc_s >= half:
        return "gc", phase
    if pause_s >= half:
        return "process", phase
    return ("device" if phase == "device_wait" else "host"), phase


class ProcessPauses:
    """What the whole process did beside its hot loops, measured where
    the time is lost: the collector's passes (one `gc.callbacks` hook)
    and the seconds in which no Python thread ran (the lateness of one
    heartbeat thread's wakes). One hook and one thread a process however
    many schedulers and trainers `start()` it; each starter's registry
    gets

      - `process_gc_pause_seconds_total`: every pass, short ones too;
      - `process_pause_seconds_total`: every wake of the heartbeat later
        than `late_s` beyond its `interval_s`, the lateness in full. A
        pass holds the interpreter and so makes the heartbeat late too:
        pause less gc is what stopped the process for another reason;
      - `process_wall_seconds_total`: the monotonic seconds the
        heartbeat has covered, the denominator of the other two and of
        the serve_tick_stall_* seconds.

    The hook reads the clock twice a pass, adds the pass to a float
    (kept in a tuple with the open pass's start) and touches no lock, no
    metric and no tracer (it runs at every generation-0 pass, on whatever
    thread allocates, which may hold the tracer's write lock); the
    heartbeat publishes. While a starter's tracer is on, the hook STAMPS
    the pass and the heartbeat writes it as a `gc.collect` span; during
    a capture the hook also holds a profiler annotation of that name
    over the pass on the thread that runs it, so that an idle gap of the
    device under a pass carries its name. A late wake is written as a
    `process.pause` span, back-dated on the wall clock (a stopped
    process has no live Python span to cover its gap). A hot loop reads
    `gc_seconds_now()` and `pause_seconds_now()` (TickStalls): a tuple
    each and at most one clock read each."""

    # A pause of 2 x interval_s or longer is always booked at half its
    # length or more (a wake was due inside it, at most interval_s in),
    # which is what stall_cause asks; shorter ones may be booked short.
    # At 10 ms the wakes cost the scheduler thread 0.03-0.13 ms a tick
    # on the chip's host (PERF.md section 6, PRs 59-61).
    interval_s = 0.050
    # Above the interpreter's 5 ms switch interval: a thread that waits
    # its turn behind another is on time.
    late_s = 0.020

    _shared: Optional["ProcessPauses"] = None
    _shared_lock = threading.Lock()

    def __init__(self, clock=time.monotonic, wall=time.time):
        self._clock, self._wall = clock, wall
        # (seconds of the passes that have ended, the start of the one
        # under way or None), stored as ONE tuple: see gc_seconds_now().
        self._gc: Tuple[float, Optional[float]] = (0.0, None)
        self.pause_seconds = 0.0
        self.wall_seconds = 0.0
        # (the last wake, `pause_seconds` as that wake left it), stored
        # as ONE tuple so that a reader never sees one without the
        # other; None: no thread runs.
        self._beat_at: Optional[Tuple[float, float]] = None
        # The pass under way while a tracer is on, (wall-clock start,
        # profiler annotation or None), and the passes stamped for the
        # heartbeat to write: (start, seconds, generation, collected).
        self._gc_open: Optional[Tuple[float, Any]] = None
        self._gc_done: deque = deque(maxlen=4096)
        # One entry a start(). The sinks (one a distinct registry) and
        # the tracers are read by the heartbeat and the hook without a
        # lock, so both are replaced whole, never mutated.
        self._starters: List[Tuple[Any, Any]] = []
        self._sinks: Dict[int, "_PauseSink"] = {}
        self._tracers: Tuple[Any, ...] = ()
        self._thread: Optional[threading.Thread] = None
        self._stop = threading.Event()

    # -- the process-wide instance ---------------------------------------
    @classmethod
    def start(cls, registry, tracer=None) -> "ProcessPauses":
        """Join the process's one instance (made, hooked and its thread
        started by the first caller). Pair with `close()`, same
        arguments. A caller with a worker thread of its own starts that
        FIRST (ContinuousScheduler._watch_stalls says why)."""
        with cls._shared_lock:
            if cls._shared is None:
                cls._shared = cls()
            shared = cls._shared
            shared.attach(registry, tracer)
            if shared._thread is None:
                gc.callbacks.append(shared._on_gc)
                shared._thread = threading.Thread(
                    target=shared._run, name="process-heartbeat", daemon=True
                )
                shared._thread.start()
            return shared

    def close(self, registry, tracer=None) -> None:
        """Leave; the last one out takes the hook off `gc.callbacks` and
        joins the thread."""
        with ProcessPauses._shared_lock:
            self._write_gc_spans()  # while the leaver's tracer is wired
            self.detach(registry, tracer)
            if not self._starters:
                self._unhook()

    @classmethod
    def shutdown(cls) -> None:
        """Every starter leaves at once: for whoever made schedulers or
        trainers and never closed them (a test session; their later
        `close()` finds nothing to do)."""
        with cls._shared_lock:
            shared = cls._shared
            if shared is not None:
                shared._write_gc_spans()
                shared._starters.clear()
                shared._rewire()
                shared._unhook()

    def _unhook(self) -> None:
        """Hook out, thread joined, the process's slot free (under
        `_shared_lock`)."""
        if self._thread is None:
            return
        if self._on_gc in gc.callbacks:
            gc.callbacks.remove(self._on_gc)
        self._stop.set()
        self._thread.join(timeout=2.0)
        self._thread = self._beat_at = None
        if ProcessPauses._shared is self:
            ProcessPauses._shared = None

    def attach(self, registry, tracer=None) -> None:
        self._starters.append((registry, tracer))
        self._rewire()

    def detach(self, registry, tracer=None) -> None:
        for i, (r, t) in enumerate(self._starters):
            if r is registry and t is tracer:
                del self._starters[i]
                break
        self._rewire()

    def _rewire(self) -> None:
        sinks: Dict[int, "_PauseSink"] = {}
        tracers: List[Any] = []
        for registry, tracer in self._starters:
            if registry is not None and id(registry) not in sinks:
                sinks[id(registry)] = self._sinks.get(
                    id(registry)
                ) or _PauseSink(registry, self)
            if tracer is not None and tracer not in tracers:
                tracers.append(tracer)
        self._sinks, self._tracers = sinks, tuple(tracers)

    # -- reads ------------------------------------------------------------
    @property
    def gc_seconds(self) -> float:
        """Seconds inside the passes that have ended."""
        return self._gc[0]

    def gc_seconds_now(self) -> float:
        """`gc_seconds` and the seconds of a pass that is under way at
        this instant. The `stop` hook is Python: the interpreter may hand
        the turn to a waiting thread as it enters it, so the thread a
        pass held up can run, and read, BEFORE the pass is added; it
        finds the pass still open and counts it from its start."""
        total, t0 = self._gc
        return total if t0 is None else total + max(0.0, self._clock() - t0)

    def pause_seconds_now(self) -> float:
        """`pause_seconds` and the lateness of a wake that is overdue at
        this instant: when a pause ends the reader may run before the
        heartbeat does (it waits its turn at the interpreter), and the
        pause is the reader's interval's all the same."""
        beat = self._beat_at
        if beat is None:
            return self.pause_seconds
        last, paused = beat
        late = self._clock() - last - self.interval_s
        return paused + (late if late > self.late_s else 0.0)

    # -- the hook and the heartbeat ---------------------------------------
    def _on_gc(self, phase: str, info: Dict[str, int]) -> None:
        """`gc.callbacks`: passes do not interleave (the collector is
        not re-entered), so one start time and one open pass do. No lock
        is taken here, a tracer's least of all: a pass can start on a
        thread that is inside `SpanTracer._record`, under its write
        lock. The span is stamped and the heartbeat writes it."""
        if phase == "start":
            self._gc = (self._gc[0], self._clock())
            on = capturing = False
            for tracer in self._tracers:
                if tracer.enabled:
                    on = True
                    capturing = capturing or tracer.use_jax_profiler
            if on:
                self._gc_open = (
                    self._wall(),
                    _profiler_annotation("gc.collect") if capturing else None,
                )
            return
        total, t0 = self._gc
        if t0 is None:  # hooked while a pass was running
            return
        seconds = self._clock() - t0
        self._gc = (total + seconds, None)
        opened, self._gc_open = self._gc_open, None
        if opened is not None:
            ts, annotation = opened
            if annotation is not None:
                annotation.__exit__(None, None, None)
            self._gc_done.append(
                (ts, seconds, info["generation"], info["collected"])
            )

    def _write_gc_spans(self) -> None:
        """The stamped passes to every tracer that is on (SpanTracer.
        record: the ring and the JSONL sink). The heartbeat's, and
        close()'s; a pop either of them loses to the other is the
        other's to write."""
        done = self._gc_done
        while done:
            try:
                ts, seconds, gen, collected = done.popleft()
            except IndexError:
                return
            for tracer in self._tracers:
                tracer.record("gc.collect", ts, seconds,
                              generation=gen, collected=collected)

    def _run(self) -> None:
        last = self._clock()
        self._beat_at = (last, self.pause_seconds)
        while not self._stop.wait(self.interval_s):
            now = self._clock()
            try:
                self._beat(now - last, now)
            except Exception as e:  # a sink or a tracer that raises:
                # the wakes are the process's clock and go on
                logger.debug("process-heartbeat: a wake raised %r", e)
            last = now

    def _beat(self, dt: float, now: float) -> None:
        """One wake of the heartbeat at `now`, `dt` seconds after the
        last."""
        late = dt - self.interval_s
        paused = late > self.late_s
        if paused:
            self.pause_seconds += late
        self.wall_seconds += dt
        self._beat_at = (now, self.pause_seconds)
        for sink in self._sinks.values():
            sink.publish(self)
        if paused:
            ended = self._wall()
            for tracer in self._tracers:
                tracer.record("process.pause", ended - late, late)
        self._write_gc_spans()


def _profiler_annotation(name: str):
    """An entered `jax.profiler.TraceAnnotation`, or None where jax is
    not loaded or its profiler refuses. `sys.modules`, not an import:
    the caller is the collector's hook."""
    jax = sys.modules.get("jax")
    try:
        annotation = jax.profiler.TraceAnnotation(name)
        annotation.__enter__()
        return annotation
    except Exception:  # no jax / no profiler backend: host-only
        return None


class _PauseSink:
    """One registry's process_* counters and what of ProcessPauses'
    totals they hold (a registry that joins late counts from there)."""

    def __init__(self, registry, pauses: ProcessPauses):
        self._counters = (
            registry.counter(
                "process_gc_pause_seconds_total",
                "Seconds inside the garbage collector's passes, every "
                "generation (gc.callbacks start to stop)",
            ),
            registry.counter(
                "process_pause_seconds_total",
                "Seconds no Python thread of the process ran: the "
                "lateness of a 50 ms heartbeat thread's wakes beyond "
                "20 ms (collector passes included)",
            ),
            registry.counter(
                "process_wall_seconds_total",
                "Monotonic seconds the heartbeat thread has covered: "
                "the denominator of the process_* and "
                "serve_tick_stall_* seconds",
            ),
        )
        self._held = self._totals(pauses)

    @staticmethod
    def _totals(p: ProcessPauses) -> Tuple[float, float, float]:
        return (p.gc_seconds, p.pause_seconds, p.wall_seconds)

    def publish(self, pauses: ProcessPauses) -> None:
        now = self._totals(pauses)
        for counter, new, old in zip(self._counters, now, self._held):
            if new > old:
                counter.inc(new - old)
        self._held = now


class TickStalls:
    """Books the serving tick's flagged ticks (StepTimeSentinel.
    observe's `explain`): the excess over the rolling median goes to
    `serve_tick_stall_seconds_total` and to exactly ONE of the four
    unlabelled `serve_tick_stall_<cause>_seconds_total` (a reader that
    sums a family's children could not split a label), by `stall_cause`
    over what the interval since the last `mark()` holds of the
    collector's seconds, the heartbeat's lateness and the thread's
    phases. So the four add up to the total; `serve_tick_stalls_total`
    counts the ticks.

    Owner thread only, no lock. `mark()` where a tick's interval starts
    (at every collect, and at the dispatch of a step that none was in
    flight ahead of) keeps seven floats and calls no metric; only
    `book()` does. A collect takes ONE `read()` beside its clock reading
    and hands it to both, so the interval that is booked is the interval
    that was timed: a pause that falls between the two (inside the
    sentinel's own arithmetic) would otherwise be in the next tick's
    seconds and in nobody's measurements."""

    def __init__(self, registry, phases, pauses: ProcessPauses):
        self._phases, self._pauses = phases, pauses
        self._m_total = registry.counter(
            "serve_tick_stall_seconds_total",
            "Seconds by which ticks the step-time sentinel flagged "
            "exceeded the rolling median (the four causes' sum)",
        )
        self._m_ticks = registry.counter(
            "serve_tick_stalls_total",
            "Ticks the step-time sentinel flagged and a cause was "
            "booked for",
        )
        self._m_cause = {
            cause: registry.counter(
                f"serve_tick_stall_{cause}_seconds_total",
                f"The flagged ticks' excess seconds booked to '{cause}' "
                "(docs/observability.md \"Goodput & sentinels\")",
            )
            for cause in STALL_CAUSES
        }
        self.mark()

    def read(self) -> Tuple[Dict[str, float], float, float]:
        """The three measurements at this instant."""
        return (
            self._phases.owner_seconds(),
            self._pauses.gc_seconds_now(),
            self._pauses.pause_seconds_now(),
        )

    def mark(self, at=None) -> None:
        """The interval of the next tick starts here (or at the
        `read()` given)."""
        self._at = at if at is not None else self.read()

    def book(self, excess_s: float, upto=None) -> Dict[str, Any]:
        """A flagged tick: its excess to the total and to one cause,
        from the measurements between the last `mark()` and now (or the
        `read()` given). Returns the `step_anomaly` event's further
        fields."""
        phases0, gc0, pause0 = self._at
        phases1, gc1, pause1 = upto if upto is not None else self.read()
        phases = {
            phase: seconds - phases0[phase]
            for phase, seconds in phases1.items()
        }
        gc_s = gc1 - gc0
        pause_s = pause1 - pause0
        cause, phase = stall_cause(excess_s, gc_s, pause_s, phases)
        self._m_total.inc(excess_s)
        self._m_cause[cause].inc(excess_s)
        self._m_ticks.inc()
        return {
            "stall_s": round(excess_s, 6),
            "stall_cause": cause,
            "stall_phase": phase,
            "gc_s": round(gc_s, 6),
            "pause_s": round(pause_s, 6),
            **{f"{p}_s": round(s, 6) for p, s in phases.items()},
        }


def host_step_skew(registry=None) -> float:
    """Per-host step-completion skew at the caller's sync point.

    Each host contributes its wall clock the moment it reaches the
    log-window sync; the spread (max - min) is the straggler signal —
    a host consistently seconds behind is dragging every collective.
    Gathers via one tiny all-gather ONLY when multiple processes exist
    (the caller is already at a lockstep boundary); single-host — the
    whole CPU/test harness — returns 0.0 with no device work at all.

    Exported as the `host_step_skew_seconds` gauge when a registry is
    passed."""
    import jax

    skew = 0.0
    if jax.process_count() > 1:
        import jax.numpy as jnp
        import numpy as np
        from jax.experimental import multihost_utils

        # Epoch seconds (~1.75e9) do NOT fit float32 (ulp ~128s), and
        # without jax_enable_x64 a float64 array silently downcasts —
        # so ship (hi, lo) split at 4096s: hi stays integer-exact in
        # float32 (< 2^24) and lo carries sub-millisecond resolution;
        # reconstruct in float64 on the host before taking max - min.
        now = time.time()
        hi = float(int(now) // 4096)
        lo = now - hi * 4096.0
        gathered = multihost_utils.process_allgather(
            jnp.asarray([hi, lo], dtype=jnp.float32)
        )
        g = np.asarray(gathered, dtype=np.float64).reshape(-1, 2)
        full = g[:, 0] * 4096.0 + g[:, 1]
        skew = float(full.max() - full.min())
    if registry is not None:
        registry.gauge(
            "host_step_skew_seconds",
            "Spread (max - min) of per-host wall clocks at the last "
            "log-window sync — the straggler signal (0.0 single-host)",
        ).set(skew)
    return skew
