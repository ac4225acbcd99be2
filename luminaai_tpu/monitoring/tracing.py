"""Request/step span tracing: context-manager spans with JSONL export.

The metrics registry (telemetry.py) answers "how fast is the system" in
aggregate; this module answers "where did THIS request's time go". A
`SpanTracer` hands out context-manager spans (queue wait, prefill,
time-to-first-token, SSE stream, train step...) that record wall-clock
start/duration, parent/child nesting per thread, and free-form
attributes, and appends each finished span as one JSON line — the same
sink shape the training health monitor already writes, greppable and
pandas-loadable without a collector deployment.

Optionally each span also opens a `jax.profiler.TraceAnnotation`, so
when a device trace is being captured (trainer `--profile-start-step`,
or `jax.profiler.trace()` around a serving window) the host-side spans
show up as named regions on the TensorBoard timeline, correlating HTTP
requests with the device steps they caused. The jax import is lazy and
every failure path degrades to plain host spans: tracing must never be
able to take down serving.

Disabled tracers (the default for serving: `--trace-jsonl` opts in) cost
one attribute check per span — no objects, no lock, no I/O.

Capture on demand: `start_capture(trace_dir)` / `stop_capture()` switch
a RUNNING tracer on together with the device profiler, so one process
can serve or train untraced and then trace a few seconds of the same
work (benchmark `--trace 2`, `Trainer.request_profile`). This module is
the only place in the package that starts or stops `jax.profiler`.
"""

from __future__ import annotations

import itertools
import json
import logging
import os
import threading
import time
from typing import Any, Dict, IO, Optional

logger = logging.getLogger(__name__)

__all__ = ["Span", "SpanTracer", "NULL_TRACER"]

_ids = itertools.count(1)

# The device profiler is one per process: whichever tracer started it
# owns it until its stop_capture().
_capture_lock = threading.Lock()
_capture_owner: Optional["SpanTracer"] = None

# JSONL spans are written through the file's own buffer and pushed to the
# OS this often (and on close / stop_capture / flush): a flush per span
# was ~200 syscalls a second on the scheduler thread.
FLUSH_EVERY_SPANS = 256


class Span:
    """One timed region. Mutable while open (`set(key=value)` adds
    attributes, e.g. tokens generated — known only at the end); frozen
    into a dict when the context exits."""

    __slots__ = (
        "name", "trace_id", "span_id", "parent_id", "t0", "duration_s",
        "attrs", "error",
    )

    def __init__(self, name: str, trace_id: int, parent_id: Optional[int],
                 attrs: Dict[str, Any]):
        self.name = name
        self.trace_id = trace_id
        self.span_id = next(_ids)
        self.parent_id = parent_id
        self.t0 = time.time()
        self.duration_s: Optional[float] = None
        self.attrs = attrs
        self.error: Optional[str] = None

    def set(self, **attrs: Any) -> "Span":
        self.attrs.update(attrs)
        return self

    def to_dict(self) -> Dict[str, Any]:
        out = {
            "name": self.name,
            "trace": self.trace_id,
            "span": self.span_id,
            "parent": self.parent_id,
            "ts": round(self.t0, 6),
            "duration_s": (
                round(self.duration_s, 6)
                if self.duration_s is not None
                else None
            ),
        }
        if self.error:
            out["error"] = self.error
        if self.attrs:
            out["attrs"] = self.attrs
        return out


class _NullSpan:
    """Shared no-op span for disabled tracers: set() swallows attrs so
    call sites never branch on whether tracing is on."""

    __slots__ = ()

    def set(self, **attrs: Any) -> "_NullSpan":
        return self

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()


class _OpenSpan:
    """Context manager binding one Span to the tracer's per-thread stack
    (parenting) and, optionally, a jax.profiler.TraceAnnotation."""

    __slots__ = ("_tracer", "_span", "_t0", "_annotation")

    def __init__(self, tracer: "SpanTracer", span: Span):
        self._tracer = tracer
        self._span = span
        self._t0 = 0.0
        self._annotation = None

    def __enter__(self) -> Span:
        tracer = self._tracer
        stack = tracer._stack()
        stack.append(self._span)
        if tracer.use_jax_profiler:
            try:
                import jax

                self._annotation = jax.profiler.TraceAnnotation(
                    self._span.name
                )
                self._annotation.__enter__()
            except Exception:  # no jax / no profiler backend: host-only
                self._annotation = None
        self._t0 = time.perf_counter()
        return self._span

    def __exit__(self, exc_type, exc, tb):
        span = self._span
        span.duration_s = time.perf_counter() - self._t0
        if exc is not None:
            span.error = f"{type(exc).__name__}: {exc}"
        if self._annotation is not None:
            try:
                self._annotation.__exit__(exc_type, exc, tb)
            except Exception:
                pass
        tracer = self._tracer
        stack = tracer._stack()
        if stack and stack[-1] is span:
            stack.pop()
        elif span in stack:  # mis-nested exit (generator close order)
            stack.remove(span)
        tracer._record(span)
        return False


class SpanTracer:
    """Span factory + JSONL writer.

    `tracer.span("prefill", slot=3)` returns a context manager yielding
    a Span; on exit the span (duration, attrs, error) is appended to the
    JSONL file under a lock. Nesting is per-thread: a span opened inside
    another on the same thread records it as parent, and the outermost
    span starts a new trace id: on the scheduler thread a tick's, on an
    HTTP thread a request's. What crosses ticks and threads (a request
    inside the scheduler) is written whole by `record()`, under a trace
    id of its own.
    """

    def __init__(
        self,
        jsonl_path: Optional[str] = None,
        enabled: bool = True,
        use_jax_profiler: bool = False,
        max_spans_in_memory: int = 1000,
    ):
        self.enabled = bool(enabled)
        self.use_jax_profiler = bool(use_jax_profiler)
        self._capture_dir: Optional[str] = None
        self._flags_before_capture = (self.enabled, self.use_jax_profiler)
        self.jsonl_path = jsonl_path
        self._write_lock = threading.Lock()
        self._file: Optional[IO[str]] = None
        self._tls = threading.local()
        self._trace_ids = itertools.count(1)
        # Ring of recent finished spans for in-process inspection
        # (/healthz debugging, tests) without re-reading the file.
        self._recent: list = []
        self._max_recent = int(max_spans_in_memory)
        self.spans_recorded = 0
        self.dropped_writes = 0
        if jsonl_path:
            try:
                d = os.path.dirname(os.path.abspath(jsonl_path))
                os.makedirs(d, exist_ok=True)
                self._file = open(jsonl_path, "a")
            except OSError as e:
                logger.warning(
                    "span jsonl %s unwritable (%s); spans kept in memory "
                    "only", jsonl_path, e,
                )
                self._file = None

    def _stack(self) -> list:
        stack = getattr(self._tls, "stack", None)
        if stack is None:
            stack = self._tls.stack = []
        return stack

    def span(self, name: str, **attrs: Any):
        """Open a span. Returns a context manager yielding the Span (or
        a shared no-op when disabled)."""
        if not self.enabled:
            return _NULL_SPAN
        stack = self._stack()
        if stack:
            parent = stack[-1]
            s = Span(name, parent.trace_id, parent.span_id, attrs)
        else:
            s = Span(name, next(self._trace_ids), None, attrs)
        return _OpenSpan(self, s)

    def record(
        self,
        name: str,
        ts: float,
        duration_s: float,
        parent: Optional[Span] = None,
        **attrs: Any,
    ) -> Optional[Span]:
        """Write a span whose two ends were no `with` block on one
        thread: a request's stages, stamped where they happened and
        written when the request ends. `ts` is the start on the wall
        clock (`Span.ts`'s own), `parent` a span this call returned
        before; without one the span is a root with a trace id of its
        own. Returns the span, or None (nothing made) when disabled: a
        caller with attributes to gather checks `enabled` first.

        The JSONL sink and the ring alone: a `TraceAnnotation` cannot be
        back-dated, so the profiler never sees these; `capture_clock`
        lays their `ts` on its timeline."""
        if not self.enabled:
            return None
        if parent is None:
            s = Span(name, next(self._trace_ids), None, attrs)
        else:
            s = Span(name, parent.trace_id, parent.span_id, attrs)
        s.t0 = ts
        s.duration_s = duration_s
        self._record(s)
        return s

    def _record(self, span: Span) -> None:
        with self._write_lock:
            self.spans_recorded += 1
            self._recent.append(span)
            if len(self._recent) > self._max_recent:
                del self._recent[: len(self._recent) - self._max_recent]
            if self._file is not None:
                try:
                    self._file.write(json.dumps(span.to_dict()) + "\n")
                    if self.spans_recorded % FLUSH_EVERY_SPANS == 0:
                        self._file.flush()
                except (OSError, ValueError):
                    self.dropped_writes += 1

    def flush(self) -> None:
        """Push buffered JSONL spans to the file (close() and
        stop_capture() do; a reader of a live file calls this first)."""
        with self._write_lock:
            if self._file is not None:
                try:
                    self._file.flush()
                except (OSError, ValueError):
                    self.dropped_writes += 1

    # -- capture on demand ------------------------------------------------
    @property
    def capturing(self) -> bool:
        return self._capture_dir is not None

    def start_capture(self, trace_dir: str) -> bool:
        """Switch this tracer on, mirrored into the device profiler, and
        start a profiler trace into `trace_dir` (host events at level 2,
        python frames off: they slow the host and swell the file). True
        if THIS call started a capture; False (and a log line) when one
        is already running in the process or the profiler refuses.
        Never raises: the caller may be a serving or training thread."""
        global _capture_owner
        with _capture_lock:
            if _capture_owner is not None:
                logger.warning("capture not started: one is already running")
                return False
            try:
                import jax

                options = jax.profiler.ProfileOptions()
                options.python_tracer_level = 0
                options.host_tracer_level = 2
                jax.profiler.start_trace(trace_dir, profiler_options=options)
            except Exception as e:  # unsupported backend, bad directory
                logger.warning("capture not started: %s", e)
                return False
            _capture_owner = self
            self._capture_dir = trace_dir
            self._flags_before_capture = (self.enabled, self.use_jax_profiler)
            self.use_jax_profiler = True
            self.enabled = True
            self._mark_clock(jax)
            return True

    def _mark_clock(self, jax) -> None:
        """One annotation whose NAME carries the host's wall clock, and
        the same instant as a span: the pair lays `Span.ts` (wall clock,
        also of request spans that cross threads) on the profiler's
        clock. Short, not empty: trace readers drop zero-length events."""
        span = Span("capture_clock", next(self._trace_ids), None, {})
        unix_ns = time.time_ns()
        t0 = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation(
                f"capture_clock unix_ns={unix_ns}"
            ):
                while time.perf_counter() - t0 < 5e-5:
                    pass
        except Exception:
            pass
        span.t0 = unix_ns / 1e9
        span.attrs["unix_ns"] = unix_ns
        span.duration_s = time.perf_counter() - t0
        self._record(span)

    def stop_capture(self) -> Optional[str]:
        """Stop the capture this tracer started, restore `enabled` and
        `use_jax_profiler` to what they were, flush the JSONL sink.
        Returns the trace directory, or None when this tracer had no
        capture open (idempotent). Never raises."""
        global _capture_owner
        with _capture_lock:
            if _capture_owner is not self:
                return None
            trace_dir = self._capture_dir
            # Flags first: the profiler takes seconds to write a trace
            # out, and the traced threads should stop paying for spans
            # the moment the capture is over.
            self.enabled, self.use_jax_profiler = self._flags_before_capture
            try:
                import jax

                jax.profiler.stop_trace()
            except Exception as e:
                logger.warning("capture stop failed: %s", e)
            self._capture_dir = None
            _capture_owner = None
        self.flush()
        return trace_dir

    def recent(self, name: Optional[str] = None) -> list:
        with self._write_lock:
            spans = list(self._recent)
        if name is not None:
            spans = [s for s in spans if s.name == name]
        return spans

    def close(self) -> None:
        self.stop_capture()
        with self._write_lock:
            if self._file is not None:
                try:
                    self._file.close()
                except OSError:
                    pass
                self._file = None


class _SharedNullTracer(SpanTracer):
    """A capture switched on here would switch on every call site that
    shares the instance, so it refuses one."""

    def start_capture(self, trace_dir: str) -> bool:
        logger.warning("capture not started: NULL_TRACER is shared")
        return False


# Shared disabled tracer for call sites that will never trace. Whatever
# may be asked for a capture later (Trainer, ContinuousScheduler,
# ChatServer, StepwiseDecoder) builds its own `SpanTracer(enabled=False)`
# instead, which costs the same while off.
NULL_TRACER = _SharedNullTracer(enabled=False)
