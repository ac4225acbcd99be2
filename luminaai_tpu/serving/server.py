"""HTTP serving for chat/completion — stdlib only.

The reference deploys its chat model behind an HTTP backend
(ref: Dockerfile.backend — Flask server on :5001 with a /health check,
docker-compose.dev.yml wiring; the Electron desktop app in package.json
talks to it). This is that surface, TPU-side: a ThreadingHTTPServer wrapping
GenerationEngine. Generation requests ride CONTINUOUS BATCHING: a
ContinuousScheduler owns a step-wise decode loop over a slot-paged KV
pool (engine.make_stepwise), admitting queued requests into slots freed
by finished ones at every token step — no lane ever idles behind a
slower request, and mixed max_new_tokens workloads share one decode
executable. The security stack (auth, rate limiting, input validation)
is optional on the same endpoints.

Endpoints:
  GET  /health            liveness + model info (ref HEALTHCHECK contract)
  GET  /healthz           readiness: 503 while the engine is warming/
                          compiling, 200 + scheduler state once serving
                          (the Dockerfile HEALTHCHECK target)
  GET  /metrics           Prometheus text exposition of the process
                          registry (serving histograms, KV-pool gauges,
                          training counters when colocated)
  GET  /stats             session counters
  POST /v1/generate       {"prompt": str, "max_new_tokens"?, "temperature"?,
                           "top_p"?, "top_k"?} → {"text", "tokens", ...}
  POST /v1/chat           {"messages": [{"role","content"},...]} or
                           {"message": str} → {"reply", ...}
  POST /v1/auth           {"user","password"} → {"token"} (secure mode)

Both generation endpoints accept {"stream": true} and then respond as
text/event-stream: one `data: {"token", "delta"}` frame per generated
token, a final `data: {"done": true, <text|reply>, tokens, latency_s,
stopped}` frame, and a `data: [DONE]` terminator (the scheduler's
submit_stream: the lane's tokens as each decode step yields them).
{"speculative": true} composes with both shapes on greedy requests: the
JSON path runs generate_speculative, the SSE path streams the
draft/verify loop (generate_stream_speculative, tokens in
accepted-prefix bursts, verify stats on the done frame); ineligible or
slot-starved requests silently take the normal path.

No flask/fastapi in the image — http.server keeps the component
dependency-free and testable in-process.
"""

from __future__ import annotations

import contextlib
import inspect
import json
import logging
import math
import queue
import re
import threading
import time
import uuid
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Any, Dict, List, Optional, Tuple

from luminaai_tpu.monitoring.events import FlightRecorder, get_recorder
from luminaai_tpu.monitoring.slo import SLOEngine, build_slo_stack
from luminaai_tpu.monitoring.timeseries import (
    TimeSeriesRing,
    get_history,
    set_history,
)
from luminaai_tpu.monitoring.watchdog import (
    HangWatchdog,
    ProcessPauses,
    StepTimeSentinel,
    TickStalls,
)
from luminaai_tpu.monitoring.telemetry import (
    DEFAULT_LATENCY_BUCKETS,
    MetricsRegistry,
    get_registry,
    register_build_info,
    weak_callback,
)
from luminaai_tpu.monitoring.goodput import (
    SERVE_TICK_PHASES,
    ThreadPhaseLedger,
)
from luminaai_tpu.monitoring.tracing import SpanTracer
from luminaai_tpu.security.auth import ANON_TENANT, tenant_hash

logger = logging.getLogger(__name__)

MAX_BODY_BYTES = 1 << 20  # 1MB request cap (input_validator also re-checks)

# Shape an inbound X-Request-Id must match to be honored (router-minted
# ids are 12 hex chars; anything else sane is fine, garbage is not).
REQUEST_ID_RX = re.compile(r"[A-Za-z0-9_-]{1,64}")

# Chain keys are sha256 hex (inference/prefix_cache.page_chain_keys);
# the page-export route rejects anything else before touching the cache.
PAGE_KEY_RX = re.compile(r"[0-9a-f]{64}")


def new_request_id() -> str:
    """Per-request correlation id: short enough for log lines and SSE
    frames, random enough to never collide within a flight record."""
    return uuid.uuid4().hex[:12]


class RequestTimeout(Exception):
    """A request's deadline passed before it finished: the scheduler
    evicted its lane (or refused admission). Blocking submits surface it
    as HTTP 504; SSE streams get an error frame (docs/resilience.md)."""


# The two rules that take turns for a tick's prefill chunk, by the parity
# of the chunks dispatched so far (pick_prefill).
PICK_RULES = ("oldest", "shortest")


def pick_prefill(remaining, turn: int) -> Tuple[int, str]:
    """Which runnable admission gets the tick's one prefill chunk:
    (its index in `remaining`, the rule that chose it). `remaining` is
    the chunks each runnable admission still has to run, in admission
    order; `turn` counts the chunks dispatched so far. On an even turn
    the OLDEST admission (index 0), on an odd one the one with the
    FEWEST chunks left (ties to the oldest). With one runnable
    admission both rules name it.

    Fewest-left-first alone gives the best mean time to a prompt's last
    chunk and starves a long prompt behind a stream of short ones;
    oldest-first alone bounds every wait and gives back little of the
    mean; round-robin (every admission a chunk in turn) finishes them
    all late together. Taking turns keeps most of the first's mean under
    the second's bound: the oldest admission gets at least every second
    chunk, so it waits at most twice the chunks of the admissions ahead
    of it in admission order, whatever arrives behind it."""
    rule = PICK_RULES[turn % 2]
    if rule == "oldest":
        return 0, rule
    return min(range(len(remaining)), key=remaining.__getitem__), rule


class _ContinuousRequest:
    """One in-flight request inside the ContinuousScheduler: its prompt,
    resolved budgets, and the sink its tokens stream into (a Queue for
    SSE streams, an Event + result for blocking submits)."""

    def __init__(self, prompt, max_new, sample_key, seed, stream,
                 deadline=None, request_id=None, tenant=ANON_TENANT,
                 t_submit=None):
        self.prompt = list(prompt)
        self.max_new = int(max_new)
        self.sample_key = sample_key
        self.seed = seed
        self.deadline = deadline  # absolute wall time; None = no limit
        # Identity for the wide-event trail and per-tenant accounting:
        # every lifecycle event this request produces carries both.
        self.request_id = request_id or new_request_id()
        self.tenant = tenant or ANON_TENANT
        self.stream = bool(stream)
        self.sink: "queue.Queue" = queue.Queue() if stream else None
        self.event = None if stream else threading.Event()
        self.result = None
        self.error: Optional[BaseException] = None
        self.tokens: List[int] = []
        self.cancelled = False
        self.done = False
        self.slot: Optional[int] = None
        self.prompt_tokens = 0
        self.admitted_step: Optional[int] = None
        # WALL time, for the deadline and the events' `ts` alone: no
        # duration is taken from it.
        self.t0 = time.time()
        # The request's life. Every instant is read from ONE monotonic
        # clock, the scheduler's `_clock` (submit() reads the same
        # function on the caller's thread; the rest are the worker's),
        # so the stages add up to the whole, per request and in the
        # histograms' sums:
        #   t_submit -> t_admit        queue wait   (slot acquired)
        #   t_admit -> t_first_chunk   prefill wait (its first pick)
        #   t_first_chunk -> t_last_chunk  prefill ride (its chunks' ticks)
        #   t_last_chunk -> t_first_token  first-token lag (that tick on
        #       the device, read one dispatch later: the lookahead)
        #   t_first_token -> t_finish  decode
        # None = not reached. A span's wall-clock `ts` is derived once,
        # where the spans are written (_write_request_spans).
        self.t_submit = time.monotonic() if t_submit is None else t_submit
        self.t_admit: Optional[float] = None
        self.t_first_chunk: Optional[float] = None
        self.t_last_chunk: Optional[float] = None
        self.t_first_token: Optional[float] = None
        self.t_finish: Optional[float] = None
        # Chunks that rode a tick; steps dispatched before admission and
        # from there through the last chunk's (ticks - chunks = the
        # turns it waited while other admissions' chunks rode).
        self.chunks = 0
        self.dispatched_at_admit = 0
        self.ticks = 0

    @property
    def turns_waited(self) -> int:
        return self.ticks - self.chunks


class _StepAtCollect:
    """The split step API (dispatch_step / collect_step /
    steps_in_flight / abandon_steps, StepwiseDecoder's) over a
    duck-typed decoder that has `decode_step()` alone: the dispatch only
    notes the key, and refuses while a step is noted, so nothing runs
    ahead and the scheduler's one loop steps such a decoder in the
    order admit, step, emit. A chunk handed to the dispatch is a call
    of its own there (`advance_prefill`), made at once; `lanes()` is
    the scheduler's count of live lanes, and with none nothing is
    noted."""

    def __init__(self, decoder, lanes):
        self.decoder = decoder
        self._lanes = lanes
        self._keys: List[Any] = []

    @property
    def steps_in_flight(self) -> int:
        return len(self._keys)

    def dispatch_step(self, sample_key=None, chunk=None) -> bool:
        if chunk is not None:
            info = self.decoder.advance_prefill(chunk)
            if info is not None:
                chunk["info"] = info
        if self._keys or not self._lanes():
            return False
        self._keys.append(sample_key)
        return True

    def collect_step(self):
        return self.decoder.decode_step(self._keys.pop())

    def abandon_steps(self) -> None:
        self._keys.clear()


class ContinuousScheduler:
    """Continuous (in-flight) batching over a slot-paged KV pool.

    Serves engines exposing the step-wise decode API
    (GenerationEngine.make_stepwise): a single worker owns the decode
    loop, and EVERY step it (1) frees the
    slots of finished lanes, (2) admits queued requests into freed slots
    (prefill-then-join), and (3) advances all active lanes one token in
    one jit call. Early finishers stop costing chip steps the moment they
    stop, p50 latency decouples from the slowest request in flight, and —
    because max_new is host state, not a compile key — mixed-length
    workloads share one decode executable instead of splitting into
    per-length micro-batches.

    The loop looks ONE step ahead: it dispatches step N+1 before it
    reads step N's tokens, so everything the host does about step N
    (the fetch, the per-lane emit, admission) happens while the device
    runs N+1. A tick is ONE program: one prefill chunk rides the step, in
    the lanes' forward pass, and it goes by turns to the oldest admission
    mid-prefill and to the one with the fewest chunks left (_next_chunk,
    pick_prefill; _run_generation_inner; docs/serving.md "The scheduler
    loop").

    Sampling parameters DO remain a compile key (the sampling math traces
    them), so one "generation" admits only requests with an identical
    resolved sampling key; a mismatched request parks in `_pending`, new
    admissions pause, the active lanes drain, and the scheduler switches
    keys — bounded-latency FIFO across keys rather than starvation.

    Tokens stream out per-slot as they decode: `submit()` blocks until
    the lane ends, `submit_stream()` returns a generator with the engine
    generate_stream contract (ints, then a stats dict) that the SSE path
    consumes; closing it cancels the lane at the next step, so a gone
    client stops costing decode immediately.
    """

    def __init__(
        self,
        engine,
        num_slots: int = 8,
        page_size: int = 128,
        admission_window_ms: float = 0.0,
        max_slot_tokens: Optional[int] = None,
        decoder=None,
        registry: Optional[MetricsRegistry] = None,
        tracer: Optional[SpanTracer] = None,
        telemetry: bool = True,
        latency_buckets=DEFAULT_LATENCY_BUCKETS,
        request_timeout_s: Optional[float] = None,
        recorder: Optional[FlightRecorder] = None,
        max_tenants: int = 64,
        tick_every: int = 16,
        prefill_chunk_tokens: Optional[int] = None,
        prefix_cache_pages: Optional[int] = None,
        prefix_cache_tenant_quota: Optional[int] = None,
        tenant_weights: Optional[Dict[str, int]] = None,
        watchdog: Optional[HangWatchdog] = None,
        page_share=None,
        clock=time.monotonic,
    ):
        self.engine = engine
        # Clock of the scheduler thread's phase ledger (tests inject one).
        self._clock = clock
        # Hang watchdog (monitoring/watchdog.py): armed per generation,
        # beaten once per decode step — a stuck decode executable fires
        # hang_suspected + serving_hangs_total and dumps forensics
        # (abort semantics are the watchdog's, not the scheduler's).
        self.watchdog = watchdog
        # Default per-request deadline; a request's own timeout_s can only
        # shorten it. None = no deadline unless the request asks for one.
        self.request_timeout_s = request_timeout_s
        if decoder is None:
            kw = dict(
                num_slots=num_slots,
                page_size=page_size,
                max_slot_tokens=max_slot_tokens,
            )
            # Duck-typed engines may predate the chunked-prefill /
            # prefix-cache kwargs: inspect the signature instead of
            # catching TypeError, which would also swallow genuine
            # constructor errors.
            try:
                accepted = set(
                    inspect.signature(engine.make_stepwise).parameters
                )
            except (TypeError, ValueError):
                accepted = set()
            if "prefill_chunk_tokens" in accepted:
                kw["prefill_chunk_tokens"] = prefill_chunk_tokens
            if "prefix_cache_pages" in accepted:
                kw["prefix_cache_pages"] = prefix_cache_pages
                kw["prefix_cache_tenant_quota"] = prefix_cache_tenant_quota
            decoder = engine.make_stepwise(**kw)
        self.decoder = decoder
        # What the loop steps through: the decoder's own dispatch /
        # collect halves, or decode_step() behind the same four names.
        self._steps = (
            decoder if hasattr(decoder, "dispatch_step")
            else _StepAtCollect(decoder, lambda: self._active_lanes)
        )
        # Cross-replica page plane (serving/page_share.py): inject the
        # client into the decoder so cold admissions consult the fleet
        # index; only meaningful when the decoder actually has a prefix
        # cache to land pulled pages in.
        self.page_share = page_share
        pool = getattr(decoder, "pool", None)
        if page_share is not None and getattr(pool, "keeps_state", False):
            from luminaai_tpu.inference.kv_pool import StateNotPagedError

            raise StateNotPagedError(
                "page pull (page_share) is not served with 'ssm' layers: "
                "a pulled page holds k/v rows and none of the recurrent "
                "state a lane keeps beside them"
            )
        if page_share is not None and getattr(pool, "ring_pages", 0):
            from luminaai_tpu.inference.kv_pool import RingKeepsWindowError

            raise RingKeepsWindowError(
                "page pull (page_share) is not served beside a ring of "
                "pages (Config.layer_windows): a ring keeps no page older "
                "than the window, so a pulled chain would find its window "
                "layers' pages overwritten"
            )
        if page_share is not None and (
            getattr(decoder, "prefix_cache", None) is not None
        ):
            decoder.page_share = page_share
        # Whether the decoder's chunked admission accepts the tenant
        # rider (the prefix cache attributes pages per tenant).
        try:
            self._prefill_takes_tenant = "tenant" in inspect.signature(
                decoder.start_prefill
            ).parameters
        except (AttributeError, TypeError, ValueError):
            self._prefill_takes_tenant = False
        # Fair-share admission (tenant QoS): queued requests park in
        # per-tenant FIFOs and are dequeued WEIGHTED ROUND-ROBIN across
        # tenants, so one hot tenant flooding the intake cannot starve
        # the rest. tenant_weights maps tenant LABEL (hashed identity) ->
        # dequeues per round (priority lanes: weight n tenants drain up
        # to n requests per rotation); default weight 1.
        self.tenant_weights: Dict[str, int] = {
            str(k): max(1, int(v))
            for k, v in (tenant_weights or {}).items()
        }
        self._tq: Dict[str, Any] = {}  # tenant -> deque of requests
        self._rr: List[str] = []  # round-robin rotation order
        self._credits: Dict[str, int] = {}  # WRR dequeues used this turn
        # The worker owns _tq's CONTENTS, but queue_depth() iterates it
        # from request threads (_shed) and /metrics scrapes — guard the
        # dict's shape so a new tenant's insert can never crash a
        # concurrent depth read with "dict changed size during
        # iteration".
        self._tq_lock = threading.Lock()
        # Admissions mid-prefill: slot -> (request, decoder chunk state),
        # in admission order (never re-ordered: an entry is inserted at
        # admission and deleted at its end). ONE chunk a tick rides the
        # decode step the worker dispatches (_next_chunk), so a long
        # prompt costs concurrent lanes no forward pass of its own; an
        # entry stays until its first token is read (_first_tokens).
        self._prefilling: Dict[int, Tuple[Any, Any]] = {}
        # Chunks dispatched so far: the turn pick_prefill alternates on.
        # It advances only when a chunk rides a step (_book_chunk), so
        # ticks without one do not shift the phase.
        self._chunk_turn = 0
        self.q: "queue.Queue" = queue.Queue()
        self.window = max(0.0, float(admission_window_ms)) / 1000.0
        # /stats names: batches = generations (one sampling key each),
        # max_batch_seen = peak concurrent lanes.
        self.batches = 0
        self.max_batch_seen = 0
        self.requests_served = 0
        self._pending: List[_ContinuousRequest] = []
        self._busy = False  # a generation cycle is running right now
        # Submit-to-terminal request count: covers the dequeue→prefill
        # window where a request is in neither the queue nor a lane.
        self._inflight = 0
        self._inflight_lock = threading.Lock()
        # Wide-event flight recorder (monitoring/events.py): request
        # lifecycle events keyed by request_id + tenant. Rides the same
        # off switch as the metrics so the overhead A/B stays honest.
        self.recorder = recorder if recorder is not None else get_recorder()
        self.max_tenants = max(1, int(max_tenants))
        self.tick_every = max(1, int(tick_every))
        # Liveness stamp for /healthz staleness: wall ts of the last
        # completed decode step. None until the first tick (an idle
        # scheduler is not stale — only a busy one that stopped ticking).
        self.last_tick_ts: Optional[float] = None
        self._init_telemetry(registry, tracer, telemetry, latency_buckets)
        self._worker = threading.Thread(target=self._loop, daemon=True)
        self._worker.start()
        if self.telemetry:
            self._watch_stalls()

    def _watch_stalls(self) -> None:
        """Join the process-wide collector hook and heartbeat (one a
        process, shared with every other scheduler and trainer) and book
        this thread's flagged ticks against them (TickStalls).

        AFTER the worker thread is started, not before: the heartbeat is
        a thread too, and on the TPU host a thread started between the
        runtime's start and the worker's made the worker's loads of
        compiled programs three times slower (a warm set-up 8 s longer,
        whatever that thread then did; PERF.md section 6, PRs 59-61).
        The worker runs no tick before a request is submitted, and
        none is before __init__ returns."""
        self._pauses = ProcessPauses.start(self.registry, self.tracer)
        self._stalls = TickStalls(self.registry, self._phases, self._pauses)

    def close(self) -> None:
        """Leave the process-wide pause recorder: its last starter takes
        the hook off `gc.callbacks` and joins the heartbeat thread
        (idempotent). The worker is a daemon thread and stays; ticks it
        still runs book their stalls against pauses that stand still."""
        pauses, self._pauses = self._pauses, None
        if pauses is not None:
            pauses.close(self.registry, self.tracer)

    def _init_telemetry(self, registry, tracer, telemetry, buckets) -> None:
        """Registry wiring: per-request latency histograms (recorded on
        the hot path only when `telemetry` — the off switch is the A/B
        for the overhead budget test) plus pull-time KV-pool gauges,
        which cost nothing until /metrics is scraped."""
        self.telemetry = bool(telemetry)
        self.registry = registry or get_registry()
        # A tracer of its own when none is given, so that a capture can
        # be switched on later (SpanTracer.start_capture); off, it costs
        # what the shared NULL_TRACER does.
        self.tracer = (
            tracer if tracer is not None else SpanTracer(enabled=False)
        )
        r = self.registry
        # Phase ledger of the scheduler THREAD (docs/observability.md
        # "Tracing"): exactly one of put / dispatch / device_wait / sched
        # / queue_idle accrues at any instant, so the five
        # serve_tick_*_seconds_total counters partition the thread's
        # elapsed time. The decoder switches the first three around its
        # transfers, program calls and device reads; a switch is a clock
        # read and a float add, and the counters are written once a tick
        # (publish()). Rides the telemetry off switch like every other
        # hot-path metric.
        self._phases = ThreadPhaseLedger(
            SERVE_TICK_PHASES, "serve_tick_{cause}_seconds_total",
            registry=r, clock=self._clock, enabled=self.telemetry,
        )
        for attr, mine in (("tracer", self.tracer),
                           ("phases", self._phases)):
            if hasattr(self.decoder, attr):
                setattr(self.decoder, attr, mine)
        self._m_queue_wait = r.histogram(
            "serve_queue_wait_seconds",
            "Submit-to-admission wait (slot contention + key parking)",
            buckets=buckets,
        )
        self._m_prefill = r.histogram(
            "serve_prefill_seconds",
            "prefill_into_slot duration (prompt KV write + first token)",
            buckets=buckets,
        )
        self._m_ttft = r.histogram(
            "serve_ttft_seconds",
            "Submit-to-first-token latency per request",
            buckets=buckets,
        )
        # serve_prefill_seconds by stage, observed with it (once a
        # request, _prefill_done): queue wait + these three = TTFT.
        self._m_prefill_wait = r.histogram(
            "serve_prefill_wait_seconds",
            "Admission to the request's first prefill chunk dispatched "
            "(its first pick among the admissions mid-prefill; a dedup "
            "follower's park)",
            buckets=buckets,
        )
        self._m_prefill_ride = r.histogram(
            "serve_prefill_ride_seconds",
            "First prefill chunk dispatched to last chunk dispatched "
            "(0 for a prompt of one chunk)",
            buckets=buckets,
        )
        self._m_first_token_lag = r.histogram(
            "serve_first_token_lag_seconds",
            "Last prefill chunk dispatched to first token booked (that "
            "tick on the device, read after the next one's dispatch)",
            buckets=buckets,
        )
        self._m_turns_waited = r.counter(
            "serve_prefill_turns_waited_total",
            "Admissions with a chunk runnable that did not get a tick's "
            "chunk rows, summed over the ticks that carried a chunk",
        )
        # How often the order of service engages (pick_prefill): the
        # two rules' picks add up to serving_prefill_chunks_total.
        picks = r.counter(
            "serve_prefill_picks_total",
            "Prefill chunks dispatched, by the rule whose turn chose "
            "the admission (oldest / shortest = fewest chunks left)",
            labelnames=("rule",),
        )
        self._m_picks = {rule: picks.labels(rule=rule) for rule in PICK_RULES}
        self._m_picks_not_oldest = r.counter(
            "serve_prefill_picks_not_oldest_total",
            "Prefill chunks that went to another admission than the "
            "oldest runnable one (0 while one admission prefills at a "
            "time)",
        )
        self._m_step = r.histogram(
            "serve_decode_step_seconds",
            "One scheduler decode step (all active lanes, one jit call)",
            buckets=buckets,
        )
        self._m_token = r.histogram(
            "serve_token_latency_seconds",
            "Per-token decode latency (step duration, one observation "
            "per lane that produced a token)",
            buckets=buckets,
        )
        # Step-time anomaly sentinel (docs/observability.md "Goodput &
        # sentinels"): robust rolling median/MAD over decode-step
        # durations — serve_decode_step_seconds_{median,mad} gauges plus
        # step_anomaly events when one step blows past the distribution.
        self._sentinel = StepTimeSentinel(
            registry=r,
            recorder=self.recorder if self.telemetry else None,
            prefix="serve_decode_step_seconds",
            program="serve",
        )
        # What a flagged tick is booked under (serve_tick_stall_*):
        # _watch_stalls, once the worker thread exists.
        self._pauses: Optional[ProcessPauses] = None
        self._stalls: Optional[TickStalls] = None
        # Rows of the chunk that rides the step in flight ahead.
        self._chunk_rows_ahead = 0
        self._m_admissions = r.counter(
            "serve_admissions_total", "Requests admitted into a KV slot"
        )
        self._m_evictions = r.counter(
            "serve_evictions_total",
            "Slots released (finished, cancelled, or failed lanes)",
        )
        self._m_generations = r.counter(
            "serve_generations_total",
            "Generations started (one sampling key each)",
        )
        self._m_decode_steps = r.counter(
            "serve_decode_steps_total", "Scheduler decode steps executed"
        )
        # The lookahead, as it engages: under load nearly every step is
        # dispatched ahead, and only a lane that ended where the host
        # could not foresee it (a stop token, a cancel, an eviction)
        # has a step dropped.
        self._m_steps_ahead = r.counter(
            "serve_steps_dispatched_ahead_total",
            "Decode steps enqueued on the device while the previous "
            "step's tokens were not yet read",
        )
        self._m_lane_steps_dropped = r.counter(
            "serve_lane_steps_dropped_total",
            "Lane-steps whose token was dropped: the lane ended while "
            "the step was in flight",
        )
        # A chunk rides the decode step (one program a tick): how many
        # did so beside at least one stepped lane, and the prompt rows
        # they carried.
        self._m_chunks_carried = r.counter(
            "serve_chunks_carried_total",
            "Prefill chunks that rode a decode step in which at least "
            "one lane was stepped",
        )
        self._m_chunk_rows = r.counter(
            "serve_chunk_rows_total",
            "Live prompt rows carried by prefill chunks",
        )
        # State-space layers (an 'ssm' mixer): rows that went through
        # the recurrence. 0 without such layers.
        self._m_ssm_rows = r.counter(
            "ssm_rows_total",
            "Live rows through the state-space recurrence: stepped lanes "
            "and live rows of prefill chunks",
        )
        self._m_ssm_state_bytes = r.counter(
            "ssm_state_bytes_total",
            "Bytes of recurrent state the ticks moved: each stepped "
            "lane's and each live chunk's state read and written, over "
            "the state-space layers (the host's count, from the pool's "
            "shapes)",
        )
        # Layers with a window of their own keep a ring of pages a lane
        # beside the full layers' whole pages: rows of k/v the ticks'
        # attention read in each kind, and the times a lane's rows came
        # round its ring. Counted from lengths the host has.
        self._m_kv_window_rows = r.counter(
            "serve_kv_window_rows_read_total",
            "Rows of k/v the ticks' attention read in layers with a "
            "window of their own (a ring of pages a lane), summed over "
            "those layers: whole rings where XLA attends the lanes, the "
            "stepped lanes' live key blocks where the decode kernel does",
        )
        self._m_kv_global_rows = r.counter(
            "serve_kv_global_rows_read_total",
            "Rows of k/v the ticks' attention read in full-attention "
            "layers, summed over those layers: every lane up to the "
            "tick's extent where XLA attends the lanes, the stepped "
            "lanes' live key blocks where the decode kernel does",
        )
        # The same reads in bytes: rows of two kinds of layer are not one
        # size (k/v heads a layer, a key kept in parts), so bytes are
        # what a tick's attention costs.
        self._m_kv_window_bytes = r.counter(
            "serve_kv_window_bytes_read_total",
            "Bytes of k/v behind serve_kv_window_rows_read_total: each "
            "layer's rows x that layer's own row bytes (k and v as "
            "stored)",
        )
        self._m_kv_global_bytes = r.counter(
            "serve_kv_global_bytes_read_total",
            "Bytes of k/v behind serve_kv_global_rows_read_total: each "
            "layer's rows x that layer's own row bytes (k and v as "
            "stored)",
        )
        # 'latent' layers keep pages of ONE row a token (LatentPages).
        self._m_kv_latent_rows = r.counter(
            "serve_kv_latent_rows_read_total",
            "Rows of one latent a token the lanes' attention read in "
            "'latent' layers, summed over those layers: every lane up to "
            "the tick's extent where XLA attends the lanes, the stepped "
            "lanes' live key blocks where the decode kernel does",
        )
        self._m_kv_latent_chunk_keys = r.counter(
            "serve_kv_latent_chunk_keys_total",
            "Stored latents the prefill chunks' attention spanned in "
            "'latent' layers (the chunk's lane up to the chunk's end), "
            "summed over those layers",
        )
        # The lanes' decode kernel (ops/ragged_paged_attention.py
        # lane_attention) skips by lane and by key block: how much of its
        # grid is live says how often the skip engages. Both stay 0 where
        # the shapes leave the lanes' attention to XLA.
        self._m_lane_blocks = r.counter(
            "serve_lane_attention_blocks_total",
            "Grid steps (lanes x key blocks) of the lanes' decode "
            "attention kernel, summed over attention layers and ticks",
        )
        self._m_lane_blocks_live = r.counter(
            "serve_lane_attention_blocks_live_total",
            "Grid steps of the lanes' decode attention kernel that "
            "fetched a block of k/v and computed",
        )
        self._m_ring_wraps = r.counter(
            "serve_ring_wraps_total",
            "Times a lane's next row was written onto the first row of "
            "its ring of pages again",
        )
        # One chip's share of the experts (Config.experts_held), served:
        # pairs of the ticks' live rows, summed over the expert layers.
        # They ride the step's token fetch. (Trainer feeds the same
        # counters a layer's mean at log cadence; the ratios agree.)
        self._m_routed_pairs = r.counter(
            "moe_routed_pairs_total", "Routed (token, expert) pairs"
        )
        self._m_held_pairs = r.counter(
            "moe_held_pairs_total",
            "Routed pairs that fell on an expert this program holds "
            "(Config.experts_held)",
        )
        self._m_held_dropped = r.counter(
            "moe_held_pairs_dropped_total",
            "Held pairs not computed: beyond the grouped matmul's static "
            "row bound (must stay 0)",
        )
        # Experts in a latent (Config.moe_latent_size): the held experts
        # at least one computed pair fell on, a layer a tick, of those
        # held: the weights the grouped matmuls read. Both stay 0
        # elsewhere.
        self._m_held_experts_hit = r.counter(
            "moe_held_experts_hit_total",
            "Held experts with at least one computed pair, summed over "
            "expert layers and ticks",
        )
        self._m_held_experts = r.counter(
            "moe_held_experts_total",
            "Held experts, summed over expert layers and ticks (the "
            "denominator of moe_held_experts_hit_total)",
        )
        # Which form the held layers' combine has in the tick program
        # (no series without a share of the experts).
        form = getattr(self.decoder, "held_combine", None)
        if form is not None:
            from luminaai_tpu.models.moe import export_held_combine

            export_held_combine(form, r, logger)
            self._event("moe_held_combine", **form)
        # What the tick program is specialised by shows in how many were
        # built: one a sampling key (`full`) where the decode kernel
        # attends every layer's lanes, one per page extent it met (the
        # rung's rows) where XLA attends them. Grows in warm-up, not
        # under load. (No `serve_tick_*` family with telemetry off, as
        # for the phase ledger's.)
        self._m_tick_programs = r.counter(
            "serve_tick_programs_built_total",
            "Tick programs the decoder built (a miss of its cache of "
            "jitted steps), by the rows of k/v the program is "
            "specialised by: `full` (the slot's whole pages) or a rung "
            "of the page-extent ladder",
            labelnames=("extent",),
        ) if self.telemetry else None
        self._tick_programs_seen: Dict[str, int] = {}
        # The decoder counts these where they happen; the registry
        # follows (_count_decoder).
        self._decoder_counters = (
            ("lane_steps_dropped", self._m_lane_steps_dropped),
            ("chunks_carried", self._m_chunks_carried),
            ("chunk_rows", self._m_chunk_rows),
            ("ssm_rows", self._m_ssm_rows),
            ("ssm_state_bytes", self._m_ssm_state_bytes),
            ("kv_window_rows", self._m_kv_window_rows),
            ("kv_global_rows", self._m_kv_global_rows),
            ("kv_window_bytes", self._m_kv_window_bytes),
            ("kv_global_bytes", self._m_kv_global_bytes),
            ("kv_latent_rows", self._m_kv_latent_rows),
            ("kv_latent_chunk_keys", self._m_kv_latent_chunk_keys),
            ("ring_wraps", self._m_ring_wraps),
            ("lane_attention_blocks", self._m_lane_blocks),
            ("lane_attention_blocks_live", self._m_lane_blocks_live),
            ("moe_routed_pairs", self._m_routed_pairs),
            ("moe_held_pairs", self._m_held_pairs),
            ("moe_held_pairs_dropped", self._m_held_dropped),
            ("moe_held_experts_hit", self._m_held_experts_hit),
            ("moe_held_experts", self._m_held_experts),
        )
        self._decoder_seen = {
            name: 0 for name, _ in self._decoder_counters
        }
        # Which attention path the decode step compiled (value is always
        # 1; the label is the payload): a scrape shows what served, not
        # what a config asked for.
        r.gauge(
            "serve_attention_backend",
            "Attention backend of the decoder behind this scheduler",
            labelnames=("backend",),
        ).labels(backend=str(getattr(self.decoder, "backend", "dense"))).set(1)
        self._m_timeouts = r.counter(
            "serving_requests_timed_out_total",
            "Requests evicted (or refused admission) because their "
            "deadline passed before completion",
        )
        self._m_prefill_chunks = r.counter(
            "serving_prefill_chunks_total",
            "Prefill chunks executed by the scheduler (chunked prefill "
            "interleaves these with decode steps)",
        )
        # Per-tenant accounting (bounded: max_tenants distinct labels,
        # then the registry's `_overflow` bucket — a tenant label can
        # never explode /metrics).
        self._m_tenant_ttft = r.histogram(
            "tenant_ttft_seconds",
            "Submit-to-first-token latency per tenant",
            buckets=buckets,
            labelnames=("tenant",),
            max_label_values=self.max_tenants,
        )
        self._m_tenant_timeouts = r.counter(
            "tenant_requests_timed_out_total",
            "Deadline-evicted (or admission-refused) requests per tenant",
            labelnames=("tenant",),
            max_label_values=self.max_tenants,
        )
        # Callback gauges hold WEAK refs: the process registry outlives
        # any one scheduler, and a strong closure would pin a replaced
        # scheduler's whole KV pool and export its stale state forever.
        r.gauge(
            "serve_active_lanes", "Lanes currently decoding"
        ).set_function(weak_callback(self, lambda s: s._active_lanes))
        r.gauge(
            "serve_queue_depth",
            "Requests waiting for admission (queued + key-parked)",
        ).set_function(weak_callback(self, lambda s: s.queue_depth()))
        self._active_lanes = 0
        pool = getattr(self.decoder, "pool", None)
        if pool is not None and hasattr(pool, "stats"):
            def pool_gauge(name, help_text, key):
                r.gauge(name, help_text).set_function(
                    weak_callback(pool, lambda p: p.stats().get(key, 0))
                )

            pool_gauge("kv_pool_slots_in_use", "KV pool slots allocated",
                       "in_use")
            pool_gauge("kv_pool_slots_free", "KV pool slots free", "free")
            pool_gauge("kv_pool_slot_reuses_total",
                       "Times a previously-used slot was re-issued",
                       "reuses")
            pool_gauge("serve_pool_rebuilds_total",
                       "Times the KV pool was rebuilt after a failed call "
                       "had taken its donated buffers (every in-flight "
                       "request failed once; the server kept serving)",
                       "rebuilds")
            pool_gauge("kv_pool_pages_in_use",
                       "Pages holding live KV rows", "pages_in_use")
            pool_gauge("kv_pool_pages_total", "Total pool pages",
                       "pages_total")
            pool_gauge(
                "kv_pool_fragmentation_rows",
                "Rows lost to page rounding (allocated but not live)",
                "fragmentation_rows",
            )
        # Prefix cache (inference/prefix_cache.py): hit/miss/savings
        # counters observed at admission, plus pull-time occupancy /
        # refcount / eviction gauges straight off the cache's stats.
        self._m_prefix_hits = r.counter(
            "serve_prefix_cache_hits_total",
            "Admissions that spliced at least one cached prefix page",
        )
        self._m_prefix_misses = r.counter(
            "serve_prefix_cache_misses_total",
            "Chunked admissions that found no cached prefix",
        )
        self._m_prefix_saved = r.counter(
            "serve_prefill_tokens_saved_total",
            "Prompt tokens whose prefill was skipped via cached prefix "
            "pages",
        )
        self._m_prefix_remote_hits = r.counter(
            "serve_prefix_remote_hits_total",
            "Admissions whose prefix hit rode pages pulled from another "
            "replica (cross-replica page sharing)",
        )
        # Tenant-keyed cache residency rides under the same label budget
        # as every other tenant series (`lumina analyze` LX009 enforces
        # the max_label_values declaration).
        self._m_tenant_prefix_pages = r.gauge(
            "tenant_prefix_cache_pages",
            "Arena pages currently cached per owning tenant",
            labelnames=("tenant",),
            max_label_values=self.max_tenants,
        )
        cache = getattr(self.decoder, "prefix_cache", None)
        if cache is not None:
            # prefix_evict flight events ride the scheduler's recorder,
            # honoring the same telemetry off switch.
            cache.recorder = self.recorder if self.telemetry else None

            def cache_gauge(name, help_text, key):
                r.gauge(name, help_text).set_function(
                    weak_callback(cache, lambda c: c.stats().get(key, 0))
                )

            cache_gauge("prefix_cache_pages_cached",
                        "Arena pages holding cached prefix KV",
                        "pages_cached")
            cache_gauge("prefix_cache_pages_free",
                        "Arena pages free for harvest", "pages_free")
            cache_gauge("prefix_cache_page_refs",
                        "Live lane references onto cached pages "
                        "(sharing fan-out)", "page_refs")
            cache_gauge("prefix_cache_evictions",
                        "Cached pages LRU-evicted since start",
                        "evictions")
            cache_gauge("prefix_cache_pages_budget",
                        "Configured arena page budget "
                        "(--prefix-cache-pages)", "capacity_pages")

    def queue_depth(self) -> int:
        with self._tq_lock:
            parked = sum(len(d) for d in self._tq.values())
        return self.q.qsize() + len(self._pending) + parked

    # -- fair-share tenant queues (worker thread only) ---------------------
    def _enqueue_tenant(self, req: "_ContinuousRequest") -> None:
        from collections import deque

        t = req.tenant or ANON_TENANT
        dq = self._tq.get(t)
        if dq is None:
            with self._tq_lock:
                dq = self._tq[t] = deque()
            self._rr.append(t)
        dq.append(req)

    def _drain_intake(self) -> None:
        """Move everything waiting on the intake queue into the
        per-tenant FIFOs (worker thread only — submit() threads touch
        only self.q)."""
        while True:
            try:
                self._enqueue_tenant(self.q.get_nowait())
            except queue.Empty:
                return

    def _next_queued(self) -> Optional["_ContinuousRequest"]:
        """Weighted round-robin dequeue across tenant queues: each
        rotation visits tenants in arrival order, draining up to
        `tenant_weights[t]` (default 1) requests before moving on —
        a tenant with 50 queued requests and a tenant with 1 alternate
        instead of the flood going first (contract-tested: the starved
        tenant's queue keeps draining under a hot-tenant flood)."""
        if not self._rr:
            return None
        # One WRR credit per call: rotate to the next tenant with work,
        # respecting per-tenant weight via a running credit counter.
        for _ in range(len(self._rr)):
            t = self._rr[0]
            dq = self._tq.get(t)
            if not dq:
                # Empty queue: drop the tenant from the rotation (it
                # re-registers on its next submit).
                self._rr.pop(0)
                with self._tq_lock:
                    self._tq.pop(t, None)
                self._credits.pop(t, None)
                continue
            used = self._credits.get(t, 0)
            if used + 1 >= self.tenant_weights.get(t, 1):
                # Weight exhausted after this dequeue: rotate.
                self._credits[t] = 0
                self._rr.append(self._rr.pop(0))
            else:
                self._credits[t] = used + 1
            return dq.popleft()
        return None

    def idle(self) -> bool:
        """No request anywhere between submit and its terminal
        finish/fail (drain completion). Counted submit-to-terminal, so
        the dequeue→prefill window — where a request is in neither the
        queue nor a lane — can never make drain() declare completion and
        shut the server down on top of the request it exists to
        protect."""
        with self._inflight_lock:
            return self._inflight == 0 and not self._busy

    def _track(self, req: _ContinuousRequest) -> _ContinuousRequest:
        with self._inflight_lock:
            self._inflight += 1
        return req

    def _untrack(self) -> None:
        with self._inflight_lock:
            self._inflight -= 1

    # -- public API --------------------------------------------------------
    def submit(
        self, prompt_tokens: List[int], gen_kwargs: Dict[str, Any]
    ) -> Tuple[List[int], Dict[str, Any]]:
        req = self._track(
            self._make_request(prompt_tokens, gen_kwargs, stream=False)
        )
        self.q.put(req)
        req.event.wait()
        if req.error is not None:
            raise req.error
        return req.result

    def submit_stream(
        self, prompt_tokens: List[int], gen_kwargs: Dict[str, Any]
    ):
        """Generator with the generate_stream contract: token ints as the
        lane decodes them, then one final stats dict. Closing it flags the
        request cancelled; the worker frees the slot at the next step."""
        req = self._track(
            self._make_request(prompt_tokens, gen_kwargs, stream=True)
        )
        self.q.put(req)

        def events():
            try:
                while True:
                    item = req.sink.get()
                    if isinstance(item, BaseException):
                        raise item
                    yield item
                    if isinstance(item, dict):
                        return
            finally:
                req.cancelled = True

        return events()

    def stats(self) -> Dict[str, Any]:
        out = {
            "scheduler": "continuous",
            "batches": self.batches,
            "max_batch_seen": self.max_batch_seen,
            "decode_steps": int(getattr(self.decoder, "steps", 0)),
            "active_lanes": self._active_lanes,
            "queue_depth": self.queue_depth(),
            "prefilling": len(self._prefilling),
        }
        pool = getattr(self.decoder, "pool", None)
        if pool is not None and hasattr(pool, "stats"):
            out["kv_pool"] = pool.stats()
        cache = getattr(self.decoder, "prefix_cache", None)
        if cache is not None:
            out["prefix_cache"] = cache.stats()
        return out

    # -- internals ---------------------------------------------------------
    def _make_request(self, prompt_tokens, gen_kwargs, stream):
        # Identity riders are host metadata, never compile keys: strip
        # them before sampling-key resolution so two tenants' otherwise
        # identical requests still share one decode executable.
        gen_kwargs = dict(gen_kwargs)
        request_id = gen_kwargs.pop("request_id", None)
        tenant = gen_kwargs.pop("tenant", None)
        resolve = getattr(self.engine, "_resolve_gen_key", None)
        if resolve is not None:
            key = resolve(
                gen_kwargs.get("max_new_tokens"),
                gen_kwargs.get("temperature"),
                gen_kwargs.get("top_p"),
                gen_kwargs.get("top_k"),
                gen_kwargs.get("repetition_penalty"),
            )
            max_new, sample_key = key[0], tuple(key[1:])
        else:  # duck-typed engines without the helper
            max_new = int(gen_kwargs.get("max_new_tokens") or 16)
            sample_key = tuple(
                sorted(
                    (k, v)
                    for k, v in gen_kwargs.items()
                    if k not in ("max_new_tokens", "seed", "timeout_s")
                )
            )
        cap = int(
            getattr(self.decoder, "token_capacity", 0)
            or getattr(self.decoder, "slot_tokens", 0)
        ) or None
        if cap:
            # A slot must hold prompt tail + budget; the prompt trims but
            # the budget can only clamp. token_capacity (not the page-
            # rounded slot size) keeps decode inside the engine's
            # max_context contract.
            max_new = max(1, min(max_new, cap - 1))
        timeout = gen_kwargs.get("timeout_s") or self.request_timeout_s
        if timeout and self.request_timeout_s:
            timeout = min(float(timeout), float(self.request_timeout_s))
        return _ContinuousRequest(
            prompt_tokens, max_new, sample_key,
            gen_kwargs.get("seed"), stream,
            deadline=(time.time() + float(timeout)) if timeout else None,
            request_id=request_id, tenant=tenant, t_submit=self._clock(),
        )

    def _emit(self, req: _ContinuousRequest, token: int) -> None:
        req.tokens.append(int(token))
        if req.stream:
            req.sink.put(int(token))

    def _event(self, type: str, req: Optional[_ContinuousRequest] = None,
               **fields) -> None:
        """Append one lifecycle event to the flight recorder, stamped
        with the request's identity. Same off switch as the metrics."""
        if not self.telemetry:
            return
        if req is not None:
            fields.setdefault("request_id", req.request_id)
            fields.setdefault("tenant", req.tenant)
        self.recorder.emit(type, **fields)

    def _finish(self, req: _ContinuousRequest, stopped: str) -> None:
        if req.done:
            return  # terminal already delivered
        dt = self._close_request(req, stopped)
        n = len(req.tokens)
        stats = {
            "tokens_generated": n,
            "seconds": round(dt, 3),
            "tokens_per_second": round(n / max(dt, 1e-9), 1),
            "prompt_tokens": req.prompt_tokens,
            "stopped": stopped,
            "slot": req.slot,
            "admitted_step": req.admitted_step,
            "finished_step": int(getattr(self.decoder, "steps", 0)),
            "scheduler": "continuous",
            "request_id": req.request_id,
            "tenant": req.tenant,
        }
        self.requests_served += 1
        req.done = True
        self._untrack()
        self._event(
            "request_completed", req,
            slot=req.slot, tokens=n, prompt_tokens=req.prompt_tokens,
            seconds=round(dt, 3), stopped=stopped,
            step=int(getattr(self.decoder, "steps", 0)),
        )
        if req.stream:
            req.sink.put(stats)
        else:
            req.result = (req.tokens, stats)
            req.event.set()

    def _fail(self, req: _ContinuousRequest, err: BaseException) -> None:
        if req.done:
            return  # terminal already delivered
        reason = "timeout" if isinstance(err, RequestTimeout) else "error"
        self._close_request(req, reason)
        req.done = True
        self._untrack()
        self._event(
            "request_evicted", req,
            slot=req.slot, tokens=len(req.tokens),
            reason=reason,
            error=str(err)[:200],
        )
        if req.stream:
            req.sink.put(err)
        else:
            req.error = err
            req.event.set()

    def _timeout(self, req: _ContinuousRequest, where: str) -> None:
        """Deadline enforcement: a lane past its deadline stops costing
        decode steps NOW (eviction frees the slot for queued work) and the
        client gets an explicit timeout instead of an open-ended wait."""
        if self.telemetry:
            self._m_timeouts.inc()
            self._m_tenant_timeouts.labels(tenant=req.tenant).inc()
        waited = self._clock() - req.t_submit
        self._fail(req, RequestTimeout(
            f"deadline exceeded after {waited:.1f}s ({where}; "
            f"{len(req.tokens)} tokens generated)"
        ))

    def _dispatched(self) -> int:
        """Steps the decoder has been handed so far: read and in flight."""
        return int(getattr(self.decoder, "steps", 0)) + (
            self._steps.steps_in_flight
        )

    def _close_request(self, req: _ContinuousRequest, stopped: str) -> float:
        """The one end of a request's life, finished, failed or timed
        out alike: the stage it was in closes here and nothing further
        is observed for it. Returns submit to now, in seconds."""
        req.t_finish = self._clock()
        if self.tracer.enabled:
            self._write_request_spans(req, stopped)
        return req.t_finish - req.t_submit

    def _write_request_spans(self, req: _ContinuousRequest,
                             stopped: str) -> None:
        """A request as spans of its own, under a trace id of its own:
        the root `request` and one child a stage it reached, written
        from the stamps, so a capture switched on mid-request has the
        true starts. The stamps are monotonic; `ts` is wall time, the
        JSONL's and `capture_clock`'s: one offset, taken here. The ticks
        that carried its chunks are the `decode_step` spans whose
        `chunk_request_id` is its id."""
        record = self.tracer.record
        end = req.t_finish
        wall = time.time() - self._clock()
        root = record(
            "request", wall + req.t_submit, end - req.t_submit,
            request_id=req.request_id, tenant=req.tenant, slot=req.slot,
            prompt_tokens=req.prompt_tokens or len(req.prompt),
            tokens=len(req.tokens), stopped=stopped,
        )
        ride = {"chunks": req.chunks, "ticks": req.ticks,
                "turns_waited": req.turns_waited}
        for name, start, stop, attrs in (
            ("req.queued", req.t_submit, req.t_admit, {}),
            ("req.prefill_wait", req.t_admit, req.t_first_chunk, {}),
            ("req.prefill_ride", req.t_first_chunk, req.t_last_chunk, ride),
            ("req.first_token", req.t_last_chunk, req.t_first_token, {}),
            ("req.decode", req.t_first_token, end, {}),
        ):
            if root is None or start is None:
                break  # switched off under us, or a stage never reached
            stop = end if stop is None else stop
            record(name, wall + start, stop - start, root, **attrs)

    def _release_slot(self, slot: int) -> None:
        """Single choke point for giving a slot back: the decoder free +
        the eviction count must never drift apart across the four
        release sites."""
        self.decoder.release_slot(slot)
        if self.telemetry:
            self._m_evictions.inc()

    def _release(self, req: _ContinuousRequest, active: dict) -> None:
        self._release_slot(req.slot)
        active.pop(req.slot, None)
        self._active_lanes = len(active)

    def _fail_all(self, active: dict, err: BaseException) -> None:
        """Fail every admitted request, decoding or mid-prefill, and
        give their slots back."""
        for r in list(active.values()):
            self._release(r, active)
            self._fail(r, err)
        for slot, (r, *_) in list(self._prefilling.items()):
            self._release_slot(slot)
            self._fail(r, err)
        self._prefilling.clear()

    def _pool_lost(self, active: dict, err: BaseException) -> bool:
        """After an exception out of a decoder call that rewrites the
        KV pool. Those programs donate the pool, so a call that failed
        once the runtime had taken the buffers leaves none: the decoder
        then rebuilds it (StepwiseDecoder.recover_pool), every lane's KV
        is gone, and every admitted request fails with `err`: the
        server keeps serving from the new pool. With the buffers still
        alive (the call failed before the runtime took them) this does
        nothing and the caller fails only what the call was for."""
        recover = getattr(self.decoder, "recover_pool", None)
        if recover is None or not recover():
            return False
        self._steps.abandon_steps()  # nothing of the old pool is read
        in_flight = len(active) + len(self._prefilling)
        logger.error(
            "KV pool lost to a failed call and rebuilt; failing %d "
            "in-flight request(s)", in_flight,
        )
        self._event("pool_rebuilt", error=str(err)[:200], failed=in_flight)
        self._fail_all(active, err)
        return True

    def _admit(self, req: _ContinuousRequest, active: dict) -> None:
        """Prefill-then-join: the request's prompt KV lands in a freed
        slot and its first token streams out immediately; the lane joins
        the shared decode from the next step."""
        if req.cancelled:
            self._finish(req, "cancelled")
            return
        if req.deadline is not None and time.time() > req.deadline:
            # Expired while queued (slot contention / key parking): refuse
            # admission rather than spend prefill on a dead request.
            self._timeout(req, "while queued")
            return
        with self._wd_pause():
            self._admit_paused(req, active)

    def _admit_paused(self, req: _ContinuousRequest, active: dict) -> None:
        """_admit's body, under the watchdog pause: the prefill below can
        hit a first-use XLA compile (new prompt bucket) that dwarfs the
        rolling decode-step stats. The pause lives HERE — exactly where
        prefill work happens — not per tick: pausing on a merely-nonempty
        queue would exclude every interval on a saturated server and
        starve the warmup, leaving real decode hangs undetectable."""
        try:
            slot = self.decoder.acquire_slot()
        except Exception as e:  # its defensive harvest flush lost the pool
            logger.exception("slot acquisition failed")
            self._pool_lost(active, e)
            self._fail(req, e)
            return
        req.slot = slot
        req.t_admit = self._clock()
        req.dispatched_at_admit = self._dispatched()
        queue_wait = req.t_admit - req.t_submit
        with self.tracer.span(
            "sched.admit", request_id=req.request_id, slot=slot,
            prompt_tokens=len(req.prompt),
            queue_wait_s=round(queue_wait, 4),
        ):
            info = self._admit_into_slot(req, slot, queue_wait, active)
        if info is not None:
            self._prefill_done(req, slot, info, active)

    def _admit_into_slot(self, req, slot, queue_wait, active):
        """The `sched.admit` span's body: admission bookkeeping, then
        `start_prefill` (chunked path: None, the chunks run from the
        worker loop) or the whole-prompt `prefill_into_slot` (its info).
        None too when the admission failed (request failed, slot
        released)."""
        if self.telemetry:
            # Queue wait = submit to slot acquisition: covers both slot
            # contention and sampling-key parking.
            self._m_queue_wait.observe(queue_wait)
            self._m_admissions.inc()
        self._event(
            "request_admitted", req,
            slot=slot, queue_wait_s=round(queue_wait, 4),
            prompt_tokens=len(req.prompt),
            step=int(getattr(self.decoder, "steps", 0)),
        )
        start = getattr(self.decoder, "start_prefill", None)
        if start is not None and getattr(self.decoder, "prefill_chunk", 0):
            try:
                st = start(
                    slot,
                    req.prompt,
                    max_new_tokens=req.max_new,
                    sample_key=req.sample_key,
                    seed=req.seed,
                    # Tenant rider: the prefix cache attributes harvested
                    # pages per tenant (quota enforcement).
                    **(
                        {"tenant": req.tenant}
                        if self._prefill_takes_tenant
                        else {}
                    ),
                )
            except Exception as e:
                logger.exception("start-prefill failed")
                self._release_slot(slot)
                self._pool_lost(active, e)
                self._fail(req, e)
                return None
            if st is not None:
                # Its chunks ride the decode steps the worker loop
                # dispatches, one a tick (_next_chunk). A dedup follower
                # parked behind its leader has none to run yet.
                self._prefilling[slot] = (req, st)
                return None
        # The whole prompt in one call of its own: no chunk waits for a
        # turn or rides a tick, so all of it is first-token lag.
        req.t_first_chunk = req.t_last_chunk = req.t_admit
        try:
            with self.tracer.span(
                "prefill", slot=slot, prompt_tokens=len(req.prompt)
            ):
                return self.decoder.prefill_into_slot(
                    slot,
                    req.prompt,
                    max_new_tokens=req.max_new,
                    sample_key=req.sample_key,
                    seed=req.seed,
                )
        except Exception as e:
            logger.exception("prefill-into-slot failed")
            self._release_slot(slot)
            self._pool_lost(active, e)
            self._fail(req, e)
            return None

    def _prefill_done(self, req, slot, info, active) -> None:
        """Shared prompt-prefilled tail for the whole-prompt and chunked
        admission paths: TTFT booking, first-token emission, lane
        activation (or immediate finish). The request's stages are
        observed here, once, from its stamps: serve_prefill_seconds is
        admission to first token on both paths and equals
        serve_prefill_wait_seconds + serve_prefill_ride_seconds +
        serve_first_token_lag_seconds; with serve_queue_wait_seconds
        before them that is serve_ttft_seconds (a chunk has no forward
        pass, so no compute time, of its own: its ticks are the ride)."""
        now = req.t_first_token = self._clock()
        wait = req.t_first_chunk - req.t_admit
        ride = req.t_last_chunk - req.t_first_chunk
        lag = now - req.t_last_chunk
        prefill_s = now - req.t_admit
        ttft = now - req.t_submit
        if self.telemetry:
            self._m_prefill_wait.observe(wait)
            self._m_prefill_ride.observe(ride)
            self._m_first_token_lag.observe(lag)
            self._m_prefill.observe(prefill_s)
            # First token is sampled inside prefill, so TTFT lands here.
            self._m_ttft.observe(ttft)
            self._m_tenant_ttft.labels(tenant=req.tenant).observe(ttft)
        self._event(
            "request_prefill", req, slot=slot,
            prefill_s=round(prefill_s, 4),
            prompt_tokens=int(info.get("prompt_tokens", 0)),
        )
        self._event(
            "request_first_token", req, slot=slot, ttft_s=round(ttft, 4),
            prefill_wait_s=round(wait, 4), prefill_ride_s=round(ride, 4),
            first_token_lag_s=round(lag, 4), chunks=req.chunks,
            turns_waited=req.turns_waited,
        )
        prefix = info.get("prefix") if isinstance(info, dict) else None
        if prefix is not None:
            if self.telemetry:
                if prefix.get("hit_pages"):
                    self._m_prefix_hits.inc()
                else:
                    self._m_prefix_misses.inc()
                saved = int(prefix.get("tokens_saved", 0))
                if saved:
                    self._m_prefix_saved.inc(saved)
                cache = getattr(self.decoder, "prefix_cache", None)
                if cache is not None:
                    t = prefix.get("tenant") or req.tenant
                    self._m_tenant_prefix_pages.labels(tenant=t).set(
                        cache.tenant_pages(t)
                    )
            if prefix.get("hit_pages"):
                self._event(
                    "prefix_hit", req, slot=slot,
                    pages=int(prefix["hit_pages"]),
                    tokens_saved=int(prefix.get("tokens_saved", 0)),
                )
            remote = prefix.get("remote")
            if isinstance(remote, dict) and remote.get("pulled"):
                if self.telemetry:
                    self._m_prefix_remote_hits.inc()
                self._event(
                    "prefix_remote_hit", req, slot=slot,
                    owner=remote.get("owner"),
                    pages=int(remote.get("pulled", 0)),
                    tokens=int(remote.get("tokens", 0)),
                    bytes=int(remote.get("bytes", 0)),
                    degraded=bool(remote.get("failed")),
                )
        req.prompt_tokens = int(info.get("prompt_tokens", 0))
        req.admitted_step = int(getattr(self.decoder, "steps", 0))
        if info.get("is_stop"):
            self._finish(req, "eos")
            self._release_slot(slot)
            return
        self._emit(req, info["token"])
        if req.max_new <= 1:
            self._finish(req, "length")
            self._release_slot(slot)
            return
        active[slot] = req
        self._active_lanes = len(active)
        self.max_batch_seen = max(self.max_batch_seen, len(active))

    def _admit_queued(self, key, active: dict) -> None:
        """Admit queued same-key requests into free slots, dequeued
        FAIR-SHARE (weighted round-robin across tenant queues — one hot
        tenant's flood cannot starve the rest; docs/serving.md "Prefix
        cache + tenant QoS"). Once a MISMATCHED-key request is waiting,
        admission pauses so the active lanes drain and the scheduler can
        switch keys (no starvation across sampling keys either)."""
        self._drain_intake()
        while self.decoder.has_free_slot() and not self._pending:
            nxt = self._next_queued()
            if nxt is None:
                break
            if nxt.sample_key == key:
                self._admit(nxt, active)
            else:
                self._pending.append(nxt)

    def _flush_harvests(self, active: dict) -> None:
        """One bulk device copy for every harvest queued this tick
        (StepwiseDecoder.flush_harvests; no-op without a prefix cache
        or an empty queue). With page sharing on, chain keys whose
        bytes just landed (this flush or a remote pull) are reported
        to the router's fleet index off-thread. A failed copy costs
        only the harvest, unless it took the pool (_pool_lost)."""
        flush = getattr(self.decoder, "flush_harvests", None)
        pending = getattr(self.decoder, "harvests_pending", None)
        if flush is not None and (pending is None or pending()):
            with self.tracer.span("sched.harvest"):
                try:
                    flush()
                except Exception as e:
                    if not self._pool_lost(active, e):
                        raise
        if self.page_share is not None:
            drain = getattr(self.decoder, "drain_landed_keys", None)
            if drain is not None:
                keys = drain()
                if keys:
                    self.page_share.report_async(keys)

    def _next_chunk(
        self, active: dict
    ) -> Optional[Tuple[int, Any, Any, str, int, int]]:
        """Choose the ONE chunk that rides the next step: (slot, request,
        prefill state, the rule that picked it, its place among the
        runnable admissions, how many those were), or None. One scan of
        `_prefilling` in admission order collects the admissions with a
        chunk to run, and pick_prefill names one of them: by turns the
        oldest and the one with the fewest chunks left, a turn a chunk
        dispatched. The chunk's rows join the decode rows in one forward
        pass, so prefill work costs the decode batch no program of its
        own — the chunked-prefill latency contract (docs/serving.md).

        A cancelled or overdue admission is ended here. Dedup followers
        parked behind an in-flight identical prefix (decoder `waiting`
        states) re-check for free (`prefill_ready`), once a tick, and
        while parked neither take the tick's chunk nor use up a turn —
        otherwise K parked followers would slow their own leader's
        prefill (and every queued one) (K+1)x. An admission whose last
        chunk is on the device waits for its first token
        (_first_tokens)."""
        ready = getattr(self.decoder, "prefill_ready", None)
        now = time.time()
        runnable = []
        for slot, (req, st) in list(self._prefilling.items()):
            waiting = bool(st.get("waiting"))
            if not waiting and st["next"] >= st["n_chunks"]:
                continue  # last chunk in flight
            if req.cancelled:
                del self._prefilling[slot]
                self._finish(req, "cancelled")
                self._release_slot(slot)
                continue
            if req.deadline is not None and now > req.deadline:
                del self._prefilling[slot]
                self._timeout(req, "mid-prefill")
                self._release_slot(slot)
                continue
            if waiting and ready is not None:
                # Resolving may flush harvests (a first-use compile).
                try:
                    with self._wd_pause():
                        let_go = ready(st)
                except Exception as e:
                    logger.exception("chunked prefill failed")
                    del self._prefilling[slot]
                    self._release_slot(slot)
                    self._pool_lost(active, e)
                    self._fail(req, e)
                    if not self._prefilling:  # a lost pool failed them all
                        return None
                    continue
                if not let_go:
                    continue
            runnable.append((slot, req, st))
        if not runnable:
            return None
        at, rule = pick_prefill(
            [st["n_chunks"] - st["next"] for _, _, st in runnable],
            self._chunk_turn,
        )
        return (*runnable[at], rule, at, len(runnable))

    def _book_chunk(self, slot: int, req, st, resident: int, rule: str,
                    oldest: bool) -> None:
        """A chunk is on its way (it rode the step just dispatched):
        the request's stamps (the clock is read for a prompt's first
        and last chunk alone), the turn and the counters (`rule` chose
        it; `oldest`: it went to the oldest runnable admission), the
        `prefill_chunk` event, liveness. `resident`: the slot's rows
        once it has run, spliced prefix included — it must agree with
        the decoder's own residency booking for a prefix hit."""
        last = st["next"] >= st["n_chunks"]
        req.chunks += 1
        if req.chunks == 1 or last:
            now = self._clock()
            if req.chunks == 1:
                req.t_first_chunk = now
            if last:
                req.t_last_chunk = now
                # (At least its chunks: a decoder without the split API
                # runs a chunk at once, in no step.)
                req.ticks = max(
                    req.chunks,
                    self._dispatched() - req.dispatched_at_admit,
                )
        self._chunk_turn += 1
        if self.telemetry:
            self._m_prefill_chunks.inc()
            self._m_picks[rule].inc()
            if not oldest:
                self._m_picks_not_oldest.inc()
        self._event(
            "prefill_chunk", req, slot=slot,
            chunk=int(st["next"]), chunks=int(st["n_chunks"]),
            rows=resident,
        )
        # A chunk is real progress: stamp liveness here too, or a
        # prefill-only window (huge prompt, no active decode lanes)
        # could read as stale to /healthz while the scheduler works.
        self.last_tick_ts = time.time()

    def _first_tokens(self, active: dict) -> None:
        """The first token of every prompt whose last chunk rode a step
        that has been read (the decoder leaves prefill_into_slot's info
        in the prefill state): TTFT is stamped and the token emitted
        here; the lane has been stepped since the next dispatch."""
        for slot, (req, st) in list(self._prefilling.items()):
            info = st.pop("info", None)
            if info is not None:
                del self._prefilling[slot]
                self._prefill_done(req, slot, info, active)

    def _wait_for_request(self, timeout: Optional[float] = None):
        """Block on the intake queue with nothing to run: the
        `sched.wait` span and the ledger's queue_idle phase."""
        self._phases.publish()  # what ran up to here; then it may be long
        with self.tracer.span("sched.wait"), \
                self._phases.region("queue_idle"):
            return self.q.get(timeout=timeout)

    def _loop(self) -> None:
        self._phases.start("sched")
        while True:
            if self._pending:
                req = self._pending.pop(0)
            else:
                self._drain_intake()
                req = self._next_queued()
                if req is None:
                    # Nothing parked anywhere: block for the next submit,
                    # then run it through the same fair-share path.
                    self._enqueue_tenant(self._wait_for_request())
                    self._drain_intake()
                    req = self._next_queued()
            self._busy = True
            try:
                self._run_generation(req)
            except Exception as e:  # never kill the worker
                logger.exception("continuous scheduler generation failed")
                if not req.done:  # the client must never hang on a bug
                    self._fail(req, e)
            finally:
                self._busy = False

    def _run_generation(self, first: _ContinuousRequest) -> None:
        self.batches += 1
        if self.telemetry:
            self._m_generations.inc()
        if self.watchdog is not None:
            # Watch only while a generation is live: an idle scheduler
            # parked on q.get() must never read as hung.
            self.watchdog.arm()
        try:
            self._run_generation_inner(first)
        finally:
            # Only an error the loop did not expect leaves a step behind.
            self._steps.abandon_steps()
            if self.watchdog is not None:
                self.watchdog.disarm()

    def _wd_pause(self):
        """Watchdog pause for the compile-prone host work between decode
        steps (admission prefills, chunk advances — first-use XLA
        compiles of new prompt/chunk buckets): the trainer's
        skip_next-on-recompile guard, serving-shaped. Callers apply it
        exactly around REAL prefill work, never per tick — pausing every
        tick would exclude every beat interval and starve the rolling
        stats. No-op without a watchdog."""
        if self.watchdog is None:
            return contextlib.nullcontext()
        return self.watchdog.pause()

    def _tick_span(self, active: dict):
        """`sched.tick`: one iteration of the generation loop, parent of
        every other scheduler-thread span. The attributes cost a lock
        (queue_depth), so they are gathered only while tracing."""
        if not self.tracer.enabled:
            return self.tracer.span("sched.tick")
        return self.tracer.span(
            "sched.tick", active_lanes=len(active),
            prefilling=len(self._prefilling),
            queue_depth=self.queue_depth(),
        )

    def _emit_lanes(self, active: dict, toks, produced, eos) -> None:
        """The per-lane tail of a decode step (`sched.emit`): stream each
        lane's token, finish and release the lanes that ended."""
        now = time.time()
        with self.tracer.span("sched.emit", lanes=len(active)):
            for slot, r in list(active.items()):
                if r.cancelled:
                    self._finish(r, "cancelled")
                    self._release(r, active)
                    continue
                if r.deadline is not None and now > r.deadline:
                    # Overdue lane (slow/stuck decode or an oversized
                    # budget): evict so the slot serves queued work.
                    self._timeout(r, "mid-decode")
                    self._release(r, active)
                    continue
                if eos[slot]:
                    self._finish(r, "eos")
                    self._release(r, active)
                    continue
                if produced[slot]:
                    self._emit(r, int(toks[slot]))
                    full = getattr(self.decoder, "lane_full", None)
                    if len(r.tokens) >= r.max_new or (
                        full is not None and full(slot)
                    ):
                        self._finish(r, "length")
                        self._release(r, active)

    def _run_generation_inner(self, first: _ContinuousRequest) -> None:
        key = first.sample_key
        active: Dict[int, _ContinuousRequest] = {}
        self._admit(first, active)
        # Optional admission window: wait briefly for same-key peers so
        # the first step already carries a batch (a latency/throughput
        # knob, NOT required for joining — lanes join at any later step).
        # Peers are dequeued through the same fair-share WRR path as
        # steady-state admission, so requests already parked in tenant
        # queues go first and a burst inside the window cannot jump them.
        deadline = time.time() + self.window
        while (
            self.window > 0
            and self.decoder.has_free_slot()
            and not self._pending
        ):
            left = deadline - time.time()
            if left <= 0:
                break
            self._drain_intake()
            nxt = self._next_queued()
            if nxt is None:
                try:
                    self._enqueue_tenant(self._wait_for_request(left))
                except queue.Empty:
                    break
                continue
            if nxt.sample_key == key:
                self._admit(nxt, active)
            else:
                self._pending.append(nxt)
        # Decode-tick accumulator: one SUMMARY event per tick_every steps
        # (per-step events would be all the ring buffer ever holds).
        self._tick_steps = self._tick_tokens = 0
        self._tick_t0 = self._t_collect = time.perf_counter()
        if self._stalls is not None:
            self._stalls.mark()
        steps = self._steps
        while active or self._prefilling or steps.steps_in_flight:
            with self._tick_span(active):
                # One step ahead: step N+1 goes onto the device's queue
                # BEFORE the host reads step N, so the read, the emit
                # and everything below run under N+1, and their
                # programs queue behind it. What N+1 steps is the
                # decoder's prediction of the lanes N leaves alive.
                # N+1 carries one prefill chunk with its lanes (one
                # program a tick: _next_chunk), so a request admitted
                # below has its first chunk in the NEXT tick's step.
                if steps.steps_in_flight and not self._step(key, active):
                    return
                self._admit_queued(key, active)
                # Harvest batching (ROADMAP item 2): every prefix-cache
                # harvest that landed this tick rides ONE jitted bulk page
                # copy instead of one pool-copy dispatch per admission.
                self._flush_harvests(active)
                # Nothing queued (a generation's first step, or every
                # lane of the last one ended or joined since): start.
                if (active or self._prefilling) and (
                    not steps.steps_in_flight
                ) and not self._step(key, active, collect=False):
                    return
            self._phases.publish()
        # A harvest landing on the generation's last tick must not wait
        # for the next admission's defensive flush.
        self._flush_harvests(active)

    def _step(self, key, active: dict, collect: bool = True) -> bool:
        """`decode_step`: choose the chunk that rides the next step and
        dispatch it, then (`collect`) read the previous one and do
        everything that follows a read: the step-time metrics, the
        watchdog's beat, the per-lane emit, the first token of a prompt
        whose last chunk rode the step read.
        False when either half raised: both steps in flight are then
        abandoned and every lane failed (the pool rebuilt if the call
        had taken it), and the generation is over."""
        steps = self._steps
        chunk = self._next_chunk(active) if self._prefilling else None
        rows = 0
        try:
            with self.tracer.span("decode_step") as sp:
                ahead = steps.steps_in_flight > 0
                if chunk is None:
                    # Parked admissions alone are no reason for a step.
                    dispatched = (active or ahead) and (
                        steps.dispatch_step(key)
                    )
                else:
                    slot, req, st, rule, at, runnable = chunk
                    start = int(st.get("start_rows", 0)) + (
                        st["next"] * st["chunk"]
                    )
                    end = int(min(start + st["chunk"], st["length"]))
                    rows = end - start
                    sp.set(chunk_slot=slot, chunk_rows=rows,
                           chunk_request_id=req.request_id,
                           chunk_pick=rule)
                    # The other admissions with a chunk to run wait a turn.
                    if runnable > 1 and self.telemetry:
                        self._m_turns_waited.inc(runnable - 1)
                    dispatched = steps.dispatch_step(key, chunk=st)
                    self._book_chunk(slot, req, st, end, rule, at == 0)
                    if "info" in st:
                        # A decoder without the split API ran the chunk
                        # at once: a prompt it ended is a lane before
                        # the step noted earlier steps it.
                        self._first_tokens(active)
                # The step read below is the one that was in flight, if
                # one was: its chunk was noted a tick ago.
                rows_read = self._chunk_rows_ahead if ahead else rows
                if dispatched:
                    self._chunk_rows_ahead = rows
                    if not ahead:
                        self._t_collect = time.perf_counter()
                        if self._stalls is not None:
                            self._stalls.mark()
                    elif self.telemetry:
                        self._m_steps_ahead.inc()
                if collect and steps.steps_in_flight:
                    toks, produced, eos = steps.collect_step()
                    # Collect to collect: with a step always queued
                    # behind the one being read, that IS the gap between
                    # a lane's tokens (from its own dispatch for a step
                    # nothing was queued ahead of).
                    now = time.perf_counter()
                    # The stall measurements beside the clock, nothing
                    # between the two (_observe_tick).
                    here = (self._stalls.read()
                            if self._stalls is not None else None)
                    step_dt, self._t_collect = now - self._t_collect, now
                    n_produced = sum(1 for slot in active if produced[slot])
                    if here is not None:
                        self._observe_tick(step_dt, sp, n_produced,
                                           rows_read, here)
                else:
                    collect = False
        except Exception as e:
            logger.exception("decode step failed")
            steps.abandon_steps()
            self._pool_lost(active, e)
            self._fail_all(active, e)  # alive or rebuilt alike
            self._count_decoder()
            return False
        if not collect:
            self._count_decoder()
            return True
        if self.watchdog is not None:
            self.watchdog.beat()
        self.last_tick_ts = time.time()
        if self.telemetry:
            self._m_step.observe(step_dt)
            self._m_decode_steps.inc()
            # Per-token decode latency: the step IS the inter-token
            # gap for every lane that emitted this step.
            self._m_token.observe(step_dt, count=max(0, n_produced))
        self._tick_steps += 1
        self._tick_tokens += max(0, n_produced)
        if self._tick_steps >= self.tick_every:
            dt_tick = time.perf_counter() - self._tick_t0
            self._event(
                "decode_tick",
                step=int(getattr(self.decoder, "steps", 0)),
                steps=self._tick_steps, tokens=self._tick_tokens,
                active_lanes=len(active),
                queue_depth=self.queue_depth(),
                tokens_per_sec=round(
                    self._tick_tokens / max(dt_tick, 1e-9), 1
                ),
            )
            self._tick_steps = self._tick_tokens = 0
            self._tick_t0 = time.perf_counter()
        self._emit_lanes(active, toks, produced, eos)
        self._first_tokens(active)
        self._count_decoder()
        return True

    def _observe_tick(self, step_dt: float, sp, lanes: int,
                      chunk_rows: int, here) -> None:
        """The step-time sentinel's turn, right after a collect and
        inside its `decode_step` span. A flagged tick books its excess
        under one cause (TickStalls), and the span and the
        `step_anomaly` event name it beside the lanes and the chunk rows
        of the step read; every tick marks where the next one's interval
        starts, and calls no metric. ONE reading (`here`), taken right
        beside the clock's, ends this interval and starts the next: a
        pause that begins between the two is timed into the next tick
        and measured into this one (met twice in ~110 stalls on the
        chip while the reading came a few lines later; PERF.md section
        6, PRs 59-61). This runs inside `_step`'s `try`, whose handler fails
        every lane: a booking that raises stays in the sentinel
        (`observe` logs it and emits the event without its fields)."""
        def explain(excess_s: float) -> Dict[str, Any]:
            booked = self._stalls.book(excess_s, here)
            sp.set(**{k: booked[k]
                      for k in ("stall_s", "stall_cause", "stall_phase")})
            return {**booked, "lanes": lanes, "chunk_rows": chunk_rows}

        self._sentinel.observe(
            step_dt, step=int(getattr(self.decoder, "steps", 0)),
            explain=explain,
        )
        self._stalls.mark(here)

    def _count_decoder(self) -> None:
        """The decoder counts the lane-steps it drops (where a lane is
        released or meets a stop token), the chunks its steps carry and
        the tick programs it builds; the registry follows."""
        for name, metric in self._decoder_counters:
            n = getattr(self.decoder, name, 0)
            seen = self._decoder_seen[name]
            if n != seen:
                if self.telemetry:
                    metric.inc(n - seen)
                self._decoder_seen[name] = n
        built = getattr(self.decoder, "tick_programs_built", None)
        if built and self._m_tick_programs is not None:
            for extent, n in tuple(built.items()):
                seen = self._tick_programs_seen.get(extent, 0)
                if n != seen:
                    self._m_tick_programs.labels(extent=extent).inc(n - seen)
                    self._tick_programs_seen[extent] = n


class _SlotStream:
    """Event-stream wrapper that releases its concurrency slot exactly
    once — on exhaustion, error, or close(). A plain generator's finally
    block never runs if the generator is closed before its first next()
    (e.g. the handler's header write fails for an already-gone client),
    which would slowly leak speculative slots until the hint never
    engages."""

    def __init__(self, inner, release):
        self._inner = inner
        self._release = release
        self._released = False

    def _release_once(self) -> None:
        if not self._released:
            self._released = True
            self._release()

    def __iter__(self):
        return self

    def __next__(self):
        try:
            return next(self._inner)
        except BaseException:
            self._release_once()  # StopIteration included
            raise

    def close(self) -> None:
        try:
            self._inner.close()
        finally:
            self._release_once()


class ChatServer:
    """Owns the engine + optional security stack; builds the handler class."""

    def __init__(
        self,
        engine,
        secure: bool = False,
        bootstrap_user: Optional[tuple] = None,
        users_path: str = "users.json",
        max_new_tokens_cap: int = 2048,
        max_streams: int = 4,
        num_slots: int = 8,
        page_size: int = 128,
        admission_window_ms: float = 0.0,
        registry: Optional[MetricsRegistry] = None,
        tracer: Optional[SpanTracer] = None,
        telemetry: bool = True,
        latency_buckets=DEFAULT_LATENCY_BUCKETS,
        warmup: bool = False,
        request_timeout_s: Optional[float] = None,
        max_queue_depth: int = 128,
        drain_grace_s: float = 30.0,
        flight_dir: Optional[str] = None,
        max_tenants: int = 64,
        recorder: Optional[FlightRecorder] = None,
        prefill_chunk_tokens: Optional[int] = None,
        prefix_cache_pages: Optional[int] = None,
        prefix_cache_tenant_quota: Optional[int] = None,
        tenant_weights: Optional[Dict[str, int]] = None,
        tenant_rate_per_s: Optional[float] = None,
        tenant_burst: Optional[int] = None,
        watchdog: Any = "auto",
        watchdog_abort: bool = False,
        watchdog_k: Optional[float] = None,
        watchdog_floor_s: Optional[float] = None,
        slo: bool = True,
        slo_config: Optional[str] = None,
        healthz_stale_after_s: Optional[float] = None,
        page_share: Optional[str] = None,
        page_share_self_url: Optional[str] = None,
        page_pull_timeout_s: float = 2.0,
        page_share_max_inflight: int = 2,
    ):
        self.engine = engine
        self.telemetry = bool(telemetry)
        self.registry = registry or get_registry()
        self.tracer = (
            tracer if tracer is not None else SpanTracer(enabled=False)
        )
        # Wide-event trail (monitoring/events.py): request identity is
        # minted at the HTTP layer, lifecycle events come from the
        # scheduler, and drain dumps the ring into flight_dir for
        # `lumina events` (crash forensics; docs/observability.md).
        self.recorder = recorder if recorder is not None else get_recorder()
        self.flight_dir = flight_dir
        self.max_tenants = max(1, int(max_tenants))
        # Graceful degradation (docs/resilience.md): deadlines evict
        # overdue lanes, queue-depth overload sheds with 503+Retry-After,
        # and SIGTERM drains in-flight work before shutdown.
        self.request_timeout_s = request_timeout_s
        self.max_queue_depth = max(0, int(max_queue_depth))
        self.drain_grace_s = float(drain_grace_s)
        self._draining = False
        # Readiness gate for /healthz: a container probe must see 503
        # while XLA is still compiling the prefill/decode executables
        # (minutes for real models) and flip to 200 the moment requests
        # can actually be served. warmup=True (the `serve` entrypoint)
        # drives a tiny generation through the real batcher path in the
        # background and sets the gate when it completes; in-process
        # embedders/tests default to immediately-ready.
        self._ready = threading.Event()
        # One scheduler: continuous batching (step-level admission over a
        # slot-paged KV pool) needs the engine's step-wise decode API.
        if not hasattr(engine, "make_stepwise"):
            raise TypeError(
                f"ChatServer needs an engine with make_stepwise() (the "
                f"step-wise decode API the ContinuousScheduler drives); "
                f"{type(engine).__name__} has none"
            )
        # Serving hang watchdog: "auto" builds one over the flight
        # dir (hang forensics land next to the drain dumps); pass
        # None/False to disable, or a configured HangWatchdog to
        # control thresholds (tests do).
        if watchdog == "auto":
            wd_kw = {}
            if watchdog_k is not None:
                wd_kw["k"] = float(watchdog_k)
            if watchdog_floor_s is not None:
                # --watchdog-floor: on cold fleets, raise above the
                # worst-case decode compile before enabling abort.
                wd_kw["floor_s"] = float(watchdog_floor_s)
            watchdog = HangWatchdog(
                kind="serving",
                registry=self.registry,
                recorder=self.recorder,
                dump_dir=flight_dir,
                abort=watchdog_abort,
                **wd_kw,
            )
        self.watchdog = watchdog or None
        # Operator-supplied tenant weights are keyed by RAW identity
        # (or the literal "anon"); hash them here so raw identities
        # never live in scheduler state — the same tenant_hash the
        # gate resolves request identities through.
        weights = {
            (k if k == ANON_TENANT else tenant_hash(str(k))): v
            for k, v in (tenant_weights or {}).items()
        }
        # Cross-replica page sharing (serving/page_share.py):
        # `page_share` is the ROUTER url; the client reports
        # harvested chain keys there and pulls indexed pages
        # replica-to-replica. self_url is how peers reach THIS
        # replica — serve() fills it from host/port; tests binding
        # port 0 set client.self_url after the listener exists.
        self.page_share = None
        if page_share:
            from luminaai_tpu.serving.page_share import (
                PageShareClient,
            )

            self.page_share = PageShareClient(
                router_url=str(page_share),
                self_url=page_share_self_url or "",
                timeout_s=page_pull_timeout_s,
                max_inflight=page_share_max_inflight,
                registry=self.registry if telemetry else None,
                recorder=self.recorder if telemetry else None,
            )
        self.batcher = ContinuousScheduler(
            engine,
            num_slots=num_slots,
            page_size=page_size,
            admission_window_ms=admission_window_ms,
            registry=self.registry,
            tracer=self.tracer,
            telemetry=telemetry,
            latency_buckets=latency_buckets,
            request_timeout_s=request_timeout_s,
            recorder=self.recorder,
            max_tenants=self.max_tenants,
            prefill_chunk_tokens=prefill_chunk_tokens,
            prefix_cache_pages=prefix_cache_pages,
            prefix_cache_tenant_quota=prefix_cache_tenant_quota,
            tenant_weights=weights,
            watchdog=self.watchdog,
            page_share=self.page_share,
        )
        # Build identity for fleet debugging (docs/observability.md):
        # which commit/jax/config answers this /metrics.
        register_build_info(self.registry, config=engine.config)
        # /healthz staleness: a wedged-but-alive process (decode loop
        # stuck inside a sync) keeps answering probes — with a stale
        # threshold set, a busy scheduler whose last decode tick is
        # older than this flips status to "degraded" (still 200) so
        # external probes catch it before the watchdog aborts.
        if healthz_stale_after_s is not None and not (
            float(healthz_stale_after_s) > 0
        ):
            # A falsy-zero check here would silently DISABLE the probe
            # the flag exists for; reject loudly instead.
            raise ValueError(
                "healthz_stale_after_s must be positive, got "
                f"{healthz_stale_after_s!r}"
            )
        self.healthz_stale_after_s = (
            float(healthz_stale_after_s)
            if healthz_stale_after_s is not None
            else None
        )
        # SLO layer (docs/observability.md "SLOs & burn rate"): windowed
        # registry history in a fixed-memory ring + burn-rate alerts
        # over the serve objectives (TTFT p95, decode p50, error rate),
        # targets from the engine's Config slo_* knobs (or a
        # --slo-config JSON override). GET /metrics/history and
        # GET /slo read these; `lumina top --url` draws them.
        self.history: Optional[TimeSeriesRing] = None
        self.slo: Optional[SLOEngine] = None
        cfg = engine.config
        if self.telemetry and slo and getattr(cfg, "slo", True):
            self.history, self.slo = build_slo_stack(
                cfg, registry=self.registry, recorder=self.recorder,
                program="serve", slo_config=slo_config,
            )
            self._installed_history = get_history() is None
            if self._installed_history:
                set_history(self.history)
            self.history.start()
        else:
            self._installed_history = False
        # Per-tenant token-bucket admission (rate_limiter.py): every
        # generation request costs one token from its tenant's bucket —
        # burst-tolerant, steady-state rate-bounded. Applies in _gate
        # whenever configured (secure or not; unauthenticated traffic
        # shares the anon tenant's bucket). Keys are ALWAYS hashed
        # tenants, never raw identities.
        self.tenant_bucket = None
        if tenant_rate_per_s:
            from luminaai_tpu.security.rate_limiter import (
                TokenBucketLimiter,
            )

            self.tenant_bucket = TokenBucketLimiter(
                rate_per_s=float(tenant_rate_per_s),
                burst=int(tenant_burst or max(1, int(tenant_rate_per_s))),
            )
        r = self.registry
        self._m_http = r.counter(
            "serve_http_requests_total",
            "HTTP requests by route and status code",
            labelnames=("route", "code"),
        )
        self._m_request = r.histogram(
            "serve_request_seconds",
            "Non-streaming generation request latency (parse to reply)",
            buckets=latency_buckets,
        )
        self._m_stream = r.histogram(
            "serve_stream_duration_seconds",
            "SSE stream duration (first event to close/abort)",
            buckets=latency_buckets,
        )
        self._m_tokens_out = r.counter(
            "serve_tokens_out_total", "Generated tokens returned to clients"
        )
        self._m_overload = r.counter(
            "serving_overload_rejections_total",
            "Generation requests shed with 503 + Retry-After because the "
            "admission queue was at max_queue_depth",
        )
        # Per-tenant request accounting (the substrate ROADMAP item 2's
        # fair-share admission prices QoS against). Bounded cardinality:
        # max_tenants distinct labels, then `_overflow`.
        tk = dict(labelnames=("tenant",), max_label_values=self.max_tenants)
        self._m_tenant_requests = r.counter(
            "tenant_requests_total",
            "Generation requests accepted for processing, per tenant",
            **tk,
        )
        self._m_tenant_tokens_in = r.counter(
            "tenant_tokens_in_total",
            "Prompt tokens submitted, per tenant", **tk,
        )
        self._m_tenant_tokens_out = r.counter(
            "tenant_tokens_out_total",
            "Generated tokens returned, per tenant", **tk,
        )
        self._m_tenant_shed = r.counter(
            "tenant_requests_shed_total",
            "Requests rejected 503 (drain or overload), per tenant", **tk,
        )
        r.gauge(
            "serve_ready",
            "1 once the engine is warmed and serving, 0 while compiling",
        ).set_function(
            weak_callback(self, lambda s: float(s._ready.is_set()))
        )
        r.gauge(
            "serve_draining",
            "1 while the server is draining (admissions stopped, in-flight "
            "generations finishing before shutdown)",
        ).set_function(
            weak_callback(self, lambda s: float(s._draining))
        )
        if warmup:
            threading.Thread(target=self._warmup, daemon=True).start()
        else:
            self._ready.set()
        # Speculative requests (JSON and SSE) run outside the scheduler:
        # each holds its own KV cache + decode loop on the device, so
        # without a cap they'd be unbounded (ThreadingHTTPServer is
        # thread-per-connection). Every other stream is a scheduler lane.
        self._stream_slots = threading.Semaphore(max(1, int(max_streams)))
        # Auth/limiter/counter state is shared across handler threads;
        # SecurityManager and RateLimiter are not thread-safe themselves.
        self.state_lock = threading.Lock()
        self.t0 = time.time()
        self.requests = 0
        self.tokens_out = 0
        self.max_new_tokens_cap = max_new_tokens_cap
        self.secure = secure
        self.security = None
        self.limiter = None
        self.validator = None
        if secure:
            from luminaai_tpu.security.auth import SecurityManager
            from luminaai_tpu.security.input_validator import InputValidator
            from luminaai_tpu.security.rate_limiter import RateLimiter

            self.security = SecurityManager(persist_path=users_path)
            self.limiter = RateLimiter()
            self.validator = InputValidator()
            if bootstrap_user is not None:
                user, password = bootstrap_user
                self.security.create_user(user, password)

    # -- readiness ---------------------------------------------------------
    def mark_ready(self) -> None:
        self._ready.set()

    # -- graceful shutdown (docs/resilience.md) ----------------------------
    def begin_drain(self) -> None:
        """Stop admitting generation requests. /healthz stays 200 (the
        process is healthy) but advertises `draining` in the body and the
        serve_draining gauge; in-flight lanes keep decoding to completion."""
        if not self._draining:
            self._draining = True
            if self.telemetry:
                self.recorder.emit(
                    "drain_started", queue_depth=self.batcher.queue_depth()
                )
            logger.warning(
                "drain started: new generations rejected, in-flight work "
                "finishing (queue_depth=%d)", self.batcher.queue_depth(),
            )

    def drain(self, timeout_s: Optional[float] = None) -> bool:
        """begin_drain + wait (bounded) for in-flight generations to
        finish. Returns True when the scheduler went idle inside the
        grace window; False means the deadline expired with lanes still
        active (the caller shuts down anyway — bounded beats hung)."""
        self.begin_drain()
        deadline = time.time() + (
            self.drain_grace_s if timeout_s is None else float(timeout_s)
        )
        idle = False
        while time.time() < deadline:
            if self.batcher.idle():
                logger.info("drain complete: scheduler idle")
                idle = True
                break
            time.sleep(0.05)
        if not idle:
            idle = self.batcher.idle()
            if not idle:
                logger.warning(
                    "drain grace expired with work still in flight; "
                    "shutting down anyway"
                )
        if self.telemetry:
            self.recorder.emit("drain_finished", idle=idle)
        # Crash forensics: the event trail survives the shutdown as a
        # flightrec-*.jsonl dump next to the checkpoints (lumina events
        # replays it; docs/observability.md "Flight recorder").
        self.dump_flight_record("drain")
        # The server is done serving: stop the watchdog's monitor thread
        # (Trainer.close does the same) — a drained server must not keep
        # a poller alive in embedding processes that cycle servers. The
        # history sampler stops for the same reason.
        if getattr(self, "watchdog", None) is not None:
            self.watchdog.close()
        self.batcher.close()
        if self.history is not None:
            self.history.stop()
            if self._installed_history and get_history() is self.history:
                set_history(None)
        return idle

    def dump_flight_record(self, reason: str) -> Optional[str]:
        """Dump the wide-event ring buffer into flight_dir (no-op without
        one), plus the time-series history when SLO retention is on
        (`lumina top <dir>` replays it). Never raises — it rides
        shutdown paths."""
        if not self.flight_dir:
            return None
        if self.history is not None:
            self.history.dump_to_dir(
                self.flight_dir, reason,
                slo=self.slo.verdicts() if self.slo is not None else None,
            )
        return self.recorder.dump_to_dir(self.flight_dir, reason)

    def _shed(self):
        """Load-shedding gate for generation endpoints: draining servers
        and full admission queues answer 503 + Retry-After immediately
        instead of queuing unboundedly (clients retry against a replica)."""
        if self._draining:
            return 503, {
                "error": "server draining; retry against another replica",
                "retry_after": 2,
            }
        depth = self.batcher.queue_depth()
        if self.max_queue_depth and depth >= self.max_queue_depth:
            if self.telemetry:
                self._m_overload.inc()
            # Rough time-to-queue-space: a slot's worth of queued work.
            slots = getattr(self.batcher.decoder, "num_slots", 8)
            return 503, {
                "error": f"overloaded: admission queue at {depth}; "
                         "retry later",
                "retry_after": max(1, depth // max(1, int(slots or 8))),
            }
        return None

    def _effective_timeout(self, body: Dict[str, Any]) -> Optional[float]:
        """Per-request deadline: the request's timeout_s can only SHORTEN
        the server's request_timeout_s cap (a client must not be able to
        pin a lane past the operator's bound)."""
        cap = self.request_timeout_s
        t = body.get("timeout_s")
        try:
            t = float(t) if t is not None else None
        except (TypeError, ValueError):
            t = None
        if t is not None and t <= 0:
            t = None
        if t is None:
            return cap
        return min(t, cap) if cap else t

    def _warmup(self) -> None:
        """Compile-priming generation through the real batcher path (the
        same executables production requests hit), then open the /healthz
        gate. A warmup failure still opens the gate — a server that can
        answer SOME requests beats one a probe kills forever — but logs
        loudly and leaves the failure visible in the health payload."""
        self._warmup_error: Optional[str] = None
        t0 = time.time()
        try:
            encode = getattr(
                getattr(self.engine.tokenizer, "backend", None),
                "encode", None,
            )
            prompt = encode("warmup") if callable(encode) else [1, 2, 3]
            with self.tracer.span("warmup"):
                self.batcher.submit(
                    list(prompt) or [1],
                    {"max_new_tokens": 2, "temperature": 0.0},
                )
            logger.info("warmup generation done in %.1fs", time.time() - t0)
        except Exception as e:
            logger.exception("warmup generation failed; serving anyway")
            self._warmup_error = f"{type(e).__name__}: {e}"
        finally:
            self._ready.set()

    def _scheduler_state(self) -> Dict[str, Any]:
        """Live scheduler occupancy for /healthz and /stats consumers."""
        st = self.batcher.stats()
        return {
            "scheduler": st["scheduler"],
            "active_lanes": st.get("active_lanes", 0),
            "queue_depth": st.get("queue_depth", 0),
            "slots_free": st.get("kv_pool", {}).get("free"),
            "decode_steps": st.get("decode_steps", 0),
        }

    def _staleness(self) -> Dict[str, Any]:
        """Liveness ages for /healthz: seconds since the scheduler's
        last decode tick and (when a trainer shares the process
        registry) since the last train step. `stale` is True only when
        a threshold is configured AND the process has work it is not
        advancing — an idle scheduler is quiet, not stale."""
        out: Dict[str, Any] = {}
        now = time.time()
        last = self.batcher.last_tick_ts
        if last is not None:
            out["last_decode_tick_age_seconds"] = round(now - last, 3)
        st = self._scheduler_state()
        busy = bool(
            st.get("active_lanes") or st.get("queue_depth")
            or self.batcher._prefilling
        )
        fam = self.registry.get("train_last_step_ts")
        if fam is not None:
            try:
                ts = float(fam.value)
            except (TypeError, ValueError):
                ts = float("nan")
            if ts == ts and ts > 0:  # NaN-safe: live train loop only
                out["last_step_age_seconds"] = round(now - ts, 3)
        thr = self.healthz_stale_after_s
        if thr:
            decode_stale = (
                busy
                and out.get("last_decode_tick_age_seconds") is not None
                and out["last_decode_tick_age_seconds"] > thr
            )
            train_stale = (
                out.get("last_step_age_seconds") is not None
                and out["last_step_age_seconds"] > thr
            )
            out["stale"] = bool(decode_stale or train_stale)
            out["stale_after_s"] = thr
        return out

    def history_route(
        self, seconds: Optional[float] = None,
        max_points: Optional[int] = None,
    ) -> tuple:
        """GET /metrics/history -> (status, payload): the ring's JSON
        snapshot. ONE implementation behind both entries — handle()
        (in-process, no query) and do_GET (parses ?seconds=&max_points=).
        Budget-guarded twice over: the ring's own capacity/series budget
        bounds the worst case, and the query params tighten a single
        response."""
        if self.history is None:
            return 404, {
                "error": "history ring disabled "
                         "(--no-slo or telemetry off)"
            }
        # Query values come off the wire: float() accepts nan/inf, and
        # int(nan) raises — a curl probe must get the full view, not a
        # handler traceback. Non-finite/non-positive -> unclamped.
        if seconds is not None and not (
            math.isfinite(float(seconds)) and seconds > 0
        ):
            seconds = None
        if max_points is not None:
            mp = float(max_points)
            max_points = (
                max(1, min(int(mp), 10_000))
                if math.isfinite(mp) and mp > 0
                else None
            )
        return 200, self.history.snapshot(
            window_s=seconds, max_points=max_points
        )

    def render_metrics(self) -> str:
        return self.registry.render_prometheus()

    # -- request handling --------------------------------------------------
    def handle(self, method: str, path: str, body: Dict[str, Any],
               token: Optional[str],
               request_id: Optional[str] = None) -> tuple:
        """Returns (status_code, payload dict). Pure-ish: no socket I/O.

        `request_id` is an inbound `X-Request-Id` (already validated by
        the HTTP handler): a fronting router minted it, and honoring it
        here means one id correlates the request across the router's and
        this replica's flight rings (`lumina events --request <id>`).
        Absent, we mint as before."""
        if method == "GET" and path == "/healthz":
            # Readiness (vs /health's liveness): 503 while the engine is
            # compiling/warming so orchestrators hold traffic, 200 with
            # scheduler occupancy once serving. The Dockerfile
            # HEALTHCHECK curls this route.
            if not self._ready.is_set():
                return 503, {
                    "status": "warming",
                    "uptime_s": round(time.time() - self.t0, 1),
                }
            # Draining stays 200: the process is healthy and finishing
            # in-flight work — a 5xx here would get it killed mid-drain.
            # Observers that care read `status` or the serve_draining
            # gauge (docker-compose.dev.yml's curl healthcheck tolerates
            # the drain window by construction). Staleness: ages since
            # the last decode tick / train step ride the body, and past
            # --healthz-stale-after a BUSY-but-silent process reports
            # "degraded" (still 200 — probes distinguish wedged from
            # dead; the watchdog owns aborting).
            status = "draining" if self._draining else "ok"
            out = {
                "uptime_s": round(time.time() - self.t0, 1),
                **self._scheduler_state(),
            }
            stale = self._staleness()
            out.update(stale)
            if status == "ok" and stale.get("stale"):
                status = "degraded"
            out["status"] = status
            warm_err = getattr(self, "_warmup_error", None)
            if warm_err:
                out["warmup_error"] = warm_err
            return 200, out
        if method == "GET" and path == "/slo":
            if self.slo is None:
                return 404, {
                    "error": "slo engine disabled "
                             "(--no-slo or telemetry off)"
                }
            return 200, self.slo.verdicts()
        if method == "GET" and path == "/metrics/history":
            return self.history_route()
        if method == "GET" and path == "/health":
            cfg = self.engine.config
            return 200, {
                "status": "ok",
                "uptime_s": round(time.time() - self.t0, 1),
                "model": {
                    "hidden_size": cfg.hidden_size,
                    "num_layers": cfg.num_layers,
                    "vocab_size": cfg.vocab_size,
                    "moe": bool(cfg.use_moe),
                },
                "secure": self.secure,
            }
        if method == "GET" and path == "/stats":
            out = {
                "requests": self.requests,
                "tokens_out": self.tokens_out,
                "uptime_s": round(time.time() - self.t0, 1),
            }
            out.update(self.batcher.stats())
            return 200, out
        if method == "POST" and path == "/v1/auth":
            if not self.secure:
                return 400, {"error": "server not in secure mode"}
            with self.state_lock:
                token = self.security.authenticate(
                    str(body.get("user", "")), str(body.get("password", ""))
                )
            if token is None:
                return 401, {"error": "authentication failed"}
            return 200, {"token": token}
        if method == "POST" and path in ("/v1/generate", "/v1/chat"):
            request_id = request_id or new_request_id()
            shed = self._shed()  # drain/overload: reject before auth work
            if shed is not None:
                self._count_shed(request_id, token, path)
                shed[1]["request_id"] = request_id
                return shed
            with self.state_lock:
                err, tenant = self._gate(body, token)
            if err is not None:
                return err
            return self._run_model(
                path, body, request_id=request_id, tenant=tenant
            )
        return 404, {"error": f"no route {method} {path}"}

    def _tenant_of(self, token: Optional[str]) -> str:
        """Tenant label outside the gate (shed accounting): hashed
        session identity or the shared anon tenant. One HMAC, no
        password work — cheap enough for the overload path."""
        if not self.secure or not token:
            return ANON_TENANT
        with self.state_lock:
            sess = self.security.validate_session(token)
        return tenant_hash(sess["username"]) if sess else ANON_TENANT

    def _count_shed(self, request_id: str, token: Optional[str],
                    route: str) -> None:
        # Same off switch as the scheduler's _event: telemetry off means
        # no accounting work at all (including the session-HMAC tenant
        # resolution), so the overhead A/B stays honest.
        if not self.telemetry:
            return
        tenant = self._tenant_of(token)
        self._m_tenant_shed.labels(tenant=tenant).inc()
        self.recorder.emit(
            "request_shed", request_id=request_id, tenant=tenant,
            route=route,
            reason="drain" if self._draining else "overload",
        )

    def _gate(self, body: Dict[str, Any], token: Optional[str]):
        """Admission checks: session token, per-tenant rate limiting,
        input validation. Returns (error_tuple | None, tenant_label) —
        the tenant is the hashed authenticated identity, so accounting,
        events AND limiter state never carry raw usernames.

        Two limiter layers compose here: the secure-mode sliding-window
        limiter (legacy request-count policy) and the optional per-tenant
        TOKEN BUCKET (--tenant-rate/--tenant-burst), which also applies
        to unauthenticated traffic via the shared anon tenant."""
        tenant = ANON_TENANT
        if self.secure:
            session = self.security.validate_session(token or "")
            if session is None:
                return (
                    (401, {"error": "missing or invalid token"}),
                    ANON_TENANT,
                )
            user = session.get("username", "anonymous")
            tenant = tenant_hash(user)
            # Limiter state is keyed by the HASHED tenant — the limiter's
            # bucket dict is introspectable (and dumpable in bug
            # reports), so raw identities must never appear in its keys.
            if not self.limiter.is_allowed(tenant, "chat"):
                return (429, {"error": "rate limit exceeded"}), tenant
        if self.tenant_bucket is not None and not self.tenant_bucket.allow(
            tenant
        ):
            retry = self.tenant_bucket.retry_after(tenant)
            return (
                429,
                {
                    "error": "tenant rate limit exceeded",
                    "retry_after": max(1, int(retry + 0.999)),
                },
            ), tenant
        if not self.secure:
            return None, tenant
        text = body.get("prompt") or body.get("message") or ""
        if not text and body.get("messages"):
            text = " ".join(
                str(m.get("content", "")) for m in body["messages"]
            )
        verdict = self.validator.validate_user_input(str(text))
        if not verdict.valid:
            return (400, {
                "error": f"input rejected: {'; '.join(verdict.errors)}"
            }), tenant
        return None, tenant

    # (name, clamp) — requests cannot push sampling params outside sane
    # bounds; max_new_tokens is capped so one request can't hold the decode
    # lock arbitrarily long (the rate limiter counts requests, not tokens).
    _OVERRIDE_CLAMPS = {
        "max_new_tokens": lambda v, cap: max(1, min(int(v), cap)),
        "temperature": lambda v, _: min(max(float(v), 0.0), 10.0),
        "top_p": lambda v, _: min(max(float(v), 0.0), 1.0),
        "top_k": lambda v, _: max(0, min(int(v), 10_000)),
        "repetition_penalty": lambda v, _: min(max(float(v), 0.5), 5.0),
    }

    def _parse_request(self, path: str, body: Dict[str, Any]):
        """Shared request parsing for the batched and streaming paths.

        Returns (error_tuple | None, prompt_ids, overrides, reply_key)."""
        overrides = {}
        for k, clamp in self._OVERRIDE_CLAMPS.items():
            if k in body:
                try:
                    overrides[k] = clamp(body[k], self.max_new_tokens_cap)
                except (TypeError, ValueError):
                    return (400, {"error": f"bad value for {k}"}), None, None, None
        if path == "/v1/chat":
            messages = body.get("messages")
            if not messages:
                msg = str(body.get("message", ""))
                if not msg:
                    return (400, {"error": "message(s) required"}), None, None, None
                messages = [{"role": "user", "content": msg}]
            for m in messages:
                if (
                    not isinstance(m, dict)
                    or not isinstance(m.get("role"), str)
                    or not isinstance(m.get("content"), str)
                ):
                    return (
                        400,
                        {
                            "error": "each message needs string "
                                     "'role' and 'content'"
                        },
                    ), None, None, None
            prompt_ids = self.engine.encode_chat(messages)
            reply_key = "reply"
        else:
            prompt = str(body.get("prompt", ""))
            if not prompt:
                return (400, {"error": "prompt required"}), None, None, None
            prompt_ids = self.engine.tokenizer.backend.encode(prompt)
            reply_key = "text"
        return None, prompt_ids, overrides, reply_key

    def _run_model(self, path: str, body: Dict[str, Any],
                   request_id: Optional[str] = None,
                   tenant: str = ANON_TENANT) -> tuple:
        t0 = time.time()
        request_id = request_id or new_request_id()
        err, prompt_ids, overrides, reply_key = self._parse_request(path, body)
        if err is not None:
            return err
        self._account_request(request_id, tenant, path, len(prompt_ids),
                              stream=False)
        if body.get("speculative"):
            out = self._run_speculative(
                prompt_ids, overrides, reply_key, t0,
                request_id=request_id, tenant=tenant,
            )
            if out is not None:
                return out
            # Not eligible (sampling params / engine support): fall
            # through to the batched path silently — speculation is an
            # accelerator hint, not a contract.
        # Concurrent requests with the same sampling params share one
        # generation of the scheduler; sampling overrides go as kwargs,
        # so there is no config mutation to serialize.
        timeout_s = self._effective_timeout(body)
        # Identity riders and the deadline ride the scheduler's submit,
        # which strips them before its compile key.
        overrides = {
            **overrides, "request_id": request_id, "tenant": tenant,
        }
        if timeout_s:
            overrides["timeout_s"] = timeout_s
        try:
            tokens, stats = self.batcher.submit(prompt_ids, overrides)
        except RequestTimeout as e:
            return 504, {
                "error": str(e), "request_id": request_id, "tenant": tenant,
            }
        return self._reply_payload(
            tokens, stats, reply_key, t0,
            request_id=request_id, tenant=tenant,
        )

    def _account_request(self, request_id, tenant, route, prompt_tokens,
                         stream) -> None:
        """Per-tenant admission accounting + the request_received event
        (one choke point for the JSON and SSE paths). Rides the same
        off switch as the metrics."""
        if not self.telemetry:
            return
        self._m_tenant_requests.labels(tenant=tenant).inc()
        self._m_tenant_tokens_in.labels(tenant=tenant).inc(
            int(prompt_tokens)
        )
        self.recorder.emit(
            "request_received", request_id=request_id, tenant=tenant,
            route=route, stream=bool(stream),
            prompt_tokens=int(prompt_tokens),
        )

    def _reply_payload(self, tokens, stats, reply_key, t0,
                       request_id: Optional[str] = None,
                       tenant: Optional[str] = None, **extra) -> tuple:
        """Shared response building + stats booking for the batched and
        speculative generation paths."""
        out = {reply_key: self.engine.tokenizer.decode(tokens)}
        n_tok = int(stats.get("tokens_generated", 0))
        with self.state_lock:
            self.requests += 1
            self.tokens_out += n_tok
        if self.telemetry:
            self._m_request.observe(time.time() - t0)
            self._m_tokens_out.inc(n_tok)
            if tenant:
                self._m_tenant_tokens_out.labels(tenant=tenant).inc(n_tok)
        self.mark_ready()  # a served request is proof of readiness
        out.update(
            tokens=n_tok,
            latency_s=round(time.time() - t0, 3),
            stopped=stats.get("stopped"),
            **extra,
        )
        if request_id is not None:
            # Correlation contract: the id in this reply matches the
            # request's server-side events and /metrics tenant series.
            out["request_id"] = request_id
            out["tenant"] = tenant or ANON_TENANT
        return 200, out

    def _speculative_eligible(self, overrides) -> bool:
        """Whether a {"speculative": true} hint can be honored for these
        request params. Eligibility is judged on the RESOLVED params
        (config defaults fill omitted fields — a request without
        temperature usually samples): greedy, no repetition penalty.
        Shared by the JSON and SSE paths so the hint means one thing."""
        resolve = getattr(self.engine, "_resolve_gen_key", None)
        if resolve is None:
            return False
        config = getattr(self.engine, "config", None)
        if config is not None and config.keeps_lane_state():
            # No draft can be rolled out of a recurrent state: the hint
            # falls back to the scheduler's path, as for sampled params.
            return False
        key = resolve(
            overrides.get("max_new_tokens"),
            overrides.get("temperature"),
            overrides.get("top_p"),
            overrides.get("top_k"),
            overrides.get("repetition_penalty"),
        )
        return key[1] <= 0.0 and key[4] == 1.0

    def _run_speculative(self, prompt_ids, overrides, reply_key, t0,
                         request_id=None, tenant=None):
        """Greedy requests with {"speculative": true} run the engine's
        prompt-lookup speculative decode (exactly the greedy sequence,
        several tokens per device call on repetitive text). It runs
        outside the scheduler under the speculative slot cap
        (max_streams); returns None when not eligible (sampling requested
        or the engine lacks the method) so the caller falls back."""
        if not hasattr(self.engine, "generate_speculative"):
            return None
        if not self._speculative_eligible(overrides):
            return None
        if not self._stream_slots.acquire(blocking=False):
            # All slots busy: fall back to the batched path rather than
            # erroring — the hint must never make a servable request fail.
            return None
        try:
            tokens, stats = self.engine.generate_speculative(
                prompt_ids,
                max_new_tokens=overrides.get("max_new_tokens"),
            )
        finally:
            self._stream_slots.release()
        return self._reply_payload(
            tokens, stats, reply_key, t0,
            request_id=request_id, tenant=tenant,
            speculative={
                "verify_calls": stats.get("verify_calls"),
                "tokens_per_verify": stats.get("tokens_per_verify"),
            },
        )

    # -- streaming (SSE) ---------------------------------------------------
    def start_stream(self, path: str, body: Dict[str, Any],
                     token: Optional[str],
                     request_id: Optional[str] = None):
        """Begin a streamed generation. Returns (error_tuple | None,
        events_generator | None). A stream is a lane of the scheduler
        (submit_stream), or, on a greedy {"speculative": true} request
        with a free slot, the engine's draft/verify loop. An inbound
        `X-Request-Id` (router-minted) is honored like handle()'s, so
        stream events correlate across tiers."""
        request_id = request_id or new_request_id()
        shed = self._shed()  # drain/overload applies to streams too
        if shed is not None:
            self._count_shed(request_id, token, path)
            shed[1]["request_id"] = request_id
            return shed, None
        with self.state_lock:
            err, tenant = self._gate(body, token)
        if err is not None:
            return err, None
        err, prompt_ids, overrides, reply_key = self._parse_request(path, body)
        if err is not None:
            return err, None
        self._account_request(request_id, tenant, path, len(prompt_ids),
                              stream=True)
        timeout_s = self._effective_timeout(body)
        if (
            body.get("speculative")
            and hasattr(self.engine, "generate_stream_speculative")
            and self._speculative_eligible(overrides)
            and self._stream_slots.acquire(blocking=False)
        ):
            # Greedy SSE with {"speculative": true}: the draft/verify
            # loop composes with the streaming contract — tokens arrive
            # in accepted-prefix bursts (engine
            # generate_stream_speculative). Like the JSON speculative
            # path it runs outside the scheduler, so it takes one of
            # the speculative slots; slots busy or sampled params fall
            # through to the scheduler's stream — the hint never makes
            # a servable request fail. The per-request deadline
            # applies: speculative streams run outside the scheduler's
            # overdue-lane eviction, so the engine's decode loop
            # enforces it instead (stopped='timeout').
            if timeout_s:
                overrides = {**overrides, "timeout_s": timeout_s}
            return None, _SlotStream(
                self._stream_events(
                    prompt_ids, overrides, reply_key, speculative=True,
                    request_id=request_id, tenant=tenant,
                ),
                self._stream_slots.release,
            )
        # Identity riders for the scheduler's lifecycle events (stripped
        # before the compile key) + the deadline.
        overrides = {
            **overrides, "request_id": request_id, "tenant": tenant,
        }
        if timeout_s:
            overrides["timeout_s"] = timeout_s
        # Streams ride the shared decode loop like any other request:
        # concurrency is bounded by the KV pool's slots (excess queues).
        # Closing the generator cancels the lane.
        return None, self._stream_events(
            prompt_ids, overrides, reply_key,
            request_id=request_id, tenant=tenant,
        )

    def _stream_events(self, prompt_ids, overrides, reply_key,
                       speculative: bool = False,
                       request_id: Optional[str] = None,
                       tenant: str = ANON_TENANT):
        """Yield SSE event dicts: {'token','delta'} per token, then a
        final {'done': True, <reply_key>: full_text, ...stats}.

        Deltas decode only the tokens since the last clean flush (O(1)
        amortized, not a full re-decode per token); a decode ending
        mid-codepoint (trailing U+FFFD from a split multi-byte char) is
        HELD — the empty delta is emitted now and the held tokens flush
        with the next clean boundary, so concatenated deltas reproduce
        the final text instead of baking replacement chars in. The done
        frame's text is authoritative (one decode of all tokens), and it
        carries a final 'delta' flushing any still-held tokens so the
        delta contract survives a stream that ENDS mid-codepoint.
        Aborted streams (client gone -> GeneratorExit) still count their
        streamed tokens into /stats via the finally block, which also
        releases the concurrency slot acquired in start_stream."""
        t0 = time.time()
        tok = self.engine.tokenizer
        tokens: List[int] = []
        base = 0  # tokens[:base] are flushed into deltas already
        counted = False
        stream_span = self.tracer.span("sse_stream", route=reply_key)
        span = stream_span.__enter__()

        def count(n: int) -> None:
            nonlocal counted
            if counted:
                return
            counted = True
            with self.state_lock:
                self.requests += 1
                self.tokens_out += n
            if self.telemetry:
                self._m_stream.observe(time.time() - t0)
                self._m_tokens_out.inc(n)
                if tenant:
                    self._m_tenant_tokens_out.labels(tenant=tenant).inc(n)
            span.set(tokens=n)
            self.mark_ready()

        # A stream is a lane of the shared scheduler loop; speculative
        # greedy streams run the engine's draft/verify loop directly.
        # Both sources honor the same contract (token ints, then a
        # stats dict).
        if speculative:
            src = self.engine.generate_stream_speculative(
                prompt_ids,
                max_new_tokens=overrides.get("max_new_tokens"),
                timeout_s=overrides.get("timeout_s"),
            )
        else:
            src = self.batcher.submit_stream(prompt_ids, overrides)
        try:
            for item in src:
                if isinstance(item, dict):  # final stats yield
                    count(int(item.get("tokens_generated", 0)))
                    done_frame = {
                        "done": True,
                        reply_key: tok.decode(tokens),
                        # Flush tokens still held by the mid-codepoint
                        # delta hold (empty when the stream ended clean).
                        "delta": (
                            tok.decode(tokens[base:])
                            if base < len(tokens)
                            else ""
                        ),
                        "tokens": int(item.get("tokens_generated", 0)),
                        "latency_s": round(time.time() - t0, 3),
                        "stopped": item.get("stopped"),
                        # Correlation contract (docs/serving.md): the
                        # done frame carries the same id/tenant as the
                        # server-side events and /metrics series.
                        "request_id": (
                            request_id or item.get("request_id")
                        ),
                        "tenant": item.get("tenant", tenant),
                    }
                    if item.get("verify_calls") is not None:
                        # Speculative stream: the done frame carries the
                        # acceptance stats the JSON path reports.
                        done_frame["speculative"] = {
                            "verify_calls": item.get("verify_calls"),
                            "tokens_per_verify": item.get(
                                "tokens_per_verify"
                            ),
                        }
                    yield done_frame
                    return
                tokens.append(int(item))
                delta = tok.decode(tokens[base:])
                if delta and (
                    not delta.endswith("�")
                    # A genuinely invalid byte would hold forever — flush
                    # after 4 held tokens (a UTF-8 codepoint spans ≤4).
                    or len(tokens) - base >= 4
                ):
                    base = len(tokens)
                else:
                    delta = ""
                yield {"token": int(item), "delta": delta}
        except Exception as e:
            # Mid-stream failures (deadline eviction, decode error)
            # become a CORRELATABLE error frame — request_id + tenant —
            # instead of the handler's anonymous fallback frame. The
            # [DONE] terminator still follows from _reply_sse.
            # GeneratorExit (client gone) is BaseException: untouched.
            logger.warning("stream %s failed: %s", request_id, e)
            yield {
                "error": str(e),
                "request_id": request_id,
                "tenant": tenant,
            }
            return
        finally:
            count(len(tokens))
            stream_span.__exit__(None, None, None)
            close = getattr(src, "close", None)
            if close is not None:
                close()  # scheduler stream: flags the lane cancelled

    # -- socket layer ------------------------------------------------------
    def export_page_by_key(self, key: str) -> Optional[bytes]:
        """Serve one cached page's framed bytes for a remote puller
        (GET /pages/<key>). None = not servable right now (not
        resident, bytes still in the deferred harvest queue, or no
        prefix cache) — the puller books a failure and degrades to
        local prefill, so refusing is always safe. The page is
        refcount-pinned across the device_get so eviction pressure
        cannot reassign its arena slot mid-serialization."""
        decoder = self.batcher.decoder
        cache = getattr(decoder, "prefix_cache", None)
        pool = getattr(decoder, "pool", None)
        if cache is None or pool is None or pool.caches is None:
            return None
        pid = cache.pin_key(key)
        if pid is None:
            return None
        try:
            if pid in getattr(decoder, "_queued_dst", ()):
                # Inserted but the bulk copy has not executed: the
                # arena bytes are still the previous occupant's.
                return None
            return pool.export_page(pid)
        except Exception:
            logger.exception("page export failed for %s", key[:16])
            return None
        finally:
            cache.release([pid])

    def make_handler(self):
        server = self

        class Handler(BaseHTTPRequestHandler):
            def log_message(self, fmt, *args):  # route to logging, not stderr
                logger.info("%s %s", self.address_string(), fmt % args)

            _KNOWN_ROUTES = (
                "/", "/chat", "/health", "/healthz", "/metrics",
                "/metrics/history", "/slo", "/stats",
                "/v1/generate", "/v1/chat", "/v1/auth", "/pages",
            )

            def _count(self, code: int) -> None:
                if server.telemetry:
                    # Unknown paths collapse into one label value: a
                    # scanner probing random routes must not be able to
                    # mint unbounded label cardinality.
                    route = self.path.split("?", 1)[0]
                    if route.startswith("/pages/"):
                        # One label for every per-key page fetch.
                        route = "/pages"
                    elif route not in self._KNOWN_ROUTES:
                        route = "<other>"
                    server._m_http.labels(
                        route=route, code=str(code)
                    ).inc()

            def _reply(self, code: int, payload: Dict[str, Any]) -> None:
                self._count(code)
                data = json.dumps(payload).encode()
                self.send_response(code)
                self.send_header("Content-Type", "application/json")
                if isinstance(payload, dict) and "retry_after" in payload:
                    # Overload/drain 503s carry the standard header so
                    # off-the-shelf clients and LBs back off correctly.
                    self.send_header(
                        "Retry-After", str(int(payload["retry_after"]))
                    )
                self.send_header("Content-Length", str(len(data)))
                self.end_headers()
                self.wfile.write(data)

            def _reply_text(self, code: int, text: str,
                            content_type: str) -> None:
                self._count(code)
                data = text.encode()
                self.send_response(code)
                self.send_header("Content-Type", content_type)
                self.send_header("Content-Length", str(len(data)))
                self.end_headers()
                self.wfile.write(data)

            def _token(self) -> Optional[str]:
                auth = self.headers.get("Authorization", "")
                return auth[7:] if auth.startswith("Bearer ") else None

            def _request_id(self) -> Optional[str]:
                # Inbound X-Request-Id (router-minted). Validated so a
                # hostile client can't inject log/JSONL garbage into two
                # tiers of flight rings; anything dubious is ignored and
                # the server mints its own as before.
                rid = self.headers.get("X-Request-Id", "")
                return rid if REQUEST_ID_RX.fullmatch(rid) else None

            def do_GET(self):
                # Health probes often add query strings (cache busting);
                # route on the bare path.
                path, _, query = self.path.partition("?")
                if path == "/metrics/history":
                    # Windowed-history query params (?seconds=&max_points=)
                    # parse here — handle() stays query-string-free; the
                    # route logic itself lives once, in history_route().
                    from urllib.parse import parse_qs

                    qs = parse_qs(query)

                    def _num(key):
                        try:
                            return float(qs[key][0]) if key in qs else None
                        except (TypeError, ValueError):
                            return None

                    self._reply(*server.history_route(
                        seconds=_num("seconds"),
                        max_points=_num("max_points"),
                    ))
                    return
                if path == "/metrics":
                    # Prometheus text exposition: the one non-JSON API
                    # route. Rendered outside handle() so a scrape can
                    # never be confused with a model request.
                    self._reply_text(
                        200,
                        server.render_metrics(),
                        "text/plain; version=0.0.4; charset=utf-8",
                    )
                    return
                if path in ("/", "/chat"):
                    # Built-in chat page (the ref's Electron app role —
                    # serving/webui.py). Static: auth gates the API calls
                    # the page makes, not the page itself.
                    from luminaai_tpu.serving.webui import PAGE

                    data = PAGE.encode()
                    self.send_response(200)
                    self.send_header(
                        "Content-Type", "text/html; charset=utf-8"
                    )
                    self.send_header("Content-Length", str(len(data)))
                    self.end_headers()
                    self.wfile.write(data)
                    return
                if path.startswith("/pages/"):
                    # Cross-replica page export (serving/page_share.py).
                    # Raw framed bytes, not JSON: the payload is a KV
                    # page image, and the puller's parser validates the
                    # LPG1 frame itself.
                    key = path[len("/pages/"):]
                    if not PAGE_KEY_RX.fullmatch(key):
                        self._reply(404, {"error": "bad page key"})
                        return
                    payload = server.export_page_by_key(key)
                    if payload is None:
                        self._reply(404, {"error": "page not available"})
                        return
                    self._count(200)
                    self.send_response(200)
                    self.send_header(
                        "Content-Type", "application/octet-stream"
                    )
                    self.send_header("Content-Length", str(len(payload)))
                    self.end_headers()
                    self.wfile.write(payload)
                    return
                code, payload = server.handle(
                    "GET", path, {}, self._token()
                )
                self._reply(code, payload)

            def _reply_sse(self, events) -> None:
                """Server-sent events: one `data: <json>` frame per event,
                closing with `data: [DONE]` (the OpenAI-style stream
                terminator clients already know how to parse)."""
                try:
                    # Header writes live INSIDE the try: a client gone
                    # before headers raises BrokenPipeError, and the
                    # handler below must still events.close() or the
                    # stream slot leaks (permanent 503s at the cap).
                    self._count(200)
                    self.send_response(200)
                    self.send_header("Content-Type", "text/event-stream")
                    self.send_header("Cache-Control", "no-cache")
                    self.send_header("Connection", "close")
                    self.end_headers()
                    for ev in events:
                        self.wfile.write(
                            b"data: " + json.dumps(ev).encode() + b"\n\n"
                        )
                        self.wfile.flush()
                    self.wfile.write(b"data: [DONE]\n\n")
                except (BrokenPipeError, ConnectionResetError):
                    logger.info("stream client disconnected")
                    events.close()  # stop decoding for a gone client
                except Exception as e:
                    # Headers are already sent: a raised-through error
                    # would make do_POST write a second status line into
                    # the open SSE body. Emit an error frame instead.
                    logger.exception("stream failed mid-flight")
                    try:
                        self.wfile.write(
                            b"data: "
                            + json.dumps({"error": str(e)}).encode()
                            + b"\n\ndata: [DONE]\n\n"
                        )
                    except OSError:
                        pass
                    events.close()

            def do_POST(self):
                try:
                    n = int(self.headers.get("Content-Length", 0))
                    if n > MAX_BODY_BYTES:
                        self._reply(413, {"error": "body too large"})
                        return
                    body = json.loads(self.rfile.read(n) or b"{}")
                    if not isinstance(body, dict):
                        raise ValueError("body must be a JSON object")
                except (ValueError, json.JSONDecodeError) as e:
                    self._reply(400, {"error": f"bad request: {e}"})
                    return
                path = self.path.split("?", 1)[0]
                try:
                    with server.tracer.span(
                        "http_request", route=path,
                        stream=bool(body.get("stream")),
                    ):
                        if (
                            body.get("stream")
                            and path in ("/v1/generate", "/v1/chat")
                        ):
                            err, events = server.start_stream(
                                path, body, self._token(),
                                request_id=self._request_id(),
                            )
                            if err is not None:
                                self._reply(*err)
                            else:
                                self._reply_sse(events)
                            return
                        code, payload = server.handle(
                            "POST", path, body, self._token(),
                            request_id=self._request_id(),
                        )
                except Exception as e:  # surface as 500, keep serving
                    logger.exception("request failed")
                    code, payload = 500, {"error": str(e)}
                self._reply(code, payload)

        return Handler

    def serve_forever(self, host: str = "127.0.0.1", port: int = 5001):
        httpd = ThreadingHTTPServer((host, port), self.make_handler())

        def _graceful(sig, frame):  # pragma: no cover - signal-driven
            logger.warning(
                "signal %s: draining (grace %.0fs) before shutdown",
                sig, self.drain_grace_s,
            )

            def _stop():
                self.drain()
                httpd.shutdown()

            # shutdown() must not run on the serve_forever thread (it
            # joins the poll loop), and a signal handler must return fast.
            threading.Thread(target=_stop, daemon=True).start()

        import signal as _signal

        try:
            _signal.signal(_signal.SIGTERM, _graceful)
            _signal.signal(_signal.SIGINT, _graceful)
        except ValueError:  # pragma: no cover - non-main thread (tests)
            pass
        logger.info("serving on http://%s:%d (secure=%s)", host, port,
                    self.secure)
        try:
            httpd.serve_forever()
        finally:
            httpd.server_close()


def build_server(
    checkpoint: Optional[str] = None,
    host: str = "127.0.0.1",
    port: int = 5001,
    secure: bool = False,
    bootstrap_user: Optional[tuple] = None,
    quantize: Optional[str] = None,
    adapter: Optional[str] = None,
    kv_cache_dtype: Optional[str] = None,
    num_slots: int = 8,
    page_size: int = 128,
    admission_window_ms: float = 0.0,
    telemetry: bool = True,
    trace_jsonl: Optional[str] = None,
    trace_jax: bool = False,
    latency_buckets=None,
    request_timeout_s: Optional[float] = None,
    max_queue_depth: int = 128,
    drain_grace_s: float = 30.0,
    flight_dir: Optional[str] = None,
    max_tenants: int = 64,
    prefill_chunk_tokens: Optional[int] = None,
    prefix_cache_pages: Optional[int] = None,
    prefix_cache_tenant_quota: Optional[int] = None,
    tenant_rate_per_s: Optional[float] = None,
    tenant_burst: Optional[int] = None,
    watchdog: bool = True,
    watchdog_abort: bool = False,
    watchdog_k: Optional[float] = None,
    watchdog_floor_s: Optional[float] = None,
    slo: bool = True,
    slo_config: Optional[str] = None,
    healthz_stale_after_s: Optional[float] = None,
    page_share: Optional[str] = None,
    page_share_self_url: Optional[str] = None,
    page_pull_timeout_s: float = 2.0,
    page_share_max_inflight: int = 2,
):
    """The stack `lumina serve` runs, short of binding the socket: engine
    restored from `checkpoint`, ContinuousScheduler, ChatServer with
    background warmup. chip_smoke.py binds the result to an ephemeral
    port in its own process; `host`/`port` only name this replica to
    its page-share peers."""
    from luminaai_tpu.inference.chat import ChatInterface

    chat = ChatInterface(
        checkpoint_dir=checkpoint, quantize=quantize, adapter=adapter,
        kv_cache_dtype=kv_cache_dtype
    )
    if page_share and not page_share_self_url:
        # Peers reach this replica at the address it serves on; an
        # explicit --page-share-self overrides (NAT, name-based LBs).
        page_share_self_url = f"http://{host}:{port}"
    # Off unless asked for, but always switchable: start_capture() on
    # the server's tracer traces a running replica.
    tracer = SpanTracer(
        jsonl_path=trace_jsonl, enabled=bool(trace_jsonl or trace_jax),
        use_jax_profiler=trace_jax,
    )
    return ChatServer(
        chat.engine, secure=secure, bootstrap_user=bootstrap_user,
        num_slots=num_slots, page_size=page_size,
        admission_window_ms=admission_window_ms,
        prefill_chunk_tokens=prefill_chunk_tokens,
        prefix_cache_pages=prefix_cache_pages,
        prefix_cache_tenant_quota=prefix_cache_tenant_quota,
        tenant_rate_per_s=tenant_rate_per_s,
        tenant_burst=tenant_burst,
        telemetry=telemetry,
        tracer=tracer,
        request_timeout_s=request_timeout_s,
        max_queue_depth=max_queue_depth,
        drain_grace_s=drain_grace_s,
        # Drain dumps the wide-event ring next to the checkpoint (or the
        # working dir) so a SIGTERM'd server leaves a queryable trail.
        flight_dir=flight_dir or checkpoint or ".",
        max_tenants=max_tenants,
        # Hang watchdog over the decode loop (--no-watchdog disables;
        # --watchdog-abort exits 75 on a confirmed stall so the
        # orchestrator restarts the replica; --watchdog-k/--watchdog-floor
        # tune the robust threshold).
        watchdog=("auto" if watchdog else None),
        watchdog_abort=watchdog_abort,
        watchdog_k=watchdog_k,
        watchdog_floor_s=watchdog_floor_s,
        # SLO engine + history ring (--no-slo disables; --slo-config
        # replaces the default objectives; --healthz-stale-after flips
        # /healthz to "degraded" on a busy-but-silent decode loop).
        slo=slo,
        slo_config=slo_config,
        healthz_stale_after_s=healthz_stale_after_s,
        # Cross-replica page sharing (--page-share <router-url>): the
        # replica reports harvested chain keys to the router and pulls
        # indexed pages from sibling replicas on cold admissions.
        page_share=page_share,
        page_share_self_url=page_share_self_url,
        page_pull_timeout_s=page_pull_timeout_s,
        page_share_max_inflight=page_share_max_inflight,
        latency_buckets=(
            tuple(latency_buckets)
            if latency_buckets
            else DEFAULT_LATENCY_BUCKETS
        ),
        # Real checkpoints compile for minutes: gate /healthz behind a
        # background warmup generation so probes hold traffic until the
        # executables exist.
        warmup=True,
    )


def serve(host: str = "127.0.0.1", port: int = 5001, **kw) -> None:
    """Build an engine from a checkpoint and serve it (CLI `serve`);
    keywords are build_server's."""
    build_server(host=host, port=port, **kw).serve_forever(host, port)

