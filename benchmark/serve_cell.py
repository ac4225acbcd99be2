"""A serving cell: `GenerationEngine` + `ContinuousScheduler` (what
`ChatServer` builds), driven as its HTTP handler drives it — token ids
into `submit_stream`, one consumer thread a request — by an open-loop
schedule or closed-loop clients from traffic_gen. The tokenizer, HTTP and
auth are outside these cells.

Time-stamping follows bench.py::_serve_run_continuous (a stamp per token
in the consumer thread), with one change that matters in an open loop: a
request's clock starts when it was DUE, not when the generator got round
to sending it, and the generator's lateness is printed.
"""

from __future__ import annotations

import shutil
import tempfile
import threading
import time
from typing import Any, Dict, List, Optional

import numpy as np

from benchmark import (common, correct, flops, layer_readers, model_config,
                       program_adapter, reference, stats, traffic_gen)
from benchmark.common import say

SAMPLE_PROMPT_TOKENS = 512
SAMPLE_NEW_TOKENS = 8
now = time.perf_counter


class StubTokenizer:
    """The engine's tokenizer contract with stop ids OUTSIDE the
    vocabulary: a request's length is its budget, never a sampled EOS."""

    def __init__(self, vocab_size: int):
        self.eos_token_id = vocab_size + 1
        self.pad_token_id = vocab_size + 2
        self.im_end = vocab_size + 3

    class backend:
        @staticmethod
        def encode(text):
            return [3 + (ord(c) % 200) for c in text]

    @staticmethod
    def decode(tokens):
        return " ".join(str(t) for t in tokens)


class Record:
    __slots__ = ("req", "t_due", "t_sent", "stamps", "error", "done")

    def __init__(self, req, t_due):
        self.req, self.t_due = req, t_due
        self.t_sent: Optional[float] = None
        self.stamps: List[float] = []
        self.error: Optional[str] = None
        self.done = False


def consume(sched, rec: Record, stop: threading.Event) -> None:
    """One request through submit_stream, a stamp per token."""
    rec.t_sent = now()
    gen = sched.submit_stream(rec.req.prompt,
                              correct.greedy_kwargs(rec.req.max_new))
    try:
        for item in gen:
            if isinstance(item, dict):
                rec.done = True
                break
            rec.stamps.append(now())
            if stop.is_set():
                break
    except Exception as e:  # a refused or failed request is a result
        rec.error = f"{type(e).__name__}: {e}"
    finally:
        gen.close()


def make_serving_params(model, seed: int):
    """Weights on the device in one jitted call from the seed, in the
    type they are served in (inference/chat.py's downcast: every float
    leaf to bfloat16)."""
    import jax
    import jax.numpy as jnp

    from luminaai_tpu.parallel.sharding import unbox

    def init(rng):
        params = unbox(
            model.init(rng, jnp.zeros((1, 8), jnp.int32))["params"])
        return jax.tree.map(
            lambda x: x.astype(jnp.bfloat16)
            if jnp.issubdtype(x.dtype, jnp.floating) else x, params)

    return jax.jit(init)(jax.random.key(seed))


def reference_phase(cell, cfg, model, params, seed: int):
    """Before the KV pool exists (the float32 reference needs the room):
    the program's uncached logits against the reference's on a seeded
    sample, and the reference's own greedy continuation of the prompt."""
    import jax

    rs = np.random.RandomState(seed % (2**32))
    prompt = rs.randint(3, cfg.vocab_size, size=SAMPLE_PROMPT_TOKENS).tolist()
    kw = reference.from_config_file(cell.config)
    ref_fn = jax.jit(lambda p, ids: reference.forward(
        program_adapter.params_view(cfg, p), ids, **kw))
    stamps = [time.time()]

    def timed_ref(x):
        out = ref_fn(params, x)
        out.block_until_ready()
        stamps.append(time.time())
        return out

    want_tokens, rows, ids = correct.reference_continuation(
        timed_ref, prompt, SAMPLE_NEW_TOKENS)
    want = timed_ref(ids)
    got = jax.jit(lambda p, x: program_adapter.program_logits(model, p, x))(
        params, ids)
    got.block_until_ready()
    verdict = correct.compare_logits(got, want)
    verdict["reference_first_call_s"] = stamps[1] - stamps[0]
    verdict["reference_later_call_s"] = (stamps[-1] - stamps[1]) / (len(stamps) - 2)
    verdict["program_forward_s"] = time.time() - stamps[-1]
    verdict["hbm_peak_gb"] = common.memory_peak_bytes() / 1e9
    del got, want
    return prompt, want_tokens, rows, verdict


def warm_up(sched, decoder, vocab: int, max_tokens: int) -> int:
    """Every program the traffic can reach: the prefill-chunk program and
    the decode step at each power-of-two page extent (the decode
    executable is specialised by the longest active lane)."""
    page = decoder.pool.page_size
    extents, p = [], 1
    while True:
        extents.append(min(p, decoder.pool.pages) * page)
        if extents[-1] >= min(max_tokens, decoder.slot_tokens):
            break
        p *= 2
    # A prompt no longer than one chunk takes the whole-prompt prefill
    # (one bucket) and the insert program instead of the chunk program.
    lengths = [max(1, decoder.prefill_chunk // 2)] + [
        max(1, min(e - 4, decoder.token_capacity - 4)) for e in extents]
    for n in lengths:
        prompt = (np.arange(n) % (vocab - 3) + 3).tolist()
        t0 = time.time()
        got = correct.decode_through_scheduler(sched, prompt, 3)
        if len(got) != 3:
            raise RuntimeError(f"warm-up prompt of {n} gave {got}")
        say("warm", prompt_tokens=n, seconds=time.time() - t0,
            hbm_peak_gb=common.memory_peak_bytes() / 1e9)
    return len(extents)


def set_up(cell, args, tracer) -> Dict[str, Any]:
    """Weights, the reference phase, the scheduler `ChatServer` would
    build, every program warm, and the paged path checked: all of set-up."""
    import jax

    from luminaai_tpu.inference.generate import GenerationEngine
    from luminaai_tpu.models.transformer import LuminaTransformer
    from luminaai_tpu.monitoring.telemetry import MetricsRegistry
    from luminaai_tpu.serving.server import ContinuousScheduler

    mix, dep = cell.traffic, cell.config["deployment"]
    cfg = model_config.build_config(
        cell.config, seed=common.fold_seed(args.seed))
    model = LuminaTransformer(cfg)
    t0 = time.time()
    params = make_serving_params(model, common.fold_seed(args.seed))
    jax.block_until_ready(params)
    say("serve", phase="weights made on device", seconds=time.time() - t0,
        params=flops.params_total(cell.config),
        hbm_peak_gb=common.memory_peak_bytes() / 1e9)

    t0 = time.time()
    prompt, want_tokens, rows, verdict = reference_phase(
        cell, cfg, model, params, args.seed)
    say("correct", what="uncached logits vs reference",
        seconds=time.time() - t0, **verdict)

    engine = GenerationEngine(model, params,
                              StubTokenizer(cfg.vocab_size), cfg)
    registry = MetricsRegistry()
    sched = ContinuousScheduler(
        engine, num_slots=int(dep["num_slots"]),
        page_size=int(dep["page_size"]),
        max_slot_tokens=int(dep["max_slot_tokens"]),
        prefix_cache_pages=int(dep.get("prefix_cache_pages", 0)),
        registry=registry, tracer=tracer,
    )
    t0 = time.time()
    longest = sum(
        mix[k].get("max", mix[k].get("value", 0))
        for k in ("prompt_tokens", "output_tokens"))
    n_ext = warm_up(sched, sched.decoder, cfg.vocab_size, longest)
    first_answer = correct.decode_through_scheduler(
        sched, prompt, SAMPLE_NEW_TOKENS)
    paged = correct.check_tokens(first_answer, want_tokens, rows)
    say("correct", what="paged prefill + decode vs reference tokens",
        seconds=time.time() - t0, decode_extents_warmed=n_ext, **paged)
    return {"cfg": cfg, "sched": sched, "registry": registry,
            "prompt": prompt, "first_answer": first_answer,
            "ok": bool(verdict["ok"] and paged["ok"])}


def run(cell, args, device: Dict[str, Any]) -> Dict[str, Any]:
    from luminaai_tpu.monitoring.tracing import SpanTracer

    mix, dep = cell.traffic, cell.config["deployment"]
    compiles = common.CompileCounter()
    # The tracer is off; --trace 2 switches it on through its own capture
    # control once the window's numbers are taken.
    tracer = SpanTracer(enabled=False)
    tracing = None
    try:
        up = set_up(cell, args, tracer)
        cfg, sched, registry = up["cfg"], up["sched"], up["registry"]
        prompt, first_answer = up["prompt"], up["first_answer"]
        decoder = sched.decoder

        if mix["kind"] == "open_loop":
            res = drive_open_loop(sched, mix, args, cfg.vocab_size, compiles,
                                  registry)
        elif mix["kind"] == "closed_loop":
            res = drive_closed_loop(sched, mix, args, cfg.vocab_size,
                                    compiles, registry, int(dep["num_slots"]))
        else:
            raise ValueError(f"traffic kind {mix['kind']!r} is not serving")
        if args.trace:
            # The window ended, drained and gave its numbers exactly as
            # under --trace 0. Only now does anything of the profiler run.
            tracing = Tracing(float(mix.get("trace_seconds", 5)), registry,
                              tracer)
            say("traced_tail", **traced_tail(
                sched, mix, args, cfg.vocab_size, tracing,
                int(dep["num_slots"])))

        again = correct.decode_through_scheduler(
            sched, prompt, SAMPLE_NEW_TOKENS)
        repeat_ok = again == first_answer
        peak_bytes = common.memory_peak_bytes()
        host = res["host"]
        host["peak_hbm_gb"] = peak_bytes / 1e9
        ok = bool(up["ok"] and repeat_ok
                  and res["built_in_window"] == 0 and res["attempted"] > 0)
        say("window", **res["report"], repeat_identical=repeat_ok,
            programs_built_in_window=res["built_in_window"],
            compile_seconds_total=compiles.backend_s,
            memory_peak_bytes=peak_bytes, setup_s=host["setup_s"],
            pool=decoder.pool.stats() if hasattr(decoder.pool, "stats") else None)
        device_out = {k: device[k] for k in ("platform", "kind", "count")}
        device_out["memory_peak_bytes"] = peak_bytes
        out = {"correct": ok, "attempted": res["attempted"],
               "failed": res["failed"], "device": device_out,
               "metrics": common.metric_values(cell.end_to_end, host)}
        if not args.trace:
            return out
        values, busy, win_s, breakdown, notes = layer_readers.reduce_traced_run(
            tracing.dir, cell,
            dict(steps=int(tracing.steps),
                 registry_delta=res["registry_delta"], host=host,
                 body=cell.config, shapes={}, peak=device["peak"]),
            keep_as=getattr(args, "keep_trace", None))
        device_out.update(busy_s=busy, window_s=win_s)
        say("per_layer", notes=notes, values=values)
        # The end-to-end numbers were taken from the untraced window, so
        # both kinds stand side by side.
        out["metrics"].update(common.metric_values(cell.per_layer, values))
        out["breakdown"] = breakdown
        return out
    finally:
        if tracing is not None:
            tracing.discard()


class Tracing:
    """The traced seconds of a --trace 2 run: `seconds` of the window's
    traffic AFTER the window has closed and its numbers are taken, through
    the program's own capture control (`SpanTracer.start_capture`), so
    that nothing of the profiler exists in the process before then."""

    def __init__(self, seconds: float, registry, tracer):
        self.seconds = seconds
        self.registry, self.tracer = registry, tracer
        self.dir: Optional[str] = None
        self.on = False
        self.steps = 0.0
        self._steps0 = 0.0

    def _steps(self) -> float:
        return layer_readers.registry_view(self.registry).get(
            "counter:serve_decode_steps_total", 0.0)

    def start_capture(self) -> None:
        """The traced seconds begin."""
        self.dir = tempfile.mkdtemp(prefix="benchmark_trace_")
        self._steps0 = self._steps()
        if not self.tracer.start_capture(self.dir):
            raise RuntimeError("the program's capture control refused")
        self.on = True

    def stop(self) -> None:
        if self.on:
            self.on = False
            # Before the stop: writing the trace out takes seconds, and
            # the scheduler keeps stepping meanwhile.
            self.steps = self._steps() - self._steps0
            self.tracer.stop_capture()

    def discard(self) -> None:
        self.stop()
        if self.dir:
            shutil.rmtree(self.dir, ignore_errors=True)


class WindowMarks:
    """Read when the window opens, differenced when it closes: set-up
    seconds, the registry, the count of programs built."""

    def __init__(self, registry, compiles):
        self.setup_s = time.time() - common.PROCESS_T0
        self._registry, self._compiles = registry, compiles
        self._reg_open = layer_readers.registry_view(registry)
        self._built_open = compiles.lowered

    def close(self):
        """(programs built in the window, registry delta over it)."""
        return (
            self._compiles.lowered - self._built_open,
            layer_readers.delta(
                layer_readers.registry_view(self._registry), self._reg_open),
        )


def _latencies(records: List[Record]):
    ttft, gaps, lateness = [], [], []
    for r in records:
        if r.t_sent is not None:
            lateness.append(r.t_sent - r.t_due)
        if r.stamps:
            ttft.append(r.stamps[0] - r.t_due)
            gaps += [b - a for a, b in zip(r.stamps, r.stamps[1:])]
    return ttft, gaps, lateness


def _ttft_stats(ttft: List[float]) -> Dict[str, float]:
    """Time to first token over ALL measured requests, in ms: the mean,
    the 90th percentile and the mean of the slowest tenth. One
    order statistic of ~100 requests moves by whole scheduler ticks from
    run to run; the averages do not (PERF.md, PR 24)."""
    s = sorted(ttft)
    if not s:
        return {}
    tail = s[len(s) - max(1, len(s) // 10):]
    return {
        "ttft_mean_ms": 1e3 * sum(s) / len(s),
        "ttft_p90_ms": 1e3 * stats.pctl(s, 90),
        "ttft_tail10_mean_ms": 1e3 * sum(tail) / len(tail),
    }


def _failed(r: Record) -> bool:
    return bool(r.error) or not r.done or len(r.stamps) != r.req.max_new


def send_schedule(sched, schedule, t_start: float, pre_s: float,
                  stop: threading.Event, at_window_open):
    """Send each request of an open-loop schedule when it is due, one
    consumer thread a request. `at_window_open` is called at the fixed
    point of the schedule where the pre-roll ends, on a server the
    pre-roll has loaded. Returns (records, threads, what that call
    returned)."""
    records: List[Record] = []
    threads: List[threading.Thread] = []
    opened, marks = False, None
    for req in schedule:
        if req.measured and not opened:
            delay = t_start + pre_s - now()
            if delay > 0:
                time.sleep(delay)
            marks = at_window_open()
            opened = True
        t_due = t_start + req.due_s
        delay = t_due - now()
        if delay > 0:
            time.sleep(delay)
        rec = Record(req, t_due)
        th = threading.Thread(target=consume, args=(sched, rec, stop),
                              daemon=True)
        th.start()
        records.append(rec)
        threads.append(th)
    return records, threads, marks


def drive_open_loop(sched, mix, args, vocab, compiles, registry):
    schedule = traffic_gen.open_loop_schedule(mix, args.seed, args.seconds,
                                              vocab)
    pre_s = float(mix["preroll_s"])
    stop = threading.Event()  # never set: every request runs to its end
    t_start = now()
    records, threads, marks = send_schedule(
        sched, schedule, t_start, pre_s, stop,
        lambda: WindowMarks(registry, compiles))
    t_end = t_start + pre_s + float(args.seconds)
    if t_end - now() > 0:
        time.sleep(t_end - now())
    built_in_window, reg = marks.close()
    deadline = now() + float(mix["drain_s"])
    for th in threads:
        th.join(max(0.0, deadline - now()))
    drained_s = now() - t_end
    measured = [r for r in records if r.req.measured]
    failed = sum(_failed(r) for r in measured)
    ttft, gaps, late = _latencies(measured)
    out_tokens = sum(len(r.stamps) for r in measured)
    in_window = sum(1 for r in records for s in r.stamps
                    if t_end - float(args.seconds) <= s < t_end)
    host = {
        "setup_s": marks.setup_s,
        **_ttft_stats(ttft),
        "itl_p95_ms": 1e3 * (stats.pctl(gaps, 95) or 0.0),
        "serve_tok_s": in_window / float(args.seconds),
        "output_tokens_in_window": float(in_window),
    }
    say("ttft", sorted_ms=[round(1e3 * x, 1) for x in sorted(ttft)],
        **{k: v for k, v in host.items() if k.startswith("ttft_")})
    report = {
        "kind": "open_loop", "rate_per_s": mix["arrivals"]["rate_per_s"],
        "requests_due_in_window": len(measured), "failed": failed,
        "preroll_requests": len(records) - len(measured),
        "ttft_ms": _ms(stats.summary(ttft, 90)), "itl_ms": _ms(stats.summary(gaps)),
        "generator_lateness_ms": _ms(stats.summary(late)),
        "completed_tokens_per_s_in_window": host["serve_tok_s"],
        "output_tokens_of_measured": out_tokens, "drain_s": drained_s,
        "errors": sorted({r.error for r in measured if r.error})[:3],
    }
    return {"attempted": len(measured), "failed": failed, "host": host,
            "report": report, "registry_delta": reg,
            "built_in_window": built_in_window}


def start_clients(sched, mix, seed: int, vocab: int, num_slots: int,
                  stop: threading.Event):
    """Closed-loop clients, each sending its next request when the last
    has ended, until `stop`. Returns (records, their lock, threads)."""
    n_clients = int(mix["clients_per_slot"] * num_slots)
    queues = traffic_gen.closed_loop_clients(
        mix, seed, n_clients, int(mix.get("requests_per_client", 64)), vocab)
    records: List[Record] = []
    lock = threading.Lock()

    def client(reqs):
        for req in reqs:
            if stop.is_set():
                return
            rec = Record(req, now())
            with lock:
                records.append(rec)
            consume(sched, rec, stop)

    threads = [threading.Thread(target=client, args=(q,), daemon=True)
               for q in queues]
    for th in threads:
        th.start()
    return records, lock, threads


def traced_tail(sched, mix, args, vocab, tracing: Tracing,
                num_slots: int) -> Dict[str, Any]:
    """--trace 2, once the window's requests have drained and its numbers
    are taken: fresh traffic of the same mix from the same generator. The
    profiler is started and stopped once for nothing, the pre-roll loads
    the server, `trace_seconds` are captured, and what still runs then is
    cancelled."""
    warm_s = common.warm_profiler(tracing.tracer)
    pre_s = float(mix["preroll_s"])
    stop = threading.Event()
    t_start = now()
    if mix["kind"] == "open_loop":
        schedule = traffic_gen.open_loop_schedule(
            mix, args.seed, tracing.seconds, vocab)
        _, threads, _ = send_schedule(sched, schedule, t_start, pre_s, stop,
                                      tracing.start_capture)
    else:
        _, _, threads = start_clients(sched, mix, args.seed, vocab,
                                      num_slots, stop)
        time.sleep(pre_s)
        tracing.start_capture()
    left = t_start + pre_s + tracing.seconds - now()
    if left > 0:
        time.sleep(left)
    tracing.stop()  # writes the trace out: seconds
    stop.set()
    deadline = now() + float(mix["drain_s"])
    for th in threads:
        th.join(max(0.0, deadline - now()))
    return {"profiler_first_start_s": warm_s,
            "decode_steps": tracing.steps,
            "tail_s": warm_s + now() - t_start,
            "still_running": sum(th.is_alive() for th in threads)}


def drive_closed_loop(sched, mix, args, vocab, compiles, registry,
                      num_slots):
    stop = threading.Event()
    records, lock, threads = start_clients(sched, mix, args.seed, vocab,
                                           num_slots, stop)
    time.sleep(float(mix["preroll_s"]))
    t_open = now()
    marks = WindowMarks(registry, compiles)
    time.sleep(float(args.seconds))
    t_close = now()
    built_in_window, reg = marks.close()
    stop.set()
    deadline = now() + float(mix["drain_s"])
    for th in threads:
        th.join(max(0.0, deadline - now()))
    with lock:
        snapshot = list(records)
    # Requests sent inside the window are the attempted ones; one that the
    # stop cut short is not a failure, one that erred or ended short is.
    measured = [r for r in snapshot if t_open <= r.t_due < t_close]
    failed = sum(1 for r in measured
                 if r.error or (r.done and len(r.stamps) != r.req.max_new))
    in_window = sum(1 for r in snapshot for s in r.stamps
                    if t_open <= s < t_close)
    ttft, gaps, _ = _latencies(measured)
    seconds = t_close - t_open
    host = {
        "setup_s": marks.setup_s,
        "serve_tok_s": in_window / seconds,
        **_ttft_stats(ttft),
        "itl_p95_ms": 1e3 * (stats.pctl(gaps, 95) or 0.0),
        "output_tokens_in_window": float(in_window),
    }
    report = {
        "kind": "closed_loop", "clients": len(threads),
        "requests_sent_in_window": len(measured), "failed": failed,
        "completed_in_window": sum(1 for r in measured if r.done),
        "window_s": seconds, "output_tokens_in_window": in_window,
        "ttft_ms": _ms(stats.summary(ttft, 90)), "itl_ms": _ms(stats.summary(gaps)),
        "drain_s": now() - t_close,
        "clients_still_running": sum(th.is_alive() for th in threads),
        "errors": sorted({r.error for r in measured if r.error})[:3],
    }
    return {"attempted": len(measured), "failed": failed, "host": host,
            "report": report, "registry_delta": reg,
            "built_in_window": built_in_window}


def _ms(summary: Dict[str, Any]) -> Dict[str, Any]:
    return {k: (1e3 * v if isinstance(v, float) else v)
            for k, v in summary.items()}
