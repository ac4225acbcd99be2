"""SLO engine, time-series ring, `lumina top`, and satellites (ISSUE 15).

Covers: ring sampling semantics (counter deltas, windowed histogram
quantiles, series budget `_overflow`), the concurrent
sample-vs-scrape-vs-emit race, windowed-quantile monotonicity, the
burn-rate fire/clear hysteresis contract, the end-to-end injected
decode stall (slow_tick -> page -> /slo + flight dump + `lumina top
--once --json` -> clear after recovery), `lumina top --once` golden
output, the sampler overhead A/B (slow-marked), build_info, /healthz
staleness, and `lumina events --stats --by`.
"""

import json
import threading
import time

import numpy as np
import pytest

from luminaai_tpu.config import Config
from luminaai_tpu.monitoring.events import FlightRecorder, events_stats
from luminaai_tpu.monitoring.slo import (
    Objective,
    SLOEngine,
    default_serve_objectives,
    default_train_objectives,
    load_slo_config,
    objectives_for,
)
from luminaai_tpu.monitoring.telemetry import (
    DEFAULT_LATENCY_BUCKETS,
    MetricsRegistry,
    register_build_info,
)
from luminaai_tpu.monitoring.timeseries import (
    OVERFLOW_SERIES,
    TimeSeriesRing,
    load_history,
    windowed_quantile,
)


# ---------------------------------------------------------------------------
# serving doubles (the tests/test_resilience.py pattern)
# ---------------------------------------------------------------------------
class _TokBackend:
    @staticmethod
    def encode(text):
        return [ord(c) % 250 for c in text]


class _Tok:
    backend = _TokBackend()

    def decode(self, tokens):
        return ",".join(str(t) for t in tokens)


class _Stepper:
    """Deterministic StepwiseDecoder double over a real PagedKVPool."""

    def __init__(self, num_slots=2, slot_tokens=64):
        from luminaai_tpu.inference.kv_pool import PagedKVPool

        self.num_slots = num_slots
        self.slot_tokens = slot_tokens
        self.pool = PagedKVPool(None, num_slots, 1, slot_tokens)
        self.steps = 0
        self._active = [False] * num_slots
        self._next = [0] * num_slots

    def has_free_slot(self):
        return self.pool.has_free()

    def acquire_slot(self):
        return self.pool.alloc()

    def release_slot(self, slot):
        self._active[slot] = False
        self.pool.free(slot)

    def lane_full(self, slot):
        return False

    def prefill_into_slot(self, slot, prompt, max_new_tokens=1,
                          sample_key=None, seed=None):
        first = int(prompt[0])
        self._active[slot] = max_new_tokens > 1
        self._next[slot] = first + 1
        self.pool.lengths[slot] = len(prompt)
        return {"token": first, "prompt_tokens": len(prompt),
                "is_stop": False}

    def decode_step(self, sample_key=None):
        time.sleep(0.003)
        toks = np.zeros((self.num_slots,), np.int64)
        eos = np.zeros((self.num_slots,), bool)
        produced = np.asarray(self._active, bool).copy()
        for s in range(self.num_slots):
            if self._active[s]:
                toks[s] = self._next[s]
                self._next[s] += 1
        self.steps += 1
        return toks, produced, eos


class _Engine:
    def __init__(self, **cfg_kw):
        self.config = Config(
            vocab_size=256, hidden_size=64, num_layers=2, num_heads=4,
            num_kv_heads=2, seq_length=64, use_flash_attention=False,
            **cfg_kw,
        )
        self.tokenizer = _Tok()
        self.stepper = _Stepper(2)

    def make_stepwise(self, **kw):
        return self.stepper

    def encode_chat(self, messages):
        return self.tokenizer.backend.encode(messages[-1]["content"])


# ---------------------------------------------------------------------------
# time-series ring: sampling semantics
# ---------------------------------------------------------------------------
def test_counter_sampled_as_deltas():
    reg = MetricsRegistry()
    c = reg.counter("jobs_total", "")
    ring = TimeSeriesRing(reg, interval_s=1.0)
    c.inc(5)
    ring.sample_once(now=100.0)
    c.inc(3)
    ring.sample_once(now=101.0)
    ring.sample_once(now=102.0)  # no traffic: delta 0
    pts = ring.window("jobs_total", 60, now=102.0)
    assert [v for _, v in pts] == [5.0, 3.0, 0.0]
    # Window sums are event counts over the window, not lifetime values.
    assert ring.window_sum(["jobs_total"], 1.5, now=102.0) == 3.0


def test_labeled_counter_series_keys_and_gauge_nan_skip():
    reg = MetricsRegistry()
    c = reg.counter("req_total", "", labelnames=("route",))
    g = reg.gauge("busted", "")
    g.set_function(lambda: float("nan"))  # collected weak ref reads NaN
    c.labels(route="/a").inc(2)
    ring = TimeSeriesRing(reg, interval_s=1.0)
    ring.sample_once(now=10.0)
    assert ring.window("req_total{route=/a}", 60, now=10.0) == [(10.0, 2.0)]
    assert ring.window("busted", 60, now=10.0) == []  # NaN never stored


def test_histogram_windowed_quantiles_reflect_window_not_lifetime():
    reg = MetricsRegistry()
    h = reg.histogram("lat", "", buckets=DEFAULT_LATENCY_BUCKETS)
    ring = TimeSeriesRing(reg, interval_s=1.0)
    for _ in range(20):
        h.observe(0.01)
    ring.sample_once(now=1.0)
    for _ in range(20):
        h.observe(3.0)
    ring.sample_once(now=2.0)
    p50 = dict(ring.window("lat:p50", 60, now=2.0))
    # First window sees only the fast observations, second ONLY the slow
    # ones — while the live histogram's lifetime p50 would straddle.
    assert p50[1.0] < 0.05
    assert p50[2.0] > 2.0
    assert h.quantile(0.5) < 1.0  # lifetime view disagrees, by design
    counts = dict(ring.window("lat:count", 60, now=2.0))
    assert counts == {1.0: 20.0, 2.0: 20.0}


def test_windowed_quantile_monotone_property():
    """Property: for any delta-count vector, quantiles are monotone in q
    (same frozen cumulative distribution as the live histogram rule)."""
    rng = np.random.RandomState(7)
    bounds = list(DEFAULT_LATENCY_BUCKETS)
    qs = (0.05, 0.25, 0.5, 0.75, 0.9, 0.95, 0.99, 1.0)
    for _ in range(200):
        counts = rng.randint(0, 4, size=len(bounds) + 1).tolist()
        if sum(counts) == 0:
            assert windowed_quantile(bounds, counts, 0.5) is None
            continue
        vals = [windowed_quantile(bounds, counts, q) for q in qs]
        assert all(
            a <= b + 1e-12 for a, b in zip(vals, vals[1:])
        ), (counts, vals)


def test_ring_quantiles_monotone_across_live_windows():
    reg = MetricsRegistry()
    h = reg.histogram("lat", "", buckets=DEFAULT_LATENCY_BUCKETS)
    ring = TimeSeriesRing(reg, interval_s=1.0)
    rng = np.random.RandomState(3)
    for i in range(30):
        for _ in range(int(rng.randint(1, 12))):
            h.observe(float(rng.exponential(0.05)))
        ring.sample_once(now=float(i))
    p50 = dict(ring.window("lat:p50", 1e9, now=30.0))
    p95 = dict(ring.window("lat:p95", 1e9, now=30.0))
    p99 = dict(ring.window("lat:p99", 1e9, now=30.0))
    assert p50 and set(p50) == set(p95) == set(p99)
    for ts in p50:
        assert p50[ts] <= p95[ts] + 1e-12 <= p99[ts] + 1e-9


def test_series_budget_overflows_like_label_budget():
    reg = MetricsRegistry()
    for i in range(8):
        reg.gauge(f"g{i}", "").set(i)
    ring = TimeSeriesRing(reg, interval_s=1.0, max_series=3)
    ring.sample_once(now=1.0)
    ring.sample_once(now=2.0)
    names = ring.series_names()
    # Budget holds: 3 real series + the shared overflow sink, never more.
    assert len(names) == 4 and OVERFLOW_SERIES in names
    st = ring.stats()
    assert st["series"] == 4
    assert st["overflow_points"] == 10  # 5 suppressed series x 2 samples
    # The sink counts suppressed points per tick (visible loss).
    assert ring.window(OVERFLOW_SERIES, 60, now=2.0) == [
        (1.0, 5.0), (2.0, 5.0),
    ]


def test_ring_capacity_bounds_points_per_series():
    reg = MetricsRegistry()
    reg.gauge("g", "").set(1)
    ring = TimeSeriesRing(reg, interval_s=1.0, capacity=16)
    for i in range(100):
        ring.sample_once(now=float(i))
    assert len(ring.window("g", 1e9, now=100.0)) == 16


def test_concurrent_sample_scrape_emit_race():
    """The PR-7-style race contract for the ring: producers emitting,
    the sampler sampling, and scrapes (ring snapshot + Prometheus
    render) all concurrently — no exception, and the sampled counter
    deltas sum to exactly what the sampler observed."""
    reg = MetricsRegistry()
    c = reg.counter("c_total", "")
    h = reg.histogram("h", "", buckets=(0.01, 0.1, 1.0))
    g = reg.gauge("g", "")
    # Capacity must exceed the free-running sampler's iteration count
    # for the whole window: once the ring wraps, the oldest counter
    # deltas are (correctly) evicted and the exact-sum assertion below
    # no longer holds — that's capacity semantics, not a race.
    ring = TimeSeriesRing(reg, interval_s=1.0, capacity=65536)
    stop = threading.Event()
    errors = []

    def guard(fn):
        def run():
            try:
                i = 0
                while not stop.is_set():
                    fn(i)
                    i += 1
            except Exception as e:  # pragma: no cover - the assertion
                errors.append(e)
        return run

    threads = [
        threading.Thread(target=guard(
            lambda i: (c.inc(), h.observe(0.05), g.set(i))
        )),
        threading.Thread(target=guard(lambda i: ring.sample_once())),
        threading.Thread(target=guard(
            lambda i: (ring.snapshot(), reg.render_prometheus())
        )),
    ]
    for t in threads:
        t.start()
    time.sleep(0.3)
    stop.set()
    for t in threads:
        t.join(timeout=5)
    assert not errors, errors
    ring.sample_once()  # flush the tail delta
    sampled = sum(v for _, v in ring.window("c_total", 1e9))
    assert sampled == c.value


def test_dump_load_roundtrip_and_forensic_naming(tmp_path):
    reg = MetricsRegistry()
    reg.counter("c_total", "").inc(4)
    ring = TimeSeriesRing(reg, interval_s=1.0)
    ring.sample_once(now=5.0)
    path = ring.dump_to_dir(str(tmp_path), reason="unit test!",
                            slo={"objectives": {"o": {"state": "ok"}}})
    assert path and "tshist-" in path and "unit_test" in path
    doc = load_history(path)
    assert doc["series"]["c_total"] == [[5.0, 4.0]]
    assert doc["slo"]["objectives"]["o"]["state"] == "ok"
    bad = tmp_path / "junk.json"
    bad.write_text("[1,2,3]")
    with pytest.raises(ValueError):
        load_history(str(bad))


# ---------------------------------------------------------------------------
# SLO engine: burn rates, fire/clear hysteresis
# ---------------------------------------------------------------------------
def _ttft_rig(**engine_kw):
    reg = MetricsRegistry()
    rec = FlightRecorder()
    h = reg.histogram("serve_ttft_seconds", "",
                      buckets=DEFAULT_LATENCY_BUCKETS)
    ring = TimeSeriesRing(reg, interval_s=1.0)
    kw = dict(fast_window_s=10.0, slow_window_s=100.0,
              fast_burn=10.0, slow_burn=2.0, clear_evals=2)
    kw.update(engine_kw)
    eng = SLOEngine(
        ring,
        [Objective(name="ttft", series="serve_ttft_seconds:p95",
                   op="<=", target=0.5, budget=0.1)],
        registry=reg, recorder=rec, program="serve", **kw,
    )
    return reg, rec, h, ring, eng


def test_burn_rate_fire_and_clear_hysteresis():
    """The alert contract: page fires the moment the fast window is
    saturated; a single good evaluation does NOT clear (hysteresis);
    `clear_evals` consecutive comfortably-below evaluations do, and the
    clear is a booked transition, not a silent flip."""
    reg, rec, h, ring, eng = _ttft_rig()
    t = 1000.0
    for i in range(12):
        h.observe(0.1)
        ring.sample_once(now=t + i)
        eng.evaluate(now=t + i)
    assert eng.state("ttft") == "ok"
    # Stall: the 10s fast window fills with violating samples.
    fired_at = None
    for i in range(12, 40):
        h.observe(4.0)
        ring.sample_once(now=t + i)
        v = eng.evaluate(now=t + i)["objectives"]["ttft"]
        if v["state"] == "page":
            fired_at = i
            break
    assert fired_at is not None, "fast-window page never fired"
    fires = rec.snapshot(type="slo_burn")
    assert fires and fires[-1]["severity"] == "page"
    assert fires[-1]["transition"] == "fire"
    alerts = reg.get("slo_burn_alerts_total")
    assert alerts.labels(objective="ttft", severity="page").value == 1
    # Recovery: healthy samples; far enough ahead that the slow window
    # dilutes. One good evaluation must NOT clear (clear_evals=2).
    t2 = t + 1000
    h.observe(0.1)
    ring.sample_once(now=t2)
    h.observe(0.1)
    ring.sample_once(now=t2 + 1)
    first = eng.evaluate(now=t2 + 1)["objectives"]["ttft"]
    assert first["state"] == "page", "cleared after a single good eval"
    second = eng.evaluate(now=t2 + 2)["objectives"]["ttft"]
    assert second["state"] == "ok"
    clears = [e for e in rec.snapshot(type="slo_burn")
              if e["transition"] == "clear"]
    assert clears and clears[-1]["prev_state"] == "page"
    # Clears are transitions, not new alerts: counter unchanged.
    assert alerts.labels(objective="ttft", severity="page").value == 1
    # State gauge followed the machine back down.
    assert reg.get("slo_state").labels(objective="ttft").value == 0


def test_flapping_indicator_resets_clear_streak():
    reg, rec, h, ring, eng = _ttft_rig()
    t = 1000.0
    for i in range(12):
        h.observe(4.0)
        ring.sample_once(now=t + i)
        eng.evaluate(now=t + i)
    assert eng.state("ttft") == "page"
    # good eval, then bad again, then good: streak must restart, so the
    # second good eval alone cannot clear.
    t2 = t + 1000
    h.observe(0.1); ring.sample_once(now=t2)
    eng.evaluate(now=t2)
    h.observe(4.0); ring.sample_once(now=t2 + 1)
    eng.evaluate(now=t2 + 1)
    h.observe(0.1); ring.sample_once(now=t2 + 1000)
    assert eng.evaluate(now=t2 + 1000)["objectives"]["ttft"][
        "state"] == "page"


def test_insufficient_samples_never_alert():
    reg, rec, h, ring, eng = _ttft_rig()
    h.observe(99.0)  # horrendous, but a single sample
    ring.sample_once(now=1.0)
    v = eng.evaluate(now=1.0)["objectives"]["ttft"]
    assert v["state"] == "ok" and v["burn_fast"] == 0.0
    assert v["samples_fast"] < 2


def test_ratio_objective_error_budget():
    reg = MetricsRegistry()
    rec = FlightRecorder()
    bad = reg.counter("shed_total", "")
    good = reg.counter("admit_total", "")
    ring = TimeSeriesRing(reg, interval_s=1.0)
    eng = SLOEngine(
        ring,
        [Objective(name="errors", bad=("shed_total",),
                   good=("admit_total",), target=0.1)],
        registry=reg, recorder=rec,
        fast_window_s=10.0, slow_window_s=100.0,
    )
    good.inc(95); bad.inc(5)
    ring.sample_once(now=1.0)
    v = eng.evaluate(now=1.0)["objectives"]["errors"]
    assert v["state"] == "ok" and v["burn_fast"] == pytest.approx(0.5)
    # All-errors FAST window (the healthy sample ages out of the 10s
    # window): ratio 1.0 / budget 0.1 = burn 10 -> page.
    bad.inc(400)
    ring.sample_once(now=50.0)
    v = eng.evaluate(now=50.0)["objectives"]["errors"]
    assert v["state"] == "page", v
    assert v["value"] == pytest.approx(1.0)  # fast-window ratio


def test_ratio_objective_min_samples_guard():
    """One shed request against zero admissions (startup lull) is a
    ratio of 1.0 but not evidence — min_samples applies to the ratio
    form too, so it cannot instantly page."""
    reg = MetricsRegistry()
    bad = reg.counter("shed_total", "")
    reg.counter("admit_total", "")
    ring = TimeSeriesRing(reg, interval_s=1.0)
    eng = SLOEngine(
        ring,
        [Objective(name="errors", bad=("shed_total",),
                   good=("admit_total",), target=0.05)],
        fast_window_s=10.0, slow_window_s=100.0,
    )
    bad.inc()  # the only event anywhere
    ring.sample_once(now=1.0)
    v = eng.evaluate(now=1.0)["objectives"]["errors"]
    assert v["state"] == "ok" and v["burn_fast"] == 0.0, v


def test_baseline_relative_objective_step_time_vs_median():
    """The train_step_time shape: p95 judged against a FACTOR of the
    rolling-median gauge, so a regression pages while an absolutely-slow
    but stable workload stays quiet."""
    reg = MetricsRegistry()
    ring = TimeSeriesRing(reg, interval_s=1.0)
    val = reg.gauge("step_p95", "")
    med = reg.gauge("step_median", "")
    eng = SLOEngine(
        ring,
        [Objective(name="steps", series="step_p95",
                   baseline="step_median", op="<=", target=2.0,
                   budget=0.1)],
        fast_window_s=10.0, slow_window_s=100.0,
    )
    med.set(5.0)  # slow hardware, stable: 5s steps are its normal
    for i in range(5):
        val.set(6.0)  # well within 2x median
        ring.sample_once(now=float(i))
        assert eng.evaluate(now=float(i))["objectives"]["steps"][
            "state"] == "ok"
    for i in range(5, 24):
        val.set(14.0)  # > 2 x 5.0: a regression against its own regime
        ring.sample_once(now=float(i))
        st = eng.evaluate(now=float(i))["objectives"]["steps"]["state"]
    assert st == "page"  # fast window saturated with violations


def test_objective_warmup_grace_suppresses_cold_start_page():
    """A lifetime-ratio indicator (goodput fraction) is structurally
    terrible during the first compile; the default train_goodput
    objective carries a warmup grace so a cold start cannot page. After
    the grace, real violations fire normally."""
    t0 = 1000.0
    reg = MetricsRegistry()
    g = reg.gauge("training_goodput_fraction", "")
    ring = TimeSeriesRing(reg, interval_s=1.0, clock=lambda: t0)
    eng = SLOEngine(
        ring,
        [Objective(name="goodput", series="training_goodput_fraction",
                   op=">=", target=0.5, budget=0.1, warmup_s=50.0)],
        fast_window_s=10.0, slow_window_s=40.0,
    )
    for i in range(30):
        g.set(0.01)  # compile-dominated: fraction near zero
        ring.sample_once(now=t0 + i)
        v = eng.evaluate(now=t0 + i)["objectives"]["goodput"]
        assert v["state"] == "ok" and v.get("warming"), (i, v)
    # Grace over, still violating: now it is a real alert.
    st = "ok"
    for i in range(50, 70):
        g.set(0.01)
        ring.sample_once(now=t0 + i)
        v = eng.evaluate(now=t0 + i)["objectives"]["goodput"]
        assert "warming" not in v
        st = v["state"]
    assert st == "page"
    # The shipped default carries the grace (= one slow window).
    cfg = Config(vocab_size=64, hidden_size=32, num_layers=1,
                 num_heads=2, num_kv_heads=1, seq_length=16)
    objs = {o.name: o for o in default_train_objectives(cfg)}
    assert objs["train_goodput"].warmup_s == cfg.slo_slow_window_s


def test_default_objectives_and_slo_config_override(tmp_path):
    cfg = Config(vocab_size=64, hidden_size=32, num_layers=1,
                 num_heads=2, num_kv_heads=1, seq_length=16)
    serve = {o.name for o in default_serve_objectives(cfg)}
    train = {o.name for o in default_train_objectives(cfg)}
    assert serve == {"serve_ttft_p95", "serve_decode_p50",
                     "serve_error_rate"}
    assert train == {"train_goodput", "train_step_time"}
    override = tmp_path / "slo.json"
    override.write_text(json.dumps({"objectives": [
        {"name": "custom", "series": "serve_ttft_seconds:p95",
         "op": "<=", "target": 0.2, "budget": 0.05},
    ]}))
    objs = objectives_for("serve", cfg, str(override))
    assert [o.name for o in objs] == ["custom"]  # replaces, not extends
    assert objs[0].target == 0.2
    (tmp_path / "bad.json").write_text("{}")
    with pytest.raises(ValueError):
        load_slo_config(str(tmp_path / "bad.json"))
    with pytest.raises(ValueError):
        Objective.from_dict({"name": "x", "series": "s", "bogus": 1})
    with pytest.raises(ValueError):
        Objective(name="both", series="s", bad=("b",), good=("g",))


# ---------------------------------------------------------------------------
# end to end: injected decode stall -> page -> forensics -> clear
# ---------------------------------------------------------------------------
def test_e2e_decode_stall_pages_dumps_and_clears(tmp_path, capsys):
    """The acceptance contract: with telemetry on, an injected decode
    stall (faults.slow_tick) produces a fast-window slo_burn alert that
    appears in /slo, the flight dump, and `lumina top --once --json`,
    then clears after recovery."""
    from luminaai_tpu.cli import main as cli_main
    from luminaai_tpu.serving.server import ChatServer
    from luminaai_tpu.testing.faults import slow_tick

    reg, rec = MetricsRegistry(), FlightRecorder()
    eng = _Engine(slo_decode_p50_s=0.05)
    srv = ChatServer(eng, registry=reg, recorder=rec,
                     flight_dir=str(tmp_path), watchdog=None)
    try:
        assert srv.slo is not None and srv.history is not None
        with slow_tick(eng.stepper, delay_s=0.12, after=0):
            srv.batcher.submit([40], {"max_new_tokens": 6})
            srv.history.sample_once()
            srv.batcher.submit([50], {"max_new_tokens": 6})
            srv.history.sample_once()
        code, verdict = srv.handle("GET", "/slo", {}, None)
        assert code == 200
        v = verdict["objectives"]["serve_decode_p50"]
        assert v["state"] == "page", v
        assert verdict["alerting"] == ["serve_decode_p50"]
        # The alert is booked: flight events + counter.
        assert rec.snapshot(type="slo_burn")
        assert reg.get("slo_burn_alerts_total").labels(
            objective="serve_decode_p50", severity="page"
        ).value >= 1
        # Forensic dump carries history + verdicts; the operator view
        # reads it back and shows the page.
        srv.dump_flight_record("slo_stall")
        assert cli_main(["top", str(tmp_path), "--json"]) == 0
        pay = json.loads(capsys.readouterr().out)
        assert pay["slo"]["objectives"]["serve_decode_p50"][
            "state"] == "page"
        assert "decode p50 s" in pay["rows"]
        # And the flight dump replays through lumina events.
        assert cli_main([
            "events", "--type", "slo_burn", "--json", str(tmp_path),
        ]) == 0
        lines = [ln for ln in capsys.readouterr().out.splitlines() if ln]
        assert lines and all(
            json.loads(ln)["type"] == "slo_burn" for ln in lines
        )
        # Recovery: healthy traffic; future-stamped samples age the
        # violations out of both windows, and the alert CLEARS.
        srv.batcher.submit([60], {"max_new_tokens": 6})
        t2 = time.time() + 900
        srv.batcher.submit([70], {"max_new_tokens": 6})
        srv.history.sample_once(now=t2)
        srv.history.sample_once(now=t2 + 1)
        srv.history.sample_once(now=t2 + 2)
        code, verdict = srv.handle("GET", "/slo", {}, None)
        assert verdict["objectives"]["serve_decode_p50"]["state"] == "ok"
        clears = [e for e in rec.snapshot(type="slo_burn")
                  if e["transition"] == "clear"]
        assert clears, "recovery never booked a clear transition"
    finally:
        srv.drain(timeout_s=2)


# ---------------------------------------------------------------------------
# lumina top
# ---------------------------------------------------------------------------
_GOLDEN_HISTORY = {
    "v": 1, "ts": 1000.0, "created_ts": 990.0, "interval_s": 1.0,
    "samples": 8, "series_count": 4, "overflow_points": 0,
    "series": {
        "serve_tokens_out_total": [[992.0 + i, 8.0 * i] for i in range(8)],
        "serve_ttft_seconds:p95": [[992.0 + i, 0.2] for i in range(8)],
        "tenant_tokens_out_total{tenant=aaa111}": [[999.0, 64.0]],
        "tenant_tokens_out_total{tenant=bbb222}": [[999.0, 8.0]],
    },
}

_GOLDEN_SLO = {
    "v": 1, "ts": 1000.0, "program": "serve",
    "windows": {"fast_s": 60.0, "slow_s": 600.0,
                "fast_burn": 10.0, "slow_burn": 2.0},
    "evaluations": 8, "alerting": ["serve_ttft_p95"],
    "objectives": {
        "serve_ttft_p95": {
            "state": "page", "burn_fast": 10.0, "burn_slow": 4.0,
            "value": 0.2, "target": 0.1, "op": "<=", "baseline": None,
            "samples_fast": 8, "samples_slow": 8, "fires": 1,
            "ok": False,
        },
    },
}


def test_top_once_golden_output():
    """`lumina top --once` is a PURE function of the two payloads:
    the frame is pinned exactly, so a rendering regression is a diff,
    not a vibe."""
    from luminaai_tpu.monitoring.top import render_top

    out = render_top(_GOLDEN_HISTORY, _GOLDEN_SLO, source="golden")
    expected = (
        "lumina top — golden — samples=8 series=4 interval=1.0s\n"
        "\n"
        "serve tok/s  ▁▂▃▄▅▆▇█                                56"
        "  [0 .. 56]\n"
        "ttft p95 s   ▄▄▄▄▄▄▄▄                            0.2000"
        "  [0.2000 .. 0.2000]\n"
        "\n"
        "top tenants (tokens out):\n"
        "  aaa111                      64\n"
        "  bbb222                       8\n"
        "\n"
        "slo (serve; fast 60.0s/slow 600.0s):\n"
        "  objective             state      burn f/s     value    target\n"
        "!!serve_ttft_p95        page    10.00/4.00     0.2000  <=0.1000\n"
        "  ALERTING: serve_ttft_p95\n"
    )
    assert out == expected


def test_top_payload_tenant_topk_and_windows():
    from luminaai_tpu.monitoring.top import top_payload

    pay = top_payload(_GOLDEN_HISTORY, None, top_k=1)
    assert pay["tenants"] == [{"tenant": "aaa111", "tokens_out": 64}]
    # Rate rows divide deltas by the interval.
    assert pay["rows"]["serve tok/s"]["last"] == 56.0
    # Window filter drops old points.
    pay = top_payload(_GOLDEN_HISTORY, None, window_s=2.0)
    assert pay["rows"]["serve tok/s"]["points"] == 2


def test_sparkline_shapes():
    from luminaai_tpu.monitoring.top import sparkline

    assert sparkline([]) == ""
    assert sparkline([5.0, 5.0, 5.0]) == "▄▄▄"  # flat ≠ empty
    ramp = sparkline(list(range(8)))
    assert ramp[0] == "▁" and ramp[-1] == "█"
    assert len(sparkline(list(range(100)), width=10)) == 10


def test_cmd_top_live_ring_shows_attached_verdicts(capsys):
    """The no-argument live attach renders the SLO table from the
    engine advertised on the ring — read-only: the cached verdicts,
    never a fresh evaluation (sample counts/hysteresis untouched)."""
    from luminaai_tpu.cli import main as cli_main
    from luminaai_tpu.monitoring.slo import build_slo_stack
    from luminaai_tpu.monitoring.timeseries import set_history

    reg = MetricsRegistry()
    reg.gauge("training_goodput_fraction", "").set(0.9)
    cfg = Config(vocab_size=64, hidden_size=32, num_layers=1,
                 num_heads=2, num_kv_heads=1, seq_length=16)
    ring, engine = build_slo_stack(cfg, registry=reg, program="train")
    ring.sample_once(now=1000.0)
    samples_before = ring.stats()["samples"]
    evals_before = engine.verdicts()["evaluations"]
    prev = set_history(ring)
    try:
        assert cli_main(["top", "--json"]) == 0
    finally:
        set_history(prev)
    pay = json.loads(capsys.readouterr().out)
    assert pay["slo"]["objectives"], pay
    assert ring.stats()["samples"] == samples_before  # view didn't sample
    assert engine.verdicts()["evaluations"] == evals_before


def test_build_slo_stack_is_the_one_constructor():
    cfg = Config(vocab_size=64, hidden_size=32, num_layers=1,
                 num_heads=2, num_kv_heads=1, seq_length=16,
                 slo_sample_interval_s=1.5, slo_ring_points=33,
                 slo_max_series=7, slo_fast_window_s=11.0,
                 slo_slow_window_s=22.0)
    from luminaai_tpu.monitoring.slo import build_slo_stack

    ring, engine = build_slo_stack(cfg, registry=MetricsRegistry(),
                                   program="serve")
    assert (ring.interval_s, ring.capacity, ring.max_series) == (
        1.5, 33, 7)
    assert (engine.fast_window_s, engine.slow_window_s) == (11.0, 22.0)
    assert ring.slo is engine  # attach() advertised it for live top


def test_healthz_stale_after_rejects_nonpositive():
    from luminaai_tpu.serving.server import ChatServer

    with pytest.raises(ValueError):
        ChatServer(_Engine(), registry=MetricsRegistry(),
                   recorder=FlightRecorder(), watchdog=None, slo=False,
                   healthz_stale_after_s=0.0)


def test_history_route_survives_hostile_query_values():
    from luminaai_tpu.serving.server import ChatServer

    srv = ChatServer(_Engine(), registry=MetricsRegistry(),
                     recorder=FlightRecorder(), watchdog=None)
    try:
        srv.history.sample_once()
        for seconds, max_points in (
            (float("nan"), None), (None, float("nan")),
            (float("inf"), float("inf")), (-5.0, -1.0),
        ):
            code, doc = srv.history_route(seconds=seconds,
                                          max_points=max_points)
            assert code == 200 and "series" in doc, (seconds, max_points)
    finally:
        srv.drain(timeout_s=1)


def test_prefill_chunk_advance_counts_as_liveness():
    """A prefill-only window (huge prompt chunking, no active decode
    lanes) is real progress: the chunk advance stamps last_tick_ts so
    /healthz staleness cannot flag it as wedged."""
    from luminaai_tpu.serving.server import (
        ContinuousScheduler,
        _ContinuousRequest,
    )

    eng = _Engine()
    st = {"next": 0, "n_chunks": 3, "chunk": 4, "length": 12,
          "start_rows": 0}
    eng.stepper.advance_prefill = lambda s: (
        s.__setitem__("next", s["next"] + 1) or
        (None if s["next"] < s["n_chunks"] else
         {"token": 7, "prompt_tokens": 12, "is_stop": False})
    )
    sched = ContinuousScheduler(eng, decoder=eng.stepper,
                                registry=MetricsRegistry(),
                                recorder=FlightRecorder())
    req = _ContinuousRequest([40], 4, None, None, False)
    sched._track(req)
    sched._prefilling[0] = (req, st)
    assert sched.last_tick_ts is None
    assert sched._step(None, {}, collect=False)  # a chunk and no lane
    assert st["next"] == 1 and sched.last_tick_ts is not None


def test_cmd_top_exit_codes_and_dump_dir(tmp_path, capsys):
    from luminaai_tpu.cli import main as cli_main

    assert cli_main(["top", str(tmp_path / "nope.json"), "--json"]) == 2
    capsys.readouterr()
    # A directory resolves to its newest tshist dump (like lumina events).
    reg = MetricsRegistry()
    reg.gauge("serve_active_lanes", "").set(3)
    ring = TimeSeriesRing(reg, interval_s=1.0)
    ring.sample_once(now=1.0)
    ring.dump_to_dir(str(tmp_path), reason="t")
    assert cli_main(["top", str(tmp_path), "--once"]) == 0
    out = capsys.readouterr().out
    assert "active lanes" in out and "lumina top" in out


# ---------------------------------------------------------------------------
# satellites: build_info, /healthz staleness, events --by
# ---------------------------------------------------------------------------
def test_build_info_registered_and_exposed():
    reg = MetricsRegistry()
    labels = register_build_info(reg, config={"x": 1})
    register_build_info(reg, config={"x": 1})  # idempotent per identity
    assert set(labels) == {"git_commit", "jax", "jaxlib",
                           "config_hash", "schema"}
    assert labels["schema"] == "1"
    text = reg.render_prometheus()
    assert "build_info{" in text and "config_hash=" in text
    snap = reg.snapshot()
    assert any(v == 1 for v in snap["build_info"].values())
    # Distinct configs mint distinct identities (colocated processes).
    register_build_info(reg, config={"x": 2})
    assert len(reg.get("build_info").children()) == 2


def test_healthz_staleness_serve_and_train(tmp_path):
    from luminaai_tpu.serving.server import ChatServer

    reg = MetricsRegistry()
    eng = _Engine()
    srv = ChatServer(eng, registry=reg, recorder=FlightRecorder(),
                     watchdog=None, slo=False, healthz_stale_after_s=5.0)
    srv.batcher.submit([40], {"max_new_tokens": 3})
    code, out = srv.handle("GET", "/healthz", {}, None)
    assert code == 200 and out["status"] == "ok"
    assert out["last_decode_tick_age_seconds"] < 5.0
    # Wedged-but-alive: lanes active, last tick ancient -> degraded 200.
    srv.batcher.last_tick_ts = time.time() - 60
    srv.batcher._active_lanes = 2
    code, out = srv.handle("GET", "/healthz", {}, None)
    assert code == 200 and out["status"] == "degraded", out
    assert out["stale"] and out["last_decode_tick_age_seconds"] > 5.0
    # Idle is quiet, not stale: no active work -> back to ok.
    srv.batcher._active_lanes = 0
    code, out = srv.handle("GET", "/healthz", {}, None)
    assert code == 200 and out["status"] == "ok"
    # Colocated trainer liveness rides the registry gauge.
    reg.gauge("train_last_step_ts", "").set(time.time() - 120)
    code, out = srv.handle("GET", "/healthz", {}, None)
    assert out["last_step_age_seconds"] > 100
    assert out["status"] == "degraded"


def test_events_stats_by_tenant_and_request(tmp_path, capsys):
    evs = (
        [{"v": 1, "seq": i, "ts": 100.0 + i, "type": "request_shed",
          "tenant": "hot", "request_id": f"r{i}"} for i in range(6)]
        + [{"v": 1, "seq": 10, "ts": 103.0, "type": "request_completed",
            "tenant": "cold", "request_id": "r9"}]
        + [{"v": 1, "seq": 11, "ts": 104.0, "type": "drain_started"}]
    )
    stats = events_stats(evs, by="tenant")
    # Burners first; count ties break lexically ("-" pools field-less).
    assert list(stats["groups"]) == ["hot", "-", "cold"]
    assert stats["groups"]["hot"]["count"] == 6
    assert stats["groups"]["hot"]["by_type"] == {"request_shed": 6}
    assert events_stats(evs, by="request")["groups"]["r9"]["count"] == 1
    with pytest.raises(ValueError):
        events_stats(evs, by="color")
    # CLI: --by implies --stats; --json emits the grouped object.
    from luminaai_tpu.cli import main as cli_main

    dump = tmp_path / "flightrec-x.jsonl"
    dump.write_text("\n".join(json.dumps(e) for e in evs))
    assert cli_main(["events", "--stats", "--by", "tenant",
                     str(dump)]) == 0
    out = capsys.readouterr().out
    assert "hot" in out and "request_shed=6" in out
    assert cli_main(["events", "--by", "tenant", "--json",
                     str(dump)]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["by"] == "tenant" and doc["groups"]["hot"]["count"] == 6


# ---------------------------------------------------------------------------
# trainer wiring + sampler overhead A/B
# ---------------------------------------------------------------------------
def _tiny_cfg(out, **kw):
    base = dict(
        vocab_size=64, hidden_size=32, num_layers=1, num_heads=2,
        num_kv_heads=1, seq_length=16, batch_size=8,
        use_flash_attention=False, gradient_checkpointing=False,
        precision="fp32", max_steps=6, eval_every_n_batches=10**6,
        save_every_n_batches=10**6, health_check_interval=10,
        output_dir=str(out), learning_rate=1e-3,
    )
    base.update(kw)
    return Config(**base)


def _loader(n=50):
    from luminaai_tpu.data.dataset import PrefetchLoader

    def gen(epoch=0):
        rng = np.random.RandomState(epoch)
        for _ in range(n):
            yield {"input_ids": rng.randint(
                1, 60, size=(8, 16)).astype(np.int32)}

    return PrefetchLoader(gen, prefetch=2)


def test_trainer_summary_carries_slo_verdicts(tmp_path):
    from luminaai_tpu.training.trainer import Trainer

    reg = MetricsRegistry()
    t = Trainer(_tiny_cfg(tmp_path), train_data=_loader(),
                checkpoint_dir=str(tmp_path / "ckpt"),
                registry=reg, recorder=FlightRecorder())
    s = t.train()
    t.close()
    slo = s["slo"]
    assert set(slo["objectives"]) == {"train_goodput", "train_step_time"}
    for v in slo["objectives"].values():
        assert v["state"] in ("ok", "warn", "page")
    assert slo["ring"]["samples"] >= 1
    # The ring retained train series (counter deltas + goodput gauge).
    assert reg.get("slo_state") is not None
    assert "build_info" in reg.snapshot()


def test_train_liveness_gauge_blanks_during_slow_host_work(tmp_path):
    """A colocated server's /healthz must not flag a trainer mid-eval or
    mid-checkpoint as wedged: the train_last_step_ts gauge reads NaN
    while the goodput ledger's open cause is a legitimate slow-host
    window (the same set the watchdog pauses for)."""
    import math

    from luminaai_tpu.training.trainer import Trainer

    reg = MetricsRegistry()
    t = Trainer(_tiny_cfg(tmp_path), train_data=_loader(),
                checkpoint_dir=str(tmp_path / "ckpt"),
                registry=reg, recorder=FlightRecorder())
    gauge = reg.get("train_last_step_ts")
    assert math.isnan(gauge.value)  # no live loop yet
    t._training_active = True
    t._last_step_wall = 123.0
    t.goodput.switch("productive")
    assert gauge.value == 123.0
    with t.goodput.region("eval"):
        assert math.isnan(gauge.value)  # long eval != wedged
    with t.goodput.region("checkpoint"):
        assert math.isnan(gauge.value)
    assert gauge.value == 123.0  # back to judged
    t._training_active = False
    t.close()


def test_trainer_slo_off_switch(tmp_path):
    from luminaai_tpu.training.trainer import Trainer

    t = Trainer(_tiny_cfg(tmp_path, slo=False), train_data=_loader(),
                checkpoint_dir=str(tmp_path / "ckpt"),
                registry=MetricsRegistry(), recorder=FlightRecorder())
    s = t.train()
    t.close()
    assert t.slo is None and t.history is None
    assert "slo" not in s


# slow: a wall-clock A/B of two Trainer runs with a 50 ms sampler thread; other workers' load decides the ratio.
@pytest.mark.slow
def test_slo_sampler_overhead_ab(tmp_path):
    """Trainer-level A/B (the watchdog test's budget): SLO on — with an
    aggressive 50ms sampling cadence, far hotter than the 5s default —
    must stay within 1.5x of SLO fully off."""
    from luminaai_tpu.training.trainer import Trainer

    def run(tag, **kw):
        t = Trainer(
            _tiny_cfg(tmp_path / tag, max_steps=30, **kw),
            train_data=_loader(),
            checkpoint_dir=str(tmp_path / tag / "ckpt"),
            registry=MetricsRegistry(), recorder=FlightRecorder(),
        )
        t0 = time.perf_counter()
        t.train()
        dt = time.perf_counter() - t0
        t.close()
        return dt

    run("warm")  # compile-cache warmup for both arms
    dt_off = run("off", slo=False)
    dt_on = run("on", slo_sample_interval_s=0.05)
    assert dt_on < dt_off * 1.5 + 0.5, (dt_on, dt_off)
