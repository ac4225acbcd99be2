"""`trace_in_run`, `--trace 2` and the per-layer metrics read from the
scheduler's phase ledger (PR 25)."""

import copy

import pytest

from benchmark import layer_readers, manifest

BENCH = manifest.load_benchmark()
SERVING = {"chat": "olmoe-serve-chat", "batch": "olmoe-serve-batch"}
PHASE_METRICS = {
    "host_put_ms_step": "serve_tick_put_seconds_total",
    "host_dispatch_ms_step": "serve_tick_dispatch_seconds_total",
    "device_wait_ms_step": "serve_tick_device_wait_seconds_total",
    "host_sched_ms_step": "serve_tick_sched_seconds_total",
}


@pytest.mark.parametrize("value,sound", [(True, True), (False, True),
                                         ("yes", False), (1, False)])
def test_trace_in_run_is_an_optional_boolean(value, sound):
    bench = copy.deepcopy(BENCH)
    bench["trace_in_run"] = value
    assert (manifest.check(bench) == []) is sound
    del bench["trace_in_run"]
    assert manifest.check(bench) == []


def test_an_unknown_top_level_key_is_still_refused():
    bench = copy.deepcopy(BENCH)
    bench["trace_after_run"] = True
    assert any("top-level" in f for f in manifest.check(bench))
    del bench["trace_after_run"], bench["command"]
    assert any("top-level" in f for f in manifest.check(bench))


@pytest.mark.parametrize("base", sorted(PHASE_METRICS))
@pytest.mark.parametrize("suffix", sorted(SERVING))
def test_phase_metrics_resolve_for_their_cells(base, suffix):
    cell = manifest.Cell(BENCH, SERVING[suffix])
    spec = cell.layer_metric_specs()[f"{base}.{suffix}"]
    assert spec["from"] == "registry" and spec["reduce"] == "ratio"
    assert spec["num"] == {"counter": PHASE_METRICS[base]}
    assert spec["den"] == {"counter": "serve_decode_steps_total"}
    other = manifest.Cell(BENCH, SERVING["batch" if suffix == "chat" else "chat"])
    assert f"{base}.{suffix}" not in other.layer_metric_specs()


def test_train_step_device_ms_selects_the_named_step():
    cell = manifest.Cell(BENCH, "mistral-7b-train-4k")
    spec = cell.layer_metric_specs()["train_step_device_ms"]
    assert spec["reduce"] == "median_ms" and spec["line"] == "XLA Modules"
    from benchmark import trace_reduce

    hit = trace_reduce.Event("jit_train_step(1234)", 0.0, 1.0)
    miss = trace_reduce.Event("jit_eval_step(99)", 0.0, 1.0)
    assert trace_reduce.matches(hit, spec["select"])
    assert not trace_reduce.matches(miss, spec["select"])


def test_the_registry_ratio_reads_the_ledgers_counters():
    """The program's own ledger on a registry of its own: one unlabelled
    counter a phase, so the reader (which sums a family's children) can
    tell the phases apart."""
    from luminaai_tpu.monitoring.goodput import (SERVE_TICK_PHASES,
                                                 ThreadPhaseLedger)
    from luminaai_tpu.monitoring.telemetry import MetricsRegistry

    t = [0.0]
    registry = MetricsRegistry()
    ledger = ThreadPhaseLedger(
        SERVE_TICK_PHASES, "serve_tick_{cause}_seconds_total",
        registry=registry, clock=lambda: t[0])
    steps = registry.counter("serve_decode_steps_total", "steps")
    before = layer_readers.registry_view(registry)
    ledger.start("sched")
    for _ in range(4):  # four decode steps of 3 + 2 + 20 + 1 ms
        for cause, ms in (("put", 3), ("dispatch", 2), ("device_wait", 20),
                          ("sched", 1)):
            ledger.switch(cause)
            t[0] += ms / 1e3
        steps.inc()
    ledger.switch("queue_idle")
    ledger.publish()  # the scheduler thread does, once a tick
    ctx = layer_readers.Context(registry_delta=layer_readers.delta(
        layer_readers.registry_view(registry), before))
    cell = manifest.Cell(BENCH, SERVING["chat"])
    got = {name: layer_readers.read(name, spec, ctx)
           for name, spec in cell.layer_metric_specs().items()
           if name.split(".")[0] in PHASE_METRICS}
    assert got == pytest.approx({
        "host_put_ms_step.chat": 3.0, "host_dispatch_ms_step.chat": 2.0,
        "device_wait_ms_step.chat": 20.0, "host_sched_ms_step.chat": 1.0})


def test_a_program_without_the_counters_leaves_the_metric_out():
    ctx = layer_readers.Context(registry_delta={
        "counter:serve_decode_steps_total": 10.0})
    cell = manifest.Cell(BENCH, SERVING["batch"])
    spec = cell.layer_metric_specs()["host_put_ms_step.batch"]
    assert layer_readers.read("host_put_ms_step.batch", spec, ctx) is None
