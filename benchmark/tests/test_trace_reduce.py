"""trace_reduce pinned on one small trace recorded on a TPU v5e (PR 24,
chip call 1): three calls of a jitted two-matmul step, each under a
`bench:step` TraceAnnotation and followed by a 3 ms `bench:host_gap`."""

import os

import pytest

from benchmark import layer_readers, trace_reduce as tr

FIXTURE = os.path.join(os.path.dirname(__file__), "fixture_v5e.xplane.pb")


@pytest.fixture(scope="module")
def trace():
    return tr.load(FIXTURE)


def test_planes_lines_and_window(trace):
    assert list(trace.device_ops) == ["/device:TPU:0"]
    assert len(trace.device_ops["/device:TPU:0"]) == 9
    assert len(trace.device_modules["/device:TPU:0"]) == 3
    assert tr.window_of(trace) == (46441472.0, 55281454.0)
    assert {"bench:step", "bench:host_gap"} <= {
        e.name for e in trace.host_spans}


def test_busy_union_and_idle_share(trace):
    busy, window = tr.busy_and_window_s(trace)
    assert busy == pytest.approx(7.1648e-05, rel=1e-6)
    assert window == pytest.approx(0.008839982, rel=1e-9)
    idle = layer_readers.read(
        "device_idle_pct", {"from": "trace", "reduce": "idle_pct"},
        layer_readers.Context(trace=trace))
    assert idle == pytest.approx(100 * (1 - 7.1648e-05 / 0.008839982))


def test_a_kernels_summed_time_and_calls(trace):
    secs, calls = tr.selected_seconds(
        trace, {"name": r"^%convolution_reduce_fusion"})
    assert calls == 3
    assert secs == pytest.approx((23791 + 23787 + 24022) / 1e9)
    assert tr.selected_seconds(trace, {"name": "^%flash_fwd"}) == (0.0, 0)
    per_step = layer_readers.read(
        "x", {"from": "trace", "reduce": "sum_ms_per_step",
              "select": {"name": r"^%convolution"}},
        layer_readers.Context(trace=trace, steps=3))
    assert per_step == pytest.approx(0.0238667, rel=1e-4)


def test_module_durations_and_a_reader_that_finds_nothing(trace):
    durs = tr.selected_durations_ms(trace, {"name": r"^jit_step\("})
    assert len(durs) == 3 and 0.0238 < sorted(durs)[1] < 0.0241
    ctx = layer_readers.Context(trace=trace, steps=3)
    spec = {"from": "trace", "reduce": "median_ms", "line": "XLA Modules",
            "select": {"name": r"^jit_nothing\("}}
    assert layer_readers.read("decode_step_ms", spec, ctx) is None


def test_idle_gaps_are_named_by_the_host_span_that_covers_them(trace):
    gaps = tr.idle_gaps(trace, 5)
    assert gaps[0][0] == "bench:host_gap"
    assert gaps[0][1] == pytest.approx(0.0087683, rel=1e-4)
    busy, window = tr.busy_and_window_s(trace)
    assert sum(g[1] for g in gaps) == pytest.approx(window - busy, rel=1e-6)


def test_top_ops_and_exposed_time(trace):
    top = tr.top_device_ops(trace, 2)
    assert top[0][0].startswith("%convolution_reduce_fusion bf16[]")
    assert top[0][1] == pytest.approx(7.16e-05)
    exposed = tr.exposed_seconds(trace, {"name": r"^%copy-(start|done)"})
    assert exposed == pytest.approx(4.8e-08)


def test_interval_arithmetic():
    assert tr.union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
    assert tr.subtract([(0, 10)], [(2, 3), (5, 7)]) == [
        (0, 2), (3, 5), (7, 10)]
    assert tr.clip([(0, 10)], 4, 6) == [(4, 6)]
    assert tr.total([(0, 3), (5, 6)]) == 4


def test_roofline_reader_counts_calls_times_least_time(trace):
    from benchmark import flops

    flops.KERNEL_FNS["_two_matmuls"] = lambda body, shapes: {
        "ops": 2 * 2 * 1024**3, "bytes": 3 * 2 * 1024**2}
    try:
        spec = {"from": "trace", "reduce": "roofline_pct", "kernels": [
            {"select": {"name": "^%convolution"}, "fn": "_two_matmuls"}]}
        ctx = layer_readers.Context(
            trace=trace, body={}, shapes={},
            peak={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
        share = layer_readers.read("k_roofline", spec, ctx)
    finally:
        del flops.KERNEL_FNS["_two_matmuls"]
    least = 3 * (4 * 1024**3 / 197e12)
    assert share == pytest.approx(100 * least / 7.16e-05)
    assert 80 < share < 100  # two 1024^3 matmuls at 91% of the bf16 peak
    assert ctx.notes["k_roofline"]["_two_matmuls"]["bound"] == "compute"
