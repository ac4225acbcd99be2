"""trace_reduce pinned on one small trace recorded on a TPU v5e (PR 24,
chip call 1): three calls of a jitted two-matmul step, each under a
`bench:step` TraceAnnotation and followed by a 3 ms `bench:host_gap`."""

import os
import random
import time

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


# -- idle_gaps: the sweep against the scan it replaced (PR 27) ------------------
def idle_gaps_by_scan(trace, n=10, window=None, plane=None):
    """`trace_reduce.idle_gaps` as it stood up to PR 26, kept here as the
    oracle: for every gap, the first span in the order (not python,
    dur_ns, place in host_spans) that covers its middle. gaps x spans."""
    lo, hi = window or tr.window_of(trace)
    name = plane or sorted(trace.device_ops)[0]
    busy = tr.clip(tr.union((e.start_ns, e.end_ns)
                            for e in trace.device_ops[name]), lo, hi)
    gaps = tr.subtract([(lo, hi)], busy)
    spans = sorted(trace.host_spans,
                   key=lambda e: (not e.stats.get("python"), e.dur_ns))
    acc = {}
    for s, e in gaps:
        mid = (s + e) / 2
        cover = next((sp.name for sp in spans
                      if sp.start_ns <= mid < sp.end_ns), "(no host span)")
        acc[cover] = acc.get(cover, 0.0) + (e - s) / 1e9
    ranked = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
    return [[k[:160], v] for k, v in ranked]


def random_trace(seed, ops=2800, spans=500):
    """Device ops with gaps between them (a few long, as between ticks),
    and host spans as a serving trace has them: on each of several
    threads (python and the runtime's) parents that hold children that
    hold grandchildren, threads overlapping each other, durations drawn
    from a few values so that equal ones meet over one gap, stretches of
    time that nothing covers, and spans before and past the device's
    first and last operation."""
    rnd = random.Random(seed)
    t, device = 1_000_000.0, []
    for i in range(ops):
        dur = float(rnd.choice((200, 1_000, 5_000, 24_000)))
        device.append(tr.Event(f"%op.{i % 7} = f32[] fusion()", t, dur))
        t += dur + float(rnd.choice((0, 0, 3, 40, 700, 9_000, 400_000)))
    end = t
    host = []
    durs = (400.0, 400.0, 2_500.0, 30_000.0, 30_000.0, 450_000.0)
    for thread in range(5):
        python = thread < 2
        at = rnd.uniform(-2e6, 2e6)
        while at < end + 2e6 and len(host) < (thread + 1) * spans // 5:
            dur = rnd.choice(durs)
            host.append(tr.Event(f"t{thread}.outer{int(dur)}", at, dur,
                                 {"python": python}))
            inner, room = at, dur
            for depth in (1, 2):
                room = float(rnd.choice((room, room / 2, room / 7)))
                inner += float(rnd.choice((0.0, room / 5)))
                host.append(tr.Event(f"t{thread}.depth{depth}", inner, room,
                                     {"python": python}))
            at += dur + float(rnd.choice((0, 10, 60_000, 3_000_000)))
    rnd.shuffle(host)
    host.sort(key=lambda e: not e.stats["python"])  # as `load` orders them
    return tr.Trace({"/device:TPU:0": device}, {}, host)


@pytest.mark.parametrize("seed", range(8))
def test_idle_gaps_equal_the_scan_on_random_traces(seed):
    trace = random_trace(seed)
    assert len(tr.device_gaps(trace)) > 1900 and len(trace.host_spans) > 450
    got, want = tr.idle_gaps(trace, 1000), idle_gaps_by_scan(trace, 1000)
    assert got == want  # names, order and float seconds, to the last digit
    assert "(no host span)" in dict(got) and len(got) > 12
    python, runtime = (
        {e.name for e in trace.host_spans if e.stats["python"] is flag}
        for flag in (True, False))
    assert python & set(dict(got)) and runtime & set(dict(got))


@pytest.mark.parametrize("seed", range(3))
def test_idle_gaps_equal_the_scan_in_a_window_that_clips(seed):
    trace = random_trace(100 + seed)
    lo, hi = tr.window_of(trace)
    window = (lo + 0.31 * (hi - lo), lo + 0.64 * (hi - lo))
    got = tr.idle_gaps(trace, 1000, window)
    assert got == idle_gaps_by_scan(trace, 1000, window)
    whole = dict(tr.idle_gaps(trace, 1000))
    assert sum(v for _, v in got) < 0.5 * sum(whole.values())


def test_ties_and_edges_fall_as_the_scan_made_them_fall():
    device = [tr.Event("%a", 0.0, 10.0), tr.Event("%b", 110.0, 10.0)]
    host = [tr.Event("runtime.first", 0.0, 200.0, {"python": False}),
            tr.Event("second", 20.0, 80.0, {"python": True}),
            tr.Event("first", 30.0, 80.0, {"python": True}),
            tr.Event("longer", 40.0, 90.0, {"python": True})]
    trace = tr.Trace({"/device:TPU:0": device}, {}, host)
    assert tr.idle_gaps(trace) == idle_gaps_by_scan(trace) == [
        ["second", 1e-07]]
    host[1], host[2] = host[2], host[1]
    assert tr.idle_gaps(trace) == idle_gaps_by_scan(trace) == [
        ["first", 1e-07]]
    # Spans that have ended before the middle (60), or start after it,
    # do not cover it.
    host[0].dur_ns = host[1].dur_ns = host[2].dur_ns = 20.0
    host[3].start_ns = 70.0
    assert tr.idle_gaps(trace) == idle_gaps_by_scan(trace) == [
        ["(no host span)", 1e-07]]
    # A span covers its start and not its end.
    host.append(tr.Event("ends.at.the.middle", 10.0, 50.0, {"python": True}))
    assert tr.idle_gaps(trace) == idle_gaps_by_scan(trace) == [
        ["(no host span)", 1e-07]]
    host.append(tr.Event("starts.at.the.middle", 60.0, 5.0, {"python": False}))
    assert tr.idle_gaps(trace) == idle_gaps_by_scan(trace) == [
        ["starts.at.the.middle", 1e-07]]


def test_idle_gaps_equal_the_scan_on_the_recorded_trace(trace):
    assert tr.idle_gaps(trace, 1000) == idle_gaps_by_scan(trace, 1000)
    assert len(tr.device_gaps(trace)) == 7
    assert tr.idle_gaps(trace, 1000, (46441472.0, 50e6)) == idle_gaps_by_scan(
        trace, 1000, (46441472.0, 50e6))


def test_a_serving_trace_many_times_the_chips_reduces_in_seconds():
    """300,000 gaps x 20,000 spans (the chip's traces of PR 25 hold
    202,112 gaps): the scan needs minutes here and is not run."""
    device = [tr.Event("%op", 100.0 * i, 60.0) for i in range(300_001)]
    host = []
    for i in range(1_000):  # ticks back to back, each with 19 spans inside
        t0 = 30_000.0 * i
        host.append(tr.Event("sched.tick", t0, 30_000.0, {"python": True}))
        host.append(tr.Event("decode.fetch", t0 + 1_000.0, 28_000.0,
                             {"python": True}))
        host += [tr.Event(f"runtime.{k}", t0 + 1_500.0 * k, 900.0,
                          {"python": k % 2 == 0}) for k in range(18)]
    trace = tr.Trace({"/device:TPU:0": device}, {}, host)
    t0 = time.perf_counter()
    got = tr.idle_gaps(trace, 30)
    seconds = time.perf_counter() - t0
    assert len(tr.device_gaps(trace)) == 300_000 and len(host) == 20_000
    assert sum(v for _, v in got) == pytest.approx(300_000 * 40e-9)
    assert got[0][0] == "decode.fetch" and len(got) == 11
    assert seconds < 10.0, seconds
