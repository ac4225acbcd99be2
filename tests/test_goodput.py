"""Goodput ledger, hang watchdog and step-time sentinel contracts
(docs/observability.md "Goodput & sentinels").

The ledger's headline invariant — causes PARTITION wall time, sum ==
elapsed — is pinned with an injected clock (exact) and end to end on a
real trainer (tolerance covers float rounding only). Resume replay is
attributed across a preempt/resume cycle from the existing faults
harness. The watchdog/sentinel robust-threshold math is unit-tested
here; the detect→dump→(abort|continue) end-to-end lives in
tests/test_resilience.py with the other fault-injection contracts.
"""

import glob
import json
import os
import time

import numpy as np
import pytest

from luminaai_tpu.config import Config
from luminaai_tpu.data.dataset import PrefetchLoader
from luminaai_tpu.monitoring.events import FlightRecorder
from luminaai_tpu.monitoring.goodput import CAUSES, GoodputLedger
from luminaai_tpu.monitoring.telemetry import MetricsRegistry
from luminaai_tpu.monitoring.watchdog import (
    HangWatchdog,
    RobustStats,
    StepTimeSentinel,
    host_step_skew,
)


class FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t

    def tick(self, dt):
        self.t += dt


# ---------------------------------------------------------------------------
# ledger arithmetic (injected clock: exact)
# ---------------------------------------------------------------------------
def test_ledger_partitions_wall_time_exactly():
    clk = FakeClock()
    led = GoodputLedger(clock=clk)
    led.start("idle")
    clk.tick(1.0)
    led.switch("productive")
    clk.tick(5.0)
    with led.region("checkpoint"):
        clk.tick(2.0)
    clk.tick(3.0)  # back in productive (region restored the cause)
    led.stop()
    secs = led.seconds()
    assert secs["idle"] == 1.0
    assert secs["productive"] == 8.0
    assert secs["checkpoint"] == 2.0
    assert sum(secs.values()) == led.elapsed() == 11.0
    assert led.fraction() == pytest.approx(8.0 / 11.0)
    snap = led.snapshot()
    assert snap["available"] and snap["partition_error_s"] == 0.0
    # Every canonical cause is present even at zero — the CI contract.
    assert set(snap["seconds"]) == set(CAUSES)


def test_ledger_reattribute_moves_open_accrual_and_clamps():
    clk = FakeClock()
    led = GoodputLedger(clock=clk)
    led.start("idle")
    led.switch("data_wait")
    clk.tick(4.0)
    # Move 3s of the open data_wait accrual to resume_replay.
    assert led.reattribute("resume_replay", 3.0) == 3.0
    # Asking for more than remains is clamped, never negative.
    assert led.reattribute("hang", 10.0) == 1.0
    clk.tick(2.0)
    led.stop()
    secs = led.seconds()
    assert secs["resume_replay"] == 3.0
    assert secs["hang"] == 1.0
    assert secs["data_wait"] == 2.0
    assert sum(secs.values()) == led.elapsed() == 6.0


def test_ledger_counters_and_gauge_export():
    reg = MetricsRegistry()
    clk = FakeClock()
    led = GoodputLedger(registry=reg, clock=clk)
    led.start("idle")
    led.switch("productive")
    clk.tick(3.0)
    led.switch("idle")
    clk.tick(1.0)
    led.stop()
    snap = reg.snapshot()
    assert snap["training_time_seconds_total"]["cause=productive"] == 3.0
    assert snap["training_goodput_fraction"] == pytest.approx(0.75)


def test_ledger_disabled_is_inert():
    led = GoodputLedger(enabled=False)
    led.start()
    led.switch("productive")
    with led.region("checkpoint"):
        pass
    led.stop()
    assert led.snapshot()["available"] is False


def test_ledger_rejects_unknown_cause():
    led = GoodputLedger(clock=FakeClock())
    led.start()
    with pytest.raises(ValueError):
        led.switch("coffee_break")


class TickingClock(FakeClock):
    """Advances on EVERY read — the adversarial schedule for a snapshot
    that read the clock twice (totals vs elapsed) would see."""

    def __call__(self):
        self.t += 0.25
        return self.t


def test_snapshot_reads_one_instant_even_under_clock_skew():
    """partition_error_s must be 0 even when every clock read advances
    time: the snapshot takes totals AND elapsed from ONE reading, so a
    descheduled reader can never fake a partition error (CI asserts
    < 0.05 on loaded runners)."""
    clk = TickingClock()
    led = GoodputLedger(clock=clk)
    led.start("productive")
    for _ in range(3):
        led.switch("data_wait")
        led.switch("productive")
    snap = led.snapshot()
    assert snap["partition_error_s"] == 0.0, snap
    assert led.fraction() <= 1.0


def test_ledger_restart_books_stopped_gap_as_idle():
    clk = FakeClock()
    led = GoodputLedger(clock=clk)
    led.start("productive")
    clk.tick(2.0)
    led.stop()
    clk.tick(5.0)  # between stop and restart: still elapsed wall time
    led.start("productive")
    clk.tick(1.0)
    led.stop()
    secs = led.seconds()
    assert secs["productive"] == 3.0
    assert secs["idle"] == 5.0
    assert sum(secs.values()) == led.elapsed() == 8.0


# ---------------------------------------------------------------------------
# robust stats + sentinel
# ---------------------------------------------------------------------------
def test_robust_stats_median_mad():
    st = RobustStats(window=16)
    for x in [1.0, 1.0, 1.0, 9.0]:
        st.add(x)
    assert st.median() == 1.0
    assert st.mad() == 0.0  # median of |x - 1| = [0,0,0,8] -> 0
    st.add(3.0)
    assert st.median() == 1.0
    assert st.mad() == 0.0


def test_sentinel_flags_spike_and_exports_gauges():
    reg = MetricsRegistry()
    rec = FlightRecorder()
    s = StepTimeSentinel(
        registry=reg, recorder=rec, prefix="train_step_seconds",
        program="train", k=4.0, warmup=5,
    )
    for _ in range(10):
        assert not s.observe(0.01)
    assert s.observe(0.5, step=11)  # 50x the median: anomalous
    evs = rec.snapshot(type="step_anomaly")
    assert evs and evs[0]["program"] == "train"
    assert evs[0]["seconds"] == pytest.approx(0.5)
    assert evs[0]["step"] == 11
    snap = reg.snapshot()
    assert snap["train_step_seconds_median"] == pytest.approx(0.01, rel=0.2)
    assert snap["step_time_anomalies_total"]["program=train"] == 1
    # Warmup: a fresh (reset) window cannot flag anything.
    s.reset()
    assert not s.observe(10.0)


def test_sentinel_not_fooled_by_noisy_window():
    """The MAD significance guard: in a widely-spread window a value
    k x median is NOT automatically an anomaly."""
    rng = np.random.RandomState(0)
    s = StepTimeSentinel(k=2.0, warmup=5, guard_sigmas=6.0)
    flagged = 0
    for _ in range(40):
        flagged += bool(s.observe(float(rng.uniform(0.01, 0.05))))
    assert flagged == 0


def test_host_step_skew_single_host_is_zero():
    reg = MetricsRegistry()
    assert host_step_skew(reg) == 0.0
    assert reg.snapshot()["host_step_skew_seconds"] == 0.0


# ---------------------------------------------------------------------------
# watchdog threshold mechanics (no trainer; injected clock + exit fn)
# ---------------------------------------------------------------------------
def test_watchdog_threshold_is_robust_and_warmup_aware():
    wd = HangWatchdog(
        kind="training", recorder=FlightRecorder(), k=10.0, floor_s=0.5,
        warmup=3,
    )
    wd.arm()
    assert wd.threshold_s() is None  # no intervals yet: cannot fire
    for _ in range(3):
        wd._stats.add(0.1)
    thr = wd.threshold_s()
    assert thr == pytest.approx(max(0.5, 10.0 * 0.1))
    wd.close()


def test_watchdog_fires_once_dumps_and_counts(tmp_path):
    reg = MetricsRegistry()
    rec = FlightRecorder()
    rec.emit("marker", x=1)
    exits = []
    wd = HangWatchdog(
        kind="training", registry=reg, recorder=rec,
        dump_dir=str(tmp_path), k=2.0, floor_s=0.15, warmup=2,
        poll_s=0.03, abort=False, exit_fn=exits.append,
    )
    wd.arm()
    for _ in range(4):
        time.sleep(0.02)
        wd.beat()
    time.sleep(0.6)  # stall: > floor, no beat arrives
    assert wd.fires == 1, wd.stats()  # fired exactly once per stall
    wd.beat()  # a beat re-enables firing for the NEXT stall
    wd.close()
    assert reg.snapshot()["training_hangs_total"] == 1
    evs = rec.snapshot(type="hang_suspected")
    assert evs and evs[0]["kind"] == "training"
    assert evs[0]["stalled_s"] > evs[0]["threshold_s"]
    dumps = glob.glob(str(tmp_path / "flightrec-*hang*.jsonl"))
    stacks = glob.glob(str(tmp_path / "stacks-*hang.txt"))
    assert dumps and stacks
    assert "thread" in open(stacks[0]).read()
    assert not exits  # abort off: the process keeps running


def test_watchdog_pause_excludes_slow_host_work():
    rec = FlightRecorder()
    wd = HangWatchdog(
        kind="training", recorder=rec, k=2.0, floor_s=0.1, warmup=2,
        poll_s=0.02,
    )
    wd.arm()
    for _ in range(3):
        time.sleep(0.02)
        wd.beat()
    with wd.pause():
        time.sleep(0.4)  # a blocking save this long must NOT fire
    time.sleep(0.05)
    wd.beat()
    wd.close()
    assert wd.fires == 0, wd.stats()
    assert not rec.snapshot(type="hang_suspected")


# ---------------------------------------------------------------------------
# trainer end-to-end (the faults-harness cycle)
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


def _loader():
    def gen(epoch=0):
        rng = np.random.RandomState(epoch)
        for _ in range(50):
            yield {
                "input_ids": rng.randint(1, 60, size=(8, 16)).astype(
                    np.int32
                )
            }

    return PrefetchLoader(gen, prefetch=2)


def test_trainer_goodput_partitions_run_wall_clock(tmp_path):
    """Causes partition elapsed (tolerance = float rounding only),
    productive/compile/checkpoint all real, fraction in (0, 1], and the
    registry carries the counter + gauge series."""
    from luminaai_tpu.training.trainer import Trainer

    reg = MetricsRegistry()
    t = Trainer(
        _tiny_cfg(tmp_path), train_data=_loader(),
        checkpoint_dir=str(tmp_path / "ckpt"), registry=reg,
        recorder=FlightRecorder(),
    )
    s = t.train()
    t.close()
    gp = s["goodput"]
    assert gp["available"], gp
    assert 0.0 < gp["goodput_fraction"] <= 1.0, gp
    assert set(gp["seconds"]) == set(CAUSES), gp
    assert gp["partition_error_s"] < 0.01, gp
    assert gp["seconds"]["productive"] > 0
    assert gp["seconds"]["compile"] > 0
    assert gp["seconds"]["checkpoint"] > 0  # final forced save
    snap = reg.snapshot()
    assert snap["training_goodput_fraction"] > 0
    assert snap["training_time_seconds_total"]["cause=productive"] > 0
    # Sentinel gauges rode the same run (log cadence observations).
    assert snap["train_step_seconds_median"] > 0
    assert snap["host_step_skew_seconds"] == 0.0  # single host


@pytest.mark.faults
def test_resume_replay_attributed_across_preempt_resume(tmp_path):
    """The preempt/resume cycle from the faults harness: the interrupted
    run banks checkpoint time for its emergency save; the resumed run
    attributes restore to checkpoint and the loader fast-forward to
    resume_replay — and both ledgers still partition exactly."""
    from luminaai_tpu.testing.faults import preempt_at_step
    from luminaai_tpu.training.trainer import Trainer

    ckpt = str(tmp_path / "ckpt")
    t1 = Trainer(
        _tiny_cfg(tmp_path), train_data=_loader(), checkpoint_dir=ckpt,
        registry=MetricsRegistry(), recorder=FlightRecorder(),
    )
    with preempt_at_step(t1, 3):
        s1 = t1.train()
    t1.close()
    assert s1["preempted"]
    gp1 = s1["goodput"]
    assert gp1["seconds"]["checkpoint"] > 0, gp1  # blocking emergency save
    assert gp1["partition_error_s"] < 0.01, gp1

    t2 = Trainer(
        _tiny_cfg(tmp_path), train_data=_loader(), checkpoint_dir=ckpt,
        registry=MetricsRegistry(), recorder=FlightRecorder(),
    )
    assert t2.global_step == s1["final_step"]
    s2 = t2.train()
    t2.close()
    gp2 = s2["goodput"]
    assert s2["resumed_exact_data_state"]
    # Fast-forwarding 3 tiny in-memory batches takes tens of µs, which
    # the summary's 4-decimal rounding can flatten to 0.0 — assert on
    # the UNROUNDED ledger (plus any tail still banked in the loader,
    # in case the prefetch thread's last banking outran the final
    # per-batch drain).
    replay_s = t2.goodput.seconds()["resume_replay"]
    replay_s += t2.train_data.consume_resume_replay_seconds()
    assert replay_s > 0, (gp2, replay_s)
    assert gp2["seconds"]["checkpoint"] > 0, gp2  # the restore
    assert 0.0 < gp2["goodput_fraction"] <= 1.0, gp2
    assert gp2["partition_error_s"] < 0.01, gp2


def test_goodput_off_switch(tmp_path):
    from luminaai_tpu.training.trainer import Trainer

    reg = MetricsRegistry()
    t = Trainer(
        _tiny_cfg(tmp_path, goodput=False, watchdog=False,
                  step_anomaly=False, max_steps=2),
        train_data=_loader(), checkpoint_dir=str(tmp_path / "ckpt"),
        registry=reg, recorder=FlightRecorder(),
    )
    s = t.train()
    t.close()
    assert s["goodput"]["available"] is False
    assert t.watchdog is None
    # Sentinel fully off: no gauges registered, observe() inert.
    assert "train_step_seconds_median" not in reg.snapshot()
    assert not t._sentinel.observe(100.0)


# ---------------------------------------------------------------------------
# overhead (the sentinel A/B)
# ---------------------------------------------------------------------------
def test_ledger_and_beat_per_op_overhead_is_negligible():
    """Tier-1 microbench: the per-boundary cost is two clock reads + a
    lock — 10k switch/beat pairs well under 200ms keeps the sentinel
    layer invisible next to a multi-ms train step."""
    led = GoodputLedger()
    led.start("productive")
    wd = HangWatchdog(kind="training", recorder=FlightRecorder())
    wd.arm()
    t0 = time.perf_counter()
    for _ in range(10_000):
        with led.region("data_wait"):
            pass
        wd.beat()
    dt = time.perf_counter() - t0
    wd.close()
    assert dt < 1.0, f"sentinel layer per-op overhead too high: {dt:.3f}s"


# slow: a wall-clock A/B of two whole Trainer runs; five other workers' load decides the ratio, not the code.
@pytest.mark.slow
def test_watchdog_and_ledger_overhead_ab(tmp_path):
    """Trainer-level A/B: sentinels on (default) vs fully off. The on-
    run must stay within a generous budget of the off-run — the layer
    heartbeats at log cadence, so there is nothing per-step to pay."""
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

    run("warm")  # one throwaway run so compile caches are warm for both
    dt_off = run("off", goodput=False, watchdog=False, step_anomaly=False)
    dt_on = run("on")
    assert dt_on < dt_off * 1.5 + 0.5, (dt_on, dt_off)


def test_prefetch_loader_banks_replay_on_early_termination():
    """Replay wall clock is banked even when the epoch ends (or the
    consumer walks away) BEFORE the skip counter reaches zero — the
    truncated-source resume case must not leave resume_replay at 0."""
    def gen(epoch=0):
        for i in range(3):  # shorter than the saved cursor below
            yield {"input_ids": np.zeros((1, 4), np.int32) + i}

    loader = PrefetchLoader(gen, prefetch=2)
    loader.load_state_dict({"epoch": 0, "batch_index": 10})
    assert list(loader) == []  # every batch consumed by the fast-forward
    assert loader.consume_resume_replay_seconds() > 0.0
    # Drained: a second consume returns 0.
    assert loader.consume_resume_replay_seconds() == 0.0
