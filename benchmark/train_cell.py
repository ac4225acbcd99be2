"""A training cell: `Trainer` (what `lumina train` builds) over the
synthetic data path, under the adaptive orchestrator and the OOM ladder a
user gets by default, measured from outside.

The window opens and closes on the trainer's own log syncs (every
health_check_interval // 10 steps it converts the step's metrics to
floats, which waits for the device): tokens of the steps between two
syncs over the host seconds between them. The run is ended from the same
hook by an exception of the harness' own, so `Trainer.train()`'s final
blocking checkpoint (about 10 B a parameter to disk) is never written: it
would be paid by every run of every later check and measures nothing.

--trace 2 closes the window as --trace 0 does, takes its numbers, and only
then asks the trainer for a capture of one more log window
(`Trainer.request_profile`), ending the run at the sync after it.
"""

from __future__ import annotations

import math
import shutil
import tempfile
import time
from typing import Any, Dict, List, Optional

import numpy as np

from benchmark import (common, correct, flops, layer_readers, model_config,
                       program_adapter, reference)
from benchmark.common import say

SAMPLE_TOKENS = 512
TRACE_LOG_WINDOWS = 1  # log windows (of 10 steps by default) to trace


class _WindowClosed(Exception):
    """Raised from the step hook to leave Trainer.train() once measured."""


class Window:
    """Host-side accounting from the trainer's step hook (called after
    each log sync)."""

    def __init__(self, seconds: float, trace_after: bool,
                 compiles: common.CompileCounter, trainer):
        self.seconds = seconds
        self.trace_after = trace_after  # --trace 2
        self.trace_dir: Optional[str] = None
        self.compiles = compiles
        self.trainer = trainer
        self.t_open: Optional[float] = None
        self.step_open = 0
        self.t_close: Optional[float] = None
        self.step_close = 0
        self.losses: List[float] = []
        self.lowered_at_open = 0
        self.lowered_in_window = 0
        self.goodput_open: Dict[str, float] = {}
        self.goodput_close: Dict[str, float] = {}
        self.registry_open: Dict[str, float] = {}
        self.registry_delta: Dict[str, float] = {}
        self.tracing = False
        self.trace_steps = 0
        self.trace_until_step = 0
        self.profiler_first_start_s = 0.0

    def _goodput(self) -> Dict[str, float]:
        return dict(self.trainer.goodput.snapshot().get("seconds", {}))

    def _log_window_steps(self) -> int:
        return TRACE_LOG_WINDOWS * max(
            1, self.trainer.config.health_check_interval // 10)

    def on_sync(self, step: int, metrics: Dict[str, Any]) -> None:
        now = time.time()
        if self.t_open is None:
            # First sync after the compile step: the warm-up ends here.
            self.t_open, self.step_open = now, step
            self.lowered_at_open = self.compiles.lowered
            self.goodput_open = self._goodput()
            self.registry_open = layer_readers.registry_view(
                self.trainer.registry)
            return
        if self.t_close is not None:
            # --trace 2's tail: the sync that ends the captured steps.
            # Its losses are not the window's, so `correct` leaves them.
            if step >= self.trace_until_step:
                self.trainer.stop_profile()
                self.tracing = False
                raise _WindowClosed()
            return
        self.losses.append(float(metrics.get("loss", float("nan"))))
        if now - self.t_open >= self.seconds:
            self.t_close, self.step_close = now, step
            self.goodput_close = self._goodput()
            self.registry_delta = layer_readers.delta(
                layer_readers.registry_view(self.trainer.registry),
                self.registry_open)
            self.lowered_in_window = (
                self.compiles.lowered - self.lowered_at_open)
            if not self.trace_after:
                raise _WindowClosed()
            # The window's numbers are taken. Start and stop the profiler
            # once for nothing (its first start is the slow one), then
            # have the trainer capture the next log window of steps.
            self.profiler_first_start_s = common.warm_profiler(
                self.trainer.tracer)
            self.trace_dir = tempfile.mkdtemp(prefix="benchmark_trace_")
            self.trace_steps = self._log_window_steps()
            self.trace_until_step = step + self.trace_steps
            self.trainer.request_profile(self.trace_steps, self.trace_dir)
            self.tracing = True

    def discard(self) -> None:
        if self.tracing:
            self.tracing = False
            self.trainer.stop_profile()
        if self.trace_dir:
            shutil.rmtree(self.trace_dir, ignore_errors=True)


def _sample_ids(seed: int, rows: int, vocab: int) -> np.ndarray:
    return np.random.RandomState(seed % (2**32)).randint(
        3, vocab, size=(rows, SAMPLE_TOKENS)).astype(np.int32)


def check_against_reference(cell, cfg, trainer, seed: int) -> Dict[str, Any]:
    """The (sharded) program's logits and loss on a seeded sample against
    the plain reference's, on the trainer's own freshly made weights."""
    import jax.numpy as jnp

    mesh = trainer.mesh
    rows = int(mesh.shape.get("data", 1) * mesh.shape.get("fsdp", 1))
    ids = jnp.asarray(_sample_ids(seed, rows, cfg.vocab_size))
    params = trainer.state.params
    got = program_adapter.jit_on_mesh(
        lambda p, x: program_adapter.program_logits(trainer.model, p, x),
        cfg, mesh)(params, ids)
    kw = reference.from_config_file(cell.config)
    want = program_adapter.jit_on_mesh(
        lambda p, x: reference.forward(
            program_adapter.params_view(cfg, p), x, **kw),
        cfg, mesh)(params, ids)
    verdict = correct.compare_logits(got, want)
    last = correct.compare_logits(got[:, -1], want[:, -1])
    loss_p = float(reference.next_token_loss(got, ids))
    loss_r = float(reference.next_token_loss(want, ids))
    verdict.update(last_position_rel_rms=last["rel_rms"],
                   loss_program=loss_p, loss_reference=loss_r)
    # The last position is printed and not judged by itself: it is one
    # row a sequence (two rows on the four-chip mesh read 1.1-3.3% over
    # seeds on the chip) and the all-position comparison holds it too.
    verdict["ok"] = bool(
        verdict["ok"]
        and abs(loss_p - loss_r) <= correct.REL_RMS_TOL * max(1.0, loss_r)
    )
    return verdict


def run(cell, args, device: Dict[str, Any]) -> Dict[str, Any]:
    import jax

    from luminaai_tpu import cli
    from luminaai_tpu.monitoring.telemetry import MetricsRegistry
    from luminaai_tpu.monitoring.tracing import SpanTracer
    from luminaai_tpu.training.orchestrator import (
        AdaptiveTrainingOrchestrator,
    )
    from luminaai_tpu.training.trainer import Trainer

    mix = cell.traffic
    chips = device["count"]
    seq = int(mix["seq_length"])
    batch = int(mix["sequences_per_chip"]) * chips
    out_dir = tempfile.mkdtemp(prefix="benchmark_train_")
    compiles = common.CompileCounter()
    window = None
    try:
        cfg = model_config.build_config(
            cell.config, batch_size=batch, seq_length=seq,
            seed=common.fold_seed(args.seed), output_dir=out_dir,
            auto_resume=False, save_every_n_batches=10**9,
            eval_every_n_batches=10**9, enable_wandb=False,
        )
        asked = {"batch_size": cfg.batch_size, "seq_length": cfg.seq_length,
                 "num_layers": cfg.num_layers,
                 "gradient_accumulation_steps": cfg.gradient_accumulation_steps}
        data = cli._synthetic_batches(cfg, seed=args.seed % (2**31))
        # Off; --trace 2 switches it on for its capture.
        tracer = SpanTracer(enabled=False)
        t0 = time.time()
        trainer = Trainer(cfg, train_data=data, registry=MetricsRegistry(),
                          tracer=tracer)
        jax.block_until_ready(trainer.state.params)
        say("train", phase="state made on device", seconds=time.time() - t0,
            mesh={a: int(n) for a, n in trainer.mesh.shape.items()},
            params=flops.params_total(cell.config))
        t0 = time.time()
        verdict = check_against_reference(cell, cfg, trainer, args.seed)
        say("correct", seconds=time.time() - t0, **verdict)

        tokens_per_step = batch * seq
        window = Window(args.seconds, bool(args.trace), compiles, trainer)
        orch = AdaptiveTrainingOrchestrator(trainer)
        inner = orch.on_metrics

        def hook(step, metrics):
            inner(step, metrics)
            window.on_sync(step, metrics)

        orch.on_metrics = hook
        try:
            orch.run(oom_protect=True)
            raise RuntimeError("training ended before the window closed")
        except _WindowClosed:
            pass
        setup_s = window.t_open - common.PROCESS_T0
        steps = window.step_close - window.step_open
        wall = window.t_close - window.t_open
        tokens = steps * tokens_per_step
        tok_s_chip = tokens / wall / chips
        per_tok = flops.train_flops_per_token(cell.config, seq)
        mfu = 100.0 * tok_s_chip * per_tok / device["peak"]["bf16_flops_per_s"]
        ran = {"batch_size": trainer.config.batch_size,
               "seq_length": trainer.config.seq_length,
               "num_layers": trainer.config.num_layers,
               "gradient_accumulation_steps":
                   trainer.config.gradient_accumulation_steps}
        reg = window.registry_delta
        rebuilt = reg.get("counter:train_recompiles_total", 0.0)
        lowered_in_window = window.lowered_in_window
        finite = all(math.isfinite(x) for x in window.losses)
        data_wait = (window.goodput_close.get("data_wait", 0.0)
                     - window.goodput_open.get("data_wait", 0.0))
        peak_bytes = common.memory_peak_bytes()
        say("window", steps=steps, seconds=wall, tokens=tokens,
            step_s=wall / steps, tokens_per_s_chip=tok_s_chip, mfu_pct=mfu,
            flops_per_token=per_tok, losses=window.losses[:3] + window.losses[-2:],
            ran=ran, asked=asked, interventions=len(trainer._interventions),
            programs_built_in_window=lowered_in_window, step_rebuilds=rebuilt,
            compile_seconds_total=compiles.backend_s,
            goodput_seconds=window.goodput_close, setup_s=setup_s,
            memory_peak_bytes=peak_bytes,
            **({"profiler_first_start_s": window.profiler_first_start_s,
                "tail_s": time.time() - window.t_close}
               if args.trace else {}))
        ok = bool(verdict["ok"] and finite and ran == asked
                  and lowered_in_window == 0 and rebuilt == 0 and steps > 0)
        host = {
            "train_tok_s_chip": tok_s_chip, "setup_s": setup_s,
            "mfu_pct": mfu, "peak_hbm_gb": peak_bytes / 1e9,
            "data_wait_ms_step": 1e3 * data_wait / steps,
        }
        trainer.close()
        device_out = {k: device[k] for k in ("platform", "kind", "count")}
        device_out["memory_peak_bytes"] = peak_bytes
        out = {"correct": ok, "attempted": steps, "failed": 0,
               "metrics": common.metric_values(cell.end_to_end, host),
               "device": device_out}
        if not args.trace:
            return out
        shapes = {"seq": seq, "seqs_per_chip": int(mix["sequences_per_chip"]),
                  "chips": chips,
                  "mesh": cell.config.get("deployment", {}).get("mesh", {})}
        values, busy, win_s, breakdown, notes = layer_readers.reduce_traced_run(
            window.trace_dir, cell,
            dict(steps=window.trace_steps, registry_delta=reg, host=host,
                 body=cell.config, shapes=shapes, peak=device["peak"]),
            keep_as=getattr(args, "keep_trace", None))
        device_out.update(busy_s=busy, window_s=win_s)
        say("per_layer", traced_steps=window.trace_steps, notes=notes,
            values=values)
        # The end-to-end numbers were taken from the untraced window, so
        # both kinds stand side by side.
        out["metrics"].update(common.metric_values(cell.per_layer, values))
        out["breakdown"] = breakdown
        return out
    finally:
        if window is not None:
            window.discard()
        shutil.rmtree(out_dir, ignore_errors=True)
