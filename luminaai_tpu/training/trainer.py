"""Training loop orchestration.

Covers the reference EnhancedConversationTrainer (ref: Src/Main_Scripts/
training/trainer.py:985 — epoch/step loops, grad accumulation, periodic
eval/save, early stopping, LR adjustment hooks, throughput + memory
tracking, OOM fallback) and training_loop.py. TPU-shape differences:

  - The step itself (fwd+bwd+accum+clip+update) is one donated pjit call
    built by `parallel.train_step`; the Python loop only feeds batches and
    reads scalars. Grad accumulation lives inside the jit (lax.scan), not
    in this loop like the reference's microbatch Python loop.
  - Async checkpointing (orbax) instead of blocking torch.save.
  - Metrics arrive as device scalars; conversion to float happens once per
    log interval so the loop never forces a sync per step.
  - Adaptive interventions (LR override, emergency rollback) are applied
    between steps by rebuilding the optax transform — the orchestrator
    drives them via `adjust_learning_rate`/`rollback`.
"""

from __future__ import annotations

import contextlib
import logging
import os
import time
from typing import Any, Callable, Dict, Iterator, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding

from luminaai_tpu.config import Config
from luminaai_tpu.models.transformer import LuminaTransformer
from luminaai_tpu.monitoring.events import FlightRecorder, get_recorder
from luminaai_tpu.monitoring.goodput import GoodputLedger
from luminaai_tpu.monitoring.logger import TrainingHealthMonitor
from luminaai_tpu.monitoring.slo import SLOEngine, build_slo_stack
from luminaai_tpu.monitoring.telemetry import (
    MetricsRegistry,
    get_registry,
    register_build_info,
    weak_callback,
)
from luminaai_tpu.monitoring.timeseries import (
    TimeSeriesRing,
    get_history,
    set_history,
)
from luminaai_tpu.monitoring.tracing import SpanTracer
from luminaai_tpu.monitoring.watchdog import (
    HangWatchdog,
    ProcessPauses,
    StepTimeSentinel,
    host_step_skew,
)
from luminaai_tpu.parallel.mesh import build_mesh, describe_mesh, initialize_multihost
from luminaai_tpu.parallel.sharding import (
    batch_spec,
    init_opt_to_shardings,
    init_sharded_state,
)
from luminaai_tpu.parallel.train_step import make_eval_step, make_train_step
from luminaai_tpu.training.checkpoint import CheckpointManager
from luminaai_tpu.utils.retry import RetryPolicy, set_default_policy
from luminaai_tpu.training.optimizer import make_optimizer, make_schedule
from luminaai_tpu.training.precision import PrecisionManager

logger = logging.getLogger(__name__)

_NO_ANNOTATION = contextlib.nullcontext()


def put_process_local_batch(
    batch: Dict[str, np.ndarray],
    batch_sharding: NamedSharding,
    global_batch_size: int,
) -> Dict[str, jax.Array]:
    """Multi-host input assembly: each host contributes ONLY its local
    rows; make_array_from_process_local_data builds the global [batch,...]
    array across processes (no host materializes or transfers another
    host's shard — the JAX-native form of the ref's rank-keyed
    DistributedSampler, backend_fsdp.py:116). Module-level so the
    multihost test drives the exact production path without a Trainer.

    Accepts either per-host-shard rows (global/process_count) or, from a
    process-oblivious loader, the full global batch — then this host's
    rows are sliced out so the device layout matches the sharded-loader
    path exactly.
    """
    pc = jax.process_count()
    if global_batch_size % pc != 0:
        raise ValueError(
            f"global batch {global_batch_size} not divisible by "
            f"process_count {pc}: trailing rows would silently drop"
        )
    local = global_batch_size // pc
    out: Dict[str, jax.Array] = {}
    for k, v in batch.items():
        v = np.asarray(v)
        if v.shape[0] == global_batch_size and local != global_batch_size:
            pi = jax.process_index()
            v = v[pi * local:(pi + 1) * local]
        elif v.shape[0] != local:
            raise ValueError(
                f"batch '{k}' rows {v.shape[0]} is neither the global "
                f"batch ({global_batch_size}) nor the per-host shard "
                f"({local})"
            )
        out[k] = jax.make_array_from_process_local_data(
            batch_sharding,
            np.ascontiguousarray(v),
            global_shape=(v.shape[0] * pc,) + v.shape[1:],
        )
    return out


class Trainer:
    """End-to-end trainer: mesh + sharded state + loop + eval + checkpoints.

    `train_data` / `eval_data` are callables returning an iterator of batch
    dicts ({'input_ids': [B, S] int32, optional 'loss_mask'/'loss_weights'})
    so epochs can restart iteration (ref create_dataloader re-shuffles).
    """

    def __init__(
        self,
        config: Config,
        train_data: Callable[[], Iterator[Dict[str, np.ndarray]]],
        eval_data: Optional[Callable[[], Iterator[Dict[str, np.ndarray]]]] = None,
        model: Optional[LuminaTransformer] = None,
        checkpoint_dir: Optional[str] = None,
        total_steps: Optional[int] = None,
        steps_per_epoch: Optional[int] = None,
        registry: Optional[MetricsRegistry] = None,
        tracer: Optional[SpanTracer] = None,
        recorder: Optional[FlightRecorder] = None,
    ):
        self.config = config
        self.train_data = train_data
        self.eval_data = eval_data
        ckpt_dir = checkpoint_dir or f"{config.output_dir}/checkpoints"
        self.model = model or LuminaTransformer(config)
        self.precision = PrecisionManager(config)

        if total_steps is None:
            if config.max_steps:
                total_steps = config.max_steps
            elif steps_per_epoch:
                total_steps = steps_per_epoch * config.num_epochs
            else:
                total_steps = 10_000
        self.total_steps = total_steps
        self.steps_per_epoch = steps_per_epoch

        initialize_multihost(config)
        self.mesh = build_mesh(config)
        logger.info("trainer mesh: %s", describe_mesh(self.mesh))
        self.schedule = make_schedule(config, total_steps)
        self.tx = make_optimizer(config, total_steps, self.schedule)
        self.state, self.shardings = init_sharded_state(
            config, self.model, self.tx, self.mesh, jax.random.key(config.seed)
        )
        self.train_step = make_train_step(
            config, self.model, self.shardings, self.mesh, self.schedule,
            self.tx,
        )
        self.eval_step = make_eval_step(
            config, self.model, self.shardings, self.mesh
        )
        self._batch_sharding = NamedSharding(self.mesh, batch_spec())

        # Unified telemetry: the same process-wide registry the serving
        # stack exports through /metrics, so training step/throughput/
        # recompile counters and health gauges ride one exposition path.
        self.registry = registry or get_registry()
        # A tracer of its own when none is given: request_profile()
        # switches it on for the profiled steps (the shared NULL_TRACER
        # is never switched on); off, a span costs one attribute check.
        self.tracer = (
            tracer if tracer is not None else SpanTracer(enabled=False)
        )
        # A capture armed by request_profile() and not yet started, and
        # the open one: (stop step, trace_dir, attribute).
        self._profile_request: Optional[tuple] = None
        self._profile_open: Optional[tuple] = None
        if config.profile_start_step:
            self.request_profile(
                config.profile_num_steps,
                config.profile_dir or f"{config.output_dir}/profile",
                attribute=True, at_step=config.profile_start_step,
            )
        # Wide-event flight recorder (monitoring/events.py): step/router/
        # recompile/preemption events land in the process ring; the
        # emergency-save paths dump it next to the checkpoints.
        self.recorder = recorder if recorder is not None else get_recorder()
        # Runtime sentinel layer (docs/observability.md "Goodput &
        # sentinels"): the goodput ledger partitions the run's wall
        # clock per cause; the watchdog heartbeats at the log-window
        # sync and fires on robust-threshold stalls; the sentinel flags
        # step-time anomalies. All host-side clocks — no new syncs
        # enter the step path.
        self.goodput = GoodputLedger(
            registry=self.registry, enabled=config.goodput
        )
        self.goodput.start("idle")
        self.watchdog: Optional[HangWatchdog] = None
        if config.watchdog:
            self.watchdog = HangWatchdog(
                kind="training",
                registry=self.registry,
                recorder=self.recorder,
                dump_dir=str(ckpt_dir),
                k=config.watchdog_k,
                floor_s=config.watchdog_floor_s,
                warmup=config.watchdog_warmup,
                poll_s=config.watchdog_poll_s,
                abort=config.watchdog_abort,
                ledger=self.goodput,
            )
        self._sentinel = StepTimeSentinel(
            registry=self.registry,
            recorder=self.recorder,
            prefix="train_step_seconds",
            program="train",
            k=config.step_anomaly_k,
            enabled=config.step_anomaly,
        )
        # Build identity (fleet debugging): one gauge whose labels say
        # which commit/jax/config this process runs.
        register_build_info(self.registry, config=config)
        # SLO layer (docs/observability.md "SLOs & burn rate"): a
        # fixed-memory ring retains windowed registry history on a
        # background sampler thread, and the engine judges the default
        # train objectives (goodput floor, step-time-vs-rolling-median)
        # — or a --slo-config override — with multi-window burn-rate
        # rules. Host-side only; the sampler reads what producers
        # already wrote.
        self.history: Optional[TimeSeriesRing] = None
        self.slo: Optional[SLOEngine] = None
        if config.slo:
            self.history, self.slo = build_slo_stack(
                config, registry=self.registry, recorder=self.recorder,
                program="train",
            )
            # First ring installed wins the process default (`lumina
            # top` with no source reads it); close() restores.
            self._prev_history = (
                set_history(self.history) if get_history() is None else None
            )
            self._installed_history = get_history() is self.history
        else:
            self._installed_history = False
        # Liveness for /healthz staleness (a colocated server reads the
        # gauge): wall ts of the last completed optimizer step, NaN when
        # no train loop is live OR while the loop is legitimately inside
        # slow host work (eval / checkpoint — the same windows the
        # watchdog pauses for), so a long eval can't read as wedged.
        # Resume replay needs no entry here: it accrues inside data_wait
        # with the stamp reset at train() entry, so there is no stale
        # stamp to age. Plain host attribute writes — no new syncs.
        self._last_step_wall: Optional[float] = None
        self._training_active = False
        _SLOW_HOST_CAUSES = ("eval", "checkpoint")

        def _liveness_ts(t: "Trainer") -> float:
            if not t._training_active or not t._last_step_wall:
                return float("nan")
            if t.goodput.current_cause() in _SLOW_HOST_CAUSES:
                return float("nan")
            return t._last_step_wall

        self.registry.gauge(
            "train_last_step_ts",
            "Wall-clock timestamp of the last completed train step "
            "(NaN outside a live train loop or during eval/checkpoint "
            "windows)",
        ).set_function(weak_callback(self, _liveness_ts))
        self.checkpoints = CheckpointManager(
            config, ckpt_dir, registry=self.registry,
            recorder=self.recorder,
        )
        # The trainer owns the process-wide durable-I/O policy while it
        # lives: data readers without a Config in hand (JsonlIndex /
        # TokenCache opens) fall back to the default policy, so the
        # io_retries/io_timeout_s knobs must reach it or they silently
        # only govern checkpoint I/O. close() restores the previous
        # policy so a short-lived trainer (tests, tools) doesn't leak
        # its settings into the rest of the process.
        self._prev_io_policy = set_default_policy(
            RetryPolicy.from_config(config, registry=self.registry)
        )
        r = self.registry
        self._m_steps = r.counter(
            "train_steps_total", "Optimizer steps executed this process"
        )
        self._m_tokens = r.counter(
            "train_tokens_total", "Tokens consumed by executed train steps"
        )
        self._m_recompiles = r.counter(
            "train_recompiles_total",
            "Train-step rebuilds forcing an XLA recompile, by cause",
            labelnames=("reason",),
        )
        self._m_step_time = r.histogram(
            "train_step_seconds",
            "Per-step wall time, averaged over each log window",
            # Train steps span ~10ms (debug CPU) to minutes (flagship
            # first-compile windows); latency buckets would clip them.
            buckets=(0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0,
                     10.0, 30.0, 60.0, 120.0),
        )
        self._m_tps = r.gauge(
            "train_tokens_per_sec", "Throughput over the last log window"
        )
        self._m_preemptions = r.counter(
            "preemptions_total",
            "Stop requests (SIGTERM/SIGINT preemption) honored at a step "
            "boundary with a blocking emergency save",
        )
        self.monitor = TrainingHealthMonitor(
            log_dir=f"{config.output_dir}/logs",
            loss_spike_threshold=config.loss_spike_threshold,
            grad_norm_threshold=config.grad_norm_threshold,
            health_check_interval=config.health_check_interval,
            registry=self.registry,
            recorder=self.recorder,
            wandb_config={
                "enable": config.enable_wandb,
                "project": config.wandb_project,
                "entity": config.wandb_entity,
                "run_name": config.experiment_name,
                "run_config": config.to_dict(),
            },
        )

        self.global_step = 0
        self._export_held_combine()
        self._last_backup_time = time.time()
        # Chinchilla-mode convergence stop (ref chinchilla_scaler's
        # ConvergenceDetector): optional early end when eval loss flattens.
        self._convergence = None
        if config.use_chinchilla_scaling:
            from luminaai_tpu.training.scaler import ConvergenceDetector

            self._convergence = ConvergenceDetector(
                patience=config.convergence_patience
            )
        self.best_eval_loss = float("inf")
        self._epochs_without_improvement = 0
        self._consecutive_nonfinite = 0
        self._first_nonfinite_step: Optional[int] = None
        self._lr_override: Optional[float] = None
        self._active_schedule = self.schedule  # reflects any LR override
        # Checkpoints older than this are shape-incompatible (expert
        # evolution changed the param tree) and must never be restored.
        self._min_restorable_step = 0
        self._interventions: list = []
        # Exact-resume data cursor: counted HERE (per trained batch), not
        # in the loader — prefetch runs ahead of training, so only the
        # consumer knows which batches actually entered a step.
        self._data_epoch = 0
        self._batch_in_epoch = 0
        self._resumed_exact_data_state = False
        # Preemption: request_stop() arms a stop at the next step
        # boundary; the loop then runs a BLOCKING emergency save and
        # returns with summary["preempted"]=True (docs/resilience.md).
        self._stop_requested: Optional[str] = None
        self._preempted = False
        # Orchestrator hook: called with (step, scalar_metrics) at log
        # cadence; may call adjust_learning_rate/rollback/evolve_experts.
        self.step_callback: Optional[Callable[[int, Dict[str, float]], None]] = None

        if config.auto_resume:
            self.maybe_resume()
        # The process-wide collector hook and heartbeat (process_gc_* /
        # process_pause_* / process_wall_seconds_total; one a process,
        # shared with a scheduler's): the goodput ledger's kind of
        # wall-clock counter, on its switch. Nothing on the step path.
        # Last: after every thread this constructor starts (the history
        # sampler, a resume's readers), as the scheduler starts it after
        # its worker.
        self._pauses: Optional[ProcessPauses] = None
        if config.goodput:
            self._pauses = ProcessPauses.start(self.registry, self.tracer)

    # -- checkpoint/resume ------------------------------------------------
    def maybe_resume(self) -> bool:
        step = self.checkpoints.get_resume_step()
        if step is None:
            return False
        # Architecture guard BEFORE restoring: a mismatched expert count
        # (the run evolved experts after this config was written) can
        # restore without raising — orbax fills the target tree it is
        # given — so the actionable error must come from the checkpoint's
        # own metadata, never from hoping the restore fails.
        saved_e = None
        try:
            saved_cfg = (self.checkpoints.load_metadata(step) or {}).get(
                "config", {}
            )
            # Only an MoE tree bakes the expert count into param shapes;
            # a dense checkpoint's num_experts field is inert config.
            if saved_cfg.get("use_moe"):
                saved_e = saved_cfg.get("num_experts")
        except Exception:
            pass  # unreadable metadata: the corrupt-restore path decides
        if saved_e is not None and saved_e != self.config.num_experts:
            raise ValueError(
                f"checkpoint at step {step} was saved with num_experts="
                f"{saved_e} (architecture evolved mid-run) but config has "
                f"{self.config.num_experts}; set num_experts={saved_e} to "
                "resume"
            )
        used = step
        try:
            with self.goodput.region("checkpoint"):
                self.state = self.checkpoints.restore(self.state, step)
        except Exception as e:
            # Architecture matches but the restore failed: the latest
            # checkpoint is corrupt/partial (kill mid-commit, disk-full).
            # Count it and walk back to the newest INTACT older step
            # instead of refusing to resume (docs/resilience.md).
            self.checkpoints._m_fallbacks.inc()
            older = [
                s for s in self.checkpoints.all_steps()
                if s < step and s >= self._min_restorable_step
            ]
            if not older:
                raise
            logger.warning(
                "latest checkpoint (step %d) failed to restore (%s: %s); "
                "falling back to an older intact one",
                step, type(e).__name__, str(e)[:200],
            )
            with self.goodput.region("checkpoint"):
                self.state, used, _ = self.checkpoints.restore_with_fallback(
                    self.state, step=max(older),
                    min_step=self._min_restorable_step,
                )
        self.global_step = int(self.state.step)
        self._load_data_state(used)
        logger.info(
            "resumed from checkpoint at step %d (exact data state: %s)",
            self.global_step, self._resumed_exact_data_state,
        )
        return True

    def _data_state(self) -> Optional[Dict[str, Any]]:
        """The loader's exact-resume cursor, with epoch/batch_index taken
        from THIS loop's consumption counters (the loader prefetches
        ahead; the trainer knows what was trained). None when the data
        callable has no checkpointable state."""
        sd = getattr(self.train_data, "state_dict", None)
        if not callable(sd):
            return None
        try:
            state = dict(sd())
        except Exception as e:  # never let data state cost the checkpoint
            logger.warning("data state_dict failed: %s", e)
            return None
        state["epoch"] = self._data_epoch
        state["batch_index"] = self._batch_in_epoch
        return state

    def _load_data_state(self, step: int) -> None:
        """Fast-forward the data loader to the cursor saved with `step`,
        so the resumed batch stream continues bitwise-identically (no
        batch replayed or dropped). Degrades to a logged warning when the
        checkpoint predates data-state metadata or the loader has no
        load_state_dict."""
        self._resumed_exact_data_state = False
        try:
            meta = self.checkpoints.load_metadata(step) or {}
        except Exception:
            return
        ds_state = meta.get("data_state")
        if not ds_state:
            logger.warning(
                "checkpoint %d carries no data state; resumed batches may "
                "replay or skip data", step,
            )
            return
        ld = getattr(self.train_data, "load_state_dict", None)
        if not callable(ld):
            logger.warning(
                "data loader has no load_state_dict; resumed batches may "
                "replay or skip data"
            )
            return
        try:
            ld(dict(ds_state))
        except Exception as e:
            logger.warning("data state restore failed: %s", e)
            return
        self._data_epoch = int(ds_state.get("epoch", 0))
        self._batch_in_epoch = int(ds_state.get("batch_index", 0))
        self._resumed_exact_data_state = True
        logger.info(
            "data loader fast-forwarded to epoch %d batch %d",
            self._data_epoch, self._batch_in_epoch,
        )

    def save_checkpoint(self, metrics=None, force: bool = False) -> None:
        with self.tracer.span("checkpoint_save", step=self.global_step), \
                self.goodput.region("checkpoint"), self._wd_pause():
            self.checkpoints.save(
                self.state, self.global_step, metrics, force=force,
                data_state=self._data_state(),
            )

    def _wd_pause(self):
        """Watchdog pause across legitimately-slow host work (eval,
        blocking saves); no-op when the watchdog is off."""
        if self.watchdog is None:
            return contextlib.nullcontext()
        return self.watchdog.pause()

    def request_stop(self, reason: str = "preemption") -> None:
        """Arm a graceful stop at the NEXT step boundary (SIGTERM/SIGINT
        preemption path). Signal-handler-safe: only sets a flag; the
        loop does the blocking emergency save from its own thread."""
        self._stop_requested = reason or "preemption"

    def _count_recompile(self, reason: str) -> None:
        """Every train-step rebuild retraces + recompiles; the counter
        makes the recompile *rate* a first-class exported signal (pjit
        TPU stacks treat compile count as a health metric — a hot
        intervention loop shows up here before it shows up as lost
        throughput)."""
        self._m_recompiles.labels(reason=reason or "config_change").inc()
        self.recorder.emit(
            "recompile", step=self.global_step,
            reason=reason or "config_change",
        )
        self._export_held_combine()
        # A rebuilt step is a NEW timing regime: the sentinel's rolling
        # stats would flag the first post-recompile window, and the
        # watchdog would misprice the recompile stall as a hang.
        self._sentinel.reset()
        if self.watchdog is not None:
            self.watchdog.skip_next()

    # -- adaptive hooks (called by the orchestrator) ----------------------
    def adjust_learning_rate(self, new_lr: float, reason: str = "") -> None:
        """Override the schedule with a constant LR by rebuilding an optax
        state-compatible transform (ref trainer.py:1144). Adam moments
        survive: only the scale-by-schedule factor changes."""
        logger.warning("LR override -> %.3g (%s)", new_lr, reason)
        self._lr_override = new_lr
        cfg = self.config
        sched = lambda step: jnp.asarray(new_lr, jnp.float32)  # noqa: E731
        self._active_schedule = sched
        self.tx = make_optimizer(cfg, self.total_steps, sched)
        self.train_step = make_train_step(
            cfg, self.model, self.shardings, self.mesh, sched, self.tx
        )
        self._count_recompile("lr_override")
        self._interventions.append(
            {"step": self.global_step, "kind": "lr_override", "lr": new_lr,
             "reason": reason}
        )

    def evolve_experts(
        self,
        action: str,
        expert_idx: Optional[int] = None,
        reason: str = "",
    ) -> bool:
        """Add or prune an MoE expert mid-run (ref trainer.py:1270,1378).

        Param surgery via training.evolution; optimizer moments reset (the
        expert axis changed shape, so stale moments would be misaligned);
        train/eval steps recompile against the new architecture.
        """
        from luminaai_tpu.parallel.sharding import state_shardings
        from luminaai_tpu.training.evolution import (
            evolution_feasible,
            grow_expert,
            prune_expert,
        )

        cfg = self.config
        delta = 1 if action == "add_expert" else -1
        new_E = cfg.num_experts + delta
        ok, why = evolution_feasible(cfg, new_E)
        if not ok:
            logger.warning("expert evolution skipped: %s", why)
            return False

        if action == "add_expert":
            new_params = grow_expert(
                self.state.params, jax.random.key(cfg.seed + self.global_step)
            )
        else:
            if expert_idx is None:
                raise ValueError("prune requires expert_idx")
            new_params = prune_expert(self.state.params, expert_idx)

        cfg.num_experts = new_E
        self.model = LuminaTransformer(cfg)
        # Keep any active LR override in force across the rebuild.
        sched = self._active_schedule
        self.tx = make_optimizer(cfg, self.total_steps, sched)
        self.shardings = state_shardings(cfg, self.model, self.tx, self.mesh)
        new_params = jax.device_put(new_params, self.shardings.params)
        # Routes around mixed-memory-kind jit outputs when the optimizer
        # state is host-offloaded (sharding.py init_opt_to_shardings).
        opt_state = init_opt_to_shardings(
            self.tx, new_params, self.shardings.opt_state
        )
        # tx.init resets optax's internal counts to 0; restore them to the
        # true step so the LR schedule does NOT silently replay warmup.
        step_now = int(self.state.step)

        def _restore_counts(path, leaf):
            last = path[-1]
            if (
                isinstance(last, jax.tree_util.GetAttrKey)
                and last.name == "count"
            ):
                # Fresh buffer per leaf: sharing one array across leaves
                # breaks the donated train step (same buffer donated twice).
                return jnp.array(step_now, leaf.dtype)
            return leaf

        opt_state = jax.tree_util.tree_map_with_path(_restore_counts, opt_state)
        self.state = self.state.replace(params=new_params, opt_state=opt_state)
        self.train_step = make_train_step(
            cfg, self.model, self.shardings, self.mesh, sched, self.tx
        )
        self.eval_step = make_eval_step(
            cfg, self.model, self.shardings, self.mesh
        )
        self._count_recompile("expert_evolution")
        logger.warning(
            "%s -> %d experts (%s); optimizer moments reset", action, new_E, reason
        )
        self._interventions.append(
            {"step": self.global_step, "kind": action, "num_experts": new_E,
             "reason": reason}
        )
        # Older checkpoints are now shape-incompatible: fence them off and
        # immediately bank a restorable post-surgery checkpoint.
        self._min_restorable_step = self.global_step
        self.save_checkpoint(force=True)
        return True

    def adjust_microbatch(self, factor: int = 2, reason: str = "") -> bool:
        """Split the global batch into more in-jit microbatches (OOM relief).

        The reference shrinks the dataloader batch and raises grad accum
        (ref trainer.py:1626); here the global batch shape is part of the
        jitted step, so the cheap equivalent is raising
        gradient_accumulation_steps — the lax.scan inside the step slices
        the same [B, S] batch into smaller microbatches, cutting peak
        activation memory ~1/factor with identical math and no data-pipeline
        change. Returns False when the batch can't split further.
        """
        cfg = self.config
        if cfg.pipeline_parallel_size > 1:
            # Under pp the memory knob is the pipeline microbatch count,
            # not grad accum (which the GPipe step doesn't read and
            # validate() rejects).
            old = cfg.pipeline_microbatches or cfg.pipeline_parallel_size
            new_micro = old * factor
            if new_micro > cfg.batch_size or cfg.batch_size % new_micro != 0:
                logger.warning(
                    "cannot raise pipeline microbatches to %d (batch %d)",
                    new_micro, cfg.batch_size,
                )
                return False
            cfg.pipeline_microbatches = new_micro
            self._rebuild_steps("microbatch_split")
            logger.warning(
                "pipeline microbatch split: %d -> %d (%s)", old, new_micro,
                reason,
            )
            self._interventions.append(
                {"step": self.global_step, "kind": "microbatch_split",
                 "from": old, "to": new_micro, "reason": reason}
            )
            return True
        new_accum = cfg.gradient_accumulation_steps * factor
        if new_accum > cfg.batch_size or cfg.batch_size % new_accum != 0:
            logger.warning(
                "cannot raise grad accum to %d (batch %d)", new_accum,
                cfg.batch_size,
            )
            return False
        old = cfg.gradient_accumulation_steps
        cfg.gradient_accumulation_steps = new_accum
        self._rebuild_steps("microbatch_split")
        logger.warning(
            "microbatch split: accum %d -> %d (%s)", old, new_accum, reason
        )
        self._interventions.append(
            {"step": self.global_step, "kind": "microbatch_split",
             "from": old, "to": new_accum, "reason": reason}
        )
        return True

    def adjust_batch_size(self, new_batch_size: int, reason: str = "") -> bool:
        """Change the global (effective) batch size mid-run (ref
        trainer.py:1626 adjust_batch_size). Unlike the reference — where the
        dataloader batch is the microbatch — our [B, S] batch IS the
        optimizer step and grad accum only slices it, so the effective batch
        equals batch_size. Accum therefore rescales *proportionally* to keep
        the in-jit microbatch size (the memory knob) constant: growing the
        batch never inflates activation memory, shrinking it never regresses
        an OOM backoff. Steps recompile; the data callable is re-invoked at
        each epoch boundary and must honor the updated config.batch_size
        (the repo's dataset loaders do)."""
        cfg = self.config
        if new_batch_size == cfg.batch_size:
            return True
        batch_ways = (
            self.mesh.shape["data"] * self.mesh.shape["fsdp"]
        )
        if new_batch_size % batch_ways != 0:
            logger.warning(
                "batch size %d not divisible by the %d-way batch sharding "
                "(data×fsdp); refusing", new_batch_size, batch_ways,
            )
            return False
        old_bs, old_accum = cfg.batch_size, cfg.gradient_accumulation_steps
        if cfg.pipeline_parallel_size > 1:
            # Keep the pipeline microbatch size (the memory knob under pp)
            # constant, mirroring the accum rescale below.
            old_micro = cfg.pipeline_microbatches or cfg.pipeline_parallel_size
            mb_rows = max(1, old_bs // old_micro)
            new_micro = max(1, new_batch_size // mb_rows)
            while new_batch_size % new_micro != 0 and new_micro > 1:
                new_micro -= 1
            cfg.pipeline_microbatches = new_micro
            new_accum = old_accum
        else:
            micro = max(1, old_bs // old_accum)
            new_accum = max(1, new_batch_size // micro)
            while new_batch_size % new_accum != 0 and new_accum > 1:
                new_accum -= 1
        cfg.batch_size = new_batch_size
        cfg.gradient_accumulation_steps = new_accum
        self._rebuild_steps("batch_size")
        self._batch_sharding = NamedSharding(self.mesh, batch_spec())
        logger.warning(
            "batch size %d -> %d (accum %d -> %d) (%s)",
            old_bs, new_batch_size, old_accum, new_accum, reason,
        )
        self._interventions.append(
            {"step": self.global_step, "kind": "batch_size",
             "from": old_bs, "to": new_batch_size, "accum": new_accum,
             "reason": reason}
        )
        return True

    def adjust_capacity_factor(self, new_factor: float, reason: str = "") -> None:
        """Adjust MoE capacity factor during training (ref trainer.py:1450).
        Capacity is a static shape inside the jit, so the step recompiles;
        params are untouched (expert buffers are activations)."""
        cfg = self.config
        if not cfg.use_moe:
            logger.warning("cannot adjust capacity factor: MoE not enabled")
            return
        old = cfg.capacity_factor
        cfg.capacity_factor = float(new_factor)
        self._rebuild_steps("capacity_factor")
        logger.warning(
            "capacity factor %.2f -> %.2f (%s)", old, new_factor, reason
        )
        self._interventions.append(
            {"step": self.global_step, "kind": "capacity_factor",
             "from": old, "to": new_factor, "reason": reason}
        )

    def adjust_routing_temperature(self, new_temp: float, reason: str = "") -> None:
        """Adjust MoE routing temperature during training (ref
        trainer.py:1471). Higher = more uniform routing."""
        cfg = self.config
        if not cfg.use_moe:
            logger.warning("cannot adjust routing temperature: MoE not enabled")
            return
        old = cfg.routing_temperature
        cfg.routing_temperature = float(new_temp)
        self._rebuild_steps("routing_temperature")
        logger.warning(
            "routing temperature %.2f -> %.2f (%s)", old, new_temp, reason
        )
        self._interventions.append(
            {"step": self.global_step, "kind": "routing_temperature",
             "from": old, "to": new_temp, "reason": reason}
        )

    def mod_statistics(self) -> Dict[str, Any]:
        """MoD routing efficiency snapshot (ref trainer.py:1583
        get_mod_statistics): live compute ratio plus the observed recent
        ratio and the implied dense-FFN compute savings."""
        if not self.config.use_mod:
            return {"error": "MoD not enabled"}
        summary = self.monitor.collector.get_metric_summary(
            "mod_compute_ratio"
        )
        ratio = summary.get("current", self.config.mod_capacity_factor)
        return {
            "configured_capacity": self.config.mod_capacity_factor,
            "observed_compute_ratio": ratio,
            "compute_savings_vs_dense_ffn": round(1.0 - ratio, 4),
            "recent": summary,
        }

    def adjust_mod_capacity(self, new_capacity: float, reason: str = "") -> None:
        """Adjust the MoD compute ratio during training (ref trainer.py:1559
        adjust_mod_capacity): what fraction of tokens get the full FFN.
        Capacity is a static shape inside the jit, so the step recompiles;
        params are untouched (the router's weights don't depend on it)."""
        cfg = self.config
        if not cfg.use_mod:
            logger.warning("cannot adjust MoD capacity: MoD not enabled")
            return
        new_capacity = float(new_capacity)
        if not 0.0 < new_capacity <= 1.0:
            raise ValueError(
                f"mod_capacity_factor {new_capacity} not in (0, 1]"
            )
        old = cfg.mod_capacity_factor
        cfg.mod_capacity_factor = new_capacity
        self._rebuild_steps("mod_capacity")
        logger.warning(
            "MoD capacity %.2f -> %.2f (%s)", old, new_capacity, reason
        )
        self._interventions.append(
            {"step": self.global_step, "kind": "mod_capacity",
             "from": old, "to": new_capacity, "reason": reason}
        )

    def enable_expert_dropout(self, rate: float, reason: str = "") -> None:
        """Enable whole-expert dropout mid-run to break expert collapse
        (ref trainer.py:1495 enable_expert_dropout). rate=0 disables."""
        cfg = self.config
        if not cfg.use_moe:
            logger.warning("cannot enable expert dropout: MoE not enabled")
            return
        rate = float(rate)
        if not 0.0 <= rate <= 0.5:
            # Check before mutating: an assert inside validate() would land
            # after the config already holds the bad rate.
            raise ValueError(f"expert_dropout_rate {rate} not in [0, 0.5]")
        old = cfg.expert_dropout_rate
        cfg.expert_dropout_rate = rate
        # Eval routing is deterministic — the dropout mask never traces into
        # the eval step, so only the train step needs a rebuild.
        self.train_step = make_train_step(
            cfg, self.model, self.shardings, self.mesh,
            self._active_schedule, self.tx,
        )
        self._count_recompile("expert_dropout")
        logger.warning("expert dropout %.2f -> %.2f (%s)", old, rate, reason)
        self._interventions.append(
            {"step": self.global_step, "kind": "expert_dropout",
             "from": old, "to": rate, "reason": reason}
        )

    def adjust_weight_decay(self, new_wd: float, reason: str = "") -> None:
        """Change AdamW weight decay mid-run (ref trainer.py:1792
        adjust_weight_decay). The optimizer is rebuilt against the mutated
        config; adamw state (mu/nu/count) is decay-independent, so the live
        optimizer state carries over untouched."""
        old = self.config.weight_decay
        self.config.weight_decay = float(new_wd)
        self.tx = make_optimizer(
            self.config, self.total_steps, self._active_schedule
        )
        # Weight decay lives in the optimizer only; eval_step never sees it.
        self.train_step = make_train_step(
            self.config, self.model, self.shardings, self.mesh,
            self._active_schedule, self.tx,
        )
        self._count_recompile("weight_decay")
        logger.warning("weight decay %.3g -> %.3g (%s)", old, new_wd, reason)
        self._interventions.append(
            {"step": self.global_step, "kind": "weight_decay",
             "from": old, "to": new_wd, "reason": reason}
        )

    def _rebuild_steps(self, reason: str = "config_change") -> None:
        """Recompile train/eval steps against the (mutated) config. Param
        and optimizer trees are untouched — only traced constants and
        microbatch shapes changed."""
        self.train_step = make_train_step(
            self.config, self.model, self.shardings, self.mesh,
            self._active_schedule, self.tx,
        )
        self.eval_step = make_eval_step(
            self.config, self.model, self.shardings, self.mesh
        )
        self._count_recompile(reason)

    def train_with_oom_protection(
        self, max_attempts: Optional[int] = None
    ) -> Dict[str, Any]:
        """OOM backoff ladder around train() (ref Main.py:292
        wrap_orchestrator_with_oom_protection). On device OOM: first split
        microbatches (in-jit, data pipeline untouched), then halve the
        global batch; each rung recompiles and resumes from the live state.
        """
        if max_attempts is None:
            # config.max_retries counts OOM recoveries; each may need a
            # microbatch rung AND a batch rung, hence ×2.
            max_attempts = max(2, self.config.max_retries * 2)
        for attempt in range(1, max_attempts + 1):
            try:
                return self.train()
            except jax.errors.JaxRuntimeError as e:
                msg = str(e)
                if "RESOURCE_EXHAUSTED" not in msg and "Ran out of memory" not in msg:
                    raise
                logger.warning(
                    "OOM on attempt %d/%d: %s", attempt, max_attempts,
                    msg.splitlines()[0][:200],
                )
                if self.adjust_microbatch(2, reason="oom_backoff"):
                    continue
                # Microbatch is already 1 token-row per accum step; the only
                # remaining knob is shrinking the effective batch itself
                # (accum rescales inside adjust_batch_size, so the
                # microbatch never grows back).
                new_bs = self.config.batch_size // 2
                if new_bs >= 1 and self.adjust_batch_size(
                    new_bs, reason="oom_backoff"
                ):
                    continue
                raise
        raise RuntimeError(f"still OOM after {max_attempts} backoff attempts")

    def set_grad_clip(self, norm: float, reason: str = "") -> None:
        """Change the gradient-clip norm mid-run (rebuilds the jitted step;
        clipping is traced into it). Companion to adjust_learning_rate."""
        old = self.config.grad_clip_norm
        self.config.grad_clip_norm = norm
        self.train_step = make_train_step(
            self.config, self.model, self.shardings, self.mesh,
            self._active_schedule, self.tx,
        )
        self._count_recompile("grad_clip")
        logger.warning("grad clip %.3g -> %.3g (%s)", old, norm, reason)
        self._interventions.append(
            {"step": self.global_step, "kind": "grad_clip", "from": old,
             "to": norm, "reason": reason}
        )

    def set_data_difficulty(self, difficulty: float, reason: str = "") -> bool:
        """Forward the curriculum difficulty signal to the data loader
        (duck-typed set_difficulty — PackedDataset maps it to a doc-length
        quantile; ref chinchilla_scaler.py:155's signal, actually applied).
        Takes effect at the next epoch restart; no recompile."""
        target = getattr(self.train_data, "set_difficulty", None)
        applied = bool(callable(target) and target(difficulty) is not False)
        if applied:
            logger.info(
                "data difficulty -> %.2f (%s)", difficulty, reason
            )
            self._interventions.append(
                {"step": self.global_step, "kind": "curriculum",
                 "to": round(float(difficulty), 3), "reason": reason}
            )
        return applied

    def rollback(self, to_step: Optional[int] = None, reason: str = "") -> bool:
        """Restore an earlier checkpoint after instability
        (ref trainer.py:1727 rollback_steps)."""
        steps = self.checkpoints.all_steps()
        candidates = [
            s for s in steps
            if (to_step is None or s <= to_step)
            and s >= self._min_restorable_step  # pre-evolution saves are
            # shape-incompatible with the current param tree
        ]
        if not candidates:
            return False  # never fall forward onto a possibly-tainted save
        target = max(candidates)
        with self.goodput.region("checkpoint"), self._wd_pause():
            self.state = self.checkpoints.restore(self.state, target)
        self.global_step = int(self.state.step)
        logger.warning("rolled back to step %d (%s)", target, reason)
        self._interventions.append(
            {"step": self.global_step, "kind": "rollback", "reason": reason}
        )
        return True

    # -- data -------------------------------------------------------------
    def _put(self, batch: Dict[str, np.ndarray]) -> Dict[str, jax.Array]:
        if jax.process_count() > 1:
            return put_process_local_batch(
                batch, self._batch_sharding, self.config.batch_size
            )
        return {
            k: jax.device_put(jnp.asarray(v), self._batch_sharding)
            for k, v in batch.items()
        }

    def _device_prefetch(self, host_iter):
        """Host→device double buffering: batch n+1's transfer is dispatched
        while step n executes (device_put is async), so the step never waits
        on PCIe/DMA (SURVEY §2 'prefetch to device'; complements the
        host-side PrefetchLoader)."""
        prev = None
        for batch in host_iter:
            cur = self._put(batch)
            if prev is not None:
                yield prev
            prev = cur
        if prev is not None:
            yield prev

    # -- eval -------------------------------------------------------------
    def evaluate(self, max_batches: int = 100) -> Dict[str, float]:
        """(ref trainer.py:2667 evaluate)"""
        if self.eval_data is None:
            return {}
        totals: Dict[str, float] = {}
        count = 0
        with self.tracer.span("evaluate", step=self.global_step) as sp, \
                self.goodput.region("eval"), self._wd_pause():
            for i, batch in enumerate(self.eval_data()):
                if i >= max_batches:
                    break
                metrics = self.eval_step(self.state, self._put(batch))
                for k, v in metrics.items():
                    if getattr(v, "ndim", 1) == 0:
                        totals[k] = totals.get(k, 0.0) + float(v)
                count += 1
            sp.set(batches=count)
        if count == 0:
            return {}
        out = {f"eval_{k}": v / count for k, v in totals.items()}
        out["eval_loss"] = out.get("eval_loss", out.get("eval_ce_loss", 0.0))
        return out

    # -- main loop ---------------------------------------------------------
    def train(self) -> Dict[str, Any]:
        """Run to total_steps (or num_epochs when steps_per_epoch known).

        Returns a summary dict (ref trainer.py:3180 train)."""
        try:
            # Fresh entry (incl. OOM-ladder re-entry in one process): a
            # prior run's step stamp must not age into a false
            # "degraded" while this run resumes/replays/compiles.
            self._last_step_wall = None
            self._training_active = True
            if self.history is not None:
                self.history.start()  # idempotent across train() calls
            return self._train_inner()
        finally:
            # Whatever path exits (done, preempted, OOM ladder re-entry,
            # propagated failure): the watchdog must stop watching a
            # loop that no longer beats, and post-run time is idle. The
            # liveness gauge flips to NaN so /healthz staleness can't
            # flag a finished trainer as wedged.
            self._training_active = False
            if self.watchdog is not None:
                self.watchdog.disarm()
            self.goodput.switch("idle")

    def _goodput_batches(self, host_iter):
        """Attribute host-loop time blocked on the loader (incl. the
        host->device put in _device_prefetch) to data_wait; replay time
        the loader banked while fast-forwarding a resume is reattributed
        to resume_replay INSIDE the open segment, so the partition and
        the monotone counters both hold."""
        it = iter(host_iter)
        consume = getattr(
            self.train_data, "consume_resume_replay_seconds", None
        )
        while True:
            with self.goodput.region("data_wait"), \
                    self.tracer.span("train.data_wait"):
                try:
                    batch = next(it)
                except StopIteration:
                    return
                if consume is not None:
                    replay = consume()
                    if replay > 0:
                        self.goodput.reattribute("resume_replay", replay)
            yield batch

    def _train_inner(self) -> Dict[str, Any]:
        cfg = self.config
        t_start = time.time()
        tokens_seen = 0
        last_metrics: Dict[str, Any] = {}
        log_every = max(1, cfg.health_check_interval // 10)
        stop = False
        # A fresh train() call starts unpreempted (in-process restart in
        # tests / notebooks); a pre-armed request_stop still honors at the
        # first step boundary.
        self._preempted = False

        epoch = 0
        # Throughput is measured over whole windows between log events, with
        # the float() conversions at each log acting as the device sync —
        # per-step host deltas only time dispatch under async execution
        # (VERDICT r1 weak #7).
        self._run_start_step = self.global_step
        window_t0 = time.time()
        window_tokens = 0
        window_steps = 0
        self.goodput.switch("productive")
        while not stop and self.global_step < self.total_steps:
            epoch += 1
            for batch in self._goodput_batches(
                self._device_prefetch(self.train_data())
            ):
                if self.global_step >= self.total_steps:
                    break
                first_step = self.global_step == self._run_start_step
                self._maybe_profile()
                if first_step:
                    # The first step call + its sync below IS the compile
                    # window; the ledger flips back to productive (and
                    # the watchdog arms) once the sync lands.
                    self.goodput.switch("compile")
                # Step marker on the profiler's timeline; a branch when
                # nothing mirrors spans into the profiler.
                with (
                    jax.profiler.StepTraceAnnotation(
                        "train_step", step_num=self.global_step
                    )
                    if self.tracer.use_jax_profiler else _NO_ANNOTATION
                ):
                    self.state, metrics = self.train_step(self.state, batch)
                self.global_step += 1
                self._batch_in_epoch += 1
                # Liveness stamp for /healthz staleness (host clock read,
                # not a device sync — the dispatch above is async).
                self._last_step_wall = time.time()
                n_tok = int(batch["input_ids"].size)
                tokens_seen += n_tok
                window_tokens += n_tok
                window_steps += 1
                self._m_steps.inc()
                self._m_tokens.inc(n_tok)
                if first_step:
                    # Sync out the XLA compile, then restart the window so
                    # the first tokens_per_sec isn't dominated by compile.
                    float(metrics["loss"])
                    self._count_recompile("initial_compile")
                    if cfg.compiled_cost_analysis:
                        self._export_compiled_costs(batch)
                    self.goodput.switch("productive")
                    if self.watchdog is not None:
                        # Armed AFTER the compile sync: the watchdog's
                        # rolling stats see only steady-state windows, so
                        # a first compile can never trip it (and nothing
                        # fires until `warmup` intervals exist anyway).
                        self.watchdog.arm()
                    window_t0, window_tokens, window_steps = time.time(), 0, 0

                if self.global_step % log_every == 0:
                    with self.tracer.span(
                        "train.log_sync", step=self.global_step
                    ):
                        scalars = {
                            k: float(v)  # ← device sync happens here
                            for k, v in metrics.items()
                            if getattr(v, "ndim", 1) == 0
                        }
                    now = time.time()
                    scalars["tokens_per_sec"] = window_tokens / max(
                        now - window_t0, 1e-9
                    )
                    if window_steps > 0:
                        # Whole-window measurement (the float() above was
                        # the sync): mean step time observed once per step
                        # in the window, so histogram counts = steps.
                        window_mean_s = (now - window_t0) / window_steps
                        self._m_step_time.observe(
                            window_mean_s, count=window_steps,
                        )
                        # Anomaly sentinel: robust median/MAD check on
                        # the window mean (train_step_seconds_{median,
                        # mad} gauges + step_anomaly events).
                        self._sentinel.observe(
                            window_mean_s, step=self.global_step
                        )
                    if self.watchdog is not None:
                        # Heartbeat at the synced boundary: a hang shows
                        # as this beat never arriving.
                        self.watchdog.beat()
                    # Straggler signal: per-host completion skew at this
                    # existing sync (one tiny all-gather on multihost
                    # fleets; single-host sets the gauge to 0.0 with no
                    # device work).
                    host_step_skew(self.registry)
                    self._m_tps.set(scalars["tokens_per_sec"])
                    window_t0, window_tokens, window_steps = now, 0, 0
                    self.monitor.log_step(self.global_step, scalars)
                    self._export_router_health(metrics, scalars)
                    self._export_held_and_decay(scalars)
                    last_metrics = scalars
                    if self.step_callback is not None:
                        cb_metrics = dict(scalars)
                        if "expert_utilization" in metrics:
                            cb_metrics["expert_utilization"] = np.asarray(
                                metrics["expert_utilization"]
                            )
                        self.step_callback(self.global_step, cb_metrics)
                    if not np.isfinite(scalars.get("loss", 0.0)):
                        stop = self._handle_nonfinite()
                        if stop:
                            break
                    else:
                        self._consecutive_nonfinite = 0
                        self._first_nonfinite_step = None

                if (
                    self.eval_data is not None
                    and self.global_step % cfg.eval_every_n_batches == 0
                ):
                    eval_metrics = self.evaluate()
                    # Eval windows are their own event type — a replayed
                    # dump's train_step cadence must not conflate them.
                    self.monitor.log_step(
                        self.global_step, eval_metrics, event="eval_step"
                    )
                    last_metrics.update(eval_metrics)
                    if self._check_early_stopping(eval_metrics.get("eval_loss")):
                        stop = True
                        break
                    if (
                        self._convergence is not None
                        and eval_metrics.get("eval_loss") is not None
                        and self._convergence.update(
                            eval_metrics["eval_loss"], self.global_step
                        )
                    ):
                        logger.info(
                            "convergence detected at step %d; stopping "
                            "(chinchilla budget satisfied early)",
                            self.global_step,
                        )
                        stop = True
                        break
                    # Eval time isn't train throughput; restart the window.
                    window_t0, window_tokens, window_steps = time.time(), 0, 0

                overdue_backup = (
                    cfg.backup_every_n_hours > 0
                    and time.time() - self._last_backup_time
                    > cfg.backup_every_n_hours * 3600
                )
                if (
                    (
                        self.global_step % cfg.save_every_n_batches == 0
                        or overdue_backup
                    )
                    and self._first_nonfinite_step is None  # not NaN-suspect
                ):
                    self.save_checkpoint(last_metrics, force=overdue_backup)
                    self._last_backup_time = time.time()
                    window_t0, window_tokens, window_steps = time.time(), 0, 0

                if self._stop_requested:
                    # Preemption: stop at this step boundary with a
                    # BLOCKING emergency save (the orbax commit lands
                    # before we return), so the process can exit with a
                    # resumable checkpoint + exact data cursor.
                    reason = self._stop_requested
                    logger.warning(
                        "stop requested (%s): emergency save at step %d",
                        reason, self.global_step,
                    )
                    self._preempted = True
                    self._m_preemptions.inc()
                    self.recorder.emit(
                        "preemption", step=self.global_step, reason=reason,
                    )
                    with self.goodput.region("checkpoint"), self._wd_pause():
                        self.checkpoints.emergency_save(
                            self.state, self.global_step, reason=reason,
                            data_state=self._data_state(),
                        )
                        # The trail must survive the exit: dump the last N
                        # step/router events next to the emergency save.
                        self._dump_flight_record(reason)
                    stop = True
                    break
            else:
                # Epoch iterator exhausted with no break: one full data
                # pass consumed — advance the exact-resume cursor.
                self._data_epoch += 1
                self._batch_in_epoch = 0

            if (
                self.steps_per_epoch is not None
                and epoch >= cfg.num_epochs
            ):
                break

        final_eval: Dict[str, float] = {}
        if not self._preempted:
            # A preempted run already banked its emergency checkpoint and
            # is racing the platform's grace period: skip final eval/save.
            final_eval = self.evaluate() if self.eval_data is not None else {}
            last_metrics.update(final_eval)
            self.save_checkpoint(last_metrics, force=True)
        with self.goodput.region("checkpoint"), self._wd_pause():
            # The final async flush can legitimately block for minutes on
            # a big model — paused like every other slow host-work site,
            # or a SUCCESSFUL run's last flush would read as a hang.
            self.checkpoints.wait()

        elapsed = time.time() - t_start
        summary = {
            "final_step": self.global_step,
            "epochs": epoch,
            "elapsed_sec": round(elapsed, 1),
            "tokens_seen": tokens_seen,
            "tokens_per_sec": round(tokens_seen / max(elapsed, 1e-9), 1),
            "final_metrics": {k: v for k, v in last_metrics.items()},
            "health": self.monitor.get_health_summary(),
            "interventions": self._interventions,
            "preempted": self._preempted,
            "resumed_exact_data_state": self._resumed_exact_data_state,
            # Wall-clock attribution for the trainer's whole life (the
            # ledger opens at __init__): productive / compile /
            # checkpoint / data_wait / resume_replay / eval / hang /
            # idle, partitioned by construction.
            "goodput": self.goodput.snapshot(),
            # What actually ran, after every ladder and orchestrator
            # intervention: a caller that asked for batch 16 x seq 2048
            # reads here whether it got it.
            "ran": {
                "batch_size": cfg.batch_size,
                "seq_length": cfg.seq_length,
                "gradient_accumulation_steps": (
                    cfg.gradient_accumulation_steps
                ),
                "num_layers": cfg.num_layers,
                "scan_layers": cfg.scan_layers,
                "mesh": {a: int(n) for a, n in self.mesh.shape.items()},
            },
        }
        if getattr(self, "_compiled_costs", None) is not None:
            # config.compiled_cost_analysis: XLA's cost model, memory
            # analysis and the Pallas kernel census of the compiled step.
            summary["compiled_costs"] = self._compiled_costs
        if self.slo is not None:
            # Final verdict over everything the ring retained: one last
            # sample so short runs (whose sampler may never have ticked)
            # still carry objective states. The attached engine already
            # evaluated via the sample listener — verdicts() reads that
            # result; a second evaluate() here would advance the clear
            # hysteresis an extra step.
            self.history.sample_once()
            summary["slo"] = {
                **self.slo.verdicts(),
                "ring": self.history.stats(),
            }
        logger.info("training done: %s", summary)
        return summary

    # -- router health (docs/observability.md "Router health") ------------
    def _export_router_health(self, metrics, scalars) -> None:
        """Per-expert load + router-entropy telemetry at log cadence.

        The vector leaves the device HERE, at the same whole-window sync
        the scalar float() conversions just performed — no new host sync
        enters the step path (LX002 stays clean). Gauges:
        moe_expert_load{expert} (share of KEPT routed tokens, sums to
        ~1.0), moe_router_entropy, moe_max_expert_share, moe_drop_rate;
        plus one router_health event per log window."""
        util = metrics.get("expert_utilization")
        if util is None:
            return
        try:
            util = np.asarray(util, dtype=np.float64)
        except Exception:
            return
        E = int(util.shape[-1])
        total = float(util.sum())
        # expert_utilization is f*E (1.0 == balanced); normalize to the
        # kept-token share per expert so the loads sum to ~1.0.
        load = (util / total) if total > 0 else np.full(E, 1.0 / max(E, 1))
        r = self.registry
        if E <= 256:  # bounded gauge cardinality, whatever the config
            g = r.gauge(
                "moe_expert_load",
                "Share of kept routed tokens per expert (sums to ~1.0; "
                "1/E == balanced)",
                labelnames=("expert",),
                max_label_values=256,
            )
            for i in range(E):
                g.labels(expert=str(i)).set(float(load[i]))
        entropy = scalars.get("moe_router_entropy")
        if entropy is not None:
            r.gauge(
                "moe_router_entropy",
                "Mean per-token routing entropy (ln(num_experts) == "
                "uniform, 0 == collapsed)",
            ).set(entropy)
        max_share = scalars.get("moe_max_expert_share")
        if max_share is not None:
            r.gauge(
                "moe_max_expert_share",
                "Hottest expert's share of kept routed tokens",
            ).set(max_share)
        drop = scalars.get("moe_drop_rate")
        if drop is not None:
            r.gauge(
                "moe_drop_rate",
                "Fraction of tokens losing >=1 routing slot to capacity "
                "(capacity dispatch paths)",
            ).set(drop)
        # a2a dispatch: per-stage routed-token counts (per-layer mean
        # from the aux metrics; global — the layer psums them over the
        # token shards). Counter sampled at log cadence like the rest of
        # this window's telemetry; the static per-stage byte plan rides
        # the ep_a2a_bytes{stage} gauges exported at trace time
        # (parallel/expert_dispatch.export_plan_gauges).
        routed = scalars.get("ep_tokens_routed")
        routed_dcn = scalars.get("ep_tokens_dcn")
        if routed is not None and routed > 0:
            c = r.counter(
                "ep_dispatch_tokens_total",
                "Routed (token, slot) pairs through the expert a2a "
                "dispatch per hierarchy stage, sampled at log cadence",
                labelnames=("stage",),
            )
            c.labels(stage="ici").inc(routed)
            if routed_dcn:
                c.labels(stage="dcn").inc(routed_dcn)
        self.recorder.emit(
            "router_health", step=self.global_step,
            expert_load=[round(float(x), 4) for x in load],
            entropy=(
                round(float(entropy), 4) if entropy is not None else None
            ),
            max_share=(
                round(float(max_share), 4) if max_share is not None else None
            ),
            drop_rate=round(float(drop), 4) if drop is not None else None,
            **(
                {
                    "ep_tokens_routed": round(float(routed), 1),
                    "ep_tokens_dcn": round(float(routed_dcn or 0.0), 1),
                }
                if routed is not None
                else {}
            ),
        )

    def _export_held_combine(self) -> None:
        """Which form the held expert layers' combine takes at the
        microbatch's shapes, for the step as built (models/moe.py
        held_combine_is_product): gauge, log line and a flight-recorder
        event; nothing without Config.experts_held."""
        from luminaai_tpu.models.moe import (
            export_held_combine, held_combine_form,
        )

        cfg = self.config
        form = held_combine_form(
            cfg,
            cfg.batch_size // max(1, cfg.gradient_accumulation_steps)
            * cfg.seq_length,
            self.model.dtype,
        )
        if form is not None:
            export_held_combine(form, self.registry, logger)
            self.recorder.emit(
                "moe_held_combine", step=self.global_step, **form
            )

    def _export_held_and_decay(self, scalars) -> None:
        """Held-expert pair counters and the delta rule's decay gauge, from
        the scalars the log-window sync above already brought to the host
        (no sync of its own). The counters grow by one step's per-layer
        mean a log window, as ep_dispatch_tokens_total does: their RATIOS
        are what is read (held / routed: the chip's share of the routing;
        dropped / held: pairs chosen for a held expert and not computed)."""
        r = self.registry
        for key, text in (
            ("moe_routed_pairs", "Routed (token, expert) pairs"),
            ("moe_held_pairs",
             "Routed pairs that fell on an expert this program holds "
             "(Config.experts_held)"),
            ("moe_held_pairs_dropped",
             "Held pairs not computed: beyond the grouped matmul's static "
             "row bound (must stay 0)"),
        ):
            v = scalars.get(key)
            if v is not None:
                r.counter(
                    f"{key}_total",
                    text + ", a layer, sampled at log cadence",
                ).inc(v)
        decay = scalars.get("kda_decay_min")
        if decay is not None:
            r.gauge(
                "kda_decay_min",
                "Most negative gate summed over one 16-token sub-chunk and "
                "channel of a delta-rule layer (float32 holds down to ~-88)",
            ).set(decay)

    # -- crash forensics (docs/observability.md "Flight recorder") --------
    def _dump_flight_record(self, reason: str) -> Optional[str]:
        """Dump the wide-event ring next to the checkpoints so the last
        N step/request events survive the exit (`lumina events` replays
        the flightrec-*.jsonl), plus the time-series history when SLO
        retention is on (`lumina top <ckpt-dir>` replays the tshist-*
        snapshot). Never raises — it rides the emergency paths."""
        if self.history is not None:
            self.history.dump_to_dir(
                str(self.checkpoints.dir), reason,
                slo=self.slo.verdicts() if self.slo is not None else None,
            )
        return self.recorder.dump_to_dir(str(self.checkpoints.dir), reason)

    # -- profiling (SURVEY §5 tracing) -------------------------------------
    def request_profile(self, num_steps: int, trace_dir: str,
                        attribute: bool = False,
                        at_step: Optional[int] = None) -> None:
        """Arm a device-profiler capture of `num_steps` steps into
        `trace_dir`: it starts at the next step boundary (or the one
        before step `at_step`) and stops `num_steps` boundaries later,
        through the tracer's capture control (monitoring/tracing.py), so
        the trainer's spans and step markers land in the same trace.
        `attribute` runs the per-subsystem attribution on the finished
        trace. Callable from the step hook of a running `train()`; it
        replaces a request not yet started."""
        self._profile_request = (max(1, int(num_steps)), trace_dir,
                                 bool(attribute), at_step)

    @property
    def profiling(self) -> bool:
        return self._profile_open is not None

    def stop_profile(self) -> Optional[str]:
        """Stop the open capture now (idempotent): waits for the device
        so that the last profiled step is whole. Returns the trace
        directory when this call stopped one."""
        if self._profile_open is None:
            return None
        _, trace_dir, attribute = self._profile_open
        self._profile_open = None
        jax.block_until_ready(self.state.params)
        if self.tracer.stop_capture() is None:
            return None
        logger.info("profiler trace stopped -> %s", trace_dir)
        if attribute:
            self._attribute_profile(trace_dir)
        return trace_dir

    def _maybe_profile(self) -> None:
        """At a step boundary: start an armed capture, stop an open one
        whose steps are done. The config's `profile_start_step` /
        `profile_num_steps` (CLI `--profile-steps N --profile-dir DIR`)
        is a request made at construction for that step; its trace is
        attributed per subsystem (monitoring/attribution.py) into
        registry gauges + <trace_dir>/attribution.jsonl."""
        if self._profile_open is not None:
            if self.global_step >= self._profile_open[0]:
                self.stop_profile()
            return
        if self._profile_request is None:
            return
        num_steps, trace_dir, attribute, at_step = self._profile_request
        if at_step is not None and self.global_step != at_step:
            return
        self._profile_request = None
        if self.tracer.start_capture(trace_dir):
            self._profile_open = (
                self.global_step + num_steps, trace_dir, attribute
            )
            logger.info("profiler trace started -> %s", trace_dir)

    def _attribute_profile(self, trace_dir: str) -> None:
        """Per-subsystem breakdown of the just-captured window. Requires
        the xprof converter; failure costs a warning, never the run."""
        from luminaai_tpu.monitoring.attribution import (
            attribute_xplane_dir,
            export_attribution,
        )

        try:
            attr = attribute_xplane_dir(
                trace_dir, n_steps=max(1, self.config.profile_num_steps)
            )
            record = export_attribution(
                attr,
                registry=self.registry,
                jsonl_path=os.path.join(trace_dir, "attribution.jsonl"),
            )
            top = list(attr.ms_per_step.items())[:3]
            logger.info(
                "step attribution (%d steps, %.1f ms/step attributed): %s "
                "-> %s/attribution.jsonl",
                attr.n_steps,
                attr.total_ms_per_step,
                ", ".join(f"{k}={v:.1f}ms" for k, v in top),
                trace_dir,
            )
            self._last_attribution = record
        except Exception as e:
            logger.warning("trace attribution unavailable: %s", e)

    def _export_compiled_costs(self, batch) -> None:
        """AOT cost/memory analysis of the just-compiled train step
        (config.compiled_cost_analysis): exports compiled_flops_per_step,
        bytes-accessed and HBM-footprint gauges plus the analytic-vs-
        compiled MFU cross-check. Graceful on backends with no cost
        model; never raises into the train loop."""
        from luminaai_tpu.monitoring.attribution import (
            analytic_train_flops,
            compiled_cost_metrics,
            donation_audit,
            tree_bytes,
        )

        try:
            tokens_per_step = int(batch["input_ids"].size)
            result = compiled_cost_metrics(
                self.train_step,
                self.state,
                batch,
                program="train",
                registry=self.registry,
                analytic_flops=analytic_train_flops(
                    self.config.estimate_active_parameters(), tokens_per_step
                ),
            )
            # Donation audit rides the same export: alias coverage over
            # the resident TrainState proves the in-place update compiled
            # (a silent donation break doubles peak optimizer HBM — the
            # r3 "optimizer + misc" bucket's failure mode).
            audit = donation_audit(
                result.get("memory"),
                tree_bytes(self.state),
                expected=self.config.donate_state,
                registry=self.registry,
            )
            result["donation_audit"] = audit
            if audit.get("flagged"):
                logger.warning(
                    "donation audit: alias coverage %.2f < %.2f — the "
                    "train step is COPYING its donated state each step",
                    audit.get("coverage") or 0.0,
                    audit.get("threshold", 0.0),
                )
            self._compiled_costs = result
            if result.get("available"):
                xc = result.get("mfu_crosscheck") or {}
                if xc.get("flagged"):
                    logger.warning(
                        "analytic-vs-compiled FLOPs diverge %.1f%% "
                        "(analytic 6NT %.3e, compiled %.3e): the MFU "
                        "headline and the compiled program disagree",
                        100 * xc["divergence"],
                        xc["analytic_flops_per_step"],
                        xc["compiled_flops_per_step"],
                    )
                else:
                    logger.info("compiled cost analysis: %s", result)
            else:
                logger.info(
                    "compiled cost analysis unavailable: %s",
                    result.get("reason"),
                )
        except Exception as e:  # pragma: no cover - belt and braces
            logger.warning("compiled cost analysis failed: %s", e)

    # -- failure handling --------------------------------------------------
    def _handle_nonfinite(self) -> bool:
        """NaN/Inf loss: rollback strictly before first detection, else abort
        (ref trainer.py train_with_oom_fallback's instability ladder).

        Detection runs at log granularity; `_first_nonfinite_step` marks the
        earliest suspect step so rollback never lands on a checkpoint saved
        inside the NaN window (saves are also suppressed while suspect)."""
        self._consecutive_nonfinite += 1
        if self._first_nonfinite_step is None:
            self._first_nonfinite_step = self.global_step
        if self._consecutive_nonfinite < 3:
            logger.warning(
                "non-finite loss at step %d (%d consecutive)",
                self.global_step, self._consecutive_nonfinite,
            )
            return False
        safe = self._first_nonfinite_step - 1
        if self.rollback(to_step=safe, reason="non-finite loss x3"):
            self._consecutive_nonfinite = 0
            self._first_nonfinite_step = None
            return False
        logger.error(
            "no checkpoint at or before step %d; aborting with emergency save",
            safe,
        )
        self.recorder.emit(
            "train_abort", step=self.global_step,
            reason="non-finite loss, no rollback point",
        )
        with self.goodput.region("checkpoint"), self._wd_pause():
            self.checkpoints.emergency_save(
                self.state, self.global_step,
                "non-finite loss, no rollback point",
                data_state=self._data_state(),
            )
            self._dump_flight_record("non_finite")
        return True

    def _check_early_stopping(self, eval_loss: Optional[float]) -> bool:
        """(ref trainer.py:3584 _check_early_stopping)"""
        if eval_loss is None:
            return False
        if eval_loss < self.best_eval_loss - 1e-4:
            self.best_eval_loss = eval_loss
            self._epochs_without_improvement = 0
            return False
        self._epochs_without_improvement += 1
        patience = self.config.early_stopping_patience
        if patience is not None and self._epochs_without_improvement >= patience:
            logger.info(
                "early stopping: no improvement in %d evals", patience
            )
            return True
        return False

    def close(self) -> None:
        self.stop_profile()  # the run ended inside the profiled window
        if self.watchdog is not None:
            self.watchdog.close()
        pauses, self._pauses = getattr(self, "_pauses", None), None
        if pauses is not None:
            pauses.close(self.registry, self.tracer)
        if self.history is not None:
            self.history.stop()
            if self._installed_history and get_history() is self.history:
                set_history(getattr(self, "_prev_history", None))
        self.checkpoints.close()
        self.goodput.stop()
        set_default_policy(self._prev_io_policy)
