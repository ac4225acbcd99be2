"""Benchmark harness (driver contract: exactly ONE JSON line on stdout).

North-star metric (SURVEY.md §6 / BASELINE.json): training tokens/sec/chip
on the 8-expert top-2 MoE config (capacity 1.25, aux 0.01), bf16, full train
step (fwd + bwd + optimizer). vs_baseline compares against the reference's
headline debug-MoE figure (59.5k tok/s, /root/reference/BENCHMARKS.md "MoE
Configuration (8 experts, top-2)" — the only published absolute throughput
for this model family).

That 59.5k figure is measured on the reference's DEBUG preset (~0.5M active
/ ~4M total params — its BENCHMARKS.md says so explicitly), so the headline
rung here runs the same model dims on the chip (ref_debug_moe) and
vs_baseline is finally like-for-like. The 757M-param flagship — the config
sized to saturate the MXU, which rounds 1-2 mistakenly compared against the
tiny-model baseline — still runs every round; its throughput/MFU/routing
numbers are embedded in extras.flagship and tracked in BENCHMARKS.md.

Process contract: the parent process imports NO jax (a chip belongs to
one process at a time). It asks a child what the default backend is, exits
non-zero when that is not a TPU — a number from a CPU run is never written
under a device metric's name — and otherwise runs each rung in a child with
a timeout, falling down the ladder to a smaller config when a rung dies.
The --smoke* modes are hermetic CPU contract checks, not measurements.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

REF_MOE_TOKENS_PER_SEC = 59_500.0
METRIC = "train_tokens_per_sec_per_chip_moe8x2"

# The reference's published throughput rows (its BENCHMARKS.md) that fit
# one chip, matched dims-for-dims. The headline rung (ref_debug_moe) and
# the DENSE_BENCH sidecar compare against two of these; the REF_TABLE
# sidecar sweeps the rest so every debug-scale row has a measured
# counterpart. (name -> (ref tok/s, rung timeout_s))
REF_TABLE_RUNGS = {
    "ref_debug_dense": (104_000.0, 420),   # "Debug" dense row
    "ref_200m_dense": (119_000.0, 600),    # "Debug 200M" dense row
    "ref_200m_mod": (172_000.0, 600),      # "Debug 200M" MoD cap 0.5 row
    "ref_200m_hybrid": (139_000.0, 600),   # "Debug 200M" hybrid row
}
REF_BASELINES = {
    "ref_debug_moe": REF_MOE_TOKENS_PER_SEC,
    "dense200": 119_000.0,
    **{k: v[0] for k, v in REF_TABLE_RUNGS.items()},
}

# (name, timeout_s). Each rung is tried in order until one emits valid JSON.
#
# ref_debug_moe is the HEADLINE rung: the reference's 59.5k tok/s figure is
# measured on its own "debug" preset — hidden 128, 2 layers, seq 256,
# ~0.6M active params (/root/reference/BENCHMARKS.md "Debug (~500K active,
# ~4M total)"; config/config_manager.py:763) — so the apples-to-apples
# comparison runs THAT model on the chip. Rounds 1-2 compared a 757M-param
# flagship against the tiny-model baseline (conservative by ~3 orders of
# magnitude of model scale); the flagship stays in the ladder as the
# MXU-utilization rung and its numbers ride along in extras.flagship.
#
# flagship_tuned is ConfigPresets.flagship(): the r3 levers (save_attn
# remat, bf16 mu) plus gmm dispatch and bf16 RoPE.
LADDER = [
    ("ref_debug_moe", 420),
    ("flagship_tuned", 900),
    ("flagship", 1500),
    ("flagship_small", 600),
]


def _child_config(name: str, n_chips: int = 1):
    """Bench configs. flagship: ~757M total / ~238M active MoE, sized to
    saturate the MXU on one v5e chip (state ~9GB of 16GB HBM). Batch scales
    with chip count so per-chip load is constant across slice sizes."""
    from luminaai_tpu.config import Config

    if name == "ref_debug_moe":
        # The reference's own headline benchmark config (ref
        # config_manager.py:763 ConfigPresets.debug model dims; routing set
        # to this bench's stated contract: 8 experts top-2, cap 1.25, aux
        # 0.01). Batch 256 was the fastest of 256/1024/4096 on chip (r3);
        # the reference's own run used ~365K tokens/step, so a large batch
        # is faithful to its methodology.
        return Config(
            vocab_size=1024,
            hidden_size=128,
            num_layers=2,
            num_heads=2,
            num_kv_heads=1,
            seq_length=256,
            intermediate_size=256,
            batch_size=256 * n_chips,
            use_moe=True,
            num_experts=8,
            moe_top_k=2,
            capacity_factor=1.25,
            load_balancing_weight=0.01,
            precision="bf16",
            use_flash_attention=True,
            gradient_checkpointing=False,
        )
    if name in ("flagship_tuned", "flagship", "flagship_small"):
        from luminaai_tpu.config import ConfigPresets

        return ConfigPresets.flagship(
            n_chips,
            tuned=name == "flagship_tuned",
            small=name == "flagship_small",
        )
    if name == "ref_debug_dense":
        # The reference's debug DENSE row (~104k tok/s): its debug preset
        # dims (ref config_manager.py:763) with MoE off.
        return Config(
            vocab_size=1024,
            hidden_size=128,
            num_layers=2,
            num_heads=2,
            num_kv_heads=1,
            seq_length=256,
            intermediate_size=256,
            batch_size=256 * n_chips,
            use_moe=False,
            precision="bf16",
            use_flash_attention=True,
            gradient_checkpointing=False,
        )
    if name in ("ref_200m_dense", "ref_200m_mod", "ref_200m_hybrid"):
        # The reference's debug_200m dims (ref config_manager.py:946:
        # vocab 1024, hidden 640, 12 layers, heads 8/8, seq 512,
        # intermediate 2560) under its three published variants: dense
        # (~119k), MoD cap 0.5 (~172k), hybrid MoE8+MoD (~139k).
        return Config(
            vocab_size=1024,
            hidden_size=640,
            num_layers=12,
            num_heads=8,
            num_kv_heads=8,
            seq_length=512,
            intermediate_size=2560,
            batch_size=64 * n_chips,
            use_moe=(name == "ref_200m_hybrid"),
            num_experts=8,
            moe_top_k=2,
            capacity_factor=1.25,
            load_balancing_weight=0.01,
            use_mod=(name != "ref_200m_dense"),
            mod_capacity_factor=0.5,
            precision="bf16",
            use_flash_attention=True,
            gradient_checkpointing=False,
        )
    if name == "dense200":
        # ~200M dense comparison point (ref BENCHMARKS.md "200M dense
        # ~119k tok/s"). Manual rung: python bench.py --child dense200.
        return Config(
            vocab_size=32768,
            hidden_size=896,
            num_layers=20,
            num_heads=14,
            num_kv_heads=7,
            seq_length=2048,
            batch_size=16 * n_chips,
            use_moe=False,
            precision="bf16",
            use_flash_attention=True,
            gradient_checkpointing=True,
        )
    if name == "smoke":
        # Hermetic CPU smoke (bench.py --smoke): a tiny model so the full
        # attribution surface — compiled cost analysis on the train and
        # decode steps, MFU cross-check, bench_gate verdict — runs in
        # seconds on any machine.
        return Config(
            vocab_size=512,
            hidden_size=64,
            num_layers=2,
            num_heads=4,
            num_kv_heads=2,
            seq_length=128,
            batch_size=4,
            use_moe=True,
            num_experts=4,
            moe_top_k=2,
            capacity_factor=1.25,
            load_balancing_weight=0.01,
            precision="fp32",
            use_flash_attention=False,
            gradient_checkpointing=False,
        )
    raise ValueError(f"unknown bench config {name!r}")


def _child_main(name: str) -> None:
    """Runs in a subprocess; prints the JSON result line on success."""
    child_t0 = time.perf_counter()
    budget = float(os.environ.get("BENCH_CHILD_BUDGET_S", "0") or 0)

    from bench_common import enable_compile_cache

    enable_compile_cache()
    import jax

    if name == "smoke":
        jax.config.update("jax_platforms", "cpu")

    import jax.numpy as jnp
    import numpy as np

    from luminaai_tpu.models.transformer import LuminaTransformer
    from luminaai_tpu.parallel.mesh import build_mesh
    from luminaai_tpu.parallel.sharding import init_sharded_state
    from luminaai_tpu.parallel.train_step import make_train_step
    from luminaai_tpu.training.optimizer import make_optimizer, make_schedule
    from luminaai_tpu.training.scaler import ComputeEfficiencyTracker

    n_chips = jax.device_count()
    platform = jax.devices()[0].platform
    if platform != "tpu" and name != "smoke":
        # A measurement path that finds no chip fails; only the hermetic
        # --smoke contract check is CPU by design.
        sys.exit(f"bench child {name}: needs a TPU, jax found {platform!r}")
    cfg = _child_config(name, n_chips)

    model = LuminaTransformer(cfg)
    schedule = make_schedule(cfg, 1000)
    tx = make_optimizer(cfg, 1000, schedule)
    mesh = build_mesh(cfg)
    state, shardings = init_sharded_state(cfg, model, tx, mesh, jax.random.key(0))
    step = make_train_step(cfg, model, shardings, mesh, schedule, tx)

    ids = np.random.RandomState(0).randint(
        1, cfg.vocab_size, size=(cfg.batch_size, cfg.seq_length)
    )
    batch = {"input_ids": jnp.asarray(ids, jnp.int32)}

    # Timing boundaries force a host transfer of the step's loss: a
    # float() round-trip cannot return before device execution finishes.

    # First step = compile + execute; measured separately.
    t0 = time.perf_counter()
    state, metrics = step(state, batch)
    float(metrics["loss"])
    compile_s = time.perf_counter() - t0

    # Warmup one more executed step so caches/donation settle.
    state, metrics = step(state, batch)
    float(metrics["loss"])

    steps = 3 if name == "smoke" else 20
    t0 = time.perf_counter()
    for _ in range(steps):
        state, metrics = step(state, batch)
    loss_val = float(metrics["loss"])
    dt = time.perf_counter() - t0
    drop_val = float(metrics.get("moe_drop_rate", 0.0))

    # Telemetry provenance: the measured window recorded into the unified
    # registry (monitoring/telemetry.py) and snapshotted into the artifact,
    # so the headline number ships with its own step-time distribution
    # instead of resting on unpersisted prints (VERDICT r5).
    from luminaai_tpu.monitoring.telemetry import get_registry

    registry = get_registry()
    registry.counter(
        "bench_steps_total", "Measured train steps in the bench window"
    ).inc(steps)
    registry.counter(
        "bench_tokens_total", "Tokens through the measured bench window"
    ).inc(steps * cfg.batch_size * cfg.seq_length)
    registry.histogram(
        "bench_step_seconds",
        "Mean step wall time over the measured window (count = steps)",
        buckets=(0.001, 0.005, 0.01, 0.05, 0.1, 0.5, 1.0, 5.0, 30.0),
    ).observe(dt / steps, count=steps)
    registry.gauge(
        "bench_compile_seconds", "First-step compile+execute time"
    ).set(compile_s)

    # Steady-state MoE routing: the 20-step window above starts from random
    # init, so its drop rate is an initialization artifact (r2 measured 22.7%
    # there). Keep stepping (cycling fresh batches so the router sees varied
    # token mixes) and report the drop rate after the router has settled.
    drop_steady = None
    if cfg.use_moe and name != "smoke":
        rng = np.random.RandomState(1)
        extra_batches = [
            {
                "input_ids": jnp.asarray(
                    rng.randint(
                        1, cfg.vocab_size, size=(cfg.batch_size, cfg.seq_length)
                    ),
                    jnp.int32,
                )
            }
            for _ in range(4)
        ]
        steady_steps = 150 if platform == "tpu" else 10
        tail = []
        for i in range(steady_steps):
            # This loop is a nice-to-have diagnostic: never let it eat the
            # rung's timeout and cost the headline number. Sync every 10
            # steps and bail at 75% of the child budget.
            if budget and i % 10 == 0:
                float(metrics["loss"])  # sync: async dispatch hides elapsed
                if time.perf_counter() - child_t0 > 0.75 * budget:
                    break
            state, metrics = step(state, extra_batches[i % 4])
            if i >= steady_steps - 10:
                tail.append(float(metrics.get("moe_drop_rate", 0.0)))
        if tail:
            drop_steady = round(sum(tail) / len(tail), 4)

    # Compiled-cost accounting (monitoring/attribution.py): what XLA's
    # own cost model says one step executable costs — FLOPs, bytes,
    # HBM footprint — plus the analytic-vs-compiled MFU cross-check,
    # embedded next to the measured number so the MFU headline carries
    # its own audit. The AOT lower+compile hits the persistent compile
    # cache where configured; budget-guarded regardless so it can never
    # cost a rung its timeout. Runs AFTER the measured window, so it
    # cannot perturb the timing either.
    from luminaai_tpu.monitoring.attribution import (
        analytic_train_flops,
        compiled_cost_metrics,
    )

    if not budget or time.perf_counter() - child_t0 < 0.85 * budget:
        compiled_cost = compiled_cost_metrics(
            step,
            state,
            batch,
            program="train",
            registry=registry,
            analytic_flops=analytic_train_flops(
                cfg.estimate_active_parameters(),
                cfg.batch_size * cfg.seq_length,
            ),
        )
    else:
        compiled_cost = {
            "available": False,
            "reason": "child budget exhausted before cost analysis",
        }

    # Donation audit (monitoring/attribution.py): the train step donates
    # its whole TrainState — XLA's alias bytes over the resident state
    # bytes proves the in-place update actually compiled, so a silently
    # broken donation (state copied every step, the "optimizer + misc"
    # HBM bucket doubling) becomes visible artifact evidence.
    from luminaai_tpu.monitoring.attribution import donation_audit, tree_bytes

    donation = donation_audit(
        compiled_cost.get("memory")
        if isinstance(compiled_cost, dict)
        else None,
        tree_bytes(state),
        expected=cfg.donate_state,
        registry=registry,
    )

    tokens = steps * cfg.batch_size * cfg.seq_length
    tps_chip = tokens / dt / n_chips
    from luminaai_tpu.utils.environment import device_peak_flops

    # The one peak table, keyed by device_kind; an unknown kind raises.
    # The CPU --smoke has no peak: its mfu is null, its rate a count.
    peak = device_peak_flops(jax.devices()[0]) if platform == "tpu" else None
    tracker = ComputeEfficiencyTracker(
        active_params=cfg.estimate_active_parameters(),
        n_chips=n_chips,
        peak_flops=peak or float("inf"),
    )
    sample = tracker.record(tokens, dt)
    mfu = round(sample["mfu"], 4) if peak else None

    sidecar_rung = (
        name == "dense200" or name in REF_TABLE_RUNGS or name == "smoke"
    )
    result = {
        "metric": (
            f"train_tokens_per_sec_per_chip_{name}"
            if sidecar_rung
            else METRIC
        ),
        "value": round(tps_chip, 1),
        "unit": "tokens/sec/chip",
        "vs_baseline": round(
            tps_chip / REF_BASELINES.get(name, REF_MOE_TOKENS_PER_SEC), 3
        ),
        "extras": {
            "chips": n_chips,
            "platform": platform,
            "config": name,
            "total_params_m": round(cfg.estimate_parameters() / 1e6, 1),
            "active_params_m": round(cfg.estimate_active_parameters() / 1e6, 1),
            "batch": cfg.batch_size,
            "seq": cfg.seq_length,
            "mfu": mfu,
            "model_tflops_per_sec": round(sample["tflops_per_sec"], 2),
            "loss": round(loss_val, 4),
            "moe_drop_rate": round(drop_val, 4),
            "moe_drop_rate_steady": drop_steady,
            "step_ms": round(dt / steps * 1e3, 2),
            "compile_s": round(compile_s, 1),
            "compiled_cost": compiled_cost,
            "donation_audit": donation,
            "telemetry": registry.snapshot(),
        },
    }
    if name == "smoke":
        ex = result["extras"]
        ex["decode_compiled_cost"] = _smoke_decode_cost(
            cfg, model, state.params, registry
        )
        # Dropless-gmm evidence (CPU-provable): XLA's own cost model on
        # the flagship-SHAPED train executable, einsum capacity dispatch
        # vs tile-padded gmm — the padding + one-hot dispatch FLOPs must
        # be GONE (>= 10% of the step's compiled FLOPs at cf 1.25).
        # Budget-guarded like the compiled-cost block above: two
        # flagship-shaped AOT compiles are the heaviest part of the
        # smoke run and must degrade, not kill, a tight child.
        if not budget or time.perf_counter() - child_t0 < 0.6 * budget:
            ex["moe_dispatch_flops"] = _smoke_dispatch_flops(registry)
        else:
            ex["moe_dispatch_flops"] = {
                "available": False,
                "reason": "child budget exhausted before dispatch A/B",
            }
        # Static recompile surface (ROADMAP item 5's baseline number):
        # distinct abstract step signatures per program, enumerated
        # without executing anything (analysis/jaxpr_audit.py). Budget-
        # guarded like the A/B above — the enumeration traces 8 step
        # variants and must degrade, not kill, a tight child.
        if not budget or time.perf_counter() - child_t0 < 0.75 * budget:
            ex["recompile_surface"] = _smoke_recompile_surface(registry)
        else:
            ex["recompile_surface"] = {
                "available": False,
                "reason": "child budget exhausted before surface audit",
            }
        # Cross-host expert dispatch (ROADMAP item 3): the comms
        # auditor's a2a-vs-replicated-gather DCN byte comparison on a
        # simulated dcn2 x ici4 mesh (subprocess with 8 virtual CPU
        # devices — this child runs single-device). CI asserts the a2a
        # path's dcn-crossing payload bytes strictly below the
        # replicated gather's (docs/parallelism.md "Expert
        # parallelism"). Budget-guarded like the audits above.
        if not budget or time.perf_counter() - child_t0 < 0.8 * budget:
            ex["ep_dispatch"] = _smoke_ep_dispatch()
        else:
            ex["ep_dispatch"] = {
                "available": False,
                "reason": "child budget exhausted before ep-dispatch audit",
            }
        # Hierarchical gradient reduction (ROADMAP item 3's other
        # cross-host hot path): the comms auditor's hierarchical-vs-flat
        # DCN byte comparison for the fsdp/dp gradient sync on the same
        # simulated dcn2 mesh (subprocess with 8 virtual CPU devices).
        # CI asserts the hierarchical sync's DCN-crossing bytes strictly
        # below the flat GSPMD baseline's (docs/parallelism.md
        # "Hierarchical gradient reduction"). Budget-guarded as above.
        if not budget or time.perf_counter() - child_t0 < 0.85 * budget:
            ex["grad_reduce"] = _smoke_grad_reduce()
        else:
            ex["grad_reduce"] = {
                "available": False,
                "reason": "child budget exhausted before grad-reduce audit",
            }
        from luminaai_tpu.training.optimizer import describe_optimizer_memory

        ex["optimizer_memory"] = describe_optimizer_memory(state.opt_state)
        # Router health (docs/observability.md "Router health"): the
        # per-expert load fractions + entropy from the measured window's
        # LAST step — live proof the router-health aux outputs thread
        # through the train step. Loads are normalized kept-token
        # shares, so CI can assert they sum to ~1.0.
        ex["router_health"] = _router_health_extras(metrics)
        # Durable I/O (docs/resilience.md "Durable I/O"): injected
        # flaky-storage save/restore cycle with manifest verification
        # and bitflip detection. Cheap (tiny arrays, no compiles) —
        # no budget guard needed.
        ex["io_resilience"] = _smoke_io_resilience()
        # Resilience surface (docs/resilience.md): a preempt-and-resume
        # cycle must report exact data-state resume; a False here fails
        # the smoke artifact loudly (error field + exit 1).
        resume_check = _smoke_resume_check()
        ex["resumed_exact_data_state"] = resume_check.pop(
            "resumed_exact_data_state"
        )
        # Goodput (docs/observability.md "Goodput & sentinels"): the
        # resumed trainer's wall-clock ledger — productive fraction plus
        # the full cause partition (compile / checkpoint / data_wait /
        # resume_replay / ...), sum == elapsed by construction.
        ex["goodput"] = resume_check.pop("goodput", None) or {
            "available": False,
            "reason": "resume check did not produce a ledger",
        }
        # SLO engine (docs/observability.md "SLOs & burn rate"): the
        # resumed trainer's objective verdicts + ring sample counts —
        # proof the retention/judgment layer rides every train process.
        # Missing verdicts fail the artifact loudly below.
        slo = resume_check.pop("slo", None)
        ex["slo"] = (
            {"available": True, **slo}
            if isinstance(slo, dict) and slo.get("objectives")
            else {
                "available": False,
                "reason": "resume check produced no slo verdicts",
            }
        )
        ex["resume_check"] = resume_check
        ex["bench_gate"] = _gate_verdict(result)
        # Wide-event spine (monitoring/events.py): the bench window
        # emits onto the process flight recorder and the artifact
        # carries the counts by type — the resume check above already
        # drove trainer events (train_step/preemption/recompile)
        # through the same ring, so a zero here means the spine broke.
        from luminaai_tpu.monitoring.events import get_recorder

        _rec = get_recorder()
        _rec.emit(
            "bench_window", config=name, steps=steps, platform=platform,
            tokens_per_sec_per_chip=round(tps_chip, 1),
        )
        ex["events"] = {
            "counts": _rec.counts_by_type(),
            "buffered": len(_rec),
            "dropped": _rec.dropped,
        }
        ex["note"] = (
            "hermetic cpu smoke: attribution + gate + resume surface "
            "check, not a performance claim"
        )
        # Build identity: the smoke artifact's telemetry must carry the
        # build_info gauge like every long-lived process.
        from luminaai_tpu.monitoring.telemetry import register_build_info

        register_build_info(registry, config=cfg)
        # Snapshot again so the decode-cost gauges land in the artifact.
        ex["telemetry"] = registry.snapshot()
        if ex["resumed_exact_data_state"] is not True:
            result["error"] = "resumed_exact_data_state_false"
        elif not ex["slo"].get("available"):
            # The SLO surface is an assertion surface like the resume
            # contract: a smoke artifact without verdicts exits 1.
            result["error"] = "slo_verdicts_missing"
    if name == "ref_debug_moe":
        result["extras"]["note"] = (
            "reference's own headline benchmark config (debug preset dims, "
            "ref BENCHMARKS.md ~59.5k tok/s row): apples-to-apples model "
            "scale for vs_baseline"
        )
    print(json.dumps(result))
    if name == "smoke" and "error" in result:
        # The smoke artifact is an ASSERTION surface (resume contract,
        # telemetry): fail loudly like --smoke-serve does.
        sys.exit(1)


def _pctl(xs, p):
    """Percentile of a small sample (nearest-rank on the sorted list)."""
    if not xs:
        return None
    xs = sorted(xs)
    k = min(len(xs) - 1, max(0, int(round(p / 100.0 * (len(xs) - 1)))))
    return xs[k]


def _serve_run_continuous(sched, prompts, budgets):
    """Drive the ContinuousScheduler with one thread per request via
    submit_stream, timestamping every token for the latency histogram.
    Returns (total_tokens, wall_s, inter_token_gaps_s, ttft_s)."""
    import threading

    results = [None] * len(prompts)

    def worker(i):
        t_s = time.perf_counter()
        stamps = []
        for item in sched.submit_stream(
            prompts[i],
            {
                "max_new_tokens": budgets[i],
                "temperature": 0.0,
                "repetition_penalty": 1.0,
            },
        ):
            if isinstance(item, dict):
                break
            stamps.append(time.perf_counter())
        results[i] = (t_s, stamps)

    threads = [
        threading.Thread(target=worker, args=(i,))
        for i in range(len(prompts))
    ]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    tokens = sum(len(stamps) for _, stamps in results)
    gaps, ttft = [], []
    for t_s, stamps in results:
        if stamps:
            ttft.append(stamps[0] - t_s)
        gaps += [b - a for a, b in zip(stamps, stamps[1:])]
    return tokens, wall, gaps, ttft


def _serve_run_legacy(batcher, prompts, budgets):
    """Same workload through the run-to-completion MicroBatcher path.
    Returns (total_tokens, wall_s)."""
    import threading

    results = [None] * len(prompts)

    def worker(i):
        results[i] = batcher.submit(
            prompts[i],
            {
                "max_new_tokens": budgets[i],
                "temperature": 0.0,
                "repetition_penalty": 1.0,
            },
        )

    threads = [
        threading.Thread(target=worker, args=(i,))
        for i in range(len(prompts))
    ]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    tokens = sum(len(toks) for toks, _ in results)
    return tokens, wall


def _serve_bench_main(smoke: bool) -> None:
    """Serving A/B: continuous batching (slot-paged pool, step-level
    admission) vs the legacy MicroBatcher on a mixed-max_new workload —
    the workload continuous batching exists for (the legacy path can't
    even group mixed lengths into one batch: max_new is part of its
    decode compile key, so the workload shatters into sequential
    run-to-completion batches, while the continuous decode step treats
    max_new as host state and serves everything on one executable).

    Hermetic by contract: forces CPU, tiny random-weight model, stub
    tokenizer, no files read. Prints exactly ONE JSON line; on any
    failure the line carries an "error" field. --smoke-serve is the
    scaled-down CI tier; --serve-bench runs the full 16-request
    {8,64,256} acceptance workload.
    """
    result = {
        "metric": "serve_tokens_per_sec_continuous",
        "value": 0.0,
        "unit": "tokens/sec",
        "vs_baseline": 0.0,
    }
    try:
        os.environ["JAX_PLATFORMS"] = "cpu"
        import jax

        jax.config.update("jax_platforms", "cpu")
        import jax.numpy as jnp
        import numpy as np
        from flax import linen as nn

        from luminaai_tpu.config import Config
        from luminaai_tpu.inference.generate import GenerationEngine
        from luminaai_tpu.models.transformer import LuminaTransformer
        from luminaai_tpu.monitoring.telemetry import MetricsRegistry
        from luminaai_tpu.serving.server import (
            ContinuousScheduler,
            MicroBatcher,
        )

        class _Tok:  # minimal engine contract; no tokenizer data needed
            eos_token_id = 1
            pad_token_id = 0
            im_end = 2

            class backend:
                @staticmethod
                def encode(text):
                    return [3 + (ord(c) % 200) for c in text]

            @staticmethod
            def decode(tokens):
                return " ".join(str(t) for t in tokens)

        cfg = Config(
            vocab_size=512,
            hidden_size=64 if smoke else 128,
            num_layers=2,
            num_heads=4,
            num_kv_heads=2,
            seq_length=512,
            use_flash_attention=False,
            precision="fp32",
            gradient_checkpointing=False,
            max_new_tokens=32,
        )
        model = LuminaTransformer(cfg)
        params = model.init(
            jax.random.key(0), jnp.ones((1, 8), jnp.int32)
        )["params"]
        params = jax.tree.map(
            lambda x: x.unbox() if isinstance(x, nn.meta.AxisMetadata) else x,
            params,
            is_leaf=lambda x: isinstance(x, nn.meta.AxisMetadata),
        )
        engine = GenerationEngine(model, params, _Tok(), cfg)

        n_req = 8 if smoke else 16
        budget_cycle = [4, 8, 24] if smoke else [8, 64, 256]
        budgets = [budget_cycle[i % len(budget_cycle)] for i in range(n_req)]
        rs = np.random.RandomState(0)
        prompts = [
            rs.randint(3, cfg.vocab_size, size=int(rs.randint(4, 24))).tolist()
            for _ in range(n_req)
        ]
        num_slots = 4 if smoke else 8
        # Dedicated registry so the embedded snapshot holds ONLY this
        # bench's serving metrics (not whatever else the process did).
        serve_registry = MetricsRegistry()
        sched = ContinuousScheduler(
            engine, num_slots=num_slots, page_size=64,
            registry=serve_registry,
        )
        legacy = MicroBatcher(engine, max_batch=num_slots, window_ms=100.0)

        # Warmup pass = compiles (both paths share the engine's caches
        # where keys overlap); the measured pass is steady-state.
        _serve_run_continuous(sched, prompts, budgets)
        _serve_run_legacy(legacy, prompts, budgets)
        c_tokens, c_wall, gaps, ttft = _serve_run_continuous(
            sched, prompts, budgets
        )
        l_tokens, l_wall = _serve_run_legacy(legacy, prompts, budgets)

        cont_tps = c_tokens / max(c_wall, 1e-9)
        leg_tps = l_tokens / max(l_wall, 1e-9)

        # -- ragged paged-attention compiled-cost comparison ----------
        # AOT-compile the decode step under the dense full-extent mask
        # and under the ragged (LaneMeta) backend at realistic
        # residency — 8 slots holding short prompts inside a deep pool —
        # and read XLA's own cost model. The ragged path must access
        # strictly fewer bytes: that is the "decode cost scales with
        # tokens resident, not pool capacity" claim, priced by the
        # compiler rather than asserted by prose (arxiv 2604.15464).
        import dataclasses as _dc

        from luminaai_tpu.monitoring.attribution import (
            compiled_cost_metrics,
        )

        def _decode_cost(backend):
            bcfg = _dc.replace(cfg, attention_backend=backend)
            beng = GenerationEngine(model, params, _Tok(), bcfg)
            dec = beng.make_stepwise(num_slots=8, page_size=64)
            # Fill the pool, not more: the full tier's 16-request
            # workload would exhaust the 8 slots on the 9th alloc.
            for p, b in list(zip(prompts, budgets))[:8]:
                dec.prefill_into_slot(
                    dec.acquire_slot(), p, max_new_tokens=b, seed=0
                )
            fn, args = dec.step_fn_and_args()
            cm = compiled_cost_metrics(
                fn, *args, program=f"decode_{backend}",
                registry=serve_registry,
            )
            return cm, dec

        dense_cost, _ = _decode_cost("dense")
        ragged_cost, rdec = _decode_cost("ragged_xla")

        def _bytes(cm):
            cost = cm.get("cost_model") or {}
            if cost.get("bytes_accessed"):
                return float(cost["bytes_accessed"])
            return float((cm.get("memory") or {}).get("temp_bytes") or 0)

        d_bytes, r_bytes = _bytes(dense_cost), _bytes(ragged_cost)
        ragged_attention = {
            "backend": "ragged_xla",
            "num_slots": 8,
            "page_size": 64,
            "slot_tokens": rdec.slot_tokens,
            "resident_extent_rows": rdec._active_extent(),
            "dense": dense_cost.get("cost_model"),
            "ragged": ragged_cost.get("cost_model"),
            "dense_bytes_accessed": d_bytes,
            "ragged_bytes_accessed": r_bytes,
            "bytes_ratio": (
                round(r_bytes / d_bytes, 4) if d_bytes else None
            ),
        }
        if not (
            dense_cost.get("available") and ragged_cost.get("available")
        ):
            ragged_attention["note"] = "cost model unavailable"
            result["error"] = "ragged_attention_cost_model_unavailable"
        elif not (0 < r_bytes < d_bytes):
            # The whole point of the ragged backend: fail the artifact
            # loudly if the compiled decode step stopped reading fewer
            # bytes than the dense-mask baseline.
            result["error"] = "ragged_bytes_not_below_dense"

        # -- shared-prefix prefix-cache tier --------------------------
        # The ROADMAP-item-2 claim, measured: 8 requests sharing a
        # 192-token prefix (a system-prompt workload) through the
        # scheduler with the radix prefix cache ON vs OFF. The cached
        # run must (a) skip >= 0.5x of total prompt tokens via cached-
        # page splices and (b) spend strictly less summed prefill time
        # than the cache-off baseline — CI asserts both from
        # extras.prefix_cache (docs/serving.md "Prefix cache").
        import threading as _threading

        rs2 = np.random.RandomState(7)
        shared_prefix = rs2.randint(3, cfg.vocab_size, size=192).tolist()
        prefix_reqs = [
            shared_prefix
            + rs2.randint(3, cfg.vocab_size, size=12).tolist()
            for _ in range(8)
        ]
        prefix_greedy = {
            "max_new_tokens": 4, "temperature": 0.0,
            "repetition_penalty": 1.0,
        }

        def _prefix_tier(cache_pages):
            reg = MetricsRegistry()
            tier_sched = ContinuousScheduler(
                GenerationEngine(model, params, _Tok(), cfg),
                num_slots=num_slots, page_size=64, registry=reg,
                prefix_cache_pages=cache_pages,
            )
            # Warm admission: the shared prefix's FIRST use pays the
            # cold prefill (and, cache on, harvests its pages) AND all
            # executable compiles (chunk prefill, harvest copy, decode
            # step). Its prefill seconds are subtracted below so the
            # measured window prices steady-state prefill work, not
            # XLA compilation.
            tier_sched.submit(list(prefix_reqs[0]), dict(prefix_greedy))
            warm_hist = reg.snapshot().get("serve_prefill_seconds") or {}
            warm_s = float(warm_hist.get("sum") or 0.0)
            ths = [
                _threading.Thread(
                    target=tier_sched.submit,
                    args=(list(p), dict(prefix_greedy)),
                )
                for p in prefix_reqs
            ]
            for t in ths:
                t.start()
            for t in ths:
                t.join()
            hist = reg.snapshot().get("serve_prefill_seconds") or {}
            cache = getattr(tier_sched.decoder, "prefix_cache", None)
            return (
                max(0.0, float(hist.get("sum") or 0.0) - warm_s),
                cache.stats() if cache is not None else None,
            )

        cold_prefill_s, _ = _prefix_tier(0)
        cached_prefill_s, pc_stats = _prefix_tier(24)
        prompt_tokens_total = sum(len(p) for p in prefix_reqs) + len(
            prefix_reqs[0]
        )
        saved = int((pc_stats or {}).get("tokens_saved", 0))
        prefix_cache = {
            "requests": len(prefix_reqs) + 1,
            "prefix_tokens": len(shared_prefix),
            "prompt_tokens_total": prompt_tokens_total,
            "hit_rate": (pc_stats or {}).get("hit_rate", 0.0),
            "hits": (pc_stats or {}).get("hits", 0),
            "misses": (pc_stats or {}).get("misses", 0),
            "pages_shared": (pc_stats or {}).get("pages_spliced", 0),
            "pages_cached": (pc_stats or {}).get("pages_cached", 0),
            "prefill_tokens_saved": saved,
            "prefill_seconds_cached": round(cached_prefill_s, 4),
            "prefill_seconds_cold": round(cold_prefill_s, 4),
            "prefill_seconds_ratio": (
                round(cached_prefill_s / cold_prefill_s, 4)
                if cold_prefill_s
                else None
            ),
        }
        if "error" not in result:
            if saved < 0.5 * prompt_tokens_total:
                result["error"] = "prefix_cache_tokens_saved_below_half"
            elif not (0 < cached_prefill_s < cold_prefill_s):
                result["error"] = "prefix_cache_prefill_not_faster"
            elif not prefix_cache["hit_rate"] > 0:
                result["error"] = "prefix_cache_no_hits"

        # -- int8 KV-cache tier (ROADMAP item 4: the serving default) --
        # The documented serving config stores the paged KV pool as int8
        # codes + per-row scales (half the cache HBM, so max concurrent
        # lanes per chip roughly doubles — docs/quantization.md). This
        # tier runs the same greedy workload through the stepwise
        # serving path under kv_cache_dtype='int8' and asserts the
        # serving-path contract: stepwise streams EXACTLY reproduce
        # generate() under the same int8 config (greedy parity — the
        # PR 1 framing, now pinned for the quantized default too).
        import time as _time

        def _kv_tier(kv_dtype):
            kcfg = _dc.replace(cfg, kv_cache_dtype=kv_dtype)
            keng = GenerationEngine(model, params, _Tok(), kcfg)
            kp = prompts[:4]
            kb = [12] * len(kp)
            refs = [
                keng.generate(
                    p, max_new_tokens=b, temperature=0.0, seed=0,
                    repetition_penalty=1.0,
                )[0]
                for p, b in zip(kp, kb)
            ]
            dec = keng.make_stepwise(num_slots=4, page_size=64)
            outs, slots = {}, {}
            t0 = _time.perf_counter()
            for i, (p, b) in enumerate(zip(kp, kb)):
                s = dec.acquire_slot()
                slots[i] = s
                info = dec.prefill_into_slot(
                    s, p, max_new_tokens=b, seed=0
                )
                outs[i] = [] if info["token"] is None else [info["token"]]
            done = {i for i in outs if not dec._active[slots[i]]}
            for _ in range(64):
                if len(done) == len(kp):
                    break
                toks, produced, eos = dec.decode_step()
                for i in set(range(len(kp))) - done:
                    s = slots[i]
                    if eos[s]:
                        done.add(i)
                        dec.release_slot(s)
                    elif produced[s]:
                        outs[i].append(int(toks[s]))
                        if len(outs[i]) >= kb[i]:
                            done.add(i)
                            dec.release_slot(s)
            wall = _time.perf_counter() - t0
            streams = [outs[i] for i in range(len(kp))]
            n_tok = sum(len(s) for s in streams)
            pool_bytes = sum(
                l.nbytes for l in jax.tree_util.tree_leaves(
                    dec.pool.caches
                )
            )
            return streams, refs, n_tok / max(wall, 1e-9), pool_bytes

        i8_streams, i8_refs, i8_tps, i8_bytes = _kv_tier("int8")
        bf_streams, bf_refs, bf_tps, bf_bytes = _kv_tier("bf16")
        kv_int8 = {
            "default_documented": "int8",
            "greedy_parity": bool(i8_streams == i8_refs),
            "bf16_greedy_parity": bool(bf_streams == bf_refs),
            "tokens_per_sec_int8": round(i8_tps, 1),
            "tokens_per_sec_bf16": round(bf_tps, 1),
            "pool_bytes_int8": i8_bytes,
            "pool_bytes_bf16": bf_bytes,
            # codes+scales vs bf16 rows: < 1.0 is the HBM halving claim
            "pool_bytes_ratio": (
                round(i8_bytes / bf_bytes, 4) if bf_bytes else None
            ),
        }
        if "error" not in result:
            if not kv_int8["greedy_parity"]:
                result["error"] = "int8_kv_greedy_parity_broken"
            elif not i8_bytes < bf_bytes:
                result["error"] = "int8_kv_pool_not_smaller"

        # -- SLO engine over the serving registry ----------------------
        # The retention + judgment layer on the series this bench just
        # produced (docs/observability.md "SLOs & burn rate"): ring
        # samples of the serve registry, default serve objectives, one
        # evaluation — verdicts + ring counts ride the artifact and CI
        # asserts they exist with valid states.
        from luminaai_tpu.monitoring.slo import build_slo_stack
        from luminaai_tpu.monitoring.telemetry import register_build_info

        register_build_info(serve_registry, config=cfg)
        slo_ring, slo_engine = build_slo_stack(
            cfg, registry=serve_registry, program="serve",
        )
        for _ in range(3):
            slo_ring.sample_once()  # attached engine evaluates per sample
        slo_extras = {
            "available": True,
            **slo_engine.verdicts(),
            "ring": slo_ring.stats(),
        }
        result.update(
            value=round(cont_tps, 1),
            # Baseline for THIS metric is the legacy micro-batched path
            # on the same workload/hardware: >1.0 means continuous wins.
            vs_baseline=round(cont_tps / max(leg_tps, 1e-9), 3),
            extras={
                "platform": jax.devices()[0].platform,
                "mode": "smoke" if smoke else "full",
                "requests": n_req,
                "max_new_mix": budget_cycle,
                "num_slots": num_slots,
                "page_size": 64,
                "tokens_continuous": c_tokens,
                "tokens_legacy": l_tokens,
                "legacy_tokens_per_sec": round(leg_tps, 1),
                "speedup_vs_microbatch": round(
                    cont_tps / max(leg_tps, 1e-9), 3
                ),
                "latency_ms_per_token": {
                    "p50": round(1e3 * _pctl(gaps, 50), 2) if gaps else None,
                    "p95": round(1e3 * _pctl(gaps, 95), 2) if gaps else None,
                },
                "ttft_ms": {
                    "p50": round(1e3 * _pctl(ttft, 50), 2) if ttft else None,
                    "p95": round(1e3 * _pctl(ttft, 95), 2) if ttft else None,
                },
                "decode_steps": int(sched.decoder.steps),
                "slot_reuses": int(sched.decoder.pool.reuses),
                "prefill_chunk_tokens": int(
                    getattr(sched.decoder, "prefill_chunk", 0)
                ),
                # Compiled FLOPs/bytes: dense-mask vs ragged decode step
                # (CI asserts ragged reads strictly fewer bytes).
                "ragged_attention": ragged_attention,
                # Shared-prefix A/B: radix prefix cache on vs off (CI
                # asserts hit_rate > 0, tokens_saved >= 0.5x prompt
                # tokens, and strictly lower summed prefill seconds).
                "prefix_cache": prefix_cache,
                # int8 KV serving tier (the documented default config):
                # stepwise==generate greedy parity under int8 + the
                # pool-bytes halving (CI asserts both).
                "kv_int8": kv_int8,
                # SLO verdicts + ring sample counts over this bench's
                # own serving series (CI asserts presence/states).
                "slo": slo_extras,
                # Registry snapshot: TTFT / per-token / queue-wait
                # histograms and KV-pool occupancy, embedded so the
                # serving perf claim carries its own telemetry
                # provenance. NOTE: spans warmup + measured passes —
                # compile-time observations inflate its p95/p99, so
                # latency_ms_per_token/ttft_ms above (measured pass
                # only) stay the headline latency figures.
                "telemetry": serve_registry.snapshot(),
                "telemetry_passes": "warmup+measured",
            },
        )
    except Exception as e:  # the artifact must stay parseable
        result["error"] = f"{type(e).__name__}: {e}"
    if "error" not in result and not result.get("extras", {}).get("telemetry"):
        # The snapshot is part of the artifact contract now: a missing
        # one means the scheduler ran uninstrumented — fail loudly
        # rather than quietly shipping an unverifiable number.
        result["error"] = "telemetry_snapshot_missing"
    if "error" not in result and not (
        result.get("extras", {}).get("slo", {}).get("objectives")
    ):
        # Same contract for the SLO surface: a serve artifact without
        # objective verdicts means the retention/judgment layer broke.
        result["error"] = "slo_verdicts_missing"
    print(json.dumps(result), flush=True)
    if "error" in result:
        sys.exit(1)


def _page_share_extras(smoke: bool) -> dict:
    """extras.page_share for the router bench (ISSUE 20): a shared-
    prefix workload priced cache-on vs cache-off — replica B pulls
    replica A's harvested pages through the real PageShareClient fetch
    path (loopback seams, no sockets) and every later admission rides
    the splice. Reports the cross-replica hit rate and summed prefill
    seconds both ways. Unlike the routing rungs this one needs jax (a
    real tiny model on CPU): the quantity measured is admission-side
    prefill compute actually avoided, which a synthetic engine cannot
    exhibit."""
    try:
        import jax
        import jax.numpy as jnp
        from flax import linen as nn

        from luminaai_tpu.config import Config
        from luminaai_tpu.data.tokenizer import ConversationTokenizer
        from luminaai_tpu.inference.generate import GenerationEngine
        from luminaai_tpu.models.transformer import LuminaTransformer
        from luminaai_tpu.serving.page_share import PageShareClient

        tok = ConversationTokenizer()
        cfg = Config(
            vocab_size=tok.vocab_size, hidden_size=64, num_layers=2,
            num_heads=1, num_kv_heads=1, seq_length=256,
            use_flash_attention=False, precision="fp32",
            gradient_checkpointing=False, max_new_tokens=4,
            prefill_chunk_size=32, attention_backend="ragged_xla",
        )
        model = LuminaTransformer(cfg)
        params = model.init(
            jax.random.key(0), jnp.ones((1, 8), jnp.int32)
        )["params"]
        params = jax.tree.map(
            lambda x: (
                x.unbox() if isinstance(x, nn.meta.AxisMetadata) else x
            ),
            params, is_leaf=lambda x: isinstance(x, nn.meta.AxisMetadata),
        )
        engine = GenerationEngine(model, params, tok, cfg)

        def mk(cache):
            kw = dict(num_slots=2, page_size=32, max_slot_tokens=192)
            if cache:
                kw["prefix_cache_pages"] = 6
            return engine.make_stepwise(**kw)

        class _Loopback(PageShareClient):
            """Router + owner conversations short-circuited onto the
            in-process owner decoder; fetch_page stays the real code."""

            def __init__(self, owner):
                super().__init__(
                    "http://router:0", self_url="http://b:1",
                    timeout_s=10.0,
                )
                self.owner = owner

            def lookup(self, keys, have=0):
                idx = self.owner.prefix_cache._index
                owned = []
                for k in keys:
                    if k not in idx:
                        break
                    owned.append(k)
                if len(owned) <= have:
                    return None, []
                return "http://a:0", owned

            def get_bytes(self, base_url, path, timeout_s=None):
                key = path.rsplit("/", 1)[1]
                pid = self.owner.prefix_cache.pin_key(key)
                if pid is None:
                    return 404, b""
                try:
                    if pid in self.owner._queued_dst:
                        return 404, b""
                    return 200, self.owner.pool.export_page(pid)
                finally:
                    self.owner.prefix_cache.release([pid])

        shared = tok.encode_text(
            "the quick brown fox jumps over the lazy dog " * 3
        )[:96]
        n = 3 if smoke else 8
        prompts = [
            shared + tok.encode_text(f"suffix {i}") for i in range(n)
        ]
        warm = tok.encode_text("warmup pass only " * 8)[:80]

        def admit(dec, prompt):
            s = dec.acquire_slot()
            t0 = time.perf_counter()
            st = dec.start_prefill(s, prompt, max_new_tokens=1)
            info = None
            while info is None:
                info = dec.advance_prefill(st)
            dt = time.perf_counter() - t0
            dec.release_slot(s)
            return dt, info

        # One warm admission per decoder: compile outside the clock.
        dec_off = mk(cache=False)
        admit(dec_off, warm)
        off_s = sum(admit(dec_off, p)[0] for p in prompts)

        dec_a = mk(cache=True)
        admit(dec_a, warm)
        admit(dec_a, prompts[0])  # A computes + harvests the prefix
        dec_a.flush_harvests()
        dec_b = mk(cache=True)
        admit(dec_b, warm)
        dec_b.page_share = _Loopback(dec_a)
        on_s, hits, saved = 0.0, 0, 0
        for p in prompts:
            dt, info = admit(dec_b, p)
            on_s += dt
            pages = int(info["prefix"]["hit_pages"])
            if pages:
                hits += 1
            saved += pages * 32
        tokens_off = sum(len(p) for p in prompts)
        return {
            "requests": n,
            "cross_replica_hit_rate": round(hits / n, 3),
            "remote_hit_admissions": dec_b.remote_hits,
            "pull_failures": dec_b.remote_pull_failures,
            "prefill_seconds_cache_on": round(on_s, 4),
            "prefill_seconds_cache_off": round(off_s, 4),
            # Wall seconds on a toy CPU model undersell the win (the
            # pull roundtrip is fixed cost, prefill compute is ~free);
            # token counts carry the compute actually avoided.
            "prefill_tokens_cache_on": tokens_off - saved,
            "prefill_tokens_cache_off": tokens_off,
        }
    except Exception as e:  # nested: the routing rungs stand on their own
        return {"error": f"{type(e).__name__}: {e}"}


def _router_bench_main(smoke: bool) -> None:
    """Serving-plane router bench: a 2-replica local fleet behind the
    data-plane router (serving/router.py), then the kill-one-replica
    rung. Headline: aggregate tokens/sec through the router;
    vs_baseline: the same workload driven at ONE replica directly (so
    >1 means the 2-replica fan-out pays for the router hop).
    extras.router carries the robustness rung CI asserts: failovers>0,
    post-kill success rate 1.0, breaker_opened true.

    Hermetic by contract: synthetic engine (no jax, no checkpoint — the
    router is pure host Python and the rung measures routing, not
    decode), loopback sockets only, exactly ONE JSON line; any failure
    rides an "error" field and exits 1.
    """
    result = {
        "metric": "router_tokens_per_sec_2replica",
        "value": 0.0,
        "unit": "tokens/sec",
        "vs_baseline": 0.0,
    }
    try:
        import threading
        import types
        import urllib.error
        import urllib.request
        from http.server import ThreadingHTTPServer

        from luminaai_tpu.config import Config
        from luminaai_tpu.monitoring.events import FlightRecorder
        from luminaai_tpu.monitoring.telemetry import MetricsRegistry
        from luminaai_tpu.serving.router import Router
        from luminaai_tpu.serving.server import ChatServer
        from luminaai_tpu.testing.faults import kill_replica

        class _Backend:
            def encode(self, text):
                return [ord(c) % 250 for c in text]

        class _Tok:
            backend = _Backend()

            def decode(self, tokens):
                return "tok:" + ",".join(str(t) for t in tokens)

        class _Eng:
            """Minimal engine contract (mirrors GenerationEngine's
            surface the way tests/test_serving.py's double does) with a
            fixed per-token pace, so tokens/sec measures the routing
            plane, not model arithmetic."""

            TICK_S = 0.0005

            def __init__(self):
                self.config = Config(
                    vocab_size=256, hidden_size=64, num_layers=2,
                    num_heads=4, num_kv_heads=2, seq_length=64,
                    use_flash_attention=False,
                )
                self.tokenizer = _Tok()

            def generate(self, prompt_tokens, max_new_tokens=16, **kw):
                n = max(1, min(int(max_new_tokens), 64))
                time.sleep(self.TICK_S * n)
                toks = [t % 250 for t in list(prompt_tokens)[:n]] or [1]
                return toks, {"tokens_generated": len(toks),
                              "stopped": "eos"}

            def generate_batch(self, prompts, **kw):
                return [self.generate(p, **kw) for p in prompts]

            def encode_chat(self, messages):
                return self.tokenizer.backend.encode(
                    messages[-1]["content"]
                )

        def _spawn_replica():
            srv = ChatServer(_Eng(), registry=MetricsRegistry())
            httpd = ThreadingHTTPServer(
                ("127.0.0.1", 0), srv.make_handler()
            )
            threading.Thread(
                target=httpd.serve_forever, daemon=True
            ).start()
            url = f"http://127.0.0.1:{httpd.server_address[1]}"
            return types.SimpleNamespace(server=srv, httpd=httpd, url=url)

        replicas = [_spawn_replica(), _spawn_replica()]
        recorder = FlightRecorder(capacity=4096)
        registry = MetricsRegistry()
        router = Router(
            [("r0", replicas[0].url), ("r1", replicas[1].url)],
            registry=registry, recorder=recorder,
            probe_interval_s=0.2, breaker_failures=3,
            breaker_cooldown_s=1.0, max_failovers=1,
        )
        router.probe_all()
        httpd_r = ThreadingHTTPServer(
            ("127.0.0.1", 0), router.make_handler()
        )
        threading.Thread(
            target=httpd_r.serve_forever, daemon=True
        ).start()
        router_url = f"http://127.0.0.1:{httpd_r.server_address[1]}"

        prompts = [
            "system alpha: summarize the day",
            "system beta: write a haiku now",
            "system gamma: translate to french",
            "system delta: count to twenty",
        ]

        def drive(base, n, out, offset=0):
            for i in range(n):
                body = {
                    "prompt": prompts[(offset + i) % len(prompts)],
                    "max_new_tokens": 16,
                }
                req = urllib.request.Request(
                    base + "/v1/generate",
                    data=json.dumps(body).encode(),
                    headers={"Content-Type": "application/json"},
                )
                try:
                    with urllib.request.urlopen(req, timeout=30) as r:
                        out.append((r.status, json.loads(r.read())))
                except urllib.error.HTTPError as e:
                    out.append((e.code, {}))
                except Exception as e:  # transport-level failure
                    out.append((0, {"error": str(e)}))

        per_client = 6 if smoke else 24
        n_clients = 4

        # -- baseline: one replica, driven directly --------------------
        base_out: list = []
        ts = [threading.Thread(target=drive,
                               args=(replicas[0].url, per_client,
                                     base_out, c))
              for c in range(n_clients)]
        t0 = time.perf_counter()
        [t.start() for t in ts]
        [t.join() for t in ts]
        base_dt = time.perf_counter() - t0
        base_tokens = sum(p.get("tokens", 0) for _, p in base_out)
        base_tps = base_tokens / max(base_dt, 1e-9)

        # -- measured: the same workload through the router ------------
        routed_out: list = []
        ts = [threading.Thread(target=drive,
                               args=(router_url, per_client,
                                     routed_out, c))
              for c in range(n_clients)]
        t0 = time.perf_counter()
        [t.start() for t in ts]
        [t.join() for t in ts]
        routed_dt = time.perf_counter() - t0
        routed_tokens = sum(p.get("tokens", 0) for _, p in routed_out)
        routed_tps = routed_tokens / max(routed_dt, 1e-9)
        n_routed = len(routed_out)
        routed_ok = sum(1 for c, _ in routed_out if c == 200)
        share = {
            r.name: round(r.requests / max(1, sum(
                x.requests for x in router.replicas
            )), 3)
            for r in router.replicas
        }

        # -- kill-one-replica rung -------------------------------------
        kill_replica(replicas[1])
        post_out: list = []
        drive(router_url, 4 if smoke else 8, post_out)  # organic failover
        router.probe_all()  # dead endpoint -> breaker trips
        drive(router_url, 4 if smoke else 8, post_out, offset=2)
        failovers = len(recorder.snapshot(type="router_failover"))
        breaker_opened = bool(recorder.snapshot(type="breaker_open"))
        post_ok = sum(1 for c, _ in post_out if c == 200)
        post_rate = post_ok / max(1, len(post_out))

        httpd_r.shutdown()
        httpd_r.server_close()
        for rep in replicas[:1]:
            rep.httpd.shutdown()
            rep.httpd.server_close()

        result.update(
            value=round(routed_tps, 1),
            vs_baseline=round(routed_tps / max(base_tps, 1e-9), 3),
            extras={
                "mode": "smoke" if smoke else "full",
                "requests": n_routed,
                "direct_tokens_per_sec": round(base_tps, 1),
                "router": {
                    "replicas": 2,
                    "routed_ok": routed_ok,
                    "routed_requests": n_routed,
                    "per_replica_share": share,
                    "failovers": failovers,
                    "post_kill_requests": len(post_out),
                    "post_kill_success_rate": round(post_rate, 3),
                    "breaker_opened": breaker_opened,
                    "breaker_states": {
                        r.name: r.breaker.state
                        for r in router.replicas
                    },
                },
                "page_share": _page_share_extras(smoke),
            },
        )
        if routed_ok != n_routed:
            result["error"] = (
                f"routed phase lost requests: {routed_ok}/{n_routed}"
            )
        elif failovers < 1:
            result["error"] = "kill rung produced zero failovers"
        elif post_rate != 1.0:
            result["error"] = (
                f"post-kill success rate {post_rate} != 1.0"
            )
        elif not breaker_opened:
            result["error"] = "breaker never opened after replica kill"
    except Exception as e:  # the artifact must stay parseable
        result["error"] = f"{type(e).__name__}: {e}"
    print(json.dumps(result), flush=True)
    if "error" in result:
        sys.exit(1)


_HERE = os.path.dirname(os.path.abspath(__file__))
def _probe_backend(timeout: int = 300):
    """Ask ONE throwaway child which platform jax's default backend is
    (the parent stays off jax so each rung's child gets the chip).
    Returns (platform | None, diag_str); no retry, no wait."""
    code = "import jax; print(jax.devices()[0].platform)"
    try:
        proc = subprocess.run(
            [sys.executable, "-c", code],
            capture_output=True, text=True, timeout=timeout, cwd=_HERE,
        )
    except subprocess.TimeoutExpired:
        return None, f"backend_probe=timeout({timeout}s)"
    if proc.returncode == 0 and proc.stdout.split():
        platform = proc.stdout.split()[-1]
        return platform, f"backend_probe={platform}"
    err_lines = (proc.stderr or "").strip().splitlines()
    last_err = err_lines[-1][-160:] if err_lines else f"rc={proc.returncode}"
    return None, f"backend_probe=failed({last_err})"


def _run_child(name: str, timeout: int):
    """Run one ladder rung; returns (parsed_json | None, diagnostic_str)."""
    from bench_common import run_child

    env = dict(os.environ, BENCH_CHILD_BUDGET_S=str(timeout))
    return run_child(
        [sys.executable, os.path.abspath(__file__), "--child", name],
        timeout,
        validate=lambda p: str(p.get("metric", "")).startswith(
            "train_tokens_per_sec_per_chip"
        ),
        label=name,
        env=env,
        cwd=os.path.dirname(os.path.abspath(__file__)),
    )


def _gate_verdict(result: dict) -> dict:
    """Regression-gate verdict for a fresh measurement against the
    committed BENCH_r*.json trajectory (scripts/bench_gate.py). Embedded
    in extras so every artifact states whether it regressed; never
    allowed to cost the artifact itself."""
    try:
        import importlib.util

        spec = importlib.util.spec_from_file_location(
            "bench_gate", os.path.join(_HERE, "scripts", "bench_gate.py")
        )
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.gate(result, mod.load_trajectory(_HERE))
    except Exception as e:
        return {"verdict": "error", "reason": f"{type(e).__name__}: {e}"}


def _router_health_extras(metrics) -> dict:
    """MoE router-health summary from one train step's metrics dict
    (--smoke only): normalized per-expert load (sums to ~1.0), routing
    entropy, max-expert share. Degrades to available=False on dense
    configs or missing aux outputs."""
    import numpy as np

    util = metrics.get("expert_utilization")
    if util is None:
        return {"available": False, "reason": "no expert_utilization"}
    try:
        util = np.asarray(util, dtype=np.float64)
        total = float(util.sum())
        if not np.isfinite(total) or total <= 0:
            return {"available": False, "reason": f"bad load sum {total}"}
        load = util / total

        def scalar(key):
            v = metrics.get(key)
            if v is None:
                return None
            f = float(v)
            return round(f, 4) if np.isfinite(f) else None

        return {
            "available": True,
            "expert_load": [round(float(x), 4) for x in load],
            "load_sum": round(float(load.sum()), 4),
            "router_entropy": scalar("moe_router_entropy"),
            "max_expert_share": scalar("moe_max_expert_share"),
            "drop_rate": scalar("moe_drop_rate"),
        }
    except Exception as e:
        return {"available": False, "reason": f"{type(e).__name__}: {e}"}


def _smoke_resume_check() -> dict:
    """Preempt-and-resume cycle on a tiny CPU trainer (--smoke only):
    train, inject a preemption at step 3 (blocking emergency save + data
    cursor), resume in a FRESH trainer, finish. The artifact must report
    resumed_exact_data_state: true — the exact-resume contract
    (docs/resilience.md) exercised on every smoke run, no hardware
    needed. Self-contained and non-fatal to the measurement (the caller
    flags the artifact when the check fails)."""
    tmp = None
    try:
        import tempfile

        import numpy as np

        from luminaai_tpu.config import Config
        from luminaai_tpu.data.dataset import PrefetchLoader
        from luminaai_tpu.testing.faults import preempt_at_step
        from luminaai_tpu.training.trainer import Trainer

        tmp = tempfile.mkdtemp(prefix="bench_smoke_resume_")

        def cfg(max_steps):
            return Config(
                vocab_size=128, hidden_size=32, num_layers=1, num_heads=2,
                num_kv_heads=1, seq_length=32, batch_size=4,
                use_moe=False, use_flash_attention=False,
                gradient_checkpointing=False, precision="fp32",
                max_steps=max_steps, eval_every_n_batches=10**6,
                # log_every = interval//10 = 1: every step emits a
                # train_step event, so extras.events proves the spine.
                save_every_n_batches=10**6, health_check_interval=10,
                output_dir=tmp, learning_rate=1e-3,
            )

        def loader():
            def gen(epoch=0):
                rng = np.random.RandomState(epoch)
                for _ in range(50):
                    yield {
                        "input_ids": rng.randint(
                            1, 100, size=(4, 32)
                        ).astype(np.int32)
                    }

            return PrefetchLoader(gen, prefetch=2)

        ckpt = tmp + "/ckpt"
        t1 = Trainer(cfg(6), train_data=loader(), checkpoint_dir=ckpt)
        with preempt_at_step(t1, 3):
            s1 = t1.train()
        t1.close()
        t2 = Trainer(cfg(6), train_data=loader(), checkpoint_dir=ckpt)
        resumed_at = t2.global_step
        s2 = t2.train()
        t2.close()
        return {
            "resumed_exact_data_state": bool(
                s1.get("preempted")
                and resumed_at == s1.get("final_step")
                and s2.get("resumed_exact_data_state")
            ),
            "preempted_at": s1.get("final_step"),
            "resumed_at": resumed_at,
            "final_step": s2.get("final_step"),
            # Goodput ledger snapshot from the RESUMED run — the cycle
            # that exercises every cause that needs a fault to appear:
            # checkpoint restore, resume replay, emergency save
            # (docs/observability.md "Goodput & sentinels"). Lifted into
            # extras.goodput; CI asserts fraction in (0, 1] and the
            # cause partition complete.
            "goodput": s2.get("goodput"),
            # SLO engine verdicts + ring sample counts from the resumed
            # trainer (docs/observability.md "SLOs & burn rate"). Lifted
            # into extras.slo; CI asserts verdicts present with valid
            # states and the ring actually sampled.
            "slo": s2.get("slo"),
        }
    except Exception as e:  # the artifact must stay parseable
        return {
            "resumed_exact_data_state": False,
            "reason": f"{type(e).__name__}: {e}",
        }
    finally:
        if tmp:
            import shutil

            shutil.rmtree(tmp, ignore_errors=True)


def _smoke_io_resilience() -> dict:
    """Durable-I/O surface (--smoke only, docs/resilience.md "Durable
    I/O"): an injected flaky-storage save/restore cycle must complete
    with retries visible in io_retries_total, the committed step must
    carry a verifying sha256 manifest, and a bitflipped byte in the
    saved state must be DETECTED at restore (manifest mismatch) — the
    silent-corruption case orbax restores without complaint. CI asserts
    available + retried + manifest_verified + corruption_detected."""
    tmp = None
    try:
        import tempfile

        import numpy as np

        from luminaai_tpu.config import Config
        from luminaai_tpu.monitoring.telemetry import MetricsRegistry
        from luminaai_tpu.testing.faults import (
            bitflip_checkpoint,
            flaky_storage,
        )
        from luminaai_tpu.training.checkpoint import (
            CheckpointIntegrityError,
            CheckpointManager,
            verify_step_dir,
        )

        tmp = tempfile.mkdtemp(prefix="bench_smoke_io_")

        class _S:
            def __init__(self, **kw):
                self.__dict__.update(kw)

            def replace(self, **kw):
                d = dict(self.__dict__)
                d.update(kw)
                return _S(**d)

        def state(v):
            return _S(
                params={"w": np.arange(4096, dtype=np.float32) + v},
                opt_state={"m": np.zeros(8, np.float32)},
                step=np.asarray(int(v)),
                rng=np.zeros((2,), np.uint32),
            )

        reg = MetricsRegistry()  # private: retry counts isolated here
        cm = CheckpointManager(Config(), tmp + "/ckpt", registry=reg)
        with flaky_storage(times=2, ops=("checkpoint",)) as stats:
            saved = cm.save(state(1), 1)
            cm.wait()
        retries = reg.get("io_retries_total").labels(
            op="checkpoint_save"
        ).value
        restored = cm.restore(state(0), 1)
        round_trip = bool(
            np.array_equal(restored.params["w"], state(1).params["w"])
        )
        manifest_verified = (
            verify_step_dir(tmp + "/ckpt/1")["status"] == "ok"
        )
        bitflip_checkpoint(tmp + "/ckpt", 1)
        corruption_detected = False
        try:
            cm.restore(state(0), 1)
        except CheckpointIntegrityError:
            corruption_detected = True
        mismatches = reg.get("checkpoint_manifest_mismatch_total").value
        cm.close()
        return {
            "available": True,
            "saved": bool(saved),
            "round_trip": round_trip,
            "injected_faults": stats["raised"],
            "io_retries_total": retries,
            "manifest_verified": manifest_verified,
            "corruption_detected": corruption_detected,
            "manifest_mismatches_total": mismatches,
        }
    except Exception as e:  # the artifact must stay parseable
        return {"available": False, "reason": f"{type(e).__name__}: {e}"}
    finally:
        if tmp:
            import shutil

            shutil.rmtree(tmp, ignore_errors=True)


def _smoke_dispatch_flops(registry=None) -> dict:
    """Compiled-FLOPs A/B on the flagship-SHAPED train step: capacity
    einsum dispatch vs tile-padded dropless gmm, priced by XLA's own cost
    model on a CPU AOT lowering (--smoke only).

    The config keeps every per-layer dimension the padding argument
    depends on — hidden 1024, 8 experts top-2 at capacity 1.25, seq 2048,
    the flagship's vocab and head layout — and cuts only depth (2 layers)
    and batch (2) so the compile fits the smoke budget; the per-layer
    FLOPs fractions being compared are depth/batch-invariant. No buffers
    materialize: the state is abstract (jax.eval_shape) and the step is
    lowered, never run. A >= 10% drop is the acceptance bar: gmm removes
    both the ~cf·k/E−1 padded-slot fraction of the expert matmuls and the
    O(S·E·C) one-hot dispatch/combine einsums."""
    try:
        import dataclasses

        import jax
        import jax.numpy as jnp

        from luminaai_tpu.models.transformer import LuminaTransformer
        from luminaai_tpu.monitoring.attribution import compiled_cost_metrics
        from luminaai_tpu.parallel.mesh import build_mesh
        from luminaai_tpu.parallel.sharding import (
            make_init_fn,
            state_shardings,
        )
        from luminaai_tpu.parallel.train_step import make_train_step
        from luminaai_tpu.training.optimizer import (
            make_optimizer,
            make_schedule,
        )

        from luminaai_tpu.models import moe as moe_mod

        # FLOPs-faithful stand-in for the ragged kernel, for LOWERING
        # only (nothing executes): megablox touches each sorted row once
        # per matmul — out, grad_lhs, grad_rhs are one [rows × H × 2F]
        # pass each. The CPU fallback instead runs a masked DENSE matmul
        # per expert (E× the work — it exists for value parity, not
        # cost), and the real Pallas call is opaque to XLA's cost model;
        # `lhs @ rhs[0]` lowers to exactly the kernel's FLOPs (counting
        # the ≤127-row pad tail, i.e. conservatively) with the matching
        # two-matmul VJP.
        def flops_standin_gmm(lhs, rhs, group_sizes, preferred_element_type,
                              **_):
            del group_sizes
            return (lhs @ rhs[0]).astype(preferred_element_type)

        base = _child_config("flagship", 1)
        flops = {}
        prev_override = moe_mod._GMM_OVERRIDE
        try:
            for mode in ("einsum", "gmm"):
                moe_mod._GMM_OVERRIDE = (
                    flops_standin_gmm if mode == "gmm" else prev_override
                )
                cfg = dataclasses.replace(
                    base,
                    num_layers=2,
                    batch_size=2,
                    micro_batch_size=None,
                    moe_dispatch=mode,
                    use_flash_attention=False,
                    routing_noise_std=0.0,
                )
                model = LuminaTransformer(cfg)
                schedule = make_schedule(cfg, 1000)
                tx = make_optimizer(cfg, 1000, schedule)
                mesh = build_mesh(cfg)
                shardings = state_shardings(cfg, model, tx, mesh)
                abstract_state = jax.eval_shape(
                    make_init_fn(cfg, model, tx), jax.random.key(0)
                )
                step = make_train_step(
                    cfg, model, shardings, mesh, schedule, tx
                )
                batch = {
                    "input_ids": jax.ShapeDtypeStruct(
                        (cfg.batch_size, cfg.seq_length), jnp.int32
                    )
                }
                cc = compiled_cost_metrics(
                    step, abstract_state, batch,
                    program=f"train_{mode}", registry=registry,
                )
                f = (cc.get("cost_model") or {}).get("flops_per_step")
                if not f:
                    return {
                        "available": False,
                        "reason": f"{mode}: no compiled flops "
                        f"({cc.get('reason', 'cost model absent')})",
                    }
                flops[mode] = f
        finally:
            moe_mod._GMM_OVERRIDE = prev_override
        reduction = 1.0 - flops["gmm"] / flops["einsum"]
        return {
            "available": True,
            "config": (
                "flagship-shaped: hidden 1024, 8 experts top-2 cf 1.25, "
                "seq 2048, vocab 32768; 2 layers, batch 2 (per-layer "
                "fractions are depth/batch-invariant)"
            ),
            "note": (
                "gmm lowered with a FLOPs-faithful dense stand-in (one "
                "pass per sorted row, pad tail counted) — the CPU "
                "fallback's masked per-expert form multiplies work by E "
                "and the Pallas call is opaque to the cost model"
            ),
            "einsum_flops_per_step": flops["einsum"],
            "gmm_flops_per_step": flops["gmm"],
            "reduction": round(reduction, 4),
            "meets_10pct_target": bool(reduction >= 0.10),
        }
    except Exception as e:
        return {"available": False, "reason": f"{type(e).__name__}: {e}"}


def _smoke_ep_dispatch() -> dict:
    """Expert-dispatch comms audit for the smoke artifact (--smoke
    only): analysis/jaxpr_audit.audit_ep_dispatch traces the a2a MoE
    layer and the replicated-gather (gmm) baseline on a simulated
    dcn2×ici4 mesh and prices each path's DCN-crossing payload bytes.
    Runs in a SUBPROCESS with 8 virtual CPU devices — the smoke child
    itself is single-device, and the device count is fixed at backend
    init. Abstract traces only; nothing executes in the child either."""
    code = (
        "import json\n"
        "from luminaai_tpu.analysis.jaxpr_audit import audit_ep_dispatch\n"
        "print(json.dumps(audit_ep_dispatch()))\n"
    )
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8"
    )
    try:
        proc = subprocess.run(
            [sys.executable, "-c", code],
            capture_output=True,
            text=True,
            timeout=300,
            env=env,
            cwd=_HERE,
        )
        if proc.returncode != 0:
            err = (proc.stderr or "").strip().splitlines()
            return {
                "available": False,
                "reason": (
                    f"audit subprocess rc={proc.returncode}: "
                    f"{err[-1][-300:] if err else 'no stderr'}"
                ),
            }
        return json.loads(proc.stdout.strip().splitlines()[-1])
    except subprocess.TimeoutExpired:
        return {"available": False, "reason": "audit subprocess timeout"}
    except Exception as e:
        return {"available": False, "reason": f"{type(e).__name__}: {e}"}


def _smoke_grad_reduce() -> dict:
    """Gradient-reduction comms audit for the smoke artifact (--smoke
    only): analysis/jaxpr_audit.audit_grad_reduce traces the train step
    under grad_reduce flat vs hierarchical (grad accumulation off AND
    on) on a simulated dcn2×ici4 data mesh and prices each path's
    DCN-crossing gradient bytes. Runs in a SUBPROCESS with 8 virtual
    CPU devices like _smoke_ep_dispatch — abstract traces only, nothing
    executes in the child either."""
    code = (
        "import json\n"
        "from luminaai_tpu.analysis.jaxpr_audit import audit_grad_reduce\n"
        "print(json.dumps(audit_grad_reduce()))\n"
    )
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8"
    )
    try:
        proc = subprocess.run(
            [sys.executable, "-c", code],
            capture_output=True,
            text=True,
            timeout=300,
            env=env,
            cwd=_HERE,
        )
        if proc.returncode != 0:
            err = (proc.stderr or "").strip().splitlines()
            return {
                "available": False,
                "reason": (
                    f"audit subprocess rc={proc.returncode}: "
                    f"{err[-1][-300:] if err else 'no stderr'}"
                ),
            }
        return json.loads(proc.stdout.strip().splitlines()[-1])
    except subprocess.TimeoutExpired:
        return {"available": False, "reason": "audit subprocess timeout"}
    except Exception as e:
        return {"available": False, "reason": f"{type(e).__name__}: {e}"}


def _smoke_recompile_surface(registry=None) -> dict:
    """Static recompile-surface report for the smoke artifact (--smoke
    only): distinct abstract train/decode step signatures across the
    config variants the codebase forks on (scan on/off, gmm vs capacity
    einsum, prefill buckets, scalar vs batched cache_index decode).
    Abstract enumeration — jax.make_jaxpr over ShapeDtypeStructs, no
    buffers, nothing executes — so the number is a property of the
    code, not the run. tests/test_analysis.py pins the same counts;
    the ROADMAP-item-5 unified-forward refactor drives them down."""
    try:
        from luminaai_tpu.analysis.jaxpr_audit import (
            enumerate_recompile_surface,
        )

        surface = enumerate_recompile_surface(registry=registry)
        return {
            "available": True,
            "total_variants": surface["total_variants"],
            "total_distinct": surface["total_distinct"],
            "host_transfer_ops": surface["host_transfer_ops"],
            "programs": {
                prog: {
                    "distinct_signatures": rec["distinct_signatures"],
                    "variants": {
                        v["variant"]: v["signature"]
                        for v in rec["variants"]
                    },
                }
                for prog, rec in surface["programs"].items()
            },
            "note": surface["note"],
        }
    except Exception as e:
        return {"available": False, "reason": f"{type(e).__name__}: {e}"}


def _smoke_decode_cost(cfg, model, params, registry) -> dict:
    """Compiled-cost accounting for the continuous-batching DECODE step
    (--smoke only): builds a StepwiseDecoder over the smoke model and
    AOT-queries XLA's cost model for one decode-step executable, so the
    serving path's cost gauges get exercised on CPU alongside the train
    step's. Self-contained and non-fatal."""
    try:
        import dataclasses

        from luminaai_tpu.analysis.jaxpr_audit import _AuditTokenizer
        from luminaai_tpu.inference.generate import GenerationEngine
        from luminaai_tpu.monitoring.attribution import compiled_cost_metrics

        dcfg = dataclasses.replace(cfg, max_new_tokens=8)
        engine = GenerationEngine(model, params, _AuditTokenizer(), dcfg)
        decoder = engine.make_stepwise(num_slots=2, page_size=64)
        decoder.prefill_into_slot(0, [5, 6, 7, 8], max_new_tokens=4, seed=0)
        fn, args = decoder.step_fn_and_args()
        return compiled_cost_metrics(
            fn, *args, program="decode", registry=registry
        )
    except Exception as e:
        return {"available": False, "reason": f"{type(e).__name__}: {e}"}


def main() -> None:
    diagnostics = []
    platform, probe_diag = _probe_backend()
    diagnostics.append(probe_diag)

    if platform != "tpu":
        # No chip, no number: a measurement path that finds no TPU fails
        # instead of timing the CPU under a device metric's name.
        print(f"bench.py needs a TPU: {probe_diag}", file=sys.stderr)
        sys.exit(2)

    for name, timeout in LADDER:
        result, diag = _run_child(name, timeout)
        diagnostics.append(diag)
        if result is not None:
            extras = result.setdefault("extras", {})
            if extras.get("platform") != "tpu":
                # The child refuses a CPU itself; a payload that still
                # names another platform is never a result.
                diagnostics.append(
                    f"{name}: ran on {extras.get('platform')!r}, refused"
                )
                continue
            if name == "ref_debug_moe":
                # MXU-utilization rung rides along: the tiny matched config
                # can't show hardware efficiency at scale, so the 757M
                # flagship number (MFU, drop rates) is captured BEFORE the
                # headline prints and embedded in its extras. ONE bounded
                # attempt (900s) — the untuned-flagship fallback ladder
                # is not worth stacking in front of a measured headline.
                fres, fdiag = _run_child("flagship_tuned", 900)
                diagnostics.append(fdiag)
                fex = (fres or {}).get("extras", {})
                if fres is not None and fex.get("platform") == "tpu":
                    extras["flagship"] = {
                        "value": fres.get("value"),
                        "vs_ref_debug_baseline": fres.get("vs_baseline"),
                        **{
                            k: fex.get(k)
                            for k in (
                                "config",
                                "total_params_m",
                                "active_params_m",
                                "batch",
                                "seq",
                                "mfu",
                                "model_tflops_per_sec",
                                "moe_drop_rate",
                                "moe_drop_rate_steady",
                                "step_ms",
                            )
                        },
                    }
            # Regression gate vs whatever BENCH_r*.json trajectory sits
            # beside this file (scripts/bench_gate.py matches on
            # platform+config; none committed → verdict "no_baseline").
            extras["bench_gate"] = _gate_verdict(result)
            print(json.dumps(result), flush=True)
            if name.startswith("flagship") or name == "ref_debug_moe":
                # Dense comparison rung (ref BENCHMARKS.md publishes dense
                # headlines too: 200M ~119k tok/s). Runs AFTER the main
                # line is printed so a sidecar hang can never cost the
                # headline artifact; result lands in DENSE_BENCH.json.
                dense, ddiag = _run_child("dense200", 700)
                if dense is not None:
                    dense["baseline_note"] = "ref dense 200M ~119k tok/s"
                    with open(
                        os.path.join(
                            os.path.dirname(os.path.abspath(__file__)),
                            "DENSE_BENCH.json",
                        ),
                        "w",
                    ) as f:
                        json.dump(dense, f, indent=2)
                # Row-for-row sweep of the reference's published
                # debug-scale table (dense, 200M dense/MoD/hybrid) —
                # matched dims, each rung bounded, results in
                # REF_TABLE.json. Runs last so a hang can only cost the
                # table, never the headline or dense sidecar.
                table = []
                for rname, (ref_tps, rtimeout) in REF_TABLE_RUNGS.items():
                    res, rdiag = _run_child(rname, rtimeout)
                    if res is not None:
                        table.append({
                            "config": rname,
                            "tokens_per_sec_per_chip": res["value"],
                            "ref_tokens_per_sec": ref_tps,
                            "vs_ref": res["vs_baseline"],
                            "step_ms": res["extras"].get("step_ms"),
                            "batch": res["extras"].get("batch"),
                            "seq": res["extras"].get("seq"),
                        })
                    else:
                        table.append(
                            {"config": rname, "error": rdiag[-300:]}
                        )
                with open(
                    os.path.join(
                        os.path.dirname(os.path.abspath(__file__)),
                        "REF_TABLE.json",
                    ),
                    "w",
                ) as f:
                    json.dump(
                        {
                            "note": (
                                "matched-dims counterparts of the "
                                "reference BENCHMARKS.md debug-scale "
                                "rows, measured on this backend"
                            ),
                            "rows": table,
                        },
                        f,
                        indent=2,
                    )
            return
    print(
        "bench.py: every rung failed: " + "; ".join(diagnostics)[-1500:],
        file=sys.stderr,
    )
    sys.exit(1)


if __name__ == "__main__":
    if len(sys.argv) >= 3 and sys.argv[1] == "--child":
        _child_main(sys.argv[2])
    elif "--smoke-serve" in sys.argv[1:]:
        _serve_bench_main(smoke=True)
    elif "--serve-bench" in sys.argv[1:]:
        _serve_bench_main(smoke=False)
    elif "--smoke-router" in sys.argv[1:]:
        _router_bench_main(smoke=True)
    elif "--router-bench" in sys.argv[1:]:
        _router_bench_main(smoke=False)
    elif "--smoke" in sys.argv[1:]:
        # Hermetic CPU smoke of the TRAIN bench child, with the full
        # attribution surface: compiled cost-analysis extras for the
        # train AND decode steps plus a bench_gate verdict — the
        # acceptance path CI exercises without hardware.
        os.environ["JAX_PLATFORMS"] = "cpu"
        _child_main("smoke")
    else:
        main()
