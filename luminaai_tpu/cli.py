"""Command-line entry point: train / resume / chat / data / diagnose /
presets.

Covers the reference CLI surface (ref: Src/Main_Scripts/Main.py:1506 main()
with config selection + adaptive-vs-standard training, :619 system
diagnostics, :1404 chinchilla auto-epochs, :1126 signal handlers, plus
Chat.py's interactive entry) as a proper argparse program:

    python -m luminaai_tpu train --preset debug --synthetic --steps 30
    python -m luminaai_tpu resume --output-dir runs/exp1
    python -m luminaai_tpu chat --checkpoint runs/exp1/checkpoints
    python -m luminaai_tpu data sample --out data/sample.jsonl
    python -m luminaai_tpu diagnose
"""

from __future__ import annotations

import argparse
import json
import logging
import os
import signal
import sys
from pathlib import Path
from typing import Any, Dict, Iterator, List, Optional

import numpy as np

logger = logging.getLogger(__name__)

# Exit code for "stopped on a preemption signal with a resumable
# checkpoint banked" — EX_TEMPFAIL by convention, distinct from both
# success (0) and failure (1/2) so orchestrators can reschedule with
# `resume` instead of alerting (docs/resilience.md).
RESUMABLE_EXIT = 75


# ---------------------------------------------------------------------------
# config assembly
# ---------------------------------------------------------------------------
def _apply_overrides(cfg, args) -> None:
    """Map CLI flags onto Config fields (only when explicitly given)."""
    for flag, field in [
        ("lr", "learning_rate"),
        ("batch_size", "batch_size"),
        ("seq_length", "seq_length"),
        ("steps", "max_steps"),
        ("epochs", "num_epochs"),
        ("precision", "precision"),
        ("output_dir", "output_dir"),
        ("experiment", "experiment_name"),
        ("grad_accum", "gradient_accumulation_steps"),
        ("tokenizer", "tokenizer_name"),
        ("dp", "data_parallel_size"),
        ("pp", "pipeline_parallel_size"),
        ("fsdp", "fsdp_parallel_size"),
        ("tp", "tensor_parallel_size"),
        ("ep", "expert_parallel_size"),
        ("sp", "sequence_parallel_size"),
        ("moe_dispatch", "moe_dispatch"),
        ("attention_window", "attention_window"),
        ("profile_dir", "profile_dir"),
        ("watchdog", "watchdog"),
        ("watchdog_k", "watchdog_k"),
        ("watchdog_floor", "watchdog_floor_s"),
        ("slo", "slo"),
        ("slo_config", "slo_config"),
    ]:
        val = getattr(args, flag, None)
        if val is not None:
            setattr(cfg, field, val)
    if getattr(args, "no_moe", False):
        cfg.use_moe = False
    if getattr(args, "no_flash", False):
        cfg.use_flash_attention = False
    # Windowed in-run profiling (docs/observability.md "Attribution"):
    # --profile-steps N captures a device trace for N steps (starting at
    # --profile-start, default step 3 so the compile step never pollutes
    # the window) and exports the per-subsystem breakdown. Either flag
    # alone enables the window — --profile-start without --profile-steps
    # uses the config's profile_num_steps (default 3), never a silent
    # no-op.
    if getattr(args, "profile_start", None):
        cfg.profile_start_step = args.profile_start
    if getattr(args, "profile_steps", None):
        cfg.profile_num_steps = args.profile_steps
        if not cfg.profile_start_step:
            cfg.profile_start_step = 3
    if getattr(args, "cost_analysis", False):
        cfg.compiled_cost_analysis = True
    if getattr(args, "watchdog_abort", False):
        cfg.watchdog_abort = True
    # Axis-implied settings (ring attention under sp, scan_layers and the
    # grad-accum fold under pp) — one shared code path on Config.
    cfg.normalize_parallelism()


def build_config(args):
    from luminaai_tpu.config import ConfigManager, ConfigPresets

    if getattr(args, "config", None):
        from luminaai_tpu.config import Config

        cfg = Config.load(args.config)
    else:
        cfg = ConfigPresets.get(args.preset)
    _apply_overrides(cfg, args)
    if getattr(args, "auto_hardware", False):
        cfg = ConfigManager.optimize_for_hardware(cfg)
    cfg.validate()
    return cfg


# ---------------------------------------------------------------------------
# data wiring
# ---------------------------------------------------------------------------
def _synthetic_batches(cfg, n_batches: int = 200, seed: int = 0):
    """Learnable repeating-pattern batches (smoke training, ref debug
    runs on synthetic data). Deterministic per (seed, epoch) and wrapped
    in a PrefetchLoader, so even synthetic runs get the exact-resume
    contract (docs/resilience.md)."""
    from luminaai_tpu.data.dataset import PrefetchLoader

    def gen(epoch: int = 0) -> Iterator[Dict[str, np.ndarray]]:
        rng = np.random.RandomState(seed + epoch)
        period = min(64, cfg.vocab_size - 2)
        for _ in range(n_batches):
            starts = rng.randint(0, 32, size=(cfg.batch_size, 1))
            seq = (starts + np.arange(cfg.seq_length)) % period + 1
            yield {"input_ids": seq.astype(np.int32)}

    return PrefetchLoader(gen, prefetch=2)


def make_data(cfg, args):
    """Returns (train_fn, eval_fn, dataset_tokens|None)."""
    from luminaai_tpu.data.dataset import (
        ConversationDataset,
        PackedDataset,
        PrefetchLoader,
        build_text_cache,
        conversation_batches,
    )
    from luminaai_tpu.data.tokenizer import ConversationTokenizer

    # --data wins; config train_data_path is a fallback only when the file
    # actually exists (its default 'data/train.jsonl' must not shadow the
    # synthetic-data default on fresh checkouts).
    cfg_path = cfg.train_data_path
    data_path = getattr(args, "data", None) or (
        cfg_path if cfg_path and Path(cfg_path).exists() else None
    )
    if getattr(args, "synthetic", False) or not data_path:
        if not getattr(args, "synthetic", False):
            logger.warning("no --data given; training on synthetic data")
        return _synthetic_batches(cfg), None, None

    path = data_path
    tokenizer = ConversationTokenizer(
        model_name=cfg.tokenizer_name,
        assistant_loss_weight=cfg.assistant_loss_weight,
    )
    if tokenizer.vocab_size > cfg.vocab_size:
        # A trained vocab larger than the model's embedding table would
        # index out of range; grow the model to fit (tokenizer.vocab_size
        # is already 128-aligned).
        logger.warning(
            "tokenizer vocab %d > model vocab_size %d; raising model "
            "vocab_size to match", tokenizer.vocab_size, cfg.vocab_size,
        )
        cfg.vocab_size = tokenizer.vocab_size
    # Per-host shard identity comes from config, not live jax state (the
    # distributed runtime comes up later, in Trainer.__init__). On pods
    # where jax auto-detects the process id, process_id is legitimately
    # None — sharding on it would put EVERY host on shard 0, so fall back
    # to the process-oblivious full-batch loader (Trainer._put slices
    # each host's rows at runtime).
    pi, pc = 0, 1
    if cfg.multihost and (cfg.num_processes or 1) > 1:
        if cfg.process_id is not None:
            pi, pc = cfg.process_id, cfg.num_processes
        else:
            logger.warning(
                "multihost without explicit process_id: data sharding "
                "disabled; every host will read the full corpus (set "
                "config.process_id to enable per-host shards)"
            )
    if getattr(args, "packed", False):
        cache = build_text_cache(
            path, str(Path(cfg.output_dir) / "cache" / Path(path).stem),
            tokenizer,
        )
        ds = PackedDataset(
            cache, cfg.batch_size, cfg.seq_length,
            pad_id=tokenizer.pad_token_id, eos_id=tokenizer.eos_token_id,
            shuffle_seed=cfg.seed,
            use_native=cfg.use_native_dataloader,
            split_docs=cfg.pack_sequences,
            process_index=pi,
            process_count=pc,
        )
        return (
            PrefetchLoader(
                lambda: iter(ds), prefetch=max(1, cfg.num_workers),
                source=ds,  # curriculum set_difficulty forwards to the ds
            ),
            None, cache.n_tokens,
        )

    ds = ConversationDataset(path, tokenizer, cfg)
    tokens = None
    if not ds.streaming:
        tokens = sum(int(s["loss_mask"].size) for s in ds.samples)

    def train_fn(epoch: int):
        # Fresh permutation per epoch, derived from the epoch NUMBER (not
        # a process-local counter): the PrefetchLoader passes the epoch
        # through, so a resumed run replays the same per-epoch shuffles
        # and the batch stream continues exactly (docs/resilience.md).
        return conversation_batches(
            ds, cfg.batch_size, seed=cfg.seed + epoch,
            process_index=pi, process_count=pc,
        )

    eval_fn = None
    eval_path = getattr(args, "eval_data", None) or (
        cfg.eval_data_path
        if cfg.eval_data_path and Path(cfg.eval_data_path).exists()
        else None
    )
    if eval_path:
        eval_ds = ConversationDataset(eval_path, tokenizer, cfg, split="eval")

        def eval_fn():
            return conversation_batches(eval_ds, cfg.batch_size, seed=0)

    return (
        PrefetchLoader(train_fn, prefetch=max(1, cfg.num_workers)),
        eval_fn, tokens,
    )


# ---------------------------------------------------------------------------
# subcommands
# ---------------------------------------------------------------------------
def cmd_train(args) -> int:
    from luminaai_tpu.training.orchestrator import AdaptiveTrainingOrchestrator
    from luminaai_tpu.training.scaler import ChinchillaScaler
    from luminaai_tpu.training.trainer import Trainer
    from luminaai_tpu.utils.environment import format_diagnostics

    if not args.quiet:
        print(format_diagnostics())

    cfg = build_config(args)
    logging.getLogger().setLevel(cfg.log_level)
    if args.resume:
        cfg.auto_resume = True
    train_fn, eval_fn, dataset_tokens = make_data(cfg, args)

    auto_epochs = args.auto_epochs or cfg.use_chinchilla_scaling
    if auto_epochs and dataset_tokens:
        # Chinchilla budget → step count (ref Main.py:1404
        # auto_adjust_epochs_chinchilla). An explicit --steps wins: the
        # budget is advice, not an override of the operator.
        plan = ChinchillaScaler(cfg).plan(dataset_tokens)
        if args.steps is None:
            cfg.max_steps = plan.recommended_steps
        print(
            f"chinchilla auto-budget: recommended_steps="
            f"{plan.recommended_steps} (dataset {dataset_tokens:,} tokens, "
            f"applied={'yes' if args.steps is None else 'no, --steps set'})"
        )

    # Rough wall-clock estimate before committing compute (ref Main.py:1008
    # estimate_and_display_training_time).
    steps = cfg.max_steps or 0
    if steps and not args.quiet:
        tok_per_step = cfg.batch_size * cfg.seq_length
        # ~40% MFU planning number on detected hardware; CPU ≈ debug only.
        from luminaai_tpu.utils.environment import (
            device_peak_flops,
            get_device_info,
        )

        dev = get_device_info()
        try:
            peak = device_peak_flops()
        except ValueError:  # no peak on record: print no invented estimate
            print(
                f"estimated training time: unknown for {steps} steps "
                f"({tok_per_step * steps / 1e6:.0f}M tokens; no peak "
                f"FLOP/s on record for {dev['platform']})"
            )
        else:
            est_tps = max(
                1.0,
                0.4 * peak * dev["device_count"]
                / (6 * max(cfg.estimate_active_parameters(), 1)),
            )
            hours = steps * tok_per_step / est_tps / 3600
            print(
                f"estimated training time: ~{hours:.2f}h for {steps} steps "
                f"({tok_per_step * steps / 1e6:.0f}M tokens at "
                f"~{est_tps:,.0f} tok/s planning rate)"
            )

    # Start-of-run experiment metadata (ref Main.py:1192
    # save_experiment_metadata) — written before the trainer is even built
    # so any crash still leaves provenance on disk. A resume never
    # overwrites the original run's record.
    meta_path = Path(cfg.output_dir) / "experiment_metadata.json"
    if not (args.resume and meta_path.exists()):
        meta_path.parent.mkdir(parents=True, exist_ok=True)
        meta_path.write_text(json.dumps(_jsonable({
            "experiment_name": cfg.experiment_name,
            "config": cfg.to_dict(),
            "total_params": cfg.estimate_parameters(),
            "active_params": cfg.estimate_active_parameters(),
            "dataset_tokens": dataset_tokens,
            "planned_steps": cfg.max_steps,
            "argv": sys.argv[1:],
        }), indent=2))

    trainer = Trainer(cfg, train_data=train_fn, eval_data=eval_fn)
    restore_signals = _install_signal_handlers(trainer)

    oom_protect = getattr(args, "oom_protect", True)
    try:
        if args.adaptive:
            orchestrator = AdaptiveTrainingOrchestrator(trainer)
            summary = orchestrator.run(oom_protect=oom_protect)
        elif oom_protect:
            summary = trainer.train_with_oom_protection()
        else:
            summary = trainer.train()
    finally:
        restore_signals()
    trainer.close()

    out = Path(cfg.output_dir) / "training_summary.json"
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps(_jsonable(summary), indent=2))
    final = summary.get("final_metrics", {})
    if summary.get("preempted"):
        print(
            f"training PREEMPTED at step {summary.get('final_step')}: "
            f"emergency checkpoint committed; rerun `resume` to continue "
            f"(exit {RESUMABLE_EXIT} = resumable)"
        )
        return RESUMABLE_EXIT
    print(
        f"training done: steps={summary.get('final_step')} "
        f"final_loss={final.get('loss', float('nan')):.4f} "
        f"summary={out}"
    )
    return 0


def cmd_chat(args) -> int:
    from luminaai_tpu.inference.chat import ChatInterface

    chat = ChatInterface(
        checkpoint_dir=args.checkpoint,
        quantize=getattr(args, "quantize", None),
        adapter=getattr(args, "adapter", None),
        kv_cache_dtype=getattr(args, "kv_cache_dtype", None),
    )
    if chat.engine.quantization_info:
        q = chat.engine.quantization_info
        if q.get("mode") == "int8_compute":
            print(
                f"serving with int8 COMPUTE quantization: "
                f"{q['quantized_leaves']} tensors run int8 MXU dots "
                f"(W8A8), {q['compression']:.2f}x smaller resident",
                file=sys.stderr,
            )
        else:
            print(
                f"serving with int{q['bits']} weight round-trip: "
                f"{q['quantized_leaves']} tensors, {q['compression']:.2f}x "
                "smaller at rest (resident serving copy stays bf16 for MXU "
                "compute)", file=sys.stderr,
            )
    # Generation defaults live on the engine's config (ref Chat.py mode
    # presets); CLI flags override them for the session.
    chat.engine.config.temperature = args.temperature
    chat.engine.config.top_p = args.top_p
    chat.engine.config.max_new_tokens = args.max_new_tokens

    if args.secure:
        # Authenticated, rate-limited, input-validated path (ref
        # security/rate_limiter.py:107 SecureConversationalChat).
        from luminaai_tpu.security import SecureChatSession

        secure = SecureChatSession(chat.respond)
        user = args.user or "operator"
        password = args.password
        if password is None:
            import getpass

            password = getpass.getpass(f"password for {user}: ")
        if user not in secure.security.users:
            if not secure.create_user(user, password):
                print("could not create user (weak password?)", file=sys.stderr)
                return 2
        token = secure.authenticate(user, password)
        if token is None:
            print("authentication failed", file=sys.stderr)
            return 2
        if args.prompt:
            out = secure.secure_respond(args.prompt, token)
            if not out["ok"]:
                print(f"rejected: {out['error']}", file=sys.stderr)
                return 1
            print(out["reply"])
            return 0
        print("secure chat — 'quit' to exit")
        while True:  # pragma: no cover - interactive
            try:
                line = input("> ")
            except (EOFError, KeyboardInterrupt):
                break
            if line.strip().lower() in ("quit", "exit"):
                break
            out = secure.secure_respond(line, token)
            print(out["reply"] if out["ok"] else f"[{out['error']}]")
        return 0

    if args.prompt:
        reply, stats = chat.respond(args.prompt)
        print(reply)
        if args.verbose:
            print(json.dumps(stats, indent=2), file=sys.stderr)
        return 0
    chat.run()
    return 0


def cmd_data(args) -> int:
    from luminaai_tpu.data.processing import (
        create_sample_data,
        process_oasst_data,
        validate_data_comprehensive,
    )

    if args.action == "sample":
        n = create_sample_data(args.out, num_conversations=args.count)
        print(f"wrote {n} sample conversations to {args.out}")
    elif args.action == "acquire":
        from luminaai_tpu.config import Config
        from luminaai_tpu.data.acquisition import DatasetDownloader

        max_per_file = args.max_per_file
        if max_per_file is None:  # flag overrides the config default
            max_per_file = Config().max_conversations_per_file
        dl = DatasetDownloader(
            args.out or "data/oasst",
            max_records_per_file=max_per_file,
        )
        if args.inp:  # offline path: local raw OASST dump
            stats = dl.process_local_dump(args.inp)
            print(json.dumps(_jsonable(stats), indent=2))
        else:
            ok = dl.download_and_process()
            if not ok:
                print(
                    "download unavailable (offline?); pass --in DUMP.jsonl "
                    "to process a local raw dump", file=sys.stderr,
                )
                return 1
    elif args.action == "train-tokenizer":
        # Offline BPE vocab training (data/bpe.py; the reference can only
        # consume pretrained tiktoken vocabs). --in accepts conversation
        # or plain-text jsonl; --vocab-size is the target vocab.
        from luminaai_tpu.data.bpe import train_bpe

        def texts():
            with open(args.inp) as f:
                for line in f:
                    line = line.strip()
                    if not line:
                        continue
                    try:
                        row = json.loads(line)
                    except json.JSONDecodeError:
                        yield line
                        continue
                    if isinstance(row, dict) and "messages" in row:
                        for m in row["messages"]:
                            yield str(m.get("content", ""))
                    elif isinstance(row, dict) and "text" in row:
                        yield str(row["text"])
                    else:
                        yield line

        tok = train_bpe(texts(), vocab_size=args.vocab_size)
        tok.save(args.out)
        sample = "The quick brown fox jumps over the lazy dog."
        n_bpe = len(tok.encode(sample))
        print(
            f"trained {tok.n_vocab}-token BPE -> {args.out} "
            f"(sample compression {len(sample.encode()) / max(n_bpe, 1):.2f} "
            "bytes/token; use with --tokenizer "
            f"bpe:{args.out})"
        )
    elif args.action == "oasst":
        n = process_oasst_data(args.inp, args.out)
        print(f"converted {n} conversations -> {args.out}")
    elif args.action == "validate":
        from luminaai_tpu.data.tokenizer import ConversationTokenizer

        report = validate_data_comprehensive(
            args.inp, ConversationTokenizer()
        )
        print(json.dumps(_jsonable(report), indent=2))
    elif args.action == "blend":
        # Weighted multi-source blend → one jsonl (ref Main.py:1350
        # setup_multi_dataset_training + multi_source main()). --sources
        # takes name=weight=glob triples.
        import glob as globlib

        from luminaai_tpu.data.multi_source import MultiSourcePipeline
        from luminaai_tpu.data.tokenizer import ConversationTokenizer

        if not args.sources:
            print(
                "blend requires --sources name=weight=glob [...]",
                file=sys.stderr,
            )
            return 2
        weights: Dict[str, float] = {}
        shards: Dict[str, List[str]] = {}
        for spec in args.sources:
            try:
                name, weight, pattern = spec.split("=", 2)
                weights[name] = float(weight)
            except ValueError:
                print(f"bad --sources entry {spec!r}", file=sys.stderr)
                return 2
            shards[name] = sorted(globlib.glob(pattern))
            if not shards[name]:
                print(f"no files match {pattern!r}", file=sys.stderr)
                return 2
        if sum(weights.values()) <= 0:
            print("--sources weights must sum to > 0", file=sys.stderr)
            return 2
        pipeline = MultiSourcePipeline(ConversationTokenizer(), weights)
        out_path = args.out or "blended.jsonl"
        n = 0
        with open(out_path, "w", encoding="utf-8") as f:
            for rec in pipeline.iter_blended(shards):
                f.write(json.dumps(rec, ensure_ascii=False) + "\n")
                n += 1
        print(f"blended {n} documents from {len(shards)} sources -> {out_path}")
    return 0


def cmd_evaluate(args) -> int:
    """Standalone perplexity/loss evaluation of a checkpoint on a jsonl
    dataset (ref trainer.py:2667 evaluate, exposed without a Trainer)."""
    import jax
    import jax.numpy as jnp

    from luminaai_tpu.data.dataset import ConversationDataset, conversation_batches
    from luminaai_tpu.data.tokenizer import ConversationTokenizer
    from luminaai_tpu.inference.chat import load_model_for_inference
    from luminaai_tpu.parallel.train_step import (
        _shifted_mask_weights,
        shift_labels,
    )
    from luminaai_tpu.ops.fused import fused_lm_head_cross_entropy

    model, params, cfg = load_model_for_inference(args.checkpoint)
    if args.batch_size:
        cfg.batch_size = args.batch_size
    tokenizer = ConversationTokenizer(
        assistant_loss_weight=cfg.assistant_loss_weight
    )
    ds = ConversationDataset(args.data, tokenizer, cfg, split="eval")

    @jax.jit
    def eval_batch(params, batch):
        hidden, _ = model.apply(
            {"params": params}, batch["input_ids"],
            deterministic=True, return_hidden=True,
        )
        labels, valid = shift_labels(batch)
        mask, weights = _shifted_mask_weights(batch, valid)
        head = params["embedder"][
            "embedding" if cfg.tie_word_embeddings else "lm_head"
        ]
        loss, metrics = fused_lm_head_cross_entropy(
            hidden, head, labels, loss_mask=mask, loss_weights=weights,
        )
        return metrics

    total_nll = total_tokens = 0.0
    n_batches = 0
    for batch in conversation_batches(
        ds, cfg.batch_size, seed=0, drop_last=False
    ):
        if args.max_batches and n_batches >= args.max_batches:
            break
        m = eval_batch(params, {k: jnp.asarray(v) for k, v in batch.items()})
        ntok = float(m["tokens_in_loss"])
        total_nll += float(m["ce_loss"]) * ntok
        total_tokens += ntok
        n_batches += 1
    if total_tokens == 0:
        print("no evaluable tokens found", file=sys.stderr)
        return 1
    loss = total_nll / total_tokens
    result = {
        "eval_loss": round(loss, 4),
        "perplexity": round(float(np.exp(min(loss, 20.0))), 2),
        "tokens": int(total_tokens),
        "batches": n_batches,
    }
    print(json.dumps(result, indent=2))
    return 0


def _fleet_child_argv(argv: List[str], port: int) -> List[str]:
    """Rebuild a replica's serve argv from the parent's: same flags,
    its own port, no --replicas (a replica must not recurse). The
    page-share wiring flags are stripped too — the fleet parent
    re-issues them pointing at its own router."""
    drop = ("--replicas", "--port", "--page-share", "--page-share-self")
    out: List[str] = []
    skip = False
    for a in argv:
        if skip:
            skip = False
            continue
        if a in drop:
            skip = True
            continue
        if any(a.startswith(d + "=") for d in drop):
            continue
        out.append(a)
    return out + ["--port", str(port)]


def _replica_env(index: int) -> Dict[str, str]:
    """Environment of fleet replica `index`: this process's own, plus
    libtpu's placement variables naming exactly one chip — a chip belongs
    to one process at a time, and without them the first replica takes
    every chip of the host and the rest get none. Each replica is a
    one-chip world of its own (1x1x1 bounds, its own runtime port).
    The CPU backend ignores all of them."""
    port = 8476 + index
    return dict(
        os.environ,
        TPU_VISIBLE_CHIPS=str(index),
        TPU_CHIPS_PER_PROCESS_BOUNDS="1,1,1",
        TPU_PROCESS_BOUNDS="1,1,1",
        TPU_PROCESS_ADDRESSES=f"localhost:{port}",
        TPU_PROCESS_PORT=str(port),
        CLOUD_TPU_TASK_ID="0",
    )


def _serve_fleet(args) -> int:
    """`lumina serve --replicas N`: spawn N replica serve processes on
    port+1..port+N — each on its own chip — wait for their /healthz, then
    front them with the router on --port. Dev-fleet ergonomics — one
    command, one ^C. This launcher never touches jax: the replicas need
    the chips."""
    import signal
    import subprocess

    from luminaai_tpu.config import Config
    from luminaai_tpu.serving.router import Router, wait_ready

    cfg = Config()
    n = args.replicas
    ports = [args.port + 1 + i for i in range(n)]
    urls = [f"http://{args.host}:{p}" for p in ports]
    procs = []
    router_url = f"http://{args.host}:{args.port}"
    try:
        for i, p in enumerate(ports):
            child = _fleet_child_argv(sys.argv[1:], p)
            # Auto-wire cross-replica page sharing: every replica
            # reports its harvested prefix keys to the fleet router and
            # can pull pages from siblings (docs/serving.md
            # "Cross-replica prefix sharing").
            child += [
                "--page-share", router_url,
                "--page-share-self", f"http://{args.host}:{p}",
            ]
            procs.append(subprocess.Popen(
                [sys.executable, "-m", "luminaai_tpu"] + child,
                env=_replica_env(i),
            ))
        print(f"fleet: {n} replica(s) on ports {ports}; waiting for "
              "warmup...", file=sys.stderr)
        wait_ready(urls, timeout_s=600.0, procs=procs)
        router = Router(
            list(zip([f"r{i}" for i in range(n)], urls)),
            probe_interval_s=cfg.router_probe_interval_s,
            breaker_failures=cfg.router_breaker_failures,
            breaker_cooldown_s=cfg.router_breaker_cooldown_s,
            max_failovers=min(cfg.router_max_failovers, n - 1),
            hedge_budget=cfg.router_hedge_budget,
            hedge_max_tokens=cfg.router_hedge_max_tokens,
            flight_dir=getattr(args, "flight_dir", None),
        )
        router.probe_all()
        router.start_probing()
        router.serve_forever(args.host, args.port)
        return 0
    finally:
        for proc in procs:
            if proc.poll() is None:
                proc.send_signal(signal.SIGTERM)
        for proc in procs:
            try:
                proc.wait(timeout=30)
            except subprocess.TimeoutExpired:
                proc.kill()


def cmd_serve(args) -> int:
    """HTTP chat/completion server (ref Dockerfile.backend: Flask on :5001
    with /health; here stdlib http.server — luminaai_tpu/serving).
    --replicas N spawns a local fleet fronted by the replica router."""
    if getattr(args, "replicas", 1) > 1:
        return _serve_fleet(args)
    from luminaai_tpu.serving import serve

    bootstrap = None
    if args.secure:
        if bool(args.user) != bool(args.password):
            print("--secure bootstrap needs BOTH --user and --password",
                  file=sys.stderr)
            return 2
        if args.user:
            bootstrap = (args.user, args.password)
        elif not Path("users.json").exists():
            print("--secure with no --user/--password and no existing "
                  "users.json: nobody could authenticate", file=sys.stderr)
            return 2
    stale_after = getattr(args, "healthz_stale_after", None)
    if stale_after is not None and stale_after <= 0:
        # Mirrors the --latency-buckets pattern: die with exit 2 NOW,
        # not a ValueError after minutes of checkpoint load.
        print(
            f"--healthz-stale-after needs a positive number of seconds, "
            f"got {stale_after!r}",
            file=sys.stderr,
        )
        return 2
    buckets = None
    raw_buckets = getattr(args, "latency_buckets", None)
    if raw_buckets:
        import math

        try:
            buckets = sorted(
                float(b) for b in raw_buckets.split(",") if b.strip()
            )
            # Mirror Histogram.__init__'s contract (unique finite
            # positive) HERE, so a bad flag dies with exit 2 now instead
            # of a ValueError traceback after minutes of checkpoint load.
            if (
                not buckets
                or any(not math.isfinite(b) or b <= 0 for b in buckets)
                or len(set(buckets)) != len(buckets)
            ):
                raise ValueError(raw_buckets)
        except ValueError:
            print(f"--latency-buckets needs unique positive "
                  f"comma-separated seconds, got {raw_buckets!r}",
                  file=sys.stderr)
            return 2
    serve(
        checkpoint=args.checkpoint,
        host=args.host,
        port=args.port,
        secure=args.secure,
        bootstrap_user=bootstrap,
        quantize=getattr(args, "quantize", None),
        adapter=getattr(args, "adapter", None),
        kv_cache_dtype=getattr(args, "kv_cache_dtype", None),
        num_slots=getattr(args, "num_slots", 8),
        page_size=getattr(args, "page_size", 128),
        admission_window_ms=getattr(args, "admission_window_ms", 0.0),
        telemetry=not getattr(args, "no_telemetry", False),
        trace_jsonl=getattr(args, "trace_jsonl", None),
        trace_jax=getattr(args, "trace_jax", False),
        latency_buckets=buckets,
        request_timeout_s=getattr(args, "request_timeout_s", None),
        max_queue_depth=getattr(args, "max_queue_depth", 128),
        drain_grace_s=getattr(args, "drain_grace_s", 30.0),
        flight_dir=getattr(args, "flight_dir", None),
        prefill_chunk_tokens=getattr(args, "prefill_chunk_tokens", None),
        prefix_cache_pages=getattr(args, "prefix_cache_pages", None),
        prefix_cache_tenant_quota=getattr(
            args, "prefix_cache_tenant_quota", None
        ),
        tenant_rate_per_s=getattr(args, "tenant_rate_per_s", None),
        tenant_burst=getattr(args, "tenant_burst", None),
        watchdog=not getattr(args, "no_watchdog", False),
        watchdog_abort=getattr(args, "watchdog_abort", False),
        watchdog_k=getattr(args, "watchdog_k", None),
        watchdog_floor_s=getattr(args, "watchdog_floor", None),
        slo=not getattr(args, "no_slo", False),
        slo_config=getattr(args, "slo_config", None),
        healthz_stale_after_s=getattr(args, "healthz_stale_after", None),
        page_share=getattr(args, "page_share", None),
        page_share_self_url=getattr(args, "page_share_self", None),
        page_pull_timeout_s=getattr(args, "page_pull_timeout", None) or 2.0,
        page_share_max_inflight=(
            getattr(args, "page_share_max_inflight", None) or 2
        ),
    )
    return 0


def cmd_route(args) -> int:
    """Health-aware data-plane router fronting N ChatServer replicas
    (docs/serving.md "Replica router"): active /healthz + /slo probing,
    per-replica circuit breakers, prefix-hash-affine dispatch with
    bounded failover, Retry-After-aware shedding, optional hedged
    dispatch. Flag defaults come from Config's router_* knobs."""
    from luminaai_tpu.config import Config
    from luminaai_tpu.serving.router import run_router

    cfg = Config()

    def knob(name, default):
        v = getattr(args, name, None)
        return default if v is None else v

    urls = []
    for u in args.replicas:
        if "://" not in u:
            u = "http://" + u
        urls.append(u.rstrip("/"))
    if len(urls) != len(set(urls)):
        print("duplicate --replica urls", file=sys.stderr)
        return 2
    run_router(
        urls,
        host=args.host,
        port=args.port,
        probe_interval_s=knob(
            "probe_interval_s", cfg.router_probe_interval_s
        ),
        breaker_failures=knob(
            "breaker_failures", cfg.router_breaker_failures
        ),
        breaker_cooldown_s=knob(
            "breaker_cooldown_s", cfg.router_breaker_cooldown_s
        ),
        max_failovers=min(
            knob("max_failovers", cfg.router_max_failovers),
            len(urls) - 1,
        ),
        request_timeout_s=getattr(args, "request_timeout_s", None),
        hedge=getattr(args, "hedge", False),
        hedge_delay_s=getattr(args, "hedge_delay_s", None),
        hedge_budget=knob("hedge_budget", cfg.router_hedge_budget),
        hedge_max_tokens=knob(
            "hedge_max_tokens", cfg.router_hedge_max_tokens
        ),
        flight_dir=getattr(args, "flight_dir", None),
    )
    return 0


def cmd_finetune(args) -> int:
    """LoRA fine-tuning against a frozen base checkpoint (docs/adapters.md;
    ref adapter programme). Optimizer state exists only for the adapter."""
    import jax
    import jax.numpy as jnp
    import optax

    from luminaai_tpu.data.dataset import (
        ConversationDataset,
        conversation_batches,
    )
    from luminaai_tpu.data.tokenizer import ConversationTokenizer
    from luminaai_tpu.inference.chat import load_model_for_inference
    from luminaai_tpu.training.adapters import (
        LoRASpec,
        init_lora_params,
        lora_param_count,
        make_lora_train_step,
        merge_lora,
        save_lora,
    )

    # keep_master_dtype: we train against (and may re-export) these
    # weights; the serving bf16 downcast would permanently round away the
    # fp32 masters and swallow small LoRA deltas at merge time.
    model, params, cfg = load_model_for_inference(
        args.checkpoint, keep_master_dtype=True
    )
    if args.batch_size:
        cfg.batch_size = args.batch_size
    patterns = [r"attention/", r"ffn/"]
    if args.adapt_experts:
        patterns.append(r"moe/")
    spec = LoRASpec(
        rank=args.rank, alpha=args.alpha, target_patterns=tuple(patterns)
    )
    rng = jax.random.key(cfg.seed)
    lora = init_lora_params(params, spec, rng)
    base_n = cfg.estimate_parameters()
    print(
        f"adapter: rank {spec.rank}, {lora_param_count(lora) / 1e6:.2f}M "
        f"params ({lora_param_count(lora) / max(base_n, 1):.3%} of base, "
        f"{len(lora)} kernels)"
    )

    tx = optax.adam(args.lr)
    step = make_lora_train_step(cfg, model, params, spec, tx)
    carry = (lora, tx.init(lora))

    tokenizer = ConversationTokenizer(
        assistant_loss_weight=cfg.assistant_loss_weight
    )
    ds = ConversationDataset(args.data, tokenizer, cfg, split="train")
    done = 0
    last = float("nan")
    while done < args.steps:
        made_progress = False
        for batch in conversation_batches(ds, cfg.batch_size, seed=done):
            if done >= args.steps:
                break
            made_progress = True
            carry, metrics = step(
                carry,
                {k: jnp.asarray(v) for k, v in batch.items()},
                jax.random.fold_in(rng, done),
            )
            done += 1
            if done % max(1, args.steps // 10) == 0 or done == 1:
                last = float(metrics["loss"])
                print(f"step {done}/{args.steps} loss {last:.4f}")
        if not made_progress:
            print(
                f"no batches: dataset has fewer than batch_size="
                f"{cfg.batch_size} usable samples (pass --batch-size)",
                file=sys.stderr,
            )
            return 1

    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    save_lora(str(out / "adapter"), carry[0], spec)
    print(f"adapter saved: {out / 'adapter'}.npz (final loss {last:.4f})")

    if args.merge_out:
        import orbax.checkpoint as ocp

        merged = merge_lora(params, carry[0], spec)
        mout = Path(args.merge_out).absolute()
        mout.mkdir(parents=True, exist_ok=True)
        with ocp.CheckpointManager(mout) as mngr:
            mngr.save(
                0,
                args=ocp.args.Composite(
                    state=ocp.args.StandardSave({"params": merged}),
                    metadata=ocp.args.JsonSave(
                        {"step": 0, "config": cfg.to_dict(),
                         "adapter": str(out / "adapter")}
                    ),
                ),
            )
            mngr.wait_until_finished()
        print(f"merged checkpoint: {mout}")
    return 0


def cmd_convert(args) -> int:
    """Convert a checkpoint between per-layer and scanned param layouts
    (the same weights, bit-identical outputs — models/transformer.py
    stack/unstack_params_for_scan), so scan_layers can change between
    runs without retraining."""
    import dataclasses as dc

    import jax
    import orbax.checkpoint as ocp

    from luminaai_tpu.config import Config
    from luminaai_tpu.inference.chat import load_model_for_inference
    from luminaai_tpu.models.transformer import (
        stack_params_for_scan,
        unstack_params_from_scan,
    )

    try:
        _, params, cfg = load_model_for_inference(args.checkpoint)
    except ValueError as e:
        # e.g. an int8 serving export fed back into convert: quantizing
        # quantized codes would write a silently-corrupt checkpoint.
        print(str(e), file=sys.stderr)
        return 1
    is_scanned = any(k.startswith("scan_") for k in params)
    if args.to == "int8":
        # Quantized serving export (ref trainer.py:681,712 GPTQ/quanto
        # model saves): weights stored as int8 codes + scales in the
        # serving compute layout — half the disk/load bytes; chat/serve
        # load it directly with no re-quantization pass.
        from luminaai_tpu.training.quantization import (
            export_quantized_tree,
            quantize_for_serving,
        )

        if is_scanned:
            print("convert --to plain first (int8 export needs the "
                  "per-layer layout)", file=sys.stderr)
            return 1
        qtree, info = quantize_for_serving(params)
        plain, manifest = export_quantized_tree(qtree)
        new_cfg = dc.replace(cfg, quantization_method=None)
        out = Path(args.out).absolute()
        out.mkdir(parents=True, exist_ok=True)
        with ocp.CheckpointManager(out) as mngr:
            mngr.save(
                0,
                args=ocp.args.Composite(
                    state=ocp.args.StandardSave({"params": plain}),
                    metadata=ocp.args.JsonSave(
                        {"step": 0, "config": new_cfg.to_dict(),
                         "converted_from": str(args.checkpoint),
                         "quantization": {"manifest": manifest,
                                          "info": info}}
                    ),
                ),
            )
            mngr.wait_until_finished()
        print(
            f"int8 serving export: {info['quantized_leaves']}/"
            f"{info['total_leaves']} tensors quantized, "
            f"{info['compression']:.2f}x smaller -> {out}"
        )
        return 0
    if args.to == "scan" and is_scanned:
        print("checkpoint is already in scanned layout", file=sys.stderr)
        return 1
    if args.to == "plain" and not is_scanned:
        print("checkpoint is already in per-layer layout", file=sys.stderr)
        return 1

    if args.to == "scan":
        new_cfg = dc.replace(cfg, scan_layers=True)
        new_params = stack_params_for_scan(new_cfg, params)
    else:
        new_params = unstack_params_from_scan(cfg, params)
        new_cfg = dc.replace(cfg, scan_layers=False)

    out = Path(args.out).absolute()
    out.mkdir(parents=True, exist_ok=True)
    with ocp.CheckpointManager(out) as mngr:
        mngr.save(
            0,
            args=ocp.args.Composite(
                state=ocp.args.StandardSave({"params": new_params}),
                metadata=ocp.args.JsonSave(
                    {"step": 0, "config": new_cfg.to_dict(),
                     "converted_from": str(args.checkpoint)}
                ),
            ),
        )
        mngr.wait_until_finished()
    n = sum(x.size for x in jax.tree.leaves(new_params))
    print(f"converted to {args.to} layout: {n / 1e6:.1f}M params -> {out}")
    return 0


def cmd_report(args) -> int:
    """HTML reports (ref utils/reporting.py)."""
    if args.kind == "training":
        from luminaai_tpu.utils.reporting import create_training_report

        if not args.dir:
            print("report training requires --dir EXPERIMENT_DIR",
                  file=sys.stderr)
            return 2
        out = create_training_report(args.dir, args.out)
        if out is None:
            print(
                f"no training_summary.json under {args.dir}", file=sys.stderr
            )
            return 1
        print(f"training report: {out}")
    else:
        from luminaai_tpu.data.tokenizer import ConversationTokenizer
        from luminaai_tpu.utils.reporting import create_data_summary_report

        out = create_data_summary_report(
            args.inputs, ConversationTokenizer(),
            output_path=args.out or "data_summary_report.html",
        )
        print(f"data report: {out}")
    return 0


def cmd_diagnose(args) -> int:
    from luminaai_tpu.utils.environment import (
        check_config_fits,
        connectivity_probe,
        format_diagnostics,
        recommend_preset,
        tpu_runtime_diagnostics,
    )

    # Runtime probes FIRST (ref cuda_debug_script.py's role): a real
    # matmul on the default backend, in this process — a chip belongs to
    # one process, so no child is ever sent to ask for it.
    rt = tpu_runtime_diagnostics()
    print(format_diagnostics(
        include_accelerator=rt["backend"]["status"] == "ok"
    ))
    print("[runtime]")
    for section, vals in rt.items():
        print(f"  {section}:")
        for k, v in vals.items():
            print(f"    {k}: {v}")
    if rt["backend"]["status"] != "ok":
        return 1
    # ICI/DCN connectivity: per-host device visibility + a timed
    # all-reduce per mesh axis, exported as diagnose_* registry gauges
    # (VERDICT "What's missing" #3; the reference's scripts/net.sh role).
    try:
        conn = connectivity_probe()
        print("[connectivity]")
        for section, vals in conn.items():
            print(f"  {section}:")
            for k, v in vals.items():
                print(f"    {k}: {v}")
        if not conn["visibility"]["visibility_ok"]:
            print(
                "    WARNING: global devices != process_count * local "
                "devices — a host is missing part of the slice"
            )
    except Exception as e:
        print(f"connectivity probe unavailable: {e}")
    # Expert-dispatch rung: a REAL timed two-stage (ici-then-dcn)
    # all-to-all over the probe mesh — the hierarchical exchange the
    # a2a MoE dispatch runs (parallel/expert_dispatch.py), priced per
    # stage for the MULTICHIP_r* harness. Single-host fleets simulate
    # the dcn tier so the two-stage path is still exercised; exported
    # as diagnose_expert_a2a_seconds{stage} gauges.
    try:
        from luminaai_tpu.parallel.expert_dispatch import expert_a2a_probe

        a2a = expert_a2a_probe()
        print("[expert-a2a]")
        print(
            f"  mesh: ep={a2a['ep']} (dcn={a2a['dcn']} x ici={a2a['ici']}"
            f"{', simulated dcn' if a2a.get('simulated_dcn') else ''})"
        )
        for stage, rec in a2a["stages"].items():
            print(f"  {stage}:")
            for k, v in rec.items():
                print(f"    {k}: {v}")
    except Exception as e:
        print(f"expert-a2a probe unavailable: {e}")
    try:
        print(f"recommended preset for this fleet: {recommend_preset()}")
        if args.preset:
            from luminaai_tpu.config import ConfigPresets

            fit = check_config_fits(ConfigPresets.get(args.preset))
            print(f"{args.preset}: {json.dumps(fit, indent=2)}")
    except Exception as e:
        print(f"recommendation unavailable: {e}")
    return 0


def cmd_analyze(args) -> int:
    """JAX-aware static analysis gate (docs/static_analysis.md).

    Source layer: analysis/astlint.py rules LX001..LX008 with inline
    `# lumina: disable=LXnnn -- reason` waivers. Abstract layer
    (skippable with --no-audit): the recompile-surface enumerator,
    sharding-coverage auditor and host-transfer detector from
    analysis/jaxpr_audit.py. Exit 1 on any unwaived, unbaselined
    finding or failed audit — this is the CI contract."""
    import luminaai_tpu
    from luminaai_tpu.analysis import astlint

    pkg_dir = os.path.dirname(os.path.abspath(luminaai_tpu.__file__))
    repo_root = os.path.dirname(pkg_dir)
    paths = args.paths or [pkg_dir]
    findings = astlint.lint_paths(paths, rel_to=repo_root)

    # Baseline: accepted legacy findings, keyed rule:path with a count —
    # line numbers shift too easily to key on. A baselined (rule, path)
    # pair only absorbs as many findings as were accepted.
    accepted: Dict[str, int] = {}
    if args.baseline and os.path.exists(args.baseline):
        with open(args.baseline) as fh:
            accepted = dict(json.load(fh).get("accepted", {}))
    budget = dict(accepted)
    unwaived = []
    baselined = 0
    for f in findings:
        if f.waived:
            continue
        key = f"{f.rule}:{f.path}"
        if budget.get(key, 0) > 0:
            budget[key] -= 1
            f.baselined = True
            baselined += 1
            continue
        unwaived.append(f)

    if args.write_baseline:
        counts: Dict[str, int] = {}
        for f in findings:
            if not f.waived:
                key = f"{f.rule}:{f.path}"
                counts[key] = counts.get(key, 0) + 1
        with open(args.write_baseline, "w") as fh:
            json.dump({"accepted": counts}, fh, indent=2, sort_keys=True)
            fh.write("\n")
        print(
            f"baseline written: {args.write_baseline} "
            f"({sum(counts.values())} accepted finding(s))",
            file=sys.stderr,
        )

    verdicts, audit_report = [], {}
    if not args.no_audit:
        from luminaai_tpu.analysis.jaxpr_audit import run_audits

        verdicts, audit_report = run_audits()

    failed_audits = [v.name for v in verdicts if not v.ok]
    exit_code = 1 if (unwaived or failed_audits) else 0

    if args.json:
        doc = astlint.findings_to_json(findings)
        doc["summary"]["baselined"] = baselined
        doc["summary"]["unwaived"] = len(unwaived)
        doc["audits"] = audit_report
        doc["audit_verdicts"] = [
            {"name": v.name, "ok": v.ok, "detail": v.detail}
            for v in verdicts
        ]
        doc["exit_code"] = exit_code
        print(json.dumps(_jsonable(doc), indent=2))
    else:
        print(astlint.format_findings(findings))
        if baselined:
            print(f"baseline: {baselined} finding(s) accepted as legacy")
        for v in verdicts:
            status = "ok" if v.ok else "FAIL"
            print(f"audit {v.name}: {status}")
        surface = audit_report.get("recompile_surface", {})
        for prog, rec in surface.get("programs", {}).items():
            print(
                f"recompile surface [{prog}]: "
                f"{rec['distinct_signatures']} distinct executable(s) "
                f"across {len(rec['variants'])} variant(s)"
            )
        if exit_code:
            print(
                f"analyze: FAIL ({len(unwaived)} unwaived finding(s), "
                f"{len(failed_audits)} failed audit(s))",
                file=sys.stderr,
            )
        else:
            print("analyze: clean")
    return exit_code


def cmd_events(args) -> int:
    """Query the wide-event flight recorder (docs/observability.md).

    Sources, in order of preference: explicit dump files, directories
    (the newest flightrec-*.jsonl inside each — checkpoint dirs are the
    usual argument), or — with no paths — this process's live ring
    buffer (mostly useful in-process / in tests). Filters: --type,
    --grep (regex over the serialized record), --since (epoch ts or
    s/m/h/d duration ago), --tail N. --stats summarizes the filtered
    set (count/rate per type, first/last ts) instead of listing.
    --json prints one JSON record per line for piping into jq."""
    from luminaai_tpu.monitoring.events import (
        events_stats,
        filter_events,
        format_event,
        get_recorder,
        latest_dump,
        parse_since,
        read_events,
    )

    if args.grep:
        import re

        try:
            re.compile(args.grep)
        except re.error as e:
            print(f"bad --grep regex {args.grep!r}: {e}", file=sys.stderr)
            return 2
    since = None
    if getattr(args, "since", None):
        try:
            since = parse_since(args.since)
        except ValueError as e:
            print(f"bad --since value {args.since!r}: {e}", file=sys.stderr)
            return 2

    events: List[Dict[str, Any]] = []
    sources: List[str] = []
    for p in args.paths or []:
        path = p
        if os.path.isdir(p):
            path = latest_dump(p)
            if path is None:
                print(f"no flightrec-*.jsonl dumps under {p}",
                      file=sys.stderr)
                return 2
        if not os.path.exists(path):
            print(f"no such dump: {path}", file=sys.stderr)
            return 2
        events.extend(read_events(path))
        sources.append(path)
    if not args.paths:
        events = get_recorder().snapshot()
        sources.append("<live buffer>")

    total = len(events)
    events = filter_events(
        events, type=args.etype, grep=args.grep,
        request=getattr(args, "request_id", None),
        since=since,
        tail=args.tail if args.tail else None,
    )
    if getattr(args, "stats", False) or getattr(args, "stats_by", None):
        # --by implies --stats (a grouping axis only means something for
        # the summary form).
        stats = events_stats(events, by=getattr(args, "stats_by", None))
        if args.json:
            print(json.dumps(stats, default=str))
        elif stats.get("by"):
            _print_grouped_stats(stats)
        else:
            import time as _time

            def _fmt_ts(ts):
                if not isinstance(ts, (int, float)):
                    return "?"
                return _time.strftime(
                    "%Y-%m-%d %H:%M:%S", _time.localtime(ts)
                )

            print(
                f"{stats['total']} event(s) spanning "
                f"{stats['span_s']}s ({_fmt_ts(stats['first_ts'])} .. "
                f"{_fmt_ts(stats['last_ts'])})"
            )
            header = f"{'type':<24}{'count':>8}{'rate/s':>10}  first .. last"
            print(header)
            print("-" * len(header))
            for t, rec in stats["by_type"].items():
                rate = (
                    f"{rec['rate_per_s']:.3f}"
                    if rec["rate_per_s"] is not None
                    else "-"
                )
                print(
                    f"{t:<24}{rec['count']:>8}{rate:>10}  "
                    f"{_fmt_ts(rec['first_ts'])} .. "
                    f"{_fmt_ts(rec['last_ts'])}"
                )
    elif args.json:
        for ev in events:
            print(json.dumps(ev, default=str))
    else:
        for ev in events:
            print(format_event(ev))
    print(
        f"{len(events)} event(s) shown of {total} from "
        f"{', '.join(sources)}",
        file=sys.stderr,
    )
    return 0


def _print_grouped_stats(stats: Dict[str, Any]) -> None:
    """`lumina events --stats --by tenant|request` table: biggest
    burners first, each with its rate and top event types."""
    import time as _time

    def _fmt_ts(ts):
        if not isinstance(ts, (int, float)):
            return "?"
        return _time.strftime("%H:%M:%S", _time.localtime(ts))

    print(
        f"{stats['total']} event(s) spanning {stats['span_s']}s, "
        f"grouped by {stats['by']}"
    )
    header = (
        f"{stats['by']:<26}{'count':>8}{'rate/s':>10}  "
        f"first .. last  top types"
    )
    print(header)
    print("-" * len(header))
    for key, rec in stats["groups"].items():
        rate = (
            f"{rec['rate_per_s']:.3f}"
            if rec["rate_per_s"] is not None
            else "-"
        )
        top = ", ".join(
            f"{t}={n}"
            for t, n in sorted(
                rec["by_type"].items(), key=lambda kv: (-kv[1], kv[0])
            )[:3]
        )
        print(
            f"{key:<26}{rec['count']:>8}{rate:>10}  "
            f"{_fmt_ts(rec['first_ts'])} .. {_fmt_ts(rec['last_ts'])}  "
            f"{top}"
        )


def _top_sources(args):
    """Resolve `lumina top`'s data source into (fetch_fn, source_label).

    fetch_fn() -> (history_dict, slo_dict_or_None, fleet_dict_or_None).
    Exit-2 errors raise SystemExit here so the caller stays flat."""
    import urllib.error
    import urllib.request

    from luminaai_tpu.monitoring.timeseries import (
        get_history,
        latest_history_dump,
        load_history,
    )

    url = getattr(args, "url", None)
    path = getattr(args, "source", None)
    if url:
        base = url.rstrip("/")

        def fetch_url():
            # --url points at either a replica (history + slo) or a
            # router (fleet table). Probe both shapes; a missing route
            # 404s, which just means the other kind of process.
            def _get(route):
                try:
                    with urllib.request.urlopen(
                        f"{base}{route}", timeout=10
                    ) as r:
                        return json.loads(r.read())
                except urllib.error.HTTPError:
                    return None

            history = _get("/metrics/history")
            slo = _get("/slo")
            fleet = _get("/fleet")
            if history is None and fleet is None:
                print(
                    f"{base} answers neither /metrics/history (replica) "
                    "nor /fleet (router)", file=sys.stderr,
                )
                raise SystemExit(2)
            return history or {"series": {}}, slo, fleet

        return fetch_url, base
    if path:
        resolved = path
        if os.path.isdir(path):
            resolved = latest_history_dump(path)
            if resolved is None:
                print(f"no tshist-*.json dumps under {path}",
                      file=sys.stderr)
                raise SystemExit(2)
        if not os.path.exists(resolved):
            print(f"no such history dump: {resolved}", file=sys.stderr)
            raise SystemExit(2)

        def fetch_file(resolved=resolved):
            try:
                doc = load_history(resolved)
            except (ValueError, json.JSONDecodeError) as e:
                print(f"bad history dump {resolved}: {e}", file=sys.stderr)
                raise SystemExit(2)
            # Dumps written by a live SLO engine embed the verdict table
            # so the post-mortem view matches the live one.
            return doc, doc.get("slo"), None

        return fetch_file, resolved

    def fetch_live():
        ring = get_history()
        if ring is None:
            print(
                "no live history ring in this process (start a trainer/"
                "server with SLO on, or pass a dump path / --url)",
                file=sys.stderr,
            )
            raise SystemExit(2)
        # Read-only attach: sampling here would split counter deltas
        # into refresh-sized intervals AND fire any attached SLO
        # engine's evaluation — viewing the dashboard must never skew
        # the data or advance the alert state machine. Between-tick
        # staleness (≤ one sample interval) is the honest trade. The
        # engine advertised on the ring supplies the verdict table from
        # its CACHED last evaluation (no state advance).
        engine = getattr(ring, "slo", None)
        return ring.snapshot(), (
            engine.verdicts() if engine is not None else None
        ), None

    return fetch_live, "<live ring>"


def cmd_top(args) -> int:
    """Live operator dashboard over the time-series ring
    (docs/observability.md "SLOs & burn rate"): sparklines for
    throughput/latency/occupancy, per-tenant top-K, and the SLO
    burn-rate verdict table. Sources: --url against a serving process
    (GET /metrics/history + /slo), a tshist-*.json dump (or a directory
    holding them), or — with neither — this process's live ring.
    --once renders a single frame; --json emits the machine form."""
    from luminaai_tpu.monitoring.top import render_top, top_payload

    try:
        fetch, source = _top_sources(args)
    except SystemExit as e:
        return int(e.code or 2)

    def frame():
        try:
            history, slo, fleet = fetch()
        except SystemExit as e:  # bad dump discovered on read
            raise
        except Exception as e:
            print(f"fetch failed: {e}", file=sys.stderr)
            raise SystemExit(2)
        if args.json:
            return json.dumps(
                top_payload(
                    history, slo,
                    window_s=args.window, top_k=args.top_k,
                    fleet=fleet,
                ),
                default=str,
            )
        return render_top(
            history, slo, source=source,
            window_s=args.window, top_k=args.top_k,
            fleet=fleet,
        )

    try:
        if args.once or args.json:
            print(frame())
            return 0
        import time as _time

        while True:  # refresh loop; ^C exits
            out = frame()
            # ANSI clear + home keeps the frame in place like top(1).
            sys.stdout.write("\x1b[2J\x1b[H" + out)
            sys.stdout.flush()
            _time.sleep(max(0.2, float(args.interval)))
    except KeyboardInterrupt:
        return 0
    except SystemExit as e:
        return int(e.code or 2)


def cmd_verify_checkpoint(args) -> int:
    """Walk a checkpoint directory's integrity manifests
    (docs/resilience.md "Durable I/O"): per-step ok / corrupt /
    unmanifested. Exit 0 when every verified step is intact
    (unmanifested legacy steps are reported, not failed), 1 on any
    corruption, 2 when the directory/step does not exist — the same
    exit-code contract shape as `lumina events`."""
    from luminaai_tpu.training.checkpoint import verify_checkpoint_dir

    try:
        report = verify_checkpoint_dir(
            args.dir, step=args.step, mode=args.mode
        )
    except FileNotFoundError as e:
        print(str(e), file=sys.stderr)
        return 2
    if args.json:
        print(json.dumps(report, default=str))
    else:
        header = f"{'step':>8}  {'status':<14}{'files':>7}{'hashed':>8}  detail"
        print(f"checkpoint manifests under {report['root']} "
              f"(mode={report['mode']})")
        print(header)
        print("-" * len(header))
        for s, rep in sorted(report["steps"].items()):
            detail = ""
            if rep["mismatches"]:
                m = rep["mismatches"][0]
                detail = f"{m['file']}: {m['reason']}"
                if len(rep["mismatches"]) > 1:
                    detail += f" (+{len(rep['mismatches']) - 1} more)"
            print(
                f"{s:>8}  {rep['status']:<14}{rep['files']:>7}"
                f"{rep['hashed']:>8}  {detail}"
            )
        print(
            f"{len(report['ok'])} ok, {len(report['corrupt'])} corrupt, "
            f"{len(report['unmanifested'])} unmanifested"
        )
    if not report["steps"]:
        print(f"no checkpoint steps under {args.dir}", file=sys.stderr)
        return 2
    return 1 if report["corrupt"] else 0


def cmd_presets(args) -> int:
    from luminaai_tpu.config import ConfigPresets

    info = ConfigPresets.get_preset_info()
    if args.json:
        print(json.dumps(info, indent=2))
        return 0
    header = (
        f"{'preset':<16}{'hidden':>8}{'layers':>8}{'params':>12}"
        f"{'active':>12}{'experts':>8}{'seq':>8}"
    )
    print(header)
    print("-" * len(header))
    for name, d in info.items():
        print(
            f"{name:<16}{d['hidden_size']:>8}{d['num_layers']:>8}"
            f"{d['total_params'] / 1e6:>10.0f}M{d['active_params'] / 1e6:>10.0f}M"
            f"{d['num_experts']:>8}{d['seq_length']:>8}"
        )
    return 0


# ---------------------------------------------------------------------------
# plumbing
# ---------------------------------------------------------------------------
def _jsonable(obj: Any) -> Any:
    if isinstance(obj, dict):
        return {k: _jsonable(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_jsonable(v) for v in obj]
    if isinstance(obj, (np.floating, np.integer)):
        return obj.item()
    if isinstance(obj, np.ndarray):
        return obj.tolist()
    if isinstance(obj, float) and not np.isfinite(obj):
        return str(obj)
    return obj


def _install_signal_handlers(trainer):
    """SIGINT/SIGTERM → graceful preemption (ref Main.py:1126
    setup_signal_handlers, rebuilt for correctness): the FIRST signal only
    arms `trainer.request_stop()` — the train loop finishes the step in
    flight, runs a BLOCKING emergency save at the boundary, and cmd_train
    exits RESUMABLE_EXIT. Saving from inside the handler (the old
    behavior) raced the dispatched train step and could checkpoint a
    half-updated state. A SECOND signal escalates: save whatever state
    exists right now and exit immediately.

    Returns a callable that puts the previous handlers back: the handler
    closes over the trainer, and a process that goes on after training
    (chip_smoke.py serves next) must not keep the whole TrainState alive
    on the device through it."""
    seen = {"n": 0}

    def handler(sig, frame):  # pragma: no cover - signal-driven
        seen["n"] += 1
        if seen["n"] == 1:
            print(
                f"\nsignal {sig}: stopping at the next step boundary "
                "(emergency checkpoint + exact data cursor); signal again "
                "to force an immediate save and exit"
            )
            trainer.request_stop(f"signal {sig}")
            return
        print(f"\nsignal {sig} (again): immediate emergency save...")
        try:
            trainer.checkpoints.emergency_save(
                trainer.state, trainer.global_step, f"signal {sig} forced",
                data_state=trainer._data_state(),
            )
            # Forensics for the forced exit: the last N step/alert
            # events ride next to the save (lumina events replays them).
            trainer._dump_flight_record(f"signal_{sig}_forced")
            print("state saved; exiting")
        except Exception as e:
            print(f"emergency save failed: {e}")
        sys.exit(RESUMABLE_EXIT)

    previous = {}
    try:
        for sig in (signal.SIGINT, signal.SIGTERM):
            previous[sig] = signal.signal(sig, handler)
    except ValueError:  # pragma: no cover - non-main thread (tests)
        pass

    def restore() -> None:
        for sig, old in previous.items():
            signal.signal(sig, old)

    return restore


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="luminaai_tpu",
        description="TPU-native adaptive training framework",
    )
    sub = p.add_subparsers(dest="command", required=True)

    def add_config_flags(sp):
        sp.add_argument("--preset", default="debug")
        sp.add_argument("--config", help="yaml/json config file")
        sp.add_argument("--lr", type=float)
        sp.add_argument("--batch-size", dest="batch_size", type=int)
        sp.add_argument("--seq-length", dest="seq_length", type=int)
        sp.add_argument("--steps", type=int, help="max optimizer steps")
        sp.add_argument("--epochs", type=int)
        sp.add_argument("--grad-accum", dest="grad_accum", type=int)
        sp.add_argument("--precision", choices=["fp32", "bf16", "mixed_bf16", "auto"])
        sp.add_argument("--output-dir", dest="output_dir")
        sp.add_argument("--experiment")
        sp.add_argument("--no-moe", action="store_true")
        sp.add_argument("--no-flash", action="store_true")
        sp.add_argument(
            "--moe-dispatch", dest="moe_dispatch",
            choices=["sort", "gather", "einsum", "gmm"],
            help="expert dispatch engine (docs/sparse_architectures.md; "
                 "gmm = ragged grouped matmul, single-chip)",
        )
        sp.add_argument(
            "--attention-window", dest="attention_window", type=int,
            help="sliding-window attention: attend to the last N "
                 "positions only (O(S*W) long-context attention)",
        )
        sp.add_argument(
            "--auto-hardware", action="store_true",
            help="optimize parallelism for detected devices",
        )
        prof = sp.add_argument_group(
            "performance attribution (docs/observability.md)"
        )
        prof.add_argument(
            "--profile-steps", dest="profile_steps", type=int,
            help="capture a jax.profiler trace for N steps and export the "
                 "per-subsystem step breakdown (gauges + attribution.jsonl)",
        )
        prof.add_argument(
            "--profile-start", dest="profile_start", type=int,
            help="first profiled step (default 3: skip the compile step)",
        )
        prof.add_argument(
            "--profile-dir", dest="profile_dir",
            help="trace output dir (default OUTPUT_DIR/profile)",
        )
        prof.add_argument(
            "--cost-analysis", dest="cost_analysis", action="store_true",
            help="export XLA compiled-cost gauges (flops/bytes/HBM) and "
                 "the analytic-vs-compiled MFU cross-check at first compile",
        )
        wd = sp.add_argument_group(
            "hang watchdog (docs/observability.md 'Goodput & sentinels')"
        )
        wd.add_argument(
            "--watchdog", dest="watchdog",
            action=argparse.BooleanOptionalAction, default=None,
            help="heartbeat hang detection over the train loop "
                 "(default: on; fires hang_suspected + stack/ring dumps "
                 "when a step window exceeds k x rolling median)",
        )
        wd.add_argument(
            "--watchdog-abort", dest="watchdog_abort", action="store_true",
            help="exit 75 (resumable) after a confirmed hang is dumped, "
                 "so the orchestrator restarts instead of burning the "
                 "reservation",
        )
        wd.add_argument(
            "--watchdog-k", dest="watchdog_k", type=float,
            help="robust threshold multiplier over the rolling median "
                 "step window (default 10)",
        )
        wd.add_argument(
            "--watchdog-floor", dest="watchdog_floor", type=float,
            help="minimum stall seconds before the watchdog can fire "
                 "(default 30)",
        )
        so = sp.add_argument_group(
            "SLO engine (docs/observability.md 'SLOs & burn rate')"
        )
        so.add_argument(
            "--slo", dest="slo",
            action=argparse.BooleanOptionalAction, default=None,
            help="windowed history ring + burn-rate alerts over the "
                 "default train objectives (default: on)",
        )
        so.add_argument(
            "--slo-config", dest="slo_config",
            help="JSON file REPLACING the default objectives "
                 "(docs/observability.md lists the schema)",
        )
        par = sp.add_argument_group("parallelism (docs/parallelism.md)")
        par.add_argument("--dp", type=int, help="data axis (-1 = auto)")
        par.add_argument(
            "--pp", type=int,
            help="pipeline stages (1F1B schedule; pipeline_schedule=gpipe "
                 "via --config for A/B)",
        )
        par.add_argument("--fsdp", type=int, help="ZeRO-3-style shard ways")
        par.add_argument("--tp", type=int, help="tensor-parallel ways")
        par.add_argument("--ep", type=int, help="expert-parallel ways")
        par.add_argument("--sp", type=int,
                         help="sequence/ring-attention ways")

    t = sub.add_parser("train", help="train a model")
    add_config_flags(t)
    t.add_argument("--data", help="jsonl conversations (or text with --packed)")
    t.add_argument("--tokenizer",
                   help="tokenizer backend: byte | bpe:PATH | tiktoken:NAME "
                        "| hf:NAME")
    t.add_argument("--eval-data", dest="eval_data")
    t.add_argument("--packed", action="store_true",
                   help="treat --data as base-training text jsonl")
    t.add_argument("--synthetic", action="store_true",
                   help="train on synthetic pattern data (smoke test)")
    t.add_argument("--adaptive", action=argparse.BooleanOptionalAction,
                   default=True, help="run under the adaptive orchestrator")
    t.add_argument("--auto-epochs", action="store_true",
                   help="chinchilla-style step budget from dataset size")
    t.add_argument("--resume", action="store_true")
    t.add_argument("--quiet", action="store_true")
    t.add_argument("--oom-protect", dest="oom_protect",
                   action=argparse.BooleanOptionalAction, default=True,
                   help="backoff ladder on device OOM (microbatch split, "
                        "then batch halving)")
    t.set_defaults(fn=cmd_train)

    r = sub.add_parser("resume", help="resume training from output dir")
    add_config_flags(r)
    r.add_argument("--data")
    r.add_argument("--tokenizer")
    r.add_argument("--eval-data", dest="eval_data")
    r.add_argument("--packed", action="store_true")
    r.add_argument("--synthetic", action="store_true")
    r.add_argument("--adaptive", action=argparse.BooleanOptionalAction,
                   default=True)
    r.add_argument("--auto-epochs", action="store_true")
    r.add_argument("--quiet", action="store_true")
    r.add_argument("--oom-protect", dest="oom_protect",
                   action=argparse.BooleanOptionalAction, default=True)
    r.set_defaults(fn=cmd_train, resume=True)
    t.set_defaults(resume=False)

    c = sub.add_parser("chat", help="interactive chat with a checkpoint")
    c.add_argument("--checkpoint", help="checkpoint dir (auto-discovers latest)")
    c.add_argument("--temperature", type=float, default=0.8)
    c.add_argument("--top-p", dest="top_p", type=float, default=0.9)
    c.add_argument("--max-new-tokens", dest="max_new_tokens", type=int,
                   default=256)
    c.add_argument("--prompt", help="one-shot prompt (non-interactive)")
    c.add_argument("--verbose", action="store_true")
    c.add_argument("--secure", action="store_true",
                   help="require auth; rate-limit and validate inputs")
    c.add_argument("--user")
    c.add_argument("--password")
    c.add_argument("--quantize", choices=["int8", "int4"],
                   help="weight-only quantization for serving")
    c.add_argument("--kv-cache-dtype", choices=["bf16", "int8"],
                   help="decode KV cache storage (int8 halves cache HBM)")
    c.add_argument("--adapter",
                   help="LoRA adapter (.npz from finetune) merged at load")
    c.set_defaults(fn=cmd_chat)

    ft = sub.add_parser(
        "finetune", help="LoRA fine-tune against a frozen base checkpoint"
    )
    ft.add_argument("--checkpoint", required=True, help="base checkpoint dir")
    ft.add_argument("--data", required=True, help="jsonl conversations")
    ft.add_argument("--out", required=True, help="adapter output dir")
    ft.add_argument("--rank", type=int, default=8)
    ft.add_argument("--alpha", type=float, default=16.0)
    ft.add_argument("--lr", type=float, default=1e-4)
    ft.add_argument("--steps", type=int, default=100)
    ft.add_argument("--batch-size", dest="batch_size", type=int)
    ft.add_argument("--adapt-experts", action="store_true",
                    help="also adapt MoE expert kernels (per-expert factors)")
    ft.add_argument("--merge-out", dest="merge_out",
                    help="also export base+adapter as a merged checkpoint")
    ft.set_defaults(fn=cmd_finetune)

    sv = sub.add_parser("serve", help="HTTP chat/completion server")
    sv.add_argument("--checkpoint", help="checkpoint dir (auto-discovers)")
    sv.add_argument("--host", default="127.0.0.1")
    sv.add_argument("--port", type=int, default=5001)
    sv.add_argument("--secure", action="store_true",
                    help="token auth + rate limit + input validation")
    sv.add_argument("--user", help="bootstrap user (secure mode)")
    sv.add_argument("--password", help="bootstrap password (secure mode)")
    sv.add_argument("--quantize", choices=["int8", "int4"])
    sv.add_argument("--kv-cache-dtype", choices=["bf16", "int8"],
                    help="decode KV cache storage (int8 halves cache HBM)")
    sv.add_argument("--adapter", help="LoRA adapter merged at load")
    sv.add_argument("--num-slots", dest="num_slots", type=int, default=8,
                    help="continuous-batching KV pool slots "
                         "(concurrent decode lanes)")
    sv.add_argument("--page-size", dest="page_size", type=int, default=128,
                    help="KV pool page granularity in tokens")
    sv.add_argument("--prefill-chunk", dest="prefill_chunk_tokens",
                    type=int, default=None,
                    help="chunked-prefill chunk size in tokens: long "
                         "admissions prefill one chunk per decode tick "
                         "instead of stalling the batch (default: the "
                         "config's prefill_chunk_size; 0 disables)")
    sv.add_argument("--admission-window-ms", dest="admission_window_ms",
                    type=float, default=0.0,
                    help="wait this long for same-key peers before a "
                         "generation's first decode step")
    sv.add_argument("--no-telemetry", dest="no_telemetry",
                    action="store_true",
                    help="skip hot-path metric recording (/metrics stays "
                         "up but latency histograms stay empty)")
    sv.add_argument("--trace-jsonl", dest="trace_jsonl",
                    help="write request/prefill/stream spans to this "
                         "JSONL file (tracing is off without it)")
    sv.add_argument("--trace-jax", dest="trace_jax", action="store_true",
                    help="mirror spans as jax.profiler TraceAnnotations "
                         "(visible when a device trace is captured)")
    sv.add_argument("--latency-buckets", dest="latency_buckets",
                    help="comma-separated histogram bucket bounds in "
                         "seconds (default spans 0.5ms..30s)")
    sv.add_argument("--request-timeout", dest="request_timeout_s",
                    type=float, default=None,
                    help="per-request deadline in seconds: overdue lanes "
                         "are evicted (504 / SSE error). A request's own "
                         "timeout_s can only shorten it. Default: none")
    sv.add_argument("--max-queue-depth", dest="max_queue_depth",
                    type=int, default=128,
                    help="admission queue cap: beyond it, generation "
                         "requests get 503 + Retry-After instead of "
                         "queuing unboundedly (0 disables shedding)")
    sv.add_argument("--drain-grace", dest="drain_grace_s", type=float,
                    default=30.0,
                    help="seconds SIGTERM waits for in-flight generations "
                         "to finish before shutdown")
    sv.add_argument("--flight-dir", dest="flight_dir",
                    help="where drain dumps the wide-event flight record "
                         "(flightrec-*.jsonl; default: the checkpoint "
                         "dir, else the working dir)")
    sv.add_argument("--prefix-cache-pages", dest="prefix_cache_pages",
                    type=int, default=None,
                    help="radix prefix cache budget in KV pool pages: "
                         "admissions splice cached shared-prefix pages "
                         "(system prompts, few-shot templates) instead "
                         "of re-prefilling them; LRU-evicted beyond the "
                         "budget (default: the config's "
                         "prefix_cache_pages; 0 disables)")
    sv.add_argument("--prefix-cache-tenant-quota",
                    dest="prefix_cache_tenant_quota", type=int,
                    default=None,
                    help="max cached pages one tenant may own — at "
                         "quota a tenant evicts its OWN pages, never "
                         "other tenants' (0 = unbounded)")
    sv.add_argument("--tenant-rate", dest="tenant_rate_per_s",
                    type=float, default=None,
                    help="per-tenant token-bucket admission rate "
                         "(requests/sec refill; unset disables the "
                         "bucket gate)")
    sv.add_argument("--tenant-burst", dest="tenant_burst", type=int,
                    default=None,
                    help="per-tenant token-bucket burst capacity "
                         "(default: ~1s of --tenant-rate)")
    sv.add_argument("--no-watchdog", dest="no_watchdog",
                    action="store_true",
                    help="disable the decode-loop hang watchdog "
                         "(hang_suspected events + stack/ring dumps on a "
                         "stuck decode step)")
    sv.add_argument("--watchdog-abort", dest="watchdog_abort",
                    action="store_true",
                    help="exit 75 (resumable) after a confirmed decode "
                         "hang is dumped, so the orchestrator restarts "
                         "the replica")
    sv.add_argument("--watchdog-k", dest="watchdog_k", type=float,
                    default=None,
                    help="robust threshold multiplier over the rolling "
                         "median decode step (default 10)")
    sv.add_argument("--watchdog-floor", dest="watchdog_floor", type=float,
                    default=None,
                    help="minimum stall seconds before the serving "
                         "watchdog can fire (default 30; raise above "
                         "your worst-case decode compile before "
                         "enabling --watchdog-abort)")
    sv.add_argument("--no-slo", dest="no_slo", action="store_true",
                    help="disable the history ring + SLO burn-rate "
                         "engine (GET /slo and /metrics/history then "
                         "answer 404)")
    sv.add_argument("--slo-config", dest="slo_config",
                    help="JSON file REPLACING the default serve "
                         "objectives (docs/observability.md 'SLOs & "
                         "burn rate')")
    sv.add_argument("--healthz-stale-after", dest="healthz_stale_after",
                    type=float, default=None,
                    help="seconds since the last decode tick (while "
                         "busy) or train step after which /healthz "
                         "reports status=degraded (still 200) so "
                         "probes catch wedged-but-alive processes "
                         "before the watchdog aborts")
    sv.add_argument("--replicas", type=int, default=1,
                    help="spawn N replica serve processes (ports "
                         "port+1..port+N) fronted by the replica "
                         "router on --port — the one-command dev "
                         "fleet (docs/serving.md 'Replica router')")
    sv.add_argument("--page-share", dest="page_share", default=None,
                    help="router URL for cross-replica KV page sharing: "
                         "report harvested prefix-chain keys there and "
                         "pull indexed pages from sibling replicas on "
                         "cold admissions (--replicas wires this "
                         "automatically; docs/serving.md 'Cross-replica "
                         "prefix sharing')")
    sv.add_argument("--page-share-self", dest="page_share_self",
                    default=None,
                    help="this replica's own base URL, as siblings "
                         "should reach it for GET /pages/<key> "
                         "(required for reporting; --replicas sets it)")
    sv.add_argument("--page-pull-timeout", dest="page_pull_timeout",
                    type=float, default=None,
                    help="seconds one whole remote page pull may take "
                         "(lookup + transfers) before the admission "
                         "degrades to local prefill (default 2)")
    sv.add_argument("--page-share-max-inflight",
                    dest="page_share_max_inflight", type=int,
                    default=None,
                    help="max concurrent remote page pulls per replica "
                         "(default 2); further cold admissions just "
                         "prefill locally")
    sv.set_defaults(fn=cmd_serve)

    rt = sub.add_parser(
        "route",
        help="data-plane router fronting N serve replicas: health "
             "probing, circuit breakers, affine dispatch + failover, "
             "hedged retries",
    )
    rt.add_argument("--replica", dest="replicas", action="append",
                    required=True,
                    help="replica base URL (repeat per replica)")
    rt.add_argument("--host", default="127.0.0.1")
    rt.add_argument("--port", type=int, default=8000)
    rt.add_argument("--probe-interval", dest="probe_interval_s",
                    type=float, default=None,
                    help="seconds between /healthz+/slo probe rounds "
                         "(default: config router_probe_interval_s)")
    rt.add_argument("--breaker-failures", dest="breaker_failures",
                    type=int, default=None,
                    help="consecutive failures opening a replica's "
                         "circuit breaker (default: config)")
    rt.add_argument("--breaker-cooldown", dest="breaker_cooldown_s",
                    type=float, default=None,
                    help="seconds an open breaker waits before its "
                         "half-open probe (default: config)")
    rt.add_argument("--max-failovers", dest="max_failovers", type=int,
                    default=None,
                    help="extra candidates a failed dispatch may try "
                         "(capped at replicas-1; default: config)")
    rt.add_argument("--request-timeout", dest="request_timeout_s",
                    type=float, default=None,
                    help="per-attempt replica timeout in seconds")
    rt.add_argument("--hedge", action="store_true",
                    help="hedged dispatch: fire a second replica for "
                         "short non-stream requests after a p95-based "
                         "delay; first answer wins, loser cancelled")
    rt.add_argument("--hedge-delay", dest="hedge_delay_s", type=float,
                    default=None,
                    help="fixed hedge delay in seconds (default: the "
                         "fleet's observed p95)")
    rt.add_argument("--hedge-budget", dest="hedge_budget", type=float,
                    default=None,
                    help="max hedged fraction of non-stream traffic "
                         "(default: config router_hedge_budget)")
    rt.add_argument("--hedge-max-tokens", dest="hedge_max_tokens",
                    type=int, default=None,
                    help="only hedge requests asking for at most this "
                         "many new tokens (default: config)")
    rt.add_argument("--flight-dir", dest="flight_dir",
                    help="dump the router's wide-event flight record "
                         "here on exit (flightrec-*.jsonl)")
    rt.set_defaults(fn=cmd_route)

    d = sub.add_parser("data", help="dataset utilities")
    d.add_argument(
        "action",
        choices=["sample", "oasst", "validate", "acquire", "blend",
                 "train-tokenizer"],
    )
    d.add_argument("--sources", nargs="*",
                   help="blend: name=weight=glob triples")
    d.add_argument("--in", dest="inp")
    d.add_argument("--out")
    d.add_argument("--count", type=int, default=100)
    d.add_argument("--vocab-size", dest="vocab_size", type=int, default=4096,
                   help="train-tokenizer: target vocab (incl. 256 bytes)")
    d.add_argument("--max-per-file", dest="max_per_file", type=int,
                   default=None,
                   help="acquire: rotate output shards after N conversations "
                        "(config.max_conversations_per_file equivalent)")
    d.set_defaults(fn=cmd_data)

    cv = sub.add_parser(
        "convert",
        help="convert checkpoint layout (scan <-> plain) or export an "
             "int8-quantized serving checkpoint",
    )
    cv.add_argument("--checkpoint", required=True)
    cv.add_argument("--to", choices=["scan", "plain", "int8"], required=True)
    cv.add_argument("--out", required=True)
    cv.set_defaults(fn=cmd_convert)

    e = sub.add_parser("evaluate", help="perplexity/loss on a dataset")
    e.add_argument("--checkpoint", required=True)
    e.add_argument("--data", required=True, help="jsonl conversations")
    e.add_argument("--batch-size", dest="batch_size", type=int)
    e.add_argument("--max-batches", dest="max_batches", type=int, default=0)
    e.set_defaults(fn=cmd_evaluate)

    rp = sub.add_parser("report", help="HTML reports")
    rp.add_argument("kind", choices=["training", "data"])
    rp.add_argument("--dir", help="experiment dir (training report)")
    rp.add_argument("--out")
    rp.add_argument("inputs", nargs="*", help="jsonl files (data report)")
    rp.set_defaults(fn=cmd_report)

    g = sub.add_parser("diagnose", help="system diagnostics")
    g.add_argument("--preset", help="also check whether PRESET fits")
    g.set_defaults(fn=cmd_diagnose)

    an = sub.add_parser(
        "analyze",
        help="static analysis gate: AST lint rules + abstract-eval audits",
    )
    an.add_argument(
        "paths", nargs="*",
        help="files/dirs to lint (default: the luminaai_tpu package)",
    )
    an.add_argument("--json", action="store_true",
                    help="machine-readable report on stdout")
    an.add_argument("--baseline",
                    help="JSON file of accepted legacy findings")
    an.add_argument("--write-baseline", metavar="FILE",
                    help="write current unwaived findings as a baseline")
    an.add_argument("--no-audit", action="store_true",
                    help="skip the abstract-eval auditors (lint only)")
    an.set_defaults(fn=cmd_analyze)

    ev = sub.add_parser(
        "events",
        help="query the wide-event flight recorder (flightrec-*.jsonl "
             "dumps or the live buffer)",
    )
    ev.add_argument(
        "paths", nargs="*",
        help="dump files or directories holding flightrec-*.jsonl "
             "(e.g. a checkpoint dir); default: the in-process buffer",
    )
    ev.add_argument("--tail", type=int, default=0,
                    help="show only the last N matching events")
    ev.add_argument("--grep", help="regex over the serialized record")
    ev.add_argument("--type", dest="etype",
                    help="only events of this type (e.g. request_admitted)")
    ev.add_argument("--request", dest="request_id",
                    help="only events of one request id: its full "
                         "lifecycle (admission -> prefix_hit -> chunks "
                         "-> completion) — the cache-splice debugging "
                         "loop")
    ev.add_argument("--since", dest="since",
                    help="only events at/after this floor: an epoch "
                         "timestamp, or a duration ago with an s/m/h/d "
                         "suffix (e.g. 90s, 5m, 2h)")
    ev.add_argument("--stats", action="store_true",
                    help="summarize instead of listing: count + rate per "
                         "event type, first/last timestamps (applies "
                         "after the other filters)")
    ev.add_argument("--by", dest="stats_by", choices=("tenant", "request"),
                    help="with --stats: group the summary by identity — "
                         "per-tenant (or per-request) counts, rates and "
                         "type breakdowns, biggest burners first")
    ev.add_argument("--json", action="store_true",
                    help="one JSON record per line (pipe into jq); with "
                         "--stats, the summary as one JSON object")
    ev.set_defaults(fn=cmd_events)

    tp = sub.add_parser(
        "top",
        help="live operator dashboard over the time-series ring "
             "(sparklines + SLO burn-rate table)",
    )
    tp.add_argument(
        "source", nargs="?",
        help="tshist-*.json history dump, or a directory holding them "
             "(e.g. a checkpoint dir); default: this process's live ring",
    )
    tp.add_argument("--url",
                    help="attach to a serving process instead: polls "
                         "GET /metrics/history + /slo (e.g. "
                         "http://127.0.0.1:5001)")
    tp.add_argument("--once", action="store_true",
                    help="render one frame and exit (scripts, tests)")
    tp.add_argument("--json", action="store_true",
                    help="machine form of the frame (implies --once)")
    tp.add_argument("--interval", type=float, default=2.0,
                    help="refresh seconds for the live view (default 2)")
    tp.add_argument("--window", type=float, default=None,
                    help="restrict rows/tenant sums to the last N "
                         "seconds (default: everything retained)")
    tp.add_argument("--top-k", dest="top_k", type=int, default=4,
                    help="tenants shown in the top-K table (default 4)")
    tp.set_defaults(fn=cmd_top)

    vc = sub.add_parser(
        "verify-checkpoint",
        help="verify checkpoint integrity manifests (exit 1 on corruption)",
    )
    vc.add_argument("dir", help="checkpoint directory (holds <step>/ dirs)")
    vc.add_argument("--step", type=int, default=None,
                    help="verify one step only (default: every step)")
    vc.add_argument("--mode", choices=("full", "sample"), default="full",
                    help="full = hash every manifested file; sample = "
                         "sizes for all, hashes for a deterministic "
                         "subset (fast mode for huge checkpoints)")
    vc.add_argument("--json", action="store_true")
    vc.set_defaults(fn=cmd_verify_checkpoint)

    s = sub.add_parser("presets", help="list model presets")
    s.add_argument("--json", action="store_true")
    s.set_defaults(fn=cmd_presets)
    return p


_COMPILING_COMMANDS = (
    cmd_train, cmd_chat, cmd_finetune, cmd_serve, cmd_evaluate,
    cmd_diagnose,
)


def main(argv: Optional[List[str]] = None) -> int:
    logging.basicConfig(
        level=logging.INFO,
        format="%(asctime)s %(name)s %(levelname)s %(message)s",
    )
    args = build_parser().parse_args(argv)
    fleet = args.fn is cmd_serve and getattr(args, "replicas", 1) > 1
    if args.fn in _COMPILING_COMMANDS and not fleet:
        # Before the first compile; the fleet launcher stays off jax.
        from luminaai_tpu.utils.environment import configure_compile_cache

        configure_compile_cache()
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
