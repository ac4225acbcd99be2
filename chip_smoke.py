#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

Drives the main path once through the entry points a user calls, on ONE
TPU, at the full width and depth of the flagship MoE
(`ConfigPresets.flagship()`: 757M total / 238M active, batch 16 x seq
2048, Pallas flash attention + megablox gmm), with random weights:

    device      refuse anything but a TPU; print what jax found
    train       `lumina train --config <flagship> --synthetic --steps 8`
    checkpoint  `lumina verify-checkpoint` on what the trainer saved
    serve       the stack `lumina serve` builds, restored from that
                checkpoint, answering JSON and SSE requests over HTTP

    python chip_smoke.py            # one chip (what the driver runs)
    python chip_smoke.py --chips 4  # builder only: the sharded step
                                    # against the one-device step

One process, no child that needs the chip. Any phase that fails exits
non-zero and prints no result line. The last stdout line of a passing run
is exactly
    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}
Everything above it is smoke output on one run, not a benchmark.

Each phase is a function of a Config; main() alone owns the refusal and
the flagship, so tests/test_chip_smoke.py and the CPU rehearsal call the
phases with a tiny config and this script has no option for them.
"""

from __future__ import annotations

import argparse
import concurrent.futures
import dataclasses
import gc
import json
import os
import shutil
import sys
import tempfile
import threading
import time
import urllib.request
from typing import Any, Dict, List, Optional, Sequence

# Pallas kernels the flagship's compiled train step must contain on the
# chip (monitoring/attribution.kernel_census names).
FLAGSHIP_TRAIN_KERNELS = (
    "flash_fwd", "flash_bwd_dq", "flash_bwd_dkv", "gmm", "tgmm",
)
TRAIN_STEPS = 8
# |sharded - one-device| bound on loss and relative grad norm: the
# tolerance tests/test_sharding.py holds sharded layouts to.
FOUR_CHIP_TOL = 5e-2


class SmokeFailure(Exception):
    """A phase found the system wrong; the run exits non-zero."""


def check(cond: bool, message: str) -> None:
    if not cond:
        raise SmokeFailure(message)


def require_kernels_in(kernels: Dict[str, int], required: Sequence[str],
                       what: str, why: Any = None) -> None:
    """`kernels` is monitoring/attribution.kernel_census of a compiled
    program: the branch that picked a kernel is not trusted, the program
    text is."""
    missing = [k for k in required if not kernels.get(k)]
    check(not missing, f"{what} lacks Pallas kernels {missing}; it holds "
                       f"{kernels} ({why})")


def say(phase: str, **fields: Any) -> None:
    """One labelled line of smoke output (never the result line)."""
    print(f"[chip_smoke:{phase}] " + json.dumps(fields, default=str),
          flush=True)


# ---------------------------------------------------------------------------
# device
# ---------------------------------------------------------------------------
def phase_device(cache_dir: str) -> Dict[str, Any]:
    """What jax found, as it reports it. The caller decides whether that
    is acceptable; this only looks."""
    import jax
    import jaxlib

    from luminaai_tpu.native import native_available

    try:
        from importlib.metadata import version

        libtpu = version("libtpu")
    except Exception:
        libtpu = "not installed"
    d = jax.devices()[0]
    info = {
        "platform": d.platform,
        "kind": d.device_kind,
        "count": len(jax.devices()),
    }
    say(
        "device", **info, jax=jax.__version__, jaxlib=jaxlib.__version__,
        libtpu=libtpu, compile_cache_dir=cache_dir,
        # The C++ packer falls back to numpy in silence; a host path, so
        # it is printed and never failed on.
        native_packer=native_available(),
    )
    return info


def _peak_hbm_gb() -> Optional[float]:
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return None if peak is None else round(peak / 1e9, 3)


# ---------------------------------------------------------------------------
# train -> checkpoint
# ---------------------------------------------------------------------------
def phase_train(cfg, steps: int, out_dir: str,
                require_kernels: Sequence[str] = ()) -> Dict[str, Any]:
    """`lumina train` with the defaults a user gets (adaptive
    orchestrator, OOM ladder), then hold the run's own summary to what
    was asked for."""
    from luminaai_tpu import cli

    cfg = dataclasses.replace(
        cfg,
        # Log (and sync) every step: every loss is checked below.
        health_check_interval=10,
        # One save, at the end: the smoke's checkpoint.
        save_every_n_batches=10**9,
        eval_every_n_batches=10**9,
    )
    cfg_path = os.path.join(out_dir, "smoke_config.yaml")
    cfg.save(cfg_path)
    argv = ["train", "--config", cfg_path, "--synthetic",
            "--steps", str(steps), "--output-dir", out_dir]
    if require_kernels:
        # The kernel census rides the compiled-cost export (one more
        # compile of the step, served by the persistent cache).
        argv.append("--cost-analysis")
    rc = cli.main(argv)
    check(rc == 0, f"lumina train exited {rc}")

    with open(os.path.join(out_dir, "training_summary.json")) as f:
        summary = json.load(f)
    with open(os.path.join(out_dir, "logs", "metrics.jsonl")) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    out = check_train_run(cfg, steps, summary, rows, require_kernels)
    out["peak_hbm_gb"] = _peak_hbm_gb()
    say("train", note="smoke output on one run, not a benchmark", **out)
    return out


def check_train_run(cfg, steps: int, summary: Dict[str, Any],
                    rows: List[Dict[str, Any]],
                    require_kernels: Sequence[str]) -> Dict[str, Any]:
    """Hold a finished run (its training_summary.json and the rows of its
    logs/metrics.jsonl) to what was asked of it."""
    rows = [r for r in rows if "loss" in r]
    losses = [r["loss"] for r in rows]

    ran = summary["ran"]
    asked = {
        "batch_size": cfg.batch_size, "seq_length": cfg.seq_length,
        "num_layers": cfg.num_layers, "scan_layers": cfg.scan_layers,
        "gradient_accumulation_steps": cfg.gradient_accumulation_steps,
    }
    got = {k: ran[k] for k in asked}
    check(got == asked, f"ran {got}, asked for {asked} (OOM ladder or "
                        f"orchestrator changed it: "
                        f"{summary.get('interventions')})")
    check(summary["final_step"] == steps,
          f"final_step {summary['final_step']} != {steps}")
    check(summary["tokens_seen"] == steps * cfg.batch_size * cfg.seq_length,
          f"tokens_seen {summary['tokens_seen']}")
    check(len(losses) == steps, f"{len(losses)} logged losses for {steps} "
                                "steps")
    check(all(l == l and abs(l) != float("inf") for l in losses),
          f"non-finite loss in {losses}")
    check(losses[-1] < losses[0], f"loss did not fall: {losses}")

    costs = summary.get("compiled_costs") or {}
    kernels = costs.get("kernels") or {}
    require_kernels_in(kernels, require_kernels, "compiled train step",
                       costs.get("reason"))

    # Steady step time: logged steps are synced, so the gap between two
    # log lines is one whole step. The first gap still holds the AOT
    # cost-analysis compile; drop it.
    gaps = [b["ts"] - a["ts"] for a, b in zip(rows[1:], rows[2:])]
    step_s = sorted(gaps)[len(gaps) // 2] if gaps else None
    return {
        "steps": steps,
        "first_loss": losses[0], "last_loss": losses[-1],
        "steady_step_s": step_s,
        "tokens_per_s": (
            cfg.batch_size * cfg.seq_length / step_s if step_s else None
        ),
        "kernels": kernels,
        "compiled_memory": costs.get("memory"),
        "goodput_seconds": summary["goodput"].get("seconds"),
        "mesh": ran["mesh"],
        "interventions": summary.get("interventions"),
    }


def phase_checkpoint(out_dir: str, live_before: int = 0) -> str:
    """The trainer's own orbax save finished (train() waits for it) and
    its integrity manifest verifies; returns the checkpoint directory.
    `live_before`: bytes of arrays alive in the process before `lumina
    train` began (0 in a process of its own; a test that shares its
    process with earlier tests hands what they left behind)."""
    import jax

    from luminaai_tpu import cli

    ckpt_dir = os.path.join(out_dir, "checkpoints")
    rc = cli.main(["verify-checkpoint", ckpt_dir, "--mode", "full"])
    check(rc == 0, f"lumina verify-checkpoint exited {rc}")
    # The train state must be gone before the server loads the weights:
    # nothing the training run built may still hold it on the device.
    gc.collect()
    jax.clear_caches()
    live = sum(a.nbytes for a in jax.live_arrays()) - live_before
    stats = jax.devices()[0].memory_stats() or {}
    say("checkpoint", dir=ckpt_dir, live_array_bytes_after_free=live,
        bytes_in_use_after_free=stats.get("bytes_in_use"))
    check(live < 2**20, f"{live} bytes of arrays outlive `lumina train`: "
                        "the train state was not freed")
    return ckpt_dir


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------
def _post(url: str, body: Dict[str, Any], timeout: float) -> Dict[str, Any]:
    req = urllib.request.Request(
        url + "/v1/generate", data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=timeout) as r:
        check(r.status == 200, f"POST /v1/generate -> {r.status}")
        ctype = r.headers.get("Content-Type", "")
        raw = r.read().decode()
    if not body.get("stream"):
        return json.loads(raw)
    check(ctype.startswith("text/event-stream"), f"SSE came as {ctype!r}")
    frames = [line[len("data: "):] for line in raw.split("\n")
              if line.startswith("data: ")]
    check(frames and frames[-1] == "[DONE]", "SSE stream did not end [DONE]")
    events = [json.loads(f) for f in frames[:-1]]
    done = events[-1]
    check(done.get("done") is True, f"SSE last event {done}")
    check(sum("token" in e for e in events) == done["tokens"],
          "SSE token frames disagree with the done frame")
    return done


def _metric(text: str, prefix: str) -> Optional[float]:
    for line in text.splitlines():
        if line.startswith(prefix):
            return float(line.rsplit(" ", 1)[1])
    return None


def phase_serve(ckpt_dir: str, expect_backend: str = "ragged_xla",
                max_new_tokens: int = 16,
                timeout: float = 600.0) -> Dict[str, Any]:
    """The stack `lumina serve` builds (engine restored from the
    checkpoint, ContinuousScheduler, ChatServer) on 127.0.0.1:0 in this
    process: a warm-up, JSON requests, one SSE stream, two concurrent
    requests with a shared long prefix."""
    from http.server import ThreadingHTTPServer

    from luminaai_tpu.monitoring.telemetry import MetricsRegistry
    from luminaai_tpu.serving import build_server

    t0 = time.time()
    srv = build_server(checkpoint=ckpt_dir, host="127.0.0.1", port=0)
    httpd = ThreadingHTTPServer(("127.0.0.1", 0), srv.make_handler())
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    url = f"http://127.0.0.1:{httpd.server_address[1]}"
    try:
        greedy = {"max_new_tokens": max_new_tokens, "temperature": 0.0}
        warm = _post(url, {"prompt": "warm up", **greedy}, timeout)
        ready_s = time.time() - t0

        t1 = time.time()
        first = _post(url, {"prompt": "the chip says", **greedy}, timeout)
        again = _post(url, {"prompt": "the chip says", **greedy}, timeout)
        check(first["tokens"] > 0, f"no tokens generated: {first}")
        check((first["text"], first["tokens"]) ==
              (again["text"], again["tokens"]),
              f"same greedy prompt, two answers: {first} / {again}")

        streamed = _post(
            url, {"prompt": "the chip says", "stream": True, **greedy},
            timeout,
        )
        check(streamed["text"] == first["text"],
              "SSE stream disagrees with the JSON answer for one prompt")

        shared = "a long shared prefix about one accelerator. " * 12
        with concurrent.futures.ThreadPoolExecutor(2) as pool:
            pair = list(pool.map(
                lambda tail: _post(
                    url, {"prompt": shared + tail, **greedy}, timeout
                ),
                ("first tail", "second tail"),
            ))
        check(all(p["tokens"] > 0 for p in pair),
              f"concurrent requests generated nothing: {pair}")
        served_s = time.time() - t1

        with urllib.request.urlopen(url + "/metrics", timeout=30) as r:
            metrics = r.read().decode()
        decode_steps = _metric(metrics, "serve_decode_steps_total")
        check(bool(decode_steps), "/metrics shows no decode steps")
        backend_line = f'serve_attention_backend{{backend="{expect_backend}"}}'
        check(_metric(metrics, backend_line) == 1.0,
              f"/metrics does not show {backend_line} 1")
        out = {
            "ready_s": round(ready_s, 1),
            "requests": 6, "requests_s": round(served_s, 2),
            "tokens": [warm["tokens"], first["tokens"], again["tokens"],
                       streamed["tokens"]] + [p["tokens"] for p in pair],
            "decode_steps": decode_steps,
            "attention_backend": expect_backend,
            "peak_hbm_gb": _peak_hbm_gb(),
        }
        say("serve", note="smoke output on one run, not a benchmark", **out)
        return out
    finally:
        srv.drain()
        httpd.shutdown()
        httpd.server_close()


# ---------------------------------------------------------------------------
# --chips 4: the sharded step against the one-device step
# ---------------------------------------------------------------------------
def _fixed_batches(cfg, seed: int):
    import numpy as np

    ids = np.random.RandomState(seed).randint(
        1, cfg.vocab_size, size=(cfg.batch_size, cfg.seq_length)
    ).astype(np.int32)

    def batches(epoch: int = 0):
        while True:
            yield {"input_ids": ids}

    return ids, batches


def phase_sharded_step(cfg, out_dir: str, n_devices: int = 4,
                       require_kernels: Sequence[str] = ()) -> Dict[str, Any]:
    """One train step through `Trainer` on an `n_devices` mesh (experts
    and FSDP spread over the chips, as cfg says), then the same seed and
    batch through the one-device step on device 0; loss and grad norm
    must agree, and the parameters must really live on every device."""
    import jax
    import jax.numpy as jnp

    from luminaai_tpu.models.transformer import LuminaTransformer
    from luminaai_tpu.monitoring.attribution import kernel_census
    from luminaai_tpu.parallel.mesh import build_mesh
    from luminaai_tpu.parallel.sharding import init_sharded_state
    from luminaai_tpu.parallel.train_step import make_train_step
    from luminaai_tpu.training.trainer import Trainer

    devices = jax.devices()
    check(len(devices) == n_devices,
          f"this phase needs {n_devices} devices, jax found {len(devices)}")
    cfg = dataclasses.replace(
        cfg, max_steps=1, output_dir=out_dir, health_check_interval=10,
        save_every_n_batches=10**9, eval_every_n_batches=10**9,
    )
    ids, batches = _fixed_batches(cfg, seed=cfg.seed)

    trainer = Trainer(cfg, train_data=batches)
    check(trainer.mesh.devices.size == n_devices,
          f"mesh spans {trainer.mesh.devices.size} devices")
    summary = trainer.train()
    sharded = summary["final_metrics"]
    check(summary["final_step"] == 1, f"final_step {summary['final_step']}")

    # The parameters really are spread.
    leaves = jax.tree.leaves(trainer.state.params)
    param_devices = {
        s.device for leaf in leaves for s in leaf.addressable_shards
    }
    check(len(param_devices) == n_devices,
          f"parameters live on {len(param_devices)} devices, not "
          f"{n_devices}")
    big = max(leaves, key=lambda a: a.size)
    big_shape = list(big.shape)
    shard_elems = int(big.addressable_shards[0].data.size)
    check(shard_elems < big.size,
          f"largest parameter {big.shape} is replicated, not sharded")
    in_use = [
        (d.memory_stats() or {}).get("bytes_in_use") for d in devices
    ]
    if all(b is not None for b in in_use):
        check(max(in_use) < 4 * max(min(in_use), 1),
              f"bytes_in_use differ by more than 4x across chips: {in_use}")
    batch = trainer._put({"input_ids": ids})
    text = trainer.train_step.jitted.lower(
        trainer.state, batch
    ).compile().as_text()
    collectives = {
        op: text.count(f" {op}(") + text.count(f" {op}-start(")
        for op in ("all-reduce", "all-gather", "reduce-scatter",
                   "all-to-all", "collective-permute")
    }
    check(sum(collectives.values()) > 0,
          "no collective in the compiled sharded step")
    kernels = kernel_census(text)
    require_kernels_in(kernels, require_kernels, "compiled sharded step")
    mesh_shape = {a: int(n) for a, n in trainer.mesh.shape.items()}
    tx, schedule = trainer.tx, trainer._active_schedule
    trainer.close()
    del trainer, leaves, big, batch
    gc.collect()

    # What it is compared with: the same model, seed and batch, one device.
    cfg1 = dataclasses.replace(
        cfg, data_parallel_size=1, fsdp_parallel_size=1,
        expert_parallel_size=1, tensor_parallel_size=1,
    )
    mesh1 = build_mesh(cfg1, devices=devices[:1])
    model1 = LuminaTransformer(cfg1)
    state1, sh1 = init_sharded_state(
        cfg1, model1, tx, mesh1, jax.random.key(cfg.seed)
    )
    step1 = make_train_step(cfg1, model1, sh1, mesh1, schedule, tx)
    _, m1 = step1(state1, {"input_ids": jnp.asarray(ids)})
    one = {k: float(m1[k]) for k in ("loss", "grad_norm")}

    d_loss = abs(sharded["loss"] - one["loss"])
    d_gnorm = abs(sharded["grad_norm"] - one["grad_norm"]) / max(
        abs(one["grad_norm"]), 1e-9
    )
    out = {
        "mesh": mesh_shape,
        "sharded": {k: sharded[k] for k in ("loss", "grad_norm")},
        "one_device": one,
        "abs_loss_diff": d_loss, "rel_grad_norm_diff": d_gnorm,
        "tolerance": FOUR_CHIP_TOL,
        "param_devices": len(param_devices),
        "largest_param": {"shape": big_shape, "per_shard": shard_elems},
        "bytes_in_use": in_use,
        "collectives": collectives, "kernels": kernels,
    }
    say("sharded_step", **out)
    check(d_loss < FOUR_CHIP_TOL, f"loss: sharded {sharded['loss']} vs "
                                  f"one device {one['loss']}")
    check(d_gnorm < FOUR_CHIP_TOL,
          f"grad norm: sharded {sharded['grad_norm']} vs one device "
          f"{one['grad_norm']}")
    return out


# ---------------------------------------------------------------------------
# main: owns the refusal and the flagship
# ---------------------------------------------------------------------------
def run_one_chip(cfg, out_dir: str,
                 require_kernels: Sequence[str]) -> None:
    phase_train(cfg, TRAIN_STEPS, out_dir, require_kernels)
    ckpt_dir = phase_checkpoint(out_dir)
    phase_serve(ckpt_dir)


def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument(
        "--chips", type=int, choices=(1, 4), default=1,
        help="4: the sharded-step comparison only (builder; four chips)",
    )
    args = parser.parse_args(argv)

    try:
        from luminaai_tpu.config import ConfigPresets
        from luminaai_tpu.utils.environment import configure_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the program is not here: {e}", file=sys.stderr)
        return 2

    cache_dir = configure_compile_cache()
    out_dir = tempfile.mkdtemp(prefix="chip_smoke_")  # run output only
    t0 = time.time()
    try:
        device = phase_device(cache_dir)
        if device["platform"] != "tpu":
            print(f"chip_smoke: needs a TPU, jax found "
                  f"{device['platform']!r}", file=sys.stderr)
            return 2
        check(device["count"] == args.chips,
              f"--chips {args.chips} but jax found {device['count']} "
              "device(s)")
        if args.chips == 4:
            # Full width, depth cut to 2 layers; experts 2 x fsdp 2 is
            # docs/parallelism.md's layout for one four-chip host.
            cfg = dataclasses.replace(
                ConfigPresets.flagship(), num_layers=2,
                expert_parallel_size=2, fsdp_parallel_size=2,
            )
            phase_sharded_step(cfg, out_dir, 4, FLAGSHIP_TRAIN_KERNELS)
        else:
            run_one_chip(
                ConfigPresets.flagship(), out_dir, FLAGSHIP_TRAIN_KERNELS
            )
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    finally:
        shutil.rmtree(out_dir, ignore_errors=True)
    say("done", seconds=round(time.time() - t0, 1))
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
