"""Transformer-op microbenchmarks (ref: Src/Main_Scripts/core/
benchmark_transformer_ops.py, training/benchmark_cuda_kernels.py:433).

Times the repo's competing op implementations head-to-head on the current
backend (real TPU under the default platform; CPU with JAX_PLATFORMS=cpu):

  - attention: Pallas flash kernel vs XLA einsum fallback (fwd and fwd+bwd)
  - MoE dispatch: sort vs gather vs einsum (one-hot) vs ragged gmm
  - loss: fused LM-head CE (chunked) vs plain logits CE (fwd+bwd)
  - int8: bf16 vs W8A8 at the decode vocab-projection shape
  - rope: fp32 vs bf16 rotation at the flagship q-projection shape

Prints one human-readable table plus a final JSON line for tooling. Timing
boundaries force a host transfer (float/device_get).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import Callable, Dict, List

import numpy as np


def _time_fn(fn: Callable, *args, iters: int = 10, warmup: int = 2) -> float:
    """Median wall seconds per call; each call synced via host transfer."""
    import jax

    def run_once() -> float:
        t0 = time.perf_counter()
        out = fn(*args)
        leaf = jax.tree.leaves(out)[0]
        np.asarray(jax.device_get(leaf)).ravel()[:1]  # force completion
        return time.perf_counter() - t0

    for _ in range(warmup):
        run_once()
    return float(np.median([run_once() for _ in range(iters)]))


def bench_attention(B=4, S=2048, Hq=16, Hkv=8, D=64) -> List[Dict]:
    import jax
    import jax.numpy as jnp

    from luminaai_tpu.ops.flash_attention import flash_attention

    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(B, S, Hq, D), jnp.bfloat16)
    k = jnp.asarray(rng.randn(B, S, Hkv, D), jnp.bfloat16)
    v = jnp.asarray(rng.randn(B, S, Hkv, D), jnp.bfloat16)

    def xla_attn(q, k, v):
        g = Hq // Hkv
        qg = q.reshape(B, S, Hkv, g, D)
        logits = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k).astype(jnp.float32)
        logits = logits / np.sqrt(D)
        mask = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
        logits = jnp.where(mask[None, None, None], logits, -1e30)
        p = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
        return jnp.einsum("bhgqk,bkhd->bqhgd", p, v).reshape(B, S, Hq, D)

    flash = jax.jit(lambda q, k, v: flash_attention(q, k, v, causal=True))
    xla = jax.jit(xla_attn)
    # Sliding window at S/4: the banded grids should beat full causal by
    # roughly the band fraction (the O(S·W) claim, measured).
    win = max(128, S // 4)
    flash_win = jax.jit(
        lambda q, k, v: flash_attention(q, k, v, causal=True, window=win)
    )

    def grad_wrap(f):
        return jax.jit(
            jax.grad(lambda q, k, v: f(q, k, v).astype(jnp.float32).sum(),
                     argnums=(0, 1, 2))
        )

    variants = (
        ("flash", flash), ("xla", xla), (f"flash_win{win}", flash_win),
    )
    rows = []
    for name, f in variants:
        rows.append({
            "op": f"attention_{name}_fwd",
            "ms": _time_fn(f, q, k, v) * 1e3,
            "shape": f"B{B}xS{S}xH{Hq}/{Hkv}xD{D}",
        })
    for name, f in variants:
        rows.append({
            "op": f"attention_{name}_fwdbwd",
            "ms": _time_fn(grad_wrap(f), q, k, v) * 1e3,
            "shape": f"B{B}xS{S}xH{Hq}/{Hkv}xD{D}",
        })
    return rows


def bench_moe_dispatch(G=8, S=2048, H=512, E=8, k=2, F=1408) -> List[Dict]:
    import dataclasses

    import jax
    import jax.numpy as jnp

    from luminaai_tpu.config import Config
    from luminaai_tpu.models.moe import MoELayer

    cfg = Config(
        vocab_size=1024, hidden_size=H, num_layers=2, num_heads=8,
        num_kv_heads=4, seq_length=S, batch_size=G, use_moe=True,
        num_experts=E, moe_top_k=k, intermediate_size=F,
        use_flash_attention=False, gradient_checkpointing=False,
    )
    x = jnp.asarray(
        np.random.RandomState(0).randn(G, S, H), jnp.bfloat16
    )

    rows = []
    for mode in ("sort", "gather", "einsum", "gmm"):
        c = dataclasses.replace(cfg, moe_dispatch=mode)
        layer = MoELayer(c)
        params = layer.init(jax.random.key(0), x)
        fwd = jax.jit(lambda p, x: layer.apply(p, x)[0])
        bwd = jax.jit(jax.grad(
            lambda p, x: layer.apply(p, x)[0].astype(jnp.float32).sum()
        ))
        rows.append({
            "op": f"moe_{mode}_fwd",
            "ms": _time_fn(fwd, params, x) * 1e3,
            "shape": f"G{G}xS{S}xH{H} E{E}k{k}",
        })
        rows.append({
            "op": f"moe_{mode}_fwdbwd",
            "ms": _time_fn(bwd, params, x) * 1e3,
            "shape": f"G{G}xS{S}xH{H} E{E}k{k}",
        })
    return rows


def bench_loss(B=8, S=2048, H=1024, V=32768) -> List[Dict]:
    import jax
    import jax.numpy as jnp

    from luminaai_tpu.ops.fused import (
        cross_entropy_loss,
        fused_lm_head_cross_entropy,
    )

    rng = np.random.RandomState(0)
    hidden = jnp.asarray(rng.randn(B, S, H) * 0.02, jnp.bfloat16)
    emb = jnp.asarray(rng.randn(V, H) * 0.02, jnp.float32)
    labels = jnp.asarray(rng.randint(0, V, (B, S)), jnp.int32)

    def plain(hidden, emb):
        logits = jnp.einsum(
            "bsh,vh->bsv", hidden.astype(jnp.float32), emb
        )
        return cross_entropy_loss(logits, labels)[0]

    def fused(hidden, emb):
        return fused_lm_head_cross_entropy(hidden, emb, labels)[0]

    rows = []
    for name, f in (("fused", fused), ("plain", plain)):
        g = jax.jit(jax.grad(f, argnums=(0, 1)))
        rows.append({
            "op": f"lm_head_ce_{name}_fwdbwd",
            "ms": _time_fn(g, hidden, emb) * 1e3,
            "shape": f"B{B}xS{S}xH{H}xV{V}",
        })
    return rows


def bench_rope(B=16, S=2048, Hq=16, D=64) -> List[Dict]:
    """RoPE rotation dtype A/B at flagship q-projection shape: fp32 table
    math (an fp32 [B,S,H,D] round-trip per projection — ~71ms/step across
    the flagship's q+k applications in the r3 trace) vs rotation in the
    bf16 compute dtype (config.rope_dtype='bf16', the r6 tuned default).
    Inputs/outputs are bf16 either way; only the product rounding differs
    (parity pinned in tests/test_model.py)."""
    import jax
    import jax.numpy as jnp

    from luminaai_tpu.models.layers import apply_rope, rope_frequencies

    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(B, S, Hq, D), jnp.bfloat16)
    cos, sin = rope_frequencies(D, S)

    variants = (
        ("fp32", jax.jit(
            lambda x: apply_rope(x, cos, sin, compute_dtype=jnp.float32)
        )),
        ("bf16", jax.jit(
            lambda x: apply_rope(x, cos, sin, compute_dtype=jnp.bfloat16)
        )),
    )

    def grad_wrap(f):
        return jax.jit(
            jax.grad(lambda x: f(x).astype(jnp.float32).sum())
        )

    shape = f"B{B}xS{S}xH{Hq}xD{D}"
    rows = []
    for name, f in variants:
        rows.append({
            "op": f"rope_{name}_fwd",
            "ms": _time_fn(f, x) * 1e3,
            "shape": shape,
        })
    for name, f in variants:
        rows.append({
            "op": f"rope_{name}_fwdbwd",
            "ms": _time_fn(grad_wrap(f), x) * 1e3,
            "shape": shape,
        })
    return rows


def bench_int8_matmul(M=256, K=1024, N=32768) -> List[Dict]:
    """bf16 vs W8A8 int8 at the decode vocab-projection shape — the MXU
    int8-peak claim (v5e ~2x bf16) measured directly, plus the full
    quantized projection (dynamic act quant included) as served."""
    import jax
    import jax.numpy as jnp

    from luminaai_tpu.ops.quantized import int8_attend, quantize_array

    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(M, K) * 0.02, jnp.bfloat16)
    w = jnp.asarray(rng.randn(N, K) * 0.02, jnp.float32)
    qt = quantize_array(w, bits=8, axis=(-1,))
    x8 = jnp.asarray(rng.randint(-127, 128, (M, K)), jnp.int8)

    def bf16(x, wbf):
        return jax.lax.dot_general(
            x, wbf, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    def raw_int8(a, b):
        return jax.lax.dot_general(
            a, b, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.int32,
        )

    shape = f"M{M}xK{K}xN{N}"
    return [
        {"op": "matmul_bf16", "ms": _time_fn(
            jax.jit(bf16), x, w.astype(jnp.bfloat16)) * 1e3,
         "shape": shape},
        {"op": "matmul_int8_raw", "ms": _time_fn(
            jax.jit(raw_int8), x8, qt.q) * 1e3, "shape": shape},
        {"op": "matmul_int8_attend_full", "ms": _time_fn(
            jax.jit(lambda xx: int8_attend(xx, qt, jnp.float32)), x) * 1e3,
         "shape": shape},
    ]


def _run_suite(suite: str, small: bool) -> List[Dict]:
    if suite == "attention":
        return bench_attention(**(dict(B=1, S=256, Hq=4, Hkv=2, D=64)
                                  if small else {}))
    if suite == "moe":
        return bench_moe_dispatch(**(dict(G=2, S=256, H=128, F=256)
                                     if small else {}))
    if suite == "int8":
        return bench_int8_matmul(**(dict(M=32, K=128, N=2048)
                                    if small else {}))
    if suite == "rope":
        return bench_rope(**(dict(B=2, S=256, Hq=4, D=64) if small else {}))
    return bench_loss(**(dict(B=2, S=256, H=128, V=2048) if small else {}))


def _child_main(suite: str, small: bool) -> None:
    from bench_common import enable_compile_cache

    enable_compile_cache()
    import jax

    platform = jax.devices()[0].platform
    rows = _run_suite(suite, small)
    print(json.dumps({"platform": platform, "results": rows}))


def main() -> None:
    """Each suite runs in a subprocess with a timeout, one at a time (the
    parent stays off jax, so each child gets the chip): one stuck suite
    must not take down the others or the JSON output."""
    parser = argparse.ArgumentParser()
    parser.add_argument(
        "--suite", default="all",
        choices=["all", "attention", "moe", "loss", "int8", "rope"],
    )
    parser.add_argument("--small", action="store_true",
                        help="CPU-sized shapes for smoke testing")
    parser.add_argument("--timeout", type=int, default=900,
                        help="per-suite timeout (seconds)")
    args = parser.parse_args()

    suites = (
        ["attention", "moe", "loss", "int8", "rope"]
        if args.suite == "all" else [args.suite]
    )
    rows: List[Dict] = []
    platform = None
    errors: List[str] = []
    from bench_common import run_child

    for suite in suites:
        cmd = [sys.executable, os.path.abspath(__file__),
               "--child", suite] + (["--small"] if args.small else [])
        parsed, diag = run_child(
            cmd, args.timeout,
            validate=lambda p: "results" in p,
            label=suite,
            cwd=os.path.dirname(os.path.abspath(__file__)),
        )
        if parsed is None:
            errors.append(diag)
            continue
        platform = parsed["platform"]
        rows += parsed["results"]

    if rows:
        width = max(len(r["op"]) for r in rows)
        print(f"\n{'op':<{width}}  {'ms':>10}  shape   [{platform}]")
        for r in rows:
            print(f"{r['op']:<{width}}  {r['ms']:>10.3f}  {r['shape']}")
    out: Dict = {"platform": platform, "results": rows}
    if errors:
        out["errors"] = errors
    print(json.dumps(out))
    if not rows:
        sys.exit(1)  # every suite failed: keep the CI failure signal


if __name__ == "__main__":
    if len(sys.argv) >= 3 and sys.argv[1] == "--child":
        _child_main(sys.argv[2], "--small" in sys.argv)
    else:
        main()
