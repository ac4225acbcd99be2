#!/usr/bin/env python
"""A/B attention kernel candidates at flagship shapes (fwd and fwd+bwd).

All candidates are timed from the model's [B, S, H, D] layout (GQA: Hkv <
Hq), so internal transposes/replication count toward their cost — that is
what the transformer actually pays. Run on the real chip:

    python scripts/attn_bench.py [B S Hq Hkv D]
"""
import functools
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def main() -> None:
    B, S, Hq, Hkv, D = (
        [int(a) for a in sys.argv[1:6]] if len(sys.argv) >= 6 else (24, 2048, 16, 8, 64)
    )
    group = Hq // Hkv
    import jax
    import jax.numpy as jnp

    from luminaai_tpu.ops.flash_attention import flash_attention as mine

    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(B, S, Hq, D), jnp.bfloat16)
    k = jnp.asarray(rng.randn(B, S, Hkv, D), jnp.bfloat16)
    v = jnp.asarray(rng.randn(B, S, Hkv, D), jnp.bfloat16)
    scale = 1.0 / D**0.5

    candidates = {}

    for bq, bkv in ((512, 512), (512, 1024), (1024, 512), (256, 1024), (1024, 1024)):
        if bq <= S and bkv <= S:
            candidates[f"mine_{bq}x{bkv}"] = functools.partial(
                mine, causal=True, block_q=bq, block_kv=bkv
            )

    # Official jax flash kernel: [B, H, S, D] MHA; GQA via kv head repeat.
    from jax.experimental.pallas.ops.tpu.flash_attention import (
        flash_attention as jx_flash,
    )

    def official_flash(q, k, v):
        qt = q.transpose(0, 2, 1, 3)
        kt = jnp.repeat(k, group, axis=2).transpose(0, 2, 1, 3)
        vt = jnp.repeat(v, group, axis=2).transpose(0, 2, 1, 3)
        o = jx_flash(qt, kt, vt, causal=True, sm_scale=scale)
        return o.transpose(0, 2, 1, 3)

    candidates["jax_flash_repkv"] = official_flash

    # Splash MQA kernel: q [heads, S, D] vs kv [S, D]; GQA = vmap over kv
    # heads with the head group folded into the q "heads" slot; vmap batch.
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk,
        splash_attention_mask as sm,
    )

    mask = sm.MultiHeadMask([sm.CausalMask((S, S)) for _ in range(group)])
    splash = sk.make_splash_mqa_single_device(mask)

    def splash_gqa(q, k, v):
        qg = q.reshape(B, S, Hkv, group, D).transpose(0, 2, 3, 1, 4)  # [B,Hkv,g,S,D]
        kt = k.transpose(0, 2, 1, 3)  # [B,Hkv,S,D]
        vt = v.transpose(0, 2, 1, 3)
        fn = jax.vmap(jax.vmap(splash))  # over B, Hkv
        o = fn(qg * scale, kt, vt)  # [B,Hkv,g,S,D]
        return o.transpose(0, 3, 1, 2, 4).reshape(B, S, Hq, D)

    candidates["splash_mqa_gqa"] = splash_gqa

    # XLA einsum reference (no pallas) for the floor check.
    def xla_attn(q, k, v):
        qg = q.reshape(B, S, Hkv, group, D)
        logits = (
            jnp.einsum("bqhgd,bkhd->bhgqk", qg, k).astype(jnp.float32) * scale
        )
        pos = jnp.arange(S)
        msk = pos[:, None] >= pos[None, :]
        logits = jnp.where(msk[None, None, None], logits, -1e30)
        p = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
        return jnp.einsum("bhgqk,bkhd->bqhgd", p, v).reshape(B, S, Hq, D)

    candidates["xla_einsum"] = xla_attn

    # Causal-aware useful FLOPs (qk + pv), fwd only.
    fwd_gflop = 2 * 2 * B * Hq * S * S * D * 0.5 / 1e9

    def timeit(f, n=10):
        o = f()
        jax.block_until_ready(o)
        t0 = time.perf_counter()
        for _ in range(n):
            o = f()
        jax.block_until_ready(o)
        # host round-trip: cannot return before the device finishes
        float(jax.tree.leaves(o)[0].reshape(-1)[0].astype(jnp.float32))
        return (time.perf_counter() - t0) / n

    for name, fn in candidates.items():
        try:
            fwd = jax.jit(fn)
            t_f = timeit(lambda: fwd(q, k, v))

            def loss(q, k, v):
                return jnp.sum(fn(q, k, v).astype(jnp.float32) ** 2)

            gfn = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
            t_b = timeit(lambda: gfn(q, k, v))
            print(
                f"{name:18s} fwd {t_f * 1e3:7.2f} ms ({fwd_gflop / t_f / 1e3:6.1f}"
                f" TF/s)  fwd+bwd {t_b * 1e3:7.2f} ms",
                flush=True,
            )
        except Exception as e:
            print(f"{name:18s} FAILED: {str(e).splitlines()[0][:140]}", flush=True)


if __name__ == "__main__":
    main()
