"""Performance smoke tests (mirrors ref Src/tests/test_performance.py:
forward/backward speed sanity + memory-leak detection; SURVEY §4).

Speed bounds are deliberately loose — CPU CI boxes vary wildly — the
point is catching order-of-magnitude regressions (accidental recompiles
per step, O(S²) fallbacks) and buffer leaks, not micro-benchmarks.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from luminaai_tpu.config import Config
from luminaai_tpu.models.transformer import LuminaTransformer
from luminaai_tpu.parallel.mesh import build_mesh
from luminaai_tpu.parallel.sharding import init_sharded_state
from luminaai_tpu.parallel.train_step import make_train_step
from luminaai_tpu.training.optimizer import make_optimizer, make_schedule


@pytest.fixture(scope="module")
def step_setup():
    cfg = Config(
        vocab_size=512,
        hidden_size=64,
        num_layers=2,
        num_heads=4,
        num_kv_heads=2,
        seq_length=128,
        batch_size=8,
        use_moe=True,
        num_experts=4,
        moe_top_k=2,
        use_flash_attention=False,
        precision="fp32",
    )
    model = LuminaTransformer(cfg)
    schedule = make_schedule(cfg, 100)
    tx = make_optimizer(cfg, 100, schedule)
    mesh = build_mesh(cfg)
    state, shardings = init_sharded_state(cfg, model, tx, mesh, jax.random.key(0))
    step = make_train_step(cfg, model, shardings, mesh, schedule, tx)
    ids = np.random.RandomState(0).randint(
        1, cfg.vocab_size, (cfg.batch_size, cfg.seq_length)
    )
    batch = {"input_ids": jnp.asarray(ids, jnp.int32)}
    state, m = step(state, batch)  # compile
    float(m["loss"])
    # step donates its state argument; tests must thread the CURRENT state
    # through this holder (a stale reference is a deleted buffer).
    holder = {"state": state}
    return cfg, step, holder, batch, model, mesh, shardings


def test_step_speed_no_per_step_recompile(step_setup):
    """Steps after compile must be far faster than the compile itself —
    a per-step retrace/recompile (e.g. an unhashable static arg) shows up
    as seconds per step."""
    cfg, step, holder, batch = step_setup[:4]
    t0 = time.perf_counter()
    n = 10
    for _ in range(n):
        holder["state"], m = step(holder["state"], batch)
    float(m["loss"])
    per_step = (time.perf_counter() - t0) / n
    assert per_step < 2.0, f"{per_step:.2f}s/step — recompiling per step?"


def test_no_buffer_leak_across_steps(step_setup):
    """Donated state must not accumulate live device buffers step over
    step (ref test_performance.py test_memory_leak, GPU-mem based; here
    counted directly via live_arrays)."""
    cfg, step, holder, batch = step_setup[:4]
    for _ in range(3):  # settle donation pattern
        holder["state"], m = step(holder["state"], batch)
    float(m["loss"])
    n0 = len(jax.live_arrays())
    for _ in range(20):
        holder["state"], m = step(holder["state"], batch)
    float(m["loss"])
    n1 = len(jax.live_arrays())
    assert n1 <= n0 + 5, f"live buffers grew {n0} -> {n1}"


def test_eval_step_not_slower_than_train(step_setup):
    """The eval step (forward + loss only, same fused-CE path) must not be
    slower than the full train step (forward + backward + optimizer) —
    ref test_performance.py forward-vs-backward speed relation."""
    cfg, step, holder, batch, model, mesh, shardings = step_setup
    from luminaai_tpu.parallel.train_step import make_eval_step

    eval_step = make_eval_step(cfg, model, shardings, mesh)

    m = eval_step(holder["state"], batch)  # compile
    float(m["loss"])
    n = 10
    t0 = time.perf_counter()
    for _ in range(n):
        m = eval_step(holder["state"], batch)
    float(m["loss"])
    eval_per_step = (time.perf_counter() - t0) / n

    t0 = time.perf_counter()
    for _ in range(n):
        holder["state"], m = step(holder["state"], batch)
    float(m["loss"])
    train_per_step = (time.perf_counter() - t0) / n
    # Loose 2x margin: at this size both steps are dispatch-dominated on
    # CPU and jitter would flake a tight ratio; the target regression is
    # eval accidentally running the backward, which is way above 2x.
    assert eval_per_step < train_per_step * 2.0, (
        eval_per_step, train_per_step,
    )


def test_sort_and_gather_dispatch_not_costlier_than_einsum():
    """Perf tripwire (VERDICT r2 weak #6): the sort and gather MoE dispatch
    engines exist because the einsum one materializes a [tokens, E, cap]
    one-hot and multiplies through it; if either regresses to
    costlier-than-einsum, something structural broke. That claim is about
    the program, so it is pinned on XLA's own count of the compiled
    layer's forward+backward FLOPs — a CPU wall-clock race between three
    full train steps said the same thing in 20-40 s and flaked under
    load."""
    import dataclasses

    from luminaai_tpu.models.moe import MoELayer

    base = Config(
        vocab_size=512,
        hidden_size=64,
        num_layers=2,
        num_heads=4,
        num_kv_heads=2,
        seq_length=128,
        batch_size=8,
        use_moe=True,
        num_experts=8,
        moe_top_k=2,
        use_flash_attention=False,
        precision="fp32",
    )
    x = jax.ShapeDtypeStruct(
        (base.batch_size, base.seq_length, base.hidden_size), jnp.float32
    )
    flops = {}
    for engine in ("einsum", "sort", "gather"):
        cfg = dataclasses.replace(base, moe_dispatch=engine)
        layer = MoELayer(cfg, dtype=jnp.float32)
        params = jax.eval_shape(layer.init, jax.random.key(0), x)

        def loss(p, xx):
            return jnp.sum(layer.apply(p, xx)[0] ** 2)

        compiled = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
            params, x
        ).compile()
        flops[engine] = compiled.cost_analysis()["flops"]
    assert flops["sort"] < flops["einsum"], flops
    assert flops["gather"] < flops["einsum"], flops


def test_save_attn_removes_flash_fwd_from_backward():
    """The save_attn remat policy stores the flash (out, lse) residuals,
    so the backward must contain one fewer pallas call per layer than
    save_outs (fwd + dq + dkv vs fwd + recomputed-fwd + dq + dkv).
    Counting calls in the jaxpr pins the mechanism without hardware."""
    import dataclasses

    base = Config(
        vocab_size=256, hidden_size=128, num_layers=2, num_heads=2,
        num_kv_heads=1, seq_length=256, batch_size=2, precision="fp32",
        use_flash_attention=True, gradient_checkpointing=True,
        flash_block_q=128, flash_block_kv=128,
    )
    ids = jnp.asarray(
        np.random.RandomState(0).randint(1, 256, (2, 256)), jnp.int32
    )

    def pallas_calls(policy):
        cfg = dataclasses.replace(base, remat_policy=policy)
        model = LuminaTransformer(cfg)
        params = jax.jit(model.init)(jax.random.key(0), ids)["params"]

        def loss(p):
            out, _ = model.apply({"params": p}, ids, deterministic=True)
            return out.astype(jnp.float32).sum()

        return str(jax.make_jaxpr(jax.grad(loss))(params)).count(
            "pallas_call"
        )

    n_outs = pallas_calls("save_outs")
    n_attn = pallas_calls("save_attn")
    # 2 layers x 4 kernels vs 2 layers x 3 kernels.
    assert n_outs == 8, n_outs
    assert n_attn == 6, n_attn
