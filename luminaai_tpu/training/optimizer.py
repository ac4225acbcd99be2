"""Optimizer and LR-schedule construction.

Covers the reference optimizer setup (ref: Src/Main_Scripts/training/
trainer.py — AdamW + warmup + {cosine,linear,constant} schedules, min_lr
floor, weight-decay exclusion for norms/bias) via optax. Adds WSD
(warmup-stable-decay) since long-horizon pretraining on TPU pods favors it.
The reference's fused/multi-tensor Adam (ColossalAI cpu_adam, fused_optim)
is unnecessary: optax's update is a handful of elementwise ops XLA fuses
into one kernel per parameter shard.
"""

from __future__ import annotations

from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import optax

from luminaai_tpu.config import Config


def make_schedule(config: Config, total_steps: int) -> optax.Schedule:
    """Warmup + decay schedule (ref trainer.py scheduler setup)."""
    warmup_steps = max(1, int(total_steps * config.warmup_ratio))
    peak = config.learning_rate
    floor = min(config.min_lr, peak)
    if not config.use_lr_scheduler:
        return optax.constant_schedule(peak)

    warmup = optax.linear_schedule(0.0, peak, warmup_steps)
    decay_steps = max(1, total_steps - warmup_steps)
    kind = config.lr_scheduler
    if kind == "cosine":
        decay = optax.cosine_decay_schedule(
            peak, decay_steps, alpha=floor / max(peak, 1e-12)
        )
    elif kind == "linear":
        decay = optax.linear_schedule(peak, floor, decay_steps)
    elif kind == "constant":
        decay = optax.constant_schedule(peak)
    elif kind == "wsd":
        stable_steps = int(decay_steps * 0.8)
        decay = optax.join_schedules(
            [
                optax.constant_schedule(peak),
                optax.linear_schedule(peak, floor, decay_steps - stable_steps),
            ],
            [stable_steps],
        )
    else:  # pragma: no cover - validated by Config
        raise ValueError(f"unknown scheduler {kind}")
    return optax.join_schedules([warmup, decay], [warmup_steps])


# Never decayed whatever their rank: the delta rule's gate parameters and
# the router's selection bias are not weights of a linear map.
NO_DECAY_NAMES = ("A_log", "dt_bias", "selection_bias")


def _decay_mask(params):
    """Apply weight decay to matrices only — norms/scales/bias excluded
    (ref trainer.py no_decay param groups), and NO_DECAY_NAMES by name."""
    import jax

    def decayed(path, p):
        last = getattr(path[-1], "key", None) if path else None
        return p.ndim >= 2 and last not in NO_DECAY_NAMES

    return jax.tree_util.tree_map_with_path(decayed, params)


class ScaleByAdamInt8State(NamedTuple):
    """Adam moments stored as int8 codes + row-wise fp32 scales.

    Five parallel trees, each shaped like the param tree, so the sharding
    derivation's path-suffix matcher gives the codes their parameter's
    sharding for free (rank matches); the rank-(n-1) scale trees fall
    back to replicated, which costs 1/last_dim of the codes' bytes.
    """

    count: Any
    mu_codes: Any   # int8, param-shaped (linear absmax per last-dim row)
    mu_scales: Any  # fp32, param.shape[:-1]
    nu_codes: Any   # int8, param-shaped (sqrt-domain absmax per row)
    nu_scales: Any  # fp32, param.shape[:-1]


def _q8(x):
    """Row-wise (last-dim) absmax int8 quantization. Returns codes, scales."""
    absmax = jnp.max(jnp.abs(x), axis=-1)
    scale = absmax / 127.0
    safe = jnp.where(scale > 0, scale, 1.0)
    codes = jnp.clip(
        jnp.round(x / safe[..., None]), -127, 127
    ).astype(jnp.int8)
    return codes, scale.astype(jnp.float32)


def _dq8(codes, scale):
    return codes.astype(jnp.float32) * scale[..., None]


def scale_by_adam_int8(
    b1: float = 0.9, b2: float = 0.999, eps: float = 1e-8
) -> optax.GradientTransformation:
    """Adam with 8-bit moment state — the TPU answer to the reference's
    8-bit optimizer (ref trainer.py:771 create_quantized_optimizer /
    ColossalAI cpu_adam's memory role). mu quantizes linearly per
    last-dim row; nu quantizes in the sqrt domain (second moments span
    decades — absmax on sqrt(nu) keeps ~1/127 relative resolution on the
    RMS, which is what the update divides by). Moments dequantize,
    update, and requantize inside the fused step; the persistent state is
    1 byte/param/moment instead of 4 (or 2 with adam_mu_dtype=bf16).
    """

    def init_fn(params):
        z8 = lambda p: jnp.zeros(p.shape, jnp.int8)
        zs = lambda p: jnp.zeros(p.shape[:-1], jnp.float32)
        return ScaleByAdamInt8State(
            count=jnp.zeros([], jnp.int32),
            mu_codes=jax.tree.map(z8, params),
            mu_scales=jax.tree.map(zs, params),
            nu_codes=jax.tree.map(z8, params),
            nu_scales=jax.tree.map(zs, params),
        )

    def update_fn(updates, state, params=None):
        del params
        count = state.count + 1
        bc1 = 1.0 - b1 ** count.astype(jnp.float32)
        bc2 = 1.0 - b2 ** count.astype(jnp.float32)

        def upd(g, mc, ms, nc, ns):
            g = g.astype(jnp.float32)
            mu = b1 * _dq8(mc, ms) + (1.0 - b1) * g
            nu_sqrt = _dq8(nc, ns)
            nu = b2 * nu_sqrt * nu_sqrt + (1.0 - b2) * g * g
            u = (mu / bc1) / (jnp.sqrt(nu / bc2) + eps)
            mc2, ms2 = _q8(mu)
            nc2, ns2 = _q8(jnp.sqrt(nu))
            return u, mc2, ms2, nc2, ns2

        out = jax.tree.map(
            upd, updates, state.mu_codes, state.mu_scales,
            state.nu_codes, state.nu_scales,
        )
        pick = lambda i: jax.tree.map(
            lambda o: o[i], out, is_leaf=lambda o: isinstance(o, tuple)
        )
        return pick(0), ScaleByAdamInt8State(
            count=count,
            mu_codes=pick(1), mu_scales=pick(2),
            nu_codes=pick(3), nu_scales=pick(4),
        )

    return optax.GradientTransformation(init_fn, update_fn)


def make_optimizer(
    config: Config,
    total_steps: int,
    schedule: Optional[optax.Schedule] = None,
) -> optax.GradientTransformation:
    """AdamW stack. Gradient clipping lives in the train step (it reports
    the pre-clip norm to monitoring, ref cuda_kernels.py FusedGradClip)."""
    if schedule is None:
        schedule = make_schedule(config, total_steps)
    if config.adam_state_quantization == "int8":
        # Same composition as optax.adamw, with the 8-bit moment kernel.
        return optax.chain(
            scale_by_adam_int8(config.beta1, config.beta2, config.eps),
            optax.add_decayed_weights(config.weight_decay, mask=_decay_mask),
            optax.scale_by_learning_rate(schedule),
        )
    mu_dtype = "bfloat16" if config.adam_mu_dtype == "bf16" else None
    return optax.adamw(
        learning_rate=schedule,
        b1=config.beta1,
        b2=config.beta2,
        eps=config.eps,
        weight_decay=config.weight_decay,
        mask=_decay_mask,
        mu_dtype=mu_dtype,
    )
