"""pjit train/eval step factory with in-jit gradient accumulation.

Replaces the reference's backend train loops (ref: Src/Main_Scripts/core/
backend/backend_deepspeed.py engine.step(), backend_fsdp.py:44,
training/training_loop.py microbatch loop). Differences, by design:

  - One jit covers forward, backward, accumulation, clip, and optimizer
    update. The reference crosses the Python boundary per microbatch; here
    grad accumulation is a `lax.scan` inside the step, so XLA pipelines
    microbatches without host round-trips.
  - Parallelism is data-driven: the same traced function runs dp / fsdp /
    tp / ep / sp depending on the shardings attached to state and batch.
    XLA inserts the gradient psum over the data axis (the reference's
    all-reduce), reduce-scatter/all-gather for fsdp (ZeRO-3), and
    all-to-alls for expert parallelism.
  - The TrainState buffer is donated: params/opt-state update in place in
    HBM, halving peak optimizer memory vs a copy.
"""

from __future__ import annotations

from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from flax import linen as nn
from jax.sharding import Mesh, NamedSharding

from luminaai_tpu.config import Config
from luminaai_tpu.ops.fused import (
    clip_by_global_norm,
    cross_entropy_loss,
    fused_lm_head_cross_entropy,
    global_norm,
)
from luminaai_tpu.parallel.mesh import use_mesh
from luminaai_tpu.parallel.sharding import (
    TrainState,
    batch_spec,
    is_host_offloaded,
    logical_axis_rules,
)

Batch = Dict[str, jax.Array]


def shift_labels(batch: Batch) -> Tuple[jax.Array, jax.Array]:
    """Next-token labels + validity mask from input_ids.

    (ref core/dataset.py builds shifted labels host-side; doing it in-jit
    keeps the host pipeline dtype-only.) Last position has no target.
    """
    ids = batch["input_ids"]
    labels = jnp.concatenate(
        [ids[:, 1:], jnp.zeros_like(ids[:, :1])], axis=1
    )
    valid = jnp.concatenate(
        [
            jnp.ones_like(ids[:, 1:], dtype=jnp.float32),
            jnp.zeros_like(ids[:, :1], dtype=jnp.float32),
        ],
        axis=1,
    )
    return labels, valid


def shift_with_labels(x: jax.Array) -> jax.Array:
    """Left-shift a per-position tensor so index i refers to the PREDICTED
    token (ids[i+1]), matching shift_labels. loss_mask/loss_weights arrive
    aligned to input positions; the loss at position i is for predicting
    token i+1, so its gate/weight must come from position i+1 (ref
    core/dataset.py:505-507 shifts labels[1:] and loss_weights[1:] together).
    """
    return jnp.concatenate([x[:, 1:], jnp.zeros_like(x[:, :1])], axis=1)


def _shifted_mask_weights(
    batch: Batch, valid: jax.Array
) -> Tuple[jax.Array, Optional[jax.Array]]:
    loss_mask = batch.get("loss_mask")
    mask = valid if loss_mask is None else valid * shift_with_labels(loss_mask)
    weights = batch.get("loss_weights")
    if weights is not None:
        weights = shift_with_labels(weights)
    return mask, weights


def _ce(
    config: Config,
    params,
    model_out,
    labels,
    mask,
    weights,
    z_loss_weight: float = 0.0,
    label_smoothing: float = 0.0,
):
    """Route to the fused LM-head CE (chunked, no [B,S,V] logits) or the
    plain logits path, depending on config.fused_lm_head_ce."""
    if config.fused_lm_head_ce:
        hidden = model_out
        head_name = (
            "embedding" if config.tie_word_embeddings else "lm_head"
        )
        embedding = params["embedder"][head_name]
        if isinstance(embedding, nn.meta.AxisMetadata):
            embedding = embedding.unbox()  # raw model.init trees are boxed
        return fused_lm_head_cross_entropy(
            hidden,
            embedding,
            labels,
            loss_mask=mask,
            loss_weights=weights,
            z_loss_weight=z_loss_weight,
            label_smoothing=label_smoothing,
            chunk_size=config.loss_chunk_size,
        )
    return cross_entropy_loss(
        model_out,
        labels,
        loss_mask=mask,
        loss_weights=weights,
        z_loss_weight=z_loss_weight,
        label_smoothing=label_smoothing,
    )


def make_loss_fn(config: Config, model) -> Callable:
    def loss_fn(params, batch: Batch, rng: jax.Array):
        rngs = {"routing": rng, "dropout": jax.random.fold_in(rng, 1)}
        model_out, aux = model.apply(
            {"params": params},
            batch["input_ids"],
            deterministic=False,
            rngs=rngs,
            return_hidden=config.fused_lm_head_ce,
        )
        labels, valid = shift_labels(batch)
        mask, weights = _shifted_mask_weights(batch, valid)
        loss, metrics = _ce(
            config, params, model_out, labels, mask, weights,
            z_loss_weight=config.z_loss_weight,
            label_smoothing=config.label_smoothing,
        )
        total = loss + aux.get("aux_loss", 0.0)
        for k, v in aux.items():
            metrics[k] = v
        metrics["loss"] = total
        return total, metrics

    return loss_fn


def _accumulate_grads(
    loss_fn, params, batch: Batch, rng: jax.Array, accum_steps: int
):
    """Gradient accumulation via lax.scan over microbatch slices.

    (ref training_loop.py loops microbatches in Python with engine
    .backward(); here the loop is compiled, grads accumulate in fp32.)
    """
    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    if accum_steps <= 1:
        (loss, metrics), grads = grad_fn(params, batch, rng)
        return grads, metrics

    def to_micro(x):
        return x.reshape(accum_steps, x.shape[0] // accum_steps, *x.shape[1:])

    micro = jax.tree.map(to_micro, batch)
    acc_grads = jax.tree.map(
        lambda p: jnp.zeros(p.shape, jnp.float32), params
    )

    def body(acc, xs):
        mb, step_rng = xs
        (_, metrics), grads = grad_fn(params, mb, step_rng)
        acc = jax.tree.map(
            lambda a, g: a + g.astype(jnp.float32) / accum_steps, acc, grads
        )
        return acc, metrics

    rngs = jax.random.split(rng, accum_steps)
    grads, metrics_stack = jax.lax.scan(body, acc_grads, (micro, rngs))
    # Count-like metrics sum over microbatches; the rest average.
    metrics = {
        k: m.sum(axis=0) if k == "tokens_in_loss" else m.mean(axis=0)
        for k, m in metrics_stack.items()
    }
    return grads, metrics


def make_train_step(
    config: Config,
    model,
    state_shardings: TrainState,
    mesh: Mesh,
    schedule: Optional[optax.Schedule],
    tx: optax.GradientTransformation,
    loss_fn: Optional[Callable] = None,
):
    """Build the donated, sharded, jitted train step.

    Returns `step(state, batch) -> (state, metrics)`. Call under no special
    context — mesh and logical rules are bound at trace time here. `tx` is
    closed over (not stored in state), so a rebuilt step with a new
    transform reuses the same TrainState as long as the opt-state structure
    matches (e.g. LR overrides).

    With pipeline_parallel_size > 1 this dispatches to the GPipe step
    (parallel/pipeline.py) — same contract, layer stack pipelined over the
    'pipe' mesh axis.
    """
    if config.pipeline_parallel_size > 1 and loss_fn is None:
        from luminaai_tpu.parallel.pipeline import make_pipeline_train_step

        return make_pipeline_train_step(
            config, model, state_shardings, mesh, schedule, tx
        )
    loss_fn = loss_fn or make_loss_fn(config, model)
    accum = config.gradient_accumulation_steps
    bspec = NamedSharding(mesh, batch_spec())
    # Host-offloaded optimizer state (pinned_host memory kinds in the
    # shardings): the update streams it through device memory in-jit.
    offloaded = is_host_offloaded(state_shardings.opt_state)

    def update(state: TrainState, batch: Batch):
        step_rng, new_rng = jax.random.split(state.rng)
        grads, metrics = _accumulate_grads(
            loss_fn, state.params, batch, step_rng, accum
        )
        if config.grad_clip_norm > 0:
            grads, grad_norm = clip_by_global_norm(grads, config.grad_clip_norm)
        else:  # clipping off; still report the norm for monitoring
            grad_norm = global_norm(grads)
        new_state = state.apply_gradients(
            grads, tx, host_offload=offloaded
        ).replace(rng=new_rng)
        metrics["grad_norm"] = grad_norm
        if schedule is not None:
            metrics["learning_rate"] = schedule(state.step)
        return new_state, metrics

    # jit names the program after this function: a device trace's module
    # line reads `jit_train_step(...)` (and `jit_eval_step(...)` below),
    # which the benchmark's train_step_device_ms selects.
    def train_step(state, batch):
        with use_mesh(mesh), nn.logical_axis_rules(logical_axis_rules(config)):
            return update(state, batch)

    jitted = jax.jit(
        train_step,
        in_shardings=(state_shardings, bspec),
        out_shardings=(state_shardings, None),
        donate_argnums=(0,) if config.donate_state else (),
    )

    def call(state, batch):
        with mesh:
            return jitted(state, batch)

    # AOT handle for compiled-cost accounting (monitoring/attribution.py):
    # `call.jitted.lower(state, batch).compile().cost_analysis()` queries
    # XLA's cost model for THIS executable without executing it.
    call.jitted = jitted
    return call


def make_eval_step(
    config: Config, model, state_shardings: TrainState, mesh: Mesh,
    loss_fn: Optional[Callable] = None,
):
    """Forward-only eval step: loss + metrics, deterministic routing.

    Dispatches to the pipelined eval (the GPipe loss injected through the
    same wrapper) under pipeline_parallel_size > 1; `loss_fn(params,
    batch) -> metrics` overrides the standard eval loss when given."""
    if config.pipeline_parallel_size > 1 and loss_fn is None:
        from luminaai_tpu.parallel.pipeline import make_pipeline_eval_step

        return make_pipeline_eval_step(config, model, state_shardings, mesh)

    def eval_loss(params, batch: Batch):
        model_out, aux = model.apply(
            {"params": params},
            batch["input_ids"],
            deterministic=True,
            return_hidden=config.fused_lm_head_ce,
        )
        labels, valid = shift_labels(batch)
        mask, weights = _shifted_mask_weights(batch, valid)
        loss, metrics = _ce(config, params, model_out, labels, mask, weights)
        for k, v in aux.items():
            metrics[k] = v
        metrics["loss"] = loss + aux.get("aux_loss", 0.0)
        return metrics

    run_loss = loss_fn or eval_loss
    bspec = NamedSharding(mesh, batch_spec())

    def eval_step(state, batch):
        with use_mesh(mesh), nn.logical_axis_rules(logical_axis_rules(config)):
            return run_loss(state.params, batch)

    jitted = jax.jit(  # lumina: disable=LX006 -- eval reads the state the next train step donates; it carries nothing
        eval_step, in_shardings=(state_shardings, bspec)
    )

    def call(state, batch):
        with mesh:
            return jitted(state, batch)

    call.jitted = jitted
    return call
