"""Logical-axis sharding rules and sharded state initialization.

Replaces the reference's per-backend sharding logic (ref: Src/Main_Scripts/
core/backend/backend_fsdp.py:44 auto-wrap policy, backend_deepspeed.py ZeRO
stage config). Model code annotates params/activations with *logical* axis
names (`flax.linen.with_logical_partitioning`); this module maps those names
onto mesh axes. One rule table expresses what the reference needed three
backends for:

  - 'embed' → fsdp        : parameters sharded over the fsdp axis = ZeRO-3.
  - 'heads'/'mlp' → tensor: Megatron-style tensor parallelism. Attention is
    column-parallel on wq/wk/wv (heads axis) and row-parallel on wo, so the
    only collective per block is the psum XLA inserts after the row-parallel
    matmuls.
  - 'expert' → expert     : expert parallelism; dispatch einsums trigger
    all-to-alls over ICI.
  - 'activation_length' → sequence: context parallelism (ring attention).

The delta-rule and latent mixers and the expert layer's additions name
their parameters with the same axes (projections ('embed', 'heads'), the
low-rank pairs ('embed', None) / (None, 'heads'), per-head vectors
('heads',) / ('head_dim',), the shared expert as any SwiGLU, the
selection bias replicated): no new rule, and every one is annotated
(tests/test_kimi_linear.py).

Optimizer state inherits parameter shardings (ZeRO-1/2 comes for free:
Adam moments carry the same fsdp sharding as their parameter).
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from flax import linen as nn
from flax import struct
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from luminaai_tpu.config import Config

# (logical axis, mesh axis/axes). First matching rule wins; a logical axis
# mapped to None stays replicated along that dimension.
LOGICAL_AXIS_RULES: Tuple[Tuple[str, Any], ...] = (
    # Leading scan axis on stacked per-layer params (scan_layers=True):
    # the pipeline axis — stage p holds its layer slice (replicated when
    # pipe=1).
    ("layers", "pipe"),
    ("embed", "fsdp"),
    ("vocab", "tensor"),
    ("heads", "tensor"),
    ("kv_heads", "tensor"),
    ("mlp", "tensor"),
    ("mlp_fused", "tensor"),
    ("expert", "expert"),
    ("head_dim", None),
    # Activations: batch over data+fsdp (fsdp reuses its devices as extra
    # data parallelism for activations), sequence over the sp axis.
    ("activation_batch", ("data", "fsdp")),
    ("activation_length", "sequence"),
    ("activation_embed", None),
    ("activation_heads", "tensor"),
    ("activation_kv_heads", "tensor"),
    ("activation_vocab", "tensor"),
    ("activation_exp_batch", ("data", "fsdp")),
)


def logical_axis_rules(config: Optional[Config] = None):
    """Rule table, adjusted for configs where a mapping would not divide.

    kv_heads often < tensor size under GQA; dropping that one rule (the kv
    projections replicate over tensor) beats failing to compile — same
    fallback the ref fsdp backend used for undivisible wrap units.
    """
    rules = list(LOGICAL_AXIS_RULES)
    if config is not None and config.tensor_parallel_size > 1:
        if config.num_kv_heads % config.tensor_parallel_size != 0:
            rules = [
                (l, None if l in ("kv_heads", "activation_kv_heads") else m)
                for l, m in rules
            ]
    if (
        config is not None
        and config.pipeline_parallel_size > 1
        and config.sequence_parallel_size > 1
    ):
        # Inside the 1F1B manual region the 'sequence' axis is manual:
        # activations arrive pre-chunked and the ring body does its own
        # ppermutes, so an auto activation_length constraint would ask the
        # SPMD partitioner to reshard over a manual axis (the group-check
        # crash class). Every block constraint traces inside the region
        # under pp, so dropping the rule for the whole pipeline step is
        # sound.
        rules = [
            (l, None if l == "activation_length" else m) for l, m in rules
        ]
    return tuple(rules)


class TrainState(struct.PyTreeNode):
    """Minimal train state: params + optimizer state + step + rng.

    (ref training/trainer.py keeps these scattered across the Trainer object
    and the DeepSpeed engine; here it is one pytree so the whole update is a
    single donated jit.) The optax transform itself is NOT stored — it is
    closed over by the train step, so the orchestrator can swap optimizers
    (LR override) without changing the pytree structure the jit was traced
    with.
    """

    step: jax.Array
    params: Any
    opt_state: Any
    rng: jax.Array

    def apply_gradients(
        self,
        grads,
        tx: optax.GradientTransformation,
        host_offload: bool = False,
    ):
        opt_state = self.opt_state
        if host_offload:
            # Optimizer state lives in pinned host RAM: stream it to
            # device memory for the update and back after (ref DeepSpeed
            # cpu_offload_optimizer role). Scalars (Adam count) never
            # left device memory (state_shardings).
            opt_state = jax.tree.map(
                lambda x: (
                    jax.device_put(x, jax.memory.Space.Device)
                    if x.ndim > 0
                    else x
                ),
                opt_state,
            )
        updates, new_opt_state = tx.update(grads, opt_state, self.params)
        new_params = optax.apply_updates(self.params, updates)
        if host_offload:
            new_opt_state = jax.tree.map(
                lambda x: (
                    jax.device_put(x, jax.memory.Space.Host)
                    if x.ndim > 0
                    else x
                ),
                new_opt_state,
            )
        return self.replace(
            step=self.step + 1,
            params=new_params,
            opt_state=new_opt_state,
        )


def unbox(tree):
    """Strip flax Partitioned metadata boxes, leaving raw arrays."""
    return jax.tree.map(
        lambda x: x.unbox() if isinstance(x, nn.meta.AxisMetadata) else x,
        tree,
        is_leaf=lambda x: isinstance(x, nn.meta.AxisMetadata),
    )


def batch_spec() -> PartitionSpec:
    """Input batches: [B, S] batch over (data, fsdp), sequence over sp."""
    return PartitionSpec(("data", "fsdp"), "sequence")


def make_init_fn(config: Config, model, tx):
    def init(rng: jax.Array) -> TrainState:
        params_rng, state_rng = jax.random.split(rng)
        dummy = jnp.zeros((1, config.seq_length), dtype=jnp.int32)
        params = unbox(model.init(params_rng, dummy)["params"])
        return TrainState(
            step=jnp.zeros((), jnp.int32),
            params=params,
            opt_state=tx.init(params),
            rng=state_rng,
        )

    return init


def _abstract_boxed_params(config: Config, model):
    dummy = jnp.zeros((1, config.seq_length), dtype=jnp.int32)
    return jax.eval_shape(
        lambda r: model.init(r, dummy)["params"], jax.random.key(0)
    )


def _shardings_from_boxed(config: Config, boxed, mesh: Mesh):
    rules = logical_axis_rules(config)
    replicated = NamedSharding(mesh, PartitionSpec())

    def spec_of(leaf):
        if isinstance(leaf, nn.LogicallyPartitioned):
            logical = PartitionSpec(*leaf.names)
            return nn.logical_to_mesh_sharding(logical, mesh, rules)
        return replicated

    return jax.tree.map(
        spec_of, boxed, is_leaf=lambda x: isinstance(x, nn.meta.AxisMetadata)
    )


def param_shardings(config: Config, model, mesh: Mesh):
    """NamedSharding tree for params from their logical annotations."""
    return _shardings_from_boxed(
        config, _abstract_boxed_params(config, model), mesh
    )


def state_shardings(config: Config, model, tx, mesh: Mesh) -> TrainState:
    """Shardings for the full TrainState without materializing it.

    Optimizer-state leaves inherit their parameter's sharding (matched by
    dict-key path suffix — Adam mu/nu mirror the param tree); counters and
    scalars replicate. This is the ZeRO-1/2 analogue: sharded Adam moments.
    """
    boxed = _abstract_boxed_params(config, model)  # one model.init trace
    p_shardings = _shardings_from_boxed(config, boxed, mesh)
    replicated = NamedSharding(mesh, PartitionSpec())

    flat_param = {
        tuple(k.key for k in path): s
        for path, s in jax.tree_util.tree_flatten_with_path(p_shardings)[0]
    }

    abstract_opt = jax.eval_shape(tx.init, unbox(boxed))

    # Optimizer-state offload to host RAM (memory_kind='pinned_host'):
    # XLA streams the moments to HBM around the update — the TPU analogue
    # of the reference's DeepSpeed cpu_offload_optimizer (config field
    # cpu_offload=True; Src/Main_Scripts/config/config_manager.py). Gate
    # on the memory spaces the backend actually exposes (the CPU backend
    # also has pinned_host, which is what lets the full offloaded step run
    # under CPU test). Scalars (Adam's count) stay in device memory — the
    # SPMD partitioner rejects placement annotations on replicated scalars.
    offload = False
    if config.host_offload_optimizer:
        # TPU-only at execution time: XLA:CPU has no runtime for the
        # annotate_device_placement custom call (and its SPMD partitioner
        # rejects placement on replicated arrays), so enabling it off-TPU
        # would crash at step compile. The CPU test instead validates
        # placement + the in-jit streaming trace directly
        # (tests/test_sharding.py test_host_offload_optimizer_*).
        platform = mesh.devices.flat[0].platform
        offload = platform == "tpu"
        if not offload:
            import logging

            logging.getLogger(__name__).warning(
                "host_offload_optimizer ignored: backend %s does not "
                "support pinned_host placement in compiled programs",
                platform,
            )

    def opt_spec(path, leaf):
        keys = tuple(
            p.key for p in path if isinstance(p, jax.tree_util.DictKey)
        )
        sharding = replicated
        for plen in range(len(keys), 0, -1):
            sh = flat_param.get(keys[-plen:])
            if sh is not None and len(sh.spec) <= len(leaf.shape):
                sharding = sh
                break
        if offload and leaf.ndim > 0:
            sharding = sharding.with_memory_kind("pinned_host")
        return sharding

    opt_shardings = jax.tree_util.tree_map_with_path(opt_spec, abstract_opt)

    return TrainState(
        step=replicated,
        params=p_shardings,
        opt_state=opt_shardings,
        rng=replicated,
    )


def init_sharded_state(
    config: Config, model, tx, mesh: Mesh, rng: jax.Array
) -> Tuple[TrainState, TrainState]:
    """Jit-init the TrainState directly into its target shardings.

    Parameters are *born sharded* — no host-side full materialization, which
    is what lets B100/B300-class configs init on a pod at all (the ref relied
    on DeepSpeed ZeRO-3 deferred init for the same reason).

    Returns (state, shardings).
    """
    shardings = state_shardings(config, model, tx, mesh)
    init = make_init_fn(config, model, tx)
    init_shardings = shardings
    if is_host_offloaded(shardings.opt_state):
        init_shardings = jax.tree.map(
            lambda s: (
                s.with_memory_kind("device")
                if getattr(s, "memory_kind", None) == "pinned_host"
                else s
            ),
            shardings,
            is_leaf=lambda s: isinstance(s, NamedSharding),
        )
        with mesh, nn.logical_axis_rules(logical_axis_rules(config)):
            state = jax.jit(init, out_shardings=init_shardings)(rng)
        state = state.replace(
            opt_state=jax.device_put(state.opt_state, shardings.opt_state)
        )
        return state, shardings
    with mesh, nn.logical_axis_rules(logical_axis_rules(config)):
        state = jax.jit(init, out_shardings=init_shardings)(rng)
    return state, shardings


def is_host_offloaded(shardings_tree) -> bool:
    """True when any leaf sharding places its buffer in pinned host RAM.

    Single source of truth for the offload marker — the train step uses
    it to enable in-jit streaming, and init/reinit paths use it to route
    around the SPMD partitioner's rejection of mixed-memory-kind jit
    outputs (init into device memory, then device_put to pinned_host)."""
    return any(
        getattr(s, "memory_kind", None) == "pinned_host"
        for s in jax.tree.leaves(shardings_tree)
    )


def init_opt_to_shardings(tx, params, opt_shardings):
    """Initialize fresh optimizer state into (possibly host-offloaded)
    target shardings. Mixed memory kinds can't be jit out_shardings
    (SPMD partitioner limitation), so offloaded trees init on device and
    stream over afterwards — the reinit twin of init_sharded_state, for
    mid-run rebuilds like expert evolution (training/trainer.py)."""
    if not is_host_offloaded(opt_shardings):
        return jax.jit(tx.init, out_shardings=opt_shardings)(params)
    device_shardings = jax.tree.map(
        lambda s: (
            s.with_memory_kind("device")
            if getattr(s, "memory_kind", None) == "pinned_host"
            else s
        ),
        opt_shardings,
        is_leaf=lambda s: isinstance(s, NamedSharding),
    )
    opt_state = jax.jit(tx.init, out_shardings=device_shardings)(params)
    return jax.device_put(opt_state, opt_shardings)
