"""Cross-host expert parallelism: hierarchical a2a dispatch (ISSUE 11).

Acceptance contracts:

  1. parity — `moe_dispatch="a2a"` forward AND both VJPs (input + param
     grads) match the replicated-gather path at bf16-tolerance allclose
     on the dp2_ep2_tp2 conftest mesh and on the factored ici×dcn
     hierarchy (ep4 = dcn2 × ici2), including under capacity pressure
     (real drops) and with overlap chunking on/off;
  2. the hierarchical exchange itself — two-stage (ici-then-dcn) equals
     the flat all-to-all both in the factored-single-axis form and on a
     REAL 2D (dcn, ici) named-axis mesh, with the single-stage fallback
     when no dcn tier exists;
  3. the static DispatchPlan — pow2 bucket bound, per-stage byte
     accounting, and the headline claim: a2a DCN-crossing bytes
     strictly below the replicated path's at flagship routing shape;
  4. config.validate fences (a2a needs an expert axis; dcn must factor
     it; sequence/pipe rejected; tp needs divisible F).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from luminaai_tpu.config import Config
from luminaai_tpu.models.moe import MoELayer
from luminaai_tpu.parallel.expert_dispatch import (
    hierarchical_all_to_all,
    hierarchical_groups,
    make_dispatch_plan,
    next_pow2,
)
from luminaai_tpu.parallel.mesh import build_mesh, shard_map, use_mesh


def moe_config(**kw) -> Config:
    # Tier-1 runtime fixture (ISSUE 12 satellite): seq 32 / vocab 128 /
    # 1 layer keep the ~2.5-min PR-10 shapes' parity pins at roughly
    # half the trace+compute cost — every tolerance below is unchanged.
    base = dict(
        vocab_size=128,
        hidden_size=64,
        num_layers=1,
        num_heads=4,
        num_kv_heads=2,
        seq_length=32,
        intermediate_size=128,
        use_moe=True,
        num_experts=4,
        moe_top_k=2,
        capacity_factor=1.5,
        gradient_checkpointing=False,
        routing_noise_std=0.0,
    )
    base.update(kw)
    return Config(**base)


def run_layer(mode, x, mesh_kw, dcn=1, chunks=2, **cfg_kw):
    """One MoELayer fwd+bwd under the requested dispatch on a mesh.
    Grads wrt (params, x): the input gradient is where the dispatch
    adjoints (bucket gathers, all-to-all transposes) actually execute."""
    cfg = moe_config(
        moe_dispatch=mode,
        expert_dcn_size=dcn if mode == "a2a" else 1,
        moe_a2a_overlap_chunks=chunks,
        **mesh_kw,
        **cfg_kw,
    )
    layer = MoELayer(cfg, dtype=jnp.float32)
    mesh = build_mesh(cfg)
    with use_mesh(mesh):
        params = jax.jit(layer.init)(jax.random.PRNGKey(0), x)

        def loss(p, xx):
            out, m = layer.apply(p, xx)
            return jnp.sum(out**2), (out, m)

        # One jitted fwd+bwd instead of op-by-op eager dispatch — the
        # tier-1 runtime lever (ISSUE 12 satellite): identical math,
        # ~half the wall clock of the un-jitted grad evaluation.
        def traced(p, xx):
            return jax.value_and_grad(
                loss, argnums=(0, 1), has_aux=True
            )(p, xx)

        with mesh:
            (_, (out, metrics)), grads = jax.jit(traced)(params, x)
    return out, metrics, grads


def assert_tree_close(a, b, atol, rtol, tag):
    for (ka, la), (_, lb) in zip(
        jax.tree_util.tree_leaves_with_path(a),
        jax.tree_util.tree_leaves_with_path(b),
    ):
        np.testing.assert_allclose(
            np.asarray(la), np.asarray(lb), atol=atol, rtol=rtol,
            err_msg=f"{tag}: mismatch at {ka}",
        )


# ---------------------------------------------------------------------------
# 1. parity vs the replicated-gather path (fwd + both VJPs)
# ---------------------------------------------------------------------------
class TestA2AParity:
    X = jax.random.normal(jax.random.PRNGKey(1), (8, 32, 64))

    def test_dp2_ep2_tp2_matches_gather(self):
        """The PR 5 composition mesh: a2a must reproduce gather's
        outputs, routing stats, input grads AND param grads."""
        kw = dict(expert_parallel_size=2, tensor_parallel_size=2)
        out_g, m_g, g_g = run_layer("gather", self.X, kw)
        out_a, m_a, g_a = run_layer("a2a", self.X, kw)
        np.testing.assert_allclose(
            np.asarray(out_a), np.asarray(out_g), atol=1e-5, rtol=1e-5
        )
        assert float(m_a["moe_drop_rate"]) == pytest.approx(
            float(m_g["moe_drop_rate"]), abs=1e-6
        )
        np.testing.assert_allclose(
            np.asarray(m_a["expert_utilization"]),
            np.asarray(m_g["expert_utilization"]),
            atol=1e-6,
        )
        assert_tree_close(g_g, g_a, 1e-4, 1e-4, "dp2_ep2_tp2")

    def test_hierarchical_ici_dcn_matches_gather(self):
        """ep4 factored as dcn2 × ici2: the two-stage exchange with
        overlap chunking must still match the replicated path."""
        kw = dict(expert_parallel_size=4)
        out_g, m_g, g_g = run_layer("gather", self.X, kw)
        out_a, m_a, g_a = run_layer("a2a", self.X, kw, dcn=2, chunks=2)
        np.testing.assert_allclose(
            np.asarray(out_a), np.asarray(out_g), atol=1e-5, rtol=1e-5
        )
        assert_tree_close(g_g, g_a, 1e-4, 1e-4, "ici_dcn")
        # Routed-token accounting: every kept pair rides the dispatch
        # (no drops at cf 1.5 on near-uniform routing), and a strict
        # subset crosses the dcn tier.
        routed = float(m_a["ep_tokens_routed"])
        dcn_t = float(m_a["ep_tokens_dcn"])
        assert routed == pytest.approx(
            8 * 32 * 2 * (1.0 - float(m_a["moe_drop_rate"])), rel=0.05
        )
        assert 0 < dcn_t < routed

    def test_single_stage_reports_zero_dcn_tokens(self):
        kw = dict(expert_parallel_size=2)
        _, m_a, _ = run_layer("a2a", self.X, kw, dcn=1)
        assert float(m_a["ep_tokens_dcn"]) == 0.0
        assert float(m_a["ep_tokens_routed"]) > 0.0

    def test_capacity_pressure_matches_gather(self):
        """Real drops (cf 0.5): dropped pairs must never travel, and
        the drop pattern must be exactly the replicated path's."""
        kw = dict(expert_parallel_size=4)
        out_g, m_g, _ = run_layer(
            "gather", self.X, kw, capacity_factor=0.5
        )
        out_a, m_a, _ = run_layer(
            "a2a", self.X, kw, dcn=2, capacity_factor=0.5
        )
        assert float(m_g["moe_drop_rate"]) > 0.0
        assert float(m_a["moe_drop_rate"]) == pytest.approx(
            float(m_g["moe_drop_rate"]), abs=1e-6
        )
        np.testing.assert_allclose(
            np.asarray(out_a), np.asarray(out_g), atol=1e-5, rtol=1e-5
        )

    def test_overlap_chunking_is_value_invariant(self):
        """The dispatch/compute overlap knob must be a pure scheduling
        hint: chunks=1 and chunks=2 produce identical values."""
        kw = dict(expert_parallel_size=4)
        out_1, _, g_1 = run_layer("a2a", self.X, kw, dcn=2, chunks=1)
        out_2, _, g_2 = run_layer("a2a", self.X, kw, dcn=2, chunks=2)
        np.testing.assert_allclose(
            np.asarray(out_1), np.asarray(out_2), atol=1e-5, rtol=1e-5
        )
        assert_tree_close(g_1, g_2, 1e-4, 1e-4, "chunks")

    def test_train_step_dp2_ep2_tp2_matches_gather(self):
        """End to end through make_train_step on the conftest mesh: two
        optimizer steps under a2a track gather's loss trajectory (the
        step-2 loss covers the backward through the routed path)."""
        from luminaai_tpu.models.transformer import LuminaTransformer
        from luminaai_tpu.parallel.sharding import init_sharded_state
        from luminaai_tpu.parallel.train_step import make_train_step
        from luminaai_tpu.training.optimizer import (
            make_optimizer,
            make_schedule,
        )

        def batch(cfg, seed):
            rng = np.random.RandomState(seed)
            return {
                "input_ids": jnp.asarray(
                    rng.randint(
                        1, cfg.vocab_size,
                        size=(cfg.batch_size, cfg.seq_length),
                    ),
                    jnp.int32,
                )
            }

        losses = {}
        for disp in ("gather", "a2a"):
            cfg = moe_config(
                moe_dispatch=disp,
                expert_parallel_size=2,
                tensor_parallel_size=2,
                expert_dcn_size=1,
                batch_size=8,
                num_experts=8,
                moe_pattern="all",
                use_flash_attention=False,
                precision="fp32",
            )
            model = LuminaTransformer(cfg)
            schedule = make_schedule(cfg, total_steps=100)
            tx = make_optimizer(cfg, total_steps=100, schedule=schedule)
            mesh = build_mesh(cfg)
            state, shardings = init_sharded_state(
                cfg, model, tx, mesh, jax.random.key(0)
            )
            step = make_train_step(cfg, model, shardings, mesh, schedule, tx)
            traj = []
            for s in range(2):
                state, metrics = step(state, batch(cfg, s))
                traj.append(
                    (float(metrics["ce_loss"]),
                     float(metrics["moe_drop_rate"]))
                )
            losses[disp] = traj
        for (la, da), (lb, db) in zip(losses["gather"], losses["a2a"]):
            assert abs(la - lb) < 2e-3, losses
            assert abs(da - db) < 1e-6, losses


# ---------------------------------------------------------------------------
# 2. the hierarchical exchange itself
# ---------------------------------------------------------------------------
class TestHierarchicalAllToAll:
    def test_factored_two_stage_equals_flat(self):
        """On one named axis of size 4 (= dcn2 × ici2): staged ici-then-
        dcn must equal the flat tiled all-to-all, values and grads."""
        from jax.sharding import Mesh, PartitionSpec as P

        devs = np.array(jax.devices()[:4])
        mesh = Mesh(devs, ("expert",))

        def body(x):
            flat = hierarchical_all_to_all(x, "expert")
            hier = hierarchical_all_to_all(x, "expert", dcn_size=2)
            return flat, hier

        f = shard_map(
            body, mesh=mesh, in_specs=P("expert"),
            out_specs=(P("expert"), P("expert")), check_vma=False,
        )
        x = jnp.arange(4 * 4 * 2 * 3, dtype=jnp.float32).reshape(16, 2, 3)
        flat, hier = f(x)
        np.testing.assert_array_equal(np.asarray(flat), np.asarray(hier))
        g = jax.grad(lambda v: (f(v)[1] ** 2).sum())(x)
        assert bool(jnp.isfinite(g).all())

    def test_named_two_axis_mesh_equals_flat(self):
        """A REAL 2D ici×dcn mesh (the probe-mesh shape): the named-axis
        spelling of the hierarchy must produce the same source-major
        result as the flat exchange over an equivalent 1D mesh."""
        from jax.sharding import Mesh, PartitionSpec as P

        devs = np.array(jax.devices()[:4])
        ep, dcn, ici = 4, 2, 2
        mesh2d = Mesh(devs.reshape(dcn, ici), ("dcn", "ici"))
        mesh1d = Mesh(devs, ("expert",))
        x = jnp.arange(4 * ep * 2, dtype=jnp.float32).reshape(4 * ep, 2)

        two = shard_map(
            lambda v: hierarchical_all_to_all(
                v, "ici", dcn_axis="dcn", dcn_size=dcn
            ),
            mesh=mesh2d, in_specs=P(("dcn", "ici")),
            out_specs=P(("dcn", "ici")), check_vma=False,
        )(x)
        flat = shard_map(
            lambda v: hierarchical_all_to_all(v, "expert"),
            mesh=mesh1d, in_specs=P("expert"),
            out_specs=P("expert"), check_vma=False,
        )(x)
        np.testing.assert_array_equal(np.asarray(two), np.asarray(flat))

    def test_groups_shapes(self):
        g1, g2 = hierarchical_groups(8, 2)
        assert g1 == [[0, 1, 2, 3], [4, 5, 6, 7]]  # contiguous = ici
        assert g2 == [[0, 4], [1, 5], [2, 6], [3, 7]]  # strided = dcn


# ---------------------------------------------------------------------------
# 3. the static DispatchPlan
# ---------------------------------------------------------------------------
class TestDispatchPlan:
    def test_pow2_bucket_bound(self):
        assert next_pow2(1) == 1 and next_pow2(48) == 64
        plan = make_dispatch_plan(
            ep=4, dcn_size=2, local_groups=1, seq=64, top_k=2,
            capacity=48, num_experts=4, hidden=64, itemsize=4,
            overlap_chunks=2,
        )
        # bound = min(N=128, G_l*E_l*C=48) -> pow2 64; chunks divide it.
        assert plan.bucket_rows == 64
        assert plan.n_chunks == 2
        assert plan.ici == 2 and plan.dcn == 2

    def test_dcn_bytes_strictly_below_replicated_at_flagship_shape(self):
        """The headline scaling claim at flagship routing shape (8
        experts top-2 cf 1.25) on an ep8 = dcn2×ici4 mesh: routed-token
        buckets cross DCN at ~cf*k/ep of the replicated path's
        full-activation psum."""
        plan = make_dispatch_plan(
            ep=8, dcn_size=2, local_groups=1, seq=64, top_k=2,
            capacity=24, num_experts=8, hidden=64, itemsize=4,
            overlap_chunks=2, dp_groups=8,
        )
        assert plan.a2a_dcn_bytes > 0
        assert plan.baseline_dcn_bytes > 0
        assert plan.a2a_dcn_bytes < plan.baseline_dcn_bytes
        d = plan.to_dict()
        for key in ("payload_bytes", "ici_stage_bytes", "dcn_stage_bytes",
                    "a2a_dcn_bytes", "baseline_dcn_bytes"):
            assert key in d

    def test_single_stage_plan_has_zero_dcn_bytes(self):
        plan = make_dispatch_plan(
            ep=4, dcn_size=1, local_groups=2, seq=64, top_k=2,
            capacity=48, num_experts=4, hidden=64, itemsize=2,
        )
        assert plan.stage_bytes("dcn") == 0
        assert plan.a2a_dcn_bytes == 0
        assert plan.stage_bytes("ici") > 0

    def test_dcn_must_factor_ep(self):
        with pytest.raises(ValueError, match="divide"):
            make_dispatch_plan(
                ep=4, dcn_size=3, local_groups=1, seq=64, top_k=2,
                capacity=48, num_experts=4, hidden=64, itemsize=4,
            )


# ---------------------------------------------------------------------------
# 4. config fences
# ---------------------------------------------------------------------------
class TestConfigValidate:
    def test_a2a_requires_expert_axis(self):
        with pytest.raises(AssertionError, match="expert mesh axis"):
            moe_config(moe_dispatch="a2a")

    def test_a2a_dcn_must_divide_ep(self):
        with pytest.raises(AssertionError, match="expert_dcn_size"):
            moe_config(
                moe_dispatch="a2a", expert_parallel_size=4,
                expert_dcn_size=3,
            )

    def test_a2a_rejects_sequence_mesh(self):
        with pytest.raises(AssertionError, match="a2a"):
            moe_config(
                moe_dispatch="a2a", expert_parallel_size=2,
                sequence_parallel_size=2, use_ring_attention=True,
            )

    def test_a2a_tensor_needs_divisible_intermediate(self):
        cfg = moe_config(
            moe_dispatch="a2a", expert_parallel_size=2,
            tensor_parallel_size=2,
        )
        assert cfg.moe_dispatch == "a2a"
        with pytest.raises(AssertionError, match="intermediate_size"):
            moe_config(
                moe_dispatch="a2a", expert_parallel_size=2,
                tensor_parallel_size=2, intermediate_size=129,
            )

    def test_a2a_accepts_hierarchy(self):
        cfg = moe_config(
            moe_dispatch="a2a", expert_parallel_size=4,
            expert_dcn_size=2,
        )
        assert cfg.expert_dcn_size == 2


# ---------------------------------------------------------------------------
# single-device fallback (init + no-mesh apply must keep working)
# ---------------------------------------------------------------------------
def test_a2a_without_mesh_falls_back_to_local_gmm():
    """Outside any mesh context the a2a layer runs the single-shard
    grouped matmul (like gmm) — CPU unit tests and flax init never see
    a collective."""
    cfg = moe_config(moe_dispatch="a2a", expert_parallel_size=2)
    layer = MoELayer(cfg, dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 32, 64))
    params = jax.jit(layer.init)(jax.random.PRNGKey(0), x)
    out, metrics = jax.jit(layer.apply)(params, x)
    assert out.shape == x.shape
    assert float(metrics["ep_tokens_routed"]) == 0.0

    cfg_s = dataclasses.replace(cfg, moe_dispatch="sort")
    layer_s = MoELayer(cfg_s, dtype=jnp.float32)
    out_s, _ = jax.jit(layer_s.apply)(params, x)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(out_s), atol=1e-5, rtol=1e-5
    )
