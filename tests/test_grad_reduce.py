"""Hierarchical cross-host gradient reduction (ISSUE 12 acceptance).

Contracts:

  1. parity — `grad_reduce="hierarchical"` loss trajectories match the
     implicit GSPMD path at 1e-6 on dp AND dp×fsdp CPU meshes, with
     grad accumulation on and off, dcn tier on and off (the explicit
     sync must be a pure reduction-order change, never a math change);
  2. the sync itself — explicit reduce-scatter / rail-psum / all-gather
     over a toy tree equals a plain psum bit-for-bit, including
     non-divisible leaf sizes (padding) and bucket splits;
  3. the static GradReducePlan — bucket sizing from grad_reduce_bucket_mb
     and the overlap floor, and the headline claim: hierarchical DCN
     bytes strictly below the flat all-reduce baseline;
  4. config.validate fences (dcn must divide the data axis; nested
     shard_map dispatches and pipe/sequence rejected);
  5. bf16-over-DCN compression is parity-GATED: enabled only by
     explicit config, trajectories stay close but are not claimed
     bitwise.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from luminaai_tpu.config import Config
from luminaai_tpu.models.transformer import LuminaTransformer
from luminaai_tpu.parallel.grad_reduce import (
    GradReducePlan,
    hierarchical_grad_sync,
    make_grad_reduce_plan,
)
from luminaai_tpu.parallel.mesh import build_mesh, shard_map
from luminaai_tpu.parallel.sharding import init_sharded_state
from luminaai_tpu.parallel.train_step import make_train_step
from luminaai_tpu.training.optimizer import make_optimizer, make_schedule


def train_cfg(**kw) -> Config:
    base = dict(
        vocab_size=128,
        hidden_size=32,
        num_layers=1,
        num_heads=2,
        num_kv_heads=1,
        seq_length=32,
        batch_size=8,
        use_flash_attention=False,
        gradient_checkpointing=False,
        precision="fp32",
        routing_noise_std=0.0,
        dropout=0.0,
        learning_rate=1e-3,
    )
    base.update(kw)
    return Config(**base)


def _batch(cfg, seed):
    rng = np.random.RandomState(seed)
    return {
        "input_ids": jnp.asarray(
            rng.randint(
                1, cfg.vocab_size, size=(cfg.batch_size, cfg.seq_length)
            ),
            jnp.int32,
        )
    }


_TRAJ_CACHE = {}


def _traj(cfg, steps=3):
    """Loss trajectory over `steps` optimizer steps on deterministic
    batches, plus the step handle (for the plan box). Several tests ask
    for the same (config, steps) — the dp8 hierarchical and flat
    baselines — so a trajectory is run once per module."""
    key = (repr(cfg), steps)
    if key not in _TRAJ_CACHE:
        _TRAJ_CACHE[key] = _run_traj(cfg, steps)
    return _TRAJ_CACHE[key]


def _run_traj(cfg, steps):
    model = LuminaTransformer(cfg)
    schedule = make_schedule(cfg, 100)
    tx = make_optimizer(cfg, 100, schedule)
    mesh = build_mesh(cfg)
    state, shardings = init_sharded_state(
        cfg, model, tx, mesh, jax.random.key(0)
    )
    step = make_train_step(cfg, model, shardings, mesh, schedule, tx)
    losses = []
    for s in range(steps):
        state, metrics = step(state, _batch(cfg, s))
        losses.append(float(metrics["loss"]))
    return losses, step


# ---------------------------------------------------------------------------
# 1. parity vs the implicit GSPMD path (the acceptance criterion)
# ---------------------------------------------------------------------------
SCENARIOS = [
    # (tag, mesh/accum overrides, gradient_dcn_size)
    ("dp8", {}, 2),
    ("dp8_accum", {"batch_size": 16, "gradient_accumulation_steps": 2}, 2),
    (
        "dp4_fsdp2",
        {"data_parallel_size": 4, "fsdp_parallel_size": 2},
        2,
    ),
    (
        "dp4_fsdp2_accum",
        {
            "data_parallel_size": 4,
            "fsdp_parallel_size": 2,
            "batch_size": 16,
            "gradient_accumulation_steps": 2,
        },
        1,  # also covers the single-stage (dcn==1) fallback
    ),
]


class TestTrajectoryParity:
    @pytest.mark.parametrize(
        "tag,overrides,dcn", SCENARIOS, ids=[s[0] for s in SCENARIOS]
    )
    def test_matches_implicit_path(self, tag, overrides, dcn):
        flat, _ = _traj(train_cfg(grad_reduce="flat", **overrides))
        hier, step = _traj(
            train_cfg(
                grad_reduce="hierarchical",
                gradient_dcn_size=dcn,
                **overrides,
            )
        )
        np.testing.assert_allclose(
            hier, flat, rtol=1e-6, atol=1e-6,
            err_msg=f"{tag}: hierarchical trajectory diverged",
        )
        plan = step.grad_reduce_plan["plan"]
        assert isinstance(plan, GradReducePlan)
        assert plan.dcn == dcn
        if dcn > 1:
            assert plan.hier_dcn_bytes < plan.flat_dcn_bytes
        else:
            assert plan.hier_dcn_bytes == 0

    def test_empty_shard_slice_keeps_exact_denominator(self):
        """Review fix: a dp shard whose rows are ENTIRELY masked out of
        the loss (dataset-tail padding) must not inflate the global CE
        denominator — the clamp applies to the raw psum, not per shard.
        On dp8 each row is one shard's whole slice; zeroing row 0's
        loss_mask makes shard 0 empty, and the trajectory must still
        match the implicit path at 1e-6."""
        mask = np.ones((8, 32), np.float32)
        mask[0] = 0.0
        mask_j = jnp.asarray(mask)

        def masked_traj(cfg):
            model = LuminaTransformer(cfg)
            schedule = make_schedule(cfg, 100)
            tx = make_optimizer(cfg, 100, schedule)
            mesh = build_mesh(cfg)
            state, shardings = init_sharded_state(
                cfg, model, tx, mesh, jax.random.key(0)
            )
            step = make_train_step(
                cfg, model, shardings, mesh, schedule, tx
            )
            losses = []
            for s in range(2):
                batch = dict(_batch(cfg, s), loss_mask=mask_j)
                state, metrics = step(state, batch)
                losses.append(float(metrics["loss"]))
            return losses

        flat = masked_traj(train_cfg(grad_reduce="flat"))
        hier = masked_traj(
            train_cfg(grad_reduce="hierarchical", gradient_dcn_size=2)
        )
        np.testing.assert_allclose(hier, flat, rtol=1e-6, atol=1e-6)

    def test_moe_aux_is_per_shard_regularizer(self):
        """MoE composition (sort dispatch): the CE gradient is exact
        but the balance aux is the DP-local per-shard formulation — a
        different regularizer from the flat path's global-batch
        product (nonlinear in routing fractions), so the pin is loose,
        not 1e-6 (module docstring / docs/parallelism.md)."""
        kw = dict(
            use_moe=True, moe_dispatch="sort", num_experts=4,
            load_balancing_weight=0.01,
        )
        flat, _ = _traj(train_cfg(grad_reduce="flat", **kw), steps=2)
        hier, _ = _traj(
            train_cfg(
                grad_reduce="hierarchical", gradient_dcn_size=2, **kw
            ),
            steps=2,
        )
        assert all(np.isfinite(hier))
        np.testing.assert_allclose(hier, flat, rtol=1e-2, atol=1e-2)

    def test_overlap_chunks_value_invariant(self):
        """The overlap knob is a pure scheduling hint: bucket counts
        change, trajectories do not."""
        one, _ = _traj(
            train_cfg(
                grad_reduce="hierarchical", gradient_dcn_size=2,
                grad_reduce_overlap_chunks=1,
            )
        )
        four, step = _traj(
            train_cfg(
                grad_reduce="hierarchical", gradient_dcn_size=2,
                grad_reduce_overlap_chunks=4,
            )
        )
        assert step.grad_reduce_plan["plan"].n_buckets == 4
        np.testing.assert_allclose(four, one, rtol=1e-6, atol=1e-6)

    def test_bf16_dcn_compression_parity_gated(self):
        """bf16-over-DCN is opt-in and loosely parity-gated: the
        trajectory tracks fp32 at bf16 tolerance (the DCN hop is the
        only narrowed leg — in-host sums stay fp32)."""
        fp32, _ = _traj(
            train_cfg(grad_reduce="hierarchical", gradient_dcn_size=2)
        )
        bf16, step = _traj(
            train_cfg(
                grad_reduce="hierarchical", gradient_dcn_size=2,
                grad_reduce_dcn_dtype="bf16",
            )
        )
        plan = step.grad_reduce_plan["plan"]
        assert plan.dcn_itemsize == 2
        # Half the DCN bytes of the fp32 hierarchical sync.
        fp32_plan = dataclasses.replace(plan, dcn_itemsize=4)
        assert plan.hier_dcn_bytes == fp32_plan.hier_dcn_bytes // 2
        np.testing.assert_allclose(bf16, fp32, rtol=2e-2, atol=2e-2)


# ---------------------------------------------------------------------------
# 2. the sync itself (bitwise vs psum on a toy tree)
# ---------------------------------------------------------------------------
class TestHierarchicalSync:
    @pytest.mark.parametrize("dcn", [1, 2, 4])
    @pytest.mark.parametrize(
        "grid", [(8, 1), (4, 2)], ids=["dp8", "dp4_fsdp2"]
    )
    def test_sync_equals_psum(self, grid, dcn):
        from jax.sharding import Mesh, PartitionSpec as P

        dp, fs = grid
        if dp % dcn:
            pytest.skip("dcn must divide the data axis")
        mesh = Mesh(
            np.array(jax.devices()[: dp * fs]).reshape(dp, fs),
            ("data", "fsdp"),
        )
        # Odd leaf sizes force padding; mixed dtypes round-trip.
        tree = {
            "a": jnp.asarray(
                np.random.RandomState(0).randn(13, 7), jnp.float32
            ),
            "b": jnp.asarray(
                np.random.RandomState(1).randn(5), jnp.float32
            ),
        }

        def body(t):
            ref = jax.tree.map(
                lambda x: jax.lax.psum(x, ("data", "fsdp")), t
            )
            hier = hierarchical_grad_sync(
                t, data_size=dp, fsdp_size=fs, dcn_size=dcn,
                bucket_mb=1e-4, overlap_chunks=2,
            )
            return ref, hier

        ref, hier = shard_map(
            body, mesh, in_specs=P(), out_specs=P(),
            axis_names=("data", "fsdp"), check_vma=False,
        )(tree)
        for k in tree:
            # Inputs replicate over all shards, so the mathematically
            # exact reduction is world * leaf. Both the staged sync and
            # XLA's all-reduce are free in association (chain vs tree
            # summation differs at the ulp), so the pin is 1e-6 — the
            # same tolerance the trajectory acceptance uses.
            np.testing.assert_allclose(
                np.asarray(hier[k]),
                np.asarray(tree[k] * (dp * fs)),
                rtol=1e-6, atol=1e-6, err_msg=k,
            )
            np.testing.assert_allclose(
                np.asarray(ref[k]), np.asarray(hier[k]),
                rtol=1e-6, atol=1e-6, err_msg=k,
            )

    def test_empty_tree_passthrough(self):
        assert hierarchical_grad_sync(
            {}, data_size=8, fsdp_size=1
        ) == {}


# ---------------------------------------------------------------------------
# 3. the static plan
# ---------------------------------------------------------------------------
class TestGradReducePlan:
    def test_bucket_sizing_and_overlap_floor(self):
        # 1 MiB of grads with 0.25 MiB buckets -> 4 buckets; the
        # overlap floor lifts a would-be-smaller count.
        plan = make_grad_reduce_plan(
            grad_elems=2**18, data_size=8, fsdp_size=1, dcn_size=2,
            bucket_mb=0.25, overlap_chunks=1,
        )
        assert plan.n_buckets == 4
        floor = make_grad_reduce_plan(
            grad_elems=2**18, data_size=8, fsdp_size=1, dcn_size=2,
            bucket_mb=64.0, overlap_chunks=3,
        )
        assert floor.n_buckets == 3
        # Padding keeps every bucket scatter-divisible.
        assert floor.padded_bytes % (floor.n_buckets * 4) == 0

    def test_dcn_bytes_strictly_below_flat(self):
        plan = make_grad_reduce_plan(
            grad_elems=10_000_000, data_size=8, fsdp_size=2, dcn_size=2,
            bucket_mb=8.0, overlap_chunks=2,
        )
        assert plan.ici_tier == 8
        assert 0 < plan.hier_dcn_bytes < plan.flat_dcn_bytes
        # Structural ratio: the DCN tier sees ~1/ici_tier of the flat
        # payload (padding aside).
        assert plan.hier_dcn_bytes <= plan.flat_dcn_bytes // 7
        d = plan.to_dict()
        for key in (
            "ici_stage_bytes", "dcn_stage_bytes", "hier_dcn_bytes",
            "flat_dcn_bytes", "n_buckets", "ici_tier",
        ):
            assert key in d
        single = make_grad_reduce_plan(
            grad_elems=1000, data_size=8, fsdp_size=1, dcn_size=1,
        )
        assert single.hier_dcn_bytes == 0
        assert single.flat_dcn_bytes == 0

    def test_dcn_must_factor_data(self):
        with pytest.raises(ValueError, match="divide"):
            make_grad_reduce_plan(
                grad_elems=1000, data_size=8, fsdp_size=1, dcn_size=3
            )


# ---------------------------------------------------------------------------
# 4. config fences
# ---------------------------------------------------------------------------
class TestConfigValidate:
    def test_rejects_bad_mode(self):
        with pytest.raises(AssertionError, match="grad_reduce"):
            train_cfg(grad_reduce="fancy")

    def test_dcn_must_divide_data(self):
        with pytest.raises(AssertionError, match="gradient_dcn_size"):
            train_cfg(
                grad_reduce="hierarchical", data_parallel_size=8,
                gradient_dcn_size=3,
            )

    def test_rejects_nested_shard_map_dispatches(self):
        with pytest.raises(AssertionError, match="hierarchical"):
            train_cfg(
                grad_reduce="hierarchical", use_moe=True,
                moe_dispatch="gmm",
            )

    def test_rejects_sequence_mesh(self):
        with pytest.raises(AssertionError, match="hierarchical"):
            train_cfg(
                grad_reduce="hierarchical", sequence_parallel_size=2,
                use_ring_attention=True,
            )

    def test_rejects_bad_dcn_dtype(self):
        with pytest.raises(AssertionError, match="dcn_dtype"):
            train_cfg(
                grad_reduce="hierarchical", grad_reduce_dcn_dtype="fp8"
            )

    def test_accepts_auto_dispatch_moe(self):
        cfg = train_cfg(
            grad_reduce="hierarchical", use_moe=True,
            moe_dispatch="gather", num_experts=4,
        )
        assert cfg.grad_reduce == "hierarchical"


# ---------------------------------------------------------------------------
# 5. diagnose probe (real timed two-stage sync on the simulated tier)
# ---------------------------------------------------------------------------
def test_grad_reduce_probe_times_two_stage():
    from luminaai_tpu.monitoring.telemetry import get_registry
    from luminaai_tpu.parallel.grad_reduce import (
        export_grad_reduce_gauges,
        grad_reduce_probe,
        make_grad_reduce_plan,
    )

    # Review fix: the probe's toy sync must not clobber a training
    # process's real plan gauges — seed the global registry and pin it.
    train_plan = make_grad_reduce_plan(
        grad_elems=123_456, data_size=8, fsdp_size=1, dcn_size=2
    )
    export_grad_reduce_gauges(train_plan)
    before = get_registry().snapshot().get("grad_reduce_bytes")

    out = grad_reduce_probe(payload_mb=0.25, iters=1)
    assert out["world"] == 8 and out["dcn"] == 2  # conftest 8-dev mesh
    assert out["simulated_dcn"] is True
    for stage in ("ici", "dcn", "two_stage"):
        rec = out["stages"][stage]
        assert "error" not in rec, rec
        assert rec["mean_seconds"] > 0
    assert get_registry().snapshot().get("grad_reduce_bytes") == before
