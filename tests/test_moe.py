"""MoE / MoD routing invariants (mirrors ref tests for MoEFFNLayer/MoDRouter)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from luminaai_tpu.config import Config
from luminaai_tpu.models.mod import MoDRouter, apply_mod
from luminaai_tpu.models.moe import MoELayer, _top_k_routing


def moe_config(**kw) -> Config:
    base = dict(
        vocab_size=256,
        hidden_size=64,
        num_layers=2,
        num_heads=4,
        num_kv_heads=2,
        seq_length=64,
        intermediate_size=128,
        use_moe=True,
        num_experts=4,
        moe_top_k=2,
        capacity_factor=1.5,
        gradient_checkpointing=False,
    )
    base.update(kw)
    return Config(**base)


class TestTopKRouting:
    def test_dispatch_one_slot_per_token_choice(self):
        rng = jax.random.PRNGKey(0)
        probs = jax.nn.softmax(jax.random.normal(rng, (2, 16, 4)), -1)
        dispatch, combine, dropped = _top_k_routing(probs, top_k=2, capacity=16)
        # Each token occupies at most k slots, each slot weight in {0,1}.
        per_token = dispatch.sum(axis=(2, 3))
        assert (per_token <= 2 + 1e-6).all()
        assert set(np.unique(np.asarray(dispatch))) <= {0.0, 1.0}
        # Each expert slot holds at most one token.
        per_slot = dispatch.sum(axis=1)
        assert (per_slot <= 1 + 1e-6).all()

    def test_combine_weights_sum_to_one_when_not_dropped(self):
        rng = jax.random.PRNGKey(1)
        probs = jax.nn.softmax(jax.random.normal(rng, (1, 8, 4)), -1)
        dispatch, combine, dropped = _top_k_routing(probs, top_k=2, capacity=8)
        weights = combine.sum(axis=(2, 3))
        undropped = np.asarray(dropped[0]) == 0
        np.testing.assert_allclose(
            np.asarray(weights[0])[undropped], 1.0, atol=1e-5
        )

    def test_capacity_enforced_and_drops_reported(self):
        # All tokens prefer expert 0 → capacity 2 forces drops.
        probs = jnp.zeros((1, 8, 4)).at[:, :, 0].set(0.97).at[:, :, 1:].set(0.01)
        dispatch, combine, dropped = _top_k_routing(probs, top_k=1, capacity=2)
        assert float(dispatch[0, :, 0].sum()) == 2.0
        assert float(dropped.sum()) == 6.0


class TestMoELayer:
    def test_forward_and_metrics(self):
        cfg = moe_config()
        rng = jax.random.PRNGKey(0)
        x = jax.random.normal(rng, (2, cfg.seq_length, cfg.hidden_size), jnp.float32)
        layer = MoELayer(cfg, dtype=jnp.float32)
        (out, metrics), _ = jax.jit(layer.init_with_output)({"params": rng}, x)
        assert out.shape == x.shape
        assert 0.0 <= float(metrics["moe_drop_rate"]) <= 1.0
        assert metrics["expert_utilization"].shape == (cfg.num_experts,)
        # aux loss for near-uniform routing should be ~ load_balancing_weight
        assert 0 < float(metrics["moe_aux_loss"]) < 1.0

    def test_balanced_router_low_aux(self):
        """Uniform routing minimizes the Switch aux loss at ~weight*1.0."""
        cfg = moe_config(load_balancing_weight=1.0)
        rng = jax.random.PRNGKey(0)
        x = jax.random.normal(rng, (4, 64, cfg.hidden_size))
        layer = MoELayer(cfg, dtype=jnp.float32)
        (_, metrics), _ = jax.jit(layer.init_with_output)({"params": rng}, x)
        # with random init the router is near-uniform → aux ≈ 1.0 (its minimum)
        assert float(metrics["moe_aux_loss"]) == pytest.approx(1.0, rel=0.2)

    def test_routing_noise_changes_assignment(self):
        cfg = moe_config(routing_noise_std=1.0)
        rng = jax.random.PRNGKey(0)
        x = jax.random.normal(rng, (1, 32, cfg.hidden_size))
        layer_train = MoELayer(cfg, dtype=jnp.float32, deterministic=False)
        variables = jax.jit(layer_train.init)({"params": rng, "routing": rng}, x)
        out1, _ = jax.jit(layer_train.apply)(variables, x, rngs={"routing": jax.random.PRNGKey(1)})
        out2, _ = jax.jit(layer_train.apply)(variables, x, rngs={"routing": jax.random.PRNGKey(2)})
        assert not jnp.allclose(out1, out2)

    def test_expert_dropout_starves_dropped_experts(self):
        """With expert_dropout_rate > 0 the step's Bernoulli mask must take
        whole experts out of routing: their utilization goes to ~0 while
        survivors pick up the load (ref trainer.py:1495)."""
        cfg = moe_config(expert_dropout_rate=0.5, routing_noise_std=0.0)
        rng = jax.random.PRNGKey(0)
        x = jax.random.normal(rng, (2, 64, cfg.hidden_size))
        layer_train = MoELayer(cfg, dtype=jnp.float32, deterministic=False)
        variables = jax.jit(layer_train.init)({"params": rng, "routing": rng}, x)
        # Find an rng whose mask actually drops >=1 expert (rate 0.5, E=4:
        # overwhelmingly likely per draw; scan a few keys to be deterministic).
        for seed in range(8):
            _, metrics = jax.jit(layer_train.apply)(
                variables, x, rngs={"routing": jax.random.PRNGKey(seed)}
            )
            util = np.asarray(metrics["expert_utilization"])
            if (util < 1e-3).any():
                assert util.max() > 1.0  # survivors absorb the load
                break
        else:
            raise AssertionError("no expert ever dropped across 8 rngs")
        # Deterministic (eval) path ignores the dropout config entirely.
        layer_eval = MoELayer(cfg, dtype=jnp.float32, deterministic=True)
        out_a, _ = jax.jit(layer_eval.apply)(variables, x)
        out_b, _ = jax.jit(layer_eval.apply)(variables, x)
        assert jnp.allclose(out_a, out_b)

    def test_grad_flows_to_router(self):
        cfg = moe_config()
        rng = jax.random.PRNGKey(0)
        x = jax.random.normal(rng, (2, 32, cfg.hidden_size))
        layer = MoELayer(cfg, dtype=jnp.float32)
        variables = jax.jit(layer.init)({"params": rng}, x)

        def loss(params):
            out, metrics = jax.jit(layer.apply)({"params": params}, x)
            return out.sum() + metrics["moe_aux_loss"]

        from flax.linen import meta

        g = jax.jit(jax.grad(loss))(variables["params"])
        router_g = meta.unbox(g)["router"]
        assert float(jnp.abs(router_g).max()) > 0


class TestMoD:
    def test_capacity_selected(self):
        rng = jax.random.PRNGKey(0)
        x = jax.random.normal(rng, (2, 64, 32))
        router = MoDRouter(capacity_factor=0.5, dtype=jnp.float32)
        (idx, gate, aux), _ = jax.jit(router.init_with_output)(rng, x)
        assert idx.shape == (2, 32)
        assert gate.shape == (2, 32)
        # indices sorted & unique per row
        for row in np.asarray(idx):
            assert (np.diff(row) > 0).all()
        assert jnp.isfinite(aux)

    def test_apply_mod_skips_unselected(self):
        rng = jax.random.PRNGKey(0)
        x = jax.random.normal(rng, (1, 16, 8))

        class Wrapper(MoDRouter.__bases__[0]):  # nn.Module
            def setup(self):
                self.router = MoDRouter(capacity_factor=0.25, dtype=jnp.float32)

            def __call__(self, x):
                return apply_mod(self.router, lambda s: s * 100.0, x)

        mod = Wrapper()
        (out, metrics), _ = jax.jit(mod.init_with_output)(rng, x)
        # exactly 4 of 16 positions get the (large) FFN output added
        changed = (jnp.abs(out[0]).sum(-1) > 1.0).sum()
        assert int(changed) == 4
        assert float(metrics["mod_compute_ratio"]) == pytest.approx(0.25)


class TestDispatchModes:
    """sort / gather / einsum dispatch must agree in outputs AND grads —
    they are alternative buffer-construction strategies around identical
    routing semantics (moe.py _sort_routing vs _top_k_routing)."""

    def _run(self, mode, x):
        import dataclasses

        cfg = dataclasses.replace(
            moe_config(routing_noise_std=0.0), moe_dispatch=mode
        )
        layer = MoELayer(cfg, dtype=jnp.float32)
        params = jax.jit(layer.init)(jax.random.PRNGKey(0), x)

        def loss(p, x):
            out, _ = jax.jit(layer.apply)(p, x)
            return jnp.sum(out**2)

        out, metrics = jax.jit(layer.apply)(params, x)
        # argnums=(0, 1): the INPUT gradient is the one place the gather
        # path's hand-written _dispatch_gather adjoint executes — param
        # grads inside a standalone layer never route through d_x, so a
        # params-only comparison would leave it unpinned.
        grads = jax.jit(jax.grad(loss, argnums=(0, 1)))(params, x)
        return out, metrics, grads

    def test_modes_equivalent(self):
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 64, 64))
        ref_out, ref_m, ref_g = self._run("sort", x)
        for mode in ("gather", "einsum", "gmm"):
            out, m, g = self._run(mode, x)
            np.testing.assert_allclose(
                np.asarray(out), np.asarray(ref_out), atol=1e-5, rtol=1e-5
            )
            assert float(m["moe_drop_rate"]) == pytest.approx(
                float(ref_m["moe_drop_rate"]), abs=1e-6
            )
            for (ka, a), (kb, b) in zip(
                jax.tree_util.tree_leaves_with_path(ref_g),
                jax.tree_util.tree_leaves_with_path(g),
            ):
                np.testing.assert_allclose(
                    np.asarray(a), np.asarray(b), atol=1e-4, rtol=1e-4,
                    err_msg=f"grad mismatch {mode} at {ka}",
                )

    def test_megablox_kernel_matches_fallback_contract(self):
        """The CPU fallback _gmm_path swaps in for megablox off-TPU; pin
        the two to the same contract by running the REAL kernel in
        interpret mode against the same grouped matmul (incl. grads via
        its custom_vjp — the wrapper in megablox/ops.py, which a reader
        of megablox/gmm.py alone would miss)."""
        from jax.experimental.pallas.ops.tpu.megablox import gmm

        rng = np.random.RandomState(0)
        lhs = jnp.asarray(rng.randn(256, 64), jnp.float32)
        rhs = jnp.asarray(rng.randn(4, 64, 96), jnp.float32)
        gs = jnp.array([128, 0, 96, 32], jnp.int32)  # ragged + empty group
        out = jax.jit(
            lambda l: gmm(l, rhs, gs, preferred_element_type=jnp.float32,
                          interpret=True)
        )(lhs)
        bounds = np.cumsum(np.asarray(gs))
        ref = np.concatenate([
            np.asarray(lhs[(0 if e == 0 else bounds[e - 1]):bounds[e]])
            @ np.asarray(rhs[e])
            for e in range(4)
        ])
        np.testing.assert_allclose(
            np.asarray(out)[: bounds[-1]], ref, atol=1e-4, rtol=1e-4
        )
        g = jax.jit(jax.grad(
            lambda l: jnp.sum(
                gmm(l, rhs, gs, preferred_element_type=jnp.float32,
                    interpret=True) ** 2
            )
        ))(lhs)
        assert bool(jnp.isfinite(g).all())

    def test_megablox_kernel_tail_rows_masked(self):
        """sum(group_sizes) < m — the shape _gmm_path actually runs under
        whenever any pair is dropped. The kernel's contract there is that
        rows past the kept region are UNDEFINED in out and grad_lhs (its
        custom VJP only zeroes the tail in the sharded-groups case), so
        _gmm_path masks the operands with jnp.where. Pin that the masked
        form gives (a) correct kept-region output, (b) exactly-zero
        grad_lhs tail rows, and (c) grad_rhs with no tail contribution —
        both vs a dense masked-matmul reference."""
        from jax.experimental.pallas.ops.tpu.megablox import gmm

        rng = np.random.RandomState(1)
        m, h, f = 256, 64, 96
        lhs = jnp.asarray(rng.randn(m, h), jnp.float32)
        rhs = jnp.asarray(rng.randn(4, h, f), jnp.float32)
        gs = jnp.array([100, 0, 60, 36], jnp.int32)  # sums to 196 < 256
        kept = int(np.asarray(gs).sum())
        row_kept = jnp.arange(m)[:, None] < kept

        def masked_loss(gmm_fn, l, r):
            out = gmm_fn(
                jnp.where(row_kept, l, 0), r, gs,
                preferred_element_type=jnp.float32,
            )
            return jnp.sum(jnp.where(row_kept, out, 0.0) ** 2)

        def kernel(l, r, group_sizes, preferred_element_type):
            return gmm(l, r, group_sizes,
                       preferred_element_type=preferred_element_type,
                       interpret=True)

        def dense_ref(l, r, group_sizes, preferred_element_type):
            bounds = jnp.cumsum(group_sizes)
            row_e = jnp.searchsorted(bounds, jnp.arange(m), side="right")
            out = jnp.zeros((m, f), preferred_element_type)
            for e in range(4):
                sel = (row_e == e)[:, None].astype(l.dtype)
                out = out + (l * sel) @ r[e]
            return out

        out_k = jax.jit(kernel, static_argnums=3)(
            jnp.where(row_kept, lhs, 0), rhs, gs, jnp.float32
        )
        out_r = jax.jit(dense_ref, static_argnums=3)(
            jnp.where(row_kept, lhs, 0), rhs, gs, jnp.float32
        )
        np.testing.assert_allclose(
            np.asarray(out_k)[:kept], np.asarray(out_r)[:kept],
            atol=1e-4, rtol=1e-4,
        )
        gl_k, gr_k = jax.jit(jax.grad(
            lambda l, r: masked_loss(kernel, l, r), argnums=(0, 1)
        ))(lhs, rhs)
        gl_r, gr_r = jax.jit(jax.grad(
            lambda l, r: masked_loss(dense_ref, l, r), argnums=(0, 1)
        ))(lhs, rhs)
        # (b) the select-VJP annihilates tail cotangents exactly — any
        # kernel garbage (NaN included) past the kept region must not leak.
        assert np.all(np.asarray(gl_k)[kept:] == 0.0)
        np.testing.assert_allclose(
            np.asarray(gl_k), np.asarray(gl_r), atol=1e-4, rtol=1e-4
        )
        # (c) grad_rhs sees only kept rows (masked lhs rows are zero).
        np.testing.assert_allclose(
            np.asarray(gr_k), np.asarray(gr_r), atol=1e-4, rtol=1e-4
        )

    @pytest.mark.parametrize("seq", [64, 50])
    def test_gmm_tile_padding_matches_sort(self, seq):
        """Arbitrary (non-multiple-of-128) row counts run dropless via
        tile padding: S=50 gives N = 2·50·2 = 200 pair rows, padded to
        256 — outputs, input grads AND param grads must match the sort
        path bit-for-bit-at-tolerance, and routing stats exactly
        (VERDICT r5 #6: this shape used to raise the 128-row fence)."""
        import dataclasses

        x = jax.random.normal(jax.random.PRNGKey(7), (2, seq, 64))
        results = {}
        for mode in ("sort", "gmm"):
            cfg = dataclasses.replace(
                moe_config(routing_noise_std=0.0), moe_dispatch=mode
            )
            layer = MoELayer(cfg, dtype=jnp.float32)
            params = jax.jit(layer.init)(jax.random.PRNGKey(0), x)

            def loss(p, xx):
                out, m = jax.jit(layer.apply)(p, xx)
                return jnp.sum(out**2), (out, m)

            (_, (out, m)), grads = jax.jit(jax.value_and_grad(
                loss, argnums=(0, 1), has_aux=True
            ))(params, x)
            results[mode] = (out, m, grads)
        out_s, m_s, g_s = results["sort"]
        out_g, m_g, g_g = results["gmm"]
        np.testing.assert_allclose(
            np.asarray(out_g), np.asarray(out_s), atol=1e-5, rtol=1e-5
        )
        assert float(m_g["moe_drop_rate"]) == pytest.approx(
            float(m_s["moe_drop_rate"]), abs=1e-6
        )
        for (ka, a), (_, b) in zip(
            jax.tree_util.tree_leaves_with_path(g_s),
            jax.tree_util.tree_leaves_with_path(g_g),
        ):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=1e-4, rtol=1e-4,
                err_msg=f"grad mismatch at {ka} (seq={seq})",
            )

    @pytest.mark.parametrize("seq", [64, 50])
    def test_gmm_path_masks_kernel_garbage(self, monkeypatch, seq):
        """Pin that _gmm_path ITSELF masks the kernel's uninitialized
        tail (not just that masking-as-a-pattern works): inject a gmm
        whose forward writes NaN into rows past sum(group_sizes) and
        whose custom-VJP backward writes NaN into the same grad_lhs rows
        — exactly the real megablox contract on TPU. With the operand
        masks in place, layer output and input grads must stay finite
        and match the sort path; without them, this test goes NaN.
        seq=50 additionally covers the TILE-PADDED tail (N=200 → 256):
        pad rows are NaN in the injected kernel too, so a padding row
        leaking into output or grads fails here."""
        import dataclasses

        from luminaai_tpu.models import moe as moe_mod

        def nan_tail_gmm(lhs, rhs, group_sizes, preferred_element_type, **_):
            m, n_e = lhs.shape[0], rhs.shape[0]

            def dense(l, r, gsf):
                gs = gsf.astype(jnp.int32)
                bounds = jnp.cumsum(gs)
                row_e = jnp.searchsorted(
                    bounds, jnp.arange(m), side="right"
                )
                out = jnp.zeros((m, r.shape[-1]), preferred_element_type)
                for e in range(n_e):
                    sel = (row_e == e)[:, None].astype(l.dtype)
                    out = out + ((l * sel) @ r[e]).astype(
                        preferred_element_type
                    )
                return out

            @jax.custom_vjp
            def core(l, r, gsf):
                kept = gsf.astype(jnp.int32).sum()
                return jnp.where(
                    jnp.arange(m)[:, None] < kept, dense(l, r, gsf), jnp.nan
                )

            def core_fwd(l, r, gsf):
                return core(l, r, gsf), (l, r, gsf)

            def core_bwd(res, ct):
                l, r, gsf = res
                kept = gsf.astype(jnp.int32).sum()
                row_kept = jnp.arange(m)[:, None] < kept
                # True cotangents for the kept region; grad_lhs tail rows
                # are garbage in the real kernel — model that as NaN.
                gl, gr = jax.vjp(
                    lambda ll, rr: dense(ll, rr, gsf), l, r
                )[1](jnp.where(row_kept, ct, 0.0))
                gl = jnp.where(row_kept, gl, jnp.nan)
                return gl, gr, jnp.zeros_like(gsf)

            core.defvjp(core_fwd, core_bwd)
            return core(lhs, rhs, group_sizes.astype(jnp.float32))

        monkeypatch.setattr(moe_mod, "_GMM_OVERRIDE", nan_tail_gmm)
        x = jax.random.normal(jax.random.PRNGKey(5), (2, seq, 64))
        cfg = dataclasses.replace(
            moe_config(routing_noise_std=0.0),
            moe_dispatch="gmm",
            capacity_factor=0.5,  # force drops: total_kept < N
        )
        layer = MoELayer(cfg, dtype=jnp.float32)
        params = jax.jit(layer.init)(jax.random.PRNGKey(0), x)

        def loss(p, xx):
            out, m = jax.jit(layer.apply)(p, xx)
            return jnp.sum(out**2), m

        (val, metrics), (gp, gx) = jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1), has_aux=True
        ))(params, x)
        assert float(metrics["moe_drop_rate"]) > 0.0  # tail is non-empty
        assert bool(jnp.isfinite(val))
        assert bool(jnp.isfinite(gx).all()), "NaN leaked into d_x"
        for _, leaf in jax.tree_util.tree_leaves_with_path(gp):
            assert bool(jnp.isfinite(leaf).all()), "NaN leaked into d_params"

        # And the values must MATCH the sort path, not merely be finite.
        monkeypatch.setattr(moe_mod, "_GMM_OVERRIDE", None)
        cfg_sort = dataclasses.replace(cfg, moe_dispatch="sort")
        layer_s = MoELayer(cfg_sort, dtype=jnp.float32)

        def loss_s(p, xx):
            out, m = jax.jit(layer_s.apply)(p, xx)
            return jnp.sum(out**2), m

        (_, _), (gp_s, gx_s) = jax.jit(jax.value_and_grad(
            loss_s, argnums=(0, 1), has_aux=True
        ))(params, x)
        np.testing.assert_allclose(
            np.asarray(gx), np.asarray(gx_s), atol=1e-4, rtol=1e-4
        )

    def test_gmm_matches_sort_under_capacity_pressure(self):
        """gmm's ragged grouping must reproduce the exact per-group FIFO
        capacity drops of _sort_routing (dropped pairs sort to the
        sentinel tail and are excluded via group_sizes)."""
        import dataclasses

        x = jax.random.normal(jax.random.PRNGKey(3), (2, 64, 64))
        outs, drops = {}, {}
        for mode in ("sort", "gmm"):
            cfg = dataclasses.replace(
                moe_config(routing_noise_std=0.0),
                moe_dispatch=mode,
                capacity_factor=0.5,  # force real drops
            )
            layer = MoELayer(cfg, dtype=jnp.float32)
            params = jax.jit(layer.init)(jax.random.PRNGKey(0), x)
            out, m = jax.jit(layer.apply)(params, x)
            outs[mode], drops[mode] = out, float(m["moe_drop_rate"])
        assert drops["sort"] > 0.0  # pressure actually dropped pairs
        assert drops["gmm"] == pytest.approx(drops["sort"], abs=1e-6)
        np.testing.assert_allclose(
            np.asarray(outs["gmm"]), np.asarray(outs["sort"]),
            atol=1e-5, rtol=1e-5,
        )
