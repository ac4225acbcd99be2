"""Model unit tests (mirrors ref Src/tests/test_model.py strategy)."""

import dataclasses
import json

import jax
import jax.numpy as jnp
import pytest

from luminaai_tpu.config import Config, ConfigPresets
from luminaai_tpu.models.layers import RMSNorm, SwiGLU, apply_rope, rope_frequencies
from luminaai_tpu.models.transformer import LuminaTransformer, count_params


def tiny_config(**kw) -> Config:
    base = dict(
        vocab_size=256,
        hidden_size=64,
        num_layers=2,
        num_heads=4,
        num_kv_heads=2,
        seq_length=64,
        intermediate_size=128,
        use_moe=False,
        use_mod=False,
        gradient_checkpointing=False,
        use_flash_attention=False,
    )
    base.update(kw)
    return Config(**base)


@pytest.fixture
def rng():
    return jax.random.PRNGKey(0)


class TestRMSNorm:
    def test_normalizes(self, rng):
        x = jax.random.normal(rng, (2, 8, 64)) * 10.0
        norm = RMSNorm(dtype=jnp.float32)
        y, _ = norm.init_with_output(rng, x)
        rms = jnp.sqrt(jnp.mean(y**2, axis=-1))
        assert jnp.allclose(rms, 1.0, atol=1e-3)

    def test_dtype(self, rng):
        x = jax.random.normal(rng, (2, 8, 64), jnp.bfloat16)
        y, variables = RMSNorm(dtype=jnp.bfloat16).init_with_output(rng, x)
        assert y.dtype == jnp.bfloat16
        # params stay fp32 (mixed precision policy); unbox sharding metadata
        from flax.linen import meta

        scale = meta.unbox(variables["params"])["scale"]
        assert scale.dtype == jnp.float32


class TestRoPE:
    def test_rotation_preserves_norm(self, rng):
        cos, sin = rope_frequencies(64, 128)
        x = jax.random.normal(rng, (1, 128, 2, 64))
        y = apply_rope(x, cos, sin)
        assert jnp.allclose(
            jnp.linalg.norm(x, axis=-1), jnp.linalg.norm(y, axis=-1), atol=1e-4
        )

    def test_position_zero_identity(self, rng):
        cos, sin = rope_frequencies(64, 16)
        x = jax.random.normal(rng, (1, 1, 1, 64))
        y = apply_rope(x, cos, sin, positions=jnp.zeros((1, 1), jnp.int32))
        assert jnp.allclose(x, y, atol=1e-6)

    def test_relative_property(self, rng):
        # <R(p)q, R(p+k)k> depends only on offset k: shift both positions.
        d = 64
        cos, sin = rope_frequencies(d, 256)
        q = jax.random.normal(rng, (1, 1, 1, d))
        k = jax.random.normal(jax.random.fold_in(rng, 1), (1, 1, 1, d))
        def dot_at(p0, p1):
            qp = apply_rope(q, cos, sin, jnp.array([[p0]]))
            kp = apply_rope(k, cos, sin, jnp.array([[p1]]))
            return float(jnp.sum(qp * kp))
        assert dot_at(3, 7) == pytest.approx(dot_at(100, 104), abs=1e-3)

    def test_bf16_rotation_parity(self, rng):
        """rope_dtype='bf16' (the r6 flagship_tuned default) only changes
        the PRODUCT rounding: bf16 inputs/outputs are quantized either
        way, so the two rotations must agree to bf16 resolution — on the
        table path AND the explicit-positions path — and bf16 rotation
        must still preserve norms."""
        d, S = 64, 128
        cos, sin = rope_frequencies(d, S)
        x = jax.random.normal(rng, (2, S, 4, d)).astype(jnp.bfloat16)
        ref = apply_rope(x, cos, sin, compute_dtype=jnp.float32).astype(
            jnp.float32
        )
        out = apply_rope(x, cos, sin, compute_dtype=jnp.bfloat16).astype(
            jnp.float32
        )
        # |x| ~ N(0,1): 2 bf16 ulps of headroom at the observed scale.
        assert float(jnp.max(jnp.abs(out - ref))) < 0.06
        assert float(
            jnp.mean(jnp.abs(out - ref))
        ) < 0.01  # drift is rounding noise, not bias
        pos = jnp.broadcast_to(jnp.arange(S)[None], (2, S))
        out_pos = apply_rope(
            x, cos, sin, positions=pos, compute_dtype=jnp.bfloat16
        ).astype(jnp.float32)
        assert jnp.allclose(out_pos, out, atol=1e-6)
        assert jnp.allclose(
            jnp.linalg.norm(x.astype(jnp.float32), axis=-1),
            jnp.linalg.norm(out, axis=-1),
            rtol=0.05,
        )


class TestSwiGLU:
    def test_shape_and_grad(self, rng):
        x = jax.random.normal(rng, (2, 8, 64), jnp.float32)
        mod = SwiGLU(intermediate_size=128, dtype=jnp.float32)
        y, variables = jax.jit(mod.init_with_output)(rng, x)
        assert y.shape == x.shape
        g = jax.jit(jax.grad(lambda p: mod.apply({"params": p}, x).sum()))(
            variables["params"]
        )
        assert all(jnp.isfinite(v).all() for v in jax.tree.leaves(g))


class TestTransformer:
    @pytest.mark.parametrize(
        "kw",
        [
            {},
            {"use_moe": True, "num_experts": 4, "moe_top_k": 2},
            {"use_mod": True, "mod_capacity_factor": 0.5},
            {
                "use_moe": True,
                "use_mod": True,
                "num_experts": 4,
                "moe_pattern": "sandwich",
                "dense_start_layers": 1,
                "dense_end_layers": 0,
                "num_layers": 3,
            },
        ],
        ids=["dense", "moe", "mod", "hybrid"],
    )
    def test_forward_backward(self, rng, kw):
        cfg = tiny_config(**kw)
        model = LuminaTransformer(cfg)
        ids = jax.random.randint(rng, (2, cfg.seq_length), 0, cfg.vocab_size)
        variables = jax.jit(model.init)({"params": rng, "routing": rng}, ids)

        # One jitted value_and_grad: eager op-by-op dispatch compiles every
        # primitive separately and cost 20-30 s a case on one core.
        def loss_fn(params):
            lg, aux = model.apply(
                {"params": params}, ids, deterministic=False, rngs={"routing": rng}
            )
            return lg.astype(jnp.float32).mean() + aux["aux_loss"], (lg, aux)

        (_, (logits, aux)), grads = jax.jit(
            jax.value_and_grad(loss_fn, has_aux=True)
        )(variables["params"])
        assert logits.shape == (2, cfg.seq_length, cfg.vocab_size)
        assert logits.dtype == jnp.float32
        assert jnp.isfinite(logits).all()
        assert jnp.isfinite(aux["aux_loss"])
        assert all(jnp.isfinite(g).all() for g in jax.tree.leaves(grads))

    def test_remat_matches_no_remat(self, rng):
        cfg = tiny_config()
        ids = jax.random.randint(rng, (2, cfg.seq_length), 0, cfg.vocab_size)
        outs = []
        variables = None
        for remat in (False, True):
            c = dataclasses.replace(cfg, gradient_checkpointing=remat)
            model = LuminaTransformer(c)
            if variables is None:
                variables = jax.jit(model.init)({"params": rng}, ids)
            logits, _ = jax.jit(model.apply)(variables, ids)
            outs.append(logits)
        assert jnp.allclose(outs[0], outs[1], atol=1e-5)

    def test_param_count_matches_estimate(self, rng):
        cfg = tiny_config(use_moe=True, num_experts=4)
        model = LuminaTransformer(cfg)
        ids = jnp.zeros((1, 8), jnp.int32)
        variables = jax.eval_shape(
            model.init, {"params": rng, "routing": rng}, ids
        )
        actual = count_params(variables["params"])
        est = cfg.estimate_parameters()
        assert abs(actual - est) / actual < 0.02, (actual, est)

    def test_causality(self, rng):
        """Changing a future token must not change past logits."""
        cfg = tiny_config()
        model = LuminaTransformer(cfg)
        ids = jax.random.randint(rng, (1, cfg.seq_length), 0, cfg.vocab_size)
        variables = jax.jit(model.init)({"params": rng}, ids)
        apply = jax.jit(model.apply)
        logits1, _ = apply(variables, ids)
        ids2 = ids.at[0, -1].set((ids[0, -1] + 1) % cfg.vocab_size)
        logits2, _ = apply(variables, ids2)
        assert jnp.allclose(logits1[0, :-1], logits2[0, :-1], atol=1e-5)


class TestKVCache:
    def test_incremental_decode_matches_full(self, rng):
        cfg = tiny_config()
        model = LuminaTransformer(cfg)
        S = 16
        ids = jax.random.randint(rng, (1, S), 0, cfg.vocab_size)
        variables = jax.jit(model.init)({"params": rng}, ids)
        full_logits, _ = jax.jit(model.apply)(variables, ids)

        @jax.jit
        def step(tok, t, caches):
            return model.apply(
                variables, tok, positions=t[None, None],
                kv_caches=caches, cache_index=t,
            )

        caches = model.init_cache(1, S)
        step_logits = []
        for t in range(S):
            lg, caches, _ = step(ids[:, t : t + 1], jnp.int32(t), caches)
            step_logits.append(lg[:, 0])
        inc = jnp.stack(step_logits, axis=1)
        assert jnp.allclose(full_logits, inc, atol=2e-2), (
            float(jnp.abs(full_logits - inc).max())
        )


class TestConfig:
    def test_presets_valid(self):
        for name in ConfigPresets.available():
            cfg = ConfigPresets.get(name)
            assert cfg.estimate_parameters() > 0

    def test_moe_patterns(self):
        cfg = tiny_config(
            use_moe=True, num_layers=6, moe_pattern="every_3rd", num_experts=4
        )
        assert [cfg.is_moe_layer(i) for i in range(6)] == [
            False, False, True, False, False, True,
        ]
        cfg2 = dataclasses.replace(cfg, moe_pattern="sandwich", dense_start_layers=2, dense_end_layers=2)
        assert [cfg2.is_moe_layer(i) for i in range(6)] == [
            False, False, True, True, False, False,
        ]

    def test_validation_errors(self):
        with pytest.raises(AssertionError):
            tiny_config(hidden_size=65)
        with pytest.raises(AssertionError):
            tiny_config(use_moe=True, moe_top_k=9, num_experts=4)
        with pytest.raises(AssertionError):
            tiny_config(use_mod=True, mod_capacity_factor=1.5)

    def test_roundtrip(self, tmp_path):
        cfg = ConfigPresets.debug()
        p = str(tmp_path / "c.yaml")
        cfg.save(p)
        cfg2 = Config.load(p)
        assert cfg.to_dict() == cfg2.to_dict()

    def test_load_drops_keys_of_removed_fields(self, tmp_path):
        """A configuration saved beside an older checkpoint may name fields
        that have since gone (PR 49 removed five): it loads, and runs what
        the remaining fields say."""
        cfg = ConfigPresets.debug()
        p = str(tmp_path / "c.json")
        cfg.save(p)
        with open(p) as f:
            saved = json.load(f)
        saved.update(a_field_since_removed="hierarchical", its_size=2)
        with open(p, "w") as f:
            json.dump(saved, f)
        assert Config.load(p).to_dict() == cfg.to_dict()


def test_untied_embeddings_has_lm_head():
    """tie_word_embeddings=False adds an independent output head used by
    both the logits path and the fused-CE path."""
    import jax

    from tests.test_sharding import run_one_step, tiny_config

    cfg = tiny_config(tie_word_embeddings=False)
    from luminaai_tpu.models.transformer import LuminaTransformer

    model = LuminaTransformer(cfg)
    ids = jnp.ones((1, cfg.seq_length), jnp.int32)
    params = jax.eval_shape(model.init, jax.random.key(0), ids)["params"]
    emb = params["embedder"]
    assert "lm_head" in emb and emb["lm_head"].value.shape == (
        cfg.vocab_size, cfg.hidden_size
    )
    _, m, _ = run_one_step(cfg)
    assert jnp.isfinite(float(m["loss"]))


def test_micro_batch_size_drives_accumulation():
    from tests.test_sharding import run_one_step, tiny_config

    cfg = tiny_config(micro_batch_size=2)  # batch 8 → accum 4
    assert cfg.gradient_accumulation_steps == 4
    base = tiny_config()
    _, m, _ = run_one_step(cfg)
    _, m0, _ = run_one_step(base)
    assert abs(float(m["ce_loss"]) - float(m0["ce_loss"])) < 5e-2


class TestRematPolicies:
    """Gradients must be identical across remat policies — they trade
    memory for recompute, never numerics (transformer.py REMAT_POLICIES)."""

    def test_policies_same_grads(self):
        rng = jax.random.PRNGKey(0)
        cfg = tiny_config(
            use_moe=True, num_experts=4, routing_noise_std=0.0,
            gradient_checkpointing=True,
            num_layers=1,  # remat wraps each block: one block shows it
        )
        ids = jax.random.randint(rng, (2, cfg.seq_length), 0, cfg.vocab_size)

        # The policy changes what backward recomputes, never the params:
        # one init, one jitted grad per policy.
        params = jax.jit(LuminaTransformer(cfg).init)({"params": rng}, ids)[
            "params"
        ]

        def grads_for(policy):
            c = dataclasses.replace(cfg, remat_policy=policy)
            model = LuminaTransformer(c)

            def loss(p):
                lg, aux = model.apply({"params": p}, ids)
                return lg.astype(jnp.float32).mean() + aux["aux_loss"]

            return jax.jit(jax.grad(loss))(params)

        ref = grads_for("nothing_saveable")
        for policy in ("save_outs", "save_attn", "dots_saveable"):
            g = grads_for(policy)
            for a, b in zip(jax.tree.leaves(ref), jax.tree.leaves(g)):
                assert jnp.allclose(a, b, atol=1e-5), policy

    def test_save_attn_parity_with_flash_kernel(self):
        """save_attn's saved (out, lse) residuals come from checkpoint_name
        tags inside the flash custom_vjp fwd — parity must hold with the
        Pallas kernel actually on (interpret mode on CPU), where the saved
        residuals replace the recomputed forward in the backward pass."""
        rng = jax.random.PRNGKey(1)
        cfg = tiny_config(
            gradient_checkpointing=True,
            use_flash_attention=True,
            flash_block_q=128,
            flash_block_kv=128,
            seq_length=256,
            num_heads=2,
            num_kv_heads=1,
            hidden_size=128,  # head_dim 64: flash_eligible
            num_layers=1,
        )
        ids = jax.random.randint(rng, (1, cfg.seq_length), 0, cfg.vocab_size)
        params = jax.jit(LuminaTransformer(cfg).init)({"params": rng}, ids)[
            "params"
        ]

        def grads_for(policy):
            c = dataclasses.replace(cfg, remat_policy=policy)
            model = LuminaTransformer(c)

            def loss(p):
                lg, aux = model.apply({"params": p}, ids)
                return lg.astype(jnp.float32).mean() + aux["aux_loss"]

            return jax.jit(jax.grad(loss))(params)

        ref = grads_for("save_outs")
        g = grads_for("save_attn")
        for a, b in zip(jax.tree.leaves(ref), jax.tree.leaves(g)):
            # 1e-5 like the policy sweep above: under jit the two
            # policies fuse differently, which moves the last float bits.
            assert jnp.allclose(a, b, atol=1e-5), float(jnp.abs(a - b).max())


def test_attention_window_model_paths_agree():
    """config.attention_window: the flash kernel's block-skip banding and
    the XLA fallback's mask must implement the same window; a windowed
    model must differ from full causal."""
    import numpy as np

    cfg = tiny_config(
        use_flash_attention=True,
        flash_block_q=128,
        flash_block_kv=128,
        seq_length=256,
        num_heads=2,
        num_kv_heads=1,
        hidden_size=128,  # head_dim 64: flash_eligible
        attention_window=64,
        precision="fp32",  # sharp flash-vs-XLA comparison (bf16 noise
        # at early positions otherwise dominates the 2e-2 tolerance)
    )
    ids = jax.random.randint(
        jax.random.PRNGKey(0), (1, cfg.seq_length), 0, cfg.vocab_size
    )
    model = LuminaTransformer(cfg)
    params = jax.jit(model.init)({"params": jax.random.PRNGKey(0)}, ids)[
        "params"
    ]

    def fwd(c):
        return jax.jit(LuminaTransformer(c).apply)({"params": params}, ids)

    flash_logits, _ = fwd(cfg)
    xla_cfg = dataclasses.replace(cfg, use_flash_attention=False)
    xla_logits, _ = fwd(xla_cfg)
    np.testing.assert_allclose(
        np.asarray(flash_logits), np.asarray(xla_logits), atol=2e-2
    )
    full_cfg = dataclasses.replace(cfg, attention_window=None)
    full_logits, _ = fwd(full_cfg)
    assert float(jnp.max(jnp.abs(flash_logits - full_logits))) > 1e-3
