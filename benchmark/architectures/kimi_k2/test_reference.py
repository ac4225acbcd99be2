"""The yardstick for `correct` is itself checked: this architecture's
reference against LuminaTransformer at a tiny size on the CPU (three
layers: dense, experts, experts; 16 experts, 4 held; low-rank queries and
a YaRN rotation whose ramp lies inside the tiny head): the program's
uncached logits, the rotation by hand at the published sizes, and the
shares of the expert layer against the uncut layer. The modules are
reached as a cell reaches them, by the architecture's name."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import correct, manifest, model_config

K2 = manifest.Architecture("kimi_k2")
k2_reference, k2_adapter = K2.reference, K2.adapter

K2_YARN = {"beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1,
           "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
           "type": "yarn"}
K2_TINY = {
    "model_type": "kimi_k2", "hidden_act": "silu", "attention_bias": False,
    "hidden_size": 64, "intermediate_size": 160, "num_hidden_layers": 3,
    "num_attention_heads": 4, "num_key_value_heads": 4, "vocab_size": 512,
    "rms_norm_eps": 1e-5, "rope_theta": 50000, "tie_word_embeddings": False,
    "first_k_dense_replace": 1, "kv_lora_rank": 32, "q_lora_rank": 24,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 16, "v_head_dim": 16,
    # 16 positions of original context put the ramp at pairs 0..3 of 8
    "rope_scaling": dict(K2_YARN, original_max_position_embeddings=16),
    "max_position_embeddings": 4096,
    "moe_intermediate_size": 32, "moe_layer_freq": 1, "n_group": 1,
    "topk_group": 1, "topk_method": "noaux_tc", "norm_topk_prob": True,
    "scoring_func": "sigmoid", "n_routed_experts": 4,
    "num_experts_per_tok": 4, "n_shared_experts": 1,
    "routed_scaling_factor": 2.827, "num_nextn_predict_layers": 0,
    "ep_size": 1, "seq_aux": True, "tf_legacy_loss": False,
    "reduced": ["num_hidden_layers", "n_routed_experts"],
    "source_values": {"num_hidden_layers": 6, "n_routed_experts": 16},
    "reference": {"rope_pairs": "interleaved"},
    "deployment": {"experts_held_offset": 4},
    "program": {"precision": "fp32", "use_flash_attention": False,
                "use_stable_embedding": False, "moe_dispatch": "gmm",
                "capacity_factor": 4.0, "routing_noise_std": 0.0},
}


def k2_build(body, **over):
    from luminaai_tpu.models.transformer import LuminaTransformer
    from luminaai_tpu.parallel.sharding import unbox

    cfg = model_config.build_config(K2, body, **{"seq_length": 96, **over})
    model = LuminaTransformer(cfg)
    params = unbox(jax.jit(model.init)(
        jax.random.key(3), jnp.zeros((1, 8), jnp.int32))["params"])
    # A bias that changes the choice, so that "for the choice alone" is
    # tested: zero (as initialised) would hide a bias added to the weights.
    for name, layer in params.items():
        if "moe" in layer:
            layer["moe"]["selection_bias"] = 0.3 * jax.random.normal(
                jax.random.key(len(name)), (cfg.num_experts,))
    return cfg, model, params


def _k2_ids(rows=2, length=80):
    return jnp.asarray(np.random.RandomState(0).randint(
        3, 512, size=(rows, length)), jnp.int32)


def test_k2_adapter_names_the_layers_the_share_and_the_rotation():
    cfg, _, params = k2_build(K2_TINY)
    assert cfg.layer_mixers == ("latent",) * 3 and not cfg.unserved_mixers()
    assert cfg.num_experts == 16 and cfg.experts_held == (4, 4)
    assert not cfg.is_moe_layer(0) and cfg.is_moe_layer(1)
    assert cfg.latent_rope and cfg.rope_layout == "interleaved"
    assert cfg.yarn() == (64.0, 16, 32.0, 1.0) and cfg.q_lora_rank == 24
    la = params["layer_1"]["latent_attention"]
    assert la["wq_a"].shape == (64, 24) and la["wq_b"].shape == (24, 4, 32)
    assert la["wkv_a"].shape == (64, 48) and "wq" not in la
    assert params["layer_1"]["moe"]["wi"].shape[0] == 4
    assert params["layer_1"]["moe"]["router"].shape == (64, 16)
    kw = k2_reference.from_config_file(K2_TINY)
    assert (kw["held_offset"], kw["num_experts"]) == (4, 16)
    assert kw["dense_layers"] == 1 and kw["pairs"] == "interleaved"


@pytest.mark.parametrize("bad, word", [
    ({"n_group": 8}, "n_group"), ({"topk_method": "greedy"}, "topk_method"),
    ({"rope_scaling": dict(K2_YARN, type="linear")}, "rope_scaling"),
    ({"mla_use_nope": True}, "does not read"),
    ({"reference": {}}, "rope_pairs"),
], ids=["groups", "choice", "scaling", "unknown_key", "pairs"])
def test_k2_adapter_refuses_what_it_cannot_express(bad, word):
    with pytest.raises(model_config.Unsupported, match=word):
        k2_adapter.source_kwargs(dict(K2_TINY, **bad))


@pytest.mark.parametrize("pairs", ["interleaved", "split"])
def test_k2_uncached_logits_match_the_reference(pairs):
    """The program's uncached forward (the expanded form, float32) against
    the reference under a non-zero selection bias, past the rotation's
    original context (80 positions against 16)."""
    body = dict(K2_TINY, reference={"rope_pairs": pairs})
    cfg, model, params = k2_build(body)
    ids = _k2_ids()
    kw = k2_reference.from_config_file(body)
    got = jax.jit(lambda p: k2_adapter.program_logits(model, p, ids))(params)
    want = jax.jit(lambda p: k2_reference.forward(
        k2_adapter.params_view(cfg, p), ids, **kw))(params)
    verdict = correct.compare_logits(got, want, rel_rms_tol=1e-4)
    assert verdict["ok"], verdict
    # blocked over the queries: the same rows
    blocked = k2_reference.forward(
        k2_adapter.params_view(cfg, params), ids, q_block=32, **kw)
    assert float(jnp.abs(blocked - want).max()) < 1e-4
    # and the bias is in the choice: without it the logits differ
    for layer in params.values():
        if "moe" in layer:
            layer["moe"]["selection_bias"] = jnp.zeros((cfg.num_experts,))
    other = k2_adapter.program_logits(model, params, ids)
    assert float(jnp.abs(other - got).max()) > 1e-3


def test_k2_yarn_by_hand_at_the_published_sizes():
    """low 8, high 20, sigma 0.144680, f'_0 = f_0, f'_31 = f_31 / 64: the
    reference's numbers, and the program's table holds the same."""
    from luminaai_tpu.config import Config
    from luminaai_tpu.models.layers import rope_frequencies, yarn_ramp

    yarn = {k: float(v) for k, v in K2_YARN.items() if k != "type"}
    freqs, (low, high) = k2_reference.yarn_frequencies(64, 50000.0, yarn)
    assert (low, high) == (8, 20)
    f = 50000.0 ** (-np.arange(32) / 32.0)
    ramp = np.clip((np.arange(32) - 8) / 12.0, 0.0, 1.0)
    want = f * (1 - ramp) + f / 64 * ramp
    np.testing.assert_allclose(np.asarray(freqs), want, rtol=1e-5)
    assert float(freqs[0]) == pytest.approx(1.0)
    assert float(freqs[31]) == pytest.approx(f[31] / 64, rel=1e-5)
    assert float(freqs[8]) == pytest.approx(f[8], rel=1e-6)
    assert float(freqs[20]) == pytest.approx(f[20] / 64, rel=1e-5)
    m = 0.1 * math.log(64.0) + 1.0
    assert m == pytest.approx(1.41589, abs=1e-5)
    assert k2_reference.softmax_scale(128, 64, yarn) == pytest.approx(
        0.144680, abs=1e-6)
    cfg = Config(hidden_size=64, num_heads=4, layer_mixers=("latent",) * 2,
                 num_layers=2, latent_rope=True, rope_theta=50000.0,
                 yarn_factor=64, yarn_original_max=4096,
                 yarn_mscale_all_dim=1.0)
    assert cfg.latent_softmax_scale() == pytest.approx(0.144680, abs=1e-6)
    assert cfg.latent_rope_mscale() == pytest.approx(1.0)
    np.testing.assert_allclose(
        yarn_ramp(64, 50000.0, cfg.yarn()), ramp, atol=1e-12)
    cos, sin = rope_frequencies(64, 8192, 50000.0, yarn=cfg.yarn())
    np.testing.assert_allclose(
        np.asarray(sin[5000]), np.sin(5000 * want), atol=2e-3)
    np.testing.assert_allclose(np.asarray(cos[1]), np.cos(want), atol=1e-6)


def test_k2_shares_of_the_expert_layer_add_up():
    """The partial results of all E / count shares (4 shares of 4 of 16
    experts), with the shared expert counted once, sum to the uncut layer
    in the reference; each share's program layer agrees with its
    reference share."""
    from luminaai_tpu.config import Config
    from luminaai_tpu.models.moe import MoELayer

    E, count, H, F, k = 16, 4, 64, 32, 4
    keys = jax.random.split(jax.random.key(5), 7)
    x = jax.random.normal(keys[0], (2, 40, H))
    full = {
        "router": jax.random.normal(keys[1], (H, E)),
        "selection_bias": 0.3 * jax.random.normal(keys[2], (E,)),
        "wi": 0.1 * jax.random.normal(keys[3], (E, H, 2 * F)),
        "wo": 0.1 * jax.random.normal(keys[4], (E, F, H)),
        "shared_wi": 0.1 * jax.random.normal(keys[5], (H, 2 * F)),
        "shared_wo": 0.1 * jax.random.normal(keys[6], (F, H)),
    }
    rule = dict(top_k=k, scale=2.827)
    with jax.default_matmul_precision("highest"):
        uncut = k2_reference.expert_layer(x, full, held_offset=0, **rule)
        total = jnp.zeros_like(uncut)
        for share, off in enumerate(range(0, E, count)):
            part = dict(full, wi=full["wi"][off:off + count],
                        wo=full["wo"][off:off + count])
            total = total + k2_reference.expert_layer(
                x, part, held_offset=off, shared=share == 0, **rule)
            # the program's layer, told the same range (shared expert in)
            cfg = Config(
                hidden_size=H, num_heads=4, intermediate_size=128,
                precision="fp32", use_moe=True, num_experts=E, moe_top_k=k,
                experts_held=(off, count), moe_dispatch="gmm",
                capacity_factor=float(E) / k, routing_noise_std=0.0,
                moe_score_func="sigmoid", moe_selection_bias=True,
                moe_routed_scale=2.827, moe_intermediate_size=F,
                num_shared_experts=1)
            got, stats = MoELayer(cfg, dtype=jnp.float32).apply(
                {"params": {
                    "router": full["router"],
                    "selection_bias": full["selection_bias"],
                    "wi": part["wi"], "wo": part["wo"],
                    "shared_expert": {"wi": full["shared_wi"],
                                      "wo": full["shared_wo"]}}}, x)
            want = k2_reference.expert_layer(x, part, held_offset=off,
                                             **rule)
            assert float(jnp.abs(got - want).max()) < 1e-4, off
            assert float(stats["moe_held_pairs_dropped"]) == 0.0
    assert float(jnp.abs(total - uncut).max()) < 1e-4
    assert float(jnp.abs(uncut).max()) > 0.1


def test_k2_cell_resolves_this_architecture():
    bench = manifest.load_benchmark()
    cell = manifest.Cell(bench, "kimi-k2-7-code-serve-longctx")
    assert cell.architecture.name == "kimi_k2" and cell.chips == 1
    kw = model_config.config_kwargs(cell.architecture, cell.config)
    assert kw["experts_held"] == (0, 12) and kw["num_experts"] == 384
    assert kw["layer_mixers"] == ("latent",) * 5 and kw["q_lora_rank"] == 1536
    work = cell.architecture.work
    assert work.params_total(cell.config) == 3_496_763_904
    assert set(work.KERNEL_FNS) == manifest.kernel_names("kimi_k2")
    for fn in work.KERNEL_FNS.values():
        counts = fn(cell.config, {})
        assert counts["ops"] > 0 and counts["bytes"] > 0
    # the catalog row's numbers, every one at the top level, no width cut
    body = cell.config
    assert body["reduced"] == ["num_hidden_layers", "n_routed_experts",
                               "vocab_size"]
    assert (body["hidden_size"], body["q_lora_rank"], body["kv_lora_rank"],
            body["moe_intermediate_size"]) == (7168, 1536, 512, 2048)
    assert body["deployment"]["layer_shared_by_chips"] == 32
