"""The yardstick for `correct` is itself checked: this architecture's
reference against LuminaTransformer at a tiny size on the CPU (five
layers: delta, delta, delta, latent, delta; 16 experts, 4 held): logits,
loss and the gradient of every parameter; and the shares of the expert
layer against the uncut layer. The modules are reached as a cell reaches
them, by the architecture's name."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import correct, manifest, model_config

KIMI = manifest.Architecture("kimi_linear")
kimi_reference, kimi_adapter = KIMI.reference, KIMI.adapter

KIMI_TINY = {
    "hidden_act": "silu", "hidden_size": 64, "intermediate_size": 160,
    "num_hidden_layers": 5, "num_attention_heads": 4,
    "num_key_value_heads": 4, "vocab_size": 512, "rms_norm_eps": 1e-5,
    "rope_theta": 1e4, "tie_word_embeddings": False,
    "first_k_dense_replace": 1, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "mla_use_nope": True,
    "q_lora_rank": None, "rope_scaling": None,
    "linear_attn_config": {
        "full_attn_layers": [4, 8], "kda_layers": [1, 2, 3, 5, 6, 7],
        "head_dim": 16, "num_heads": 4, "short_conv_kernel_size": 4},
    "moe_intermediate_size": 32, "moe_layer_freq": 1,
    "moe_renormalize": True, "moe_router_activation_func": "sigmoid",
    "num_expert_group": 1, "topk_group": 1, "num_experts": 4,
    "num_experts_per_token": 4, "num_shared_experts": 1,
    "routed_scaling_factor": 2.446, "num_nextn_predict_layers": 0,
    "reduced": ["num_hidden_layers", "num_experts"],
    "source_values": {"num_hidden_layers": 8, "num_experts": 16},
    "deployment": {"experts_held_offset": 4},
    "program": {"precision": "fp32", "use_flash_attention": False,
                "use_stable_embedding": False, "moe_dispatch": "gmm",
                "capacity_factor": 4.0, "routing_noise_std": 0.0,
                "load_balancing_weight": 0.0, "router_z_loss_weight": 0.0,
                "gradient_checkpointing": False},
}


def _kimi_build(body, **over):
    from luminaai_tpu.models.transformer import LuminaTransformer
    from luminaai_tpu.parallel.sharding import unbox

    cfg = model_config.build_config(KIMI, body, seq_length=96, batch_size=2,
                                    **over)
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


def _kimi_ids(rows=2, length=80):
    return jnp.asarray(np.random.RandomState(0).randint(
        3, 512, size=(rows, length)), jnp.int32)


def test_kimi_adapter_names_the_layers_and_the_share():
    cfg, _, params = _kimi_build(KIMI_TINY)
    assert cfg.layer_mixers == ("kda", "kda", "kda", "latent", "kda")
    assert cfg.num_experts == 16 and cfg.experts_held == (4, 4)
    assert not cfg.is_moe_layer(0) and cfg.is_moe_layer(4)
    assert params["layer_1"]["moe"]["wi"].shape[0] == 4
    assert params["layer_1"]["moe"]["router"].shape == (64, 16)
    kw = kimi_reference.from_config_file(KIMI_TINY)
    assert kw["layer_kinds"] == cfg.layer_mixers
    assert (kw["held_offset"], kw["num_experts"]) == (4, 16)


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "save_attn"])
def test_kimi_logits_loss_and_every_gradient_match(remat):
    """The program (its interpreted kernels, float32) against the
    reference's forward pass and `jax.grad`: logits, next-token loss, and
    the gradient of the loss in every parameter."""
    cfg, model, params = _kimi_build(
        KIMI_TINY, gradient_checkpointing=remat, remat_policy="save_attn")
    ids = _kimi_ids()
    kw = kimi_reference.from_config_file(KIMI_TINY)

    def program_loss(p):
        logits = kimi_adapter.program_logits(model, p, ids)
        return correct.next_token_loss(logits, ids), logits

    def reference_loss(p):
        logits = kimi_reference.forward(
            kimi_adapter.params_view(cfg, p), ids, **kw)
        return correct.next_token_loss(logits, ids), logits

    (loss_p, got), grad_p = jax.jit(
        jax.value_and_grad(program_loss, has_aux=True))(params)
    (loss_r, want), grad_r = jax.jit(
        jax.value_and_grad(reference_loss, has_aux=True))(params)
    verdict = correct.compare_logits(got, want, rel_rms_tol=1e-4)
    assert verdict["ok"], verdict
    assert abs(float(loss_p) - float(loss_r)) < 1e-5
    flat_p = jax.tree_util.tree_leaves_with_path(grad_p)
    flat_r = jax.tree.leaves(grad_r)
    assert len(flat_p) == len(flat_r) > 60
    for (path, a), b in zip(flat_p, flat_r):
        name = jax.tree_util.keystr(path)
        scale = float(jnp.abs(b).max())
        err = float(jnp.abs(a - b).max())
        if "selection_bias" in name:
            assert scale == 0.0 and err == 0.0, name  # the choice alone
            continue
        assert scale > 0.0, f"{name}: the reference's gradient is zero"
        assert err <= 2e-4 * scale + 1e-9, (name, err, scale)


def test_kimi_shares_of_the_expert_layer_add_up():
    """The partial results of all E / count shares, with the shared expert
    counted once, sum to the uncut layer: in the reference, and each
    share's program layer agrees with its reference share."""
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
    rule = dict(top_k=k, scale=2.446)
    with jax.default_matmul_precision("highest"):
        uncut = kimi_reference.expert_layer(x, full, held_offset=0, **rule)
        total = jnp.zeros_like(uncut)
        for share, off in enumerate(range(0, E, count)):
            part = dict(full, wi=full["wi"][off:off + count],
                        wo=full["wo"][off:off + count])
            ref_share = kimi_reference.expert_layer(
                x, part, held_offset=off, shared=share == 0, **rule)
            total = total + ref_share
            # the program's layer, told the same range (shared expert in)
            cfg = Config(
                hidden_size=H, num_heads=4, intermediate_size=128,
                precision="fp32", use_moe=True, num_experts=E, moe_top_k=k,
                experts_held=(off, count), moe_dispatch="gmm",
                capacity_factor=float(E) / k, routing_noise_std=0.0,
                moe_score_func="sigmoid", moe_selection_bias=True,
                moe_routed_scale=2.446, moe_intermediate_size=F,
                num_shared_experts=1)
            got, stats = MoELayer(cfg, dtype=jnp.float32).apply(
                {"params": {
                    "router": full["router"],
                    "selection_bias": full["selection_bias"],
                    "wi": part["wi"], "wo": part["wo"],
                    "shared_expert": {"wi": full["shared_wi"],
                                      "wo": full["shared_wo"]}}}, x)
            want = kimi_reference.expert_layer(x, part, held_offset=off,
                                               **rule)
            assert float(jnp.abs(got - want).max()) < 1e-4, off
            assert float(stats["moe_held_pairs_dropped"]) == 0.0
    assert float(jnp.abs(total - uncut).max()) < 1e-4
    assert float(jnp.abs(uncut).max()) > 0.1


def test_kimi_cell_resolves_this_architecture():
    bench = manifest.load_benchmark()
    cell = manifest.Cell(bench, "kimi-linear-train-8k")
    assert cell.architecture.name == "kimi_linear" and cell.chips == 1
    kw = model_config.config_kwargs(cell.architecture, cell.config)
    assert kw["experts_held"] == (0, 8) and kw["num_experts"] == 256
    assert kw["layer_mixers"] == ("kda", "kda", "kda", "latent", "kda")
    work = cell.architecture.work
    assert abs(work.params_total(cell.config) - 602.4e6) < 1e6
    assert set(work.KERNEL_FNS) == manifest.kernel_names("kimi_linear")
