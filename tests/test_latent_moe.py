"""Experts in a latent, and layers that are one sub-layer (ISSUE 63).

  - `MoELayer` under `experts_held` with a latent width (`fc1` / `fc2`
    around dispatch and combine), non-gated relu^2 experts and a shared
    expert of its own width, against the plain reference's expert layer
    (benchmark/architectures/nemotron_h), and THE SHARE TEST: four shares
    of 8 of 32 experts, `fc2` of each part and the shared expert counted
    once, add up to the uncut layer;
  - the options are refused by name outside `experts_held`;
  - a mixer-only and a feed-forward-only `TransformerBlock` have ONE norm
    and ONE residual add;
  - the tiny tick of two configurations this PR does not serve differently
    (`jamba`, `mimo_v2`) lowers to the text it lowered to on the parent.
"""

import hashlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import manifest, model_config
from luminaai_tpu.config import Config
from luminaai_tpu.models.layers import RMSNorm
from luminaai_tpu.models.moe import MoELayer
from luminaai_tpu.models.ssm import ScalarDecaySSM
from luminaai_tpu.models.transformer import TransformerBlock

ARCH = manifest.Architecture("nemotron_h")
E, COUNT, H, LAT, F, FS, K = 32, 8, 64, 32, 48, 96, 6


def _layer_config(offset, **over):
    kw = dict(
        hidden_size=H, num_heads=4, intermediate_size=128, precision="fp32",
        use_moe=True, num_experts=E, moe_top_k=K,
        experts_held=(offset, COUNT), moe_dispatch="gmm",
        capacity_factor=float(E) / COUNT, routing_noise_std=0.0,
        moe_score_func="sigmoid", moe_selection_bias=True,
        moe_routed_scale=5.0, moe_intermediate_size=F,
        moe_expert_act="relu2", moe_latent_size=LAT, moe_shared_size=FS)
    kw.update(over)
    return Config(**kw)


@pytest.fixture(scope="module")
def whole():
    """The uncut layer's weights in the reference's own names."""
    ks = jax.random.split(jax.random.key(5), 9)
    return {
        "x": jax.random.normal(ks[0], (2, 40, H)),
        "router": jax.random.normal(ks[1], (H, E)),
        "selection_bias": 0.3 * jax.random.normal(ks[2], (E,)),
        "fc1": 0.2 * jax.random.normal(ks[3], (H, LAT)),
        "wi": 0.2 * jax.random.normal(ks[4], (E, LAT, F)),
        "wo": 0.2 * jax.random.normal(ks[5], (E, F, LAT)),
        "fc2": 0.2 * jax.random.normal(ks[6], (LAT, H)),
        "shared_wi": 0.1 * jax.random.normal(ks[7], (H, FS)),
        "shared_wo": 0.1 * jax.random.normal(ks[8], (FS, H)),
    }


def _share(whole, off):
    return dict(whole, wi=whole["wi"][off:off + COUNT],
                wo=whole["wo"][off:off + COUNT])


def _program_params(part):
    return {"router": part["router"],
            "selection_bias": part["selection_bias"], "fc1": part["fc1"],
            "wi": part["wi"], "wo": part["wo"], "fc2": part["fc2"],
            "shared_expert": {"wi": part["shared_wi"],
                              "wo": part["shared_wo"]}}


@pytest.mark.parametrize("offset", [0, 8, 16, 24])
def test_a_shares_layer_matches_the_reference(whole, offset):
    """The program's layer holding experts [offset, offset + 8) against
    the reference's share (the shared expert in both), float32: 1e-4 of
    the layer's own scale, and no pair beyond the row bound."""
    part = _share(whole, offset)
    with jax.default_matmul_precision("highest"):
        want = ARCH.reference.expert_layer(
            whole["x"], part, top_k=K, held_offset=offset, scale=5.0)
        got, stats = MoELayer(_layer_config(offset), dtype=jnp.float32).apply(
            {"params": _program_params(part)}, whole["x"])
    assert float(jnp.abs(want).max()) > 0.1
    assert float(jnp.abs(got - want).max()) < 1e-4 * float(
        jnp.abs(want).max()), offset
    assert float(stats["moe_held_pairs_dropped"]) == 0.0
    assert float(stats["moe_routed_pairs"]) == 2 * 40 * K
    assert 0 < float(stats["moe_held_pairs"]) < 2 * 40 * K
    assert 0 < float(stats["moe_held_experts_hit"]) <= COUNT
    # the control: the bias entering the weights is another layer
    other = ARCH.reference.expert_layer(
        whole["x"], part, top_k=K, held_offset=offset, scale=5.0,
        bias_in_weights=True)
    assert float(jnp.abs(got - other).max()) > 1e-2 * float(
        jnp.abs(want).max())


def test_the_shares_add_up_to_the_uncut_layer(whole):
    """Four shares of 8 of 32: `fc2` of each share's part (no bias, so
    they add) and the shared expert counted ONCE are the uncut layer, in
    the reference and in the program's layers alike."""
    rule = dict(top_k=K, scale=5.0)
    with jax.default_matmul_precision("highest"):
        uncut = ARCH.reference.expert_layer(
            whole["x"], whole, held_offset=0, **rule)
        shared = ARCH.reference._relu2(
            whole["x"], whole["shared_wi"], whole["shared_wo"])
        total = program_total = jnp.zeros_like(uncut)
        for i, off in enumerate(range(0, E, COUNT)):
            part = _share(whole, off)
            total = total + ARCH.reference.expert_layer(
                whole["x"], part, held_offset=off, shared=i == 0, **rule)
            got, _ = MoELayer(_layer_config(off), dtype=jnp.float32).apply(
                {"params": _program_params(part)}, whole["x"])
            # every chip computes the shared expert: counted once
            program_total = program_total + got - (shared if i else 0.0)
    scale = float(jnp.abs(uncut).max())
    assert scale > 0.1
    assert float(jnp.abs(total - uncut).max()) < 1e-5 * scale
    assert float(jnp.abs(program_total - uncut).max()) < 1e-4 * scale


@pytest.mark.parametrize("over, word", [
    (dict(experts_held=None, moe_dispatch="sort"), "moe_expert_act"),
    (dict(experts_held=None, moe_dispatch="gmm", moe_expert_act="swiglu"),
     "moe_latent_size"),
    (dict(experts_held=None, moe_dispatch="einsum", moe_expert_act="swiglu",
          moe_latent_size=None, moe_score_func="softmax",
          moe_selection_bias=False, moe_routed_scale=1.0), "moe_shared_size"),
    (dict(moe_expert_act="gelu"), "invalid moe_expert_act"),
], ids=["relu2_sorted", "latent_unshared_gmm", "shared_width_einsum",
        "unknown_act"])
def test_the_other_dispatch_paths_refuse_the_options_by_name(over, word):
    with pytest.raises(AssertionError, match=word):
        _layer_config(0, **over)


def _block_config():
    return Config(
        hidden_size=H, num_heads=4, num_kv_heads=2, intermediate_size=128,
        num_layers=4, precision="fp32", scan_layers=False, use_rope=False,
        layer_mixers=("ssm2", "none", "attention", "none"),
        layer_ffns=("none", "moe", "none", "dense"),
        ssm2_num_heads=8, ssm2_head_dim=16, ssm2_groups=2, ssm2_chunk=8,
        ssm_state_size=8, use_flash_attention=False, use_moe=True,
        num_experts=E, moe_top_k=K, experts_held=(8, COUNT),
        moe_dispatch="gmm", capacity_factor=4.0, routing_noise_std=0.0,
        moe_score_func="sigmoid", moe_intermediate_size=F,
        moe_expert_act="relu2", moe_latent_size=LAT, moe_shared_size=FS)


@pytest.mark.parametrize("layer, norm, branch", [
    (0, "attn_norm", "ssm"), (1, "ffn_norm", "moe"),
    (2, "attn_norm", "attention"), (3, "ffn_norm", "ffn")])
def test_a_layer_of_one_sub_layer_has_one_norm_and_one_add(layer, norm,
                                                           branch):
    """x + f(norm(x)): the block's parameters are ONE norm and the branch,
    and its output is the residual plus the branch applied by hand."""
    cfg = _block_config()
    assert not cfg.is_moe_layer(0) and cfg.is_moe_layer(1)
    assert (cfg.ffn_kind(2), cfg.ffn_kind(3)) == ("none", "dense")
    block = TransformerBlock(cfg, layer_idx=layer, dtype=jnp.float32)
    x = jax.random.normal(jax.random.key(layer), (2, 24, H))
    params = block.init(jax.random.key(9), x)["params"]
    params = jax.tree.map(lambda v: getattr(v, "value", v), params,
                          is_leaf=lambda v: hasattr(v, "value"))
    assert sorted(params) == sorted([norm, branch])
    out, cache, _ = block.apply({"params": params}, x)
    assert cache is None
    normed = RMSNorm(cfg.rms_norm_eps, dtype=jnp.float32).apply(
        {"params": params[norm]}, x)
    if branch == "ssm":
        by_hand, _ = ScalarDecaySSM(cfg, dtype=jnp.float32).apply(
            {"params": params["ssm"]}, normed)
    elif branch == "moe":
        by_hand, _ = MoELayer(cfg, dtype=jnp.float32).apply(
            {"params": params["moe"]}, normed)
    else:
        return  # the attention and dense branches are the old modules
    np.testing.assert_allclose(np.asarray(out), np.asarray(x + by_hand),
                               atol=1e-5)


def test_a_layer_needs_a_mixer_or_a_feed_forward():
    with pytest.raises(AssertionError, match="neither mixer nor"):
        Config(num_layers=2, scan_layers=False,
               layer_mixers=("none", "attention"),
               layer_ffns=("none", "dense"))
    with pytest.raises(AssertionError, match="layer_ffns"):
        Config(num_layers=2, scan_layers=True,
               layer_ffns=("dense", "none"))


def test_the_model_has_no_cache_entry_for_a_layer_without_a_mixer():
    from luminaai_tpu.models.ssm import is_lane_state
    from luminaai_tpu.models.transformer import LuminaTransformer

    cfg = _block_config()
    caches = LuminaTransformer(cfg).init_cache(3, 32)
    assert [c is None for c in caches] == [False, True, False, True]
    assert is_lane_state(caches[0])
    assert caches[0].state.shape == (3, 8, 128)
    assert caches[0].tail.shape == (3, 3, 128 + 2 * 2 * 8)


# sha256 of `fn.lower(*args).as_text()` of the tiny tick (4 slots of 64
# tokens, pages of 16, a 16-row chunk, ragged_xla, float32) at the parent
# of ISSUE 63's change (commit 34238d3): the modules this PR touched
# (TransformerBlock, SelectiveSSM's convolution helpers, MoELayer,
# _gmm_held, the tick's held-pair fetch) must trace for these stacks what
# they traced. Regenerate only with a change that means to move them.
PARENT_TICK_SHA256 = {
    "jamba": ("28c62cbaeb18b625dd303f577b0a63bf5954965e1d81f7509f34c07e320575"
              "92"),
    "mimo_v2": ("796631e755cd9d2575115ecf18361e7b3569332179551a611e9395bded2a"
                "32af"),
}


@pytest.mark.parametrize("arch_name", sorted(PARENT_TICK_SHA256))
def test_the_other_stacks_tick_lowers_to_the_parents_text(arch_name):
    from benchmark.architectures.jamba.test_reference import JAMBA_TINY
    from benchmark.architectures.mimo_v2.test_reference import MIMO_TINY
    from benchmark.serve_cell import StubTokenizer
    from luminaai_tpu.inference.generate import (GREEDY_SAMPLE_KEY,
                                                 GenerationEngine)
    from luminaai_tpu.models.transformer import LuminaTransformer
    from luminaai_tpu.parallel.sharding import unbox

    body = {"jamba": JAMBA_TINY, "mimo_v2": MIMO_TINY}[arch_name]
    cfg = model_config.build_config(
        manifest.Architecture(arch_name), body, seq_length=64,
        prefill_chunk_size=16, attention_backend="ragged_xla")
    model = LuminaTransformer(cfg)
    params = unbox(jax.jit(model.init)(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32))["params"])
    dec = GenerationEngine(
        model, params, StubTokenizer(cfg.vocab_size), cfg).make_stepwise(
            num_slots=4, page_size=16, max_slot_tokens=64)
    fn, args = dec.step_fn_and_args(GREEDY_SAMPLE_KEY)
    text = fn.lower(*args).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == PARENT_TICK_SHA256[
        arch_name], (
        f"the tiny {arch_name} tick no longer lowers to the parent's text "
        f"({len(text)} characters now)")
