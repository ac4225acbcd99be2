"""What command-a-plus's block asks of the model code (ISSUE 42), a piece
at a time, at a tiny size on the CPU in float32: a head size that is not
hidden / heads, interleaved against split-halves rotation, a rotation and
a window a layer, the parallel block over one bias-free LayerNorm, shared
experts averaged; every one is data with today's behaviour as its default,
so an existing model's logits are bit for bit what they were. And the
share test the model-configs guide asks for: the routed parts of all eight
shares, with the shared experts counted once, add up to the uncut
reference's layer.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import manifest
from luminaai_tpu.config import Config
from luminaai_tpu.models.layers import apply_rope, rope_frequencies
from luminaai_tpu.models.moe import MoELayer
from luminaai_tpu.models.transformer import LuminaTransformer
from luminaai_tpu.parallel.sharding import unbox

COHERE = manifest.Architecture("cohere2_moe")


def _config(**over):
    kw = dict(vocab_size=64, hidden_size=32, num_layers=2, num_heads=4,
              num_kv_heads=2, intermediate_size=48, seq_length=32,
              precision="fp32", use_flash_attention=False,
              use_stable_embedding=False, scan_layers=False, init_std=0.3)
    kw.update(over)
    cfg = Config(**kw)
    cfg.validate()
    return cfg


def _model(cfg, key=0):
    model = LuminaTransformer(cfg)
    params = unbox(jax.jit(model.init)(
        jax.random.key(key), jnp.zeros((1, 8), jnp.int32))["params"])
    return model, params


def _logits(cfg, params, ids):
    return np.asarray(
        LuminaTransformer(cfg).apply({"params": params}, ids)[0])


IDS = jnp.asarray(np.random.RandomState(0).randint(3, 64, size=(2, 24)))


def test_a_head_size_of_its_own():
    cfg = _config(num_heads=6, attn_head_dim=16)  # 6 x 16 = 96 != 32
    assert cfg.head_dim() == 16 and 6 * 16 != cfg.hidden_size
    _, params = _model(cfg)
    attn = params["layer_0"]["attention"]
    assert attn["wq"].shape == (32, 6, 16) and attn["wo"].shape == (6, 16, 32)
    assert attn["wk"].shape == (32, 2, 16)
    assert np.isfinite(_logits(cfg, params, IDS)).all()
    k, v = LuminaTransformer(cfg).init_cache(3, 32)[0]
    assert k.shape == (3, 32, 2, 16) == v.shape
    assert cfg.estimate_parameters() == sum(
        x.size for x in jax.tree.leaves(params))
    # hidden_size need not divide by num_heads once the head has a size
    _config(hidden_size=40, num_heads=6, attn_head_dim=16)
    with pytest.raises(AssertionError, match="divisible by num_heads"):
        _config(hidden_size=40, num_heads=6)


def test_interleaved_rotation_is_split_halves_under_a_column_permutation():
    d, S = 16, 12
    x = jnp.asarray(np.random.RandomState(1).randn(1, S, 3, d), jnp.float32)
    cos, sin = rope_frequencies(d, S, 50000.0)
    split = apply_rope(x, cos, sin)
    inter = apply_rope(x, cos, sin, layout="interleaved")
    assert float(jnp.abs(split - inter).max()) > 0.1
    # column 2i -> i, column 2i + 1 -> i + d/2
    perm = np.concatenate([np.arange(0, d, 2), np.arange(1, d, 2)])
    assert np.allclose(np.asarray(apply_rope(x[..., perm], cos, sin)),
                       np.asarray(inter)[..., perm], atol=1e-6)
    # ... so one model under a permutation of each head's q and k columns
    cfg_s, cfg_i = _config(), _config(rope_layout="interleaved")
    _, params = _model(cfg_s)
    moved = jax.tree.map(lambda a: a, params)
    d = cfg_s.head_dim()
    inv = np.argsort(np.concatenate([np.arange(0, d, 2), np.arange(1, d, 2)]))
    for i in range(cfg_s.num_layers):
        a = dict(moved[f"layer_{i}"]["attention"])
        a["wq"], a["wk"] = a["wq"][..., inv], a["wk"][..., inv]
        moved[f"layer_{i}"] = dict(moved[f"layer_{i}"], attention=a)
    assert np.abs(_logits(cfg_i, params, IDS)
                  - _logits(cfg_s, params, IDS)).max() > 1e-3
    assert np.allclose(_logits(cfg_i, moved, IDS),
                       _logits(cfg_s, params, IDS), atol=2e-5)


def test_a_rotation_and_a_window_a_layer():
    base = _config()
    _, params = _model(base)
    every = _logits(base, params, IDS)
    none = _logits(_config(use_rope=False), params, IDS)
    mixed = _logits(_config(layer_rope=(True, False)), params, IDS)
    assert np.abs(mixed - every).max() > 1e-3
    assert np.abs(mixed - none).max() > 1e-3
    # the values the one switch gives, a layer at a time: bit for bit
    assert (_logits(_config(layer_rope=(True, True)), params, IDS)
            == every).all()
    assert (_logits(_config(layer_rope=(False, False)), params, IDS)
            == none).all()
    banded = _logits(_config(attention_window=5), params, IDS)
    assert (_logits(_config(layer_windows=(5, 5)), params, IDS)
            == banded).all()
    one = _logits(_config(layer_windows=(5, None)), params, IDS)
    assert np.abs(one - banded).max() > 1e-4
    assert np.abs(one - every).max() > 1e-4
    # inside the window nothing differs
    assert np.allclose(one[:, :5], every[:, :5], atol=1e-6)
    cfg = _config(layer_windows=(5, None), layer_rope=(True, False))
    assert [cfg.window_of(i) for i in range(2)] == [5, None]
    assert [cfg.rope_of(i) for i in range(2)] == [True, False]
    with pytest.raises(AssertionError, match="layer_windows names 1"):
        _config(layer_windows=(5,))
    with pytest.raises(AssertionError, match="one uniform window"):
        _config(layer_windows=(5, None), attention_window=7)
    with pytest.raises(AssertionError, match="one kind of layer"):
        _config(layer_windows=(5, None), scan_layers=True)


def test_the_parallel_block_over_one_layernorm():
    cfg = _config(parallel_block=True, norm_kind="layernorm", num_layers=1)
    model, params = _model(cfg)
    layer = params["layer_0"]
    assert "ffn_norm" not in layer and set(layer["attn_norm"]) == {"scale"}
    assert set(params["final_norm"]) == {"scale"}  # no bias anywhere
    scale = 1.0 + 0.3 * jax.random.normal(jax.random.key(5), (32,))
    params = dict(params, layer_0=dict(layer, attn_norm={"scale": scale}))
    got = _logits(cfg, params, IDS)

    # by hand: x + attn(LN(x)) + ffn(LN(x)), LN mean-subtracting
    def ln(x, g):
        mu = x.mean(-1, keepdims=True)
        var = ((x - mu) ** 2).mean(-1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + cfg.layer_norm_eps) * g

    from luminaai_tpu.models.layers import GQAttention, SwiGLU

    x = params["embedder"]["embedding"][IDS]
    h = ln(x, scale)
    attn, _ = GQAttention(cfg, dtype=jnp.float32, layer_idx=0).apply(
        {"params": layer["attention"]}, h)
    ffn = SwiGLU(cfg.intermediate_size, dtype=jnp.float32).apply(
        {"params": layer["ffn"]}, h)
    out = ln(x + attn + ffn, params["final_norm"]["scale"])
    want = out @ params["embedder"]["embedding"].T
    assert np.allclose(got, np.asarray(want), atol=2e-5)
    # and it is not the sequential block
    seq = _config(norm_kind="layernorm", num_layers=1)
    seq_params = dict(params, layer_0=dict(
        params["layer_0"], ffn_norm={"scale": jnp.ones((32,))}))
    assert np.abs(_logits(seq, seq_params, IDS) - got).max() > 1e-3


MOE = dict(use_moe=True, moe_pattern="all", num_experts=8, moe_top_k=2,
           moe_dispatch="gmm", moe_score_func="sigmoid",
           moe_intermediate_size=24, num_shared_experts=2,
           routing_noise_std=0.0, num_layers=1)


def test_shared_experts_averaged():
    summed = _config(capacity_factor=4.0, **MOE)
    mean = _config(capacity_factor=4.0, shared_expert_combine="average",
                   **MOE)
    _, params = _model(summed)
    moe = {"params": params["layer_0"]["moe"]}
    x = jax.random.normal(jax.random.key(2), (2, 9, 32))
    y_sum, _ = MoELayer(summed, dtype=jnp.float32).apply(moe, x)
    y_mean, _ = MoELayer(mean, dtype=jnp.float32).apply(moe, x)
    from luminaai_tpu.models.layers import SwiGLU

    shared = SwiGLU(2 * 24, dtype=jnp.float32).apply(
        {"params": params["layer_0"]["moe"]["shared_expert"]}, x)
    assert float(jnp.abs(shared).max()) > 0.1
    assert np.allclose(np.asarray(y_sum - y_mean), np.asarray(shared / 2),
                       atol=1e-5)


@pytest.mark.parametrize("held", [1, 2], ids=["8_shares", "4_shares"])
def test_the_shares_add_up_to_the_uncut_layer(held):
    """One chip of E / held computes the held experts' part of the routed
    result (weights from the FULL top-k) plus the shared experts whole.
    The routed parts of all the shares, and the shared experts once, are
    the uncut reference's expert layer; with a share left out they are
    not (the control)."""
    E, n_shared = 8, 2
    whole = _config(capacity_factor=float(E), **MOE,
                    shared_expert_combine="average")
    _, params = _model(whole, key=3)
    moe = params["layer_0"]["moe"]
    x = jax.random.normal(jax.random.key(4), (3, 7, 32))
    view = {"router": moe["router"], "wi": moe["wi"], "wo_e": moe["wo"],
            "shared_wi": moe["shared_expert"]["wi"],
            "shared_wo": moe["shared_expert"]["wo"]}
    with jax.default_matmul_precision("highest"):
        uncut = COHERE.reference._expert_layer(
            x, view, top_k=2, held_offset=0, n_shared=n_shared)
        only_shared = COHERE.reference._expert_layer(
            x, dict(view, wi=moe["wi"][:0], wo_e=moe["wo"][:0]),
            top_k=2, held_offset=0, n_shared=n_shared)
    assert float(jnp.abs(uncut - only_shared).max()) > 0.1

    routed = []
    for off in range(0, E, held):
        cfg = _config(experts_held=(off, held),
                      capacity_factor=float(E) / held, **MOE,
                      shared_expert_combine="average")
        share = dict(moe, wi=moe["wi"][off:off + held],
                     wo=moe["wo"][off:off + held])
        y, stats = MoELayer(cfg, dtype=jnp.float32).apply(
            {"params": share}, x)
        assert float(stats["moe_held_pairs_dropped"]) == 0.0
        routed.append(y - only_shared)  # what every chip computes alike
    total = sum(routed) + only_shared
    assert float(jnp.abs(total - uncut).max()) < 1e-5 * float(
        jnp.abs(uncut).max())
    short = sum(routed[1:]) + only_shared
    assert float(jnp.abs(short - uncut).max()) > 1e-3


def test_the_defaults_leave_an_existing_model_bit_for_bit():
    """Each new field at the value that says what the model already did:
    the same parameters (names, shapes, values) and the same logits."""
    plain = _config(attention_window=6)
    said = _config(
        layer_windows=(6, 6), layer_rope=(True, True), rope_layout="split",
        attn_head_dim=8, norm_kind="rms", parallel_block=False,
        shared_expert_combine="sum")
    _, p_plain = _model(plain)
    _, p_said = _model(said)
    flat_a = jax.tree_util.tree_leaves_with_path(p_plain)
    flat_b = jax.tree_util.tree_leaves_with_path(p_said)
    assert [k for k, _ in flat_a] == [k for k, _ in flat_b]
    assert all((a == b).all() for (_, a), (_, b) in zip(flat_a, flat_b))
    assert (_logits(plain, p_plain, IDS) == _logits(said, p_plain, IDS)).all()
    fresh = Config()
    assert (fresh.layer_windows, fresh.layer_rope, fresh.attn_head_dim,
            fresh.rope_layout, fresh.norm_kind, fresh.parallel_block,
            fresh.shared_expert_combine) == (
                None, None, None, "split", "rms", False, "sum")
