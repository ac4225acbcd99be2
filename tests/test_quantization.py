"""Weight-only quantization tests (ref trainer.py:575 QuantizationManager)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from luminaai_tpu.config import Config
from luminaai_tpu.models.transformer import LuminaTransformer
from luminaai_tpu.training.quantization import (
    QuantizationManager,
    QuantizedTensor,
    quantize_array,
    quantize_tree,
)


def tiny_config(**kw) -> Config:
    base = dict(
        vocab_size=256,
        hidden_size=64,
        num_layers=1,
        num_heads=4,
        num_kv_heads=2,
        seq_length=64,
        batch_size=2,
        use_flash_attention=False,
        gradient_checkpointing=False,
        precision="fp32",
    )
    base.update(kw)
    return Config(**base)


@pytest.mark.parametrize("bits", [8, 4])
def test_quantize_roundtrip_error(bits):
    w = jnp.asarray(np.random.RandomState(0).randn(128, 64), jnp.float32) * 0.02
    qt = quantize_array(w, bits=bits)
    deq = qt.dequantize(jnp.float32)
    assert deq.shape == w.shape
    # Per-channel symmetric: error bounded by scale/2 per element.
    rel = float(jnp.abs(deq - w).max() / jnp.abs(w).max())
    assert rel < (0.01 if bits == 8 else 0.12), rel


def test_int4_packs_two_per_byte():
    w = jnp.ones((16, 64), jnp.float32)
    qt = quantize_array(w, bits=4)
    assert qt.q.shape == (16, 32)  # packed along last axis
    assert qt.q.dtype == jnp.int8


def test_int4_odd_axis_padding():
    w = jnp.asarray(np.random.RandomState(1).randn(8, 63), jnp.float32)
    qt = quantize_array(w, bits=4)
    deq = qt.dequantize(jnp.float32)
    assert deq.shape == w.shape


def test_quantize_tree_skips_small_and_norms():
    params = {
        "attn": {"wq": jnp.ones((64, 128)), "scale": jnp.ones((64, 128))},
        "norm": {"scale": jnp.ones((128,))},
        "tiny": {"w": jnp.ones((2, 2))},
    }
    qtree, info = quantize_tree(params, bits=8, min_size=1024)
    assert isinstance(qtree["attn"]["wq"], QuantizedTensor)
    assert not isinstance(qtree["attn"]["scale"], QuantizedTensor)  # name skip
    assert not isinstance(qtree["norm"]["scale"], QuantizedTensor)
    assert not isinstance(qtree["tiny"]["w"], QuantizedTensor)  # size skip
    assert info["quantized_leaves"] == 1


def test_manager_validation():
    with pytest.raises(ValueError):
        QuantizationManager(tiny_config(quantization_method="gguf"))
    with pytest.raises(ValueError):
        QuantizationManager(
            tiny_config(quantization_method="int8", quantization_bits=3)
        )
    m = QuantizationManager(tiny_config())
    assert not m.enabled
    m = QuantizationManager(
        tiny_config(quantization_method="int4", quantization_bits=8)
    )
    assert m.bits == 4  # method/bits kept consistent


def test_quantized_model_forward_close_and_generates():
    cfg = tiny_config(quantization_method="int8")
    model = LuminaTransformer(cfg)
    ids = jnp.asarray(
        np.random.RandomState(0).randint(1, 256, (2, 32)), jnp.int32
    )
    params = jax.jit(model.init)(jax.random.key(0), ids)["params"]
    logits, _ = jax.jit(model.apply, static_argnames="deterministic")(
        {"params": params}, ids, deterministic=True
    )

    manager = QuantizationManager(cfg)
    qparams = manager.quantize_for_inference(params)
    assert manager.is_quantized
    assert manager.quantization_info["compression"] > 1.5
    deq = manager.materialize(qparams, jnp.float32)
    qlogits, _ = jax.jit(model.apply, static_argnames="deterministic")(
        {"params": deq}, ids, deterministic=True
    )
    # int8 weight-only: logits shift a little; argmax should mostly agree.
    agree = float(
        (jnp.argmax(logits, -1) == jnp.argmax(qlogits, -1)).mean()
    )
    assert agree > 0.9, agree

    from luminaai_tpu.data.tokenizer import ConversationTokenizer
    from luminaai_tpu.inference.generate import GenerationEngine

    tok = ConversationTokenizer(model_name="byte")
    # The engine wires quantization itself from config.quantization_method.
    engine = GenerationEngine(model, params, tok, config=cfg)
    assert engine.quantization_info.get("quantized_leaves", 0) > 0
    out_ids, stats = engine.generate(
        [1, 2, 3], max_new_tokens=5, temperature=0.0, seed=0
    )
    assert len(out_ids) >= 1
    assert all(0 <= t < cfg.vocab_size for t in out_ids)


# ---------------------------------------------------------------------------
# int8 COMPUTE path (W8A8, ops/quantized.py) — ref trainer.py:658 kernel swap
# ---------------------------------------------------------------------------
def _relerr(a, b):
    return float(jnp.abs(a - b).max() / (jnp.abs(b).max() + 1e-9))


def test_int8_project_matches_dequant_matmul():
    from luminaai_tpu.ops.quantized import int8_project

    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(4, 7, 64), jnp.float32)
    # 2D weight [K, N]
    w2 = jnp.asarray(rng.randn(64, 96), jnp.float32) * 0.02
    qt2 = quantize_array(w2, bits=8, axis=(0,))
    y = int8_project(x, qt2, jnp.float32)
    ref = x @ qt2.dequantize(jnp.float32)
    assert y.shape == (4, 7, 96)
    assert _relerr(y, ref) < 0.02, _relerr(y, ref)
    # 3D weight [K, h, d] (attention projection shape)
    w3 = jnp.asarray(rng.randn(64, 4, 16), jnp.float32) * 0.02
    qt3 = quantize_array(w3, bits=8, axis=(0,))
    y3 = int8_project(x, qt3, jnp.float32)
    ref3 = jnp.einsum("bsk,khd->bshd", x, qt3.dequantize(jnp.float32))
    assert y3.shape == (4, 7, 4, 16)
    assert _relerr(y3, ref3) < 0.02


def test_int8_attend_and_out_proj_match():
    from luminaai_tpu.ops.quantized import int8_attend, int8_out_proj

    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(2, 5, 64), jnp.float32)
    emb = jnp.asarray(rng.randn(256, 64), jnp.float32) * 0.02
    qe = quantize_array(emb, bits=8, axis=(-1,))
    y = int8_attend(x, qe, jnp.float32)
    ref = jnp.einsum("bsk,vk->bsv", x, qe.dequantize(jnp.float32))
    assert y.shape == (2, 5, 256)
    assert _relerr(y, ref) < 0.02

    out = jnp.asarray(rng.randn(2, 5, 4, 16), jnp.float32)
    wo = jnp.asarray(rng.randn(4, 16, 64), jnp.float32) * 0.02
    qo = quantize_array(wo, bits=8, axis=(0, 1))
    y2 = int8_out_proj(out, qo, jnp.float32)
    ref2 = jnp.einsum("bshk,hkd->bsd", out, qo.dequantize(jnp.float32))
    assert _relerr(y2, ref2) < 0.02


def test_int8_expert_matches():
    from luminaai_tpu.ops.quantized import int8_expert

    rng = np.random.RandomState(2)
    x = jnp.asarray(rng.randn(8, 2, 16, 64), jnp.float32)
    w = jnp.asarray(rng.randn(8, 64, 32), jnp.float32) * 0.02
    qt = quantize_array(w, bits=8, axis=(1,))
    y = int8_expert(x, qt, jnp.float32)
    ref = jnp.einsum("egch,ehf->egcf", x, qt.dequantize(jnp.float32))
    assert y.shape == (8, 2, 16, 32)
    assert _relerr(y, ref) < 0.02


def test_int8_embed_rows_match():
    from luminaai_tpu.ops.quantized import embed_rows

    rng = np.random.RandomState(3)
    emb = jnp.asarray(rng.randn(128, 64), jnp.float32) * 0.02
    qe = quantize_array(emb, bits=8, axis=(-1,))
    toks = jnp.asarray(rng.randint(0, 128, (2, 9)), jnp.int32)
    rows = embed_rows(qe, toks, jnp.float32)
    ref = jnp.take(qe.dequantize(jnp.float32), toks, axis=0)
    np.testing.assert_allclose(np.asarray(rows), np.asarray(ref), atol=1e-5)


def test_quantize_for_serving_axes_and_roles():
    from luminaai_tpu.training.quantization import quantize_for_serving

    cfg = tiny_config(use_moe=True, num_experts=4, moe_top_k=2)
    model = LuminaTransformer(cfg)
    ids = jnp.ones((1, 32), jnp.int32)
    params = jax.jit(model.init)(jax.random.key(0), ids)["params"]
    qp, info = quantize_for_serving(params, min_size=1024)
    assert info["quantized_leaves"] > 0
    flat = jax.tree_util.tree_flatten_with_path(
        qp, is_leaf=lambda x: isinstance(x, QuantizedTensor)
    )[0]
    for path, leaf in flat:
        keys = tuple(
            p.key for p in path if isinstance(p, jax.tree_util.DictKey)
        )
        name = keys[-1]
        if not isinstance(leaf, QuantizedTensor):
            assert name in ("scale", "bias", "router") or leaf.size < 1024, keys
            continue
        # Scale must be reduced over the CONTRACTION axes of each role.
        if name == "embedding":
            assert leaf.scale.shape == (leaf.orig_shape[0], 1)
        elif name in ("wq", "wk", "wv"):
            assert leaf.scale.shape == (1,) + leaf.orig_shape[1:]
        elif name == "wi":  # moe [E, H, 2F]
            assert leaf.scale.shape == (
                leaf.orig_shape[0], 1, leaf.orig_shape[2]
            )
        elif name == "wo":
            if any("moe" in k for k in keys):
                assert leaf.scale.shape == (
                    leaf.orig_shape[0], 1, leaf.orig_shape[2]
                )
            else:  # attention [heads, d, H]
                assert leaf.scale.shape == (1, 1, leaf.orig_shape[2])


def test_quantize_for_serving_idempotent():
    """Re-quantizing a tree that already holds QuantizedTensor leaves
    (chat/serve --quantize int8 pointed at an int8 serving export) must
    pass them through unchanged — not nest QT(q=QT(...)) and explode at
    trace time in int8_project (ADVICE r4 medium)."""
    from luminaai_tpu.training.quantization import quantize_for_serving

    cfg = tiny_config(use_moe=True, num_experts=4, moe_top_k=2,
                      routing_noise_std=0.0)
    model = LuminaTransformer(cfg)
    ids = jnp.asarray(
        np.random.RandomState(0).randint(1, 256, (2, 32)), jnp.int32
    )
    params = jax.jit(model.init)(jax.random.key(0), ids)["params"]
    qp1, info1 = quantize_for_serving(params, min_size=1024)
    qp2, info2 = quantize_for_serving(qp1, min_size=1024)
    assert info2["quantized_leaves"] == info1["quantized_leaves"]
    flat1 = jax.tree_util.tree_leaves(
        qp1, is_leaf=lambda x: isinstance(x, QuantizedTensor)
    )
    flat2 = jax.tree_util.tree_leaves(
        qp2, is_leaf=lambda x: isinstance(x, QuantizedTensor)
    )
    for a, b in zip(flat1, flat2):
        if isinstance(a, QuantizedTensor):
            assert b is a  # passed through, not re-quantized
            assert not isinstance(a.q, QuantizedTensor)
    # The re-quantized tree still traces and runs the int8 path.
    qlogits, _ = jax.jit(model.apply, static_argnames="deterministic")(
        {"params": qp2}, ids, deterministic=True
    )
    assert bool(jnp.isfinite(qlogits).all())
    # quantize_tree (storage path) is idempotent the same way.
    qt1, i1 = quantize_tree(params, bits=8, min_size=1024)
    qt2, i2 = quantize_tree(qt1, bits=8, min_size=1024)
    assert i2["quantized_leaves"] == i1["quantized_leaves"]
    # A DIFFERENT bit-width re-quantizes (round-trips through bf16)
    # instead of passing mismatched leaves through under the new label.
    qt4, i4 = quantize_tree(qt1, bits=4, min_size=1024)
    four_bit = [
        l for l in jax.tree_util.tree_leaves(
            qt4, is_leaf=lambda x: isinstance(x, QuantizedTensor)
        ) if isinstance(l, QuantizedTensor)
    ]
    assert four_bit and all(l.bits == 4 for l in four_bit)
    # Storage-layout trees fed to quantize_for_serving get re-quantized
    # into the serving (contraction-axis) layout, then trace fine.
    qs, _ = quantize_for_serving(qt1, min_size=1024)
    slogits, _ = jax.jit(model.apply, static_argnames="deterministic")(
        {"params": qs}, ids, deterministic=True
    )
    assert bool(jnp.isfinite(slogits).all())


def test_quantized_axis_always_tuple():
    """QuantizedTensor.axis is canonically a tuple for every entry path
    (int axis, negative axis, tuple, int4), so consumers never branch on
    int-vs-tuple (ADVICE r4)."""
    w = jnp.asarray(np.random.RandomState(0).randn(16, 64), jnp.float32)
    assert quantize_array(w, bits=8, axis=-1).axis == (1,)
    assert quantize_array(w, bits=8, axis=0).axis == (0,)
    assert quantize_array(w, bits=8, axis=(0, 1)).axis == (0, 1)
    assert quantize_array(w, bits=4, axis=-1).axis == (1,)
    # int4 dequantize still un-packs correctly through the tuple axis.
    qt = quantize_array(w, bits=4, axis=0)
    assert qt.dequantize(jnp.float32).shape == w.shape


def test_int8_layout_mismatch_raises_valueerror():
    """Layout contract violations raise ValueError (asserts are stripped
    under python -O and would silently produce wrong logits)."""
    from luminaai_tpu.ops.quantized import int8_project

    w = jnp.asarray(np.random.RandomState(0).randn(64, 32), jnp.float32)
    qt_wrong = quantize_array(w, bits=8, axis=-1)  # kernel wants axis 0
    x = jnp.ones((2, 64), jnp.float32)
    with pytest.raises(ValueError, match="quantized over axes"):
        int8_project(x, qt_wrong, jnp.float32)


@pytest.mark.parametrize("use_moe", [False, True])
def test_int8_compute_model_forward_close(use_moe):
    """End-to-end quality delta: the model applied with QuantizedTensor
    leaves (real int8 dots at every quantization-aware call site) stays
    close to the fp32 forward — and actually runs the int8 path (pinned
    by the serving-layout scale shapes above)."""
    from luminaai_tpu.training.quantization import quantize_for_serving

    cfg = tiny_config(
        use_moe=use_moe, num_experts=4, moe_top_k=2,
        routing_noise_std=0.0,
    )
    model = LuminaTransformer(cfg)
    ids = jnp.asarray(
        np.random.RandomState(0).randint(1, 256, (2, 32)), jnp.int32
    )
    params = jax.jit(model.init)(jax.random.key(0), ids)["params"]
    logits, _ = jax.jit(model.apply, static_argnames="deterministic")(
        {"params": params}, ids, deterministic=True
    )
    qp, _ = quantize_for_serving(params, min_size=1024)
    qlogits, _ = jax.jit(model.apply, static_argnames="deterministic")(
        {"params": qp}, ids, deterministic=True
    )
    assert qlogits.shape == logits.shape
    agree = float(
        (jnp.argmax(logits, -1) == jnp.argmax(qlogits, -1)).mean()
    )
    assert agree > 0.9, agree


def test_int8_scan_layers_falls_back_to_storage_path():
    """Scanned checkpoints stack layer params on a leading L axis; the
    int8 compute layout's static contraction axes can't survive nn.scan
    slicing, so serving must fall back to the layout-agnostic
    storage-only quantization — and still generate."""
    cfg = tiny_config(quantization_method="int8", scan_layers=True)
    model = LuminaTransformer(cfg)
    ids = jnp.ones((1, 8), jnp.int32)
    from flax.linen import meta

    params = meta.unbox(model.init(jax.random.key(0), ids)["params"])

    from luminaai_tpu.data.tokenizer import ConversationTokenizer
    from luminaai_tpu.inference.generate import GenerationEngine

    tok = ConversationTokenizer(model_name="byte")
    engine = GenerationEngine(model, params, tok, config=cfg)
    assert engine.quantization_info.get("mode") != "int8_compute"
    assert not any(
        isinstance(l, QuantizedTensor)
        for l in jax.tree_util.tree_leaves(
            engine.params,
            is_leaf=lambda x: isinstance(x, QuantizedTensor),
        )
    )
    out_ids, _ = engine.generate(
        [1, 2, 3], max_new_tokens=4, temperature=0.0, seed=0
    )
    assert len(out_ids) >= 1
