"""`ops/kda.py::gated_kda` and `::mixer_out` (interpreted) against the plain
forms they stand for, `models/kda.py::gates_plain` with `ops/kda.py::
fold_beta` and `models/kda.py::out_plain`: the same bits forward, one and
several heads a block, a T that is no whole block; the gradients of every
input and parameter against the plain forms' and a float32 reference; k's
cotangent written once; under the remat policy the cells train with; which
of the two forms the mixer takes for a head size."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from luminaai_tpu.config import Config
from luminaai_tpu.models.kda import (KimiDeltaAttention, gates_plain,
                                     out_plain)
from luminaai_tpu.models.transformer import REMAT_POLICIES
from luminaai_tpu.ops import flash_attention as fa
from luminaai_tpu.ops import kda as kda_ops
from tests.test_kimi_linear import _eqns

B, T, HEADS, D = 2, 150, 4, 16   # three blocks of 64 rows, the last ragged
WIDE = HEADS * D
EPS = 1e-5
F32 = jnp.float32
GATED = ("q", "k", "v", "a", "beta_logits", "dt_bias", "A_log")
OUT = ("o", "gate_logits", "o_norm")


def _blocks(monkeypatch, heads_a_block):
    """64 rows x `heads_a_block` heads a grid step: T crosses two block
    boundaries, the channels are four blocks or two."""
    monkeypatch.setattr(kda_ops, "_PREP_ROWS", 64)
    monkeypatch.setattr(kda_ops, "_PREP_LANES", heads_a_block * D)
    monkeypatch.setattr(kda_ops, "_PREP_STRIP", 16)


@pytest.fixture(params=[1, 2], ids=["one_head_a_block", "two_heads_a_block"])
def heads_a_block(request, monkeypatch):
    _blocks(monkeypatch, request.param)
    return request.param


@pytest.fixture
def two_heads_a_block(monkeypatch):
    _blocks(monkeypatch, 2)


def _gated_inputs(dtype, seed=0):
    ks = jax.random.split(jax.random.key(seed), 7)

    def normal(key, shape, scale=1.0):
        return (scale * jax.random.normal(key, shape)).astype(dtype)

    wide = (B, T, WIDE)
    return (normal(ks[0], wide, D ** -0.5), normal(ks[1], wide, D ** -0.5),
            normal(ks[2], wide), normal(ks[3], wide, 2.0),
            normal(ks[4], (B, T, HEADS), 2.0),
            jax.random.normal(ks[5], (WIDE,)),
            jnp.log(jax.random.uniform(ks[6], (HEADS,), F32, 0.05, 0.8)))


def _out_inputs(dtype, seed=0):
    ko, kz, kw = jax.random.split(jax.random.key(seed), 3)
    return ((jax.random.normal(ko, (B, T, WIDE))).astype(dtype),
            (2.0 * jax.random.normal(kz, (B, T, WIDE))).astype(dtype),
            1.0 + 0.2 * jax.random.normal(kw, (D,)))


def _gates_kernel(q, k, v, a, bl, dt_bias, a_log):
    """What `mixer_gates_fwd` writes, through the jitted call `gated_kda`
    makes: (g, beta * k, beta * v) over the rows padded to whole blocks."""
    blocks = kda_ops._prep_blocks(T, WIDE, D)
    k, v, a, bl = kda_ops._pad_rows([k, v, a, bl], blocks[0])
    return tuple(x[:, :T] for x in kda_ops._gates_fwd_call(
        a, k, v, bl, kda_ops._gates_params(dt_bias, a_log, D), d=D,
        blocks=blocks, interpret=True))


def _gates_plain(q, k, v, a, bl, dt_bias, a_log):
    g, beta = gates_plain(a, bl, dt_bias, a_log)
    return (g, *kda_ops.fold_beta(k, v, beta))


def _gated_kernel(*args):
    return kda_ops.gated_kda(*args)[0]


def _gated_plain(q, k, v, a, bl, dt_bias, a_log):
    g, beta = gates_plain(a, bl, dt_bias, a_log)
    return kda_ops.kda_flat(q, k, v, g, beta)


def _out_kernel(o, z, w):
    return kda_ops.mixer_out(o, z, w, eps=EPS)


def _out_plain(o, z, w):
    return out_plain(o, z, w, EPS)


@pytest.mark.parametrize("which", [0, 1, 2], ids=["g", "beta_k", "beta_v"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_gates_forward_is_the_plain_forms_bits(
        heads_a_block, dtype, which):
    """g float32 whatever the inputs, beta * k and beta * v rounded once
    where the plain form rounds; a head's column of the logits is picked
    by an exact sum, so every value is the same bits."""
    args = _gated_inputs(jnp.dtype(dtype))
    got = jax.jit(_gates_kernel)(*args)[which]
    want = jax.jit(_gates_plain)(*args)[which]
    assert got.shape == want.shape == (B, T, WIDE)
    assert got.dtype == want.dtype == (F32 if which == 0 else jnp.dtype(dtype))
    assert float(jnp.abs(want.astype(F32)).max()) > 0.0
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_gated_kda_is_the_plain_gates_in_front_of_kda_flat(
        heads_a_block, dtype):
    """The recurrence reads the same g, beta * k and beta * v, so its
    output is the same bits; the g that comes back for the gauge is the
    plain form's and carries no gradient."""
    args = _gated_inputs(jnp.dtype(dtype), seed=1)
    o, g = jax.jit(kda_ops.gated_kda)(*args)
    np.testing.assert_array_equal(
        np.asarray(o), np.asarray(jax.jit(_gated_plain)(*args)))
    np.testing.assert_array_equal(
        np.asarray(g), np.asarray(gates_plain(*args[3:])[0]))
    through_g = jax.grad(
        lambda a: kda_ops.gated_kda(*args[:3], a, *args[4:])[1].sum())
    assert not bool(jnp.any(through_g(args[3])))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_output_norm_and_gate_are_the_plain_forms_bits(
        heads_a_block, dtype):
    args = _out_inputs(jnp.dtype(dtype))
    got, want = jax.jit(_out_kernel)(*args), jax.jit(_out_plain)(*args)
    assert got.dtype == want.dtype == jnp.dtype(dtype)
    assert float(jnp.abs(want.astype(F32)).max()) > 0.0
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def _grads(fn, args, ct, wrt):
    def loss(*xs):
        return (fn(*xs).astype(F32) * ct).sum()
    return jax.jit(jax.grad(loss, argnums=wrt))(*args)


@functools.lru_cache(maxsize=None)
def _grad_triple(stage, dtype):
    """(the kernels', the plain form's, the plain form's in float32 from
    the same rounded inputs): gradients of every argument."""
    kernel, plain, inputs, names = {
        "gated": (_gated_kernel, _gated_plain, _gated_inputs, GATED),
        "out": (_out_kernel, _out_plain, _out_inputs, OUT)}[stage]
    args = inputs(jnp.dtype(dtype), seed=2)
    ct = jax.random.normal(jax.random.key(3), (B, T, WIDE))
    wrt = tuple(range(len(names)))
    exact = tuple(x.astype(F32) for x in args)
    return (_grads(kernel, args, ct, wrt), _grads(plain, args, ct, wrt),
            _grads(plain, exact, ct, wrt))


def _rms(x):
    return float(jnp.sqrt(jnp.mean(jnp.square(x.astype(F32)))))


@pytest.mark.parametrize("stage,of", [("gated", n) for n in GATED] + [
    ("out", n) for n in OUT])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_gradients_are_the_plain_forms(two_heads_a_block, stage, dtype, of):
    """Every cotangent comes back in its primal's dtype. The parameters'
    are float32 sums over every row, compared relatively; k's is the
    recurrence's own plus the fold's, added in float32 and rounded ONCE
    where the plain form adds two rounded arrays, so in bf16 it is held
    to a step of bf16 and to lying no further from the float32 gradient
    than the plain form's does."""
    n = (GATED if stage == "gated" else OUT).index(of)
    got, want, exact = (t[n] for t in _grad_triple(stage, dtype))
    param = of in ("dt_bias", "A_log", "o_norm")
    assert got.shape == want.shape
    assert got.dtype == want.dtype == (
        F32 if param else jnp.dtype(dtype))
    assert np.isfinite(np.asarray(got.astype(F32))).all()
    assert _rms(want) > 0.0
    if param:
        rel = 2e-5
    elif dtype == "bfloat16" and of == "k":
        rel = 1e-2
    else:
        rel = 0.0
    diff = got.astype(F32) - want.astype(F32)
    assert float(jnp.abs(diff).max()) <= rel * float(
        jnp.abs(want.astype(F32)).max())
    assert _rms(got.astype(F32) - exact) <= _rms(
        want.astype(F32) - exact) * (1 + 1e-3) + 1e-6 * _rms(exact)


def _kernel_names(fn, *args):
    return [e.params["name"] for e in _eqns(jax.make_jaxpr(fn)(*args).jaxpr)
            if e.primitive.name == "pallas_call"]


def test_ks_cotangent_is_written_once(two_heads_a_block):
    """ONE backward around the fold and the recurrence: `mixer_gates_bwd`
    reads the recurrence's dk and writes the whole one, so nothing adds
    two [B, T, D] cotangents outside the kernels."""
    args = _gated_inputs(jnp.bfloat16)
    grad = jax.grad(lambda *xs: _gated_kernel(*xs).astype(F32).sum(),
                    argnums=tuple(range(7)))
    eqns = list(_eqns(jax.make_jaxpr(grad)(*args).jaxpr))
    names = [e.params["name"] for e in eqns if e.primitive.name == "pallas_call"]
    assert names == ["mixer_gates_fwd", "kda_tri", "kda_fwd", "kda_bwd",
                     "mixer_gates_bwd"]
    assert not [e for e in eqns if e.primitive.name in ("add_any", "add")
                and e.outvars[0].aval.shape[-1:] == (WIDE,)
                and e.outvars[0].aval.ndim == 3]


@pytest.mark.parametrize("policy,tri", [
    ("save_attn", 1), ("nothing_saveable", 2)])
def test_under_remat_the_forward_kernels_run_again_and_the_backward_once(
        two_heads_a_block, policy, tri):
    """Nothing of either stage carries a name a policy keeps: the block's
    backward runs `mixer_gates_fwd` and `mixer_out_fwd` again (with
    `kda_fwd`, for the chunk states) and each backward kernel once, with
    the same gradients."""
    args = _gated_inputs(F32, seed=4)
    z, w = _out_inputs(F32, seed=5)[1:]
    ct = jax.random.normal(jax.random.key(6), (B, T, WIDE))

    def loss(*xs):  # what reads y needs it in its backward
        y = _out_kernel(_gated_kernel(*xs[:7]), *xs[7:])
        return (jnp.square(y) * ct).sum()

    wrt = tuple(range(9))
    remat = jax.checkpoint(loss, policy=REMAT_POLICIES[policy])
    names = _kernel_names(jax.grad(remat, argnums=wrt), *args, z, w)
    assert {n: names.count(n) for n in sorted(set(names))} == {
        "kda_bwd": 1, "kda_fwd": 2, "kda_tri": tri, "mixer_gates_bwd": 1,
        "mixer_gates_fwd": 2, "mixer_out_bwd": 1, "mixer_out_fwd": 2}
    got = jax.jit(jax.grad(remat, argnums=wrt))(*args, z, w)
    want = jax.jit(jax.grad(loss, argnums=wrt))(*args, z, w)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("on_chip,head_dim,kernels", [
    (False, 16, True), (True, 16, False), (True, 128, True)],
    ids=["interpreted_any_size", "chip_head_of_16", "chip_head_of_128"])
def test_the_mixer_takes_the_form_its_head_size_allows(
        monkeypatch, on_chip, head_dim, kernels):
    """`kda_eligible` decides for both stages as for the recurrence: on the
    chip a head of a multiple of 128 lanes, interpreted any size. init's
    one-row dummy takes the plain forms whatever the size."""
    monkeypatch.setattr(fa, "_interpret", lambda: not on_chip)
    cfg = Config(
        vocab_size=256, hidden_size=64, num_layers=1, num_heads=2,
        num_kv_heads=2, seq_length=32, intermediate_size=128,
        precision="fp32", use_stable_embedding=False,
        tie_word_embeddings=False, kda_head_dim=head_dim, kda_num_heads=2,
        layer_mixers=("kda",))
    mixer = KimiDeltaAttention(cfg, F32)
    x = jnp.zeros((1, 32, 64), F32)
    assert _kernel_names(
        lambda: mixer.init(jax.random.key(0), x[:, :1])) == []
    params = jax.eval_shape(lambda: mixer.init(jax.random.key(0), x[:, :1]))
    names = _kernel_names(lambda p: mixer.apply(p, x), params)
    assert names == (["qkv_prepare_fwd", "mixer_gates_fwd", "kda_tri",
                      "kda_fwd", "mixer_out_fwd"] if kernels else [])


@pytest.mark.parametrize("stage", ["gated", "out"])
def test_a_trace_for_the_chip_is_not_served_to_the_next_caller(
        monkeypatch, stage):
    """The four calls are jitted and jit's cache outlives a patched
    `_interpret()`: the flag is one of each call's static arguments, so a
    described-chip trace (tests/test_chip_compile.py, benchmark/rehearse.py)
    at some shape leaves the interpreted call at that shape its own."""
    ks = jax.random.split(jax.random.key(7), 7)
    wide = (1, 64, 128)
    if stage == "gated":
        args = tuple(jax.random.normal(k, wide) for k in ks[:4]) + (
            jax.random.normal(ks[4], (1, 64, 1)),
            jax.random.normal(ks[5], (128,)), jnp.zeros((1,)))
        kernel, plain = _gated_kernel, _gated_plain
        names = ["mixer_gates_fwd", "kda_tri", "kda_fwd"]
    else:
        args = (jax.random.normal(ks[0], wide), jax.random.normal(ks[1], wide),
                1.0 + 0.2 * jax.random.normal(ks[2], (128,)))
        kernel, plain = _out_kernel, _out_plain
        names = ["mixer_out_fwd"]

    def caller():  # each its own function: no trace of the caller is shared
        return lambda *xs: kernel(*xs)

    monkeypatch.setattr(fa, "_interpret", lambda: False)
    assert _kernel_names(caller(), *args) == names
    monkeypatch.setattr(fa, "_interpret", lambda: True)
    np.testing.assert_allclose(
        jax.jit(caller())(*args), plain(*args), rtol=1e-5, atol=1e-6)
