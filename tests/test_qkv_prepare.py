"""`ops/kda.py::qkv_prepare` (interpreted) against the plain form it stands
for, `models/kda.py::qkv_plain`: the convolution's first rows and the rows
across a block of the grid, the q, k and v thirds, a T that is no whole
block; its gradients for the projection's output and for the taps; under
the remat policy the cells train with; and which of the two forms the
mixer takes for a head size."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from luminaai_tpu.config import Config
from luminaai_tpu.models.kda import KimiDeltaAttention, qkv_plain
from luminaai_tpu.models.transformer import REMAT_POLICIES
from luminaai_tpu.ops import flash_attention as fa
from luminaai_tpu.ops import kda as kda_ops
from tests.test_kimi_linear import _eqns

B, T, HEADS, D = 2, 150, 4, 16   # three blocks of 64 rows, the last ragged
F32 = jnp.float32


@pytest.fixture(autouse=True)
def _small_blocks(monkeypatch):
    """64 rows x 2 heads a grid step: T crosses two block boundaries and
    every third is two channel blocks."""
    monkeypatch.setattr(kda_ops, "_PREP_ROWS", 64)
    monkeypatch.setattr(kda_ops, "_PREP_LANES", 2 * D)
    monkeypatch.setattr(kda_ops, "_PREP_STRIP", 16)


def _inputs(dtype, seed=0, t=T):
    kx, kw = jax.random.split(jax.random.key(seed))
    x = (1.5 * jax.random.normal(kx, (B, t, 3 * HEADS * D))).astype(dtype)
    return x, 0.5 * jax.random.normal(kw, (4, 3 * HEADS * D))


def _kernel(x, w):
    return kda_ops.qkv_prepare(x, w, heads=HEADS, head_dim=D)


def _plain(x, w):
    return qkv_plain(x, w, D)


def _ulps(a, b):
    """Distance in bf16 steps (no value here is near a sign change)."""
    bits = lambda t: jax.lax.bitcast_convert_type(  # noqa: E731
        t, jnp.int16).astype(jnp.int32)
    return np.asarray(jnp.abs(bits(a) - bits(b)))


@functools.lru_cache(maxsize=None)
def _forward_pair():
    x, w = _inputs(jnp.bfloat16)
    return jax.jit(_kernel)(x, w), jax.jit(_plain)(x, w)


@pytest.mark.parametrize("third", [0, 1, 2], ids=["q", "k", "v"])
@pytest.mark.parametrize("rows", [
    slice(0, 3), slice(60, 70), slice(124, 132), slice(140, T), slice(0, T)],
    ids=["first_three_rows", "first_block_boundary", "second_block_boundary",
         "ragged_tail", "every_row"])
def test_forward_is_the_plain_form(rows, third):
    """One rounding to bf16 at each output, where the plain form rounds:
    v (no norm) is the same bits; q and k may differ by the order of the
    norm's sum, one step of bf16 at most."""
    got, want = (pair[third][:, rows] for pair in _forward_pair())
    assert got.dtype == jnp.bfloat16 and got.shape == want.shape
    assert float(jnp.abs(want.astype(F32)).max()) > 0.0
    steps = _ulps(got, want)
    assert steps.max() <= (0 if third == 2 else 1)
    assert (steps > 0).mean() < 0.01


def test_rows_before_the_sequence_are_zeros_not_the_other_sequence():
    """Sequence 1's first rows must not see sequence 0's last (the tile in
    front of a block is masked where the block is the sequence's first)."""
    x, w = _inputs(jnp.bfloat16)
    both = jax.jit(_kernel)(x, w)
    alone = jax.jit(_kernel)(x[1:], w)
    for a, b in zip(both, alone):
        assert bool((a[1:] == b).all())


def _scalar(fn, cts):
    def loss(x, w):
        return sum((y.astype(F32) * c).sum() for y, c in zip(fn(x, w), cts))
    return loss


@functools.lru_cache(maxsize=None)
def _grad_pair(dtype):
    x, w = _inputs(dtype, seed=1)
    cts = tuple(jax.random.normal(k, (B, T, HEADS * D))
                for k in jax.random.split(jax.random.key(2), 3))
    grad = lambda fn: jax.jit(  # noqa: E731
        jax.grad(_scalar(fn, cts), argnums=(0, 1)))(x, w)
    return grad(_kernel), grad(_plain)


@pytest.mark.parametrize("of", [0, 1], ids=["x", "taps"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_gradients_are_the_plain_forms(dtype, of):
    """x's cotangent comes back in x's dtype (the plain form rounds it at
    the same place, the transpose of the widening); the taps' is a float32
    sum over every row of every sequence, compared relatively."""
    (got, want) = (pair[of] for pair in _grad_pair(dtype))
    assert got.shape == want.shape
    assert got.dtype == want.dtype == (F32 if of else jnp.dtype(dtype))
    got, want = got.astype(F32), want.astype(F32)
    assert np.isfinite(np.asarray(got)).all()
    rel = 1e-2 if (dtype == "bfloat16" and not of) else 2e-5
    assert float(jnp.abs(got - want).max()) <= rel * float(
        jnp.abs(want).max())
    assert float(jnp.sqrt(jnp.mean((got - want) ** 2))) <= 0.3 * rel * float(
        jnp.sqrt(jnp.mean(want ** 2)))


def _kernel_names(fn, *args):
    return [e.params["name"] for e in _eqns(jax.make_jaxpr(fn)(*args).jaxpr)
            if e.primitive.name == "pallas_call"]


@pytest.mark.parametrize("policy", ["save_attn", "nothing_saveable"])
def test_under_remat_the_forward_kernel_runs_again(policy):
    """Nothing of the preparation carries a name a policy keeps: the
    residual is x and the taps, the block's backward runs the forward
    kernel again and then the backward once, with the same gradients."""
    x, w = _inputs(F32, seed=3)
    cts = tuple(jax.random.normal(k, (B, T, HEADS * D))
                for k in jax.random.split(jax.random.key(4), 3))

    def loss(x, w):  # what reads q, k and v needs them in its backward
        return sum((jnp.square(y.astype(F32)) * c).sum()
                   for y, c in zip(_kernel(x, w), cts))

    remat = jax.checkpoint(loss, policy=REMAT_POLICIES[policy])
    assert _kernel_names(jax.grad(remat, argnums=(0, 1)), x, w) == [
        "qkv_prepare_fwd", "qkv_prepare_fwd", "qkv_prepare_bwd"]
    got = jax.jit(jax.grad(remat, argnums=(0, 1)))(x, w)
    want = jax.jit(jax.grad(loss, argnums=(0, 1)))(x, w)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("on_chip,head_dim,kernels", [
    (False, 16, True), (True, 16, False), (True, 128, True)],
    ids=["interpreted_any_size", "chip_head_of_16", "chip_head_of_128"])
def test_the_mixer_takes_the_form_its_head_size_allows(
        monkeypatch, on_chip, head_dim, kernels):
    """`kda_eligible` decides, for the preparation as for the recurrence:
    on the chip a head of a multiple of 128 lanes, interpreted any size.
    init's one-row dummy takes the plain form whatever the size."""
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
    assert ("qkv_prepare_fwd" in names) is kernels
    assert ("kda_fwd" in names) is kernels


def test_a_trace_for_the_chip_is_not_served_to_the_next_caller(monkeypatch):
    """The two calls are jitted and jit's cache outlives a patched
    `_interpret()`: the flag is one of the calls' static arguments, so a
    described-chip trace (tests/test_chip_compile.py, benchmark/rehearse.py)
    at some shape leaves the interpreted call at that shape its own."""
    kx, kw = jax.random.split(jax.random.key(6))
    x = jax.random.normal(kx, (1, 64, 3 * 128))
    w = 0.5 * jax.random.normal(kw, (4, 3 * 128))

    def caller():  # each its own function: no trace of the caller is shared
        return lambda x, w: kda_ops.qkv_prepare(x, w, heads=1, head_dim=128)

    monkeypatch.setattr(fa, "_interpret", lambda: False)
    assert _kernel_names(caller(), x, w) == ["qkv_prepare_fwd"]
    monkeypatch.setattr(fa, "_interpret", lambda: True)
    for got, want in zip(jax.jit(caller())(x, w), qkv_plain(x, w, 128)):
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("t", [128, 150], ids=["whole_chunks", "ragged_tail"])
def test_kda_flat_is_kda_in_the_layout_the_kernels_read(t):
    """`kda` reshapes into `kda_flat` and back: the same output and the
    same gradients from [B, T, H*d] arrays as from their [B, T, H, d]
    views, beta folded by `repeat` where it was broadcast."""
    kq, kk, kv, kg, kb, kc = jax.random.split(jax.random.key(5), 6)
    shape = (B, t, HEADS, D)
    q = jax.random.normal(kq, shape) * D ** -0.5
    k = jax.random.normal(kk, shape) * D ** -0.5
    v = jax.random.normal(kv, shape)
    g = -jax.nn.softplus(jax.random.normal(kg, shape))
    beta = jax.nn.sigmoid(jax.random.normal(kb, (B, t, HEADS)))
    ct = jax.random.normal(kc, shape)
    flat = lambda x: x.reshape(B, t, -1)  # noqa: E731

    def by_heads(*args):
        return kda_ops.kda(*args).reshape(B, t, -1)

    def as_flat(q, k, v, g, beta):
        return kda_ops.kda_flat(flat(q), flat(k), flat(v), flat(g), beta)

    def out_and_grads(fn):
        o, vjp = jax.vjp(fn, q, k, v, g, beta)
        return (o, *vjp(flat(ct)))

    got, want = jax.jit(out_and_grads, static_argnums=0)(as_flat), jax.jit(
        out_and_grads, static_argnums=0)(by_heads)
    assert got[0].shape == (B, t, HEADS * D)
    for a, b in zip(got, want):
        assert a.shape == b.shape and float(jnp.abs(b).max()) > 0.0
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
