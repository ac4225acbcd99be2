"""The hybrid linear-attention model's parts, each against its definition:
the chunked delta-rule kernels (interpreted) against the token-by-token
recurrence, flash attention with values narrower than scores against XLA
attention, the two mixers and the sigmoid-routed expert layer against the
benchmark's plain reference, the routing rule's defaults against the rule
as it was, and the fences around what does not compose yet."""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from luminaai_tpu.config import Config
from luminaai_tpu.models import moe
from luminaai_tpu.ops import kda as kda_ops

from benchmark.architectures.kimi_linear import reference

HI = jax.lax.Precision.HIGHEST


def _delta_inputs(B, T, H, dk, dv, gate, seed=0):
    ks = jax.random.split(jax.random.key(seed), 5)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(ks[0], (B, T, H, dk))) * dk ** -0.5
    k = unit(jax.random.normal(ks[1], (B, T, H, dk)))
    v = jax.random.normal(ks[2], (B, T, H, dv))
    g = -gate * jax.nn.softplus(jax.random.normal(ks[3], (B, T, H, dk)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (B, T, H)))
    return q, k, v, g, beta


# T: below one chunk, one chunk, straddling (a tail shorter than a
# sub-chunk; a tail longer); gate: weak, as initialised, and strong enough
# that e^{-G} over a whole chunk (64 tokens x ~2.4 = ~155) leaves float32
# while over a 16-token sub-chunk (under 88) it does not.
# The last case is the benchmark cell's head size: two whole chunks.
@pytest.mark.parametrize("T,gate,dk,dv", [
    (40, 1.0, 32, 16), (64, 1.0, 32, 16), (133, 0.05, 32, 16),
    (150, 1.0, 32, 16), (96, 3.0, 32, 16), (128, 1.0, 128, 128)])
def test_kda_kernels_match_the_recurrence(T, gate, dk, dv):
    args = _delta_inputs(2, T, 2, dk, dv, gate)
    _hold_to_recurrence(args, *_kda_and_grads(args))
    if gate == 3.0:
        assert -88.0 < float(kda_ops.chunk_decay_min(args[3])) < -45.0
        assert float(args[3][:, :64].sum(axis=1).min()) < -120.0


def _weights(args):
    v = args[2]
    return jax.random.normal(jax.random.key(9), v.shape)


def _kda_and_grads(args):
    """(o from the forward alone, the gradients in all five arguments)
    through the kernels: the forward that keeps no states, then the one
    that does and the backward."""
    w = _weights(args)
    loss = lambda *a: (kda_ops.kda(*a).astype(jnp.float32) * w).sum()  # noqa: E731
    return kda_ops.kda(*args), jax.grad(loss, argnums=(0, 1, 2, 3, 4))(*args)


def _hold_to_recurrence(args, got, grads):
    """float32 arguments: the kernels' output and gradients against the
    token-by-token recurrence's."""
    w = _weights(args)
    want = kda_ops.kda_recurrent(*args)
    assert np.isfinite(np.asarray(got)).all()
    assert float(jnp.abs(got - want).max()) <= 2e-5 * float(
        jnp.abs(want).max())
    wants = jax.grad(lambda *a: (kda_ops.kda_recurrent(*a) * w).sum(),
                     argnums=(0, 1, 2, 3, 4))(*args)
    for name, a, b in zip(("q", "k", "v", "g", "beta"), grads, wants):
        assert np.isfinite(np.asarray(a)).all(), name
        assert float(jnp.abs(a - b).max()) <= 1e-4 * float(
            jnp.abs(b).max()) + 1e-7, name


def _inverse_by_products(A):
    """(I + A)^{-1} as ops/kda.py multiplies it out, left to autodiff."""
    C = A.shape[0]
    M, T = -A, jnp.eye(C, dtype=A.dtype) - A
    for _ in range((C - 1).bit_length() - 1):
        M = jnp.matmul(M, M, precision=HI)
        T = T + jnp.matmul(T, M, precision=HI)
    return T


# A as the kernel forms it, beta_i (k_i * e^{G_i - G_j}) . k_j below the
# diagonal, from unit keys of 8 channels (so |A_ij| reaches ~1): with no
# gate at all (the largest entries the delta rule can make), at C = 16,
# and under a strong gate (entries that fall off within a few tokens).
@pytest.mark.parametrize("C,gate", [(64, 0.0), (16, 0.0), (64, 3.0)])
def test_inverse_adjoint_matches_autodiff_of_the_products(C, gate):
    _, k, _, g, beta = (x[0, :, 0] for x in _delta_inputs(1, C, 1, 8, 8, gate))
    G = jnp.cumsum(g, axis=0)
    A = jnp.einsum("ic,jc,ijc->ij", beta[:, None] * k, k,
                   jnp.exp(jnp.minimum(G[:, None] - G[None], 0.0)),
                   precision=HI)
    A = jnp.tril(A, -1)
    assert gate or 0.5 < float(jnp.abs(A).max()) <= 1.0
    dT = jax.random.normal(jax.random.key(C), (C, C))
    got_T, vjp = jax.vjp(kda_ops._unit_lower_inverse, A)
    want_T, want_vjp = jax.vjp(_inverse_by_products, A)
    assert (np.asarray(got_T) == np.asarray(want_T)).all()
    got, want = jnp.tril(vjp(dT)[0], -1), jnp.tril(want_vjp(dT)[0], -1)
    assert float(jnp.abs(got - want).max()) <= 1e-5 * float(
        jnp.abs(want).max())


def _eqns(jaxpr):
    for e in jaxpr.eqns:
        yield e
        for sub in jax.core.jaxprs_in_params(e.params):
            yield from _eqns(sub)


def _one_function_chunk(q, k, kb, vb, g, st, *, sub, mxu):
    """The chunk's math as ONE function, as ops/kda.py held it before the
    inverse was carried (PR 36's `_chunk_math`): A's rows and P's in the
    same products, T rebuilt in place. Returns (o, S^T leaving, T)."""
    C = g.shape[0]
    dot = lambda a, b, dims, prec=HI: jax.lax.dot_general(  # noqa: E731
        a, b, (dims, ((), ())), precision=prec,
        preferred_element_type=jnp.float32)
    nn, nt, tn = ((1,), (0,)), ((1,), (1,)), ((0,), (0,))
    lo_p = None if mxu == jnp.bfloat16 else HI
    row = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    tok = jax.lax.broadcasted_iota(jnp.int32, (C, 1), 0)
    G = dot((row >= col).astype(jnp.float32), g, nn)
    qf, kf, kbf = (x.astype(jnp.float32) for x in (q, k, kb))
    a_rows, p_rows = [], []
    for a in range(C // sub):
        lo, hi = a * sub, (a + 1) * sub
        Gn = G[lo:lo + 1]
        rf = jnp.exp(G[lo:hi] - Gn)
        lhs = jnp.concatenate([kbf[lo:hi] * rf, qf[lo:hi] * rf], axis=0)
        seen = tok < hi
        rhs = jnp.where(seen, kf * jnp.exp(jnp.where(seen, Gn - G, 0.0)), 0.0)
        s = dot(lhs, rhs, nt)
        a_rows.append(s[:sub])
        p_rows.append(s[sub:])
    A = jnp.where(row > col, jnp.concatenate(a_rows, axis=0), 0.0)
    P = jnp.where(row >= col, jnp.concatenate(p_rows, axis=0), 0.0)
    T = kda_ops._unit_lower_inverse(A)
    E = jnp.exp(G)
    W = dot(T, kbf * E, nn)
    st_lo = st.astype(mxu)
    U_lo = (dot(T, vb.astype(jnp.float32), nn)
            - dot(W.astype(mxu), st_lo, nt, lo_p)).astype(mxu)
    o = dot((qf * E).astype(mxu), st_lo, nt, lo_p) + dot(
        P.astype(mxu), U_lo, nn, lo_p)
    Gc = G[C - 1:]
    Kd = (kf * jnp.exp(Gc - G)).astype(mxu)
    return o, st * jnp.exp(Gc) + dot(U_lo, Kd, tn, lo_p), T


# One chunk, a tail that is padded, the cell's 128-wide heads over 16
# chunks in training's bf16, and a strong gate: carrying T re-orders
# nothing, so nothing may move by a bit.
@pytest.mark.parametrize("T,gate,d,dtype", [
    (64, 1.0, 32, jnp.float32), (200, 1.0, 32, jnp.float32),
    (1024, 1.0, 128, jnp.bfloat16), (96, 3.0, 32, jnp.float32)])
def test_carried_inverse_is_the_one_function_maths_bit_for_bit(T, gate, d, dtype):
    B, H, C = 1, 2, kda_ops.CHUNK
    q, k, v, g, beta = _delta_inputs(B, T, H, d, d, gate)
    q, k, v = (x.astype(dtype) for x in (q, k, v))
    got = kda_ops.kda(q, k, v, g, beta)

    b = beta[..., None]
    kb = (k.astype(jnp.float32) * b).astype(dtype)
    vb = (v.astype(jnp.float32) * b).astype(dtype)
    pad = ((0, 0), (0, -T % C), (0, 0), (0, 0))
    q, k, kb, vb, g = (jnp.pad(x, pad) for x in (q, k, kb, vb, g))
    nt = q.shape[1] // C
    flat = lambda x: x.reshape(B, nt * C, H * d)  # noqa: E731
    carried = kda_ops._tri_call(flat(k), flat(kb), flat(g), H=H, C=C)
    assert carried.shape == (B, H, nt, C // 2, 2 * C)
    assert carried.dtype == jnp.float32

    chunk = jax.jit(functools.partial(
        _one_function_chunk, sub=kda_ops.SUB, mxu=jnp.dtype(dtype)))
    for h in range(H):
        st = jnp.zeros((d, d), jnp.float32)
        for t in range(nt):
            o, st, inv = chunk(*(x[0, t * C:(t + 1) * C, h]
                                 for x in (q, k, kb, vb, g)), st)
            assert jnp.array_equal(
                kda_ops._unpack(carried[0, h, t]), inv), (h, t)
            rows = slice(t * C, min((t + 1) * C, T))
            assert jnp.array_equal(
                got[0, rows, h], o.astype(dtype)[:rows.stop - rows.start]
            ), (h, t)


def _chunk_fns():
    math = functools.partial(
        kda_ops._chunk_math, sub=kda_ops.SUB, mxu=jnp.bfloat16)

    def tri(q, k, kb, vb, g, st, inv, do, dst):
        return kda_ops._tri_math(k, kb, g, sub=kda_ops.SUB)

    def fwd(q, k, kb, vb, g, st, inv, do, dst):
        return math(q, k, kb, vb, g, st, inv)

    def bwd(q, k, kb, vb, g, st, inv, do, dst):
        _, vjp = jax.vjp(functools.partial(
            math, T=inv, through_inverse=True), q, k, kb, vb, g, st)
        return vjp((do, dst))

    return {"kda_tri": tri, "kda_fwd": fwd, "kda_bwd": bwd}


@pytest.mark.parametrize("kernel,want", [
    ("kda_tri", 10), ("kda_fwd", 0), ("kda_bwd", 2)])
def test_chunk_backward_runs_twelve_square_products_not_thirty(kernel, want):
    """The three kernels' chunk functions at the cell's shapes: the ten
    [64,64] x [64,64] products that build the inverse run in `kda_tri`
    alone; the forward reads T and runs none; the backward's `jax.vjp`
    runs the two of the adjoint -T^T dT T^T against the T it read (twelve
    when it rebuilt T first; autodiff through the ten would add twenty)."""
    C, d = 64, 128
    lo = jax.ShapeDtypeStruct((C, d), jnp.bfloat16)
    f32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.float32)
    jaxpr = jax.make_jaxpr(_chunk_fns()[kernel])(
        lo, lo, lo, lo, f32((C, d)), f32((d, d)), f32((C, C)),
        f32((C, d)), f32((d, d)))
    square = [e for e in _eqns(jaxpr.jaxpr) if e.primitive.name == "dot_general"
              and all(v.aval.shape == (C, C) for v in e.invars)]
    assert len(square) == want
    assert all(e.params["precision"] == (HI, HI) for e in square)


# Engagement: T kept across the remat boundary means the block's backward
# re-runs `kda_fwd` (for the chunk states) and NOT `kda_tri`; a policy that
# keeps nothing re-runs both.
@pytest.mark.parametrize("policy,tri,fwd", [
    ("save_attn", 2, 4), ("nothing_saveable", 4, 4)])
def test_remat_keeps_the_inverse_and_reruns_the_forward_alone(policy, tri, fwd):
    from luminaai_tpu.models.transformer import LuminaTransformer

    cfg = _tiny(layer_mixers=("kda", "kda"), seq_length=128,
                gradient_checkpointing=True, remat_policy=policy)
    ids = jnp.asarray(
        np.random.RandomState(0).randint(1, 256, (2, 128)), jnp.int32)
    model = LuminaTransformer(cfg)
    params = jax.jit(model.init)(jax.random.key(0), ids)["params"]

    def loss(p):
        out, _ = model.apply({"params": p}, ids, deterministic=True)
        return out.astype(jnp.float32).sum()

    names = [e.params["name"] for e in _eqns(
        jax.make_jaxpr(jax.grad(loss))(params).jaxpr)
        if e.primitive.name == "pallas_call"]
    assert sorted(set(names)) == [
        "kda_bwd", "kda_fwd", "kda_tri", "mixer_gates_bwd", "mixer_gates_fwd",
        "mixer_out_bwd", "mixer_out_fwd", "qkv_prepare_bwd",
        "qkv_prepare_fwd"]
    assert names.count("kda_tri") == tri
    assert names.count("kda_fwd") == fwd
    assert names.count("kda_bwd") == 2
    # q, k and v are kept by neither policy: the block's backward makes
    # them again from the projection's output, then differentiates once.
    assert names.count("qkv_prepare_fwd") == 4
    assert names.count("qkv_prepare_bwd") == 2


# `kda_fwd` and `kda_bwd` take several chunks a grid step: 8 chunks
# (T = 512) are one step of the forward's 8 and two of the backward's 4;
# 6 chunks (T = 384) divide by neither, so both walk gcd = 2 a step. The
# one-chunk walk is the same calls with both constants at 1. The last
# case is training's dtype at the cell's head size.
@pytest.mark.parametrize("T,dk,dv,dtype", [
    (512, 32, 16, jnp.float32), (384, 32, 16, jnp.float32),
    (512, 128, 128, jnp.float32), (384, 128, 128, jnp.float32),
    (512, 128, 128, jnp.bfloat16)])
def test_several_chunks_a_grid_step_walk_as_one_chunk_does(
        monkeypatch, T, dk, dv, dtype):
    """The forward, and the states it keeps for the backward, bit for
    bit. The gradients are the same bits on the chip, where Mosaic lowers
    the body operation by operation (PERF.md section 6, PR 58: every
    output of both kernels at the cell's shapes, at 1, 2, 4, 8 and 16
    chunks a step). Here XLA:CPU compiles the interpreted kernel and
    contracts multiplies into adds by what it fuses, which differs between
    a program of one chunk and one of several: float32 sums may end one
    rounding apart, so the gradients are held to that and no looser."""
    nt = T // kda_ops.CHUNK
    assert math.gcd(nt, kda_ops._FWD_CHUNKS) > 1 < math.gcd(
        nt, kda_ops._BWD_CHUNKS)
    B, H, C = 1, 2, kda_ops.CHUNK
    q, k, v, g, beta = _delta_inputs(B, T, H, dk, dv, 1.0, seed=5)
    args = (q.astype(dtype), k.astype(dtype), v.astype(dtype), g, beta)

    qf, kf, vf, gf = (x.reshape(B, T, -1) for x in args[:4])
    kb, vb = kda_ops.fold_beta(kf, vf, beta)
    inv = kda_ops._tri_call(kf, kb, gf, H=H, C=C)

    def walk():
        return *_kda_and_grads(args), kda_ops._fwd_call(
            qf, kf, kb, vb, gf, inv, H=H, C=C, mxu=jnp.dtype(dtype),
            keep_states=True)

    got, grads, states = walk()
    monkeypatch.setattr(kda_ops, "_FWD_CHUNKS", 1)
    monkeypatch.setattr(kda_ops, "_BWD_CHUNKS", 1)
    one, one_grads, one_states = walk()

    assert jnp.array_equal(got, one)
    for a, b in zip(states, one_states):
        assert jnp.array_equal(a, b)
    for name, a, b in zip(("q", "k", "v", "g", "beta"), grads, one_grads):
        assert np.isfinite(np.asarray(a, np.float32)).all(), name
        rounding = float(jnp.finfo(jnp.float32 if name == "g" else dtype).eps)
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        assert float(jnp.abs(a - b).max()) <= 2 * rounding * float(
            jnp.abs(b).max()), name

    if dtype == jnp.float32:
        _hold_to_recurrence(args, got, grads)
    else:  # as test_kda_in_bf16_stays_near_the_recurrence
        want = kda_ops.kda_recurrent(*(a.astype(jnp.float32) for a in args))
        err = got.astype(jnp.float32) - want
        assert float(jnp.sqrt(jnp.mean(err ** 2)) / jnp.std(want)) < 0.02


def test_kda_recurrence_is_the_references_delta_rule():
    args = _delta_inputs(1, 50, 2, 16, 16, 1.0, seed=3)
    with jax.default_matmul_precision("highest"):
        want = reference.delta_rule(*args)
    assert float(jnp.abs(kda_ops.kda_recurrent(*args) - want).max()) < 1e-6


def test_kda_in_bf16_stays_near_the_recurrence():
    """Training's dtype: operands of the matmuls against S and U in bf16,
    everything the recurrence accumulates in float32."""
    args = _delta_inputs(1, 192, 2, 32, 32, 1.0, seed=1)
    lo = tuple(a.astype(jnp.bfloat16) for a in args[:3]) + args[3:]
    want = kda_ops.kda_recurrent(*(a.astype(jnp.float32) for a in lo))
    got = kda_ops.kda(*lo).astype(jnp.float32)
    rel = float(jnp.sqrt(jnp.mean((got - want) ** 2)) / jnp.std(want))
    assert rel < 0.02, rel


def test_flash_with_values_narrower_than_scores():
    from luminaai_tpu.ops.flash_attention import flash_attention

    B, S, H, D, Dv = 1, 256, 2, 192, 128
    ks = jax.random.split(jax.random.key(0), 4)
    q = jax.random.normal(ks[0], (B, S, H, D))
    k = jax.random.normal(ks[1], (B, S, H, D))
    v = jax.random.normal(ks[2], (B, S, H, Dv))
    w = jax.random.normal(ks[3], (B, S, H, Dv))

    def xla(q, k, v):
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HI) / D ** 0.5
        s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v,
                          precision=HI)

    def flash(q, k, v):
        return flash_attention(q, k, v, block_q=128, block_kv=128)

    assert flash(q, k, v).shape == (B, S, H, Dv)
    assert float(jnp.abs(flash(q, k, v) - xla(q, k, v)).max()) < 1e-5
    got = jax.grad(lambda *a: (flash(*a) * w).sum(), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: (xla(*a) * w).sum(), (0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        assert a.shape == b.shape
        assert float(jnp.abs(a - b).max()) < 2e-5


def _tiny(**over):
    kw = dict(
        vocab_size=256, hidden_size=64, num_layers=2, num_heads=4,
        num_kv_heads=4, seq_length=160, intermediate_size=128,
        precision="fp32", rms_norm_eps=1e-5, use_stable_embedding=False,
        tie_word_embeddings=False, kda_head_dim=16, kda_num_heads=4,
        kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, layer_mixers=("kda", "latent"),
    )
    kw.update(over)
    return Config(**kw)


def _x(shape=(2, 160, 64), seed=7):
    return jax.random.normal(jax.random.key(seed), shape)


def _apply(module, x):
    from luminaai_tpu.parallel.sharding import unbox

    params = unbox(jax.jit(module.init)(jax.random.key(1), x)["params"])
    return params, jax.jit(module.apply)({"params": params}, x)


def test_kimi_delta_attention_matches_the_reference():
    from luminaai_tpu.models.kda import KimiDeltaAttention

    cfg = _tiny()
    x = _x()
    params, (got, stats) = _apply(KimiDeltaAttention(cfg, jnp.float32), x)
    assert sorted(params) == sorted(
        ["wq", "wk", "wv", "conv_q", "conv_k", "conv_v", "w_a1", "w_a2",
         "w_g1", "w_g2", "w_beta", "A_log", "dt_bias", "o_norm", "wo"])
    # log U(1, 16) a head; the inverse softplus of dt in [1e-3, 1e-1]
    assert 0.0 <= float(params["A_log"].min()) and float(
        params["A_log"].max()) <= np.log(16.0)
    dt = jax.nn.softplus(params["dt_bias"])
    assert 0.999e-3 <= float(dt.min()) and float(dt.max()) <= 0.1001
    with jax.default_matmul_precision("highest"):
        want = reference._kda(x, params, cfg.rms_norm_eps)
    assert float(jnp.abs(got - want).max()) < 1e-5 * max(
        1.0, float(jnp.abs(want).max()))
    assert float(stats["kda_decay_min"]) < 0.0


@pytest.mark.parametrize("flash", [False, True], ids=["xla", "flash"])
def test_latent_attention_matches_the_reference(flash):
    from luminaai_tpu.models.layers import LatentAttention

    # 128 + 64 score dims so that the flash kernels take the call
    cfg = _tiny(seq_length=256, use_flash_attention=flash,
                qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
                flash_block_q=128, flash_block_kv=128)
    x = _x((1, 256, 64))
    params, (got, no_cache) = _apply(LatentAttention(cfg, jnp.float32), x)
    assert no_cache is None  # the training form keeps nothing
    view = dict(params, kv_norm=params["kv_norm"]["scale"])
    with jax.default_matmul_precision("highest"):
        want = reference._latent(x, view, cfg.rms_norm_eps)
    assert float(jnp.abs(got - want).max()) < 2e-5


@pytest.mark.parametrize("dispatch", ["gmm", "sort", "gather"])
def test_sigmoid_routed_layer_matches_the_reference(dispatch):
    """Sigmoid scores, a selection bias in the choice alone, the chosen
    scores renormalised and scaled, a shared expert, experts narrower
    than the dense FFN: every expert held (the uncut layer)."""
    E, k, F = 8, 2, 32
    cfg = _tiny(use_moe=True, num_experts=E, moe_top_k=k,
                moe_dispatch=dispatch, capacity_factor=float(E) / k,
                routing_noise_std=0.0, moe_score_func="sigmoid",
                moe_selection_bias=True, moe_routed_scale=2.446,
                moe_intermediate_size=F, num_shared_experts=1,
                layer_mixers=None)
    x = _x((2, 48, 64))
    layer = moe.MoELayer(cfg, dtype=jnp.float32)
    from luminaai_tpu.parallel.sharding import unbox

    params = unbox(layer.init(jax.random.key(1), x)["params"])
    params["selection_bias"] = 0.3 * jax.random.normal(jax.random.key(2), (E,))
    got, _ = layer.apply({"params": params}, x)
    assert params["wi"].shape == (E, 64, 2 * F)
    view = {"router": params["router"],
            "selection_bias": params["selection_bias"],
            "wi": params["wi"], "wo": params["wo"],
            "shared_wi": params["shared_expert"]["wi"],
            "shared_wo": params["shared_expert"]["wo"]}
    with jax.default_matmul_precision("highest"):
        want = reference.expert_layer(x, view, top_k=k, held_offset=0,
                                      scale=2.446)
        unbiased = reference.expert_layer(
            x, dict(view, selection_bias=jnp.zeros(E)), top_k=k,
            held_offset=0, scale=2.446)
    assert float(jnp.abs(got - want).max()) < 1e-5
    assert float(jnp.abs(want - unbiased).max()) > 1e-3  # the bias chose


def _routing_as_it_was(probs, top_k, capacity):
    """`_sort_routing`'s choice and weights before the rule became data
    (softmax scores, top-k, renormalised), written out again."""
    vals, choice = jax.lax.top_k(probs, top_k)
    return choice, vals / (vals.sum(-1, keepdims=True) + 1e-9)


def test_default_rule_is_bit_identical_to_the_rule_as_it_was():
    probs = jax.nn.softmax(
        3.0 * jax.random.normal(jax.random.key(4), (3, 64, 8)), axis=-1)
    k, C = 2, 64
    slot, gate, dropped, counts = moe._sort_routing(probs, k, C)
    again = moe._sort_routing(probs, k, C, select_bias=None,
                              renormalize=True, scale=1.0)
    for a, b in zip((slot, gate, dropped, counts), again):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    choice, weights = jax.vmap(
        lambda p: _routing_as_it_was(p, k, C))(probs)
    assert np.array_equal(np.asarray(slot // C), np.asarray(choice))
    assert np.array_equal(np.asarray(gate), np.asarray(weights))
    # and the layer's parameters are as they were: no bias, no shared expert
    cfg = _tiny(use_moe=True, num_experts=8, moe_top_k=2, layer_mixers=None)
    params = moe.MoELayer(cfg, dtype=jnp.float32).init(
        jax.random.key(0), _x((1, 16, 64)))["params"]
    assert sorted(params) == ["router", "wi", "wo"]


def test_held_rows_beyond_the_bound_are_counted_not_computed():
    """A row bound below the held experts' pairs: the rest is dropped and
    said. No expert has a capacity of its own on this path."""
    E, k = 8, 2
    cfg = _tiny(use_moe=True, num_experts=E, moe_top_k=k,
                experts_held=(0, 4), moe_dispatch="gmm",
                capacity_factor=0.25, routing_noise_std=0.0,
                layer_mixers=None)
    x = _x((2, 512, 64))
    layer = moe.MoELayer(cfg, dtype=jnp.float32)
    params = layer.init(jax.random.key(1), x)["params"]
    out, stats = layer.apply({"params": params}, x)
    assert np.isfinite(np.asarray(out)).all()
    assert float(stats["moe_routed_pairs"]) == 2 * 512 * k
    held, lost = (float(stats["moe_held_pairs"]),
                  float(stats["moe_held_pairs_dropped"]))
    # 0.25 * 2048 pairs * 4/8 = 256 rows are computed; about half of the
    # 2048 pairs chose a held expert
    assert 800 < held < 1250 and lost == held - 256
    assert float(stats["moe_drop_rate"]) == 0.0
    roomy = moe.MoELayer(
        _tiny(use_moe=True, num_experts=E, moe_top_k=k, experts_held=(0, 4),
              moe_dispatch="gmm", capacity_factor=2.5,
              routing_noise_std=0.0, layer_mixers=None), dtype=jnp.float32)
    _, stats = roomy.apply({"params": params}, x)
    assert float(stats["moe_held_pairs_dropped"]) == 0.0


def test_held_path_masks_the_kernels_uninitialised_tail(monkeypatch):
    """The megablox kernel leaves rows past sum(group_sizes) undefined in
    its output and in grad_lhs; the CPU stand-in masks them itself, so the
    held path is run over a grouped matmul that writes NaN there."""
    def nan_tail_gmm(lhs, rhs, group_sizes, preferred_element_type, **_):
        m = lhs.shape[0]
        bounds = jnp.cumsum(group_sizes)
        row_e = jnp.searchsorted(bounds, jnp.arange(m), side="right")
        kept = (jnp.arange(m) < bounds[-1])[:, None]

        def dense(l, r):
            out = jnp.zeros((m, r.shape[-1]), preferred_element_type)
            for e in range(r.shape[0]):
                out = out + (l * (row_e == e)[:, None]) @ r[e]
            return out

        @jax.custom_vjp
        def core(l, r):
            return jnp.where(kept, dense(l, r), jnp.nan)

        def bwd(res, ct):
            gl, gr = jax.vjp(dense, *res)[1](jnp.where(kept, ct, 0.0))
            return jnp.where(kept, gl, jnp.nan), gr

        core.defvjp(lambda l, r: (core(l, r), (l, r)), bwd)
        return core(lhs, rhs)

    E, k = 8, 2
    cfg = _tiny(use_moe=True, num_experts=E, moe_top_k=k,
                experts_held=(4, 4), moe_dispatch="gmm", capacity_factor=2.0,
                routing_noise_std=0.0, layer_mixers=None)
    x = _x((2, 96, 64))
    layer = moe.MoELayer(cfg, dtype=jnp.float32)
    params = layer.init(jax.random.key(1), x)["params"]

    def loss(p, xx):
        return jnp.sum(layer.apply({"params": p}, xx)[0] ** 2)

    want = jax.grad(loss, argnums=(0, 1))(params, x)
    monkeypatch.setattr(moe, "_GMM_OVERRIDE", nan_tail_gmm)
    got = jax.grad(loss, argnums=(0, 1))(params, x)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert np.isfinite(np.asarray(a)).all()
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


@pytest.mark.parametrize("over,word", [
    (dict(layer_mixers=("kda",)), "names 1 layers"),
    (dict(layer_mixers=("kda", "conv")), "invalid layer_mixers"),
    (dict(scan_layers=True), "one mixer kind"),
    (dict(attention_window=64), "attention_window"),
    (dict(tensor_parallel_size=2), "tensor_parallel_size"),
    (dict(use_moe=True, moe_score_func="sigmoid", moe_dispatch="einsum"),
     "sort, gather or gmm"),
    (dict(use_moe=True, experts_held=(0, 4), moe_dispatch="sort"),
     "moe_dispatch='gmm'"),
    (dict(use_moe=True, experts_held=(6, 4), moe_dispatch="gmm"),
     "outside num_experts"),
    (dict(use_moe=True, experts_held=(0, 4), moe_dispatch="gmm",
          expert_parallel_size=2), "compose with a mesh"),
], ids=["length", "kind", "scan", "window", "tensor", "rule_dispatch",
        "held_dispatch", "held_range", "held_mesh"])
def test_fences_around_what_does_not_compose(over, word):
    with pytest.raises(AssertionError, match=word):
        _tiny(**over)


def test_scan_layers_takes_a_stack_of_one_new_kind():
    cfg = _tiny(layer_mixers=("kda", "kda"), scan_layers=True)
    assert cfg.mixer_kind(1) == "kda" and cfg.recurrent_or_latent()
    assert not _tiny(layer_mixers=None).recurrent_or_latent()


def test_serving_refuses_the_new_mixers_by_name():
    from luminaai_tpu.inference.generate import (GenerationEngine,
                                                 UnservedMixerError)
    from luminaai_tpu.models.transformer import LuminaTransformer

    cfg = _tiny()
    model = LuminaTransformer(cfg)
    with pytest.raises(UnservedMixerError, match=r"\['kda'\]"):
        GenerationEngine(model, {}, tokenizer=None, config=cfg)
    # and the block itself refuses a cache, whoever calls it
    params = jax.jit(model.init)(
        jax.random.key(0), jnp.zeros((1, 160), jnp.int32))["params"]
    cache = [(jnp.zeros((1, 8, 4, 16)),) * 2] * 2
    with pytest.raises(NotImplementedError, match="no decode path"):
        model.apply({"params": params}, jnp.zeros((1, 1), jnp.int32),
                    kv_caches=cache, cache_index=jnp.int32(0))


def test_gate_parameters_and_selection_bias_are_not_decayed():
    from luminaai_tpu.training.optimizer import _decay_mask

    params = {"layer_0": {
        "kda": {"A_log": jnp.zeros(4), "dt_bias": jnp.zeros(64),
                "conv_q": jnp.zeros((4, 64)), "wq": jnp.zeros((64, 64)),
                "o_norm": jnp.zeros(16)},
        "moe": {"selection_bias": jnp.zeros(8), "router": jnp.zeros((64, 8)),
                # a stacked (scanned) bias has rank 2 and is still no weight
                "stacked": {"selection_bias": jnp.zeros((2, 8))}},
    }}
    mask = _decay_mask(params)["layer_0"]
    assert mask["kda"] == {"A_log": False, "dt_bias": False, "conv_q": True,
                           "wq": True, "o_norm": False}
    assert mask["moe"]["selection_bias"] is False and mask["moe"]["router"]
    assert mask["moe"]["stacked"]["selection_bias"] is False


def test_every_new_parameter_has_its_logical_axes():
    """parallel/sharding.py derives shardings from the annotations: a new
    parameter without one would fall to replicated in silence."""
    from flax import linen as nn

    from luminaai_tpu.models.transformer import LuminaTransformer
    from luminaai_tpu.parallel.sharding import LOGICAL_AXIS_RULES

    cfg = _tiny(use_moe=True, num_experts=8, moe_top_k=2, moe_pattern="all",
                experts_held=(0, 4), moe_dispatch="gmm",
                moe_score_func="sigmoid", moe_selection_bias=True,
                num_shared_experts=1, moe_intermediate_size=32)
    boxed = jax.eval_shape(
        LuminaTransformer(cfg).init, jax.random.key(0),
        jnp.zeros((1, 160), jnp.int32))["params"]
    known = {name for name, _ in LOGICAL_AXIS_RULES} | {None}
    leaves = jax.tree.leaves(
        boxed, is_leaf=lambda x: isinstance(x, nn.meta.AxisMetadata))
    assert len(leaves) > 35
    for leaf in leaves:
        assert isinstance(leaf, nn.LogicallyPartitioned), leaf
        assert len(leaf.names) == leaf.value.ndim
        assert set(leaf.names) <= known, leaf.names


def test_trainer_exports_the_counters_and_the_gauge():
    from luminaai_tpu.monitoring.telemetry import MetricsRegistry
    from luminaai_tpu.training.trainer import Trainer

    trainer = Trainer.__new__(Trainer)
    trainer.registry = MetricsRegistry()
    window = {"moe_routed_pairs": 1024.0, "moe_held_pairs": 32.0,
              "moe_held_pairs_dropped": 0.0, "kda_decay_min": -21.5}
    trainer._export_held_and_decay(window)
    trainer._export_held_and_decay(window)
    trainer._export_held_and_decay({"loss": 1.0})  # a dense model: nothing
    seen = {fam.name: fam.children()[0].value
            for fam in trainer.registry.families()}
    assert seen == {"moe_routed_pairs_total": 2048.0,
                    "moe_held_pairs_total": 64.0,
                    "moe_held_pairs_dropped_total": 0.0,
                    "kda_decay_min": -21.5}
