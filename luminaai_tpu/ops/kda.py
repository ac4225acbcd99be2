"""Pallas TPU kernels for the chunked gated delta rule (Kimi Delta Attention).

Per head, with a state S in R^{dk x dv} (S_0 = 0), a per-channel log decay
g_t <= 0 and a write strength beta_t in (0, 1):

    S'_t = Diag(exp(g_t)) S_{t-1}
    S_t  = S'_t + beta_t k_t (v_t - S'_t^T k_t)^T
    o_t  = S_t^T q_t

`kda_recurrent` below is that recurrence token by token (the oracle the
tests hold the kernels to, and the path for head sizes the kernel does not
tile). The kernels compute the same thing a chunk of C = 64 tokens at a
time. With G the running sum of g inside the chunk (inclusive) and S the
state entering it:

    A_ij = beta_i (k_i * e^{G_i - G_j}) . k_j        j <  i  (strictly lower)
    P_ij = (q_i * e^{G_i - G_j}) . k_j               j <= i
    T    = (I + A)^{-1}           A is nilpotent: ten products, no substitution
    W    = T (beta k * e^{G});   U = T (beta v) - W S
    O    = (q * e^{G}) S + P U
    S   <- Diag(e^{G_C}) S + (k * e^{G_C - G})^T U

e^{G_i - G_j} is never formed from e^{G_i} and e^{-G_j} over the whole
chunk: e^{-G_j} would leave float32 after a few dozen tokens of a strong
gate. The scores are built a 16-token sub-chunk of rows at a time against
that sub-chunk's first row n: (x_i * e^{G_i - G_n}) . (k_j * e^{G_n - G_j}).
The first exponent is <= 0 always, the second is <= 0 for every j before
the sub-chunk and inside it is at most 15 tokens of decay, so float32 holds
it down to a summed gate of about -88 over 16 tokens (the `kda_decay_min`
gauge of models/kda.py reports how near a run came). Nothing is clamped.

Gates, running sums, A, P, T and S are float32 (float32 matmuls at HIGHEST
precision); the matmuls against S and U take their operands in the
activations' dtype (bfloat16 in training) and accumulate in float32.

T depends on k, beta and g alone: not on the state, not on q. So it is built
once and carried, in float32, as it was computed. Three kernels, each a
grid over (batch, head, chunks): a grid step takes several chunks of one
head where that count divides T/C (`_TRI_CHUNKS` = 4, `_FWD_CHUNKS` = 8,
`_BWD_CHUNKS` = 4 at most; a T/C they do not divide takes their gcd with
it, one chunk at worst), laid out one behind another, so that one chunk's
products run in the gaps of its neighbour's; the arithmetic, its order and
every output's bits are those of one chunk a step. By MXU passes a chunk
(a float32 product at HIGHEST is six bf16 passes, and on the chip the
kernels' time follows the count):

  `kda_tri`  reads k, beta*k, g; forms G and A's rows and writes
             T = (I + A)^{-1}, [B, H, T/C, C/2, 2C] float32 (a chunk's
             [C, C] with the second half of its rows beside the first:
             whole 128-lane tiles, 16 KB a chunk). No scratch, no order: every
             grid axis is parallel. G 6 + scores 24 + the inverse's ten
             products 60 = 90 passes.
  `kda_fwd`  reads q, k, beta*k, beta*v, g and the chunk's T; chunks
             innermost and in order, S^T carried through the step's
             chunks and left in float32 VMEM scratch between steps. It forms
             G and P's rows, never A: 6 + 24 + W and T (beta v) 12 + the
             four matmuls against the state 4 = 46 passes (106 when it
             built T itself). Under differentiation it also writes each
             chunk's ENTERING state ([B, H, T/C, dv, dk] float32: 64 KB a
             chunk a head), which the backward reads instead of
             recomputing the scan.
  `kda_bwd`  the same walk from the last chunk to the first (the blocks
             of a step's chunks in reverse, and the chunks inside one)
             with dS carried likewise, reading the same T and the entering
             state; it differentiates each chunk's own forward math (`jax.vjp`
             of `_chunk_math`, traced into the kernel), so the two cannot
             drift apart. There the inverse is `_carried_inverse(A, T)`:
             its primal is the T that was read, and its cotangent reaches
             k, beta*k and g through A by the inverse's own adjoint: from
             T (I + A) = I, dT = -T dA T, so dA = -T^T dT T^T, two
             products, where autodiff through the ten would run twenty. 34
             matmuls, 2 of them float32 [64, 64] x [64, 64], 144 passes
             (204 when it rebuilt T first).

T carries the name "kda_inverse" (`checkpoint_name`): a remat policy that
keeps it (models/transformer.py `save_attn`) re-runs `kda_fwd` alone in the
block's backward, and a layer builds the inverse once a step: 90 + 2 x 46 +
144 = 326 passes a chunk where it ran 106 + 106 + 204 = 416.

Layout: q, k, g [B, T, H, dk]; v [B, T, H, dv]; beta [B, T, H]. The
kernels read [B, T, H*d] blocks of (1, C, d) directly, no head-major
transpose (`kda_flat` takes that layout as it is). They take beta folded
into k and v: `gated_kda`, the mixer's entry, folds it in the kernel that
also forms g (`mixer_gates_fwd`) and differentiates the fold and the
recurrence in ONE backward (`kda_bwd`, then `mixer_gates_bwd`); `kda_flat`,
from a g and a beta already formed, folds it in plain jax (`fold_beta`:
one elementwise pass in XLA, which also carries its gradient).

What stands around the recurrence in the mixer is here too, below it:
`qkv_prepare` (q, k and v from their projection's output), the front
stage of `gated_kda` and `mixer_out` (the output norm x gate): six more
kernels, `qkv_prepare_fwd` / `_bwd`, `mixer_gates_fwd` / `_bwd` and
`mixer_out_fwd` / `_bwd`, named apart from the three above because the
benchmark's `kda_ms_step` and `kda_roofline` read every op called `kda_*`
as the recurrence (and `qkv_prepare_ms_step` every `qkv_prepare_*`).

Interpreted off the TPU: it asks `flash_attention._interpret()` through
the module at call time, the one switch tests and benchmark/rehearse.py
patch to compile the real kernels for a described chip.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from luminaai_tpu.ops import flash_attention as _fa

CHUNK = 64
SUB = 16
# Chunks a grid step of `kda_tri`. On a v5e at 2 x 8192 tokens and 32 heads:
# 14.71 ms a call at 1, 13.76 at 2, 13.29 at 4, 13.01 at 8 (read then as a
# grid step's fixed cost, 8192 of them at 1; `kda_fwd`'s rolled loop, below,
# puts that at a third of this gain: the rest is chunks laid out straight);
# 8 unrolls twice the code for 0.3 ms.
_TRI_CHUNKS = 4
# Chunks a grid step of `kda_fwd` and of `kda_bwd`, in order, by a loop
# that is laid out straight (`unroll=True`: the body is traced once and
# lowered once a chunk). On a v5e at 2 x 8192 tokens and 32 heads, ms a
# call, forward / backward: 7.04 / 22.94 at 1, 5.81 / 21.76 at 2, 5.24 /
# 21.15 at 4, 4.95 / 20.90 at 8. The same chunks as a rolled loop: 6.83 /
# 22.82 at 2, 6.65 / 22.52 at 4, 6.56 / 22.37 at 8, 6.52 / 22.30 at 16: a
# grid step's fixed cost is 0.07-0.09 us (0.55 and 0.70 ms a call at 8192
# steps), and what pays is the products of one chunk that wait for no
# state (G, the scores, W, T (beta v)) scheduled into the gaps of its
# neighbour's chain through the state. The backward's 8 is twice the code
# of a whole `jax.vjp` for 0.25 ms.
_FWD_CHUNKS = 8
_BWD_CHUNKS = 4
_HI = jax.lax.Precision.HIGHEST
_F32 = jnp.float32


def _dot(a, b, dims, precision=None):
    return jax.lax.dot_general(
        a, b, (dims, ((), ())), precision=precision,
        preferred_element_type=_F32,
    )


_NN = ((1,), (0,))
_NT = ((1,), (1,))
_TN = ((0,), (0,))


@jax.custom_vjp
def _unit_lower_inverse(A):
    """T = (I + A)^{-1} for a strictly lower [C, C] A, which is nilpotent
    (A^C = 0): (I - A)(I + A^2)(I + A^4)..., two products a doubling."""
    C = A.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    M = -A
    T = (row == col).astype(_F32) + M
    steps = max(0, (C - 1).bit_length() - 1)
    for _ in range(steps):
        M = _dot(M, M, _NN, _HI)
        T = T + _dot(T, M, _NN, _HI)
    return T


def _unit_lower_inverse_fwd(A):
    T = _unit_lower_inverse(A)
    return T, T


def _unit_lower_inverse_bwd(T, dT):
    # T (I + A) = I  =>  dT = -T dA T  =>  dA = -T^T dT T^T. Only the
    # strictly lower part is A's: the mask that made A keeps it.
    return (-_dot(_dot(T, dT, _TN, _HI), T, _NT, _HI),)


_unit_lower_inverse.defvjp(_unit_lower_inverse_fwd, _unit_lower_inverse_bwd)


@jax.custom_vjp
def _carried_inverse(A, T):
    """The T that `kda_tri` wrote from this chunk's A, as a function of A:
    the primal is the carried T, the cotangent of A is the inverse's."""
    del A
    return T


def _carried_inverse_fwd(A, T):
    del A
    return T, T


def _carried_inverse_bwd(T, dT):
    return _unit_lower_inverse_bwd(T, dT) + (None,)


_carried_inverse.defvjp(_carried_inverse_fwd, _carried_inverse_bwd)


def _running_sum(g):
    C = g.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    return _dot((row >= col).astype(_F32), g, _NN, _HI)  # inclusive


def _scores(xs, kf, G, sub: int):
    """For each x of `xs` ([C, dk] float32) the [C, C] scores
    (x_i * e^{G_i - G_j}) . k_j, unmasked, a `sub`-row sub-chunk at a time
    against that sub-chunk's first row; the sub-chunk's rows of every x
    ride one product."""
    C = G.shape[0]
    tok = jax.lax.broadcasted_iota(jnp.int32, (C, 1), 0)
    rows = [[] for _ in xs]
    for a in range(C // sub):
        lo, hi = a * sub, (a + 1) * sub
        Ga = G[lo:hi]
        Gn = Ga[:1]                                  # the sub-chunk's first row
        rf = jnp.exp(Ga - Gn)                        # <= 1
        lhs = jnp.concatenate([x[lo:hi] * rf for x in xs], axis=0)
        seen = tok < hi                              # rows this sub-chunk may see
        rhs = jnp.where(seen, kf * jnp.exp(jnp.where(seen, Gn - G, 0.0)), 0.0)
        s = _dot(lhs, rhs, _NT, _HI)                 # [len(xs)*sub, C]
        for n, r in enumerate(rows):
            r.append(s[n * sub:(n + 1) * sub])
    return [jnp.concatenate(r, axis=0) for r in rows]


def _lower(a, *, strictly: bool):
    C = a.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    return jnp.where(row > col if strictly else row >= col, a, 0.0)


def _tri_math(k, kb, g, *, sub: int):
    """One chunk of one head: T = (I + A)^{-1} [C, C] float32 from k, kb
    (= beta*k) [C, dk] and g [C, dk] float32. No state and no q in it."""
    G = _running_sum(g)
    a, = _scores([kb.astype(_F32)], k.astype(_F32), G, sub)
    return _unit_lower_inverse(_lower(a, strictly=True))


def _chunk_math(q, k, kb, vb, g, st, T, *, sub: int, mxu,
                through_inverse: bool = False):
    """One chunk of one head. q, k, kb (= beta*k) [C, dk], vb (= beta*v)
    [C, dv], g [C, dk] float32, st = S^T [dv, dk] float32 entering the
    chunk, T [C, C] float32 the chunk's inverse as `_tri_math` made it;
    `mxu` is the operand dtype of the matmuls against S and U.
    `through_inverse` (the backward's `jax.vjp`) also forms A, beside P in
    the same products, so that T's cotangent reaches k, kb and g.
    Returns (o [C, dv] float32, S^T leaving)."""
    C = g.shape[0]
    lo_p = None if mxu == jnp.bfloat16 else _HI  # fp32 runs stay exact
    G = _running_sum(g)
    qf, kf, kbf = q.astype(_F32), k.astype(_F32), kb.astype(_F32)

    if through_inverse:
        a, p = _scores([kbf, qf], kf, G, sub)
        T = _carried_inverse(_lower(a, strictly=True), T)
    else:
        p, = _scores([qf], kf, G, sub)
    P = _lower(p, strictly=False)

    E = jnp.exp(G)                                   # <= 1: underflow is benign
    W = _dot(T, kbf * E, _NN, _HI)                   # [C, dk]
    Ur = _dot(T, vb.astype(_F32), _NN, _HI)          # [C, dv]
    st_lo = st.astype(mxu)
    U = Ur - _dot(W.astype(mxu), st_lo, _NT, lo_p)   # [C, dv]
    U_lo = U.astype(mxu)
    o = _dot((qf * E).astype(mxu), st_lo, _NT, lo_p) + _dot(
        P.astype(mxu), U_lo, _NN, lo_p)
    Gc = G[C - 1:]                                   # [1, dk] the chunk's decay
    Kd = (kf * jnp.exp(Gc - G)).astype(mxu)
    st_new = st * jnp.exp(Gc) + _dot(U_lo, Kd, _TN, lo_p)  # [dv, dk]
    return o, st_new


def _pack(T):
    """A chunk's [C, C] as it lies in HBM: the second half of its rows
    beside the first, [C/2, 2C], so that the chunk's block is whole
    128-lane tiles (a last dimension of 64 is stored padded to 128: twice
    the bytes)."""
    half = T.shape[0] // 2
    return jnp.concatenate([T[:half], T[half:]], axis=1)


def _unpack(P):
    C = P.shape[1] // 2
    return jnp.concatenate([P[:, :C], P[:, C:]], axis=0)


def _tri_kernel(k_ref, kb_ref, g_ref, t_ref, *, sub: int):
    C = t_ref.shape[-1] // 2
    for n in range(t_ref.shape[2]):
        rows = slice(n * C, (n + 1) * C)
        t_ref[0, 0, n] = _pack(_tri_math(
            k_ref[0, rows], kb_ref[0, rows], g_ref[0, rows], sub=sub))


def _chunk_rows(n, C):
    """Chunk n's rows of a grid step's token block (n may be traced)."""
    return pl.ds(pl.multiple_of(n * C, C), C)


def _fwd_kernel(q_ref, k_ref, kb_ref, vb_ref, g_ref, t_ref, o_ref, *rest,
                sub: int, mxu, keep_states: bool):
    st_scr = rest[-1]
    C = t_ref.shape[-1] // 2

    @pl.when(pl.program_id(2) == 0)
    def _init():
        st_scr[...] = jnp.zeros_like(st_scr)

    def chunk(n, st):
        rows = _chunk_rows(n, C)
        if keep_states:
            rest[0][0, 0, n] = st
        o, st = _chunk_math(
            q_ref[0, rows], k_ref[0, rows], kb_ref[0, rows], vb_ref[0, rows],
            g_ref[0, rows], st, _unpack(t_ref[0, 0, n]), sub=sub, mxu=mxu)
        o_ref[0, rows] = o.astype(o_ref.dtype)
        return st

    st_scr[...] = jax.lax.fori_loop(
        0, t_ref.shape[2], chunk, st_scr[...], unroll=True)


def _bwd_kernel(q_ref, k_ref, kb_ref, vb_ref, g_ref, st_ref, t_ref, do_ref,
                dq_ref, dk_ref, dkb_ref, dvb_ref, dg_ref, dst_scr, *,
                sub: int, mxu):
    C = t_ref.shape[-1] // 2
    chunks = t_ref.shape[2]

    @pl.when(pl.program_id(2) == 0)
    def _init():
        dst_scr[...] = jnp.zeros_like(dst_scr)

    def chunk(i, dst):
        n = chunks - 1 - i  # the step's chunks from its last to its first
        rows = _chunk_rows(n, C)
        _, vjp = jax.vjp(
            functools.partial(_chunk_math, T=_unpack(t_ref[0, 0, n]), sub=sub,
                              mxu=mxu, through_inverse=True),
            q_ref[0, rows], k_ref[0, rows], kb_ref[0, rows], vb_ref[0, rows],
            g_ref[0, rows], st_ref[0, 0, n])
        dq, dk, dkb, dvb, dg, dst = vjp(
            (do_ref[0, rows].astype(_F32), dst))
        dq_ref[0, rows] = dq.astype(dq_ref.dtype)
        dk_ref[0, rows] = dk.astype(dk_ref.dtype)
        dkb_ref[0, rows] = dkb.astype(dkb_ref.dtype)
        dvb_ref[0, rows] = dvb.astype(dvb_ref.dtype)
        dg_ref[0, rows] = dg.astype(dg_ref.dtype)
        return dst

    dst_scr[...] = jax.lax.fori_loop(
        0, chunks, chunk, dst_scr[...], unroll=True)


def _pad_rows(xs, to):
    """[B, T, .] arrays with T padded to a whole multiple of `to`."""
    pad = -xs[0].shape[1] % to
    if not pad:
        return xs
    return [jnp.pad(x, ((0, 0), (0, pad), (0, 0))) for x in xs]


def _params(*semantics):
    return pltpu.CompilerParams(dimension_semantics=semantics)


def _specs(C, dk, dv, n, order):
    """BlockSpecs of a grid step's `n` chunks: of q, k, kb, g (dk wide)
    and vb (dv wide) over [B, T, H*d] arrays, and of the chunks' [.., r, c]
    float32 blocks of a [B, H, T/C, r, c] array; `order(t)` maps the
    grid's last axis to a block of `n` chunks."""
    def tok(d):
        return pl.BlockSpec((1, n * C, d), lambda b, h, t: (b, order(t), h))

    def per_chunk(r, c):
        return pl.BlockSpec(
            (1, 1, n, r, c), lambda b, h, t: (b, h, order(t), 0, 0))

    return tok(dk), tok(dv), per_chunk


def _tri_call(k, kb, g, *, H, C):
    """T = (I + A)^{-1} of every chunk, packed: [B, H, T/C, C/2, 2C]
    float32. No chunk waits for another, so every grid axis is parallel
    and a grid step takes `_TRI_CHUNKS` chunks where that divides T/C."""
    B, T, _ = k.shape
    dk = k.shape[-1] // H
    nt = T // C
    n = math.gcd(nt, _TRI_CHUNKS)
    kspec = pl.BlockSpec((1, n * C, dk), lambda b, h, t: (b, t, h))
    return pl.pallas_call(
        functools.partial(_tri_kernel, sub=min(SUB, C)),
        grid=(B, H, nt // n),
        in_specs=[kspec, kspec, kspec],
        out_specs=pl.BlockSpec(
            (1, 1, n, C // 2, 2 * C), lambda b, h, t: (b, h, t, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, nt, C // 2, 2 * C), _F32),
        compiler_params=_params("parallel", "parallel", "parallel"),
        interpret=_fa._interpret(),
        name="kda_tri",
    )(k, kb, g)


# jitted, as `_prep_fwd_call` is and for its reasons: a step lowers each
# of the forward, the forward that keeps its states and the backward once,
# not once a layer (a body of `n` chunks laid out straight is `n` times
# the operations to lower, and lowering is not cached from run to run).
# `n` and `interpret` are arguments because jit's cache outlives a patched
# constant or `_interpret()`.
@functools.partial(jax.jit, static_argnames=(
    "H", "C", "mxu", "keep_states", "n", "interpret"))
def _fwd_walk(q, k, kb, vb, g, inv, *, H, C, mxu, keep_states, n, interpret):
    B, T, _ = q.shape
    dk, dv = q.shape[-1] // H, vb.shape[-1] // H
    nt = T // C
    kspec, vspec, per_chunk = _specs(C, dk, dv, n, lambda t: t)
    out_specs = [vspec]
    out_shape = [jax.ShapeDtypeStruct((B, T, H * dv), vb.dtype)]
    if keep_states:
        out_specs.append(per_chunk(dv, dk))
        out_shape.append(jax.ShapeDtypeStruct((B, H, nt, dv, dk), _F32))
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, sub=min(SUB, C), mxu=mxu,
                          keep_states=keep_states),
        grid=(B, H, nt // n),
        in_specs=[kspec, kspec, kspec, vspec, kspec,
                  per_chunk(C // 2, 2 * C)],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((dv, dk), _F32)],
        compiler_params=_params("parallel", "parallel", "arbitrary"),
        interpret=interpret,
        name="kda_fwd",
    )(q, k, kb, vb, g, inv)
    return out if keep_states else (out[0], None)


@functools.partial(jax.jit, static_argnames=(
    "H", "C", "mxu", "n", "interpret"))
def _bwd_walk(q, k, kb, vb, g, states, inv, do, *, H, C, mxu, n, interpret):
    B, T, _ = q.shape
    dk, dv = q.shape[-1] // H, vb.shape[-1] // H
    nt = T // C
    kspec, vspec, per_chunk = _specs(
        C, dk, dv, n, lambda t: nt // n - 1 - t)
    like = lambda x, dt=None: jax.ShapeDtypeStruct(x.shape, dt or x.dtype)  # noqa: E731
    return pl.pallas_call(
        functools.partial(_bwd_kernel, sub=min(SUB, C), mxu=mxu),
        grid=(B, H, nt // n),
        in_specs=[kspec, kspec, kspec, vspec, kspec, per_chunk(dv, dk),
                  per_chunk(C // 2, 2 * C), vspec],
        out_specs=[kspec, kspec, kspec, vspec, kspec],
        out_shape=[like(q), like(k), like(kb), like(vb), like(g)],
        scratch_shapes=[pltpu.VMEM((dv, dk), _F32)],
        compiler_params=_params("parallel", "parallel", "arbitrary"),
        interpret=interpret,
        name="kda_bwd",
    )(q, k, kb, vb, g, states, inv, do)


def _fwd_call(q, k, kb, vb, g, inv, *, H, C, mxu, keep_states):
    """(o, the chunks' entering states or None): `kda_fwd`, `_FWD_CHUNKS`
    chunks a grid step where that divides T/C, else their gcd."""
    return _fwd_walk(
        q, k, kb, vb, g, inv, H=H, C=C, mxu=mxu, keep_states=keep_states,
        n=math.gcd(q.shape[1] // C, _FWD_CHUNKS), interpret=_fa._interpret())


def _bwd_call(q, k, kb, vb, g, states, inv, do, *, H, C, mxu):
    """(dq, dk, dkb, dvb, dg): `kda_bwd`, `_BWD_CHUNKS` chunks a grid step
    as `_fwd_call` takes its own."""
    return _bwd_walk(
        q, k, kb, vb, g, states, inv, do, H=H, C=C, mxu=mxu,
        n=math.gcd(q.shape[1] // C, _BWD_CHUNKS), interpret=_fa._interpret())


def _inverse_then_fwd(q, k, kb, vb, g, H, C, mxu, keep_states):
    """The two forward calls. T is named so that a remat policy can keep it
    across the forward / backward boundary (models/transformer.py
    `save_attn` does): the block's backward then re-runs `kda_fwd` alone,
    for the states, and `kda_tri` is dead code there."""
    inv = checkpoint_name(_tri_call(k, kb, g, H=H, C=C), "kda_inverse")
    o, states = _fwd_call(q, k, kb, vb, g, inv, H=H, C=C, mxu=mxu,
                          keep_states=keep_states)
    return o, states, inv


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _kda_flat(q, k, kb, vb, g, H, C, mxu):
    return _inverse_then_fwd(q, k, kb, vb, g, H, C, mxu, False)[0]


def _kda_flat_fwd(q, k, kb, vb, g, H, C, mxu):
    """(o, what `_bwd_call` reads): the forward under differentiation,
    `_kda_flat`'s and `_gated_kda`'s."""
    o, states, inv = _inverse_then_fwd(q, k, kb, vb, g, H, C, mxu, True)
    # Named too, and kept by no policy of this repo: with them the block's
    # backward would not run `kda_fwd` a second time (537 MB a layer at
    # the kimi-linear cell's shapes).
    o = checkpoint_name(o, "kda_out")
    states = checkpoint_name(states, "kda_states")
    return o, (q, k, kb, vb, g, states, inv)


def _kda_flat_bwd(H, C, mxu, res, do):
    return tuple(_bwd_call(*res, do, H=H, C=C, mxu=mxu))


_kda_flat.defvjp(_kda_flat_fwd, _kda_flat_bwd)


# q, k and v from the q/k/v projection's output. A grid step's block of
# that output, tokens x channels (whole heads), and the rows of it the
# arithmetic walks at a time (a head's [_PREP_STRIP, 128] float32 stays in
# vector registers, not a VMEM array). On a v5e at [2, 8192, 3 * 4096] the
# forward / backward kernel took 2.60 / 5.81 ms at 256 x 512 blocks in
# strips of 16, 2.23 / 5.05 at 32, 2.03 / 4.57 at 64, 1.93 / 4.36 at 128,
# and 1.87 / 4.17 at 512 x 512 in strips of 128; blocks from 256 x 256 to
# 512 x 1024 lie within 5% of one another at one strip.
_PREP_ROWS = 512
_PREP_LANES = 512
_PREP_STRIP = 128
# The rows in front of a block come as the 16-row tile before it (one bf16
# tile), of which the last _PREP_PAD (one float32 tile) are kept: room for
# convolutions of up to _PREP_PAD + 1 taps.
_PREP_HALO = 16
_PREP_PAD = 8
# (the l2 norm a head, the scale d ** -0.5 after it) of the q, k, v thirds
_PREP_THIRDS = ((True, True), (True, False), (False, False))


def _prep_post(c, *, norm: bool, scaled: bool):
    """What follows the convolution, on one head's [rows, d] float32:
    SiLU, then for q and k the l2 norm over the head (eps 1e-6) and for q
    the scale d ** -0.5. models/kda.py's plain form, line for line."""
    s = jax.nn.silu(c)
    if norm:
        s = s * jax.lax.rsqrt(
            jnp.sum(jnp.square(s), axis=-1, keepdims=True) + 1e-6)
    if scaled:
        s = s * c.shape[-1] ** -0.5
    return s


def _prep_fill(x_ref, halo_ref, xp_scr, first):
    """The block and the _PREP_PAD rows before it (zeros in front of the
    sequence) in float32 scratch: row r of the block lies at _PREP_PAD + r,
    so tap i of K reads the rows from _PREP_PAD - (K - 1) + i."""
    halo = halo_ref[0].astype(_F32)[_PREP_HALO - _PREP_PAD:]
    xp_scr[:_PREP_PAD] = jnp.where(first, 0.0, halo)
    xp_scr[_PREP_PAD:] = x_ref[0].astype(_F32)


def _prep_strips(rows, lanes, d, strip):
    """A block as (head's lanes, that head's strips of rows)."""
    n = math.gcd(rows, strip)
    for c0 in range(0, lanes, d):
        yield slice(c0, c0 + d), [
            slice(r0, r0 + n) for r0 in range(0, rows, n)]


def _prep_conv(xp_scr, w_ref, rows, cols):
    """(the K shifted views of a strip, their weighted sum in the plain
    form's order)."""
    K = w_ref.shape[0]
    lo = _PREP_PAD - (K - 1)
    xs = [xp_scr[rows.start + lo + i:rows.stop + lo + i, cols]
          for i in range(K)]
    return xs, sum(x * w_ref[i:i + 1, cols] for i, x in enumerate(xs))


def _prep_fwd_kernel(*refs, d: int, strip: int):
    x_refs, halo_refs, w_refs, o_refs = (refs[3 * n:3 * n + 3]
                                         for n in range(4))
    xp_scr = refs[12]
    first = pl.program_id(2) == 0
    for x_ref, halo_ref, w_ref, o_ref, (norm, scaled) in zip(
            x_refs, halo_refs, w_refs, o_refs, _PREP_THIRDS):
        _prep_fill(x_ref, halo_ref, xp_scr, first)
        for cols, strips in _prep_strips(*o_ref.shape[1:], d, strip):
            for rows in strips:
                _, c = _prep_conv(xp_scr, w_ref, rows, cols)
                o_ref[0, rows, cols] = _prep_post(
                    c, norm=norm, scaled=scaled).astype(o_ref.dtype)


def _prep_bwd_block(x_ref, halo_ref, w_ref, dy_ref, dx_ref, dw_ref, xp_scr,
                    dc_scr, *, d, strip, norm, scaled, first, last):
    """One block of one third, walked from the sequence's end: dc, the
    convolution's cotangent, is formed in float32 scratch and never
    written; the _PREP_PAD rows of it that the NEXT block (the one walked
    before) began with sit behind this block's, for dx's taps."""
    K = w_ref.shape[0]
    bt = x_ref.shape[1]
    _prep_fill(x_ref, halo_ref, xp_scr, first)
    dc_scr[bt:] = jnp.where(last, 0.0, dc_scr[:_PREP_PAD])
    post = functools.partial(_prep_post, norm=norm, scaled=scaled)
    for cols, strips in _prep_strips(*x_ref.shape[1:], d, strip):
        dw = [0.0] * K
        for rows in strips:
            xs, c = _prep_conv(xp_scr, w_ref, rows, cols)
            dc, = jax.vjp(post, c)[1](dy_ref[0, rows, cols].astype(_F32))
            dc_scr[rows, cols] = dc
            dw = [a + jnp.sum(dc * x, axis=0, keepdims=True)
                  for a, x in zip(dw, xs)]
        for i, a in enumerate(dw):
            dw_ref[i:i + 1, cols] += a
        for rows in strips:  # x_t is tap i of y_{t + K - 1 - i}
            dx_ref[0, rows, cols] = sum(
                dc_scr[rows.start + K - 1 - i:rows.stop + K - 1 - i, cols]
                * w_ref[i:i + 1, cols] for i in range(K)).astype(dx_ref.dtype)


def _prep_bwd_kernel(x_ref, halo_ref, w_ref, dq_ref, dk_ref, dv_ref, dx_ref,
                     dw_ref, xp_scr, dc_scr, *, d: int, strip: int, J: int):
    third = pl.program_id(0) // J
    t, nt = pl.program_id(2), pl.num_programs(2)

    @pl.when((pl.program_id(1) == 0) & (t == 0))
    def _init():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    for n, (dy_ref, (norm, scaled)) in enumerate(
            zip((dq_ref, dk_ref, dv_ref), _PREP_THIRDS)):
        @pl.when(third == n)
        def _third(dy_ref=dy_ref, norm=norm, scaled=scaled):
            _prep_bwd_block(
                x_ref, halo_ref, w_ref, dy_ref, dx_ref, dw_ref, xp_scr,
                dc_scr, d=d, strip=strip, norm=norm, scaled=scaled,
                first=t == nt - 1, last=t == 0)


def _prep_blocks(T, D, d):
    """(rows, lanes, rows of a strip) of a grid step's block for
    x [B, T, 3*D] of heads of d: whole tiles of rows (T is padded to whole
    blocks of them), whole heads, lanes dividing D."""
    return (min(_PREP_ROWS, -(-T // _PREP_HALO) * _PREP_HALO),
            d * math.gcd(D // d, max(1, _PREP_LANES // d)), _PREP_STRIP)


# jitted: a step traces and lowers the kernel once, not once a layer and
# again under remat (the bodies are unrolled strip by strip). `interpret`
# is an argument because jit's cache outlives a patched `_interpret()`.
@functools.partial(jax.jit, static_argnames=("d", "blocks", "interpret"))
def _prep_fwd_call(x, w, *, d, blocks, interpret):
    B, T, D3 = x.shape
    D = D3 // 3
    bt, bd, strip = blocks
    J, per = D // bd, bt // _PREP_HALO

    def third(n):
        return (
            pl.BlockSpec((1, bt, bd), lambda b, j, t: (b, t, n * J + j)),
            pl.BlockSpec((1, _PREP_HALO, bd), lambda b, j, t: (
                b, jnp.maximum(t * per - 1, 0), n * J + j)),
            pl.BlockSpec((w.shape[0], bd), lambda b, j, t: (0, n * J + j)))

    xs, halos, ws = zip(*(third(n) for n in range(3)))
    out = pl.BlockSpec((1, bt, bd), lambda b, j, t: (b, t, j))
    return pl.pallas_call(
        functools.partial(_prep_fwd_kernel, d=d, strip=strip),
        grid=(B, J, T // bt),
        in_specs=[*xs, *halos, *ws],
        out_specs=[out] * 3,
        out_shape=[jax.ShapeDtypeStruct((B, T, D), x.dtype)] * 3,
        scratch_shapes=[pltpu.VMEM((_PREP_PAD + bt, bd), _F32)],
        compiler_params=_params("parallel", "parallel", "parallel"),
        interpret=interpret,
        name="qkv_prepare_fwd",
    )(x, x, x, x, x, x, w, w, w)


@functools.partial(jax.jit, static_argnames=("d", "blocks", "interpret"))
def _prep_bwd_call(x, w, dq, dk, dv, *, d, blocks, interpret):
    """(dx in x's dtype, the taps' gradient [K, 3*D] float32). The grid is
    (channel blocks of all three thirds, B, T blocks from the end): a
    channel block's taps' gradient stays in VMEM while its B x T rows go
    by; dq, dk and dv each stand at their first block while another
    third's channels are walked (an unchanged block is not fetched)."""
    B, T, D3 = x.shape
    D = D3 // 3
    bt, bd, strip = blocks
    J, nt, per = D // bd, T // bt, bt // _PREP_HALO

    def rows(t):
        return nt - 1 - t

    def cotangent(n):
        def index(j, b, t):
            mine = j // J == n
            return tuple(jnp.where(mine, i, 0)
                         for i in (b, rows(t), j - n * J))
        return pl.BlockSpec((1, bt, bd), index)

    block = pl.BlockSpec((1, bt, bd), lambda j, b, t: (b, rows(t), j))
    taps = pl.BlockSpec((w.shape[0], bd), lambda j, b, t: (0, j))
    return pl.pallas_call(
        functools.partial(_prep_bwd_kernel, d=d, strip=strip, J=J),
        grid=(3 * J, B, nt),
        in_specs=[block,
                  pl.BlockSpec((1, _PREP_HALO, bd), lambda j, b, t: (
                      b, jnp.maximum(rows(t) * per - 1, 0), j)),
                  taps, cotangent(0), cotangent(1), cotangent(2)],
        out_specs=[block, taps],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct(w.shape, _F32)],
        scratch_shapes=[pltpu.VMEM((_PREP_PAD + bt, bd), _F32),
                        pltpu.VMEM((bt + _PREP_PAD, bd), _F32)],
        compiler_params=_params("parallel", "arbitrary", "arbitrary"),
        interpret=interpret,
        name="qkv_prepare_bwd",
    )(x, x, w, dq, dk, dv)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _qkv_prepare(x, w, d, blocks):
    return tuple(_prep_fwd_call(
        x, w, d=d, blocks=blocks, interpret=_fa._interpret()))


def _qkv_prepare_fwd(x, w, d, blocks):
    return _qkv_prepare(x, w, d, blocks), (x, w)


def _qkv_prepare_bwd(d, blocks, res, cts):
    return tuple(_prep_bwd_call(
        *res, *cts, d=d, blocks=blocks, interpret=_fa._interpret()))


_qkv_prepare.defvjp(_qkv_prepare_fwd, _qkv_prepare_bwd)


def qkv_prepare_eligible(head_dim: int, taps: int) -> bool:
    """Heads as `kda_eligible` takes them, and taps that fit the rows kept
    in front of a block."""
    return kda_eligible(head_dim, head_dim) and taps <= _PREP_PAD + 1


def qkv_prepare(x: jax.Array, conv_w: jax.Array, *, heads: int,
                head_dim: int):
    """q, k, v [B, T, heads*head_dim] in x's dtype from the q/k/v
    projection's output x [B, T, 3*heads*head_dim] and the three causal
    depthwise convolutions' taps conv_w [K, 3*heads*head_dim] (float32;
    conv_w[K-1] is the tap on the token itself): convolution, SiLU, for q
    and k the l2 norm a head (eps 1e-6), for q the scale head_dim ** -0.5.
    One kernel forward and one backward; x is widened to float32 in VMEM
    and each result rounded once, as models/kda.py's plain form rounds it.
    Differentiable in x (the cotangent comes back in x's dtype) and conv_w.
    Any T: the tail is padded to a whole block and cut off again."""
    T, D = x.shape[1], heads * head_dim
    assert x.shape[2] == 3 * D == conv_w.shape[1], (x.shape, conv_w.shape)
    assert qkv_prepare_eligible(head_dim, conv_w.shape[0]), conv_w.shape
    blocks = _prep_blocks(T, D, head_dim)
    x, = _pad_rows([x], blocks[0])
    q, k, v = _qkv_prepare(x, conv_w.astype(_F32), head_dim, blocks)
    return q[:, :T], k[:, :T], v[:, :T]


# What stands between the projections and the recurrence, and behind it:
# the decay and beta's fold in front (`mixer_gates_fwd` / `_bwd`), the
# output norm x gate behind (`mixer_out_fwd` / `_bwd`). Elementwise passes
# over [B, T, H*d] with one number a head in them (beta, the norm's rsqrt),
# which XLA reduces and broadcasts in a head-a-row tiling [.., H, d] and
# re-tiles on the way in and out. Here a head is 128 lanes of a row: the
# blocks and the strip walk are `qkv_prepare`'s, the grid is (B, row
# blocks, channel blocks) with the channel blocks innermost, so that the
# [rows, H] block of beta's logits (H is that array's whole last
# dimension) stands while its heads go by.


def _head_column(x, h):
    """Column h of x [rows, H] as [rows, 1] (h may be traced): one value
    and zeros summed over the lanes, so exact."""
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    return jnp.sum(jnp.where(lane == h, x, 0.0), axis=-1, keepdims=True)


def _gates_math(a, k, v, bl, bias, rate, *, h):
    """One head's strip in float32: a, k, v [rows, d], beta's logits
    bl [rows, H] of which head h is this one, bias and rate [1, d] (the
    head's dt_bias and -exp(A_log)). Returns (g, beta * k, beta * v):
    models/kda.py's `gates_plain` and `fold_beta` below, line for line."""
    b = jax.nn.sigmoid(_head_column(bl, h))
    return rate * jax.nn.softplus(a + bias), k * b, v * b


def _out_math(o, z, w, *, eps):
    """One head's strip in float32: o, z [rows, d], w [1, d]. Returns
    rmsnorm_d(o) * w * sigmoid(z): models/kda.py's `out_plain`."""
    o = o * jax.lax.rsqrt(
        jnp.mean(jnp.square(o), axis=-1, keepdims=True) + eps) * w
    return o * jax.nn.sigmoid(z)


def _f32(ref, rows, cols):
    return ref[0, rows, cols].astype(_F32)


def _gates_fwd_kernel(a_ref, k_ref, v_ref, bl_ref, p_ref, g_ref, kb_ref,
                      vb_ref, *, d: int, strip: int):
    bt, bd = a_ref.shape[1:]
    h0 = pl.program_id(2) * (bd // d)
    for cols, strips in _prep_strips(bt, bd, d, strip):
        math = functools.partial(_gates_math, h=h0 + cols.start // d)
        for rows in strips:
            g, kb, vb = math(
                _f32(a_ref, rows, cols), _f32(k_ref, rows, cols),
                _f32(v_ref, rows, cols), bl_ref[0, rows].astype(_F32),
                p_ref[0:1, cols], p_ref[1:2, cols])
            g_ref[0, rows, cols] = g
            kb_ref[0, rows, cols] = kb.astype(kb_ref.dtype)
            vb_ref[0, rows, cols] = vb.astype(vb_ref.dtype)


def _gates_bwd_kernel(a_ref, k_ref, v_ref, bl_ref, p_ref, dg_ref, dkb_ref,
                      dvb_ref, dkr_ref, da_ref, dk_ref, dv_ref, dbl_ref,
                      dp_ref, *, d: int, strip: int):
    """`_gates_math`'s own vjp a head's strip (the two cannot drift
    apart). dk is the recurrence's dk plus the fold's, rounded once; the
    logits' cotangent gathers its heads in the block that stands while
    the channel blocks go by; dt_bias's and the rate's are this grid
    step's sums over its rows (the caller adds the steps')."""
    bt, bd = a_ref.shape[1:]
    h0 = pl.program_id(2) * (bd // d)

    @pl.when(pl.program_id(2) == 0)
    def _init():
        dbl_ref[...] = jnp.zeros_like(dbl_ref)

    for cols, strips in _prep_strips(bt, bd, d, strip):
        math = functools.partial(_gates_math, h=h0 + cols.start // d)
        dbias = drate = 0.0
        for rows in strips:
            _, vjp = jax.vjp(
                math, _f32(a_ref, rows, cols), _f32(k_ref, rows, cols),
                _f32(v_ref, rows, cols), bl_ref[0, rows].astype(_F32),
                p_ref[0:1, cols], p_ref[1:2, cols])
            da, dk, dv, dbl, db, dr = vjp((
                dg_ref[0, rows, cols], _f32(dkb_ref, rows, cols),
                _f32(dvb_ref, rows, cols)))
            da_ref[0, rows, cols] = da.astype(da_ref.dtype)
            dk_ref[0, rows, cols] = (
                _f32(dkr_ref, rows, cols) + dk).astype(dk_ref.dtype)
            dv_ref[0, rows, cols] = dv.astype(dv_ref.dtype)
            # the other heads' columns of dbl are zeros: the sum is exact
            dbl_ref[0, rows] = (
                dbl_ref[0, rows].astype(_F32) + dbl).astype(dbl_ref.dtype)
            dbias, drate = dbias + db, drate + dr
        dp_ref[0, 0:1, cols] = dbias
        dp_ref[0, 1:2, cols] = drate


def _out_fwd_kernel(o_ref, z_ref, w_ref, y_ref, *, d: int, strip: int,
                    eps: float):
    for cols, strips in _prep_strips(*o_ref.shape[1:], d, strip):
        for rows in strips:
            y_ref[0, rows, cols] = _out_math(
                _f32(o_ref, rows, cols), _f32(z_ref, rows, cols), w_ref[...],
                eps=eps).astype(y_ref.dtype)


def _out_bwd_kernel(o_ref, z_ref, w_ref, dy_ref, do_ref, dz_ref, dw_ref, *,
                    d: int, strip: int, eps: float):
    """`_out_math`'s own vjp a head's strip; the norm's weight is every
    head's, so its cotangent is one [1, d] sum a grid step."""
    dw = 0.0
    for cols, strips in _prep_strips(*o_ref.shape[1:], d, strip):
        for rows in strips:
            _, vjp = jax.vjp(
                functools.partial(_out_math, eps=eps),
                _f32(o_ref, rows, cols), _f32(z_ref, rows, cols), w_ref[...])
            do, dz, dw_strip = vjp(_f32(dy_ref, rows, cols))
            do_ref[0, rows, cols] = do.astype(do_ref.dtype)
            dz_ref[0, rows, cols] = dz.astype(dz_ref.dtype)
            dw = dw + dw_strip
    dw_ref[0] = dw


def _tok_spec(bt, bd):
    """A [B, T, D] array's block over the grid (B, row blocks, channel
    blocks)."""
    return pl.BlockSpec((1, bt, bd), lambda b, t, j: (b, t, j))


def _gates_specs(bt, bd, H):
    """(a [B, T, D] array's block, the whole rows of beta's logits
    [B, T, H], the two rows of per-channel numbers [2, D])."""
    return (_tok_spec(bt, bd),
            pl.BlockSpec((1, bt, H), lambda b, t, j: (b, t, 0)),
            pl.BlockSpec((2, bd), lambda b, t, j: (0, j)))


# jitted with `interpret` static, as `_prep_fwd_call` is and for its reasons.
@functools.partial(jax.jit, static_argnames=("d", "blocks", "interpret"))
def _gates_fwd_call(a, k, v, bl, p, *, d, blocks, interpret):
    """(g float32, beta * k, beta * v) from a, k, v [B, T, D], beta's
    logits bl [B, T, H] and p [2, D] float32 (dt_bias, -exp(A_log) a
    channel)."""
    B, T, D = a.shape
    bt, bd, strip = blocks
    tok, per_head, per_channel = _gates_specs(bt, bd, bl.shape[2])
    return pl.pallas_call(
        functools.partial(_gates_fwd_kernel, d=d, strip=strip),
        grid=(B, T // bt, D // bd),
        in_specs=[tok, tok, tok, per_head, per_channel],
        out_specs=[tok] * 3,
        out_shape=[jax.ShapeDtypeStruct(a.shape, _F32),
                   jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        compiler_params=_params("parallel", "parallel", "parallel"),
        interpret=interpret,
        name="mixer_gates_fwd",
    )(a, k, v, bl, p)


@functools.partial(jax.jit, static_argnames=("d", "blocks", "interpret"))
def _gates_bwd_call(a, k, v, bl, p, dg, dkb, dvb, dk, *, d, blocks,
                    interpret):
    """(da, dk, dv, dbl in their primals' dtypes, dp [2, D] float32) from
    the cotangents of g, beta * k, beta * v and the dk that reached k
    past the fold (the recurrence's own)."""
    B, T, D = a.shape
    bt, bd, strip = blocks
    nt = T // bt
    tok, per_head, per_channel = _gates_specs(bt, bd, bl.shape[2])
    *grads, dp = pl.pallas_call(
        functools.partial(_gates_bwd_kernel, d=d, strip=strip),
        grid=(B, nt, D // bd),
        in_specs=[tok, tok, tok, per_head, per_channel, tok, tok, tok, tok],
        out_specs=[tok, tok, tok, per_head,
                   pl.BlockSpec((1, 2, bd), lambda b, t, j: (b * nt + t, 0, j))],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype)
                   for x in (a, k, v, bl)] + [
            jax.ShapeDtypeStruct((B * nt, 2, D), _F32)],
        compiler_params=_params("parallel", "parallel", "arbitrary"),
        interpret=interpret,
        name="mixer_gates_bwd",
    )(a, k, v, bl, p, dg, dkb, dvb, dk)
    return (*grads, dp.sum(axis=0))


@functools.partial(jax.jit,
                   static_argnames=("d", "eps", "blocks", "interpret"))
def _out_fwd_call(o, z, w, *, d, eps, blocks, interpret):
    B, T, D = o.shape
    bt, bd, strip = blocks
    tok = _tok_spec(bt, bd)
    return pl.pallas_call(
        functools.partial(_out_fwd_kernel, d=d, strip=strip, eps=eps),
        grid=(B, T // bt, D // bd),
        in_specs=[tok, tok, pl.BlockSpec((1, d), lambda b, t, j: (0, 0))],
        out_specs=tok,
        out_shape=jax.ShapeDtypeStruct(o.shape, o.dtype),
        compiler_params=_params("parallel", "parallel", "parallel"),
        interpret=interpret,
        name="mixer_out_fwd",
    )(o, z, w)


@functools.partial(jax.jit,
                   static_argnames=("d", "eps", "blocks", "interpret"))
def _out_bwd_call(o, z, w, dy, *, d, eps, blocks, interpret):
    """(do, dz in their primals' dtypes, dw [1, d] float32)."""
    B, T, D = o.shape
    bt, bd, strip = blocks
    nt, J = T // bt, D // bd
    tok = _tok_spec(bt, bd)
    do, dz, dw = pl.pallas_call(
        functools.partial(_out_bwd_kernel, d=d, strip=strip, eps=eps),
        grid=(B, nt, J),
        in_specs=[tok, tok, pl.BlockSpec((1, d), lambda b, t, j: (0, 0)),
                  tok],
        out_specs=[tok, tok, pl.BlockSpec(
            (1, 1, d), lambda b, t, j: ((b * nt + t) * J + j, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct(o.shape, o.dtype),
                   jax.ShapeDtypeStruct(z.shape, z.dtype),
                   jax.ShapeDtypeStruct((B * nt * J, 1, d), _F32)],
        compiler_params=_params("parallel", "parallel", "parallel"),
        interpret=interpret,
        name="mixer_out_bwd",
    )(o, z, w, dy)
    return do, dz, dw.sum(axis=0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _mixer_out(o, z, w, d, eps, blocks):
    return _out_fwd_call(o, z, w, d=d, eps=eps, blocks=blocks,
                         interpret=_fa._interpret())


def _mixer_out_fwd(o, z, w, d, eps, blocks):
    return _mixer_out(o, z, w, d, eps, blocks), (o, z, w)


def _mixer_out_bwd(d, eps, blocks, res, dy):
    return _out_bwd_call(*res, dy, d=d, eps=eps, blocks=blocks,
                         interpret=_fa._interpret())


_mixer_out.defvjp(_mixer_out_fwd, _mixer_out_bwd)


def mixer_out(o: jax.Array, gate_logits: jax.Array, o_norm: jax.Array, *,
              eps: float) -> jax.Array:
    """rmsnorm_d(o; o_norm) * sigmoid(gate_logits) in o's dtype: o and
    the logits [B, T, H*d], o_norm [d] the norm's weight (every head's).
    One kernel forward and one backward, float32 in VMEM alone, each
    result rounded once as models/kda.py's `out_plain` rounds it.
    Differentiable in all three (the cotangents in their primals'
    dtypes). Any T, as `qkv_prepare` takes it."""
    T, D = o.shape[1:]
    d = o_norm.shape[0]
    assert kda_eligible(d, d) and D % d == 0, (o.shape, o_norm.shape)
    blocks = _prep_blocks(T, D, d)
    o, z = _pad_rows([o, gate_logits], blocks[0])
    y = _mixer_out(o, z, o_norm.astype(_F32).reshape(1, d), d, float(eps),
                   blocks)
    return y[:, :T]


def _gates(a, k, v, bl, p, H, blocks):
    return _gates_fwd_call(a, k, v, bl, p, d=a.shape[-1] // H, blocks=blocks,
                           interpret=_fa._interpret())


def _gates_params(dt_bias, a_log, d):
    """The two rows of per-channel numbers the gates' kernels read:
    dt_bias and -exp(A_log) a channel, [2, H*d] float32."""
    return jnp.stack([dt_bias.astype(_F32),
                      -jnp.repeat(jnp.exp(a_log.astype(_F32)), d)])


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9))
def _gated_kda(q, k, v, a, bl, p, H, C, mxu, blocks):
    g, kb, vb = _gates(a, k, v, bl, p, H, blocks)
    return _inverse_then_fwd(q, k, kb, vb, g, H, C, mxu, False)[0], g


def _gated_kda_fwd(q, k, v, a, bl, p, H, C, mxu, blocks):
    g, kb, vb = _gates(a, k, v, bl, p, H, blocks)
    o, res = _kda_flat_fwd(q, k, kb, vb, g, H, C, mxu)
    return (o, g), (res, v, a, bl, p)


def _gated_kda_bwd(H, C, mxu, blocks, res, cts):
    """ONE backward for the fold and the recurrence, so that k's two
    cotangents (the recurrence's own and the fold's) are added in float32
    inside `mixer_gates_bwd` and written once. g leaves `gated_kda`
    under stop_gradient: its cotangent is not read."""
    res, v, a, bl, p = res
    k = res[1]
    dq, dk, dkb, dvb, dg = _bwd_call(*res, cts[0], H=H, C=C, mxu=mxu)
    da, dk, dv, dbl, dp = _gates_bwd_call(
        a, k, v, bl, p, dg, dkb, dvb, dk, d=a.shape[-1] // H,
        blocks=blocks, interpret=_fa._interpret())
    return dq, dk, dv, da, dbl, dp


_gated_kda.defvjp(_gated_kda_fwd, _gated_kda_bwd)


def gated_kda(q: jax.Array, k: jax.Array, v: jax.Array, a: jax.Array,
              beta_logits: jax.Array, dt_bias: jax.Array, a_log: jax.Array,
              *, chunk: int = CHUNK, mxu_dtype=None):
    """`kda_flat` from what the mixer's projections give: the decay
    g = -exp(a_log) * softplus(a + dt_bias) and beta = sigmoid(beta_logits)
    are formed, and beta folded into k and v, in one kernel in front of
    the recurrence's (float32 in VMEM alone; g float32, beta * k and
    beta * v rounded once, as models/kda.py's `gates_plain` and
    `fold_beta` round them), and one kernel behind the recurrence's
    backward writes the cotangents of a, k, v, the logits, dt_bias and
    a_log, k's once. q, k, v, a [B, T, H*d]; beta_logits [B, T, H];
    dt_bias [H*d]; a_log [H]. Returns (o [B, T, H*d] in v's dtype, g
    [B, T, H*d] float32 under stop_gradient: for gauges)."""
    B, T, H = beta_logits.shape
    D = a.shape[-1]
    d = D // H
    assert kda_eligible(d, d), d
    assert q.shape == k.shape == v.shape == a.shape, (q.shape, a.shape)
    blocks = _prep_blocks(T, D, d)
    args = _pad_rows([q, k, v, a, beta_logits], math.lcm(blocks[0], chunk))
    o, g = _gated_kda(*args, _gates_params(dt_bias, a_log, d), H, chunk,
                      jnp.dtype(mxu_dtype or q.dtype), blocks)
    return o[:, :T], jax.lax.stop_gradient(g[:, :T])


def kda_eligible(dk: int, dv: int) -> bool:
    """The kernels tile heads of a multiple of 128 on the chip; interpreted
    (CPU tests) any size runs."""
    return _fa._interpret() or (dk % 128 == 0 and dv % 128 == 0)


def kda(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
        beta: jax.Array, *, chunk: int = CHUNK, mxu_dtype=None) -> jax.Array:
    """Chunked gated delta rule, differentiable in all five arguments.

    q, k, g [B, T, H, dk]; v [B, T, H, dv]; beta [B, T, H]. g is the log
    decay (<= 0) and is taken in float32. Returns o [B, T, H, dv] in v's
    dtype. `mxu_dtype` (default: q's dtype) is the operand dtype of the
    matmuls against the state: float32 q, k, v with mxu_dtype bfloat16
    keeps the scores, the inverse and the output unrounded and only those
    four matmuls in bf16. Any T: the tail is padded to a whole chunk with
    tokens that write nothing (k = v = 0, g = 0) and cut off again.
    """
    B, T, H, _ = q.shape
    o = kda_flat(*(x.reshape(B, T, -1) for x in (q, k, v, g)), beta,
                 chunk=chunk, mxu_dtype=mxu_dtype)
    return o.reshape(B, T, H, v.shape[-1])


def fold_beta(k: jax.Array, v: jax.Array, beta: jax.Array):
    """(beta * k, beta * v) in k's and v's dtypes: k [B, T, H*dk],
    v [B, T, H*dv], beta [B, T, H]. The plain form of what
    `mixer_gates_fwd` does with beta."""
    b = beta.astype(_F32)
    fold = lambda x: (x.astype(_F32) * jnp.repeat(  # noqa: E731
        b, x.shape[-1] // b.shape[-1], axis=-1)).astype(x.dtype)
    return fold(k), fold(v)


def kda_flat(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
             beta: jax.Array, *, chunk: int = CHUNK,
             mxu_dtype=None) -> jax.Array:
    """`kda` in the layout the kernels read: q, k, g [B, T, H*dk],
    v [B, T, H*dv], beta [B, T, H]; returns o [B, T, H*dv]. On the chip a
    [B, T, H, d] view of such an array is a relayout (another tiling), and
    the way back here a second: a caller that has the projections' outputs
    as they come keeps them so."""
    B, T, H = beta.shape
    dk, dv = q.shape[-1] // H, v.shape[-1] // H
    if not kda_eligible(dk, dv):
        heads = lambda x: x.reshape(B, T, H, -1)  # noqa: E731
        return kda_recurrent(
            *(heads(x) for x in (q, k, v, g)), beta).reshape(v.shape)
    args = _pad_rows([q, k, *fold_beta(k, v, beta), g.astype(_F32)], chunk)
    o = _kda_flat(*args, H, chunk, jnp.dtype(mxu_dtype or q.dtype))
    return o[:, :T]


def kda_recurrent(q, k, v, g, beta) -> jax.Array:
    """The delta rule token by token in float32 (`lax.scan` over T)."""
    B, T, H, dk = q.shape
    dv = v.shape[-1]
    f = lambda x: jnp.moveaxis(x.astype(_F32), 1, 0)  # noqa: E731

    def step(S, xs):
        q_t, k_t, v_t, g_t, b_t = xs                   # [B, H, .]
        S = S * jnp.exp(g_t)[..., None]                # [B, H, dk, dv]
        pred = jnp.einsum("bhkv,bhk->bhv", S, k_t, precision=_HI)
        u = b_t[..., None] * (v_t - pred)
        S = S + k_t[..., None] * u[..., None, :]
        return S, jnp.einsum("bhkv,bhk->bhv", S, q_t, precision=_HI)

    S0 = jnp.zeros((B, H, dk, dv), _F32)
    _, o = jax.lax.scan(step, S0, (f(q), f(k), f(v), f(g), f(beta)))
    return jnp.moveaxis(o, 0, 1).astype(v.dtype)


def chunk_decay_min(g: jax.Array) -> jax.Array:
    """The most negative gate summed over one 16-token sub-chunk and one
    channel: the exponent whose negation the kernel exponentiates. float32
    holds e^{-x} down to about -88."""
    B, T = g.shape[:2]
    pad = -T % SUB
    if pad:
        g = jnp.pad(g, ((0, 0), (0, pad)) + ((0, 0),) * (g.ndim - 2))
    g = g.reshape(B, (T + pad) // SUB, SUB, *g.shape[2:])
    return jnp.min(jnp.sum(g.astype(_F32), axis=2))
