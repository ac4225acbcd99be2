"""Pallas TPU flash attention (forward + backward), GQA-aware.

Replaces the reference's FlashAttention-2 CUDA dependency (ref:
Src/Main_Scripts/core/model.py:740 _flash_attention, ColossalAI
flash_attention extensions). Online-softmax tiling keeps the [S, S] score
matrix out of HBM: scores are computed block-by-block in VMEM with running
max/denominator scratch, so HBM traffic is O(S·D) instead of O(S²).

Layout: q [B, S, Hq, D] / k [B, S, Hkv, D] / v [B, S, Hkv, Dv] (Dv = D
unless the caller's values are narrower than its scores, as latent
attention's 192 / 128 are; the output is Dv wide; GQA folds the query-head group
via index arithmetic in the BlockSpec index maps — KV blocks are fetched once
per group without materializing repeated heads). Backward uses the standard
two-pass recomputation with the forward's logsumexp, as separate dq and dkv
kernels so each accumulates over its own innermost grid axis.

Falls back to interpreter mode off-TPU (CPU tests), XLA remains available via
GQAttention's einsum path.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128  # lane-replicated storage for per-row stats (TPU tiling)


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


# -- banded-grid geometry (shared by all three kernels) ----------------------
# With a sliding window the kv (resp. q) grid axis is SHRUNK to the number
# of blocks that can intersect any block's band, and an offset index map
# slides the band along the diagonal: skipped out-of-band blocks then cost
# neither grid steps nor K/V block DMA, making windowed attention O(S*W)
# in both compute and HBM traffic (the splash-attention approach).
def _kv_block_offset(i, block_q: int, block_kv: int, window: int):
    """First kv block intersecting q block i's window band (traced-safe)."""
    return jnp.maximum(0, i * block_q - window + 1) // block_kv


def _q_block_offset(j, block_q: int, block_kv: int):
    """First q block intersecting kv block j's causal region."""
    return (j * block_kv) // block_q


def _n_kv_steps(skv: int, block_q: int, block_kv: int, window: int) -> int:
    n = skv // block_kv
    if window:
        n = min(n, (window + block_q - 2) // block_kv + 2)
    return n


def _n_q_steps(sq: int, block_q: int, block_kv: int, window: int) -> int:
    n = sq // block_q
    if window:
        n = min(n, (window + block_kv - 2) // block_q + 2)
    return n


def _block_needed(q_start, kv_start, block_q, block_kv, causal, window,
                  kv_limit):
    """Does the (q block, kv block) pair intersect the attention band?"""
    needed = (not causal) or (kv_start <= q_start + block_q - 1)
    if window:
        needed = jnp.logical_and(
            needed, kv_start + block_kv - 1 >= q_start - window + 1
        )
        # Offset grids can run past the sequence end; those steps fetch a
        # clamped block and must not compute.
        needed = jnp.logical_and(needed, kv_start < kv_limit)
    return needed


def _band_mask(s, q_start, kv_start, block_q, block_kv, window):
    """In-block causal(+window) masking of the [block_q, block_kv] scores."""
    q_pos = q_start + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_kv), 0
    )
    k_pos = kv_start + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_kv), 1
    )
    keep = q_pos >= k_pos
    if window:
        keep = jnp.logical_and(keep, q_pos - k_pos < window)
    return jnp.where(keep, s, NEG_INF)


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------
def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr, *, scale, block_q, block_kv, causal, window, skv):
    j = pl.program_id(3)
    nj = pl.num_programs(3)
    i = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    q_start = i * block_q
    # Banded grid under a window: grid step j maps to kv block offset+j
    # (the same formula as the K/V BlockSpec index maps).
    jv = _kv_block_offset(i, block_q, block_kv, window) + j if window else j
    kv_start = jv * block_kv
    needed = _block_needed(
        q_start, kv_start, block_q, block_kv, causal, window, skv
    )

    @pl.when(needed)
    def _compute():
        # Matmul operands stay in their stored dtype (bf16 in training):
        # an fp32 MXU pass costs several bf16 passes on TPU, and fp32
        # accumulation via preferred_element_type keeps the numerics.
        q = q_ref[0, 0, :, :]
        k = k_ref[0, 0, :, :]
        v = v_ref[0, 0, :, :]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # [bq, bkv] fp32
        if causal:
            s = _band_mask(s, q_start, kv_start, block_q, block_kv, window)
        m_prev = m_scr[:, :]  # [bq, 128] lane-replicated running max
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1)[:, None])
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, :1])
        l_scr[:, :] = l_scr[:, :] * alpha + jnp.sum(p, axis=-1)[:, None]
        acc_scr[:] = acc_scr[:] * alpha[:, :1] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[:, :] = m_new

    @pl.when(j == nj - 1)
    def _finalize():
        l = l_scr[:, :]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0, :, :] = (acc_scr[:] / safe_l[:, :1]).astype(o_ref.dtype)
        lse_ref[0, 0, :, :] = m_scr[:, :] + jnp.log(safe_l)


def _kv_index_map(group, block_q, block_kv, window, n_kv):
    """K/V BlockSpec index map: banded offset under a window (clamped to
    the last block; clamped steps are compute-skipped via _block_needed)."""
    if not window:
        return lambda b, h, i, j: (b, h // group, j, 0)

    def index(b, h, i, j):
        jv = _kv_block_offset(i, block_q, block_kv, window) + j
        return (b, h // group, jnp.minimum(jv, n_kv - 1), 0)

    return index


def _fwd(q, k, v, *, scale, causal, block_q, block_kv, window=0):
    B, Sq, Hq, D = q.shape
    _, Skv, Hkv, _ = k.shape
    Dv = v.shape[-1]  # values may be narrower than the scores (latent attention)
    group = Hq // Hkv
    qt = q.transpose(0, 2, 1, 3)  # [B, Hq, Sq, D]
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    grid = (B, Hq, Sq // block_q, _n_kv_steps(Skv, block_q, block_kv, window))
    kv_map = _kv_index_map(group, block_q, block_kv, window, Skv // block_kv)

    out, lse = pl.pallas_call(
        functools.partial(
            _fwd_kernel, scale=scale, block_q=block_q, block_kv=block_kv, causal=causal, window=window, skv=Skv
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_kv, D), kv_map),
            pl.BlockSpec((1, 1, block_kv, Dv), kv_map),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, Dv), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_q, LANES), lambda b, h, i, j: (b, h, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, Hq, Sq, Dv), q.dtype),
            jax.ShapeDtypeStruct((B, Hq, Sq, LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, LANES), jnp.float32),
            pltpu.VMEM((block_q, LANES), jnp.float32),
            pltpu.VMEM((block_q, Dv), jnp.float32),
        ],
        interpret=_interpret(),
        name="flash_fwd",
    )(qt, kt, vt)
    return out.transpose(0, 2, 1, 3), lse


# ---------------------------------------------------------------------------
# Backward
# ---------------------------------------------------------------------------
def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_scr, *, scale, block_q, block_kv, causal, window, skv):
    j = pl.program_id(3)
    nj = pl.num_programs(3)
    i = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    q_start = i * block_q
    jv = _kv_block_offset(i, block_q, block_kv, window) + j if window else j
    kv_start = jv * block_kv
    needed = _block_needed(
        q_start, kv_start, block_q, block_kv, causal, window, skv
    )

    @pl.when(needed)
    def _compute():
        q = q_ref[0, 0, :, :]
        k = k_ref[0, 0, :, :]
        v = v_ref[0, 0, :, :]
        do = do_ref[0, 0, :, :]
        lse = lse_ref[0, 0, :, 0:1]  # [bq, 1]
        delta = delta_ref[0, 0, :, 0:1]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        if causal:
            s = _band_mask(s, q_start, kv_start, block_q, block_kv, window)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = (p * (dp - delta) * scale).astype(k.dtype)
        dq_scr[:] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(j == nj - 1)
    def _finalize():
        dq_ref[0, 0, :, :] = dq_scr[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref, dk_scr, dv_scr, *, scale, block_q, block_kv, causal, window, sq):
    i = pl.program_id(3)  # q blocks innermost here
    ni = pl.num_programs(3)
    j = pl.program_id(2)

    @pl.when(i == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    # Banded grid under a window: grid step i maps to q block offset+i
    # (the same formula as the q-side BlockSpec index maps).
    iv = _q_block_offset(j, block_q, block_kv) + i if window else i
    q_start = iv * block_q
    kv_start = j * block_kv
    # Like _block_needed, but the offset axis here is q: the overrun guard
    # bounds q_start instead of kv_start.
    needed = (not causal) or (kv_start <= q_start + block_q - 1)
    if window:
        needed = jnp.logical_and(
            needed, kv_start + block_kv - 1 >= q_start - window + 1
        )
        needed = jnp.logical_and(needed, q_start < sq)

    @pl.when(needed)
    def _compute():
        q = q_ref[0, 0, :, :]
        k = k_ref[0, 0, :, :]
        v = v_ref[0, 0, :, :]
        do = do_ref[0, 0, :, :]
        lse = lse_ref[0, 0, :, 0:1]  # [bq, 1]
        delta = delta_ref[0, 0, :, 0:1]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        if causal:
            s = _band_mask(s, q_start, kv_start, block_q, block_kv, window)
        p = jnp.exp(s - lse)  # [bq, bkv] fp32
        p_lo = p.astype(do.dtype)
        dv_scr[:] += jax.lax.dot_general(
            p_lo, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = (p * (dp - delta) * scale).astype(q.dtype)
        dk_scr[:] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(i == ni - 1)
    def _finalize():
        dk_ref[0, 0, :, :] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0, 0, :, :] = dv_scr[:].astype(dv_ref.dtype)


def _bwd(scale, causal, block_q, block_kv, window, res, g, g_lse=None):
    q, k, v, out, lse_small = res
    do = g
    B, Sq, Hq, D = q.shape
    _, Skv, Hkv, _ = k.shape
    Dv = v.shape[-1]
    group = Hq // Hkv

    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    dot = do.transpose(0, 2, 1, 3)
    ot = out.transpose(0, 2, 1, 3)
    # Residual lse is compact [B, Hq, Sq]; re-expand to the kernel's
    # lane-replicated layout only for the lifetime of the bwd kernels.
    lse = jnp.broadcast_to(lse_small[..., None], (*lse_small.shape, LANES))
    delta = jnp.sum(dot.astype(jnp.float32) * ot.astype(jnp.float32), axis=-1)
    if g_lse is not None:
        # lse cotangent folds into delta: dlse/ds = p, so
        # ds = p·(dp − delta + ḡ_lse) = p·(dp − (delta − ḡ_lse)) — the
        # kernels need no change to also differentiate the lse output.
        delta = delta - g_lse.astype(jnp.float32)
    delta = jnp.broadcast_to(delta[..., None], (*delta.shape, LANES))

    kv_map = _kv_index_map(group, block_q, block_kv, window, Skv // block_kv)
    common_specs = [
        pl.BlockSpec((1, 1, block_q, D), lambda b, h, i, j: (b, h, i, 0)),
        pl.BlockSpec((1, 1, block_kv, D), kv_map),
        pl.BlockSpec((1, 1, block_kv, Dv), kv_map),
        pl.BlockSpec((1, 1, block_q, Dv), lambda b, h, i, j: (b, h, i, 0)),
        pl.BlockSpec((1, 1, block_q, LANES), lambda b, h, i, j: (b, h, i, 0)),
        pl.BlockSpec((1, 1, block_q, LANES), lambda b, h, i, j: (b, h, i, 0)),
    ]

    dq = pl.pallas_call(
        functools.partial(
            _bwd_dq_kernel, scale=scale, block_q=block_q, block_kv=block_kv, causal=causal, window=window, skv=Skv
        ),
        grid=(B, Hq, Sq // block_q, _n_kv_steps(Skv, block_q, block_kv, window)),
        in_specs=common_specs,
        out_specs=pl.BlockSpec((1, 1, block_q, D), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, Sq, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        interpret=_interpret(),
        name="flash_bwd_dq",
    )(qt, kt, vt, dot, lse, delta)

    # dkv kernels iterate q blocks innermost; index maps swap (i, j) roles,
    # and under a window the q axis carries the banded offset.
    n_q = Sq // block_q
    if window:
        def q_map(b, h, j, i):
            iv = _q_block_offset(j, block_q, block_kv) + i
            return (b, h, jnp.minimum(iv, n_q - 1), 0)
    else:
        def q_map(b, h, j, i):
            return (b, h, i, 0)
    dkv_specs = [
        pl.BlockSpec((1, 1, block_q, D), q_map),
        pl.BlockSpec((1, 1, block_kv, D), lambda b, h, j, i: (b, h // group, j, 0)),
        pl.BlockSpec((1, 1, block_kv, Dv), lambda b, h, j, i: (b, h // group, j, 0)),
        pl.BlockSpec((1, 1, block_q, Dv), q_map),
        pl.BlockSpec((1, 1, block_q, LANES), q_map),
        pl.BlockSpec((1, 1, block_q, LANES), q_map),
    ]
    dk_h, dv_h = pl.pallas_call(
        functools.partial(
            _bwd_dkv_kernel, scale=scale, block_q=block_q, block_kv=block_kv, causal=causal, window=window, sq=Sq
        ),
        grid=(B, Hq, Skv // block_kv, _n_q_steps(Sq, block_q, block_kv, window)),
        in_specs=dkv_specs,
        out_specs=[
            pl.BlockSpec((1, 1, block_kv, D), lambda b, h, j, i: (b, h, j, 0)),
            pl.BlockSpec((1, 1, block_kv, Dv), lambda b, h, j, i: (b, h, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, Hq, Skv, D), jnp.float32),
            jax.ShapeDtypeStruct((B, Hq, Skv, Dv), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_kv, D), jnp.float32),
            pltpu.VMEM((block_kv, Dv), jnp.float32),
        ],
        interpret=_interpret(),
        name="flash_bwd_dkv",
    )(qt, kt, vt, dot, lse, delta)

    # Sum GQA head groups back to the kv heads.
    dk = dk_h.reshape(B, Hkv, group, Skv, D).sum(axis=2).astype(k.dtype)
    dv = dv_h.reshape(B, Hkv, group, Skv, Dv).sum(axis=2).astype(v.dtype)
    return (
        dq.transpose(0, 2, 1, 3),
        dk.transpose(0, 2, 1, 3),
        dv.transpose(0, 2, 1, 3),
    )


# -- flash with exposed logsumexp (chunk-mergeable attention) ----------------
# The plain flash_attention path is this same custom_vjp with the lse
# output dropped (one implementation to keep in sync; a zero lse cotangent
# costs one subtract in bwd, noise next to the kernels).
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_lse(q, k, v, scale, causal, block_q, block_kv, window):
    out, lse = _fwd(q, k, v, scale=scale, causal=causal, block_q=block_q, block_kv=block_kv, window=window)
    return out, lse[..., 0]


def _flash_lse_fwd(q, k, v, scale, causal, block_q, block_kv, window):
    out, lse = _fwd(q, k, v, scale=scale, causal=causal, block_q=block_q, block_kv=block_kv, window=window)
    # Save lse de-replicated: [B, Hq, Sq] fp32 (2MB-scale) instead of the
    # kernel's [B, Hq, Sq, 128] layout (256MB-scale at flagship shapes) —
    # the lane-padded buffer lives only inside this fwd call (r1 OOM fix).
    lse_small = lse[..., 0]
    # checkpoint_name on the residuals: under the 'save_attn' remat policy
    # (models/transformer.py REMAT_POLICIES) these are stored across the
    # fwd/bwd boundary, so the branch backward rebuilds only the cheap
    # q/k/v projections and the forward flash kernel is never re-executed.
    # Under other policies the tags are inert. (Same mechanism as splash
    # attention's residual_checkpoint_name.)
    out_r = checkpoint_name(out, "flash_out")
    lse_r = checkpoint_name(lse_small, "flash_lse")
    return (out_r, lse_r), (q, k, v, out_r, lse_r)


def _flash_lse_bwd(scale, causal, block_q, block_kv, window, res, g):
    g_out, g_lse = g
    return _bwd(scale, causal, block_q, block_kv, window, res, g_out, g_lse=g_lse)


_flash_lse.defvjp(_flash_lse_fwd, _flash_lse_bwd)


def fit_block(seq_len: int, want: int) -> int:
    """Largest lane-aligned block <= `want` that divides seq_len.

    Scans multiples of 128 downward (clean Mosaic tiling; finds e.g. 768
    for seq 1536 under a 1024 request, or 512 for seq 1024 under a 768
    request). If no 128-multiple divides seq_len, falls back to a halving
    search whose result may be < 128 — flash_eligible treats that as
    ineligible and callers take the XLA path."""
    b = min(want, seq_len)
    b -= b % 128
    while b >= 128 and seq_len % b:
        b -= 128
    if b >= 128:
        return b
    b = max(1, min(want, seq_len))
    while seq_len % b:
        b //= 2
    return b


def flash_attention_with_lse(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    scale: Optional[float] = None,
    block_q: int = 512,
    block_kv: int = 512,
    window: Optional[int] = None,
) -> tuple:
    """flash_attention that also returns per-row logsumexp [B, Hq, Sq].

    The (out, lse) pair makes chunks mergeable with the online-softmax
    recurrence — ring attention combines per-ring-step chunk results this
    way (ops/ring_attention.py). Differentiable in both outputs. Block
    sizes self-fit to the sequence lengths (largest divisor <= requested),
    so any length flash_eligible admits runs without caller-side tuning.
    """
    B, Sq, Hq, D = q.shape
    Skv, Hkv = k.shape[1], k.shape[2]
    assert Hq % Hkv == 0, "num q heads must be a multiple of kv heads"
    block_q = fit_block(Sq, block_q)
    block_kv = fit_block(Skv, block_kv)
    # Degenerate fits (odd lengths halve all the way down) would compile a
    # pathologically fine grid — fail loudly instead; flash_eligible is the
    # caller-side gate with the same rule.
    assert block_q >= 128 and block_kv >= 128, (
        f"no usable flash block for seq lengths ({Sq},{Skv}); largest "
        f"fitting blocks ({block_q},{block_kv}) < 128 — gate calls with "
        "flash_eligible() and fall back to the XLA path"
    )
    if scale is None:
        scale = 1.0 / (D**0.5)
    if window is not None:
        assert causal, "sliding window requires causal attention"
        assert window > 0, f"window must be positive, got {window}"
    return _flash_lse(
        q, k, v, scale, causal, block_q, block_kv, int(window or 0)
    )


def flash_eligible(
    seq_len: int, head_dim: int, block_q: int, block_kv: int
) -> bool:
    """Single source of truth for when the Pallas kernel applies:
    long-enough sequence, lane-friendly head_dim (Mosaic pads 64→128 lanes;
    below 64 the pad waste dominates), and a usable block fit — the kernel
    self-fits blocks downward, but below 128 the grid overhead beats the
    XLA fallback."""
    return (
        seq_len >= 128
        and head_dim % 64 == 0
        and fit_block(seq_len, block_q) >= 128
        and fit_block(seq_len, block_kv) >= 128
    )


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    scale: Optional[float] = None,
    block_q: int = 512,
    block_kv: int = 512,
    window: Optional[int] = None,
    sink: Optional[jax.Array] = None,
) -> jax.Array:
    """Flash attention over [B, S, H, D] tensors (differentiable).

    Supports GQA (k/v may have fewer heads than q). Block sizes self-fit
    downward to the largest divisor of the sequence length (>= 128, else
    this raises — gate with flash_eligible); head_dim should be a multiple
    of 64.

    `sink` [Hq] float32 (Config.layer_sink): one more column of logit
    sink[h] in every row's softmax that gives no value. The kernels are
    the plain ones: with the row's logsumexp L, the sink's share of the
    denominator is exp(sink) / (exp(L) + exp(sink)), so the output is the
    plain one times sigmoid(L - sink), exactly; the gradients of q, k, v
    and of the sink follow through the lse output's own cotangent.
    """
    out, lse = flash_attention_with_lse(
        q, k, v, causal=causal, scale=scale,
        block_q=block_q, block_kv=block_kv, window=window,
    )
    if sink is None:
        return out
    keep = jax.nn.sigmoid(lse - sink.astype(jnp.float32)[None, :, None])
    return (
        out.astype(jnp.float32) * keep.transpose(0, 2, 1)[..., None]
    ).astype(out.dtype)


def flash_attention_on_mesh(q, k, v, mesh, q_spec, kv_spec, **kw):
    """flash_attention under a device mesh.

    GSPMD cannot partition a Mosaic kernel ("Mosaic kernels cannot be
    automatically partitioned"), so on a mesh of more than one device the
    call runs under shard_map: the kernel is independent per (batch row,
    head), batch rows split over the data axes and heads over the tensor
    axis as `q_spec` / `kv_spec` say (PartitionSpecs over [B, S, H, D]
    from the active logical axis rules). Sequence stays whole — sequence
    parallelism is ring attention's job. Heads are split only when q and
    kv heads split the same way, so every local q head still finds its kv
    group; a dimension its axes do not divide evenly stays whole. Inside
    an enclosing manual region (the 1F1B pipeline's stages are the one
    such region in the package) the caller's axes are already manual and
    the kernel is called as is.
    """
    from jax.sharding import PartitionSpec as P

    from luminaai_tpu.parallel.mesh import shard_map

    sink = kw.pop("sink", None)
    if mesh is None or mesh.size == 1 or jax.sharding.get_abstract_mesh(
    ).manual_axes:
        return flash_attention(q, k, v, sink=sink, **kw)

    def fits(axes, *dims):
        # shard_map wants even splits; GSPMD would pad. A dim the axes do
        # not divide (a microbatch smaller than the data axes) stays whole.
        names = (axes,) if isinstance(axes, str) else tuple(axes or ())
        n = math.prod(mesh.shape[a] for a in names)
        return axes if all(d % n == 0 for d in dims) else None

    heads = q_spec[2] if q_spec[2] == kv_spec[2] else None
    spec = P(
        fits(q_spec[0], q.shape[0]), None,
        fits(heads, q.shape[2], k.shape[2]), None,
    )
    if sink is not None:
        # A logit a query head: split as the heads are.
        return shard_map(
            lambda q, k, v, b: flash_attention(q, k, v, sink=b, **kw),
            mesh, in_specs=(spec, spec, spec, P(spec[2])), out_specs=spec,
            check_vma=False,
        )(q, k, v, sink)
    return shard_map(
        lambda q, k, v: flash_attention(q, k, v, **kw),
        mesh, in_specs=(spec, spec, spec), out_specs=spec, check_vma=False,
    )(q, k, v)

