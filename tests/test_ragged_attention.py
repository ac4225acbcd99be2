"""Ragged paged attention: kernel/reference parity + LaneMeta contracts.

Three layers of evidence, innermost out:
  1. the pure-XLA reference reproduces the dense per-lane decode mask
     BIT-exactly on resident rows (it is the same einsum with the same
     mask, restricted by residency);
  2. the Pallas kernel (interpret mode on CPU) matches the reference
     within float tolerance across lengths, windows, GQA groups, and
     permuted page tables;
  3. the KV pool's page-table/length views honor the no-aliasing
     contract the kernel's indirection depends on.
Stream-level parity (greedy tokens through the full model) lives in
tests/test_inference.py.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from luminaai_tpu.ops.ragged_paged_attention import (
    LaneMeta,
    implied_page_size,
    lane_attention,
    lane_attention_eligible,
    lane_attention_engaged,
    paged_attention,
    ragged_paged_attention_xla,
)


def _dense_per_lane(q, k, v, pos, window=None):
    """The legacy dense per-lane decode mask (models/layers.py) — the
    oracle the ragged reference must reproduce bit-for-bit."""
    B, Sq, n_q, d = q.shape
    Skv, n_kv = k.shape[1], k.shape[2]
    g = n_q // n_kv
    qg = q.reshape(B, Sq, n_kv, g, d)
    scale = 1.0 / jnp.sqrt(d).astype(jnp.float32)
    logits = (
        jnp.einsum("bqhgd,bkhd->bhgqk", qg, k).astype(jnp.float32) * scale
    )
    qp = pos[:, None, None] + jnp.arange(Sq)[None, :, None]
    kp = jnp.arange(Skv)[None, None, :]
    mask = kp <= qp
    if window is not None:
        mask = jnp.logical_and(mask, qp - kp < window)
    logits = jnp.where(mask[:, None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, v)
    return out.reshape(B, Sq, n_q, d)


def _rand_qkv(rng, B, C, Hq, Hkv, D):
    q = jnp.asarray(rng.randn(B, 1, Hq, D), jnp.float32)
    k = jnp.asarray(rng.randn(B, C, Hkv, D), jnp.float32)
    v = jnp.asarray(rng.randn(B, C, Hkv, D), jnp.float32)
    return q, k, v


@pytest.mark.parametrize(
    "B,P,ps,Hq,Hkv,D,window",
    [
        (3, 4, 8, 2, 1, 64, None),
        (2, 2, 16, 4, 2, 64, None),
        (3, 4, 8, 2, 2, 128, 20),
        (1, 8, 8, 1, 1, 64, None),
        (4, 4, 32, 8, 2, 64, 40),
    ],
)
def test_kernel_and_reference_match_dense(B, P, ps, Hq, Hkv, D, window):
    rng = np.random.RandomState(B * 100 + P)
    C = P * ps
    q, k, v = _rand_qkv(rng, B, C, Hq, Hkv, D)
    lengths = jnp.asarray(rng.randint(1, C + 1, size=(B,)), jnp.int32)
    meta = LaneMeta(lengths=lengths, window=window, page_size=ps)

    ref = ragged_paged_attention_xla(q, k, v, meta)
    dense = _dense_per_lane(q, k, v, lengths - 1, window=window)
    # The reference IS the dense mask restricted by residency: for
    # decode (qp = lengths-1) the restrictions coincide, so bit-exact.
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(dense))

    # Off the chip the kernel is interpreted at any shape, under 'ragged'.
    assert lane_attention_engaged("ragged", 1, Hq, Hkv, D, ps)
    out = lane_attention(q, k, v, meta)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=2e-6, rtol=2e-5
    )


def test_zero_length_lane_is_safe():
    """lengths == 0 marks a lane with nothing attendable: both
    implementations must return finite garbage, never NaN (the decode
    step runs free/mid-prefill slots through the same executable and
    discards their outputs host-side)."""
    rng = np.random.RandomState(0)
    q, k, v = _rand_qkv(rng, 2, 32, 2, 1, 64)
    meta = LaneMeta(
        lengths=jnp.asarray([0, 17], jnp.int32), page_size=8
    )
    for fn in (ragged_paged_attention_xla, lane_attention):
        out = np.asarray(fn(q, k, v, meta))
        assert np.isfinite(out).all(), fn.__name__


def test_page_table_indirection_matches_physical_gather():
    """A permuted page table must read exactly the pages a physical
    gather would have moved — in the reference AND the kernel (whose
    BlockSpec index maps chase the table directly)."""
    rng = np.random.RandomState(1)
    B, P, ps, Hq, Hkv, D = 2, 4, 8, 2, 1, 64
    C = P * ps
    q, k, v = _rand_qkv(rng, B, C, Hq, Hkv, D)
    perm = jnp.asarray(
        np.stack([rng.permutation(P) for _ in range(B)]), jnp.int32
    )
    lengths = jnp.asarray([C, C - 5], jnp.int32)
    meta = LaneMeta(
        lengths=lengths, page_table=perm, page_size=ps,
        identity_pages=False,
    )
    idx = perm[:, :, None, None, None]
    kg = jnp.take_along_axis(
        k.reshape(B, P, ps, Hkv, D), idx, axis=1
    ).reshape(B, C, Hkv, D)
    vg = jnp.take_along_axis(
        v.reshape(B, P, ps, Hkv, D), idx, axis=1
    ).reshape(B, C, Hkv, D)
    ref = ragged_paged_attention_xla(
        q, kg, vg, LaneMeta(lengths=lengths, page_size=ps)
    )
    via_table_xla = ragged_paged_attention_xla(q, k, v, meta)
    np.testing.assert_array_equal(
        np.asarray(via_table_xla), np.asarray(ref)
    )
    via_table_kernel = lane_attention(q, k, v, meta)
    np.testing.assert_allclose(
        np.asarray(via_table_kernel), np.asarray(ref),
        atol=2e-6, rtol=2e-5,
    )


def test_prefill_positions_mask_padding_rows():
    """Multi-row (chunked-prefill) reference semantics: -1-marked
    padding rows attend nothing; live rows reproduce the dense per-lane
    prefill mask."""
    rng = np.random.RandomState(2)
    B, C, Hq, Hkv, D, Sq = 2, 64, 2, 1, 32, 8
    q = jnp.asarray(rng.randn(B, Sq, Hq, D), jnp.float32)
    k = jnp.asarray(rng.randn(B, C, Hkv, D), jnp.float32)
    v = jnp.asarray(rng.randn(B, C, Hkv, D), jnp.float32)
    start, L = 16, 21  # final chunk: 5 live rows, 3 padding
    pos = start + np.arange(Sq)
    positions = jnp.asarray(
        np.where(pos < L, pos, -1)[None].repeat(B, 0), jnp.int32
    )
    meta = LaneMeta(
        lengths=jnp.full((B,), L, jnp.int32), page_size=8
    )
    out = ragged_paged_attention_xla(q, k, v, meta, positions=positions)
    dense = _dense_per_lane(
        q, k, v, jnp.full((B,), start, jnp.int32)
    )
    live = L - start
    np.testing.assert_array_equal(
        np.asarray(out[:, :live]), np.asarray(dense[:, :live])
    )
    assert np.isfinite(np.asarray(out)).all()


def test_dispatcher_gating():
    """The kernel takes a decode batch (one q row a lane) alone; on a
    TPU by the shapes (lane_attention_eligible), under 'ragged' and
    'ragged_xla' alike; off it only 'ragged' runs it, interpreted, so a
    CPU program under 'ragged_xla' is the XLA reference's (interpret
    mode costs interpreter time) and 'dense' never sees it."""
    # the cells' shapes: command-a-plus, Jamba2, the latent entry, OLMoE
    assert lane_attention_eligible(128, 8, 128, 128)
    assert lane_attention_eligible(20, 1, 128, 128)
    assert lane_attention_eligible(64, 1, 640, 128)
    assert lane_attention_eligible(16, 16, 128, 128)  # MHA, since PR 47
    assert lane_attention_eligible(32, 8, 128, 128)  # a group of 4
    # the layout's terms, and nothing else
    assert not lane_attention_eligible(128, 8, 64, 128)  # half a lane
    assert not lane_attention_eligible(128, 8, 128, 12)  # unaligned page
    # 4 k/v heads: one 128-lane tile a head flattens for free, two do not
    assert lane_attention_eligible(64, 4, 128, 128)
    assert not lane_attention_eligible(64, 4, 256, 128)
    assert lane_attention_engaged("ragged", 1, 2, 1, 48, 8)
    assert not lane_attention_engaged("ragged", 4, 2, 1, 64, 8)  # multi-row q
    assert not lane_attention_engaged("ragged_xla", 1, 128, 8, 128, 128)
    assert not lane_attention_engaged("dense", 1, 128, 8, 128, 128)
    rng = np.random.RandomState(3)
    q, k, v = _rand_qkv(rng, 2, 32, 2, 1, 48)
    meta = LaneMeta(lengths=jnp.asarray([9, 30], jnp.int32), page_size=8)
    out = paged_attention(q, k, v, meta, backend="ragged_xla")
    ref = ragged_paged_attention_xla(q, k, v, meta)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    via_kernel = paged_attention(q, k, v, meta, backend="ragged")
    assert not np.array_equal(np.asarray(via_kernel), np.asarray(ref))
    np.testing.assert_allclose(
        np.asarray(via_kernel), np.asarray(ref), atol=2e-6, rtol=2e-5
    )


def test_implied_page_size():
    assert implied_page_size(512) == 128
    assert implied_page_size(192) == 64
    assert implied_page_size(48) == 16
    assert implied_page_size(20) == 20  # nothing aligned divides


# -- KV pool metadata views (the contract the indirection rests on) --------
def test_pool_views_and_no_alias_across_realloc():
    """page_table_array()/lengths_array() are device-transferable
    SNAPSHOTS, and free/realloc can never alias a live lane's pages: a
    live slot's table row is identity over its own page axis and is
    never mutated by other slots' alloc/free churn."""
    from luminaai_tpu.inference.kv_pool import PagedKVPool

    pool = PagedKVPool(None, num_slots=3, pages=4, page_size=8)
    ident = np.arange(4, dtype=np.int32)

    a = pool.alloc()
    pool.lengths[a] = 17
    table_live = pool.page_table_array()[a].copy()
    np.testing.assert_array_equal(table_live, ident)

    # Churn the OTHER slots hard while `a` stays live.
    for _ in range(5):
        b = pool.alloc()
        c = pool.alloc()
        pool.lengths[b] = 9
        pool.free(b)
        pool.free(c)
    np.testing.assert_array_equal(pool.page_table_array()[a], table_live)
    assert pool.lengths_array()[a] == 17

    # The view is a copy: mutating it cannot corrupt pool accounting.
    view = pool.page_table_array()
    view[a] = 99
    np.testing.assert_array_equal(pool.page_table_array()[a], ident)

    # Realloc of a freed slot re-issues ITS OWN identity row (fresh, not
    # whatever a previous occupant left) and zeroed length.
    pool.free(a)
    pool.page_tables[a] = 7  # simulate a stale retargeted row
    a2 = pool.alloc()
    assert a2 == a  # LIFO free-list re-issues the warmest slot
    np.testing.assert_array_equal(pool.page_table_array()[a2], ident)
    assert pool.lengths_array()[a2] == 0

    # Dtypes are what the kernel's scalar-prefetch operands want.
    assert pool.page_table_array().dtype == np.int32
    assert pool.lengths_array().dtype == np.int32
