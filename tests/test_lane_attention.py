"""lane_attention (ops/ragged_paged_attention.py): a decode batch's attention
over the pool read in place, one Pallas kernel (interpreted on the CPU) for
whole pages and rings of pages, skipping by lane and by key block.

  - against the two XLA references (ragged_paged_attention_xla over whole
    pages, banded_attention_xla + ring_key_positions over a ring): lengths
    0, 1, a page's edge, a block's edge, exactly the extent; a window; a
    real page table and global (slot, page) ids; a ring not yet full, full,
    wrapped once and twice, and ending inside a page (stale rows behind the
    newest); 16 query heads a k/v head over 8, and 20 over 1; MHA (16 over
    16 at OLMoE's own sizes, 4 over 4) and a group of 4 (32 over 8) over
    lengths 0 / 1 / a page's and a block's edge / the extent;
  - its plan: a lane that is not stepped and a block with no key in the
    band keep the index of the block fetched last (no DMA), and the host's
    count of it (StepwiseDecoder._kv_rows_of, the two counters) against a
    hand count for a tick of three lanes;
  - who runs it: the rule by shape (every shape a benchmark cell serves,
    by cell), and off the chip 'ragged' alone; an MHA tick lowered for a
    TPU calls the kernel and makes no slice of the pool; the kernel's
    program at the other cells' shapes is the one it was (PR 47).
"""

import hashlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from luminaai_tpu.config import Config
from luminaai_tpu.inference.generate import (GREEDY_SAMPLE_KEY,
                                             GenerationEngine)
from luminaai_tpu.models.transformer import LuminaTransformer
from luminaai_tpu.ops import ragged_paged_attention as rpa
from luminaai_tpu.ops.ragged_paged_attention import (
    LaneMeta, banded_attention_xla, lane_attention, lane_attention_eligible,
    lane_attention_engaged, lane_blocks, lane_pages_held, lane_plan,
    ragged_paged_attention_xla, ring_key_positions)
from luminaai_tpu.parallel.sharding import unbox


def _qkv(seed, B, T, C, Hq, Hkv, D, dtype=jnp.float32):
    rng = np.random.RandomState(seed)
    return (jnp.asarray(rng.randn(B, 1, Hq, D), dtype),
            jnp.asarray(rng.randn(T, C, Hkv, D), dtype),
            jnp.asarray(rng.randn(T, C, Hkv, D), dtype))


def _close(got, want, live, tol):
    got, want = (np.asarray(a, np.float32)[np.asarray(live)]
                 for a in (got, want))
    np.testing.assert_allclose(got, want, atol=tol, rtol=tol)


# Pages of 8 rows, 16 a lane; k/v heads of 64 in float32: _LANE_BLOCK_BYTES
# holds all 16, so the block is cut to 4 pages (32 rows) to have edges.
PS, PAGES = 8, 16
ROWS = PS * PAGES


@pytest.fixture
def four_page_blocks(monkeypatch):
    monkeypatch.setattr(rpa, "_LANE_BLOCK_BYTES", 4 * PS * 2 * 64 * 4)


@pytest.mark.parametrize("window", [None, 20], ids=["full", "window_20"])
@pytest.mark.parametrize("lengths,extent", [
    ([0, 1, 0], ROWS),            # nothing, one row, nothing
    ([8, 9, 7], ROWS),            # a page's edge, from both sides
    ([32, 33, 31], ROWS),         # a block's edge
    ([ROWS, 0, ROWS - 1], ROWS),  # exactly the extent
    ([64, 5, 0], 64),             # a narrower extent, filled
    ([0, 0, 0], ROWS),            # no lane stepped
], ids=["one_row", "page_edge", "block_edge", "the_extent", "extent_64",
        "none_stepped"])
def test_whole_pages_match_the_xla_reference(four_page_blocks, lengths,
                                             extent, window):
    q, k, v = _qkv(len(lengths) + extent, 3, 3, ROWS, 4, 2, 64)
    meta = LaneMeta(lengths=jnp.asarray(lengths, jnp.int32), window=window,
                    page_size=PS, extent=extent)
    assert lane_blocks(extent // PS, PS, 2, 64, 4) == (4, 4)
    out = lane_attention(q, k, v, meta)
    assert np.isfinite(np.asarray(out)).all()  # a dead lane: finite, unread
    want = ragged_paged_attention_xla(q, k[:, :extent], v[:, :extent], meta)
    _close(out, want, np.asarray(lengths) > 0, 2e-5)


@pytest.mark.parametrize("Hq,Hkv,D,ps,dtype,tol", [
    (128, 8, 128, 16, jnp.bfloat16, 2e-2),  # 16 query heads a k/v head
    (20, 1, 128, 128, jnp.bfloat16, 2e-2),  # 20 over one
    (6, 2, 16, 4, jnp.float32, 2e-5),       # the tick test's tiny heads
    (32, 2, 128, 128, jnp.bfloat16, 2e-2),  # 16 over each of two
], ids=["group16_of_8", "group20_of_1", "group3_of_2", "group16_of_2"])
def test_head_groups_match_the_xla_reference(Hq, Hkv, D, ps, dtype, tol):
    C = 8 * ps
    lengths = [C, 0, 3 * ps + 1, 1]
    q, k, v = _qkv(Hq, 4, 4, C, Hq, Hkv, D, dtype)
    meta = LaneMeta(lengths=jnp.asarray(lengths, jnp.int32), page_size=ps)
    out = lane_attention(q, k, v, meta)
    assert out.shape == q.shape and out.dtype == q.dtype
    _close(out, ragged_paged_attention_xla(q, k, v, meta),
           np.asarray(lengths) > 0, tol)


# One query head a k/v head (MHA) and a group of 4, two pages a block:
# (Hq, Hkv, D, page, dtype, tol, _LANE_BLOCK_BYTES or None for the rule's own)
SMALL_GROUPS = {
    # OLMoE's sizes: a page of 128 rows is 2,048 score columns, twice a
    # tile's, so lane_blocks halves the 2 MB block's 4 pages itself.
    "mha_16_of_16": (16, 16, 128, 128, jnp.bfloat16, 2e-2, None),
    "mha_4_of_4": (4, 4, 32, 8, jnp.float32, 2e-5, 2 * 8 * 4 * 32 * 4),
    "group4_32_of_8": (32, 8, 128, 16, jnp.bfloat16, 2e-2,
                       2 * 16 * 8 * 128 * 2),
}


@pytest.mark.parametrize("lengths", [
    lambda ps: [0, 1, 0, ps],                      # nothing, a row, a page
    lambda ps: [ps + 1, ps - 1, 0, 2 * ps],        # a page's, a block's edge
    lambda ps: [2 * ps + 1, 2 * ps - 1, 8 * ps, 0],  # and the extent
], ids=["one_row", "page_and_block_edge", "block_edge_and_the_extent"])
@pytest.mark.parametrize("shape", list(SMALL_GROUPS))
def test_mha_and_small_groups_match_the_xla_reference(monkeypatch, shape,
                                                      lengths):
    """The block-diagonal matmul at any group: every query head against
    every k/v head's keys, the constant mask keeps a head's own; lanes not
    stepped (length 0) among the stepped ones."""
    Hq, Hkv, D, ps, dtype, tol, block_bytes = SMALL_GROUPS[shape]
    if block_bytes:
        monkeypatch.setattr(rpa, "_LANE_BLOCK_BYTES", block_bytes)
    C, lengths = 8 * ps, lengths(ps)
    assert lane_blocks(8, ps, Hkv, D, jnp.dtype(dtype).itemsize)[0] == 2
    q, k, v = _qkv(Hq + sum(lengths), 4, 4, C, Hq, Hkv, D, dtype)
    meta = LaneMeta(lengths=jnp.asarray(lengths, jnp.int32), page_size=ps,
                    extent=C)
    out = lane_attention(q, k, v, meta)
    assert out.shape == q.shape and out.dtype == q.dtype
    assert np.isfinite(np.asarray(out, np.float32)).all()
    _close(out, ragged_paged_attention_xla(q, k, v, meta),
           np.asarray(lengths) > 0, tol)


@pytest.mark.parametrize("window", [None, 11])
def test_a_real_page_table_is_chased_a_page_a_step(window):
    """A lane's own permutation of its pages: the reference gathers, the
    kernel's index map reads each logical page where the table says."""
    rng = np.random.RandomState(5)
    B, P = 3, 6
    q, k, v = _qkv(6, B, B, P * PS, 4, 2, 32)
    table = jnp.asarray(np.stack([rng.permutation(P) for _ in range(B)]),
                        jnp.int32)
    lengths = [P * PS, 0, 2 * PS + 3]
    meta = LaneMeta(lengths=jnp.asarray(lengths, jnp.int32), window=window,
                    page_table=table, page_size=PS, identity_pages=False)
    held, slot, blk, per_block, per_tile = lane_plan(meta, B, P * PS, 2, 32, 4)
    assert (per_block, per_tile) == (1, 1)
    live = np.asarray(held).reshape(-1) >= 0
    np.testing.assert_array_equal(
        np.asarray(blk)[live], np.asarray(table).reshape(-1)[live])
    _close(lane_attention(q, k, v, meta),
           ragged_paged_attention_xla(q, k, v, meta),
           np.asarray(lengths) > 0, 2e-5)


@pytest.mark.parametrize("extent", [None, 3 * PS])
def test_global_ids_read_another_slots_pages(extent):
    """Prefix-cache addressing: table entries are (slot, page) ids into the
    whole pool, two arena slots behind the two lanes; both lanes share the
    arena's first two pages and keep the rest in their own slot."""
    B, T, P = 2, 4, 5
    q, k, v = _qkv(7, B, T, P * PS, 4, 2, 32)
    table = np.stack([b * P + np.arange(P) for b in range(B)])
    table[:, :2] = 2 * P + np.arange(2)  # the shared prefix: slot 2
    table[1, 2] = 3 * P + 4              # and one page of slot 3
    lengths = [3 * PS, 2 * PS + 5]
    meta = LaneMeta(lengths=jnp.asarray(lengths, jnp.int32), page_size=PS,
                    page_table=jnp.asarray(table, jnp.int32), extent=extent,
                    identity_pages=False, global_pages=True)
    held, slot, blk, _, _ = lane_plan(meta, B, P * PS, 2, 32, 4)
    assert set(np.asarray(slot).tolist()) == {0, 2, 3}
    _close(lane_attention(q, k, v, meta),
           ragged_paged_attention_xla(q, k, v, meta), [True, True], 2e-5)


# A ring of 5 pages of 8 rows under a window of 20 (a chunk of 6 rows is
# written before it is read: ceil(26 / 8) + 1); logical page j lives at
# ring page j mod 5, as the pool lays it.
WINDOW, N_RING, TABLE_PAGES = 20, 5, 16
RING = N_RING * PS


def _ring_of(k_seq, lengths):
    """The ring as a lane wrote it: row p at page table[p // ps], later
    laps over earlier ones; rows past lengths - 1 keep what an earlier lap
    left there."""
    B, _, Hkv, D = k_seq.shape
    ring = np.zeros((B, RING, Hkv, D), np.float32)
    for b, n in enumerate(lengths):
        for p in range(n):
            ring[b, ((p // PS) % N_RING) * PS + p % PS] = k_seq[b, p]
    return jnp.asarray(ring)


@pytest.mark.parametrize("lengths", [
    [17, 0, 3],                       # not yet full
    [RING, RING - 1, 1],              # full, to the row
    [RING + 1, RING + PS, 2 * RING - 1],          # wrapped once
    [2 * RING + 13, 3 * RING, 2 * RING + 1],      # twice, and three times
    [RING + 6 * 3 + 5, 6 * 11, 6 * 7 + 2],        # ends of chunks of 6
], ids=["not_full", "full", "wrapped_once", "wrapped_twice", "chunk_ends"])
def test_a_ring_matches_both_xla_references(lengths):
    B, Hq, Hkv, D = 3, 4, 2, 32
    rng = np.random.RandomState(sum(lengths))
    q = jnp.asarray(rng.randn(B, 1, Hq, D), jnp.float32)
    k_seq = rng.randn(B, TABLE_PAGES * PS, Hkv, D).astype(np.float32)
    v_seq = rng.randn(B, TABLE_PAGES * PS, Hkv, D).astype(np.float32)
    k, v = _ring_of(k_seq, lengths), _ring_of(v_seq, lengths)
    table = jnp.asarray(np.broadcast_to(
        np.arange(TABLE_PAGES, dtype=np.int32) % N_RING, (B, TABLE_PAGES)))
    L = jnp.asarray(lengths, jnp.int32)
    meta = LaneMeta(lengths=L, window=WINDOW, page_size=PS, ring_table=table)
    out = lane_attention(q, k, v, meta, ring=True)
    live = np.asarray(lengths) > 0
    # the ring read in place by XLA, as GQAttention._ring_lanes does
    kpos = ring_key_positions(table, L, PS, RING)
    _close(out, banded_attention_xla(q, k, v, (L - 1)[:, None], kpos, WINDOW),
           live, 2e-5)
    # and the lane's whole history under the window, no ring anywhere
    whole = LaneMeta(lengths=L, window=WINDOW, page_size=PS)
    _close(out, ragged_paged_attention_xla(
        q, jnp.asarray(k_seq), jnp.asarray(v_seq), whole), live, 2e-5)


def test_a_step_that_reads_nothing_keeps_the_last_blocks_index(
        four_page_blocks):
    """Lane 0 is not stepped, lane 1 holds 40 rows (blocks 0 and 1 of 4),
    lane 2 none, lane 3 100 rows under a window of 20 (block 2 and 3 hold
    rows 81..99): every other step names the block the step before it
    named, which Pallas does not fetch again."""
    meta = LaneMeta(lengths=jnp.asarray([0, 40, 0, 100], jnp.int32),
                    window=None, page_size=PS)
    held, slot, blk, per_block, _ = lane_plan(meta, 4, ROWS, 2, 64, 4)
    assert per_block == 4 and held.shape == (4, PAGES)
    np.testing.assert_array_equal(
        np.asarray(held)[1], np.where(np.arange(PAGES) < 5, np.arange(PAGES), -1))
    steps = list(zip(np.asarray(slot).tolist(), np.asarray(blk).tolist()))
    assert steps == (
        [(1, 0)] * 4                          # lane 0: the first live block
        + [(1, 0), (1, 1), (1, 1), (1, 1)]    # lane 1: two live, two kept
        + [(1, 1)] * 4                        # lane 2
        + [(3, 0), (3, 1), (3, 2), (3, 3)])   # lane 3, no window: all four
    windowed = lane_plan(meta.replace(window=20), 4, ROWS, 2, 64, 4)
    steps = list(zip(*(np.asarray(a).tolist() for a in windowed[1:3])))
    assert steps[12:] == [(1, 1), (1, 1), (3, 2), (3, 3)]
    # one rule for the kernel's plan and for the host's count of it
    np.testing.assert_array_equal(
        np.asarray(windowed[0]),
        lane_pages_held(np.asarray([0, 40, 0, 100]), PS, PAGES, 20, xp=np))


# Every shape a cell of BENCHMARK.json serves, by cell: (query heads, k/v
# heads, head size, page). The rule is the pool's layout alone (PR 47 took
# the group term out on a measurement: PERF.md section 6).
SERVED = {
    "olmoe-serve-chat": (16, 16, 128, 128),            # MHA
    "olmoe-serve-batch": (16, 16, 128, 128),
    "jamba2-3b-serve-burst": (20, 1, 128, 128),        # one k/v head
    "command-a-plus-serve-mixed": (128, 8, 128, 128),  # 16 a k/v head
    "kimi-k2-7-code-serve-longctx": (64, 1, 640, 128),  # one latent row
    "mistral-7b-v03, were it served": (32, 8, 128, 128),  # a group of 4
    # (PR 50) 4 k/v heads of ONE 128-lane tile: the chip tiles such an
    # array (4, 128), rows one after another, so it flattens for free
    "mimo-v2-flash-serve-reason, full layers": (64, 4, 128, 128),
    # (PR 63) and 2 heads of one 128-lane tile alike: tiled (2, 128)
    "nemotron-3-super-serve-reason": (32, 2, 128, 128),
}
NOT_BY_LAYOUT = {
    "the flagship preset: half a lane a head": (16, 8, 64, 128),
    "half a tile a row, two tiles wide": (64, 4, 256, 128),
    "two k/v heads, two tiles wide": (16, 2, 256, 128),
    "an unaligned page": (128, 8, 128, 12),
    "a page short of whole lanes": (16, 8, 128, 8),
}


@pytest.mark.parametrize("cell", list(SERVED))
def test_the_rule_serves_every_cells_shape(monkeypatch, cell):
    shape = SERVED[cell]
    assert lane_attention_eligible(*shape)
    # on the CPU: 'ragged' interprets it at any shape, 'ragged_xla' never
    assert lane_attention_engaged("ragged", 1, *shape)
    assert not lane_attention_engaged("ragged_xla", 1, *shape)
    # on a TPU: both strings, one query row a lane
    monkeypatch.setattr(rpa, "_interpret", lambda: False)
    for backend in ("ragged", "ragged_xla"):
        assert lane_attention_engaged(backend, 1, *shape)
        assert not lane_attention_engaged(backend, 2, *shape)
    assert not lane_attention_engaged("dense", 1, *shape)


@pytest.mark.parametrize("why", list(NOT_BY_LAYOUT))
def test_the_rule_is_the_layout_and_off_the_chip_ragged_alone(monkeypatch,
                                                              why):
    shape = NOT_BY_LAYOUT[why]
    assert not lane_attention_eligible(*shape)
    assert lane_attention_engaged("ragged", 1, *shape)  # interpreted
    monkeypatch.setattr(rpa, "_interpret", lambda: False)
    for backend in ("ragged", "ragged_xla"):
        assert not lane_attention_engaged(backend, 1, *shape)


def test_the_number_of_query_heads_is_in_no_term():
    for n_kv in (1, 8, 16, 32):
        assert all(lane_attention_eligible(g * n_kv, n_kv, 128, 128)
                   for g in (1, 2, 4, 7, 8, 16))


@pytest.mark.parametrize("pages,row,want", [
    # OLMoE (16 k/v heads: a page is two tiles' columns): 2 pages a block
    (4, (16, 128), (2, 1)), (8, (16, 128), (2, 1)), (16, (16, 128), (2, 1)),
    # command-a-plus: 8 pages of whole pages, a ring of 35 in 5 x 7
    (128, (8, 128), (8, 1)), (16, (8, 128), (8, 1)), (35, (8, 128), (7, 1)),
    # Jamba2: the extent whole, tiles of 8 pages
    (8, (1, 128), (8, 8)), (16, (1, 128), (16, 8)),
    # the latent entry: 12 pages would fit, 8 divide the extent
    (256, (1, 640), (8, 8)), (16, (1, 640), (8, 8)),
], ids=lambda x: str(x).replace(" ", ""))
def test_blocks_follow_the_rows_bytes_as_they_did(pages, row, want):
    """lane_blocks at every served row, bf16: what the parent gave for the
    shapes it ran (PR 47 may not move them), and OLMoE's."""
    assert lane_blocks(pages, 128, *row, 2) == want
    assert lane_blocks(pages, 128, *row, 2, chased=True) == (1, 1)


class _Tok:
    vocab_size = 64
    eos_token_id = pad_token_id = im_end = 65

    class backend:
        @staticmethod
        def encode(text):
            return [3 + (ord(c) % 50) for c in text]

    @staticmethod
    def decode(tokens):
        return " ".join(str(t) for t in tokens)


def _decoder(slots=3, **over):
    """Three lanes of 64 rows in pages of 4 over window, window, full
    layers (window 8, chunks of 6: a ring of 5 pages = 20 rows)."""
    kw = dict(
        vocab_size=64, hidden_size=32, num_layers=3, num_heads=4,
        num_kv_heads=2, intermediate_size=48, seq_length=64,
        layer_windows=(8, 8, None), precision="fp32",
        use_flash_attention=False, use_stable_embedding=False,
        scan_layers=False, prefill_chunk_size=6, attention_backend="ragged",
        max_new_tokens=8,
    )
    kw.update(over)
    cfg = Config(**kw)
    cfg.validate()
    model = LuminaTransformer(cfg)
    params = unbox(jax.jit(model.init)(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32))["params"])
    engine = GenerationEngine(model, params, _Tok(), cfg)
    return engine.make_stepwise(num_slots=slots, page_size=4,
                                max_slot_tokens=64, prefill_chunk_tokens=6)


def test_the_host_counts_what_a_tick_of_three_lanes_reads():
    """Lane 0 writes row 2 (3 rows held), lane 1 row 29 (30 held: its ring
    of 20 rows has wrapped), lane 2 is not stepped; no chunk rides. The
    tick's extent is 32 rows = 8 pages. By hand, pages of 4 rows:
    the full layer (blocks of 8 pages: one a lane): lanes 0 and 1 fetch
    theirs, 2 x 32 rows, 2 of 3 steps live;
    a window layer (ring of 5 pages, one block): 2 x 20 rows, 2 of 3 live,
    and there are two of them."""
    dec = _decoder()
    assert dec._lane_kernel and dec.pool.ring_pages == 5
    assert lane_blocks(8, 4, 2, 8, 4) == (8, 8)
    assert lane_blocks(5, 4, 2, 8, 4) == (5, 5)
    pos = np.asarray([2, 29, 11], np.int32)
    live = np.asarray([True, True, False])
    dec._kv_rows_of(32, pos, live, None)
    assert dec.kv_global_rows == 2 * 32
    assert dec.kv_window_rows == 2 * (2 * 20)
    assert dec.lane_attention_blocks == 3 + 2 * 3
    assert dec.lane_attention_blocks_live == 2 + 2 * 2
    # XLA's count of the same tick (the kernel not engaged): every lane up
    # to the extent, every ring whole.
    xla = _decoder(attention_backend="ragged_xla")
    assert not xla._lane_kernel
    xla._kv_rows_of(32, pos, live, None)
    assert (xla.kv_global_rows, xla.kv_window_rows) == (3 * 32, 2 * 3 * 20)
    assert xla.lane_attention_blocks == xla.lane_attention_blocks_live == 0


def test_smaller_blocks_skip_inside_a_lane(monkeypatch):
    """The same tick with room for two pages a block: the full layer's 8
    pages go in four blocks, of which lane 0's 3 rows are one and lane 1's
    30 rows all four; a ring of five pages has no divisor but one, so it
    goes a page a step: lane 0 sees its page 0, lane 1 (window 8) rows
    22..29, logical pages 5, 6, 7, three of the ring's five."""
    monkeypatch.setattr(rpa, "_LANE_BLOCK_BYTES", 2 * 4 * 2 * 8 * 4)
    dec = _decoder()
    dec._kv_rows_of(32, np.asarray([2, 29, 11], np.int32),
                    np.asarray([True, True, False]), None)
    assert dec.kv_global_rows == (1 + 4) * 8
    assert (dec.lane_attention_blocks, dec.lane_attention_blocks_live) == (
        3 * 4 + 2 * 3 * 5, 5 + 2 * (1 + 3))
    assert dec.kv_window_rows == 2 * (1 + 3) * 4


def test_the_host_counts_an_mha_tick_of_three_lanes(monkeypatch):
    """One query head a k/v head, three full layers, pages of 4 rows:
    lane 0 holds 3 rows, lane 1 30, lane 2 is never stepped; the tick's
    extent is 32 rows = 8 pages, two pages a block (4 blocks a lane). By
    hand, a layer: lane 0 fetches its first block (8 rows), lane 1 all
    four (32 rows), lane 2 none: 5 of 12 grid steps live, 40 rows."""
    mha = dict(num_heads=4, num_kv_heads=4, layer_windows=None)
    monkeypatch.setattr(rpa, "_LANE_BLOCK_BYTES", 2 * 4 * 4 * 8 * 4)
    dec = _decoder(**mha)
    assert dec._lane_kernel and dec.pool.ring_pages == 0
    assert lane_blocks(8, 4, 4, 8, 4) == (2, 2)
    pos = np.asarray([2, 29, 11], np.int32)
    live = np.asarray([True, True, False])
    dec._kv_rows_of(32, pos, live, None)
    assert dec.kv_global_rows == 3 * 40 and dec.kv_window_rows == 0
    assert dec.lane_attention_blocks == 3 * 12
    assert dec.lane_attention_blocks_live == 3 * 5
    # XLA's count of the same tick: every lane up to the extent
    xla = _decoder(attention_backend="ragged_xla", **mha)
    assert not xla._lane_kernel
    xla._kv_rows_of(32, pos, live, None)
    assert xla.kv_global_rows == 3 * 3 * 32
    assert xla.lane_attention_blocks == xla.lane_attention_blocks_live == 0


def _wide_decoder(heads, kv_heads):
    """Heads of 128 in pages of 16 rows (eligible by the layout at 8 k/v
    heads), two layers, three lanes of 256 rows, bf16."""
    cfg = Config(
        vocab_size=64, hidden_size=128 * heads, num_layers=2,
        num_heads=heads, num_kv_heads=kv_heads, intermediate_size=64,
        seq_length=256, precision="bf16", use_flash_attention=False,
        use_stable_embedding=False, scan_layers=False, prefill_chunk_size=8,
        attention_backend="ragged_xla", max_new_tokens=8,
    )
    cfg.validate()
    model = LuminaTransformer(cfg)
    params = unbox(jax.jit(model.init)(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32))["params"])
    engine = GenerationEngine(model, params, _Tok(), cfg)
    return engine.make_stepwise(num_slots=3, page_size=16,
                                max_slot_tokens=256, prefill_chunk_tokens=8)


def test_an_mha_tick_on_a_tpus_rule_calls_the_kernel_and_slices_no_pool(
        monkeypatch):
    """OLMoE's kind of shape (one query head a k/v head, heads of 128):
    lowered for a TPU the tick's decode rows go through lane_attention,
    one lowering for both layers, over the pool as it lies; the CPU's
    program under 'ragged_xla' is XLA's, which slices every layer's k and
    v to the tick's extent first and knows no kernel."""

    def tick(dec, platform):
        dec._active[:] = True
        dec._pos[:] = 9
        fn, args = dec.step_fn_and_args(GREEDY_SAMPLE_KEY)
        return fn.trace(*args).lower(lowering_platforms=(platform,)).as_text()

    pool = "tensor<3x256x8x128xbf16>"

    def extent_slices(text):
        return [ln for ln in text.splitlines()
                if "stablehlo.slice" in ln and f"({pool})" in ln]

    on_cpu = tick(_wide_decoder(8, 8), "cpu")
    assert "lane_attention" not in on_cpu and "tpu_custom_call" not in on_cpu
    assert len(extent_slices(on_cpu)) == 2 * 2  # k and v, two layers
    monkeypatch.setattr(rpa, "_interpret", lambda: False)
    dec = _wide_decoder(8, 8)
    assert dec._lane_kernel
    on_tpu = tick(dec, "tpu")
    assert on_tpu.count("tpu_custom_call") == 1 and "lane_attention" in on_tpu
    assert extent_slices(on_tpu) == []
    # (the tiny heads of the other tests are not eligible on a TPU, MHA or
    # grouped: the rule is the layout, not the string)
    assert not _decoder(attention_backend="ragged_xla")._lane_kernel


# The kernel's program (its jaxpr as text: the plan, the grid, the index
# maps, the kernel body; no source locations) at the shapes the other three
# served configurations run, as the parent of PR 47 traced it: (lanes, rows a
# lane, query heads, k/v heads, row width, window, extent, ring, v_dim,
# scale) -> sha256. A change of lane_blocks' result or of _lane_attention's
# static arguments for these shapes changes the text. (The ring's is PR 47's
# parent's still; the six over whole pages were taken again at PR 62, whose
# grid stops at the longest lane's last block: lane_grid_blocks.)
AS_IT_WAS = {
    "command_a_ring": (
        (32, 35 * 128, 128, 8, 128, 4096, None, True, 0, None),
        "f396f86a29272c92"),
    "command_a_whole_pages": (
        (32, 16384, 128, 8, 128, None, 16384, False, 0, None),
        "1dac87dcf1d2309d"),
    "command_a_extent_2048": (
        (32, 16384, 128, 8, 128, None, 2048, False, 0, None),
        "29091bbf43f1735e"),
    "jamba_extent_1024": (
        (128, 2048, 20, 1, 128, None, 1024, False, 0, None),
        "d0fd9490840446c3"),
    "jamba_extent_2048": (
        (128, 2048, 20, 1, 128, None, 2048, False, 0, None),
        "0c9090fda4f48b2c"),
    "latent_widest": (
        (32, 32768, 64, 1, 640, None, 32768, False, 512, 0.14468),
        "14c8ef58caef6a8e"),
    "latent_extent_2048": (
        (32, 32768, 64, 1, 640, None, 2048, False, 512, 0.14468),
        "d8c41df8b90515e2"),
}


@pytest.mark.parametrize("case", list(AS_IT_WAS))
def test_the_other_cells_kernel_is_the_program_it_was(monkeypatch, case):
    (lanes, rows, hq, hkv, d, window, extent, ring, v_dim, scale), want = (
        AS_IT_WAS[case])
    monkeypatch.setattr(rpa, "_interpret", lambda: False)

    def decode(q, k, v, lengths, table):
        meta = LaneMeta(lengths=lengths, window=window, page_size=128,
                        extent=extent, ring_table=table if ring else None)
        if v_dim:
            return lane_attention(q, k, None, meta, scale=scale, v_dim=v_dim)
        return lane_attention(q, k, v, meta, ring=ring)

    sds = jax.ShapeDtypeStruct
    kv = sds((lanes, rows, hkv, d), jnp.bfloat16)
    text = str(jax.make_jaxpr(decode)(
        sds((lanes, 1, hq, d), jnp.bfloat16), kv, kv,
        sds((lanes,), jnp.int32), sds((lanes, 128), jnp.int32)))
    assert "lane_attention" in text
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == want
