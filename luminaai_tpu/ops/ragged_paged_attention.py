"""Ragged paged attention for the serving decode path (arxiv 2604.15464).

The continuous-batching pool (inference/kv_pool.py) is slot-paged: each
lane's KV lives in `pages` tiles of `page_size` rows, addressed through a
per-lane page table, with a per-lane length saying how many rows are
actually resident. The dense decode path ignores all of that structure —
it materializes a `[B, S_cache]` mask over the FULL pool extent every
step, so decode cost scales with pool capacity instead of tokens
resident. This module closes that gap with two implementations behind
one dispatcher:

- `ragged_paged_attention_xla`: pure-XLA reference. Gathers the lane's
  pages through the page table (skippable when the table is the pool's
  identity layout — the gather would only copy bytes) and masks by
  per-lane length. It is the parity oracle for the kernel AND the
  fallback whenever the kernel is ineligible (odd head_dim/page_size,
  multi-row q). Callers bound its cost by slicing the page axis to the
  resident extent before calling (StepwiseDecoder does), so even the
  fallback reads O(tokens resident), not O(pool capacity).

- `ragged_paged_attention` (Pallas): grid over (lane, head, kv-page)
  with the page table and lengths as SCALAR-PREFETCH operands — the
  K/V BlockSpec index maps chase the table directly, pages past a
  lane's length are clamped to the last live page (a re-fetch Pallas
  elides) and compute-skipped via `pl.when`, and the running
  (max, denominator, accumulator) online softmax means no [B, S_cache]
  score row ever exists. Interpret mode on CPU, compiled on TPU — the
  same pattern ops/flash_attention.py established.

`LaneMeta` is the lane-metadata struct (lengths, page table, window,
kind) that ROADMAP item 5 collapses the per-variant attention masking
behind: models/layers.py threads it through GQAttention, so the
scalar-offset decode, batched `cache_index` decode, and chunked-prefill
variants all describe themselves the same way and the ragged kernel is
a drop-in backend (`config.attention_backend`).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from flax import struct
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128  # lane-replicated per-row stats, matching flash_attention


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


@struct.dataclass
class LaneMeta:
    """Per-lane attention metadata for length-aware decode/prefill.

    lengths: [B] int32 — rows resident per lane INCLUDING rows written
      by the current call (decode at position p ⇒ lengths = p + 1).
      0 marks a lane with nothing attendable (its output is garbage the
      caller must ignore — inactive pool slots during a shared step).
      None makes the struct a BACKEND HINT only: the attention layer
      derives lengths/window/page_size itself (from cache_index /
      positions) and honors just the `backend` field — how an engine
      whose config differs from the model's construction-time config
      still decides the backend (the kv_cache_dtype override contract).
    page_table: [B, P] int32 — logical page j of lane b lives at
      physical page `page_table[b, j]` of the lane's visible page axis.
      The pool's layout is the identity table today; the indirection is
      what page sharing/compaction (prefix caching) will retarget.
    window: static sliding-window width (None = full causal).
    kind: static 'decode' (S=1 rows at lengths-1) or 'prefill'
      (multi-row chunks; q positions come from the `positions` operand).
    page_size: static rows per page.
    """

    lengths: Optional[jax.Array] = None
    page_table: Optional[jax.Array] = None
    # Static backend override ('dense' | 'ragged_xla' | 'ragged'); None
    # defers to the model config's attention_backend. The ENGINE config
    # wins when both exist — callers thread it here.
    backend: Optional[str] = struct.field(pytree_node=False, default=None)
    window: Optional[int] = struct.field(pytree_node=False, default=None)
    kind: str = struct.field(pytree_node=False, default="decode")
    page_size: int = struct.field(pytree_node=False, default=128)
    # The pool hands out identity tables (contract-tested); skipping the
    # XLA reference's physical gather then saves a pool-sized copy per
    # step. The Pallas kernel always honors the table — its index maps
    # cost nothing either way.
    identity_pages: bool = struct.field(pytree_node=False, default=True)
    # Static resident-extent bound in ROWS (page-aligned): the attention
    # layer slices the post-write K/V to [:, :extent] before dispatch, so
    # even the XLA reference reads O(tokens resident) instead of O(pool
    # capacity). The CALLER picks it from a small power-of-two page
    # ladder (StepwiseDecoder does) so the executable count stays
    # O(log pages), mirroring the prompt-bucket discipline. None = full
    # extent. Every lane's lengths must satisfy lengths <= extent.
    # (Under global_pages the extent bounds the LOGICAL page count — it
    # slices the page TABLE, not the K/V rows, since physical pages may
    # live in any slot.)
    extent: Optional[int] = struct.field(pytree_node=False, default=None)
    # GLOBAL page addressing (prefix cache): table entries are ids into
    # the flattened (slot, page) space of the WHOLE pool — global id
    # t * P_slot + p addresses physical page p of slot t — so a lane can
    # alias pages physically resident in ANOTHER slot (the copy-on-write
    # prefix-sharing substrate). k/v then arrive as the full pool
    # [T, C, Hkv, D] with T >= B; q stays [B, ...]. Lanes' private pages
    # are their own identity ids (b * P_slot + j); shared read-only
    # prefix pages point into the cache arena. Implies a real gather
    # (identity_pages is ignored).
    global_pages: bool = struct.field(pytree_node=False, default=False)
    # A prefill CHUNK riding a decode batch (StepwiseDecoder's one
    # program a tick): the last `chunk_rows` rows of the batch are not
    # lanes but `chunk_rows` consecutive prompt rows of pool slot
    # `chunk_slot`, the first of them at position `chunk_start` (the
    # `positions` operand carries each row's own position, -1 for the
    # chunk's padding). Every weight then runs once over both kinds of
    # rows; the attention layer writes the chunk's K/V into its slot
    # and attends it against that slot alone (GQAttention._tick_
    # attention). `lengths` / `page_table` describe the decode rows
    # only. chunk_rows == 0: a plain decode batch.
    chunk_rows: int = struct.field(pytree_node=False, default=0)
    chunk_slot: Optional[jax.Array] = None
    chunk_start: Optional[jax.Array] = None
    # [num_slots, P] int32, for the layers that keep a RING of pages a
    # lane (a window of their own, GQAttention.init_cache): logical page
    # j of slot s lives at physical page `ring_table[s, j]` of the ring.
    # Positions stay absolute; rows are written and read through this
    # table (ring_key_positions). None: no layer of the pool keeps a ring.
    ring_table: Optional[jax.Array] = None


def ragged_eligible(page_size: int, head_dim: int, s_q: int) -> bool:
    """When the Pallas decode kernel applies: one q row per lane,
    sublane-aligned pages, lane-friendly head_dim (Mosaic pads 64→128).
    Everything else takes the XLA reference path."""
    return s_q == 1 and page_size % 8 == 0 and head_dim % 64 == 0


def implied_page_size(cache_rows: int) -> int:
    """Page size for a LaneMeta DERIVED inside the attention layer (no
    pool in sight — scalar-offset decode, bucketed prefill): the largest
    sublane-aligned power of two dividing the cache extent, capped at
    128, so the Pallas kernel stays eligible whenever the extent allows
    it. Falls back to the full extent (kernel ineligible unless it is
    itself aligned)."""
    ps = 128
    while ps >= 8:
        if cache_rows % ps == 0:
            return ps
        ps //= 2
    return cache_rows


# ---------------------------------------------------------------------------
# Pure-XLA reference (parity oracle + fallback)
# ---------------------------------------------------------------------------
def ragged_paged_attention_xla(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    meta: LaneMeta,
    positions: Optional[jax.Array] = None,
) -> jax.Array:
    """Length-masked paged attention, reference semantics.

    q: [B, Sq, Hq, D]; k/v: [B, C, Hkv, D] flat with C == P * page_size
    (the caller's resident-extent slice). positions: [B, Sq] absolute q
    positions for prefill chunks (-1 rows are padding and fully masked);
    decode (Sq == 1) derives the q position from lengths.

    The mask formula is exactly the dense per-lane decode mask
    (models/layers.py) restricted by residency — greedy streams through
    this path are token-identical to the dense backend by construction.

    Under meta.global_pages, k/v are the FULL pool [T, C, Hkv, D]
    (T >= B lanes + prefix-cache arena slots) and table entries are
    global (slot, page) ids — the gather pulls each lane's logical pages
    from wherever they physically live, which is how a shared cached
    prefix page serves many lanes without its bytes ever being copied
    into their slots. meta.extent slices the TABLE's logical pages, so
    compute/bytes still scale with tokens resident.
    """
    B, Sq, n_q, d = q.shape
    C, n_kv = k.shape[1], k.shape[2]
    ps = meta.page_size
    if meta.global_pages:
        # Global gather: [T, C] pool rows -> [T*P_all, ps] physical
        # pages -> [B, P_l, ps] logical pages per lane via the global
        # page table (extent-sliced: logical pages past the resident
        # bound are never touched).
        T, P_all = k.shape[0], C // ps
        table = meta.page_table.astype(jnp.int32)
        if meta.extent is not None and meta.extent < C:
            table = table[:, : meta.extent // ps]
        P_l = table.shape[1]
        k = jnp.take(
            k.reshape(T * P_all, ps, n_kv, d), table, axis=0
        ).reshape(B, P_l * ps, n_kv, d)
        v = jnp.take(
            v.reshape(T * P_all, ps, n_kv, d), table, axis=0
        ).reshape(B, P_l * ps, n_kv, d)
        C = P_l * ps
    elif meta.page_table is not None and not meta.identity_pages:
        # Physical gather through the page table: [B, P] page ids pick
        # pages off the lane's own page axis. Identity tables skip this
        # (the values would be bit-identical; the copy would not be free).
        P = C // ps
        table = meta.page_table[:, :P]
        paged = k.reshape(B, P, ps, n_kv, d)
        k = jnp.take_along_axis(
            paged, table[:, :, None, None, None], axis=1
        ).reshape(B, C, n_kv, d)
        paged_v = v.reshape(B, P, ps, n_kv, d)
        v = jnp.take_along_axis(
            paged_v, table[:, :, None, None, None], axis=1
        ).reshape(B, C, n_kv, d)

    g = n_q // n_kv
    qg = q.reshape(B, Sq, n_kv, g, d)
    scale = 1.0 / jnp.sqrt(d).astype(jnp.float32)
    logits = (
        jnp.einsum("bqhgd,bkhd->bhgqk", qg, k).astype(jnp.float32) * scale
    )

    if positions is not None:
        qp = positions[:, :, None]  # [B, Sq, 1]; -1 rows mask everything
    else:
        qp = (meta.lengths[:, None, None] - Sq) + jnp.arange(Sq)[None, :, None]
    kp = jnp.arange(C)[None, None, :]
    mask = jnp.logical_and(kp <= qp, kp < meta.lengths[:, None, None])
    if meta.window is not None:
        mask = jnp.logical_and(mask, qp - kp < meta.window)
    logits = jnp.where(mask[:, None, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, v)
    return out.reshape(B, Sq, n_q, d)


# ---------------------------------------------------------------------------
# Keys by position: a ring of pages read in place, and the tick's chunk
# ---------------------------------------------------------------------------
def ring_key_positions(
    ring_table: jax.Array, lengths: jax.Array, page_size: int, rows: int
) -> jax.Array:
    """[B, rows] int32: the absolute position each physical row of a
    lane's ring holds once `lengths` rows of the lane are written, -1
    where it holds nothing of this lane. The ring has rows // page_size
    pages; the logical pages still resident are the last that many up to
    the page of row lengths - 1, and each sits where the ring table
    (`ring_table[b, j]`: logical page j's physical page) put it. Rows of
    the last page past lengths - 1 read as positions not yet written: a
    causal mask drops them as it drops any future key."""
    B, P = ring_table.shape
    n_ring = rows // page_size
    last = (lengths.astype(jnp.int32) - 1) // page_size  # -1: empty lane
    logical = last[:, None] - jnp.arange(n_ring, dtype=jnp.int32)[None, :]
    held = jnp.logical_and(logical >= 0, lengths[:, None] > 0)
    physical = jnp.take_along_axis(
        ring_table.astype(jnp.int32), jnp.clip(logical, 0, P - 1), axis=1
    )
    resident = jnp.full((B, n_ring), -1, jnp.int32).at[
        jnp.arange(B)[:, None], jnp.where(held, physical, n_ring)
    ].set(logical, mode="drop")
    within = jnp.arange(page_size, dtype=jnp.int32)[None, None, :]
    kpos = jnp.where(
        resident[:, :, None] >= 0,
        resident[:, :, None] * page_size + within, -1,
    )
    return kpos.reshape(B, n_ring * page_size)


def banded_attention_xla(
    q: jax.Array, k: jax.Array, v: jax.Array,
    qpos: jax.Array, kpos: jax.Array, window: Optional[int],
) -> jax.Array:
    """Attention masked by POSITIONS given as data: q [B, Sq, Hq, D] at
    qpos [B, Sq] over k / v [B, C, Hkv, D] whose row c holds position
    kpos [B, c] (-1: nothing). Key j is seen by query i iff
    0 <= i - j (< window). What ragged_paged_attention_xla computes when
    kpos is the row number; a ring of pages hands the positions its rows
    hold now."""
    B, Sq, n_q, d = q.shape
    n_kv = k.shape[2]
    g = n_q // n_kv
    qg = q.reshape(B, Sq, n_kv, g, d)
    scale = 1.0 / jnp.sqrt(d).astype(jnp.float32)
    logits = (
        jnp.einsum("bqhgd,bkhd->bhgqk", qg, k).astype(jnp.float32) * scale
    )
    qp, kp = qpos[:, :, None], kpos[:, None, :]
    mask = jnp.logical_and(kp >= 0, kp <= qp)
    if window is not None:
        mask = jnp.logical_and(mask, qp - kp < window)
    logits = jnp.where(mask[:, None, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, v)
    return out.reshape(B, Sq, n_q, d)


def _chunk_key_block(rows: int) -> int:
    """Keys a grid step of chunk_attention: the largest multiple of 128
    up to 1,024 that divides the lane's rows (a ring of 35 pages of 128
    is 5 x 896), else the rows whole."""
    for t in range(1024, 127, -128):
        if rows % t == 0:
            return t
    return rows


def chunk_attention_eligible(n_rows: int, key_rows: int, head_dim: int
                             ) -> bool:
    """Where the blocked kernel compiles for the chip: sublane-aligned
    chunk, lane-aligned head, key rows in 128-row blocks. Off the chip it
    is interpreted at any size (tests)."""
    if _interpret():
        return True
    return (
        n_rows % 8 == 0 and head_dim % 128 == 0 and key_rows % 128 == 0
    )


def _chunk_kernel(
    blocks_ref,  # scalar prefetch [1]: key blocks that hold live rows
    qpos_ref, kpos_ref, q_ref, k_ref, v_ref, o_ref,
    m_scr, l_scr, acc_scr, *, scale, window,
):
    j = pl.program_id(1)
    nj = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(j < blocks_ref[0])
    def _compute():
        q = q_ref[0]  # [n, D]
        k = k_ref[0]  # [Bk, D]
        v = v_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [n, Bk]
        qp = qpos_ref[:, :1]  # [n, 1]
        kp = kpos_ref[:1, :]  # [1, Bk]
        keep = jnp.logical_and(kp >= 0, kp <= qp)
        if window:
            keep = jnp.logical_and(keep, qp - kp < window)
        s = jnp.where(keep, s, NEG_INF)
        m_prev = m_scr[:, :]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1)[:, None])
        alpha = jnp.exp(m_prev - m_new)
        # A row with no key in band yet keeps m == NEG_INF: its exp(0)
        # terms must not count.
        p = jnp.where(keep, jnp.exp(s - m_new[:, :1]), 0.0)
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
        o_ref[0] = (acc_scr[:] / safe_l[:, :1]).astype(o_ref.dtype)


def chunk_attention(
    q: jax.Array, k: jax.Array, v: jax.Array,
    qpos: jax.Array, kpos: jax.Array, window: Optional[int],
    live_rows: jax.Array,
) -> jax.Array:
    """The tick's prefill chunk over its own lane, blocked over the keys
    with an online softmax: q [n, Hq, D] at positions qpos [n] (-1: a
    padding row, which sees nothing) over ONE lane's k / v [C, Hkv, D]
    whose row c holds position kpos [c] (-1: nothing; whole pages hand
    their row numbers, a ring what ring_key_positions says). Key j is
    seen by query i iff 0 <= i - j (< window): banded_attention_xla's
    rule. `live_rows`: rows of the lane that may hold a key (a scalar,
    traced); key blocks wholly past it cost neither a DMA nor a step's
    arithmetic. Grid (q head, key block): a head's [n, D] queries stay
    put while its k/v head's blocks stream past; [n, block] float32
    scores live in VMEM and nowhere else. Returns [n, Hq, D]."""
    n, Hq, D = q.shape
    C, Hkv = k.shape[0], k.shape[1]
    group = Hq // Hkv
    bk = _chunk_key_block(C)
    nb = C // bk
    blocks = jnp.reshape(
        (jnp.asarray(live_rows, jnp.int32) + bk - 1) // bk, (1,)
    )

    def kv_map(h, j, blocks):
        return (h // group, jnp.minimum(j, jnp.maximum(blocks[0] - 1, 0)), 0)

    def kpos_map(h, j, blocks):
        return (0, jnp.minimum(j, jnp.maximum(blocks[0] - 1, 0)))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(Hq, nb),
        in_specs=[
            pl.BlockSpec((n, LANES), lambda h, j, blocks: (0, 0)),
            pl.BlockSpec((8, bk), kpos_map),
            pl.BlockSpec((1, n, D), lambda h, j, blocks: (h, 0, 0)),
            pl.BlockSpec((1, bk, D), kv_map),
            pl.BlockSpec((1, bk, D), kv_map),
        ],
        out_specs=pl.BlockSpec((1, n, D), lambda h, j, blocks: (h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((n, LANES), jnp.float32),
            pltpu.VMEM((n, LANES), jnp.float32),
            pltpu.VMEM((n, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _chunk_kernel, scale=1.0 / (D**0.5), window=int(window or 0)
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Hq, n, D), q.dtype),
        interpret=_interpret(),
        name="chunk_attention",
    )(
        blocks,
        # Lane- and sublane-replicated so each is a tile the kernel reads
        # whole: [n, 128] and [8, C] int32.
        jnp.broadcast_to(qpos.astype(jnp.int32)[:, None], (n, LANES)),
        jnp.broadcast_to(kpos.astype(jnp.int32)[None, :], (8, C)),
        q.transpose(1, 0, 2),
        k.transpose(1, 0, 2),
        v.transpose(1, 0, 2),
    )
    return out.transpose(1, 0, 2)


# ---------------------------------------------------------------------------
# Pallas decode kernel: grid (lane, q head, kv page), page-table-native
# ---------------------------------------------------------------------------
def _decode_kernel(
    lengths_ref,  # scalar prefetch [B]
    table_ref,  # scalar prefetch [B, P]
    q_ref,
    k_ref,
    v_ref,
    o_ref,
    m_scr,
    l_scr,
    acc_scr,
    *,
    scale,
    page_size,
    window,
):
    b = pl.program_id(0)
    j = pl.program_id(2)
    nj = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    length = lengths_ref[b]
    # One q row per lane at position length-1. Pages wholly past the
    # length (and, under a window, wholly before the band) cost neither
    # compute nor a fresh DMA — the index map below pins skipped steps
    # to an already-fetched page.
    page_start = j * page_size
    needed = page_start < length
    if window:
        needed = jnp.logical_and(
            needed, page_start + page_size - 1 >= length - window
        )

    @pl.when(needed)
    def _compute():
        q = q_ref[0, 0, :, :]  # [1, D]
        k = k_ref[0, 0, 0, :, :]  # [page_size, D]
        v = v_ref[0, 0, 0, :, :]
        s = (
            jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            * scale
        )  # [1, page_size] fp32
        kp = page_start + jax.lax.broadcasted_iota(
            jnp.int32, (1, page_size), 1
        )
        keep = kp < length
        if window:
            keep = jnp.logical_and(keep, (length - 1) - kp < window)
        s = jnp.where(keep, s, NEG_INF)
        m_prev = m_scr[:, :]
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


def _page_index_map(group, page_size, n_pages, window, pool_pages=None):
    """K/V BlockSpec index map: chase the page table for live pages,
    clamp skipped grid steps onto the lane's last live page (same block
    index as a neighbouring step ⇒ Pallas skips the DMA entirely).

    pool_pages: pages-per-slot of the pool when table entries are GLOBAL
    (slot, page) ids (prefix-cache aliasing) — the map then decomposes
    the id back into (slot, page) block coordinates, so a lane's logical
    page can be fetched from another slot's storage."""

    def index(b, h, j, lengths, table):
        length = lengths[b]
        last = jnp.maximum(length - 1, 0) // page_size
        first = 0
        if window:
            first = jnp.maximum(length - window, 0) // page_size
        jv = jnp.clip(j, first, last)
        phys = table[b, jnp.minimum(jv, n_pages - 1)]
        if pool_pages is not None:
            return (phys // pool_pages, h // group, phys % pool_pages, 0, 0)
        return (b, h // group, phys, 0, 0)

    return index


def ragged_paged_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    meta: LaneMeta,
) -> jax.Array:
    """Pallas page-table-native decode attention.

    q: [B, 1, Hq, D]; k/v: [B, C, Hkv, D] flat, C == P * meta.page_size.
    Returns [B, 1, Hq, D]. Gate with ragged_eligible(); interpret mode
    off-TPU (CPU tests), compiled on TPU.
    """
    B, Sq, Hq, D = q.shape
    C, Hkv = k.shape[1], k.shape[2]
    ps = meta.page_size
    assert Sq == 1, "the Pallas kernel is decode-shaped (one q row/lane)"
    assert C % ps == 0, (C, ps)
    P = C // ps
    group = Hq // Hkv

    lengths = meta.lengths.astype(jnp.int32)
    pool_pages = None
    if meta.global_pages:
        # Global (slot, page) addressing: k/v are the full pool
        # [T, C, ...]; the grid's page axis runs over each lane's
        # LOGICAL pages (extent-sliced), and the index map decomposes
        # global table ids into pool block coordinates.
        pool_pages = P
        table = meta.page_table.astype(jnp.int32)
        if meta.extent is not None and meta.extent < C:
            table = table[:, : meta.extent // ps]
        P_grid = table.shape[1]
    elif meta.page_table is not None:
        table = meta.page_table.astype(jnp.int32)[:, :P]
        P_grid = P
    else:
        table = jnp.tile(jnp.arange(P, dtype=jnp.int32)[None], (B, 1))
        P_grid = P

    qt = q.transpose(0, 2, 1, 3)  # [B, Hq, 1, D]
    T = k.shape[0]
    kt = k.reshape(T, P, ps, Hkv, D).transpose(0, 3, 1, 2, 4)
    vt = v.reshape(T, P, ps, Hkv, D).transpose(0, 3, 1, 2, 4)

    window = int(meta.window or 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, Hq, P_grid),
        in_specs=[
            pl.BlockSpec(
                (1, 1, 1, D), lambda b, h, j, lengths, table: (b, h, 0, 0)
            ),
            pl.BlockSpec(
                (1, 1, 1, ps, D),
                _page_index_map(group, ps, P_grid, window, pool_pages),
            ),
            pl.BlockSpec(
                (1, 1, 1, ps, D),
                _page_index_map(group, ps, P_grid, window, pool_pages),
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, 1, D), lambda b, h, j, lengths, table: (b, h, 0, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((1, LANES), jnp.float32),
            pltpu.VMEM((1, LANES), jnp.float32),
            pltpu.VMEM((1, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _decode_kernel,
            scale=1.0 / (D**0.5),
            page_size=ps,
            window=window,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hq, 1, D), q.dtype),
        interpret=_interpret(),
        name="ragged_paged_decode",
    )(lengths, table, qt, kt, vt)
    return out.transpose(0, 2, 1, 3)


def paged_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    meta: LaneMeta,
    *,
    backend: str = "ragged",
    positions: Optional[jax.Array] = None,
) -> jax.Array:
    """Backend dispatcher (config.attention_backend):

    'ragged'      Pallas kernel when eligible, XLA reference otherwise
    'ragged_xla'  always the XLA reference (the CPU-serving default —
                  interpret-mode kernels cost interpreter time)

    Prefill chunks (Sq > 1) always take the reference path; the kernel
    is decode-specialized.
    """
    Sq, D = q.shape[1], q.shape[3]
    if backend == "ragged" and ragged_eligible(meta.page_size, D, Sq):
        return ragged_paged_attention(q, k, v, meta)
    return ragged_paged_attention_xla(q, k, v, meta, positions=positions)
