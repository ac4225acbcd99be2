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
  per-lane length. It is the parity oracle for the kernel AND what runs
  wherever the kernel does not (multi-row q; on a TPU, shapes outside
  lane_attention_eligible; off it, every backend but 'ragged'). The
  dispatcher bounds its cost by slicing the page axis to the resident
  extent (StepwiseDecoder picks it), so it reads every lane's rows up
  to the extent: O(lanes x extent), not O(pool capacity).

- `lane_attention` (Pallas): grid over (lane, key block) with the
  lengths and a small plan as SCALAR-PREFETCH operands. The pool is read
  in place, a block of whole pages with every k/v head at a time; a
  lane that is not stepped and a block in which the lane's query sees no
  key (past the length, before the window, a ring page that holds
  nothing) are pinned to the block fetched last (a re-fetch Pallas
  elides) and compute-skipped via `pl.when`, and the running
  (max, denominator, accumulator) online softmax means no [B, S_cache]
  score row ever exists. Whole pages (identity, a real page table,
  global ids) and rings of pages go through the one kernel, masked by
  position. Interpret mode on CPU, compiled on TPU — the same pattern
  ops/flash_attention.py established.

`LaneMeta` is the lane-metadata struct (lengths, page table, window,
kind) that ROADMAP item 5 collapses the per-variant attention masking
behind: models/layers.py threads it through GQAttention, so the
scalar-offset decode, batched `cache_index` decode, and chunked-prefill
variants all describe themselves the same way and the ragged kernel is
a drop-in backend (`config.attention_backend`).
"""

from __future__ import annotations

import functools
import operator
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
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


def whole_key(k):
    """A key kept in parts (Config.key_parts: a tuple of [.., kv_heads,
    value width] arrays, the last zero-padded) as one array of their
    columns side by side; one array as it is."""
    return jnp.concatenate(k, axis=-1) if isinstance(k, tuple) else k


def sink_softmax(logits: jax.Array, sink: Optional[jax.Array]) -> jax.Array:
    """Softmax over the last axis of float32 `logits` [B, Hkv, G, Sq, K]
    with a learned SINK: one more column of logit sink[h] a query head
    (`sink` [Hkv * G], Config.layer_sink) that takes its share of the
    probability and gives no value, so a row's probabilities add up to
    less than one. None: the plain softmax. A row that sees no key gives
    zeros, not NaN."""
    if sink is None:
        return jax.nn.softmax(logits, axis=-1)
    b = sink.astype(jnp.float32).reshape(1, *logits.shape[1:3], 1, 1)
    m = jax.lax.stop_gradient(
        jnp.maximum(jnp.max(logits, axis=-1, keepdims=True), b)
    )
    e = jnp.exp(logits - m)
    return e / (jnp.sum(e, axis=-1, keepdims=True) + jnp.exp(b - m))


def _scale_of(scale: Optional[float], d: int):
    """The score scale: the caller's (a head padded past its own width
    keeps its own D^-1/2), else the query's width's."""
    if scale is not None:
        return scale
    return 1.0 / jnp.sqrt(d).astype(jnp.float32)


# ---------------------------------------------------------------------------
# Pure-XLA reference (parity oracle + fallback)
# ---------------------------------------------------------------------------
def ragged_paged_attention_xla(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    meta: LaneMeta,
    positions: Optional[jax.Array] = None,
    *, scale: Optional[float] = None, sink: Optional[jax.Array] = None,
) -> jax.Array:
    """Length-masked paged attention, reference semantics.

    q: [B, Sq, Hq, D]; k: [B, C, Hkv, D] and v: [B, C, Hkv, Dv] flat with
    C == P * page_size (the caller's resident-extent slice; k may come in
    parts, whole_key). `scale` / `sink`: _scale_of, sink_softmax.
    positions: [B, Sq] absolute q
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
    k = whole_key(k)
    B, Sq, n_q, d = q.shape
    C, n_kv, dv = k.shape[1], k.shape[2], v.shape[3]
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
            v.reshape(T * P_all, ps, n_kv, dv), table, axis=0
        ).reshape(B, P_l * ps, n_kv, dv)
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
        paged_v = v.reshape(B, P, ps, n_kv, dv)
        v = jnp.take_along_axis(
            paged_v, table[:, :, None, None, None], axis=1
        ).reshape(B, C, n_kv, dv)

    g = n_q // n_kv
    qg = q.reshape(B, Sq, n_kv, g, d)
    logits = (
        jnp.einsum("bqhgd,bkhd->bhgqk", qg, k).astype(jnp.float32)
        * _scale_of(scale, d)
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
    probs = sink_softmax(logits, sink).astype(q.dtype)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, v)
    return out.reshape(B, Sq, n_q, dv)


# ---------------------------------------------------------------------------
# Keys by position: a ring of pages read in place, and the tick's chunk
# ---------------------------------------------------------------------------
def _ring_resident(
    ring_table, lengths, page_size: int, n_ring: int, xp=jnp
):
    """[B, n_ring] int32: the logical page each physical page of a
    lane's ring holds once `lengths` rows of the lane are written, -1
    where it holds nothing of this lane. The logical pages still resident
    are the last n_ring up to the page of row lengths - 1, and each sits
    where the ring table (`ring_table[b, j]`: logical page j's physical
    page) put it. `xp`: numpy for the host's count of what a tick reads
    (StepwiseDecoder._kv_rows_of), jax.numpy in a program."""
    P = ring_table.shape[1]
    lengths = lengths.astype(xp.int32)
    last = (lengths - 1) // page_size  # -1: empty lane
    logical = last[:, None] - xp.arange(n_ring, dtype=xp.int32)[None, :]
    held = xp.logical_and(logical >= 0, lengths[:, None] > 0)
    physical = xp.take_along_axis(
        ring_table.astype(xp.int32), xp.clip(logical, 0, P - 1), axis=1
    )
    here = xp.logical_and(
        held[:, :, None],
        physical[:, :, None]
        == xp.arange(n_ring, dtype=xp.int32)[None, None, :],
    )
    return xp.max(xp.where(here, logical[:, :, None], -1), axis=1)


def ring_key_positions(
    ring_table: jax.Array, lengths: jax.Array, page_size: int, rows: int
) -> jax.Array:
    """[B, rows] int32: the absolute position each physical row of a
    lane's ring holds once `lengths` rows of the lane are written, -1
    where it holds nothing of this lane (_ring_resident, a row at a
    time). Rows of the last page past lengths - 1 read as positions not
    yet written: a causal mask drops them as it drops any future key."""
    n_ring = rows // page_size
    resident = _ring_resident(ring_table, lengths, page_size, n_ring)
    within = jnp.arange(page_size, dtype=jnp.int32)[None, None, :]
    kpos = jnp.where(
        resident[:, :, None] >= 0,
        resident[:, :, None] * page_size + within, -1,
    )
    return kpos.reshape(ring_table.shape[0], n_ring * page_size)


def banded_attention_xla(
    q: jax.Array, k: jax.Array, v: jax.Array,
    qpos: jax.Array, kpos: jax.Array, window: Optional[int],
    *, scale: Optional[float] = None, sink: Optional[jax.Array] = None,
) -> jax.Array:
    """Attention masked by POSITIONS given as data: q [B, Sq, Hq, D] at
    qpos [B, Sq] over k [B, C, Hkv, D] (or its parts, whole_key) and v
    [B, C, Hkv, Dv] whose row c holds position kpos [B, c] (-1: nothing).
    Key j is seen by query i iff 0 <= i - j (< window). What
    ragged_paged_attention_xla computes when kpos is the row number; a
    ring of pages hands the positions its rows hold now. `scale` /
    `sink`: _scale_of, sink_softmax."""
    k = whole_key(k)
    B, Sq, n_q, d = q.shape
    n_kv = k.shape[2]
    g = n_q // n_kv
    qg = q.reshape(B, Sq, n_kv, g, d)
    logits = (
        jnp.einsum("bqhgd,bkhd->bhgqk", qg, k).astype(jnp.float32)
        * _scale_of(scale, d)
    )
    qp, kp = qpos[:, :, None], kpos[:, None, :]
    mask = jnp.logical_and(kp >= 0, kp <= qp)
    if window is not None:
        mask = jnp.logical_and(mask, qp - kp < window)
    logits = jnp.where(mask[:, None, None], logits, NEG_INF)
    probs = sink_softmax(logits, sink).astype(q.dtype)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, v)
    return out.reshape(B, Sq, n_q, v.shape[3])


def latent_attention_xla(
    q: jax.Array, rows: jax.Array, qpos: jax.Array, scale: float,
    v_dim: int,
) -> jax.Array:
    """Absorbed latent attention, reference semantics: multi-query
    attention of q [B, Sq, Hq, W] at positions qpos [B, Sq] (-1: a row
    that sees nothing) over ONE shared key a token, the latent entry's
    rows [B, C, 1, W] at their row numbers, whose first `v_dim` columns
    are the value. Key j is seen by query i iff j <= i. Returns
    [B, Sq, Hq, v_dim]: what lane_attention and chunk_attention compute
    over the same entry (`v=None`), and what runs wherever they do not."""
    k = rows[:, :, 0]
    s = jnp.einsum("bqnw,bcw->bnqc", q, k).astype(jnp.float32) * scale
    seen = jnp.arange(k.shape[1])[None, None, :] <= qpos[:, :, None]
    s = jnp.where(seen[:, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bnqc,bcv->bqnv", p, k[..., :v_dim])


# Stacked query rows (rows x folded heads) a grid step of chunk_attention
# over a latent entry: [1,024, 1,024] float32 scores are 4 MB of VMEM.
_CHUNK_FOLD_ROWS = 1024


def _chunk_key_block(rows: int) -> int:
    """Keys a grid step of chunk_attention: the largest multiple of 128
    up to 1,024 that divides the lane's rows (a ring of 35 pages of 128
    is 5 x 896), else the rows whole."""
    for t in range(1024, 127, -128):
        if rows % t == 0:
            return t
    return rows


def chunk_attention_eligible(n_rows: int, key_rows: int, head_dim: int,
                             v_dim: Optional[int] = None) -> bool:
    """Where the blocked kernel compiles for the chip: sublane-aligned
    chunk, lane-aligned key and value widths (`head_dim` the key's as it
    is handed over, a padded 192 being 256; `v_dim` the value's, None:
    the same), key rows in 128-row blocks. Off the chip it is interpreted
    at any size (tests)."""
    if _interpret():
        return True
    return (
        n_rows % 8 == 0 and head_dim % 128 == 0
        and (v_dim or head_dim) % 128 == 0 and key_rows % 128 == 0
    )


def _chunk_kernel(
    blocks_ref,  # scalar prefetch [1]: key blocks that hold live rows
    qpos_ref, kpos_ref, q_ref, k_ref, *rest, scale, window, v_dim, sink,
):
    # `v_dim`: no v operand, the value is the key's first v_dim columns.
    # `sink`: one more operand, the head's sink logit on every lane.
    rest = list(rest)
    v_ref = None if v_dim else rest.pop(0)
    sink_ref = rest.pop(0) if sink else None
    o_ref, m_scr, l_scr, acc_scr = rest
    j = pl.program_id(1)
    nj = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        if sink:
            # The sink is a column seen by every row that gives no value:
            # the running maximum starts at its logit, the denominator
            # at its exp(0).
            m_scr[:] = jnp.broadcast_to(sink_ref[0, :1, :], m_scr.shape)
            l_scr[:] = jnp.ones_like(l_scr)
        else:
            m_scr[:] = jnp.full_like(m_scr, NEG_INF)
            l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(j < blocks_ref[0])
    def _compute():
        q = q_ref[0]  # [n, D]
        k = k_ref[0]  # [Bk, D]
        v = k[:, :v_dim] if v_dim else v_ref[0]
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
    q: jax.Array, k: jax.Array, v: Optional[jax.Array],
    qpos: jax.Array, kpos: jax.Array, window: Optional[int],
    live_rows: jax.Array, *, scale: Optional[float] = None,
    v_dim: int = 0, sink: Optional[jax.Array] = None,
) -> jax.Array:
    """The tick's prefill chunk over its own lane, blocked over the keys
    with an online softmax: q [n, Hq, D] at positions qpos [n] (-1: a
    padding row, which sees nothing) over ONE lane's k [C, Hkv, D] and v
    [C, Hkv, Dv] (Dv the value's own width, the output's)
    whose row c holds position kpos [c] (-1: nothing; whole pages hand
    their row numbers, a ring what ring_key_positions says). Key j is
    seen by query i iff 0 <= i - j (< window): banded_attention_xla's
    rule. `live_rows`: rows of the lane that may hold a key (a scalar,
    traced); key blocks wholly past it cost neither a DMA nor a step's
    arithmetic. Grid (q head, key block): a head's [n, D] queries stay
    put while its k/v head's blocks stream past; [n, block] float32
    scores live in VMEM and nowhere else. `sink` [Hq] float32
    (sink_softmax's rule): a head's running maximum starts at its sink's
    logit and its denominator at 1. Returns [n, Hq, Dv].

    A latent entry (models/layers.py LatentPages): `v=None` and
    `v_dim`, the value is the first v_dim columns of the ONE shared key
    row, read once a block for both matmuls; `scale` is the mixer's own
    (default D^-1/2); and as many query heads as keep a grid step's
    stacked rows at _CHUNK_FOLD_ROWS or under share the step ([n x fold, D]
    queries against the block: 4 heads at a 256-row chunk), so the lane's
    rows stream past Hq / fold times and not Hq. Returns [n, Hq, v_dim]."""
    n, Hq, D = q.shape
    fold = 1
    while v_dim and Hq % (2 * fold) == 0 and n * 2 * fold <= _CHUNK_FOLD_ROWS:
        fold *= 2
    if fold > 1:
        assert k.shape[1] == 1 and sink is None, k.shape
        out = _chunk_call(
            q.reshape(n, Hq // fold, fold, D).transpose(0, 2, 1, 3)
            .reshape(n * fold, Hq // fold, D),
            k, v, jnp.repeat(qpos, fold), kpos, window, live_rows, scale,
            v_dim,
        )
        return out.reshape(n, fold, Hq // fold, -1).transpose(
            0, 2, 1, 3).reshape(n, Hq, -1)
    return _chunk_call(q, k, v, qpos, kpos, window, live_rows, scale, v_dim,
                       sink)


def _chunk_call(q, k, v, qpos, kpos, window, live_rows, scale, v_dim,
                sink=None):
    n, Hq, D = q.shape
    C, Hkv = k.shape[0], k.shape[1]
    Dv = v_dim or v.shape[2]
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
        ] + ([] if v_dim else [pl.BlockSpec((1, bk, Dv), kv_map)]) + (
            [] if sink is None else
            [pl.BlockSpec((1, 8, LANES), lambda h, j, blocks: (h, 0, 0))]
        ),
        out_specs=pl.BlockSpec((1, n, Dv), lambda h, j, blocks: (h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((n, LANES), jnp.float32),
            pltpu.VMEM((n, LANES), jnp.float32),
            pltpu.VMEM((n, Dv), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _chunk_kernel, scale=scale or 1.0 / (D**0.5),
            window=int(window or 0), v_dim=v_dim, sink=sink is not None,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Hq, n, Dv), q.dtype),
        # (Past the default scoped VMEM only with folded heads: the
        # scores are [n x fold, block] float32.)
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_LANE_VMEM_LIMIT) if v_dim else None,
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
        *(() if v_dim else (v.transpose(1, 0, 2),)),
        *(() if sink is None else (jnp.broadcast_to(
            sink.astype(jnp.float32)[:, None, None], (Hq, 8, LANES)),)),
    )
    return out.transpose(1, 0, 2)


# ---------------------------------------------------------------------------
# Pallas decode kernel: grid (lane, key block), the pool read in place
# ---------------------------------------------------------------------------
# Of k (and as much of v) a grid step fetches, at most: a step that is
# skipped costs ~0.35 us whatever it would have read, so a block is several
# pages (8 of 128 rows at 8 k/v heads of 128; a ring of 35 pages is 5 x 7).
_LANE_BLOCK_BYTES = 2 << 20
# Score columns (keys x k/v heads) of one online-softmax update inside a
# block: [query heads, 1,024] float32 is 128 vregs at 128 query heads.
_LANE_TILE_COLS = 1024
_LANE_VMEM_LIMIT = 64 << 20


def lane_attention_eligible(
    n_q: int, n_kv: int, head_dim: int, page_size: int,
    v_dim: Optional[int] = None,
) -> bool:
    """Where `lane_attention` is the lanes' decode attention on a TPU: a
    pure function of the shapes the dispatcher sees, every term a fact of
    the layout: heads fill whole lanes (`head_dim`: the width of an array
    the entry keeps its key in, a whole key or one part of it; `v_dim`:
    the value array's, None = the same); a pool row [kv_heads, width]
    is whole (8, 128) tiles, one head, or 2 or 4 heads of one 128-lane
    tile (the chip tiles such an array (2, 128) or (4, 128), rows one
    after another), so
    the pool flattens to [rows x kv_heads, width] for free: 4 k/v heads
    of 256 columns do NOT (a copy of the pool in front of the kernel),
    which is why a key wider than its value lies in value-width parts
    (Config.key_parts); a page's score columns fill whole lanes. `n_q`
    is in no term: a tile scores EVERY query head
    against every k/v head's keys in one matmul and a constant mask keeps
    each head's own, so one query head a k/v head (MHA) is the same
    program as sixteen, and the MXU's time a block follows the block's
    bytes, not the group. Measured alone on a v5e against XLA's program
    over the [lanes, extent] slice (PERF.md section 6, PR 47; 28 lanes of
    2,048 rows, us a layer): 16 heads over 16 with 5 lanes stepped 40-78
    for XLA's 198-674, with 27 stepped 216 for 352, every lane full 624
    for 674; 32 over 8, 16 over 8, 64 over 16 and 128 over 16 likewise
    ahead at both kinds of lengths."""
    del n_q
    return (
        page_size % 8 == 0
        and (page_size * n_kv) % 128 == 0
        and all(
            width % 128 == 0
            and (n_kv == 1 or n_kv % 8 == 0
                 or (n_kv in (2, 4) and width == 128))
            for width in (head_dim, v_dim or head_dim)
        )
    )


def lane_attention_engaged(
    backend: Optional[str], s_q: int, n_q: int, n_kv: int, head_dim: int,
    page_size: int, v_dim: Optional[int] = None,
) -> bool:
    """Whether a decode batch's attention runs `lane_attention`: on a TPU
    under either ragged backend when the shapes are eligible; off it only
    under 'ragged' (interpreted, at any shape: tests), so a CPU program
    under 'ragged_xla' is the XLA reference's."""
    if s_q != 1 or backend not in ("ragged", "ragged_xla"):
        return False
    if _interpret():
        return backend == "ragged"
    return lane_attention_eligible(n_q, n_kv, head_dim, page_size, v_dim)


def _largest_divisor(n: int, cap: int) -> int:
    return max(d for d in range(1, max(1, min(n, cap)) + 1) if n % d == 0)


def lane_blocks(pages: int, page_size: int, n_kv: int, head_dim: int,
                itemsize: int, chased: bool = False,
                block_bytes: Optional[int] = None) -> Tuple[int, int]:
    """(pages a key block, pages an inner tile) of `lane_attention` over
    `pages` pages a lane. A block is what one grid step fetches: whole
    pages that lie together in the pool, as many as _LANE_BLOCK_BYTES
    hold; one page where a page table is chased (`chased`), since the
    next logical page may live anywhere. A tile is what one online-softmax
    update covers: _LANE_TILE_COLS score columns, a page at the least.
    Where ONE page's columns pass that (16 k/v heads at pages of 128:
    2,048), a block is as many times fewer pages: its rows are rounded up
    a lane, and a lane of so wide a row holds few. Measured (PERF.md
    section 6, PR 47; 16 k/v heads of 128 in bf16, a layer): 2 pages a
    block read 27 stepped lanes of 128-768 rows in 216 us where 4 take
    252, and 5 stepped lanes in 40 / 57 / 78 us (extents 512 / 1,024 /
    2,048) where 4 take 45 / 61 / 76; 1 page takes 196 and 41 / 62 / 93
    (448 grid steps a call at extent 2,048)."""
    if chased:
        return 1, 1
    page_cols = page_size * n_kv
    page_bytes = page_cols * head_dim * itemsize
    page_tiles = max(1, page_cols // _LANE_TILE_COLS)
    per_block = _largest_divisor(
        pages, (block_bytes or _LANE_BLOCK_BYTES) // page_bytes // page_tiles
    )
    per_tile = _largest_divisor(
        per_block, min(8, _LANE_TILE_COLS // page_cols)
    )
    return per_block, per_tile


def lane_pages_held(
    lengths, page_size: int, pages: int, window: Optional[int],
    ring_table=None, xp=jnp,
):
    """[B, pages] int32: the logical page whose keys the lane's query (at
    position lengths - 1) sees in each page the grid visits, -1 where it
    sees none (a lane not stepped, a page past the length or wholly before
    the window). Whole pages are visited in logical order; a ring's
    (`ring_table`) in physical order, each holding what the table put
    there. `xp` as in _ring_resident: one rule for the kernel's plan and
    for the host's count of it."""
    L = lengths.astype(xp.int32)[:, None]
    if ring_table is None:
        logical = xp.broadcast_to(
            xp.arange(pages, dtype=xp.int32)[None], (L.shape[0], pages)
        )
    else:
        logical = _ring_resident(ring_table, lengths, page_size, pages, xp)
    seen = xp.logical_and(logical >= 0, logical * page_size < L)
    if window is not None:
        seen = xp.logical_and(
            seen, (logical + 1) * page_size - 1 >= L - window
        )
    return xp.where(seen, logical, -1)


def lane_grid_blocks(lengths, block_rows: int, blocks: int, xp=jnp):
    """Key blocks a lane that lane_attention's grid visits over whole
    pages, of the `blocks` the plan has: up to the longest lane's last,
    one at the least. Whole pages are visited in logical order, so no
    later block holds a key any lane's query sees. On the device it is a
    number the grid reads (a dynamic bound), not a shape: one program
    whatever the lanes hold, and no grid step for rows that no lane has.
    `xp` as in _ring_resident: the kernel's bound and the host's count of
    its steps are one rule."""
    longest = xp.max(lengths, initial=0)
    return xp.clip(-(-longest // block_rows), 1, blocks)


def lane_plan(
    meta: LaneMeta, lanes: int, pool_rows: int, n_kv: int, head_dim: int,
    itemsize: int, ring: bool = False, block_bytes: Optional[int] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array, int, int]:
    """What lane_attention's grid does, from the lanes' metadata alone:
    (held [lanes, pages]: lane_pages_held over the pages the grid visits;
    slot, block [lanes x blocks]: where each grid step's block of k/v lies
    in the pool; pages a block; pages a tile). `meta.extent` bounds the
    pages of a lane's whole pages, the ring is visited whole. A step that
    reads nothing keeps the block of the live step before it (the first
    live step's, ahead of that): same index, no DMA."""
    ps = meta.page_size
    pool_pages = pool_rows // ps
    chased = not ring and (
        meta.global_pages
        or (meta.page_table is not None and not meta.identity_pages)
    )
    pages = pool_pages
    if not ring and meta.extent is not None and meta.extent < pool_rows:
        pages = meta.extent // ps
    if chased:
        pages = min(pages, meta.page_table.shape[1])
    per_block, per_tile = lane_blocks(
        pages, ps, n_kv, head_dim, itemsize, chased, block_bytes
    )
    nb = pages // per_block
    held = lane_pages_held(
        meta.lengths[:lanes], ps, pages, meta.window,
        meta.ring_table[:lanes] if ring else None,
    )
    slot = jnp.asarray(np.repeat(np.arange(lanes, dtype=np.int32), nb))
    blk = jnp.asarray(np.tile(np.arange(nb, dtype=np.int32), lanes))
    if chased:
        # A logical page lies where the table says: a page of the lane's
        # own slot, or (global ids) of any slot.
        blk = meta.page_table.astype(jnp.int32)[:lanes, :pages].reshape(-1)
        if meta.global_pages:
            slot, blk = blk // pool_pages, blk % pool_pages
    live = jnp.any(
        held.reshape(lanes, nb, per_block) >= 0, axis=2
    ).reshape(-1)
    step = jnp.arange(lanes * nb, dtype=jnp.int32)
    last = jax.lax.cummax(jnp.where(live, step, -1), axis=0)
    src = jnp.where(last >= 0, last, jnp.argmax(live).astype(jnp.int32))
    return held, slot[src], blk[src], per_block, per_tile


def _lane_kernel(
    len_ref,  # scalar prefetch [B]
    held_ref,  # scalar prefetch [B * pages]: lane_pages_held, flat
    slot_ref,  # scalar prefetch [B * blocks]: the block's slot in the pool
    blk_ref,  # scalar prefetch [B * blocks]: and its block of that slot
    rowh_ref, colh_ref, colk_ref, q_ref, *rest,
    scale, window, page_size, per_block, per_tile, cols, v_dim, parts, sink,
    blocks,
):
    del slot_ref, blk_ref  # the index maps read them
    # `blocks`: key blocks a lane in the plan (held_ref's layout); the
    # grid may stop short of them (lane_grid_blocks).
    # `parts`: the key comes as that many operands, each as wide as the
    # value, and the query's columns side by side in their order.
    # `v_dim`: no v operand, the value is the key's first v_dim columns.
    # `sink`: one more operand, each query head's sink logit on every lane.
    rest = list(rest)
    k_refs = [rest.pop(0) for _ in range(parts)]
    v_ref = None if v_dim else rest.pop(0)
    sink_ref = rest.pop(0) if sink else None
    o_ref, m_scr, l_scr, acc_scr, bias_scr = rest
    b = pl.program_id(0)
    j = pl.program_id(1)
    nj = pl.num_programs(1)

    @pl.when(jnp.logical_and(b == 0, j == 0))
    def _heads():
        # Column c of a tile is key c // kv_heads under k/v head
        # c % kv_heads; query row r belongs to k/v head r // group. One
        # matmul scores every query head against every k/v head's keys;
        # this keeps a head's own.
        bias_scr[:] = jnp.where(
            rowh_ref[:, :1] == colh_ref[:1, :], 0.0, NEG_INF
        )

    @pl.when(j == 0)
    def _init():
        if sink:
            # sink_softmax's rule: a column every query sees that gives
            # no value. (A lane not stepped then reads 0 / 1.)
            m_scr[:] = sink_ref[:]
            l_scr[:] = jnp.ones_like(l_scr)
        else:
            m_scr[:] = jnp.full_like(m_scr, NEG_INF)
            l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    qpos = len_ref[b] - 1
    first = (b * blocks + j) * per_block  # this block's first page in held_ref

    def tile(t):
        page0 = first + t * per_tile
        held = [held_ref[page0 + g] for g in range(per_tile)]
        live = held[0] >= 0
        for h in held[1:]:
            live = jnp.logical_or(live, h >= 0)

        @pl.when(live)
        def _compute():
            at = pl.ds(pl.multiple_of(t * cols, cols), cols)
            # [cols, width] a part: keys x k/v heads
            ks = [k_ref[0, at, :] for k_ref in k_refs]
            v = ks[0][:, :v_dim] if v_dim else v_ref[0, at, :]
            w = ks[0].shape[1]
            s = functools.reduce(operator.add, [
                jax.lax.dot_general(
                    q_ref[0] if parts == 1 else q_ref[0, :, i * w:(i + 1) * w],
                    k, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
                for i, k in enumerate(ks)
            ])
            s = s * scale + bias_scr[:]  # [Hq, cols]
            colk = colk_ref[:1, :]  # the column's key, within the tile
            kpos = jnp.where(held[0] >= 0, held[0] * page_size + colk, -1)
            for g in range(1, per_tile):
                kpos = jnp.where(
                    colk >= g * page_size,
                    jnp.where(
                        held[g] >= 0, (held[g] - g) * page_size + colk, -1
                    ),
                    kpos,
                )
            keep = jnp.logical_and(kpos >= 0, kpos <= qpos)
            if window:
                keep = jnp.logical_and(keep, qpos - kpos < window)
            s = jnp.where(keep, s, NEG_INF)
            m_prev = m_scr[:, :]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1)[:, None])
            alpha = jnp.exp(m_prev - m_new)
            # A live tile holds a key the query sees, so every query row
            # has a real maximum and a masked column's exp is 0.
            p = jnp.exp(s - m_new[:, :1])
            l_scr[:, :] = l_scr[:, :] * alpha + jnp.sum(p, axis=-1)[:, None]
            acc_scr[:] = acc_scr[:] * alpha[:, :1] + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            m_scr[:, :] = m_new

    n_tiles = per_block // per_tile
    if n_tiles == 1:
        tile(0)
    else:
        jax.lax.fori_loop(0, n_tiles, lambda t, c: (tile(t), c)[1], 0)

    @pl.when(j == nj - 1)
    def _finalize():
        l = l_scr[:, :]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[:] / safe_l[:, :1]).astype(o_ref.dtype)


def lane_attention(
    q: jax.Array, k: jax.Array, v: Optional[jax.Array], meta: LaneMeta,
    ring: bool = False, *, scale: Optional[float] = None, v_dim: int = 0,
    sink: Optional[jax.Array] = None,
) -> jax.Array:
    """A decode batch's attention, one query row a lane, over the pool as
    it lies: q [B, 1, Hq, D]; k [T, C, Hkv, D] and v [T, C, Hkv, Dv] with
    lane b in slot b (T > B: a prefix-cache arena behind the lanes), whole
    pages or, with `ring`, the lanes' rings of pages
    (LaneMeta.ring_table). A key kept in parts (Config.key_parts): `k` a
    tuple of [T, C, Hkv, Dv] arrays whose columns side by side are the D
    of q; a tile's scores are the sum of a matmul a part. `sink` [Hq]
    float32 (sink_softmax's rule): a head's running maximum starts at its
    sink's logit and its denominator at 1. Returns [B, 1, Hq, Dv].

    Grid (lane, key block); a block is whole pages with every k/v head,
    so a row of k/v is fetched once. `meta.lengths` say what a lane
    holds; a block in which the lane's query (position lengths - 1) sees
    no key (a lane not stepped, pages past the length, pages wholly
    before the window, ring pages that hold nothing) is pinned by the
    index map to the block fetched last, so it costs neither a DMA nor
    arithmetic. Key j is seen iff 0 <= i - j (< window) and the row holds
    it: whole pages hold their row numbers through the page table
    (identity, a lane's own permutation, or `global_pages` ids into any
    slot), a ring what its table put there (ring_key_positions' rule, a
    page at a time). Online softmax in float32, probabilities cast to
    v's dtype: banded_attention_xla's rounding. `meta.extent` bounds the
    grid, not the operand: no slice of the pool is made. Over whole pages
    the grid's key blocks stop at the longest lane's last
    (lane_grid_blocks: a bound the program reads, so one program serves
    every length and a tick of short lanes pays no step for the rows a
    slot could hold).

    Inside a block, all k/v heads go through the MXU together: k flat as
    [keys x kv_heads, D] against every query head, and a constant mask
    keeps each query head's own k/v head. Wasted MXU work (kv_heads
    times) for no transpose of the pool's row.

    A latent entry (models/layers.py LatentPages: ONE row a token,
    Hkv == 1): `v=None` and `v_dim`, the value is the first v_dim columns
    of the key row, so a row is fetched once for both matmuls; `scale`
    is the mixer's own (default D^-1/2). Returns [B, 1, Hq, v_dim]."""
    assert q.shape[1] == 1, "one query row a lane"
    ks = k if isinstance(k, tuple) else (k,)
    assert ks[0].shape[1] % meta.page_size == 0, (ks[0].shape, meta.page_size)
    assert sum(a.shape[3] for a in ks) == q.shape[3], (
        q.shape, [a.shape for a in ks])
    if ring:
        # A ring is visited whole: without the tick's extent in it, the
        # ring layers of every tick program share one trace.
        meta = meta.replace(extent=None)
    return _lane_attention(
        q, k, v, meta, ring, _interpret(), _LANE_BLOCK_BYTES,
        scale or 1.0 / (q.shape[3]**0.5), v_dim, sink,
    )


# Jitted with everything that shapes the kernel static, so that the layers
# of one tick that share shapes (three rings) are traced and lowered once.
@functools.partial(jax.jit, static_argnums=(4, 5, 6, 7, 8))
def _lane_attention(q, k, v, meta, ring, interpret, block_bytes, scale,
                    v_dim, sink=None):
    B, _, Hq, D = q.shape
    ks = k if isinstance(k, tuple) else (k,)
    Dv = v_dim or v.shape[3]
    T, C, Hkv, W = ks[0].shape
    ps = meta.page_size
    group = Hq // Hkv
    lengths = meta.lengths.astype(jnp.int32)[:B]
    held, slot, blk, per_block, per_tile = lane_plan(
        meta, B, C, Hkv, D, ks[0].dtype.itemsize, ring, block_bytes
    )
    nb = held.shape[1] // per_block
    # A ring is visited whole, in physical order.
    steps = nb if ring else lane_grid_blocks(lengths, per_block * ps, nb)

    Hp = -(-Hq // 16) * 16
    cols = per_tile * ps * Hkv
    rows = per_block * ps * Hkv
    qf = jnp.pad(q[:, 0], ((0, 0), (0, Hp - Hq), (0, 0)))
    # (Constants of the shapes: numpy, so nothing of them is lowered.) A
    # padded query row belongs to no k/v head.
    row_head = np.where(np.arange(Hp) < Hq, np.arange(Hp) // group, Hkv)
    col = np.arange(cols, dtype=np.int32)

    def kv_map(b, j, lengths, held, slot, blk):
        return (slot[b * nb + j], blk[b * nb + j], 0)

    const = lambda b, j, *_: (0, 0)  # noqa: E731
    own = lambda b, j, *_: (b, 0, 0)  # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B, steps),
        in_specs=[
            pl.BlockSpec((Hp, LANES), const),
            pl.BlockSpec((8, cols), const),
            pl.BlockSpec((8, cols), const),
            pl.BlockSpec((1, Hp, D), own),
        ] + [pl.BlockSpec((1, rows, W), kv_map)] * len(ks) + (
            [] if v_dim else [pl.BlockSpec((1, rows, Dv), kv_map)]
        ) + ([] if sink is None else [pl.BlockSpec((Hp, LANES), const)]),
        out_specs=pl.BlockSpec((1, Hp, Dv), own),
        scratch_shapes=[
            pltpu.VMEM((Hp, LANES), jnp.float32),
            pltpu.VMEM((Hp, LANES), jnp.float32),
            pltpu.VMEM((Hp, Dv), jnp.float32),
            pltpu.VMEM((Hp, cols), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _lane_kernel, scale=scale, window=int(meta.window or 0),
            page_size=ps, per_block=per_block, per_tile=per_tile, cols=cols,
            v_dim=v_dim, parts=len(ks), sink=sink is not None, blocks=nb,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hp, Dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_LANE_VMEM_LIMIT,
        ),
        interpret=interpret,
        name="lane_attention",
    )(
        lengths, held.reshape(-1), slot, blk,
        np.broadcast_to(row_head.astype(np.int32)[:, None], (Hp, LANES)),
        np.broadcast_to((col % Hkv)[None], (8, cols)),
        np.broadcast_to((col // Hkv)[None], (8, cols)),
        qf,
        # The pool's row [Hkv, width] is whole tiles: flat for free.
        *(a.reshape(T, C * Hkv, W) for a in ks),
        *(() if v_dim else (v.reshape(T, C * Hkv, Dv),)),
        *(() if sink is None else (jnp.broadcast_to(
            jnp.pad(sink.astype(jnp.float32), (0, Hp - Hq))[:, None],
            (Hp, LANES)),)),
    )
    return out[:, None, :Hq]


def paged_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    meta: LaneMeta,
    *,
    backend: str = "ragged",
    positions: Optional[jax.Array] = None,
    scale: Optional[float] = None,
    sink: Optional[jax.Array] = None,
) -> jax.Array:
    """Backend dispatcher (config.attention_backend) over k / v as the
    pool keeps them, unsliced: a decode batch (one q row a lane) takes
    `lane_attention` where lane_attention_engaged says so (on a TPU by
    the shapes, under 'ragged' and 'ragged_xla' alike; off it under
    'ragged' alone, interpreted), and the XLA reference otherwise, which
    reads the `meta.extent` slice of the rows. Prefill chunks (Sq > 1)
    always take the reference."""
    _, Sq, Hq, _ = q.shape
    k0 = k[0] if isinstance(k, tuple) else k
    if lane_attention_engaged(backend, Sq, Hq, k0.shape[2], k0.shape[3],
                              meta.page_size, v.shape[3]):
        return lane_attention(q, k, v, meta, scale=scale, sink=sink)
    k = whole_key(k)
    if (
        not meta.global_pages
        and meta.extent is not None and meta.extent < k.shape[1]
    ):
        # The resident-extent slice: the reference reads O(tokens
        # resident), not O(pool capacity). (Under global_pages it slices
        # the page TABLE instead: physical pages may live in any slot.)
        k = jax.lax.slice_in_dim(k, 0, meta.extent, axis=1)
        v = jax.lax.slice_in_dim(v, 0, meta.extent, axis=1)
    return ragged_paged_attention_xla(
        q, k, v, meta, positions=positions, scale=scale, sink=sink)
