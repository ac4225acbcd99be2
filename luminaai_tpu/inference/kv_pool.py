"""Slot-paged KV cache pool for continuous (in-flight) batching.

The serving decode path keeps ONE preallocated KV pool shaped
`[num_slots, pages, page_size, kv_heads, head_dim]` per layer (per k/v;
int8 caches carry a (codes, scales) pair per side) instead of allocating
a fresh cache per batch. Requests are admitted into *slots* — the unit
the host-side free-list hands out — and a slot's KV region is tiled into
`pages` of `page_size` tokens, the TPU-friendly granularity the ragged
paged-attention literature standardizes on (arxiv 2604.15464): page-
aligned rows keep cache writes on (8,128)-tiled boundaries and leave the
door open to page-level sharing/compaction without relayout.

The pool holds TWO kinds of entry a layer, and the layer's mixer says
which (`LuminaTransformer.init_cache`): pages of k/v as above, or a fixed
state a lane (`models/ssm.py::LaneState`: a state-space layer's
[state, channels] float32 state and its convolution's tail, `[num_slots,
...]`, the same size whatever the lane holds). A state has no rows: it is
never paged, sliced to an extent or addressed through the page table, a
slot's admission starts it from zero on the device, and a page that is
exported, imported or shared carries none of it, which is why a pool with
states refuses those three by name (`StateNotPagedError`).

A layer with a window of its own (`Config.layer_windows`) keeps a THIRD
kind: a RING of pages, `[num_slots, ring_pages, page_size, kv_heads,
head_dim]` with `ring_pages = ceil((window + prefill chunk) / page_size) +
1`, where a full layer beside it keeps all `pages`. Positions stay
absolute: logical page j of a slot's ring layer lives at physical page
`ring_tables[slot, j]` (j mod ring_pages), written and read through that
table (`ops/ragged_paged_attention.py::ring_key_positions`). A ring keeps
no page older than the window, so what needs an old page is refused by
name (`RingKeepsWindowError`): the prefix cache, a page export or import,
speculation's k-row verify; so are int8 k/v and a pool without chunked
prefill, which the ring's write path does not serve.

A 'latent' layer (models/layers.py LatentAttention) keeps a FOURTH kind:
pages of ONE row a token, `[num_slots, pages, page_size, 1, width]` with
width = kv_lora_rank + qk_rope_head_dim padded to whole 128-lane tiles
(576 -> 640), shared by every head (`LatentPages`; expanded k/v of 64
heads x (192 + 128) would be 32 times that). Positions are absolute and
the rotation is in the stored row, so it is paged, inserted, rebuilt and
exported as k or v are, the axis of length one standing where they keep
their heads; what reads it is the mixer's absorbed attention. The prefix
cache over it is refused by name (`LatentPagesOwnedError`): the absorbed
attention reads a lane's own pages in place and chases no table yet.

Device arrays live here only as an opaque pytree (`self.caches`); all
accounting — the free-list, per-slot length vector, reuse counters — is
host-side numpy, so the scheduler never has to read device memory to
make an admission decision. The pool is deliberately dumb: it allocates
and frees slots and REFUSES to double-allocate; which request occupies a
slot, and when it is evicted, is the ContinuousScheduler's business
(serving/server.py), and how rows are written per-lane is the attention
layer's (models/layers.py per-lane cache update).
"""

from __future__ import annotations

import json
import math
import threading
from typing import Any, List, Optional

import numpy as np

# Wire format for one serialized KV page (cross-replica page pulls,
# ISSUE 20): magic, 4-byte big-endian header length, JSON header
# {"page_size": int, "leaves": [{"shape": [...], "dtype": "..."}]},
# then each leaf's C-order bytes concatenated in tree-flatten order.
# int8 pools need no special casing — codes and scales are separate
# tree leaves and each frames its own slice.
PAGE_WIRE_MAGIC = b"LPG1"


class StateNotPagedError(NotImplementedError):
    """Asked of a pool that holds a fixed state a lane beside its pages:
    something that moves or shares PAGES (the prefix cache, a page export
    or import) and would leave the state behind."""


class RingKeepsWindowError(NotImplementedError):
    """Asked of a pool in which some layer keeps a ring of pages a lane
    (a window of its own): something that needs a page older than the
    window, or a write path the ring does not have."""


class LatentPagesOwnedError(NotImplementedError):
    """Asked of a pool in which some layer keeps pages of one latent a
    token: something that reads a lane's pages through another slot's
    (the prefix cache's splice), which the absorbed attention over a
    latent entry does not follow yet."""


def map_pages(fn, tree, *rest, states=None):
    """`fn` over the paged (k/v) leaves of a cache tree; a lane's fixed
    states go through `states` (as they are when it is None)."""
    import jax

    from luminaai_tpu.models.ssm import is_lane_state

    def entry(x, *r):
        if is_lane_state(x):
            return x if states is None else states(x, *r)
        return jax.tree.map(fn, x, *r)

    return jax.tree.map(entry, tree, *rest, is_leaf=is_lane_state)


def lane_states(tree) -> list:
    """The fixed states (one a state-space layer) of a cache tree."""
    import jax

    from luminaai_tpu.models.ssm import is_lane_state

    return [
        x for x in jax.tree.leaves(tree, is_leaf=is_lane_state)
        if is_lane_state(x)
    ]


def parse_page_payload(payload: bytes) -> List[np.ndarray]:
    """Decode a PAGE_WIRE_MAGIC-framed payload into per-leaf numpy
    slices (tree-flatten order). Raises ValueError on any framing
    mismatch — truncated, trailing, or mislabeled bytes must never
    reach the device arena."""
    if payload[:4] != PAGE_WIRE_MAGIC:
        raise ValueError("bad page payload magic")
    if len(payload) < 8:
        raise ValueError("truncated page payload header")
    hlen = int.from_bytes(payload[4:8], "big")
    try:
        header = json.loads(payload[8:8 + hlen].decode("utf-8"))
    except (UnicodeDecodeError, json.JSONDecodeError) as e:
        raise ValueError(f"bad page payload header: {e}") from e
    off = 8 + hlen
    out: List[np.ndarray] = []
    for meta in header.get("leaves", []):
        try:
            dt = np.dtype(meta["dtype"])
        except TypeError:
            import ml_dtypes  # noqa: F401  registers bfloat16 et al.

            dt = np.dtype(meta["dtype"])
        shape = tuple(int(d) for d in meta["shape"])
        n = math.prod(shape) * dt.itemsize
        buf = payload[off:off + n]
        if len(buf) != n:
            raise ValueError("truncated page payload body")
        out.append(np.frombuffer(buf, dtype=dt).reshape(shape))
        off += n
    if off != len(payload):
        raise ValueError("trailing bytes after page payload")
    return out


def to_paged(tree, page_size: int):
    """Reshape a model-layout cache tree into the paged pool layout:
    [..., C, heads, dim] leaves become [..., C / page_size, page_size,
    heads, dim]: the pool's `pages` for a layer that keeps whole pages,
    its own fewer for a layer that keeps a ring. The row axis is addressed
    from the TAIL (ndim-3) so the rule covers both the plain per-layer
    layout ([slots, C, ...]) and the scan_layers layout with its extra
    leading segment axis ([count, slots, C, ...]). Pure metadata under
    jit (the rows are contiguous)."""
    return map_pages(
        lambda x: x.reshape(
            x.shape[:-3] + (x.shape[-3] // page_size, page_size)
            + x.shape[-2:]
        ),
        tree,
    )


def to_flat(tree, page_size: int):
    """Inverse of to_paged: the [..., rows, heads, dim] view
    the model's attention layers consume (a ring's own pages for a ring)."""
    return map_pages(
        lambda x: x.reshape(
            x.shape[:-4] + (x.shape[-4] * page_size,) + x.shape[-2:]
        ),
        tree,
    )


class PagedKVPool:
    """Host-side slot accounting over a preallocated paged KV cache tree.

    caches: the device pytree in paged layout (or None for accounting-only
    use in tests/fakes). alloc()/free() manage the slot free-list; lengths
    tracks rows in use per slot (the attention mask budget); reuses counts
    how many times a previously-occupied slot was handed out again — the
    continuous-batching win condition.
    """

    def __init__(
        self,
        caches: Optional[Any],
        num_slots: int,
        pages: int,
        page_size: int,
        ring_pages: int = 0,
    ):
        if num_slots < 1 or pages < 1 or page_size < 1:
            raise ValueError(
                f"pool needs >=1 slot/page/row, got "
                f"{num_slots}/{pages}/{page_size}"
            )
        self.caches = caches
        self._keeps_state: Optional[bool] = None  # read once from the tree
        self.num_slots = int(num_slots)
        self.pages = int(pages)
        self.page_size = int(page_size)
        self.lengths = np.zeros((num_slots,), np.int64)
        # Per-slot page table: logical page j of slot s lives at physical
        # page `page_tables[s, j]` of the slot's own page axis. Identity
        # today — the indirection is the seam page sharing / compaction
        # (prefix caching, ROADMAP item 2) will retarget; the ragged
        # attention kernel already chases it. Rows are RESET to identity
        # at alloc and never mutated while a slot is live, so a live
        # lane's pages can never silently alias another's (contract-
        # tested).
        self.page_tables = np.tile(
            np.arange(pages, dtype=np.int32), (num_slots, 1)
        )
        # Layers that keep a RING of `ring_pages` pages a lane (0: none
        # does): logical page j of slot s lives at physical page
        # `ring_tables[s, j]` of the ring, the only place that says where
        # a position's row is. Never retargeted: a ring's pages are the
        # lane's own and are shared with nobody.
        self.ring_pages = int(ring_pages)
        self.ring_tables = (
            np.tile(
                np.arange(pages, dtype=np.int32) % self.ring_pages,
                (num_slots, 1),
            )
            if self.ring_pages else None
        )
        # LIFO free-list: the most recently freed slot is re-issued first,
        # so its cache rows are the warmest in HBM when overwritten.
        self._free: List[int] = list(range(num_slots - 1, -1, -1))
        self._allocated: set = set()
        self.reuses = 0
        # Times the owner replaced a cache tree that a failed donating
        # call had taken (StepwiseDecoder.recover_pool).
        self.rebuilds = 0
        self.slot_uses = np.zeros((num_slots,), np.int64)
        # Accounting lock: the scheduler worker mutates the free-list
        # while /healthz and /metrics HTTP threads read stats() —
        # unguarded, iterating _allocated during an alloc()/free() raises
        # "Set changed size during iteration" and drops the probe. RLock
        # so stats() can call the public occupancy helpers.
        self._lock = threading.RLock()

    @property
    def slot_tokens(self) -> int:
        """Token capacity of one slot (pages * page_size rows)."""
        return self.pages * self.page_size

    def free_count(self) -> int:
        return len(self._free)

    def has_free(self) -> bool:
        return bool(self._free)

    def alloc(self) -> int:
        """Hand out a free slot. Raises when exhausted; a slot can never
        be live twice (the double-allocation class of bug that silently
        interleaves two requests' KV rows)."""
        with self._lock:
            if not self._free:
                raise RuntimeError("KV pool exhausted: no free slots")
            slot = self._free.pop()
            if slot in self._allocated:  # pragma: no cover - invariant guard
                raise RuntimeError(f"slot {slot} double-allocated")
            self._allocated.add(slot)
            # Fresh occupants start from the identity layout; a future
            # prefix cache retargets entries AFTER alloc, never across a
            # free/realloc boundary.
            self.page_tables[slot] = np.arange(self.pages, dtype=np.int32)
            if self.slot_uses[slot] > 0:
                self.reuses += 1
            self.slot_uses[slot] += 1
            return slot

    def free(self, slot: int) -> None:
        """Return a slot to the free-list. Stale rows are NOT zeroed —
        every consumer masks by length, and the next prefill overwrites
        the rows it needs. The page-table row IS reset to identity here
        (not just at the next alloc): with page sharing a freed slot's
        stale entry aliasing a since-evicted cached page is a
        silent-corruption class — a decode step between free and realloc
        still gathers through every lane's table row (masked lanes'
        output is discarded, but the gather indices must stay honest),
        so the tombstone cannot wait for alloc (contract-tested across
        the free → cache-evict → realloc ordering)."""
        with self._lock:
            if slot not in self._allocated:
                raise ValueError(f"slot {slot} is not allocated")
            self._allocated.remove(slot)
            self.lengths[slot] = 0
            self.page_tables[slot] = np.arange(self.pages, dtype=np.int32)
            self._free.append(slot)

    def allocated_slots(self) -> List[int]:
        with self._lock:
            return sorted(self._allocated)

    # -- device-transferable metadata views ------------------------------
    def page_table_array(self) -> np.ndarray:
        """[num_slots, pages] int32 SNAPSHOT of the page tables — a copy,
        so the scheduler can hand it to a jit call while HTTP threads
        alloc/free, and mutating the view can never corrupt pool
        accounting. Identity rows for every slot today (contract-tested
        with the no-alias invariant)."""
        with self._lock:
            return self.page_tables.copy()

    def lengths_array(self) -> np.ndarray:
        """[num_slots] int32 snapshot of rows resident per slot (0 for
        free slots) — the `lengths` operand of the ragged attention
        kernel, in the dtype it wants on device."""
        with self._lock:
            return self.lengths.astype(np.int32)

    def buffers_alive(self) -> bool:
        """False once a program that DONATES the cache tree (every one
        that rewrites it does) failed after the runtime had taken the
        buffers: the leaves are deleted and no new tree came back.
        Asked after an exception, never on the hot path."""
        import jax

        return not any(
            leaf.is_deleted() for leaf in jax.tree.leaves(self.caches)
        )

    @property
    def keeps_state(self) -> bool:
        """True when some layer keeps a fixed state a lane beside the
        pages (read from the cache tree, whatever mixer made it)."""
        if self._keeps_state is None:
            if self.caches is None:
                return False
            self._keeps_state = bool(lane_states(self.caches))
        return self._keeps_state

    def slot_bytes(self) -> dict:
        """What ONE slot holds on the device, by entry kind: whole pages
        of k/v (`pages` a layer), rings of pages (`ring_pages` a layer),
        pages of one latent a token, fixed states. Read from the cache
        tree's own shapes."""
        import jax

        from luminaai_tpu.models.layers import is_latent_pages
        from luminaai_tpu.models.ssm import is_lane_state

        out = {"pages": 0, "ring": 0, "latent": 0, "state": 0}
        if self.caches is None:
            return out
        for entry in jax.tree.leaves(
            self.caches,
            is_leaf=lambda x: is_lane_state(x) or is_latent_pages(x),
        ):
            if is_lane_state(entry):
                out["state"] += entry.nbytes() // entry.state.shape[-3]
                continue
            if is_latent_pages(entry):
                out["latent"] += entry.rows.nbytes // entry.rows.shape[-5]
                continue
            ring = entry.shape[-4] != self.pages
            out["ring" if ring else "pages"] += (
                entry.nbytes // entry.shape[-5]
            )
        out["total"] = sum(out.values())
        return out

    def _pages_only(self, what: str) -> None:
        if self.ring_pages:
            raise RingKeepsWindowError(
                f"{what}: a layer of this pool keeps a ring of "
                f"{self.ring_pages} pages a lane (a window of its own), "
                "and a ring keeps no page older than the window: the page "
                "asked for may hold another position's rows by now"
            )
        if self.keeps_state:
            raise StateNotPagedError(
                f"{what}: this pool keeps a fixed state a lane (an 'ssm' "
                "layer) beside its pages of k/v, and a page carries none "
                "of it"
            )

    # -- cross-replica page serialization (ISSUE 20) ---------------------
    def _locate(self, gid: int, leaves):
        """Global page id -> physical (slot, page). Bounds-checked
        against the PHYSICAL slot axis of the cache tree (`leaves`), not
        `num_slots`: the prefix-cache arena lives in extra slots past
        the lane pool (generate.py carves them out as
        total_slots > num_slots), and arena pages are exactly what the
        cross-replica tier exports and imports."""
        slot, page = divmod(int(gid), self.pages)
        physical = leaves[0].shape[-5]
        if not (0 <= slot < physical):
            raise ValueError(
                f"page id {gid} outside pool "
                f"({physical} physical slots x {self.pages} pages)"
            )
        return slot, page

    def export_page(self, gid: int) -> bytes:
        """Serialize ONE physical page (global id = slot * pages + page)
        into a framed host payload: every KV leaf's [page_size, heads,
        dim] slice (plus any leading scan_layers axes), device_get'd
        here — the transfer tier runs OFF the decode hot path, never
        inside a jitted step. int8 pools carry codes AND scales because
        both are tree leaves of the same paged layout.

        Runs on an HTTP thread while the scheduler thread rebinds
        `self.caches` every call and DONATES the old tree: the tree is
        read once, so a page comes from one tree or, when those leaves
        are taken mid-export, the read raises (export_page_by_key turns
        that into "not servable")."""
        import jax

        caches = self.caches
        if caches is None:
            raise RuntimeError("accounting-only pool has no cache tree")
        self._pages_only("page export")
        leaves = jax.tree.leaves(caches)
        slot, page = self._locate(gid, leaves)
        metas, blobs = [], []
        for leaf in leaves:
            # slot axis at ndim-5, page axis at ndim-4 (the ellipsis
            # absorbs scan_layers' leading segment axis when present).
            arr = np.ascontiguousarray(
                jax.device_get(leaf[..., slot, page, :, :, :])
            )
            metas.append({"shape": list(arr.shape),
                          "dtype": str(arr.dtype)})
            blobs.append(arr.tobytes())
        header = json.dumps(
            {"page_size": self.page_size, "leaves": metas}
        ).encode("utf-8")
        return b"".join(
            [PAGE_WIRE_MAGIC, len(header).to_bytes(4, "big"), header]
            + blobs
        )

    def import_page(self, gid: int, payload: bytes) -> int:
        """Write a pulled page's bytes into physical page `gid`
        (device_put off the hot path). Every leaf slice is validated
        against this pool's layout BEFORE the tree is touched — a
        mismatched payload (different model geometry, different
        kv_cache_dtype) raises instead of corrupting the arena.
        Returns the payload size in bytes for transfer accounting."""
        import jax

        if self.caches is None:
            raise RuntimeError("accounting-only pool has no cache tree")
        self._pages_only("page import")
        arrs = parse_page_payload(payload)
        leaves, treedef = jax.tree.flatten(self.caches)
        slot, page = self._locate(gid, leaves)
        if len(arrs) != len(leaves):
            raise ValueError(
                f"page payload has {len(arrs)} leaves, pool has "
                f"{len(leaves)}"
            )
        for arr, leaf in zip(arrs, leaves):
            want_shape = tuple(leaf.shape[:-5]) + tuple(leaf.shape[-3:])
            if tuple(arr.shape) != want_shape or (
                np.dtype(arr.dtype) != np.dtype(leaf.dtype)
            ):
                raise ValueError(
                    f"page leaf mismatch: got {arr.shape}/{arr.dtype}, "
                    f"pool wants {want_shape}/{np.dtype(leaf.dtype)}"
                )
        new = [
            leaf.at[..., slot, page, :, :, :].set(arr)
            for arr, leaf in zip(arrs, leaves)
        ]
        self.caches = jax.tree.unflatten(treedef, new)
        return len(payload)

    # -- occupancy accounting (telemetry) --------------------------------
    def pages_in_use(self) -> int:
        """Pages holding live KV rows: per allocated slot, its length
        rounded UP to whole pages (a page is the relayout/sharing unit,
        so a 1-token tail costs a full page — that cost is exactly what
        fragmentation_rows below makes visible)."""
        with self._lock:
            total = 0
            for slot in self._allocated:
                n = int(self.lengths[slot])
                if n > 0:
                    total += -(-n // self.page_size)
            return total

    def fragmentation_rows(self) -> int:
        """Rows allocated by page rounding but not holding KV: pages_in_use
        * page_size minus the live row count. High values mean page_size is
        oversized for the workload's typical sequence lengths."""
        with self._lock:
            live = int(
                sum(int(self.lengths[s]) for s in self._allocated)
            )
            return self.pages_in_use() * self.page_size - live

    def _length_summary(self) -> dict:
        """Min/mean/max live length over allocated slots (0s when idle):
        the at-a-glance shape of what the pool is holding."""
        with self._lock:
            vals = [int(self.lengths[s]) for s in self._allocated]
        if not vals:
            return {"min": 0, "mean": 0.0, "max": 0}
        return {
            "min": min(vals),
            "mean": round(sum(vals) / len(vals), 1),
            "max": max(vals),
        }

    def stats(self) -> dict:
        with self._lock:
            return {
                "num_slots": self.num_slots,
                "pages": self.pages,
                "page_size": self.page_size,
                "slot_tokens": self.slot_tokens,
                "in_use": len(self._allocated),
                "free": len(self._free),
                "reuses": self.reuses,
                "rebuilds": self.rebuilds,
                "pages_in_use": self.pages_in_use(),
                "pages_total": self.num_slots * self.pages,
                "fragmentation_rows": self.fragmentation_rows(),
                "lengths": self._length_summary(),
                "ring_pages": self.ring_pages,
            }
