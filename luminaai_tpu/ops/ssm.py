"""Mamba-1's selective scan: a per-channel linear recurrence over a
[state, channels] float32 state,

    h_t = exp(dt_t * A) * h_{t-1} + (dt_t * x_t) outer B_t      [N, D]
    y_t = h_t . C_t                                             [D]

in two forms.

`selective_scan` is the chunked scan in XLA (`lax.associative_scan` inside
chunks of 64 tokens, `lax.scan` over the chunks): differentiable, from zero
state or a given one. The uncached forward pass (training, the logits
comparison) and the single-sequence cache paths run it.

`ssm_scan` is the served form, ONE `pallas_call` a layer a tick
(`ssm_scan` on the device's op line): the tick's `lanes` decode rows step
each lane's stored state once, and the rows after them are one prefill
chunk, walked in order with its slot's state resident in VMEM, entering
from the stored state (from zero where `chunk_start == 0`: decided on the
device, so admission needs no reset program, transfer or sync) and written
back to that slot. A row at position -1 (a lane not stepped, the chunk's
padding) changes nothing: the pool's states stay in HBM, aliased in and
out, and only a stepped lane's slab and a live chunk's are copied to VMEM
and back, so an idle lane costs no traffic and a chunk with no live row
neither reads, walks nor writes. It also applies the skip (`D * x`) and
the gate (`silu(z)`), so that what leaves is the output projection's
operand.

Layout: channels on the lane axis everywhere (state [slots, N, D], rows
[R, D]); the grid runs over blocks of channels. A grid step starts the
copies of the stepped lanes' slabs of its block, forms `dt * x` while they
fly, steps each lane and sends its slab back while the chunk is walked. B
and C arrive broadcast over 128 lanes ([R, N, 128], 5 MB a layer at 320
rows): a row's column is then a leading-axis read, where a lane-axis slice
at a dynamic offset would not lower.

Mamba-2's heads (`models/ssm.py::ScalarDecaySSM`) are the same recurrence
with A and dt ONE scalar a head of P channels and B, C shared by a group
of heads: over the same [state, channels] layout,

    S_t[n, d] = exp(dt_t[h(d)] a[h(d)]) S_{t-1}[n, d]
                + dt_t[h(d)] x_t[d] B_t[g(d)][n]
    y_t[d]    = sum_n S_t[n, d] C_t[g(d)][n].

That structure is what the two forms written for it use; `ssm_scan` is
not widened (Jamba's call is the text it was):

`block_scan` is the chunk in BLOCK form in XLA: with L_t the running sum
of dt a over a block of `chunk` rows, y_t = C_t . (e^{L_t} S_0) +
sum_{s<=t} e^{L_t - L_s} dt_s (C_t . B_s) x_s and S_L = e^{L_L} S_0 +
sum_s e^{L_L - L_s} dt_s x_s outer B_s: three matmuls a group a block,
float32 at HIGHEST (the state is float32), `lax.scan` over the blocks.
Differentiable; from zero or from a given state. The uncached forward, the
single-sequence cache paths AND the tick's prefill chunk run it (the
chunk enters from its slot's stored state).

`ssm_scan_heads` steps the tick's LANES, one `pallas_call` a layer a tick
(`ssm_scan_heads` on the op line): the grid runs over blocks of channels
inside one group, and a grid step walks the lanes the tick steps (their
ids compacted by the caller) through a ring of VMEM buffers: lane k + 2's
[N, block] slab is fetched while lane k is stepped and lane k - 1's is
written back, so the pool's states stay in HBM, aliased in and out, and
an idle lane costs no traffic. B and C arrive transposed ([G, N, rows]);
a lane's column, spread over 128 lanes, is one product with a one-hot
matrix on the MXU (exact: one term a sum), where a lane-axis slice at a
dynamic offset would not lower.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from luminaai_tpu.ops import flash_attention as _fa

_F32 = jnp.float32
CHUNK = 64      # tokens a chunk of the XLA scan
_GROUP = 8      # rows a loop iteration of the kernel: one sublane tile
_LANES = 128
# A block has room for every lane's [N, block] slab (all lanes stepped).
_STATE_BLOCK_BYTES = 16 << 20
_VMEM_LIMIT = 100 << 20


def selective_scan(
    x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array, c: jax.Array,
    h0: Optional[jax.Array] = None, chunk: int = CHUNK,
) -> Tuple[jax.Array, jax.Array]:
    """x, dt [B,T,D]; a [N,D]; b, c [B,T,N]; h0 [B,N,D] or None (zero).
    Returns (y [B,T,D], h_T [B,N,D]), float32. A row with dt = 0 leaves
    the state as it is (padding is marked so by the caller)."""
    B, T, D = x.shape
    N = a.shape[0]
    x, dt, b, c = (t.astype(_F32) for t in (x, dt, b, c))
    a = a.astype(_F32)
    L = min(chunk, T)
    pad = -T % L
    if pad:
        x, dt, b, c = (jnp.pad(t, ((0, 0), (0, pad), (0, 0)))
                       for t in (x, dt, b, c))
    nc = (T + pad) // L

    def chunks(t):  # [B, nc*L, F] -> [nc, B, L, F]
        return jnp.swapaxes(t.reshape(B, nc, L, t.shape[-1]), 0, 1)

    def combine(left, right):
        (a1, b1), (a2, b2) = left, right
        return a1 * a2, a2 * b1 + b2

    def body(h, blk):
        x_c, dt_c, b_c, c_c = blk
        decay = jnp.exp(dt_c[:, :, None, :] * a)                  # [B,L,N,D]
        drive = (dt_c * x_c)[:, :, None, :] * b_c[..., None]
        total, hs = jax.lax.associative_scan(
            combine, (decay, drive), axis=1)
        hs = hs + total * h[:, None]
        return hs[:, -1], jnp.einsum("blnd,bln->bld", hs, c_c)

    if h0 is None:
        h0 = jnp.zeros((B, N, D), _F32)
    h, y = jax.lax.scan(body, h0.astype(_F32),
                        tuple(chunks(t) for t in (x, dt, b, c)))
    y = jnp.swapaxes(y, 0, 1).reshape(B, nc * L, D)[:, :T]
    return y, h


def _block_width(slots: int, n: int, d: int) -> int:
    """Channels a grid step: the widest multiple of 128 that divides D
    and keeps one block of every lane's state under _STATE_BLOCK_BYTES
    (the whole of D where D is no multiple of 128: tiny test sizes)."""
    if d % _LANES:
        return d
    width = _LANES
    while (d % (2 * width) == 0
           and slots * n * 2 * width * 4 <= _STATE_BLOCK_BYTES):
        width *= 2
    return width


def _kernel(pos_ref, chunk_ref, s_hbm, x_ref, z_ref, dt_ref, bb_ref, cb_ref,
            a_ref, d_ref, y_ref, so_hbm, buf, u_scr, y_scr, sem, *,
            lanes: int, lane_rows: int, chunk_rows: int):
    slots = buf.shape[0] - 1        # buf[slots] is the chunk's slab
    width = buf.shape[2]
    cols = pl.ds(pl.program_id(0) * width, width)
    c_slot, c_start = chunk_ref[0], chunk_ref[1]
    a = a_ref[...]

    def fetch(slot, k):
        return pltpu.make_async_copy(
            s_hbm.at[slot, :, cols], buf.at[k], sem.at[0])

    def store(slot, k):
        return pltpu.make_async_copy(
            buf.at[k], so_hbm.at[slot, :, cols], sem.at[1])

    def wait_all(copy, n):
        # Every slab is one size and one direction shares a semaphore:
        # n waits are n copies landed, whichever they were.
        jax.lax.fori_loop(0, n, lambda _, c: (copy.wait(), c)[1], 0)

    def wide(col):  # [N, 128] (one value a row of it) -> [N, width]
        reps = width // col.shape[1]
        return col if reps <= 1 else jnp.concatenate([col] * reps, axis=1)

    def advance(h, r, dt_row, u_row):
        h = jnp.exp(dt_row * a) * h + u_row * wide(bb_ref[r])
        return h, jnp.sum(h * wide(cb_ref[r]), axis=0, keepdims=True)

    def live_at(r):
        return (pos_ref[r] >= 0).astype(jnp.int32)

    # Only the slabs of the lanes this tick steps, and of the chunk's slot
    # where a live chunk continues a prompt, cross between HBM and VMEM.
    def start_lane(r, n):
        @pl.when(pos_ref[r] >= 0)
        def _():
            fetch(r, r).start()
        return n + live_at(r)

    n_lanes = jax.lax.fori_loop(0, lanes, start_lane, jnp.int32(0))
    chunk_live = jax.lax.fori_loop(
        lane_rows, lane_rows + chunk_rows,
        lambda r, n: n + live_at(r), jnp.int32(0)) > 0
    chunk_reads = chunk_live & (c_start != 0)

    @pl.when(chunk_reads)
    def _():
        fetch(c_slot, slots).start()

    x = x_ref[...].astype(_F32)
    u_scr[...] = dt_ref[...] * x
    y_scr[...] = jnp.zeros_like(y_scr)
    wait_all(fetch(0, 0), n_lanes + chunk_reads.astype(jnp.int32))

    def step_lane(r, carry):
        @pl.when(pos_ref[r] >= 0)
        def _():
            h, y = advance(buf[r], r, dt_ref[pl.ds(r, 1), :],
                           u_scr[pl.ds(r, 1), :])
            buf[r] = h
            y_scr[pl.ds(r, 1), :] = y
            store(r, r).start()
        return carry

    jax.lax.fori_loop(0, lanes, step_lane, 0)

    if chunk_rows:
        @pl.when(chunk_live)
        def _():
            # The chunk's slot is no stepped lane (it is being prefilled).
            h0 = jnp.where(c_start == 0, 0.0, buf[slots])

            def chunk_group(g, h):
                base = pl.multiple_of(lane_rows + g * _GROUP, _GROUP)
                dt_t = dt_ref[pl.ds(base, _GROUP), :]
                u_t = u_scr[pl.ds(base, _GROUP), :]
                ys = []
                for j in range(_GROUP):
                    r = base + j
                    walked, y = advance(h, r, dt_t[j:j + 1], u_t[j:j + 1])
                    ys.append(y)
                    h = jnp.where(pos_ref[r] >= 0, walked, h)
                y_scr[pl.ds(base, _GROUP), :] = jnp.concatenate(ys, axis=0)
                return h

            buf[slots] = jax.lax.fori_loop(
                0, chunk_rows // _GROUP, chunk_group, h0)
            store(c_slot, slots).start()

    gate = z_ref[...].astype(_F32)
    y = (y_scr[...] + d_ref[...] * x) * (gate * jax.nn.sigmoid(gate))
    y_ref[...] = y.astype(y_ref.dtype)
    wait_all(store(0, 0), n_lanes + chunk_live.astype(jnp.int32))


def ssm_scan(
    state: jax.Array, x: jax.Array, z: jax.Array, dt: jax.Array,
    b: jax.Array, c: jax.Array, a: jax.Array, d_skip: jax.Array,
    pos: jax.Array, *, lanes: int, chunk_slot=0, chunk_start=0,
) -> Tuple[jax.Array, jax.Array]:
    """One tick of one layer. state [slots, N, D] float32; x, z [R, D]
    (the activation type), dt [R, D], b, c [R, N], a [N, D], d_skip [D],
    pos [R] int32 with -1 on a row that must change nothing. Rows
    0..lanes-1 step lanes 0..lanes-1 (lanes <= slots); the R - lanes rows
    after them are one chunk of slot `chunk_slot` (no stepped lane),
    entering from zero where `chunk_start == 0`. Returns (y [R, D] in x's
    type: skip and gate applied, the new state). The state is aliased:
    donate it."""
    slots, N, D = state.shape
    R = x.shape[0]
    n_chunk = R - lanes
    assert 0 <= lanes <= slots and n_chunk >= 0, (lanes, slots, R)
    lane_rows = -(-lanes // _GROUP) * _GROUP
    chunk_rows = -(-n_chunk // _GROUP) * _GROUP
    rows = lane_rows + chunk_rows

    def place(t, fill=0):
        """Lane rows and chunk rows each padded to whole row groups."""
        if rows == R:
            return t
        parts = []
        for part, want in ((t[:lanes], lane_rows), (t[lanes:], chunk_rows)):
            widths = [(0, want - part.shape[0])] + [(0, 0)] * (t.ndim - 1)
            parts.append(jnp.pad(part, widths, constant_values=fill))
        return jnp.concatenate(parts, axis=0)

    def over_lanes(t):  # [R, N] -> [R, N, 128]: one value a row of 128
        return jnp.broadcast_to(
            place(t.astype(_F32))[:, :, None], (rows, N, min(_LANES, D)))

    width = _block_width(slots, N, D)
    per_block = lambda i, *_: (0, i)            # noqa: E731
    whole = lambda i, *_: (0, 0, 0)             # noqa: E731
    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    row_spec = pl.BlockSpec((rows, width), per_block)
    col_spec = pl.BlockSpec((rows, N, min(_LANES, D)), whole)
    y, new_state = pl.pallas_call(
        functools.partial(_kernel, lanes=lanes, lane_rows=lane_rows,
                          chunk_rows=chunk_rows),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(D // width,),
            in_specs=[in_hbm, row_spec, row_spec, row_spec, col_spec,
                      col_spec, pl.BlockSpec((N, width), per_block),
                      pl.BlockSpec((1, width), per_block)],
            out_specs=[row_spec, in_hbm],
            scratch_shapes=[pltpu.VMEM((slots + 1, N, width), _F32),
                            pltpu.VMEM((rows, width), _F32),
                            pltpu.VMEM((rows, width), _F32),
                            pltpu.SemaphoreType.DMA((2,))],
        ),
        out_shape=[jax.ShapeDtypeStruct((rows, D), x.dtype),
                   jax.ShapeDtypeStruct(state.shape, _F32)],
        input_output_aliases={2: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=_fa._interpret(),
        name="ssm_scan",
    )(
        place(pos.astype(jnp.int32), fill=-1),
        jnp.stack([jnp.asarray(chunk_slot, jnp.int32),
                   jnp.asarray(chunk_start, jnp.int32)]),
        state, place(x), place(z), place(dt.astype(_F32)),
        over_lanes(b), over_lanes(c), a.astype(_F32),
        d_skip.astype(_F32)[None, :],
    )
    if rows != R:
        y = jnp.concatenate([y[:lanes], y[lane_rows:lane_rows + n_chunk]])
    return y, new_state


_HI = jax.lax.Precision.HIGHEST
_UNROLL_BLOCKS = 4      # block_scan lays out this many blocks straight


def block_scan(
    x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array, c: jax.Array,
    h0: Optional[jax.Array] = None, chunk: int = 128,
) -> Tuple[jax.Array, jax.Array]:
    """Scalar-decay heads in block form. x [B,T,H,P]; dt [B,T,H] (after
    the softplus; 0 on a row that must leave the state as it is); a [H]
    (negative); b, c [B,T,G,N] with H a multiple of G (head h reads group
    h // (H/G)); h0 [B,N,H*P] or None (zero). Returns (y [B,T,H,P] without
    the skip, h_T [B,N,H*P]), float32."""
    B, T, H, P = x.shape
    G, N = b.shape[2], b.shape[3]
    J = H // G
    x, dt, b, c = (t.astype(_F32) for t in (x, dt, b, c))
    a = a.astype(_F32)
    L = min(chunk, T)
    pad = -T % L
    if pad:
        x, dt, b, c = (
            jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
            for t in (x, dt, b, c))
    nc = (T + pad) // L

    def chunks(t):  # [B, nc*L, ...] -> [nc, B, L, ...]
        return jnp.swapaxes(t.reshape(B, nc, L, *t.shape[2:]), 0, 1)

    seen = jnp.tril(jnp.ones((L, L), bool))[None, :, :, None, None]

    def body(h, blk):                                  # h [B,N,G,J,P]
        x_c, dt_c, b_c, c_c = blk
        lam = jnp.cumsum(dt_c * a, axis=1).reshape(B, L, G, J)
        u = (x_c * dt_c[..., None]).reshape(B, L, G, J, P)
        cb = jnp.einsum("blgn,bsgn->blsg", c_c, b_c, precision=_HI)
        gap = lam[:, :, None] - lam[:, None, :]        # [B,L(t),L(s),G,J]
        w = jnp.where(seen, jnp.exp(jnp.where(seen, gap, 0.0)), 0.0)
        y = jnp.einsum("blsgj,bsgjp->blgjp", w * cb[..., None], u,
                       precision=_HI)
        y = y + jnp.exp(lam)[..., None] * jnp.einsum(
            "blgn,bngjp->blgjp", c_c, h, precision=_HI)
        left = jnp.exp(lam[:, -1:] - lam)              # a row's decay to L
        h = jnp.exp(lam[:, -1])[:, None, :, :, None] * h + jnp.einsum(
            "bsgn,bsgjp->bngjp", b_c, u * left[..., None], precision=_HI)
        return h, y

    if h0 is None:
        h0 = jnp.zeros((B, N, H * P), _F32)
    # A prefill chunk is a few blocks: laid out straight, their outputs
    # are concatenated, not written one by one into a stacked array.
    h, y = jax.lax.scan(body, h0.astype(_F32).reshape(B, N, G, J, P),
                        tuple(chunks(t) for t in (x, dt, b, c)),
                        unroll=nc if nc <= _UNROLL_BLOCKS else 1)
    y = jnp.swapaxes(y, 0, 1).reshape(B, nc * L, H, P)[:, :T]
    return y, h.reshape(B, N, H * P)


_RING = 4       # slabs in the lanes' ring of buffers
_AHEAD = 2      # fetches in flight ahead of the lane being stepped
_HEADS_BLOCK = 1024     # channels a grid step, at most


def _heads_block_width(d: int, groups: int) -> int:
    """Channels a grid step of ssm_scan_heads: a group's, halved while
    wider than _HEADS_BLOCK (a block lies inside ONE group)."""
    width = d // groups
    while width > _HEADS_BLOCK and width % 256 == 0:
        width //= 2
    return width


def _heads_kernel(ids_ref, n_ref, s_hbm, dec_ref, u_ref, bt_ref, ct_ref,
                  y_ref, so_hbm, buf, row_scr, sem_in, sem_out):
    width = buf.shape[2]
    tile = min(_LANES, width)
    cols = pl.ds(pl.program_id(0) * width, width)
    n = n_ref[0]
    rows = bt_ref.shape[-1]
    precision = None if bt_ref.dtype.itemsize <= 2 else _HI

    def fetch(k):
        return pltpu.make_async_copy(
            s_hbm.at[ids_ref[k], :, cols], buf.at[k % _RING],
            sem_in.at[k % _RING])

    def store(k):
        return pltpu.make_async_copy(
            buf.at[k % _RING], so_hbm.at[ids_ref[k], :, cols],
            sem_out.at[k % _RING])

    y_ref[...] = jnp.zeros_like(y_ref)
    for k in range(_AHEAD):
        @pl.when(k < n)
        def _():
            fetch(k).start()

    def step(k, carry):
        r = ids_ref[k]
        slot = k % _RING
        # The lane's column of B and of C over 128 lanes: one term a sum.
        pick = (jax.lax.broadcasted_iota(jnp.int32, (rows, tile), 0)
                == r).astype(bt_ref.dtype)
        b_col = jnp.dot(bt_ref[0], pick, precision=precision,
                        preferred_element_type=_F32)           # [N, tile]
        c_col = jnp.dot(ct_ref[0], pick, precision=precision,
                        preferred_element_type=_F32)
        # The lane's two rows, where a tile of them is a static read.
        row_scr[0:1, :] = dec_ref[pl.ds(r, 1), :]
        row_scr[8:9, :] = u_ref[pl.ds(r, 1), :]
        fetch(k).wait()
        ys = []
        for t in range(width // tile):
            at = pl.ds(t * tile, tile)
            h = (buf[slot, :, at] * row_scr[0:1, at]
                 + b_col * row_scr[8:9, at])
            buf[slot, :, at] = h
            ys.append(jnp.sum(h * c_col, axis=0, keepdims=True))
        y_ref[pl.ds(r, 1), :] = (
            ys[0] if len(ys) == 1 else jnp.concatenate(ys, axis=1))
        store(k).start()
        ahead = k + _AHEAD

        @pl.when(ahead < n)
        def _():
            @pl.when(ahead >= _RING)
            def _():
                store(ahead - _RING).wait()
            fetch(ahead).start()
        return carry

    jax.lax.fori_loop(0, n, step, 0)
    jax.lax.fori_loop(jnp.maximum(n - _RING, 0), n,
                      lambda k, c: (store(k).wait(), c)[1], 0)


def ssm_scan_heads(
    state: jax.Array, decay: jax.Array, drive: jax.Array, b: jax.Array,
    c: jax.Array, pos: jax.Array,
) -> Tuple[jax.Array, jax.Array]:
    """One tick of one layer's LANES. state [slots, N, D] float32; decay
    (exp(dt a)) and drive (dt x) [S, D] float32, each head's value over
    its channels; b, c [S, G, N]; pos [S] int32, -1 on a lane that must
    change nothing. Row r steps lane r (S <= slots). Returns (y [S, D]
    float32 without the skip, zero on a lane not stepped; the new state).
    The state is aliased: donate it."""
    slots, N, D = state.shape
    S, G = b.shape[0], b.shape[1]
    assert S <= slots and D % G == 0, (S, slots, D, G)
    rows = -(-S // _LANES) * _LANES
    width = _heads_block_width(D, G)
    per_group = (D // G) // width
    live = pos >= 0
    ids = jnp.nonzero(live, size=S, fill_value=0)[0].astype(jnp.int32)

    def rows_of(t):
        return jnp.pad(t.astype(_F32), ((0, rows - S), (0, 0)))

    def columns(t):  # [S, G, N] -> [G, N, rows]
        return jnp.pad(jnp.transpose(t, (1, 2, 0)),
                       ((0, 0), (0, 0), (0, rows - S)))

    per_block = lambda i, *_: (0, i)                        # noqa: E731
    of_group = lambda i, *_: (i // per_group, 0, 0)         # noqa: E731
    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    row_spec = pl.BlockSpec((rows, width), per_block)
    col_spec = pl.BlockSpec((1, N, rows), of_group)
    y, new_state = pl.pallas_call(
        _heads_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(D // width,),
            in_specs=[in_hbm, row_spec, row_spec, col_spec, col_spec],
            out_specs=[row_spec, in_hbm],
            scratch_shapes=[pltpu.VMEM((_RING, N, width), _F32),
                            pltpu.VMEM((16, width), _F32),
                            pltpu.SemaphoreType.DMA((_RING,)),
                            pltpu.SemaphoreType.DMA((_RING,))],
        ),
        out_shape=[jax.ShapeDtypeStruct((rows, D), _F32),
                   jax.ShapeDtypeStruct(state.shape, _F32)],
        input_output_aliases={2: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=_fa._interpret(),
        name="ssm_scan_heads",
    )(
        ids, live.sum().astype(jnp.int32)[None], state,
        rows_of(decay), rows_of(drive), columns(b), columns(c),
    )
    return y[:S], new_state
