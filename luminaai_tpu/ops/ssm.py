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
