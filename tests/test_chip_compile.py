"""Compile the main path's Pallas kernels for a described TPU v5e, at the
flagship's real shapes, without a chip.

The TPU compiler is installed with jax and compiles for a chip that is
described and not attached (`topologies.get_topology_desc`), so what
Mosaic refuses — a misaligned slice, too much VMEM, an unpartitionable
kernel — is refused here, in ~2 s a kernel, at no chip time. Nothing
runs: these tests say nothing about results or speed.

Rules this file keeps (guide "on-chip-measurement" §2): the topology is
described inside a module-scoped fixture, never at import, in a skipif or
in a parametrize argument; everything built from it is built in the test;
compiles happen in this process; the persistent compile cache is off
around them (a described-chip executable is written but cannot be read
back without a chip). The code under test asks `jax.default_backend()`,
which is `cpu` here, so the tests steer `_interpret` / pick the megablox
kernel themselves.
"""

import functools
import math

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from luminaai_tpu.config import ConfigPresets
from luminaai_tpu.ops import flash_attention as fa
from luminaai_tpu.ops import ragged_paged_attention as rpa
from tests.test_kimi_linear import _eqns

CFG = ConfigPresets.flagship()
B, S = CFG.batch_size, CFG.seq_length  # 16 x 2048
HQ, HKV = CFG.num_heads, CFG.num_kv_heads  # 16 / 8
D = CFG.hidden_size // CFG.num_heads  # 64
BQ, BKV = CFG.flash_block_q, CFG.flash_block_kv  # 1024 x 1024
E, H = CFG.num_experts, CFG.hidden_size
F = CFG.intermediate_size
ROUTED = B * S * CFG.moe_top_k  # 65,536 rows into the grouped matmuls
SLOTS, PAGE = 8, 128  # serving/server.py build_server defaults
BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def _no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(autouse=True)
def _compile_kernels_not_interpreter(monkeypatch):
    monkeypatch.setattr(fa, "_interpret", lambda: False)
    monkeypatch.setattr(rpa, "_interpret", lambda: False)


def _compile(fn, one_chip, *shapes):
    args = [
        jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
        for shape, dtype in shapes
    ]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the program"
    return text


def _qkv(seq=S):
    return (
        ((B, seq, HQ, D), BF16), ((B, seq, HKV, D), BF16),
        ((B, seq, HKV, D), BF16),
    )


def _flash_fwd(q, k, v, window=0):
    return fa._fwd(
        q, k, v, scale=D**-0.5, causal=True, block_q=BQ, block_kv=BKV,
        window=window,
    )


def _flash_bwd(q, k, v, o, lse, do, window=0):
    return fa._bwd(
        D**-0.5, True, BQ, BKV, window, (q, k, v, o, lse), do
    )


_BWD_SHAPES = _qkv() + (
    ((B, S, HQ, D), BF16), ((B, HQ, S), jnp.float32),
    ((B, S, HQ, D), BF16),
)


@pytest.mark.parametrize(
    "fn,shapes,n_kernels",
    [
        (_flash_fwd, _qkv(), 1),
        # dq and dkv are separate pallas_calls of one backward.
        (_flash_bwd, _BWD_SHAPES, 2),
        # Banded (windowed) grid: the kv/q axes shrink to the band.
        (functools.partial(_flash_fwd, window=512), _qkv(), 1),
        (functools.partial(_flash_bwd, window=512), _BWD_SHAPES, 2),
    ],
    ids=["fwd", "bwd_dq_dkv", "banded_fwd", "banded_bwd_dq_dkv"],
)
def test_flash_attention_compiles_at_flagship_shapes(
    one_chip, fn, shapes, n_kernels
):
    """B 16, S 2048, 16/8 heads, D 64 (half a lane tile), 1024x1024
    blocks: the default scoped VMEM limit must hold the fp32 score tile
    plus scratch — no compiler_params are passed at any pallas_call."""
    text = _compile(fn, one_chip, *shapes)
    assert text.count('custom_call_target="tpu_custom_call"') == n_kernels


def test_flash_vjp_is_three_kernels(one_chip):
    """The differentiable entry point the model calls: forward, dq and
    dkv all reach the program (save_attn keeps (out, lse), so no second
    forward)."""

    def loss(q, k, v):
        out = fa.flash_attention(q, k, v, block_q=BQ, block_kv=BKV)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    text = _compile(
        jax.grad(loss, argnums=(0, 1, 2)), one_chip, *_qkv()
    )
    assert text.count('custom_call_target="tpu_custom_call"') == 3


@pytest.mark.parametrize(
    "transpose",
    [False, True],
    ids=["fwd_gate_up_and_down", "bwd_dlhs_and_tgmm"],
)
def test_megablox_gmm_compiles_at_expert_shapes(one_chip, transpose):
    """8 experts, 65,536 routed rows, H 1024 -> 2F and F -> H — the two
    grouped matmuls of _gmm_local; the backward adds the transposed-rhs
    gmm (d_lhs) and tgmm (d_rhs) through megablox's custom VJP."""
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    def expert_ffn(rows, wi, wo, group_sizes):
        fused = gmm(rows, wi, group_sizes, preferred_element_type=BF16)
        gate, up = jnp.split(fused, 2, axis=-1)
        return gmm(
            jax.nn.silu(gate) * up, wo, group_sizes,
            preferred_element_type=BF16,
        )

    def loss(rows, wi, wo, group_sizes):
        out = expert_ffn(rows, wi, wo, group_sizes)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    fn = jax.grad(loss, argnums=(0, 1, 2)) if transpose else expert_ffn
    text = _compile(
        fn, one_chip,
        ((ROUTED, H), BF16), ((E, H, 2 * F), BF16), ((E, F, H), BF16),
        ((E,), jnp.int32),
    )
    # fwd: 2 gmm. bwd: those 2 again + a d_lhs gmm and a tgmm for each.
    n = text.count('custom_call_target="tpu_custom_call"')
    assert n == (6 if transpose else 2), n


# -- the lanes' decode kernel, at the shapes of the cells that run it --------
@pytest.mark.parametrize("lanes,rows,hq,hkv,window,extent,ring", [
    (32, 35 * 128, 128, 8, 4096, None, True),
    (32, 16384, 128, 8, None, 16384, False),
    (32, 16384, 128, 8, None, 2048, False),
    (128, 2048, 20, 1, None, 1024, False),
    (28, 2048, 16, 16, None, 512, False),
    (28, 2048, 16, 16, None, 2048, False),
    (128, 6144, 32, 2, None, 6144, False),
], ids=["command_a_ring", "command_a_whole_pages", "command_a_extent_2048",
        "jamba_one_kv_head", "olmoe_mha_extent_512", "olmoe_mha_extent_2048",
        "nemotron_two_kv_heads"])
@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_lane_attention_compiles_at_the_ticks_shapes(
        one_chip, lanes, rows, hq, hkv, window, extent, ring, kv_dtype):
    """One layer's decode rows of one tick: command-a-plus's 32 lanes of
    128 query heads over 8 k/v heads (a ring of 35 pages under the 4,096
    window; 16,384 whole rows at the widest and at a narrow extent),
    Jamba2's 128 lanes of 20 query heads over one, and OLMoE's 28 lanes of
    16 over 16 (MHA: a page of 128 rows is 2,048 score columns, so two
    pages a block, 1 MB of k) at its narrowest and widest extents. The
    pool goes in as it lies: no copy of it is made in front of the kernel
    (the program's temporaries stay under a hundredth of the k/v it
    reads), whatever the extent, and the kernel asks for _LANE_VMEM_LIMIT
    of scoped VMEM, of which two k and two v blocks in flight are an
    eighth at most. int8 KV is (codes, per-row scales) dequantized in
    front of the kernel, as models/layers.py reads it: that copy is the
    path's own."""
    assert rpa.lane_attention_eligible(hq, hkv, 128, PAGE)
    assert rpa.lane_attention_engaged("ragged_xla", 1, hq, hkv, 128, PAGE)

    def decode(q, k, v, lengths, ring_table):
        if kv_dtype == "int8":
            k, v = (
                (codes.astype(jnp.float32) * scales).astype(BF16)
                for codes, scales in (k, v)
            )
        meta = rpa.LaneMeta(
            lengths=lengths, window=window, page_size=PAGE, extent=extent,
            ring_table=ring_table if ring else None,
        )
        return rpa.lane_attention(q, k, v, meta, ring=ring)

    sds = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    if kv_dtype == "int8":
        kv = (
            sds((lanes, rows, hkv, 128), jnp.int8),
            sds((lanes, rows, hkv, 1), jnp.float32),
        )
    else:
        kv = sds((lanes, rows, hkv, 128), BF16)
    compiled = jax.jit(decode).lower(
        sds((lanes, 1, hq, 128), BF16), kv, kv,
        sds((lanes,), jnp.int32), sds((lanes, 128), jnp.int32),
    ).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert "lane_attention" in text
    call = next(ln for ln in text.splitlines()
                if 'custom_call_target="tpu_custom_call"' in ln)
    assert f'"size":"{rpa._LANE_VMEM_LIMIT}"' in call
    pages = rows // PAGE if ring else extent // PAGE
    per_block, _ = rpa.lane_blocks(pages, PAGE, hkv, 128, 2)
    assert per_block == {8: 7 if ring else 8, 1: 8, 16: 2, 2: 24}[hkv]
    in_flight = 2 * 2 * per_block * PAGE * hkv * 128 * 2
    assert in_flight <= rpa._LANE_VMEM_LIMIT // 8
    if kv_dtype == "bf16":
        pool = 2 * lanes * rows * hkv * 128 * 2
        assert compiled.memory_analysis().temp_size_in_bytes < pool // 100


# -- the hybrid linear-attention cell's kernels (kimi-linear-train-8k) -------
KB, KS, KH = 2, 8192, 32  # sequences a chip, tokens a sequence, heads


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "fwd_states_bwd"])
def test_kda_kernels_compile_at_cell_shapes(one_chip, grad):
    """The chunked delta rule at 32 heads of 128 over 2 x 8192 tokens:
    `kda_tri` (every chunk's inverse, a grid with no order), the chunk's
    math reading that inverse (and, backward, its `jax.vjp` with the
    inverse's closed-form adjoint -T^T dT T^T against the carried T,
    traced into the kernel) must lower in Mosaic and fit the scoped
    VMEM."""
    from luminaai_tpu.ops import kda

    def run(q, k, v, g, beta):
        return kda.kda(q, k, v, g, beta)

    def loss(q, k, v, g, beta):
        return jnp.sum(run(q, k, v, g, beta).astype(jnp.float32))

    wide = ((KB, KS, KH, 128), BF16)
    shapes = (wide, wide, wide, ((KB, KS, KH, 128), jnp.float32),
              ((KB, KS, KH), jnp.float32))
    fn = jax.grad(loss, argnums=(0, 1, 2, 3, 4)) if grad else run
    text = _compile(fn, one_chip, *shapes)
    assert text.count('custom_call_target="tpu_custom_call"') == (
        3 if grad else 2)
    assert "kda_tri" in text and "kda_fwd" in text
    assert ("kda_bwd" in text) is grad
    # The static counter of "several chunks a grid step": the grids of the
    # calls as traced. 2 x 32 x 128 chunks were 8,192 steps a call at one.
    traced = jax.make_jaxpr(fn)(
        *(jax.ShapeDtypeStruct(*shape) for shape in shapes))
    steps = {e.params["name"]: math.prod(e.params["grid_mapping"].grid)
             for e in _eqns(traced.jaxpr) if e.primitive.name == "pallas_call"}
    chunks = KB * KH * KS // kda.CHUNK
    assert kda._FWD_CHUNKS > 1 and kda._BWD_CHUNKS > 1
    assert steps["kda_fwd"] == chunks // kda._FWD_CHUNKS
    assert steps.get("kda_bwd") == (
        chunks // kda._BWD_CHUNKS if grad else None)


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "fwd_bwd"])
def test_qkv_prepare_compiles_at_cell_shapes(one_chip, grad):
    """The delta-rule mixer's q, k and v from the projection's
    bf16[2, 8192, 3 * 4096] (32 heads of 128): the shifted float32 reads of
    the scratch, the lane sums a head and the one-row updates of the taps'
    gradient must lower in Mosaic, and nothing float32 of that size may
    stand around the kernels: forward + backward need the three bf16
    cotangents and little else (the plain form's float32 temporaries are
    4.03 GB there)."""
    from luminaai_tpu.ops import kda

    def run(x, w):
        return kda.qkv_prepare(x, w, heads=KH, head_dim=128)

    def both(x, w, dq, dk, dv):
        out, vjp = jax.vjp(run, x, w)
        return out, vjp((dq, dk, dv))

    third = ((KB, KS, KH * 128), BF16)
    shapes = (((KB, KS, 3 * KH * 128), BF16), ((4, 3 * KH * 128), jnp.float32))
    if grad:
        shapes += (third,) * 3
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in shapes]
    compiled = jax.jit(both if grad else run).lower(*args).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == (
        2 if grad else 1)
    assert "qkv_prepare_fwd" in text
    assert ("qkv_prepare_bwd" in text) is grad
    assert "f32[2,8192," not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 0.5e9


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "fwd_bwd"])
def test_mixer_gates_compile_at_cell_shapes(one_chip, grad):
    """The decay and beta's fold in front of the recurrence, from the
    cell's bf16[2, 8192, 4096] (32 heads of 128) and beta's logits
    bf16[2, 8192, 32]: a head's column picked out of the [rows, 32] block
    and broadcast over its 128 lanes, the logits' cotangent gathered into
    that block, must lower in Mosaic. One backward for the fold and the
    recurrence: k's cotangent leaves `mixer_gates_bwd` alone, and no
    [.., 32, 128] view of a [2, 8192, 4096] array stands anywhere."""
    from luminaai_tpu.ops import kda

    def run(q, k, v, a, bl, dt_bias, a_log):
        return kda.gated_kda(q, k, v, a, bl, dt_bias, a_log)

    def both(do, *args):
        out, vjp = jax.vjp(lambda *xs: run(*xs)[0], *args)
        return out, vjp(do)

    wide = ((KB, KS, KH * 128), BF16)
    shapes = (wide,) * 4 + (((KB, KS, KH), BF16), ((KH * 128,), jnp.float32),
                            ((KH,), jnp.float32))
    text = _compile(both if grad else run, one_chip,
                    *(((wide,) if grad else ()) + shapes))
    assert text.count('custom_call_target="tpu_custom_call"') == (
        5 if grad else 3)
    assert "mixer_gates_fwd" in text and "kda_fwd" in text
    for name in ("mixer_gates_bwd", "kda_bwd"):
        assert (name in text) is grad
    assert "[2,8192,32,128]" not in text and "[2048,8,32,128]" not in text


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "fwd_bwd"])
def test_mixer_out_compiles_at_cell_shapes(one_chip, grad):
    """The output norm x gate behind the recurrence at the cell's shapes:
    a head's mean square is a sum over the 128 lanes of a row, and
    nothing float32 of the arrays' size stands around the kernels."""
    from luminaai_tpu.ops import kda

    def run(o, z, w):
        return kda.mixer_out(o, z, w, eps=1e-5)

    def both(dy, o, z, w):
        out, vjp = jax.vjp(run, o, z, w)
        return out, vjp(dy)

    wide = ((KB, KS, KH * 128), BF16)
    shapes = (wide, wide, ((128,), jnp.float32))
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in ((wide,) if grad else ()) + shapes]
    compiled = jax.jit(both if grad else run).lower(*args).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == (
        2 if grad else 1)
    assert "mixer_out_fwd" in text
    assert ("mixer_out_bwd" in text) is grad
    assert "f32[2,8192," not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 0.1e9


def test_flash_compiles_with_values_narrower_than_scores(one_chip):
    """Latent attention's shapes: scores over 192, values and output of
    128, forward and both backward kernels."""

    def loss(q, k, v):
        out = fa.flash_attention(q, k, v, block_q=BQ, block_kv=BKV)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    text = _compile(
        jax.grad(loss, argnums=(0, 1, 2)), one_chip,
        ((KB, KS, KH, 192), BF16), ((KB, KS, KH, 192), BF16),
        ((KB, KS, KH, 128), BF16),
    )
    assert text.count('custom_call_target="tpu_custom_call"') == 3


# -- the state-space serving cell's kernel (jamba2-3b-serve-burst) -----------
@pytest.mark.parametrize("slots", [128, 256], ids=["128_lanes", "256_lanes"])
def test_ssm_scan_compiles_at_the_ticks_shapes(one_chip, slots):
    """One layer of one tick at Jamba2-3B's widths: `slots` lanes' states
    of 16 x 5120 float32, their decode rows and a 64-row chunk in bf16.
    A block of channels has room in VMEM for every lane's slab (the tick
    in which all are stepped), copied in and out a stepped lane at a
    time."""
    from luminaai_tpu.ops import ssm

    n, d, rows = 16, 5120, slots + 64

    def run(state, x, z, dt, b, c, a, skip, pos, slot, start):
        return ssm.ssm_scan(state, x, z, dt, b, c, a, skip, pos,
                            lanes=slots, chunk_slot=slot, chunk_start=start)

    f32, i32 = jnp.float32, jnp.int32
    text = _compile(
        run, one_chip, ((slots, n, d), f32), ((rows, d), BF16),
        ((rows, d), BF16), ((rows, d), f32), ((rows, n), f32),
        ((rows, n), f32), ((n, d), f32), ((d,), f32), ((rows,), i32),
        ((), i32), ((), i32))
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert "ssm_scan" in text
    assert ssm._block_width(slots, n, d) == 1024


# -- the scalar-decay heads' lanes (nemotron-3-super-serve-reason) -----------
def test_ssm_scan_heads_compiles_at_the_ticks_shapes(one_chip):
    """One state-space layer's lanes of one tick at Nemotron-3-Super's
    widths: 128 lanes' states of 128 x 8192 float32 (537 MB, aliased in
    and out: no copy of the pool, temporaries a ten-thousandth of it), a
    grid step a group's 1,024 channels, a ring of four [128, 1024] slabs."""
    from luminaai_tpu.ops import ssm

    slots, n, d, g = 128, 128, 8192, 8
    f32 = jnp.float32
    sds = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    compiled = jax.jit(ssm.ssm_scan_heads, donate_argnums=(0,)).lower(
        sds((slots, n, d), f32), sds((slots, d), f32), sds((slots, d), f32),
        sds((slots, g, n), BF16), sds((slots, g, n), BF16),
        sds((slots,), jnp.int32)).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert "ssm_scan_heads" in text
    assert ssm._heads_block_width(d, g) == 1024
    mem = compiled.memory_analysis()
    pool = slots * n * d * 4
    assert mem.alias_size_in_bytes == pool
    assert mem.temp_size_in_bytes < pool // 1000


# -- the mixed-window serving cell's kernel (command-a-plus-serve-mixed) -----
@pytest.mark.parametrize("rows,window", [(35 * 128, 4096), (16384, None)],
                         ids=["ring_of_35_pages", "whole_pages"])
def test_chunk_attention_compiles_at_the_ticks_shapes(one_chip, rows, window):
    """One layer of one tick at command-a-plus's widths: a 256-row chunk
    of 128 query heads of 128 over one lane's k/v (8 heads), a ring of 35
    pages under the 4,096 window or 16,384 whole rows, keys by position.
    [256, block] float32 scores and the accumulators fit VMEM at key blocks
    of 896 and 1,024 rows."""
    i32 = jnp.int32

    def run(q, k, v, qpos, kpos, live):
        return rpa.chunk_attention(q, k, v, qpos, kpos, window, live)

    text = _compile(
        run, one_chip, ((256, 128, 128), BF16), ((rows, 8, 128), BF16),
        ((rows, 8, 128), BF16), ((256,), i32), ((rows,), i32), ((), i32))
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert "chunk_attention" in text
    assert rpa._chunk_key_block(rows) == (896 if window else 1024)
    assert rpa.chunk_attention_eligible(256, rows, 128)
    assert not rpa.chunk_attention_eligible(256, rows, 64)


# -- the latent serving cell's kernels (kimi-k2-7-code-serve-longctx) --------
@pytest.mark.parametrize("extent", [32768, 2048], ids=["widest", "narrow"])
def test_lane_attention_compiles_over_a_latent_entry(one_chip, extent):
    """One latent layer's decode rows of one tick at Kimi-K2's widths: 32
    lanes of 64 absorbed query heads over ONE shared key row of 640
    columns (512 latent + 64 rope + 64 of zeros) whose first 512 are the
    value: no v operand, and no copy of the pool in front of the kernel."""
    lanes, rows, width, rank = 32, 32768, 640, 512
    assert rpa.lane_attention_engaged("ragged_xla", 1, 64, 1, width, PAGE)

    def decode(q, entry, lengths):
        meta = rpa.LaneMeta(lengths=lengths, page_size=PAGE, extent=extent)
        return rpa.lane_attention(q, entry, None, meta, scale=0.14468,
                                  v_dim=rank)

    sds = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    compiled = jax.jit(decode).lower(
        sds((lanes, 1, 64, width), BF16), sds((lanes, rows, 1, width), BF16),
        sds((lanes,), jnp.int32),
    ).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert "lane_attention" in text
    assert compiled.memory_analysis().output_size_in_bytes == (
        lanes * 64 * rank * 2)
    pool = lanes * rows * width * 2
    assert compiled.memory_analysis().argument_size_in_bytes < 1.01 * pool
    assert compiled.memory_analysis().temp_size_in_bytes < pool // 100


@pytest.mark.parametrize("chunk", [256, 512])
def test_chunk_attention_compiles_over_a_latent_entry(one_chip, chunk):
    """One latent layer of one tick: a chunk's rows of 64 absorbed query
    heads over one lane's 32,768 latent rows, heads folded so that a grid
    step holds 1,024 stacked rows: [1,024, 1,024] float32 scores, the
    [1,024, 512] accumulator and the key block fit the raised VMEM limit."""
    i32 = jnp.int32

    def run(q, entry, qpos, kpos, live):
        return rpa.chunk_attention(q, entry, None, qpos, kpos, None, live,
                                   scale=0.14468, v_dim=512)

    text = _compile(
        run, one_chip, ((chunk, 64, 640), BF16), ((32768, 1, 640), BF16),
        ((chunk,), i32), ((32768,), i32), ((), i32))
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert "chunk_attention" in text
    # 1,024 // chunk heads a grid step: [64 // fold, chunk x fold, 512] out
    assert f"bf16[{64 * chunk // 1024},1024,512]" in text
    assert rpa.chunk_attention_eligible(chunk, 32768, 640)


# -- the held experts' layer: how its sorted rows go back to their tokens ----
def _held_layer(tokens, groups, experts, held, cf, live):
    """models/moe.py _gmm_held over megablox at a cell's shapes: x, the
    router's scores, the held experts' weights, the live rows."""
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    from luminaai_tpu.models import moe

    rows = -(-int(cf * tokens * 8 * held / experts) // 128) * 128

    def layer(x, probs, wi, wo, lv):
        return moe._gmm_held(
            x, probs, wi, wo, top_k=8, num_experts=experts, offset=0,
            row_bound=rows, dtype=BF16, gmm_fn=gmm,
            rule=dict(select_bias=None, renormalize=True, scale=1.0),
            live=lv if live else None,
        )[0]

    return layer, rows


def _held_shapes(tokens, groups, hidden, ffn, experts, held):
    seq = tokens // groups
    return (
        ((groups, seq, hidden), BF16), ((groups, seq, experts), jnp.float32),
        ((held, hidden, 2 * ffn), BF16), ((held, ffn, hidden), BF16),
        ((groups, seq), jnp.bool_),
    )


@pytest.mark.parametrize("hidden,ffn,experts,held,cf", [
    (7168, 2048, 384, 12, 32.0), (4096, 4096, 128, 16, 8.0),
], ids=["kimi_k2_tick", "command_a_plus_tick"])
def test_a_ticks_held_rows_return_as_a_product(one_chip, hidden, ffn,
                                               experts, held, cf):
    """32 lanes + a 256-row chunk, every pair a row of the sorted buffer
    (2,304): no float32 scatter of [288, H] is left in the program, and a
    matmul under `moe_held_combine` is (the masks fused into it)."""
    import re

    layer, rows = _held_layer(288, 1, experts, held, cf, live=True)
    assert rows == 2304
    text = _compile(layer, one_chip,
                    *_held_shapes(288, 1, hidden, ffn, experts, held))
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    assert not re.search(rf"f32\[288,{hidden}\]\S* scatter\(", text)
    assert re.search(
        rf"f32\[288,{hidden}\]\S* convolution\(.*moe_held_combine/dot_general",
        text)


def test_a_training_steps_held_rows_return_as_a_scatter(one_chip):
    """kimi-linear-train-8k's step, forward and backward: 16,384 tokens,
    8,192 sorted rows, H 2,304: the shape rule keeps the float32
    scatter-add."""
    import re

    layer, rows = _held_layer(16384, 2, 256, 8, 2.0, live=False)
    assert rows == 8192

    def loss(x, probs, wi, wo, lv):
        return jnp.sum(layer(x, probs, wi, wo, lv).astype(jnp.float32) ** 2)

    text = _compile(jax.grad(loss, argnums=(0, 1, 2, 3)), one_chip,
                    *_held_shapes(16384, 2, 2304, 1024, 256, 8))
    assert re.search(r"f32\[16384,2304\]\S* scatter\(", text)
    assert "moe_held_combine)/scatter-add" in text
    assert "moe_held_combine)/dot_general" not in text


# -- the sink-and-window serving cell's kernels (mimo-v2-flash-serve-reason) --
@pytest.mark.parametrize("rows,hkv,window,extent,ring", [
    (512, 8, 128, None, True),
    (10240, 4, None, 10240, False),
    (10240, 4, None, 2048, False),
], ids=["window_ring_8_heads", "full_4_heads", "full_extent_2048"])
def test_lane_attention_compiles_over_a_key_in_parts(
        one_chip, rows, hkv, window, extent, ring):
    """One attention layer's decode rows of one tick at MiMo-V2-Flash's
    widths: 96 lanes of 64 query heads over a key of 192 columns kept as
    two arrays of 128 (the second half zeros) beside a value of 128, 8 k/v
    heads in a ring of 4 pages under the 128 window with the sink, 4 in
    10,240 whole rows without. Every array of the entry goes in as it
    lies: no copy of the pool in front of the kernel. (ONE array of 4
    heads x 256 columns would be copied whole: the rule refuses it.)"""
    lanes, hq = 96, 64
    assert rpa.lane_attention_engaged("ragged_xla", 1, hq, hkv, 128, PAGE, 128)
    assert not rpa.lane_attention_eligible(hq, 4, 256, PAGE, 128)

    def decode(q, k0, k1, v, sink, lengths, ring_table):
        meta = rpa.LaneMeta(
            lengths=lengths, window=window, page_size=PAGE, extent=extent,
            ring_table=ring_table if ring else None,
        )
        return rpa.lane_attention(q, (k0, k1), v, meta, ring=ring,
                                  scale=192**-0.5,
                                  sink=sink if ring else None)

    sds = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    kv = sds((lanes, rows, hkv, 128), BF16)
    compiled = jax.jit(decode).lower(
        sds((lanes, 1, hq, 256), BF16), kv, kv, kv, sds((hq,), jnp.float32),
        sds((lanes,), jnp.int32), sds((lanes, 80), jnp.int32),
    ).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert "lane_attention" in text
    mem = compiled.memory_analysis()
    pool = 3 * lanes * rows * hkv * 128 * 2
    # (the queries are 3 MB beside a ring's 150)
    assert mem.argument_size_in_bytes < pool + (4 << 20)
    assert mem.temp_size_in_bytes < pool // 100
    assert mem.output_size_in_bytes == lanes * hq * 128 * 2


@pytest.mark.parametrize("rows,hkv,window", [(512, 8, 128), (10240, 4, None)],
                         ids=["ring_of_4_pages", "whole_pages"])
def test_chunk_attention_compiles_with_values_narrower_than_keys(
        one_chip, rows, hkv, window):
    """One attention layer of one tick at MiMo-V2-Flash's widths: a
    256-row chunk of 64 query heads over one lane's key (its two parts
    side by side: 256 columns) and value (128), with the window layers'
    sink."""
    i32 = jnp.int32

    def run(q, k, v, sink, qpos, kpos, live):
        return rpa.chunk_attention(q, k, v, qpos, kpos, window, live,
                                   scale=192**-0.5,
                                   sink=sink if window else None)

    text = _compile(
        run, one_chip, ((256, 64, 256), BF16), ((rows, hkv, 256), BF16),
        ((rows, hkv, 128), BF16), ((64,), jnp.float32), ((256,), i32),
        ((rows,), i32), ((), i32))
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert "chunk_attention" in text
    assert rpa.chunk_attention_eligible(256, rows, 256, 128)
