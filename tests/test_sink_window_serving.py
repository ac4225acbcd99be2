"""Window layers with a learned sink beside full layers, each kind with
its own count of k/v heads, keys wider than values, a part-rotated head
and a rotation base a layer (ISSUE 50): the pool keeps every layer's
entry in that layer's own shape (a ring of pages of 4 k/v heads beside
whole pages of 2), and a key wider than its value lies in value-width
parts.

At a tiny size on the CPU, float32 (window 8; layers full + dense, window,
window, full, window; 4 query heads over 2 k/v heads in the full layers
and 4 in the window layers; keys of 24 columns, the first 8 rotated, over
values of 16, so a key is two parts of 16; theta 5e6 and 1e4; a sink of
N(0, 1) a query head in the window layers; values scaled by 0.707;
sigmoid top-2 of 8 experts by score + a non-zero selection bias, 4 held),
pages of 4 rows, a ring of 5 pages:

  - the uncached forward against the plain reference
    (benchmark/architectures/mimo_v2), through XLA and through the flash
    kernel with its sink, and the sink's gradient against jax.grad of the
    reference;
  - the CACHED path's logits, read out of the tick program by a spy, at
    EVERY row across the window's edge and the ring's wrap, through XLA
    and through both kernels interpreted; three controls that must each
    fail (the sink left out, the value scale left out, the window taken
    one key wider);
  - both kernels against the XLA forms over sink / no sink, equal /
    unequal widths, 1 / 4 / 8 k/v heads;
  - a slot's bytes as the sum of each layer's own rows x bytes, the byte
    counters, the eligibility rules at the published shapes and at every
    shape the other cells have, and Config.validate's refusals.
"""

import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import correct, manifest
from luminaai_tpu.config import Config
from luminaai_tpu.inference.generate import GenerationEngine
from luminaai_tpu.models import layers
from luminaai_tpu.models.transformer import LuminaTransformer
from luminaai_tpu.ops import flash_attention as fa
from luminaai_tpu.ops import ragged_paged_attention as rpa
from luminaai_tpu.parallel.sharding import unbox
from test_window_ring_serving import (CAP, CASES, PAGE, VOCAB, _Tok, serve,
                                      worst_row)

MIMO = manifest.Architecture("mimo_v2")
WINDOW = 8
KINDS = (0, 1, 1, 0, 1)
REF_KW = dict(eps=1e-5, kinds=KINDS, rotated=8, theta_full=5e6,
              theta_window=1e4, window=WINDOW, value_scale=0.707,
              dense_layers=1, top_k=2, held_offset=0, num_experts=8,
              routed_scale=1.0)


def tiny_config(**over):
    kw = dict(
        vocab_size=VOCAB, hidden_size=32, num_layers=5, num_heads=4,
        num_kv_heads=2, attn_head_dim=24, attn_value_dim=16,
        attn_value_scale=0.707, rope_dim=8, rope_theta=5e6, rms_norm_eps=1e-5,
        intermediate_size=48, moe_intermediate_size=24, seq_length=CAP,
        layer_windows=tuple(WINDOW if t else None for t in KINDS),
        layer_kv_heads=tuple(4 if t else 2 for t in KINDS),
        layer_rope_theta=tuple(1e4 if t else 5e6 for t in KINDS),
        layer_sink=tuple(bool(t) for t in KINDS), attn_sink_init_std=1.0,
        tie_word_embeddings=False, use_moe=True, moe_pattern="sandwich",
        dense_start_layers=1, dense_end_layers=0, num_experts=8, moe_top_k=2,
        experts_held=(0, 4), moe_dispatch="gmm", capacity_factor=2.0,
        moe_score_func="sigmoid", moe_selection_bias=True,
        moe_selection_bias_init_std=0.3, num_shared_experts=0,
        precision="fp32", use_flash_attention=False,
        use_stable_embedding=False, scan_layers=False, prefill_chunk_size=6,
        routing_noise_std=0.0, attention_backend="ragged_xla", init_std=0.3,
        max_new_tokens=16,
    )
    kw.update(over)
    cfg = Config(**kw)
    cfg.validate()
    return cfg


@pytest.fixture(scope="module")
def tiny():
    cfg = tiny_config()
    model = LuminaTransformer(cfg)
    params = unbox(jax.jit(model.init)(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32))["params"])

    def reference(ids, drop_sink=False, **over):
        view = MIMO.adapter.params_view(cfg, params)
        if drop_sink:
            for lw in view["layers"]:
                lw["mixer"].pop("sink", None)
        return np.asarray(MIMO.reference.forward(
            view, jnp.asarray(ids)[None], **dict(REF_KW, **over)))[0]

    return dict(cfg=cfg, model=model, params=params, reference=reference,
                head=params["embedder"]["lm_head"])


def _prompt(seed, n):
    return np.random.RandomState(seed).randint(3, VOCAB, size=n).tolist()


def test_uncached_logits_match_the_reference(tiny):
    ids = np.random.RandomState(0).randint(3, VOCAB, size=(80,))
    got = np.asarray(jax.jit(lambda p: tiny["model"].apply(
        {"params": p}, jnp.asarray(ids)[None], deterministic=True)[0])(
            tiny["params"]))[0]
    want = tiny["reference"](ids)
    verdict = correct.compare_logits(got, want, rel_rms_tol=1e-4)
    assert verdict["ok"], verdict
    # blocked over the queries: the same rows
    assert np.abs(tiny["reference"](ids, q_block=32) - want).max() < 1e-4


CONTROLS = {
    "sink_left_out": dict(drop_sink=True),
    "value_scale_left_out": dict(value_scale=1.0),
    "window_129_for_128": dict(window=WINDOW + 1),
}


@pytest.mark.parametrize("case", ["ring_wrap", "two_lanes", "slot_reused"])
def test_cached_logits_match_the_reference_at_every_row(tiny, case):
    requests, slots, chunk, _why = CASES[case]
    dec, rows, seqs = serve(tiny, requests, slots=slots, chunk=chunk)
    assert dec.pool.ring_pages == 5
    for name, prompt, _ in requests:
        assert sorted(p for p, _ in rows[name]) == list(range(CAP))
        err, at = worst_row(tiny, rows[name], seqs[name][:CAP])
        assert err < 1e-4, (name, err, at)
        for control, over in CONTROLS.items():
            err, at = worst_row(tiny, rows[name], seqs[name][:CAP], **over)
            assert err > 1e-2, (name, control, err, at)


def test_the_kernels_serve_the_same_rows(tiny, monkeypatch):
    """The lanes through lane_attention (rings of 4 k/v heads and whole
    pages of 2, a key in two parts, the sink seeding the window layers'
    softmax) and every chunk through chunk_attention, both interpreted
    (backend 'ragged', no room for the chunk's scores), beside a slot that
    is never stepped: the same rows to the same tolerance, and the three
    controls fail."""
    calls = []
    lanes, chunk_k = rpa.lane_attention, rpa.chunk_attention

    def lanes_counted(q, k, v, meta, ring=False, **kw):
        calls.append(("lanes", ring, len(k), k[0].shape[2:], v.shape[2:],
                      kw["sink"] is not None))
        return lanes(q, k, v, meta, ring=ring, **kw)

    def chunk_counted(q, k, v, *a, **kw):
        calls.append(("chunk", k.shape[1:], v.shape[1:],
                      kw["sink"] is not None))
        return chunk_k(q, k, v, *a, **kw)

    monkeypatch.setattr(rpa, "lane_attention", lanes_counted)
    monkeypatch.setattr(rpa, "chunk_attention", chunk_counted)
    monkeypatch.setattr(layers, "_CHUNK_SCORES_LIMIT", 0)
    served = dict(tiny, cfg=tiny_config(attention_backend="ragged"))
    requests, _, chunk, _ = CASES["two_lanes"]
    dec, rows, seqs = serve(served, requests, slots=3, chunk=chunk)
    assert dec._lane_kernel and set(dec._lane_kernels) == {(None, 2), (8, 4)}
    # A window layer: a ring, 4 k/v heads, the sink; a full layer: whole
    # pages, 2 heads, none. A key is two parts of the value's 16 columns;
    # the chunk's kernel gets them side by side.
    assert set(calls) == {
        ("lanes", True, 2, (4, 16), (4, 16), True),
        ("lanes", False, 2, (2, 16), (2, 16), False),
        ("chunk", (4, 32), (4, 16), True),
        ("chunk", (2, 32), (2, 16), False),
    }
    for name, _, _ in requests:
        assert sorted(p for p, _ in rows[name]) == list(range(CAP))
        err, at = worst_row(tiny, rows[name], seqs[name][:CAP])
        assert err < 1e-4, (name, err, at)
        for control, over in CONTROLS.items():
            err, at = worst_row(tiny, rows[name], seqs[name][:CAP], **over)
            assert err > 1e-2, (name, control, err, at)
    assert 0 < dec.lane_attention_blocks_live < dec.lane_attention_blocks


def _qkv(rs, lanes, rows, hq, hkv, d, dv):
    q = jnp.asarray(rs.randn(lanes, 1, hq, d), jnp.float32)
    k = jnp.asarray(rs.randn(lanes, rows, hkv, d), jnp.float32)
    v = jnp.asarray(rs.randn(lanes, rows, hkv, dv), jnp.float32)
    return q, k, v


@pytest.mark.parametrize("hkv", [1, 4, 8])
@pytest.mark.parametrize("widths", [(16, 16), (24, 16)],
                         ids=["equal_widths", "key_wider_than_value"])
@pytest.mark.parametrize("with_sink", [False, True], ids=["plain", "sink"])
def test_lane_attention_is_the_xla_form(hkv, widths, with_sink):
    """The lanes' kernel (interpreted) against ragged_paged_attention_xla
    over whole pages and banded_attention_xla over a ring: a lane not
    stepped, lengths inside and across pages, a window; a key wider than
    its value handed over in value-width parts under the head's own scale;
    a sink that holds a third of the probability."""
    rs = np.random.RandomState(hkv)
    d, dv = widths
    lanes, pages, ps, hq = 3, 6, 4, 8
    q, k, v = _qkv(rs, lanes, pages * ps, hq, hkv, d, dv)
    sink = jnp.asarray(rs.randn(hq) + 1.0, jnp.float32) if with_sink else None
    parts = -(-d // dv)
    scale = d ** -0.5
    wide = ((0, 0),) * 3 + ((0, parts * dv - d),)
    qw, kw_ = jnp.pad(q, wide), jnp.pad(k, wide)
    k_parts = tuple(jnp.split(kw_, parts, axis=-1)) if parts > 1 else k
    lengths = jnp.asarray([13, 0, 22], jnp.int32)
    for window in (None, 5):
        meta = rpa.LaneMeta(lengths=lengths, window=window, page_size=ps)
        want = rpa.ragged_paged_attention_xla(q, k, v, meta, sink=sink)
        got = rpa.lane_attention(qw, k_parts, v, meta, scale=scale,
                                 sink=sink)
        assert got.shape == (lanes, 1, hq, dv)
        live = np.asarray(lengths) > 0
        assert float(jnp.abs(got - want)[live].max()) < 2e-5
        assert float(jnp.abs(got[~live]).max()) == 0.0
    # a ring of 3 pages under a window of 5, rows where a table put them
    table = jnp.asarray(np.tile(np.arange(pages) % 3, (lanes, 1)), jnp.int32)
    ring_rows = 3 * ps
    kr, vr = k[:, :ring_rows], v[:, :ring_rows]
    kr_parts = (tuple(a[:, :ring_rows] for a in k_parts)
                if parts > 1 else kr)
    meta = rpa.LaneMeta(lengths=lengths, window=5, page_size=ps,
                        ring_table=table)
    kpos = rpa.ring_key_positions(table, lengths, ps, ring_rows)
    want = rpa.banded_attention_xla(q, kr, vr, (lengths - 1)[:, None], kpos,
                                    5, sink=sink)
    got = rpa.lane_attention(qw, kr_parts, vr, meta, ring=True, scale=scale,
                             sink=sink)
    live = np.asarray(lengths) > 0
    assert float(jnp.abs(got - want)[live].max()) < 2e-5
    if with_sink:
        # not a no-op: without the sink the rows differ
        plain = rpa.lane_attention(qw, kr_parts, vr, meta, ring=True,
                                   scale=scale)
        assert float(jnp.abs(plain - got)[live].max()) > 1e-2


@pytest.mark.parametrize("hkv", [1, 4, 8])
@pytest.mark.parametrize("widths", [(16, 16), (24, 16)],
                         ids=["equal_widths", "key_wider_than_value"])
@pytest.mark.parametrize("with_sink", [False, True], ids=["plain", "sink"])
def test_chunk_attention_is_the_xla_form(hkv, widths, with_sink):
    """The chunk's kernel (interpreted) against banded_attention_xla: keys
    by position, padding queries, a window; values narrower than the keys;
    the sink."""
    rs = np.random.RandomState(10 + hkv)
    d, dv = widths
    n, C, hq = 8, 24, 8
    q = jnp.asarray(rs.randn(n, hq, d), jnp.float32)
    k = jnp.asarray(rs.randn(C, hkv, d), jnp.float32)
    v = jnp.asarray(rs.randn(C, hkv, dv), jnp.float32)
    sink = jnp.asarray(rs.randn(hq) + 1.0, jnp.float32) if with_sink else None
    qpos = jnp.asarray([9, 10, 11, 12, 13, -1, -1, -1], jnp.int32)
    kpos = jnp.where(jnp.arange(C) < 14, jnp.arange(C), -1).astype(jnp.int32)
    for window in (None, 5):
        want = rpa.banded_attention_xla(
            q[None], k[None], v[None], qpos[None], kpos[None], window,
            sink=sink)[0]
        got = rpa.chunk_attention(q, k, v, qpos, kpos, window, jnp.int32(C),
                                  sink=sink)
        assert got.shape == (n, hq, dv)
        assert float(jnp.abs(got - want)[:5].max()) < 2e-5
        assert float(jnp.abs(got[5:]).max()) == 0.0
    if with_sink:
        plain = rpa.chunk_attention(q, k, v, qpos, kpos, 5, jnp.int32(C))
        assert float(jnp.abs(plain - got)[:5].max()) > 1e-2


def test_sink_softmax_is_one_more_column_dropped_after_it():
    rs = np.random.RandomState(0)
    logits = jnp.asarray(rs.randn(2, 2, 3, 4, 7) * 3, jnp.float32)
    logits = logits.at[0, 0, 0, 1].set(rpa.NEG_INF)  # a row that sees nothing
    sink = jnp.asarray(rs.randn(6), jnp.float32)
    col = jnp.broadcast_to(sink.reshape(1, 2, 3, 1, 1), (2, 2, 3, 4, 1))
    want = jax.nn.softmax(jnp.concatenate([logits, col], -1), -1)[..., :-1]
    got = rpa.sink_softmax(logits, sink)
    assert float(jnp.abs(got - want).max()) < 1e-6
    assert float(jnp.abs(got[0, 0, 0, 1]).max()) == 0.0
    assert rpa.sink_softmax(logits, None).sum(-1).min() > 0.999


def test_flash_takes_the_sink_and_gives_its_gradient(monkeypatch):
    """The uncached kernel path: flash_attention with a sink (the plain
    kernels' output times sigmoid(lse - sink), exactly) against the XLA
    rule at 192-over-128-like widths (48 over 32) under a window, and
    every gradient, the sink's too, against jax.grad of the XLA rule."""
    rs = np.random.RandomState(0)
    B, S, hq, hkv, d, dv, window = 1, 128, 4, 2, 64, 32, 40
    q, k, v = (jnp.asarray(rs.randn(*s), jnp.float32) for s in (
        (B, S, hq, d), (B, S, hkv, d), (B, S, hkv, dv)))
    sink = jnp.asarray(rs.randn(hq), jnp.float32)
    pos = jnp.tile(jnp.arange(S)[None], (B, 1))

    def xla(q, k, v, sink):
        return rpa.banded_attention_xla(q, k, v, pos, pos, window, sink=sink)

    def flash(q, k, v, sink):
        return fa.flash_attention(q, k, v, causal=True, window=window,
                                  block_q=128, block_kv=128, sink=sink)

    want, got = xla(q, k, v, sink), flash(q, k, v, sink)
    assert float(jnp.abs(got - want).max()) < 2e-5
    assert float(jnp.abs(flash(q, k, v, None) - got).max()) > 1e-2
    w = jnp.asarray(rs.randn(*want.shape), jnp.float32)
    g_want = jax.grad(lambda *a: (xla(*a) * w).sum(), argnums=(0, 1, 2, 3))(
        q, k, v, sink)
    g_got = jax.grad(lambda *a: (flash(*a) * w).sum(), argnums=(0, 1, 2, 3))(
        q, k, v, sink)
    for a, b, name in zip(g_got, g_want, "qkvs"):
        assert float(jnp.abs(a - b).max()) < 1e-3 * float(
            jnp.abs(b).max()), name
    assert float(jnp.abs(g_want[3]).max()) > 1e-3


def test_a_stack_with_a_sink_trains(tiny):
    """Training such a stack works: the loss's gradient in the sink (and
    in a window layer's value projection) through the program's uncached
    forward is jax.grad of the reference's."""
    cfg, model, params = tiny["cfg"], tiny["model"], tiny["params"]
    ids = jnp.asarray(
        np.random.RandomState(1).randint(3, VOCAB, size=(1, 40)))

    def loss_of(logits):
        lp = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32))
        return -jnp.take_along_axis(lp, ids[:, 1:, None], -1).mean()

    def program(p):
        return loss_of(model.apply({"params": p}, ids, deterministic=True)[0])

    def reference(p):
        return loss_of(MIMO.reference.forward(
            MIMO.adapter.params_view(cfg, p), ids, **REF_KW))

    got = jax.jit(jax.grad(program))(params)["layer_1"]["attention"]
    want = jax.jit(jax.grad(reference))(params)["layer_1"]["attention"]
    assert float(jnp.abs(want["sink"]).max()) > 1e-5
    for name in ("sink", "wv", "wk"):
        assert float(jnp.abs(got[name] - want[name]).max()) < 1e-3 * float(
            jnp.abs(want[name]).max()), name


def test_a_slots_bytes_are_each_layers_own_rows_times_bytes(tiny):
    cfg = tiny["cfg"]
    engine = GenerationEngine(tiny["model"], tiny["params"], _Tok(), cfg)
    dec = engine.make_stepwise(num_slots=3, page_size=PAGE,
                               max_slot_tokens=CAP, prefill_chunk_tokens=6)
    pool = dec.pool
    assert pool.ring_pages == 5 and pool.pages == CAP // PAGE
    k_full, v_full = pool.caches[0]
    k_window, v_window = pool.caches[1]
    # whole pages of 2 k/v heads; a ring of 5 pages of 4; a key of 24
    # columns in two parts of the value's 16
    assert [a.shape for a in k_full] == [(3, 24, PAGE, 2, 16)] * 2
    assert v_full.shape == (3, 24, PAGE, 2, 16)
    assert [a.shape for a in k_window] == [(3, 5, PAGE, 4, 16)] * 2
    assert v_window.shape == (3, 5, PAGE, 4, 16)
    row = {i: cfg.kv_row_bytes(i, 4) for i in range(5)}
    assert row[0] == 2 * (32 + 16) * 4 and row[1] == 4 * (32 + 16) * 4
    full = sum(CAP * row[i] for i in (0, 3))
    ring = sum(5 * PAGE * row[i] for i in (1, 2, 4))
    assert pool.slot_bytes() == {"pages": full, "ring": ring, "latent": 0,
                                 "state": 0, "total": full + ring}
    assert dec._row_bytes == {2: row[0], 4: row[1]}


def test_the_byte_counters_follow_the_rows_by_kind(tiny):
    """serve_kv_{window,global}_bytes_read_total: each kind's rows x its
    own row bytes, so here (XLA attends: whole rings, lanes up to the
    extent) bytes / rows is each kind's row size."""
    requests, slots, chunk, _ = CASES["two_lanes"]
    dec, _, _ = serve(tiny, requests, slots=slots, chunk=chunk)
    assert dec.kv_window_rows > 0 and dec.kv_global_rows > 0
    assert dec.kv_window_bytes == dec.kv_window_rows * dec._row_bytes[4]
    assert dec.kv_global_bytes == dec.kv_global_rows * dec._row_bytes[2]
    from benchmark import layer_readers
    from luminaai_tpu.monitoring.telemetry import MetricsRegistry
    from luminaai_tpu.serving.server import ContinuousScheduler

    engine = GenerationEngine(tiny["model"], tiny["params"], _Tok(),
                              tiny["cfg"])
    registry = MetricsRegistry()
    sched = ContinuousScheduler(engine, num_slots=2, page_size=PAGE,
                                max_slot_tokens=CAP, registry=registry)
    got = correct.decode_through_scheduler(sched, _prompt(5, 41), 20)
    assert got == engine.generate(_prompt(5, 41), max_new_tokens=20,
                                  temperature=0.0)[0]

    def counters():
        return {k.split(":", 1)[1]: v for k, v in
                layer_readers.registry_view(registry).items()
                if k.startswith("counter:")}

    # (A tick's tokens reach the stream before its counts the registry.)
    deadline = time.time() + 30
    while (counters().get("serve_decode_steps_total", 0) < 20
           and time.time() < deadline):
        time.sleep(0.01)
    time.sleep(0.2)
    snap = counters()
    assert snap["serve_kv_window_bytes_read_total"] == (
        snap["serve_kv_window_rows_read_total"] * dec._row_bytes[4]) > 0
    assert snap["serve_kv_global_bytes_read_total"] == (
        snap["serve_kv_global_rows_read_total"] * dec._row_bytes[2]) > 0


# (n_q, n_kv, width of an array of the key, page, width of the value's):
# the lanes' shapes of every serving cell before this one
OLD_LANE_SHAPES = [
    (16, 16, 128, 128, None), (20, 1, 128, 128, None),
    (128, 8, 128, 128, None), (64, 1, 640, 128, None),
]


def test_the_eligibility_rules_at_the_published_shapes():
    # MiMo-V2-Flash: 64 query heads; window layers 8 k/v heads, full
    # layers 4; a key of 192 kept as two parts of 128 beside a value of 128
    for n_kv in (8, 4):
        assert rpa.lane_attention_eligible(64, n_kv, 128, 128, 128)
    # what does NOT flatten for free: 4 heads of one 256-column array, and
    # any head that is not whole lanes
    assert not rpa.lane_attention_eligible(64, 4, 256, 128, 128)
    assert rpa.lane_attention_eligible(64, 8, 256, 128, 128)
    assert not rpa.lane_attention_eligible(64, 4, 192, 128, 128)
    assert not rpa.lane_attention_eligible(64, 8, 128, 128, 64)
    # 2 heads of one 128-lane tile flatten for free like 4 (PR 63)
    assert rpa.lane_attention_eligible(64, 2, 128, 128)
    assert not rpa.lane_attention_eligible(64, 2, 256, 128)
    for shape in OLD_LANE_SHAPES:
        assert rpa.lane_attention_eligible(*shape), shape
    for n_kv in (3, 5, 6, 7):
        assert not rpa.lane_attention_eligible(16, n_kv, 128, 128)
    assert not rpa.lane_attention_eligible(16, 8, 64, 128)


def test_the_chunks_eligibility_on_the_chip(monkeypatch):
    monkeypatch.setattr(rpa, "_interpret", lambda: False)
    # the chunk's key comes as its parts side by side: 256 over 128
    assert rpa.chunk_attention_eligible(256, 10240, 256, 128)
    assert rpa.chunk_attention_eligible(256, 512, 256, 128)
    assert not rpa.chunk_attention_eligible(256, 512, 192, 128)
    assert not rpa.chunk_attention_eligible(256, 512, 256, 64)
    # the cells before this one
    assert rpa.chunk_attention_eligible(256, 4480, 128)
    assert rpa.chunk_attention_eligible(256, 16384, 128)
    assert rpa.chunk_attention_eligible(256, 32768, 640)
    assert not rpa.chunk_attention_eligible(256, 4480, 64)
    for n_kv in (8, 4):
        assert rpa.lane_attention_engaged(
            "ragged_xla", 1, 64, n_kv, 128, 128, 128)


@pytest.mark.parametrize("over, word", [
    (dict(layer_kv_heads=(2, 4, 4)), "layer_kv_heads names 3 layers"),
    (dict(layer_kv_heads=(2, 3, 4, 2, 4)), "divisible by the k/v heads"),
    (dict(layer_sink=(True,) * 4), "layer_sink names 4 layers"),
    (dict(layer_rope_theta=(1e4, 0.0, 1e4, 1e4, 1e4)), "positive"),
    (dict(rope_dim=7), "rope_dim 7"),
    (dict(rope_dim=26), "rope_dim 26"),
    (dict(attn_value_dim=32), "attn_value_dim 32"),
    (dict(attn_value_scale=0.0), "attn_value_scale"),
    (dict(kv_cache_dtype="int8"), "int8"),
    (dict(scan_layers=True), "one kind of layer"),
    (dict(sequence_parallel_size=2, layer_windows=None, attn_value_dim=None,
          attention_window=None), "layer_sink does not compose"),
], ids=["kv_heads_length", "kv_heads_divide", "sink_length", "theta",
        "odd_rotation", "rotation_past_the_head", "value_wider_than_key",
        "value_scale", "int8_over_parts", "scan_layers", "ring_sequence"])
def test_validate_refuses_by_name(over, word):
    with pytest.raises(AssertionError, match=word):
        tiny_config(**over)


def test_the_defaults_are_todays_program():
    cfg = Config()
    assert (cfg.layer_kv_heads, cfg.attn_value_dim, cfg.rope_dim,
            cfg.layer_rope_theta, cfg.layer_sink) == (None,) * 5
    assert cfg.attn_value_scale == 1.0 and cfg.key_parts() == 1
    assert cfg.kv_heads_of(0) == cfg.num_kv_heads
    assert cfg.value_dim() == cfg.head_dim()
    assert cfg.rope_theta_of(0) == cfg.rope_theta and not cfg.sink_of(0)
    k, v = layers.GQAttention.init_cache(cfg, 2, 16, jnp.float32, layer=0)
    assert k.shape == v.shape == (2, 16, cfg.num_kv_heads, cfg.head_dim())
    # the estimate follows the layer's own heads and the value's width
    wide = tiny_config()
    h, nq = wide.hidden_size, wide.num_heads
    attn = sum(h * nq * 24 + nq * 16 * h + h * wide.kv_heads_of(i) * 40
               + (nq if wide.sink_of(i) else 0) for i in range(5))
    plain = tiny_config(layer_kv_heads=None, attn_value_dim=None,
                        layer_sink=None)
    assert wide.estimate_parameters() - plain.estimate_parameters() == (
        attn - 5 * (2 * h * nq * 24 + 2 * h * 2 * 24))
