"""Window and full attention layers side by side in one pool (ISSUE 42): a
layer with a window of its own keeps a RING of pages a lane, the full
layer beside it whole pages, and the tick serves both at absolute
positions through the pool's ring table.

At a tiny size on the CPU, float32 (window 8; layers window, window,
window, full; 6 query heads of 16 over hidden 32, 2 k/v heads; interleaved
rotation on the window layers alone; one LayerNorm a layer feeding
attention and the expert layer; sigmoid top-2 of 8 experts with 4 held and
2 shared experts averaged), pages of 4 rows, a ring of 5 pages (20 rows):

  - the CACHED path's logits (not tokens), read out of the tick program
    itself by a spy, against the plain reference's full forward pass
    (benchmark/architectures/cohere2_moe) at EVERY row: across the
    window's edge, across the ring's wrap (a prompt of five windows in
    chunks that do not divide the ring, then more than two rings of
    one-token steps), with a second lane of another length beside it, and
    in a slot another request's ring has been through; a control (the
    reference's window off by one) must fail;
  - the same through the blocked kernel (chunk_attention, interpreted);
  - a chunk's rows are written before they are read and never over a row
    still in some query's band (the ring table against a store of
    positions, for many windows, chunks and page sizes);
  - what a slot holds, by entry kind; what a ring cannot honour, refused
    by name; the counters the tick and the host feed;
  - the same recipe over an MHA stack of OLMoE's kind (pre-norm, RoPE,
    all-expert layers; benchmark/architectures/prenorm_decoder): its lanes'
    rows through lane_attention against the reference at every row, and
    the control that fails (a lane's length off by one) (PR 47).
"""

import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import manifest
from luminaai_tpu.config import Config
from luminaai_tpu.inference.generate import GenerationEngine
from luminaai_tpu.inference.kv_pool import RingKeepsWindowError
from luminaai_tpu.models import layers
from luminaai_tpu.models.transformer import LuminaTransformer
from luminaai_tpu.monitoring.telemetry import MetricsRegistry
from luminaai_tpu.ops.ragged_paged_attention import (LaneMeta,
                                                     banded_attention_xla,
                                                     chunk_attention,
                                                     ring_key_positions)
from luminaai_tpu.parallel.sharding import unbox
from luminaai_tpu.serving.server import ContinuousScheduler

COHERE = manifest.Architecture("cohere2_moe")
GREEDY = (0.0, 0, 1.0, 1.0)
WINDOW, PAGE, CAP, VOCAB = 8, 4, 96, 64
KINDS = ("sliding_attention",) * 3 + ("full_attention",)


def tiny_config(**over):
    kw = dict(
        vocab_size=VOCAB, hidden_size=32, num_layers=4, num_heads=6,
        num_kv_heads=2, attn_head_dim=16, intermediate_size=48,
        moe_intermediate_size=48, seq_length=CAP,
        layer_windows=(WINDOW, WINDOW, WINDOW, None),
        layer_rope=(True, True, True, False), rope_layout="interleaved",
        rope_theta=50000.0, norm_kind="layernorm", parallel_block=True,
        use_moe=True, moe_pattern="all", num_experts=8, moe_top_k=2,
        experts_held=(0, 4), moe_dispatch="gmm", capacity_factor=2.0,
        moe_score_func="sigmoid", num_shared_experts=2,
        shared_expert_combine="average", precision="fp32",
        use_flash_attention=False, use_stable_embedding=False,
        scan_layers=False, prefill_chunk_size=6, routing_noise_std=0.0,
        attention_backend="ragged_xla", init_std=0.3, max_new_tokens=16,
    )
    kw.update(over)
    cfg = Config(**kw)
    cfg.validate()
    return cfg


class _Tok:
    """The engine's tokenizer contract with stop ids outside the
    vocabulary."""

    vocab_size = VOCAB
    eos_token_id = pad_token_id = im_end = VOCAB + 1

    class backend:
        @staticmethod
        def encode(text):
            return [3 + (ord(c) % 50) for c in text]

    @staticmethod
    def decode(tokens):
        return " ".join(str(t) for t in tokens)


class _Spy:
    """The engine's model, handing out the tick's final hidden states."""

    def __init__(self, model):
        self._model = model
        self.seen = []

    def __getattr__(self, name):
        return getattr(self._model, name)

    def apply(self, *args, **kwargs):
        out = self._model.apply(*args, **kwargs)
        jax.debug.callback(lambda h: self.seen.append(np.asarray(h)), out[0])
        return out


@pytest.fixture(scope="module")
def tiny():
    cfg = tiny_config()
    model = LuminaTransformer(cfg)
    params = unbox(jax.jit(model.init)(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32))["params"])
    kw = dict(eps=cfg.layer_norm_eps, theta=cfg.rope_theta,
              layer_types=KINDS, window=WINDOW, top_k=2, held_offset=0,
              num_experts=8, n_shared=2)

    def reference(ids, **over):
        return np.asarray(COHERE.reference.forward(
            COHERE.adapter.params_view(cfg, params), jnp.asarray(ids)[None],
            **dict(kw, **over)))[0]

    return dict(cfg=cfg, model=model, params=params, reference=reference)


def serve(tiny, requests, *, slots=2, chunk=6, cap=CAP, ticks=400):
    """Drive a StepwiseDecoder as the scheduler does (one tick a turn, the
    oldest admission's chunk riding it) and return, a request, every row
    the tick computed for it as (position, logits) and its whole token
    sequence. `requests`: (name, prompt, admit at tick). A lane steps
    until its slot is full."""
    spy = _Spy(tiny["model"])
    engine = GenerationEngine(spy, tiny["params"], _Tok(), tiny["cfg"])
    dec = engine.make_stepwise(num_slots=slots, page_size=PAGE,
                               max_slot_tokens=cap,
                               prefill_chunk_tokens=chunk)
    # (An untied stack hands its own head: tests/test_sink_window_serving.)
    emb = np.asarray(
        tiny.get("head", tiny["params"]["embedder"]["embedding"]))
    S, n = dec.num_slots, dec.prefill_chunk
    rows, seqs, lane, pending, done = {}, {}, {}, [], set()
    todo = sorted(requests, key=lambda r: r[2])
    for t in range(ticks):
        while todo and todo[0][2] <= t and dec.has_free_slot():
            name, prompt, _ = todo.pop(0)
            slot = dec.acquire_slot()
            # (a budget the prompt is not trimmed for; the lane steps on
            # until its slot is full: nobody here enforces the budget)
            st = dec.start_prefill(slot, prompt,
                                   max_new_tokens=cap - len(prompt) - 1,
                                   sample_key=GREEDY, seed=1)
            assert st is not None, "a pool with rings takes chunks alone"
            lane[name], rows[name], seqs[name] = slot, [], list(prompt)
            pending.append((name, st))
        riding = pending[0] if pending and dec.prefill_ready(
            pending[0][1]) else None
        stepped = {nm: int(dec._pos[s]) for nm, s in lane.items()
                   if nm not in done and dec._active[s]}
        if riding:
            st = riding[1]
            start = dec._chunk_start(st)
            end = min(start + n, st["length"])
        if not dec.dispatch_step(GREEDY, chunk=riding[1] if riding else None):
            break
        toks, produced, _ = dec.collect_step()
        jax.effects_barrier()
        logits = spy.seen[-1][:, 0] @ emb.T
        for nm, p in stepped.items():
            if produced[lane[nm]]:
                rows[nm].append((p, logits[lane[nm]]))
                seqs[nm].append(int(toks[lane[nm]]))
        if riding:
            nm, st = riding
            rows[nm] += [(start + j, logits[S + j])
                         for j in range(end - start)]
            if "info" in st:
                seqs[nm].append(st.pop("info")["token"])
                pending.pop(0)
        for nm, s in lane.items():
            waiting = any(p[0] == nm for p in pending)
            if nm not in done and not waiting and (
                    dec.lane_full(s) or not dec._active[s]):
                done.add(nm)
                dec.release_slot(s)
        if not todo and not pending and len(done) == len(lane):
            break
    return dec, rows, seqs


def worst_row(tiny, rows, seq, **reference_kw):
    """(largest |served - reference| over a request's rows as a share of
    the logits' spread, the position it lies at)."""
    want = tiny["reference"](seq, **reference_kw)
    worst = max((float(np.abs(got - want[p]).max()), p) for p, got in rows)
    return worst[0] / float(want.std()), worst[1]


def _prompt(seed, n):
    return np.random.RandomState(seed).randint(3, VOCAB, size=n).tolist()


# name -> (requests, slots, chunk, what the case is there for)
CASES = {
    "window_edge": ([("a", _prompt(1, 7), 0)], 1, 6,
                    "a prompt inside the window, stepped across its edge"),
    "ring_wrap": ([("a", _prompt(2, 43), 0)], 1, 6,
                  "five windows of prompt in chunks of 6 over a ring of "
                  "20 rows, then two and a half rings of steps"),
    "chunk_divides_ring": ([("a", _prompt(3, 41), 0)], 1, 4,
                           "chunks of a page, a ring of 4 pages"),
    "two_lanes": ([("a", _prompt(4, 43), 0), ("b", _prompt(5, 11), 3)], 2, 6,
                  "a second lane of another length admitted while the "
                  "first is mid-prompt"),
    "slot_reused": ([("a", _prompt(6, 50), 0), ("b", _prompt(7, 9), 1)], 1,
                    6, "one slot: b's ring has been all the way round "
                    "under a"),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_cached_logits_match_the_reference_at_every_row(tiny, case):
    requests, slots, chunk, _why = CASES[case]
    dec, rows, seqs = serve(tiny, requests, slots=slots, chunk=chunk)
    ring = dec.pool.ring_pages * PAGE
    assert ring == (-(-(WINDOW + chunk) // PAGE) + 1) * PAGE < CAP
    for name, prompt, _ in requests:
        # every position of the request was computed by some tick, once
        assert sorted(p for p, _ in rows[name]) == list(range(CAP))
        assert len(seqs[name]) == CAP + 1 and seqs[name][:len(prompt)] == prompt
        err, at = worst_row(tiny, rows[name], seqs[name][:CAP])
        assert err < 1e-4, (name, err, at)
        # The control: a reference whose window is one key wider is
        # another model from the first row past the window's edge.
        err, at = worst_row(tiny, rows[name], seqs[name][:CAP],
                            window=WINDOW + 1)
        assert err > 1e-2 and at >= WINDOW, (name, err, at)
    if case == "slot_reused":
        assert dec.pool.reuses == 1
    # a row came round the ring once for every `ring` rows of a lane
    assert dec.ring_wraps == len(requests) * ((CAP - 1) // ring)


def test_a_window_layer_on_whole_pages_is_still_banded(tiny):
    """A lane no longer than the ring would be keeps whole pages in every
    layer (no ring table), and the window layers still mask their band."""
    cap = 20  # the ring would be 5 pages of 4 = 20 rows: no smaller
    dec, rows, seqs = serve(tiny, [("a", _prompt(8, 13), 0)], slots=1,
                            chunk=6, cap=cap)
    assert dec.pool.ring_pages == 0 and dec.ring_wraps == 0
    want = tiny["reference"](seqs["a"][:cap])
    assert sorted(p for p, _ in rows["a"]) == list(range(cap))
    worst = max(float(np.abs(got - want[p]).max()) for p, got in rows["a"])
    assert worst / float(want.std()) < 1e-4


def test_the_blocked_kernel_serves_the_same_rows(tiny, monkeypatch):
    """Every chunk through chunk_attention (interpreted here), ring and
    whole pages: the same rows to the same tolerance."""
    monkeypatch.setattr(layers, "_CHUNK_SCORES_LIMIT", 0)
    requests, slots, chunk, _ = CASES["two_lanes"]
    _, rows, seqs = serve(tiny, requests, slots=slots, chunk=chunk)
    for name, _, _ in requests:
        err, at = worst_row(tiny, rows[name], seqs[name][:CAP])
        assert err < 1e-4, (name, err, at)


@pytest.mark.parametrize("window", [None, 5])
def test_chunk_attention_is_banded_attention(window):
    """The kernel against the XLA rule on keys given by position: a ring's
    (positions out of row order, rows that hold nothing) and whole pages
    with a tail not yet written; padding queries see nothing."""
    rs = np.random.RandomState(0)
    n, C, hq, hkv, d = 8, 24, 4, 2, 16
    q, k, v = (jnp.asarray(rs.randn(*s), jnp.float32)
               for s in ((n, hq, d), (C, hkv, d), (C, hkv, d)))
    qpos = jnp.asarray([9, 10, 11, 12, 13, -1, -1, -1], jnp.int32)
    ring = np.full((C,), -1, np.int32)
    ring[np.arange(14) % C] = np.arange(14)
    ring = np.roll(ring, 5)  # positions where a table put them
    for kpos in (jnp.asarray(ring), jnp.where(
            jnp.arange(C) < 14, jnp.arange(C), -1).astype(jnp.int32)):
        want = banded_attention_xla(q[None], k[None], v[None], qpos[None],
                                    kpos[None], window)[0]
        got = chunk_attention(q, k, v, qpos, kpos, window, jnp.int32(C))
        assert float(jnp.abs(got - want)[:5].max()) < 1e-5
        assert float(jnp.abs(got[5:]).max()) == 0.0
    # no live key at all: a chunk that is all padding
    none = chunk_attention(q, k, v, jnp.full((n,), -1, jnp.int32),
                           jnp.full((C,), -1, jnp.int32), window,
                           jnp.int32(0))
    assert float(jnp.abs(none).max()) == 0.0


@pytest.mark.parametrize("window,chunk,page", [
    (8, 6, 4), (8, 4, 4), (8, 1, 4), (5, 7, 2), (16, 3, 8), (4096, 256, 128),
])
def test_a_chunk_never_lands_on_a_row_in_some_querys_band(window, chunk, page):
    """The ring's size is what makes this true: ceil((window + chunk) /
    page) + 1 pages. A store of positions written through the ring table
    chunk by chunk, then row by row: after each write, every key in the
    band of every query just written is still where it was put, and
    ring_key_positions reads the store back."""
    cfg = Config(num_layers=1, layer_windows=(window,),
                 prefill_chunk_size=chunk)
    n_ring = cfg.ring_pages(0, page, chunk)
    assert n_ring == -(-(window + chunk) // page) + 1
    rows, total = n_ring * page, 3 * n_ring * page + 5
    pages = -(-total // page)
    table = (np.arange(pages) % n_ring).astype(np.int32)[None]
    store = np.full((rows,), -1, np.int64)

    def write(lo, hi):
        at = np.arange(lo, hi)
        store[table[0, at // page] * page + at % page] = at
        for i in (lo, hi - 1):  # the band's two ends cover the rest
            band = np.arange(max(0, i - window + 1), i + 1)
            assert np.isin(band, store).all(), (lo, hi, i)
        kpos = np.asarray(ring_key_positions(
            jnp.asarray(table), jnp.asarray([hi], jnp.int32), page, rows))[0]
        held = kpos >= 0
        # what the table says is resident is what was written there (rows
        # of the last page past the length read as not yet written)
        assert (store[held & (kpos < hi)] == kpos[held & (kpos < hi)]).all()
        band = np.arange(max(0, lo - window + 1), hi)
        assert np.isin(band, kpos).all()

    prompt = 2 * rows + 3
    for lo in range(0, prompt, chunk):
        write(lo, min(lo + chunk, prompt))
    for at in range(prompt, total):
        write(at, at + 1)


def test_a_slots_bytes_by_entry_kind(tiny):
    engine = GenerationEngine(tiny["model"], tiny["params"], _Tok(),
                              tiny["cfg"])
    dec = engine.make_stepwise(num_slots=3, page_size=PAGE,
                               max_slot_tokens=CAP, prefill_chunk_tokens=6)
    pool = dec.pool
    assert pool.ring_pages == 5 and pool.pages == CAP // PAGE == 24
    k_window, k_full = pool.caches[0][0], pool.caches[3][0]
    assert k_window.shape == (3, 5, PAGE, 2, 16)  # a ring of 5 pages
    assert k_full.shape == (3, 24, PAGE, 2, 16)   # whole pages
    row = 2 * 16 * 4 * 2  # k/v heads x head x float32, k and v
    assert pool.slot_bytes() == {
        "pages": CAP * row, "ring": 3 * 5 * PAGE * row, "latent": 0,
        "state": 0,
        "total": (CAP + 3 * 5 * PAGE) * row}
    assert (pool.ring_tables == np.arange(24) % 5).all()
    assert pool.stats()["ring_pages"] == 5
    # a ring no smaller than the lane is whole pages
    whole = engine.make_stepwise(num_slots=1, page_size=PAGE,
                                 max_slot_tokens=16, prefill_chunk_tokens=6)
    assert whole.pool.ring_pages == 0
    assert whole.pool.slot_bytes()["ring"] == 0


def test_what_a_ring_cannot_honour_is_refused_by_name(tiny):
    cfg, model, params = tiny["cfg"], tiny["model"], tiny["params"]
    engine = GenerationEngine(model, params, _Tok(), cfg)
    kw = dict(num_slots=2, page_size=PAGE, max_slot_tokens=CAP)
    # 1. the prefix cache
    with pytest.raises(RingKeepsWindowError, match="prefix cache"):
        engine.make_stepwise(prefix_cache_pages=8, **kw)
    dec = engine.make_stepwise(**kw)
    # 2. page export and import
    with pytest.raises(RingKeepsWindowError, match="page export"):
        dec.pool.export_page(0)
    with pytest.raises(RingKeepsWindowError, match="page import"):
        dec.pool.import_page(0, b"")
    with pytest.raises(RingKeepsWindowError, match="page pull"):
        ContinuousScheduler(engine, page_share=object(),
                            registry=MetricsRegistry(), **kw)
    # 3. speculation's k-row verify: a multi-row write into the pool
    flat = dec._flat(dec.pool.caches)
    meta = LaneMeta(lengths=jnp.asarray([4, 0], jnp.int32),
                    page_table=dec._table, page_size=PAGE,
                    backend="ragged_xla", ring_table=dec._ring_table)
    with pytest.raises(RingKeepsWindowError, match="k-row verify"):
        model.apply({"params": params}, jnp.ones((2, 4), jnp.int32),
                    positions=jnp.tile(jnp.arange(4), (2, 1)),
                    kv_caches=flat, cache_index=jnp.zeros((2,), jnp.int32),
                    multi_row_update=True, lane_meta=meta)
    # and the paths the ring's writes do not have
    with pytest.raises(RingKeepsWindowError, match="whole prompt"):
        dec.prefill_into_slot(dec.acquire_slot(), [3, 4, 5])
    with pytest.raises(RingKeepsWindowError, match="chunked prefill"):
        engine.make_stepwise(prefill_chunk_tokens=0, **kw)
    for over, what in ((dict(kv_cache_dtype="int8"), "int8"),
                       (dict(attention_backend="dense"), "dense")):
        other = GenerationEngine(model, params, _Tok(), tiny_config(**over))
        with pytest.raises(RingKeepsWindowError, match=what):
            other.make_stepwise(**kw)
    # two windows that give two ring sizes: one table a pool
    two = tiny_config(layer_windows=(8, 16, 8, None))
    with pytest.raises(RingKeepsWindowError, match="one ring size"):
        GenerationEngine(LuminaTransformer(two), params, _Tok(),
                         two).make_stepwise(**kw)


def test_the_scheduler_serves_generates_tokens_and_feeds_the_counters(tiny):
    """ContinuousScheduler over rings and whole pages: the tokens
    generate() gives (the single-stream engine keeps whole rows and masks
    the band), and the registry's counters: rows read by kind of layer,
    wraps, the share's pairs with none dropped."""
    cfg = tiny["cfg"]
    engine = GenerationEngine(tiny["model"], tiny["params"], _Tok(), cfg)
    registry = MetricsRegistry()
    sched = ContinuousScheduler(engine, num_slots=2, page_size=PAGE,
                                max_slot_tokens=CAP, registry=registry)
    prompts = [_prompt(11, 37), _prompt(12, 5), _prompt(13, 21)]
    kw = {"max_new_tokens": 30, "temperature": 0.0}
    want = [engine.generate(p, **kw)[0] for p in prompts]
    got = [None] * len(prompts)

    def ask(i):
        got[i] = [x for x in sched.submit_stream(prompts[i], dict(kw))
                  if not isinstance(x, dict)]

    threads = [threading.Thread(target=ask, args=(i,))
               for i in range(len(prompts))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(120)
    assert got == want
    # A tick's tokens reach the streams before its counts reach the
    # registry (_emit_lanes, then _count_decoder): let the loop finish the
    # last tick's books.
    routed = 4 * 2 * (sum(len(p) for p in prompts) + 3 * 29)
    deadline = time.time() + 30
    while (_counters(registry)["moe_routed_pairs_total"] < routed
           and time.time() < deadline):
        time.sleep(0.01)
    snap = _counters(registry)
    steps = snap["serve_decode_steps_total"]
    ring = sched.decoder.pool.ring_pages * PAGE
    # every lane's ring a window layer a tick, plus the chunks' lanes
    assert snap["serve_kv_window_rows_read_total"] >= 3 * 2 * ring * steps
    assert snap["serve_kv_window_rows_read_total"] <= 3 * (2 * ring + ring) * steps
    assert 0 < snap["serve_kv_global_rows_read_total"] <= (2 * CAP + CAP) * steps
    assert snap["serve_ring_wraps_total"] == sum(
        (len(p) + 30 - 1 - 1) // ring for p in prompts)
    assert snap["moe_routed_pairs_total"] == routed
    assert 0 < snap["moe_held_pairs_total"] < routed
    assert snap["moe_held_pairs_dropped_total"] == 0


def _counters(registry):
    from benchmark import layer_readers

    return {k.split(":", 1)[1]: v
            for k, v in layer_readers.registry_view(registry).items()
            if k.startswith("counter:")}


def test_a_tick_whose_every_pair_is_held_drops_none(tiny):
    """All 8 experts held: the row bound is every pair of the tick
    (capacity_factor 1 = experts / held), each is computed, and the
    counters say so."""
    cfg = tiny_config(experts_held=(0, 8), capacity_factor=1.0)
    model = LuminaTransformer(cfg)
    params = unbox(jax.jit(model.init)(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32))["params"])
    dec = GenerationEngine(model, params, _Tok(), cfg).make_stepwise(
        num_slots=2, page_size=PAGE, max_slot_tokens=CAP)
    slot = dec.acquire_slot()
    st = dec.start_prefill(slot, _prompt(21, 17), max_new_tokens=8,
                           sample_key=GREEDY, seed=1)
    while dec.advance_prefill(st) is None:
        pass
    for _ in range(5):
        dec.decode_step(GREEDY)
    live_rows = 17 + 5
    assert dec.moe_routed_pairs == 4 * 2 * live_rows
    assert dec.moe_held_pairs == dec.moe_routed_pairs
    assert dec.moe_held_pairs_dropped == 0


def test_the_layers_metrics_are_reduced_in_one_order():
    """The tick reads the held-pair counts out of the reduced metrics, so
    the order their sums are emitted in is part of the compiled tick's cache
    key: sorted, not the iteration order of a set of strings, which differs
    from one process's hash seed to the next (the cell's set-up then compiled
    all eight extents anew in half of its runs)."""
    names = ("moe_routed_pairs", "moe_held_pairs", "moe_held_pairs_dropped",
             "load_balance_loss", "router_z_loss", "expert_load_min",
             "drop_rate")
    layers_metrics = [{k: jnp.float32(i) for k in names[i % 2:]}
                      for i in range(4)]
    out = LuminaTransformer._reduce_metrics(None, layers_metrics)
    assert list(out) == ["aux_loss"] + sorted(names)


def test_the_lanes_kernel_serves_the_same_rows(tiny, monkeypatch):
    """The lanes' decode rows through lane_attention (interpreted here:
    backend 'ragged'), rings and whole pages read in place: two lanes of
    different lengths across the window's edge and the ring's wrap beside
    a slot that is never stepped, the same rows to the same tolerance,
    and the control (the reference's window off by one) fails."""
    from luminaai_tpu.ops import ragged_paged_attention as rpa

    calls = []
    kernel = rpa.lane_attention

    def counted(q, k, v, meta, ring=False, **kw):
        calls.append((ring, k.shape[1]))
        return kernel(q, k, v, meta, ring=ring, **kw)

    monkeypatch.setattr(rpa, "lane_attention", counted)
    served = dict(tiny, cfg=tiny_config(attention_backend="ragged"))
    requests, _, chunk, _ = CASES["two_lanes"]
    dec, rows, seqs = serve(served, requests, slots=3, chunk=chunk)
    ring = dec.pool.ring_pages * PAGE
    # Every tick program traced three rings and one layer of whole pages.
    assert sorted(set(calls)) == [(False, CAP), (True, ring)]
    assert calls.count((True, ring)) == 3 * calls.count((False, CAP))
    for name, _, _ in requests:
        assert sorted(p for p, _ in rows[name]) == list(range(CAP))
        err, at = worst_row(tiny, rows[name], seqs[name][:CAP])
        assert err < 1e-4, (name, err, at)
        err, at = worst_row(tiny, rows[name], seqs[name][:CAP],
                            window=WINDOW + 1)
        assert err > 1e-2 and at >= WINDOW, (name, err, at)
    # The third slot was never stepped: of the grid's steps (3 lanes x key
    # blocks a layer) under two thirds fetched anything.
    assert 0 < dec.lane_attention_blocks_live
    assert 3 * dec.lane_attention_blocks_live < 2 * dec.lane_attention_blocks


# -- one query head a k/v head (OLMoE's kind of stack) through the kernel ----
PRENORM = manifest.Architecture("prenorm_decoder")


@pytest.fixture(scope="module")
def tiny_mha():
    """OLMoE in small: a pre-norm RoPE stack of three all-expert layers
    (top-2 of 8 renormalised, sort dispatch with a dropless capacity), 4
    query heads of 8 each over its own k/v head, whole pages alone."""
    cfg = Config(
        vocab_size=VOCAB, hidden_size=32, num_layers=3, num_heads=4,
        num_kv_heads=4, intermediate_size=48, seq_length=CAP, use_moe=True,
        moe_pattern="all", num_experts=8, moe_top_k=2, moe_dispatch="sort",
        capacity_factor=4.0, routing_noise_std=0.0, precision="fp32",
        use_flash_attention=False, use_stable_embedding=False,
        scan_layers=False, prefill_chunk_size=6, attention_backend="ragged",
        init_std=0.3, max_new_tokens=16, tie_word_embeddings=True,
    )
    cfg.validate()
    model = LuminaTransformer(cfg)
    params = unbox(jax.jit(model.init)(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32))["params"])

    def reference(ids):
        return np.asarray(PRENORM.reference.forward(
            PRENORM.adapter.params_view(cfg, params), jnp.asarray(ids)[None],
            eps=cfg.rms_norm_eps, theta=cfg.rope_theta, top_k=2,
            combine="renormalised"))[0]

    return dict(cfg=cfg, model=model, params=params, reference=reference)


@pytest.mark.parametrize("off_by", [0, 1], ids=["as_served", "length_off_by_one"])
def test_an_mha_stacks_lanes_go_through_the_kernel_at_every_row(
        tiny_mha, monkeypatch, off_by):
    """The lanes' decode rows of an MHA stack through lane_attention
    (interpreted: backend 'ragged'), the rule PR 47 opened to it: two
    lanes of different lengths beside a slot that is never stepped, the
    cached path's logits against the plain reference at every row. The
    control: the kernel told each lane holds one row fewer (its query no
    longer sees its own key) is another model."""
    from luminaai_tpu.ops import ragged_paged_attention as rpa

    calls = []
    kernel = rpa.lane_attention

    def counted(q, k, v, meta, ring=False, **kw):
        calls.append((q.shape[2], k.shape[2], k.shape[1]))
        meta = meta.replace(lengths=jnp.maximum(meta.lengths - off_by, 0))
        return kernel(q, k, v, meta, ring=ring, **kw)

    monkeypatch.setattr(rpa, "lane_attention", counted)
    requests = [("a", _prompt(8, 43), 0), ("b", _prompt(9, 11), 3)]
    dec, rows, seqs = serve(tiny_mha, requests, slots=3, chunk=6)
    assert dec._lane_kernel and dec.pool.ring_pages == 0
    assert set(calls) == {(4, 4, CAP)}  # every layer, the pool unsliced
    for name, prompt, _ in requests:
        assert sorted(p for p, _ in rows[name]) == list(range(CAP))
        # the chunk's rows are XLA's on both sides: the lanes' rows tell
        stepped = [(p, got) for p, got in rows[name] if p >= len(prompt)]
        err, at = worst_row(tiny_mha, stepped, seqs[name][:CAP])
        if off_by:
            assert err > 1e-2, (name, err, at)
        else:
            assert err < 1e-4, (name, err, at)
            err, _ = worst_row(tiny_mha, rows[name], seqs[name][:CAP])
            assert err < 1e-4, (name, err)
    assert 0 < dec.lane_attention_blocks_live
    assert 3 * dec.lane_attention_blocks_live < 2 * dec.lane_attention_blocks
