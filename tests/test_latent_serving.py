"""Latent attention served (ISSUE 45): a 'latent' layer keeps ONE row a
token a layer in the pool (LatentPages: the normed latent and the rotated
shared key part), and the tick attends in the absorbed form over it.

At a tiny size on the CPU, float32 (three layers: dense, experts,
experts; 4 heads, latent 32, nope / rope / value 16, low-rank queries of
24, interleaved pairs under a YaRN whose ramp lies inside the head and
whose original context is 16 positions; sigmoid top-4 of 16 experts with 4
held, a seeded selection bias, one shared expert), pages of 4 rows:

  - the CACHED path's logits (not tokens), read out of the tick program
    itself by a spy, against the plain reference's full forward pass
    (benchmark/architectures/kimi_k2) at EVERY row: a prompt of four
    chunks over six pages, then steps to the slot's end, a second lane
    stepping beside it, a lane in a slot another request has left; a
    control (the entry stored in bfloat16) must fail;
  - the same rows through the kernels (lane_attention and chunk_attention
    over a latent entry, interpreted);
  - the serving form against the training form, float32 and bfloat16;
  - what a slot holds, recover_pool, a page's round trip, speculation,
    and the prefix cache's refusal by name; the counters the host feeds.
"""

import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import manifest
from benchmark.architectures.kimi_k2.test_reference import K2_TINY, k2_build
from luminaai_tpu.inference.generate import (GenerationEngine,
                                             UnservedMixerError)
from luminaai_tpu.inference.kv_pool import LatentPagesOwnedError
from luminaai_tpu.models import layers
from luminaai_tpu.models.layers import (LatentAttention, LatentPages,
                                        latent_entry_width)
from luminaai_tpu.monitoring.telemetry import MetricsRegistry
from luminaai_tpu.ops.ragged_paged_attention import (LaneMeta,
                                                     chunk_attention,
                                                     lane_attention,
                                                     latent_attention_xla)
from luminaai_tpu.serving.server import ContinuousScheduler

K2 = manifest.Architecture("kimi_k2")
GREEDY = (0.0, 0, 1.0, 1.0)
PAGE, CAP, VOCAB = 4, 48, 512
PROGRAM = dict(seq_length=CAP, prefill_chunk_size=6, scan_layers=False,
               attention_backend="ragged_xla", init_std=0.3,
               max_new_tokens=16)


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
    cfg, model, params = k2_build(K2_TINY, **PROGRAM)
    assert cfg.layer_mixers == ("latent",) * 3
    kw = K2.reference.from_config_file(K2_TINY)

    def reference(ids):
        return np.asarray(K2.reference.forward(
            K2.adapter.params_view(cfg, params), jnp.asarray(ids)[None],
            **kw))[0]

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
    head = np.asarray(tiny["params"]["embedder"]["lm_head"])
    S, n = dec.num_slots, dec.prefill_chunk
    rows, seqs, lane, pending, done = {}, {}, {}, [], set()
    todo = sorted(requests, key=lambda r: r[2])
    for t in range(ticks):
        while todo and todo[0][2] <= t and dec.has_free_slot():
            name, prompt, _ = todo.pop(0)
            slot = dec.acquire_slot()
            st = dec.start_prefill(slot, prompt,
                                   max_new_tokens=cap - len(prompt) - 1,
                                   sample_key=GREEDY, seed=1)
            assert st is not None, "prompts here are longer than a chunk"
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
        logits = spy.seen[-1][:, 0] @ head.T
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


def worst_row(tiny, rows, seq):
    """(largest |served - reference| over a request's rows as a share of
    the logits' spread, the position it lies at)."""
    want = tiny["reference"](seq)
    worst = max((float(np.abs(got - want[p]).max()), p) for p, got in rows)
    return worst[0] / float(want.std()), worst[1]


def _prompt(seed, n):
    return np.random.RandomState(seed).randint(3, VOCAB, size=n).tolist()


# name -> (requests, slots, what the case is there for)
CASES = {
    "one_lane": ([("a", _prompt(1, 23), 0)], 1,
                 "four chunks of 6 over six pages of 4, then 25 steps"),
    "two_lanes": ([("a", _prompt(2, 23), 0), ("b", _prompt(3, 9), 2)], 2,
                  "a second lane admitted mid-prompt, stepping beside the "
                  "first's chunks and steps"),
    "slot_reused": ([("a", _prompt(4, 31), 0), ("b", _prompt(5, 13), 1)], 1,
                    "one slot: b is inserted where a's rows still lie"),
}


def _check_every_row(tiny, requests, rows, seqs, tol=1e-4):
    for name, prompt, _ in requests:
        # every position of the request was computed by some tick, once
        assert sorted(p for p, _ in rows[name]) == list(range(CAP))
        assert len(seqs[name]) == CAP + 1
        assert seqs[name][:len(prompt)] == prompt
        err, at = worst_row(tiny, rows[name], seqs[name][:CAP])
        assert err < tol, (name, err, at)


@pytest.mark.parametrize("case", sorted(CASES))
def test_cached_logits_match_the_reference_at_every_row(tiny, case):
    requests, slots, _why = CASES[case]
    dec, rows, seqs = serve(tiny, requests, slots=slots)
    _check_every_row(tiny, requests, rows, seqs)
    if case == "slot_reused":
        assert dec.pool.reuses == 1
    # what the host booked: a chunk's lane up to its end in three layers
    ends = [min(lo + 6, len(p)) for _, p, _ in requests
            for lo in range(0, len(p), 6)]
    assert dec.kv_latent_chunk_keys == 3 * sum(ends)
    assert dec.kv_latent_rows > 0 and dec.kv_global_rows == 0


def test_an_entry_stored_at_lower_precision_fails_the_same_test(
        tiny, monkeypatch):
    """The control: the same comparison with the entry kept in bfloat16
    (8 bits of mantissa under a float32 program) reads a hundred times
    the tolerance."""
    init = LatentAttention.init_cache

    def rounded(cfg, batch_size, max_len, dtype, lead=()):
        return init(cfg, batch_size, max_len, jnp.bfloat16, lead)

    monkeypatch.setattr(LatentAttention, "init_cache", staticmethod(rounded))
    requests, slots, _ = CASES["two_lanes"]
    dec, rows, seqs = serve(tiny, requests, slots=slots)
    assert dec.pool.caches[0].rows.dtype == jnp.bfloat16
    err, _ = worst_row(tiny, rows["a"], seqs["a"][:CAP])
    assert err > 1e-3, err
    with pytest.raises(AssertionError):
        _check_every_row(tiny, requests, rows, seqs)


def test_the_kernels_serve_the_same_rows(tiny, monkeypatch):
    """The lanes through lane_attention and every chunk through
    chunk_attention over the latent entry (both interpreted here: backend
    'ragged', no room for the chunk's scores), beside a slot that is never
    stepped."""
    from luminaai_tpu.ops import ragged_paged_attention as rpa

    calls = {"lanes": 0, "chunk": 0}
    lanes, chunk = rpa.lane_attention, rpa.chunk_attention

    def lanes_counted(q, k, v, meta, **kw):
        assert v is None and kw["v_dim"] == 32 and k.shape[2:] == (1, 128)
        calls["lanes"] += 1
        return lanes(q, k, v, meta, **kw)

    def chunk_counted(q, k, v, *args, **kw):
        assert v is None and kw["v_dim"] == 32 and q.shape == (6, 4, 128)
        calls["chunk"] += 1
        return chunk(q, k, v, *args, **kw)

    monkeypatch.setattr(rpa, "lane_attention", lanes_counted)
    monkeypatch.setattr(rpa, "chunk_attention", chunk_counted)
    monkeypatch.setattr(layers, "_CHUNK_SCORES_LIMIT", 0)
    import dataclasses

    served = dict(tiny, cfg=dataclasses.replace(
        tiny["cfg"], attention_backend="ragged"))
    requests, _, _ = CASES["two_lanes"]
    dec, rows, seqs = serve(served, requests, slots=3)
    assert calls["lanes"] == calls["chunk"] > 0  # each once a layer a trace
    _check_every_row(tiny, requests, rows, seqs)
    # The third slot was never stepped: of the grid's steps (3 lanes x key
    # blocks a layer) under two thirds fetched anything.
    assert 0 < dec.lane_attention_blocks_live
    assert 3 * dec.lane_attention_blocks_live < 2 * dec.lane_attention_blocks


def test_a_short_prompt_takes_the_whole_prompt_bucket(tiny):
    """A prompt no longer than a chunk is written by the bucket program and
    the insert; the rows stepped behind it see it through the entry."""
    spy = _Spy(tiny["model"])
    engine = GenerationEngine(spy, tiny["params"], _Tok(), tiny["cfg"])
    dec = engine.make_stepwise(num_slots=2, page_size=PAGE,
                               max_slot_tokens=CAP, prefill_chunk_tokens=6)
    head = np.asarray(tiny["params"]["embedder"]["lm_head"])
    prompt = _prompt(6, 5)
    slot = dec.acquire_slot()
    assert dec.start_prefill(slot, prompt, max_new_tokens=12,
                             sample_key=GREEDY, seed=1) is None
    seq = prompt + [dec.prefill_into_slot(
        slot, prompt, max_new_tokens=12, sample_key=GREEDY)["token"]]
    got = []
    for _ in range(10):
        at = int(dec._pos[slot])
        toks, _, _ = dec.decode_step(GREEDY)
        jax.effects_barrier()
        got.append((at, spy.seen[-1][slot, 0] @ head.T))
        seq.append(int(toks[slot]))
    want = tiny["reference"](seq[:-1])
    assert seq[len(prompt)] == int(want[len(prompt) - 1].argmax())
    for at, row in got:
        assert float(np.abs(row - want[at]).max()) / want.std() < 1e-4, at


@pytest.mark.parametrize("dtype, tol", [
    (jnp.float32, 1e-6),
    # bfloat16: the absorbed query and the summed latent are each rounded
    # once more than the expanded form's k and v (8 bits of mantissa, 2^-9
    # a rounding): rms 0.4% of the output's spread here, held to 1%, where
    # a wrong fold or a wrong scale reads tens of percent.
    (jnp.bfloat16, 1e-2),
], ids=["float32", "bfloat16"])
def test_the_serving_form_is_the_training_form(tiny, dtype, tol):
    """LatentAttention alone: prefill of 20 rows at a scalar offset, then
    one row at a time, against its own uncached call, at positions past
    the rotation's original context (16)."""
    cfg = tiny["cfg"]
    mixer = LatentAttention(cfg, dtype=dtype)
    x = jax.random.normal(jax.random.key(0), (2, 40, cfg.hidden_size))
    params = jax.jit(mixer.init)(jax.random.key(1), x)
    want, none = jax.jit(mixer.apply)(params, x)
    assert none is None
    cache = LatentAttention.init_cache(cfg, 2, 48, dtype)
    assert cache.rows.shape == (2, 48, 1, latent_entry_width(cfg) == 128
                                and 128)
    pos = jnp.tile(jnp.arange(40), (2, 1))
    got, cache = mixer.apply(params, x[:, :20], positions=pos[:, :20],
                             kv_cache=cache, cache_index=0)
    outs = [got]
    for t in range(20, 40):
        got, cache = mixer.apply(params, x[:, t:t + 1],
                                 positions=pos[:, t:t + 1], kv_cache=cache,
                                 cache_index=t)
        outs.append(got)
    got = jnp.concatenate(outs, axis=1).astype(jnp.float32)
    want = want.astype(jnp.float32)
    rel_rms = float(jnp.sqrt(jnp.mean(jnp.square(got - want)))
                    / want.std())
    assert rel_rms < tol, rel_rms
    # the entry: the normed latent, the rotated key part, zeros
    rows = np.asarray(cache.rows.astype(jnp.float32))
    assert np.abs(rows[:, :40, 0, :48]).min() > 0
    assert (rows[:, :, 0, 48:] == 0).all() and (rows[:, 40:] == 0).all()


@pytest.mark.parametrize("fold_rows", [8, 1024], ids=["a_head", "folded"])
def test_the_kernels_are_the_absorbed_attention(fold_rows, monkeypatch):
    """lane_attention and chunk_attention over a latent entry (`v=None`)
    against latent_attention_xla: one shared key row, the value its first
    columns, the mixer's own scale; a head a grid step, and four folded."""
    from luminaai_tpu.ops import ragged_paged_attention as rpa

    monkeypatch.setattr(rpa, "_CHUNK_FOLD_ROWS", fold_rows)
    rs = np.random.RandomState(0)
    n, T, C, H, W, V, page = 8, 3, 32, 4, 128, 64, 8
    rows = jnp.asarray(rs.randn(T, C, 1, W), jnp.float32)
    scale = 0.31
    # the chunk: rows 9..13 of slot 1 live, the rest padding
    q = jnp.asarray(rs.randn(n, H, W), jnp.float32)
    qpos = jnp.asarray([9, 10, 11, 12, 13, -1, -1, -1], jnp.int32)
    kpos = jnp.where(jnp.arange(C) < 14, jnp.arange(C), -1).astype(jnp.int32)
    want = latent_attention_xla(q[None], rows[1:2], qpos[None], scale, V)[0]
    got = chunk_attention(q, rows[1], None, qpos, kpos, None, jnp.int32(14),
                          scale=scale, v_dim=V)
    assert got.shape == (n, H, V)
    assert float(jnp.abs(got - want)[:5].max()) < 1e-5
    assert float(jnp.abs(got[5:]).max()) == 0.0
    # the lanes: slot 0 holds 19 rows, slot 1 is not stepped, slot 2 one
    lengths = jnp.asarray([19, 0, 1], jnp.int32)
    ql = jnp.asarray(rs.randn(T, 1, H, W), jnp.float32)
    meta = LaneMeta(lengths=lengths, page_size=page, backend="ragged",
                    extent=24)
    want = latent_attention_xla(ql, rows, (lengths - 1)[:, None], scale, V)
    got = lane_attention(ql, rows, None, meta, scale=scale, v_dim=V)
    assert got.shape == (T, 1, H, V)
    assert float(jnp.abs(got - want)[jnp.asarray([0, 2])].max()) < 1e-5


def test_a_slots_bytes_and_a_pool_lost_and_rebuilt(tiny):
    cfg = tiny["cfg"]
    engine = GenerationEngine(tiny["model"], tiny["params"], _Tok(), cfg)
    dec = engine.make_stepwise(num_slots=3, page_size=PAGE,
                               max_slot_tokens=CAP, prefill_chunk_tokens=6)
    pool = dec.pool
    width = latent_entry_width(cfg)
    assert width == 128  # 32 + 16 columns in whole 128-lane tiles
    entry = pool.caches[0]
    assert isinstance(entry, LatentPages) and len(pool.caches) == 3
    assert entry.rows.shape == (3, CAP // PAGE, PAGE, 1, width)
    # tokens x width x bytes x layers: ONE row a token a layer
    assert pool.slot_bytes() == {
        "pages": 0, "ring": 0, "latent": CAP * width * 4 * 3, "state": 0,
        "total": CAP * width * 4 * 3}
    assert not pool.keeps_state and pool.ring_pages == 0
    # a page's round trip: positions are absolute and the rotation is in
    # the row, so a page is whole as it lies
    slot = dec.acquire_slot()
    st = dec.start_prefill(slot, _prompt(7, 11), max_new_tokens=4,
                           sample_key=GREEDY, seed=1)
    while dec.advance_prefill(st) is None:
        pass
    payload = pool.export_page(slot * pool.pages + 1)
    before = np.asarray(pool.caches[1].rows[slot, 1])
    assert np.abs(before[:, 0, :48]).min() > 0
    pool.import_page(2 * pool.pages + 5, payload)
    assert (np.asarray(pool.caches[1].rows[2, 5]) == before).all()
    # a donating call that failed after the runtime took the buffers
    assert not dec.recover_pool()
    for leaf in jax.tree.leaves(pool.caches):
        leaf.delete()
    assert dec.recover_pool() and pool.rebuilds == 1
    assert isinstance(pool.caches[0], LatentPages)
    assert float(jnp.abs(pool.caches[1].rows).max()) == 0.0
    assert pool.slot_bytes()["latent"] == CAP * width * 4 * 3


def test_the_prefix_cache_is_refused_by_name_and_speculation_serves(tiny):
    cfg, model, params = tiny["cfg"], tiny["model"], tiny["params"]
    engine = GenerationEngine(model, params, _Tok(), cfg)
    with pytest.raises(LatentPagesOwnedError, match="prefix cache"):
        engine.make_stepwise(num_slots=2, page_size=PAGE,
                             max_slot_tokens=CAP, prefix_cache_pages=8)
    with pytest.raises(AssertionError, match="int8"):
        import dataclasses

        dataclasses.replace(cfg, kv_cache_dtype="int8")
    # speculation's k-row verify is a multi-row write at a scalar offset:
    # the entry takes it, and a rejected draft's rows are overwritten
    prompt = (_prompt(8, 6) * 4)[:21]
    want, _ = engine.generate(prompt, max_new_tokens=12, temperature=0.0)
    got, stats = engine.generate_speculative(prompt, max_new_tokens=12,
                                             draft_k=4)
    assert got == want and stats["verify_calls"] >= 1
    # a delta-rule layer beside the latent ones still refuses, by its name
    import dataclasses

    mixed = dataclasses.replace(
        cfg, layer_mixers=("latent", "kda", "latent"), kda_head_dim=16,
        kda_num_heads=4)
    assert mixed.unserved_mixers() == ("kda",)
    with pytest.raises(UnservedMixerError, match=r"\['kda'\]"):
        GenerationEngine(model, params, _Tok(), mixed)


def test_the_scheduler_serves_generates_tokens_and_feeds_the_counters(tiny):
    """ContinuousScheduler over a latent pool: the tokens generate() gives
    (the single-stream engine's scalar-offset cache), and the registry's
    counters: latent rows the lanes read, keys the chunks spanned, the
    share's pairs with none dropped."""
    from benchmark import layer_readers

    cfg = tiny["cfg"]
    engine = GenerationEngine(tiny["model"], tiny["params"], _Tok(), cfg)
    registry = MetricsRegistry()
    sched = ContinuousScheduler(engine, num_slots=2, page_size=PAGE,
                                max_slot_tokens=CAP, registry=registry)
    prompts = [_prompt(11, 27), _prompt(12, 5), _prompt(13, 14)]
    kw = {"max_new_tokens": 12, "temperature": 0.0}
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

    def counters():
        return {k.split(":", 1)[1]: v for k, v in
                layer_readers.registry_view(registry).items()
                if k.startswith("counter:")}

    # (the tick's rows: the short prompt went through the bucket program)
    chunked = [p for p in prompts if len(p) > 6]
    routed = 2 * 4 * (sum(len(p) for p in chunked) + 3 * 11)
    deadline = time.time() + 30
    while (counters()["moe_routed_pairs_total"] < routed
           and time.time() < deadline):
        time.sleep(0.01)
    snap = counters()
    steps = snap["serve_decode_steps_total"]
    assert snap["serve_kv_latent_chunk_keys_total"] == 3 * sum(
        min(lo + 6, len(p)) for p in chunked for lo in range(0, len(p), 6))
    # XLA attends the lanes here: both lanes up to the tick's extent
    assert 0 < snap["serve_kv_latent_rows_read_total"] <= 3 * 2 * CAP * steps
    assert snap["serve_kv_global_rows_read_total"] == 0
    assert snap["moe_routed_pairs_total"] == routed
    assert 0 < snap["moe_held_pairs_total"] < routed
    assert snap["moe_held_pairs_dropped_total"] == 0
    reader = manifest.Cell(
        manifest.load_benchmark(), "kimi-k2-7-code-serve-longctx"
    ).layer_metric_specs()["kv_latent_rows_per_step"]
    ctx = layer_readers.Context(
        registry_delta=layer_readers.registry_view(registry))
    assert layer_readers.read("kv_latent_rows_per_step", reader, ctx) == (
        snap["serve_kv_latent_rows_read_total"] / steps)

