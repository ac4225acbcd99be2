"""State-space layers served (ISSUE 37): a lane keeps a fixed state a
layer beside its pages of k/v, and the tick's kernel (ops/ssm.py) carries
it through a prefill chunk and steps every lane.

At a tiny size on the CPU, float32, two periods of four layers with the
attention layer (no positions, one k/v head) inside each:

  - the CACHED path's logits (not tokens), read out of the tick program
    itself: chunked prefill of a prompt that is no multiple of the chunk,
    then one-token steps, through the state pool and the pages, with
    other lanes live and a lane reused after release, against the plain
    reference's full forward pass (benchmark/architectures/jamba) to 1e-4
    of the logits' spread;
  - tokens through ContinuousScheduler against generate();
  - rows that are not there change nothing, bit for bit; a released
    lane's next request starts from zero; recover_pool rebuilds the
    states; what cannot share or roll back a state refuses by name.
"""

import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import manifest
from luminaai_tpu.config import Config
from luminaai_tpu.data.tokenizer import ConversationTokenizer
from luminaai_tpu.inference.generate import (GenerationEngine,
                                             UnservedMixerError)
from luminaai_tpu.inference.kv_pool import StateNotPagedError, lane_states
from luminaai_tpu.models.transformer import LuminaTransformer
from luminaai_tpu.monitoring.telemetry import MetricsRegistry
from luminaai_tpu.parallel.sharding import unbox
from luminaai_tpu.serving.server import ContinuousScheduler

JAMBA = manifest.Architecture("jamba")
CHUNK = 16
GREEDY = (0.0, 0, 1.0, 1.0)
MIXERS = ("ssm", "ssm", "attention", "ssm") * 2


class _NoStop:
    """The tokenizer with its stop ids outside the vocabulary."""

    def __init__(self, tok):
        self._tok = tok
        self.eos_token_id = self.pad_token_id = self.im_end = (
            tok.vocab_size + 1)

    def __getattr__(self, name):
        return getattr(self._tok, name)


class _Spy:
    """The engine's model, handing out what each program computed: the
    model's first output (the tick's final hidden states, the bucket
    program's logits) of every call, in order."""

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
    tok = ConversationTokenizer()
    cfg = Config(
        vocab_size=tok.vocab_size, hidden_size=64, intermediate_size=160,
        num_layers=8, num_heads=4, num_kv_heads=1, seq_length=128,
        layer_mixers=MIXERS, use_rope=False, ssm_dt_rank=4,
        use_flash_attention=False, precision="fp32",
        gradient_checkpointing=False, max_new_tokens=16,
        prefill_chunk_size=CHUNK, attention_backend="ragged_xla",
        use_stable_embedding=False, tie_word_embeddings=True,
    )
    model = LuminaTransformer(cfg)
    params = unbox(jax.jit(model.init)(
        jax.random.key(0), jnp.ones((1, 8), jnp.int32))["params"])

    def scale(path, x):
        # At its initial scale the model repeats its last prompt token
        # whatever the cache holds; eight times the matrices and a
        # stream follows the context, so a wrong state or tail shows.
        name = jax.tree_util.keystr(path)
        if x.ndim >= 2 and "embed" not in name and "A_log" not in name:
            return x * 8.0
        if "conv_bias" in name or "_norm" in name:
            return x + 0.3 * jax.random.normal(
                jax.random.key(len(name)), x.shape)
        return x

    params = jax.tree_util.tree_map_with_path(scale, params)
    engine = GenerationEngine(model, params, _NoStop(tok), cfg)
    text = tok.encode_text(
        "the quick brown fox jumps over the lazy dog again and again and "
        "then some more of it")
    prompts = {"long": text[:37], "short": text[40:51], "mid": text[3:26]}
    kw = JAMBA.reference.from_config_file({
        "rms_norm_eps": cfg.rms_norm_eps, "attn_layer_period": 4,
        "attn_layer_offset": 2, "num_hidden_layers": 8})
    assert kw["layer_kinds"] == MIXERS
    ref = jax.jit(lambda ids: JAMBA.reference.forward(
        JAMBA.adapter.params_view(cfg, params), ids, **kw))
    return dict(tok=tok, cfg=cfg, model=model, params=params, engine=engine,
                prompts=prompts, ref=ref)


def _decoder(tiny, slots=3, **kw):
    return tiny["engine"].make_stepwise(
        num_slots=slots, page_size=16, max_slot_tokens=64, **kw)


def _admit(dec, prompt, budget, seed=1):
    slot = dec.acquire_slot()
    st = dec.start_prefill(slot, prompt, max_new_tokens=budget,
                           sample_key=GREEDY, seed=seed)
    if st is None:
        info = dec.prefill_into_slot(slot, prompt, max_new_tokens=budget,
                                     sample_key=GREEDY, seed=seed)
        return slot, None, [info["token"]]
    return slot, st, []


def _states(dec):
    return [(np.asarray(s.state), np.asarray(s.tail))
            for s in lane_states(dec.pool.caches)]


def test_the_pool_holds_two_kinds_of_entry(tiny):
    dec = _decoder(tiny)
    tree = dec.pool.caches
    assert len(lane_states(tree)) == 6 and len(tree) == 8
    st = lane_states(tree)[0]
    assert st.state.shape == (3, 16, 128) and st.state.dtype == jnp.float32
    assert st.tail.shape == (3, 3, 128)
    k, _ = tree[2]
    assert k.shape == (3, 4, 16, 1, 16)  # slots, pages, page, kv heads, d
    assert dec.pool.keeps_state
    assert sum(s.nbytes() for s in lane_states(tree)) \
        == 6 * 3 * (16 * 128 + 3 * 128) * 4


def test_cached_logits_match_the_reference(tiny):
    """Every row the tick program computed for three requests: `mid`
    decoding while `long` is prefilled chunk by chunk (16 + 16 + 5 rows)
    in the same ticks, then both stepping, then `short` (the whole-prompt
    bucket path) in the slot `mid` gave back: each row's logits against
    the reference's full forward pass over that request's whole sequence."""
    spy = _Spy(tiny["model"])
    engine = GenerationEngine(spy, tiny["params"], _NoStop(tiny["tok"]),
                              tiny["cfg"])
    dec = engine.make_stepwise(num_slots=3, page_size=16, max_slot_tokens=64)
    S, emb = dec.num_slots, np.asarray(tiny["params"]["embedder"]["embedding"])
    seqs, rows = {}, {}  # name -> its tokens; name -> [(position, logits)]

    def tick(chunk_of=None):
        st = chunk_of and chunk_of[1]
        riding = st is not None and dec.prefill_ready(st)
        if riding:
            start, slot = dec._chunk_start(st), st["slot"]
            end = min(start + CHUNK, st["length"])
        stepped_pos = {n: int(dec._pos[s]) for n, s in slots.items()
                       if dec._active[s]}
        assert dec.dispatch_step(GREEDY, chunk=st if riding else None)
        toks, produced, _ = dec.collect_step()
        jax.effects_barrier()
        logits = spy.seen[-1][:, 0] @ emb.T
        for n, p in stepped_pos.items():
            rows[n].append((p, logits[slots[n]]))
            seqs[n].append(int(toks[slots[n]]))
        if riding:
            name = chunk_of[0]
            rows[name] += [(start + j, logits[S + j])
                           for j in range(end - start)]
            if "info" in st:
                seqs[name].append(st.pop("info")["token"])

    slots = {}
    for name in ("mid", "long", "short"):
        seqs[name], rows[name] = list(tiny["prompts"][name]), []
    slots["mid"], st_mid, _ = _admit(dec, tiny["prompts"]["mid"], 12)
    while "mid" not in [n for n in slots if dec._active[slots[n]]]:
        tick(("mid", st_mid))
    tick()
    slots["long"], st_long, _ = _admit(dec, tiny["prompts"]["long"], 6)
    while st_long["next"] < st_long["n_chunks"] or not dec._active[slots["long"]]:
        tick(("long", st_long))
    for _ in range(4):
        tick()
    assert len(seqs["long"]) == 37 + 5 and len(seqs["mid"]) > 23 + 8
    dec.release_slot(slots.pop("mid"))
    n_seen = len(spy.seen)
    slot, st, first = _admit(dec, tiny["prompts"]["short"], 5)
    assert st is None and slot == 0  # whole prompt, the slot `mid` left
    jax.effects_barrier()
    # The bucket program's rows (one call, [1, bucket] tokens; it asks
    # the model for logits, not the hidden state).
    bucket = spy.seen[n_seen][0]
    rows["short"] += [(j, bucket[j]) for j in range(11)]
    seqs["short"].append(first[0])
    slots["short"] = slot
    for _ in range(3):
        tick()

    for name in ("mid", "long", "short"):
        ids = np.asarray(seqs[name], np.int32)[None]
        want = np.asarray(tiny["ref"](ids))[0]
        spread = float(np.std(want))
        assert len(rows[name]) >= len(tiny["prompts"][name]) + 3
        for p, got in rows[name]:
            err = float(np.sqrt(np.mean(np.square(got - want[p]))))
            assert err <= 1e-4 * spread, (name, p, err / spread)
            if len(tiny["prompts"][name]) - 1 <= p < ids.shape[1] - 1:
                # Greedy: from the prompt's last row on, a row chose
                # the token after it.
                assert int(got.argmax()) == int(ids[0, p + 1])
    assert dec.ssm_rows == dec.chunk_rows + sum(
        len(seqs[n]) - len(tiny["prompts"][n]) - 1 for n in seqs)


def test_rows_that_are_not_there_change_nothing(tiny):
    """A tick that steps one lane and carries an empty chunk, then one
    that carries a chunk and steps no lane: every other slot's state and
    tail come back bit for bit."""
    dec = _decoder(tiny)
    a, st_a, _ = _admit(dec, tiny["prompts"]["long"], 8)
    while dec.advance_prefill(st_a) is None:
        pass
    b, st_b, _ = _admit(dec, tiny["prompts"]["mid"], 8)
    assert dec.advance_prefill(st_b) is None  # b: one chunk in, parked
    before = _states(dec)
    dec.decode_step(GREEDY)  # steps a alone; 16 padding rows ride
    after = _states(dec)
    for (s0, t0), (s1, t1) in zip(before, after):
        assert not np.array_equal(s0[a], s1[a])
        assert not np.array_equal(t0[a], t1[a])
        for idle in (b, 2):
            assert s0[idle].tobytes() == s1[idle].tobytes()
            assert t0[idle].tobytes() == t1[idle].tobytes()
    assert dec.advance_prefill(st_b) is not None  # 7 live rows, 9 padding
    last = _states(dec)
    for (s1, t1), (s2, t2) in zip(after, last):
        assert not np.array_equal(s1[b], s2[b])
        for idle in (a, 2):
            assert s1[idle].tobytes() == s2[idle].tobytes()
            assert t1[idle].tobytes() == t2[idle].tobytes()


def test_an_idle_lanes_state_never_crosses_the_kernel():
    """`ssm_scan` copies in and out only the stepped lanes' slabs and a
    live chunk's: an idle lane's state may hold anything (NaN here) and
    neither reaches a live row nor changes by a bit; a chunk with no live
    row leaves its slot alone too."""
    from luminaai_tpu.ops import ssm

    slots, chunk, N, D = 6, 8, 4, 256
    ks = jax.random.split(jax.random.key(5), 6)
    R = slots + chunk
    state = jax.random.normal(ks[0], (slots, N, D), jnp.float32)
    idle = np.array([1, 4])
    state = state.at[idle].set(jnp.nan)
    x, z = (jax.random.normal(k, (R, D), jnp.float32) for k in ks[1:3])
    dt = jax.nn.softplus(jax.random.normal(ks[3], (R, D), jnp.float32))
    b, c = (jax.random.normal(k, (R, N), jnp.float32) for k in ks[4:6])
    a = -jnp.ones((N, D), jnp.float32)
    pos = np.full((R,), 9, np.int32)
    pos[idle] = -1
    for chunk_slot, live_rows in ((4, 0), (1, 5)):
        pos[slots:] = np.where(np.arange(chunk) < live_rows,
                               16 + np.arange(chunk), -1)
        y, out = jax.jit(lambda st, p: ssm.ssm_scan(
            st, x, z, dt, b, c, a, jnp.ones((D,)), p, lanes=slots,
            chunk_slot=chunk_slot, chunk_start=0))(state, jnp.asarray(pos))
        assert np.isfinite(np.asarray(y)[pos >= 0]).all()
        out, was = np.asarray(out), np.asarray(state)
        for lane in range(slots):
            same = out[lane].tobytes() == was[lane].tobytes()
            written = pos[lane] >= 0 or (lane == chunk_slot and live_rows)
            assert same != bool(written), (lane, chunk_slot, live_rows)
        assert np.isfinite(out[pos[:slots] >= 0]).all()


def _serve(dec, prompt, budget):
    slot, st, out = _admit(dec, prompt, budget)
    while not out:
        info = dec.advance_prefill(st)
        out = [info["token"]] if info else []
    while len(out) < budget:
        toks, produced, _ = dec.decode_step(GREEDY)
        assert produced[slot]
        out.append(int(toks[slot]))
    dec.release_slot(slot)
    return out


@pytest.mark.parametrize("name", ["long", "short"])
def test_a_released_lanes_next_request_starts_from_zero(tiny, name):
    """The slot a request left holds that request's state; the next one
    in it (chunked, or the whole-prompt path) reads none of it."""
    want = tiny["engine"].generate(
        tiny["prompts"][name], max_new_tokens=6, temperature=0.0,
        repetition_penalty=1.0, seed=1)[0]
    dec = _decoder(tiny, slots=1)
    _serve(dec, tiny["prompts"]["mid"], 4)
    assert any(s.any() for s, _ in _states(dec))  # the slot is dirty
    assert _serve(dec, tiny["prompts"][name], 6) == want


def test_recover_pool_rebuilds_the_states(tiny):
    dec = _decoder(tiny, slots=2)
    want = _serve(dec, tiny["prompts"]["long"], 5)
    slot, st, _ = _admit(dec, tiny["prompts"]["mid"], 4)
    dec.advance_prefill(st)
    for leaf in jax.tree.leaves(dec.pool.caches):
        leaf.delete()
    assert dec.recover_pool() is True and dec.pool.rebuilds == 1
    assert len(lane_states(dec.pool.caches)) == 6
    assert not any(s.any() or t.any() for s, t in _states(dec))
    dec.release_slot(slot)
    assert _serve(dec, tiny["prompts"]["long"], 5) == want


def test_tokens_through_the_scheduler_match_generate(tiny):
    engine = tiny["engine"]
    registry = MetricsRegistry()
    sched = ContinuousScheduler(engine, num_slots=2, page_size=16,
                                max_slot_tokens=64, registry=registry)
    kw = dict(temperature=0.0, repetition_penalty=1.0)
    out = {}

    def hit(name):
        out[name] = sched.submit(
            tiny["prompts"][name], dict(max_new_tokens=7, seed=3, **kw))[0]

    threads = [threading.Thread(target=hit, args=(n,))
               for n in ("long", "short", "mid")]
    for th in threads:
        th.start()
    for th in threads:
        th.join(300)
    for name, got in out.items():
        assert got == engine.generate(
            tiny["prompts"][name], max_new_tokens=7, seed=3, **kw)[0], name
    assert len(out) == 3
    snap = {f.name: sum(c.value for c in f.children())
            for f in registry.families() if f.type in ("counter", "gauge")}
    assert snap["ssm_rows_total"] >= 37 + 23 + 3 * 6


def test_what_cannot_share_a_state_refuses_by_name(tiny):
    engine = tiny["engine"]
    with pytest.raises(StateNotPagedError, match="prefix cache"):
        _decoder(tiny, prefix_cache_pages=4)
    with pytest.raises(StateNotPagedError, match="page pull"):
        ContinuousScheduler(engine, num_slots=1, page_size=16,
                            max_slot_tokens=64, page_share=object())
    dec = _decoder(tiny, slots=1)
    with pytest.raises(StateNotPagedError, match="page export"):
        dec.pool.export_page(0)
    with pytest.raises(StateNotPagedError, match="page import"):
        dec.pool.import_page(0, b"")
    with pytest.raises(UnservedMixerError, match="speculation"):
        engine.generate_speculative(tiny["prompts"]["mid"], 4)
    with pytest.raises(UnservedMixerError, match="speculation"):
        engine.generate_stream_speculative(tiny["prompts"]["mid"], 4)


@pytest.mark.parametrize("kind", ["kda", "latent"])
def test_the_other_mixers_still_refuse(tiny, kind):
    """A delta-rule layer beside the state-space ones is refused by its
    name; a latent layer (served since PR 45: a paged entry of one latent
    a token) no longer is."""
    import dataclasses

    cfg = dataclasses.replace(
        tiny["cfg"], layer_mixers=("ssm", kind) * 4, kda_head_dim=16,
        kda_num_heads=4, kv_lora_rank=16, qk_nope_head_dim=8,
        qk_rope_head_dim=8, v_head_dim=8)
    assert cfg.keeps_lane_state()
    if kind == "latent":
        assert not cfg.recurrent_or_latent() and not cfg.unserved_mixers()
        GenerationEngine(LuminaTransformer(cfg), {}, tiny["tok"], cfg)
    else:
        assert cfg.recurrent_or_latent()
        with pytest.raises(UnservedMixerError, match=r"\['kda'\]"):
            GenerationEngine(LuminaTransformer(cfg), {}, tiny["tok"], cfg)
    assert not tiny["cfg"].recurrent_or_latent()


def test_single_sequence_chunking_needs_an_aligned_context(tiny):
    """generate()'s chunked prefill re-feeds rows where the chunk grid
    overhangs the cache; a state would take them twice, so an engine
    whose context is no multiple of the chunk keeps the bucket ladder,
    and serves the same tokens."""
    odd = GenerationEngine(tiny["model"], tiny["params"],
                           _NoStop(tiny["tok"]), tiny["cfg"], max_context=60)
    assert odd._prefill_chunk_len() == 0
    assert tiny["engine"]._prefill_chunk_len() == CHUNK
    kw = dict(max_new_tokens=5, temperature=0.0, repetition_penalty=1.0,
              seed=1)
    assert (odd.generate(tiny["prompts"]["long"], **kw)[0]
            == tiny["engine"].generate(tiny["prompts"]["long"], **kw)[0])
