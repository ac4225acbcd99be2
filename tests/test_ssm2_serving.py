"""Scalar-decay state-space heads served (ISSUE 63): a stack whose layers
are ONE sub-layer each (a Mamba-2 mixer, an attention without positions,
a latent expert layer), a lane keeping a [state, channels] float32 state
for each mixer layer, pages of k/v for the attention layer and NOTHING for
an expert layer; the tick steps the lanes through `ops/ssm.py::
ssm_scan_heads` and carries the prefill chunk in block form
(`block_scan`) entering from the slot's stored state.

At a tiny size on the CPU, float32, the pattern M E M * E M E, over both
block sizes (the chunk one block, the chunk two blocks as in the cell):

  - the CACHED path's logits (not tokens), read out of the tick program
    itself by a spy on `apply`, at every row: chunked prefill of a prompt
    that is no multiple of the chunk (so chunks start mid-prompt), then
    one-token steps, with other lanes live, lanes at -1, a chunk with no
    live row, and a lane recycled, against the plain reference's full
    forward pass (benchmark/architectures/nemotron_h);
  - the tolerance is 5e-6 of the logits' spread: float32 both sides, the
    block form against the recurrence reads ~1e-6 here, and the CONTROL, a
    reference that keeps its state in bfloat16, reads 2e-5 and must fail;
  - tokens through ContinuousScheduler against generate(), with the new
    counters; rows that are not there change nothing, bit for bit;
    recover_pool rebuilds the states and keeps the layers with no entry.
"""

import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import manifest, model_config
from benchmark.architectures.nemotron_h.test_reference import NEMOTRON_TINY
from benchmark.serve_cell import StubTokenizer
from luminaai_tpu.inference.generate import GenerationEngine
from luminaai_tpu.inference.kv_pool import StateNotPagedError, lane_states
from luminaai_tpu.models.transformer import LuminaTransformer
from luminaai_tpu.monitoring.telemetry import MetricsRegistry
from luminaai_tpu.parallel.sharding import unbox
from luminaai_tpu.serving.server import ContinuousScheduler

ARCH = manifest.Architecture("nemotron_h")
CHUNK = 16
GREEDY = (0.0, 0, 1.0, 1.0)
# float32 against float32; see the module docstring for the two readings.
TOL = 5e-6
PATTERN = "MEM*EME"

# The architecture's own tiny body (benchmark/architectures/nemotron_h/
# test_reference.py: 7 of 12 layers, 8 of 32 experts held from offset 8,
# init_std 0.12 so that every branch weighs in the logits), served.
BODY = dict(NEMOTRON_TINY, program=dict(
    NEMOTRON_TINY["program"], seq_length=128, prefill_chunk_size=CHUNK,
    attention_backend="ragged_xla", max_new_tokens=16,
    gradient_checkpointing=False))
assert BODY["hybrid_override_pattern"][:BODY["num_hidden_layers"]] == PATTERN


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


@pytest.fixture(scope="module", params=[16, 8], ids=["one_block", "two_blocks"])
def tiny(request):
    body = dict(BODY, chunk_size=request.param)
    cfg = model_config.build_config(ARCH, body)
    assert cfg.ssm2_chunk == request.param
    model = LuminaTransformer(cfg)
    params = unbox(jax.jit(model.init)(
        jax.random.key(0), jnp.ones((1, 8), jnp.int32))["params"])
    tok = StubTokenizer(cfg.vocab_size)
    engine = GenerationEngine(model, params, tok, cfg)
    rs = np.random.RandomState(7)
    prompts = {name: rs.randint(3, cfg.vocab_size, size=n).tolist()
               for name, n in (("long", 37), ("short", 11), ("mid", 23))}
    kw = ARCH.reference.from_config_file(body)
    assert kw["pattern"] == PATTERN
    view = ARCH.adapter.params_view(cfg, params)
    ref = jax.jit(lambda ids: ARCH.reference.forward(view, ids, **kw))
    bf16_state = jax.jit(lambda ids: ARCH.reference.forward(
        view, ids, controls={"state_dtype": jnp.bfloat16}, **kw))
    return dict(tok=tok, cfg=cfg, model=model, params=params, engine=engine,
                prompts=prompts, ref=ref, bf16_state=bf16_state)


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


def test_the_pool_holds_a_state_pages_or_nothing_a_layer(tiny):
    dec = _decoder(tiny)
    tree = dec.pool.caches
    assert len(tree) == 7
    assert [e is None for e in tree] == [c == "E" for c in PATTERN]
    assert len(lane_states(tree)) == 3
    st = lane_states(tree)[0]
    assert st.state.shape == (3, 16, 128) and st.state.dtype == jnp.float32
    assert st.tail.shape == (3, 3, 128 + 2 * 4 * 16)
    k, _ = tree[3]
    assert k.shape == (3, 4, 16, 2, 16)  # slots, pages, page, kv heads, d
    assert dec.pool.keeps_state
    held = dec.pool.slot_bytes()
    assert held["state"] == 3 * (16 * 128 + 3 * 256) * 4
    assert held["pages"] == 2 * 64 * 2 * 16 * 4
    assert held["total"] == held["state"] + held["pages"]
    assert dec._state_bytes == 3 * 16 * 128 * 4


def test_cached_logits_match_the_reference(tiny):
    """Every row the tick program computed for three requests: `mid`
    decoding while `long` is prefilled chunk by chunk (16 + 16 + 5 rows,
    the second and third entering from the slot's stored state) in the
    same ticks, then both stepping (the third slot at -1 throughout, the
    chunk's rows all -1), then `short` (the whole-prompt bucket path,
    called directly) in the slot `mid` gave back: each row's logits against the reference's
    full forward pass over that request's whole sequence."""
    spy = _Spy(tiny["model"])
    engine = GenerationEngine(spy, tiny["params"], tiny["tok"], tiny["cfg"])
    dec = engine.make_stepwise(num_slots=3, page_size=16, max_slot_tokens=64)
    S, head = dec.num_slots, np.asarray(
        tiny["params"]["embedder"]["lm_head"])
    seqs, rows = {}, {}  # name -> its tokens; name -> [(position, logits)]

    def tick(chunk_of=None):
        st = chunk_of and chunk_of[1]
        riding = st is not None and dec.prefill_ready(st)
        if riding:
            start = dec._chunk_start(st)
            end = min(start + CHUNK, st["length"])
        stepped_pos = {n: int(dec._pos[s]) for n, s in slots.items()
                       if dec._active[s]}
        assert dec.dispatch_step(GREEDY, chunk=st if riding else None)
        toks, produced, _ = dec.collect_step()
        jax.effects_barrier()
        logits = spy.seen[-1][:, 0] @ head.T
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
    # The scheduler takes every prompt of this stack in chunks
    # (start_prefill never declines); the whole-prompt path stays for
    # direct callers, and is held to the reference here all the same.
    slot = dec.acquire_slot()
    assert slot == 0  # the slot `mid` left
    first = [dec.prefill_into_slot(
        slot, tiny["prompts"]["short"], max_new_tokens=5,
        sample_key=GREEDY, seed=1)["token"]]
    jax.effects_barrier()
    # The bucket program's rows (one call, [1, bucket] tokens; it asks
    # the model for logits, not the hidden state).
    bucket = spy.seen[n_seen][0]
    rows["short"] += [(j, bucket[j]) for j in range(11)]
    seqs["short"].append(first[0])
    slots["short"] = slot
    for _ in range(3):
        tick()

    worst = worst_control = 0.0
    for name in ("mid", "long", "short"):
        ids = np.asarray(seqs[name], np.int32)[None]
        want = np.asarray(tiny["ref"](ids))[0]
        control = np.asarray(tiny["bf16_state"](ids))[0]
        spread = float(np.std(want))
        assert len(rows[name]) >= len(tiny["prompts"][name]) + 3
        for p, got in rows[name]:
            err = float(np.sqrt(np.mean(np.square(got - want[p]))))
            assert err <= TOL * spread, (name, p, err / spread)
            worst = max(worst, err / spread)
            worst_control = max(worst_control, float(np.sqrt(np.mean(
                np.square(got - control[p])))) / spread)
            if len(tiny["prompts"][name]) - 1 <= p < ids.shape[1] - 1:
                # Greedy: from the prompt's last row on, a row chose
                # the token after it.
                assert int(got.argmax()) == int(ids[0, p + 1])
    # The control: a state kept in bfloat16 reads as another model.
    assert worst_control > TOL, (worst, worst_control)
    stepped = sum(len(seqs[n]) - len(tiny["prompts"][n]) - 1 for n in seqs)
    assert dec.ssm_rows == dec.chunk_rows + stepped
    probe = dec.acquire_slot()
    assert dec.start_prefill(probe, tiny["prompts"]["short"], 2,
                             sample_key=GREEDY, seed=1) is not None
    # Two reads and writes of 3 layers x [16, 128] float32 a stepped lane
    # and a live chunk (mid 2 chunks, long 3).
    assert dec.ssm_state_bytes == 2 * (stepped + 5) * 3 * 16 * 128 * 4


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
    """`ssm_scan_heads` copies in and out only the stepped lanes' slabs:
    an idle lane's state may hold anything (NaN here) and neither reaches
    a live row nor changes by a bit; more lanes than the ring of buffers
    holds come back right."""
    from luminaai_tpu.ops import ssm

    slots, H, P, G, N = 9, 8, 8, 2, 4
    ks = jax.random.split(jax.random.key(5), 6)
    state = jax.random.normal(ks[0], (slots, N, H * P), jnp.float32)
    idle = np.array([1, 4])
    state = state.at[idle].set(jnp.nan)
    x = jax.random.normal(ks[1], (slots, H, P), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[2], (slots, H), jnp.float32))
    b, c = (jax.random.normal(k, (slots, G, N), jnp.float32)
            for k in ks[3:5])
    a = -jnp.linspace(0.5, 4.0, H)
    pos = np.full((slots,), 9, np.int32)
    pos[idle] = -1
    wide = lambda t: jnp.repeat(t, P, axis=-1)  # noqa: E731
    y, out = jax.jit(ssm.ssm_scan_heads)(
        state, wide(jnp.exp(dt * a)), wide(dt) * x.reshape(slots, -1), b, c,
        jnp.asarray(pos))
    y, out, was = np.asarray(y), np.asarray(out), np.asarray(state)
    assert np.isfinite(y).all() and not y[idle].any()
    for lane in range(slots):
        same = out[lane].tobytes() == was[lane].tobytes()
        assert same == (pos[lane] < 0), lane
    assert np.isfinite(out[pos >= 0]).all()
    # against one row of the block form entering from the same state
    want_y, want_s = ssm.block_scan(
        x[:, None], dt[:, None], a, b[:, None], c[:, None],
        h0=jnp.where(jnp.isnan(state), 0.0, state))
    live = pos >= 0
    np.testing.assert_allclose(
        y[live], np.asarray(want_y)[live, 0].reshape(-1, H * P), atol=2e-5)
    np.testing.assert_allclose(out[live], np.asarray(want_s)[live],
                               atol=2e-5)


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
def test_a_recycled_lanes_next_request_starts_from_zero(tiny, name):
    """The slot a request left holds that request's state; the next one
    in it (chunked, or the whole-prompt path) reads none of it."""
    want = tiny["engine"].generate(
        tiny["prompts"][name], max_new_tokens=6, temperature=0.0,
        repetition_penalty=1.0, seed=1)[0]
    dec = _decoder(tiny, slots=1)
    _serve(dec, tiny["prompts"]["mid"], 4)
    assert any(s.any() for s, _ in _states(dec))  # the slot is dirty
    assert _serve(dec, tiny["prompts"][name], 6) == want


def test_recover_pool_rebuilds_the_states_and_the_layers_with_none(tiny):
    dec = _decoder(tiny, slots=2)
    want = _serve(dec, tiny["prompts"]["long"], 5)
    slot, st, _ = _admit(dec, tiny["prompts"]["mid"], 4)
    dec.advance_prefill(st)
    for leaf in jax.tree.leaves(dec.pool.caches):
        leaf.delete()
    assert dec.recover_pool() is True and dec.pool.rebuilds == 1
    assert len(lane_states(dec.pool.caches)) == 3
    assert [e is None for e in dec.pool.caches] == [
        c == "E" for c in PATTERN]
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
    sched.close()
    snap = {f.name: sum(c.value for c in f.children())
            for f in registry.families() if f.type in ("counter", "gauge")}
    assert snap["ssm_rows_total"] >= 37 + 23 + 3 * 6
    assert snap["ssm_state_bytes_total"] >= 2 * 3 * 6 * 3 * 16 * 128 * 4
    # 8 of 32 experts held, 3 expert layers a tick, 6 picks a live row
    assert snap["moe_held_experts_total"] % (8 * 3) == 0
    assert 0 < snap["moe_held_experts_hit_total"] <= snap[
        "moe_held_experts_total"]
    assert snap["moe_held_pairs_dropped_total"] == 0
    assert 0 < snap["moe_held_pairs_total"] < snap["moe_routed_pairs_total"]


def test_what_cannot_share_a_state_refuses_by_name(tiny):
    with pytest.raises(StateNotPagedError, match="prefix cache"):
        _decoder(tiny, prefix_cache_pages=4)
    dec = _decoder(tiny, slots=1)
    with pytest.raises(StateNotPagedError, match="page export"):
        dec.pool.export_page(0)
