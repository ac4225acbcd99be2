"""The scheduler dispatches decode step N+1 before it reads step N
(ISSUE 32): the same tokens as generate(), however a lane ends.

What a lookahead can get wrong is exactly what these pin down, on every
serving backend and KV dtype, greedy and sampled:

  - a lane that ends where the host could not foresee it (a stop token,
    a cancel, a deadline) has been stepped once more: that token is
    dropped, never emitted, and the KV row it wrote is never attended
    by the slot's next occupant;
  - a lane that ends where the host CAN foresee it (its budget, its
    slot's last row) is not stepped again at all;
  - the overlap is there by construction (the dispatch of N+1 precedes
    the collect of N), not by timing, and a step that fails with
    another in flight loses neither the server nor a client.
"""

import dataclasses
import threading
import time
from types import SimpleNamespace

import pytest

import jax
import jax.numpy as jnp

from luminaai_tpu.config import Config
from luminaai_tpu.data.tokenizer import ConversationTokenizer
from luminaai_tpu.inference.generate import GenerationEngine
from luminaai_tpu.models.transformer import LuminaTransformer
from luminaai_tpu.monitoring.telemetry import MetricsRegistry
from luminaai_tpu.serving.server import ContinuousScheduler, RequestTimeout
from luminaai_tpu.testing.faults import fail_pool_call, slow_decode

SAMPLING = {
    "greedy": {"temperature": 0.0, "repetition_penalty": 1.0},
    "sampled": {"temperature": 0.8, "top_k": 20, "repetition_penalty": 1.3},
}
COMBOS = [
    (backend, kv, how)
    for backend in ("dense", "ragged_xla")
    for kv in ("bf16", "int8")
    for how in SAMPLING
]


class _StopAt:
    """The tokenizer, with `stop` the ONE token that stops a lane (none
    if None: the ids then lie outside the vocabulary, so a stream ends
    on its budget alone)."""

    def __init__(self, tok, stop=None):
        self._tok = tok
        out = tok.vocab_size + 1
        self.eos_token_id = out if stop is None else int(stop)
        self.pad_token_id = self.im_end = out

    def __getattr__(self, name):
        return getattr(self._tok, name)


@pytest.fixture(scope="module")
def tiny():
    tok = ConversationTokenizer()
    cfg = Config(
        vocab_size=tok.vocab_size, hidden_size=64, num_layers=2,
        num_heads=2, num_kv_heads=1, seq_length=256,
        use_flash_attention=False, precision="fp32",
        gradient_checkpointing=False, max_new_tokens=16,
        prefill_chunk_size=16,
    )
    model = LuminaTransformer(cfg)
    params = jax.jit(model.init)(
        jax.random.key(0), jnp.ones((1, 8), jnp.int32)
    )["params"]
    from flax import linen as nn

    params = jax.tree.map(
        lambda x: x.unbox() if isinstance(x, nn.meta.AxisMetadata) else x,
        params, is_leaf=lambda x: isinstance(x, nn.meta.AxisMetadata),
    )
    # At its initial scale the model repeats its last prompt token
    # whatever the cache holds (tied embeddings win); eight times the
    # matrices and a greedy stream follows the context, so a stale or
    # missing KV row shows in the tokens.
    params = jax.tree_util.tree_map_with_path(
        lambda path, x: x * 8.0 if x.ndim >= 2 and (
            "embed" not in jax.tree_util.keystr(path)
        ) else x,
        params,
    )
    return tok, cfg, model, params


@pytest.fixture(scope="module", params=COMBOS, ids="-".join)
def world(request, tiny):
    """One backend x KV dtype x sampling: an engine one of whose stop
    tokens request A emits mid-stream, the scheduler over it (shared by
    the cases below: they read counters as differences), and
    generate()'s streams as the reference."""
    backend, kv, how = request.param
    tok, cfg, model, params = tiny
    cfg = dataclasses.replace(
        cfg, attention_backend=backend, kv_cache_dtype=kv
    )
    text = tok.encode_text(
        "the quick brown fox jumps over the lazy dog again and again"
    )
    prompts = {"A": text[:22], "B": text[30:41], "C": text[3:21]}
    seeds = {"A": 7, "B": 8, "C": 9}
    kw = SAMPLING[how]

    def generate(engine, name, budget):
        return engine.generate(
            prompts[name], max_new_tokens=budget, seed=seeds[name], **kw
        )[0]

    free = generate(
        GenerationEngine(model, params, _StopAt(tok), cfg), "A", 16
    )
    assert len(free) == 16, free
    for k in range(3, 12):
        stop = free[k]
        if stop in free[:k]:
            continue
        engine = GenerationEngine(model, params, _StopAt(tok, stop), cfg)
        refs = {}

        def ref(name, budget, engine=engine, refs=refs):
            if (name, budget) not in refs:
                refs[name, budget] = generate(engine, name, budget)
            return refs[name, budget]

        if (ref("A", 16) == free[:k] and len(ref("B", 40)) == 40
                and len(ref("C", 40)) == 40):
            break
    else:
        pytest.fail(f"no stop token that only A meets: {free}")
    registry = MetricsRegistry()
    sched = ContinuousScheduler(
        engine, num_slots=2, page_size=16, max_slot_tokens=128,
        registry=registry, admission_window_ms=1500.0,
    )
    return SimpleNamespace(
        engine=engine, sched=sched, dec=sched.decoder, registry=registry,
        prompts=prompts, seeds=seeds, kw=kw, ref=ref, stop_at=k,
    )


def _consume(gen, name, out, take=None):
    """Read one submit_stream into out[name]: its tokens and final
    stats, or the exception that ended it. `take`: close the stream
    (cancel the request) after that many tokens."""
    toks = []
    try:
        for item in gen:
            if isinstance(item, dict):
                out[name] = (toks, item)
                return
            toks.append(item)
            if take is not None and len(toks) >= take:
                gen.close()
                out[name] = (toks, {"stopped": "closed"})
                return
    except Exception as e:  # the scheduler ended the request
        out[name] = (toks, e)


def _serve(w, jobs):
    """Submit every (name, budget, kwargs) in this order, then consume
    each stream on its own thread. name -> (tokens, stats or error)."""
    out = {}
    threads = []
    for name, budget, more in jobs:
        more = dict(more)
        take = more.pop("take", None)
        gen = w.sched.submit_stream(
            w.prompts[name],
            dict(w.kw, max_new_tokens=budget, seed=w.seeds[name], **more),
        )
        threads.append(threading.Thread(
            target=_consume, args=(gen, name, out, take)
        ))
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
        assert not t.is_alive(), "a client was left hanging"
    return out


def _counts(w):
    snap = w.registry.snapshot()
    return SimpleNamespace(
        steps=snap.get("serve_decode_steps_total", 0),
        ahead=snap.get("serve_steps_dispatched_ahead_total", 0),
        dropped=snap.get("serve_lane_steps_dropped_total", 0),
    )


def _settled(w):
    """The worker releases a slot after the client has its reply."""
    deadline = time.time() + 10
    while time.time() < deadline:
        if w.sched.idle() and w.dec.pool.stats()["in_use"] == 0:
            break
        time.sleep(0.005)
    assert w.dec.steps_in_flight == 0
    assert w.dec.pool.stats()["in_use"] == 0
    assert not w.dec._steps_ahead().any()


def test_a_lane_that_meets_a_stop_token_is_stepped_once_more_and_dropped(
    world,
):
    """(a) A meets its stop token while B decodes on. The step after is
    already on the device and steps A too: its token is dropped, and
    both streams are generate()'s."""
    w = world
    before = _counts(w)
    got = _serve(w, [("A", 16, {}), ("B", 40, {})])
    assert got["A"][0] == w.ref("A", 16) and len(got["A"][0]) == w.stop_at
    assert got["A"][1]["stopped"] == "eos"
    assert got["B"][0] == w.ref("B", 40)
    assert got["B"][1]["stopped"] == "length"
    _settled(w)
    after = _counts(w)
    assert after.dropped - before.dropped == 1  # A's one step too many
    assert after.ahead - before.ahead >= 30


def test_a_lane_that_ends_on_its_budget_is_not_stepped_again(world):
    """(b) The host knows a budget's last step before it reads it: no
    lane is stepped past max_new, so nothing is dropped."""
    w = world
    before = _counts(w)
    got = _serve(w, [("B", 6, {}), ("C", 16, {})])
    assert got["B"][0] == w.ref("B", 6) and len(got["B"][0]) == 6
    assert got["C"][0] == w.ref("C", 40)[:16] and len(got["C"][0]) == 16
    assert {got[n][1]["stopped"] for n in got} == {"length"}
    _settled(w)
    after = _counts(w)
    assert after.dropped == before.dropped
    # C's 15 steps and the one or two of B's before C's second chunk
    # let it join, all but the first queued behind the one before.
    steps = after.steps - before.steps
    assert 15 <= steps <= 20
    assert after.ahead - before.ahead == steps - 1


def test_a_lane_that_fills_its_slot_is_never_stepped_past_its_last_row(
    world,
):
    """(c) lane_full is predicted too: the lane's last step writes the
    slot's last row, and no step is dispatched at the row after it."""
    w = world
    dec = w.dec
    rows = len(w.prompts["B"]) + 5
    cap, finish, pack = dec.token_capacity, dec._finish_prefill, (
        dec._pack_lanes
    )
    stepped_at = []

    def finish_then_shrink(*args, **kwargs):
        info = finish(*args, **kwargs)
        dec.token_capacity = rows  # admission sized the request by `cap`
        return info

    def recording():
        lanes, live = pack()
        stepped_at.extend(int(r) for r in lanes[0][live])
        return lanes, live

    before = _counts(w)
    dec._finish_prefill, dec._pack_lanes = finish_then_shrink, recording
    try:
        got = _serve(w, [("B", 16, {})])
    finally:
        dec.token_capacity = cap
        del dec._finish_prefill, dec._pack_lanes
    assert got["B"][0] == w.ref("B", 16)[:6]  # first token + 5 rows
    assert got["B"][1]["stopped"] == "length"
    assert max(stepped_at) == rows - 1
    _settled(w)
    assert _counts(w).dropped == before.dropped


def test_a_slot_taken_over_after_a_stop_token_serves_the_cold_stream(world):
    """(d) C waits for a slot, gets A's the moment A meets its stop
    token, with a SHORTER prompt: the row the dropped step wrote lies
    past C's prompt, and C writes it again before it attends it."""
    w = world
    before = _counts(w)
    got = _serve(w, [("A", 16, {}), ("B", 40, {}), ("C", 16, {})])
    assert got["A"][0] == w.ref("A", 16)
    assert got["B"][0] == w.ref("B", 40)
    assert got["C"][0] == w.ref("C", 40)[:16]
    assert got["C"][1]["slot"] == got["A"][1]["slot"]
    # C decodes through the row A's dropped step wrote.
    dropped_row = len(w.prompts["A"]) + w.stop_at
    assert len(w.prompts["C"]) < dropped_row < len(w.prompts["C"]) + 15
    _settled(w)
    assert _counts(w).dropped - before.dropped == 1


def test_a_cancelled_lane_is_dropped_with_its_step_in_flight(world):
    """(e) A client that goes away: its lane leaves at the next emit
    with a step in flight, the others' tokens and the slot's next
    occupant are untouched."""
    w = world
    before = _counts(w)
    with slow_decode(w.dec, 0.02):  # C is still decoding when it goes
        got = _serve(
            w, [("C", 40, {"take": 3}), ("B", 40, {}), ("A", 16, {})]
        )
    assert got["C"][0] == w.ref("C", 40)[:3]
    assert got["B"][0] == w.ref("B", 40)
    assert got["A"][0] == w.ref("A", 16)
    _settled(w)
    # C's step in flight when it was cancelled, and A's at its stop.
    assert _counts(w).dropped - before.dropped == 2


def test_a_lane_past_its_deadline_is_evicted_with_its_step_in_flight(world):
    """(e) Deadline eviction: the overdue lane fails with a timeout, one
    tick later at most, and takes nothing of the other lane with it."""
    w = world
    _serve(w, [("C", 16, {}), ("B", 6, {})])  # every program built
    _settled(w)
    before = _counts(w)
    # 39 steps of 50 ms against a deadline of 1 s: C is mid-decode.
    with slow_decode(w.dec, 0.05):
        got = _serve(
            w, [("C", 40, {"timeout_s": 1.0}), ("B", 40, {})]
        )
    toks, err = got["C"]
    assert isinstance(err, RequestTimeout), err
    assert 0 < len(toks) < 40 and toks == w.ref("C", 40)[:len(toks)]
    assert got["B"][0] == w.ref("B", 40)
    _settled(w)
    assert _counts(w).dropped - before.dropped == 1
    after = _serve(w, [("C", 16, {})])
    assert after["C"][0] == w.ref("C", 40)[:16]


# -- the overlap, by construction ------------------------------------------
@pytest.fixture()
def plain(tiny):
    """A greedy dense engine and a scheduler of its own."""
    tok, cfg, model, params = tiny
    engine = GenerationEngine(model, params, _StopAt(tok), cfg)
    registry = MetricsRegistry()
    sched = ContinuousScheduler(
        engine, num_slots=2, page_size=16, max_slot_tokens=128,
        registry=registry,
    )
    prompt = tok.encode_text("the quick brown fox jumps over")

    def ask(budget):
        return sched.submit(
            prompt, dict(SAMPLING["greedy"], max_new_tokens=budget, seed=0)
        )[0]

    def ref(budget):
        return engine.generate(
            prompt, max_new_tokens=budget, seed=0, **SAMPLING["greedy"]
        )[0]

    return SimpleNamespace(
        sched=sched, dec=sched.decoder, registry=registry, ask=ask, ref=ref
    )


def _record_halves(dec):
    """Log every dispatch that enqueued a step and every collect."""
    log = []
    dispatch, collect = dec.dispatch_step, dec.collect_step

    def dispatch_step(key=None, chunk=None):
        sent = dispatch(key, chunk)
        if sent:
            log.append("dispatch")
        return sent

    def collect_step():
        log.append("collect")
        return collect()

    dec.dispatch_step, dec.collect_step = dispatch_step, collect_step
    return log


def test_step_n_plus_1_is_dispatched_before_step_n_is_collected(plain):
    w = plain
    log = _record_halves(w.dec)
    toks = w.ask(65)
    assert toks == w.ref(65) and len(toks) == 65
    # The prompt is two chunks: each rides a tick (no lane is live yet),
    # and the lane is stepped from the tick after its last chunk's, on
    # the host's prediction, before its first token has been read.
    steps = log.count("collect")
    assert steps == 2 + 64 and log.count("dispatch") == 2 + 64
    # Before the i-th collect, i + 1 steps have been dispatched: on
    # every tick but the last, whose lane the host knew would end.
    reads = sent = 0
    for event in log:
        if event == "dispatch":
            sent += 1
        else:
            reads += 1
            assert sent == min(reads + 1, steps), (reads, sent)
    snap = w.registry.snapshot()
    assert snap["serve_decode_steps_total"] == 66
    ahead = snap["serve_steps_dispatched_ahead_total"]
    assert ahead == 65 and ahead / snap["serve_decode_steps_total"] > 0.9
    assert snap["serving_prefill_chunks_total"] == 2
    assert snap["serve_chunk_rows_total"] == 30  # the prompt's tokens
    assert snap["serve_chunks_carried_total"] == 0  # no lane beside them
    assert snap["serve_lane_steps_dropped_total"] == 0


@pytest.mark.parametrize(
    "half,at,lose_pool,rebuilds",
    [
        ("collect_step", 3, True, 1),
        ("collect_step", 3, False, 0),
        ("dispatch_step", 4, True, 1),
        ("dispatch_step", 4, False, 0),
    ],
)
def test_a_step_that_fails_with_another_in_flight_is_survived(
    plain, half, at, lose_pool, rebuilds
):
    """Either half of the step raises on the tick where step 4 is
    dispatched and step 3 read: both are accounted for (the client
    fails once, nothing of them is read later, the pool is rebuilt if
    the call had taken it) and the next requests get their tokens."""
    w = plain
    events = []
    emit = w.sched.recorder.emit
    w.sched.recorder = SimpleNamespace(
        emit=lambda type, **f: (events.append(type), emit(type, **f))[1]
    )
    log = _record_halves(w.dec)
    with fail_pool_call(w.dec, half, at=at, lose_pool=lose_pool) as stats:
        with pytest.raises(jax.errors.JaxRuntimeError):
            w.ask(20)
    assert stats["raised"] == 1
    # The failing tick had one step in flight and went for the second.
    assert log[:5] == ["dispatch", "dispatch", "collect", "dispatch",
                       "collect"]
    assert w.dec.steps_in_flight == 0
    assert w.dec.pool.rebuilds == rebuilds and w.dec.pool.buffers_alive()
    assert events.count("pool_rebuilt") == rebuilds
    assert w.sched.stats()["kv_pool"]["in_use"] == 0
    for budget in (20, 9):
        assert w.ask(budget) == w.ref(budget)


def test_the_decoder_runs_two_steps_deep_and_sends_one_array_a_step(tiny):
    """At the decoder: two steps dispatched before the first is read
    give the serial loop's tokens, and what the host sends a step is
    one int32 array: four numbers a slot, then the chunk's five and its
    token ids."""
    tok, cfg, model, params = tiny
    engine = GenerationEngine(model, params, _StopAt(tok), cfg)
    prompt = tok.encode_text("hello world")
    want = engine.generate(
        prompt, max_new_tokens=9, seed=0, **SAMPLING["greedy"]
    )[0]
    dec = engine.make_stepwise(num_slots=2, page_size=16,
                               max_slot_tokens=128)
    slot = dec.acquire_slot()
    info = dec.prefill_into_slot(slot, prompt, max_new_tokens=9, seed=0)
    _, args = dec.step_fn_and_args()
    # (params, pool, previous tokens, TICK, counts, rngs, page table):
    # the rest lives on the device from step to step.
    assert args[3].dtype == jnp.int32
    assert args[3].shape == (4 * 2 + 5 + dec.prefill_chunk,)
    out = [info["token"]]
    assert dec.dispatch_step() and dec.dispatch_step()
    assert dec.steps_in_flight == 2 and dec._steps_ahead()[slot] == 2
    sent = 2
    while dec.steps_in_flight:
        toks, produced, eos = dec.collect_step()
        assert produced[slot] and not eos[slot]
        out.append(int(toks[slot]))
        # Refused once the steps in flight use up the lane's budget.
        if dec.steps_in_flight:
            sent += dec.dispatch_step()
    assert out == want and sent == 8 and dec.lane_steps_dropped == 0
    # With nothing in flight the caller owns the loop (decode_step's
    # contract): the step runs, and a step nobody reads is dropped.
    assert dec.dispatch_step() and dec.steps_in_flight == 1
    dec.abandon_steps()
    assert dec.steps_in_flight == 0 and dec.lane_steps_dropped == 1
    dec.release_slot(slot)
    assert not dec._steps_ahead().any()
