"""One program a tick (ISSUE 34): a prefill chunk's rows ride the decode
step, in the forward pass that steps the lanes.

What sharing a forward pass can get wrong is what these pin down, against
generate() (no tolerance: the same tokens), at the decoder and through
the scheduler:

  - the chunk's rows and the lanes' rows must not see each other: a
    prompt prefilled chunk by chunk WHILE other lanes decode gives
    generate()'s tokens, and so do the lanes that decoded beside it, on
    every backend, KV dtype, with a window, with a prefix cache's global
    page table, through a mixture of experts, greedy and sampled;
  - the first token, sampled inside the program that carries the last
    chunk, is the one the whole-prompt path samples on the host;
  - rows that are not there write nothing: an empty chunk, a lane that
    is not stepped, the slot being prefilled;
  - a first token that is a stop id ends the request before anything is
    emitted, at the price of one dropped lane-step;
  - the counters say what rode.
"""

import dataclasses
import threading
from types import SimpleNamespace

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from luminaai_tpu.config import Config
from luminaai_tpu.data.tokenizer import ConversationTokenizer
from luminaai_tpu.inference.generate import GenerationEngine
from luminaai_tpu.models.transformer import LuminaTransformer
from luminaai_tpu.monitoring.telemetry import MetricsRegistry
from luminaai_tpu.serving.server import ContinuousScheduler

SAMPLING = {
    "greedy": {"temperature": 0.0, "repetition_penalty": 1.0},
    "sampled": {"temperature": 0.8, "top_k": 20, "repetition_penalty": 1.3},
}
CHUNK = 16
# backend, KV dtype, attention window, sampling, what else
COMBOS = [
    ("ragged_xla", kv, window, how, "plain")
    for kv in ("bf16", "int8")
    for window in (None, 24)
    for how in SAMPLING
] + [
    ("dense", "bf16", None, "greedy", "plain"),
    ("dense", "int8", 24, "sampled", "plain"),
    ("ragged", "bf16", None, "greedy", "plain"),
    # A prefix cache: the page table holds global ids and the chunk
    # attends through its slot's row of it.
    ("ragged_xla", "bf16", None, "greedy", "cache"),
    ("ragged_xla", "int8", 24, "sampled", "cache"),
    # A mixture of experts, dropless (capacity_factor = experts / top-k)
    # and not: see test_chunk_rows_are_routed_alone.
    ("ragged_xla", "bf16", None, "greedy", "moe"),
]


class _StopAt:
    """The tokenizer, with `stop` the ONE token that stops a lane (none
    if None: the ids then lie outside the vocabulary)."""

    def __init__(self, tok, stop=None):
        self._tok = tok
        out = tok.vocab_size + 1
        self.eos_token_id = out if stop is None else int(stop)
        self.pad_token_id = self.im_end = out

    def __getattr__(self, name):
        return getattr(self._tok, name)


def _init(cfg):
    from flax import linen as nn

    model = LuminaTransformer(cfg)
    params = jax.jit(model.init)(
        jax.random.key(0), jnp.ones((1, 8), jnp.int32)
    )["params"]
    params = jax.tree.map(
        lambda x: x.unbox() if isinstance(x, nn.meta.AxisMetadata) else x,
        params, is_leaf=lambda x: isinstance(x, nn.meta.AxisMetadata),
    )
    # At its initial scale the model repeats its last prompt token
    # whatever the cache holds; eight times the matrices and a stream
    # follows the context, so a wrong or missing KV row shows.
    params = jax.tree_util.tree_map_with_path(
        lambda path, x: x * 8.0 if x.ndim >= 2 and (
            "embed" not in jax.tree_util.keystr(path)
        ) else x,
        params,
    )
    return model, params


@pytest.fixture(scope="module")
def tiny():
    tok = ConversationTokenizer()
    cfg = Config(
        vocab_size=tok.vocab_size, hidden_size=64, num_layers=2,
        num_heads=2, num_kv_heads=1, seq_length=128,
        use_flash_attention=False, precision="fp32",
        gradient_checkpointing=False, max_new_tokens=16,
        prefill_chunk_size=CHUNK,
    )
    moe = dataclasses.replace(
        cfg, use_moe=True, num_experts=4, moe_top_k=2,
        capacity_factor=2.0, intermediate_size=32, moe_dispatch="sort",
    )
    text = tok.encode_text(
        "the quick brown fox jumps over the lazy dog again and again"
    )
    prompts = {"long": text[:40], "short": text[44:55], "mid": text[3:23]}
    assert len(prompts["long"]) == 40 and len(prompts["short"]) == 11
    return SimpleNamespace(
        tok=tok, cfg=cfg, dense=_init(cfg), moe_cfg=moe, moe=_init(moe),
        prompts=prompts, seeds={"long": 7, "short": 8, "mid": 9},
    )


def _engine(tiny, backend="ragged_xla", kv="bf16", window=None,
            kind="plain", stop=None, **more):
    cfg, (_, params) = (
        (tiny.moe_cfg, tiny.moe) if kind == "moe" else (tiny.cfg, tiny.dense)
    )
    cfg = dataclasses.replace(
        cfg, attention_backend=backend, kv_cache_dtype=kv,
        attention_window=window, **more,
    )
    # The window and the experts' capacity are the MODEL's: no parameter
    # depends on them, so every variant shares the one set of weights.
    return GenerationEngine(
        LuminaTransformer(cfg), params, _StopAt(tiny.tok, stop), cfg
    )


def _decoder(engine, kind="plain", slots=3):
    return engine.make_stepwise(
        num_slots=slots, page_size=16, max_slot_tokens=64,
        prefix_cache_pages=4 if kind == "cache" else 0,
    )


def _key(engine, kw):
    return tuple(engine._resolve_gen_key(
        None, kw.get("temperature"), kw.get("top_p"), kw.get("top_k"),
        kw.get("repetition_penalty"),
    )[1:])


def _ref(engine, tiny, name, budget, kw):
    return engine.generate(
        tiny.prompts[name], max_new_tokens=budget, seed=tiny.seeds[name],
        **kw,
    )[0]


def _serve_at_the_decoder(dec, tiny, kw, plan, budgets):
    """Drive the decoder as the scheduler does, one tick at a time and
    serially: `plan` maps a tick number to the request admitted before
    it. A chunked admission's next chunk rides each tick's step.
    name -> tokens."""
    key = _key(dec.engine, kw)
    out, slot_of, prefilling = {}, {}, {}
    for tick in range(80):
        name = plan.get(tick)
        if name is not None:
            slot = slot_of[name] = dec.acquire_slot()
            args = dict(max_new_tokens=budgets[name], sample_key=key,
                        seed=tiny.seeds[name])
            st = dec.start_prefill(slot, tiny.prompts[name], **args)
            if st is None:
                info = dec.prefill_into_slot(slot, tiny.prompts[name], **args)
                out[name] = [info["token"]]
            else:
                prefilling[name] = st
        done = [n for n in out if len(out[n]) >= budgets[n]]
        if tick > max(plan) and len(done) == len(plan):
            break
        riding = next(iter(prefilling.items()), None)
        assert dec.dispatch_step(
            key, chunk=riding[1] if riding and dec.prefill_ready(riding[1])
            else None,
        )
        toks, produced, eos = dec.collect_step()
        assert not eos.any()
        for n, slot in slot_of.items():
            if n in out and produced[slot] and len(out[n]) < budgets[n]:
                out[n].append(int(toks[slot]))
                if len(out[n]) >= budgets[n]:
                    dec.release_slot(slot)
        if riding and "info" in riding[1]:
            info = riding[1].pop("info")
            assert info["prompt_tokens"] == len(tiny.prompts[riding[0]])
            out[riding[0]] = [info["token"]]
            del prefilling[riding[0]]
    assert not prefilling and dec.steps_in_flight == 0
    return out


@pytest.mark.parametrize(
    "backend,kv,window,how,kind", COMBOS,
    ids=["-".join(str(x) for x in c) for c in COMBOS],
)
def test_a_prompt_prefilled_beside_decoding_lanes_serves_generates_tokens(
    tiny, backend, kv, window, how, kind
):
    """`short` decodes from tick 0; `long` (three chunks) arrives at
    tick 2 and `mid` (two) at tick 4, so every chunk rides a step that
    steps one or two lanes, and the lanes run on through five ticks
    that carry somebody else's rows."""
    kw = SAMPLING[how]
    engine = _engine(tiny, backend, kv, window, kind)
    dec = _decoder(engine, kind)
    budgets = {"short": 20, "long": 8, "mid": 6}
    got = _serve_at_the_decoder(
        dec, tiny, kw, {0: "short", 2: "long", 4: "mid"}, budgets
    )
    for name, budget in budgets.items():
        assert got[name] == _ref(engine, tiny, name, budget, kw), name
    assert dec.chunks_carried == 5 and dec.lane_steps_dropped == 0
    assert dec.chunk_rows == len(tiny.prompts["long"]) + len(
        tiny.prompts["mid"]
    )
    if kind == "cache":
        # The same prompt again: its first two pages are spliced, the
        # suffix alone is prefilled (one chunk), the tokens are the same.
        dec.flush_harvests()
        again = _serve_at_the_decoder(
            dec, tiny, kw, {0: "short", 2: "long"}, budgets
        )
        assert again["long"] == got["long"]
        assert again["short"] == got["short"]
        assert dec.prefix_cache.stats()["hits"] == 1
        assert dec.chunk_rows == 60 + (40 - 32)


@pytest.mark.parametrize("how", list(SAMPLING))
@pytest.mark.parametrize("name", ["long", "mid"])
def test_the_first_token_sampled_in_the_program_is_the_whole_prompt_paths(
    tiny, how, name
):
    """The tick program derives the first token's key from the request's
    seed as _finish_prefill does on the host: a decoder that does not
    chunk (prefill_into_slot, _finish_prefill) and one that does give
    the same first token and the same stream after it."""
    kw = SAMPLING[how]
    engine = _engine(tiny)
    key = _key(engine, kw)
    whole = engine.make_stepwise(num_slots=2, page_size=16,
                                 max_slot_tokens=64, prefill_chunk_tokens=0)
    chunked = _decoder(engine, slots=2)
    streams = []
    for dec in (whole, chunked):
        slot = dec.acquire_slot()
        args = dict(max_new_tokens=5, sample_key=key, seed=tiny.seeds[name])
        st = dec.start_prefill(slot, tiny.prompts[name], **args)
        if dec is whole:
            assert st is None
            info = dec.prefill_into_slot(slot, tiny.prompts[name], **args)
        else:
            info = None
            while info is None:
                info = dec.advance_prefill(st)
        toks = [info["token"]]
        while len(toks) < 5:
            out, produced, _ = dec.decode_step(key)
            assert produced[slot]
            toks.append(int(out[slot]))
        streams.append(toks)
    assert streams[0] == streams[1] == _ref(engine, tiny, name, 5, kw)


def _device_state(dec):
    return [np.asarray(x) for x in jax.tree.leaves(
        (dec.pool.caches, dec._counts, dec._rngs)
    )]


@pytest.mark.parametrize("kv", ["bf16", "int8"])
def test_rows_that_are_not_there_write_nothing(tiny, kv):
    """A tick with no lane stepped and no chunk leaves the pool, the
    counts and the rngs bit-identical; with one lane stepped and an
    empty chunk, that lane's one row, counts row and rng are all that
    moved: nothing of a free slot, nothing of a slot being prefilled."""
    engine = _engine(tiny, kv=kv)
    dec = _decoder(engine)
    idle = _device_state(dec)
    dec.decode_step()  # nothing live, nothing pending
    for before, after in zip(idle, _device_state(dec)):
        np.testing.assert_array_equal(before, after)

    lane = dec.acquire_slot()
    dec.prefill_into_slot(lane, tiny.prompts["short"], max_new_tokens=8,
                          seed=0)
    parked = dec.acquire_slot()
    st = dec.start_prefill(parked, tiny.prompts["long"], max_new_tokens=4,
                           seed=0)
    assert dec.advance_prefill(st) is None  # 16 of its rows are written
    row = int(dec._pos[lane])
    before = _device_state(dec)
    _, produced, _ = dec.decode_step()
    assert produced[lane] and not produced[parked]
    after = _device_state(dec)
    *pool_b, counts_b, rngs_b = before
    *pool_a, counts_a, rngs_a = after
    for b, a in zip(pool_b, pool_a):
        # Paged layout [..., slot, page, row, heads, dim]: flatten rows.
        b = b.reshape(b.shape[:-4] + (-1,) + b.shape[-2:])
        a = a.reshape(a.shape[:-4] + (-1,) + a.shape[-2:])
        changed = np.argwhere((a != b).any(axis=(-1, -2)))
        assert [tuple(c) for c in changed] == [(lane, row)]
    moved = np.flatnonzero((counts_a != counts_b).any(axis=1))
    assert moved.tolist() == [lane]
    moved = np.flatnonzero((rngs_a != rngs_b).any(axis=1))
    assert moved.tolist() == [lane]


@pytest.mark.parametrize("beside", [True, False], ids=["lane", "alone"])
def test_a_first_token_that_is_a_stop_id_ends_the_request(tiny, beside):
    """The host learns a prompt's first token where it reads the tick
    that carried its last chunk, one step after it started stepping the
    lane on its prediction: a stop id there emits nothing, ends the
    request as generate() does, and drops that one lane-step."""
    free = _engine(tiny)
    first = _ref(free, tiny, "long", 1, SAMPLING["greedy"])[0]
    engine = _engine(tiny, stop=first)
    assert _ref(engine, tiny, "long", 6, SAMPLING["greedy"]) == []
    registry = MetricsRegistry()
    sched = ContinuousScheduler(
        engine, num_slots=2, page_size=16, max_slot_tokens=64,
        registry=registry, admission_window_ms=1500.0 if beside else 0.0,
    )
    jobs = [("long", 6)] + ([("short", 12)] if beside else [])
    got = _submit_all(sched, tiny, jobs, SAMPLING["greedy"])
    toks, stats = got["long"]
    assert toks == [] and stats["stopped"] == "eos"
    assert stats["prompt_tokens"] == 40
    if beside:
        short = _ref(engine, tiny, "short", 12, SAMPLING["greedy"])
        assert got["short"][0] == short and len(short) == 12
    snap = registry.snapshot()
    assert snap["serve_lane_steps_dropped_total"] == 1
    assert sched.decoder.pool.stats()["in_use"] == 0 or _settles(sched)


def _settles(sched):
    for _ in range(2000):
        if sched.idle() and sched.decoder.pool.stats()["in_use"] == 0:
            return True
        threading.Event().wait(0.005)
    return False


def _submit_all(sched, tiny, jobs, kw):
    out = {}

    def ask(name, budget):
        out[name] = sched.submit(
            tiny.prompts[name],
            dict(kw, max_new_tokens=budget, seed=tiny.seeds[name]),
        )

    threads = [threading.Thread(target=ask, args=j) for j in jobs]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
        assert not t.is_alive(), "a client was left hanging"
    return out


@pytest.mark.parametrize("how", list(SAMPLING))
def test_a_tick_with_a_chunk_and_no_live_lane(tiny, how):
    """A lone long prompt: its chunks ride ticks that step nobody, the
    loop still ends, the tokens are generate()'s, and nothing counts as
    carried beside a lane."""
    kw = SAMPLING[how]
    engine = _engine(tiny)
    registry = MetricsRegistry()
    sched = ContinuousScheduler(
        engine, num_slots=2, page_size=16, max_slot_tokens=64,
        registry=registry,
    )
    got = _submit_all(sched, tiny, [("long", 7)], kw)
    assert got["long"][0] == _ref(engine, tiny, "long", 7, kw)
    assert _settles(sched)
    snap = registry.snapshot()
    assert snap["serving_prefill_chunks_total"] == 3
    assert snap["serve_chunk_rows_total"] == 40
    assert snap["serve_chunks_carried_total"] == 0
    # Three ticks of chunks, then six steps of the lane; all but the
    # first queued behind the one before.
    assert snap["serve_decode_steps_total"] == 9
    assert snap["serve_steps_dispatched_ahead_total"] == 8
    assert snap["serve_lane_steps_dropped_total"] == 0
    # A chunked prompt meets the tick program and no other.
    assert {key[0] for key in sched.decoder._fns} == {"step"}


@pytest.mark.parametrize("how", list(SAMPLING))
def test_the_counters_say_what_rode(tiny, how):
    """Through the scheduler, one step ahead: `short` is decoding when
    `long` and `mid` are admitted, so each of their five chunks rides a
    step that steps a lane, and each request has generate()'s tokens."""
    kw = SAMPLING[how]
    engine = _engine(tiny)
    registry = MetricsRegistry()
    sched = ContinuousScheduler(
        engine, num_slots=3, page_size=16, max_slot_tokens=64,
        registry=registry, admission_window_ms=1500.0,
    )
    jobs = [("short", 24), ("long", 8), ("mid", 6)]
    got = _submit_all(sched, tiny, jobs, kw)
    for name, budget in jobs:
        assert got[name][0] == _ref(engine, tiny, name, budget, kw), name
    assert _settles(sched)
    snap = registry.snapshot()
    assert snap["serving_prefill_chunks_total"] == 5
    assert snap["serve_chunks_carried_total"] == 5
    assert snap["serve_chunk_rows_total"] == 40 + 20
    # 23 steps of `short`, the first of them started by hand.
    assert snap["serve_decode_steps_total"] == 23
    assert snap["serve_steps_dispatched_ahead_total"] == 22
    assert snap["serve_lane_steps_dropped_total"] == 0
    assert sched.decoder.chunks_carried == 5


def test_chunk_rows_are_routed_alone(tiny):
    """docs/serving.md, "MoE rows of a chunk": a row of the tick is its
    own routing group, so no row of a chunk is ever dropped for lack of
    expert capacity, whatever capacity_factor says. Where generate()'s
    prefill drops none either (capacity_factor >= experts / top-k: the
    COMBOS case above) the tokens are the same; where it does drop, the
    served stream is the dropless one, not generate()'s."""
    kw = SAMPLING["greedy"]
    tight = _engine(tiny, kind="moe", capacity_factor=0.5)
    dropless = _engine(tiny, kind="moe")
    served = _serve_at_the_decoder(
        _decoder(tight), tiny, kw, {0: "long"}, {"long": 8}
    )["long"]
    # capacity_factor only sizes the prompt's groups: a decode row is a
    # group of one token with room for it, in generate() too.
    assert served == _ref(dropless, tiny, "long", 8, kw)
    assert served != _ref(tight, tiny, "long", 8, kw)


def test_a_chunk_rides_a_step_of_its_own_sampling_key(tiny):
    engine = _engine(tiny)
    dec = _decoder(engine)
    st = dec.start_prefill(
        dec.acquire_slot(), tiny.prompts["long"], max_new_tokens=4,
        sample_key=_key(engine, SAMPLING["sampled"]), seed=1,
    )
    with pytest.raises(ValueError, match="sampling key"):
        dec.dispatch_step(_key(engine, SAMPLING["greedy"]), chunk=st)
    assert dec.steps_in_flight == 0 and st["next"] == 0
