"""The KV pool is donated to every program that rewrites it, and a call
that takes the buffers with it is survived (ISSUE 28).

Two contracts:

  1. donation — the decode step, both chunk programs, the slot insert
     and the page copy consume `pool.caches` (the previous leaves are
     deleted after a call) and the compiled program aliases the whole
     pool in place, so an edit that silently breaks the aliasing fails
     here instead of costing a pool copy a call unseen;
  2. recovery — after a failed call the pool says whether its buffers
     are alive. Alive: the call's own request fails, the same pool
     serves on. Deleted: the decoder rebuilds the pool, the scheduler
     fails every admitted request once and keeps serving, and nothing
     of the old arena (prefix pages, pins, leases) survives to be
     spliced or exported.
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
from luminaai_tpu.inference.generate import (
    GREEDY_SAMPLE_KEY,
    GenerationEngine,
)
from luminaai_tpu.models.transformer import LuminaTransformer
from luminaai_tpu.monitoring.attribution import (
    compiled_cost_metrics,
    donation_audit,
    tree_bytes,
)
from luminaai_tpu.monitoring.telemetry import MetricsRegistry
from luminaai_tpu.serving.server import ChatServer, ContinuousScheduler
from luminaai_tpu.testing.faults import fail_pool_call

GREEDY = {"temperature": 0.0, "repetition_penalty": 1.0}


@pytest.fixture(scope="module")
def setup():
    tok = ConversationTokenizer()
    cfg = Config(
        vocab_size=tok.vocab_size, hidden_size=64, num_layers=2,
        num_heads=1, num_kv_heads=1, seq_length=256,
        use_flash_attention=False, precision="fp32",
        gradient_checkpointing=False, max_new_tokens=16,
        prefill_chunk_size=32,
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
    return tok, cfg, model, params


def _engine(setup, cached=False):
    """A dense-backend engine, or (cached) a ragged_xla one whose
    decoders can carry a prefix cache."""
    tok, cfg, model, params = setup
    if cached:
        cfg = dataclasses.replace(cfg, attention_backend="ragged_xla")
    return GenerationEngine(model, params, tok, cfg)


def _decoder(engine, cached=False):
    return engine.make_stepwise(
        num_slots=2, page_size=32, max_slot_tokens=192,
        prefix_cache_pages=6 if cached else 0,
    )


def _reference(engine, prompt, budget):
    return engine.generate(
        prompt, max_new_tokens=budget, temperature=0.0, seed=0,
        repetition_penalty=1.0,
    )[0]


def _drive(dec, prompt, budget):
    """Admit one prompt the way the scheduler would (chunked when the
    decoder chunks it), decode to budget, release. (tokens, info)."""
    s = dec.acquire_slot()
    st = dec.start_prefill(s, prompt, max_new_tokens=budget, seed=0)
    if st is None:
        info = dec.prefill_into_slot(s, prompt, max_new_tokens=budget,
                                     seed=0)
    else:
        info = None
        while info is None:
            info = dec.advance_prefill(st)
    out = [] if info["token"] is None else [info["token"]]
    while dec._active[s] and len(out) < budget:
        toks, produced, eos = dec.decode_step()
        if eos[s]:
            break
        if produced[s]:
            out.append(int(toks[s]))
    dec.release_slot(s)
    dec.flush_harvests()
    return out, info


def _long(tok, tail=""):
    """A prompt of three chunks (> 2 * prefill_chunk_size tokens) whose
    first two pages are shared by every `tail`."""
    return tok.encode_text("system: be brief and kind. " * 4)[:70] + (
        tok.encode_text(tail)
    )


# ---------------------------------------------------------------------------
# 1. donation
# ---------------------------------------------------------------------------
def _step_case(dec, tok):
    dec.prefill_into_slot(dec.acquire_slot(), tok.encode_text("hello"),
                          max_new_tokens=8, seed=0)
    fn, args = dec.step_fn_and_args(GREEDY_SAMPLE_KEY)
    return fn, args, dec.decode_step


def _insert_case(dec, tok):
    prompt = tok.encode_text("hello world")
    slot = dec.acquire_slot()
    _, fresh = dec._get_prefill(64)(
        dec.params, jnp.zeros((1, 64), jnp.int32),
        jnp.asarray(len(prompt), jnp.int32),
    )
    args = (dec.pool.caches, fresh, jnp.asarray(slot, jnp.int32))
    return dec._get_insert(), args, lambda: dec.prefill_into_slot(
        slot, prompt, max_new_tokens=4, seed=0
    )


def _chunk_case(dec, tok):
    slot = dec.acquire_slot()
    st = dec.start_prefill(slot, _long(tok), max_new_tokens=4, seed=0)
    assert st is not None and st["n_chunks"] == 3
    # A chunk rides the tick program (local page ids, or with a prefix
    # cache global ones): no program of its own rewrites the pool.
    fn, args = dec.step_fn_and_args(GREEDY_SAMPLE_KEY)
    return fn, args, lambda: dec.advance_prefill(st)


def _copy_case(dec, tok):
    slot = dec.acquire_slot()
    st = dec.start_prefill(slot, _long(tok), max_new_tokens=4, seed=0)
    info = None
    while info is None:
        info = dec.advance_prefill(st)
    assert info["prefix"]["pages_harvested"] == 2  # queued, not copied
    pairs = jnp.zeros((2,), jnp.int32)
    return dec._get_copy_pages(2), (dec.pool.caches, pairs, pairs), (
        dec.flush_harvests
    )


@pytest.mark.parametrize(
    "program,cached,case",
    [
        ("decode_step", False, _step_case),
        ("prefill_chunk", False, _chunk_case),
        ("prefill_chunk_cached", True, _chunk_case),
        ("slot_insert", False, _insert_case),
        ("copy_pages", True, _copy_case),
    ],
)
def test_pool_is_donated_and_aliased(setup, program, cached, case):
    """Each program that rewrites the pool (a) aliases >= 90% of it in
    place in its compiled form, with no second pool among its temps,
    and (b) consumes the previous tree when the decoder calls it."""
    tok = setup[0]
    dec = _decoder(_engine(setup, cached), cached)
    fn, args, call = case(dec, tok)
    pool_bytes = tree_bytes(dec.pool.caches)
    cost = compiled_cost_metrics(
        fn, *args, program=program, registry=MetricsRegistry()
    )
    assert cost["available"], cost
    audit = donation_audit(
        cost["memory"], pool_bytes, program=program,
        registry=MetricsRegistry(),
    )
    assert audit["coverage"] >= 0.9 and not audit["flagged"], audit
    assert cost["memory"]["temp_bytes"] < pool_bytes, cost["memory"]

    before = jax.tree.leaves(dec.pool.caches)
    call()
    assert all(leaf.is_deleted() for leaf in before)
    assert dec.pool.buffers_alive()
    assert tree_bytes(dec.pool.caches) == pool_bytes


# ---------------------------------------------------------------------------
# 2. recovery, at the decoder
# ---------------------------------------------------------------------------
def test_recover_pool_leaves_a_live_pool_alone(setup):
    dec = _decoder(_engine(setup))
    tree = dec.pool.caches
    assert dec.recover_pool() is False
    assert dec.pool.caches is tree and dec.pool.rebuilds == 0


@pytest.mark.parametrize(
    "method",
    ["decode_step", "advance_prefill", "prefill_into_slot",
     "flush_harvests"],
)
def test_decoder_serves_reference_tokens_after_a_lost_pool(setup, method):
    """Whichever pool-rewriting call takes the buffers with it, one
    recover_pool() later the decoder serves generate()'s tokens from a
    zeroed pool, and nothing of the old arena is left to splice."""
    tok = setup[0]
    engine = _engine(setup, cached=True)
    dec = _decoder(engine, cached=True)
    prompts = {
        "decode_step": _long(tok, "one"),
        "advance_prefill": _long(tok, "one"),
        "flush_harvests": _long(tok, "one"),
        "prefill_into_slot": tok.encode_text("short and cold"),
    }
    # Something cached and pinned before the fault: it must not survive.
    _drive(dec, _long(tok, "warm"), 3)
    assert dec.prefix_cache.pages_cached() == 2
    with fail_pool_call(dec, method, at=1, lose_pool=True) as stats:
        with pytest.raises(jax.errors.JaxRuntimeError):
            _drive(dec, prompts[method], 6)
    assert stats["raised"] == 1
    assert not dec.pool.buffers_alive()
    assert dec.recover_pool() is True
    assert dec.pool.buffers_alive() and dec.pool.rebuilds == 1
    cache = dec.prefix_cache.stats()
    assert cache["pages_cached"] == 0 and cache["page_refs"] == 0
    assert cache["pending_pages"] == 0 and cache["pages_free"] == 6
    assert not dec._leases and not dec._pending_claims
    assert not dec._harvest_queue and not dec._queued_dst
    assert not dec._active.any()
    # The owner gives the slots back (the scheduler's _fail_all).
    for slot in dec.pool.allocated_slots():
        dec.release_slot(slot)
    prompt = _long(tok, "after")
    out, info = _drive(dec, prompt, 6)
    assert info["prefix"]["hit_pages"] == 0  # a miss: the arena was lost
    assert out == _reference(engine, prompt, 6)


def test_failed_page_copy_that_takes_the_pool_is_raised(setup):
    """flush_harvests swallows a failed copy (the harvest is only an
    optimisation) UNLESS the failed call took the donated pool: then the
    lanes' KV is gone too and the caller has to know."""
    tok = setup[0]
    dec = _decoder(_engine(setup, cached=True), cached=True)

    def losing(K):
        def fail(caches, src, dst):
            for leaf in jax.tree.leaves(caches):
                leaf.delete()
            raise RuntimeError("injected copy failure")
        return fail

    dec._get_copy_pages = losing
    s = dec.acquire_slot()
    st = dec.start_prefill(s, _long(tok), max_new_tokens=4, seed=0)
    info = None
    while info is None:
        info = dec.advance_prefill(st)
    assert info["prefix"]["pages_harvested"] == 2
    with pytest.raises(RuntimeError, match="injected copy failure"):
        dec.flush_harvests()
    # The unwind ran before the raise: no index entry for unwritten pages.
    assert dec.prefix_cache.pages_cached() == 0 and not dec._queued_dst
    assert dec.recover_pool() is True


# ---------------------------------------------------------------------------
# 3. recovery, through a real ContinuousScheduler
# ---------------------------------------------------------------------------
def _submit_all(sched, jobs):
    """Submit every (prompt, budget) at once from its own thread; the
    outcome of each is its (tokens, stats) or the exception it raised."""
    out = [None] * len(jobs)

    def hit(i):
        prompt, budget = jobs[i]
        try:
            out[i] = sched.submit(
                prompt, dict(GREEDY, max_new_tokens=budget)
            )
        except Exception as e:  # the scheduler failed the request
            out[i] = e

    threads = [
        threading.Thread(target=hit, args=(i,)) for i in range(len(jobs))
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
        assert not t.is_alive(), "a client was left hanging"
    return out


@pytest.mark.parametrize(
    "method,at,lose_pool,survivors,rebuilds",
    [
        # A chunk rides the step (ISSUE 34): the lanes and the prompt
        # rows are one program, so a tick that fails fails the decoding
        # lane and the request mid-prefill alike. (a) buffers alive:
        # the pool serves on; here the first tick (chunk 1 beside the
        # lane, nothing in flight) fails where it is dispatched,
        ("dispatch_step", 1, False, set(), 0),
        # and here where it is read;
        ("decode_step", 1, False, set(), 0),
        # (b), (c) buffers deleted: every active and prefilling request
        # fails once, one rebuild.
        ("decode_step", 1, True, set(), 1),
        ("dispatch_step", 1, True, set(), 1),
        # The scheduler runs one step ahead (ISSUE 32): "decode_step"
        # above fails where step 1 is READ, with step 2 already on the
        # device; these fail the dispatch of step 2 with step 1 unread.
        ("dispatch_step", 2, False, set(), 0),
        ("dispatch_step", 2, True, set(), 1),
    ],
)
def test_scheduler_survives_a_failed_pool_call(
    setup, method, at, lose_pool, survivors, rebuilds
):
    tok = setup[0]
    engine = _engine(setup)
    reg = MetricsRegistry()
    # The admission window holds the first tick until both requests are
    # in: one is decoding and one is mid-prefill when the fault lands.
    sched = ContinuousScheduler(
        engine, decoder=_decoder(engine), registry=reg,
        admission_window_ms=1500.0,
    )
    jobs = {
        "short": (tok.encode_text("hello world"), 12),  # whole-prompt path
        "long": (_long(tok, "chunked"), 4),  # three chunks
    }
    with fail_pool_call(sched.decoder, method, at=at,
                        lose_pool=lose_pool) as stats:
        got = dict(zip(jobs, _submit_all(sched, list(jobs.values()))))
    assert stats["raised"] == 1
    for name, (prompt, budget) in jobs.items():
        if name in survivors:
            assert got[name][0] == _reference(engine, prompt, budget)
        else:
            assert isinstance(got[name], jax.errors.JaxRuntimeError), (
                name, got[name],
            )
    pool = sched.decoder.pool
    assert pool.rebuilds == rebuilds and pool.buffers_alive()
    assert reg.snapshot()["serve_pool_rebuilds_total"] == rebuilds
    assert sched.stats()["kv_pool"]["rebuilds"] == rebuilds
    assert sched.stats()["kv_pool"]["in_use"] == 0
    # The server keeps serving, with the reference tokens, on both
    # admission paths.
    after = _submit_all(sched, list(jobs.values()))
    for (prompt, budget), res in zip(jobs.values(), after):
        assert not isinstance(res, Exception), res
        assert res[0] == _reference(engine, prompt, budget)


def test_prefix_cache_is_a_miss_then_a_hit_after_a_rebuild(setup):
    """(d) The arena lived in the lost buffers: after the rebuild the
    same prefix must MISS (no stale splice of zeroed pages), harvest
    again, then hit, and no pin or lease may be left behind."""
    tok = setup[0]
    engine = _engine(setup, cached=True)
    reg = MetricsRegistry()
    dec = _decoder(engine, cached=True)
    sched = ContinuousScheduler(engine, decoder=dec, registry=reg)

    def ask(tail, budget=5):
        prompt = _long(tok, tail)
        res = _submit_all(sched, [(prompt, budget)])[0]
        return res, _reference(engine, prompt, budget)

    def counts():
        snap = reg.snapshot()
        return (snap.get("serve_prefix_cache_hits_total", 0),
                snap.get("serve_prefix_cache_misses_total", 0))

    res, ref = ask("one")
    assert res[0] == ref and counts() == (0, 1)
    res, ref = ask("two")
    assert res[0] == ref and counts() == (1, 1)
    with fail_pool_call(dec, "decode_step", at=2, lose_pool=True):
        res, _ = ask("three", budget=8)
    assert isinstance(res, jax.errors.JaxRuntimeError)
    assert dec.pool.rebuilds == 1
    cache = dec.prefix_cache.stats()
    assert cache["pages_cached"] == 0 and cache["page_refs"] == 0
    assert not dec._leases and not dec._pending_claims
    res, ref = ask("four")
    assert res[0] == ref and counts() == (2, 2)  # "three" hit, "four" missed
    res, ref = ask("five")
    assert res[0] == ref and counts() == (3, 2)
    deadline = time.time() + 5
    while dec.prefix_cache.page_refs() and time.time() < deadline:
        time.sleep(0.01)  # the worker releases the slot after the reply
    assert dec.prefix_cache.page_refs() == 0 and not dec._leases
    assert dec.prefix_cache.pages_cached() == 2


@pytest.mark.parametrize("at", [1, 3])
def test_scheduler_survives_a_harvest_copy_that_takes_the_pool(setup, at):
    """The prefix cache's bulk page copy donates the pool too. Whether
    it takes the buffers from an admission's defensive flush (the 1st
    call, inside acquire_slot) or from the tick's flush of the request's
    own harvest (the 3rd), that request fails, no slot leaks, and the
    next one is served from the rebuilt pool."""
    tok = setup[0]
    engine = _engine(setup, cached=True)
    dec = _decoder(engine, cached=True)
    sched = ContinuousScheduler(engine, decoder=dec,
                                registry=MetricsRegistry())
    prompt = _long(tok, "one")
    with fail_pool_call(dec, "flush_harvests", at=at,
                        lose_pool=True) as stats:
        res = _submit_all(sched, [(prompt, 6)])[0]
    assert stats["raised"] == 1
    assert isinstance(res, jax.errors.JaxRuntimeError)
    assert dec.pool.rebuilds == 1 and not dec.pool.allocated_slots()
    assert dec.prefix_cache.pages_cached() == 0
    res = _submit_all(sched, [(prompt, 6)])[0]
    assert res[0] == _reference(engine, prompt, 6)


def test_export_page_refuses_a_tree_donated_under_it(setup):
    """(e) The page export runs on an HTTP thread while the scheduler
    thread donates the tree it reads: an export that took the tree just
    before the next call must answer None ("not servable"), never raise,
    and drop its pin."""
    tok = setup[0]
    dec = _decoder(_engine(setup, cached=True), cached=True)
    _drive(dec, _long(tok, "warm"), 3)
    key = dec.prefix_cache.keys_for_pages(
        list(dec.prefix_cache._by_page)
    )[0]
    server = SimpleNamespace(batcher=SimpleNamespace(decoder=dec))
    assert ChatServer.export_page_by_key(server, key) is not None
    stale = dec.pool.caches
    _drive(dec, tok.encode_text("anything"), 2)  # donates `stale`
    live = dec.pool.caches
    assert all(x.is_deleted() for x in jax.tree.leaves(stale))
    dec.pool.caches = stale  # the reference a racing export holds
    try:
        assert ChatServer.export_page_by_key(server, key) is None
    finally:
        dec.pool.caches = live
    assert dec.prefix_cache.page_refs() == 0
    assert ChatServer.export_page_by_key(server, key) is not None
