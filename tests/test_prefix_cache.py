"""Radix prefix cache over the paged KV pool (ISSUE 9 acceptance).

Three layers of contract:

  1. host index semantics — chained content hashes, longest-prefix
     lookup, refcount pinning, chain-ordered LRU eviction, per-tenant
     quotas (inference/prefix_cache.py alone, no jax);
  2. decoder splice correctness — cached-prefix admissions are BIT-EXACT
     vs cold prefill (greedy AND seeded sampling) across the ragged_xla
     and ragged backends, with the dense backend as the cold oracle, and
     the sharing is real aliasing (the lane's table points into the
     arena; prefix bytes are never copied into its slot);
  3. lifecycle invariants — no page freed while referenced, no lane
     admitted pointing at an evicted page, tombstoned page tables across
     the free → cache-evict → realloc ordering.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from luminaai_tpu.config import Config
from luminaai_tpu.data.tokenizer import ConversationTokenizer
from luminaai_tpu.inference.generate import GenerationEngine
from luminaai_tpu.inference.prefix_cache import (
    RadixPrefixCache,
    page_chain_keys,
)
from luminaai_tpu.models.transformer import LuminaTransformer


# ---------------------------------------------------------------------------
# 1. host index semantics
# ---------------------------------------------------------------------------
def test_page_chain_keys_encode_the_whole_prefix():
    a = page_chain_keys([1, 2, 3, 4, 5, 6, 7, 8], page_size=4)
    b = page_chain_keys([1, 2, 3, 4, 9, 9, 9, 9], page_size=4)
    c = page_chain_keys([9, 2, 3, 4, 5, 6, 7, 8], page_size=4)
    assert len(a) == 2
    assert a[0] == b[0]  # same first page -> same key
    assert a[1] != b[1]  # diverging second page
    # A differing FIRST page changes EVERY later key (hash chaining):
    # page 2's key encodes everything before it.
    assert a[0] != c[0] and a[1] != c[1]
    # Partial tail pages are never keyed.
    assert len(page_chain_keys([1, 2, 3, 4, 5], page_size=4)) == 1


def test_lookup_and_acquire_longest_prefix():
    cache = RadixPrefixCache(list(range(100, 110)), page_size=4)
    prompt = list(range(12))
    assert cache.insert(prompt, from_page=0, tenant="a") == [
        (0, 100), (1, 101), (2, 102),
    ]
    # Full match, then a diverging tail: only the shared pages splice.
    ids, rows = cache.acquire(prompt + [77, 78, 79, 80])
    assert ids == [100, 101, 102] and rows == 12
    ids2, rows2 = cache.acquire(prompt[:8] + [50, 51, 52, 53])
    assert ids2 == [100, 101] and rows2 == 8
    assert cache.acquire([9, 9, 9, 9]) == ([], 0)
    assert cache.hits == 2 and cache.misses == 1
    # max_pages caps the splice (the decoder always recomputes >= 1 row).
    ids3, rows3 = cache.acquire(prompt, max_pages=2)
    assert ids3 == [100, 101] and rows3 == 8


def test_referenced_pages_survive_eviction_pressure():
    """Invariant: no page freed while referenced — an arena under
    pressure refuses inserts rather than evicting pinned pages."""
    cache = RadixPrefixCache([100, 101], page_size=4)
    cache.insert(list(range(8)), from_page=0, tenant="a")
    ids, _ = cache.acquire(list(range(8)))  # pin both pages
    assert cache.page_refs() == 2
    # A different prompt cannot steal the pinned pages.
    assert cache.insert([9] * 8, from_page=0, tenant="b") == []
    assert cache.evictions == 0 and cache.pages_cached() == 2
    assert cache.acquire(list(range(8)))[0] == ids  # still resident
    cache.release(ids)
    cache.release(ids)  # drop both pins
    # Unreferenced now: LRU eviction makes room (tail-first, so the
    # chain never keeps a suffix without its prefix).
    assert cache.insert([9] * 8, from_page=0, tenant="b") != []
    assert cache.evictions > 0


def test_eviction_eats_chains_from_the_tail():
    cache = RadixPrefixCache([100, 101, 102], page_size=4)
    cache.insert(list(range(12)), from_page=0, tenant="a")
    # Only the tail page (no children) is evictable; evicting the head
    # would orphan the suffix.
    cache._evict_one()
    assert cache.pages_cached() == 2
    ids, rows = cache.acquire(list(range(12)))
    assert rows == 8  # intact prefix still serves


def test_tenant_quota_evicts_own_pages_only():
    cache = RadixPrefixCache(list(range(100, 120)), page_size=4,
                             tenant_quota=2)
    assert len(cache.insert(list(range(12)), from_page=0, tenant="a")) == 2
    assert cache.tenant_pages("a") == 2  # third page refused at quota
    # Tenant b's inserts are untouched by a's quota pressure.
    assert len(cache.insert([7] * 8, from_page=0, tenant="b")) == 2
    # A NEW prompt from a at quota evicts a's own LRU tail, never b's.
    before_b = cache.tenant_pages("b")
    cache.insert([5] * 4, from_page=0, tenant="a")
    assert cache.tenant_pages("a") <= 2
    assert cache.tenant_pages("b") == before_b
    chain_b = page_chain_keys([7] * 8, 4)
    assert all(k in cache._index for k in chain_b)


# ---------------------------------------------------------------------------
# 2. decoder splice parity (the bit-exactness acceptance criterion)
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def setup():
    tok = ConversationTokenizer()
    # head_dim = 64 so the 'ragged' backend runs the REAL Pallas kernel
    # (interpret mode) rather than the fallback.
    cfg = Config(
        vocab_size=tok.vocab_size, hidden_size=64, num_layers=1,
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


def _drive(dec, prompt, budget, seed=0, sample_key=None, tenant="anon"):
    """Admit one prompt (chunked when available), decode to budget,
    release; returns (tokens, info)."""
    s = dec.acquire_slot()
    st = None
    if getattr(dec, "prefill_chunk", 0):
        st = dec.start_prefill(
            s, prompt, max_new_tokens=budget, sample_key=sample_key,
            seed=seed, tenant=tenant,
        )
    if st is None:
        info = dec.prefill_into_slot(
            s, prompt, max_new_tokens=budget, sample_key=sample_key,
            seed=seed,
        )
    else:
        info = None
        while info is None:
            info = dec.advance_prefill(st)
    out = [] if info["token"] is None else [info["token"]]
    while dec._active[s] and len(out) < budget:
        toks, produced, eos = dec.decode_step(sample_key)
        if eos[s]:
            break
        if produced[s]:
            out.append(int(toks[s]))
    dec.release_slot(s)
    return out, info


@pytest.mark.parametrize("backend", ["ragged_xla", "ragged"])
def test_cached_prefix_decode_bit_exact_vs_cold(setup, backend):
    """Acceptance: cached-prefix decode output is bit-exact vs
    cold-prefill output — greedy AND seeded sampling — on the same
    backend (the cache must never change what a request decodes), with
    the DENSE backend as an extra greedy oracle."""
    tok, cfg, model, params = setup
    prefix = tok.encode_text(
        "the quick brown fox jumps over the lazy dog " * 3
    )[:96]
    suffixes = ["alpha beta", "gamma delta epsilon", "zeta"]
    prompts = [prefix + tok.encode_text(s) for s in suffixes]
    greedy = (0.0, 0, 1.0, 1.0)
    sampled = (0.9, 0, 1.0, 1.0)

    bcfg = dataclasses.replace(cfg, attention_backend=backend)
    cold = GenerationEngine(model, params, tok, bcfg).make_stepwise(
        num_slots=2, page_size=32, max_slot_tokens=192
    )
    cached = GenerationEngine(model, params, tok, bcfg).make_stepwise(
        num_slots=2, page_size=32, max_slot_tokens=192,
        prefix_cache_pages=6,
    )
    assert cached.prefix_cache is not None
    dense_cfg = dataclasses.replace(cfg, attention_backend="dense")
    dense = GenerationEngine(model, params, tok, dense_cfg).make_stepwise(
        num_slots=2, page_size=32, max_slot_tokens=192
    )
    for key in (greedy, sampled):
        for i, p in enumerate(prompts):
            want, _ = _drive(cold, p, 8, seed=11 + i, sample_key=key)
            got, info = _drive(cached, p, 8, seed=11 + i, sample_key=key)
            assert got == want, (backend, key, i)
            if key == greedy:
                oracle, _ = _drive(dense, p, 8, seed=11 + i,
                                   sample_key=key)
                assert got == oracle, (backend, i)
    # Every prompt after the first spliced the full 3-page prefix.
    st = cached.prefix_cache.stats()
    assert st["hits"] >= 4 and st["tokens_saved"] >= 4 * 96


def test_splice_is_real_aliasing_not_a_copy(setup):
    """The lane's page table points at ARENA pages for the matched
    prefix and the prefix bytes are never written into its own slot —
    the no-byte-moving sharing claim, checked at the buffers."""
    tok, cfg, model, params = setup
    engine = GenerationEngine(model, params, tok, cfg)
    dec = engine.make_stepwise(
        num_slots=2, page_size=32, max_slot_tokens=192,
        prefix_cache_pages=6,
    )
    prefix = tok.encode_text("shared system prompt " * 8)[:64]
    p1 = prefix + tok.encode_text("one")
    p2 = prefix + tok.encode_text("two two two")
    _drive(dec, p1, 4)  # cold: harvests 2 pages into the arena
    # Poison the pool's lane storage so any accidental copy-back of
    # prefix bytes into the hit lane's own pages is detectable.
    leaves_before = [np.array(x) for x in jax.tree.leaves(dec.pool.caches)]

    s = dec.acquire_slot()
    st = dec.start_prefill(s, p2, max_new_tokens=4, seed=0)
    assert st is not None and st["p0"] == 2  # 2 pages spliced
    arena_base = dec.num_slots * dec.pool.pages
    assert all(int(g) >= arena_base for g in dec._gtable[s, :2])
    assert dec._leases[s] == list(dec._gtable[s, :2])
    assert dec.prefix_cache.page_refs() == 2  # pinned while admitted
    info = None
    while info is None:
        info = dec.advance_prefill(st)
    # Own prefix pages untouched: rows [0, 64) of the lane's slot are
    # byte-identical to before the admission (the blend discarded the
    # shared pages instead of writing them back).
    for before, after in zip(
        leaves_before, jax.tree.leaves(dec.pool.caches)
    ):
        own = np.asarray(after)
        sel_before = before[..., s, :2, :, :, :]
        sel_after = own[..., s, :2, :, :, :]
        np.testing.assert_array_equal(sel_before, sel_after)
    dec.release_slot(s)
    assert dec.prefix_cache.page_refs() == 0  # refcounted release
    # Tombstone: the freed lane's rows are identity again.
    assert all(
        int(g) == s * dec.pool.pages + j
        for j, g in enumerate(dec._gtable[s])
    )


def test_dense_backend_gates_the_cache_off(setup):
    tok, cfg, model, params = setup
    dense_cfg = dataclasses.replace(cfg, attention_backend="dense")
    dec = GenerationEngine(model, params, tok, dense_cfg).make_stepwise(
        num_slots=2, page_size=32, prefix_cache_pages=8
    )
    assert dec.prefix_cache is None
    assert dec.total_slots == dec.num_slots  # no arena allocated


def test_cache_without_chunked_prefill_gates_off(setup):
    tok, cfg, model, params = setup
    dec = GenerationEngine(model, params, tok, cfg).make_stepwise(
        num_slots=2, page_size=32, prefix_cache_pages=8,
        prefill_chunk_tokens=0,
    )
    assert dec.prefix_cache is None


# ---------------------------------------------------------------------------
# 3. lifecycle invariants
# ---------------------------------------------------------------------------
def test_pool_free_tombstones_page_table_row():
    """Satellite: free() resets the page-table row at FREE time, not
    the next alloc — a stale row aliasing a since-evicted cached page
    between free and realloc is the silent-corruption class."""
    from luminaai_tpu.inference.kv_pool import PagedKVPool

    pool = PagedKVPool(None, num_slots=2, pages=4, page_size=16)
    a = pool.alloc()
    pool.page_tables[a] = [7, 7, 7, 7]  # simulate a retargeted splice
    pool.free(a)
    ident = np.arange(4, dtype=np.int32)
    np.testing.assert_array_equal(pool.page_tables[a], ident)


def test_no_alias_across_free_evict_realloc(setup):
    """Contract across the free → cache-evict → realloc ordering: after
    its pages are evicted, a freed-then-reallocated slot must come back
    identity-mapped (never admitted pointing at an evicted page), and
    the decoder's device table must agree."""
    tok, cfg, model, params = setup
    engine = GenerationEngine(model, params, tok, cfg)
    dec = engine.make_stepwise(
        num_slots=1, page_size=32, max_slot_tokens=128,
        prefix_cache_pages=2,  # tiny arena: the 2nd prompt evicts the 1st
    )
    prefix_a = tok.encode_text("tenant a system prompt " * 6)[:64]
    prefix_b = tok.encode_text("tenant b entirely different " * 6)[:64]
    _drive(dec, prefix_a + tok.encode_text("x"), 3, tenant="a")
    keys_a, ids_a = dec.prefix_cache.lookup(prefix_a)
    assert len(keys_a) == 2 and len(ids_a) == 2
    # Slot freed (release inside _drive); now evict a's pages by
    # inserting b's prefix into the full arena.
    _drive(dec, prefix_b + tok.encode_text("y"), 3, tenant="b")
    assert dec.prefix_cache.evictions >= 2
    assert dec.prefix_cache.lookup(prefix_a)[1] == []
    # Realloc: identity table, and an admission of a's prompt is a MISS
    # (never spliced onto the evicted/reused pages).
    out, info = _drive(dec, prefix_a + tok.encode_text("z"), 3, tenant="a")
    assert info["prefix"]["hit_pages"] == 0
    np.testing.assert_array_equal(
        np.asarray(dec._table),
        np.arange(dec.pool.pages, dtype=np.int32)[None, :],
    )


def test_events_request_filter():
    """`lumina events --request <id>` shows one request's lifecycle."""
    from luminaai_tpu.monitoring.events import filter_events

    evs = [
        {"type": "request_admitted", "request_id": "aaa"},
        {"type": "prefix_hit", "request_id": "aaa", "pages": 3},
        {"type": "request_admitted", "request_id": "bbb"},
        {"type": "request_completed", "request_id": "aaa"},
    ]
    got = filter_events(evs, request="aaa")
    assert [e["type"] for e in got] == [
        "request_admitted", "prefix_hit", "request_completed",
    ]
    assert filter_events(evs, request="aaa", type="prefix_hit") == [evs[1]]
    assert filter_events(evs, request="zzz") == []


def test_forget_unwinds_failed_harvest_registration():
    cache = RadixPrefixCache(list(range(100, 110)), page_size=4)
    assignments = cache.insert(list(range(12)), from_page=0, tenant="a")
    ids = [pid for _, pid in assignments]
    assert cache.forget(ids) == 3
    assert cache.pages_cached() == 0 and cache.tenant_pages("a") == 0
    assert len(cache._free) == 10  # pages back in the arena
    # Forgetting is not eviction: no event-worthy lifecycle happened.
    assert cache.evictions == 0


def test_harvest_device_copy_failure_leaves_no_poisoned_hits(setup):
    """Review fix: if the arena page copy fails, the index must not
    keep pointing at never-written pages — the next admission of the
    same prefix must be a genuine MISS, not a garbage splice."""
    tok, cfg, model, params = setup
    engine = GenerationEngine(model, params, tok, cfg)
    dec = engine.make_stepwise(
        num_slots=2, page_size=32, max_slot_tokens=192,
        prefix_cache_pages=6,
    )

    def boom(K):
        def fail(*a, **kw):
            raise RuntimeError("injected copy failure")
        return fail

    real = dec._get_copy_pages
    dec._get_copy_pages = boom
    prefix = tok.encode_text("system prompt " * 10)[:64]
    out, info = _drive(dec, prefix + tok.encode_text("one"), 3)
    # Harvests are QUEUED at admission end (deferred bulk copy) and the
    # injected failure surfaces at flush: the unwind must leave the
    # index clean so a later lookup can never splice unwritten pages.
    assert info["prefix"]["pages_harvested"] == 2  # queued
    assert dec.flush_harvests() == 0  # injected failure -> unwound
    assert dec.prefix_cache.pages_cached() == 0
    dec._get_copy_pages = real
    out2, info2 = _drive(dec, prefix + tok.encode_text("two"), 3)
    assert info2["prefix"]["hit_pages"] == 0  # miss, never a stale hit
    assert info2["prefix"]["pages_harvested"] == 2  # healthy again
    out3, info3 = _drive(dec, prefix + tok.encode_text("three"), 3)
    assert info3["prefix"]["hit_pages"] == 2


def test_harvest_batching_one_bulk_copy_per_tick(setup):
    """ROADMAP item 2 REMAINING (harvest batching): every harvest that
    lands between flushes coalesces into ONE jitted bulk page copy —
    the call count is the contract. Three distinct cold admissions
    finish in the same 'tick' (no intervening acquire), one
    flush_harvests() runs one copy call, and the flushed pages serve
    later admissions as genuine bit-exact hits."""
    tok, cfg, model, params = setup
    engine = GenerationEngine(model, params, tok, cfg)
    dec = engine.make_stepwise(
        num_slots=4, page_size=32, max_slot_tokens=192,
        prefix_cache_pages=8,
    )
    prompts = [
        tok.encode_text(f"distinct system prompt number {i} " * 6)[:70]
        for i in range(3)
    ]
    # Admit all three FIRST (the defensive flush at admission sees an
    # empty queue), then advance interleaved — the scheduler-tick shape.
    slots, sts = [], []
    for p in prompts:
        s = dec.acquire_slot()
        st = dec.start_prefill(s, p, max_new_tokens=4, seed=0)
        assert st is not None
        slots.append(s)
        sts.append(st)
    infos = [None] * 3
    while any(i is None for i in infos):
        for j, st in enumerate(sts):
            if infos[j] is None:
                infos[j] = dec.advance_prefill(st)
    # All three harvests queued, ZERO device copies dispatched yet.
    assert [i["prefix"]["pages_harvested"] for i in infos] == [2, 2, 2]
    assert dec.harvest_copy_calls == 0
    assert dec.flush_harvests() == 6
    assert dec.harvest_copy_calls == 1  # the pinned call count
    assert dec.flush_harvests() == 0  # idempotent on an empty queue
    assert dec.harvest_copy_calls == 1
    greedy_cold = []
    for j, s in enumerate(slots):
        out = [infos[j]["token"]]
        while dec._active[s] and len(out) < 4:
            toks, produced, eos = dec.decode_step()
            if eos[s]:
                break
            if produced[s]:
                out.append(int(toks[s]))
        greedy_cold.append(out)
        dec.release_slot(s)
    # The flushed pages are REAL: re-admissions hit and decode the
    # exact cold streams.
    for j, p in enumerate(prompts):
        out, info = _drive(dec, p, 4)
        assert info["prefix"]["hit_pages"] == 2, info
        assert out == greedy_cold[j], j


@pytest.mark.parametrize("key", [(0.0, 0, 1.0, 1.0), (0.9, 0, 1.0, 1.0)])
def test_cached_prefix_decode_bit_exact_int8_kv(setup, key):
    """ROADMAP item 2 REMAINING: prefix cache × int8 KV parity. Under
    kv_cache_dtype='int8' the pool stores quantized codes + per-page
    scales; a harvested arena page copies BOTH leaves bit-identically,
    so cached-vs-cold decode must stay exactly equal on ragged_xla —
    greedy AND seeded sampling — like the bf16 pins above."""
    tok, cfg, model, params = setup
    icfg = dataclasses.replace(
        cfg, attention_backend="ragged_xla", kv_cache_dtype="int8"
    )
    prefix = tok.encode_text(
        "the quick brown fox jumps over the lazy dog " * 3
    )[:96]
    prompts = [
        prefix + tok.encode_text(s) for s in ("alpha beta", "gamma", "z")
    ]
    cold = GenerationEngine(model, params, tok, icfg).make_stepwise(
        num_slots=2, page_size=32, max_slot_tokens=192
    )
    cached = GenerationEngine(model, params, tok, icfg).make_stepwise(
        num_slots=2, page_size=32, max_slot_tokens=192,
        prefix_cache_pages=6,
    )
    assert cached.prefix_cache is not None
    for i, p in enumerate(prompts):
        want, _ = _drive(cold, p, 8, seed=11 + i, sample_key=key)
        got, _ = _drive(cached, p, 8, seed=11 + i, sample_key=key)
        assert got == want, ("int8", key, i)
    st = cached.prefix_cache.stats()
    assert st["hits"] >= 2 and st["tokens_saved"] >= 2 * 96


# ---------------------------------------------------------------------------
# in-flight dedup (ROADMAP item 2 REMAINING)
# ---------------------------------------------------------------------------
def test_pending_claim_semantics():
    """Host-index unit contract: the first admission claims the
    non-resident chain; followers see has_pending_prefix and park;
    release unblocks."""
    cache = RadixPrefixCache(list(range(100, 110)), page_size=4)
    chain = page_chain_keys(list(range(12)), 4)
    assert not cache.has_pending_prefix(chain)
    own = cache.claim_pending(chain, owner=0)
    assert own == chain
    assert cache.has_pending_prefix(chain)
    # A second claimant gets nothing (the leader's harvest covers it).
    assert cache.claim_pending(chain, owner=1) == []
    # Divergent chains are unaffected.
    other = page_chain_keys([9] * 8, 4)
    assert not cache.has_pending_prefix(other)
    # Harvest lands: pages resident, pending released -> follower hits.
    cache.insert(list(range(12)), from_page=0, tenant="a")
    cache.release_pending(own)
    assert not cache.has_pending_prefix(chain)
    assert cache.pending_pages() == 0
    ids, rows = cache.acquire(list(range(12)), keys=chain)
    assert rows == 12


def test_inflight_dedup_second_admission_waits_then_hits(setup):
    """Decoder contract: two same-prefix admissions in flight — the
    second parks behind the leader's pending-insert entry (no cold
    prefill), resolves to a genuine HIT after the leader's harvest,
    and decodes bit-exactly. stats: one miss (the leader), one hit
    (the follower) — NOT two misses."""
    tok, cfg, model, params = setup
    engine = GenerationEngine(model, params, tok, cfg)
    dec = engine.make_stepwise(
        num_slots=2, page_size=32, max_slot_tokens=192,
        prefix_cache_pages=6,
    )
    prefix = tok.encode_text("shared few-shot template " * 8)[:96]
    p1 = prefix + tok.encode_text("one")
    p2 = prefix + tok.encode_text("two")

    # Cold reference for the follower's prompt.
    ref = GenerationEngine(model, params, tok, cfg).make_stepwise(
        num_slots=2, page_size=32, max_slot_tokens=192
    )
    want, _ = _drive(ref, p2, 6, seed=1)

    s1, s2 = dec.acquire_slot(), dec.acquire_slot()
    st1 = dec.start_prefill(s1, p1, max_new_tokens=6, seed=0)
    st2 = dec.start_prefill(s2, p2, max_new_tokens=6, seed=1)
    assert st1 is not None and st2 is not None
    assert st2.get("waiting") is True
    assert dec.prefix_cache.dedup_waits == 1
    # Interleave like the scheduler: one chunk (or wait re-check) per
    # lane per tick. The follower burns ticks, never chunk FLOPs,
    # until the leader's final chunk harvests.
    info1 = info2 = None
    for _ in range(64):
        if info1 is None:
            info1 = dec.advance_prefill(st1)
        if info2 is None:
            info2 = dec.advance_prefill(st2)
        if info1 is not None and info2 is not None:
            break
    assert info1 is not None and info2 is not None
    assert info1["prefix"]["hit_pages"] == 0
    assert info1["prefix"]["pages_harvested"] == 3
    # The follower resolved to a real hit on the leader's pages.
    assert info2["prefix"]["hit_pages"] == 3
    assert info2["prefix"]["dedup_wait_ticks"] >= 1
    st = dec.prefix_cache.stats()
    assert st["misses"] == 1 and st["hits"] == 1
    assert st["pending_pages"] == 0  # claims released with the harvest
    # And the follower's decode is bit-exact vs cold.
    out2 = [] if info2["token"] is None else [info2["token"]]
    while dec._active[s2] and len(out2) < 6:
        toks, produced, eos = dec.decode_step(None)
        if eos[s2]:
            break
        if produced[s2]:
            out2.append(int(toks[s2]))
    dec.release_slot(s1)
    dec.release_slot(s2)
    assert out2 == want


def test_inflight_dedup_leader_death_unparks_follower(setup):
    """A leader evicted mid-prefill must release its pending claims so
    the parked follower proceeds COLD instead of waiting out its
    budget — no admission can be wedged by a dead leader."""
    tok, cfg, model, params = setup
    engine = GenerationEngine(model, params, tok, cfg)
    dec = engine.make_stepwise(
        num_slots=2, page_size=32, max_slot_tokens=192,
        prefix_cache_pages=6,
    )
    prefix = tok.encode_text("doomed leader prompt " * 8)[:96]
    s1, s2 = dec.acquire_slot(), dec.acquire_slot()
    st1 = dec.start_prefill(
        s1, prefix + tok.encode_text("a"), max_new_tokens=4, seed=0
    )
    st2 = dec.start_prefill(
        s2, prefix + tok.encode_text("b"), max_new_tokens=4, seed=0
    )
    assert st2.get("waiting") is True
    dec.release_slot(s1)  # leader dies before any harvest
    assert dec.prefix_cache.pending_pages() == 0
    info2 = None
    for _ in range(16):
        info2 = dec.advance_prefill(st2)
        if info2 is not None:
            break
    assert info2 is not None
    assert info2["prefix"]["hit_pages"] == 0  # cold, not a stale hit
    assert info2["prefix"]["pages_harvested"] == 3  # and IT harvests
    dec.release_slot(s2)


def test_inflight_dedup_two_admissions_one_scheduler_tick(setup):
    """Scheduler contract (the ISSUE's acceptance shape): two
    same-prefix requests admitted into free slots in one scheduler
    tick share ONE pending-insert entry — one miss, one dedup wait
    resolving to a hit — and both streams complete correctly."""
    import threading

    from luminaai_tpu.monitoring.telemetry import MetricsRegistry
    from luminaai_tpu.serving.server import ContinuousScheduler

    tok, cfg, model, params = setup
    engine = GenerationEngine(model, params, tok, cfg)
    dec = engine.make_stepwise(
        num_slots=2, page_size=32, max_slot_tokens=192,
        prefix_cache_pages=6,
    )
    sched = ContinuousScheduler(
        engine, decoder=dec, registry=MetricsRegistry()
    )
    prefix_text = "system: you are a helpful assistant. " * 4
    results = {}
    lock = threading.Lock()

    def hit(name, tail):
        out = sched.submit(
            tok.encode_text(prefix_text + tail),
            {"max_new_tokens": 4, "temperature": 0.0,
             "repetition_penalty": 1.0},
        )
        with lock:
            results[name] = out

    threads = [
        threading.Thread(target=hit, args=(f"r{i}", f"tail {i}"))
        for i in range(2)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert len(results) == 2
    st = dec.prefix_cache.stats()
    # One of the two cold-started (miss + harvest); the other either
    # parked behind the pending entry (dedup_waits) or — if the races
    # landed it after the harvest — hit outright. Never two misses.
    assert st["misses"] == 1, st
    assert st["hits"] == 1, st
    assert st["pending_pages"] == 0, st
    for out in results.values():
        toks = out[0] if isinstance(out, tuple) else out
        assert isinstance(toks, list) and len(toks) >= 1


def test_short_cold_prompts_do_not_skew_miss_counts(setup):
    """Review fix: a short prompt that falls back to the monolithic
    prefill path must not book a cache miss — cache.stats() and the
    scheduler's hit/miss counters describe the same admissions."""
    tok, cfg, model, params = setup
    engine = GenerationEngine(model, params, tok, cfg)
    dec = engine.make_stepwise(
        num_slots=2, page_size=32, max_slot_tokens=192,
        prefix_cache_pages=6,
    )
    short = tok.encode_text("hi")  # <= one chunk, nothing cached
    _drive(dec, short, 3)
    st = dec.prefix_cache.stats()
    assert st["hits"] == 0 and st["misses"] == 0
