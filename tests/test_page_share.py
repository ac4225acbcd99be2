"""Cross-replica KV page sharing (ISSUE 20 acceptance).

Four layers of contract:

  1. wire format — a page payload round-trips export -> import -> export
     byte-identically and every framing violation raises before the
     device arena is touched (inference/kv_pool.py alone);
  2. keying rule — the router's affinity key and the cache's chain
     ownership share serving/page_share.py's whole-block rule: shared
     cacheable prefixes collide, short unrelated prompts spread;
  3. remote-hit admission parity — a replica that pulls another
     replica's pages decodes BIT-EXACT vs cold prefill (greedy AND
     seeded sampling, bf16 AND int8 KV on ragged_xla), and a repeat
     admission hits locally without a second pull;
  4. degradation — dropped pulls, deadline-slow owners, and unflushed
     owner pages all fall back to local prefill with identical decode
     output and booked failure counters (transfer failure is never
     worse than a cache miss).
"""

import dataclasses
from types import SimpleNamespace

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from luminaai_tpu.config import Config
from luminaai_tpu.data.tokenizer import ConversationTokenizer
from luminaai_tpu.inference.generate import GenerationEngine
from luminaai_tpu.inference.kv_pool import (
    PAGE_WIRE_MAGIC,
    parse_page_payload,
)
from luminaai_tpu.inference.prefix_cache import page_chain_keys
from luminaai_tpu.models.transformer import LuminaTransformer
from luminaai_tpu.monitoring.telemetry import MetricsRegistry
from luminaai_tpu.serving.page_share import (
    AFFINITY_BLOCK_CHARS,
    PageShareClient,
    affinity_key,
)
from luminaai_tpu.testing.faults import drop_page_pulls, slow_page_pulls

GREEDY = (0.0, 0, 1.0, 1.0)
SAMPLED = (0.9, 0, 1.0, 1.0)


# ---------------------------------------------------------------------------
# 2. the shared keying rule (router affinity <-> cache chain granularity)
# ---------------------------------------------------------------------------
def test_affinity_keys_on_whole_blocks_only():
    """Whole-block truncation mirrors page_chain_keys never keying a
    partial tail page: prompts sharing their leading blocks share a
    key no matter how their sub-block tails diverge."""
    base = "s" * (2 * AFFINITY_BLOCK_CHARS)
    a = affinity_key("/v1/generate", {"prompt": base + "tail one"})
    b = affinity_key("/v1/generate", {"prompt": base + "other"})
    c = affinity_key("/v1/generate", {"prompt": base})
    assert a == b == c
    # A differing leading block is a different chain -> different key.
    d = affinity_key("/v1/generate", {"prompt": "x" + base})
    assert d != a


def test_affinity_sub_block_prompts_still_spread():
    """A prompt too short to fill one block has no cacheable chain
    either; it keys on its raw text purely for load spread."""
    keys = {
        affinity_key("/v1/generate", {"prompt": f"p{i}"})
        for i in range(10)
    }
    assert len(keys) == 10


def test_affinity_chat_keys_on_first_message():
    """Chat requests key on the FIRST message (the system prompt — the
    stable shared prefix), so later turns still land together."""
    sys_msg = {"role": "system", "content": "rules " * 30}
    a = affinity_key("/v1/chat", {"messages": [sys_msg, {"role": "user",
                                                         "content": "hi"}]})
    b = affinity_key("/v1/chat", {"messages": [sys_msg, {"role": "user",
                                                         "content": "bye"}]})
    assert a == b
    # The route is part of the identity: same text, different path.
    assert affinity_key("/v1/generate", {"prompt": "z" * 100}) != \
        affinity_key("/v1/chat", {"prompt": "z" * 100})


# ---------------------------------------------------------------------------
# 1. wire format
# ---------------------------------------------------------------------------
def test_parse_page_payload_rejects_framing_violations():
    with pytest.raises(ValueError, match="magic"):
        parse_page_payload(b"NOPE" + b"\x00" * 16)
    with pytest.raises(ValueError, match="truncated"):
        parse_page_payload(PAGE_WIRE_MAGIC + b"\x00\x00")
    good_header = (b'{"page_size": 4, "leaves": [{"shape": [2, 1, 1], '
                   b'"dtype": "float32"}]}')
    framed = (PAGE_WIRE_MAGIC + len(good_header).to_bytes(4, "big")
              + good_header)
    body = np.zeros((2, 1, 1), np.float32).tobytes()
    with pytest.raises(ValueError, match="truncated"):
        parse_page_payload(framed + body[:-1])
    with pytest.raises(ValueError, match="trailing"):
        parse_page_payload(framed + body + b"x")
    leaves = parse_page_payload(framed + body)
    assert len(leaves) == 1 and leaves[0].shape == (2, 1, 1)


# ---------------------------------------------------------------------------
# fixtures (idiom of tests/test_prefix_cache.py)
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def setup():
    tok = ConversationTokenizer()
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
    s = dec.acquire_slot()
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


class LoopbackClient(PageShareClient):
    """A PageShareClient whose router + owner conversations short-
    circuit into another in-process decoder: lookup walks the owner's
    radix index directly and get_bytes serves its arena pages through
    the SAME pin -> refuse-unflushed -> export sequence the server
    route runs. fetch_page (retry, metrics, deadline accounting) stays
    the real code — exactly the seam testing/faults.py wraps."""

    OWNER_URL = "http://owner:1"

    def __init__(self, owner_dec, **kw):
        kw.setdefault("timeout_s", 10.0)
        super().__init__(
            router_url="http://router:0", self_url="http://me:2", **kw
        )
        self.owner_dec = owner_dec
        self.fetches = 0

    def lookup(self, keys, have=0):
        cache = self.owner_dec.prefix_cache
        owned = []
        for k in keys:
            if k not in cache._index:
                break
            owned.append(k)
        if len(owned) <= have:
            return None, []
        return self.OWNER_URL, owned

    def get_bytes(self, base_url, path, timeout_s=None):
        self.fetches += 1
        key = path.rsplit("/", 1)[1]
        dec = self.owner_dec
        pid = dec.prefix_cache.pin_key(key)
        if pid is None:
            return 404, b""
        try:
            if pid in dec._queued_dst:
                return 404, b""  # harvest copy not flushed yet
            return 200, dec.pool.export_page(pid)
        finally:
            dec.prefix_cache.release([pid])


def _mk(setup_vals, backend="ragged_xla", kv_dtype=None, cache_pages=6):
    tok, cfg, model, params = setup_vals
    over = {"attention_backend": backend}
    if kv_dtype:
        over["kv_cache_dtype"] = kv_dtype
    bcfg = dataclasses.replace(cfg, **over)
    kw = {"num_slots": 2, "page_size": 32, "max_slot_tokens": 192}
    if cache_pages:
        kw["prefix_cache_pages"] = cache_pages
    return GenerationEngine(model, params, tok, bcfg).make_stepwise(**kw)


def _metric(registry, prefix):
    for line in registry.render_prometheus().splitlines():
        if line.startswith(prefix):
            return float(line.rsplit(" ", 1)[1])
    return None


# ---------------------------------------------------------------------------
# 3. remote-hit admission parity (the bit-exactness acceptance criterion)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("kv_dtype", [None, "int8"])
def test_remote_pull_decode_bit_exact_vs_cold(setup, kv_dtype):
    """Acceptance: replica B, cold, pulls replica A's harvested pages
    and decodes BIT-EXACT vs its own cold prefill — greedy AND seeded
    sampling, bf16 AND int8 KV (codes + scales both cross the wire) on
    ragged_xla. A repeat admission hits locally: ONE pull per chain.
    Both sampling keys share one decoder trio (the executables dominate
    the wall clock); each key gets its own chain so each pull is a
    genuinely cold remote admission."""
    tok = setup[0]
    cold = _mk(setup, kv_dtype=kv_dtype, cache_pages=0)
    dec_a = _mk(setup, kv_dtype=kv_dtype)
    dec_b = _mk(setup, kv_dtype=kv_dtype)
    registry = MetricsRegistry()
    dec_b.page_share = LoopbackClient(dec_a, registry=registry)
    pulled_total = 0
    for i, key in enumerate((GREEDY, SAMPLED)):
        prompt = tok.encode_text(
            f"key {i} quick brown fox jumps over the lazy dog " * 3
        )[:96] + tok.encode_text("remote suffix")
        want, _ = _drive(cold, prompt, 8, seed=11, sample_key=key)
        _drive(dec_a, prompt, 8, seed=11, sample_key=key)  # A computes
        dec_a.flush_harvests()  # pages land in A's arena (exportable)
        got, info = _drive(dec_b, prompt, 8, seed=11, sample_key=key)
        assert got == want, (kv_dtype, key)
        prefix = info["prefix"]
        remote = prefix["remote"]
        npages = len(page_chain_keys(prompt, 32, (len(prompt) - 1) // 32))
        pulled_total += npages
        assert remote and remote["pulled"] == npages
        assert not remote["failed"]
        assert remote["tokens"] == npages * 32 and remote["bytes"] > 0
        # The pull produced a GENUINE local hit: full chain spliced, the
        # chunked prefill ran only the uncached suffix.
        assert prefix["hit_pages"] == npages
        assert prefix["tokens_saved"] == npages * 32
        assert dec_b.remote_hits == i + 1
        assert dec_b.remote_pull_failures == 0
        assert _metric(
            registry, "serve_prefix_remote_pulls_total"
        ) == pulled_total
        assert _metric(registry, "serve_page_transfer_bytes_total") > 0
        # Re-admission: local hit, NO second pull.
        fetches = dec_b.page_share.fetches
        got2, info2 = _drive(dec_b, prompt, 8, seed=11, sample_key=key)
        assert got2 == want
        assert info2["prefix"]["hit_pages"] == npages
        assert dec_b.page_share.fetches == fetches
        assert dec_b.remote_hits == i + 1
        # B now advertises the pulled pages too (report-after-land).
        assert set(dec_b.drain_landed_keys()) == set(
            page_chain_keys(prompt, 32, npages)
        )


def test_partial_remote_chain_extends_contiguously(setup):
    """B already holds the first page locally (have > 0): the pull
    fetches only the owner's EXTENSION of B's resident prefix and the
    admission splices both."""
    tok = setup[0]
    shared = tok.encode_text("common preamble words " * 10)[:96]
    p_short = shared[:40]   # harvests page 0 only
    p_full = shared + tok.encode_text("tail")
    cold = _mk(setup, cache_pages=0)
    want, _ = _drive(cold, p_full, 6)

    dec_a = _mk(setup)
    _drive(dec_a, p_full, 6)
    dec_a.flush_harvests()

    dec_b = _mk(setup)
    _drive(dec_b, p_short, 6)       # page 0 resident locally
    dec_b.flush_harvests()
    dec_b.page_share = LoopbackClient(dec_a)
    got, info = _drive(dec_b, p_full, 6)
    assert got == want
    npages = (len(p_full) - 1) // 32
    assert info["prefix"]["hit_pages"] == npages
    assert info["prefix"]["remote"]["pulled"] == npages - 1  # not page 0


# ---------------------------------------------------------------------------
# 4. degradation (transfer failure is never worse than a cache miss)
# ---------------------------------------------------------------------------
def test_dropped_pulls_degrade_to_local_prefill(setup):
    tok = setup[0]
    prompt = tok.encode_text(
        "the quick brown fox jumps over the lazy dog " * 3
    )[:96]
    cold = _mk(setup, cache_pages=0)
    want, _ = _drive(cold, prompt, 8)

    dec_a = _mk(setup)
    _drive(dec_a, prompt, 8)
    dec_a.flush_harvests()

    dec_b = _mk(setup)
    registry = MetricsRegistry()
    client = LoopbackClient(dec_a, registry=registry)
    dec_b.page_share = client
    with drop_page_pulls(client) as stats:
        got, info = _drive(dec_b, prompt, 8)
    assert got == want  # identical to a plain miss, zero client errors
    assert stats["dropped"] >= 1
    assert dec_b.remote_pull_failures == 1 and dec_b.remote_hits == 0
    assert info["prefix"]["remote"]["failed"]
    assert info["prefix"]["remote"]["pulled"] == 0
    assert _metric(
        registry, "serve_prefix_remote_pull_failures_total"
    ) >= 1
    # The failed admission computed its own pages: the NEXT admission
    # hits locally like any post-miss repeat.
    got2, info2 = _drive(dec_b, prompt, 8)
    assert got2 == want and info2["prefix"]["hit_pages"] >= 1


def test_slow_owner_hits_deadline_and_keeps_partial_prefix(setup):
    """Every fetch stalls past the transfer deadline: at most one page
    lands before the budget is gone; the imported prefix stays (a
    valid shorter chain), the tail is recomputed locally, output is
    still bit-exact."""
    tok = setup[0]
    prompt = tok.encode_text(
        "the quick brown fox jumps over the lazy dog " * 3
    )[:96]
    cold = _mk(setup, cache_pages=0)
    want, _ = _drive(cold, prompt, 8)

    dec_a = _mk(setup)
    _drive(dec_a, prompt, 8)
    dec_a.flush_harvests()

    dec_b = _mk(setup)
    client = LoopbackClient(dec_a, timeout_s=0.25)
    dec_b.page_share = client
    with slow_page_pulls(client, delay_s=0.3) as stats:
        got, info = _drive(dec_b, prompt, 8)
    assert got == want
    assert stats["calls"] >= 1
    remote = info["prefix"]["remote"]
    assert remote["failed"] and remote["pulled"] < (len(prompt) - 1) // 32
    assert dec_b.remote_pull_failures == 1


def test_unflushed_owner_pages_are_never_served(setup):
    """Report-after-flush safety: A has inserted its pages but the
    harvest device copy has NOT flushed — the export path must refuse
    (the arena bytes are still the previous occupant's) and B must
    degrade to local prefill, not splice garbage."""
    tok = setup[0]
    prompt = tok.encode_text("unflushed owner page bytes " * 8)[:80]
    cold = _mk(setup, cache_pages=0)
    want, _ = _drive(cold, prompt, 6)

    dec_a = _mk(setup)
    _drive(dec_a, prompt, 6)
    assert dec_a._queued_dst  # copy still queued: the dangerous window

    dec_b = _mk(setup)
    dec_b.page_share = LoopbackClient(dec_a)
    got, info = _drive(dec_b, prompt, 6)
    assert got == want
    assert dec_b.remote_hits == 0 and dec_b.remote_pull_failures == 1


def test_export_route_core_pins_and_refuses_queued_pages(setup):
    """ChatServer.export_page_by_key semantics without HTTP: a flushed
    page round-trips export -> import byte-identically; a queued
    (unflushed) page and an unknown key both answer None; the pin is
    always released."""
    from luminaai_tpu.serving.server import ChatServer

    tok = setup[0]
    prompt = tok.encode_text("export route core words " * 8)[:80]
    dec = _mk(setup)
    _drive(dec, prompt, 6)
    chain = page_chain_keys(prompt, 32, (len(prompt) - 1) // 32)
    fake = SimpleNamespace(batcher=SimpleNamespace(decoder=dec))
    # Queued (unflushed) pages refuse service.
    assert ChatServer.export_page_by_key(fake, chain[0]) is None
    dec.flush_harvests()
    payload = ChatServer.export_page_by_key(fake, chain[0])
    assert payload is not None and payload[:4] == PAGE_WIRE_MAGIC
    assert dec.prefix_cache.page_refs() == 0  # pin released either way
    assert ChatServer.export_page_by_key(fake, "ab" * 32) is None
    # Round-trip: import into another pool, re-export, bytes identical.
    dec2 = _mk(setup)
    gid = 0
    assert dec2.pool.import_page(gid, payload) == len(payload)
    assert dec2.pool.export_page(gid) == payload
    # A geometry-mismatched payload must raise, not corrupt the arena.
    dec8 = _mk(setup, kv_dtype="int8")
    with pytest.raises(ValueError, match="leaf|leaves"):
        dec8.pool.import_page(0, payload)
