"""What the tick program is specialised by (ISSUE 62): ONE program a
sampling key where the decode kernel attends every layer's lanes, a ladder
of them (one per power-of-two page extent) where XLA attends any layer's
lanes or a prefix cache's page table is chased.

At a tiny size on the CPU, float32, three lanes in pages of 4 rows, with
lanes whose lengths cross every former rung of the ladder:

  - under 'ragged' (the kernel, interpreted) the decoder builds exactly one
    tick program and serves the tokens of the laddered 'ragged_xla'
    decoder, which builds every rung, and of generate() under 'dense': over
    grouped and one-to-one heads, rings beside whole pages, a latent entry,
    state-space layers beside an attention layer;
  - with a prefix cache on the ladder stays, kernel or not;
  - the one program's grid stops at the longest lane's last key block (a
    bound it reads), and the host's count of its steps follows that rule
    whatever the extent; its live steps are what they were;
  - `serve_tick_programs_built_total` reads what was built.
"""

import dataclasses
import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.architectures.kimi_k2.test_reference import K2_TINY, k2_build
from luminaai_tpu.config import Config
from luminaai_tpu.inference.generate import GenerationEngine
from luminaai_tpu.models.transformer import LuminaTransformer
from luminaai_tpu.monitoring.telemetry import MetricsRegistry
from luminaai_tpu.ops import ragged_paged_attention as rpa
from luminaai_tpu.parallel.sharding import unbox
from luminaai_tpu.serving.server import ContinuousScheduler

GREEDY = (0.0, 0, 1.0, 1.0)
PAGE, CAP, CHUNK, VOCAB = 4, 64, 6, 64
PLAIN = dict(
    vocab_size=VOCAB, hidden_size=32, num_layers=3, num_heads=4,
    num_kv_heads=2, intermediate_size=48, seq_length=CAP, precision="fp32",
    use_flash_attention=False, use_stable_embedding=False, scan_layers=False,
    prefill_chunk_size=CHUNK, max_new_tokens=8,
)
KINDS = {
    "grouped_heads": {},
    "one_to_one_heads": dict(num_kv_heads=4),
    # window 8 over chunks of 6: a ring of 5 pages beside the full layer's
    # whole pages
    "rings_beside_whole_pages": dict(layer_windows=(8, 8, None)),
    "state_space_beside_attention": dict(
        layer_mixers=("ssm", "attention", "ssm"), use_rope=False,
        ssm_dt_rank=4, tie_word_embeddings=True),
    "latent_entry": None,  # the kimi_k2 tiny stack (three 'latent' layers)
}
# name -> (prompt length, tokens to serve, admitted before tick): `first`
# steps alone from 3 rows to the slot's end, through every rung; the others
# arrive in chunks beside it.
REQUESTS = {"first": (3, 40, 0), "long": (20, 10, 4), "mid": (9, 6, 9)}


class _Tok:
    vocab_size = 512
    eos_token_id = pad_token_id = im_end = 513

    class backend:
        @staticmethod
        def encode(text):
            return [3 + (ord(c) % 50) for c in text]

    @staticmethod
    def decode(tokens):
        return " ".join(str(t) for t in tokens)


def _louder(params):
    """At its initial scale a model repeats its last prompt token whatever
    the cache holds; eight times the matrices and a stream follows the
    context, so a key read wrongly or not at all shows."""
    def scale(path, x):
        name = jax.tree_util.keystr(path)
        loud = x.ndim >= 2 and "embed" not in name and "A_log" not in name
        return x * 8.0 if loud else x

    return jax.tree_util.tree_map_with_path(scale, params)


def _plain(**over):
    """(config, loud params) of the plain three-layer stack, with `over`."""
    cfg = Config(**dict(PLAIN, **over))
    cfg.validate()
    params = unbox(jax.jit(LuminaTransformer(cfg).init)(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32))["params"])
    return cfg, _louder(params)


@pytest.fixture(scope="module", params=list(KINDS))
def stack(request):
    """(kind, config, params) of one kind of stack: the backends below share
    the weights, since no parameter depends on the backend."""
    if request.param == "latent_entry":
        cfg, _, params = k2_build(
            K2_TINY, seq_length=CAP, prefill_chunk_size=CHUNK,
            scan_layers=False, attention_backend="ragged_xla", init_std=0.3,
            max_new_tokens=8)
        return request.param, cfg, params
    return (request.param, *_plain(**KINDS[request.param]))


def _engine(stack, backend):
    _, cfg, params = stack
    cfg = dataclasses.replace(cfg, attention_backend=backend)
    return GenerationEngine(LuminaTransformer(cfg), params, _Tok(), cfg)


def _prompt(cfg, name):
    n = REQUESTS[name][0]
    return ((np.arange(n) * 7 + len(name)) % (cfg.vocab_size - 3) + 3).tolist()


def _serve(dec):
    """Drive the decoder as the scheduler does, a tick at a time: a request
    is admitted before its tick, the oldest admission's next chunk rides
    each step. name -> the tokens served."""
    cfg = dec.engine.config
    out, slot_of, prefilling = {}, {}, {}
    budget = {name: r[1] for name, r in REQUESTS.items()}
    admit = {r[2]: name for name, r in REQUESTS.items()}
    for tick in range(80):
        name = admit.get(tick)
        if name is not None:
            slot = slot_of[name] = dec.acquire_slot()
            args = dict(max_new_tokens=budget[name], sample_key=GREEDY, seed=1)
            st = dec.start_prefill(slot, _prompt(cfg, name), **args)
            if st is None:  # no longer than a chunk: the whole-prompt path
                info = dec.prefill_into_slot(slot, _prompt(cfg, name), **args)
                out[name] = [info["token"]]
            else:
                prefilling[name] = st
        if tick > max(admit) and all(
                len(out.get(n, ())) >= budget[n] for n in REQUESTS):
            break
        riding = next(iter(prefilling.items()), None)
        assert dec.dispatch_step(
            GREEDY, chunk=riding[1]
            if riding and dec.prefill_ready(riding[1]) else None)
        toks, produced, eos = dec.collect_step()
        assert not eos.any()
        for n, slot in slot_of.items():
            if n in out and produced[slot] and len(out[n]) < budget[n]:
                out[n].append(int(toks[slot]))
                if len(out[n]) >= budget[n]:
                    dec.release_slot(slot)
        if riding and "info" in riding[1]:
            out[riding[0]] = [riding[1].pop("info")["token"]]
            del prefilling[riding[0]]
    assert not prefilling and dec.steps_in_flight == 0
    assert {n: len(t) for n, t in out.items()} == budget
    return out


def _decoder(engine, **kw):
    return engine.make_stepwise(num_slots=3, page_size=PAGE,
                                max_slot_tokens=CAP,
                                prefill_chunk_tokens=CHUNK, **kw)


def _ladder(dec):
    """Every rung's label: power-of-two page counts up to the slot's."""
    rungs, p = set(), 1
    while True:
        rungs.add(str(min(p, dec.pool.pages) * PAGE))
        if p >= dec.pool.pages:
            return rungs
        p *= 2


def _steps_built(dec):
    return [key for key in dec._fns if key[0] == "step"]


@pytest.fixture(scope="module")
def served(stack):
    """The same three requests through the kernel's decoder and XLA's."""
    decs = {b: _decoder(_engine(stack, b)) for b in ("ragged", "ragged_xla")}
    return decs, {b: _serve(dec) for b, dec in decs.items()}


def test_where_the_kernel_attends_the_lanes_one_program_serves_every_length(
        stack, served):
    decs, tokens = served
    dec = decs["ragged"]
    assert not dec._tick_ladder
    assert dec.tick_programs_built == {"full": 1}
    assert [key[3] for key in _steps_built(dec)] == [None]
    # the ladder's tokens, and the whole-cache path's under 'dense'
    assert tokens["ragged"] == tokens["ragged_xla"]
    dense = _engine(stack, "dense")
    for name, (_, budget, _) in REQUESTS.items():
        assert tokens["ragged"][name] == dense.generate(
            _prompt(dense.config, name), max_new_tokens=budget, seed=1,
            temperature=0.0, repetition_penalty=1.0)[0], name


def test_where_xla_attends_the_lanes_the_ladder_is_built_rung_by_rung(
        stack, served):
    kind, _, _ = stack
    dec = served[0]["ragged_xla"]
    assert dec._tick_ladder
    assert dec.tick_programs_built == {rung: 1 for rung in _ladder(dec)}
    assert len(_steps_built(dec)) == len(_ladder(dec)) >= 5
    assert dec.lane_attention_blocks == 0


def test_the_one_programs_grid_stops_at_the_longest_lane(stack, served):
    """The one program's plan covers the slot's whole pages; its grid visits
    a lane's key blocks up to the longest stepped lane's last (a bound the
    program reads, lane_grid_blocks) and a ring whole, so the host counts
    no more steps than under the ladder's rungs and fewer than the slot's."""
    dec = served[0]["ragged"]
    whole = 0
    for (window, n_kv), layers in dec._kinds.items():
        pages = dec.pool.ring_pages if window else dec.pool.pages
        per_block, _ = rpa.lane_blocks(pages, PAGE, n_kv, dec._key_width, 4)
        whole += layers * dec.num_slots * (pages // per_block)
    if dec._n_latent_layers:
        per_block, _ = rpa.lane_blocks(dec.pool.pages, PAGE,
                                       *dec._latent_row, 4)
        whole += dec._n_latent_layers * dec.num_slots * (
            dec.pool.pages // per_block)
    assert 0 < dec.lane_attention_blocks_live <= dec.lane_attention_blocks
    assert dec.lane_attention_blocks <= dec.steps * whole
    if dec.pool.pages > whole // dec.num_slots:
        return  # every layer's slot is one block: nothing to stop short of
    assert dec.lane_attention_blocks < dec.steps * whole


@pytest.mark.parametrize("lengths,want", [
    ([0, 0, 0], 1), ([1, 0, 0], 1), ([8, 3, 0], 1), ([9, 3, 0], 2),
    ([3, 30, 12], 4), ([64, 1, 1], 8), ([70, 1, 1], 8),
], ids=str)
def test_the_grids_bound_is_the_longest_lanes_last_block(lengths, want):
    """Blocks of 8 rows, 8 of them in the plan: one at the least, the
    plan's at the most, on the device and on the host alike."""
    for xp in (np, jnp):
        got = rpa.lane_grid_blocks(xp.asarray(lengths, xp.int32), 8, 8, xp)
        assert int(got) == want
    assert int(rpa.lane_grid_blocks(np.zeros((0,), np.int32), 8, 8, np)) == 1


@pytest.mark.parametrize("backend", ["ragged", "ragged_xla"])
def test_a_chased_page_table_keeps_the_ladder(backend):
    """Under a prefix cache a block is one page and a whole grid lanes x
    pages steps: the extent still bounds it."""
    stack = ("grouped_heads", *_plain())
    dec = _decoder(_engine(stack, backend), prefix_cache_pages=8)
    assert dec.prefix_cache is not None and dec._tick_ladder
    assert dec._lane_kernel == (backend == "ragged")
    plain = _decoder(_engine(stack, backend))
    assert _serve(dec) == _serve(plain)
    assert dec.tick_programs_built == {rung: 1 for rung in _ladder(dec)}
    assert all(key[3] is not None for key in _steps_built(dec))


def test_dense_knows_no_extent():
    dec = _decoder(_engine(("grouped_heads", *_plain()), "dense"))
    assert not dec._tick_ladder
    _serve(dec)
    assert dec.tick_programs_built == {"full": 1}


@pytest.mark.parametrize("extent", [32, None],
                         ids=["a_rung_of_32_rows", "the_whole_slot"])
def test_the_hosts_count_of_the_grid_follows_the_lanes_not_the_extent(
        monkeypatch, extent):
    """Three MHA layers, two pages a block, lane 0 holding 3 rows, lane 1
    30, lane 2 not stepped: 5 blocks a layer are live (lane 0's first,
    lane 1's four) and 40 rows read, and the grid visits 4 blocks a lane
    (lane 1's 30 rows end in the fourth) under a rung of 32 rows (4 blocks
    a lane) and over the whole 64 (8 in the plan) alike."""
    monkeypatch.setattr(rpa, "_LANE_BLOCK_BYTES", 2 * PAGE * 4 * 8 * 4)
    dec = _decoder(_engine(
        ("one_to_one_heads", *_plain(num_kv_heads=4)), "ragged"))
    assert rpa.lane_blocks(CAP // PAGE, PAGE, 4, 8, 4) == (2, 2)
    dec._kv_rows_of(extent, np.asarray([2, 29, 11], np.int32),
                    np.asarray([True, True, False]), None)
    assert dec.lane_attention_blocks == 3 * 3 * 4
    assert dec.lane_attention_blocks_live == 3 * 5
    assert dec.kv_global_rows == 3 * 40
    # shorter lanes, fewer steps: 3 and 12 rows end in the second block
    dec._kv_rows_of(extent, np.asarray([2, 11, 40], np.int32),
                    np.asarray([True, True, False]), None)
    assert dec.lane_attention_blocks == 3 * 3 * (4 + 2)
    assert dec.lane_attention_blocks_live == 3 * (5 + 1 + 2)


@pytest.mark.parametrize("backend,built", [
    ("ragged", {"extent=full": 1}),
    ("ragged_xla", {"extent=4": 1, "extent=16": 1}),
])
def test_the_registry_reads_what_was_built(backend, built):
    """Through the scheduler: a prompt of 9 tokens and 5 more meet the
    rungs of 4 rows (the chunks' ticks step no lane) and of 16."""
    cfg, params = _plain()
    engine = _engine(("grouped_heads", cfg, params), backend)
    registry = MetricsRegistry()
    sched = ContinuousScheduler(
        engine, num_slots=2, page_size=PAGE, max_slot_tokens=CAP,
        registry=registry)
    try:
        got = sched.submit(_prompt(cfg, "mid"), dict(
            max_new_tokens=5, seed=1, temperature=0.0,
            repetition_penalty=1.0))
        assert len(got[0]) == 5
        for _ in range(2000):
            if sched.idle():
                break
            threading.Event().wait(0.005)
        snap = registry.snapshot()
        assert snap["serve_tick_programs_built_total"] == built
        assert dict(sched.decoder.tick_programs_built) == {
            k.split("=")[1]: v for k, v in built.items()}
    finally:
        sched.close()
