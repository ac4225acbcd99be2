"""Regression tests for the advisor findings (ADVICE.md listed them; it
was deleted in PR 49 once every one was fixed, and these tests hold them):

- loss_mask/loss_weights must be shifted with the labels so the loss for
  predicting token i+1 is gated by token i+1's mask, not token i's.
- Trailing EOS gets assistant weight only when the conversation ends on an
  assistant turn.
- PackedDataset's shuffled epoch must not materialize the corpus and must
  produce the same batches as packing the fully materialized permuted
  stream.
- PrefetchLoader must release its worker thread when the consumer abandons
  the iterator early.
"""

import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from luminaai_tpu.config import Config
from luminaai_tpu.data.dataset import PackedDataset, PrefetchLoader, TokenCache
from luminaai_tpu.data.tokenizer import ConversationTokenizer
from luminaai_tpu.models.transformer import LuminaTransformer
from luminaai_tpu.native import pack_batch, shuffle_indices
from luminaai_tpu.parallel.train_step import make_loss_fn, shift_with_labels


# -- loss mask/weight alignment -------------------------------------------
def _tiny_model():
    cfg = Config(
        vocab_size=64,
        hidden_size=32,
        num_layers=1,
        num_heads=2,
        num_kv_heads=2,
        seq_length=16,
        batch_size=2,
        use_moe=False,
        use_flash_attention=False,
        gradient_checkpointing=False,
        precision="fp32",
        z_loss_weight=0.0,
        label_smoothing=0.0,
        dropout=0.0,
    )
    model = LuminaTransformer(cfg)
    ids = jnp.arange(cfg.batch_size * cfg.seq_length, dtype=jnp.int32)
    ids = ids.reshape(cfg.batch_size, cfg.seq_length) % cfg.vocab_size
    params = jax.jit(model.init)(jax.random.key(0), ids)["params"]
    return cfg, model, params, ids


def test_shift_with_labels_moves_left_and_zeroes_tail():
    x = jnp.asarray([[1.0, 2.0, 3.0, 4.0]])
    out = shift_with_labels(x)
    assert out.tolist() == [[2.0, 3.0, 4.0, 0.0]]


def test_loss_mask_gates_predicted_token_position():
    """A mask marking only token j must yield the CE of logits[j-1]
    predicting ids[j] — i.e. the mask follows the label shift."""
    cfg, model, params, ids = _tiny_model()
    j = 5
    loss_mask = np.zeros((cfg.batch_size, cfg.seq_length), np.float32)
    loss_mask[:, j] = 1.0
    batch = {"input_ids": ids, "loss_mask": jnp.asarray(loss_mask)}

    loss_fn = make_loss_fn(cfg, model)
    loss, _ = loss_fn(params, batch, jax.random.key(1))

    logits, _ = jax.jit(model.apply, static_argnames="deterministic")(
        {"params": params}, ids, deterministic=True
    )
    logp = jax.nn.log_softmax(logits[:, j - 1].astype(jnp.float32), axis=-1)
    expected = -jnp.take_along_axis(
        logp, ids[:, j][:, None], axis=-1
    ).mean()
    np.testing.assert_allclose(float(loss), float(expected), rtol=1e-4)


def test_loss_weights_follow_label_shift():
    """Weighting token j by w must scale exactly the loss term for
    predicting ids[j] (at logits position j-1)."""
    cfg, model, params, ids = _tiny_model()
    loss_fn = make_loss_fn(cfg, model)
    base_mask = np.ones((cfg.batch_size, cfg.seq_length), np.float32)

    weights = np.ones((cfg.batch_size, cfg.seq_length), np.float32)
    j = 7
    weights[:, j] = 3.0
    rng = jax.random.key(1)
    loss_w, _ = loss_fn(
        params,
        {
            "input_ids": ids,
            "loss_mask": jnp.asarray(base_mask),
            "loss_weights": jnp.asarray(weights),
        },
        rng,
    )
    loss_u, _ = loss_fn(
        params,
        {"input_ids": ids, "loss_mask": jnp.asarray(base_mask)},
        rng,
    )
    # Compute the per-position CE at j-1 (predicting ids[j]) directly.
    logits, _ = jax.jit(model.apply, static_argnames="deterministic")(
        {"params": params}, ids, deterministic=True
    )
    logp = jax.nn.log_softmax(logits[:, j - 1].astype(jnp.float32), axis=-1)
    ce_j = -jnp.take_along_axis(logp, ids[:, j][:, None], axis=-1)[:, 0]
    n = cfg.batch_size * (cfg.seq_length - 1)  # valid loss positions
    # weighted mean = (sum_u + 2*sum(ce_j)) / (n + 2*batch)
    expected = (float(loss_u) * n + 2.0 * float(ce_j.sum())) / (
        n + 2.0 * cfg.batch_size
    )
    np.testing.assert_allclose(float(loss_w), expected, rtol=1e-4)


# -- trailing EOS weight ----------------------------------------------------
def test_trailing_eos_weight_follows_final_role():
    tok = ConversationTokenizer(assistant_loss_weight=2.0)
    ends_user = {
        "messages": [
            {"role": "assistant", "content": "hi"},
            {"role": "user", "content": "tell me more"},
        ]
    }
    enc = tok.encode_conversation(ends_user)
    assert enc["input_ids"][-1] == tok.eos_token_id
    assert enc["loss_mask"][-1] == 0.0  # EOS after a user turn: no loss

    ends_assistant = {
        "messages": [
            {"role": "user", "content": "hi"},
            {"role": "assistant", "content": "hello"},
        ]
    }
    enc = tok.encode_conversation(ends_assistant)
    assert enc["input_ids"][-1] == tok.eos_token_id
    assert enc["loss_mask"][-1] == 1.0
    assert enc["loss_weights"][-1] == 2.0


# -- shuffled packing equivalence ------------------------------------------
def _make_cache(tmp_path, n_docs=37, seed=3):
    rng = np.random.RandomState(seed)
    docs = [
        rng.randint(1, 100, size=rng.randint(3, 40)).tolist()
        for _ in range(n_docs)
    ]
    return TokenCache(str(tmp_path / "c")).build(iter(docs))


def test_shuffled_packing_matches_materialized_reference(tmp_path):
    cache = _make_cache(tmp_path)
    B, S, SEED = 4, 16, 11
    ds = PackedDataset(
        cache, batch_size=B, seq_length=S, pad_id=0, eos_id=1,
        shuffle_seed=SEED,
    )
    got = list(ds)

    # Reference: materialize the permuted stream, pack in one walk (the
    # old O(corpus) behavior we are matching without the memory cost).
    perm = shuffle_indices(cache.n_docs, SEED)
    toks = np.concatenate(
        [np.asarray(cache.tokens[cache.offsets[d]:cache.offsets[d + 1]])
         for d in perm]
    )
    lens = (cache.offsets[1:] - cache.offsets[:-1])[perm]
    offs = np.concatenate([[0], np.cumsum(lens)]).astype(np.int64)
    want = []
    doc, tok = 0, 0
    while doc < cache.n_docs:
        out, mask, doc, tok = pack_batch(
            toks, offs, doc, B, S, pad_id=0, eos_id=1,
            split_docs=True, start_token=tok,
        )
        if mask.sum() == 0:
            break
        want.append((out, mask))

    assert len(got) == len(want)
    for g, (w_out, w_mask) in zip(got, want):
        np.testing.assert_array_equal(g["input_ids"], w_out)
        np.testing.assert_array_equal(g["loss_mask"], w_mask.astype(np.float32))


def test_shuffled_packing_covers_all_tokens(tmp_path):
    cache = _make_cache(tmp_path, n_docs=20)
    ds = PackedDataset(
        cache, batch_size=2, seq_length=32, pad_id=0, eos_id=-1,
        shuffle_seed=7,
    )
    real = sum(int(b["loss_mask"].sum()) for b in ds)
    # every corpus token appears exactly once (no eos inserted, pad excluded),
    # except a possible dropped tail shorter than one row
    assert cache.n_tokens - real < 2 * 32


# -- prefetch loader abandonment -------------------------------------------
def test_prefetch_abandoned_iterator_releases_worker():
    def slow_batches():
        for i in range(1000):
            yield {"input_ids": np.zeros((1, 4), np.int32) + i}

    before = threading.active_count()
    loader = PrefetchLoader(slow_batches, prefetch=1)
    it = iter(loader)
    first = next(it)
    assert int(first["input_ids"][0, 0]) == 0
    it.close()  # abandon mid-epoch; finally must stop the worker
    deadline = time.time() + 5.0
    while threading.active_count() > before and time.time() < deadline:
        time.sleep(0.05)
    assert threading.active_count() <= before


def test_prefetch_full_epoch_still_complete():
    n = 17

    def batches():
        for i in range(n):
            yield {"x": np.asarray([i])}

    out = list(PrefetchLoader(batches, prefetch=3))
    assert [int(b["x"][0]) for b in out] == list(range(n))


# -- round-5 advisor findings ----------------------------------------------
def _spec_engine(seq_length, attention_window, max_context):
    """Tiny engine for the speculative rolling-cache regressions."""
    from flax import linen as nn

    from luminaai_tpu.inference.generate import GenerationEngine

    tok = ConversationTokenizer()
    cfg = Config(
        vocab_size=tok.vocab_size, hidden_size=32, num_layers=1,
        num_heads=2, num_kv_heads=2, seq_length=seq_length,
        attention_window=attention_window, use_flash_attention=False,
        precision="fp32", gradient_checkpointing=False, max_new_tokens=16,
    )
    model = LuminaTransformer(cfg)
    params = jax.jit(model.init)(jax.random.key(0), jnp.ones((1, 8), jnp.int32))[
        "params"
    ]
    params = jax.tree.map(
        lambda x: x.unbox() if isinstance(x, nn.meta.AxisMetadata) else x,
        params, is_leaf=lambda x: isinstance(x, nn.meta.AxisMetadata),
    )
    return (
        GenerationEngine(model, params, tok, cfg, max_context=max_context),
        tok,
    )


def test_speculative_small_max_context_rolls_and_falls_back():
    """ADVICE r5 medium: the attention layer rolls whenever the cache is
    smaller than seq_length, but generate_speculative only engaged its
    draft cap when the cache was smaller than MAX_CONTEXT — with
    seq_length=512, max_context=128, window=124 a speculative request hit
    the layer's trace-time slack ValueError (an HTTP 500) instead of the
    promised cap/fallback. The cap condition now mirrors the layer's."""
    engine, tok = _spec_engine(
        seq_length=512, attention_window=124, max_context=128
    )
    prompt = tok.encode_text("the quick brown fox jumps over " * 3)
    ref, _ = engine.generate(
        prompt, max_new_tokens=12, temperature=0.0, seed=0,
        repetition_penalty=1.0,
    )
    # Previously: ValueError at trace time. Now: capped draft, exact
    # greedy sequence.
    spec, stats = engine.generate_speculative(
        prompt, max_new_tokens=12, draft_k=8, seed=0
    )
    assert spec == ref, (stats, spec, ref)


def test_speculative_window_wider_than_context_falls_back():
    """Zero/negative slack (window >= cache slots): speculation must fall
    back to plain greedy decode, not crash."""
    engine, tok = _spec_engine(
        seq_length=512, attention_window=130, max_context=128
    )
    prompt = tok.encode_text("pack my box with five dozen " * 3)
    ref, _ = engine.generate(
        prompt, max_new_tokens=8, temperature=0.0, seed=0,
        repetition_penalty=1.0,
    )
    spec, stats = engine.generate_speculative(
        prompt, max_new_tokens=8, draft_k=8, seed=0
    )
    assert spec == ref
    assert "verify_calls" not in stats  # plain-generate fallback


def test_trim_prompt_clamps_oversized_max_new():
    """ADVICE r5 low: max_new_tokens larger than the context budget made
    _trim_prompt's budget non-positive and p[-max_prompt:] then KEPT an
    over-long prompt, crashing prefill with an HTTP 500. The budget now
    clamps to >= 1: the request serves (truncated by length) instead of
    crashing."""
    engine, tok = _spec_engine(
        seq_length=64, attention_window=None, max_context=32
    )
    prompt = tok.encode_text("a very long prompt " * 10)
    assert len(prompt) > 32
    assert len(engine._trim_prompt(prompt, max_new=engine.max_context)) == 1
    tokens, stats = engine.generate(
        prompt, max_new_tokens=40, temperature=0.0, seed=0,
        repetition_penalty=1.0,
    )
    assert isinstance(tokens, list)
    assert stats["stopped"] in ("eos", "length")
    # Speculative trims with max_new + draft_k slack; same clamp applies.
    spec, _ = engine.generate_speculative(
        prompt, max_new_tokens=40, draft_k=4, seed=0
    )
    assert isinstance(spec, list)


def test_ring_attention_window_noncausal_raises_on_both_paths():
    """ADVICE r5 low: the einsum ring silently computed a one-sided band
    for window + non-causal while the flash path raised. Both paths now
    raise the same ValueError."""
    from jax.sharding import Mesh

    from luminaai_tpu.ops.ring_attention import ring_attention

    mesh = Mesh(np.array(jax.devices()[:2]).reshape(2), ("sequence",))
    q = jnp.zeros((1, 8, 2, 4), jnp.float32)
    k = jnp.zeros((1, 8, 2, 4), jnp.float32)
    v = jnp.zeros((1, 8, 2, 4), jnp.float32)
    for use_flash in (False, True):
        with pytest.raises(ValueError, match="causal-only"):
            ring_attention(
                q, k, v, mesh, causal=False, window=4, use_flash=use_flash
            )
