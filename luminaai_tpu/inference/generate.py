"""Generation engine: jitted prefill + in-device decode loop with KV cache.

Covers the reference GenerationEngine (ref: Src/Main_Scripts/Chat.py:346 —
temperature / top-k / top-p sampling, repetition penalty over recent
tokens, stop-token handling, streaming, session stats). Re-designed for
XLA rather than translated:

  - The reference re-runs the FULL model over the growing sequence every
    step (no KV cache, O(S²) per token). Here: one prefill pass fills a
    preallocated KV cache, then a `lax.while_loop` decodes with S=1 steps
    entirely on device — no host round-trip per token.
  - Sampling (temperature, top-k, top-p, repetition penalty) is traced
    into the loop; the repetition penalty keeps a per-vocab count buffer
    updated functionally instead of scanning a Python list.
  - Prompt lengths bucket to powers of two so jit recompiles O(log S)
    times, not per length.
"""

from __future__ import annotations

import collections
import functools
import logging
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from luminaai_tpu.config import Config
from luminaai_tpu.monitoring.goodput import (
    SERVE_TICK_PHASES,
    ThreadPhaseLedger,
)
from luminaai_tpu.monitoring.tracing import SpanTracer

logger = logging.getLogger(__name__)

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Sampling (pure, traced)
# ---------------------------------------------------------------------------
def apply_repetition_penalty(
    logits: jax.Array, counts: jax.Array, penalty: float
) -> jax.Array:
    """CTRL-style penalty on every token generated so far (ref Chat.py:392
    applies it to the last 50; the count buffer covers the whole response).
    """
    if penalty == 1.0:
        return logits
    seen = counts > 0
    scaled = jnp.where(logits > 0, logits / penalty, logits * penalty)
    return jnp.where(seen, scaled, logits)


def apply_top_k(logits: jax.Array, k: int) -> jax.Array:
    if k <= 0:
        return logits
    k = min(k, logits.shape[-1])
    kth = jax.lax.top_k(logits, k)[0][..., -1]
    return jnp.where(logits < kth, NEG_INF, logits)


def apply_top_p(logits: jax.Array, p: float) -> jax.Array:
    """Nucleus filtering (ref Chat.py:411). Keeps at least one token."""
    if p >= 1.0:
        return logits
    sorted_logits = jnp.sort(logits)[..., ::-1]
    probs = jax.nn.softmax(sorted_logits, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    # Keep tokens whose cumulative mass (exclusive) is below p.
    keep_sorted = (cum - probs) < p
    kth = jnp.min(
        jnp.where(keep_sorted, sorted_logits, jnp.inf), axis=-1
    )
    return jnp.where(logits < kth, NEG_INF, logits)


def sample_token(
    rng: jax.Array,
    logits: jax.Array,
    counts: jax.Array,
    *,
    temperature: float,
    top_k: int,
    top_p: float,
    repetition_penalty: float,
) -> jax.Array:
    logits = logits.astype(jnp.float32)
    logits = apply_repetition_penalty(logits, counts, repetition_penalty)
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1)
    logits = logits / max(temperature, 0.01)
    logits = apply_top_k(logits, top_k)
    logits = apply_top_p(logits, top_p)
    return jax.random.categorical(rng, logits, axis=-1)


def _bucket_len(n: int, minimum: int = 64) -> int:
    b = minimum
    while b < n:
        b *= 2
    return b


def ngram_propose(
    history: Sequence[int], k: int, max_ngram: int = 3
) -> List[int]:
    """Prompt-lookup draft: find the most recent earlier occurrence of the
    history's trailing n-gram (longest n first) and propose the k tokens
    that followed it. Zero model cost — the draft source is the text
    itself, which is highly repetitive in the workloads speculative
    decoding targets (code, structured chat, retrieval contexts). Returns
    [] when no n-gram recurs.

    Reference implementation (O(len·n) scan); the decode loop uses the
    incremental _NgramIndex with identical proposals."""
    h = list(history)
    n_h = len(h)
    for n in range(min(max_ngram, n_h - 1), 0, -1):
        tail = h[n_h - n:]
        # Scan right-to-left for the latest earlier match.
        for i in range(n_h - n - 1, -1, -1):
            if h[i:i + n] == tail:
                cont = h[i + n: i + n + k]
                if cont:
                    return cont
    return []


class _NgramIndex:
    """Incremental prompt-lookup index: each n-gram maps to its two most
    recent end offsets, so per-round proposals are O(max_ngram) dict hits
    instead of a full history rescan between device steps (the host-side
    stall grows with context otherwise). Proposals match ngram_propose:
    latest EARLIER occurrence, longest n first (the tail's own occurrence
    is ent[0] with an empty continuation, so ent[1] supplies the match)."""

    def __init__(self, history: Sequence[int], max_ngram: int = 3):
        self.h: List[int] = list(history)
        self.max_n = max_ngram
        self.map: Dict[tuple, List[Optional[int]]] = {}
        for end in range(1, len(self.h) + 1):
            self._register(end)

    def _register(self, end: int) -> None:
        h = self.h
        for n in range(1, self.max_n + 1):
            if end - n < 0:
                break
            key = tuple(h[end - n:end])
            ent = self.map.get(key)
            if ent is None:
                self.map[key] = [end, None]
            elif ent[0] != end:
                self.map[key] = [end, ent[0]]

    def append(self, token: int) -> None:
        self.h.append(token)
        self._register(len(self.h))

    def propose(self, k: int) -> List[int]:
        h = self.h
        L = len(h)
        for n in range(min(self.max_n, L - 1), 0, -1):
            ent = self.map.get(tuple(h[L - n:]))
            if not ent:
                continue
            for end in ent:
                if end is not None:
                    cont = h[end:end + k]
                    if cont:
                        return cont
        return []


class GenerationEngine:
    """Single-sequence generation over a LuminaTransformer + params."""

    def __init__(
        self,
        model,
        params,
        tokenizer,
        config: Optional[Config] = None,
        max_context: Optional[int] = None,
    ):
        self.model = model
        self.tokenizer = tokenizer
        self.config = config or model.config
        self.max_context = max_context or self.config.seq_length
        # Inference quantization (config.quantization_method = 'int8'/
        # 'int4'; ref trainer.py:575). int8 keeps QuantizedTensor leaves in
        # the param tree — the model's quantization-aware layers run real
        # int8 MXU dots (ops/quantized.py), the TPU counterpart of the
        # ref's kernel-swapping quantization. int4 is storage-only
        # (dequantized to bf16 here; packed nibbles have no MXU dtype).
        self.quantization_info: dict = {}
        if getattr(self.config, "quantization_method", None):
            from luminaai_tpu.training.quantization import QuantizationManager

            manager = QuantizationManager(self.config)
            params = manager.prepare_serving_params(params, model.dtype)
            self.quantization_info = manager.quantization_info
        self.params = params
        self._decode_fn = {}  # keyed by generation kwargs (static args)
        self._prefill_fn = functools.lru_cache(maxsize=16)(self._make_prefill)

    def _lane_hint(self):
        """Backend-only LaneMeta threaded into every jitted model call:
        the ENGINE's config decides the attention backend even when the
        model was built from a different config (the same override
        contract kv_cache_dtype has). The attention layer derives
        lengths/window itself."""
        from luminaai_tpu.ops.ragged_paged_attention import LaneMeta

        return LaneMeta(
            lengths=None,
            backend=getattr(self.config, "attention_backend", "dense"),
        )

    # -- prefill -----------------------------------------------------------
    def _prefill_chunk_len(self) -> int:
        """Static chunk length for chunked prefill; 0 when disabled or
        when the engine's cache can roll (attention_window) — chunk
        writes are only defined on non-wrapping layouts, so windowed
        single-stream engines keep the bucket ladder."""
        chunk = int(getattr(self.config, "prefill_chunk_size", 0) or 0)
        if chunk <= 0:
            return 0
        if getattr(self.config, "attention_window", None) is not None:
            return 0
        return min(chunk, self.max_context)

    def _make_chunk_prefill_fn(self, chunk: int):
        """One fixed-shape prefill step: feed `chunk` prompt rows at
        positions start..start+chunk-1 (rows past `length` marked -1)
        into the carried cache, return the cache and the logits at the
        prompt's last row (clamped; consumed only on the final chunk).
        ONE executable serves every prompt length — the O(log S) bucket
        ladder this replaces is the decode-side recompile surface
        ROADMAP item 5 drives down."""

        hint = self._lane_hint()

        def chunk_fn(params, caches, ids, start, length):
            pos = start + jnp.arange(chunk)
            positions = jnp.where(pos < length, pos, -1)[None, :]
            logits, caches, _ = self.model.apply(
                {"params": params},
                ids,
                positions=positions,
                kv_caches=caches,
                cache_index=start,
                deterministic=True,
                lane_meta=hint,
            )
            last_idx = jnp.clip(length - 1 - start, 0, chunk - 1)
            last = jnp.take_along_axis(
                logits, last_idx[None, None, None], axis=1
            )[:, 0, :]
            return last, caches

        return chunk_fn

    def _get_chunk_prefill(self, chunk: int):
        key = ("chunk_prefill", chunk)
        if key not in self._decode_fn:
            # The cache carry is donated: each chunk consumes the
            # previous chunk's buffers (per-request state — a failed
            # call costs only that request, unlike the shared pool).
            self._decode_fn[key] = jax.jit(
                self._make_chunk_prefill_fn(chunk), donate_argnums=(1,)
            )
        return self._decode_fn[key]

    def _prefill_chunked(self, prompt: List[int], chunk: int):
        """Chunked prefill driver: ceil(L/chunk) re-entries into the one
        chunk executable. Cache rows and the last live row's logits
        match the bucketed path's — K/V rows depend only on their own
        token/position, and each chunk's attention admits exactly the
        rows the full-bucket mask admits."""
        L = len(prompt)
        # An empty prompt still runs ONE chunk (all padding rows), so the
        # caller always gets logits — matching the bucket path, which fed
        # an all-pad bucket rather than skipping the forward.
        n = max(1, -(-L // chunk))
        ids = np.zeros((1, n * chunk), dtype=np.int32)
        ids[0, :L] = prompt
        caches = self.model.init_cache(
            1, self.max_context,
            kv_cache_dtype=getattr(self.config, "kv_cache_dtype", None),
        )
        fn = self._get_chunk_prefill(chunk)
        length = jnp.asarray(L, jnp.int32)
        logits = None
        for c in range(n):
            start = c * chunk
            if start + chunk > self.max_context:
                # The padded chunk grid may overhang a cache whose extent
                # is not chunk-aligned; XLA CLAMPS an out-of-range
                # dynamic_update_slice start, which would land this
                # chunk's rows on top of earlier residents. Re-anchor the
                # window to end at the cache edge instead: the re-fed
                # overlap rows rewrite bit-identical K/V (a row depends
                # only on its own token and position), so the cache is
                # unchanged where it was already live.
                start = self.max_context - chunk
            logits, caches = fn(
                self.params,
                caches,
                jnp.asarray(ids[:, start:start + chunk]),
                jnp.asarray(start, jnp.int32),
                length,
            )
        return logits, caches

    def _make_prefill(self, prompt_bucket: int):
        return jax.jit(self._make_prefill_fn(prompt_bucket))

    def _make_prefill_fn(self, prompt_bucket: int):
        hint = self._lane_hint()

        def prefill(params, ids, length):
            # The ENGINE's config decides cache storage, so serving-time
            # overrides work regardless of which config built the model.
            caches = self.model.init_cache(
                1, self.max_context,
                kv_cache_dtype=getattr(self.config, "kv_cache_dtype", None),
            )
            # Padding rows carry position -1 so the rolling-cache scatter
            # (attention_window) can tell live prompt rows from bucket
            # padding — padding written as if it were positions
            # length..bucket-1 would clobber in-band slots once the
            # bucket exceeds the slot count. Harmless otherwise: padding
            # K/V is masked (or overwritten) on every cache layout.
            pos = jnp.arange(prompt_bucket)
            positions = jnp.where(pos < length, pos, -1)[None, :]
            logits, caches, _ = self.model.apply(
                {"params": params},
                ids,
                positions=positions,
                kv_caches=caches,
                cache_index=0,
                deterministic=True,
                lane_meta=hint,
            )
            last = jnp.take_along_axis(
                logits, (length - 1)[None, None, None], axis=1
            )[:, 0, :]
            return last, caches

        return prefill

    # -- decode loop -------------------------------------------------------
    def _make_decode(self, gen_key, carry: bool = False):
        """The jitted decode while-loop. With carry=True the function also
        returns (rng, token, caches, counts) so a caller can resume — the
        chunked streaming path re-enters this loop every `chunk` tokens,
        and because the body splits the rng exactly once per iteration,
        the chunked token sequence is bit-identical to one long loop."""
        max_new, temperature, top_k, top_p, rep_penalty = gen_key
        max_new = max_new - 1  # the prefill already sampled token #1
        stop_ids = jnp.asarray(sorted(self._stop_set), dtype=jnp.int32)
        hint = self._lane_hint()

        def cond(state):
            i, done = state[0], state[5]
            return jnp.logical_and(i < max_new, jnp.logical_not(done))

        def body(params, state):
            i, rng, token, caches, counts, done, out, start = state
            rng, step_rng = jax.random.split(rng)
            positions = (start + i)[None, None]
            logits, caches, _ = self.model.apply(
                {"params": params},
                token[None, None],
                positions=positions,
                kv_caches=caches,
                cache_index=start + i,
                deterministic=True,
                lane_meta=hint,
            )
            nxt = sample_token(
                step_rng, logits[0, -1], counts,
                temperature=temperature, top_k=top_k, top_p=top_p,
                repetition_penalty=rep_penalty,
            ).astype(jnp.int32)
            counts = counts.at[nxt].add(1)
            done = jnp.any(nxt == stop_ids)
            out = out.at[i].set(jnp.where(done, -1, nxt))
            return (i + 1, rng, nxt, caches, counts, done, out, start)

        def decode(params, rng, first_token, caches, counts, start, done0):
            out = jnp.full((max_new,), -1, jnp.int32)
            state = (
                jnp.int32(0), rng, first_token, caches, counts,
                done0, out, start,
            )
            state = jax.lax.while_loop(
                cond, functools.partial(body, params), state
            )
            if carry:
                return (
                    state[6], state[0], state[5],
                    state[1], state[2], state[3], state[4],
                )
            return state[6], state[0], state[5]

        return decode

    def _get_decode(self, gen_key):
        if gen_key not in self._decode_fn:
            self._decode_fn[gen_key] = jax.jit(self._make_decode(gen_key))
        return self._decode_fn[gen_key]

    def _get_stream_decode(self, chunk_key):
        key = ("stream", chunk_key)
        if key not in self._decode_fn:
            self._decode_fn[key] = jax.jit(
                self._make_decode(chunk_key, carry=True)
            )
        return self._decode_fn[key]

    # -- shared request plumbing -------------------------------------------
    @property
    def _stop_set(self):
        tok = self.tokenizer
        return {tok.eos_token_id, tok.pad_token_id, tok.im_end}

    def _resolve_gen_key(
        self, max_new_tokens, temperature, top_p, top_k, repetition_penalty
    ):
        """(max_new, temperature, top_k, top_p, rep_penalty) with config
        defaults filled — the decode loop's static compile key."""
        cfg = self.config
        return (
            int(max_new_tokens or cfg.max_new_tokens),
            float(cfg.temperature if temperature is None else temperature),
            int(cfg.top_k if top_k is None else top_k),
            float(cfg.top_p if top_p is None else top_p),
            float(
                cfg.repetition_penalty
                if repetition_penalty is None
                else repetition_penalty
            ),
        )

    def _trim_prompt(
        self, prompt, max_new: int, capacity: Optional[int] = None
    ) -> List[int]:
        """Keep the prompt tail that fits the context budget (ref :374).

        capacity defaults to the engine's max_context; the step-wise
        decoder passes its slot budget so both paths share ONE formula
        (and stay token-identical for over-length prompts).

        Clamped to >= 1: an oversized max_new (the server caps it, but
        its cap can exceed a small engine's max_context) would make the
        budget non-positive, and p[-max_prompt:] with a POSITIVE index
        then keeps an over-budget prompt that crashes prefill — serve the
        last token and let the length budget truncate instead (ADVICE r5
        low)."""
        cap = self.max_context if capacity is None else capacity
        max_prompt = max(1, cap - max_new - 1)
        p = list(prompt)
        return p[-max_prompt:] if len(p) > max_prompt else p

    def _get_verify(self, k: int):
        """Jitted speculative-verification step: feed k tokens (the last
        accepted token + k-1 drafted) at positions start..start+k-1 —
        their cache rows are written in the same pass — and return the
        greedy argmax at every fed position. One device call scores k
        draft tokens; decode is HBM-bound, so the k-row forward costs
        little more than an S=1 step."""
        key = ("verify", k)
        if key not in self._decode_fn:
            hint = self._lane_hint()

            def verify(params, ids, caches, start):
                positions = (start + jnp.arange(k))[None, :]
                logits, caches, _ = self.model.apply(
                    {"params": params},
                    ids,
                    positions=positions,
                    kv_caches=caches,
                    cache_index=start,
                    deterministic=True,
                    multi_row_update=True,
                    lane_meta=hint,
                )
                return (
                    jnp.argmax(logits[0], axis=-1).astype(jnp.int32),
                    caches,
                )

            self._decode_fn[key] = jax.jit(verify)
        return self._decode_fn[key]

    def generate_speculative(
        self,
        prompt_tokens: Sequence[int],
        max_new_tokens: Optional[int] = None,
        draft_k: int = 8,
        seed: Optional[int] = None,
    ) -> Tuple[List[int], Dict[str, Any]]:
        """Greedy decode with prompt-lookup (n-gram) speculative drafts.

        Each round verifies up to draft_k-1 drafted tokens plus the model's
        own next prediction in ONE k-row forward; accepted prefixes advance
        multiple positions per device call. Output is exactly the plain
        greedy generate() sequence (verification accepts a draft token only
        when it IS the greedy choice given its true prefix). Greedy-only by
        construction — temperature/top-p sampling would need rejection
        resampling; use generate() for sampled decoding.

        Blocking collector over generate_stream_speculative — one decode
        loop serves both the JSON and the SSE serving paths.

        (The reference has no speculative path; its decode re-runs the
        full model per token, Chat.py:346. This is a TPU-first serving
        addition: decode is HBM-bound, so scoring k rows costs ~one step.)
        """
        tokens: List[int] = []
        stats: Dict[str, Any] = {}
        for item in self.generate_stream_speculative(
            prompt_tokens, max_new_tokens=max_new_tokens,
            draft_k=draft_k, seed=seed,
        ):
            if isinstance(item, dict):
                stats = item
            else:
                tokens.append(int(item))
        return tokens, stats

    def generate_stream_speculative(
        self,
        prompt_tokens: Sequence[int],
        max_new_tokens: Optional[int] = None,
        draft_k: int = 8,
        seed: Optional[int] = None,
        timeout_s: Optional[float] = None,
    ):
        """Streaming prompt-lookup speculative decode: the SSE-facing twin
        of generate_speculative, honoring the generate_stream contract —
        token ints as they are ACCEPTED, then one final stats dict. Each
        verify round can release several tokens at once, so frames arrive
        in accepted-prefix bursts; the token sequence is exactly the plain
        greedy stream's. When the rolling-window cache leaves no slack for
        a k-row verify, it degrades to the chunked greedy stream.

        timeout_s bounds the decode loop (checked per verify round): on
        expiry the stream ends early with stopped='timeout' — the serving
        layer passes its per-request deadline here, since speculative
        streams run outside the continuous scheduler's lane eviction."""
        max_new = int(max_new_tokens or self.config.max_new_tokens)
        k = max(2, int(draft_k))
        w = getattr(self.config, "attention_window", None)
        if w is not None and self.max_context <= self.config.seq_length:
            # Rolling cache: a k-row verify needs C - window >= k-1 slots
            # of slack or later rows evict earlier rows' in-band keys
            # (enforced at trace time in the attention layer). Cap the
            # draft; with zero slack (window % 128 == 0) fall back to
            # plain greedy decode. The layer rolls whenever C_cache <
            # seq_length — NOT < max_context — so mirror exactly that
            # condition or a small-max_context engine 500s at trace time
            # instead of falling back (ADVICE r5 medium).
            slots = min(self.max_context, ((w + 127) // 128) * 128)
            if slots < self.config.seq_length:  # rolling actually engages
                k = min(k, slots - w + 1)
                if k < 2:
                    # Degrade to the plain greedy stream WITHOUT dropping
                    # the deadline: generate_stream has no timeout
                    # parameter, so enforce it here per yielded token —
                    # the serving layer routed this stream outside the
                    # scheduler's eviction on the promise that the engine
                    # loop honors timeout_s.
                    start = time.time()
                    produced = 0
                    src = self.generate_stream(
                        prompt_tokens, max_new_tokens=max_new,
                        temperature=0.0, repetition_penalty=1.0, seed=seed,
                    )
                    for item in src:
                        if isinstance(item, dict):
                            yield item
                            return
                        yield item
                        produced += 1
                        if (
                            timeout_s is not None
                            and time.time() - start > timeout_s
                        ):
                            src.close()
                            dt = time.time() - start
                            yield {
                                "tokens_generated": produced,
                                "seconds": round(dt, 3),
                                "tokens_per_second": round(
                                    produced / max(dt, 1e-9), 1
                                ),
                                "prompt_tokens": len(prompt_tokens),
                                "stopped": "timeout",
                            }
                            return
                    return
        gen_key = (max_new, 0.0, 0, 1.0, 1.0)  # greedy, no penalty
        t0 = time.time()
        # Trim leaves room for the verify overshoot (up to k-1 cache rows
        # past the final token) so cache writes never clamp out of range.
        prompt = self._trim_prompt(prompt_tokens, max_new + k)
        first_token, caches, counts, rng, length, first_is_stop = (
            self._prefill_and_sample_first(prompt, gen_key, seed)
        )
        del counts, rng  # greedy without penalty needs neither
        verify_calls = 0
        produced = 0
        stopped = "length"
        if first_is_stop:
            stopped = "eos"
        elif max_new >= 1:
            yield int(first_token)
            produced = 1
            index = _NgramIndex(list(prompt) + [int(first_token)])
            verify = self._get_verify(k)
            fn_stop = self._stop_set
            pos = length  # next cache row to write
            token = int(first_token)  # accepted, not yet fed
            while produced < max_new:
                if (
                    timeout_s is not None
                    and time.time() - t0 > timeout_s
                ):
                    stopped = "timeout"
                    break
                draft = index.propose(k - 1)
                ids = [token] + draft + [-1] * (k - 1 - len(draft))
                nxt, caches = verify(
                    self.params,
                    jnp.asarray([ids], jnp.int32),
                    caches,
                    jnp.asarray(pos, jnp.int32),
                )
                nxt = np.asarray(nxt)
                verify_calls += 1
                # Accept drafted tokens while each IS the greedy choice
                # given its (now verified) prefix, then take the model's
                # own prediction at the divergence point as a bonus.
                j = 0
                while j < k - 1 and int(nxt[j]) == ids[j + 1]:
                    j += 1
                accepted = [int(ids[m + 1]) for m in range(j)] + [int(nxt[j])]
                done = False
                for t in accepted:
                    if t in fn_stop:
                        stopped = "eos"
                        done = True
                        break
                    yield int(t)
                    produced += 1
                    index.append(t)
                    if produced >= max_new:
                        done = True
                        break
                # Cache rows 0..j carried correct tokens; the next round
                # re-feeds from pos+j+1, overwriting any stale drafted
                # rows before they can be attended.
                pos += j + 1
                token = accepted[-1]
                if done:
                    break
        dt = time.time() - t0
        yield {
            "tokens_generated": produced,
            "seconds": round(dt, 3),
            "tokens_per_second": round(produced / max(dt, 1e-9), 1),
            "prompt_tokens": length,
            "stopped": stopped,
            "verify_calls": verify_calls,
            "tokens_per_verify": round(
                produced / max(verify_calls, 1), 2
            ),
        }

    def _prefill_and_sample_first(self, prompt_tokens, gen_key, seed):
        """Shared prompt->first-token path for generate/generate_stream:
        trim, bucket, prefill, sample token #1. Returns (first_token,
        caches, counts, rng, prompt_len, first_is_stop)."""
        max_new = gen_key[0]
        prompt = self._trim_prompt(prompt_tokens, max_new)
        length = len(prompt)
        chunk = self._prefill_chunk_len()
        if chunk:
            first_logits, caches = self._prefill_chunked(prompt, chunk)
        else:
            bucket = min(_bucket_len(length), self.max_context)
            ids = np.zeros((1, bucket), dtype=np.int32)
            ids[0, :length] = prompt
            first_logits, caches = self._prefill_fn(bucket)(
                self.params, jnp.asarray(ids), jnp.asarray(length, jnp.int32)
            )
        counts = jnp.zeros((first_logits.shape[-1],), jnp.int32)
        rng = jax.random.key(
            seed if seed is not None else (time.time_ns() & 0xFFFFFFFF)
        )
        rng, first_rng = jax.random.split(rng)
        first_token = sample_token(
            first_rng, first_logits[0], counts,
            temperature=gen_key[1], top_k=gen_key[2], top_p=gen_key[3],
            repetition_penalty=gen_key[4],
        ).astype(jnp.int32)
        first_is_stop = int(first_token) in self._stop_set
        return first_token, caches, counts, rng, length, first_is_stop

    # -- public API --------------------------------------------------------
    def generate(
        self,
        prompt_tokens: Sequence[int],
        max_new_tokens: Optional[int] = None,
        temperature: Optional[float] = None,
        top_p: Optional[float] = None,
        top_k: Optional[int] = None,
        repetition_penalty: Optional[float] = None,
        seed: Optional[int] = None,
    ) -> Tuple[List[int], Dict[str, Any]]:
        """Returns (generated_token_ids, stats) (ref Chat.py:355)."""
        gen_key = self._resolve_gen_key(
            max_new_tokens, temperature, top_p, top_k, repetition_penalty
        )
        max_new = gen_key[0]

        t0 = time.time()
        first_token, caches, counts, rng, length, first_is_stop = (
            self._prefill_and_sample_first(prompt_tokens, gen_key, seed)
        )
        if first_is_stop or max_new <= 1:
            # A stop token is dropped; a normal token under a 1-token
            # budget is a valid result that exhausted the length.
            tokens = [] if first_is_stop else [int(first_token)]
            dt = time.time() - t0
            return tokens, {
                "tokens_generated": len(tokens),
                "seconds": round(dt, 3),
                "tokens_per_second": round(len(tokens) / max(dt, 1e-9), 1),
                "prompt_tokens": length,
                "stopped": "eos" if first_is_stop else "length",
            }

        counts = counts.at[first_token].add(1)
        out, n, hit_stop = self._get_decode(gen_key)(
            self.params, rng, first_token, caches, counts,
            jnp.asarray(length, jnp.int32), jnp.asarray(False),
        )
        out = np.asarray(out)
        n = int(n)
        tokens = [int(first_token)] + [t for t in out[:n].tolist() if t >= 0]
        dt = time.time() - t0
        stats = {
            "tokens_generated": len(tokens),
            "seconds": round(dt, 3),
            "tokens_per_second": round(len(tokens) / max(dt, 1e-9), 1),
            "prompt_tokens": length,
            # The loop's own done flag distinguishes eos-on-last-step from
            # genuine length exhaustion (both return n == max_new - 1).
            "stopped": "eos" if bool(hit_stop) else "length",
        }
        return tokens, stats

    def generate_stream(
        self,
        prompt_tokens: Sequence[int],
        max_new_tokens: Optional[int] = None,
        temperature: Optional[float] = None,
        top_p: Optional[float] = None,
        top_k: Optional[int] = None,
        repetition_penalty: Optional[float] = None,
        seed: Optional[int] = None,
        chunk_tokens: int = 8,
    ):
        """Yield generated token ids as they decode (SSE serving path).

        Chunked re-entry into the jitted decode loop: every `chunk_tokens`
        tokens the carry (rng/token/caches/counts) round-trips to host and
        the new tokens are yielded. The rng splits once per iteration
        inside the loop, so the stream is bit-identical to generate() with
        the same seed. The final yield is a stats dict (same schema as
        generate's), distinguishable because every other yield is an int.
        """
        gen_key = self._resolve_gen_key(
            max_new_tokens, temperature, top_p, top_k, repetition_penalty
        )
        max_new = gen_key[0]
        chunk = max(1, int(chunk_tokens))
        t0 = time.time()
        first_token, caches, counts, rng, length, first_is_stop = (
            self._prefill_and_sample_first(prompt_tokens, gen_key, seed)
        )
        produced = 0
        stopped = "length"
        if not first_is_stop:
            yield int(first_token)
            produced = 1
        if first_is_stop or max_new <= 1:
            stopped = "eos" if first_is_stop else "length"
        else:
            token = first_token
            counts = counts.at[token].add(1)
            # One compile per gen params (chunk size is fixed); the tail
            # chunk may over-decode up to chunk-1 iterations, trimmed to
            # the budget below so tokens AND the stopped status match
            # generate()'s single-loop semantics exactly.
            chunk_key = (chunk + 1,) + gen_key[1:]
            fn = self._get_stream_decode(chunk_key)
            budget_iters = max_new - 1  # prefill already produced token #1
            offset = 0  # decode iterations done (= cache slots past prompt)
            while offset < budget_iters:
                out, n, done, rng, token, caches, counts = fn(
                    self.params, rng, token, caches, counts,
                    jnp.asarray(length + offset, jnp.int32),
                    jnp.asarray(False),
                )
                n = int(n)
                if n <= 0:
                    break
                within = min(n, budget_iters - offset)
                fresh = [
                    t for t in np.asarray(out)[:within].tolist() if t >= 0
                ]
                for t in fresh:
                    yield int(t)
                produced += len(fresh)
                if bool(done) and n <= budget_iters - offset:
                    stopped = "eos"
                    break
                offset += n
        dt = time.time() - t0
        yield {
            "tokens_generated": produced,
            "seconds": round(dt, 3),
            "tokens_per_second": round(produced / max(dt, 1e-9), 1),
            "prompt_tokens": length,
            "stopped": stopped,
        }

    def encode_chat(self, messages: List[Dict[str, str]]) -> List[int]:
        """Conversation → prompt ids, with an open assistant turn for the
        model to complete."""
        tok = self.tokenizer
        prompt: List[int] = []
        for m in messages:
            body = tok.backend.encode(m.get("content", ""))
            prompt += [tok.im_start, tok.get_role_token(m["role"]), *body,
                       tok.im_end]
        prompt += [tok.im_start, tok.get_role_token("assistant")]
        return prompt

    def chat_response(
        self, messages: List[Dict[str, str]], **kw
    ) -> Tuple[str, Dict[str, Any]]:
        """Encode a conversation, generate, decode assistant text."""
        tokens, stats = self.generate(self.encode_chat(messages), **kw)
        return self.tokenizer.decode(tokens), stats

    # -- continuous batching (step-wise decode over a slot-paged pool) -----
    def make_stepwise(
        self,
        num_slots: int = 8,
        page_size: int = 128,
        max_slot_tokens: Optional[int] = None,
        prefill_chunk_tokens: Optional[int] = None,
        prefix_cache_pages: Optional[int] = None,
        prefix_cache_tenant_quota: Optional[int] = None,
    ) -> "StepwiseDecoder":
        """Build a StepwiseDecoder: the scheduler-owned decode API
        (prefill_into_slot + decode_step) continuous batching runs on.
        The single-sequence generate() above stays as it is: the oracle
        the step-wise parity tests compare against, and the chat REPL's
        path."""
        return StepwiseDecoder(
            self,
            num_slots=num_slots,
            page_size=page_size,
            max_slot_tokens=max_slot_tokens,
            prefill_chunk_tokens=prefill_chunk_tokens,
            prefix_cache_pages=prefix_cache_pages,
            prefix_cache_tenant_quota=prefix_cache_tenant_quota,
        )


GREEDY_SAMPLE_KEY = (0.0, 0, 1.0, 1.0)  # (temperature, top_k, top_p, rep)


class StepwiseDecoder:
    """Step-wise decode over a slot-paged KV pool (continuous batching).

    The run-to-completion path (generate) traces the whole decode into
    one lax.while_loop: one request from start to end, nothing admitted
    in between. Here the HOST owns the loop:

      prefill_into_slot(slot, prompt, ...) writes a request's prompt KV
        into its pool slot (one jit call, bucketed like generate's
        prefill) and samples its first token;
      decode_step(sample_key) advances ALL active lanes one token in one
        jit call and reports per-lane (token, produced, eos) — the
        scheduler evicts finished slots and admits queued requests into
        the freed lanes BETWEEN steps. It is dispatch_step() +
        collect_step(): the scheduler calls the halves itself, the
        dispatch of step N+1 BEFORE the collect of step N, so the
        device always has its next program queued while the host reads
        and works on the previous one (docs/serving.md "The scheduler
        loop").

    Greedy step-wise decode is token-identical to generate() (same
    prefill bucketing, same sampling math, same rng split discipline —
    parity-tested), and sampled decode is bit-identical for the same
    per-request seed. The pool is plain-layout (never rolling): admission
    bounds prompt+max_new to the slot capacity, so positions never wrap,
    and attention_window configs are served by the per-lane band mask.

    One decode-step compile per sampling parameter set (max_new is host
    state now, NOT part of the compile key — mixed-length workloads share
    one executable, the core of the continuous-batching win).
    """

    # In-flight dedup safety bound: a parked follower proceeds cold
    # after this many re-check ticks even if the pending entry never
    # clears (release_slot clears leaked claims far sooner in practice;
    # this only fences a pathological leader wedged mid-prefill).
    DEDUP_WAIT_TICKS = 512

    def __init__(
        self,
        engine: GenerationEngine,
        num_slots: int = 8,
        page_size: int = 128,
        max_slot_tokens: Optional[int] = None,
        prefill_chunk_tokens: Optional[int] = None,
        prefix_cache_pages: Optional[int] = None,
        prefix_cache_tenant_quota: Optional[int] = None,
    ):
        from luminaai_tpu.inference.kv_pool import PagedKVPool

        self.engine = engine
        self.model = engine.model
        self.params = engine.params
        cap = int(max_slot_tokens or engine.max_context)
        page_size = max(1, int(page_size))
        pages = max(1, -(-cap // page_size))
        num_slots = max(1, int(num_slots))
        # Radix prefix cache (inference/prefix_cache.py): a budget of
        # arena pages, carved out as extra pool slots PAST the lane
        # range, holds content-hashed prompt pages that admissions splice
        # into their global page tables instead of re-prefilling. None ->
        # the engine config's prefix_cache_pages; 0 disables.
        if prefix_cache_pages is None:
            prefix_cache_pages = int(
                getattr(engine.config, "prefix_cache_pages", 0) or 0
            )
        if prefix_cache_tenant_quota is None:
            prefix_cache_tenant_quota = int(
                getattr(engine.config, "prefix_cache_tenant_quota", 0) or 0
            )
        backend = getattr(engine.config, "attention_backend", "dense")
        if prefix_cache_pages > 0 and backend == "dense":
            # The dense per-lane mask reads only the lane's own rows — it
            # cannot follow a cross-slot page alias. Gated off rather
            # than silently serving stale rows (docs/serving.md).
            logger.warning(
                "prefix cache disabled: attention_backend='dense' cannot "
                "read shared pages (use ragged_xla/ragged)"
            )
            prefix_cache_pages = 0
        _chunk_eff = (
            int(prefill_chunk_tokens)
            if prefill_chunk_tokens is not None
            else int(getattr(engine.config, "prefill_chunk_size", 0) or 0)
        )
        if prefix_cache_pages > 0 and _chunk_eff <= 0:
            # The suffix-only prefill rides the chunked executables; a
            # cache without chunking has no splice path.
            logger.warning(
                "prefix cache disabled: chunked prefill is off "
                "(prefill_chunk_tokens=0)"
            )
            prefix_cache_pages = 0
        arena_slots = -(-prefix_cache_pages // pages) if (
            prefix_cache_pages > 0
        ) else 0
        self.total_slots = num_slots + arena_slots
        self.num_slots = num_slots
        self.slot_tokens = pages * page_size
        # Lane accounting covers ONLY the first num_slots rows; the arena
        # slots are never allocatable — their pages are addressed purely
        # through global page-table entries.
        self.pool = PagedKVPool(
            None,
            num_slots=num_slots,
            pages=pages,
            page_size=page_size,
        )
        self.pool.caches = self._init_pool_caches()
        # The decode budget honors the ENGINE's context contract: the
        # page rounding above may leave slack rows past max_context, and
        # decoding into them would silently run the model at
        # out-of-contract positions. Trim/clamp arithmetic below uses
        # this, with exactly generate()'s _trim_prompt formula, so the
        # two paths serve identical tokens for over-length prompts too.
        self.token_capacity = min(self.slot_tokens, engine.max_context)
        # Host-side lane state; device state is the pool + counts + rngs.
        self._reset_lane_state()
        self.steps = 0
        # Lane-steps whose token the host dropped: the lane ended (a
        # stop token, a cancel, an eviction) while the step was in
        # flight (_drop_ahead).
        self.lane_steps_dropped = 0
        self._fns: Dict[Any, Any] = {}
        # Serving attention backend (config.attention_backend): 'dense'
        # keeps the legacy full-extent per-lane mask; the ragged backends
        # thread a LaneMeta (pool page table + resident page extent)
        # through the decode step so attention reads O(tokens resident).
        self.backend = getattr(
            engine.config, "attention_backend", "dense"
        )
        # Device copy of the pool's page table, refreshed at admission
        # (identity today; a prefix cache would retarget entries there).
        self._table = jnp.asarray(self.pool.page_table_array())
        # Chunked prefill: fixed chunk length (None -> the engine
        # config's prefill_chunk_size), clamped to the slot budget;
        # 0 disables, callers fall back to prefill_into_slot.
        if prefill_chunk_tokens is None:
            prefill_chunk_tokens = int(
                getattr(engine.config, "prefill_chunk_size", 0) or 0
            )
        self.prefill_chunk = max(
            0, min(int(prefill_chunk_tokens), self.token_capacity)
        )
        self.prefix_cache = None
        if arena_slots > 0:
            from luminaai_tpu.inference.prefix_cache import RadixPrefixCache

            arena_ids = [
                (num_slots + a) * pages + p
                for a in range(arena_slots)
                for p in range(pages)
            ][:max(prefix_cache_pages, 1)]
            self.prefix_cache = RadixPrefixCache(
                arena_ids,
                page_size=page_size,
                tenant_quota=prefix_cache_tenant_quota,
            )
        # Global page table [num_slots, pages]: entry (s, j) is the
        # GLOBAL pool page id (slot * pages + page) logical page j of
        # lane s reads through. Identity (own pages) except where a
        # prefix splice retargets a lane's matched prefix onto shared
        # arena pages. Authoritative only when the prefix cache is on —
        # without it the pool's per-slot LOCAL identity table keeps the
        # PR-8 contract (and its no-alias tests) unchanged.
        self._gtable = self._identity_gtable()
        # Arena page ids each lane currently references (released with
        # the slot in release_slot -> refcounts drop, pages survive).
        self._leases: Dict[int, List[int]] = {}
        # In-flight dedup: chain keys each mid-prefill lane has claimed
        # as the harvester (release_slot must unclaim them if the lane
        # dies before its harvest, or followers park until their wait
        # budget expires).
        self._pending_claims: Dict[int, List[str]] = {}
        # Deferred harvest queue: (src global id, dst arena id) page
        # copies registered by _harvest but not yet executed on device.
        # flush_harvests() coalesces EVERYTHING queued into one jitted
        # bulk copy — the scheduler flushes once per tick, so N
        # admissions finishing in one tick cost one dispatch, not N
        # (ROADMAP item 2 harvest batching). Queued dst pages are
        # refcount-pinned; _arm_prefill flushes before any acquire so a
        # hit can never splice a page whose bytes have not landed.
        self._harvest_queue: List[Tuple[int, int]] = []
        # Arena dst pages whose harvest copy has NOT executed yet (a
        # superset of _harvest_queue's dst column, cleared only after
        # pool.caches actually carries the bytes). The page-export HTTP
        # path refuses these so a remote puller can never receive a
        # page whose copy is still queued or mid-flight.
        self._queued_dst: set = set()
        self.harvest_copy_calls = 0
        self.harvest_flushes = 0
        # Cross-replica page plane (ISSUE 20): the scheduler injects a
        # serving/page_share.PageShareClient here; start_prefill then
        # consults the fleet index for chains resident on another
        # replica and imports their pages before the local acquire.
        # _landed_keys accumulates chain keys whose BYTES are arena-
        # resident (flushed harvest or completed pull) — the scheduler
        # drains them into ownership reports; keys are never reported
        # while their copy is still queued.
        self.page_share = None
        self._landed_keys: List[str] = []
        self.remote_hits = 0
        self.remote_pull_failures = 0
        # The owning scheduler replaces both with its own (serving/
        # server.py): spans around each transfer / program call / device
        # read of a step, and the scheduler thread's phase ledger those
        # three switch. Standalone, both are off and cost a branch.
        self.tracer = SpanTracer(enabled=False)
        self.phases = ThreadPhaseLedger(
            SERVE_TICK_PHASES, "serve_tick_{cause}_seconds_total",
            enabled=False,
        )
        self._refresh_table()

    def _init_pool_caches(self):
        """A zeroed cache tree at the pool's geometry, paged layout.
        Built in ONE jitted call: eager, the flat tree and its paged
        reshape would be two pools live at once."""
        config = self.engine.config

        def init():
            return self._paged(
                self.engine.model.init_cache(
                    self.total_slots,
                    self.slot_tokens,
                    kv_cache_dtype=getattr(config, "kv_cache_dtype", None),
                    rolling=False,
                )
            )

        return jax.jit(init)()

    def _reset_lane_state(self) -> None:
        """Every lane idle at row 0; the device half (repetition counts,
        per-lane rng) is rewritten by the decode step, which donates it
        together with the pool."""
        S = self.num_slots
        # As of the last COLLECTED step (the host has read its tokens).
        self._tokens = np.zeros((S,), np.int32)
        self._pos = np.zeros((S,), np.int32)
        self._active = np.zeros((S,), bool)
        self._counts = jnp.zeros(
            (S, self.engine.config.vocab_size), jnp.int32
        )
        self._rngs = jax.random.split(jax.random.PRNGKey(0), S)
        # Steps dispatched and not yet collected, oldest first, each
        # with the lanes it stepped. The next step's token input is
        # the newest step's device output (`_nxt_dev`), except for
        # lanes whose token the host set since (`_host_tok`: a lane
        # _finish_prefill just activated). `_budget` is the number of
        # decode steps each lane's request still allows (max_new - 1 at
        # activation): with a step in flight it is how the host knows,
        # without reading that step, which lanes it ends.
        self._inflight: collections.deque = collections.deque()
        self._budget = np.zeros((S,), np.int32)
        self._host_tok = np.ones((S,), bool)
        self._nxt_dev = jnp.zeros((S,), jnp.int32)

    def _identity_gtable(self) -> np.ndarray:
        P = self.pool.pages
        return (
            np.arange(self.num_slots, dtype=np.int32)[:, None] * P
            + np.arange(P, dtype=np.int32)[None, :]
        )

    def recover_pool(self) -> bool:
        """Call after ANY exception out of a program that rewrites the
        pool (decode step, prefill chunk, slot insert, page copy): each
        donates `pool.caches`, so a call the runtime had already taken
        the buffers for leaves them deleted. Decided by what can be
        observed, not by the kind of error:

        - buffers alive (the call failed before the runtime took them:
          a bad argument, a Python error): nothing is touched, returns
          False, the same pool serves on and the caller fails only what
          the call was for;
        - buffers deleted: the pool is rebuilt zeroed at the same
          geometry (the old one is already freed, so memory allows it),
          every lane goes idle, the page tables return to identity and
          the prefix cache forgets every page with its pins, leases,
          pending claims and queued harvests (arena pages lived in the
          lost buffers: none may be spliced again). Returns True: every
          lane's KV is gone, so the caller must fail every request it
          had admitted and release their slots; slot allocation stays
          the caller's."""
        if self.pool.buffers_alive():
            return False
        self.pool.caches = self._init_pool_caches()
        self.pool.rebuilds += 1
        self.pool.lengths[:] = 0
        self._reset_lane_state()
        self._gtable = self._identity_gtable()
        self._leases.clear()
        self._pending_claims.clear()
        self._harvest_queue.clear()
        self._queued_dst.clear()
        self._landed_keys.clear()
        if self.prefix_cache is not None:
            self.prefix_cache.clear()
        self._refresh_table()
        return True

    def _refresh_table(self) -> None:
        """Device copy of the authoritative page table: the decoder's
        global table when the prefix cache is on (splices retarget it),
        the pool's local identity table otherwise (PR-8 contract)."""
        if self.prefix_cache is not None:
            self._table = jnp.asarray(self._gtable)
        else:
            self._table = jnp.asarray(self.pool.page_table_array())

    def _reset_gtable_row(self, slot: int) -> None:
        self._gtable[slot] = (
            slot * self.pool.pages
            + np.arange(self.pool.pages, dtype=np.int32)
        )

    # -- slot lifecycle ----------------------------------------------------
    def has_free_slot(self) -> bool:
        return self.pool.has_free()

    def acquire_slot(self) -> int:
        if self.prefix_cache is not None:
            # A queued harvest may source from a slot being recycled:
            # its pages must land in the arena before the new occupant
            # writes over them. (Before the alloc: a flush that loses
            # the pool raises, and must not leak a slot.)
            self.flush_harvests()
        slot = self.pool.alloc()
        if self.prefix_cache is not None:
            # Fresh occupants start from identity; a prefix splice
            # retargets entries AFTER acquire, never across realloc.
            self._reset_gtable_row(slot)
            self._refresh_table()
        return slot

    def release_slot(self, slot: int) -> None:
        self._active[slot] = False
        self._drop_ahead(slot)
        if self.prefix_cache is not None:
            # Refcounted release: the lane's spliced arena pages drop
            # their pin (they stay cached — shared pages survive lane
            # eviction) and the lane's table row tombstones back to
            # identity so a stale alias can never ride into the next
            # occupant.
            self.prefix_cache.release(self._leases.pop(slot, []))
            # A mid-prefill lane dying with unharvested pending claims
            # must unblock its followers (they re-check and go cold).
            claims = self._pending_claims.pop(slot, None)
            if claims:
                self.prefix_cache.release_pending(claims)
            self._reset_gtable_row(slot)
            self._refresh_table()
        self.pool.free(slot)

    def active_count(self) -> int:
        return int(self._active.sum())

    def lane_full(self, slot: int) -> bool:
        """Next decode row would overflow the slot's token budget."""
        return int(self._pos[slot]) >= self.token_capacity

    # -- jitted pieces -----------------------------------------------------
    def _flat(self, tree):
        from luminaai_tpu.inference.kv_pool import to_flat

        return to_flat(tree, self.pool.pages, self.pool.page_size)

    def _paged(self, tree):
        from luminaai_tpu.inference.kv_pool import to_paged

        return to_paged(tree, self.pool.pages, self.pool.page_size)

    def _get_prefill(self, bucket: int):
        key = ("prefill", bucket)
        if key not in self._fns:
            engine = self.engine
            # Page-aligned prefix, not the whole slot: the insert below
            # then moves O(prompt) rows per admission instead of
            # O(slot_tokens). Rows past the prefix keep the previous
            # occupant's stale K/V — safe, because every row is written
            # by its occupant before the per-lane mask first admits it.
            ps = self.pool.page_size
            capacity = min(-(-bucket // ps) * ps, self.slot_tokens)
            hint = self.engine._lane_hint()

            def prefill(params, ids, length):
                caches = engine.model.init_cache(
                    1,
                    capacity,
                    kv_cache_dtype=getattr(
                        engine.config, "kv_cache_dtype", None
                    ),
                    rolling=False,
                )
                pos = jnp.arange(bucket)
                positions = jnp.where(pos < length, pos, -1)[None, :]
                logits, caches, _ = engine.model.apply(
                    {"params": params},
                    ids,
                    positions=positions,
                    kv_caches=caches,
                    # [1]-shaped index selects the PER-LANE cache path:
                    # plain absolute rows even under attention_window
                    # (the pool never rolls).
                    cache_index=jnp.zeros((1,), jnp.int32),
                    deterministic=True,
                    lane_meta=hint,
                )
                last = jnp.take_along_axis(
                    logits, (length - 1)[None, None, None], axis=1
                )[:, 0, :]
                return last, caches

            self._fns[key] = jax.jit(prefill)
        return self._fns[key]

    def _get_insert(self):
        if "insert" not in self._fns:

            page_size = self.pool.page_size

            def insert(pool_caches, fresh, slot):
                def put(p, f):
                    # Page the fresh rows (a page-aligned PREFIX of the
                    # slot, not necessarily all of it), then land them at
                    # the slot axis — ndim-5 in paged layout, so the rule
                    # also covers scan_layers' extra leading segment axis.
                    fp = f.reshape(
                        f.shape[:-3]
                        + (f.shape[-3] // page_size, page_size)
                        + f.shape[-2:]
                    )
                    starts = [0] * p.ndim
                    starts[p.ndim - 5] = slot
                    return jax.lax.dynamic_update_slice(p, fp, tuple(starts))

                return jax.tree.map(put, pool_caches, fresh)

            self._fns["insert"] = jax.jit(insert, donate_argnums=(0,))
        return self._fns["insert"]

    def _active_extent(self, pos=None, live=None) -> int:
        """Resident-extent bound in ROWS for the ragged decode step: a
        power-of-two page count covering every active lane's rows
        (>= 1 page, <= the slot's pages). The step executable is
        specialized per extent — O(log pages) executables, the same
        ladder discipline as prompt buckets — and within one extent the
        kernel/length mask still skips per-lane. `pos` / `live`: the
        write rows and lanes of a step about to be dispatched (the
        host's prediction, an upper bound); default, the collected
        state."""
        if pos is None:
            pos, live = self._pos, self._active
        ps = self.pool.page_size
        need = int(pos[live].max()) + 1 if live.any() else 1
        pages_needed = -(-need // ps)
        p = 1
        while p < pages_needed:
            p *= 2
        return min(p, self.pool.pages) * ps

    def _get_step(self, sample_key, extent: Optional[int] = None):
        use_global = self.prefix_cache is not None
        key = ("step", sample_key, self.backend, extent, use_global)
        if key not in self._fns:
            temperature, top_k, top_p, rep_penalty = sample_key
            stop_ids = jnp.asarray(
                sorted(self.engine._stop_set), dtype=jnp.int32
            )
            S = self.num_slots
            backend = self.backend
            window = getattr(self.engine.config, "attention_window", None)
            page_size = self.pool.page_size

            def step(params, caches, prev_nxt, lanes, counts, rngs, table):
                # `lanes` is everything the host sends a step, one
                # [4, S] int32 transfer (_pack_lanes): the write row,
                # the lanes to step, and the token of each lane the host
                # set since the last step. Every other lane's token is
                # the previous step's output, which never left the
                # device.
                pos = lanes[0]
                active = lanes[1] != 0
                tokens = jnp.where(lanes[2] != 0, lanes[3], prev_nxt)
                flat = self._flat(caches)
                split2 = jax.vmap(lambda r: jax.random.split(r, 2))(rngs)
                new_rngs, step_rngs = split2[:, 0], split2[:, 1]
                from luminaai_tpu.ops.ragged_paged_attention import (
                    LaneMeta,
                )

                if backend == "dense":
                    meta = LaneMeta(lengths=None, backend="dense")
                else:
                    # lengths INCLUDE the row this step writes (pos);
                    # 0 marks lanes with nothing attendable (free or
                    # mid-chunked-prefill slots) whose output is garbage
                    # the host discards via `active`.
                    # With the prefix cache on, table entries are GLOBAL
                    # (slot, page) ids and the attention gather chases
                    # them across slots — a lane's matched prefix reads
                    # the shared arena pages in place (identity_pages
                    # must be off: the gather is real).
                    meta = LaneMeta(
                        lengths=jnp.where(active, pos + 1, 0).astype(
                            jnp.int32
                        ),
                        page_table=table,
                        window=window,
                        kind="decode",
                        page_size=page_size,
                        extent=extent,
                        backend=backend,
                        identity_pages=not use_global,
                        global_pages=use_global,
                    )
                logits, flat, _ = self.model.apply(
                    {"params": params},
                    tokens[:, None],
                    positions=pos[:, None],
                    kv_caches=flat,
                    cache_index=pos,  # [S]: per-lane offsets
                    deterministic=True,
                    lane_meta=meta,
                )
                nxt = jax.vmap(
                    lambda r, l, c: sample_token(
                        r, l, c,
                        temperature=temperature, top_k=top_k, top_p=top_p,
                        repetition_penalty=rep_penalty,
                    )
                )(step_rngs, logits[:, -1], counts).astype(jnp.int32)
                nxt = jnp.where(active, nxt, tokens)
                counts = counts.at[jnp.arange(S), nxt].add(
                    active.astype(counts.dtype)
                )
                eos = jnp.logical_and(
                    active,
                    jnp.any(nxt[:, None] == stop_ids[None, :], axis=1),
                )
                return self._paged(flat), nxt, eos, counts, new_rngs

            # Everything the step rewrites is donated (the pool, the
            # repetition counts, the lane rngs): the one-row scatter
            # lands in place instead of in a copy of the pool. A call
            # that fails after the runtime took the buffers leaves them
            # deleted; recover_pool() is what the caller does about it.
            # (Not the previous tokens: the host may not have read them
            # yet.)
            self._fns[key] = jax.jit(step, donate_argnums=(1, 4, 5))
        return self._fns[key]

    # -- scheduler-facing API ----------------------------------------------
    def prefill_into_slot(
        self,
        slot: int,
        prompt_tokens: Sequence[int],
        max_new_tokens: int = 1,
        sample_key: Optional[Tuple] = None,
        seed: Optional[int] = None,
    ) -> Dict[str, Any]:
        """Write a request's prompt KV into pool slot `slot` and sample
        its first token. Returns {"token": int | None, "prompt_tokens",
        "is_stop"}; the lane is activated unless the first token already
        stopped (or the budget is a single token)."""
        sample_key = sample_key or GREEDY_SAMPLE_KEY
        max_new = max(1, int(max_new_tokens))
        if not list(prompt_tokens):
            raise ValueError("prefill_into_slot needs a non-empty prompt")
        # generate()'s own trim against the slot's budget — one shared
        # formula, so the two paths stay token-identical even for
        # over-length prompts.
        prompt = self.engine._trim_prompt(
            prompt_tokens, max_new, capacity=self.token_capacity
        )
        L = len(prompt)
        bucket = min(_bucket_len(L), self.slot_tokens)
        ids = np.zeros((1, bucket), dtype=np.int32)
        ids[0, :L] = prompt
        span, region = self.tracer.span, self.phases.region
        with region("put"), span("prefill.put"):
            ids_d = jnp.asarray(ids)
            len_d = jnp.asarray(L, jnp.int32)
            slot_d = jnp.asarray(slot, jnp.int32)
        with region("dispatch"), span("prefill.dispatch"):
            logits, fresh = self._get_prefill(bucket)(
                self.params, ids_d, len_d
            )
            self.pool.caches = self._get_insert()(
                self.pool.caches, fresh, slot_d
            )
        with region("put"), span("prefill.put"):
            self._refresh_table()  # the page table, host to device
        return self._finish_prefill(slot, logits, L, max_new, sample_key,
                                    seed)

    def _finish_prefill(self, slot, logits, L, max_new, sample_key, seed):
        """Shared prompt-KV-written → lane-activated tail: sample token
        #1, set the host lane state, return prefill_into_slot's info
        contract. Used by the whole-prompt path above and by the final
        chunk of a chunked prefill."""
        with self.tracer.span("prefill.sample", slot=slot):
            rng = jax.random.PRNGKey(
                seed if seed is not None else (time.time_ns() & 0xFFFFFFFF)
            )
            rng, first_rng = jax.random.split(rng)
            # int() blocks until the prefill program has produced the
            # logits: the device wait of an admission.
            with self.phases.region("device_wait"):
                first = int(
                    sample_token(
                        first_rng,
                        logits[0],
                        jnp.zeros((logits.shape[-1],), jnp.int32),
                        temperature=sample_key[0], top_k=sample_key[1],
                        top_p=sample_key[2],
                        repetition_penalty=sample_key[3],
                    )
                )
            is_stop = first in self.engine._stop_set
            self.pool.lengths[slot] = L
            self._tokens[slot] = first
            self._host_tok[slot] = True
            self._pos[slot] = L
            self._active[slot] = (not is_stop) and max_new > 1
            self._budget[slot] = max_new - 1
            self._counts = self._counts.at[slot].set(0)
            if not is_stop:
                self._counts = self._counts.at[slot, first].add(1)
            self._rngs = self._rngs.at[slot].set(rng)
        return {
            "token": None if is_stop else first,
            "prompt_tokens": L,
            "is_stop": is_stop,
        }

    # -- chunked prefill (scheduler-interleaved admission) -----------------
    def _get_chunk_prefill(self):
        """One fixed-shape prefill step writing `prefill_chunk` rows of
        one lane DIRECTLY into the pool slot (no fresh-cache + insert):
        slice the lane off the slot axis, run the per-lane multi-row
        path at absolute positions, land the updated lane back. ONE
        executable for every prompt length; the scheduler interleaves
        these calls with decode steps so a long admission stalls the
        decode batch for at most ~one chunk's step time."""
        key = "chunk_prefill"
        if key not in self._fns:
            engine = self.engine
            chunk = self.prefill_chunk
            hint = engine._lane_hint()

            def chunk_fn(params, pool_caches, ids, slot, start, length):
                def lane_of(p):
                    return jax.lax.dynamic_slice_in_dim(
                        p, slot, 1, axis=p.ndim - 5
                    )

                lane = jax.tree.map(lane_of, pool_caches)
                flat = self._flat(lane)
                pos = start + jnp.arange(chunk)
                positions = jnp.where(pos < length, pos, -1)[None, :]
                logits, flat, _ = engine.model.apply(
                    {"params": params},
                    ids,
                    positions=positions,
                    kv_caches=flat,
                    # [1]-shaped start offset selects the per-lane
                    # multi-row path: rows land at absolute positions,
                    # -1-marked padding drops into the dummy row.
                    cache_index=jnp.reshape(start, (1,)),
                    deterministic=True,
                    lane_meta=hint,
                )
                last_idx = jnp.clip(length - 1 - start, 0, chunk - 1)
                last = jnp.take_along_axis(
                    logits, last_idx[None, None, None], axis=1
                )[:, 0, :]
                paged_lane = self._paged(flat)

                def put(p, fresh):
                    starts = [0] * p.ndim
                    starts[p.ndim - 5] = slot
                    return jax.lax.dynamic_update_slice(
                        p, fresh, tuple(starts)
                    )

                return last, jax.tree.map(put, pool_caches, paged_lane)

            # The pool is donated (as in the decode step): the lane
            # lands back in place.
            self._fns[key] = jax.jit(chunk_fn, donate_argnums=(1,))
        return self._fns[key]

    def start_prefill(
        self,
        slot: int,
        prompt_tokens: Sequence[int],
        max_new_tokens: int = 1,
        sample_key: Optional[Tuple] = None,
        seed: Optional[int] = None,
        tenant: str = "anon",
    ) -> Optional[Dict[str, Any]]:
        """Begin a CHUNKED prefill into `slot`. Returns a host-side
        state dict for advance_prefill, or None when chunking is
        disabled (callers fall back to prefill_into_slot). The lane
        stays inactive until the final chunk activates it.

        With the prefix cache on, the longest cached page chain for this
        prompt is PINNED and spliced into the lane's global page table
        here — chunked prefill then runs only over the uncached suffix,
        so a cached 1000-token system prompt costs zero prefill FLOPs.
        At least one row is always recomputed (the last prompt row must
        produce logits to sample token #1), so a fully-cached prompt
        still runs one chunk.

        In-flight dedup (ROADMAP item 2): when this prompt's first
        non-resident page is ALREADY being computed by another live
        admission, the lane parks in a `waiting` state instead of
        re-running the same prefill cold — advance_prefill re-checks
        each tick and resolves to a genuine HIT once the leader's
        harvest lands (or goes cold if the leader dies). Concurrent
        identical prefixes before the first harvest thus share one
        pending-insert entry instead of all missing."""
        if not self.prefill_chunk:
            return None
        sample_key = sample_key or GREEDY_SAMPLE_KEY
        max_new = max(1, int(max_new_tokens))
        if not list(prompt_tokens):
            raise ValueError("start_prefill needs a non-empty prompt")
        prompt = self.engine._trim_prompt(
            prompt_tokens, max_new, capacity=self.token_capacity
        )
        L = len(prompt)
        chunk = self.prefill_chunk
        ps = self.pool.page_size
        st: Dict[str, Any] = {
            "slot": slot, "length": L, "chunk": chunk, "next": 0,
            "n_chunks": 0, "sample_key": sample_key, "seed": seed,
            "max_new": max_new, "prompt": prompt, "tenant": tenant,
            "start_rows": 0, "p0": 0,
        }
        if self.prefix_cache is not None:
            from luminaai_tpu.inference.prefix_cache import page_chain_keys

            # One chained hash of the prompt per admission, shared by
            # the peek and the pin below. The peek counts NOTHING: short
            # cold prompts fall back to the monolithic path, and a miss
            # booked for an admission the cache never served would make
            # cache.stats() disagree with serve_prefix_cache_misses_total.
            chain = page_chain_keys(
                prompt, self.pool.page_size, (L - 1) // ps
            )
            st["chain"] = chain
            peek_keys, _ = self.prefix_cache.lookup(prompt, keys=chain)
            if len(peek_keys) < len(chain) and (
                self.prefix_cache.has_pending_prefix(chain)
            ):
                # Park behind the in-flight leader. Neither hit nor
                # miss is booked yet — resolution does the acquire.
                self.prefix_cache.note_dedup_wait()
                st["waiting"] = True
                st["wait_ticks"] = 0
                self._park_lane(slot, 0)
                return st
            if len(peek_keys) < len(chain) and self.page_share is not None:
                # Cold (or partially cold) chain: ask the fleet index
                # whether another replica already computed these pages
                # and import them BEFORE the acquire below — a
                # successful pull turns this admission into a genuine
                # local hit; any failure leaves it exactly a miss.
                if self._try_remote_pull(slot, prompt, chain,
                                         len(peek_keys), st):
                    peek_keys, _ = self.prefix_cache.lookup(
                        prompt, keys=chain
                    )
            if L <= chunk and not peek_keys:
                return None
        elif L <= chunk:
            # A one-chunk prompt can't stall anyone longer than a chunk
            # anyway, and the bucketed prefill_into_slot path moves only
            # a page-aligned prompt prefix where a chunk call round-trips
            # the whole lane — cheaper AND the stall bound still holds.
            # (Prefix HITS always take the chunked path: the splice +
            # suffix-only prefill only exists here.)
            return None
        self._arm_prefill(st)
        return st

    def _try_remote_pull(
        self,
        slot: int,
        prompt: Sequence[int],
        chain: List[str],
        have: int,
        st: Dict[str, Any],
    ) -> int:
        """Pull this chain's non-resident pages from their fleet owner
        into the local arena (ISSUE 20 remote-hit admission). Returns
        pages imported; 0 means "proceed as the plain miss you were".

        Sequence: fleet lookup → pull-slot acquire (bounded, non-
        blocking) → pending-claim the keys (concurrent same-chain
        admissions park exactly like behind a local harvest, so N
        arrivals cost ONE pull) → register arena assignments via the
        normal insert() path → fetch + import each page IN CHAIN ORDER
        under one transfer deadline. The import is synchronous inside
        the admission (single scheduler worker), so no other acquire
        can splice a page whose bytes have not landed. On a mid-chain
        failure the already-imported prefix stays (a valid shorter
        chain); the unwritten tail is released + forgotten, mirroring
        the flush_harvests failure unwind — transfer failure is never
        worse than a cache miss."""
        client = self.page_share
        cache = self.prefix_cache
        ps = self.pool.page_size
        try:
            owner, owned = client.lookup(chain, have=have)
        except Exception:  # a sick router must never block admission
            logger.debug("page-share lookup failed", exc_info=True)
            return 0
        if owner is None or len(owned) <= have:
            return 0
        if not client.try_begin_pull():
            return 0
        deadline = time.monotonic() + client.timeout_s
        claimed = cache.claim_pending(owned, owner=slot)
        imported: List[int] = []
        imported_keys: List[str] = []
        nbytes = 0
        failed = False
        try:
            assignments = cache.insert(
                list(prompt[: len(owned) * ps]), from_page=have,
                tenant=st.get("tenant", "anon"),
            )
            if not assignments:
                return 0
            cache.pin_pages([pid for _, pid in assignments])
            try:
                for j, pid in assignments:
                    remaining = deadline - time.monotonic()
                    if remaining <= 0:
                        raise OSError("page pull deadline exceeded")
                    payload = client.fetch_page(
                        owner, chain[j], timeout_s=remaining
                    )
                    nbytes += self.pool.import_page(pid, payload)
                    imported.append(pid)
                    imported_keys.append(chain[j])
            except Exception as e:
                failed = True
                self.remote_pull_failures += 1
                logger.warning(
                    "page pull from %s failed after %d/%d page(s): %s",
                    owner, len(imported), len(assignments), e,
                )
                tail = [
                    pid for _, pid in assignments if pid not in imported
                ]
                cache.release(tail)
                cache.forget(tail)
            cache.release(imported)
            if imported:
                # The pulled pages are arena-resident here too now:
                # advertise ownership so the NEXT replica can pull from
                # whichever owner is closer/live.
                self._landed_keys.extend(imported_keys)
            st["remote"] = {
                "owner": owner,
                "pulled": len(imported),
                "tokens": len(imported) * ps,
                "bytes": nbytes,
                "failed": failed,
            }
            if imported:
                self.remote_hits += 1
            return len(imported)
        finally:
            cache.release_pending(claimed)
            client.end_pull()

    def _park_lane(self, slot: int, rows: int) -> None:
        """Interleaved decode steps still write one (garbage) row at
        _pos for every lane, active or not; park the mid-prefill
        lane's write row at the slot's LAST row — admission bounds
        prompts to token_capacity - 1, so no chunk writes it, and a
        lane that eventually decodes there overwrites it before its
        mask first admits it. (The last row is always a PRIVATE page:
        splices cover at most (L-1)//ps full pages.)"""
        self._pos[slot] = self.slot_tokens - 1
        self._active[slot] = False
        self.pool.lengths[slot] = rows

    def _arm_prefill(self, st: Dict[str, Any]) -> None:
        """Resolve a prefill state into a runnable one: pin + splice the
        cached prefix (books the hit/miss), claim the non-resident tail
        for this lane's harvest (in-flight dedup), size the chunk ids
        buffer, park the lane. Shared by the immediate start_prefill
        path and advance_prefill's waiting-state resolution."""
        slot, prompt, L = st["slot"], st["prompt"], st["length"]
        chunk = st["chunk"]
        hit_ids: List[int] = []
        hit_rows = 0
        if self.prefix_cache is not None:
            # Any queued harvest must land before this admission can
            # acquire: a hit on a freshly-inserted page whose copy has
            # not flushed would splice unwritten arena K/V.
            self.flush_harvests()
            chain = st["chain"]
            # Pin before splicing: an acquired page cannot be evicted
            # until release_slot drops the lease. (Counts the hit/miss.)
            hit_ids, hit_rows = self.prefix_cache.acquire(
                prompt, keys=chain
            )
            st["pending_keys"] = self.prefix_cache.claim_pending(
                chain, owner=slot
            )
            if st["pending_keys"]:
                self._pending_claims[slot] = st["pending_keys"]
        n = -(-(L - hit_rows) // chunk)
        ids = np.zeros((1, hit_rows + n * chunk), np.int32)
        ids[0, :L] = prompt
        if hit_ids:
            self._leases[slot] = list(hit_ids)
            self._gtable[slot, :len(hit_ids)] = np.asarray(
                hit_ids, np.int32
            )
            self._refresh_table()
        self._park_lane(slot, hit_rows)
        if self.prefix_cache is None:
            self._refresh_table()
        st.update(
            ids=ids, n_chunks=n, start_rows=hit_rows, p0=len(hit_ids)
        )
        st.pop("waiting", None)

    def advance_prefill(
        self, st: Dict[str, Any]
    ) -> Optional[Dict[str, Any]]:
        """Run ONE prefill chunk (one jit call). Returns None while
        chunks remain; the final chunk samples token #1, activates the
        lane, and returns prefill_into_slot's info dict (plus a
        `prefix` block when the cache is on: hit/harvest accounting for
        the scheduler's counters and prefix_hit events).

        Chunks start at `start_rows` (the spliced prefix extent, 0 when
        cold) — the suffix-only prefill that turns a prefix hit into
        skipped FLOPs.

        A `waiting` state (in-flight dedup, see start_prefill) burns a
        tick re-checking the leader instead of computing: once the
        leader's harvest lands the acquire books a real HIT and the
        suffix-only prefill runs; if the leader dies (release_pending
        in release_slot) or the wait budget expires, the lane proceeds
        cold. Either way no chunk FLOPs are spent while parked."""
        if st.get("waiting"):
            st["wait_ticks"] += 1
            cache = self.prefix_cache
            if (
                cache is not None
                and cache.has_pending_prefix(st["chain"])
                and st["wait_ticks"] < self.DEDUP_WAIT_TICKS
            ):
                return None
            self._arm_prefill(st)
            # Fall through: this tick runs the first real chunk.
        c = st["next"]
        chunk = st["chunk"]
        slot = st["slot"]
        base = int(st.get("start_rows", 0))
        start = base + c * chunk
        cached = self.prefix_cache is not None
        fn = (self._get_chunk_prefill_cached() if cached
              else self._get_chunk_prefill())
        with self.phases.region("put"), self.tracer.span("prefill.put"):
            args = [
                jnp.asarray(st["ids"][:, start:start + chunk]),
                jnp.asarray(slot, jnp.int32),
            ]
            if cached:
                args += [
                    jnp.asarray(self._gtable[slot]),
                    jnp.asarray(int(st.get("p0", 0)), jnp.int32),
                ]
            args += [
                jnp.asarray(start, jnp.int32),
                jnp.asarray(st["length"], jnp.int32),
            ]
        with self.phases.region("dispatch"), \
                self.tracer.span("prefill.dispatch"):
            logits, caches = fn(self.params, self.pool.caches, *args)
            # Dropped while the chunk runs, as call-site temporaries
            # would be: the runtime then frees them behind the program,
            # not on this thread after the first-token sync.
            del args
        self.pool.caches = caches
        st["next"] = c + 1
        if st["next"] < st["n_chunks"]:
            # Residency telemetry tracks rows as they land; the lane
            # itself stays inactive until the final chunk.
            self.pool.lengths[slot] = min(
                base + (c + 1) * chunk, st["length"]
            )
            return None
        info = self._finish_prefill(
            slot, logits, st["length"], st["max_new"],
            st["sample_key"], st["seed"],
        )
        if self.prefix_cache is not None:
            harvested = self._harvest(slot, st)
            # Harvest landed (or failed and was unwound): release this
            # lane's pending claims so parked followers resolve — to a
            # hit in the first case, cold in the second.
            claims = self._pending_claims.pop(slot, None)
            if claims:
                self.prefix_cache.release_pending(claims)
            info["prefix"] = {
                "hit_pages": int(st.get("p0", 0)),
                "tokens_saved": base,
                "pages_harvested": harvested,
                "tenant": st.get("tenant", "anon"),
                "dedup_wait_ticks": int(st.get("wait_ticks", 0)),
                # Cross-replica pull accounting (None for purely local
                # admissions): the scheduler books remote-hit counters
                # and prefix_remote_hit events from this.
                "remote": st.get("remote"),
            }
        return info

    def _harvest(self, slot: int, st: Dict[str, Any]) -> int:
        """Register this prompt's freshly-computed full pages in the
        prefix cache and QUEUE their K/V copy from the lane's slot into
        the arena (the one-time cost future admissions amortize away).
        The device copy itself is deferred to flush_harvests() so every
        harvest landing in one scheduler tick rides ONE jitted bulk
        copy instead of one dispatch per admission. Queued dst pages
        are pinned (a later insert's eviction pressure cannot reassign
        them mid-queue). Returns the number of pages queued."""
        assignments = self.prefix_cache.insert(
            st["prompt"], from_page=int(st.get("p0", 0)),
            tenant=st.get("tenant", "anon"),
        )
        if not assignments:
            return 0
        P = self.pool.pages
        self.prefix_cache.pin_pages([pid for _, pid in assignments])
        self._queued_dst.update(pid for _, pid in assignments)
        self._harvest_queue.extend(
            (slot * P + j, pid) for j, pid in assignments
        )
        return len(assignments)

    def harvests_pending(self) -> bool:
        return bool(self._harvest_queue)

    def flush_harvests(self) -> int:
        """Execute every queued harvest as ONE jitted bulk page copy
        (pow2-padded pair count, same executable ladder as before).
        Called by the scheduler once per tick, and defensively before
        any cache acquire / slot realloc (see _harvest). Returns pages
        flushed; on copy failure the queued inserts are forgotten so
        the index never points at unwritten arena pages, and the error
        is re-raised only if the failed call took the donated pool with
        it (the lanes' KV is then gone too: recover_pool())."""
        if not self._harvest_queue:
            return 0
        pairs, self._harvest_queue = self._harvest_queue, []
        src = [s for s, _ in pairs]
        dst = [d for _, d in pairs]
        self.harvest_flushes += 1
        K = 1
        while K < len(src):
            K *= 2
        # Pad with self-copies (page 0 -> page 0): bit-identical writes,
        # so the pow2 executable ladder stays O(log pages).
        src += [0] * (K - len(src))
        dst += [0] * (K - len(dst))
        try:
            self.harvest_copy_calls += 1
            self.pool.caches = self._get_copy_pages(K)(
                self.pool.caches,
                jnp.asarray(src, jnp.int32),
                jnp.asarray(dst, jnp.int32),
            )
        except Exception:
            # The index must never point at arena pages that were not
            # actually written — a later hit would splice uninitialized
            # K/V. Unwind and keep serving: harvest is an optimization,
            # the lanes' own prefills already succeeded.
            logger.exception(
                "prefix-cache harvest copy failed; unwinding %d page(s)",
                len(pairs),
            )
            self.prefix_cache.release([d for _, d in pairs])
            self.prefix_cache.forget([d for _, d in pairs])
            self._queued_dst.difference_update(d for _, d in pairs)
            if not self.pool.buffers_alive():
                raise
            return 0
        self.prefix_cache.release([d for _, d in pairs])
        # Bytes are on device as of the (synchronous) copy above —
        # only now may the export path serve these pages.
        self._queued_dst.difference_update(d for _, d in pairs)
        if self.page_share is not None:
            # Bytes are arena-resident as of this flush: these keys are
            # now safely servable to pullers, so queue the ownership
            # report (the scheduler drains after its flush call).
            self._landed_keys.extend(
                self.prefix_cache.keys_for_pages([d for _, d in pairs])
            )
        return len(pairs)

    def drain_landed_keys(self) -> List[str]:
        """Chain keys whose page bytes became arena-resident since the
        last drain (harvest flushes + completed remote pulls). The
        scheduler reports them to the router's fleet index."""
        out, self._landed_keys = self._landed_keys, []
        return out

    def _get_copy_pages(self, K: int):
        """Jitted bulk page copy: K (src, dst) GLOBAL page id pairs moved
        inside the paged pool in one call (harvest: lane pages -> arena).
        One executable per pow2 K."""
        key = ("copy_pages", K)
        if key not in self._fns:
            P = self.pool.pages

            def copy(caches, src, dst):
                def body(i, caches):
                    s, d = src[i], dst[i]

                    def cp(leaf):
                        nd = leaf.ndim
                        sizes = list(leaf.shape)
                        sizes[nd - 5] = 1
                        sizes[nd - 4] = 1
                        starts = [jnp.asarray(0, jnp.int32)] * nd
                        starts[nd - 5] = s // P
                        starts[nd - 4] = s % P
                        page = jax.lax.dynamic_slice(
                            leaf, tuple(starts), tuple(sizes)
                        )
                        starts[nd - 5] = d // P
                        starts[nd - 4] = d % P
                        return jax.lax.dynamic_update_slice(
                            leaf, page, tuple(starts)
                        )

                    return jax.tree.map(cp, caches)

                return jax.lax.fori_loop(0, K, body, caches)

            self._fns[key] = jax.jit(copy, donate_argnums=(0,))
        return self._fns[key]

    def _get_chunk_prefill_cached(self):
        """Prefix-cache-aware chunk prefill: the lane's LOGICAL cache
        view is gathered through its global page table (spliced arena
        pages read in place), the chunk runs the identical per-lane
        multi-row path the legacy executable runs, and the updated view
        is blended back so only PRIVATE pages (>= p0) land in the lane's
        own storage — shared prefix bytes are never copied into the
        slot. ONE executable serves cold (identity table, p0 = 0) and
        hit admissions alike."""
        key = "chunk_prefill_cached"
        if key not in self._fns:
            engine = self.engine
            chunk = self.prefill_chunk
            hint = engine._lane_hint()
            P = self.pool.pages
            ps = self.pool.page_size

            def chunk_fn(params, pool_caches, ids, slot, table_row, p0,
                         start, length):
                def view_of(leaf):
                    nd = leaf.ndim
                    lead = leaf.shape[:nd - 5]
                    T_ = leaf.shape[nd - 5]
                    flat = leaf.reshape(
                        lead + (T_ * P,) + leaf.shape[nd - 3:]
                    )
                    view = jnp.take(flat, table_row, axis=nd - 5)
                    return view.reshape(
                        lead + (1, P * ps) + leaf.shape[nd - 2:]
                    )

                lane = jax.tree.map(view_of, pool_caches)
                pos = start + jnp.arange(chunk)
                positions = jnp.where(pos < length, pos, -1)[None, :]
                logits, lane, _ = engine.model.apply(
                    {"params": params},
                    ids,
                    positions=positions,
                    kv_caches=lane,
                    cache_index=jnp.reshape(start, (1,)),
                    deterministic=True,
                    lane_meta=hint,
                )
                last_idx = jnp.clip(length - 1 - start, 0, chunk - 1)
                last = jnp.take_along_axis(
                    logits, last_idx[None, None, None], axis=1
                )[:, 0, :]
                # Private pages only: the where keeps shared (< p0)
                # pages' slots holding whatever the lane already had, so
                # cached bytes never duplicate into lane storage and the
                # arena pages stay the single physical copy.
                keep = (jnp.arange(P) >= p0).reshape(1, P, 1, 1, 1)

                def put(p, new_flat):
                    nd = p.ndim
                    lead = p.shape[:nd - 5]
                    paged = new_flat.reshape(
                        lead + (1, P) + p.shape[nd - 3:]
                    )
                    own = jax.lax.dynamic_slice_in_dim(
                        p, slot, 1, axis=nd - 5
                    )
                    merged = jnp.where(keep, paged, own)
                    starts = [0] * nd
                    starts[nd - 5] = slot
                    return jax.lax.dynamic_update_slice(
                        p, merged, tuple(starts)
                    )

                return last, jax.tree.map(put, pool_caches, lane)

            self._fns[key] = jax.jit(chunk_fn, donate_argnums=(1,))
        return self._fns[key]

    def _drop_ahead(self, slot: int) -> None:
        """The lane ended (a stop token, a release) with steps in flight
        that step it: the host drops their tokens. Each wrote one KV row
        into the lane's OWN slot, past every row attended so far, and
        bumped its own `counts` / `rngs` rows; the slot's next admission
        resets all three, and its programs queue behind those steps."""
        for step in self._inflight:
            if step["stepped"][slot]:
                step["stepped"][slot] = False
                self.lane_steps_dropped += 1

    def _steps_ahead(self) -> np.ndarray:
        """Per lane, how many of the steps in flight step it."""
        ahead = np.zeros((self.num_slots,), np.int32)
        for step in self._inflight:
            ahead += step["stepped"]
        return ahead

    def _pack_lanes(self) -> Tuple[np.ndarray, np.ndarray]:
        """What the next step would be called with, right now: the one
        [4, S] int32 array the host sends (write row, stepped, token
        from the host, that token) and the lanes it steps.

        With steps in flight the rows and the lanes are the host's
        PREDICTION of the state behind them: a lane stepped by k of
        them writes k rows further, and is left out once those k steps
        use up its request's budget or its slot's rows (the scheduler
        ends such a lane when it collects the step, `max_new` /
        `lane_full`). What cannot be known before a step is read is a
        stop token: that lane is stepped once more and _drop_ahead
        drops the token. A lane is never stepped at a row past
        `token_capacity`, in flight or not."""
        ahead = self._steps_ahead()
        pos = self._pos + ahead
        live = self._active & (pos < self.token_capacity) & (
            (ahead == 0) | (ahead < self._budget)
        )
        lanes = np.empty((4, self.num_slots), np.int32)
        lanes[0] = pos
        lanes[1] = live
        lanes[2] = self._host_tok
        lanes[3] = self._tokens
        return lanes, live

    def _next_step(self, sample_key: Optional[Tuple]):
        """(step function, packed lanes, lanes stepped) of the step
        that would be dispatched right now."""
        lanes, live = self._pack_lanes()
        extent = (
            self._active_extent(lanes[0], live)
            if self.backend != "dense" else None
        )
        fn = self._get_step(sample_key or GREEDY_SAMPLE_KEY, extent)
        return fn, lanes, live

    def step_fn_and_args(
        self, sample_key: Optional[Tuple] = None
    ) -> Tuple[Any, Tuple]:
        """The jitted decode-step function and the argument tuple
        dispatch_step would call it with right now. Exposed so
        monitoring/attribution.py can AOT-lower the decode executable for
        compiled-cost accounting without executing a step (bench
        extras.ragged_attention compares the dense and ragged backends'
        compiled bytes through exactly this handle). For LOWERING only:
        the function donates the pool, the counts and the rngs, so a
        caller that RUNS it must rebind all three from the result as
        dispatch_step does, or the decoder is left holding deleted
        buffers."""
        fn, lanes, _ = self._next_step(sample_key)
        args = (
            self.params,
            self.pool.caches,
            self._nxt_dev,
            jax.device_put(lanes),
            self._counts,
            self._rngs,
            self._table,
        )
        return fn, args

    @property
    def steps_in_flight(self) -> int:
        return len(self._inflight)

    def dispatch_step(self, sample_key: Optional[Tuple] = None) -> bool:
        """Enqueue one decode step on the device and return at once;
        collect_step() reads it. Called with the previous step still in
        flight (the scheduler's steady state) it steps the lanes that
        step cannot end (_pack_lanes), and returns False, enqueueing
        nothing, when there is none."""
        fn, lanes, live = self._next_step(sample_key)
        if self._inflight and not live.any():
            return False
        span, region = self.tracer.span, self.phases.region
        with region("put"), span("decode.put"):
            lanes_d = jax.device_put(lanes)
        with region("dispatch"), span("decode.dispatch"):
            caches, nxt, eos, counts, rngs = fn(
                self.params, self.pool.caches, self._nxt_dev, lanes_d,
                self._counts, self._rngs, self._table,
            )
        self.pool.caches = caches
        self._counts = counts
        self._rngs = rngs
        self._nxt_dev = nxt
        # The copies to the host start behind the step, not at the read.
        nxt.copy_to_host_async()
        eos.copy_to_host_async()
        self._host_tok[:] = False
        self._inflight.append({"nxt": nxt, "eos": eos, "stepped": live})
        return True

    def collect_step(self) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Read the oldest step in flight (blocks until the device has
        run it) and do its host bookkeeping. Returns decode_step's
        (tokens[S], produced[S], eos[S]), for the lanes that step
        stepped and that have not been released since."""
        step = self._inflight.popleft()
        with self.phases.region("device_wait"), \
                self.tracer.span("decode.fetch"):
            nxt_h = np.asarray(step["nxt"])
            eos_h = np.asarray(step["eos"])
        stepped = step["stepped"]
        eos_h = eos_h & stepped
        self._tokens[stepped] = nxt_h[stepped]
        self._pos[stepped] += 1
        self.pool.lengths[stepped] += 1
        self._budget[stepped] -= 1
        self._active &= ~eos_h
        for slot in np.flatnonzero(eos_h):
            self._drop_ahead(int(slot))
        self.steps += 1
        return nxt_h, stepped & ~eos_h, eos_h

    def abandon_steps(self) -> None:
        """Forget every step in flight: nothing of them is read. For
        the caller whose dispatch or collect raised; it must release
        every lane they stepped (the scheduler fails them all)."""
        for step in self._inflight:
            self.lane_steps_dropped += int(step["stepped"].sum())
        self._inflight.clear()

    def decode_step(
        self, sample_key: Optional[Tuple] = None
    ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Advance every active lane one token (one jit call). Returns
        (tokens[S], produced[S], eos[S]): `produced` lanes emitted
        tokens[slot] this step; `eos` lanes hit a stop token (dropped,
        matching generate()) and were deactivated — the scheduler frees
        their slots. dispatch_step() + collect_step() with nothing else
        in flight: the serial form, for callers that own their loop."""
        self.dispatch_step(sample_key)
        return self.collect_step()


def _per_layer_view(params: Dict[str, Any]) -> Tuple[Dict[str, Any], bool]:
    """Flatten a scanned ('scan_{s}/block_{j}', leading scan axis) param
    tree into the per-layer 'layer_{i}' view. Layer order is recoverable
    without a Config: segments are numbered in stack order and each one is
    `count` repetitions of its block_0..block_{u-1} unit."""
    scan_keys = [k for k in params if k.startswith("scan_")]
    if not scan_keys:
        return params, False
    out = {k: v for k, v in params.items() if not k.startswith("scan_")}
    idx = 0
    for sk in sorted(scan_keys, key=lambda k: int(k.split("_")[1])):
        seg = params[sk]
        blocks = sorted(seg.keys(), key=lambda k: int(k.split("_")[1]))
        count = jax.tree.leaves(seg[blocks[0]])[0].shape[0]
        for rep in range(count):
            for b in blocks:
                out[f"layer_{idx}"] = jax.tree.map(
                    lambda x, rep=rep: x[rep], seg[b]
                )
                idx += 1
    return out, True


def infer_config_from_params(params: Dict[str, Any]) -> Config:
    """Reconstruct an architecture Config from a param tree, in either the
    per-layer or the scanned layout (ref Chat.py:219
    infer_config_from_state_dict)."""
    params, was_scanned = _per_layer_view(params)
    emb = params["embedder"]["embedding"]
    vocab, hidden = emb.shape
    layers = sorted(
        int(k.split("_")[1]) for k in params if k.startswith("layer_")
    )
    l0 = params["layer_0"]
    wq = l0["attention"]["wq"]  # [H, n_heads, head_dim]
    n_heads = wq.shape[1]
    n_kv = l0["attention"]["wk"].shape[1]
    use_moe = any("moe" in params[f"layer_{i}"] for i in layers)
    kw: Dict[str, Any] = dict(
        vocab_size=vocab,
        hidden_size=hidden,
        num_layers=len(layers),
        num_heads=n_heads,
        num_kv_heads=n_kv,
        use_moe=use_moe,
        # Untied checkpoints carry a separate output head; missing this
        # would silently decode with the input embeddings.
        tie_word_embeddings="lm_head" not in params["embedder"],
    )
    if use_moe:
        moe_layers = [i for i in layers if "moe" in params[f"layer_{i}"]]
        moe = params[f"layer_{moe_layers[0]}"]["moe"]
        kw["num_experts"] = moe["router"].shape[-1]
        kw["intermediate_size"] = moe["wo"].shape[1]
        if len(moe_layers) == len(layers):
            kw["moe_pattern"] = "all"
        elif all(i % 3 == 2 for i in moe_layers):
            kw["moe_pattern"] = "every_3rd"
        elif all(i % 4 == 3 for i in moe_layers):
            kw["moe_pattern"] = "every_4th"
        elif moe_layers == list(
            range(moe_layers[0], moe_layers[0] + len(moe_layers))
        ):
            kw["moe_pattern"] = "sandwich"
            kw["dense_start_layers"] = moe_layers[0]
            kw["dense_end_layers"] = len(layers) - 1 - moe_layers[-1]
    else:
        ffn = l0.get("ffn") or l0.get("mod_ffn")
        if ffn is not None and "wi" in ffn:
            kw["intermediate_size"] = ffn["wi"].shape[-1] // 2
    if was_scanned:
        kw["scan_layers"] = True
    return Config(**kw)
