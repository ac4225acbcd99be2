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


class UnservedMixerError(NotImplementedError):
    """The Config names a token mixer that only training runs."""


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
        for cfg in (self.config, model.config):
            if cfg.unserved_mixers():
                raise UnservedMixerError(
                    "this model cannot be served yet: layer_mixers="
                    f"{cfg.layer_mixers} has {list(cfg.unserved_mixers())} "
                    "layers, and the engine keeps no delta-rule state a "
                    "lane (inference/kv_pool.py holds pages of k/v, pages "
                    "of one latent a token and an 'ssm' layer's fixed "
                    "state, nothing else). `lumina train` runs it; "
                    "`lumina serve` and `lumina chat` need docs/"
                    "serving.md's 'Mixers that are not served'."
                )
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
        chunk = min(chunk, self.max_context)
        if self.config.keeps_lane_state() and self.max_context % chunk:
            # _prefill_chunked re-feeds rows where the chunk grid
            # overhangs the cache: k/v rows are rewritten bit for bit, a
            # recurrent state would take them twice.
            return 0
        return chunk

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

    def generate_stream_speculative(self, *args, **kwargs):
        """_stream_speculative, refused by name where a layer keeps a
        fixed state a lane: a rejected draft would have to roll the state
        back, and no snapshot of it is kept."""
        if self.config.keeps_lane_state():
            raise UnservedMixerError(
                "speculation (generate_speculative) is not served with "
                "'ssm' layers: a rejected draft cannot be rolled out of a "
                "recurrent state, and the engine keeps no snapshot of it"
            )
        return self._stream_speculative(*args, **kwargs)

    def _stream_speculative(
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
        loop");
      start_prefill(slot, prompt, ...) begins a CHUNKED prefill of a
        longer prompt: dispatch_step(key, chunk=state) carries its next
        `prefill_chunk` rows in the SAME forward pass as the lanes (one
        program a tick, _get_step), and the tick that carries the last
        chunk samples the first token on the device. advance_prefill()
        is that tick with no lane stepped, for callers that own their
        loop.

    Greedy step-wise decode is token-identical to generate() (same
    prefill bucketing, same sampling math, same rng split discipline —
    parity-tested), and sampled decode is bit-identical for the same
    per-request seed. Positions in the pool are absolute and never wrap:
    admission bounds prompt+max_new to the slot capacity. One uniform
    attention_window keeps whole pages and is served by the per-lane band
    mask; a layer with a window of its own (Config.layer_windows) keeps a
    RING of pages a lane beside the full layers' whole pages, addressed
    through the pool's ring table (kv_pool.py), and what a ring cannot
    honour is refused by name (RingKeepsWindowError).

    One tick-program compile per sampling parameter set (max_new is host
    state, NOT part of the compile key — mixed-length workloads share one
    executable, the core of the continuous-batching win — and so is
    whether a chunk is pending: an empty chunk is padding rows). Where
    the decode kernel attends every layer's lanes (lane_attention, whose
    grid stops at the longest lane's last key block and skips by the
    lanes' lengths) that is the ONE program of a sampling set, planned
    over the slot's whole pages; where XLA attends any layer's lanes (it
    reads a [lanes, extent] slice), or a prefix cache's page table is
    chased, a ladder of them, one per power-of-two page extent
    (_tick_ladder, _active_extent). `tick_programs_built` counts what
    was built, by extent.
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
        from luminaai_tpu.inference.kv_pool import PagedKVPool, lane_states

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
        if prefix_cache_pages > 0 and engine.config.keeps_lane_state():
            from luminaai_tpu.inference.kv_pool import StateNotPagedError

            raise StateNotPagedError(
                f"prefix cache (prefix_cache_pages={prefix_cache_pages}) "
                "is not served with 'ssm' layers: a cached page holds "
                "k/v rows and no snapshot of the recurrent state at its "
                "end, so a spliced prefix would start from the wrong state"
            )
        latent_layers = (engine.config.layer_mixers or ()).count("latent")
        if prefix_cache_pages > 0 and latent_layers:
            from luminaai_tpu.inference.kv_pool import LatentPagesOwnedError

            raise LatentPagesOwnedError(
                f"prefix cache (prefix_cache_pages={prefix_cache_pages}) "
                "is not served with 'latent' layers yet: a spliced prefix "
                "lives in another slot's pages, and the absorbed attention "
                "over a latent entry reads a lane's own pages in place"
            )
        backend = getattr(engine.config, "attention_backend", "dense")
        _chunk_eff = (
            int(prefill_chunk_tokens)
            if prefill_chunk_tokens is not None
            else int(getattr(engine.config, "prefill_chunk_size", 0) or 0)
        )
        _chunk_eff = max(0, min(
            _chunk_eff, pages * page_size, engine.max_context
        ))
        ring_pages = self._ring_pages_of(
            engine.config, pages, page_size, _chunk_eff, backend,
            prefix_cache_pages,
        )
        if prefix_cache_pages > 0 and backend == "dense":
            # The dense per-lane mask reads only the lane's own rows — it
            # cannot follow a cross-slot page alias. Gated off rather
            # than silently serving stale rows (docs/serving.md).
            logger.warning(
                "prefix cache disabled: attention_backend='dense' cannot "
                "read shared pages (use ragged_xla/ragged)"
            )
            prefix_cache_pages = 0
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
            ring_pages=ring_pages,
        )
        self._ring = (page_size, _chunk_eff) if ring_pages else None
        # A stack with 'ssm2' layers takes EVERY prompt in chunks, as a
        # pool with rings does: the tick's chunk enters from the slot's
        # stored state in block form and rides the weights' one read,
        # where the whole-prompt path is one more block-form program a
        # bucket (64, 128, 256 rows) and an insert of the fresh states
        # (4 MB a layer) an admission.
        self._chunks_every_prompt = _chunk_eff > 0 and "ssm2" in (
            engine.config.layer_mixers or ())
        self.pool.caches = self._init_pool_caches()
        # The decode budget honors the ENGINE's context contract: the
        # page rounding above may leave slack rows past max_context, and
        # decoding into them would silently run the model at
        # out-of-contract positions. Trim/clamp arithmetic below uses
        # this, with exactly generate()'s _trim_prompt formula, so the
        # two paths serve identical tokens for over-length prompts too.
        self.token_capacity = min(self.slot_tokens, engine.max_context)
        # A share of the experts (Config.experts_held): (token, expert)
        # pairs of the ticks' live rows, summed over layers: routed, on a
        # held expert, held and not computed. They ride the step's token
        # fetch (_get_step).
        self._held = bool(
            engine.config.use_moe and engine.config.experts_held
        )
        # Experts in a latent: a fourth count, the held experts a tick's
        # pairs touched, summed over layers (their weights are what the
        # grouped matmuls read).
        self._held_keys = (
            "moe_routed_pairs", "moe_held_pairs", "moe_held_pairs_dropped",
        ) + (("moe_held_experts_hit",)
             if self._held and engine.config.moe_latent_size else ())
        # Host-side lane state; device state is the pool + counts + rngs.
        self._reset_lane_state()
        self.steps = 0
        # Lane-steps whose token the host dropped: the lane ended (a
        # stop token, a cancel, an eviction) while the step was in
        # flight (_drop_ahead).
        self.lane_steps_dropped = 0
        # Prefill chunks that rode a tick in which a lane was stepped,
        # and the live prompt rows all chunks carried.
        self.chunks_carried = 0
        self.chunk_rows = 0
        # With state-space layers: live rows through the recurrence
        # (stepped lanes + live chunk rows), and the bytes of state they
        # moved (a stepped lane's and a live chunk's slab read and
        # written, a layer: twice _state_bytes, what one slot's states
        # hold without their tails, from the pool's own shapes).
        self.ssm_rows = 0
        self.ssm_state_bytes = 0
        self._state_bytes = sum(
            s.state.nbytes // s.state.shape[-3]
            for s in lane_states(self.pool.caches))
        # Rows of k/v the ticks' attention read, by kind of layer (window
        # of its own / full), and the times a lane's rows came round its
        # ring: counted on the host from the lengths it has
        # (_kv_rows_of).
        # (A kind of attention layer: its window and its k/v heads; a
        # stack may have full layers of 4 beside window layers of 8.)
        self._kinds = collections.Counter(
            (engine.config.window_of(i), engine.config.kv_heads_of(i))
            for i in range(engine.config.num_layers)
            if engine.config.mixer_kind(i) == "attention"
        )
        self.kv_window_rows = 0
        self.kv_global_rows = 0
        # The same reads in bytes: each layer's rows x that layer's own
        # row bytes (k and v as stored, _row_bytes): rows of two kinds
        # are not one size.
        self.kv_window_bytes = 0
        self.kv_global_bytes = 0
        self.ring_wraps = 0
        # Rows of one latent a token the lanes' attention read in the
        # 'latent' layers, and the keys the chunk's attention spanned
        # there (its lane up to the chunk's end), summed over those layers.
        self._n_latent_layers = latent_layers
        self.kv_latent_rows = 0
        self.kv_latent_chunk_keys = 0
        # Grid steps (lanes x key blocks) of the lanes' decode kernel
        # (ops/ragged_paged_attention.py lane_attention), summed over
        # attention layers, and those that fetched and computed: both 0
        # where the shapes leave the lanes' attention to XLA.
        self.lane_attention_blocks = 0
        self.lane_attention_blocks_live = 0
        self.moe_routed_pairs = 0
        self.moe_held_pairs = 0
        self.moe_held_pairs_dropped = 0
        self.moe_held_experts_hit = 0
        self.moe_held_experts = 0
        self._fns: Dict[Any, Any] = {}
        # Serving attention backend (config.attention_backend): 'dense'
        # keeps the legacy full-extent per-lane mask; the ragged backends
        # thread a LaneMeta (pool page table + resident page extent)
        # through the decode step so attention reads O(tokens resident).
        self.backend = getattr(
            engine.config, "attention_backend", "dense"
        )
        from luminaai_tpu.models.layers import latent_entry_width
        from luminaai_tpu.ops.ragged_paged_attention import (
            lane_attention_engaged,
        )

        # By kind of layer (_kinds): whether the lanes' rows go through
        # the kernel, from the shapes of the arrays the entry keeps (a
        # key in parts: the value's width, Config.key_parts).
        cfg = engine.config
        self._key_width = cfg.key_width()
        self._lane_kernels = {
            kind: lane_attention_engaged(
                self.backend, 1, cfg.num_heads, kind[1],
                self._key_width // cfg.key_parts(), self.pool.page_size,
                cfg.value_dim(),
            )
            for kind in self._kinds
        }
        self._lane_kernel = bool(self._lane_kernels) and all(
            self._lane_kernels.values())
        self._row_bytes = {
            cfg.kv_heads_of(i): cfg.kv_row_bytes(
                i, jnp.dtype(self.model.dtype).itemsize,
                cfg.kv_cache_dtype)
            for i in range(cfg.num_layers)
            if cfg.mixer_kind(i) == "attention"
        }
        # A latent entry is one shared key row of its own width.
        self._latent_row = (1, latent_entry_width(engine.config))
        self._latent_lane_kernel = lane_attention_engaged(
            self.backend, 1, engine.config.num_heads, *self._latent_row,
            self.pool.page_size,
        )
        # Device copy of the pool's page table, refreshed at admission
        # (identity today; a prefix cache would retarget entries there).
        self._table = jnp.asarray(self.pool.page_table_array())
        self._ring_table = (
            jnp.asarray(self.pool.ring_tables) if ring_pages else None
        )
        # Chunked prefill: fixed chunk length (None -> the engine
        # config's prefill_chunk_size), clamped to the slot budget;
        # 0 disables, callers fall back to prefill_into_slot.
        self.prefill_chunk = _chunk_eff
        # How the tick's held expert layers take their sorted rows back
        # to their tokens at this program's shapes (models/moe.py
        # held_combine_is_product): {"T", "R", "H", "product"}; None
        # without a share of the experts. Every extent's tick has these
        # rows, so it is a fact of the decoder, not of a step.
        from luminaai_tpu.models.moe import held_combine_form

        self.held_combine = held_combine_form(
            engine.config, num_slots + _chunk_eff, self.model.dtype
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
        # What the tick program is specialised by. Where XLA attends any
        # layer's lanes it reads a [lanes, extent] slice, so the program
        # is built per power-of-two page extent and a tick reads no
        # further than its longest lane. Where the decode kernel attends
        # every layer's lanes (each paged kind, the latent layers) an
        # extent would only bound its grid, which the kernel bounds
        # itself from the lengths (lane_grid_blocks): ONE program a
        # sampling key, planned over the slot's whole pages. Under a
        # chased page table (the prefix cache) a block is one page and
        # no cell has measured a plan of lanes x pages steps: the ladder
        # stays there. 'dense' knows no extent.
        kernels = list(self._lane_kernels.values()) + (
            [self._latent_lane_kernel] if latent_layers else [])
        self._tick_ladder = self.backend != "dense" and not (
            all(kernels) and self.prefix_cache is None)
        # Tick programs built (_get_step missed its cache), by extent:
        # "full" or the rung's rows.
        self.tick_programs_built: Dict[str, int] = collections.Counter()
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

    @staticmethod
    def _ring_pages_of(config, pages, page_size, chunk, backend,
                       prefix_cache_pages) -> int:
        """Pages of the ring a lane keeps of each layer with a window of
        its own (0: no layer keeps one, or a ring would be no smaller
        than the whole lane), and the refusals, by name, of what a ring
        cannot honour."""
        from luminaai_tpu.inference.kv_pool import RingKeepsWindowError

        if getattr(config, "layer_windows", None) is None:
            return 0
        rings = {
            config.ring_pages(i, page_size, chunk)
            for i in range(config.num_layers)
        } - {None}
        rings = {r for r in rings if r < pages}
        if not rings:
            return 0
        if len(rings) > 1:
            raise RingKeepsWindowError(
                f"layer_windows {config.layer_windows} give rings of "
                f"{sorted(rings)} pages: one pool keeps one ring table, "
                "so one ring size"
            )
        for bad, why in (
            (prefix_cache_pages > 0,
             f"the prefix cache (prefix_cache_pages={prefix_cache_pages}): "
             "a cached page chain starts at position 0, and a ring keeps "
             "no page older than the window, so a spliced prefix would "
             "find its window layers' pages overwritten"),
            (chunk <= 0,
             "a pool without chunked prefill (prefill_chunk_size=0): a "
             "ring is sized to the window plus ONE chunk, and a whole "
             "prompt written at once would overrun it"),
            (getattr(config, "kv_cache_dtype", "bf16") == "int8",
             "kv_cache_dtype='int8': the ring's write and read paths "
             "carry no per-row scales yet"),
            (backend == "dense",
             "attention_backend='dense': its mask takes a row's number "
             "for its position, and a ring's rows hold the positions the "
             "ring table says"),
        ):
            if bad:
                raise RingKeepsWindowError(
                    f"not served beside a ring of pages "
                    f"(layer_windows {config.layer_windows}): {why}"
                )
        return rings.pop()

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
                    ring=self._ring,
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
        # lanes whose token the host set since (`_host_tok`: a lane the
        # whole-prompt path's _finish_prefill just activated; a chunked
        # prompt's first token is already in that output). `_budget` is
        # the number of decode steps each lane's request still allows
        # (max_new - 1 at activation): with a step in flight it is how
        # the host knows, without reading that step, which lanes it ends.
        self._inflight: collections.deque = collections.deque()
        self._budget = np.zeros((S,), np.int32)
        self._host_tok = np.ones((S,), bool)
        # (With a share of the experts its three pair counts ride behind
        # the lanes' tokens.)
        self._nxt_dev = jnp.zeros(
            (S + len(self._held_keys) * self._held,), jnp.int32)

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

        return to_flat(tree, self.pool.page_size)

    def _paged(self, tree):
        from luminaai_tpu.inference.kv_pool import to_paged

        return to_paged(tree, self.pool.page_size)

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
            from luminaai_tpu.inference.kv_pool import map_pages

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

                return map_pages(
                    put, pool_caches, fresh,
                    states=lambda p, f: p.insert(f, slot))

            self._fns["insert"] = jax.jit(insert, donate_argnums=(0,))
        return self._fns["insert"]

    def _active_extent(self, pos=None, live=None) -> int:
        """Resident-extent bound in ROWS for the ragged decode step: a
        power-of-two page count covering every active lane's rows
        (>= 1 page, <= the slot's pages). Where XLA attends the lanes
        (_tick_ladder) the step executable is specialized per extent —
        O(log pages) executables, the same ladder discipline as prompt
        buckets — and within one extent the length mask still skips
        per-lane. `pos` / `live`: the write rows and lanes of a step
        about to be dispatched (the host's prediction, an upper bound);
        default, the collected state."""
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
        """The TICK program, one executable per (sample key, backend,
        extent, table kind): one forward pass over `num_slots` decode
        rows AND the `prefill_chunk` rows of one prompt being prefilled,
        so a tick that admits a chunk streams every weight once, not
        once for the step and once for the chunk. A tick with no chunk
        pending runs the same program with the chunk's rows as padding
        (position -1: they write nothing and their output is unread):
        no second shape exists for a warm-up to miss. `extent` None: the
        one program of a sampling key, over the slot's whole pages (the
        decode kernel attends every lane, or 'dense'); a number of rows:
        one rung of the ladder (_tick_ladder), whose XLA attention reads
        that slice."""
        use_global = self.prefix_cache is not None
        key = ("step", sample_key, self.backend, extent, use_global)
        if key not in self._fns:
            self.tick_programs_built[str(extent or "full")] += 1
            from luminaai_tpu.models.layers import Embedder

            temperature, top_k, top_p, rep_penalty = sample_key
            stop_ids = jnp.asarray(
                sorted(self.engine._stop_set), dtype=jnp.int32
            )
            S = self.num_slots
            n = self.prefill_chunk
            backend = self.backend
            window = getattr(self.engine.config, "attention_window", None)
            page_size = self.pool.page_size
            ring_table = self._ring_table
            held = self._held
            held_keys = self._held_keys
            moe_layers = self.engine.config.num_moe_layers()
            lm_head = Embedder(self.model.config, dtype=self.model.dtype)

            def sample(rng, logits, counts):
                return sample_token(
                    rng, logits, counts,
                    temperature=temperature, top_k=top_k, top_p=top_p,
                    repetition_penalty=rep_penalty,
                ).astype(jnp.int32)

            def step(params, caches, prev_nxt, tick, counts, rngs, table):
                # `tick` is everything the host sends, one int32
                # transfer (_pack_tick): per lane the write row, whether
                # it is stepped, and the token of each lane the host set
                # since the last step (every other lane's token is the
                # previous step's output, which never left the device);
                # then the chunk: its slot, first row, the prompt's
                # length, whether it is the prompt's last, the request's
                # seed, and its token ids.
                from luminaai_tpu.ops.ragged_paged_attention import (
                    LaneMeta,
                )

                lanes = tick[: 4 * S].reshape(4, S)
                pos = lanes[0]
                active = lanes[1] != 0
                tokens = jnp.where(lanes[2] != 0, lanes[3], prev_nxt[:S])
                c_slot, c_start, c_len, c_last, c_seed = (
                    tick[4 * S + i] for i in range(5)
                )
                c_pos = c_start + jnp.arange(n)
                c_pos = jnp.where(c_pos < c_len, c_pos, -1)
                chunk = dict(
                    chunk_rows=n, chunk_slot=c_slot, chunk_start=c_start
                ) if n else {}
                flat = self._flat(caches)
                split2 = jax.vmap(lambda r: jax.random.split(r, 2))(rngs)
                # Only a stepped lane's stream moves on: a tick that
                # steps no lane (a chunk alone) leaves every rng as it is.
                new_rngs = jnp.where(active[:, None], split2[:, 0], rngs)
                step_rngs = split2[:, 1]
                if backend == "dense":
                    meta = LaneMeta(lengths=None, backend="dense", **chunk)
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
                        ring_table=ring_table,
                        **chunk,
                    )
                # One token a row: S lanes, then the chunk's rows. A row
                # at position -1 writes no K/V: a lane not stepped (a
                # slot being prefilled among them) and the chunk's
                # padding.
                hidden, flat, aux = self.model.apply(
                    {"params": params},
                    jnp.concatenate([tokens, tick[4 * S + 5:]])[:, None],
                    positions=jnp.concatenate(
                        [jnp.where(active, pos, -1), c_pos]
                    )[:, None],
                    kv_caches=flat,
                    cache_index=jnp.concatenate([pos, c_pos]),
                    deterministic=True,
                    lane_meta=meta,
                    return_hidden=True,
                )
                # The LM head runs over the rows a token is sampled
                # from: the lanes, and the chunk's last live row.
                rows = hidden[:S]
                if n:
                    last = S + jnp.clip(c_len - 1 - c_start, 0, n - 1)
                    rows = jnp.concatenate([
                        rows,
                        jax.lax.dynamic_slice_in_dim(hidden, last, 1, 0),
                    ])
                logits = lm_head.apply(
                    {"params": params["embedder"]}, rows, method="decode"
                )[:, 0]
                nxt = jax.vmap(sample)(step_rngs, logits[:S], counts)
                nxt = jnp.where(active, nxt, tokens)
                counts = counts.at[jnp.arange(S), nxt].add(
                    active.astype(counts.dtype)
                )
                eos = jnp.logical_and(
                    active,
                    jnp.any(nxt[:, None] == stop_ids[None, :], axis=1),
                )
                if n:
                    # The prompt's last chunk: its first token, sampled
                    # as _finish_prefill samples it (the same key
                    # derivation from the request's seed, empty counts),
                    # lands in nxt[slot] for the next step to read, and
                    # the slot's counts and rng start over.
                    is_last = c_last != 0
                    rng, first_rng = jax.random.split(
                        jax.random.PRNGKey(c_seed)
                    )
                    first = sample(
                        first_rng, logits[S], jnp.zeros_like(counts[0])
                    )
                    nxt = jnp.where(
                        jnp.logical_and(is_last, jnp.arange(S) == c_slot),
                        first, nxt,
                    )
                    fresh = jnp.zeros_like(counts[0]).at[first].add(
                        1 - jnp.any(first == stop_ids).astype(counts.dtype)
                    )
                    counts = counts.at[c_slot].set(
                        jnp.where(is_last, fresh, counts[c_slot])
                    )
                    new_rngs = new_rngs.at[c_slot].set(
                        jnp.where(is_last, rng, new_rngs[c_slot])
                    )
                if held:
                    # The share's pair counts of this tick's live rows
                    # (a layer's mean x the expert layers), behind the
                    # tokens: one fetch brings both.
                    nxt = jnp.concatenate([nxt, jnp.round(jnp.stack([
                        aux[k] for k in held_keys
                    ]) * moe_layers).astype(jnp.int32)])
                return self._paged(flat), nxt, eos, counts, new_rngs

            # Everything the step rewrites is donated (the pool, the
            # repetition counts, the lane rngs): the scatter of its rows
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
        if self.pool.ring_pages:
            from luminaai_tpu.inference.kv_pool import RingKeepsWindowError

            raise RingKeepsWindowError(
                "prefill_into_slot writes a whole prompt at once; a pool "
                "with rings of pages takes every prompt in chunks "
                "(start_prefill never declines there)"
            )
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
        """The whole-prompt path's prompt-KV-written → lane-activated
        tail: sample token #1 (a device sync), set the host lane state,
        return prefill_into_slot's info contract. A chunked prefill's
        first token is sampled inside the tick program that carries its
        last chunk (_get_step), with this key derivation, and read with
        that step (_first_token)."""
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
        elif (L <= chunk and not self.pool.ring_pages
              and not self._chunks_every_prompt):
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
        """A slot being prefilled is no lane: it is not stepped, and a
        row that is not stepped writes nothing (_get_step). `rows`: what
        is resident so far (a spliced prefix)."""
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

    def prefill_ready(self, st: Dict[str, Any]) -> bool:
        """Whether `st` has a chunk to run now. A `waiting` state
        (in-flight dedup, see start_prefill) burns a tick re-checking
        the leader instead of computing: once the leader's harvest lands
        the acquire books a real HIT and the suffix-only prefill runs;
        if the leader dies (release_pending in release_slot) or the wait
        budget expires, the lane proceeds cold. Either way no chunk
        FLOPs are spent while parked."""
        if st.get("waiting"):
            st["wait_ticks"] += 1
            cache = self.prefix_cache
            if (
                cache is not None
                and cache.has_pending_prefix(st["chain"])
                and st["wait_ticks"] < self.DEDUP_WAIT_TICKS
            ):
                return False
            self._arm_prefill(st)
        return st["next"] < st["n_chunks"]

    def advance_prefill(
        self, st: Dict[str, Any]
    ) -> Optional[Dict[str, Any]]:
        """Run ONE prefill chunk: the tick program with that chunk and
        no lane stepped, read at once. Returns None while chunks remain
        (or while `st` is parked, prefill_ready); the final chunk samples
        token #1, activates the lane, and returns prefill_into_slot's
        info dict (plus a `prefix` block when the cache is on: hit /
        harvest accounting for the scheduler's counters and prefix_hit
        events). For callers that own their loop: the scheduler hands
        the chunk to the step it dispatches (dispatch_step(chunk=st))
        and the chunk costs no forward pass of its own.

        Chunks start at `start_rows` (the spliced prefix extent, 0 when
        cold) — the suffix-only prefill that turns a prefix hit into
        skipped FLOPs."""
        if not self.prefill_ready(st):
            return None
        step = self._dispatch(st["sample_key"], st, step_lanes=False)
        # (A share's pair counts ride every tick's tokens: read them all.)
        if step["chunk"]["last"] or self._held:
            with self.phases.region("device_wait"), \
                    self.tracer.span("decode.fetch"):
                first = int(self._read_tokens(step)[st["slot"]])
        if step["chunk"]["last"]:
            self._first_token(st, first)
        return st.pop("info", None)

    @staticmethod
    def _chunk_start(st: Dict[str, Any]) -> int:
        """The row `st`'s next chunk starts at (past a spliced prefix)."""
        return int(st["start_rows"]) + st["next"] * st["chunk"]

    def _chunk_dispatched(
        self, st: Dict[str, Any], carried: bool
    ) -> Dict[str, Any]:
        """Host bookkeeping of a chunk, once the tick that carries it is
        on the device's queue. After a prompt's LAST chunk the slot is a
        lane by the host's prediction (row `length`, a budget of
        max_new - 1), so the next step dispatched already steps it, with
        the first token it reads from the device; what the prediction
        cannot know, a first token that is a stop id, is settled where
        the tick is read (_first_token)."""
        slot, L = st["slot"], st["length"]
        start = self._chunk_start(st)
        end = min(start + st["chunk"], L)
        self.chunk_rows += end - start
        self.chunks_carried += int(carried)
        if self.pool.keeps_state:
            self.ssm_rows += end - start
            self.ssm_state_bytes += 2 * self._state_bytes
        st["next"] += 1
        last = st["next"] >= st["n_chunks"]
        # Residency telemetry tracks rows as they land.
        self.pool.lengths[slot] = end
        if last:
            self._pos[slot] = L
            self._budget[slot] = st["max_new"] - 1
            self._active[slot] = st["max_new"] > 1
        return {"st": st, "last": last}

    def _first_token(self, st: Dict[str, Any], first: int) -> None:
        """The tick that carried `st`'s last chunk has been read: book
        the prompt's first token and leave prefill_into_slot's info dict
        in st["info"]. A stop id ends the lane here, and the step already
        dispatched for it is dropped (_drop_ahead), as for any stop
        token."""
        slot = st["slot"]
        is_stop = first in self.engine._stop_set
        self._tokens[slot] = first
        if is_stop:
            self._active[slot] = False
            self._drop_ahead(slot)
        info: Dict[str, Any] = {
            "token": None if is_stop else first,
            "prompt_tokens": st["length"],
            "is_stop": is_stop,
        }
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
                "tokens_saved": int(st["start_rows"]),
                "pages_harvested": harvested,
                "tenant": st.get("tenant", "anon"),
                "dedup_wait_ticks": int(st.get("wait_ticks", 0)),
                # Cross-replica pull accounting (None for purely local
                # admissions): the scheduler books remote-hit counters
                # and prefix_remote_hit events from this.
                "remote": st.get("remote"),
            }
        st["info"] = info

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

    def _drop_ahead(self, slot: int) -> None:
        """The lane ended (a stop token, a release) with steps in flight
        that step it: the host drops their tokens. Each wrote one KV row
        into the lane's OWN slot, past every row attended so far, and
        bumped its own `counts` / `rngs` rows; the slot's next admission
        resets all three, and its programs queue behind those steps. A
        LAST chunk of the slot in flight has its first token dropped the
        same way (only a release can meet one: the slot is no lane yet)."""
        for step in self._inflight:
            if step["stepped"][slot]:
                step["stepped"][slot] = False
                self.lane_steps_dropped += 1
            chunk = step["chunk"]
            if chunk is not None and chunk["st"]["slot"] == slot:
                chunk["last"] = False

    def _steps_ahead(self) -> np.ndarray:
        """Per lane, how many of the steps in flight step it."""
        ahead = np.zeros((self.num_slots,), np.int32)
        for step in self._inflight:
            ahead += step["stepped"]
        return ahead

    def _pack_lanes(self) -> Tuple[np.ndarray, np.ndarray]:
        """The lanes' part of what the next step would be called with,
        right now: [4, S] int32 (write row, stepped, token from the
        host, that token) and the lanes it steps.

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

    def _next_step(
        self,
        sample_key: Optional[Tuple],
        chunk: Optional[Dict[str, Any]] = None,
        step_lanes: bool = True,
    ):
        """(tick program, packed tick, lanes stepped, extent) of the step that
        would be dispatched right now: everything a tick sends is ONE
        int32 array, the lanes (_pack_lanes), then five numbers of the
        chunk (slot, first row, prompt length, is-last, seed) and its
        `prefill_chunk` token ids. `chunk`: the prefill state whose next
        chunk rides the step; None leaves the chunk's rows padding
        (length 0). `step_lanes=False` steps no lane."""
        sample_key = sample_key or GREEDY_SAMPLE_KEY
        lanes, live = self._pack_lanes()
        if not step_lanes:
            live = np.zeros_like(live)
            lanes[1] = 0
        S, n = self.num_slots, self.prefill_chunk
        tick = np.zeros((4 * S + 5 + n,), np.int32)
        tick[: 4 * S] = lanes.reshape(-1)
        if chunk is not None:
            if chunk["sample_key"] != sample_key:
                # The program samples the prompt's first token too.
                raise ValueError(
                    "a chunk rides a step of its own sampling key: "
                    f"{chunk['sample_key']} != {sample_key}"
                )
            start = self._chunk_start(chunk)
            seed = chunk["seed"]
            if seed is None:
                seed = time.time_ns()
            tick[4 * S: 4 * S + 5] = (
                chunk["slot"], start, chunk["length"],
                chunk["next"] + 1 >= chunk["n_chunks"],
                # PRNGKey keeps a seed's low 32 bits; as int32 bits here.
                np.array(seed & 0xFFFFFFFF, np.uint32).view(np.int32),
            )
            tick[4 * S + 5:] = chunk["ids"][0, start:start + n]
        extent = (
            self._active_extent(lanes[0], live) if self._tick_ladder else None
        )
        return self._get_step(sample_key, extent), tick, live, extent

    def _kv_rows_of(self, extent, pos, live, chunk) -> None:
        """Book what the tick about to be dispatched reads and wraps,
        from lengths the host has. The chunk's lane: its rows up to the
        chunk's end (a ring: at most the ring). The lanes, where XLA
        attends them: every lane's rows up to the tick's extent in a full
        layer and its whole ring in a layer with a window of its own (the
        program reads a lane whether or not it is stepped). Where the
        decode kernel does (`_lane_kernel`): for a stepped lane the rows
        of the key blocks in which its query sees a key, nothing for a
        lane not stepped (_lane_blocks_read: the kernel's own plan, on the
        host), and the kernel's grid steps, all and live; `extent` None
        (the one tick program): the plan is the slot's whole pages. A
        wrap each time a row is written onto the ring's first row again."""
        ring = self.pool.ring_pages * self.pool.page_size
        lanes_full = extent or self.slot_tokens
        lanes_window = ring or lanes_full
        c_full = c_window = 0
        if chunk is not None:
            start = self._chunk_start(chunk)
            end = min(start + chunk["chunk"], chunk["length"])
            c_full, c_window = end, min(end, lanes_window)
            if ring:
                self.ring_wraps += (end - 1) // ring - max(start - 1, 0) // ring
        held, slots = pos[live] + 1, np.flatnonzero(live)
        for (window, n_kv), layers in self._kinds.items():
            full = window is None
            lanes_rows = lanes_full if full else lanes_window
            rows = layers * self.num_slots * lanes_rows
            if self._lane_kernels[(window, n_kv)]:
                steps, fetched, per = self._lane_blocks_read(
                    held, slots, window, lanes_rows,
                    bool(ring) and not full, (n_kv, self._key_width),
                )
                self.lane_attention_blocks += layers * steps
                self.lane_attention_blocks_live += layers * fetched
                rows = layers * fetched * per
            rows += layers * (c_full if full else c_window)
            if full:
                self.kv_global_rows += rows
                self.kv_global_bytes += rows * self._row_bytes[n_kv]
            else:
                self.kv_window_rows += rows
                self.kv_window_bytes += rows * self._row_bytes[n_kv]
        if self._n_latent_layers:
            layers = self._n_latent_layers
            rows = self.num_slots * lanes_full
            if self._latent_lane_kernel:
                steps, fetched, per = self._lane_blocks_read(
                    pos[live] + 1, np.flatnonzero(live), None, lanes_full,
                    False, self._latent_row,
                )
                self.lane_attention_blocks += layers * steps
                self.lane_attention_blocks_live += layers * fetched
                rows = fetched * per
            self.kv_latent_rows += layers * rows
            self.kv_latent_chunk_keys += layers * c_full
        if ring:
            at = pos[live]
            self.ring_wraps += int(((at > 0) & (at % ring == 0)).sum())

    def _lane_blocks_read(self, held, slots, window, rows, ring, row):
        """(grid steps, steps that fetch and compute, rows of k/v such a
        step fetches) of one layer's lane_attention call in a tick that
        steps the lanes `slots`, holding `held` rows each, over `rows`
        rows a lane (the slot's whole pages or the tick's extent, or the
        ring): lane_pages_held, lane_blocks and lane_grid_blocks, the
        kernel's own plan and its grid's bound, over the host's lengths.
        `row`: (k/v heads, key columns) of a pool row."""
        from luminaai_tpu.ops.ragged_paged_attention import (
            lane_blocks,
            lane_grid_blocks,
            lane_pages_held,
        )

        ps = self.pool.page_size
        pages = rows // ps
        per_block, _ = lane_blocks(
            pages, ps, *row, jnp.dtype(self.model.dtype).itemsize,
            chased=self.prefix_cache is not None,
        )
        seen = lane_pages_held(
            held, ps, pages, window,
            self.pool.ring_tables[slots] if ring else None, xp=np,
        ) >= 0
        fetched = int(
            seen.reshape(len(slots), pages // per_block, per_block)
            .any(axis=2).sum()
        )
        blocks = pages // per_block
        if not ring:
            blocks = int(lane_grid_blocks(held, per_block * ps, blocks, np))
        return self.num_slots * blocks, fetched, per_block * ps

    def step_fn_and_args(
        self, sample_key: Optional[Tuple] = None
    ) -> Tuple[Any, Tuple]:
        """The jitted tick program and the argument tuple dispatch_step
        would call it with right now (no chunk pending). Exposed so
        monitoring/attribution.py can AOT-lower the decode executable for
        compiled-cost accounting without executing a step (bench
        extras.ragged_attention compares the dense and ragged backends'
        compiled bytes through exactly this handle). For LOWERING only:
        the function donates the pool, the counts and the rngs, so a
        caller that RUNS it must rebind all three from the result as
        dispatch_step does, or the decoder is left holding deleted
        buffers."""
        fn, tick, _, _ = self._next_step(sample_key)
        args = (
            self.params,
            self.pool.caches,
            self._nxt_dev,
            jax.device_put(tick),
            self._counts,
            self._rngs,
            self._table,
        )
        return fn, args

    @property
    def steps_in_flight(self) -> int:
        return len(self._inflight)

    def _dispatch(
        self,
        sample_key: Optional[Tuple],
        chunk: Optional[Dict[str, Any]],
        step_lanes: bool = True,
    ) -> Optional[Dict[str, Any]]:
        """Put one tick on the device's queue: the record collect_step
        reads, or None (nothing enqueued) when a step is in flight and
        there is neither a lane it leaves alive nor a chunk."""
        span, region = self.tracer.span, self.phases.region
        # (`decode.pack` and `decode.book` are spans alone: the ledger
        # books the host's packing and bookkeeping to `sched`.)
        with span("decode.pack"):
            fn, tick, live, extent = self._next_step(
                sample_key, chunk, step_lanes
            )
        if self._inflight and chunk is None and not live.any():
            return None
        with region("put"), span("decode.put"):
            tick_d = jax.device_put(tick)
        with region("dispatch"), span("decode.dispatch"):
            caches, nxt, eos, counts, rngs = fn(
                self.params, self.pool.caches, self._nxt_dev, tick_d,
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
        if self.pool.keeps_state:
            self.ssm_rows += int(live.sum())
            self.ssm_state_bytes += 2 * int(live.sum()) * self._state_bytes
        self._kv_rows_of(extent, tick[: self.num_slots], live, chunk)
        return {
            "nxt": nxt, "eos": eos, "stepped": live,
            "chunk": None if chunk is None else self._chunk_dispatched(
                chunk, bool(live.any())
            ),
        }

    def dispatch_step(
        self,
        sample_key: Optional[Tuple] = None,
        chunk: Optional[Dict[str, Any]] = None,
    ) -> bool:
        """Enqueue one tick on the device and return at once;
        collect_step() reads it. `chunk`: a prefill state (start_prefill,
        prefill_ready) whose next chunk rides this step, in the same
        forward pass as the lanes. Called with the previous step still
        in flight (the scheduler's steady state) it steps the lanes that
        step cannot end (_pack_lanes), and returns False, enqueueing
        nothing, when there is none and no chunk either."""
        step = self._dispatch(sample_key, chunk)
        if step is None:
            return False
        self._inflight.append(step)
        return True

    def collect_step(self) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Read the oldest step in flight (blocks until the device has
        run it) and do its host bookkeeping. Returns decode_step's
        (tokens[S], produced[S], eos[S]), for the lanes that step
        stepped and that have not been released since. A prompt whose
        last chunk rode the step has its first token booked here, and
        prefill_into_slot's info dict left in its state's "info"."""
        step = self._inflight.popleft()
        with self.phases.region("device_wait"), \
                self.tracer.span("decode.fetch"):
            nxt_h = self._read_tokens(step)
            eos_h = np.asarray(step["eos"])
        with self.tracer.span("decode.book"):
            stepped = step["stepped"]
            eos_h = eos_h & stepped
            self._tokens[stepped] = nxt_h[stepped]
            self._pos[stepped] += 1
            self.pool.lengths[stepped] += 1
            self._budget[stepped] -= 1
            self._active &= ~eos_h
            for slot in np.flatnonzero(eos_h):
                self._drop_ahead(int(slot))
            chunk = step["chunk"]
            if chunk is not None and chunk["last"]:
                self._first_token(
                    chunk["st"], int(nxt_h[chunk["st"]["slot"]])
                )
            self.steps += 1
            return nxt_h, stepped & ~eos_h, eos_h

    def _read_tokens(self, step: Dict[str, Any]) -> np.ndarray:
        """The lanes' tokens of a dispatched tick (blocks until it has
        run). With a share of the experts (Config.experts_held) the same
        fetch carries the tick's three pair counts behind them (a step
        is read once: by collect_step, or by advance_prefill)."""
        got = np.asarray(step["nxt"])
        if self._held:
            for key, n in zip(self._held_keys, got[self.num_slots:]):
                setattr(self, key, getattr(self, key) + int(n))
            if "moe_held_experts_hit" in self._held_keys:
                cfg = self.engine.config
                self.moe_held_experts += (
                    cfg.experts_held[1] * cfg.num_moe_layers())
        return got[: self.num_slots]

    def abandon_steps(self) -> None:
        """Forget every step in flight: nothing of them is read. For
        the caller whose dispatch or collect raised; it must release
        every lane they stepped and every slot whose chunk they carried
        (the scheduler fails them all)."""
        for step in self._inflight:
            self.lane_steps_dropped += int(step["stepped"].sum())
        self._inflight.clear()

    def decode_step(
        self, sample_key: Optional[Tuple] = None
    ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Advance every active lane one token (one jit call): the tick
        with no chunk. Returns (tokens[S], produced[S], eos[S]):
        `produced` lanes emitted tokens[slot] this step; `eos` lanes hit
        a stop token (dropped, matching generate()) and were deactivated
        — the scheduler frees their slots. dispatch_step() +
        collect_step() with nothing else in flight: the serial form, for
        callers that own their loop."""
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
    if any("attention" not in params[f"layer_{i}"] for i in layers):
        # Shapes cannot say what a mixed stack needs (whether attention
        # rotates, which layer is of which kind past its parameters).
        raise ValueError(
            "a checkpoint with 'ssm', 'kda' or 'latent' layers is served "
            "from the Config in its metadata; shape inference covers "
            "attention-only stacks"
        )
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
