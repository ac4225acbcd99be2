"""HTTP serving surface (ref Dockerfile.backend Flask-on-:5001 contract)."""

import itertools
import json
import threading
import urllib.error
import urllib.request
from http.server import ThreadingHTTPServer

import pytest

from luminaai_tpu.config import Config
from luminaai_tpu.serving.server import ChatServer


class FakeTokenizerBackend:
    def encode(self, text):
        return [ord(c) % 250 for c in text]


class FakeTokenizer:
    backend = FakeTokenizerBackend()

    def decode(self, tokens):
        return "tok:" + ",".join(str(t) for t in tokens)


class FakeStepper:
    """Hermetic StepwiseDecoder double: deterministic token streams
    (by default prompt[0], prompt[0]+1, ...; `lane_tokens(prompt)` gives
    another, and a lane whose tokens run out reports eos) over a real
    PagedKVPool's slot accounting, so scheduler logic (admission,
    eviction, reuse ordering, cancellation) is testable without jax."""

    def __init__(self, num_slots=2, slot_tokens=64, lane_tokens=None):
        from luminaai_tpu.inference.kv_pool import PagedKVPool

        self.num_slots = num_slots
        self.slot_tokens = slot_tokens
        self.pool = PagedKVPool(None, num_slots, 1, slot_tokens)
        self.steps = 0
        self.prefills = 0
        self.lane_tokens = lane_tokens or (
            lambda prompt: itertools.count(int(prompt[0]))
        )
        self._lanes = [None] * num_slots  # slot -> its tokens' iterator

    def has_free_slot(self):
        return self.pool.has_free()

    def acquire_slot(self):
        return self.pool.alloc()

    def release_slot(self, slot):
        self._lanes[slot] = None
        self.pool.free(slot)

    def lane_full(self, slot):
        return False

    def prefill_into_slot(self, slot, prompt, max_new_tokens=1,
                          sample_key=None, seed=None):
        self.prefills += 1
        lane = iter(self.lane_tokens(prompt))
        first = next(lane, None)
        self._lanes[slot] = lane if max_new_tokens > 1 else None
        self.pool.lengths[slot] = len(prompt)
        return {"token": 0 if first is None else int(first),
                "prompt_tokens": len(prompt), "is_stop": first is None}

    def decode_step(self, sample_key=None):
        import time as _time

        import numpy as np

        _time.sleep(0.01)  # a "device step": keeps admission ordering real
        toks = np.zeros((self.num_slots,), np.int64)
        eos = np.zeros((self.num_slots,), bool)
        produced = np.zeros((self.num_slots,), bool)
        for s, lane in enumerate(self._lanes):
            if lane is None:
                continue
            nxt = next(lane, None)
            if nxt is None:
                eos[s] = True
                self._lanes[s] = None
            else:
                toks[s], produced[s] = nxt, True
        self.steps += 1
        return toks, produced, eos


class FakeEngine:
    """Engine double mirroring GenerationEngine's contract as ChatServer
    uses it: make_stepwise hands the scheduler a FakeStepper whose lanes
    play lane_tokens(prompt); encode_chat maps messages -> prompt ids;
    .tokenizer does the text round-trip."""

    def __init__(self):
        self.config = Config(
            vocab_size=256, hidden_size=64, num_layers=2, num_heads=4,
            num_kv_heads=2, seq_length=64, use_flash_attention=False,
        )
        self.tokenizer = FakeTokenizer()
        self.stepper = FakeStepper(
            num_slots=2, lane_tokens=self.lane_tokens
        )

    def lane_tokens(self, prompt):
        """What a request generates: its first three prompt ids, then
        the lane ends (eos)."""
        return list(prompt)[:3]

    def _resolve_gen_key(self, mnt, temp, top_p, top_k, rep):
        return (
            int(mnt or 8),
            float(0.0 if temp is None else temp),
            int(top_k or 0),
            float(1.0 if top_p is None else top_p),
            float(1.0 if rep is None else rep),
        )

    def make_stepwise(self, **kw):
        return self.stepper

    def encode_chat(self, messages):
        return self.tokenizer.backend.encode(messages[-1]["content"])


@pytest.fixture()
def server_url():
    srv = ChatServer(FakeEngine())
    httpd = ThreadingHTTPServer(("127.0.0.1", 0), srv.make_handler())
    t = threading.Thread(target=httpd.serve_forever, daemon=True)
    t.start()
    yield f"http://127.0.0.1:{httpd.server_address[1]}", srv
    httpd.shutdown()
    httpd.server_close()


def _post(url, path, body, token=None):
    req = urllib.request.Request(
        url + path, data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json",
                 **({"Authorization": f"Bearer {token}"} if token else {})},
    )
    try:
        with urllib.request.urlopen(req, timeout=10) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def _get(url, path):
    with urllib.request.urlopen(url + path, timeout=10) as r:
        return r.status, json.loads(r.read())


def test_chat_page_served(server_url):
    """GET / serves the built-in chat UI (the ref Electron app's role)."""
    url, _ = server_url
    with urllib.request.urlopen(url + "/", timeout=10) as r:
        assert r.status == 200
        assert r.headers.get("Content-Type", "").startswith("text/html")
        page = r.read().decode()
    assert "/v1/chat" in page and "text/event-stream" in page


def test_health(server_url):
    url, _ = server_url
    code, body = _get(url, "/health")
    assert code == 200 and body["status"] == "ok"
    assert body["model"]["hidden_size"] == 64


def test_generate_and_stats(server_url):
    url, srv = server_url
    code, body = _post(url, "/v1/generate", {"prompt": "hiya"})
    assert code == 200 and body["text"].startswith("tok:")
    assert body["tokens"] == 3
    code, body = _post(url, "/v1/chat", {"message": "yo"})
    # Chat rides the same scheduler: encode_chat -> a lane -> decode.
    assert code == 200 and body["reply"] == "tok:121,111"
    code, body = _get(url, "/stats")
    assert body["requests"] == 2 and body["tokens_out"] == 5


def test_bad_requests(server_url):
    url, _ = server_url
    assert _post(url, "/v1/generate", {})[0] == 400
    assert _post(url, "/nope", {})[0] == 404
    code, body = _get(url, "/stats")  # GET unknown POST-only route
    assert code == 200


def test_generation_overrides_are_scoped(server_url):
    url, srv = server_url
    base = srv.engine.config.max_new_tokens
    code, _ = _post(url, "/v1/generate",
                    {"prompt": "x", "max_new_tokens": 7})
    assert code == 200
    assert srv.engine.config.max_new_tokens == base  # restored


class TestSecure:
    @pytest.fixture()
    def secure_url(self, tmp_path, monkeypatch):
        monkeypatch.chdir(tmp_path)  # SecurityManager persists users.json
        srv = ChatServer(
            FakeEngine(), secure=True, bootstrap_user=("operator", "hunter22x")
        )
        httpd = ThreadingHTTPServer(("127.0.0.1", 0), srv.make_handler())
        t = threading.Thread(target=httpd.serve_forever, daemon=True)
        t.start()
        yield f"http://127.0.0.1:{httpd.server_address[1]}", srv
        httpd.shutdown()
        httpd.server_close()

    def test_auth_flow(self, secure_url):
        url, _ = secure_url
        assert _post(url, "/v1/chat", {"message": "hi"})[0] == 401
        code, body = _post(url, "/v1/auth",
                           {"user": "operator", "password": "wrong1234"})
        assert code == 401
        code, body = _post(url, "/v1/auth",
                           {"user": "operator", "password": "hunter22x"})
        assert code == 200 and body["token"]
        token = body["token"]
        code, body = _post(url, "/v1/chat", {"message": "hi"}, token=token)
        assert code == 200 and body["reply"]

    def test_input_validation(self, secure_url):
        url, _ = secure_url
        code, body = _post(url, "/v1/auth",
                           {"user": "operator", "password": "hunter22x"})
        token = body["token"]
        code, body = _post(url, "/v1/chat", {"message": "   "}, token=token)
        assert code == 400


def _post_sse(url, path, body, timeout=10):
    """POST with stream:true; return (content_type, list of data frames)."""
    req = urllib.request.Request(
        url + path, data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=timeout) as r:
        ctype = r.headers.get("Content-Type", "")
        raw = r.read().decode()
    frames = [
        line[len("data: "):]
        for line in raw.split("\n")
        if line.startswith("data: ")
    ]
    return ctype, frames


def test_streaming_sse(server_url):
    """stream:true responds as text/event-stream: one token frame per
    generated token (deltas concatenate to the final text), a done frame
    with the same schema as the non-streaming reply, then [DONE]."""
    url, srv = server_url
    _, ref = _post(url, "/v1/generate", {"prompt": "hi"})
    ctype, frames = _post_sse(url, "/v1/generate",
                              {"prompt": "hi", "stream": True})
    assert ctype.startswith("text/event-stream")
    assert frames[-1] == "[DONE]"
    events = [json.loads(f) for f in frames[:-1]]
    toks = [e for e in events if "token" in e]
    done = events[-1]
    assert done.get("done") is True
    assert len(toks) == done["tokens"] == ref["tokens"]
    assert done["text"] == ref["text"]
    assert done["stopped"] == ref["stopped"]
    # /v1/chat streams with the reply key.
    _, frames = _post_sse(url, "/v1/chat",
                          {"message": "yo", "stream": True})
    done = json.loads(frames[-2])
    assert done["done"] is True and done["reply"].startswith("tok:")
    # Stats count streamed requests/tokens too (3 requests: the non-stream
    # reference + two streams; "hi"→2 tokens ×2 + "yo"→2 tokens).
    _, stats = _get(url, "/stats")
    assert stats["requests"] >= 3 and stats["tokens_out"] >= 6


def test_streaming_errors(server_url):
    url, _ = server_url
    code, body = _post(url, "/v1/generate", {"stream": True})  # no prompt
    assert code == 400


def test_streaming_multibyte_delta_hold():
    """A multi-byte codepoint split across tokens must not bake U+FFFD
    into the delta stream: the partial decode is held and flushed at the
    next clean boundary, so concatenated deltas == final text."""

    class ByteTokenizerBackend:
        def encode(self, text):
            return list(text.encode())

    class ByteTokenizer:
        backend = ByteTokenizerBackend()

        def decode(self, tokens):
            return bytes(tokens).decode("utf-8", errors="replace")

    class ByteEngine(FakeEngine):
        def __init__(self):
            super().__init__()
            self.tokenizer = ByteTokenizer()

        def lane_tokens(self, prompt):
            return list("héllo".encode())  # é = 2 bytes, split mid-stream

    srv = ChatServer(ByteEngine())
    events = list(srv._stream_events([1], {}, "text"))
    done = events[-1]
    deltas = "".join(e["delta"] for e in events[:-1])
    assert done["text"] == "héllo"
    assert deltas == done["text"]
    # The held frame emitted an empty delta, not a replacement char.
    assert all("�" not in e["delta"] for e in events[:-1])


def test_streaming_midflight_error_emits_error_frame(server_url):
    """An engine exception after frames have been sent must surface as an
    SSE error frame + [DONE], never a second HTTP status line inside the
    open stream body."""

    class ExplodingEngine(FakeEngine):
        def lane_tokens(self, prompt):
            yield int(prompt[0])
            raise RuntimeError("device fell over")

    srv = ChatServer(ExplodingEngine())
    httpd = ThreadingHTTPServer(("127.0.0.1", 0), srv.make_handler())
    t = threading.Thread(target=httpd.serve_forever, daemon=True)
    t.start()
    try:
        url = f"http://127.0.0.1:{httpd.server_address[1]}"
        ctype, frames = _post_sse(url, "/v1/generate",
                                  {"prompt": "x", "stream": True})
        assert ctype.startswith("text/event-stream")
        assert frames[-1] == "[DONE]"
        err = json.loads(frames[-2])
        assert "device fell over" in err["error"]
        json.loads(frames[0])  # the pre-error token frame is parseable
    finally:
        httpd.shutdown()
        httpd.server_close()


def test_speculative_stream_slot_cap():
    """Speculative streams run outside the scheduler, so a slot
    semaphore (max_streams) caps them: over the limit the hint is dropped
    and the request is a scheduler lane, never a 503; slots release on
    completion AND on a close before the first event (the leak path)."""
    from luminaai_tpu.serving.server import _SlotStream

    class SpecEngine(FakeEngine):
        def generate_stream_speculative(self, prompt_tokens,
                                        max_new_tokens=None,
                                        timeout_s=None):
            yield 1
            yield 2
            yield {"tokens_generated": 2, "stopped": "eos",
                   "verify_calls": 1, "tokens_per_verify": 2.0}

    srv = ChatServer(SpecEngine(), max_streams=1)
    body = {"prompt": "a", "temperature": 0, "speculative": True}
    err1, ev1 = srv.start_stream("/v1/generate", dict(body), None)
    assert err1 is None and isinstance(ev1, _SlotStream)
    err2, ev2 = srv.start_stream("/v1/generate", dict(body), None)
    assert err2 is None and not isinstance(ev2, _SlotStream)
    assert "speculative" not in list(ev2)[-1]
    # Closing BEFORE the first next() must still release the slot.
    ev1.close()
    err3, ev3 = srv.start_stream("/v1/generate", dict(body), None)
    assert err3 is None and isinstance(ev3, _SlotStream)
    # Draining to exhaustion releases too.
    assert list(ev3)[-1]["speculative"]["verify_calls"] == 1
    err4, ev4 = srv.start_stream("/v1/generate", dict(body), None)
    assert err4 is None and isinstance(ev4, _SlotStream)
    ev4.close()


def test_stream_tail_flush_on_done_frame():
    """A stream ending mid-codepoint flushes the held tokens as the done
    frame's delta, so concatenated deltas still reproduce the text."""

    class ByteTokenizerBackend:
        def encode(self, text):
            return list(text.encode())

    class ByteTokenizer:
        backend = ByteTokenizerBackend()

        def decode(self, tokens):
            return bytes(tokens).decode("utf-8", errors="replace")

    class TruncatedEngine(FakeEngine):
        def __init__(self):
            super().__init__()
            self.tokenizer = ByteTokenizer()

        def lane_tokens(self, prompt):
            return list("hé".encode())[:-1] + [0xC3]  # ends mid-codepoint

    srv = ChatServer(TruncatedEngine())
    events = list(srv._stream_events([1], {}, "text"))
    done = events[-1]
    deltas = "".join(e["delta"] for e in events)
    assert done["text"] == deltas  # tail flushed via done frame's delta
    assert done["delta"] != ""


def test_speculative_request_path():
    """{"speculative": true} on a greedy request runs the engine's
    speculative path (stats surfaced); sampling requests silently fall
    back to the scheduler; engines without the method fall back."""

    class SpecEngine(FakeEngine):
        def __init__(self):
            super().__init__()
            self.spec_calls = 0

        def generate_speculative(self, prompt_tokens, max_new_tokens=None):
            self.spec_calls += 1
            toks = list(prompt_tokens)[:3]
            return toks, {
                "tokens_generated": len(toks), "stopped": "eos",
                "verify_calls": 2, "tokens_per_verify": 1.5,
            }

    eng = SpecEngine()
    srv = ChatServer(eng)
    httpd = ThreadingHTTPServer(("127.0.0.1", 0), srv.make_handler())
    t = threading.Thread(target=httpd.serve_forever, daemon=True)
    t.start()
    url = f"http://127.0.0.1:{httpd.server_address[1]}"
    try:
        # Greedy + speculative: engine path used, stats in the reply.
        code, body = _post(url, "/v1/generate",
                           {"prompt": "hiya", "temperature": 0,
                            "speculative": True})
        assert code == 200 and eng.spec_calls == 1
        assert body["speculative"]["verify_calls"] == 2
        assert body["text"].startswith("tok:")
        # Sampling + speculative: silently rides the scheduler.
        code, body = _post(url, "/v1/generate",
                           {"prompt": "hiya", "temperature": 0.7,
                            "speculative": True})
        assert code == 200 and eng.spec_calls == 1
        assert "speculative" not in body
        # Stats counted both.
        _, stats = _get(url, "/stats")
        assert stats["requests"] == 2
    finally:
        httpd.shutdown()
        httpd.server_close()

    # Engine without the method: plain fallback, no error.
    srv2 = ChatServer(FakeEngine())
    code, body = srv2._run_model(
        "/v1/generate", {"prompt": "hiya", "speculative": True}
    )
    assert code == 200 and "speculative" not in body

    # Slots exhausted: falls back to the scheduler, never 503s — the
    # hint must not make a servable request fail.
    eng3 = SpecEngine()
    srv3 = ChatServer(eng3, max_streams=1)
    assert srv3._stream_slots.acquire(blocking=False)  # hog the slot
    code, body = srv3._run_model(
        "/v1/generate",
        {"prompt": "hiya", "temperature": 0, "speculative": True},
    )
    assert code == 200 and "speculative" not in body
    assert eng3.spec_calls == 0


def test_aborted_stream_still_counted():
    """Closing the event generator early (client disconnect) still books
    the streamed tokens into /stats."""
    srv = ChatServer(FakeEngine())
    gen = srv._stream_events([1, 2, 3, 4], {}, "text")
    next(gen)
    next(gen)
    gen.close()
    assert srv.requests == 1
    assert srv.tokens_out == 2


def test_engine_without_stepwise_api_is_refused():
    """ChatServer has one scheduler: an engine that cannot hand it a
    step-wise decoder is refused at construction, by name."""

    class RunToCompletionEngine:
        config = FakeEngine().config
        tokenizer = FakeTokenizer()

        def generate(self, prompt_tokens, **kw):
            return list(prompt_tokens), {"stopped": "eos"}

    with pytest.raises(TypeError, match="make_stepwise.*RunToCompletion"):
        ChatServer(RunToCompletionEngine())


def test_override_clamps(server_url):
    url, srv = server_url
    code, body = _post(url, "/v1/generate",
                       {"prompt": "x", "max_new_tokens": 10**9,
                        "temperature": 99, "top_p": 5})
    assert code == 200  # clamped, not refused
    code, body = _post(url, "/v1/generate",
                       {"prompt": "x", "max_new_tokens": "lots"})
    assert code == 400


def test_health_with_query_string(server_url):
    url, _ = server_url
    code, body = _get(url, "/health?probe=1")
    assert code == 200 and body["status"] == "ok"


def test_malformed_chat_messages(server_url):
    url, _ = server_url
    code, body = _post(url, "/v1/chat", {"messages": [{"content": "hi"}]})
    assert code == 400 and "role" in body["error"]


# -- continuous batching ---------------------------------------------------
def test_paged_pool_free_list_never_double_allocates():
    """The slot free-list is the continuous scheduler's safety invariant:
    exhaustion raises (never hands out a live slot), free() of a
    non-allocated slot raises, and reuse is counted."""
    from luminaai_tpu.inference.kv_pool import PagedKVPool

    pool = PagedKVPool(None, num_slots=3, pages=4, page_size=16)
    assert pool.slot_tokens == 64
    got = [pool.alloc() for _ in range(3)]
    assert sorted(got) == [0, 1, 2]  # each slot handed out exactly once
    with pytest.raises(RuntimeError):
        pool.alloc()
    with pytest.raises(ValueError):
        pool.free(99)
    pool.lengths[got[0]] = 17
    pool.free(got[0])
    assert pool.lengths[got[0]] == 0  # length reset on free
    again = pool.alloc()
    assert again == got[0]
    assert pool.reuses == 1
    with pytest.raises(RuntimeError):
        pool.alloc()  # still exhausted: no phantom slots appeared
    pool.free(again)
    with pytest.raises(ValueError):
        pool.free(again)  # double-free rejected


def test_continuous_scheduler_admits_mid_decode():
    """A queued request must join the running decode in a freed slot
    BEFORE the longest in-flight request completes (step-level
    admission), and every request's tokens must be its own stream."""
    from luminaai_tpu.serving.server import ContinuousScheduler

    stepper = FakeStepper(num_slots=2)
    sched = ContinuousScheduler(FakeEngine(), decoder=stepper)
    results = {}
    lock = threading.Lock()

    def hit(name, first_tok, max_new):
        out = sched.submit([first_tok], {"max_new_tokens": max_new})
        with lock:
            results[name] = out

    ta = threading.Thread(target=hit, args=("a", 100, 3))
    tb = threading.Thread(target=hit, args=("b", 200, 40))
    ta.start()
    tb.start()
    import time as _time

    _time.sleep(0.05)  # let a/b occupy both slots so c queues
    tc = threading.Thread(target=hit, args=("c", 300, 3))
    tc.start()
    for t in (ta, tb, tc):
        t.join(timeout=30)
    assert set(results) == {"a", "b", "c"}
    toks_a, stats_a = results["a"]
    toks_b, stats_b = results["b"]
    toks_c, stats_c = results["c"]
    assert toks_a == [100, 101, 102]
    assert toks_b == list(range(200, 240))
    assert toks_c == [300, 301, 302]
    # c rode a freed slot while b was still decoding.
    assert stats_c["admitted_step"] < stats_b["finished_step"]
    assert stepper.pool.reuses >= 1
    assert sched.max_batch_seen == 2


def test_continuous_scheduler_switches_sampling_keys():
    """Mismatched sampling params cannot share one traced decode step;
    they park, the active generation drains, and the scheduler switches —
    every request completes."""
    from luminaai_tpu.serving.server import ContinuousScheduler

    sched = ContinuousScheduler(
        FakeEngine(), decoder=FakeStepper(num_slots=2)
    )
    results = []
    lock = threading.Lock()

    def hit(i):
        out = sched.submit(
            [50 + i], {"max_new_tokens": 4, "temperature": 0.1 * (i % 2)}
        )
        with lock:
            results.append((i, out))

    threads = [threading.Thread(target=hit, args=(i,)) for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    assert len(results) == 4
    for i, (toks, stats) in results:
        assert toks == [50 + i, 51 + i, 52 + i, 53 + i]
    assert sched.batches >= 2  # at least one key switch


def test_continuous_stream_cancel_frees_slot():
    """Closing a continuous SSE stream flags the lane cancelled; the
    scheduler frees its slot at the next step instead of decoding for a
    gone client."""
    from luminaai_tpu.serving.server import ContinuousScheduler

    stepper = FakeStepper(num_slots=1)
    sched = ContinuousScheduler(FakeEngine(), decoder=stepper)
    gen = sched.submit_stream([70], {"max_new_tokens": 10_000})
    assert next(gen) == 70
    gen.close()
    import time as _time

    deadline = _time.time() + 5.0
    while _time.time() < deadline and not stepper.pool.has_free():
        _time.sleep(0.01)
    assert stepper.pool.has_free(), "cancelled stream never freed its slot"
    # The freed slot is immediately serviceable.
    toks, stats = sched.submit([80], {"max_new_tokens": 2})
    assert toks == [80, 81]


def test_continuous_server_http_end_to_end():
    """Generation and SSE ride the continuous scheduler, /stats reports
    it."""
    from luminaai_tpu.serving.server import ContinuousScheduler

    srv = ChatServer(FakeEngine())
    assert isinstance(srv.batcher, ContinuousScheduler)
    httpd = ThreadingHTTPServer(("127.0.0.1", 0), srv.make_handler())
    t = threading.Thread(target=httpd.serve_forever, daemon=True)
    t.start()
    url = f"http://127.0.0.1:{httpd.server_address[1]}"
    try:
        code, body = _post(url, "/v1/generate",
                           {"prompt": "abc", "max_new_tokens": 3})
        assert code == 200
        assert body["text"] == "tok:97,98,99"  # ord('a'), +1, +2
        assert body["stopped"] == "length"
        ctype, frames = _post_sse(
            url, "/v1/generate",
            {"prompt": "abc", "max_new_tokens": 3, "stream": True},
        )
        assert ctype.startswith("text/event-stream")
        assert frames[-1] == "[DONE]"
        events = [json.loads(f) for f in frames[:-1]]
        toks = [e["token"] for e in events if "token" in e]
        assert toks == [97, 98, 99]
        assert events[-1]["done"] is True
        assert events[-1]["text"] == "tok:97,98,99"
        _, stats = _get(url, "/stats")
        assert stats["scheduler"] == "continuous"
        assert stats["requests"] == 2
        assert stats["kv_pool"]["num_slots"] == 2
        assert stats["decode_steps"] >= 2
    finally:
        httpd.shutdown()
        httpd.server_close()


def test_mismatched_params_requeue_not_starve():
    """Requests with different sampling params fall into separate
    generations but all complete."""
    srv = ChatServer(FakeEngine())
    httpd = ThreadingHTTPServer(("127.0.0.1", 0), srv.make_handler())
    t = threading.Thread(target=httpd.serve_forever, daemon=True)
    t.start()
    url = f"http://127.0.0.1:{httpd.server_address[1]}"
    try:
        codes = []
        lock = threading.Lock()

        def hit(i):
            code, _ = _post(
                url, "/v1/generate",
                {"prompt": "z", "temperature": 0.1 * (i % 2)},
            )
            with lock:
                codes.append(code)

        threads = [
            threading.Thread(target=hit, args=(i,)) for i in range(4)
        ]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        assert codes == [200] * 4
    finally:
        httpd.shutdown()
        httpd.server_close()


# -- telemetry: /healthz, /metrics, parity, overhead ------------------------
def test_healthz_warming_then_ready():
    """/healthz is the READINESS probe: 503 while the engine is
    compiling/warming (so the Dockerfile HEALTHCHECK holds traffic),
    200 with scheduler state once serving."""
    srv = ChatServer(FakeEngine())
    httpd = ThreadingHTTPServer(("127.0.0.1", 0), srv.make_handler())
    t = threading.Thread(target=httpd.serve_forever, daemon=True)
    t.start()
    url = f"http://127.0.0.1:{httpd.server_address[1]}"
    try:
        srv._ready.clear()  # simulate mid-compile
        code, body = _post(url, "/healthz", {})  # POST -> 404 route check
        assert code == 404
        try:
            _get(url, "/healthz")
            assert False, "expected 503"
        except urllib.error.HTTPError as e:
            assert e.code == 503
            assert json.loads(e.read())["status"] == "warming"
        # /health (liveness) stays 200 while warming; only readiness gates.
        code, _ = _get(url, "/health")
        assert code == 200
        srv.mark_ready()
        code, body = _get(url, "/healthz?probe=1")
        assert code == 200 and body["status"] == "ok"
        assert body["scheduler"] == "continuous"
        assert body["active_lanes"] == 0
        assert body["queue_depth"] == 0
        assert body["slots_free"] == 2
    finally:
        httpd.shutdown()
        httpd.server_close()


def test_healthz_warmup_flow_marks_ready():
    """warmup=True starts not-ready, drives a generation through the real
    batcher path in the background, and flips the gate when it completes."""
    import time as _time

    srv = ChatServer(FakeEngine(), warmup=True)
    assert srv._ready.wait(timeout=10), "warmup never marked ready"
    assert srv.batcher.requests_served >= 1  # warmup used the real path
    code, body = srv.handle("GET", "/healthz", {}, None)
    assert code == 200 and body["status"] == "ok"
    assert "warmup_error" not in body
    _time.sleep(0)


def test_healthz_warmup_failure_still_serves():
    """A broken warmup must not brick the server: the gate opens anyway
    and the failure is surfaced in the health payload."""

    class BrokenPrefill(FakeStepper):
        def prefill_into_slot(self, *a, **kw):
            raise RuntimeError("compile exploded")

    eng = FakeEngine()
    eng.stepper = BrokenPrefill(num_slots=2)
    srv = ChatServer(eng, warmup=True)
    assert srv._ready.wait(timeout=10)
    code, body = srv.handle("GET", "/healthz", {}, None)
    assert code == 200
    assert "compile exploded" in body.get("warmup_error", "")


def test_healthz_scheduler_state():
    srv = ChatServer(FakeEngine())
    code, body = srv.handle("GET", "/healthz", {}, None)
    assert code == 200
    assert body["scheduler"] == "continuous"
    assert body["queue_depth"] == 0


def test_metrics_endpoint_round_trips_and_covers_serving():
    """GET /metrics on a running server returns valid Prometheus text
    exposition (independent minimal parser) including the serving
    histograms (TTFT, per-token decode), KV-pool gauges, and — with a
    colocated training monitor on the same registry — training series.
    The acceptance-criterion test for the unified sink."""
    from luminaai_tpu.monitoring.logger import TrainingHealthMonitor
    from luminaai_tpu.monitoring.telemetry import MetricsRegistry
    from prom_parser import check_histogram_wellformed, parse_prometheus_text

    registry = MetricsRegistry()
    srv = ChatServer(FakeEngine(), registry=registry)
    # Training flows into the SAME registry (the unified-sink contract).
    monitor = TrainingHealthMonitor(registry=registry)
    monitor.log_step(5, {"loss": 2.0, "grad_norm": 0.5})
    httpd = ThreadingHTTPServer(("127.0.0.1", 0), srv.make_handler())
    t = threading.Thread(target=httpd.serve_forever, daemon=True)
    t.start()
    url = f"http://127.0.0.1:{httpd.server_address[1]}"
    try:
        # Generate some traffic: one batched request + one SSE stream.
        code, _ = _post(url, "/v1/generate",
                        {"prompt": "abc", "max_new_tokens": 3})
        assert code == 200
        _post_sse(url, "/v1/generate",
                  {"prompt": "abd", "max_new_tokens": 3, "stream": True})
        with urllib.request.urlopen(url + "/metrics", timeout=10) as r:
            assert r.status == 200
            ctype = r.headers.get("Content-Type", "")
            text = r.read().decode()
        assert ctype.startswith("text/plain")
        assert "version=0.0.4" in ctype

        families = parse_prometheus_text(text)  # strict: raises on junk
        for name, fam in families.items():
            assert fam["type"] is not None, f"{name} missing TYPE"
            # A labeled family with no children yet (e.g. an alert
            # counter before any alert) legally renders TYPE-only.

        # Serving histograms saw the traffic.
        for hist in ("serve_ttft_seconds", "serve_token_latency_seconds",
                     "serve_prefill_seconds", "serve_queue_wait_seconds",
                     "serve_decode_step_seconds",
                     "serve_stream_duration_seconds"):
            assert families[hist]["type"] == "histogram", hist
            check_histogram_wellformed(hist, families[hist])
        ttft_count = [
            v for (n, l, v) in families["serve_ttft_seconds"]["samples"]
            if n.endswith("_count")
        ]
        assert ttft_count == [2]  # both requests measured

        # KV-pool gauges are exported.
        for g in ("kv_pool_slots_in_use", "kv_pool_slots_free",
                  "kv_pool_pages_in_use", "kv_pool_fragmentation_rows"):
            assert families[g]["type"] == "gauge", g
        (_, _, free), = families["kv_pool_slots_free"]["samples"]
        assert free == 2  # all slots released after completion

        # Training series ride the same exposition.
        (_, _, loss), = families["training_loss"]["samples"]
        assert loss == 2.0
        assert families["training_health_score"]["type"] == "gauge"

        # HTTP counter carries route/code labels.
        http = {
            (l["route"], l["code"]): v
            for (_, l, v) in families["serve_http_requests_total"]["samples"]
        }
        assert http[("/v1/generate", "200")] == 2
    finally:
        httpd.shutdown()
        httpd.server_close()


def test_decode_parity_with_telemetry_on_off():
    """Telemetry must be observation-only: the exact token streams come
    out of the continuous scheduler with recording on and off (the
    acceptance-criterion parity check)."""
    from luminaai_tpu.monitoring.telemetry import MetricsRegistry
    from luminaai_tpu.serving.server import ContinuousScheduler

    outs = {}
    for on in (True, False):
        sched = ContinuousScheduler(
            FakeEngine(),
            decoder=FakeStepper(num_slots=2),
            registry=MetricsRegistry(),
            telemetry=on,
        )
        results = {}
        lock = threading.Lock()

        def hit(name, first_tok, max_new, sched=sched, results=results,
                lock=lock):
            out = sched.submit([first_tok], {"max_new_tokens": max_new})
            with lock:
                results[name] = out[0]

        threads = [
            threading.Thread(target=hit, args=(f"r{i}", 100 + 10 * i, 3 + i))
            for i in range(4)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
        outs[on] = results
    assert outs[True] == outs[False]
    for i in range(4):
        first = 100 + 10 * i
        assert outs[True][f"r{i}"] == list(range(first, first + 3 + i))


# slow: a wall-clock A/B of a sub-second scheduler loop; the budget is a ratio that a loaded machine breaks.
@pytest.mark.slow
def test_telemetry_overhead_within_budget():
    """Scheduler A/B with metrics on vs off: recording must stay inside
    budget. The fake stepper does no sleeping, so the workload is almost
    PURE scheduler overhead — the harshest possible ratio; the real
    decode step is orders of magnitude heavier."""
    import time as _time

    from luminaai_tpu.monitoring.telemetry import MetricsRegistry
    from luminaai_tpu.serving.server import ContinuousScheduler

    class FastStepper(FakeStepper):
        def decode_step(self, sample_key=None):
            import numpy as np

            toks = np.zeros((self.num_slots,), np.int64)
            eos = np.zeros((self.num_slots,), bool)
            produced = np.asarray(self._active, bool).copy()
            for s in range(self.num_slots):
                if self._active[s]:
                    toks[s] = self._next[s]
                    self._next[s] += 1
            self.steps += 1
            return toks, produced, eos

    def run_once(telemetry_on):
        sched = ContinuousScheduler(
            FakeEngine(),
            decoder=FastStepper(num_slots=4),
            registry=MetricsRegistry(),
            telemetry=telemetry_on,
        )
        t0 = _time.perf_counter()
        threads = [
            threading.Thread(
                target=sched.submit,
                args=([50 + i], {"max_new_tokens": 500}),
            )
            for i in range(4)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        return _time.perf_counter() - t0

    # Interleave and take mins to shed scheduler-timing noise.
    on = min(run_once(True) for _ in range(3))
    off = min(run_once(False) for _ in range(3))
    # Budget: recording may cost at most 50% on a zero-work decode step
    # plus a 20ms absolute floor for timer jitter.
    assert on <= off * 1.5 + 0.02, (on, off)


def test_speculative_stream_path():
    """{"speculative": true} on an SSE request composes the draft/verify
    loop with the streaming contract (VERDICT r5 #5 slice): greedy
    streams ride generate_stream_speculative (done frame carries the
    acceptance stats), sampled streams silently use the scheduler's
    stream, slot exhaustion falls back rather than failing, and the slot
    releases on drain."""

    class SpecStreamEngine(FakeEngine):
        def __init__(self):
            super().__init__()
            self.spec_streams = 0

        def generate_stream_speculative(self, prompt_tokens,
                                        max_new_tokens=None,
                                        timeout_s=None):
            self.spec_streams += 1
            yield from (1, 2, 3)
            yield {"tokens_generated": 3, "stopped": "eos",
                   "verify_calls": 2, "tokens_per_verify": 1.5}

    eng = SpecStreamEngine()
    srv = ChatServer(eng, max_streams=1)

    # Greedy + speculative: the draft/verify stream serves the SSE.
    err, ev = srv.start_stream(
        "/v1/generate",
        {"prompt": "abcabc", "temperature": 0, "speculative": True},
        None,
    )
    assert err is None
    events = list(ev)
    assert eng.spec_streams == 1 and eng.stepper.prefills == 0
    assert [e["token"] for e in events[:-1]] == [1, 2, 3]
    done = events[-1]
    assert done["done"] and done["stopped"] == "eos"
    assert done["speculative"]["verify_calls"] == 2

    # Slot released on drain: a second speculative stream gets it back.
    err, ev = srv.start_stream(
        "/v1/generate",
        {"prompt": "abcabc", "temperature": 0, "speculative": True},
        None,
    )
    assert err is None
    list(ev)
    assert eng.spec_streams == 2

    # Sampled + speculative: silently a scheduler lane (hint ignored).
    err, ev = srv.start_stream(
        "/v1/generate",
        {"prompt": "abcabc", "temperature": 0.7, "speculative": True},
        None,
    )
    assert err is None
    events = list(ev)
    assert eng.stepper.prefills == 1 and eng.spec_streams == 2
    assert "speculative" not in events[-1]

    # Slot hogged: the hint falls back to the scheduler's stream, never
    # 503s for a request the normal path can serve.
    assert srv._stream_slots.acquire(blocking=False)
    err, ev = srv.start_stream(
        "/v1/generate",
        {"prompt": "abcabc", "temperature": 0, "speculative": True},
        None,
    )
    assert err is None
    assert "speculative" not in list(ev)[-1]
    assert eng.spec_streams == 2 and eng.stepper.prefills == 2
    srv._stream_slots.release()


def test_engine_stream_speculative_matches_greedy_stream():
    """generate_stream_speculative must reproduce generate_stream's
    greedy token sequence exactly on a real (tiny) model, and its
    blocking collector (generate_speculative) must agree with both."""
    import jax
    import jax.numpy as jnp
    from flax import linen as nn

    from luminaai_tpu.config import Config
    from luminaai_tpu.inference.generate import GenerationEngine
    from luminaai_tpu.models.transformer import LuminaTransformer

    class _Tok:
        eos_token_id = 1
        pad_token_id = 0
        im_end = 2

        class backend:
            @staticmethod
            def encode(text):
                return [3 + (ord(c) % 60) for c in text]

        @staticmethod
        def decode(tokens):
            return " ".join(str(t) for t in tokens)

    cfg = Config(
        vocab_size=128, hidden_size=32, num_layers=2, num_heads=2,
        num_kv_heads=1, seq_length=128, use_flash_attention=False,
        precision="fp32", gradient_checkpointing=False, max_new_tokens=16,
    )
    model = LuminaTransformer(cfg)
    params = jax.jit(model.init)(jax.random.key(0), jnp.ones((1, 8), jnp.int32))[
        "params"
    ]
    params = jax.tree.map(
        lambda x: x.unbox() if isinstance(x, nn.meta.AxisMetadata) else x,
        params,
        is_leaf=lambda x: isinstance(x, nn.meta.AxisMetadata),
    )
    engine = GenerationEngine(model, params, _Tok(), cfg)
    # Repetitive prompt so the n-gram index actually drafts.
    prompt = [5, 6, 7, 8, 5, 6, 7, 8, 5, 6, 7, 8]

    ref = [
        t for t in engine.generate_stream(
            prompt, max_new_tokens=12, temperature=0.0,
            repetition_penalty=1.0, seed=0,
        )
        if not isinstance(t, dict)
    ]
    streamed, stats = [], None
    for item in engine.generate_stream_speculative(
        prompt, max_new_tokens=12, seed=0
    ):
        if isinstance(item, dict):
            stats = item
        else:
            streamed.append(item)
    assert streamed == ref, (streamed, ref)
    assert stats["verify_calls"] >= 1
    blocking, bstats = engine.generate_speculative(
        prompt, max_new_tokens=12, seed=0
    )
    assert blocking == ref
    assert bstats["tokens_generated"] == len(ref)


def test_speculative_stream_honors_request_deadline():
    """Speculative streams run outside the continuous scheduler's lane
    eviction, so the engine's decode loop enforces the per-request
    deadline: an expired timeout ends the stream with stopped='timeout'
    instead of holding its slot for the full token budget."""

    class DeadlineEngine(FakeEngine):
        def __init__(self):
            super().__init__()
            self.seen_timeout = None

        def _resolve_gen_key(self, mnt, temp, top_p, top_k, rep):
            return (int(mnt or 8), float(0.0 if temp is None else temp),
                    0, 1.0, 1.0)

        def generate_stream_speculative(self, prompt_tokens,
                                        max_new_tokens=None,
                                        timeout_s=None):
            self.seen_timeout = timeout_s
            yield 1
            yield {"tokens_generated": 1,
                   "stopped": "timeout" if timeout_s else "length"}

    eng = DeadlineEngine()
    srv = ChatServer(eng, request_timeout_s=2.5)
    err, ev = srv.start_stream(
        "/v1/generate",
        {"prompt": "abc", "temperature": 0, "speculative": True},
        None,
    )
    assert err is None
    events = list(ev)
    assert eng.seen_timeout == 2.5
    assert events[-1]["stopped"] == "timeout"


def test_speculative_stream_window_degrade_keeps_deadline():
    """When the rolling-window cache leaves no verify slack (k < 2), the
    speculative stream degrades to the plain greedy stream — but must
    NOT drop the per-request deadline on the way (the serving layer
    routed it outside the scheduler's eviction on that promise)."""
    import jax
    import jax.numpy as jnp
    from flax import linen as nn

    from luminaai_tpu.config import Config
    from luminaai_tpu.inference.generate import GenerationEngine
    from luminaai_tpu.models.transformer import LuminaTransformer

    class _Tok:
        eos_token_id = 1
        pad_token_id = 0
        im_end = 2

        class backend:
            @staticmethod
            def encode(text):
                return [3 + (ord(c) % 60) for c in text]

        @staticmethod
        def decode(tokens):
            return " ".join(str(t) for t in tokens)

    # window % 128 == 0 -> rolling slack slots - w + 1 == 1 < 2: the
    # draft can't fit, generate_stream_speculative degrades.
    cfg = Config(
        vocab_size=128, hidden_size=32, num_layers=1, num_heads=2,
        num_kv_heads=1, seq_length=512, attention_window=128,
        use_flash_attention=False, precision="fp32",
        gradient_checkpointing=False, max_new_tokens=8,
    )
    model = LuminaTransformer(cfg)
    params = jax.jit(model.init)(jax.random.key(0), jnp.ones((1, 8), jnp.int32))[
        "params"
    ]
    params = jax.tree.map(
        lambda x: x.unbox() if isinstance(x, nn.meta.AxisMetadata) else x,
        params,
        is_leaf=lambda x: isinstance(x, nn.meta.AxisMetadata),
    )
    engine = GenerationEngine(model, params, _Tok(), cfg)
    prompt = [5, 6, 7, 8] * 3

    # Expired deadline: the degraded stream stops early with 'timeout'.
    items = list(engine.generate_stream_speculative(
        prompt, max_new_tokens=8, seed=0, timeout_s=0.0
    ))
    stats = items[-1]
    assert isinstance(stats, dict)
    assert stats["stopped"] == "timeout"
    assert stats["tokens_generated"] < 8

    # No deadline: same degrade path runs to completion.
    items = list(engine.generate_stream_speculative(
        prompt, max_new_tokens=8, seed=0
    ))
    assert items[-1]["stopped"] in ("eos", "length")


# ---------------------------------------------------------------------------
# tenant QoS: fair-share admission + token-bucket gate + identity hygiene
# ---------------------------------------------------------------------------
def test_wrr_dequeue_interleaves_tenants():
    """Weighted round-robin dequeue: a hot tenant's flood alternates
    with other tenants' requests instead of draining first; weights
    grant extra dequeues per rotation (priority lanes)."""
    from luminaai_tpu.serving.server import ContinuousScheduler

    sched = ContinuousScheduler(
        FakeEngine(), decoder=FakeStepper(num_slots=2),
        tenant_weights={"vip": 2},
    )
    # The worker thread is parked in q.get(); the tenant queues are
    # worker-side state we can drive directly for a deterministic
    # dequeue-order check.
    def req(tenant, i):
        r = sched._make_request([i], {"tenant": tenant}, stream=False)
        return r

    for i in range(4):
        sched._enqueue_tenant(req("hot", i))
    for i in range(2):
        sched._enqueue_tenant(req("cold", 10 + i))
    for i in range(2):
        sched._enqueue_tenant(req("vip", 20 + i))
    order = []
    while True:
        nxt = sched._next_queued()
        if nxt is None:
            break
        order.append(nxt.tenant)
    assert len(order) == 8
    # One rotation serves every tenant before hot's flood repeats: both
    # cold requests and both vip requests land in the first 2 rotations.
    assert order.index("cold") < 3
    assert order[:5].count("hot") <= 2
    # vip (weight 2) drains both its requests inside one rotation.
    first_vip = order.index("vip")
    assert order[first_vip + 1] == "vip" or order.count("vip") == 2
    assert sched.queue_depth() == 0


def test_fair_share_keeps_starved_tenant_draining():
    """Acceptance: under an injected hot-tenant flood, the starved
    tenant's queue keeps draining — its requests complete before the
    flood's tail."""
    import time as _time

    from luminaai_tpu.serving.server import ContinuousScheduler

    sched = ContinuousScheduler(
        FakeEngine(), decoder=FakeStepper(num_slots=1)
    )
    done = []
    lock = threading.Lock()

    def hit(tenant, tok, budget):
        sched.submit([tok], {"max_new_tokens": budget, "tenant": tenant})
        with lock:
            done.append(tenant)

    # A blocker occupies the single slot while the flood + starved
    # tenant enqueue behind it.
    blocker = threading.Thread(target=hit, args=("hot", 50, 60))
    blocker.start()
    _time.sleep(0.1)
    threads = [
        threading.Thread(target=hit, args=("hot", 100 + i, 3))
        for i in range(6)
    ] + [
        threading.Thread(target=hit, args=("starved", 200 + i, 3))
        for i in range(2)
    ]
    for t in threads:
        t.start()
    for t in [blocker] + threads:
        t.join(timeout=60)
    assert len(done) == 9
    # Both starved completions land before the flood's tail: WRR admits
    # starved's requests in the first rotations after the blocker.
    last_starved = max(i for i, t in enumerate(done) if t == "starved")
    assert last_starved <= 6, done


def test_tenant_token_bucket_gate_429s_and_recovers():
    srv = ChatServer(FakeEngine(), tenant_rate_per_s=100.0, tenant_burst=2)
    # Deterministic clock for the bucket.
    now = [0.0]
    srv.tenant_bucket.clock = lambda: now[0]
    srv.tenant_bucket._buckets.clear()
    ok1 = srv.handle("POST", "/v1/generate", {"prompt": "a"}, None)
    ok2 = srv.handle("POST", "/v1/generate", {"prompt": "b"}, None)
    limited = srv.handle("POST", "/v1/generate", {"prompt": "c"}, None)
    assert ok1[0] == 200 and ok2[0] == 200
    assert limited[0] == 429
    assert "retry_after" in limited[1]
    now[0] += 1.0  # 100 tokens/s refill
    assert srv.handle("POST", "/v1/generate", {"prompt": "d"}, None)[0] == 200


def test_secure_gate_limiter_keys_are_hashed_tenants():
    """Satellite: the gate's limiter state is keyed by tenant_hash, so
    raw usernames never appear in limiter keys."""
    from luminaai_tpu.security.auth import tenant_hash

    srv = ChatServer(
        FakeEngine(), secure=True,
        bootstrap_user=("alice", "correct-horse1"),
        users_path="/dev/null",
    )
    code, payload = srv.handle(
        "POST", "/v1/auth",
        {"user": "alice", "password": "correct-horse1"}, None,
    )
    assert code == 200
    token = payload["token"]
    code, _ = srv.handle("POST", "/v1/chat", {"message": "hi"}, token)
    assert code == 200
    keys = list(srv.limiter._events)
    assert keys, "limiter recorded nothing"
    assert all(ident == tenant_hash("alice") for ident, _ in keys)
    assert all(ident != "alice" for ident, _ in keys)
