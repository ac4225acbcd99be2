"""Resilient serving-plane router (luminaai_tpu/serving/router.py).

Every failure contract here runs on an injectable clock + in-memory
transport — NO wall-clock sleeps: probes, breaker cooldowns and shed
windows advance by `clock.advance()`, and the router's backoff sleep is
a no-op recorder. The handful of real-HTTP tests at the bottom exercise
the socket seam (ChatServer replicas, the router's own HTTP surface,
the kill_replica injector) with fast local connections only.
"""

import json
import threading
import urllib.error
import urllib.request
from http.server import ThreadingHTTPServer
from types import SimpleNamespace

import pytest

from luminaai_tpu.cli import main
from luminaai_tpu.monitoring.events import FlightRecorder, filter_events
from luminaai_tpu.monitoring.telemetry import MetricsRegistry
from luminaai_tpu.monitoring.top import render_top
from luminaai_tpu.serving.router import CircuitBreaker, Router
from luminaai_tpu.serving.server import REQUEST_ID_RX, ChatServer
from luminaai_tpu.testing.faults import kill_replica, replica_5xx_burst
from tests.test_serving import FakeEngine, _get, _post, _post_sse


class FakeClock:
    def __init__(self, t=1000.0):
        self.t = float(t)

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += float(dt)


class SimReplica:
    """One in-memory ChatServer as the FakeTransport sees it: scripted
    health, 5xx/shed bursts, death, and SSE frame plans."""

    def __init__(self, name):
        self.name = name
        self.alive = True
        self.status = "ok"
        self.fail_next = 0       # POSTs answered 500
        self.shed_next = 0       # POSTs answered 503
        self.retry_after = 7
        self.posts = 0
        self.stream_frames = 3   # tokens before the done frame
        self.stream_die_after = None  # frames yielded before death

    def request(self, method, path, body, headers):
        if not self.alive:
            raise ConnectionRefusedError(f"{self.name} is dead")
        if method == "GET" and path == "/healthz":
            return 200, {}, {"status": self.status}
        if method == "GET":
            return 404, {}, {"error": "no route"}
        self.posts += 1
        if self.shed_next > 0:
            self.shed_next -= 1
            return 503, {}, {"error": "shedding",
                            "retry_after": self.retry_after}
        if self.fail_next > 0:
            self.fail_next -= 1
            return 500, {}, {"error": "boom"}
        return 200, {}, {
            "text": f"ok:{self.name}", "tokens": 3,
            "request_id": (headers or {}).get("X-Request-Id"),
        }

    def stream(self, path, body, headers):
        if not self.alive:
            raise ConnectionRefusedError(f"{self.name} is dead")
        if self.shed_next > 0:
            self.shed_next -= 1
            return 503, {}, {"error": "shedding",
                            "retry_after": self.retry_after}, None

        def frames():
            for i in range(self.stream_frames):
                if (self.stream_die_after is not None
                        and i >= self.stream_die_after):
                    raise ConnectionError(f"{self.name} died mid-stream")
                yield json.dumps({"token": i, "replica": self.name})
            yield json.dumps({"done": True, "replica": self.name})

        return 200, {}, None, frames()


class FakeTransport:
    """Routes transport calls to SimReplicas by URL."""

    def __init__(self, sims):
        self.by_url = {f"http://sim/{s.name}": s for s in sims}

    def endpoints(self):
        return [(s.name, url) for url, s in self.by_url.items()]

    def request(self, base_url, method, path, body=None, headers=None,
                timeout_s=None, cancel=None):
        return self.by_url[base_url].request(method, path, body, headers)

    def stream(self, base_url, path, body, headers=None, timeout_s=None):
        return self.by_url[base_url].stream(path, body, headers)


def make_router(n=2, **kw):
    sims = [SimReplica(f"r{i}") for i in range(n)]
    transport = FakeTransport(sims)
    clock = FakeClock()
    sleeps = []
    recorder = FlightRecorder(capacity=512)
    kw.setdefault("breaker_failures", 3)
    kw.setdefault("breaker_cooldown_s", 5.0)
    kw.setdefault("max_failovers", n - 1)
    router = Router(
        transport.endpoints(), transport=transport,
        registry=MetricsRegistry(), recorder=recorder,
        clock=clock, sleep=sleeps.append, **kw,
    )
    return SimpleNamespace(router=router, sims=sims, clock=clock,
                           sleeps=sleeps, recorder=recorder)


def metric_line(registry, prefix):
    for line in registry.render_prometheus().splitlines():
        if line.startswith(prefix):
            return float(line.rsplit(" ", 1)[1])
    return None


# -- circuit breaker FSM ----------------------------------------------------

def test_breaker_consecutive_failures_open_halfopen_close():
    clock = FakeClock()
    seen = []
    b = CircuitBreaker("r0", failures=3, cooldown_s=5.0, clock=clock,
                       on_transition=lambda bk, o, n, r: seen.append((o, n)))
    for _ in range(2):
        b.record_failure()
    assert b.state == "closed" and b.allow()
    b.record_failure()
    assert b.state == "open" and not b.allow()
    clock.advance(4.9)
    assert not b.allow()  # cooldown not elapsed
    clock.advance(0.2)
    assert b.allow()      # the ONE half-open probe
    assert b.state == "half_open"
    assert not b.allow()  # slot already owned
    b.record_success()
    assert b.state == "closed" and b.allow()
    assert seen == [("closed", "open"), ("open", "half_open"),
                    ("half_open", "closed")]


def test_breaker_halfopen_failure_reopens_and_probe_rearms():
    clock = FakeClock()
    b = CircuitBreaker("r0", failures=1, cooldown_s=5.0, clock=clock)
    b.record_failure()
    clock.advance(5.1)
    assert b.allow() and b.state == "half_open"
    b.record_failure()
    assert b.state == "open" and not b.allow()
    # A probe lost without a verdict re-arms after another cooldown.
    clock.advance(5.1)
    assert b.allow() and b.state == "half_open"
    clock.advance(5.1)
    assert b.allow()  # prior probe presumed lost: slot re-armed


def test_breaker_error_rate_opens_without_consecutive_run():
    b = CircuitBreaker("r0", failures=5, error_rate=0.5, min_requests=8,
                       clock=FakeClock())
    for _ in range(4):  # alternate ok/fail: never 5 consecutive
        b.record_success()
        b.record_failure()
    assert b.state == "open"


def test_breaker_trip_forces_open():
    b = CircuitBreaker("r0", failures=3, clock=FakeClock())
    b.trip("probe failed: ConnectionRefusedError")
    assert b.state == "open" and not b.allow()


# -- dispatch: affinity, failover, shed -------------------------------------

def test_affinity_stable_per_prompt_and_spreads_across_prompts():
    env = make_router(n=3)
    key = env.router._affinity_key("/v1/generate", {"prompt": "shared sys"})
    heads = {env.router._ordered(key)[0].name for _ in range(10)}
    assert len(heads) == 1  # same prompt, same head, every time
    spread = {
        env.router._ordered(
            env.router._affinity_key("/v1/generate", {"prompt": f"p{i}"})
        )[0].name
        for i in range(24)
    }
    assert len(spread) > 1  # distinct prompts land on distinct replicas


def test_failover_on_dead_replica_is_invisible_to_client():
    env = make_router(n=2)
    env.sims[0].alive = False
    for i in range(6):
        status, payload = env.router.dispatch(
            "/v1/generate", {"prompt": f"p{i}"})
        assert status == 200
        assert payload["text"] == "ok:r1"
    failovers = env.recorder.snapshot(type="router_failover")
    assert failovers and all(
        e["to_replica"] == "r1" and e["kind"] == "request"
        for e in failovers
    )
    # Backoff between candidates went through the injected sleep.
    assert env.sleeps and all(s >= 0 for s in env.sleeps)


def test_shed_is_a_routing_signal_not_a_client_error():
    env = make_router(n=2)
    env.sims[0].shed_next = 1
    env.sims[0].retry_after = 7
    # Pick a prompt whose affine head is the shedding replica.
    prompt = next(
        f"p{i}" for i in range(64)
        if env.router._ordered(env.router._affinity_key(
            "/v1/generate", {"prompt": f"p{i}"}))[0].name == "r0"
    )
    status, payload = env.router.dispatch(
        "/v1/generate", {"prompt": prompt})
    assert status == 200 and payload["text"] == "ok:r1"
    assert metric_line(env.router.registry,
                       'router_sheds_total{replica="r0"}') == 1
    # r0 is now on shed-cooldown: the next request skips it WITHOUT
    # contacting it, and the breaker is untouched (shed != failure).
    posts_before = env.sims[0].posts
    status, _ = env.router.dispatch("/v1/generate", {"prompt": prompt})
    assert status == 200 and env.sims[0].posts == posts_before
    assert env.router.replicas[0].breaker.state == "closed"
    # Cooldown expires on the injected clock: r0 serves again.
    env.clock.advance(7.1)
    status, payload = env.router.dispatch(
        "/v1/generate", {"prompt": prompt})
    assert status == 200 and payload["text"] == "ok:r0"


def test_all_shedding_returns_503_with_max_retry_after():
    env = make_router(n=2)
    env.sims[0].shed_next = 1
    env.sims[0].retry_after = 7
    env.sims[1].shed_next = 1
    env.sims[1].retry_after = 3
    status, payload = env.router.dispatch("/v1/generate", {"prompt": "x"})
    assert status == 503
    assert payload["retry_after"] == 7  # the max, so clients back off enough
    assert payload["request_id"]
    assert env.recorder.snapshot(type="router_shed_all")
    assert metric_line(env.router.registry,
                       "router_shed_returned_total") == 1


def test_5xx_burst_opens_breaker_then_failover_serves():
    env = make_router(n=2)
    env.sims[0].fail_next = 10
    prompt = next(
        f"p{i}" for i in range(64)
        if env.router._ordered(env.router._affinity_key(
            "/v1/generate", {"prompt": f"p{i}"}))[0].name == "r0"
    )
    for _ in range(5):
        status, _ = env.router.dispatch("/v1/generate", {"prompt": prompt})
        assert status == 200  # every 5xx absorbed by failover
    assert env.router.replicas[0].breaker.state == "open"
    assert env.recorder.snapshot(type="breaker_open")
    # Once open, r0 is skipped: its POST count stops moving.
    posts = env.sims[0].posts
    env.router.dispatch("/v1/generate", {"prompt": prompt})
    assert env.sims[0].posts == posts


# -- THE acceptance contract ------------------------------------------------

@pytest.mark.faults
def test_acceptance_kill_one_of_two_replicas_zero_client_5xx():
    """ISSUE 19 acceptance: two replicas, one dies mid-load. The router
    completes in-flight survivor streams, opens the dead replica's
    breaker within one probe round, serves every subsequent request with
    zero client-visible 5xx, and walks half-open → closed when the
    replica returns. Injected clock + transport: no wall-clock sleeps."""
    env = make_router(n=2, breaker_cooldown_s=5.0)
    router, clock = env.router, env.clock
    router.probe_all()
    assert [r.status for r in router.replicas] == ["ok", "ok"]

    # Warm traffic over both replicas.
    for i in range(8):
        status, _ = router.dispatch("/v1/generate", {"prompt": f"warm{i}"})
        assert status == 200

    # An in-flight stream pinned to the survivor (r1): start it, then
    # kill r0 mid-consumption.
    survivor_prompt = next(
        f"s{i}" for i in range(64)
        if router._ordered(router._affinity_key(
            "/v1/chat", {"message": f"s{i}", "stream": True}))[0].name == "r1"
    )
    err, frames = router.open_stream(
        "/v1/chat", {"message": survivor_prompt, "stream": True})
    assert err is None
    it = iter(frames)
    first = json.loads(next(it))
    assert first["replica"] == "r1"

    env.sims[0].alive = False  # SIGKILL equivalent: connections refused

    # The survivor's in-flight stream drains to completion.
    rest = [json.loads(f) for f in it]
    assert rest[-1]["done"] is True
    assert all(f["replica"] == "r1" for f in rest[:-1])

    # One probe round opens the dead replica's breaker (trip: a refused
    # TCP endpoint needs no statistical evidence).
    router.probe_all()
    assert router.replicas[0].breaker.state == "open"
    assert router.replicas[0].status == "down"
    opens = env.recorder.snapshot(type="breaker_open")
    assert opens and opens[-1]["replica"] == "r0"
    assert metric_line(router.registry,
                       'router_breaker_state{replica="r0"}') == 2

    # Every subsequent request lands 200 — zero client-visible 5xx.
    for i in range(10):
        clock.advance(0.3)  # stay inside the cooldown: r0 never probed
        status, payload = router.dispatch(
            "/v1/generate", {"prompt": f"post-kill {i}"})
        assert status == 200 and payload["text"] == "ok:r1"
    # Streams too.
    err, frames = router.open_stream(
        "/v1/generate", {"prompt": "post-kill stream", "stream": True})
    assert err is None
    assert json.loads(list(frames)[-1])["done"] is True

    # Replica returns: after the cooldown the next probe walks the
    # breaker half-open → closed and traffic reaches r0 again.
    env.sims[0].alive = True
    clock.advance(5.1)
    router.probe_all()
    assert router.replicas[0].breaker.state == "closed"
    assert router.replicas[0].status == "ok"
    types = [e["type"] for e in env.recorder.snapshot()
             if e["type"].startswith("breaker_")]
    assert types[-2:] == ["breaker_half_open", "breaker_close"]
    assert metric_line(router.registry,
                       'router_breaker_state{replica="r0"}') == 0
    status, _ = router.dispatch("/v1/generate", {"prompt": "recovered"})
    assert status == 200


# -- streams ----------------------------------------------------------------

@pytest.mark.faults
def test_stream_pre_first_token_fails_over_transparently():
    env = make_router(n=2)
    env.sims[0].stream_die_after = 0  # dies before the first frame
    prompt = next(
        f"p{i}" for i in range(64)
        if env.router._ordered(env.router._affinity_key(
            "/v1/generate", {"prompt": f"p{i}"}))[0].name == "r0"
    )
    err, frames = env.router.open_stream(
        "/v1/generate", {"prompt": prompt, "stream": True})
    assert err is None
    out = [json.loads(f) for f in frames]
    # No error frame: the client sees a clean stream from the survivor.
    assert out[-1]["done"] is True
    assert all(f.get("replica") == "r1" for f in out)
    fo = env.recorder.snapshot(type="router_failover")
    assert fo and fo[-1]["kind"] == "stream"


@pytest.mark.faults
def test_stream_mid_generation_surfaces_error_frame_with_request_id():
    env = make_router(n=2)
    env.sims[0].stream_die_after = 2  # two tokens reach the client first
    prompt = next(
        f"p{i}" for i in range(64)
        if env.router._ordered(env.router._affinity_key(
            "/v1/generate", {"prompt": f"p{i}"}))[0].name == "r0"
    )
    rid = "req-mid-stream-1"
    err, frames = env.router.open_stream(
        "/v1/generate", {"prompt": prompt, "stream": True},
        headers={"X-Request-Id": rid})
    assert err is None
    out = [json.loads(f) for f in frames]
    # Replaying elsewhere would duplicate the two delivered tokens, so
    # the death surfaces as an error frame carrying the original id.
    assert [f.get("token") for f in out[:2]] == [0, 1]
    assert out[-1]["error"] and out[-1]["request_id"] == rid
    assert metric_line(env.router.registry,
                       "router_stream_errors_total") == 1
    ev = env.recorder.snapshot(type="router_stream_error")
    assert ev and ev[-1]["request_id"] == rid


def test_stream_all_shedding_returns_503():
    env = make_router(n=2)
    env.sims[0].shed_next = 1
    env.sims[1].shed_next = 1
    err, frames = env.router.open_stream(
        "/v1/generate", {"prompt": "x", "stream": True})
    assert frames is None
    status, payload = err
    assert status == 503 and payload["retry_after"] >= 1


# -- hedging ----------------------------------------------------------------

class BlockingTransport(FakeTransport):
    """r0 blocks POSTs until released — the hedge must win."""

    def __init__(self, sims, slow_name):
        super().__init__(sims)
        self.slow_name = slow_name
        self.release = threading.Event()

    def request(self, base_url, method, path, body=None, headers=None,
                timeout_s=None, cancel=None):
        sim = self.by_url[base_url]
        if method == "POST" and sim.name == self.slow_name:
            self.release.wait(timeout=5.0)
        return sim.request(method, path, body, headers)


def test_hedged_dispatch_second_replica_wins():
    sims = [SimReplica("r0"), SimReplica("r1")]
    transport = BlockingTransport(sims, slow_name="r0")
    recorder = FlightRecorder(capacity=128)
    router = Router(
        transport.endpoints(), transport=transport,
        registry=MetricsRegistry(), recorder=recorder,
        sleep=lambda dt: None, hedge=True, hedge_delay_s=0.005,
        hedge_budget=1.0,
    )
    prompt = next(
        f"p{i}" for i in range(64)
        if router._ordered(router._affinity_key(
            "/v1/generate", {"prompt": f"p{i}"}))[0].name == "r0"
    )
    try:
        status, payload = router.dispatch(
            "/v1/generate", {"prompt": prompt, "max_new_tokens": 8})
        assert status == 200 and payload["text"] == "ok:r1"
    finally:
        transport.release.set()
    assert metric_line(router.registry, "router_hedges_total") == 1
    assert metric_line(router.registry, "router_hedge_wins_total") == 1
    ev = recorder.snapshot(type="router_hedge")
    assert ev and ev[-1]["primary"] == "r0" and ev[-1]["hedge"] == "r1"


def test_hedge_budget_and_eligibility_bounds():
    env = make_router(n=2, hedge=True, hedge_budget=0.1,
                      hedge_max_tokens=32)
    r = env.router
    # Streams and long generations never hedge.
    assert not r._hedge_eligible({"stream": True})
    assert not r._hedge_eligible({"max_new_tokens": 64})
    # Budget 0.1: hedges may never exceed 10% of non-stream traffic, so
    # cold traffic can't hedge at all — no tail-chasing under no load.
    assert not r._hedge_eligible({"max_new_tokens": 8})
    with r._stats_lock:
        r._nonstream_total = 9
    assert r._hedge_eligible({"max_new_tokens": 8})
    # After one hedge, another 10% of traffic must accrue first.
    with r._stats_lock:
        r._hedges_fired = 1
        r._nonstream_total = 15
    assert not r._hedge_eligible({"max_new_tokens": 8})
    with r._stats_lock:
        r._nonstream_total = 40
    assert r._hedge_eligible({"max_new_tokens": 8})
    # A hedge partner is only ever a closed-breaker, unshedded replica —
    # peeked, never consuming a half-open probe slot.
    r.replicas[1].breaker.trip("dead")
    order = r._ordered("k")
    primary = r.replicas[0] if order[0] is r.replicas[0] else r.replicas[1]
    assert r._hedge_partner(order, order[0]) is None


# -- fleet / health surfaces ------------------------------------------------

def test_healthz_aggregate_degraded_and_down():
    env = make_router(n=2)
    env.router.probe_all()
    code, payload = env.router.health_payload()
    assert (code, payload["status"]) == (200, "ok")
    env.sims[0].alive = False
    env.router.probe_all()
    code, payload = env.router.health_payload()
    # One dead replica degrades the plane but must NOT pull it from
    # rotation: the survivor is still serving.
    assert (code, payload["status"]) == (200, "degraded")
    assert payload["available"] == 1 and payload["breakers_open"] == 1
    env.sims[1].alive = False
    env.router.probe_all()
    code, payload = env.router.health_payload()
    assert (code, payload["status"]) == (503, "down")


def test_fleet_payload_and_top_render():
    env = make_router(n=2)
    env.router.probe_all()
    env.router.dispatch("/v1/generate", {"prompt": "x"})
    env.sims[1].alive = False
    env.router.probe_all()
    fleet = env.router.fleet_payload()
    assert fleet["status"] == "degraded"
    by_name = {r["replica"]: r for r in fleet["replicas"]}
    assert by_name["r1"]["breaker"] == "open"
    assert by_name["r1"]["status"] == "down"
    assert by_name["r0"]["breaker"] == "closed"
    frame = render_top({"series": {}}, source="router", fleet=fleet)
    assert "fleet — degraded (1/2 available" in frame
    assert "! r1" in frame.replace("!  r1", "! r1")  # open breaker flagged
    assert "(no series" not in frame  # router mode: fleet replaces rows


# -- real HTTP: ChatServer replicas behind the router -----------------------

@pytest.fixture()
def fleet_url():
    """Two real ChatServer replicas + the router's own HTTP surface,
    all in-process on loopback."""
    servers, httpds, urls = [], [], []
    for _ in range(2):
        srv = ChatServer(FakeEngine(), registry=MetricsRegistry(),
                         recorder=FlightRecorder(capacity=512))
        httpd = ThreadingHTTPServer(("127.0.0.1", 0), srv.make_handler())
        threading.Thread(target=httpd.serve_forever, daemon=True).start()
        servers.append(srv)
        httpds.append(httpd)
        urls.append(f"http://127.0.0.1:{httpd.server_address[1]}")
    recorder = FlightRecorder(capacity=512)
    router = Router(
        [("r0", urls[0]), ("r1", urls[1])],
        registry=MetricsRegistry(), recorder=recorder,
        sleep=lambda dt: None, max_failovers=1,
        breaker_cooldown_s=5.0,
    )
    rhttpd = ThreadingHTTPServer(("127.0.0.1", 0), router.make_handler())
    threading.Thread(target=rhttpd.serve_forever, daemon=True).start()
    yield SimpleNamespace(
        url=f"http://127.0.0.1:{rhttpd.server_address[1]}",
        router=router, servers=servers, httpds=httpds,
        replica_urls=urls, recorder=recorder,
    )
    for h in [rhttpd] + httpds:
        h.shutdown()
        h.server_close()


def test_router_http_surface_end_to_end(fleet_url):
    f = fleet_url
    code, body = _post(f.url, "/v1/generate", {"prompt": "hiya"})
    assert code == 200 and body["text"].startswith("tok:")
    assert REQUEST_ID_RX.fullmatch(body["request_id"])
    code, body = _post(f.url, "/v1/chat", {"message": "yo"})
    assert code == 200 and body["reply"].startswith("tok:")
    ctype, frames = _post_sse(f.url, "/v1/generate",
                              {"prompt": "hi", "stream": True})
    assert ctype.startswith("text/event-stream")
    assert frames[-1] == "[DONE]"
    assert json.loads(frames[-2])["done"] is True
    code, health = _get(f.url, "/healthz")
    assert code == 200 and health["status"] == "ok"
    code, fleet = _get(f.url, "/fleet")
    assert code == 200 and len(fleet["replicas"]) == 2
    with urllib.request.urlopen(f.url + "/metrics", timeout=10) as r:
        text = r.read().decode()
    assert "router_requests_total" in text
    assert "router_breaker_state" in text
    assert _post(f.url, "/nope", {})[0] == 404
    assert _get(f.url, "/healthz?verbose=1")[0] == 200


@pytest.mark.faults
def test_real_5xx_burst_opens_breaker_then_probe_recovers(fleet_url):
    """satellite 1: the replica_5xx_burst injector drives the breaker
    open over real HTTP, and a probe after the (fake-clock) cooldown
    walks it half-open → closed."""
    f = fleet_url
    clock = FakeClock()
    # Re-arm every breaker on the fake clock so recovery needs no sleep.
    for rep in f.router.replicas:
        rep.breaker._clock = clock
    head = f.router._ordered(
        f.router._affinity_key("/v1/generate", {"prompt": "burst"}))[0]
    victim = f.servers[f.replica_urls.index(head.url)]
    with replica_5xx_burst(victim, times=8) as hits:
        for _ in range(5):
            code, _ = _post(f.url, "/v1/generate", {"prompt": "burst"})
            assert code == 200  # failover absorbs every injected 500
    assert hits["calls"] >= 3
    assert head.breaker.state == "open"
    assert f.recorder.snapshot(type="breaker_open")
    # Burst exhausted + cooldown elapsed: one probe round recovers.
    clock.advance(5.1)
    f.router.probe_once(head)
    assert head.breaker.state == "closed"
    code, _ = _post(f.url, "/v1/generate", {"prompt": "burst"})
    assert code == 200


@pytest.mark.faults
def test_request_id_correlates_router_and_replica_rings(fleet_url, tmp_path,
                                                        capsys):
    """satellite 2: one X-Request-Id threads client → router → replica;
    `lumina events --request <id>` joins both flight rings."""
    f = fleet_url
    rid = "req-corr-42"
    # Kill one replica so the router books a failover event for this id.
    dead = f.router.replicas[0]
    dead_idx = f.replica_urls.index(dead.url)
    f.httpds[dead_idx].shutdown()
    f.httpds[dead_idx].server_close()
    prompt = next(
        f"p{i}" for i in range(64)
        if f.router._ordered(f.router._affinity_key(
            "/v1/generate", {"prompt": f"p{i}"}))[0] is dead
    )
    req = urllib.request.Request(
        f.url + "/v1/generate",
        data=json.dumps({"prompt": prompt}).encode(),
        headers={"Content-Type": "application/json", "X-Request-Id": rid},
    )
    with urllib.request.urlopen(req, timeout=10) as r:
        assert r.status == 200
        assert r.headers.get("X-Request-Id") == rid
        body = json.loads(r.read())
    assert body["request_id"] == rid

    survivor = f.servers[1 - dead_idx]
    router_ev = filter_events(f.recorder.snapshot(), request=rid)
    replica_ev = filter_events(survivor.recorder.snapshot(), request=rid)
    assert any(e["type"] == "router_failover" for e in router_ev)
    assert any(e["type"] == "request_received" for e in replica_ev)

    # The CLI joins the two rings from their dumps.
    d_router = tmp_path / "router"
    d_replica = tmp_path / "replica"
    f.recorder.dump_to_dir(str(d_router), reason="test")
    survivor.recorder.dump_to_dir(str(d_replica), reason="test")
    assert main(["events", "--request", rid, "--json",
                 str(d_router), str(d_replica)]) == 0
    lines = [json.loads(ln) for ln in
             capsys.readouterr().out.strip().splitlines()]
    types = {e["type"] for e in lines}
    assert "router_failover" in types and "request_received" in types
    assert all(e["request_id"] == rid for e in lines)


def test_invalid_inbound_request_id_is_replaced(fleet_url):
    f = fleet_url
    req = urllib.request.Request(
        f.url + "/v1/generate",
        data=json.dumps({"prompt": "x"}).encode(),
        headers={"Content-Type": "application/json",
                 "X-Request-Id": "bad id!! with spaces"},
    )
    with urllib.request.urlopen(req, timeout=10) as r:
        body = json.loads(r.read())
    assert body["request_id"] != "bad id!! with spaces"
    assert REQUEST_ID_RX.fullmatch(body["request_id"])


@pytest.mark.faults
def test_kill_replica_injector_refuses_new_connections(fleet_url):
    f = fleet_url
    victim = SimpleNamespace(httpd=f.httpds[0], url=f.replica_urls[0])
    kill_replica(victim)
    # Depending on backlog timing the client sees refused (URLError) or
    # reset (the kernel RSTs connections queued before the close).
    with pytest.raises((urllib.error.URLError, ConnectionResetError)):
        urllib.request.urlopen(f.replica_urls[0] + "/healthz", timeout=2)
    # The prober sees the dead endpoint and trips the breaker in ONE round.
    f.router.probe_all()
    assert f.router.replicas[0].breaker.state == "open"
    assert f.router.replicas[0].status == "down"
    # The plane keeps serving through the survivor.
    for i in range(4):
        code, _ = _post(f.url, "/v1/generate", {"prompt": f"after {i}"})
        assert code == 200


def test_lumina_top_renders_router_fleet(fleet_url, capsys):
    """satellite 4: `lumina top --url <router>` detects the /fleet shape
    and renders the per-replica table."""
    f = fleet_url
    f.router.probe_all()
    assert main(["top", "--url", f.url, "--once"]) == 0
    out = capsys.readouterr().out
    assert "fleet — ok (2/2 available" in out
    assert "r0" in out and "r1" in out
    assert main(["top", "--url", f.url, "--once", "--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["fleet"]["replicas"][0]["breaker"] == "closed"


# -- CLI wiring -------------------------------------------------------------

def test_cli_route_and_serve_replicas_parse():
    from luminaai_tpu.cli import _fleet_child_argv, build_parser

    p = build_parser()
    args = p.parse_args([
        "route", "--replica", "http://a:1", "--replica", "http://b:2",
        "--breaker-failures", "4", "--hedge", "--port", "8123",
    ])
    assert args.replicas == ["http://a:1", "http://b:2"]
    assert args.breaker_failures == 4 and args.hedge and args.port == 8123
    args = p.parse_args(["serve", "--replicas", "3"])
    assert args.replicas == 3
    # Fleet children inherit the serve argv minus the fleet/port flags.
    argv = ["serve", "--replicas", "3", "--port", "8000", "--continuous"]
    child = _fleet_child_argv(argv, 8001)
    assert "--replicas" not in child
    assert child[-2:] == ["--port", "8001"] and "--continuous" in child


def test_cli_route_rejects_duplicate_replicas(capsys):
    assert main(["route", "--replica", "http://a:1",
                 "--replica", "http://a:1/"]) == 2
    assert "duplicate" in capsys.readouterr().err


# -- fleet page index (ISSUE 20: cross-replica page sharing) ----------------

def test_page_report_registered_replicas_only_then_fifo_cap():
    """Only registered replica URLs are indexed (an unknown reporter
    could otherwise poison every lookup); the index is FIFO-bounded;
    last reporter wins per key."""
    env = make_router(n=2, page_index_capacity=3)
    out = env.router.handle_page_report(
        {"replica": "http://evil/x", "keys": ["k1"]})
    assert out == {"indexed": 0, "known": False}
    assert env.router.handle_page_lookup({"keys": ["k1"]})["owner"] is None
    out = env.router.handle_page_report(
        {"replica": "http://sim/r0", "keys": ["k1", "k2"]})
    assert out == {"indexed": 2, "known": True}
    # Last reporter wins: r1 re-reports k2.
    env.router.handle_page_report(
        {"replica": "http://sim/r1", "keys": ["k2"]})
    assert env.router.handle_page_lookup(
        {"keys": ["k2"]})["owner"] == "http://sim/r1"
    # Beyond capacity the OLDEST key falls out, never the newest.
    env.router.handle_page_report(
        {"replica": "http://sim/r0", "keys": ["k3", "k4"]})
    assert env.router.handle_page_lookup({"keys": ["k1"]})["owner"] is None
    assert env.router.handle_page_lookup(
        {"keys": ["k4"]})["owner"] == "http://sim/r0"
    assert metric_line(env.router.registry, "router_page_index_keys") == 3
    assert metric_line(
        env.router.registry, "router_page_reports_total") == 5


def test_page_lookup_contiguous_prefix_have_offset_and_health():
    """Lookup names one owner for a contiguous run from `have`, skips
    the asker, and never points a puller at a replica the router would
    not route a request to."""
    env = make_router(n=2)
    env.router.handle_page_report(
        {"replica": "http://sim/r0", "keys": ["a", "b", "c"]})
    # The covered prefix stops at the first key the owner lacks.
    res = env.router.handle_page_lookup(
        {"keys": ["a", "b", "zz"], "exclude": "http://sim/r1"})
    assert res["owner"] == "http://sim/r0" and res["keys"] == ["a", "b"]
    # have>0: the asker's resident prefix is covered without ownership
    # checks (it will not pull those), extension stays contiguous.
    res = env.router.handle_page_lookup({"keys": ["a", "b", "c"], "have": 1})
    assert res["owner"] == "http://sim/r0"
    assert res["keys"] == ["a", "b", "c"]
    assert env.router.handle_page_lookup(
        {"keys": ["a"], "have": 5})["owner"] is None
    # The asker never pulls from itself.
    assert env.router.handle_page_lookup(
        {"keys": ["a"], "exclude": "http://sim/r0"})["owner"] is None
    # Unhealthy owners are invisible: down status, then open breaker.
    r0 = env.router.replicas[0]
    r0.status = "down"
    assert env.router.handle_page_lookup({"keys": ["a"]})["owner"] is None
    r0.status = "ok"
    r0.breaker.trip("probe failed")
    assert env.router.handle_page_lookup({"keys": ["a"]})["owner"] is None
    # ...and the half-open probe slot is NOT consumed by lookups.
    env.clock.advance(6.0)
    assert env.router.handle_page_lookup({"keys": ["a"]})["owner"] is None
    assert r0.breaker.state == "open"  # lookup never called allow()
    assert r0.breaker.allow()  # the probe slot is still armed


def test_fleet_payload_shared_index_columns():
    env = make_router(n=2)
    env.router.handle_page_report(
        {"replica": "http://sim/r0", "keys": ["a", "b"]})
    by = {r["replica"]: r for r in env.router.fleet_payload()["replicas"]}
    assert by["r0"]["shared_pages"] == 2 and by["r0"]["page_reports"] == 2
    assert by["r1"]["shared_pages"] == 0 and by["r1"]["page_reports"] == 0


def test_page_index_http_routes(fleet_url):
    f = fleet_url
    key = "ab" * 32
    code, body = _post(f.url, "/pages/report",
                       {"replica": f.replica_urls[0], "keys": [key]})
    assert code == 200 and body == {"indexed": 1, "known": True}
    code, body = _post(f.url, "/pages/lookup",
                       {"keys": [key], "exclude": f.replica_urls[1]})
    assert code == 200
    assert body["owner"] == f.replica_urls[0] and body["keys"] == [key]
