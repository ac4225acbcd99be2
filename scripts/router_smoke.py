#!/usr/bin/env python
"""Multi-process router smoke: the real-SIGKILL shape of ISSUE 19.

Spawns two replica subprocesses (this script re-invoked with
--replica), fronts them with an in-process Router, drives traffic,
SIGKILLs one replica mid-load, and asserts the plane's contract:

  - every post-kill request answers 200 (zero client-visible 5xx);
  - the dead replica's breaker opens within one probe round;
  - the flight ring records breaker_open / router_failover, dumped to
    --out so `lumina events --type breaker_open <out>` replays it.

A second rung exercises ISSUE 20's cross-replica page sharing with
REAL (tiny, CPU) model replicas behind the router's HTTP index:
replica A admits + harvests a shared prompt and reports its chain
keys; replica B — hit directly, bypassing affinity — must pull A's
pages and book a remote hit with prefill tokens saved > 0.

CPU-only, stdlib HTTP — no checkpoint weights, no accelerator.
CI runs it as the "router smoke (multi-process)" step in test.yml.

Usage:
  python scripts/router_smoke.py [--out routersmoke] [--requests 8]
  python scripts/router_smoke.py --replica --port 18011   (child mode)
  python scripts/router_smoke.py --replica --paged --port 18013 \
      --router http://127.0.0.1:18015                 (paged child mode)
"""
import argparse
import json
import os
import subprocess
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def build_engine():
    """Host-only synthetic engine speaking GenerationEngine's contract."""
    import numpy as np

    from luminaai_tpu.config import Config

    class _TokBackend:
        def encode(self, text):
            return [ord(c) % 250 for c in text]

    class _Tok:
        backend = _TokBackend()

        def decode(self, tokens):
            return "tok:" + ",".join(str(t) for t in tokens)

    class _Stepper:
        """decode_step() alone: a lane plays its prompt's first four ids
        and ends. The scheduler steps it through its _StepAtCollect
        seam, as the tests' fakes are stepped."""

        def __init__(self, num_slots=2):
            self.num_slots = num_slots
            self.steps = 0
            self._free = list(range(num_slots))
            self._lanes = [None] * num_slots

        def has_free_slot(self):
            return bool(self._free)

        def acquire_slot(self):
            return self._free.pop()

        def release_slot(self, slot):
            self._lanes[slot] = None
            self._free.append(slot)

        def prefill_into_slot(self, slot, prompt, max_new_tokens=1,
                              sample_key=None, seed=None):
            lane = iter(list(prompt)[:4])
            first = next(lane, None)
            self._lanes[slot] = lane if max_new_tokens > 1 else None
            return {"token": first or 0, "prompt_tokens": len(prompt),
                    "is_stop": first is None}

        def decode_step(self, sample_key=None):
            toks = np.zeros((self.num_slots,), np.int64)
            produced = np.zeros((self.num_slots,), bool)
            eos = np.zeros((self.num_slots,), bool)
            for s, lane in enumerate(self._lanes):
                if lane is None:
                    continue
                nxt = next(lane, None)
                if nxt is None:
                    eos[s], self._lanes[s] = True, None
                else:
                    toks[s], produced[s] = nxt, True
            self.steps += 1
            return toks, produced, eos

    class _Eng:
        def __init__(self):
            self.config = Config(
                vocab_size=256, hidden_size=64, num_layers=2, num_heads=4,
                num_kv_heads=2, seq_length=64, use_flash_attention=False,
            )
            self.tokenizer = _Tok()

        def make_stepwise(self, **kw):
            return _Stepper()

        def encode_chat(self, messages):
            return self.tokenizer.backend.encode(messages[-1]["content"])

    return _Eng()


def replica_main(port: int) -> int:
    from http.server import ThreadingHTTPServer

    from luminaai_tpu.monitoring.telemetry import MetricsRegistry
    from luminaai_tpu.serving.server import ChatServer

    srv = ChatServer(build_engine(), registry=MetricsRegistry())
    httpd = ThreadingHTTPServer(("127.0.0.1", port), srv.make_handler())
    print(f"replica serving on {port}", flush=True)
    httpd.serve_forever()
    return 0


def paged_replica_main(port: int, router_url: str) -> int:
    """Child mode for the page-sharing rung: a REAL (tiny) model with
    continuous batching, a prefix cache, and a PageShareClient wired at
    the parent's router — the full replica shape of ISSUE 20, scaled to
    a CPU."""
    from http.server import ThreadingHTTPServer

    import jax
    import jax.numpy as jnp
    from flax import linen as nn

    from luminaai_tpu.config import Config
    from luminaai_tpu.data.tokenizer import ConversationTokenizer
    from luminaai_tpu.inference.generate import GenerationEngine
    from luminaai_tpu.models.transformer import LuminaTransformer
    from luminaai_tpu.monitoring.telemetry import MetricsRegistry
    from luminaai_tpu.serving.server import ChatServer

    tok = ConversationTokenizer()
    # Both paged children init from seed 0: identical weights, so A's
    # harvested pages are exactly what B would have computed.
    cfg = Config(
        vocab_size=tok.vocab_size, hidden_size=64, num_layers=2,
        num_heads=1, num_kv_heads=1, seq_length=256,
        use_flash_attention=False, precision="fp32",
        gradient_checkpointing=False, max_new_tokens=8,
        prefill_chunk_size=32, attention_backend="ragged_xla",
    )
    model = LuminaTransformer(cfg)
    params = model.init(
        jax.random.key(0), jnp.ones((1, 8), jnp.int32)
    )["params"]
    params = jax.tree.map(
        lambda x: x.unbox() if isinstance(x, nn.meta.AxisMetadata) else x,
        params, is_leaf=lambda x: isinstance(x, nn.meta.AxisMetadata),
    )
    engine = GenerationEngine(model, params, tok, cfg)
    srv = ChatServer(
        engine, registry=MetricsRegistry(),
        num_slots=2, page_size=32, prefix_cache_pages=6,
        page_share=router_url,
        page_share_self_url=f"http://127.0.0.1:{port}",
    )
    httpd = ThreadingHTTPServer(("127.0.0.1", port), srv.make_handler())
    print(f"paged replica serving on {port}", flush=True)
    httpd.serve_forever()
    return 0


def _post_json(url, path, body, timeout=60):
    import urllib.request

    req = urllib.request.Request(
        url + path, data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.status, json.loads(r.read())


def _metric(url, name, timeout=10):
    import urllib.request

    with urllib.request.urlopen(url + "/metrics", timeout=timeout) as r:
        for line in r.read().decode().splitlines():
            if line.startswith(name + " ") or line.startswith(name + "{"):
                return float(line.rsplit(" ", 1)[1])
    return 0.0


def page_share_rung(args, failures) -> dict:
    """ISSUE 20 acceptance rung: two real paged replicas + the router's
    HTTP page index; replica B (hit DIRECTLY, so affinity cannot help
    it) must book a remote hit with prefill tokens saved."""
    from http.server import ThreadingHTTPServer
    import threading

    from luminaai_tpu.monitoring.telemetry import MetricsRegistry
    from luminaai_tpu.serving.router import Router, wait_ready

    ports = [args.port + 2, args.port + 3]
    router_port = args.port + 4
    urls = [f"http://127.0.0.1:{p}" for p in ports]
    router_url = f"http://127.0.0.1:{router_port}"
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    router = Router(
        list(zip(("pA", "pB"), urls)),
        registry=MetricsRegistry(), max_failovers=1,
    )
    rhttpd = ThreadingHTTPServer(
        ("127.0.0.1", router_port), router.make_handler()
    )
    threading.Thread(target=rhttpd.serve_forever, daemon=True).start()
    children = [
        subprocess.Popen(
            [sys.executable, os.path.abspath(__file__),
             "--replica", "--paged", "--port", str(p),
             "--router", router_url],
            env=env,
        )
        for p in ports
    ]
    summary = {}
    try:
        wait_ready(urls, timeout_s=300)
        router.probe_all()  # owners must look healthy to the index
        shared = ("the quick brown fox jumps over the lazy dog " * 3
                  + "shared fleet prefix")
        # Replica A computes + harvests; its end-of-generation flush
        # reports the chain keys to the router index (async).
        status, _ = _post_json(urls[0], "/v1/generate",
                               {"prompt": shared}, timeout=240)
        if status != 200:
            failures.append(f"page rung: replica A answered {status}")
        deadline = time.monotonic() + 20
        while time.monotonic() < deadline:
            if router._page_index_counts().get(urls[0], 0) > 0:
                break
            time.sleep(0.2)
        else:
            failures.append("page rung: A's harvest report never "
                            "reached the router index")
        # Replica B DIRECTLY (bypassing affinity): cold chain, indexed
        # elsewhere -> must pull and admit as a remote hit.
        status, _ = _post_json(urls[1], "/v1/generate",
                               {"prompt": shared}, timeout=240)
        if status != 200:
            failures.append(f"page rung: replica B answered {status}")
        summary = {
            "remote_hits": _metric(urls[1],
                                   "serve_prefix_remote_hits_total"),
            "remote_pulls": _metric(urls[1],
                                    "serve_prefix_remote_pulls_total"),
            "pull_failures": _metric(
                urls[1], "serve_prefix_remote_pull_failures_total"),
            "transfer_bytes": _metric(urls[1],
                                      "serve_page_transfer_bytes_total"),
            "prefill_tokens_saved": _metric(
                urls[1], "serve_prefill_tokens_saved_total"),
            "indexed_keys_a": router._page_index_counts().get(urls[0], 0),
        }
        if summary["remote_hits"] < 1:
            failures.append(
                f"page rung: B booked no remote hit ({summary})")
        if summary["prefill_tokens_saved"] <= 0:
            failures.append(
                f"page rung: B saved no prefill tokens ({summary})")
        if summary["transfer_bytes"] <= 0:
            failures.append(
                f"page rung: no page bytes crossed replicas ({summary})")
        return summary
    finally:
        rhttpd.shutdown()
        rhttpd.server_close()
        for c in children:
            if c.poll() is None:
                c.terminate()
        deadline = time.monotonic() + 15
        for c in children:
            try:
                c.wait(timeout=max(0.1, deadline - time.monotonic()))
            except subprocess.TimeoutExpired:
                c.kill()


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--replica", action="store_true")
    ap.add_argument("--paged", action="store_true")
    ap.add_argument("--port", type=int, default=18011)
    ap.add_argument("--router", default="")
    ap.add_argument("--out", default="routersmoke")
    ap.add_argument("--requests", type=int, default=8)
    args = ap.parse_args()
    if args.replica:
        if args.paged:
            return paged_replica_main(args.port, args.router)
        return replica_main(args.port)

    from luminaai_tpu.monitoring.events import FlightRecorder
    from luminaai_tpu.monitoring.telemetry import MetricsRegistry
    from luminaai_tpu.serving.router import Router, wait_ready
    from luminaai_tpu.testing.faults import kill_replica

    ports = [args.port, args.port + 1]
    urls = [f"http://127.0.0.1:{p}" for p in ports]
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    children = [
        subprocess.Popen(
            [sys.executable, os.path.abspath(__file__),
             "--replica", "--port", str(p)],
            env=env,
        )
        for p in ports
    ]
    failures = []
    try:
        wait_ready(urls, timeout_s=120)
        recorder = FlightRecorder(capacity=2048)
        router = Router(
            list(zip(("r0", "r1"), urls)),
            registry=MetricsRegistry(), recorder=recorder,
            max_failovers=1, breaker_cooldown_s=5.0,
        )
        router.probe_all()

        def drive(n, tag):
            ok = 0
            for i in range(n):
                status, payload = router.dispatch(
                    "/v1/generate", {"prompt": f"{tag} {i}"})
                if status == 200:
                    ok += 1
                else:
                    failures.append(f"{tag} {i}: http {status}: {payload}")
            return ok

        warm_ok = drive(args.requests, "warm")
        if warm_ok != args.requests:
            failures.append(f"warm phase: {warm_ok}/{args.requests} ok")

        # Real SIGKILL, mid-load: no FIN, no drain, sockets just die.
        kill_replica(children[1])
        children[1].wait(timeout=30)
        killed_ok = drive(args.requests, "post-kill")
        if killed_ok != args.requests:
            failures.append(
                f"post-kill phase: {killed_ok}/{args.requests} ok "
                "(client-visible failure after replica death)"
            )
        router.probe_all()  # one probe round must open the breaker
        state = router.replicas[1].breaker.state
        if state != "open":
            failures.append(f"breaker after probe: {state} (want open)")
        after_ok = drive(4, "post-probe")
        if after_ok != 4:
            failures.append(f"post-probe phase: {after_ok}/4 ok")

        dump = recorder.dump_to_dir(args.out, reason="router_smoke")
        page_share = page_share_rung(args, failures)
        summary = {
            "replicas": 2,
            "warm_ok": warm_ok,
            "post_kill_ok": killed_ok,
            "post_probe_ok": after_ok,
            "breaker_r1": state,
            "failovers": len(recorder.snapshot(type="router_failover")),
            "breaker_open_events": len(
                recorder.snapshot(type="breaker_open")),
            "page_share": page_share,
            "dump": dump,
            "failures": failures,
        }
        print(json.dumps(summary))
        return 1 if failures else 0
    finally:
        for c in children:
            if c.poll() is None:
                c.terminate()
        deadline = time.monotonic() + 15
        for c in children:
            try:
                c.wait(timeout=max(0.1, deadline - time.monotonic()))
            except subprocess.TimeoutExpired:
                c.kill()


if __name__ == "__main__":
    sys.exit(main())
