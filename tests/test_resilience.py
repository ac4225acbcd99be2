"""Fault-injection resilience suite (pytest marker: `faults`).

Proves the recovery story is a CONTRACT, not incidental code
(docs/resilience.md): exact-resume data state (kill at step k, resume,
batch/loss streams bitwise-identical to an uninterrupted run), blocking
emergency saves, restore fallback-walk past a corrupt latest checkpoint,
the OOM backoff ladder under an injected device OOM, rollback landing
strictly before a loss spike, and serving graceful degradation (drain,
deadlines, overload shedding). Everything runs on CPU via
luminaai_tpu/testing/faults.py injectors.
"""

import json
import os
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request
from http.server import ThreadingHTTPServer

import numpy as np
import pytest

import jax.numpy as jnp

from luminaai_tpu.config import Config
from luminaai_tpu.data.dataset import PackedDataset, PrefetchLoader, TokenCache
from luminaai_tpu.monitoring.telemetry import MetricsRegistry, get_registry
from luminaai_tpu.serving.server import (
    ChatServer,
    ContinuousScheduler,
    RequestTimeout,
)
from luminaai_tpu.testing.faults import (
    corrupt_checkpoint,
    fail_step_at,
    preempt_at_step,
    slow_decode,
)

pytestmark = pytest.mark.faults


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------
def tiny_cfg(out, **kw) -> Config:
    base = dict(
        vocab_size=64, hidden_size=32, num_layers=1, num_heads=2,
        num_kv_heads=1, seq_length=16, batch_size=8,
        use_flash_attention=False, gradient_checkpointing=False,
        precision="fp32", max_steps=8, eval_every_n_batches=10**6,
        save_every_n_batches=10**6, health_check_interval=1000,
        output_dir=str(out), learning_rate=1e-3,
    )
    base.update(kw)
    return Config(**base)


def gen_loader(n_batches=200) -> PrefetchLoader:
    """Deterministic epoch-aware synthetic loader (exact-resume capable)."""

    def gen(epoch=0):
        rng = np.random.RandomState(epoch)
        for _ in range(n_batches):
            yield {"input_ids": rng.randint(1, 60, size=(8, 16)).astype(np.int32)}

    return PrefetchLoader(gen, prefetch=2)


def record_steps(trainer, sink):
    """Record (input batch, loss) per EXECUTED train step — the
    authoritative 'trained batch stream' the resume contract compares."""
    orig = trainer.train_step

    def wrap(state, batch):
        arr = np.asarray(batch["input_ids"]).copy()
        out = orig(state, batch)
        sink.append((arr, float(out[1]["loss"])))
        return out

    trainer.train_step = wrap


# ---------------------------------------------------------------------------
# data-layer exact-resume state (no trainer)
# ---------------------------------------------------------------------------
def _build_cache(tmp_path) -> TokenCache:
    rng = np.random.RandomState(0)
    docs = [rng.randint(1, 60, size=rng.randint(5, 40)).tolist()
            for _ in range(60)]
    return TokenCache(str(tmp_path / "cache")).build(iter(docs))


def test_packed_dataset_state_roundtrip(tmp_path):
    """state_dict/load_state_dict mid-epoch: the restored stream is the
    exact continuation — nothing replayed, nothing dropped — across the
    epoch boundary too."""
    cache = _build_cache(tmp_path)

    def mk():
        return PackedDataset(cache, batch_size=8, seq_length=16,
                             shuffle_seed=0)

    ref = []
    ds = mk()
    for _ in range(2):
        ref.extend(b["input_ids"].copy() for b in ds)

    ds2 = mk()
    it = iter(ds2)
    got = [next(it)["input_ids"].copy() for _ in range(3)]
    state = ds2.state_dict()
    assert state["epoch"] == 0 and state["batch_index"] == 3
    it.close()

    ds3 = mk()
    ds3.load_state_dict(state)
    for _ in range(2):
        got.extend(b["input_ids"].copy() for b in ds3)
    got = got[: len(ref)]
    assert len(got) == len(ref)
    for a, b in zip(ref, got):
        np.testing.assert_array_equal(a, b)


def test_packed_dataset_state_restores_difficulty(tmp_path):
    """The curriculum difficulty snapshot rides in the state: a resumed
    dataset filters docs exactly like the interrupted one did."""
    cache = _build_cache(tmp_path)
    ds = PackedDataset(cache, batch_size=8, seq_length=16, shuffle_seed=0)
    ds.set_difficulty(0.4)
    state = ds.state_dict()
    assert state["difficulty"] == 0.4
    ds2 = PackedDataset(cache, batch_size=8, seq_length=16, shuffle_seed=0)
    ds2.load_state_dict(state)
    assert ds2.difficulty == 0.4
    a = [b["input_ids"].copy() for b in ds]
    b = [b["input_ids"].copy() for b in ds2]
    assert len(a) == len(b)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)


def test_prefetch_loader_epoch_aware_resume():
    """PrefetchLoader passes the epoch to epoch-aware batch_fns and its
    skip-based fast-forward continues the stream exactly, including
    per-epoch reshuffles after the restart."""

    def gen(epoch):
        rng = np.random.RandomState(epoch)
        for _ in range(5):
            yield {"input_ids": rng.randint(0, 9, size=(2, 3))}

    ref = []
    pl = PrefetchLoader(gen, prefetch=2)
    for _ in range(2):
        ref.extend(b["input_ids"].copy() for b in pl)

    pl2 = PrefetchLoader(gen, prefetch=2)
    it = iter(pl2)
    got = [next(it)["input_ids"].copy() for _ in range(3)]
    state = pl2.state_dict()
    # The loader's own cursor counts batches YIELDED: standalone
    # state_dict/load_state_dict round-trips without a trainer.
    assert state["epoch"] == 0 and state["batch_index"] == 3
    it.close()

    pl3 = PrefetchLoader(gen, prefetch=2)
    pl3.load_state_dict(state)
    for _ in range(2):
        got.extend(b["input_ids"].copy() for b in pl3)
    assert len(got) == len(ref)
    for a, b in zip(ref, got):
        np.testing.assert_array_equal(a, b)


def test_blend_iterator_resume(tmp_path):
    """Multi-source mixture positions are checkpointable: a resumed blend
    continues at the exact record the interrupted one stopped at."""
    from luminaai_tpu.data.multi_source import MultiSourcePipeline

    for name, n in (("a", 30), ("b", 20)):
        with open(tmp_path / f"{name}.jsonl", "w") as f:
            for i in range(n):
                f.write(json.dumps({"text": f"{name}{i}"}) + "\n")
    shards = {"a": [str(tmp_path / "a.jsonl")],
              "b": [str(tmp_path / "b.jsonl")]}
    pipe = MultiSourcePipeline(None, {"a": 0.5, "b": 0.5})

    ref = [r["text"] for r in pipe.iter_blended(shards, seed=7)]
    it = pipe.iter_blended(shards, seed=7)
    got = []
    for r in it:
        got.append(r["text"])
        if len(got) == 11:
            break
    state = it.state_dict()
    assert state["emitted"] == 11 and sum(state["per_source"].values()) == 11
    it2 = pipe.iter_blended(shards, seed=7, state=state)
    got.extend(r["text"] for r in it2)
    assert got == ref


# ---------------------------------------------------------------------------
# kill-and-resume contract (acceptance criterion)
# ---------------------------------------------------------------------------
def test_kill_and_resume_bitwise_identical(tmp_path):
    """THE resilience contract: preempt at step 4 of 8, resume in a fresh
    trainer, and the trained-batch AND loss streams are bitwise-identical
    to an uninterrupted run — no batch replayed, none dropped."""
    from luminaai_tpu.training.trainer import Trainer

    cache = _build_cache(tmp_path)

    def loader():
        ds = PackedDataset(cache, batch_size=8, seq_length=16,
                           shuffle_seed=0)
        return PrefetchLoader(lambda: iter(ds), prefetch=2, source=ds)

    ref = []
    ta = Trainer(tiny_cfg(tmp_path / "a"), train_data=loader(),
                 checkpoint_dir=str(tmp_path / "a" / "ckpt"))
    record_steps(ta, ref)
    sa = ta.train()
    ta.close()
    assert sa["final_step"] == 8 and len(ref) == 8

    got = []
    tb = Trainer(tiny_cfg(tmp_path / "b"), train_data=loader(),
                 checkpoint_dir=str(tmp_path / "b" / "ckpt"))
    record_steps(tb, got)
    with preempt_at_step(tb, 4):
        sb = tb.train()
    tb.close()
    assert sb["preempted"] is True and sb["final_step"] == 4
    assert get_registry().get("preemptions_total").value >= 1
    # The emergency save COMMITTED (blocking): the step dir is on disk.
    assert (tmp_path / "b" / "ckpt" / "4").is_dir()

    tb2 = Trainer(tiny_cfg(tmp_path / "b"), train_data=loader(),
                  checkpoint_dir=str(tmp_path / "b" / "ckpt"))
    assert tb2.global_step == 4
    assert tb2._resumed_exact_data_state is True
    record_steps(tb2, got)
    sb2 = tb2.train()
    tb2.close()
    assert sb2["final_step"] == 8
    assert sb2["resumed_exact_data_state"] is True

    assert len(got) == len(ref)
    for i, ((ba, la), (bb, lb)) in enumerate(zip(ref, got)):
        np.testing.assert_array_equal(ba, bb, err_msg=f"batch {i} differs")
        assert la == lb, f"loss {i}: {la} != {lb}"


# ---------------------------------------------------------------------------
# restore hardening
# ---------------------------------------------------------------------------
def test_corrupt_latest_checkpoint_falls_back(tmp_path):
    """A truncated latest checkpoint (kill mid-commit) must not kill the
    resume: the restore walks back to the newest intact step and counts
    the fallback."""
    from luminaai_tpu.training.trainer import Trainer

    cfg = tiny_cfg(tmp_path, max_steps=4, save_every_n_batches=2)
    t = Trainer(cfg, train_data=gen_loader(),
                checkpoint_dir=str(tmp_path / "ckpt"))
    t.train()
    t.close()
    assert sorted(
        int(p) for p in os.listdir(tmp_path / "ckpt") if p.isdigit()
    ) == [2, 4]

    corrupt_checkpoint(tmp_path / "ckpt", 4)
    before = get_registry().get("checkpoint_restore_fallbacks_total").value
    t2 = Trainer(tiny_cfg(tmp_path, max_steps=4), train_data=gen_loader(),
                 checkpoint_dir=str(tmp_path / "ckpt"))
    after = get_registry().get("checkpoint_restore_fallbacks_total").value
    assert t2.global_step == 2  # newest INTACT step, not a crash
    assert t2._resumed_exact_data_state is True  # step-2 cursor restored
    assert after - before >= 1
    t2.close()


def test_emergency_save_blocks_and_survives_immediate_exit(tmp_path):
    """Satellite regression: emergency_save must not return until the
    async orbax commit has fully landed. The child process emergency-saves
    and os._exit()s IMMEDIATELY (no GC, no atexit, no orbax finalizers);
    the checkpoint must still restore here, bit-exact, with its data
    cursor."""
    child = f"""
import os
os.environ["JAX_PLATFORMS"] = "cpu"
import numpy as np
from luminaai_tpu.config import Config
from luminaai_tpu.training.checkpoint import CheckpointManager

class S:
    def __init__(self, **kw): self.__dict__.update(kw)
    def replace(self, **kw):
        d = dict(self.__dict__); d.update(kw); return S(**d)

cm = CheckpointManager(Config(), {str(tmp_path / 'ckpt')!r})
state = S(params={{"w": np.arange(8, dtype=np.float32)}},
          opt_state={{"m": np.zeros(8, np.float32)}},
          step=np.asarray(7), rng=np.zeros((2,), np.uint32))
ok = cm.emergency_save(state, 7, "sigterm preemption",
                       data_state={{"epoch": 1, "batch_index": 3}})
os._exit(0 if ok else 1)  # the exit a preempted process performs
"""
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run(
        [sys.executable, "-c", child], env=env, timeout=180,
        capture_output=True, text=True,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert proc.returncode == 0, proc.stderr[-2000:]

    from luminaai_tpu.training.checkpoint import CheckpointManager

    class S:
        def __init__(self, **kw):
            self.__dict__.update(kw)

        def replace(self, **kw):
            d = dict(self.__dict__)
            d.update(kw)
            return S(**d)

    cm = CheckpointManager(Config(), str(tmp_path / "ckpt"))
    target = S(params={"w": np.zeros(8, np.float32)},
               opt_state={"m": np.zeros(8, np.float32)},
               step=np.asarray(0), rng=np.zeros((2,), np.uint32))
    restored = cm.restore(target, 7)
    np.testing.assert_array_equal(
        restored.params["w"], np.arange(8, dtype=np.float32)
    )
    meta = cm.load_metadata(7)
    assert meta["data_state"] == {"epoch": 1, "batch_index": 3}
    assert meta["metrics"].get("emergency") == 1.0
    cm.close()


def test_emergency_save_waits_even_when_save_raises(tmp_path):
    """The blocking flush lives in a finally: a failing save still waits
    for any in-flight commit before returning (and reports False)."""
    from luminaai_tpu.training.checkpoint import CheckpointManager

    cm = CheckpointManager(Config(), str(tmp_path / "ckpt"))
    calls = []
    cm.save = lambda *a, **k: (_ for _ in ()).throw(RuntimeError("boom"))
    orig_wait = cm.wait
    cm.wait = lambda: (calls.append("wait"), orig_wait())[0]
    before = get_registry().get("emergency_saves_total")
    ok = cm.emergency_save(object(), 3, "non-finite loss")
    assert ok is False
    assert calls == ["wait"]  # flushed before returning
    assert before.labels(reason="non_finite").value >= 1
    cm.close()


# ---------------------------------------------------------------------------
# OOM ladder + rollback fence
# ---------------------------------------------------------------------------
def test_oom_ladder_recovers_from_injected_oom(tmp_path):
    """An injected RESOURCE_EXHAUSTED on step 2 must engage the backoff
    ladder: microbatch split (accum x2), recompile, and run to
    completion — not crash."""
    from luminaai_tpu.training.trainer import Trainer

    t = Trainer(tiny_cfg(tmp_path, max_steps=4, auto_resume=False),
                train_data=gen_loader(),
                checkpoint_dir=str(tmp_path / "ckpt"))
    assert t.config.gradient_accumulation_steps == 1
    with fail_step_at(t, 2) as stats:
        summary = t.train_with_oom_protection()
    assert stats["raised"] == 1
    assert summary["final_step"] == 4
    assert t.config.gradient_accumulation_steps == 2
    assert any(i["kind"] == "microbatch_split" for i in t._interventions)
    t.close()


def test_rollback_lands_strictly_before_spike(tmp_path):
    """Satellite (orchestrator.py rollback fence): periodic saves keep
    landing during a finite loss spike, so the LATEST checkpoint holds
    diverged weights — the rollback must restore the last healthy step
    (60), never the in-spike save (70)."""
    from luminaai_tpu.training.orchestrator import AdaptiveTrainingOrchestrator
    from luminaai_tpu.training.trainer import Trainer

    cfg = tiny_cfg(tmp_path, max_steps=1000, health_check_interval=10,
                   auto_resume=False)
    t = Trainer(cfg, train_data=gen_loader(),
                checkpoint_dir=str(tmp_path / "ckpt"))
    orch = AdaptiveTrainingOrchestrator(t)

    def save_at(step):
        t.global_step = step
        t.state = t.state.replace(
            step=jnp.asarray(step, t.state.step.dtype)
        )
        t.save_checkpoint(force=True)

    for step in range(1, 61):  # healthy regime, checkpoints at 20/40/60
        if step in (20, 40, 60):
            save_at(step)
        orch.on_metrics(step, {"loss": 1.0, "grad_norm": 1.0})
    for step in range(61, 75):  # spike; a save lands DURING it (step 70)
        if step == 70:
            save_at(step)
        orch.on_metrics(step, {"loss": 9.0, "grad_norm": 1.0})
    t.checkpoints.wait()

    applied = [d for d in orch.decisions
               if d.kind == "rollback" and d.applied]
    assert applied, "loss spike did not trigger a rollback"
    assert t.global_step == 60, (
        f"rolled back to {t.global_step}: the step-70 checkpoint holds "
        "spiked weights and must not be the restore target"
    )
    t.close()


# ---------------------------------------------------------------------------
# serving graceful degradation (hermetic stubs, no jax decode)
# ---------------------------------------------------------------------------
class _Tok:
    class backend:
        @staticmethod
        def encode(text):
            return [ord(c) % 250 for c in text]

    def decode(self, tokens):
        return ",".join(str(t) for t in tokens)


class _Stepper:
    """Deterministic StepwiseDecoder double over a real PagedKVPool
    (mirrors tests/test_serving.py's FakeStepper)."""

    def __init__(self, num_slots=2, slot_tokens=64):
        from luminaai_tpu.inference.kv_pool import PagedKVPool

        self.num_slots = num_slots
        self.slot_tokens = slot_tokens
        self.pool = PagedKVPool(None, num_slots, 1, slot_tokens)
        self.steps = 0
        self._active = [False] * num_slots
        self._next = [0] * num_slots

    def has_free_slot(self):
        return self.pool.has_free()

    def acquire_slot(self):
        return self.pool.alloc()

    def release_slot(self, slot):
        self._active[slot] = False
        self.pool.free(slot)

    def lane_full(self, slot):
        return False

    def prefill_into_slot(self, slot, prompt, max_new_tokens=1,
                          sample_key=None, seed=None):
        first = int(prompt[0])
        self._active[slot] = max_new_tokens > 1
        self._next[slot] = first + 1
        self.pool.lengths[slot] = len(prompt)
        return {"token": first, "prompt_tokens": len(prompt),
                "is_stop": False}

    def decode_step(self, sample_key=None):
        time.sleep(0.005)
        toks = np.zeros((self.num_slots,), np.int64)
        eos = np.zeros((self.num_slots,), bool)
        produced = np.asarray(self._active, bool).copy()
        for s in range(self.num_slots):
            if self._active[s]:
                toks[s] = self._next[s]
                self._next[s] += 1
        self.steps += 1
        return toks, produced, eos


class _Engine:
    def __init__(self):
        self.config = Config(
            vocab_size=256, hidden_size=64, num_layers=2, num_heads=4,
            num_kv_heads=2, seq_length=64, use_flash_attention=False,
        )
        self.tokenizer = _Tok()
        self.stepper = _Stepper(2)

    def make_stepwise(self, **kw):
        return self.stepper

    def encode_chat(self, messages):
        return self.tokenizer.backend.encode(messages[-1]["content"])


def test_deadline_evicts_overdue_lane():
    """A slow/stuck lane past its deadline is evicted: the blocking
    submit raises RequestTimeout, the slot frees, and the timeout
    counter increments."""
    reg = MetricsRegistry()
    eng = _Engine()
    sched = ContinuousScheduler(eng, decoder=eng.stepper, registry=reg)
    with slow_decode(eng.stepper, 0.05):
        with pytest.raises(RequestTimeout):
            sched.submit([40], {"max_new_tokens": 500, "timeout_s": 0.2})
    assert reg.get("serving_requests_timed_out_total").value == 1
    # The slot was released: a fresh request completes normally.
    toks, stats = sched.submit([50], {"max_new_tokens": 3})
    assert toks == [50, 51, 52]


def test_deadline_sse_stream_gets_error_event():
    """An SSE stream whose lane goes overdue receives an error frame
    (data: {"error": ...}) followed by [DONE] — not a hung connection."""
    eng = _Engine()
    srv = ChatServer(eng, registry=MetricsRegistry(),
                     request_timeout_s=0.2)
    httpd = ThreadingHTTPServer(("127.0.0.1", 0), srv.make_handler())
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    try:
        with slow_decode(eng.stepper, 0.05):
            req = urllib.request.Request(
                f"http://127.0.0.1:{httpd.server_address[1]}/v1/generate",
                data=json.dumps({"prompt": "hello", "stream": True,
                                 "max_new_tokens": 500}).encode(),
                headers={"Content-Type": "application/json"},
            )
            frames = []
            with urllib.request.urlopen(req, timeout=30) as r:
                assert r.status == 200
                for line in r:
                    line = line.decode().strip()
                    if line.startswith("data: "):
                        frames.append(line[6:])
        assert frames[-1] == "[DONE]"
        err_frames = [f for f in frames[:-1] if "error" in json.loads(f)]
        assert err_frames, frames
        assert "deadline exceeded" in json.loads(err_frames[-1])["error"]
    finally:
        httpd.shutdown()
        httpd.server_close()


def test_overload_returns_503_with_retry_after():
    """Queue-depth overload sheds with 503 + Retry-After (header and
    body) instead of queuing unboundedly, and counts the rejection."""
    reg = MetricsRegistry()
    srv = ChatServer(_Engine(), registry=reg, max_queue_depth=1)
    srv.batcher.queue_depth = lambda: 99  # saturated scheduler
    httpd = ThreadingHTTPServer(("127.0.0.1", 0), srv.make_handler())
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    try:
        req = urllib.request.Request(
            f"http://127.0.0.1:{httpd.server_address[1]}/v1/generate",
            data=json.dumps({"prompt": "x"}).encode(),
            headers={"Content-Type": "application/json"},
        )
        with pytest.raises(urllib.error.HTTPError) as exc:
            urllib.request.urlopen(req, timeout=10)
        assert exc.value.code == 503
        assert int(exc.value.headers["Retry-After"]) >= 1
        body = json.loads(exc.value.read())
        assert "overloaded" in body["error"]
    finally:
        httpd.shutdown()
        httpd.server_close()
    assert reg.get("serving_overload_rejections_total").value == 1


def test_drain_finishes_inflight_and_reports_healthz():
    """begin_drain stops admissions (503 + retry_after) while /healthz
    stays 200 advertising `draining` (+ gauge); the in-flight generation
    completes and drain() reports idle."""
    reg = MetricsRegistry()
    srv = ChatServer(_Engine(), registry=reg)
    code, body = srv.handle("GET", "/healthz", {}, None)
    assert code == 200 and body["status"] == "ok"

    res = {}

    def inflight():
        res["out"] = srv.batcher.submit([60], {"max_new_tokens": 30})

    th = threading.Thread(target=inflight)
    th.start()
    time.sleep(0.03)  # let it occupy a lane
    srv.begin_drain()

    code, body = srv.handle("POST", "/v1/generate", {"prompt": "hi"}, None)
    assert code == 503 and body["retry_after"] >= 1
    err, events = srv.start_stream("/v1/chat", {"message": "hi"}, None)
    assert err is not None and err[0] == 503 and events is None  # SSE too

    code, body = srv.handle("GET", "/healthz", {}, None)
    assert code == 200 and body["status"] == "draining"
    assert reg.get("serve_draining").value == 1.0

    th.join(timeout=10)
    assert len(res["out"][0]) == 30  # in-flight lane ran to completion
    assert srv.drain(5.0) is True


# ---------------------------------------------------------------------------
# end-to-end: SIGTERM → RESUMABLE_EXIT → resume (CLI)
# ---------------------------------------------------------------------------
# slow: two whole CLI child processes (start-up, train, emergency save, resume), ~22 s alone and several times that beside five busy workers; test_kill_and_resume_bitwise_identical above holds the same contract in-process in tier-1.
@pytest.mark.slow
def test_cli_sigterm_exits_resumable_and_resumes(tmp_path):
    """Full preemption loop through the CLI: SIGTERM mid-training →
    graceful stop + emergency save → exit code RESUMABLE_EXIT (75) →
    `resume` continues with exact data state."""
    from luminaai_tpu.cli import RESUMABLE_EXIT

    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("PYTHONPATH", None)
    env.pop("XLA_FLAGS", None)  # conftest's 8-device mesh is ours, not the CLI's
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = str(tmp_path / "run")
    args = [
        sys.executable, "-m", "luminaai_tpu", "train", "--preset", "debug",
        "--synthetic", "--no-moe", "--batch-size", "8", "--seq-length", "32",
        "--steps", "1000000", "--output-dir", out, "--quiet",
        "--no-adaptive",
    ]
    log_path = tmp_path / "child.log"
    ckpt_dir = os.path.join(out, "checkpoints")
    with open(log_path, "w") as log:
        # stdout goes to a FILE: the debug preset logs at DEBUG level and
        # an unread PIPE would fill and block the child mid-init (the
        # signal would then land before the handler exists).
        proc = subprocess.Popen(args, env=env, cwd=repo, stdout=log,
                                stderr=subprocess.STDOUT, text=True)
        try:
            # Signal only once the train LOOP is demonstrably running:
            # the first periodic checkpoint dir proves the handler is
            # installed and steps are executing.
            deadline = time.time() + 240
            while time.time() < deadline:
                if proc.poll() is not None:
                    break
                if os.path.isdir(ckpt_dir) and any(
                    p.isdigit() for p in os.listdir(ckpt_dir)
                ):
                    break
                time.sleep(0.5)
            assert proc.poll() is None, "training exited before signal"
            assert os.path.isdir(ckpt_dir), "training never checkpointed"
            proc.send_signal(__import__("signal").SIGTERM)
            proc.wait(timeout=180)
        finally:
            if proc.poll() is None:
                proc.kill()
    assert proc.returncode == RESUMABLE_EXIT, (
        proc.returncode, log_path.read_text()[-3000:]
    )
    summary = json.loads(
        open(os.path.join(out, "training_summary.json")).read()
    )
    assert summary["preempted"] is True
    killed_step = summary["final_step"]
    assert killed_step >= 1

    resume = subprocess.run(
        [sys.executable, "-m", "luminaai_tpu", "resume", "--preset", "debug",
         "--synthetic", "--no-moe", "--batch-size", "8", "--seq-length",
         "32", "--steps", str(killed_step + 3), "--output-dir", out,
         "--quiet", "--no-adaptive"],
        env=env, cwd=repo, capture_output=True, text=True, timeout=300,
    )
    assert resume.returncode == 0, resume.stdout[-3000:] + resume.stderr[-2000:]
    summary2 = json.loads(
        open(os.path.join(out, "training_summary.json")).read()
    )
    assert summary2["final_step"] == killed_step + 3
    assert summary2["resumed_exact_data_state"] is True


# ---------------------------------------------------------------------------
# hang watchdog (docs/observability.md "Goodput & sentinels")
# ---------------------------------------------------------------------------
def _wd_cfg(out, **kw):
    """Tight watchdog thresholds so a ~1s injected stall fires within
    the test budget; the floor stays well above real step jitter."""
    return tiny_cfg(
        out, health_check_interval=10,  # log_every=1: per-step beats
        watchdog_floor_s=0.4, watchdog_k=3.0, watchdog_warmup=2,
        watchdog_poll_s=0.05, **kw,
    )


def test_watchdog_detects_hang_dumps_and_continues(tmp_path):
    """hang_step_at stalls one step well past k x rolling median: the
    watchdog emits hang_suspected, dumps all-thread stacks + the flight
    ring next to the checkpoints, bumps training_hangs_total, the
    goodput ledger books the stall as `hang` — and with abort OFF the
    run completes normally."""
    import glob

    from luminaai_tpu.monitoring.events import FlightRecorder, read_events
    from luminaai_tpu.testing.faults import hang_step_at
    from luminaai_tpu.training.trainer import Trainer

    rec, reg = FlightRecorder(), MetricsRegistry()
    ckpt = str(tmp_path / "ckpt")
    t = Trainer(_wd_cfg(tmp_path), train_data=gen_loader(),
                checkpoint_dir=ckpt, registry=reg, recorder=rec)
    with hang_step_at(t, 6, seconds=1.5) as stats:
        summary = t.train()
    t.close()
    assert stats["hangs"] == 1
    evs = rec.snapshot(type="hang_suspected")
    assert evs, "watchdog never fired on a 1.5s stall"
    assert evs[0]["stalled_s"] > evs[0]["threshold_s"] > 0
    assert evs[0]["kind"] == "training" and evs[0]["abort"] is False
    # Detect -> continue: the stalled step completed and the run ran on.
    assert summary["final_step"] == t.config.max_steps
    assert reg.snapshot()["training_hangs_total"] >= 1
    assert summary["goodput"]["seconds"]["hang"] > 0
    # Forensics on disk, replayable by the dump readers.
    stacks = glob.glob(ckpt + "/stacks-*hang.txt")
    dumps = glob.glob(ckpt + "/flightrec-*hang*.jsonl")
    assert stacks and dumps
    assert "thread" in open(stacks[0]).read()
    assert any(
        e["type"] == "hang_suspected" for e in read_events(dumps[0])
    )


def test_watchdog_abort_exits_resumable(tmp_path):
    """--watchdog-abort: after detect + dump the watchdog calls the exit
    fn with RESUMABLE_EXIT=75 (injected here — the real fn is os._exit,
    driven end to end by the CI hang smoke)."""
    from luminaai_tpu.monitoring.events import FlightRecorder
    from luminaai_tpu.monitoring.watchdog import RESUMABLE_EXIT
    from luminaai_tpu.testing.faults import hang_step_at
    from luminaai_tpu.training.trainer import Trainer

    rec = FlightRecorder()
    t = Trainer(_wd_cfg(tmp_path, watchdog_abort=True),
                train_data=gen_loader(),
                checkpoint_dir=str(tmp_path / "ckpt"),
                registry=MetricsRegistry(), recorder=rec)
    exits = []
    t.watchdog._exit_fn = exits.append
    with hang_step_at(t, 5, seconds=1.2):
        t.train()
    t.close()
    assert exits == [RESUMABLE_EXIT], exits
    evs = rec.snapshot(type="hang_suspected")
    assert evs and evs[0]["abort"] is True


def test_watchdog_quiet_during_first_compile_and_clean_run(tmp_path):
    """No-false-positive contract: the watchdog arms AFTER the first
    compile sync and needs `warmup` intervals before it can fire — a
    multi-second first compile over ~10ms steps never trips it, and an
    uninjected run stays silent end to end."""
    from luminaai_tpu.monitoring.events import FlightRecorder
    from luminaai_tpu.training.trainer import Trainer

    rec, reg = FlightRecorder(), MetricsRegistry()
    t = Trainer(_wd_cfg(tmp_path), train_data=gen_loader(),
                checkpoint_dir=str(tmp_path / "ckpt"),
                registry=reg, recorder=rec)
    summary = t.train()
    t.close()
    assert summary["final_step"] == t.config.max_steps
    assert not rec.snapshot(type="hang_suspected")
    assert reg.snapshot().get("training_hangs_total", 0) == 0


def test_serving_watchdog_detects_slow_tick(tmp_path):
    """The scheduler arms the watchdog per generation and beats per
    decode step: slow_tick's post-warmup stall crosses the robust
    threshold -> hang_suspected + serving_hangs_total, while the
    request itself still completes (detect -> continue)."""
    from luminaai_tpu.monitoring.events import FlightRecorder
    from luminaai_tpu.monitoring.watchdog import HangWatchdog
    from luminaai_tpu.testing.faults import slow_tick

    rec, reg = FlightRecorder(), MetricsRegistry()
    eng = _Engine()
    wd = HangWatchdog(
        kind="serving", registry=reg, recorder=rec,
        dump_dir=str(tmp_path), k=3.0, floor_s=0.25, warmup=2,
        poll_s=0.03,
    )
    sched = ContinuousScheduler(
        eng, decoder=eng.stepper, registry=reg, recorder=rec,
        watchdog=wd,
    )
    with slow_tick(eng.stepper, delay_s=0.8, after=6):
        toks, stats = sched.submit([40], {"max_new_tokens": 10})
    wd.close()
    assert toks == list(range(40, 50))  # the lane still finished
    evs = rec.snapshot(type="hang_suspected")
    assert evs and evs[0]["kind"] == "serving"
    assert reg.snapshot()["serving_hangs_total"] >= 1
    # Idle scheduler (generation over, watchdog disarmed): no re-fire.
    time.sleep(0.4)
    assert wd.fires == len(evs)


def test_serving_sentinel_flags_decode_step_anomaly():
    """One decode step blowing past the rolling median/MAD emits a
    step_anomaly event tagged program=serve and keeps the
    serve_decode_step_seconds_{median,mad} gauges fresh."""
    from luminaai_tpu.monitoring.events import FlightRecorder
    from luminaai_tpu.testing.faults import slow_tick

    rec, reg = FlightRecorder(), MetricsRegistry()
    eng = _Engine()
    sched = ContinuousScheduler(
        eng, decoder=eng.stepper, registry=reg, recorder=rec,
    )
    with slow_tick(eng.stepper, delay_s=0.3, after=8):
        sched.submit([40], {"max_new_tokens": 12})
    evs = rec.snapshot(type="step_anomaly")
    assert evs and evs[0]["program"] == "serve"
    snap = reg.snapshot()
    assert snap["serve_decode_step_seconds_median"] > 0
    assert snap["step_time_anomalies_total"]["program=serve"] >= 1


def test_serving_watchdog_ignores_slow_admission_prefill(tmp_path):
    """A mid-generation admission whose prefill stalls past the floor
    (first-use XLA compile of a new prompt bucket on real engines) is
    excluded via the scheduler's pause — no false hang fires, and the
    watchdog still watches the decode steps around it."""
    from luminaai_tpu.monitoring.events import FlightRecorder
    from luminaai_tpu.monitoring.watchdog import HangWatchdog

    rec, reg = FlightRecorder(), MetricsRegistry()
    eng = _Engine()
    orig_prefill = eng.stepper.prefill_into_slot

    def slow_prefill(*a, **kw):
        time.sleep(0.6)  # > floor: would fire if not paused
        return orig_prefill(*a, **kw)

    eng.stepper.prefill_into_slot = slow_prefill
    wd = HangWatchdog(
        kind="serving", registry=reg, recorder=rec,
        dump_dir=str(tmp_path), k=3.0, floor_s=0.25, warmup=2,
        poll_s=0.03,
    )
    sched = ContinuousScheduler(
        eng, decoder=eng.stepper, registry=reg, recorder=rec,
        watchdog=wd,
    )
    # Two requests: the second admits mid-generation through the paused
    # admission path while the first keeps decoding.
    results = []

    def submit(prompt, n):
        results.append(sched.submit([prompt], {"max_new_tokens": n}))

    t1 = threading.Thread(target=submit, args=(40, 30))
    t1.start()
    time.sleep(0.15)  # let A's generation start
    t2 = threading.Thread(target=submit, args=(80, 5))
    t2.start()
    t1.join(timeout=30)
    t2.join(timeout=30)
    wd.close()
    assert len(results) == 2
    assert not rec.snapshot(type="hang_suspected"), (
        rec.snapshot(type="hang_suspected")
    )
    assert reg.snapshot().get("serving_hangs_total", 0) == 0
