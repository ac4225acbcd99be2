"""The four per-layer metrics that split `ttft_mean_ms` into its stages
(PR 39): data files alone, read by the registry ratio the harness has,
on a registry delta that the program's own scheduler made over a fake
decoder (no model, no jax)."""

import threading
from types import SimpleNamespace

import numpy as np
import pytest

from benchmark import layer_readers, manifest

BENCH = manifest.load_benchmark()
CELLS = ("olmoe-serve-chat", "jamba2-3b-serve-burst")
STAGES = {
    "prefill_wait_mean_ms": "serve_prefill_wait_seconds",
    "prefill_ride_mean_ms": "serve_prefill_ride_seconds",
    "first_token_lag_mean_ms": "serve_first_token_lag_seconds",
}
TURNS = "prefill_turns_waited_per_chunk"
CHUNK = 4


class ChunkedFake:
    """The least decoder the scheduler steps: slots, chunked admission
    (one chunk a call, `prefill_chunk` tokens each) and a decode step in
    which every lane emits its next token. `hold`: an Event the first
    admission waits for, so that a test fixes the admission order."""

    prefill_chunk = CHUNK

    def __init__(self, num_slots=2):
        self.free = list(range(num_slots))
        self.lanes = {}
        self.steps = 0
        self.hold = None

    def has_free_slot(self):
        return bool(self.free)

    def acquire_slot(self):
        return self.free.pop(0)

    def release_slot(self, slot):
        self.lanes.pop(slot, None)
        self.free.append(slot)

    def start_prefill(self, slot, prompt, max_new_tokens=1, sample_key=None,
                      seed=None):
        if self.hold is not None:
            assert self.hold.wait(10)
        if len(prompt) <= CHUNK:
            return None
        return {"slot": slot, "prompt": list(prompt), "next": 0,
                "n_chunks": -(-len(prompt) // CHUNK), "chunk": CHUNK,
                "length": len(prompt)}

    def prefill_into_slot(self, slot, prompt, max_new_tokens=1,
                          sample_key=None, seed=None):
        self.lanes[slot] = int(prompt[0])
        return {"token": int(prompt[0]), "prompt_tokens": len(prompt),
                "is_stop": False}

    def advance_prefill(self, st):
        threading.Event().wait(0.002)  # a chunk takes a while
        st["next"] += 1
        if st["next"] < st["n_chunks"]:
            return None
        return self.prefill_into_slot(st["slot"], st["prompt"])

    def decode_step(self, sample_key=None):
        n = len(self.free) + len(self.lanes) + 8
        toks, produced = np.zeros((n,), np.int64), np.zeros((n,), bool)
        for slot in self.lanes:
            self.lanes[slot] += 1
            toks[slot], produced[slot] = self.lanes[slot], True
        self.steps += 1
        return toks, produced, np.zeros((n,), bool)


@pytest.fixture(scope="module")
def window():
    """A registry delta over two interleaved chunked admissions (three
    and two chunks, A first) and one whole-prompt admission."""
    from luminaai_tpu.monitoring.events import FlightRecorder
    from luminaai_tpu.monitoring.telemetry import MetricsRegistry
    from luminaai_tpu.serving.server import ContinuousScheduler

    registry, dec = MetricsRegistry(), ChunkedFake()
    sched = ContinuousScheduler(SimpleNamespace(), decoder=dec,
                                registry=registry, recorder=FlightRecorder())
    before = layer_readers.registry_view(registry)
    dec.hold = threading.Event()
    threads = []
    for first, n_tokens in ((10, 3 * CHUNK), (50, 2 * CHUNK)):
        threads.append(threading.Thread(
            target=sched.submit,
            args=([first] * n_tokens, {"max_new_tokens": 2}), daemon=True))
        threads[-1].start()
        for _ in range(2000):  # A holds its slot, then B is queued
            if (len(dec.free), sched.queue_depth()) == (1, len(threads) - 1):
                break
            threading.Event().wait(0.005)
    dec.hold.set()
    for th in threads:
        th.join(20)
    dec.hold = None
    assert sched.submit([90] * CHUNK, {"max_new_tokens": 2})[0] == [90, 91]
    return layer_readers.delta(layer_readers.registry_view(registry), before)


def _read(name, delta):
    cell = manifest.Cell(BENCH, CELLS[0])
    return layer_readers.read(name, cell.layer_metric_specs()[name],
                              layer_readers.Context(registry_delta=delta))


def test_the_manifest_is_sound_with_the_four_entries():
    assert manifest.check(BENCH) == []
    names = [m["name"] for m in BENCH["per_layer"]]
    assert names[-4:] == [*STAGES, TURNS]  # appended, nothing moved


@pytest.mark.parametrize("name", [*STAGES, TURNS])
@pytest.mark.parametrize("cell_name", CELLS)
def test_a_stage_metric_resolves_for_the_two_chat_cells(name, cell_name):
    (entry,) = [m for m in BENCH["per_layer"] if m["name"] == name]
    assert entry["moves"] == "ttft_mean_ms" and entry["layer"] == "scheduler"
    assert entry["source"] == "program_counter" and entry["better"] == "lower"
    assert entry["workloads"] == list(CELLS)
    spec = manifest.Cell(BENCH, cell_name).layer_metric_specs()[name]
    assert spec["from"] == "registry" and spec["reduce"] == "ratio"
    if name == TURNS:
        assert spec["num"] == {"counter": "serve_prefill_turns_waited_total"}
        assert spec["den"] == {"counter": "serving_prefill_chunks_total"}
    else:
        assert spec["num"] == {"hist_sum": STAGES[name]}
        assert spec["den"] == {"hist_count": STAGES[name]}
        assert spec["scale"] == 1000.0
    batch = manifest.Cell(BENCH, "olmoe-serve-batch").layer_metric_specs()
    assert name not in batch


def test_the_stage_means_add_up_to_the_programs_ttft(window):
    got = {name: _read(name, window) for name in (*STAGES, TURNS,
                                                  "queue_wait_mean_ms")}
    assert all(v is not None and v >= 0.0 for v in got.values()), got
    n = window["hist_count:serve_ttft_seconds"]
    assert n == 3 == window["hist_count:serve_prefill_wait_seconds"]
    ttft_mean_ms = 1e3 * window["hist_sum:serve_ttft_seconds"] / n
    stages = sum(got[name] for name in (*STAGES, "queue_wait_mean_ms"))
    assert stages == pytest.approx(ttft_mean_ms, rel=1e-9)
    prefill_ms = 1e3 * window["hist_sum:serve_prefill_seconds"] / n
    assert sum(got[name] for name in STAGES) == pytest.approx(
        prefill_ms, rel=1e-9)
    assert got["prefill_ride_mean_ms"] > 0.0
    # Ticks A B A B A: the first four each leave one admission waiting.
    assert window["counter:serving_prefill_chunks_total"] == 5
    assert got[TURNS] == pytest.approx(4 / 5)


@pytest.mark.parametrize("name", [*STAGES, TURNS])
def test_a_program_without_the_stage_counters_leaves_the_metric_out(
        name, window):
    """The parent commit: nothing to read is None, never an error."""
    old = {k: v for k, v in window.items()
           if "prefill_wait" not in k and "prefill_ride" not in k
           and "first_token_lag" not in k and "turns_waited" not in k}
    assert _read(name, old) is None
    assert _read("queue_wait_mean_ms", old) is not None
