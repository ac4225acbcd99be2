"""The benchmark's own CPU tests, inside tier-1.

`benchmark/tests/test_*.py` and each architecture's `test_*.py` (the
manifest's rules, the traffic generator, the trace reduction on a
recorded v5e trace, the references against the program at a tiny size)
guard the yardstick every PR is measured with, and the driver's command
names only `tests/`. The star-imports below bring their test functions
and fixtures into this module, so they are collected, and counted, here;
nothing under `benchmark/` is edited for it. They run under
tests/conftest.py's eight-device CPU mesh, as under
`python -m pytest benchmark`.
"""

import glob
import importlib
import os

import pytest

from benchmark.tests import test_manifest as _manifest_tests
from benchmark.tests import test_ttft_stages as _ttft_stages
from benchmark.architectures.cohere2_moe.test_reference import *  # noqa: F401,F403
from benchmark.architectures.jamba.test_reference import *  # noqa: F401,F403
from benchmark.architectures.kimi_k2.test_reference import *  # noqa: F401,F403
from benchmark.architectures.kimi_linear.test_reference import *  # noqa: F401,F403
from benchmark.architectures.mimo_v2.test_reference import *  # noqa: F401,F403
from benchmark.architectures.nemotron_h.test_reference import *  # noqa: F401,F403
from benchmark.architectures.prenorm_decoder.test_reference import *  # noqa: F401,F403
from benchmark.tests.test_architectures import *  # noqa: F401,F403
from benchmark.tests.test_control_serve import *  # noqa: F401,F403
from benchmark.tests.test_manifest import *  # noqa: F401,F403
from benchmark.tests.test_trace_in_run import *  # noqa: F401,F403
from benchmark.tests.test_trace_reduce import *  # noqa: F401,F403
from benchmark.tests.test_traffic import *  # noqa: F401,F403
from benchmark.tests.test_ttft_stages import *  # noqa: F401,F403

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
COLLECTED = (
    "benchmark/architectures/cohere2_moe/test_reference.py",
    "benchmark/architectures/jamba/test_reference.py",
    "benchmark/architectures/kimi_k2/test_reference.py",
    "benchmark/architectures/kimi_linear/test_reference.py",
    "benchmark/architectures/mimo_v2/test_reference.py",
    "benchmark/architectures/nemotron_h/test_reference.py",
    "benchmark/architectures/prenorm_decoder/test_reference.py",
    "benchmark/tests/test_architectures.py",
    "benchmark/tests/test_control_serve.py",
    "benchmark/tests/test_manifest.py",
    "benchmark/tests/test_trace_in_run.py",
    "benchmark/tests/test_trace_reduce.py",
    "benchmark/tests/test_traffic.py",
    "benchmark/tests/test_ttft_stages.py",
)


# benchmark/tests/test_architectures.py was written when the benchmark had
# ONE architecture, and its resolver test asserts that every cell's is
# `prenorm_decoder`. PR 35 adds a second one (PR 37 a third, PR 42 a fourth, PR 45 a
# fifth, PR 50 a sixth, PR 63 a seventh), and a PR that adds to the
# benchmark may not edit a file the benchmark has: the same test is taken
# here with each cell held to the architecture its own configuration file
# names, under the same name so that it is counted once. A `benchmark` PR
# should move this body into benchmark/tests/ and drop the override.
#
# benchmark/tests/test_ttft_stages.py holds the program to the order of
# the round-robin ring ("Ticks A B A B A": 4 turns waited in 5 chunks).
# Since PR 40 the tick's chunk goes by turns to the oldest admission and
# to the one with the fewest chunks left (serving/server.py::pick_prefill),
# so A's three chunks ride before B's two: 3 turns in 5. The same test is
# taken here with that one line changed; a `benchmark` PR should change it
# there and drop the override.
#
# The same file holds `BENCHMARK.json` to the state PR 39 left it in: the
# four stage metrics are the LAST entries of `per_layer`, reported by the
# two chat cells alone. PR 42 appends a cell to their `workloads` lists and
# nine entries behind them (what a PR that adds a cell does). The two tests
# are taken here with "the last four" read as "in this order, nothing
# between them" and "the two cells" as "those two first"; a `benchmark` PR
# should change them there and drop the overrides.
#
# benchmark/tests/test_manifest.py's `test_faults_are_found[chips]` makes
# TWO cells four-chip and expects "too many four-chip cells": true while
# the benchmark had up to seven cells (a quarter, rounded down, is one).
# PR 50 brings the eighth, and two of eight are allowed. The same test is
# taken here with that one case made of THREE cells; a `benchmark` PR
# should change it there and drop the override.
SUPERSEDED_HERE = (
    "test_faults_are_found",
    "test_every_cell_resolves_its_architecture",
    "test_the_stage_means_add_up_to_the_programs_ttft",
    "test_the_manifest_is_sound_with_the_four_entries",
    "test_a_stage_metric_resolves_for_the_two_chat_cells",
)


_FAULTS = _manifest_tests.test_faults_are_found.pytestmark[0]


@pytest.mark.parametrize("mutate,word", [
    case if word != "four-chip" else (
        lambda b: [w.update(chips=4) for w in b["workloads"][:3]], word)
    for case in _FAULTS.args[1] for word in [case[1]]
], ids=_FAULTS.kwargs["ids"])
def test_faults_are_found(mutate, word):  # noqa: F811
    import copy

    from benchmark import manifest

    bad = copy.deepcopy(_manifest_tests.BENCH)
    mutate(bad)
    faults = manifest.check(bad)
    assert any(word in f for f in faults), faults


def test_every_cell_resolves_its_architecture():  # noqa: F811
    from benchmark import manifest

    bench = manifest.load_benchmark()
    seen = set()
    for w in bench["workloads"]:
        cell = manifest.Cell(bench, w["name"])
        arch = cell.architecture
        assert arch.name == cell.config["architecture"]
        seen.add(arch.name)
        for part, required in manifest.ARCH_PARTS.items():
            module = getattr(arch, part)
            assert module.__name__ == (
                f"benchmark.architectures.{arch.name}.{part}")
            for name in required:
                assert hasattr(module, name), (part, name)
        assert set(arch.work.KERNEL_FNS) == manifest.kernel_names(arch.name)
    assert seen == {"prenorm_decoder", "kimi_linear", "jamba", "cohere2_moe",
                    "kimi_k2", "mimo_v2", "nemotron_h"}


def test_the_stage_means_add_up_to_the_programs_ttft(window):  # noqa: F811
    read, stages = _ttft_stages._read, tuple(_ttft_stages.STAGES)
    got = {name: read(name, window) for name in (
        *stages, _ttft_stages.TURNS, "queue_wait_mean_ms")}
    assert all(v is not None and v >= 0.0 for v in got.values()), got
    n = window["hist_count:serve_ttft_seconds"]
    assert n == 3 == window["hist_count:serve_prefill_wait_seconds"]
    ttft_mean_ms = 1e3 * window["hist_sum:serve_ttft_seconds"] / n
    assert sum(got[name] for name in (*stages, "queue_wait_mean_ms")) == (
        pytest.approx(ttft_mean_ms, rel=1e-9))
    prefill_ms = 1e3 * window["hist_sum:serve_prefill_seconds"] / n
    assert sum(got[name] for name in stages) == pytest.approx(
        prefill_ms, rel=1e-9)
    assert got["prefill_ride_mean_ms"] > 0.0
    # Ticks A A A B B (A is the oldest and never the longer): the first
    # three each leave one admission waiting.
    assert window["counter:serving_prefill_chunks_total"] == 5
    assert got[_ttft_stages.TURNS] == pytest.approx(3 / 5)
    assert window["counter:serve_prefill_picks_not_oldest_total"] == 0


def test_the_manifest_is_sound_with_the_four_entries():  # noqa: F811
    from benchmark import manifest

    bench = _ttft_stages.BENCH
    assert manifest.check(bench) == []
    names = [m["name"] for m in bench["per_layer"]]
    want = [*_ttft_stages.STAGES, _ttft_stages.TURNS]
    at = names.index(want[0])
    assert names[at:at + 4] == want  # appended together, nothing moved


@pytest.mark.parametrize("name", [*_ttft_stages.STAGES, _ttft_stages.TURNS])
@pytest.mark.parametrize("cell_name", _ttft_stages.CELLS)
def test_a_stage_metric_resolves_for_the_two_chat_cells(  # noqa: F811
        name, cell_name):
    from benchmark import manifest

    bench, stages = _ttft_stages.BENCH, _ttft_stages.STAGES
    (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
    assert entry["moves"] == "ttft_mean_ms" and entry["layer"] == "scheduler"
    assert entry["source"] == "program_counter" and entry["better"] == "lower"
    cells = list(_ttft_stages.CELLS)
    assert entry["workloads"][:len(cells)] == cells  # later cells appended
    spec = manifest.Cell(bench, cell_name).layer_metric_specs()[name]
    assert spec["from"] == "registry" and spec["reduce"] == "ratio"
    if name == _ttft_stages.TURNS:
        assert spec["num"] == {"counter": "serve_prefill_turns_waited_total"}
        assert spec["den"] == {"counter": "serving_prefill_chunks_total"}
    else:
        assert spec["num"] == {"hist_sum": stages[name]}
        assert spec["den"] == {"hist_count": stages[name]}
        assert spec["scale"] == 1000.0
    batch = manifest.Cell(bench, "olmoe-serve-batch").layer_metric_specs()
    assert name not in batch


def test_every_benchmark_test_file_is_collected_here():
    """A new test_*.py under benchmark/ must be star-imported above (and
    named in COLLECTED), and no test of one file may hide a test of
    another behind the same name."""
    on_disk = sorted(
        os.path.relpath(p, REPO)
        for p in glob.glob(
            os.path.join(REPO, "benchmark", "**", "test_*.py"),
            recursive=True,
        )
    )
    assert on_disk == sorted(COLLECTED)
    for path in COLLECTED:
        mod = importlib.import_module(path[:-3].replace("/", "."))
        for name, obj in vars(mod).items():
            if name.startswith("test_") and callable(obj):
                if name in SUPERSEDED_HERE:
                    assert globals()[name] is not obj
                    continue
                assert globals().get(name) is obj, (path, name)


@pytest.mark.parametrize("section", ["configs", "workloads", "per_layer"])
def test_every_line_of_text_in_the_manifest_fits(section):
    """`manifest.check` measures a cell's `why` alone; the driver holds a
    configuration's `why` and `source` and a metric's `layer` to the same
    200 printable characters on one line (PR 50 was refused for a
    configuration's `why` of 223)."""
    import json

    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for entry in bench[section]:
        for key in ("why", "source", "layer"):
            if key in entry:
                text = entry[key]
                assert 1 <= len(text) <= 200, (entry["name"], key, len(text))
                assert text.isprintable(), (entry["name"], key)
