"""BENCHMARK.json against the contract and the files under benchmark/."""

import copy
import json
import os
import subprocess
import sys

import pytest

from benchmark import manifest, model_config

BENCH = manifest.load_benchmark()


def test_committed_manifest_is_sound():
    assert manifest.check(BENCH) == []


def test_every_cell_resolves_its_files_and_metrics():
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for w in BENCH["workloads"]:
        cell = manifest.Cell(BENCH, w["name"])
        assert cell.traffic["kind"] in ("train", "open_loop", "closed_loop")
        assert "setup_s" in {m["name"] for m in cell.end_to_end}
        assert len(cell.end_to_end) >= 2 and cell.per_layer
        for name, spec in cell.layer_metric_specs().items():
            assert spec["from"] in ("trace", "registry", "host"), name
        for m in cell.per_layer:
            assert m["moves"] in e2e
            assert m["moves"] in {x["name"] for x in cell.end_to_end}
        kw = model_config.config_kwargs(cell.config)
        assert kw["hidden_size"] == cell.config["hidden_size"]
        for key in ("source", "reduced", "assumed", "departures"):
            assert key in cell.config


def test_at_most_one_four_chip_cell():
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= 1


@pytest.mark.parametrize("mutate,word", [
    (lambda b: b["per_layer"][0].update(moves="nope"), "moves unknown"),
    (lambda b: b["workloads"][0].update(name="has space"), "allowed char"),
    (lambda b: b["end_to_end"][0].update(unit="tokens per s"), "unit"),
    (lambda b: b["end_to_end"][0].update(bound=0.5), "bound"),
    (lambda b: b["workloads"][0].update(traffic="missing-mix"), "traffic file"),
    (lambda b: [w.update(chips=4) for w in b["workloads"][:2]], "four-chip"),
    (lambda b: b["configs"][0].update(reduced=["hidden_size"]), "width"),
    (lambda b: b["per_layer"][0].update(why="x"), "extra key"),
    (lambda b: b.update(extra=1), "top-level"),
], ids=["moves", "name", "unit", "bound", "traffic", "chips", "width",
        "extra_key", "top_keys"])
def test_faults_are_found(mutate, word):
    bad = copy.deepcopy(BENCH)
    mutate(bad)
    faults = manifest.check(bad)
    assert any(word in f for f in faults), faults


def test_a_per_layer_metric_must_move_a_metric_its_cells_report():
    bad = copy.deepcopy(BENCH)
    train_only = next(m for m in bad["per_layer"] if m["name"] == "mfu_pct")
    train_only["moves"] = "serve_tok_s"
    assert any("does not report" in f for f in manifest.check(bad))


def test_the_command_refuses_a_cpu():
    """No TPU: exit code 2, and no line of standard output is a result."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload",
         BENCH["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        cwd=manifest.ROOT, env=env, capture_output=True, text=True,
        timeout=300,
    )
    assert proc.returncode == 2, proc.stderr[-500:]
    assert "needs a TPU" in proc.stderr
    for line in proc.stdout.splitlines():
        assert not line.startswith("{"), line


def test_peaks_have_a_source_and_no_default():
    from benchmark import common

    peaks = common.load_peaks()
    assert peaks["TPU v5 lite"]["bf16_flops_per_s"] == 197e12
    assert peaks["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9
    raw = json.load(open(os.path.join(manifest.HERE, "peaks.json")))
    assert "Google Cloud" in raw["_source"]
