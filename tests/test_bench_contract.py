"""bench.py driver-contract tests: with a chip, main() emits exactly ONE
JSON line with the right structure down the ladder; without one it exits
non-zero and prints no result. Children are stubbed; only main()'s
ladder/embedding logic runs (the children themselves are exercised by
--smoke in CI and by the real chip)."""

import contextlib
import io
import json
import os
import subprocess
import types

import pytest

import bench

_TPU_PROBE = ("tpu", "backend_probe=tpu")


@pytest.fixture
def restore_bench(monkeypatch, tmp_path):
    """Stub seams + redirect the sidecar artifacts into tmp."""
    real_open = open
    sidecar = tmp_path / "DENSE_BENCH.json"

    def fake_open(path, *a, **k):
        for name in ("DENSE_BENCH.json", "REF_TABLE.json"):
            if str(path).endswith(name):
                return real_open(tmp_path / name, *a, **k)
        return real_open(path, *a, **k)

    monkeypatch.setattr(bench, "open", fake_open, raising=False)
    monkeypatch.setattr(bench, "_probe_backend", lambda *a, **k: _TPU_PROBE)
    return sidecar


def _canned(name, platform="tpu"):
    if name == "ref_debug_moe":
        return {
            "metric": bench.METRIC, "value": 1_474_875.0,
            "unit": "tokens/sec/chip", "vs_baseline": 24.788,
            "extras": {"chips": 1, "platform": platform,
                       "config": "ref_debug_moe", "batch": 256, "seq": 256,
                       "mfu": 0.001, "step_ms": 44.4},
        }
    if name == "flagship_tuned":
        return {
            "metric": bench.METRIC, "value": 31_557.0,
            "unit": "tokens/sec/chip", "vs_baseline": 0.53,
            "extras": {"chips": 1, "platform": platform,
                       "config": "flagship_tuned", "total_params_m": 757.0,
                       "active_params_m": 238.0, "batch": 16, "seq": 2048,
                       "mfu": 0.229, "model_tflops_per_sec": 45.1,
                       "moe_drop_rate": 0.22, "moe_drop_rate_steady": 0.04,
                       "step_ms": 1038.0},
        }
    if name == "dense200":
        return {
            "metric": "train_tokens_per_sec_per_chip_dense200",
            "value": 50_000.0, "unit": "tokens/sec/chip",
            "vs_baseline": 0.42, "extras": {"config": "dense200"},
        }
    return None


def _run_main():
    """(exit code, parsed JSON lines on stdout, stderr text)."""
    out, err = io.StringIO(), io.StringIO()
    code = 0
    with contextlib.redirect_stdout(out), contextlib.redirect_stderr(err):
        try:
            bench.main()
        except SystemExit as e:
            code = e.code
    lines = [
        json.loads(l) for l in out.getvalue().splitlines()
        if l.strip().startswith("{")
    ]
    return code, lines, err.getvalue()


def _one_line():
    code, lines, err = _run_main()
    assert code == 0, err
    assert len(lines) == 1, f"driver contract: exactly one JSON line: {lines}"
    return lines[0]


def test_tpu_flow_headline_and_flagship_embed(monkeypatch, restore_bench):
    """TPU path: ref-matched headline, flagship riding in extras, dense
    sidecar written."""
    calls = []

    def fake(name, timeout):
        calls.append(name)
        payload = _canned(name)
        return payload, f"{name}: {'ok' if payload else 'unexpected'}"

    monkeypatch.setattr(bench, "_run_child", fake)
    out = _one_line()
    assert calls == [
        "ref_debug_moe", "flagship_tuned", "dense200",
        *bench.REF_TABLE_RUNGS,
    ]
    assert out["value"] == 1_474_875.0
    assert out["extras"]["flagship"]["value"] == 31_557.0
    assert out["extras"]["flagship"]["mfu"] == 0.229
    # Every fresh measurement self-reports its regression-gate verdict.
    assert "verdict" in out["extras"]["bench_gate"]
    assert json.loads(restore_bench.read_text())["value"] == 50_000.0


def test_tpu_flow_survives_flagship_failure(monkeypatch, restore_bench):
    """A dead flagship rung costs only the extras annotation — the
    measured headline must still print."""

    def fake(name, timeout):
        if name in ("flagship_tuned", "dense200"):
            return None, f"{name}: timeout"
        return _canned(name), f"{name}: ok"

    monkeypatch.setattr(bench, "_run_child", fake)
    out = _one_line()
    assert out["value"] == 1_474_875.0
    assert "flagship" not in out["extras"]


def test_headline_falls_back_down_the_ladder(monkeypatch, restore_bench):
    """ref_debug_moe failing falls through to flagship_tuned as headline."""

    def fake(name, timeout):
        if name == "ref_debug_moe":
            return None, f"{name}: crashed"
        return _canned(name), f"{name}: ok"

    monkeypatch.setattr(bench, "_run_child", fake)
    assert _one_line()["value"] == 31_557.0


@pytest.mark.parametrize(
    "probe",
    [("cpu", "backend_probe=cpu"), (None, "backend_probe=failed(rc=1)")],
    ids=["cpu", "dead"],
)
def test_no_chip_exits_nonzero_and_prints_no_result(monkeypatch, probe):
    """No TPU → no rung runs, nothing on stdout, non-zero exit: a CPU
    number is never written under the device metric's name."""
    monkeypatch.setattr(bench, "_probe_backend", lambda *a, **k: probe)
    monkeypatch.setattr(
        bench, "_run_child",
        lambda n, t: pytest.fail(f"rung {n} ran without a chip"),
    )
    code, lines, err = _run_main()
    assert code not in (0, None)
    assert lines == []
    assert "needs a TPU" in err and probe[1] in err


def test_every_rung_failing_exits_nonzero(monkeypatch, restore_bench):
    monkeypatch.setattr(
        bench, "_run_child", lambda n, t: (None, f"{n}: dead")
    )
    code, lines, err = _run_main()
    assert code not in (0, None)
    assert lines == []
    assert "flagship_small: dead" in err


def test_rung_that_ran_on_cpu_is_refused(monkeypatch, restore_bench):
    """A payload naming another platform is never the result, whatever
    the probe saw: main() moves down the ladder and then fails."""
    monkeypatch.setattr(
        bench, "_run_child",
        lambda n, t: (_canned(n, platform="cpu"), f"{n}: ok"),
    )
    code, lines, err = _run_main()
    assert code not in (0, None)
    assert lines == []
    assert "ran on 'cpu', refused" in err


def test_ladder_has_no_cpu_rung():
    assert [n for n, _ in bench.LADDER] == [
        "ref_debug_moe", "flagship_tuned", "flagship", "flagship_small",
    ]
    with pytest.raises(ValueError, match="unknown bench config"):
        bench._child_config("cpu_fallback")


def test_flagship_rungs_share_the_one_definition():
    """bench.py and chip_smoke.py import ConfigPresets.flagship — one
    definition of the 757M MoE, not a copy."""
    from luminaai_tpu.config import ConfigPresets

    tuned = bench._child_config("flagship_tuned")
    assert tuned == ConfigPresets.flagship()
    assert (tuned.moe_dispatch, tuned.rope_dtype, tuned.remat_policy,
            tuned.adam_mu_dtype) == ("gmm", "bf16", "save_attn", "bf16")
    assert (tuned.batch_size, tuned.seq_length, tuned.num_layers,
            tuned.hidden_size) == (16, 2048, 10, 1024)
    assert bench._child_config("flagship", 4).batch_size == 64
    assert bench._child_config("flagship_small").batch_size == 8
    assert bench._child_config("flagship").moe_dispatch != "gmm"


@pytest.mark.parametrize(
    "rc,stdout,stderr,want",
    [
        (0, "tpu\n", "", "tpu"),
        (0, "WARNING: noise\ncpu\n", "", "cpu"),
        (1, "", "RuntimeError: Unable to initialize backend", None),
    ],
    ids=["tpu", "cpu", "crash"],
)
def test_probe_asks_one_child_once(monkeypatch, rc, stdout, stderr, want):
    """The probe is one throwaway child and one answer — no wait loop."""
    calls = []

    def fake_run(cmd, **kw):
        calls.append(cmd)
        return types.SimpleNamespace(
            returncode=rc, stdout=stdout, stderr=stderr
        )

    monkeypatch.setattr(subprocess, "run", fake_run)
    platform, diag = bench._probe_backend()
    assert platform == want
    assert len(calls) == 1
    assert diag.startswith("backend_probe=")
    if want is None:
        assert "Unable to initialize" in diag


def test_probe_timeout_is_an_answer_not_a_wait(monkeypatch):
    def fake_run(cmd, timeout=None, **kw):
        raise subprocess.TimeoutExpired(cmd, timeout)

    monkeypatch.setattr(subprocess, "run", fake_run)
    monkeypatch.setattr(
        bench.time, "sleep", lambda s: pytest.fail("probe slept")
    )
    assert bench._probe_backend(timeout=7) == (
        None, "backend_probe=timeout(7s)"
    )


# -- serving bench (--smoke-serve) -----------------------------------------
@pytest.mark.slow
def test_smoke_serve_emits_wellformed_continuous_metric():
    """bench.py --smoke-serve is the hermetic CPU serving contract: one
    JSON line with the serve_tokens_per_sec_continuous metric, the
    latency histogram, and — the acceptance criterion — strictly more
    tokens/sec from the continuous scheduler than from the legacy
    MicroBatcher on the same mixed-max_new workload."""
    import subprocess
    import sys

    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run(
        [sys.executable, os.path.abspath(bench.__file__), "--smoke-serve"],
        capture_output=True,
        text=True,
        timeout=540,
        cwd=os.path.dirname(os.path.abspath(bench.__file__)),
        env=env,
    )
    lines = [
        l for l in proc.stdout.splitlines() if l.strip().startswith("{")
    ]
    assert len(lines) == 1, (proc.stdout, proc.stderr[-2000:])
    result = json.loads(lines[0])
    assert result["metric"] == "serve_tokens_per_sec_continuous"
    assert "error" not in result, result
    assert result["unit"] == "tokens/sec"
    assert result["value"] > 0
    ex = result["extras"]
    assert ex["platform"] == "cpu"  # hermetic by contract
    assert ex["legacy_tokens_per_sec"] > 0
    # Continuous batching must beat run-to-completion micro-batching on
    # the mixed-length workload (and both served the same token count —
    # greedy decode is path-identical).
    assert result["value"] > ex["legacy_tokens_per_sec"], result
    assert result["vs_baseline"] > 1.0
    assert ex["tokens_continuous"] == ex["tokens_legacy"] > 0
    assert ex["slot_reuses"] >= 1
    for hist in ("latency_ms_per_token", "ttft_ms"):
        assert ex[hist]["p50"] > 0
        assert ex[hist]["p95"] >= ex[hist]["p50"]
    # Telemetry provenance contract: the artifact embeds a registry
    # snapshot (bench.py fails loudly without one), and its serving
    # histograms saw the measured workload with monotone quantiles.
    telem = ex["telemetry"]
    for hist in ("serve_ttft_seconds", "serve_token_latency_seconds"):
        assert telem[hist]["count"] > 0, hist
        assert telem[hist]["p50"] <= telem[hist]["p95"] <= telem[hist]["p99"]
    assert telem["serve_admissions_total"] >= ex["requests"]
    assert telem["kv_pool_slot_reuses_total"] >= 1


@pytest.mark.slow
def test_smoke_embeds_dispatch_flops_and_donation_audit():
    """bench.py --smoke is the CPU-provable evidence surface for the r6
    MFU attack: the artifact must embed the gmm-vs-einsum compiled-FLOPs
    A/B on the flagship-shaped train step with the >=10% reduction met,
    a clean donation audit (state aliased in place), and the optimizer
    memory breakdown — CI gates on exactly these fields."""
    import subprocess
    import sys

    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run(
        [sys.executable, os.path.abspath(bench.__file__), "--smoke"],
        capture_output=True,
        text=True,
        timeout=540,
        cwd=os.path.dirname(os.path.abspath(bench.__file__)),
        env=env,
    )
    lines = [
        l for l in proc.stdout.splitlines() if l.strip().startswith("{")
    ]
    assert len(lines) == 1, (proc.stdout, proc.stderr[-2000:])
    result = json.loads(lines[0])
    assert proc.returncode == 0, (result, proc.stderr[-1000:])
    ex = result["extras"]
    ab = ex["moe_dispatch_flops"]
    assert ab["available"], ab
    assert ab["gmm_flops_per_step"] < ab["einsum_flops_per_step"]
    assert ab["reduction"] >= 0.10, ab
    assert ab["meets_10pct_target"] is True
    aud = ex["donation_audit"]
    assert aud["available"] and aud["coverage"] > 0.9, aud
    assert aud["flagged"] is False
    assert ex["optimizer_memory"]["total_bytes"] > 0
    rs = ex["recompile_surface"]
    assert rs["available"], rs
    assert rs["programs"]["train"]["distinct_signatures"] >= 1
    assert rs["programs"]["decode"]["distinct_signatures"] >= 1
    assert rs["host_transfer_ops"] == {}, rs


def test_smoke_recompile_surface_embedding_contract(monkeypatch):
    """The smoke artifact's extras.recompile_surface field: per-program
    distinct-signature counts plus the variant->signature map, flattened
    from the auditor's report (the full enumeration itself is pinned in
    tests/test_analysis.py; here the WIRING is the contract)."""
    from luminaai_tpu.analysis import jaxpr_audit

    canned = {
        "programs": {
            "train": {
                "distinct_signatures": 4,
                "variants": [
                    {"variant": "scan=off/einsum", "signature": "aa",
                     "host_transfer_ops": {}},
                    {"variant": "scan=off/gmm", "signature": "bb",
                     "host_transfer_ops": {}},
                ],
            },
        },
        "total_variants": 2,
        "total_distinct": 2,
        "host_transfer_ops": {},
        "note": "canned",
    }
    monkeypatch.setattr(
        jaxpr_audit, "enumerate_recompile_surface",
        lambda registry=None, **k: canned,
    )
    out = bench._smoke_recompile_surface()
    assert out["available"] is True
    assert out["total_distinct"] == 2
    assert out["programs"]["train"]["distinct_signatures"] == 4
    assert out["programs"]["train"]["variants"] == {
        "scan=off/einsum": "aa", "scan=off/gmm": "bb",
    }
    assert out["host_transfer_ops"] == {}


def test_smoke_recompile_surface_degrades_without_killing_child(
    monkeypatch,
):
    """An auditor crash must degrade to available=False with a reason —
    the smoke child's artifact contract (one JSON line) survives."""
    from luminaai_tpu.analysis import jaxpr_audit

    def boom(registry=None, **k):
        raise RuntimeError("enumeration wedged")

    monkeypatch.setattr(
        jaxpr_audit, "enumerate_recompile_surface", boom
    )
    out = bench._smoke_recompile_surface()
    assert out["available"] is False
    assert "enumeration wedged" in out["reason"]
