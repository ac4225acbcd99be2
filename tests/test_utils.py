"""utils package tests: reporting, environment (SURVEY §2/§5)."""

import json
from pathlib import Path

import pytest

from luminaai_tpu.utils.reporting import (
    create_data_summary_report,
    create_training_report,
)


def test_training_report(tmp_path):
    exp = tmp_path / "exp"
    exp.mkdir()
    (exp / "training_summary.json").write_text(json.dumps({
        "experiment_name": "unit",
        "total_training_time_hours": 0.5,
        "total_epochs": 1,
        "total_steps": 100,
        "final_metrics": {"best_eval_loss": 2.5},
        "model_config": {"hidden_size": 64, "num_layers": 2},
        "health_summary": {"status": "healthy", "health_score": 0.9},
    }))
    with open(exp / "metrics.jsonl", "w") as f:
        f.write(json.dumps({"step": 100, "loss": 2.6}) + "\n")
    out = create_training_report(str(exp))
    html = Path(out).read_text()
    assert "unit" in html and "2.5" in html and "hidden_size" in html


def test_training_report_missing_summary(tmp_path):
    assert create_training_report(str(tmp_path)) is None


def test_data_summary_report(tmp_path):
    data = tmp_path / "data.jsonl"
    with open(data, "w") as f:
        for i in range(3):
            f.write(json.dumps({"messages": [
                {"role": "user", "content": f"hello {i}"},
                {"role": "assistant", "content": "hi there"},
            ]}) + "\n")

    from luminaai_tpu.data.tokenizer import ConversationTokenizer

    tok = ConversationTokenizer(model_name="byte")
    out = create_data_summary_report(
        [str(data)], tok, output_path=str(tmp_path / "report.html")
    )
    html = Path(out).read_text()
    assert "data.jsonl" in html and "Issue Breakdown" in html


def test_trainer_profile_window(tmp_path):
    """config.profile_start_step captures a device trace mid-run."""
    from luminaai_tpu.training.trainer import Trainer
    from tests.test_orchestrator import patterned_data, tiny_config

    cfg = tiny_config(
        tmp_path, max_steps=6, profile_start_step=2, profile_num_steps=2,
    )
    t = Trainer(cfg, train_data=patterned_data(cfg),
                checkpoint_dir=str(tmp_path / "ckpt"))
    t.train()
    t.close()
    profile_dir = Path(cfg.output_dir) / "profile"
    assert profile_dir.exists() and any(profile_dir.rglob("*"))


def test_tpu_runtime_diagnostics_cpu_backend():
    """Probe runs a real matmul in this process (CPU here), reports
    status/timings, and inspects the compile-cache state."""
    from luminaai_tpu.utils.environment import tpu_runtime_diagnostics

    rt = tpu_runtime_diagnostics()
    assert rt["backend"]["status"] == "ok", rt
    assert rt["backend"]["platform"] == "cpu"
    assert rt["backend"]["devices"] >= 1
    assert rt["backend"]["cold_matmul_s"] >= 0
    assert "compile_cache" in rt


def test_tpu_runtime_diagnostics_never_starts_a_child(monkeypatch):
    """A chip belongs to one process: the probe asks in-process, and a
    backend that fails is reported as an error, not raised."""
    import subprocess as sp

    from luminaai_tpu.utils import environment

    def no_child(*a, **k):
        raise AssertionError("diagnostics must not start a process")

    monkeypatch.setattr(sp, "run", no_child)
    monkeypatch.setattr(sp, "Popen", no_child)
    assert environment.tpu_runtime_diagnostics()["backend"]["status"] == "ok"

    def dead():
        raise RuntimeError("Unable to initialize backend 'tpu'")

    monkeypatch.setattr(environment, "_backend_probe", dead)
    rt = environment.tpu_runtime_diagnostics()
    assert rt["backend"]["status"] == "error"
    assert "Unable to initialize" in rt["backend"]["last_error"]


def test_device_peak_flops_table():
    from luminaai_tpu.utils.environment import device_peak_flops

    class D:
        def __init__(self, kind):
            self.device_kind = kind

    assert device_peak_flops(D("TPU v5 lite")) == 197e12
    assert device_peak_flops(D("TPU v5p")) == 459e12
    assert device_peak_flops(D("TPU v6e")) == 918e12
    with pytest.raises(ValueError, match="no peak"):
        device_peak_flops(D("cpu"))  # unknown kind is an error, no default
