"""chip_smoke.py's contract, on the CPU, with a tiny config injected.

The script itself only passes on a TPU; what is pinned here is that it
cannot pass anywhere else, the shape of its result line, the rules it
leans on (compile cache placed from outside, one peak table with no
default, one chip for each fleet child), and —
once, at a tiny size — that its phases still drive train -> checkpoint ->
serve through the real entry points.
"""

import gc
import json
import os
import shutil
import subprocess
import sys
import types

import jax
import pytest

import chip_smoke
from luminaai_tpu import cli
from luminaai_tpu.config import Config
from luminaai_tpu.utils import environment

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TPU1 = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}


def _stdout_lines(capsys):
    return [l for l in capsys.readouterr().out.splitlines() if l.strip()]


# -- the refusal and the result line ----------------------------------------
def test_refuses_anything_but_a_tpu(capsys, monkeypatch):
    """JAX is held to the CPU here: non-zero exit, no result line, and
    no phase past `device` ever starts."""
    monkeypatch.setattr(
        chip_smoke, "run_one_chip",
        lambda *a, **k: pytest.fail("a phase ran without a TPU"),
    )
    assert chip_smoke.main([]) != 0
    out = capsys.readouterr()
    assert '"ok"' not in out.out
    assert "needs a TPU, jax found 'cpu'" in out.err


def test_alone_in_a_directory_it_fails(tmp_path):
    """A directory that holds chip_smoke.py and nothing else of the repo:
    non-zero, no result. (The child never gets as far as importing jax.)"""
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=tmp_path, env=env,
        capture_output=True, text=True, timeout=60,
    )
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "the program is not here" in proc.stderr


@pytest.mark.parametrize(
    "argv,device",
    [([], TPU1), (["--chips", "4"], {**TPU1, "count": 4})],
    ids=["one_chip", "four_chips"],
)
def test_last_line_is_exactly_the_result(capsys, monkeypatch, argv, device):
    """With the phases stubbed to pass, the last stdout line is the one
    JSON object the driver reads, with the device as jax reported it; and
    --chips 4 runs the sharded comparison and no other phase."""
    ran = []
    monkeypatch.setattr(chip_smoke, "phase_device", lambda cache: device)
    monkeypatch.setattr(
        chip_smoke, "run_one_chip", lambda *a, **k: ran.append("one")
    )
    monkeypatch.setattr(
        chip_smoke, "phase_sharded_step",
        lambda cfg, out, n, kernels: ran.append(
            (n, cfg.num_layers, cfg.expert_parallel_size,
             cfg.fsdp_parallel_size, cfg.hidden_size)
        ),
    )
    assert chip_smoke.main(argv) == 0
    last = _stdout_lines(capsys)[-1]
    assert last == (
        '{"ok": true, "device": {"platform": "tpu", "kind": "TPU v5 lite", '
        f'"count": {device["count"]}}}}}'
    )
    # Full width, depth cut to 2, experts 2 x fsdp 2 — and nothing else.
    assert ran == (["one"] if not argv else [(4, 2, 2, 2, 1024)])


@pytest.mark.parametrize(
    "argv,device,boom",
    [
        ([], TPU1, chip_smoke.SmokeFailure("loss did not fall")),
        ([], TPU1, RuntimeError("RESOURCE_EXHAUSTED")),
        # The option and the machine disagree: never a pass.
        (["--chips", "4"], TPU1, None),
        ([], {**TPU1, "count": 4}, None),
    ],
    ids=["check_failed", "crash", "asked4_found1", "asked1_found4"],
)
def test_a_failing_phase_exits_nonzero_with_no_result(
    capsys, monkeypatch, argv, device, boom
):
    def phases(*a, **k):
        if boom is not None:
            raise boom

    monkeypatch.setattr(chip_smoke, "phase_device", lambda cache: device)
    monkeypatch.setattr(chip_smoke, "run_one_chip", phases)
    monkeypatch.setattr(chip_smoke, "phase_sharded_step", phases)
    try:
        rc = chip_smoke.main(argv)
    except RuntimeError:
        rc = 1  # an uncaught crash is a traceback and a non-zero exit
    assert rc != 0
    assert '"ok"' not in capsys.readouterr().out


# -- what a finished training run is held to ---------------------------------
def _good_run(cfg, steps):
    summary = {
        "final_step": steps,
        "tokens_seen": steps * cfg.batch_size * cfg.seq_length,
        "interventions": [],
        "goodput": {"seconds": {"productive": 1.0, "compile": 2.0}},
        "ran": {
            "batch_size": cfg.batch_size, "seq_length": cfg.seq_length,
            "num_layers": cfg.num_layers, "scan_layers": cfg.scan_layers,
            "gradient_accumulation_steps": cfg.gradient_accumulation_steps,
            "mesh": {"data": 1},
        },
        "compiled_costs": {"kernels": {"flash_fwd": 10, "gmm": 60}},
    }
    rows = [
        {"step": i + 1, "ts": 100.0 + i, "loss": 10.0 - i}
        for i in range(steps)
    ]
    return summary, rows


def _doctor(path, value):
    def apply(summary, rows):
        node = summary
        for key in path[:-1]:
            node = node[key]
        node[path[-1]] = value

    return apply


def _set_loss(index, value):
    def apply(summary, rows):
        rows[index]["loss"] = value

    return apply


@pytest.mark.parametrize(
    "doctor,words",
    [
        (None, None),
        (_doctor(("ran", "batch_size"), 8), "asked for"),
        (_doctor(("ran", "scan_layers"), True), "asked for"),
        (_doctor(("final_step",), 7), "final_step"),
        (_set_loss(3, float("nan")), "non-finite"),
        (_set_loss(-1, 11.0), "did not fall"),
        (_doctor(("compiled_costs", "kernels"), {"flash_fwd": 10}),
         "lacks Pallas kernels ['gmm']"),
        (_doctor(("compiled_costs",), {"available": False,
                                       "reason": "lower/compile failed"}),
         "lower/compile failed"),
    ],
    ids=["good", "batch_halved", "layout_changed", "stopped_early",
         "nan_loss", "loss_rose", "kernel_missing", "no_compiled_text"],
)
def test_check_train_run(doctor, words):
    """The OOM ladder halving the batch, a changed layer layout, a loss
    that is not finite or did not fall, a compiled step without its
    kernels: each fails the phase, whatever exit code training gave."""
    cfg = Config(vocab_size=512, hidden_size=64, num_layers=2, num_heads=4,
                 num_kv_heads=2, seq_length=64, batch_size=16)
    summary, rows = _good_run(cfg, 8)
    if doctor is None:
        out = chip_smoke.check_train_run(
            cfg, 8, summary, rows, ("flash_fwd", "gmm")
        )
        assert out["steady_step_s"] == 1.0
        assert out["tokens_per_s"] == 16 * 64
        return
    doctor(summary, rows)
    with pytest.raises(chip_smoke.SmokeFailure) as e:
        chip_smoke.check_train_run(cfg, 8, summary, rows, ("flash_fwd", "gmm"))
    assert words in str(e.value)


def test_kernel_census_reads_names_from_compiled_text():
    from luminaai_tpu.monitoring.attribution import kernel_census

    call = 'custom-call(%a), custom_call_target="tpu_custom_call", '
    text = "\n".join([
        f'  %x = bf16[2] {call}metadata={{op_name="jit(s)/jvp(flash_fwd)'
        '/pallas_call"}',
        f'  %y = bf16[2] {call}metadata={{op_name="jit(s)/transpose(jvp('
        'jit(tgmm)))/pallas_call"}',
        f'  %z = bf16[2] {call}metadata={{op_name="jit(s)/jvp(jit(gmm))'
        '/pallas_call"}',
        f'  %w = bf16[2] {call}metadata={{op_name="jit(s)/jvp(jit(gmm))'
        '/pallas_call"}',
        '  %v = f32[2] custom-call(%a), custom_call_target="Sharding"',
        f"  %u = f32[2] {call}backend_config={{}}",
    ])
    assert kernel_census(text) == {
        "flash_fwd": 1, "tgmm": 1, "gmm": 2, "unnamed": 1,
    }
    assert kernel_census("ROOT %add = f32[] add(%a, %b)") == {}


# -- once, for real, at a tiny size -------------------------------------------
def test_phases_drive_train_checkpoint_serve_at_a_tiny_size(tmp_path):
    """The CPU rehearsal of the guide's §2, kept as a test: `lumina
    train` -> the trainer's checkpoint verified -> the `lumina serve`
    stack restored from it answering JSON, SSE and concurrent requests.
    No kernel is required of a CPU program."""
    cfg = Config(
        vocab_size=512, hidden_size=64, num_layers=2, num_heads=4,
        num_kv_heads=2, seq_length=64, batch_size=8, use_moe=True,
        num_experts=4, moe_top_k=2, precision="fp32",
        use_flash_attention=False, moe_dispatch="gmm",
        learning_rate=1e-3,
    )
    # (What earlier tests of this worker's process left alive is not the
    # training run's: tests/test_inference.py alone leaves 2 MB.)
    gc.collect()
    live_before = sum(a.nbytes for a in jax.live_arrays())
    train = chip_smoke.phase_train(cfg, 4, str(tmp_path), ())
    assert train["steps"] == 4 and train["kernels"] == {}
    assert train["last_loss"] < train["first_loss"]
    ckpt = chip_smoke.phase_checkpoint(str(tmp_path), live_before)
    serve = chip_smoke.phase_serve(ckpt, max_new_tokens=3, timeout=120)
    assert serve["attention_backend"] == "ragged_xla"
    assert serve["decode_steps"] > 0 and all(serve["tokens"])
    # On the chip the same run must hold the flagship's kernels: here
    # the census is empty, so requiring one fails the phase.
    with open(tmp_path / "training_summary.json") as f:
        summary = json.load(f)
    with open(tmp_path / "logs" / "metrics.jsonl") as f:
        rows = [json.loads(l) for l in f if l.strip()]
    with pytest.raises(chip_smoke.SmokeFailure, match="lacks Pallas"):
        chip_smoke.check_train_run(cfg, 4, summary, rows, ("flash_fwd",))


# -- the compile cache is placed from outside ----------------------------------
@pytest.fixture
def restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_env_var_wins(monkeypatch, restore_cache_dir):
    """JAX_COMPILATION_CACHE_DIR set: that directory, and no directory
    is set in code."""
    jax.config.update("jax_compilation_cache_dir", "/seen/by/jax/from/env")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    assert environment.configure_compile_cache() == "/some/dir"
    assert jax.config.jax_compilation_cache_dir == "/seen/by/jax/from/env"


def test_compile_cache_defaults_to_the_checkout(monkeypatch,
                                                restore_cache_dir):
    """Unset: the fixed <checkout>/.jax_cache (the path is part of the
    cache key) — never tempfile, a pid or the time; and the same for
    every caller (lumina train/serve, chip_smoke.py, benchmark.run)."""
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    want = os.path.join(REPO, ".jax_cache")
    assert environment.configure_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_cli_main_configures_the_cache_but_not_for_the_launcher(monkeypatch):
    calls = []
    monkeypatch.setattr(
        environment, "configure_compile_cache", lambda: calls.append(1)
    )
    def diagnose(args):
        return 0

    monkeypatch.setattr(cli, "cmd_presets", lambda args: 0)
    monkeypatch.setattr(cli, "_serve_fleet", lambda args: 0)
    monkeypatch.setattr(cli, "cmd_diagnose", diagnose)
    monkeypatch.setattr(
        cli, "_COMPILING_COMMANDS", cli._COMPILING_COMMANDS + (diagnose,)
    )
    assert cli.main(["presets"]) == 0 and calls == []
    # The fleet launcher stays off jax: its children need the chips.
    assert cli.main(["serve", "--replicas", "2"]) == 0 and calls == []
    assert cli.main(["diagnose"]) == 0 and calls == [1]


# -- one peak table, no default ---------------------------------------------------
@pytest.mark.parametrize(
    "kind,peak",
    [("TPU v5 lite", 197e12), ("TPU v4", 275e12), ("cpu", None),
     ("NVIDIA H100", None), ("", None)],
)
def test_device_peak_flops_has_no_default(kind, peak):
    device = types.SimpleNamespace(device_kind=kind)
    if peak is None:
        with pytest.raises(ValueError, match="no peak FLOP/s on record"):
            environment.device_peak_flops(device)
    else:
        assert environment.device_peak_flops(device) == peak


# -- one process for each chip -------------------------------------------------------
class _FakeChild:
    def __init__(self, rc):
        self.rc = rc
        self.signalled = False

    def poll(self):
        return self.rc

    def send_signal(self, sig):
        self.signalled = True

    def wait(self, timeout=None):
        return self.rc


def test_each_fleet_child_gets_its_own_chip(monkeypatch):
    monkeypatch.setenv("SOME_USER_VAR", "kept")
    envs = [cli._replica_env(i) for i in range(4)]
    assert [e["TPU_VISIBLE_CHIPS"] for e in envs] == ["0", "1", "2", "3"]
    assert len({e["TPU_PROCESS_PORT"] for e in envs}) == 4
    for e in envs:
        # A one-chip world of its own, on top of the caller's environment.
        assert e["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
        assert e["TPU_PROCESS_BOUNDS"] == "1,1,1"
        assert e["SOME_USER_VAR"] == "kept"
        assert e["JAX_PLATFORMS"] == "cpu"  # CPU behaviour unchanged


def test_fleet_launcher_fails_fast_on_a_dead_child(monkeypatch):
    """A replica that exits before it is ready (no chip left for it)
    stops the launch at once — not after wait_ready's 600 s — and the
    launcher terminates the others."""
    spawned = []

    def fake_popen(cmd, env=None, **kw):
        # The first child lives (never ready); the second dies at once.
        child = _FakeChild(None if not spawned else 1)
        spawned.append((cmd, env, child))
        return child

    monkeypatch.setattr(subprocess, "Popen", fake_popen)
    monkeypatch.setattr(
        sys, "argv", ["lumina", "serve", "--replicas", "2", "--port", "7000"]
    )
    args = cli.build_parser().parse_args(sys.argv[1:])
    with pytest.raises(RuntimeError, match="exited with code 1 before"):
        cli._serve_fleet(args)
    assert [env["TPU_VISIBLE_CHIPS"] for _, env, _ in spawned] == ["0", "1"]
    first = spawned[0][0]
    assert first[first.index("--port") + 1] == "7001"
    assert "--replicas" not in first
    assert spawned[0][2].signalled  # the live sibling was terminated


def test_wait_ready_without_procs_still_times_out():
    from luminaai_tpu.serving.router import wait_ready

    with pytest.raises(TimeoutError, match="never became ready"):
        wait_ready(["http://127.0.0.1:9"], timeout_s=0.2, poll_s=0.05)
