"""Performance attribution tests (ISSUE 3).

Covers: the op-classifier goldens, attribute_trace on a synthetic
fixture, compiled-cost gauges present-or-gracefully-absent on CPU, the
analytic-vs-compiled MFU cross-check, and the donation audit."""

import json

import pytest

from luminaai_tpu.monitoring.attribution import (
    MFU_DIVERGENCE_THRESHOLD,
    OpRow,
    analytic_train_flops,
    attribute_trace,
    classify_op,
    compiled_cost_metrics,
    donation_audit,
    export_attribution,
    tree_bytes,
)
from luminaai_tpu.monitoring.telemetry import MetricsRegistry


# ---------------------------------------------------------------------------
# classifier goldens
# ---------------------------------------------------------------------------

# Representative framework-op names from the r3 flagship hlo_stats table;
# the classifier promoted out of scripts/analyze_trace.py must keep
# mapping them to the same subsystems or historical breakdowns silently
# change meaning.
CLASSIFIER_GOLDENS = [
    # (fw_name, category, source) -> subsystem
    (
        ("transformer/layer_3/attention/pallas_call", "custom-call", ""),
        "attn_flash_kernels",
    ),
    (("jit(einsum)/bch,vh->bcv", "dot", ""), "ce_loss"),
    (("loss/chunk", "dot", "luminaai_tpu/ops/fused.py:120"), "ce_loss"),
    (("moe/experts/egch,ehf->egcf", "dot", ""), "moe_expert_matmul"),
    (("moe/experts/egcf,efh->egch", "dot", ""), "moe_expert_matmul"),
    (("moe/gmm/pallas_call", "custom-call", ""), "moe_expert_matmul"),
    (("transformer/moe/router/top_k", "sort", ""), "moe_route_dispatch"),
    (("transformer/layer_0/attention/qkv_fused", "dot", ""), "attn_proj_rope"),
    (("rope/qkv", "convert", ""), "attn_proj_rope"),
    (("copy.1", "data formatting", ""), "data_formatting"),
    (("", "fusion", ""), "unattributed(optimizer+dispatch_bwd)"),
    (("something/else", "fusion", ""), "other"),
]


@pytest.mark.parametrize("args,want", CLASSIFIER_GOLDENS)
def test_classify_op_goldens(args, want):
    assert classify_op(*args) == want


def test_attribute_trace_synthetic_fixture():
    """A synthetic 2-step trace folds into the right ms/step, fractions
    and dominant bounds, heaviest subsystem first."""
    rows = [
        OpRow(6000.0, "moe/experts/egch,ehf->egcf", "dot", "", "MXU"),
        OpRow(2000.0, "moe/experts/egcf,efh->egch", "dot", "", "HBM"),
        OpRow(3000.0, "l/attention/pallas_call", "custom-call", "", "mixed"),
        OpRow(1000.0, "", "fusion", "", "HBM"),
    ]
    attr = attribute_trace(rows, n_steps=2, top_k=2)
    assert list(attr.ms_per_step) == [
        "moe_expert_matmul",
        "attn_flash_kernels",
        "unattributed(optimizer+dispatch_bwd)",
    ]
    # 8000us over 2 steps = 4.0 ms/step for the expert matmuls.
    assert attr.ms_per_step["moe_expert_matmul"] == pytest.approx(4.0)
    assert attr.total_ms_per_step == pytest.approx(6.0)
    assert attr.fraction["moe_expert_matmul"] == pytest.approx(8 / 12)
    # Dominant bound is time-weighted: 6000us MXU beats 2000us HBM.
    assert attr.dominant_bound["moe_expert_matmul"] == "MXU"
    assert len(attr.top_ops) == 2
    assert attr.top_ops[0]["ms_per_step"] == pytest.approx(3.0)


def test_attribute_trace_rejects_bad_steps():
    with pytest.raises(ValueError):
        attribute_trace([], n_steps=0)


def test_export_attribution_gauges_and_jsonl(tmp_path):
    attr = attribute_trace(
        [OpRow(1000.0, "moe/experts/egch,ehf->x", "dot", "", "MXU")],
        n_steps=1,
    )
    reg = MetricsRegistry()
    jsonl = tmp_path / "attribution.jsonl"
    record = export_attribution(attr, registry=reg, jsonl_path=str(jsonl))
    snap = reg.snapshot()
    assert snap["attribution_ms_per_step"][
        "subsystem=moe_expert_matmul"
    ] == pytest.approx(1.0)
    assert snap["attribution_fraction"][
        "subsystem=moe_expert_matmul"
    ] == pytest.approx(1.0)
    assert snap["attribution_total_ms_per_step"] == pytest.approx(1.0)
    on_disk = json.loads(jsonl.read_text())
    assert on_disk == record
    assert on_disk["subsystems"]["moe_expert_matmul"]["bound"] == "MXU"


# ---------------------------------------------------------------------------
# compiled-cost accounting (CPU)
# ---------------------------------------------------------------------------

def test_compiled_cost_metrics_on_cpu_jit():
    """Cost-analysis gauges are present on the CPU backend (which has a
    cost model) — or the result says available: False with a reason.
    Either way nothing raises and nothing is fabricated."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(x):
        return (x @ x).sum()

    reg = MetricsRegistry()
    out = compiled_cost_metrics(
        f, jnp.ones((32, 32), jnp.float32), program="train", registry=reg
    )
    assert out["available"] is True
    snap = reg.snapshot()
    if out["cost_model"] is not None:
        assert out["cost_model"]["flops_per_step"] > 0
        assert (
            snap["compiled_flops_per_step"]["program=train"]
            == out["cost_model"]["flops_per_step"]
        )
    else:
        assert out["reason"]
        assert "compiled_flops_per_step" not in snap
    # memory_analysis present on CPU; peak sums the components minus
    # aliased (donated) bytes so donated state isn't double-counted.
    if out["memory"]:
        m = out["memory"]
        assert m["peak_bytes"] == (
            m.get("argument_bytes", 0)
            + m.get("output_bytes", 0)
            + m.get("temp_bytes", 0)
            + m.get("generated_code_bytes", 0)
            - m.get("alias_bytes", 0)
        )


def test_peak_bytes_discounts_donated_buffers():
    """A donated argument aliases its output: peak must count the buffer
    once (argument+output-alias), not twice."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: x + 1, donate_argnums=(0,))
    out = compiled_cost_metrics(f, jnp.ones((256, 256), jnp.float32))
    m = out["memory"]
    if not m or not m.get("alias_bytes"):
        pytest.skip("backend reports no aliasing")
    nbytes = 256 * 256 * 4
    # One live copy of x (donated in-place) + temps, never 2x.
    assert m["peak_bytes"] < 2 * nbytes


def test_compiled_cost_metrics_degrades_without_handle():
    """A plain callable (no .lower, no .jitted) degrades gracefully."""
    out = compiled_cost_metrics(lambda x: x, 1.0)
    assert out == {
        "available": False,
        "reason": "function has no .lower/.jitted handle",
    }


def test_compiled_cost_metrics_uses_wrapper_jitted_handle():
    """Wrappers exposing .jitted (make_train_step's `call`) are lowered
    through the handle."""
    import jax
    import jax.numpy as jnp

    jitted = jax.jit(lambda x: x * 2)

    def wrapper(x):
        return jitted(x)

    wrapper.jitted = jitted
    out = compiled_cost_metrics(wrapper, jnp.ones((4,)))
    assert out["available"] is True


def test_mfu_crosscheck_flags_divergence():
    """|compiled/analytic - 1| > 10% trips the flag; within 10% passes."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(x):
        return (x @ x).sum()

    x = jnp.ones((64, 64), jnp.float32)
    base = compiled_cost_metrics(f, x)
    if not (base.get("cost_model") or {}).get("flops_per_step"):
        pytest.skip("backend returned no cost model")
    flops = base["cost_model"]["flops_per_step"]

    agree = compiled_cost_metrics(f, x, analytic_flops=flops * 1.05)
    assert agree["mfu_crosscheck"]["flagged"] is False
    diverge = compiled_cost_metrics(f, x, analytic_flops=flops * 2.0)
    xc = diverge["mfu_crosscheck"]
    assert xc["flagged"] is True
    assert xc["divergence"] == pytest.approx(-0.5)
    assert xc["threshold"] == MFU_DIVERGENCE_THRESHOLD


def test_analytic_train_flops_is_6nt():
    assert analytic_train_flops(1000, 10) == 60000.0


# ---------------------------------------------------------------------------
# diagnose connectivity probe (CPU-safe single-host fallback)
# ---------------------------------------------------------------------------

def test_connectivity_probe_cpu_single_host():
    from luminaai_tpu.utils.environment import connectivity_probe

    reg = MetricsRegistry()
    out = connectivity_probe(payload_mb=0.05, iters=1, registry=reg)
    vis = out["visibility"]
    assert vis["visibility_ok"] is True
    assert vis["global_device_count"] == (
        vis["process_count"] * vis["local_device_count"]
    )
    ici = out["allreduce"]["ici"]
    assert "error" not in ici
    assert ici["mean_seconds"] > 0
    snap = reg.snapshot()
    assert snap["diagnose_device_visibility_ok"] == 1.0
    assert snap["diagnose_allreduce_seconds"]["axis=ici"] > 0
    assert snap["diagnose_allreduce_gbps"]["axis=ici"] > 0


def test_connectivity_probe_reports_degraded_slice(monkeypatch):
    """A ragged device grid (a host missing part of the slice) is the
    case the probe exists for: it must still REPORT — visibility dict,
    visibility gauges, and a skipped-all-reduce note — instead of dying
    on the mesh reshape."""
    import jax

    from luminaai_tpu.utils.environment import connectivity_probe

    n = jax.device_count()
    monkeypatch.setattr(jax, "process_count", lambda: n + 2)
    reg = MetricsRegistry()
    out = connectivity_probe(payload_mb=0.01, iters=1, registry=reg)
    assert out["visibility"]["visibility_ok"] is False
    assert "ragged" in out["allreduce"]["skipped"]
    snap = reg.snapshot()
    assert snap["diagnose_device_visibility_ok"] == 0.0
    assert snap["diagnose_processes"] == n + 2
    assert "diagnose_allreduce_seconds" not in snap


# -- donation audit (r6) ----------------------------------------------------
def _donation_step_memory(donate: bool, accum: int = 1):
    import jax
    import jax.numpy as jnp

    from luminaai_tpu.config import Config
    from luminaai_tpu.models.transformer import LuminaTransformer
    from luminaai_tpu.parallel.mesh import build_mesh
    from luminaai_tpu.parallel.sharding import init_sharded_state
    from luminaai_tpu.parallel.train_step import make_train_step
    from luminaai_tpu.training.optimizer import make_optimizer, make_schedule

    cfg = Config(
        vocab_size=128, hidden_size=32, num_layers=1, num_heads=2,
        num_kv_heads=1, seq_length=32, batch_size=16,
        use_flash_attention=False, gradient_checkpointing=False,
        precision="fp32", donate_state=donate,
        gradient_accumulation_steps=accum,
    )
    model = LuminaTransformer(cfg)
    schedule = make_schedule(cfg, 100)
    tx = make_optimizer(cfg, 100, schedule)
    mesh = build_mesh(cfg)
    state, shardings = init_sharded_state(
        cfg, model, tx, mesh, jax.random.key(0)
    )
    step = make_train_step(cfg, model, shardings, mesh, schedule, tx)
    batch = {"input_ids": jnp.ones((cfg.batch_size, cfg.seq_length),
                                   jnp.int32)}
    cc = compiled_cost_metrics(step, state, batch, program="train",
                               registry=MetricsRegistry())
    return cc.get("memory"), tree_bytes(state)


def test_donation_audit_full_coverage_through_scan_accumulation():
    """The donated train step must alias ~its whole resident state —
    INCLUDING when grad accumulation runs as a lax.scan inside the jit
    (the 'scan'd accumulation step' of the r6 audit): opt-state buffers
    update in place, coverage ≈ 1."""
    memory, state_bytes = _donation_step_memory(donate=True, accum=2)
    reg = MetricsRegistry()
    audit = donation_audit(memory, state_bytes, expected=True, registry=reg)
    assert audit["available"] and audit["coverage"] is not None
    assert audit["coverage"] > 0.9, audit
    assert audit["flagged"] is False
    snap = reg.snapshot()
    assert snap["donation_alias_coverage"]["program=train"] > 0.9
    assert snap["donation_audit_flagged"]["program=train"] == 0.0


def test_donation_audit_flags_missing_donation():
    """donate_state=False compiles a copying step: alias bytes collapse
    and the audit flags it — the failure mode the audit exists for."""
    memory, state_bytes = _donation_step_memory(donate=False)
    audit = donation_audit(
        memory, state_bytes, expected=True, registry=MetricsRegistry()
    )
    assert audit["coverage"] < 0.1, audit
    assert audit["flagged"] is True


def test_donation_audit_degrades_without_memory():
    audit = donation_audit(
        None, 1000, expected=True, registry=MetricsRegistry()
    )
    assert audit["available"] is False
    assert "reason" in audit


def test_tree_bytes_counts_mixed_dtypes_and_keys():
    import jax
    import jax.numpy as jnp

    tree = {
        "a": jnp.zeros((4, 4), jnp.float32),       # 64 bytes
        "b": jnp.zeros((8,), jnp.int8),            # 8 bytes
        "c": jax.ShapeDtypeStruct((2, 2), jnp.bfloat16),  # 8 bytes
        "k": jax.random.key(0),                    # extended dtype: no crash
    }
    total = tree_bytes(tree)
    assert total >= 64 + 8 + 8


