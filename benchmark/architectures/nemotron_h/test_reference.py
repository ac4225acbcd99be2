"""The yardstick for `correct` is itself checked: this architecture's
reference against LuminaTransformer at a tiny size on the CPU (seven
layers M E M * E M E, each ONE sub-layer: 16 state-space heads of 8 over 4
groups with a state of 16; 8 query heads over 2 k/v heads without
positions; 32 experts of which 8 are held, 6 picks, relu^2 in a latent of
32 beside a shared expert of 96): the program's uncached logits with the
four controls that must read as another model, the block form against the
recurrence, the published size from the catalog's keys, the adapter's
refusals, and the cell's tiny rehearsal with this architecture's OWN
widths shrunk. The modules are reached as a cell reaches them, by the
architecture's name."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import correct, manifest, model_config

NEMOTRON = manifest.Architecture("nemotron_h")
nemotron_reference, nemotron_adapter = NEMOTRON.reference, NEMOTRON.adapter
NEMOTRON_CELL = "nemotron-3-super-serve-reason"

NEMOTRON_TINY = {
    "model_type": "nemotron_h", "attention_bias": False, "mlp_bias": False,
    "use_bias": False, "mamba_proj_bias": False, "use_conv_bias": True,
    "mamba_hidden_act": "silu", "mlp_hidden_act": "relu2",
    "chunk_size": 16, "conv_kernel": 4, "expand": 2, "head_dim": 16,
    "hidden_size": 64, "hybrid_override_pattern": "MEM*EMEMEM*E",
    "intermediate_size": 48, "layer_norm_epsilon": 1e-5,
    "mamba_head_dim": 8, "mamba_num_heads": 16,
    "max_position_embeddings": 4096, "moe_intermediate_size": 48,
    "moe_latent_size": 32, "moe_shared_expert_intermediate_size": 96,
    "moe_shared_expert_overlap": False, "mtp_hybrid_override_pattern": "*E",
    "n_group": 1, "n_groups": 4, "n_routed_experts": 8,
    "n_shared_experts": 1, "norm_eps": 1e-5, "norm_topk_prob": True,
    "num_attention_heads": 8, "num_experts_per_tok": 6,
    "num_hidden_layers": 7, "num_key_value_heads": 2,
    "num_logits_to_keep": 1, "num_nextn_predict_layers": 1,
    "partial_rotary_factor": 1, "rescale_prenorm_residual": True,
    "residual_in_fp32": False, "rope_theta": 10000,
    "routed_scaling_factor": 5, "sliding_window": None,
    "ssm_state_size": 16, "tie_word_embeddings": False,
    "time_step_floor": 1e-4, "time_step_max": 0.1, "time_step_min": 0.001,
    "topk_group": 1, "use_mamba_kernels": True, "vocab_size": 512,
    "reduced": ["num_hidden_layers", "n_routed_experts"],
    "source_values": {"num_hidden_layers": 12, "n_routed_experts": 32},
    "reference": {"selection_bias_init_std": 0.3},
    "deployment": {"experts_held_offset": 8},
    # init_std 0.12: at the repo's 0.02 and hidden 64 every branch is a
    # thousandth of the residual and no control reads as another model.
    "program": {"precision": "fp32", "use_flash_attention": False,
                "use_stable_embedding": False, "scan_layers": False,
                "moe_dispatch": "gmm", "capacity_factor": 4.0,
                "routing_noise_std": 0.0, "init_std": 0.12},
}


def nemotron_build(body, **over):
    from luminaai_tpu.models.transformer import LuminaTransformer
    from luminaai_tpu.parallel.sharding import unbox

    cfg = model_config.build_config(NEMOTRON, body,
                                    **{"seq_length": 96, **over})
    cfg.validate()
    model = LuminaTransformer(cfg)
    params = unbox(jax.jit(model.init)(
        jax.random.key(3), jnp.zeros((1, 8), jnp.int32))["params"])
    return cfg, model, params


def _nemotron_ids(rows=2, length=80):
    return jnp.asarray(np.random.RandomState(0).randint(
        3, 512, size=(rows, length)), jnp.int32)


def test_nemotron_adapter_names_the_layers_the_share_and_the_widths():
    cfg, _, params = nemotron_build(NEMOTRON_TINY)
    assert cfg.layer_mixers == ("ssm2", "none", "ssm2", "attention", "none",
                                "ssm2", "none")
    assert cfg.layer_ffns == ("none", "moe", "none", "none", "moe", "none",
                              "moe")
    assert not cfg.use_rope and cfg.keeps_lane_state()
    assert (cfg.ssm2_num_heads, cfg.ssm2_head_dim, cfg.ssm2_groups,
            cfg.ssm_state_size, cfg.ssm2_chunk) == (16, 8, 4, 16, 16)
    assert cfg.num_experts == 32 and cfg.experts_held == (8, 8)
    assert (cfg.moe_top_k, cfg.moe_latent_size, cfg.moe_shared_size,
            cfg.moe_expert_act, cfg.moe_routed_scale) == (
                6, 32, 96, "relu2", 5.0)
    assert cfg.moe_selection_bias and cfg.moe_score_func == "sigmoid"
    # one norm a layer; nothing of a dense feed-forward anywhere
    assert sorted(params["layer_0"]) == ["attn_norm", "ssm"]
    assert sorted(params["layer_1"]) == ["ffn_norm", "moe"]
    assert sorted(params["layer_3"]) == ["attention", "attn_norm"]
    ssm, moe = params["layer_0"]["ssm"], params["layer_1"]["moe"]
    assert ssm["w_in"].shape == (64, 128 + 256 + 16)
    assert ssm["conv"].shape == (4, 256) and ssm["A_log"].shape == (16,)
    assert moe["wi"].shape == (8, 32, 48) and moe["wo"].shape == (8, 48, 32)
    assert moe["fc1"].shape == (64, 32) and moe["router"].shape == (64, 32)
    assert moe["shared_expert"]["wi"].shape == (64, 96)
    # the family's initialisers: a decay of 1..16 and a step in [1e-3, 0.1]
    a, dt = np.exp(np.asarray(ssm["A_log"])), np.log1p(
        np.exp(np.asarray(ssm["dt_bias"])))
    assert (a >= 1).all() and (a <= 16).all() and a.std() > 1
    assert (dt >= 1e-3 - 1e-6).all() and (dt <= 0.1 + 1e-6).all()
    assert float(jnp.abs(moe["selection_bias"]).max()) > 0.05
    kw = nemotron_reference.from_config_file(NEMOTRON_TINY)
    assert kw["pattern"] == "MEM*EME" and kw["routed_scale"] == 5.0
    assert (kw["held_offset"], kw["num_experts"], kw["top_k"]) == (8, 32, 6)


@pytest.mark.parametrize("bad, word", [
    ({"n_group": 8}, "n_group"), ({"topk_group": 2}, "topk_group"),
    ({"mamba_num_heads": 12}, "mamba_num_heads x mamba_head_dim"),
    ({"hybrid_override_pattern": "MEM-EME"}, "hybrid_override_pattern"),
    ({"hybrid_override_pattern": "MEM"}, "names 3 layers"),
    ({"mlp_hidden_act": "silu"}, "mlp_hidden_act"),
    ({"n_shared_experts": 2}, "n_shared_experts"),
    ({"layer_norm_epsilon": 1e-6}, "layer_norm_epsilon"),
    ({"rope_scaling": {"type": "yarn"}}, "does not read"),
    ({"reference": {}}, "selection_bias_init_std"),
], ids=["groups", "group_limit", "inner_width", "dense_layer",
        "short_pattern", "gated_experts", "two_shared", "two_epsilons",
        "unknown_key", "inits"])
def test_nemotron_adapter_refuses_what_it_cannot_express(bad, word):
    with pytest.raises(model_config.Unsupported, match=word):
        nemotron_adapter.source_kwargs(dict(NEMOTRON_TINY, **bad))


def test_nemotron_uncached_logits_match_the_reference():
    """The program's uncached forward (float32, the BLOCK form over five
    blocks of 16) against the reference's token-by-token recurrence, and
    the four controls, each read as another model by compare_logits at a
    tolerance of 5e-6 (the sound reading is ~4e-7)."""
    cfg, model, params = nemotron_build(NEMOTRON_TINY)
    ids = _nemotron_ids()
    kw = nemotron_reference.from_config_file(NEMOTRON_TINY)
    got = jax.jit(lambda p: nemotron_adapter.program_logits(model, p, ids))(
        params)
    view = nemotron_adapter.params_view(cfg, params)
    want = jax.jit(lambda v: nemotron_reference.forward(v, ids, **kw))(view)
    verdict = correct.compare_logits(got, want, rel_rms_tol=5e-6)
    assert verdict["ok"], verdict
    blocked = nemotron_reference.forward(view, ids, q_block=32, **kw)
    assert float(jnp.abs(blocked - want).max()) < 1e-4
    for name, control in {
        "bf16_state": {"state_dtype": jnp.bfloat16},
        "gate_after_the_norm": {"gate_after_norm": True},
        "b_c_of_the_wrong_group": {"group_shift": 1},
        "bias_in_the_weights": {"bias_in_weights": True},
    }.items():
        other = nemotron_reference.forward(view, ids, controls=control, **kw)
        assert not correct.compare_logits(got, other, rel_rms_tol=5e-6)[
            "ok"], name


def test_nemotron_block_form_is_the_recurrence_and_has_a_gradient():
    """ops/ssm.py::block_scan against the reference's recurrence on the
    mixer alone, entering from a state, with padding rows; and a loss
    through the whole tiny stack has finite, non-zero gradients in every
    mixer parameter (`lumina train` runs this form)."""
    from luminaai_tpu.ops import ssm

    rs = np.random.RandomState(1)
    B, T, H, P, G, N = 2, 37, 4, 8, 2, 16
    x = jnp.asarray(rs.randn(B, T, H, P), jnp.float32)
    dt = jnp.asarray(rs.rand(B, T, H) * 0.5, jnp.float32).at[:, 30:].set(0.0)
    a = -jnp.asarray(rs.rand(H) * 8 + 1, jnp.float32)
    b, c = (jnp.asarray(rs.randn(B, T, G, N), jnp.float32) for _ in "bc")
    h0 = jnp.asarray(rs.randn(B, N, H * P), jnp.float32)
    y, h = ssm.block_scan(x, dt, a, b, c, h0=h0, chunk=16)
    S = h0.reshape(B, N, H, P)
    of_head = jnp.arange(H) // (H // G)
    for t in range(T):
        S = (jnp.exp(dt[:, t] * a)[:, None, :, None] * S + jnp.einsum(
            "bhn,bhp->bnhp", b[:, t][:, of_head],
            dt[:, t][..., None] * x[:, t]))
        want = jnp.einsum("bnhp,bhn->bhp", S, c[:, t][:, of_head])
        assert float(jnp.abs(y[:, t] - want).max()) < 1e-4, t
    assert float(jnp.abs(h - S.reshape(B, N, H * P)).max()) < 1e-5
    cfg, model, params = nemotron_build(NEMOTRON_TINY)
    ids = _nemotron_ids(2, 40)
    grads = jax.jit(jax.grad(lambda p: correct.next_token_loss(
        nemotron_adapter.program_logits(model, p, ids), ids)))(params)
    for name, g in grads["layer_2"]["ssm"].items():
        g = np.asarray(g)
        assert np.isfinite(g).all() and np.abs(g).max() > 0, name
    for name in ("fc1", "fc2", "wi", "wo", "router"):
        g = np.asarray(grads["layer_1"]["moe"][name])
        assert np.isfinite(g).all() and np.abs(g).max() > 0, name


def test_nemotron_work_reproduces_the_published_size():
    """From the catalog row's keys alone: 40 state-space layers x 109.64M
    + 8 attention x 35.66M + 40 expert layers x (54.53M + 512 x 5.505M) +
    2 x 131,072 x 4,096 + the final norm = 120.67B, 12.77B active a token
    at top-22; and the file's cut is 4,648M."""
    bench = manifest.load_benchmark()
    cell = manifest.Cell(bench, NEMOTRON_CELL)
    work, body = cell.architecture.work, cell.config
    whole = work.published_params(body)
    assert whole["total"] == 120_668_707_840
    assert whole["active"] == 12_770_237_440
    assert round(whole["total"] / 1e9, 2) == 120.67
    assert round(whole["active"] / 1e9, 2) == 12.77
    assert work._mixer_params(body) == 109_640_064
    assert work._expert_params(body) == 5_505_024
    assert work.params_total(body) == 4_648_163_712


def test_nemotron_cell_resolves_this_architecture():
    bench = manifest.load_benchmark()
    assert manifest.check(bench) == []
    cell = manifest.Cell(bench, NEMOTRON_CELL)
    assert cell.architecture.name == "nemotron_h" and cell.chips == 1
    kw = model_config.config_kwargs(cell.architecture, cell.config)
    assert kw["experts_held"] == (0, 128) and kw["num_experts"] == 512
    assert "".join({"ssm2": "M", "attention": "*", "none": "E"}[m]
                   for m in kw["layer_mixers"]) == "MEMEMEM*EME"
    assert kw["layer_ffns"] == tuple(
        "moe" if m == "none" else "none" for m in kw["layer_mixers"])
    assert kw["capacity_factor"] == 4.0 and kw["prefill_chunk_size"] == 256
    assert kw["use_rope"] is False and kw["moe_top_k"] == 22
    work = cell.architecture.work
    assert set(work.KERNEL_FNS) == manifest.kernel_names("nemotron_h") == {
        "ssm_tick", "grouped_matmul", "chunk_attention", "lane_attention"}
    for fn in work.KERNEL_FNS.values():
        counts = fn(cell.config, {})
        assert counts["ops"] > 0 and counts["bytes"] > 0
    # the catalog row's numbers, every one at the top level, no width cut
    body = cell.config
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(json.loads(ln) for ln in f
                   if "Nemotron-3-Super-120B-A12B-BF16" in ln)
    assert body["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in body["reduced"]:
            assert body["source_values"][key] == value, key
        else:
            assert body[key] == value, key
    assert body["reduced"] == ["num_hidden_layers", "n_routed_experts",
                               "vocab_size"]
    assert (body["num_hidden_layers"], body["n_routed_experts"],
            body["vocab_size"]) == (11, 128, 32768)
    assert (body["hidden_size"], body["mamba_num_heads"],
            body["mamba_head_dim"], body["ssm_state_size"], body["n_groups"],
            body["conv_kernel"], body["num_attention_heads"],
            body["num_key_value_heads"], body["head_dim"],
            body["moe_latent_size"], body["moe_intermediate_size"],
            body["moe_shared_expert_intermediate_size"],
            body["num_experts_per_tok"], body["routed_scaling_factor"]) == (
                4096, 128, 64, 128, 8, 4, 32, 2, 128, 1024, 2688, 5376, 22,
                5)
    dep = body["deployment"]
    assert dep["layer_shared_by_chips"] == 4 and dep["num_slots"] % 8 == 0
    assert dep["experts_held_offset"] == 0 and dep["stands_for"]
    assert (dep["page_size"], dep["max_slot_tokens"]) == (128, 6144)
    assert body["departures"] == [] and set(body["assumed"]) >= {
        "positions", "initialisers", "left_out", "mixer", "experts"}
    reported = {m["name"] for m in bench["per_layer"]
                if NEMOTRON_CELL in m.get("workloads", ())}
    other = {m["name"] for m in bench["per_layer"]
             if "mimo-v2-flash-serve-reason" in m.get("workloads", ())}
    assert reported == (other - {
        "kv_window_rows_per_step", "kv_window_bytes_per_step",
        "ring_wraps_per_request"}) | {
        "ssm_ms_step", "ssm_roofline", "ssm_rows_per_step",
        "ssm_state_bytes_per_step", "held_experts_hit_pct"}
    mix = cell.traffic
    assert mix["prompt_tokens"] == {"dist": "lognormal", "median": 512,
                                    "sigma": 0.8, "min": 64, "max": 4096}
    assert mix["output_tokens"] == {"dist": "lognormal", "median": 1024,
                                    "sigma": 0.6, "min": 128, "max": 2048}
    assert mix["arrivals"]["process"] == "poisson"
    assert mix["arrivals"]["rate_per_s"] == pytest.approx(
        0.7 * mix["arrivals"]["knee_per_s"], rel=0.02)


NEMOTRON_REHEARSAL = """
import json
from types import SimpleNamespace
import jax
from benchmark import manifest, rehearse, serve_cell

serve_cell.SAMPLE_PROMPT_TOKENS = 40
cell = rehearse.tiny_cell(manifest.Cell(manifest.load_benchmark(), %r))
# rehearse.TINY shrinks the keys every architecture shares; this one's own
# widths follow them here (PERF.md section 7).
cell.config.update(
    num_hidden_layers=11, num_attention_heads=8, expand=2,
    mamba_num_heads=16, mamba_head_dim=8, ssm_state_size=16, n_groups=4,
    chunk_size=8, moe_latent_size=32, moe_intermediate_size=48,
    intermediate_size=48, moe_shared_expert_intermediate_size=96,
    num_experts_per_tok=6, n_routed_experts=8)
cell.config["source_values"]["n_routed_experts"] = 32
cell.config["program"]["init_std"] = 0.12
device = {"platform": "cpu", "kind": "rehearsal", "count": 1,
          "peak": {"bf16_flops_per_s": 1.0, "hbm_bytes_per_s": 1.0}}
args = SimpleNamespace(seed=2**31 + 12345, seconds=3.0, trace=0,
                       keep_trace=None)
res = serve_cell.run(cell, args, device)
print("REHEARSED " + json.dumps({
    "correct": res["correct"], "attempted": res["attempted"],
    "failed": res["failed"], "metrics": sorted(res["metrics"])}))
"""


def test_nemotron_cells_tiny_rehearsal_reads_correct():
    """The cell's driver end to end on the CPU at toy widths (a child
    process: the harness's clocks and compile cache are a process's own):
    the reference phase, the scheduler over states, pages and layers with
    no entry, the open-loop window, `correct` true and no request failed."""
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=root,
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    proc = subprocess.run(
        [sys.executable, "-c", NEMOTRON_REHEARSAL % NEMOTRON_CELL], cwd=root,
        env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = next(ln for ln in proc.stdout.splitlines()
                if ln.startswith("REHEARSED "))
    result = json.loads(line.split(" ", 1)[1])
    assert result["correct"] is True, result
    assert result["attempted"] > 0 and result["failed"] == 0
    assert {"ttft_mean_ms", "itl_p95_ms", "setup_s"} <= set(
        result["metrics"])
