"""The yardstick for `correct` is itself checked: this architecture's
reference against LuminaTransformer at a tiny size on the CPU (four layers:
full + dense, window, window, full; 8 query heads over 2 k/v heads in the
full layers and 4 in the window layers; keys of 24 columns, 8 of them
rotated, over values of 16; a sink on the window layers; 16 experts, 4
held): the program's uncached logits, the part-rotated head by hand at the
published sizes, the sink as one more softmax column, and the shares of
the expert layer against the uncut layer. The modules are reached as a
cell reaches them, by the architecture's name."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import correct, manifest, model_config

MIMO = manifest.Architecture("mimo_v2")
mimo_reference, mimo_adapter = MIMO.reference, MIMO.adapter
MIMO_CELL = "mimo-v2-flash-serve-reason"

MIMO_TINY = {
    "model_type": "mimo_v2_flash", "hidden_act": "silu",
    "attention_bias": False, "hidden_size": 64, "intermediate_size": 160,
    "num_hidden_layers": 4, "num_attention_heads": 8,
    "num_key_value_heads": 2, "head_dim": 24, "v_head_dim": 16,
    "swa_num_attention_heads": 8, "swa_num_key_value_heads": 4,
    "swa_head_dim": 24, "swa_v_head_dim": 16,
    "attention_value_scale": 0.707, "vocab_size": 512,
    "layernorm_epsilon": 1e-5, "rope_theta": 5000000,
    "swa_rope_theta": 10000, "partial_rotary_factor": 0.334,
    "tie_word_embeddings": False, "sliding_window": 16,
    "sliding_window_size": 16, "attention_chunk_size": 16,
    "hybrid_layer_pattern": [0, 1, 1, 0, 1, 1],
    "moe_layer_freq": [0, 1, 1, 1, 1, 1],
    "add_swa_attention_sink_bias": True,
    "add_full_attention_sink_bias": False,
    "max_position_embeddings": 4096, "moe_intermediate_size": 32,
    "n_group": 1, "topk_group": 1, "topk_method": "noaux_tc",
    "norm_topk_prob": True, "scoring_func": "sigmoid",
    "n_routed_experts": 4, "num_experts_per_tok": 4,
    "n_shared_experts": None, "routed_scaling_factor": None,
    "reduced": ["num_hidden_layers", "n_routed_experts"],
    "source_values": {"num_hidden_layers": 6, "n_routed_experts": 16},
    "reference": {"sink_init_std": 1.0, "selection_bias_init_std": 0.3},
    "deployment": {"experts_held_offset": 4},
    "program": {"precision": "fp32", "use_flash_attention": False,
                "use_stable_embedding": False, "moe_dispatch": "gmm",
                "capacity_factor": 4.0, "routing_noise_std": 0.0},
}


def mimo_build(body, **over):
    from luminaai_tpu.models.transformer import LuminaTransformer
    from luminaai_tpu.parallel.sharding import unbox

    cfg = model_config.build_config(MIMO, body, **{"seq_length": 96, **over})
    cfg.validate()
    model = LuminaTransformer(cfg)
    params = unbox(jax.jit(model.init)(
        jax.random.key(3), jnp.zeros((1, 8), jnp.int32))["params"])
    return cfg, model, params


def _mimo_ids(rows=2, length=80):
    return jnp.asarray(np.random.RandomState(0).randint(
        3, 512, size=(rows, length)), jnp.int32)


def test_mimo_adapter_names_the_kinds_the_share_and_the_rotation():
    cfg, _, params = mimo_build(MIMO_TINY)
    assert cfg.layer_windows == (None, 16, 16, None)
    assert cfg.layer_kv_heads == (2, 4, 4, 2)
    assert cfg.layer_rope_theta == (5e6, 1e4, 1e4, 5e6)
    assert cfg.layer_sink == (False, True, True, False)
    assert (cfg.attn_head_dim, cfg.attn_value_dim, cfg.rope_dim) == (24, 16, 8)
    assert cfg.key_parts() == 2 and cfg.attn_value_scale == 0.707
    assert cfg.num_experts == 16 and cfg.experts_held == (4, 4)
    assert not cfg.is_moe_layer(0) and cfg.is_moe_layer(1)
    assert cfg.moe_selection_bias and cfg.num_shared_experts == 0
    full, window = (params[f"layer_{i}"]["attention"] for i in (0, 1))
    assert full["wk"].shape == (64, 2, 24) and full["wv"].shape == (64, 2, 16)
    assert window["wk"].shape == (64, 4, 24) and window["wo"].shape == (
        8, 16, 64)
    # the sink exists where the kind has one, and neither it nor the
    # selection bias is a no-op as initialised
    assert "sink" not in full and window["sink"].shape == (8,)
    assert float(jnp.abs(window["sink"]).max()) > 0.1
    bias = params["layer_1"]["moe"]["selection_bias"]
    assert bias.shape == (16,) and float(jnp.abs(bias).max()) > 0.05
    assert params["layer_1"]["moe"]["wi"].shape[0] == 4
    kw = mimo_reference.from_config_file(MIMO_TINY)
    assert (kw["held_offset"], kw["num_experts"]) == (4, 16)
    assert kw["kinds"] == (0, 1, 1, 0) and kw["dense_layers"] == 1
    assert kw["rotated"] == 8 and kw["routed_scale"] == 1.0


@pytest.mark.parametrize("bad, word", [
    ({"n_group": 8}, "n_group"), ({"topk_method": "greedy"}, "topk_method"),
    ({"n_shared_experts": 1}, "n_shared_experts"),
    ({"swa_head_dim": 32}, "swa_head_dim"),
    ({"attention_chunk_size": 64}, "attention_chunk_size"),
    ({"hybrid_layer_pattern": [0, 2, 1, 0, 1, 1]}, "hybrid_layer_pattern"),
    ({"moe_layer_freq": [0, 1, 0, 1, 1, 1]}, "moe_layer_freq"),
    ({"partial_rotary_factor": 0.3}, "partial_rotary_factor"),
    ({"rope_scaling": {"type": "yarn"}}, "does not read"),
    ({"reference": {"sink_init_std": 1.0}}, "selection_bias_init_std"),
], ids=["groups", "choice", "shared_expert", "window_head", "chunk_size",
        "kinds", "dense_between", "odd_rotation", "unknown_key", "inits"])
def test_mimo_adapter_refuses_what_it_cannot_express(bad, word):
    with pytest.raises(model_config.Unsupported, match=word):
        mimo_adapter.source_kwargs(dict(MIMO_TINY, **bad))


def test_mimo_a_sink_on_the_full_layers_is_expressed_too():
    """`add_full_attention_sink_bias` true is a file the program CAN run
    (a sink is data a layer), so the adapter maps it rather than refuse."""
    kw = mimo_adapter.source_kwargs(
        dict(MIMO_TINY, add_full_attention_sink_bias=True))
    assert kw["layer_sink"] == (True,) * 4


def test_mimo_uncached_logits_match_the_reference():
    """The program's uncached forward (float32) against the reference, past
    the window (80 positions against 16), with the sink, the value scale
    and the selection bias each shown to matter."""
    cfg, model, params = mimo_build(MIMO_TINY)
    ids = _mimo_ids()
    kw = mimo_reference.from_config_file(MIMO_TINY)
    got = jax.jit(lambda p: mimo_adapter.program_logits(model, p, ids))(
        params)
    view = mimo_adapter.params_view(cfg, params)
    want = jax.jit(lambda v: mimo_reference.forward(v, ids, **kw))(view)
    verdict = correct.compare_logits(got, want, rel_rms_tol=1e-4)
    assert verdict["ok"], verdict
    # blocked over the queries: the same rows
    blocked = mimo_reference.forward(view, ids, q_block=32, **kw)
    assert float(jnp.abs(blocked - want).max()) < 1e-4
    # the three controls: each reads another model by compare_logits
    no_sink = dict(view, layers=[
        dict(lw, mixer={k: v for k, v in lw["mixer"].items() if k != "sink"})
        for lw in view["layers"]])
    controls = {
        "sink_left_out": mimo_reference.forward(no_sink, ids, **kw),
        "value_scale_left_out": mimo_reference.forward(
            view, ids, **dict(kw, value_scale=1.0)),
        "window_one_key_wider": mimo_reference.forward(
            view, ids, **dict(kw, window=kw["window"] + 1)),
    }
    for name, other in controls.items():
        assert not correct.compare_logits(got, other, rel_rms_tol=1e-4)[
            "ok"], name
    # and the bias is in the choice: without it the logits differ
    for layer in params.values():
        if "moe" in layer:
            layer["moe"]["selection_bias"] = jnp.zeros((cfg.num_experts,))
    other = mimo_adapter.program_logits(model, params, ids)
    assert float(jnp.abs(other - got).max()) > 1e-3


def test_mimo_part_rotation_by_hand_at_the_published_sizes():
    """int(192 x 0.334) = 64 columns: pair i is (x[i], x[i + 32]) at
    theta^(-2i / 64); columns 64.. pass untouched; and the program's own
    rotation of the first rope_dim columns is the same."""
    from luminaai_tpu.models.layers import apply_rope, rope_frequencies

    assert int(192 * 0.334) == 64
    rs = np.random.RandomState(0)
    x = jnp.asarray(rs.randn(1, 6, 2, 192), jnp.float32)
    for theta in (5e6, 1e4):
        got = np.asarray(mimo_reference._rotate_first(x, 64, theta))
        np.testing.assert_array_equal(got[..., 64:], np.asarray(x)[..., 64:])
        f = theta ** (-np.arange(32) / 32.0)
        pos = 5
        a, b = np.asarray(x)[0, pos, 1, :32], np.asarray(x)[0, pos, 1, 32:64]
        np.testing.assert_allclose(
            got[0, pos, 1, :32], a * np.cos(pos * f) - b * np.sin(pos * f),
            atol=1e-5)
        np.testing.assert_allclose(
            got[0, pos, 1, 32:64], b * np.cos(pos * f) + a * np.sin(pos * f),
            atol=1e-5)
        cos, sin = rope_frequencies(64, 16, theta)
        mine = jnp.concatenate(
            [apply_rope(x[..., :64], cos, sin), x[..., 64:]], axis=-1)
        np.testing.assert_allclose(np.asarray(mine), got, atol=1e-5)


def test_mimo_the_sink_takes_probability_and_gives_no_value():
    """One head, two keys of equal score, a sink of the same logit: each
    key gets a third, so the output is two thirds of the values' mean."""
    H = 4
    mw = {"wq": jnp.zeros((H, 1, 2)), "wk": jnp.zeros((H, 1, 2)),
          "wv": jnp.eye(H)[:, None, :], "wo": jnp.eye(H)[None],
          "sink": jnp.zeros((1,))}
    h = jnp.asarray([[[1.0, 0, 0, 0], [0, 1.0, 0, 0]]])
    out = mimo_reference.attention(h, mw, rotated=2, theta=1e4, window=None,
                                   value_scale=1.0)
    np.testing.assert_allclose(np.asarray(out[0, 0]), [0.5, 0, 0, 0],
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(out[0, 1]),
                               [1 / 3, 1 / 3, 0, 0], atol=1e-6)


def test_mimo_shares_of_the_expert_layer_add_up():
    """The partial results of all 16 shares (2 of 32 experts each) sum to
    the uncut layer in the reference; each share's program layer agrees
    with its reference share (no shared expert anywhere)."""
    from luminaai_tpu.config import Config
    from luminaai_tpu.models.moe import MoELayer

    E, count, H, F, k = 32, 2, 64, 32, 8
    keys = jax.random.split(jax.random.key(5), 5)
    x = jax.random.normal(keys[0], (2, 40, H))
    full = {
        "router": jax.random.normal(keys[1], (H, E)),
        "selection_bias": 0.3 * jax.random.normal(keys[2], (E,)),
        "wi": 0.1 * jax.random.normal(keys[3], (E, H, 2 * F)),
        "wo": 0.1 * jax.random.normal(keys[4], (E, F, H)),
    }
    rule = dict(top_k=k, scale=1.0)
    with jax.default_matmul_precision("highest"):
        uncut = mimo_reference.expert_layer(x, full, held_offset=0, **rule)
        total = jnp.zeros_like(uncut)
        for off in range(0, E, count):
            part = dict(full, wi=full["wi"][off:off + count],
                        wo=full["wo"][off:off + count])
            want = mimo_reference.expert_layer(x, part, held_offset=off,
                                               **rule)
            total = total + want
            if off % 8:
                continue  # the program's layer at four of the shares
            cfg = Config(
                hidden_size=H, num_heads=4, intermediate_size=128,
                precision="fp32", use_moe=True, num_experts=E, moe_top_k=k,
                experts_held=(off, count), moe_dispatch="gmm",
                capacity_factor=float(E) / count, routing_noise_std=0.0,
                moe_score_func="sigmoid", moe_selection_bias=True,
                moe_routed_scale=1.0, moe_intermediate_size=F,
                num_shared_experts=0)
            got, stats = MoELayer(cfg, dtype=jnp.float32).apply(
                {"params": {
                    "router": full["router"],
                    "selection_bias": full["selection_bias"],
                    "wi": part["wi"], "wo": part["wo"]}}, x)
            assert float(jnp.abs(got - want).max()) < 1e-4, off
            assert float(stats["moe_held_pairs_dropped"]) == 0.0
    assert float(jnp.abs(total - uncut).max()) < 1e-4
    assert float(jnp.abs(uncut).max()) > 0.1


def test_mimo_cell_resolves_this_architecture():
    bench = manifest.load_benchmark()
    assert manifest.check(bench) == []
    cell = manifest.Cell(bench, MIMO_CELL)
    assert cell.architecture.name == "mimo_v2" and cell.chips == 1
    kw = model_config.config_kwargs(cell.architecture, cell.config)
    assert kw["experts_held"] == (0, 16) and kw["num_experts"] == 256
    assert kw["layer_windows"] == (None, 128, 128, 128, 128, None, 128)
    assert kw["layer_kv_heads"] == (4, 8, 8, 8, 8, 4, 8)
    assert kw["layer_sink"] == tuple(w is not None
                                     for w in kw["layer_windows"])
    assert kw["rope_dim"] == 64 and kw["dense_start_layers"] == 1
    assert kw["capacity_factor"] == 16.0 and kw["prefill_chunk_size"] == 256
    work = cell.architecture.work
    assert work.params_total(cell.config) == 3_429_955_392
    assert set(work.KERNEL_FNS) == manifest.kernel_names("mimo_v2")
    for fn in work.KERNEL_FNS.values():
        counts = fn(cell.config, {})
        assert counts["ops"] > 0 and counts["bytes"] > 0
    # the catalog row's numbers, every one at the top level, no width cut
    body = cell.config
    assert body["reduced"] == ["num_hidden_layers", "n_routed_experts",
                               "vocab_size"]
    assert body["source_values"] == {
        "num_hidden_layers": 48, "n_routed_experts": 256,
        "vocab_size": 152576}
    assert (body["hidden_size"], body["head_dim"], body["v_head_dim"],
            body["moe_intermediate_size"], body["intermediate_size"],
            body["num_experts_per_tok"]) == (4096, 192, 128, 2048, 16384, 8)
    dep = body["deployment"]
    assert dep["layer_shared_by_chips"] == 16 and dep["num_slots"] % 8 == 0
    assert (dep["page_size"], dep["max_slot_tokens"]) == (128, 10240)
    assert body["departures"] == [] and set(body["assumed"]) >= {
        "sink", "value_scale", "window", "rotation", "initialisers"}
    # the cell reports what the mixed-window cell reports, the lanes'
    # roofline and the two byte metrics
    reported = {m["name"] for m in bench["per_layer"]
                if MIMO_CELL in m.get("workloads", ())}
    other = {m["name"] for m in bench["per_layer"]
             if "command-a-plus-serve-mixed" in m.get("workloads", ())}
    assert reported == other | {
        "lane_attention_roofline", "kv_window_bytes_per_step",
        "kv_global_bytes_per_step"}
    mix = cell.traffic
    assert mix["prompt_tokens"] == {"dist": "lognormal", "median": 1024,
                                    "sigma": 0.8, "min": 128, "max": 8192}
    assert mix["output_tokens"] == {"dist": "lognormal", "median": 768,
                                    "sigma": 0.6, "min": 128, "max": 2048}
    assert mix["arrivals"]["rate_per_s"] == pytest.approx(
        0.7 * mix["arrivals"]["knee_per_s"], rel=0.02)


MIMO_REHEARSAL = """
import json
from types import SimpleNamespace
import jax
from benchmark import manifest, rehearse, serve_cell

serve_cell.SAMPLE_PROMPT_TOKENS = 40
cell = rehearse.tiny_cell(manifest.Cell(manifest.load_benchmark(), %r))
# rehearse.TINY shrinks the keys every architecture shares; this one's own
# widths follow them here (PERF.md section 7).
cell.config.update(
    head_dim=24, v_head_dim=16, swa_head_dim=24, swa_v_head_dim=16,
    swa_num_attention_heads=4, swa_num_key_value_heads=4,
    moe_intermediate_size=32, sliding_window=16, sliding_window_size=16,
    attention_chunk_size=16)
device = {"platform": "cpu", "kind": "rehearsal", "count": 1,
          "peak": {"bf16_flops_per_s": 1.0, "hbm_bytes_per_s": 1.0}}
args = SimpleNamespace(seed=2**31 + 12345, seconds=3.0, trace=0,
                       keep_trace=None)
res = serve_cell.run(cell, args, device)
print("REHEARSED " + json.dumps({
    "correct": res["correct"], "attempted": res["attempted"],
    "failed": res["failed"], "metrics": sorted(res["metrics"])}))
"""


def test_mimo_cells_tiny_rehearsal_reads_correct():
    """The cell's driver end to end on the CPU at toy widths (a child
    process: the harness's clocks and compile cache are a process's own):
    the reference phase, the scheduler over rings of 4 k/v heads and whole
    pages of 2, the open-loop window, `correct` true and no request
    failed."""
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=root,
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    proc = subprocess.run(
        [sys.executable, "-c", MIMO_REHEARSAL % MIMO_CELL], cwd=root,
        env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = next(ln for ln in proc.stdout.splitlines()
                if ln.startswith("REHEARSED "))
    result = json.loads(line.split(" ", 1)[1])
    assert result["correct"] is True, result
    assert result["attempted"] > 0 and result["failed"] == 0
    assert {"ttft_mean_ms", "itl_p95_ms", "setup_s"} <= set(
        result["metrics"])
