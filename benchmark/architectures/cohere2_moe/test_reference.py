"""The yardstick for `correct` is itself checked: this architecture's
reference against LuminaTransformer at a tiny size on the CPU (window 8;
layers window, window, window, full; 6 heads of 16 over hidden 32; 8
experts top-2 with 4 held; 2 shared experts averaged): the uncached
logits, whole and in blocks of queries; each equation's switch against a
reference that leaves it out; the adapter's refusals, key by key; the
catalog's row against the configuration file; the work counts. The CACHED
path (rings of pages beside whole pages, the tick's chunk kernel) is held
to the same reference in tests/test_window_ring_serving.py. The modules
are reached as a cell reaches them, by the architecture's name."""

import copy
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import correct, manifest, model_config

COHERE = manifest.Architecture("cohere2_moe")
cohere_reference, cohere_adapter = COHERE.reference, COHERE.adapter

COHERE_TINY = {
    "attention_bias": False, "expert_selection_fn": "sigmoid",
    "first_k_dense_replace": 0, "head_dim": 16, "hidden_act": "silu",
    "hidden_size": 32, "intermediate_size": 48, "layer_norm_eps": 1e-5,
    "layer_switch": 4,
    "layer_types": ["sliding_attention"] * 3 + ["full_attention"],
    "logit_scale": 1, "max_position_embeddings": 4096,
    "model_type": "cohere2_moe", "norm_topk_prob": True,
    "num_attention_heads": 6, "num_experts": 4, "num_experts_per_tok": 2,
    "num_hidden_layers": 4, "num_key_value_heads": 2,
    "num_shared_experts": 2,
    "order_of_interleaved_layers": "local_attn_first",
    "position_embedding_type": "rope_gptj",
    "prefix_dense_intermediate_size": 96,
    "prefix_dense_sliding_window_pattern": 1, "rms_norm_eps": None,
    "rope_parameters": {"rope_theta": 50000, "rope_type": "default"},
    "rope_theta": 50000, "rotary_pct": 1,
    "shared_expert_combination_strategy": "average", "sliding_window": 8,
    "tf_legacy_loss": False, "tie_word_embeddings": True,
    "use_embedding_sharing": True, "use_gated_activation": True,
    "use_parallel_block": True, "use_parallel_embedding": False,
    "use_qk_norm": False, "vocab_size": 64,
    "reduced": ["num_experts"], "source_values": {"num_experts": 8},
    "program": {"precision": "fp32", "use_flash_attention": False,
                "use_stable_embedding": False, "scan_layers": False,
                "moe_dispatch": "gmm", "capacity_factor": 2.0,
                "routing_noise_std": 0.0, "init_std": 0.3,
                "seq_length": 64},
    "deployment": {"experts_held_offset": 4, "stands_for": "a test",
                   "layer_shared_by_chips": 2},
}


def _cohere_build(body):
    from luminaai_tpu.models.transformer import LuminaTransformer
    from luminaai_tpu.parallel.sharding import unbox

    cfg = model_config.build_config(COHERE, body)
    cfg.validate()
    model = LuminaTransformer(cfg)
    params = unbox(jax.jit(model.init)(
        jax.random.key(3), jnp.zeros((1, 8), jnp.int32))["params"])

    def stir(path, x):
        # Initialised at 1, a norm's scale would hide one applied twice.
        if "_norm" in jax.tree_util.keystr(path):
            return x + 0.3 * jax.random.normal(
                jax.random.key(x.size), x.shape)
        return x

    return cfg, model, jax.tree_util.tree_map_with_path(stir, params)


def _cohere_ids(rows=2, length=40):
    return jnp.asarray(np.random.RandomState(0).randint(
        3, 64, size=(rows, length)), jnp.int32)


def test_cohere2_adapter_maps_every_key():
    cfg, _, params = _cohere_build(COHERE_TINY)
    assert cfg.layer_windows == (8, 8, 8, None)
    assert cfg.layer_rope == (True, True, True, False)
    assert (cfg.head_dim(), cfg.num_heads, cfg.num_kv_heads) == (16, 6, 2)
    assert (cfg.rope_layout, cfg.norm_kind, cfg.parallel_block) == (
        "interleaved", "layernorm", True)
    assert (cfg.num_experts, cfg.experts_held, cfg.moe_top_k) == (8, (4, 4), 2)
    assert (cfg.moe_score_func, cfg.moe_renormalize,
            cfg.shared_expert_combine) == ("sigmoid", True, "average")
    assert cfg.expert_width() == 48 and cfg.layer_norm_eps == 1e-5
    layer = params["layer_0"]
    assert layer["moe"]["wi"].shape == (4, 32, 96)       # the held experts
    assert layer["moe"]["router"].shape == (32, 8)       # the published width
    assert layer["moe"]["shared_expert"]["wi"].shape == (32, 2 * 2 * 48)
    assert layer["attention"]["wq"].shape == (32, 6, 16)
    assert "ffn_norm" not in layer and "lm_head" not in params["embedder"]
    kw = cohere_reference.from_config_file(COHERE_TINY)
    assert kw["layer_types"] == tuple(COHERE_TINY["layer_types"])
    assert (kw["held_offset"], kw["num_experts"], kw["n_shared"]) == (4, 8, 2)


@pytest.mark.parametrize("key,value", [
    ("model_type", "cohere2"), ("hidden_act", "gelu"),
    ("use_gated_activation", False), ("attention_bias", True),
    ("use_qk_norm", True), ("rotary_pct", 0.5),
    ("position_embedding_type", "rope_neox"), ("logit_scale", 0.25),
    ("rms_norm_eps", 1e-6), ("first_k_dense_replace", 1),
    ("tie_word_embeddings", False), ("use_embedding_sharing", False),
    ("use_parallel_embedding", True), ("tf_legacy_loss", True),
    ("order_of_interleaved_layers", "global_attn_first"),
    ("expert_selection_fn", "relu"),
    ("shared_expert_combination_strategy", "concat"),
    ("layer_switch", 2),
    ("rope_parameters", {"rope_type": "yarn", "rope_theta": 50000}),
    ("some_new_key", 1),
])
def test_cohere2_adapter_refuses_by_name(key, value):
    """What the program cannot express is refused with the key's name,
    and a key this adapter has never heard of is refused too: nothing of
    the source is ignored silently."""
    body = copy.deepcopy(COHERE_TINY)
    body[key] = value
    with pytest.raises(model_config.Unsupported, match=key):
        cohere_adapter.source_kwargs(body)
    missing = {k: v for k, v in COHERE_TINY.items() if k != "use_qk_norm"}
    with pytest.raises(model_config.Unsupported, match="use_qk_norm"):
        cohere_adapter.source_kwargs(missing)


def test_cohere2_logits_match_whole_and_in_blocks():
    """The program's uncached forward (float32) against the reference, to
    1e-4 of the logits' spread; the reference in blocks of 7 queries is
    the reference."""
    cfg, model, params = _cohere_build(COHERE_TINY)
    ids = _cohere_ids()
    kw = cohere_reference.from_config_file(COHERE_TINY)
    view = cohere_adapter.params_view(cfg, params)
    got = cohere_adapter.program_logits(model, params, ids)
    want = cohere_reference.forward(view, ids, **kw)
    verdict = correct.compare_logits(got, want, rel_rms_tol=1e-4)
    assert verdict["ok"], verdict
    blocks = cohere_reference.forward(view, ids, q_block=7, **kw)
    assert float(jnp.abs(blocks - want).max()) < 1e-4
    # params_view is no copy
    assert view["layers"][2]["wi"] is params["layer_2"]["moe"]["wi"]


@pytest.mark.parametrize("what,over", [
    ("the window", {"window": 9}),
    ("which layers are full", {"layer_types": ("sliding_attention",) * 4}),
    ("which layers rotate", {"layer_types": ("full_attention",) * 4}),
    ("rope_theta", {"theta": 10000.0}),
    ("the held range", {"held_offset": 0}),
    ("experts per token", {"top_k": 3}),
    ("the norm's eps", {"eps": 1e-2}),
    ("logit_scale", {"logit_scale": 0.5}),
])
def test_cohere2_reference_notices_each_equation(what, over):
    """The controls: a reference that states one thing otherwise is
    another model, by far more than the tolerance."""
    cfg, model, params = _cohere_build(COHERE_TINY)
    ids = _cohere_ids()
    kw = dict(cohere_reference.from_config_file(COHERE_TINY), **over)
    got = cohere_adapter.program_logits(model, params, ids)
    want = cohere_reference.forward(
        cohere_adapter.params_view(cfg, params), ids, **kw)
    verdict = correct.compare_logits(got, want, rel_rms_tol=1e-4)
    assert not verdict["ok"] and verdict["rel_rms"] > 1e-3, (what, verdict)


def test_cohere2_shared_experts_are_averaged_not_summed():
    cfg, model, params = _cohere_build(COHERE_TINY)
    ids = _cohere_ids()
    kw = cohere_reference.from_config_file(COHERE_TINY)
    body = copy.deepcopy(COHERE_TINY)
    body["shared_expert_combination_strategy"] = "sum"
    from luminaai_tpu.models.transformer import LuminaTransformer

    summed = LuminaTransformer(model_config.build_config(COHERE, body))
    want = cohere_reference.forward(
        cohere_adapter.params_view(cfg, params), ids, **kw)
    got = cohere_adapter.program_logits(summed, params, ids)
    assert correct.compare_logits(got, want, rel_rms_tol=1e-4)["rel_rms"] > 1e-2


def test_cohere2_cell_resolves_this_architecture():
    bench = manifest.load_benchmark()
    cell = manifest.Cell(bench, "command-a-plus-serve-mixed")
    assert cell.architecture.name == "cohere2_moe" and cell.chips == 1
    kw = model_config.config_kwargs(cell.architecture, cell.config)
    assert kw["layer_windows"] == (4096, 4096, 4096, None)
    assert kw["layer_rope"] == (True, True, True, False)
    assert (kw["num_experts"], kw["experts_held"], kw["moe_top_k"]) == (
        128, (0, 16), 8)
    assert (kw["hidden_size"], kw["num_heads"], kw["num_kv_heads"],
            kw["attn_head_dim"], kw["moe_intermediate_size"]) == (
                4096, 128, 8, 128, 4096)
    # dropless: the grouped matmul's row bound is every pair of a tick
    assert kw["capacity_factor"] == 128 / 16 and kw["moe_dispatch"] == "gmm"
    dep, prog = cell.config["deployment"], cell.config["program"]
    assert dep["prefix_cache_pages"] == 0 and dep["layer_shared_by_chips"] == 8
    # a lane: whole pages for the full layer, three rings of 35 pages
    from luminaai_tpu.config import Config

    cfg = Config(**kw)
    ring = cfg.ring_pages(0, dep["page_size"], prog["prefill_chunk_size"])
    assert ring == 35 and cfg.ring_pages(3, 128, 256) is None
    row = 2 * 8 * 128 * 2  # k and v, 8 heads of 128, bf16
    lane = (dep["max_slot_tokens"] + 3 * ring * dep["page_size"]) * row
    assert abs(lane - 122.2e6) < 0.1e6
    work = cell.architecture.work
    assert set(work.KERNEL_FNS) == manifest.kernel_names("cohere2_moe")
    assert work.params_total(cell.config) == 4_733_292_544
    assert abs(work.matmul_params_active(cell.config) - 1.70e9) < 0.02e9
    gmm = work.grouped_matmul(cell.config, {})
    # memory-bound by its counts: 16 experts' weights a call
    assert gmm["ops"] / 197e12 < gmm["bytes"] / 819e9
    # the weights of the experts some live row picked: between the six a
    # lanes-only tick touches and all sixteen
    expert = 4096 * 1.5 * 4096 * 2
    assert 6 * expert < gmm["bytes"] < 16.1 * expert
    attn = work.chunk_attention(cell.config, {})
    assert attn["ops"] / 197e12 > attn["bytes"] / 819e9  # compute-bound
    means = dep["tick_means"]
    assert 0 < means["chunk_rows"] <= prog["prefill_chunk_size"]
    assert means["chunk_rows"] <= means["live_rows"] <= (
        dep["num_slots"] + prog["prefill_chunk_size"])
    assert means["chunk_rows"] / prog["prefill_chunk_size"] <= (
        means["chunk_ride_share"]) <= 1.0
    assert means["chunk_keys_window"] <= min(
        4096, means["chunk_keys_full"])
    assert means["chunk_span_window"] <= means["chunk_span_full"]


def test_cohere2_file_holds_every_key_of_the_catalog_row():
    """The configuration file against the published config the PR was
    given: every key of the catalog row at the top level, equal but for
    the three in `reduced`, each of which states the published number."""
    with open(manifest.config_file("command-a-plus-ep8-serve")) as f:
        body = json.load(f)
    published = {
        "attention_bias": False, "expert_selection_fn": "sigmoid",
        "first_k_dense_replace": 0, "head_dim": 128, "hidden_act": "silu",
        "hidden_size": 4096, "intermediate_size": 4096,
        "layer_norm_eps": 1e-05, "layer_switch": 4, "logit_scale": 1,
        "max_position_embeddings": 200000, "model_type": "cohere2_moe",
        "norm_topk_prob": True, "num_attention_heads": 128,
        "num_experts": 128, "num_experts_per_tok": 8,
        "num_hidden_layers": 32, "num_key_value_heads": 8,
        "num_shared_experts": 4,
        "order_of_interleaved_layers": "local_attn_first",
        "position_embedding_type": "rope_gptj",
        "prefix_dense_intermediate_size": 16384,
        "prefix_dense_sliding_window_pattern": 1, "rms_norm_eps": None,
        "rope_parameters": {"rope_theta": 50000, "rope_type": "default"},
        "rope_theta": 50000, "rotary_pct": 1,
        "shared_expert_combination_strategy": "average",
        "sliding_window": 4096, "tf_legacy_loss": False,
        "tie_word_embeddings": True, "use_embedding_sharing": True,
        "use_gated_activation": True, "use_parallel_block": True,
        "use_parallel_embedding": False, "use_qk_norm": False,
        "vocab_size": 262144,
    }
    reduced = {"num_hidden_layers": 4, "num_experts": 16, "vocab_size": 32768}
    assert sorted(body["reduced"]) == sorted(reduced)
    for key, value in published.items():
        if key in reduced:
            assert body[key] == reduced[key], key
            assert body["source_values"][key] == value, key
        else:
            assert body[key] == value, key
    assert body["layer_types"] == (["sliding_attention"] * 3
                                   + ["full_attention"]) * 8
    assert body["departures"] == [] and len(body["assumed"]) >= 8
    # the floors: a whole period and four layers, 8 experts, 1/8 vocabulary
    assert body["num_hidden_layers"] % body["layer_switch"] == 0
    assert body["num_experts"] >= 8
    assert body["vocab_size"] * 8 >= body["source_values"]["vocab_size"]
    assert "vision tower" in body["deployment"]["_note"]
