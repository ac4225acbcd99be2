"""The `mimo_v2` architecture on the program: the only file of this
architecture that imports luminaai_tpu. `source_kwargs` maps EVERY key of
the source to a `Config` field or refuses it by name (`Unsupported`):
nothing is ignored silently. `params_view` hands the reference a neutral
view of the SAME arrays, `program_logits` is the program's own uncached
forward pass (the served path, over a pool whose entries differ in shape
by layer, is held to the reference by tests/test_sink_window_serving.py
and by the cell's served tokens).

Where `n_routed_experts` stands in `reduced` it is the count this chip
HOLDS: the router keeps the published width
(`source_values.n_routed_experts`) and the held count with
`deployment.experts_held_offset` becomes `Config.experts_held`."""

from __future__ import annotations

from typing import Any, Dict

import jax

from benchmark.model_config import Unsupported

SOURCE_TO_CONFIG = {
    "vocab_size": "vocab_size",
    "hidden_size": "hidden_size",
    "intermediate_size": "intermediate_size",
    "num_hidden_layers": "num_layers",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "head_dim": "attn_head_dim",
    "v_head_dim": "attn_value_dim",
    "attention_value_scale": "attn_value_scale",
    "layernorm_epsilon": "rms_norm_eps",
    "rope_theta": "rope_theta",
    "tie_word_embeddings": "tie_word_embeddings",
    "num_experts_per_tok": "moe_top_k",
    "moe_intermediate_size": "moe_intermediate_size",
    "norm_topk_prob": "moe_renormalize",
    "scoring_func": "moe_score_func",
}

# Keys that must read exactly this for the program to express the model.
ONLY = {
    "model_type": "mimo_v2_flash",
    "hidden_act": "silu",
    "attention_bias": False,
    "n_group": 1,                    # one group: no group-limited choice
    "topk_group": 1,
    "topk_method": "noaux_tc",       # the top-k of score + bias
    "tie_word_embeddings": False,
    "n_shared_experts": None,        # no shared expert, and no mapping
}

# The window layers' keys that must repeat the full layers' (the program
# has one count of query heads and one pair of head widths a stack).
SAME_AS = {
    "swa_num_attention_heads": "num_attention_heads",
    "swa_head_dim": "head_dim",
    "swa_v_head_dim": "v_head_dim",
    "sliding_window_size": "sliding_window",
    "attention_chunk_size": "sliding_window",
}

# The harness's own groups of a configuration file, not the source's.
FILE_KEYS = {"source", "architecture", "reduced", "source_values", "assumed",
             "departures", "reference", "program", "deployment"}
# Read below, outside the tables.
READ_HERE = {"n_routed_experts", "hybrid_layer_pattern", "moe_layer_freq",
             "sliding_window", "swa_rope_theta", "swa_num_key_value_heads",
             "partial_rotary_factor", "add_swa_attention_sink_bias",
             "add_full_attention_sink_bias", "routed_scaling_factor",
             "max_position_embeddings"}


def layer_kinds(body: Dict[str, Any]):
    """The first `num_hidden_layers` entries of `hybrid_layer_pattern`
    (0 full, 1 window) and the count of leading layers without experts
    (`moe_layer_freq`: zeros, then ones)."""
    L = body["num_hidden_layers"]
    kinds = tuple(body["hybrid_layer_pattern"][:L])
    freq = tuple(body["moe_layer_freq"][:L])
    if len(kinds) != L or len(freq) != L:
        raise Unsupported(
            f"hybrid_layer_pattern / moe_layer_freq name fewer than {L} "
            "layers")
    if set(kinds) - {0, 1}:
        raise Unsupported(f"hybrid_layer_pattern={kinds!r}: 0 or 1 a layer")
    dense = 0
    while dense < L and freq[dense] == 0:
        dense += 1
    if set(freq[dense:]) - {1}:
        raise Unsupported(
            f"moe_layer_freq={freq!r}: dense layers first, then an expert "
            "layer every layer")
    return kinds, dense


def source_kwargs(body: Dict[str, Any]) -> Dict[str, Any]:
    known = (set(SOURCE_TO_CONFIG) | set(ONLY) | set(SAME_AS) | FILE_KEYS
             | READ_HERE)
    unknown = sorted(k for k in body if k not in known)
    if unknown:
        raise Unsupported(f"keys this adapter does not read: {unknown}")
    for key, want in ONLY.items():
        if key not in body:
            raise Unsupported(f"{key} is not stated")
        if body[key] != want:
            raise Unsupported(f"{key}={body[key]!r}: only {want!r} runs")
    for key, other in SAME_AS.items():
        if body.get(key) != body[other]:
            raise Unsupported(
                f"{key}={body.get(key)!r} against {other}={body[other]!r}: "
                "the program has one of these a stack")
    if body["scoring_func"] not in ("sigmoid", "softmax"):
        raise Unsupported(f"scoring_func={body['scoring_func']!r}")
    seq = body.get("program", {}).get("seq_length", 0)
    if seq > body["max_position_embeddings"]:
        raise Unsupported(f"seq_length {seq} past max_position_embeddings "
                          f"{body['max_position_embeddings']}")
    ref = body.get("reference", {})
    for key in ("sink_init_std", "selection_bias_init_std"):
        if not isinstance(ref.get(key), (int, float)):
            raise Unsupported(f"reference.{key} is not stated")
    kinds, dense = layer_kinds(body)
    rotated = int(body["head_dim"] * body["partial_rotary_factor"])
    if rotated <= 0 or rotated % 2:
        raise Unsupported(
            f"partial_rotary_factor={body['partial_rotary_factor']!r} of "
            f"head_dim {body['head_dim']}: {rotated} rotated columns")
    kw = {dst: body[src] for src, dst in SOURCE_TO_CONFIG.items()}
    held = body["n_routed_experts"]
    published = held
    if "n_routed_experts" in body.get("reduced", ()):
        published = body["source_values"]["n_routed_experts"]
        offset = body.get("deployment", {}).get("experts_held_offset", 0)
        kw["experts_held"] = (offset, held)
    scale = body["routed_scaling_factor"]
    kw.update(
        num_experts=published,
        layer_windows=tuple(
            body["sliding_window"] if t else None for t in kinds),
        layer_kv_heads=tuple(
            body["swa_num_key_value_heads"] if t
            else body["num_key_value_heads"] for t in kinds),
        layer_rope_theta=tuple(
            body["swa_rope_theta"] if t else body["rope_theta"]
            for t in kinds),
        layer_sink=tuple(
            bool(body["add_swa_attention_sink_bias"] if t
                 else body["add_full_attention_sink_bias"]) for t in kinds),
        attn_sink_init_std=float(ref["sink_init_std"]),
        rope_dim=rotated,
        rope_layout="split",
        use_moe=True,
        moe_pattern="sandwich",
        dense_start_layers=dense,
        dense_end_layers=0,
        num_shared_experts=0,
        moe_routed_scale=1.0 if scale is None else float(scale),
        # e_score_correction_bias of the family's router (noaux_tc): in
        # the choice alone.
        moe_selection_bias=True,
        moe_selection_bias_init_std=float(ref["selection_bias_init_std"]),
    )
    return kw


def params_view(cfg, params: Dict[str, Any]) -> Dict[str, Any]:
    emb = params["embedder"]
    layers = []
    for i in range(cfg.num_layers):
        p = params[f"layer_{i}"]
        a = p["attention"]
        mixer = {name: a[name] for name in ("wq", "wk", "wv", "wo")}
        if "sink" in a:
            mixer["sink"] = a["sink"]
        if "moe" in p:
            m = p["moe"]
            ffn = {"router": m["router"],
                   "selection_bias": m["selection_bias"],
                   "wi": m["wi"], "wo": m["wo"]}
        else:
            ffn = {"wi": p["ffn"]["wi"], "wo": p["ffn"]["wo"]}
        layers.append({"attn_norm": p["attn_norm"]["scale"],
                       "ffn_norm": p["ffn_norm"]["scale"],
                       "mixer": mixer, "ffn": ffn})
    return {"embedding": emb["embedding"], "lm_head": emb["lm_head"],
            "final_norm": params["final_norm"]["scale"], "layers": layers}


def program_logits(model, params, ids) -> jax.Array:
    """The program's forward pass as training runs it: no cache,
    deterministic, its own kernels and compute dtype."""
    logits, _aux = model.apply({"params": params}, ids, deterministic=True)
    return logits
