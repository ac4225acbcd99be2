"""The `cohere2_moe` architecture on the program: the only file of this
architecture that imports luminaai_tpu. `source_kwargs` maps EVERY key of
the source to a `Config` field or refuses it by name (`Unsupported`):
nothing is ignored silently. `params_view` hands the reference a neutral
view of the SAME arrays, `program_logits` is the program's own uncached
forward pass.

Where `num_experts` stands in `reduced` it is the count this chip HOLDS:
the router keeps the published width (`source_values.num_experts`) and the
held count with `deployment.experts_held_offset` becomes
`Config.experts_held`."""

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
    "layer_norm_eps": "layer_norm_eps",
    "rope_theta": "rope_theta",
    "tie_word_embeddings": "tie_word_embeddings",
    "num_experts_per_tok": "moe_top_k",
    "num_shared_experts": "num_shared_experts",
    "norm_topk_prob": "moe_renormalize",
    "expert_selection_fn": "moe_score_func",
    "shared_expert_combination_strategy": "shared_expert_combine",
    "use_parallel_block": "parallel_block",
}

# Keys that must read exactly this for the program to express the model.
ONLY = {
    "model_type": "cohere2_moe",
    "hidden_act": "silu",
    "use_gated_activation": True,          # SwiGLU
    "attention_bias": False,
    "use_qk_norm": False,
    "rotary_pct": 1,                       # all of a head's dims rotate
    "position_embedding_type": "rope_gptj",   # interleaved pairs
    "logit_scale": 1,
    "rms_norm_eps": None,                  # LayerNorm, not RMSNorm
    "first_k_dense_replace": 0,            # no leading dense layer
    "tie_word_embeddings": True,
    "use_embedding_sharing": True,         # the same tie, the family's key
    "use_parallel_embedding": False,
    "tf_legacy_loss": False,               # a training-loss switch
    "order_of_interleaved_layers": "local_attn_first",
}

# The harness's own groups of a configuration file, not the source's.
FILE_KEYS = {"source", "architecture", "reduced", "source_values", "assumed",
             "departures", "reference", "program", "deployment"}
# Read below, outside the two tables.
READ_HERE = {"num_experts", "layer_types", "layer_switch", "sliding_window",
             "max_position_embeddings", "rope_parameters",
             "prefix_dense_intermediate_size",
             "prefix_dense_sliding_window_pattern"}


def layer_kinds(body: Dict[str, Any]):
    """(window, rotates) a layer, from `layer_types`, held to the keys that
    say the same thing another way (`layer_switch`: every layer_switch-th
    layer is full; `order_of_interleaved_layers`: the period starts with
    its window layers)."""
    kinds = body["layer_types"][:body["num_hidden_layers"]]
    period = body["layer_switch"]
    for i, kind in enumerate(body["layer_types"]):
        want = "full_attention" if i % period == period - 1 else (
            "sliding_attention")
        if kind != want:
            raise Unsupported(
                f"layer_types[{i}]={kind!r} against layer_switch={period}")
    windows = tuple(
        body["sliding_window"] if kind == "sliding_attention" else None
        for kind in kinds)
    # The family's full layers carry no position (`assumed`): NoPE.
    rotates = tuple(kind == "sliding_attention" for kind in kinds)
    return windows, rotates


def source_kwargs(body: Dict[str, Any]) -> Dict[str, Any]:
    known = set(SOURCE_TO_CONFIG) | set(ONLY) | FILE_KEYS | READ_HERE
    unknown = sorted(k for k in body if k not in known)
    if unknown:
        raise Unsupported(f"keys this adapter does not read: {unknown}")
    for key, want in ONLY.items():
        if key not in body:
            raise Unsupported(f"{key} is not stated")
        if body[key] != want:
            raise Unsupported(f"{key}={body[key]!r}: only {want!r} runs")
    if body["expert_selection_fn"] not in ("sigmoid", "softmax"):
        raise Unsupported(f"expert_selection_fn={body['expert_selection_fn']!r}")
    if body["shared_expert_combination_strategy"] not in ("average", "sum"):
        raise Unsupported("shared_expert_combination_strategy="
                          f"{body['shared_expert_combination_strategy']!r}")
    rp = body["rope_parameters"]
    if rp.get("rope_type") != "default" or (
            rp.get("rope_theta") != body["rope_theta"]) or set(rp) - {
                "rope_type", "rope_theta"}:
        raise Unsupported(f"rope_parameters={rp!r}: plain rotation at "
                          "rope_theta only")
    # prefix_dense_* size leading dense layers; first_k_dense_replace 0
    # (held above) means they name no layer.
    for key in ("prefix_dense_intermediate_size",
                "prefix_dense_sliding_window_pattern"):
        if key not in body:
            raise Unsupported(f"{key} is not stated")
    seq = body.get("program", {}).get("seq_length", 0)
    if seq > body["max_position_embeddings"]:
        raise Unsupported(f"seq_length {seq} past max_position_embeddings "
                          f"{body['max_position_embeddings']}")
    kw = {dst: body[src] for src, dst in SOURCE_TO_CONFIG.items()}
    held = body["num_experts"]
    published = held
    if "num_experts" in body.get("reduced", ()):
        published = body["source_values"]["num_experts"]
        offset = body.get("deployment", {}).get("experts_held_offset", 0)
        kw["experts_held"] = (offset, held)
    windows, rotates = layer_kinds(body)
    kw.update(
        num_experts=published,
        # The catalog's note: no key of its own for an expert's width.
        moe_intermediate_size=body["intermediate_size"],
        layer_windows=windows,
        layer_rope=rotates,
        rope_layout="interleaved",
        norm_kind="layernorm",
        use_moe=True,
        moe_pattern="all",
        moe_selection_bias=False,
        moe_routed_scale=1.0,
    )
    return kw


def params_view(cfg, params: Dict[str, Any]) -> Dict[str, Any]:
    layers = []
    for i in range(cfg.num_layers):
        p = params[f"layer_{i}"]
        a, m = p["attention"], p["moe"]
        layers.append({
            "norm": p["attn_norm"]["scale"],
            "wq": a["wq"], "wk": a["wk"], "wv": a["wv"], "wo": a["wo"],
            "router": m["router"], "wi": m["wi"], "wo_e": m["wo"],
            "shared_wi": m["shared_expert"]["wi"],
            "shared_wo": m["shared_expert"]["wo"],
        })
    return {"embedding": params["embedder"]["embedding"],
            "final_norm": params["final_norm"]["scale"], "layers": layers}


def program_logits(model, params, ids) -> jax.Array:
    """The program's forward pass as training runs it: no cache,
    deterministic, its own kernels and compute dtype."""
    logits, _aux = model.apply({"params": params}, ids, deterministic=True)
    return logits
