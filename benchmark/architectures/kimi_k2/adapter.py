"""The `kimi_k2` architecture on the program: the only file of this
architecture that imports luminaai_tpu. `source_kwargs` maps EVERY key of
the source to a `Config` field or refuses it by name (`Unsupported`):
nothing is ignored silently. `params_view` hands the reference a neutral
view of the SAME arrays, `program_logits` is the program's own uncached
forward pass (the expanded form of the latent attention; the served path
is the absorbed form over the paged latent entry, held to the reference by
tests/test_latent_serving.py and by the cell's served tokens).

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
    "rms_norm_eps": "rms_norm_eps",
    "rope_theta": "rope_theta",
    "tie_word_embeddings": "tie_word_embeddings",
    "num_experts_per_tok": "moe_top_k",
    "moe_intermediate_size": "moe_intermediate_size",
    "n_shared_experts": "num_shared_experts",
    "norm_topk_prob": "moe_renormalize",
    "routed_scaling_factor": "moe_routed_scale",
    "scoring_func": "moe_score_func",
    "first_k_dense_replace": "dense_start_layers",
    "kv_lora_rank": "kv_lora_rank",
    "q_lora_rank": "q_lora_rank",
    "qk_nope_head_dim": "qk_nope_head_dim",
    "qk_rope_head_dim": "qk_rope_head_dim",
    "v_head_dim": "v_head_dim",
}

# Keys that must read exactly this for the program to express the model.
ONLY = {
    "model_type": "kimi_k2",
    "hidden_act": "silu",
    "attention_bias": False,
    "moe_layer_freq": 1,             # every layer past the dense ones
    "n_group": 1,                    # one group: no group-limited choice
    "topk_group": 1,
    "topk_method": "noaux_tc",       # the top-k of score + bias
    "norm_topk_prob": True,
    "num_nextn_predict_layers": 0,
    "tie_word_embeddings": False,
    "ep_size": 1,                    # the source runtime's own switch
    "seq_aux": True,                 # training-loss switches: no shape
    "tf_legacy_loss": False,
}

YARN_TO_CONFIG = {
    "factor": "yarn_factor",
    "original_max_position_embeddings": "yarn_original_max",
    "beta_fast": "yarn_beta_fast",
    "beta_slow": "yarn_beta_slow",
    "mscale": "yarn_mscale",
    "mscale_all_dim": "yarn_mscale_all_dim",
}

# The harness's own groups of a configuration file, not the source's.
FILE_KEYS = {"source", "architecture", "reduced", "source_values", "assumed",
             "departures", "reference", "program", "deployment"}
# Read below, outside the tables. (`head_dim` is no key of the source:
# `rehearse.py`'s toy widths set it for every cell, and latent attention
# takes its head widths from qk_nope / qk_rope / v_head_dim.)
READ_HERE = {"n_routed_experts", "rope_scaling", "max_position_embeddings",
             "head_dim"}


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
    if body["scoring_func"] not in ("sigmoid", "softmax"):
        raise Unsupported(f"scoring_func={body['scoring_func']!r}")
    rs = body["rope_scaling"]
    if rs.get("type") != "yarn" or set(rs) - set(YARN_TO_CONFIG) - {"type"}:
        raise Unsupported(f"rope_scaling={rs!r}: YaRN with "
                          f"{sorted(YARN_TO_CONFIG)} only")
    pairs = body.get("reference", {}).get("rope_pairs")
    if pairs not in ("split", "interleaved"):
        raise Unsupported(f"reference.rope_pairs={pairs!r}")
    seq = body.get("program", {}).get("seq_length", 0)
    if seq > body["max_position_embeddings"]:
        raise Unsupported(f"seq_length {seq} past max_position_embeddings "
                          f"{body['max_position_embeddings']}")
    kw = {dst: body[src] for src, dst in SOURCE_TO_CONFIG.items()}
    kw.update({dst: rs[src] for src, dst in YARN_TO_CONFIG.items()})
    held = body["n_routed_experts"]
    published = held
    if "n_routed_experts" in body.get("reduced", ()):
        published = body["source_values"]["n_routed_experts"]
        offset = body.get("deployment", {}).get("experts_held_offset", 0)
        kw["experts_held"] = (offset, held)
    kw.update(
        num_experts=published,
        layer_mixers=("latent",) * body["num_hidden_layers"],
        latent_rope=True,
        rope_layout=pairs,
        use_moe=True,
        moe_pattern="sandwich",
        dense_end_layers=0,
        # e_score_correction_bias of the family's router (noaux_tc): in
        # the choice alone.
        moe_selection_bias=True,
    )
    return kw


def params_view(cfg, params: Dict[str, Any]) -> Dict[str, Any]:
    emb = params["embedder"]
    layers = []
    for i in range(cfg.num_layers):
        p = params[f"layer_{i}"]
        la = p["latent_attention"]
        mixer = {"wq_a": la["wq_a"], "q_norm": la["q_norm"]["scale"],
                 "wq_b": la["wq_b"], "wkv_a": la["wkv_a"],
                 "kv_norm": la["kv_norm"]["scale"], "wkv_b": la["wkv_b"],
                 "wo": la["wo"]}
        if "moe" in p:
            m = p["moe"]
            ffn = {"router": m["router"],
                   "selection_bias": m["selection_bias"],
                   "wi": m["wi"], "wo": m["wo"],
                   "shared_wi": m["shared_expert"]["wi"],
                   "shared_wo": m["shared_expert"]["wo"]}
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
