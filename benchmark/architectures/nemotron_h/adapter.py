"""The `nemotron_h` architecture on the program: the only file of this
architecture that imports luminaai_tpu. `source_kwargs` maps EVERY key of
the source to a `Config` field, checks it against the one value the
program runs, or lists it as a switch of the source's own runtime; any
other key is refused by name (`Unsupported`). `params_view` hands the
reference a neutral view of the SAME arrays, `program_logits` is the
program's own uncached forward pass (the block form from zero state; the
served forms are held to the reference by tests/test_ssm2_serving.py and
by the cell's served tokens).

Where `n_routed_experts` stands in `reduced` it is the count this chip
HOLDS: the router keeps the published width
(`source_values.n_routed_experts`) and the held count with
`deployment.experts_held_offset` becomes `Config.experts_held`.
`hybrid_override_pattern` keeps its published 88 characters; the first
`num_hidden_layers` of them are built."""

from __future__ import annotations

from typing import Any, Dict

import jax

from benchmark.model_config import Unsupported

SOURCE_TO_CONFIG = {
    "vocab_size": "vocab_size",
    "hidden_size": "hidden_size",
    "num_hidden_layers": "num_layers",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "head_dim": "attn_head_dim",
    "norm_eps": "rms_norm_eps",
    "tie_word_embeddings": "tie_word_embeddings",
    "mamba_num_heads": "ssm2_num_heads",
    "mamba_head_dim": "ssm2_head_dim",
    "n_groups": "ssm2_groups",
    "ssm_state_size": "ssm_state_size",
    "conv_kernel": "ssm_conv_size",
    "chunk_size": "ssm2_chunk",
    "time_step_min": "ssm2_dt_min",
    "time_step_max": "ssm2_dt_max",
    "time_step_floor": "ssm2_dt_floor",
    "num_experts_per_tok": "moe_top_k",
    "moe_intermediate_size": "moe_intermediate_size",
    "moe_latent_size": "moe_latent_size",
    "moe_shared_expert_intermediate_size": "moe_shared_size",
    "norm_topk_prob": "moe_renormalize",
    "routed_scaling_factor": "moe_routed_scale",
}

# Keys that must read exactly this for the program to express the model.
ONLY = {
    "model_type": "nemotron_h",
    "attention_bias": False,
    "mlp_bias": False,
    "use_bias": False,
    "mamba_proj_bias": False,
    "use_conv_bias": True,
    "mamba_hidden_act": "silu",
    "mlp_hidden_act": "relu2",
    "n_group": 1,                    # one group: no group-limited choice
    "topk_group": 1,
    "n_shared_experts": 1,           # one shared MLP of its own width
    "tie_word_embeddings": False,
    "sliding_window": None,
    "residual_in_fp32": False,
}

# The source's runtime or initialiser switches and what only bounds
# something: no shape, ignored (the file's `assumed` lists them).
IGNORED = {"rescale_prenorm_residual", "use_mamba_kernels",
           "num_logits_to_keep", "moe_shared_expert_overlap", "rope_theta",
           "partial_rotary_factor",
           "num_nextn_predict_layers", "mtp_hybrid_override_pattern"}
# The harness's own groups of a configuration file, not the source's.
FILE_KEYS = {"source", "architecture", "reduced", "source_values", "assumed",
             "departures", "reference", "program", "deployment"}
# Read below, outside the tables.
READ_HERE = {"n_routed_experts", "hybrid_override_pattern", "expand",
             "intermediate_size", "max_position_embeddings",
             "layer_norm_epsilon"}

MIXER_OF = {"M": "ssm2", "*": "attention", "E": "none"}
FFN_OF = {"M": "none", "*": "none", "E": "moe"}


def pattern_of(body: Dict[str, Any]) -> str:
    """The first `num_hidden_layers` characters of the published pattern:
    M a state-space layer, * attention, E experts; each is ONE sub-layer."""
    L = int(body["num_hidden_layers"])
    pattern = str(body["hybrid_override_pattern"])
    if len(pattern) < L:
        raise Unsupported(
            f"hybrid_override_pattern names {len(pattern)} layers, "
            f"num_hidden_layers is {L}")
    bad = sorted(set(pattern) - set(MIXER_OF))
    if bad:
        raise Unsupported(
            f"hybrid_override_pattern has {bad}: only M, E and * are built "
            "(a dense feed-forward layer '-' has no expert router)")
    return pattern[:L]


def source_kwargs(body: Dict[str, Any]) -> Dict[str, Any]:
    known = (set(SOURCE_TO_CONFIG) | set(ONLY) | IGNORED | FILE_KEYS
             | READ_HERE)
    unknown = sorted(k for k in body if k not in known)
    if unknown:
        raise Unsupported(f"keys this adapter does not read: {unknown}")
    for key, want in ONLY.items():
        if key not in body:
            raise Unsupported(f"{key} is not stated")
        if body[key] != want:
            raise Unsupported(f"{key}={body[key]!r}: only {want!r} runs")
    inner = body["mamba_num_heads"] * body["mamba_head_dim"]
    if inner != body["expand"] * body["hidden_size"]:
        raise Unsupported(
            f"mamba_num_heads x mamba_head_dim = {inner} against expand x "
            f"hidden_size = {body['expand'] * body['hidden_size']}")
    if body["mamba_num_heads"] % body["n_groups"]:
        raise Unsupported(
            f"mamba_num_heads {body['mamba_num_heads']} is no multiple of "
            f"n_groups {body['n_groups']}")
    if body["intermediate_size"] != body["moe_intermediate_size"]:
        raise Unsupported(
            f"intermediate_size={body['intermediate_size']} against "
            f"moe_intermediate_size={body['moe_intermediate_size']}: no "
            "layer of the pattern is a dense feed-forward, and the key "
            "repeats the experts' width")
    if body["layer_norm_epsilon"] != body["norm_eps"]:
        raise Unsupported(
            f"layer_norm_epsilon={body['layer_norm_epsilon']!r} against "
            f"norm_eps={body['norm_eps']!r}: the program has one epsilon "
            "for the blocks' norms and the mixer's gated norm")
    seq = body.get("program", {}).get("seq_length", 0)
    if seq > body["max_position_embeddings"]:
        raise Unsupported(f"seq_length {seq} past max_position_embeddings "
                          f"{body['max_position_embeddings']}")
    ref = body.get("reference", {})
    if not isinstance(ref.get("selection_bias_init_std"), (int, float)):
        raise Unsupported("reference.selection_bias_init_std is not stated")
    pattern = pattern_of(body)
    kw = {dst: body[src] for src, dst in SOURCE_TO_CONFIG.items()}
    kw["moe_routed_scale"] = float(kw["moe_routed_scale"])
    held = body["n_routed_experts"]
    published = held
    if "n_routed_experts" in body.get("reduced", ()):
        published = body["source_values"]["n_routed_experts"]
        offset = body.get("deployment", {}).get("experts_held_offset", 0)
        kw["experts_held"] = (offset, held)
    else:
        # The latent expert layer runs as a share alone; the whole layer
        # is the share that holds every expert.
        kw["experts_held"] = (0, held)
    kw.update(
        num_experts=published,
        layer_mixers=tuple(MIXER_OF[c] for c in pattern),
        layer_ffns=tuple(FFN_OF[c] for c in pattern),
        # The family's attention layers rotate nothing (assumed.positions).
        use_rope=False,
        use_moe=True,
        moe_pattern="all",
        moe_score_func="sigmoid",
        moe_expert_act="relu2",
        # e_score_correction_bias: in the choice alone.
        moe_selection_bias=True,
        moe_selection_bias_init_std=float(ref["selection_bias_init_std"]),
        num_shared_experts=0,
        # No dense feed-forward is built; Config wants a number.
        intermediate_size=body["intermediate_size"],
    )
    return kw


def params_view(cfg, params: Dict[str, Any]) -> Dict[str, Any]:
    emb = params["embedder"]
    layers = []
    for i in range(cfg.num_layers):
        p = params[f"layer_{i}"]
        kind = cfg.mixer_kind(i)
        if kind == "ssm2":
            m = p["ssm"]
            lw = {"norm": p["attn_norm"]["scale"],
                  "gate_norm": m["norm"],
                  **{k: m[k] for k in ("w_in", "conv", "conv_bias", "dt_bias",
                                       "A_log", "D", "w_out")}}
        elif kind == "attention":
            lw = {"norm": p["attn_norm"]["scale"],
                  **{k: p["attention"][k] for k in ("wq", "wk", "wv", "wo")}}
        else:
            m = p["moe"]
            lw = {"norm": p["ffn_norm"]["scale"],
                  "shared_wi": m["shared_expert"]["wi"],
                  "shared_wo": m["shared_expert"]["wo"],
                  **{k: m[k] for k in ("router", "selection_bias", "fc1",
                                       "wi", "wo", "fc2")}}
        layers.append(lw)
    return {"embedding": emb["embedding"], "lm_head": emb["lm_head"],
            "final_norm": params["final_norm"]["scale"], "layers": layers}


def program_logits(model, params, ids) -> jax.Array:
    """The program's forward pass as training runs it: no cache,
    deterministic, its own compute dtype."""
    logits, _aux = model.apply({"params": params}, ids, deterministic=True)
    return logits
