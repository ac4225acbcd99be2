"""The `kimi_linear` architecture on the program: the only file of this
architecture that imports luminaai_tpu. `source_kwargs` maps the source's
keys to `Config` fields (with its refusals), `params_view` hands the
reference a neutral view of the SAME arrays, `program_logits` is the
program's own uncached forward pass.

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
    "rms_norm_eps": "rms_norm_eps",
    "rope_theta": "rope_theta",
    "tie_word_embeddings": "tie_word_embeddings",
    "num_experts_per_token": "moe_top_k",
    "moe_intermediate_size": "moe_intermediate_size",
    "num_shared_experts": "num_shared_experts",
    "moe_renormalize": "moe_renormalize",
    "routed_scaling_factor": "moe_routed_scale",
    "moe_router_activation_func": "moe_score_func",
    "first_k_dense_replace": "dense_start_layers",
    "kv_lora_rank": "kv_lora_rank",
    "qk_nope_head_dim": "qk_nope_head_dim",
    "qk_rope_head_dim": "qk_rope_head_dim",
    "v_head_dim": "v_head_dim",
}


def layer_mixers(body: Dict[str, Any]):
    lin = body["linear_attn_config"]
    kinds = []
    for layer in range(1, body["num_hidden_layers"] + 1):
        if layer in lin["kda_layers"]:
            kinds.append("kda")
        elif layer in lin["full_attn_layers"]:
            kinds.append("latent")
        else:
            raise Unsupported(f"layer {layer} is of no stated kind")
    return tuple(kinds)


def source_kwargs(body: Dict[str, Any]) -> Dict[str, Any]:
    if body.get("hidden_act", "silu") != "silu":
        raise Unsupported(f"hidden_act {body['hidden_act']!r}: SwiGLU only")
    for key, want in (("num_expert_group", 1), ("topk_group", 1),
                      ("moe_layer_freq", 1), ("mla_use_nope", True),
                      ("num_nextn_predict_layers", 0)):
        if body.get(key, want) != want:
            raise Unsupported(f"{key}={body[key]!r}: only {want!r} runs")
    for key in ("q_lora_rank", "rope_scaling"):
        if body.get(key):
            raise Unsupported(f"{key}={body[key]!r} is not expressible")
    if body["moe_router_activation_func"] not in ("sigmoid", "softmax"):
        raise Unsupported("moe_router_activation_func")
    lin = body["linear_attn_config"]
    kw = {dst: body[src] for src, dst in SOURCE_TO_CONFIG.items()
          if src in body}
    held = body["num_experts"]
    published = held
    if "num_experts" in body.get("reduced", ()):
        published = body["source_values"]["num_experts"]
        offset = body.get("deployment", {}).get("experts_held_offset", 0)
        kw["experts_held"] = (offset, held)
    kw.update(
        num_experts=published,
        layer_mixers=layer_mixers(body),
        kda_num_heads=lin["num_heads"],
        kda_head_dim=lin["head_dim"],
        kda_conv_size=lin["short_conv_kernel_size"],
        use_moe=True,
        moe_pattern="sandwich",
        dense_end_layers=0,
        # e_score_correction_bias of the family's router: in the choice
        # alone (the configuration file's `assumed`).
        moe_selection_bias=True,
    )
    return kw


def params_view(cfg, params: Dict[str, Any]) -> Dict[str, Any]:
    emb = params["embedder"]
    layers = []
    for i in range(cfg.num_layers):
        p = params[f"layer_{i}"]
        kind = cfg.mixer_kind(i)
        if kind == "kda":
            mixer = dict(p["kda"])
        else:
            la = p["latent_attention"]
            mixer = {"wq": la["wq"], "wkv_a": la["wkv_a"],
                     "kv_norm": la["kv_norm"]["scale"],
                     "wkv_b": la["wkv_b"], "wo": la["wo"]}
        if "moe" in p:
            m = p["moe"]
            ffn = {"router": m["router"],
                   "selection_bias": m["selection_bias"],
                   "wi": m["wi"], "wo": m["wo"]}
            if "shared_expert" in m:
                ffn.update(shared_wi=m["shared_expert"]["wi"],
                           shared_wo=m["shared_expert"]["wo"])
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
