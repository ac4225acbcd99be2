"""The `jamba` architecture on the program: the only file of this
architecture that imports luminaai_tpu. `source_kwargs` maps the source's
keys to `Config` fields (with its refusals), `params_view` hands the
reference a neutral view of the SAME arrays, `program_logits` is the
program's own uncached forward pass (the chunked XLA scan from zero
state; the served forms are held to the reference through the scheduler:
`serve_cell.set_up`)."""

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
    "tie_word_embeddings": "tie_word_embeddings",
    "mamba_d_state": "ssm_state_size",
    "mamba_dt_rank": "ssm_dt_rank",
    "mamba_expand": "ssm_expand",
    "mamba_d_conv": "ssm_conv_size",
}


def layer_mixers(body: Dict[str, Any]):
    """The family's modelling code: attention where
    l % attn_layer_period == attn_layer_offset, a Mamba layer elsewhere."""
    period, offset = body["attn_layer_period"], body["attn_layer_offset"]
    return tuple("attention" if i % period == offset else "ssm"
                 for i in range(body["num_hidden_layers"]))


def source_kwargs(body: Dict[str, Any]) -> Dict[str, Any]:
    if body.get("hidden_act", "silu") != "silu":
        raise Unsupported(f"hidden_act {body['hidden_act']!r}: SwiGLU only")
    for key, want in (("num_experts", 1), ("num_experts_per_tok", 1),
                      ("mamba_conv_bias", True), ("mamba_proj_bias", False),
                      ("sliding_window", None)):
        if body.get(key, want) != want:
            raise Unsupported(f"{key}={body[key]!r}: only {want!r} runs")
    kw = {dst: body[src] for src, dst in SOURCE_TO_CONFIG.items()
          if src in body}
    kw.update(layer_mixers=layer_mixers(body), use_rope=False, use_moe=False)
    return kw


def params_view(cfg, params: Dict[str, Any]) -> Dict[str, Any]:
    layers = []
    for i in range(cfg.num_layers):
        p = params[f"layer_{i}"]
        if cfg.mixer_kind(i) == "ssm":
            mixer = dict(p["ssm"])
        else:
            mixer = {k: p["attention"][k] for k in ("wq", "wk", "wv", "wo")}
        layers.append({"attn_norm": p["attn_norm"]["scale"],
                       "ffn_norm": p["ffn_norm"]["scale"],
                       "mixer": mixer,
                       "ffn": {"wi": p["ffn"]["wi"], "wo": p["ffn"]["wo"]}})
    return {"embedding": params["embedder"]["embedding"],
            "final_norm": params["final_norm"]["scale"], "layers": layers}


def program_logits(model, params, ids) -> jax.Array:
    """The program's forward pass as training runs it: no cache,
    deterministic, its own compute dtype."""
    logits, _aux = model.apply({"params": params}, ids, deterministic=True)
    return logits
