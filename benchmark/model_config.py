"""From a configuration file (the source's own keys) to the program's
`Config`. The mapping is general: a later configuration whose source uses
these keys needs a file and no code."""

from __future__ import annotations

from typing import Any, Dict

# source key -> luminaai_tpu Config field
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
    "num_experts": "num_experts",
    "num_experts_per_tok": "moe_top_k",
}
MESH_TO_CONFIG = {
    "data": "data_parallel_size",
    "fsdp": "fsdp_parallel_size",
    "expert": "expert_parallel_size",
    "tensor": "tensor_parallel_size",
}


class Unsupported(Exception):
    """The file states something the program cannot express."""


def config_kwargs(body: Dict[str, Any], **overrides: Any) -> Dict[str, Any]:
    """Keyword arguments of `Config` for a configuration file's body."""
    if body.get("hidden_act", "silu") != "silu":
        raise Unsupported(f"hidden_act {body['hidden_act']!r}: SwiGLU only")
    for key in ("attention_bias", "sliding_window", "rope_scaling",
                "clip_qkv"):
        if body.get(key):
            raise Unsupported(f"{key}={body[key]!r} is not expressible")
    kw = {
        dst: body[src] for src, dst in SOURCE_TO_CONFIG.items() if src in body
    }
    if "head_dim" in body and (
        body["head_dim"] * body["num_attention_heads"] != body["hidden_size"]
    ):
        raise Unsupported("head_dim * heads != hidden_size")
    kw.update(
        {k: v for k, v in body.get("program", {}).items()
         if not k.startswith("_")}
    )
    for axis, size in body.get("deployment", {}).get("mesh", {}).items():
        kw[MESH_TO_CONFIG[axis]] = size
    kw.update(overrides)
    return kw


def build_config(body: Dict[str, Any], **overrides: Any):
    from luminaai_tpu.config import Config

    return Config(**config_kwargs(body, **overrides))
