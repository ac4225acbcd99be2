"""The only place that knows how the program lays out its parameter tree
and how its forward pass is called. `params_view` hands the reference a
neutral view of the SAME arrays (no copy); `program_logits` is the
program's own uncached forward pass."""

from __future__ import annotations

from typing import Any, Dict

import jax


def params_view(cfg, params: Dict[str, Any]) -> Dict[str, Any]:
    from luminaai_tpu.models.transformer import unstack_params_from_scan

    if cfg.scan_layers:
        params = unstack_params_from_scan(cfg, params)
    emb = params["embedder"]
    layers = []
    for i in range(cfg.num_layers):
        p = params[f"layer_{i}"]
        a = p["attention"]
        lw = {
            "attn_norm": p["attn_norm"]["scale"],
            "ffn_norm": p["ffn_norm"]["scale"],
            "wq": a["wq"], "wk": a["wk"], "wv": a["wv"], "wo": a["wo"],
        }
        if "moe" in p:
            lw.update(router=p["moe"]["router"], wi=p["moe"]["wi"],
                      wo_ffn=p["moe"]["wo"])
        else:
            lw.update(wi=p["ffn"]["wi"], wo_ffn=p["ffn"]["wo"])
        layers.append(lw)
    return {
        "embedding": emb["embedding"],
        "lm_head": emb.get("lm_head"),
        "final_norm": params["final_norm"]["scale"],
        "layers": layers,
    }


def program_logits(model, params, ids) -> jax.Array:
    """The program's forward pass as training and prefill run it: no
    cache, deterministic, its own kernels and compute dtype."""
    logits, _aux = model.apply({"params": params}, ids, deterministic=True)
    return logits


def jit_on_mesh(fn, cfg, mesh):
    """Trace `fn` under the program's mesh and logical axis rules, as
    parallel/train_step.py traces its steps, and call it inside the mesh."""
    from flax import linen as nn

    from luminaai_tpu.parallel.mesh import use_mesh
    from luminaai_tpu.parallel.sharding import logical_axis_rules

    def traced(*args):
        with use_mesh(mesh), nn.logical_axis_rules(logical_axis_rules(cfg)):
            return fn(*args)

    jitted = jax.jit(traced)

    def call(*args):
        with mesh:
            return jitted(*args)

    return call
