"""Work counts of the `kimi_linear` architecture: operations and bytes the
algorithm needs, from shapes alone. Kept with the benchmark: a PR that
claims a gain cannot change how work is counted. Imports nothing of the
program.

Every function takes the configuration file's body (source keys) and
returns plain numbers. `shapes` is what the cell runs at:
    {"seq": tokens a sequence, "seqs_per_chip": sequences a chip a step,
     "chips": n, "mesh": {...}}
Recomputed operations are NOT model work (mfu) but ARE kernel work (a
kernel's roofline share counts every call the trace shows).

The body's `num_experts` is what this chip holds where it stands in
`reduced` (the published count is `source_values.num_experts`): a token's
`num_experts_per_token` picks fall on a held expert with probability
held / published each, so the routed experts' work a token is that share
of a token's picks, not all of them (`_held_picks`).

`KERNEL_FNS` names the kernels a `roofline_pct` reader may ask for in a
cell of this architecture (its `fn`); `manifest.check` reads the keys from
this file's text, so it stays a literal dict of names.
"""

from __future__ import annotations

from typing import Any, Dict

BF16, F32 = 2, 4
CHUNK = 64  # tokens a chunk of the delta-rule kernels


def _kinds(body):
    lin = body["linear_attn_config"]
    return ["kda" if layer in lin["kda_layers"] else "latent"
            for layer in range(1, body["num_hidden_layers"] + 1)]


def _kda_dims(body):
    lin = body["linear_attn_config"]
    return lin["num_heads"], lin["head_dim"]


def _kda_params(body) -> int:
    H = body["hidden_size"]
    n, d = _kda_dims(body)
    D = n * d
    # q, k, v, two rank-d pairs (decay, output gate), beta, output
    return 3 * H * D + 2 * (H * d + d * D) + H * n + D * H


def _latent_params(body) -> int:
    H, n = body["hidden_size"], body["num_attention_heads"]
    dq = body["qk_nope_head_dim"] + body["qk_rope_head_dim"]
    rank, dv = body["kv_lora_rank"], body["v_head_dim"]
    return (H * n * dq + H * (rank + body["qk_rope_head_dim"])
            + rank * n * (body["qk_nope_head_dim"] + dv) + n * dv * H)


def _published_experts(body) -> int:
    return body.get("source_values", {}).get("num_experts",
                                             body["num_experts"])


def _held_picks(body) -> float:
    """Of a token's picks, those that fall on an expert held here."""
    return (body["num_experts_per_token"] * body["num_experts"]
            / _published_experts(body))


def _expert_params(body) -> int:
    return 3 * body["hidden_size"] * body["moe_intermediate_size"]


def matmul_params_active(body: Dict[str, Any]) -> float:
    """Weights a token is multiplied by ON THIS CHIP: every mixer's
    projections, the dense FFN of the leading layers, the router (all
    published columns), the shared experts, the routed experts at this
    chip's expected share of the token's picks (8 picks x 8 / 256 held =
    0.25 expert), and the LM head over the held vocabulary. The short
    convolutions and the embedding lookup are not matmuls."""
    H = body["hidden_size"]
    total = 0.0
    for i, kind in enumerate(_kinds(body)):
        total += _kda_params(body) if kind == "kda" else _latent_params(body)
        if i < body["first_k_dense_replace"]:
            total += 3 * H * body["intermediate_size"]
        else:
            total += (H * _published_experts(body)
                      + body["num_shared_experts"] * _expert_params(body)
                      + _held_picks(body) * _expert_params(body))
    return total + body["vocab_size"] * H


def params_total(body: Dict[str, Any]) -> int:
    """Parameters this chip holds."""
    H = body["hidden_size"]
    n, d = _kda_dims(body)
    total = 0
    for i, kind in enumerate(_kinds(body)):
        if kind == "kda":
            K = body["linear_attn_config"]["short_conv_kernel_size"]
            total += _kda_params(body) + 3 * K * n * d + n + n * d + d
        else:
            total += _latent_params(body) + body["kv_lora_rank"]
        if i < body["first_k_dense_replace"]:
            total += 3 * H * body["intermediate_size"]
        else:
            E = _published_experts(body)
            total += (H * E + E + _expert_params(body) * (
                body["num_experts"] + body["num_shared_experts"]))
        total += 2 * H
    return total + 2 * body["vocab_size"] * H + H


def _kda_chunk_macs(body) -> float:
    """Multiply-adds of one chunk of one head, forward, at chunk 64: the
    running sum of the gate (C^2 d), the two score matrices A and P
    (2 C^2 d), (I + A)^-1 by products (5 squarings and 5 products of
    C^3), T against beta k e^G and beta v (2 C^2 d), W S and q e^G S
    (2 C d^2), P U (C^2 d), the state's update (C d^2)."""
    n, d = _kda_dims(body)
    C = CHUNK
    return 6.0 * C * C * d + 10.0 * C ** 3 + 3.0 * C * d * d


def train_flops_per_token(body: Dict[str, Any], seq: int) -> float:
    """6 x active matmul weights; causal attention of the latent layers
    (forward 2 matmuls over seq/2 keys of (dq + dv) a head, backward twice
    that); the delta-rule layers' chunk work (forward + twice that back)."""
    n_kda, d = _kda_dims(body)
    kinds = _kinds(body)
    dq = body["qk_nope_head_dim"] + body["qk_rope_head_dim"]
    attn = 3.0 * seq * body["num_attention_heads"] * (dq + body["v_head_dim"])
    kda = 3.0 * 2.0 * n_kda * _kda_chunk_macs(body) / CHUNK
    return (6.0 * matmul_params_active(body)
            + kinds.count("latent") * attn + kinds.count("kda") * kda)


# -- kernels: per CALL, on one chip -----------------------------------------
def _attn_call(body, shapes, score_matmuls: int, value_matmuls: int):
    """The latent layer's flash kernels: scores over dq = nope + rope,
    values over dv; causal half."""
    n = body["num_attention_heads"]
    dq = body["qk_nope_head_dim"] + body["qk_rope_head_dim"]
    dv = body["v_head_dim"]
    B, S = shapes["seqs_per_chip"], shapes["seq"]
    ops = 2.0 * B * n * S * S / 2 * (score_matmuls * dq + value_matmuls * dv)
    return ops, B * S * n * dq * BF16, B * S * n * dv * BF16


def flash_fwd(body, shapes):       # QK^T | PV
    ops, qk, vo = _attn_call(body, shapes, 1, 1)
    return {"ops": ops, "bytes": 2.0 * qk + 2.0 * vo}   # q k | v o


def flash_bwd_dq(body, shapes):    # QK^T, dS K | dO V^T
    ops, qk, vo = _attn_call(body, shapes, 2, 1)
    return {"ops": ops, "bytes": 3.0 * qk + 2.0 * vo}   # q k dq | v dO


def flash_bwd_dkv(body, shapes):   # QK^T, dS^T Q | P^T dO, dO V^T
    ops, qk, vo = _attn_call(body, shapes, 2, 2)
    return {"ops": ops, "bytes": 3.0 * qk + 3.0 * vo}   # q k dk | v dO dv


def grouped_matmul(body, shapes):
    """One megablox gmm/tgmm call over the rows of the HELD experts,
    averaged over the two shapes it is called with (rows x H x 2F and
    rows x F x H): 3*rows*H*F multiply-adds x2."""
    H, F = body["hidden_size"], body["moe_intermediate_size"]
    rows = shapes["seqs_per_chip"] * shapes["seq"] * _held_picks(body)
    ops = 2.0 * rows * H * 1.5 * F
    byts = BF16 * (rows * (H + 1.5 * F) + body["num_experts"] * H * 1.5 * F)
    return {"ops": ops, "bytes": byts}


def _kda_call(body, shapes):
    n, d = _kda_dims(body)
    tokens = shapes["seqs_per_chip"] * shapes["seq"]
    chunks = tokens / CHUNK * n
    return n, d, tokens, chunks


def kda_fwd(body, shapes):
    """The forward kernel under differentiation: q, k, v, g (float32) and
    beta read, o and each chunk's entering state (float32) written."""
    n, d, tokens, chunks = _kda_call(body, shapes)
    byts = (tokens * n * (3 * d * BF16 + d * F32 + F32)   # q k v | g | beta
            + tokens * n * d * BF16                       # o
            + chunks * d * d * F32)                       # kept states
    return {"ops": 2.0 * chunks * _kda_chunk_macs(body), "bytes": byts}


def kda_bwd(body, shapes):
    """The backward kernel: the chunk's forward again and twice that for
    the gradients; reads q, k, v, g, beta, dO and the kept states, writes
    dq, dk, dv, dg (float32), dbeta."""
    n, d, tokens, chunks = _kda_call(body, shapes)
    byts = (tokens * n * (3 * d * BF16 + d * F32 + F32)
            + tokens * n * d * BF16                       # dO
            + chunks * d * d * F32                        # states
            + tokens * n * (3 * d * BF16 + d * F32 + F32))
    return {"ops": 3.0 * 2.0 * chunks * _kda_chunk_macs(body), "bytes": byts}


KERNEL_FNS = {
    "flash_fwd": flash_fwd,
    "flash_bwd_dq": flash_bwd_dq,
    "flash_bwd_dkv": flash_bwd_dkv,
    "grouped_matmul": grouped_matmul,
    "kda_fwd": kda_fwd,
    "kda_bwd": kda_bwd,
}
