"""Operations and bytes the algorithm needs, from shapes alone. Kept with
the benchmark: a PR that claims a gain cannot change how work is counted.

Every function takes the configuration file's body (source keys) and
returns plain numbers. `shapes` is what the cell runs at:
    {"seq": tokens a sequence, "seqs_per_chip": sequences a chip a step,
     "chips": n, "mesh": {"fsdp":..,"expert":..}}
Recomputed operations are NOT model work (mfu) but ARE kernel work (a
kernel's roofline share counts every call the trace shows).
"""

from __future__ import annotations

from typing import Any, Dict

BF16 = 2


def _dims(body: Dict[str, Any]):
    H = body["hidden_size"]
    nq = body["num_attention_heads"]
    nkv = body["num_key_value_heads"]
    d = body.get("head_dim") or H // nq
    return H, nq, nkv, d, body["intermediate_size"], body["num_hidden_layers"]


def matmul_params_active(body: Dict[str, Any]) -> int:
    """Weights a token is multiplied by: projections, its top-k experts
    (or the dense FFN), the router and the LM head. The embedding lookup
    is not a matmul."""
    H, nq, nkv, d, F, L = _dims(body)
    attn = H * nq * d + 2 * H * nkv * d + nq * d * H
    E = body.get("num_experts", 0)
    k = body.get("num_experts_per_tok", 1) if E else 1
    ffn = 3 * H * F * k + (H * E if E else 0)
    return L * (attn + ffn) + body["vocab_size"] * H


def params_total(body: Dict[str, Any]) -> int:
    H, nq, nkv, d, F, L = _dims(body)
    attn = H * nq * d + 2 * H * nkv * d + nq * d * H
    E = body.get("num_experts", 0)
    ffn = 3 * H * F * max(E, 1) + (H * E if E else 0)
    emb = body["vocab_size"] * H * (1 if body.get("tie_word_embeddings") else 2)
    return L * (attn + ffn + 2 * H) + emb + H


def train_flops_per_token(body: Dict[str, Any], seq: int) -> float:
    """6 x active matmul weights, plus causal attention: forward 2 matmuls
    of 2*(seq/2)*nq*d a token a layer, backward twice that."""
    H, nq, nkv, d, F, L = _dims(body)
    return 6.0 * matmul_params_active(body) + L * 6.0 * seq * nq * d


# -- kernels: per CALL, on one chip -----------------------------------------
def _attn_call(body, shapes, matmuls: int) -> Dict[str, float]:
    H, nq, nkv, d, F, L = _dims(body)
    B, S = shapes["seqs_per_chip"], shapes["seq"]
    ops = matmuls * 2.0 * B * nq * S * S * d / 2  # causal half
    qo = B * S * nq * d * BF16
    kv = B * S * nkv * d * BF16
    return {"ops": ops, "bytes": 2.0 * qo + 2.0 * kv}


def flash_fwd(body, shapes):       # QK^T, PV
    return _attn_call(body, shapes, 2)


def flash_bwd_dq(body, shapes):    # QK^T, dO V^T, dS K
    out = _attn_call(body, shapes, 3)
    out["bytes"] *= 1.5            # also reads dO, writes dQ
    return out


def flash_bwd_dkv(body, shapes):   # QK^T, P^T dO, dO V^T, dS^T Q
    out = _attn_call(body, shapes, 4)
    out["bytes"] *= 2.0            # also reads dO, writes dK and dV
    return out


def _expert_rows(body, shapes) -> float:
    """Rows (token, expert) pairs a chip's local experts see in a layer:
    its token shard's picks that land on its expert shard."""
    mesh = shapes.get("mesh", {})
    ep = mesh.get("expert", 1)
    tokens = shapes["seqs_per_chip"] * shapes["seq"] * ep
    return tokens * body["num_experts_per_tok"] / ep


def grouped_matmul(body, shapes):
    """One megablox gmm/tgmm call, averaged over the two shapes it is
    called with (rows x H x 2F and rows x F x H): 3*rows*H*F multiply-adds
    x2."""
    H, nq, nkv, d, F, L = _dims(body)
    rows = _expert_rows(body, shapes)
    e_local = body["num_experts"] / shapes.get("mesh", {}).get("expert", 1)
    ops = 2.0 * rows * H * 1.5 * F
    byts = BF16 * (rows * (H + 1.5 * F) + e_local * H * 1.5 * F)
    return {"ops": ops, "bytes": byts}


KERNEL_FNS = {
    "flash_fwd": flash_fwd,
    "flash_bwd_dq": flash_bwd_dq,
    "flash_bwd_dkv": flash_bwd_dkv,
    "grouped_matmul": grouped_matmul,
}


def least_seconds(work: Dict[str, float], peak: Dict[str, float]):
    """(roofline time of one call, which bound sets it)."""
    t_ops = work["ops"] / peak["bf16_flops_per_s"]
    t_mem = work["bytes"] / peak["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")
