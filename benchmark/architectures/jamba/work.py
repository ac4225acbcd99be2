"""Work counts of the `jamba` architecture: operations and bytes the
algorithm needs, from shapes alone. Kept with the benchmark: a PR that
claims a gain cannot change how work is counted. Imports nothing of the
program.

Every function takes the configuration file's body (source keys) and
returns plain numbers. A training cell hands `shapes` as the other
architectures' files describe it; a SERVING cell hands `shapes = {}`, so a
kernel of the serving tick counts from the file's own `deployment`
(`num_slots`, `lanes_stepped_a_tick`) and `program` (`prefill_chunk_size`)
groups: the tick has one shape whatever the traffic, and of its work only
the lanes the traffic steps are needed.

`KERNEL_FNS` names the kernels a `roofline_pct` reader may ask for in a
cell of this architecture (its `fn`); `manifest.check` reads the keys from
this file's text, so it stays a literal dict of names.
"""

from __future__ import annotations

from typing import Any, Dict

BF16, F32 = 2, 4


def _kinds(body):
    period, offset = body["attn_layer_period"], body["attn_layer_offset"]
    return ["attention" if i % period == offset else "ssm"
            for i in range(body["num_hidden_layers"])]


def _ssm_dims(body):
    D = body["mamba_expand"] * body["hidden_size"]
    return D, body["mamba_d_state"], body["mamba_dt_rank"], body["mamba_d_conv"]


def _ssm_matmul_params(body) -> int:
    H = body["hidden_size"]
    D, N, R, _ = _ssm_dims(body)
    return H * 2 * D + D * (R + 2 * N) + R * D + D * H   # in, x, dt, out


def _attn_params(body) -> int:
    H, nq, nkv = (body["hidden_size"], body["num_attention_heads"],
                  body["num_key_value_heads"])
    d = H // nq
    return H * nq * d + 2 * H * nkv * d + nq * d * H


def matmul_params_active(body: Dict[str, Any]) -> int:
    """Weights a token is multiplied by: every mixer's projections, the
    dense SwiGLU of every layer, the tied LM head. The convolution, the
    recurrence and the embedding lookup are not matmuls."""
    H = body["hidden_size"]
    kinds = _kinds(body)
    return (kinds.count("ssm") * _ssm_matmul_params(body)
            + kinds.count("attention") * _attn_params(body)
            + len(kinds) * 3 * H * body["intermediate_size"]
            + body["vocab_size"] * H)


def params_total(body: Dict[str, Any]) -> int:
    H = body["hidden_size"]
    D, N, R, K = _ssm_dims(body)
    # conv + its bias, dt's bias, A_log, D, the three inner norms
    ssm = _ssm_matmul_params(body) + K * D + D + D + N * D + D + R + 2 * N
    kinds = _kinds(body)
    emb = body["vocab_size"] * H * (1 if body["tie_word_embeddings"] else 2)
    return (kinds.count("ssm") * ssm
            + kinds.count("attention") * _attn_params(body)
            + len(kinds) * (3 * H * body["intermediate_size"] + 2 * H)
            + emb + H)


def _scan_ops_per_row(body) -> float:
    """The recurrence a token a layer: over [N, D], dt*A, the exponential,
    decay*h, (dt x)*B and their sum, h*C and its sum: three multiply-adds
    and an exponential an element."""
    D, N, _, _ = _ssm_dims(body)
    return N * D * (3 * 2.0 + 1.0)


def train_flops_per_token(body: Dict[str, Any], seq: int) -> float:
    """6 x active matmul weights; causal attention of the attention layers
    (forward 2 matmuls over seq/2 keys, backward twice that); the scan
    forward and twice that back."""
    kinds = _kinds(body)
    H, nq = body["hidden_size"], body["num_attention_heads"]
    attn = 6.0 * seq * nq * (H // nq)
    return (6.0 * matmul_params_active(body)
            + kinds.count("attention") * attn
            + kinds.count("ssm") * 3.0 * _scan_ops_per_row(body))


# -- kernels: per CALL, on one chip -----------------------------------------
def ssm_tick(body, shapes):
    """`ssm_scan`, one layer of one serving tick, as the cell's traffic
    needs it: the states of the lanes a tick STEPS
    (`deployment.lanes_stepped_a_tick`, the mean measured at the cell's
    rate: an idle lane's state needs no traffic, however many slots the
    pool holds) and of the chunk's slot read and written (float32); for
    the num_slots + prefill_chunk_size rows of the tick's one shape x, z
    and y in bf16, dt in float32, B and C in float32 (the projections
    around the kernel produce and consume every row); the recurrence's
    operations over the stepped lanes' and the chunk's rows. Memory-bound
    by these counts."""
    D, N, _, _ = _ssm_dims(body)
    chunk = int(body["program"]["prefill_chunk_size"])
    rows = int(body["deployment"]["num_slots"]) + chunk
    stepped = float(body["deployment"]["lanes_stepped_a_tick"])
    byts = (2.0 * (stepped + 1) * N * D * F32
            + rows * (3 * D * BF16 + D * F32 + 2 * N * F32))
    return {"ops": (stepped + chunk) * _scan_ops_per_row(body),
            "bytes": byts}


KERNEL_FNS = {
    "ssm_tick": ssm_tick,
}
