"""Work counts of the `kimi_k2` architecture: operations and bytes the
algorithm needs, from shapes alone. Kept with the benchmark: a PR that
claims a gain cannot change how work is counted. Imports nothing of the
program.

Every function takes the configuration file's body (source keys) and
returns plain numbers. A SERVING cell hands `shapes = {}`, so a kernel of
the serving tick counts from the file's own `deployment` and `program`
groups: the tick has one shape whatever the traffic, and of its work only
what the traffic's live rows need is counted (`deployment.tick_means`,
measured once at the cell's rate).

The attention kernels count the ABSORBED form's needed work, whatever
implements it: a head scores a key over kv_lora_rank + qk_rope_head_dim
columns and sums kv_lora_rank columns of value, 2 x 576 + 2 x 512 = 2,176
operations a live key a head, and each live latent row (576 columns of
bf16; the stored row's padding is no needed byte) is read ONCE for all
heads.

The body's `n_routed_experts` is what this chip holds where it stands in
`reduced` (the published count is `source_values.n_routed_experts`): a
token's `num_experts_per_tok` picks fall on a held expert with probability
held / published each.

`KERNEL_FNS` names the kernels a `roofline_pct` reader may ask for in a
cell of this architecture (its `fn`); `manifest.check` reads the keys from
this file's text, so it stays a literal dict of names.
"""

from __future__ import annotations

from typing import Any, Dict

BF16 = 2


def _published_experts(body) -> int:
    return body.get("source_values", {}).get("n_routed_experts",
                                             body["n_routed_experts"])


def _held_picks(body) -> float:
    """Of a token's picks, those that fall on an expert held here."""
    return (body["num_experts_per_tok"] * body["n_routed_experts"]
            / _published_experts(body))


def _attn_params(body) -> int:
    H, n = body["hidden_size"], body["num_attention_heads"]
    dn, dr, dv = (body["qk_nope_head_dim"], body["qk_rope_head_dim"],
                  body["v_head_dim"])
    rq, r = body["q_lora_rank"], body["kv_lora_rank"]
    return (H * rq + rq * n * (dn + dr) + H * (r + dr) + r * n * (dn + dv)
            + n * dv * H)


def _expert_params(body) -> int:
    return 3 * body["hidden_size"] * body["moe_intermediate_size"]


def _dense_params(body) -> int:
    return 3 * body["hidden_size"] * body["intermediate_size"]


def _layers(body):
    dense = min(body["first_k_dense_replace"], body["num_hidden_layers"])
    return dense, body["num_hidden_layers"] - dense


def matmul_params_active(body: Dict[str, Any]) -> float:
    """Weights a token is multiplied by ON THIS CHIP: attention's five
    projections, the dense layer's SwiGLU, and in an expert layer the
    router (all published columns), the shared expert and the routed
    experts at this chip's expected share of the token's picks (8 picks x
    12 / 384 held = a quarter of an expert); the LM head over the held
    vocabulary. The embedding lookup is no matmul."""
    H = body["hidden_size"]
    dense, moe = _layers(body)
    moe_layer = (H * _published_experts(body)
                 + (body["n_shared_experts"] + _held_picks(body))
                 * _expert_params(body))
    return (body["num_hidden_layers"] * _attn_params(body)
            + dense * _dense_params(body) + moe * moe_layer
            + body["vocab_size"] * H)


def params_total(body: Dict[str, Any]) -> int:
    """Parameters this chip holds: per layer attention, its two inner norms
    (q_lora_rank + kv_lora_rank) and the block's two; the dense layer's
    SwiGLU or router + selection bias + shared + held experts; embedding,
    untied head, final norm."""
    H = body["hidden_size"]
    dense, moe = _layers(body)
    layer = (_attn_params(body) + body["q_lora_rank"] + body["kv_lora_rank"]
             + 2 * H)
    E = _published_experts(body)
    moe_layer = (H * E + E + (body["n_shared_experts"]
                              + body["n_routed_experts"])
                 * _expert_params(body))
    return (body["num_hidden_layers"] * layer + dense * _dense_params(body)
            + moe * moe_layer + 2 * body["vocab_size"] * H + H)


def train_flops_per_token(body: Dict[str, Any], seq: int) -> float:
    """6 x active matmul weights; causal attention in the EXPANDED form
    (what training runs) forward 2 matmuls over seq / 2 keys of 192 and
    128 columns a head, backward twice that. No cell trains this model."""
    n = body["num_attention_heads"]
    cols = (body["qk_nope_head_dim"] + body["qk_rope_head_dim"]
            + body["v_head_dim"])
    return (6.0 * matmul_params_active(body)
            + 6.0 * n * cols * (seq / 2) * body["num_hidden_layers"])


# -- kernels: per CALL, on one chip -----------------------------------------
def _tick_means(body) -> Dict[str, float]:
    return body["deployment"]["tick_means"]


def _absorbed(body):
    """(operations a live key a head, bytes a live latent row)."""
    r, dr = body["kv_lora_rank"], body["qk_rope_head_dim"]
    return 2.0 * (r + dr) + 2.0 * r, BF16 * (r + dr)


def grouped_matmul(body, shapes):
    """One megablox gmm call of one serving tick over the rows of the HELD
    experts, averaged over the two shapes it is called with (rows x H x
    2F and rows x F x H): the tick's live rows (`tick_means.live_rows`:
    stepped lanes + live chunk rows) x the held picks a row, and the
    weights of the held experts SOME live row picked: about all 12 in a
    tick a chunk rides (`chunk_ride_share` of the ticks), few in a tick
    that steps the lanes alone (a row picks a given expert with
    probability top-k / published; the kernel visits no expert without
    rows). Memory-bound by these counts."""
    H, F = body["hidden_size"], body["moe_intermediate_size"]
    m = _tick_means(body)
    ride = m["chunk_ride_share"]
    lanes = m["live_rows"] - m["chunk_rows"]
    missed = 1.0 - body["num_experts_per_tok"] / _published_experts(body)
    touched = body["n_routed_experts"] * (
        ride * (1.0 - missed ** (lanes + m["chunk_rows"] / ride))
        + (1.0 - ride) * (1.0 - missed ** lanes))
    rows = m["live_rows"] * _held_picks(body)
    ops = 2.0 * rows * H * 1.5 * F
    byts = BF16 * (rows * (H + 1.5 * F) + touched * H * 1.5 * F)
    return {"ops": ops, "bytes": byts}


def chunk_attention(body, shapes):
    """One call of `chunk_attention` (ops/ragged_paged_attention.py) over a
    latent entry: one layer's absorbed attention of the tick's prefill
    chunk over its own lane. The chunk's live rows
    (`tick_means.chunk_rows`, a mean over ALL ticks) each see
    `tick_means.chunk_keys` stored latents (the mean of position + 1 over
    the traffic's chunk rows) under every head. Bytes: the latent rows a
    riding chunk's band spans, once (`chunk_span`: the chunk's end x the
    share of ticks a chunk rides), and the queries (576 columns a head)
    and sums (512) once. Compute-bound by these counts."""
    n, r = body["num_attention_heads"], body["kv_lora_rank"]
    per_key, per_row = _absorbed(body)
    m = _tick_means(body)
    ops = per_key * m["chunk_rows"] * n * m["chunk_keys"]
    byts = per_row * m["chunk_span"] + m["chunk_rows"] * n * (
        per_row + BF16 * r)
    return {"ops": ops, "bytes": byts}


def lane_attention(body, shapes):
    """One call of `lane_attention` over a latent entry: one layer's
    absorbed attention of the lanes a tick steps
    (`tick_means.lanes_stepped`), each query over the latents its lane
    holds (`tick_means.lane_keys`: the mean length of a stepped lane) under
    every head; each of those latent rows read once
    (`lanes_stepped x lane_keys` rows: what the stepped lanes HOLD, not the
    whole blocks the kernel fetches), the queries and sums once. Between
    the two bounds by these counts (121 operations a byte against the
    v5e's 240: memory-bound)."""
    n, r = body["num_attention_heads"], body["kv_lora_rank"]
    per_key, per_row = _absorbed(body)
    m = _tick_means(body)
    keys = m["lanes_stepped"] * m["lane_keys"]
    ops = per_key * n * keys
    byts = per_row * keys + m["lanes_stepped"] * n * (per_row + BF16 * r)
    return {"ops": ops, "bytes": byts}


KERNEL_FNS = {
    "grouped_matmul": grouped_matmul,
    "chunk_attention": chunk_attention,
    "lane_attention": lane_attention,
}
