"""Work counts of the `nemotron_h` architecture: operations and bytes the
algorithm needs, from shapes alone. Kept with the benchmark: a PR that
claims a gain cannot change how work is counted. Imports nothing of the
program.

Every function takes the configuration file's body (source keys) and
returns plain numbers. A SERVING cell hands `shapes = {}`, so a kernel of
the serving tick counts from the file's own `deployment` and `program`
groups: the tick has one shape whatever the traffic, and of its work only
what the traffic's live rows need is counted (`deployment.tick_means`,
measured once at the cell's rate).

A layer is ONE sub-layer, by its character of `hybrid_override_pattern`
(the first `num_hidden_layers` of the published 88): M a Mamba-2 mixer, *
an attention, E a latent expert layer. The body's `n_routed_experts` is
what this chip holds where it stands in `reduced` (the published count is
`source_values.n_routed_experts`): a token's `num_experts_per_tok` picks
fall on a held expert with probability held / published each.

`KERNEL_FNS` names the kernels a `roofline_pct` reader may ask for in a
cell of this architecture (its `fn`); `manifest.check` reads the keys from
this file's text, so it stays a literal dict of names.
"""

from __future__ import annotations

from typing import Any, Dict

BF16, F32 = 2, 4


def _published(body, key):
    return body.get("source_values", {}).get(key, body[key])


def _pattern(body) -> str:
    return str(body["hybrid_override_pattern"])[:body["num_hidden_layers"]]


def _mixer_dims(body):
    """(inner D, conv channels D + 2GN, heads)."""
    D = body["mamba_num_heads"] * body["mamba_head_dim"]
    return (D, D + 2 * body["n_groups"] * body["ssm_state_size"],
            body["mamba_num_heads"])


def _mixer_matmul_params(body) -> int:
    H = body["hidden_size"]
    D, C, nh = _mixer_dims(body)
    return H * (D + C + nh) + D * H            # in (z, xBC, dt), out


def _mixer_params(body) -> int:
    """+ the convolution and its bias, the gated norm, dt's bias, A_log,
    D (one a head), the layer's norm."""
    D, C, nh = _mixer_dims(body)
    return (_mixer_matmul_params(body) + (body["conv_kernel"] + 1) * C + D
            + 3 * nh + body["hidden_size"])


def _attn_matmul_params(body) -> int:
    H, n, kv, d = (body["hidden_size"], body["num_attention_heads"],
                   body["num_key_value_heads"], body["head_dim"])
    return 2 * H * n * d + 2 * H * kv * d


def _expert_params(body) -> int:
    """One routed expert: W_up and W_down in the latent, no gate."""
    return 2 * body["moe_latent_size"] * body["moe_intermediate_size"]


def _expert_layer_matmul_outside(body, experts: int) -> int:
    """Router (all `experts` columns), fc1, fc2, the shared relu^2 MLP."""
    H = body["hidden_size"]
    return (H * experts + 2 * H * body["moe_latent_size"]
            + 2 * H * body["moe_shared_expert_intermediate_size"])


def _count(pattern: str, experts: int, held: int, picks: float, vocab: int,
           body) -> Dict[str, float]:
    """(total, active a token) of a stack of `pattern` with `held` experts
    a layer of a router `experts` wide and `picks` computed a token."""
    H = body["hidden_size"]
    m, a, e = (pattern.count(c) for c in "M*E")
    outside = _expert_layer_matmul_outside(body, experts) + experts + H
    fixed = (m * _mixer_params(body) + a * (_attn_matmul_params(body) + H)
             + e * outside + 2 * vocab * H + H)
    return {"total": fixed + e * held * _expert_params(body),
            "active": fixed + e * picks * _expert_params(body)}


def published_params(body: Dict[str, Any]) -> Dict[str, float]:
    """The WHOLE published model from the file's keys (`source_values`
    where the file runs a share): 120.67B parameters, 12.77B active a
    token at top-22 (the name's 120B-A12B). The prediction layer is not
    in the count (assumed.left_out)."""
    experts = _published(body, "n_routed_experts")
    pattern = str(body["hybrid_override_pattern"])[
        :_published(body, "num_hidden_layers")]
    return _count(pattern, experts, experts, body["num_experts_per_tok"],
                  _published(body, "vocab_size"), body)


def _held_picks(body) -> float:
    """Of a token's picks, those that fall on an expert held here."""
    return (body["num_experts_per_tok"] * body["n_routed_experts"]
            / _published(body, "n_routed_experts"))


def params_total(body: Dict[str, Any]) -> int:
    """Parameters this chip holds: the mixers, the attention, router +
    selection bias + fc1 / fc2 + shared expert whole, the held experts,
    one norm a layer, embedding, untied head, final norm."""
    return int(_count(
        _pattern(body), _published(body, "n_routed_experts"),
        body["n_routed_experts"], 0, body["vocab_size"], body)["total"])


def matmul_params_active(body: Dict[str, Any]) -> float:
    """Weights a token is multiplied by ON THIS CHIP: the mixers' and the
    attention's projections, in an expert layer the router, fc1, fc2, the
    shared expert and the routed experts at this chip's expected share of
    the token's picks (22 x 128 / 512 = 5.5); the LM head over the held
    vocabulary. The convolution, the recurrence and the embedding lookup
    are no matmuls."""
    H = body["hidden_size"]
    p = _pattern(body)
    return (p.count("M") * _mixer_matmul_params(body)
            + p.count("*") * _attn_matmul_params(body)
            + p.count("E") * (
                _expert_layer_matmul_outside(
                    body, _published(body, "n_routed_experts"))
                + _held_picks(body) * _expert_params(body))
            + body["vocab_size"] * H)


def _scan_ops_per_row(body) -> float:
    """The recurrence a token a layer over [N, D]: the decay's product,
    the drive's multiply-add, C's multiply-add."""
    D, _, _ = _mixer_dims(body)
    return 5.0 * body["ssm_state_size"] * D


def train_flops_per_token(body: Dict[str, Any], seq: int) -> float:
    """6 x active matmul weights; causal attention forward 2 matmuls over
    seq / 2 keys, backward twice that; the scan forward and twice that
    back. No cell trains this model."""
    p = _pattern(body)
    attn = 6.0 * seq * body["num_attention_heads"] * body["head_dim"]
    return (6.0 * matmul_params_active(body) + p.count("*") * attn
            + p.count("M") * 3.0 * _scan_ops_per_row(body))


# -- kernels: per CALL, on one chip -----------------------------------------
def _tick_means(body) -> Dict[str, float]:
    return body["deployment"]["tick_means"]


def ssm_tick(body, shapes):
    """`ssm_scan_heads`, one state-space layer of one serving tick: the
    LANES alone (the chunk's rows are block-form products in XLA, outside
    this kernel and outside this count). The float32 state of each lane
    the tick steps (`tick_means.lanes_stepped`) read and written, 2 x N x
    D x 4 B = 8.39 MB a lane; for the `num_slots` rows of the call's one
    shape the decay and the drive in and y out in float32, B and C in
    bf16; the recurrence's operations over the stepped lanes.
    Memory-bound by these counts."""
    D, _, _ = _mixer_dims(body)
    N, G = body["ssm_state_size"], body["n_groups"]
    rows = int(body["deployment"]["num_slots"])
    stepped = float(_tick_means(body)["lanes_stepped"])
    byts = (2.0 * stepped * N * D * F32
            + rows * (3 * D * F32 + 2 * G * N * BF16))
    return {"ops": stepped * _scan_ops_per_row(body), "bytes": byts}


def grouped_matmul(body, shapes):
    """One megablox gmm call of one serving tick over the rows of the HELD
    experts; both calls of a layer (rows x latent x F, rows x F x latent)
    have the same counts. Rows: the tick's live rows
    (`tick_means.live_rows`) x the held picks a row (22 x 128 / 512 =
    5.5). Weights: ONE matrix of each held expert SOME live row picked
    (`tick_means.experts_hit`, the mean a layer a tick the program counts:
    moe_held_experts_hit_total; the kernel visits no expert without
    rows), read once. Memory-bound by these counts."""
    L, F = body["moe_latent_size"], body["moe_intermediate_size"]
    m = _tick_means(body)
    rows = m["live_rows"] * _held_picks(body)
    return {"ops": 2.0 * rows * L * F,
            "bytes": BF16 * (rows * (L + F) + m["experts_hit"] * L * F)}


def chunk_attention(body, shapes):
    """One call of `chunk_attention` (ops/ragged_paged_attention.py): the
    attention layer's part of the tick's prefill chunk over its own lane.
    The chunk's live rows (`tick_means.chunk_rows`, a mean over ALL
    ticks) each see `chunk_keys` keys (the mean of position + 1 over the
    traffic's chunk rows) under every query head, a score and a sum over
    head_dim columns each. Bytes: the rows of k and v a riding chunk
    spans (`chunk_span`, already times the share of ticks a chunk rides),
    once a k/v head, and q and the output once."""
    n, kv, d = (body["num_attention_heads"], body["num_key_value_heads"],
                body["head_dim"])
    m = _tick_means(body)
    return {"ops": 4.0 * d * m["chunk_rows"] * n * m["chunk_keys"],
            "bytes": BF16 * 2 * d * (m["chunk_span"] * kv
                                     + m["chunk_rows"] * n)}


def lane_attention(body, shapes):
    """One call of `lane_attention`: the attention layer's part of the
    lanes a tick steps (`tick_means.lanes_stepped`), each query over the
    keys it holds (`lane_keys`, their mean length) under every query
    head. Bytes: the rows of k and v in the key blocks the kernel fetches
    for them (`lane_rows`: live blocks a call x a block's rows, from the
    kernel's own plan run on the host), once a k/v head, and the queries
    and outputs once. Memory-bound by these counts."""
    n, kv, d = (body["num_attention_heads"], body["num_key_value_heads"],
                body["head_dim"])
    m = _tick_means(body)
    return {"ops": 4.0 * d * n * m["lanes_stepped"] * m["lane_keys"],
            "bytes": BF16 * 2 * d * (m["lane_rows"] * kv
                                     + m["lanes_stepped"] * n)}


KERNEL_FNS = {
    "ssm_tick": ssm_tick,
    "grouped_matmul": grouped_matmul,
    "chunk_attention": chunk_attention,
    "lane_attention": lane_attention,
}
