"""Work counts of the `cohere2_moe` architecture: operations and bytes the
algorithm needs, from shapes alone. Kept with the benchmark: a PR that
claims a gain cannot change how work is counted. Imports nothing of the
program.

Every function takes the configuration file's body (source keys) and
returns plain numbers. A SERVING cell hands `shapes = {}`, so a kernel of
the serving tick counts from the file's own `deployment` and `program`
groups: the tick has one shape whatever the traffic, and of its work only
what the traffic's live rows need is counted (`deployment.tick_means`,
measured once at the cell's rate, as jamba's `lanes_stepped_a_tick` is).

The body's `num_experts` is what this chip holds where it stands in
`reduced` (the published count is `source_values.num_experts`): a token's
`num_experts_per_tok` picks fall on a held expert with probability
held / published each.

`KERNEL_FNS` names the kernels a `roofline_pct` reader may ask for in a
cell of this architecture (its `fn`); `manifest.check` reads the keys from
this file's text, so it stays a literal dict of names.
"""

from __future__ import annotations

from typing import Any, Dict

BF16, F32 = 2, 4


def _published_experts(body) -> int:
    return body.get("source_values", {}).get("num_experts",
                                             body["num_experts"])


def _held_picks(body) -> float:
    """Of a token's picks, those that fall on an expert held here."""
    return (body["num_experts_per_tok"] * body["num_experts"]
            / _published_experts(body))


def _attn_params(body) -> int:
    H, d = body["hidden_size"], body["head_dim"]
    nq, nkv = body["num_attention_heads"], body["num_key_value_heads"]
    return 2 * H * nq * d + 2 * H * nkv * d          # q, o | k, v


def _expert_params(body) -> int:
    return 3 * body["hidden_size"] * body["intermediate_size"]


def matmul_params_active(body: Dict[str, Any]) -> float:
    """Weights a token is multiplied by ON THIS CHIP: attention's four
    projections, the router (all published columns), the shared experts,
    the routed experts at this chip's expected share of the token's picks
    (8 picks x 16 / 128 held = 1 expert), and the LM head over the held
    vocabulary. The embedding lookup is no matmul."""
    H = body["hidden_size"]
    layer = (_attn_params(body) + H * _published_experts(body)
             + (body["num_shared_experts"] + _held_picks(body))
             * _expert_params(body))
    return body["num_hidden_layers"] * layer + body["vocab_size"] * H


def params_total(body: Dict[str, Any]) -> int:
    """Parameters this chip holds (one norm a layer, no bias; tied
    embedding; the final norm)."""
    H = body["hidden_size"]
    layer = (_attn_params(body) + H * _published_experts(body) + H
             + (body["num_shared_experts"] + body["num_experts"])
             * _expert_params(body))
    return body["num_hidden_layers"] * layer + body["vocab_size"] * H + H


def train_flops_per_token(body: Dict[str, Any], seq: int) -> float:
    """6 x active matmul weights; causal attention forward 2 matmuls over
    the keys a query sees (seq / 2 in a full layer, at most the window in
    a window layer), backward twice that. No cell trains this model."""
    nq, d = body["num_attention_heads"], body["head_dim"]
    kinds = body["layer_types"][:body["num_hidden_layers"]]
    keys = sum(min(seq / 2, body["sliding_window"])
               if k == "sliding_attention" else seq / 2 for k in kinds)
    return 6.0 * matmul_params_active(body) + 12.0 * nq * d * keys


# -- kernels: per CALL, on one chip -----------------------------------------
def _tick_means(body) -> Dict[str, float]:
    return body["deployment"]["tick_means"]


def grouped_matmul(body, shapes):
    """One megablox gmm call of one serving tick over the rows of the HELD
    experts, averaged over the two shapes it is called with (rows x H x
    2F and rows x F x H): the tick's live rows (`tick_means.live_rows`:
    stepped lanes + live chunk rows) x the held picks a row, and the
    weights of the held experts SOME live row picked: all 16 in a tick a
    chunk rides (`chunk_ride_share` of the ticks, ~250 rows), about six in
    a tick that steps the lanes alone (a row picks a given expert with
    probability top-k / published; the kernel visits no expert without
    rows). Memory-bound by these counts: a row an expert-pick against 50M
    weights an expert."""
    H, F = body["hidden_size"], body["intermediate_size"]
    m = _tick_means(body)
    ride = m["chunk_ride_share"]
    lanes = m["live_rows"] - m["chunk_rows"]
    missed = 1.0 - body["num_experts_per_tok"] / _published_experts(body)
    touched = body["num_experts"] * (
        ride * (1.0 - missed ** (lanes + m["chunk_rows"] / ride))
        + (1.0 - ride) * (1.0 - missed ** lanes))
    rows = m["live_rows"] * _held_picks(body)
    ops = 2.0 * rows * H * 1.5 * F
    byts = BF16 * (rows * (H + 1.5 * F) + touched * H * 1.5 * F)
    return {"ops": ops, "bytes": byts}


def chunk_attention(body, shapes):
    """One call of `chunk_attention` (ops/ragged_paged_attention.py): one
    layer's attention of the tick's prefill chunk over its own lane,
    averaged over the period's layers (3 window : 1 full). The chunk's
    live rows (`tick_means.chunk_rows`) each see the keys their band
    holds: `tick_means.chunk_keys_window` / `chunk_keys_full`, the mean
    over the traffic's chunks of min(position + 1, window) and of
    position + 1; two matmuls of head_dim a query head a key. Bytes: the
    keys and values a chunk's band spans, once a k/v head
    (`chunk_span_window` / `chunk_span_full` rows), and q and the output
    once. Compute-bound by these counts."""
    nq, nkv, d = (body["num_attention_heads"], body["num_key_value_heads"],
                  body["head_dim"])
    m = _tick_means(body)
    kinds = body["layer_types"][:body["num_hidden_layers"]]
    n_win = sum(k == "sliding_attention" for k in kinds)
    share = n_win / len(kinds)
    keys = share * m["chunk_keys_window"] + (1 - share) * m["chunk_keys_full"]
    span = share * m["chunk_span_window"] + (1 - share) * m["chunk_span_full"]
    ops = 4.0 * m["chunk_rows"] * nq * d * keys
    byts = BF16 * (2.0 * span * nkv * d + 2.0 * m["chunk_rows"] * nq * d)
    return {"ops": ops, "bytes": byts}


KERNEL_FNS = {
    "grouped_matmul": grouped_matmul,
    "chunk_attention": chunk_attention,
}
