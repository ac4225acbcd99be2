"""Work counts of the `mimo_v2` architecture: operations and bytes the
algorithm needs, from shapes alone. Kept with the benchmark: a PR that
claims a gain cannot change how work is counted. Imports nothing of the
program.

Every function takes the configuration file's body (source keys) and
returns plain numbers. A SERVING cell hands `shapes = {}`, so a kernel of
the serving tick counts from the file's own `deployment` and `program`
groups: the tick has one shape whatever the traffic, and of its work only
what the traffic's live rows need is counted (`deployment.tick_means`,
measured once at the cell's rate).

Two kinds of attention layer (`hybrid_layer_pattern`: 0 full, 1 window)
with their own k/v heads (`num_key_value_heads` / `swa_num_key_value_heads`);
a head scores a key over `head_dim` columns and sums `v_head_dim` columns
of value: 2 x 192 + 2 x 128 = 640 operations a live key a query head; a
row of k and v is `head_dim + v_head_dim` columns of bf16 a k/v head (the
stored key's zero padding to 256 is no needed byte). A kernel is called
once an attention layer a tick, so a call is the mean over the stack's
layers of the two kinds.

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


def _kinds(body):
    """(window layers, full layers) of the layers this file runs."""
    kinds = body["hybrid_layer_pattern"][:body["num_hidden_layers"]]
    n_window = sum(1 for t in kinds if t)
    return n_window, len(kinds) - n_window


def _kv_heads(body):
    """(k/v heads of a window layer, of a full layer)."""
    return body["swa_num_key_value_heads"], body["num_key_value_heads"]


def _attn_params(body, n_kv: int) -> int:
    H, n = body["hidden_size"], body["num_attention_heads"]
    d, dv = body["head_dim"], body["v_head_dim"]
    return H * n * d + H * n_kv * (d + dv) + n * dv * H


def _attn_params_all(body) -> int:
    n_window, n_full = _kinds(body)
    kv_window, kv_full = _kv_heads(body)
    return (n_window * _attn_params(body, kv_window)
            + n_full * _attn_params(body, kv_full))


def _expert_params(body) -> int:
    return 3 * body["hidden_size"] * body["moe_intermediate_size"]


def _dense_params(body) -> int:
    return 3 * body["hidden_size"] * body["intermediate_size"]


def _layers(body):
    freq = body["moe_layer_freq"][:body["num_hidden_layers"]]
    moe = sum(1 for f in freq if f)
    return len(freq) - moe, moe


def matmul_params_active(body: Dict[str, Any]) -> float:
    """Weights a token is multiplied by ON THIS CHIP: attention's four
    projections at each layer's own k/v heads, the dense layer's SwiGLU,
    and in an expert layer the router (all published columns) and the
    routed experts at this chip's expected share of the token's picks (8
    picks x 16 / 256 held = half an expert); the LM head over the held
    vocabulary. The embedding lookup is no matmul."""
    H = body["hidden_size"]
    dense, moe = _layers(body)
    moe_layer = (H * _published_experts(body)
                 + _held_picks(body) * _expert_params(body))
    return (_attn_params_all(body) + dense * _dense_params(body)
            + moe * moe_layer + body["vocab_size"] * H)


def params_total(body: Dict[str, Any]) -> int:
    """Parameters this chip holds: per layer attention (a sink's logit a
    query head where the kind has one) and the block's two norms; the
    dense layer's SwiGLU or router + selection bias + held experts;
    embedding, untied head, final norm."""
    H, n = body["hidden_size"], body["num_attention_heads"]
    dense, moe = _layers(body)
    n_window, n_full = _kinds(body)
    sinks = n * (n_window * bool(body["add_swa_attention_sink_bias"])
                 + n_full * bool(body["add_full_attention_sink_bias"]))
    E = _published_experts(body)
    moe_layer = H * E + E + body["n_routed_experts"] * _expert_params(body)
    return (_attn_params_all(body) + sinks
            + body["num_hidden_layers"] * 2 * H
            + dense * _dense_params(body) + moe * moe_layer
            + 2 * body["vocab_size"] * H + H)


def train_flops_per_token(body: Dict[str, Any], seq: int) -> float:
    """6 x active matmul weights; causal attention forward 2 matmuls over
    the keys a query sees (seq / 2 in a full layer, at most the window in
    a window layer) of head_dim and v_head_dim columns a head, backward
    twice that. No cell trains this model."""
    n = body["num_attention_heads"]
    cols = body["head_dim"] + body["v_head_dim"]
    n_window, n_full = _kinds(body)
    keys = (n_window * min(seq / 2, body["sliding_window"])
            + n_full * seq / 2)
    return 6.0 * matmul_params_active(body) + 6.0 * n * cols * keys


# -- kernels: per CALL, on one chip -----------------------------------------
def _tick_means(body) -> Dict[str, float]:
    return body["deployment"]["tick_means"]


def _by_kind(body, window: float, full: float) -> float:
    """The mean over the stack's attention layers of a per-call count
    that differs by kind."""
    n_window, n_full = _kinds(body)
    return (n_window * window + n_full * full) / (n_window + n_full)


def grouped_matmul(body, shapes):
    """One megablox gmm call of one serving tick over the rows of the HELD
    experts, averaged over the two shapes it is called with (rows x H x
    2F and rows x F x H): the tick's live rows (`tick_means.live_rows`:
    stepped lanes + live chunk rows) x the held picks a row, and the
    weights of the held experts SOME live row picked: all 16 in a tick a
    chunk rides (`chunk_ride_share` of the ticks), most in a tick that
    steps some sixty lanes alone (a row picks a given expert with
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
    """One call of `chunk_attention` (ops/ragged_paged_attention.py): one
    layer's attention of the tick's prefill chunk over its own lane,
    averaged over the stack's layers by kind. The chunk's live rows
    (`tick_means.chunk_rows`, a mean over ALL ticks) each see the keys
    their band holds: `chunk_keys_window` / `chunk_keys_full`, the mean
    over the traffic's chunk rows of min(position + 1, window) and of
    position + 1; a score over head_dim and a sum over v_head_dim columns
    a query head a key. Bytes: the rows of k and v a riding chunk's band
    spans, once a k/v head of the kind (`chunk_span_window` /
    `chunk_span_full` rows, each already times the share of ticks a chunk
    rides), and q and the output once. Compute-bound by these counts."""
    n = body["num_attention_heads"]
    d, dv = body["head_dim"], body["v_head_dim"]
    kv_window, kv_full = _kv_heads(body)
    m = _tick_means(body)
    keys = _by_kind(body, m["chunk_keys_window"], m["chunk_keys_full"])
    span = _by_kind(body, m["chunk_span_window"] * kv_window,
                    m["chunk_span_full"] * kv_full)
    ops = 2.0 * (d + dv) * m["chunk_rows"] * n * keys
    byts = BF16 * (d + dv) * (span + m["chunk_rows"] * n)
    return {"ops": ops, "bytes": byts}


def lane_attention(body, shapes):
    """One call of `lane_attention`: one layer's attention of the lanes a
    tick steps (`tick_means.lanes_stepped`), each query over the keys it
    sees (`lane_keys_window`: the mean of min(length, window) over stepped
    lanes; `lane_keys_full`: their mean length) under every query head.
    Bytes: the rows of k and v in the key blocks the kernel fetches for
    them (`lane_rows_window` / `lane_rows_full`: live blocks a call x a
    block's rows, from the kernel's own plan run on the host,
    `serve_kv_*_bytes_read_total` less the chunk's), once a k/v head of
    the kind at head_dim + v_head_dim columns, and the queries and outputs
    once: a block is the least a lane's query can be served from (a ring
    of four pages is one block). Memory-bound by these counts."""
    n = body["num_attention_heads"]
    d, dv = body["head_dim"], body["v_head_dim"]
    kv_window, kv_full = _kv_heads(body)
    m = _tick_means(body)
    keys = m["lanes_stepped"] * _by_kind(
        body, m["lane_keys_window"], m["lane_keys_full"])
    rows = _by_kind(body, m["lane_rows_window"] * kv_window,
                    m["lane_rows_full"] * kv_full)
    ops = 2.0 * (d + dv) * n * keys
    byts = BF16 * (d + dv) * (rows + m["lanes_stepped"] * n)
    return {"ops": ops, "bytes": byts}


KERNEL_FNS = {
    "grouped_matmul": grouped_matmul,
    "chunk_attention": chunk_attention,
    "lane_attention": lane_attention,
}
