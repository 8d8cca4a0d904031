"""Work of mellum2-12b-a2.5b serving, from shapes alone.

Parameters: the embedding and the untied LM head, per layer the q/k/v/o
projections, the router and ``n_experts`` SwiGLU experts.  A token
activates the attention projections, the router, ``top_k`` experts and,
where logits are required, the LM head.  Attention operations are counted
per key a query attends (QK^T and PV, 2 x 2 x heads x head_dim each), with
the rows a window layer attends clipped to its window: the window's counts
come from the engine's ``decode_rows_window`` and ``prompt_pairs_window``.
KV bytes are counted at bfloat16, the precision of the values the model
computes, whatever the pool stores.
"""
from __future__ import annotations


def _attn_params(c):
    d, hq, hkv, dh = c["d_model"], c["n_heads"], c["n_kv_heads"], c["head_dim"]
    return d * hq * dh * 2 + d * hkv * dh * 2


def expert_params(c):
    return 3 * c["d_model"] * c["d_ff"]


def head_params(c):
    return c["d_model"] * c["vocab_size"]


def layers_of(c, kind):
    """Layers of one attention kind (``sliding_attention``/``full_attention``)."""
    period = c["attention_period"]
    return period.count(kind) * (c["n_layers"] // len(period))


def total_params(c, padded_vocab=None):
    """All parameters as the program holds them (embedding and head rows
    padded to ``padded_vocab`` where given), norm scales included."""
    d, L = c["d_model"], c["n_layers"]
    layer = (_attn_params(c) + d * c["n_experts"]
             + c["n_experts"] * expert_params(c) + 2 * d)
    return L * layer + 2 * (padded_vocab or c["vocab_size"]) * d + d


def active_params(c):
    """Parameters one token's forward multiplies with, LM head included."""
    per_layer = (_attn_params(c) + c["d_model"] * c["n_experts"]
                 + c["top_k"] * expert_params(c))
    return c["n_layers"] * per_layer + head_params(c)


def attn_flops_per_key(c, kind):
    """Attention operations per (query, key) pair over the layers of one
    kind."""
    return 4 * layers_of(c, kind) * c["n_heads"] * c["head_dim"]


def kv_bytes_per_row(c, kind, itemsize=2):
    """K and V of one cached token over the layers of one kind."""
    return 2 * layers_of(c, kind) * c["n_kv_heads"] * c["head_dim"] * itemsize


def serve_flops(c, w):
    """Operations the window's tokens require: prompts (LM head at the
    last position only, causal attention over real tokens, window-clipped
    on the sliding layers) and decode steps (one token per active slot,
    attention over its cached rows, window-clipped on the sliding
    layers)."""
    act, head = active_params(c), head_params(c)
    full = attn_flops_per_key(c, "full_attention")
    win = attn_flops_per_key(c, "sliding_attention")
    prompt = (2 * (act - head) * w["prompt_tokens"] + 2 * head * w["admits"]
              + full * w["prompt_pairs"] + win * w["prompt_pairs_window"])
    decode = (2 * act * w["decode_tokens"] + full * w["decode_rows_full"]
              + win * w["decode_rows_window"])
    return prompt + decode
