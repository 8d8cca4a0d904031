"""Work of granite-moe-1b-a400m serving, from shapes alone.

Parameters: the embedding (tied to the LM head), per layer the q/k/v/o
projections, the router and ``n_experts`` SwiGLU experts.  A token
activates the attention projections, the router, ``top_k`` experts and,
where logits are required, the LM head.  Attention operations are counted
per key a query attends (QK^T and PV, 2 x 2 x heads x head_dim each).  KV
bytes are counted at bfloat16, the precision of the values the model
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


def total_params(c, padded_vocab=None):
    """All parameters as the program holds them (embedding rows padded to
    ``padded_vocab`` where given), norm scales included."""
    d, L = c["d_model"], c["n_layers"]
    layer = (_attn_params(c) + d * c["n_experts"]
             + c["n_experts"] * expert_params(c) + 2 * d)
    return L * layer + (padded_vocab or c["vocab_size"]) * d + d


def active_params(c):
    """Parameters one token's forward multiplies with, LM head included."""
    per_layer = (_attn_params(c) + c["d_model"] * c["n_experts"]
                 + c["top_k"] * expert_params(c))
    return c["n_layers"] * per_layer + head_params(c)


def attn_flops_per_key(c):
    return 4 * c["n_layers"] * c["n_heads"] * c["head_dim"]


def kv_bytes_per_row(c, itemsize=2):
    """K and V of one cached token over all layers."""
    return 2 * c["n_layers"] * c["n_kv_heads"] * c["head_dim"] * itemsize


def serve_flops(c, w):
    """Operations the window's tokens require: prompts (LM head at the
    last position only, causal attention over real tokens) and decode
    steps (one token per active slot, attention over its cached rows)."""
    act, head = active_params(c), head_params(c)
    prompt = (2 * (act - head) * w["prompt_tokens"] + 2 * head * w["admits"]
              + attn_flops_per_key(c) * w["prompt_pairs"])
    decode = (2 * act * w["decode_tokens"]
              + attn_flops_per_key(c) * w["decode_rows"])
    return prompt + decode
