"""Work of the FedFiTS round on the paper's CNN, from shapes alone.

``round_flops`` counts the operations a round requires: every client's
local epochs (forward and backward, 3x the forward), the fitness
evaluations of the global and the local model on each client's eval rows,
and the server's evaluation of the test and trigger sets.  The attacker,
codec, election and aggregation are not counted: they move bytes and do
next to no arithmetic.  ``agg_least_bytes`` is the least traffic of the
int8 aggregation: the codes and per-block scales read once and the f32
result written once.
"""
from __future__ import annotations

import math


def leaf_sizes(c):
    """Parameter count of each leaf, in the model's tree order."""
    out, cin, size = [], c["in_channels"], c["image_size"]
    for i in range(c["n_layers"]):
        cout = c["d_model"] * 2 ** i
        out += [9 * cin * cout, cout]
        cin, size = cout, (size + 1) // 2
    feat = size * size * cin
    out += [feat * c["d_ff"], c["d_ff"],
            c["d_ff"] * c["vocab_size"], c["vocab_size"]]
    return out


def n_params(c):
    return sum(leaf_sizes(c))


def forward_flops(c):
    """Multiply-adds x 2 of one image's forward pass (bias and ReLU not
    counted)."""
    f, cin, size = 0, c["in_channels"], c["image_size"]
    for i in range(c["n_layers"]):
        cout = c["d_model"] * 2 ** i
        size = (size + 1) // 2
        f += 2 * size * size * cout * 9 * cin
        cin = cout
    feat = size * size * cin
    return f + 2 * feat * c["d_ff"] + 2 * c["d_ff"] * c["vocab_size"]


def round_flops(c, traffic):
    fed, p = traffic["federation"], traffic["protocol"]
    k, fwd = p["n_clients"], forward_flops(c)
    local = k * p["local_epochs"] * fed["batch"] * 3 * fwd
    fitness = k * 2 * fed["eval_batch"] * fwd
    server = 2 * fed["holdout"] * fwd
    return local + fitness + server


def agg_least_bytes(c, traffic):
    """int8 codes (1 B a coordinate) and f32 scales (one per ``qblk``
    coordinates of each leaf) of every client, plus the f32 aggregate."""
    p = traffic["protocol"]
    k, qblk = p["n_clients"], p["qblk"]
    sizes = leaf_sizes(c)
    codes = k * sum(sizes)
    scales = 4 * k * sum(math.ceil(n / qblk) for n in sizes)
    return codes + scales + 4 * sum(sizes)
