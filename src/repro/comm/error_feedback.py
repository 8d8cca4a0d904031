"""Error-feedback (EF) residual state for the compressed uplink.

Biased codecs (quantization, top-k) drop part of every update; EF
[Seide et al. 2014; Karimireddy et al. 2019 "EF-SGD"] keeps the dropped
part as a per-client residual and re-injects it into the NEXT round's
update before encoding:

    target_t   = update_t + residual_{t-1}
    wire_t     = encode(target_t)
    residual_t = target_t - decode(wire_t)

so the compression error telescopes instead of accumulating — the sum of
decoded updates tracks the sum of true updates to within one residual.
The residual lives CLIENT-side (each client knows exactly what it sent),
so it adds no wire traffic; in the simulation it is a (K, ...) pytree
carried through the ``ScanDriver`` donated carry as ``FedState.ef`` /
``PodFedState.ef`` — zero host round-trips, updated in place.

``compress`` is the one-call client boundary: EF inject -> encode ->
decode -> residual update.  The decode it returns is what a dense-path
server would aggregate; an int8 server aggregates the ENCODED form
directly (bit-identical — the ``Int8`` loader of
``kernels/robust_pipeline.py``) and still uses the same residual.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def init(updates_like):
    """Zero residual state matching a (K, ...) update pytree (arrays or
    ShapeDtypeStructs)."""
    return jax.tree_util.tree_map(
        lambda l: jnp.zeros(l.shape, l.dtype), updates_like)


def compress(codec, updates, residual=None, rng=None):
    """One client->server boundary crossing.

    Returns ``(enc, dec, new_residual)``: the encoded wire pytree, its
    decode (what the server's dense path aggregates), and the updated
    EF residual (``None`` in, ``None`` out = EF disabled)."""
    if residual is not None:
        target = jax.tree_util.tree_map(
            lambda u, r: u + r.astype(u.dtype), updates, residual)
    else:
        target = updates
    enc = codec.encode_tree(target, rng=rng)
    dec = codec.decode_tree(enc, updates)
    if residual is None:
        return enc, dec, None
    new_residual = jax.tree_util.tree_map(
        lambda t, d: (t - d).astype(t.dtype), target, dec)
    return enc, dec, new_residual
