"""jit'd public wrapper: robust aggregation over *pytrees* of client
updates. Flattens every (C, ...) leaf into one (C, N) matrix, pads N to
the kernel block, runs the Pallas kernel, and unflattens.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.backend import resolve_interpret
from repro.kernels.robust_agg import robust_agg_fwd


@functools.partial(jax.jit,
                   static_argnames=("mode", "trim_frac", "blk", "interpret"))
def robust_aggregate_tree(updates, mask, *, mode="trimmed", trim_frac=0.2,
                          blk=2048, interpret=None):
    """updates: pytree of (C, ...) leaves; mask: (C,) -> pytree of (...)."""
    leaves, treedef = jax.tree_util.tree_flatten(updates)
    C = leaves[0].shape[0]
    sizes = [int(l.size // C) for l in leaves]
    flat = jnp.concatenate(
        [l.reshape(C, -1).astype(jnp.float32) for l in leaves], axis=1)
    N = flat.shape[1]
    blk = min(blk, max(128, N))
    pad = (-N) % blk
    if pad:
        flat = jnp.pad(flat, ((0, 0), (0, pad)))
    interpret = resolve_interpret(interpret)
    agg = robust_agg_fwd(flat, mask.astype(jnp.float32), mode=mode,
                         trim_frac=trim_frac, blk=blk, interpret=interpret)
    agg = agg[:N]
    out, off = [], 0
    for l, n in zip(leaves, sizes):
        out.append(agg[off:off + n].reshape(l.shape[1:]).astype(l.dtype))
        off += n
    return jax.tree_util.tree_unflatten(treedef, out)


def robust_aggregate_tree_ref(updates, mask, *, mode="trimmed",
                              trim_frac=0.2):
    """Oracle with the same pytree contract."""
    if mode == "trimmed":
        from repro.core.aggregation import trimmed_mean
        return trimmed_mean(updates, mask, trim_frac)
    from repro.core.aggregation import median
    return median(updates, mask)
