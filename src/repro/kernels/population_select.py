"""O(M) Gumbel-top-d population selection (ROADMAP item 1).

Sampling a cohort of d clients without replacement with probability
proportional to per-client weights is top-d of ``log w + Gumbel noise``
(Efraimidis-Spirakis).  The dense route — ``argsort`` of all M perturbed
keys — is O(M log M) with a full sorted-permutation materialisation,
which is exactly the wrong shape for a million-client registry sampling
a 64-client cohort.

This module implements the selection as a two-stage SEGMENTED REDUCTION,
O(M) streaming + O((M/blk) * d) merge:

  stage 1   the population streams in (blk,)-blocks; each block reduces
            to its local top-d candidates (values + global indices).
            Two interchangeable engines:
              * ``segmented`` — XLA: reshape to (M/blk, blk) and a
                batched ``lax.top_k`` per segment (the production path
                on every backend);
              * ``pallas``   — a blocked Pallas kernel, one grid step
                per segment, extracting the block top-d in VMEM by
                iterative max-and-mask (d tiny vs blk, so the extraction
                is O(blk * d) flops against one (blk,) DMA — validated
                in interpret mode on CPU like the other kernels in this
                package).
  stage 2   one ``lax.top_k`` over the (M/blk) * d surviving candidates
            — negligible next to the stream.

Every engine returns the same index set in the same (descending-key)
order — Gumbel keys are ties-free almost surely — so the round drivers
can swap engines without breaking scan==python bit parity, and the
``population_select/*`` entries of bench_kernels record all three walls
at M in {1e4, 1e5, 1e6}.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.backend import resolve_interpret

METHODS = ("argsort", "segmented", "pallas")


def _pad_neg_inf(g, blk):
    m = g.shape[0]
    pad = (-m) % blk
    if pad:
        g = jnp.concatenate([g, jnp.full((pad,), -jnp.inf, g.dtype)])
    return g, m + pad


# ---------------------------------------------------------------------------
# dense baseline: full argsort (the pre-PR behavior, kept for the bench)
# ---------------------------------------------------------------------------
def topd_argsort(g, d):
    """O(M log M) full sort baseline."""
    return jnp.argsort(-g)[:d].astype(jnp.int32)


# ---------------------------------------------------------------------------
# segmented XLA reduction
# ---------------------------------------------------------------------------
def topd_segmented(g, d, *, blk=4096):
    """Blocked two-stage top-d: per-segment ``lax.top_k`` then one merge."""
    blk = max(int(blk), d)
    g, mp = _pad_neg_inf(g.astype(jnp.float32), blk)
    nb = mp // blk
    seg = g.reshape(nb, blk)
    v, i = jax.lax.top_k(seg, d)                       # (nb, d) each
    gi = (i + (jnp.arange(nb) * blk)[:, None]).astype(jnp.int32)
    _, j = jax.lax.top_k(v.reshape(-1), d)
    return gi.reshape(-1)[j]


# ---------------------------------------------------------------------------
# Pallas blocked kernel
# ---------------------------------------------------------------------------
def _block_topd_body(x_ref, v_ref, i_ref, *, d, blk):
    """One grid step = one (blk,) population segment: extract the block's
    top-d by iterative max-and-mask entirely in VMEM, emit (d,) values +
    GLOBAL indices.  The (C,)-style running-accumulator layout of the
    robust pipeline is deliberately avoided: per-block candidates keep
    the kernel associative (a segmented reduction), so the merge can run
    anywhere and the grid steps carry no cross-step state.  Every step
    is whole-vector work (max, first-index-of-max by a min over a lane
    iota, a masked write), which is what the TPU vector unit lowers."""
    x = x_ref[...].astype(jnp.float32)                    # (1, blk)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, blk), 1)
    slot = jax.lax.broadcasted_iota(jnp.int32, (1, d), 1)

    def step(k, carry):
        x, vs, ids = carry
        val = jnp.max(x)
        a = jnp.min(jnp.where(x == val, lane, blk))
        x = jnp.where(lane == a, -jnp.inf, x)
        vs = jnp.where(slot == k, val, vs)
        ids = jnp.where(slot == k, a, ids)
        return x, vs, ids

    _, vs, ids = jax.lax.fori_loop(
        0, d, step, (x, jnp.full((1, d), -jnp.inf, jnp.float32),
                     jnp.zeros((1, d), jnp.int32)))
    v_ref[0] = vs
    i_ref[0] = ids + pl.program_id(0) * blk


def topd_pallas(g, d, *, blk=4096, interpret=None):
    """Stage-1 candidates from the blocked Pallas kernel, stage-2 merge
    in XLA.  Each step's (d,) candidates are a (1, d) row of its own
    (nb, 1, d) output slab, a whole trailing tile on the TPU."""
    interpret = resolve_interpret(interpret)
    blk = max(int(blk), d)
    g, mp = _pad_neg_inf(g.astype(jnp.float32), blk)
    nb = mp // blk
    v, gi = pl.pallas_call(
        functools.partial(_block_topd_body, d=d, blk=blk),
        grid=(nb,),
        in_specs=[pl.BlockSpec((1, blk), lambda i: (0, i))],
        out_specs=[pl.BlockSpec((1, 1, d), lambda i: (i, 0, 0)),
                   pl.BlockSpec((1, 1, d), lambda i: (i, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((nb, 1, d), jnp.float32),
                   jax.ShapeDtypeStruct((nb, 1, d), jnp.int32)],
        interpret=interpret,
    )(g.reshape(1, mp))
    _, j = jax.lax.top_k(v.reshape(-1), d)
    return gi.reshape(-1)[j]


# ---------------------------------------------------------------------------
# dispatcher
# ---------------------------------------------------------------------------
def topd(g, d, *, method="segmented", blk=4096):
    d = int(d)
    if d >= g.shape[0]:
        # degenerate cohort >= population: every client, by key order
        return jnp.argsort(-g).astype(jnp.int32)[:d]
    if method == "argsort":
        return topd_argsort(g, d)
    if method == "segmented":
        return topd_segmented(g, d, blk=blk)
    if method == "pallas":
        return topd_pallas(g, d, blk=blk)
    raise ValueError(f"unknown top-d method {method!r}; known: {METHODS}")


def gumbel_topd(logw, d, rng, *, method="segmented", blk=4096):
    """Without-replacement ∝-weights cohort sample: top-d of the
    Gumbel-perturbed log weights.  (d,) int32 population indices."""
    g = logw.astype(jnp.float32) + jax.random.gumbel(
        rng, logw.shape, jnp.float32)
    return topd(g, d, method=method, blk=blk)
