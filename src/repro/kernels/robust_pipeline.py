"""Fused two-pass robust-aggregation pipeline — Pallas TPU engine.

The trust-aware robust aggregation of paper Eq. 11 (median reference ->
gradient-cosine outlier gate -> trimmed-mean / median / weighted-mean /
Krum) as TWO streaming passes over the cohort-batched client update
matrix, instead of the ~4+ independent sort-based XLA passes of the
reference path in ``core/aggregation.py``:

  pass 1   streams (C, blk) blocks once.  Per block it computes the
           coordinate-median reference with the O(C^2) stable-rank
           network (shared with kernels/robust_agg.py) AND accumulates
           the per-client cosine partials — dot(x_i, ref), ||x_i||^2,
           ||ref||^2 — into (C,) VMEM accumulators that live across the
           whole N sweep (init at block 0, revisited every block).  The
           median itself stays in VMEM: only the O(C) partials reach HBM.

  gate     resolved on-device between the passes from the (G, C)
           accumulators: O(G*C) jnp scalars, no host round-trip, no
           re-read of the update matrix.

  pass 2   streams the blocks once more, applying the gated mask (and
           the caller's trust weights for the mean modes) to emit the
           final aggregated row: trimmed mean / median via the rank
           network, or the normalised weighted mean.

  krum     an extra blocked pairwise-distance kernel accumulates the
           (C, C) Gram matrix in one more streaming pass; the O(C^2)
           Krum scoring runs on-device in jnp and the winners are
           averaged by pass 2 in ``mean`` mode.

The leading G (cohort) grid axis batches every slot of the two-stage
scheme in ONE ``pallas_call`` — the reference's per-cohort Python loop
becomes a grid dimension.

Leaf streaming (this PR): multi-leaf pytrees no longer flatten through a
(C, N) ``concatenate``.  A *segment-offset table* (static, derived from
the leaf sizes and ``blk``) assigns each leaf a contiguous run of grid
steps; both passes are ONE ``pallas_call`` whose per-leaf BlockSpec
index maps clamp into the leaf's segment, so each leaf block is DMA'd
exactly once and the (C,) dot/norm/gate accumulators are SHARED across
all segments in VMEM.  Leaves stream in place (a reshape view, no copy);
ragged tails are masked in-kernel, accumulation is fp32 throughout, and
each leaf is cast back to its own dtype exactly once — by the pass-2
output write.  The 2-pass HBM roofline is therefore end-to-end: no
flatten concatenate, no unflatten slice-copy.  (The PR-1 flatten path is
kept below as ``*_flat`` — the bench baseline and a parity oracle.)

Distribution hooks: ``fused_pipeline_leafwise`` takes ``axis_name`` +
``leaf_scale`` so ``aggregation.aggregate_sharded`` can run the passes
shard-locally under ``shard_map`` — only the (C,) cosine partials (and
Krum's Gram matrix) cross devices, in one ``psum``.

HBM traffic: the reference path reads (and for sorts, re-writes) the
(C, N) matrix >= 4 times; the fused pipeline reads it exactly twice
(three times for Krum) and writes only the (1, N) output.  See
``benchmarks/bench_kernels.py::robust_pipeline_roofline``.

Layout note: the (C,)-shaped accumulators use C as the minor dimension;
on real TPUs C < 128 relies on Mosaic's small-array padding.  The
pipeline is validated in interpret mode on CPU (the repo's test
substrate); ``auto_blk`` keeps grids short there and VMEM-sized on TPU.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import on_tpu, resolve_interpret
from repro.kernels.robust_agg import _BIG, stable_ranks


# ---------------------------------------------------------------------------
# segment-offset table + block autotune
# ---------------------------------------------------------------------------

class _Seg(NamedTuple):
    """One leaf's contiguous run of grid steps: steps [start, start +
    nblocks) stream its (C, n) matrix in (C, blk) blocks.  ``blk`` is
    per-leaf: a leaf narrower than the pipeline block gets a 128-aligned
    block of its own width, so small norm/bias leaves don't pay a full
    rank-network block of padding."""
    start: int
    nblocks: int
    n: int
    blk: int


def make_segments(sizes, blk):
    """Static segment-offset table mapping grid steps to (leaf, block).

    Leaves that need several blocks get sequential step runs; leaves that
    fit ONE block all share step 0 (their block index is constant, so
    they cost no extra DMA and no extra grid steps — on a single-block
    tree the whole pass collapses to one step per cohort).  Segments may
    therefore overlap: a step computes every leaf whose run covers it.
    """
    segs, start = [], 0
    for n in sizes:
        b = min(blk, _round_up(int(n), 128))
        nb = max(1, -(-int(n) // b))
        if nb == 1:
            segs.append(_Seg(0, 1, int(n), b))
        else:
            segs.append(_Seg(start, nb, int(n), b))
            start += nb
    return tuple(segs), max(1, start)


def _round_up(x, m):
    return -(-x // m) * m


# Scoped VMEM a Mosaic kernel may use on the TPU v5e (the compiler's
# default limit), and the bytes each lane of a leaf block keeps live in
# the pass-1/pass-2 bodies: the rank network's two (C, C) f32 compare
# planes per coordinate (8 C^2), the double-buffered input block plus
# its f32 working copies (24 C).  Fitted to what the v5e compiler
# accepts (tests/test_tpu_compile.py): one C=16 leaf compiles at
# blk=8192 and not at 10240, one C=32 leaf at 2048 and not at 3072.
TPU_VMEM_BYTES = 16 * 2 ** 20


def _vmem_per_lane(c):
    return 8 * c * c + 24 * c


def _body_lanes(sizes, blk):
    """Lanes of every leaf block the kernel body holds at ``blk``: each
    leaf's branch is compiled into the one body, so their stacks add."""
    return sum(min(blk, _round_up(int(n), 128)) for n in sizes)


def auto_blk(c, sizes, *, backend=None):
    """Pick the streaming block size from the backend + memory budget.

    CPU interpret: the rank network materialises (C, C, blk) f32
    intermediates, so blocks are sized to keep that working set inside
    the last-level cache (~16 MB — measured 2x wall time when it spills)
    while staying large enough to amortise the per-step interpreter
    overhead: clamp to [2048, 32768] lanes, and never wider than the
    longest leaf.  TPU: the widest multiple of 128 (at most 8192) for
    which every leaf block of the body, at ``_vmem_per_lane`` bytes per
    lane, fits ``TPU_VMEM_BYTES``; 128 when even that does not fit.
    """
    if backend is None:
        backend = "tpu" if on_tpu() else "cpu"
    c = max(c, 8)
    if backend == "tpu":
        lanes = TPU_VMEM_BYTES // _vmem_per_lane(c)
        blk = min(8192, _round_up(max(sizes), 128))
        while blk > 128 and _body_lanes(sizes, blk) > lanes:
            blk -= 128
        return int(blk)
    budget = 16 * 2 ** 20
    blk = budget // (4 * c ** 2)
    blk = max(2048, min(_round_up(blk, 128), 1 << 15))
    return int(min(blk, _round_up(max(sizes), 128)))


def _seg_index_map(seg):
    """Clamped per-leaf BlockSpec index map: outside the leaf's segment the
    block index pins to the segment edge, so no re-DMA happens on the
    off-segment steps (the scalar-prefetch refs arrive as trailing args)."""
    return lambda g, i, *_: (g, 0, jnp.clip(i - seg.start, 0,
                                            seg.nblocks - 1))


def _foreach_active_leaf(segs, total, i, fn):
    """Run ``fn(l, seg)`` for every leaf whose segment covers step ``i``.
    A segment spanning the WHOLE grid (the collapsed single-step layout
    ``make_segments`` emits on short grids) runs unconditionally — the
    ``pl.when`` cond would otherwise fence XLA's fusion of the rank
    network in interpret mode (~30% wall time on CPU)."""
    for l, seg in enumerate(segs):
        if seg.start == 0 and seg.nblocks >= total:
            fn(l, seg)
        else:
            pl.when((i >= seg.start) & (i < seg.start + seg.nblocks))(
                functools.partial(fn, l, seg))


def _leaf_block(x_refs, l, seg, i):
    """Load leaf ``l``'s current (C, seg.blk) block in fp32 with the
    ragged tail masked to zero (OOB lanes of the overrunning last block
    carry unspecified values; ``where`` keeps them out of every
    accumulator).  ``i`` is the step index, read by the caller at kernel
    top level — ``pl.program_id`` inside a ``pl.when`` branch is not
    substituted by the interpreter."""
    x = x_refs[l][0].astype(jnp.float32)
    if seg.n % seg.blk:
        valid = seg.n - (i - seg.start) * seg.blk
        col = jax.lax.broadcasted_iota(jnp.int32, (1, seg.blk), 1)
        x = jnp.where(col < valid, x, 0.0)
    return x


# ---------------------------------------------------------------------------
# pass 1: median reference + cosine-gate partials
# ---------------------------------------------------------------------------

def _median_block(x, m, n, c):
    """Coordinate-median of an fp32 (C, blk) block via the rank network;
    stays in VMEM (consumed by the partials, recomputed by pass 2)."""
    xm = jnp.where(m > 0, x, _BIG)
    rank = stable_ranks(xm, c)
    lo = jnp.floor((n - 1.0) / 2.0)
    hi = jnp.ceil((n - 1.0) / 2.0)
    pick_lo = (rank == lo).astype(jnp.float32) * m
    pick_hi = (rank == hi).astype(jnp.float32) * m
    return 0.5 * ((x * pick_lo).sum(axis=0, keepdims=True)
                  + (x * pick_hi).sum(axis=0, keepdims=True))   # (1, blk)


def _pass1_body(n_ref, x_ref, mask_ref, dot_ref, sqn_ref, refsq_ref, *, c):
    g = pl.program_id(0)
    i = pl.program_id(1)
    x = x_ref[0].astype(jnp.float32)              # (C, blk)
    m = mask_ref[0].astype(jnp.float32)           # (C, 1)
    n = n_ref[g].astype(jnp.float32)
    med = _median_block(x, m, n, c)

    @pl.when(i == 0)
    def _init():
        dot_ref[...] = jnp.zeros_like(dot_ref)
        sqn_ref[...] = jnp.zeros_like(sqn_ref)
        refsq_ref[...] = jnp.zeros_like(refsq_ref)

    dot_ref[...] += (x * med).sum(axis=1)[None, :]
    sqn_ref[...] += (x * x).sum(axis=1)[None, :]
    refsq_ref[...] += (med * med).sum(axis=1, keepdims=True)


def cosine_gate_partials(x, mask, *, blk=4096, interpret=False):
    """x: (G, C, N) f32, mask: (G, C) 0/1 ->
    (dots (G, C), sqnorms (G, C), refsq (G, 1)) — the per-client cosine
    partials vs the coordinate-median reference, in one streaming read."""
    G, C, N = x.shape
    assert N % blk == 0, (N, blk)
    n_sel = mask.sum(axis=1).astype(jnp.float32)  # (G,)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(G, N // blk),
        in_specs=[
            pl.BlockSpec((1, C, blk), lambda g, i, n: (g, 0, i)),
            pl.BlockSpec((1, C, 1), lambda g, i, n: (g, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, C), lambda g, i, n: (g, 0)),
            pl.BlockSpec((1, C), lambda g, i, n: (g, 0)),
            pl.BlockSpec((1, 1), lambda g, i, n: (g, 0)),
        ],
    )
    dots, sqn, refsq = pl.pallas_call(
        functools.partial(_pass1_body, c=C),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((G, C), jnp.float32),
            jax.ShapeDtypeStruct((G, C), jnp.float32),
            jax.ShapeDtypeStruct((G, 1), jnp.float32),
        ],
        interpret=interpret,
    )(n_sel, x, mask.reshape(G, C, 1))
    return dots, sqn, refsq


def _pass1_leaf_body(n_ref, scale_ref, *refs, segs, total, c):
    L = len(segs)
    x_refs = refs[:L]
    mask_ref = refs[L]
    dot_ref, sqn_ref, refsq_ref = refs[L + 1:]
    g = pl.program_id(0)
    i = pl.program_id(1)
    m = mask_ref[0].astype(jnp.float32)           # (C, 1)
    n = n_ref[g].astype(jnp.float32)

    @pl.when(i == 0)
    def _init():
        dot_ref[...] = jnp.zeros_like(dot_ref)
        sqn_ref[...] = jnp.zeros_like(sqn_ref)
        refsq_ref[...] = jnp.zeros_like(refsq_ref)

    def accumulate(l, seg):
        x = _leaf_block(x_refs, l, seg, i)
        med = _median_block(x, m, n, c)
        s = scale_ref[l]
        dot_ref[...] += s * (x * med).sum(axis=1)[None, :]
        sqn_ref[...] += s * (x * x).sum(axis=1)[None, :]
        refsq_ref[...] += s * (med * med).sum(axis=1, keepdims=True)

    _foreach_active_leaf(segs, total, i, accumulate)


def cosine_gate_partials_leafwise(leaves, mask, *, blk, leaf_scale,
                                  interpret=False):
    """Segment-table pass 1: leaves [(G, C, n_l)] stream through ONE
    ``pallas_call`` sharing the (C,) accumulators across all segments.
    ``leaf_scale`` (L,) scales each leaf's contribution (1.0 everywhere
    off-mesh; under ``shard_map`` it de-duplicates replicated leaves
    before the cross-device psum)."""
    G, C = leaves[0].shape[:2]
    sizes = tuple(int(l.shape[-1]) for l in leaves)
    segs, total = make_segments(sizes, blk)
    n_sel = mask.sum(axis=1).astype(jnp.float32)  # (G,)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(G, total),
        in_specs=[pl.BlockSpec((1, C, seg.blk), _seg_index_map(seg))
                  for seg in segs]
        + [pl.BlockSpec((1, C, 1), lambda g, i, *_: (g, 0, 0))],
        out_specs=[
            pl.BlockSpec((1, C), lambda g, i, *_: (g, 0)),
            pl.BlockSpec((1, C), lambda g, i, *_: (g, 0)),
            pl.BlockSpec((1, 1), lambda g, i, *_: (g, 0)),
        ],
    )
    dots, sqn, refsq = pl.pallas_call(
        functools.partial(_pass1_leaf_body, segs=segs, total=total, c=C),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((G, C), jnp.float32),
            jax.ShapeDtypeStruct((G, C), jnp.float32),
            jax.ShapeDtypeStruct((G, 1), jnp.float32),
        ],
        interpret=interpret,
    )(n_sel, leaf_scale, *leaves, mask.reshape(G, C, 1))
    return dots, sqn, refsq


# ---------------------------------------------------------------------------
# pass 2: gated robust combine
# ---------------------------------------------------------------------------

def _combine_block(x, m, w, n, *, c, mode, trim_frac):
    """One (C, blk) -> (1, blk) gated combine in fp32."""
    if mode == "mean":
        return (x * w).sum(axis=0, keepdims=True)
    xm = jnp.where(m > 0, x, _BIG)
    rank = stable_ranks(xm, c)
    if mode == "trimmed":
        t = jnp.floor(trim_frac * n)
        keep = ((rank >= t) & (rank < n - t)).astype(jnp.float32) * m
        cnt = jnp.maximum(n - 2.0 * t, 1.0)
        return (x * keep).sum(axis=0, keepdims=True) / cnt
    # median
    lo = jnp.floor((n - 1.0) / 2.0)
    hi = jnp.ceil((n - 1.0) / 2.0)
    pick_lo = (rank == lo).astype(jnp.float32) * m
    pick_hi = (rank == hi).astype(jnp.float32) * m
    return 0.5 * ((x * pick_lo).sum(axis=0, keepdims=True)
                  + (x * pick_hi).sum(axis=0, keepdims=True))


def _pass2_body(n_ref, x_ref, m_ref, w_ref, o_ref, *, c, mode, trim_frac):
    g = pl.program_id(0)
    x = x_ref[0].astype(jnp.float32)              # (C, blk)
    m = m_ref[0].astype(jnp.float32)              # (C, 1)
    w = w_ref[0].astype(jnp.float32)              # (C, 1) pre-normalised
    n = n_ref[g].astype(jnp.float32)
    o_ref[0] = _combine_block(x, m, w, n, c=c, mode=mode,
                              trim_frac=trim_frac).astype(o_ref.dtype)


def gated_combine(x, gated_mask, weights, *, mode, trim_frac=0.2, blk=4096,
                  interpret=False):
    """x: (G, C, N); gated_mask: (G, C); weights: (G, C) (normalised,
    ``mean`` mode only) -> (G, N)."""
    G, C, N = x.shape
    assert N % blk == 0, (N, blk)
    n_sel = gated_mask.sum(axis=1).astype(jnp.float32)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(G, N // blk),
        in_specs=[
            pl.BlockSpec((1, C, blk), lambda g, i, n: (g, 0, i)),
            pl.BlockSpec((1, C, 1), lambda g, i, n: (g, 0, 0)),
            pl.BlockSpec((1, C, 1), lambda g, i, n: (g, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, blk), lambda g, i, n: (g, 0, i)),
    )
    out = pl.pallas_call(
        functools.partial(_pass2_body, c=C, mode=mode, trim_frac=trim_frac),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((G, 1, N), jnp.float32),
        interpret=interpret,
    )(n_sel, x, gated_mask.reshape(G, C, 1), weights.reshape(G, C, 1))
    return out[:, 0]


def _pass2_leaf_body(n_ref, *refs, segs, total, c, mode, trim_frac):
    L = len(segs)
    x_refs = refs[:L]
    m_ref, w_ref = refs[L], refs[L + 1]
    o_refs = refs[L + 2:]
    g = pl.program_id(0)
    i = pl.program_id(1)
    m = m_ref[0].astype(jnp.float32)              # (C, 1)
    w = w_ref[0].astype(jnp.float32)              # (C, 1)
    n = n_ref[g].astype(jnp.float32)

    def emit(l, seg):
        x = _leaf_block(x_refs, l, seg, i)
        o_refs[l][0] = _combine_block(
            x, m, w, n, c=c, mode=mode, trim_frac=trim_frac
        ).astype(o_refs[l].dtype)

    _foreach_active_leaf(segs, total, i, emit)


def gated_combine_leafwise(leaves, gated_mask, weights, *, mode,
                           trim_frac=0.2, blk, out_dtypes, interpret=False):
    """Segment-table pass 2: per-leaf (G, n_l) outputs, each written in its
    own ``out_dtypes[l]`` — the single fp32->leaf-dtype cast of the whole
    pipeline happens at this output write."""
    G, C = leaves[0].shape[:2]
    sizes = tuple(int(l.shape[-1]) for l in leaves)
    segs, total = make_segments(sizes, blk)
    n_sel = gated_mask.sum(axis=1).astype(jnp.float32)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(G, total),
        in_specs=[pl.BlockSpec((1, C, seg.blk), _seg_index_map(seg))
                  for seg in segs]
        + [pl.BlockSpec((1, C, 1), lambda g, i, *_: (g, 0, 0)),
           pl.BlockSpec((1, C, 1), lambda g, i, *_: (g, 0, 0))],
        out_specs=[pl.BlockSpec((1, 1, seg.blk), _seg_index_map(seg))
                   for seg in segs],
    )
    outs = pl.pallas_call(
        functools.partial(_pass2_leaf_body, segs=segs, total=total, c=C,
                          mode=mode, trim_frac=trim_frac),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((G, 1, seg.n), dt)
                   for seg, dt in zip(segs, out_dtypes)],
        interpret=interpret,
    )(n_sel, *leaves, gated_mask.reshape(G, C, 1), weights.reshape(G, C, 1))
    return [o[:, 0] for o in outs]


# ---------------------------------------------------------------------------
# blocked pairwise distances (Krum)
# ---------------------------------------------------------------------------

def _pairwise_body(x_ref, gram_ref, sqn_ref, *, c):
    i = pl.program_id(1)
    x = x_ref[0].astype(jnp.float32)              # (C, blk)

    @pl.when(i == 0)
    def _init():
        gram_ref[...] = jnp.zeros_like(gram_ref)
        sqn_ref[...] = jnp.zeros_like(sqn_ref)

    gram_ref[0] += jax.lax.dot_general(
        x, x, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    sqn_ref[...] += (x * x).sum(axis=1)[None, :]


def pairwise_sq_dists_blocked(x, mask, *, blk=4096, interpret=False):
    """Blocked (G, C, C) squared distances: streams N once, accumulating
    the Gram matrix and row norms; masked-out pairs pushed to +_BIG (same
    contract as ``aggregation.pairwise_sq_dists``)."""
    G, C, N = x.shape
    assert N % blk == 0, (N, blk)
    gram, sqn = pl.pallas_call(
        functools.partial(_pairwise_body, c=C),
        grid=(G, N // blk),
        in_specs=[pl.BlockSpec((1, C, blk), lambda g, i: (g, 0, i))],
        out_specs=[
            pl.BlockSpec((1, C, C), lambda g, i: (g, 0, 0)),
            pl.BlockSpec((1, C), lambda g, i: (g, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((G, C, C), jnp.float32),
            jax.ShapeDtypeStruct((G, C), jnp.float32),
        ],
        interpret=interpret,
    )(x)
    d = sqn[:, :, None] + sqn[:, None, :] - 2.0 * gram
    big = _BIG * (1.0 - mask[:, :, None] * mask[:, None, :])
    return jnp.maximum(d, 0.0) + big


def _pairwise_leaf_body(scale_ref, *refs, segs, total, c):
    L = len(segs)
    x_refs = refs[:L]
    gram_ref, sqn_ref = refs[L:]
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        gram_ref[...] = jnp.zeros_like(gram_ref)
        sqn_ref[...] = jnp.zeros_like(sqn_ref)

    def accumulate(l, seg):
        x = _leaf_block(x_refs, l, seg, i)
        s = scale_ref[l]
        gram_ref[0] += s * jax.lax.dot_general(
            x, x, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        sqn_ref[...] += s * (x * x).sum(axis=1)[None, :]

    _foreach_active_leaf(segs, total, i, accumulate)


def pairwise_sq_dists_leafwise(leaves, mask, *, blk, leaf_scale,
                               interpret=False, axis_name=None):
    """Segment-table Krum distance pass: Gram + row norms accumulate across
    all leaf segments in one streaming read; under ``shard_map`` the (C, C)
    Gram matrix (not the update matrix) is what crosses devices."""
    G, C = leaves[0].shape[:2]
    sizes = tuple(int(l.shape[-1]) for l in leaves)
    segs, total = make_segments(sizes, blk)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(G, total),
        in_specs=[pl.BlockSpec((1, C, seg.blk), _seg_index_map(seg))
                  for seg in segs],
        out_specs=[
            pl.BlockSpec((1, C, C), lambda g, i, *_: (g, 0, 0)),
            pl.BlockSpec((1, C), lambda g, i, *_: (g, 0)),
        ],
    )
    gram, sqn = pl.pallas_call(
        functools.partial(_pairwise_leaf_body, segs=segs, total=total, c=C),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((G, C, C), jnp.float32),
            jax.ShapeDtypeStruct((G, C), jnp.float32),
        ],
        interpret=interpret,
    )(leaf_scale, *leaves)
    if axis_name is not None:
        gram = jax.lax.psum(gram, axis_name)
        sqn = jax.lax.psum(sqn, axis_name)
    d = sqn[:, :, None] + sqn[:, None, :] - 2.0 * gram
    big = _BIG * (1.0 - mask[:, :, None] * mask[:, None, :])
    return jnp.maximum(d, 0.0) + big


def _krum_weights(d, mask, f, multi_m):
    """Krum selection weights from (G, C, C) distances; mirrors
    ``aggregation.krum`` (scores = sum of n-f-2 smallest distances,
    multi_m best averaged)."""
    G, C, _ = d.shape
    d = d + _BIG * jnp.eye(C)[None]               # exclude self
    n = mask.sum(axis=1, keepdims=True)           # (G, 1)
    closest = jnp.sort(d, axis=2)
    j = jnp.arange(C, dtype=jnp.float32)[None, None, :]
    take = jnp.maximum(n - f - 2, 1.0)[:, :, None]
    scores = jnp.where(j < take, closest, 0.0).sum(axis=2)    # (G, C)
    # inf (not _BIG) so a lone selected client (score _BIG + d, from
    # distances to masked peers) still outranks the excluded rows
    scores = jnp.where(mask > 0, scores, jnp.inf)
    pos = jnp.argsort(jnp.argsort(scores, axis=1), axis=1)
    # winners restricted to masked-in clients: an empty cohort row (all
    # scores tied at _BIG) must produce zero weights, not an arbitrary
    # client's update (mirrors aggregation.krum's empty-cohort guard)
    sel = (pos < multi_m).astype(jnp.float32) * (mask > 0)
    return sel / jnp.maximum(sel.sum(axis=1, keepdims=True), 1e-12)


# ---------------------------------------------------------------------------
# the fused pipeline — flat (single pre-flattened matrix) and leafwise
# ---------------------------------------------------------------------------

def fused_pipeline(x, weights, mask, *, aggregator="trimmed_mean",
                   trim_frac=0.2, cosine_thresh=-0.5, krum_f=1,
                   krum_multi_m=1, blk=4096, interpret=None):
    """Full Eq.-11 pipeline over a cohort batch of ONE pre-flattened
    matrix.

    x: (G, C, N) f32 flattened client updates; weights, mask: (G, C).
    Returns the (G, N) aggregated rows.  Semantically equivalent to
    ``aggregation.aggregate_ref`` vmapped over G (parity-tested)."""
    G, C, N = x.shape
    interpret = resolve_interpret(interpret)
    blk = min(blk, max(128, N))
    pad = (-N) % blk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, 0), (0, pad)))
    x = x.astype(jnp.float32)
    mask = mask.astype(jnp.float32)

    # ---- pass 1: median reference + cosine partials (1 read of x) ----
    dots, sqn, refsq = cosine_gate_partials(
        x, mask, blk=blk, interpret=interpret)

    # ---- on-device gate resolution: O(G*C) scalars ----
    m = _resolve_gate(dots, sqn, refsq, mask, cosine_thresh)

    # ---- pass 2 (+ Krum distance pass): gated combine ----
    if aggregator == "fedavg":
        w = weights * m
        w = w / jnp.maximum(w.sum(axis=1, keepdims=True), 1e-12)
        out = gated_combine(x, m, w, mode="mean", blk=blk,
                            interpret=interpret)
    elif aggregator == "trimmed_mean":
        out = gated_combine(x, m, m, mode="trimmed", trim_frac=trim_frac,
                            blk=blk, interpret=interpret)
    elif aggregator == "median":
        out = gated_combine(x, m, m, mode="median", blk=blk,
                            interpret=interpret)
    elif aggregator == "krum":
        d = pairwise_sq_dists_blocked(x, m, blk=blk, interpret=interpret)
        w = _krum_weights(d, m, krum_f, krum_multi_m)
        out = gated_combine(x, m, w, mode="mean", blk=blk,
                            interpret=interpret)
    else:
        raise ValueError(aggregator)
    return out[:, :N] if pad else out


def _resolve_gate(dots, sqn, refsq, mask, cosine_thresh):
    """Cosine outlier gate from the pass-1 partials; never gates everyone
    out. O(G*C) scalars, on-device.  An INCOMING all-zero mask row passes
    through unchanged — every pass-2 combine mode then emits a zero row
    for that cohort (the kernels mask by ``m``), matching the reference
    path's empty-cohort semantics."""
    cos = dots / jnp.maximum(jnp.sqrt(sqn * refsq), 1e-12)
    gate = ((cos >= cosine_thresh) & (mask > 0)).astype(jnp.float32)
    m = mask * gate
    return jnp.where(m.sum(axis=1, keepdims=True) > 0, m, mask)


def fused_pipeline_leafwise(leaves, weights, mask, *,
                            aggregator="trimmed_mean", trim_frac=0.2,
                            cosine_thresh=-0.5, krum_f=1, krum_multi_m=1,
                            blk=None, interpret=None, axis_name=None,
                            leaf_scale=None, out_dtypes=None):
    """Full Eq.-11 pipeline over a LIST of (G, C, n_l) leaf matrices —
    the segment-table passes stream every leaf in place (no concatenate).

    Returns the per-leaf (G, n_l) aggregated rows in ``out_dtypes``
    (default fp32; pass leaf dtypes for the single end-of-pipe cast).

    Distribution: under ``shard_map`` pass ``axis_name`` (mesh axis name
    or tuple) so the (C,) cosine partials and Krum's Gram matrix psum
    across devices, and ``leaf_scale`` (L,) with 0/1 entries that keep
    replicated (non-divisible) leaves from being double-counted."""
    G, C = leaves[0].shape[:2]
    sizes = tuple(int(l.shape[-1]) for l in leaves)
    interpret = resolve_interpret(interpret)
    if blk is None:
        blk = auto_blk(C, sizes)
    if leaf_scale is None:
        leaf_scale = jnp.ones((len(leaves),), jnp.float32)
    if out_dtypes is None:
        out_dtypes = [jnp.float32] * len(leaves)
    mask = mask.astype(jnp.float32)

    # ---- pass 1: shared accumulators across all leaf segments ----
    dots, sqn, refsq = cosine_gate_partials_leafwise(
        leaves, mask, blk=blk, leaf_scale=leaf_scale, interpret=interpret)
    if axis_name is not None:
        dots = jax.lax.psum(dots, axis_name)
        sqn = jax.lax.psum(sqn, axis_name)
        refsq = jax.lax.psum(refsq, axis_name)

    m = _resolve_gate(dots, sqn, refsq, mask, cosine_thresh)

    combine = functools.partial(gated_combine_leafwise, leaves, m, blk=blk,
                                out_dtypes=out_dtypes, interpret=interpret)
    if aggregator == "fedavg":
        w = weights * m
        w = w / jnp.maximum(w.sum(axis=1, keepdims=True), 1e-12)
        return combine(w, mode="mean")
    if aggregator == "trimmed_mean":
        return combine(m, mode="trimmed", trim_frac=trim_frac)
    if aggregator == "median":
        return combine(m, mode="median")
    if aggregator == "krum":
        d = pairwise_sq_dists_leafwise(
            leaves, m, blk=blk, leaf_scale=leaf_scale, interpret=interpret,
            axis_name=axis_name)
        w = _krum_weights(d, m, krum_f, krum_multi_m)
        return combine(w, mode="mean")
    raise ValueError(aggregator)


# ---------------------------------------------------------------------------
# pytree wrappers (the core/aggregation.py hot path)
# ---------------------------------------------------------------------------

def _flatten_cohorts(updates, lead):
    """Flatten a pytree of (*lead, ...) leaves into one (*lead, N) f32
    matrix; returns (flat, treedef, leaves, sizes).  The PR-1 path — the
    concatenate is an extra (C, N) HBM copy; kept for the ``*_flat``
    baseline/oracle only."""
    leaves, treedef = jax.tree_util.tree_flatten(updates)
    sizes = [int(l.size // max(1, _prod(l.shape[:lead]))) for l in leaves]
    flat = jnp.concatenate(
        [l.reshape(*l.shape[:lead], -1).astype(jnp.float32) for l in leaves],
        axis=-1)
    return flat, treedef, leaves, sizes


def _prod(shape):
    out = 1
    for s in shape:
        out *= s
    return out


def _unflatten(agg, treedef, leaves, sizes, lead):
    out, off = [], 0
    for l, n in zip(leaves, sizes):
        out.append(agg[..., off:off + n].reshape(l.shape[lead:]).astype(
            l.dtype))
        off += n
    return jax.tree_util.tree_unflatten(treedef, out)


def _leaf_views(updates, lead):
    """Reshape-only (no copy) views of the pytree's leaves as a list of
    (*lead, n_l) matrices, in native dtype."""
    leaves, treedef = jax.tree_util.tree_flatten(updates)
    flat = [l.reshape(*l.shape[:lead], -1) for l in leaves]
    return flat, treedef, leaves


@functools.partial(jax.jit, static_argnames=("cfg", "blk", "interpret"))
def fused_aggregate_tree(updates, weights, mask, cfg, *, blk=None,
                         interpret=None):
    """Single-cohort Eq.-11 aggregation over a pytree of (C, ...) leaves;
    drop-in for ``aggregation.aggregate_ref`` (which stays as the parity
    oracle).  Leaf-streaming: no concatenate, no unflatten copy — each
    leaf is a reshape view into the segment-table passes and is cast back
    to its dtype once, by the pass-2 output write."""
    flat, treedef, leaves = _leaf_views(updates, 1)
    outs = fused_pipeline_leafwise(
        [f[None] for f in flat], weights[None], mask[None],
        aggregator=cfg.aggregator, trim_frac=cfg.trim_frac,
        cosine_thresh=cfg.cosine_outlier_thresh, krum_f=cfg.krum_f,
        blk=blk, interpret=interpret,
        out_dtypes=[l.dtype for l in leaves])
    outs = [o[0].reshape(l.shape[1:]) for o, l in zip(outs, leaves)]
    return jax.tree_util.tree_unflatten(treedef, outs)


@functools.partial(jax.jit, static_argnames=("cfg", "blk", "interpret"))
def fused_two_stage_tree(slot_updates, slot_weights, slot_masks, cfg, *,
                         blk=None, interpret=None):
    """Cohort-batched two-stage scheme: every slot rides the G grid axis of
    ONE fused pipeline call per pass (the reference's per-cohort Python
    loop becomes a grid dimension), then the cross-slot size-weighted mean
    in fp32 with one cast per leaf."""
    flat, treedef, leaves = _leaf_views(slot_updates, 2)
    per = fused_pipeline_leafwise(
        flat, slot_weights, slot_masks,
        aggregator=cfg.aggregator, trim_frac=cfg.trim_frac,
        cosine_thresh=cfg.cosine_outlier_thresh, krum_f=cfg.krum_f,
        blk=blk, interpret=interpret)                      # [(G, n_l)] f32
    cw = slot_masks.sum(axis=1).astype(jnp.float32)
    cw = cw / jnp.maximum(cw.sum(), 1e-12)
    outs = [jnp.tensordot(cw, p, axes=(0, 0)).reshape(l.shape[2:]).astype(
        l.dtype) for p, l in zip(per, leaves)]
    return jax.tree_util.tree_unflatten(treedef, outs)


@functools.partial(jax.jit, static_argnames=("cfg", "blk", "interpret"))
def fused_aggregate_tree_flat(updates, weights, mask, cfg, *, blk=4096,
                              interpret=None):
    """The PR-1 flatten path (one (C, N) concatenate + unflatten copies).
    Kept as the leafwise bench baseline and a parity oracle."""
    flat, treedef, leaves, sizes = _flatten_cohorts(updates, 1)
    out = fused_pipeline(
        flat[None], weights[None], mask[None],
        aggregator=cfg.aggregator, trim_frac=cfg.trim_frac,
        cosine_thresh=cfg.cosine_outlier_thresh, krum_f=cfg.krum_f,
        blk=blk, interpret=interpret)[0]
    return _unflatten(out, treedef, leaves, sizes, 1)


@functools.partial(jax.jit, static_argnames=("cfg", "blk", "interpret"))
def fused_two_stage_tree_flat(slot_updates, slot_weights, slot_masks, cfg,
                              *, blk=4096, interpret=None):
    """PR-1 flatten path of the cohort-batched two-stage scheme (bench
    baseline / parity oracle)."""
    flat, treedef, leaves, sizes = _flatten_cohorts(slot_updates, 2)
    per = fused_pipeline(
        flat, slot_weights, slot_masks,
        aggregator=cfg.aggregator, trim_frac=cfg.trim_frac,
        cosine_thresh=cfg.cosine_outlier_thresh, krum_f=cfg.krum_f,
        blk=blk, interpret=interpret)                      # (G, N)
    cw = slot_masks.sum(axis=1).astype(jnp.float32)
    cw = cw / jnp.maximum(cw.sum(), 1e-12)
    combined = jnp.tensordot(cw, per, axes=(0, 0))         # (N,)
    return _unflatten(combined, treedef, leaves, sizes, 2)
