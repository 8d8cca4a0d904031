"""Fused two-pass robust-aggregation pipeline — Pallas TPU engine.

The trust-aware robust aggregation of paper Eq. 11 (median reference ->
gradient-cosine outlier gate -> trimmed-mean / median / weighted-mean /
Krum) as TWO streaming passes over the cohort-batched client updates,
instead of the ~4+ independent sort-based XLA passes of the reference
path in ``core/aggregation.py``:

  pass 1   streams (C, blk) blocks once.  Per block it computes the
           coordinate-median reference with the O(C^2) stable-rank
           network AND accumulates the per-client cosine partials —
           dot(x_i, ref), ||x_i||^2, ||ref||^2 — into (C,) VMEM
           accumulators that live across the whole sweep (init at step
           0, revisited every step).  Only the O(C) partials reach HBM.

  gate     resolved on-device between the passes from the (G, C)
           accumulators: O(G*C) jnp scalars, no host round-trip.

  pass 2   streams the blocks once more, applying the gated mask (and
           the caller's trust weights for the mean modes) to emit the
           final aggregated row: trimmed mean / median via the rank
           network, or the normalised weighted mean.

  krum     one more streaming pass accumulates the (C, C) Gram matrix;
           the O(C^2) Krum scoring runs on-device in jnp and the winners
           are averaged by pass 2 in ``mean`` mode.

The leading G (cohort) grid axis batches every slot of the two-stage
scheme in ONE ``pallas_call`` per pass.

Leaf streaming: a pytree never flattens through a (C, N) concatenate.  A
static *segment-offset table* (``make_segments``) assigns each leaf a
run of grid steps; each pass is ONE ``pallas_call`` whose per-leaf
BlockSpec index maps clamp into the leaf's segment, so each leaf block
is DMA'd exactly once and the (C,) accumulators are shared across all
segments.  Ragged tails are masked in-kernel, accumulation is fp32
throughout, and each leaf is cast back to its own dtype exactly once —
by the pass-2 output write.

Block loaders: every pass body reads its (C, blk) fp32 block through a
static loader, which also supplies the pass's input BlockSpecs and its
kernel and scope names.

  DENSE       (G, C, n_l) leaves in their own dtype;
              kernels robust_pass1/2, robust_gram
  Int8(qblk)  int8 (G, C, n_l) codes + (G, C, nq_l) per-quant-block f32
              scales of the wire format (``comm/codecs.py``),
              dequantized in VMEM; kernels dequant_pass1/2, dequant_gram

The int8 loader relays each leaf's (C, nq) scale rows to (nblocks, C,
blk/qblk) before the passes (``_scale_blocks``, 1/32 of the code bytes),
so one grid step's scales are a whole trailing tile, and widens them to
(C, blk) in VMEM through a 0/1 expansion matmul at HIGHEST precision
(exact).  The multiply ``q_f32 * scale_f32`` is the one
``codecs.quant_decode`` performs, so aggregating the codes is
bit-identical to decoding first and running the dense loader.  It needs
every streaming block tiled by the quant block (``fusable``).  Passes
read ``C*N*1`` code bytes + ``C*N*4/qblk`` scale bytes in place of the
dense ``C*N*4``.

Distribution hooks: ``fused_pipeline_leafwise`` takes ``axis_name`` +
``leaf_scale`` so ``aggregation.aggregate(..., mesh=)`` can run the
passes shard-locally under ``shard_map`` — only the (C,) cosine partials
(and Krum's Gram matrix) cross devices, in one ``psum``.

Layout note: the (C,)-shaped accumulators use C as the minor dimension;
on real TPUs C < 128 relies on Mosaic's small-array padding.  The
pipeline is validated in interpret mode on CPU (the repo's test
substrate); ``auto_blk`` keeps grids short there and VMEM-sized on TPU.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import on_tpu, resolve_interpret

_BIG = 1e30


def stable_ranks(xm, c):
    """Per-coordinate stable ranks of an already-masked (C, blk) block:
    rank_i = #{j: x_j < x_i} + #{j<i: x_j == x_i}. Masked-out rows must
    arrive pushed to +_BIG so they rank past every real row. O(C^2)
    elementwise VPU ops — for C <= 64 this beats a data-dependent sort on
    the TPU vector unit and keeps everything in registers/VMEM."""
    xi = xm[:, None, :]                           # (C, 1, blk)
    xj = xm[None, :, :]                           # (1, C, blk)
    less = (xj < xi).astype(jnp.float32)
    row_i = jax.lax.broadcasted_iota(jnp.int32, (c, c, 1), 0)
    row_j = jax.lax.broadcasted_iota(jnp.int32, (c, c, 1), 1)
    tie = ((xj == xi) & (row_j < row_i)).astype(jnp.float32)
    return (less + tie).sum(axis=1)               # (C, blk)


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


# ---------------------------------------------------------------------------
# block loaders
# ---------------------------------------------------------------------------

def _mask_tail(x, seg, i):
    """Zero the ragged tail of leaf block ``x`` at step ``i`` (OOB lanes of
    the overrunning last block carry unspecified values; ``where`` keeps
    them out of every accumulator).  ``i`` is the step index, read by the
    caller at kernel top level — ``pl.program_id`` inside a ``pl.when``
    branch is not substituted by the interpreter."""
    if seg.n % seg.blk:
        valid = seg.n - (i - seg.start) * seg.blk
        col = jax.lax.broadcasted_iota(jnp.int32, (1, seg.blk), 1)
        x = jnp.where(col < valid, x, 0.0)
    return x


@dataclasses.dataclass(frozen=True)
class Dense:
    """Loader of (G, C, n_l) leaves in their own dtype.

    A loader is static: ``width`` reads a leaf's streamed length,
    ``prepare`` lays the leaves out for the passes once, ``operands``
    flattens them into the ``pallas_call`` inputs that ``in_specs`` block,
    and ``block`` loads leaf ``l``'s fp32 (C, blk) block at step ``i``
    inside a pass body.  ``prefix`` names the kernels and ``align`` is
    the coordinate multiple a mesh shard must keep."""
    prefix = "robust"
    align = 1

    def width(self, leaf):
        return int(leaf.shape[-1])

    def prepare(self, leaves, segs):
        return list(leaves)

    def operands(self, streams):
        return list(streams)

    def in_specs(self, segs, c):
        return [pl.BlockSpec((1, c, seg.blk), _seg_index_map(seg))
                for seg in segs]

    def block(self, refs, l, seg, i):
        return _mask_tail(refs[l][0].astype(jnp.float32), seg, i)


DENSE = Dense()


def _scale_blocks(s, seg, qblk):
    """(G, C, nq) wire scales -> (G, nblocks, C, blk/qblk): grid step
    ``j`` of the leaf reads the whole trailing (C, blk/qblk) tile
    ``[:, j]``.  The padded columns sit past the leaf's last code and
    only ever scale masked lanes."""
    G, C, nq = s.shape
    sb = seg.blk // qblk
    s = jnp.pad(s, ((0, 0), (0, 0), (0, seg.nblocks * sb - nq)),
                constant_values=1.0)
    return s.reshape(G, C, seg.nblocks, sb).transpose(0, 2, 1, 3)


def _scale_index_map(seg):
    """Scale-tile twin of ``_seg_index_map``."""
    return lambda g, i, *_: (g, jnp.clip(i - seg.start, 0,
                                         seg.nblocks - 1), 0, 0)


def _scale_specs(segs, c, qblk):
    return [pl.BlockSpec((1, 1, c, seg.blk // qblk), _scale_index_map(seg))
            for seg in segs]


def _widen(s, blk, qblk):
    """(C, blk/qblk) scales -> (C, blk), each scale repeated over its
    qblk lanes, by a matmul against the 0/1 block-membership matrix.
    One nonzero term per output at HIGHEST precision: exact."""
    sb = s.shape[1]
    row = jax.lax.broadcasted_iota(jnp.int32, (sb, blk), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (sb, blk), 1)
    member = ((lane >= row * qblk) & (lane < (row + 1) * qblk)).astype(
        jnp.float32)
    return jax.lax.dot_general(s, member, (((1,), (0,)), ((), ())),
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)


@dataclasses.dataclass(frozen=True)
class Int8:
    """Loader of int8 wire leaves: per leaf a pair of (G, C, n_l) codes
    and (G, C, nq_l) per-quant-block scales, dequantized in VMEM."""
    qblk: int = 128
    prefix = "dequant"

    @property
    def align(self):
        return self.qblk

    def width(self, leaf):
        return int(leaf[0].shape[-1])

    def prepare(self, leaves, segs):
        assert all(seg.blk % self.qblk == 0 for seg in segs), \
            (self.qblk, [seg.blk for seg in segs])
        return [(q, _scale_blocks(s, seg, self.qblk))
                for (q, s), seg in zip(leaves, segs)]

    def operands(self, streams):
        return [q for q, _ in streams] + [s for _, s in streams]

    def in_specs(self, segs, c):
        return DENSE.in_specs(segs, c) + _scale_specs(segs, c, self.qblk)

    def block(self, refs, l, seg, i):
        L = len(refs) // 2
        q = refs[l][0].astype(jnp.float32)                # (C, blk)
        s = refs[L + l][0, 0].astype(jnp.float32)         # (C, blk/qblk)
        return _mask_tail(q * _widen(s, seg.blk, self.qblk), seg, i)


def fusable(sizes, c, qblk):
    """True when every per-leaf streaming block, at the ``auto_blk`` the
    pipeline runs, is tiled exactly by the quant block, so the ``Int8``
    loader can stream these leaves."""
    segs, _ = make_segments(sizes, auto_blk(c, sizes))
    return all(seg.blk % qblk == 0 for seg in segs)


def _streamed(streams, blk, loader):
    """(operands, segs, total, G, C) of one pass over ``streams``."""
    ops = loader.operands(streams)
    segs, total = make_segments(tuple(loader.width(s) for s in streams),
                                blk)
    return ops, segs, total, ops[0].shape[0], ops[0].shape[1]


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


def _pass1_body(n_ref, scale_ref, *refs, loader, n_in, segs, total, c):
    in_refs = refs[:n_in]
    mask_ref = refs[n_in]
    dot_ref, sqn_ref, refsq_ref = refs[n_in + 1:]
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
        x = loader.block(in_refs, l, seg, i)
        med = _median_block(x, m, n, c)
        s = scale_ref[l]
        dot_ref[...] += s * (x * med).sum(axis=1)[None, :]
        sqn_ref[...] += s * (x * x).sum(axis=1)[None, :]
        refsq_ref[...] += s * (med * med).sum(axis=1, keepdims=True)

    _foreach_active_leaf(segs, total, i, accumulate)


def cosine_gate_partials(streams, mask, *, blk, leaf_scale, loader=DENSE,
                         interpret=False):
    """Pass 1: per-leaf ``streams`` (in ``loader``'s format) go through
    ONE ``pallas_call`` sharing the (C,) accumulators across all
    segments -> (dots (G, C), sqnorms (G, C), refsq (G, 1)).
    ``leaf_scale`` (L,) scales each leaf's contribution (1.0 everywhere
    off-mesh; under ``shard_map`` it de-duplicates replicated leaves
    before the cross-device psum)."""
    ops, segs, total, G, C = _streamed(streams, blk, loader)
    n_sel = mask.sum(axis=1).astype(jnp.float32)  # (G,)
    name = f"{loader.prefix}_pass1"

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(G, total),
        in_specs=loader.in_specs(segs, C)
        + [pl.BlockSpec((1, C, 1), lambda g, i, *_: (g, 0, 0))],
        out_specs=[
            pl.BlockSpec((1, C), lambda g, i, *_: (g, 0)),
            pl.BlockSpec((1, C), lambda g, i, *_: (g, 0)),
            pl.BlockSpec((1, 1), lambda g, i, *_: (g, 0)),
        ],
    )
    with jax.named_scope(name):
        dots, sqn, refsq = pl.pallas_call(
            functools.partial(_pass1_body, loader=loader, n_in=len(ops),
                              segs=segs, total=total, c=C),
            grid_spec=grid_spec,
            out_shape=[
                jax.ShapeDtypeStruct((G, C), jnp.float32),
                jax.ShapeDtypeStruct((G, C), jnp.float32),
                jax.ShapeDtypeStruct((G, 1), jnp.float32),
            ],
            name=name,
            interpret=interpret,
        )(n_sel, leaf_scale, *ops, mask.reshape(G, C, 1))
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


def _pass2_body(n_ref, *refs, loader, n_in, segs, total, c, mode,
                trim_frac):
    in_refs = refs[:n_in]
    m_ref, w_ref = refs[n_in], refs[n_in + 1]
    o_refs = refs[n_in + 2:]
    g = pl.program_id(0)
    i = pl.program_id(1)
    m = m_ref[0].astype(jnp.float32)              # (C, 1)
    w = w_ref[0].astype(jnp.float32)              # (C, 1)
    n = n_ref[g].astype(jnp.float32)

    def emit(l, seg):
        x = loader.block(in_refs, l, seg, i)
        o_refs[l][0] = _combine_block(
            x, m, w, n, c=c, mode=mode, trim_frac=trim_frac
        ).astype(o_refs[l].dtype)

    _foreach_active_leaf(segs, total, i, emit)


def gated_combine(streams, gated_mask, weights, *, mode, trim_frac=0.2,
                  blk, out_dtypes, loader=DENSE, interpret=False):
    """Pass 2: per-leaf (G, n_l) outputs, each written in its own
    ``out_dtypes[l]`` — the single fp32->leaf-dtype cast of the whole
    pipeline happens at this output write.  ``weights`` (G, C) are
    normalised and read in ``mean`` mode only."""
    ops, segs, total, G, C = _streamed(streams, blk, loader)
    n_sel = gated_mask.sum(axis=1).astype(jnp.float32)
    name = f"{loader.prefix}_pass2"

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(G, total),
        in_specs=loader.in_specs(segs, C)
        + [pl.BlockSpec((1, C, 1), lambda g, i, *_: (g, 0, 0)),
           pl.BlockSpec((1, C, 1), lambda g, i, *_: (g, 0, 0))],
        out_specs=[pl.BlockSpec((1, 1, seg.blk), _seg_index_map(seg))
                   for seg in segs],
    )
    with jax.named_scope(name):
        outs = pl.pallas_call(
            functools.partial(_pass2_body, loader=loader, n_in=len(ops),
                              segs=segs, total=total, c=C, mode=mode,
                              trim_frac=trim_frac),
            grid_spec=grid_spec,
            out_shape=[jax.ShapeDtypeStruct((G, 1, seg.n), dt)
                       for seg, dt in zip(segs, out_dtypes)],
            name=name,
            interpret=interpret,
        )(n_sel, *ops, gated_mask.reshape(G, C, 1),
          weights.reshape(G, C, 1))
    return [o[:, 0] for o in outs]


# ---------------------------------------------------------------------------
# blocked pairwise distances (Krum)
# ---------------------------------------------------------------------------

def _gram_body(scale_ref, *refs, loader, n_in, segs, total, c):
    in_refs = refs[:n_in]
    gram_ref, sqn_ref = refs[n_in:]
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        gram_ref[...] = jnp.zeros_like(gram_ref)
        sqn_ref[...] = jnp.zeros_like(sqn_ref)

    def accumulate(l, seg):
        x = loader.block(in_refs, l, seg, i)
        s = scale_ref[l]
        gram_ref[0] += s * jax.lax.dot_general(
            x, x, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        sqn_ref[...] += s * (x * x).sum(axis=1)[None, :]

    _foreach_active_leaf(segs, total, i, accumulate)


def pairwise_sq_dists(streams, mask, *, blk, leaf_scale, loader=DENSE,
                      interpret=False, axis_name=None):
    """Krum distance pass: Gram + row norms accumulate across all leaf
    segments in one streaming read -> (G, C, C) squared distances with
    masked-out pairs pushed to +_BIG (the contract of
    ``aggregation.pairwise_sq_dists``).  Under ``shard_map`` the (C, C)
    Gram matrix, not the update matrix, is what crosses devices."""
    ops, segs, total, G, C = _streamed(streams, blk, loader)
    name = f"{loader.prefix}_gram"

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(G, total),
        in_specs=loader.in_specs(segs, C),
        out_specs=[
            pl.BlockSpec((1, C, C), lambda g, i, *_: (g, 0, 0)),
            pl.BlockSpec((1, C), lambda g, i, *_: (g, 0)),
        ],
    )
    with jax.named_scope(name):
        gram, sqn = pl.pallas_call(
            functools.partial(_gram_body, loader=loader, n_in=len(ops),
                              segs=segs, total=total, c=C),
            grid_spec=grid_spec,
            out_shape=[
                jax.ShapeDtypeStruct((G, C, C), jnp.float32),
                jax.ShapeDtypeStruct((G, C), jnp.float32),
            ],
            name=name,
            interpret=interpret,
        )(leaf_scale, *ops)
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
# the fused pipeline
# ---------------------------------------------------------------------------

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


def fused_pipeline_leafwise(leaves, weights, mask, *, loader=DENSE,
                            aggregator="trimmed_mean", trim_frac=0.2,
                            cosine_thresh=-0.5, krum_f=1, krum_multi_m=1,
                            blk=None, interpret=None, axis_name=None,
                            leaf_scale=None, out_dtypes=None):
    """Full Eq.-11 pipeline over a LIST of per-leaf inputs in ``loader``'s
    format — (G, C, n_l) matrices for ``DENSE``, (codes, scales) pairs
    for ``Int8`` — streamed in place by the segment-table passes.

    Returns the per-leaf (G, n_l) aggregated rows in ``out_dtypes``
    (default fp32; pass leaf dtypes for the single end-of-pipe cast).

    Distribution: under ``shard_map`` pass ``axis_name`` (mesh axis name
    or tuple) so the (C,) cosine partials and Krum's Gram matrix psum
    across devices, and ``leaf_scale`` (L,) with 0/1 entries that keep
    replicated (non-divisible) leaves from being double-counted."""
    sizes = tuple(loader.width(l) for l in leaves)
    interpret = resolve_interpret(interpret)
    if blk is None:
        blk = auto_blk(mask.shape[1], sizes)
    streams = loader.prepare(leaves, make_segments(sizes, blk)[0])
    if leaf_scale is None:
        leaf_scale = jnp.ones((len(leaves),), jnp.float32)
    if out_dtypes is None:
        out_dtypes = [jnp.float32] * len(leaves)
    mask = mask.astype(jnp.float32)
    run = dict(blk=blk, loader=loader, interpret=interpret)

    # ---- pass 1: shared accumulators across all leaf segments ----
    dots, sqn, refsq = cosine_gate_partials(
        streams, mask, leaf_scale=leaf_scale, **run)
    if axis_name is not None:
        dots = jax.lax.psum(dots, axis_name)
        sqn = jax.lax.psum(sqn, axis_name)
        refsq = jax.lax.psum(refsq, axis_name)

    m = _resolve_gate(dots, sqn, refsq, mask, cosine_thresh)

    combine = functools.partial(gated_combine, streams, m,
                                out_dtypes=out_dtypes, **run)
    if aggregator == "fedavg":
        w = weights * m
        w = w / jnp.maximum(w.sum(axis=1, keepdims=True), 1e-12)
        return combine(w, mode="mean")
    if aggregator == "trimmed_mean":
        return combine(m, mode="trimmed", trim_frac=trim_frac)
    if aggregator == "median":
        return combine(m, mode="median")
    if aggregator == "krum":
        d = pairwise_sq_dists(streams, m, leaf_scale=leaf_scale,
                              axis_name=axis_name, **run)
        w = _krum_weights(d, m, krum_f, krum_multi_m)
        return combine(w, mode="mean")
    raise ValueError(aggregator)


# ---------------------------------------------------------------------------
# pytree wrappers over dense leaves
# ---------------------------------------------------------------------------

def _leaf_views(updates, lead):
    """Reshape-only (no copy) views of the pytree's leaves as a list of
    (*lead, n_l) matrices, in native dtype."""
    leaves, treedef = jax.tree_util.tree_flatten(updates)
    flat = [l.reshape(*l.shape[:lead], -1) for l in leaves]
    return flat, treedef, leaves


@functools.partial(jax.jit, static_argnames=("cfg", "blk", "interpret"))
def fused_aggregate_tree(updates, weights, mask, cfg, *, blk=None,
                         interpret=None):
    """Single-cohort Eq.-11 aggregation over a pytree of (C, ...) leaves
    through the dense loader (``aggregation.aggregate_ref`` is its parity
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
