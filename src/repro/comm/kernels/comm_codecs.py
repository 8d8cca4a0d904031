"""Fused dequant-into-aggregation Pallas kernels for the int8 uplink.

The server never materialises dense per-client updates: both passes of
the Eq.-11 robust pipeline (``kernels/robust_pipeline.py``) get variants
here whose per-leaf inputs are the **encoded** int8 code matrices plus
their per-(client, quant-block) f32 scales, dequantized in VMEM right
after the block DMA:

  pass 1   streams int8 (C, blk) code blocks + (C, blk/qblk) scale
           blocks; dequantizes in VMEM (one multiply against the
           scales widened to (C, blk)) and feeds the SAME
           median-reference / cosine-partial accumulation as the dense
           engine.
  pass 2   same dequant load, same gated combine; per-leaf outputs in
           the caller's dtypes.
  krum     same dequant load into the blocked Gram accumulation.

HBM traffic: the dense engine reads ``C*N*4`` bytes per pass; this one
reads ``C*N*1`` code bytes + ``C*N*4/qblk`` scale bytes — a ~4x cut per
pass at qblk=128, ON TOP of the 2-pass (3 for Krum) streaming roofline.
The decode-then-aggregate path (``codecs.quant_decode`` into the dense
engine) is retained as the parity oracle: the kernel's in-VMEM dequant
replays the exact ``q_f32 * scale_f32`` multiply of ``quant_decode``, so
the two are **bit-identical** (tested), and both sit within quantization
error of the dense fp32 oracle.

Layout contract: every per-leaf streaming block ``seg.blk`` is a
multiple of 128 (``make_segments``), so any ``qblk`` dividing 128 (or
equal to it) tiles the block exactly; ``fusable`` checks the general
condition and callers fall back to decode-then-aggregate when it fails.
The wire's (C, nq) scale rows are relaid per leaf to (nblocks, C,
blk/qblk) before the passes (``_scale_blocks``, 1/32 of the code bytes),
so one grid step's scales are a whole trailing tile — a (C, blk/qblk)
window of the (C, nq) row is not a block shape the TPU accepts.  In
VMEM the (C, blk/qblk) scales widen to (C, blk) through a 0/1 expansion
matmul at HIGHEST precision, which reproduces every scale exactly.
Under ``shard_map`` (``fused_dequant_aggregate_sharded``) the flattened
code axis shards over the mesh with its scale columns riding along
(alignment guaranteed by the ``align=qblk`` leg of
``sharding.specs.client_flat_specs``); only the (C,) cosine partials and
Krum's Gram matrix cross devices, exactly like the dense sharded path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.comm import codecs
from repro.kernels import robust_pipeline as rp
from repro.kernels.backend import resolve_interpret


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
    """Scale-tile twin of ``robust_pipeline._seg_index_map``."""
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


def _dq_block(q_refs, s_refs, l, seg, i, qblk):
    """Load leaf ``l``'s current int8 (C, blk) code block and its
    (C, blk/qblk) scale tile, dequantize in VMEM, and mask the ragged
    tail (same contract as ``robust_pipeline._leaf_block``).  The
    multiply is the exact op ``codecs.quant_decode`` performs, so the
    fused path is bit-identical to decode-then-aggregate."""
    q = q_refs[l][0].astype(jnp.float32)                 # (C, blk)
    s = s_refs[l][0, 0].astype(jnp.float32)              # (C, blk/qblk)
    x = q * _widen(s, seg.blk, qblk)
    if seg.n % seg.blk:
        valid = seg.n - (i - seg.start) * seg.blk
        col = jax.lax.broadcasted_iota(jnp.int32, (1, seg.blk), 1)
        x = jnp.where(col < valid, x, 0.0)
    return x


def fusable(sizes, c, blk, qblk):
    """True when every per-leaf streaming block (at the blk the pipeline
    would actually run — ``auto_blk`` when unpinned) is tiled exactly by
    the quant block."""
    if blk is None:
        blk = rp.auto_blk(c, sizes)
    segs, _ = rp.make_segments(sizes, blk)
    return all(seg.blk % qblk == 0 for seg in segs)


def should_fuse(codec, cfg, like):
    """The ONE routing predicate for the fused dequant path (shared by
    fedfits.make_round and pod.make_train_step): int8 wire format,
    fused aggregation enabled, and every streaming block tiled by the
    quant block — anything else takes the decode-then-aggregate path."""
    if codec is None or codec.name != "int8":
        return False
    if not (getattr(cfg, "fused_agg", True)
            and getattr(cfg, "fused_dequant", True)):
        return False
    leaves = jax.tree_util.tree_leaves(like)
    c = leaves[0].shape[0]
    sizes = [int(l.size) // c for l in leaves]
    return fusable(sizes, c, getattr(cfg, "agg_blk", None), codec.qblk)


# ---------------------------------------------------------------------------
# pass 1: median reference + cosine-gate partials, from int8 codes
# ---------------------------------------------------------------------------

def _pass1_dq_body(n_ref, scale_ref, *refs, segs, total, c, qblk):
    L = len(segs)
    q_refs = refs[:L]
    s_refs = refs[L:2 * L]
    mask_ref = refs[2 * L]
    dot_ref, sqn_ref, refsq_ref = refs[2 * L + 1:]
    g = pl.program_id(0)
    i = pl.program_id(1)
    m = mask_ref[0].astype(jnp.float32)                  # (C, 1)
    n = n_ref[g].astype(jnp.float32)

    @pl.when(i == 0)
    def _init():
        dot_ref[...] = jnp.zeros_like(dot_ref)
        sqn_ref[...] = jnp.zeros_like(sqn_ref)
        refsq_ref[...] = jnp.zeros_like(refsq_ref)

    def accumulate(l, seg):
        x = _dq_block(q_refs, s_refs, l, seg, i, qblk)
        med = rp._median_block(x, m, n, c)
        s = scale_ref[l]
        dot_ref[...] += s * (x * med).sum(axis=1)[None, :]
        sqn_ref[...] += s * (x * x).sum(axis=1)[None, :]
        refsq_ref[...] += s * (med * med).sum(axis=1, keepdims=True)

    rp._foreach_active_leaf(segs, total, i, accumulate)


def dequant_gate_partials(q_leaves, s_leaves, mask, *, qblk, blk,
                          leaf_scale, interpret=False):
    """Segment-table pass 1 over int8 code leaves [(G, C, n_l)] + scale
    tiles [(G, nblocks_l, C, blk_l/qblk)] (``_scale_blocks``): one
    ``pallas_call``, shared (C,) accumulators across all segments — the
    dequant happens in VMEM per block."""
    G, C = q_leaves[0].shape[:2]
    sizes = tuple(int(l.shape[-1]) for l in q_leaves)
    segs, total = rp.make_segments(sizes, blk)
    n_sel = mask.sum(axis=1).astype(jnp.float32)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(G, total),
        in_specs=[pl.BlockSpec((1, C, seg.blk), rp._seg_index_map(seg))
                  for seg in segs]
        + _scale_specs(segs, C, qblk)
        + [pl.BlockSpec((1, C, 1), lambda g, i, *_: (g, 0, 0))],
        out_specs=[
            pl.BlockSpec((1, C), lambda g, i, *_: (g, 0)),
            pl.BlockSpec((1, C), lambda g, i, *_: (g, 0)),
            pl.BlockSpec((1, 1), lambda g, i, *_: (g, 0)),
        ],
    )
    with jax.named_scope("dequant_pass1"):
        dots, sqn, refsq = pl.pallas_call(
            functools.partial(_pass1_dq_body, segs=segs, total=total, c=C,
                              qblk=qblk),
            grid_spec=grid_spec,
            out_shape=[
                jax.ShapeDtypeStruct((G, C), jnp.float32),
                jax.ShapeDtypeStruct((G, C), jnp.float32),
                jax.ShapeDtypeStruct((G, 1), jnp.float32),
            ],
            name="dequant_pass1",
            interpret=interpret,
        )(n_sel, leaf_scale, *q_leaves, *s_leaves, mask.reshape(G, C, 1))
    return dots, sqn, refsq


# ---------------------------------------------------------------------------
# pass 2: gated robust combine, from int8 codes
# ---------------------------------------------------------------------------

def _pass2_dq_body(n_ref, *refs, segs, total, c, qblk, mode, trim_frac):
    L = len(segs)
    q_refs = refs[:L]
    s_refs = refs[L:2 * L]
    m_ref, w_ref = refs[2 * L], refs[2 * L + 1]
    o_refs = refs[2 * L + 2:]
    g = pl.program_id(0)
    i = pl.program_id(1)
    m = m_ref[0].astype(jnp.float32)                     # (C, 1)
    w = w_ref[0].astype(jnp.float32)                     # (C, 1)
    n = n_ref[g].astype(jnp.float32)

    def emit(l, seg):
        x = _dq_block(q_refs, s_refs, l, seg, i, qblk)
        o_refs[l][0] = rp._combine_block(
            x, m, w, n, c=c, mode=mode, trim_frac=trim_frac
        ).astype(o_refs[l].dtype)

    rp._foreach_active_leaf(segs, total, i, emit)


def dequant_gated_combine(q_leaves, s_leaves, gated_mask, weights, *, qblk,
                          mode, trim_frac, blk, out_dtypes, interpret=False):
    """Segment-table pass 2 over int8 code leaves: per-leaf (G, n_l)
    outputs, each written in its own ``out_dtypes[l]``."""
    G, C = q_leaves[0].shape[:2]
    sizes = tuple(int(l.shape[-1]) for l in q_leaves)
    segs, total = rp.make_segments(sizes, blk)
    n_sel = gated_mask.sum(axis=1).astype(jnp.float32)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(G, total),
        in_specs=[pl.BlockSpec((1, C, seg.blk), rp._seg_index_map(seg))
                  for seg in segs]
        + _scale_specs(segs, C, qblk)
        + [pl.BlockSpec((1, C, 1), lambda g, i, *_: (g, 0, 0)),
           pl.BlockSpec((1, C, 1), lambda g, i, *_: (g, 0, 0))],
        out_specs=[pl.BlockSpec((1, 1, seg.blk), rp._seg_index_map(seg))
                   for seg in segs],
    )
    with jax.named_scope("dequant_pass2"):
        outs = pl.pallas_call(
            functools.partial(_pass2_dq_body, segs=segs, total=total, c=C,
                              qblk=qblk, mode=mode, trim_frac=trim_frac),
            grid_spec=grid_spec,
            out_shape=[jax.ShapeDtypeStruct((G, 1, seg.n), dt)
                       for seg, dt in zip(segs, out_dtypes)],
            name="dequant_pass2",
            interpret=interpret,
        )(n_sel, *q_leaves, *s_leaves, gated_mask.reshape(G, C, 1),
          weights.reshape(G, C, 1))
    return [o[:, 0] for o in outs]


# ---------------------------------------------------------------------------
# blocked pairwise distances (Krum), from int8 codes
# ---------------------------------------------------------------------------

def _pairwise_dq_body(scale_ref, *refs, segs, total, c, qblk):
    L = len(segs)
    q_refs = refs[:L]
    s_refs = refs[L:2 * L]
    gram_ref, sqn_ref = refs[2 * L:]
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        gram_ref[...] = jnp.zeros_like(gram_ref)
        sqn_ref[...] = jnp.zeros_like(sqn_ref)

    def accumulate(l, seg):
        x = _dq_block(q_refs, s_refs, l, seg, i, qblk)
        s = scale_ref[l]
        gram_ref[0] += s * jax.lax.dot_general(
            x, x, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        sqn_ref[...] += s * (x * x).sum(axis=1)[None, :]

    rp._foreach_active_leaf(segs, total, i, accumulate)


def dequant_pairwise_sq_dists(q_leaves, s_leaves, mask, *, qblk, blk,
                              leaf_scale, interpret=False, axis_name=None):
    G, C = q_leaves[0].shape[:2]
    sizes = tuple(int(l.shape[-1]) for l in q_leaves)
    segs, total = rp.make_segments(sizes, blk)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(G, total),
        in_specs=[pl.BlockSpec((1, C, seg.blk), rp._seg_index_map(seg))
                  for seg in segs]
        + _scale_specs(segs, C, qblk),
        out_specs=[
            pl.BlockSpec((1, C, C), lambda g, i, *_: (g, 0, 0)),
            pl.BlockSpec((1, C), lambda g, i, *_: (g, 0)),
        ],
    )
    with jax.named_scope("dequant_gram"):
        gram, sqn = pl.pallas_call(
            functools.partial(_pairwise_dq_body, segs=segs, total=total, c=C,
                              qblk=qblk),
            grid_spec=grid_spec,
            out_shape=[
                jax.ShapeDtypeStruct((G, C, C), jnp.float32),
                jax.ShapeDtypeStruct((G, C), jnp.float32),
            ],
            name="dequant_gram",
            interpret=interpret,
        )(leaf_scale, *q_leaves, *s_leaves)
    if axis_name is not None:
        gram = jax.lax.psum(gram, axis_name)
        sqn = jax.lax.psum(sqn, axis_name)
    d = sqn[:, :, None] + sqn[:, None, :] - 2.0 * gram
    big = rp._BIG * (1.0 - mask[:, :, None] * mask[:, None, :])
    return jnp.maximum(d, 0.0) + big


# ---------------------------------------------------------------------------
# the fused dequant pipeline
# ---------------------------------------------------------------------------

def fused_dequant_pipeline_leafwise(q_leaves, s_leaves, weights, mask, *,
                                    qblk, aggregator="trimmed_mean",
                                    trim_frac=0.2, cosine_thresh=-0.5,
                                    krum_f=1, krum_multi_m=1, blk=None,
                                    interpret=None, axis_name=None,
                                    leaf_scale=None, out_dtypes=None):
    """Full Eq.-11 pipeline over int8 code leaves [(G, C, n_l)] + scale
    leaves [(G, C, nq_l)] — same semantics, distribution hooks and
    return contract as ``robust_pipeline.fused_pipeline_leafwise`` on the
    decoded tree, without ever materialising it."""
    G, C = q_leaves[0].shape[:2]
    sizes = tuple(int(l.shape[-1]) for l in q_leaves)
    interpret = resolve_interpret(interpret)
    if blk is None:
        blk = rp.auto_blk(C, sizes)
    segs, _ = rp.make_segments(sizes, blk)
    assert all(seg.blk % qblk == 0 for seg in segs), \
        (qblk, [seg.blk for seg in segs])
    s_leaves = [_scale_blocks(sl, seg, qblk)
                for sl, seg in zip(s_leaves, segs)]
    if leaf_scale is None:
        leaf_scale = jnp.ones((len(q_leaves),), jnp.float32)
    if out_dtypes is None:
        out_dtypes = [jnp.float32] * len(q_leaves)
    mask = mask.astype(jnp.float32)

    dots, sqn, refsq = dequant_gate_partials(
        q_leaves, s_leaves, mask, qblk=qblk, blk=blk, leaf_scale=leaf_scale,
        interpret=interpret)
    if axis_name is not None:
        dots = jax.lax.psum(dots, axis_name)
        sqn = jax.lax.psum(sqn, axis_name)
        refsq = jax.lax.psum(refsq, axis_name)

    m = rp._resolve_gate(dots, sqn, refsq, mask, cosine_thresh)

    combine = functools.partial(
        dequant_gated_combine, q_leaves, s_leaves, m, qblk=qblk, blk=blk,
        out_dtypes=out_dtypes, interpret=interpret)
    if aggregator == "fedavg":
        w = weights * m
        w = w / jnp.maximum(w.sum(axis=1, keepdims=True), 1e-12)
        return combine(w, mode="mean", trim_frac=trim_frac)
    if aggregator == "trimmed_mean":
        return combine(m, mode="trimmed", trim_frac=trim_frac)
    if aggregator == "median":
        return combine(m, mode="median", trim_frac=trim_frac)
    if aggregator == "krum":
        d = dequant_pairwise_sq_dists(
            q_leaves, s_leaves, m, qblk=qblk, blk=blk,
            leaf_scale=leaf_scale, interpret=interpret, axis_name=axis_name)
        w = rp._krum_weights(d, m, krum_f, krum_multi_m)
        return combine(w, mode="mean", trim_frac=trim_frac)
    raise ValueError(aggregator)


def _enc_views(enc, like):
    """Flatten an int8-encoded tree to ((1, C, n) code views,
    (1, C, nq) scale views, like-leaves, treedef)."""
    enc_leaves = jax.tree_util.tree_flatten(
        enc, is_leaf=codecs.is_encoded)[0]
    like_leaves, treedef = jax.tree_util.tree_flatten(like)
    C = enc_leaves[0].q.shape[0]
    q = [e.q.reshape(1, C, -1) for e in enc_leaves]
    s = [e.s.reshape(1, C, -1) for e in enc_leaves]
    return q, s, like_leaves, treedef


def fused_dequant_aggregate_tree(enc, weights, mask, cfg, *, like,
                                 blk=None, interpret=None):
    """Single-cohort Eq.-11 aggregation STRAIGHT from the int8 wire
    format: drop-in for ``aggregation.aggregate`` on the decoded tree
    (bit-identical to decode-then-fused-aggregate at the same ``blk``;
    within quantization error of the dense fp32 oracle).  ``like`` is
    the dense update pytree (arrays or ShapeDtypeStructs) defining the
    output shapes/dtypes.  Call under jit (the FL round functions are)."""
    qblk = getattr(cfg, "compress_qblk", 128)
    q, s, like_leaves, treedef = _enc_views(enc, like)
    outs = fused_dequant_pipeline_leafwise(
        q, s, weights[None], mask[None], qblk=qblk,
        aggregator=cfg.aggregator, trim_frac=cfg.trim_frac,
        cosine_thresh=cfg.cosine_outlier_thresh, krum_f=cfg.krum_f,
        blk=blk if blk is not None else getattr(cfg, "agg_blk", None),
        interpret=interpret,
        out_dtypes=[l.dtype for l in like_leaves])
    outs = [o[0].reshape(l.shape[1:]) for o, l in zip(outs, like_leaves)]
    return jax.tree_util.tree_unflatten(treedef, outs)


def fused_dequant_aggregate_sharded(enc, weights, mask, cfg, mesh, *, like,
                                    axes=None):
    """Mesh-sharded fused-dequant aggregation: the flattened int8 code
    axis shards over ``axes`` (default: every mesh axis but "pod") with
    its scale columns riding along; every device dequantizes and streams
    only its shard through both passes and one psum moves the (C,)
    cosine partials (+ Krum's Gram).  Leaves whose size does not divide
    ``extent * qblk`` stay replicated (de-duplicated by the 0/1 per-leaf
    scale) — the ``align=qblk`` condition keeps each shard's scale
    columns exactly aligned with its code columns."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.sharding import specs as sh

    qblk = getattr(cfg, "compress_qblk", 128)
    if axes is None:
        axes = tuple(a for a in mesh.axis_names if a != "pod")
    axes = tuple(axes)
    q, s, like_leaves, treedef = _enc_views(enc, like)
    q_specs, shard_flags = sh.client_flat_specs(
        [f.shape[-1] for f in q], mesh, axes, align=qblk)
    s_specs = tuple(P(None, None, axes) if f else P(None, None, None)
                    for f in shard_flags)
    out_specs = tuple(P(None, axes) if f else P(None, None)
                      for f in shard_flags)
    # constrain codes AND scales before the boundary so the encoder's
    # outputs materialise in the (C, shard) layout (no boundary reshard,
    # same contract as the dense aggregate_sharded path)
    q = [jax.lax.with_sharding_constraint(f, NamedSharding(mesh, sp))
         for f, sp in zip(q, q_specs)]
    s = [jax.lax.with_sharding_constraint(f, NamedSharding(mesh, sp))
         for f, sp in zip(s, s_specs)]

    L = len(q)

    def agg(w, m, *flat):
        ql, sl = list(flat[:L]), list(flat[L:])
        own = jnp.float32(1.0)
        for a in axes:                                   # linear-index == 0
            own = own * (jax.lax.axis_index(a) == 0).astype(jnp.float32)
        scale = jnp.stack([jnp.float32(1.0) if f else own
                           for f in shard_flags])
        outs = fused_dequant_pipeline_leafwise(
            ql, sl, w[None], m[None], qblk=qblk,
            aggregator=cfg.aggregator, trim_frac=cfg.trim_frac,
            cosine_thresh=cfg.cosine_outlier_thresh, krum_f=cfg.krum_f,
            blk=getattr(cfg, "agg_blk", None),
            axis_name=axes, leaf_scale=scale,
            out_dtypes=[l.dtype for l in like_leaves])
        return tuple(outs)

    wrapped = jax.shard_map(agg, mesh=mesh,
                            in_specs=(P(None), P(None)) + tuple(q_specs)
                            + tuple(s_specs),
                            out_specs=out_specs, check_vma=False)
    outs = wrapped(weights, mask, *q, *s)
    outs = [o.reshape(l.shape[1:]) for o, l in zip(outs, like_leaves)]
    return jax.tree_util.tree_unflatten(treedef, outs)
