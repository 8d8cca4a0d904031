"""Robust, trust-aware aggregation A(.) of per-client updates (paper Eq. 11).

Updates are pytrees whose leaves carry a leading client axis (K, ...).
Every aggregator takes a float mask (K,) — only masked-in clients count —
and weights (K,) already normalised by the caller.

  fedavg        weighted mean (memory-light; the big-arch default)
  median        coordinate-wise masked median
  trimmed_mean  coordinate-wise masked trimmed mean
  krum          (multi-)Krum by pairwise distances

plus the trust machinery: EWMA trust decay and gradient-cosine outlier
gating, and the two-stage slot-internal -> cross-slot combine.

``aggregate`` is the one entry point of the full Eq.-11 pipeline (median
reference -> cosine gate -> aggregator) and the one place that chooses
how it runs, from what it can observe:

  cfg.fused_agg False     the multi-pass XLA reference ``aggregate_ref``
                          on the (decoded) updates — the parity oracle
  an int8 ``enc`` whose   the fused two-pass Pallas engine of
  blocks ``fusable``      kernels/robust_pipeline.py with its ``Int8``
  accepts                 loader: the server aggregates straight from the
                          wire codes, dequantized in VMEM
  anything else           the same engine with its ``DENSE`` loader
  a ``mesh``              either loader under one shard_map: the
                          flattened param axis (codes with their scale
                          columns) shards over the mesh, both passes
                          stream shard-locally, and only the (C,) cosine
                          partials / Krum Gram matrix cross devices in
                          one psum

``two_stage`` batches the cohorts of the two-stage scheme on the
engine's grid (``two_stage_ref`` is its oracle).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.comm import codecs
from repro.kernels import robust_pipeline as rp
from repro.sharding import specs as sh

_BIG = 1e30


def normalize_weights(weights, mask):
    w = weights * mask
    return w / jnp.maximum(w.sum(), 1e-12)


def sanitize_updates(updates, mask, *, norm_mult=1e4):
    """Aggregation-boundary hardening: reject non-finite (NaN/Inf) or
    absurd-norm client deliveries BEFORE any aggregator sees them.

    A crashed client ships NaNs, a hostile one ships 1e30-scale rows —
    either one entering even a single coordinate of the global model is
    unrecoverable (NaN propagates through every later round), and the
    robust aggregators do NOT cover it: a nan row poisons the sort-based
    order statistics and the cosine gate's own reference.  Runs on the
    raw delivered rows ahead of BOTH the fused Pallas and XLA reference
    paths, so both are covered by construction.

    Rejection rule per masked-in client row:
      * any non-finite coordinate anywhere in its update tree, or
      * tree-wide L2 norm > ``norm_mult`` x the median norm of the
        finite masked-in rows (``norm_mult`` <= 0 disables the norm
        rule; the finiteness rule always applies).  The threshold is
        RELATIVE — absolute scales are model/lr-dependent — and the
        default 1e4 headroom keeps every legitimate attack scenario
        (10x sign-flip, ALIE) untouched: this guard is for absurd rows,
        the Eq.-11 pipeline handles the adversarial-but-plausible ones.

    Returns ``(clean_updates, clean_mask, rejected)``: rejected rows are
    zeroed and masked out (an all-rejected cohort therefore hits the
    aggregators' empty-mask path and yields a ZERO update), and
    ``rejected`` (K,) 0/1 lets the caller charge a trust penalty.  With
    all-finite sane inputs the outputs are bit-identical passthroughs.
    """
    k = mask.shape[0]
    finite = jnp.ones((k,), bool)
    sq = jnp.zeros((k,), jnp.float32)
    for leaf in jax.tree_util.tree_leaves(updates):
        f = leaf.reshape(k, -1).astype(jnp.float32)
        ok = jnp.isfinite(f)
        finite = finite & ok.all(axis=1)
        sq = sq + jnp.sum(jnp.where(ok, f, 0.0) ** 2, axis=1)
    norm = jnp.sqrt(sq)
    good = finite & (mask > 0)
    if norm_mult and norm_mult > 0:
        # masked median of the finite rows' norms (the reference scale)
        n_good = good.sum()
        s = jnp.sort(jnp.where(good, norm, jnp.inf))
        lo = jnp.floor(jnp.maximum(n_good - 1, 0) / 2).astype(jnp.int32)
        hi = jnp.ceil(jnp.maximum(n_good - 1, 0) / 2).astype(jnp.int32)
        med = 0.5 * (s[lo] + s[hi])
        med = jnp.where(n_good > 0, med, 0.0)
        sane = norm <= norm_mult * jnp.maximum(med, 1e-12)
        ok_row = finite & sane
    else:
        ok_row = finite
    rejected = ((mask > 0) & ~ok_row).astype(jnp.float32)
    okf = ok_row.astype(jnp.float32)
    clean = jax.tree_util.tree_map(
        lambda l: jnp.where(
            okf.reshape((k,) + (1,) * (l.ndim - 1)) > 0, l,
            jnp.zeros_like(l)),
        updates)
    return clean, mask * okf, rejected


def rejection_kinds(updates, mask, *, norm_mult=1e4):
    """Telemetry readout of the guard's decision, split by KIND: returns
    ``(nonfinite, norm)`` 0/1 (K,) vectors with ``nonfinite + norm ==
    rejected`` of :func:`sanitize_updates` on the same inputs (a row
    failing both counts as nonfinite — the finiteness rule fires first).
    Shares its reductions with the guard itself, so inside one jit XLA
    CSE makes the extra accounting free."""
    k = mask.shape[0]
    finite = jnp.ones((k,), bool)
    sq = jnp.zeros((k,), jnp.float32)
    for leaf in jax.tree_util.tree_leaves(updates):
        f = leaf.reshape(k, -1).astype(jnp.float32)
        ok = jnp.isfinite(f)
        finite = finite & ok.all(axis=1)
        sq = sq + jnp.sum(jnp.where(ok, f, 0.0) ** 2, axis=1)
    norm = jnp.sqrt(sq)
    good = finite & (mask > 0)
    in_mask = mask > 0
    nonfinite = (in_mask & ~finite).astype(jnp.float32)
    if norm_mult and norm_mult > 0:
        n_good = good.sum()
        s = jnp.sort(jnp.where(good, norm, jnp.inf))
        lo = jnp.floor(jnp.maximum(n_good - 1, 0) / 2).astype(jnp.int32)
        hi = jnp.ceil(jnp.maximum(n_good - 1, 0) / 2).astype(jnp.int32)
        med = 0.5 * (s[lo] + s[hi])
        med = jnp.where(n_good > 0, med, 0.0)
        sane = norm <= norm_mult * jnp.maximum(med, 1e-12)
        norm_rej = (in_mask & finite & ~sane).astype(jnp.float32)
    else:
        norm_rej = jnp.zeros((k,), jnp.float32)
    return nonfinite, norm_rej


def weighted_mean(updates, weights, mask):
    w = normalize_weights(weights, mask)

    def agg(leaf):
        return jnp.tensordot(w.astype(leaf.dtype), leaf, axes=(0, 0))

    return jax.tree_util.tree_map(agg, updates)


def _masked_sorted(leaf, mask):
    """Sort clients per coordinate with masked-out clients pushed to +inf."""
    k = leaf.shape[0]
    m = mask.reshape((k,) + (1,) * (leaf.ndim - 1))
    return jnp.sort(jnp.where(m > 0, leaf.astype(jnp.float32), _BIG), axis=0)

def median(updates, mask):
    """Coordinate-wise median over masked-in clients.  An empty cohort
    (all-zero mask) returns a ZERO update: the three-phase protocol makes
    empty participation masks a normal state, and the unclamped rank
    index (-1 wraps to the last sorted entry) used to leak the ``_BIG``
    masked-out sentinel into the global model."""
    n = mask.sum()

    def agg(leaf):
        s = _masked_sorted(leaf, mask)
        k = leaf.shape[0]
        # indices of the middle element(s) among the first n sorted
        # entries, clamped to n >= 1 so an empty mask cannot index -1
        lo = jnp.floor(jnp.maximum(n - 1, 0) / 2).astype(jnp.int32)
        hi = jnp.ceil(jnp.maximum(n - 1, 0) / 2).astype(jnp.int32)
        take = lambda i: jnp.take_along_axis(
            s, jnp.broadcast_to(i, (1,) + leaf.shape[1:]).astype(jnp.int32), 0)[0]
        out = 0.5 * (take(lo) + take(hi))
        return jnp.where(n > 0, out, 0.0).astype(leaf.dtype)

    return jax.tree_util.tree_map(agg, updates)


def trimmed_mean(updates, mask, trim_frac):
    """Coordinate-wise mean after dropping trim_frac per side (of n selected)."""
    n = mask.sum()
    t = jnp.floor(trim_frac * n).astype(jnp.int32)

    def agg(leaf):
        s = _masked_sorted(leaf, mask)
        k = leaf.shape[0]
        idx = jnp.arange(k).reshape((k,) + (1,) * (leaf.ndim - 1))
        keep = (idx >= t) & (idx < (n - t).astype(jnp.int32))
        cnt = jnp.maximum(n - 2 * t, 1.0)
        return (jnp.where(keep, s, 0.0).sum(0) / cnt).astype(leaf.dtype)

    return jax.tree_util.tree_map(agg, updates)


def pairwise_sq_dists(updates, mask):
    """(K, K) squared distances between flattened client updates."""
    def leaf_d(leaf):
        f = leaf.reshape(leaf.shape[0], -1).astype(jnp.float32)
        sq = jnp.sum(f * f, axis=1)
        return sq[:, None] + sq[None, :] - 2.0 * (f @ f.T)

    d = sum(jax.tree_util.tree_leaves(jax.tree_util.tree_map(leaf_d, updates)))
    big = _BIG * (1 - mask[:, None] * mask[None, :])
    return jnp.maximum(d, 0.0) + big


def krum(updates, mask, f, *, multi_m=1):
    """(Multi-)Krum [Blanchard et al. 2017]. Scores each client by the sum of
    its n - f - 2 smallest distances to other selected clients; averages the
    multi_m best."""
    d = pairwise_sq_dists(updates, mask)
    k = d.shape[0]
    d = d + _BIG * jnp.eye(k)                     # exclude self
    n = mask.sum()
    closest = jnp.sort(d, axis=1)
    j = jnp.arange(k, dtype=jnp.float32)[None, :]
    take = jnp.maximum(n - f - 2, 1.0)
    scores = jnp.where(j < take, closest, 0.0).sum(1)
    # masked-out clients rank past every real one — inf, not _BIG: a lone
    # selected client's score is _BIG + d (its distances are to masked
    # peers) and must still beat the excluded rows
    scores = jnp.where(mask > 0, scores, jnp.inf)
    order = jnp.argsort(scores)
    # restrict winners to masked-in clients: an empty cohort must yield a
    # zero update, not an arbitrary client's (all scores tie at _BIG)
    sel = jnp.zeros((k,), jnp.float32).at[order[:multi_m]].set(1.0) * mask
    return weighted_mean(updates, sel, sel)


def cosine_to_ref(updates, ref):
    """Tree-wide cosine similarity (K,) of each client's update vs. a
    reference direction pytree (one streaming pass, no sort)."""
    def dot_leaf(leaf, rleaf):
        f = leaf.reshape(leaf.shape[0], -1).astype(jnp.float32)
        r = rleaf.reshape(-1).astype(jnp.float32)
        return f @ r, jnp.sum(f * f, axis=1), jnp.sum(r * r)

    dots, n1, n2 = 0.0, 0.0, 0.0
    for leaf, rleaf in zip(jax.tree_util.tree_leaves(updates),
                           jax.tree_util.tree_leaves(ref)):
        d, a, b = dot_leaf(leaf, rleaf)
        dots, n1, n2 = dots + d, n1 + a, n2 + b
    return dots / jnp.maximum(jnp.sqrt(n1 * n2), 1e-12)


def cosine_outlier_mask(updates, ref, mask, thresh):
    """Gate clients whose update has cosine similarity < thresh vs. a
    reference direction (e.g. the trust-weighted mean). Returns 0/1 (K,)."""
    cos = cosine_to_ref(updates, ref)
    return ((cos >= thresh) & (mask > 0)).astype(jnp.float32)


def update_trust(trust, scores, mask, decay):
    """EWMA trust: selected clients' trust tracks their normalised score;
    unselected clients keep (decayed-toward-neutral) trust."""
    smax = jnp.maximum(jnp.max(scores * mask), 1e-12)
    norm_score = jnp.clip(scores / smax, 0.0, 1.0)
    upd = decay * trust + (1.0 - decay) * norm_score
    hold = decay * trust + (1.0 - decay) * 0.5     # drift to neutral
    return jnp.where(mask > 0, upd, hold)


def aggregate_ref(updates, weights, mask, cfg):
    """Multi-pass XLA reference for the Eq.-11 pipeline: applies the
    gradient-cosine outlier gate first, then the configured aggregator.
    Kept as the parity oracle for the fused Pallas engine
    (kernels/robust_pipeline.py), which replaces these ~4 sort-based
    passes with 2 streaming passes.

    The gate's reference direction is the coordinate MEDIAN, not the mean:
    a mean reference is itself corruptible (large-magnitude poison flips
    the reference's sign and the gate would then excise the honest
    clients)."""
    ref = median(updates, mask)
    gate = cosine_outlier_mask(updates, ref, mask, cfg.cosine_outlier_thresh)
    m = mask * gate
    # never gate everyone out; an INCOMING all-zero mask (empty cohort —
    # a normal state of the slotted protocol) falls through to the
    # aggregators, each of which returns a zero update for it
    m = jnp.where(m.sum() > 0, m, mask)
    if cfg.aggregator == "fedavg":
        return weighted_mean(updates, weights, m)
    if cfg.aggregator == "median":
        return median(updates, m)
    if cfg.aggregator == "trimmed_mean":
        return trimmed_mean(updates, m, cfg.trim_frac)
    if cfg.aggregator == "krum":
        return krum(updates, m, cfg.krum_f)
    raise ValueError(cfg.aggregator)


def aggregate(updates, weights, mask, cfg, *, enc=None, mesh=None,
              axes=None):
    """Eq.-11 aggregation of a pytree of (C, ...) updates -> a pytree of
    (...).  ``enc`` is the same updates' wire encoding
    (``comm/codecs.py``), when the uplink has one; ``updates`` then are
    its decode and fix the output shapes and dtypes.  ``mesh`` (with
    ``axes``, default every mesh axis but "pod") shards the streaming
    passes over devices.  See the module docstring for which path runs."""
    if not getattr(cfg, "fused_agg", True):
        return aggregate_ref(updates, weights, mask, cfg)
    like, treedef = jax.tree_util.tree_flatten(updates)
    loader, leaves = _loader_leaves(like, enc, cfg)
    run = functools.partial(
        rp.fused_pipeline_leafwise, loader=loader,
        aggregator=cfg.aggregator, trim_frac=cfg.trim_frac,
        cosine_thresh=cfg.cosine_outlier_thresh, krum_f=cfg.krum_f,
        out_dtypes=[l.dtype for l in like])
    if mesh is None:
        outs = run(leaves, weights[None], mask[None])
    else:
        outs = _shard_mapped(run, loader, leaves, weights, mask, mesh, axes)
    outs = [o.reshape(l.shape[1:]) for o, l in zip(outs, like)]
    return jax.tree_util.tree_unflatten(treedef, outs)


def _loader_leaves(like, enc, cfg):
    """The engine's loader and its per-leaf (1, C, n) views (reshapes,
    no copy): the int8 codes with their scale rows when ``enc`` is int8
    and every streaming block is tiled by the quant block, else the
    dense updates."""
    c = like[0].shape[0]
    if enc is not None:
        codes = jax.tree_util.tree_flatten(enc, is_leaf=codecs.is_encoded)[0]
        qblk = getattr(cfg, "compress_qblk", 128)
        if (all(isinstance(e, codecs.QuantLeaf) and e.q.dtype == jnp.int8
                for e in codes)
                and rp.fusable([e.q.shape[1] for e in codes], c, qblk)):
            return rp.Int8(qblk), [(e.q.reshape(1, c, -1),
                                    e.s.reshape(1, c, -1)) for e in codes]
    return rp.DENSE, [l.reshape(1, c, -1) for l in like]


def _shard_mapped(run, loader, leaves, weights, mask, mesh, axes):
    """``run`` under shard_map: each leaf whose width divides
    ``extent * loader.align`` shards its last axis — every array of the
    leaf, so int8 codes keep their own scale columns — and the rest stay
    replicated, de-duplicated by a 0/1 per-leaf scale before the psum.
    Parity with the unsharded path is ~1e-5, from the shard-local
    summation order."""
    if axes is None:
        axes = tuple(a for a in mesh.axis_names if a != "pod")
    axes = tuple(axes)
    specs, flags = sh.client_flat_specs([loader.width(l) for l in leaves],
                                        mesh, axes, align=loader.align)
    flat, struct = jax.tree_util.tree_flatten(leaves)
    flat_specs = [sp for l, sp in zip(leaves, specs)
                  for _ in jax.tree_util.tree_leaves(l)]
    # constrain the views to the shard_map input layout BEFORE the
    # boundary: GSPMD then materialises the producer's outputs (the
    # vmap'd per-client backward, the encoder) directly in the
    # (C, shard) layout, so the shard_map entry is a no-op instead of an
    # all-to-all reshard (jaxpr-guarded in tests/test_sharded_agg.py)
    flat = [jax.lax.with_sharding_constraint(f, NamedSharding(mesh, sp))
            for f, sp in zip(flat, flat_specs)]

    def body(w, m, *arrs):
        own = jnp.float32(1.0)
        for a in axes:                                    # linear-index == 0
            own = own * (jax.lax.axis_index(a) == 0).astype(jnp.float32)
        scale = jnp.stack([jnp.float32(1.0) if f else own for f in flags])
        return tuple(run(jax.tree_util.tree_unflatten(struct, arrs),
                         w[None], m[None], axis_name=axes,
                         leaf_scale=scale))

    out_specs = tuple(P(None, axes) if f else P(None, None) for f in flags)
    return jax.shard_map(body, mesh=mesh,
                         in_specs=(P(None), P(None)) + tuple(flat_specs),
                         out_specs=out_specs, check_vma=False)(
        weights, mask, *flat)


def two_stage_ref(slot_updates, slot_weights, slot_masks, cfg):
    """Cohort-batched reference for the two-stage scheme: ``aggregate_ref``
    vmapped over the leading cohort axis (matching the fused kernel's
    cohort-grid semantics — no serially-traced Python loop), then the
    cross-slot size-weighted mean."""
    per_cohort = jax.vmap(
        lambda u, w, m: aggregate_ref(u, w, m, cfg)
    )(slot_updates, slot_weights, slot_masks)
    cw = slot_masks.sum(axis=1).astype(jnp.float32)
    cw = cw / jnp.maximum(cw.sum(), 1e-12)
    return jax.tree_util.tree_map(
        lambda l: jnp.tensordot(cw.astype(l.dtype), l, axes=(0, 0)),
        per_cohort)


def two_stage(slot_updates, slot_weights, slot_masks, cfg):
    """Slot-internal robust aggregation per cohort, then cross-slot mean —
    the paper's two-stage scheme; on the pod this is psum(data) then
    psum(pod). Here: cohort-major pytrees (n_cohorts leading axis).  All
    cohorts ride the G grid axis of ONE fused ``pallas_call`` when
    cfg.fused_agg (default); the vmapped XLA oracle runs otherwise."""
    if getattr(cfg, "fused_agg", True):
        return rp.fused_two_stage_tree(slot_updates, slot_weights,
                                       slot_masks, cfg)
    return two_stage_ref(slot_updates, slot_weights, slot_masks, cfg)
