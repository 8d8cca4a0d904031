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

The full Eq.-11 pipeline (median reference -> cosine gate -> aggregator)
runs by default through the fused two-pass Pallas engine in
kernels/robust_pipeline.py (``aggregate``/``two_stage`` dispatch on
cfg.fused_agg); the multi-pass XLA implementations here remain the
parity oracles (``aggregate_ref``/``two_stage_ref``).  The engine
streams pytrees leaf-wise (segment-table grid, no flatten concatenate)
with the block size autotuned unless cfg.agg_blk pins it.  On a mesh,
``aggregate_sharded`` runs the same pipeline with the flattened param
axis sharded over devices (shard_map): both passes stream shard-locally
and only the (C,) cosine partials / Krum Gram matrix cross devices in
one psum.  The standalone kernel in kernels/robust_agg.py keeps the
bare masked trimmed-mean / median contract.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

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
    gradient-cosine outlier gate first (robust pipeline of DESIGN.md §1
    item 5), then the configured aggregator.  Kept as the parity oracle
    for the fused Pallas engine (kernels/robust_pipeline.py), which
    replaces these ~4 sort-based passes with 2 streaming passes.

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


def aggregate(updates, weights, mask, cfg):
    """Dispatch on cfg.aggregator.  Routes through the fused two-pass
    Pallas engine (kernels/robust_pipeline.py; interpret mode off-TPU)
    unless cfg.fused_agg is False, in which case the multi-pass XLA
    reference runs instead."""
    if getattr(cfg, "fused_agg", True):
        from repro.kernels.robust_pipeline import fused_aggregate_tree
        return fused_aggregate_tree(updates, weights, mask, cfg,
                                    blk=getattr(cfg, "agg_blk", None))
    return aggregate_ref(updates, weights, mask, cfg)


def aggregate_sharded(updates, weights, mask, cfg, mesh, axes=None):
    """Mesh-sharded Eq.-11 aggregation over a pytree of (C, ...) leaves.

    Each leaf's flattened parameter axis is sharded over the ``axes``
    mesh axes (default: every axis except "pod"), so every device streams
    only its shard through both fused passes; only the (C,) cosine
    partials — and Krum's (C, C) Gram matrix — cross devices, in one
    psum.  Leaves whose size does not divide the axis extent stay
    replicated; a 0/1 per-leaf scale keeps them from being double-counted
    in the psum.  Semantically equivalent to ``aggregate`` (parity atol
    ~1e-5 from the shard-local summation order)."""
    from jax.sharding import PartitionSpec as P

    from repro.kernels import robust_pipeline as rp
    from repro.sharding import specs as sh

    if axes is None:
        axes = tuple(a for a in mesh.axis_names if a != "pod")
    axes = tuple(axes)
    leaves, treedef = jax.tree_util.tree_flatten(updates)
    C = leaves[0].shape[0]
    flat = [l.reshape(1, C, -1) for l in leaves]          # views, no copy
    in_shardings, shard_flags = sh.client_flat_shardings(
        [f.shape[-1] for f in flat], mesh, axes)
    in_specs = tuple(s.spec for s in in_shardings)
    out_specs = tuple(P(None, axes) if f else P(None, None)
                      for f in shard_flags)
    # constrain the flat views to the shard_map input layout BEFORE the
    # boundary: GSPMD then materialises the producer's outputs (e.g. the
    # vmap'd per-client backward) directly in the (C, shard) layout, so
    # the shard_map entry is a no-op instead of an all-to-all reshard
    # (jaxpr-guarded in tests/test_sharded_agg.py)
    flat = [jax.lax.with_sharding_constraint(f, s)
            for f, s in zip(flat, in_shardings)]

    def agg(w, m, *fl):
        own = jnp.float32(1.0)
        for a in axes:                                    # linear-index == 0
            own = own * (jax.lax.axis_index(a) == 0).astype(jnp.float32)
        scale = jnp.stack([jnp.float32(1.0) if f else own
                           for f in shard_flags])
        outs = rp.fused_pipeline_leafwise(
            list(fl), w[None], m[None],
            aggregator=cfg.aggregator, trim_frac=cfg.trim_frac,
            cosine_thresh=cfg.cosine_outlier_thresh, krum_f=cfg.krum_f,
            blk=getattr(cfg, "agg_blk", None),
            axis_name=axes, leaf_scale=scale,
            out_dtypes=[l.dtype for l in leaves])
        return tuple(outs)

    wrapped = jax.shard_map(agg, mesh=mesh,
                            in_specs=(P(None), P(None)) + tuple(in_specs),
                            out_specs=out_specs, check_vma=False)
    outs = wrapped(weights, mask, *flat)
    outs = [o.reshape(l.shape[1:]) for o, l in zip(outs, leaves)]
    return jax.tree_util.tree_unflatten(treedef, outs)


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
        from repro.kernels.robust_pipeline import fused_two_stage_tree
        return fused_two_stage_tree(slot_updates, slot_weights, slot_masks,
                                    cfg, blk=getattr(cfg, "agg_blk", None))
    return two_stage_ref(slot_updates, slot_weights, slot_masks, cfg)
