"""Partition-spec rules: name-based mapping from parameter paths to
PartitionSpecs over the ("data", "model") (+ optional "pod") mesh.

Sharding scheme (MaxText-style FSDP x TP + FL semantics):
  * batch            -> ("pod","data") axes (clients are data-axis groups)
  * weights          -> 2D-sharded: one dim over "model" (tensor/expert
    parallel), the other over "data" (FSDP; GSPMD inserts the per-layer
    all-gather). Without the FSDP leg, 72B fp32 params at 1/16 would be
    18 GB/chip — over the v5e budget. Params stay pod-replicated (grads
    all-reduce over "pod" = the cross-slot aggregation leg).
  * embeddings       -> vocab over "model", d_model over "data"
  * KV caches        -> batch over data, *sequence* over "model" (kv-head
    counts (5, 8) don't divide the 16-way model axis; sequence does)
  * small/recurrent leaves (norms, gates, biases, sLSTM recurrence)
    replicated

Layer params carry a leading stacked n_units axis -> specs get a leading
None.
"""
from __future__ import annotations

import re
from typing import Any

import jax
from jax.sharding import NamedSharding, PartitionSpec as P


# (regex over the param path, spec for the *unstacked* leaf)
_RULES = [
    # embeddings / head
    (r"(^|/)embed$", lambda nd: P("model", "data")),
    (r"(^|/)lm_head$", lambda nd: P("data", "model")),
    # attention
    (r"attn/w[qkv]$|xattn/w[qkv]$", lambda nd: P("data", "model")),
    (r"attn/wo$|xattn/wo$", lambda nd: P("model", "data")),
    (r"attn/b[qkv]$|xattn/b[qkv]$", lambda nd: P("model")),
    # dense mlp
    (r"mlp/w[gu]$", lambda nd: P("data", "model")),
    (r"mlp/wo$", lambda nd: P("model", "data")),
    # moe (expert-parallel over "model", FSDP over "data")
    (r"moe/router$", lambda nd: P(None, None)),
    (r"moe/w[guo]$", lambda nd: P("model", "data", None)),
    # mamba (d_inner over "model")
    (r"mamba/in_proj$", lambda nd: P("data", "model")),
    (r"mamba/conv_w$", lambda nd: P(None, "model")),
    (r"mamba/conv_b$|mamba/dt_bias$|mamba/D$", lambda nd: P("model")),
    (r"mamba/x_proj$|mamba/out_proj$|mamba/A_log$", lambda nd: P("model", None)),
    (r"mamba/dt_proj$", lambda nd: P(None, "model")),
    # mlstm (d_inner over "model"; tiny gate/norm leaves replicated)
    (r"mlstm/up$", lambda nd: P("data", "model")),
    (r"mlstm/w[qkv]$", lambda nd: P("data", "model")),
    (r"mlstm/conv_w$", lambda nd: P(None, "model")),
    (r"mlstm/conv_b$|mlstm/gn$", lambda nd: P("model")),
    (r"mlstm/down$", lambda nd: P("model", "data")),
    # slstm
    (r"slstm/w$", lambda nd: P("data", "model")),
    (r"slstm/up_[gu]$", lambda nd: P("data", "model")),
    (r"slstm/down$", lambda nd: P("model", "data")),
]


def _path_str(path):
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                    for p in path)


def param_specs(params: Any, *, mesh=None) -> Any:
    """PartitionSpec pytree matching ``params`` (any pytree containing a
    params subtree — opt states / PodState included, the path rules match
    on suffixes). If ``mesh`` is given, any sharded dim that does not
    divide its mesh-axis extent falls back to replicated (safety net)."""

    def spec_for(path, leaf):
        s = _path_str(path)
        stacked = re.search(r"(^|/)layers/", s) is not None
        for pat, fn in _RULES:
            if re.search(pat, s):
                base = fn(leaf.ndim - (1 if stacked else 0))
                parts = tuple(base)
                if stacked:
                    parts = (None,) + parts
                # pad/truncate to leaf rank
                parts = parts[: leaf.ndim]
                parts = parts + (None,) * (leaf.ndim - len(parts))
                if mesh is not None:
                    parts = tuple(
                        a if (a is None or leaf.shape[i] %
                              _axis_size(mesh, a) == 0) else None
                        for i, a in enumerate(parts))
                return P(*parts)
        return P(*([None] * leaf.ndim))     # replicate by default

    return jax.tree_util.tree_map_with_path(spec_for, params)


def param_specs_moe_ff(params: Any, *, mesh=None) -> Any:
    """MoE-aware FSDP variant: expert weights keep expert-parallel over
    "model" but put the FSDP ("data") leg on the *FFN* dimension instead of
    d_model. Contracting dims stay unsharded for wg/wu, so their (C, ff)
    outputs need NO all-reduce; only wo's (C, d) partial sum reduces —
    ~75% of the MoE-layer all-reduce bytes removed vs. the baseline, while
    per-chip expert memory stays 1/(16*16) of total."""
    full = param_specs(params, mesh=mesh)

    def fix(path, spec, leaf):
        s = _path_str(path)
        if re.search(r"moe/w[gu]$", s):
            return _div_guard(P(None, "model", None, "data"), leaf, mesh)
        if re.search(r"moe/wo$", s):
            return _div_guard(P(None, "model", "data", None), leaf, mesh)
        return spec

    return jax.tree_util.tree_map_with_path(
        lambda p, sp, lf: fix(p, sp, lf), full, params)


def _div_guard(spec, leaf, mesh):
    if mesh is None:
        return spec
    parts = tuple(
        a if (a is None or leaf.shape[i] % _axis_size(mesh, a) == 0)
        else None for i, a in enumerate(tuple(spec)[: leaf.ndim]))
    return P(*parts)


def param_specs_zero1_moe(params: Any, *, mesh=None) -> Any:
    """Hybrid ZeRO-1 compute layout for MoE archs: dense/attention weights
    TP-only (gathered bf16 per step — cheap, they're small), expert weights
    STAY sharded (model x ff-over-data — they're the bulk; gathering them
    is what made plain ZeRO-1 regress on dbrx)."""
    tp = param_specs_tp(params, mesh=mesh)
    moe = param_specs_moe_ff(params, mesh=mesh)

    def pick(path, tp_spec, moe_spec):
        s = _path_str(path)
        return moe_spec if re.search(r"moe/w[guo]$", s) else tp_spec

    return jax.tree_util.tree_map_with_path(pick, tp, moe)


def param_specs_tp(params: Any, *, mesh=None) -> Any:
    """Tensor-parallel-only variant: the FSDP ("data") leg dropped.

    Used by the ZeRO-1 optimized train step (compute weights bf16,
    TP-sharded, data-replicated; master params + optimizer state stay
    fully sharded) and by TP-only serving. Removing the contracting-dim
    "data" sharding stops GSPMD from resolving matmuls as partial-sum +
    activation all-reduce (the dominant collective in the baseline)."""
    full = param_specs(params, mesh=mesh)

    def strip(spec):
        return P(*[None if a == "data" else a for a in spec])

    return jax.tree_util.tree_map(
        strip, full, is_leaf=lambda x: isinstance(x, P))


def _axis_size(mesh, axis):
    if isinstance(axis, tuple):
        r = 1
        for a in axis:
            r *= mesh.shape[a]
        return r
    return mesh.shape[axis]


def batch_specs(batch: Any, mesh) -> Any:
    """Shard the leading (global-batch) dim over pod+data axes.
    Batches smaller than the dp extent (e.g. long_500k's batch=1) stay
    replicated on that dim."""
    dp = _dp_axes(mesh)
    dp_size = _axis_size(mesh, dp)

    def spec_for(leaf):
        if leaf.ndim == 0 or leaf.shape[0] % dp_size != 0:
            return P(*([None] * leaf.ndim))
        return P(dp, *([None] * (leaf.ndim - 1)))

    return jax.tree_util.tree_map(spec_for, batch)


def cache_specs(cache: Any, mesh) -> Any:
    """KV caches: batch over data axes, sequence dim over "model".

    Leaf shapes: kv (B, L, Hkv, dh) -> P(dp, "model", None, None);
    ssm/xlstm states (B, ...) -> P(dp, None...); scalars replicated.
    """
    dp = _dp_axes(mesh)
    dp_size = _axis_size(mesh, dp)
    msize = mesh.shape["model"]

    def spec_for(path, leaf):
        name = _path_str(path).rsplit("/", 1)[-1]
        nd = leaf.ndim
        if nd == 0:
            return P()
        # stacked caches have a leading n_units axis; dims: (units, B, ...)
        b_ok = nd >= 2 and leaf.shape[1] % dp_size == 0
        bspec = dp if b_ok else None
        if name in ("k", "v", "ck", "cv") and nd >= 5:
            s_ok = leaf.shape[2] % msize == 0
            return P(None, bspec, "model" if s_ok else None,
                     *([None] * (nd - 3)))
        if nd >= 2:
            return P(None, bspec, *([None] * (nd - 2)))
        return P(*([None] * nd))

    return jax.tree_util.tree_map_with_path(spec_for, cache)


def client_flat_specs(sizes, mesh, axes=("data", "model"), align=1):
    """PartitionSpecs for the (1, C, n_l)-flattened per-client update
    leaves of the sharded robust-aggregation path
    (``aggregation.aggregate(..., mesh=)``): the flattened param axis
    shards over ``axes`` when its size divides the combined axis extent,
    else the leaf stays replicated (small norm/bias leaves — the fused
    pipeline de-duplicates them before its psum).  ``align`` additionally
    requires every SHARD to be a multiple of that many coordinates — the
    int8 loader passes its quant-block width so each device's code shard
    carries exactly its own scale columns.  Returns
    (specs, sharded_flags)."""
    axes = tuple(axes)
    size = _axis_size(mesh, axes)
    specs, flags = [], []
    for n in sizes:
        if n >= size and n % (size * align) == 0:
            specs.append(P(None, None, axes))
            flags.append(True)
        else:
            specs.append(P(None, None, None))
            flags.append(False)
    return tuple(specs), tuple(flags)


def client_store_specs(store, mesh, axes=("data", "model")) -> Any:
    """PartitionSpecs for the population-scale ClientStore
    (core/clientstore.py): every (M,) per-client column shards its
    population axis over the COMBINED ``axes`` extent when M divides it
    (a million-row registry spreads evenly over all devices; the per-row
    scalars have no other axis to shard), else the column replicates
    (the sync engine's M == K == tens-of-clients case).  Optional
    (M, ...)-leaved EF residual handles shard the same leading axis —
    the trailing param dims stay unsharded, since gather/scatter of the
    sampled cohort's rows is the only cross-shard traffic of the
    selection path and row-wise layout keeps it a single-axis
    all-gather."""
    axes = tuple(axes)
    size = _axis_size(mesh, axes)

    def spec_for(leaf):
        if leaf.ndim == 0 or leaf.shape[0] % size != 0:
            return P(*([None] * leaf.ndim))
        return P(axes, *([None] * (leaf.ndim - 1)))

    return jax.tree_util.tree_map(spec_for, store)


def _dp_axes(mesh):
    names = mesh.axis_names
    return ("pod", "data") if "pod" in names else ("data",)


def named(mesh, spec_tree):
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, P))
