"""Fused two-pass robust-aggregation pipeline (kernels/robust_pipeline.py)
vs the multi-pass XLA oracles — leaf-streaming (segment-table) engine,
dtype round-trips, and the jaxpr no-copy guarantee — plus the scan
round-driver equivalence."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.traversal import all_eqns
from repro.configs.base import FedConfig
from repro.core import aggregation
from repro.kernels.robust_pipeline import (auto_blk, fused_aggregate_tree,
                                           fused_two_stage_tree,
                                           make_segments, pairwise_sq_dists)

KEY = jax.random.PRNGKey(0)
AGGS = ["fedavg", "median", "trimmed_mean", "krum"]


def _tree(c, key=KEY):
    return {"a": jax.random.normal(key, (c, 13, 7)),
            "b": jax.random.normal(jax.random.fold_in(key, 1), (c, 301))}


def _assert_tree_close(out, ref, atol=1e-5):
    for k in ref:
        np.testing.assert_allclose(np.asarray(out[k], np.float32),
                                   np.asarray(ref[k], np.float32), atol=atol)


@pytest.mark.parametrize("agg", AGGS)
@pytest.mark.parametrize("c", [8, 9])            # even + odd C
def test_fused_matches_ref_all_modes(agg, c):
    tree = _tree(c)
    mask = jnp.ones((c,)).at[2].set(0.0)         # partial mask
    w = jax.random.uniform(jax.random.fold_in(KEY, 2), (c,)) + 0.1
    cfg = FedConfig(n_clients=c, aggregator=agg)
    out = fused_aggregate_tree(tree, w, mask, cfg, blk=128)
    ref = aggregation.aggregate_ref(tree, w, mask, cfg)
    _assert_tree_close(out, ref)


@pytest.mark.parametrize("agg", ["median", "trimmed_mean"])
def test_fused_pad_path(agg):
    """N = 13*7 + 301 = 392 with blk=256 -> pad 120 zero columns; the pad
    must not perturb the cosine gate or the aggregated coordinates."""
    c = 7
    tree = _tree(c)
    mask = jnp.ones((c,)).at[0].set(0.0).at[4].set(0.0)
    cfg = FedConfig(n_clients=c, aggregator=agg)
    out = fused_aggregate_tree(tree, jnp.ones((c,)), mask, cfg, blk=256)
    ref = aggregation.aggregate_ref(tree, jnp.ones((c,)), mask, cfg)
    _assert_tree_close(out, ref)


def test_fused_gate_excises_sign_flipped_clients():
    c = 8
    honest = jax.random.normal(KEY, (c, 30)) * 0.01 + 1.0
    upd = {"w": honest.at[0].set(-50.0).at[1].set(-50.0)}
    cfg = FedConfig(n_clients=c, aggregator="median")
    out = fused_aggregate_tree(upd, jnp.ones((c,)), jnp.ones((c,)), cfg)
    assert np.all(np.asarray(out["w"]) > 0.5)


@pytest.mark.parametrize("agg", AGGS)
def test_two_stage_cohort_batched_matches_ref(agg):
    g, k = 3, 8
    upd = {"w": jax.random.normal(KEY, (g, k, 57)),
           "b": jax.random.normal(jax.random.fold_in(KEY, 3), (g, k, 5, 3))}
    sw = jax.random.uniform(jax.random.fold_in(KEY, 4), (g, k)) + 0.1
    sm = jnp.ones((g, k)).at[0, 3].set(0.0).at[2, 1].set(0.0)
    cfg = FedConfig(aggregator=agg)
    out = fused_two_stage_tree(upd, sw, sm, cfg, blk=128)
    ref = aggregation.two_stage_ref(upd, sw, sm, cfg)
    _assert_tree_close(out, ref)


def test_two_stage_leafwise_matches_flat():
    """Cohort-batched leaf-streaming vs the vmapped XLA oracle: the G grid
    axis carries each cohort's own partials and gate."""
    g, k = 3, 8
    upd = {"w": jax.random.normal(KEY, (g, k, 57)),
           "b": jax.random.normal(jax.random.fold_in(KEY, 3), (g, k, 5, 3))}
    sw = jax.random.uniform(jax.random.fold_in(KEY, 4), (g, k)) + 0.1
    sm = jnp.ones((g, k)).at[1, 2].set(0.0)
    cfg = FedConfig(aggregator="trimmed_mean")
    out = fused_two_stage_tree(upd, sw, sm, cfg, blk=128)
    ref = aggregation.two_stage_ref(upd, sw, sm, cfg)
    _assert_tree_close(out, ref)


def test_two_stage_router_uses_fused_path():
    g, k = 2, 6
    upd = jax.random.normal(KEY, (g, k, 33))
    sw = jnp.ones((g, k))
    sm = jnp.ones((g, k))
    import dataclasses
    cfg = FedConfig(aggregator="trimmed_mean")
    out = aggregation.two_stage(upd, sw, sm, cfg)
    ref = aggregation.two_stage(upd, sw, sm,
                                dataclasses.replace(cfg, fused_agg=False))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def _mixed_tree(c, key=KEY):
    """Multi-leaf, mixed-dtype, odd-size tree: a ragged f32 matrix, a
    bf16 leaf, a tiny bias-like leaf, and an f16 leaf."""
    return {"a": jax.random.normal(key, (c, 13, 7)),
            "b": jax.random.normal(jax.random.fold_in(key, 1),
                                   (c, 301)).astype(jnp.bfloat16),
            "c": jax.random.normal(jax.random.fold_in(key, 2), (c, 5)),
            "d": jax.random.normal(jax.random.fold_in(key, 3),
                                   (c, 192)).astype(jnp.float16)}


@pytest.mark.parametrize("agg", AGGS)
def test_leafwise_matches_flatten_on_mixed_tree(agg):
    """Leaf-streaming (segment-table) engine vs the XLA oracle on a
    multi-leaf mixed-dtype/odd-size tree."""
    c = 9
    tree = _mixed_tree(c)
    mask = jnp.ones((c,)).at[3].set(0.0)
    w = jax.random.uniform(jax.random.fold_in(KEY, 5), (c,)) + 0.1
    cfg = FedConfig(n_clients=c, aggregator=agg)
    leaf = fused_aggregate_tree(tree, w, mask, cfg, blk=128)
    flat = aggregation.aggregate_ref(tree, w, mask, cfg)
    for k in tree:
        assert leaf[k].dtype == tree[k].dtype
        # half-precision leaves: within one ulp of each other's rounding
        atol = 1e-5 if leaf[k].dtype == jnp.float32 else 2e-2
        np.testing.assert_allclose(np.asarray(leaf[k], np.float32),
                                   np.asarray(flat[k], np.float32),
                                   atol=atol)


@pytest.mark.parametrize("agg", ["median", "trimmed_mean", "fedavg"])
def test_halfprec_leaves_match_fp32_oracle(agg):
    """bf16/f16 leaves accumulate fp32 throughout with exactly one cast at
    the pass-2 output write: the result must match the fp32 oracle (the
    same tree in fp32) to half-precision resolution — a per-slice cast
    round-trip would drift further."""
    c = 8
    tree = _mixed_tree(c)
    tree32 = jax.tree_util.tree_map(
        lambda l: l.astype(jnp.float32), tree)
    mask = jnp.ones((c,)).at[1].set(0.0)
    w = jnp.ones((c,))
    cfg = FedConfig(n_clients=c, aggregator=agg)
    out = fused_aggregate_tree(tree, w, mask, cfg, blk=128)
    oracle = aggregation.aggregate_ref(tree32, w, mask, cfg)
    for k in tree:
        tol = 1e-5 if tree[k].dtype == jnp.float32 else 2e-2
        np.testing.assert_allclose(np.asarray(out[k], np.float32),
                                   np.asarray(oracle[k]), atol=tol)


def test_jaxpr_has_no_leaf_sized_concatenate():
    """Acceptance guard for the leaf-streaming rework: the jaxpr of
    ``fused_aggregate_tree`` on a multi-leaf tree must not materialise a
    flattened (C, N) matrix — no concatenate at (or above) leaf size."""
    c = 8
    tree = _mixed_tree(c)
    mask = jnp.ones((c,))
    w = jnp.ones((c,))
    cfg = FedConfig(n_clients=c, aggregator="trimmed_mean")
    jaxpr = jax.make_jaxpr(
        lambda u, ww, m: fused_aggregate_tree(u, ww, m, cfg, blk=128)
    )(tree, w, mask)
    min_leaf = min(int(l.size) for l in tree.values())
    big_concats = [
        eqn for _, eqn in all_eqns(jaxpr)
        if eqn.primitive.name == "concatenate"
        and int(np.prod(eqn.outvars[0].aval.shape)) >= min_leaf]
    assert not big_concats, big_concats


def test_segment_table_and_auto_blk():
    segs, total = make_segments([300, 128, 5], 128)
    # (start, nblocks, n, per-leaf blk): narrow leaves get 128-aligned
    # blocks of their own width, and single-block leaves all share step 0
    # (constant block index -> no extra DMA, no extra grid steps)
    assert [tuple(s) for s in segs] == [
        (0, 3, 300, 128), (0, 1, 128, 128), (0, 1, 5, 128)]
    assert total == 3
    segs, total = make_segments([16384, 379, 5], 16384)
    assert [s.blk for s in segs] == [16384, 384, 128]
    assert total == 1                      # whole tree in one grid step
    segs, total = make_segments([300, 300], 128)
    assert [s.start for s in segs] == [0, 3] and total == 6
    # CPU: never wider than the longest leaf, 128-aligned
    assert auto_blk(8, [300, 128, 5], backend="cpu") == 384
    # cache cap: the (C, C, blk) rank working set stays in the LLC
    assert auto_blk(8, [1 << 20], backend="cpu") == 1 << 15
    assert auto_blk(16, [1 << 20], backend="cpu") == 1 << 14
    # TPU: VMEM-sized, 128-aligned, clamped
    blk = auto_blk(16, [1 << 20], backend="tpu")
    assert 512 <= blk <= 8192 and blk % 128 == 0


def test_pairwise_distance_kernel_matches_ref():
    g, c, n = 2, 9, 300                           # odd C, ragged tail
    x = jax.random.normal(KEY, (g, c, n))
    mask = jnp.ones((g, c)).at[1, 2].set(0.0)
    d = pairwise_sq_dists([x], mask, blk=128, leaf_scale=jnp.ones((1,)),
                          interpret=True)
    for gi in range(g):
        ref = aggregation.pairwise_sq_dists(x[gi], mask[gi])
        np.testing.assert_allclose(np.asarray(d[gi]), np.asarray(ref),
                                   atol=1e-2)  # _BIG-masked entries dominate
        real = np.asarray(mask[gi])[:, None] * np.asarray(mask[gi])[None, :]
        np.testing.assert_allclose(np.asarray(d[gi])[real > 0],
                                   np.asarray(ref)[real > 0],
                                   rtol=1e-5, atol=1e-3)


def test_scan_driver_matches_python_loop_bitwise():
    """fedfits.run driver="scan" must reproduce the per-round jit loop
    history (and final state) bit-for-bit on a fixed seed — including a
    ragged tail chunk and availability sampling inside the scan."""
    from repro.configs.registry import ARCHS
    from repro.core import fedfits
    from repro.data.pipeline import build_federation
    from repro.models.model import build

    k = 6
    model = build(ARCHS["paper-mlp"])
    fed, test = build_federation(0, kind="tabular", n=600, n_clients=k,
                                 batch_size=16, n_classes=22)

    @jax.jit
    def eval_fn(params):
        l, m = model.loss(params, test)
        return {"test_loss": l, "test_acc": m["acc"]}

    cfg = FedConfig(n_clients=k, algorithm="fedfits", local_epochs=1,
                    local_lr=0.05, avail_prob=0.7,
                    aggregator="trimmed_mean")
    s_py, h_py = fedfits.run(model, cfg, fed.data_fn, 5,
                             jax.random.PRNGKey(7), eval_fn=eval_fn,
                             driver="python")
    s_sc, h_sc = fedfits.run(model, cfg, fed.data_fn, 5,
                             jax.random.PRNGKey(7), eval_fn=eval_fn,
                             driver="scan", chunk_rounds=3)
    assert len(h_py) == len(h_sc) == 5
    for r_py, r_sc in zip(h_py, h_sc):
        assert set(r_py) == set(r_sc)
        for key in r_py:
            np.testing.assert_array_equal(np.asarray(r_py[key]),
                                          np.asarray(r_sc[key]),
                                          err_msg=f"round {r_py['round']} "
                                                  f"key {key}")
    for a, b in zip(jax.tree_util.tree_leaves(s_py),
                    jax.tree_util.tree_leaves(s_sc)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
