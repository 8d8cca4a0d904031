"""Model/data-poisoning attack models (core/attacks.py): seeded
determinism of the stochastic attacks and the honest-rows-untouched
contract."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import attacks

KEY = jax.random.PRNGKey(0)
K = 6
MAL = jnp.zeros((K,)).at[jnp.arange(2)].set(1.0)


def _updates(key=KEY):
    return {"w": jax.random.normal(key, (K, 17, 3)),
            "b": jax.random.normal(jax.random.fold_in(key, 1), (K, 5))}


def test_gaussian_update_seeded_determinism():
    upd = _updates()
    a = attacks.gaussian_update(upd, MAL, 2.0, jax.random.PRNGKey(3))
    b = attacks.gaussian_update(upd, MAL, 2.0, jax.random.PRNGKey(3))
    c = attacks.gaussian_update(upd, MAL, 2.0, jax.random.PRNGKey(4))
    for k in upd:
        np.testing.assert_array_equal(np.asarray(a[k]), np.asarray(b[k]))
        # a different seed draws different noise on the malicious rows
        assert not np.allclose(np.asarray(a[k][:2]), np.asarray(c[k][:2]))


def test_gaussian_update_leaves_honest_rows_untouched():
    upd = _updates()
    out = attacks.gaussian_update(upd, MAL, 2.0, jax.random.PRNGKey(3))
    for k in upd:
        np.testing.assert_array_equal(np.asarray(out[k][2:]),
                                      np.asarray(upd[k][2:]))
        assert not np.allclose(np.asarray(out[k][:2]),
                               np.asarray(upd[k][:2]))


def test_gaussian_update_distinct_noise_per_leaf():
    """Each leaf draws from its own key: the (K, 5) slice of one leaf
    must not reuse another leaf's noise stream."""
    upd = {"x": jnp.zeros((K, 5)), "y": jnp.zeros((K, 5))}
    out = attacks.gaussian_update(upd, jnp.ones((K,)), 1.0,
                                  jax.random.PRNGKey(3))
    assert not np.allclose(np.asarray(out["x"]), np.asarray(out["y"]))


def test_sign_flip_and_scale_attack_deterministic():
    upd = _updates()
    for fn in [lambda u: attacks.sign_flip(u, MAL, scale=3.0),
               lambda u: attacks.scale_attack(u, MAL, 5.0)]:
        a, b = fn(upd), fn(upd)
        for k in upd:
            np.testing.assert_array_equal(np.asarray(a[k]),
                                          np.asarray(b[k]))
            np.testing.assert_array_equal(np.asarray(a[k][2:]),
                                          np.asarray(upd[k][2:]))


def test_sign_flip_flips_only_malicious():
    upd = _updates()
    out = attacks.sign_flip(upd, MAL, scale=1.0)
    for k in upd:
        np.testing.assert_allclose(np.asarray(out[k][:2]),
                                   -np.asarray(upd[k][:2]), rtol=1e-6)


def test_label_flip_modes():
    y = jnp.arange(K * 4).reshape(K, 4) % 10
    shift = attacks.label_flip(y, 10, MAL, mode="shift")
    np.testing.assert_array_equal(np.asarray(shift[:2]),
                                  (np.asarray(y[:2]) + 1) % 10)
    np.testing.assert_array_equal(np.asarray(shift[2:]), np.asarray(y[2:]))
    target = attacks.label_flip(y, 10, MAL, mode="target")
    assert np.all(np.asarray(target[:2]) == 0)


def test_feature_noise_seeded_determinism():
    x = jax.random.normal(KEY, (K, 8, 8, 1))
    a = attacks.feature_noise(x, MAL, 0.5, jax.random.PRNGKey(5))
    b = attacks.feature_noise(x, MAL, 0.5, jax.random.PRNGKey(5))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(a[2:]), np.asarray(x[2:]))


# ------------------------------------------------------------------
# layout-aware backdoor trigger (regression: the old stamp hardcoded
# NHWC and sliced the batch/feature axes of tabular inputs)
# ------------------------------------------------------------------
def test_stamp_trigger_image_layout():
    x = jnp.zeros((K, 4, 6, 6, 2))
    out = attacks.stamp_trigger(x, patch=3, value=1.0)
    assert np.all(np.asarray(out[:, :, :3, :3, :]) == 1.0)
    assert np.all(np.asarray(out[:, :, 3:, :, :]) == 0.0)
    assert np.all(np.asarray(out[:, :, :, 3:, :]) == 0.0)


def test_stamp_trigger_tabular_feature_prefix():
    x = jnp.zeros((K, 5, 9))
    out = attacks.stamp_trigger(x, patch=3, value=1.0)
    assert np.all(np.asarray(out[..., :3]) == 1.0)
    assert np.all(np.asarray(out[..., 3:]) == 0.0)


def test_stamp_trigger_hw_axes_override():
    """Channel-less (B, H, W) would hit the feature-prefix heuristic —
    hw_axes pins the spatial axes explicitly."""
    x = jnp.zeros((5, 8, 8))
    out = attacks.stamp_trigger(x, patch=2, hw_axes=(-2, -1))
    assert np.all(np.asarray(out[:, :2, :2]) == 1.0)
    assert np.all(np.asarray(out[:, 2:, :]) == 0.0)


def test_backdoor_trigger_image_layout():
    x = jax.random.normal(KEY, (K, 4, 6, 6, 1))
    y = jnp.ones((K, 4), jnp.int32) * 5
    bx, by = attacks.backdoor_trigger(x, y, MAL, target=0, patch=2)
    assert np.all(np.asarray(bx[:2, :, :2, :2, :]) == 1.0)
    np.testing.assert_array_equal(np.asarray(bx[2:]), np.asarray(x[2:]))
    assert np.all(np.asarray(by[:2]) == 0)
    np.testing.assert_array_equal(np.asarray(by[2:]), np.asarray(y[2:]))


def test_backdoor_trigger_tabular_layout_regression():
    """(K, B, D) tabular batches: the trigger is a feature prefix — the
    batch axis must NOT be sliced (the old NHWC stamp corrupted the first
    `patch` EXAMPLES of every malicious client instead)."""
    x = jax.random.normal(KEY, (K, 5, 9))
    y = jnp.ones((K, 5), jnp.int32)
    bx, by = attacks.backdoor_trigger(x, y, MAL, target=0, patch=3)
    assert np.all(np.asarray(bx[:2, :, :3]) == 1.0)
    # every malicious EXAMPLE carries the trigger; trailing features and
    # honest clients are untouched
    np.testing.assert_array_equal(np.asarray(bx[:2, :, 3:]),
                                  np.asarray(x[:2, :, 3:]))
    np.testing.assert_array_equal(np.asarray(bx[2:]), np.asarray(x[2:]))
    assert np.all(np.asarray(by[:2]) == 0)


# ------------------------------------------------------------------
# adaptive (optimization-based) attacks
# ------------------------------------------------------------------
def _honest_mu_sd(upd, n_mal=2):
    flat = np.concatenate([np.asarray(l).reshape(K, -1)
                           for l in jax.tree_util.tree_leaves(upd)], axis=1)
    h = flat[n_mal:]
    return flat, h.mean(0), h.std(0)


def test_alie_explicit_z_matches_honest_stats():
    upd = _updates()
    out = attacks.alie(upd, MAL, z=2.0)
    flat, mu, sd = _honest_mu_sd(out)
    np.testing.assert_allclose(flat[0], mu - 2.0 * sd, rtol=1e-4, atol=1e-5)
    np.testing.assert_array_equal(flat[0], flat[1])  # colluders identical


def test_alie_default_z_prescription_finite():
    out = attacks.alie(_updates(), MAL)
    for l in jax.tree_util.tree_leaves(out):
        assert np.all(np.isfinite(np.asarray(l)))


def test_adaptive_attacks_deterministic_and_honest_untouched():
    upd = _updates()
    cfg_like = type("C", (), {"cosine_outlier_thresh": -0.5,
                              "trim_frac": 0.25,
                              "aggregator": "trimmed_mean"})()
    for fn in [lambda u: attacks.alie(u, MAL),
               lambda u: attacks.min_max(u, MAL),
               lambda u: attacks.min_sum(u, MAL),
               lambda u: attacks.gate_aware(u, MAL, cfg_like)]:
        a, b = fn(upd), fn(upd)
        for k in upd:
            np.testing.assert_array_equal(np.asarray(a[k]), np.asarray(b[k]))
            np.testing.assert_array_equal(np.asarray(a[k][2:]),
                                          np.asarray(upd[k][2:]))


def _pairwise_sq(flat):
    d = ((flat[:, None] - flat[None]) ** 2).sum(-1)
    return d


def test_min_max_distance_stays_inside_honest_profile():
    upd = _updates()
    out = attacks.min_max(upd, MAL)
    flat, _, _ = _honest_mu_sd(out)
    d = _pairwise_sq(flat)
    budget = d[2:, 2:].max()            # max honest<->honest distance
    assert d[0, 2:].max() <= budget * (1 + 1e-4)


def test_min_sum_distance_stays_inside_honest_profile():
    upd = _updates()
    out = attacks.min_sum(upd, MAL)
    flat, _, _ = _honest_mu_sd(out)
    d = _pairwise_sq(flat)
    budget = d[2:, 2:].sum(1).max()     # worst honest row-sum
    assert d[0, 2:].sum() <= budget * (1 + 1e-4)


def test_gate_aware_sits_inside_trim_window():
    from repro.configs.base import FedConfig
    key = jax.random.PRNGKey(7)
    k = 10
    mal = jnp.zeros((k,)).at[jnp.arange(3)].set(1.0)
    upd = {"w": jax.random.normal(key, (k, 64)) * 0.1 + 1.0}
    cfg = FedConfig(n_clients=k, aggregator="trimmed_mean", trim_frac=0.2,
                    cosine_outlier_thresh=-0.5)
    out = np.asarray(attacks.gate_aware(upd, mal, cfg)["w"])
    honest = out[3:]
    t = int(np.floor(0.2 * 7))
    s = np.sort(honest, axis=0)
    lo, hi = s[t], s[7 - 1 - t]
    assert np.all(out[0] >= lo - 1e-5) and np.all(out[0] <= hi + 1e-5)
    # and it is adversarial: anti-correlated with the honest mean
    mu = honest.mean(0)
    assert float(out[0] @ mu) < float(mu @ mu)
    # and it clears its own gate: cosine vs the honest median >= thresh
    med = np.median(honest, axis=0)
    cos = (out[0] @ med) / (np.linalg.norm(out[0]) * np.linalg.norm(med))
    assert cos >= cfg.cosine_outlier_thresh - 1e-5


def test_gate_aware_unbounded_against_plain_mean():
    """vs a fedavg aggregator there is no trim window: the crafted update
    is the boosted anti-mean direction, far outside the honest spread."""
    from repro.configs.base import FedConfig
    key = jax.random.PRNGKey(7)
    k = 10
    mal = jnp.zeros((k,)).at[jnp.arange(3)].set(1.0)
    upd = {"w": jax.random.normal(key, (k, 64)) * 0.1 + 1.0}
    cfg = FedConfig(n_clients=k, aggregator="fedavg")
    out = np.asarray(attacks.gate_aware(upd, mal, cfg)["w"])
    assert np.linalg.norm(out[0]) > 5.0 * np.linalg.norm(out[3:], axis=1).max()


# ---- gate-aware order statistics: parity with the gather formula ----
def _frozen_targets(flat, malicious, cfg, scale=100.0):
    """The gate-aware targets as first written: order statistics through
    ``take_along_axis`` with a broadcast index, and a second (descending)
    sort for the upper trim bound.  Kept to pin the row-slice rewrite."""
    mu, _, h, nh = attacks._honest_stats(flat, malicious)
    k = flat.shape[0]
    trims = cfg.aggregator != "fedavg"
    asc = jnp.sort(jnp.where(h[:, None] > 0, flat, jnp.inf), axis=0)
    t = jnp.floor(cfg.trim_frac * nh).astype(jnp.int32)
    take = lambda s, i: jnp.take_along_axis(
        s, jnp.broadcast_to(i, (1, flat.shape[1])).astype(jnp.int32), 0)[0]
    lo = take(asc, t)
    desc = jnp.sort(jnp.where(h[:, None] > 0, flat, -jnp.inf), axis=0)
    hi = take(desc, k - 1 - t)
    nh_i = nh.astype(jnp.int32)
    ref = 0.5 * (take(asc, (nh_i - 1) // 2) + take(asc, nh_i // 2))
    if not trims:
        m_cnt = k - nh_i
        side = (mu > 0).astype(jnp.int32)
        lo_r = jnp.clip((k - 1) // 2 - m_cnt * side, 0, nh_i - 1)
        hi_r = jnp.clip(k // 2 - m_cnt * side, 0, nh_i - 1)
        ref = 0.5 * (take(asc, lo_r) + take(asc, hi_r))
        lo, hi = jnp.full_like(lo, -jnp.inf), jnp.full_like(hi, jnp.inf)
    v = jnp.clip(-scale * mu, lo, hi)
    return mu, v, ref, lo, hi, trims


def _frozen_gate_aware(updates, malicious, cfg, margin=0.1, scale=100.0,
                       n_iters=20):
    flat, leaves, treedef = attacks._flatten_clients(updates)
    mu, v, ref, lo, hi, trims = _frozen_targets(flat, malicious, cfg, scale)
    target = jnp.float32(cfg.cosine_outlier_thresh + margin)
    rn = jnp.sqrt(jnp.sum(ref * ref))

    def cos_w(w):
        u = (1.0 - w) * v + w * ref
        un = jnp.sqrt(jnp.sum(u * u))
        return jnp.sum(u * ref) / jnp.maximum(un * rn, attacks._EPS)

    def body(_, bounds):
        lo_w, hi_w = bounds
        mid = 0.5 * (lo_w + hi_w)
        ok = cos_w(mid) >= target
        return jnp.where(ok, lo_w, mid), jnp.where(ok, mid, hi_w)

    _, w = jax.lax.fori_loop(
        0, n_iters, body, (jnp.float32(0.0), jnp.float32(1.0)))
    w = jnp.where(cos_w(jnp.float32(0.0)) >= target, jnp.float32(0.0), w)
    crafted = (1.0 - w) * v + w * ref
    if trims:
        crafted = jnp.clip(crafted, lo, hi)
    else:
        cn = jnp.sqrt(jnp.sum(crafted * crafted))
        crafted = crafted * (scale * jnp.sqrt(jnp.sum(mu * mu))
                             / jnp.maximum(cn, attacks._EPS))
    return attacks._unflatten_clients(
        attacks._replace_malicious(flat, malicious, crafted), leaves, treedef)


def _frozen_cross_round(updates, malicious, cfg, carry, scale=100.0,
                        lr=0.5):
    blend, prev_gated = carry
    caught = (prev_gated * malicious).sum() > 0
    blend = jnp.where(caught, blend + lr * (1.0 - blend), blend * (1.0 - lr))
    flat, leaves, treedef = attacks._flatten_clients(updates)
    _, v, ref, lo, hi, trims = _frozen_targets(flat, malicious, cfg, scale)
    crafted = (1.0 - blend) * v + blend * ref
    if trims:
        crafted = jnp.clip(crafted, lo, hi)
    out = attacks._unflatten_clients(
        attacks._replace_malicious(flat, malicious, crafted), leaves, treedef)
    return out, blend


_PK = 10
_MASKS = {
    "leading3": [0, 1, 2],
    "scattered3": [1, 4, 8],
    "none": [],
    "all_but_one": list(range(1, _PK)),
    "all": list(range(_PK)),
}


def _parity_inputs(mask):
    key = jax.random.PRNGKey(11)
    upd = {"w": jax.random.normal(key, (_PK, 33, 7)) * 0.5 + 0.1,
           "b": jax.random.normal(jax.random.fold_in(key, 1), (_PK, 129))}
    mal = jnp.zeros((_PK,)).at[jnp.asarray(_MASKS[mask], jnp.int32)].set(1.0)
    return upd, mal


def _assert_bits_equal(a, b):
    for la, lb in zip(jax.tree_util.tree_leaves(a),
                      jax.tree_util.tree_leaves(b)):
        la, lb = np.asarray(la), np.asarray(lb)
        assert la.dtype == lb.dtype and la.shape == lb.shape
        np.testing.assert_array_equal(la.view(np.uint32), lb.view(np.uint32))


@pytest.mark.parametrize("aggregator", ["trimmed_mean", "krum", "fedavg"])
@pytest.mark.parametrize("mask", list(_MASKS))
def test_gate_aware_bit_parity_with_gather_formula(mask, aggregator):
    from repro.configs.base import FedConfig
    upd, mal = _parity_inputs(mask)
    cfg = FedConfig(n_clients=_PK, aggregator=aggregator, trim_frac=0.3)
    new = jax.jit(lambda u, m: attacks.gate_aware(u, m, cfg))(upd, mal)
    old = jax.jit(lambda u, m: _frozen_gate_aware(u, m, cfg))(upd, mal)
    _assert_bits_equal(new, old)


@pytest.mark.parametrize("aggregator", ["trimmed_mean", "krum", "fedavg"])
@pytest.mark.parametrize("mask", list(_MASKS))
def test_cross_round_gate_aware_bit_parity_with_gather_formula(mask,
                                                               aggregator):
    from repro.configs.base import FedConfig
    upd, mal = _parity_inputs(mask)
    cfg = FedConfig(n_clients=_PK, aggregator=aggregator, trim_frac=0.3)
    att = attacks.CrossRoundGateAware(cfg)
    # one round that caught a colluder, so the blend moves off blend0
    carry = (jnp.float32(0.5), jnp.zeros((_PK,)).at[1].set(1.0))
    new = jax.jit(lambda u, m, c: att(u, m, None, c))(upd, mal, carry)
    old = jax.jit(lambda u, m, c: _frozen_cross_round(u, m, cfg, c))(
        upd, mal, carry)
    _assert_bits_equal(new, old)


@pytest.mark.parametrize("aggregator", ["trimmed_mean", "fedavg"])
def test_gate_aware_compiles_to_one_sort_and_no_gather(aggregator):
    """The order statistics are row slices of one sort: the compiled
    attacker holds no gather (a per-element gather per order statistic
    on the TPU) and no second sort."""
    from repro.configs.base import FedConfig
    cfg = FedConfig(n_clients=10, aggregator=aggregator)
    upd = {"w": jnp.zeros((10, 4096), jnp.float32)}
    mal = jnp.zeros((10,), jnp.float32)
    hlo = jax.jit(lambda u, m: attacks.gate_aware(u, m, cfg)).lower(
        upd, mal).compile().as_text()
    assert hlo.count(" gather(") == 0
    assert hlo.count(" sort(") == 1
