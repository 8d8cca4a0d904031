"""Poisoning attack models (paper §VI: data & model poisoning) for the
robustness experiments. Data attacks corrupt the client's batch; model
attacks corrupt the client's *update* before it reaches the server.

Two attacker tiers:

  static    sign_flip / gaussian_update / scale_attack / label_flip /
            backdoor_trigger — oblivious to the defense.
  adaptive  alie / min_max / min_sum / gate_aware — optimization-based
            attackers (Baruch et al. 2019; Shejwalkar & Houmansadr 2021)
            that read the HONEST updates' statistics (omniscient-attacker
            convention: malicious clients collude and see every honest
            update) and, for ``gate_aware``, the defense's own config
            (``cosine_outlier_thresh`` / ``trim_frac``) to craft updates
            sitting *just inside* the cosine gate and trim window.

All model attacks leave honest rows bit-identical and are deterministic
given their inputs (the adaptive ones take no rng at all), so the scan
and python round drivers stay bit-for-bit equal under attack.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

_EPS = 1e-12


# ---------------------------------------------------------------- data ----
def label_flip(labels, n_classes, malicious, *, mode="shift"):
    """Flip labels of malicious clients. labels: (K, B); malicious: (K,) 0/1.

    mode 'shift': y -> (y+1) % C (paper's label-flipping attack);
    mode 'target': everything -> class 0 (targeted).
    """
    if mode == "shift":
        flipped = jnp.mod(labels + 1, n_classes)
    else:
        flipped = jnp.zeros_like(labels)
    m = malicious.reshape((-1,) + (1,) * (labels.ndim - 1))
    return jnp.where(m > 0, flipped, labels)


def stamp_trigger(x, *, patch=3, value=1.0, hw_axes=None):
    """Stamp the backdoor trigger onto a batch of inputs, layout-aware.

    Image batches carry an explicit channel axis, so any (..., H, W, C)
    layout has ndim >= 4 once a batch axis is present — those get a
    ``patch x patch`` corner stamp on the (H, W) axes.  2-D/3-D batches
    ((B, D) or client-stacked (K, B, D) tabular/flattened inputs) get a
    FEATURE-PREFIX trigger instead: the first ``patch`` features set to
    ``value``.  Pass ``hw_axes`` (e.g. (-3, -2)) to pin the spatial axes
    explicitly when the heuristic is wrong (e.g. channel-less (B, H, W)).
    """
    if hw_axes is None:
        if x.ndim >= 4:
            hw_axes = (-3, -2)
        else:                                   # feature-prefix trigger
            return x.at[..., :patch].set(value)
    idx = [slice(None)] * x.ndim
    for ax in hw_axes:
        idx[ax % x.ndim] = slice(0, patch)
    return x.at[tuple(idx)].set(value)


def backdoor_trigger(images, labels, malicious, *, target=0, patch=3,
                     hw_axes=None):
    """Stamp the trigger + relabel to ``target`` on malicious clients'
    batches (backdoor / targeted poisoning).  Layout-aware via
    ``stamp_trigger``: NHWC image batches get the classic white corner
    patch; (K, B, D) tabular batches get the feature-prefix trigger
    (the old unconditional ``[..., :p, :p, :]`` stamp silently sliced
    the batch and feature axes of non-image inputs)."""
    trig = stamp_trigger(images, patch=patch, hw_axes=hw_axes)
    m_im = malicious.reshape((-1,) + (1,) * (images.ndim - 1))
    m_lb = malicious.reshape((-1,) + (1,) * (labels.ndim - 1))
    return (jnp.where(m_im > 0, trig, images),
            jnp.where(m_lb > 0, jnp.full_like(labels, target), labels))


def feature_noise(x, malicious, sigma, rng):
    """Gaussian feature corruption (tabular/image)."""
    noise = sigma * jax.random.normal(rng, x.shape, x.dtype)
    m = malicious.reshape((-1,) + (1,) * (x.ndim - 1))
    return jnp.where(m > 0, x + noise, x)


# --------------------------------------------------------------- model ----
def sign_flip(updates, malicious, *, scale=1.0):
    """Byzantine sign-flip: u -> -scale * u for malicious clients."""
    def leaf(l):
        m = malicious.reshape((-1,) + (1,) * (l.ndim - 1)).astype(l.dtype)
        return l * (1.0 - m) + (-scale) * l * m

    return jax.tree_util.tree_map(leaf, updates)


def gaussian_update(updates, malicious, sigma, rng):
    """Replace malicious updates with pure noise."""
    flat, treedef = jax.tree_util.tree_flatten(updates)
    keys = jax.random.split(rng, len(flat))

    out = []
    for l, k in zip(flat, keys):
        m = malicious.reshape((-1,) + (1,) * (l.ndim - 1)).astype(l.dtype)
        noise = sigma * jax.random.normal(k, l.shape, l.dtype)
        out.append(l * (1.0 - m) + noise * m)
    return jax.tree_util.tree_unflatten(treedef, out)


def scale_attack(updates, malicious, gamma):
    """Model-replacement scaling: u -> gamma * u (boosted poisoning)."""
    def leaf(l):
        m = malicious.reshape((-1,) + (1,) * (l.ndim - 1)).astype(l.dtype)
        return l * (1.0 + (gamma - 1.0) * m)

    return jax.tree_util.tree_map(leaf, updates)


# ---------------------------------------------- adaptive (optimization) ----
def _flatten_clients(updates):
    """(K, N) fp32 view of a (K, ...)-leaved pytree + reassembly info."""
    leaves, treedef = jax.tree_util.tree_flatten(updates)
    k = leaves[0].shape[0]
    flat = jnp.concatenate(
        [l.reshape(k, -1).astype(jnp.float32) for l in leaves], axis=1)
    return flat, leaves, treedef


def _unflatten_clients(flat, leaves, treedef):
    out, o = [], 0
    for l in leaves:
        n = l[0].size
        out.append(flat[:, o:o + n].reshape(l.shape).astype(l.dtype))
        o += n
    return jax.tree_util.tree_unflatten(treedef, out)


def _honest_stats(flat, malicious):
    """Per-coordinate mean/std over the HONEST rows (mask-weighted)."""
    h = (1.0 - malicious).astype(jnp.float32)
    nh = jnp.maximum(h.sum(), 1.0)
    mu = (flat * h[:, None]).sum(0) / nh
    var = (h[:, None] * jnp.square(flat - mu[None])).sum(0) / nh
    return mu, jnp.sqrt(var), h, nh


def _replace_malicious(flat, malicious, crafted):
    poisoned = jnp.where(malicious[:, None] > 0, crafted[None], flat)
    return poisoned


def alie(updates, malicious, *, z=None):
    """A-Little-Is-Enough [Baruch et al. 2019]: every malicious client
    submits mu - z * sigma per coordinate, where (mu, sigma) are the
    honest per-coordinate statistics and z is the largest deviation that
    still hides inside the honest spread.  Default z is the ALIE
    prescription z = Phi^-1((n - m - s) / (n - m)) with s = floor(n/2+1)-m
    (the count of honest clients a coordinate-median defense needs to
    out-vote), clipped to [0, 3]."""
    flat, leaves, treedef = _flatten_clients(updates)
    mu, sd, _, _ = _honest_stats(flat, malicious)
    if z is None:
        n = jnp.float32(flat.shape[0])
        m = malicious.astype(jnp.float32).sum()
        s = jnp.floor(n / 2.0 + 1.0) - m
        phi = jnp.clip((n - m - s) / jnp.maximum(n - m, 1.0),
                       0.5, 1.0 - 1e-6)
        z = jnp.clip(jax.scipy.special.ndtri(phi), 0.0, 3.0)
    crafted = mu - z * sd
    return _unflatten_clients(_replace_malicious(flat, malicious, crafted),
                              leaves, treedef)


def _dev_direction(dev, mu, sd):
    if dev == "unit":
        return -mu / jnp.maximum(jnp.linalg.norm(mu), _EPS)
    if dev == "std":
        return -sd
    if dev == "sign":
        return -jnp.sign(mu)
    raise ValueError(dev)


def _distance_attack(updates, malicious, *, dev, mode, n_iters=25,
                     gamma_init=10.0):
    """Shared core of min_max / min_sum [Shejwalkar & Houmansadr 2021]:
    the malicious update is mu + gamma * p with the perturbation p a
    deviation direction and gamma the LARGEST value keeping the crafted
    update's distance profile inside the honest clients' own:

      min_max:  max_h ||m - u_h||^2 <= max_{h,h'} ||u_h - u_h'||^2
      min_sum:  sum_h ||m - u_h||^2 <= max_h sum_{h'} ||u_h - u_h'||^2

    Distances are quadratics in gamma, so a fixed bisection (branchless,
    jittable) finds gamma; gamma=0 (crafted == honest mean) is the safe
    fallback when nothing larger is feasible."""
    flat, leaves, treedef = _flatten_clients(updates)
    mu, sd, h, _ = _honest_stats(flat, malicious)
    p = _dev_direction(dev, mu, sd)

    sq = jnp.sum(flat * flat, axis=1)
    d = jnp.maximum(sq[:, None] + sq[None, :] - 2.0 * (flat @ flat.T), 0.0)
    hh = h[:, None] * h[None, :]
    if mode == "max":
        budget = jnp.max(d * hh)
    else:
        rows = (d * h[None, :]).sum(1)
        budget = jnp.max(jnp.where(h > 0, rows, -jnp.inf))

    diff = mu[None] - flat                          # (K, N)
    a = jnp.sum(diff * diff, axis=1)                # ||mu - u_k||^2
    b = diff @ p
    c = jnp.sum(p * p)

    def feasible(g):
        dist = a + 2.0 * g * b + g * g * c
        if mode == "max":
            return jnp.max(jnp.where(h > 0, dist, -jnp.inf)) <= budget
        return (dist * h).sum() <= budget

    def body(_, carry):
        g, step, best = carry
        ok = feasible(g)
        best = jnp.where(ok, jnp.maximum(best, g), best)
        g = jnp.where(ok, g + step, g - step)
        return g, 0.5 * step, best

    _, _, gamma = jax.lax.fori_loop(
        0, n_iters, body,
        (jnp.float32(gamma_init), jnp.float32(gamma_init) / 2.0,
         jnp.float32(0.0)))
    crafted = mu + gamma * p
    return _unflatten_clients(_replace_malicious(flat, malicious, crafted),
                              leaves, treedef)


def min_max(updates, malicious, *, dev="std", n_iters=25, gamma_init=10.0):
    """Min-max distance attack: see ``_distance_attack``."""
    return _distance_attack(updates, malicious, dev=dev, mode="max",
                            n_iters=n_iters, gamma_init=gamma_init)


def min_sum(updates, malicious, *, dev="std", n_iters=25, gamma_init=10.0):
    """Min-sum distance attack: see ``_distance_attack``."""
    return _distance_attack(updates, malicious, dev=dev, mode="sum",
                            n_iters=n_iters, gamma_init=gamma_init)


class CrossRoundGateAware:
    """Stateful cross-round adaptive attacker (PR-5 follow-up): instead
    of modelling the gate analytically like ``gate_aware``, it PROBES it
    — the carry holds a blend weight b and last round's gate outcome,
    and each round it re-tunes b from whether its colluders were caught:

      caught   (any malicious row gated/rejected last round):
               b <- b + lr * (1 - b)   — retreat toward the reference
      evaded:  b <- b * (1 - lr)       — press the attack harder

    The crafted update is (1-b) * v + b * ref with v the trim-window
    poison corner and ref the anticipated contaminated median (both from
    the ``gate_aware`` machinery), so b=1 is indistinguishable from an
    honest-looking median and b=0 is the full boosted poison.  The carry
    rides the round scan (``FedState.attacker``), so scan==python
    bit-parity holds with the attacker adapting across rounds — and the
    async buffer delivers its STALE probes late, which is exactly the
    evasion channel the ``async_late_poison`` scenario stresses.

    Protocol (``stateful = True``; see core/fedfits.py):
      init(K)                     -> carry (b0, zeros(K))
      __call__(upd, mal, rng, c)  -> (crafted, adapted b)
      observe(b, gated_mask)      -> next carry (b, gated_mask)
    """

    stateful = True

    def __init__(self, cfg, *, scale=100.0, lr=0.5, blend0=0.5):
        self.cfg = cfg
        self.scale = float(scale)
        self.lr = float(lr)
        self.blend0 = float(blend0)

    def init(self, n_clients):
        return (jnp.float32(self.blend0),
                jnp.zeros((n_clients,), jnp.float32))

    def __call__(self, updates, malicious, rng, carry):
        blend, prev_gated = carry
        caught = (prev_gated * malicious).sum() > 0
        blend = jnp.where(caught,
                          blend + self.lr * (1.0 - blend),
                          blend * (1.0 - self.lr))
        flat, leaves, treedef = _flatten_clients(updates)
        _, v, ref, lo, hi, trims = _gate_aware_targets(
            flat, malicious, self.cfg, scale=self.scale)
        crafted = (1.0 - blend) * v + blend * ref
        if trims:
            crafted = jnp.clip(crafted, lo, hi)
        out = _unflatten_clients(
            _replace_malicious(flat, malicious, crafted), leaves, treedef)
        return out, blend

    def observe(self, blend, gated_mask):
        return (blend, gated_mask)

    @staticmethod
    def gather(carry, idx):
        """Cohort view of a population-scale carry (the async engine
        keeps prev_gated as an (M,) column and hands the attacker just
        the sampled rows)."""
        blend, prev_gated = carry
        return (blend, prev_gated[idx])


def _gate_aware_targets(flat, malicious, cfg, *, scale=100.0):
    """The honest mean mu, poison corner v, gate reference ref and trim
    window (lo, hi) shared by ``gate_aware`` (analytic blend) and
    ``CrossRoundGateAware`` (probed blend).

    The honest order statistics are rows of ONE ascending sort with the
    malicious rows at +inf, which puts the nh honest values first.  Each
    statistic sits at a scalar row index, so it is read as a dynamic
    slice: a ``take_along_axis`` with a broadcast index lowers to a
    per-element gather, which costs the TPU milliseconds per row."""
    mu, _, h, nh = _honest_stats(flat, malicious)
    k = flat.shape[0]
    trims = cfg.aggregator != "fedavg"
    asc = jnp.sort(jnp.where(h[:, None] > 0, flat, jnp.inf), axis=0)
    row = lambda i: jax.lax.dynamic_index_in_dim(asc, i, 0, keepdims=False)
    nh_i = nh.astype(jnp.int32)
    # trim window: the t-th honest value from each end; with no honest
    # row it is empty (lo = +inf, hi = -inf)
    t = jnp.floor(cfg.trim_frac * nh).astype(jnp.int32)
    lo = row(t)
    hi = jnp.where(h.sum() > 0, row(nh_i - 1 - t), -jnp.inf)
    ref = 0.5 * (row((nh_i - 1) // 2) + row(nh_i // 2))
    if not trims:
        # anticipated contaminated median: the m crafted values land
        # BELOW every honest value where mu > 0 (the boosted direction
        # is -scale*mu) and ABOVE where mu < 0, shifting the all-updates
        # median onto one of two honest order statistics per coordinate
        m_cnt = k - nh_i
        side = mu > 0                               # crafted on low side
        at = lambda i: row(jnp.clip(i, 0, nh_i - 1))
        pick = lambda r: jnp.where(side, at(r - m_cnt), at(r))
        ref = 0.5 * (pick((k - 1) // 2) + pick(k // 2))
        lo, hi = jnp.full_like(lo, -jnp.inf), jnp.full_like(hi, jnp.inf)
    v = jnp.clip(-scale * mu, lo, hi)
    return mu, v, ref, lo, hi, trims


def gate_aware(updates, malicious, cfg, *, margin=0.1, scale=100.0,
               n_iters=20):
    """Defense-aware attacker for the Eq.-11 pipeline: reads
    ``cfg.aggregator``, ``cfg.cosine_outlier_thresh`` and
    ``cfg.trim_frac`` and crafts a colluding update that sits *just
    inside* the defenses actually deployed:

      1. trim window (every robust aggregator): per coordinate, the
         most-adversarial corner of the honest trimmed range [q_lo,
         q_hi] (the t-th / (nh-1-t)-th honest order statistics, t =
         floor(trim_frac * nh)) — pushing against -mu as far as the
         window allows, so a sorting defense cannot excise it as an
         extreme order statistic, and (for Krum) its distances to the
         honest cluster stay comparable to the honest spread.  Against a
         PLAIN-MEAN aggregator no window applies and the raw boosted
         direction ``-scale * mu`` is used (the classic
         model-replacement boost, limited only by the gate).
      2. cosine gate: the crafted vector is blended toward the
         ANTICIPATED gate reference — the coordinate median of the
         cohort *with the crafted points inserted* (the gate's reference
         is computed over all updates, so an attacker aiming at the
         honest median mis-models the gate it is trying to evade and
         gets caught by its own contamination) — by the SMALLEST weight
         whose tree-wide cosine clears ``thresh + margin`` (bisected,
         branchless), then re-clamped to the trim window when one
         applies.
    """
    flat, leaves, treedef = _flatten_clients(updates)
    mu, v, ref, lo, hi, trims = _gate_aware_targets(flat, malicious, cfg,
                                                    scale=scale)
    target = jnp.float32(cfg.cosine_outlier_thresh + margin)
    rn = jnp.sqrt(jnp.sum(ref * ref))

    def cos_w(w):
        u = (1.0 - w) * v + w * ref
        un = jnp.sqrt(jnp.sum(u * u))
        return jnp.sum(u * ref) / jnp.maximum(un * rn, _EPS)

    def body(_, bounds):
        lo_w, hi_w = bounds
        mid = 0.5 * (lo_w + hi_w)
        ok = cos_w(mid) >= target
        return jnp.where(ok, lo_w, mid), jnp.where(ok, mid, hi_w)

    # w=1 is always feasible (cos=1); find the smallest feasible blend
    _, w = jax.lax.fori_loop(
        0, n_iters, body, (jnp.float32(0.0), jnp.float32(1.0)))
    w = jnp.where(cos_w(jnp.float32(0.0)) >= target, jnp.float32(0.0), w)
    crafted = (1.0 - w) * v + w * ref
    if trims:
        crafted = jnp.clip(crafted, lo, hi)
    else:
        # the blend can near-cancel ||v|| against ||ref||; the gate only
        # sees direction, so restore the boosted magnitude along it
        cn = jnp.sqrt(jnp.sum(crafted * crafted))
        crafted = crafted * (scale * jnp.sqrt(jnp.sum(mu * mu))
                             / jnp.maximum(cn, _EPS))
    return _unflatten_clients(_replace_malicious(flat, malicious, crafted),
                              leaves, treedef)
