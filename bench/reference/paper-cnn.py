"""Plain reference of the FedFiTS round on the paper's CNN.

Straightforward ``jax.numpy`` in float32 at the matmul precision the
configuration states (``matmul_precision``: JAX's default, one bfloat16
pass per float32 product on the chip, as the program runs), written from the protocol the traffic file states and from the paper
(fitness Eqs. 1-3 and 18-19, slots Eqs. 4-5, trust-aware aggregation
Eq. 11), with nothing taken from the program: its own weight init from the
seed, its own local training, attacker, int8 codec with error feedback,
guard, election, dropout, cosine gate, trimmed mean and trust updates.

The same streams of random keys as the program's round are drawn from the
seed (``split(rng, 5)`` per round; dropout from ``fold_in(r_sel, 12)``), so
that sound runs of both sides make the same draws.

``dtype=bfloat16`` computes the whole round in bfloat16: the control that
``correct`` has to reject, the nearest precision below float32 at the
default matmul precision.  ``fault`` plants
one of the faults that the comparison has to catch: ``"half_batch"`` trains
each client on half of its batch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

_EPS = 1e-12
_BIG = 1e30


# -------------------------------------------------------------------- model
def _truncated_normal_init(key, shape, fan_in):
    return (1.0 / np.sqrt(max(fan_in, 1))) * jax.random.truncated_normal(
        key, -2.0, 2.0, shape, jnp.float32)


def init_params(key, cfg):
    """Conv blocks of ``d_model * 2**i`` channels (3x3, stride 2), a dense
    layer of ``d_ff`` and a head of ``vocab_size`` outputs: leaves in the
    order and from the keys of the published init (fan-in truncated
    normal, zero biases)."""
    n_conv, c = cfg["n_layers"], cfg["d_model"]
    ks = jax.random.split(key, n_conv + 2)
    convs, cin, size = [], cfg["in_channels"], cfg["image_size"]
    for i in range(n_conv):
        cout = c * 2 ** i
        convs.append({"w": _truncated_normal_init(ks[i], (3, 3, cin, cout),
                                                  9 * cin),
                      "b": jnp.zeros((cout,), jnp.float32)})
        cin, size = cout, (size + 1) // 2
    feat = size * size * cin
    return {"convs": convs,
            "dense": {"w": _truncated_normal_init(ks[-2], (feat, cfg["d_ff"]),
                                                  feat),
                      "b": jnp.zeros((cfg["d_ff"],), jnp.float32)},
            "head": {"w": _truncated_normal_init(
                         ks[-1], (cfg["d_ff"], cfg["vocab_size"]), cfg["d_ff"]),
                     "b": jnp.zeros((cfg["vocab_size"],), jnp.float32)}}


def forward(params, x):
    for cp in params["convs"]:
        x = jax.lax.conv_general_dilated(
            x, cp["w"], window_strides=(2, 2), padding="SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        x = jax.nn.relu(x + cp["b"])
    x = x.reshape(x.shape[0], -1)
    x = jax.nn.relu(x @ params["dense"]["w"] + params["dense"]["b"])
    return x @ params["head"]["w"] + params["head"]["b"]


def loss_acc(params, x, y):
    logits = forward(params, x).astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, y[:, None], 1)[:, 0]
    return jnp.mean(logz - gold), jnp.mean(
        (jnp.argmax(logits, -1) == y).astype(jnp.float32))


_loss_acc = jax.jit(loss_acc)


@functools.partial(jax.jit, static_argnames=("epochs",))
def _local(params, x, y, lr, epochs):
    """``epochs`` full-batch gradient steps of one client from ``params``."""
    w = params
    for _ in range(epochs):
        g = jax.grad(lambda q: loss_acc(q, x, y)[0])(w)
        w = jax.tree_util.tree_map(lambda a, b: a - lr * b, w, g)
    return w


# ----------------------------------------------------------- round pieces
def _rows(tree):
    """(K, N) view of a pytree of (K, ...) leaves, and the way back."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    k = leaves[0].shape[0]
    flat = jnp.concatenate([l.reshape(k, -1) for l in leaves], axis=1)

    def back(f):
        out, o = [], 0
        for l in leaves:
            n = l[0].size
            out.append(f[:, o:o + n].reshape((f.shape[0],) + l.shape[1:]))
            o += n
        return jax.tree_util.tree_unflatten(treedef, out)

    return flat, back


@functools.partial(jax.jit, static_argnames=("iters",))
def _gate_aware(flat, mal, trim_frac, scale, target, iters):
    """Defense-aware colluders: the adversarial corner of the honest trim
    window, blended toward the honest coordinate median by the smallest
    weight (bisection) whose cosine to it clears ``target``
    (thresh + margin)."""
    h = 1.0 - mal
    nh = jnp.maximum(h.sum(), 1.0)
    k = flat.shape[0]
    mu = (flat * h[:, None]).sum(0) / nh
    asc = jnp.sort(jnp.where(h[:, None] > 0, flat, jnp.inf), axis=0)
    desc = jnp.sort(jnp.where(h[:, None] > 0, flat, -jnp.inf), axis=0)
    t = jnp.floor(trim_frac * nh).astype(jnp.int32)
    nh_i = nh.astype(jnp.int32)
    lo, hi = asc[t], desc[k - 1 - t]
    ref = 0.5 * (asc[(nh_i - 1) // 2] + asc[nh_i // 2])
    v = jnp.clip(-scale * mu, lo, hi)
    rn = jnp.linalg.norm(ref)

    def cos_w(w):
        u = (1.0 - w) * v + w * ref
        return jnp.sum(u * ref) / jnp.maximum(jnp.linalg.norm(u) * rn, _EPS)

    a, b = jnp.asarray(0.0, flat.dtype), jnp.asarray(1.0, flat.dtype)
    for _ in range(iters):
        mid = 0.5 * (a + b)
        ok = cos_w(mid) >= target
        a, b = jnp.where(ok, a, mid), jnp.where(ok, mid, b)
    w = jnp.where(cos_w(jnp.zeros_like(a)) >= target, 0.0, b)
    crafted = jnp.clip((1.0 - w) * v + w * ref, lo, hi)
    return jnp.where(mal[:, None] > 0, crafted[None], flat)


def int8_roundtrip(flat_leaf, qblk):
    """Blockwise absmax int8 encode then decode of a (K, n) leaf."""
    k, n = flat_leaf.shape
    nq = -(-n // qblk)
    b = jnp.pad(flat_leaf, ((0, 0), (0, nq * qblk - n))).reshape(k, nq, qblk)
    amax = jnp.max(jnp.abs(b), axis=2)
    s = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.clip(jnp.round(b / s[:, :, None]), -127.0, 127.0)
    return (q * s[:, :, None]).reshape(k, nq * qblk)[:, :n]


def masked_median(flat, mask):
    n = mask.sum()
    s = jnp.sort(jnp.where(mask[:, None] > 0, flat, _BIG), axis=0)
    lo = jnp.floor(jnp.maximum(n - 1, 0) / 2).astype(jnp.int32)
    hi = jnp.ceil(jnp.maximum(n - 1, 0) / 2).astype(jnp.int32)
    return jnp.where(n > 0, 0.5 * (s[lo] + s[hi]), 0.0)


def cosine(flat, ref):
    return (flat @ ref) / jnp.maximum(
        jnp.sqrt(jnp.sum(flat * flat, 1) * jnp.sum(ref * ref)), _EPS)


def trimmed_mean(flat, mask, frac):
    n = mask.sum()
    t = jnp.floor(frac * n).astype(jnp.int32)
    s = jnp.sort(jnp.where(mask[:, None] > 0, flat, _BIG), axis=0)
    idx = jnp.arange(flat.shape[0])[:, None]
    keep = (idx >= t) & (idx < (n - t).astype(jnp.int32))
    return jnp.where(keep, s, 0.0).sum(0) / jnp.maximum(n - 2 * t, 1.0)


def theta(gl, ga, ll, la):
    """Eq. 1, the angle of the (loss, accuracy) midpoint to the loss axis."""
    num = gl + ll
    den = jnp.sqrt(jnp.square(gl + ll) + jnp.square(ga + la))
    return jnp.arccos(jnp.clip(num / jnp.maximum(den, _EPS), -1.0, 1.0))


# -------------------------------------------------------------------- round
def init_state(params, p, rng, dtype):
    k = p["n_clients"]
    cast = lambda t: jax.tree_util.tree_map(lambda a: a.astype(dtype), t)
    return {"params": cast(params), "team": jnp.ones((k,), dtype),
            "h": True, "rng": rng, "t": 1,
            "trust": jnp.full((k,), 0.5, dtype),
            "gate_trust": jnp.ones((k,), dtype),
            "ef": jax.tree_util.tree_map(
                lambda a: jnp.zeros((k,) + a.shape, dtype), params),
            "slot_p": 0, "prev_theta": -np.inf}


def round_step(st, data, p, *, dtype=jnp.float32, fault=None):
    """One round of the protocol ``p`` on client-stacked ``data``; returns
    the next state (a plain dict, host-side control flow)."""
    k, t = p["n_clients"], st["t"]
    rng, _, _, r_sel, _ = jax.random.split(st["rng"], 5)
    params = st["params"]
    x, y = data["x"].astype(dtype), data["y"]
    if fault == "half_batch":
        x, y = x[:, : x.shape[1] // 2], y[:, : y.shape[1] // 2]
    ex, ey = data["eval_x"].astype(dtype), data["eval_y"]
    n_k = data["n"].astype(dtype)
    avail = jnp.ones((k,), dtype)

    lr = jnp.asarray(p["local_lr"], dtype)
    locals_ = [_local(params, x[i], y[i], lr, p["local_epochs"])
               for i in range(k)]
    gl, ga = map(jnp.stack, zip(*[_loss_acc(params, ex[i], ey[i])
                                  for i in range(k)]))
    ll, la = map(jnp.stack, zip(*[_loss_acc(locals_[i], ex[i], ey[i])
                                  for i in range(k)]))
    upd_tree = jax.tree_util.tree_map(
        lambda *ws: jnp.stack(ws), *locals_)
    upd_tree = jax.tree_util.tree_map(lambda a, b: a - b[None], upd_tree,
                                      params)
    flat, back = _rows(upd_tree)

    mal = (jnp.arange(k) < p["n_malicious"]).astype(dtype)
    if p["attack"] == "gate_aware":
        flat = _gate_aware(flat, mal, p["trim_frac"], p["attack_scale"],
                           p["cosine_thresh"] + p["attack_margin"],
                           p["attack_iters"])
    elif p["attack"] != "none":
        raise ValueError(p["attack"])

    ef = st["ef"]
    if p["compress"] == "int8":
        target = back(flat)
        target = jax.tree_util.tree_map(lambda u, r: u + r, target, ef)
        dec = jax.tree_util.tree_map(
            lambda l: int8_roundtrip(l.reshape(k, -1), p["qblk"]).reshape(
                l.shape).astype(dtype), target)
        ef = jax.tree_util.tree_map(lambda a, b: a - b, target, dec)
        flat, _ = _rows(dec)
    elif p["compress"] != "none":
        raise ValueError(p["compress"])

    q = n_k * avail / jnp.maximum((n_k * avail).sum(), _EPS)
    th = jnp.zeros((k,), dtype) if t == 1 else theta(gl, ga, ll, la)
    alpha = ((q > th).astype(dtype) * avail).sum() / jnp.maximum(
        avail.sum(), 1.0) if p["dynamic_alpha"] else jnp.asarray(
            p["alpha"], dtype)
    scores = alpha * q + (1.0 - alpha) * th
    if p["trust_in_fitness"]:
        scores = scores * st["gate_trust"]

    if t == 1:
        team = avail
    elif st["h"]:
        thr = (scores * avail).sum() / jnp.maximum(avail.sum(), 1.0) \
            * (1.0 - p["beta"])
        team = (scores >= thr).astype(dtype) * avail
        if float(team.sum()) < 1:
            team = team.at[jnp.argmax(jnp.where(avail > 0, scores,
                                                -jnp.inf))].set(1.0)
    else:
        team = st["team"] * avail

    if p["dropout_prob"] > 0:
        u = jax.random.uniform(jax.random.fold_in(r_sel, 12), (k,))
        lost = (u < p["dropout_prob"]).astype(dtype) * team
    else:
        lost = jnp.zeros((k,), dtype)
    delivered = team * (1.0 - lost)
    part_pre = jnp.clip(delivered, 0.0, 1.0)

    # guard: non-finite or absurd-norm rows are zeroed and masked out
    finite = jnp.all(jnp.isfinite(flat), axis=1)
    norm = jnp.sqrt(jnp.sum(jnp.where(jnp.isfinite(flat), flat, 0.0) ** 2,
                            axis=1))
    good = finite & (part_pre > 0)
    med = masked_median(norm[:, None], good.astype(dtype))[0]
    ok_row = finite & (norm <= p["guard_norm_mult"] * jnp.maximum(med, _EPS))
    rejected = ((part_pre > 0) & ~ok_row).astype(dtype)
    flat = jnp.where(ok_row[:, None], flat, 0.0)
    delivered = delivered * (1.0 - rejected)
    part = jnp.clip(delivered, 0.0, 1.0)

    mask = (part > 0).astype(dtype)
    if p["paper_exact_agg"]:
        w = n_k * delivered
        agg = (w / jnp.maximum(w.sum(), _EPS)) @ flat
    else:
        ref = masked_median(flat, mask)
        gate = ((cosine(flat, ref) >= p["cosine_thresh"]) & (mask > 0))
        m = mask * gate.astype(dtype)
        m = jnp.where(m.sum() > 0, m, mask)
        if p["aggregator"] != "trimmed_mean":
            raise ValueError(p["aggregator"])
        agg = trimmed_mean(flat, m, p["trim_frac"])
    new_params = jax.tree_util.tree_map(
        lambda a, u: a + u[0], params, back(agg[None]))

    theta_team = float((th * team).sum())
    slot_p = st["slot_p"] + 1 if (t > 2 and theta_team < st["prev_theta"]) \
        else 0
    h_next = slot_p >= p["pft"] or (t + 1) % p["msl"] == 0 or t == 1
    decay = p["trust_decay"]
    smax = jnp.maximum(jnp.max(scores * team), _EPS)
    norm_score = jnp.clip(scores / smax, 0.0, 1.0)
    trust = jnp.where(team > 0, decay * st["trust"] + (1 - decay) * norm_score,
                      decay * st["trust"] + (1 - decay) * 0.5)
    gated = ((cosine(flat, agg) < p["cosine_thresh"]) & (part > 0)
             ).astype(dtype)
    bad = jnp.maximum(gated, rejected)
    gate_trust = jnp.where(part_pre > 0, decay * st["gate_trust"]
                           + (1 - decay) * (1.0 - bad), st["gate_trust"])
    return {"params": new_params, "team": team, "h": bool(h_next), "rng": rng,
            "t": t + 1, "trust": trust, "gate_trust": gate_trust, "ef": ef,
            "slot_p": slot_p, "prev_theta": theta_team}


def run_rounds(key, cfg, p, batches, server_test, *, dtype=jnp.float32,
               fault=None):
    """Rounds 1..len(batches) from the seed's key.  Returns, per round, the
    server test loss after the round, each leaf's norm of the change from
    the initial parameters (leaves in tree order) and the elected team."""
    with jax.default_matmul_precision(cfg["matmul_precision"]):
        r_init, r_run = jax.random.split(key)
        p0 = init_params(r_init, cfg)
        st = init_state(p0, p, r_run, dtype)
        p0 = st["params"]
        tx = server_test["x"].astype(dtype)
        out = []
        for data in batches:
            st = round_step(st, data, p, dtype=dtype, fault=fault)
            loss, _ = _loss_acc(st["params"], tx, server_test["y"])
            dist = [float(jnp.linalg.norm((a - b).astype(jnp.float32)))
                    for a, b in zip(jax.tree_util.tree_leaves(st["params"]),
                                    jax.tree_util.tree_leaves(p0))]
            out.append({"test_loss": float(loss), "dist": dist,
                        "team": [int(v) for v in np.asarray(st["team"])]})
        return out
