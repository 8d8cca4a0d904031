"""Input generators of the benchmark: one per kind of traffic, read from the
traffic files under ``bench/traffic/``.

``federation`` builds the client-stacked image federation of a federated
cell and its per-round batch sampler (the synthetic Table V federation:
class templates plus pixel noise, Dirichlet label skew, fixed-capacity
client stacks).  ``requests`` draws the request list of a serving cell.

Both are functions of the seed alone.  The serving generator gives every
seed the same multiset of prompt and output lengths, block by block, in a
different order, so that the seed changes which tokens are served and not
how much work a window holds.
"""
from __future__ import annotations

import functools
import math

import numpy as np


def key_seed(seed: int) -> int:
    """A 31-bit seed for ``jax.random.PRNGKey`` from any whole number."""
    return int(np.random.SeedSequence(int(seed)).generate_state(1)[0]) \
        & 0x7FFFFFFF


# --------------------------------------------------------------- federation
def make_images(key, n, *, size=28, n_classes=10, sep=1.5):
    """Per-class rank-4 templates plus pixel noise through a sigmoid:
    (n, size, size, 1) f32 in [0, 1] and (n,) i32 labels."""
    import jax
    import jax.numpy as jnp

    kt, kc, kx = jax.random.split(key, 3)
    rank = 4
    u = jax.random.normal(kt, (n_classes, size, rank))
    v = jax.random.normal(jax.random.fold_in(kt, 1), (n_classes, rank, size))
    templates = jnp.einsum("csr,crt->cst", u, v) / jnp.sqrt(rank)
    y = jax.random.randint(kc, (n,), 0, n_classes)
    x = sep * templates[y] + jax.random.normal(kx, (n, size, size))
    x = jax.nn.sigmoid(x)[..., None]
    return x.astype(jnp.float32), y.astype(jnp.int32)


def dirichlet_partition(rng, labels, n_clients, alpha):
    """Label-skewed client index lists (Dirichlet(alpha) per class)."""
    n_classes = int(labels.max()) + 1
    idx_by_class = [np.where(labels == c)[0] for c in range(n_classes)]
    for idx in idx_by_class:
        rng.shuffle(idx)
    client_idx = [[] for _ in range(n_clients)]
    for idx in idx_by_class:
        props = rng.dirichlet([alpha] * n_clients)
        cuts = (np.cumsum(props) * len(idx)).astype(int)[:-1]
        for k, part in enumerate(np.split(idx, cuts)):
            client_idx[k].extend(part.tolist())
    out = []
    for k in range(n_clients):
        a = np.asarray(client_idx[k], dtype=np.int64)
        rng.shuffle(a)
        if len(a) == 0:
            a = np.array([rng.integers(0, len(labels))], dtype=np.int64)
        out.append(a)
    return out


EVAL_FRAC = 0.2


def stack_clients(x, y, parts, *, eval_frac=EVAL_FRAC):
    """Fixed-capacity (K, cap, ...) train and (K, ecap, ...) eval stacks;
    short clients repeat their own rows; ``n`` holds the true train
    sizes."""
    sizes = np.array([len(p) for p in parts])
    cap = int(sizes.max())
    e_sizes = np.maximum((sizes * eval_frac).astype(int), 1)
    t_sizes = np.maximum(sizes - e_sizes, 1)
    ecap = max(int(e_sizes.max()), 1)

    def take(idx, count, capacity):
        sub = idx[:count]
        if len(sub) == 0:
            sub = idx if len(idx) else np.array([0], dtype=np.int64)
        return np.tile(sub, int(np.ceil(capacity / len(sub))))[:capacity]

    xs, ys, exs, eys = [], [], [], []
    for k, p in enumerate(parts):
        tr = take(p, t_sizes[k], cap)
        ev = take(p[t_sizes[k]:], e_sizes[k], ecap)
        xs.append(x[tr])
        ys.append(y[tr])
        exs.append(x[ev])
        eys.append(y[ev])
    return {"x": np.stack(xs), "y": np.stack(ys),
            "eval_x": np.stack(exs), "eval_y": np.stack(eys),
            "n": t_sizes.astype(np.float32)}


def _pad_rows(a, rows):
    """``a`` (K, cap, ...) zero-padded along axis 1 to ``rows``."""
    pad = [(0, 0), (0, rows - a.shape[1])] + [(0, 0)] * (a.ndim - 2)
    return np.pad(a, pad)


def _sample(data, cap, ecap, key, *, b, eb):
    import jax

    kb, ke = jax.random.split(key)
    k = data["x"].shape[0]
    bi = jax.random.randint(kb, (k, b), 0, cap)
    ei = jax.random.randint(ke, (k, eb), 0, ecap)
    take = jax.vmap(lambda a, i: a[i])
    return {"x": take(data["x"], bi), "y": take(data["y"], bi),
            "eval_x": take(data["eval_x"], ei),
            "eval_y": take(data["eval_y"], ei), "n": data["n"]}


@functools.cache
def _sampler():
    import jax

    return jax.jit(_sample, static_argnames=("b", "eb"))


class Federation:
    """The stacked federation on the device and its per-round sampler:
    ``data_fn(t, key)`` draws ``batch`` train and ``eval_batch`` eval rows
    per client, with replacement, from ``key``, out of the first ``cap``
    (``ecap``) rows of each client's stack.  The stacks are padded to a
    capacity that no seed's partition can pass, and ``cap`` and ``ecap``
    are data, so that every seed runs one compiled sampler."""

    def __init__(self, seed, fed):
        import jax
        import jax.numpy as jnp

        n, holdout = fed["n"], fed["holdout"]
        x, y = make_images(jax.random.PRNGKey(key_seed(seed)), n + holdout,
                           n_classes=fed["n_classes"], sep=fed["sep"])
        x, y = np.asarray(x), np.asarray(y)
        self.server_test = {"x": jnp.asarray(x[n:]), "y": jnp.asarray(y[n:])}
        parts = dirichlet_partition(np.random.default_rng(int(seed)), y[:n],
                                    fed["n_clients"], fed["dirichlet_alpha"])
        stacked = stack_clients(x[:n], y[:n], parts, eval_frac=EVAL_FRAC)
        cap = stacked["x"].shape[1]
        ecap = stacked["eval_x"].shape[1]
        erows = max(int(n * EVAL_FRAC), 1)
        for name, rows in (("x", n), ("y", n), ("eval_x", erows),
                           ("eval_y", erows)):
            stacked[name] = _pad_rows(stacked[name], rows)
        self.data = {k: jnp.asarray(v) for k, v in stacked.items()}
        self._cap = (jnp.int32(cap), jnp.int32(ecap))
        self._b = min(fed["batch"], cap)
        self._eb = min(fed["eval_batch"], ecap)

    def data_fn(self, t, key):
        return _sampler()(self.data, *self._cap, key, b=self._b, eb=self._eb)


# ----------------------------------------------------------------- requests
def _log_uniform_grid(lo, hi, n):
    """n lengths at the midpoints of n equal steps of log-length."""
    u = (np.arange(n) + 0.5) / n
    return np.rint(np.exp(math.log(lo) + u * (math.log(hi) - math.log(lo)))
                   ).astype(int)


def requests(seed, mix, vocab):
    """The request list of a serving cell as (prompt tokens, max_new) pairs.

    ``mix``: ``prompt`` [lo, hi] and ``output`` [lo, hi] log-uniform
    ranges, ``block`` requests per block, ``n_blocks`` blocks.  Every block
    holds the same ``block`` prompt lengths and ``block`` output lengths
    (a log-uniform grid), paired and ordered by a permutation drawn from
    the seed.  The first ``warm`` requests (whole blocks) instead take
    outputs spread evenly over (0, mean output], so that the first slots
    free at a steady pace, as in a server that has been running, rather
    than all at once."""
    rng = np.random.default_rng(int(seed))
    blk, warm = mix["block"], mix["warm"]
    if warm % blk:
        raise ValueError("warm must be a whole number of blocks")
    plens = _log_uniform_grid(*mix["prompt"], blk)
    olens = _log_uniform_grid(*mix["output"], blk)
    mean = float(olens.mean())
    spread = np.maximum(np.rint(mean * (np.arange(warm) + 1) / max(warm, 1)),
                        2).astype(int)
    out = []
    for b in range(mix["n_blocks"]):
        p = plens[rng.permutation(blk)]
        o = olens[rng.permutation(blk)]
        if b * blk < warm:
            o = spread[b * blk:(b + 1) * blk][rng.permutation(blk)]
        for pl, ol in zip(p, o):
            toks = rng.integers(0, vocab, int(pl))
            out.append((tuple(int(t) for t in toks), int(ol)))
    return out
