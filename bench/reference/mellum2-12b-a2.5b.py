"""Plain reference of mellum2-12b-a2.5b (the language model of
JetBrains/Mellum2-12B-A2.5B-Instruct) and the benchmark's weights for it.

``init_params`` makes the random weights of a serving cell from the seed in
one jitted call, laid out as the program's parameter tree (the blocks of one
period of ``attention_period``, each leaf stacked on a leading axis over the
periods) and held in bfloat16, as the configuration serves them; the
reference makes them again from the same seed and computes from them in
float32.  Fan-in truncated normal projections, N(0, 0.02) embedding, unit
norm scales, an untied LM head.

``forward_logits`` is the forward pass in float32 at ``highest`` matmul
precision with no cache, kernel or batching: RMSNorm; grouped-query
attention with rotary positions (rotate-half), causal, and on the
``sliding_attention`` layers bounded to the last ``sliding_window`` keys
(key j is seen from position i when i - window < j <= i); default RoPE at
``rope_theta`` on the sliding layers and YaRN on the full layers (the
inverse frequencies of HF transformers' ``_compute_yarn_parameters``, cos and
sin multiplied by ``yarn_attention_factor``); a top-k softmax router whose k
weights are renormalised, SwiGLU experts computed for every token and
combined by the router weights (no capacity, no token dropped); the LM head.
Attention and the expert layer run over blocks of query rows, so that a
sequence of some thousands of tokens fits.

``quant="fp8"`` computes every matmul from fp8 (e4m3) weights (one scale per
output channel) and activations (one per row): the control that ``correct``
has to reject, one precision below the bfloat16 the configuration computes
in.  ``fault`` puts a broken forward in the program's place:
``"no_window"`` attends every earlier key on the sliding layers,
``"no_yarn"`` uses default RoPE on the full layers.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

ROWS = 512          # query rows per attention and expert block


def _tn(key, shape, fan_in):
    return (1.0 / np.sqrt(fan_in)) * jax.random.truncated_normal(
        key, -2.0, 2.0, shape, jnp.float32)


def padded_vocab(c):
    return -(-c["vocab_size"] // 128) * 128


def _init(key, c):
    period = c["attention_period"]
    units = c["n_layers"] // len(period)
    d, hq, hkv, dh = c["d_model"], c["n_heads"], c["n_kv_heads"], c["head_dim"]
    e, ff = c["n_experts"], c["d_ff"]
    ones = lambda *s: jnp.ones(s, jnp.float32)
    kb, ke, kh = jax.random.split(key, 3)
    layers = {}
    for i, bk in enumerate(jax.random.split(kb, len(period))):
        ks = jax.random.split(bk, 8)
        layers[f"b{i}"] = {
            "ln1": {"scale": ones(units, d)},
            "attn": {"wq": _tn(ks[0], (units, d, hq * dh), d),
                     "wk": _tn(ks[1], (units, d, hkv * dh), d),
                     "wv": _tn(ks[2], (units, d, hkv * dh), d),
                     "wo": _tn(ks[3], (units, hq * dh, d), hq * dh)},
            "ln2": {"scale": ones(units, d)},
            "moe": {"router": _tn(ks[4], (units, d, e), d),
                    "wg": _tn(ks[5], (units, e, d, ff), d),
                    "wu": _tn(ks[6], (units, e, d, ff), d),
                    "wo": _tn(ks[7], (units, e, ff, d), ff)},
        }
    if c["tie_embeddings"]:
        raise ValueError("the configuration's LM head is untied")
    params = {"layers": layers, "ln_f": {"scale": ones(d)},
              "embed": 0.02 * jax.random.normal(ke, (padded_vocab(c), d),
                                                jnp.float32),
              "lm_head": _tn(kh, (d, padded_vocab(c)), d)}
    dtype = jnp.dtype(c["param_dtype"])
    return jax.tree_util.tree_map(lambda x: x.astype(dtype), params)


def _static(c):
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in c.items()))


def init_params(key, c):
    """The cell's weights, made on the device in one jitted call."""
    return jax.jit(functools.partial(_init, c=c))(key)


def param_shapes(c):
    return jax.eval_shape(functools.partial(_init, c=c),
                          jax.random.PRNGKey(0))


# ------------------------------------------------------------------ forward
def _fp8(x, axis):
    """fp8 (e4m3) fake quantisation with one absmax scale per slice along
    ``axis`` (the absmax maps to 448, e4m3's largest value)."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(x.dtype) * s


def _mm(x, w, quant, spec="...d,df->...f"):
    """A float32 product; a bfloat16 weight is widened where it is used,
    so that no float32 copy of a whole layer is held."""
    w = w.astype(jnp.float32)
    if quant == "fp8":
        x, w = _fp8(x, -1), _fp8(w, -2)
    return jnp.einsum(spec, x, w)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(jnp.float32)


def rope_tables(c, kind, fault=None):
    """(inverse frequencies, cos/sin factor) of a layer kind."""
    dh, theta = c["head_dim"], c["rope_theta"]
    base = theta ** (np.arange(0, dh, 2, dtype=np.float32) / dh)
    inv = (1.0 / base).astype(np.float32)
    if kind != "full_attention" or fault == "no_yarn":
        return inv, 1.0
    factor, orig = c["yarn_factor"], c["yarn_original_max_pos"]

    def dim(rotations):
        return dh * math.log(orig / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(dim(c["yarn_beta_fast"])), 0)
    high = min(math.ceil(dim(c["yarn_beta_slow"])), dh - 1)
    ramp = np.clip((np.arange(dh // 2, dtype=np.float32) - low)
                   / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp                       # share of the unscaled frequency
    inv = (1.0 / (factor * base)) * (1.0 - keep) + inv * keep
    return inv.astype(np.float32), c["yarn_attention_factor"]


def _rope(x, pos, inv, factor):
    ang = pos[:, None].astype(jnp.float32) * jnp.asarray(inv)  # (T, dh/2)
    cos = jnp.cos(ang)[:, None] * factor
    sin = jnp.sin(ang)[:, None] * factor
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(x, lp, c, kind, quant, fault):
    t, d = x.shape
    hq, hkv, dh = c["n_heads"], c["n_kv_heads"], c["head_dim"]
    g = hq // hkv
    window = c["sliding_window"] if (kind == "sliding_attention"
                                     and fault != "no_window") else 0
    inv, factor = rope_tables(c, kind, fault)
    pos = jnp.arange(t)
    a, m = lp["attn"], lp["moe"]
    h = _rms(x, lp["ln1"]["scale"], c["norm_eps"])
    q = _rope(_mm(h, a["wq"], quant).reshape(t, hq, dh), pos, inv, factor)
    k = _rope(_mm(h, a["wk"], quant).reshape(t, hkv, dh), pos, inv, factor)
    v = _mm(h, a["wv"], quant).reshape(t, hkv, dh)
    rows = min(ROWS, t)

    def block(i):
        lo = i * rows
        qb = jax.lax.dynamic_slice_in_dim(q, lo, rows).reshape(
            rows, hkv, g, dh)
        s = jnp.einsum("shgd,thd->hgst", qb * dh ** -0.5, k)
        qpos = lo + jnp.arange(rows)[:, None]
        seen = pos[None, :] <= qpos
        if window:
            seen &= pos[None, :] > qpos - window
        p = jax.nn.softmax(jnp.where(seen, s, -1e30), axis=-1)
        o = jnp.einsum("hgst,thd->shgd", p, v).reshape(rows, hq * dh)
        xb = jax.lax.dynamic_slice_in_dim(x, lo, rows) + _mm(o, a["wo"], quant)
        y = _rms(xb, lp["ln2"]["scale"], c["norm_eps"])
        probs = jax.nn.softmax(_mm(y, m["router"], quant), axis=-1)
        top_p, top_e = jax.lax.top_k(probs, c["top_k"])
        top_p = top_p / jnp.sum(top_p, -1, keepdims=True)
        gate = jnp.zeros_like(probs).at[jnp.arange(rows)[:, None],
                                        top_e].set(top_p)
        hg = _mm(y, m["wg"], quant, "td,edf->tef")
        hu = _mm(y, m["wu"], quant, "td,edf->tef")
        eo = _mm(jax.nn.silu(hg) * hu, m["wo"], quant, "tef,efd->ted")
        return xb + jnp.einsum("ted,te->td", eo, gate)

    return jax.lax.map(block, jnp.arange(t // rows)).reshape(t, d)


@functools.partial(jax.jit, static_argnames=("c", "quant", "fault"))
def _forward(params, tokens, c, quant, fault):
    c = dict(c)
    x = params["embed"][tokens].astype(jnp.float32)
    period = c["attention_period"]

    def body(x, unit):
        for i, kind in enumerate(period):
            x = _layer(x, unit[f"b{i}"], c, kind, quant, fault)
        return x, None

    x, _ = jax.lax.scan(body, x, params["layers"])
    x = _rms(x, params["ln_f"]["scale"], c["norm_eps"])
    return _mm(x, params["lm_head"][:, :c["vocab_size"]], quant)


def forward_logits(params, c, tokens, quant=None, fault=None):
    """(T, vocab) logits of a token sequence at every position; T is a
    multiple of ``ROWS`` or at most ``ROWS``."""
    with jax.default_matmul_precision("highest"):
        return _forward(params, jnp.asarray(tokens, jnp.int32), _static(c),
                        quant, fault)


def served_gaps(key, c, seqs, max_len, *, quant=None, fault=None):
    """For each (prompt, served tokens) pair: at every served position, how
    far the reference's logit of the served token lies below its best
    (float32).  With ``quant`` or ``fault`` the served token is replaced by
    the token that the quantised or broken forward puts first at that
    position: the control's and the fault's reading.  Every sequence is
    padded at its end to ``max_len`` rounded up to whole blocks of rows, so
    that one program serves them all (positions after a sequence change
    none of its causal logits)."""
    params = init_params(key, c)
    length = max_len if max_len <= ROWS else -(-max_len // ROWS) * ROWS
    out = []
    for prompt, served in seqs:
        toks = list(prompt) + list(served[:-1])
        pad = np.zeros((length,), np.int32)
        pad[:len(toks)] = toks
        lo, hi = len(prompt) - 1, len(prompt) - 1 + len(served)
        ref = forward_logits(params, c, pad)[lo:hi]
        if quant is None and fault is None:
            pick = jnp.asarray(served, jnp.int32)
        else:
            pick = jnp.argmax(
                forward_logits(params, c, pad, quant, fault)[lo:hi], -1)
        got = jnp.take_along_axis(ref, pick[:, None], 1)[:, 0]
        out.append(np.asarray(jnp.max(ref, -1) - got))
    return out
