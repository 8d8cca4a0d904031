"""Plain reference of granite-moe-1b-a400m (the language model of
ibm-granite/granite-3.0-1b-a400m-base) and the benchmark's weights for it.

``init_params`` makes the random weights of a serving cell from the seed in
one jitted call, leaf stack by leaf stack, laid out as the program's
parameter tree (per-layer leaves stacked on a leading layer axis); the
program is served these weights and the reference makes them again from
the same seed.  Fan-in truncated normal projections, N(0, 0.02) embedding,
unit norm scales.

``forward_logits`` is the forward pass in float32 at ``highest`` matmul
precision with no cache, kernel or batching: RMSNorm, grouped-query
attention with rotary positions (rotate-half), a top-k softmax router whose
k weights are renormalised, SwiGLU experts computed for every token and
combined by the router weights (no capacity, no token dropped), and the
LM head tied to the embedding.  The published model's scalar multipliers
(embedding, attention, residual, logits) are omitted, as the program omits
them; they change no shape and no work.

``quant="fp8"`` computes every matmul from fp8 (e4m3) weights (one scale
per output channel) and activations (one per row), the control that
``correct`` has to reject: one precision below the bfloat16 the
configuration computes in.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def _tn(key, shape, fan_in):
    return (1.0 / np.sqrt(fan_in)) * jax.random.truncated_normal(
        key, -2.0, 2.0, shape, jnp.float32)


def padded_vocab(c):
    return -(-c["vocab_size"] // 128) * 128


def _init(key, c):
    L, d, hq, hkv, dh = (c["n_layers"], c["d_model"], c["n_heads"],
                         c["n_kv_heads"], c["head_dim"])
    e, ff = c["n_experts"], c["d_ff"]
    ks = jax.random.split(key, 9)
    ones = lambda *s: jnp.ones(s, jnp.float32)
    layer = {
        "ln1": {"scale": ones(L, d)},
        "attn": {"wq": _tn(ks[0], (L, d, hq * dh), d),
                 "wk": _tn(ks[1], (L, d, hkv * dh), d),
                 "wv": _tn(ks[2], (L, d, hkv * dh), d),
                 "wo": _tn(ks[3], (L, hq * dh, d), hq * dh)},
        "ln2": {"scale": ones(L, d)},
        "moe": {"router": _tn(ks[4], (L, d, e), d),
                "wg": _tn(ks[5], (L, e, d, ff), d),
                "wu": _tn(ks[6], (L, e, d, ff), d),
                "wo": _tn(ks[7], (L, e, ff, d), ff)},
    }
    params = {"layers": {"b0": layer}, "ln_f": {"scale": ones(d)},
              "embed": 0.02 * jax.random.normal(ks[8], (padded_vocab(c), d),
                                                jnp.float32)}
    if not c["tie_embeddings"]:
        raise ValueError("the configuration ties the LM head to the "
                         "embedding")
    return params


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
    if quant == "fp8":
        x, w = _fp8(x, -1), _fp8(w, -2)
    return jnp.einsum(spec, x, w)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, pos, theta):
    dh = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = pos[:, None].astype(jnp.float32) * freqs          # (T, dh/2)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(x, lp, c, quant):
    t = x.shape[0]
    hq, hkv, dh = c["n_heads"], c["n_kv_heads"], c["head_dim"]
    g = hq // hkv
    pos = jnp.arange(t)
    h = _rms(x, lp["ln1"]["scale"], c["norm_eps"])
    a = lp["attn"]
    q = _rope(_mm(h, a["wq"], quant).reshape(t, hkv, g, dh).reshape(
        t, hq, dh), pos, c["rope_theta"])
    k = _rope(_mm(h, a["wk"], quant).reshape(t, hkv, dh), pos, c["rope_theta"])
    v = _mm(h, a["wv"], quant).reshape(t, hkv, dh)
    q = q.reshape(t, hkv, g, dh)
    s = jnp.einsum("shgd,thd->hgst", q * dh ** -0.5, k)
    causal = pos[None, :] <= pos[:, None]
    p = jax.nn.softmax(jnp.where(causal, s, -1e30), axis=-1)
    o = jnp.einsum("hgst,thd->shgd", p, v).reshape(t, hq * dh)
    x = x + _mm(o, a["wo"], quant)

    m = lp["moe"]
    y = _rms(x, lp["ln2"]["scale"], c["norm_eps"])
    probs = jax.nn.softmax(_mm(y, m["router"], quant), axis=-1)
    top_p, top_e = jax.lax.top_k(probs, c["top_k"])
    top_p = top_p / jnp.sum(top_p, -1, keepdims=True)
    gate = jnp.zeros_like(probs).at[jnp.arange(t)[:, None], top_e].set(top_p)
    hg = _mm(y, m["wg"], quant, "td,edf->tef")
    hu = _mm(y, m["wu"], quant, "td,edf->tef")
    eo = _mm(jax.nn.silu(hg) * hu, m["wo"], quant, "tef,efd->ted")
    return x + jnp.einsum("ted,te->td", eo, gate)


@functools.partial(jax.jit, static_argnames=("c", "quant"))
def _forward(params, tokens, c, quant):
    c = dict(c)
    x = params["embed"][tokens]

    def body(x, lp):
        return _layer(x, lp, c, quant), None

    x, _ = jax.lax.scan(body, x, params["layers"]["b0"])
    x = _rms(x, params["ln_f"]["scale"], c["norm_eps"])
    return _mm(x, params["embed"][:c["vocab_size"]].T, quant)


def forward_logits(params, c, tokens, quant=None):
    """(T, vocab) logits of a token sequence at every position."""
    with jax.default_matmul_precision("highest"):
        return _forward(params, jnp.asarray(tokens, jnp.int32),
                        tuple(sorted(c.items())), quant)


def served_gaps(key, c, seqs, max_len, *, quant=None):
    """For each (prompt, served tokens) pair: at every served position, how
    far the reference's logit of the served token lies below its best
    (float32).  With ``quant`` the served token is replaced by the token
    that the quantised forward puts first at that position: the control's
    reading.  Each sequence is padded at its end to the next multiple of
    ``max_len // 8`` (at least 128), so that a few programs serve them all
    and little of the reference's time goes to padding (positions after a
    sequence change none of its causal logits)."""
    params = init_params(key, c)
    step = max(128, max_len // 8)
    out = []
    for prompt, served in seqs:
        toks = list(prompt) + list(served[:-1])
        pad = np.zeros((min(max_len, -(-len(toks) // step) * step),),
                       np.int32)
        pad[:len(toks)] = toks
        lo, hi = len(prompt) - 1, len(prompt) - 1 + len(served)
        ref = forward_logits(params, c, pad)[lo:hi]
        if quant is None:
            pick = jnp.asarray(served, jnp.int32)
        else:
            pick = jnp.argmax(forward_logits(params, c, pad, quant)[lo:hi], -1)
        got = jnp.take_along_axis(ref, pick[:, None], 1)[:, 0]
        out.append(np.asarray(jnp.max(ref, -1) - got))
    return out
