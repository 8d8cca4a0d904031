"""Composable decoder-only transformer covering all assigned architectures.

Layer stacking: the per-layer block pattern (cfg.layers) is always a
repetition of a short *cycle* (length 1 for homogeneous stacks, 5 for the
VLM's every-5th cross-attn, 8 for xLSTM's 7:1 mix). Parameters for each
cycle *unit* are stacked along a leading axis and the stack is executed
with ``lax.scan`` — compile time scales with the cycle size, not with
n_layers (needed for the 80/100-layer dry-runs), and ``jax.checkpoint``
on the scan body gives per-unit activation rematerialisation.

Modes:
  train/prefill : full-sequence forward (cache=None -> no cache,
                  cache given -> prefill fills it)
  decode        : S=1 step against KV/SSM caches (decode_32k, long_500k)
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.models import attention as attn_lib
from repro.models import moe as moe_lib
from repro.models import ssm as ssm_lib
from repro.models import xlstm as xlstm_lib
from repro.models.layers import (dense_init, embed_init, init_mlp,
                                 init_rms_norm, mlp_fwd, rms_norm)


def _unit(cfg):
    """The repeating unit of (block kind, attention kind) pairs over the
    layers; (unit, n_units)."""
    pattern = tuple(zip(cfg.layers, cfg.attn_types))
    n = len(pattern)
    for c in range(1, n + 1):
        if n % c == 0 and pattern == pattern[:c] * (n // c):
            return pattern[:c], n // c
    return pattern, 1


def layer_cycle(cfg):
    """The repeating unit of cfg.layers (and of cfg.attn_types with it);
    (cycle of block kinds, n_units)."""
    unit, n_units = _unit(cfg)
    return tuple(kind for kind, _ in unit), n_units


def unit_attn_types(cfg):
    """Attention kind of each block of the scan unit."""
    return tuple(t for _, t in _unit(cfg)[0])


def unit_windows(cfg):
    """Attention window of each block of the scan unit (0 = full)."""
    return tuple(cfg.window_of(t) for t in unit_attn_types(cfg))


# ----------------------------------------------------------------------
# init
# ----------------------------------------------------------------------
def _init_block(key, kind, cfg):
    ks = jax.random.split(key, 8)
    d = cfg.d_model
    if kind == "attn":
        return {
            "ln1": init_rms_norm(d),
            "attn": attn_lib.init_attention(ks[0], cfg),
            "ln2": init_rms_norm(d),
            "mlp": init_mlp(ks[1], d, cfg.d_ff),
        }
    if kind == "moe":
        return {
            "ln1": init_rms_norm(d),
            "attn": attn_lib.init_attention(ks[0], cfg),
            "ln2": init_rms_norm(d),
            "moe": moe_lib.init_moe(ks[1], cfg),
        }
    if kind == "hybrid":
        return {
            "ln1": init_rms_norm(d),
            "attn": attn_lib.init_attention(ks[0], cfg),
            "mamba": ssm_lib.init_mamba(ks[1], cfg),
            "lna": init_rms_norm(d),
            "lnm": init_rms_norm(d),
            "ln2": init_rms_norm(d),
            "mlp": init_mlp(ks[2], d, cfg.d_ff),
        }
    if kind == "xattn":
        return {
            "ln1": init_rms_norm(d),
            "xattn": attn_lib.init_attention(ks[0], cfg, cross=True),
            "gate": jnp.zeros((), jnp.float32),   # zero-init cross-attn gate
            "ln2": init_rms_norm(d),
            "mlp": init_mlp(ks[1], d, cfg.d_ff),
        }
    if kind == "mlstm":
        return {"ln1": init_rms_norm(d), "mlstm": xlstm_lib.init_mlstm(ks[0], cfg)}
    if kind == "slstm":
        return {"ln1": init_rms_norm(d), "slstm": xlstm_lib.init_slstm(ks[0], cfg)}
    raise ValueError(kind)


def init_transformer(key, cfg):
    cycle, n_units = layer_cycle(cfg)
    keys = jax.random.split(key, n_units + 3)
    units = []
    for u in range(n_units):
        uks = jax.random.split(keys[u], len(cycle))
        units.append({f"b{i}": _init_block(uks[i], kind, cfg)
                      for i, kind in enumerate(cycle)})
    stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *units) \
        if n_units > 1 else jax.tree_util.tree_map(lambda x: x[None], units[0])
    params = {"layers": stacked, "ln_f": init_rms_norm(cfg.d_model)}
    if cfg.embed_inputs:
        params["embed"] = embed_init(keys[-1], (cfg.padded_vocab, cfg.d_model))
    if not cfg.tie_embeddings or not cfg.embed_inputs:
        params["lm_head"] = dense_init(keys[-2],
                                       (cfg.d_model, cfg.padded_vocab))
    pdt = jnp.dtype(cfg.param_dtype)
    if pdt != jnp.float32:
        params = jax.tree_util.tree_map(lambda x: x.astype(pdt), params)
    return params


# ----------------------------------------------------------------------
# per-block forward
# ----------------------------------------------------------------------
def _block_fwd(bp, kind, x, cfg, positions, cache, image_embeds, window,
               rope):
    dtype = x.dtype
    eps = cfg.norm_eps
    if kind in ("attn", "moe"):
        h, new_cache = attn_lib.attention_fwd(
            bp["attn"], rms_norm(x, bp["ln1"]["scale"], eps), cfg, positions,
            window=window, rope=rope, cache=cache)
        x = x + h
        y = rms_norm(x, bp["ln2"]["scale"], eps)
        if kind == "moe":
            m, aux = moe_lib.moe_fwd(bp["moe"], y, cfg)
        else:
            m, aux = mlp_fwd(bp["mlp"], y, dtype), 0.0
        return x + m, new_cache, aux
    if kind == "hybrid":
        y = rms_norm(x, bp["ln1"]["scale"], eps)
        a_cache = cache["attn"] if cache is not None else None
        m_cache = cache["mamba"] if cache is not None else None
        ha, na = attn_lib.attention_fwd(bp["attn"], y, cfg, positions,
                                        window=window, rope=rope,
                                        cache=a_cache)
        hm, nm = ssm_lib.mamba_fwd(bp["mamba"], y, cfg, state=m_cache)
        h = 0.5 * (rms_norm(ha, bp["lna"]["scale"], eps)
                   + rms_norm(hm, bp["lnm"]["scale"], eps))
        x = x + h
        y = rms_norm(x, bp["ln2"]["scale"], eps)
        new_cache = None if cache is None else {"attn": na, "mamba": nm}
        return x + mlp_fwd(bp["mlp"], y, dtype), new_cache, 0.0
    if kind == "xattn":
        h, new_cache = attn_lib.attention_fwd(
            bp["xattn"], rms_norm(x, bp["ln1"]["scale"], eps), cfg, positions,
            cache=cache, kv_source=image_embeds)
        x = x + jnp.tanh(bp["gate"]).astype(dtype) * h
        y = rms_norm(x, bp["ln2"]["scale"], eps)
        return x + mlp_fwd(bp["mlp"], y, dtype), new_cache, 0.0
    if kind == "mlstm":
        h, ns = xlstm_lib.mlstm_fwd(
            bp["mlstm"], rms_norm(x, bp["ln1"]["scale"], eps), cfg, state=cache)
        return x + h, ns, 0.0
    if kind == "slstm":
        h, ns = xlstm_lib.slstm_fwd(
            bp["slstm"], rms_norm(x, bp["ln1"]["scale"], eps), cfg, state=cache)
        return x + h, ns, 0.0
    raise ValueError(kind)


# ----------------------------------------------------------------------
# cache construction
# ----------------------------------------------------------------------
def init_cache(cfg, batch, max_len, *, ring=False, dtype=jnp.bfloat16):
    """Stacked (n_units-leading) cache pytree matching the layer scan."""
    cycle, n_units = layer_cycle(cfg)

    def one(kind, window):
        # ring caches bound memory at the layer's window size
        W = min(max_len, window) if (ring and window) else max_len
        if kind in ("attn", "moe"):
            return attn_lib.init_kv_cache(cfg, batch, W, ring=ring, dtype=dtype)
        if kind == "hybrid":
            mamba_p = {"A_log": jnp.zeros((cfg.d_inner, cfg.ssm_state)),
                       "conv_w": jnp.zeros((cfg.ssm_conv, cfg.d_inner))}
            return {"attn": attn_lib.init_kv_cache(cfg, batch, W, ring=ring,
                                                   dtype=dtype),
                    "mamba": ssm_lib.init_mamba_state(mamba_p, batch, cfg, dtype)}
        if kind == "xattn":
            hkv, dh = cfg.n_kv_heads, cfg.resolved_head_dim
            z = jnp.zeros((batch, cfg.n_image_tokens, hkv, dh), dtype)
            return {"ck": z, "cv": z}
        if kind == "mlstm":
            di = 4 * cfg.d_model  # up-proj factor 2 -> d_inner = 2*d ; wq in di
            H = cfg.n_heads
            dh = (2 * cfg.d_model) // H
            return {"C": jnp.zeros((batch, H, dh, dh), jnp.float32),
                    "n": jnp.zeros((batch, H, dh), jnp.float32),
                    "m": jnp.full((batch, H), -1e30, jnp.float32),
                    "conv": jnp.zeros((batch, cfg.ssm_conv - 1, 2 * cfg.d_model),
                                      dtype)}
        if kind == "slstm":
            H, dh = cfg.n_heads, cfg.d_model // cfg.n_heads
            z = jnp.zeros((batch, H, dh), jnp.float32)
            return {"c": z, "n": z,
                    "m": jnp.full((batch, H, dh), -1e30, jnp.float32), "h": z}
        raise ValueError(kind)

    unit = {f"b{i}": one(kind, w)
            for i, (kind, w) in enumerate(zip(cycle, unit_windows(cfg)))}
    return jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x[None], (n_units,) + x.shape), unit)


# ----------------------------------------------------------------------
# full forward
# ----------------------------------------------------------------------
def forward(params, cfg, *, tokens=None, embeds=None, image_embeds=None,
            positions=None, cache=None, collect_logits=True):
    """Returns (logits or hidden, new_cache, aux_loss).

    tokens: (B, S) int32 or embeds: (B, S, d) when cfg.embed_inputs=False.
    """
    dtype = jnp.dtype(cfg.dtype)
    if embeds is None:
        x = params["embed"].astype(dtype)[tokens]
    else:
        x = embeds.astype(dtype)
    B, S, _ = x.shape
    if positions is None:
        positions = jnp.arange(S)[None, :]
    cycle, n_units = layer_cycle(cfg)
    kinds = unit_attn_types(cfg)

    def unit_fwd(x, unit_params, unit_cache):
        new_cache = {} if unit_cache is not None else None
        aux = 0.0
        for i, kind in enumerate(cycle):
            c_in = None if unit_cache is None else unit_cache[f"b{i}"]
            x, c_out, a = _block_fwd(unit_params[f"b{i}"], kind, x, cfg,
                                     positions, c_in, image_embeds,
                                     cfg.window_of(kinds[i]),
                                     attn_lib.rope_of(cfg, kinds[i]))
            if new_cache is not None:
                new_cache[f"b{i}"] = c_out
            aux = aux + a
        return x, new_cache, aux

    if cfg.remat:
        unit_fwd = jax.checkpoint(unit_fwd)

    def scan_body(x, xs):
        unit_params, unit_cache = xs
        x, new_cache, aux = unit_fwd(x, unit_params, unit_cache)
        return x, (new_cache, aux)

    if cfg.scan_unroll:
        # python loop over units (dry-run cost probes / tiny models):
        # avoids while-loops so HloCostAnalysis sees every layer
        aux = 0.0
        caches = []
        for u in range(n_units):
            up = jax.tree_util.tree_map(lambda l: l[u], params["layers"])
            uc = (None if cache is None else
                  jax.tree_util.tree_map(lambda l: l[u], cache))
            x, nc, a = unit_fwd(x, up, uc)
            aux = aux + a
            caches.append(nc)
        new_cache = (None if cache is None else
                     jax.tree_util.tree_map(lambda *xs: jnp.stack(xs),
                                            *caches))
        aux = jnp.asarray(aux)
    elif cache is None:
        # scan over units with cache=None: ys carries only aux
        def body_nc(x, up):
            x, _, aux = unit_fwd(x, up, None)
            return x, aux
        x, auxs = jax.lax.scan(body_nc, x, params["layers"])
        new_cache = None
        aux = jnp.sum(jnp.asarray(auxs))
    else:
        x, (new_cache, auxs) = jax.lax.scan(scan_body, x,
                                            (params["layers"], cache))
        aux = jnp.sum(jnp.asarray(auxs))

    x = rms_norm(x, params["ln_f"]["scale"], cfg.norm_eps)
    if not collect_logits:
        return x, new_cache, aux
    logits = lm_head(params, cfg, x)
    return logits, new_cache, aux


def lm_head(params, cfg, x):
    dtype = x.dtype
    if "lm_head" in params:
        logits = x @ params["lm_head"].astype(dtype)
    else:
        logits = x @ params["embed"].astype(dtype).T
    if cfg.padded_vocab != cfg.vocab_size:
        # mask padded vocab entries out of softmax/argmax
        pad_mask = jnp.arange(cfg.padded_vocab) < cfg.vocab_size
        logits = jnp.where(pad_mask, logits, jnp.asarray(-1e30, logits.dtype))
    return logits


def cross_entropy(logits, targets, mask=None):
    """Mean CE over valid tokens; also returns accuracy. fp32 numerics."""
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    ll = logz - gold
    correct = (jnp.argmax(logits, -1) == targets).astype(jnp.float32)
    if mask is None:
        mask = jnp.ones_like(ll)
    denom = jnp.maximum(mask.sum(), 1.0)
    return (ll * mask).sum() / denom, (correct * mask).sum() / denom


def loss_fn(params, cfg, batch):
    """batch: {tokens|embeds, targets, [image_embeds], [mask]} -> (loss, metrics).

    cfg.loss_chunk > 0 chunks the LM head + CE over the sequence dim to
    avoid materialising (B, S, vocab) logits.
    """
    hidden, _, aux = forward(
        params, cfg,
        tokens=batch.get("tokens"), embeds=batch.get("embeds"),
        image_embeds=batch.get("image_embeds"), collect_logits=False)
    targets = batch["targets"]
    mask = batch.get("mask")
    S = hidden.shape[1]
    chunk = cfg.loss_chunk
    if chunk and S > chunk and S % chunk == 0:
        n = S // chunk
        h = hidden.reshape(hidden.shape[0], n, chunk, -1).transpose(1, 0, 2, 3)
        t = targets.reshape(targets.shape[0], n, chunk).transpose(1, 0, 2)
        m = (mask.reshape(mask.shape[0], n, chunk).transpose(1, 0, 2)
             if mask is not None else jnp.ones_like(t, jnp.float32))

        def body(carry, xs):
            hc, tc, mc = xs
            logits = lm_head(params, cfg, hc).astype(jnp.float32)
            logz = jax.nn.logsumexp(logits, axis=-1)
            gold = jnp.take_along_axis(logits, tc[..., None], -1)[..., 0]
            correct = (jnp.argmax(logits, -1) == tc).astype(jnp.float32)
            loss_sum, acc_sum, msum = carry
            return (loss_sum + ((logz - gold) * mc).sum(),
                    acc_sum + (correct * mc).sum(), msum + mc.sum()), None

        (ls, accs, ms), _ = jax.lax.scan(
            body, (jnp.float32(0), jnp.float32(0), jnp.float32(0)), (h, t, m),
            unroll=n if cfg.scan_unroll else 1)
        loss = ls / jnp.maximum(ms, 1.0)
        acc = accs / jnp.maximum(ms, 1.0)
    else:
        logits = lm_head(params, cfg, hidden)
        loss, acc = cross_entropy(logits, targets, mask)
    total = loss + aux
    return total, {"loss": loss, "acc": acc, "aux": aux}
