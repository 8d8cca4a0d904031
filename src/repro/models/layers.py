"""Shared parameter-init helpers and primitive layers (pure functional JAX).

Parameters are plain nested dicts of jnp arrays; every layer is an
``init_*(key, ...) -> params`` + ``*_fwd(params, x, ...) -> y`` pair.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def dense_init(key, shape, in_axis=0, dtype=jnp.float32):
    """Truncated-normal fan-in init (LeCun-style)."""
    fan_in = np.prod([shape[i] for i in np.atleast_1d(in_axis)])
    std = 1.0 / np.sqrt(max(fan_in, 1))
    return std * jax.random.truncated_normal(key, -2.0, 2.0, shape, dtype)


def embed_init(key, shape, dtype=jnp.float32):
    return jax.random.normal(key, shape, dtype) * 0.02


# ----------------------------------------------------------------------
def rms_norm(x, scale, eps=1e-5):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def init_rms_norm(d):
    return {"scale": jnp.ones((d,), jnp.float32)}


def group_norm(x, scale, n_groups, eps=1e-5):
    """Per-head group norm used by xLSTM cells. x: (..., d)."""
    *lead, d = x.shape
    x32 = x.astype(jnp.float32).reshape(*lead, n_groups, d // n_groups)
    mu = x32.mean(-1, keepdims=True)
    var = x32.var(-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    y = y.reshape(*lead, d)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


# ----------------------------------------------------------------------
# Rotary position embeddings
# ----------------------------------------------------------------------
def rope_freqs(head_dim, theta):
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def yarn_freqs(head_dim, theta, factor, original_max_pos):
    """YaRN inverse frequencies, as HF transformers'
    ``_compute_yarn_parameters`` makes them (``truncate`` on, its default
    betas 32 and 1): a linear ramp over the frequency indices [low, high]
    blends the default frequencies (kept below ``low``) with the same
    divided by ``factor`` (taken above ``high``)."""
    beta_fast, beta_slow = 32.0, 1.0

    def correction_dim(rotations):
        return (head_dim * math.log(original_max_pos
                                    / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), head_dim - 1)
    if low == high:
        high += 0.001
    extrapolation = rope_freqs(head_dim, theta)
    ramp = jnp.clip((jnp.arange(head_dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    return extrapolation / factor * ramp + extrapolation * (1.0 - ramp)


def apply_rope(x, positions, theta, *, freqs=None, scale=1.0):
    """x: (B, S, H, dh); positions: (B, S) or (S,) int32.  ``freqs``
    replaces the default frequencies of ``theta`` (YaRN), and ``scale``
    multiplies cos and sin (YaRN's attention factor)."""
    dh = x.shape[-1]
    if freqs is None:
        freqs = rope_freqs(dh, theta)                   # (dh/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (B,S,dh/2)|(S,dh/2)
    if angles.ndim == 2:                                # (S, dh/2) -> (1,S,dh/2)
        angles = angles[None]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    if scale != 1.0:
        cos, sin = cos * scale, sin * scale
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ----------------------------------------------------------------------
# SwiGLU MLP
# ----------------------------------------------------------------------
def init_mlp(key, d_model, d_ff, dtype=jnp.float32):
    kg, ku, ko = jax.random.split(key, 3)
    return {
        "wg": dense_init(kg, (d_model, d_ff), dtype=dtype),
        "wu": dense_init(ku, (d_model, d_ff), dtype=dtype),
        "wo": dense_init(ko, (d_ff, d_model), dtype=dtype),
    }


def mlp_fwd(params, x, dtype):
    h = jax.nn.silu(x @ params["wg"].astype(dtype)) * (x @ params["wu"].astype(dtype))
    return h @ params["wo"].astype(dtype)


def causal_depthwise_conv(x, kernel, bias, state=None):
    """Causal depthwise 1D conv. x: (B, S, C); kernel: (K, C).

    If ``state`` (B, K-1, C) is given, runs a single-step decode update and
    returns (y, new_state) with S expected == 1.
    """
    K = kernel.shape[0]
    if state is not None:
        window = jnp.concatenate([state, x], axis=1)      # (B, K, C)
        y = jnp.einsum("bkc,kc->bc", window.astype(jnp.float32),
                       kernel.astype(jnp.float32))[:, None]
        y = (y + bias.astype(jnp.float32)).astype(x.dtype)
        return y, window[:, 1:]
    pad = jnp.zeros(x.shape[:1] + (K - 1,) + x.shape[2:], x.dtype)
    xp = jnp.concatenate([pad, x], axis=1)                # (B, S+K-1, C)
    y = jax.lax.conv_general_dilated(
        xp.astype(jnp.float32),
        kernel[:, None, :].astype(jnp.float32),           # (K, 1, C)
        window_strides=(1,),
        padding="VALID",
        dimension_numbers=("NWC", "WIO", "NWC"),
        feature_group_count=x.shape[-1],
    )
    return (y + bias.astype(jnp.float32)).astype(x.dtype), None
