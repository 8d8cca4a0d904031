"""jit'd public wrapper for the flash attention kernel.

Accepts the model's (B, S, H, dh) layout, transposes to the kernel's
(B, H, S, dh), and runs the kernel compiled on the TPU or in interpret
mode elsewhere (``kernels.backend``).  A shape the kernel cannot tile
(S or dh not a multiple of 128) falls back to the ref only in interpret
mode, for tiny smoke sizes; on the TPU it raises.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.backend import resolve_interpret
from repro.kernels.flash_attention import flash_attention_fwd
from repro.kernels.flash_attention_ref import flash_attention_ref


@functools.partial(jax.jit, static_argnames=("causal", "window", "interpret"))
def flash_attention(q, k, v, *, causal=True, window=0, interpret=None):
    """q: (B, S, Hq, dh); k/v: (B, S, Hkv, dh) -> (B, S, Hq, dh)."""
    B, S, Hq, dh = q.shape
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    interpret = resolve_interpret(interpret)
    if S % 128 != 0 or dh % 128 != 0:
        if not interpret:
            raise ValueError(
                f"flash_attention cannot tile S={S}, dh={dh} on the TPU "
                "(both must be multiples of 128)")
        out = flash_attention_ref(qt, kt, vt, causal=causal, window=window)
    else:
        out = flash_attention_fwd(qt, kt, vt, causal=causal, window=window,
                                  interpret=interpret)
    return out.transpose(0, 2, 1, 3)
