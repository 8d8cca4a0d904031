"""Pallas flash-decode over a paged KV cache (the serving decode kernel).

One query token per slot attends over that slot's pages, gathered
straight from the (N, Hkv, page, dh) pool into VMEM via the per-slot
page table — no contiguous K/V copy, so eviction never compacts.  The
pool is head-major so that each (page, dh) block is a whole trailing
tile of the pool: the block the kernel fetches for (page, head) is the
pool's own last two dims, which tiles on the TPU for any head count and
any head dim.

Grid ``(S, Hkv, maxp)`` with the page axis minor-most; the page table
and per-slot visible-key counts ride scalar prefetch
(``PrefetchScalarGridSpec``), so the k/v BlockSpec index maps read
``table[s, p]`` to pick which pool page the next block DMA fetches.
Online-softmax accumulators (acc, m, l) live in VMEM scratch and carry
across the page axis exactly like kernels/flash_attention.py carries
across KV blocks; dead pages (``p*page >= lengths[s]``) are skipped with
``pl.when`` (their DMA still lands — table entries for unallocated pages
are 0, a valid pool index — but no FLOPs are spent).

The int8 path fuses dequantization into the page loads: codes are
fetched as int8 (quarter the bytes of f32) and the per-(row, head) f32
scales, stored as one (1, page) row per (page, head), are applied to
the rows where they act — the key scales to the (g, page) scores, the
value scales to the probabilities before the PV product — so the
unquantized K/V never exist in HBM or VMEM.

Parity oracle: kernels/paged_decode_ref.py (contract documented there).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import resolve_interpret

NEG_INF = -1e30


def _kernel(table_ref, len_ref, q_ref, k_ref, v_ref, *rest,
            page: int, maxp: int, int8: bool):
    if int8:
        ks_ref, vs_ref, o_ref, acc_ref, m_ref, l_ref = rest
    else:
        o_ref, acc_ref, m_ref, l_ref = rest
    s = pl.program_id(0)
    p = pl.program_id(2)

    @pl.when(p == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    n_keys = len_ref[s]

    @pl.when(p * page < n_keys)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)        # (g, dh)
        q = q * (q.shape[-1] ** -0.5)
        k = k_ref[0, 0].astype(jnp.float32)        # (page, dh)
        v = v_ref[0, 0].astype(jnp.float32)
        scores = jax.lax.dot_general(                # (g, page)
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        if int8:
            # fused dequant of K: row j's scale multiplies score column j
            scores = scores * ks_ref[0, 0]           # (1, page) row
        kpos = p * page + jax.lax.broadcasted_iota(jnp.int32, (1, page), 1)
        scores = jnp.where(kpos < n_keys, scores, NEG_INF)
        m_prev = m_ref[...]                          # (g, 1)
        m_new = jnp.maximum(m_prev, jnp.max(scores, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        pexp = jnp.exp(scores - m_new)               # (g, page)
        # fused dequant of V: row j's scale multiplies probability column j
        pv = pexp * vs_ref[0, 0] if int8 else pexp
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            pv, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(pexp, axis=1,
                                                  keepdims=True)
        m_ref[...] = m_new

    @pl.when(p == maxp - 1)
    def _finalize():
        out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        out = jnp.where(n_keys > 0, out, 0.0)        # inactive slot -> 0
        o_ref[...] = out[None, None].astype(o_ref.dtype)


def paged_flash_decode(q, kp, vp, table, lengths, *, k_scale=None,
                       v_scale=None, interpret=None):
    """Paged flash-decode; same contract as paged_decode_ref.

    Compiled on the TPU, interpret mode elsewhere
    (``kernels.backend.resolve_interpret``).  Every block is a pool's own
    trailing (page, dh) or (1, page) tile, so there is no shape this
    kernel cannot tile and no reference fallback.
    """
    s, hq, dh = q.shape
    n, hkv, page, _ = kp.shape
    g = hq // hkv
    maxp = table.shape[1]
    interpret = resolve_interpret(interpret)
    int8 = k_scale is not None

    q4 = q.reshape(s, hkv, g, dh)
    page_map = lambda si, h, p, tab, ln: (tab[si, p], h, 0, 0)  # noqa: E731
    in_specs = [
        pl.BlockSpec((1, 1, g, dh), lambda si, h, p, tab, ln: (si, h, 0, 0)),
        pl.BlockSpec((1, 1, page, dh), page_map),
        pl.BlockSpec((1, 1, page, dh), page_map),
    ]
    args = [table, lengths.astype(jnp.int32), q4, kp, vp]
    if int8:
        in_specs += [pl.BlockSpec((1, 1, 1, page), page_map),
                     pl.BlockSpec((1, 1, 1, page), page_map)]
        args += [k_scale, v_scale]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(s, hkv, maxp),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, g, dh),
                               lambda si, h, p, tab, ln: (si, h, 0, 0)),
        scratch_shapes=[pltpu.VMEM((g, dh), jnp.float32),
                        pltpu.VMEM((g, 1), jnp.float32),
                        pltpu.VMEM((g, 1), jnp.float32)],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, page=page, maxp=maxp, int8=int8),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s, hkv, g, dh), jnp.float32),
        interpret=interpret,
    )(*args)
    return out.reshape(s, hq, dh)
