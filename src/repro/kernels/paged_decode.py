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

Window layers hold a fixed ring of R pages per slot, (S, R, Hkv, page,
dh), reused in place: the grid's page axis is then the ring's R pages,
mapped through the ring by absolute position, so a window layer's grid
and DMAs are bounded by the window and not by the slot's length.

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


def _kernel(*refs, page: int, steps: int, int8: bool, window: int):
    if window:
        len_ref, q_ref, k_ref, v_ref, *rest = refs
    else:
        _table_ref, len_ref, q_ref, k_ref, v_ref, *rest = refs
    if int8:
        ks_ref, vs_ref, o_ref, acc_ref, m_ref, l_ref = rest
    else:
        o_ref, acc_ref, m_ref, l_ref = rest
    s = pl.program_id(0)
    p = pl.program_id(2)
    # the pool block's leading unit axes: (page, head), or on a ring
    # (slot, ring page, head)
    lead = (0, 0, 0) if window else (0, 0)

    @pl.when(p == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    n_keys = len_ref[s]
    if window:
        # step p holds absolute page lo // page + p of the window
        lo = jnp.maximum(n_keys - window, 0)
        a = jax.lax.div(lo, page) + p
    else:
        a = p

    @pl.when(a * page < n_keys)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)        # (g, dh)
        q = q * (q.shape[-1] ** -0.5)
        k = k_ref[lead].astype(jnp.float32)        # (page, dh)
        v = v_ref[lead].astype(jnp.float32)
        scores = jax.lax.dot_general(                # (g, page)
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        if int8:
            # fused dequant of K: row j's scale multiplies score column j
            scores = scores * ks_ref[lead]           # (1, page) row
        kpos = a * page + jax.lax.broadcasted_iota(jnp.int32, (1, page), 1)
        visible = kpos < n_keys
        if window:
            visible &= kpos >= lo
        scores = jnp.where(visible, scores, NEG_INF)
        m_prev = m_ref[...]                          # (g, 1)
        m_new = jnp.maximum(m_prev, jnp.max(scores, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        pexp = jnp.exp(scores - m_new)               # (g, page)
        # fused dequant of V: row j's scale multiplies probability column j
        pv = pexp * vs_ref[lead] if int8 else pexp
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            pv, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(pexp, axis=1,
                                                  keepdims=True)
        m_ref[...] = m_new

    @pl.when(p == steps - 1)
    def _finalize():
        out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        out = jnp.where(n_keys > 0, out, 0.0)        # inactive slot -> 0
        o_ref[...] = out[None, None].astype(o_ref.dtype)


def paged_flash_decode(q, kp, vp, table, lengths, *, k_scale=None,
                       v_scale=None, window=0, interpret=None):
    """Paged flash-decode; same contract as paged_decode_ref.

    Compiled on the TPU, interpret mode elsewhere
    (``kernels.backend.resolve_interpret``).  Every block is a pool's own
    trailing (page, dh) or (1, page) tile, so there is no shape this
    kernel cannot tile and no reference fallback.

    ``window > 0``: the pools are per-slot rings (S, R, Hkv, page, dh)
    and ``table`` is None.  The grid's page axis is the ring's R pages,
    named ``paged_decode_window``: step p fetches absolute page
    max(n - window, 0) // page + p from ring page (that) % R, and steps
    past the last live page fetch it again (the same block, so no new
    DMA lands).
    """
    s, hq, dh = q.shape
    hkv, page = kp.shape[-3], kp.shape[-2]
    g = hq // hkv
    interpret = resolve_interpret(interpret)
    int8 = k_scale is not None

    q4 = q.reshape(s, hkv, g, dh)
    if window:
        steps = kp.shape[1]
        name = "paged_decode_window"

        def page_map(si, h, p, ln):
            n = ln[si]
            first = jax.lax.div(jnp.maximum(n - window, 0), page)
            last = jax.lax.div(jnp.maximum(n - 1, 0), page)
            a = jnp.minimum(first + p, last)
            return (si, jax.lax.rem(a, steps), h, 0, 0)

        unit = (1, 1, 1)
        row_map = lambda si, h, p, ln: (si, h, 0, 0)  # noqa: E731
        prefetch = [lengths.astype(jnp.int32)]
    else:
        steps = table.shape[1]
        name = "paged_decode"
        page_map = lambda si, h, p, tab, ln: (tab[si, p], h, 0, 0)  # noqa: E731
        unit = (1, 1)
        row_map = lambda si, h, p, tab, ln: (si, h, 0, 0)  # noqa: E731
        prefetch = [table, lengths.astype(jnp.int32)]
    in_specs = [
        pl.BlockSpec((1, 1, g, dh), row_map),
        pl.BlockSpec(unit + (page, dh), page_map),
        pl.BlockSpec(unit + (page, dh), page_map),
    ]
    args = prefetch + [q4, kp, vp]
    if int8:
        in_specs += [pl.BlockSpec(unit + (1, page), page_map),
                     pl.BlockSpec(unit + (1, page), page_map)]
        args += [k_scale, v_scale]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(s, hkv, steps),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, g, dh), row_map),
        scratch_shapes=[pltpu.VMEM((g, dh), jnp.float32),
                        pltpu.VMEM((g, 1), jnp.float32),
                        pltpu.VMEM((g, 1), jnp.float32)],
    )
    with jax.named_scope(name):
        out = pl.pallas_call(
            functools.partial(_kernel, page=page, steps=steps, int8=int8,
                              window=window),
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((s, hkv, g, dh), jnp.float32),
            name=name,
            interpret=interpret,
        )(*args)
    return out.reshape(s, hq, dh)
