"""Pallas flash-decode over a paged KV cache (the serving decode kernel).

One query token per slot attends over that slot's pages, gathered
straight from the (N, Hkv, page, dh) pool into VMEM via the per-slot
page table — no contiguous K/V copy, so eviction never compacts.  The
pool is head-major, so one pool page is ``Hkv`` whole trailing
(page, dh) tiles in a row: one contiguous DMA carries every head of it.

Grid ``(S, ceil(maxp / P))``: one step takes a block of P pages of all
Hkv heads of one slot, with P = min(maxp, max(1, 128 // page)) — 8 at
page 16, so a block's P * page = 128 keys fill the lanes of its score
row.  The pool reaches the kernel as P operands, one per page of the
block, each a BlockSpec of one whole pool page whose index map reads
the scalar-prefetched fetch table (``_fetch_table``); Pallas
double-buffers them, so the next block's P pages land while this one
computes.  A page past the slot's length, and every page of a block
wholly past it, repeats the pool page its operand fetched last: the
block index does not change and no DMA is issued.  Such a block is not
computed either (``pl.when``).  Per live block, one product over all
heads at once, ``(Hkv, g, dh) x (Hkv, P*page, dh)``, and one
online-softmax update of the (Hkv, g, dh) / (Hkv, g, 1) accumulators
in VMEM scratch, which carry across the block axis like
kernels/flash_attention.py carries across KV blocks.

Window layers hold a fixed ring of R pages per slot, (S, R, Hkv, page,
dh), reused in place; the kernel sees the rings as one (S*R, ...) pool
and the block axis covers the ring's R pages from the window's first
absolute page, each mapped through the ring (absolute page a of slot s
is pool page s*R + a % R).  A window layer's grid and DMAs are bounded
by the window and not by the slot's length.

The int8 path fuses dequantization into the page loads: codes are
fetched as int8 (quarter the bytes of f32) and the per-(row, head) f32
scales, stored as one (1, page) row per (page, head), are applied to
the rows where they act — the key scales to the score columns, the
value scales to the probabilities before the PV product — so the
unquantized K/V never exist in HBM or VMEM.

The pages are fetched by BlockSpecs and not by in-kernel async copies
from an unblocked pool: Mosaic refuses a DMA slice of a pool whose last
dim is narrower than 128 lanes (granite's dh 64, every int8 scale row),
while a BlockSpec of whole trailing tiles compiles at every width.

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


def block_pages(span: int, page: int) -> int:
    """Pages a grid step takes: enough for 128 keys, at most ``span``
    (the table's maxp, or the ring's R)."""
    return min(span, max(1, 128 // page))


def _fetch_table(table, lengths, *, page, blk, nblk, window, ring):
    """(S, nblk * blk) pool pages the grid fetches, block by block.

    Entry (s, i * blk + j) is the pool page operand j takes at grid step
    (s, i).  A live page is its table entry (or ring page); a dead one
    repeats the last live page operand j fetched before it in grid
    order, so the block index is unchanged and the pipeline issues no
    DMA for it (before operand j's first live page it takes slot 0's
    entry j, a valid pool page)."""
    s = lengths.shape[0]
    end = (lengths + page - 1) // page
    if window:
        first = jnp.maximum(lengths - window, 0) // page
        a = first[:, None] + jnp.arange(nblk * blk)[None]  # absolute pages
        src = jnp.arange(s)[:, None] * ring + a % ring
    else:
        a = jnp.arange(nblk * blk)[None]
        src = jnp.pad(table, ((0, 0), (0, nblk * blk - table.shape[1])))
    live = a < end[:, None]
    src = src.reshape(s * nblk, blk)
    step = jnp.where(live.reshape(s * nblk, blk),
                     jnp.arange(s * nblk)[:, None], 0)
    last = jax.lax.cummax(step, axis=0)
    return jnp.take_along_axis(src, last, axis=0).reshape(s, nblk * blk)


def _kernel(_pages_ref, len_ref, q_ref, *refs, page: int, blk: int,
            nblk: int, int8: bool, window: int):
    k_refs, v_refs = refs[:blk], refs[blk:2 * blk]
    if int8:
        ks_refs, vs_refs = refs[2 * blk:3 * blk], refs[3 * blk:4 * blk]
    o_ref, acc_ref, m_ref, l_ref = refs[(4 if int8 else 2) * blk:]
    s = pl.program_id(0)
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    n_keys = len_ref[s]
    lo = jnp.maximum(n_keys - window, 0) if window else 0
    # the block's first key: absolute page (window's first page) + i * blk
    base = (jax.lax.div(lo, page) + i * blk) * page if window \
        else i * blk * page

    @pl.when(base < n_keys)
    def _compute():
        q = q_ref[0].astype(jnp.float32)             # (Hkv, g, dh)
        q = q * (q.shape[-1] ** -0.5)
        # (Hkv, blk * page, dh): the block's pages side by side per head
        k = jnp.concatenate([r[0].astype(jnp.float32) for r in k_refs], 1)
        v = jnp.concatenate([r[0].astype(jnp.float32) for r in v_refs], 1)
        scores = jnp.einsum("hgd,htd->hgt", q, k,     # (Hkv, g, blk * page)
                            preferred_element_type=jnp.float32)
        if int8:
            # fused dequant of K: row j's scale multiplies score column j
            scores = scores * jnp.concatenate([r[0] for r in ks_refs], 2)
        kpos = base + jax.lax.broadcasted_iota(
            jnp.int32, (1, 1, blk * page), 2)
        visible = kpos < n_keys
        if window:
            visible &= kpos >= lo
        scores = jnp.where(visible, scores, NEG_INF)
        m_prev = m_ref[...]                          # (Hkv, g, 1)
        m_new = jnp.maximum(m_prev, jnp.max(scores, axis=2, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        pexp = jnp.exp(scores - m_new)
        # fused dequant of V: row j's scale multiplies probability column j
        pv = pexp * jnp.concatenate([r[0] for r in vs_refs], 2) if int8 \
            else pexp
        acc_ref[...] = acc_ref[...] * alpha + jnp.einsum(
            "hgt,htd->hgd", pv, v, preferred_element_type=jnp.float32)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(pexp, axis=2,
                                                  keepdims=True)
        m_ref[...] = m_new

    @pl.when(i == nblk - 1)
    def _finalize():
        out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        out = jnp.where(n_keys > 0, out, 0.0)        # inactive slot -> 0
        o_ref[...] = out[None].astype(o_ref.dtype)


def paged_flash_decode(q, kp, vp, table, lengths, *, k_scale=None,
                       v_scale=None, window=0, interpret=None):
    """Paged flash-decode; same contract as paged_decode_ref.

    Compiled on the TPU, interpret mode elsewhere
    (``kernels.backend.resolve_interpret``).  Every block is a pool's own
    trailing (Hkv, page, dh) or (Hkv, 1, page) tiles, so there is no
    shape this kernel cannot tile and no reference fallback.

    Grid ``(S, ceil(span / P))``, span the table's maxp or, with
    ``window > 0``, the ring's R; P = ``block_pages(span, page)``.  With
    ``window > 0`` the pools are per-slot rings (S, R, Hkv, page, dh),
    ``table`` is None and the kernel is named ``paged_decode_window``:
    step (s, i) takes absolute pages max(n - window, 0) // page + i * P
    + j of slot s, ring page (that) % R.
    """
    s, hq, dh = q.shape
    hkv, page = kp.shape[-3], kp.shape[-2]
    g = hq // hkv
    interpret = resolve_interpret(interpret)
    int8 = k_scale is not None
    lengths = lengths.astype(jnp.int32)

    if window:
        name = "paged_decode_window"
        ring = kp.shape[1]
        span = ring
        # the rings as one pool: slot s's ring page r is page s * R + r
        kp, vp = (x.reshape((-1,) + x.shape[2:]) for x in (kp, vp))
        if int8:
            k_scale, v_scale = (x.reshape((-1,) + x.shape[2:])
                                for x in (k_scale, v_scale))
    else:
        name = "paged_decode"
        ring = 0
        span = table.shape[1]
    blk = block_pages(span, page)
    nblk = -(-span // blk)

    def page_spec(j, shape):
        return pl.BlockSpec(
            (1,) + shape,
            lambda si, i, pg, ln: (pg[si, i * blk + j], 0, 0, 0))

    row_map = lambda si, i, pg, ln: (si, 0, 0, 0)  # noqa: E731
    in_specs = [pl.BlockSpec((1, hkv, g, dh), row_map)]
    in_specs += [page_spec(j, (hkv, page, dh)) for j in range(blk)] * 2
    args = [q.reshape(s, hkv, g, dh)] + [kp] * blk + [vp] * blk
    if int8:
        in_specs += [page_spec(j, (hkv, 1, page)) for j in range(blk)] * 2
        args += [k_scale] * blk + [v_scale] * blk

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(s, nblk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, hkv, g, dh), row_map),
        scratch_shapes=[pltpu.VMEM((hkv, g, dh), jnp.float32),
                        pltpu.VMEM((hkv, g, 1), jnp.float32),
                        pltpu.VMEM((hkv, g, 1), jnp.float32)],
    )
    with jax.named_scope(name):
        # the fetch table is the kernel's own index work: under its scope
        pages = _fetch_table(table, lengths, page=page, blk=blk,
                             nblk=nblk, window=window, ring=ring)
        out = pl.pallas_call(
            functools.partial(_kernel, page=page, blk=blk, nblk=nblk,
                              int8=int8, window=window),
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((s, hkv, g, dh), jnp.float32),
            name=name,
            interpret=interpret,
        )(pages, lengths, *args)
    return out.reshape(s, hq, dh)
