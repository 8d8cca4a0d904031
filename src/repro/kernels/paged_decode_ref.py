"""Dense reference for paged flash-decode (the parity oracle).

Gathers every slot's pages into a contiguous (S, Hkv, T, dh) K/V block
via the page table, then runs plain fp32 softmax attention — the same
shape of oracle as kernels/flash_attention_ref.py.  The Pallas kernel
(kernels/paged_decode.py) must match this bit-for-bit up to fp32
accumulation order (tests/test_serve.py pins the atol).

Contract shared with the kernel:
  q        (S, Hq, dh)        one query token per slot (GQA: Hq = g*Hkv)
  kp, vp   (N, Hkv, page, dh) page pools, head-major (f32, or int8
                              codes)
  table    (S, maxp) int32    per-slot page table; every entry must be a
                              valid pool index (unallocated entries are 0
                              and masked out by ``lengths``)
  lengths  (S,) int32         visible keys per slot INCLUDING the token
                              appended this step; <= 0 -> zero output
                              (inactive slot)
  k_scale, v_scale (N, Hkv, 1, page) f32  per-(row, head) absmax
                              scales for the int8 pools (comm/codecs.py
                              placement: qblk = dh, one scale per cache
                              row per head), one (1, page) row per
                              (page, head)
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def gather_pages(pool, table):
    """pool (N, Hkv, page, dh) gathered to (S, Hkv, maxp*page, dh) via
    table."""
    s, maxp = table.shape
    _, hkv, page, dh = pool.shape
    g = pool[table]                                  # (S, maxp, Hkv, page, dh)
    return g.transpose(0, 2, 1, 3, 4).reshape(s, hkv, maxp * page, dh)


def dequant_pool(codes, scale):
    """int8 page pool -> f32: the exact codecs.quant_decode multiply
    (codes * scale), each row's scale broadcast over the dh axis."""
    return codes.astype(jnp.float32) * jnp.swapaxes(scale, -1, -2)


def paged_decode_ref(q, kp, vp, table, lengths, *, k_scale=None,
                     v_scale=None):
    """Returns (S, Hq, dh) f32 attention outputs (see module contract)."""
    s, hq, dh = q.shape
    hkv = kp.shape[1]
    g = hq // hkv
    if k_scale is not None:
        kp = dequant_pool(kp, k_scale)
        vp = dequant_pool(vp, v_scale)
    k = gather_pages(kp, table).astype(jnp.float32)   # (S, Hkv, T, dh)
    v = gather_pages(vp, table).astype(jnp.float32)
    t = k.shape[2]
    qg = q.reshape(s, hkv, g, dh).astype(jnp.float32) * dh ** -0.5
    scores = jnp.einsum("shgd,shtd->shgt", qg, k)
    visible = jnp.arange(t)[None, :] < lengths[:, None]          # (S, T)
    scores = jnp.where(visible[:, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("shgt,shtd->shgd", probs, v)
    # fully-masked (inactive) slots: all-NEG_INF softmax is uniform
    # garbage — force the contract's zero output
    out = jnp.where((lengths > 0)[:, None, None, None], out, 0.0)
    return out.reshape(s, hq, dh)
