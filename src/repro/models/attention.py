"""GQA self-attention (full / sliding-window), cross-attention and KV caches.

Three execution modes per layer:
  * train/prefill: full-sequence attention, optional causal sliding window.
    ``attn_impl='pallas'`` routes the score/softmax/value contraction to the
    Pallas flash kernel (kernels/flash_attention.py).
  * decode (full cache): one query token against a (B, L, Hkv, dh) cache.
  * decode (ring cache, sliding window): (B, W, Hkv, dh) ring buffer —
    O(window) memory for the long_500k shape.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.models.layers import apply_rope, dense_init

NEG_INF = -1e30


def init_attention(key, cfg, cross: bool = False):
    d, hq, hkv = cfg.d_model, cfg.n_heads, cfg.n_kv_heads
    dh = cfg.resolved_head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], (d, hq * dh)),
        "wk": dense_init(ks[1], (d, hkv * dh)),
        "wv": dense_init(ks[2], (d, hkv * dh)),
        "wo": dense_init(ks[3], (hq * dh, d)),
    }
    if cfg.qkv_bias and not cross:
        p["bq"] = jnp.zeros((hq * dh,), jnp.float32)
        p["bk"] = jnp.zeros((hkv * dh,), jnp.float32)
        p["bv"] = jnp.zeros((hkv * dh,), jnp.float32)
    return p


def _proj(params, name, x, heads, dh, dtype):
    y = x @ params["w" + name].astype(dtype)
    if "b" + name in params:
        y = y + params["b" + name].astype(dtype)
    return y.reshape(*x.shape[:-1], heads, dh)


def _sdpa(q, k, v, mask):
    """q: (B,S,Hkv,G,dh); k/v: (B,T,Hkv,dh); mask: broadcastable (B,1,1,S,T)."""
    scale = q.shape[-1] ** -0.5
    scores = jnp.einsum("bshgd,bthd->bhgst", q.astype(jnp.float32) * scale,
                        k.astype(jnp.float32))
    scores = jnp.where(mask, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgst,bthd->bshgd", probs, v.astype(jnp.float32))
    return out


def causal_mask(s, t_offset=0, window=0):
    """(S, T) boolean mask; query i at absolute pos i+t_offset attends key j."""
    qpos = jnp.arange(s)[:, None] + t_offset
    kpos = jnp.arange(s + t_offset)[None, :]
    m = kpos <= qpos
    if window:
        m &= kpos > qpos - window
    return m


def attention_fwd(params, x, cfg, positions, *, window=0, cache=None,
                  kv_source=None, layer_idx=0):
    """Returns (out, new_cache).

    x: (B, S, d).  kv_source: (B, T, d) for cross-attention (no rope/causal).
    cache:
      None                     -> train/prefill, no cache returned
      {"k","v","length"}       -> full cache decode/prefill-fill
      {"k","v","pos"} (ring)   -> sliding-window ring cache decode
      {"kp","vp","table",...}  -> paged pool cache (serving; see
                                  init_paged_kv_cache)
      {"ck","cv"}              -> frozen cross-attention KV
    """
    dtype = x.dtype
    hq, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    g = hq // hkv
    B, S, _ = x.shape

    q = _proj(params, "q", x, hq, dh, dtype)

    if kv_source is not None or (cache is not None and "ck" in cache):
        # ---- cross attention: prefill (kv_source given) computes + stores
        # the frozen KV; decode (S==1, no kv_source) reuses the cache ----
        if kv_source is None:
            k, v = cache["ck"], cache["cv"]
            new_cache = cache
        else:
            k = _proj(params, "k", kv_source, hkv, dh, dtype)
            v = _proj(params, "v", kv_source, hkv, dh, dtype)
            new_cache = {"ck": k.astype(cache["ck"].dtype),
                         "cv": v.astype(cache["cv"].dtype)} \
                if cache is not None else None
        qg = q.reshape(B, S, hkv, g, dh)
        mask = jnp.ones((1, 1, 1, S, k.shape[1]), bool)
        out = _sdpa(qg, k, v, mask)
        out = out.reshape(B, S, hq * dh).astype(dtype) @ params["wo"].astype(dtype)
        return out, new_cache

    q = apply_rope(q, positions, cfg.rope_theta)
    k_new = _proj(params, "k", x, hkv, dh, dtype)
    k_new = apply_rope(k_new, positions, cfg.rope_theta)
    v_new = _proj(params, "v", x, hkv, dh, dtype)

    if cache is None:                              # ---- train / prefill ----
        if cfg.attn_impl == "pallas" and S >= 128:
            from repro.kernels import flash_attention_ops
            out = flash_attention_ops.flash_attention(
                q, k_new, v_new, causal=True, window=window)
        else:
            qg = q.reshape(B, S, hkv, g, dh)
            mask = causal_mask(S, window=window)[None, None, None]
            out = _sdpa(qg, k_new, v_new, mask)
            out = out.reshape(B, S, hq * dh)
        out = out.astype(dtype).reshape(B, S, hq * dh) @ params["wo"].astype(dtype)
        return out, None

    if "table" in cache:                           # ---- paged pool cache ----
        return _paged_fwd(params, cache, q, k_new, v_new, cfg, window)

    if "pos" in cache and S > 1:                   # ---- ring-cache prefill ----
        W = cache["k"].shape[1]
        # full windowed attention for outputs, then fill the ring with the
        # last min(S, W) keys/values (assumes prefill starts at pos 0)
        qg = q.reshape(B, S, hkv, g, dh)
        mask = causal_mask(S, window=window)[None, None, None]
        out = _sdpa(qg, k_new, v_new, mask)
        out = out.reshape(B, S, hq * dh).astype(dtype) @ params["wo"].astype(dtype)
        take = min(S, W)
        slots = jnp.mod(jnp.arange(S - take, S), W)
        k = cache["k"].at[:, slots].set(k_new[:, -take:].astype(cache["k"].dtype))
        v = cache["v"].at[:, slots].set(v_new[:, -take:].astype(cache["v"].dtype))
        return out, {"k": k, "v": v, "pos": jnp.asarray(S, jnp.int32)}

    if "pos" in cache:                             # ---- ring-cache decode ----
        W = cache["k"].shape[1]
        pos = cache["pos"]                         # scalar absolute position
        slot = jnp.mod(pos, W)
        k = jax.lax.dynamic_update_slice(cache["k"], k_new, (0, slot, 0, 0))
        v = jax.lax.dynamic_update_slice(cache["v"], v_new, (0, slot, 0, 0))
        # slot j holds absolute position: the largest p <= pos with p % W == j
        j = jnp.arange(W)
        abs_pos = pos - jnp.mod(pos - j, W)
        valid = (abs_pos >= 0) & (abs_pos <= pos)
        if window:
            valid &= abs_pos > pos - window
        qg = q.reshape(B, S, hkv, g, dh)
        mask = valid[None, None, None, None, :]
        out = _sdpa(qg, k, v, mask)
        out = out.reshape(B, S, hq * dh).astype(dtype) @ params["wo"].astype(dtype)
        return out, {"k": k, "v": v, "pos": pos + 1}

    # ---- full-cache: prefill-fill or decode ----
    L = cache["k"].shape[1]
    length = cache["length"]                       # tokens already in cache
    k = jax.lax.dynamic_update_slice(cache["k"], k_new, (0, length, 0, 0))
    v = jax.lax.dynamic_update_slice(cache["v"], v_new, (0, length, 0, 0))
    kpos = jnp.arange(L)
    qpos = length + jnp.arange(S)
    mask = kpos[None, :] <= qpos[:, None]
    if window:
        mask &= kpos[None, :] > qpos[:, None] - window
    qg = q.reshape(B, S, hkv, g, dh)
    out = _sdpa(qg, k, v, mask[None, None, None])
    out = out.reshape(B, S, hq * dh).astype(dtype) @ params["wo"].astype(dtype)
    return out, {"k": k, "v": v, "length": length + S}


def _paged_quant(x):
    """int8 KV append quantization: x (..., Hkv, dh) -> (codes int8 of
    x.shape, scales f32 of x.shape[:-1]).  One absmax scale per cache
    row per head (comm/codecs.py blockwise machinery with qblk = dh), so
    appends never touch other rows' scales and the fused kernel dequant
    is the exact quant_decode multiply."""
    from repro.comm import codecs
    flat = x.reshape(-1, x.shape[-1]).astype(jnp.float32)
    q, s = codecs.quant_encode(flat, x.shape[-1], 127.0)
    return q.reshape(x.shape), s.reshape(x.shape[:-1])


def _paged_fwd(params, cache, q, k_new, v_new, cfg, window):
    """Paged-pool branch of attention_fwd (serving; sliding windows are
    not supported here — the serving configs cap sequence length at the
    page budget instead).

    Cache contract (see init_paged_kv_cache):
      kp, vp    (N, Hkv, page, dh)  shared head-major page pools (f32 or
                                    int8 codes)
      ks, vs    (N, Hkv, 1, page)   f32 per-(row, head) scales (int8 only)
      table     (A, maxp) int32     per-slot page table (unallocated = 0)
      length    (A,) int32          valid tokens already in the slot
      active    (A,) f32            1 = slot holds a live request
      new_valid (A,) int32          prefill only: valid rows of x to
                                    scatter (pad rows are dropped)

    Prefill (S > 1) scatters rows [0, new_valid) into the slot's pages;
    decode (S == 1) appends one row at position ``length`` per active
    slot and attends over the pages via the flash-decode kernel
    (cfg.attn_impl == 'pallas') or the dense gather reference.  The
    returned cache echoes the context leaves unchanged — the serving
    engine owns length/active advancement and eviction.
    """
    from repro.kernels.paged_decode import paged_flash_decode
    from repro.kernels.paged_decode_ref import paged_decode_ref

    dtype = k_new.dtype
    hq, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    g = hq // hkv
    B, S = q.shape[0], q.shape[1]
    kp, vp, table = cache["kp"], cache["vp"], cache["table"]
    n_pages, page = kp.shape[0], kp.shape[2]
    maxp = table.shape[1]
    int8 = "ks" in cache
    length, active = cache["length"], cache["active"]
    new_cache = dict(cache)

    if S > 1:
        # ---- prefill: causal attention over the (padded) prompt, then
        # scatter the valid rows into the slot's pages.  Pad rows are
        # dropped (dest = n_pages); rows beyond the prompt are garbage in
        # the output and the engine only reads position new_valid-1.
        qg = q.reshape(B, S, hkv, g, dh)
        mask = causal_mask(S, window=window)[None, None, None]
        out = _sdpa(qg, k_new, v_new, mask)
        out = out.reshape(B, S, hq * dh)
        pos = jnp.arange(S)
        valid = pos[None, :] < cache["new_valid"][:, None]       # (B, S)
        prow = jnp.clip(pos // page, 0, maxp - 1)
        pg = jnp.take_along_axis(table, jnp.broadcast_to(prow[None],
                                                         (B, S)), axis=1)
        dest = jnp.where(valid, pg, n_pages)       # n_pages = drop
        row = jnp.broadcast_to(pos % page, (B, S))
        if int8:
            kq, ks = _paged_quant(k_new)
            vq, vs = _paged_quant(v_new)
            new_cache["ks"] = cache["ks"].at[dest, :, 0, row].set(
                ks, mode="drop")
            new_cache["vs"] = cache["vs"].at[dest, :, 0, row].set(
                vs, mode="drop")
            k_cast, v_cast = kq, vq
        else:
            k_cast = k_new.astype(kp.dtype)
            v_cast = v_new.astype(vp.dtype)
        new_cache["kp"] = kp.at[dest, :, row].set(k_cast, mode="drop")
        new_cache["vp"] = vp.at[dest, :, row].set(v_cast, mode="drop")
        out = out.astype(dtype) @ params["wo"].astype(dtype)
        return out, new_cache

    # ---- decode: append one row at position ``length`` per active slot
    prow = jnp.clip(length // page, 0, maxp - 1)
    pg = jnp.take_along_axis(table, prow[:, None], axis=1)[:, 0]
    dest = jnp.where(active > 0, pg, n_pages)
    row = length % page
    if int8:
        kq, ks = _paged_quant(k_new[:, 0])
        vq, vs = _paged_quant(v_new[:, 0])
        new_cache["ks"] = cache["ks"].at[dest, :, 0, row].set(
            ks, mode="drop")
        new_cache["vs"] = cache["vs"].at[dest, :, 0, row].set(
            vs, mode="drop")
        k_cast, v_cast = kq, vq
        k_scale, v_scale = new_cache["ks"], new_cache["vs"]
    else:
        k_cast = k_new[:, 0].astype(kp.dtype)
        v_cast = v_new[:, 0].astype(vp.dtype)
        k_scale = v_scale = None
    kp = new_cache["kp"] = kp.at[dest, :, row].set(k_cast, mode="drop")
    vp = new_cache["vp"] = vp.at[dest, :, row].set(v_cast, mode="drop")
    n_keys = jnp.where(active > 0, length + 1, 0)
    attend = paged_flash_decode if cfg.attn_impl == "pallas" \
        else paged_decode_ref
    out3 = attend(q[:, 0], kp, vp, table, n_keys,
                  k_scale=k_scale, v_scale=v_scale)
    out = out3.reshape(B, 1, hq * dh)
    out = out.astype(dtype) @ params["wo"].astype(dtype)
    return out, new_cache


def init_kv_cache(cfg, batch, max_len, *, ring=False, dtype=jnp.bfloat16):
    hkv, dh = cfg.n_kv_heads, cfg.resolved_head_dim
    shape = (batch, max_len, hkv, dh)
    z = jnp.zeros(shape, dtype)
    if ring:
        return {"k": z, "v": z, "pos": jnp.array(0, jnp.int32)}
    return {"k": z, "v": z, "length": jnp.array(0, jnp.int32)}


def init_paged_kv_cache(cfg, slots, num_pages, page_size, max_pages, *,
                        int8=False, dtype=jnp.float32):
    """One attention layer's paged pool cache (serving).  Pools are
    shared across slots; the per-slot page table indexes into them
    (unallocated entries stay 0 — always a valid pool index, masked out
    by length/active).  ``int8`` stores codes + per-(row, head) f32
    scales instead of raw K/V (see _paged_quant).  Pools are head-major,
    (N, Hkv, page, dh), so one page of one head is a whole trailing tile
    for kernels/paged_decode.py; the scales keep one (1, page) row per
    (page, head) for the same reason."""
    hkv, dh = cfg.n_kv_heads, cfg.resolved_head_dim
    pool_dtype = jnp.int8 if int8 else dtype
    z = jnp.zeros((num_pages, hkv, page_size, dh), pool_dtype)
    c = {"kp": z, "vp": z,
         "table": jnp.zeros((slots, max_pages), jnp.int32),
         "length": jnp.zeros((slots,), jnp.int32),
         "active": jnp.zeros((slots,), jnp.float32),
         "new_valid": jnp.zeros((slots,), jnp.int32)}
    if int8:
        s = jnp.ones((num_pages, hkv, 1, page_size), jnp.float32)
        c["ks"], c["vs"] = s, s
    return c
