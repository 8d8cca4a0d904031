"""Client->server transport codecs — the compressed uplink wire formats.

Every update a client submits crosses the client->server boundary; at
cross-device scale (millions of users, SSIV communication complexity)
the uplink is the binding constraint, so the codecs here compress the
per-client update pytree into compact wire formats whose **measured**
byte counts drive the cost accounting in ``core/fedfits.py`` (no more
analytic ``2*|params|*4`` billing):

  int8      blockwise absmax quantization: 1 byte/coord + one f32 scale
            per ``qblk``-coordinate block per client (~3.9x at qblk=128)
  int4      same scheme at 4 bits, two codes packed per byte (~7.5x)
  signsgd   1-bit sign-SGD [Bernstein et al. 2018]: 8 signs/byte + the
            per-block mean-|x| magnitude; ``majority_vote`` implements
            the server-side majority-vote decode (~30x)
  topk      top-k sparsification: k = ceil(frac*n) largest-|x| coords
            as (int32 idx, f32 val) pairs; ``randk`` draws the k coords
            uniformly instead (unbiased, no magnitude pass)
  randk     the random-k fallback as its own codec (needs an rng)

All encode/decode paths are jit-able per leaf (static shapes; the only
data-dependent op is topk's ``lax.top_k``).  Encoded leaves are pytrees
(NamedTuples), so an encoded tree threads through ``lax.scan`` carries,
``shard_map`` and donation like any other state.  The int8 format is
additionally consumed *without decoding* by the ``Int8`` block loader of
the fused Eq.-11 pipeline (``kernels/robust_pipeline.py``).

Compression error handling (EF residuals) lives in
``comm/error_feedback.py``; this module is purely the wire format.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp


class QuantLeaf(NamedTuple):
    """Blockwise-quantized leaf.

    q: (K, n) int8 codes (int4: (K, ceil(n/2)) with two codes per byte);
    s: (K, nq) f32 per-(client, quant-block) absmax scales, nq=ceil(n/qblk).
    """
    q: jnp.ndarray
    s: jnp.ndarray


class SignLeaf(NamedTuple):
    """1-bit sign-SGD leaf: bits (K, ceil(n/8)) uint8 packed signs
    (bit=1 -> +1), s (K, nq) f32 per-block mean-|x| magnitudes."""
    bits: jnp.ndarray
    s: jnp.ndarray


class SparseLeaf(NamedTuple):
    """Top-k / random-k leaf: idx (K, k) int32, val (K, k) f32."""
    idx: jnp.ndarray
    val: jnp.ndarray


ENC_TYPES = (QuantLeaf, SignLeaf, SparseLeaf)


def is_encoded(x) -> bool:
    return isinstance(x, ENC_TYPES)


def _flat2d(leaf):
    """(K, ...) leaf as a (K, n) view (reshape only, no copy)."""
    return leaf.reshape(leaf.shape[0], -1)


def _blocks(x2, qblk):
    """(K, n) f32 -> (K, nq, qblk) zero-padded quant-block view."""
    K, n = x2.shape
    nq = -(-n // qblk)
    xp = jnp.pad(x2.astype(jnp.float32), ((0, 0), (0, nq * qblk - n)))
    return xp.reshape(K, nq, qblk), nq


# ------------------------------------------------------------- int8/int4 --
def quant_encode(x2, qblk, levels):
    """Blockwise absmax quantization of a (K, n) matrix to
    ``levels``-level symmetric codes: q (K, n) int8 in [-levels, levels],
    s (K, nq) f32 scales.  dec = q * s[block] (the exact multiply the
    pipeline's int8 loader replays in VMEM — bit-identical)."""
    K, n = x2.shape
    b, nq = _blocks(x2, qblk)
    amax = jnp.max(jnp.abs(b), axis=2)                       # (K, nq)
    s = jnp.where(amax > 0, amax / levels, 1.0)
    q = jnp.clip(jnp.round(b / s[:, :, None]), -levels, levels)
    return q.reshape(K, nq * qblk)[:, :n].astype(jnp.int8), s


def quant_decode(q, s, n, qblk):
    """Inverse of ``quant_encode``: (K, n) f32 = q * s[block]."""
    K = q.shape[0]
    nq = s.shape[1]
    qp = jnp.pad(q, ((0, 0), (0, nq * qblk - n)))
    x = qp.astype(jnp.float32).reshape(K, nq, qblk) * s[:, :, None]
    return x.reshape(K, nq * qblk)[:, :n]


def pack_int4(q):
    """(K, n) int8 codes in [-7, 7] -> (K, ceil(n/2)) uint8, two 4-bit
    two's-complement nibbles per byte (low nibble = even coord)."""
    K, n = q.shape
    qp = jnp.pad(q, ((0, 0), (0, n % 2))).astype(jnp.uint8)
    lo = qp[:, 0::2] & 0x0F
    hi = (qp[:, 1::2] & 0x0F) << 4
    return lo | hi


def unpack_int4(p, n):
    """Inverse of ``pack_int4``: sign-extend both nibbles back to int8."""
    K = p.shape[0]
    lo = (p << 4).astype(jnp.int8) >> 4                       # low nibble
    hi = p.astype(jnp.int8) >> 4                              # high nibble
    return jnp.stack([lo, hi], axis=-1).reshape(K, -1)[:, :n]


# -------------------------------------------------------------- signsgd --
def pack_bits(b):
    """(K, n) 0/1 -> (K, ceil(n/8)) uint8, LSB-first."""
    K, n = b.shape
    bp = jnp.pad(b.astype(jnp.uint8), ((0, 0), (0, (-n) % 8)))
    w = (jnp.uint8(1) << jnp.arange(8, dtype=jnp.uint8))
    return (bp.reshape(K, -1, 8) * w).sum(-1).astype(jnp.uint8)


def unpack_bits(p, n):
    shifts = jnp.arange(8, dtype=jnp.uint8)
    bits = (p[:, :, None] >> shifts) & jnp.uint8(1)
    return bits.reshape(p.shape[0], -1)[:, :n]


def sign_encode(x2, qblk):
    """1-bit sign + per-block mean-|x| magnitude (scaled signSGD)."""
    K, n = x2.shape
    b, nq = _blocks(x2, qblk)
    # tail block averages over its REAL coords, not the zero padding
    cnts = jnp.full((nq,), float(qblk)).at[-1].set(
        float(n - (nq - 1) * qblk))
    s = jnp.abs(b).sum(-1) / cnts
    bits = pack_bits((x2 >= 0).astype(jnp.uint8))
    return bits, s


def sign_decode(bits, s, n, qblk):
    """Per-client decode: sign * per-block mean magnitude."""
    K = bits.shape[0]
    sg = unpack_bits(bits, n).astype(jnp.float32) * 2.0 - 1.0
    nq = s.shape[1]
    sp = jnp.pad(sg, ((0, 0), (0, nq * qblk - n)))
    x = sp.reshape(K, nq, qblk) * s[:, :, None]
    return x.reshape(K, nq * qblk)[:, :n]


def majority_vote(enc: SignLeaf, n, qblk, mask, weights=None):
    """Server-side majority-vote decode of a cohort of sign-SGD leaves:
    per-coordinate (optionally weighted) vote over masked-in clients,
    scaled by the masked mean of the clients' block magnitudes.  Returns
    ONE (n,) aggregate row (the signSGD-with-majority-vote server rule
    [Bernstein et al. 2019]); the per-client ``sign_decode`` path is what
    feeds the robust aggregation gate instead."""
    sg = unpack_bits(enc.bits, n).astype(jnp.float32) * 2.0 - 1.0
    w = mask if weights is None else weights * mask
    vote = jnp.sign(jnp.tensordot(w, sg, axes=(0, 0)))
    ms = jnp.tensordot(mask, enc.s, axes=(0, 0)) \
        / jnp.maximum(mask.sum(), 1.0)                        # (nq,)
    scale = jnp.repeat(ms, qblk)[:n]
    return vote * scale


# ---------------------------------------------------------------- top-k --
def topk_encode(x2, k):
    _, idx = jax.lax.top_k(jnp.abs(x2), k)
    val = jnp.take_along_axis(x2.astype(jnp.float32), idx, axis=1)
    return idx.astype(jnp.int32), val


def randk_encode(x2, k, rng):
    K, n = x2.shape
    keys = jax.random.split(rng, K)
    idx = jax.vmap(
        lambda kk: jax.random.permutation(kk, n)[:k])(keys).astype(jnp.int32)
    val = jnp.take_along_axis(x2.astype(jnp.float32), idx, axis=1)
    return idx, val


def sparse_decode(idx, val, n):
    def one(i, v):
        return jnp.zeros((n,), jnp.float32).at[i].set(v)

    return jax.vmap(one)(idx, val)


# ------------------------------------------------------------ the codec --
@dataclasses.dataclass(frozen=True)
class Codec:
    """One wire format.  Frozen/hashable so it can close over jitted
    round functions and ride static jit arguments."""
    name: str                          # int8|int4|signsgd|topk|randk
    qblk: int = 128                    # quant-block width (per-block scales)
    topk_frac: float = 0.05            # top-k kept fraction of each leaf

    @property
    def stochastic(self) -> bool:
        return self.name == "randk"

    def _k(self, n):
        """Kept coords per leaf: ceil(frac * n), clamped to [1, n]."""
        return max(1, min(int(n), math.ceil(self.topk_frac * int(n))))

    # ---- per-leaf ----------------------------------------------------
    def encode(self, leaf, rng=None):
        x2 = _flat2d(leaf)
        n = x2.shape[1]
        if self.name == "int8":
            q, s = quant_encode(x2, self.qblk, 127.0)
            return QuantLeaf(q, s)
        if self.name == "int4":
            q, s = quant_encode(x2, self.qblk, 7.0)
            return QuantLeaf(pack_int4(q), s)
        if self.name == "signsgd":
            bits, s = sign_encode(x2, self.qblk)
            return SignLeaf(bits, s)
        if self.name == "topk":
            return SparseLeaf(*topk_encode(x2, self._k(n)))
        if self.name == "randk":
            # the random-k fallback: same sparse wire format, indices
            # drawn uniformly (unbiased, no magnitude ranking pass)
            if rng is None:
                raise ValueError("randk codec needs an rng at encode time")
            return SparseLeaf(*randk_encode(x2, self._k(n), rng))
        raise ValueError(self.name)

    def decode(self, enc, like):
        """Decode one encoded leaf back to ``like``'s shape/dtype.
        ``like`` may be an array or a ShapeDtypeStruct."""
        shape, dtype = like.shape, like.dtype
        n = 1
        for d in shape[1:]:
            n *= d
        if self.name == "int8":
            x = quant_decode(enc.q, enc.s, n, self.qblk)
        elif self.name == "int4":
            x = quant_decode(unpack_int4(enc.q, n), enc.s, n, self.qblk)
        elif self.name == "signsgd":
            x = sign_decode(enc.bits, enc.s, n, self.qblk)
        elif self.name == "topk":
            x = sparse_decode(enc.idx, enc.val, n)
        elif self.name == "randk":
            # importance-scale by n/k so the estimator is UNBIASED over
            # the uniform index draw (E[dec] = x); top-k keeps raw values
            # (biased by construction — EF mops up the dropped mass)
            k = enc.val.shape[1]
            x = sparse_decode(enc.idx, enc.val, n) * (n / k)
        else:
            raise ValueError(self.name)
        return x.reshape(shape).astype(dtype)

    # ---- pytrees -----------------------------------------------------
    def encode_tree(self, tree, rng=None):
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        if self.stochastic:
            if rng is None:
                raise ValueError("randk codec needs an rng at encode time")
            keys = list(jax.random.split(rng, len(leaves)))
        else:
            keys = [None] * len(leaves)
        return jax.tree_util.tree_unflatten(
            treedef, [self.encode(l, k) for l, k in zip(leaves, keys)])

    def decode_tree(self, enc, like):
        enc_leaves = jax.tree_util.tree_flatten(enc, is_leaf=is_encoded)[0]
        like_leaves, treedef = jax.tree_util.tree_flatten(like)
        return jax.tree_util.tree_unflatten(
            treedef, [self.decode(e, l)
                      for e, l in zip(enc_leaves, like_leaves)])


def make_codec(cfg) -> Optional[Codec]:
    """Build the configured codec from a FedConfig (None when off)."""
    name = getattr(cfg, "compress", "none") or "none"
    if name == "none":
        return None
    return Codec(name=name, qblk=getattr(cfg, "compress_qblk", 128),
                 topk_frac=getattr(cfg, "compress_topk_frac", 0.05))


# ---------------------------------------------------- measured byte sizes --
def wire_bytes_per_client(enc_tree) -> float:
    """MEASURED uplink bytes one client's encoded update occupies on the
    wire: summed over every array of the encoded pytree (codes, scales,
    indices — all of it), from the actual dtypes and shapes.  Static at
    trace time (shape/dtype only), so it folds into jitted accounting."""
    arrs = jax.tree_util.tree_leaves(enc_tree)
    k = arrs[0].shape[0]
    return float(sum(a.size * jnp.dtype(a.dtype).itemsize
                     for a in arrs)) / float(k)


def dense_bytes_per_client(tree) -> float:
    """Uncompressed uplink bytes per client for a (K, ...) update pytree,
    from the actual leaf dtype itemsizes (bf16 leaves are 2 bytes, not
    the analytic model's flat 4)."""
    leaves = jax.tree_util.tree_leaves(tree)
    k = leaves[0].shape[0]
    return float(sum(l.size * jnp.dtype(l.dtype).itemsize
                     for l in leaves)) / float(k)


def param_bytes(params) -> float:
    """Downlink bytes of one dense global-model broadcast, from actual
    leaf dtype itemsizes."""
    return float(sum(l.size * jnp.dtype(l.dtype).itemsize
                     for l in jax.tree_util.tree_leaves(params)))
