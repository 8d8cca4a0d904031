"""Per-kernel validation: shape/dtype sweeps, Pallas (interpret) vs the
pure-jnp ref oracle.  The masked trimmed-mean / median contract runs
through the fused Eq.-11 pipeline with its cosine gate opened to every
client (threshold -2 lies below any cosine)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import FedConfig
from repro.core import aggregation
from repro.kernels.flash_attention_ops import flash_attention
from repro.kernels.flash_attention_ref import flash_attention_ref
from repro.kernels.robust_pipeline import fused_aggregate_tree

KEY = jax.random.PRNGKey(0)


def _qkv(B, S, Hq, Hkv, dh, dtype):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, S, Hq, dh), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, Hkv, dh), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, Hkv, dh), jnp.float32)
    return q.astype(dtype), k.astype(dtype), v.astype(dtype)


@pytest.mark.parametrize("S", [128, 256])
@pytest.mark.parametrize("Hq,Hkv", [(4, 4), (4, 2), (8, 1)])
@pytest.mark.parametrize("window", [0, 64, 128])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(S, Hq, Hkv, window, dtype):
    q, k, v = _qkv(2, S, Hq, Hkv, 128, dtype)
    out = flash_attention(q, k, v, causal=True, window=window,
                          interpret=True)
    ref = flash_attention_ref(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), causal=True, window=window
    ).transpose(0, 2, 1, 3)
    tol = 2e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol)


def test_flash_attention_window_blocks_old_tokens():
    """Row S-1 with window W must equal attention over only last W keys."""
    S, W = 256, 64
    q, k, v = _qkv(1, S, 2, 2, 128, jnp.float32)
    out = flash_attention(q, k, v, causal=True, window=W, interpret=True)
    qw = q[:, -1:, :, :]
    ref_probs_in = k[:, S - W:S]
    scores = jnp.einsum("bshd,bthd->bhst",
                        qw * 128 ** -0.5, ref_probs_in)
    probs = jax.nn.softmax(scores, -1)
    ref = jnp.einsum("bhst,bthd->bshd", probs, v[:, S - W:S])
    np.testing.assert_allclose(np.asarray(out[:, -1:]), np.asarray(ref),
                               atol=2e-6)


def test_flash_attention_small_shape_fallback():
    q, k, v = _qkv(1, 32, 2, 2, 64, jnp.float32)   # not tileable -> ref path
    out = flash_attention(q, k, v, causal=True)
    assert out.shape == q.shape and np.isfinite(np.asarray(out)).all()


def _robust(tree, mask, mode, trim_frac=0.2):
    """Ungated masked trimmed mean / median of ``tree`` by the fused
    pipeline."""
    cfg = FedConfig(n_clients=mask.shape[0],
                    aggregator="trimmed_mean" if mode == "trimmed"
                    else "median",
                    trim_frac=trim_frac, cosine_outlier_thresh=-2.0)
    return fused_aggregate_tree(tree, jnp.ones_like(mask), mask, cfg,
                                blk=128, interpret=True)


def _robust_ref(tree, mask, mode, trim_frac=0.2):
    if mode == "trimmed":
        return aggregation.trimmed_mean(tree, mask, trim_frac)
    return aggregation.median(tree, mask)


@pytest.mark.parametrize("C", [4, 16, 32])
@pytest.mark.parametrize("mode", ["trimmed", "median"])
def test_robust_agg_sweep(C, mode):
    tree = {"a": jax.random.normal(KEY, (C, 13, 7)),
            "b": jax.random.normal(jax.random.fold_in(KEY, 1), (C, 257))}
    mask = jnp.ones((C,)).at[0].set(0.0)
    out = _robust(tree, mask, mode)
    ref = _robust_ref(tree, mask, mode)
    for kk in tree:
        np.testing.assert_allclose(np.asarray(out[kk]), np.asarray(ref[kk]),
                                   atol=1e-5)


@pytest.mark.parametrize("mode", ["trimmed", "median"])
def test_robust_agg_defends_poison(mode):
    C = 8
    honest = jnp.ones((C, 64)) + 0.01 * jax.random.normal(KEY, (C, 64))
    poisoned = honest.at[0].set(-1e6)
    out = _robust({"w": poisoned}, jnp.ones((C,)), mode)
    assert np.all(np.asarray(out["w"]) > 0.9)


def test_robust_agg_dtype_bf16_inputs():
    C = 16
    tree = {"w": jax.random.normal(KEY, (C, 384)).astype(jnp.bfloat16)}
    out = _robust(tree, jnp.ones((C,)), "median")
    assert out["w"].dtype == jnp.bfloat16
    ref = _robust_ref(
        {"w": tree["w"].astype(jnp.float32)}, jnp.ones((C,)), "median")
    np.testing.assert_allclose(np.asarray(out["w"], np.float32),
                               np.asarray(ref["w"]), atol=1e-2)
