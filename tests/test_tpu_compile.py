"""The main-path Pallas kernels compile for a TPU v5e chip.

Each test lowers a kernel at its real shapes with ``interpret=False`` for
one chip of a described (not attached) ``v5e:2x2`` topology and compiles
it with the TPU compiler that ships with libtpu.  Nothing runs: this is
the check that finds block shapes the chip refuses and kernels that
overrun VMEM, which interpret mode on the CPU cannot see.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every pytest worker
imports every test file.  Keep these tests in this one file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.comm import codecs
from repro.configs.base import FedConfig
from repro.configs.registry import ARCHS, get_config
from repro.core import aggregation
from repro.kernels import backend, robust_pipeline
from repro.kernels.flash_attention import flash_attention_fwd
from repro.kernels.paged_decode import paged_flash_decode
from repro.kernels.population_select import topd_pallas
from repro.kernels.robust_pipeline import auto_blk, fused_aggregate_tree
from repro.models.model import build


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu, or the library is held elsewhere
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one; keep the cache out of it
    from jax.experimental.compilation_cache import compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    return SingleDeviceSharding(topo.devices[0])


def _sd(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    """Compile ``fn`` for the described chip; the Mosaic kernel must be
    in the program."""
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


def _update_tree(arch, c, sharding):
    params = jax.eval_shape(build(ARCHS[arch]).init, jax.random.PRNGKey(0))
    return jax.tree_util.tree_map(
        lambda l: _sd(sharding, (c,) + l.shape), params)


def _sizes(tree):
    return [int(l.size) // l.shape[0] for l in jax.tree_util.tree_leaves(tree)]


@pytest.mark.parametrize("c", [16, 32])
@pytest.mark.parametrize("agg", ["trimmed_mean", "krum"])
@pytest.mark.parametrize("arch", ["paper-cnn", "paper-mlp"])
def test_fused_aggregation_compiles(one_chip, arch, agg, c):
    upd = _update_tree(arch, c, one_chip)
    blk = auto_blk(c, _sizes(upd), backend="tpu")
    cfg = FedConfig(n_clients=c, aggregator=agg)
    _compile(lambda u, w, m: fused_aggregate_tree(u, w, m, cfg, blk=blk,
                                                  interpret=False),
             upd, _sd(one_chip, (c,)), _sd(one_chip, (c,)))


@pytest.mark.parametrize("agg", ["trimmed_mean", "krum"])
def test_fused_int8_dequant_compiles(one_chip, agg, monkeypatch):
    """``aggregation.aggregate(..., enc=)`` as the chip builds it: the
    block size ``auto_blk`` fits to VMEM and compiled (not interpreted)
    kernels, with the int8 loader chosen."""
    monkeypatch.setattr(backend, "on_tpu", lambda: True)
    monkeypatch.setattr(robust_pipeline, "on_tpu", lambda: True)
    c = 16
    upd = _update_tree("paper-cnn", c, one_chip)
    cfg = FedConfig(n_clients=c, aggregator=agg, compress="int8")
    codec = codecs.make_codec(cfg)
    like = jax.tree_util.tree_map(
        lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype), upd)
    enc = jax.tree_util.tree_map(lambda l: _sd(one_chip, l.shape, l.dtype),
                                 jax.eval_shape(codec.encode_tree, like))
    text = _compile(lambda u, e, w, m: aggregation.aggregate(
        u, w, m, cfg, enc=e), upd, enc, _sd(one_chip, (c,)),
        _sd(one_chip, (c,)))
    assert "dequant_pass1" in text and "robust_pass1" not in text


@pytest.mark.parametrize("int8,slots,maxp,n", [
    (False, 8, 12, 96), (True, 8, 12, 96),
    (False, 32, 128, 2048), (True, 32, 128, 4096)],
    ids=["f32", "int8", "f32-cell", "int8-cell"])
def test_paged_decode_compiles_at_granite_shapes(one_chip, int8, slots,
                                                 maxp, n):
    """granite-moe-1b-a400m: 16 query heads over 8 kv heads, dh=64 — the
    GQA, sub-128 head dim layout that the pool must tile for; at a small
    table (maxp 12: blocks of 8 pages, the last part-empty) and at the
    long cells' 32 slots of 128 pages over 2048 f32 or 4096 int8 pages."""
    cfg = get_config("granite-moe-1b-a400m")
    page = 16
    hq, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    pool = _sd(one_chip, (n, hkv, page, dh), jnp.int8 if int8 else
               jnp.float32)
    args = [_sd(one_chip, (slots, hq, dh)), pool, pool,
            _sd(one_chip, (slots, maxp), jnp.int32),
            _sd(one_chip, (slots,), jnp.int32)]
    if int8:
        scale = _sd(one_chip, (n, hkv, 1, page))
        _compile(lambda q, k, v, t, l, ks, vs: paged_flash_decode(
            q, k, v, t, l, k_scale=ks, v_scale=vs, interpret=False),
            *args, scale, scale)
    else:
        _compile(lambda q, k, v, t, l: paged_flash_decode(
            q, k, v, t, l, interpret=False), *args)


@pytest.mark.parametrize("int8,window", [
    (False, 1024), (True, 1024), (False, 0)],
    ids=["f32", "int8", "f32-full"])
def test_paged_window_decode_compiles_at_mellum_shapes(one_chip, int8,
                                                       window):
    """mellum2-12b-a2.5b: 32 query heads over 4 kv heads of 128, 64
    slots; the window layers' rings of ceil(1024/16)+1 = 65 pages, whose
    ring index is scalar arithmetic in the fetch table, and the full
    layer's 224-page tables over a pool of 14,336 pages."""
    slots, ring, page, maxp = 64, 65, 16, 224
    hq, hkv, dh = 32, 4, 128
    lead = (slots, ring) if window else (slots * maxp,)
    pool = _sd(one_chip, lead + (hkv, page, dh),
               jnp.int8 if int8 else jnp.float32)
    table = None if window else _sd(one_chip, (slots, maxp), jnp.int32)
    args = [_sd(one_chip, (slots, hq, dh)), pool, pool, table,
            _sd(one_chip, (slots,), jnp.int32)]
    if int8:
        scale = _sd(one_chip, lead + (hkv, 1, page))
        _compile(lambda q, k, v, t, l, ks, vs: paged_flash_decode(
            q, k, v, t, l, k_scale=ks, v_scale=vs, window=window,
            interpret=False), *args, scale, scale)
    else:
        _compile(lambda q, k, v, t, l: paged_flash_decode(
            q, k, v, t, l, window=window, interpret=False), *args)


def test_topd_pallas_compiles_at_population_scale(one_chip):
    _compile(lambda g: topd_pallas(g, 64, interpret=False),
             _sd(one_chip, (1 << 20,)))


def test_flash_attention_compiles(one_chip):
    q = _sd(one_chip, (1, 8, 1024, 128), jnp.bfloat16)
    _compile(lambda q, k, v: flash_attention_fwd(q, k, v, causal=True,
                                                 interpret=False), q, q, q)
