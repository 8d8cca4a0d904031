"""Window and full attention layers with YaRN (mellum2-12b-a2.5b) on the CPU.

At a small size with Mellum2's layer pattern (three sliding-window layers,
then one full layer with YaRN), 8 experts top-2, window 16 and pages of 4:
the model's forward against the plain float32 reference of
``bench/reference/mellum2-12b-a2.5b.py`` on its seeded weights; serving
through ``ServeEngine`` (prefill, then decode through the window rings,
with contexts that wrap each ring several times) against the reference's
full forward; the window-ignored and YaRN-dropped programs failing the
same comparison; the paged kernel's window path against the dense oracle;
the YaRN tables at the published sizes; and a granite-shaped stack with no
``layer_types``, whose outputs and kernel grid stay as they were.
"""
import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_config
from repro.kernels.paged_decode import paged_flash_decode
from repro.kernels.paged_decode_ref import paged_decode_ref
from repro.models import attention, transformer
from repro.models.model import build
from repro.serve import Request, ServeConfig, ServeEngine

ROOT = Path(__file__).resolve().parents[1]
PERIOD = ("sliding_attention",) * 3 + ("full_attention",)
SMALL = dict(n_layers=4, layer_types=PERIOD, d_model=256, n_heads=8,
             n_kv_heads=2, head_dim=32, d_ff=64, n_experts=8, top_k=2,
             vocab_size=512, capacity_factor=4.0, sliding_window=16,
             yarn_original_max_pos=1024, dtype="float32",
             param_dtype="float32")
CFG = get_config("mellum2-12b-a2.5b").replace(**SMALL)
FAULTS = {"no_window": dict(sliding_window=0), "no_yarn": dict(yarn_factor=0.0)}
# float32 on both sides: the program and the reference differ only in the
# order of float32 accumulation (measured ~1e-6 on logits of size ~1)
ATOL = 1e-4
# served tokens are the argmax of float32 logits that match the reference
# to ATOL, so each served token's reference logit lies within ATOL of the
# best (0 on every measured position)
GAP_TOL = 1e-4


def _reference():
    path = ROOT / "bench" / "reference" / "mellum2-12b-a2.5b.py"
    spec = importlib.util.spec_from_file_location("mellum_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _reference()


def _ref_config(cfg):
    keys = ("n_layers", "d_model", "n_heads", "n_kv_heads", "d_ff",
            "n_experts", "top_k", "vocab_size", "rope_theta", "yarn_factor",
            "yarn_original_max_pos", "sliding_window", "norm_eps",
            "tie_embeddings", "param_dtype")
    c = {k: getattr(cfg, k) for k in keys}
    c.update(head_dim=cfg.resolved_head_dim, attention_period=list(PERIOD),
             yarn_beta_fast=32.0, yarn_beta_slow=1.0,
             yarn_attention_factor=1.2772588722239782)
    return c


C = _ref_config(CFG)
KEY = jax.random.PRNGKey(3)


@pytest.fixture(scope="module")
def params():
    return REF.init_params(KEY, C)


def _program_logits(params, cfg, toks):
    logits, _, _ = transformer.forward(params, cfg, tokens=toks[None])
    return np.asarray(logits[0, :, :cfg.vocab_size])


@pytest.mark.parametrize("fault", [None, "no_window", "no_yarn"])
def test_forward_matches_reference(params, fault):
    """The program's forward against the reference; each broken program
    (window ignored on the sliding layers, YaRN dropped on the full one)
    matches the reference's own broken forward and fails the comparison
    with the sound one by far."""
    toks = jax.random.randint(jax.random.PRNGKey(9), (64,), 0, CFG.vocab_size)
    ref = np.asarray(REF.forward_logits(params, C, toks))
    cfg = CFG.replace(**FAULTS[fault]) if fault else CFG
    got = _program_logits(params, cfg, toks)
    if fault is None:
        np.testing.assert_allclose(got, ref, atol=ATOL)
    else:
        np.testing.assert_allclose(
            got, np.asarray(REF.forward_logits(params, C, toks, fault=fault)),
            atol=ATOL)
        assert np.abs(got - ref).max() > 100 * ATOL


def _serve(cfg, params, attn="pallas", seed=0):
    scfg = ServeConfig(max_slots=3, page_size=4, max_len=96, prompt_pad=64,
                       attn=attn)
    rng = np.random.default_rng(seed)
    reqs = [Request(i, tuple(int(t) for t in rng.integers(0, cfg.vocab_size,
                                                          int(p))), int(n))
            for i, (p, n) in enumerate([(60, 30), (24, 20), (41, 12),
                                        (33, 25), (18, 8)])]
    out, _ = ServeEngine(cfg, scfg, params).run(reqs)
    return reqs, out


@pytest.mark.parametrize("attn", ["pallas", "ref"])
def test_engine_through_the_rings_matches_reference(params, attn):
    """Prefill writes only the rows the window keeps into a 5-page ring;
    decode appends in place and attends the last 16 keys.  Contexts reach
    89 rows, so each ring wraps four times.  Every served token is the
    reference's best at its position."""
    reqs, out = _serve(CFG, params, attn)
    seqs = [(r.tokens, out[r.req_id]) for r in reqs]
    for r in reqs:
        assert len(out[r.req_id]) == r.max_new
    gaps = REF.served_gaps(KEY, C, seqs, 96)
    assert max(float(g.max()) for g in gaps) <= GAP_TOL


@pytest.mark.parametrize("fault", ["no_window", "no_yarn"])
def test_broken_engine_fails_the_comparison(params, fault):
    reqs, out = _serve(CFG.replace(**FAULTS[fault]), params)
    gaps = REF.served_gaps(KEY, C, [(r.tokens, out[r.req_id])
                                    for r in reqs], 96)
    assert max(float(g.max()) for g in gaps) > 100 * GAP_TOL


def test_window_rings_hold_the_configured_pages():
    from repro.serve import init_paged_cache
    scfg = ServeConfig(max_slots=3, page_size=4, max_len=96, num_pages=50)
    cache = init_paged_cache(CFG, scfg)
    for i, kind in enumerate(PERIOD):
        shape = cache[f"b{i}"]["kp"].shape
        if kind == "sliding_attention":
            assert shape == (1, 3, 5, 2, 4, 32)     # ceil(16 / 4) + 1 pages
        else:
            assert shape == (1, 50, 2, 4, 32)


# ------------------------------------------------------------- the kernel
def _rings(seed, s, ring, page, hkv, dh, hq):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (s, hq, dh), jnp.float32)
    kp = jax.random.normal(ks[1], (s, ring, hkv, page, dh), jnp.float32)
    vp = jax.random.normal(ks[2], (s, ring, hkv, page, dh), jnp.float32)
    return q, kp, vp


# lengths: inactive, inside the first page, exactly the window, one past
# it (the window starts mid-page), and a context that wrapped the ring
LENGTHS = [0, 3, 14, 15, 47, 90]


# window 200 over pages of 16 rings 14 pages, which the kernel takes in
# blocks of 8 (the last one part-empty): inactive, inside the first
# page, shorter than the window, exactly the window, one past it, and
# two contexts whose live pages wrap the ring
BLOCK_LENGTHS = [0, 3, 100, 200, 201, 500, 1000]


@pytest.mark.parametrize("int8,window,page,heads,lengths", [
    (int8, *case) for case in [
        (14, 4, (4, 2, 64), LENGTHS),
        (200, 16, (4, 2, 64), BLOCK_LENGTHS),
        (200, 16, (16, 2, 128), BLOCK_LENGTHS)]
    for int8 in (False, True)],
    ids=["f32", "int8", "f32-blocks_g2_dh64", "int8-blocks_g2_dh64",
         "f32-blocks_g8_dh128", "int8-blocks_g8_dh128"])
def test_window_kernel_matches_oracle(int8, window, page, heads, lengths):
    """fp32 kernel against the oracle: fp32 op order (~3e-7 measured); the
    int8 kernel against the int8 oracle: the same dequant, fp32 order."""
    hq, hkv, dh = heads
    ring = attention.ring_pages(window, page, 1 << 20)
    q, kp, vp = _rings(int8, len(lengths), ring, page, hkv, dh, hq)
    lengths = jnp.asarray(lengths, jnp.int32)
    kw = {}
    if int8:
        kp, ks = attention._paged_quant(kp)
        vp, vs = attention._paged_quant(vp)
        kw = dict(k_scale=ks[..., None, :], v_scale=vs[..., None, :])
    got = paged_flash_decode(q, kp, vp, None, lengths, window=window,
                             interpret=True, **kw)
    want = paged_decode_ref(q, kp, vp, None, lengths, window=window, **kw)
    np.testing.assert_allclose(got, want, atol=2e-5)
    assert not np.asarray(got[0]).any()             # inactive slot -> 0


def test_window_oracle_is_windowed_attention():
    """The ring oracle against plain softmax attention over the last
    ``window`` keys laid out in order."""
    window, page, ring, s = 14, 4, 5, len(LENGTHS)
    q, _, _ = _rings(1, s, ring, page, 2, 64, 4)
    keys = jax.random.normal(jax.random.PRNGKey(5), (s, 96, 2, 64))
    vals = jax.random.normal(jax.random.PRNGKey(6), (s, 96, 2, 64))
    kp = jnp.zeros((s, ring, 2, page, 64))
    vp = jnp.zeros((s, ring, 2, page, 64))
    for i, n in enumerate(LENGTHS):                 # append row by row
        for p in range(n):
            a = p // page % ring
            kp = kp.at[i, a, :, p % page].set(keys[i, p])
            vp = vp.at[i, a, :, p % page].set(vals[i, p])
    got = paged_decode_ref(q, kp, vp, None, jnp.asarray(LENGTHS), window=window)
    for i, n in enumerate(LENGTHS[1:], 1):
        k, v = keys[i, max(n - window, 0):n], vals[i, max(n - window, 0):n]
        qg = q[i].reshape(2, 2, 64) / 8.0
        p = jax.nn.softmax(jnp.einsum("hgd,thd->hgt", qg, k), -1)
        want = jnp.einsum("hgt,thd->hgd", p, v).reshape(4, 64)
        np.testing.assert_allclose(got[i], want, atol=1e-5)


def _grid(window):
    """The paged kernel's grid at Mellum2's serving shapes: 64 slots,
    4 kv heads of 128, pages of 16, max_len 3584 (224 pages a slot)."""
    s, hq, hkv, page, dh = 64, 32, 4, 16, 128
    q = jax.ShapeDtypeStruct((s, hq, dh), jnp.float32)
    n = jax.ShapeDtypeStruct((s,), jnp.int32)
    if window:
        ring = attention.ring_pages(window, page, 224)
        pool = jax.ShapeDtypeStruct((s, ring, hkv, page, dh), jnp.float32)
        fn = lambda q, k, v, n: paged_flash_decode(  # noqa: E731
            q, k, v, None, n, window=window, interpret=True)
        jaxpr = jax.make_jaxpr(fn)(q, pool, pool, n)
    else:
        pool = jax.ShapeDtypeStruct((14336, hkv, page, dh), jnp.float32)
        table = jax.ShapeDtypeStruct((s, 224), jnp.int32)
        fn = lambda q, k, v, t, n: paged_flash_decode(  # noqa: E731
            q, k, v, t, n, interpret=True)
        jaxpr = jax.make_jaxpr(fn)(q, pool, pool, table, n)
    eqn = next(e for e in jaxpr.jaxpr.eqns
               if e.primitive.name == "pallas_call")
    return tuple(eqn.params["grid_mapping"].grid), eqn.params["name"]


@pytest.mark.parametrize("window,grid,name", [
    (1024, (64, 9), "paged_decode_window"),
    (0, (64, 28), "paged_decode")])
def test_kernel_grid_page_axis(window, grid, name):
    """A grid step takes a block of 8 pages of all 4 kv heads: a window
    layer's grid walks its ring's ceil(1024/16)+1 = 65 pages in 9 blocks,
    not max_len/page = 224 pages in 28, as the full layer's does."""
    assert _grid(window) == (grid, name)


# ------------------------------------------------------------------- YaRN
def test_yarn_tables_at_published_sizes():
    """d 128, theta 5e5, original 8192, beta 32/1: the ramp runs over
    frequency indices [18, 35] (correction dims 18.08 and 34.98), the
    attention factor is 0.1 ln 16 + 1."""
    cfg = get_config("mellum2-12b-a2.5b")
    freqs, scale = attention.rope_of(cfg, "full_attention")
    assert attention.rope_of(cfg, "sliding_attention") is None
    assert scale == pytest.approx(1.2772588722239782, abs=1e-15)
    base = 1.0 / 5e5 ** (np.arange(0, 128, 2) / 128)
    freqs = np.asarray(freqs)
    np.testing.assert_allclose(freqs[:19], base[:19], rtol=1e-6)
    np.testing.assert_allclose(freqs[35:], base[35:] / 16, rtol=1e-6)
    mid = freqs[19:35]
    assert ((mid < base[19:35]) & (mid > base[19:35] / 16)).all()
    c = dict(head_dim=128, rope_theta=5e5, yarn_factor=16.0,
             yarn_original_max_pos=8192, yarn_beta_fast=32.0,
             yarn_beta_slow=1.0, yarn_attention_factor=scale)
    inv, factor = REF.rope_tables(c, "full_attention")
    np.testing.assert_allclose(freqs, inv, rtol=1e-6)
    assert factor == scale


# ------------------------------------------- configs without layer_types
GRANITE = get_config("granite-moe-1b-a400m").replace(
    n_layers=2, d_model=128, n_heads=4, n_kv_heads=2, head_dim=32, d_ff=64,
    n_experts=4, top_k=2, capacity_factor=2.0, vocab_size=256,
    dtype="float32")


def test_granite_shape_without_layer_types_is_unchanged():
    """A stack with no ``layer_types`` is every layer full attention with
    default RoPE: the same logits and served tokens, bit for bit, as the
    same stack stated full attention layer by layer, on the shared pool
    and table with the kernel's ``maxp`` page axis."""
    assert GRANITE.attn_types == ("full_attention",) * 2
    assert transformer.unit_windows(GRANITE) == (0,)
    stated = GRANITE.replace(layer_types=("full_attention",) * 2)
    params = build(GRANITE).init(jax.random.PRNGKey(1))
    toks = jax.random.randint(jax.random.PRNGKey(2), (40,), 0, 256)
    a = _program_logits(params, GRANITE, toks)
    b = _program_logits(params, stated, toks)
    assert np.array_equal(a, b)
    _, out_a = _serve(GRANITE, params)
    _, out_b = _serve(stated, params)
    assert out_a == out_b
    from repro.serve import init_paged_cache
    scfg = ServeConfig(max_slots=3, page_size=4, max_len=96)
    assert init_paged_cache(GRANITE, scfg)["b0"]["kp"].shape == \
        (2, 3 * 24, 2, 4, 32)


@pytest.mark.parametrize("name,dtype", [("mellum2-12b-a2.5b", jnp.bfloat16),
                                        ("granite-moe-1b-a400m",
                                         jnp.float32)])
def test_param_dtype_is_honoured(name, dtype):
    tree = jax.eval_shape(build(get_config(name)).init, jax.random.PRNGKey(0))
    assert {l.dtype for l in jax.tree_util.tree_leaves(tree)} == {
        jnp.dtype(dtype)}
