"""Compressed client->server transport subsystem (repro/comm/): codec
round-trip error bounds, EF residual contraction, fused dequant-into-
aggregation parity (bit-exact vs decode-then-aggregate, quantization
error vs the dense fp32 oracle, incl. the mesh-sharded path), empty-
cohort x compression interaction, and the measured-bytes accounting
through ``fedfits.run``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.traversal import all_eqns
from repro.comm import codecs, error_feedback
from repro.configs.base import FedConfig
from repro.core import aggregation
from repro.kernels import robust_pipeline as rp

multidevice = pytest.mark.skipif(
    jax.device_count() < 2,
    reason="needs >=2 devices "
           "(XLA_FLAGS=--xla_force_host_platform_device_count=4)")

KEY = jax.random.PRNGKey(0)
AGGS = ["fedavg", "median", "trimmed_mean", "krum"]


def _tree(c, key=KEY):
    """Multi-leaf, ragged, tiny-bias tree (the shapes that stress the
    segment table + quant-block alignment)."""
    return {"a": jax.random.normal(key, (c, 13, 7)),
            "b": jax.random.normal(jax.random.fold_in(key, 1), (c, 301)),
            "c": jax.random.normal(jax.random.fold_in(key, 2), (c, 5)),
            "d": jax.random.normal(jax.random.fold_in(key, 3), (c, 512))}


# ---------------------------------------------------------------- codecs --
@pytest.mark.parametrize("name,levels", [("int8", 127.0), ("int4", 7.0)])
def test_quant_roundtrip_error_bound(name, levels):
    """Blockwise absmax quantization: per-coordinate error <= half a
    quantization step of its OWN block (s/2 = blockmax/levels/2)."""
    c, qblk = 6, 64
    tree = _tree(c)
    codec = codecs.Codec(name, qblk=qblk)
    dec = codec.decode_tree(codec.encode_tree(tree), tree)
    for k in tree:
        x = np.asarray(tree[k], np.float32).reshape(c, -1)
        d = np.asarray(dec[k], np.float32).reshape(c, -1)
        n = x.shape[1]
        nq = -(-n // qblk)
        xp = np.pad(x, ((0, 0), (0, nq * qblk - n))).reshape(c, nq, qblk)
        step = np.abs(xp).max(-1) / levels            # (c, nq)
        bound = np.repeat(step, qblk, axis=1)[:, :n]
        assert np.all(np.abs(x - d) <= 0.5 * bound + 1e-7), k


def test_int4_pack_unpack_exact():
    q = jax.random.randint(KEY, (3, 11), -7, 8, jnp.int8)
    p = codecs.pack_int4(q)
    assert p.dtype == jnp.uint8 and p.shape == (3, 6)
    np.testing.assert_array_equal(np.asarray(codecs.unpack_int4(p, 11)),
                                  np.asarray(q))


def test_bit_pack_unpack_exact():
    b = (jax.random.uniform(KEY, (4, 21)) > 0.5).astype(jnp.uint8)
    p = codecs.pack_bits(b)
    assert p.dtype == jnp.uint8 and p.shape == (4, 3)
    np.testing.assert_array_equal(np.asarray(codecs.unpack_bits(p, 21)),
                                  np.asarray(b))


def test_signsgd_roundtrip_sign_and_magnitude():
    c, qblk = 5, 32
    tree = {"w": jax.random.normal(KEY, (c, 100)) + 0.01}
    codec = codecs.Codec("signsgd", qblk=qblk)
    dec = codec.decode_tree(codec.encode_tree(tree), tree)
    x = np.asarray(tree["w"]); d = np.asarray(dec["w"])
    # signs preserved everywhere (no exact zeros in the input)
    assert np.all(np.sign(d) == np.sign(x))
    # magnitude = per-block mean |x| (tail block over its 100-96=4 reals)
    blocks = np.abs(x[:, :96]).reshape(c, 3, qblk).mean(-1)
    np.testing.assert_allclose(np.abs(d[:, :96]).reshape(c, 3, qblk),
                               np.repeat(blocks[:, :, None], qblk, 2),
                               rtol=1e-5)
    np.testing.assert_allclose(
        np.abs(d[:, 96:]),
        np.broadcast_to(np.abs(x[:, 96:]).mean(-1, keepdims=True), (c, 4)),
        rtol=1e-5)


def test_signsgd_majority_vote_defeats_minority_flippers():
    c = 9
    honest = jnp.ones((c, 64)) * 0.5
    upd = honest.at[0].set(-0.5).at[1].set(-0.5)      # 2/9 sign-flipped
    enc = codecs.Codec("signsgd", qblk=32).encode(upd)
    out = codecs.majority_vote(enc, 64, 32, jnp.ones((c,)))
    assert np.all(np.asarray(out) > 0.0)              # majority wins
    np.testing.assert_allclose(np.asarray(out), 0.5, rtol=1e-5)


def test_topk_keeps_largest_and_zeros_rest():
    c, n, frac = 4, 200, 0.1
    x = {"w": jax.random.normal(KEY, (c, n))}
    codec = codecs.Codec("topk", topk_frac=frac)
    enc_leaf = jax.tree_util.tree_flatten(
        codec.encode_tree(x), is_leaf=codecs.is_encoded)[0][0]
    dec = codec.decode_tree(codec.encode_tree(x), x)
    k = codec._k(n)
    assert enc_leaf.val.shape == (c, k) == enc_leaf.idx.shape
    xa, da = np.asarray(x["w"]), np.asarray(dec["w"])
    for i in range(c):
        nz = np.nonzero(da[i])[0]
        assert len(nz) == k
        np.testing.assert_array_equal(da[i][nz], xa[i][nz])  # kept exact
        # kept coords are the k largest magnitudes
        assert np.abs(xa[i][nz]).min() >= \
            np.sort(np.abs(xa[i]))[-k] - 1e-7


def test_randk_fallback_needs_rng_and_is_unbiased():
    c, n = 3, 150
    x = {"w": jax.random.normal(KEY, (c, n))}
    codec = codecs.Codec("randk", topk_frac=0.2)
    with pytest.raises(ValueError):
        codec.encode_tree(x)
    dec = codec.decode_tree(codec.encode_tree(x, rng=KEY), x)
    da, xa = np.asarray(dec["w"]), np.asarray(x["w"])
    k = codec._k(n)
    for i in range(c):
        nz = np.nonzero(da[i])[0]
        assert len(nz) == k                           # k distinct coords
        # kept values importance-scaled by n/k -> E[dec] = x (unbiased)
        np.testing.assert_allclose(da[i][nz], xa[i][nz] * n / k, rtol=1e-6)
    # unbiasedness over the index draw: the mean of many independent
    # decodes converges on the true vector
    acc = np.zeros_like(xa)
    reps = 200
    for r in range(reps):
        d = codec.decode_tree(
            codec.encode_tree(x, rng=jax.random.fold_in(KEY, r)), x)
        acc += np.asarray(d["w"])
    # per-coord std of the mean is ~|x| * sqrt((n/k - 1) / reps) ~ 0.14|x|;
    # atol sits at ~3.5 sigma of the largest coords
    np.testing.assert_allclose(acc / reps, xa, atol=1.2)


def test_wire_bytes_measured_from_actual_shapes():
    c = 4
    tree = {"w": jnp.zeros((c, 1024)), "b": jnp.zeros((c, 8))}
    dense = codecs.dense_bytes_per_client(tree)
    assert dense == (1024 + 8) * 4
    enc = codecs.Codec("int8", qblk=128).encode_tree(tree)
    wire = codecs.wire_bytes_per_client(enc)
    # codes: 1032 bytes; scales: (8 + 1) blocks * 4 bytes
    assert wire == 1032 + 9 * 4
    assert dense / wire > 3.5                         # the headline ratio
    # bf16 leaves bill 2 bytes, not the analytic flat 4
    assert codecs.dense_bytes_per_client(
        {"w": jnp.zeros((c, 10), jnp.bfloat16)}) == 20.0


# --------------------------------------------------------- error feedback --
def test_ef_residual_contracts_compression_error():
    """With a FIXED true update u each round, EF makes the decoded sum
    track the true sum: the running-mean error shrinks well below the
    single-shot compression error (the residual telescopes)."""
    c = 4
    u = {"w": jax.random.normal(KEY, (c, 256)) * 0.1}
    codec = codecs.Codec("topk", topk_frac=0.1)
    res = error_feedback.init(u)
    acc = jax.tree_util.tree_map(jnp.zeros_like, u)
    single = None
    for t in range(12):
        enc, dec, res = error_feedback.compress(codec, u, res)
        if t == 0:
            single = float(jnp.abs(dec["w"] - u["w"]).max())
        acc = jax.tree_util.tree_map(lambda a, d: a + d, acc, dec)
        # residual stays bounded (norm of what one round drops)
        assert float(jnp.abs(res["w"]).max()) <= 2.0 * float(
            jnp.abs(u["w"]).max()) * 256
    err = float(jnp.abs(acc["w"] / 12 - u["w"]).max())
    assert err < 0.5 * single, (err, single)


def test_ef_disabled_threads_none():
    u = {"w": jnp.ones((2, 64))}
    enc, dec, res = error_feedback.compress(
        codecs.Codec("int8"), u, None)
    assert res is None


# ------------------------------------------- the pipeline's int8 loader --
def _kernels(fn, *args):
    """Names of the Pallas kernels in ``fn``'s jaxpr."""
    return {e.params["name"] for _, e in all_eqns(jax.make_jaxpr(fn)(*args))
            if e.primitive.name == "pallas_call"}


@pytest.mark.parametrize("agg", AGGS)
def test_fused_dequant_bit_exact_vs_decode_then_aggregate(agg):
    """The int8 loader's in-VMEM dequant replays quant_decode's exact
    q_f32 * scale_f32 multiply into the same pass bodies as the dense
    loader, so aggregating the wire codes is BIT-IDENTICAL to decoding
    first and aggregating the decode."""
    c = 9
    tree = _tree(c)
    mask = jnp.ones((c,)).at[3].set(0.0)
    w = jax.random.uniform(jax.random.fold_in(KEY, 5), (c,)) + 0.1
    cfg = FedConfig(n_clients=c, aggregator=agg, compress="int8")
    codec = codecs.make_codec(cfg)
    enc = codec.encode_tree(tree)
    dec = codec.decode_tree(enc, tree)

    def fused(u, e, ww, m):
        return aggregation.aggregate(u, ww, m, cfg, enc=e)

    assert "dequant_pass1" in _kernels(fused, dec, enc, w, mask)
    out = jax.jit(fused)(dec, enc, w, mask)
    oracle = jax.jit(lambda u, ww, m: aggregation.aggregate(u, ww, m, cfg))(
        dec, w, mask)
    for k in tree:
        np.testing.assert_array_equal(np.asarray(out[k]),
                                      np.asarray(oracle[k]), err_msg=k)


@pytest.mark.parametrize("agg", ["trimmed_mean", "median", "krum"])
@pytest.mark.parametrize("c", [8, 9])                 # even + odd C
def test_fused_dequant_within_quant_error_of_dense_oracle(agg, c):
    """Acceptance bound: int8 aggregation from the codes within atol 1e-2
    of the dense fp32 multi-pass XLA oracle on the UNCOMPRESSED tree —
    at realistic update scale (local-lr-sized steps; the rank-based
    aggregators pass single coordinates through, so their error is the
    half-quantization-step of that coordinate's block, ~amax/254)."""
    tree = jax.tree_util.tree_map(lambda l: 0.25 * l, _tree(c))
    mask = jnp.ones((c,)).at[2].set(0.0)
    w = jax.random.uniform(jax.random.fold_in(KEY, 6), (c,)) + 0.1
    cfg = FedConfig(n_clients=c, aggregator=agg, compress="int8")
    codec = codecs.make_codec(cfg)
    enc = codec.encode_tree(tree)
    out = jax.jit(lambda e, ww, m: aggregation.aggregate(
        tree, ww, m, cfg, enc=e))(enc, w, mask)
    dense = aggregation.aggregate_ref(tree, w, mask, cfg)
    for k in tree:
        np.testing.assert_allclose(np.asarray(out[k]),
                                   np.asarray(dense[k]), atol=1e-2,
                                   err_msg=k)


@pytest.mark.parametrize("comp", ["int8", "int4", "signsgd", "topk"])
def test_empty_cohort_times_compression_yields_zero(comp):
    """An all-zero participation mask (a NORMAL slotted-protocol state)
    must produce a ZERO update through every codec path — the decoded
    tree through the dense loader AND int8 through the int8 loader."""
    c = 6
    tree = _tree(c)
    w = jnp.ones((c,))
    zero_mask = jnp.zeros((c,))
    cfg = FedConfig(n_clients=c, aggregator="trimmed_mean", compress=comp)
    codec = codecs.make_codec(cfg)
    enc = codec.encode_tree(tree)
    dec = codec.decode_tree(enc, tree)
    out = aggregation.aggregate(dec, w, zero_mask, cfg)
    assert all(not np.any(np.asarray(l))
               for l in jax.tree_util.tree_leaves(out))
    out = jax.jit(lambda e, ww, m: aggregation.aggregate(
        dec, ww, m, cfg, enc=e))(enc, w, zero_mask)
    assert all(not np.any(np.asarray(l))
               for l in jax.tree_util.tree_leaves(out))


def test_unfusable_blk_falls_back_to_decode_path():
    """A quant block that no streaming block tiles (256 against the
    128-lane block of a leaf narrower than 128) must route int8 through
    the dense loader on the decode instead of tripping the int8
    loader's alignment assert — and a full round must still run."""
    from repro.core import fedfits

    cfg = FedConfig(n_clients=6, aggregator="trimmed_mean",
                    compress="int8", compress_qblk=256)
    codec = codecs.make_codec(cfg)
    tree = {"w": jax.random.normal(KEY, (6, 512)),
            "b": jax.random.normal(jax.random.fold_in(KEY, 1), (6, 22))}
    enc = codec.encode_tree(tree)
    dec = codec.decode_tree(enc, tree)
    assert not rp.fusable([512, 22], 6, 256)
    assert rp.fusable([512, 22], 6, 128)
    names = _kernels(lambda u, e, w, m: aggregation.aggregate(
        u, w, m, cfg, enc=e), dec, enc, jnp.ones((6,)), jnp.ones((6,)))
    assert names == {"robust_pass1", "robust_pass2"}, names
    model, fed = _sim(6)
    state, _ = fedfits.run(model, cfg, fed.data_fn, 2,
                           jax.random.PRNGKey(7))
    assert float(state.cost_bytes_up) > 0


def test_fused_dequant_gate_excises_sign_flipped_clients():
    """The cosine outlier gate must keep working ON THE WIRE CODES: int8
    sign-flip poison is excised before the combine."""
    c = 8
    honest = jax.random.normal(KEY, (c, 256)) * 0.01 + 1.0
    upd = {"w": honest.at[0].set(-50.0).at[1].set(-50.0)}
    cfg = FedConfig(n_clients=c, aggregator="median", compress="int8")
    enc = codecs.make_codec(cfg).encode_tree(upd)
    out = jax.jit(lambda e: aggregation.aggregate(
        upd, jnp.ones((c,)), jnp.ones((c,)), cfg, enc=e))(enc)
    assert np.all(np.asarray(out["w"]) > 0.5)


@pytest.mark.parametrize("compress,qblk,kernel", [
    ("int8", 128, "dequant"), ("int8", 256, "robust"),
    ("none", 128, "robust")])
def test_round_routes_aggregation_by_wire_format(compress, qblk, kernel):
    """``make_round`` leaves the choice of loader to
    ``aggregation.aggregate``: an int8 uplink whose blocks the quant
    block tiles aggregates from the codes, an untiled one (paper-mlp's
    22-wide bias at qblk 256) or a dense uplink from the updates."""
    from repro.core import fedfits

    k = 6
    model, fed = _sim(k)
    cfg = FedConfig(n_clients=k, algorithm="fedfits", local_epochs=1,
                    aggregator="trimmed_mean", compress=compress,
                    compress_qblk=qblk)
    state = fedfits.init_state(model.init(KEY), k, cfg, KEY)
    batch = fed.data_fn(0, KEY)
    names = _kernels(fedfits.make_round(model, cfg), state, batch)
    assert f"{kernel}_pass1" in names, names
    other = "robust" if kernel == "dequant" else "dequant"
    assert not any(n.startswith(other) for n in names), names


# ------------------------------------------------------ sharded dequant --
@multidevice
@pytest.mark.parametrize("agg", AGGS)
def test_sharded_fused_dequant_matches_oracle(agg):
    """4-device shard_map int8 loader: codes + scales shard together
    (align=qblk), parity vs decode-then-reference within the shard-local
    summation-order tolerance."""
    from jax.sharding import Mesh

    c = 8
    tree = {"w": jax.random.normal(KEY, (c, 64, 8)),
            "r": jax.random.normal(jax.random.fold_in(KEY, 1), (c, 301)),
            "b": jax.random.normal(jax.random.fold_in(KEY, 2), (c, 5)),
            "h": jax.random.normal(jax.random.fold_in(KEY, 3), (c, 2048))}
    mask = jnp.ones((c,)).at[2].set(0.0)
    w = jax.random.uniform(jax.random.fold_in(KEY, 4), (c,)) + 0.1
    cfg = FedConfig(n_clients=c, aggregator=agg, compress="int8")
    codec = codecs.make_codec(cfg)
    enc = codec.encode_tree(tree)
    dec = codec.decode_tree(enc, tree)
    mesh = Mesh(np.array(jax.devices()).reshape(jax.device_count()),
                ("data",))

    def fused(u, e, ww, m):
        return aggregation.aggregate(u, ww, m, cfg, enc=e, mesh=mesh,
                                     axes=("data",))

    assert "dequant_pass1" in _kernels(fused, dec, enc, w, mask)
    out = jax.jit(fused)(dec, enc, w, mask)
    ref = aggregation.aggregate_ref(dec, w, mask, cfg)
    for k in tree:
        np.testing.assert_allclose(np.asarray(out[k]), np.asarray(ref[k]),
                                   atol=1e-5, err_msg=k)


@multidevice
def test_sharded_dequant_scale_alignment_flags():
    """align=qblk: a leaf divisible by the axis extent but NOT by
    extent*qblk must stay replicated (its scale columns cannot shard
    alongside its codes)."""
    from repro.sharding import specs as sh
    from jax.sharding import Mesh

    D = jax.device_count()
    mesh = Mesh(np.array(jax.devices()).reshape(D), ("data",))
    sizes = [2048 * D, 8 * D, 301]
    _, flags = sh.client_flat_specs(sizes, mesh, ("data",), align=128)
    assert flags == (True, False, False)


# ------------------------------------------------ fedfits round wiring --
def _sim(k=6):
    from repro.configs.registry import ARCHS
    from repro.data.pipeline import build_federation
    from repro.models.model import build

    model = build(ARCHS["paper-mlp"])
    fed, test = build_federation(0, kind="tabular", n=600, n_clients=k,
                                 batch_size=16, n_classes=22)
    return model, fed


def test_measured_bytes_accounting_and_unchanged_client_rounds():
    """fedfits.run bills the MEASURED encoded uplink (int8 ~3.9x below
    dense) and the dense downlink, at unchanged cost_client_rounds
    (selection is driven by client-side fitness, untouched by the
    codec)."""
    from repro.core import fedfits

    k = 6
    model, fed = _sim(k)
    outs = {}
    for comp in ["none", "int8"]:
        cfg = FedConfig(n_clients=k, algorithm="fedfits", local_epochs=1,
                        local_lr=0.05, aggregator="trimmed_mean",
                        compress=comp)
        state, _ = fedfits.run(model, cfg, fed.data_fn, 3,
                               jax.random.PRNGKey(7))
        outs[comp] = state
    dense, int8 = outs["none"], outs["int8"]
    assert float(dense.cost_client_rounds) == float(int8.cost_client_rounds)
    assert float(dense.cost_bytes_down) == float(int8.cost_bytes_down) > 0
    ratio = float(dense.cost_bytes_up) / float(int8.cost_bytes_up)
    assert ratio >= 3.5, ratio
    # dense measured == dense itemsize accounting (all-f32 model)
    params = model.init(jax.random.PRNGKey(0))
    p_bytes = sum(l.size * l.dtype.itemsize
                  for l in jax.tree_util.tree_leaves(params))
    assert float(dense.cost_bytes_up) == \
        float(dense.cost_client_rounds) * p_bytes


def test_scan_driver_bitwise_parity_with_compression():
    """driver="scan" must stay bit-for-bit equal to driver="python" with
    the codec + EF residual threaded through the donated carry."""
    from repro.core import fedfits

    k = 6
    model, fed = _sim(k)
    cfg = FedConfig(n_clients=k, algorithm="fedfits", local_epochs=1,
                    local_lr=0.05, aggregator="trimmed_mean",
                    compress="int8", avail_prob=0.7)
    s_py, h_py = fedfits.run(model, cfg, fed.data_fn, 4,
                             jax.random.PRNGKey(7), driver="python")
    s_sc, h_sc = fedfits.run(model, cfg, fed.data_fn, 4,
                             jax.random.PRNGKey(7), driver="scan",
                             chunk_rounds=3)
    for a, b in zip(jax.tree_util.tree_leaves(s_py),
                    jax.tree_util.tree_leaves(s_sc)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_pod_compress_requires_per_client_boundary():
    from repro.configs.base import TrainConfig
    from repro.configs.registry import ARCHS
    from repro.core import pod

    with pytest.raises(ValueError):
        pod.make_train_step(ARCHS["tiny-lm"].reduced(),
                            FedConfig(n_clients=4, compress="int8"),
                            TrainConfig(global_batch=8, seq_len=32))


def _pod_run(comp, rounds=4):
    from repro.configs.base import TrainConfig
    from repro.configs.registry import ARCHS
    from repro.core import pod
    from repro.launch.train import synthetic_lm_batches
    from repro.models import transformer
    from repro.optim import optimizers

    cfgm = ARCHS["tiny-lm"].replace(n_layers=2, d_model=64, n_heads=4,
                                    n_kv_heads=2, d_ff=128, vocab_size=128,
                                    head_dim=16)
    C, B, S = 4, 8, 32
    fed_cfg = FedConfig(n_clients=C, aggregator="trimmed_mean",
                        compress=comp)
    tc = TrainConfig(global_batch=B, seq_len=S, lr=1e-2, warmup_steps=2,
                     total_steps=rounds)
    params = transformer.init_transformer(jax.random.PRNGKey(0), cfgm)
    opt_init, _ = optimizers.make_optimizer(tc)
    state = pod.init_pod_state(params, opt_init, C, fed_cfg,
                               jax.random.PRNGKey(0))
    step = pod.make_train_step(cfgm, fed_cfg, tc, robust="per_client")
    sampler = synthetic_lm_batches(cfgm, tc, C, 0)
    skey = jax.random.PRNGKey(123)                # never aliased
    return pod.run(state, step, lambda t: sampler(jax.random.fold_in(
        skey, t)), rounds, driver="scan", chunk_rounds=2)


def test_pod_per_client_compressed_scan_run():
    """The pod engine's codec path end-to-end through the scan driver:
    EF residual rides the donated PodFedState carry across chunks, the
    int8 wire codes feed the fused dequant aggregation, the measured
    comm_bytes_up metric surfaces per round — and the trajectory stays
    within quantization distance of the dense run."""
    state_d, hist_d = _pod_run("none")
    state_c, hist_c = _pod_run("int8")
    assert "comm_bytes_up" not in hist_d[0]
    assert hist_c[0]["comm_bytes_up"] > 0
    assert state_c.fed.ef is not None             # EF survived the carry
    assert any(float(jnp.abs(l).max()) > 0
               for l in jax.tree_util.tree_leaves(state_c.fed.ef))
    for rd, rc in zip(hist_d, hist_c):
        assert np.isfinite(rc["loss"])
        np.testing.assert_allclose(rc["loss"], rd["loss"], atol=5e-2)


def test_topk_ef_reaches_dense_accuracy_on_images():
    """Acceptance: EF-enabled top-k within 1 point of the dense path's
    best accuracy on the synthetic image benchmark (the residual
    re-injects every dropped coordinate within a few rounds)."""
    from benchmarks import common

    model, fed, ev = common.make_setup("images", n_clients=8, n=1200)
    best = {}
    for comp in ["none", "topk"]:
        r = common.run_fl(model, fed, ev, algo="fedfits", rounds=8,
                          n_clients=8, aggregator="trimmed_mean",
                          compress=comp, compress_topk_frac=0.1)
        best[comp] = r["best_acc"]
    assert best["topk"] >= best["none"] - 0.01, best
