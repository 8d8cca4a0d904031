"""Mesh-sharded robust aggregation (``aggregation.aggregate(..., mesh=)``)
vs the replicated oracles on forced multi-device CPU.

Run with XLA_FLAGS=--xla_force_host_platform_device_count=4 (the CI
multi-device job does); on a single-device interpreter these tests skip —
the trivial 1-device mesh path is still covered by the sharded bench
entry in benchmarks/bench_kernels.py.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.analysis.hlo import parse_collectives
from repro.analysis.traversal import all_eqns
from repro.configs.base import FedConfig
from repro.core import aggregation

multidevice = pytest.mark.skipif(
    jax.device_count() < 2,
    reason="needs >=2 devices "
           "(XLA_FLAGS=--xla_force_host_platform_device_count=4)")

KEY = jax.random.PRNGKey(0)
AGGS = ["fedavg", "median", "trimmed_mean", "krum"]


def _mesh(shape, names):
    from jax.sharding import Mesh
    devs = np.array(jax.devices()[: int(np.prod(shape))]).reshape(shape)
    return Mesh(devs, names)


def _tree(c):
    """Sharded-path exercise tree: a divisible matrix leaf, a ragged leaf
    (stays replicated), a tiny bias leaf, and a bf16 divisible leaf."""
    return {"w": jax.random.normal(KEY, (c, 64, 8)),
            "r": jax.random.normal(jax.random.fold_in(KEY, 1), (c, 301)),
            "b": jax.random.normal(jax.random.fold_in(KEY, 2), (c, 5)),
            "h": jax.random.normal(jax.random.fold_in(KEY, 3),
                                   (c, 256)).astype(jnp.bfloat16)}


@multidevice
@pytest.mark.parametrize("agg", AGGS)
def test_sharded_matches_ref_all_modes(agg):
    c = 8
    tree = _tree(c)
    mask = jnp.ones((c,)).at[2].set(0.0)
    w = jax.random.uniform(jax.random.fold_in(KEY, 4), (c,)) + 0.1
    cfg = FedConfig(n_clients=c, aggregator=agg)
    mesh = _mesh((jax.device_count(),), ("data",))
    out = aggregation.aggregate(tree, w, mask, cfg, mesh=mesh,
                                axes=("data",))
    ref = aggregation.aggregate_ref(tree, w, mask, cfg)
    for k in ref:
        assert out[k].dtype == tree[k].dtype
        atol = 1e-5 if out[k].dtype == jnp.float32 else 2e-2
        np.testing.assert_allclose(np.asarray(out[k], np.float32),
                                   np.asarray(ref[k], np.float32),
                                   atol=atol, err_msg=k)


@multidevice
def test_sharded_2d_mesh_and_pod_axis_excluded():
    """Default axes skip "pod"; a 2D ("data","model") sub-mesh shards the
    flat axis over both."""
    if jax.device_count() < 4:
        pytest.skip("needs 4 devices")
    c = 8
    tree = _tree(c)
    mask = jnp.ones((c,))
    w = jnp.ones((c,))
    cfg = FedConfig(n_clients=c, aggregator="trimmed_mean")
    mesh = _mesh((2, 2), ("data", "model"))
    out = aggregation.aggregate(tree, w, mask, cfg, mesh=mesh)
    ref = aggregation.aggregate_ref(tree, w, mask, cfg)
    for k in ref:
        atol = 1e-5 if out[k].dtype == jnp.float32 else 2e-2
        np.testing.assert_allclose(np.asarray(out[k], np.float32),
                                   np.asarray(ref[k], np.float32),
                                   atol=atol, err_msg=k)


@multidevice
def test_sharded_gate_excises_sign_flipped_clients():
    """The cosine gate must resolve identically when the partials arrive
    via the cross-device psum."""
    c = 8
    honest = jax.random.normal(KEY, (c, 64)) * 0.01 + 1.0
    upd = {"w": honest.at[0].set(-50.0).at[1].set(-50.0)}
    cfg = FedConfig(n_clients=c, aggregator="median")
    mesh = _mesh((jax.device_count(),), ("data",))
    out = aggregation.aggregate(upd, jnp.ones((c,)), jnp.ones((c,)), cfg,
                                mesh=mesh, axes=("data",))
    assert np.all(np.asarray(out["w"]) > 0.5)


@multidevice
def test_no_reshard_between_backward_and_shard_map():
    """ROADMAP open item 2: the per-client vmap'd backward's grad outputs
    are constrained to the ``client_flat_specs`` layout, so the
    ``aggregate(..., mesh=)`` shard_map boundary does no reshard.  Guarded at
    two levels: (a) in the jaxpr, every tensor operand of the shard_map
    is produced by a ``sharding_constraint`` whose sharding IS the
    boundary's in_spec — GSPMD therefore has nothing to move; (b) the
    compiled backward->aggregation program contains no all-to-all."""
    from repro.configs.registry import ARCHS
    from repro.data import synthetic
    from repro.models import transformer
    from repro.sharding import specs as sh

    CFG = ARCHS["tiny-lm"].replace(n_layers=2, d_model=64, n_heads=4,
                                   n_kv_heads=2, d_ff=128, vocab_size=128,
                                   head_dim=16)
    C, B, S = 4, 8, 32
    cfg = FedConfig(n_clients=C, aggregator="trimmed_mean")
    params = transformer.init_transformer(KEY, CFG)
    toks = synthetic.make_lm_tokens(KEY, B, S + 1, CFG.vocab_size,
                                    n_latent=2)
    batch = {"tokens": toks[:, :-1], "targets": toks[:, 1:]}
    mesh = _mesh((jax.device_count(),), ("data",))

    def backward_and_agg(params, batch, w, team):
        def client_grad(c):
            bc = B // C

            def one_loss(p):
                sub = {k: jax.lax.dynamic_slice_in_dim(v, c * bc, bc)
                       for k, v in batch.items()}
                return transformer.loss_fn(p, CFG, sub)

            (_, _), g = jax.value_and_grad(one_loss, has_aux=True)(params)
            return g

        grads_c = jax.vmap(client_grad)(jnp.arange(C))
        return aggregation.aggregate(grads_c, w, team, cfg, mesh=mesh,
                                     axes=("data",))

    w = jnp.full((C,), 1.0 / C)
    team = jnp.ones((C,))
    jaxpr = jax.make_jaxpr(backward_and_agg)(params, batch, w, team)

    shard_maps = [(j, e) for j, e in all_eqns(jaxpr)
                  if e.primitive.name == "shard_map"]
    assert len(shard_maps) == 1
    j, eqn = shard_maps[0]
    producers = {id(ov): e2 for e2 in j.eqns for ov in e2.outvars}
    checked = 0
    for iv in eqn.invars:
        shape = getattr(iv.aval, "shape", ())
        if len(shape) != 3:
            continue                     # (C,) weights/mask ride replicated
        prod = producers.get(id(iv))
        assert prod is not None and prod.primitive.name == \
            "sharding_constraint", (shape, prod and prod.primitive.name)
        expected, _ = sh.client_flat_specs([shape[-1]], mesh, ("data",))
        assert prod.params["sharding"].spec == expected[0], shape
        checked += 1
    assert checked >= 4                  # every grad leaf crosses constrained

    txt = jax.jit(backward_and_agg).lower(params, batch, w, team) \
        .compile().as_text()
    assert parse_collectives(txt)["all-to-all"] == 0


@multidevice
def test_pod_run_prefetch_stages_per_shard_and_matches_python():
    """Sharding-aware prefetch (ROADMAP open item 3): pod.run's scan
    driver stages each chunk's batches DIRECTLY onto their pod shards
    (device_put with the lifted NamedSharding), and the sharded-staged
    scan history stays bit-for-bit equal to the python per-round loop fed
    the same shardings."""
    from jax.sharding import PartitionSpec as P

    from repro.configs.base import TrainConfig
    from repro.configs.registry import ARCHS
    from repro.core import driver, pod
    from repro.launch import inputs
    from repro.launch.train import synthetic_lm_batches
    from repro.models import transformer
    from repro.optim import optimizers

    CFG = ARCHS["tiny-lm"].replace(n_layers=2, d_model=64, n_heads=4,
                                   n_kv_heads=2, d_ff=128, vocab_size=128,
                                   head_dim=16)
    C, B, S = 4, 8, 32
    mesh = _mesh((jax.device_count(),), ("data",))

    def setup(seed=0):
        key = jax.random.PRNGKey(seed)
        fed = FedConfig(n_clients=C)
        tc = TrainConfig(global_batch=B, seq_len=S, lr=1e-2,
                         warmup_steps=2, total_steps=8)
        params = transformer.init_transformer(key, CFG)
        opt_init, _ = optimizers.make_optimizer(tc)
        state = pod.init_pod_state(params, opt_init, C, fed, key)
        step = pod.make_train_step(CFG, fed, tc)
        sampler = synthetic_lm_batches(CFG, tc, C, seed)
        return key, state, step, sampler

    key, state_sc, step, sampler = setup()
    _, state_py, _, _ = setup()
    sample_key = jnp.array(np.asarray(key))

    def batch_fn(t):
        return sampler(jax.random.fold_in(sample_key, t))

    batch_sh = inputs.batch_shardings(
        jax.eval_shape(sampler, jax.random.PRNGKey(0)), mesh)
    assert batch_sh["tokens"].spec == P(("data",), None)

    # staging lands on the shards, leading chunk dim replicated
    lifted = driver.chunk_sharding(batch_sh)
    _, stacked = driver.stage_chunk(batch_fn, [0, 1, 2], lifted)
    assert stacked["tokens"].shape == (3, B, S)
    assert stacked["tokens"].sharding == lifted["tokens"]
    assert len(stacked["tokens"].sharding.device_set) == jax.device_count()

    s_sc, h_sc = pod.run(state_sc, step, batch_fn, 5, driver="scan",
                         chunk_rounds=2, batch_sharding=batch_sh)
    s_py, h_py = pod.run(state_py, step, batch_fn, 5, driver="python",
                         batch_sharding=batch_sh)
    assert len(h_sc) == len(h_py) == 5
    for r_py, r_sc in zip(h_py, h_sc):
        for k in r_py:
            np.testing.assert_array_equal(
                np.asarray(r_py[k]), np.asarray(r_sc[k]),
                err_msg=f"step {r_py['step']} key {k}")
    for a, b in zip(jax.tree_util.tree_leaves(s_py.params),
                    jax.tree_util.tree_leaves(s_sc.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@multidevice
def test_pod_per_client_sharded_matches_replicated():
    """One pod train step with robust='per_client': the mesh-sharded
    aggregation path must reproduce the replicated path's new params."""
    from repro.configs.base import TrainConfig
    from repro.configs.registry import ARCHS
    from repro.core import pod
    from repro.data import synthetic
    from repro.models import transformer
    from repro.optim import optimizers

    CFG = ARCHS["tiny-lm"].replace(n_layers=2, d_model=64, n_heads=4,
                                   n_kv_heads=2, d_ff=128, vocab_size=128,
                                   head_dim=16)
    C, B, S = 4, 8, 32
    fed = FedConfig(n_clients=C, aggregator="trimmed_mean")
    tc = TrainConfig(global_batch=B, seq_len=S, total_steps=4,
                     warmup_steps=1)
    params = transformer.init_transformer(KEY, CFG)
    opt_init, _ = optimizers.make_optimizer(tc)
    state = pod.init_pod_state(params, opt_init, C, fed, KEY)
    toks = synthetic.make_lm_tokens(KEY, B, S + 1, CFG.vocab_size,
                                    n_latent=2)
    batch = {"tokens": toks[:, :-1], "targets": toks[:, 1:]}

    mesh = _mesh((jax.device_count(),), ("data",))
    step_rep = jax.jit(pod.make_train_step(CFG, fed, tc,
                                           robust="per_client"))
    step_sh = jax.jit(pod.make_train_step(CFG, fed, tc,
                                          robust="per_client",
                                          agg_mesh=mesh, agg_axes=("data",)))
    s_rep, m_rep = step_rep(state, batch)
    s_sh, m_sh = step_sh(state, batch)
    np.testing.assert_allclose(float(m_rep["loss"]), float(m_sh["loss"]),
                               rtol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(s_rep.params),
                    jax.tree_util.tree_leaves(s_sh.params)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=1e-5)
