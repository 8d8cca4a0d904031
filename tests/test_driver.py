"""Shared chunked-scan round driver (core/driver.py) + pod.run: scan vs
python-loop bit-for-bit parity, the donated-carry PRNG aliasing footgun,
and the sharding-aware chunk staging helpers."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import FedConfig, TrainConfig
from repro.configs.registry import ARCHS
from repro.core import driver, pod
from repro.launch.train import synthetic_lm_batches
from repro.models import transformer
from repro.optim import optimizers

CFG = ARCHS["tiny-lm"].replace(n_layers=2, d_model=64, n_heads=4,
                               n_kv_heads=2, d_ff=128, vocab_size=128,
                               head_dim=16)
C, B, S = 4, 8, 32


def _setup(seed=0):
    """train.py-shaped setup: pod state whose PodFedState.rng ALIASES the
    returned key (the donated-carry footgun), plus the jitted sampler."""
    key = jax.random.PRNGKey(seed)
    fed = FedConfig(n_clients=C)
    tc = TrainConfig(global_batch=B, seq_len=S, lr=1e-2, warmup_steps=2,
                     total_steps=10)
    params = transformer.init_transformer(key, CFG)
    opt_init, _ = optimizers.make_optimizer(tc)
    state = pod.init_pod_state(params, opt_init, C, fed, key)
    step = pod.make_train_step(CFG, fed, tc)
    sampler = synthetic_lm_batches(CFG, tc, C, seed)
    return key, state, step, sampler


def _assert_history_equal(h_a, h_b):
    assert len(h_a) == len(h_b)
    for r_a, r_b in zip(h_a, h_b):
        assert set(r_a) == set(r_b)
        for k in r_a:
            np.testing.assert_array_equal(
                np.asarray(r_a[k]), np.asarray(r_b[k]),
                err_msg=f"step {r_a['step']} key {k}")


def test_pod_scan_matches_python_loop_bitwise():
    """pod.run driver="scan" must reproduce the per-round jitted loop
    over make_train_step bit-for-bit — including a ragged tail chunk."""
    key, s_py_state, step, sampler = _setup()
    _, s_sc_state, _, _ = _setup()
    sample_key = jnp.array(np.asarray(key))     # copy: the carry is donated

    def batch_fn(t):
        return sampler(jax.random.fold_in(sample_key, t))

    s_py, h_py = pod.run(s_py_state, step, batch_fn, 7, driver="python")
    s_sc, h_sc = pod.run(s_sc_state, step, batch_fn, 7, driver="scan",
                         chunk_rounds=3)
    _assert_history_equal(h_py, h_sc)
    for a, b in zip(jax.tree_util.tree_leaves(s_py.params),
                    jax.tree_util.tree_leaves(s_sc.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_donated_carry_prng_aliasing_regression():
    """ROADMAP footgun: the scan chunk donates the carry, and the carry
    aliases the init key via PodFedState.rng — the sampler MUST consume
    its key from a copy taken before the first chunk, or the donated
    buffer error bites mid-run.  Guards that (a) sampling from the copy
    keeps the drivers bit-for-bit, (b) when donation is active the
    aliased original is really gone."""
    key, state, step, sampler = _setup(seed=3)
    _, state2, _, _ = _setup(seed=3)
    sample_key = jnp.array(np.asarray(key))     # the REQUIRED live copy

    def batch_fn(t):
        return sampler(jax.random.fold_in(sample_key, t))

    s_sc, h_sc = pod.run(state, step, batch_fn, 6, driver="scan",
                         chunk_rounds=2)
    if key.is_deleted():
        # donation active: the original key's buffer was freed by chunk 0
        # — a sampler holding `key` instead of the copy would crash here
        with pytest.raises((RuntimeError, ValueError)):
            jax.random.fold_in(key, 0).block_until_ready()
    _, h_py = pod.run(state2, step, batch_fn, 6, driver="python")
    _assert_history_equal(h_py, h_sc)


def test_run_chunked_rows_ragged_tail_and_on_chunk():
    """Generic driver contract: n_steps rows labeled by index_key, a
    ragged tail chunk, and the per-chunk callback firing with live
    state."""
    def body(st, xs):
        t, batch = xs
        st = st + batch["x"]
        return st, {"sum": st, "t": t}

    calls = []
    state, hist = driver.run_chunked(
        body, jnp.float32(0.0), lambda t: {"x": jnp.float32(t)}, 5,
        chunk_steps=3, t0=1, index_key="round",
        on_chunk=lambda st, rows: calls.append(len(rows)))
    assert [r["round"] for r in hist] == [1, 2, 3, 4, 5]
    assert calls == [3, 2]                       # full chunk + ragged tail
    np.testing.assert_allclose([r["sum"] for r in hist],
                               np.cumsum([1, 2, 3, 4, 5]))
    assert float(state) == 15.0


def test_chunk_sharding_lifts_leading_dim():
    """The stacked (chunk, ...) batches keep the per-batch sharding with
    a leading replicated chunk dim."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1), ("data",))
    tree = {"tokens": NamedSharding(mesh, P("data", None)),
            "targets": NamedSharding(mesh, P("data", None))}
    lifted = driver.chunk_sharding(tree)
    assert lifted["tokens"].spec == P(None, "data", None)
    assert lifted["targets"].mesh == mesh


def test_stage_chunk_places_batches_on_sharding():
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1), ("data",))
    batch_sh = {"x": NamedSharding(mesh, P("data", None))}
    lifted = driver.chunk_sharding(batch_sh)
    ts_dev, stacked = driver.stage_chunk(
        lambda t: {"x": jnp.ones((2, 3)) * t}, [0, 1, 2], lifted)
    assert stacked["x"].shape == (3, 2, 3)
    assert stacked["x"].sharding == lifted["x"]
    np.testing.assert_array_equal(np.asarray(ts_dev), [0, 1, 2])


class _Spans:
    """The duck-typed telemetry the driver calls, keeping its spans."""

    def __init__(self):
        from repro.obs import TraceRecorder
        self.tracer = TraceRecorder("sync")

    def now_us(self):
        return 0.0

    def observe_rows(self, rows, w0, dur):
        pass

    def feeds(self):
        return [e["args"]["feed"] for e in self.tracer.events
                if e["name"] == "driver.stage"]


def _float_body(st, xs):
    t, batch = xs
    h = jnp.tanh(st["w"] @ batch["x"].T + batch["y"].astype(jnp.float32))
    st = {"w": st["w"] * 0.9 + 0.1 * h.mean(-1, keepdims=True),
          "acc": st["acc"] + h.sum() * batch["s"]}
    return st, {"h": h, "t": t}


def _float_batches(n):
    rng = np.random.default_rng(0)
    return [{"x": rng.standard_normal((5, 3)).astype(np.float32),
             "y": rng.integers(0, 4, (5,)).astype(np.int32),
             "s": np.float32(rng.standard_normal())} for _ in range(n)]


@pytest.mark.parametrize("feed", ["device", "host"])
def test_run_chunked_device_and_host_feeds_bitwise(feed):
    """Device-array batches go to the chunk unstacked and are stacked
    inside it; numpy batches are stacked on host.  Either feed, with a
    ragged tail chunk, gives the per-step jitted loop's history and final
    state bit for bit, and ``driver.stage`` names the feed it took."""
    n = 7
    host = _float_batches(n)
    batches = (host if feed == "host" else
               [jax.tree_util.tree_map(jnp.asarray, b) for b in host])

    def state0():
        return {"w": jnp.linspace(-1.0, 1.0, 12, dtype=jnp.float32
                                  ).reshape(4, 3),
                "acc": jnp.float32(0.0)}

    step = jax.jit(_float_body)
    st_ref, h_ref = state0(), []
    for t in range(n):
        st_ref, m = step(st_ref, (jnp.int32(t), batches[t]))
        h_ref.append({**jax.device_get(m), "step": t})

    tele = _Spans()
    st, hist = driver.run_chunked(_float_body, state0(),
                                  lambda t: batches[t], n, chunk_steps=3,
                                  telemetry=tele)
    assert tele.feeds() == [feed] * 3                # 3 + 3 + ragged 1
    _assert_history_equal(h_ref, hist)
    for a, b in zip(jax.tree_util.tree_leaves(st_ref),
                    jax.tree_util.tree_leaves(st)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_device_feed_stacks_only_inside_the_chunk(monkeypatch):
    """The device feed issues no host-side ``jnp.stack``: every stack of a
    run over device-array batches happens while the chunk is traced."""
    real = jnp.stack

    def traced_only(xs, *a, **k):
        if not any(isinstance(x, jax.core.Tracer) for x in xs):
            raise AssertionError("jnp.stack outside the compiled chunk")
        return real(xs, *a, **k)

    monkeypatch.setattr(driver.jnp, "stack", traced_only)
    batches = [jax.tree_util.tree_map(jnp.asarray, b)
               for b in _float_batches(5)]
    tele = _Spans()
    _, hist = driver.run_chunked(
        _float_body, {"w": jnp.ones((4, 3)), "acc": jnp.float32(0.0)},
        lambda t: batches[t], 5, chunk_steps=2, telemetry=tele)
    assert tele.feeds() == ["device"] * 3
    assert [r["step"] for r in hist] == list(range(5))


def test_fedfits_chunk_keys_are_fold_in_of_each_round():
    """``fedfits.run`` hands ``data_fn`` the key ``fold_in(rng, t)`` of
    every round, bit for bit, though it derives a chunk's keys in one
    program — the tail chunk's too."""
    from repro.configs.base import FedConfig
    from repro.core import fedfits
    from repro.data.pipeline import build_federation
    from repro.models.model import build

    model = build(ARCHS["paper-mlp"])
    fed, _ = build_federation(6, kind="tabular", n=240, n_clients=6,
                              batch_size=8, n_classes=10)
    cfg = FedConfig(n_clients=6, algorithm="fedfits", local_epochs=1,
                    local_lr=0.05)
    rng = jax.random.PRNGKey(2 ** 31 + 11)
    seen = []

    def data_fn(t, key):
        seen.append((t, np.asarray(key)))
        return fed.data_fn(t, key)

    fedfits.run(model, cfg, data_fn, 5, rng, driver="scan", chunk_rounds=2)
    assert [t for t, _ in seen] == [1, 2, 3, 4, 5]
    for t, key in seen:
        np.testing.assert_array_equal(
            key, np.asarray(jax.random.fold_in(rng, t)), err_msg=str(t))
    keys = fedfits.round_keys(rng, np.asarray([6, 7, 2 ** 31 + 3],
                                              np.uint32))
    for t, key in zip([6, 7, 2 ** 31 + 3], keys):
        np.testing.assert_array_equal(
            np.asarray(key), np.asarray(jax.random.fold_in(rng, t)))
