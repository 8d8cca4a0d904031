"""The audited entry points.

Each :class:`EntryPoint` lazily builds a :class:`Target` — a concrete
jittable fn + args at linter scale (tiny models, small cohorts: the
invariants under audit are structural, not scale-dependent) — plus the
entry's declared expectations: copy-lint mode and threshold, collective
byte allowlist, donation expectations, rng-advance checks.

Registering a new entry point (see the package docstring for the full
guide)::

    @register_entry("my_entry", min_devices=1)
    def _build():
        fn, args = ...
        return Target(fn, args, copy_mode="engine",
                      copy_threshold=max_param_leaf, ...)
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Optional, Tuple


@dataclasses.dataclass
class Target:
    """One traced entry: the fn, its example args, and expectations."""
    fn: Callable
    args: Tuple
    donate_argnums: Tuple[int, ...] = ()
    copy_mode: str = "off"              # "strict" | "engine" | "off"
    copy_threshold: int = 0
    collective_allowlist: Optional[Dict[str, int]] = None
    donate_must_alias: Tuple = ()       # ((flat param number, path), ...)
    check_rng_advance: bool = False
    rules_off: Tuple[str, ...] = ()
    compile: bool = True                # lower+compile for hlo-kind rules
    hbm_pass_cap: Optional[float] = None   # fusion_count: max HBM-pass
                                           # multiple of the payload
    hbm_payload_bytes: int = 0             # one pass worth of bytes
    hbm_bytes_threshold: int = 0           # min buffer size that counts


@dataclasses.dataclass
class EntryPoint:
    name: str
    build: Callable[[], Target]
    min_devices: int = 1
    doc: str = ""


ENTRYPOINTS: Dict[str, EntryPoint] = {}


def register_entry(name: str, *, min_devices: int = 1, doc: str = ""):
    def deco(build_fn):
        ENTRYPOINTS[name] = EntryPoint(name, build_fn, min_devices, doc)
        return build_fn
    return deco


def _leaf_sizes(tree):
    import jax
    return [int(l.size) for l in jax.tree_util.tree_leaves(tree)
            if hasattr(l, "size")]


def _leaf_bytes(tree):
    import jax
    return sum(int(l.size) * int(l.dtype.itemsize)
               for l in jax.tree_util.tree_leaves(tree)
               if hasattr(l, "size"))


def _must_alias(state, prefixes):
    """(flat param number, path) pairs for the heavy carry buffers that a
    donating entry MUST reuse in place.  Bookkeeping scalars that stay
    live past the return (e.g. fairness counters read by metrics) are
    legitimately copied, so the contract names buffer families by path
    prefix rather than demanding every leaf alias."""
    import jax
    flat, _ = jax.tree_util.tree_flatten_with_path(state)
    return tuple(
        (i, jax.tree_util.keystr(path))
        for i, (path, _leaf) in enumerate(flat)
        if any(jax.tree_util.keystr(path).startswith(p) for p in prefixes))


# --------------------------------------------------------------------- #
# aggregation kernels (strict copy lint: the PR-2 no-flatten contract)  #
# --------------------------------------------------------------------- #

def _mixed_tree(c, key=None):
    """The PR-2 guard's multi-leaf mixed-dtype odd-size tree."""
    import jax
    import jax.numpy as jnp
    key = key if key is not None else jax.random.PRNGKey(0)
    return {"a": jax.random.normal(key, (c, 13, 7)),
            "b": jax.random.normal(jax.random.fold_in(key, 1),
                                   (c, 301)).astype(jnp.bfloat16),
            "c": jax.random.normal(jax.random.fold_in(key, 2), (c, 5)),
            "d": jax.random.normal(jax.random.fold_in(key, 3),
                                   (c, 192)).astype(jnp.float16)}


@register_entry("aggregate", doc="fused Eq.-11 tree aggregation")
def _build_aggregate():
    import jax.numpy as jnp

    from repro.configs.base import FedConfig
    from repro.core import aggregation

    c = 8
    tree = _mixed_tree(c)
    cfg = FedConfig(n_clients=c, aggregator="trimmed_mean")
    w = jnp.ones((c,))
    mask = jnp.ones((c,)).at[2].set(0.0)

    def fn(u, ww, m):
        return aggregation.aggregate(u, ww, m, cfg)

    # fusion_count: one pass = the (C, ...) cohort update tree.  The CPU
    # backend inlines the pallas rank-compare kernels, whose (C, C, leaf)
    # comparison tensors put the fused baseline at ~C passes/leaf
    # (measured 64.0x at this fixture scale) — the cap is a regression
    # tripwire ~10% above: an un-fused mean path or an extra
    # comparison-tensor materialization jumps it by ~C, far past the
    # headroom, while run-to-run XLA jitter stays inside it.
    return Target(fn, (tree, w, mask), copy_mode="strict",
                  copy_threshold=min(_leaf_sizes(tree)),
                  collective_allowlist={},
                  hbm_pass_cap=70.0, hbm_payload_bytes=_leaf_bytes(tree),
                  hbm_bytes_threshold=128)


@register_entry("two_stage", doc="cohort-batched two-stage aggregation")
def _build_two_stage():
    import jax
    import jax.numpy as jnp

    from repro.configs.base import FedConfig
    from repro.core import aggregation

    g, k = 3, 8
    key = jax.random.PRNGKey(0)
    upd = {"w": jax.random.normal(key, (g, k, 57)),
           "b": jax.random.normal(jax.random.fold_in(key, 3), (g, k, 5, 3))}
    sw = jnp.ones((g, k))
    sm = jnp.ones((g, k)).at[0, 3].set(0.0)
    cfg = FedConfig(aggregator="trimmed_mean")

    def fn(u, w, m):
        return aggregation.two_stage(u, w, m, cfg)

    # two vmapped rank-compare stages (K-wide then G-wide) put the fused
    # baseline at 15.3x payload on this backend; tripwire ~10% above.
    return Target(fn, (upd, sw, sm), copy_mode="strict",
                  copy_threshold=min(_leaf_sizes(upd)),
                  collective_allowlist={},
                  hbm_pass_cap=17.0, hbm_payload_bytes=_leaf_bytes(upd),
                  hbm_bytes_threshold=128)


@register_entry("aggregate_sharded", min_devices=2,
                doc="mesh-sharded Eq.-11 aggregation (PR-3 contract)")
def _build_aggregate_sharded():
    import numpy as np

    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    from repro.configs.base import FedConfig
    from repro.core import aggregation

    c = 8
    key = jax.random.PRNGKey(0)
    tree = {"w": jax.random.normal(key, (c, 64, 8)),
            "r": jax.random.normal(jax.random.fold_in(key, 1), (c, 301)),
            "b": jax.random.normal(jax.random.fold_in(key, 2), (c, 5)),
            "h": jax.random.normal(jax.random.fold_in(key, 3),
                                   (c, 256)).astype(jnp.bfloat16)}
    cfg = FedConfig(n_clients=c, aggregator="trimmed_mean")
    mesh = Mesh(np.array(jax.devices()), ("data",))
    w = jnp.ones((c,))
    mask = jnp.ones((c,))

    def fn(u, ww, m):
        return aggregation.aggregate(u, ww, m, cfg, mesh=mesh,
                                     axes=("data",))

    # only the (C,) cosine partials + per-leaf scales (and Krum's (C,C)
    # Gram) may cross devices; the per-leaf payload bytes/chip stay far
    # below one leaf. all-gather covers re-replicating the aggregated
    # rows at the boundary exit; all-to-all would mean the shard_map
    # entry resharded the flat axis — forbidden outright.
    payload = sum(_leaf_sizes(tree)) * 4
    # partitioned module: per-chip shards cut the rank-compare tensors
    # 4-fold but the shard_map exit re-replicates the aggregated rows
    # (all-gather results are fresh buffers); measured 19.9x payload
    # under the forced-4-device CI pass, tripwire ~10% above.
    return Target(fn, (tree, w, mask), copy_mode="strict",
                  copy_threshold=min(_leaf_sizes(tree)),
                  collective_allowlist={"all-reduce": 16 * 1024,
                                        "all-gather": payload,
                                        "reduce-scatter": payload,
                                        "collective-permute": payload},
                  hbm_pass_cap=22.0, hbm_payload_bytes=_leaf_bytes(tree),
                  hbm_bytes_threshold=128)


# --------------------------------------------------------------------- #
# round engines (engine copy lint, rng discipline, donation)            #
# --------------------------------------------------------------------- #

@register_entry("fedfits.make_round",
                doc="synchronous FedFiTS round body (Algorithm 1+2)")
def _build_sync_round():
    import jax
    import jax.numpy as jnp

    from repro.configs.base import FedConfig
    from repro.configs.registry import ARCHS
    from repro.core import fedfits
    from repro.data.pipeline import build_federation
    from repro.models.model import build

    k = 6
    model = build(ARCHS["paper-mlp"])
    fed, _ = build_federation(0, kind="tabular", n=240, n_clients=k,
                              batch_size=8, n_classes=10)
    cfg = FedConfig(n_clients=k, algorithm="fedfits", local_epochs=1,
                    local_lr=0.05, avail_prob=0.7,
                    aggregator="trimmed_mean")
    r_init, r_run = jax.random.split(jax.random.PRNGKey(0))
    state = fedfits.init_state(model.init(r_init), k, cfg, r_run)
    batch = dict(fed.data_fn(1, jax.random.PRNGKey(1)))
    batch["avail"] = jnp.ones((k,), jnp.float32)
    round_fn = fedfits.make_round(model, cfg)
    return Target(round_fn, (state, batch), donate_argnums=(0,),
                  copy_mode="engine",
                  copy_threshold=max(_leaf_sizes(state.params)),
                  collective_allowlist={}, check_rng_advance=True,
                  donate_must_alias=_must_alias(
                      state, (".params", ".rng", ".clients.ef")))


@register_entry("async_engine.make_async_round",
                doc="buffered-async round body (PR-6 engine)")
def _build_async_round():
    import jax

    from repro.configs.base import FedConfig
    from repro.configs.registry import ARCHS
    from repro.core import async_engine
    from repro.data.pipeline import build_federation
    from repro.models.model import build

    m, c = 12, 4
    model = build(ARCHS["paper-mlp"])
    fed, _ = build_federation(0, kind="tabular", n=360, n_clients=m,
                              batch_size=8, n_classes=10)
    cfg = FedConfig(n_clients=c, population=m, algorithm="fedavg",
                    aggregator="trimmed_mean", async_max_retries=2,
                    staleness_decay=0.5)
    r_init, r_run = jax.random.split(jax.random.PRNGKey(0))
    state = async_engine.init_async_state(model.init(r_init), cfg, r_run)
    round_fn = async_engine.make_async_round(model, cfg, fed.data,
                                             batch_size=8)
    return Target(round_fn, (state, {}), donate_argnums=(0,),
                  copy_mode="engine",
                  copy_threshold=max(_leaf_sizes(state.params)),
                  collective_allowlist={}, check_rng_advance=True,
                  donate_must_alias=_must_alias(
                      state, (".params", ".rng", ".buf.upd")))


@register_entry("pod.make_train_step",
                doc="pod SPMD train step (robust per-client aggregation)")
def _build_pod_step():
    import jax

    from repro.configs.base import FedConfig, TrainConfig
    from repro.configs.registry import ARCHS
    from repro.core import pod
    from repro.data import synthetic
    from repro.models import transformer
    from repro.optim import optimizers

    CFG = ARCHS["tiny-lm"].replace(n_layers=2, d_model=64, n_heads=4,
                                   n_kv_heads=2, d_ff=128, vocab_size=128,
                                   head_dim=16)
    C, B, S = 4, 8, 32
    key = jax.random.PRNGKey(0)
    fed = FedConfig(n_clients=C, aggregator="trimmed_mean")
    tc = TrainConfig(global_batch=B, seq_len=S, total_steps=4,
                     warmup_steps=1)
    params = transformer.init_transformer(key, CFG)
    opt_init, _ = optimizers.make_optimizer(tc)
    state = pod.init_pod_state(params, opt_init, C, fed, key)
    toks = synthetic.make_lm_tokens(key, B, S + 1, CFG.vocab_size,
                                    n_latent=2)
    batch = {"tokens": toks[:, :-1], "targets": toks[:, 1:]}
    step = pod.make_train_step(CFG, fed, tc, robust="per_client")
    # the transformer forward legitimately concats/reshapes at single-
    # activation size (RoPE rotate-half, attention head merges), so the
    # pod threshold is whole-tree scale: only a flatten materialization
    # of the full parameter tree (the anti-pattern the robust
    # aggregation path was built to avoid) can trip it.
    return Target(step, (state, batch), donate_argnums=(0,),
                  copy_mode="engine",
                  copy_threshold=sum(_leaf_sizes(params)),
                  collective_allowlist={}, check_rng_advance=True,
                  donate_must_alias=_must_alias(
                      state, (".params", ".opt_state", ".rng")))


@register_entry("examples.async_healthcare.round",
                doc="walkthrough async round with the telemetry column "
                    "riding the donated carry")
def _build_example_round():
    import importlib.util
    from pathlib import Path

    # examples/ is not a package: load the walkthrough module from the
    # repo root so the linter audits the EXACT round body users run —
    # the telemetry counter column must not break carry donation.
    path = (Path(__file__).resolve().parents[3]
            / "examples" / "async_healthcare.py")
    spec = importlib.util.spec_from_file_location(
        "_example_async_healthcare", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    round_fn, state = mod.make_telemetry_round()
    return Target(round_fn, (state, {}), donate_argnums=(0,),
                  copy_mode="engine",
                  copy_threshold=max(_leaf_sizes(state.params)),
                  collective_allowlist={}, check_rng_advance=True,
                  donate_must_alias=_must_alias(
                      state, (".params", ".rng", ".buf.upd")))


# --------------------------------------------------------------------- #
# comm codec round-trips (rng + dtype discipline on the wire boundary)  #
# --------------------------------------------------------------------- #

def _codec_entry(name):
    import jax

    from repro.comm import codecs as comm_codecs, error_feedback
    from repro.configs.base import FedConfig

    cfg = FedConfig(n_clients=4, compress=name)
    codec = comm_codecs.make_codec(cfg)
    tree = _mixed_tree(4)
    residual = error_feedback.init(tree)

    def fn(u, r, rng):
        return error_feedback.compress(
            codec, u, r, rng=rng if codec.stochastic else None)

    return Target(fn, (tree, residual, jax.random.PRNGKey(3)),
                  copy_mode="off", collective_allowlist={},
                  copy_threshold=max(_leaf_sizes(tree)))


for _name in ("int8", "int4", "signsgd", "topk", "randk"):
    register_entry(f"comm.codec.{_name}",
                   doc=f"{_name} wire round-trip through EF")(
        lambda _n=_name: _codec_entry(_n))


# --------------------------------------------------------------------- #
# serving                                                               #
# --------------------------------------------------------------------- #

@register_entry("serve.decode_step",
                doc="autoregressive decode+sample step (launch/serve.py)")
def _build_decode_step():
    import jax
    import jax.numpy as jnp

    from repro.configs.registry import get_config
    from repro.launch.serve import make_decode_step
    from repro.models.model import build

    cfg = get_config("tiny-lm").reduced()
    model = build(cfg)
    key = jax.random.PRNGKey(0)
    params = model.init(key)
    B, P = 2, 16
    cache = model.init_cache(B, P + 8, dtype=jnp.float32)
    # prefill positions [0, P) so the decode step sees a warm cache
    prompts = jax.random.randint(key, (B, P), 0, cfg.vocab_size)
    _, cache = jax.jit(model.prefill)(params, {"tokens": prompts}, cache)
    tok = jnp.zeros((B, 1), jnp.int32)
    step = make_decode_step(model, temperature=1.0)
    return Target(step, (params, tok, cache, jnp.int32(P),
                         jax.random.PRNGKey(7)),
                  copy_mode="engine",
                  copy_threshold=max(_leaf_sizes(params)),
                  collective_allowlist={}, check_rng_advance=True)


@register_entry("serve.paged_decode_step",
                doc="continuous-batching paged decode step "
                    "(serve/engine.py: flash-decode kernel, donated "
                    "pools + slot carry)")
def _build_paged_decode_step():
    import jax
    import numpy as np

    from repro.configs.registry import get_config
    from repro.models.model import build
    from repro.serve import Request, ServeConfig, ServeEngine

    cfg = get_config("tiny-lm").reduced()
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    scfg = ServeConfig(max_slots=4, page_size=8, max_len=32,
                       prompt_pad=8, temperature=1.0, attn="pallas")
    engine = ServeEngine(cfg, scfg, params, seed=1)
    # warm two slots through the real admit path so the audited step
    # sees live page tables
    cache, st = engine.fresh_state()
    rng = np.random.RandomState(0)
    for rid in range(2):
        prompt = jax.numpy.zeros((scfg.prompt_pad,), jax.numpy.int32) \
            .at[:4].set(jax.numpy.asarray(
                rng.randint(0, cfg.vocab_size, 4), jax.numpy.int32))
        cache, st, _ = engine._admit(
            params, cache, st, prompt, jax.numpy.int32(4),
            jax.numpy.int32(8), jax.numpy.int32(rid))
    decode = engine._make_decode()
    off = len(jax.tree_util.tree_leaves(params))
    pool_alias = tuple(
        (off + i, jax.tree_util.keystr(path))
        for i, (path, _l) in enumerate(
            jax.tree_util.tree_flatten_with_path(cache)[0])
        if any(f"'{k}'" in jax.tree_util.keystr(path)
               for k in ("kp", "vp")))
    return Target(decode, (params, cache, st),
                  donate_argnums=(1, 2),
                  copy_mode="engine",
                  copy_threshold=max(_leaf_sizes(params)),
                  collective_allowlist={}, check_rng_advance=True,
                  donate_must_alias=pool_alias)


def get_entry(name: str) -> EntryPoint:
    return ENTRYPOINTS[name]
