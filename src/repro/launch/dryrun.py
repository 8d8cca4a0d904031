import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: prove the distribution config is coherent for every
(architecture x input shape x mesh) combination with .lower().compile()
on placeholder devices — no allocation, ShapeDtypeStruct inputs only.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2.5-14b \
      --shape train_4k [--multi-pod] [--json out.json]
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod]

Emits memory_analysis / cost_analysis and the three roofline terms
(EXPERIMENTS.md §Dry-run / §Roofline read from this output).
"""

import argparse
import json
import sys
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs.base import (INPUT_SHAPES, FedConfig, TrainConfig)
from repro.configs.registry import ARCHS, ASSIGNED, get_config
from repro.core import pod
from repro.launch import inputs as inputs_lib
from repro.launch import roofline as roof
from repro.launch.mesh import make_production_mesh
from repro.models import transformer
from repro.models.model import build
from repro.optim import optimizers
from repro.sharding import specs as sh

# the production mesh these dry-runs model is built of TPU v5e chips;
# the placeholder devices are host CPUs, so the kind is named, not read
V5E = "TPU v5 lite"


def _named(mesh, spec_tree):
    from jax.sharding import NamedSharding, PartitionSpec
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, PartitionSpec))


def lower_train(cfg, shape_name, mesh, variant="baseline"):
    shape = INPUT_SHAPES[shape_name]
    n_dp_groups = 1
    for ax in ("pod", "data"):
        if ax in mesh.axis_names:
            n_dp_groups *= mesh.shape[ax]
    C = min(n_dp_groups, shape.global_batch)
    fed = FedConfig(n_clients=C)
    tc = TrainConfig(global_batch=shape.global_batch, seq_len=shape.seq_len)

    params_s = jax.eval_shape(
        lambda k: transformer.init_transformer(k, cfg), jax.random.PRNGKey(0))
    opt_init, _ = optimizers.make_optimizer(tc)
    state_s = jax.eval_shape(
        lambda p: pod.init_pod_state(p, opt_init, C, fed,
                                     jax.random.PRNGKey(0)), params_s)
    batch_s = inputs_lib.train_batch_specs(cfg, shape_name)

    spec_fn = (sh.param_specs_moe_ff if variant in ("moe_ff", "zero1_moe")
               else sh.param_specs)
    state_sh = _named(mesh, spec_fn(state_s, mesh=mesh))
    batch_sh = _named(mesh, sh.batch_specs(batch_s, mesh))

    zero1 = None
    if variant == "zero1":
        compute_sh = _named(mesh, sh.param_specs_tp(params_s, mesh=mesh))
        master_sh = _named(mesh, sh.param_specs(params_s, mesh=mesh))
        zero1 = (compute_sh, master_sh)
    elif variant == "zero1_moe":
        compute_sh = _named(mesh,
                            sh.param_specs_zero1_moe(params_s, mesh=mesh))
        master_sh = _named(mesh, sh.param_specs_moe_ff(params_s, mesh=mesh))
        zero1 = (compute_sh, master_sh)
    step = pod.make_train_step(cfg, fed, tc, zero1_shardings=zero1)
    with mesh:
        lowered = jax.jit(step, in_shardings=(state_sh, batch_sh),
                          out_shardings=(state_sh, None)).lower(
                              state_s, batch_s)
    return lowered, params_s


def lower_prefill(cfg, shape_name, mesh, variant="baseline"):
    model = build(cfg)
    params_s = jax.eval_shape(
        lambda k: transformer.init_transformer(k, cfg), jax.random.PRNGKey(0))
    params_bf16 = jax.tree_util.tree_map(
        lambda l: jax.ShapeDtypeStruct(l.shape, jnp.bfloat16), params_s)
    batch_s = inputs_lib.infer_batch_specs(cfg, shape_name)
    cache_s = inputs_lib.cache_specs_struct(cfg, shape_name)

    spec_fn = sh.param_specs_tp if variant == "tp_serve" else sh.param_specs
    params_sh = _named(mesh, spec_fn(params_bf16, mesh=mesh))
    batch_sh = _named(mesh, sh.batch_specs(batch_s, mesh))
    cache_sh = _named(mesh, sh.cache_specs(cache_s, mesh))

    with mesh:
        lowered = jax.jit(
            model.prefill,
            in_shardings=(params_sh, batch_sh, cache_sh),
            out_shardings=(None, cache_sh)).lower(params_bf16, batch_s,
                                                  cache_s)
    return lowered, params_s


def lower_decode(cfg, shape_name, mesh, variant="baseline"):
    model = build(cfg)
    shape = INPUT_SHAPES[shape_name]
    params_s = jax.eval_shape(
        lambda k: transformer.init_transformer(k, cfg), jax.random.PRNGKey(0))
    params_bf16 = jax.tree_util.tree_map(
        lambda l: jax.ShapeDtypeStruct(l.shape, jnp.bfloat16), params_s)
    batch_s = inputs_lib.infer_batch_specs(cfg, shape_name, decode=True)
    cache_s = inputs_lib.cache_specs_struct(cfg, shape_name)
    pos_s = jax.ShapeDtypeStruct((), jnp.int32)

    spec_fn = sh.param_specs_tp if variant == "tp_serve" else sh.param_specs
    params_sh = _named(mesh, spec_fn(params_bf16, mesh=mesh))
    batch_sh = _named(mesh, sh.batch_specs(batch_s, mesh))
    cache_sh = _named(mesh, sh.cache_specs(cache_s, mesh))

    with mesh:
        lowered = jax.jit(
            model.decode,
            in_shardings=(params_sh, batch_sh, cache_sh, None),
            out_shardings=(None, cache_sh)).lower(params_bf16, batch_s,
                                                  cache_s, pos_s)
    return lowered, params_s


def _kind_probe_cfg(cfg, block_kind, n_layers_probe):
    """Probe variant: n_layers_probe layers of ONE block kind, unrolled.

    HloCostAnalysis counts a while-loop body ONCE regardless of trip count,
    so the scanned full model under-reports flops/bytes/collectives. The
    dry-run therefore compiles two small UNROLLED probes per distinct
    block kind (1 and 2 layers) and composes

        cost_full = base + sum_kind n_kind * delta_kind,

    where base = 2*cost(kind, 1) - cost(kind, 2) (embed/head/loss/fitness,
    identical across kinds) and delta_kind = cost(kind, 2) - cost(kind, 1).
    Per-kind probing keeps probe graphs tiny even for heterogeneous stacks
    (xLSTM's 8-layer cycle, the VLM's 5-layer cycle) where unrolling whole
    cycles made compiles intractable.
    (Residual known undercount: the sLSTM time-step scan, inherently
    sequential, ~<1% of xlstm flops — documented in EXPERIMENTS.md.)
    """
    return cfg.replace(n_layers=n_layers_probe,
                       block_pattern=(block_kind,) * n_layers_probe,
                       scan_unroll=True)


def _lower_for(cfg, shape_name, mesh, kind, variant="baseline"):
    if kind == "train":
        return lower_train(cfg, shape_name, mesh, variant)
    if kind == "prefill":
        return lower_prefill(cfg, shape_name, mesh, variant)
    return lower_decode(cfg, shape_name, mesh, variant)


def _probe_costs(cfg, shape_name, mesh, kind, variant="baseline"):
    """Composed per-chip flops/bytes/collective-bytes for the full depth,
    from two unrolled shallow probes per distinct block kind."""
    from collections import Counter

    kind_counts = Counter(cfg.layers)

    def one_probe(block_kind, n_layers_probe):
        pcfg = _kind_probe_cfg(cfg, block_kind, n_layers_probe)
        lowered, _ = _lower_for(pcfg, shape_name, mesh, kind, variant)
        compiled = lowered.compile()
        cost = compiled.cost_analysis()
        coll = roof.parse_collectives(compiled.as_text())
        return (float(cost.get("flops", 0.0)),
                float(cost.get("bytes accessed", 0.0)), coll)

    base_f = base_b = None
    base_c = None
    tot_f = tot_b = 0.0
    tot_c = {}
    for bk, n_bk in kind_counts.items():
        f1, b1, c1 = one_probe(bk, 1)
        f2, b2, c2 = one_probe(bk, 2)
        if base_f is None:
            base_f = 2 * f1 - f2
            base_b = 2 * b1 - b2
            base_c = {kk: 2 * c1[kk] - c2[kk] for kk in c1}
        tot_f += n_bk * (f2 - f1)
        tot_b += n_bk * (b2 - b1)
        for kk in c1:
            tot_c[kk] = tot_c.get(kk, 0.0) + n_bk * (c2[kk] - c1[kk])
    flops = max(base_f + tot_f, 0.0)
    byts = max(base_b + tot_b, 0.0)
    coll = {kk: max(base_c.get(kk, 0.0) + v, 0.0) for kk, v in tot_c.items()}
    return {"flops": flops, "bytes accessed": byts}, coll


def run_one(arch: str, shape_name: str, *, multi_pod=False, verbose=True,
            probe=True, variant="baseline"):
    base = get_config(arch)
    cfg = inputs_lib.shape_variant(base, shape_name)
    shape = INPUT_SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    lowered, params_s = _lower_for(cfg, shape_name, mesh, shape.kind,
                                   variant)
    compiled = lowered.compile()
    dt = time.time() - t0

    mem = compiled.memory_analysis()
    if probe:
        cost, coll = _probe_costs(cfg, shape_name, mesh, shape.kind, variant)
    else:
        cost = compiled.cost_analysis()
        coll = roof.parse_collectives(compiled.as_text())
    terms = roof.roofline(cost, coll, V5E)
    n_params = roof.count_params(params_s)
    mflops = roof.model_flops(cfg, n_params, shape, shape.kind)
    n_chips = mesh.size
    terms["model_flops_global"] = mflops
    terms["model_flops_per_chip"] = mflops / n_chips
    terms["useful_ratio"] = (mflops / n_chips) / max(terms["hlo_flops"], 1.0)
    result = {
        "arch": arch, "shape": shape_name, "variant": variant,
        "mesh": "x".join(map(str, mesh.devices.shape)),
        "kind": shape.kind,
        "n_params": n_params,
        "compile_s": round(dt, 1),
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "peak_bytes": getattr(mem, "peak_memory_in_bytes", None),
        },
        **terms,
    }
    if verbose:
        print(json.dumps(result, indent=1, default=float))
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None,
                    choices=list(INPUT_SHAPES) + [None])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--json", default=None, help="append results as jsonl")
    ap.add_argument("--variant", default="baseline",
                    choices=["baseline", "zero1", "tp_serve"])
    ap.add_argument("--no-probe", action="store_true",
                    help="skip cost probes (lowering proof only; the\n"
                    "multi-pod pass does not feed the roofline table)")
    args = ap.parse_args()

    combos = []
    if args.all:
        combos = [(a, s) for a in ASSIGNED for s in INPUT_SHAPES]
    else:
        archs = [args.arch] if args.arch else ASSIGNED
        shapes = [args.shape] if args.shape else list(INPUT_SHAPES)
        combos = [(a, s) for a in archs for s in shapes]

    ok, failed = 0, []
    for arch, shape in combos:
        tag = f"{arch} x {shape} ({'2x16x16' if args.multi_pod else '16x16'})"
        print(f"==== {tag} ====", flush=True)
        try:
            res = run_one(arch, shape, multi_pod=args.multi_pod,
                          variant=args.variant, probe=not args.no_probe)
            ok += 1
            if args.json:
                with open(args.json, "a") as f:
                    f.write(json.dumps(res, default=float) + "\n")
        except Exception:
            traceback.print_exc()
            failed.append(tag)
    print(f"\nDRY-RUN: {ok}/{len(combos)} combinations compiled")
    if failed:
        print("FAILED:", *failed, sep="\n  ")
        sys.exit(1)


if __name__ == "__main__":
    main()
