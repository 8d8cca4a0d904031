from repro.launch import dryrun  # noqa: F401  (sets XLA_FLAGS first)

"""Perf-iteration driver (§Perf): measures roofline terms for optimisation
variants of the three hillclimbed (arch x shape) pairs, probe-only (the
full lowering proof for each accepted variant is run separately).

  PYTHONPATH=src python -m repro.launch.perf --pair qwen --variant zero1
"""

import argparse
import json

from repro.configs.base import INPUT_SHAPES
from repro.configs.registry import get_config
from repro.launch import inputs as inputs_lib
from repro.launch import roofline as roof
from repro.launch.dryrun import V5E, _probe_costs
from repro.launch.mesh import make_production_mesh

PAIRS = {
    "qwen": ("qwen2.5-14b", "train_4k"),
    "dbrx": ("dbrx-132b", "train_4k"),
    "hymba": ("hymba-1.5b", "train_4k"),
}

# variant name -> (cfg override dict, lowering variant)
VARIANTS = {
    "baseline": ({}, "baseline"),
    "zero1": ({}, "zero1"),
    "moe_ff": ({}, "moe_ff"),
    "moe_ff_cap1": ({"capacity_factor": 1.0}, "moe_ff"),
    "zero1_moe": ({}, "zero1_moe"),
    "zero1_cap1": ({"capacity_factor": 1.0}, "zero1"),
    "noremat": ({"remat": False}, "baseline"),
    "zero1_noremat": ({"remat": False}, "zero1"),
    "bf16scan": ({"ssm_scan_dtype": "bfloat16"}, "baseline"),
    "zero1_bf16scan": ({"ssm_scan_dtype": "bfloat16"}, "zero1"),
    "zero1_bf16scan_noremat": (
        {"ssm_scan_dtype": "bfloat16", "remat": False}, "zero1"),
    "chunk512": ({"scan_chunk": 512}, "baseline"),
}


def measure(pair, variant_name, json_path=None):
    arch, shape_name = PAIRS[pair]
    overrides, lower_variant = VARIANTS[variant_name]
    cfg = inputs_lib.shape_variant(get_config(arch), shape_name)
    if overrides:
        cfg = cfg.replace(**overrides)
    mesh = make_production_mesh()
    shape = INPUT_SHAPES[shape_name]
    cost, coll = _probe_costs(cfg, shape_name, mesh, shape.kind,
                              lower_variant)
    terms = roof.roofline(cost, coll, V5E)
    res = {"pair": pair, "arch": arch, "shape": shape_name,
           "variant": variant_name, **terms}
    print(json.dumps(res, indent=1, default=float))
    if json_path:
        with open(json_path, "a") as f:
            f.write(json.dumps(res, default=float) + "\n")
    return res


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--pair", required=True, choices=list(PAIRS))
    ap.add_argument("--variant", required=True, choices=list(VARIANTS))
    ap.add_argument("--json", default="results/perf_iters.jsonl")
    args = ap.parse_args()
    measure(args.pair, args.variant, args.json)


if __name__ == "__main__":
    main()
