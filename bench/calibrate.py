#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, for one cell.

  python bench/calibrate.py --workload <cell> --seeds <n,n,...> \\
      [--controls <k>] [--seconds <s>]

For every seed, in this one process: the program's numbers as a run of the
cell computes them (the same set-up, compiled programs and comparison with
the plain reference; federated cells also report the loss gaps of rounds 2
and 3, which ``correct`` does not compare).  For the first ``--controls`` seeds also the control's (the
reference put in the program's place one precision below the configuration's:
the whole round in bfloat16 for the federated cells' float32, fp8 (e4m3)
products for the serving cells' bfloat16) and each planted fault's.  One JSON line per seed.  The benchmark's own
runs never run the control or the faults.

Federated cells: faults ``half_batch`` (each client trains on half its
batch, in the reference put in the program's place) and ``unchanged``
(a round that returns its state unchanged: every change reads 0, so each
leaf's gap reads 1 by the measure, no run needed).  Serving cells: fault
``altered_token`` (one served token of each sampled request replaced by the
next id, where the program emits it).
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def _fl(ctx, eng, control):
    import jax.numpy as jnp

    from bench import common

    ctx.seconds, ctx.window = 0.0, common.Window(None)
    hook, fed, key = eng.program_rounds(ctx)
    prog = eng.program_readings(hook)
    del hook
    gc.collect()
    ref = eng.reference_readings(ctx, fed, key)
    out = {"program": eng.compare(prog, ref),
           "later": eng.later_losses(prog, ref)}
    if control:
        ctl = eng.reference_readings(ctx, fed, key, dtype=jnp.bfloat16)
        half = eng.reference_readings(ctx, fed, key, fault="half_batch")
        k = len(ref[0]["team"])
        still = [{"test_loss": _init_loss(ctx, fed, key),
                  "dist": [0.0] * len(ref[0]["dist"]), "team": [1] * k}
                 ] * eng.CHECK_ROUNDS
        out["control"] = eng.compare(ctl, ref)
        out["control_later"] = eng.later_losses(ctl, ref)
        out["faults"] = {"half_batch": eng.compare(half, ref),
                         "half_batch_later": eng.later_losses(half, ref),
                         "unchanged": eng.compare(still, ref)}
    return out


def _init_loss(ctx, fed, key):
    """The server test loss of the initial parameters (what a round that
    returns its state unchanged reports)."""
    import jax

    from bench import common

    ref = common.load_module(ROOT / "bench" / "reference"
                             / f"{ctx.workload['config']}.py")
    with jax.default_matmul_precision(ctx.config["config"]["matmul_precision"]):
        p0 = ref.init_params(jax.random.split(key)[0],
                             ctx.config["config"])
        return float(ref.loss_acc(p0, fed.server_test["x"],
                                  fed.server_test["y"])[0])


def _serve(ctx, eng, control):
    from bench import common

    tr = ctx.traffic
    cfg, params, engine, drawn, reqs = eng.build_engine(ctx)
    rec = eng.serve(ctx, engine, drawn, reqs, warmup=tr["warmup_steps"],
                    seconds=ctx.seconds, window=common.Window(None))
    del engine, params, reqs
    gc.collect()
    rids = eng.sample_finished(rec, drawn, tr["check_requests"], ctx.seed)
    gaps = eng.reference_gaps(ctx, rec, drawn, rids)
    out = {"program": eng.gap_numbers(gaps),
           "served_tokens": int(sum(len(g) for g in gaps)),
           "requests": len(rids)}
    if control:
        ctl = eng.reference_gaps(ctx, rec, drawn, rids, quant="fp8")
        out["control"] = eng.gap_numbers(ctl)
        for r in rids:                      # plant: alter one served token
            toks = rec.tokens[r]
            j = len(toks) // 2
            toks[j] = ((toks[j][0] + 1) % ctx.config["config"]["vocab_size"],
                       toks[j][1])
        alt = eng.reference_gaps(ctx, rec, drawn, rids)
        out["faults"] = {"altered_token": eng.gap_numbers(alt)}
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=None)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(ROOT))

    from bench import common

    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    wl = next(w for w in bench["workloads"] if w["name"] == args.workload)
    cfg_entry = next(c for c in bench["configs"] if c["name"] == wl["config"])
    config = json.loads((ROOT / cfg_entry["file"]).read_text())
    traffic = json.loads(
        (ROOT / "bench" / "traffic" / f"{wl['traffic']}.json").read_text())
    eng = common.load_module(ROOT / "bench" / "engines"
                             / f"{traffic['engine']}.py")
    for k, v in getattr(eng, "JAX_ENV", {}).items():
        os.environ.setdefault(k, v)
    common.enable_compile_cache(ROOT)
    seconds = args.seconds if args.seconds is not None \
        else bench["run_seconds"]
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        ctx = common.Context(workload=wl, config=config, traffic=traffic,
                             seed=seed, seconds=seconds, window=None,
                             root=ROOT)
        control = i < args.controls
        if traffic["engine"] == "fl_sync":
            out = _fl(ctx, eng, control)
        else:
            out = _serve(ctx, eng, control)
        out.update(seed=seed, seconds=time.perf_counter() - t0)
        print(json.dumps(out), flush=True)
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
