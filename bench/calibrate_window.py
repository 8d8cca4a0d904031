#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, for a serving cell
of a model with window and full attention layers (``serve_window`` cells).

  python bench/calibrate_window.py --workload <cell> --seeds <n,n,...> \\
      [--controls <k>] [--seconds <s>]

``bench/calibrate.py``'s serving readings, with two more faults for the
first ``--controls`` seeds: ``no_window`` (the reference with every earlier
key seen on the window layers put in the program's place) and ``no_yarn``
(the same with default RoPE on the full layers), beside the control (fp8
products) and ``altered_token``.  One JSON line per seed.  The benchmark's
own runs never run the control or the faults.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
FAULTS = ("no_window", "no_yarn")


def readings(ctx, eng, control):
    import jax

    from bench import common, gen

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
    if not control:
        return out
    out["control"] = eng.gap_numbers(
        eng.reference_gaps(ctx, rec, drawn, rids, quant="fp8"))
    ref = eng.reference(ctx)
    key = jax.random.PRNGKey(gen.key_seed(ctx.seed))
    seqs = [(list(drawn[r][0]), [t for t, _ in rec.tokens[r]]) for r in rids]
    out["faults"] = {f: eng.gap_numbers(ref.served_gaps(
        key, ctx.config["config"], seqs, tr["max_len"], fault=f))
        for f in FAULTS}
    for r in rids:                      # plant: alter one served token
        toks = rec.tokens[r]
        j = len(toks) // 2
        toks[j] = ((toks[j][0] + 1) % ctx.config["config"]["vocab_size"],
                   toks[j][1])
    out["faults"]["altered_token"] = eng.gap_numbers(
        eng.reference_gaps(ctx, rec, drawn, rids))
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
    common.enable_compile_cache(ROOT)
    seconds = args.seconds if args.seconds is not None \
        else bench["run_seconds"]
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        ctx = common.Context(workload=wl, config=config, traffic=traffic,
                             seed=seed, seconds=seconds, window=None,
                             root=ROOT)
        out = readings(ctx, eng, i < args.controls)
        out.update(seed=seed, seconds=time.perf_counter() - t0)
        print(json.dumps(out), flush=True)
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
