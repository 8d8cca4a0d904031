#!/usr/bin/env python3
"""One run of one benchmark cell on the chips of this machine.

  python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Reads the cell from ``BENCHMARK.json``, its configuration file and its
traffic file (``bench/traffic/<traffic>.json``), and hands them to the
engine adapter the traffic file names (``bench/engines/<engine>.py``).  The
adapter builds the program from the seed, warms up, measures for
``--seconds`` and checks what the timed path produced against the plain
reference.  The last line on standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics read from the device trace by
``bench/metrics/<metric>.py``), ``device``, with ``--trace 1`` ``breakdown``,
and last ``checks``: each number compared with its limit, which also close
standard error.  An adapter's ``JAX_ENV`` is set before JAX is imported.

Exits 2 when the checkout holds no program, 3 when JAX finds no TPU or
fewer chips than the cell asks for; neither prints a result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
TRACE_DIR = ROOT / ".bench_trace"


def _fail(code, msg):
    print(f"bench: {msg}", file=sys.stderr)
    return code


def _cell(bench, name):
    for wl in bench["workloads"]:
        if wl["name"] == name:
            return wl
    raise SystemExit(_fail(2, f"no workload {name!r} in BENCHMARK.json"))


def _metrics_for(entries, wl):
    return [m for m in entries if wl["name"] in m.get("workloads",
                                                      [wl["name"]])]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    wl = _cell(bench, args.workload)
    cfg_entry = next(c for c in bench["configs"] if c["name"] == wl["config"])
    config = json.loads((ROOT / cfg_entry["file"]).read_text())
    traffic = json.loads(
        (ROOT / "bench" / "traffic" / f"{wl['traffic']}.json").read_text())
    if not (ROOT / "src" / "repro").is_dir():
        return _fail(2, f"no program under {ROOT / 'src'}")
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(ROOT))

    from bench import common

    engine = common.load_module(ROOT / "bench" / "engines"
                                / f"{traffic['engine']}.py")
    for k, v in getattr(engine, "JAX_ENV", {}).items():
        os.environ.setdefault(k, v)

    import jax

    common.enable_compile_cache(ROOT)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < wl["chips"]:
        return _fail(3, f"the cell needs {wl['chips']} TPU chip(s); JAX "
                        f"found {len(devices)} {devices[0].platform} "
                        "device(s)")

    from bench import peaks as peaks_mod, trace as trace_mod

    pk = peaks_mod.peaks(devices[0].device_kind)
    trace_dir = None
    if args.trace:
        trace_dir = TRACE_DIR / wl["name"]
        shutil.rmtree(trace_dir, ignore_errors=True)
    window = common.Window(trace_dir)
    # a traced run measures only the traced window, the first
    # ``trace_seconds`` of the window, so that reading the trace stays short
    seconds = min(args.seconds, traffic["trace_seconds"]) if args.trace \
        else args.seconds
    ctx = common.Context(workload=wl, config=config, traffic=traffic,
                         seed=args.seed, seconds=seconds, window=window,
                         root=ROOT)
    out = engine.run(ctx)
    setup_s = window.t0 - T_START

    metrics, breakdown = {}, None
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": out["memory_peak_bytes"]}
    if not args.trace:
        for m in _metrics_for(bench["end_to_end"], wl):
            value = setup_s if m["name"] == "setup_s" else \
                out["e2e"][m["name"]]
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        tr = trace_mod.Trace.load(trace_dir)
        work = common.load_module(ROOT / "bench" / "work"
                                  / f"{wl['config']}.py")
        inp = trace_mod.LayerInput(trace=tr, window=out["window"], work=work,
                                   peaks=pk, config=config, traffic=traffic)
        for m in _metrics_for(bench["per_layer"], wl):
            reader = common.load_module(ROOT / "bench" / "metrics"
                                        / f"{m['name']}.py")
            value = reader.read(inp)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device["busy_s"] = tr.busy_s
        device["window_s"] = tr.window_s
        breakdown = {"device_ops": tr.top_ops(10),
                     "idle_gaps": tr.idle_by_host(10)}
        shutil.rmtree(trace_dir, ignore_errors=True)

    checks = {name: {"value": value, "limit": limit}
              for name, value, limit in out["checks"]}
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    result = {"correct": correct, "attempted": out["attempted"],
              "failed": out["failed"], "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
