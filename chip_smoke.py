#!/usr/bin/env python3
"""Run both engines once on one TPU chip, through their normal entry points.

  python chip_smoke.py                 # phases fl_round and serve (1 chip)
  python chip_smoke.py --four-chips    # the sharded int8 aggregation on a
                                       # 4-chip host, and its comparison

Phases:
  fl_round    ``scenarios.run_scenario`` on the Table V images federation
              (paper-cnn, 10 clients, 2 classes), cell
              gate_aware_int8_dropout with FedFiTS election and slots: the
              fused trimmed-mean pipeline with int8 fused dequant, an
              attacker and dropout, on the scan driver.  Then one round
              against the same round through the XLA oracle.
  serve       ``ServeEngine`` (continuous, paged flash-decode) on
              granite-moe-1b-a400m at its published widths, with f32 and
              with int8 KV pools, and the paged kernel against its
              reference at the engine's pool shapes.
  four_chips  trimmed mean from int8 codes over a 4-device mesh on the
              param axis, for 16 clients' updates shaped like
              granite-moe-1b-a400m (about 22 GB of codes), against the
              one-chip aggregation over the leaves that fit one chip.

Each phase prints one JSON line (compile and run seconds, checks,
``peak_bytes_in_use`` per device); the last line is
``{"ok": true, "device": {...}}`` as JAX reports the device.  Without a
TPU the script exits 2 before any phase; a failed phase or check exits 1
and prints no result line.  Everything runs in this one process.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
import traceback
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"

# Bounds.  FL_ROUND_ATOL: the CPU parity tests pin the fused pipeline
# against the XLA oracle at 1e-5 (test_robust_pipeline.py::
# test_two_stage_router_uses_fused_path, test_comm.py::
# test_sharded_fused_dequant_matches_oracle).  SERVE_KERNEL_ATOL: q and
# K/V are bf16-valued as the model writes them, so QK^T is exact in f32;
# the PV product may round the f32 probabilities to bf16 on the MXU
# (relative 2^-9), which bounds the output error near 2^-9 * max|v|.
# SHARDED_ATOL: the sharded and one-chip aggregations combine the same
# per-coordinate values; only the psum order of the (C,) cosine partials
# differs, pinned at 1e-5 like the repo's sharded tests.
FL_ROUND_ATOL = 1e-5
SERVE_KERNEL_ATOL = 1e-2
SHARDED_ATOL = 1e-5


def _emit(obj):
    print(json.dumps(obj), flush=True)


def _peak_bytes(devices):
    return [(d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in devices]


def _check(checks, name, value, ok):
    checks[name] = {"value": value, "ok": bool(ok)}


def _max_abs_diff(a, b):
    import jax
    import jax.numpy as jnp
    return max(float(jnp.max(jnp.abs(x.astype(jnp.float32)
                                     - y.astype(jnp.float32))))
               for x, y in zip(jax.tree_util.tree_leaves(a),
                               jax.tree_util.tree_leaves(b)))


# ---------------------------------------------------------------- fl_round
def phase_fl_round(seed, *, rounds=6):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import fedfits
    from repro.scenarios import registry
    from repro.scenarios.engine import build_cell, run_scenario

    sc = registry.get("gate_aware_int8_dropout").replace(algorithm="fedfits")
    # the Table V federation (benchmarks/bench_table5_xray.py)
    fed = dict(n_clients=10, seed=seed, kind="images", n=2000, n_classes=2,
               sep=0.6, dirichlet_alpha=1.0)
    checks = {}
    t0 = time.perf_counter()
    summary, hist = run_scenario(sc, n_rounds=rounds, driver="scan", **fed)
    scenario_s = time.perf_counter() - t0
    values = [np.asarray(v, np.float64) for h in hist for v in h.values()
              if np.issubdtype(np.asarray(v).dtype, np.number)]
    _check(checks, "rounds", len(hist), len(hist) == rounds)
    _check(checks, "metrics_finite", len(values),
           all(np.isfinite(v).all() for v in values))

    # one round, fused kernels vs the XLA oracle, from the same state
    cell = build_cell(sc, **fed)
    cfg, k = cell.fed_cfg, cell.fed_cfg.n_clients
    rng = jax.random.PRNGKey(seed + 1)          # run_scenario's round rng
    r_init, r_run = jax.random.split(rng)
    att = cell.update_attack \
        if getattr(cell.update_attack, "stateful", False) else None
    state = fedfits.init_state(cell.model.init(r_init), k, cfg, r_run,
                               attacker=att)
    batch = dict(cell.federation.data_fn(1, jax.random.fold_in(rng, 1)))
    if cfg.avail_prob < 1.0:
        batch["avail"] = jnp.ones((k,), jnp.float32)

    def round_jit(c):
        return jax.jit(fedfits.make_round(
            cell.model, c, data_attack=cell.data_attack,
            update_attack=cell.update_attack, malicious=cell.malicious,
            faults=sc.faults))

    t0 = time.perf_counter()
    compiled = round_jit(cfg).lower(state, batch).compile()
    compile_s = time.perf_counter() - t0
    kernel = "tpu_custom_call" in compiled.as_text()
    _check(checks, "tpu_custom_call", kernel, kernel)
    t0 = time.perf_counter()
    fused, _ = jax.block_until_ready(compiled(state, batch))
    run_s = time.perf_counter() - t0
    oracle_cfg = dataclasses.replace(cfg, fused_agg=False,
                                     fused_dequant=False)
    oracle, _ = round_jit(oracle_cfg)(state, batch)
    diff = _max_abs_diff(fused.params, oracle.params)
    _check(checks, "round_vs_xla_oracle_max_abs", diff,
           diff <= FL_ROUND_ATOL)
    return {"scenario": sc.name, "algorithm": sc.algorithm,
            "scenario_rounds": rounds,
            "scenario_wall_s_incl_compile": scenario_s,
            "final_acc": summary["final_acc"],
            "round_compile_s": compile_s, "round_run_s": run_s,
            "bound": FL_ROUND_ATOL, "checks": checks}


# ------------------------------------------------------------------- serve
def _kernel_vs_ref(seed, scfg, cfg):
    """Max |paged_flash_decode - paged_decode_ref| at the engine's pool
    shapes, f32 and int8 pools, with bf16-valued inputs as the model
    writes them and the reference at full f32 matmul precision."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.paged_decode import paged_flash_decode
    from repro.kernels.paged_decode_ref import paged_decode_ref
    from repro.models.attention import _paged_quant

    s, page, maxp, n = (scfg.max_slots, scfg.page_size, scfg.pages_per_slot,
                        scfg.total_pages)
    hq, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)

    def bf16_normal(key, shape):
        return jax.random.normal(key, shape).astype(jnp.bfloat16).astype(
            jnp.float32)

    q = bf16_normal(ks[0], (s, hq, dh))
    kp = bf16_normal(ks[1], (n, hkv, page, dh))
    vp = bf16_normal(ks[2], (n, hkv, page, dh))
    table = jax.random.permutation(ks[3], n)[:s * maxp].reshape(s, maxp)
    lengths = jax.random.randint(ks[4], (s,), 1, maxp * page + 1)
    lengths = lengths.at[0].set(maxp * page).at[1].set(page + 1).at[2].set(0)
    table, lengths = table.astype(jnp.int32), lengths.astype(jnp.int32)

    def quant(pool):
        codes, scale = _paged_quant(pool)
        return codes, scale[:, :, None, :]

    kq, ksc = quant(kp)
    vq, vsc = quant(vp)
    out = {}
    with jax.default_matmul_precision("highest"):
        ref32 = jax.jit(paged_decode_ref)(q, kp, vp, table, lengths)
        ref8 = jax.jit(lambda *a: paged_decode_ref(
            *a[:5], k_scale=a[5], v_scale=a[6]))(
                q, kq, vq, table, lengths, ksc, vsc)
    got32 = jax.jit(paged_flash_decode)(q, kp, vp, table, lengths)
    got8 = jax.jit(lambda *a: paged_flash_decode(
        *a[:5], k_scale=a[5], v_scale=a[6]))(q, kq, vq, table, lengths,
                                             ksc, vsc)
    out["f32"] = float(jnp.max(jnp.abs(got32 - ref32)))
    out["int8"] = float(jnp.max(jnp.abs(got8 - ref8)))
    return out


def phase_serve(seed, *, arch="granite-moe-1b-a400m", cfg=None,
                n_requests=16, prompt_len=128, gen_min=16, gen_max=64,
                max_slots=8, page_size=16):
    import jax

    from repro.configs.registry import get_config
    from repro.launch.serve import draw_requests
    from repro.models.model import build
    from repro.serve import ServeConfig, ServeEngine

    cfg = cfg or get_config(arch)
    t0 = time.perf_counter()
    params = jax.block_until_ready(build(cfg).init(jax.random.PRNGKey(seed)))
    init_s = time.perf_counter() - t0
    init_peak = _peak_bytes(jax.devices()[:1])[0]
    n_params = sum(int(l.size) for l in jax.tree_util.tree_leaves(params))
    reqs = draw_requests(n_requests, prompt_len, gen_min, gen_max,
                         cfg.vocab_size, seed=seed)
    checks, runs = {}, {}
    for kv in ("f32", "int8"):
        scfg = ServeConfig(max_slots=max_slots, page_size=page_size,
                           max_len=prompt_len + gen_max,
                           prompt_pad=prompt_len, kv_int8=kv == "int8",
                           attn="pallas")
        engine = ServeEngine(cfg, scfg, params, seed=seed)
        t0 = time.perf_counter()
        engine.run(reqs[:1])                     # compiles admit + decode
        warm_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        results, stats = engine.run(reqs)
        run_s = time.perf_counter() - t0
        short = [r.req_id for r in reqs
                 if len(results[r.req_id]) != r.max_new]
        _check(checks, f"{kv}/exact_max_new", len(reqs) - len(short),
               not short)
        cache, st = jax.eval_shape(engine.fresh_state)
        text = engine._decode.lower(params, cache, st).compile().as_text()
        kernel = "tpu_custom_call" in text
        _check(checks, f"{kv}/decode_tpu_custom_call", kernel, kernel)
        runs[kv] = {"compile_and_first_request_s": warm_s, "run_s": run_s,
                    "steps": stats["steps"], "tokens": stats["tokens"],
                    "tokens_per_s": stats["tokens_per_s"]}
    diffs = _kernel_vs_ref(seed, scfg, cfg)
    for kv, d in diffs.items():
        _check(checks, f"{kv}/kernel_vs_ref_max_abs", d,
               d <= SERVE_KERNEL_ATOL)
    return {"arch": cfg.name, "n_params": n_params, "init_s": init_s,
            "peak_bytes_after_init": init_peak, "requests": n_requests, "prompt_len": prompt_len,
            "max_slots": max_slots, "page_size": page_size, "runs": runs,
            "bound": SERVE_KERNEL_ATOL, "checks": checks}


# -------------------------------------------------------------- four_chips
def _draw_quant_leaf(key, c, n, qblk, sharding):
    """int8 codes in [-127, 127] and positive per-block scales for a
    (c, n) update leaf, drawn on the devices in their sharded layout
    (one byte per code: no wider intermediate)."""
    import jax
    import jax.numpy as jnp

    from repro.comm.codecs import QuantLeaf

    def draw(key):
        kq, ks = jax.random.split(key)
        bits = jax.random.bits(kq, (c, n), jnp.uint8)
        q = jnp.maximum(jax.lax.bitcast_convert_type(bits, jnp.int8), -127)
        s = jax.random.uniform(ks, (c, n // qblk), jnp.float32, 0.5e-3,
                               1.5e-3)
        return QuantLeaf(q, s)

    return jax.jit(draw, out_shardings=QuantLeaf(sharding, sharding))(key)


def phase_four_chips(seed, *, arch="granite-moe-1b-a400m", cfg=None,
                     clients=16, subset_code_bytes=2 ** 30):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from jax.sharding import SingleDeviceSharding

    from repro.comm.kernels import comm_codecs as dq
    from repro.configs.base import FedConfig
    from repro.configs.registry import get_config
    from repro.models.model import build

    devices = jax.devices()[:4]
    if len(devices) < 4:
        raise RuntimeError(f"--four-chips needs 4 devices, found "
                           f"{len(jax.devices())}")
    mesh = Mesh(np.array(devices), ("model",))
    cfg = cfg or get_config(arch)
    fed = FedConfig(n_clients=clients, aggregator="trimmed_mean",
                    compress="int8")
    qblk = fed.compress_qblk
    params = jax.eval_shape(build(cfg).init, jax.random.PRNGKey(0))
    leaves, treedef = jax.tree_util.tree_flatten(params)
    sizes = [int(l.size) for l in leaves]
    bad = [n for n in sizes if n % (len(devices) * qblk)]
    if bad:
        raise ValueError(f"leaf sizes {bad} do not shard over "
                         f"{len(devices)} x {qblk}")
    sharded = NamedSharding(mesh, P(None, "model"))
    like = [jax.ShapeDtypeStruct((clients,) + l.shape, jnp.float32)
            for l in leaves]
    key = jax.random.PRNGKey(seed)
    t0 = time.perf_counter()
    enc = [_draw_quant_leaf(jax.random.fold_in(key, i), clients, n, qblk,
                            sharded) for i, n in enumerate(sizes)]
    jax.block_until_ready(enc)
    draw_s = time.perf_counter() - t0
    weights = jnp.ones((clients,), jnp.float32)
    mask = jnp.ones((clients,), jnp.float32)

    agg = jax.jit(lambda e, w, m: dq.fused_dequant_aggregate_sharded(
        e, w, m, fed, mesh, like=like, axes=("model",)))
    t0 = time.perf_counter()
    compiled = agg.lower(enc, weights, mask).compile()
    compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    out = jax.block_until_ready(compiled(enc, weights, mask))
    run_s = time.perf_counter() - t0
    checks = {}
    kernel = "tpu_custom_call" in compiled.as_text()
    _check(checks, "tpu_custom_call", kernel, kernel)
    finite = all(bool(jnp.isfinite(o).all()) for o in out)
    _check(checks, "outputs_finite", len(out), finite)

    # the one-chip comparison: the smallest leaves whose codes fit
    order = sorted(range(len(sizes)), key=sizes.__getitem__)
    subset, total = [], 0
    for i in order:
        if total + clients * sizes[i] > subset_code_bytes:
            break
        subset.append(i)
        total += clients * sizes[i]
    out = {i: out[i] for i in subset}            # free the rest
    one = SingleDeviceSharding(devices[0])
    enc_one = [jax.device_put(enc[i], one) for i in subset]
    single = jax.jit(lambda e, w, m: dq.fused_dequant_aggregate_tree(
        e, w, m, fed, like=[like[i] for i in subset]))
    out_one = single(enc_one, jax.device_put(weights, one),
                     jax.device_put(mask, one))
    names = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(params)[0]]
    per_leaf = {names[i]: float(jnp.max(jnp.abs(
        jax.device_put(out[i], one) - o))) for i, o in zip(subset, out_one)}
    worst = max(per_leaf.values())
    _check(checks, "sharded_vs_one_chip_max_abs", worst,
           worst <= SHARDED_ATOL)
    return {"arch": cfg.name, "clients": clients,
            "params": sum(sizes), "code_bytes": clients * sum(sizes),
            "mesh": {"model": len(devices)}, "draw_s": draw_s,
            "compile_s": compile_s, "run_s": run_s,
            "subset_leaves": len(subset), "subset_code_bytes": total,
            "per_leaf_max_abs": per_leaf, "bound": SHARDED_ATOL,
            "checks": checks}


# -------------------------------------------------------------------- main
def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded aggregation phase on a "
                         "4-chip host, and its one-chip comparison")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: no repro package under {SRC}; run from a "
              "checkout", file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (default device "
              f"{devices[0].platform}); refusing a CPU run",
              file=sys.stderr)
        return 2
    if args.four_chips:
        phases = [("four_chips", phase_four_chips)]
    else:
        phases = [("fl_round", phase_fl_round), ("serve", phase_serve)]
    ok = True
    for name, fn in phases:
        t0 = time.perf_counter()
        try:
            row = fn(args.seed)
            row_ok = all(c["ok"] for c in row["checks"].values())
        except Exception as e:  # report, then fail the script
            traceback.print_exc()
            row, row_ok = {"error": f"{type(e).__name__}: {e}"}, False
        ok &= row_ok
        _emit({"phase": name, "ok": row_ok,
               "wall_s": time.perf_counter() - t0, **row,
               "peak_bytes_in_use": _peak_bytes(devices)})
    if not ok:
        print("chip_smoke: a phase failed", file=sys.stderr)
        return 1
    d = devices[0]
    _emit({"ok": True, "device": {"platform": d.platform,
                                  "kind": d.device_kind,
                                  "count": len(devices)}})
    return 0


if __name__ == "__main__":
    sys.exit(main())
