"""Kernel micro-benchmarks: wall time per call (CPU interpret / XLA-ref
execution — TPU numbers come from the dry-run roofline) + analytic kernel
roofline (FLOPs, bytes, arithmetic intensity per VMEM tile).

``robust_pipeline`` compares the fused two-pass Pallas Eq.-11 engine
(kernels/robust_pipeline.py) against the multi-pass XLA reference
(aggregation.aggregate_ref) and accounts HBM passes analytically.
``robust_pipeline/leafwise`` times the segment-table leaf-streaming
engine on a multi-leaf tree, and ``robust_pipeline/sharded`` the
shard_map'd path (``aggregation.aggregate(..., mesh=)``) against the
replicated one on however many devices exist (the CI multi-device job
forces 4 host devices), recording the parity gap vs the XLA oracle.
``comm/*`` records the compressed-transport subsystem (repro/comm):
per-codec encode+decode wall and measured bytes-on-wire per round vs the
dense uplink, and the pipeline's int8 loader
(``aggregation.aggregate(..., enc=)``) vs its dense loader (agg-byte
reduction ~4x at qblk=128).
``population_select/*`` records the O(M) Gumbel-top-d cohort-selection
engines (kernels/population_select.py) against the dense argsort
baseline at M up to 1e6 registered clients.
Results are also dumped to BENCH_kernels.json (the perf trajectory
artifact CI uploads every run).
"""
from __future__ import annotations

import functools
import os
import time

import jax
import jax.numpy as jnp

from benchmarks import common
from repro.configs.base import FedConfig
from repro.core import aggregation
from repro.kernels import population_select
from repro.launch.roofline import peaks
from repro.kernels.flash_attention_ops import flash_attention
from repro.kernels.robust_pipeline import fused_aggregate_tree

BENCH_JSON = os.environ.get("BENCH_KERNELS_JSON", "BENCH_kernels.json")
# the modeled t_*_us terms are TPU v5e roofline bounds, not measurements
V5E = peaks("TPU v5 lite")


def _time(fn, *args, reps=5, warmup=1, **kw):
    """Clean warmup + timed-reps helper: runs ``warmup`` untimed calls
    (compile + cache fill), then takes the BEST of ``reps`` individually
    timed calls (min is robust to scheduler noise on shared machines).
    jax.block_until_ready handles any pytree/tuple result."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args, **kw))
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args, **kw))
        best = min(best, time.perf_counter() - t0)
    return best


def flash_roofline(B, S, Hq, dh, window, blk=128):
    """Analytic per-chip roofline for the flash kernel."""
    kv_touched = min(window or S, S)
    flops = 4.0 * B * Hq * S * kv_touched * dh         # qk^T + pv
    byts = 2.0 * B * S * Hq * dh * 2 + 2.0 * B * kv_touched * Hq * dh * 2
    return {
        "flops": flops, "bytes": byts,
        "intensity": flops / byts,
        "t_compute_us": 1e6 * flops / V5E["flops_bf16"],
        "t_memory_us": 1e6 * byts / V5E["hbm_bw"],
        "vmem_tile_kb": (3 * blk * dh * 2 + blk * dh * 4) / 1024,
    }


def robust_pipeline_roofline(C, N, aggregator):
    """HBM-pass accounting for the Eq.-11 pipeline over the (C, N) f32
    update matrix (one pass = C*N*4 bytes moved).

    Reference (aggregation.aggregate_ref), all sort-based:
      median reference   sort read + sorted write      2 passes
      cosine gate        read                          1 pass
      aggregator         sort read + write + reduce    3 passes
                         (fedavg: 1 read; krum: gram read + mean read = 2)
    Fused (kernels/robust_pipeline.py), streaming:
      pass 1  read (median ref + cosine partials)      1 pass
      pass 2  read (gated combine)                     1 pass
      krum    +1 blocked pairwise-distance read        1 pass

    The leaf-streaming wrappers hit this kernel-contract roofline
    end-to-end (a reshape view per leaf, no copy).
    """
    ref = {"fedavg": 4.0, "median": 6.0, "trimmed_mean": 6.0, "krum": 5.0}
    fused = {"fedavg": 2.0, "median": 2.0, "trimmed_mean": 2.0, "krum": 3.0}
    bytes_per_pass = 4.0 * C * N
    return {
        "hbm_passes_ref": ref[aggregator],
        "hbm_passes_fused": fused[aggregator],
        "hbm_pass_ratio": ref[aggregator] / fused[aggregator],
        "bytes_ref": ref[aggregator] * bytes_per_pass,
        "bytes_fused": fused[aggregator] * bytes_per_pass,
        # rank network: C^2 compares + C picks per coordinate, 2 sweeps
        "flops_fused": 2.0 * C * C * N + 4.0 * C * N,
        "t_memory_fused_us": 1e6 * fused[aggregator] * bytes_per_pass
        / V5E["hbm_bw"],
    }


def run(budget="small"):
    out = []
    B, S, Hq, Hkv, dh = 1, 256, 4, 2, 128
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (B, S, Hq, dh), jnp.bfloat16)
    k = jax.random.normal(key, (B, S, Hkv, dh), jnp.bfloat16)
    v = jax.random.normal(key, (B, S, Hkv, dh), jnp.bfloat16)
    for window in [0, 128]:
        t = _time(lambda: flash_attention(q, k, v, window=window,
                                          interpret=True))
        r = {"name": f"flash_attention/S{S}/w{window}", "wall_s": t}
        r.update(flash_roofline(B, S, Hq, dh, window))
        out.append(r)
    # long-context projection (the long_500k serving tile)
    out.append({"name": "flash_attention/S524288/w8192(analytic)",
                "wall_s": 0.0,
                **flash_roofline(1, 524288, 64, 128, 8192)})

    # ---- fused Eq.-11 pipeline vs multi-pass XLA reference ----
    C, N = 16, 1 << 16
    ptree = {"w": jax.random.normal(key, (C, N))}
    pmask = jnp.ones((C,)).at[0].set(0.0)
    pw = jnp.ones((C,))
    aggs = ["trimmed_mean", "median"] if budget == "small" else \
        ["fedavg", "trimmed_mean", "median", "krum"]
    for agg in aggs:
        cfg = FedConfig(n_clients=C, aggregator=agg)
        ref_fn = jax.jit(functools.partial(aggregation.aggregate_ref,
                                           cfg=cfg))
        # interleave the contenders so cgroup-throttle bursts on shared
        # CI runners hit both timing windows equally
        t_ref, t_fused = float("inf"), float("inf")
        for _ in range(7):
            t_ref = min(t_ref, _time(lambda: ref_fn(ptree, pw, pmask),
                                     reps=1))
            t_fused = min(t_fused, _time(
                lambda: fused_aggregate_tree(ptree, pw, pmask, cfg,
                                             blk=8192), reps=1))
        r = {"name": f"robust_pipeline/{agg}/C{C}/N{N}", "wall_s": t_fused,
             "wall_s_ref": t_ref, "speedup_vs_ref": t_ref / t_fused}
        r.update(robust_pipeline_roofline(C, N, agg))
        out.append(r)

    # ---- leaf-streaming (segment-table) engine on a multi-leaf tree
    # totalling N=65536 coords: matrix-shaped leaves plus a ragged one
    # and a tiny bias
    sizes = [(1 << 14,), (128, 128), (64, 256), (16_000,), (379,), (5,)]
    ltree = {f"l{j}": jax.random.normal(jax.random.fold_in(key, j),
                                       (C,) + s)
             for j, s in enumerate(sizes)}
    n_tot = sum(int(jnp.prod(jnp.asarray(s))) for s in sizes)
    for agg in aggs:
        cfg = FedConfig(n_clients=C, aggregator=agg)
        t_leaf = _time(lambda: fused_aggregate_tree(ltree, pw, pmask, cfg))
        r = {"name": f"robust_pipeline/leafwise/{agg}/C{C}/N{n_tot}",
             "wall_s": t_leaf}
        r.update(robust_pipeline_roofline(C, n_tot, agg))
        out.append(r)

    # ---- mesh-sharded per_client path vs replicated, on whatever devices
    # exist (CI forces 4 host CPU devices); parity vs the XLA oracle
    from jax.sharding import Mesh
    import numpy as np
    D = jax.device_count()
    mesh = Mesh(np.array(jax.devices()).reshape(D), ("data",))
    for agg in aggs:
        cfg = FedConfig(n_clients=C, aggregator=agg)
        sh_fn = jax.jit(lambda t, w, m, cfg=cfg: aggregation.aggregate(
            t, w, m, cfg, mesh=mesh, axes=("data",)))
        t_sh, t_rep = float("inf"), float("inf")
        for _ in range(5):
            t_rep = min(t_rep, _time(
                lambda: fused_aggregate_tree(ltree, pw, pmask, cfg),
                reps=1))
            t_sh = min(t_sh, _time(lambda: sh_fn(ltree, pw, pmask), reps=1))
        ref = aggregation.aggregate_ref(ltree, pw, pmask, cfg)
        got = sh_fn(ltree, pw, pmask)
        parity = max(float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                           - b.astype(jnp.float32))))
                     for a, b in zip(jax.tree_util.tree_leaves(got),
                                     jax.tree_util.tree_leaves(ref)))
        roof = robust_pipeline_roofline(C, n_tot, agg)
        out.append({
            "name": f"robust_pipeline/sharded/{agg}/C{C}/N{n_tot}/dev{D}",
            "wall_s": t_sh, "wall_s_replicated": t_rep,
            "speedup_vs_replicated": t_rep / t_sh,
            "devices": D, "parity_max_abs_diff": parity,
            "parity_ok_1e-5": bool(parity <= 1e-5),
            # each device streams 1/D of the matrix in both passes
            "bytes_per_device": roof["bytes_fused"] / D,
            "hbm_passes_fused": roof["hbm_passes_fused"],
        })

    # ---- comm codecs: wire bytes + encode/decode wall + fused dequant --
    # same multi-leaf tree as the leafwise section; bytes are MEASURED
    # from the encoded arrays (codes + scales + indices), not modelled
    from repro.comm import codecs as comm_codecs

    dense_pc = comm_codecs.dense_bytes_per_client(ltree)
    codec_names = ["int8", "topk"] if budget == "small" else \
        ["int8", "int4", "signsgd", "topk"]
    int8_enc, int8_codec = None, None
    for name in codec_names:
        codec = comm_codecs.Codec(name, qblk=128, topk_frac=0.05)
        enc_fn = jax.jit(codec.encode_tree)
        dec_fn = jax.jit(lambda e: codec.decode_tree(e, ltree))
        enc = enc_fn(ltree)
        if name == "int8":
            int8_enc, int8_codec = enc, codec
        t_enc = _time(lambda: enc_fn(ltree))
        t_dec = _time(lambda: dec_fn(enc))
        wire_pc = comm_codecs.wire_bytes_per_client(enc)
        out.append({
            "name": f"comm/{name}/roundtrip/C{C}/N{n_tot}",
            "wall_s": t_enc + t_dec,
            "wall_s_encode": t_enc, "wall_s_decode": t_dec,
            "wire_bytes_per_client": wire_pc,
            "dense_bytes_per_client": dense_pc,
            "wire_reduction": dense_pc / wire_pc,
            # one cohort's uplink per round, on the wire
            "bytes_on_wire_per_round": wire_pc * C,
        })

    # the int8 loader vs the dense loader: the aggregation passes stream
    # int8 codes + scales (~C*N*(1 + 4/qblk) bytes/pass) instead of
    # C*N*4 — wall measured, bytes analytic
    for agg in aggs:
        cfg = FedConfig(n_clients=C, aggregator=agg, compress="int8")
        dq_fn = jax.jit(lambda e, w, m, cfg=cfg: aggregation.aggregate(
            ltree, w, m, cfg, enc=e))
        t_dense, t_dq = float("inf"), float("inf")
        for _ in range(5):                         # interleaved (see above)
            t_dense = min(t_dense, _time(
                lambda: fused_aggregate_tree(ltree, pw, pmask, cfg),
                reps=1))
            t_dq = min(t_dq, _time(lambda: dq_fn(int8_enc, pw, pmask),
                                   reps=1))
        roof = robust_pipeline_roofline(C, n_tot, agg)
        passes = roof["hbm_passes_fused"]
        bytes_dq = passes * C * n_tot * (1.0 + 4.0 / int8_codec.qblk)
        out.append({
            "name": f"comm/fused_dequant/{agg}/C{C}/N{n_tot}",
            "wall_s": t_dq, "wall_s_dense_fused": t_dense,
            "speedup_vs_dense_fused": t_dense / t_dq,
            "hbm_passes_fused": passes,
            "agg_bytes_dense": roof["bytes_fused"],
            "agg_bytes_dequant": bytes_dq,
            "agg_bytes_reduction": roof["bytes_fused"] / bytes_dq,
            "bytes_on_wire_per_round":
                comm_codecs.wire_bytes_per_client(int8_enc) * C,
        })

    # ---- O(M) population selection (kernels/population_select.py) -----
    # Gumbel-top-d cohort sampling for the buffered-async engine: the
    # segmented two-stage reduction and the blocked Pallas kernel
    # (interpret mode off-TPU) vs the dense O(M log M) argsort baseline,
    # at registry sizes up to the million-client regime (d = 64 cohort)
    d_sel = 64
    for m_pop in (10_000, 100_000, 1_000_000):
        g = jax.random.normal(jax.random.fold_in(key, m_pop), (m_pop,))
        walls = {}
        for method in ("argsort", "segmented", "pallas"):
            fn = jax.jit(functools.partial(population_select.topd, d=d_sel,
                                           method=method, blk=4096))
            walls[method] = _time(lambda: fn(g), reps=3)
        for method in ("segmented", "pallas"):
            out.append({
                "name": f"population_select/{method}/M{m_pop}/d{d_sel}",
                "wall_s": walls[method],
                "wall_s_argsort": walls["argsort"],
                "speedup_vs_argsort": walls["argsort"] / walls[method],
                "population": m_pop, "cohort": d_sel, "blk": 4096,
                # stage 1 streams M keys once; stage 2 merges (M/blk)*d
                # candidates — vs the sort's full key + permutation traffic
                "bytes_stream": 4.0 * m_pop,
                "candidates_merged": (m_pop // 4096 + 1) * d_sel,
            })

    out.append(bench_pod_scan_driver())
    return out


def bench_pod_scan_driver(rounds=8, chunk=4):
    """Multi-round PodEngine training through the shared chunked-scan
    driver (core/driver.py, used by pod.run) vs the per-round jitted
    python loop: the scan driver does ONE host sync per chunk instead of
    one per round and donates the carry.  Tiny-lm reduced config so the
    entry stays cheap on the CI CPU; histories are bit-for-bit equal
    (tests/test_driver.py), so this measures pure driver overhead."""
    from repro.configs.base import TrainConfig
    from repro.configs.registry import ARCHS
    from repro.core import driver as scan_driver, pod
    from repro.launch.train import synthetic_lm_batches
    from repro.models import transformer
    from repro.optim import optimizers

    cfgm = ARCHS["tiny-lm"].replace(n_layers=2, d_model=64, n_heads=4,
                                    n_kv_heads=2, d_ff=128, vocab_size=128,
                                    head_dim=16)
    C, B, S = 4, 8, 32
    fed = FedConfig(n_clients=C)
    tc = TrainConfig(global_batch=B, seq_len=S, lr=1e-2, warmup_steps=2,
                     total_steps=rounds)
    params = transformer.init_transformer(jax.random.PRNGKey(0), cfgm)
    opt_init, _ = optimizers.make_optimizer(tc)

    def fresh_state():
        # fresh buffers every call: the drivers DONATE the carry, which
        # would otherwise free the shared template params
        p = jax.tree_util.tree_map(jnp.array, params)
        return pod.init_pod_state(p, opt_init, C, fed,
                                  jax.random.PRNGKey(0))

    step = pod.make_train_step(cfgm, fed, tc)
    sampler = synthetic_lm_batches(cfgm, tc, C, 0)
    sample_key = jax.random.PRNGKey(123)        # never aliased into a carry
    batch_fn = lambda t: sampler(jax.random.fold_in(sample_key, t))

    drv = scan_driver.ScanDriver(lambda st, xs: step(st, xs[1]),
                                 chunk_steps=chunk)
    step_jit = jax.jit(step, donate_argnums=(0,))

    def run_scan(st):
        drv.run(st, batch_fn, rounds)

    def run_python(st):
        for t in range(rounds):
            st, m = step_jit(st, dict(batch_fn(t)))
            jax.device_get(m)                   # per-round host sync

    def time_driver(fn, reps=3):
        fn(fresh_state())                       # warmup: compile paths
        best = float("inf")
        for _ in range(reps):
            st = fresh_state()                  # donated: fresh per rep
            t0 = time.perf_counter()
            fn(st)
            best = min(best, time.perf_counter() - t0)
        return best

    t_scan, t_py = float("inf"), float("inf")
    for _ in range(3):                          # interleaved (see above)
        t_py = min(t_py, time_driver(run_python, reps=1))
        t_scan = min(t_scan, time_driver(run_scan, reps=1))
    return {
        "name": f"driver/pod_scan/R{rounds}/chunk{chunk}/C{C}",
        "wall_s": t_scan, "wall_s_python": t_py,
        "speedup_vs_python": t_py / t_scan,
        "rounds": rounds, "chunk_rounds": chunk,
        "host_syncs_scan": -(-rounds // chunk), "host_syncs_python": rounds,
    }


def main(budget="small"):
    results = run(budget)
    for r in results:
        if "speedup_vs_replicated" in r:
            extra = (f"speedup_vs_replicated="
                     f"{r['speedup_vs_replicated']:.2f}x dev={r['devices']} "
                     f"parity={r['parity_max_abs_diff']:.1e}")
        elif "speedup_vs_dense_fused" in r:
            extra = (f"speedup_vs_dense_fused="
                     f"{r['speedup_vs_dense_fused']:.2f}x "
                     f"agg_bytes_x{r['agg_bytes_reduction']:.1f}")
        elif "wire_reduction" in r:
            extra = (f"wire_x{r['wire_reduction']:.1f} "
                     f"bytes/round={r['bytes_on_wire_per_round']:.0f}")
        elif "speedup_vs_argsort" in r:
            extra = (f"speedup_vs_argsort={r['speedup_vs_argsort']:.1f}x "
                     f"M={r['population']} d={r['cohort']}")
        elif "speedup_vs_python" in r:
            extra = (f"speedup_vs_python={r['speedup_vs_python']:.2f}x "
                     f"syncs={r['host_syncs_scan']}"
                     f"/{r['host_syncs_python']}")
        elif "speedup_vs_ref" in r:
            extra = (f"speedup={r['speedup_vs_ref']:.2f}x "
                     f"hbm_passes={r['hbm_passes_fused']:.0f}"
                     f"/{r['hbm_passes_ref']:.0f}")
        elif "intensity" in r:
            extra = f"intensity={r['intensity']:.1f}"
        else:
            extra = ""
        common.csv_row(r["name"], r["wall_s"], extra)
    # non-destructive merge by row name: other benches' sections
    # (robustness/* rows, driver rows from separate runs) survive no
    # matter where this bench sits in benchmarks/run.py
    merged = common.merge_rows(results, path=BENCH_JSON)
    print(f"# wrote {BENCH_JSON} ({len(results)} kernel rows, "
          f"{len(merged)} total)", flush=True)


if __name__ == "__main__":
    main()
