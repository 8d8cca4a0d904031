"""End-to-end federated training driver (runs on whatever devices exist).

Runs the PodEngine: FedFiTS client groups on the mesh data axis, one SPMD
program per round. With the default tiny-lm config this trains a ~100M
decoder on synthetic non-IID LM data on CPU; on a pod the same script
scales to the assigned architectures via --arch.

  PYTHONPATH=src python -m repro.launch.train --arch tiny-lm --steps 50 \
      --global-batch 16 --seq 256 --clients 4 [--ckpt-dir /tmp/ck]
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import checkpoint as ckpt
from repro.configs.base import FedConfig, TrainConfig
from repro.configs.registry import get_config
from repro.core import pod
from repro.data import synthetic
from repro.launch import inputs
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models import transformer
from repro.optim import optimizers
from repro.sharding import specs as sh


def synthetic_lm_batches(cfg, tc, n_clients, seed):
    """Per-client non-IID LM streams: each client group draws from its own
    latent Markov mixture component (label-skew analogue for LM data)."""
    key = jax.random.PRNGKey(seed)
    per = 64  # sequences per client pool
    pools = []
    for c in range(n_clients):
        toks = synthetic.make_lm_tokens(
            jax.random.fold_in(key, c), per, tc.seq_len + 1,
            cfg.vocab_size, n_latent=2)
        pools.append(np.asarray(toks))
    pools = jnp.asarray(np.stack(pools))        # (C, per, S+1)

    def sample(step_rng):
        bc = tc.global_batch // n_clients
        idx = jax.random.randint(step_rng, (n_clients, bc), 0, per)
        seqs = jax.vmap(lambda p, i: p[i])(pools, idx)  # (C, bc, S+1)
        seqs = seqs.reshape(tc.global_batch, tc.seq_len + 1)
        return {"tokens": seqs[:, :-1], "targets": seqs[:, 1:]}

    return jax.jit(sample)


def make_telemetry(args, run_name="run"):
    """--trace/--telemetry-jsonl/--profile-dir -> a Telemetry (or None
    when no obs output was requested; the scenario path still attaches
    its default in-memory telemetry in that case)."""
    from repro import obs

    sinks = []
    if args.telemetry_jsonl:
        sinks.append(obs.JsonlSink(args.telemetry_jsonl))
    if not (args.telemetry_jsonl or args.trace or args.profile_dir):
        return None
    return obs.Telemetry(sinks=sinks, trace_path=args.trace,
                         profiler_dir=args.profile_dir, run_name=run_name)


def run_scenario_cli(args):
    """--scenario: one robustness-registry cell through the SimEngine."""
    from repro.scenarios import run_scenario

    rounds = min(args.steps, 50)        # SimEngine rounds, not LM steps
    telemetry = make_telemetry(args, run_name=args.scenario)
    ctx = telemetry.profiled() if telemetry is not None else None
    if ctx is not None:
        ctx.__enter__()
    try:
        summary, hist = run_scenario(
            args.scenario, n_clients=args.clients, n_rounds=rounds,
            driver=args.driver, chunk_rounds=args.chunk_rounds,
            population=args.population, async_deadline=args.async_deadline,
            telemetry=telemetry)
    finally:
        if ctx is not None:
            ctx.__exit__(None, None, None)
    for h in hist:
        print(json.dumps({
            "round": int(h["round"]),
            "test_acc": round(float(h["test_acc"]), 4),
            "trigger_acc": round(float(h["trigger_acc"]), 4),
            "fair_worst_decile": round(float(h["fair_worst_decile"]), 4),
            "fair_part_gini": round(float(h["fair_part_gini"]), 4),
            "gated_frac": round(float(h["gated_frac"]), 4),
        }))
    print(json.dumps(summary))


def main():
    ap = argparse.ArgumentParser(
        epilog="The jittable entry points behind these flags (round "
               "engines, aggregation kernels, codecs, decode step) are "
               "statically audited — copy/RNG/donation/dtype/collective/"
               "VMEM invariants — by `python -m repro.analysis.lint "
               "--all` (see `--list` there for the entry registry); CI "
               "gates on it.")
    ap.add_argument("--arch", default="tiny-lm")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--global-batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--clients", type=int, default=4)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--data-axis", type=int, default=1)
    ap.add_argument("--model-axis", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--reduced", action="store_true",
                    help="use the smoke-test reduced arch variant")
    ap.add_argument("--robust", default=None, choices=[None, "per_client"],
                    help="per_client: coordinate-robust aggregation over "
                         "per-client grads, mesh-sharded along the "
                         "flattened param axis")
    ap.add_argument("--compress", default="none",
                    choices=["none", "int8", "int4", "signsgd", "topk"],
                    help="client->server transport codec (repro/comm/): "
                         "per-client grads cross the boundary encoded, "
                         "with EF residuals in the scan carry; int8 "
                         "aggregates straight from the wire codes "
                         "(fused dequant). Requires --robust per_client")
    ap.add_argument("--driver", default="scan", choices=["scan", "python"],
                    help="scan: chunked lax.scan rounds (donated carry, "
                         "sharding-aware batch prefetch); python: the "
                         "per-round jit loop (parity oracle)")
    ap.add_argument("--chunk-rounds", type=int, default=8)
    ap.add_argument("--scenario", default=None,
                    help="run a named robustness scenario (attack x "
                         "heterogeneity x compression x aggregator cell "
                         "from repro.scenarios.registry — e.g. "
                         "alie_fedavg, gate_aware_trimmed, "
                         "gate_aware_int8_dropout) through the SimEngine "
                         "instead of the pod LM trainer; --steps sets the "
                         "round count and --clients the cohort size. "
                         "Prints per-round accuracy/trigger-accuracy/"
                         "fairness rows and the robustness summary")
    ap.add_argument("--population", type=int, default=None,
                    help="register this many clients in the population-"
                         "scale ClientStore and route the --scenario run "
                         "through the buffered-async engine "
                         "(core/async_engine.py): each round samples a "
                         "--clients-sized cohort by O(M) Gumbel-top-d "
                         "over the store's fitness x trust priority; "
                         "late deliveries retry through the staleness-"
                         "weighted buffer. Only meaningful with "
                         "--scenario")
    ap.add_argument("--trace", default=None, metavar="OUT_JSON",
                    help="write a Chrome/Perfetto trace-event JSON for "
                         "the run (repro/obs/trace.py): the measured host "
                         "spans (driver.stage/dispatch/drain/hooks per "
                         "chunk, or one round span per round under "
                         "--driver python) on the profiler's clock, and "
                         "counter tracks. Load in ui.perfetto.dev; "
                         "validate with python -m repro.obs.check")
    ap.add_argument("--telemetry-jsonl", default=None, metavar="OUT_JSONL",
                    help="stream the obs metric rows + drift-monitor "
                         "warnings as JSON lines (one record per round; "
                         "kind=metrics|warning|summary)")
    ap.add_argument("--profile-dir", default=None,
                    help="wrap the run in jax.profiler.trace(DIR) — the "
                         "ground-truth XLA timeline escape hatch (view "
                         "with TensorBoard/Perfetto)")
    ap.add_argument("--async-deadline", type=float, default=None,
                    help="per-round delivery deadline of the buffered-"
                         "async engine (the exponential client delays "
                         "race it; FedConfig.async_deadline). Forces the "
                         "--scenario cell through the async engine, like "
                         "--population")
    args = ap.parse_args()
    enable_compile_cache()

    if (args.population or args.async_deadline) and not args.scenario:
        ap.error("--population/--async-deadline drive the buffered-async "
                 "SimEngine and need --scenario (e.g. "
                 "--scenario async_hetero)")
    if (args.population or args.async_deadline) and args.scenario:
        from repro.scenarios import registry as scen_registry
        try:
            sc = scen_registry.get(args.scenario)
        except Exception:
            sc = None                 # unknown name: run_scenario reports it
        if sc is not None and sc.compress != "none":
            ap.error(f"--scenario {args.scenario} is a compressed-uplink "
                     f"cell (compress={sc.compress}), but the buffered-"
                     "async engine (--population/--async-deadline) is "
                     "dense-uplink only — drop those flags to run the "
                     "cell on the sync engine, or pick a dense cell "
                     "(e.g. async_hetero)")

    if args.scenario:
        run_scenario_cli(args)
        return

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if args.compress != "none" and args.robust != "per_client":
        ap.error("--compress needs --robust per_client (only that path "
                 "moves per-client updates across the wire)")
    fed = FedConfig(n_clients=args.clients, compress=args.compress)
    tc = TrainConfig(global_batch=args.global_batch, seq_len=args.seq,
                     lr=args.lr, total_steps=args.steps,
                     warmup_steps=max(args.steps // 10, 1))

    mesh = make_host_mesh(args.data_axis, args.model_axis)
    key = jax.random.PRNGKey(tc.seed)
    params = transformer.init_transformer(key, cfg)
    opt_init, _ = optimizers.make_optimizer(tc)
    state = pod.init_pod_state(params, opt_init, fed.n_clients, fed, key)

    state_sh = sh.named(mesh, sh.param_specs(state, mesh=mesh))
    state = jax.device_put(state, state_sh)
    # the scan driver donates the carry: params/opt-state update in
    # place, no per-step copy (sharding follows the committed state)
    step_fn = pod.make_train_step(cfg, fed, tc, robust=args.robust,
                                  agg_mesh=mesh if args.robust else None)

    start = 0
    if args.ckpt_dir:
        restored, at = ckpt.restore_latest(args.ckpt_dir, state, state_sh)
        if restored is not None:
            state, start = restored, at
            print(f"restored checkpoint at step {at}")

    # scan-driver checkpoints happen at chunk ends (mid-chunk states never
    # exist host-side): align the chunk size to the checkpoint cadence so
    # a crash loses at most ckpt_every-1 steps, like the python driver
    chunk_rounds = args.chunk_rounds
    if args.ckpt_dir and args.driver == "scan":
        chunk_rounds = min(chunk_rounds, args.ckpt_every)
        if args.ckpt_every % chunk_rounds:
            print(f"# note: ckpt-every {args.ckpt_every} not divisible by "
                  f"chunk-rounds {chunk_rounds}; saves land on the first "
                  f"chunk end at/after each due step")

    sampler = synthetic_lm_batches(cfg, tc, fed.n_clients, tc.seed)
    # the donated carry aliases `key` (PodFedState.rng) and deletes its
    # buffer on the first chunk; sample from a live copy
    sample_key = jnp.array(np.asarray(key))
    # sharding-aware prefetch: stage each chunk's batches directly onto
    # their pod shards while the previous chunk computes
    batch_sh = inputs.batch_shardings(
        jax.eval_shape(sampler, jax.random.PRNGKey(0)), mesh)
    t0 = time.time()

    def on_chunk(st, rows):
        for row in rows:
            step = row["step"]
            if step % 5 == 0 or step == args.steps - 1:
                m = {k: round(float(v), 4) for k, v in row.items()
                     if k != "step"}
                m["step"] = step
                m["wall_s"] = round(time.time() - t0, 1)
                print(json.dumps(m))
        last = rows[-1]["step"]
        if args.ckpt_dir and any((r["step"] + 1) % args.ckpt_every == 0
                                 for r in rows):
            ckpt.save_step(args.ckpt_dir, last + 1, st)

    telemetry = make_telemetry(args, run_name=args.arch)
    with mesh:
        if telemetry is not None:
            with telemetry.profiled():
                state, _ = pod.run(
                    state, step_fn, lambda t: sampler(jax.random.fold_in(
                        sample_key, t)),
                    args.steps - start, driver=args.driver,
                    chunk_rounds=chunk_rounds, batch_sharding=batch_sh,
                    t0=start, on_chunk=on_chunk, telemetry=telemetry)
            telemetry.finish()
        else:
            state, _ = pod.run(
                state, step_fn, lambda t: sampler(jax.random.fold_in(
                    sample_key, t)),
                args.steps - start, driver=args.driver,
                chunk_rounds=chunk_rounds, batch_sharding=batch_sh,
                t0=start, on_chunk=on_chunk)
    print("done")


if __name__ == "__main__":
    main()
