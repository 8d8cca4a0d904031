"""Federated cells: FedFiTS rounds through ``repro.core.fedfits.run`` on the
scan driver, as a healthcare federation runs them.

Set-up builds the federation from the seed and starts one ``fedfits.run``
call.  Its first chunks compile and warm up; the window opens at the chunk
boundary after ``warmup_chunks`` chunks and closes at the first chunk
boundary ``--seconds`` later, where the telemetry hook ends the call.
Rounds 1 to ``CHECK_ROUNDS`` of the same call, which ran through the same
compiled chunk and feed as the window's, are compared with the plain
reference once the window has closed and the program's state is freed.
"""
from __future__ import annotations

import gc
import time

from bench import common
from bench.gen import Federation, key_seed

# Set before JAX is imported.  The server evaluation closes over the seed's
# holdout set and initial parameters; hoisted as arguments of the compiled
# chunk, they no longer make each seed's chunk a program of its own.
JAX_ENV = {"JAX_USE_SIMPLIFIED_JAXPR_CONSTANTS": "1"}

# Rounds compared: the election of rounds 2 and 3 by fitness, with the
# error-feedback residual carried from the round before.
CHECK_ROUNDS = 3
# Never reached: the telemetry hook ends the call at the window's close.
ROUND_CAP = 10 ** 6
# Leaves whose reference change after round 1 is under this share of the
# median leaf's move by round-off alone and are left out of the gaps.
SKIP_RULE = 1e-3


class _WindowClosed(Exception):
    """Raised from the telemetry hook to end the run at the window's close."""


class _Hook:
    """The duck-typed telemetry ``fedfits.run`` and the scan driver call:
    it keeps each chunk's rows and host timestamps and opens and closes
    the window at chunk boundaries."""

    counters = False
    tracer = None

    def __init__(self, window, warmup_chunks, seconds):
        self.window = window
        self.warmup = warmup_chunks
        self.seconds = seconds
        self.rows = []
        self.chunk_ends = []
        self.window_rounds = 0

    def bind_engine(self, engine):
        return self

    def begin(self, name):
        pass

    def end(self, name, **args):
        pass

    def now_us(self):
        return time.perf_counter() * 1e6

    def observe_rows(self, rows, w0, dur, **kw):
        self.rows.extend(rows)
        self.chunk_ends.append(time.perf_counter())
        n = len(self.chunk_ends)
        if n == self.warmup:
            self.window.open()
        elif n > self.warmup:
            self.window_rounds += len(rows)
            if time.perf_counter() - self.window.t0 >= self.seconds:
                self.window.close()
                raise _WindowClosed()


def _program_objects(traffic, config):
    """The program's scenario, model and round hooks for this cell, checked
    against the protocol the traffic file states."""
    from repro.configs.base import ModelConfig
    from repro.models.model import build
    from repro.scenarios import registry
    from repro.scenarios.engine import make_attack_fns

    s = traffic["scenario"]
    over = dict(s.get("overrides", {}))
    if "fed" in over:
        over["fed"] = tuple(tuple(kv) for kv in over["fed"])
    sc = registry.get(s["base"]).replace(**over)
    p = traffic["protocol"]
    fed_cfg = sc.fed_config(p["n_clients"])
    mcfg = common.program_config(config)
    if not isinstance(mcfg, ModelConfig):
        raise TypeError(mcfg)
    model = build(mcfg)
    data_attack, update_attack = make_attack_fns(sc, fed_cfg,
                                                 traffic["federation"]
                                                 ["n_classes"])
    import jax.numpy as jnp
    n_mal = max(int(round(sc.mal_frac * p["n_clients"])), 1) \
        if sc.attack != "none" else 0
    malicious = (jnp.zeros((p["n_clients"],)).at[jnp.arange(n_mal)].set(1.0)
                 if n_mal else None)
    stated = {
        "n_clients": fed_cfg.n_clients, "algorithm": fed_cfg.algorithm,
        "aggregator": fed_cfg.aggregator, "compress": fed_cfg.compress,
        "qblk": fed_cfg.compress_qblk, "error_feedback": fed_cfg.error_feedback,
        "local_epochs": fed_cfg.local_epochs, "local_lr": fed_cfg.local_lr,
        "trim_frac": fed_cfg.trim_frac, "beta": fed_cfg.beta,
        "alpha": fed_cfg.alpha, "dynamic_alpha": fed_cfg.dynamic_alpha,
        "trust_decay": fed_cfg.trust_decay,
        "trust_in_fitness": fed_cfg.trust_in_fitness,
        "cosine_thresh": fed_cfg.cosine_outlier_thresh,
        "paper_exact_agg": fed_cfg.paper_exact_agg,
        "guard_norm_mult": fed_cfg.guard_norm_mult,
        "msl": fed_cfg.msl, "pft": fed_cfg.pft,
        "dropout_prob": sc.faults.dropout_prob, "attack": sc.attack,
        "n_malicious": n_mal, "avail_prob": fed_cfg.avail_prob,
        "stale_weight": fed_cfg.stale_weight, "prox_mu": fed_cfg.prox_mu,
        "participation_floor": fed_cfg.participation_floor,
        "explore_eps": fed_cfg.explore_eps,
    }
    for k, v in stated.items():
        if k in p and p[k] != v:
            raise ValueError(f"program's {k}={v!r}, protocol states {p[k]!r}")
    missing = [k for k in stated if k not in p]
    if missing:
        raise ValueError(f"protocol does not state {missing}")
    return sc, fed_cfg, model, data_attack, update_attack, malicious


def make_eval_fn(model, server_test, params0, backdoor_patch, target=0):
    """The scenario engine's per-round server evaluation (test accuracy and
    trigger accuracy on the holdout), with the test loss it already
    computes and each leaf's distance from the initial parameters: the
    readings the comparison takes."""
    import jax
    import jax.numpy as jnp

    from repro.core import attacks

    trig = {"x": attacks.stamp_trigger(server_test["x"],
                                       patch=backdoor_patch),
            "y": server_test["y"]}

    @jax.jit
    def eval_fn(params):
        loss, m = model.loss(params, server_test)
        logits = model.forward(params, trig)
        dist = [jnp.linalg.norm(a - b) for a, b in zip(
            jax.tree_util.tree_leaves(params),
            jax.tree_util.tree_leaves(params0))]
        return {"test_loss": loss, "test_acc": m["acc"],
                "trigger_acc": (logits.argmax(-1) == target).mean(),
                "bench_dist": jnp.stack(dist)}

    return eval_fn


def program_rounds(ctx):
    """Drive the program: one ``fedfits.run`` call through warm-up and the
    window.  Returns the hook (rows, chunk times, rounds in the window)."""
    import jax

    from repro.core import fedfits

    traffic = ctx.traffic
    sc, fed_cfg, model, data_attack, update_attack, malicious = \
        _program_objects(traffic, ctx.config)
    fed = Federation(ctx.seed, traffic["federation"])
    key = jax.random.PRNGKey(key_seed(ctx.seed))
    params0 = model.init(jax.random.split(key)[0])
    eval_fn = make_eval_fn(model, fed.server_test, params0,
                           sc.backdoor_patch, sc.backdoor_target)
    hook = _Hook(ctx.window, traffic["warmup_chunks"], ctx.seconds)
    try:
        fedfits.run(model, fed_cfg, fed.data_fn, ROUND_CAP, key,
                    eval_fn=eval_fn, data_attack=data_attack,
                    update_attack=update_attack, malicious=malicious,
                    faults=sc.faults, driver="scan", telemetry=hook)
    except _WindowClosed:
        pass
    else:
        raise RuntimeError("the round cap ended the run before the window")
    return hook, fed, key


def program_readings(hook):
    return [{"test_loss": float(r["test_loss"]),
             "dist": [float(d) for d in r["bench_dist"]],
             "team": [int(v) for v in r["team"]]}
            for r in hook.rows[:CHECK_ROUNDS]]


def reference_readings(ctx, fed, key, *, dtype=None, fault=None):
    import jax
    import jax.numpy as jnp

    ref = common.load_module(ctx.root / "bench" / "reference"
                             / f"{ctx.workload['config']}.py")
    batches = [fed.data_fn(t, jax.random.fold_in(key, t))
               for t in range(1, CHECK_ROUNDS + 1)]
    return ref.run_rounds(key, ctx.config["config"], ctx.traffic["protocol"],
                          batches, fed.server_test,
                          dtype=dtype or jnp.float32, fault=fault)


def _leaf_gap(prog, ref, t):
    """Worst leaf's gap between the program's and the reference's norm of
    the change after round ``t + 1``, over the larger of the leaf's and
    the median leaf's reference norm; leaves whose reference change after
    round 1 is under ``SKIP_RULE`` of the median leaf's are left out."""
    import numpy as np

    r1 = np.asarray(ref[0]["dist"])
    keep = r1 >= SKIP_RULE * float(np.median(r1))
    rd, pd = np.asarray(ref[t]["dist"]), np.asarray(prog[t]["dist"])
    den = np.maximum(rd, float(np.median(rd)))
    return float(np.max(np.abs(pd - rd)[keep] / den[keep]))


def _loss_gap(prog, ref, t):
    r, p = ref[t]["test_loss"], prog[t]["test_loss"]
    return abs(p - r) / max(abs(r), 1e-12)


def compare(prog, ref):
    """The numbers ``correct`` compares (see PERF.md), over rounds 1 to
    ``CHECK_ROUNDS``: the relative gap of the server test loss after round
    1, the worst leaf's gap of the norm of the first update the server
    applies, the worst leaf's gap of the norm of the change after the last
    round, and the number of rounds whose elected teams differ."""
    last = CHECK_ROUNDS - 1
    return {"loss_gap": _loss_gap(prog, ref, 0),
            "update_gap": _leaf_gap(prog, ref, 0),
            "change_gap": _leaf_gap(prog, ref, last),
            "team_mismatch": float(sum(
                p["team"] != r["team"] for p, r in zip(prog, ref)))}


def later_losses(prog, ref):
    """Loss gaps of rounds 2.. that ``correct`` does not compare."""
    return {f"loss_gap{t + 1}": _loss_gap(prog, ref, t)
            for t in range(1, CHECK_ROUNDS)}


def run(ctx):
    import numpy as np

    hook, fed, key = program_rounds(ctx)
    peak = common.memory_peak()
    window_s = ctx.window.t1 - ctx.window.t0
    prog = program_readings(hook)
    in_window = hook.rows[len(hook.rows) - hook.window_rounds:]
    failed = sum(1 for r in in_window
                 if not np.isfinite(float(r["test_loss"])))
    rounds = len(in_window)
    del hook, in_window
    gc.collect()

    gaps = compare(prog, reference_readings(ctx, fed, key))
    limits = ctx.traffic["limits"]
    return {
        "attempted": rounds,
        "failed": failed,
        "e2e": {"rounds_per_s": rounds / window_s},
        "checks": [(k, gaps[k], limits[k]) for k in gaps],
        "memory_peak_bytes": peak,
        "window": {"rounds": rounds, "seconds": window_s},
    }
