"""Turns one registry ``Scenario`` into a run of the SimEngine and a
robustness/fairness summary row.

Attacks bind to the round loop through the existing ``make_round`` hooks
(data_attack / update_attack), faults through the ``faults`` FaultConfig
— so a scenario run exercises exactly the code path every other
experiment uses, scan driver included.  Backdoor trigger accuracy is
tracked per round for EVERY scenario (the trigger-stamped server test
set scored against the backdoor target class): for non-backdoor cells it
stays at the target-class base rate, which is the regression signal.
"""
from __future__ import annotations

import dataclasses
import time
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.configs.registry import ARCHS
from repro.core import async_engine, attacks, fedfits
from repro.data.pipeline import build_federation
from repro.models.model import build
from repro.obs import MemorySink, Telemetry
from repro.scenarios import registry


def make_attack_fns(sc, fed_cfg, n_classes):
    """(data_attack, update_attack) closures for one scenario cell."""
    data_attack = update_attack = None
    a = sc.attack
    if a == "label_flip":
        def data_attack(data, mal, rng):
            return {"y": attacks.label_flip(data["y"], n_classes, mal)}
    elif a == "backdoor":
        def data_attack(data, mal, rng):
            x, y = attacks.backdoor_trigger(
                data["x"], data["y"], mal, target=sc.backdoor_target,
                patch=sc.backdoor_patch)
            return {"x": x, "y": y}
    elif a == "sign_flip":
        def update_attack(upd, mal, rng):
            return attacks.sign_flip(upd, mal, scale=sc.attack_scale)
    elif a == "gaussian":
        def update_attack(upd, mal, rng):
            return attacks.gaussian_update(upd, mal, sc.attack_scale, rng)
    elif a == "scale":
        def update_attack(upd, mal, rng):
            return attacks.scale_attack(upd, mal, sc.attack_scale)
    elif a == "alie":
        def update_attack(upd, mal, rng):
            return attacks.alie(upd, mal, z=sc.alie_z)
    elif a in ("min_max", "min_sum"):
        fn = getattr(attacks, a)

        def update_attack(upd, mal, rng):
            return fn(upd, mal)
    elif a == "gate_aware":
        def update_attack(upd, mal, rng):
            return attacks.gate_aware(upd, mal, fed_cfg)
    elif a == "cross_round":
        # stateful: the round engines detect .stateful and thread the
        # (blend, prev_gated) carry through the scan (FedState.attacker)
        update_attack = attacks.CrossRoundGateAware(fed_cfg)
    elif a != "none":
        raise ValueError(f"unknown attack {a!r}")
    return data_attack, update_attack


class Cell(NamedTuple):
    """Everything one scenario cell's rounds close over."""
    fed_cfg: object
    model: object
    federation: object
    server_test: dict
    malicious: object            # (pop,) 0/1, or None without an attack
    n_mal: int
    data_attack: object
    update_attack: object


def build_cell(sc, *, n_clients, seed, kind, n, n_classes, sep,
               dirichlet_alpha, arch=None) -> Cell:
    """The federation, model, config and attack hooks of one cell — what
    ``run_scenario`` drives, for callers that drive single rounds."""
    fed_cfg = sc.fed_config(n_clients)
    # async cells register a POPULATION of clients and sample the cohort
    pop = (sc.population or 3 * n_clients) if sc.async_mode else n_clients
    if sc.async_mode:
        fed_cfg = dataclasses.replace(fed_cfg, population=pop)
    model = build(ARCHS[arch or
                        ("paper-cnn" if kind == "images" else "paper-mlp")])
    federation, server_test = build_federation(
        seed, kind=kind, n=n, n_clients=pop, batch_size=32,
        n_classes=n_classes, sep=sep, dirichlet_alpha=dirichlet_alpha)

    n_mal = max(int(round(sc.mal_frac * pop)), 1) \
        if sc.attack != "none" else 0
    malicious = jnp.zeros((pop,)).at[jnp.arange(n_mal)].set(1.0) \
        if n_mal else None
    data_attack, update_attack = make_attack_fns(sc, fed_cfg, n_classes)
    return Cell(fed_cfg, model, federation, server_test, malicious, n_mal,
                data_attack, update_attack)


def run_scenario(scenario, *, n_clients=10, n_rounds=10, seed=0,
                 kind="tabular", n=1600, n_classes=10, sep=1.0,
                 dirichlet_alpha=1.0, arch=None, driver="scan",
                 chunk_rounds=4, population=None, async_deadline=None,
                 telemetry=None):
    """Run one scenario cell; returns (summary dict, per-round history).

    ``population`` / ``async_deadline`` (the launch CLI's --population /
    --async-deadline) force the cell through the buffered-async engine
    with that registered-client count / round deadline, overriding the
    scenario's own async settings.  Async cells (``sc.async_mode``)
    sample a cohort of ``n_clients`` per round from the M-row
    ClientStore; ``n_clients`` is the COHORT size, not the population.

    ``telemetry``: a ``repro.obs.Telemetry``; by default every cell gets
    one with an in-memory sink, so the summary row always carries the
    drift-monitor outcome (``obs_warnings``/``obs_rows``).  Pass your
    own to route the cell's metric stream to JSONL/stdout sinks or a
    ``--trace`` Perfetto file; pass ``telemetry=False`` to opt out
    entirely (the engines then run the telemetry-free program).

    ``sep`` defaults below the pipeline's easy-mode class separation: on
    the trivially-separable default every aggregator reaches ~1.0 within
    a couple of rounds and attack degradation has no headroom to show.
    ``dirichlet_alpha`` defaults milder than the pipeline's 0.3: under
    heavy label skew the honest updates' own spread is so wide that any
    within-spread attacker (gate_aware, ALIE) gets a huge free budget
    and every aggregator degrades — 1.0 keeps the honest cluster tight
    enough that robust-aggregator margins are attributable to the
    attack, not the heterogeneity (which has its own fault-injection
    axis).
    """
    sc = registry.get(scenario) if isinstance(scenario, str) else scenario
    if (population or async_deadline) and sc.compress != "none":
        raise ValueError(
            f"scenario {sc.name!r} uses compress={sc.compress!r}, which "
            "the buffered-async engine does not support — drop "
            "--population/--async-deadline (the sync engine runs every "
            "codec cell) or pick a dense-uplink scenario (e.g. "
            "async_hetero)")
    if population or async_deadline:
        sc = sc.replace(
            async_mode=True, population=population or sc.population,
            fed=sc.fed + ((("async_deadline", float(async_deadline)),)
                          if async_deadline else ()))
    cell = build_cell(sc, n_clients=n_clients, seed=seed, kind=kind, n=n,
                      n_classes=n_classes, sep=sep,
                      dirichlet_alpha=dirichlet_alpha, arch=arch)
    (fed_cfg, model, federation, server_test, malicious, n_mal,
     data_attack, update_attack) = cell

    trig_test = {"x": attacks.stamp_trigger(server_test["x"],
                                            patch=sc.backdoor_patch),
                 "y": server_test["y"]}

    @jax.jit
    def eval_fn(params):
        _, m = model.loss(params, server_test)
        logits = model.forward(params, trig_test)
        trig_acc = (logits.argmax(-1) == sc.backdoor_target).mean()
        return {"test_acc": m["acc"], "trigger_acc": trig_acc}

    if telemetry is None:
        telemetry = Telemetry(sinks=[MemorySink()], run_name=sc.name)
    elif telemetry is False:
        telemetry = None
    t0 = time.time()
    if sc.async_mode:
        state, hist = async_engine.run_async(
            model, fed_cfg, federation.data, n_rounds,
            jax.random.PRNGKey(seed + 1), eval_fn=eval_fn,
            batch_size=federation.batch_size,
            eval_batch=federation.eval_batch,
            data_attack=data_attack, update_attack=update_attack,
            malicious=malicious, faults=sc.faults,
            straggler_rows=sc.straggler_rows, driver=driver,
            chunk_rounds=chunk_rounds, telemetry=telemetry)
    else:
        state, hist = fedfits.run(
            model, fed_cfg, federation.data_fn, n_rounds,
            jax.random.PRNGKey(seed + 1), eval_fn=eval_fn,
            data_attack=data_attack, update_attack=update_attack,
            malicious=malicious, faults=sc.faults, driver=driver,
            chunk_rounds=chunk_rounds, telemetry=telemetry)
    wall = time.time() - t0
    summary = summarize(sc, state, hist, n_mal, wall)
    if telemetry is not None:
        obs = telemetry.finish()
        summary["obs_rows"] = obs["rows"]
        summary["obs_warnings"] = obs["n_warnings"]
        summary["obs_warning_counts"] = obs["warnings"]
    return summary, hist


def summarize(sc, state, hist, n_mal, wall_s):
    """One robustness/* row: accuracy, trigger accuracy, fairness, trust
    separation, and cost for a finished scenario run."""
    accs = [float(h["test_acc"]) for h in hist]
    trig = [float(h["trigger_acc"]) for h in hist]
    last = hist[-1]
    gt = jnp.asarray(state.gate_trust)
    mal_mask = jnp.arange(gt.shape[0]) < n_mal
    return {
        "name": f"robustness/{sc.name}",
        "attack": sc.attack, "aggregator": sc.aggregator,
        "algorithm": sc.algorithm, "compress": sc.compress,
        "faults_active": sc.faults.active, "n_malicious": n_mal,
        "rounds": len(hist),
        "final_acc": accs[-1], "best_acc": max(accs),
        "final_trigger_acc": trig[-1], "max_trigger_acc": max(trig),
        "fair_acc_var": float(last["fair_acc_var"]),
        "fair_worst_decile": float(last["fair_worst_decile"]),
        "fair_part_gini": float(last["fair_part_gini"]),
        "gated_frac_mean": float(jnp.mean(jnp.asarray(
            [h["gated_frac"] for h in hist]))),
        "gate_trust_malicious": (
            float(jnp.where(mal_mask, gt, 0.0).sum() / n_mal)
            if n_mal else None),
        "gate_trust_honest": float(jnp.where(mal_mask, 0.0, gt).sum()
                                   / max(gt.shape[0] - n_mal, 1)),
        "cost_client_rounds": float(state.cost_client_rounds),
        "cost_bytes_up": float(state.cost_bytes_up),
        "wall_s": round(wall_s, 2),
    }
