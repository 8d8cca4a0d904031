"""Model-poisoning defense A/B: sign-flip byzantine clients vs the
aggregator zoo (FedAvg mean, coordinate median, trimmed mean, Krum) and
the fused Pallas Eq.-11 pipeline on the same updates — plus the
compressed-transport walkthrough: the same attack under the int8 uplink
codec (repro/comm/), where the server aggregates STRAIGHT from the wire
codes (fused dequant) and bills the measured encoded bytes.

  PYTHONPATH=src python examples/poisoning_defense.py
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import FedConfig
from repro.configs.registry import ARCHS
from repro.core import aggregation, attacks, fedfits
from repro.data.pipeline import build_federation
from repro.models.model import build

K, ROUNDS, N_MAL = 10, 12, 2

model = build(ARCHS["paper-mlp"])
federation, server_test = build_federation(
    seed=0, kind="tabular", n=1600, n_clients=K, batch_size=32, n_classes=22)
malicious = jnp.zeros((K,)).at[jnp.arange(N_MAL)].set(1.0)


def update_attack(upd, mal, rng):
    return attacks.sign_flip(upd, mal, scale=10.0)


@jax.jit
def evaluate(params):
    loss, m = model.loss(params, server_test)
    return {"test_acc": m["acc"]}


print(f"{N_MAL}/{K} byzantine clients (10x sign-flipped updates)\n")
for agg in ["fedavg", "median", "trimmed_mean", "krum"]:
    cfg = FedConfig(n_clients=K, algorithm="fedfits", aggregator=agg,
                    local_epochs=2, local_lr=0.05,
                    cosine_outlier_thresh=-0.5)
    state, hist = fedfits.run(model, cfg, federation.data_fn, ROUNDS,
                              jax.random.PRNGKey(2), eval_fn=evaluate,
                              update_attack=update_attack,
                              malicious=malicious)
    accs = [float(h["test_acc"]) for h in hist]
    print(f"aggregator={agg:12s} best_acc={max(accs):.3f} "
          f"final={accs[-1]:.3f}")

# ---- defense under the compressed uplink (repro/comm/) -----------------
# sign-flip attackers + int8 transport: the trimmed-mean defense must
# keep working on the WIRE CODES — the cosine gate and rank network run
# inside the fused pipeline, whose int8 loader never materialises dense
# per-client updates on the server.  Bytes below are MEASURED from the
# encoded arrays (codes + per-block scales), not an analytic
# 4-bytes-per-param.
print("\nsign-flip attackers under the compressed uplink "
      "(trimmed_mean defense):")
for comp in ["none", "int8"]:
    cfg = FedConfig(n_clients=K, algorithm="fedfits",
                    aggregator="trimmed_mean", local_epochs=2,
                    local_lr=0.05, cosine_outlier_thresh=-0.5,
                    compress=comp)
    state, hist = fedfits.run(model, cfg, federation.data_fn, ROUNDS,
                              jax.random.PRNGKey(2), eval_fn=evaluate,
                              update_attack=update_attack,
                              malicious=malicious)
    accs = [float(h["test_acc"]) for h in hist]
    print(f"compress={comp:5s} best_acc={max(accs):.3f} "
          f"uplink={float(state.cost_bytes_up) / 1e6:6.2f} MB "
          f"downlink={float(state.cost_bytes_down) / 1e6:.2f} MB "
          f"(client-rounds {float(state.cost_client_rounds):.0f})")

# ---- named cells from the robustness scenario registry -----------------
# the scenario engine (repro/scenarios/) runs curated attack x fault x
# compression x aggregator cells through the exact same round loop and
# reports fairness (worst-decile accuracy, per-client accuracy variance,
# participation Gini) and backdoor trigger accuracy next to plain
# accuracy — trigger accuracy is tracked for EVERY cell; for
# non-backdoor cells it sits at the target-class base rate, which is the
# regression signal
from repro.scenarios import run_scenario

print("\nscenario registry cells (fairness + trigger-accuracy table):")
print(f"{'cell':20s} {'best':>6s} {'final':>6s} {'trig':>6s} "
      f"{'worst10%':>8s} {'acc_var':>8s} {'gini':>5s} {'gated':>6s}")
for cell in ["clean_trimmed", "alie_fedavg", "alie_trimmed",
             "gate_aware_trimmed", "backdoor_trimmed", "dropout_trimmed"]:
    s, _ = run_scenario(cell, n_clients=K, n_rounds=8, n=800)
    print(f"{cell:20s} {s['best_acc']:6.3f} {s['final_acc']:6.3f} "
          f"{s['final_trigger_acc']:6.3f} {s['fair_worst_decile']:8.3f} "
          f"{s['fair_acc_var']:8.4f} {s['fair_part_gini']:5.2f} "
          f"{s['gated_frac_mean']:6.2f}")

# ---- the fused Pallas pipeline on one poisoned round of updates --------
key = jax.random.PRNGKey(3)
honest = {"w": jax.random.normal(key, (K, 512)) * 0.01 + 1.0}
poisoned = attacks.sign_flip(honest, malicious, scale=10.0)
for agg in ["trimmed_mean", "median"]:
    cfg = FedConfig(n_clients=K, aggregator=agg)
    out = jax.jit(lambda u, w, m, cfg=cfg: aggregation.aggregate(
        u, w, m, cfg))(poisoned, jnp.ones((K,)), jnp.ones((K,)))
    print(f"pallas Eq.-11 pipeline [{agg}] mean coordinate "
          f"= {float(np.mean(np.asarray(out['w']))):.3f} "
          f"(honest value 1.0; naive mean "
          f"{float(np.mean(np.asarray(poisoned['w']))):.3f})")
