"""On-device counter/metric registry (ROADMAP item 7, the obs layer).

The round engines used to expose their outcome signals (gate rejections,
buffer occupancy, billed bytes) as ad-hoc entries scattered through the
per-round metrics dict.  This module makes them a REGISTRY: every
telemetry signal is declared once as a :class:`CounterSpec` — a typed,
named, documented on-device array — and the engines publish them through
two channels that both respect the driver invariants:

  * **carry column** — cumulative counters ride the scan carry as ONE
    pytree column (``FedState.tele`` / ``AsyncState.tele``, a flat
    ``{name: jnp.ndarray}`` dict built by :func:`init_column`), updated
    with :func:`accumulate` each round.  Totals survive chunk
    boundaries, donation, and checkpointing exactly like every other
    carry field.
  * **per-round metrics** — the same round's instantaneous values are
    folded into the metrics dict under ``obs/<name>`` keys
    (:func:`metric_keys`), so they stack through ``lax.scan`` and drain
    through the existing ``on_chunk`` boundary — the 1-host-sync-per-
    chunk contract is untouched.

Telemetry is a PURE READOUT: every counter is computed from values the
round already produces (masks, weights, the delivery buffer) and nothing
downstream reads it back, so model state, rng streams and billing are
bit-identical with telemetry on or off (tests/test_obs.py asserts this
for both engines under both drivers).

Counter-naming scheme (``<subsystem>/<signal>``):

  gate/…       cosine-gate outcomes            (gate/cosine_rejected)
  guard/…      sanitize-boundary rejections by kind
               (guard/nonfinite, guard/norm)
  buffer/…     async DeliveryBuffer occupancy/parked/overflow/exhausted
               and the retry-age histogram (buffer/age_hist)
  delivery/…   on-time vs late arrival counts
  agg/…        aggregation-weight mass split fresh vs stale
  cohort/…     per-cohort trust/fitness/gate-trust quantiles
               ([p10, p50, p90] gauges)
  select/…     cohort/team size and availability
  wire/…       MEASURED uplink/downlink bytes (mirrors cost_bytes_*)
  fault/…      injected-fault outcomes (mid-round losses)

  serve/…      serving-engine signals (slot occupancy, admits/evicts,
               pages in use, decode throughput) — per decode STEP

The privacy accountant (ROADMAP item 2) will publish its per-round ε
spend as ``privacy/epsilon`` through exactly this registry.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax.numpy as jnp

METRIC_PREFIX = "obs/"

KIND_COUNTER = "counter"      # monotonic; carry column accumulates
KIND_GAUGE = "gauge"          # instantaneous; carry column holds last


@dataclasses.dataclass(frozen=True)
class CounterSpec:
    """One registered telemetry signal."""
    name: str                           # "<subsystem>/<signal>"
    kind: str                           # counter | gauge
    doc: str
    engines: Tuple[str, ...] = ("sync", "async")
    shape: Tuple[int, ...] = ()         # () scalar; histograms/quantiles
                                        # declare their static length via
                                        # shape_for (cfg-dependent)
    unit: str = "count"


REGISTRY: Dict[str, CounterSpec] = {}


def register(spec: CounterSpec) -> CounterSpec:
    if spec.name in REGISTRY:
        raise ValueError(f"duplicate counter {spec.name!r}")
    REGISTRY[spec.name] = spec
    return spec


def _r(name, kind, doc, engines=("sync", "async"), unit="count"):
    return register(CounterSpec(name, kind, doc, tuple(engines), (), unit))


# quantile gauges are fixed [p10, p50, p90] vectors
QUANTILE_PROBS = (0.1, 0.5, 0.9)

# ---- gate / guard ----------------------------------------------------
_r("gate/cosine_rejected", KIND_COUNTER,
   "participants whose update fell under the cosine-gate threshold")
_r("guard/nonfinite", KIND_COUNTER,
   "deliveries rejected by the sanitize boundary for NaN/Inf")
_r("guard/norm", KIND_COUNTER,
   "deliveries rejected for an absurd norm (> mult x masked median)")
# ---- selection / delivery -------------------------------------------
_r("select/team_size", KIND_GAUGE, "cohort/team rows this round")
_r("select/available", KIND_GAUGE, "available clients this round",
   engines=("sync",))
_r("delivery/on_time", KIND_COUNTER,
   "cohort deliveries that beat the round deadline", engines=("async",))
_r("delivery/late", KIND_COUNTER,
   "cohort deliveries that missed the deadline", engines=("async",))
# ---- async buffer ----------------------------------------------------
_r("buffer/occupancy", KIND_GAUGE,
   "DeliveryBuffer rows active after this round's update",
   engines=("async",), unit="rows")
_r("buffer/parked", KIND_COUNTER,
   "late deliveries parked into the buffer this round",
   engines=("async",))
_r("buffer/overflow", KIND_COUNTER,
   "late deliveries dropped because the buffer was full",
   engines=("async",))
_r("buffer/exhausted", KIND_COUNTER,
   "buffered rows abandoned after their retry budget ran out",
   engines=("async",))
register(CounterSpec(
    "buffer/age_hist", KIND_GAUGE,
    "active buffered rows by retry age (bucket i = age i+1)",
    ("async",), (), "rows"))
# ---- aggregation mass ------------------------------------------------
_r("agg/fresh_mass", KIND_GAUGE,
   "aggregation-weight mass of on-time deliveries", unit="mass")
_r("agg/stale_mass", KIND_GAUGE,
   "aggregation-weight mass of stale/buffered catch-up deliveries",
   unit="mass")
# ---- cohort state quantiles -----------------------------------------
register(CounterSpec("cohort/trust_q", KIND_GAUGE,
                     "cohort trust [p10, p50, p90]",
                     ("sync", "async"), (3,), "trust"))
register(CounterSpec("cohort/gate_trust_q", KIND_GAUGE,
                     "cohort gate-trust EWMA [p10, p50, p90]",
                     ("sync", "async"), (3,), "trust"))
register(CounterSpec("cohort/fitness_q", KIND_GAUGE,
                     "cohort fitness score [p10, p50, p90]",
                     ("sync", "async"), (3,), "score"))
# ---- measured wire bytes --------------------------------------------
_r("wire/bytes_up", KIND_COUNTER,
   "measured uplink bytes billed this round", unit="bytes")
_r("wire/bytes_down", KIND_COUNTER,
   "measured downlink bytes billed this round", unit="bytes")
# ---- fault injection -------------------------------------------------
_r("fault/lost", KIND_COUNTER,
   "selected clients whose update was lost mid-round",
   engines=("sync",))
# ---- serving (ROADMAP item 3; rows are per decode STEP, not round) ---
_r("serve/admitted", KIND_COUNTER,
   "requests admitted into decode slots this step", engines=("serve",),
   unit="requests")
_r("serve/evicted", KIND_COUNTER,
   "requests evicted (EOS / length budget) this step",
   engines=("serve",), unit="requests")
_r("serve/tokens", KIND_COUNTER,
   "tokens decoded this step", engines=("serve",), unit="tokens")
_r("serve/slot_occupancy", KIND_GAUGE,
   "decode slots holding a live request after this step",
   engines=("serve",), unit="slots")
_r("serve/pages_in_use", KIND_GAUGE,
   "KV pages allocated out of the pool after this step",
   engines=("serve",), unit="pages")
_r("serve/tokens_per_s", KIND_GAUGE,
   "measured decode throughput (host wall clock, filled at drain)",
   engines=("serve",), unit="tok/s")
_r("serve/kv_rows_full", KIND_COUNTER,
   "KV rows one full-attention layer attended this step, over active "
   "slots", engines=("serve",), unit="rows")
_r("serve/kv_rows_window", KIND_COUNTER,
   "KV rows one window layer attended this step, over active slots",
   engines=("serve",), unit="rows")


def age_hist_len(fed_cfg) -> int:
    """Static retry-age histogram length: ages 1..max_retries (a row
    older than its budget is abandoned, never buffered)."""
    return max(int(getattr(fed_cfg, "async_max_retries", 0)), 1)


def shape_for(spec: CounterSpec, fed_cfg) -> Tuple[int, ...]:
    if spec.name == "buffer/age_hist":
        return (age_hist_len(fed_cfg),)
    return spec.shape


def specs_for(engine: str) -> Dict[str, CounterSpec]:
    """The registry slice one engine publishes."""
    return {n: s for n, s in REGISTRY.items() if engine in s.engines}


def init_column(engine: str, fed_cfg) -> Dict[str, jnp.ndarray]:
    """The carry column: one zeroed f32 array per registered signal.
    A flat dict-of-arrays pytree — it rides the scan carry and donates
    like any other state field."""
    return {n: jnp.zeros(shape_for(s, fed_cfg), jnp.float32)
            for n, s in specs_for(engine).items()}


def accumulate(tele: Dict[str, jnp.ndarray],
               round_values: Dict[str, jnp.ndarray],
               engine: str) -> Dict[str, jnp.ndarray]:
    """Fold one round's instantaneous values into the carry column:
    counters add, gauges overwrite.  ``round_values`` must cover every
    registered signal of the engine (init_column's keys)."""
    specs = specs_for(engine)
    out = {}
    for name, spec in specs.items():
        v = jnp.asarray(round_values[name], jnp.float32)
        out[name] = tele[name] + v if spec.kind == KIND_COUNTER else v
    return out


def metric_keys(round_values: Dict[str, jnp.ndarray]) -> Dict[str, jnp.ndarray]:
    """Per-round metrics entries: ``obs/<name>`` -> f32 array.  These
    stack through the scan like every other metric and drain once per
    chunk."""
    return {METRIC_PREFIX + n: jnp.asarray(v, jnp.float32)
            for n, v in round_values.items()}


def quantiles(x: jnp.ndarray) -> jnp.ndarray:
    """[p10, p50, p90] gauge of a cohort column."""
    return jnp.quantile(x.astype(jnp.float32),
                        jnp.asarray(QUANTILE_PROBS, jnp.float32))


def age_histogram(age: jnp.ndarray, active: jnp.ndarray,
                  fed_cfg) -> jnp.ndarray:
    """Active buffered rows bucketed by retry age: bucket i counts rows
    aged i+1 (ages start at 1 when a row parks)."""
    n = age_hist_len(fed_cfg)
    buckets = jnp.arange(1, n + 1)
    onehot = (age[:, None] == buckets[None, :]).astype(jnp.float32)
    return (onehot * active[:, None]).sum(axis=0)


def row_obs(row: dict) -> dict:
    """The ``obs/`` slice of one drained history row, prefix stripped."""
    return {k[len(METRIC_PREFIX):]: v for k, v in row.items()
            if isinstance(k, str) and k.startswith(METRIC_PREFIX)}
