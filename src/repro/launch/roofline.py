"""Roofline-term derivation from compiled dry-run artifacts (no real TPU).

  compute term    = HLO_FLOPs / peak_FLOP/s          (per chip)
  memory term     = HLO_bytes / HBM_bw               (per chip)
  collective term = sum(collective operand bytes x ring factor) / ICI_bw

cost_analysis() runs on the *partitioned* module, so its flops/bytes are
already per-chip. Collective bytes are parsed from the partitioned HLO
text (per-chip shapes); all-reduce gets a 2x ring factor (reduce-scatter +
all-gather phases), others 1x. '-done' halves of async pairs are skipped
to avoid double counting.
"""
from __future__ import annotations

from typing import Dict

# HLO-text parsing lives in the shared analysis helpers; the roofline and
# the invariant linter (repro/analysis/rules.py) read the same parser.
from repro.analysis.hlo import (COLLECTIVES as _COLL,  # noqa: F401
                                DTYPE_BYTES as _DTYPE_BYTES,
                                SHAPE_RE as _SHAPE_RE,
                                parse_collectives,
                                shape_bytes as _shape_bytes)
# Published per-chip peaks, keyed by jax ``Device.device_kind``.
# "TPU v5 lite" is the TPU v5e: Google Cloud documentation, "TPU v5e"
# (cloud.google.com/tpu/docs/v5e) — 197 TFLOP/s bf16, 393 TOP/s int8,
# 16 GB HBM at 819 GB/s, 1,600 Gbit/s of interchip interconnect
# (200 GB/s over 4 links = 50 GB/s per link).
PEAKS = {
    "TPU v5 lite": {
        "flops_bf16": 197e12,         # FLOP/s
        "ops_int8": 393e12,           # OP/s
        "hbm_bytes": 16e9,            # B
        "hbm_bw": 819e9,              # B/s
        "ici_bw": 50e9,               # B/s per link
    },
}


def peaks(device_kind: str) -> dict:
    """The peak table row of one device kind; a kind with no published
    row is an error, never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}; known: "
            f"{sorted(PEAKS)}") from None


def roofline(cost: dict, coll_bytes: Dict[str, int],
             device_kind: str) -> dict:
    pk = peaks(device_kind)
    flops = float(cost.get("flops", 0.0))
    byts = float(cost.get("bytes accessed", 0.0))
    cbytes = sum(v * (2 if k == "all-reduce" else 1)
                 for k, v in coll_bytes.items())
    t_compute = flops / pk["flops_bf16"]
    t_memory = byts / pk["hbm_bw"]
    t_coll = cbytes / pk["ici_bw"]
    terms = {"compute_s": t_compute, "memory_s": t_memory,
             "collective_s": t_coll}
    dom = max(terms, key=terms.get)
    return {
        **terms,
        "hlo_flops": flops,
        "hlo_bytes": byts,
        "collective_bytes": cbytes,
        "collective_by_kind": dict(coll_bytes),
        "dominant": dom,
        "bound_s": max(terms.values()),
    }


def measured_wire_bytes(rows) -> dict:
    """Aggregate the MEASURED ``wire/bytes_up``/``wire/bytes_down``
    telemetry gauges so they can sit next to the modeled roofline terms.

    ``rows`` is either a list of drained metric rows (dicts with
    ``obs/wire/...`` keys) or a path to a telemetry JSONL stream
    (``kind == "metrics"`` records are used).  Returns totals and
    per-round means; ``rounds`` is the number of rows that carried the
    gauges (0 when telemetry counters were off)."""
    if isinstance(rows, str):
        import json
        with open(rows) as f:
            rows = [r for r in (json.loads(l) for l in f if l.strip())
                    if r.get("kind") == "metrics"]
    up = [float(r["obs/wire/bytes_up"]) for r in rows
          if "obs/wire/bytes_up" in r]
    down = [float(r["obs/wire/bytes_down"]) for r in rows
            if "obs/wire/bytes_down" in r]
    n = max(len(up), len(down))
    return {
        "rounds": n,
        "bytes_up": sum(up),
        "bytes_down": sum(down),
        "bytes_up_per_round": sum(up) / n if n else 0.0,
        "bytes_down_per_round": sum(down) / n if n else 0.0,
    }


def count_params(params_struct) -> int:
    import jax

    return sum(int(_prod(l.shape)) for l in
               jax.tree_util.tree_leaves(params_struct))


def active_params(cfg, n_params: int) -> int:
    """6*N_active*D MoE correction: expert FFN weights scale by top_k/E."""
    if not cfg.n_experts:
        return n_params
    cycle_moe = sum(1 for k in cfg.layers if k == "moe")
    expert_w = cycle_moe * cfg.n_experts * 3 * cfg.d_model * cfg.d_ff
    return n_params - expert_w + expert_w * cfg.top_k // cfg.n_experts


def model_flops(cfg, n_params: int, shape, kind: str) -> float:
    """6*N*D (train) / 2*N*D (inference forward) reference FLOPs, global."""
    n_act = active_params(cfg, n_params)
    if kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n_act * tokens
    if kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n_act * tokens
    # decode: one token per sequence
    return 2.0 * n_act * shape.global_batch


def _prod(t):
    r = 1
    for x in t:
        r *= x
    return r
