"""Serving cells of models whose layers mix window and full attention:
``engines/serve.py``'s closed backlog through ``ServeEngine.run``, with the
decode step's on-device counters of the KV rows attended per layer of each
kind.

The window's dict gains ``decode_rows_full`` and ``decode_rows_window``,
summed over the window's decode steps from the program's
``serve/kv_rows_full`` and ``serve/kv_rows_window``, and
``prompt_pairs_window``, the (query, key) pairs of the window's admitted
prompts with keys clipped to the window.  ``decode_rows_full`` must equal
the recorder's own ``decode_rows``: the run fails where they differ.  Set-up
also checks that the program runs the layer kinds the configuration file
states (``attention_period``).
"""
from __future__ import annotations

import gc

import numpy as np

from bench import common

base = common.load_module(common.ROOT / "bench" / "engines" / "serve.py")
sample_finished = base.sample_finished
reference_gaps = base.reference_gaps
gap_numbers = base.gap_numbers
reference = base.reference

ROWS = ("serve/kv_rows_full", "serve/kv_rows_window")


class Recorder(base.Recorder):
    """``engines/serve.py``'s ``Recorder`` that also keeps each decode
    step's KV-row counters: ``(t, full, window)``."""

    def __init__(self, engine, plens):
        super().__init__(engine, plens)
        self.rows = []

    def decode(self, params, pools, st):
        import jax

        out = super().decode(params, pools, st)
        v = jax.device_get({k: out[2]["vals"][k] for k in ROWS})
        self.rows.append((self.steps[-1][0], *(float(v[k]) for k in ROWS)))
        return out


def build_engine(ctx):
    cfg, params, engine, drawn, reqs = base.build_engine(ctx)
    c = ctx.config["config"]
    period = tuple(c["attention_period"])
    if cfg.attn_types != period * (c["n_layers"] // len(period)):
        raise ValueError(f"the program runs layer kinds {cfg.attn_types}, "
                         f"the file states the period {period}")
    return cfg, params, engine, drawn, reqs


def serve(ctx, engine, drawn, reqs, *, warmup, seconds, window):
    """``engines/serve.py``'s ``serve`` with this file's recorder."""
    rec = Recorder(engine, [len(t) for t, _ in drawn])
    base._warm_prompt_padding(drawn, ctx.traffic["prompt_pad"])
    try:
        engine.run(reqs, telemetry=base._Hook(window, warmup, seconds))
    except base._WindowClosed:
        pass
    else:
        raise RuntimeError("the backlog emptied before the window closed")
    return rec


def prompt_pairs_window(plen, window):
    """(query, key) pairs of a causal prompt whose keys are clipped to the
    last ``window`` positions."""
    inside = min(plen, window)
    return inside * (inside + 1) // 2 + (plen - inside) * window


def window_readings(rec, t0, t1, window):
    w = base.window_readings(rec, t0, t1)
    steps = [r for r in rec.rows if t0 < r[0] <= t1]
    w["decode_rows_full"] = int(sum(r[1] for r in steps))
    w["decode_rows_window"] = int(sum(r[2] for r in steps))
    w["prompt_pairs_window"] = sum(prompt_pairs_window(p, window)
                                   for t, p in rec.admits if t0 < t <= t1)
    if w["decode_rows_full"] != w["decode_rows"]:
        raise RuntimeError(
            f"serve/kv_rows_full counted {w['decode_rows_full']} rows in the "
            f"window, the recorder {w['decode_rows']}")
    return w


def run(ctx):
    tr = ctx.traffic
    cfg, params, engine, drawn, reqs = build_engine(ctx)
    rec = serve(ctx, engine, drawn, reqs, warmup=tr["warmup_steps"],
                seconds=ctx.seconds, window=ctx.window)
    peak = common.memory_peak()
    w = window_readings(rec, ctx.window.t0, ctx.window.t1,
                        cfg.sliding_window)
    del engine, params, reqs
    gc.collect()

    rids = sample_finished(rec, drawn, tr["check_requests"], ctx.seed)
    gaps = gap_numbers(reference_gaps(ctx, rec, drawn, rids))
    finished = sum(1 for rid, toks in rec.tokens.items()
                   if len(toks) == drawn[rid][1]
                   and ctx.window.t0 < toks[-1][1] <= ctx.window.t1)
    itl = w.pop("gaps_s")
    return {
        "attempted": finished,
        "failed": 0,
        "e2e": {"tokens_per_s": w["tokens"] / w["seconds"],
                "itl_p95_ms": float(np.percentile(itl, 95)) * 1e3},
        "checks": [(k, v, tr["limits"][k]) for k, v in gaps.items()
                   if k in tr["limits"]],
        "memory_peak_bytes": peak,
        "window": w,
    }
