"""Serving cells: a closed backlog of requests through
``repro.serve.ServeEngine.run`` (continuous batching over the paged KV
pools, paged flash-decode), as an offline batch job drives it.

Set-up makes the weights on the device from the seed in one jitted call
(``init_params`` of the reference file, in the program's parameter layout,
which is checked against the program's own init), draws the request list (``bench/gen.py``), compiles
the admit and decode programs and the host-side prompt padding for every
prompt length in the list, and serves until ``warmup_steps`` decode steps
have run.  The window then runs for ``--seconds`` and closes at a decode
step boundary, where the telemetry hook ends the run.

Spans recorded from this file around the engine's two step programs
(``ServeEngine._admit`` and ``._decode``) give each token its host time of
emission (the step's outputs, synced as the engine syncs them) and its
request; from them come tokens per second, the gaps between a request's
tokens, and the tokens the comparison checks.  Once the window has closed
and the program's state is freed, a sample of finished requests drawn from
the seed, the longest among them, is scored by the plain reference.
"""
from __future__ import annotations

import gc
import time

import numpy as np

from bench import common, gen


class _WindowClosed(Exception):
    """Raised from the telemetry hook to end the run at the window's close."""


class Recorder:
    """Wraps the engine's admit and decode programs: each call's outputs
    are synced (the engine syncs them right after) and every emitted token
    is kept with its request and host time."""

    def __init__(self, engine, plens):
        self.plens = plens
        self.tokens = {}            # req_id -> [(token, t), ...]
        self.admits = []            # (t, prompt length)
        self.steps = []             # (t, tokens, KV rows attended, gauge)
        self._admit, self._decode = engine._admit, engine._decode
        engine._admit, engine._decode = self.admit, self.decode

    def admit(self, params, pools, st, prompt, plen, max_new, req_id):
        import jax

        with jax.profiler.TraceAnnotation("bench.step.admit"):
            out = self._admit(params, pools, st, prompt, plen, max_new,
                              req_id)
            o = jax.device_get(out[2])
        t = time.perf_counter()
        rid = int(req_id)
        self.tokens[rid] = [(int(o["tok0"]), t)]
        self.admits.append((t, int(plen)))
        return out

    def decode(self, params, pools, st):
        import jax

        with jax.profiler.TraceAnnotation("bench.step.decode"):
            out = self._decode(params, pools, st)
            o = jax.device_get(out[2])
        t = time.perf_counter()
        n, rows = 0, 0
        for i in np.flatnonzero(np.asarray(o["emitted"]) > 0):
            rid = int(o["req"][i])
            toks = self.tokens[rid]
            rows += self.plens[rid] + len(toks)
            toks.append((int(o["next"][i]), t))
            n += 1
        self.steps.append((t, n, rows,
                           float(o["vals"]["serve/slot_occupancy"])))
        return out


class _Hook:
    """The duck-typed telemetry ``ServeEngine.run`` calls once per decode
    step: opens the window after ``warmup`` steps and ends the run at the
    first step boundary ``seconds`` later."""

    def __init__(self, window, warmup, seconds):
        self.window, self.warmup, self.seconds = window, warmup, seconds
        self.n = 0

    def bind_engine(self, engine):
        return self

    def now_us(self):
        return time.perf_counter() * 1e6

    def observe_rows(self, rows, w0, dur, **kw):
        self.n += 1
        if self.n == self.warmup:
            self.window.open()
        elif self.n > self.warmup and \
                time.perf_counter() - self.window.t0 >= self.seconds:
            self.window.close()
            raise _WindowClosed()


def _warm_prompt_padding(reqs, pad):
    """Compile, in set-up, the engine's host-side padding of each prompt
    length the list holds (``zeros(pad).at[:len].set(tokens)``), which
    would otherwise compile at the first admission of each length."""
    import jax
    import jax.numpy as jnp

    seen = {}
    for toks, _ in reqs:
        seen.setdefault(len(toks), toks)
    for toks in seen.values():
        jax.block_until_ready(jnp.zeros((pad,), jnp.int32).at[:len(toks)].set(
            jnp.asarray(toks, jnp.int32)))


def window_readings(rec, t0, t1):
    """Counts and times of the window (t0, t1] from the recorded spans."""
    gaps, n_tok = [], 0
    for toks in rec.tokens.values():
        for j, (_, t) in enumerate(toks):
            if t0 < t <= t1:
                n_tok += 1
                if j:
                    gaps.append(t - toks[j - 1][1])
    steps = [s for s in rec.steps if t0 < s[0] <= t1]
    admits = [a for a in rec.admits if t0 < a[0] <= t1]
    return {
        "seconds": t1 - t0,
        "tokens": n_tok,
        "gaps_s": gaps,
        "decode_steps": len(steps),
        "decode_tokens": sum(s[1] for s in steps),
        "decode_rows": sum(s[2] for s in steps),
        "occupancy": (float(np.mean([s[3] for s in steps]))
                      if steps else None),
        "admits": len(admits),
        "prompt_tokens": sum(a[1] for a in admits),
        "prompt_pairs": sum(a[1] * (a[1] + 1) // 2 for a in admits),
    }


def build_engine(ctx):
    """The program's weights, engine and request list for this cell."""
    import jax

    from repro.models.model import build
    from repro.serve import Request, ServeConfig, ServeEngine

    tr = ctx.traffic
    cfg = common.program_config(ctx.config)
    ref = reference(ctx)
    c = ctx.config["config"]
    want = jax.eval_shape(build(cfg).init, jax.random.PRNGKey(0))
    got = ref.param_shapes(c)
    if jax.tree_util.tree_structure(want) != jax.tree_util.tree_structure(
            got) or any((a.shape, a.dtype) != (b.shape, b.dtype) for a, b in
                        zip(jax.tree_util.tree_leaves(want),
                            jax.tree_util.tree_leaves(got))):
        raise ValueError("the program's parameter tree is not the one the "
                         "benchmark makes weights for")
    key = jax.random.PRNGKey(gen.key_seed(ctx.seed))
    params = jax.block_until_ready(ref.init_params(key, c))
    scfg = ServeConfig(max_slots=tr["slots"], page_size=tr["page_size"],
                       max_len=tr["max_len"], prompt_pad=tr["prompt_pad"],
                       num_pages=tr["num_pages"], kv_int8=tr["kv_int8"],
                       attn="pallas", temperature=0.0, eos_id=-1)
    engine = ServeEngine(cfg, scfg, params, seed=gen.key_seed(ctx.seed))
    drawn = gen.requests(ctx.seed, tr["mix"], cfg.vocab_size)
    reqs = [Request(i, toks, n) for i, (toks, n) in enumerate(drawn)]
    return cfg, params, engine, drawn, reqs


def serve(ctx, engine, drawn, reqs, *, warmup, seconds, window):
    """Drive ``engine.run`` through warm-up and the window; returns the
    recorder."""
    rec = Recorder(engine, [len(t) for t, _ in drawn])
    _warm_prompt_padding(drawn, ctx.traffic["prompt_pad"])
    try:
        engine.run(reqs, telemetry=_Hook(window, warmup, seconds))
    except _WindowClosed:
        pass
    else:
        raise RuntimeError("the backlog emptied before the window closed")
    return rec


def sample_finished(rec, drawn, n, seed):
    """Finished requests to compare: the one with most served tokens and
    ``n - 1`` more drawn from the seed."""
    done = [rid for rid, toks in rec.tokens.items()
            if len(toks) == drawn[rid][1]]
    if not done:
        raise RuntimeError("no request finished")
    done.sort(key=lambda r: (-len(rec.tokens[r]), r))
    rest = done[1:]
    rng = np.random.default_rng(int(seed) + 1)
    pick = list(rng.choice(len(rest), size=min(n - 1, len(rest)),
                           replace=False)) if rest else []
    return [done[0]] + [rest[i] for i in sorted(pick)]


def reference(ctx):
    return common.load_module(ctx.root / "bench" / "reference"
                              / f"{ctx.workload['config']}.py")


def reference_gaps(ctx, rec, drawn, rids, *, quant=None):
    """For each sampled request, the gaps by which each served token's
    reference logit lies below the reference's best at that position."""
    import jax

    ref = reference(ctx)
    c = ctx.config["config"]
    key = jax.random.PRNGKey(gen.key_seed(ctx.seed))
    seqs = [(list(drawn[r][0]), [t for t, _ in rec.tokens[r]]) for r in rids]
    return ref.served_gaps(key, c, seqs, ctx.traffic["max_len"], quant=quant)


def gap_numbers(gaps):
    """The widest gap of any served token and the mean gap over all served
    tokens of the sample; ``correct`` compares the numbers that the traffic
    file gives a limit (PERF.md says why the widest is not one)."""
    flat = np.concatenate([np.asarray(g, np.float64) for g in gaps])
    return {"served_gap": float(np.max(flat)),
            "served_gap_mean": float(np.mean(flat))}


def run(ctx):
    tr = ctx.traffic
    cfg, params, engine, drawn, reqs = build_engine(ctx)
    rec = serve(ctx, engine, drawn, reqs, warmup=tr["warmup_steps"],
                seconds=ctx.seconds, window=ctx.window)
    peak = common.memory_peak()
    w = window_readings(rec, ctx.window.t0, ctx.window.t1)
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
