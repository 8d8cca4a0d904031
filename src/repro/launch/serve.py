"""Serving driver: continuous batching over the paged KV cache
(repro/serve/), with the fixed-batch dense-cache loop kept as baselines.

  PYTHONPATH=src python -m repro.launch.serve --arch tiny-lm --reduced \
      --requests 16 --max-slots 4 --page-size 8 --prompt-len 8 \
      --gen-min 16 --gen-max 64 [--engine continuous|fixed|dense] \
      [--kv-int8] [--telemetry-jsonl obs.jsonl] [--trace trace.json]

Engines:
  continuous  slot scheduler + paged KV + flash-decode (the default)
  fixed       same compiled programs, batch-until-drained admission —
              the scheduling baseline for the BENCH serve/* rows
  dense       the original fixed-batch full-cache loop (make_decode_step)

Per-request generation lengths are drawn log-uniformly in
[--gen-min, --gen-max] (mixed-length workload: the regime where
continuous batching wins).
"""
from __future__ import annotations

import argparse
import json
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.registry import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models.model import build


def make_decode_step(model, *, temperature=1.0):
    """One jittable autoregressive decode step: run the model on the last
    token, then sample (temperature > 0) or argmax the next one.

    Returns ``step(params, tok, cache, pos, key) -> (tok', cache', key')``
    with the PRNG key advanced through ``jax.random.split`` every step —
    the serving loop threads the returned key, never reusing one (this is
    a registered entry point of ``repro.analysis``; the lint CLI audits
    exactly that discipline)."""

    def step(params, tok, cache, pos, key):
        logits, cache = model.decode(params, {"tokens": tok}, cache, pos)
        if temperature > 0:
            key, sub = jax.random.split(key)
            nxt = jax.random.categorical(
                sub, logits[:, -1] / temperature)[:, None]
        else:
            nxt = jnp.argmax(logits[:, -1], -1)[:, None]
        return nxt, cache, key

    return step


def draw_requests(n, prompt_len, gen_min, gen_max, vocab, seed=0):
    """Mixed-length synthetic workload: log-uniform generation budgets."""
    from repro.serve import Request
    rng = np.random.RandomState(seed)
    reqs = []
    for i in range(n):
        gen = int(round(math.exp(rng.uniform(math.log(gen_min),
                                             math.log(gen_max)))))
        prompt = tuple(rng.randint(0, vocab, prompt_len).tolist())
        reqs.append(Request(i, prompt, max(gen, 1)))
    return reqs


def run_dense(model, cfg, args, key):
    """The original fixed-batch full-cache loop (every request padded to
    the longest generation)."""
    params = model.init(key)
    B, P, G = args.max_slots, args.prompt_len, args.gen_max
    prompts = jax.random.randint(key, (args.requests, P), 0,
                                 cfg.vocab_size)
    decode_step = jax.jit(make_decode_step(model,
                                           temperature=args.temperature))
    prefill = jax.jit(model.prefill)
    total = 0
    t0 = time.time()
    for lo in range(0, args.requests, B):
        batch = prompts[lo:lo + B]
        cache = model.init_cache(batch.shape[0], P + G,
                                 dtype=jnp.float32)
        logits, cache = prefill(params, {"tokens": batch}, cache)
        tok = jnp.argmax(logits[:, -1], -1)[:, None]
        for i in range(G - 1):
            tok, cache, key = decode_step(params, tok, cache,
                                          jnp.int32(P + i), key)
        tok.block_until_ready()
        total += batch.shape[0] * G
    wall = time.time() - t0
    return {"engine": "dense", "tokens": total, "wall_s": round(wall, 3),
            "tokens_per_s": round(total / max(wall, 1e-9), 1)}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tiny-lm")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--engine", default="continuous",
                    choices=["continuous", "fixed", "dense"])
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--max-slots", type=int, default=4)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=0,
                    help="per-request KV cap; 0 -> prompt-len + gen-max")
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--gen-min", type=int, default=16)
    ap.add_argument("--gen-max", type=int, default=64)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--kv-int8", action="store_true")
    ap.add_argument("--attn", default="pallas", choices=["ref", "pallas"])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--trace", default=None, metavar="OUT_JSON")
    ap.add_argument("--telemetry-jsonl", default=None, metavar="OUT_JSONL")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = build(cfg)
    key = jax.random.PRNGKey(args.seed)

    if args.engine == "dense":
        print(json.dumps({"arch": cfg.name, **run_dense(model, cfg, args,
                                                        key)}))
        return

    from repro import obs
    from repro.serve import ServeConfig, ServeEngine

    telemetry = None
    if args.trace or args.telemetry_jsonl:
        sinks = []
        if args.telemetry_jsonl:
            sinks.append(obs.JsonlSink(args.telemetry_jsonl))
        telemetry = obs.Telemetry(sinks=sinks, trace_path=args.trace,
                                  run_name="serve")

    max_len = args.max_len or (args.prompt_len + args.gen_max)
    scfg = ServeConfig(
        max_slots=args.max_slots, page_size=args.page_size,
        max_len=max_len, prompt_pad=max(args.prompt_len, 1),
        temperature=args.temperature, kv_int8=args.kv_int8,
        attn=args.attn)
    params = model.init(key)
    engine = ServeEngine(cfg, scfg, params, seed=args.seed)
    reqs = draw_requests(args.requests, args.prompt_len, args.gen_min,
                         args.gen_max, cfg.vocab_size, seed=args.seed)
    results, stats = engine.run(reqs, telemetry=telemetry,
                                continuous=args.engine == "continuous")
    if telemetry is not None:
        telemetry.finish()
    trail = stats.pop("occupancy_trail")
    print(json.dumps({
        "arch": cfg.name, **stats,
        "requests": len(reqs),
        "kv_int8": args.kv_int8,
        "tokens_per_s": round(stats["tokens_per_s"], 1),
        "wall_s": round(stats["wall_s"], 3),
        "mean_occupancy": round(sum(trail) / max(len(trail), 1), 2),
        "sample_tokens": results[0][:16],
    }))


if __name__ == "__main__":
    main()
