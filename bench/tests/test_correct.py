"""``correct`` on the CPU at sizes a test run holds.

The federated reference follows the program's round to float32 rounding;
a run whose timed path is broken underneath (a round that returns its
state unchanged, clients that train on half their batch, a served token
altered where the engine emits it) reads ``correct`` false under the
cells' limits; the control (the reference in bfloat16 for the federated
cells, with fp8 products for serving) reads far from the reference.  Each test drives the engine adapter as ``bench/run.py``
does, past the harness's look for a chip.
"""
import copy
import json

import pytest

from bench import common

BENCH = json.loads((common.ROOT / "BENCHMARK.json").read_text())


def _files(cell):
    wl = next(w for w in BENCH["workloads"] if w["name"] == cell)
    entry = next(c for c in BENCH["configs"] if c["name"] == wl["config"])
    config = json.loads((common.ROOT / entry["file"]).read_text())
    traffic = json.loads((common.ROOT / "bench" / "traffic"
                          / f"{wl['traffic']}.json").read_text())
    return wl, config, traffic


def _ctx(cell, seed, *, seconds, config=None, traffic=None):
    wl, cfg, tr = _files(cell)
    return common.Context(workload=wl, config=config or cfg,
                          traffic=traffic or tr, seed=seed, seconds=seconds,
                          window=common.Window(None))


def _engine(name):
    return common.load_module(common.ROOT / "bench" / "engines"
                              / f"{name}.py")


def _failed(out):
    return any(value > limit for _, value, limit in out["checks"])


# --------------------------------------------------------------- federated
def _small_fl(cell):
    _, _, tr = _files(cell)
    tr = copy.deepcopy(tr)
    tr["federation"].update(n=400, holdout=128)
    tr["warmup_chunks"] = 1
    return tr


@pytest.mark.parametrize("cell", ["fl_xray_paper_clean",
                                  "fl_xray_attack_int8"])
def test_fl_reference_follows_the_program(cell):
    out = _engine("fl_sync").run(_ctx(cell, 2 ** 35 + 11, seconds=0.0,
                                      traffic=_small_fl(cell)))
    assert out["attempted"] >= 8 and out["failed"] == 0
    for name, value, _ in out["checks"]:
        assert value < 1e-3, name
    assert not _failed(out)


def _unchanged(fedfits):
    orig = fedfits.make_round

    def make_round(*a, **k):
        round_fn = orig(*a, **k)

        def broken(state, data):
            new, metrics = round_fn(state, data)
            return new._replace(params=state.params), metrics

        return broken

    return "make_round", make_round


def _half_batch(fedfits):
    orig = fedfits.make_client_update

    def make_client_update(model, fed_cfg):
        update = orig(model, fed_cfg)

        def broken(params, data, rng, n_epochs):
            h = data["x"].shape[0] // 2
            data = dict(data, x=data["x"][:h], y=data["y"][:h])
            return update(params, data, rng, n_epochs)

        return broken

    return "make_client_update", make_client_update


@pytest.mark.parametrize("fault", [_unchanged, _half_batch])
def test_fl_broken_round_is_not_correct(fault, monkeypatch):
    from repro.core import fedfits

    monkeypatch.setattr(fedfits, *fault(fedfits))
    cell = "fl_xray_paper_clean"
    out = _engine("fl_sync").run(_ctx(cell, 77, seconds=0.0,
                                      traffic=_small_fl(cell)))
    assert _failed(out)


def test_fl_control_reads_far_from_the_reference():
    """The control (the reference in bfloat16 in the program's place) on
    both cells' protocols.  On the CPU the program follows the reference to
    float32 rounding (above); the control reads orders of magnitude
    further off."""
    import jax
    import jax.numpy as jnp

    from bench.gen import Federation, key_seed

    eng = _engine("fl_sync")
    for cell in ("fl_xray_paper_clean", "fl_xray_attack_int8"):
        ctx = _ctx(cell, 5, seconds=0.0, traffic=_small_fl(cell))
        fed = Federation(ctx.seed, ctx.traffic["federation"])
        key = jax.random.PRNGKey(key_seed(ctx.seed))
        ref = eng.reference_readings(ctx, fed, key)
        ctl = eng.reference_readings(ctx, fed, key, dtype=jnp.bfloat16)
        gaps = eng.compare(ctl, ref)
        assert max(gaps.values()) > 1e-3, cell


def test_fl_other_election_is_not_correct():
    """Rounds whose elected teams differ fail ``correct`` however close the
    losses and changes read."""
    eng = _engine("fl_sync")
    _, _, tr = _files("fl_xray_attack_int8")
    ref = [{"test_loss": 0.5, "dist": [1.0, 2.0, 3.0], "team": [1, 1, 0]}
           for _ in range(eng.CHECK_ROUNDS)]
    prog = [dict(r) for r in ref]
    prog[-1] = dict(ref[-1], team=[1, 0, 1])
    gaps = eng.compare(prog, ref)
    assert gaps["team_mismatch"] == 1.0
    assert gaps["team_mismatch"] > tr["limits"]["team_mismatch"]
    assert eng.compare(ref, ref) == {k: 0.0 for k in gaps}


# ----------------------------------------------------------------- serving
def _small_serve():
    """granite-moe-1b-a400m's widths, vocabulary and logit scale with two
    layers of 16 experts (top 8), four slots and short requests."""
    _, config, traffic = _files("serve_granite_long")
    config = copy.deepcopy(config)
    small = {"n_layers": 2, "n_experts": 16, "top_k": 8,
             "capacity_factor": 2.0}
    config["config"].update(small)
    config["program"]["overrides"].update(small)
    traffic = copy.deepcopy(traffic)
    traffic.update(slots=4, page_size=16, prompt_pad=64, max_len=128,
                   num_pages=32, warmup_steps=2, check_requests=3)
    traffic["mix"] = {"prompt": [8, 64], "output": [4, 24], "block": 4,
                      "n_blocks": 12, "warm": 4}
    return config, traffic


def _serve_run(monkeypatch=None, alter=False):
    config, traffic = _small_serve()
    if alter:
        from repro.serve import engine as engine_mod

        orig = engine_mod.ServeEngine._make_decode

        def make_decode(self):
            decode = orig(self)

            def broken(params, pools, st):
                cache, st2, out = decode(params, pools, st)
                nxt = out["next"].at[0].set((out["next"][0] + 1)
                                            % self.cfg.vocab_size)
                return cache, st2._replace(tok=nxt[:, None]), \
                    dict(out, next=nxt)

            return broken

        monkeypatch.setattr(engine_mod.ServeEngine, "_make_decode",
                            make_decode)
    return _engine("serve").run(_ctx("serve_granite_long", 2 ** 32 + 3,
                                     seconds=2.0, config=config,
                                     traffic=traffic))


def test_serve_sound_run_is_correct():
    out = _serve_run()
    assert out["e2e"]["tokens_per_s"] > 0
    assert not _failed(out)


def test_serve_altered_token_is_not_correct(monkeypatch):
    assert _failed(_serve_run(monkeypatch, alter=True))
