"""Round-trace telemetry subsystem (repro/obs): bitwise on/off parity
across both engines x both drivers (the counter column rides the donated
carry but must never perturb numerics, rng, or billing), counter
correctness against hand-computable engine outcomes, monitor/sink/trace
plumbing, the artifact schema checks, and the bench-merge contract."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import FedConfig
from repro.configs.registry import ARCHS
from repro.core import async_engine, fedfits
from repro.core.faults import FaultConfig
from repro.data.pipeline import build_federation
from repro.models.model import build
from repro.obs import (JsonlSink, MemorySink, MultiSink, Telemetry,
                       counters as obs_counters)
from repro.obs.check import check_jsonl, check_trace
from repro.obs.monitors import Monitor, MonitorBank
from repro.obs.sinks import jsonable
from repro.obs.trace import TraceRecorder, span

_LATE = FaultConfig(straggler_frac=0.3, straggler_delay=3.0,
                    base_delay=0.3)


def _setup(seed=0, m=12, n=360):
    model = build(ARCHS["paper-mlp"])
    fed, _ = build_federation(seed, kind="tabular", n=n, n_clients=m,
                              batch_size=8, n_classes=10)
    return model, fed


def _sync_cfg(k=6, **kw):
    base = dict(n_clients=k, algorithm="fedfits", local_epochs=1,
                local_lr=0.05, avail_prob=0.7, aggregator="trimmed_mean")
    base.update(kw)
    return FedConfig(**base)


def _async_cfg(c=4, m=12, **kw):
    base = dict(n_clients=c, population=m, algorithm="fedavg",
                aggregator="trimmed_mean", local_epochs=1, local_lr=0.2,
                async_max_retries=2, staleness_decay=0.5)
    base.update(kw)
    return FedConfig(**base)


def _leaves(tree):
    return [np.asarray(l) for l in jax.tree_util.tree_leaves(tree)
            if hasattr(l, "shape")]


def _assert_same_history(h_on, h_off):
    """Telemetry adds obs/ keys; every pre-existing key stays bit-equal."""
    assert len(h_on) == len(h_off)
    for r_on, r_off in zip(h_on, h_off):
        assert set(r_off) <= set(r_on)
        assert any(k.startswith("obs/") for k in r_on)
        for k in r_off:
            np.testing.assert_array_equal(
                np.asarray(r_on[k]), np.asarray(r_off[k]), err_msg=k)


# --------------------------------------------------------------------- #
# bitwise on/off parity                                                 #
# --------------------------------------------------------------------- #

@pytest.mark.parametrize("driver", ["python", "scan"])
def test_sync_engine_on_off_bit_parity(driver):
    """Model state, rng, billing, and every pre-existing metric are
    bit-identical with the counter column on vs off."""
    model, fed = _setup(0, m=6, n=240)
    cfg = _sync_cfg()
    kw = dict(driver=driver, chunk_rounds=2)
    st_off, h_off = fedfits.run(model, cfg, fed.data_fn, 4,
                                jax.random.PRNGKey(0), **kw)
    st_on, h_on = fedfits.run(model, cfg, fed.data_fn, 4,
                              jax.random.PRNGKey(0),
                              telemetry=Telemetry(sinks=[MemorySink()]),
                              **kw)
    for a, b in zip(_leaves(st_off.params), _leaves(st_on.params)):
        np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(np.asarray(st_off.rng),
                                  np.asarray(st_on.rng))
    assert float(st_off.cost_bytes_up) == float(st_on.cost_bytes_up)
    assert float(st_off.cost_client_rounds) == \
        float(st_on.cost_client_rounds)
    _assert_same_history(h_on, h_off)


@pytest.mark.parametrize("driver", ["python", "scan"])
def test_async_engine_on_off_bit_parity(driver):
    model, fed = _setup(1)
    cfg = _async_cfg()
    kw = dict(driver=driver, chunk_rounds=2, batch_size=8, faults=_LATE,
              straggler_rows="head")
    st_off, h_off = async_engine.run_async(
        model, cfg, fed.data, 4, jax.random.PRNGKey(1), **kw)
    st_on, h_on = async_engine.run_async(
        model, cfg, fed.data, 4, jax.random.PRNGKey(1),
        telemetry=Telemetry(sinks=[MemorySink()]), **kw)
    for a, b in zip(_leaves(st_off.params), _leaves(st_on.params)):
        np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(np.asarray(st_off.rng),
                                  np.asarray(st_on.rng))
    assert float(st_off.cost_client_rounds) == \
        float(st_on.cost_client_rounds)
    _assert_same_history(h_on, h_off)
    # the fault injection actually exercised the buffer counters
    assert sum(r["obs/buffer/parked"] for r in h_on) > 0


def test_async_scan_python_parity_with_telemetry_on():
    """scan==python bit parity holds WITH the counter column riding the
    scan carry — including every obs/ metric."""
    model, fed = _setup(2)
    cfg = _async_cfg()
    kw = dict(batch_size=8, faults=_LATE, straggler_rows="head")
    _, h_p = async_engine.run_async(
        model, cfg, fed.data, 4, jax.random.PRNGKey(2), driver="python",
        telemetry=Telemetry(sinks=[MemorySink()]), **kw)
    _, h_s = async_engine.run_async(
        model, cfg, fed.data, 4, jax.random.PRNGKey(2), driver="scan",
        chunk_rounds=2, telemetry=Telemetry(sinks=[MemorySink()]), **kw)
    for rp, rs in zip(h_p, h_s):
        assert set(rp) == set(rs)
        for k in rp:
            np.testing.assert_array_equal(
                np.asarray(rp[k]), np.asarray(rs[k]), err_msg=k)


# --------------------------------------------------------------------- #
# counter correctness vs engine outcomes                                #
# --------------------------------------------------------------------- #

def test_sync_guard_counters_match_nan_outcome():
    """One NaN-poisoning client: the guard rejects exactly it each round
    and obs/guard/nonfinite bills the same rejection, by kind."""
    model, fed = _setup(3, m=6, n=240)
    # full participation (fedavg, no election) so the poisoner is in
    # every round's team and the per-round count is exactly 1
    cfg = _sync_cfg(algorithm="fedavg", aggregator="fedavg",
                    avail_prob=1.0)
    mal = jnp.zeros((6,)).at[0].set(1.0)

    def nan_attack(upd, malicious, rng):
        return jax.tree_util.tree_map(
            lambda l: jnp.where(
                malicious.reshape((-1,) + (1,) * (l.ndim - 1)) > 0,
                jnp.full_like(l, jnp.nan), l), upd)

    _, hist = fedfits.run(model, cfg, fed.data_fn, 3,
                          jax.random.PRNGKey(3), driver="python",
                          update_attack=nan_attack, malicious=mal,
                          telemetry=Telemetry(sinks=[MemorySink()]))
    for h in hist:
        assert float(h["guard_rejected"]) == 1.0
        assert float(h["obs/guard/nonfinite"]) == 1.0
        assert float(h["obs/guard/norm"]) == 0.0


def test_async_counters_match_buffer_outcomes():
    """Every buffer counter reconciles with the engine's own metrics:
    parked==buffered, occupancy==buf_fill, exhausted+overflow==abandoned,
    guard kinds sum to guard_rejected, on_time is the cohort fraction,
    and the retry-age histogram sums to the live-row count."""
    model, fed = _setup(4)
    cfg = _async_cfg()
    _, hist = async_engine.run_async(
        model, cfg, fed.data, 8, jax.random.PRNGKey(4), driver="python",
        batch_size=8, faults=_LATE, straggler_rows="head",
        telemetry=Telemetry(sinks=[MemorySink()]))
    c = cfg.n_clients
    assert sum(float(h["buffered"]) for h in hist) > 0
    for h in hist:
        assert float(h["obs/buffer/parked"]) == float(h["buffered"])
        assert float(h["obs/buffer/occupancy"]) == float(h["buf_fill"])
        assert (float(h["obs/buffer/exhausted"])
                + float(h["obs/buffer/overflow"])
                == float(h["abandoned"]))
        assert (float(h["obs/guard/nonfinite"])
                + float(h["obs/guard/norm"])
                == float(h["guard_rejected"]))
        np.testing.assert_allclose(
            float(h["obs/delivery/on_time"]),
            float(h["on_time_frac"]) * c, rtol=1e-6)
        assert np.asarray(h["obs/buffer/age_hist"]).sum() == \
            float(h["buf_fill"])


def test_async_exhaustion_counter_totals():
    """One retry, hopeless stragglers (delay >> any backoff window):
    every parked row burns its retry and exhausts, and the abandonment
    counters total exactly the engine's abandoned work and the
    ClientStore failure tally."""
    model, fed = _setup(5, m=16, n=480)
    cfg = _async_cfg(c=4, m=16, async_max_retries=1, async_deadline=0.5)
    fl = FaultConfig(straggler_frac=0.3, straggler_delay=50.0,
                     base_delay=0.01)
    state, hist = async_engine.run_async(
        model, cfg, fed.data, 8, jax.random.PRNGKey(5), driver="python",
        batch_size=8, faults=fl, straggler_rows="head",
        telemetry=Telemetry(sinks=[MemorySink()]))
    exhausted = sum(float(h["obs/buffer/exhausted"]) for h in hist)
    overflow = sum(float(h["obs/buffer/overflow"]) for h in hist)
    assert exhausted > 0                # parked rows time out on retry 1
    abandoned = sum(float(h["abandoned"]) for h in hist)
    assert exhausted + overflow == abandoned
    # clean data -> no guard rejections, so the chronic-failure tally is
    # exactly the abandoned deliveries
    assert abandoned == np.asarray(state.clients.failures).sum()


# --------------------------------------------------------------------- #
# monitors                                                              #
# --------------------------------------------------------------------- #

def test_monitor_k_consecutive_streaks():
    m = Monitor("hot", lambda r: r.get("x"), ">", 0.5, k_consecutive=2)
    fires = [m.observe({"x": v, "round": i}) is not None
             for i, v in enumerate([0.6, 0.4, 0.6, 0.7, 0.7])]
    # a lone trip never fires; the 2nd consecutive (and each after) does
    assert fires == [False, False, False, True, True]
    assert m.observe({"y": 1}) is None          # not-applicable rows skip


def test_monitor_bank_guard_majority_warning():
    bank = MonitorBank()
    row = {"round": 1, "obs/guard/nonfinite": 3.0, "obs/guard/norm": 0.0,
           "obs/select/team_size": 4.0, "obs/gate/cosine_rejected": 0.0,
           "obs/cohort/trust_q": [0.4, 0.5, 0.6]}
    assert bank.observe(row) == []              # streak 1 of 2
    fired = bank.observe({**row, "round": 2})
    assert [w["monitor"] for w in fired] == ["guard_rejecting_majority"]
    assert fired[0]["round"] == 2 and fired[0]["streak"] == 2
    assert bank.counts() == {"guard_rejecting_majority": 1}


# --------------------------------------------------------------------- #
# sinks                                                                 #
# --------------------------------------------------------------------- #

def test_jsonable_coerces_device_scalars():
    assert jsonable(jnp.float32(3.0)) == 3
    assert jsonable(jnp.float32(3.5)) == 3.5
    assert jsonable(np.float64(2**60)) == float(2**60)   # too big for int
    assert jsonable(jnp.arange(3.0)) == [0, 1, 2]
    assert jsonable({"a": (jnp.int32(1), None)}) == {"a": [1, None]}


def test_jsonl_sink_roundtrip_and_close(tmp_path):
    path = str(tmp_path / "m.jsonl")
    s = JsonlSink(path)
    s.emit({"kind": "metrics", "round": 1, "obs/x": jnp.float32(2.0)})
    s.close()
    rows = [json.loads(l) for l in open(path)]
    assert rows == [{"kind": "metrics", "round": 1, "obs/x": 2}]
    with pytest.raises(ValueError):
        s.emit({"kind": "metrics"})


def test_multi_and_memory_sinks_fan_out():
    a, b = MemorySink(), MemorySink(capacity=1)
    multi = MultiSink([a, b])
    multi.emit({"kind": "metrics", "round": 1})
    multi.emit({"kind": "warning", "monitor": "m"})
    assert len(a.records) == 2 and len(b.records) == 1   # ring bounded
    assert a.by_kind("warning") == [{"kind": "warning", "monitor": "m"}]


# --------------------------------------------------------------------- #
# trace + artifact checks                                               #
# --------------------------------------------------------------------- #

DRIVER_SPANS = ("driver.stage", "driver.dispatch", "driver.drain",
                "driver.hooks")


def _fake_row(t):
    return {"round": t, "obs/gate/cosine_rejected": 0.0,
            "obs/buffer/occupancy": float(t)}


def test_trace_recorder_emits_checkable_phase_spans(tmp_path):
    """Spans opened through ``obs.trace.span`` land in the recorder as
    measured "X" events with their args; drained gauges land as counter
    events at the drain; the schema check asks for named spans."""
    rec = TraceRecorder("sync")
    for name in DRIVER_SPANS:
        with span(name, rec, first=1):
            pass
    rec.counters([_fake_row(1), _fake_row(2)], ts_us=1234.0)
    trace = rec.to_json()
    assert trace["displayTimeUnit"] == "ms"
    xs = [e for e in trace["traceEvents"] if e["ph"] == "X"]
    assert [e["name"] for e in xs] == list(DRIVER_SPANS)
    assert all(e["args"] == {"first": 1} and e["dur"] > 0 for e in xs)
    assert all(b["ts"] >= a["ts"] + a["dur"] for a, b in zip(xs, xs[1:]))
    cs = [e for e in trace["traceEvents"] if e["ph"] == "C"]
    assert [(e["name"], e["ts"], e["args"]["value"]) for e in cs] == [
        ("buffer/occupancy", 1234.0, 1.0), ("buffer/occupancy", 1234.0,
                                            2.0)]
    assert not check_trace(trace, spans=DRIVER_SPANS)
    path = tmp_path / "t.json"
    rec.save(str(path))
    assert not check_trace(str(path), spans=DRIVER_SPANS)
    # mutation twin: drop one span -> the check names it
    trace["traceEvents"] = [e for e in trace["traceEvents"]
                            if e["name"] != "driver.drain"]
    errs = check_trace(trace, spans=DRIVER_SPANS)
    assert len(errs) == 1 and "driver.drain" in errs[0]


def test_run_artifacts_pass_schema_checks(tmp_path):
    """A real scan-driver run: the JSONL stream and the Perfetto trace
    both pass the CI schema checks, with every registered counter
    present and one measured span of each driver phase per chunk."""
    model, fed = _setup(6, m=6, n=240)
    cfg = _sync_cfg()
    jsonl = str(tmp_path / "obs.jsonl")
    tr = str(tmp_path / "trace.json")
    tele = Telemetry(sinks=[JsonlSink(jsonl)], trace_path=tr)
    fedfits.run(model, cfg, fed.data_fn, 3, jax.random.PRNGKey(6),
                driver="scan", chunk_rounds=2, telemetry=tele)
    summary = tele.finish()
    assert summary["rows"] == 3
    assert not check_jsonl(jsonl, require_obs=True, engine="sync")
    assert not check_trace(tr, spans=DRIVER_SPANS)
    evs = json.load(open(tr))["traceEvents"]
    for name in DRIVER_SPANS:
        assert sorted(e["args"]["first"] for e in evs
                      if e["name"] == name) == [1, 3]      # two chunks
    # mutation twin: a stream with no summary record fails the check
    bad = str(tmp_path / "bad.jsonl")
    with open(jsonl) as f, open(bad, "w") as g:
        g.writelines(l for l in f
                     if json.loads(l).get("kind") != "summary")
    assert check_jsonl(bad, require_obs=True, engine="sync")


# --------------------------------------------------------------------- #
# bench artifact merge contract                                         #
# --------------------------------------------------------------------- #

def test_bench_merge_rows_is_order_independent(tmp_path, monkeypatch):
    """Re-running any bench replaces only its own section: kernel rows
    re-merge by name without dropping the robustness rows, whatever the
    registration order."""
    from benchmarks.common import bench_json_path, merge_rows
    path = str(tmp_path / "BENCH.json")
    monkeypatch.setenv("BENCH_KERNELS_JSON", path)
    assert bench_json_path() == path            # env read at call time
    merge_rows([{"name": "robustness/clean", "acc": 0.9}])
    merge_rows([{"name": "agg/fused", "us": 10.0}])
    merged = merge_rows([{"name": "agg/fused", "us": 12.0}])
    assert merged == json.load(open(path))
    assert {r["name"] for r in merged} == {"robustness/clean",
                                           "agg/fused"}
    assert next(r for r in merged
                if r["name"] == "agg/fused")["us"] == 12.0
