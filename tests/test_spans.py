"""Spans and scopes inside the program (repro/obs/trace.py): the round's
device scopes in the jaxpr, the scan driver's and the serving loop's host
spans in a CPU ``jax.profiler`` trace, the recorder on the profiler's
clock, and the benchmark's readers of those spans and scopes."""
import glob

import jax
import numpy as np
import pytest

from bench.trace import Op, Trace
from repro.analysis.traversal import all_eqns
from repro.configs.base import FedConfig
from repro.configs.registry import ARCHS, get_config
from repro.core import fedfits
from repro.data.pipeline import build_federation
from repro.launch.serve import draw_requests
from repro.models.model import build
from repro.obs import Telemetry, TraceRecorder, span
from repro.obs.trace import now_us
from repro.scenarios import engine as sc_engine, registry
from repro.serve import ServeConfig, ServeEngine

DRIVER_SPANS = ("driver.stage", "driver.dispatch", "driver.drain",
                "driver.hooks")
SERVE_SPANS = ("serve.admit", "serve.decode", "serve.bookkeep")


def _profile(tmp_path, fn):
    """Run ``fn`` under a CPU ``jax.profiler`` trace; returns the trace
    as ``jax.profiler.ProfileData``."""
    d = str(tmp_path / "prof")
    with jax.profiler.trace(d):
        fn()
    path, = glob.glob(d + "/plugins/profile/*/*.xplane.pb")
    return jax.profiler.ProfileData.from_file(path)


def _host_events(prof, names):
    """(name, start_ns, duration_ns, stats) of the host events named in
    ``names``."""
    out = []
    for plane in prof.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in names:
                        out.append((ev.name, ev.start_ns, ev.duration_ns,
                                    dict(ev.stats)))
    return out


# --------------------------------------------------------------------- #
# device scopes of the round                                            #
# --------------------------------------------------------------------- #

def test_round_jaxpr_names_attack_codec_and_server_eval():
    """A gate_aware + int8 + error-feedback sync round, with the server
    evaluation the scan body runs, has equations under the name stacks
    ``update_attack``, ``codec`` and ``server_eval`` beside the phases
    that were scoped before."""
    sc = registry.get("gate_aware_int8_dropout")
    cell = sc_engine.build_cell(sc, n_clients=6, seed=0, kind="tabular",
                                n=240, n_classes=4, sep=1.0,
                                dirichlet_alpha=1.0)
    cfg = cell.fed_cfg
    assert cfg.compress == "int8" and cfg.error_feedback
    round_fn = fedfits.make_round(cell.model, cfg,
                                  data_attack=cell.data_attack,
                                  update_attack=cell.update_attack,
                                  malicious=cell.malicious,
                                  faults=sc.faults)
    test = cell.server_test

    def eval_fn(params):
        loss, m = cell.model.loss(params, test)
        return {"test_loss": loss, "test_acc": m["acc"]}

    scoped = fedfits.scoped_eval(eval_fn)

    def body(st, batch):
        st, metrics = round_fn(st, batch)
        return st, {**metrics, **scoped(st.params)}

    params = cell.model.init(jax.random.PRNGKey(0))
    state = fedfits.init_state(params, cfg.n_clients, cfg,
                               jax.random.PRNGKey(1))
    batch = cell.federation.data_fn(1, jax.random.PRNGKey(2))
    jaxpr = jax.make_jaxpr(body)(state, batch)
    under = {}
    for _, e in all_eqns(jaxpr):
        for part in str(e.source_info.name_stack).split("/"):
            under.setdefault(part, []).append(e)
    for name in ("update_attack", "codec", "server_eval", "client_update",
                 "selection", "sanitize", "aggregate", "writeback"):
        assert name in under, name
    # the attacker's sort and its order-statistic row slices, and the
    # codec's absmax, are in their scopes, not beside them
    calls = {e.params.get("name") for e in under["update_attack"]
             if e.primitive.name == "jit"}
    assert "sort" in calls and "take_along_axis" not in calls
    assert "dynamic_slice" in {e.primitive.name
                               for e in under["update_attack"]}
    assert "reduce_max" in {e.primitive.name for e in under["codec"]}


# --------------------------------------------------------------------- #
# host spans in a profiler trace                                        #
# --------------------------------------------------------------------- #

def _sync_setup():
    model = build(ARCHS["paper-mlp"])
    fed, _ = build_federation(6, kind="tabular", n=240, n_clients=6,
                              batch_size=8, n_classes=10)
    cfg = FedConfig(n_clients=6, algorithm="fedfits", local_epochs=1,
                    local_lr=0.05, avail_prob=0.7,
                    aggregator="trimmed_mean")
    return model, fed, cfg


def test_profiler_trace_holds_driver_spans(tmp_path):
    """A 2-chunk scan-driver run under the profiler: one host span of
    each driver phase per chunk, each carrying the chunk's first round;
    no telemetry is needed for the spans."""
    model, fed, cfg = _sync_setup()
    prof = _profile(tmp_path, lambda: fedfits.run(
        model, cfg, fed.data_fn, 4, jax.random.PRNGKey(6), driver="scan",
        chunk_rounds=2))
    evs = _host_events(prof, DRIVER_SPANS)
    for name in DRIVER_SPANS:
        firsts = sorted(int(st["first"]) for n, _, _, st in evs
                        if n == name)
        assert firsts == [1, 3], (name, firsts)
    # the federation's sampler returns device arrays: the chunk stacks them
    assert [st["feed"] for n, _, _, st in evs
            if n == "driver.stage"] == ["device", "device"]
    # the phases of one chunk follow one another on the host
    dispatch = min(s for n, s, _, _ in evs if n == "driver.dispatch")
    drain = min(s for n, s, _, _ in evs if n == "driver.drain")
    assert dispatch < drain


def test_profiler_trace_holds_serving_spans(tmp_path):
    """A tiny ``ServeEngine.run`` under the profiler: one ``serve.admit``
    per request, one ``serve.decode`` and one ``serve.bookkeep`` per
    decode step."""
    cfg = get_config("tiny-lm").reduced()
    params = build(cfg).init(jax.random.PRNGKey(0))
    scfg = ServeConfig(max_slots=4, page_size=8, max_len=48, prompt_pad=8,
                       attn="ref")
    engine = ServeEngine(cfg, scfg, params, seed=0)
    reqs = draw_requests(4, 6, 2, 10, cfg.vocab_size, seed=0)
    box = {}

    def go():
        box["stats"] = engine.run(reqs)[1]

    evs = _host_events(_profile(tmp_path, go), SERVE_SPANS)
    steps = box["stats"]["steps"]
    assert sorted(int(st["req_id"]) for n, _, _, st in evs
                  if n == "serve.admit") == sorted(r.req_id for r in reqs)
    for name in ("serve.decode", "serve.bookkeep"):
        assert sorted(int(st["step"]) for n, _, _, st in evs
                      if n == name) == list(range(1, steps + 1))


def test_recorded_span_lands_on_the_profilers_clock(tmp_path):
    """One span recorded both ways: the recorder's timestamp, taken on
    the profiler's host clock, lands within 1 ms of the profiler's."""
    rec = TraceRecorder()

    def go():
        for i in range(3):
            with span("clock.check", rec, i=i):
                jax.block_until_ready(jax.numpy.ones(8) + i)

    prof = _profile(tmp_path, go)
    env = prof.find_plane_with_name("Task Environment")
    start_ns = dict(env.stats)["profile_start_time"]
    prof_spans = sorted((int(st["i"]), s, d) for n, s, d, st in
                        _host_events(prof, {"clock.check"}))
    assert len(prof_spans) == 3 and len(rec.events) == 3
    for (i, s, d), e in zip(prof_spans, rec.events):
        assert e["args"] == {"i": i}
        assert abs(e["ts"] - (start_ns + s) / 1e3) < 1e3        # µs
        assert abs(e["dur"] - d / 1e3) < 1e3


def test_telemetry_begin_end_route_through_span(tmp_path):
    """``Telemetry.begin``/``end`` open and close an ``obs.trace.span``:
    the recorder and the profiler both see it."""
    tele = Telemetry(trace_path=str(tmp_path / "t.json"))

    def go():
        t0 = now_us()
        tele.begin("launch.step", step=7)
        tele.end("launch.step")
        assert tele.now_us() >= t0

    evs = _host_events(_profile(tmp_path, go), {"launch.step"})
    assert [(n, int(st["step"])) for n, _, _, st in evs
            if n == "launch.step"] == [("launch.step", 7)]
    assert [(e["name"], e["args"]) for e in tele.tracer.events] == [
        ("launch.step", {"step": 7})]
    tele.end("never.opened")                     # no-op, no raise
    assert len(tele.tracer.events) == 1


# --------------------------------------------------------------------- #
# the benchmark's readers of the new spans and scopes                   #
# --------------------------------------------------------------------- #

MS = 1_000_000      # ns


def _op(start_ms, dur_ms, scope, program="scan_chunk"):
    return Op(name="fusion.1", start=start_ms * MS, dur=dur_ms * MS,
              scope=scope, category="", program=program, device=0)


def _fl_trace(with_program=True):
    """A 100 ms window with two scan chunks.  Device busy: 0-20 (client
    update, 4 ms; update attack, 10 ms; codec, 2 ms; server eval, 4 ms)
    and 50-70 likewise.  Host: driver.stage 20-40 (idle 20-40 overlaps
    it fully) and 90-110 (clipped to 90-100; idle 90-100 overlaps it).
    Without the program's spans and scopes, as before they existed, the
    same ops carry only the scopes that were there."""
    def scoped(name):
        return f"/{name}" if with_program else ""

    ops = []
    for base in (0, 50):
        ops += [_op(base, 4, "jit(scan_chunk)/while/body/client_update"),
                _op(base + 4, 10, "jit(scan_chunk)/while/body"
                    + scoped("update_attack") + "/jit(take_along_axis)"),
                _op(base + 14, 2, "jit(scan_chunk)/while/body"
                    + scoped("codec")),
                _op(base + 16, 4, "jit(scan_chunk)/while/body"
                    + scoped("server_eval") + "/jit(eval_fn)")]
    host = [(45 * MS, 3 * MS, "PjitFunction(scan_chunk)")]
    if with_program:
        host += [(20 * MS, 20 * MS, "driver.stage"),
                 (90 * MS, 20 * MS, "driver.stage"),
                 (40 * MS, 5 * MS, "driver.dispatch")]
    return Trace(ops, (0, 100 * MS), host, 1, [])


def _serve_trace(with_program=True):
    """A 100 ms window.  Device busy: 10-40 (admit), 50-80 (decode).
    Idle: 0-10, 40-50, 80-100.  Host: serve.admit 0-12 (overlaps idle
    0-10: 10 ms), bench.step.admit 2-12 inside it, serve.decode 45-82
    (idle 45-50 and 80-82: 7 ms), serve.bookkeep 82-90 (8 ms)."""
    ops = [_op(10, 30, "", program="admit"), _op(50, 30, "",
                                                 program="decode")]
    host = [(2 * MS, 10 * MS, "bench.step.admit")]
    if with_program:
        host += [(0, 12 * MS, "serve.admit"),
                 (45 * MS, 37 * MS, "serve.decode"),
                 (82 * MS, 8 * MS, "serve.bookkeep")]
    return Trace(ops, (0, 100 * MS), host, 1, [])


class _Inp:
    def __init__(self, trace, window):
        self.trace, self.window = trace, window


FL_WINDOW = {"rounds": 4, "seconds": 0.1}
SERVE_WINDOW = {"admits": 2, "decode_steps": 5, "seconds": 0.1}

READERS = [
    # (metric, trace maker, window, expected value)
    ("fl.attack_ms", _fl_trace, FL_WINDOW, 20 / 4),
    ("fl.codec_ms", _fl_trace, FL_WINDOW, 4 / 4),
    ("fl.server_eval_ms", _fl_trace, FL_WINDOW, 8 / 4),
    ("fl.stage_ms", _fl_trace, FL_WINDOW, 30 / 4),
    ("fl.stage_idle_share", _fl_trace, FL_WINDOW, 100 * 30 / 100),
    ("serve.admit_idle_ms", _serve_trace, SERVE_WINDOW, 10 / 2),
    ("serve.step_idle_ms", _serve_trace, SERVE_WINDOW, (7 + 8) / 5),
]


def _reader(name):
    from bench import common
    return common.load_module(common.ROOT / "bench" / "metrics"
                              / f"{name}.py")


@pytest.mark.parametrize("name,make,window,want", READERS,
                         ids=[r[0] for r in READERS])
def test_reader_reads_its_span_or_scope(name, make, window, want):
    got = _reader(name).read(_Inp(make(), window))
    np.testing.assert_allclose(got, want, rtol=1e-9)


@pytest.mark.parametrize("name,make,window,want", READERS,
                         ids=[r[0] for r in READERS])
def test_reader_is_none_without_its_span_or_scope(name, make, window,
                                                  want):
    assert _reader(name).read(_Inp(make(False), window)) is None
