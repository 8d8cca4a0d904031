"""Spans, scopes and the Chrome/Perfetto trace-event recorder.

Every span here is a measurement of the host or a scope on the device.

  * :func:`annotate` is ``jax.named_scope`` alone.  Inside jitted round
    bodies it names the ops of one phase (``client_update``,
    ``update_attack``, ``codec``, ``selection``, ``sanitize``,
    ``aggregate``, ``writeback``, ``server_eval``), so the device plane
    of a ``jax.profiler`` trace, the lowered HLO's ``op_name`` and the
    analysis linter all carry the phase.
  * :class:`span` times one host phase of a driver loop
    (``driver.stage`` / ``.dispatch`` / ``.drain`` / ``.hooks``,
    ``serve.admit`` / ``.decode`` / ``.bookkeep``, a python driver's
    ``round``).  It always opens a ``jax.profiler.TraceAnnotation``, so
    a running profiler puts the span on the host plane of its
    ``.xplane.pb`` beside the device ops; handed a
    :class:`TraceRecorder`, it also records the span there.  With no
    profiler running a span costs about a microsecond and never syncs.
  * :class:`TraceRecorder` writes ``{"traceEvents": [...]}`` for
    ``--trace``: the spans recorded through :class:`span` and the
    registered scalar gauges as counter ("C") events.  Its timestamps
    are :func:`now_us`, the profiler's host clock, so a recorded span
    lands where the profiler put the same span (a profile's times are
    offsets from its ``profile_start_time``, on the same clock).

For device timings pass ``profiler_dir`` to ``Telemetry``
(``--profile-dir`` on the launchers): the run is wrapped in
``jax.profiler.trace``.
"""
from __future__ import annotations

import contextlib
import json
import time
from typing import List, Optional, Sequence

import jax


def now_us() -> float:
    """The profiler's host clock (the wall clock, which stamps the host
    and device planes of a ``jax.profiler`` trace), in microseconds."""
    return time.time_ns() / 1e3


def counter_tracks():
    """The registered scalar gauges exported as Perfetto counter ("C")
    tracks: the async buffer occupancy plus every serve/* gauge."""
    from repro.obs import counters as obs_counters
    return tuple(
        n for n, s in obs_counters.REGISTRY.items()
        if s.kind == obs_counters.KIND_GAUGE and s.shape == ()
        and (n == "buffer/occupancy" or n.startswith("serve/")))


def annotate(name: str):
    """Phase scope inside jitted round bodies: names the ops for
    jaxpr/HLO/profiler consumers.  Pure metadata — no ops are added, so
    telemetry-on stays bit-identical."""
    return jax.named_scope(name)


class span:
    """``with span(name, recorder, **args):`` — one measured host span.

    Opens ``jax.profiler.TraceAnnotation(name, **args)`` (the args become
    the event's stats in the profile) and, when ``recorder`` is not
    None, records the same span there on :func:`now_us`'s clock."""

    __slots__ = ("_ann", "_rec", "_name", "_args", "_t0")

    def __init__(self, name: str, recorder: Optional["TraceRecorder"] = None,
                 **args):
        self._ann = jax.profiler.TraceAnnotation(name, **args)
        self._rec = recorder
        self._name = name
        self._args = args

    def __enter__(self):
        self._ann.__enter__()
        if self._rec is not None:
            self._t0 = now_us()
        return self

    def __exit__(self, *exc):
        if self._rec is not None:
            t1 = now_us()
            self._rec.record(self._name, self._t0, t1 - self._t0,
                             **self._args)
        return self._ann.__exit__(*exc)


class TraceRecorder:
    """Collects trace events and writes ``{"traceEvents": [...]}``:
    complete ("X") events for measured spans and counter ("C") events,
    with microsecond timestamps on the profiler's host clock."""

    def __init__(self, engine: str = "sync"):
        self.engine = engine
        self.events: List[dict] = []

    def record(self, name: str, ts_us: float, dur_us: float,
               **args) -> None:
        self.events.append({
            "name": name, "ph": "X", "pid": 0, "tid": 0,
            "ts": ts_us, "dur": max(dur_us, 0.01), "args": args,
        })

    def counters(self, rows: Sequence[dict], ts_us: float) -> None:
        """The drained rows' scalar gauges (:func:`counter_tracks`) as
        counter events, stamped at ``ts_us``: the drain that made the
        rows visible to the host."""
        tracks = counter_tracks()
        for row in rows:
            for name in tracks:
                v = row.get("obs/" + name)
                if v is not None:
                    self.events.append({
                        "name": name, "ph": "C", "pid": 0, "tid": 1,
                        "ts": ts_us, "args": {"value": float(v)}})

    def to_json(self) -> dict:
        return {"traceEvents": list(self.events),
                "displayTimeUnit": "ms",
                "otherData": {"engine": self.engine}}

    def save(self, path: str) -> str:
        with open(path, "w") as f:
            json.dump(self.to_json(), f)
        return path


@contextlib.contextmanager
def profiler_session(profiler_dir: Optional[str]):
    """The ground-truth escape hatch: wrap the run in
    ``jax.profiler.trace`` when a directory is given, else no-op."""
    if profiler_dir:
        with jax.profiler.trace(profiler_dir):
            yield
    else:
        yield
