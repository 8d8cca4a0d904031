"""Round-trace telemetry (obs layer): counters, traces, monitors, sinks.

:class:`Telemetry` is the one object callers hand to ``fedfits.run`` /
``async_engine.run_async`` / ``pod.run`` / ``run_scenario``.  It owns

  * the **counter registry** switch (``counters=True``): the round
    bodies publish the registered on-device signals as an extra carry
    column + ``obs/`` metric keys (see obs/counters.py) — a pure
    readout, bit-parity preserving;
  * the **trace recorder** (``trace_path=...``): Perfetto trace-event
    JSON with the measured host spans the drivers and the serving loop
    open through ``obs.trace.span`` and the gauges' counter tracks (see
    obs/trace.py), plus ``profiler_dir``, which wraps the run in
    ``jax.profiler.trace``;
  * the **sink stream + drift monitors**: every drained row becomes a
    ``kind="metrics"`` record, every monitor trip a ``kind="warning"``
    record, fanned to the configured sinks (see obs/sinks.py,
    obs/monitors.py).

Everything runs host-side at the existing ``on_chunk`` drain boundary —
telemetry adds zero host syncs and zero device ops that feed the model.
"""
from __future__ import annotations

from typing import Dict, Optional, Sequence

from repro.obs import counters, monitors as monitors_mod, sinks as sinks_mod
from repro.obs.counters import METRIC_PREFIX
from repro.obs.monitors import Monitor, MonitorBank, default_monitors
from repro.obs.sinks import (JsonlSink, MemorySink, MultiSink, Sink,
                             StdoutSink, jsonable)
from repro.obs.trace import (TraceRecorder, annotate, now_us,
                             profiler_session, span)

__all__ = [
    "Telemetry", "Monitor", "MonitorBank", "default_monitors",
    "Sink", "JsonlSink", "MemorySink", "MultiSink", "StdoutSink",
    "TraceRecorder", "annotate", "span", "profiler_session", "jsonable",
    "METRIC_PREFIX", "counters",
]


class Telemetry:
    """Facade wiring counters, traces, sinks and monitors together.

    Construct once per run; the engines route it to the driver and the
    metric drain.  ``engine`` is set by whichever run() consumes it.
    """

    def __init__(self, *,
                 counters: bool = True,
                 sinks: Optional[Sequence[Sink]] = None,
                 monitors: Optional[Sequence[Monitor]] = None,
                 trace_path: Optional[str] = None,
                 profiler_dir: Optional[str] = None,
                 run_name: str = "run"):
        self.counters = counters
        self.sink: Sink = MultiSink(sinks or [])
        self.bank = MonitorBank(monitors)
        self.trace_path = trace_path
        self.profiler_dir = profiler_dir
        self.run_name = run_name
        self.engine: str = "sync"
        self.tracer: Optional[TraceRecorder] = (
            TraceRecorder() if trace_path else None)
        self.rows_seen = 0
        self._open: Dict[str, span] = {}
        self._finished = False

    # -- engine hooks --------------------------------------------------
    def bind_engine(self, engine: str) -> "Telemetry":
        """Called by the consuming run(): names the engine in records
        and in the trace."""
        self.engine = engine
        if self.tracer is not None:
            self.tracer.engine = engine
        return self

    def observe_rows(self, rows: Sequence[dict],
                     window_start_us: Optional[float] = None,
                     window_dur_us: Optional[float] = None) -> None:
        """Drain boundary: one call per chunk (scan), round (python) or
        decode step (serving), with the host window (on :meth:`now_us`'s
        clock) that ended in the drain.  Emits metrics records, runs
        monitors, and — when tracing — stamps the rows' gauges as
        counter events at the drain."""
        rows = list(rows)
        if not rows:
            return
        for row in rows:
            self.rows_seen += 1
            rec = {"kind": "metrics", "engine": self.engine,
                   "run": self.run_name}
            rec.update(jsonable(row))
            self.sink.emit(rec)
            for w in self.bank.observe(row):
                w = dict(w)
                w["engine"] = self.engine
                w["run"] = self.run_name
                self.sink.emit(w)
        if self.tracer is not None:
            drain = (window_start_us + window_dur_us
                     if window_dur_us is not None else now_us())
            self.tracer.counters(rows, drain)

    # host spans opened and closed by name, through obs.trace.span
    def begin(self, name: str, **args) -> None:
        self._open[name] = sp = span(name, self.tracer, **args)
        sp.__enter__()

    def end(self, name: str) -> None:
        sp = self._open.pop(name, None)
        if sp is not None:
            sp.__exit__(None, None, None)

    def now_us(self) -> float:
        return now_us()

    # -- lifecycle -----------------------------------------------------
    def profiled(self):
        """Context manager for the jax.profiler escape hatch."""
        return profiler_session(self.profiler_dir)

    def summary(self) -> dict:
        return {"kind": "summary", "engine": self.engine,
                "run": self.run_name, "rows": self.rows_seen,
                "warnings": self.bank.counts(),
                "n_warnings": len(self.bank.warnings)}

    def finish(self) -> dict:
        """Flush sinks, write the trace file; idempotent."""
        s = self.summary()
        if self._finished:
            return s
        self._finished = True
        self.sink.emit(s)
        if self.tracer is not None and self.trace_path:
            self.tracer.save(self.trace_path)
        self.sink.close()
        return s
