"""Reduction of one traced window (a JAX profiler ``.xplane.pb``) to what the
per-layer metrics and the ``breakdown`` read.

Device planes are ``/device:TPU:<n>``.  On each, the ``XLA Ops`` line holds
one event per operation that ran (a fusion, a copy, a Pallas kernel as a
``custom-call``), with its HLO text, category and the name scope path of
the JAX code that made it in its stats; the ``XLA Modules`` line holds one
event per run of a compiled program (``jit_<name>(<id>)``).  The window's
bounds are the two ``bench.window.*`` markers that ``bench/common.Window``
leaves on the host timeline; every interval is clipped to them.

Busy time is the union of the operation intervals (control-flow ops, whose
events span their whole body, are left out); idle gaps are the
stretches of the window that no operation covers, each named by the host
event, outside the profiler's own, that overlaps it most.
"""
from __future__ import annotations

import bisect
import dataclasses
import re
from pathlib import Path
from typing import Dict, List, Tuple

OPEN, CLOSE = "bench.window.open", "bench.window.close"
_DEVICE = re.compile(r"^/device:TPU:\d+$")
_PROGRAM = re.compile(r"^(?:jit_)?(.*?)(?:\(\d+\))?$")
_SCOPE_STATS = ("tf_op", "name_scope", "long_name")
# control-flow ops whose events span their whole body, idle stretches
# inside it included: not operations of their own
_CONTAINERS = ("while", "conditional", "call")


@dataclasses.dataclass
class Op:
    name: str           # the op's display name (e.g. "fusion.12")
    start: int          # ns, host clock
    dur: int            # ns, clipped to the window
    scope: str          # the name-scope path the op carries ("" if none)
    category: str       # XLA's hlo_category stat ("" if none)
    program: str        # the compiled program it ran in ("" if unknown)
    device: int
    custom_call: str = ""   # the custom-call target of a kernel call


_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def xplane_pb2():
    """The XSpace protobuf classes, loaded from the generated module that
    the installed TensorFlow package carries (only ``google.protobuf`` is
    imported; TensorFlow itself is not)."""
    import importlib.util

    spec = importlib.util.find_spec("tensorflow")
    if spec is None or not spec.submodule_search_locations:
        raise ImportError("reading a trace needs the XSpace protobuf "
                          "module of the installed tensorflow package")
    path = Path(spec.submodule_search_locations[0]) / "tsl" / "profiler" \
        / "protobuf" / "xplane_pb2.py"
    mod_spec = importlib.util.spec_from_file_location("bench_xplane_pb2",
                                                      path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def _op_meta(plane):
    """metadata id -> (display name, name-scope path, HLO category,
    custom-call target) of a device plane's op events."""
    stat_names = {k: m.name for k, m in plane.stat_metadata.items()}
    out = {}
    for mid, md in plane.event_metadata.items():
        st = {}
        for s in md.stats:
            key = stat_names.get(s.metadata_id)
            if key in _SCOPE_STATS or key == "hlo_category":
                st[key] = (stat_names.get(s.ref_value, "")
                           if s.HasField("ref_value") else s.str_value)
        scope = next((st[k] for k in _SCOPE_STATS if st.get(k)), "")
        m = _TARGET.search(md.name)
        out[mid] = (md.display_name or md.name, scope,
                    st.get("hlo_category", ""), m.group(1) if m else "")
    return out


def program_name(module_event_name: str) -> str:
    """``jit_decode(123)`` -> ``decode``."""
    return _PROGRAM.match(module_event_name).group(1)


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


class Trace:
    """One traced window, reduced."""

    def __init__(self, ops: List[Op], window: Tuple[int, int],
                 host: List[Tuple[int, int, str]], n_devices: int,
                 programs: List[Tuple[str, int, int]]):
        self.ops = ops
        self.t0, self.t1 = window
        self.host = host
        self.n_devices = max(n_devices, 1)
        self.programs = programs
        self.window_s = (self.t1 - self.t0) / 1e9
        per_dev = {}
        for op in ops:
            per_dev.setdefault(op.device, []).append(
                (op.start, op.start + op.dur))
        self._busy = {d: _union(iv) for d, iv in per_dev.items()}
        busy = sum(e - s for iv in self._busy.values() for s, e in iv)
        self.busy_s = busy / 1e9 / self.n_devices

    # ------------------------------------------------------------- loading
    @classmethod
    def load(cls, trace_dir) -> "Trace":
        files = sorted(Path(trace_dir).glob("plugins/profile/*/*.xplane.pb"))
        if not files:
            raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
        space = xplane_pb2().XSpace()
        space.ParseFromString(files[-1].read_bytes())
        return cls.from_space(space)

    @classmethod
    def from_space(cls, space) -> "Trace":
        host_events, marks, device_planes = [], {}, []
        for plane in space.planes:
            if _DEVICE.match(plane.name):
                device_planes.append(plane)
            elif plane.name.startswith("/host:"):
                names = {k: m.name for k, m in plane.event_metadata.items()}
                for line in plane.lines:
                    base = line.timestamp_ns * 1000
                    for ev in line.events:
                        name = names.get(ev.metadata_id, "")
                        s = (base + ev.offset_ps) // 1000
                        if name in (OPEN, CLOSE):
                            marks[name] = s
                        else:
                            host_events.append((s, ev.duration_ps // 1000,
                                                name))
        if OPEN not in marks or CLOSE not in marks:
            raise ValueError("the trace holds no window markers")
        t0, t1 = marks[OPEN], marks[CLOSE]
        ops, programs = [], []
        for di, plane in enumerate(device_planes):
            meta = _op_meta(plane)
            lines = {line.name: line for line in plane.lines}
            mods = []
            if "XLA Modules" in lines:
                line = lines["XLA Modules"]
                base = line.timestamp_ns * 1000
                for ev in line.events:
                    s = (base + ev.offset_ps) // 1000
                    e = s + ev.duration_ps // 1000
                    if e > t0 and s < t1:
                        mods.append((s, e, program_name(
                            plane.event_metadata[ev.metadata_id].name)))
            mods.sort()
            programs.extend((n, s, e) for s, e, n in mods
                            if s >= t0 and e <= t1)
            starts = [m[0] for m in mods]
            if "XLA Ops" not in lines:
                continue
            line = lines["XLA Ops"]
            base = line.timestamp_ns * 1000
            for ev in line.events:
                s = (base + ev.offset_ps) // 1000
                e = s + ev.duration_ps // 1000
                if e <= t0 or s >= t1:
                    continue
                name, scope, cat, hlo = meta[ev.metadata_id]
                if cat in _CONTAINERS:
                    continue
                i = bisect.bisect_right(starts, s) - 1
                prog = mods[i][2] if i >= 0 and mods[i][1] >= s else ""
                s, e = max(s, t0), min(e, t1)
                ops.append(Op(name, s, e - s, scope, cat, prog, di, hlo))
        host = [(s, d, n) for s, d, n in host_events
                if s + d > t0 and s < t1]
        return cls(ops, (t0, t1), host, len(device_planes), programs)

    # ------------------------------------------------------------- queries
    def device_time(self, pred) -> float:
        """Seconds of device time of the ops ``pred(op)`` selects, summed
        over the devices (kernel time; overlapping ops both count)."""
        return sum(op.dur for op in self.ops if pred(op)) / 1e9

    def scope_time(self, scope: str) -> float:
        """Device seconds of ops whose name-scope path holds ``scope`` as
        one of its components."""
        pat = re.compile(rf"(^|/){re.escape(scope)}(/|$)")
        return self.device_time(lambda op: bool(pat.search(op.scope)))

    def program_runs(self, name: str) -> List[float]:
        """Device seconds of each run of the compiled program ``name`` that
        lies wholly inside the window."""
        return [(e - s) / 1e9 for n, s, e in self.programs if n == name]

    def has_scopes(self) -> bool:
        return any(op.scope for op in self.ops)

    def top_ops(self, n=10):
        tot = {}
        for op in self.ops:
            tot[op.name] = tot.get(op.name, 0) + op.dur
        return [[k, v / 1e9] for k, v in
                sorted(tot.items(), key=lambda kv: -kv[1])[:n]]

    def idle_gaps(self) -> List[Tuple[int, int]]:
        """Stretches of the window no op covers on the first device."""
        if not self._busy:
            return [(self.t0, self.t1)]
        iv = self._busy[min(self._busy)]
        gaps, at = [], self.t0
        for s, e in iv:
            if s > at:
                gaps.append((at, s))
            at = max(at, e)
        if at < self.t1:
            gaps.append((at, self.t1))
        return gaps

    def idle_by_host(self, n=10):
        """Idle device seconds summed by the host event that overlaps each
        gap most (``"(no host event)"`` where none does), largest first.
        One sweep over the gaps and the host events, both in time order."""
        import heapq

        skip = ("ProfilerSession", "bench.window")
        ev = sorted((s, s + d, nm) for s, d, nm in self.host
                    if not nm.startswith(skip))
        tot: Dict[str, int] = {}
        active, k = [], 0           # heap of (end, start, name)
        for gs, ge in self.idle_gaps():
            while k < len(ev) and ev[k][0] < ge:
                heapq.heappush(active, (ev[k][1], ev[k][0], ev[k][2]))
                k += 1
            while active and active[0][0] <= gs:
                heapq.heappop(active)
            best, name = 0, "(no host event)"
            for e, s, nm in active:
                ov = min(ge, e) - max(gs, s)
                if ov > best:
                    best, name = ov, nm
            tot[name] = tot.get(name, 0) + (ge - gs)
        return [[k_, v / 1e9] for k_, v in
                sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


@dataclasses.dataclass
class LayerInput:
    """What a per-layer metric reader is handed: the reduced trace, the
    engine's counts of the same window, the configuration's work
    functions, the chip's peaks and the cell's files."""
    trace: Trace
    window: dict
    work: object
    peaks: dict
    config: dict
    traffic: dict
