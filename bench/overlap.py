"""The program's host spans in a reduced trace: the stretches of the window
that spans of given names cover, and how much of the device's idle time
lies inside them.  A span name the window does not hold gives ``None``, so
that a program without the span reads nothing."""
from __future__ import annotations


def covered(trace, names):
    """Merged ``[start, end)`` stretches (ns, clipped to the window) that
    the host spans named in ``names`` cover; ``None`` where the window
    holds no such span."""
    iv = sorted((max(s, trace.t0), min(s + d, trace.t1))
                for s, d, n in trace.host if n in names)
    if not iv:
        return None
    out = []
    for s, e in iv:
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def length_s(stretches):
    return sum(e - s for s, e in stretches) / 1e9


def idle_inside_s(trace, stretches):
    """Seconds of the device's idle gaps (``trace.idle_gaps()``) that lie
    inside the stretches; both lists are sorted and disjoint."""
    gaps, tot, j = trace.idle_gaps(), 0, 0
    for gs, ge in gaps:
        while j < len(stretches) and stretches[j][1] <= gs:
            j += 1
        k = j
        while k < len(stretches) and stretches[k][0] < ge:
            tot += min(ge, stretches[k][1]) - max(gs, stretches[k][0])
            k += 1
    return tot / 1e9
