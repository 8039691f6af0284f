"""Share of the traced window in which the caller is inside a serving call
(a ``gp.mean_var`` span of ``GP.mean_var``, a ``repro.obs.span``, wholly
inside the window) and no op runs on the device: the spans' time minus
the union of the device's op intervals within them, summed, over the
window, averaged over the chips that ran any op.  A part of
``device_idle_pct.predict``: the idle time the call's own host work
leaves, as against the caller's wait between calls."""
import bisect

from bench.lib import trace

SPAN = "gp.mean_var"


def _merged(intervals):
    """Disjoint, sorted [start, end] intervals covering ``intervals``."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def read(run):
    tr = run.trace
    if tr is None or not tr.ops:
        return None
    spans = _merged((e.start_ns, e.end_ns) for e in trace.in_window(
        tr, [e for e in tr.host if e.name == SPAN]))
    if not spans:
        return None
    per = []
    for evs in tr.ops.values():
        if not evs:
            continue
        busy = _merged((e.start_ns, e.end_ns) for e in evs)
        starts = [b[0] for b in busy]
        ends = [b[1] for b in busy]
        idle = 0.0
        for s, e in spans:
            # the busy intervals that end after s and start before e
            lo = bisect.bisect_right(ends, s)
            hi = bisect.bisect_left(starts, e)
            idle += (e - s) - trace.union_ns(busy[lo:hi], s, e)
        per.append(idle)
    if not per:
        return None
    return 100.0 * sum(per) / len(per) / (tr.t1_ns - tr.t0_ns)
