"""Helpers the idle readers share: the device-idle stretches of a traced
run on the program's clock, and interval arithmetic.

The stretches are those of device 0 between operations, within the
traced window, from the same op union and the same window as
``trace_reduce.reduce``.  The program pins its span clock
(``time.perf_counter_ns``) to the profiler's with clock anchors: host
annotations named ``scda.clock`` whose ``t_ns`` stat is the program's
timestamp.  Each stretch is mapped through them, by interpolation
between neighbouring anchors, onto the clock of ``m.spans``.  A program
that emits no anchors gives nothing to read.

A reader gets the run's reduced trace (``m.device``), not its path: the
file is the one a run leaves in the checkout's fixed work directory
(``harness.WORKDIR_NAME``, ``drive.Run``'s ``trace``), or under
``m.trace_dir`` where an input names one.  It is read only when its
window is the one ``m.device`` reports, so a trace left by another run
gives nothing.
"""
import bisect
import math
import os

from chipbench import harness, trace_reduce

CLOCK_ANCHOR = "scda.clock"
_UNREAD = object()
union = trace_reduce._union


def intervals(m):
    """Device-idle ``(t0, t1)`` stretches in ``perf_counter`` seconds, or
    None where the run has no device trace, or no clock anchors.  Read
    once and kept on ``m`` as ``m.device_idle``."""
    got = getattr(m, "device_idle", _UNREAD)
    if got is _UNREAD:
        got = m.device_idle = _own(m)
    return got


def trace_dir(m):
    return getattr(m, "trace_dir", None) or os.path.join(
        harness.ROOT, harness.WORKDIR_NAME, "trace")


def _own(m):
    dt = getattr(m, "device", None)
    path = trace_reduce.find_xplane(trace_dir(m)) if dt else None
    got = read(path) if path else None
    if got is None or not math.isclose(got[0], dt.window_s, rel_tol=1e-9):
        return None
    return got[1]


def read(path):
    """``(window_s, stretches)`` of the trace at ``path``: its window's
    length and its device-idle stretches on the program's clock
    (seconds); or None (no TPU operation, or no clock anchor)."""
    from jax.profiler import ProfileData
    ops, ends, anchors = {}, [], []
    for plane in ProfileData.from_file(path).planes:
        dev = trace_reduce.DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if dev and line.name in trace_reduce.OP_LINES:
                ev = [(e.start_ns, e.start_ns + e.duration_ns)
                      for e in line.events]
                ops.setdefault(int(dev.group(1)), []).extend(ev)
                ends += [x for ab in ev for x in ab]
            elif dev and line.name == trace_reduce.MODULE_LINE:
                ends += [x for e in line.events
                         for x in (e.start_ns, e.start_ns + e.duration_ns)]
            elif plane.name == trace_reduce.HOST_PLANE:
                for e in line.events:
                    if e.name.startswith(trace_reduce.ANNOTATION_PREFIX):
                        ends += [e.start_ns, e.start_ns + e.duration_ns]
                    elif e.name == CLOCK_ANCHOR:
                        t = dict(e.stats).get("t_ns")
                        if t is not None:
                            anchors.append((float(e.start_ns), float(t)))
    ops = {d: v for d, v in ops.items() if v}
    if not ops or not anchors:
        return None
    lo, hi = min(ends), max(ends)
    merged = union([(max(a, lo), min(b, hi)) for a, b in ops[min(ops)]
                    if b > lo and a < hi])
    edges = [lo] + [x for ab in merged for x in ab] + [hi]
    anchors.sort()
    return (hi - lo) / 1e9, [(_map(a, anchors) / 1e9, _map(b, anchors) / 1e9)
                             for a, b in zip(edges[0::2], edges[1::2])
                             if b > a]


def _map(t, anchors):
    """Trace ns -> program ns: interpolated between the neighbouring
    anchors, shifted by the nearest anchor's offset outside them."""
    i = bisect.bisect_right(anchors, (t, float("inf")))
    if 0 < i < len(anchors):
        (t0, p0), (t1, p1) = anchors[i - 1], anchors[i]
        if t1 > t0:
            return p0 + (t - t0) * (p1 - p0) / (t1 - t0)
    t0, p0 = anchors[min(max(i - 1, 0), len(anchors) - 1)]
    return p0 + (t - t0)


def minus(ivs, cut):
    """The parts of ``ivs`` outside every interval of ``cut``."""
    out = []
    cut = union(cut)
    for a, b in union(ivs):
        for c, d in cut:
            if d <= a or c >= b:
                continue
            if c > a:
                out.append((a, c))
            a = max(a, d)
            if a >= b:
                break
        if a < b:
            out.append((a, b))
    return out


def overlap(ivs, within):
    """Seconds of ``ivs`` that lie inside ``within``."""
    ivs, within = union(ivs), union(within)
    total = 0.0
    for a, b in ivs:
        for c, d in within:
            total += max(0.0, min(b, d) - max(a, c))
    return total


def total(ivs):
    return sum(b - a for a, b in union(ivs))
