"""Reduce a profiler trace (``.xplane.pb``) to device numbers.

A TPU device is a plane named ``/device:TPU:<n>``.  Its ``XLA Ops`` and
``Async XLA Ops`` lines hold one event per operation that ran; its
``XLA Modules`` line one event per run of an executable, named
``<module>(<program id>)``.  The host's ``TraceAnnotation``s are events
of the host plane (``/host:CPU``), on the same clock.

* busy: the union of all operation intervals of a device, within the
  traced window; idle = window - busy;
* executable time: per module name, the summed duration of its runs;
* top operations: by summed duration, named by the operation's own name
  (what precedes `` = `` in the HLO text);
* idle gaps: the stretches between operations on device 0, summed by the
  innermost ``chipbench.*`` annotation open on the host at each one's
  middle (``host`` where none is open).
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OP_LINES = ("XLA Ops", "Async XLA Ops")
MODULE_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
ANNOTATION_PREFIX = "chipbench."


@dataclasses.dataclass
class DeviceTrace:
    devices: int
    window_s: float
    busy_s: float                       # mean over devices
    module_s: Dict[str, float]          # mean over devices
    module_runs: Dict[str, int]         # per device (the most any has)
    top_ops: List[Tuple[str, float]]    # mean over devices
    idle_gaps: List[Tuple[str, float]]  # idle seconds by annotation, dev 0

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def find_xplane(trace_dir: str) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    return found[-1] if found else None


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _op_name(hlo: str) -> str:
    return hlo.split(" = ", 1)[0].strip()


def _module_name(name: str) -> str:
    return re.sub(r"\(\d+\)$", "", name)


def reduce(path: str, window: Optional[Tuple[float, float]] = None,
           top: int = 10) -> Optional[DeviceTrace]:
    """Device numbers of the trace at ``path``; None when it holds no TPU
    plane or no operation.  ``window`` (ns, the trace's clock) defaults to
    the span from the first to the last device event or benchmark
    annotation: the profiler runs only while the benchmark's window is
    open, so that span is the traced window."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops: Dict[int, List[Tuple[float, float, str]]] = {}
    mods: Dict[int, List[Tuple[float, float, str]]] = {}
    marks: List[Tuple[float, float, str]] = []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and (line.name in OP_LINES or line.name == MODULE_LINE):
                into = mods if line.name == MODULE_LINE else ops
                into.setdefault(int(m.group(1)), []).extend(
                    (e.start_ns, e.start_ns + e.duration_ns, e.name)
                    for e in line.events)
            elif plane.name == HOST_PLANE:
                marks.extend((e.start_ns, e.start_ns + e.duration_ns, e.name)
                             for e in line.events
                             if e.name.startswith(ANNOTATION_PREFIX))
    ops = {d: v for d, v in ops.items() if v}
    if not ops:
        return None
    if window is None:
        ends = [x for v in list(ops.values()) + list(mods.values())
                for a, b, _ in v for x in (a, b)]
        ends += [x for a, b, _ in marks for x in (a, b)]
        lo, hi = min(ends), max(ends)
    else:
        lo, hi = window
    span = hi - lo
    if span <= 0:
        return None
    busy, op_time, mod_time, runs = [], {}, {}, {}
    gaps: List[Tuple[str, float]] = []
    for d in sorted(ops):
        clipped = [(max(a, lo), min(b, hi)) for a, b, _ in ops[d]
                   if b > lo and a < hi]
        merged = _union(clipped)
        busy.append(sum(b - a for a, b in merged))
        for a, b, name in ops[d]:
            if b > lo and a < hi:
                k = _op_name(name)
                op_time[k] = op_time.get(k, 0.0) + (min(b, hi) - max(a, lo))
        counts: Dict[str, int] = {}
        for a, b, name in mods.get(d, []):
            if b > lo and a < hi:
                k = _module_name(name)
                mod_time[k] = mod_time.get(k, 0.0) + (min(b, hi) - max(a, lo))
                counts[k] = counts.get(k, 0) + 1
        for k, n in counts.items():
            runs[k] = max(runs.get(k, 0), n)
        if d == min(ops):
            edges = [lo] + [x for ab in merged for x in ab] + [hi]
            marks.sort()
            starts = [a for a, _, _ in marks]
            idle: Dict[str, float] = {}
            for a, b in zip(edges[0::2], edges[1::2]):
                if b > a:
                    k = _label(marks, starts, (a + b) / 2)
                    idle[k] = idle.get(k, 0.0) + (b - a)
            gaps = sorted(idle.items(), key=lambda g: -g[1])
    n = len(ops)
    return DeviceTrace(
        devices=n, window_s=span / 1e9, busy_s=sum(busy) / n / 1e9,
        module_s={k: v / n / 1e9 for k, v in mod_time.items()},
        module_runs=runs,
        top_ops=sorted(((k, v / n / 1e9) for k, v in op_time.items()),
                       key=lambda kv: -kv[1])[:top],
        idle_gaps=[(k, v / 1e9) for k, v in gaps[:top]])


def _label(marks, starts, t, lookback: int = 64) -> str:
    """The innermost benchmark annotation open at ``t`` (``marks`` sorted
    by start; annotations nest, so the open ones start shortly before
    ``t``), else ``host``."""
    import bisect
    i = bisect.bisect_right(starts, t)
    best = None
    for a, b, name in marks[max(0, i - lookback):i]:
        if a <= t <= b and (best is None or b - a < best[1] - best[0]):
            best = (a, b, name)
    return best[2] if best else "host"
