"""Reduce a profiler trace (``.xplane.pb``) to device metrics.

Planes of a TPU trace: ``/device:TPU:<n>`` for each chip, whose ``XLA Ops``
line holds one event per operation that ran on it, and ``/host:CPU``, whose
threads hold the host spans (``jax.profiler.TraceAnnotation``). All event
times are in nanoseconds on one clock. The traced window is the host span
named ``WINDOW``, which the harness opens around its measured loop.

* busy: the union of the intervals in which an operation ran on a chip,
  clipped to the window; averaged over the chips used;
* idle share: 1 - busy / window;
* exposed all-to-all: time in which an all-to-all ran on a chip while no
  other operation did, over the window; averaged over the chips;
* idle gaps: the device's idle intervals (on the first chip), each charged
  to the innermost harness span (name starting ``bench.``) that covers its
  middle, or to ``(none)``.
"""

from __future__ import annotations

import collections
import dataclasses
import glob
import os
from typing import Dict, List, Optional, Tuple

WINDOW = "bench.window"
OPS_LINE = "XLA Ops"
DEVICE_PREFIX = "/device:TPU:"


@dataclasses.dataclass
class Event:
    name: str
    start: float  # ns
    end: float    # ns


@dataclasses.dataclass
class Trace:
    window: Tuple[float, float]
    devices: Dict[str, List[Event]]   # plane name -> ops
    host_spans: List[Event]


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float                     # mean over chips
    idle_share: float                 # 1 - busy_s / window_s
    a2a_exposed_s: Optional[float]    # mean over chips; None: no all-to-all
    device_ops: List[Tuple[str, float]]
    idle_gaps: List[Tuple[str, float]]


def op_name(hlo: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def find_xplane(trace_dir: str) -> Optional[str]:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return max(paths, key=os.path.getmtime) if paths else None


def load(path: str) -> Trace:
    """Read the events the reduction needs from an ``.xplane.pb`` file."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, host, window = {}, [], None
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            ops = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops += [Event(op_name(e.name), e.start_ns, e.start_ns
                                  + e.duration_ns) for e in line.events]
            devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        ev = Event(e.name, e.start_ns,
                                   e.start_ns + e.duration_ns)
                        if e.name == WINDOW:
                            window = (ev.start, ev.end)
                        else:
                            host.append(ev)
    if window is None:
        raise ValueError(f"no {WINDOW} span in {path}")
    return Trace(window, devices, host)


def union(intervals, lo: float, hi: float) -> List[Tuple[float, float]]:
    """Merged intervals, clipped to [lo, hi]."""
    out: List[List[float]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def total(intervals) -> float:
    return sum(e - s for s, e in intervals)


def gaps(busy, lo: float, hi: float) -> List[Tuple[float, float]]:
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def is_all_to_all(name: str) -> bool:
    n = name.lower()
    return "all-to-all" in n or "alltoall" in n or "all_to_all" in n


def overlap(a, b) -> float:
    """Total overlap of two merged, sorted interval lists."""
    i = j = 0
    t = 0.0
    while i < len(a) and j < len(b):
        t += max(0.0, min(a[i][1], b[j][1]) - max(a[i][0], b[j][0]))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return t


def exposed(a2a, others, lo: float, hi: float) -> float:
    """Time inside the a2a intervals that no other interval covers."""
    mine = union(a2a, lo, hi)
    return total(mine) - overlap(mine, union(others, lo, hi))


def charge(gap_list, spans: List[Event]) -> Dict[str, float]:
    """Sum each gap into the innermost host span covering its middle."""
    out: Dict[str, float] = collections.defaultdict(float)
    spans = sorted(spans, key=lambda sp: sp.start)
    active: List[Event] = []
    k = 0
    for s, e in sorted(gap_list, key=lambda g: g[0] + g[1]):
        mid = (s + e) / 2
        while k < len(spans) and spans[k].start <= mid:
            active.append(spans[k])
            k += 1
        active = [sp for sp in active if sp.end >= mid]
        best = min(active, key=lambda sp: sp.end - sp.start, default=None)
        out[best.name if best else "(none)"] += e - s
    return out


def summarize(tr: Trace, top: int = 10) -> Optional[Summary]:
    """None where the trace holds no TPU plane (a run off the chip)."""
    lo, hi = tr.window
    win = hi - lo
    if not tr.devices:
        return None
    busy_each, a2a_each = [], []
    op_time: Dict[str, float] = collections.defaultdict(float)
    first = sorted(tr.devices)[0]
    first_busy = []
    for plane in sorted(tr.devices):
        ops = tr.devices[plane]
        busy = union([(o.start, o.end) for o in ops], lo, hi)
        busy_each.append(total(busy))
        a2a = [(o.start, o.end) for o in ops if is_all_to_all(o.name)]
        if a2a:
            rest = [(o.start, o.end) for o in ops
                    if not is_all_to_all(o.name)]
            a2a_each.append(exposed(a2a, rest, lo, hi))
        for o in ops:
            d = min(o.end, hi) - max(o.start, lo)
            if d > 0:
                op_time[o.name] += d / len(tr.devices)
        if plane == first:
            first_busy = busy
    busy_s = sum(busy_each) / len(busy_each) / 1e9
    idle = charge(gaps(first_busy, lo, hi), tr.host_spans)
    return Summary(
        window_s=win / 1e9,
        busy_s=busy_s,
        idle_share=1.0 - busy_s / (win / 1e9),
        a2a_exposed_s=(sum(a2a_each) / len(tr.devices) / 1e9
                       if a2a_each else None),
        device_ops=sorted(((k, v / 1e9) for k, v in op_time.items()),
                          key=lambda kv: -kv[1])[:top],
        idle_gaps=sorted(((k, v / 1e9) for k, v in idle.items()),
                         key=lambda kv: -kv[1])[:top])
