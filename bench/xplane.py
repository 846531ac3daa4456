"""Reduce a JAX profiler trace (``.xplane.pb``) to device time.

A device's busy time is the union of the intervals in which one of its
operations ran (the "XLA Ops" line of each ``/device:TPU:<n>`` plane);
idle gaps are the holes in that union inside the traced window.  Kernel
time is the summed duration of the events that carry the kernel's name.
Host spans (the benchmark's own ``TraceAnnotation``s and JAX's dispatch
events) are on the same clock, so each idle gap is named by the host span
that overlaps it most.

The reduction works on plain ``(name, start_ns, duration_ns)`` tuples;
:func:`load` is the only part that reads the file.
"""

from __future__ import annotations

import glob
import os
from typing import Dict, Iterable, List, Sequence, Tuple

Event = Tuple[str, float, float]          # (name, start_ns, duration_ns)

OPS_LINE = "XLA Ops"
CONTAINERS = ("while", "conditional", "call")  # ops that hold other ops
BENCH_SPAN = "bench."                     # prefix of the benchmark's spans


def find(trace_dir: str) -> str:
    """The one ``.xplane.pb`` under a ``jax.profiler`` output directory."""
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {trace_dir}, "
                           f"found {len(files)}")
    return files[0]


def op_name(text: str) -> str:
    """The HLO instruction's name without its ".N" suffix:
    "%pbit_brick_update_int.8 = (s8[...]) custom-call(...)" ->
    "pbit_brick_update_int"."""
    head = text.split(" = ", 1)[0].lstrip("%")
    base, _, num = head.rpartition(".")
    return base if base and num.isdigit() else head


def load(path: str) -> Tuple[Dict[str, List[Event]], List[Event]]:
    """(device ops by plane name, host spans) of one trace file; device
    ops are named by :func:`op_name`."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices: Dict[str, List[Event]] = {}
    host: List[Event] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            evs = devices.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name == OPS_LINE:
                    evs.extend((op_name(e.name), e.start_ns,
                                e.duration_ns) for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, e.start_ns, e.duration_ns)
                            for e in line.events)
    return devices, host


def union(events: Iterable[Event]) -> List[Tuple[float, float]]:
    """Sorted, disjoint (start, end) intervals covered by the events."""
    out: List[List[float]] = []
    for _, t, d in sorted(events, key=lambda e: e[1]):
        if out and t <= out[-1][1]:
            out[-1][1] = max(out[-1][1], t + d)
        else:
            out.append([t, t + d])
    return [(a, b) for a, b in out]


def busy_ns(events: Iterable[Event]) -> float:
    return sum(b - a for a, b in union(events))


def gaps(events: Sequence[Event], t0: float, t1: float
         ) -> List[Tuple[float, float]]:
    """Idle (start, end) intervals of [t0, t1] outside every event."""
    out, cur = [], t0
    for a, b in union(events):
        if a > cur:
            out.append((cur, min(a, t1)))
        cur = max(cur, b)
        if cur >= t1:
            break
    if cur < t1:
        out.append((cur, t1))
    return [(a, b) for a, b in out if b > a]


def per_name(events: Iterable[Event]) -> Dict[str, Tuple[int, float]]:
    """name -> (launches, summed duration ns)."""
    out: Dict[str, Tuple[int, float]] = {}
    for name, _, d in events:
        n, s = out.get(name, (0, 0.0))
        out[name] = (n + 1, s + d)
    return out


def name_gap(gap: Tuple[float, float], host: Sequence[Event]) -> str:
    """The host span that overlaps ``gap`` most, the benchmark's own
    spans first; "host idle" when none does."""
    a, b = gap
    best, best_ov = "host idle", 0.0
    for prefer in (True, False):
        for name, t, d in host:
            if name.startswith(BENCH_SPAN) != prefer:
                continue
            ov = min(b, t + d) - max(a, t)
            if ov > best_ov:
                best, best_ov = name, ov
        if best_ov > 0:
            break
    return best
