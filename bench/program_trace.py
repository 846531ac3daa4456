#!/usr/bin/env python3
"""Reduce a JAX profiler trace by the program's own names.

    python3 bench/program_trace.py <jax.profiler output directory>

prints one JSON object: device seconds per program and where the device
idled, by the program's own spans.  Two reductions, beside those of
``xplane`` (which this reuses and leaves as it is):

* the "XLA Modules" line of each ``/device:TPU:<n>`` plane gives each
  program's device time under its jitted function's name
  ("jit_lattice_energy(123...)" -> "lattice_energy");
* the program's own host spans (dotted lower-case names such as
  ``cursor.readout``, other than the benchmark's ``bench.*``) take each
  piece of an idle interval: the innermost one open over it, else
  "outside program" (``idle_by_span``); ``idle_in_span`` is the idle
  under each span name, and under each family ``<layer>.*``, nested
  spans included.

Idle is taken over a window: the host span :data:`WINDOW_SPAN` where the
trace has one (so the idle before the first operation, an anneal's
start, counts too), else each device's first to last operation.  Seconds
are per device.  The reductions work on plain ``(name, start_ns,
duration_ns)`` tuples; :func:`load` is the only part that reads the file.
"""

from __future__ import annotations

import json
import re
import sys
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import xplane
from xplane import Event

MODULES_LINE = "XLA Modules"
PROGRAM_SPAN = re.compile(r"[a-z][a-z0-9_]*(\.[a-z][a-z0-9_]*)+")
OUTSIDE = "outside program"
WINDOW_SPAN = "bench.window"


def program_name(text: str) -> str:
    """A module of the "XLA Modules" line by its jitted function's name:
    "jit_lattice_energy(7505623244848524224)" -> "lattice_energy"."""
    name = re.sub(r"\(\d+\)$", "", text)
    return name[len("jit_"):] if name.startswith("jit_") else name


def load(path: str) -> Tuple[Dict[str, List[Event]],
                             Dict[str, List[Event]], List[Event]]:
    """(device ops by plane name, device modules by plane name, host
    spans) of one trace file: ``xplane.load``'s two, and the modules
    named by :func:`program_name`."""
    from jax.profiler import ProfileData
    devices, host = xplane.load(path)
    modules = {plane.name: [(program_name(e.name), e.start_ns, e.duration_ns)
                            for line in plane.lines
                            if line.name == MODULES_LINE
                            for e in line.events]
               for plane in ProfileData.from_file(path).planes
               if plane.name.startswith("/device:TPU:")}
    return devices, modules, host


def traced_window(host: Iterable[Event]) -> Optional[Tuple[float, float]]:
    """(start, end) of the :data:`WINDOW_SPAN` span, or None."""
    for name, t, d in host:
        if name == WINDOW_SPAN:
            return t, t + d
    return None


def program_spans(host: Iterable[Event]) -> List[Event]:
    """The program's own spans among the host events."""
    return [e for e in host if e[2] > 0
            and not e[0].startswith(xplane.BENCH_SPAN)
            and PROGRAM_SPAN.fullmatch(e[0])]


def idle_by_span(idle: Sequence[Tuple[float, float]],
                 spans: Sequence[Event]) -> Dict[str, float]:
    """Idle ns under each innermost program span (the latest to open of
    those open), and under none as :data:`OUTSIDE`.  A gap that straddles
    spans is split where they open and close; the buckets sum to the
    idle time."""
    edges = sorted([(t, 1, i) for i, (_, t, _) in enumerate(spans)] +
                   [(t + d, 0, i) for i, (_, t, d) in enumerate(spans)])
    open_spans: Dict[int, Event] = {}
    out: Dict[str, float] = {}
    j = 0

    def take(upto: float) -> None:
        nonlocal j
        while j < len(edges) and edges[j][0] <= upto:
            _, opens, i = edges[j]
            if opens:
                open_spans[i] = spans[i]
            else:
                open_spans.pop(i, None)
            j += 1

    for a, b in sorted(idle):
        take(a)
        cur = a
        while cur < b:
            nxt = min(b, edges[j][0]) if j < len(edges) else b
            inner = max(open_spans.values(), key=lambda e: (e[1], -e[2]),
                        default=None)
            name = OUTSIDE if inner is None else inner[0]
            out[name] = out.get(name, 0.0) + (nxt - cur)
            cur = nxt
            if cur < b:
                take(cur)
    return out


def idle_within(idle: Sequence[Tuple[float, float]],
                spans: Iterable[Event]) -> float:
    """Idle ns covered by the union of ``spans`` (nested or not)."""
    cover = xplane.union(spans)
    total, k = 0.0, 0
    for a, b in sorted(idle):
        while k < len(cover) and cover[k][1] <= a:
            k += 1
        m = k
        while m < len(cover) and cover[m][0] < b:
            total += min(b, cover[m][1]) - max(a, cover[m][0])
            m += 1
    return total


def reduce(devices: dict, modules: dict, host: list) -> dict:
    """Device seconds per program (``programs``: name -> [launches, s]);
    the idle seconds of the window (``idle_s``; ``idle_between_ops_s``,
    each device's first to last operation, in any case), split by
    innermost program span (``idle_by_span``) and summed under each span
    name and family (``idle_in_span``)."""
    window = traced_window(host)
    spans = program_spans(host)
    names: Dict[str, List[Event]] = {}
    for e in spans:
        names.setdefault(e[0], []).append(e)
        names.setdefault(e[0].split(".")[0] + ".*", []).append(e)
    by_span: Dict[str, float] = {}
    within = dict.fromkeys(names, 0.0)
    idle = between_ops = 0.0
    n_dev = 0
    for evs in devices.values():
        if not evs:
            continue
        n_dev += 1
        t0 = min(t for _, t, _ in evs)
        t1 = max(t + d for _, t, d in evs)
        between_ops += sum(b - a for a, b in xplane.gaps(evs, t0, t1))
        g = xplane.gaps(evs, *(window or (t0, t1)))
        idle += sum(b - a for a, b in g)
        for name, ns in idle_by_span(g, spans).items():
            by_span[name] = by_span.get(name, 0.0) + ns
        for name, evs_n in names.items():
            within[name] += idle_within(g, evs_n)
    per = 1e9 * max(n_dev, 1)
    programs = xplane.per_name(e for evs in modules.values() for e in evs)
    return {
        "programs": {n: [k, ns / 1e9] for n, (k, ns) in programs.items()},
        "idle_s": idle / per,
        "idle_between_ops_s": between_ops / per,
        "idle_by_span": {n: ns / per for n, ns in sorted(
            by_span.items(), key=lambda kv: -kv[1])},
        "idle_in_span": {n: ns / per for n, ns in within.items()},
    }


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 1:
        print(__doc__.split("\n\n")[1], file=sys.stderr)
        return 2
    print(json.dumps(reduce(*load(xplane.find(argv[0])))))
    return 0


if __name__ == "__main__":
    sys.exit(main())
