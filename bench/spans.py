"""The program's host spans in a profiler trace, on the device's clock.

The engine marks each host phase of a tick with a ``TraceAnnotation``
named ``ocl.<phase>`` (``core/batched.py``), whose arguments the profiler
keeps as event stats (``tick``, ``level``, ``rows``, ``tokens``, ...).
``xplane.py`` reads the benchmark's own ``bench.*`` spans and the device
planes; this module reads the trace again for the program's spans and
puts every stretch of the traced window in which the chip is idle on the
host phase that held it:

- ``idle_by_span``: each idle stretch split exactly across the innermost
  span (of either prefix) over each part: the shortest covering span, the
  first read of equal ones; ``host:none`` where no span covers it.
  Per chip, averaged over chips as ``xplane``'s ``busy_s`` is;
- ``idle_gaps``: the ten longest idle stretches, each named by the
  innermost span at its middle;
- ``program_spans``: the ``ocl.*`` spans that start inside the window,
  as ``(name, start_s, end_s, args)`` from the window's start.

A trace of a program without these spans gives no ``program_spans``, and
the metrics that read them read nothing.
"""
from __future__ import annotations

import bisect
import heapq
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Optional, Tuple

import xplane

PROGRAM_PREFIX = "ocl."
_SPAN_PREFIXES = ("bench.", PROGRAM_PREFIX)

Span = Tuple[int, int, str, dict]       # start_ns, end_ns, name, arguments


def read(path) -> Tuple[list, List[Span]]:
    """``(planes, spans)``: the planes in ``xplane.reduce_planes``' input
    shape, and every ``bench.*``/``ocl.*`` host span with its stats."""
    from jax.profiler import ProfileData
    planes, spans = [], []
    for plane in ProfileData.from_file(str(path)).planes:
        host = plane.name.startswith("/host:")
        lines = []
        for line in plane.lines:
            evs = [(ev.name, int(ev.start_ns), int(ev.duration_ns))
                   for ev in line.events]
            if host:
                spans += [(s, s + d, n, dict(ev.stats))
                          for (n, s, d), ev in zip(evs, line.events)
                          if n.startswith(_SPAN_PREFIXES)]
            lines.append((line.name, evs))
        planes.append((plane.name, lines))
    return planes, spans


def _innermost(spans: List[Span]) -> List[Tuple[int, int, str]]:
    """The host timeline cut into ``(start, end, name)`` pieces, each
    named by the innermost span over it (``bench.window`` is no span
    here)."""
    inner = sorted((x for x in spans if x[2] != xplane.WINDOW_SPAN),
                   key=lambda x: x[1] - x[0])
    by_start = sorted(range(len(inner)), key=lambda k: inner[k][0])
    bounds = sorted({t for x in inner for t in x[:2]})
    heap: List[Tuple[int, int]] = []       # (rank by length, end)
    pieces: List[list] = []
    j = 0
    for a, b in zip(bounds, bounds[1:]):
        while j < len(by_start) and inner[by_start[j]][0] <= a:
            heapq.heappush(heap, (by_start[j], inner[by_start[j]][1]))
            j += 1
        while heap and heap[0][1] <= a:
            heapq.heappop(heap)
        if not heap:
            continue
        name = inner[heap[0][0]][2]
        if pieces and pieces[-1][1] == a and pieces[-1][2] == name:
            pieces[-1][1] = b
        else:
            pieces.append([a, b, name])
    return [(a, b, n) for a, b, n in pieces]


def _idle_gaps(planes, w0: int, w1: int) -> Tuple[List[Tuple[int, int]],
                                                  int, int]:
    """The window's idle stretches on every chip, the chips' summed busy
    nanoseconds and the chip count, as ``xplane.reduce_planes`` finds
    busy time: the union of the "XLA Ops" intervals inside the window."""
    gaps: List[Tuple[int, int]] = []
    busy_ns, n_dev = 0, 0
    for pname, lines in planes:
        if not pname.startswith("/device:TPU:"):
            continue
        n_dev += 1
        ivs = [(max(s, w0), min(s + d, w1)) for lname, evs in lines
               if lname in xplane._OP_LINES for _, s, d in evs
               if min(s + d, w1) > max(s, w0)]
        prev = w0
        for s, e in xplane._union(ivs):
            busy_ns += e - s
            if s > prev:
                gaps.append((prev, s))
            prev = e
        if w1 > prev:
            gaps.append((prev, w1))
    return gaps, busy_ns, n_dev


def reduce(planes, spans: List[Span]) -> Optional[dict]:
    """Put the window's idle time on the host spans; None without a
    ``bench.window`` span."""
    win = [(s, e) for s, e, n, _ in spans if n == xplane.WINDOW_SPAN]
    if not win:
        return None
    w0, w1 = win[0]
    gaps, busy_ns, n_dev = _idle_gaps(planes, w0, w1)
    pieces = _innermost(spans)
    starts = [a for a, _, _ in pieces]
    split: Dict[str, int] = defaultdict(int)
    for g0, g1 in gaps:
        k = max(bisect.bisect_right(starts, g0) - 1, 0)
        left = g1 - g0
        while k < len(pieces) and pieces[k][0] < g1:
            a, b, n = pieces[k]
            part = min(b, g1) - max(a, g0)
            if part > 0:
                split[n] += part
                left -= part
            k += 1
        if left > 0:
            split["host:none"] += left

    def host_doing(t: int) -> str:
        k = bisect.bisect_right(starts, t) - 1
        return pieces[k][2] if k >= 0 and t < pieces[k][1] else "host:none"

    n = max(n_dev, 1)
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:10]
    program = sorted((x for x in spans if x[2].startswith(PROGRAM_PREFIX)
                      and w0 <= x[0] < w1), key=lambda x: x[0])
    return {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": busy_ns * 1e-9 / n,
        "idle_by_span": {k: v * 1e-9 / n for k, v in split.items()},
        "idle_gaps": [(host_doing((s + e) // 2), (e - s) * 1e-9)
                      for s, e in longest],
        "program_spans": [(name, (s - w0) * 1e-9, (e - w0) * 1e-9, args)
                          for s, e, name, args in program],
    }


def traced_run(red: Optional[dict], root: Path) -> Optional[dict]:
    """The reduction of the traced run that ``red`` (``xplane``'s
    reduction of it) came from: the newest trace under the benchmark's
    trace directory, ``<root>/.bench_trace``, checked to have ``red``'s
    window.  None where there is none."""
    if not red:
        return None
    found = sorted((root / ".bench_trace").rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not found:
        return None
    out = reduce(*read(found[-1]))
    if out is None or abs(out["window_s"] - red["window_s"]) > 1e-9:
        return None
    return out
