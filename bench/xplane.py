"""Reduce a profiler trace (``.xplane.pb``) to the numbers the metrics read.

Device planes (``/device:TPU:<n>``) carry one line of XLA programs
("XLA Modules") and one of the operations inside them ("XLA Ops").  Host
planes carry the benchmark's own ``TraceAnnotation`` spans, named
``bench.<what>``, on the same clock.  The reduction:

- busy time: the union of the intervals in which an operation ran, per
  chip, inside the traced window (the ``bench.window`` span);
- time per XLA program and per operation name, summed over chips;
- idle gaps: the stretches of the window with no operation running, each
  named by the innermost benchmark span that covers its middle on the
  host (``host:none`` where no span does).
"""
from __future__ import annotations

import re
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Optional, Tuple

WINDOW_SPAN = "bench.window"
_MODULE_LINES = ("XLA Modules",)
_OP_LINES = ("XLA Ops",)


def find_xplane(log_dir: str) -> Optional[Path]:
    """The newest ``*.xplane.pb`` under a profiler log directory."""
    found = sorted(Path(log_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    return found[-1] if found else None


def _union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def module_base(name: str) -> str:
    """``jit_route_pass(12)`` -> ``route_pass``: an XLA program's name
    without the ``jit_`` prefix and the run counter."""
    name = re.sub(r"\(\d+\)$", "", name.strip())
    return name[4:] if name.startswith("jit_") else name


def reduce_planes(planes) -> dict:
    """Reduce already-parsed planes.  ``planes`` is a list of
    ``(plane_name, [(line_name, [(event_name, start_ns, dur_ns)])])``,
    the shape ``read_xplane`` returns; tests build it by hand."""
    spans: List[Tuple[int, int, str]] = []
    devices = {}
    for pname, lines in planes:
        if pname.startswith("/device:TPU:"):
            devices[pname] = lines
        elif pname.startswith("/host:"):
            for _, events in lines:
                spans += [(s, s + d, n) for n, s, d in events
                          if n.startswith("bench.")]
    win = [(s, e) for s, e, n in spans if n == WINDOW_SPAN]
    if win:
        w0, w1 = win[0]
    else:
        starts = [s for lines in devices.values() for _, ev in lines
                  for _, s, _ in ev]
        ends = [s + d for lines in devices.values() for _, ev in lines
                for _, s, d in ev]
        w0, w1 = (min(starts), max(ends)) if starts else (0, 0)
    modules: Dict[str, float] = defaultdict(float)
    module_calls: Dict[str, int] = defaultdict(int)
    ops: Dict[str, float] = defaultdict(float)
    busy_ns = 0
    gaps: List[Tuple[int, int]] = []
    for pname, lines in devices.items():
        ivs = []
        for lname, events in lines:
            for n, s, d in events:
                s2, e2 = max(s, w0), min(s + d, w1)
                if e2 <= s2:
                    continue
                if lname in _MODULE_LINES:
                    modules[module_base(n)] += (e2 - s2) * 1e-9
                    module_calls[module_base(n)] += 1
                elif lname in _OP_LINES:
                    ops[n] += (e2 - s2) * 1e-9
                    ivs.append((s2, e2))
        u = _union(ivs)
        busy_ns += sum(e - s for s, e in u)
        prev = w0
        for s, e in u:
            if s > prev:
                gaps.append((prev, s))
            prev = e
        if w1 > prev:
            gaps.append((prev, w1))
    n_dev = max(len(devices), 1)
    spans.sort(key=lambda x: x[1] - x[0])

    def host_doing(t: int) -> str:
        for s, e, n in spans:              # shortest (innermost) first
            if s <= t < e and n != WINDOW_SPAN:
                return n
        return "host:none"

    named = sorted(((host_doing((s + e) // 2), (e - s) * 1e-9)
                    for s, e in gaps), key=lambda x: -x[1])
    idle_by: Dict[str, float] = defaultdict(float)
    for n, sec in named:
        idle_by[n] += sec
    return {
        "devices": len(devices),
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": busy_ns * 1e-9 / n_dev,
        "modules": dict(modules),
        "module_calls": dict(module_calls),
        "ops": dict(ops),
        "idle_gaps": named[:10],
        "idle_by_host": dict(idle_by),
        "host_spans": [(n, (e - s) * 1e-9) for s, e, n in spans],
    }


def read_xplane(path) -> list:
    """Parse a ``.xplane.pb`` into ``reduce_planes``' input shape."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    planes = []
    for plane in pd.planes:
        lines = []
        for line in plane.lines:
            lines.append((line.name, [(ev.name, int(ev.start_ns),
                                       int(ev.duration_ns))
                                      for ev in line.events]))
        planes.append((plane.name, lines))
    return planes


def reduce_trace(path) -> dict:
    """``reduce_planes`` of the trace file at ``path``."""
    return reduce_planes(read_xplane(path))


def breakdown(red: dict) -> dict:
    """The result line's ``breakdown``: the ten device operations that
    took most time, and the ten longest idle gaps by host activity."""
    top = sorted(red["ops"].items(), key=lambda x: -x[1])[:10]
    return {"device_ops": [[n, s] for n, s in top],
            "idle_gaps": [[n, s] for n, s in red["idle_gaps"]]}
