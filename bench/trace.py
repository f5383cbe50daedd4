"""Reduces a profiler trace (``.xplane.pb``) to what the per-layer
metrics read: device busy time, device op time, the executed query
steps, and the idle gaps labelled by what the benchmark's client was
doing in them.

The window is the one the benchmark marks with the host annotations
``bench.window.open`` and ``bench.window.close``; every interval is
clipped to it.  Device planes are those named ``/device:...``; their
``XLA Ops`` line holds the operations (named by their HLO names, nested
ones included: a loop and the kernels it runs) and their ``XLA Modules``
line the executed programs.  The client's spans are the host annotations
``bench.request`` (a request sent and not yet answered) and
``bench.ingest`` (a call to ``server.ingest``).
"""
from __future__ import annotations

import glob
import gzip
import os
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

OPEN, CLOSE = "bench.window.open", "bench.window.close"
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
#: idle gaps shorter than this are the seams between back-to-back ops
MIN_GAP_NS = 1000


class Event(NamedTuple):
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


class Summary(NamedTuple):
    window_s: float
    busy_s: float                          # mean over the chips used
    op_s: Dict[str, float]                 # device op time by name
    module_runs: Dict[str, float]          # executions inside the window
    module_s: Dict[str, float]             # program device time by name
    gaps: List[Tuple[str, float]]          # idle gaps of device 0
    breakdown: dict

    def op_seconds(self, patterns: Sequence[str]) -> float:
        """Device time of the ops whose name holds one of ``patterns``."""
        return sum(s for name, s in self.op_s.items()
                   if any(p in name for p in patterns))

    def runs(self, pattern: str) -> float:
        """Executions, inside the window, of the programs whose name
        holds ``pattern`` (one cut by an edge counts its share)."""
        return sum(n for name, n in self.module_runs.items()
                   if pattern in name)

    def module_seconds(self, pattern: str) -> float:
        return sum(s for name, s in self.module_s.items() if pattern in name)


def short_name(name: str) -> str:
    """An operation's HLO name without its text: ``%level_step.6 = (...)
    custom-call(...)`` is ``level_step.6``."""
    return name.split(" = ", 1)[0].lstrip("%")


def load_events(xspace: bytes) -> List[Event]:
    from jax.profiler import ProfileData
    pd = ProfileData.from_serialized_xspace(xspace)
    out = []
    for plane in pd.planes:
        device = plane.name.startswith("/device:")
        for line in plane.lines:
            for e in line.events:
                if device and line.name in (OPS_LINE, MODULES_LINE):
                    out.append(Event(plane.name, line.name,
                                     short_name(e.name), e.start_ns,
                                     e.duration_ns))
                elif not device and e.name.startswith("bench."):
                    out.append(Event(plane.name, line.name, e.name,
                                     e.start_ns, e.duration_ns))
    return out


def read_xspace(path: str) -> bytes:
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        return f.read()


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[List[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def _clip(e: Event, lo: float, hi: float) -> float:
    return max(0.0, min(e.end_ns, hi) - max(e.start_ns, lo))


def _window(events: List[Event]) -> Tuple[float, float]:
    opens = [e.start_ns for e in events if e.name == OPEN]
    closes = [e.start_ns for e in events if e.name == CLOSE]
    if not opens or not closes:
        raise ValueError("the trace holds no window markers "
                         f"({OPEN!r}, {CLOSE!r})")
    return min(opens), max(closes)


def _label(gap: Tuple[float, float], spans: Dict[str, list]) -> str:
    def overlaps(name):
        return any(a < gap[1] and b > gap[0] for a, b in spans.get(name, ()))
    if overlaps("bench.ingest"):
        return "ingest"
    if overlaps("bench.request"):
        return "requests_outstanding"
    return "no_request_outstanding"


def summarize(events: List[Event], chips: int = 1) -> Optional[Summary]:
    """The reduction of one traced window; None where the trace holds no
    device operation (nothing ran on a chip)."""
    lo, hi = _window(events)
    devices = sorted({e.plane for e in events if e.line == OPS_LINE})
    if not devices:
        return None
    devices = devices[:chips]
    busy, gaps_dev0 = [], []
    op_s: Dict[str, float] = {}
    runs: Dict[str, float] = {}
    mod_s: Dict[str, float] = {}
    for i, dev in enumerate(devices):
        ops = [e for e in events if e.plane == dev and e.line == OPS_LINE
               and e.end_ns > lo and e.start_ns < hi]
        merged = _union([(max(e.start_ns, lo), min(e.end_ns, hi))
                         for e in ops])
        busy.append(sum(b - a for a, b in merged))
        for e in ops:
            op_s[e.name] = op_s.get(e.name, 0.0) + _clip(e, lo, hi) / 1e9
        for e in events:
            if (e.plane == dev and e.line == MODULES_LINE and e.dur_ns > 0
                    and e.end_ns > lo and e.start_ns < hi):
                runs[e.name] = runs.get(e.name, 0.0) + _clip(e, lo, hi) / e.dur_ns
                mod_s[e.name] = mod_s.get(e.name, 0.0) + _clip(e, lo, hi) / 1e9
        if i == 0:
            edges = [lo] + [x for ab in merged for x in ab] + [hi]
            gaps_dev0 = [(edges[j], edges[j + 1])
                         for j in range(0, len(edges), 2)
                         if edges[j + 1] > edges[j]]
    n = len(devices)
    op_s = {k: v / n for k, v in op_s.items()}
    runs = {k: v / n for k, v in runs.items()}
    mod_s = {k: v / n for k, v in mod_s.items()}
    spans: Dict[str, list] = {}
    for e in events:
        if e.name in ("bench.request", "bench.ingest"):
            spans.setdefault(e.name, []).append((e.start_ns, e.end_ns))
    gaps = sorted(((_label(g, spans), (g[1] - g[0]) / 1e9)
                   for g in gaps_dev0 if g[1] - g[0] >= MIN_GAP_NS),
                  key=lambda x: -x[1])
    top_ops = sorted(op_s.items(), key=lambda x: -x[1])[:10]
    breakdown = {"device_ops": [[k, v] for k, v in top_ops],
                 "idle_gaps": [[k, v] for k, v in gaps[:10]]}
    return Summary((hi - lo) / 1e9, sum(busy) / n / 1e9, op_s, runs, mod_s,
                   gaps, breakdown)


def summarize_dir(trace_dir: str, run) -> Optional[Summary]:
    """The reduction of the one ``.xplane.pb`` the profiler wrote under
    ``trace_dir``."""
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return summarize(load_events(read_xspace(max(paths, key=os.path.getmtime))),
                     run.cell.chips)
