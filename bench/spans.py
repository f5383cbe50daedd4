"""The program's own spans in a profiler trace, and runs that report
them.

The serving path records spans named ``cooc.*`` (``repro.core.spans``):
the server keeps them in its snapshot and, under a profiler trace, each
lands on a host line of the same ``.xplane.pb`` as the device's
operations.  This module reduces them beside ``bench/trace.py``:

- per span name, the intervals that overlap the window;
- device 0's idle gaps (those of ``bench/trace.py``'s ``Summary.gaps``),
  each labelled by the ``cooc.*`` span that holds most of it: every
  instant of a gap goes to the innermost span covering it, a span doing
  work before one waiting (``WAITS``), and where no ``cooc.*`` span
  covers any of it the gap keeps ``bench/trace.py``'s label;
- what the spans read: the lane lock's wait per batch, a step's host
  time (the step less its device wait), an ingest's application time,
  and how the step's four children tile it.

    python3 bench/spans.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1> [--trace-out DIR]
    python3 bench/spans.py --cost <n>

The first runs one cell as ``bench/run.py`` does and prints, before the
result line, a ``spans:`` line of the window's spans
(``name=count/total_ms/max_ms``), the span log's counters and, traced,
the reduction above (``--trace-out`` keeps the gzipped ``.xplane.pb``).
The second times ``n`` empty spans with the profiler off and on.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import glob  # noqa: E402
import gzip  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from typing import Dict, List, NamedTuple, Tuple  # noqa: E402

import numpy as np  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]

PREFIX = "cooc."
STEP = "cooc.engine.step"
STEP_CHILDREN = ("cooc.step.prepare", "cooc.step.dispatch",
                 "cooc.step.device", "cooc.step.fetch")
#: spans in which the program waits rather than works
WAITS = frozenset({"cooc.lane.idle", "cooc.lane.linger", "cooc.lane.lock",
                   "cooc.lane.ingest_lock"})

Interval = Tuple[float, float]


class Reduction(NamedTuple):
    spans: Dict[str, List[Interval]]   # ns, those overlapping the window
    window: Interval                   # ns
    gaps: List[Tuple[str, float]]      # device 0, longest first, seconds
    tiling: List[float]                # per step: its children / the step

    def inside(self, name: str) -> List[Interval]:
        """The intervals of ``name`` that start in the window."""
        lo, hi = self.window
        return [(a, b) for a, b in self.spans.get(name, ()) if lo <= a < hi]

    def idle_share(self) -> float:
        """Share of the gaps' time in gaps labelled by a ``cooc.*`` span."""
        total = sum(s for _, s in self.gaps)
        cooc = sum(s for lab, s in self.gaps if lab.startswith(PREFIX))
        return cooc / total if total else 0.0

    def readings(self) -> Dict[str, float]:
        """The host-side readings of the window's spans, in ms: the lane
        lock's wait per batch, a step's host time (the step less its
        device wait) and an ingest's application."""
        out = {}
        batches = self.inside("cooc.lane.batch")
        if batches:
            out["lock_wait_ms"] = _ms(self.inside("cooc.lane.lock")) / len(
                batches)
        steps = self.inside(STEP)
        if steps:
            device = self.spans.get("cooc.step.device", [])
            out["step_host_ms"] = sum(
                _ms([(a, b)]) - _ms([(c, d) for c, d in device
                                     if a <= c and d <= b])
                for a, b in steps) / len(steps)
        ingest = self.inside("cooc.index.ingest")
        if ingest:
            out["ingest_apply_ms"] = _ms(ingest) / len(ingest)
        return out


def _ms(intervals: List[Interval]) -> float:
    return 1e-6 * sum(b - a for a, b in intervals)


def load_spans(xspace: bytes) -> list:
    """The host events named ``cooc.*``, as ``bench/trace.py``'s
    ``Event``."""
    from jax.profiler import ProfileData

    from bench import trace as TR
    pd = ProfileData.from_serialized_xspace(xspace)
    return [TR.Event(plane.name, line.name, e.name, e.start_ns,
                     e.duration_ns)
            for plane in pd.planes if not plane.name.startswith("/device:")
            for line in plane.lines for e in line.events
            if e.name.startswith(PREFIX)]


def device_gaps(events: list, lo: float, hi: float) -> List[Interval]:
    """Device 0's idle gaps in the window, as ``bench/trace.py`` finds
    them (``Summary.gaps``)."""
    from bench import trace as TR
    devices = sorted({e.plane for e in events if e.line == TR.OPS_LINE})
    if not devices:
        return []
    merged = TR._union([(max(e.start_ns, lo), min(e.end_ns, hi))
                        for e in events
                        if e.plane == devices[0] and e.line == TR.OPS_LINE
                        and e.end_ns > lo and e.start_ns < hi])
    edges = [lo] + [x for ab in merged for x in ab] + [hi]
    return [(edges[j], edges[j + 1]) for j in range(0, len(edges), 2)
            if edges[j + 1] - edges[j] >= TR.MIN_GAP_NS]


def _owner(gap: Interval, cover: List[Tuple[str, float, float]]):
    """The span name that holds most of ``gap``: each instant goes to
    the innermost covering span, one doing work before one waiting."""
    cuts = sorted({gap[0], gap[1]} | {x for _, a, b in cover for x in (a, b)
                                      if gap[0] < x < gap[1]})
    held: Dict[str, float] = {}
    for x, y in zip(cuts, cuts[1:]):
        on = [(name in WAITS, b - a, name) for name, a, b in cover
              if a <= x and b >= y]
        if on:
            name = min(on)[2]
            held[name] = held.get(name, 0.0) + (y - x)
    return max(held, key=held.get) if held else None


def reduce(events: list, spans: list) -> Reduction:
    """The reduction of one traced window: ``events`` as
    ``bench/trace.py`` loads them, ``spans`` as :func:`load_spans`
    does."""
    from bench import trace as TR
    lo, hi = TR._window(events)
    by_name: Dict[str, List[Interval]] = {}
    for e in spans:
        if e.end_ns > lo and e.start_ns < hi:
            by_name.setdefault(e.name, []).append((e.start_ns, e.end_ns))
    for ivs in by_name.values():
        ivs.sort()
    flat = [(n, a, b) for n, ivs in by_name.items() for a, b in ivs]
    starts = np.asarray([a for _, a, _ in flat])
    ends = np.asarray([b for _, _, b in flat])
    bench = {}
    for e in events:
        if e.name in ("bench.request", "bench.ingest"):
            bench.setdefault(e.name, []).append((e.start_ns, e.end_ns))
    gaps = []
    for g in device_gaps(events, lo, hi):
        hit = (np.flatnonzero((starts < g[1]) & (ends > g[0]))
               if flat else [])
        label = _owner(g, [flat[i] for i in hit]) or TR._label(g, bench)
        gaps.append((label, (g[1] - g[0]) / 1e9))
    gaps.sort(key=lambda x: -x[1])
    children = [iv for n in STEP_CHILDREN for iv in by_name.get(n, ())]
    tiling = []
    for a, b in by_name.get(STEP, ()):
        if lo <= a and b <= hi and b > a:
            tiling.append(sum(d - c for c, d in children
                              if a <= c and d <= b) / (b - a))
    return Reduction(by_name, (lo, hi), gaps, tiling)


def reduce_file(path: str) -> Reduction:
    from bench import trace as TR
    data = TR.read_xspace(path)
    return reduce(TR.load_events(data), load_spans(data))


def spans_line(logs, t0: float, t1: float) -> str:
    """``name=count/total_ms/max_ms`` of the spans of ``logs``
    (``repro.core.spans.SpanLog``) that started in ``[t0, t1)``."""
    out: Dict[str, List[float]] = {}
    for log in {id(x): x for x in logs}.values():
        for name in log.names():
            out.setdefault(name, []).extend(
                (b - a) * 1e3 for a, b in list(log.ring(name))
                if t0 <= a < t1)
    return " ".join(f"{n}={len(ms)}/{sum(ms):.3f}/{max(ms):.3f}"
                    for n, ms in sorted(out.items()) if ms)


def say_reduction(r: Reduction) -> None:
    from bench.harness import say
    say("span_counts", **{n: len(r.inside(n)) for n in sorted(r.spans)})
    say("span_readings", **{k: round(v, 4) for k, v in r.readings().items()})
    idle = sum(s for _, s in r.gaps)
    say("idle_gaps", n=len(r.gaps), idle_s=idle,
        cooc_labelled_share=r.idle_share())
    by: Dict[str, List[float]] = {}
    for lab, s in r.gaps:
        by.setdefault(lab, []).append(s)
    say("idle_by_label", **{k: f"{len(v)}/{sum(v):.6f}"
                            for k, v in sorted(by.items())})
    say("idle_longest", gaps=json.dumps([[k, v] for k, v in r.gaps[:10]]))
    if r.tiling:
        say("step_tiling", steps=len(r.tiling), min=min(r.tiling),
            max=max(r.tiling))


def run(args, root=ROOT) -> dict:
    """One run of the cell through ``bench/harness.py``'s ``run_cell``,
    which is left as it is: three of its functions are wrapped to keep
    the window, the server's span logs and the trace it drops."""
    from bench import harness as H
    from bench import trace as TR
    from bench.run import chips_or_refuse, process_setup
    from bench.spec import load_cell
    cell = load_cell(root, args.workload)
    chips_or_refuse(cell.chips)
    process_setup()
    kept = {}
    serve, make_server, summarize_dir = (H._serve, H.make_server,
                                         TR.summarize_dir)

    async def keep_run(run, *a):
        kept["run"] = run
        await serve(run, *a)

    def keep_logs(cell_, built):
        server = make_server(cell_, built)
        kept["logs"] = [server.spans] + [
            lane.engine.ctx.spans for lane in server._lanes.values()]
        return server

    def keep_trace(trace_dir, run_):
        path = max(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
        data = TR.read_xspace(path)
        if args.trace_out:
            os.makedirs(args.trace_out, exist_ok=True)
            with gzip.open(os.path.join(args.trace_out,
                                        "window.xplane.pb.gz"), "wb") as g:
                g.write(data)
        kept["reduction"] = reduce(TR.load_events(data), load_spans(data))
        return summarize_dir(trace_dir, run_)

    H._serve, H.make_server, TR.summarize_dir = (keep_run, keep_logs,
                                                 keep_trace)
    try:
        out = H.run_cell(root, cell, args.seed, args.seconds,
                         bool(args.trace), T_START)
    finally:
        H._serve, H.make_server, TR.summarize_dir = (serve, make_server,
                                                     summarize_dir)
    from repro.core.spans import merge
    w = kept["run"]
    print("spans: " + spans_line(kept["logs"], w.t0, w.t1), flush=True)
    H.say("span_counters", **merge(kept["logs"])[1])
    if "reduction" in kept:
        say_reduction(kept["reduction"])
    return out


def span_cost_us(n: int) -> float:
    """Microseconds per empty span, over ``n`` of them."""
    from repro.core.spans import SpanLog
    log = SpanLog(window=n)
    t = time.perf_counter()
    for _ in range(n):
        with log.span("cooc.cost"):
            pass
    return (time.perf_counter() - t) / n * 1e6


def cost(n: int, repeats: int = 5) -> Dict[str, float]:
    """Median over ``repeats`` of :func:`span_cost_us`, with the
    profiler off and on (as the benchmark traces)."""
    import jax
    from bench.harness import start_profile
    off = [span_cost_us(n) for _ in range(repeats)]
    with tempfile.TemporaryDirectory(prefix="bench-span-cost-") as tdir:
        start_profile(tdir)
        try:
            on = [span_cost_us(n) for _ in range(repeats)]
        finally:
            jax.profiler.stop_trace()
    return {"off_us": statistics.median(off), "on_us": statistics.median(on),
            "platform": jax.devices()[0].platform}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int)
    ap.add_argument("--seconds", type=float)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-out")
    ap.add_argument("--cost", type=int)
    args = ap.parse_args(argv)
    for p in (str(ROOT / "src"), str(ROOT)):
        if p not in sys.path:
            sys.path.insert(0, p)
    if args.cost:
        print("span_cost: " + json.dumps(cost(args.cost)), flush=True)
        return 0
    if args.workload is None or args.seed is None or args.seconds is None:
        ap.error("--workload, --seed and --seconds are needed for a run")
    print(json.dumps(run(args)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
