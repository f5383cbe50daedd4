"""Find an open-loop cell's knee: serve its mix at several fixed rates,
one window each, after one set-up, in one process.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> \
        --rates 2,2.5,3 [--no-ingest] [--trace-out DIR]

Per rate it prints the requests due, answered inside the window, still
outstanding at the close, and the latency p50/p90 (due to answer).  The
knee is the highest rate whose answers keep up with the offered load
with no backlog at the close.  No answer is checked here.
``--trace-out`` also profiles a 3 s window at the first rate and keeps
its ``.xplane.pb`` (gzipped) in DIR.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import glob  # noqa: E402
import gzip  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]


async def _sweep(cell, built, seed, seconds, rates, no_ingest, trace_out):
    import jax
    import numpy as np
    from bench import harness as H
    server = H.make_server(cell, built)
    await server.start()
    try:
        drv = H.Driver(H.Run(cell, seed, seconds), built, server,
                       annotate=True)
        await drv.warm_up()
        H.say("warm", since_start_s=time.perf_counter() - T_START)
        windows = [(r, seconds, None) for r in rates]
        if trace_out:
            windows.append((rates[0], 3.0, trace_out))
        for rate, secs, tdir in windows:
            mix = dict(cell.mix, rate_qps=rate)
            if no_ingest:
                mix.pop("ingest", None)
            run = H.Run(cell._replace(mix=mix), seed, secs)
            drv.run, drv.tasks = run, []
            tmp = tempfile.mkdtemp() if tdir else None
            if tmp:
                H.start_profile(tmp)
            run.t0 = time.perf_counter() + 0.01
            run.t1 = run.t0 + secs
            await drv.sleep_until(run.t0)
            with drv.trace_annotation("bench.window.open"):
                pass
            await drv.window()
            if tmp:
                jax.profiler.stop_trace()
                src = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                                recursive=True)[0]
                os.makedirs(tdir, exist_ok=True)
                with open(src, "rb") as f, gzip.open(
                        os.path.join(tdir, "window.xplane.pb.gz"), "wb") as g:
                    g.write(f.read())
                shutil.rmtree(tmp)
            w = run.window_requests()
            lat = [(r.done - r.due) * 1e3 for r in w if r.answered]
            H.say("rate", qps=rate, seconds=secs, due=len(w),
                  answered_in_window=sum(r.answered and r.done <= run.t1
                                         for r in w),
                  outstanding_at_close=sum(r.done is None or r.done > run.t1
                                           for r in w),
                  p50_ms=np.percentile(lat, 50) if lat else None,
                  p90_ms=np.percentile(lat, 90) if lat else None,
                  occupancy=np.mean([r.result.batch_occupancy for r in w
                                     if r.answered]) if lat else None,
                  engine_ms=np.mean([r.result.latency_ms for r in w
                                   if r.answered]) if lat else None)
    finally:
        await server.stop()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--no-ingest", action="store_true")
    ap.add_argument("--trace-out")
    args = ap.parse_args(argv)
    for p in (str(ROOT / "src"), str(ROOT)):
        if p not in sys.path:
            sys.path.insert(0, p)
    from bench.harness import Built
    from bench.run import chips_or_refuse, process_setup
    from bench.spec import load_cell
    cell = load_cell(ROOT, args.workload)
    if cell.mix["loop"] != "open":
        raise SystemExit("bench: a sweep needs an open-loop mix")
    chips_or_refuse(cell.chips)
    process_setup()
    rates = [float(r) for r in args.rates.split(",")]
    built = Built(cell, args.seed, args.seconds)
    asyncio.run(_sweep(cell, built, args.seed, args.seconds, rates,
                       args.no_ingest, args.trace_out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
