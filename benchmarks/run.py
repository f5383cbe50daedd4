"""Benchmark driver: one bench per paper table/figure + the roofline table.

    PYTHONPATH=src python -m benchmarks.run [--quick] [--json]

Emits ``name,value`` CSV lines at the end (and per-bench CSVs under
results/bench/).  ``--json`` additionally writes one machine-readable
``BENCH_<name>.json`` per executed bench (throughput records + run
metadata) under results/bench/ — the artifacts CI archives so the perf
trajectory is queryable across runs.

``--compare <baseline>`` (a committed ``BENCH_<name>.json`` file or a
directory of them) diffs every produced record against the baseline and
exits nonzero when a throughput-like metric drops (or a latency-like
metric rises) by more than 20% — the CI perf gate.  Baselines are loaded
BEFORE any bench runs, since ``--json`` overwrites results/bench/ in
place.
"""
from __future__ import annotations

import argparse
import sys
import traceback


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="smaller corpora (CI-speed)")
    ap.add_argument("--json", action="store_true",
                    help="write BENCH_<name>.json records per bench")
    ap.add_argument("--only", default=None,
                    choices=("fig7", "fig5", "scaling", "engine_throughput",
                             "streaming", "full_network", "sharded",
                             "serving", "approx", "roofline"))
    ap.add_argument("--compare", default=None, metavar="BASELINE",
                    help="BENCH_<name>.json file or directory of them; "
                         "exit 1 on any >20%% metric regression")
    args = ap.parse_args()

    from repro.launch.flags import use_compile_cache
    use_compile_cache()

    baseline = None
    if args.compare:
        from benchmarks.common import load_bench_baselines
        # load the committed numbers FIRST — --json rewrites results/bench/
        baseline = load_bench_baselines(args.compare)
        print(f"loaded {len(baseline)} baseline metrics from {args.compare}")

    results = []
    failures = []
    per_bench = {}

    def run_bench(name, fn):
        if args.only and args.only != name:
            return
        try:
            out = fn() or []
            results.extend(out)
            per_bench[name] = out
        except Exception:
            traceback.print_exc()
            failures.append(name)

    if args.quick:
        from benchmarks import bench_paper_fig7_fig8 as f78
        from benchmarks.common import section

        def quick_fig7():
            section("Paper Fig.7/8 (quick)")
            out = f78.run(n_docs=4000, vocab=2048, n_queries=20)
            s = out["summary"]
            print("time speedup x%.1f  wilcoxon p=%.2e" % (
                s["time"]["speedup_median"], s["time"]["wilcoxon"]["p"]))
            return [{"name": "fig7_time_speedup_quick",
                     "value": s["time"]["speedup_median"]}]

        run_bench("fig7", quick_fig7)
    else:
        from benchmarks import bench_paper_fig7_fig8
        run_bench("fig7", bench_paper_fig7_fig8.main)

    from benchmarks import bench_depth_sensitivity
    run_bench("fig5", bench_depth_sensitivity.main)

    from benchmarks import bench_scaling
    run_bench("scaling", bench_scaling.main)

    from benchmarks import bench_engine_throughput
    engine_argv = (["--n-docs", "1024", "--n-queries", "64"]
                   if args.quick else [])
    run_bench("engine_throughput",
              lambda: bench_engine_throughput.main(engine_argv))

    from benchmarks import bench_streaming_window
    streaming_argv = (["--window", "512", "--block", "64", "--rounds", "12"]
                      if args.quick else [])
    run_bench("streaming",
              lambda: bench_streaming_window.main(streaming_argv))

    from benchmarks import bench_full_network
    full_net_argv = (["--n-docs", "1024", "--vocab", "256", "--k", "8",
                      "--repeats", "1"] if args.quick else [])
    run_bench("full_network",
              lambda: bench_full_network.main(full_net_argv))

    from benchmarks import bench_sharded
    sharded_argv = (["--n-docs", "1024", "--vocab", "256", "--n-queries",
                     "16", "--k", "4"] if args.quick else [])
    run_bench("sharded", lambda: bench_sharded.main(sharded_argv))

    from benchmarks import bench_serving
    serving_argv = (["--n-docs", "1024", "--vocab", "256", "--n-requests",
                     "120", "--rate", "30", "--burst", "48", "--hostile", "3",
                     "--max-queue-depth", "24"] if args.quick else [])
    run_bench("serving", lambda: bench_serving.main(serving_argv))

    from benchmarks import bench_approx
    approx_argv = (["--n-docs", "768", "--repeats", "3", "--num-perms",
                    "32", "128"] if args.quick else [])
    run_bench("approx", lambda: bench_approx.main(approx_argv))

    from benchmarks import roofline
    run_bench("roofline", roofline.main)

    if args.json:
        from benchmarks.common import write_bench_json
        for name, out in per_bench.items():
            path = write_bench_json(name, out, quick=args.quick)
            print(f"JSON -> {path}")

    print("\n== summary (name,value) ==")
    for r in results:
        v = r["value"]
        print(f"{r['name']},{v:.6g}" if isinstance(v, float) else
              f"{r['name']},{v}")

    regressed = []
    if baseline is not None:
        from benchmarks.common import compare_records
        lines, regressed = compare_records(baseline, results)
        print("\n== compare vs baseline (gate: >20% directional move) ==")
        for ln in lines:
            print(ln)
        print(f"{len(regressed)} regressed metric(s)"
              + (f": {regressed}" if regressed else ""))

    if failures:
        print("FAILED benches:", failures)
        return 1
    return 1 if regressed else 0


if __name__ == "__main__":
    sys.exit(main())
