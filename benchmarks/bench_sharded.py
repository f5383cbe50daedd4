"""Device-mesh sharded serving vs single-device: throughput + bit-exactness.

Term-sharded execution (``core.distributed``): the packed postings split
on the vocabulary axis, every device counts against its local shard, and
the shards merge cross-device (gather / partial-top-k merge).  This bench
drives BOTH paths over one corpus — micro-batched engine serving and
full-network materialization — reports queries/s and vocab rows/s per
device layout, and asserts the sharded results are bit-identical to the
single-device oracle (the differential harness's invariant, enforced at
bench time too).

    PYTHONPATH=src python -m benchmarks.bench_sharded

It needs at least two devices and fails with a message on fewer.  On a
CPU-only host the caller forces host devices before the process starts:
``JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8``.
"""
from __future__ import annotations

import argparse
import sys
import time
from typing import Dict, List

import numpy as np


def _parse(argv):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--n-docs", type=int, default=4096)
    ap.add_argument("--vocab", type=int, default=512)
    ap.add_argument("--n-queries", type=int, default=64)
    ap.add_argument("--q-batch", type=int, default=8)
    ap.add_argument("--depth", type=int, default=2)
    ap.add_argument("--topk", type=int, default=8)
    ap.add_argument("--beam", type=int, default=16)
    ap.add_argument("--k", type=int, default=8)
    ap.add_argument("--methods", default="gemm,popcount,pallas,fused")
    ap.add_argument("--no-sweep", action="store_true",
                    help="skip the (V, W) crossover sweep")
    return ap.parse_args(argv)


def main(argv: List[str] | None = None) -> List[Dict]:
    args = _parse(argv)
    import jax

    if len(jax.devices()) < 2:
        raise RuntimeError(
            f"bench_sharded needs >= 2 devices; this process sees "
            f"{len(jax.devices())} ({jax.devices()[0].platform}).  On a "
            "CPU-only host force them before start: JAX_PLATFORMS=cpu "
            "XLA_FLAGS=--xla_force_host_platform_device_count=8")

    from repro.core import QueryContext, make_cooc_mesh, materialize
    from repro.data import synthetic_csl
    from repro.serve.cooc_engine import CoocEngine
    from benchmarks.common import section, write_csv

    n_dev = len(jax.devices())
    methods = tuple(m for m in args.methods.split(",") if m)
    section(f"Sharded queries + materialization — {args.n_docs} docs, "
            f"V={args.vocab}, {n_dev} devices (term-sharded), "
            f"Q={args.n_queries} x depth={args.depth}")
    docs = synthetic_csl(args.n_docs, args.vocab, seed=0)
    mesh = make_cooc_mesh()
    ctxs = {"1dev": QueryContext.from_docs(docs, args.vocab),
            f"{n_dev}dev": QueryContext.from_docs(docs, args.vocab,
                                                  mesh=mesh)}
    rng = np.random.default_rng(0)
    seeds = rng.integers(0, args.vocab, args.n_queries)

    rows, out = [], []
    for method in methods:
        qps, mat_rows, nets, sample = {}, {}, {}, {}
        for label, ctx in ctxs.items():
            eng = CoocEngine(ctx, depth=args.depth, topk=args.topk,
                             beam=args.beam, q_batch=args.q_batch,
                             method=method)
            eng.submit([int(seeds[0])]).result()       # compile + warm
            futs = [eng.submit([int(s)]) for s in seeds]
            t0 = time.perf_counter()
            eng.run_until_drained()
            dt = time.perf_counter() - t0
            qps[label] = args.n_queries / dt
            sample[label] = [f.result().edges() for f in futs[:8]]

            t0 = time.perf_counter()
            net = materialize(ctx, k=args.k, method=method, use_cache=False)
            jax.block_until_ready(net.weight)
            mat_rows[label] = args.vocab / (time.perf_counter() - t0)
            nets[label] = net

        # the bench's correctness gate: sharded == single-device, bit-exact
        a, b = nets["1dev"], nets[f"{n_dev}dev"]
        for f in ("src", "dst", "weight", "valid"):
            np.testing.assert_array_equal(
                np.asarray(getattr(a, f)), np.asarray(getattr(b, f)),
                err_msg=f"sharded materialize diverged ({method}/{f})")
        assert sample["1dev"] == sample[f"{n_dev}dev"], \
            f"sharded query results diverged ({method})"

        for label in ctxs:
            print(f"{method:>9} [{label:>5}]: {qps[label]:9,.1f} q/s   "
                  f"{mat_rows[label]:9,.1f} mat rows/s")
            rows.append({"method": method, "layout": label,
                         "n_devices": 1 if label == "1dev" else n_dev,
                         "n_docs": args.n_docs, "vocab": args.vocab,
                         "qps": qps[label], "mat_rows_per_s": mat_rows[label]})
            out.append({"name": f"sharded_qps_{method}_{label}",
                        "value": qps[label]})
            out.append({"name": f"sharded_mat_rows_per_s_{method}_{label}",
                        "value": mat_rows[label]})
        print(f"{'':>9}  results bit-exact across layouts  [ok]")

    # --- (V, W) crossover sweep: where does the mesh start winning? ---
    # Materialization under the "rows" strategy folds the whole row sweep
    # into ONE launch (per-device lax.map over contiguous row blocks); as
    # V grows and W (packed doc words) shrinks, the single-device path's
    # per-block dispatch loop dominates the roofline and the n-device
    # layout overtakes one device even when all forced devices share a
    # core.  row_tile=32 keeps the per-block (bm, V) transient small —
    # the dispatch-dominated regime the strategy exists for.
    if not args.no_sweep:
        sweep = [(args.vocab, args.n_docs)]
        for mult in (2, 4, 8):
            sweep.append((args.vocab * mult,
                          max(128, args.n_docs // (4 * mult))))
        xover = None
        for v_s, d_s in sweep:
            docs_s = synthetic_csl(d_s, v_s, seed=1)
            per = {}
            for label, ctx in (
                    ("1dev", QueryContext.from_docs(docs_s, v_s)),
                    (f"{n_dev}dev",
                     QueryContext.from_docs(docs_s, v_s, mesh=mesh))):
                w_words = int(ctx.index.n_words)
                best = 0.0
                for _ in range(3):
                    t0 = time.perf_counter()
                    net = materialize(ctx, k=args.k, method="popcount",
                                      use_cache=False, row_tile=32)
                    jax.block_until_ready(net.weight)
                    best = max(best, v_s / (time.perf_counter() - t0))
                per[label] = best
                out.append({"name": f"sharded_xover_mat_rows_per_s_V{v_s}"
                                    f"_W{w_words}_{label}", "value": best})
            won = per[f"{n_dev}dev"] > per["1dev"]
            print(f"xover V={v_s:>5} W={w_words:>4}: "
                  f"1dev {per['1dev']:9,.1f} rows/s   "
                  f"{n_dev}dev {per[f'{n_dev}dev']:9,.1f} rows/s  "
                  f"[{f'{n_dev}dev WINS' if won else '1dev wins'}]")
            if won and xover is None:
                xover = (v_s, w_words)
        out.append({"name": "sharded_crossover_found",
                    "value": 1 if xover else 0})
        if xover:
            out.append({"name": "sharded_crossover_vocab",
                        "value": xover[0]})
            out.append({"name": "sharded_crossover_words",
                        "value": xover[1]})

    path = write_csv("sharded", rows)
    print(f"CSV -> {path}")
    return out


if __name__ == "__main__":
    main(sys.argv[1:])
