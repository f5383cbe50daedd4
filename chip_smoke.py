#!/usr/bin/env python3
"""Smoke run of the served co-occurrence path on a TPU, at cooccur-csl size.

    python chip_smoke.py                # one chip
    python chip_smoke.py --chips 4      # the term-sharded path on four chips

One chip: builds the cooccur-csl corpus (396,209 docs x 65,536 terms,
``configs/cooccur_csl.py``) from ``--seed``, packs it into a
``QueryContext`` with 4,096 docs of ingest slack, starts a ``CoocServer``
with one scoped and one unscoped tenant, answers ``fused`` and ``pallas``
requests through ``await server.submit``, ingests one block of 4,096 docs
through ``server.ingest`` and answers a query that must see it.  Every
answer is compared edge for edge -- values and order -- with the host
reference (``build_host_index`` + ``bfs_construct_host_fast``) built from
the same docs.  ``--chips 4`` serves the same corpus and requests from a
``make_cooc_mesh(4, shard="terms")`` context and runs no other phase.

The script refuses to run without a TPU: it never falls back to the CPU.
Earlier lines report the device, the corpus, the artifact bytes, compile
seconds, request latencies, ingest-to-visible time and peak device memory;
the last line of stdout is one JSON object naming the device.  Any
failure exits non-zero without that line.
"""
from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

#: docs of ingest slack past the corpus, and the size of the ingest block
INGEST_DOCS = 4_096
#: the scoped tenant sees the newest third of the corpus (a time window)
RECENT_DOCS = 131_072
#: requests per method: one alone (cold), a concurrent burst, then warm
#: singles -- (first, burst, warm)
PLAN = {"fused": (1, 16, 3), "pallas": (1, 8, 3)}
#: every request must be answered, compile included: no deadline misses
DEADLINE_MS = 600_000.0
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def say(key: str, **fields) -> None:
    print(f"{key}: " + " ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)


def edges_of(result) -> list:
    """The served network's valid edges as (src, dst, weight), in slot
    order -- the order ``bfs_construct_host_fast`` emits them in."""
    import numpy as np
    net = result.network
    v = np.asarray(net.valid)
    return list(zip(np.asarray(net.src)[v].tolist(),
                    np.asarray(net.dst)[v].tolist(),
                    np.asarray(net.weight)[v].tolist()))


def make_requests(rng, host, beam: int):
    """(tenant, seeds, method) per request, tenants alternating.  Seeds
    are 1-3 distinct terms among each tenant's 4,096 most frequent ones
    that occur at all, so every query expands a real frontier."""
    import numpy as np
    tops = {}
    for tenant, hidx in host.items():
        df = np.asarray([len(p) for p in hidx.postings])
        tops[tenant] = np.argsort(-df, kind="stable")[
            :min(4096, int(np.count_nonzero(df)))]
    out = {}
    for method, (first, burst, warm) in PLAN.items():
        reqs = []
        for i in range(first + burst + warm):
            tenant = ("open", "recent")[i % 2]
            n = int(rng.integers(1, min(3, beam) + 1))
            seeds = rng.choice(tops[tenant], size=n, replace=False)
            reqs.append((tenant, [int(s) for s in seeds], method))
        out[method] = reqs
    return out


def ingest_block(seed: int, vocab: int, n: int):
    """``n`` new docs about one new topic: synthetic CSL docs that all
    carry the two rarest term ids as a marker pair.  Returns the docs and
    the marker."""
    from repro.data import synthetic_csl
    marker = [vocab - 2, vocab - 1]
    return [d + marker for d in synthetic_csl(n, vocab, seed=seed)], marker


async def serve(ctx, requests, cfg, *, ingest=None):
    """Serve ``requests`` through a CoocServer; then, when ``ingest`` is
    given as ``(docs, seeds)``, ingest the docs and serve the seeds once
    per method.  Returns the responses in request order and the timings."""
    from repro.serve import CoocServer, ServerConfig, TenantConfig
    server = CoocServer(
        ctx, tenants=[TenantConfig("open"),
                      TenantConfig("recent", scope="recent")],
        config=ServerConfig(depth=cfg.default_depth, topk=cfg.default_topk,
                            beam=cfg.default_beam, q_batch=8,
                            method="fused", default_deadline_ms=DEADLINE_MS))
    await server.start()
    out, timing = {}, {}
    try:
        async def one(tenant, seeds, method):
            t0 = time.perf_counter()
            resp = await server.submit(tenant, {"seeds": seeds,
                                                "method": method})
            return resp, (time.perf_counter() - t0) * 1e3

        for method, reqs in requests.items():
            first, burst, warm = PLAN[method]
            resps = [await one(*reqs[0])]
            t0 = time.perf_counter()
            resps += await asyncio.gather(
                *(one(*r) for r in reqs[first:first + burst]))
            burst_ms = (time.perf_counter() - t0) * 1e3
            for r in reqs[first + burst:]:
                resps.append(await one(*r))
            out[method] = resps
            timing[method] = {
                "first_ms": resps[0][1], "burst_ms": burst_ms,
                "burst_n": burst,
                "warm_ms": [ms for _, ms in resps[first + burst:]]}
        if ingest is not None:
            docs, seeds = ingest
            t0 = time.perf_counter()
            await server.ingest("open", docs,
                                max_len=max(len(d) for d in docs))
            t_ingest = time.perf_counter()
            post = {}
            for method in PLAN:
                post[method] = await one("open", seeds, method)
            # the first post-ingest answer is the first that can see it
            first_ms = next(iter(post.values()))[1]
            timing["ingest"] = {"ingest_ms": (t_ingest - t0) * 1e3,
                                "visible_ms": (t_ingest - t0) * 1e3
                                + first_ms}
            out["post_ingest"] = post
    finally:
        await server.stop()
    return out, timing


def report_artifacts(ctx, chips: int) -> None:
    """Print the bytes of the packed index and ``packed_t_pad``, in all
    and per device; with several chips each must hold its share of
    ``packed_t_pad``.  Holds no reference past the call: the ingest must
    be able to free both."""
    import jax
    t0 = time.perf_counter()
    packed, ptp = ctx.index.packed, ctx.packed_t_pad()
    jax.block_until_ready(ptp)
    say("artifacts", packed_bytes=packed.nbytes, packed_shape=packed.shape,
        packed_t_pad_bytes=ptp.nbytes, packed_t_pad_shape=ptp.shape,
        build_s=time.perf_counter() - t0)
    for name, arr in (("packed", packed), ("packed_t_pad", ptp)):
        per_dev = sorted((s.device.id, s.data.nbytes)
                         for s in arr.addressable_shards)
        say(f"shard_bytes.{name}", **{f"dev{d}": b for d, b in per_dev})
    if chips > 1:
        held = {s.device.id: s.data.nbytes for s in ptp.addressable_shards}
        if len(held) != chips or max(held.values()) > 1.05 * ptp.nbytes / chips:
            raise RuntimeError(
                f"packed_t_pad is not split over {chips} devices: {held}")


def check(label: str, resp, want: list) -> None:
    if resp.status != "ok":
        raise RuntimeError(f"{label}: status {resp.status} ({resp.reason})")
    got = edges_of(resp.result)
    if got != want:
        bad = next((i for i, (g, w) in enumerate(zip(got, want)) if g != w),
                   min(len(got), len(want)))
        raise RuntimeError(
            f"{label}: {len(got)} edges served, {len(want)} expected; first "
            f"difference at edge {bad}: served "
            f"{got[bad] if bad < len(got) else None}, expected "
            f"{want[bad] if bad < len(want) else None}")


def smoke(*, n_docs: int, vocab: int, seed: int, chips: int,
          ingest_docs: int = INGEST_DOCS,
          recent_docs: int = RECENT_DOCS) -> None:
    """Build, serve and check; raises on any failure.  One chip runs the
    full smoke with the ingest phase, ``chips > 1`` the term-sharded
    serving only."""
    import jax

    compiles = []

    def on_duration(event, secs, **kw):
        if event == COMPILE_EVENT:
            compiles.append((kw.get("fun_name", "?"), secs))

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    try:
        _smoke(compiles, n_docs=n_docs, vocab=vocab, seed=seed, chips=chips,
               ingest_docs=ingest_docs, recent_docs=recent_docs)
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)


def _smoke(compiles: list, *, n_docs: int, vocab: int, seed: int, chips: int,
           ingest_docs: int, recent_docs: int) -> None:
    import jax
    import numpy as np

    from repro.configs.cooccur_csl import CONFIG
    from repro.core import (
        QueryContext,
        bfs_construct_host_fast,
        build_host_index,
        make_cooc_mesh,
    )
    from repro.data import synthetic_csl

    t0 = time.perf_counter()
    docs = synthetic_csl(n_docs, vocab, seed=seed)
    mesh = make_cooc_mesh(chips, shard="terms") if chips > 1 else None
    ctx = QueryContext.from_docs(docs, vocab, capacity=n_docs + ingest_docs,
                                 mesh=mesh)
    recent = np.arange(n_docs - recent_docs, n_docs)
    ctx.define_scope("recent", recent)
    say("corpus", docs=n_docs, terms=vocab, words=ctx.index.n_words,
        capacity=ctx.index.capacity,
        build_s=time.perf_counter() - t0)

    report_artifacts(ctx, chips)

    t0 = time.perf_counter()
    host = {"open": build_host_index(docs, vocab),
            "recent": build_host_index(docs[n_docs - recent_docs:], vocab)}
    say("host_reference", build_s=time.perf_counter() - t0)

    rng = np.random.default_rng(seed + 1)
    requests = make_requests(rng, host, CONFIG.default_beam)
    ingest = None
    if chips == 1:
        new_docs, marker = ingest_block(seed + 2, vocab, ingest_docs)
        ingest = (new_docs, marker[:1])
    epoch0 = ctx.epoch

    t0 = time.perf_counter()
    out, timing = asyncio.run(serve(ctx, requests, CONFIG, ingest=ingest))
    say("served", wall_s=time.perf_counter() - t0)
    for fun_name, secs in compiles:
        if secs >= 0.1:
            say("compile", executable=fun_name, seconds=secs)
    say("compile_total", executables=len(compiles),
        seconds=sum(s for _, s in compiles))

    def host_edges(tenant, seeds, hidx=None):
        return bfs_construct_host_fast(
            hidx or host[tenant], seeds, depth=CONFIG.default_depth,
            topk=CONFIG.default_topk, beam=CONFIG.default_beam)

    t0 = time.perf_counter()
    n_checked = 0
    for method, reqs in requests.items():
        for i, ((tenant, seeds, _), (resp, _ms)) in enumerate(
                zip(reqs, out[method])):
            check(f"{method} request {i} ({tenant}, seeds {seeds})", resp,
                  host_edges(tenant, seeds))
            n_checked += 1
        t = timing[method]
        say(f"latency.{method}", first_ms=t["first_ms"],
            burst_ms=t["burst_ms"], burst_n=t["burst_n"],
            warm_ms=t["warm_ms"])

    if ingest is not None:
        new_docs, seeds = ingest
        after = build_host_index(docs + new_docs, vocab)
        want = host_edges("open", seeds, after)
        if want == host_edges("open", seeds):
            raise RuntimeError("the ingest block changes no answer")
        for method, (resp, _ms) in out["post_ingest"].items():
            check(f"{method} post-ingest query", resp, want)
            if resp.result.epoch <= epoch0:
                raise RuntimeError(f"{method} post-ingest query answered "
                                   f"at epoch {resp.result.epoch}")
            n_checked += 1
        t = timing["ingest"]
        say("ingest", docs=len(new_docs), ingest_ms=t["ingest_ms"],
            visible_ms=t["visible_ms"])
    say("checked", requests=n_checked, matched=n_checked,
        check_s=time.perf_counter() - t0)

    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        say(f"memory.dev{d.id}", peak_bytes_in_use=stats.get(
            "peak_bytes_in_use", "not reported"),
            bytes_limit=stats.get("bytes_limit", "not reported"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    import jax
    platform = jax.default_backend()
    if platform != "tpu":
        print(f"chip_smoke: needs a TPU, but JAX's default backend is "
              f"{platform!r}; nothing was run", file=sys.stderr)
        return 2
    devices = jax.devices()
    if len(devices) != args.chips:
        # the result line names the chips the run used: all JAX sees
        print(f"chip_smoke: --chips {args.chips} runs on exactly "
              f"{args.chips} TPU(s), JAX sees {len(devices)}",
              file=sys.stderr)
        return 2
    say("device", platform=devices[0].platform,
        kind=repr(devices[0].device_kind), count=len(devices),
        jax=jax.__version__)

    from repro.configs.cooccur_csl import CONFIG
    from repro.launch.flags import use_compile_cache
    say("compile_cache", dir=use_compile_cache())
    smoke(n_docs=CONFIG.n_docs, vocab=CONFIG.vocab_size, seed=args.seed,
          chips=args.chips)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
