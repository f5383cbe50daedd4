"""Runs one cell: builds the index from the seed, serves the cell's
traffic through ``CoocServer`` for a measured window, checks the answers
against the plain reference and reads the cell's metrics.

The order of a run:

1. set-up: the corpus from the seed, ``QueryContext.from_docs``, the
   tenants' scopes, ``CoocServer.start``, then warm-up requests (one per
   tenant) and, where the mix ingests, one warm-up block followed by a
   request, so that every program the window uses is compiled;
2. the window: ``--seconds`` of requests and ingests on the mix's
   schedule (profiled when ``trace`` is on);
3. the drain: every request due in the window is waited for, up to
   ``DRAIN_S`` past the close; then the device's peak memory is read and
   the server is stopped and dropped;
4. the check: a sample of the answers (drawn from the seed, with the
   largest network) is compared with the reference, edge for edge.
"""
from __future__ import annotations

import asyncio
import contextlib
import ctypes
import dataclasses
import gc
import sys
import tempfile
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

from bench import corpus as C
from bench import reference as R
from bench import traffic as T
from bench.spec import Cell, peaks_for, read_metrics

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
#: how long past the window's close answers are waited for
DRAIN_S = 60.0
#: each compared number and its limit: the comparison is exact
LIMITS = {"wrong_answers": 0, "unanswered": 0, "stale_answers": 0,
          "unseen_blocks": 0}


@dataclasses.dataclass
class Request:
    kind: str                     # "query" | "probe"
    tenant: str
    seeds: Tuple[int, ...]
    due: Optional[float] = None   # host clock (s); None: when sent
    sent: float = 0.0
    done: Optional[float] = None
    epoch_at_send: int = 0
    status: str = "unanswered"
    result: object = None         # the program's QueryResult
    resp_latency_ms: float = 0.0  # ServeResponse.latency_ms
    block: Optional[int] = None   # the block a probe follows

    @property
    def answered(self) -> bool:
        return self.result is not None and self.status in ("ok",
                                                           "deadline_miss")


@dataclasses.dataclass
class Ingest:
    block: int
    start: float
    end: float
    epoch: int                    # ctx.epoch once the ingest returned
    docs_after: int               # documents sent to the index so far
    warm: bool = False


@dataclasses.dataclass
class Run:
    """Everything a metric reader may read."""
    cell: Cell
    seed: int
    seconds: float
    t0: float = 0.0               # window open (host clock, s)
    t1: float = 0.0               # window close
    setup_seconds: float = 0.0
    requests: List[Request] = dataclasses.field(default_factory=list)
    ingests: List[Ingest] = dataclasses.field(default_factory=list)
    late_s: List[float] = dataclasses.field(default_factory=list)
    gc_pauses_s: List[float] = dataclasses.field(default_factory=list)
    compiles_in_window: int = 0
    memory_peak_bytes: int = 0
    device_kind: str = ""
    peaks: dict = dataclasses.field(default_factory=dict)
    trace: object = None          # bench.trace.Summary

    @property
    def serving(self) -> dict:
        return self.cell.config["serving"]

    @property
    def capacity(self) -> int:
        return capacity(self.cell.config, self.cell.mix, self.seconds)

    def window_requests(self) -> List[Request]:
        return [r for r in self.requests if self.t0 <= r.due < self.t1]

    def window_blocks(self) -> List[Ingest]:
        return [g for g in self.ingests if not g.warm]


def capacity(config: dict, mix: dict, seconds: float) -> int:
    """Documents the index is built to hold: the corpus, plus every block
    the mix ingests (its warm-up block included) when it ingests."""
    n = int(config["corpus"]["n_docs"])
    ing = mix.get("ingest")
    if not ing:
        return n
    slack = int(config["capacity"]["ingest_slack_docs"])
    need = (T.n_blocks(mix, seconds) + 1) * int(ing["block_docs"])
    if need > slack:
        raise ValueError(
            f"{seconds} s of ingest needs {need} docs of slack, the "
            f"configuration holds {slack}")
    return n + slack


def release_host_memory() -> None:
    """Hand freed heap memory back to the system before the reference
    runs: the program's set-up leaves gigabytes of freed host buffers."""
    gc.collect()
    try:
        ctypes.CDLL("libc.so.6").malloc_trim(0)
    except (OSError, AttributeError):
        pass


def host_memory() -> Dict[str, float]:
    """This process's resident and peak resident host memory, GB."""
    out = {}
    with open("/proc/self/status") as f:
        for line in f:
            if line.startswith(("VmRSS:", "VmHWM:")):
                key, kb = line.split()[:2]
                out[key[:-1]] = int(kb) / 1e6
    return out


def say(key: str, **fields) -> None:
    print(f"{key}: " + " ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)


def _edges(result) -> List[R.Edge]:
    net = result.network
    v = np.asarray(net.valid)
    return list(zip(np.asarray(net.src)[v].tolist(),
                    np.asarray(net.dst)[v].tolist(),
                    np.asarray(net.weight)[v].tolist()))


# -- set-up -------------------------------------------------------------------

class Built:
    """The corpus, the tenants' seed pools and the program's context."""

    def __init__(self, cell: Cell, seed: int, seconds: float):
        from repro.core import QueryContext
        cfg, mix = cell.config, cell.mix
        spec = cfg["corpus"]
        self.vocab = int(spec["vocab"])
        self.docs = C.make_corpus(seed, spec)
        n = self.docs.n_docs
        ing = mix.get("ingest")
        self.blocks = (C.make_blocks(seed, spec, T.n_blocks(mix, seconds) + 1,
                                     int(ing["block_docs"])) if ing else [])
        self.ctx = QueryContext.from_docs(
            self.docs.as_lists(), self.vocab,
            capacity=capacity(cfg, mix, seconds))
        self.scope_lo: Dict[str, int] = {}
        self.pools: Dict[str, np.ndarray] = {}
        top = int(mix["seeds"]["top"])
        for t in mix["tenants"]:
            lo = max(0, n - int(t["newest_docs"])) if "newest_docs" in t else 0
            if "newest_docs" in t:
                self.ctx.define_scope(t["name"], np.arange(lo, n))
            self.scope_lo[t["name"]] = lo
            df = C.doc_freq(self.docs.slice(lo, n), self.vocab)
            self.pools[t["name"]] = T.top_terms(df, top)

    def probe_seed(self, block: int) -> int:
        return int(T.top_terms(C.doc_freq(self.blocks[block], self.vocab),
                               1)[0])


def make_server(cell: Cell, built: Built):
    """A ``CoocServer`` with the mix's tenants, deadline and queue bound.
    The count method goes to ``ServerConfig`` while it has such a field."""
    from repro.serve import (AdmissionPolicy, CoocServer, ServerConfig,
                             TenantConfig)
    mix, serving = cell.mix, cell.config["serving"]
    kw = dict(depth=serving["depth"], topk=serving["topk"],
              beam=serving["beam"], q_batch=serving["q_batch"],
              default_deadline_ms=float(mix["deadline_ms"]),
              policy=AdmissionPolicy(max_queue_depth=mix["max_queue_depth"]))
    if "method" in {f.name for f in dataclasses.fields(ServerConfig)}:
        kw["method"] = mix["method"]
    tenants = [TenantConfig(t["name"], scope=t["name"]
                            if "newest_docs" in t else None)
               for t in mix["tenants"]]
    return CoocServer(built.ctx, tenants=tenants, config=ServerConfig(**kw))


# -- the window ---------------------------------------------------------------

class Driver:
    """Sends the mix's traffic to a started server and records it."""

    def __init__(self, run: Run, built: Built, server, annotate: bool):
        import jax
        self.run, self.built, self.server = run, built, server
        self.tasks: List[asyncio.Task] = []
        # the client's spans in the profiler's trace, when one is taken
        self.trace_annotation = (jax.profiler.TraceAnnotation if annotate
                                 else lambda name: contextlib.nullcontext())

    async def request(self, req: Request) -> None:
        req.sent = time.perf_counter()
        if req.due is None:             # sent when due
            req.due = req.sent
        req.epoch_at_send = self.server.ctx.epoch
        self.run.requests.append(req)
        with self.trace_annotation("bench.request"):
            resp = await self.server.submit(req.tenant,
                                            {"seeds": list(req.seeds)})
        req.done = time.perf_counter()
        req.status, req.result = resp.status, resp.result
        req.resp_latency_ms = resp.latency_ms

    async def ingest(self, block: int, warm: bool) -> None:
        docs = self.built.blocks[block]
        max_len = int(self.run.cell.config["ingest"]["max_len"])
        start = time.perf_counter()
        with self.trace_annotation("bench.ingest"):
            await self.server.ingest(self.run.cell.mix["tenants"][0]["name"],
                                     docs.as_lists(), max_len=max_len)
        end = time.perf_counter()
        before = (self.run.ingests[-1].docs_after if self.run.ingests
                  else self.built.docs.n_docs)
        self.run.ingests.append(Ingest(block, start, end,
                                       self.server.ctx.epoch,
                                       before + docs.n_docs, warm))

    async def warm_up(self) -> None:
        for t in self.run.cell.mix["tenants"]:
            pool = self.built.pools[t["name"]]
            resp = await self.server.submit(t["name"],
                                            {"seeds": [int(pool[0])]})
            if not resp.ok:
                raise RuntimeError(f"warm-up request of tenant {t['name']} "
                                   f"came back {resp.status} ({resp.reason})")
        if self.built.blocks:
            await self.ingest(len(self.built.blocks) - 1, warm=True)
            t = self.run.cell.mix["tenants"][0]["name"]
            resp = await self.server.submit(
                t, {"seeds": [int(self.built.pools[t][0])]})
            if not resp.ok:
                raise RuntimeError("warm-up request after the warm-up ingest "
                                   f"came back {resp.status}")

    async def sleep_until(self, t: float) -> None:
        dt = t - time.perf_counter()
        if dt > 0:
            await asyncio.sleep(dt)

    async def open_loop(self, plan: List[T.Planned]) -> None:
        for p in plan:
            due = self.run.t0 + p.due_s
            await self.sleep_until(due)
            self.run.late_s.append(time.perf_counter() - due)
            self.spawn(self.request(Request("query", p.tenant, p.seeds, due)))

    async def client(self, walk: T.SeedWalk) -> None:
        while time.perf_counter() < self.run.t1:
            tenant, seeds = walk.next()
            await self.request(Request("query", tenant, seeds))

    async def ingest_loop(self) -> None:
        period = float(self.run.cell.mix["ingest"]["period_s"])
        for i in range(T.n_blocks(self.run.cell.mix, self.run.seconds)):
            due = self.run.t0 + (i + 1) * period
            await self.sleep_until(due)
            await self.ingest(i, warm=False)
            seed = self.built.probe_seed(i)
            self.spawn(self.request(Request(
                "probe", self.run.cell.mix["tenants"][0]["name"], (seed,),
                block=i)))

    def spawn(self, coro) -> None:
        self.tasks.append(asyncio.create_task(coro))

    async def window(self) -> None:
        mix, run = self.run.cell.mix, self.run
        if mix["loop"] == "open":
            self.spawn(self.open_loop(T.open_loop(
                mix, run.seconds, run.seed, self.built.pools)))
        elif mix["loop"] == "closed":
            walk = T.SeedWalk(mix, run.seed, self.built.pools)
            for _ in range(int(mix["clients"])):
                self.spawn(self.client(walk))
        else:
            raise ValueError(f"unknown loop {mix['loop']!r}")
        if mix.get("ingest"):
            self.spawn(self.ingest_loop())
        await self.sleep_until(run.t1)
        with self.trace_annotation("bench.window.close"):
            pass
        # every request due in the window is waited for, a while past
        # the close; tasks still open then are cancelled and unanswered
        deadline = run.t1 + DRAIN_S
        while True:
            pending = [t for t in self.tasks if not t.done()]
            if not pending:
                break
            left = deadline - time.perf_counter()
            if left <= 0:
                for t in pending:
                    t.cancel()
                await asyncio.gather(*pending, return_exceptions=True)
                break
            await asyncio.wait(pending, timeout=left)
        for t in self.tasks:
            if t.done() and not t.cancelled() and t.exception() is not None:
                raise t.exception()


async def _serve(run: Run, built: Built, t_start: float,
                 trace_dir: Optional[str]) -> None:
    import jax
    server = make_server(run.cell, built)
    await server.start()
    drv = Driver(run, built, server, annotate=bool(trace_dir))
    stopped = False
    try:
        await drv.warm_up()
        if trace_dir:
            start_profile(trace_dir)
        run.t0 = time.perf_counter() + 0.01
        run.t1 = run.t0 + run.seconds
        run.setup_seconds = run.t0 - t_start
        await drv.sleep_until(run.t0)
        with drv.trace_annotation("bench.window.open"):
            pass
        gc_start = [0.0]

        def on_gc(phase, info):       # full collections stop every thread
            if info["generation"] == 2:
                if phase == "start":
                    gc_start[0] = time.perf_counter()
                else:
                    run.gc_pauses_s.append(time.perf_counter() - gc_start[0])
        gc.callbacks.append(on_gc)

        def on_event(event, secs, **kw):  # nothing should compile now
            if event == COMPILE_EVENT:
                run.compiles_in_window += 1
        jax.monitoring.register_event_duration_secs_listener(on_event)
        try:
            await drv.window()
        finally:
            gc.callbacks.remove(on_gc)
            jax.monitoring.unregister_event_duration_listener(on_event)
            if trace_dir:
                await asyncio.get_running_loop().run_in_executor(
                    None, jax.profiler.stop_trace)
        run.memory_peak_bytes = peak_bytes(run.cell.chips)
        await server.stop(drain=not any(r.done is None
                                        for r in run.requests))
        stopped = True
    finally:
        if not stopped:
            await server.stop(drain=False)


def start_profile(trace_dir: str) -> None:
    """The profiler with Python's own calls left untraced: the host keeps
    its runtime events and the benchmark's annotations."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(trace_dir, profiler_options=opts)


def peak_bytes(chips: int) -> int:
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()[:chips]]
    return int(max(peaks))


# -- the check ----------------------------------------------------------------

def docs_at(run: Run, n0: int, epoch: int) -> int:
    """Documents an answer at ``epoch`` must reflect."""
    n = n0
    for g in run.ingests:
        if g.epoch <= epoch:
            n = max(n, g.docs_after)
    return n


def first_visible(run: Run, block: Ingest) -> Optional[Request]:
    """The first answer, among requests sent once the block's ingest had
    begun, whose epoch includes the block."""
    seen = [r for r in run.requests if r.answered and r.sent >= block.start
            and r.result.epoch >= block.epoch]
    return min(seen, key=lambda r: r.done) if seen else None


def to_check(run: Run) -> List[Request]:
    """What is compared: the largest network, and samples drawn from the
    seed of the probes (``probe_sample``) and of the other answers
    (``check_sample``)."""
    mix = run.cell.mix
    answered = [r for r in run.window_requests() if r.answered]
    if not answered:
        return []
    big = max(answered, key=lambda r: int(np.asarray(
        r.result.network.valid).sum()))
    rng = C.rng_for(run.seed, 4)
    chosen = [big]
    for kind, n in (("probe", mix.get("probe_sample", 0)),
                    ("query", mix["check_sample"])):
        pool = [r for r in answered if r.kind == kind and r is not big]
        chosen += [pool[i] for i in sorted(rng.permutation(len(pool))[:n])]
    return chosen


def check(run: Run, built: Built, controls=(None,)
          ) -> Dict[Optional[str], dict]:
    """The numbers compared, each against ``LIMITS``, for the program's
    answers (``None``) and for each named control: the reference, with
    one guarantee broken, put in the program's place.  ``bf16_counts``
    holds counts in bfloat16, ``scope_ignored`` answers scoped tenants
    over the whole corpus, ``stale_epoch`` answers one ingest behind the
    epoch an answer reports."""
    window = run.window_requests()
    n0 = built.docs.n_docs
    checked = to_check(run)
    all_docs = C.concat([built.docs] + [built.blocks[g.block]
                                        for g in run.ingests])
    hidx = R.build_index(all_docs.tokens, all_docs.ptr, built.vocab)
    shape = {k: run.serving[k] for k in ("depth", "topk", "beam")}
    shape["dedup"] = True

    def query(r: Request, ctl: Optional[str]) -> R.Query:
        epoch = r.result.epoch
        if ctl == "stale_epoch":
            epoch = max([g.epoch for g in run.ingests if g.epoch < epoch],
                        default=-1)
        lo = 0 if ctl == "scope_ignored" else built.scope_lo[r.tenant]
        return R.Query(r.seeds, lo, docs_at(run, n0, epoch),
                       bf16=ctl == "bf16_counts")

    t = time.perf_counter()
    want = R.answer_all(hidx, [query(r, None) for r in checked], shape)
    say("check", compared=len(checked),
        answered=sum(r.answered for r in window), due=len(window),
        reference_s=time.perf_counter() - t)
    common = {
        "unanswered": sum(not r.answered for r in window),
        "stale_answers": sum(r.result.epoch < r.epoch_at_send
                             for r in window if r.answered),
        "unseen_blocks": sum(first_visible(run, g) is None
                             for g in run.window_blocks()),
    }
    out = {}
    for ctl in controls:
        got = ([_edges(r.result) for r in checked] if ctl is None else
               R.answer_all(hidx, [query(r, ctl) for r in checked], shape))
        out[ctl] = {"wrong_answers": sum(g != w for g, w in zip(got, want)),
                    **common}
    return out


# -- a whole run --------------------------------------------------------------

def run_cell(root, cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float, controls=()) -> dict:
    """One run of ``cell``; returns the result line's object.  Raises on
    any failure of the program or the harness.  Each of ``controls`` is
    compared too, and its numbers are returned under ``controls``."""
    import jax
    dev = jax.devices()[0]
    run = Run(cell, seed, float(seconds),
              device_kind=dev.device_kind)
    run.peaks = peaks_for(root, dev.device_kind)
    say("device", platform=dev.platform, kind=repr(dev.device_kind),
        chips=cell.chips, jax=jax.__version__,
        compile_cache=jax.config.jax_compilation_cache_dir)

    built = Built(cell, seed, seconds)
    say("built", docs=built.docs.n_docs, terms=built.vocab,
        tokens=len(built.docs.tokens), words=built.ctx.index.n_words,
        blocks=len(built.blocks), since_start_s=time.perf_counter() - t_start)
    say("host_gb", stage="built", **host_memory())
    release_host_memory()
    say("host_gb", stage="trimmed", **host_memory())

    with tempfile.TemporaryDirectory(prefix="bench-trace-") as tdir:
        asyncio.run(_serve(run, built, t_start, tdir if trace else None))
        built.ctx = None
        release_host_memory()
        if trace:
            from bench import trace as TR
            run.trace = TR.summarize_dir(tdir, run)
    report(run)
    say("host_gb", stage="served", **host_memory())
    by_control = check(run, built, (None, *controls))
    say("host_gb", stage="checked", **host_memory())
    numbers = by_control[None]
    metrics = read_metrics(cell.per_layer if trace else cell.end_to_end, run)
    correct = all(numbers[k] <= LIMITS[k] for k in LIMITS)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": cell.chips, "memory_peak_bytes": run.memory_peak_bytes}
    out = {"correct": bool(correct),
           "attempted": len(run.window_requests()),
           "failed": sum(not r.answered for r in run.window_requests()),
           "metrics": metrics, "device": device}
    if trace and run.trace is not None:
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
        out["breakdown"] = run.trace.breakdown
    if controls:
        out["controls"] = {c: {k: {"value": by_control[c][k],
                                   "limit": LIMITS[k]} for k in LIMITS}
                           for c in controls}
    out["checks"] = {k: {"value": numbers[k], "limit": LIMITS[k]}
                     for k in LIMITS}
    for k in LIMITS:
        print(f"check {k}: {numbers[k]} (limit {LIMITS[k]})",
              file=sys.stderr, flush=True)
    return out


def report(run: Run) -> None:
    """Earlier lines: what the last line leaves out."""
    w = run.window_requests()
    lat = [(r.done - r.due) * 1e3 for r in w if r.answered]
    if lat:
        p50, p90, p99 = np.percentile(lat, [50, 90, 99])
        say("latency_ms", n=len(lat), p50=p50, p90=p90, p99=p99,
            max=max(lat))
    if run.late_s:
        say("generator_late_ms", mean=1e3 * float(np.mean(run.late_s)),
            max=1e3 * max(run.late_s))
    say("compiles_in_window", n=run.compiles_in_window)
    say("gc_full_collections", n=len(run.gc_pauses_s),
        max_ms=1e3 * max(run.gc_pauses_s, default=0.0),
        total_ms=1e3 * sum(run.gc_pauses_s))
    statuses: Dict[str, int] = {}
    for r in w:
        statuses[r.status] = statuses.get(r.status, 0) + 1
    say("requests", due=len(w), **statuses)
    blocks = run.window_blocks()
    if blocks:
        say("ingest", blocks=len(blocks), mean_ms=1e3 * float(np.mean(
            [g.end - g.start for g in blocks])))
    say("memory", peak_bytes_in_use=run.memory_peak_bytes,
        setup_seconds=run.setup_seconds)
