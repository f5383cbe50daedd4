"""CoocEngine — plan-aware, micro-batched co-occurrence query serving.

Design notes (see README.md §Design):

The paper's target is web-grade real-time construction over a LIVE index:
many concurrent, *heterogeneous* queries, continuous ingest.  One-query-
at-a-time jit calls leave the accelerator mostly idle — the throughput
lives in batched postings evaluation (Billerbeck et al., PAPERS.md) — and
an engine that freezes (depth, topk, beam, method) at construction needs
one engine (and one compile) per parameter combination.  This engine is
plan-aware instead:

* queries are typed :class:`~repro.core.query.QuerySpec` objects;
  :meth:`submit` returns a :class:`CoocFuture` (``.done()`` /
  ``.result() -> QueryResult``);
* each :meth:`step` groups queued requests by :class:`PlanKey`
  (depth/topk/beam/dedup/method — everything that shapes the compiled
  executable), admits up to ``q_batch`` of the head plan into a fixed
  ``(Q, beam)`` seed batch (idle slots padded with -1 seeds, which produce
  no edges by construction) and runs ONE jitted ``bfs_construct_batch``
  from the **per-plan executor cache** — compile count grows with distinct
  plans, never with query count;
* the per-epoch artifacts (gemm's dense incidence) come from the shared
  :class:`repro.core.QueryContext` — cached, sharded, rebuilt only on
  ingest — so a warm engine performs zero unpacks per query;
* per-query latency and batch-occupancy statistics are kept in fixed-size
  ring buffers (a long-lived engine holds O(window) state, not O(queries)).

The jit signature per plan is shape-stable: always ``(q_batch, beam)``, so
the engine never retraces as load varies.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from collections import OrderedDict, deque
from typing import Callable, Deque, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (
    CoocNetwork,
    PackedIndex,
    QueryContext,
    bfs_construct_batch,
)
from repro.core.query import (
    PlanKey,
    QueryResult,
    QuerySpec,
    canonical_exec_key,
    get_count_method,
)
from repro.serve.metrics import percentile_ms


class EngineClosedError(RuntimeError):
    """Raised by :meth:`CoocEngine.submit` after :meth:`CoocEngine.shutdown`,
    and set as the error on any request flushed by a non-draining shutdown."""


@dataclasses.dataclass
class CoocRequest:
    """Engine-internal record of one submitted query."""
    rid: int
    spec: QuerySpec
    t_submit: float = 0.0
    t_done: float = 0.0
    result: Optional[QueryResult] = None
    error: Optional[Exception] = None

    @property
    def seed_terms(self) -> List[int]:
        return list(self.spec.seeds)

    @property
    def edges(self) -> Optional[Dict[Tuple[int, int], int]]:
        return self.result.edges() if self.result is not None else None

    @property
    def latency_ms(self) -> float:
        return (self.t_done - self.t_submit) * 1e3

    @property
    def batch_occupancy(self) -> int:
        return self.result.batch_occupancy if self.result is not None else 0


class CoocFuture:
    """Handle for a submitted query.

    ``done()`` is non-blocking; ``result()`` drives the owning engine's
    step loop until this request is served, then returns the
    :class:`QueryResult` (repeat calls return the same object).  A request
    that FAILED at execution (e.g. its scope was dropped between submit
    and step) raises that error from ``result()`` instead — repeat calls
    re-raise; the rest of the queue is unaffected.
    """

    __slots__ = ("_engine", "_req")

    def __init__(self, engine: "CoocEngine", req: CoocRequest):
        self._engine = engine
        self._req = req

    @property
    def rid(self) -> int:
        return self._req.rid

    @property
    def spec(self) -> QuerySpec:
        return self._req.spec

    def done(self) -> bool:
        return self._req.result is not None or self._req.error is not None

    def result(self) -> QueryResult:
        while self._req.result is None and self._req.error is None:
            if self._engine.step() == 0:
                raise RuntimeError(
                    f"request {self._req.rid} is not queued in its engine "
                    "(queue drained without serving it)")   # pragma: no cover
        if self._req.error is not None:
            raise self._req.error
        return self._req.result


@dataclasses.dataclass
class EngineStats:
    n: int
    p50_ms: float
    p95_ms: float
    p99_ms: float
    max_ms: float
    batches: int = 0
    mean_occupancy: float = 0.0   # mean admitted queries per executed batch
    compiled_plans: int = 0       # distinct executables currently cached
    failed_total: int = 0         # requests resolved onto an error (cumulative)
    p999_ms: float = 0.0          # tail quantile (shares percentile_ms with serve.metrics)
    window: int = 0               # ring-buffer capacity the quantiles cover
    plan_evictions: int = 0       # executables dropped by the compile budget (cumulative)


class CoocEngine:
    """Plan-aware micro-batched BFS query engine over a shared QueryContext.

    The ``depth/topk/beam/dedup/method`` constructor arguments are only the
    DEFAULT spec applied when :meth:`submit` receives a bare seed list —
    any mix of QuerySpecs flows through the same engine, grouped by plan.
    ``window`` bounds the stats ring buffers (and the ``finished`` log).
    ``compile_budget`` bounds the per-plan executor cache (LRU): diverse or
    hostile plan traffic evicts-and-recompiles instead of growing compiled
    state without bound.  ``None`` leaves the cache unbounded.
    """

    def __init__(self, ctx, *, depth: int = 3, topk: int = 16, beam: int = 32,
                 q_batch: int = 8, method: str = "gemm", dedup: bool = True,
                 on_overflow: str = "raise", window: int = 2048,
                 compile_budget: Optional[int] = None):
        get_count_method(method)        # unknown method -> ValueError
        if compile_budget is not None and compile_budget < 1:
            raise ValueError(
                f"compile_budget must be >= 1 or None, got {compile_budget}")
        if isinstance(ctx, PackedIndex):
            ctx = QueryContext(ctx)
        self.ctx: QueryContext = ctx
        self.depth, self.topk, self.beam = depth, topk, beam
        self.dedup, self.method = dedup, method
        self.q_batch = q_batch
        self.on_overflow = on_overflow
        self.window = window
        self.compile_budget = compile_budget
        self.queue: List[CoocRequest] = []
        self.finished: Deque[CoocRequest] = deque(maxlen=window)
        self.latencies_ms: Deque[float] = deque(maxlen=window)
        self.batch_occupancy: Deque[int] = deque(maxlen=window)
        self.served_total = 0
        self.batches_total = 0
        self.failed_total = 0
        self.plan_evictions_total = 0
        self._next_rid = 0
        self._closed = False
        self._executors: "OrderedDict[PlanKey, callable]" = OrderedDict()
        #: optional hook fired with each LRU-evicted exec key (the server
        #: uses it to drop the key's step-time history, which would
        #: otherwise predict warm times for a plan that must recompile)
        self.on_plan_evict: Optional[Callable[[PlanKey], None]] = None

    # -- plan cache ---------------------------------------------------------

    @property
    def compiled_plans(self) -> int:
        """Size of the per-plan executor cache: grows with DISTINCT
        executable identities served — never with query count, and never
        past ``compile_budget`` (acceptance metric)."""
        return len(self._executors)

    @property
    def closed(self) -> bool:
        return self._closed

    def _executor(self, key: PlanKey):
        """Jitted executable for ``key``, from the LRU-bounded cache.

        The cache key is :func:`canonical_exec_key` — the scope NAME is
        erased entirely, because :meth:`step` always passes a scope bitmap
        operand (the named scope's, or the context's cached all-ones mask
        for unscoped plans, which is the identity under AND).  Scoped and
        unscoped plans with equal shape fields therefore share ONE
        executable: queries over "7d", "30d" and no scope at all never
        compile thrice.  The context's mesh (if any) is baked into every
        executable: a mesh-bearing engine serves every plan sharded,
        bit-exactly.

        Dropping an evicted entry drops its ``jax.jit`` wrapper object,
        which owns the compiled-executable cache — eviction genuinely
        frees the compilation, and the next request for that plan pays a
        fresh compile (bit-exact round trip; see tests).
        """
        exec_key = canonical_exec_key(key)
        fn = self._executors.get(exec_key)
        if fn is not None:
            self._executors.move_to_end(exec_key)
            return fn
        step = functools.partial(
            bfs_construct_batch, depth=key.depth, topk=key.topk,
            beam=key.beam, dedup=key.dedup, method=key.method,
            mesh=self.ctx.mesh)
        # the executable's name in compile events and profiler traces
        step.__name__ = (f"cooc_plan_{key.method}_d{key.depth}_k{key.topk}"
                         f"_b{key.beam}")
        fn = jax.jit(step)
        self._executors[exec_key] = fn
        if self.compile_budget is not None:
            while len(self._executors) > self.compile_budget:
                evicted, _ = self._executors.popitem(last=False)
                self.plan_evictions_total += 1
                if self.on_plan_evict is not None:
                    self.on_plan_evict(evicted)
        return fn

    # -- query path ---------------------------------------------------------

    def make_spec(self, seed_terms: Sequence[int], **overrides) -> QuerySpec:
        """Engine defaults + per-query overrides -> a validated QuerySpec."""
        params = dict(depth=self.depth, topk=self.topk, beam=self.beam,
                      dedup=self.dedup, method=self.method)
        params.update(overrides)
        return QuerySpec(seeds=tuple(int(s) for s in seed_terms), **params)

    def submit(self, query: Union[QuerySpec, Sequence[int]],
               **overrides) -> CoocFuture:
        """Queue a query; returns its CoocFuture.

        ``query`` is a QuerySpec, or a bare seed-term sequence completed
        with the engine defaults (plus keyword overrides).  Validation
        (empty seeds, seeds exceeding the beam, unknown method) happens
        here, in QuerySpec — invalid queries never reach the device.
        """
        if self._closed:
            raise EngineClosedError(
                "engine is shut down; create a new CoocEngine over the "
                "context to serve further queries")
        if isinstance(query, QuerySpec):
            if overrides:
                query = dataclasses.replace(query, **overrides)
            spec = query
        else:
            spec = self.make_spec(query, **overrides)
        if spec.scope is not None and spec.scope not in self.ctx.scope_names():
            # same policy as the rest of QuerySpec validation: fail at
            # submit, never after the request is admitted (a step-time
            # failure would drop the whole micro-batch's futures)
            raise KeyError(
                f"unknown scope {spec.scope!r}; define/tag it on the "
                f"context before submitting (defined: "
                f"{list(self.ctx.scope_names())})")
        req = CoocRequest(self._next_rid, spec, t_submit=time.perf_counter())
        self._next_rid += 1
        self.queue.append(req)
        return CoocFuture(self, req)

    def step(self) -> int:
        """Serve one micro-batch: admit up to q_batch queued queries of the
        head-of-queue PLAN, run its cached jitted executable once,
        distribute QueryResults.  Returns #requests resolved (served, or
        failed onto their futures).

        Timed as one ``cooc.engine.step`` span on the context's log, tiled
        by four children: ``cooc.step.prepare`` (plan, scope bitmap, seeds,
        operands), ``cooc.step.dispatch`` (the executable's call; a compile
        lands here), ``cooc.step.device`` (the host blocked on the device)
        and ``cooc.step.fetch`` (results to the host)."""
        if not self.queue:
            return 0
        spans = self.ctx.spans
        with spans.span("cooc.engine.step"):
            with spans.span("cooc.step.prepare"):
                key = self.queue[0].spec.plan_key
                kwargs = {}
                if key.scope is not None:
                    # resolved BEFORE the queue is mutated; grouping by plan
                    # key guarantees the whole batch shares this one bitmap.
                    # A scope dropped between submit and step poisons
                    # exactly that plan's requests — they fail onto their
                    # futures and leave the queue, so one bad scope can
                    # never wedge the engine.
                    try:
                        kwargs["scope_mask"] = self.ctx.scope(key.scope)
                    except KeyError as e:
                        poisoned = [r for r in self.queue
                                    if r.spec.plan_key == key]
                        self.queue = [r for r in self.queue
                                      if r.spec.plan_key != key]
                        return self._fail_requests(poisoned, e)
                else:
                    # unscoped plans pass the context's cached all-ones
                    # bitmap — the identity under AND — so they trace with
                    # the same operand signature as scoped plans and share
                    # their executable
                    kwargs["scope_mask"] = self.ctx.full_mask()
                admitted: List[CoocRequest] = []
                rest: List[CoocRequest] = []
                for req in self.queue:
                    if (req.spec.plan_key == key
                            and len(admitted) < self.q_batch):
                        admitted.append(req)
                    else:
                        rest.append(req)
                self.queue = rest

                seeds = np.full((self.q_batch, key.beam), -1, np.int32)
                for i, req in enumerate(admitted):
                    seeds[i] = req.spec.seed_row()
                operands = self.ctx.operands(key.method)
            with spans.span("cooc.step.dispatch"):
                net = self._executor(key)(self.ctx.index, jnp.asarray(seeds),
                                          operands=operands, **kwargs)
            with spans.span("cooc.step.device"):
                jax.block_until_ready(net.src)
            with spans.span("cooc.step.fetch"):
                src = np.asarray(net.src).reshape(self.q_batch, -1)
                dst = np.asarray(net.dst).reshape(self.q_batch, -1)
                w = np.asarray(net.weight).reshape(self.q_batch, -1)
                valid = np.asarray(net.valid).reshape(self.q_batch, -1)
                t_done = time.perf_counter()
                occ = len(admitted)
                self.batch_occupancy.append(occ)
                self.batches_total += 1
                for i, req in enumerate(admitted):
                    req.t_done = t_done
                    req.result = QueryResult(
                        network=CoocNetwork(src[i], dst[i], w[i], valid[i]),
                        spec=req.spec, epoch=self.ctx.epoch,
                        latency_ms=req.latency_ms, batch_occupancy=occ)
                    self.latencies_ms.append(req.latency_ms)
                    self.finished.append(req)
                    self.served_total += 1
        return occ

    def _fail_requests(self, reqs: List[CoocRequest], error: Exception) -> int:
        """Resolve ``reqs`` onto their futures with ``error``.  Failures
        are resolved requests: they enter the finished log, the latency
        window, and the failure counter, so EngineStats never silently
        under-reports a poisoned plan or a flushed shutdown."""
        t_done = time.perf_counter()
        for r in reqs:
            r.error = error
            r.t_done = t_done
            self.latencies_ms.append(r.latency_ms)
            self.finished.append(r)
        self.failed_total += len(reqs)
        return len(reqs)

    def run_until_drained(self, max_steps: int = 100000) -> List[CoocRequest]:
        """Step until the queue is empty; returns the (window-bounded)
        finished log as a list snapshot."""
        for _ in range(max_steps):
            if not self.queue:
                break
            self.step()
        return list(self.finished)

    def shutdown(self, *, drain: bool = True) -> List[CoocRequest]:
        """Close the engine: subsequent :meth:`submit` calls raise
        :class:`EngineClosedError`.

        With ``drain=True`` (default) every queued request is SERVED
        before the engine closes — graceful shutdown.  With
        ``drain=False`` queued requests are flushed: each pending future
        resolves to an :class:`EngineClosedError` instead of hanging a
        caller blocked in ``result()`` forever.  Idempotent; returns the
        finished-log snapshot either way.
        """
        self._closed = True
        if drain:
            return self.run_until_drained()
        flushed, self.queue = self.queue, []
        if flushed:
            self._fail_requests(flushed, EngineClosedError(
                "engine shut down (drain=False) before this request was "
                "served"))
        return list(self.finished)

    def query(self, seed_terms: Union[QuerySpec, Sequence[int]],
              **overrides) -> Dict[Tuple[int, int], int]:
        """Synchronous convenience: submit + drive to completion + return
        this query's edge dict (earlier queued queries are served first,
        FIFO within their plan)."""
        return self.submit(seed_terms, **overrides).result().edges()

    # -- ingest path --------------------------------------------------------

    def ingest_docs(self, doc_terms: Sequence[Sequence[int]], *,
                    max_len: int = 64, on_long: str = "raise",
                    doc_window=None, scope=None):
        """Real-time ingest through the context: host-side capacity check
        (raise/grow per ``on_overflow``), jitted scatter, epoch bump — the
        next batch sees the new docs and rebuilds the dense cache once.

        ``doc_window``/``scope`` pass through to
        :meth:`QueryContext.ingest_docs` (sliding-window doc cap, scope
        tagging); returns the new docs' slot ids.  Named ``doc_window``
        here — NOT ``window`` — because the engine constructor's
        ``window=`` already sizes the stats ring buffers."""
        return self.ctx.ingest_docs(doc_terms, max_len=max_len,
                                    on_overflow=self.on_overflow,
                                    on_long=on_long, window=doc_window,
                                    scope=scope)

    # -- stats --------------------------------------------------------------

    def stats(self) -> EngineStats:
        """Latency/occupancy percentiles over the ring-buffer window (the
        last ``window`` queries/batches, the capacity surfaced on
        ``EngineStats.window``); cumulative totals live on
        ``served_total`` / ``batches_total`` / ``plan_evictions_total``.

        Quantiles come from :func:`repro.serve.metrics.percentile_ms` —
        the ONE quantile implementation shared with the server metrics
        and the serving bench, so p50/p99/p999 can never disagree across
        layers.  (The former hand-rolled ``xs[int(n * p)]`` index was off
        by one at exact rank multiples.)
        """
        xs = np.fromiter(self.latencies_ms, dtype=np.float64)
        if xs.size == 0:
            return EngineStats(0, 0, 0, 0, 0,
                               compiled_plans=self.compiled_plans,
                               failed_total=self.failed_total,
                               window=self.window,
                               plan_evictions=self.plan_evictions_total)
        p50, p95, p99, p999 = percentile_ms(xs)
        occ = self.batch_occupancy
        return EngineStats(int(xs.size), p50, p95, p99,
                           float(xs.max()), batches=len(occ),
                           mean_occupancy=float(np.mean(occ)) if occ else 0.0,
                           compiled_plans=self.compiled_plans,
                           failed_total=self.failed_total,
                           p999_ms=p999, window=self.window,
                           plan_evictions=self.plan_evictions_total)
