"""Spans and counters of the serving path (``repro.core.spans``): the
ring and its counters, the spans a served run records, how they tile the
engine step, and their export through the server's snapshot and render.

One small served run on the CPU (``served`` fixture) is shared by the
tests that read it: a warm request, a burst, an ingest and requests after
it, on the ``fused`` method so that the padded transpose is rebuilt per
ingest epoch.
"""
import asyncio

import jax.numpy as jnp
import pytest

from repro.core import QueryContext
from repro.core.spans import SpanLog, merge
from repro.data import synthetic_csl
from repro.serve import CoocEngine, CoocServer, ServerConfig, TenantConfig

STEP_CHILDREN = ("cooc.step.prepare", "cooc.step.dispatch",
                 "cooc.step.device", "cooc.step.fetch")
PTP = 'artifact_rebuilds_total{artifact="packed_t_pad"}'


def _ctx(n_docs=120, vocab=32, seed=7, **kw):
    return QueryContext.from_docs(synthetic_csl(n_docs, vocab, seed=seed),
                                  vocab, **kw)


def _config(**kw):
    cfg = dict(depth=1, topk=4, beam=8, q_batch=4, compile_budget=4,
               default_deadline_ms=120000.0, linger_ms=50.0, method="fused")
    cfg.update(kw)
    return ServerConfig(**cfg)


class _Recorder:
    """Wraps a lane's step-time model to keep what it observes."""

    def __init__(self, model):
        self.model, self.seen = model, []

    def observe(self, key, ms):
        self.seen.append(ms)
        self.model.observe(key, ms)

    def __getattr__(self, name):
        return getattr(self.model, name)


@pytest.fixture(scope="module")
def served():
    async def go():
        ctx = _ctx(capacity=512)
        server = CoocServer(ctx, [TenantConfig("t")], config=_config())
        lane = server._lanes["shared"]
        lane.model = _Recorder(lane.model)
        await server.start()
        assert (await server.submit("t", [1])).ok      # pays the compile
        ptp_warm = ctx.spans.counters[PTP]
        burst = await asyncio.gather(
            *[server.submit("t", [s]) for s in (2, 3, 4, 5, 6)])
        await server.ingest("t", [[1, 2, 3]] * 3, max_len=4)
        after = await asyncio.gather(
            *[server.submit("t", [s]) for s in (1, 2)])
        ptp_ingested = ctx.spans.counters[PTP]
        snap = server.snapshot()
        text = server.render_metrics()
        await server.stop()
        return dict(ctx=ctx, server=server, lane=lane, resps=burst + after,
                    snap=snap, text=text, ptp=(ptp_warm, ptp_ingested))
    return asyncio.run(go())


def test_span_log_ring_stays_at_its_window():
    log = SpanLog(window=3)
    for _ in range(5):
        with log.span("x") as s:
            pass
        assert s.ms >= 0 and s.end >= s.start
    assert len(log.ring("x")) == 3
    with pytest.raises(ValueError):
        SpanLog(window=0)


def test_counters_and_merge():
    a, b = SpanLog(), SpanLog()
    a.count("rebuilds_total", artifact="x")
    a.count("rebuilds_total", 2, artifact="x")
    b.count("rebuilds_total", artifact="x")
    b.count("plain_total")
    for log in (a, b):
        with log.span("y"):
            pass
    spans, counters = merge([a, b, a])          # a counted once
    assert counters == {'rebuilds_total{artifact="x"}': 4, "plain_total": 1}
    assert spans["y"].count == 2
    assert spans["y"].max_ms <= spans["y"].total_ms


def test_every_lane_and_engine_span_is_recorded(served):
    spans = served["snap"].spans
    batches = served["lane"].engine.batches_total
    assert spans["cooc.engine.step"].count == batches
    for name in STEP_CHILDREN + ("cooc.lane.batch", "cooc.lane.lock",
                                 "cooc.lane.resolve"):
        assert spans[name].count == batches, name
    assert spans["cooc.lane.ingest_lock"].count == 1
    assert spans["cooc.index.ingest"].count == 1
    assert spans["cooc.lane.idle"].count >= 1
    assert spans["cooc.lane.linger"].count >= 1
    assert spans["cooc.index.rebuild"].count >= 2


def test_step_children_tile_the_step(served):
    log = served["ctx"].spans
    children = [iv for name in STEP_CHILDREN for iv in log.ring(name)]
    for a, b in log.ring("cooc.engine.step"):
        inside = [(c, d) for c, d in children if a <= c and d <= b]
        assert len(inside) == 4
        covered = sum(d - c for c, d in inside)
        assert (b - a) - covered < 1e-3           # within 1 ms


def test_queue_ms_is_part_of_the_server_wait(served):
    for r in served["resps"]:
        assert r.ok
        wait = r.latency_ms - r.result.latency_ms
        assert 0 < r.result.queue_ms <= wait


def test_step_time_model_observes_the_batch_span(served):
    batch = [(b - a) * 1e3 for a, b in
             served["server"].spans.ring("cooc.lane.batch")]
    assert served["lane"].model.seen == pytest.approx(batch)


def test_packed_t_pad_rebuilt_once_per_ingest_epoch(served):
    warm, ingested = served["ptp"]
    assert warm == 1 and ingested == 2
    assert served["snap"].counters[PTP] == 2


def test_render_carries_spans_and_counters(served):
    text, snap = served["text"], served["snap"]
    n = snap.spans["cooc.engine.step"].count
    assert f'cooc_serve_span_count{{span="cooc.engine.step"}} {n}' in text
    assert 'cooc_serve_span_ms_total{span="cooc.lane.batch"} ' in text
    assert 'cooc_serve_span_ms_max{span="cooc.index.ingest"} ' in text
    assert f"cooc_serve_{PTP} 2" in text


def test_server_span_ring_stays_at_its_window():
    async def go():
        server = CoocServer(_ctx(), [TenantConfig("t")],
                            config=_config(metrics_window=2))
        await server.start()
        for s in (1, 2, 3):
            assert (await server.submit("t", [s])).ok
        await server.stop()
        return server

    server = asyncio.run(go())
    assert len(server.spans.ring("cooc.lane.batch")) == 2
    assert server.snapshot().spans["cooc.lane.batch"].count == 2


def test_standalone_engine_and_context_record_spans():
    ctx = _ctx(capacity=512)
    eng = CoocEngine(ctx, depth=1, topk=4, beam=8, q_batch=2)
    assert eng.query([1])
    ctx.ingest_docs([[1, 2]], max_len=4)        # pads, then ingest()
    assert eng.query([2])
    assert len(ctx.spans.ring("cooc.engine.step")) == 2
    assert len(ctx.spans.ring("cooc.index.ingest")) == 1
    ctx.ingest(jnp.asarray([[3, 4]], jnp.int32), jnp.asarray([True]))
    assert len(ctx.spans.ring("cooc.index.ingest")) == 2
    rebuilt = ctx.spans.counters['artifact_rebuilds_total{artifact="x_dense"}']
    assert rebuilt == 2


def test_two_servers_keep_their_own_spans():
    async def go():
        servers = [CoocServer(_ctx(seed=s), [TenantConfig("t")],
                              config=_config()) for s in (7, 11)]
        for srv in servers:
            await srv.start()
        assert (await servers[0].submit("t", [1])).ok
        for srv in servers:
            await srv.stop()
        return [srv.snapshot() for srv in servers]

    first, second = asyncio.run(go())
    assert first.spans["cooc.engine.step"].count == 1
    assert "cooc.engine.step" not in second.spans
