"""The metric readers' arithmetic: percentiles, rates, occupancy, the
roofline and the idle share, on hand-made runs and traces."""
import json

import numpy as np
import pytest

from bench import cost
from bench import trace as TR
from bench.harness import Ingest, Request, Run
from bench.spec import Cell, load_reader
from conftest import ROOT

CONFIG = json.loads((ROOT / "bench/configs/cooccur-csl.json").read_text())
MIX = json.loads((ROOT / "bench/mixes/steady-2tenant.json").read_text())
V5E = json.loads((ROOT / "bench/peaks.json").read_text())["devices"][
    "TPU v5 lite"]


class _Result:
    def __init__(self, latency_ms, occupancy, epoch=0):
        self.latency_ms, self.batch_occupancy, self.epoch = (
            latency_ms, occupancy, epoch)


def _run(mix=MIX, seconds=10.0):
    return Run(Cell("c", 1, CONFIG, mix, [], []), 1, seconds,
               t0=100.0, t1=100.0 + seconds, peaks=V5E)


def _req(due, latency_s, occ=8, status="ok", engine_ms=5.0):
    r = Request("query", "open", (1,), due, sent=due)
    if status is not None:
        r.done, r.status = due + latency_s, status
        r.result = _Result(engine_ms, occ)
        r.resp_latency_ms = latency_s * 1e3
    return r


def read(name, run):
    return load_reader(ROOT, name)(run)


def test_p90_is_linear_over_every_due_request():
    run = _run()
    run.requests = [_req(100.0 + i * 0.1, (i + 1) / 1000) for i in range(20)]
    run.requests.append(_req(99.0, 5.0))          # due before the window
    assert read("query_p90_ms", run) == pytest.approx(
        np.percentile(np.arange(1, 21), 90))      # 18.1
    run.requests[0].status = "shed"               # beyond every limit
    run.requests[0].result = None
    assert read("query_p90_ms", run) == pytest.approx(19.1)
    run.requests[1].status = "error"              # the 90th percentile
    run.requests[1].result = None                 # now lies among them
    assert read("query_p90_ms", run) is None


def test_served_counts_answers_done_inside_the_window():
    run = _run(seconds=10.0)
    run.requests = [_req(100.0 + i, 0.5) for i in range(10)]
    run.requests[-1].done = 111.0                  # answered after the close
    assert read("served_qps", run) == pytest.approx(0.9)


def test_occupancy_counts_batches_from_the_answers():
    run = _run()
    # one batch of 8, two of 2, one of 1: 13 requests in 4 batches
    occs = [8] * 8 + [2] * 4 + [1]
    run.requests = [_req(100.0 + i * 0.1, 0.1, occ=k)
                    for i, k in enumerate(occs)]
    assert read("batch_occupancy_pct", run) == pytest.approx(
        100 * 13 / 4 / 8)


def test_server_wait_and_step():
    run = _run()
    run.requests = [_req(100.0, 0.030, engine_ms=10.0),
                    _req(101.0, 0.050, engine_ms=20.0)]
    assert read("server_wait_ms", run) == pytest.approx(25.0)
    assert read("step_ms", run) == pytest.approx(15.0)


def test_visible_and_ingest_ms():
    mix = json.loads((ROOT / "bench/mixes/news-stream.json").read_text())
    run = _run(mix)
    run.ingests = [Ingest(-1, 90.0, 90.1, 1, 10, warm=True),
                   Ingest(0, 102.0, 102.2, 2, 20),
                   Ingest(1, 104.5, 104.6, 3, 30)]
    early = _req(101.0, 0.5)                       # sent before the ingest
    early.result.epoch = 2
    seen0 = _req(102.3, 0.7)                       # done 103.0, epoch 2
    seen0.result.epoch = 2
    seen1 = _req(104.7, 1.0)                       # done 105.7, epoch 3
    seen1.result.epoch = 3
    run.requests = [early, seen0, seen1]
    assert read("visible_ms", run) == pytest.approx((1000 + 1200) / 2)
    assert read("ingest_ms", run) == pytest.approx((200 + 100) / 2)
    run.requests = [early, seen0]                  # block 1 never seen
    assert read("visible_ms", run) is None


def _summary(ops, modules, window=(0.0, 1e9)):
    ev = [TR.Event("/host:CPU", "py", TR.OPEN, window[0], 0),
          TR.Event("/host:CPU", "py", TR.CLOSE, window[1], 0)]
    ev += [TR.Event("/device:TPU:0", TR.OPS_LINE, n, a, d) for n, a, d in ops]
    ev += [TR.Event("/device:TPU:0", TR.MODULES_LINE, n, a, d)
           for n, a, d in modules]
    return TR.summarize(ev)


def test_trace_busy_idle_and_step_count():
    # ops overlap; the last is cut by the window's close
    ops = [("_level_step_kernel", 0.1e9, 0.3e9), ("top_k", 0.2e9, 0.2e9),
           ("_level_step_kernel", 0.6e9, 0.2e9), ("copy", 0.9e9, 0.2e9)]
    mods = [("jit_cooc_plan_fused_d3_k16_b32", 0.1e9, 0.5e9),
            ("jit_cooc_plan_fused_d3_k16_b32", 0.6e9, 0.5e9)]
    s = _summary(ops, mods)
    assert s.window_s == pytest.approx(1.0)
    assert s.busy_s == pytest.approx(0.3 + 0.2 + 0.1)   # union, clipped
    assert s.runs(cost.STEP_PROGRAM) == pytest.approx(1.0 + 0.8)
    assert s.op_seconds(cost.COUNT_KERNELS) == pytest.approx(0.5)
    gaps = sorted(g for _, g in s.gaps)
    assert gaps == pytest.approx([0.1, 0.1, 0.2])
    run = _run()
    run.trace = s
    assert read("device_idle_pct", run) == pytest.approx(40.0)
    assert read("count_kernel_ms", run) == pytest.approx(500 / 1.8)
    floor = 3 * 65536 * (-(-396209 // 32)) * 4 / 819e9
    assert cost.floor_seconds(run, 1.0) == pytest.approx(floor)
    assert read("count_roofline", run) == pytest.approx(
        100 * 1.8 * floor / 0.5)
    assert read("step_roofline", run) == pytest.approx(
        100 * 1.8 * floor / 0.9)


def test_trace_readers_are_silent_without_device_work():
    run = _run()
    for name in ("count_kernel_ms", "count_roofline", "step_roofline",
                 "device_idle_pct"):
        assert read(name, run) is None
    assert _summary([], [("x", 0, 1)]) is None


def test_idle_gaps_are_labelled_by_the_client_spans():
    ev = [TR.Event("h", "py", TR.OPEN, 0, 0), TR.Event("h", "py", TR.CLOSE,
                                                       10e9, 0),
          TR.Event("h", "py", "bench.ingest", 1e9, 1e9),
          TR.Event("h", "py", "bench.request", 5e9, 2e9),
          TR.Event("/device:TPU:0", TR.OPS_LINE, "k", 0.5e9, 0.2e9),
          TR.Event("/device:TPU:0", TR.OPS_LINE, "k", 3e9, 3e9),
          TR.Event("/device:TPU:0", TR.OPS_LINE, "k", 6.5e9, 0.5e9)]
    s = TR.summarize(ev)
    # idle: 0-0.5, 0.7-3 (an ingest), 6-6.5 (a request waits), 7-10
    assert [(lab, round(g, 6)) for lab, g in s.gaps] == [
        ("no_request_outstanding", 3.0), ("ingest", 2.3),
        ("no_request_outstanding", 0.5), ("requests_outstanding", 0.5)]
    assert s.breakdown["idle_gaps"][0] == ["no_request_outstanding", 3.0]
    assert s.breakdown["device_ops"] == [["k", pytest.approx(3.7)]]
