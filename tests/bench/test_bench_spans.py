"""The program's spans as the benchmark reads them: ``bench/spans.py``'s
reduction and gap labels, the ``queue_ms`` reader, a run that reports
the window's spans (CPU, tiny), and the committed trace excerpt read
exactly as before the program had spans."""
import json
import types

import pytest

from bench import spans as S
from bench import trace as TR
from conftest import ROOT
from test_bench_arithmetic import _req, _run, read

EXCERPT = ROOT / "tests/bench/data/csl-steady-fused-3s.xplane.pb.gz"
#: a 3 s window of ``rcv1-stream`` on one TPU v5e with the program's
#: spans (one ingest, two steps), written by ``bench/sweep.py
#: --trace-out``
SPANS_EXCERPT = ROOT / "tests/bench/data/rcv1-stream-spans-3s.xplane.pb.gz"


@pytest.fixture(scope="module")
def fixture_events():
    return TR.load_events(TR.read_xspace(str(EXCERPT)))


@pytest.fixture(scope="module")
def spans_excerpt():
    return S.reduce_file(str(SPANS_EXCERPT))


def test_committed_excerpt_reads_as_before(fixture_events):
    """Pinned from the excerpt before the program recorded spans: every
    ``Summary`` field, its breakdown, and the readers that read it."""
    s = TR.summarize(fixture_events)
    assert s.window_s == pytest.approx(3.000131582, abs=1e-9)
    assert s.busy_s == pytest.approx(2.988627607, abs=1e-9)
    assert len(s.op_s) == 125
    assert list(s.module_runs.values()) == pytest.approx([1.3016543137753052])
    assert list(s.module_s.values()) == pytest.approx([2.988628604])
    assert s.gaps == [("requests_outstanding", 0.006692593),
                      ("requests_outstanding", 0.004811322)]
    assert s.breakdown["idle_gaps"] == [list(g) for g in s.gaps]
    assert [k for k, _ in s.breakdown["device_ops"]] == [
        "while.23", "level_step.6", "while.25", "while.24",
        "dynamic-slice.38", "dynamic-slice.35", "dynamic-update-slice.13",
        "dynamic-update-slice.12", "slice.126", "slice.125"]
    assert s.breakdown["device_ops"][0][1] == pytest.approx(2.987134399)
    run = _run()
    run.trace = s
    for name, want in (("count_kernel_ms", 2292.5459896836505),
                       ("count_roofline", 0.5186209041576259),
                       ("step_roofline", 0.5178354616519827),
                       ("device_idle_pct", 0.3834490150038894)):
        assert read(name, run) == pytest.approx(want, rel=1e-12), name


def test_excerpt_without_program_spans_keeps_the_client_labels(
        fixture_events):
    r = S.reduce(fixture_events, [])
    assert r.gaps == TR.summarize(fixture_events).gaps
    assert r.idle_share() == 0.0 and r.readings() == {}


def test_recorded_spans_label_every_gap(spans_excerpt):
    r = spans_excerpt
    for name in ("cooc.engine.step", "cooc.lane.batch", "cooc.lane.lock",
                 "cooc.index.ingest", "cooc.lane.ingest_lock",
                 *S.STEP_CHILDREN):
        assert r.spans.get(name), name
    assert r.gaps and all(lab.startswith(S.PREFIX) for lab, _ in r.gaps)
    assert r.idle_share() == 1.0
    # the trace's own reduction sees the same gaps, by the client's labels
    events = TR.load_events(TR.read_xspace(str(SPANS_EXCERPT)))
    assert [g for _, g in TR.summarize(events).gaps] == [
        g for _, g in r.gaps]
    assert r.tiling and all(0.98 <= t <= 1.0 for t in r.tiling)
    got = r.readings()
    assert set(got) == {"lock_wait_ms", "step_host_ms", "ingest_apply_ms"}
    assert all(v > 0 for v in got.values())
    assert got["step_host_ms"] < 10 and got["ingest_apply_ms"] < 100


def _ev(name, a, b, line="thread"):
    return TR.Event("/host:CPU", line, name, a, b - a)


def test_gap_goes_to_the_innermost_working_span():
    own = S._owner
    cover = [("cooc.lane.batch", 0, 100), ("cooc.engine.step", 10, 90),
             ("cooc.step.fetch", 40, 90), ("cooc.lane.lock", 0, 100)]
    assert own((50, 60), cover) == "cooc.step.fetch"
    # most of the gap after the step: the batch's own tail
    assert own((85, 100), cover) == "cooc.lane.batch"
    # only a wait covers it
    assert own((150, 160), [("cooc.lane.idle", 100, 200)]) == "cooc.lane.idle"
    assert own((150, 160), []) is None


def test_reduction_of_a_hand_made_window():
    ms = 1e6
    events = [_ev(TR.OPEN, 0, 0), _ev(TR.CLOSE, 100 * ms, 100 * ms),
              TR.Event("/device:TPU:0", TR.OPS_LINE, "op", 0, 40 * ms),
              TR.Event("/device:TPU:0", TR.OPS_LINE, "op", 50 * ms, 40 * ms),
              _ev("bench.request", 0, 100 * ms, "loop")]
    spans = [_ev("cooc.lane.batch", 0, 49 * ms),
             _ev("cooc.engine.step", 1 * ms, 48 * ms),
             _ev("cooc.step.prepare", 1 * ms, 2 * ms),
             _ev("cooc.step.dispatch", 2 * ms, 3 * ms),
             _ev("cooc.step.device", 3 * ms, 40 * ms),
             _ev("cooc.step.fetch", 40 * ms, 48 * ms),
             _ev("cooc.lane.lock", 48 * ms, 50 * ms, "loop")]
    r = S.reduce(events, spans)
    # two gaps: 40-50 ms (fetch, then the batch's tail, then the lock)
    # and 90-100 ms, which no program span covers
    assert r.gaps == [("cooc.step.fetch", 0.01),
                      ("requests_outstanding", 0.01)]
    assert r.idle_share() == pytest.approx(0.5)
    assert r.tiling == [pytest.approx(1.0)]
    got = r.readings()
    assert got["step_host_ms"] == pytest.approx(47 - 37)
    assert got["lock_wait_ms"] == pytest.approx(2.0)
    assert "ingest_apply_ms" not in got


def test_queue_ms_reads_the_results_field():
    run = _run()
    run.requests = [_req(100.0 + i, 0.5) for i in range(4)]
    assert read("queue_ms", run) is None       # a program without the field
    for i, r in enumerate(run.requests):
        r.result.queue_ms = 10.0 * i
    run.requests[3].status, run.requests[3].result = "shed", None
    assert read("queue_ms", run) == pytest.approx(10.0)


def test_queue_ms_is_a_front_end_metric_of_the_cells_it_reads():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    m = {x["name"]: x for x in bench["per_layer"]}["queue_ms"]
    assert m["layer"] == "front end" and m["moves"] == "query_p90_ms"
    assert m["source"] == "program_span"
    cells = {w["name"] for w in bench["workloads"]}
    assert set(m["workloads"]) <= cells


def test_traced_run_reports_the_window_spans(tiny_root, monkeypatch, capsys):
    from bench import run as R
    monkeypatch.setattr(R, "chips_or_refuse", lambda chips: "cpu")
    monkeypatch.setattr(R, "process_setup", lambda: None)
    args = types.SimpleNamespace(workload="rcv1-stream", seed=2**40 + 7,
                                 seconds=3.0, trace=1, trace_out=None)
    out = S.run(args, root=tiny_root)
    assert out["correct"] is True
    lines = capsys.readouterr().out.splitlines()
    spans = dict(kv.split("=", 1) for kv in next(
        x for x in lines if x.startswith("spans: "))[7:].split())
    for name in ("cooc.engine.step", "cooc.lane.batch", "cooc.lane.lock",
                 "cooc.index.ingest", "cooc.lane.ingest_lock",
                 *S.STEP_CHILDREN):
        count, total, peak = spans[name].split("/")
        assert int(count) >= 1 and 0 <= float(peak) <= float(total), name
    counts = next(x for x in lines if x.startswith("span_counts: "))
    assert "cooc.engine.step=" in counts and "cooc.index.ingest=" in counts
    assert any(x.startswith("span_counters: ") for x in lines)
    # without a device plane there are no gaps to label
    assert any(x.startswith("idle_gaps: n=0") for x in lines)


def test_span_cost_is_measured_with_the_profiler_off_and_on():
    got = S.cost(200, repeats=1)
    assert got["off_us"] > 0 and got["on_us"] > 0
