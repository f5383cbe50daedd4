"""The trace reduction on a recorded excerpt: a 3 s window of
``csl-steady`` on one TPU v5e (fused steps back to back), profiled by
``bench/sweep.py --trace-out`` and kept as it was written."""
import pytest

from bench import cost
from bench import trace as TR
from conftest import ROOT

EXCERPT = ROOT / "tests/bench/data/csl-steady-fused-3s.xplane.pb.gz"


@pytest.fixture(scope="module")
def events():
    return TR.load_events(TR.read_xspace(str(EXCERPT)))


def test_events_hold_the_window_the_device_and_the_client(events):
    names = {e.name for e in events}
    assert {TR.OPEN, TR.CLOSE, "bench.request"} <= names
    planes = {e.plane for e in events if e.line == TR.OPS_LINE}
    assert planes == {"/device:TPU:0"}
    # operations are named by their HLO names, without the HLO text
    assert all(" = " not in e.name for e in events)
    assert any(e.name.startswith("level_step") for e in events)


def test_summary_of_back_to_back_fused_steps(events):
    s = TR.summarize(events)
    assert s.window_s == pytest.approx(3.0, abs=0.01)
    assert 0 < s.busy_s <= s.window_s
    assert s.busy_s / s.window_s > 0.99          # steps back to back
    steps = s.runs(cost.STEP_PROGRAM)
    assert steps == pytest.approx(s.window_s / 2.3, rel=0.05)
    kernel = s.op_seconds(cost.COUNT_KERNELS)
    assert 0.99 * s.busy_s < kernel <= s.busy_s  # the fused kernel is it
    assert kernel <= s.module_seconds(cost.STEP_PROGRAM) + 1e-9
    ops = dict(s.breakdown["device_ops"])
    assert len(ops) == 10 and max(ops, key=ops.get).startswith("while")
    assert all(g >= TR.MIN_GAP_NS / 1e9 for _, g in s.gaps)
    assert {lab for lab, _ in s.gaps} <= {"requests_outstanding",
                                          "no_request_outstanding", "ingest"}


def test_summary_feeds_the_kernel_readers(events):
    from test_bench_arithmetic import _run, read
    run = _run()
    run.trace = TR.summarize(events)
    ms = read("count_kernel_ms", run)
    assert 2200 < ms < 2400                     # one fused step's kernels
    share = read("count_roofline", run)
    assert 0 < share < 100
    assert share == pytest.approx(
        100 * cost.floor_seconds(run, 1.0) * 1e3 / ms)
    assert 0 < read("step_roofline", run) <= share
    assert read("device_idle_pct", run) < 1
