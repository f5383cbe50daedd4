"""The harness end to end on the CPU, at tiny sizes: a run through
``run_cell``, discovery of a new configuration, mix and metric from
files alone, and the refusal to run without a TPU."""
import json
import re

import pytest

from conftest import ROOT

HARNESS_FILES = ["harness.py", "spec.py", "traffic.py", "corpus.py",
                 "reference.py", "trace.py", "cost.py", "run.py",
                 "sweep.py", "control.py"]


@pytest.fixture(autouse=True)
def _jax_config():
    """The harness's entry points set process-wide compile-cache options;
    leave none of them behind for other tests."""
    import jax
    keep = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs")}
    yield
    for k, v in keep.items():
        jax.config.update(k, v)


@pytest.mark.parametrize("cell", ["csl-steady", "rcv1-stream",
                                  "csl-backfill"])
def test_tiny_cell_end_to_end(run_tiny, cell, capsys):
    out = run_tiny(cell)
    assert out["correct"] is True
    assert out["attempted"] > 0 and out["failed"] == 0
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    want = {m["name"] for m in bench["end_to_end"]
            if cell in m.get("workloads", [cell])}
    want.discard("peak_hbm_gb")          # the CPU reports no peak memory
    assert set(out["metrics"]) == want
    assert list(out)[-1] == "checks"
    assert all(c["value"] == 0 == c["limit"] for c in out["checks"].values())
    err = capsys.readouterr().err.strip().splitlines()
    assert err[-len(out["checks"]):] == [
        f"check {k}: 0 (limit 0)" for k in out["checks"]]


def test_traced_run_reads_the_host_side_layers(run_tiny):
    out = run_tiny("rcv1-stream", trace=True)
    assert out["correct"] is True
    # no device plane on the CPU: the device metrics stay silent
    assert set(out["metrics"]) == {"server_wait_ms", "batch_occupancy_pct",
                                   "step_ms", "ingest_ms"}


def test_new_config_mix_and_metric_are_files_only(tiny_root, run_tiny):
    bench = json.loads((tiny_root / "BENCHMARK.json").read_text())
    cfg = json.loads((tiny_root / "bench/configs/tiny.json").read_text())
    cfg["corpus"] = dict(cfg["corpus"], n_docs=2000, vocab=384)
    (tiny_root / "bench/configs/tiny-two.json").write_text(json.dumps(cfg))
    mix = json.loads((tiny_root / "bench/mixes/steady-2tenant.json")
                     .read_text())
    mix["tenants"] = [{"name": "only", "share": 1.0, "newest_docs": 500}]
    (tiny_root / "bench/mixes/one-scoped.json").write_text(json.dumps(mix))
    (tiny_root / "bench/metrics/answered_share.py").write_text(
        "def read(run):\n"
        "    w = run.window_requests()\n"
        "    return 100.0 * sum(r.answered for r in w) / len(w)\n")
    bench["configs"].append({"name": "tiny-two", "source": "test",
                             "file": "bench/configs/tiny-two.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "two-scoped", "config": "tiny-two",
                               "traffic": "one-scoped", "chips": 1,
                               "why": "test"})
    bench["end_to_end"].append({"name": "answered_share", "unit": "%",
                                "better": "higher", "bound": 0.01,
                                "source": "host_clock",
                                "workloads": ["two-scoped"]})
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(bench))
    out = run_tiny("two-scoped")
    assert out["correct"] is True
    assert out["metrics"]["answered_share"]["value"] == 100.0
    assert "query_p90_ms" in out["metrics"]


def test_harness_names_no_cell_mix_or_metric():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    names = ([w["name"] for w in bench["workloads"]]
             + [w["traffic"] for w in bench["workloads"]]
             + [c["name"] for c in bench["configs"]]
             + [m["name"] for m in bench["end_to_end"] + bench["per_layer"]])
    for f in HARNESS_FILES:
        text = (ROOT / "bench" / f).read_text()
        for name in names:
            assert not re.search(rf"(?<![\w-]){re.escape(name)}(?![\w-])",
                                 text), (f, name)


def test_refuses_without_a_tpu(capsys):
    from bench import run
    with pytest.raises(SystemExit) as e:
        run.main(["--workload", "csl-steady", "--seed", "1", "--seconds",
                  "1"])
    assert e.value.code != 0 and "needs a TPU" in str(e.value.code)
    assert capsys.readouterr().out == ""


def test_refuses_fewer_chips_than_the_cell_asks(monkeypatch):
    import jax

    from bench.run import chips_or_refuse
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(SystemExit, match="needs 4 chip"):
        chips_or_refuse(4)


def test_unknown_device_fails_the_run(tiny_root, run_tiny):
    peaks = json.loads((tiny_root / "bench/peaks.json").read_text())
    del peaks["devices"]["cpu"]
    (tiny_root / "bench/peaks.json").write_text(json.dumps(peaks))
    with pytest.raises(KeyError, match="not in bench/peaks.json"):
        run_tiny("csl-steady")
