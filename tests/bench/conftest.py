"""Fixtures for the benchmark's own tests: the repository root on the
import path (for ``bench``), and a copy of the benchmark with every cell
cut to a size the CPU runs in seconds."""
import json
import pathlib
import shutil
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

#: the tiny stand-in for every configuration: CSL's shape, 3,000 docs
TINY_CONFIG = {
    "name": "tiny", "source": "test", "reduced": [],
    "corpus": {"n_docs": 3000, "vocab": 512,
               "length": {"dist": "poisson", "mean": 12.0, "min": 1},
               "zipf": {"a": 1.15, "offset": 2.7}, "distinct": False},
    "capacity": {"ingest_slack_docs": 4096},
    "ingest": {"max_len": 64},
    "serving": {"depth": 3, "topk": 8, "beam": 8, "q_batch": 4},
}


def shrink_mix(mix: dict) -> dict:
    """The same mix at a rate, scope and block size the tiny index and
    the CPU fit; every count method runs its CPU form."""
    mix = json.loads(json.dumps(mix))
    if mix["loop"] == "open":
        mix["rate_qps"] = 4.0
    else:
        mix["clients"] = 8
    for t in mix["tenants"]:
        if "newest_docs" in t:
            t["newest_docs"] = 1000
    mix["seeds"]["top"] = 100
    mix["check_sample"] = 40
    if "probe_sample" in mix:
        mix["probe_sample"] = 4
    mix["method"] = "fused"
    if "ingest" in mix:
        mix["ingest"] = {"block_docs": 128, "period_s": 1.0}
    return mix


@pytest.fixture
def tiny_root(tmp_path):
    """A checkout of the benchmark alone, with the real BENCHMARK.json's
    cells pointed at the tiny configuration and their mixes shrunk, and
    the CPU given peaks so that the run finds its device."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    (tmp_path / "bench" / "configs" / "tiny.json").write_text(
        json.dumps(TINY_CONFIG))
    bench["configs"] = [{"name": "tiny", "source": "test", "reduced": [],
                         "file": "bench/configs/tiny.json", "why": "test"}]
    for w in bench["workloads"]:
        w["config"] = "tiny"
    for path in (tmp_path / "bench" / "mixes").glob("*.json"):
        path.write_text(json.dumps(shrink_mix(json.loads(path.read_text()))))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    peaks = json.loads((tmp_path / "bench" / "peaks.json").read_text())
    peaks["devices"]["cpu"] = dict(peaks["devices"]["TPU v5 lite"])
    (tmp_path / "bench" / "peaks.json").write_text(json.dumps(peaks))
    return tmp_path


@pytest.fixture
def run_tiny(tiny_root):
    """``run(cell, seed, **kw)``: one run of a tiny cell through
    ``run_cell``, on the CPU."""
    import time

    from bench.harness import run_cell
    from bench.spec import load_cell

    def run(cell, seed=2**40 + 5, seconds=3.0, trace=False, **kw):
        return run_cell(tiny_root, load_cell(tiny_root, cell), seed, seconds,
                        trace, time.perf_counter(), **kw)
    return run
