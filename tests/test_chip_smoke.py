"""chip_smoke.py: its refusal without a TPU, and its served path and
edge-for-edge host check at a tiny size on the CPU (the chip runs it at
cooccur-csl size)."""
import importlib.util
import json
import pathlib

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_refuses_without_tpu(chip_smoke, capsys):
    assert chip_smoke.main([]) == 2
    out, err = capsys.readouterr()
    assert out == ""                     # no result line, nothing built
    assert "needs a TPU" in err and "'cpu'" in err


def test_serves_ingests_and_matches_host_reference(chip_smoke, capsys):
    chip_smoke.smoke(n_docs=3000, vocab=512, seed=0, chips=1,
                     ingest_docs=256, recent_docs=1024)
    lines = capsys.readouterr().out.splitlines()
    n_requests = sum(sum(p) for p in chip_smoke.PLAN.values()) + 2
    assert f"checked: requests={n_requests} matched={n_requests}" in "\n".join(
        lines)
    assert any(line.startswith("ingest: docs=256 ") for line in lines)
    assert any("cooc_plan_fused" in line for line in lines
               if line.startswith("compile:"))
    for line in lines:                   # the result line is main()'s alone
        with pytest.raises(json.JSONDecodeError):
            json.loads(line)


@pytest.mark.parametrize("env_dir", [None, "/var/cache/jax-from-env"])
def test_compile_cache_placement(monkeypatch, env_dir):
    """JAX reads $JAX_COMPILATION_CACHE_DIR itself; only without it does
    the helper set the fixed in-repo directory."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    from repro.launch.flags import use_compile_cache
    before = jax.config.jax_compilation_cache_dir
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    try:
        got = use_compile_cache()
        if env_dir is None:
            assert got == str(ROOT / ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == got
        else:
            assert got == env_dir
            assert jax.config.jax_compilation_cache_dir == before
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        compilation_cache.reset_cache()


def test_edges_of_keeps_slot_order(chip_smoke):
    import numpy as np

    from repro.core import CoocNetwork
    from repro.core.query import QueryResult, QuerySpec
    net = CoocNetwork(src=np.array([3, 3, 5, 7]), dst=np.array([9, 1, 2, 8]),
                      weight=np.array([4, 4, 0, 2]),
                      valid=np.array([True, True, False, True]))
    res = QueryResult(network=net, spec=QuerySpec(seeds=(3,)))
    assert chip_smoke.edges_of(res) == [(3, 9, 4), (3, 1, 4), (7, 8, 2)]
