"""``correct`` comes out false where it must: for each of a cell's
controls (the reference with one stated guarantee broken, in the
program's place)
and for the program broken underneath a whole run, once for each fault a
one-chip cell can have."""
import json

import pytest

from conftest import ROOT

CELLS = {w["name"]: w["traffic"] for w in json.loads(
    (ROOT / "BENCHMARK.json").read_text())["workloads"]}


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_control_is_not_correct(run_tiny, cell):
    mix = json.loads((ROOT / f"bench/mixes/{CELLS[cell]}.json").read_text())
    controls = tuple(mix["controls"])
    out = run_tiny(cell, controls=controls)
    assert out["correct"] is True
    assert set(out["controls"]) == set(controls)
    for ctl in controls:
        assert out["controls"][ctl]["wrong_answers"]["value"] > 0, ctl


def _break_step(monkeypatch, alter):
    """Run every engine step through ``alter(net, q_batch)``."""
    from repro.serve import cooc_engine
    real = cooc_engine.bfs_construct_batch

    def broken(index, seed_terms, **kw):
        return alter(real(index, seed_terms, **kw), seed_terms.shape[0])
    monkeypatch.setattr(cooc_engine, "bfs_construct_batch", broken)


def test_half_the_batch_left_out(run_tiny, monkeypatch):
    def half(net, q):
        valid = net.valid.reshape(q, -1).at[q // 2:].set(False)
        return net._replace(valid=valid.reshape(-1))
    _break_step(monkeypatch, half)
    out = run_tiny("csl-backfill")          # closed loop: full batches
    assert out["correct"] is False
    assert out["checks"]["wrong_answers"]["value"] > 0


def test_answer_altered_where_it_is_produced(run_tiny, monkeypatch):
    def one_off(net, q):
        w = net.weight.reshape(q, -1).at[:, 0].add(1)
        return net._replace(weight=w.reshape(-1))
    _break_step(monkeypatch, one_off)
    out = run_tiny("csl-steady")
    assert out["correct"] is False
    assert out["checks"]["wrong_answers"]["value"] > 0


def test_ingest_that_leaves_the_index_unchanged(run_tiny, monkeypatch):
    from repro.core import query_context
    monkeypatch.setattr(query_context, "ingest_at",
                        lambda index, *args, **kw: index)
    out = run_tiny("rcv1-stream")
    assert out["correct"] is False
    assert out["checks"]["wrong_answers"]["value"] > 0
