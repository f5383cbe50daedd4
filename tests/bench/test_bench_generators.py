"""The benchmark's seeded generators: the same seed gives the same
corpus, blocks and traffic; every seed gives the same amount of work."""
import json

import numpy as np
import pytest

from bench import corpus as C
from bench import traffic as T
from conftest import ROOT

BIG_SEED = 2**33 + 12345          # seeds run past 32 bits


def _spec(distinct=False):
    spec = json.loads((ROOT / "bench/configs/cooccur-rcv1.json").read_text())
    spec = spec["corpus"] if distinct else json.loads(
        (ROOT / "bench/configs/cooccur-csl.json").read_text())["corpus"]
    return dict(spec, n_docs=2000)


@pytest.mark.parametrize("distinct", [False, True])
def test_corpus_is_a_function_of_the_seed(distinct):
    spec = _spec(distinct)
    a, b = C.make_corpus(BIG_SEED, spec), C.make_corpus(BIG_SEED, spec)
    assert np.array_equal(a.tokens, b.tokens) and np.array_equal(a.ptr, b.ptr)
    c = C.make_corpus(BIG_SEED + 1, spec)
    assert not np.array_equal(a.tokens[:1000], c.tokens[:1000])
    assert a.n_docs == 2000 and a.tokens.max() < spec["vocab"]
    assert a.lengths().min() >= 1
    if distinct:                  # a set of terms per document, sorted
        for d in range(0, 2000, 97):
            assert np.all(np.diff(a.doc(d)) > 0)


def test_blocks_depend_on_seed_and_index_only():
    spec = _spec(True)
    three = C.make_blocks(BIG_SEED, spec, 3, 64)
    five = C.make_blocks(BIG_SEED, spec, 5, 64)
    for x, y in zip(three, five):
        assert np.array_equal(x.tokens, y.tokens)
    assert not np.array_equal(three[0].tokens, three[1].tokens)


def test_csl_generator_keeps_the_programs_semantics():
    """The vectorised copy draws what ``synthetic_csl`` draws: the same
    lengths and terms from the same generator state."""
    from repro.data import synthetic_csl
    spec = dict(_spec(False), n_docs=500, vocab=512)
    ours = C.draw_docs(np.random.default_rng(3), 500, spec)
    theirs = synthetic_csl(500, 512, seed=3)
    assert [d.tolist() for d in ours.as_lists()] == theirs


def test_rcv1_shape_matches_its_assumptions():
    cfg = json.loads((ROOT / "bench/configs/cooccur-rcv1.json").read_text())
    spec = dict(cfg["corpus"], n_docs=20000)
    docs = C.make_corpus(BIG_SEED, spec)
    assert 72 <= docs.lengths().mean() <= 77          # about 75 distinct
    assert docs.lengths().max() <= cfg["ingest"]["max_len"]
    df = C.doc_freq(docs, spec["vocab"])
    assert 0.3 < df[0] / docs.n_docs < 0.45


def _mix(name):
    return json.loads((ROOT / f"bench/mixes/{name}.json").read_text())


def test_open_loop_schedule_is_the_mixs_terms_the_seeds():
    mix, pools = _mix("steady-2tenant"), {"open": np.arange(100, 4196),
                                          "recent": np.arange(4096)}
    a = T.open_loop(mix, 45.0, BIG_SEED, pools)
    assert a == T.open_loop(mix, 45.0, BIG_SEED, pools)
    b = T.open_loop(mix, 45.0, BIG_SEED + 7, pools)
    assert len(a) == len(b) == round(mix["rate_qps"] * 45)
    assert [(x.due_s, x.tenant, len(x.seeds)) for x in a] == [
        (x.due_s, x.tenant, len(x.seeds)) for x in b]
    assert [x.seeds for x in a] != [x.seeds for x in b]
    assert a[0].due_s == 0.0 and all(0 <= x.due_s < 45.0 for x in a)
    gaps = np.diff([x.due_s for x in a] + [45.0])
    q = (np.arange(len(a)) + 0.5) / len(a)
    want = -np.log1p(-q)
    assert np.allclose(np.sort(gaps), np.sort(want * 45.0 / want.sum()))
    for x in a:
        assert len(set(x.seeds)) == len(x.seeds)
        assert set(x.seeds) <= set(pools[x.tenant].tolist())
    shares = {t: sum(x.tenant == t for x in a) for t in pools}
    assert abs(shares["open"] - shares["recent"]) <= 1


def test_closed_loop_walks_the_top_terms():
    mix, pool = _mix("backfill-closed"), np.arange(50)
    w1, w2 = (T.SeedWalk(mix, BIG_SEED, {"open": pool}) for _ in range(2))
    got = [w1.next() for _ in range(50)]
    assert got == [w2.next() for _ in range(50)]
    assert sorted(s for _, (s,) in got) == list(range(50))   # each once
    assert [s for _, (s,) in got] != list(range(50))         # seeded order


def test_ingest_schedule():
    mix = _mix("news-stream")
    assert T.n_blocks(mix, 45.0) == 22      # at 2, 4, ..., 44 s
    assert T.n_blocks(_mix("steady-2tenant"), 45.0) == 0
    assert list(T.top_terms(np.array([3, 0, 5, 5, 1]), 10)) == [2, 3, 0, 4]
