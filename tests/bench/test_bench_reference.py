"""The benchmark's own reference agrees with the program's host copy
(``build_host_index`` + ``bfs_construct_host_fast``): whole corpus, a
scope of the newest documents, and the corpus after an ingest."""
import numpy as np
import pytest

from bench import corpus as C
from bench import reference as R

SPEC = {"n_docs": 3000, "vocab": 512,
        "length": {"dist": "poisson", "mean": 12.0, "min": 1},
        "zipf": {"a": 1.15, "offset": 2.7}}
SHAPE = dict(depth=3, topk=16, beam=32)


@pytest.fixture(scope="module")
def corpus():
    docs = C.make_corpus(2**35 + 1, SPEC)
    blocks = C.make_blocks(2**35 + 1, SPEC, 2, 256)
    return docs, blocks


def _queries(n, seed=0):
    rng = np.random.default_rng(seed)
    return [tuple(int(s) for s in rng.choice(150, size=rng.integers(1, 4),
                                             replace=False))
            for _ in range(n)]


@pytest.mark.parametrize("where", ["whole", "scope", "after_ingest"])
def test_agrees_with_the_programs_copy(corpus, where):
    from repro.core import bfs_construct_host_fast, build_host_index
    docs, blocks = corpus
    both = C.concat([docs] + blocks)
    ours = R.build_index(both.tokens, both.ptr, 512)
    lists = [d.tolist() for d in both.as_lists()]
    n = docs.n_docs
    lo, hi = {"whole": (0, n), "scope": (n - 1000, n),
              "after_ingest": (0, n + 256)}[where]
    theirs = build_host_index(lists[lo:hi], 512)
    for seeds in _queries(25):
        got = R.bfs(ours, seeds, lo=lo, hi=hi, **SHAPE)
        assert got == bfs_construct_host_fast(theirs, seeds, **SHAPE), seeds


def test_top_positive_is_a_stable_descending_prefix():
    rng = np.random.default_rng(1)
    for _ in range(200):
        counts = rng.integers(-1, 6, size=rng.integers(1, 60))
        k = int(rng.integers(1, 20))
        want = [int(t) for t in np.argsort(-counts, kind="stable")[:k]
                if counts[t] > 0]
        assert R.top_positive(counts, k).tolist() == want


def test_bf16_rounding():
    x = np.array([0, 1, 255, 256, 257, 258, 259, 1000, 123457])
    assert R.bf16_round(x).tolist() == [0, 1, 255, 256, 256, 258, 260, 1000,
                                        123392]


def test_counts_do_not_depend_on_the_chunk(corpus):
    docs, _ = corpus
    hidx = R.build_index(docs.tokens, docs.ptr, 512)
    some = np.arange(0, docs.n_docs, 3)
    assert np.array_equal(R.gather_counts(hidx, some, chunk=5),
                          R.gather_counts(hidx, some))
