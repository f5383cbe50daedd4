"""Seeded corpus and ingest-block generator (numpy, vectorised).

The semantics are those of the program's ``synthetic_csl``: document
lengths from a length distribution, term ids drawn with replacement from
a Zipf law over the lexicon, term id == frequency rank.  Here the whole
corpus is drawn in bulk and kept as one flat token array with document
offsets (CSR), so a corpus of 800k documents is made in seconds.

``corpus`` parameters (a configuration's ``corpus`` object):

- ``n_docs``, ``vocab``;
- ``length``: ``{"dist": "poisson", "mean": m}`` or
  ``{"dist": "lognormal", "median": m, "sigma": s}``, with ``min`` and
  an optional ``max`` that clip the number of draws per document;
- ``zipf``: ``{"a": a, "offset": c}``, P(rank r) ~ 1 / (r + c)^a;
- ``distinct``: when true, each document keeps its distinct terms only,
  sorted (a document is then a set of terms, as in a term-vector corpus).
"""
from __future__ import annotations

from typing import List, NamedTuple, Optional

import numpy as np


class Docs(NamedTuple):
    """Documents as one flat token array: doc ``d`` holds
    ``tokens[ptr[d]:ptr[d + 1]]``."""
    tokens: np.ndarray      # int32
    ptr: np.ndarray         # int64, n_docs + 1

    @property
    def n_docs(self) -> int:
        return len(self.ptr) - 1

    def lengths(self) -> np.ndarray:
        return np.diff(self.ptr)

    def doc(self, d: int) -> np.ndarray:
        return self.tokens[self.ptr[d]:self.ptr[d + 1]]

    def as_lists(self) -> List[np.ndarray]:
        """One array view per document (what the program's ingest path
        takes as a sequence of token sequences)."""
        return np.split(self.tokens, self.ptr[1:-1])

    def slice(self, lo: int, hi: int) -> "Docs":
        p = self.ptr[lo:hi + 1]
        return Docs(self.tokens[p[0]:p[-1]], p - p[0])


def concat(parts: List[Docs]) -> Docs:
    tokens = np.concatenate([p.tokens for p in parts])
    ptrs, off = [np.zeros(1, np.int64)], 0
    for p in parts:
        ptrs.append(p.ptr[1:] + off)
        off += int(p.ptr[-1])
    return Docs(tokens, np.concatenate(ptrs))


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    """An independent generator per (seed, stream): any whole number
    seeds it, however large."""
    return np.random.default_rng([int(seed) % (1 << 64), *stream])


def zipf_p(vocab: int, a: float, offset: float) -> np.ndarray:
    ranks = np.arange(1, vocab + 1, dtype=np.float64)
    p = 1.0 / (ranks + offset) ** a
    return p / p.sum()


def draw_lengths(rng: np.random.Generator, n: int, spec: dict) -> np.ndarray:
    dist = spec["dist"]
    if dist == "poisson":
        lens = rng.poisson(spec["mean"], size=n)
    elif dist == "lognormal":
        lens = np.rint(rng.lognormal(np.log(spec["median"]), spec["sigma"],
                                     size=n))
    else:
        raise ValueError(f"unknown length distribution {dist!r}")
    hi: Optional[int] = spec.get("max")
    return np.clip(lens, spec.get("min", 1), hi).astype(np.int64)


def draw_docs(rng: np.random.Generator, n: int, spec: dict) -> Docs:
    """``n`` documents of the corpus ``spec``."""
    vocab = int(spec["vocab"])
    lens = draw_lengths(rng, n, spec["length"])
    z = spec["zipf"]
    tokens = rng.choice(vocab, size=int(lens.sum()),
                        p=zipf_p(vocab, z["a"], z["offset"])).astype(np.int32)
    ptr = np.zeros(n + 1, np.int64)
    np.cumsum(lens, out=ptr[1:])
    docs = Docs(tokens, ptr)
    return distinct(docs, vocab) if spec.get("distinct") else docs


def distinct(docs: Docs, vocab: int) -> Docs:
    """Each document's distinct terms, sorted."""
    doc_of = np.repeat(np.arange(docs.n_docs, dtype=np.int64), docs.lengths())
    key = np.unique(doc_of * vocab + docs.tokens)
    d = key // vocab
    ptr = np.zeros(docs.n_docs + 1, np.int64)
    np.cumsum(np.bincount(d, minlength=docs.n_docs), out=ptr[1:])
    return Docs((key % vocab).astype(np.int32), ptr)


def make_corpus(seed: int, spec: dict) -> Docs:
    """The configuration's corpus, the same for the same seed."""
    return draw_docs(rng_for(seed, 0), int(spec["n_docs"]), spec)


def make_blocks(seed: int, spec: dict, n_blocks: int, block_docs: int
                ) -> List[Docs]:
    """``n_blocks`` ingest blocks of new documents from the same
    distribution as the corpus; block ``i`` depends on (seed, i) only."""
    return [draw_docs(rng_for(seed, 1, i), block_docs, spec)
            for i in range(n_blocks)]


def doc_freq(docs: Docs, vocab: int) -> np.ndarray:
    """Document frequency of every term."""
    doc_of = np.repeat(np.arange(docs.n_docs, dtype=np.int64), docs.lengths())
    return np.bincount(np.unique(doc_of * vocab + docs.tokens) % vocab,
                       minlength=vocab)
