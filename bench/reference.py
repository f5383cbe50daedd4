"""The benchmark's plain reference: a host inverted index and the
paper's breadth-first network construction over it (numpy only).

This is an independent copy of the program's ``build_host_index`` +
``bfs_construct_host_fast`` semantics, kept with the benchmark so that no
change to the program can move the yardstick:

- the index is a forward list (each document's distinct terms) and a
  postings list (each term's sorted documents), both CSR arrays;
- a query restricted to documents ``[lo, hi)`` sees only those documents
  (a scope of the newest documents, or the corpus as of an epoch);
- each level counts, for every frontier node, how many of its filtered
  documents hold each term (one pass over their forward lists), masks
  the node itself and every visited term, keeps the ``topk`` heaviest
  (ties to the lower term id), and carries the ``beam`` heaviest
  distinct targets to the next level with their intersected documents.

Edges are returned in the order the network's slots hold them:
``[(src, dst, weight), ...]``.

The module imports nothing of the program and nothing of JAX.
"""
from __future__ import annotations

from typing import List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

Edge = Tuple[int, int, int]


class HostIndex(NamedTuple):
    fwd_terms: np.ndarray   # int32, distinct terms of each doc
    fwd_ptr: np.ndarray     # int64, n_docs + 1
    post_docs: np.ndarray   # int64, sorted docs of each term
    post_ptr: np.ndarray    # int64, vocab + 1
    vocab: int


def build_index(tokens: np.ndarray, ptr: np.ndarray, vocab: int) -> HostIndex:
    """Host index over documents given as a flat token array with
    offsets (``tokens[ptr[d]:ptr[d+1]]`` are the terms of doc ``d``,
    duplicates allowed)."""
    n = len(ptr) - 1
    lens = np.diff(ptr)
    doc_of = np.repeat(np.arange(n, dtype=np.int64), lens)
    tokens = np.asarray(tokens)
    inner = np.ones(len(tokens), bool)
    inner[ptr[:-1][lens > 0]] = False
    if len(tokens) and not np.all(np.diff(tokens)[inner[1:]] > 0):
        key = np.unique(doc_of * vocab + tokens)
        doc_of, terms = key // vocab, (key % vocab).astype(np.int32)
        fwd_ptr = np.zeros(n + 1, np.int64)
        np.cumsum(np.bincount(doc_of, minlength=n), out=fwd_ptr[1:])
    else:
        terms, fwd_ptr = tokens.astype(np.int32), np.asarray(ptr, np.int64)
    order = np.argsort(terms, kind="stable")
    post_ptr = np.zeros(vocab + 1, np.int64)
    np.cumsum(np.bincount(terms, minlength=vocab), out=post_ptr[1:])
    return HostIndex(terms, fwd_ptr, doc_of[order], post_ptr, vocab)


def postings(hidx: HostIndex, t: int, lo: int, hi: int) -> np.ndarray:
    p = hidx.post_docs[hidx.post_ptr[t]:hidx.post_ptr[t + 1]]
    return p[np.searchsorted(p, lo):np.searchsorted(p, hi)]


def gather_counts(hidx: HostIndex, docs: np.ndarray,
                  chunk: int = 1 << 22) -> np.ndarray:
    """Document frequency of every term over ``docs``: one pass over
    their forward lists, ``chunk`` postings at a time."""
    counts = np.zeros(hidx.vocab, np.int64)
    starts = hidx.fwd_ptr[docs]
    lens = hidx.fwd_ptr[docs + 1] - starts
    ends = np.cumsum(lens)
    lo = 0
    while lo < len(docs):
        hi = int(np.searchsorted(ends, (ends[lo - 1] if lo else 0) + chunk,
                                 side="right"))
        hi = max(hi, lo + 1)
        s, n = starts[lo:hi], lens[lo:hi]
        total = int(n.sum())
        if total:
            shifted = np.concatenate(([0], np.cumsum(n)[:-1]))
            offs = np.repeat(s - shifted, n) + np.arange(total)
            counts += np.bincount(hidx.fwd_terms[offs], minlength=hidx.vocab)
        lo = hi
    return counts


def intersect(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Sorted intersection of two sorted arrays of distinct values."""
    if len(a) > len(b):
        a, b = b, a
    if len(a) == 0:
        return a
    i = np.searchsorted(b, a)
    i[i == len(b)] = 0
    return a[b[i] == a]


def top_positive(counts: np.ndarray, k: int) -> np.ndarray:
    """Ids of the ``k`` largest positive counts, largest first, ties to
    the lower id: the positive prefix of a stable descending sort."""
    pos = np.flatnonzero(counts > 0)
    if len(pos) > k:
        kth = np.partition(counts[pos], len(pos) - k)[len(pos) - k]
        pos = pos[counts[pos] >= kth]
    order = np.lexsort((pos, -counts[pos]))
    return pos[order[:k]]


def bf16_round(counts: np.ndarray) -> np.ndarray:
    """Counts as bfloat16 would hold them (round to nearest even)."""
    f = counts.astype(np.float32).view(np.uint32).astype(np.uint64)
    f = (f + 0x7FFF + ((f >> 16) & 1)) & 0xFFFF0000
    return f.astype(np.uint32).view(np.float32).astype(np.int64)


def bfs(hidx: HostIndex, seeds: Sequence[int], *, depth: int, topk: int,
        beam: int, dedup: bool = True, lo: int = 0,
        hi: Optional[int] = None, bf16: bool = False) -> List[Edge]:
    """The network of ``seeds`` over documents ``[lo, hi)``.  ``bf16``
    rounds every count to bfloat16 (a control, never the reference)."""
    hi = len(hidx.fwd_ptr) - 1 if hi is None else hi
    edges: List[Edge] = []
    visited = set(int(s) for s in seeds)
    frontier = [(postings(hidx, int(s), lo, hi), int(s)) for s in seeds]
    for _ in range(depth):
        cands = []          # (weight, src, dst, the src's documents)
        for docs, term in frontier:
            counts = gather_counts(hidx, docs)
            if bf16:
                counts = bf16_round(counts)
            counts[term] = -1
            if dedup:
                counts[list(visited)] = -1
            for t in top_positive(counts, topk):
                t = int(t)
                edges.append((term, t, int(counts[t])))
                cands.append((int(counts[t]), term, t, docs))
        if dedup:
            visited |= {c[2] for c in cands}
            seen, uniq = set(), []
            for c in sorted(cands, key=lambda c: -c[0]):
                if c[2] not in seen:
                    seen.add(c[2])
                    uniq.append(c)
            cands = uniq
        else:
            cands.sort(key=lambda c: -c[0])
        # the next frontier: each kept target with its filtered documents
        frontier = [(intersect(c[3], postings(hidx, c[2], lo, hi)), c[2])
                    for c in cands[:beam]]
        if not frontier:
            break
    return edges


class Query(NamedTuple):
    seeds: Tuple[int, ...]
    lo: int
    hi: int
    bf16: bool = False


def answer_all(hidx: HostIndex, queries: Sequence[Query], shape: dict
               ) -> List[List[Edge]]:
    """Reference answers for ``queries`` (``shape``: depth, topk, beam,
    dedup)."""
    return [bfs(hidx, q.seeds, lo=q.lo, hi=q.hi, bf16=q.bf16, **shape)
            for q in queries]
