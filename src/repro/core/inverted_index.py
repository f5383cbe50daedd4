"""Inverted index substrate, TPU-adapted.

The paper's inverted index (term -> postings list) is realised as a
**bit-packed incidence matrix** ``packed`` of shape ``(W, V)`` uint32 where
``W = ceil(D / 32)``: bit ``d % 32`` of ``packed[d // 32, v]`` is set iff
document ``d`` contains term ``v``.  Column ``v`` IS the postings list of
term ``v`` (a compressed doc-id bitmap); a filter condition (AND of terms)
is a bitwise AND of columns; document frequency under a filter is a
popcount reduction.  This makes every index operation a dense VPU/MXU op
and shards trivially: ``W`` (docs) over ("pod","data"), ``V`` over "model".

A lexicon (term string <-> id, global df, total tf) lives host-side, as in
any real retrieval system; the device never sees strings.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np


class PackedIndex(NamedTuple):
    """Device-side inverted index (bit-packed doc-term incidence)."""

    packed: jax.Array      # (W, V) uint32 postings bitmaps
    doc_freq: jax.Array    # (V,) int32 — global document frequency per term
    n_docs: jax.Array      # () int32 — logical number of ingested docs

    @property
    def n_words(self) -> int:
        return self.packed.shape[0]

    @property
    def vocab_size(self) -> int:
        return self.packed.shape[1]

    @property
    def capacity(self) -> int:
        """Max docs this packed buffer can hold."""
        return self.n_words * 32


@dataclasses.dataclass
class Lexicon:
    """Host-side term dictionary (the paper's lexicon component)."""

    term_to_id: Dict[str, int] = dataclasses.field(default_factory=dict)
    id_to_term: List[str] = dataclasses.field(default_factory=list)

    def add(self, term: str) -> int:
        tid = self.term_to_id.get(term)
        if tid is None:
            tid = len(self.id_to_term)
            self.term_to_id[term] = tid
            self.id_to_term.append(term)
        return tid

    def __len__(self) -> int:
        return len(self.id_to_term)

    def lookup(self, term: str) -> int:
        return self.term_to_id[term]


# ---------------------------------------------------------------------------
# Host-side construction (ingest path — the paper's "tokenisation decoupling")
# ---------------------------------------------------------------------------


def pack_docs(doc_terms: Sequence[Sequence[int]], vocab_size: int,
              capacity: Optional[int] = None) -> PackedIndex:
    """Build a PackedIndex from tokenised documents (lists of term ids).

    This is the offline ingest path: tokenisation has already happened in
    ``repro.data``; here we only pack term ids into postings bitmaps.
    """
    packed, df, n_docs = _pack_host(doc_terms, vocab_size, capacity)
    return PackedIndex(jnp.asarray(packed), jnp.asarray(df), jnp.asarray(n_docs, jnp.int32))


def _pack_host(doc_terms: Sequence[Sequence[int]], vocab_size: int,
               capacity: Optional[int] = None
               ) -> Tuple[np.ndarray, np.ndarray, np.int32]:
    """:func:`pack_docs` in host memory: (packed, doc_freq, n_docs) as
    numpy, for a caller that places the arrays itself."""
    n_docs = len(doc_terms)
    cap = capacity if capacity is not None else n_docs
    cap = max(cap, n_docs)
    n_words = (cap + 31) // 32
    packed = np.zeros((n_words, vocab_size), dtype=np.uint32)
    df = np.zeros((vocab_size,), dtype=np.int32)
    for d, terms in enumerate(doc_terms):
        uniq = np.unique(np.asarray(terms, dtype=np.int64))
        uniq = uniq[(uniq >= 0) & (uniq < vocab_size)]
        packed[d // 32, uniq] |= np.uint32(1) << np.uint32(d % 32)
        df[uniq] += 1
    return packed, df, np.int32(n_docs)


def grow_capacity(index: PackedIndex, min_capacity: int) -> PackedIndex:
    """Repack to a larger doc capacity (at least ``min_capacity``).

    Capacity doubles until it fits, so repeated ingest-with-growth is
    amortised O(1) per doc.  The packed bitmap only gains all-zero word
    rows (doc ids are stable), so every existing filter/query result is
    unchanged — callers' cached dense unpacks must still be invalidated
    because X's doc axis grows (``QueryContext`` handles that via its
    epoch).
    """
    if min_capacity <= index.capacity:
        return index
    cap = max(index.capacity, 32)
    while cap < min_capacity:
        cap *= 2
    new_words = (cap + 31) // 32
    packed = jnp.pad(index.packed,
                     ((0, new_words - index.n_words), (0, 0)))
    return PackedIndex(packed, index.doc_freq, index.n_docs)


def grow_vocab(index: PackedIndex, min_vocab: int) -> PackedIndex:
    """Repack to a larger vocabulary (at least ``min_vocab`` term columns).

    The term axis doubles until it fits, so a live lexicon that keeps
    minting term ids (repro.api.CoocIndex) repacks amortised O(1) per term.
    New columns are all-zero postings (no document contains the new terms
    yet) and existing term ids keep their columns, so every existing
    filter/query result is unchanged; cached dense unpacks must be
    invalidated because X's term axis grows (``QueryContext.grow_vocab``
    handles that via its epoch).
    """
    if min_vocab <= index.vocab_size:
        return index
    v = max(index.vocab_size, 1)
    while v < min_vocab:
        v *= 2
    packed = jnp.pad(index.packed, ((0, 0), (0, v - index.vocab_size)))
    df = jnp.pad(index.doc_freq, (0, v - index.vocab_size))
    return PackedIndex(packed, df, index.n_docs)


@jax.jit
def transpose_pad(packed: jax.Array) -> jax.Array:
    """The fused level step's postings layout: ``packed`` (W, V) transposed
    to (V, W) and zero-padded to (V -> 8, W -> 128), the int32 TPU tile.
    One compiled op, so no unpadded transpose is held beside the result."""
    w, v = packed.shape
    return jnp.pad(packed.T, ((0, (-v) % 8), (0, (-w) % 128)))


def incidence_dense(index: PackedIndex, dtype=jnp.float32) -> jax.Array:
    """Unpack to the dense incidence matrix X (D, V). D = capacity."""
    w = index.packed  # (W, V)
    bits = (w[:, None, :] >> jnp.arange(32, dtype=jnp.uint32)[None, :, None]) & jnp.uint32(1)
    x = bits.reshape(index.n_words * 32, index.vocab_size)
    return x.astype(dtype)


# ---------------------------------------------------------------------------
# Device-side index algebra (all pure jnp; shard-map wrappers in cooccurrence)
# ---------------------------------------------------------------------------


def empty_mask(index: PackedIndex) -> jax.Array:
    """All-docs bitmap (the unconstrained filter), masked to n_docs."""
    return _valid_bitmap(index.n_words, index.n_docs)


def _valid_bitmap(n_words: int, n_docs: jax.Array) -> jax.Array:
    """Bitmap with bits [0, n_docs) set."""
    word_idx = jnp.arange(n_words, dtype=jnp.int32)
    base = n_docs - word_idx * 32
    nbits = jnp.clip(base, 0, 32)
    full = jnp.uint32(0xFFFFFFFF)
    # (1 << nbits) - 1, careful with nbits == 32
    m = jnp.where(nbits >= 32, full, (jnp.uint32(1) << nbits.astype(jnp.uint32)) - jnp.uint32(1))
    return m


def term_postings(index: PackedIndex, term_id: jax.Array) -> jax.Array:
    """Postings bitmap of one term: column term_id of packed. (W,) uint32."""
    return jax.lax.dynamic_index_in_dim(index.packed, term_id, axis=1, keepdims=False)


def and_term(index: PackedIndex, mask: jax.Array, term_id: jax.Array) -> jax.Array:
    """Add a term to the filter conditions (paper: 'add word to retrieval
    conditions') = AND its postings into the filter bitmap."""
    return mask & term_postings(index, term_id)


def mask_count(mask: jax.Array) -> jax.Array:
    """Number of documents matching a filter bitmap."""
    return jnp.sum(jax.lax.population_count(mask).astype(jnp.int32))


def doc_freq_under(index: PackedIndex, mask: jax.Array) -> jax.Array:
    """Document frequency of every term within the filtered doc set.

    f[v] = popcount(mask & postings[:, v]) summed over words — the paper's
    'retrieve the words and their frequencies from the documents that meet
    the filtering conditions', vectorised over the whole lexicon.
    """
    anded = index.packed & mask[:, None]
    return jnp.sum(jax.lax.population_count(anded).astype(jnp.int32), axis=0)


def doc_freq_under_batch(index: PackedIndex, masks: jax.Array) -> jax.Array:
    """Batched variant: masks (B, W) -> counts (B, V).

    This is the BFS frontier expansion (DESIGN.md §2): all frontier filters
    evaluated against the whole index in one pass over ``packed``.
    VPU formulation (AND + popcount); see ``doc_freq_under_batch_gemm``
    for the MXU formulation (EXPERIMENTS.md §Perf A1).
    """
    anded = masks[:, :, None] & index.packed[None, :, :]
    return jnp.sum(jax.lax.population_count(anded).astype(jnp.int32), axis=1)


def unpack_bitmap(masks: jax.Array, dtype=jnp.bfloat16) -> jax.Array:
    """Filter bitmaps (B, W) uint32 -> dense 0/1 (B, W*32)."""
    b, w = masks.shape
    bits = (masks[:, :, None] >> jnp.arange(32, dtype=jnp.uint32)[None, None, :]
            ) & jnp.uint32(1)
    return bits.reshape(b, w * 32).astype(dtype)


def doc_freq_under_batch_gemm(masks: jax.Array, x_dense: jax.Array) -> jax.Array:
    """MXU formulation of the frontier expansion (§Perf A1):

        counts = unpack(masks) @ X        (B, D) x (D, V) -> (B, V)

    0/1 bf16 operands with fp32 accumulation — exact for D < 2^24 (CSL:
    396,209 OK).  ``x_dense`` is the incidence unpacked ONCE per query
    batch (not per level) and sharded (docs, terms); the matmul contracts
    the doc axis on the MXU instead of streaming packed words through the
    VPU popcount, which removes the (B, W, V) intermediate entirely.
    """
    m = unpack_bitmap(masks, x_dense.dtype)
    counts = jnp.einsum("bd,dv->bv", m, x_dense,
                        preferred_element_type=jnp.float32)
    return counts.astype(jnp.int32)


def slots_bitmap(doc_slots, n_words: int) -> np.ndarray:
    """Host helper: doc slot ids -> (W,) uint32 doc bitmap.

    The bitmap form of a document set — a retirement target for
    :func:`retire_docs`, or a scope operand for ``bfs_construct``'s
    ``scope_mask`` (both consume the same representation).
    """
    m = np.zeros((n_words,), np.uint32)
    s = np.asarray(doc_slots, np.int64).reshape(-1)
    if s.size:
        if s.min() < 0 or s.max() >= n_words * 32:
            raise ValueError(f"doc slot out of range [0, {n_words * 32})")
        np.bitwise_or.at(m, s // 32, np.uint32(1) << (s % 32).astype(np.uint32))
    return m


def retire_docs(index: PackedIndex, doc_mask: jax.Array) -> PackedIndex:
    """Evict a document set: clear its postings bits, decrement doc_freq.

    doc_mask: (W,) uint32 bitmap of the doc slots to retire (see
    :func:`slots_bitmap`).  Purely functional and jit-safe: one AND pass
    over ``packed`` plus a popcount reduction for the df decrement.

    Doc slot ids are stable (no compaction): retired slots keep their
    positions but hold all-zero postings, so no term filter — and hence no
    query — can ever match them again.  ``n_docs`` is unchanged: it is the
    valid-slot high-water mark (bits at/above it are guaranteed zero), not
    the live-doc count; the ring bookkeeping in ``QueryContext`` tracks
    liveness and hands freed slots to :func:`ingest_at`.
    """
    removed = index.packed & doc_mask[:, None]
    df_removed = jnp.sum(jax.lax.population_count(removed).astype(jnp.int32),
                         axis=0)
    packed = index.packed & ~doc_mask[:, None]
    return PackedIndex(packed, index.doc_freq - df_removed, index.n_docs)


def ingest(index: PackedIndex, new_doc_terms: jax.Array, new_doc_valid: jax.Array) -> PackedIndex:
    """Real-time ingest: append a block of documents to the index.

    new_doc_terms: (N, M) int32 term ids, padded with -1.
    new_doc_valid: (N,) bool — which rows are real documents.

    Purely functional scatter into the packed bitmap, starting at
    ``index.n_docs``; the returned index answers queries immediately
    (the paper's 'real-time' property).  Requires capacity headroom.
    """
    doc_ids = index.n_docs + jnp.cumsum(new_doc_valid.astype(jnp.int32)) - 1  # (N,)
    return ingest_at(index, new_doc_terms, new_doc_valid, doc_ids)


@jax.jit
def ingest_at(index: PackedIndex, new_doc_terms: jax.Array,
              new_doc_valid: jax.Array, doc_slots: jax.Array) -> PackedIndex:
    """Scatter a block of documents into EXPLICIT slot positions.

    The ring-write primitive behind sliding-window ingest: ``doc_slots``
    (N,) int32 names the target slot of each row (slots of invalid rows are
    ignored; valid rows name distinct slots).  Target slots must currently
    hold all-zero postings — either never used, or cleared by
    :func:`retire_docs` — because the OR-scatter below relies on the
    target bits being 0; ``QueryContext`` evicts before it reuses.
    ``n_docs`` advances to the new valid-slot high-water mark (it never
    shrinks: slot ids are stable).
    """
    n_new, m = new_doc_terms.shape
    if n_new == 0:
        return index

    # Dedupe each document's terms so each (doc, term) contributes one bit
    # and one df count, regardless of within-doc term repetition: rows are
    # distinct documents, so a sort within each row suffices (a global
    # sort of all (doc, term) pairs takes tens of seconds to compile for
    # a TPU v5e).
    terms = jnp.sort(jnp.where(new_doc_valid[:, None], new_doc_terms, -1),
                     axis=1)                                       # (N, M)
    first = (terms >= 0) & jnp.concatenate(
        [jnp.ones((n_new, 1), bool), terms[:, 1:] != terms[:, :-1]], axis=1)
    slots = jnp.clip(doc_slots, 0)[:, None]                         # (N, 1)
    word_s = jnp.where(first, slots // 32, 0).astype(jnp.int32).reshape(-1)
    terms_s = jnp.where(first, terms, 0).reshape(-1)
    bit = (slots % 32).astype(jnp.uint32)
    contrib = jnp.where(first, jnp.uint32(1) << bit, jnp.uint32(0)).reshape(-1)

    # Bitwise-OR scatter.  JAX scatter has add/min/max/mul but no OR; after
    # (doc, term) dedupe every (word, term, bit) triple is unique and — the
    # target slots being cleared — the target bits are all currently 0,
    # so scatter-add on disjoint bits IS bitwise OR (no carries possible).
    packed = index.packed.at[word_s, terms_s].add(contrib, mode="drop")

    df = index.doc_freq.at[terms_s].add(
        jnp.where(first, 1, 0).reshape(-1), mode="drop")
    high_water = jnp.max(jnp.where(new_doc_valid,
                                   jnp.clip(doc_slots, 0) + 1, 0))
    n_docs = jnp.maximum(index.n_docs, high_water.astype(jnp.int32))
    return PackedIndex(packed, df, n_docs)
