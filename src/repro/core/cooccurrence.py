"""Co-occurrence network construction algorithms (the paper's core).

Three algorithms, mirroring the paper:

* ``traversal_construct_host``  — Algorithm 1: per-document term-pair
  enumeration (numpy/dict).  The honest CPU baseline, used both as the
  correctness oracle and as the timed baseline in the benchmarks.
* ``recursive_construct_host``  — Algorithm 2: recursive DFS over the
  inverted index (host Python; recursion is not a TPU pattern — kept as a
  semantic reference, as the paper itself recommends the BFS form).
* ``bfs_construct``             — Algorithm 3: inverted-index + BFS,
  TPU-adapted: fixed-width *beam* frontier, batched popcount frontier
  expansion (one pass over the packed index per level), distributed
  top-k.  Pure jnp — works under jit on one device and under pjit on a
  ("pod","data","model") mesh with the index sharded.
* ``traversal_construct_dense`` — the traversal baseline *on TPU*: the
  full co-occurrence matrix as one X^T X GEMM (exact for D < 2^24).

Edge semantics (paper §3): an edge (a, b, w) means "term b is one of the
top-k most frequent terms among documents matching the filter path ending
at a", with w = that document count.  With depth >= 2 the filter is the AND
of the whole path, i.e. conditional co-occurrence along the BFS path.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Mapping, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.inverted_index import PackedIndex, incidence_dense, transpose_pad
from repro.core.network import CoocNetwork


# ---------------------------------------------------------------------------
# Algorithm 1 — traversal baseline (host oracle)
# ---------------------------------------------------------------------------


def traversal_construct_host(doc_terms: Sequence[Sequence[int]],
                             vocab_size: int) -> Dict[Tuple[int, int], int]:
    """Paper Algorithm 1: iterate documents, enumerate term pairs, count.

    Returns a dict {(min(a,b), max(a,b)): count}.  Self-pairs skipped, as in
    the paper's pseudocode.  A pair co-occurring in one document counts once
    (doc-level co-occurrence — consistent with the index-based algorithms).
    """
    counts: Dict[Tuple[int, int], int] = {}
    for terms in doc_terms:
        uniq = sorted(set(int(t) for t in terms if 0 <= int(t) < vocab_size))
        for i, a in enumerate(uniq):
            for b in uniq[i + 1:]:
                if a == b:
                    continue
                counts[(a, b)] = counts.get((a, b), 0) + 1
    return counts


def traversal_construct_dense(x: jax.Array) -> jax.Array:
    """TPU-adapted traversal baseline: C = X^T X over the dense incidence.

    x: (D, V) 0/1 incidence (any float dtype).  Result (V, V) fp32 with
    C[v, v] = df(v) on the diagonal; off-diagonal entries are exact pair
    co-occurrence counts for D < 2^24.
    """
    return jnp.einsum("dv,dw->vw", x, x, preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# Algorithm 2 — recursive DFS reference (host)
# ---------------------------------------------------------------------------


def recursive_construct_host(x: np.ndarray, seed_term: int, depth: int, topk: int,
                             dedup: bool = True) -> List[Tuple[int, int, int]]:
    """Paper Algorithm 2 on a dense bool incidence matrix (reference only).

    Returns [(src, dst, weight), ...] in DFS discovery order.
    """
    edges: List[Tuple[int, int, int]] = []
    visited = {int(seed_term)}

    def rec(mask: np.ndarray, term: int, d: int) -> None:
        if d >= depth:
            return
        counts = x[mask].sum(axis=0).astype(np.int64)
        counts[term] = -1
        if dedup:
            for t in visited:
                counts[t] = -1
        order = np.argsort(-counts, kind="stable")[:topk]
        chosen = [int(t) for t in order if counts[t] > 0]
        for t in chosen:
            edges.append((term, t, int(counts[t])))
            if dedup:
                visited.add(t)
        for t in chosen:
            rec(mask & x[:, t].astype(bool), t, d + 1)

    seed_mask = x[:, int(seed_term)].astype(bool)
    rec(seed_mask, int(seed_term), 0)
    return edges


def bfs_construct_host(x: np.ndarray, seed_term: int, depth: int, topk: int,
                       beam: Optional[int] = None, dedup: bool = True
                       ) -> List[Tuple[int, int, int]]:
    """Paper Algorithm 3 on a dense bool incidence matrix (reference).

    Level-synchronous BFS; optional beam cap (by weight) per level to match
    the TPU implementation.  Returns [(src, dst, weight), ...].
    """
    edges: List[Tuple[int, int, int]] = []
    visited = {int(seed_term)}
    frontier: List[Tuple[np.ndarray, int]] = [(x[:, int(seed_term)].astype(bool), int(seed_term))]
    for _ in range(depth):
        candidates: List[Tuple[int, np.ndarray, int, int]] = []  # (w, mask, src, dst)
        for mask, term in frontier:
            counts = x[mask].sum(axis=0).astype(np.int64)
            counts[term] = -1
            if dedup:
                for t in visited:
                    counts[t] = -1
            order = np.argsort(-counts, kind="stable")[:topk]
            for t in order:
                t = int(t)
                if counts[t] > 0:
                    edges.append((term, t, int(counts[t])))
                    candidates.append((int(counts[t]), mask & x[:, t].astype(bool), term, t))
        # level-synchronous: all edge targets recorded this level -> visited
        if dedup:
            visited |= {c[3] for c in candidates}
            seen_lvl = set()
            uniq = []
            for c in sorted(candidates, key=lambda c: -c[0]):
                if c[3] not in seen_lvl:
                    seen_lvl.add(c[3])
                    uniq.append(c)
            candidates = uniq
        else:
            candidates.sort(key=lambda c: -c[0])
        if beam is not None:
            candidates = candidates[:beam]
        frontier = [(c[1], c[3]) for c in candidates]
        if not frontier:
            break
    return edges


class HostIndex(NamedTuple):
    """Paper-faithful host-side inverted + forward index (numpy).

    postings[t]  — sorted doc-id array for term t (the inverted lists);
    fwd_terms / fwd_ptr — CSR forward index: unique terms of doc d are
    ``fwd_terms[fwd_ptr[d]:fwd_ptr[d+1]]`` (what the search engine's
    aggregation walks).
    """
    postings: List[np.ndarray]
    fwd_terms: np.ndarray
    fwd_ptr: np.ndarray
    vocab_size: int


def build_host_index(doc_terms: Sequence[Sequence[int]], vocab_size: int
                     ) -> HostIndex:
    uniq_per_doc = [np.unique(np.asarray(d, dtype=np.int64)) for d in doc_terms]
    fwd_ptr = np.zeros(len(doc_terms) + 1, np.int64)
    np.cumsum([len(u) for u in uniq_per_doc], out=fwd_ptr[1:])
    fwd_terms = (np.concatenate(uniq_per_doc) if uniq_per_doc
                 else np.zeros(0, np.int64)).astype(np.int32)
    by_term: List[List[int]] = [[] for _ in range(vocab_size)]
    for d, u in enumerate(uniq_per_doc):
        for t in u:
            by_term[int(t)].append(d)
    postings = [np.asarray(p, dtype=np.int64) for p in by_term]
    return HostIndex(postings, fwd_terms, fwd_ptr, vocab_size)


def _gather_counts(hidx: HostIndex, doc_ids: np.ndarray) -> np.ndarray:
    """Term document-frequencies over a doc subset: one pass over the
    matched docs' forward lists (O(sum m), NOT O(sum m^2))."""
    if doc_ids.size == 0:
        return np.zeros(hidx.vocab_size, np.int64)
    starts = hidx.fwd_ptr[doc_ids]
    ends = hidx.fwd_ptr[doc_ids + 1]
    lens = ends - starts
    total = int(lens.sum())
    if total == 0:
        return np.zeros(hidx.vocab_size, np.int64)
    # vectorised multi-range gather: element j of range i sits at
    # starts[i] + j; expand all ranges with one repeat + arange
    shifted = np.concatenate(([0], np.cumsum(lens)[:-1]))
    offs = np.repeat(starts - shifted, lens) + np.arange(total)
    return np.bincount(hidx.fwd_terms[offs], minlength=hidx.vocab_size)


def bfs_construct_host_fast(hidx: HostIndex, seed_terms: Sequence[int], *,
                            depth: int, topk: int, beam: Optional[int] = None,
                            dedup: bool = True) -> List[Tuple[int, int, int]]:
    """Paper Algorithm 3, host-faithful: the optimized algorithm exactly as
    deployable on CPU + a search engine — postings-list intersection for the
    filter, forward-index aggregation for the high-frequency word set.

    Per level-node cost is O(sum_{matched docs} m + V log k), versus the
    traversal baseline's O(sum m^2) pair enumeration: this is the
    measured-speedup implementation behind the paper's Fig. 7/8 claim.
    ``bfs_construct`` (bit-packed, jnp) is the TPU-native throughput form
    of the same algorithm — identical edge semantics (tested).
    """
    edges: List[Tuple[int, int, int]] = []
    visited = set(int(s) for s in seed_terms)
    frontier = [(hidx.postings[int(s)], int(s)) for s in seed_terms]
    for _ in range(depth):
        candidates: List[Tuple[int, np.ndarray, int, int]] = []
        for doc_ids, term in frontier:
            counts = _gather_counts(hidx, doc_ids)
            counts[term] = -1
            if dedup:
                for t in visited:
                    counts[t] = -1
            # stable sort: ties break by term id, matching the dense host
            # reference and the device top_k exactly
            order = np.argsort(-counts, kind="stable")[:topk]
            for t in order:
                t = int(t)
                if counts[t] > 0:
                    edges.append((term, t, int(counts[t])))
                    candidates.append((int(counts[t]),
                                       np.intersect1d(doc_ids, hidx.postings[t],
                                                      assume_unique=True),
                                       term, t))
        if dedup:
            visited |= {c[3] for c in candidates}
            seen_lvl = set()
            uniq = []
            for c in sorted(candidates, key=lambda c: -c[0]):
                if c[3] not in seen_lvl:
                    seen_lvl.add(c[3])
                    uniq.append(c)
            candidates = uniq
        else:
            candidates.sort(key=lambda c: -c[0])
        if beam is not None:
            candidates = candidates[:beam]
        frontier = [(c[1], c[3]) for c in candidates]
        if not frontier:
            break
    return edges


# ---------------------------------------------------------------------------
# Algorithm 3 — inverted-index + BFS on TPU (the paper's contribution)
# ---------------------------------------------------------------------------


class BFSState(NamedTuple):
    masks: jax.Array    # (B, W) uint32 — per-frontier-node filter bitmaps
    terms: jax.Array    # (B,) int32   — frontier terms
    valid: jax.Array    # (B,) bool
    visited: jax.Array  # (V,) bool


def chunked_top_k(x: jax.Array, k: int, n_chunks: int = 16):
    """Two-stage top-k over the last axis (EXPERIMENTS.md §Perf A2).

    Stage 1: top-k within each of ``n_chunks`` contiguous column chunks —
    with the columns sharded over the model axis and n_chunks = its size,
    stage 1 is shard-LOCAL.  Stage 2: top-k over the n_chunks*k merged
    candidates (tiny).  Under SPMD this turns the (B, V) all-gather that a
    plain lax.top_k needs into a (B, n_chunks*k) one.

    Exact: every global top-k element is in its chunk's top-k.  Exact
    ORDER too: lax.top_k breaks ties by lower index; merged candidates are
    laid out chunk-major = global-index-major, and within a chunk local
    top-k already emits lower index first.

    Shape contract: always returns (B, k) — ``k > V`` (tiny vocab,
    generous spec) is clamped to V internally and the missing slots pad
    back with weight -1 / index 0, matching ``_expand_level``'s invalid-
    slot convention.  The former behavior — falling through to
    ``jax.lax.top_k(x, k)``, which REQUIRES k <= V — crashed every caller
    that didn't replicate ``_expand_level``'s private guard.

    Single-pass threshold: the chunked form only pays off when stage 2's
    candidate set is SMALLER than the input — ``n_chunks * k < V``.  At
    small V (or large k) the merge degenerates to a full extra
    ``lax.top_k`` pass over >= V candidates, pure overhead on top of the
    n_chunks stage-1 passes; those cases take the direct single-pass path
    (identical values and tie order — both are exact lax.top_k order).
    """
    b, v = x.shape
    k_eff = min(k, v)
    if (v % n_chunks != 0 or v // n_chunks < k_eff
            or n_chunks * k_eff >= v):
        w, gi = jax.lax.top_k(x, k_eff)
    else:
        c = v // n_chunks
        xs = x.reshape(b, n_chunks, c)
        w1, i1 = jax.lax.top_k(xs, k_eff)                 # (B, n_chunks, k)
        gi1 = i1 + (jnp.arange(n_chunks, dtype=i1.dtype) * c)[None, :, None]
        w2, sel = jax.lax.top_k(w1.reshape(b, n_chunks * k_eff), k_eff)
        w, gi = w2, jnp.take_along_axis(gi1.reshape(b, n_chunks * k_eff),
                                        sel, axis=1)
    if k_eff < k:
        w = jnp.pad(w, ((0, 0), (0, k - k_eff)), constant_values=-1)
        gi = jnp.pad(gi, ((0, 0), (0, k - k_eff)))
    return w, gi


def _frontier_counts(index: PackedIndex, masks: jax.Array, method: str,
                     operands: Mapping[str, jax.Array],
                     mesh=None) -> jax.Array:
    """Frontier-expansion dispatch: masks (B, W) -> counts (B, V).

    Resolved through the single count-method registry in
    :mod:`repro.core.query` — built-ins:

    "gemm"     — unpack(masks) @ operands["x_dense"] on the MXU;
    "popcount" — AND + popcount over the packed bitmap, pure jnp (VPU);
    "pallas"   — the same popcount op through the tiled Pallas postings
                 kernel (compiled on TPU, interpret mode on the CPU;
                 padding to tile multiples handled by kernels.ops).

    With a ``mesh`` the same method runs term- or doc-sharded: per-shard
    partial counts merged cross-device (gather / psum), bit-exact vs the
    single-device path (:mod:`repro.core.distributed`).
    """
    if mesh is not None:
        from repro.core.distributed import sharded_counts
        return sharded_counts(index, masks, method, operands, mesh)
    from repro.core.query import get_count_method
    m = get_count_method(method)
    return m.fn(index, masks, operands)


def _resolve_operands(index, method: str, x_dense: Optional[jax.Array],
                      operands: Optional[Mapping[str, jax.Array]],
                      mesh=None
                      ) -> Tuple[PackedIndex, Dict[str, jax.Array], object]:
    """Unwrap a QueryContext and assemble the method's operands mapping
    (plus the resolved mesh: the explicit argument, else the context's).

    Precedence per needed operand: explicit ``operands`` entry > legacy
    ``x_dense`` kwarg > the context's cached artifact (zero rebuilds on a
    warm context) > the x_dense one-shot unpack fallback.  This is the one
    place operand plumbing happens — registering a method with a new
    ``needs`` entry requires no engine/bfs changes, only a new context
    artifact.
    """
    from repro.core.query import get_count_method
    from repro.core.query_context import QueryContext
    ops: Dict[str, jax.Array] = dict(operands) if operands else {}
    if x_dense is not None:
        ops.setdefault("x_dense", x_dense)
    needs = get_count_method(method).needs
    if isinstance(index, QueryContext):
        ctx = index
        index = ctx.index
        if mesh is None:
            mesh = ctx.mesh
        for name in needs:
            if name not in ops:
                ops[name] = getattr(ctx, name)()
    # Legacy one-shot builders (no context): each needed artifact is built
    # ONCE (outside the level loop).  x_dense padding rows beyond n_docs
    # are all-zero bits so they can never contribute to counts;
    # packed_t_pad matches QueryContext.packed_t_pad's (V->8, W->128)
    # layout.  Serving goes through QueryContext, which builds once per
    # ingest EPOCH and shards at build time.
    def _x_dense_oneshot():
        from repro.launch.sharding import constrain
        return constrain(incidence_dense(index, jnp.bfloat16),
                         ("docs", "terms"))

    builders = {"x_dense": _x_dense_oneshot,
                "packed_t": lambda: jnp.transpose(index.packed),
                "packed_t_pad": lambda: transpose_pad(index.packed)}
    for name in needs:
        if name not in ops:
            ops[name] = builders[name]()
    return index, ops, mesh


def _postings_rows(index: PackedIndex, operands: Mapping[str, jax.Array],
                   terms: jax.Array) -> jax.Array:
    """Postings bitmaps of ``terms`` as (n, W) rows.  A row gather from the
    transposed artifact when the method carries one; else a column gather
    from ``packed`` (XLA then keeps one column-major copy of the index for
    the step — the methods without the artifact pay that)."""
    pt = operands.get("packed_t_pad")
    if pt is None:
        pt = operands.get("packed_t")
    if pt is not None:
        return pt[terms, :index.n_words]
    return jnp.take(index.packed, terms, axis=1).T


def _expand_level(index: PackedIndex, state: BFSState, topk: int, dedup: bool,
                  method: str, operands: Mapping[str, jax.Array], mesh=None):
    """One BFS level: batched frontier expansion + beam re-selection.

    The expansion-to-top-k segment dispatches three ways, all bit-exact
    (values AND tie order) against each other:

    * mesh          — :func:`distributed.sharded_level_topk`: per-shard
      counts + per-shard masking + LOCAL top-k, merged by a candidate-only
      gather (n·k candidates cross the interconnect, never (B, V) counts);
    * ``level_fn``  — the method's fused level step (one kernel launch:
      method "fused");
    * default       — the unfused chain: registry counts, the three masks,
      ``chunked_top_k``.

    k can exceed V (tiny vocab, generous spec): every path clamps to V
    and pads the missing slots back as invalid (weight -1 / index 0) —
    the (depth, B, topk) edge-record shape contract is independent of the
    vocabulary.
    """
    from repro.core.query import get_count_method
    b = state.masks.shape[0]

    m = get_count_method(method)
    if mesh is not None:
        from repro.core.distributed import sharded_level_topk
        w_top, idx_top = sharded_level_topk(
            index, state.masks, state.terms, state.valid, state.visited,
            method, operands, mesh, k=topk, dedup=dedup)
    elif m.level_fn is not None:
        w_top, idx_top = m.level_fn(index, state.masks, state.terms,
                                    state.valid, state.visited, operands,
                                    k=topk, dedup=dedup)
    else:
        counts = m.fn(index, state.masks, operands)             # (B, V) int32
        # mask self-pairs, invalid rows, and (optionally) visited terms
        counts = counts.at[jnp.arange(b), jnp.clip(state.terms, 0)].set(-1)
        if dedup:
            counts = jnp.where(state.visited[None, :], -1, counts)
        counts = jnp.where(state.valid[:, None], counts, -1)
        w_top, idx_top = chunked_top_k(counts, topk)            # (B, topk)
    edge_valid = w_top > 0
    edges = (
        jnp.broadcast_to(state.terms[:, None], (b, topk)),      # src
        idx_top,                                                # dst
        jnp.where(edge_valid, w_top, 0),                        # weight
        edge_valid,
    )

    # Candidate pool for the next frontier: B*k (dst, weight, parent-row).
    flat_w = jnp.where(edge_valid, w_top, -1).reshape(-1)       # (B*k,)
    flat_dst = idx_top.reshape(-1)
    flat_parent = jnp.repeat(jnp.arange(b), topk)
    if dedup:
        # Keep one candidate per dst term (the heaviest): sort by -weight,
        # then stably by dst; first occurrence per dst = heaviest.
        order = jnp.argsort(-flat_w, stable=True)
        dst_sorted = flat_dst[order]
        o2 = jnp.argsort(dst_sorted, stable=True)
        ds2 = dst_sorted[o2]
        first2 = jnp.concatenate([jnp.array([True]), ds2[1:] != ds2[:-1]])
        keep_sorted = jnp.zeros_like(first2).at[o2].set(first2)
        keep = jnp.zeros_like(keep_sorted).at[order].set(keep_sorted)
        flat_w = jnp.where(keep, flat_w, -1)

    n_next = b
    w_next, cand_idx = jax.lax.top_k(flat_w, n_next)  # cooclint: disable=COOC002 -- n_next = b <= flat_w's B*topk columns by construction
    next_valid = w_next > 0
    next_dst = flat_dst[cand_idx]
    next_parent = flat_parent[cand_idx]
    parent_masks = state.masks[next_parent]                     # (B, W)
    post = _postings_rows(index, operands, jnp.clip(next_dst, 0))  # (B, W)
    next_masks = jnp.where(next_valid[:, None], parent_masks & post, jnp.uint32(0))
    visited = state.visited
    if dedup:
        # every edge target recorded this level becomes visited
        # (level-synchronous BFS: counts above used the previous level's set)
        vis_i32 = visited.astype(jnp.int32)
        vis_i32 = vis_i32.at[jnp.clip(idx_top, 0).reshape(-1)].add(
            edge_valid.reshape(-1).astype(jnp.int32))
        visited = vis_i32 > 0
    new_state = BFSState(next_masks, jnp.where(next_valid, next_dst, -1), next_valid, visited)
    return new_state, edges


def bfs_construct(index, seed_terms: jax.Array, *, depth: int,
                  topk: int, beam: int, dedup: bool = True,
                  method: str = "gemm",
                  x_dense: Optional[jax.Array] = None,
                  operands: Optional[Mapping[str, jax.Array]] = None,
                  scope_mask: Optional[jax.Array] = None,
                  mesh=None
                  ) -> CoocNetwork:
    """Paper Algorithm 3, TPU-adapted (see README.md §Design).

    index: a PackedIndex, or a ``QueryContext`` — with a context, cached
    per-epoch operands (the gemm path's dense incidence) are pulled from
    it instead of being rebuilt here, so a warm context performs ZERO
    unpacks per query.

    seed_terms: (S,) int32, padded with -1 (S <= beam).  The frontier is a
    fixed-width beam of ``beam`` filter bitmaps; each level evaluates every
    frontier filter against the whole index in one batched pass, then a
    distributed top-k.  Returns a CoocNetwork with ``depth * beam * topk``
    edge slots (invalid slots masked).

    method:
      "gemm"     — counts = unpack(masks) @ X on the MXU (EXPERIMENTS.md
                   §Perf A1 — the optimized form).  X comes from
                   ``x_dense`` (pass the context's cached, sharded copy
                   when serving) or is unpacked here as a fallback;
      "popcount" — bit-packed AND + popcount streamed through the VPU
                   (the paper-faithful-baseline TPU adaptation);
      "pallas"   — popcount via the tiled ``kernels.postings`` Pallas
                   kernel (compiled on TPU, interpret mode on CPU);
      "fused"    — the whole level step (popcount + masking + top-k) as
                   ONE launch over the pre-padded transposed postings
                   (``kernels.level_step``; compiled Pallas on TPU, the
                   fused XLA form on the CPU) — zero per-query padding.
    All are exact (0/1 operands, fp32/int32 accumulation) and tested
    equal.

    Registered methods receive their ``needs`` through the ``operands``
    mapping (``x_dense=`` remains as a legacy spelling of
    ``operands={"x_dense": ...}``).

    scope_mask: optional (W,) uint32 document bitmap restricting the query
    to a doc subset (a time window, a source tag — see
    ``QueryContext.scope``).  ANDed into the depth-0 seed filters only:
    every deeper filter is ``parent_mask & postings``, so the scope is
    inherited by the whole BFS for free, and results are exactly those of
    an index containing only the scoped documents.

    mesh: an optional query mesh (``distributed.make_cooc_mesh``) — the
    frontier expansion runs term- or doc-sharded across its devices with
    a cross-device merge, bit-exact vs the single-device path.  Defaults
    to the context's mesh when ``index`` is a mesh-bearing QueryContext;
    ``None`` (no context mesh) is the unchanged single-device path.
    """
    index, ops, mesh = _resolve_operands(index, method, x_dense, operands,
                                         mesh)
    v = index.vocab_size
    b = beam
    s = seed_terms.shape[0]
    assert s <= b, "seed set must fit in the beam"

    seed_valid = seed_terms >= 0
    seeds = jnp.clip(seed_terms, 0)
    masks0 = jnp.zeros((b, index.n_words), jnp.uint32)
    masks0 = masks0.at[:s].set(jnp.where(seed_valid[:, None],
                                         _postings_rows(index, ops, seeds),
                                         jnp.uint32(0)))
    if scope_mask is not None:
        masks0 = masks0 & scope_mask[None, :]
    terms0 = jnp.full((b,), -1, jnp.int32).at[:s].set(jnp.where(seed_valid, seeds, -1))
    valid0 = jnp.zeros((b,), jnp.bool_).at[:s].set(seed_valid)
    visited0 = (jnp.zeros((v,), jnp.int32).at[seeds].add(seed_valid.astype(jnp.int32))) > 0

    state = BFSState(masks0, terms0.astype(jnp.int32), valid0, visited0)

    def step(state, _):
        new_state, edges = _expand_level(index, state, topk, dedup, method,
                                         ops, mesh)
        return new_state, edges

    from repro.launch.flags import unroll_scans
    if unroll_scans():
        es = []
        for _ in range(depth):
            state, edges = step(state, None)
            es.append(edges)
        src, dst, w, ev = (jnp.stack([e[i] for e in es]) for i in range(4))
    else:
        _, (src, dst, w, ev) = jax.lax.scan(step, state, None, length=depth)
    # (depth, B, k) -> flat
    return CoocNetwork(
        src=src.reshape(-1).astype(jnp.int32),
        dst=dst.reshape(-1).astype(jnp.int32),
        weight=w.reshape(-1).astype(jnp.int32),
        valid=ev.reshape(-1),
    )


def bfs_construct_batch(index, seed_terms: jax.Array, *, depth: int,
                        topk: int, beam: int, dedup: bool = True,
                        method: str = "gemm",
                        x_dense: Optional[jax.Array] = None,
                        operands: Optional[Mapping[str, jax.Array]] = None,
                        scope_mask: Optional[jax.Array] = None,
                        mesh=None
                        ) -> CoocNetwork:
    """Batched queries (the web-service scenario): seed_terms (Q, S).

    vmaps the whole BFS over independent queries; the packed index (and
    the method's operands — whether cached in a QueryContext or passed via
    ``operands``/``x_dense``) is closed over — broadcast, i.e. sharded
    once, not replicated per query, under pjit.  ``scope_mask`` is shared
    by the whole batch (the engine groups queries by scope, so a batch is
    scope-homogeneous).  ``mesh`` shards the frontier expansion exactly
    as in :func:`bfs_construct` (vmap batches straight through the
    shard_map'd counts).
    """
    index, ops, mesh = _resolve_operands(index, method, x_dense, operands,
                                         mesh)
    fn = functools.partial(bfs_construct, index, depth=depth, topk=topk,
                           beam=beam, dedup=dedup, method=method,
                           operands=ops, scope_mask=scope_mask, mesh=mesh)
    nets = jax.vmap(fn)(seed_terms)
    return CoocNetwork(
        src=nets.src.reshape(-1), dst=nets.dst.reshape(-1),
        weight=nets.weight.reshape(-1), valid=nets.valid.reshape(-1),
    )


def construct(index, spec) -> "QueryResult":
    """Typed one-shot entry point: run one :class:`~repro.core.query.QuerySpec`
    and return a :class:`~repro.core.query.QueryResult`.

    ``index`` is a PackedIndex or a QueryContext (cached operands are pulled
    from a context, exactly as in :func:`bfs_construct`).  This is the
    reference semantics for the engine's batched path — a micro-batched
    result must be bit-identical to ``construct(ctx, spec)``.

    A spec with ``scope`` set requires a QueryContext (the scope NAME
    resolves to the context's cached bitmap; a bare PackedIndex has no
    scope table).
    """
    from repro.core.query import QueryResult
    from repro.core.query_context import QueryContext
    scope_mask = None
    if spec.scope is not None:
        if not isinstance(index, QueryContext):
            raise ValueError(
                f"spec.scope={spec.scope!r} needs a QueryContext to resolve "
                "the scope name to a document bitmap; got a bare index")
        scope_mask = index.scope(spec.scope)
    net = bfs_construct(index, jnp.asarray(spec.seed_row()), depth=spec.depth,
                        topk=spec.topk, beam=spec.beam, dedup=spec.dedup,
                        method=spec.method, scope_mask=scope_mask)
    epoch = index.epoch if isinstance(index, QueryContext) else 0
    return QueryResult(network=net, spec=spec, epoch=epoch)
