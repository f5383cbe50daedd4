"""Typed query surface: QuerySpec / PlanKey / QueryResult + the count-method
registry.

Design notes (see README.md §Design):

Before this existed the query parameters travelled as loose kwargs through
three independent dispatch sites (``COUNT_METHODS`` in query_context, the
if-chain in ``cooccurrence._frontier_counts``, the validation in
``CoocEngine``), and the engine froze (depth, topk, beam, method) at
construction — one engine per parameter combination.  This module is the
single source of truth:

* :class:`QuerySpec`  — a frozen, validated description of ONE query.  The
  per-query knobs (seeds) and the per-PLAN knobs (depth/topk/beam/dedup/
  method) live together; :attr:`QuerySpec.plan_key` splits them back out.
  Everything that shapes the compiled executable is in the plan key, so an
  engine can batch heterogeneous specs by grouping on it and cache one
  jitted executable per distinct key (``serve.cooc_engine``).
* :class:`QueryResult` — the typed response: the fixed-shape
  :class:`CoocNetwork` plus serving metadata (latency, index epoch, batch
  occupancy), with the host-side edge views (``edges`` / ``edge_index`` /
  ``top`` / ``nodes``) as methods instead of loose ``network.py`` calls.
* :func:`register_count_method` — the pluggable frontier-count registry.
  A method is ``(name, needs, fn)`` where ``needs`` names the context
  artifacts the method consumes (today only ``"x_dense"``) and ``fn`` maps
  ``(index, masks, operands) -> counts (B, V)`` under jit.  The built-in
  gemm / popcount / pallas methods are registered here; QueryContext's
  operand table, ``bfs_construct``'s frontier dispatch, and the engine's
  validation all read this one registry.
"""
from __future__ import annotations

import dataclasses
from typing import (
    Callable,
    Dict,
    List,
    Mapping,
    NamedTuple,
    Optional,
    Sequence,
    Tuple,
    Union,
)

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.inverted_index import (
    PackedIndex,
    doc_freq_under_batch,
    doc_freq_under_batch_gemm,
)
from repro.core.network import CoocNetwork, nodes_of, to_edge_dict, to_edge_index


# ---------------------------------------------------------------------------
# Count-method registry (the single dispatch site)
# ---------------------------------------------------------------------------

#: context artifacts a count method may request via ``needs``.  Each name is
#: a zero-arg method on QueryContext returning a cached, sharded operand.
KNOWN_OPERANDS = ("x_dense", "packed_t", "packed_t_pad")

#: fn(index, masks (B, W) uint32, operands dict) -> counts (B, V) int32,
#: traceable under jit/vmap.
CountFn = Callable[[PackedIndex, jax.Array, Mapping[str, jax.Array]], jax.Array]

#: level_fn(index, masks, terms, valid, visited, operands, *, k, dedup)
#: -> (weights (B, k), ids (B, k)) int32 — the whole BFS level step
#: (counts + self/visited/valid masking + top-k) as ONE fused call,
#: bit-identical to the unfused chain.  Optional: methods without one run
#: counts through ``fn`` and reduce via ``chunked_top_k``.
LevelFn = Callable[..., Tuple[jax.Array, jax.Array]]


class CountMethod(NamedTuple):
    name: str
    needs: Tuple[str, ...]
    fn: CountFn
    level_fn: Optional[LevelFn] = None


_REGISTRY: Dict[str, CountMethod] = {}


def register_count_method(name: str, needs: Sequence[str], fn: CountFn, *,
                          level_fn: Optional[LevelFn] = None,
                          overwrite: bool = False) -> CountMethod:
    """Register a frontier-count method under ``name``.

    ``needs`` lists the QueryContext artifacts the method consumes (subset
    of :data:`KNOWN_OPERANDS`); they are delivered to ``fn`` in the
    operands mapping.  ``level_fn`` optionally fuses the whole level step
    (counts + masks + top-k) into one call — ``bfs_construct`` prefers it
    over the ``fn``-then-``chunked_top_k`` chain when present (it must be
    bit-identical, values and tie order).  Registration makes the method
    valid everywhere a ``method=`` is accepted: QuerySpec, bfs_construct,
    CoocEngine, CoocIndex.
    """
    needs = tuple(needs)
    unknown = [n for n in needs if n not in KNOWN_OPERANDS]
    if unknown:
        raise ValueError(f"unknown operand(s) {unknown} in needs; "
                         f"known context artifacts: {KNOWN_OPERANDS}")
    if name in _REGISTRY and not overwrite:
        raise ValueError(f"count method {name!r} already registered; "
                         "pass overwrite=True to replace it")
    m = CountMethod(name, needs, fn, level_fn)
    _REGISTRY[name] = m
    return m


def unregister_count_method(name: str) -> None:
    """Remove a registered method (primarily for test hygiene)."""
    if name in ("gemm", "popcount", "pallas", "fused"):
        raise ValueError(f"refusing to unregister built-in method {name!r}")
    _REGISTRY.pop(name, None)


def get_count_method(name: str) -> CountMethod:
    m = _REGISTRY.get(name)
    if m is None:
        raise ValueError(f"unknown method {name!r}; "
                         f"choose from {sorted(_REGISTRY)}")
    return m


def count_method_names() -> Tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


def _gemm_counts(index: PackedIndex, masks: jax.Array,
                 operands: Mapping[str, jax.Array]) -> jax.Array:
    x_dense = operands.get("x_dense")
    assert x_dense is not None, "gemm method needs the dense incidence"
    return doc_freq_under_batch_gemm(masks, x_dense)


def _popcount_counts(index: PackedIndex, masks: jax.Array,
                     operands: Mapping[str, jax.Array]) -> jax.Array:
    return doc_freq_under_batch(index, masks)


def _pallas_counts(index: PackedIndex, masks: jax.Array,
                   operands: Mapping[str, jax.Array]) -> jax.Array:
    from repro.kernels import ops
    return ops.postings_counts(masks, index.packed,
                               backend=ops.pallas_backend())


def _fused_counts(index: PackedIndex, masks: jax.Array,
                  operands: Mapping[str, jax.Array]) -> jax.Array:
    """Counts-only form of the fused method (the materialize/registry
    path, and the per-shard local counts under a mesh): the same popcount
    as "popcount", read from the pre-padded transposed postings when the
    artifact is present (padding words AND to zero; padding columns slice
    off), else straight off the packed index."""
    pt = operands.get("packed_t_pad")
    if pt is None:
        return doc_freq_under_batch(index, masks)
    wp = pt.shape[1]
    m = jnp.pad(masks, ((0, 0), (0, wp - masks.shape[1])))
    anded = m[:, None, :] & pt[None, :, :]
    c = jnp.sum(jax.lax.population_count(anded).astype(jnp.int32), axis=2)
    return c[:, :index.vocab_size]


def _fused_level(index: PackedIndex, masks: jax.Array, terms: jax.Array,
                 valid: jax.Array, visited: jax.Array,
                 operands: Mapping[str, jax.Array], *, k: int, dedup: bool
                 ) -> Tuple[jax.Array, jax.Array]:
    """The fused level step: one ``kernels.ops.level_step`` launch over
    the pre-padded transposed postings (compiled Pallas on TPU, the fused
    XLA form on the CPU) — counts, masking, and top-k never round-trip
    the (B, V) block."""
    from repro.kernels import ops
    return ops.level_step(masks, operands["packed_t_pad"], terms, valid,
                          visited, v=index.vocab_size, k=k, dedup=dedup)


register_count_method("gemm", ("x_dense",), _gemm_counts)
register_count_method("popcount", (), _popcount_counts)
register_count_method("pallas", (), _pallas_counts)
register_count_method("fused", ("packed_t_pad",), _fused_counts,
                      level_fn=_fused_level)


# ---------------------------------------------------------------------------
# QuerySpec / PlanKey
# ---------------------------------------------------------------------------


class PlanKey(NamedTuple):
    """Everything that shapes one executed batch — and nothing else.

    Two specs with equal plan keys run through the same jitted executable
    (possibly in the same micro-batch).  ``scope`` is the one field that is
    an OPERAND name rather than a compile-time shape: it keeps batches
    scope-homogeneous (one bitmap per executed batch) and tells the engine
    which context bitmap to fetch, but the engine's executor cache
    collapses all scoped plans with equal shape fields onto one compiled
    executable (the bitmap is a traced argument).
    """
    depth: int
    topk: int
    beam: int
    dedup: bool
    method: str
    scope: Optional[str] = None


def canonical_exec_key(key: PlanKey) -> PlanKey:
    """Collapse a plan key to its EXECUTABLE identity.

    The scope is an operand choice, never a compiled shape: the engine
    feeds every batch a ``(W,)`` scope bitmap (the named scope's, or the
    all-ones :meth:`~repro.core.query_context.QueryContext.full_mask` for
    unscoped plans), so scoped and unscoped plans with equal shape fields
    share ONE jitted executable.  This is the compile-bomb canonicalization
    layer: traffic that varies only scope names — or toggles scope on and
    off — can never grow the executor cache past one entry per distinct
    (depth, topk, beam, dedup, method) shape.
    """
    return key._replace(scope=None)


#: field names a wire-format query request may carry (== QuerySpec fields).
SPEC_FIELDS: Tuple[str, ...] = ("seeds", "depth", "topk", "beam", "dedup",
                                "method", "scope")


def canonicalize_request(
        request: Union["QuerySpec", Mapping, Sequence[int]], *,
        defaults: Optional[Mapping] = None) -> "QuerySpec":
    """Normalise a wire-format query request into a validated QuerySpec.

    Serving front ends receive queries as loosely-shaped payloads; this is
    the single place they collapse onto the canonical form, so two requests
    that differ only in key order, or in spelling defaults out explicitly
    vs omitting them, produce EQUAL specs — hence equal plan keys, hence
    (with :func:`canonical_exec_key`) one compiled executable.

    ``request`` is one of:

    * a :class:`QuerySpec` — already canonical, returned as-is;
    * a mapping — arbitrary key order; omitted fields fall back to
      ``defaults`` then to the QuerySpec defaults; UNKNOWN keys raise
      (a typo'd field name must never silently become a default);
    * a bare seed-term sequence — completed from ``defaults``.

    ``defaults`` entries outside :data:`SPEC_FIELDS` are ignored, so an
    engine/server can pass its whole config mapping.
    """
    if isinstance(request, QuerySpec):
        return request
    base = {k: v for k, v in dict(defaults or {}).items() if k in SPEC_FIELDS}
    if isinstance(request, Mapping):
        unknown = sorted(set(request) - set(SPEC_FIELDS))
        if unknown:
            raise ValueError(
                f"unknown QuerySpec field(s) {unknown} in request; "
                f"valid fields: {sorted(SPEC_FIELDS)}")
        base.update(request)
        if "seeds" not in base:
            raise ValueError("request names no seeds")
    else:
        base["seeds"] = request
    base["seeds"] = tuple(int(s) for s in base["seeds"])
    return QuerySpec(**base)


@dataclasses.dataclass(frozen=True)
class QuerySpec:
    """A validated, hashable description of one co-occurrence query.

    seeds  — term ids to root the BFS at (1..beam of them);
    depth  — BFS levels; topk — edges kept per frontier node per level;
    beam   — frontier width (and max seeds); dedup — level-synchronous
    visited-set dedup; method — a registered count method;
    scope  — optional name of a QueryContext document scope (time bucket,
    source tag): the query runs as if the index held only the scoped docs.
    Scope existence is checked at execution (the name resolves against the
    serving context, which QuerySpec never sees).
    """
    seeds: Tuple[int, ...]
    depth: int = 3
    topk: int = 16
    beam: int = 32
    dedup: bool = True
    method: str = "gemm"
    scope: Optional[str] = None

    def __post_init__(self):
        seeds = tuple(int(s) for s in self.seeds)
        object.__setattr__(self, "seeds", seeds)
        if not seeds:
            raise ValueError("empty seed set")
        if any(s < 0 for s in seeds):
            raise ValueError(f"negative seed term id in {seeds} "
                             "(-1 is the internal padding sentinel)")
        if len(seeds) > self.beam:
            raise ValueError(
                f"{len(seeds)} seed terms exceed beam={self.beam}; raise the "
                f"spec's beam or split the query")
        for field in ("depth", "topk", "beam"):
            if int(getattr(self, field)) < 1:
                raise ValueError(f"{field} must be >= 1")
        if self.scope is not None and (not isinstance(self.scope, str)
                                       or not self.scope):
            raise ValueError(f"scope must be None or a non-empty scope name, "
                             f"got {self.scope!r}")
        get_count_method(self.method)        # unknown method -> ValueError

    @property
    def plan_key(self) -> PlanKey:
        return PlanKey(self.depth, self.topk, self.beam, self.dedup,
                       self.method, self.scope)

    @property
    def max_edges(self) -> int:
        """Edge slots a network built under this spec occupies."""
        return self.depth * self.beam * self.topk

    def seed_row(self) -> np.ndarray:
        """(beam,) int32 seeds padded with -1 — the executor's row format."""
        row = np.full((self.beam,), -1, np.int32)
        row[:len(self.seeds)] = self.seeds
        return row


# ---------------------------------------------------------------------------
# QueryResult
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class QueryResult:
    """Typed response: the network + serving metadata + host-side views.

    network — fixed-shape edge record (host numpy-backed once served);
    spec    — the QuerySpec that produced it;
    epoch   — the index epoch answered against (which ingests are visible);
    latency_ms / batch_occupancy — serving stats for THIS query (0 / 1 for
    one-shot construction outside an engine).
    queue_ms — set by a CoocServer: from enqueue until the query's batch
    left the server's queue (the batcher's linger included); 0 elsewhere.
    """
    network: CoocNetwork
    spec: QuerySpec
    epoch: int = 0
    latency_ms: float = 0.0
    batch_occupancy: int = 1
    queue_ms: float = 0.0
    _edges: Optional[Dict[Tuple[int, int], int]] = dataclasses.field(
        default=None, repr=False, compare=False)

    def edges(self) -> Dict[Tuple[int, int], int]:
        """Undirected {(min, max): weight} dict (dedup keeps max weight)."""
        if self._edges is None:
            self._edges = to_edge_dict(self.network)
        return self._edges

    def edge_index(self) -> Tuple[np.ndarray, np.ndarray]:
        """(2, E) int32 symmetrised edge index + (E,) weights (GNN-ready)."""
        return to_edge_index(self.network)

    def top(self, limit: int) -> List[Tuple[int, int, int]]:
        """The ``limit`` heaviest undirected edges as (a, b, weight),
        heaviest first (ties by term ids) — the paper's visualisation cut."""
        ranked = sorted(((a, b, w) for (a, b), w in self.edges().items()),
                        key=lambda t: (-t[2], t[0], t[1]))
        return ranked[:limit]

    def nodes(self) -> List[int]:
        return nodes_of(self.network)

    @property
    def num_edges(self) -> int:
        return len(self.edges())
