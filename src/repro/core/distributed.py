"""Device-mesh sharded query execution (the scale-out axis).

Billerbeck et al. (PAPERS.md) show term-partitioned co-occurrence counting
is the natural way to scale pair counting past one machine; on a JAX
device mesh the same decomposition falls out of the bit-packed index
directly.  This module makes the repo's dormant logical-axis sharding
layer (``launch/sharding.py`` rules for ``docs``/``terms``) *execute*
distributed instead of merely annotating placement:

* **Term sharding** (the primary axis, ``shard="terms"``): the packed
  postings ``(W, V)`` — and the dense incidence / transposed postings
  artifacts — split on the vocabulary axis.  Every device evaluates the
  frontier filters against ITS V/n postings columns (per-shard partial
  counts; the Pallas kernels run on the local shard), and the shards
  merge cross-device with an ``all_gather`` along the term axis
  (:func:`sharded_counts`) or a per-shard partial top-k + candidate
  gather + final top-k (:func:`sharded_block_topk`, the materialization
  merge — only ``n * k`` candidates cross the interconnect per row
  block, never the (bm, V) counts).
* **Doc sharding** (``shard="docs"``): the packed word rows ``(W,)``
  split across devices; each device popcounts its document slice and the
  partial counts merge with an integer ``psum`` — exact, since int32
  sums are associative.

Every sharded path is **bit-exact** against the single-device execution
— values AND tie order — which the forced-multi-device differential
harness in ``tests/test_differential.py`` asserts for all count methods
(gemm / popcount / pallas-interpret), bare ``bfs_construct``, batched
engine submission, and ``materialize``:

* counts are exact integers under every method (popcounts, or 0/1 GEMMs
  with fp32 accumulation, exact for D < 2^24), so per-shard partials
  merged by gather or psum reproduce the single-device counts bit for
  bit;
* the top-k merge preserves exact ``lax.top_k`` ORDER by the same
  argument as :func:`~repro.core.cooccurrence.chunked_top_k`: shards are
  contiguous id ranges laid out shard-major (= global-index-major) in
  the candidate buffer, local top-k emits lower-id-first on ties, and
  ``lax.top_k`` prefers earlier candidate slots.

Mesh convention: 2-D ``("data", "model")`` like ``launch/mesh.py``, docs
over "data", terms over "model" (exactly the DEFAULT_RULES binding), one
axis of size > 1.  Build one with :func:`make_cooc_mesh`; pass it to
``QueryContext(mesh=...)`` / ``CoocIndex(mesh=...)`` (or ``devices=``),
or per-call via ``bfs_construct(..., mesh=...)`` /
``materialize(..., mesh=...)``.  With no mesh every path falls back to
the single-device implementation unchanged.
"""
from __future__ import annotations

import functools
from typing import Mapping, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.inverted_index import PackedIndex, unpack_bitmap
from repro.core.query import get_count_method

#: physical mesh axes (launch/mesh.py convention; DEFAULT_RULES maps the
#: logical "terms" axis onto "model" and "docs" onto "data")
DOC_AXIS = "data"
TERM_AXIS = "model"

#: every sharded site maps with the replication check off: the popcount /
#: all_gather compositions here don't all carry replication rules
_smap = functools.partial(jax.shard_map, check_vma=False)


def _round_up(n: int, mult: int) -> int:
    return ((n + mult - 1) // mult) * mult


def _pad_dim(x: jax.Array, axis: int, size: int) -> jax.Array:
    if x.shape[axis] == size:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, size - x.shape[axis])
    return jnp.pad(x, widths)


# ---------------------------------------------------------------------------
# Mesh construction / validation
# ---------------------------------------------------------------------------


def make_cooc_mesh(n_shards: Optional[int] = None, *,
                   devices: Optional[Sequence] = None,
                   shard: str = "terms") -> Mesh:
    """A query-serving mesh over ``n_shards`` devices (default: all).

    shard="terms" -> ("data"=1, "model"=n): postings columns split.
    shard="docs"  -> ("data"=n, "model"=1): packed word rows split.
    """
    if shard not in ("terms", "docs"):
        raise ValueError(f"shard must be 'terms' or 'docs', got {shard!r}")
    devs = list(devices) if devices is not None else list(jax.devices())
    if n_shards is not None:
        if n_shards < 1 or n_shards > len(devs):
            raise ValueError(f"n_shards={n_shards} outside [1, {len(devs)}] "
                             "available devices")
        devs = devs[:n_shards]
    n = len(devs)
    shape = (1, n) if shard == "terms" else (n, 1)
    return Mesh(np.asarray(devs).reshape(shape), (DOC_AXIS, TERM_AXIS))


def validate_mesh(mesh: Mesh) -> None:
    """Reject meshes the sharded paths can't serve (both axes > 1, or
    missing the ("data", "model") axis names)."""
    for ax in (DOC_AXIS, TERM_AXIS):
        if ax not in mesh.shape:
            raise ValueError(
                f"mesh axes {tuple(mesh.shape)} miss {ax!r}; build one with "
                "make_cooc_mesh (axes ('data', 'model'))")
    if mesh.shape[DOC_AXIS] > 1 and mesh.shape[TERM_AXIS] > 1:
        raise ValueError(
            f"mesh shards BOTH docs ({mesh.shape[DOC_AXIS]}) and terms "
            f"({mesh.shape[TERM_AXIS]}); the query paths shard one axis "
            "at a time — use make_cooc_mesh(shard='terms'|'docs')")


def shard_kind(mesh: Mesh) -> str:
    """'docs' when the data axis carries the split, else 'terms' (a 1x1
    mesh degenerates to a single-shard 'terms' layout)."""
    validate_mesh(mesh)
    return "docs" if mesh.shape[DOC_AXIS] > 1 else "terms"


def n_shards(mesh: Mesh) -> int:
    return max(mesh.shape[DOC_AXIS], mesh.shape[TERM_AXIS])


# term-sharded operand layout: (sharded dim, PartitionSpec) per known
# QueryContext artifact; doc-sharded layout below.  x_dense rows are doc
# slots (32 per packed word), packed_t is (V, W).
_TERM_LAYOUT = {"x_dense": (1, P(None, TERM_AXIS)),
                "packed_t": (0, P(TERM_AXIS, None))}
_DOC_LAYOUT = {"x_dense": (0, P(DOC_AXIS, None)),
               "packed_t": (1, P(None, DOC_AXIS))}


def _local_counts(method: str, cooc_gemm: bool, index_l: PackedIndex,
                  masks: jax.Array, ops_l: Mapping[str, jax.Array]
                  ) -> jax.Array:
    """One shard's (B, V_local) counts.  ``cooc_gemm`` routes method
    "pallas" through the tiled Pallas co-occurrence GEMM
    (``kernels.ops.cooccur_counts`` — the materialization path's kernel,
    whose grid tiles the local shard) instead of the postings-popcount
    kernel the frontier registry uses."""
    if cooc_gemm and method == "pallas":
        from repro.kernels import ops as kops
        x = ops_l["x_dense"]
        xl = unpack_bitmap(masks, x.dtype).T
        return kops.cooccur_counts(xl, x, backend=kops.pallas_backend())
    return get_count_method(method).fn(index_l, masks, ops_l)


def _needs(method: str, cooc_gemm: bool) -> Tuple[str, ...]:
    if cooc_gemm and method == "pallas":
        return ("x_dense",)
    if method == "fused":
        # under a mesh the fused method counts straight off the LOCAL
        # packed shard (its fn's no-artifact form): the pre-padded
        # (V->8) artifact's layout need not divide the shard count, and
        # per-shard top-k replaces the fused kernel's merge anyway
        return ()
    return get_count_method(method).needs


def _tiled_all_gather(x: jax.Array, axis_name: str, *, axis: int,
                      tile_axis: int, n_tiles: int = 2) -> jax.Array:
    """``all_gather(axis, tiled=True)`` issued as ``n_tiles`` independent
    collectives over slices of ``tile_axis`` (an axis OTHER than the
    gather axis, so the concatenated result is laid out identically to
    the monolithic gather — bit-exact).  Independent collectives give
    XLA's scheduler the freedom to overlap transfer with the surrounding
    compute (the pipelining hook); falls back to one gather when the tile
    axis doesn't split."""
    if n_tiles <= 1 or x.shape[tile_axis] % n_tiles != 0 \
            or x.shape[tile_axis] < n_tiles:
        return jax.lax.all_gather(x, axis_name, axis=axis, tiled=True)
    parts = jnp.split(x, n_tiles, axis=tile_axis)
    return jnp.concatenate(
        [jax.lax.all_gather(p, axis_name, axis=axis, tiled=True)
         for p in parts], axis=tile_axis)


# ---------------------------------------------------------------------------
# Sharded frontier counts (bfs_construct's expansion under a mesh)
# ---------------------------------------------------------------------------


def sharded_counts(index: PackedIndex, masks: jax.Array, method: str,
                   operands: Mapping[str, jax.Array], mesh: Mesh, *,
                   cooc_gemm: bool = False) -> jax.Array:
    """(B, V) int32 frontier counts under ``mesh`` — replicated output,
    bit-exact vs the single-device method.

    Term mesh: each device counts against its V/n postings columns and
    the partials concatenate with a tiled ``all_gather`` (the cross-
    device merge).  Doc mesh: each device popcounts its word rows and
    the int32 partials ``psum`` — exact, integer addition is associative.
    """
    kind = shard_kind(mesh)
    n = n_shards(mesh)
    needs = _needs(method, cooc_gemm)
    v = index.vocab_size

    if kind == "terms":
        v_pad = _round_up(v, n)
        packed = _pad_dim(index.packed, 1, v_pad)
        df = _pad_dim(index.doc_freq, 0, v_pad)
        extras = [_pad_dim(operands[name], _TERM_LAYOUT[name][0], v_pad)
                  for name in needs]
        specs = tuple(_TERM_LAYOUT[name][1] for name in needs)

        def local(masks, packed_l, df_l, n_docs, *xs):
            idx_l = PackedIndex(packed_l, df_l, n_docs)
            c = _local_counts(method, cooc_gemm, idx_l, masks,
                              dict(zip(needs, xs)))
            return _tiled_all_gather(c, TERM_AXIS, axis=1, tile_axis=0)

        out = _smap(local, mesh=mesh,
                    in_specs=(P(), P(None, TERM_AXIS), P(TERM_AXIS), P(),
                              *specs),
                    out_specs=P(None, None))(
            masks, packed, df, index.n_docs, *extras)
        return out[:, :v]

    # doc sharding: split the packed word rows; masks split with them
    w = index.n_words
    w_pad = _round_up(w, n)
    packed = _pad_dim(index.packed, 0, w_pad)
    masks_p = _pad_dim(masks, 1, w_pad)
    extras, specs = [], []
    for name in needs:
        dim, spec = _DOC_LAYOUT[name]
        size = w_pad * 32 if name == "x_dense" else w_pad
        extras.append(_pad_dim(operands[name], dim, size))
        specs.append(spec)

    def local(masks_l, packed_l, df, n_docs, *xs):
        idx_l = PackedIndex(packed_l, df, n_docs)
        c = _local_counts(method, cooc_gemm, idx_l, masks_l,
                          dict(zip(needs, xs)))
        return jax.lax.psum(c, DOC_AXIS)

    return _smap(local, mesh=mesh,
                 in_specs=(P(None, DOC_AXIS), P(DOC_AXIS, None), P(), P(),
                           *specs),
                 out_specs=P(None, None))(
        masks_p, packed, index.doc_freq, index.n_docs, *extras)


# ---------------------------------------------------------------------------
# Sharded MinHash signatures (the approximate-materialization sketch)
# ---------------------------------------------------------------------------


def sharded_signatures(packed: jax.Array, a: jax.Array, b: jax.Array,
                       mesh: Mesh, *, perm_tile: int = 16) -> jax.Array:
    """Per-term MinHash signatures (V, P) uint32 under ``mesh`` —
    bit-exact vs :func:`repro.core.sketch.minhash_signatures`.

    Term mesh: each device hashes ITS V/n postings columns — the
    signatures are computed term-sharded alongside the postings, and
    only the (V/n, P) shard results cross the interconnect in the final
    gather.  Doc mesh: each device hashes its word rows against GLOBAL
    slot keys and the partial signatures merge with a ``pmin`` — min is
    associative and commutative, so the merge is exact in any shard
    order (all-zero padding rows hash to ``SIG_EMPTY`` and never move a
    minimum; padding columns are sliced off after the gather).
    """
    from repro.core.sketch import signatures_from_packed
    kind = shard_kind(mesh)
    n = n_shards(mesh)
    w, v = packed.shape

    if kind == "terms":
        v_pad = _round_up(v, n)
        packed_p = _pad_dim(packed, 1, v_pad)
        keys = jnp.arange(w * 32, dtype=jnp.uint32)

        def local(packed_l, keys, a, b):
            sig = signatures_from_packed(packed_l, keys, a, b,
                                         perm_tile=perm_tile)
            return _tiled_all_gather(sig, TERM_AXIS, axis=0, tile_axis=1)

        out = _smap(local, mesh=mesh,
                    in_specs=(P(None, TERM_AXIS), P(), P(), P()),
                    out_specs=P(None, None))(packed_p, keys, a, b)
        return out[:v]

    w_pad = _round_up(w, n)
    w_loc = w_pad // n
    packed_p = _pad_dim(packed, 0, w_pad)

    def local(packed_l, a, b):
        off = jax.lax.axis_index(DOC_AXIS).astype(jnp.uint32) \
            * jnp.uint32(w_loc * 32)
        keys = off + jnp.arange(w_loc * 32, dtype=jnp.uint32)
        sig = signatures_from_packed(packed_l, keys, a, b,
                                     perm_tile=perm_tile)
        return jax.lax.pmin(sig, DOC_AXIS)

    return _smap(local, mesh=mesh,
                 in_specs=(P(DOC_AXIS, None), P(), P()),
                 out_specs=P(None, None))(packed_p, a, b)


# ---------------------------------------------------------------------------
# Sharded row-block top-k (materialize's merge under a mesh)
# ---------------------------------------------------------------------------


def sharded_block_topk(index: PackedIndex, masks: jax.Array, rows: jax.Array,
                       operands: Mapping[str, jax.Array], *, k: int,
                       method: str, mesh: Mesh
                       ) -> Tuple[jax.Array, jax.Array]:
    """Top-``k`` neighbors for one materialization row block under
    ``mesh``: (weights, ids), weight -1 marking empty slots — the same
    contract, values, and tie order as the single-device
    ``materialize._topk_row_block``.

    Term mesh (the showcase): per-shard partial top-k over the local
    V/n columns, then only the ``n * k`` candidates are gathered and
    reduced by a final ``lax.top_k`` — the (bm, V) count block never
    crosses the interconnect.  Self-pairs and padding columns are forced
    to -1 BEFORE the local top-k, exactly as the single-device block
    masks them.  Doc mesh: psum-merged replicated counts through the
    single-device ``chunked_top_k``.
    """
    from repro.core.cooccurrence import chunked_top_k
    bm = masks.shape[0]
    v = index.vocab_size

    if shard_kind(mesh) == "docs":
        counts = sharded_counts(index, masks, method, operands, mesh,
                                cooc_gemm=True)
        counts = counts.at[jnp.arange(bm),
                           jnp.clip(rows, 0, v - 1)].set(-1)
        return chunked_top_k(counts, k)

    n = n_shards(mesh)
    v_pad = _round_up(v, n)
    v_loc = v_pad // n
    k_loc = min(k, v_loc)
    k_fin = min(k, n * k_loc)
    needs = _needs(method, cooc_gemm=True)
    packed = _pad_dim(index.packed, 1, v_pad)
    df = _pad_dim(index.doc_freq, 0, v_pad)
    extras = [_pad_dim(operands[name], _TERM_LAYOUT[name][0], v_pad)
              for name in needs]
    specs = tuple(_TERM_LAYOUT[name][1] for name in needs)

    def local(masks, rows, packed_l, df_l, n_docs, *xs):
        idx_l = PackedIndex(packed_l, df_l, n_docs)
        c = _local_counts(method, True, idx_l, masks, dict(zip(needs, xs)))
        off = jax.lax.axis_index(TERM_AXIS).astype(jnp.int32) * v_loc
        cols = off + jnp.arange(v_loc, dtype=jnp.int32)
        # self-pairs and padding columns can never be neighbors: force
        # them BELOW every real count (including real zeros) so the
        # merged order equals the single-device lax.top_k order
        c = jnp.where((cols[None, :] == rows[:, None])
                      | (cols >= v)[None, :], -1, c)
        w_l, i_l = jax.lax.top_k(c, k_loc)
        w_all = _tiled_all_gather(w_l, TERM_AXIS, axis=1, tile_axis=0)
        i_all = _tiled_all_gather(off + i_l, TERM_AXIS, axis=1, tile_axis=0)
        w2, sel = jax.lax.top_k(w_all, k_fin)
        return w2, jnp.take_along_axis(i_all, sel, axis=1)

    w2, i2 = _smap(local, mesh=mesh,
                   in_specs=(P(), P(), P(None, TERM_AXIS), P(TERM_AXIS),
                             P(), *specs),
                   out_specs=(P(None, None), P(None, None)))(
        masks, rows, packed, df, index.n_docs, *extras)
    if k_fin < k:          # k > V (tiny vocab): pad like chunked_top_k
        w2 = jnp.pad(w2, ((0, 0), (0, k - k_fin)), constant_values=-1)
        i2 = jnp.pad(i2, ((0, 0), (0, k - k_fin)))
    return w2, i2


# ---------------------------------------------------------------------------
# Sharded fused level step (bfs_construct's expansion-to-top-k under a mesh)
# ---------------------------------------------------------------------------


def sharded_level_topk(index: PackedIndex, masks: jax.Array,
                       terms: jax.Array, valid: jax.Array,
                       visited: jax.Array, method: str,
                       operands: Mapping[str, jax.Array], mesh: Mesh, *,
                       k: int, dedup: bool) -> Tuple[jax.Array, jax.Array]:
    """One BFS level's (weights, ids) — both (B, k) int32 — under ``mesh``,
    bit-identical (values AND tie order) to the single-device
    counts -> masks -> ``chunked_top_k`` chain.

    Term mesh (the overlap showcase): each device counts against its V/n
    postings columns, applies ALL the level masks locally (self-pair,
    visited, invalid rows — plus padding columns forced to -2, strictly
    below every real masked count), and reduces to a LOCAL top-k.  Only
    the ``n * k`` (weight, id) candidates cross the interconnect (tiled
    gathers the scheduler can overlap) — the former path gathered the
    full (B, V) count block per level and masked it replicated.  The
    merged order is exact ``lax.top_k`` order: shards are contiguous id
    ranges laid out shard-major in the candidate buffer, local top-k
    emits lower-id-first on ties, and the -2 padding sentinels can never
    displace a real candidate (>= k real columns always survive, since
    k is clamped to V).

    Doc mesh: per-shard partial counts ``psum`` to replicated exact
    counts (this merge is irreducible — every document word contributes
    to every count), then the single-device masked ``chunked_top_k``.
    """
    from repro.core.cooccurrence import chunked_top_k
    v = index.vocab_size
    k_eff = min(k, v)
    tclip = jnp.clip(terms, 0).astype(jnp.int32)
    vis = (visited if dedup else jnp.zeros_like(visited)).astype(jnp.int32)

    if shard_kind(mesh) == "terms":
        n = n_shards(mesh)
        v_pad = _round_up(v, n)
        v_loc = v_pad // n
        k_loc = min(k_eff, v_loc)
        needs = _needs(method, cooc_gemm=False)
        packed = _pad_dim(index.packed, 1, v_pad)
        df = _pad_dim(index.doc_freq, 0, v_pad)
        vis_p = _pad_dim(vis, 0, v_pad)
        extras = [_pad_dim(operands[name], _TERM_LAYOUT[name][0], v_pad)
                  for name in needs]
        specs = tuple(_TERM_LAYOUT[name][1] for name in needs)

        def local(masks, tclip, valid, vis_l, packed_l, df_l, n_docs, *xs):
            idx_l = PackedIndex(packed_l, df_l, n_docs)
            c = _local_counts(method, False, idx_l, masks,
                              dict(zip(needs, xs)))
            off = jax.lax.axis_index(TERM_AXIS).astype(jnp.int32) * v_loc
            cols = off + jnp.arange(v_loc, dtype=jnp.int32)
            c = jnp.where(cols[None, :] == tclip[:, None], -1, c)
            c = jnp.where(vis_l[None, :] > 0, -1, c)
            c = jnp.where(valid[:, None], c, -1)
            c = jnp.where((cols >= v)[None, :], jnp.int32(-2), c)
            w_l, i_l = jax.lax.top_k(c, k_loc)
            w_all = _tiled_all_gather(w_l, TERM_AXIS, axis=1, tile_axis=0)
            i_all = _tiled_all_gather(off + i_l, TERM_AXIS, axis=1,
                                      tile_axis=0)
            w2, sel = jax.lax.top_k(w_all, k_eff)
            return w2, jnp.take_along_axis(i_all, sel, axis=1)

        w2, i2 = _smap(local, mesh=mesh,
                       in_specs=(P(), P(), P(), P(TERM_AXIS),
                                 P(None, TERM_AXIS), P(TERM_AXIS), P(),
                                 *specs),
                       out_specs=(P(None, None), P(None, None)))(
            masks, tclip, valid, vis_p, packed, df, index.n_docs, *extras)
    else:
        counts = sharded_counts(index, masks, method, operands, mesh)
        b = masks.shape[0]
        counts = counts.at[jnp.arange(b), tclip].set(-1)
        counts = jnp.where(vis[None, :] > 0, -1, counts)
        counts = jnp.where(valid[:, None], counts, -1)
        w2, i2 = chunked_top_k(counts, k_eff)

    if k_eff < k:          # k > V (tiny vocab): pad like chunked_top_k
        w2 = jnp.pad(w2, ((0, 0), (0, k - k_eff)), constant_values=-1)
        i2 = jnp.pad(i2, ((0, 0), (0, k - k_eff)))
    return w2, i2


# ---------------------------------------------------------------------------
# Row-sharded materialization (n row blocks per launch, one per device)
# ---------------------------------------------------------------------------


def sharded_row_block_topk(index: PackedIndex, packed_t: jax.Array,
                           scope_mask: Optional[jax.Array],
                           operands: Mapping[str, jax.Array], *, k: int,
                           bm: int, method: str,
                           mesh: Mesh) -> Tuple[jax.Array, jax.Array]:
    """Materialization strategy "rows": the ENTIRE row sweep in one
    launch — every device walks a contiguous range of row blocks against
    the full (replicated) index and only the (rows, k) results are
    gathered.  Returns (weights, ids), both (n_blocks * bm, k) covering
    at least ``ceil(V / bm)`` blocks (trailing rows >= V are garbage the
    caller slices off).

    Where the column-split strategy (:func:`sharded_block_topk`) divides
    ONE row block's columns across devices and merges candidates per
    block — one host dispatch per row block, V/n columns per device —
    this one turns the whole materialization into a single dispatch: the
    host's Python loop over ``ceil(V/bm)`` blocks (and its per-call
    dispatch overhead, the dominant term for small-W corpora — see
    ``benchmarks.roofline``) collapses into a per-device ``lax.map``
    over ``n_blocks/n`` blocks, peak transient still one (bm, V) count
    block per device.  Per-block computation is the single-device
    ``materialize._topk_row_block`` registry path verbatim (same masks,
    same ``chunked_top_k`` tie order — bit-exact trivially), there is no
    cross-device reduction at all, and the gather is over contiguous
    block ranges, so the concatenation IS global row order.
    """
    from repro.core.cooccurrence import chunked_top_k
    n = n_shards(mesh)
    ax = TERM_AXIS if shard_kind(mesh) == "terms" else DOC_AXIS
    v = index.vocab_size
    needs = _needs(method, cooc_gemm=True)
    n_blocks = _round_up(-(-v // bm), n)
    starts = bm * jnp.arange(n_blocks, dtype=jnp.int32)     # (n_blocks,)
    scope = (scope_mask if scope_mask is not None
             else jnp.full((index.n_words,), 0xFFFFFFFF, jnp.uint32))
    extras = [operands[name] for name in needs]

    def local(starts_l, packed, df, n_docs, packed_t, scope, *xs):
        idx = PackedIndex(packed, df, n_docs)

        def block(start):
            rows = start + jnp.arange(bm, dtype=jnp.int32)
            masks = packed_t[jnp.clip(rows, 0, v - 1)]
            masks = jnp.where((rows < v)[:, None], masks, jnp.uint32(0))
            masks = masks & scope[None, :]
            c = _local_counts(method, True, idx, masks,
                              dict(zip(needs, xs)))
            c = c.at[jnp.arange(bm), jnp.clip(rows, 0, v - 1)].set(-1)
            return chunked_top_k(c, k)

        w, i = jax.lax.map(block, starts_l)    # (n_blocks/n, bm, k) each
        w = w.reshape(-1, k)
        i = i.reshape(-1, k)
        return (jax.lax.all_gather(w, ax, axis=0, tiled=True),
                jax.lax.all_gather(i, ax, axis=0, tiled=True))

    return _smap(local, mesh=mesh,
                 in_specs=(P(ax), P(), P(), P(), P(), P(),
                           *(P() for _ in needs)),
                 out_specs=(P(None, None), P(None, None)))(
        starts, index.packed, index.doc_freq, index.n_docs, packed_t,
        scope, *extras)
