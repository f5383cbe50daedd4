"""Corpus-level network materialization (the paper's whole-corpus artifact).

The BFS query path (`bfs_construct`) serves seed-rooted neighborhoods; the
paper's CSL experiments, and every global-statistics consumer downstream
(degree distributions, density — Margan et al., PAPERS.md), need the FULL
co-occurrence network.  Computing it naively is the (V, V) dense matrix
``C = X^T X`` — quadratic memory that no serving deployment can afford.

:func:`materialize` computes the same network **tile by tile** and keeps
only each term's top-``k`` heaviest neighbors (Billerbeck et al.'s
observation that corpus-scale pair counting is tractable when you tile and
truncate per term):

* rows are processed in ``(row_tile,)`` blocks of terms; a block's filter
  bitmaps are its postings rows (AND a scope bitmap, if any), so
  ``C[i, j] = popcount(post_i & scope & post_j)`` — exactly the counts the
  query path computes, over exactly the scoped document set;
* counts come from ``method=``:

  - ``"pallas"``   — the tiled Pallas co-occurrence GEMM
    (:func:`repro.kernels.cooccur.cooccur_gemm_pallas` via
    ``kernels.ops.cooccur_counts``): ``C_tile = X_l^T @ X_r`` over the
    dense incidence columns of the row/column tiles; the tiles stream
    through a running per-row top-``k`` merge (`lax.scan`), so the block
    never holds more than one ``(row_tile, col_tile)`` count tile
    (compiled on TPU, interpret mode on the CPU);
  - ``"gemm"`` / ``"popcount"`` (and any registered method) — the
    count-method registry (:mod:`repro.core.query`): one registry call
    per row block produces the (row_tile, V) counts, reduced by one
    ``chunked_top_k`` (identical tie order);

  either way the (V, V) matrix is never allocated — the peak transient is
  a single row block's counts and the result is O(V·k).

Top-k semantics match the host oracles bit-exactly: ties break toward the
lower term id (`lax.top_k` order; earlier column tiles occupy earlier
candidate slots), self-pairs are excluded, zero counts emit no edge.

With a :class:`~repro.core.query_context.QueryContext` the dense incidence
and the transposed postings are the context's epoch-versioned cached
artifacts — a warm context materializes with ZERO unpacks — and the
finished network itself is cached per (k, method, scope) and invalidated
by ingest/evict/grow epoch bumps (and by scope redefinition, via the
per-scope version counters).

**Approximate mode** (``mode="approx"``, :mod:`repro.core.sketch`): the
exact sweep above is quadratic in V no matter how it is tiled.  The
approximate mode prunes it with MinHash/LSH — per-term signatures over
the packed postings generate candidate term pairs, and the exact
counting machinery runs ONLY on each row block's candidate columns,
gathered into a dense sub-index so the registry kernels and the sharded
candidate merge are reused unchanged.  Candidates are exact-counted, so
every *emitted* edge weight is exact; only edges whose endpoints never
collided in a band can be missed (the recall/speedup differential
harness in ``tests/test_differential.py`` measures exactly that trade).
"""
from __future__ import annotations

import functools
from typing import Mapping, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.inverted_index import (
    PackedIndex,
    incidence_dense,
    transpose_pad,
    unpack_bitmap,
)
from repro.core.network import CoocNetwork
from repro.core.query import get_count_method
from repro.core.sketch import (
    DEFAULT_NUM_PERM,
    DEFAULT_THRESHOLD,
    TILE_QUANTUM,
    ApproxCoocNetwork,
    ApproxStats,
    candidate_columns,
    estimate_recall,
    gathered_top_k,
    hash_coefficients,
    lsh_params,
    minhash_signatures,
    pad_candidates,
)


def _round_up(n: int, mult: int) -> int:
    return ((n + mult - 1) // mult) * mult


@functools.partial(jax.jit,
                   static_argnames=("k", "row_tile", "col_tile", "method",
                                    "mesh"))
def _topk_row_block(index: PackedIndex, packed_t: jax.Array,
                    scope_mask: Optional[jax.Array],
                    operands: Mapping[str, jax.Array], row_start, *,
                    k: int, row_tile: int, col_tile: int, method: str,
                    mesh=None) -> Tuple[jax.Array, jax.Array]:
    """Top-k neighbors for one block of ``row_tile`` consecutive terms;
    returns (weights, neighbor ids), weight -1 marking empty slots.

    Registry methods produce the block's (row_tile, V) counts in one call
    and reduce through ``chunked_top_k``; the pallas path never holds more
    than a (row_tile, col_tile) count tile — tiles stream through a
    running (row_tile, k) merge.  Both orders are exact ``lax.top_k``
    order: in the merge, the running candidates (earlier = lower column
    tiles, already weight-sorted with lower-id-first ties) precede the new
    tile's columns (laid out in id order), and ``lax.top_k`` prefers
    earlier slots.
    """
    v = packed_t.shape[0]
    rows = row_start + jnp.arange(row_tile, dtype=jnp.int32)        # (bm,)
    masks = packed_t[jnp.clip(rows, 0, v - 1)]                      # (bm, W)
    masks = jnp.where((rows < v)[:, None], masks, jnp.uint32(0))
    if scope_mask is not None:
        masks = masks & scope_mask[None, :]

    if mesh is not None:
        # sharded block: per-shard partial counts/top-k, cross-device
        # candidate merge — same values, same tie order (distributed.py)
        from repro.core.distributed import sharded_block_topk
        return sharded_block_topk(index, masks, rows, operands, k=k,
                                  method=method, mesh=mesh)

    if method != "pallas":
        # one registry call materializes the whole (row_tile, V) count
        # block — reduce it in one chunked_top_k (same lower-id-first tie
        # order as the streaming merge below, and the k > V pad already
        # matches the -1/0 empty-slot contract)
        from repro.core.cooccurrence import chunked_top_k
        blk = get_count_method(method).fn(index, masks, operands)   # (bm, V)
        blk = blk.at[jnp.arange(row_tile), jnp.clip(rows, 0, v - 1)].set(-1)
        return chunked_top_k(blk, k)

    from repro.kernels import ops
    v_pad = _round_up(v, col_tile)
    n_tiles = v_pad // col_tile
    x = operands["x_dense"]                        # (D, v_pad) — pre-padded
    xl = unpack_bitmap(masks, x.dtype).T                            # (D, bm)
    backend = ops.pallas_backend()

    def tile_counts(j0):
        xr = jax.lax.dynamic_slice(x, (0, j0), (x.shape[0], col_tile))
        return ops.cooccur_counts(xl, xr, backend=backend,
                                  bm=row_tile, bn=col_tile)

    def merge(carry, jt):
        run_w, run_i = carry
        j0 = jt * col_tile
        cols = j0 + jnp.arange(col_tile, dtype=jnp.int32)
        counts = tile_counts(j0)
        counts = jnp.where(cols[None, :] == rows[:, None], -1, counts)
        cand_w = jnp.concatenate([run_w, counts], axis=1)
        cand_i = jnp.concatenate(
            [run_i, jnp.broadcast_to(cols[None, :], counts.shape)], axis=1)
        w2, sel = jax.lax.top_k(cand_w, k)  # cooclint: disable=COOC002 -- cand_w has k + col_tile >= k columns by construction
        return (w2, jnp.take_along_axis(cand_i, sel, axis=1)), None

    run0 = (jnp.full((row_tile, k), -1, jnp.int32),
            jnp.zeros((row_tile, k), jnp.int32))
    (run_w, run_i), _ = jax.lax.scan(merge, run0,
                                     jnp.arange(n_tiles, dtype=jnp.int32))
    return run_w, run_i


@functools.partial(jax.jit,
                   static_argnames=("k", "row_tile", "method", "mesh"))
def _topk_row_blocks_rows(index: PackedIndex, packed_t: jax.Array,
                          scope_mask: Optional[jax.Array],
                          operands: Mapping[str, jax.Array], *,
                          k: int, row_tile: int, method: str, mesh
                          ) -> Tuple[jax.Array, jax.Array]:
    """Row-sharded materialization: the WHOLE row sweep in one launch —
    each device ``lax.map``s a contiguous range of row blocks against
    the replicated index, so the host-side per-block dispatch loop (the
    dominant term for small-W corpora; see ``benchmarks.roofline``)
    disappears entirely.  Returns (n_blocks * row_tile, k)."""
    from repro.core.distributed import sharded_row_block_topk
    return sharded_row_block_topk(index, packed_t, scope_mask, operands,
                                  k=k, bm=row_tile, method=method,
                                  mesh=mesh)


@functools.partial(jax.jit,
                   static_argnames=("k", "row_tile", "method", "mesh"))
def _approx_topk_row_block(index: PackedIndex, packed_t: jax.Array,
                           operands: Mapping[str, jax.Array], row_start,
                           cand_cols: jax.Array, rows_pos: jax.Array, *,
                           k: int, row_tile: int, method: str,
                           mesh=None) -> Tuple[jax.Array, jax.Array]:
    """Top-k neighbors for one row block over its LSH candidate columns
    only — ``mode="approx"``'s tile step.

    cand_cols: (C,) int32 sorted global candidate term ids, -1 padding
    to the power-of-two tile bucket (``sketch.pad_candidates``);
    rows_pos: (row_tile,) int32 position of each row's own term inside
    cand_cols (== C when absent, matching no column).  The candidates
    gather into a dense (W, C) sub-index with pad columns ZEROED — a pad
    column counts 0 everywhere, so it can never emit a valid edge — and
    the exact machinery runs on the sub-problem unchanged: the
    count-method registry (or ``distributed.sharded_block_topk``'s
    candidate merge under a mesh) produces the (row_tile, C) counts, and
    the winners map back to global term ids.  Tie order matches the
    exact path: candidates are gathered in ascending global-id order and
    ``lax.top_k`` prefers earlier slots.
    """
    v = packed_t.shape[0]
    c = cand_cols.shape[0]
    rows = row_start + jnp.arange(row_tile, dtype=jnp.int32)        # (bm,)
    masks = packed_t[jnp.clip(rows, 0, v - 1)]                      # (bm, W)
    masks = jnp.where((rows < v)[:, None], masks, jnp.uint32(0))

    pad = cand_cols < 0
    safe = jnp.clip(cand_cols, 0, v - 1)
    sub_packed = jnp.where(pad[None, :], jnp.uint32(0),
                           jnp.take(index.packed, safe, axis=1))    # (W, C)
    sub_df = jnp.where(pad, 0, jnp.take(index.doc_freq, safe))
    sub_index = PackedIndex(sub_packed, sub_df, index.n_docs)
    sub_ops = {}
    if "x_dense" in operands:
        x = operands["x_dense"]
        sub_ops["x_dense"] = jnp.where(pad[None, :],
                                       jnp.zeros((), x.dtype),
                                       jnp.take(x, safe, axis=1))

    if mesh is not None:
        # candidate-merge the sub-problem across the mesh: rows_pos are
        # the sub-problem's "row term" ids, so the shard-local self mask
        # hits exactly the gathered self column (C when absent — no
        # local column matches, since C divides into the shard padding)
        from repro.core.distributed import sharded_block_topk
        w_b, loc = sharded_block_topk(sub_index, masks, rows_pos, sub_ops,
                                      k=k, method=method, mesh=mesh)
        ids = jnp.take(jnp.maximum(cand_cols, 0), jnp.clip(loc, 0, c - 1))
        return w_b, ids

    blk = get_count_method(method).fn(sub_index, masks, sub_ops)    # (bm, C)
    cols = jnp.arange(c, dtype=jnp.int32)
    blk = jnp.where(cols[None, :] == rows_pos[:, None], -1, blk)
    return gathered_top_k(blk, cand_cols, k)


def _resolve_materialize_operands(index, method: str, needs=None):
    """(ctx-or-None, PackedIndex, packed_t, operands) for ``method``.

    The pallas path consumes the dense incidence (the cooccur GEMM's right
    operand); registry methods declare their ``needs`` (``needs=``
    overrides — the approx path gathers candidate columns per block, so
    it drops pre-padded artifacts whose layout can't survive the gather).
    With a QueryContext every artifact is the epoch-versioned cache; a
    bare index builds them one-shot.
    """
    from repro.core.query_context import QueryContext
    if needs is None:
        needs = (("x_dense",) if method == "pallas"
                 else get_count_method(method).needs)
    if isinstance(index, QueryContext):
        ctx = index
        return (ctx, ctx.index, ctx.packed_t(),
                {name: getattr(ctx, name)() for name in needs})
    builders = {
        "x_dense": lambda: incidence_dense(index, jnp.bfloat16),
        "packed_t": lambda: index.packed.T,
        "packed_t_pad": lambda: transpose_pad(index.packed),
    }
    return (None, index, index.packed.T,
            {name: builders[name]() for name in needs})


def materialize(index, *, k: int = 8, method: str = "gemm",
                scope: Optional[str] = None,
                scope_mask: Optional[jax.Array] = None,
                row_tile: int = 128, col_tile: int = 512,
                use_cache: bool = True, mesh=None,
                shard_strategy: str = "auto", mode: str = "exact",
                threshold: float = DEFAULT_THRESHOLD,
                num_perm: int = DEFAULT_NUM_PERM,
                sketch_seed: int = 0) -> CoocNetwork:
    """Materialize the corpus co-occurrence network, top-``k`` per term.

    index: a PackedIndex, or a QueryContext (cached artifacts + result
    caching).  method: ``"pallas"`` routes through the tiled Pallas
    co-occurrence GEMM; any registered count method (``"gemm"``,
    ``"popcount"``, ...) runs through the registry.  scope: a context
    scope NAME (time bucket, source tag); scope_mask: an explicit (W,)
    uint32 doc bitmap (mutually exclusive with ``scope``).  Either way the
    result is exactly the network of an index holding only the scoped
    documents.  The reserved name ``scope="all-time"`` widens instead of
    narrowing: live docs PLUS every window-evicted block spilled to the
    context's cold store (``QueryContext(cold_store=...)``) answer
    together, exactly as if nothing had ever been evicted.

    Returns a :class:`CoocNetwork` with ``V * k`` edge slots — slot
    ``i*k + j`` is term ``i``'s j-th heaviest neighbor (``src=i``), ties
    broken toward the lower term id, self-pairs and zero counts invalid.
    The (V, V) matrix is never allocated: beyond the cached incidence the
    query path already holds and this O(V·k) result, the peak transient
    is one (row_tile, col_tile) count tile under ``method="pallas"``, or
    one row block's (row_tile, V) counts under a registry method.

    mesh: an optional query mesh (``distributed.make_cooc_mesh``;
    defaults to the context's).  shard_strategy picks how the mesh
    divides the work, both bit-exact vs the single-device path:

    * ``"rows"`` — n different row blocks per launch, one per device
      against the replicated index; no cross-device reduction, n× fewer
      host dispatches (the term that dominates small-W corpora);
    * ``"cols"`` — one row block's columns split V/n per device with a
      candidate-only top-k merge (per-device transient is the LOCAL
      shard's counts — the memory-bound regime's strategy);
    * ``"auto"`` (default) — ``"rows"``.

    mode="approx" (``threshold=``, ``num_perm=``, ``sketch_seed=``):
    sketch-pruned materialization (:mod:`repro.core.sketch`).  Per-term
    MinHash signatures (``num_perm`` permutations) feed LSH banding at
    the Jaccard ``threshold``; each row block is exact-counted ONLY
    against its candidate columns, gathered into a dense tile (blocks
    with no candidates are skipped outright).  Emitted edge weights are
    exact; edges can only be *missed*, never wrong.  Returns an
    :class:`~repro.core.sketch.ApproxCoocNetwork` — the same edge-slot
    contract plus ``recall_estimate`` (sketch-estimated detection
    probability of the emitted edges) and ``stats`` (tiles counted vs
    the exact sweep, candidate pairs, chosen bands).  Scoped
    materialization stays exact-only (a scope rewrites every filter
    bitmap, so live signatures would estimate the wrong Jaccard);
    ``scope="all-time"`` is supported — the combined live+cold index is
    re-sketched.  Under a mesh the candidate tiles run through the
    sharded candidate merge (``shard_strategy="rows"`` does not apply).
    """
    from repro.core.query_context import QueryContext
    if k < 1:
        raise ValueError(f"k must be >= 1, got {k}")
    if method != "pallas":
        get_count_method(method)           # unknown method -> ValueError
    if scope is not None and scope_mask is not None:
        raise ValueError("pass scope= (a context scope name) OR scope_mask= "
                         "(an explicit bitmap), not both")
    ctx = index if isinstance(index, QueryContext) else None
    if scope is not None and ctx is None:
        raise ValueError(
            f"scope={scope!r} needs a QueryContext to resolve the scope "
            "name to a document bitmap; got a bare index")
    if mesh is None and ctx is not None:
        mesh = ctx.mesh
    if shard_strategy not in ("auto", "rows", "cols"):
        raise ValueError(f"shard_strategy must be 'auto', 'rows' or 'cols', "
                         f"got {shard_strategy!r}")
    if mode not in ("exact", "approx"):
        raise ValueError(f"mode must be 'exact' or 'approx', got {mode!r}")
    if mode == "approx":
        if scope_mask is not None or (scope is not None
                                      and scope != "all-time"):
            raise ValueError(
                "mode='approx' does not support scoped materialization: "
                "a scope rewrites every filter bitmap, so the live "
                "signatures would estimate the wrong Jaccard — "
                "materialize the scope exactly, or sketch a dedicated "
                "index holding only the scoped documents")
        if shard_strategy == "rows":
            raise ValueError(
                "mode='approx' prunes per row block, so the whole-sweep "
                "shard_strategy='rows' launch does not apply; use "
                "'auto'/'cols' (the sharded candidate merge)")

    if scope == "all-time":
        # the cold-tier scope: live docs + every evicted block spilled to
        # the context's cold store, answered through this same tiled path
        # over the stacked bitmap (counts are additive over disjoint doc
        # sets).  Cached per (epoch, cold_version): live ingest moves the
        # epoch, a new spill moves the version — either invalidates.
        combined = ctx.all_time_index()
        if combined is ctx.index:
            # nothing spilled (or no cold store): all-time == live
            scope = None
        else:
            cache_key = None
            ver = ctx.cold_version()
            if use_cache:
                mesh_key = (tuple(int(d.id) for d in mesh.devices.flat)
                            if mesh is not None else None)
                cache_key = ("materialize", "all-time", k, method, row_tile,
                             col_tile, mesh_key, shard_strategy, mode,
                             float(threshold), int(num_perm),
                             int(sketch_seed))
                hit = ctx.cached_artifact(cache_key, ver)
                if hit is not None:
                    return hit
            net = materialize(combined, k=k, method=method,
                              row_tile=row_tile, col_tile=col_tile,
                              mesh=mesh, shard_strategy=shard_strategy,
                              mode=mode, threshold=threshold,
                              num_perm=num_perm, sketch_seed=sketch_seed)
            if cache_key is not None:
                ctx.store_artifact(cache_key, net, ver)
            return net
    if mode == "approx":
        return _materialize_approx(index, ctx, k=k, method=method,
                                   row_tile=row_tile, mesh=mesh,
                                   threshold=threshold, num_perm=num_perm,
                                   sketch_seed=sketch_seed,
                                   use_cache=use_cache)
    strategy = None if mesh is None else (
        "rows" if shard_strategy == "auto" else shard_strategy)

    v = (ctx.index if ctx is not None else index).vocab_size
    # shrink tiles toward the vocab so tiny indices don't pad to 128/512
    # (tile minima match the fp32 (8, 128) TPU layout; ops.cooccur_counts
    # re-adapts the kernel's own tiles to the operands it receives)
    bm = min(row_tile, _round_up(v, 8))
    bn = min(col_tile, _round_up(v, 128))

    cache_key = None
    cache_ver = 0
    if ctx is not None and use_cache and (scope is not None or scope_mask is None):
        # the entry is versioned by (epoch, scope_version): a dropped or
        # redefined scope misses here and fails/rebuilds below (the new
        # store OVERWRITES the superseded network — no leak), so a warm
        # hit is a dict lookup — no operand resolution, no device work.
        # The mesh joins the key: sharded and single-device results are
        # bit-identical in VALUE, but their device placement differs —
        # a cached network must not masquerade under a different
        # placement (device IDENTITY matters, not just the axis shape:
        # two same-shape meshes over disjoint devices are distinct)
        mesh_key = (tuple(int(d.id) for d in mesh.devices.flat)
                    if mesh is not None else None)
        cache_key = ("materialize", k, method, scope, bm, bn, mesh_key,
                     strategy)
        cache_ver = ctx.scope_version(scope) if scope is not None else 0
        hit = ctx.cached_artifact(cache_key, cache_ver)
        if hit is not None:
            return hit

    _, pidx, packed_t, operands = _resolve_materialize_operands(index, method)
    if scope is not None:
        scope_mask = ctx.scope(scope)
    elif scope_mask is not None:
        scope_mask = jnp.asarray(scope_mask)
        if scope_mask.shape != (pidx.n_words,):
            raise ValueError(f"scope_mask shape {scope_mask.shape} != "
                             f"({pidx.n_words},) (one uint32 per 32 doc slots)")

    if method == "pallas" and (mesh is None or strategy == "rows"):
        # pad the incidence columns ONCE so every column tile is full-width
        # (the sharded path pads to the shard multiple internally instead)
        x = operands["x_dense"]
        v_pad = _round_up(v, bn)
        if v_pad > v:
            operands = dict(operands)
            operands["x_dense"] = jnp.pad(x, ((0, 0), (0, v_pad - v)))

    if strategy == "rows":
        run_w, run_i = _topk_row_blocks_rows(pidx, packed_t, scope_mask,
                                             operands, k=k, row_tile=bm,
                                             method=method, mesh=mesh)
        run_w, run_i = run_w[:v], run_i[:v]
    else:
        ws, ids = [], []
        for r0 in range(0, _round_up(v, bm), bm):
            w_b, i_b = _topk_row_block(pidx, packed_t, scope_mask, operands,
                                       r0, k=k, row_tile=bm, col_tile=bn,
                                       method=method, mesh=mesh)
            ws.append(w_b)
            ids.append(i_b)
        run_w = jnp.concatenate(ws, axis=0)[:v]                 # (V, k)
        run_i = jnp.concatenate(ids, axis=0)[:v]
    valid = run_w > 0
    net = CoocNetwork(
        src=jnp.repeat(jnp.arange(v, dtype=jnp.int32), k),
        dst=jnp.where(valid, run_i, -1).reshape(-1),
        weight=jnp.where(valid, run_w, 0).reshape(-1),
        valid=valid.reshape(-1),
    )
    if cache_key is not None:
        ctx.store_artifact(cache_key, net, cache_ver)
    return net


def _materialize_approx(index, ctx, *, k: int, method: str, row_tile: int,
                        mesh, threshold: float, num_perm: int,
                        sketch_seed: int, use_cache: bool
                        ) -> ApproxCoocNetwork:
    """``mode="approx"``'s driver: signatures -> banding -> candidate
    tiles -> exact counts on the candidates only.

    The host loop mirrors the exact per-block loop, but each block
    counts against ONLY its gathered candidate columns (power-of-two
    bucketed widths, so recompiles are O(log V) shapes) and blocks with
    no candidates are skipped without any device work.  Work accounting
    runs in (row_tile, TILE_QUANTUM) tile units against the exact
    sweep's total — the differential harness's ``tiles_fraction``.
    """
    pidx = ctx.index if ctx is not None else index
    v = pidx.vocab_size
    bm = min(row_tile, _round_up(v, 8))

    cache_key = None
    if ctx is not None and use_cache:
        mesh_key = (tuple(int(d.id) for d in mesh.devices.flat)
                    if mesh is not None else None)
        cache_key = ("materialize", "approx", k, method, bm, mesh_key,
                     float(threshold), int(num_perm), int(sketch_seed))
        # epoch-checked inside cached_artifact; version 0 — approx serves
        # the all-time scope only, so the epoch is the whole story
        hit = ctx.cached_artifact(cache_key, version=0)
        if hit is not None:
            return hit

    bands, rows_per_band = lsh_params(threshold, num_perm)
    if ctx is not None:
        sigs_dev = ctx.term_signatures(num_perm=num_perm, seed=sketch_seed)
    else:
        a_np, b_np = hash_coefficients(num_perm, sketch_seed)
        sigs_dev = minhash_signatures(pidx.packed, jnp.asarray(a_np),
                                      jnp.asarray(b_np))
    sigs = np.asarray(jax.device_get(sigs_dev))
    active = np.asarray(jax.device_get(pidx.doc_freq)) > 0
    per_block, n_pairs = candidate_columns(sigs, b=bands, r=rows_per_band,
                                           active=active, row_tile=bm)

    # candidate tiles re-gather columns per block, so pre-padded operand
    # layouts can't ride along: fused falls back to its packed-popcount
    # path, pallas runs the registry postings kernel single-device and
    # the cooccur GEMM's x_dense only under the sharded merge
    needs = get_count_method(method).needs if method != "pallas" else ()
    if method == "pallas" and mesh is not None:
        needs = ("x_dense",)
    needs = tuple(n for n in needs if n != "packed_t_pad")
    _, pidx, packed_t, operands = _resolve_materialize_operands(
        index, method, needs=needs)

    n_stripes = _round_up(v, TILE_QUANTUM) // TILE_QUANTUM
    n_blocks = _round_up(v, bm) // bm
    tiles_counted = 0
    ws, ids = [], []
    for bi in range(n_blocks):
        cols = per_block[bi]
        if cols is None:
            ws.append(jnp.full((bm, k), -1, jnp.int32))
            ids.append(jnp.zeros((bm, k), jnp.int32))
            continue
        cand = pad_candidates(cols, v)                    # (C,) -1-padded
        tiles_counted += len(cand) // TILE_QUANTUM
        r0 = bi * bm
        terms = np.arange(r0, r0 + bm, dtype=np.int64)
        pos = np.minimum(np.searchsorted(cols, np.clip(terms, 0, v - 1)),
                         len(cols) - 1)
        present = (cols[pos] == terms) & (terms < v)
        rows_pos = np.where(present, pos, len(cand)).astype(np.int32)
        w_b, i_b = _approx_topk_row_block(
            pidx, packed_t, operands, r0, jnp.asarray(cand),
            jnp.asarray(rows_pos), k=k, row_tile=bm, method=method,
            mesh=mesh)
        ws.append(w_b)
        ids.append(i_b)
    run_w = jnp.concatenate(ws, axis=0)[:v]                       # (V, k)
    run_i = jnp.concatenate(ids, axis=0)[:v]
    valid = run_w > 0

    w_np = np.asarray(jax.device_get(run_w))
    i_np = np.asarray(jax.device_get(run_i))
    valid_np = (w_np > 0).reshape(-1)
    recall = estimate_recall(sigs, np.repeat(np.arange(v), k),
                             i_np.reshape(-1), valid_np,
                             b=bands, r=rows_per_band)
    net = ApproxCoocNetwork(
        src=jnp.repeat(jnp.arange(v, dtype=jnp.int32), k),
        dst=jnp.where(valid, run_i, -1).reshape(-1),
        weight=jnp.where(valid, run_w, 0).reshape(-1),
        valid=valid.reshape(-1),
        recall_estimate=recall,
        stats=ApproxStats(tiles_counted=int(tiles_counted),
                          tiles_total=int(n_blocks * n_stripes),
                          candidate_pairs=int(n_pairs),
                          num_perm=int(num_perm),
                          threshold=float(threshold),
                          bands=int(bands),
                          rows_per_band=int(rows_per_band)),
    )
    if cache_key is not None:
        ctx.store_artifact(cache_key, net)
    return net
