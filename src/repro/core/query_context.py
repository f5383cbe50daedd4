"""QueryContext — the single execution abstraction behind every query path.

Design notes (see README.md §Design):

Before this existed, each jitted ``bfs_construct`` call re-unpacked the
bit-packed index into the dense incidence matrix X (D, V) — per query, per
service, with no reuse and no sharding at the unpack site.  The context
inverts that: it owns the packed index plus **epoch-versioned derived
artifacts** (the dense X used by the ``gemm`` method, the named scope
bitmaps), builds them lazily ONCE per ingest epoch, and shards them at
build time via ``launch.sharding.constrain`` so the jitted query functions
receive already-placed operands.

* ``x_dense()``     — cached dense incidence, rebuilt iff the epoch moved.
* ``ingest(...)``   — host-side capacity check (raise or grow-by-repack)
                      BEFORE the jitted scatter, then an epoch bump; the
                      stale cache is rebuilt exactly once, not per query.
* ``operands(m)``   — the method dispatch table: per-method extra operands
                      for ``bfs_construct`` (gemm needs X; popcount and
                      pallas read the packed bitmap directly).

**Sliding window (streaming mode).**  With ``window=N`` the context stops
growing and manages doc slots as a ring: each ingest batch is a *block*
occupying consecutive ring slots, and when live docs would exceed the
window the OLDEST blocks are evicted — their postings bits cleared and
their ``doc_freq`` contributions decremented on device
(:func:`~repro.core.inverted_index.retire_docs`) — before the new block is
scattered into the freed slots (:func:`~repro.core.inverted_index.ingest_at`).
Capacity is fixed at ``ceil(window / 32) * 32`` slots: a long-lived
streaming index holds O(window) memory no matter how many docs flow
through.  Doc slot ids are stable for a block's whole lifetime; liveness
is host bookkeeping (the block deque), never a device search.

**Scopes.**  A scope is a named ``(W,)`` uint32 document bitmap — a time
bucket, a source tag — maintained host-side and served to queries as a
cached epoch-versioned device artifact (``scope(name)``).  In the
bit-packed index a doc scope is just one more bitmap ANDed into the
depth-0 seed filters (``bfs_construct(..., scope_mask=...)``), so scoped
queries cost one extra AND, not a re-index.  Eviction clears retired docs
from every scope; ``ingest_docs(..., scope="tag")`` tags the new block.

The context is host-side state (plain Python object, NOT a pytree): jitted
functions take ``(index, seeds, x_dense)`` as array arguments, so a new
epoch is a new array — no retrace, no stale constants baked into traces.
"""
from __future__ import annotations

import contextlib
from collections import deque
from collections.abc import Mapping
from typing import Deque, Dict, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.inverted_index import (
    PackedIndex,
    _pack_host,
    grow_capacity,
    grow_vocab,
    incidence_dense,
    ingest_at,
    pack_docs,
    retire_docs,
    slots_bitmap,
    transpose_pad,
)
from repro.core.query import get_count_method
from repro.core.spans import SpanLog


class _CountMethodsView(Mapping):
    """Deprecated read-only alias over the count-method registry.

    The single source of truth is :mod:`repro.core.query`
    (``register_count_method`` / ``get_count_method``); this view keeps the
    legacy ``COUNT_METHODS`` mapping-of-needs shape alive for old callers
    and stays live as methods are registered.
    """

    def __getitem__(self, name):
        try:
            return get_count_method(name).needs
        except ValueError as e:           # Mapping protocol wants KeyError
            raise KeyError(name) from e

    def __iter__(self):
        from repro.core.query import count_method_names
        return iter(count_method_names())

    def __len__(self):
        from repro.core.query import count_method_names
        return len(count_method_names())


#: Deprecated: use repro.core.query.get_count_method / register_count_method.
COUNT_METHODS = _CountMethodsView()


class CapacityError(ValueError):
    """Ingest would overflow the packed index's doc capacity."""


class QueryContext:
    """Packed index + epoch-versioned caches + method dispatch table."""

    def __init__(self, index: PackedIndex, *, dtype=jnp.bfloat16,
                 window: Optional[int] = None, mesh=None, cold_store=None):
        if mesh is not None:
            from repro.core.distributed import validate_mesh
            validate_mesh(mesh)
        self._mesh = mesh
        # cold tier: a dict-like (MutableMapping[str, bytes]) store; when
        # set, every evicted block is spilled (re-packed + df) BEFORE its
        # postings bits are cleared, and scope="all-time" materialization
        # re-queries live + cold together (core.storage, core.materialize)
        self._cold = cold_store
        self._cold_seq = 0        # next spill key / cold-tier version
        if mesh is not None:
            # the index itself lives sharded on the mesh, as its artifacts
            # do: left on one device, every sharded query would re-split
            # the whole bitmap across the mesh
            index = PackedIndex(self._place(index.packed, ("docs", "terms")),
                                self._place(index.doc_freq, ("terms",)),
                                self._place(index.n_docs, ()))
        self._index = index
        self._dtype = dtype
        self.epoch = 0
        #: spans of the engine steps, ingests and artifact rebuilds served
        #: from this context (``cooc.engine.step``, ``cooc.index.*``)
        self.spans = SpanLog()
        self._ingesting = False
        self._x_dense: Optional[jax.Array] = None
        self._x_epoch = -1
        self.unpack_count = 0   # monitoring: dense rebuilds == ingest epochs
        self._packed_t: Optional[jax.Array] = None
        self._pt_epoch = -1
        self._packed_t_pad: Optional[jax.Array] = None
        self._ptp_epoch = -1
        # generic epoch-versioned artifact cache (materialized networks):
        # entries are (epoch, version, value); stale epochs are pruned on
        # store, and a re-store under the same key overwrites — a key
        # holds at most one live value
        self._artifact_cache: Dict[Tuple, Tuple[int, int, object]] = {}
        # per-scope redefinition counters: tag/define/drop mutate a scope
        # WITHOUT an epoch bump, so artifacts derived from a scope key on
        # (epoch, scope_version) to stay correct across redefinitions
        self._scope_ver: Dict[str, int] = {}
        # MinHash sketch state (core.sketch): per (num_perm, seed) config,
        # the per-live-block signatures as (block_array, sig) pairs —
        # strong refs matched by identity, so term_signatures() hashes
        # only blocks it has never seen (a block's postings bits are
        # immutable while it is live).  The merged (V, P) signature is
        # served through the epoch-versioned artifact cache.
        self._sketch_blocks: Dict[Tuple[int, int], list] = {}
        # streaming state: live ingest blocks (slot arrays, oldest first),
        # ring write head, named scope bitmaps + their device cache
        n0 = int(index.n_docs)
        self._blocks: Deque[np.ndarray] = deque()
        if n0 > 0:
            self._blocks.append(np.arange(n0, dtype=np.int64))
        self._ring_tail = n0
        self._window: Optional[int] = None
        # blocks allocated before a set_window capacity growth may sit
        # anywhere in the padded ring ("stranded"); only the oldest
        # _stranded blocks can ever overlap a fresh target range, so the
        # ingest-path overlap sweep is O(0) in steady state
        self._stranded = 0
        self._scopes: Dict[str, np.ndarray] = {}
        self._scope_dev: Dict[str, Tuple[int, jax.Array]] = {}
        self._full_mask: Optional[jax.Array] = None
        self.evicted_docs_total = 0    # monitoring: docs retired by the ring
        if window is not None:
            if n0 > int(window):
                # same contract as the ingest path: a block that could
                # never be live in full is an error, not a silent wipe
                # (set_window's whole-block eviction would retire the
                # entire initial corpus)
                raise ValueError(
                    f"initial corpus of {n0} docs exceeds window={window}; "
                    "it could never be live in full — raise the window or "
                    "pre-trim the corpus")
            self.set_window(window)

    @classmethod
    def from_docs(cls, doc_terms: Sequence[Sequence[int]], vocab_size: int, *,
                  capacity: Optional[int] = None, dtype=jnp.bfloat16,
                  window: Optional[int] = None, mesh=None,
                  cold_store=None) -> "QueryContext":
        if mesh is None:
            index = pack_docs(doc_terms, vocab_size, capacity=capacity)
        else:   # from the host straight to its shards (placed in __init__)
            index = PackedIndex(*_pack_host(doc_terms, vocab_size, capacity))
        return cls(index, dtype=dtype, window=window, mesh=mesh,
                   cold_store=cold_store)

    @property
    def index(self) -> PackedIndex:
        return self._index

    @property
    def mesh(self):
        """The context's query mesh (None = single-device execution).
        When set, queries and materialization against this context run
        sharded across the mesh's devices (``core.distributed``) and the
        cached artifacts are CONSTRUCTED already placed on it."""
        return self._mesh

    def _place(self, x: jax.Array, axes) -> jax.Array:
        """Shard an artifact at build time: under a mesh, device_put with
        the logical-axis rules bound to this mesh (indivisible dims
        degrade to replication — the shard_map'd execution paths re-pad
        and re-shard as needed); without one, the legacy constrain (a
        no-op outside an active axis_rules context)."""
        from repro.launch.sharding import axis_rules, constrain, named_sharding
        if self._mesh is None:
            return constrain(x, axes)
        with axis_rules(self._mesh):
            return jax.device_put(x, named_sharding(axes, x.shape))

    @property
    def vocab_size(self) -> int:
        return self._index.vocab_size

    @property
    def n_docs(self) -> int:
        return int(self._index.n_docs)

    # -- streaming window ---------------------------------------------------

    @property
    def window(self) -> Optional[int]:
        return self._window

    @property
    def live_docs(self) -> int:
        """Documents currently answering queries (ingested minus evicted)."""
        return sum(len(b) for b in self._blocks)

    @property
    def n_blocks(self) -> int:
        return len(self._blocks)

    def live_slots(self) -> np.ndarray:
        """Slot ids of all live documents, oldest block first."""
        if not self._blocks:
            return np.zeros((0,), np.int64)
        return np.concatenate(list(self._blocks))

    def set_window(self, window: int) -> None:
        """Enter (or resize) sliding-window mode: at most ``window`` live
        docs, capacity pinned at ``ceil(window/32)*32`` slots.  Shrinking
        below the current live count evicts oldest blocks to fit."""
        window = int(window)
        if window < 1:
            raise ValueError(f"window must be >= 1, got {window}")
        need_words = (window + 31) // 32
        if need_words > self._index.n_words:
            packed = jnp.pad(self._index.packed,
                             ((0, need_words - self._index.n_words), (0, 0)))
            self._index = PackedIndex(packed, self._index.doc_freq,
                                      self._index.n_docs)
            self.epoch += 1          # X's doc axis grew: rebuild once
            if self._blocks:
                self._stranded = len(self._blocks)
        self._window = window
        if self._evict_for(0):
            self.epoch += 1          # retired docs: caches must rebuild

    def _evict_for(self, n_new: int) -> int:
        """Evict oldest blocks until ``live + n_new <= window``; one device
        retire pass for all of them.  Returns #docs evicted."""
        assert self._window is not None
        evicted: list = []
        while self._blocks and self.live_docs + n_new > self._window:
            evicted.append(self._blocks.popleft())
            self._stranded = max(0, self._stranded - 1)
        if not evicted:
            return 0
        slots = np.concatenate(evicted)
        self._retire_slots(slots)
        return len(slots)

    def _retire_slots(self, slots: np.ndarray) -> None:
        """One device retire pass + host scope cleanup for ``slots``.
        With a cold store attached, the block's postings are spilled
        (re-packed into a self-contained payload) BEFORE the bits are
        cleared — eviction demotes the block to the cold tier instead of
        destroying it."""
        if self._cold is not None and len(slots):
            self._spill_block(np.asarray(slots, np.int64))
        mask = slots_bitmap(slots, self._index.n_words)
        self._index = retire_docs(self._index, jnp.asarray(mask))
        for name in self._scopes:
            self._scopes[name] = self._scope_host(name) & ~mask
            self._scope_dev.pop(name, None)
        self.evicted_docs_total += len(slots)

    def retire_oldest_block(self) -> int:
        """Manually evict the oldest ingest block (postings cleared,
        doc_freq decremented, scopes updated).  Returns #docs retired;
        bumps the epoch iff anything was retired."""
        if not self._blocks:
            return 0
        slots = self._blocks.popleft()
        self._stranded = max(0, self._stranded - 1)
        self._retire_slots(slots)
        self.epoch += 1
        return len(slots)

    # -- cold tier ----------------------------------------------------------

    @property
    def cold_store(self):
        """The attached cold-tier store (a MutableMapping[str, bytes]),
        or None — without one, evicted blocks are simply destroyed."""
        return self._cold

    def cold_version(self) -> int:
        """Monotonic spill counter: bumps once per spilled block, so
        artifacts derived from the cold tier (the all-time network) can
        version on it the way scoped artifacts version on
        :meth:`scope_version`."""
        return self._cold_seq

    def cold_blocks(self) -> int:
        return len(self._cold) if self._cold is not None else 0

    def _spill_block(self, slots: np.ndarray) -> None:
        """Extract ``slots``' postings from the live bitmap and write them
        to the cold store as a self-contained :class:`~repro.core.storage.
        ColdBlock` — its own word rows (one per 32 docs) + per-term df.
        Only the touched word rows transfer off device, not the whole
        (W, V) bitmap."""
        from repro.core.storage import ColdBlock, encode_block
        v = self._index.vocab_size
        uw = np.unique(slots // 32)
        rows = np.asarray(jax.device_get(
            jnp.take(self._index.packed, jnp.asarray(uw, jnp.int32), axis=0)))
        pos = np.searchsorted(uw, slots // 32)
        bits = ((rows[pos] >> (slots % 32).astype(np.uint32)[:, None])
                & np.uint32(1))                                    # (n, V)
        df = bits.sum(axis=0).astype(np.int32)
        n = len(slots)
        nw = (n + 31) // 32
        b = np.zeros((nw * 32, v), np.uint32)
        b[:n] = bits
        packed = np.bitwise_or.reduce(
            b.reshape(nw, 32, v)
            << np.arange(32, dtype=np.uint32)[None, :, None], axis=1)
        key = f"block-{self._cold_seq:08d}"
        self._cold[key] = encode_block(ColdBlock(packed, df, n, v))
        self._cold_seq += 1

    def all_time_index(self) -> PackedIndex:
        """Live + cold tiers as ONE bare :class:`PackedIndex`: the cold
        blocks' word rows stacked under the live bitmap (co-occurrence
        counts are additive over disjoint doc sets, so any count method
        over the combined bitmap answers over every doc ever ingested).
        Returns the live index itself when nothing has spilled."""
        if self._cold is None or len(self._cold) == 0:
            return self._index
        from repro.core.storage import decode_block
        v = self._index.vocab_size
        parts = [self._index.packed]
        df = self._index.doc_freq
        for key in sorted(self._cold):
            blk = decode_block(self._cold[key])
            cw, cdf = blk.packed, blk.doc_freq
            if blk.vocab > v:
                # only an all-zero overhang is droppable (shrink_vocab's
                # contract on the live index, mirrored here)
                if cdf[v:].any():
                    raise ValueError(
                        f"cold block {key} holds postings for terms >= the "
                        f"live vocab {v}; cannot query it under this index")
                cw, cdf = cw[:, :v], cdf[:v]
            elif blk.vocab < v:
                cw = np.pad(cw, ((0, 0), (0, v - blk.vocab)))
                cdf = np.pad(cdf, (0, v - blk.vocab))
            parts.append(jnp.asarray(cw))
            df = df + jnp.asarray(cdf)
        packed = jnp.concatenate(parts, axis=0)
        return PackedIndex(packed, df,
                           jnp.asarray(packed.shape[0] * 32, jnp.int32))

    # -- scopes -------------------------------------------------------------

    def _scope_host(self, name: str) -> np.ndarray:
        """Host bitmap for ``name``, padded to the current word count
        (capacity growth only appends all-zero words)."""
        m = self._scopes[name]
        w = self._index.n_words
        if len(m) < w:
            m = np.pad(m, (0, w - len(m)))
            self._scopes[name] = m
        return m

    def tag_scope(self, name: str, doc_slots) -> None:
        """OR ``doc_slots`` into the named scope bitmap (created empty on
        first use)."""
        if name not in self._scopes:
            self._scopes[name] = np.zeros((self._index.n_words,), np.uint32)
        self._scopes[name] = (self._scope_host(name)
                              | slots_bitmap(doc_slots, self._index.n_words))
        self._scope_dev.pop(name, None)
        self._scope_ver[name] = self._scope_ver.get(name, 0) + 1

    def define_scope(self, name: str, doc_slots) -> None:
        """Set/replace the named scope to exactly ``doc_slots``.  A no-op
        when the membership is unchanged, so callers that re-derive a scope
        per query (the facade's trailing time buckets) keep the device
        cache warm instead of re-uploading an identical bitmap."""
        new = slots_bitmap(doc_slots, self._index.n_words)
        old = self._scopes.get(name)
        if old is not None and len(old) == len(new) and (old == new).all():
            return
        self._scopes[name] = new
        self._scope_dev.pop(name, None)
        self._scope_ver[name] = self._scope_ver.get(name, 0) + 1

    def drop_scope(self, name: str) -> None:
        self._scopes.pop(name, None)
        self._scope_dev.pop(name, None)
        if name in self._scope_ver:
            self._scope_ver[name] += 1

    def scope_version(self, name: str) -> int:
        """Monotonic redefinition counter for ``name`` (0 if never touched).
        Epoch bumps do NOT advance it: (epoch, scope_version) together
        version any artifact derived from a scope's membership."""
        return self._scope_ver.get(name, 0)

    def scope_names(self) -> Tuple[str, ...]:
        return tuple(sorted(self._scopes))

    def full_mask(self) -> jax.Array:
        """All-ones ``(W,)`` doc bitmap — the canonical "unscoped" scope
        operand.  ``masks & full == masks`` bit-exactly (slots past the
        live docs hold no postings bits), so the engine can feed EVERY
        batch a scope bitmap and serve scoped and unscoped plans of equal
        shape through one executable (:func:`repro.core.query.canonical_exec_key`).
        Cached per word count (only capacity growth changes W)."""
        w = self._index.n_words
        if self._full_mask is None or self._full_mask.shape[0] != w:
            self._full_mask = jnp.full((w,), 0xFFFFFFFF, jnp.uint32)
        return self._full_mask

    def scope(self, name: str) -> jax.Array:
        """Device bitmap of the named scope — the ``scope_mask`` operand of
        ``bfs_construct``.  Cached per epoch (ingest/evict/grow all bump the
        epoch; ``tag_scope``/``define_scope`` invalidate explicitly), so a
        warm scoped plan uploads nothing per query."""
        if name not in self._scopes:
            raise KeyError(f"unknown scope {name!r}; "
                           f"defined scopes: {list(self.scope_names())}")
        ent = self._scope_dev.get(name)
        if ent is None or ent[0] != self.epoch:
            with self._rebuild("scope"):
                arr = jnp.asarray(self._scope_host(name))
            self._scope_dev[name] = (self.epoch, arr)
            ent = self._scope_dev[name]
        return ent[1]

    # -- cached artifacts ---------------------------------------------------

    def _rebuild(self, artifact: str):
        """The ``cooc.index.rebuild`` span of one per-epoch artifact
        (re)built on a miss, counted under
        ``artifact_rebuilds_total{artifact=...}``."""
        self.spans.count("artifact_rebuilds_total", artifact=artifact)
        return self.spans.span("cooc.index.rebuild")

    def x_dense(self) -> jax.Array:
        """Dense incidence X (capacity, V), unpacked once per epoch and
        sharded (docs, terms) at build time."""
        if self._x_epoch != self.epoch:
            self._x_dense = None
            with self._rebuild("x_dense"):
                self._x_dense = self._place(
                    incidence_dense(self._index, self._dtype),
                    ("docs", "terms"))
            self._x_epoch = self.epoch
            self.unpack_count += 1
        return self._x_dense

    def packed_t(self) -> jax.Array:
        """Transposed postings (V, W) uint32, cached per epoch and sharded
        (terms, docs) at build time — the row-block mask gather of
        full-network materialization reads term rows contiguously instead
        of striding over ``packed``'s columns."""
        if self._pt_epoch != self.epoch:
            self._packed_t = None
            with self._rebuild("packed_t"):
                self._packed_t = self._place(
                    jnp.transpose(self._index.packed), ("terms", "docs"))
            self._pt_epoch = self.epoch
        return self._packed_t

    def packed_t_pad(self) -> jax.Array:
        """Transposed postings pre-padded to the fused level-step kernel's
        tile layout — (V_pad, W_pad) uint32 with V rounded up to 8 and W
        to 128 (the int32 TPU tile) — cached per epoch and sharded
        (terms, docs) at build time.

        This is the padding-at-ingest invariant: the pad happens ONCE per
        ingest epoch, here, so steady-state ``method="fused"`` queries
        launch with zero ``jnp.pad`` of the postings
        (``kernels.ops.level_step`` refuses to pad its big operand).
        Padding columns/words are all-zero bits: they contribute nothing
        to counts and the kernel forces their columns below every real
        candidate.
        """
        if self._ptp_epoch != self.epoch:
            self._packed_t_pad = None         # never hold stale + new
            with self._rebuild("packed_t_pad"):
                self._packed_t_pad = self._place(
                    transpose_pad(self._index.packed), ("terms", "docs"))
            self._ptp_epoch = self.epoch
        return self._packed_t_pad

    def _drop_artifacts(self) -> None:
        """Release the derived per-epoch artifacts before the index they
        derive from is replaced: at CSL size each is as large as the
        index, and a stale copy held across the rebuild would double
        it."""
        self._x_dense = self._packed_t = self._packed_t_pad = None
        self._x_epoch = self._pt_epoch = self._ptp_epoch = -1

    def term_signatures(self, *, num_perm: int = 128, seed: int = 0
                        ) -> jax.Array:
        """Per-term MinHash signatures (V, num_perm) uint32 over the LIVE
        postings (:mod:`repro.core.sketch`) — the approximate
        materialization's pruning artifact, epoch-versioned through the
        artifact cache like every other derived artifact.

        Single-device the rebuild is INCREMENTAL: each live ingest
        block's signature is hashed exactly once (keyed on block
        identity — a live block's postings bits never change) and the
        served signature is a min-reduce over the live blocks, so an
        ingest hashes only the new block, an eviction just drops the
        evicted block's part, and min's associativity + commutativity
        makes the merge independent of ingest order.  Vocab growth pads
        old block signatures with ``SIG_EMPTY`` (old blocks hold no
        postings for new terms); vocab shrink slices (the dropped
        columns were postings-free by :meth:`shrink_vocab`'s contract).
        Under a mesh the signatures are computed sharded alongside the
        postings (:func:`repro.core.distributed.sharded_signatures`).
        """
        from repro.core import sketch
        cfg = (int(num_perm), int(seed))
        key = ("minhash",) + cfg
        # epoch-checked inside cached_artifact; version 0 — the key pins
        # the config, ingest/evict/grow move the epoch
        hit = self.cached_artifact(key, version=0)
        if hit is not None:
            return hit
        v = self.vocab_size
        a, b = sketch.hash_coefficients(num_perm, seed)
        if self._mesh is not None:
            from repro.core.distributed import sharded_signatures
            sig = sharded_signatures(self._index.packed, jnp.asarray(a),
                                     jnp.asarray(b), self._mesh)
        else:
            prev = {id(e[0]): e for e in self._sketch_blocks.get(cfg, [])}
            ents = []
            for blk in self._blocks:
                ent = prev.get(id(blk))
                if ent is None or ent[0] is not blk:
                    ent = (blk, sketch.block_signatures(
                        self._index.packed, blk, a, b))
                elif ent[1].shape[0] != v:
                    sig_b = ent[1]
                    if sig_b.shape[0] > v:
                        sig_b = sig_b[:v]
                    else:
                        sig_b = jnp.concatenate([
                            sig_b,
                            jnp.full((v - sig_b.shape[0], sig_b.shape[1]),
                                     sketch.SIG_EMPTY, jnp.uint32)])
                    ent = (blk, sig_b)
                ents.append(ent)
            self._sketch_blocks[cfg] = ents
            sig = sketch.merge_signatures([e[1] for e in ents], v,
                                          int(num_perm))
        self.store_artifact(key, sig)
        return sig

    def cached_artifact(self, key: Tuple, version: int = 0):
        """Epoch-checked lookup in the generic artifact cache (None on
        miss, stale epoch, or stale ``version``).  Used by
        :func:`repro.core.materialize` to reuse a warm full-network result
        until ingest/evict/grow moves the epoch or a scope redefinition
        moves the version — the version lives IN the entry, not the key,
        so a superseded artifact is overwritten, never leaked."""
        ent = self._artifact_cache.get(key)
        if ent is not None and ent[0] == self.epoch and ent[1] == version:
            return ent[2]
        return None

    def store_artifact(self, key: Tuple, value, version: int = 0) -> None:
        """Store ``value`` under ``key`` at the current epoch, pruning
        every stale-epoch entry so the cache holds only live artifacts
        (one value per key — same-epoch re-stores overwrite)."""
        if any(e[0] != self.epoch for e in self._artifact_cache.values()):
            self._artifact_cache = {k: e for k, e in
                                    self._artifact_cache.items()
                                    if e[0] == self.epoch}
        self._artifact_cache[key] = (self.epoch, version, value)

    def operands(self, method: str) -> dict:
        """Extra (traced-array) operands ``bfs_construct`` needs for
        ``method`` — the registry's ``needs`` realised against this
        context's caches (raises ValueError on an unregistered method)."""
        return {name: getattr(self, name)()
                for name in get_count_method(method).needs}

    # -- ingest path --------------------------------------------------------

    def ingest(self, new_doc_terms: jax.Array, new_doc_valid: jax.Array, *,
               on_overflow: str = "raise",
               scope: Union[str, Sequence[str], None] = None) -> np.ndarray:
        """Ingest a block of documents; returns the slot ids assigned to
        the block's valid rows (in row order).  Timed as one
        ``cooc.index.ingest`` span.

        Append mode (no window): host-side capacity check BEFORE the jitted
        scatter (the device scatter clamps out-of-range writes with
        ``mode="drop"``, which silently loses docs — never acceptable in
        the serving path).  on_overflow: "raise" -> CapacityError; "grow"
        -> double capacity via :func:`grow_capacity` repack until the block
        fits.

        Window mode: the oldest blocks are evicted until the new block fits
        under ``window``, then the block is scattered into ring slots —
        capacity NEVER grows.  A block larger than the window is rejected
        (it could never be live in full).

        ``scope`` tags the new block into the named scope bitmap(s).
        """
        with self._ingest_span():
            return self._ingest(new_doc_terms, new_doc_valid,
                                on_overflow=on_overflow, scope=scope)

    @contextlib.contextmanager
    def _ingest_span(self):
        """``cooc.index.ingest`` around the outermost of
        :meth:`ingest_docs` / :meth:`ingest`: one span per ingest."""
        if self._ingesting:
            yield
            return
        self._ingesting = True
        try:
            with self.spans.span("cooc.index.ingest"):
                yield
        finally:
            self._ingesting = False

    def _ingest(self, new_doc_terms: jax.Array, new_doc_valid: jax.Array, *,
                on_overflow: str,
                scope: Union[str, Sequence[str], None]) -> np.ndarray:
        valid_np = np.asarray(new_doc_valid).astype(bool)
        n_new = int(valid_np.sum())
        n_rows = valid_np.shape[0]
        if self._window is not None:
            if n_new > self._window:
                raise ValueError(
                    f"ingest block of {n_new} docs exceeds window="
                    f"{self._window}; it could never be live in full — "
                    "split the block or raise the window")
            self._evict_for(n_new)
            cap = self._index.capacity
            slots = (self._ring_tail + np.arange(n_new, dtype=np.int64)) % cap
            # ingest_at's OR-scatter needs all-zero target slots.  The
            # window-count eviction above guarantees that while the live
            # region is circular-contiguous, but a set_window(...) growth
            # repack can leave wrapped live blocks stranded anywhere in the
            # ring — evict (oldest-first) until none overlaps the target
            # range.  Only the oldest _stranded blocks can overlap (post-
            # growth blocks are allocated consecutively from the tail), so
            # steady-state ingest skips the sweep entirely.
            stranded = []
            while self._stranded and any(
                    np.isin(b, slots).any()
                    for b in list(self._blocks)[:self._stranded]):
                stranded.append(self._blocks.popleft())
                self._stranded -= 1
            if stranded:
                self._retire_slots(np.concatenate(stranded))
            self._ring_tail = int((self._ring_tail + n_new) % cap)
        else:
            needed = self.n_docs + n_new
            if needed > self._index.capacity:
                if on_overflow == "grow":
                    self._index = grow_capacity(self._index, needed)
                else:
                    raise CapacityError(
                        f"ingest of {n_new} docs would exceed capacity "
                        f"{self._index.capacity} (n_docs={self.n_docs}); "
                        f"pass on_overflow='grow' to repack")
            start = self.n_docs
            slots = np.arange(start, start + n_new, dtype=np.int64)
            self._ring_tail = start + n_new
        row_slots = np.zeros((n_rows,), np.int64)
        row_slots[np.flatnonzero(valid_np)] = slots
        self._drop_artifacts()
        self._index = ingest_at(self._index, new_doc_terms, new_doc_valid,
                                jnp.asarray(row_slots, jnp.int32))
        if n_new > 0:
            self._blocks.append(slots)
            if scope is not None:
                names = (scope,) if isinstance(scope, str) else tuple(scope)
                for name in names:
                    self.tag_scope(name, slots)
        self.epoch += 1
        return slots

    def grow_vocab(self, min_vocab: int) -> None:
        """Widen the term axis to at least ``min_vocab`` (doubling, so
        repeated growth is amortised O(1) per term).  Existing postings and
        doc ids are unchanged; the epoch bumps so cached artifacts (the
        dense X, whose V axis grew) rebuild once."""
        new = grow_vocab(self._index, min_vocab)
        if new is not self._index:
            self._index = new
            self.epoch += 1

    def shrink_vocab(self, vocab_size: int) -> None:
        """Roll back a :meth:`grow_vocab` whose batch never indexed: drop
        trailing term columns down to ``vocab_size``.  Refuses when any
        dropped column holds postings (its term exists — shrinking would
        corrupt the index); the rollback path only ever drops the all-zero
        columns a failed ingest's growth appended."""
        v = int(vocab_size)
        if v >= self._index.vocab_size:
            return
        if v < 1:
            raise ValueError(f"vocab_size must be >= 1, got {v}")
        tail_df = np.asarray(self._index.doc_freq[v:])
        if tail_df.any():
            raise ValueError(
                f"cannot shrink vocab to {v}: "
                f"{int((tail_df > 0).sum())} dropped column(s) hold postings")
        self._index = PackedIndex(self._index.packed[:, :v],
                                  self._index.doc_freq[:v],
                                  self._index.n_docs)
        self.epoch += 1

    def ingest_docs(self, doc_terms: Sequence[Sequence[int]], *,
                    max_len: int = 64, on_overflow: str = "raise",
                    on_long: str = "raise", window: Optional[int] = None,
                    scope: Union[str, Sequence[str], None] = None
                    ) -> np.ndarray:
        """Host convenience: pad token lists to (N, max_len) and ingest.
        Returns the slot ids assigned to the new docs.

        on_long: "raise" -> ValueError when any document holds more than
        ``max_len`` term ids (truncation would silently drop postings —
        the repo's raise-don't-drop policy); "truncate" -> explicit opt-in
        to keep only the first ``max_len`` ids per document.

        window: enters (or resizes) sliding-window mode before this ingest
        — equivalent to :meth:`set_window` then :meth:`ingest`.
        scope: tag the new docs into the named scope bitmap(s).

        The whole call, padding included, is one ``cooc.index.ingest``
        span.
        """
        with self._ingest_span():
            if window is not None:
                self.set_window(window)
            doc_terms = [list(t) for t in doc_terms]
            over = [(i, len(t)) for i, t in enumerate(doc_terms)
                    if len(t) > max_len]
            if over and on_long != "truncate":
                i0, l0 = over[0]
                raise ValueError(
                    f"{len(over)} document(s) exceed max_len={max_len} "
                    f"(first: doc {i0} with {l0} terms); term ids past "
                    f"max_len would be silently dropped — raise max_len or "
                    f"pass on_long='truncate'")
            n = len(doc_terms)
            ids = np.full((n, max_len), -1, np.int32)
            for i, t in enumerate(doc_terms):
                t = t[:max_len]
                ids[i, :len(t)] = t
            return self.ingest(jnp.asarray(ids),
                               jnp.asarray(np.ones((n,), bool)),
                               on_overflow=on_overflow, scope=scope)
