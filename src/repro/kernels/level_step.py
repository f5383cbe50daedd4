"""Fused BFS level-step kernel: popcount counts + masking + top-k, one launch.

One BFS level of ``cooccurrence._expand_level`` used to be a CHAIN of
device ops — the postings popcount (its own Pallas launch, with per-call
operand padding), a scatter for the self-pair mask, two ``where``s for the
visited/valid masks, then ``chunked_top_k`` (two more ``lax.top_k``
passes).  Every stage round-trips the (B, V) count block through HBM.

This kernel fuses the whole level step over the TRANSPOSED padded postings
``packed_t_pad (V_pad, W_pad)`` (a ``QueryContext`` epoch artifact — padded
once at ingest time, never per query):

    counts[b, v] = sum_w popcount(masks[b, w] & packed_t[v, w])
    counts masked: self-pair (col == term), visited cols, invalid rows,
                   padding cols (forced to -2, strictly below real -1s)
    (w, i)[b]    = top-k of the masked row, exact lax.top_k tie order

Grid (nv, nw), W innermost: each W step accumulates the AND+popcount
partial into a VMEM (B, bv) scratch block; the LAST W step applies the
masks and folds the tile into the running (B, k) top-k held in the
revisited output refs — the (B, V) count matrix never exists in HBM.

Tie order is exact ``lax.top_k`` order (lower index wins) by the running-
merge argument of ``materialize._topk_row_block``: running candidates come
from strictly earlier column tiles (lower global ids) and are already
sorted lower-id-first within equal weights, the new tile's columns are laid
out in id order after them, and the per-round first-maximum extraction picks
the FIRST maximum slot.

``level_step_topk_xla`` is the bit-exact XLA form, the default on the CPU
(interpret-mode Pallas is a correctness path there, not a serving path).
On a TPU the compiled kernel always runs (``kernels.ops``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _masked_counts(counts: jax.Array, cols: jax.Array, terms: jax.Array,
                   valid: jax.Array, visited: jax.Array, v: int) -> jax.Array:
    """Apply the level-step masks to a (B, ncols) count block.

    ``cols`` are the block's global column ids; ``terms`` is already
    clipped to [0, V).  Padding columns (>= v) go to -2: strictly below
    every real masked count (-1), so they can never displace a real
    candidate on a tie, and never surface while k <= V real columns exist.
    """
    counts = jnp.where(cols == terms, -1, counts)            # self-pairs
    counts = jnp.where(visited > 0, -1, counts)              # dedup
    counts = jnp.where(valid > 0, counts, -1)                # invalid rows
    return jnp.where(cols >= v, jnp.int32(-2), counts)       # padding cols


def _topk_rounds(cand_w: jax.Array, cand_i: jax.Array, k: int):
    """Exact top-k by k rounds of first-maximum extraction (no lax.top_k
    inside the kernel).  Each round takes the row max, then the lowest
    slot holding it (a min over slot ids: Mosaic lowers argmax for float32
    only) == the lowest candidate index under the merge layout —
    lax.top_k order."""
    n_cand = cand_w.shape[1]
    slot = jax.lax.broadcasted_iota(jnp.int32, (1, n_cand), 1)
    ws, ids = [], []
    for _ in range(k):
        w_max = jnp.max(cand_w, axis=1, keepdims=True)
        sel = jnp.min(jnp.where(cand_w == w_max, slot, n_cand), axis=1,
                      keepdims=True)                         # first max
        hit = slot == sel
        ws.append(w_max[:, 0])
        ids.append(jnp.sum(jnp.where(hit, cand_i, 0), axis=1))
        cand_w = jnp.where(hit, jnp.int32(-3), cand_w)       # pop the slot
    return jnp.stack(ws, axis=1), jnp.stack(ids, axis=1)


def _level_step_kernel(masks_ref, pt_ref, terms_ref, valid_ref, vis_ref,
                       w_out_ref, i_out_ref, acc_ref, *, v: int, k: int,
                       bv: int, nw: int):
    iv, iw = pl.program_id(0), pl.program_id(1)

    @pl.when(iw == 0)
    def _init_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when((iv == 0) & (iw == 0))
    def _init_out():
        # -2 loses to every real candidate (>= -1); all init slots are
        # displaced before the final output (V >= k real columns exist)
        w_out_ref[...] = jnp.full_like(w_out_ref, -2)
        i_out_ref[...] = jnp.zeros_like(i_out_ref)

    m = masks_ref[...]                                       # (bb, bw) uint32
    p = pt_ref[...]                                          # (bv, bw) uint32
    anded = m[:, None, :] & p[None, :, :]                    # (bb, bv, bw)
    acc_ref[...] += jnp.sum(
        jax.lax.population_count(anded).astype(jnp.int32), axis=2)

    @pl.when(iw == nw - 1)
    def _mask_and_merge():
        cols = iv * bv + jax.lax.broadcasted_iota(jnp.int32, (1, bv), 1)
        c = _masked_counts(acc_ref[...], cols, terms_ref[...],
                           valid_ref[...], vis_ref[...], v)
        cand_w = jnp.concatenate([w_out_ref[...], c], axis=1)
        cand_i = jnp.concatenate(
            [i_out_ref[...], jnp.broadcast_to(cols, c.shape)], axis=1)
        w2, i2 = _topk_rounds(cand_w, cand_i, k)
        w_out_ref[...] = w2
        i_out_ref[...] = i2


def level_step_pallas(masks: jax.Array, packed_t_pad: jax.Array,
                      terms: jax.Array, valid: jax.Array, visited: jax.Array,
                      *, v: int, k: int, bv: int = 256, bw: int = 128,
                      interpret: bool = False):
    """Fused level step.  masks (B, W_pad) uint32; packed_t_pad
    (V_pad, W_pad) uint32; terms (B, 1) int32 (clipped to [0, V));
    valid (B, 1) int32; visited (1, V_pad) int32.  Returns
    (weights, ids) both (B, k) int32, exact ``lax.top_k`` of the masked
    counts.  Requires B % 8 == 0, V_pad % bv == 0, W_pad % bw == 0,
    k <= v (callers clamp k and pad the missing slots back).

    VMEM per step: the (B, bv, bw) AND intermediate dominates —
    (32, 256, 128) is 4 MB.  The (B, k) outputs are revisited across the
    whole grid (the running merge state), written last on each V tile.
    """
    b, wp = masks.shape
    vp = packed_t_pad.shape[0]
    assert packed_t_pad.shape[1] == wp, (packed_t_pad.shape, wp)
    assert vp % bv == 0 and wp % bw == 0, (vp, wp, bv, bw)
    assert 0 < k <= v <= vp, (k, v, vp)
    nv, nw = vp // bv, wp // bw
    kern = functools.partial(_level_step_kernel, v=v, k=k, bv=bv, nw=nw)
    return pl.pallas_call(
        kern,
        grid=(nv, nw),
        in_specs=[
            pl.BlockSpec((b, bw), lambda iv, iw: (0, iw)),       # masks
            pl.BlockSpec((bv, bw), lambda iv, iw: (iv, iw)),     # packed_t
            pl.BlockSpec((b, 1), lambda iv, iw: (0, 0)),         # terms
            pl.BlockSpec((b, 1), lambda iv, iw: (0, 0)),         # valid
            pl.BlockSpec((1, bv), lambda iv, iw: (0, iv)),       # visited
        ],
        out_specs=[
            pl.BlockSpec((b, k), lambda iv, iw: (0, 0)),
            pl.BlockSpec((b, k), lambda iv, iw: (0, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((b, k), jnp.int32),
                   jax.ShapeDtypeStruct((b, k), jnp.int32)],
        scratch_shapes=[pltpu.VMEM((b, bv), jnp.int32)],
        interpret=interpret,
    )(masks, packed_t_pad, terms, valid, visited)


def level_step_topk_xla(masks: jax.Array, packed_t_pad: jax.Array,
                        terms: jax.Array, valid: jax.Array,
                        visited: jax.Array, *, v: int, k: int):
    """Bit-exact XLA form for the CPU (same operands as the Pallas kernel,
    minus the tile-shape constraints): one popcount pass over the padded
    postings, the fused masks, one chunked top-k.  Padding columns sit at
    -2 so k <= v outputs are always real columns in lax.top_k order.

    The reduce routes through ``chunked_top_k`` — the very reduce the
    unfused oracle chain uses, so its output (values and tie order) IS
    the reference by construction, and its per-chunk partial sort beats
    one monolithic ``lax.top_k`` on wide count rows."""
    from repro.core.cooccurrence import chunked_top_k
    anded = masks[:, None, :] & packed_t_pad[None, :, :]     # (B, V_pad, W_pad)
    counts = jnp.sum(jax.lax.population_count(anded).astype(jnp.int32),
                     axis=2)
    vp = packed_t_pad.shape[0]
    cols = jax.lax.broadcasted_iota(jnp.int32, (1, vp), 1)
    counts = _masked_counts(counts, cols, terms, valid, visited, v)
    return chunked_top_k(counts, k)
