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

Grid (nv, nw), W innermost.  With W on the lanes, summing a word block
to one count per (b, v) is a reduction across the 128 lanes and then a
move of the result from sublanes onto lanes; done on every W step, that
was most of the kernel's time.  So the accumulator stays lane-wide: a
VMEM (B, bv, 128) int32 scratch block, where lane l holds the popcount
of the words w == l (mod 128) seen so far.  A W step adds the popcount
of each 128-word chunk of its (bv, bw) tile elementwise, with no lane
reduction and no relayout: block by block of (8 rows, 32 sublanes) of
the accumulator, 32 vregs that sum the bw / 128 chunks in registers and
use each loaded packed_t vreg for 8 rows.  The LAST W step reduces
across the lanes once, to (B, bv), applies the masks and folds the tile
into the running (B, k) top-k held in the revisited output refs — the
(B, V) count matrix never exists in HBM.  A lane partial is at most
32 × W / 128 (≈ 3,100 at CSL width): exact int32.

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

LANES = 128         # a vreg's lanes: the accumulator's width
ROWS, SUB = 8, 32   # the W loop's register block: rows of B x sublanes of V


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

    # lane-wide partials: acc[r, v, l] sums popcount(masks[r, w] &
    # packed_t[v, w]) over the words w == l (mod 128) seen so far, so the
    # W loop is elementwise work only (no lane reduction, no relayout).
    # A (ROWS, SUB, 128) block of it is 32 vregs: it takes the bw / 128
    # chunks in registers, and each packed_t vreg loaded serves ROWS rows.
    nb, bw = masks_ref.shape
    for v0 in range(0, bv, SUB):
        vs = slice(v0, min(v0 + SUB, bv))
        for r0 in range(0, nb, ROWS):
            rs = slice(r0, r0 + ROWS)
            part = acc_ref[rs, vs, :]
            for c in range(0, bw, LANES):
                m = masks_ref[rs, c:c + LANES]               # (ROWS, 128)
                p = pt_ref[vs, c:c + LANES]                  # (SUB, 128)
                part += jax.lax.population_count(
                    m[:, None, :] & p[None, :, :]).astype(jnp.int32)
            acc_ref[rs, vs, :] = part

    @pl.when(iw == nw - 1)
    def _mask_and_merge():
        # the V tile's one reduction across lanes: (B, bv, 128) -> (B, bv)
        counts = jnp.sum(acc_ref[...], axis=2)
        cols = iv * bv + jax.lax.broadcasted_iota(jnp.int32, (1, bv), 1)
        c = _masked_counts(counts, cols, terms_ref[...], valid_ref[...],
                           vis_ref[...], v)
        cand_w = jnp.concatenate([w_out_ref[...], c], axis=1)
        cand_i = jnp.concatenate(
            [i_out_ref[...], jnp.broadcast_to(cols, c.shape)], axis=1)
        w2, i2 = _topk_rounds(cand_w, cand_i, k)
        w_out_ref[...] = w2
        i_out_ref[...] = i2


def level_step_pallas(masks: jax.Array, packed_t_pad: jax.Array,
                      terms: jax.Array, valid: jax.Array, visited: jax.Array,
                      *, v: int, k: int, bv: int, bw: int,
                      interpret: bool = False):
    """Fused level step.  masks (B, nw * bw) uint32, zero past packed_t's
    words; packed_t_pad (V_pad, W_pad) uint32; terms (B, 1) int32
    (clipped to [0, V)); valid (B, 1) int32; visited (1, V_pad) int32.
    Returns (weights, ids) both (B, k) int32, exact ``lax.top_k`` of the
    masked counts.  Requires B % 8 == 0, V_pad % bv == 0, bw % 128 == 0,
    k <= v (callers clamp k and pad the missing slots back).  The last W
    block may run past W_pad: the zero mask words AND whatever it reads
    there to zero.

    VMEM per step: the (B, bv, 128) int32 lane-wide accumulator, B * bv
    * 512 bytes, plus the double-buffered (bv, bw) packed_t tile and
    (B, bw) masks.  At the engine's CSL tiles (B 32, bv 256, bw 1,024):
    4 MiB + 2 x 1 MiB + 2 x 128 KiB, inside the default scoped VMEM, so
    no ``vmem_limit_bytes``.  The (B, k) outputs are revisited across the
    whole grid (the running merge state), written last on each V tile.
    """
    b, wm = masks.shape
    vp, wp = packed_t_pad.shape
    assert bw % LANES == 0 and wm == -(-wp // bw) * bw, (wm, wp, bw)
    assert vp % bv == 0, (vp, bv)
    assert 0 < k <= v <= vp, (k, v, vp)
    nv, nw = vp // bv, wm // bw
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
        scratch_shapes=[pltpu.VMEM((b, bv, LANES), jnp.int32)],
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
