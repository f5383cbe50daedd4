"""Pallas TPU kernel: bit-packed postings intersection + popcount.

The inverted-index hot path (DESIGN.md §2): given B filter bitmaps
(frontier filters) and the packed postings matrix, produce per-term
document frequencies

    counts[b, v] = sum_w popcount(masks[b, w] & packed[w, v])

This is the memory-bound streaming op of the optimized algorithm — one
pass over ``packed`` per BFS level.  int32 accumulation, exact for any D.

Tiling: grid (B/bb, V/bv, ceil(W/bw)); W innermost, accumulating into
the resident (bb, bv) int32 output block.  ``packed`` is never padded: the
last W block may run past the index, and the masks (padded with zero
words, O(B·W)) AND whatever it reads there to zero.  VPU op (AND + popcount + reduce) —
no MXU involvement, so the roofline term is pure HBM bandwidth.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _postings_kernel(masks_ref, packed_ref, out_ref):
    w = pl.program_id(2)

    @pl.when(w == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    m = masks_ref[...]   # (bb, bw) uint32
    p = packed_ref[...]  # (bw, bv) uint32
    anded = m[:, :, None] & p[None, :, :]          # (bb, bw, bv)
    pc = jax.lax.population_count(anded).astype(jnp.int32)
    out_ref[...] += jnp.sum(pc, axis=1)


def postings_counts_pallas(masks: jax.Array, packed: jax.Array, *, bb: int = 8,
                           bv: int = 512, bw: int = 256,
                           interpret: bool = False) -> jax.Array:
    """counts (B, V) int32 from masks (B, W_pad) and packed (W, V), both
    uint32, with W_pad = ceil(W / bw) * bw and mask words >= W all zero.

    Requires B % bb == V % bv == 0 (ops.py pads).  VMEM per step:
    bb*bw*4 + bw*bv*4 + bb*bw*bv*4 (the AND intermediate) — with
    (8, 512, 256) the intermediate is 4 MB; fits VMEM with headroom.
    """
    b, wp = masks.shape
    w, v = packed.shape
    assert wp == -(-w // bw) * bw, (wp, w, bw)
    assert b % bb == 0 and v % bv == 0, (b, v, bb, bv)
    grid = (b // bb, v // bv, wp // bw)
    return pl.pallas_call(
        _postings_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bb, bw), lambda i, j, k: (i, k)),
            pl.BlockSpec((bw, bv), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bb, bv), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((b, v), jnp.int32),
        interpret=interpret,
    )(masks, packed)
