"""Public kernel API: jit'd wrappers with padding + backend selection.

``backend``:
  * "pallas"     — compiled Pallas (the TPU target)
  * "interpret"  — Pallas interpret mode (CPU correctness validation)
  * "xla"        — the pure-jnp oracle from ref.py (CPU only)
  * None         — pick: pallas on TPU, xla on the CPU; any other
                   platform raises (nothing stands in for the chip).

All wrappers pad to the kernels' tile multiples and slice the result back,
so callers never see shape constraints.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.cooccur import cooccur_gemm_pallas
from repro.kernels.dot_interaction import dot_interaction_pallas
from repro.kernels.flash_decode import flash_decode_pallas
from repro.kernels.level_step import level_step_pallas, level_step_topk_xla
from repro.kernels.postings import postings_counts_pallas


def _platform() -> str:
    """The platform the kernels are traced for (the default backend)."""
    return jax.default_backend()


def _pick(on_tpu: str, on_cpu: str) -> str:
    p = _platform()
    if p == "tpu":
        return on_tpu
    if p == "cpu":
        return on_cpu
    raise RuntimeError(
        f"no kernel backend for platform {p!r}: compiled Pallas needs a "
        "TPU, and the XLA reference and interpret mode run on the CPU only")


def _resolve(backend: Optional[str]) -> str:
    if backend is None:
        return _pick("pallas", "xla")
    if backend in ("xla", "interpret") and _platform() != "cpu":
        raise RuntimeError(
            f"backend {backend!r} runs on the CPU only; on {_platform()!r} "
            "the kernels run compiled (backend='pallas' or None)")
    return backend


def pallas_backend() -> str:
    """Backend string that always exercises the Pallas kernel: compiled on
    TPU, interpret mode on the CPU (correctness runs); any other platform
    is an error."""
    return _pick("pallas", "interpret")


def _pad_to(x: jax.Array, axis: int, mult: int, value=0) -> jax.Array:
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


# -- co-occurrence GEMM ------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("backend", "bm", "bn", "bk"))
def cooccur_gemm(x_l: jax.Array, x_r: jax.Array, *, backend: Optional[str] = None,
                 bm: int = 128, bn: int = 128, bk: int = 512) -> jax.Array:
    b = _resolve(backend)
    if b == "xla":
        return ref.cooccur_gemm_ref(x_l, x_r)
    vl, vr = x_l.shape[1], x_r.shape[1]
    xl = _pad_to(_pad_to(x_l, 1, bm), 0, bk)
    xr = _pad_to(_pad_to(x_r, 1, bn), 0, bk)
    out = cooccur_gemm_pallas(xl, xr, bm=bm, bn=bn, bk=bk,
                              interpret=(b == "interpret"))
    return out[:vl, :vr]


def _fit_tile(n: int, tile: int, mult: int) -> int:
    """Largest useful tile: ``tile``, shrunk to ``n`` rounded up to the
    layout multiple, so sub-tile operands don't pay full-tile padding."""
    return min(tile, ((n + mult - 1) // mult) * mult)


@functools.partial(jax.jit, static_argnames=("backend", "bm", "bn", "bk"))
def cooccur_counts(x_l: jax.Array, x_r: jax.Array, *,
                   backend: Optional[str] = None, bm: int = 128,
                   bn: int = 128, bk: int = 512) -> jax.Array:
    """Integer co-occurrence counts ``C = x_l^T @ x_r`` as int32.

    The materialization-path form of :func:`cooccur_gemm`: 0/1 incidence
    operands (any float dtype), fp32 accumulation (exact for D < 2^24),
    rounded to int32 counts.  Tile sizes adapt DOWN to the operands —
    ``bk`` to the doc axis (16-row layout multiples), ``bm``/``bn`` to the
    vocab tiles (8/128) — so the skinny row-block GEMMs that full-network
    materialization issues per (row, column) tile don't pad tiny operands
    to the full 128x128x512 MXU schedule.
    """
    b = _resolve(backend)
    if b == "xla":
        return jnp.round(ref.cooccur_gemm_ref(x_l, x_r)).astype(jnp.int32)
    d, vl = x_l.shape
    vr = x_r.shape[1]
    bm = _fit_tile(vl, bm, 8)
    bn = _fit_tile(vr, bn, 128)
    bk = _fit_tile(d, bk, 16)
    xl = _pad_to(_pad_to(x_l, 1, bm), 0, bk)
    xr = _pad_to(_pad_to(x_r, 1, bn), 0, bk)
    out = cooccur_gemm_pallas(xl, xr, bm=bm, bn=bn, bk=bk,
                              interpret=(b == "interpret"))
    return jnp.round(out[:vl, :vr]).astype(jnp.int32)


def cooccur_counts_sharded(x_l: jax.Array, x_r: jax.Array, *, mesh,
                           backend: Optional[str] = None, bm: int = 128,
                           bn: int = 128, bk: int = 512) -> jax.Array:
    """:func:`cooccur_counts` under a device mesh — per-shard tile
    dispatch: the Pallas GEMM's grid runs on each device's LOCAL shard
    and the partials merge cross-device, bit-exactly.

    Term-sharded mesh ("model" axis > 1): ``x_r``'s columns split, each
    device computes its (Vl, Vr/n) count block, merged with a tiled
    ``all_gather``.  Doc-sharded mesh ("data" axis > 1): both operands'
    contraction rows split, per-device partial products merged with an
    integer ``psum`` (0/1 operands accumulate in fp32 exactly, and the
    int32 partials sum associatively — no precision loss).  Columns/rows
    pad to the shard multiple and slice back, as the single-device
    wrapper pads to tile multiples.
    """
    from jax.sharding import PartitionSpec as P
    n_data = mesh.shape.get("data", 1)
    n_model = mesh.shape.get("model", 1)
    if n_data > 1 and n_model > 1:
        raise ValueError("cooccur_counts_sharded shards one axis at a time; "
                         f"got mesh shape {dict(mesh.shape)}")
    vr = x_r.shape[1]

    if n_model > 1:          # term-sharded columns + gather merge
        xr = _pad_to(x_r, 1, n_model)

        def local(x_l, x_r_l):
            c = cooccur_counts(x_l, x_r_l, backend=backend, bm=bm, bn=bn,
                               bk=bk)
            return jax.lax.all_gather(c, "model", axis=1, tiled=True)

        out = jax.shard_map(local, mesh=mesh,
                            in_specs=(P(), P(None, "model")),
                            out_specs=P(None, None), check_vma=False)(x_l, xr)
        return out[:, :vr]

    # doc-sharded contraction rows + psum merge
    xl = _pad_to(x_l, 0, n_data)
    xr = _pad_to(x_r, 0, n_data)

    def local(x_l_l, x_r_l):
        c = cooccur_counts(x_l_l, x_r_l, backend=backend, bm=bm, bn=bn, bk=bk)
        return jax.lax.psum(c, "data")

    return jax.shard_map(local, mesh=mesh,
                         in_specs=(P("data", None), P("data", None)),
                         out_specs=P(None, None), check_vma=False)(xl, xr)


# -- postings popcount -------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("backend", "bb", "bv", "bw"))
def postings_counts(masks: jax.Array, packed: jax.Array, *,
                    backend: Optional[str] = None, bb: int = 8, bv: int = 512,
                    bw: int = 256) -> jax.Array:
    b = _resolve(backend)
    if b == "xla":
        return ref.postings_counts_ref(masks, packed)
    nb, v = masks.shape[0], packed.shape[1]
    bw = min(bw, packed.shape[0])     # a short index is one full block
    m = _pad_to(_pad_to(masks, 0, bb), 1, bw)
    p = _pad_to(packed, 1, bv)        # the W axis is never padded
    out = postings_counts_pallas(m, p, bb=bb, bv=bv, bw=bw,
                                 interpret=(b == "interpret"))
    return out[:nb, :v]


# -- fused BFS level step ----------------------------------------------------

def _level_step_tiles(b: int, vp: int, wp: int) -> Tuple[int, int]:
    """(bv, bw) of the fused level-step kernel, from the shapes alone.

    bv: the largest multiple of 8 dividing V_pad, at most 256 and at most
    8,192 / B, so the (B, bv, 128) int32 lane-wide accumulator stays
    within 4 MiB of VMEM (B = 32: bv = 256).  bw: W_pad's 128-word
    chunks split into the fewest blocks of at most 1,024 words, as evenly
    as whole chunks allow; the last block may overhang W_pad by under one
    chunk per block (W_pad 12,416 = 97 chunks: 13 blocks of 1,024).
    """
    bv = min(256, vp, max(8, 8192 // b // 8 * 8))
    while vp % bv:                    # vp is a multiple of 8, so
        bv -= 8                       # this terminates at >= 8
    chunks = wp // 128
    nw = -(-chunks // 8)
    return bv, 128 * -(-chunks // nw)


@functools.partial(jax.jit, static_argnames=("v", "k", "dedup", "backend"))
def level_step(masks: jax.Array, packed_t_pad: jax.Array, terms: jax.Array,
               valid: jax.Array, visited: jax.Array, *, v: int, k: int,
               dedup: bool = True, backend: Optional[str] = None):
    """One fused BFS level step: popcount counts + self/visited/valid
    masking + exact top-k, one launch (``kernels.level_step``).

    masks (B, W) uint32; packed_t_pad (V_pad, W_pad) uint32 — the
    PRE-PADDED transposed postings (``QueryContext.packed_t_pad``: V to a
    multiple of 8, W to a multiple of 128, padded once per ingest epoch);
    terms (B,) int32 (-1 = invalid); valid (B,) bool; visited (V,) bool.
    Returns (weights, ids) both (B, k) int32 — bit-identical (values AND
    tie order) to masked counts through ``chunked_top_k``: ``k > v``
    clamps internally and pads the missing slots with weight -1 / id 0.

    Unlike the other wrappers this one REFUSES to pad its big operand:
    steady-state queries must launch with zero ``jnp.pad`` of the
    postings (the per-call prepad this kernel exists to kill).  The
    per-query frontier state (masks rows/words, the visited vector) may
    still pad — O(B·W + V) per call, never O(V·W).
    """
    b = _resolve(backend)
    vp, wp = packed_t_pad.shape
    if vp % 8 or wp % 128 or vp < v:
        raise ValueError(
            f"packed_t_pad {packed_t_pad.shape} is not the pre-padded "
            f"(V->8, W->128) artifact for v={v}; pass "
            "QueryContext.packed_t_pad() — level_step never pads it")
    nb = masks.shape[0]
    k_eff = min(k, v)
    tclip = jnp.clip(terms, 0).astype(jnp.int32)
    vis = (visited.astype(jnp.int32) if dedup
           else jnp.zeros(visited.shape, jnp.int32))
    vld = valid.astype(jnp.int32)
    if b == "xla":
        # the XLA form (CPU only) has no tile-shape constraint: slice
        # the artifact back to the true (v, W) so the popcount touches
        # zero padding work (a static slice of the cached artifact, not a
        # per-call pad — shapes stay fixed across submits within an epoch)
        pt = packed_t_pad[:v, :masks.shape[1]]
        w, i = level_step_topk_xla(masks, pt, tclip[:, None],
                                   vld[:, None], vis[None, :],
                                   v=v, k=k_eff)
    else:
        m2 = _pad_to(masks, 0, 8)
        bv, bw = _level_step_tiles(m2.shape[0], vp, wp)
        # zero mask words up to the last W block's end: where it overhangs
        # W_pad they AND the unspecified words read there to zero
        m2 = _pad_to(m2, 1, bw)
        t2 = _pad_to(tclip[:, None], 0, 8)
        v2 = _pad_to(vld[:, None], 0, 8)      # pad rows invalid -> all -1
        vis_p = _pad_to(vis, 0, vp)
        w, i = level_step_pallas(m2, packed_t_pad, t2, v2, vis_p[None, :],
                                 v=v, k=k_eff, bv=bv, bw=bw,
                                 interpret=(b == "interpret"))
        w, i = w[:nb], i[:nb]
    if k_eff < k:
        w = jnp.pad(w, ((0, 0), (0, k - k_eff)), constant_values=-1)
        i = jnp.pad(i, ((0, 0), (0, k - k_eff)))
    return w, i


# -- flash decode attention --------------------------------------------------


def flash_decode_xla(q: jax.Array, k: jax.Array, v: jax.Array,
                     length: jax.Array) -> jax.Array:
    """Optimised XLA decode attention (EXPERIMENTS.md §Perf B1): K/V feed
    the dots in their storage dtype with fp32 accumulation — no
    materialised fp32 cast of the (huge) KV cache, unlike the oracle."""
    b, hq, d = q.shape
    s, hkv = k.shape[1], k.shape[2]
    g = hq // hkv
    qg = q.reshape(b, hkv, g, d)
    scores = jnp.einsum("bhgd,bshd->bhgs", qg, k,
                        preferred_element_type=jnp.float32)
    scores = scores / jnp.sqrt(jnp.float32(d))
    pos = jnp.arange(s)
    ln = jnp.broadcast_to(jnp.asarray(length), (b,))
    scores = jnp.where((pos[None, :] < ln[:, None])[:, None, None, :],
                       scores, -1e30)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgs,bshd->bhgd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, hq, d).astype(q.dtype)


def decode_attn(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                length: jax.Array, k_cur: jax.Array, v_cur: jax.Array
                ) -> jax.Array:
    """Decode attention over (cache prefix + current token) WITHOUT writing
    the cache first (EXPERIMENTS.md §Perf B2).

    The naive decode flow (write entry -> attend over cache) forces a full
    cache copy per layer under functional updates (read+write of the whole
    (B,S,H,d) buffer), which dominated the decode memory roofline term
    (measured ~32x the cache size per step for a 32-layer model).  Here the
    current token's scores are merged analytically — only the (tiny) score
    tensors concatenate — and the cache is written ONCE per step by the
    caller (single donated scatter).

    q (B, Hq, d); k_cache/v_cache (B, S, Hkv, dk/dv); length (B,) = #valid
    cache entries (the current token is IN ADDITION to these);
    k_cur/v_cur (B, Hkv, dk/dv).  Returns (B, Hq, dv).
    """
    b, hq, d = q.shape
    s, hkv = k_cache.shape[1], k_cache.shape[2]
    dv = v_cache.shape[-1]
    g = hq // hkv
    qg = q.reshape(b, hkv, g, d)
    scale = 1.0 / jnp.sqrt(jnp.float32(d))
    s1 = jnp.einsum("bhgd,bshd->bhgs", qg, k_cache,
                    preferred_element_type=jnp.float32) * scale
    pos = jnp.arange(s)
    ln = jnp.broadcast_to(jnp.asarray(length), (b,))
    s1 = jnp.where((pos[None, :] < ln[:, None])[:, None, None, :], s1, -1e30)
    s2 = jnp.einsum("bhgd,bhd->bhg", qg, k_cur,
                    preferred_element_type=jnp.float32) * scale   # (B,H,G)
    # §Perf B3: merge via explicit max/sum-exp arithmetic rather than
    # concatenating on the (sequence-sharded) score axis — a concat of a
    # sharded 32k dim with a length-1 tensor forces SPMD to rematerialise
    # the cache (measured: +35 GB of all-gathers per step).
    m = jnp.maximum(jnp.max(s1, axis=-1), s2)                     # (B,H,G)
    e1 = jnp.exp(s1 - m[..., None])
    e2 = jnp.exp(s2 - m)
    denom = jnp.sum(e1, axis=-1) + e2                             # (B,H,G)
    o1 = jnp.einsum("bhgs,bshd->bhgd", e1.astype(v_cache.dtype), v_cache,
                    preferred_element_type=jnp.float32)
    out = (o1 + e2[..., None] * v_cur.astype(jnp.float32)[:, :, None, :]
           ) / denom[..., None]
    return out.reshape(b, hq, dv).astype(q.dtype)


@functools.partial(jax.jit, static_argnames=("backend", "chunk"))
def flash_decode(q: jax.Array, k: jax.Array, v: jax.Array, length: jax.Array,
                 *, backend: Optional[str] = None, chunk: int = 512) -> jax.Array:
    """q (B, Hq, d); k, v (B, S, Hkv, d); length (B,) -> (B, Hq, d)."""
    b = _resolve(backend)
    if b == "xla":
        return flash_decode_xla(q, k, v, length)
    bsz, hq, d = q.shape
    s, hkv = k.shape[1], k.shape[2]
    g = hq // hkv
    qg = q.reshape(bsz, hkv, g, d)
    ck = min(chunk, s)
    kp = _pad_to(k, 1, ck)
    vp = _pad_to(v, 1, ck)
    ln = jnp.broadcast_to(jnp.asarray(length, jnp.int32), (bsz,))
    out = flash_decode_pallas(qg, kp, vp, ln, chunk=ck,
                              interpret=(b == "interpret"))
    return out.reshape(bsz, hq, d)


# -- DLRM dot interaction ----------------------------------------------------

@functools.partial(jax.jit, static_argnames=("backend", "bb"))
def dot_interaction(x: jax.Array, *, backend: Optional[str] = None,
                    bb: int = 128) -> jax.Array:
    b = _resolve(backend)
    if b == "xla":
        return ref.dot_interaction_ref(x)
    nb = x.shape[0]
    xp = _pad_to(x, 0, bb)
    out = dot_interaction_pallas(xp, bb=bb, interpret=(b == "interpret"))
    return out[:nb]
