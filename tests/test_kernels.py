"""Per-kernel validation: Pallas (interpret mode on CPU) vs pure-jnp oracle,
sweeping shapes and dtypes (instructions deliverable (c))."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref


# ---------------------------------------------------------------------------
# cooccur GEMM
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("d,vl,vr", [
    (64, 32, 32), (512, 128, 128), (300, 200, 100), (1024, 128, 256),
    (33, 17, 9),                       # ragged (forces padding path)
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_cooccur_gemm_shapes(d, vl, vr, dtype):
    rng = np.random.default_rng(d + vl)
    xl = (rng.random((d, vl)) < 0.15).astype(np.float32)
    xr = (rng.random((d, vr)) < 0.15).astype(np.float32)
    out = ops.cooccur_gemm(jnp.asarray(xl, dtype), jnp.asarray(xr, dtype),
                           backend="interpret", bm=32, bn=32, bk=64)
    want = ref.cooccur_gemm_ref(jnp.asarray(xl), jnp.asarray(xr))
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=0, atol=0)


def test_cooccur_gemm_counts_are_exact_integers():
    rng = np.random.default_rng(7)
    x = (rng.random((640, 128)) < 0.3).astype(np.float32)
    out = np.asarray(ops.cooccur_gemm(jnp.asarray(x), jnp.asarray(x),
                                      backend="interpret", bm=64, bn=64, bk=128))
    assert np.all(out == np.round(out))
    assert out.max() <= 640


@pytest.mark.parametrize("shard", ["terms", "docs"])
@pytest.mark.parametrize("d,vl,vr", [(70, 23, 37), (128, 64, 64)])
def test_cooccur_counts_sharded_matches_single_device(shard, d, vl, vr):
    """The mesh-aware wrapper (per-shard Pallas grid + gather/psum merge)
    must equal the single-device counts bit for bit — on whatever devices
    this host exposes (1 device degenerates to a 1-shard mesh; the
    multidevice CI job runs it on a real 8-device split)."""
    from repro.core.distributed import make_cooc_mesh
    rng = np.random.default_rng(d + vl)
    xl = jnp.asarray((rng.random((d, vl)) < 0.2), jnp.bfloat16)
    xr = jnp.asarray((rng.random((d, vr)) < 0.2), jnp.bfloat16)
    want = ops.cooccur_counts(xl, xr, backend="interpret")
    mesh = make_cooc_mesh(shard=shard)
    out = ops.cooccur_counts_sharded(xl, xr, mesh=mesh, backend="interpret")
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))


def test_cooccur_counts_sharded_rejects_two_axis_split():
    from jax.sharding import Mesh
    if len(jax.devices()) < 4:
        pytest.skip("needs >= 4 devices to build a 2x2 mesh")
    mesh = Mesh(np.asarray(jax.devices()[:4]).reshape(2, 2),
                ("data", "model"))
    x = jnp.ones((8, 8), jnp.bfloat16)
    with pytest.raises(ValueError, match="one axis at a time"):
        ops.cooccur_counts_sharded(x, x, mesh=mesh, backend="interpret")


@given(st.integers(1, 200), st.integers(1, 50), st.integers(0, 1 << 16))
@settings(max_examples=10, deadline=None)
def test_cooccur_gemm_property(d, v, seed):
    rng = np.random.default_rng(seed)
    x = (rng.random((d, v)) < 0.2).astype(np.float32)
    out = np.asarray(ops.cooccur_gemm(jnp.asarray(x), jnp.asarray(x),
                                      backend="interpret", bm=32, bn=32, bk=32))
    want = x.T @ x
    np.testing.assert_array_equal(out, want)


# ---------------------------------------------------------------------------
# postings popcount
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("b,w,v", [
    (8, 256, 512), (4, 100, 300), (16, 64, 1024), (3, 33, 65),
])
def test_postings_counts_shapes(b, w, v):
    rng = np.random.default_rng(b * w)
    masks = rng.integers(0, 1 << 32, (b, w), dtype=np.uint32)
    packed = rng.integers(0, 1 << 32, (w, v), dtype=np.uint32)
    out = ops.postings_counts(jnp.asarray(masks), jnp.asarray(packed),
                              backend="interpret", bb=4, bv=64, bw=32)
    want = ref.postings_counts_ref(jnp.asarray(masks), jnp.asarray(packed))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))


@pytest.mark.parametrize("n_docs,vocab,n_masks", [
    (256, 512, 8),     # divisible everywhere
    (100, 65, 3),      # non-divisible B, V, W (ops.py padding path)
    (33, 300, 5),      # W=2 words, far below the bw tile
])
def test_postings_pallas_matches_doc_freq_under_batch(n_docs, vocab, n_masks):
    """The Pallas postings kernel (interpret mode) against the index-level
    oracle ``doc_freq_under_batch`` on random PACKED INDICES — i.e. real
    postings bitmaps built by pack_docs, not arbitrary uint32 noise."""
    from repro.core import doc_freq_under_batch, pack_docs, term_postings
    rng = np.random.default_rng(n_docs + vocab)
    docs = [rng.integers(0, vocab, rng.integers(1, 12)).tolist()
            for _ in range(n_docs)]
    idx = pack_docs(docs, vocab)
    masks = jnp.stack([term_postings(idx, jnp.int32(t))
                       for t in rng.integers(0, vocab, n_masks)])
    out = ops.postings_counts(masks, idx.packed, backend="interpret")
    want = doc_freq_under_batch(idx, masks)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))


def test_postings_pallas_small_tiles_non_divisible():
    """Tile sizes that do NOT divide the padded shapes' originals: padding
    in ops.py must make every (bb, bv, bw) choice exact."""
    from repro.core import doc_freq_under_batch, pack_docs
    rng = np.random.default_rng(9)
    docs = [rng.integers(0, 50, 6).tolist() for _ in range(77)]
    idx = pack_docs(docs, 50)
    masks = jnp.asarray(rng.integers(0, 1 << 32, (5, idx.n_words),
                                     dtype=np.uint32))
    want = np.asarray(doc_freq_under_batch(idx, masks))
    for bb, bv, bw in [(2, 16, 8), (3, 7, 5), (8, 64, 32)]:
        out = ops.postings_counts(masks, idx.packed, backend="interpret",
                                  bb=bb, bv=bv, bw=bw)
        np.testing.assert_array_equal(np.asarray(out), want)


@pytest.mark.parametrize("platform,want", [
    ("tpu", "pallas"), ("cpu", "interpret"), ("gpu", None)])
def test_pallas_backend_resolution(monkeypatch, platform, want):
    """pallas_backend(): compiled on TPU, interpret mode on the CPU — the
    method='pallas' dispatch always exercises the kernel — and an error
    on any other platform."""
    monkeypatch.setattr(ops, "_platform", lambda: platform)
    if want is None:
        with pytest.raises(RuntimeError, match=platform):
            ops.pallas_backend()
    else:
        assert ops.pallas_backend() == want


def test_postings_counts_sparse_bitmaps():
    """All-zero masks -> zero counts; all-ones -> column popcounts."""
    w, v = 32, 128
    rng = np.random.default_rng(3)
    packed = rng.integers(0, 1 << 32, (w, v), dtype=np.uint32)
    zeros = np.zeros((1, w), np.uint32)
    ones = np.full((1, w), 0xFFFFFFFF, np.uint32)
    out0 = np.asarray(ops.postings_counts(jnp.asarray(zeros), jnp.asarray(packed),
                                          backend="interpret", bb=1, bv=64, bw=32))
    out1 = np.asarray(ops.postings_counts(jnp.asarray(ones), jnp.asarray(packed),
                                          backend="interpret", bb=1, bv=64, bw=32))
    assert (out0 == 0).all()
    colpc = np.array([[bin(int(x)).count("1") for x in packed[:, j]]
                      for j in range(v)]).sum(axis=1)
    np.testing.assert_array_equal(out1[0], colpc)


# ---------------------------------------------------------------------------
# fused BFS level step
# ---------------------------------------------------------------------------


def _level_inputs(b, v, w, seed, ties=False):
    rng = np.random.default_rng(seed)
    if ties:
        # 0-3 set bits per column, at random words (so in random lane
        # chunks and W blocks): small counts, many equal, in every V tile
        packed = np.zeros((w, v), np.uint32)
        for col in range(v):
            n = rng.choice(4, p=[0.3, 0.4, 0.25, 0.05])
            for word, bit in zip(rng.integers(0, w, n), rng.integers(0, 32, n)):
                packed[word, col] |= np.uint32(1) << np.uint32(bit)
        packed = jnp.asarray(packed)
    else:
        packed = jnp.asarray(rng.integers(0, 1 << 32, (w, v), dtype=np.uint32))
    masks = jnp.asarray(rng.integers(0, 1 << 32, (b, w), dtype=np.uint32))
    terms = jnp.asarray(rng.integers(-1, v, (b,)), jnp.int32)
    valid = jnp.asarray(rng.integers(0, 2, (b,)), bool)
    visited = jnp.asarray(rng.integers(0, 2, (v,)), bool)
    pt = jnp.pad(packed.T, ((0, (-v) % 8), (0, (-w) % 128)))
    return packed, masks, terms, valid, visited, pt


def _level_oracle(packed, masks, terms, valid, visited, *, k, dedup):
    """The unfused reference chain the kernel must reproduce bit for bit:
    popcount counts -> self-mask -> visited -> valid -> chunked_top_k."""
    from repro.core.cooccurrence import chunked_top_k
    b, v = masks.shape[0], packed.shape[1]
    c = jnp.sum(jax.lax.population_count(
        masks[:, :, None] & packed[None, :, :]).astype(jnp.int32), axis=1)
    c = c.at[jnp.arange(b), jnp.clip(terms, 0)].set(-1)
    if dedup:
        c = jnp.where(visited[None, :], -1, c)
    c = jnp.where(valid[:, None], c, -1)
    return chunked_top_k(c, k)


@pytest.mark.parametrize("b,v,w,k,dedup,ties", [
    pytest.param(5, 97, 7, 6, True, False,       # ragged everything
                 id="5-97-7-6-True"),
    pytest.param(3, 40, 3, 50, False, False,     # k > V (clamp + pad),
                 id="3-40-3-50-False"),          # dedup off
    pytest.param(8, 256, 4, 8, True, False,      # tile-friendly B/V
                 id="8-256-4-8-True"),
    pytest.param(1, 9, 1, 9, True, False,        # single row, k == V
                 id="1-9-1-9-True"),
    # several V tiles (bv 200) and 3 W blocks, the last overhanging
    # W_pad (2,176 words: a multiple of 128, not of the W block)
    pytest.param(8, 600, 2100, 8, True, False, id="8-600-2100-8-True"),
    # equal counts in different lane chunks, W blocks and V tiles: the
    # lower id must win across the lane reduction and the running merge
    pytest.param(16, 1000, 2200, 16, True, True,
                 id="ties-16-1000-2200-16-True"),
    pytest.param(8, 600, 2200, 24, False, True,
                 id="ties-8-600-2200-24-False"),
])
@pytest.mark.parametrize("backend", ["xla", "interpret"])
def test_level_step_matches_oracle_chain(b, v, w, k, dedup, ties, backend):
    """Fused level step == counts -> masking -> chunked_top_k, exact in
    values AND tie order, on both the compiled-XLA fallback and the
    Pallas kernel (interpret mode)."""
    packed, masks, terms, valid, visited, pt = _level_inputs(b, v, w, b * v,
                                                             ties)
    want_w, want_i = _level_oracle(packed, masks, terms, valid, visited,
                                   k=k, dedup=dedup)
    got_w, got_i = ops.level_step(masks, pt, terms, valid, visited,
                                  v=v, k=k, dedup=dedup, backend=backend)
    np.testing.assert_array_equal(np.asarray(want_w), np.asarray(got_w))
    np.testing.assert_array_equal(np.asarray(want_i), np.asarray(got_i))


def test_level_step_kernel_under_vmap_matches_oracle_chain():
    """The serving engine vmaps the level step over a batch of queries
    sharing one artifact: the batched kernel (extra grid axis, revisited
    outputs, VMEM scratch) must still equal the per-query oracle, here
    with several V and W tiles per query."""
    q, b, v, w, k = 3, 5, 300, 200, 7
    packed, _, _, _, _, pt = _level_inputs(b, v, w, 11)
    rng = np.random.default_rng(12)
    masks = jnp.asarray(rng.integers(0, 1 << 32, (q, b, w), dtype=np.uint32))
    terms = jnp.asarray(rng.integers(-1, v, (q, b)), jnp.int32)
    valid = jnp.asarray(rng.integers(0, 2, (q, b)), bool)
    visited = jnp.asarray(rng.integers(0, 2, (q, v)), bool)
    step = jax.vmap(lambda m, t, va, vi: ops.level_step(
        m, pt, t, va, vi, v=v, k=k, backend="interpret"))
    got_w, got_i = step(masks, terms, valid, visited)
    for j in range(q):
        want_w, want_i = _level_oracle(packed, masks[j], terms[j], valid[j],
                                       visited[j], k=k, dedup=True)
        np.testing.assert_array_equal(np.asarray(want_w), np.asarray(got_w[j]))
        np.testing.assert_array_equal(np.asarray(want_i), np.asarray(got_i[j]))


def test_level_step_refuses_unpadded_artifact():
    """level_step never pads its big operand — handing it a raw (V, W)
    transpose instead of the pre-padded epoch artifact is an error, not a
    silent per-call repad."""
    packed, masks, terms, valid, visited, _ = _level_inputs(4, 33, 3, 0)
    with pytest.raises(ValueError, match="pre-padded"):
        ops.level_step(masks, packed.T, terms, valid, visited, v=33, k=4)


def test_level_step_pad_columns_stay_below_real_candidates():
    """V padded 97 -> 104: the 7 pad columns must never be returned even
    when every real column is masked to -1 (they sit at -2, strictly
    below)."""
    packed, masks, terms, _, _, pt = _level_inputs(2, 97, 7, 5)
    valid = jnp.ones((2,), bool)
    visited = jnp.ones((97,), bool)          # every real column -> -1
    for backend in ("xla", "interpret"):
        w, i = ops.level_step(masks, pt, terms, valid, visited,
                              v=97, k=6, dedup=True, backend=backend)
        assert int(jnp.max(i)) < 97
        assert (np.asarray(w) == -1).all()


# ---------------------------------------------------------------------------
# flash decode
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("b,hq,hkv,d,s,chunk", [
    (2, 8, 2, 64, 512, 128), (1, 4, 4, 32, 256, 64),
    (3, 16, 8, 128, 300, 128),          # ragged S (padding path)
    (2, 8, 1, 64, 1024, 256),           # MQA
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_decode_shapes(b, hq, hkv, d, s, chunk, dtype):
    rng = np.random.default_rng(b * s)
    q = rng.standard_normal((b, hq, d)).astype(np.float32)
    k = rng.standard_normal((b, s, hkv, d)).astype(np.float32)
    v = rng.standard_normal((b, s, hkv, d)).astype(np.float32)
    length = rng.integers(1, s + 1, (b,)).astype(np.int32)
    out = ops.flash_decode(jnp.asarray(q, dtype), jnp.asarray(k, dtype),
                           jnp.asarray(v, dtype), jnp.asarray(length),
                           backend="interpret", chunk=chunk)
    want = ref.flash_decode_ref(jnp.asarray(q, dtype), jnp.asarray(k, dtype),
                                jnp.asarray(v, dtype), jnp.asarray(length))
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), rtol=tol, atol=tol)


def test_flash_decode_short_length():
    """length=1: attention reduces to v[0]."""
    b, hq, hkv, d, s = 2, 4, 2, 32, 256
    rng = np.random.default_rng(0)
    q = rng.standard_normal((b, hq, d)).astype(np.float32)
    k = rng.standard_normal((b, s, hkv, d)).astype(np.float32)
    v = rng.standard_normal((b, s, hkv, d)).astype(np.float32)
    out = np.asarray(ops.flash_decode(jnp.asarray(q), jnp.asarray(k),
                                      jnp.asarray(v), jnp.asarray([1, 1]),
                                      backend="interpret", chunk=64))
    g = hq // hkv
    want = np.repeat(v[:, 0], g, axis=1).reshape(b, hq, d)
    np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-5)


@given(st.integers(1, 3), st.integers(1, 4), st.integers(16, 200),
       st.integers(0, 1 << 16))
@settings(max_examples=10, deadline=None)
def test_flash_decode_property(b, hkv, s, seed):
    """Output is a convex combination of cached values (rows of V)."""
    g, d = 2, 16
    hq = hkv * g
    rng = np.random.default_rng(seed)
    q = rng.standard_normal((b, hq, d)).astype(np.float32)
    k = rng.standard_normal((b, s, hkv, d)).astype(np.float32)
    v = rng.standard_normal((b, s, hkv, d)).astype(np.float32)
    ln = rng.integers(1, s + 1, (b,)).astype(np.int32)
    out = np.asarray(ops.flash_decode(jnp.asarray(q), jnp.asarray(k),
                                      jnp.asarray(v), jnp.asarray(ln),
                                      backend="interpret", chunk=64))
    for bi in range(b):
        lo = v[bi, :ln[bi]].min(axis=0).min()
        hi = v[bi, :ln[bi]].max(axis=0).max()
        assert out[bi].min() >= lo - 1e-4
        assert out[bi].max() <= hi + 1e-4


# ---------------------------------------------------------------------------
# DLRM dot interaction
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("b,f,e", [
    (128, 27, 64), (37, 27, 64), (64, 8, 16), (256, 40, 10),
])
def test_dot_interaction_shapes(b, f, e):
    rng = np.random.default_rng(b + f)
    x = rng.standard_normal((b, f, e)).astype(np.float32)
    out = ops.dot_interaction(jnp.asarray(x), backend="interpret", bb=32)
    want = ref.dot_interaction_ref(jnp.asarray(x))
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_dot_interaction_pair_order():
    """Entry ordering matches (i, j) with i > j, row-major over i."""
    f, e = 4, 2
    x = np.arange(f * e, dtype=np.float32).reshape(1, f, e)
    out = np.asarray(ops.dot_interaction(jnp.asarray(x), backend="interpret", bb=1))
    gram = x[0] @ x[0].T
    want = [gram[1, 0], gram[2, 0], gram[2, 1], gram[3, 0], gram[3, 1], gram[3, 2]]
    np.testing.assert_allclose(out[0], want, rtol=1e-6)


# ---------------------------------------------------------------------------
# backend dispatch
# ---------------------------------------------------------------------------


def test_default_backend_is_xla_on_cpu():
    rng = np.random.default_rng(1)
    x = (rng.random((64, 32)) < 0.2).astype(np.float32)
    out = ops.cooccur_gemm(jnp.asarray(x), jnp.asarray(x))   # backend=None
    want = ref.cooccur_gemm_ref(jnp.asarray(x), jnp.asarray(x))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))


@pytest.mark.parametrize("platform,backend,want", [
    ("tpu", None, "pallas"),
    ("tpu", "pallas", "pallas"),
    ("cpu", None, "xla"),
    ("cpu", "interpret", "interpret"),
    ("cpu", "pallas", "pallas"),
    ("tpu", "xla", None),            # the reference never stands in for the chip
    ("tpu", "interpret", None),
    ("gpu", None, None),             # no kernel backend: an error, not a fallback
])
def test_backend_choice_per_platform(monkeypatch, platform, backend, want):
    monkeypatch.setattr(ops, "_platform", lambda: platform)
    if want is None:
        with pytest.raises(RuntimeError, match=platform):
            ops._resolve(backend)
    else:
        assert ops._resolve(backend) == want
