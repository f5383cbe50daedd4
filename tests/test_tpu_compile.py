"""The main-path kernels and the serving step, compiled for a TPU v5e.

Nothing runs: each test lowers a kernel (or the whole jitted serving
step) at ``cooccur-csl`` width for a described ``v5e:2x2`` topology and
compiles it with the TPU compiler, which refuses what the chip would
refuse -- misaligned blocks, too much VMEM, a program over HBM.  Each
compiled text must hold the Pallas kernel (``tpu_custom_call``): on the
chip no XLA reference or interpret mode stands in for it.

The topology is described inside a fixture only, so that importing this
file (every xdist worker does) never loads the TPU library.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.cooccur_csl import CONFIG
from repro.core import PackedIndex, bfs_construct_batch
from repro.kernels import ops

V = CONFIG.vocab_size                          # 65,536 terms
W = -(-(CONFIG.n_docs + 4096) // 32)           # 12,510 words: corpus + ingest slack
W_PAD = -(-W // 128) * 128                     # 12,544: packed_t_pad's word axis
D = W * 32                                     # 400,320 doc slots
Q, B, K = 8, CONFIG.default_beam, CONFIG.default_topk   # engine batch, beam, top-k
HBM_BYTES = 16 * 10**9                         # one v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def sds(topo):
    """ShapeDtypeStruct factory placed on one described chip."""
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one_chip)


@pytest.fixture
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache off here."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def _compile(fn, *args, kernel=True, **kwargs):
    compiled = jax.jit(fn).lower(*args, **kwargs).compile()
    has_kernel = "tpu_custom_call" in compiled.as_text()
    assert has_kernel == kernel, f"Pallas kernel in the executable: {has_kernel}"
    return compiled


def test_level_step_under_engine_vmap(sds, no_compile_cache):
    def step(masks, packed_t_pad, terms, valid, visited):
        one = functools.partial(ops.level_step, v=V, k=K, backend="pallas")
        return jax.vmap(one, in_axes=(0, None, 0, 0, 0))(
            masks, packed_t_pad, terms, valid, visited)

    w, i = _compile(step, sds((Q, B, W), jnp.uint32),
                    sds((V, W_PAD), jnp.uint32), sds((Q, B), jnp.int32),
                    sds((Q, B), jnp.bool_), sds((Q, V), jnp.bool_)
                    ).out_info
    assert w.shape == i.shape == (Q, B, K)


def test_postings_counts_under_engine_vmap(sds, no_compile_cache):
    def counts(masks, packed):
        one = functools.partial(ops.postings_counts, backend="pallas")
        return jax.vmap(one, in_axes=(0, None))(masks, packed)

    out = _compile(counts, sds((Q, B, W), jnp.uint32),
                   sds((W, V), jnp.uint32)).out_info
    assert out.shape == (Q, B, V)


def test_cooccur_counts_at_materialize_tile(sds, no_compile_cache):
    out = _compile(functools.partial(ops.cooccur_counts, backend="pallas"),
                   sds((D, 128), jnp.bfloat16),
                   sds((D, 128), jnp.bfloat16)).out_info
    assert out.shape == (128, 128)


@pytest.mark.parametrize("method", ["fused", "pallas"])
def test_serving_step_fits_one_chip(method, sds, no_compile_cache,
                                    monkeypatch):
    """The engine's whole jitted step (``CoocEngine._executor``) for a
    full (Q, beam) batch: the kernel is in it, and its buffers fit HBM."""
    monkeypatch.setattr(ops, "_platform", lambda: "tpu")  # trace for the chip
    index = PackedIndex(sds((W, V), jnp.uint32), sds((V,), jnp.int32),
                        sds((), jnp.int32))
    operands = ({"packed_t_pad": sds((V, W_PAD), jnp.uint32)}
                if method == "fused" else {})
    step = functools.partial(
        bfs_construct_batch, depth=CONFIG.default_depth, topk=K, beam=B,
        dedup=True, method=method, mesh=None)
    compiled = _compile(step, index, sds((Q, B), jnp.int32),
                        operands=operands, scope_mask=sds((W,), jnp.uint32))
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert total < HBM_BYTES, mem


@pytest.mark.parametrize("method", ["fused", "pallas"])
def test_term_sharded_serving_step_on_four_chips(method, topo,
                                                 no_compile_cache,
                                                 monkeypatch):
    """The same step over ``make_cooc_mesh(4, shard="terms")`` on the
    described 2x2: each chip counts against its quarter of the postings,
    and holds about a quarter of them.  Method "pallas" runs the postings
    kernel on each shard; "fused" counts each shard with XLA's popcount
    and merges per-shard top-k (``core.distributed.sharded_level_topk``)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core import make_cooc_mesh
    monkeypatch.setattr(ops, "_platform", lambda: "tpu")
    mesh = make_cooc_mesh(devices=topo.devices, shard="terms")
    assert mesh.devices.size == 4

    def sds(shape, dtype, *spec):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, P(*spec)))

    index = PackedIndex(sds((W, V), jnp.uint32, None, "model"),
                        sds((V,), jnp.int32, "model"), sds((), jnp.int32))
    operands = ({"packed_t_pad": sds((V, W_PAD), jnp.uint32, "model")}
                if method == "fused" else {})
    step = functools.partial(
        bfs_construct_batch, depth=CONFIG.default_depth, topk=K, beam=B,
        dedup=True, method=method, mesh=mesh)
    compiled = _compile(step, index, sds((Q, B), jnp.int32),
                        kernel=method == "pallas", operands=operands,
                        scope_mask=sds((W,), jnp.uint32))
    quarter = W_PAD * V * 4 // 4            # a quarter of one big operand
    n_big = len(operands) + 1               # packed (+ packed_t_pad)
    mem = compiled.memory_analysis()        # per device
    assert mem.argument_size_in_bytes < 1.1 * n_big * quarter, mem
