"""Gradient compression: int8 all-reduce with error feedback.

Data-parallel gradient all-reduce is the dominant cross-pod collective.
Compressing the payload 4x (fp32 -> int8) cuts the collective roofline
term proportionally at the cost of quantisation error, which error
feedback (residual carried to the next step) provably compensates
(Karimireddy et al., EF-SGD).

Protocol per tensor (inside shard_map over the data axes):
  1. e   = grad + residual
  2. s   = psum_max(max|e|) / 127         (shared scale — one scalar)
  3. q   = round(e / s)  in int8          (payload: 1 byte/elem)
  4. g'  = psum(q) * s / n_shards
  5. residual = e - q * s

``compressed_psum`` is the building block; ``make_ddp_train_step`` wires
it into a shard_map data-parallel step for models whose params fit one
device (recsys / GNN tiers) — the pjit paths use XLA's native psum and
enable this only via cfg.grad_compression.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def quantize_int8(e: jax.Array, scale: jax.Array) -> jax.Array:
    return jnp.clip(jnp.round(e / scale), -127, 127).astype(jnp.int8)


def compressed_psum(tree: Any, residual: Any, axis_names: Tuple[str, ...],
                    n_shards: int) -> Tuple[Any, Any]:
    """All-reduce-mean `tree` in int8 with error feedback.  Must run inside
    shard_map with `axis_names` bound.  Returns (mean_tree, new_residual)."""

    def one(g, r):
        e = g.astype(jnp.float32) + r
        local_max = jnp.max(jnp.abs(e))
        gmax = jax.lax.pmax(local_max, axis_names)
        scale = jnp.maximum(gmax / 127.0, 1e-12)
        q = quantize_int8(e, scale)
        qsum = jax.lax.psum(q.astype(jnp.int32), axis_names)
        mean = qsum.astype(jnp.float32) * scale / n_shards
        new_r = e - q.astype(jnp.float32) * scale
        return mean.astype(g.dtype), new_r

    out = jax.tree.map(one, tree, residual)
    leaves, treedef = jax.tree.flatten(out, is_leaf=lambda t: isinstance(t, tuple))
    mean = jax.tree.unflatten(treedef, [l[0] for l in leaves])
    new_res = jax.tree.unflatten(treedef, [l[1] for l in leaves])
    return mean, new_res


def init_residual(params: Any) -> Any:
    return jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)


def make_ddp_train_step(mesh: Mesh, data_axes: Tuple[str, ...],
                        loss_fn: Callable, optimizer) -> Callable:
    """Data-parallel train step with int8-compressed gradient all-reduce.

    params/opt_state/residual replicated; batch sharded on its leading axis
    over `data_axes`.
    """
    n_shards = 1
    for a in data_axes:
        n_shards *= mesh.shape[a]

    def step(params, opt_state, residual, batch):
        def shard_fn(params, opt_state, residual, batch):
            grads = jax.grad(lambda p: loss_fn(p, batch)[0])(params)
            grads, residual = compressed_psum(grads, residual, data_axes, n_shards)
            params, opt_state, stats = optimizer.update(grads, opt_state, params)
            return params, opt_state, residual, stats

        batch_spec = jax.tree.map(lambda _: P(data_axes), batch)
        rep = lambda t: jax.tree.map(lambda _: P(), t)
        return jax.shard_map(
            shard_fn, mesh=mesh,
            in_specs=(rep(params), rep(opt_state), rep(residual), batch_spec),
            out_specs=(rep(params), rep(opt_state), rep(residual),
                       {"grad_norm": P(), "lr": P()}),
            check_vma=False,
        )(params, opt_state, residual, batch)

    return step
