"""Logical-axis sharding: models annotate tensors with logical axis names;
the launch layer binds them to physical mesh axes (MaxText-style).

Models call ``constrain(x, ("batch", "seq", None))``.  Outside an active
``axis_rules`` context this is the identity, so unit tests and single-CPU
runs never touch device state.  Inside, logical names resolve to
PartitionSpec via the rule table and apply with_sharding_constraint.

Physical mesh axes: ("pod", "data", "model") multi-pod, ("data", "model")
single-pod (see launch/mesh.py).
"""
from __future__ import annotations

import contextlib
import contextvars
from typing import Dict, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Axis = Union[str, None, Tuple[str, ...]]

# logical axis -> physical mesh axes (tuple = axis product)
DEFAULT_RULES: Dict[str, Tuple[str, ...]] = {
    "batch": ("pod", "data"),        # data parallel
    "seq": ("model",),               # sequence parallelism between blocks
    "kv_seq": ("data", "model"),     # long-context KV cache sequence sharding
    "heads": ("model",),             # tensor parallel attention
    "kv_heads": ("model",),
    "ff": ("model",),                # tensor parallel FFN
    "vocab": ("model",),             # tensor parallel embedding / lm head
    "experts": ("model",),           # expert parallel
    "embed": (),                     # d_model stays replicated (TP activations)
    "fsdp": ("data",),               # param/opt-state FSDP axis
    "edges": ("pod", "data"),        # GNN edge partition
    "nodes": (),                     # GNN node tensors replicated
    "feat": ("model",),              # GNN/recsys feature dim
    "rows": ("model",),              # embedding-table row sharding
    "docs": ("pod", "data"),         # packed index: doc-word axis
    "terms": ("model",),             # packed index: vocabulary axis
    "cooc_row": ("pod", "data"),     # co-occurrence matrix row axis (V x V out)
    "cand": ("pod", "data", "model"),  # retrieval candidate axis
}


class _Ctx:
    def __init__(self, mesh: Mesh, rules: Dict[str, Tuple[str, ...]]):
        self.mesh = mesh
        self.rules = rules


_ACTIVE: contextvars.ContextVar[Optional[_Ctx]] = contextvars.ContextVar(
    "sharding_ctx", default=None)


@contextlib.contextmanager
def axis_rules(mesh: Mesh, rules: Optional[Dict[str, Tuple[str, ...]]] = None):
    """Activate logical->physical sharding for the enclosed region."""
    merged = dict(DEFAULT_RULES)
    if rules:
        merged.update(rules)
    tok = _ACTIVE.set(_Ctx(mesh, merged))
    try:
        with jax.sharding.set_mesh(mesh):
            yield
    finally:
        _ACTIVE.reset(tok)


def _resolve_axis(ctx: _Ctx, axis: Axis, dim_size: int,
                  used: set) -> Optional[Tuple[str, ...]]:
    """Map one logical axis to mesh axes, dropping axes that don't divide
    the dim or are already consumed by an earlier dim of the same tensor."""
    if axis is None:
        return None
    names = (axis,) if isinstance(axis, str) else axis
    phys: list = []
    for n in names:
        for m in ctx.rules.get(n, ()):
            if m in ctx.mesh.shape:
                phys.append(m)
    if not phys:
        return None
    total = 1
    kept = []
    for m in phys:
        if m in kept or m in used:
            continue
        sz = ctx.mesh.shape[m]
        if dim_size % (total * sz) == 0:
            kept.append(m)
            total *= sz
    return tuple(kept) or None


def logical_to_spec(axes: Sequence[Axis], shape: Sequence[int]) -> P:
    """Resolve logical axes to a PartitionSpec under the active context.

    Indivisible dims degrade to replication per-mesh-axis (the
    ``shard_if_divisible`` rule from DESIGN.md — e.g. qwen's 40 heads on a
    16-way model axis); a mesh axis is used by at most one dim (first dim
    in ``axes`` order wins).
    """
    ctx = _ACTIVE.get()
    assert ctx is not None
    parts = []
    used: set = set()
    for ax, n in zip(axes, shape):
        r = _resolve_axis(ctx, ax, n, used)
        if r is None:
            parts.append(None)
        elif len(r) == 1:
            parts.append(r[0])
            used.add(r[0])
        else:
            parts.append(tuple(r))
            used.update(r)
    return P(*parts)


def named_sharding(axes: Sequence[Axis], shape: Sequence[int]) -> NamedSharding:
    """One NamedSharding from logical axes + a concrete shape (or SDS)."""
    ctx = _ACTIVE.get()
    assert ctx is not None
    sh = shape.shape if hasattr(shape, "shape") else shape
    return NamedSharding(ctx.mesh, logical_to_spec(axes, sh))


def constrain(x: jax.Array, axes: Sequence[Axis]) -> jax.Array:
    """with_sharding_constraint via logical axes; identity outside context."""
    ctx = _ACTIVE.get()
    if ctx is None:
        return x
    spec = logical_to_spec(axes, x.shape)
    return jax.lax.with_sharding_constraint(x, NamedSharding(ctx.mesh, spec))


def spec_tree(specs_logical, shapes) -> "jax.tree_util.PyTreeDef":
    """Map a pytree of logical-axis tuples + matching shapes to PartitionSpecs."""
    return jax.tree.map(
        lambda ax, sh: logical_to_spec(ax, sh.shape if hasattr(sh, "shape") else sh),
        specs_logical, shapes,
        is_leaf=lambda v: isinstance(v, tuple) and all(
            isinstance(a, (str, tuple, type(None))) for a in v),
    )


def sharding_tree(specs_logical, shapes):
    """Same but returns NamedSharding leaves (for in_shardings / device_put)."""
    ctx = _ACTIVE.get()
    assert ctx is not None
    st = spec_tree(specs_logical, shapes)
    return jax.tree.map(lambda s: NamedSharding(ctx.mesh, s),
                        st, is_leaf=lambda v: isinstance(v, P))


def active_mesh() -> Optional[Mesh]:
    ctx = _ACTIVE.get()
    return ctx.mesh if ctx else None
