"""Layer 2: trace-based sync-point auditing of the jitted entry points.

The AST rules (layer 1) catch what the source *says*; this layer checks
what the compiler will actually *execute*.  Each serving-critical entry
point — ``bfs_construct_batch``, the fused ``level_step``, the
materialize tile step, the approximate (sketch-pruned) tile step and
MinHash signature kernel, and the sharded merge paths — is abstractly
traced with :func:`jax.make_jaxpr` over shape/dtype stand-ins (no device
work, no real data) and its jaxpr is walked recursively (into
pjit/scan/while/shard_map sub-jaxprs) asserting:

* **no host callbacks** — ``pure_callback`` / ``io_callback`` /
  ``debug_callback`` primitives force a device→host round trip per
  launch, which is exactly the per-step host sync PR 6 fused the level
  step to eliminate;
* **no transfer primitives** — ``device_put`` / infeed / outfeed inside
  a compiled region re-stages operands the serving layer already cached
  on device;
* **no 64-bit widening** — the packed postings are ``uint32`` by
  contract; any 64-bit aval, or a ``convert_element_type`` from a 32-bit
  integer to a 64-bit type, doubles the postings traffic the inverted
  index exists to minimize;
* **no trace-time host sync** — materializing a traced value on the
  host (``np.asarray`` / ``float()`` / ``.item()``, including on the
  result of a ``jax.device_get``, which jax traces through untouched)
  raises a concretization error during tracing; the auditor converts
  that crash into a finding.

Use from the CLI (``python -m tools.cooclint --jaxpr``) or from pytest
(:func:`audit_entry_points` / :func:`assert_clean`).  The sharded
entries need >= 2 devices and report ``skipped`` otherwise (CI forces 8
host devices via ``XLA_FLAGS``).
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Iterable, List, Optional, Tuple

FORBIDDEN_SUBSTRINGS = ("callback",)
FORBIDDEN_PRIMITIVES = frozenset({"infeed", "outfeed", "device_put"})
_WIDE_DTYPES = ("int64", "uint64", "float64")


@dataclasses.dataclass
class AuditResult:
    entry: str
    status: str                  # "clean" | "findings" | "skipped"
    findings: List[str]
    note: str = ""

    @property
    def ok(self) -> bool:
        return self.status != "findings"

    def render(self) -> str:
        head = f"[{self.status}] {self.entry}"
        if self.note:
            head += f" ({self.note})"
        return "\n".join([head] + [f"  - {f}" for f in self.findings])


# ---------------------------------------------------------------------------
# jaxpr walking
# ---------------------------------------------------------------------------


def _sub_jaxprs(value) -> Iterable:
    from jax.extend.core import ClosedJaxpr, Jaxpr
    if isinstance(value, ClosedJaxpr):
        yield value.jaxpr
    elif isinstance(value, Jaxpr):
        yield value
    elif isinstance(value, (list, tuple)):
        for v in value:
            yield from _sub_jaxprs(v)


def iter_eqns(jaxpr) -> Iterable:
    """Every equation in ``jaxpr`` and, recursively, in every sub-jaxpr
    carried in equation params (pjit bodies, scan/while/cond branches,
    shard_map bodies, custom_jvp/vjp call jaxprs)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in _sub_jaxprs(v):
                yield from iter_eqns(sub)


def audit_jaxpr(closed_jaxpr, entry: str = "<fn>") -> List[str]:
    """Walk one (closed) jaxpr; return finding strings (empty == clean)."""
    jaxpr = getattr(closed_jaxpr, "jaxpr", closed_jaxpr)
    findings: List[str] = []
    seen_wide: set = set()
    for eqn in iter_eqns(jaxpr):
        name = eqn.primitive.name
        if (name in FORBIDDEN_PRIMITIVES
                or any(s in name for s in FORBIDDEN_SUBSTRINGS)):
            findings.append(
                f"{entry}: forbidden primitive '{name}' in traced path — "
                "host callback / transfer inside a compiled region")
        if name == "convert_element_type":
            new = str(eqn.params.get("new_dtype", ""))
            src_avals = [str(v.aval.dtype) for v in eqn.invars
                         if hasattr(v, "aval") and hasattr(v.aval, "dtype")]
            if new in _WIDE_DTYPES and any(
                    d in ("int32", "uint32") for d in src_avals):
                findings.append(
                    f"{entry}: convert_element_type "
                    f"{src_avals[0]} -> {new} widens packed 32-bit data")
        for var in list(eqn.invars) + list(eqn.outvars):
            aval = getattr(var, "aval", None)
            dt = str(getattr(aval, "dtype", ""))
            if dt in _WIDE_DTYPES and (name, dt) not in seen_wide:
                seen_wide.add((name, dt))
                findings.append(
                    f"{entry}: 64-bit aval ({dt}) flowing through "
                    f"'{name}' — the postings contract is 32-bit")
    return findings


def trace_and_audit(fn: Callable, args: Tuple, entry: str = "<fn>",
                    kwargs: Optional[dict] = None) -> List[str]:
    """``make_jaxpr`` over abstract args, then :func:`audit_jaxpr`.

    Every ``kwargs`` entry made of ``ShapeDtypeStruct`` leaves (a cached
    operand such as ``operands={"x_dense": ...}``) is traced like ``args``;
    the rest (depths, method names, meshes) stay static.  A trace-time
    concretization error (``jax.device_get``, ``.item()``, python
    ``float()`` on a tracer) IS a sync-point finding, not an auditor crash.
    """
    import jax
    import jax.errors
    sync_errors = (jax.errors.ConcretizationTypeError,
                   jax.errors.TracerArrayConversionError,
                   jax.errors.TracerIntegerConversionError)

    def abstract(v) -> bool:
        leaves = jax.tree_util.tree_leaves(v)
        return bool(leaves) and all(isinstance(x, jax.ShapeDtypeStruct)
                                    for x in leaves)

    kwargs = kwargs or {}
    traced = {k: v for k, v in kwargs.items() if abstract(v)}
    static = {k: v for k, v in kwargs.items() if k not in traced}

    def call(args, traced):
        return fn(*args, **traced, **static)

    try:
        closed = jax.make_jaxpr(call)(args, traced)
    except sync_errors as e:
        first = str(e).strip().splitlines()[0]
        return [f"{entry}: trace-time host sync "
                f"({type(e).__name__}: {first})"]
    return audit_jaxpr(closed, entry)


# ---------------------------------------------------------------------------
# entry-point registry
# ---------------------------------------------------------------------------

# Tiny but structurally faithful shapes: V terms, W uint32 words
# (capacity 32*W docs), B frontier rows.  Shapes only scale buffer sizes;
# the primitive set in the jaxpr is what the audit asserts on.
_V, _W, _B, _K = 64, 4, 4, 4


def _sds(shape, dtype):
    import jax
    return jax.ShapeDtypeStruct(shape, dtype)


def _abstract_index():
    import jax.numpy as jnp
    from repro.core.inverted_index import PackedIndex
    return PackedIndex(packed=_sds((_W, _V), jnp.uint32),
                       doc_freq=_sds((_V,), jnp.int32),
                       n_docs=_sds((), jnp.int32))


def _audit_bfs_construct_batch() -> List[str]:
    import jax.numpy as jnp
    from repro.core.cooccurrence import bfs_construct_batch
    index = _abstract_index()
    seeds = _sds((2, 2), jnp.int32)                       # (Q, S)
    x_dense = _sds((_W * 32, _V), jnp.float32)            # cached artifact
    return trace_and_audit(
        bfs_construct_batch, (index, seeds), "bfs_construct_batch",
        kwargs=dict(depth=2, topk=_K, beam=_B, method="gemm",
                    operands={"x_dense": x_dense}))


def _audit_level_step() -> List[str]:
    import jax.numpy as jnp
    from repro.kernels.ops import level_step
    masks = _sds((_B, _W), jnp.uint32)
    packed_t_pad = _sds((_V, 128), jnp.uint32)            # V->8, W->128 pad
    terms = _sds((_B,), jnp.int32)
    valid = _sds((_B,), jnp.bool_)
    visited = _sds((_V,), jnp.bool_)
    return trace_and_audit(
        level_step, (masks, packed_t_pad, terms, valid, visited),
        "level_step", kwargs=dict(v=_V, k=_K))


def _audit_materialize_tile() -> List[str]:
    import jax.numpy as jnp
    from repro.core.materialize import _topk_row_block
    index = _abstract_index()
    packed_t = _sds((_V, _W), jnp.uint32)
    x_dense = _sds((_W * 32, _V), jnp.float32)
    row_start = _sds((), jnp.int32)
    return trace_and_audit(
        _topk_row_block,
        (index, packed_t, None, {"x_dense": x_dense}, row_start),
        "materialize._topk_row_block",
        kwargs=dict(k=_K, row_tile=8, col_tile=16, method="gemm"))


def _audit_approx_tile() -> List[str]:
    import jax.numpy as jnp
    from repro.core.materialize import _approx_topk_row_block
    index = _abstract_index()
    packed_t = _sds((_V, _W), jnp.uint32)
    row_start = _sds((), jnp.int32)
    cand_cols = _sds((16,), jnp.int32)        # one 64-wide stripe would be
    rows_pos = _sds((8,), jnp.int32)          # overkill at _V=64; 16 is the
    return trace_and_audit(                   # same primitive set
        _approx_topk_row_block,
        (index, packed_t, {}, row_start, cand_cols, rows_pos),
        "materialize._approx_topk_row_block",
        kwargs=dict(k=_K, row_tile=8, method="popcount"))


def _audit_minhash_signatures() -> List[str]:
    import jax.numpy as jnp
    from repro.core.sketch import minhash_signatures
    packed = _sds((_W, _V), jnp.uint32)
    a = _sds((16,), jnp.uint32)
    b = _sds((16,), jnp.uint32)
    return trace_and_audit(
        minhash_signatures, (packed, a, b), "sketch.minhash_signatures",
        kwargs=dict(perm_tile=8))


def _sharded_mesh():
    import jax
    from repro.core.distributed import make_cooc_mesh
    if len(jax.devices()) < 2:
        return None
    return make_cooc_mesh(2, shard="terms")


def _audit_sharded_counts() -> List[str]:
    import jax.numpy as jnp
    from repro.core.distributed import sharded_counts
    mesh = _sharded_mesh()
    if mesh is None:
        raise _Skip("needs >= 2 devices "
                    "(set XLA_FLAGS=--xla_force_host_platform_device_count=8)")
    index = _abstract_index()
    masks = _sds((_B, _W), jnp.uint32)
    return trace_and_audit(
        sharded_counts, (index, masks), "sharded_counts",
        kwargs=dict(method="popcount", operands={}, mesh=mesh))


def _audit_sharded_block_topk() -> List[str]:
    import jax.numpy as jnp
    from repro.core.distributed import sharded_block_topk
    mesh = _sharded_mesh()
    if mesh is None:
        raise _Skip("needs >= 2 devices "
                    "(set XLA_FLAGS=--xla_force_host_platform_device_count=8)")
    index = _abstract_index()
    masks = _sds((8, _W), jnp.uint32)
    rows = _sds((8,), jnp.int32)
    return trace_and_audit(
        sharded_block_topk, (index, masks, rows), "sharded_block_topk",
        kwargs=dict(operands={}, k=_K, method="popcount", mesh=mesh))


class _Skip(Exception):
    pass


#: entry name -> zero-arg callable returning finding strings (or raising
#: :class:`_Skip`).  The four ISSUE-mandated jitted entry points.
ENTRY_POINTS: Dict[str, Callable[[], List[str]]] = {
    "bfs_construct_batch": _audit_bfs_construct_batch,
    "level_step": _audit_level_step,
    "materialize._topk_row_block": _audit_materialize_tile,
    "materialize._approx_topk_row_block": _audit_approx_tile,
    "sketch.minhash_signatures": _audit_minhash_signatures,
    "sharded_counts": _audit_sharded_counts,
    "sharded_block_topk": _audit_sharded_block_topk,
}


def audit_entry_points(names: Optional[Iterable[str]] = None
                       ) -> List[AuditResult]:
    """Audit every registered entry point (or just ``names``)."""
    results: List[AuditResult] = []
    for name in (list(names) if names is not None else list(ENTRY_POINTS)):
        runner = ENTRY_POINTS[name]
        try:
            findings = runner()
        except _Skip as s:
            results.append(AuditResult(name, "skipped", [], note=str(s)))
            continue
        results.append(AuditResult(
            name, "findings" if findings else "clean", findings))
    return results


def assert_clean(names: Optional[Iterable[str]] = None) -> None:
    """Pytest-importable gate: raise AssertionError listing every finding."""
    bad = [r for r in audit_entry_points(names) if not r.ok]
    if bad:
        raise AssertionError(
            "jaxpr sync-point audit failed:\n"
            + "\n".join(r.render() for r in bad))
