"""Logical-axis sharding layer.

Models and the trainer annotate every tensor dimension with a *logical*
axis name; this module owns the single mapping from logical axes to the
physical mesh axes of whatever mesh is currently installed:

  logical      mesh axes                        carried by
  "batch"      ("pod", "data")                  data parallelism
  "fsdp"       ("data",) or ("pod", "data")     ZeRO-3 parameter shards
  "tp"         ("model",)                       tensor parallelism
  "expert"     ("model",)                       MoE expert parallelism
  "seq_sp"     ("model",)                       sequence parallelism
  "pod"        ("pod",)                         cross-pod placement

"fsdp" spans the pod axis only when `set_fsdp_spans_pods(True)` is active
(400B+ configs whose optimizer state cannot fit a single pod).

Every mapping is pruned against reality: mesh axes that do not exist on
the current mesh, are already consumed by an earlier dimension, or do not
evenly divide the dimension being sharded are dropped (that dimension is
replicated). With no mesh installed — the 1-device CPU test environment —
`shard` is the identity and `axis_size` is 1, so model code never branches
on the execution environment.

The mesh itself is ambient state installed with `use_mesh(mesh)`; only the
launchers touch it, and they build it with `Auto` axes
(`launch/mesh.py`), the only kind `with_sharding_constraint` accepts.
"""
from __future__ import annotations

from contextlib import contextmanager
from typing import Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# --------------------------------------------------------------- mesh state

_MESH_STACK: list = []
_FSDP_SPANS_PODS = [False]


def get_mesh() -> Optional[Mesh]:
    """The innermost mesh installed by `use_mesh`, or None off-mesh."""
    return _MESH_STACK[-1] if _MESH_STACK else None


@contextmanager
def use_mesh(mesh: Mesh):
    """Install `mesh` as the ambient mesh for the dynamic extent."""
    _MESH_STACK.append(mesh)
    try:
        yield mesh
    finally:
        _MESH_STACK.pop()


def set_fsdp_spans_pods(flag: bool) -> None:
    """ZeRO-3 state spans the "pod" axis too (400B+ multi-pod configs)."""
    _FSDP_SPANS_PODS[0] = bool(flag)


def fsdp_spans_pods() -> bool:
    return _FSDP_SPANS_PODS[0]


# ------------------------------------------------------- logical -> physical

_RULES = {
    "batch": ("pod", "data"),
    "tp": ("model",),
    "expert": ("model",),
    "seq_sp": ("model",),
    "pod": ("pod",),
    # raw mesh-axis names pass through (launch code occasionally uses them)
    "data": ("data",),
    "model": ("model",),
}


def _mesh_axes_for(logical: Optional[str]) -> Tuple[str, ...]:
    if logical is None:
        return ()
    if logical == "fsdp":
        return ("pod", "data") if fsdp_spans_pods() else ("data",)
    try:
        return _RULES[logical]
    except KeyError:
        raise ValueError(f"unknown logical axis {logical!r}; "
                         f"expected one of {sorted(_RULES) + ['fsdp']}")


def axis_size(mesh: Optional[Mesh], logical: Optional[str]) -> int:
    """Total device count behind a logical axis (1 off-mesh / unmapped)."""
    if mesh is None:
        return 1
    n = 1
    for a in _mesh_axes_for(logical):
        n *= int(mesh.shape.get(a, 1))
    return n


def logical_to_spec(mesh: Mesh, axes: Sequence[Optional[str]],
                    shape: Optional[Sequence[int]] = None) -> P:
    """Map logical axes to a PartitionSpec on `mesh`.

    Pruning rules (per dimension, in order): a mesh axis is kept only if it
    exists on `mesh`, was not already used by an earlier dimension, and —
    when `shape` is given — the accumulated shard count still divides the
    dimension. Dropped axes leave the dimension replicated.
    """
    used: set = set()
    entries = []
    for i, lg in enumerate(axes):
        keep = []
        size = 1
        for a in _mesh_axes_for(lg):
            asz = int(mesh.shape.get(a, 0))
            if asz <= 0 or a in used:
                continue
            if shape is not None and (i >= len(shape) or
                                      shape[i] % (size * asz) != 0):
                continue
            keep.append(a)
            size *= asz
        used.update(keep)
        if not keep:
            entries.append(None)
        elif len(keep) == 1:
            entries.append(keep[0])
        else:
            entries.append(tuple(keep))
    return P(*entries)


def _fit(axes: Sequence[Optional[str]], ndim: int) -> Tuple[Optional[str], ...]:
    ax = tuple(axes)[:ndim]
    return ax + (None,) * (ndim - len(ax))


def sharding_for(mesh: Mesh, *axes: Optional[str],
                 shape: Optional[Sequence[int]] = None) -> NamedSharding:
    """NamedSharding for one array from its logical axes (shape-pruned)."""
    ax = _fit(axes, len(shape)) if shape is not None else axes
    return NamedSharding(mesh, logical_to_spec(mesh, ax, shape=shape))


def spec_tree_to_shardings(mesh: Mesh, spec_tree, struct_tree):
    """Tree of NamedShardings from a logical-spec tree + matching
    shape-bearing tree (arrays or ShapeDtypeStructs), pruned per-leaf.

    Spec leaves are tuples of logical axis names / None; specs shorter
    (or longer) than a leaf's rank are padded (or truncated) with
    replication, so scalar leaves may use `()`.
    """
    def one(spec, leaf):
        return sharding_for(mesh, *spec, shape=tuple(leaf.shape))

    def is_spec(x):
        return isinstance(x, tuple) and all(
            e is None or isinstance(e, str) for e in x)

    return jax.tree.map(one, spec_tree, struct_tree, is_leaf=is_spec)


def shard(x, *axes: Optional[str]):
    """Constrain `x` to its logical sharding; identity off-mesh.

    The workhorse annotation inside model code: a no-op without a mesh or
    on a 1-device mesh, `with_sharding_constraint` otherwise. Extra axes
    beyond `x.ndim` are ignored and missing ones replicate, so call sites
    never need rank plumbing.
    """
    mesh = get_mesh()
    if mesh is None or mesh.size == 1:
        return x
    spec = logical_to_spec(mesh, _fit(axes, x.ndim), shape=x.shape)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def shard_map(f, mesh: Mesh, in_specs, out_specs, check: bool = False):
    """`jax.shard_map` with its replication check spelled once
    (`check_vma`) for model code."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check)
