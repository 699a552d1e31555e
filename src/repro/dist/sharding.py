"""Activation + state sharding: one place that maps arrays onto mesh axes.

Axis naming convention (DESIGN.md §4): meshes use up to three named axes —
``pod`` (across pods), ``data`` (row/batch parallel) and ``model`` (tensor /
sequence parallel). Model code never names mesh axes directly; it labels array
dims with the *logical* tags ``"dp"`` (rows: the pod+data product axis),
``"model"`` or ``None`` and calls :func:`shard_act`. The labels resolve
against the mesh registered with :func:`activation_sharding`, so the same
model source traces to a no-op on one device and to
`with_sharding_constraint`s on a pod.

Resolution drops any label whose dim is not divisible by the target axes'
size — tiny smoke configs (e.g. 2-row batches on a 4-way data axis) fall back
to replication instead of erroring, mirroring `models.spec.partition_spec`.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

DATA_AXES = ("pod", "data")
MODEL_AXIS = "model"


# ------------------------------------------------------- mesh-context registry
class _MeshStack(threading.local):
    def __init__(self):
        self.stack: list = []


_CTX = _MeshStack()


def current_mesh() -> Optional[Mesh]:
    """The mesh of the innermost :func:`activation_sharding` context, or
    None off-mesh."""
    return _CTX.stack[-1] if _CTX.stack else None


@contextlib.contextmanager
def activation_sharding(mesh: Optional[Mesh]):
    """Register `mesh` as the target for `shard_act`/`shard_res`.

    Entered around *tracing* (the launcher wraps the step fn before `jit`), so
    constraints bake into the jaxpr. ``activation_sharding(None)`` explicitly
    disables sharding in the dynamic extent (used by oracle replays).
    """
    _CTX.stack.append(mesh)
    try:
        yield mesh
    finally:
        _CTX.stack.pop()


# ------------------------------------------------------------ axis arithmetic
def _axis_sizes(mesh: Mesh) -> dict:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def data_axes(mesh: Mesh) -> tuple:
    """The row-parallel axes present in `mesh`, in (pod, data) order."""
    return tuple(a for a in DATA_AXES if a in mesh.axis_names)


def dp_entry(mesh: Mesh):
    """PartitionSpec entry for the fused row axis: tuple, name, or None."""
    axes = data_axes(mesh)
    if not axes:
        return None
    return axes if len(axes) > 1 else axes[0]


def dp_axis_size(mesh: Optional[Mesh] = None) -> int:
    """Total row-parallel ways (pod × data); 1 off-mesh."""
    mesh = mesh if mesh is not None else current_mesh()
    if mesh is None:
        return 1
    sizes = _axis_sizes(mesh)
    return int(np.prod([sizes[a] for a in data_axes(mesh)], initial=1))

def model_axis_size(mesh: Optional[Mesh] = None) -> int:
    """Size of the model (tensor/sequence-parallel) axis; 1 off-mesh."""
    mesh = mesh if mesh is not None else current_mesh()
    if mesh is None:
        return 1
    return int(_axis_sizes(mesh).get(MODEL_AXIS, 1))


# --------------------------------------------------------- constraint helpers
def resolve_spec(mesh: Mesh, dims: Sequence[int], labels: Sequence) -> P:
    """Map per-dim labels ("dp" | axis name | None) to a PartitionSpec.

    A label is dropped (-> None) when its axes are absent, already used by an
    earlier dim, trivial (product 1), or do not divide the dim size.
    """
    sizes = _axis_sizes(mesh)
    used: set = set()
    entries = []
    for dim, lbl in zip(dims, labels):
        if lbl is None:
            entries.append(None)
            continue
        axes = data_axes(mesh) if lbl == "dp" else (lbl,)
        axes = tuple(a for a in axes if a in sizes and a not in used)
        total = int(np.prod([sizes[a] for a in axes], initial=1))
        if not axes or total == 1 or dim % total != 0:
            entries.append(None)
            continue
        used.update(axes)
        entries.append(axes if len(axes) > 1 else axes[0])
    return P(*entries)


def shard_act(x: jax.Array, *labels) -> jax.Array:
    """Constrain activation `x` (one label per dim); identity off-mesh."""
    mesh = current_mesh()
    if mesh is None or mesh.size <= 1:
        return x
    if len(labels) != x.ndim:
        raise ValueError(
            f"shard_act: {len(labels)} labels for rank-{x.ndim} array "
            f"(shape {x.shape}, labels {labels})")
    spec = resolve_spec(mesh, x.shape, labels)
    if all(e is None for e in spec):
        return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def shard_res(x: jax.Array) -> jax.Array:
    """Residual-stream policy for (B, S, d): rows over dp, sequence over
    `model` when S divides it (sequence parallelism between blocks — the MoE
    dispatch and attention then all-gather S exactly once per layer)."""
    if x.ndim == 3:
        return shard_act(x, "dp", MODEL_AXIS, None)
    return shard_act(x, "dp", *(None,) * (x.ndim - 1))


def concat_rows(parts: Sequence[jax.Array], axis: int = 0,
                labels: Optional[Sequence] = None) -> jax.Array:
    """Concatenate array blocks with explicitly pinned result sharding.

    jax 0.4.37's partitioner miscompiles `concatenate` whenever an operand or
    the result is sharded on a multi-axis mesh: the output comes back summed
    over the other mesh axes (observed on the (data, model) grid — every value
    doubled by the 2-way model axis, for any operand size, with or without
    explicit constraints on the operands). `dynamic_update_slice` of the same
    blocks into a zeros buffer partitions correctly for every tested sharding
    combination, so on-mesh the concat is expressed that way, with the result
    pinned to an explicit sharding. The pin is total and applied even when
    every label resolves to replicated — leaving the result unconstrained
    would hand it back to the propagation pass that miscompiles. Off-mesh this
    is exactly `jnp.concatenate`, so mesh-agnostic core code can use it
    unconditionally.

    ``labels`` gives one :func:`shard_act`-style label per result dim (for
    feature-axis concats of sharded activations, e.g. the MLA nope|rope
    head-dim concat). Default: ``"dp"`` on `axis`, replicated elsewhere — the
    [batch | halo] row-block layout of `core/lmc.py`. A (rows,
    model-sharded-features) default output is deliberately traded for
    correctness here.
    """
    parts = list(parts)
    mesh = current_mesh()
    if mesh is None or mesh.size <= 1:
        return jnp.concatenate(parts, axis=axis)

    dtype = jnp.result_type(*parts)  # match jnp.concatenate's promotion
    shape = list(parts[0].shape)
    shape[axis] = sum(int(x.shape[axis]) for x in parts)
    out = jnp.zeros(tuple(shape), dtype)
    offset = 0
    for x in parts:
        start = [0] * out.ndim
        start[axis] = offset
        out = jax.lax.dynamic_update_slice(out, x.astype(dtype), tuple(start))
        offset += int(x.shape[axis])
    if labels is None:
        labels = [None] * out.ndim
        labels[axis % out.ndim] = "dp"
    spec = resolve_spec(mesh, out.shape, labels)
    return jax.lax.with_sharding_constraint(out, NamedSharding(mesh, spec))


# ------------------------------------------------------ NamedSharding factory
def named(mesh: Mesh, *entries) -> NamedSharding:
    return NamedSharding(mesh, P(*entries))


def replicated(mesh: Mesh) -> NamedSharding:
    return named(mesh)


def row_sharding(mesh: Mesh) -> NamedSharding:
    """Per-row 1-D arrays (gids, masks, edge lists): leading dim over dp."""
    return named(mesh, dp_entry(mesh))


def store_sharding(mesh: Mesh, *, model_axis: str | None = MODEL_AXIS,
                   leading_dims: int = 1) -> NamedSharding:
    """LMC historical stores ``(L, n, d)`` (and friends): node axis over dp,
    feature axis over `model_axis` when present (DESIGN.md §3/§4)."""
    feat = model_axis if model_axis in mesh.axis_names else None
    return named(mesh, *(None,) * leading_dims, dp_entry(mesh), feat)
