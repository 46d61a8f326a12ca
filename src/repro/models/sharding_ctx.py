"""Activation-sharding context.

XLA's sharding propagation loses the batch dimension through the
transpose/reshape-heavy recurrent scans (it then replicates multi-GB
intermediates on every device — observed as all-gathers of the global
batch in the xLSTM dry-run).  Model code therefore pins activations with
``constrain(x, dims)`` at block boundaries and around time-scans.

The context is process-global and set by the launcher (dryrun/train/serve)
before tracing; when unset (CPU unit tests), constraints are no-ops.
``dims`` marks each tensor dim as one of:

  'b'  — batch          -> the data axes ('pod','data')
  'm'  — model-parallel -> 'model'
  None — unsharded
"""
from __future__ import annotations

import contextlib
from typing import Optional, Sequence

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

_CTX = {"mesh": None, "batch_axes": None, "model_axis": None, "manual": False,
        "tp_axis": None}


@contextlib.contextmanager
def tp_region(axis: Optional[str]):
    """Declare a MANUAL tensor-parallel shard_map axis for the duration of
    tracing: transformer dense-FFN blocks switch from ``layers.mlp`` to the
    explicit Megatron wire ``layers.mlp_tp`` over ``axis`` (DESIGN.md §14).
    This is the manual-collectives sibling of ``model_axis`` (which lets
    XLA's partitioner insert the TP collectives): inside a shard_map whose
    manual axes include ``axis``, the activation reductions go through
    ``collectives.api`` and are OURS to schedule and price."""
    old = _CTX["tp_axis"]
    _CTX["tp_axis"] = axis
    try:
        yield
    finally:
        _CTX["tp_axis"] = old


def tp_axis() -> Optional[str]:
    """The active manual tp axis name, or None."""
    return _CTX["tp_axis"]


@contextlib.contextmanager
def manual_region():
    """Inside a shard_map whose manual axes include the data axes, sharding
    constraints must not name them — so all constraints become no-ops
    while tracing the manual body."""
    old = _CTX["manual"]
    _CTX["manual"] = True
    try:
        yield
    finally:
        _CTX["manual"] = old


def manual_axes(mesh, axes: Sequence[str]) -> set:
    """``axis_names`` for a shard_map manual over ``axes``: those axes plus
    every size-1 axis of ``mesh``.  A one-shard axis computes the same
    manual or auto; named manual, it keeps the body full-manual on the
    standard ``data(N) x model(1)`` session mesh rather than
    partial-manual, the form every multi-device check pins.  (It was
    introduced because XLA aborted on a host callback in a partial-manual
    body; no step program carries a host callback now.)"""
    return set(axes) | {a for a in mesh.axis_names if mesh.shape[a] == 1}


def set_mesh_ctx(mesh, batch_axes: Sequence[str], model_axis: Optional[str] = "model"):
    _CTX["mesh"] = mesh
    _CTX["batch_axes"] = tuple(batch_axes)
    _CTX["model_axis"] = model_axis if (model_axis in getattr(mesh, "axis_names", ())) else None


def clear_mesh_ctx():
    _CTX["mesh"] = None
    _CTX["batch_axes"] = None
    _CTX["model_axis"] = None


@contextlib.contextmanager
def mesh_ctx(mesh, batch_axes: Sequence[str], model_axis: Optional[str] = "model"):
    old = dict(_CTX)
    set_mesh_ctx(mesh, batch_axes, model_axis)
    try:
        yield
    finally:
        _CTX.update(old)


def num_batch_shards() -> int:
    """Size of the data axes in the active context (1 when unset) — used by
    the MoE layer to group its dispatch per data shard (expert-parallel
    per-rank capacity semantics)."""
    mesh = _CTX["mesh"]
    if mesh is None or not _CTX["batch_axes"] or _CTX["manual"]:
        return 1  # inside a manual region the body already IS one shard
    n = 1
    for a in _CTX["batch_axes"]:
        n *= mesh.shape[a]
    return n


def constrain_hard(x, dims: Sequence[Optional[str]]):
    """Like constrain, but un-pinned dims are HARD-replicated (None), not
    UNCONSTRAINED.  Use inside recurrent time scans: without the hard pin,
    the SPMD partitioner may shard the small carried state over 'model' and
    emit an all-reduce PER TIME STEP (found in the xlstm §Perf iteration)."""
    mesh = _CTX["mesh"]
    if mesh is None or _CTX["manual"] or x.ndim != len(dims):
        return x
    spec = []
    for i, d in enumerate(dims):
        if d == "b" and _CTX["batch_axes"]:
            size = 1
            for a in _CTX["batch_axes"]:
                size *= mesh.shape[a]
            ok = x.shape[i] % size == 0 and x.shape[i] >= size
            spec.append(_CTX["batch_axes"] if ok else None)
        elif d == "m" and _CTX["model_axis"]:
            size = mesh.shape[_CTX["model_axis"]]
            ok = x.shape[i] % size == 0 and x.shape[i] >= size
            spec.append(_CTX["model_axis"] if ok else None)
        else:
            spec.append(None)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P(*spec)))


def constrain(x, dims: Sequence[Optional[str]]):
    """Pin sharding of ``x``: dims[i] in {'b', 'm', None} per dimension.
    No-op when no mesh context is set or a dim is not divisible."""
    mesh = _CTX["mesh"]
    if mesh is None or _CTX["manual"] or x.ndim != len(dims):
        return x
    # Dims we don't explicitly pin stay UNCONSTRAINED: a None entry in a
    # with_sharding_constraint spec is a HARD replication constraint, which
    # forces XLA to all-gather naturally-sharded values (e.g. kv=8 heads on
    # a 16-way model axis) — the dominant collective-churn bug found in the
    # §Perf iterations.
    U = P.UNCONSTRAINED
    spec = []
    pinned = 0
    for i, d in enumerate(dims):
        if d == "b" and _CTX["batch_axes"]:
            size = 1
            for a in _CTX["batch_axes"]:
                size *= mesh.shape[a]
            ok = x.shape[i] % size == 0 and x.shape[i] >= size
            spec.append(_CTX["batch_axes"] if ok else U)
            pinned += ok
        elif d == "m" and _CTX["model_axis"]:
            size = mesh.shape[_CTX["model_axis"]]
            ok = x.shape[i] % size == 0 and x.shape[i] >= size
            spec.append(_CTX["model_axis"] if ok else U)
            pinned += ok
        else:
            spec.append(U)
    if not pinned:
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(*spec)))
