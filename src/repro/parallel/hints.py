"""Sharding hints that degrade to no-ops outside a mesh context.

Model code calls `shard_hint(x, "model", None, ...)` to pin intermediate
layouts (expert buffers, attention activations). Under pjit with an active
mesh the hint becomes a with_sharding_constraint; in single-device smoke
tests it vanishes. Mesh-context discovery goes through `repro.compat`,
whose meshes have Auto axes, so the constraint is a hint to the
partitioner and never an assertion.

`tp_reduce` / `tp_serving` are the manual-collective counterpart for
shard_map regions: the sharded serving engine traces the model inside a
``tp_serving(axis, tags)`` context, and the model's row-parallel
projection outputs (``attn_out``, ``ffn_down``) pass through
``tp_reduce`` — a psum over the model axis when the engine declared that
projection sharded, the identity everywhere else (single-device serving,
training, GSPMD paths). Keeping the gate tag-based lets the engine make
the psum placement agree *exactly* with the PartitionSpecs it built: a
projection whose in-axis did not shard must not be reduced (its per-shard
output is already the full sum).
"""
from __future__ import annotations

import contextlib
from typing import Optional

import jax
from jax.sharding import PartitionSpec as P

from repro import compat

# (axis_name, frozenset of enabled reduce tags) — set only while the
# sharded serving engine traces its shard_map bodies
_TP_CTX: list[tuple[str, frozenset]] = []


def _active_mesh():
    return compat.active_mesh()


def shard_hint(x: jax.Array, *spec) -> jax.Array:
    mesh = _active_mesh()
    if mesh is None:
        return x
    # inside shard_map regions axes are Manual — constraints are illegal
    # there (the sharding is already explicit); the hint becomes a no-op
    if compat.manual_axis_in(mesh):
        return x
    axes = set(mesh.axis_names)
    clean = []
    for s in spec:
        if s is None:
            clean.append(None)
        elif isinstance(s, tuple):
            kept = tuple(a for a in s if a in axes)
            clean.append(kept if kept else None)
        else:
            clean.append(s if s in axes else None)
    # drop axes whose size does not divide the dim
    fixed = []
    for dim, s in zip(x.shape, clean):
        names = (s,) if isinstance(s, str) else (s or ())
        size = 1
        for a in names:
            size *= mesh.shape[a]
        fixed.append(s if size > 0 and dim % size == 0 else None)
    return jax.lax.with_sharding_constraint(x, P(*fixed))


@contextlib.contextmanager
def tp_serving(axis: str, reduce_tags):
    """Enable tensor-parallel psums for the enclosed trace.

    Entered *inside* the shard_map body (so it is active whenever jit
    re-traces the step), with ``reduce_tags`` naming exactly the
    row-parallel projections the engine's specs sharded on their
    contraction axis."""
    _TP_CTX.append((axis, frozenset(reduce_tags)))
    try:
        yield
    finally:
        _TP_CTX.pop()


def tp_reduce(x: jax.Array, tag: str) -> jax.Array:
    """psum ``x`` over the TP axis iff tracing under ``tp_serving`` with
    ``tag`` enabled; the identity otherwise (every non-shard_map path)."""
    if not _TP_CTX:
        return x
    axis, tags = _TP_CTX[-1]
    if tag not in tags:
        return x
    return jax.lax.psum(x, axis)


def tp_context() -> Optional[tuple[str, frozenset]]:
    """The active tp_serving context, or None (diagnostics/tests)."""
    return _TP_CTX[-1] if _TP_CTX else None


def shard_hint_leaves(tree, *spec):
    """Apply one shard_hint to every array leaf of a small pytree.

    The main consumer is the compressed-operand pin in ``nm_matmul``:
    an NMWeight's vals and idx (same shape, same layout role) must be
    co-sharded so the FSDP gather moves the compressed pair together."""
    return jax.tree.map(lambda l: shard_hint(l, *spec), tree)
