"""Single home for the JAX APIs the rest of the repo must spell one way.

The repo supports the installed JAX line (0.9). The names below are
resolved HERE and the rest of the codebase imports them from
``repro.compat`` (enforced by tests/test_compat.py), so a later move of
any of them is a one-file change.

* ``get_abstract_mesh()`` / ``active_mesh()`` — the active abstract mesh,
  or ``None`` when no mesh context is active (callers treat that as
  "hints are no-ops").
* ``set_mesh(mesh)`` — context manager activating ``mesh``.
* ``make_mesh(shape, axes)`` — every axis ``Auto``. On JAX
  0.9 ``jax.make_mesh`` defaults to ``Explicit`` axes, under which
  ``with_sharding_constraint`` asserts the sharding instead of hinting
  it; the repo's sharding hints and shard_map engines are written for
  compiler-propagated (Auto) shardings.
* ``manual_axis_in(mesh)`` — True when any mesh axis is Manual (inside
  shard_map).
* ``shard_map`` — ``jax.shard_map``.
* ``cost_analysis(compiled)`` — ``Compiled.cost_analysis()`` as a dict.
* ``tpu_compiler_params(**kw)`` — ``pltpu.CompilerParams``; an unknown
  field raises.
* ``register_dataclass(cls, data_fields, meta_fields)`` — pytree
  registration for dataclasses (``NMWeight``).
"""
from __future__ import annotations

from typing import Any, Sequence

import jax
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import AxisType

# ---------------------------------------------------------------------------
# mesh context
# ---------------------------------------------------------------------------


def get_abstract_mesh():
    """Active (abstract) mesh, or None outside any mesh context."""
    m = jax.sharding.get_abstract_mesh()
    return m if m is not None and m.shape_tuple else None


# call sites outside this module use `compat.active_mesh()`
active_mesh = get_abstract_mesh


def set_mesh(mesh):
    """Context manager activating `mesh` so shard hints see it."""
    return jax.set_mesh(mesh)


def make_mesh(shape: Sequence[int], axes: Sequence[str]):
    """A mesh with every axis ``Auto`` (see module docstring)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def manual_axis_in(mesh: Any) -> bool:
    """True iff any axis of `mesh` is Manual (inside a shard_map region)."""
    return any(t == AxisType.Manual for t in mesh.axis_types)


def shard_map(f, *, mesh, in_specs, out_specs, check_vma: bool = True):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)


# ---------------------------------------------------------------------------
# compiled-artifact introspection, Pallas params, pytree registration
# ---------------------------------------------------------------------------


def cost_analysis(compiled) -> dict:
    """`Compiled.cost_analysis()` as a flat dict."""
    return dict(compiled.cost_analysis() or {})


def tpu_compiler_params(**kwargs) -> Any:
    """``pltpu.CompilerParams(**kwargs)``."""
    return pltpu.CompilerParams(**kwargs)


def register_dataclass(cls, data_fields: Sequence[str],
                       meta_fields: Sequence[str]):
    """Register ``cls`` as a pytree: data_fields are leaves (with
    GetAttrKey paths), meta_fields are static treedef aux data."""
    return jax.tree_util.register_dataclass(
        cls, list(data_fields), list(meta_fields))
