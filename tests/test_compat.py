"""Regression: the compat shim resolves on the installed JAX, and no
source file outside repro/compat.py touches the drifted names directly."""
import pathlib

import pytest

from repro import compat

SRC = pathlib.Path(__file__).resolve().parents[1] / "src"
BENCH = pathlib.Path(__file__).resolve().parents[1] / "benchmarks"

# Names whose home/spelling moved across JAX releases — only the shim may
# reference them.
DRIFTED = ("get_abstract_mesh", "TPUCompilerParams", "pltpu.CompilerParams",
           "jax.set_mesh", "use_mesh", "jax.shard_map", "check_rep")


def test_all_shims_resolved():
    for name in ("get_abstract_mesh", "active_mesh", "set_mesh",
                 "make_mesh", "manual_axis_in", "shard_map",
                 "cost_analysis", "tpu_compiler_params",
                 "register_dataclass"):
        assert callable(getattr(compat, name, None)), name


def test_mesh_context_roundtrip():
    assert compat.get_abstract_mesh() is None
    mesh = compat.make_mesh((1,), ("data",))
    with compat.set_mesh(mesh):
        active = compat.get_abstract_mesh()
        assert active is not None
        assert tuple(active.axis_names) == ("data",)
        assert dict(active.shape) == {"data": 1}
        assert not compat.manual_axis_in(active)
    assert compat.get_abstract_mesh() is None


def test_tpu_compiler_params_constructs():
    params = compat.tpu_compiler_params(
        dimension_semantics=("parallel", "parallel", "arbitrary")
    )
    assert params is not None
    assert tuple(params.dimension_semantics) == (
        "parallel", "parallel", "arbitrary"
    )


def test_unknown_param_fields_are_dropped():
    # one JAX line: an unknown (misspelled or stale) field is not
    # dropped in silence, it fails the caller
    with pytest.raises(TypeError, match="definitely_not_a_real_field_xyz"):
        compat.tpu_compiler_params(
            dimension_semantics=("parallel",),
            definitely_not_a_real_field_xyz=1,
        )


@pytest.mark.parametrize("root", [SRC, BENCH], ids=["src", "benchmarks"])
def test_no_drifted_api_outside_compat(root):
    offenders = []
    for path in root.rglob("*.py"):
        if path.name == "compat.py":
            continue
        text = path.read_text()
        for name in DRIFTED:
            if name in text:
                offenders.append(f"{path.relative_to(root)}: {name}")
    assert not offenders, (
        "version-drifted JAX APIs referenced outside repro/compat.py:\n"
        + "\n".join(offenders)
    )


def test_make_mesh_axes_are_auto():
    # JAX 0.9's make_mesh defaults to Explicit axes, under which the
    # sharding hints would assert instead of hint
    from jax.sharding import AxisType

    mesh = compat.make_mesh((1, 1), ("data", "model"))
    assert tuple(mesh.axis_types) == (AxisType.Auto, AxisType.Auto)
