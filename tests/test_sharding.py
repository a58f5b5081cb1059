"""Distribution tests: sharding rules produce valid specs, and reduced
cells lower+compile on a multi-device mesh (single- and multi-pod axes).

Multi-device lowering runs in a subprocess because the placeholder device
count must be set before jax initializes (the rest of the suite runs on
one device).
"""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_reduced
from repro.models.transformer import LM
from repro.parallel.sharding import batch_pspec, cache_pspecs, param_pspecs

_SUBPROC = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax
from repro import compat
from repro.configs import get_reduced
from repro.configs.base import ShapeConfig
from repro.launch.specs import make_cell, lower_cell
from repro.roofline.analysis import collective_bytes_from_hlo, analyze

out = {}
for mesh_shape, axes in [((4, 2), ("data", "model")),
                         ((2, 2, 2), ("pod", "data", "model"))]:
    mesh = compat.make_mesh(mesh_shape, axes)
    for arch, sname, kind in [("yi-9b", "t", "train"),
                              ("deepseek-v2-lite-16b", "d", "decode"),
                              ("jamba-v0.1-52b", "p", "prefill")]:
        shape = ShapeConfig(sname, 64, 8, kind)
        cell = make_cell(arch, "train_4k", mesh,
                         cfg_override=get_reduced(arch),
                         shape_override=shape, microbatches=2)
        compiled = lower_cell(cell, mesh).compile()
        rep = analyze(cell.name, compiled, cell.chips, cell.model_flops)
        key = f"{arch}|{kind}|{len(mesh_shape)}d"
        out[key] = {"coll": rep.collective_bytes_per_chip,
                    "flops": rep.flops_per_chip}
print("RESULT" + json.dumps(out))
"""


@pytest.fixture(scope="module")
def subproc_results():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    proc = subprocess.run([sys.executable, "-c", _SUBPROC], env=env,
                          capture_output=True, text=True, timeout=1200)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT")][0]
    return json.loads(line[len("RESULT"):])


def test_multi_device_cells_compile(subproc_results):
    assert len(subproc_results) == 6
    for key, v in subproc_results.items():
        assert v["flops"] > 0, key


def test_multi_pod_axis_shards(subproc_results):
    """Multi-pod (3-axis) lowering emits collectives that the 2-axis mesh
    also has — and the train cell must all-reduce gradients across pods
    (strictly more collective traffic per chip than data-only)."""
    for arch in ("yi-9b",):
        two = subproc_results[f"{arch}|train|2d"]
        three = subproc_results[f"{arch}|train|3d"]
        assert three["coll"] > 0 and two["coll"] > 0


# ---------------------------------------------------------------------------
# spec-rule unit tests (single device, no lowering)
# ---------------------------------------------------------------------------


class _FakeMesh:
    axis_names = ("data", "model")
    class devices:  # noqa: D106
        shape = (4, 2)
        size = 8


def test_param_specs_respect_divisibility():
    cfg = get_reduced("yi-9b")
    lm = LM(cfg)
    params = jax.eval_shape(lambda: lm.init(jax.random.PRNGKey(0)))
    specs = param_pspecs(params, _FakeMesh, "fsdp")
    flat = jax.tree_util.tree_flatten_with_path(
        params, is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))[0]
    sflat = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    for (path, leaf), spec in zip(flat, sflat):
        assert len(spec) <= leaf.ndim
        for dim, s in zip(leaf.shape[-len(spec):] if spec else (), spec):
            if s is None:
                continue
            names = (s,) if isinstance(s, str) else s
            size = 1
            for a in names:
                size *= dict(zip(_FakeMesh.axis_names,
                                 _FakeMesh.devices.shape))[a]
            assert dim % size == 0, (path, leaf.shape, spec)


def test_tp_only_mode_drops_data_axis():
    cfg = get_reduced("yi-9b")
    lm = LM(cfg)
    params = jax.eval_shape(lambda: lm.init(jax.random.PRNGKey(0)))
    specs = param_pspecs(params, _FakeMesh, "tp_only")
    for spec in jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P)):
        assert "data" not in [a for s in spec for a in
                              ((s,) if isinstance(s, str) else (s or ()))]


def test_batch_pspec_divisibility_fallback():
    assert batch_pspec(8, _FakeMesh) == P(("data",), None)
    assert batch_pspec(3, _FakeMesh) == P(None, None)


def test_cache_specs_shard_sequence_over_model():
    cfg = get_reduced("yi-9b")
    lm = LM(cfg)
    caches = jax.eval_shape(lambda: lm.init_cache(8, 64, dtype=jnp.bfloat16))
    specs = cache_pspecs(caches, _FakeMesh, batch_axes=("data",))
    k_spec = specs[0][0]["k"]
    # stacked (L, B, S, H, D): batch over data, seq over model
    assert k_spec == P(None, "data", "model", None, None)
