"""Compile the serve path's kernels and one decode step for a described
TPU v5e chip, at yi-9b's published widths, without a chip attached.

The TPU compiler is installed with jaxlib, and it refuses here what the
chip would refuse (a slice Mosaic cannot lower, a block over the VMEM
budget, a program over HBM). Nothing runs, so nothing here says anything
about results or speed. The topology is described only inside a fixture,
so collecting this file never loads the TPU library.
"""
import dataclasses
import math
import os
import pathlib
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import api
from repro.configs import cut_layers, get_config
from repro.core.nmweight import KernelPolicy, NMWeight
from repro.core.sparsity import NMConfig
from repro.kernels import registry
from repro.models.transformer import LM
from repro.quant import QNMWeight
from repro.serving.engine import make_serve_steps


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def chip_compile(monkeypatch):
    """Steer the dispatch to what a chip host takes: compiled Pallas
    kernels and mixed-precision dots. The persistent cache is off: a
    described-topology compile cannot be read back without a chip."""
    import repro.kernels.indexmac.ops as ops
    from jax.experimental.compilation_cache import compilation_cache

    monkeypatch.setattr(ops, "interpret_for", lambda backend: False)
    monkeypatch.setenv("REPRO_MIXED_PRECISION_DOTS", "1")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)


_ONE_GRID_STEP = """
import jax, jax.numpy as jnp
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding
from repro.core.sparsity import NMConfig
from repro.kernels.indexmac.decode_kernel import nm_spmm_pallas_decode

dev = topologies.get_topology_desc(platform="tpu",
                                   topology_name="v5e:2x2").devices[0]
sds = lambda s, d: jax.ShapeDtypeStruct(s, d,
                                        sharding=SingleDeviceSharding(dev))
jax.jit(lambda x, v, i: nm_spmm_pallas_decode(
    x, v, i, cfg=NMConfig(2, 4), block_n=256, block_k=1024)).lower(
    sds((8, 1024), jnp.bfloat16), sds((512, 256), jnp.bfloat16),
    sds((512, 256), jnp.int8)).compile()
"""


def test_decode_grid_step_stays_small_for_v5e(tmp_path):
    """One (8, 256, 1024) 2:4 bf16 grid step of the decode kernel lowers
    to a few thousand LLO operations (the compressed-domain loop; a
    dense expansion of the tile took about 21,000). Counted in Mosaic's
    post-finalize-llo dump, in a subprocess so the dump flag reaches the
    TPU library when it loads. It is the file's first test: one process
    at a time may load that library, and the ``topo`` fixture keeps it
    loaded in this one from its first use on."""
    env = dict(os.environ)
    env["LIBTPU_INIT_ARGS"] = (env.get("LIBTPU_INIT_ARGS", "")
                               + f" --xla_mosaic_dump_to={tmp_path}")
    src = pathlib.Path(__file__).resolve().parents[1] / "src"
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (str(src), env.get("PYTHONPATH")) if p)
    done = subprocess.run([sys.executable, "-c", _ONE_GRID_STEP], env=env,
                          capture_output=True, text=True, timeout=600)
    dumps = sorted(tmp_path.glob("*_decode_kernel-post-finalize-llo.txt"))
    if not dumps:  # the compile itself is guarded by the tests below
        why = (done.stderr.strip().splitlines() or ["no error"])[-1]
        pytest.skip(f"no Mosaic LLO dump was written ({why[:300]})")
    text = dumps[-1].read_text()
    ops = len(re.findall(r'^\s*(?:%[^=\n]+= )?"?llo\.', text, re.M))
    assert 0 < ops < 5000, ops


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _weight(k, n, kind, sharding, nm=NMConfig(2, 4)):
    kc = k * nm.n // nm.m
    idx = _sds((kc, n), jnp.int8, sharding)
    pol = KernelPolicy("auto")
    if kind == "int8":
        return QNMWeight(vals=_sds((kc, n), jnp.int8, sharding), idx=idx,
                         scales=_sds((n,), jnp.float32, sharding), nm=nm,
                         axis=0, kernel_policy=pol)
    return NMWeight(vals=_sds((kc, n), jnp.bfloat16, sharding), idx=idx,
                    nm=nm, axis=0, kernel_policy=pol)


# yi-9b: M = 8 slots x 128 prompt tokens (prefill) or 8 slots (decode);
# (K, N) of w_up and w_down
@pytest.mark.parametrize("kind", ["bf16", "int8"])
@pytest.mark.parametrize("m", [1024, 8], ids=["prefill", "decode"])
@pytest.mark.parametrize("k,n", [(4096, 11008), (11008, 4096)],
                         ids=["w_up", "w_down"])
def test_kernel_compiles_for_v5e(one_chip, chip_compile, kind, m, k, n):
    w = _weight(k, n, kind, one_chip)
    x = _sds((m, k), jnp.bfloat16, one_chip)
    registry.clear_history()
    compiled = jax.jit(api.nm_matmul).lower(x, w).compile()
    rec = registry.last_dispatch()
    assert rec.impl.startswith("pallas") and not rec.interpret, rec
    assert ("decode" in rec.op) == (m == 8), rec
    assert rec.mxu_dtype == ("bfloat16" if m == 8 else None), rec
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    if rec.padded == rec.shape:
        # the kernel reads the compressed pair in its stored layout: no
        # per-call relayout of the weight on the memory-bound path. The
        # prefill program relayouts nothing; the decode program only x,
        # into its (m, M, K*n/m) expansion.
        limit = 0 if m > 8 else w.nm.n * m * k
        for line in text.splitlines():
            if any(op in line for op in ("transpose(", "copy(",
                                         "copy-start(", "pad(")):
                dims = re.search(r"= \(?\w+\[([\d,]*)\]", line).group(1)
                size = math.prod(int(d) for d in dims.split(",") if d)
                assert size <= limit, line


# the patterns beside 2:4 take the same compressed-domain loop
@pytest.mark.parametrize("nm", [NMConfig(1, 4), NMConfig(4, 8)],
                         ids=lambda c: c.tag)
@pytest.mark.parametrize("k,n", [(4096, 11008), (11008, 4096)],
                         ids=["w_up", "w_down"])
def test_decode_kernel_compiles_per_pattern(one_chip, chip_compile, nm, k, n):
    w = _weight(k, n, "bf16", one_chip, nm)
    x = _sds((8, k), jnp.bfloat16, one_chip)
    registry.clear_history()
    compiled = jax.jit(api.nm_matmul).lower(x, w).compile()
    rec = registry.last_dispatch()
    assert rec.impl == "pallas_decode" and not rec.interpret, rec
    assert rec.mxu_dtype == "bfloat16", rec
    assert "tpu_custom_call" in compiled.as_text()


def _compile_yi_decode_step(one_chip):
    """One yi-9b layer's decode step at published widths, compiled for
    the described chip with the dispatch records it made."""
    cfg = cut_layers(get_config("yi-9b"), 1)
    cfg = dataclasses.replace(cfg, sparsity=dataclasses.replace(
        cfg.sparsity, use_kernel=True))
    assert (cfg.d_model, cfg.vocab_size) == (4096, 64000)
    lm = LM(cfg)

    def place(tree):
        return jax.tree.map(
            lambda a: _sds(a.shape, a.dtype, one_chip), tree)

    params = place(jax.eval_shape(
        lambda: lm.init(jax.random.PRNGKey(0), param_dtype=jnp.bfloat16)))
    caches = place(jax.eval_shape(lambda: lm.init_cache(8, 160)))
    _, decode_step = make_serve_steps(lm)
    registry.clear_history()
    compiled = decode_step.lower(
        params, _sds((8, 1), jnp.int32, one_chip), caches,
        _sds((8,), jnp.int32, one_chip)).compile()
    return compiled, registry.dispatch_history()


def test_decode_step_compiles_for_v5e(one_chip, chip_compile):
    compiled, recs = _compile_yi_decode_step(one_chip)
    assert len(recs) == 7 and all(
        r.op == "nm_matmul_decode" and r.impl == "pallas_decode"
        and not r.interpret for r in recs), recs
    assert compiled.as_text().count("tpu_custom_call") >= 7
    # one layer's compressed weights plus embedding and head fit a chip
    assert compiled.memory_analysis().argument_size_in_bytes < 16e9


def test_decode_kernel_ops_are_named_per_projection(one_chip, chip_compile):
    """The chip's device ops carry each projection's name, and the
    family prefix covers every decode kernel and nothing else: what a
    profile of the step reads as ``nm_spmm_pallas_decode_<target>_<proj>.N``."""
    import re

    compiled, _ = _compile_yi_decode_step(one_chip)
    kernels = [line.split("=")[0].strip().removeprefix("ROOT ")
               for line in compiled.as_text().splitlines()
               if "tpu_custom_call" in line and "custom-call(" in line]
    named = sorted(re.sub(r"\.\d+$", "", k) for k in kernels)
    assert named == sorted(
        f"%nm_spmm_pallas_decode_{p}" for p in (
            "attn_proj_wq", "attn_proj_wk", "attn_proj_wv", "attn_proj_wo",
            "ffn_w_up", "ffn_w_gate", "ffn_w_down"))
    text = compiled.as_text()
    assert text.count("%nm_spmm_pallas_decode") == text.count(
        "%nm_spmm_pallas_decode_attn_proj_") + text.count(
        "%nm_spmm_pallas_decode_ffn_")


def test_layer_init_temporaries_fit_for_v5e(one_chip, chip_compile):
    """One yi-9b layer's init (dense f32 draws, N:M prune, compress)
    compiles for the chip with well under a GB of temporaries: the N:M
    block never sits on the minor axis, which the TPU pads to 128 lanes
    (a (..., blocks, 4) layout needed 6.7 GB here)."""
    from repro.models.transformer import group_init

    cfg = cut_layers(get_config("yi-9b"), 1)
    blk, rep = cfg.plan[0]
    compiled = jax.jit(group_init, static_argnums=(1, 2, 3, 4)).lower(
        _sds((2,), jnp.uint32, one_chip), blk, rep, cfg,
        jnp.bfloat16).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 1e9
