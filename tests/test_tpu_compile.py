"""Compile the serve path's kernels and one decode step for a described
TPU v5e chip, at yi-9b's published widths, without a chip attached.

The TPU compiler is installed with jaxlib, and it refuses here what the
chip would refuse (a slice Mosaic cannot lower, a block over the VMEM
budget, a program over HBM). Nothing runs, so nothing here says anything
about results or speed. The topology is described only inside a fixture,
so collecting this file never loads the TPU library.
"""
import dataclasses

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


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _weight(k, n, kind, sharding):
    nm = NMConfig(2, 4)
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
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    if rec.padded == rec.shape:
        # the kernel reads the compressed pair in its stored layout: no
        # per-call relayout of the weight on the memory-bound path
        assert not any(op in text for op in ("transpose(", "copy(",
                                             "copy-start(", "pad(")), text


def test_decode_step_compiles_for_v5e(one_chip, chip_compile):
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
    recs = registry.dispatch_history()
    assert len(recs) == 7 and all(
        r.op == "nm_matmul_decode" and r.impl == "pallas_decode"
        and not r.interpret for r in recs), recs
    assert compiled.as_text().count("tpu_custom_call") >= 7
    # one layer's compressed weights plus embedding and head fit a chip
    assert compiled.memory_analysis().argument_size_in_bytes < 16e9


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
