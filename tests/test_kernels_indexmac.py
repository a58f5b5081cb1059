"""Shape/dtype sweeps for the indexmac Pallas kernel vs the jnp oracle.

The kernel body executes in interpret mode on CPU (per task spec) — the
same body is what Mosaic compiles on a real TPU.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.sparsity import NMConfig, compress_nm, random_nm_matrix
from repro.kernels.indexmac.kernel import nm_spmm_pallas
from repro.kernels.indexmac.ops import nm_matmul_positional as nm_matmul
from repro.kernels.indexmac.ref import nm_matmul_ref

CFGS = [NMConfig(1, 2), NMConfig(1, 4), NMConfig(2, 4), NMConfig(2, 8),
        NMConfig(4, 8)]


def _mk(cfg, K, N, M, dtype, seed=0):
    w = random_nm_matrix(jax.random.PRNGKey(seed), (K, N), cfg, axis=0).astype(dtype)
    vals, idx = compress_nm(w, cfg, axis=0)
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (M, K)).astype(dtype)
    return x, w, vals, idx


@pytest.mark.parametrize("cfg", CFGS, ids=lambda c: c.tag)
@pytest.mark.parametrize(
    "shape",
    [(128, 128, 64), (256, 128, 8), (512, 384, 128), (1024, 256, 32)],
    ids=lambda s: "K%dN%dM%d" % s,
)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_kernel_matches_oracle(cfg, shape, dtype):
    K, N, M = shape
    x, w, vals, idx = _mk(cfg, K, N, M, dtype)
    y_ref = nm_matmul_ref(x, vals, idx, cfg, out_dtype=jnp.float32)
    y_k = nm_spmm_pallas(
        x, vals, idx, cfg=cfg,
        block_m=min(64, M), block_n=min(128, N), block_k=min(256, K),
        out_dtype=jnp.float32, interpret=True,
    )
    tol = 1e-4 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(y_k), np.asarray(y_ref), rtol=tol, atol=tol * 10
    )


@pytest.mark.parametrize("blocks", [(64, 128, 128), (128, 128, 512), (64, 384, 256)])
def test_kernel_block_shape_sweep(blocks):
    cfg = NMConfig(2, 4)
    K, N, M = 512, 384, 128
    x, w, vals, idx = _mk(cfg, K, N, M, jnp.float32)
    bm, bn, bk = blocks
    y_ref = nm_matmul_ref(x, vals, idx, cfg)
    y_k = nm_spmm_pallas(
        x, vals, idx, cfg=cfg, block_m=bm, block_n=bn, block_k=bk, interpret=True
    )
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_ref), rtol=1e-4, atol=1e-3)


def test_kernel_multi_k_accumulation():
    """k-grid > 1 exercises the VMEM scratch accumulation path."""
    cfg = NMConfig(2, 4)
    x, w, vals, idx = _mk(cfg, 2048, 128, 16, jnp.float32)
    y_ref = nm_matmul_ref(x, vals, idx, cfg)
    y_k = nm_spmm_pallas(
        x, vals, idx, cfg=cfg, block_m=16, block_n=128, block_k=256, interpret=True
    )
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_ref), rtol=1e-4, atol=1e-3)


def test_op_dispatch_and_grad():
    cfg = NMConfig(2, 4)
    x, w, vals, idx = _mk(cfg, 256, 128, 64, jnp.float32)

    y = nm_matmul(x, vals, idx, cfg)
    np.testing.assert_allclose(np.asarray(y), np.asarray(x @ w), rtol=1e-4, atol=1e-3)

    g_x, g_v = jax.grad(lambda x, v: jnp.sum(nm_matmul(x, v, idx, cfg) ** 2),
                        argnums=(0, 1))(x, vals)
    g_dx, g_dw = jax.grad(lambda x, w: jnp.sum((x @ w) ** 2), argnums=(0, 1))(x, w)
    np.testing.assert_allclose(np.asarray(g_x), np.asarray(g_dx), rtol=1e-4, atol=1e-3)
    grow = (np.arange(vals.shape[0]) // cfg.n)[:, None] * cfg.m + np.asarray(
        idx, dtype=np.int64
    )
    expect = np.take_along_axis(np.asarray(g_dw), grow, axis=0)
    np.testing.assert_allclose(np.asarray(g_v), expect, rtol=1e-4, atol=1e-3)


def test_op_falls_back_on_odd_shapes():
    """Non-tileable shapes must still produce correct results via the ref."""
    cfg = NMConfig(2, 4)
    x, w, vals, idx = _mk(cfg, 36, 20, 5, jnp.float32)
    y = nm_matmul(x, vals, idx, cfg)
    np.testing.assert_allclose(np.asarray(y), np.asarray(x @ w), rtol=1e-4, atol=1e-3)


def test_op_routes_patterns_the_kernel_cannot_expand_to_ref():
    """n must divide m for the kernels' slot expansion: 3:8 runs on the
    reference with the reason recorded, and the kernel itself refuses."""
    from repro.kernels import registry

    cfg = NMConfig(3, 8)
    x, w, vals, idx = _mk(cfg, 256, 128, 64, jnp.float32)
    y = nm_matmul(x, vals, idx, cfg)
    rec = registry.last_dispatch("nm_matmul")
    assert rec.impl == "reference" and "n to divide m" in rec.reason, rec
    np.testing.assert_allclose(np.asarray(y), np.asarray(x @ w), rtol=1e-4,
                               atol=1e-3)
    with pytest.raises(ValueError, match="n to divide m"):
        nm_spmm_pallas(x, vals, idx, cfg=cfg, interpret=True)


def test_kernel_rejects_bad_shapes():
    cfg = NMConfig(2, 4)
    x, w, vals, idx = _mk(cfg, 256, 128, 64, jnp.float32)
    with pytest.raises(ValueError):
        nm_spmm_pallas(x, vals[:-2], idx[:-2], cfg=cfg, interpret=True)
    with pytest.raises(ValueError):
        nm_spmm_pallas(x, vals, idx, cfg=cfg, block_k=100, interpret=True)
