"""GPU kernel family (Pallas-on-Triton lowering) + backend-axis tests.

Parity sweeps run the GPU kernel bodies in interpret mode against the
jnp oracles — the same bodies Triton compiles on a real GPU. The
routing tests opt the gpu backend in with ``REPRO_GPU_INTERPRET=1``
(per-test, via monkeypatch) and assert the dispatch layer routes,
reports and counts the backend exactly as a CUDA host would.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.core.nmweight import KernelPolicy, NMWeight
from repro.core.sparsity import NMConfig, compress_nm, random_nm_matrix
from repro.kernels import autotune, registry
from repro.kernels.backend import interpret_for, platform_backend, resolve_backend
from repro.kernels.indexmac.ref import nm_matmul_q_ref, nm_matmul_ref
from repro.kernels.indexmac_gather.ref import (
    indexmac_gather_q_ref,
    indexmac_gather_ref,
)
from repro.kernels.indexmac_gpu import (
    indexmac_gather_gpu,
    indexmac_gather_gpu_q,
    nm_spmm_gpu,
    nm_spmm_gpu_decode,
    nm_spmm_gpu_decode_q,
    nm_spmm_gpu_q,
)

CFGS = [NMConfig(1, 2), NMConfig(1, 4), NMConfig(2, 4)]


def _mk(cfg, K, N, M, dtype, seed=0):
    w = random_nm_matrix(jax.random.PRNGKey(seed), (K, N), cfg, axis=0).astype(dtype)
    vals, idx = compress_nm(w, cfg, axis=0)
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (M, K)).astype(dtype)
    return x, w, vals, idx


def _mk_int8(cfg, K, N, M, seed=0):
    """Integer-lattice operands: integer-valued f32 x, int8 vals — every
    partial sum is an exactly-representable integer (< 2^24), so the
    kernel must be *bit-exact* vs the reference regardless of tiling."""
    _, _, vals, idx = _mk(cfg, K, N, M, jnp.float32, seed)
    vals_q = jnp.clip(jnp.round(vals * 64.0), -127, 127).astype(jnp.int8)
    scales = (0.5 + jax.random.uniform(jax.random.PRNGKey(seed + 2), (N,))
              ).astype(jnp.float32)
    x = jnp.round(
        jax.random.normal(jax.random.PRNGKey(seed + 3), (M, K)) * 8.0)
    return x, vals_q, idx, scales


# ---------------------------------------------------------------------------
# kernel parity (interpret mode), all three GPU families
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("cfg", CFGS, ids=lambda c: c.tag)
@pytest.mark.parametrize(
    "shape", [(256, 128, 64), (512, 384, 128)], ids=lambda s: "K%dN%dM%d" % s
)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_gpu_prefill_matches_oracle(cfg, shape, dtype):
    K, N, M = shape
    x, w, vals, idx = _mk(cfg, K, N, M, dtype)
    y_ref = nm_matmul_ref(x, vals, idx, cfg, out_dtype=jnp.float32)
    y_k = nm_spmm_gpu(x, vals, idx, cfg=cfg, out_dtype=jnp.float32,
                      interpret=True)
    tol = 1e-4 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(y_k), np.asarray(y_ref), rtol=tol, atol=tol * 10
    )


def test_gpu_prefill_multi_k_chunks():
    """K > block_k exercises the in-kernel reduction loop (nk > 1)."""
    cfg = NMConfig(2, 4)
    x, w, vals, idx = _mk(cfg, 1024, 128, 32, jnp.float32)
    y_ref = nm_matmul_ref(x, vals, idx, cfg)
    y_k = nm_spmm_gpu(x, vals, idx, cfg=cfg, block_m=32, block_n=128,
                      block_k=256, interpret=True)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_ref),
                               rtol=1e-4, atol=1e-3)


@pytest.mark.parametrize("cfg", CFGS, ids=lambda c: c.tag)
def test_gpu_prefill_int8_bit_exact(cfg):
    K, N, M = 512, 128, 16
    x, vals_q, idx, scales = _mk_int8(cfg, K, N, M)
    y_ref = nm_matmul_q_ref(x, vals_q, idx, scales, cfg)
    y_k = nm_spmm_gpu_q(x, vals_q, idx, scales, cfg=cfg, block_k=256,
                        interpret=True)
    assert np.array_equal(np.asarray(y_k), np.asarray(y_ref))


def test_gpu_decode_matches_oracle_with_fused_epilogue():
    cfg = NMConfig(2, 4)
    K, N, M = 512, 256, 8
    x, w, vals, idx = _mk(cfg, K, N, M, jnp.float32)
    bias = jax.random.normal(jax.random.PRNGKey(7), (N,)).astype(jnp.float32)
    y_ref = jnp.maximum(nm_matmul_ref(x, vals, idx, cfg) + bias, 0.0)
    y_k = nm_spmm_gpu_decode(x, vals, idx, bias, cfg=cfg, block_n=128,
                             activation="relu", interpret=True)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_ref),
                               rtol=1e-4, atol=1e-3)


def test_gpu_decode_int8_bit_exact():
    cfg = NMConfig(2, 4)
    K, N, M = 512, 256, 8
    x, vals_q, idx, scales = _mk_int8(cfg, K, N, M)
    y_ref = nm_matmul_q_ref(x, vals_q, idx, scales, cfg)
    y_k = nm_spmm_gpu_decode_q(x, vals_q, idx, scales, None, cfg=cfg,
                               interpret=True)
    assert np.array_equal(np.asarray(y_k), np.asarray(y_ref))


@pytest.mark.parametrize("cfg", CFGS, ids=lambda c: c.tag)
def test_gpu_gather_matches_oracle(cfg):
    Mr, K, Nc = 32, 512, 128
    a = random_nm_matrix(jax.random.PRNGKey(0), (Mr, K), cfg, axis=1)
    vals, idx = compress_nm(a, cfg, axis=1)
    b = jax.random.normal(jax.random.PRNGKey(1), (K, Nc), dtype=jnp.float32)
    y_ref = indexmac_gather_ref(vals, idx, b, cfg)
    y_k = indexmac_gather_gpu(vals, idx, b, cfg=cfg, block_m=16,
                              interpret=True)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_ref),
                               rtol=1e-4, atol=1e-3)


def test_gpu_gather_int8_bit_exact():
    cfg = NMConfig(2, 4)
    Mr, K, Nc = 32, 512, 128
    a = random_nm_matrix(jax.random.PRNGKey(0), (Mr, K), cfg, axis=1)
    vals, idx = compress_nm(a, cfg, axis=1)
    vals_q = jnp.clip(jnp.round(vals * 64.0), -127, 127).astype(jnp.int8)
    scales = (0.5 + jax.random.uniform(jax.random.PRNGKey(2), (Mr,))
              ).astype(jnp.float32)
    b = jnp.round(jax.random.normal(jax.random.PRNGKey(1), (K, Nc)) * 8.0)
    y_ref = indexmac_gather_q_ref(vals_q, idx, scales, b, cfg)
    y_k = indexmac_gather_gpu_q(vals_q, idx, scales, b, cfg=cfg, block_m=16,
                                interpret=True)
    assert np.array_equal(np.asarray(y_k), np.asarray(y_ref))


def test_gpu_kernels_reject_bad_shapes():
    cfg = NMConfig(2, 4)
    x, w, vals, idx = _mk(cfg, 256, 128, 64, jnp.float32)
    with pytest.raises(ValueError):
        nm_spmm_gpu(x, vals[:-2], idx[:-2], cfg=cfg, interpret=True)
    with pytest.raises(ValueError):  # block_k % m != 0
        nm_spmm_gpu(x, vals, idx, cfg=cfg, block_k=100, interpret=True)
    with pytest.raises(ValueError):  # decode M must be a sublane multiple
        nm_spmm_gpu_decode(x[:5], vals, idx, cfg=cfg, interpret=True)
    with pytest.raises(ValueError):  # quantized kernel needs int8 vals
        nm_spmm_gpu_q(x, vals, idx, jnp.ones((128,)), cfg=cfg, interpret=True)


# ---------------------------------------------------------------------------
# backend resolution (no GPU host in CI — the error paths are the point)
# ---------------------------------------------------------------------------


def _gpu_native() -> bool:
    return jax.default_backend() == "gpu"


def test_resolve_backend_auto_follows_platform(monkeypatch):
    monkeypatch.delenv("REPRO_BACKEND", raising=False)
    assert resolve_backend(None) == platform_backend()
    assert resolve_backend("auto") == platform_backend()
    assert resolve_backend("tpu") == "tpu"  # interpreter keeps tpu runnable


def test_resolve_backend_env_override(monkeypatch):
    monkeypatch.setenv("REPRO_GPU_INTERPRET", "1")
    monkeypatch.setenv("REPRO_BACKEND", "gpu")
    assert resolve_backend(None) == "gpu"
    # an explicit call/policy value beats the env var
    assert resolve_backend("tpu") == "tpu"
    monkeypatch.setenv("REPRO_BACKEND", "bogus")
    with pytest.raises(ValueError, match="REPRO_BACKEND"):
        resolve_backend(None)
    with pytest.raises(ValueError):
        resolve_backend("cuda")


@pytest.mark.skipif(_gpu_native(), reason="host has a real GPU")
def test_forcing_gpu_without_opt_in_raises_typed_error(monkeypatch):
    monkeypatch.delenv("REPRO_GPU_INTERPRET", raising=False)
    monkeypatch.delenv("REPRO_BACKEND", raising=False)
    with pytest.raises(api.KernelForceError, match="gpu"):
        resolve_backend("gpu")
    cfg = NMConfig(2, 4)
    w = random_nm_matrix(jax.random.PRNGKey(0), (256, 128), cfg, axis=0)
    sw = api.sparsify(w, cfg,
                      kernel_policy=KernelPolicy("force", backend="gpu"))
    x = jnp.ones((16, 256), jnp.float32)
    with pytest.raises(api.KernelForceError, match="call/policy"):
        api.nm_matmul(x, sw)
    with pytest.raises(api.KernelForceError, match="call/policy"):
        api.explain_dispatch(x.shape, sw)
    # $REPRO_BACKEND names its own source in the error
    sw_auto = api.sparsify(w, cfg, kernel_policy="force")
    monkeypatch.setenv("REPRO_BACKEND", "gpu")
    with pytest.raises(api.KernelForceError, match=r"\$REPRO_BACKEND"):
        api.nm_matmul(x, sw_auto)


def test_interpret_for_tracks_platform(monkeypatch):
    assert interpret_for("tpu") == (jax.default_backend() != "tpu")
    assert interpret_for("gpu") == (jax.default_backend() != "gpu")


def test_positional_reference_route_runs_on_a_gpu_host(monkeypatch):
    """The positional surfaces run the TPU family only: on a GPU host
    their reference route (use_kernel=False) still computes, and only
    the kernel route raises the typed error."""
    from repro.kernels.indexmac.ops import (
        nm_matmul_positional,
        nm_matmul_q_positional,
    )

    cfg = NMConfig(2, 4)
    kw, kx = jax.random.split(jax.random.PRNGKey(3))
    sw = api.sparsify(jax.random.normal(kw, (32, 16)), cfg)
    qw = api.quantize(sw)
    x = jax.random.normal(kx, (4, 32))
    want = nm_matmul_positional(x, sw.vals, sw.idx, cfg, use_kernel=False)
    want_q = nm_matmul_q_positional(x, qw.vals, qw.idx, qw.scales, cfg,
                                    use_kernel=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    monkeypatch.delenv("REPRO_GPU_INTERPRET", raising=False)
    np.testing.assert_array_equal(
        nm_matmul_positional(x, sw.vals, sw.idx, cfg, use_kernel=False),
        want)
    np.testing.assert_array_equal(
        nm_matmul_q_positional(x, qw.vals, qw.idx, qw.scales, cfg,
                               use_kernel=False), want_q)
    with pytest.raises(api.KernelForceError, match="tpu"):
        nm_matmul_positional(x, sw.vals, sw.idx, cfg, use_kernel=True)


# ---------------------------------------------------------------------------
# dispatch routing end-to-end under the interpreter opt-in
# ---------------------------------------------------------------------------


@pytest.fixture
def gpu_interpret(monkeypatch):
    monkeypatch.setenv("REPRO_GPU_INTERPRET", "1")
    monkeypatch.delenv("REPRO_BACKEND", raising=False)
    registry.clear_history()
    yield
    registry.clear_history()


def test_policy_backend_routes_prefill_to_gpu(gpu_interpret):
    cfg = NMConfig(2, 4)
    w = random_nm_matrix(jax.random.PRNGKey(0), (512, 128), cfg, axis=0)
    sw = api.sparsify(w, cfg,
                      kernel_policy=KernelPolicy("force", backend="gpu"))
    x = jax.random.normal(jax.random.PRNGKey(1), (64, 512), jnp.float32)

    rec = api.explain_dispatch(x.shape, sw)
    assert rec.impl == "pallas_gpu" and rec.backend == "gpu"

    y = api.nm_matmul(x, sw)
    np.testing.assert_allclose(np.asarray(y), np.asarray(x @ api.densify(sw)),
                               rtol=1e-4, atol=1e-3)
    counts = registry.dispatch_counts(backend="gpu")
    assert counts[("nm_matmul", "pallas_gpu", "gpu")] >= 1


def test_call_arg_backend_overrides_auto_policy(gpu_interpret):
    cfg = NMConfig(2, 4)
    w = random_nm_matrix(jax.random.PRNGKey(0), (512, 128), cfg, axis=0)
    sw = api.sparsify(w, cfg, kernel_policy="force")  # backend stays auto
    x = jax.random.normal(jax.random.PRNGKey(1), (64, 512), jnp.float32)
    rec = api.explain_dispatch(x.shape, sw, backend="gpu")
    assert rec.impl == "pallas_gpu" and rec.backend == "gpu"
    y = api.nm_matmul(x, sw, backend="gpu")
    np.testing.assert_allclose(np.asarray(y), np.asarray(x @ api.densify(sw)),
                               rtol=1e-4, atol=1e-3)


def test_env_backend_routes_auto_policy(gpu_interpret, monkeypatch):
    monkeypatch.setenv("REPRO_BACKEND", "gpu")
    cfg = NMConfig(2, 4)
    w = random_nm_matrix(jax.random.PRNGKey(0), (512, 128), cfg, axis=0)
    sw = api.sparsify(w, cfg, kernel_policy="force")
    rec = api.explain_dispatch((64, 512), sw)
    assert rec.backend == "gpu"


def test_gpu_decode_route_and_quantized_families(gpu_interpret):
    cfg = NMConfig(2, 4)
    w = random_nm_matrix(jax.random.PRNGKey(0), (512, 256), cfg, axis=0)
    sw = api.sparsify(w, cfg,
                      kernel_policy=KernelPolicy("force", backend="gpu"))
    x1 = jax.random.normal(jax.random.PRNGKey(1), (1, 512), jnp.float32)
    rec = api.explain_dispatch(x1.shape, sw)
    assert rec.impl == "pallas_gpu_decode" and rec.backend == "gpu"
    y = api.nm_matmul(x1, sw)
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(x1 @ api.densify(sw)),
                               rtol=1e-4, atol=1e-3)

    qw = api.quantize(sw)
    qrec = api.explain_dispatch((64, 512), qw)
    assert qrec.impl == "pallas_gpu_q" and qrec.backend == "gpu"
    qrec1 = api.explain_dispatch(x1.shape, qw)
    assert qrec1.impl == "pallas_gpu_decode_q" and qrec1.backend == "gpu"


def test_gpu_gather_route(gpu_interpret):
    cfg = NMConfig(2, 4)
    a = random_nm_matrix(jax.random.PRNGKey(0), (32, 512), cfg, axis=1)
    vals, idx = compress_nm(a, cfg, axis=1)
    gw = NMWeight(vals=vals, idx=idx, nm=cfg, axis=1,
                  kernel_policy=KernelPolicy("force", backend="gpu"))
    b = jax.random.normal(jax.random.PRNGKey(1), (512, 128), jnp.float32)
    rec = api.explain_dispatch(b.shape, gw)
    assert rec.impl == "pallas_gpu_gather" and rec.backend == "gpu"
    y = api.indexmac_gather(gw, b)
    np.testing.assert_allclose(np.asarray(y), np.asarray(api.densify(gw) @ b),
                               rtol=1e-4, atol=1e-3)


def test_gpu_blocksparse_attention_route_and_parity(gpu_interpret):
    """backend="gpu" routes the bs_attention family to the output-tile
    gather kernel (interpret mode on this host — the same body Triton
    compiles on a real GPU) and matches the dense masked reference."""
    from repro.kernels.blocksparse_attn.mask import MaskSpec
    from repro.kernels.blocksparse_attn.ref import masked_reference

    spec = MaskSpec("local", block=16, window=24)
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(kq, (2, 64, 4, 16), jnp.float32)
    k = jax.random.normal(kk, (2, 64, 2, 16), jnp.float32)
    v = jax.random.normal(kv, (2, 64, 2, 16), jnp.float32)

    rec = api.explain_dispatch_attention(q.shape, k.shape, mask=spec,
                                         backend="gpu", tile=(16, 16))
    assert rec.impl == "gpu_bs_attention" and rec.backend == "gpu"
    y = api.attention(q, k, v, mask=spec, backend="gpu", tile=(16, 16))
    np.testing.assert_allclose(
        np.asarray(y), np.asarray(masked_reference(q, k, v, spec=spec)),
        rtol=1e-5, atol=2e-5)
    counts = registry.dispatch_counts(backend="gpu")
    assert counts[("bs_attention", "gpu_bs_attention", "gpu")] >= 1


@pytest.mark.skipif(_gpu_native(), reason="host has a real GPU")
def test_default_policy_still_routes_tpu_silently(monkeypatch):
    """Without the opt-in, gpu registrations are filtered *silently*:
    the default route keeps backend 'tpu' and an empty skip reason."""
    monkeypatch.delenv("REPRO_GPU_INTERPRET", raising=False)
    monkeypatch.delenv("REPRO_BACKEND", raising=False)
    cfg = NMConfig(2, 4)
    w = random_nm_matrix(jax.random.PRNGKey(0), (512, 128), cfg, axis=0)
    sw = api.sparsify(w, cfg, kernel_policy="force")
    rec = api.explain_dispatch((64, 512), sw)
    assert rec.backend == "tpu"
    assert rec.impl.startswith("pallas")
    assert rec.reason == ""


# ---------------------------------------------------------------------------
# autotune: backend-qualified keys + v1 -> v2 migration
# ---------------------------------------------------------------------------


@pytest.fixture
def tmp_cache(tmp_path, monkeypatch):
    path = tmp_path / "autotune.json"
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(path))
    autotune.clear_memory_cache()
    yield path
    autotune.clear_memory_cache()


def test_key_carries_kernel_backend():
    cfg = NMConfig(2, 4)
    k_tpu = autotune._key(64, 128, 512, cfg, jnp.float32, "cpu")
    k_gpu = autotune._key(64, 128, 512, cfg, jnp.float32, "cpu", "gpu")
    assert k_tpu == "v2|cpu|tpu|float32|2:4|64x512x128"
    assert k_gpu == "v2|cpu|gpu|float32|2:4|64x512x128"
    assert autotune._key(8, 128, 512, cfg, jnp.float32, "cpu", "gpu",
                         "decode").endswith("|decode")


def test_migrate_key_v1_to_v2():
    old = "v1|cpu|float32|2:4|64x512x128"
    assert autotune._migrate_key(old) == "v2|cpu|tpu|float32|2:4|64x512x128"
    # decode-family suffix survives
    assert autotune._migrate_key("v1|tpu|int8|2:4|8x512x128|decode") == \
        "v2|tpu|tpu|int8|2:4|8x512x128|decode"
    # non-v1 and malformed keys pass through untouched
    v2 = "v2|cpu|gpu|float32|2:4|64x512x128"
    assert autotune._migrate_key(v2) == v2
    assert autotune._migrate_key("v1|broken") == "v1|broken"


def test_legacy_cache_migrates_on_load(tmp_cache):
    cfg = NMConfig(2, 4)
    platform = jax.default_backend()
    tmp_cache.write_text(json.dumps({
        # legacy entry: pre-backend-axis schema, tpu family implied
        f"v1|{platform}|float32|2:4|64x512x128": [64, 128, 256],
        # legacy entry shadowed by a native v2 one for the same problem
        f"v1|{platform}|float32|2:4|8x512x128": [8, 128, 256],
        f"v2|{platform}|tpu|float32|2:4|8x512x128": [8, 256, 512],
    }))
    assert autotune.cached_block(64, 128, 512, cfg, jnp.float32) == \
        (64, 128, 256)
    # native v2 wins over the migrated legacy entry
    assert autotune.cached_block(8, 128, 512, cfg, jnp.float32) == \
        (8, 256, 512)
    # the migrated entry is tpu-family only: no gpu hit
    assert autotune.cached_block(64, 128, 512, cfg, jnp.float32,
                                 backend="gpu") is None


def test_gpu_defaults_and_candidates(tmp_cache):
    assert autotune.default_block(backend="gpu") == autotune.DEFAULT_GPU_BLOCK
    assert autotune.default_block("decode", "gpu") == \
        autotune.DEFAULT_GPU_DECODE_BLOCK
    assert autotune.best_block(64, 128, 512, NMConfig(2, 4), jnp.float32,
                               backend="gpu") == autotune.DEFAULT_GPU_BLOCK
    cands = autotune.candidate_blocks(64, 128, 512, NMConfig(2, 4),
                                      backend="gpu")
    assert cands and all(len(c) == 3 for c in cands)


# ---------------------------------------------------------------------------
# checkpoint manifests carry the policy backend
# ---------------------------------------------------------------------------


def test_checkpoint_policy_meta_roundtrip():
    from repro.training.checkpoint import _policy_meta, policy_from_meta

    pol = KernelPolicy("force", block=(64, 128, 512), backend="gpu")
    meta = _policy_meta(pol)
    assert meta["backend"] == "gpu"
    assert policy_from_meta(meta) == pol
    # manifests written before the backend axis restore as "auto"
    legacy = {"mode": "auto", "block": None, "decode_block": None}
    assert policy_from_meta(legacy).backend == "auto"
