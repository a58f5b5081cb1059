"""Decode-shape parity suite for the skinny-M kernel family.

Every combination of M in {1, 2, 4, 7} x {f32, int8, bf16, int8 values
with bf16 x} x {2:4, 1:4, 4:8} x {epilogue off, bias + activation} must
be *bit-exact* against the reference composition
``activation(x @ densify(w) + bias)`` (with the dequant scales applied
before the bias for the int8 family), cast to x's dtype.

Bit-exactness is checked on the integer lattice: integer-valued
operands keep every f32 accumulation exact regardless of summation
order, and (for int8) power-of-two scales keep the scale multiply
exact, so kernel and reference must agree to the last bit — any
discrepancy is a real kernel bug, not float noise. (Arbitrary absmax
scales can differ by 1 ulp from the two-op reference when the backend
fuses the scale-multiply and bias-add into an FMA; the lattice tests
deliberately stay where both orderings are exact.)
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.core.sparsity import NMConfig, decompress_nm
from repro.kernels import registry
from repro.kernels.epilogue import apply_epilogue_f32
from repro.quant.qnmweight import QNMWeight

K, N = 128, 256
MS = (1, 2, 4, 7)
CFGS = (NMConfig(2, 4), NMConfig(1, 4), NMConfig(4, 8))
# family -> (x dtype, quantized values, the kernel's MXU operand dtype)
FAMILIES = {
    "f32": (jnp.float32, False, "float32"),
    "int8": (jnp.float32, True, "float32"),
    "bf16": (jnp.bfloat16, False, "bfloat16"),
    "int8_bf16x": (jnp.bfloat16, True, "bfloat16"),
}


def _int_operands(cfg: NMConfig, m_rows: int, seed: int = 0, k: int = K,
                  dtype=jnp.float32, policy="force"):
    """Integer-valued (x, weight, bias) on the exact-f32 lattice, x and
    the weight's values in ``dtype`` (small integers are exact in bf16)."""
    kw = jax.random.randint(jax.random.PRNGKey(seed), (k, N), -4, 5)
    sw = api.sparsify(kw.astype(dtype), cfg, kernel_policy=policy)
    x = jax.random.randint(
        jax.random.PRNGKey(seed + 1), (m_rows, k), -4, 5).astype(dtype)
    bias = jax.random.randint(
        jax.random.PRNGKey(seed + 2), (N,), -3, 4).astype(jnp.float32)
    return x, sw, bias


def _quantized(sw) -> QNMWeight:
    """int8 weight with power-of-two scales: every dequant multiply is
    exact, so the lattice parity stays bit-for-bit."""
    vals8 = jnp.clip(sw.vals, -127, 127).astype(jnp.int8)
    scales = jnp.full((N,), 0.25, jnp.float32)
    return QNMWeight(vals=vals8, idx=sw.idx, scales=scales, nm=sw.nm,
                     axis=0, kernel_policy=sw.kernel_policy)


def _reference(x, w, bias, activation):
    """activation(f32(x) @ f32(densify(w)) [* scales] + bias) — the
    composition contract every dispatch family implements."""
    if isinstance(w, QNMWeight):
        dense = decompress_nm(w.vals, w.idx, w.nm, axis=0).astype(jnp.float32)
        y32 = (x.astype(jnp.float32) @ dense) * w.scales[None, :]
    else:
        y32 = x.astype(jnp.float32) @ api.densify(w).astype(jnp.float32)
    return apply_epilogue_f32(y32, bias, activation).astype(x.dtype)


@pytest.mark.parametrize("cfg", CFGS, ids=lambda c: c.tag)
@pytest.mark.parametrize("m_rows", MS)
@pytest.mark.parametrize("family", list(FAMILIES))
@pytest.mark.parametrize("epilogue_on", [False, True],
                         ids=["plain", "fused"])
def test_decode_kernel_bit_exact(cfg, m_rows, family, epilogue_on):
    dtype, quantized, mxu = FAMILIES[family]
    x, sw, bias = _int_operands(cfg, m_rows, seed=m_rows, dtype=dtype)
    w = _quantized(sw) if quantized else sw
    if epilogue_on:
        ep = api.Epilogue(bias=bias, activation="silu")
        ref = _reference(x, w, bias, "silu")
    else:
        ep, ref = None, _reference(x, w, None, None)
    registry.clear_history()
    y = api.nm_matmul(x, w, epilogue=ep)
    rec = registry.last_dispatch()
    assert rec.op == ("nm_matmul_decode_q" if quantized
                      else "nm_matmul_decode")
    assert rec.impl.startswith("pallas"), rec
    assert rec.mxu_dtype == mxu, rec
    assert y.dtype == dtype
    np.testing.assert_array_equal(np.asarray(y), np.asarray(ref))


@pytest.mark.parametrize("cfg", CFGS, ids=lambda c: c.tag)
@pytest.mark.parametrize("family", list(FAMILIES))
def test_decode_kernel_bit_exact_over_k_blocks(cfg, family):
    """K = 2 x block_k: the accumulator carries across K blocks and the
    x_m block follows the k index."""
    dtype, quantized, _ = FAMILIES[family]
    block_k = 128
    policy = api.KernelPolicy("force", decode_block=(8, 128, block_k))
    x, sw, bias = _int_operands(cfg, 5, seed=43, k=2 * block_k,
                                dtype=dtype, policy=policy)
    w = _quantized(sw) if quantized else sw
    registry.clear_history()
    y = api.nm_matmul(x, w, epilogue=api.Epilogue(bias=bias,
                                                  activation="relu"))
    rec = registry.last_dispatch()
    assert rec.impl.startswith("pallas_decode"), rec
    assert rec.block[2] == block_k and rec.padded[1] == 2 * block_k, rec
    np.testing.assert_array_equal(
        np.asarray(y), np.asarray(_reference(x, w, bias, "relu")))


@pytest.mark.parametrize("block_k", [64, 32], ids=["nk1", "nk2"])
@pytest.mark.parametrize("cfg", CFGS + (NMConfig(1, 2), NMConfig(2, 8)),
                         ids=lambda c: c.tag)
def test_expand_x_matches_its_definition(cfg, block_k):
    """x_m[k, j, r, c] == x[r, (kc // n) * m + j], kc = k * bkc + c,
    element by element."""
    from repro.kernels.indexmac.decode_kernel import expand_x

    rows, k = 8, 64
    x = np.arange(rows * k, dtype=np.float32).reshape(rows, k)
    xm = np.asarray(expand_x(jnp.asarray(x), cfg.n, cfg.m, block_k))
    bkc = block_k * cfg.n // cfg.m
    assert xm.shape == (k // block_k, cfg.m, rows, bkc)
    want = np.empty_like(xm)
    for kb in range(k // block_k):
        for j in range(cfg.m):
            for r in range(rows):
                for c in range(bkc):
                    kc = kb * bkc + c
                    want[kb, j, r, c] = x[r, (kc // cfg.n) * cfg.m + j]
    np.testing.assert_array_equal(xm, want)


def test_explain_dispatch_reports_the_mxu_dtype():
    """A bf16 2:4 decode call takes bf16 MXU operands, an f32 one f32;
    the dry run and the real call's record agree, and routes that run no
    decode kernel report none."""
    cfg = NMConfig(2, 4)
    for dtype, mxu in ((jnp.bfloat16, "bfloat16"), (jnp.float32, "float32")):
        x, sw, _ = _int_operands(cfg, 8, seed=47, dtype=dtype)
        rec = api.explain_dispatch(x.shape, sw, dtype=dtype)
        assert (rec.op, rec.mxu_dtype) == ("nm_matmul_decode", mxu), rec
        registry.clear_history()
        api.nm_matmul(x, sw)
        assert registry.last_dispatch().mxu_dtype == mxu
    # f32 activations keep the f32 MXU path even for bf16 values
    _, sw16, _ = _int_operands(cfg, 8, seed=47, dtype=jnp.bfloat16)
    assert api.explain_dispatch((8, K), sw16,
                                dtype=jnp.float32).mxu_dtype == "float32"
    assert api.explain_dispatch((64, K), sw16).mxu_dtype is None
    off = dataclasses.replace(sw16, kernel_policy=api.KernelPolicy("off"))
    rec = api.explain_dispatch((8, K), off)
    assert (rec.impl, rec.mxu_dtype) == ("reference_decode", None), rec


@pytest.mark.parametrize("activation",
                         ["relu", "gelu", "silu", "relu_sq"])
def test_every_activation_bit_exact(activation):
    cfg = NMConfig(2, 4)
    x, sw, bias = _int_operands(cfg, 4, seed=17)
    y = api.nm_matmul(x, sw,
                      epilogue=api.Epilogue(bias=bias, activation=activation))
    ref = _reference(x, sw, bias, activation)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(ref))


def test_reference_decode_matches_kernel_composition():
    """Policy "off" still routes to the decode family (reference impl)
    and applies the identical composition — flipping use_kernel never
    changes the arithmetic on the lattice."""
    cfg = NMConfig(2, 4)
    x, sw, bias = _int_operands(cfg, 2, seed=23)
    ep = api.Epilogue(bias=bias, activation="relu")
    y_kernel = api.nm_matmul(x, sw, epilogue=ep)
    sw_off = dataclasses.replace(sw, kernel_policy=api.KernelPolicy("off"))
    registry.clear_history()
    y_ref = api.nm_matmul(x, sw_off, epilogue=ep)
    assert registry.last_dispatch().impl == "reference_decode"
    np.testing.assert_array_equal(np.asarray(y_kernel), np.asarray(y_ref))


def test_bias_only_and_activation_only():
    cfg = NMConfig(1, 4)
    x, sw, bias = _int_operands(cfg, 7, seed=29)
    y_b = api.nm_matmul(x, sw, epilogue=api.Epilogue(bias=bias))
    np.testing.assert_array_equal(
        np.asarray(y_b), np.asarray(_reference(x, sw, bias, None)))
    y_a = api.nm_matmul(x, sw, epilogue=api.Epilogue(activation="relu_sq"))
    np.testing.assert_array_equal(
        np.asarray(y_a), np.asarray(_reference(x, sw, None, "relu_sq")))


def test_leading_batch_dims_flatten_into_decode_m():
    cfg = NMConfig(2, 4)
    x, sw, _ = _int_operands(cfg, 6, seed=31)
    x3 = x.reshape(2, 3, K)
    registry.clear_history()
    y = api.nm_matmul(x3, sw)
    assert registry.last_dispatch().op == "nm_matmul_decode"
    np.testing.assert_array_equal(
        np.asarray(y), np.asarray(_reference(x, sw, None, None)).reshape(
            2, 3, N))


def test_decode_m_max_env_moves_the_threshold(monkeypatch):
    cfg = NMConfig(2, 4)
    x, sw, _ = _int_operands(cfg, 7, seed=37)
    monkeypatch.setenv("REPRO_DECODE_M_MAX", "4")
    assert api.explain_dispatch((7, K), sw).op == "nm_matmul"
    assert api.explain_dispatch((4, K), sw).op == "nm_matmul_decode"


def test_fused_epilogue_grads_flow():
    """The fused float path trains: grads reach x, vals and bias through
    the custom_vjp (reference-composition backward)."""
    cfg = NMConfig(2, 4)
    x, sw, bias = _int_operands(cfg, 2, seed=41)

    def loss(xv, vv, bv):
        w = dataclasses.replace(sw, vals=vv)
        y = api.nm_matmul(
            xv, w, epilogue=api.Epilogue(bias=bv, activation="silu"))
        return (y ** 2).sum()

    def ref_loss(xv, vv, bv):
        dense = decompress_nm(vv, sw.idx, cfg, axis=0).astype(jnp.float32)
        y = apply_epilogue_f32(xv.astype(jnp.float32) @ dense, bv, "silu")
        return (y ** 2).sum()

    dx, dv, db = jax.grad(loss, argnums=(0, 1, 2))(x, sw.vals, bias)
    rx, rv, rb = jax.grad(ref_loss, argnums=(0, 1, 2))(x, sw.vals, bias)
    assert dx.shape == x.shape and dv.shape == sw.vals.shape
    assert db.shape == bias.shape
    for g in (dx, dv, db):
        assert bool(jnp.isfinite(g).all()) and float(jnp.abs(g).max()) > 0
    np.testing.assert_allclose(np.asarray(dx), np.asarray(rx), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(dv), np.asarray(rv), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(db), np.asarray(rb), rtol=1e-5,
                               atol=1e-12)
