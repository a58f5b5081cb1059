"""Skinny-M Pallas kernels for decode-shaped N:M sparse GEMMs.

The serving decode step issues M = slots (1-8) row GEMMs against every
projection — shapes where the prefill kernel's (mi, ni, ki) tiling is
all padding and XLA's dense reference wins by default. This family drops
the M tiling:

* grid is (ni, ki) — no M tiling; the whole padded x block, as its
  (m, 8, bk*n/m) expansion ``x_m``, pins in VMEM across the n sweep
  (the stationary operand).
* ``vals``/``idx`` stream exactly once per (n, k) block, in their
  logical layout, and never expand to a dense tile: the kernel
  multiplies in the compressed domain (below).
* the epilogue — dequant scales (int8 family), bias add, activation —
  runs on the f32 accumulator at writeback (see
  :mod:`repro.kernels.epilogue` for the composition contract), so a
  decode GEMM is one kernel launch end to end.

Compressed-domain product. Slot ``kc`` of a compressed column sits in
M-group ``kc // n`` and holds dense row ``(kc // n) * m + idx``. With
``x_m[j, r, kc] = x[r, (kc // n) * m + j]`` (:func:`expand_x`, built once
per call in XLA and split by K block, a few hundred KB at most),

    y = sum_j  x_m[j] @ where(idx == j, vals, 0)

Each masked tile keeps the compressed (bk*n/m, bn) shape: one compare
and one select per j, no repeat, roll or dense (bk, bn) operand — the
vindexmac analogue, an indexed read beside the multiply. The MXU sees
m dots of K*n/m rows each, n times a dense tile's volume, which an
8-row x leaves far from the MXU's limit.

The MXU operands are bf16 when x is bf16 and the values are bf16 or
int8 (each masked entry is one stored value or zero, exact in bf16),
else f32 (:func:`mxu_dtype`). Accumulation is f32 in VMEM scratch; on
the integer lattice the result is bit-exact against the reference
composition regardless of tiling.

A call with a ``name`` (``<target>_<projection>``) shows in a profile as
``nm_spmm_pallas_decode_<name>.N``; ``nm_spmm_pallas_decode`` stays the
prefix of every decode kernel's device op, named or not.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import compat
from repro.core.sparsity import NMConfig
from repro.kernels.epilogue import ACTIVATIONS
from repro.kernels.indexmac.kernel import check_pattern, named_kernel


def mxu_dtype(x_dtype, vals_dtype):
    """The dtype of the decode kernel's MXU operands: bf16 when x is
    bf16 and the stored values are bf16 or int8 (both exact in bf16),
    else f32."""
    exact = (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.int8))
    if (jnp.dtype(x_dtype) == jnp.bfloat16
            and jnp.dtype(vals_dtype) in exact):
        return jnp.dtype(jnp.bfloat16)
    return jnp.dtype(jnp.float32)


def expand_x(x, n: int, m: int, block_k: int):
    """``x_m[k, j, r, c] = x[r, (kc // n) * m + j]`` for the compressed row
    ``kc = k * bkc + c`` of K block k (``bkc = block_k * n/m``): for each
    in-group index j, the rows of x that compressed slot kc would read if
    its index were j, split by K block so that a kernel block is the
    array's whole minor (M, bkc) plane for every block_k.

    One gather by a constant column table: a repeat along the minor axis
    lowers on the TPU to relayouts of a 2-wide axis, costed by XLA at an
    order of magnitude more cycles than this gather at yi-9b's K."""
    mm, kk = x.shape
    bkc = block_k * n // m
    kc = np.arange(kk * n // m).reshape(kk // block_k, 1, bkc)
    cols = (kc // n) * m + np.arange(m)[None, :, None]  # (nk, m, bkc)
    return jnp.take(x, cols, axis=1).transpose(1, 2, 0, 3)  # (nk, m, M, bkc)


def _writeback(acc, o_ref, scales_ref, bias_ref, *, activation, out_dtype):
    y = acc
    if scales_ref is not None:
        y = y * scales_ref[...]
    if bias_ref is not None:
        y = y + bias_ref[...]
    if activation is not None:
        y = ACTIVATIONS[activation](y)
    o_ref[...] = y.astype(out_dtype)


def _decode_kernel(xm_ref, vals_ref, idx_ref, *rest, m, nk, mxu, out_dtype,
                   activation, quantized, has_bias):
    refs = list(rest)
    scales_ref = refs.pop(0) if quantized else None
    bias_ref = refs.pop(0) if has_bias else None
    o_ref, acc_ref = refs
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    v = vals_ref[...].astype(jnp.float32)  # (bkc, bn)
    ii = idx_ref[...].astype(jnp.int32)  # Mosaic compares no int8
    acc = acc_ref[...]
    for j in range(m):
        w_j = jnp.where(ii == j, v, 0.0).astype(mxu)
        acc += jax.lax.dot(xm_ref[j].astype(mxu), w_j,
                           preferred_element_type=jnp.float32)
    acc_ref[...] = acc

    @pl.when(ki == nk - 1)
    def _done():
        _writeback(acc_ref[...], o_ref, scales_ref, bias_ref,
                   activation=activation, out_dtype=out_dtype)


@functools.partial(
    jax.jit,
    static_argnames=("cfg", "block_n", "block_k", "activation", "out_dtype",
                     "interpret", "name"),
)
def nm_spmm_pallas_decode(
    x: jax.Array,
    vals: jax.Array,
    idx: jax.Array,
    bias: Optional[jax.Array] = None,
    *,
    cfg: NMConfig,
    block_n: int = 256,
    block_k: int = 1024,
    activation: Optional[str] = None,
    out_dtype=None,
    interpret: bool = False,
    name: Optional[str] = None,
) -> jax.Array:
    """y = epilogue(x @ decompress(vals, idx)) for skinny x, multiplied
    in the compressed domain.

    Shape requirements (enforced): M a sublane multiple (the op layer
    pads 1..8 rows up to 8), N % block_n == 0, K % block_k == 0,
    block_k % m == 0; ``bias`` is (N,) when given.
    """
    run = functools.partial(_pallas_decode, cfg=cfg, block_n=block_n,
                            block_k=block_k, activation=activation,
                            out_dtype=out_dtype, interpret=interpret)
    return named_kernel(run, "nm_spmm_pallas_decode", name)(
        x, vals, idx, None, bias)


@functools.partial(
    jax.jit,
    static_argnames=("cfg", "block_n", "block_k", "activation", "out_dtype",
                     "interpret", "name"),
)
def nm_spmm_pallas_decode_q(
    x: jax.Array,
    vals: jax.Array,
    idx: jax.Array,
    scales: jax.Array,
    bias: Optional[jax.Array] = None,
    *,
    cfg: NMConfig,
    block_n: int = 256,
    block_k: int = 1024,
    activation: Optional[str] = None,
    out_dtype=None,
    interpret: bool = False,
    name: Optional[str] = None,
) -> jax.Array:
    """int8 decode sibling: one byte per kept value streams once, the
    per-output-channel ``scales`` multiply the f32 accumulator before
    the bias/activation epilogue — one launch from int8 payload to
    activated output."""
    if vals.dtype != jnp.int8:
        raise ValueError(f"quantized kernel needs int8 vals, got {vals.dtype}")
    if scales.shape != (vals.shape[1],):
        raise ValueError(
            f"scales shape {scales.shape} != (N,) = ({vals.shape[1]},)")
    run = functools.partial(_pallas_decode, cfg=cfg, block_n=block_n,
                            block_k=block_k, activation=activation,
                            out_dtype=out_dtype, interpret=interpret)
    return named_kernel(run, "nm_spmm_pallas_decode_q", name)(
        x, vals, idx, scales, bias)


def _pallas_decode(x, vals, idx, scales, bias, *, cfg, block_n, block_k,
                   activation, out_dtype, interpret):
    mm, kk = x.shape
    kc, nn = vals.shape
    if kc * cfg.m != kk * cfg.n:
        raise ValueError(f"vals rows {kc} inconsistent with K={kk} and {cfg.tag}")
    if idx.shape != vals.shape:
        raise ValueError("idx/vals shape mismatch")
    check_pattern(cfg)
    if mm % 8:
        raise ValueError(f"decode kernel needs M a sublane multiple, got {mm}")
    if activation is not None and activation not in ACTIVATIONS:
        raise ValueError(f"unknown activation {activation!r}")
    block_k = min(block_k, kk)
    block_n = min(block_n, nn)
    if kk % block_k or block_k % cfg.m:
        raise ValueError(f"K={kk} block_k={block_k} m={cfg.m} not tileable")
    if nn % block_n:
        raise ValueError(f"N={nn} not divisible by block_n={block_n}")
    if bias is not None and bias.shape != (nn,):
        raise ValueError(f"bias shape {bias.shape} != (N,) = ({nn},)")
    out_dtype = out_dtype or x.dtype
    nk = kk // block_k
    bkc = block_k * cfg.n // cfg.m

    quantized = scales is not None
    has_bias = bias is not None
    grid = (nn // block_n, nk)
    # the whole (skinny) x_m block is index (k, 0, 0, 0): resident across
    # the n sweep — the stationary operand of the decode dataflow.
    in_specs = [
        pl.BlockSpec((None, cfg.m, mm, bkc), lambda j, k: (k, 0, 0, 0)),
        pl.BlockSpec((bkc, block_n), lambda j, k: (k, j)),
        pl.BlockSpec((bkc, block_n), lambda j, k: (k, j)),
    ]
    operands = [expand_x(x, cfg.n, cfg.m, block_k), vals, idx]
    if quantized:
        in_specs.append(pl.BlockSpec((1, block_n), lambda j, k: (0, j)))
        operands.append(scales.astype(jnp.float32).reshape(1, nn))
    if has_bias:
        in_specs.append(pl.BlockSpec((1, block_n), lambda j, k: (0, j)))
        operands.append(bias.astype(jnp.float32).reshape(1, nn))

    kernel = functools.partial(
        _decode_kernel, m=cfg.m, nk=nk, mxu=mxu_dtype(x.dtype, vals.dtype),
        out_dtype=out_dtype, activation=activation, quantized=quantized,
        has_bias=has_bias,
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((mm, block_n), lambda j, k: (0, j)),
        out_shape=jax.ShapeDtypeStruct((mm, nn), out_dtype),
        scratch_shapes=[pltpu.VMEM((mm, block_n), jnp.float32)],
        compiler_params=compat.tpu_compiler_params(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
    )(*operands)
