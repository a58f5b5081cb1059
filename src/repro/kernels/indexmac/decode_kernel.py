"""Skinny-M Pallas kernels for decode-shaped N:M sparse GEMMs.

The serving decode step issues M = slots (1-8) row GEMMs against every
projection — shapes where the prefill kernel's (mi, ni, ki) tiling is
all padding and XLA's dense reference wins by default. This family drops
the M tiling:

* grid is (ni, ki) — no M tiling; the whole padded x block (8, bk)
  pins in VMEM across the entire sweep (the stationary operand).
* ``vals``/``idx`` stream exactly once per (n, k) block, in their
  logical layout, and expand in-register to a dense (bk, bn) tile with
  the prefill kernel's repeat, roll and select (the vindexmac analogue: a local, bounded
  select, never an HBM gather) before one (8, bk) x (bk, bn) dot.
* the epilogue — dequant scales (int8 family), bias add, activation —
  runs on the f32 accumulator at writeback (see
  :mod:`repro.kernels.epilogue` for the composition contract), so a
  decode GEMM is one kernel launch end to end.

Accumulation is f32 in VMEM scratch; on the integer lattice the result
is bit-exact against the reference composition regardless of tiling.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import compat
from repro.core.sparsity import NMConfig
from repro.kernels.epilogue import ACTIVATIONS
from repro.kernels.indexmac.kernel import check_pattern, decompress_block


def _writeback(acc, o_ref, scales_ref, bias_ref, *, activation, out_dtype):
    y = acc
    if scales_ref is not None:
        y = y * scales_ref[...]
    if bias_ref is not None:
        y = y + bias_ref[...]
    if activation is not None:
        y = ACTIVATIONS[activation](y)
    o_ref[...] = y.astype(out_dtype)


def _decode_kernel(x_ref, vals_ref, idx_ref, *rest, n, m, nk, out_dtype,
                   activation, quantized, has_bias):
    refs = list(rest)
    scales_ref = refs.pop(0) if quantized else None
    bias_ref = refs.pop(0) if has_bias else None
    o_ref, acc_ref = refs
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w = decompress_block(vals_ref[...], idx_ref[...], n, m)  # (bk, bn) f32
    x = x_ref[...].astype(jnp.float32)
    acc_ref[...] += jax.lax.dot(x, w, preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _done():
        _writeback(acc_ref[...], o_ref, scales_ref, bias_ref,
                   activation=activation, out_dtype=out_dtype)


@functools.partial(
    jax.jit,
    static_argnames=("cfg", "block_n", "block_k", "activation", "out_dtype",
                     "interpret"),
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
) -> jax.Array:
    """y = epilogue(x @ decompress(vals, idx)) for skinny x.

    Shape requirements (enforced): M a sublane multiple (the op layer
    pads 1..8 rows up to 8), N % block_n == 0, K % block_k == 0,
    block_k % m == 0; ``bias`` is (N,) when given.
    """
    return _pallas_decode(x, vals, idx, None, bias, cfg=cfg,
                          block_n=block_n, block_k=block_k,
                          activation=activation, out_dtype=out_dtype,
                          interpret=interpret)


@functools.partial(
    jax.jit,
    static_argnames=("cfg", "block_n", "block_k", "activation", "out_dtype",
                     "interpret"),
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
    return _pallas_decode(x, vals, idx, scales, bias, cfg=cfg,
                          block_n=block_n, block_k=block_k,
                          activation=activation, out_dtype=out_dtype,
                          interpret=interpret)


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
    # the whole (skinny) x block is index (0, k): resident across the n
    # sweep — the stationary operand of the decode dataflow.
    in_specs = [
        pl.BlockSpec((mm, block_k), lambda j, k: (0, k)),
        pl.BlockSpec((bkc, block_n), lambda j, k: (k, j)),
        pl.BlockSpec((bkc, block_n), lambda j, k: (k, j)),
    ]
    operands = [x, vals, idx]
    if quantized:
        in_specs.append(pl.BlockSpec((1, block_n), lambda j, k: (0, j)))
        operands.append(scales.astype(jnp.float32).reshape(1, nn))
    if has_bias:
        in_specs.append(pl.BlockSpec((1, block_n), lambda j, k: (0, j)))
        operands.append(bias.astype(jnp.float32).reshape(1, nn))

    kernel = functools.partial(
        _decode_kernel, n=cfg.n, m=cfg.m, nk=nk, out_dtype=out_dtype,
        activation=activation, quantized=quantized, has_bias=has_bias,
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
