"""Pallas TPU kernel for N:M structured-sparse matmul (the IndexMAC port).

Computes  y[M, N] = x[M, K] @ W  with W stored compressed along K:
  vals[Kc, N] (x dtype), idx[Kc, N] (int8 in [0, m)),  Kc = K * n / m.

TPU adaptation of the paper's vindexmac + B-stationary dataflow
(DESIGN.md §2/§4):

* The *dense* operand tile is pinned in VMEM: the grid is (mi, ni, ki) with
  k innermost; when the K dimension fits a single k-block (the common case
  for transformer projections, K <= 8k bf16), the x block index is constant
  across the whole n sweep, so Pallas's pipeline loads it once and keeps it
  resident — the paper's "pre-load tile of B in the register file".
* The compressed operand is streamed from HBM at (n/m)·(1 + 0.5) of the
  dense byte volume (values + int8 indices) — the eliminated memory traffic
  the paper measures in Fig. 6.
* The bounded indices are expanded *inside VMEM* into a dense tile via
  iota-compare selects (the indirect-register-read analogue: a local,
  bounded indexed operation, never an HBM gather) and handed to the MXU.
  The compressed pair is read in its logical layout, with no copy;
  :func:`decompress_block` separates the slots with sublane rolls.

Accumulation is fp32 in a VMEM scratch buffer, output written on the last
k step.

A call may carry a ``name`` (the model's ``<target>_<projection>``, e.g.
``ffn_w_up``): the kernel then runs under a jit of its own, and its
device op reads ``nm_spmm_pallas_<name>.N`` in a profile instead of the
bare family name (see :func:`named_kernel`).
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


# scoped VMEM for the prefill kernels: the dense tile's f32 temporaries
# at the default (bk, bn) = (2048, 256) take ~17 MiB, over Mosaic's
# 16 MiB default; 32 MiB fits in every TPU generation's VMEM
VMEM_LIMIT = 32 * 2**20


def named_kernel(run, family: str, name):
    """``run`` itself when ``name`` is None, else ``run`` under a jit
    called ``<family>_<name>``. XLA names a kernel's device op after the
    innermost jitted function around it, so a profile then reads
    ``<family>_<name>.N`` for this call, whatever the rest of the program
    holds; ``<family>`` stays a prefix of every name of the family."""
    if name is None:
        return run

    def call(*args):
        return run(*args)

    call.__name__ = call.__qualname__ = f"{family}_{name}"
    return jax.jit(call)


def check_pattern(cfg: NMConfig) -> None:
    """The kernels' one limit on the N:M pattern (see
    :func:`decompress_block`)."""
    if cfg.m % cfg.n:
        raise ValueError(f"{cfg.tag}: the kernels need n to divide m")


def decompress_block(v, ii, n: int, m: int):
    """Expand a compressed (bk*n/m, bn) block to dense (bk, bn) f32.

    The block is in the logical layout: the n kept values of M-group g
    sit in rows g*n .. g*n+n-1. Dense row d = g*m + j takes slot s where
    ``idx == j``: w[d, c] = sum_s v[g*n+s, c] * (ii[g*n+s, c] == j).

    Picking slot s of every group is a sublane-strided slice, which
    Mosaic does not lower. Instead each row is repeated q = m/n times, so
    slot s of group g lands on dense rows g*m + s*q .. g*m + s*q + q-1.
    A value repeated onto row r with ``idx == r%m - k*q`` belongs on
    row r - k*q, its own group's row for that idx (0 <= idx < m keeps it
    in the group), and exactly one repeat of each kept value meets that
    test for some k in (-n, n): one masked select and one sublane roll
    per k move every kept value home. Repeat, roll and iota-compare
    selects all lower. Needs n to divide m (the op layer routes other
    patterns to the reference).
    """
    bkc, bn = v.shape
    q = m // n
    bk = bkc * q
    j = jax.lax.broadcasted_iota(jnp.int32, (bk, bn), 0) % m
    v_rep = jnp.repeat(v.astype(jnp.float32), q, axis=0)  # (bk, bn)
    i_rep = jnp.repeat(ii.astype(jnp.int32), q, axis=0)
    w = jnp.zeros((bk, bn), dtype=jnp.float32)
    for k in range(1 - n, n):  # slot order at every dense row
        t = jnp.where(i_rep == j - k * q, v_rep, 0.0)
        w = w + (t if k == 0 else pltpu.roll(t, (-k * q) % bk, 0))
    return w


def _nm_spmm_kernel(x_ref, vals_ref, idx_ref, o_ref, acc_ref, *, n, m, nk, out_dtype):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w = decompress_block(vals_ref[...], idx_ref[...], n, m)  # (bk, bn) f32
    x = x_ref[...].astype(jnp.float32)
    acc_ref[...] += jax.lax.dot(
        x, w, preferred_element_type=jnp.float32
    )

    @pl.when(ki == nk - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(out_dtype)


def _nm_spmm_q_kernel(
    x_ref, vals_ref, idx_ref, scales_ref, o_ref, acc_ref, *, n, m, nk, out_dtype
):
    """int8-value variant: the compressed tile streams as one byte per
    kept value; dequantization happens in-register — the int8 block is
    expanded to a dense f32 tile inside VMEM (the int8 -> f32 cast rides
    the same iota-compare selects as the float path) and the per-output-
    channel scales multiply the f32 accumulator once at writeback, so
    the inner loop never touches a float weight operand."""
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # decompress_block casts the int8 values to f32 in-register: exact
    # (|q| <= 127 << 2^24), so the MXU sees the integer lattice scaled
    # only at the end.
    w = decompress_block(vals_ref[...], idx_ref[...], n, m)  # (bk, bn) f32
    x = x_ref[...].astype(jnp.float32)
    acc_ref[...] += jax.lax.dot(
        x, w, preferred_element_type=jnp.float32
    )

    @pl.when(ki == nk - 1)
    def _done():
        # scales: (1, bn) f32, one per output column — constant over K,
        # so one multiply per output element at writeback.
        o_ref[...] = (acc_ref[...] * scales_ref[...]).astype(out_dtype)


@functools.partial(
    jax.jit,
    static_argnames=("cfg", "block_m", "block_n", "block_k", "out_dtype",
                     "interpret", "name"),
)
def nm_spmm_pallas_q(
    x: jax.Array,
    vals: jax.Array,
    idx: jax.Array,
    scales: jax.Array,
    *,
    cfg: NMConfig,
    block_m: int = 256,
    block_n: int = 256,
    block_k: int = 2048,
    out_dtype=None,
    interpret: bool = False,
    name: Optional[str] = None,
) -> jax.Array:
    """y = (x @ decompress(int8 vals, idx)) * scales[col].

    Same tiling contract as :func:`nm_spmm_pallas`; additionally
    ``vals`` must be int8 and ``scales`` float32 of shape (N,).
    """
    run = functools.partial(
        _spmm_q, cfg=cfg, block_m=block_m, block_n=block_n, block_k=block_k,
        out_dtype=out_dtype, interpret=interpret)
    return named_kernel(run, "nm_spmm_pallas_q", name)(x, vals, idx, scales)


def _spmm_q(x, vals, idx, scales, *, cfg, block_m, block_n, block_k,
            out_dtype, interpret):
    mm, kk = x.shape
    kc, nn = vals.shape
    if kc * cfg.m != kk * cfg.n:
        raise ValueError(f"vals rows {kc} inconsistent with K={kk} and {cfg.tag}")
    if idx.shape != vals.shape:
        raise ValueError("idx/vals shape mismatch")
    check_pattern(cfg)
    if vals.dtype != jnp.int8:
        raise ValueError(f"quantized kernel needs int8 vals, got {vals.dtype}")
    if scales.shape != (nn,):
        raise ValueError(
            f"scales shape {scales.shape} != (N,) = ({nn},)")
    block_k = min(block_k, kk)
    block_m = min(block_m, mm)
    block_n = min(block_n, nn)
    if kk % block_k or block_k % cfg.m:
        raise ValueError(f"K={kk} block_k={block_k} m={cfg.m} not tileable")
    if mm % block_m or nn % block_n:
        raise ValueError(f"M={mm}/N={nn} not divisible by blocks {block_m}/{block_n}")
    out_dtype = out_dtype or x.dtype
    nk = kk // block_k
    bkc = block_k * cfg.n // cfg.m

    grid = (mm // block_m, nn // block_n, nk)
    kernel = functools.partial(
        _nm_spmm_q_kernel, n=cfg.n, m=cfg.m, nk=nk, out_dtype=out_dtype
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, k: (i, k)),
            pl.BlockSpec((bkc, block_n), lambda i, j, k: (k, j)),
            pl.BlockSpec((bkc, block_n), lambda i, j, k: (k, j)),
            # per-column scales: tiny, constant over the k sweep.
            pl.BlockSpec((1, block_n), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mm, nn), out_dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        compiler_params=compat.tpu_compiler_params(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT,
        ),
        interpret=interpret,
    )(x, vals, idx, scales.astype(jnp.float32).reshape(1, nn))


@functools.partial(
    jax.jit,
    static_argnames=("cfg", "block_m", "block_n", "block_k", "out_dtype",
                     "interpret", "name"),
)
def nm_spmm_pallas(
    x: jax.Array,
    vals: jax.Array,
    idx: jax.Array,
    *,
    cfg: NMConfig,
    block_m: int = 256,
    block_n: int = 256,
    block_k: int = 2048,
    out_dtype=None,
    interpret: bool = False,
    name: Optional[str] = None,
) -> jax.Array:
    """y = x @ decompress(vals, idx). See module docstring.

    Shape requirements (enforced): M % block_m == 0, N % block_n == 0,
    K % block_k == 0 (block_k clamped to K), block_k % m == 0.
    """
    run = functools.partial(
        _spmm, cfg=cfg, block_m=block_m, block_n=block_n, block_k=block_k,
        out_dtype=out_dtype, interpret=interpret)
    return named_kernel(run, "nm_spmm_pallas", name)(x, vals, idx)


def _spmm(x, vals, idx, *, cfg, block_m, block_n, block_k, out_dtype,
          interpret):
    mm, kk = x.shape
    kc, nn = vals.shape
    if kc * cfg.m != kk * cfg.n:
        raise ValueError(f"vals rows {kc} inconsistent with K={kk} and {cfg.tag}")
    if idx.shape != vals.shape:
        raise ValueError("idx/vals shape mismatch")
    check_pattern(cfg)
    block_k = min(block_k, kk)
    block_m = min(block_m, mm)
    block_n = min(block_n, nn)
    if kk % block_k or block_k % cfg.m:
        raise ValueError(f"K={kk} block_k={block_k} m={cfg.m} not tileable")
    if mm % block_m or nn % block_n:
        raise ValueError(f"M={mm}/N={nn} not divisible by blocks {block_m}/{block_n}")
    out_dtype = out_dtype or x.dtype
    nk = kk // block_k
    bkc = block_k * cfg.n // cfg.m

    grid = (mm // block_m, nn // block_n, nk)
    kernel = functools.partial(
        _nm_spmm_kernel, n=cfg.n, m=cfg.m, nk=nk, out_dtype=out_dtype
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            # dense operand: constant across the n sweep when nk == 1 -> the
            # pipeline keeps it VMEM-resident (paper's stationary tile).
            pl.BlockSpec((block_m, block_k), lambda i, j, k: (i, k)),
            pl.BlockSpec((bkc, block_n), lambda i, j, k: (k, j)),
            pl.BlockSpec((bkc, block_n), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mm, nn), out_dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        compiler_params=compat.tpu_compiler_params(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT,
        ),
        interpret=interpret,
    )(x, vals, idx)
