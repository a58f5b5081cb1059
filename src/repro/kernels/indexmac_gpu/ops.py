"""Registry registrations for the GPU lowering of the indexmac families.

Importing this module (done by :mod:`repro.kernels` at package import)
registers the Pallas-on-Triton implementations under ``backend="gpu"``
in the SAME dispatch families as the TPU lowering — ``nm_matmul``,
``nm_matmul_q``, ``nm_matmul_decode``, ``nm_matmul_decode_q``,
``indexmac_gather``, ``indexmac_gather_q`` — with impl names prefixed
``pallas_gpu``. The registry's backend filter (see
:mod:`repro.kernels.registry`) picks the lowering; everything else
(family routing by M, pad plans, waste limits, epilogue composition,
autotune block lookup) is shared with the TPU path byte for byte:

* the routing predicates are literally the TPU module's
  ``_pallas_supports`` / ``_decode_supports`` — a shape that kernels on
  TPU kernels on GPU, and the fallback reasons read identically;
* the pad/slice wrappers reuse :class:`repro.kernels.padding.PadPlan`
  (its sublane/lane granularity is TPU-motivated but GPU-legal, and
  keeping one geometry means one autotune cache schema and bit-exact
  parity fixtures across backends).

The ``name`` the op layer passes (a kernel label for TPU profiles) is
accepted and not used here: the GPU kernels keep their family names.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.sparsity import NMConfig
from repro.kernels import registry
from repro.kernels.backend import interpret_for
from repro.kernels.indexmac.ops import _decode_supports, _pallas_supports
from repro.kernels.indexmac_gather.ops import (
    _pallas_supports as _gather_supports,
)
from repro.kernels.indexmac_gpu.decode_kernel import (
    nm_spmm_gpu_decode,
    nm_spmm_gpu_decode_q,
)
from repro.kernels.indexmac_gpu.gather_kernel import (
    indexmac_gather_gpu,
    indexmac_gather_gpu_q,
)
from repro.kernels.indexmac_gpu.kernel import nm_spmm_gpu, nm_spmm_gpu_q
from repro.kernels.padding import PadPlan, pad_nm_operands


# ---------------------------------------------------------------------------
# prefill-shaped family
# ---------------------------------------------------------------------------


def run_gpu_padded(
    x2: jax.Array,
    vals: jax.Array,
    idx: jax.Array,
    *,
    cfg: NMConfig,
    plan: PadPlan,
    interpret: bool,
) -> jax.Array:
    """Pad operands to the plan, run the GPU kernel, slice the logical
    output — the GPU twin of ``indexmac.ops.run_pallas_padded``."""
    xp, vp, ip = pad_nm_operands(x2, vals, idx, plan, cfg)
    bm, bn, bk = plan.block
    y = nm_spmm_gpu(
        xp, vp, ip, cfg=cfg, block_m=bm, block_n=bn, block_k=bk,
        interpret=interpret,
    )
    return y[: plan.m, : plan.n]


@registry.register("nm_matmul", "pallas_gpu", priority=100,
                   supports=_pallas_supports, uses_plan=True,
                   backend="gpu")
def _run_gpu_impl(x2, vals, idx, *, cfg, plan, interpret, name=None):
    return run_gpu_padded(
        x2, vals, idx, cfg=cfg, plan=plan, interpret=interpret
    )


def run_gpu_padded_q(
    x2: jax.Array,
    vals: jax.Array,
    idx: jax.Array,
    scales: jax.Array,
    *,
    cfg: NMConfig,
    plan: PadPlan,
    interpret: bool,
) -> jax.Array:
    """Quantized sibling: appended columns get unit scales (sliced away)."""
    xp, vp, ip = pad_nm_operands(x2, vals, idx, plan, cfg)
    sp = scales
    if plan.pn > plan.n:
        sp = jnp.pad(scales, (0, plan.pn - plan.n), constant_values=1.0)
    bm, bn, bk = plan.block
    y = nm_spmm_gpu_q(
        xp, vp, ip, sp, cfg=cfg, block_m=bm, block_n=bn, block_k=bk,
        interpret=interpret,
    )
    return y[: plan.m, : plan.n]


@registry.register("nm_matmul_q", "pallas_gpu_q", priority=100,
                   supports=_pallas_supports, uses_plan=True,
                   backend="gpu")
def _run_gpu_q_impl(x2, vals, idx, scales, *, cfg, plan, interpret,
                    name=None):
    return run_gpu_padded_q(
        x2, vals, idx, scales, cfg=cfg, plan=plan, interpret=interpret
    )


# ---------------------------------------------------------------------------
# decode-shaped families (fused epilogue)
# ---------------------------------------------------------------------------


def run_gpu_decode(
    x2: jax.Array,
    vals: jax.Array,
    idx: jax.Array,
    bias: Optional[jax.Array],
    *,
    cfg: NMConfig,
    plan: PadPlan,
    activation: Optional[str],
    interpret: bool,
) -> jax.Array:
    """Pad to the plan and run the fused GPU decode kernel. Padded bias
    columns are zero and every epilogue activation fixes 0, so the
    slice-back stays exact (same argument as the TPU wrapper)."""
    xp, vp, ip = pad_nm_operands(x2, vals, idx, plan, cfg)
    bp = bias
    if bias is not None and plan.pn > plan.n:
        bp = jnp.pad(bias, (0, plan.pn - plan.n))
    _, bn, bk = plan.block
    y = nm_spmm_gpu_decode(
        xp, vp, ip, bp, cfg=cfg, block_n=bn, block_k=bk,
        activation=activation, interpret=interpret,
    )
    return y[: plan.m, : plan.n]


@registry.register("nm_matmul_decode", "pallas_gpu_decode", priority=100,
                   supports=_decode_supports, uses_plan=True,
                   backend="gpu")
def _run_gpu_decode_impl(x2, vals, idx, bias, *, cfg, plan, activation,
                         interpret, name=None):
    return run_gpu_decode(
        x2, vals, idx, bias, cfg=cfg, plan=plan, activation=activation,
        interpret=interpret,
    )


def run_gpu_decode_q(
    x2: jax.Array,
    vals: jax.Array,
    idx: jax.Array,
    scales: jax.Array,
    bias: Optional[jax.Array],
    *,
    cfg: NMConfig,
    plan: PadPlan,
    activation: Optional[str],
    interpret: bool,
) -> jax.Array:
    """int8 decode sibling: padded columns get unit scales + zero bias."""
    xp, vp, ip = pad_nm_operands(x2, vals, idx, plan, cfg)
    sp, bp = scales, bias
    if plan.pn > plan.n:
        sp = jnp.pad(scales, (0, plan.pn - plan.n), constant_values=1.0)
        if bias is not None:
            bp = jnp.pad(bias, (0, plan.pn - plan.n))
    _, bn, bk = plan.block
    y = nm_spmm_gpu_decode_q(
        xp, vp, ip, sp, bp, cfg=cfg, block_n=bn, block_k=bk,
        activation=activation, interpret=interpret,
    )
    return y[: plan.m, : plan.n]


@registry.register("nm_matmul_decode_q", "pallas_gpu_decode_q", priority=100,
                   supports=_decode_supports, uses_plan=True,
                   backend="gpu")
def _run_gpu_decode_q_impl(x2, vals, idx, scales, bias, *, cfg, plan,
                           activation, interpret, name=None):
    return run_gpu_decode_q(
        x2, vals, idx, scales, bias, cfg=cfg, plan=plan,
        activation=activation, interpret=interpret,
    )


# ---------------------------------------------------------------------------
# gather-port families (no padding, same as the TPU port)
# ---------------------------------------------------------------------------


@registry.register("indexmac_gather", "pallas_gpu_gather", priority=100,
                   supports=_gather_supports, backend="gpu")
def _run_gpu_gather(vals, idx, b, *, cfg, block):
    bm, bn, bk = block
    return indexmac_gather_gpu(
        vals, idx, b, cfg=cfg, block_m=bm, block_n=bn, block_k=bk,
        interpret=interpret_for("gpu"),
    )


@registry.register("indexmac_gather_q", "pallas_gpu_gather_q", priority=100,
                   supports=_gather_supports, backend="gpu")
def _run_gpu_gather_q(vals, idx, scales, b, *, cfg, block):
    bm, bn, bk = block
    return indexmac_gather_gpu_q(
        vals, idx, scales, b, cfg=cfg, block_m=bm, block_n=bn, block_k=bk,
        interpret=interpret_for("gpu"),
    )
