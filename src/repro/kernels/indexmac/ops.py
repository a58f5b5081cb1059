"""The compressed-GEMM op layer: one typed entry point, four dispatch
families, fused epilogues.

``nm_matmul(x, w, *, epilogue=None)`` consumes an
:class:`repro.core.nmweight.NMWeight` or an int8
:class:`repro.quant.qnmweight.QNMWeight`: the weight's own ``NMConfig``
and :class:`KernelPolicy` drive dispatch — ``off`` pins the XLA
reference, ``auto`` takes a Pallas kernel when the shape normalizes
within the family's waste limit, ``force`` ignores the limit (and
*raises* :class:`repro.kernels.registry.KernelForceError` when no legal
kernel geometry exists, instead of silently serving reference timings).

Dispatch families (the registry selects by M-threshold, not by falling
back to reference):

  nm_matmul          float values, M > REPRO_DECODE_M_MAX (prefill /
                     training shapes; (mi, ni, ki)-tiled kernel)
  nm_matmul_q        int8 values, same shapes (dequantizing kernel)
  nm_matmul_decode   float values, M <= REPRO_DECODE_M_MAX (default 8):
                     the skinny-M kernel of
                     :mod:`repro.kernels.indexmac.decode_kernel`, with
                     the epilogue fused into the accumulator writeback
  nm_matmul_decode_q int8 decode sibling (scales fused too)

The :class:`repro.kernels.epilogue.Epilogue` spec (bias + activation
name) is honored on *every* path: decode kernels fuse it at writeback;
the non-decode families apply the identical f32 composition after the
GEMM; the reference implementations mirror it exactly — so parity is
bit-exact on the integer lattice across all eight implementations.

``explain_dispatch(x_shape, w)`` answers "which family/kernel/block/pad
plan *would* run" without executing anything — the public dry-run used
by benchmarks instead of sniffing the record history.

The positional surfaces are deprecated: they live only in
:mod:`repro.kernels.raw` and warn on use; the non-warning
``nm_matmul_positional`` / ``nm_matmul_q_positional`` internals remain
for kernel-level tests.

Training backward (both float families; padding never changes it — it
works on logical shapes, via the differentiable reference composition):

  y     = act(x @ W + bias),   W = decompress(vals, idx)
  dx    = (dy * act'(..)) @ W^T
  dvals = gather_{kept positions}(x^T @ (dy * act'(..)))
  dbias = sum over rows of (dy * act'(..))     (straight-through on idx)
"""
from __future__ import annotations

import functools
import math
import os
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.nmweight import NMWeight
from repro.core.sparsity import NMConfig, decompress_nm
from repro.kernels import autotune, registry
from repro.kernels.backend import interpret_for, resolve_backend
from repro.kernels.epilogue import apply_epilogue_f32, resolve_epilogue
from repro.kernels.indexmac.decode_kernel import (
    nm_spmm_pallas_decode,
    nm_spmm_pallas_decode_q,
)
from repro.kernels.indexmac.kernel import nm_spmm_pallas, nm_spmm_pallas_q
from repro.kernels.indexmac.ref import nm_matmul_q_ref, nm_matmul_ref
from repro.kernels.padding import (
    PadPlan,
    decode_pad_waste_limit,
    pad_nm_operands,
    pad_waste_limit,
    plan_nm_matmul,
)
from repro.quant.qnmweight import QNMWeight


def decode_m_max() -> int:
    """Largest M (flattened row count) routed to the decode families."""
    return int(os.environ.get("REPRO_DECODE_M_MAX", 8))


def _pin_compressed(vals, idx):
    if os.environ.get("REPRO_GATHER_COMPRESSED") == "1":
        # Pin the compressed operands to (None, "model") so the FSDP
        # all-gather over "data" moves the COMPRESSED bytes (vals+idx,
        # 0.375-0.75x dense) and decompression runs shard-locally — without
        # this, SPMD may decompress on the home shards and gather the
        # dense W (EXPERIMENTS.md §Perf P3).
        from repro.parallel.hints import shard_hint_leaves

        vals, idx = shard_hint_leaves((vals, idx), None, "model")
    return vals, idx


def _validate_pair(vals, idx, k, cfg):
    if vals.shape[0] * cfg.m != k * cfg.n:
        raise ValueError(
            f"vals rows {vals.shape[0]} inconsistent with K={k} and {cfg.tag}"
        )
    if idx.shape != vals.shape:
        raise ValueError("idx/vals shape mismatch")


# ---------------------------------------------------------------------------
# routing: (M, K, N) + policy -> dispatch family + pad plan
# ---------------------------------------------------------------------------


def _route(mm, nn, kk, cfg, dtype, use_kernel, force, block, decode_block,
           quantized, backend="tpu"):
    """Resolve the dispatch family, block triple and pad plan for one
    call — shared by the executing paths and :func:`explain_dispatch`,
    so the explanation can never drift from the real routing.
    ``backend`` is the *resolved* kernel backend (never "auto") — it
    selects which lowering family the registry may pick and which
    autotune cache namespace supplies the default block."""
    decode = mm <= decode_m_max()
    family = "decode" if decode else ""
    op = ("nm_matmul_decode" if decode else "nm_matmul") + (
        "_q" if quantized else "")
    key_dtype = jnp.int8 if quantized else dtype
    plan = None
    if use_kernel:  # skip block resolution (cache I/O, possible inline
        # sweep under REPRO_AUTOTUNE=1) when the kernel can't be taken
        blk = decode_block if decode else block
        if blk is None:
            blk = autotune.best_block(mm, nn, kk, cfg, key_dtype,
                                      family=family, backend=backend)
        plan = plan_nm_matmul(mm, nn, kk, cfg, tuple(blk))
        if plan is None and force:
            raise registry.KernelForceError(
                f"KernelPolicy('force') on a "
                f"{'QNMWeight' if quantized else 'NMWeight'} compressed "
                f"along axis 0 with pattern {cfg.tag}: shape "
                f"M={mm} K={kk} N={nn} does not normalize to any legal "
                f"kernel geometry, and force forbids the reference "
                f"fallback")
    ctx = registry.make_ctx(
        (mm, kk, nn), nm=cfg, use_kernel=use_kernel, plan=plan,
        dtype=key_dtype, force=force, backend=backend,
    )
    return op, plan, ctx


def _pallas_supports(ctx: dict) -> Optional[str]:
    if not ctx["use_kernel"]:
        return "use_kernel=False"
    plan = ctx["plan"]
    if plan is None:
        return "shape not normalizable"
    if ctx.get("force"):
        return None  # KernelPolicy "force": waste limit ignored
    limit = pad_waste_limit()
    if plan.waste > limit:
        return f"padding waste {plan.waste:.2f}x > limit {limit:.2f}x"
    return None


def _decode_supports(ctx: dict) -> Optional[str]:
    if not ctx["use_kernel"]:
        return "use_kernel=False"
    plan = ctx["plan"]
    if plan is None:
        return "shape not normalizable"
    if ctx.get("force"):
        return None
    limit = decode_pad_waste_limit()
    if plan.waste_nk > limit:
        return (f"N/K padding waste {plan.waste_nk:.2f}x > decode limit "
                f"{limit:.2f}x")
    return None


def _tpu(supports):
    """``supports`` plus the TPU kernels' one limit on the N:M pattern
    (see :func:`repro.kernels.indexmac.kernel.decompress_block`)."""
    def check(ctx: dict) -> Optional[str]:
        nm = ctx["cfg"]
        if ctx["use_kernel"] and nm.m % nm.n:
            return f"{nm.tag}: the TPU kernels need n to divide m"
        return supports(ctx)
    return check


# ---------------------------------------------------------------------------
# prefill-shaped family (M > decode_m_max): (mi, ni, ki)-tiled kernel
# ---------------------------------------------------------------------------


def run_pallas_padded(
    x2: jax.Array,
    vals: jax.Array,
    idx: jax.Array,
    *,
    cfg: NMConfig,
    plan: PadPlan,
    interpret: bool,
    name: Optional[str] = None,
) -> jax.Array:
    """Pad operands to the plan, run the kernel, slice the logical output."""
    xp, vp, ip = pad_nm_operands(x2, vals, idx, plan, cfg)
    bm, bn, bk = plan.block
    y = nm_spmm_pallas(
        xp, vp, ip, cfg=cfg, block_m=bm, block_n=bn, block_k=bk,
        interpret=interpret, name=name,
    )
    return y[: plan.m, : plan.n]


@registry.register("nm_matmul", "pallas_padded", priority=100,
                   supports=_tpu(_pallas_supports), uses_plan=True,
                   backend="tpu")
def _run_pallas_impl(x2, vals, idx, *, cfg, plan, interpret, name=None):
    return run_pallas_padded(
        x2, vals, idx, cfg=cfg, plan=plan, interpret=interpret, name=name
    )


@registry.register("nm_matmul", "reference", priority=0)
def _run_ref_impl(x2, vals, idx, *, cfg, plan, interpret, name=None):
    return nm_matmul_ref(x2, vals, idx, cfg)


def run_pallas_padded_q(
    x2: jax.Array,
    vals: jax.Array,
    idx: jax.Array,
    scales: jax.Array,
    *,
    cfg: NMConfig,
    plan: PadPlan,
    interpret: bool,
    name: Optional[str] = None,
) -> jax.Array:
    """Quantized sibling of :func:`run_pallas_padded`: pads the int8
    operands (appended columns get unit scales — they are sliced away)
    and runs the dequantizing kernel."""
    xp, vp, ip = pad_nm_operands(x2, vals, idx, plan, cfg)
    sp = scales
    if plan.pn > plan.n:
        sp = jnp.pad(scales, (0, plan.pn - plan.n), constant_values=1.0)
    bm, bn, bk = plan.block
    y = nm_spmm_pallas_q(
        xp, vp, ip, sp, cfg=cfg, block_m=bm, block_n=bn, block_k=bk,
        interpret=interpret, name=name,
    )
    return y[: plan.m, : plan.n]


@registry.register("nm_matmul_q", "pallas_padded_q", priority=100,
                   supports=_tpu(_pallas_supports), uses_plan=True,
                   backend="tpu")
def _run_pallas_q_impl(x2, vals, idx, scales, *, cfg, plan, interpret,
                       name=None):
    return run_pallas_padded_q(
        x2, vals, idx, scales, cfg=cfg, plan=plan, interpret=interpret,
        name=name,
    )


@registry.register("nm_matmul_q", "reference_q", priority=0)
def _run_ref_q_impl(x2, vals, idx, scales, *, cfg, plan, interpret,
                    name=None):
    return nm_matmul_q_ref(x2, vals, idx, scales, cfg)


# ---------------------------------------------------------------------------
# decode-shaped families (M <= decode_m_max): skinny-M kernel, fused epilogue
# ---------------------------------------------------------------------------


def run_pallas_decode(
    x2: jax.Array,
    vals: jax.Array,
    idx: jax.Array,
    bias: Optional[jax.Array],
    *,
    cfg: NMConfig,
    plan: PadPlan,
    activation: Optional[str],
    interpret: bool,
    name: Optional[str] = None,
) -> jax.Array:
    """Pad to the plan and run the fused decode kernel. Padded bias
    columns are zero and all epilogue activations fix 0 (act(0) == 0 for
    relu/gelu/silu/relu_sq), so the slice-back stays exact."""
    xp, vp, ip = pad_nm_operands(x2, vals, idx, plan, cfg)
    bp = bias
    if bias is not None and plan.pn > plan.n:
        bp = jnp.pad(bias, (0, plan.pn - plan.n))
    _, bn, bk = plan.block
    y = nm_spmm_pallas_decode(
        xp, vp, ip, bp, cfg=cfg, block_n=bn, block_k=bk,
        activation=activation, interpret=interpret, name=name,
    )
    return y[: plan.m, : plan.n]


@registry.register("nm_matmul_decode", "pallas_decode", priority=100,
                   supports=_tpu(_decode_supports), uses_plan=True,
                   backend="tpu")
def _run_pallas_decode_impl(x2, vals, idx, bias, *, cfg, plan, activation,
                            interpret, name=None):
    return run_pallas_decode(
        x2, vals, idx, bias, cfg=cfg, plan=plan, activation=activation,
        interpret=interpret, name=name,
    )


@registry.register("nm_matmul_decode", "reference_decode", priority=0)
def _run_ref_decode_impl(x2, vals, idx, bias, *, cfg, plan, activation,
                         interpret, name=None):
    w = decompress_nm(vals, idx, cfg, axis=0)
    y32 = jnp.dot(
        x2.astype(jnp.float32), w.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )
    return apply_epilogue_f32(y32, bias, activation).astype(x2.dtype)


def run_pallas_decode_q(
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
    name: Optional[str] = None,
) -> jax.Array:
    """int8 decode sibling: padded columns get unit scales + zero bias."""
    xp, vp, ip = pad_nm_operands(x2, vals, idx, plan, cfg)
    sp, bp = scales, bias
    if plan.pn > plan.n:
        sp = jnp.pad(scales, (0, plan.pn - plan.n), constant_values=1.0)
        if bias is not None:
            bp = jnp.pad(bias, (0, plan.pn - plan.n))
    _, bn, bk = plan.block
    y = nm_spmm_pallas_decode_q(
        xp, vp, ip, sp, bp, cfg=cfg, block_n=bn, block_k=bk,
        activation=activation, interpret=interpret, name=name,
    )
    return y[: plan.m, : plan.n]


@registry.register("nm_matmul_decode_q", "pallas_decode_q", priority=100,
                   supports=_tpu(_decode_supports), uses_plan=True,
                   backend="tpu")
def _run_pallas_decode_q_impl(x2, vals, idx, scales, bias, *, cfg, plan,
                              activation, interpret, name=None):
    return run_pallas_decode_q(
        x2, vals, idx, scales, bias, cfg=cfg, plan=plan,
        activation=activation, interpret=interpret, name=name,
    )


@registry.register("nm_matmul_decode_q", "reference_decode_q", priority=0)
def _run_ref_decode_q_impl(x2, vals, idx, scales, bias, *, cfg, plan,
                           activation, interpret, name=None):
    w8 = decompress_nm(vals, idx, cfg, axis=0)
    y32 = jnp.dot(
        x2.astype(jnp.float32), w8.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )
    y32 = y32 * scales.astype(jnp.float32)[None, :]
    return apply_epilogue_f32(y32, bias, activation).astype(x2.dtype)


# ---------------------------------------------------------------------------
# typed entry point
# ---------------------------------------------------------------------------


def _epilogue_after(y, bias, activation):
    """Non-decode paths apply the identical f32 composition after the
    GEMM (the decode kernels fuse it; same arithmetic either way)."""
    if bias is None and activation is None:
        return y
    return apply_epilogue_f32(
        y.astype(jnp.float32), bias, activation).astype(y.dtype)


def nm_matmul(x: jax.Array, w, *,
              block: Optional[tuple[int, int, int]] = None,
              epilogue=None, backend: Optional[str] = None,
              name: Optional[str] = None) -> jax.Array:
    """y = epilogue(x @ densify(w)); x: (..., K), w: an NMWeight or
    QNMWeight compressed along its axis 0 (the contraction dim).

    The weight's own metadata drives dispatch: ``w.nm`` is the pattern,
    ``w.kernel_policy`` picks reference/Pallas, the kernel backend and
    the block triples, the weight's *type* picks the quantization family
    (int8 weights route to the dequantizing kernels, which have their
    own autotune keys), and the flattened row count picks prefill-shaped
    vs decode families. ``epilogue`` is an
    :class:`repro.kernels.epilogue.Epilogue` (bias + activation) fused
    into the decode kernels' writeback. ``block`` overrides the policy's
    block for this call (benchmarks); ``backend`` overrides the policy's
    backend (``"auto"``/``"tpu"``/``"gpu"`` — see
    :mod:`repro.kernels.backend`; forcing an unavailable backend raises
    :class:`repro.kernels.registry.KernelForceError`). ``name`` labels
    the TPU kernel's device op (``nm_spmm_pallas[_decode]_<name>.N`` in a
    profile; models pass ``<target>_<projection>``); it changes nothing
    else.
    """
    bias, activation = resolve_epilogue(epilogue)
    if isinstance(w, QNMWeight):
        _check_axis0(w, "nm_matmul")
        pol = w.kernel_policy
        be = resolve_backend(
            backend if backend is not None
            else getattr(pol, "backend", "auto"))
        return _nm_matmul_q_core(
            x, w.vals, w.idx, w.scales, bias, w.nm, activation,
            pol.mode != "off", block or pol.block,
            block or pol.decode_block, pol.mode == "force", be, name)
    if not isinstance(w, NMWeight):
        raise TypeError(
            f"nm_matmul expects an NMWeight or QNMWeight, got "
            f"{type(w).__name__}; wrap compressed operands with "
            "repro.api.sparsify / repro.api.quantize, or use "
            "repro.kernels.raw for positional (vals, idx, cfg) calls"
        )
    _check_axis0(w, "nm_matmul")
    pol = w.kernel_policy
    be = resolve_backend(
        backend if backend is not None else getattr(pol, "backend", "auto"))
    return _nm_matmul_core(
        x, w.vals, w.idx, bias, w.nm, activation,
        pol.mode != "off", block or pol.block,
        block or pol.decode_block, pol.mode == "force", be, name)


def _check_axis0(w, name):
    if w.axis != 0:
        raise ValueError(
            f"{name} needs the weight compressed along axis 0 (the "
            f"contraction dim of y = x @ W); got axis={w.axis}"
        )


def nm_matmul_q(x: jax.Array, w: QNMWeight, *,
                block: Optional[tuple[int, int, int]] = None,
                epilogue=None, backend: Optional[str] = None) -> jax.Array:
    """Quantized alias of :func:`nm_matmul` (the unified entry point
    type-dispatches; this name survives for callers that want the int8
    family asserted by construction)."""
    if not isinstance(w, QNMWeight):
        raise TypeError(
            f"nm_matmul_q expects a QNMWeight, got {type(w).__name__}; "
            "produce one with repro.api.quantize"
        )
    return nm_matmul(x, w, block=block, epilogue=epilogue, backend=backend)


# float core: custom_vjp so compressed fine-tuning trains through every
# family (the bwd runs the differentiable reference composition on
# logical shapes — padding and family choice never change it)


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(4, 5, 6, 7, 8, 9, 10, 11))
def _nm_matmul_core(x, vals, idx, bias, cfg, activation, use_kernel, block,
                    decode_block, force, backend, name):
    return _core_fwd_impl(x, vals, idx, bias, cfg, activation, use_kernel,
                          block, decode_block, force, backend, name)


def _core_fwd_impl(x, vals, idx, bias, cfg, activation, use_kernel, block,
                   decode_block, force, backend, name):
    vals, idx = _pin_compressed(vals, idx)
    lead = x.shape[:-1]
    k = x.shape[-1]
    x2 = x.reshape(-1, k)
    mm = x2.shape[0]
    nn = vals.shape[1]
    _validate_pair(vals, idx, k, cfg)
    op, plan, ctx = _route(mm, nn, k, cfg, x.dtype, use_kernel, force,
                           block, decode_block, quantized=False,
                           backend=backend)
    interp = interpret_for(backend)
    if op == "nm_matmul_decode":
        y2 = registry.dispatch(
            op, ctx, x2, vals, idx, bias,
            cfg=cfg, plan=plan, activation=activation, interpret=interp,
            name=name,
        )
    else:
        y2 = registry.dispatch(
            op, ctx, x2, vals, idx,
            cfg=cfg, plan=plan, interpret=interp, name=name,
        )
        y2 = _epilogue_after(y2, bias, activation)
    return y2.reshape(*lead, nn)


def _core_fwd(x, vals, idx, bias, cfg, activation, use_kernel, block,
              decode_block, force, backend, name):
    y = _core_fwd_impl(x, vals, idx, bias, cfg, activation, use_kernel,
                       block, decode_block, force, backend, name)
    return y, (x, vals, idx, bias)


def _core_bwd(cfg, activation, use_kernel, block, decode_block, force,
              backend, name, res, dy):
    x, vals, idx, bias = res

    def ref(x_, vals_, bias_):
        w = decompress_nm(vals_, idx, cfg, axis=0).astype(jnp.float32)
        y = jnp.einsum("...k,kn->...n", x_.astype(jnp.float32), w)
        return apply_epilogue_f32(y, bias_, activation)

    dy32 = dy.astype(jnp.float32)
    if bias is None:
        _, vjp = jax.vjp(lambda x_, v_: ref(x_, v_, None), x, vals)
        dx, dvals = vjp(dy32)
        dbias = None
    else:
        _, vjp = jax.vjp(ref, x, vals, bias)
        dx, dvals, dbias = vjp(dy32)
        dbias = dbias.astype(bias.dtype)
    # decompress_nm is a one-hot einsum in vals: its vjp IS the gather of
    # the dense grad at the kept positions (straight-through on idx).
    return (dx.astype(x.dtype), dvals.astype(vals.dtype),
            jnp.zeros_like(idx), dbias)


_nm_matmul_core.defvjp(_core_fwd, _core_bwd)


# int8 core: inference-only (the optimizer never trains int8 leaves)


def _nm_matmul_q_core(x, vals, idx, scales, bias, cfg, activation,
                      use_kernel, block, decode_block, force, backend, name):
    vals, idx = _pin_compressed(vals, idx)
    lead = x.shape[:-1]
    k = x.shape[-1]
    x2 = x.reshape(-1, k)
    mm = x2.shape[0]
    nn = vals.shape[1]
    _validate_pair(vals, idx, k, cfg)
    op, plan, ctx = _route(mm, nn, k, cfg, x.dtype, use_kernel, force,
                           block, decode_block, quantized=True,
                           backend=backend)
    interp = interpret_for(backend)
    if op == "nm_matmul_decode_q":
        y2 = registry.dispatch(
            op, ctx, x2, vals, idx, scales, bias,
            cfg=cfg, plan=plan, activation=activation, interpret=interp,
            name=name,
        )
    else:
        y2 = registry.dispatch(
            op, ctx, x2, vals, idx, scales,
            cfg=cfg, plan=plan, interpret=interp, name=name,
        )
        y2 = _epilogue_after(y2, bias, activation)
    return y2.reshape(*lead, nn)


# ---------------------------------------------------------------------------
# dry-run routing: the public explanation surface
# ---------------------------------------------------------------------------


def explain_dispatch(x_shape, w, *, epilogue=None, dtype=None, backend=None):
    """The :class:`repro.kernels.registry.DispatchRecord` that
    ``nm_matmul(x, w)`` *would* produce for an ``x`` of shape
    ``x_shape`` — family, kernel, backend, block triple and padded
    geometry — without running anything.

    ``x_shape`` is the activation shape ``(..., K)`` (for a gather-port
    weight, ``w.axis == 1``, it is the dense B operand's ``(K, N)``).
    ``dtype`` is the activation dtype for autotune-cache lookup; it
    defaults to the weight's value dtype (the int8 family always keys on
    int8 regardless). ``backend`` overrides the policy's backend, same
    contract as :func:`nm_matmul`. Raises the same typed errors as the
    real call — including :class:`KernelForceError` for a forced weight
    whose shape cannot normalize or a forced backend this host cannot
    execute.
    """
    if not isinstance(w, (NMWeight, QNMWeight)):
        raise TypeError(
            f"explain_dispatch expects an NMWeight or QNMWeight, got "
            f"{type(w).__name__}")
    if w.axis == 1:
        from repro.kernels.indexmac_gather.ops import explain_gather

        return explain_gather(x_shape, w, backend=backend)
    _check_axis0(w, "explain_dispatch")
    resolve_epilogue(epilogue)  # validates; epilogue never changes routing
    k = x_shape[-1]
    mm = math.prod(x_shape[:-1]) if len(x_shape) > 1 else 1
    nn = w.vals.shape[1]
    _validate_pair(w.vals, w.idx, k, w.nm)
    pol = w.kernel_policy
    quantized = isinstance(w, QNMWeight)
    dtype = dtype if dtype is not None else w.vals.dtype
    be = resolve_backend(
        backend if backend is not None else getattr(pol, "backend", "auto"))
    op, plan, ctx = _route(
        mm, nn, k, w.nm, dtype, pol.mode != "off", pol.mode == "force",
        pol.block, pol.decode_block, quantized, backend=be)
    return registry.explain(op, ctx)


# ---------------------------------------------------------------------------
# positional internals (kernel-level tests) + deprecated re-export shims
# ---------------------------------------------------------------------------


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6)
)
def nm_matmul_positional(
    x: jax.Array,
    vals: jax.Array,
    idx: jax.Array,
    cfg: NMConfig,
    use_kernel: bool = True,
    block: Optional[tuple[int, int, int]] = None,
    force: bool = False,
) -> jax.Array:
    """Positional surface: y = x @ decompress(vals, idx); x: (..., K),
    vals/idx: (Kc, N). Internal (kernel-level tests / the deprecated
    ``repro.kernels.raw`` wrappers); always the prefill-shaped family —
    no decode routing, no epilogue. ``block=None`` consults the autotune
    cache; ``force=True`` skips the padding waste limit.
    """
    return _nm_matmul_fwd_impl(x, vals, idx, cfg, use_kernel, block, force)


def _nm_matmul_fwd_impl(x, vals, idx, cfg, use_kernel, block, force):
    vals, idx = _pin_compressed(vals, idx)
    lead = x.shape[:-1]
    k = x.shape[-1]
    x2 = x.reshape(-1, k)
    mm = x2.shape[0]
    nn = vals.shape[1]
    _validate_pair(vals, idx, k, cfg)
    plan = None
    if use_kernel:
        if block is None:
            block = autotune.best_block(mm, nn, k, cfg, x.dtype)
        plan = plan_nm_matmul(mm, nn, k, cfg, tuple(block))
    ctx = registry.make_ctx(
        (mm, k, nn), nm=cfg, use_kernel=use_kernel, plan=plan,
        dtype=x.dtype, force=force,
    )
    y2 = registry.dispatch(
        "nm_matmul", ctx, x2, vals, idx,
        cfg=cfg, plan=plan,
        # only a Pallas impl reads it: the reference route runs anywhere
        interpret=use_kernel and interpret_for("tpu"),
    )
    return y2.reshape(*lead, nn)


def _fwd(x, vals, idx, cfg, use_kernel, block, force):
    y = _nm_matmul_fwd_impl(x, vals, idx, cfg, use_kernel, block, force)
    return y, (x, vals, idx)


def _bwd(cfg, use_kernel, block, force, res, dy):
    x, vals, idx = res
    w = decompress_nm(vals, idx, cfg, axis=0)  # (K, N)
    dy32 = dy.astype(jnp.float32)
    dx = jnp.einsum("...n,kn->...k", dy32, w.astype(jnp.float32)).astype(x.dtype)
    dw = jnp.einsum(
        "...k,...n->kn", x.astype(jnp.float32), dy32
    )  # dense (K, N) grad
    # gather kept positions: dvals[r, c] = dw[(r//n)*m + idx[r, c], c]
    kc, nn = vals.shape
    block_id = jnp.arange(kc, dtype=jnp.int32) // cfg.n  # (Kc,)
    grow = block_id[:, None] * cfg.m + idx.astype(jnp.int32)  # (Kc, N)
    dvals = jnp.take_along_axis(dw, grow, axis=0).astype(vals.dtype)
    return dx, dvals, jnp.zeros_like(idx)


nm_matmul_positional.defvjp(_fwd, _bwd)


def nm_matmul_q_positional(
    x: jax.Array,
    vals: jax.Array,
    idx: jax.Array,
    scales: jax.Array,
    cfg: NMConfig,
    use_kernel: bool = True,
    block: Optional[tuple[int, int, int]] = None,
    force: bool = False,
) -> jax.Array:
    """Positional quantized surface: y = (x @ decompress(vals, idx)) *
    scales[col]. Internal; see :func:`nm_matmul_positional`."""
    vals, idx = _pin_compressed(vals, idx)
    lead = x.shape[:-1]
    k = x.shape[-1]
    x2 = x.reshape(-1, k)
    mm = x2.shape[0]
    nn = vals.shape[1]
    _validate_pair(vals, idx, k, cfg)
    plan = None
    if use_kernel:
        if block is None:
            block = autotune.best_block(mm, nn, k, cfg, jnp.int8)
        plan = plan_nm_matmul(mm, nn, k, cfg, tuple(block))
    ctx = registry.make_ctx(
        (mm, k, nn), nm=cfg, use_kernel=use_kernel, plan=plan,
        dtype=jnp.int8, force=force,
    )
    y2 = registry.dispatch(
        "nm_matmul_q", ctx, x2, vals, idx, scales,
        cfg=cfg, plan=plan,
        # only a Pallas impl reads it: the reference route runs anywhere
        interpret=use_kernel and interpret_for("tpu"),
    )
    return y2.reshape(*lead, nn)

