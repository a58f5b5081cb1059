"""Public facade for the compressed N:M representation.

The verbs most users need:

  sparsify(w, nm)   dense (K, N) array -> NMWeight (prune + compress)
  quantize(w)       NMWeight / dense array -> int8 QNMWeight (+ scales)
  dequantize(qw)    QNMWeight -> float NMWeight (fallback path)
  densify(w)        any typed weight node / {"w": ...} -> dense array
  nm_matmul(x, w, epilogue=...)
                    y = epilogue(x @ densify(w)), dispatched by w's own
                    metadata and *type* (QNMWeight -> the int8 kernel
                    family); skinny-M calls route to the fused decode
                    kernel family
  explain_dispatch(x_shape, w)
                    the DispatchRecord nm_matmul *would* produce —
                    family, kernel, block, pad plan — without running
  is_sparse(obj)    True for typed sparse weight nodes
  attention(q, k, v, mask=..., cache=...)
                    block-sparse attention under a declared MaskSpec;
                    prefill vs cache-view decode/chunk dispatch decided
                    by the CacheView argument (None = prefill/train);
                    explain_dispatch_attention is its dry-run twin

An :class:`NMWeight` is a registered JAX pytree: ``vals``/``idx`` are
leaves (jit/vmap/grad/shard like any array), while the ``NMConfig``, the
compressed ``axis`` and the :class:`KernelPolicy` ride as static treedef
metadata — the weight is self-describing, so nothing threads a sparsity
config through apply paths, and different layers of one model can carry
different N:M patterns.

Kernel policy semantics (``KernelPolicy.mode``):

  off    always the XLA reference implementation (default).
  auto   padded Pallas kernel when the shape normalizes within the
         padding waste limit (REPRO_PAD_WASTE_LIMIT), else reference.
  force  Pallas whenever the shape normalizes at all; the waste limit
         is ignored.

``KernelPolicy.block`` optionally pins the (block_m, block_n, block_k)
tile triple (``decode_block`` likewise for the decode family); ``None``
consults the autotune cache.

Kernel backends (``KernelPolicy.backend`` / the ``backend=`` kwarg on
``nm_matmul`` / ``explain_dispatch`` / ``indexmac_gather``):

  auto   (default) ``$REPRO_BACKEND`` if set, else the device platform
         — a GPU host resolves to ``gpu``, everything else to ``tpu``.
  tpu    the Pallas-on-Mosaic kernel family (interprets off-TPU).
  gpu    the Pallas-on-Triton family (:mod:`repro.kernels.indexmac_gpu`)
         — available on a GPU host, or anywhere under
         ``REPRO_GPU_INTERPRET=1`` (interpreter; CI parity lane).

Forcing a backend the host cannot execute raises the typed
:class:`KernelForceError` naming the backend; ``explain_dispatch``
dry-runs the identical resolution without executing a kernel, and the
:class:`DispatchRecord` it returns (like every record the real calls
write) carries the resolved ``backend`` field.

Epilogues: :class:`Epilogue` is a (bias, activation-name) spec.
``nm_matmul(x, w, epilogue=Epilogue(bias=b, activation="silu"))``
computes ``silu(x @ densify(w) + b)`` with one composition contract on
every path — fused into the decode kernels' f32 accumulator writeback,
applied identically outside the prefill-shaped kernels — so outputs are
bit-exact against the reference composition on the integer lattice.
"""
from __future__ import annotations

from typing import Optional, Union

import jax
import jax.numpy as jnp

from repro.core.nmweight import (
    KernelPolicy,
    MaskedNMWeight,
    NMWeight,
    is_weight_node,
)
from repro.core.sparsity import (
    NMConfig,
    apply_mask,
    compress_nm,
    decompress_nm,
    prune_mask_nm,
)
from repro.kernels.backend import resolve_backend  # noqa: F401 (re-export)
from repro.kernels.blocksparse_attn.mask import MaskSpec
from repro.kernels.blocksparse_attn.ops import (
    MaskForceError,
    bs_attention as _bs_attention,
    bs_attention_decode as _bs_attention_decode,
    explain_dispatch_attention as _explain_dispatch_attention,
)
from repro.kernels.epilogue import Epilogue
from repro.kernels.indexmac.ops import (
    explain_dispatch as _explain_dispatch,
)
from repro.kernels.indexmac.ops import nm_matmul as _nm_matmul_typed
from repro.kernels.indexmac_gather.ops import (
    indexmac_gather as _indexmac_gather,
)
import repro.kernels.indexmac_gpu.ops  # noqa: F401 (gpu-backend registrations)
from repro.kernels.registry import DispatchRecord, KernelForceError
from repro.models.cache import CacheView
from repro.quant import QNMWeight
from repro.quant import dequantize as _dequantize
from repro.quant import quantize_nm as _quantize_nm
from repro.quant import quantize_tree, dequantize_tree  # noqa: F401 (re-export)

__all__ = [
    "CacheView",
    "DispatchRecord",
    "Epilogue",
    "KernelForceError",
    "KernelPolicy",
    "MaskForceError",
    "MaskSpec",
    "MaskedNMWeight",
    "NMConfig",
    "NMWeight",
    "QNMWeight",
    "attention",
    "conv2d",
    "densify",
    "dequantize",
    "dequantize_tree",
    "explain_dispatch",
    "explain_dispatch_attention",
    "indexmac_gather",
    "is_sparse",
    "nm_matmul",
    "quantize",
    "quantize_tree",
    "resolve_backend",
    "sparsify",
    "sparsify_conv",
]


def _as_policy(kernel_policy) -> KernelPolicy:
    if isinstance(kernel_policy, KernelPolicy):
        return kernel_policy
    if isinstance(kernel_policy, str):
        return KernelPolicy(mode=kernel_policy)
    raise TypeError(
        f"kernel_policy must be a KernelPolicy or a mode string "
        f"('off' | 'auto' | 'force'), got {type(kernel_policy).__name__}"
    )


def sparsify(
    w: jax.Array,
    nm: NMConfig,
    *,
    axis: int = 0,
    kernel_policy: Union[KernelPolicy, str] = KernelPolicy("auto"),
) -> NMWeight:
    """Prune a dense weight to top-|w| N:M along ``axis`` and compress.

    An already N:M-sparse ``w`` passes through losslessly (its non-zeros
    are the per-block top-n by construction). ``axis=0`` is the
    contraction dim of ``y = x @ W`` — what ``nm_matmul`` consumes.
    """
    if w.ndim != 2:
        raise ValueError(f"sparsify expects a 2D weight, got shape {w.shape}")
    if w.shape[axis] % nm.m != 0:
        raise ValueError(
            f"axis {axis} size {w.shape[axis]} not divisible by M={nm.m}")
    pruned = apply_mask(w, prune_mask_nm(w, nm, axis=axis))
    vals, idx = compress_nm(pruned, nm, axis=axis)
    return NMWeight(vals=vals, idx=idx, nm=nm, axis=axis,
                    kernel_policy=_as_policy(kernel_policy))


def quantize(w, nm=None, *, method="absmax", axis: int = 0,
             kernel_policy=None) -> QNMWeight:
    """int8-quantize a weight (symmetric, per output channel).

    ``w`` is an :class:`NMWeight` (the common case — quantize after
    sparsify) or a dense 2D array (``nm`` required; pruned + compressed
    first). ``method`` is ``"absmax"`` | ``"percentile"`` or a
    pre-populated observer from :mod:`repro.quant.calibrate`. For whole
    param trees use :func:`quantize_tree`.
    """
    return _quantize_nm(w, nm, method=method, axis=axis,
                        kernel_policy=kernel_policy)


def dequantize(qw: QNMWeight, dtype=None) -> NMWeight:
    """Float :class:`NMWeight` with the same pattern — the fallback for
    consumers that cannot take the int8 path."""
    return _dequantize(qw, dtype=dtype or jnp.float32)


def densify(w) -> jax.Array:
    """Materialize the dense array behind any linear-weight node."""
    if isinstance(w, NMWeight):
        return decompress_nm(w.vals, w.idx, w.nm, axis=w.axis)
    if isinstance(w, QNMWeight):
        return w.to_dense()
    if isinstance(w, MaskedNMWeight):
        return w.project()
    if isinstance(w, dict) and "w" in w:
        return w["w"]
    return w  # already a dense array


def is_sparse(obj) -> bool:
    """True for the typed sparse weight nodes (compressed or masked)."""
    return is_weight_node(obj)


def nm_matmul(x: jax.Array, w, *,
              block: Optional[tuple[int, int, int]] = None,
              epilogue: Optional[Epilogue] = None,
              backend: Optional[str] = None) -> jax.Array:
    """y = epilogue(x @ densify(w)) for an :class:`NMWeight` or int8
    :class:`QNMWeight`; dispatch (reference vs Pallas, decode vs prefill
    family, tile sizes, kernel backend, and the float-vs-int8 kernel
    family) is decided by ``w.kernel_policy``, the weight's type and the
    flattened row count — see the module docstring. ``epilogue`` is an
    :class:`Epilogue` (bias + activation) fused into the decode kernels'
    writeback; ``backend`` (``"auto"``/``"tpu"``/``"gpu"``) overrides
    the policy's kernel backend for this call."""
    return _nm_matmul_typed(x, w, block=block, epilogue=epilogue,
                            backend=backend)


def explain_dispatch(x_shape, w, *, epilogue: Optional[Epilogue] = None,
                     dtype=None, backend: Optional[str] = None,
                     ) -> DispatchRecord:
    """The :class:`DispatchRecord` that ``nm_matmul(x, w)`` (or, for an
    axis-1 weight, ``indexmac_gather(w, b)``) *would* produce for an
    operand of shape ``x_shape`` — dispatch family, chosen kernel,
    resolved backend, block triple, padded geometry and, for a TPU
    decode kernel, its MXU operand dtype — without executing anything.
    ``backend`` overrides the policy's kernel backend, same contract as
    :func:`nm_matmul`. Raises the same typed errors as the real call,
    including :class:`KernelForceError` for a forced weight whose shape
    cannot normalize or a forced backend this host cannot execute."""
    return _explain_dispatch(x_shape, w, epilogue=epilogue, dtype=dtype,
                             backend=backend)


def attention(q: jax.Array, k: jax.Array, v: jax.Array, *, mask: MaskSpec,
              cache: Optional[CacheView] = None, scale=None,
              policy="auto", backend: Optional[str] = None,
              tile: Optional[tuple[int, int]] = None) -> jax.Array:
    """Block-sparse attention under a declared :class:`MaskSpec` — the
    attention sibling of :func:`nm_matmul`: one typed entry, family
    dispatch by shape and cache view.

    ``cache=None`` is the prefill/train case (q and k/v cover the same
    absolute positions from 0): routes the ``bs_attention`` family —
    pair-list Pallas kernel on TPU, gather kernel on the gpu lane,
    XLA block-gather elsewhere, dense fallback under the density/waste
    budgets (``REPRO_BS_DENSITY_LIMIT`` / ``REPRO_BS_WASTE_LIMIT``).

    A :class:`CacheView` in decode/chunk mode means k/v are fixed-size
    cache views: routes ``bs_attention_decode`` with the valid extent
    ``cache_len + Sq`` (chunk mode masks by the queries' absolute
    positions). ``policy``/``backend``/``tile`` follow the
    :class:`KernelPolicy` contract; ``KernelPolicy("force")`` on an
    untileable mask raises the typed :class:`MaskForceError`."""
    if cache is None:
        return _bs_attention(q, k, v, spec=mask, scale=scale, policy=policy,
                             backend=backend, tile=tile)
    if not isinstance(cache, CacheView):
        raise TypeError(
            f"cache must be a CacheView (or None for prefill/train), got "
            f"{type(cache).__name__}")
    if not cache.offset_mode:
        raise ValueError(
            f"a {cache.mode!r} CacheView carries no cache offset — pass "
            f"cache=None for prefill/train attention")
    sq = q.shape[1]
    q_positions = cache.positions
    if cache.mode == "chunk" and q_positions is None:
        cl = cache.cache_len
        q_positions = (cl[:, None] + jnp.arange(sq) if cl.ndim == 1
                       else jnp.arange(sq) + cl)
    if cache.mode == "decode":
        q_positions = None
    return _bs_attention_decode(
        q, k, v, spec=mask, length=cache.cache_len + sq,
        q_positions=q_positions, scale=scale, policy=policy,
        backend=backend)


def explain_dispatch_attention(q_shape, kv_shape, *, mask: MaskSpec,
                               decode: bool = False, dtype=None,
                               policy="auto", backend: Optional[str] = None,
                               tile: Optional[tuple[int, int]] = None,
                               ) -> DispatchRecord:
    """The :class:`DispatchRecord` that :func:`attention` *would* write
    for operands of these shapes (``decode=True`` for the cache-view
    family) — it shares the route function with the executing call, so
    the explanation cannot drift from the real dispatch. Raises the same
    typed errors, including :class:`MaskForceError` for a forced
    untileable mask."""
    return _explain_dispatch_attention(
        q_shape, kv_shape, mask=mask, decode=decode,
        dtype=dtype if dtype is not None else jnp.float32, policy=policy,
        backend=backend, tile=tile)


def indexmac_gather(w, b: jax.Array, *,
                    block: Optional[tuple[int, int, int]] = None,
                    backend: Optional[str] = None) -> jax.Array:
    """C = densify(w) @ b for a row-compressed A (``w.axis == 1``) — the
    literal gather-port orientation of the paper. Accepts an
    :class:`NMWeight` or int8 :class:`QNMWeight`; ``backend`` overrides
    the policy's kernel backend."""
    return _indexmac_gather(w, b, block=block, backend=backend)


def sparsify_conv(
    w: jax.Array,
    nm: NMConfig,
    *,
    kernel_policy: Union[KernelPolicy, str] = KernelPolicy("auto"),
) -> NMWeight:
    """Prune + compress a conv kernel for the im2col GEMM path.

    ``w`` is HWIO ``(kh, kw, C_in, C_out)``; the N:M pattern is applied
    along the flattened contraction axis K = kh*kw*C_in (the axis
    :func:`conv2d` contracts over), so the result is exactly the weight
    node a :class:`repro.models.conv.SparseConv2D` holds.
    """
    if w.ndim != 4:
        raise ValueError(
            f"sparsify_conv expects an HWIO (kh, kw, C_in, C_out) kernel, "
            f"got shape {w.shape}")
    kh, kw, c_in, c_out = w.shape
    return sparsify(w.reshape(kh * kw * c_in, c_out), nm, axis=0,
                    kernel_policy=kernel_policy)


def conv2d(x: jax.Array, w, *, kh: int, kw: int, stride=1,
           padding: str = "SAME", compute_dtype=None) -> jax.Array:
    """y = conv(x, densify(w)) through the im2col GEMM on the kernel
    path; ``w`` is a node from :func:`sparsify_conv` (or its quantized /
    dense sibling). See :mod:`repro.models.conv` for layers and whole
    backbones."""
    from repro.models.conv import conv2d as _conv2d  # lazy: api <-> models

    return _conv2d(x, w, kh=kh, kw=kw, stride=stride, padding=padding,
                   compute_dtype=compute_dtype)
