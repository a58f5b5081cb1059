"""Shared model building blocks: linear (dense / N:M sparse), norms,
rotary embeddings, token embedding.

Parameters are pytrees (nested dicts of jnp arrays, plus typed weight
nodes); every layer is a pair of pure functions
`*_init(key, ...) -> params` / `*_apply(params, x)`.

Sparsity is integrated at the linear layer: a linear created with a
target tag that the model's SparsityConfig covers stores a typed weight
node — :class:`repro.core.nmweight.NMWeight` (compressed (vals, idx)
pair) or :class:`MaskedNMWeight` (dense storage, mask re-derived each
forward) — whose static metadata carries its own ``NMConfig`` and kernel
policy. Apply paths dispatch on the node type; nothing threads an
``sp=`` config through forward calls (the weight is self-describing),
and nothing sniffs ``{"vals", "idx"}`` dict keys. Dense linears remain
plain ``{"w": ...}`` dicts.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import SparsityConfig
from repro.core.nmweight import KernelPolicy, MaskedNMWeight, NMWeight
from repro.core.sparsity import (
    apply_mask,
    compress_nm,
    prune_mask_nm,
)
from repro.kernels.epilogue import Epilogue, apply_epilogue_f32, resolve_epilogue
from repro.kernels.indexmac.ops import nm_matmul
from repro.quant.qnmweight import QNMWeight

DEFAULT_PARAM_DTYPE = jnp.float32
DEFAULT_COMPUTE_DTYPE = jnp.bfloat16

_COMPUTE = {"dtype": DEFAULT_COMPUTE_DTYPE}


def get_compute_dtype():
    return _COMPUTE["dtype"]


def set_compute_dtype(dt) -> None:
    """Process-wide activation dtype (tests flip to f32 to separate
    numerics from logic; training/serving use bf16)."""
    _COMPUTE["dtype"] = dt


# ---------------------------------------------------------------------------
# linear
# ---------------------------------------------------------------------------


def sparse_applies(sp: Optional[SparsityConfig], target: str, in_dim: int) -> bool:
    return (
        sp is not None
        and target in sp.targets
        and in_dim % sp.nm_for(target).m == 0
    )


def linear_init(
    key: jax.Array,
    in_dim: int,
    out_dim: int,
    *,
    sp: Optional[SparsityConfig] = None,
    target: str = "dense",
    param_dtype=DEFAULT_PARAM_DTYPE,
    scale: Optional[float] = None,
):
    """Returns ``{"w": ...}`` (dense) or a typed sparse weight node.

    ``sp`` routes *initialization only*: which targets are sparsified,
    at which N:M pattern (per-target overrides allowed), in which mode.
    The resulting node carries all of that as its own metadata — apply
    paths never see the SparsityConfig again.
    """
    scale = scale if scale is not None else in_dim ** -0.5
    w = jax.random.normal(key, (in_dim, out_dim), dtype=jnp.float32) * scale
    if not sparse_applies(sp, target, in_dim):
        return {"w": w.astype(param_dtype)}
    nm = sp.nm_for(target)
    mask = prune_mask_nm(w, nm, axis=0)
    if sp.mode == "masked":
        # dense storage; forward re-derives the top-N:M mask (SR-STE style)
        return MaskedNMWeight(
            w=apply_mask(w, mask).astype(param_dtype), nm=nm, axis=0
        )
    vals, idx = compress_nm(apply_mask(w, mask), nm, axis=0)
    return NMWeight(
        vals=vals.astype(param_dtype), idx=idx, nm=nm, axis=0,
        kernel_policy=KernelPolicy("auto" if sp.use_kernel else "off"),
    )


def linear_apply(
    params,
    x: jax.Array,
    *,
    compute_dtype=None,
    epilogue: Optional[Epilogue] = None,
    name: Optional[str] = None,
) -> jax.Array:
    """y = epilogue(x @ W). Dispatches on the weight node's type:
    NMWeight goes to the indexmac kernel path (its own nm/policy),
    QNMWeight to the int8 dequantizing kernel family, MaskedNMWeight
    re-projects onto the N:M constraint set (straight-through grads),
    ``{"w": ...}`` is a plain dense GEMM.

    ``epilogue`` (an :class:`repro.kernels.epilogue.Epilogue`: bias +
    activation name) rides through to ``nm_matmul`` for the compressed
    types — decode-shaped calls fuse it into the kernel writeback — and
    is applied with the identical f32 composition for the dense/masked
    kinds, so swapping a layer's weight representation never changes the
    epilogue arithmetic.

    ``name`` (``<target>_<projection>``, e.g. ``ffn_w_down``) labels the
    compressed types' kernel in a profile, as in
    ``nm_spmm_pallas_decode_ffn_w_down.N``; the other kinds ignore it."""
    compute_dtype = compute_dtype or get_compute_dtype()
    xc = x.astype(compute_dtype)
    if isinstance(params, QNMWeight):
        # int8 payload stays int8 — dequantization happens in-register
        # inside the kernel (scales at accumulator writeback); only the
        # activation follows the compute dtype.
        return nm_matmul(xc, params, epilogue=epilogue, name=name)
    if isinstance(params, NMWeight):
        return nm_matmul(xc, params.astype(compute_dtype), epilogue=epilogue,
                         name=name)
    if isinstance(params, MaskedNMWeight):
        # re-project every forward; gradients flow to all entries
        # (straight-through), pruned entries can revive.
        y = jnp.einsum("...k,kn->...n", xc,
                       params.project().astype(compute_dtype))
        return _dense_epilogue(y, epilogue)
    if not isinstance(params, dict) or "w" not in params:
        raise TypeError(
            "linear_apply expects an NMWeight, a MaskedNMWeight, or dense "
            f"{{'w': ...}} params; got {type(params).__name__}. Legacy "
            "compressed dicts must be upgraded to the typed representation "
            "(repro.api.sparsify; checkpoints migrate on restore)."
        )
    y = jnp.einsum("...k,kn->...n", xc, params["w"].astype(compute_dtype))
    return _dense_epilogue(y, epilogue)


def _dense_epilogue(y: jax.Array, epilogue: Optional[Epilogue]) -> jax.Array:
    bias, activation = resolve_epilogue(epilogue)
    if bias is None and activation is None:
        return y
    return apply_epilogue_f32(
        y.astype(jnp.float32), bias, activation).astype(y.dtype)


def linear_weight_dense(params) -> jax.Array:
    """Materialize the *effective* dense weight (tests / export): what
    the forward pass multiplies by. For masked weights that is the N:M
    projection, matching ``repro.api.densify`` — the raw (unpruned)
    training storage is ``params.w``."""
    if isinstance(params, (NMWeight, QNMWeight)):
        return params.to_dense()
    if isinstance(params, MaskedNMWeight):
        return params.project()
    return params["w"]


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------


def rmsnorm_init(d: int, param_dtype=DEFAULT_PARAM_DTYPE) -> dict:
    return {"scale": jnp.ones((d,), dtype=param_dtype)}


def rmsnorm_apply(params: dict, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    x32 = x.astype(jnp.float32)
    rms = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (x32 * rms).astype(x.dtype) * params["scale"].astype(x.dtype)


def layernorm_init(d: int, param_dtype=DEFAULT_PARAM_DTYPE) -> dict:
    return {
        "scale": jnp.ones((d,), dtype=param_dtype),
        "bias": jnp.zeros((d,), dtype=param_dtype),
    }


def layernorm_apply(params: dict, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean((x32 - mu) ** 2, axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return y.astype(x.dtype) * params["scale"].astype(x.dtype) + params[
        "bias"
    ].astype(x.dtype)


# ---------------------------------------------------------------------------
# rotary position embedding (llama-style half rotation)
# ---------------------------------------------------------------------------


def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    half = head_dim // 2
    return 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., seq, heads, head_dim); positions: (..., seq)."""
    head_dim = x.shape[-1]
    freqs = rope_freqs(head_dim, theta)  # (half,)
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # (..., s, half)
    cos = jnp.cos(angles)[..., :, None, :]  # (..., s, 1, half)
    sin = jnp.sin(angles)[..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# embedding
# ---------------------------------------------------------------------------


def embedding_init(
    key: jax.Array, vocab: int, d: int, param_dtype=DEFAULT_PARAM_DTYPE
) -> dict:
    e = jax.random.normal(key, (vocab, d), dtype=jnp.float32) * (d ** -0.5)
    return {"embedding": e.astype(param_dtype)}


def embedding_apply(params: dict, tokens: jax.Array, compute_dtype=None):
    return params["embedding"].astype(compute_dtype or get_compute_dtype())[tokens]


def embedding_attend(params: dict, x: jax.Array) -> jax.Array:
    """Tied output head: logits = x @ E^T (fp32 logits)."""
    return jnp.einsum(
        "...d,vd->...v", x.astype(jnp.float32),
        params["embedding"].astype(jnp.float32),
    )
