"""Sparse 2D convolution on the indexmac kernel path, via im2col.

The paper's entire evaluation (§IV) is structured-sparse *CNN* layers
mapped to sparse-dense GEMMs: a conv with HWIO weights ``(kh, kw, C_in,
C_out)`` becomes ``A(M=C_out, K=C_in*kh*kw) x B(K, N=H_out*W_out)``.
This module is that mapping executed on the real kernels:

* :func:`im2col` lowers NHWC activations to patch rows whose feature
  layout ``(kh, kw, C_in)`` matches ``w_hwio.reshape(K, C_out)`` — so a
  conv is exactly ``patches @ W2d``.
* :class:`SparseConv2D` holds its weight as the same typed node a linear
  does (:class:`NMWeight` / int8 :class:`QNMWeight` / dense ``{"w"}``),
  compressed along the K = C_in*kh*kw contraction axis. Both value
  families, autotune, shape padding and kernel-policy dispatch apply to
  convs unchanged because the forward *is* ``linear_apply`` on patches.
* :class:`SparseCNN` runs a whole backbone (ResNet-bottleneck or
  DenseNet dense-block topology from a :class:`CNNConfig`), and
  :func:`cnn_layer_specs` / :func:`cnn_layer_gemms` derive the per-layer
  conv list and the paper's im2col GEMM table from the same config —
  ``benchmarks/cnn_specs.py`` and the measured fig4/5/6 benchmarks both
  consume it.

Gradients work end-to-end: im2col is pure (differentiable) slicing and
``nm_matmul`` brings its custom VJP, so :class:`SparseConv2D` trains the
compressed representation directly (straight-through on idx).
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Union

import jax
import jax.numpy as jnp

from repro.configs.base import (
    BottleneckStage,
    CNNConfig,
    ConvSpec,
    DenseStage,
    SparsityConfig,
)
from repro.models.common import linear_apply, linear_init

__all__ = [
    "im2col",
    "conv2d",
    "SparseConv2D",
    "SparseCNN",
    "ConvLayer",
    "cnn_layer_specs",
    "cnn_layer_gemms",
]


# ---------------------------------------------------------------------------
# im2col lowering
# ---------------------------------------------------------------------------


def _same_pads(size: int, k: int, s: int) -> tuple[int, int]:
    """XLA 'SAME' split: total = max((ceil(size/s)-1)*s + k - size, 0)."""
    out = -(-size // s)
    pad = max((out - 1) * s + k - size, 0)
    return pad // 2, pad - pad // 2


def _out_dim(size: int, k: int, s: int, padding: str) -> int:
    if padding == "SAME":
        return -(-size // s)
    return (size - k) // s + 1


def im2col(
    x: jax.Array,
    kh: int,
    kw: int,
    *,
    stride: Union[int, tuple[int, int]] = 1,
    padding: str = "SAME",
) -> jax.Array:
    """NHWC activations -> im2col patch rows.

    x: (..., H, W, C) -> (..., H_out, W_out, kh*kw*C). The patch feature
    layout is ``(kh, kw, C)`` — exactly ``w_hwio.reshape(kh*kw*C, C_out)``
    — so ``im2col(x) @ W2d == lax.conv_general_dilated(x, w_hwio)`` with
    NHWC/HWIO dimension numbers. Pure slicing: differentiable, jit-safe.
    """
    sh, sw = (stride, stride) if isinstance(stride, int) else stride
    if padding not in ("SAME", "VALID"):
        raise ValueError(f"padding must be 'SAME' or 'VALID', got {padding!r}")
    *_, h, w, _c = x.shape
    ho = _out_dim(h, kh, sh, padding)
    wo = _out_dim(w, kw, sw, padding)
    if ho <= 0 or wo <= 0:
        raise ValueError(
            f"conv window ({kh}x{kw}, stride {sh}x{sw}, {padding}) does not "
            f"fit the {h}x{w} input")
    if padding == "SAME":
        pt, pb = _same_pads(h, kh, sh)
        pl, pr = _same_pads(w, kw, sw)
        pad = [(0, 0)] * (x.ndim - 3) + [(pt, pb), (pl, pr), (0, 0)]
        x = jnp.pad(x, pad)
    cols = [
        x[..., i: i + (ho - 1) * sh + 1: sh, j: j + (wo - 1) * sw + 1: sw, :]
        for i in range(kh)
        for j in range(kw)
    ]
    return cols[0] if len(cols) == 1 else jnp.concatenate(cols, axis=-1)


def conv2d(
    x: jax.Array,
    w,
    *,
    kh: int,
    kw: int,
    stride: Union[int, tuple[int, int]] = 1,
    padding: str = "SAME",
    compute_dtype=None,
) -> jax.Array:
    """y = conv(x, W) through the im2col GEMM on the kernel path.

    ``w`` is any linear-weight node over the flattened contraction axis:
    an :class:`NMWeight`/:class:`QNMWeight` compressed along
    K = C_in*kh*kw (axis 0), or dense ``{"w": (K, C_out)}``. Dispatch
    (reference vs Pallas, block triple, float vs int8 family) follows the
    weight's own metadata, exactly as for a linear layer.
    """
    with jax.named_scope("im2col"):
        patches = im2col(x, kh, kw, stride=stride, padding=padding)
    return linear_apply(w, patches, compute_dtype=compute_dtype)


# ---------------------------------------------------------------------------
# SparseConv2D layer
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class SparseConv2D:
    """A conv layer whose weight is the typed sparse node of a linear.

    ``init`` produces the weight node (the params *are* the node — same
    convention as ``linear_init``); ``apply`` is im2col + linear_apply.
    int8 execution needs no support here: ``repro.api.quantize`` /
    ``quantize_tree`` turn the NMWeight into a QNMWeight and ``apply``
    dispatches on the type unchanged.
    """

    spec: ConvSpec

    def init(
        self,
        key: jax.Array,
        *,
        sp: Optional[SparsityConfig] = None,
        param_dtype=jnp.float32,
    ):
        return linear_init(
            key, self.spec.k_gemm, self.spec.c_out,
            sp=sp, target=self.spec.target, param_dtype=param_dtype,
        )

    def apply(self, params, x: jax.Array, *, compute_dtype=None) -> jax.Array:
        s = self.spec
        return conv2d(x, params, kh=s.kh, kw=s.kw, stride=s.stride,
                      padding=s.padding, compute_dtype=compute_dtype)


# ---------------------------------------------------------------------------
# per-layer walker: the conv list / GEMM table of a CNNConfig
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ConvLayer:
    """A :class:`ConvSpec` placed at its resolved input resolution."""

    spec: ConvSpec
    h_in: int
    w_in: int

    @property
    def h_out(self) -> int:
        return self.spec.out_hw(self.h_in, self.w_in)[0]

    @property
    def w_out(self) -> int:
        return self.spec.out_hw(self.h_in, self.w_in)[1]

    @property
    def gemm(self) -> tuple[str, int, int, int]:
        """(name, M=C_out, K=C_in*kh*kw, N=H_out*W_out) — paper §IV."""
        from repro.core.cost_model import conv_gemm_dims  # lazy, no cycle

        s = self.spec
        return (s.name, *conv_gemm_dims(s.c_out, s.c_in, s.kh, s.kw,
                                        self.h_out, self.w_out))


def cnn_layer_specs(cfg: CNNConfig) -> list[ConvLayer]:
    """Every conv of the backbone in execution order, with resolved
    channel counts and spatial resolutions."""
    layers = [ConvLayer(cfg.stem, cfg.input_hw, cfg.input_hw)]
    hw = layers[0].h_out
    if cfg.stem_pool > 1:
        hw = -(-hw // cfg.stem_pool)
    ch = cfg.stem.c_out

    def conv(name, c_in, c_out, k=1, stride=1, at=None, target="conv"):
        layers.append(ConvLayer(
            ConvSpec(name, c_in, c_out, k, k, stride, target=target),
            at, at))

    if cfg.kind == "resnet":
        for si, st in enumerate(cfg.stages):
            assert isinstance(st, BottleneckStage), st
            for b in range(st.blocks):
                tag = f"s{si + 2}b{b + 1}"
                stride = st.stride if b == 0 else 1
                conv(f"{tag}_1x1a", ch, st.mid, 1, stride, at=hw)
                hw_out = -(-hw // stride)
                conv(f"{tag}_3x3", st.mid, st.mid, 3, at=hw_out)
                conv(f"{tag}_1x1b", st.mid, st.out, 1, at=hw_out)
                if b == 0:
                    conv(f"{tag}_proj", ch, st.out, 1, stride, at=hw,
                         target="proj")
                ch = st.out
                hw = hw_out
    elif cfg.kind == "densenet":
        for bi, st in enumerate(cfg.stages):
            assert isinstance(st, DenseStage), st
            for li in range(st.layers):
                tag = f"d{bi + 1}l{li + 1}"
                conv(f"{tag}_1x1", ch, 4 * st.growth, 1, at=hw)
                conv(f"{tag}_3x3", 4 * st.growth, st.growth, 3, at=hw)
                ch += st.growth
            if bi < len(cfg.stages) - 1:
                conv(f"t{bi + 1}_1x1", ch, ch // 2, 1, at=hw)
                ch //= 2
                hw = -(-hw // 2)  # ceil: matches the SAME-padded avg-pool
    else:
        raise ValueError(f"unknown CNN kind {cfg.kind!r}")
    return layers


def cnn_layer_gemms(cfg: CNNConfig) -> list[tuple[str, int, int, int]]:
    """The paper's im2col GEMM table: (name, M=C_out, K, N=H_out*W_out)."""
    return [layer.gemm for layer in cnn_layer_specs(cfg)]


def cnn_final_channels(cfg: CNNConfig) -> int:
    """Channel count entering the classifier head."""
    if cfg.kind == "resnet":
        return cfg.stages[-1].out
    ch = cfg.stem.c_out
    for bi, st in enumerate(cfg.stages):
        ch += st.layers * st.growth
        if bi < len(cfg.stages) - 1:
            ch //= 2
    return ch


# ---------------------------------------------------------------------------
# SparseCNN forward model
# ---------------------------------------------------------------------------


def _max_pool(x: jax.Array, k: int = 3, stride: int = 2) -> jax.Array:
    return jax.lax.reduce_window(
        x, -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating)
        else jnp.iinfo(x.dtype).min,
        jax.lax.max, (1, k, k, 1), (1, stride, stride, 1), "SAME")


def _avg_pool(x: jax.Array, k: int = 2, stride: int = 2) -> jax.Array:
    s = jax.lax.reduce_window(
        x.astype(jnp.float32), 0.0, jax.lax.add,
        (1, k, k, 1), (1, stride, stride, 1), "SAME")
    return (s / (k * k)).astype(x.dtype)


class SparseCNN:
    """A CNN backbone executing every conv through the sparse GEMM path.

    Params are ``{"convs": {layer_name: weight_node}, "head": {"w"}}`` —
    conv weight nodes are exactly what ``linear_init`` produces over the
    flattened K = C_in*kh*kw axis, so ``repro.api.quantize_tree``, the
    optimizer, sharding and checkpointing all treat a CNN like any other
    model. Topology (residual adds, dense-block concats, transitions)
    comes from the :class:`CNNConfig`.
    """

    def __init__(self, cfg: CNNConfig):
        self.cfg = cfg
        self.layers = cnn_layer_specs(cfg)
        self._conv = {l.spec.name: SparseConv2D(l.spec) for l in self.layers}

    def init(self, key: jax.Array, *, param_dtype=jnp.float32):
        sp = self.cfg.sparsity
        keys = jax.random.split(key, len(self.layers) + 1)
        convs = {
            l.spec.name: self._conv[l.spec.name].init(
                k, sp=sp, param_dtype=param_dtype)
            for k, l in zip(keys[:-1], self.layers)
        }
        head = linear_init(
            keys[-1], cnn_final_channels(self.cfg), self.cfg.num_classes,
            sp=None, target="head", param_dtype=param_dtype,
        )
        return {"convs": convs, "head": head}

    def _run(self, convs, name, x, *, compute_dtype):
        return self._conv[name].apply(convs[name], x,
                                      compute_dtype=compute_dtype)

    def apply(self, params, x: jax.Array, *, compute_dtype=None) -> jax.Array:
        """x: (B, H, W, 3) NHWC -> logits (B, num_classes)."""
        cfg = self.cfg
        convs = params["convs"]
        x = jax.nn.relu(self._run(convs, cfg.stem.name, x,
                                  compute_dtype=compute_dtype))
        if cfg.stem_pool > 1:
            x = _max_pool(x, 3, cfg.stem_pool)
        if cfg.kind == "resnet":
            for si, st in enumerate(cfg.stages):
                for b in range(st.blocks):
                    tag = f"s{si + 2}b{b + 1}"
                    h = jax.nn.relu(self._run(convs, f"{tag}_1x1a", x,
                                              compute_dtype=compute_dtype))
                    h = jax.nn.relu(self._run(convs, f"{tag}_3x3", h,
                                              compute_dtype=compute_dtype))
                    h = self._run(convs, f"{tag}_1x1b", h,
                                  compute_dtype=compute_dtype)
                    short = (self._run(convs, f"{tag}_proj", x,
                                       compute_dtype=compute_dtype)
                             if b == 0 else x)
                    x = jax.nn.relu(h + short)
        else:
            for bi, st in enumerate(cfg.stages):
                for li in range(st.layers):
                    tag = f"d{bi + 1}l{li + 1}"
                    h = jax.nn.relu(self._run(convs, f"{tag}_1x1", x,
                                              compute_dtype=compute_dtype))
                    h = self._run(convs, f"{tag}_3x3", h,
                                  compute_dtype=compute_dtype)
                    x = jnp.concatenate([x, h], axis=-1)
                if bi < len(cfg.stages) - 1:
                    x = self._run(convs, f"t{bi + 1}_1x1", jax.nn.relu(x),
                                  compute_dtype=compute_dtype)
                    x = _avg_pool(x, 2, 2)
        x = jnp.mean(x.astype(jnp.float32), axis=(-3, -2))  # global avg pool
        return linear_apply(params["head"], x, compute_dtype=jnp.float32)
