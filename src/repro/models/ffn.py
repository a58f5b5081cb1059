"""Feed-forward blocks (dense MLP) — sparse-eligible (target "ffn").

``sp`` is init-time routing only; the built weights carry their own
sparsity metadata, so ``ffn_apply`` takes no config."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import FFNConfig, SparsityConfig
from repro.kernels.epilogue import Epilogue
from repro.models.common import linear_apply, linear_init
from repro.parallel.hints import tp_reduce


def ffn_init(
    key: jax.Array,
    d_model: int,
    cfg: FFNConfig,
    *,
    sp: Optional[SparsityConfig] = None,
    param_dtype=jnp.float32,
    target: str = "ffn",
) -> dict:
    ks = jax.random.split(key, 3)
    p = {
        "w_up": linear_init(ks[0], d_model, cfg.d_ff, sp=sp, target=target,
                            param_dtype=param_dtype),
        "w_down": linear_init(ks[1], cfg.d_ff, d_model, sp=sp, target=target,
                              param_dtype=param_dtype),
    }
    if cfg.act in ("swiglu", "geglu"):
        p["w_gate"] = linear_init(ks[2], d_model, cfg.d_ff, sp=sp, target=target,
                                  param_dtype=param_dtype)
    return p


def ffn_apply(
    params: dict,
    x: jax.Array,
    cfg: FFNConfig,
    *,
    target: str = "ffn",
) -> jax.Array:
    """``target`` is the sparsity target the weights were built under
    (``ffn_init``'s); it only names the kernels (``<target>_w_up``, ...)."""
    # the activation rides as an Epilogue on its projection: decode-shaped
    # sparse GEMMs fuse it into the kernel writeback (one launch), every
    # other path applies the identical f32 composition after the GEMM.
    if cfg.act in ("swiglu", "geglu"):
        up = linear_apply(params["w_up"], x, name=f"{target}_w_up")
        act = "silu" if cfg.act == "swiglu" else "gelu"
        gate = linear_apply(params["w_gate"], x,
                            epilogue=Epilogue(activation=act),
                            name=f"{target}_w_gate")
        h = gate * up
    elif cfg.act in ("gelu", "relu_sq"):
        h = linear_apply(params["w_up"], x,
                         epilogue=Epilogue(activation=cfg.act),
                         name=f"{target}_w_up")
    else:
        raise ValueError(cfg.act)
    # w_down is row-parallel under TP serving: per-shard output is a
    # partial sum over the sharded d_ff — reduced here, identity elsewhere
    return tp_reduce(linear_apply(params["w_down"], h,
                                  name=f"{target}_w_down"), "ffn_down")
