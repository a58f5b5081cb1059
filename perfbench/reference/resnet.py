"""Plain float32 reference of a bottleneck ResNet (ResNet-50).

The architecture of arXiv:1512.03385 (Table 1, ResNet v1: the stride of
a downsampling block sits on its first 1x1 conv and on the projection
shortcut) with the departures the configuration states: no batch norm
and no conv bias (the program has none), a 3x3/2 max-pool after the 7x7/2
stem, ReLU after every conv but the block's last, whose sum with the
shortcut is ReLU'd. Global average pool, then a dense head. Convolutions
are ``lax.conv_general_dilated`` (NHWC, HWIO, 'SAME') in float32 at
``Precision.HIGHEST``. Weights come from ``harness.weights`` by layer
name; a conv's weight is drawn as the (kh*kw*C_in, C_out) matrix of its
im2col GEMM, compressed N:M along that K axis.

``fp8=True`` is the control: every conv's and the head's operands
rounded to float8 e4m3 (per-tensor absmax scaling).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from harness import weights as wg

HI = jax.lax.Precision.HIGHEST


def convs(cfg: dict) -> list[dict]:
    """Every conv in execution order: name, c_in, c_out, k, stride,
    h_in (square input), h_out, sparse."""
    out = []
    targets = cfg["sparsity"]["targets"]

    def add(name, c_in, c_out, k, stride, h_in, target):
        out.append(dict(name=name, c_in=c_in, c_out=c_out, k=k, stride=stride,
                        h_in=h_in, h_out=-(-h_in // stride),
                        sparse=target in targets and (k * k * c_in)
                        % cfg["sparsity"]["m"] == 0))

    st = cfg["stem"]
    add("conv1", 3, st["c_out"], st["k"], st["stride"], cfg["input_hw"], "stem")
    hw = -(-out[0]["h_out"] // cfg["stem_pool"])
    ch = st["c_out"]
    for si, (mid, c_out, blocks, stride) in enumerate(cfg["stages"]):
        for b in range(blocks):
            tag = f"s{si + 2}b{b + 1}"
            s = stride if b == 0 else 1
            add(f"{tag}_1x1a", ch, mid, 1, s, hw, "conv")
            h2 = -(-hw // s)
            add(f"{tag}_3x3", mid, mid, 3, 1, h2, "conv")
            add(f"{tag}_1x1b", mid, c_out, 1, 1, h2, "conv")
            if b == 0:
                add(f"{tag}_proj", ch, c_out, 1, s, hw, "proj")
            ch, hw = c_out, h2
    return out


def tensors(cfg: dict) -> dict:
    """Role -> (kind, shape, std) of every weight: He scaling over the
    kept fan-in, so a unit-variance input keeps its scale through ReLU."""
    dens = cfg["sparsity"]["n"] / cfg["sparsity"]["m"]
    out = {}
    for c in convs(cfg):
        k = c["k"] * c["k"] * c["c_in"]
        if c["sparse"]:
            out[c["name"]] = ("nm", (k, c["c_out"]), math.sqrt(2 / (k * dens)))
        else:
            out[c["name"]] = ("dense", (k, c["c_out"]), math.sqrt(2 / k))
    feat = cfg["stages"][-1][1]
    out["head"] = ("dense", (feat, cfg["num_classes"]), 1 / math.sqrt(feat))
    return out


def make(cfg: dict, key, role: str, dtype):
    return draw(cfg, wg.role_key(key, role), tensors(cfg)[role], dtype)


def draw(cfg: dict, rk, spec: tuple, dtype):
    """The tensor of a ``tensors`` entry, drawn from its role key."""
    kind, shape, std = spec
    if kind == "nm":
        sp = cfg["sparsity"]
        return wg.nm_weight(rk, shape[0], shape[1], sp["n"], sp["m"], std,
                            dtype)
    return wg.int_grid(rk, shape, std, dtype)


def dense(cfg: dict, key, role: str) -> jax.Array:
    kind = tensors(cfg)[role][0]
    t = make(cfg, key, role, jnp.bfloat16)
    if kind == "nm":
        sp = cfg["sparsity"]
        return wg.expand(t[0], t[1], sp["n"], sp["m"])
    return t.astype(jnp.float32)


def images(key, batches: int, batch: int, hw: int, dtype=jnp.bfloat16):
    """Input batches (batches, batch, hw, hw, 3): k/128 for integer k in
    [-127, 127], exact in bf16."""
    q = wg.uniform_ints(key, (batches, batch, hw, hw, 3), -127, 128)
    return (q.astype(jnp.float32) / 128.0).astype(dtype)


def q8(a: jax.Array) -> jax.Array:
    s = jnp.max(jnp.abs(a)) / 448.0
    s = jnp.where(s > 0, s, 1.0)
    return (a / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _conv(cfg, key, c, x, fp8):
    w = dense(cfg, key, c["name"]).reshape(c["k"], c["k"], c["c_in"],
                                           c["c_out"])
    if fp8:
        x, w = q8(x), q8(w)
    return jax.lax.conv_general_dilated(
        x, w, (c["stride"],) * 2, "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HI)


@functools.partial(jax.jit, static_argnums=(0, 3))
def _forward(cfg_items, key, x, fp8):
    cfg = _thaw(cfg_items)
    layers = {c["name"]: c for c in convs(cfg)}
    run = lambda name, h: _conv(cfg, key, layers[name], h, fp8)  # noqa: E731
    x = jax.nn.relu(run("conv1", x.astype(jnp.float32)))
    p = cfg["stem_pool"]
    if p > 1:
        x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 3, 3, 1),
                                  (1, p, p, 1), "SAME")
    for si, (_, _, blocks, _) in enumerate(cfg["stages"]):
        for b in range(blocks):
            tag = f"s{si + 2}b{b + 1}"
            h = jax.nn.relu(run(f"{tag}_1x1a", x))
            h = jax.nn.relu(run(f"{tag}_3x3", h))
            h = run(f"{tag}_1x1b", h)
            short = run(f"{tag}_proj", x) if b == 0 else x
            x = jax.nn.relu(h + short)
    feat = jnp.mean(x, axis=(1, 2))
    w = dense(cfg, key, "head")
    if fp8:
        feat, w = q8(feat), q8(w)
    return jnp.einsum("bc,cn->bn", feat, w, precision=HI)


_USED = ("input_hw", "num_classes", "stem", "stem_pool", "stages", "sparsity")


def _freeze(v):
    if isinstance(v, dict):
        return tuple(sorted((k, _freeze(x)) for k, x in v.items()))
    if isinstance(v, (list, tuple)):
        return tuple(_freeze(x) for x in v)
    return v


def _items(cfg: dict) -> tuple:
    return tuple(sorted((k, _freeze(cfg[k])) for k in _USED))


def _thaw(items: tuple) -> dict:
    cfg = dict(items)
    cfg["stem"] = dict(cfg["stem"])
    cfg["sparsity"] = dict(cfg["sparsity"])
    return cfg


def logits(cfg: dict, key, x, fp8: bool = False, block: int = 64):
    """Logits (B, classes) of images x (B, H, W, 3), ``block`` at a time."""
    items = _items(cfg)
    return jnp.concatenate([_forward(items, key, x[i:i + block], fp8)
                            for i in range(0, x.shape[0], block)])
