"""Plain float32 reference of a llama-architecture decoder (yi-9b).

Follows the published architecture (arXiv:2403.04652; the HF ``llama``
modelling code): token embedding, then per layer RMSNorm -> GQA attention
with rotary embeddings (half rotation) -> residual, RMSNorm -> SwiGLU FFN
-> residual, a final RMSNorm and an untied output head. Every matrix
product runs in float32 at ``Precision.HIGHEST``; there is no cache, no
kernel and no batching trick: each sequence attends causally over all of
its own positions. Weights come from ``harness.weights`` by role name, one
layer at a time, so nothing of the program is read.

``fp8=True`` is the control: the same computation with every matrix
product's two operands rounded to float8 e4m3 (per-tensor absmax
scaling), the precision step below the bf16 that the configuration
serves in.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from harness import weights as wg

HI = jax.lax.Precision.HIGHEST
NEG = -1e30


def tensors(cfg: dict) -> dict:
    """Role -> (kind, shape, std, per_layer) of every weight of the model.

    Sparse projections are drawn compressed (kind "nm"); std makes a
    unit-variance input give a unit-variance output, except the head,
    whose logits get a std of 2."""
    d = cfg["hidden_size"]
    hd = cfg["head_dim"]
    q = cfg["num_attention_heads"] * hd
    kv = cfg["num_key_value_heads"] * hd
    f = cfg["intermediate_size"]
    sp = cfg["sparsity"]
    dens = sp["n"] / sp["m"]
    out = {
        "embed_tokens": ("dense", (cfg["vocab_size"], d), 1.0, False),
        "norm": ("norm", (d,), None, False),
        "lm_head": ("dense", (d, cfg["vocab_size"]), 2.0 / math.sqrt(d), False),
        "input_layernorm": ("norm", (d,), None, True),
        "post_attention_layernorm": ("norm", (d,), None, True),
    }
    for role, (k, n), target in (
            ("q_proj", (d, q), "attn_proj"), ("k_proj", (d, kv), "attn_proj"),
            ("v_proj", (d, kv), "attn_proj"), ("o_proj", (q, d), "attn_proj"),
            ("gate_proj", (d, f), "ffn"), ("up_proj", (d, f), "ffn"),
            ("down_proj", (f, d), "ffn")):
        if target in sp["targets"]:
            out[role] = ("nm", (k, n), 1.0 / math.sqrt(k * dens), True)
        else:
            out[role] = ("dense", (k, n), 1.0 / math.sqrt(k), True)
    return out


def make(cfg: dict, key, role: str, layer, dtype):
    """One tensor in the form the program stores: (vals, idx) for "nm"."""
    kind, shape, std, _ = tensors(cfg)[role]
    rk = wg.role_key(key, role, layer)
    if kind == "nm":
        sp = cfg["sparsity"]
        return wg.nm_weight(rk, shape[0], shape[1], sp["n"], sp["m"], std,
                            dtype)
    if kind == "norm":
        return wg.uniform_around_one(rk, shape, dtype)
    return wg.int_grid(rk, shape, std, dtype)


def dense(cfg: dict, key, role: str, layer=0) -> jax.Array:
    """The float32 matrix (or vector) of one role in one layer."""
    kind = tensors(cfg)[role][0]
    t = make(cfg, key, role, layer, jnp.bfloat16)
    if kind == "nm":
        sp = cfg["sparsity"]
        return wg.expand(t[0], t[1], sp["n"], sp["m"])
    return t.astype(jnp.float32)


def q8(a: jax.Array) -> jax.Array:
    """Round to float8 e4m3 with a per-tensor absmax scale."""
    s = jnp.max(jnp.abs(a)) / 448.0
    s = jnp.where(s > 0, s, 1.0)
    return (a / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(subs, a, b, fp8):
    if fp8:
        a, b = q8(a), q8(b)
    return jnp.einsum(subs, a, b, precision=HI)


def rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rope(x, theta):
    """x: (S, H, D) at positions 0..S-1, llama half rotation."""
    s, _, d = x.shape
    half = d // 2
    inv = 1.0 / (theta ** (np.arange(half, dtype=np.float32) / half))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attend(cfg, q, k, v, fp8):
    """One sequence: q (S, Hq, D), k/v (S, Hkv, D); causal softmax."""
    s = q.shape[0]
    g = cfg["num_attention_heads"] // cfg["num_key_value_heads"]
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    sc = _mm("qhd,khd->hqk", q, k, fp8) / math.sqrt(cfg["head_dim"])
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    sc = jnp.where(causal[None], sc, NEG)
    p = jax.nn.softmax(sc, axis=-1)
    return _mm("hqk,khd->qhd", p, v, fp8)


@functools.partial(jax.jit, static_argnums=(0, 4))
def _layer(cfg_items, key, layer, x, fp8):
    cfg = _thaw(cfg_items)
    b, s, d = x.shape
    hd = cfg["head_dim"]
    eps = cfg["rms_norm_eps"]
    w = {r: dense(cfg, key, r, layer)
         for r, t in tensors(cfg).items() if t[3]}
    h = rmsnorm(x, w["input_layernorm"], eps)
    q = _mm("bsd,dn->bsn", h, w["q_proj"], fp8).reshape(b, s, -1, hd)
    k = _mm("bsd,dn->bsn", h, w["k_proj"], fp8).reshape(b, s, -1, hd)
    v = _mm("bsd,dn->bsn", h, w["v_proj"], fp8).reshape(b, s, -1, hd)
    theta = cfg["rope_theta"]
    q = jax.vmap(lambda t: rope(t, theta))(q)
    k = jax.vmap(lambda t: rope(t, theta))(k)
    o = jax.lax.map(lambda qkv: _attend(cfg, *qkv, fp8), (q, k, v))
    x = x + _mm("bsn,nd->bsd", o.reshape(b, s, -1), w["o_proj"], fp8)
    h = rmsnorm(x, w["post_attention_layernorm"], eps)
    gate = _mm("bsd,df->bsf", h, w["gate_proj"], fp8)
    up = _mm("bsd,df->bsf", h, w["up_proj"], fp8)
    return x + _mm("bsf,fd->bsd", jax.nn.silu(gate) * up, w["down_proj"], fp8)


@functools.partial(jax.jit, static_argnums=(0,))
def _embed(cfg_items, key, tokens):
    cfg = _thaw(cfg_items)
    return dense(cfg, key, "embed_tokens")[tokens]


@functools.partial(jax.jit, static_argnums=(0, 5))
def _head(cfg_items, key, x, rows, toks, fp8):
    """Per picked row: (max logit, logit of toks, argmax) after the final
    norm and head; x (B, S, D), rows (R, 2) of (batch, position)."""
    cfg = _thaw(cfg_items)
    hsel = x[rows[:, 0], rows[:, 1]]
    h = rmsnorm(hsel, dense(cfg, key, "norm"), cfg["rms_norm_eps"])
    logits = _mm("rd,dv->rv", h, dense(cfg, key, "lm_head"), fp8)
    at = jnp.take_along_axis(logits, toks[:, None], axis=1)[:, 0]
    return logits.max(-1), at, logits.argmax(-1)


_USED = ("hidden_size", "head_dim", "num_attention_heads",
         "num_key_value_heads", "intermediate_size", "num_hidden_layers",
         "vocab_size", "rope_theta", "rms_norm_eps", "sparsity")


def _items(cfg: dict) -> tuple:
    """Hashable form of the sizes the reference reads (a jit static)."""
    sp = cfg["sparsity"]
    sp = (("m", sp["m"]), ("n", sp["n"]), ("targets", tuple(sp["targets"])))
    return tuple(sorted((k, sp if k == "sparsity" else cfg[k])
                        for k in _USED))


def _thaw(items: tuple) -> dict:
    cfg = dict(items)
    cfg["sparsity"] = dict(cfg["sparsity"])
    return cfg


def hidden(cfg: dict, key, tokens: np.ndarray, fp8: bool = False):
    """Final-layer residual stream (B, S, D) of token batch (B, S)."""
    items = _items(cfg)
    x = _embed(items, key, jnp.asarray(tokens, jnp.int32))
    for layer in range(cfg["num_hidden_layers"]):
        x = _layer(items, key, jnp.int32(layer), x, fp8)
    return x


def logit_rows(cfg: dict, key, x, rows: np.ndarray, toks: np.ndarray,
               fp8: bool = False, block: int = 512):
    """(max, logit at toks, argmax) for each (batch, position) row."""
    items = _items(cfg)
    n = len(rows)
    pad = (-n) % block
    rows = np.concatenate([rows, np.zeros((pad, 2), rows.dtype)])
    toks = np.concatenate([toks, np.zeros(pad, toks.dtype)])
    outs = [_head(items, key, x, jnp.asarray(rows[i:i + block]),
                  jnp.asarray(toks[i:i + block]), fp8)
            for i in range(0, len(rows), block)]
    mx, at, am = (np.concatenate([np.asarray(o[j]) for o in outs])[:n]
                  for j in range(3))
    return mx, at, am
