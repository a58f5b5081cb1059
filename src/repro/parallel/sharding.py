"""Sharding rules: params, optimizer state, caches, and batches -> PartitionSpecs.

Mesh axes (launch/mesh.py):
  single-pod: ("data"=16, "model"=16)          — 256 chips
  multi-pod:  ("pod"=2, "data"=16, "model"=16) — 512 chips

Strategy (MaxText-style 2D param sharding):
  * TP over "model": attention heads / FFN hidden / vocab / experts (EP).
  * FSDP over "data": the other large axis of every 2D+ weight is sharded
    over "data" — parameter and optimizer-state memory scale with the full
    mesh (ZeRO-3-equivalent storage; XLA SPMD inserts the all-gathers).
  * DP over ("pod", "data") for the batch; gradients all-reduce over those
    axes (cross-pod traffic only carries gradient reductions).
  * SP for decode caches: the sequence axis shards over "model" so a 524k
    KV cache fits; softmax reductions over the sharded axis lower to
    all-reduces.

`sharding_mode`:
  fsdp     — as above (default; memory-optimal)
  tp_only  — params replicated over "data" (lower collective volume,
             higher memory) — a hillclimb knob
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs.base import AttnConfig, MoEConfig, ModelConfig
from repro.core.nmweight import MaskedNMWeight, NMWeight, is_weight_node
from repro.quant import QNMWeight

# parameter leaves whose *last-but-one / last* axes are (in, out) of a GEMM,
# keyed by leaf name: value = (spec for in-axis, spec for out-axis)
_COL = ("data", "model")   # column-parallel: out axis = heads/ffn-hidden
_ROW = ("model", "data")   # row-parallel: in axis sharded
_GEMM_RULES: dict[str, tuple] = {
    # attention
    "wq": _COL, "wk": _COL, "wv": _COL, "wo": _ROW,
    "wq_a": _COL, "wq_b": _COL, "wkv_a": ("data", None), "wk_rope": ("data", None),
    # ffn
    "w_up": _COL, "w_gate": _COL, "w_down": _ROW,
    # mamba / rwkv projections
    "w_in": _COL, "w_x": _ROW, "w_dt": (None, "model"), "w_out": _ROW,
    "w_r": _COL, "w_k": _COL, "w_v": _COL, "w_g": _COL, "w_o": _ROW,
    "w_cm_k": _COL, "w_cm_v": _ROW, "w_cm_r": _COL,
    # heads / embeddings
    "w_lm_head": _COL,
    # router stays replicated (tiny, and gate math wants full logits)
    "w_router": (None, None),
}
# non-GEMM leaves: full spec by name (leading axes listed explicitly)
_NAMED_RULES: dict[str, tuple] = {
    "embedding": ("model", "data"),       # vocab x d_model
    "pos": (None, "data"),
    "enc_pos": (None, "data"),
    "w_uk": ("model", None, None),        # (heads, lora, hd) — heads = TP
    "w_uv": ("model", None, None),
    "conv_w": (None, "model"),
    "a_log": ("model", None),
    "mix_lora_a": ("data", None),
    "mix_lora_b": (None, None, "data"),
    "decay_lora_a": ("data", None),
    "decay_lora_b": (None, "data"),
    "mu": (None, "data"),
    "cm_mu": (None, "data"),
    "bonus": (None, None),
}


def _axis_ok(dim: int, axis, mesh_shape: dict[str, int]) -> bool:
    if axis is None:
        return True
    names = (axis,) if isinstance(axis, str) else axis
    size = int(np.prod([mesh_shape[a] for a in names]))
    return dim % size == 0


def _fit(spec: tuple, shape: tuple, mesh_shape: dict[str, int]) -> P:
    """Drop axes that don't divide; pad/trim spec to the array rank
    (stacked scan params get leading None axes)."""
    spec = tuple(spec)
    if len(spec) < len(shape):
        spec = (None,) * (len(shape) - len(spec)) + spec
    spec = spec[-len(shape):] if len(spec) > len(shape) else spec
    fixed = tuple(
        s if _axis_ok(d, s, mesh_shape) else None for d, s in zip(shape, spec)
    )
    return P(*fixed)


def _gemm_rule(owner: str) -> tuple:
    rule = _GEMM_RULES.get(owner)
    if rule is None:
        rule = _COL if owner not in ("router",) else (None, None)
    if owner == "router":
        rule = (None, None)
    if owner == "lm_head":
        rule = _COL
    return rule


def _adjust_rule(rule: tuple, names: list, sharding_mode: str) -> tuple:
    if "experts" in names:
        # experts are stacked on a leading E axis -> expert parallelism
        rule = ("model",) + tuple(None if r == "model" else r for r in rule)
    elif "shared" in names:
        # shared experts enter the MoE shard_map as pure TP blocks
        rule = tuple(None if r == "data" else r for r in rule)
    if sharding_mode == "tp_only":
        rule = tuple(None if r == "data" else r for r in rule)
    return rule


def _leaf_spec(path: tuple, leaf, mesh_shape: dict[str, int],
               sharding_mode: str):
    names = [getattr(k, "key", getattr(k, "name", str(k))) for k in path]
    name = names[-1]

    if isinstance(leaf, NMWeight):
        # typed dispatch: the GEMM rule comes from the weight's own slot
        # name; idx is co-sharded with vals (same logical layout — both
        # halves of the compressed operand the FSDP gather must move
        # together).
        rule = _adjust_rule(_gemm_rule(name), names, sharding_mode)
        return dataclasses.replace(
            leaf,
            vals=_fit(rule, leaf.vals.shape, mesh_shape),
            idx=_fit(rule, leaf.idx.shape, mesh_shape),
        )
    if isinstance(leaf, QNMWeight):
        # quantized triple: vals/idx shard like the float pair; the
        # per-output-channel scales are co-sharded with vals' output
        # axis (the channel a scale belongs to must live on the shard
        # that holds its column — the writeback multiply is local).
        # Explicit leading rule axes (expert stacking) carry over too:
        # scales of an expert-sharded (E, ..., N) weight are (E, N) and
        # must shard the E axis with vals, not replicate across it.
        rule = _adjust_rule(_gemm_rule(name), names, sharding_mode)
        out_rule = rule[-1] if leaf.axis == 0 else rule[-2]
        scales_rule = tuple(rule[:-2]) + (out_rule,)
        return dataclasses.replace(
            leaf,
            vals=_fit(rule, leaf.vals.shape, mesh_shape),
            idx=_fit(rule, leaf.idx.shape, mesh_shape),
            scales=_fit(scales_rule, leaf.scales.shape, mesh_shape),
        )
    if isinstance(leaf, MaskedNMWeight):
        rule = _adjust_rule(_gemm_rule(name), names, sharding_mode)
        return dataclasses.replace(
            leaf, w=_fit(rule, leaf.w.shape, mesh_shape))

    if name == "w":
        owner = names[-2] if len(names) >= 2 else ""
        rule = _gemm_rule(owner)
    elif name in _NAMED_RULES:
        rule = _NAMED_RULES[name]
    else:
        rule = (None,) * leaf.ndim

    rule = _adjust_rule(rule, names, sharding_mode)
    return _fit(rule, leaf.shape, mesh_shape)


def param_pspecs(params: Any, mesh: Mesh, sharding_mode: str = "fsdp"):
    mesh_shape = dict(zip(mesh.axis_names, mesh.devices.shape))
    return jax.tree_util.tree_map_with_path(
        lambda p, l: _leaf_spec(p, l, mesh_shape, sharding_mode), params,
        is_leaf=is_weight_node,
    )


def param_shardings(params: Any, mesh: Mesh, sharding_mode: str = "fsdp"):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        param_pspecs(params, mesh, sharding_mode),
        is_leaf=lambda x: isinstance(x, P),
    )


# ---------------------------------------------------------------------------
# caches
# ---------------------------------------------------------------------------

# axis-1-is-sequence cache leaves (sharded over "model" = SP for decode)
_SEQ_CACHE = {"k", "v", "ckv", "kr", "cross_k", "cross_v"}


def _cache_leaf_spec(path, leaf, mesh_shape, batch_axes) -> P:
    names = [getattr(k, "key", getattr(k, "name", str(k))) for k in path]
    name = names[-1]
    # stacked scan caches have a leading layer axis; batch is the first
    # axis whose size matches nothing structural — we detect by rank of the
    # known layouts instead:
    base_rank = {"k": 4, "v": 4, "cross_k": 4, "cross_v": 4, "ckv": 3,
                 "kr": 3, "conv": 3, "ssm": 3, "wkv": 4, "tm_last": 2,
                 "cm_last": 2}.get(name, leaf.ndim)
    lead = leaf.ndim - base_rank  # 0 or 1 (scan-stacked)
    spec = [None] * leaf.ndim
    b_dim = lead  # batch axis position
    if _axis_ok(leaf.shape[b_dim], batch_axes, mesh_shape):
        # unwrap singleton axis tuples: P("data") and P(("data",)) shard
        # identically but only compare equal on newer JAX
        spec[b_dim] = (batch_axes[0]
                       if isinstance(batch_axes, tuple) and len(batch_axes) == 1
                       else batch_axes)
    if name in _SEQ_CACHE and _axis_ok(leaf.shape[b_dim + 1], "model",
                                       mesh_shape):
        spec[b_dim + 1] = "model"
    elif name in ("conv", "ssm") and _axis_ok(leaf.shape[b_dim + 1], "model",
                                              mesh_shape):
        # mamba states: channel axis over model
        if name == "ssm":
            spec[b_dim + 1] = "model"
        else:
            spec[b_dim + 2] = ("model" if _axis_ok(leaf.shape[b_dim + 2],
                                                   "model", mesh_shape)
                               else None)
    return P(*spec)


def cache_pspecs(caches: Any, mesh: Mesh, batch_axes=("data",)):
    mesh_shape = dict(zip(mesh.axis_names, mesh.devices.shape))
    return jax.tree_util.tree_map_with_path(
        lambda p, l: _cache_leaf_spec(p, l, mesh_shape, batch_axes), caches
    )


# ---------------------------------------------------------------------------
# batches
# ---------------------------------------------------------------------------


# ---------------------------------------------------------------------------
# tensor-parallel serving (shard_map) specs
#
# The sharded serving engine runs the model *manually* partitioned under
# shard_map: column-parallel q/k/v/up/gate projections (out axis over
# "model"), row-parallel wo/w_down (in axis over "model", partial sums
# psum'd via hints.tp_reduce), KV caches sharded on the head axis, batch
# slots over "data". These rules are head-aware — a projection only
# shards when the *head count* divides the TP degree, not merely the flat
# axis (splitting head_dim would scramble the (B,S,H,D) reshapes) — so
# they live apart from the GSPMD training rules above. NMWeight /
# QNMWeight nodes keep vals+idx(+scales) co-sharded, and row-parallel
# compressed weights additionally require the per-shard slice to land on
# an N:M group boundary (validated here, loudly).
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ServeTPPlan:
    """Which projection families shard over "model" for a given config.

    Uniform across the whole plan by construction (`serve_tp_plan`
    raises otherwise): the psum placement in the model (`tp_reduce`
    tags) is global, so a half-sharded plan would double-count."""

    tp: int
    shard_attn: bool  # wq(+wq_b/w_uk/w_uv) out axis, wo in axis
    shard_kv: bool    # wk/wv out axis + cache head axis (GQA only)
    shard_ffn: bool   # w_up/w_gate out axis, w_down in axis

    @property
    def reduce_tags(self) -> frozenset:
        tags = set()
        if self.shard_attn:
            tags.add("attn_out")
        if self.shard_ffn:
            tags.add("ffn_down")
        return frozenset(tags)


def serve_tp_plan(cfg: ModelConfig, tp: int) -> ServeTPPlan:
    """Decide (and validate) the TP sharding for serving ``cfg``.

    Supported plans: attention mixers (GQA / MLA) with dense-FFN or no
    MLP, no cross-attention. MoE would nest its own shard_map, and
    mamba/rwkv state caches have no head axis — both raise."""
    attn_f: set = set()
    kv_f: set = set()
    ffn_f: set = set()
    for entry, _rep in cfg.plan:
        blocks = entry if isinstance(entry, tuple) else (entry,)
        for blk in blocks:
            mx = blk.mixer
            if not isinstance(mx, AttnConfig) or blk.cross_attn:
                raise NotImplementedError(
                    f"TP serving supports attention-mixer decoder plans; "
                    f"{cfg.name} has {type(mx).__name__}"
                    f"{' + cross_attn' if blk.cross_attn else ''}")
            if isinstance(blk.mlp, MoEConfig):
                raise NotImplementedError(
                    f"TP serving does not support MoE blocks ({cfg.name}):"
                    " moe_apply opens its own shard_map")
            if tp == 1:
                continue
            q_ok = mx.q_heads % tp == 0
            if mx.kind == "mla":
                attn_f.add(q_ok)
                kv_f.add(False)  # latent ckv/kr cache is head-free
            else:
                kv_ok = q_ok and mx.kv_heads % tp == 0
                if q_ok and not kv_ok and mx.kv_heads != 1:
                    # q-sharding over replicated KV is only sound for
                    # MQA (kv_heads == 1): with kv_heads > 1 a shard's
                    # contiguous q-head slice spans one *global* KV
                    # group, but the local (hkv, g) reshape would pair
                    # it round-robin across all KV heads — wrong tokens,
                    # silently. Fall back to replicated attention.
                    q_ok = False
                attn_f.add(q_ok)
                kv_f.add(kv_ok and q_ok)
            if blk.mlp is not None:
                ffn_f.add(blk.mlp.d_ff % tp == 0)
    if tp == 1:
        return ServeTPPlan(1, False, False, False)
    if len(attn_f) > 1 or len(kv_f) > 1 or len(ffn_f) > 1:
        raise ValueError(
            f"{cfg.name}: plan is not uniformly TP-shardable at tp={tp} "
            "(blocks disagree on head/d_ff divisibility); the global psum "
            "tags cannot represent a mixed plan")
    return ServeTPPlan(tp,
                       attn_f.pop() if attn_f else False,
                       kv_f.pop() if kv_f else False,
                       ffn_f.pop() if ffn_f else False)


def serve_local_cfg(cfg: ModelConfig, plan: ServeTPPlan) -> ModelConfig:
    """The per-shard view of ``cfg``: head counts divided by tp so the
    (B, S, H, D) reshapes inside the mixers match the local projections.
    d_ff needs no scaling — ffn_apply derives shapes from the weights."""
    if plan.tp == 1 or not (plan.shard_attn or plan.shard_kv):
        return cfg
    new_plan = []
    for entry, rep in cfg.plan:
        blocks = entry if isinstance(entry, tuple) else (entry,)
        nb = []
        for blk in blocks:
            mx = blk.mixer
            q = mx.q_heads // plan.tp if plan.shard_attn else mx.q_heads
            kv = (mx.kv_heads // plan.tp
                  if plan.shard_kv and mx.kind != "mla" else mx.kv_heads)
            nb.append(dataclasses.replace(
                blk, mixer=dataclasses.replace(mx, q_heads=q, kv_heads=kv)))
        new_plan.append(
            (tuple(nb) if isinstance(entry, tuple) else nb[0], rep))
    return dataclasses.replace(cfg, plan=tuple(new_plan))


_COL_TP = (None, "model")
_ROW_TP = ("model", None)


def _serve_rule(owner: str, ndim: int, plan: ServeTPPlan):
    if plan.shard_attn and owner in ("wq", "wq_b"):
        return _COL_TP
    if plan.shard_attn and owner == "wo":
        return _ROW_TP
    if plan.shard_attn and owner in ("w_uk", "w_uv"):
        return ("model", None, None)  # (heads, lora, hd): heads = TP
    if plan.shard_kv and owner in ("wk", "wv"):
        return _COL_TP
    if plan.shard_ffn and owner in ("w_up", "w_gate"):
        return _COL_TP
    if plan.shard_ffn and owner == "w_down":
        return _ROW_TP
    return (None,) * max(ndim, 2)  # replicated (embed/norms/lm_head/...)


def _check_nm_row_split(leaf, owner: str, tp: int) -> None:
    """Row-parallel compressed weight: the per-shard slice of vals/idx
    must land on an N:M group boundary (idx entries are positions *within*
    a group, so any other cut would orphan half a group)."""
    kc = leaf.vals.shape[-2]
    n = leaf.nm.n
    if kc % tp or (kc // tp) % n:
        raise ValueError(
            f"{owner}: compressed in-axis Kc={kc} ({leaf.nm.tag}) does not "
            f"split into tp={tp} shards on group boundaries")


def _serve_leaf_spec(path, leaf, mesh_shape: dict, plan: ServeTPPlan):
    names = [getattr(k, "key", getattr(k, "name", str(k))) for k in path]
    name = names[-1]
    owner = names[-2] if name == "w" and len(names) >= 2 else name

    if isinstance(leaf, (NMWeight, QNMWeight)):
        rule = _serve_rule(owner, 2, plan)
        if leaf.axis != 0:
            rule = (None, None)  # out-axis compression: keep replicated
        elif rule == _ROW_TP:
            _check_nm_row_split(leaf, owner, plan.tp)
        if isinstance(leaf, QNMWeight):
            out_rule = rule[-1] if leaf.axis == 0 else rule[-2]
            scales_rule = tuple(rule[:-2]) + (out_rule,)
            return dataclasses.replace(
                leaf,
                vals=_fit(rule, leaf.vals.shape, mesh_shape),
                idx=_fit(rule, leaf.idx.shape, mesh_shape),
                scales=_fit(scales_rule, leaf.scales.shape, mesh_shape),
            )
        return dataclasses.replace(
            leaf,
            vals=_fit(rule, leaf.vals.shape, mesh_shape),
            idx=_fit(rule, leaf.idx.shape, mesh_shape),
        )
    if isinstance(leaf, MaskedNMWeight):
        rule = _serve_rule(owner, leaf.w.ndim, plan)
        return dataclasses.replace(
            leaf, w=_fit(rule, leaf.w.shape, mesh_shape))
    rule = _serve_rule(owner, leaf.ndim, plan)
    return _fit(rule, leaf.shape, mesh_shape)


def serve_param_pspecs(params: Any, mesh: Mesh, plan: ServeTPPlan):
    """TP-serving PartitionSpecs (shard_map in_specs for the param tree)."""
    mesh_shape = dict(zip(mesh.axis_names, mesh.devices.shape))
    return jax.tree_util.tree_map_with_path(
        lambda p, l: _serve_leaf_spec(p, l, mesh_shape, plan), params,
        is_leaf=is_weight_node,
    )


def serve_param_shardings(params: Any, mesh: Mesh, plan: ServeTPPlan):
    """:func:`serve_param_pspecs` as ``NamedSharding``s: where the
    sharded serve engine keeps each param (``device_put``, or
    ``LM.init(shardings=...)`` to create them there). ``params`` may be
    abstract (``jax.eval_shape``)."""
    return jax.tree.map(lambda s: NamedSharding(mesh, s),
                        serve_param_pspecs(params, mesh, plan),
                        is_leaf=lambda x: isinstance(x, P))


def serve_cache_pspecs(caches: Any, mesh: Mesh, plan: ServeTPPlan,
                       batch_axes=("data",)):
    """Decode-cache specs for TP serving: batch slots over "data", GQA
    K/V head axis over "model" when the plan shards KV; everything else
    (MLA latents, positions) replicated beyond the batch axis."""
    mesh_shape = dict(zip(mesh.axis_names, mesh.devices.shape))

    def one(path, leaf) -> P:
        names = [getattr(k, "key", getattr(k, "name", str(k)))
                 for k in path]
        name = names[-1]
        base_rank = {"k": 4, "v": 4, "ckv": 3, "kr": 3}.get(name, leaf.ndim)
        lead = leaf.ndim - base_rank
        spec = [None] * leaf.ndim
        if _axis_ok(leaf.shape[lead], batch_axes, mesh_shape):
            spec[lead] = (batch_axes[0]
                          if isinstance(batch_axes, tuple)
                          and len(batch_axes) == 1 else batch_axes)
        if plan.shard_kv and name in ("k", "v") \
                and _axis_ok(leaf.shape[lead + 2], "model", mesh_shape):
            spec[lead + 2] = "model"
        # drop trailing Nones: jit outputs come back with the normalized
        # spec, and a device_put'd P(..., None, None) vs an output's
        # P(...) would register as two compiled-step signatures
        while spec and spec[-1] is None:
            spec.pop()
        return P(*spec)

    return jax.tree_util.tree_map_with_path(one, caches)


def batch_pspec(batch_size: int, mesh: Mesh, rank: int = 2) -> P:
    """Shard the batch axis over as many of (pod, data) as divide it."""
    mesh_shape = dict(zip(mesh.axis_names, mesh.devices.shape))
    cands = [a for a in ("pod", "data") if a in mesh_shape]
    axes: tuple = ()
    size = 1
    for a in cands:
        if batch_size % (size * mesh_shape[a]) == 0:
            axes = axes + (a,)
            size *= mesh_shape[a]
    spec = (axes if axes else None,) + (None,) * (rank - 1)
    return P(*spec)


def dp_axes(mesh: Mesh) -> tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)
