"""Model assembly: blocks -> groups -> LM (decoder-only or enc-dec backbone).

A model's `plan` is a tuple of (Block, repeat) groups. Groups with
repeat > 1 execute under lax.scan over stacked parameters — compile time
and HLO size stay O(#distinct block types), not O(depth), which is what
keeps the 512-device dry-run (and 1000+ node compiles) tractable.

Execution modes thread a per-layer cache pytree with the same group
structure (stacked leading dim for scanned groups).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import (
    AttnConfig,
    Block,
    FFNConfig,
    MambaConfig,
    MoEConfig,
    ModelConfig,
    RWKVConfig,
)
from repro.models import attention, mamba, moe, rwkv
from repro.models.cache import CacheView, view_from_legacy_kwargs
from repro.models.common import (
    DEFAULT_COMPUTE_DTYPE,
    get_compute_dtype,
    embedding_apply,
    embedding_attend,
    embedding_init,
    linear_apply,
    linear_init,
    rmsnorm_apply,
    rmsnorm_init,
)
from repro.models.ffn import ffn_apply, ffn_init
from repro.parallel.hints import shard_hint

# ---------------------------------------------------------------------------
# single block
# ---------------------------------------------------------------------------


def block_init(key: jax.Array, d_model: int, block: Block, cfg: ModelConfig,
               param_dtype=jnp.float32) -> dict:
    sp = cfg.sparsity
    ks = jax.random.split(key, 6)
    p: dict[str, Any] = {"norm1": rmsnorm_init(d_model, param_dtype)}
    mx = block.mixer
    if isinstance(mx, AttnConfig):
        p["mixer"] = attention.attn_init(
            ks[0], d_model, mx, sp=sp, param_dtype=param_dtype,
            qk_norm=mx.qk_norm,
        )
    elif isinstance(mx, MambaConfig):
        p["mixer"] = mamba.mamba_init(ks[0], d_model, mx, sp=sp,
                                      param_dtype=param_dtype)
    elif isinstance(mx, RWKVConfig):
        assert isinstance(block.mlp, FFNConfig)
        p["mixer"] = rwkv.rwkv_init(ks[0], d_model, mx, d_ff=block.mlp.d_ff,
                                    sp=sp, param_dtype=param_dtype)
    else:
        raise TypeError(mx)
    if block.cross_attn:
        assert isinstance(mx, AttnConfig)
        p["norm_cross"] = rmsnorm_init(d_model, param_dtype)
        p["cross"] = attention.gqa_init(ks[1], d_model, mx, sp=sp,
                                        param_dtype=param_dtype)
    if block.mlp is not None and not isinstance(mx, RWKVConfig):
        p["norm2"] = rmsnorm_init(d_model, param_dtype)
        if isinstance(block.mlp, MoEConfig):
            p["mlp"] = moe.moe_init(ks[2], d_model, block.mlp, sp=sp,
                                    param_dtype=param_dtype)
        else:
            p["mlp"] = ffn_init(ks[2], d_model, block.mlp, sp=sp,
                                param_dtype=param_dtype)
    return p


def block_empty_cache(block: Block, batch: int, max_seq: int, cfg: ModelConfig,
                      dtype=DEFAULT_COMPUTE_DTYPE) -> dict:
    mx = block.mixer
    c: dict[str, Any] = {}
    if isinstance(mx, AttnConfig):
        c = attention.attn_empty_cache(batch, max_seq, mx, dtype)
    elif isinstance(mx, MambaConfig):
        c = mamba.mamba_empty_cache(batch, cfg.d_model, mx)
    elif isinstance(mx, RWKVConfig):
        c = rwkv.rwkv_empty_cache(batch, cfg.d_model, mx, dtype)
    if block.cross_attn:
        assert isinstance(mx, AttnConfig)
        c["cross_k"] = jnp.zeros(
            (batch, cfg.encoder_seq, mx.kv_heads, mx.head_dim), dtype)
        c["cross_v"] = jnp.zeros(
            (batch, cfg.encoder_seq, mx.kv_heads, mx.head_dim), dtype)
    return c


def block_apply(
    params: dict,
    x: jax.Array,
    block: Block,
    cfg: ModelConfig,
    *,
    view: CacheView,
    cache: Optional[dict],
    enc_out: Optional[jax.Array] = None,
):
    """Returns (x, new_cache, aux). Sparse weights are self-describing
    typed nodes, so no sparsity config threads through apply calls.
    ``view`` carries mode/positions/cache addressing as one typed pytree
    (internal surfaces take it exclusively — no legacy keywords here);
    ``view.block_table`` switches attention caches to the paged layout
    (see attention.paged_write); only AttnConfig mixers use it."""
    mx = block.mixer
    mode = view.mode
    positions = view.positions
    aux = jnp.zeros((), jnp.float32)
    h = rmsnorm_apply(params["norm1"], x, cfg.norm_eps)
    mixer_cache = None
    if cache is not None:
        mixer_cache = {k: v for k, v in cache.items()
                       if not k.startswith("cross_")} or None
    if isinstance(mx, AttnConfig):
        y, new_mc = attention.attn_apply(
            params["mixer"], h, mx, view=view, cache=mixer_cache,
            rope_theta=mx.rope_theta or cfg.rope_theta,
            chunk=cfg.attn_chunk,
        )
    elif isinstance(mx, MambaConfig):
        y, new_mc = mamba.mamba_apply(params["mixer"], h, mx, mode=mode,
                                      cache=mixer_cache)
    else:
        y, new_mc = rwkv.rwkv_apply(params["mixer"], h, mx, mode=mode,
                                    cache=mixer_cache)
    x = x + y
    new_cache = dict(cache) if cache is not None else None
    if new_cache is not None and new_mc is not None:
        new_cache.update(new_mc)

    if block.cross_attn:
        hc = rmsnorm_apply(params["norm_cross"], x, cfg.norm_eps)
        if mode in ("train", "prefill"):
            assert enc_out is not None
            amx = dataclasses.replace(mx, rope=False, causal=False)
            b = enc_out.shape[0]
            kx = linear_apply(params["cross"]["wk"], enc_out)
            vx = linear_apply(params["cross"]["wv"], enc_out)
            kx = kx.reshape(b, -1, mx.kv_heads, mx.head_dim)
            vx = vx.reshape(b, -1, mx.kv_heads, mx.head_dim)
            yc, _ = attention.gqa_apply(
                params["cross"], hc, amx,
                view=CacheView.train(positions=positions),
                rope_theta=cfg.rope_theta, chunk=cfg.attn_chunk,
                cross_kv=(kx, vx),
            )
            if new_cache is not None:
                new_cache["cross_k"] = kx.astype(new_cache["cross_k"].dtype)
                new_cache["cross_v"] = vx.astype(new_cache["cross_v"].dtype)
        else:  # decode: static cross KV from cache
            amx = dataclasses.replace(mx, rope=False, causal=False)
            yc, _ = attention.gqa_apply(
                params["cross"], hc, amx,
                view=CacheView(mode="decode", positions=positions),
                rope_theta=cfg.rope_theta, chunk=cfg.attn_chunk,
                cross_kv=(cache["cross_k"], cache["cross_v"]),
            )
        x = x + yc

    if isinstance(mx, RWKVConfig):
        # channel-mix sublayer (token-shifted FFN) with its own state
        hm = rmsnorm_apply(params["mixer"]["cm_norm"], x, cfg.norm_eps)
        last = cache["cm_last"] if cache is not None else None
        y2, cm_last = rwkv.rwkv_channel_mix(params["mixer"], hm, last=last)
        x = x + y2
        if new_cache is not None:
            new_cache["cm_last"] = cm_last.astype(new_cache["cm_last"].dtype)
    elif block.mlp is not None:
        hm = rmsnorm_apply(params["norm2"], x, cfg.norm_eps)
        if isinstance(block.mlp, MoEConfig):
            y2, aux = moe.moe_apply(params["mlp"], hm, block.mlp)
        else:
            y2 = ffn_apply(params["mlp"], hm, block.mlp)
        x = x + y2
    x = shard_hint(x, ("pod", "data"), None, None)
    return x, new_cache, aux


# ---------------------------------------------------------------------------
# group (scan) execution — a group is (super_block, repeat) where the
# super_block is one Block or a tuple of Blocks (a repeating period, e.g.
# gemma3's 5 local + 1 global, jamba's 8-layer mamba/attn/moe period).
# Scanning the period keeps HLO size O(#distinct blocks).
# ---------------------------------------------------------------------------


def _as_blocks(entry) -> tuple[Block, ...]:
    return entry if isinstance(entry, tuple) else (entry,)


def _super_init(key, blocks: tuple[Block, ...], cfg: ModelConfig, param_dtype):
    ks = jax.random.split(key, len(blocks))
    return [block_init(k, cfg.d_model, b, cfg, param_dtype)
            for k, b in zip(ks, blocks)]


def group_init(key, entry, repeat: int, cfg: ModelConfig, param_dtype):
    """Stacked params of one group. ``lax.map`` builds one period at a
    time, so a layer's dense f32 draw (before pruning and compression)
    is the largest temporary — never the dense stack of the group."""
    blocks = _as_blocks(entry)
    if repeat == 1:
        return _super_init(key, blocks, cfg, param_dtype)
    keys = jax.random.split(key, repeat)
    return jax.lax.map(lambda k: _super_init(k, blocks, cfg, param_dtype),
                       keys)


def _head_init(key, d: int, vocab: int, param_dtype):
    return linear_init(key, d, vocab, sp=None, param_dtype=param_dtype)


def _init_on(fn, static_argnums, shardings=None):
    """``fn`` as an init piece: inside jit, so its output is created
    where it will live and in param_dtype — with ``shardings`` already
    sharded, else on the default device. Compiles once per (config,
    dtype, shardings)."""
    kw = {} if shardings is None else {"out_shardings": shardings}
    return jax.jit(fn, static_argnums=static_argnums, **kw)


def group_empty_cache(entry, repeat: int, batch: int, max_seq: int,
                      cfg: ModelConfig, dtype):
    blocks = _as_blocks(entry)
    c = [block_empty_cache(b, batch, max_seq, cfg, dtype) for b in blocks]
    if repeat > 1:
        c = jax.tree.map(lambda a: jnp.broadcast_to(a, (repeat, *a.shape)).copy(), c)
    return c


def group_apply(params, x, entry, repeat: int, cfg: ModelConfig, *,
                view: CacheView, cache, enc_out, remat: str):
    blocks = _as_blocks(entry)

    def one(p_list, x, c_list):
        aux = jnp.zeros((), jnp.float32)
        new_cs = []
        for p, b, c in zip(p_list, blocks,
                           c_list if c_list is not None else [None] * len(blocks)):
            x, nc, a = block_apply(p, x, b, cfg, view=view, cache=c,
                                   enc_out=enc_out)
            new_cs.append(nc)
            aux = aux + a
        return x, new_cs, aux

    if remat != "none" and view.mode == "train":
        policy = (jax.checkpoint_policies.dots_with_no_batch_dims_saveable
                  if remat == "dots" else None)
        one = jax.checkpoint(one, policy=policy)

    if repeat == 1:
        return one(params, x, cache)

    def body(carry, xs):
        x, aux = carry
        p, c = xs
        x, new_c, a = one(p, x, c)
        return (x, aux + a), new_c

    cache_xs = cache if cache is not None else None
    (x, aux), new_caches = jax.lax.scan(
        body, (x, jnp.zeros((), jnp.float32)), (params, cache_xs)
    )
    return x, new_caches, aux


# ---------------------------------------------------------------------------
# language model
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class LM:
    cfg: ModelConfig

    # ---- init -----------------------------------------------------------
    def init(self, key: jax.Array, param_dtype=jnp.float32,
             shardings: Any = None) -> dict:
        """Random params in ``param_dtype``, built inside jit a group at
        a time (see :func:`group_init`), so a published-width model
        initializes directly on the device it is served from.

        ``shardings`` — a tree of ``Sharding`` matching the params (e.g.
        mapped over ``jax.eval_shape`` of this method) — creates every
        piece already sharded, so no device ever holds more than its
        share. The values do not depend on it."""
        cfg = self.cfg
        sh = shardings or {}
        group_sh = sh.get("groups") or [None] * len(cfg.plan)
        ks = jax.random.split(key, 5 + len(cfg.plan)
                              + len(cfg.encoder_plan or ()))
        p: dict[str, Any] = {
            "embed": _init_on(embedding_init, (1, 2, 3), sh.get("embed"))(
                ks[0], cfg.vocab_size, cfg.d_model, param_dtype),
            "final_norm": rmsnorm_init(cfg.d_model, param_dtype),
        }
        if cfg.pos_embed == "learned":
            p["pos"] = (jax.random.normal(ks[1], (cfg.max_seq, cfg.d_model))
                        * 0.02).astype(param_dtype)
        if not cfg.tie_embeddings:
            p["lm_head"] = _init_on(_head_init, (1, 2, 3),
                                    sh.get("lm_head"))(
                ks[2], cfg.d_model, cfg.vocab_size, param_dtype)
        p["groups"] = [
            _init_on(group_init, (1, 2, 3, 4), group_sh[i])(
                ks[5 + i], blk, rep, cfg, param_dtype)
            for i, (blk, rep) in enumerate(cfg.plan)
        ]
        if cfg.encoder_plan is not None:
            off = 5 + len(cfg.plan)
            enc_sh = sh.get("enc_groups") or [None] * len(cfg.encoder_plan)
            p["enc_groups"] = [
                _init_on(group_init, (1, 2, 3, 4), enc_sh[i])(
                    ks[off + i], blk, rep, cfg, param_dtype)
                for i, (blk, rep) in enumerate(cfg.encoder_plan)
            ]
            p["enc_final_norm"] = rmsnorm_init(cfg.d_model, param_dtype)
            p["enc_pos"] = (jax.random.normal(ks[3],
                            (cfg.encoder_seq, cfg.d_model)) * 0.02
                            ).astype(param_dtype)
            if cfg.encoder_inputs == "tokens":
                p["enc_embed"] = embedding_init(ks[4], cfg.vocab_size,
                                                cfg.d_model, param_dtype)
        if shardings is not None:
            p = jax.device_put(p, shardings)  # the small pieces built eagerly
        return p

    # ---- caches ----------------------------------------------------------
    def init_cache(self, batch: int, max_seq: int, dtype=None) -> list:
        cfg = self.cfg
        dtype = dtype or get_compute_dtype()
        return [group_empty_cache(blk, rep, batch, max_seq, cfg, dtype)
                for blk, rep in cfg.plan]

    # ---- encoder ---------------------------------------------------------
    def encode(self, params, enc_input, *, remat="none"):
        cfg = self.cfg
        if cfg.encoder_inputs == "tokens":
            x = embedding_apply(params["enc_embed"], enc_input)
        else:
            x = enc_input.astype(get_compute_dtype())
        s = x.shape[1]
        x = x + params["enc_pos"][:s].astype(x.dtype)
        positions = jnp.arange(s)
        for gp, (blk, rep) in zip(params["enc_groups"], cfg.encoder_plan):
            x, _, _ = group_apply(gp, x, blk, rep, cfg,
                                  view=CacheView.train(positions=positions),
                                  cache=None, enc_out=None, remat=remat)
        return rmsnorm_apply(params["enc_final_norm"], x, cfg.norm_eps)

    # ---- forward ---------------------------------------------------------
    def forward(
        self,
        params: dict,
        tokens: jax.Array,  # (B, S)
        *,
        view: Optional[CacheView] = None,
        caches: Optional[list] = None,
        enc_input: Optional[jax.Array] = None,
        remat: str = "none",
        **kw,
    ):
        """``view`` (:class:`repro.models.cache.CacheView`) is the typed
        cache-addressing struct; None means train. The legacy keywords
        (mode/cache_len/block_table/write_mask) still work for one
        release via the deprecation shim. ``view.positions`` is derived
        here from ``cache_len`` when not already set."""
        view = view_from_legacy_kwargs(view, kw, caller="LM.forward")
        if kw:
            raise TypeError(
                f"LM.forward got unknown keyword(s) {sorted(kw)}")
        if view is None:
            view = CacheView.train()
        cfg = self.cfg
        mode = view.mode
        cache_len = view.cache_len
        b, s = tokens.shape
        enc_out = None
        if cfg.encoder_plan is not None and mode in ("train", "prefill"):
            assert enc_input is not None
            enc_out = self.encode(params, enc_input, remat=remat)
        x = embedding_apply(params["embed"], tokens)
        # "chunk" = one prefill_chunk-sized piece of a prompt against a
        # partially-filled cache: positions/cache writes offset by cache_len
        # exactly like decode, but s > 1 tokens at a time (causal masking
        # within the chunk happens in the attention mixers)
        offset_mode = view.offset_mode
        vec_len = (offset_mode and cache_len is not None
                   and getattr(cache_len, "ndim", 0) == 1)
        if cfg.pos_embed == "learned":
            pos_table = params["pos"].astype(x.dtype)
            if not offset_mode:
                x = x + pos_table[:s]
            elif vec_len:
                x = x + pos_table[cache_len[:, None]
                                  + jnp.arange(s)[None, :]]
            else:
                x = x + jax.lax.dynamic_slice(
                    pos_table, (cache_len, 0), (s, cfg.d_model))
        if view.positions is not None:
            positions = view.positions
        elif offset_mode:
            if vec_len:
                positions = cache_len[:, None] + jnp.arange(s)[None, :]
            else:
                positions = jnp.arange(s) + cache_len
        else:
            positions = jnp.arange(s)
        view = view.with_positions(positions)
        x = shard_hint(x, ("pod", "data"), None, None)

        aux_total = jnp.zeros((), jnp.float32)
        new_caches = []
        for i, (gp, (blk, rep)) in enumerate(zip(params["groups"], cfg.plan)):
            c = caches[i] if caches is not None else None
            x, new_c, aux = group_apply(
                gp, x, blk, rep, cfg, view=view, cache=c, enc_out=enc_out,
                remat=remat)
            new_caches.append(new_c)
            aux_total = aux_total + aux

        x = rmsnorm_apply(params["final_norm"], x, cfg.norm_eps)
        if cfg.tie_embeddings:
            logits = embedding_attend(params["embed"], x)
        else:
            logits = linear_apply(params["lm_head"], x,
                                  compute_dtype=jnp.float32)
        if cfg.logit_softcap:
            c = cfg.logit_softcap
            logits = jnp.tanh(logits / c) * c
        return logits, new_caches, aux_total

    # ---- loss ------------------------------------------------------------
    def loss(self, params, batch: dict, *, remat: str = "none"):
        """batch: tokens (B,S) int32, labels (B,S) int32 (-100 = pad),
        optional enc_input for enc-dec models."""
        logits, _, aux = self.forward(
            params, batch["tokens"], view=CacheView.train(),
            enc_input=batch.get("enc_input"), remat=remat)
        labels = batch["labels"]
        mask = (labels >= 0).astype(jnp.float32)
        lab = jnp.maximum(labels, 0)
        logz = jax.nn.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, lab[..., None], axis=-1)[..., 0]
        nll = (logz - ll) * mask
        loss = nll.sum() / jnp.maximum(mask.sum(), 1.0)
        return loss + aux, {"nll": loss, "aux": aux}


# ---------------------------------------------------------------------------
# parameter counting (MODEL_FLOPS)
# ---------------------------------------------------------------------------


def count_params(cfg: ModelConfig, active_only: bool = False) -> int:
    """Exact parameter count via eval_shape of init (no allocation).

    active_only: count MoE experts at top_k (+ shared) instead of all —
    the N_active used for MoE MODEL_FLOPS.
    """
    import math

    lm = LM(cfg)
    shapes = jax.eval_shape(lambda: lm.init(jax.random.PRNGKey(0)))
    # float leaves only: the int8 idx arrays are pattern metadata, not
    # parameters (they carry no FLOPs and no gradients)
    total = sum(math.prod(l.shape) for l in jax.tree.leaves(shapes)
                if jnp.issubdtype(l.dtype, jnp.inexact))
    if not active_only:
        return total
    # subtract the inactive routed-expert fraction analytically
    inactive = 0
    for entry, rep in cfg.plan:
        for blk in _as_blocks(entry):
            if isinstance(blk.mlp, MoEConfig):
                me = blk.mlp
                per_expert = 3 * cfg.d_model * me.d_expert  # swiglu
                if me.act == "gelu":
                    per_expert = 2 * cfg.d_model * me.d_expert
                if cfg.sparsity is not None and "expert" in cfg.sparsity.targets \
                   and cfg.sparsity.mode == "compressed":
                    per_expert = int(
                        per_expert * cfg.sparsity.nm_for("expert").density)
                inactive += rep * per_expert * (me.n_experts - me.top_k)
    return total - inactive
