"""Attention mixers: GQA (with RoPE, sliding window, qk-norm) and MLA
(DeepSeek-V2 multi-head latent attention), with three execution modes:

  train    — full-sequence, chunked flash-style softmax (lax.scan over KV
             chunks, fp32 running max/denominator) so the Sq x Skv score
             matrix is never materialized; O(Sq * chunk) memory.
  prefill  — same math as train; additionally returns the KV cache laid
             out (B, S, ...) so decode can shard S over the model axis.
  decode   — single new token against the cache. Written as plain reductions
             over the (sharded) cache axis so SPMD lowers them to
             all-reduces; MLA uses the weight-absorbed form and attends
             directly over the compressed c_kv cache.

All projections are sparse-eligible (target "attn_proj") — the paper's
technique applied to attention GEMMs. Sparsity routing happens at init;
the typed weight nodes are self-describing, so apply paths take no
sparsity config.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import AttnConfig, SparsityConfig
from repro.kernels.blocksparse_attn import ops as bs_ops
from repro.kernels.blocksparse_attn.ref import jnp_token_mask
from repro.models.cache import (
    AttnKwargError,
    CacheView,
    view_from_legacy_kwargs,
)
from repro.models.common import (
    DEFAULT_COMPUTE_DTYPE,
    apply_rope,
    linear_apply,
    linear_init,
    rmsnorm_apply,
    rmsnorm_init,
)

from repro.core.dots import acc_einsum  # noqa: E402  (shared dot policy)
from repro.parallel.hints import tp_reduce

NEG_INF = -1e30


class CacheLenError(ValueError):
    """A concrete ``cache_len`` would write outside the cache bounds."""


def _check_cache_len(cache_len, s: int, max_seq: int) -> None:
    """Bounds-check concrete offsets; traced values can't be inspected,
    so inside jit the scatters below carry an explicit ``mode="drop"``
    (out-of-range writes are discarded, never wrapped around)."""
    if isinstance(cache_len, jax.core.Tracer):
        return
    cl = np.asarray(cache_len)
    if (cl < 0).any() or (cl + s > max_seq).any():
        raise CacheLenError(
            f"cache_len={cl.tolist()} with a {s}-token write exceeds "
            f"cache bounds [0, {max_seq}]")


def _write_cache(cache_arr: jax.Array, new: jax.Array,
                 cache_len: jax.Array) -> jax.Array:
    """Write an s-token update starting at position cache_len.

    cache_len scalar: same position for the whole batch (dry-run shapes).
    cache_len (B,): per-slot positions (continuous batching / chunked
    prefill — each slot's chunk lands at its own offset).
    new: (B, s, ...) slice to write into cache (B, S, ...).

    Concrete out-of-range offsets raise :class:`CacheLenError`; traced
    ones drop the out-of-range rows (scatter mode="drop") rather than
    silently wrapping around.
    """
    s = new.shape[1]
    _check_cache_len(cache_len, s, cache_arr.shape[1])
    if cache_len.ndim == 0:
        start = (0, cache_len) + (0,) * (cache_arr.ndim - 2)
        return jax.lax.dynamic_update_slice(cache_arr,
                                            new.astype(cache_arr.dtype), start)
    b = new.shape[0]
    if s == 1:
        return cache_arr.at[jnp.arange(b), cache_len].set(
            new[:, 0].astype(cache_arr.dtype), mode="drop")
    rows = jnp.arange(b)[:, None]
    cols = cache_len[:, None] + jnp.arange(s)[None, :]
    return cache_arr.at[rows, cols].set(new.astype(cache_arr.dtype),
                                        mode="drop")


# ---------------------------------------------------------------------------
# paged cache: gather/scatter through a block table
# ---------------------------------------------------------------------------


def paged_write(pool: jax.Array, new: jax.Array, cache_len: jax.Array,
                table: jax.Array, write_mask: jax.Array) -> jax.Array:
    """Scatter an s-token update into the page pool via the block table.

    pool: (rows, page_size, ...) — local page pool; row 0 is the null
    page. table: (B, pages_per_slot) int32 of *global* page ids (``%
    rows`` recovers the local row on every shard — the host allocator
    guarantees a slot's pages live in its own shard's sub-pool).
    write_mask: (B,) — masked-off slots (idle, or mid-prefill during a
    decode step) land their writes in the null page instead of page 0 of
    their table row, which may be a *shared prefix* page.
    """
    rows, ps = pool.shape[0], pool.shape[1]
    b, s = new.shape[:2]
    n_pages = table.shape[1]
    pos = cache_len[:, None] + jnp.arange(s)[None, :]           # (B, s)
    page_idx = pos // ps
    ok = (page_idx < n_pages) & write_mask[:, None]
    local = jnp.take_along_axis(
        table, jnp.minimum(page_idx, n_pages - 1), axis=1) % rows
    local = jnp.where(ok, local, 0)                             # null page
    flat = local * ps + pos % ps                                # (B, s)
    pool_flat = pool.reshape((rows * ps,) + pool.shape[2:])
    pool_flat = pool_flat.at[flat].set(new.astype(pool.dtype), mode="drop")
    return pool_flat.reshape(pool.shape)


def paged_gather(pool: jax.Array, table: jax.Array) -> jax.Array:
    """Assemble each slot's logical cache view from its pages.

    (rows, page_size, ...) gathered through (B, pages_per_slot) ->
    (B, pages_per_slot * page_size, ...): a drop-in replacement for the
    slot cache's (B, S, ...) that downstream length masks treat
    identically (positions past ``cache_len`` read unwritten/null pages
    and are masked to exact zeros by the softmax)."""
    rows = pool.shape[0]
    b, n_pages = table.shape
    g = jnp.take(pool, table % rows, axis=0)    # (B, n_pages, ps, ...)
    return g.reshape((b, n_pages * pool.shape[1]) + pool.shape[2:])


def _len_mask(length: jax.Array, s: int) -> jax.Array:
    """valid-position mask; (s,) for scalar length, (B, s) for vector."""
    pos = jnp.arange(s)
    if length.ndim == 0:
        return pos < length
    return pos[None, :] < length[:, None]


def _apply_len_mask(logits: jax.Array, valid: jax.Array) -> jax.Array:
    """logits: (b, ..., s); valid: (s,) scalar-length or (b, s) per-slot."""
    if valid.ndim == 1:
        shape = (1,) * (logits.ndim - 1) + (valid.shape[-1],)
    else:
        shape = (valid.shape[0],) + (1,) * (logits.ndim - 2) + (valid.shape[-1],)
    return jnp.where(valid.reshape(shape), logits, NEG_INF)


# ---------------------------------------------------------------------------
# chunked (flash-style) attention core
# ---------------------------------------------------------------------------


def chunked_attention(
    q: jax.Array,  # (B, Sq, Hq, Dk)
    k: jax.Array,  # (B, Skv, Hkv, Dk)
    v: jax.Array,  # (B, Skv, Hkv, Dv)
    *,
    causal: bool,
    window: Optional[int],
    chunk: int,
    q_offset: int = 0,
    scale: Optional[float] = None,
) -> jax.Array:
    """Online-softmax attention, scanning over KV chunks."""
    b, sq, hq, dk = q.shape
    _, skv, hkv, _ = k.shape
    dv = v.shape[-1]
    g = hq // hkv
    scale = scale if scale is not None else dk ** -0.5
    chunk = min(chunk, skv)
    valid_kv = skv
    if skv % chunk:  # pad KV to a chunk multiple; pad keys are masked off
        pad = chunk - skv % chunk
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        skv += pad
    n_chunks = skv // chunk

    # operands stay in the model dtype; accumulation is f32 via
    # preferred_element_type — casting k/v to f32 materializes full f32
    # copies of the KV stream (measured 2x memory term; EXPERIMENTS §Perf)
    qf = (q * jnp.asarray(scale, q.dtype)).reshape(b, sq, hkv, g, dk)
    kc = k.reshape(b, n_chunks, chunk, hkv, dk).swapaxes(0, 1)
    vc = v.reshape(b, n_chunks, chunk, hkv, dv).swapaxes(0, 1)
    q_pos = q_offset + jnp.arange(sq)

    def step(carry, inp):
        m, l, o = carry  # (b,sq,hkv,g), same, (b,sq,hkv,g,dv)
        kb, vb, c0 = inp
        s = acc_einsum("bqhgd,bchd->bqhgc", qf, kb)  # (b,sq,hkv,g,chunk)
        kv_pos = c0 + jnp.arange(chunk)
        mask = jnp.broadcast_to((kv_pos < valid_kv)[None, :], (sq, chunk))
        if causal:
            mask = mask & (q_pos[:, None] >= kv_pos[None, :])
        if window is not None:
            mask = mask & (q_pos[:, None] - kv_pos[None, :] < window)
        s = jnp.where(mask[None, :, None, None, :], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(-1)
        o_new = o * corr[..., None] + acc_einsum(
            "bqhgc,bchd->bqhgd", p.astype(v.dtype), vb)
        return (m_new, l_new, o_new), None

    m0 = jnp.full((b, sq, hkv, g), NEG_INF, dtype=jnp.float32)
    l0 = jnp.zeros((b, sq, hkv, g), dtype=jnp.float32)
    o0 = jnp.zeros((b, sq, hkv, g, dv), dtype=jnp.float32)
    starts = jnp.arange(n_chunks) * chunk
    (m, l, o), _ = jax.lax.scan(step, (m0, l0, o0), (kc, vc, starts))
    out = o / jnp.maximum(l[..., None], 1e-30)
    return out.reshape(b, sq, hq, dv).astype(q.dtype)


def decode_attention(
    q: jax.Array,  # (B, sq, Hq, Dk) — sq > 1 only with q_positions
    k: jax.Array,  # (B, S, Hkv, Dk) — S may be sharded over 'model'
    v: jax.Array,  # (B, S, Hkv, Dv)
    *,
    length: jax.Array,  # valid cache length (scalar int32)
    window: Optional[int],
    scale: Optional[float] = None,
    q_positions: Optional[jax.Array] = None,  # (sq,) or (B, sq)
) -> jax.Array:
    """One-token attention as plain (SPMD-friendly) reductions over S.

    With ``q_positions`` (absolute position of every query token) the same
    math serves chunked prefill: each query attends causally — cache slot
    ``j`` is visible iff ``j <= q_pos`` — so a multi-token chunk against
    an already-partially-filled cache reproduces full-prefill masking.
    """
    b, sq, hq, dk = q.shape
    _, s, hkv, _ = k.shape
    g = hq // hkv
    scale = scale if scale is not None else dk ** -0.5
    # bf16 operands + f32 accumulation: casting the (sharded, huge) cache
    # to f32 would materialize f32 copies of it every step
    qf = (q * jnp.asarray(scale, q.dtype)).reshape(b, sq, hkv, g, dk)
    logits = acc_einsum("bqhgd,bshd->bqhgs", qf, k.astype(q.dtype))
    pos = jnp.arange(s)
    if q_positions is not None:
        qp = (q_positions if q_positions.ndim == 2
              else q_positions[None, :])  # (B|1, sq)
        valid = pos[None, None, :] <= qp[..., None]  # causal vs cache slots
        if window is not None:
            valid &= pos[None, None, :] > qp[..., None] - window
        logits = jnp.where(valid[:, :, None, None, :], logits, NEG_INF)
    else:
        valid = _len_mask(length, s)
        if window is not None:
            if length.ndim == 0:
                valid &= pos >= length - window
            else:
                valid &= pos[None, :] >= (length - window)[:, None]
        logits = _apply_len_mask(logits, valid)
    m = logits.max(-1, keepdims=True)
    p = jnp.exp(logits - m)
    p = p / p.sum(-1, keepdims=True)
    out = acc_einsum("bqhgs,bshd->bqhgd", p.astype(q.dtype),
                     v.astype(q.dtype))
    return out.reshape(b, sq, hq, -1).astype(q.dtype)


# ---------------------------------------------------------------------------
# GQA
# ---------------------------------------------------------------------------


def gqa_init(
    key: jax.Array,
    d_model: int,
    cfg: AttnConfig,
    *,
    sp: Optional[SparsityConfig] = None,
    param_dtype=jnp.float32,
    qk_norm: bool = False,
) -> dict:
    ks = jax.random.split(key, 4)
    p = {
        "wq": linear_init(ks[0], d_model, cfg.q_heads * cfg.head_dim,
                          sp=sp, target="attn_proj", param_dtype=param_dtype),
        "wk": linear_init(ks[1], d_model, cfg.kv_heads * cfg.head_dim,
                          sp=sp, target="attn_proj", param_dtype=param_dtype),
        "wv": linear_init(ks[2], d_model, cfg.kv_heads * cfg.head_dim,
                          sp=sp, target="attn_proj", param_dtype=param_dtype),
        "wo": linear_init(ks[3], cfg.q_heads * cfg.head_dim, d_model,
                          sp=sp, target="attn_proj", param_dtype=param_dtype),
    }
    if qk_norm:
        p["q_norm"] = rmsnorm_init(cfg.head_dim, param_dtype)
        p["k_norm"] = rmsnorm_init(cfg.head_dim, param_dtype)
    return p


def gqa_empty_cache(
    batch: int, max_seq: int, cfg: AttnConfig, dtype=DEFAULT_COMPUTE_DTYPE
) -> dict:
    shape = (batch, max_seq, cfg.kv_heads, cfg.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def gqa_apply(
    params: dict,
    x: jax.Array,  # (B, S, D)
    cfg: AttnConfig,
    *,
    view: Optional[CacheView] = None,
    cache: Optional[dict] = None,
    rope_theta: float = 10_000.0,
    chunk: int = 512,
    cross_kv: Optional[tuple[jax.Array, jax.Array]] = None,
    **kw,
):
    """Returns (y, new_cache). ``view`` is the typed cache-addressing
    struct (:class:`repro.models.cache.CacheView`) — mode, positions,
    cache_len and paged addressing in one pytree; None means train.
    cross_kv supplies precomputed encoder K/V for cross-attention
    (whisper); cache is then unused. ``view.block_table`` switches
    decode/chunk to the paged cache: ``cache`` leaves are page pools
    (rows, page_size, ...), writes scatter through the table (masked
    slots into the null page) and reads gather each slot's logical view
    — per-slot ``cache_len`` semantics are unchanged. With ``cfg.mask``
    set, self-attention routes through the block-sparse families.

    The old loose keywords (mode/positions/cache_len/block_table/
    write_mask) still work for one release via the deprecation shim."""
    view = view_from_legacy_kwargs(view, kw, caller="gqa_apply")
    if kw:
        raise AttnKwargError(
            f"gqa_apply got unknown keyword(s) {sorted(kw)}")
    if view is None:
        view = CacheView.train()
    mode = view.mode
    cache_len = view.cache_len
    block_table = view.block_table
    write_mask = view.write_mask
    b, s, _ = x.shape
    positions = view.positions
    if positions is None:
        positions = jnp.arange(s)
    q = linear_apply(params["wq"], x, name="attn_proj_wq").reshape(
        b, s, cfg.q_heads, cfg.head_dim)
    if cross_kv is None:
        k = linear_apply(params["wk"], x, name="attn_proj_wk").reshape(
            b, s, cfg.kv_heads, cfg.head_dim)
        v = linear_apply(params["wv"], x, name="attn_proj_wv").reshape(
            b, s, cfg.kv_heads, cfg.head_dim)
    else:
        k, v = cross_kv
    if "q_norm" in params:
        q = rmsnorm_apply(params["q_norm"], q)
        if cross_kv is None:
            k = rmsnorm_apply(params["k_norm"], k)
    if cfg.rope and cross_kv is None:
        q = apply_rope(q, positions, rope_theta)
        k = apply_rope(k, positions, rope_theta)

    new_cache = cache
    if mode in ("decode", "chunk") and cross_kv is None:
        assert cache is not None and cache_len is not None
        if block_table is not None:
            assert write_mask is not None
            with jax.named_scope("kv_write"):
                k_cache = paged_write(cache["k"], k, cache_len,
                                      block_table, write_mask)
                v_cache = paged_write(cache["v"], v, cache_len,
                                      block_table, write_mask)
            new_cache = {"k": k_cache, "v": v_cache}
            k_view = paged_gather(k_cache, block_table)
            v_view = paged_gather(v_cache, block_table)
        else:
            with jax.named_scope("kv_write"):
                k_view = k_cache = _write_cache(cache["k"], k, cache_len)
                v_view = v_cache = _write_cache(cache["v"], v, cache_len)
            new_cache = {"k": k_cache, "v": v_cache}
        # chunk (multi-token prefill piece): causal masking via absolute
        # query positions; decode (s=1) keeps the plain length mask.
        # cfg.mask swaps in the mask-aware decode family (the spec's own
        # causal/window semantics replace cfg.window).
        if cfg.mask is not None:
            out = bs_ops.bs_attention_decode(
                q, k_view, v_view, spec=cfg.mask, length=cache_len + s,
                q_positions=positions if mode == "chunk" else None,
            )
        else:
            out = decode_attention(
                q, k_view, v_view, length=cache_len + s, window=cfg.window,
                q_positions=positions if mode == "chunk" else None,
            )
    elif mode == "decode":  # cross-attention decode: static KV, full attend
        out = decode_attention(
            q, k, v, length=jnp.int32(k.shape[1]), window=None
        )
    elif cfg.mask is not None and cross_kv is None:
        # block-sparse prefill/train: dispatch the bs_attention family
        # (pair-list kernel / block gather; dense fallback under budgets)
        out = bs_ops.bs_attention(q, k, v, spec=cfg.mask)
    else:
        out = chunked_attention(
            q, k, v, causal=cfg.causal and cross_kv is None,
            window=cfg.window, chunk=chunk,
        )
    if mode == "prefill" and cross_kv is None:
        assert cache is not None
        with jax.named_scope("kv_write"):
            k_cache = jax.lax.dynamic_update_slice(
                cache["k"], k.astype(cache["k"].dtype), (0, 0, 0, 0)
            )
            v_cache = jax.lax.dynamic_update_slice(
                cache["v"], v.astype(cache["v"].dtype), (0, 0, 0, 0)
            )
        new_cache = {"k": k_cache, "v": v_cache}
    # wo is row-parallel under TP serving (local heads in, full d_model
    # out): per-shard output is a partial sum — reduced here only when the
    # serving engine declared the in-axis sharded, identity elsewhere
    y = tp_reduce(linear_apply(params["wo"], out.reshape(b, s, -1),
                               name="attn_proj_wo"), "attn_out")
    return y, new_cache


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V2)
# ---------------------------------------------------------------------------


def mla_init(
    key: jax.Array,
    d_model: int,
    cfg: AttnConfig,
    *,
    sp: Optional[SparsityConfig] = None,
    param_dtype=jnp.float32,
) -> dict:
    ks = jax.random.split(key, 8)
    h = cfg.q_heads
    qk_dim = cfg.nope_head_dim + cfg.rope_head_dim
    p = {}
    if cfg.q_lora_rank:
        p["wq_a"] = linear_init(ks[0], d_model, cfg.q_lora_rank, sp=sp,
                                target="attn_proj", param_dtype=param_dtype)
        p["q_a_norm"] = rmsnorm_init(cfg.q_lora_rank, param_dtype)
        p["wq_b"] = linear_init(ks[1], cfg.q_lora_rank, h * qk_dim, sp=sp,
                                target="attn_proj", param_dtype=param_dtype)
    else:
        p["wq"] = linear_init(ks[0], d_model, h * qk_dim, sp=sp,
                              target="attn_proj", param_dtype=param_dtype)
    p["wkv_a"] = linear_init(ks[2], d_model, cfg.kv_lora_rank, sp=sp,
                             target="attn_proj", param_dtype=param_dtype)
    p["kv_a_norm"] = rmsnorm_init(cfg.kv_lora_rank, param_dtype)
    p["wk_rope"] = linear_init(ks[3], d_model, cfg.rope_head_dim, sp=sp,
                               target="attn_proj", param_dtype=param_dtype)
    # up-projections from the latent: stored per-head for absorbed decode
    p["w_uk"] = (
        jax.random.normal(ks[4], (h, cfg.kv_lora_rank, cfg.nope_head_dim))
        * cfg.kv_lora_rank ** -0.5
    ).astype(param_dtype)
    p["w_uv"] = (
        jax.random.normal(ks[5], (h, cfg.kv_lora_rank, cfg.v_head_dim))
        * cfg.kv_lora_rank ** -0.5
    ).astype(param_dtype)
    p["wo"] = linear_init(ks[6], h * cfg.v_head_dim, d_model, sp=sp,
                          target="attn_proj", param_dtype=param_dtype)
    return p


def mla_empty_cache(
    batch: int, max_seq: int, cfg: AttnConfig, dtype=DEFAULT_COMPUTE_DTYPE
) -> dict:
    return {
        "ckv": jnp.zeros((batch, max_seq, cfg.kv_lora_rank), dtype),
        "kr": jnp.zeros((batch, max_seq, cfg.rope_head_dim), dtype),
    }


def _mla_q(params, x, cfg, positions, rope_theta):
    b, s, _ = x.shape
    h = cfg.q_heads
    qk_dim = cfg.nope_head_dim + cfg.rope_head_dim
    if "wq_a" in params:
        cq = rmsnorm_apply(params["q_a_norm"],
                           linear_apply(params["wq_a"], x,
                                        name="attn_proj_wq_a"))
        q = linear_apply(params["wq_b"], cq, name="attn_proj_wq_b")
    else:
        q = linear_apply(params["wq"], x, name="attn_proj_wq")
    q = q.reshape(b, s, h, qk_dim)
    q_nope = q[..., : cfg.nope_head_dim]
    q_rope = apply_rope(q[..., cfg.nope_head_dim:], positions, rope_theta)
    return q_nope, q_rope


def mla_apply(
    params: dict,
    x: jax.Array,
    cfg: AttnConfig,
    *,
    view: Optional[CacheView] = None,
    cache: Optional[dict] = None,
    rope_theta: float = 10_000.0,
    chunk: int = 512,
    **kw,
):
    """MLA self-attention over a :class:`~repro.models.cache.CacheView`
    (same contract as :func:`gqa_apply`; legacy keywords shimmed for one
    release). With ``cfg.mask`` set, train/prefill routes the
    ``bs_attention`` family over the materialized per-head K/V; the
    absorbed decode/chunk path — whose two-term latent logits never form
    (B, S, H, D) K/V operands — applies the spec's token predicate
    inline on the logits instead (no dispatch-family record)."""
    view = view_from_legacy_kwargs(view, kw, caller="mla_apply")
    if kw:
        raise AttnKwargError(
            f"mla_apply got unknown keyword(s) {sorted(kw)}")
    if view is None:
        view = CacheView.train()
    mode = view.mode
    cache_len = view.cache_len
    block_table = view.block_table
    write_mask = view.write_mask
    b, s, _ = x.shape
    positions = view.positions
    if positions is None:
        positions = jnp.arange(s)
    h = cfg.q_heads
    q_nope, q_rope = _mla_q(params, x, cfg, positions, rope_theta)
    ckv = rmsnorm_apply(params["kv_a_norm"],
                        linear_apply(params["wkv_a"], x,
                                     name="attn_proj_wkv_a"))
    kr = apply_rope(
        linear_apply(params["wk_rope"], x, name="attn_proj_wk_rope")
        [:, :, None, :], positions, rope_theta
    )[:, :, 0, :]  # (b, s, rope_dim), shared across heads

    w_uk = params["w_uk"].astype(q_nope.dtype)  # (h, lora, nope)
    w_uv = params["w_uv"].astype(q_nope.dtype)  # (h, lora, v)
    scale = (cfg.nope_head_dim + cfg.rope_head_dim) ** -0.5

    new_cache = cache
    if mode in ("decode", "chunk"):
        assert cache is not None and cache_len is not None
        if block_table is not None:
            assert write_mask is not None
            with jax.named_scope("kv_write"):
                ckv_c = paged_write(cache["ckv"], ckv, cache_len,
                                    block_table, write_mask)
                kr_c = paged_write(cache["kr"], kr, cache_len,
                                   block_table, write_mask)
            new_cache = {"ckv": ckv_c, "kr": kr_c}
            ckv_v = paged_gather(ckv_c, block_table)
            kr_v = paged_gather(kr_c, block_table)
        else:
            with jax.named_scope("kv_write"):
                ckv_v = ckv_c = _write_cache(cache["ckv"], ckv, cache_len)
                kr_v = kr_c = _write_cache(cache["kr"], kr, cache_len)
            new_cache = {"ckv": ckv_c, "kr": kr_c}
        # absorbed attention over the compressed cache (MLA decode):
        #   logits = q_nope W_uk . ckv + q_rope . kr
        # operands stay bf16 (f32 casts of the cache would materialize f32
        # copies of it); accumulation is f32 via preferred_element_type
        dt = x.dtype
        q_abs = acc_einsum("bqhd,hcd->bqhc", q_nope, w_uk).astype(dt)
        logits = acc_einsum("bqhc,bsc->bqhs", q_abs, ckv_v.astype(dt))
        logits += acc_einsum("bqhr,bsr->bqhs", q_rope, kr_v.astype(dt))
        logits *= scale
        if cfg.mask is not None:
            # absorbed path: the spec's token predicate applied inline —
            # positions are the queries' absolute positions in both
            # decode and chunk modes, so one expression covers both.
            # Cache-validity (slot j written iff j <= q position) rides
            # along as the causal term of the predicate intersection.
            S = ckv_v.shape[1]
            qp = positions if positions.ndim == 2 else positions[None, :]
            kp = jnp.arange(S)
            cvalid = jnp_token_mask(
                cfg.mask, qp[..., None], kp[None, None, :],
                max_q=S, max_k=S)
            cvalid &= kp[None, None, :] <= qp[..., None]  # (B|1, sq, S)
            logits = jnp.where(cvalid[:, :, None, :], logits, NEG_INF)
        elif mode == "chunk":
            # multi-token prefill piece: cache slot j visible to query
            # token i iff j <= position(i) — logits are (b, sq, h, S)
            qp = positions if positions.ndim == 2 else positions[None, :]
            cvalid = (jnp.arange(ckv_v.shape[1])[None, None, :]
                      <= qp[..., None])  # (B|1, sq, S)
            logits = jnp.where(cvalid[:, :, None, :], logits, NEG_INF)
        else:
            valid = _len_mask(cache_len + s, ckv_v.shape[1])
            logits = _apply_len_mask(logits, valid)
        m = logits.max(-1, keepdims=True)
        p = jnp.exp(logits - m)
        p = p / p.sum(-1, keepdims=True)
        o_abs = acc_einsum("bqhs,bsc->bqhc", p.astype(dt),
                           ckv_v.astype(dt)).astype(dt)
        out = acc_einsum("bqhc,hcv->bqhv", o_abs, w_uv)
        out = out.astype(x.dtype)
    else:
        # train/prefill: materialize per-head K/V from the latent, use the
        # chunked flash path. K = [k_nope | kr broadcast], V = v.
        k_nope = jnp.einsum("bsc,hcd->bshd", ckv, w_uk)  # (b,s,h,nope)
        vfull = jnp.einsum("bsc,hcv->bshv", ckv, w_uv)  # (b,s,h,v)
        kr_b = jnp.broadcast_to(kr[:, :, None, :], (b, s, h, cfg.rope_head_dim))
        k = jnp.concatenate([k_nope, kr_b], axis=-1)
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
        if cfg.mask is not None:
            out = bs_ops.bs_attention(q, k, vfull, spec=cfg.mask,
                                      scale=scale)
        else:
            out = chunked_attention(
                q, k, vfull, causal=True, window=cfg.window, chunk=chunk,
                scale=scale
            )
        if mode == "prefill":
            assert cache is not None
            with jax.named_scope("kv_write"):
                ckv_c = jax.lax.dynamic_update_slice(
                    cache["ckv"], ckv.astype(cache["ckv"].dtype), (0, 0, 0)
                )
                kr_c = jax.lax.dynamic_update_slice(
                    cache["kr"], kr.astype(cache["kr"].dtype), (0, 0, 0)
                )
            new_cache = {"ckv": ckv_c, "kr": kr_c}
    y = tp_reduce(
        linear_apply(params["wo"], out.reshape(b, s, h * cfg.v_head_dim),
                     name="attn_proj_wo"),
        "attn_out")
    return y, new_cache


# ---------------------------------------------------------------------------
# dispatch
# ---------------------------------------------------------------------------


def attn_init(key, d_model, cfg: AttnConfig, *, sp=None, param_dtype=jnp.float32,
              qk_norm: bool = False):
    if cfg.kind == "mla":
        return mla_init(key, d_model, cfg, sp=sp, param_dtype=param_dtype)
    return gqa_init(key, d_model, cfg, sp=sp, param_dtype=param_dtype,
                    qk_norm=qk_norm)


def attn_apply(params, x, cfg: AttnConfig, *, view: Optional[CacheView] = None,
               cache: Optional[dict] = None, rope_theta: float = 10_000.0,
               chunk: int = 512, cross_kv=None, **kw):
    """Kind dispatch with a *typed* keyword surface: every keyword is
    validated against the resolved cache kind before the apply runs —
    unknown keys raise :class:`~repro.models.cache.AttnKwargError`
    instead of the old silent ``**kw`` passthrough (where a typo like
    ``cache_length=`` was dropped on the floor). Legacy addressing
    keywords route through the one-release shim first."""
    view = view_from_legacy_kwargs(view, kw, caller="attn_apply")
    if kw:
        valid = "view, cache, rope_theta, chunk" + (
            ", cross_kv" if cfg.kind == "gqa" else "")
        raise AttnKwargError(
            f"attn_apply got unknown keyword(s) {sorted(kw)} for cache "
            f"kind {cfg.kind!r}; valid keywords: {valid}")
    if cfg.kind == "mla":
        if cross_kv is not None:
            raise AttnKwargError(
                "cross_kv is only valid for the 'gqa' cache kind; 'mla' "
                "is self-attention only")
        return mla_apply(params, x, cfg, view=view, cache=cache,
                         rope_theta=rope_theta, chunk=chunk)
    return gqa_apply(params, x, cfg, view=view, cache=cache,
                     rope_theta=rope_theta, chunk=chunk, cross_kv=cross_kv)


def attn_empty_cache(batch, max_seq, cfg: AttnConfig, dtype=DEFAULT_COMPUTE_DTYPE):
    if cfg.kind == "mla":
        return mla_empty_cache(batch, max_seq, cfg, dtype)
    return gqa_empty_cache(batch, max_seq, cfg, dtype)
