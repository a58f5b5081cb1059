"""Mixture-of-Experts with sort-based token dispatch and fixed capacity.

Dispatch is built from XLA-native sort/scatter/gather so it partitions
under pjit: the expert buffer (E, C, D) is sharded over the "model" axis
(expert parallelism); token movement between the data-sharded token axis
and the expert-sharded buffer lowers to all-to-all style collectives chosen
by the SPMD partitioner.

Expert FFN weights are sparse-eligible (target "expert") — for DeepSeek-V2
expert weights dominate total bytes, making them the paper technique's
biggest beneficiary (DESIGN.md §6).

Routing follows DeepSeek-V2: softmax scores, top-k selection, no renorm,
plus n_shared always-active shared experts; aux load-balance loss returned
to the caller.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import FFNConfig, MoEConfig, SparsityConfig
from repro.models.common import linear_apply, linear_init
from repro.models.ffn import ffn_apply, ffn_init
from repro.parallel.hints import shard_hint


def _round_up(x: int, mult: int) -> int:
    return -(-x // mult) * mult


def capacity(tokens: int, cfg: MoEConfig) -> int:
    return max(8, _round_up(int(tokens * cfg.top_k / cfg.n_experts
                                 * cfg.capacity_factor), 8))


def moe_init(
    key: jax.Array,
    d_model: int,
    cfg: MoEConfig,
    *,
    sp: Optional[SparsityConfig] = None,
    param_dtype=jnp.float32,
) -> dict:
    ks = jax.random.split(key, 2 + cfg.n_experts)
    router = linear_init(ks[0], d_model, cfg.n_experts, sp=None,
                         target="router", param_dtype=jnp.float32)
    expert_keys = jnp.stack(list(ks[2:]))
    experts = jax.vmap(
        lambda k: ffn_init(
            k, d_model, FFNConfig(d_ff=cfg.d_expert, act=cfg.act),
            sp=sp, param_dtype=param_dtype, target="expert",
        )
    )(expert_keys)
    p = {"router": router, "experts": experts}
    if cfg.n_shared:
        p["shared"] = ffn_init(
            ks[1], d_model, FFNConfig(d_ff=cfg.n_shared * cfg.d_expert, act=cfg.act),
            sp=sp, param_dtype=param_dtype, target="expert",
        )
    return p


def _expert_ffn(params, xe: jax.Array, cfg: MoEConfig):
    """xe: (E, C, D) -> (E, C, D), vmapped over the expert axis."""
    fcfg = FFNConfig(d_ff=cfg.d_expert, act=cfg.act)
    return jax.vmap(lambda pp, xx: ffn_apply(pp, xx, fcfg, target="expert"))(
        params, xe)


def moe_apply(
    params: dict,
    x: jax.Array,  # (B, S, D)
    cfg: MoEConfig,
):
    """Returns (y, aux_loss). Dispatches to the shard_map expert-parallel
    path under an active multi-device mesh, else the single-device path."""
    from repro.parallel.hints import _active_mesh

    mesh = _active_mesh()
    if mesh is not None and "model" in mesh.axis_names \
            and cfg.n_experts % mesh.shape["model"] == 0:
        return _moe_apply_shard_map(params, x, cfg, mesh)
    return _moe_apply_local(params, x, cfg)


def _moe_apply_local(
    params: dict,
    x: jax.Array,  # (B, S, D)
    cfg: MoEConfig,
):
    b, s, d = x.shape
    t = b * s
    xf = x.reshape(t, d)
    k = cfg.top_k
    e = cfg.n_experts
    c = capacity(t, cfg)

    xf = shard_hint(xf, ("pod", "data"), None)
    logits = linear_apply(params["router"], xf,
                          compute_dtype=jnp.float32)  # (T, E) fp32
    scores = jax.nn.softmax(logits, axis=-1)
    gate_w, sel = jax.lax.top_k(scores, k)  # (T, k)

    # ---- sort-based dispatch ------------------------------------------------
    flat_e = sel.reshape(-1)  # (T*k,) expert id per expanded token
    order = jnp.argsort(flat_e)  # stable
    sorted_e = flat_e[order]
    # rank within expert: position among same-expert entries
    starts = jnp.searchsorted(sorted_e, jnp.arange(e, dtype=sorted_e.dtype),
                              side="left")
    rank = jnp.arange(t * k) - starts[sorted_e]
    keep = rank < c
    token_of = order // k
    # 3D scatter with OOB drop: overflow (rank >= c) lands out of bounds.
    gathered = shard_hint(xf[token_of], ("pod", "data"), None)
    buf = jnp.zeros((e, c, d), dtype=x.dtype).at[sorted_e, rank].set(
        gathered, mode="drop")
    buf = shard_hint(buf, "model", None, None)

    h = _expert_ffn(params["experts"], buf, cfg)  # (E, C, D)
    h = shard_hint(h, "model", None, None)

    out_sorted = jnp.where(
        keep[:, None],
        h[sorted_e, jnp.minimum(rank, c - 1)], 0.0)
    out_sorted = shard_hint(out_sorted, ("pod", "data"), None)
    # unsort and combine with gate weights
    out_flat = jnp.zeros((t * k, d), dtype=h.dtype).at[order].set(out_sorted)
    out_flat = shard_hint(out_flat, ("pod", "data"), None)
    y = (out_flat.reshape(t, k, d)
         * gate_w.astype(h.dtype)[..., None]).sum(axis=1)
    y = shard_hint(y, ("pod", "data"), None)

    if "shared" in params:
        y = y + ffn_apply(
            params["shared"], xf,
            FFNConfig(d_ff=cfg.n_shared * cfg.d_expert, act=cfg.act),
            target="expert",
        )

    # load-balance aux loss (Switch-style): E * sum_e f_e * P_e
    dispatch_frac = jnp.mean(
        (jax.nn.one_hot(sel, e, dtype=jnp.float32)).sum(1), axis=0
    ) / k
    router_prob = jnp.mean(scores, axis=0)
    aux = cfg.router_aux_coef * e * jnp.sum(dispatch_frac * router_prob)
    return y.reshape(b, s, d), aux


# ---------------------------------------------------------------------------
# shard_map expert-parallel path (device-limited routing)
#
# Activations are batch-sharded over ("pod","data") and replicated over
# "model"; experts are sharded over "model" (EP). Every model-rank routes
# its local tokens, computes ONLY its own experts over them, and the
# partial outputs are psum'd over "model" — the same reduction a
# tensor-parallel dense FFN would do. All gathers/sorts are shard-local,
# so the SPMD partitioner never rewrites them (the pure-pjit path
# materializes per-element u32 index maps for cross-shard scatter — the
# dominant memory term before this path existed; see EXPERIMENTS.md §Perf).
#
# Capacity and the balance aux are per data shard (GShard "group"
# semantics): drops are local, and aux equals the global loss up to the
# across-group variance (tests/test_moe_distributed.py).
# ---------------------------------------------------------------------------


def _moe_apply_shard_map(params, x, cfg: MoEConfig, mesh):
    from jax.sharding import PartitionSpec as P

    b, s, d = x.shape
    k, e = cfg.top_k, cfg.n_experts
    axes = mesh.axis_names
    # DP axes limited to those dividing the batch (decode may have B=1 ->
    # tokens replicated over data, which is the correct degenerate case)
    dp_list: list = []
    size = 1
    for a in ("pod", "data"):
        if a in axes and b % (size * mesh.shape[a]) == 0:
            dp_list.append(a)
            size *= mesh.shape[a]
    dp = tuple(dp_list)
    tp_size = mesh.shape["model"]
    e_loc = e // tp_size

    def local(x_blk, router, experts, shared):
        # x_blk: (B_loc, S, D) — this device's tokens, full model dim
        bl, sl, dl = x_blk.shape
        t_loc = bl * sl
        xf = x_blk.reshape(t_loc, dl)
        c_loc = capacity(t_loc, cfg)
        r = jax.lax.axis_index("model")

        logits = linear_apply(router, xf,
                              compute_dtype=jnp.float32)
        scores = jax.nn.softmax(logits, axis=-1)
        gate_w, sel = jax.lax.top_k(scores, k)  # (T, k)

        flat_e = sel.reshape(-1)
        order = jnp.argsort(flat_e)
        sorted_e = flat_e[order]
        starts = jnp.searchsorted(
            sorted_e, jnp.arange(e, dtype=sorted_e.dtype), side="left")
        counts = jnp.append(starts[1:], t_loc * k) - starts
        rank = jnp.arange(t_loc * k) - starts[sorted_e]
        sorted_x = xf[order // k]  # local gather
        sorted_x = jnp.concatenate(
            [sorted_x, jnp.zeros((c_loc, dl), sorted_x.dtype)], axis=0)

        own = jnp.arange(e_loc) + r * e_loc  # expert ids on this rank
        own_starts = starts[own]
        own_counts = jnp.minimum(counts[own], c_loc)

        def take(st):  # (C_loc, D) slice of the sorted token stream
            return jax.lax.dynamic_slice(sorted_x, (st, 0), (c_loc, dl))

        buf = jax.vmap(take)(own_starts)  # (E_loc, C_loc, D)
        mask = (jnp.arange(c_loc)[None, :]
                < own_counts[:, None])  # (E_loc, C_loc)
        buf = buf * mask[..., None].astype(buf.dtype)

        h = _expert_ffn(experts, buf, cfg)  # (E_loc, C_loc, D)
        h = (h * mask[..., None].astype(h.dtype)).reshape(e_loc * c_loc, dl)

        # local combine: row for sorted slot i lives at
        # (sorted_e[i]-r*e_loc)*C_loc + rank[i] when this rank owns it
        owned = (sorted_e >= r * e_loc) & (sorted_e < (r + 1) * e_loc) \
            & (rank < c_loc)
        hidx = jnp.clip((sorted_e - r * e_loc) * c_loc + rank, 0,
                        e_loc * c_loc - 1)
        out_sorted = jnp.where(owned[:, None], h[hidx], 0)
        inv = jnp.argsort(order)  # unsort by inverse permutation (gather)
        out_flat = out_sorted[inv]
        y = (out_flat.reshape(t_loc, k, dl)
             * gate_w.astype(out_flat.dtype)[..., None]).sum(axis=1)

        if shared is not None:
            # shared experts run TP-style: hidden dim pre-sharded over
            # "model" in the param specs -> partial sums here
            y = y + ffn_apply(
                shared, xf,
                FFNConfig(d_ff=cfg.n_shared * cfg.d_expert // tp_size,
                          act=cfg.act), target="expert")

        y = jax.lax.psum(y, "model")

        dispatch_frac = jnp.mean(
            jax.nn.one_hot(sel, e, dtype=jnp.float32).sum(1), axis=0) / k
        router_prob = jnp.mean(scores, axis=0)
        aux = cfg.router_aux_coef * e * jnp.sum(dispatch_frac * router_prob)
        aux = jax.lax.pmean(aux, dp) if dp else aux
        return y.reshape(bl, sl, dl), aux

    shared = params.get("shared")
    # param blocks: experts sharded over model on E; router replicated;
    # shared-expert hidden sharded over model (column/row parallel pair)
    expert_specs = jax.tree.map(lambda _: P("model"), params["experts"])
    shared_specs = None
    if shared is not None:
        shared_specs = {
            k_: jax.tree.map(
                lambda _: P("model", None) if k_ == "w_down"
                else P(None, "model"), v)
            for k_, v in shared.items()
        }
    in_specs = (P(dp, None, None),
                jax.tree.map(lambda _: P(), params["router"]),
                expert_specs, shared_specs)
    from repro import compat

    fn = compat.shard_map(
        local, mesh=mesh, in_specs=in_specs,
        out_specs=(P(dp, None, None), P()), check_vma=False)
    return fn(x, params["router"], params["experts"], shared)
