"""Serving engines: continuous batching over fixed-shape compiled steps.

``ServeEngine`` (single device) owns compiled prefill/decode steps and a
slot-based KV cache. The host loop is thin: all per-request decisions
live in ``repro.serving.scheduler`` and sampling happens on device
(``repro.serving.sampling``) with a threaded PRNG key — per-token logits
never round-trip to the host, only sampled ``(slots,)`` token ids do.
Admissions are batched: every engine step runs at most ONE prefill call
covering all admitted slots (the original engine ran one full
``slots x prefill_len`` forward per request and kept a single slot's
rows), and ``prefill_chunk`` splits long prompts into fixed-shape pieces
so time-to-first-token is bounded by one chunk's compute.

``ShardedServeEngine`` runs the same host loop with the steps wrapped in
``shard_map`` over a ("data", "model") device mesh: batch slots shard
over "data"; attention/FFN projections run tensor-parallel over "model"
with the head-aware specs from ``repro.parallel.sharding`` (NMWeight /
QNMWeight vals+idx+scales co-sharded, KV caches sharded on the head
axis), so the sparse Pallas kernels execute on their local shard of the
compressed operand with no mid-flight resharding; the row-parallel
partial sums are psum'd inside the model via ``hints.tp_reduce``.

Every device step is fixed-shape, so after the first prefill + decode
compile the engines never recompile — ``compiled_cache_sizes()`` exposes
the underlying jit cache sizes so tests (and fleet monitoring) can
assert exactly that.
"""
from __future__ import annotations

import os
import time
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs as _obs
from repro.configs.base import AttnConfig
from repro.models.cache import CacheView
from repro.models.transformer import LM
from repro.serving.paging import PageManager
from repro.serving.sampling import make_sampler
from repro.serving.scheduler import Request, Scheduler

__all__ = ["Request", "ServeEngine", "ShardedServeEngine",
           "make_serve_steps", "merge_cache_slot", "merge_cache_slots"]

# cache-leaf base ranks (without scan-stacking); leading extra axes are
# layer stacking, the batch axis sits right after them
_BASE_RANK = {"k": 4, "v": 4, "cross_k": 4, "cross_v": 4, "ckv": 3, "kr": 3,
              "conv": 3, "ssm": 3, "wkv": 4, "tm_last": 2, "cm_last": 2}


def _batch_axis(path, leaf) -> int:
    name = [getattr(k, "key", str(k)) for k in path][-1]
    return leaf.ndim - _BASE_RANK.get(name, leaf.ndim)


def merge_cache_slot(new: Any, old: Any, slot: int) -> Any:
    """Take slot `slot` (batch axis) from `new`, everything else from `old`."""

    def one(path, n, o):
        ax = _batch_axis(path, n)
        return jax.lax.dynamic_update_slice_in_dim(
            o, jax.lax.slice_in_dim(n, slot, slot + 1, axis=ax), slot, axis=ax)

    return jax.tree_util.tree_map_with_path(one, new, old)


def merge_cache_slots(new: Any, old: Any, keep: jax.Array) -> Any:
    """Batched ``merge_cache_slot``: keep the batch rows of `new` where
    ``keep`` (bool, length = batch) is set, `old` everywhere else.
    Element-select semantics make this bit-exact with per-slot merges."""

    def one(path, n, o):
        ax = _batch_axis(path, n)
        shape = [1] * n.ndim
        shape[ax] = n.shape[ax]
        return jnp.where(keep.reshape(shape), n, o)

    with jax.named_scope("kv_write"):
        return jax.tree_util.tree_map_with_path(one, new, old)


def make_serve_steps(lm: LM, *, jit: bool = True):
    """Returns (prefill_step, decode_step) pure fns (dry-run cells).

    prefill_step(params, tokens, caches)            -> (last_logits, caches)
    decode_step(params, token, caches, cache_len)   -> (logits, caches)
    """

    def prefill_step(params, tokens, caches, enc_input=None):
        logits, caches, _ = lm.forward(
            params, tokens, view=CacheView.prefill(), caches=caches,
            enc_input=enc_input)
        return logits[:, -1], caches

    def decode_step(params, token, caches, cache_len):
        logits, caches, _ = lm.forward(
            params, token, view=CacheView.decode(cache_len), caches=caches)
        return logits[:, 0], caches

    if jit:
        prefill_step = jax.jit(prefill_step)
        decode_step = jax.jit(decode_step)
    return prefill_step, decode_step


def _jit_cache_size(fn) -> int:
    """Compiled-signature count of a jitted fn (-1 when unavailable)."""
    try:
        return int(fn._cache_size())
    except (AttributeError, TypeError):
        return -1


class ServeEngine:
    """Slot-based continuous batching over fixed-shape compiled steps."""

    def __init__(self, lm: LM, params: Any, *, slots: int, max_seq: int,
                 prefill_len: int, temperature: float = 0.0, seed: int = 0,
                 autotune_blocks: bool = False,
                 quantize: Optional[str] = None,
                 prefill_chunk: Optional[int] = None,
                 strict: bool = False,
                 paged: bool = False,
                 page_size: Optional[int] = None,
                 pool_pages: Optional[int] = None,
                 obs=None):
        if quantize not in (None, "int8"):
            raise ValueError(
                f"quantize must be None or 'int8', got {quantize!r}")
        if quantize == "int8":
            # load-time weight quantization: every compressed NMWeight
            # leaf becomes an int8 QNMWeight (per-output-channel absmax
            # scales); dense / masked leaves are untouched. Decode then
            # streams one byte per kept value instead of two (bf16).
            from repro.quant import quantize_tree

            params = quantize_tree(params)
        self.lm = lm
        # observability: explicit bundle wins, else the process-global
        # one (None when off — every instrumented site is is-not-None
        # gated, so the off path allocates and records nothing)
        self.obs = obs if obs is not None else _obs.get_obs()
        self.slots = slots
        self.max_seq = max_seq
        self.prefill_len = prefill_len
        self.temperature = temperature
        self.strict = strict
        self.paged = paged
        self.page_manager: Optional[PageManager] = None
        chunk = prefill_chunk or prefill_len
        if paged:
            # paged KV: the cache becomes a pool of fixed-size pages
            # addressed through a per-slot block table. Prefill always
            # runs in mode="chunk" (offset writes through the table), so
            # the model needs the chunkable mixers even at full chunk.
            _validate_chunkable(lm.cfg)
            ps = int(page_size if page_size is not None
                     else os.environ.get("REPRO_KV_PAGE_SIZE") or chunk)
            groups = self._data_parallel()
            pool = int(pool_pages if pool_pages is not None
                       else os.environ.get("REPRO_KV_POOL_PAGES")
                       or slots * (max_seq // ps))
            if pool % groups:
                raise ValueError(
                    f"pool_pages={pool} must divide over the data-parallel "
                    f"degree ({groups}): each data shard owns an "
                    "independent sub-pool")
            self.page_manager = PageManager(
                page_size=ps, pages_per_group=pool // groups,
                slots=slots, max_seq=max_seq, groups=groups, obs=self.obs)
        self.scheduler = Scheduler(
            slots=slots, max_seq=max_seq, prefill_len=prefill_len,
            prefill_chunk=prefill_chunk, strict=strict,
            paging=self.page_manager, obs=self.obs)
        self.prefill_chunk = self.scheduler.prefill_chunk
        if self.prefill_chunk != prefill_len and not paged:
            _validate_chunkable(lm.cfg)
        self.params = params
        if autotune_blocks:
            # pre-pay the per-shape block sweep for every compressed GEMM
            # this engine will issue, so the first real request never eats
            # an inline autotune (results persist in the on-disk cache).
            self._autotune_sparse_blocks()
        self.params = self._place_params(self.params)
        self._sampler = make_sampler(temperature)
        self._key = jax.random.PRNGKey(seed)
        self._build_steps()
        # the paged pool reuses the slot-cache constructor: "batch" rows
        # become pool pages (row 0 of each shard's sub-pool = null page),
        # "max_seq" becomes the page size — same leaf layout, so the
        # sharded engine's cache pspecs apply unchanged
        if paged:
            pm = self.page_manager
            self.caches = self._place_caches(
                lm.init_cache(pm.rows, pm.page_size))
        else:
            self.caches = self._place_caches(lm.init_cache(slots, max_seq))
        self.decode_times: list[float] = []  # wall clock after each decode
        self.queue_depths: list[int] = []    # per-step admission backlog
        self.page_utils: list[float] = []    # per-step pool utilization
        self.steps = 0

    # ---- engine-flavour hooks (overridden by ShardedServeEngine) ---------

    def _data_parallel(self) -> int:
        return 1

    def _place_params(self, params: Any) -> Any:
        return params

    def _place_caches(self, caches: Any) -> Any:
        return caches

    def _build_steps(self) -> None:
        lm, sampler = self.lm, self._sampler
        full = self.prefill_chunk == self.prefill_len

        if self.paged:
            # no merge_cache_slots: the write mask itself gates the cache
            # (masked slots scatter into the null page), so the pool is
            # only ever touched at positions the scheduler owns
            def prefill_step(params, tokens, caches, cache_len, table,
                             mask, key):
                logits, new_caches, _ = lm.forward(
                    params, tokens, caches=caches,
                    view=CacheView.chunk(cache_len, block_table=table,
                                         write_mask=mask))
                toks, key = sampler(logits[:, -1], key)
                return toks, new_caches, key

            def decode_step(params, token, caches, cache_len, table,
                            mask, key):
                logits, new_caches, _ = lm.forward(
                    params, token, caches=caches,
                    view=CacheView.decode(cache_len, block_table=table,
                                          write_mask=mask))
                toks, key = sampler(logits[:, 0], key)
                return toks, new_caches, key

            self._prefill = jax.jit(prefill_step, donate_argnums=(2,))
            self._decode = jax.jit(decode_step, donate_argnums=(2,))
            return

        def prefill_logits(params, tokens, caches, cache_len, mask):
            if full:
                logits, new_caches, _ = lm.forward(
                    params, tokens, view=CacheView.prefill(), caches=caches)
            else:
                logits, new_caches, _ = lm.forward(
                    params, tokens, view=CacheView.chunk(cache_len),
                    caches=caches)
            new_caches = merge_cache_slots(new_caches, caches, mask)
            return logits[:, -1], new_caches

        def prefill_step(params, tokens, caches, cache_len, mask, key):
            logits, new_caches = prefill_logits(params, tokens, caches,
                                                cache_len, mask)
            toks, key = sampler(logits, key)
            return toks, new_caches, key

        def decode_step(params, token, caches, cache_len, mask, key):
            logits, new_caches, _ = lm.forward(
                params, token, view=CacheView.decode(cache_len),
                caches=caches)
            new_caches = merge_cache_slots(new_caches, caches, mask)
            toks, key = sampler(logits[:, 0], key)
            return toks, new_caches, key

        self._prefill = jax.jit(prefill_step, donate_argnums=(2,))
        self._decode = jax.jit(decode_step, donate_argnums=(2,))
        self._prefill_logits = jax.jit(prefill_logits)

    # ---- public API -------------------------------------------------------

    def prefill_logits(self, tokens) -> jax.Array:
        """Last-position logits of one prefill of ``tokens`` (slots,
        prefill_len) on fresh caches: what the prefill step samples from,
        through the engine's own model path (parity checks between
        kernel and reference paths, or sharded and single-device
        engines). The engine's caches and queue are untouched; this is a
        separate compiled program, so it does not count in
        :meth:`compiled_cache_sizes`."""
        if self.paged:
            raise NotImplementedError("prefill_logits: slot engines only")
        caches = self._place_caches(
            self.lm.init_cache(self.slots, self.max_seq))
        logits, _ = self._prefill_logits(
            self.params, jnp.asarray(tokens, jnp.int32), caches,
            jnp.zeros((self.slots,), jnp.int32),
            jnp.ones((self.slots,), bool))
        return logits

    def submit(self, req: Request) -> None:
        self.scheduler.submit(req, now=time.perf_counter())

    @property
    def queue(self) -> list:
        return self.scheduler.queue

    @property
    def finished(self) -> list:
        return self.scheduler.finished

    def compiled_cache_sizes(self) -> dict:
        """jit-cache entry counts for the two steps; after warmup these
        must stay at 1 each (fixed shapes => zero recompiles)."""
        return {"prefill": _jit_cache_size(self._prefill),
                "decode": _jit_cache_size(self._decode)}

    def step(self) -> None:
        """One engine step: (batched, possibly chunked) prefill for every
        slot with pending prompt pieces, then one decode for every slot
        whose prefill completed.

        With observability on, the step is the ``engine.step`` span
        (slots occupied and queue depth as it starts), and each phase a
        child span: ``engine.plan`` (the scheduler's plan),
        ``engine.prefill`` / ``engine.decode`` (the device call, synced
        on the sampled tokens) holding ``engine.upload``,
        ``engine.dispatch`` and ``engine.readback``, then
        ``engine.finish`` (the scheduler's bookkeeping)."""
        obs = self.obs
        if obs is None:
            self._step(_obs.null_span())
            return
        sched = self.scheduler
        occupied = sum(1 for s in sched.slots if s.req is not None)
        with obs.tracer.span("engine.step", step=self.steps,
                             occupied=occupied, queue=len(sched.queue)):
            self._step(obs.tracer.span)
            occupied = sum(1 for s in sched.slots if s.req is not None)
            obs.metrics.inc("serve_steps_total")
            obs.metrics.set_gauge("serve_slots_occupied", occupied)
            obs.metrics.set_gauge("serve_queue_depth", len(sched.queue))
            if self.page_manager is not None:
                # unified with PageStats: gauges mirror the same numbers
                # throughput_stats() reports
                obs.metrics.set_gauge("page_pool_utilization",
                                      self.page_utils[-1])
                obs.metrics.set_gauge(
                    "prefix_hit_rate",
                    self.page_manager.stats.prefix_hit_rate)

    def _step(self, span) -> None:
        sched = self.scheduler
        obs = self.obs
        with span("engine.plan"):
            pf = sched.plan_prefill()
        if pf is not None:
            # paged: snapshot the block table AFTER planning — admission
            # just assigned pages for the newly admitted slots
            tbl = self._table(span)
            with span("engine.prefill", step=self.steps,
                      active=len(pf.active), finishing=len(pf.finishing),
                      rows=pf.tokens.size, real_rows=pf.real_rows):
                toks_np = self._call(span, self._prefill, pf.tokens,
                                     pf.cache_len, tbl, pf.mask)
            with span("engine.finish"):
                sched.finish_prefill(pf, toks_np, now=time.perf_counter())
            if obs is not None:
                obs.metrics.inc("serve_prefill_rows_total", pf.real_rows,
                                kind="real")
                obs.metrics.inc("serve_prefill_rows_total",
                                pf.tokens.size - pf.real_rows, kind="pad")
        with span("engine.plan"):
            dc = sched.plan_decode()
        if dc is not None:
            # paged: plan_decode may have allocated fresh pages (or
            # preempted a slot), so re-snapshot the table
            tbl = self._table(span)
            with span("engine.decode", step=self.steps,
                      active=len(dc.active), ctx_tokens=dc.ctx_tokens):
                toks_np = self._call(span, self._decode, dc.tokens,
                                     dc.lengths, tbl, dc.mask)
            with span("engine.finish"):
                now = time.perf_counter()
                self.decode_times.append(now)
                if len(self.decode_times) > 8192:  # bounded history: a
                    # long-running server must not grow a float per token
                    del self.decode_times[:4096]
                sched.finish_decode(dc, toks_np, now=now)
            if obs is not None:
                obs.metrics.inc("serve_decode_steps_total")
                obs.metrics.inc("serve_tokens_total", len(dc.active))
        self.queue_depths.append(len(sched.queue))
        if self.page_manager is not None:
            self.page_utils.append(self.page_manager.utilization())
        if len(self.queue_depths) > 8192:
            del self.queue_depths[:4096]
            del self.page_utils[:4096]
        self.steps += 1

    def _table(self, span) -> tuple:
        """The paged engine's block table on the device, as the step
        functions' extra argument; nothing for the contiguous cache."""
        if not self.paged:
            return ()
        with span("engine.upload"):
            return (jnp.asarray(self.page_manager.table),)

    def _call(self, span, fn, tokens, lengths, tbl, mask) -> np.ndarray:
        """One device call: upload the plan's arrays, dispatch the step
        function ``fn``, and read its sampled tokens back — the device
        sync, so the caller's span times the device step."""
        with span("engine.upload"):
            tokens, lengths, mask = (jnp.asarray(tokens),
                                     jnp.asarray(lengths), jnp.asarray(mask))
        with span("engine.dispatch"):
            toks, self.caches, self._key = fn(
                self.params, tokens, self.caches, lengths, *tbl, mask,
                self._key)
        with span("engine.readback"):
            return np.asarray(toks)

    def run(self, max_steps: int = 10_000) -> list[Request]:
        steps = 0
        while self.scheduler.has_work and steps < max_steps:
            self.step()
            steps += 1
        return self.scheduler.finished

    def throughput_stats(self) -> dict:
        """Serving metrics over everything finished so far (the serve
        bench's source of truth): generated tokens, mean + p50/p99 TTFT,
        and p50/p99 inter-token latency.

        ITL percentiles pool each finished request's *own* inter-token
        gaps (``Request.t_tokens``). The old estimate diffed the global
        ``decode_times`` wall clock, which conflates a request's token
        cadence with engine-level stalls between *other* requests'
        decode steps (admission gaps, preemption recompute) — a request
        that decoded smoothly would inherit latency spikes it never saw.
        """
        reqs = list(self.scheduler.finished)
        toks = sum(len(r.out) for r in reqs)
        ttfts = [r.t_first - r.t_submit for r in reqs
                 if r.t_first is not None and r.t_submit is not None]
        gaps = [r.itl_s() for r in reqs]
        itl = np.concatenate(gaps) if gaps else np.asarray([])
        stats = {
            "requests": len(reqs),
            "tokens": toks,
            "decode_steps": len(self.decode_times),
            "ttft_s": float(np.mean(ttfts)) if ttfts else float("nan"),
            "ttft_p50_s": float(np.percentile(ttfts, 50)) if ttfts else
            float("nan"),
            "ttft_p99_s": float(np.percentile(ttfts, 99)) if ttfts else
            float("nan"),
            "itl_p50_s": float(np.percentile(itl, 50)) if itl.size else
            float("nan"),
            "itl_p99_s": float(np.percentile(itl, 99)) if itl.size else
            float("nan"),
            "queue_depth_mean": float(np.mean(self.queue_depths))
            if self.queue_depths else 0.0,
            "queue_depth_max": int(max(self.queue_depths, default=0)),
        }
        if self.page_manager is not None:
            pm = self.page_manager
            stats.update({
                "page_util_mean": float(np.mean(self.page_utils))
                if self.page_utils else 0.0,
                "page_util_max": float(max(self.page_utils, default=0.0)),
                "prefix_hit_pages": pm.stats.prefix_hit_pages,
                "prefix_lookup_pages": pm.stats.prefix_lookup_pages,
                "prefix_hit_rate": pm.stats.prefix_hit_rate,
                "page_evictions": pm.stats.evictions,
                "preemptions": self.scheduler.preemptions,
            })
        return stats

    # ---- warmup -----------------------------------------------------------

    def _autotune_sparse_blocks(self) -> None:
        """Warm the autotune cache for this engine's sparse-GEMM shapes:
        decode steps run M = slots rows, prefill M = slots * prefill_len.

        Walks the typed NMWeight / QNMWeight leaves of the param tree:
        each weight's own NMConfig supplies the Kc -> K ratio, so a
        model mixing 2:4 and 1:4 layers tunes every shape at its true
        geometry (the old dict walk hardcoded the global ratio), and
        int8 leaves tune under the quantized family's own cache keys
        (value dtype int8). Each leaf's policy also carries the kernel
        backend, so a gpu-pinned weight pre-pays the GPU family's sweep
        under its own key namespace. Dense and masked models contribute
        no such leaves — the walk is the gate."""
        from repro.core.nmweight import NMWeight
        from repro.kernels import autotune
        from repro.kernels.backend import resolve_backend
        from repro.models.common import get_compute_dtype
        from repro.quant import QNMWeight

        typed = (NMWeight, QNMWeight)
        shapes: set[tuple[int, int, Any, Any, str]] = set()
        for leaf in jax.tree.leaves(
                self.params, is_leaf=lambda x: isinstance(x, typed)):
            if isinstance(leaf, typed):
                kc, n = leaf.vals.shape[-2:]  # scan-stacked leaves
                dt = (jnp.int8 if isinstance(leaf, QNMWeight)
                      else get_compute_dtype())
                be = resolve_backend(
                    getattr(leaf.kernel_policy, "backend", "auto"))
                shapes.add((kc * leaf.nm.m // leaf.nm.n, n, leaf.nm, dt, be))
        from repro.kernels.indexmac.ops import decode_m_max

        for k, n, nm, dt, be in sorted(
                shapes, key=lambda t: (t[0], t[1], t[2].tag, str(t[3]), t[4])):
            for m_rows in {self.slots, self.slots * self.prefill_len}:
                if m_rows <= decode_m_max():
                    # skinny-M rows route to the decode kernel family,
                    # which sweeps its own grid under its own cache keys
                    autotune.ensure_tuned(m_rows, n, k, nm, dtype=dt,
                                          family="decode", backend=be)
                else:
                    autotune.ensure_tuned(m_rows, n, k, nm, dtype=dt,
                                          backend=be)

        # block-sparse attention masks pre-pay the bs_attn tile sweep at
        # the full-prefill shape (the only serving shape that routes the
        # bs_attention prefill family; decode/chunk use the mask-aware
        # dense path, which has no tile to tune)
        from repro.kernels.blocksparse_attn.ops import tune_for_serving

        attn_shapes: set = set()
        for entry, _rep in self.lm.cfg.plan:
            blocks = entry if isinstance(entry, tuple) else (entry,)
            for blk in blocks:
                mx = blk.mixer
                if isinstance(mx, AttnConfig) and mx.mask is not None:
                    dk = (mx.nope_head_dim + mx.rope_head_dim
                          if mx.kind == "mla" else mx.head_dim)
                    attn_shapes.add((self.prefill_len, dk, mx.mask))
        for sq, dk, spec in sorted(
                attn_shapes, key=lambda t: (t[0], t[1], t[2].tag)):
            tune_for_serving(sq, sq, dk, spec, dtype=get_compute_dtype(),
                             backend=resolve_backend("auto"))


def _validate_chunkable(cfg) -> None:
    """Chunked prefill needs the mixers' mode="chunk" path (multi-token
    write at a cache offset + causal masking vs absolute positions) —
    implemented for attention (GQA/MLA); state-space / rwkv caches would
    need a resume-from-state prefill instead."""
    for entry, _rep in cfg.plan:
        blocks = entry if isinstance(entry, tuple) else (entry,)
        for blk in blocks:
            if not isinstance(blk.mixer, AttnConfig) or blk.cross_attn:
                raise NotImplementedError(
                    f"prefill_chunk < prefill_len needs attention-mixer "
                    f"decoder blocks; {cfg.name} has "
                    f"{type(blk.mixer).__name__}"
                    f"{' + cross_attn' if blk.cross_attn else ''}")


class ShardedServeEngine(ServeEngine):
    """The same engine with prefill/decode under ``shard_map`` on a
    ("data", "model") mesh: slots data-parallel, projections
    tensor-parallel with head-aware specs, KV caches sharded on heads,
    compressed vals+idx+scales co-sharded so each shard's Pallas kernel
    reads only its local slice. Token streams are identical to the
    single-device engine (same scheduler, same sampler key stream)."""

    def __init__(self, lm: LM, params: Any, *, mesh, **kw):
        from repro.parallel.sharding import serve_tp_plan

        names = getattr(mesh, "axis_names", ())
        if "data" not in names or "model" not in names:
            raise ValueError(
                f"ShardedServeEngine needs a ('data', 'model') mesh, got "
                f"axes {names}")
        self.mesh = mesh
        self._mesh_shape = dict(zip(mesh.axis_names, mesh.devices.shape))
        slots = kw.get("slots")
        if slots is None or slots % self._mesh_shape["data"]:
            raise ValueError(
                f"slots={slots} must divide over the data axis "
                f"({self._mesh_shape['data']})")
        self.tp_plan = serve_tp_plan(lm.cfg, self._mesh_shape["model"])
        super().__init__(lm, params, **kw)
        # commit the sampler key to the mesh (replicated) up front: the
        # first step's key would otherwise be single-device while every
        # later key is a mesh-committed jit output — two compiled
        # signatures for one step function (breaks the zero-recompile
        # invariant the fleet monitors)
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        self._key = jax.device_put(
            self._key, NamedSharding(self.mesh, P()))

    def _data_parallel(self) -> int:
        # one independent page-pool group per data shard: a slot's pages
        # always live in its own shard's sub-pool, so the paged
        # gather/scatter stays shard-local (no collectives)
        return self._mesh_shape["data"]

    def _place_params(self, params: Any) -> Any:
        from repro.parallel.sharding import serve_param_shardings

        # params built with LM.init(shardings=serve_param_shardings(...))
        # are in place already: nothing moves
        return jax.device_put(params, serve_param_shardings(
            params, self.mesh, self.tp_plan))

    def _place_caches(self, caches: Any) -> Any:
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        from repro.parallel.sharding import serve_cache_pspecs

        specs = serve_cache_pspecs(caches, self.mesh, self.tp_plan)
        shardings = jax.tree.map(
            lambda s: NamedSharding(self.mesh, s), specs,
            is_leaf=lambda x: isinstance(x, P))
        return jax.device_put(caches, shardings)

    def _build_steps(self) -> None:
        from jax.sharding import PartitionSpec as P

        from repro import compat
        from repro.parallel import hints
        from repro.parallel.sharding import (
            serve_cache_pspecs,
            serve_local_cfg,
            serve_param_pspecs,
        )

        mesh, plan, sampler = self.mesh, self.tp_plan, self._sampler
        full = self.prefill_chunk == self.prefill_len
        # per-shard view of the model: head counts divided by tp so the
        # (B, S, H, D) reshapes match the local projection slices
        lm_local = LM(serve_local_cfg(self.lm.cfg, plan))
        p_specs = serve_param_pspecs(self.params, mesh, plan)
        if self.paged:
            pm = self.page_manager
            cache_shape = lambda: self.lm.init_cache(pm.rows, pm.page_size)  # noqa: E731
        else:
            cache_shape = lambda: self.lm.init_cache(self.slots, self.max_seq)  # noqa: E731
        c_specs = serve_cache_pspecs(jax.eval_shape(cache_shape), mesh, plan)
        tags = plan.reduce_tags
        p_tok = P("data", None)
        p_vec = P("data")

        if self.paged:
            # block table shards with the slots over "data"; the pool's
            # page rows shard over "data" too (rows = dp * stride), so
            # inside each shard `table % stride` is the local page row
            p_tbl = P("data", None)

            def prefill_body(params, tokens, caches, cache_len, table, mask):
                with hints.tp_serving("model", tags):
                    logits, new_caches, _ = lm_local.forward(
                        params, tokens, caches=caches,
                        view=CacheView.chunk(cache_len, block_table=table,
                                             write_mask=mask))
                return logits[:, -1], new_caches

            sh_prefill = compat.shard_map(
                prefill_body, mesh=mesh,
                in_specs=(p_specs, p_tok, c_specs, p_vec, p_tbl, p_vec),
                out_specs=(p_tok, c_specs), check_vma=False)

            def decode_body(params, token, caches, cache_len, table, mask):
                with hints.tp_serving("model", tags):
                    logits, new_caches, _ = lm_local.forward(
                        params, token, caches=caches,
                        view=CacheView.decode(cache_len, block_table=table,
                                              write_mask=mask))
                return logits[:, 0], new_caches

            sh_decode = compat.shard_map(
                decode_body, mesh=mesh,
                in_specs=(p_specs, p_tok, c_specs, p_vec, p_tbl, p_vec),
                out_specs=(p_tok, c_specs), check_vma=False)

            def prefill_step(params, tokens, caches, cache_len, table,
                             mask, key):
                logits, new_caches = sh_prefill(
                    params, tokens, caches, cache_len, table, mask)
                toks, key = sampler(logits, key)
                return toks, new_caches, key

            def decode_step(params, token, caches, cache_len, table,
                            mask, key):
                logits, new_caches = sh_decode(
                    params, token, caches, cache_len, table, mask)
                toks, key = sampler(logits, key)
                return toks, new_caches, key

            self._prefill = jax.jit(prefill_step, donate_argnums=(2,))
            self._decode = jax.jit(decode_step, donate_argnums=(2,))
            return

        def prefill_body(params, tokens, caches, cache_len, mask):
            with hints.tp_serving("model", tags):
                if full:
                    logits, new_caches, _ = lm_local.forward(
                        params, tokens, view=CacheView.prefill(),
                        caches=caches)
                else:
                    logits, new_caches, _ = lm_local.forward(
                        params, tokens, view=CacheView.chunk(cache_len),
                        caches=caches)
            new_caches = merge_cache_slots(new_caches, caches, mask)
            return logits[:, -1], new_caches

        sh_prefill = compat.shard_map(
            prefill_body, mesh=mesh,
            in_specs=(p_specs, p_tok, c_specs, p_vec, p_vec),
            out_specs=(p_tok, c_specs), check_vma=False)

        def decode_body(params, token, caches, cache_len, mask):
            with hints.tp_serving("model", tags):
                logits, new_caches, _ = lm_local.forward(
                    params, token, view=CacheView.decode(cache_len),
                    caches=caches)
            new_caches = merge_cache_slots(new_caches, caches, mask)
            return logits[:, 0], new_caches

        sh_decode = compat.shard_map(
            decode_body, mesh=mesh,
            in_specs=(p_specs, p_tok, c_specs, p_vec, p_vec),
            out_specs=(p_tok, c_specs), check_vma=False)

        # sampling sits outside the shard_map (logits are tiny) but inside
        # the jit: one categorical over the *global* (slots, V) block, so
        # the gumbel noise — hence the sampled stream — is independent of
        # the device mesh and matches the single-device engine bit-for-bit
        def prefill_step(params, tokens, caches, cache_len, mask, key):
            logits, new_caches = sh_prefill(
                params, tokens, caches, cache_len, mask)
            toks, key = sampler(logits, key)
            return toks, new_caches, key

        def decode_step(params, token, caches, cache_len, mask, key):
            logits, new_caches = sh_decode(
                params, token, caches, cache_len, mask)
            toks, key = sampler(logits, key)
            return toks, new_caches, key

        self._prefill = jax.jit(prefill_step, donate_argnums=(2,))
        self._decode = jax.jit(decode_step, donate_argnums=(2,))
        self._prefill_logits = jax.jit(sh_prefill)
