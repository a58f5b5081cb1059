"""Block-size autotuner for the N:M Pallas kernel, with a persistent cache.

The follow-up paper (arXiv 2501.10189) shows the speedup of structured-
sparse matmul hinges on picking the right tiling per layer shape — one
fixed block triple leaves decode-shaped GEMMs memory-starved and
prefill-shaped ones pipeline-stalled. This module sweeps candidate
``(block_m, block_n, block_k)`` triples per problem key and remembers the
winner on disk so the sweep is paid once per shape per machine.

Cache
-----
JSON at ``$REPRO_AUTOTUNE_CACHE`` (default
``~/.cache/repro/autotune.json``), one entry per key::

    {"v2|platform|kernel_backend|dtype|n:m|MxKxN": [bm, bn, bk], ...}

The key carries two distinct backend tokens: ``platform`` is the
*device* (``jax.default_backend()`` — an interpret-mode sweep on CPU
must never shadow a compiled sweep), ``kernel_backend`` is the *kernel
family* (``tpu``/``gpu`` — the GPU lowering sweeps different grids and
gets its own winners even when both families run on one host). Legacy
``v1`` keys (no kernel-backend token — written before the backend axis
existed, always the TPU family) migrate in place on load, so checked-in
CI caches keep their entries without a re-sweep.

Lookup policy in the hot path (``nm_matmul`` with ``block=None``):
cache hit wins; on a miss the default triple is used unless
``REPRO_AUTOTUNE=1``, in which case the sweep runs inline and the result
is persisted. The serving engine and the roofline benchmark call
:func:`ensure_tuned` explicitly to pre-pay sweeps for their shapes.
"""
from __future__ import annotations

import json
import os
import threading
import time
from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from repro import obs as _obs
from repro.core.sparsity import NMConfig
from repro.kernels.backend import interpret_for
from repro.kernels.padding import plan_nm_matmul

DEFAULT_BLOCK = (256, 256, 2048)
# decode family: M is one sublane by construction, K blocks are kept
# small enough that a single k step covers typical reduced projections.
DEFAULT_DECODE_BLOCK = (8, 256, 1024)
# GPU family: output tiles sized for Triton program instances (the K
# reduction is in-kernel, so block_k only bounds the chunk loop, not a
# grid dimension); smaller than the TPU MXU-sweep tiles by design.
DEFAULT_GPU_BLOCK = (64, 128, 512)
DEFAULT_GPU_DECODE_BLOCK = (8, 128, 512)
_CACHE_VERSION = "v2"
_LEGACY_VERSION = "v1"  # pre-backend-axis keys: always the tpu family

_LOCK = threading.Lock()
_MEM: dict[str, tuple] = {}
# why each candidate of the most recent tune() call failed: block -> reason
failures: dict[tuple, str] = {}
_LOADED_FROM: Optional[str] = None


def cache_path() -> str:
    return os.environ.get(
        "REPRO_AUTOTUNE_CACHE",
        os.path.join(os.path.expanduser("~"), ".cache", "repro", "autotune.json"),
    )


def _key(m: int, n: int, k: int, cfg: NMConfig, dtype, platform: str,
         backend: str = "tpu", family: str = "") -> str:
    """Cache key; ``platform`` is the device, ``backend`` the kernel
    family (see module docstring). ``family`` distinguishes kernel
    families that sweep different grids over the same problem (the
    decode family gets a ``|decode`` suffix)."""
    base = (f"{_CACHE_VERSION}|{platform}|{backend}|"
            f"{jnp.dtype(dtype).name}|{cfg.tag}|{m}x{k}x{n}")
    return f"{base}|{family}" if family else base


def _migrate_key(key: str) -> str:
    """Map a legacy v1 key (no kernel-backend token) onto the v2 schema.

    Everything written under v1 was the TPU kernel family — the only one
    that existed — so ``v1|plat|rest`` becomes ``v2|plat|tpu|rest``.
    Non-v1 keys pass through unchanged."""
    if not key.startswith(f"{_LEGACY_VERSION}|"):
        return key
    parts = key.split("|")
    if len(parts) < 5:
        return key  # malformed: keep as-is, it simply never matches
    return "|".join([_CACHE_VERSION, parts[1], "tpu"] + parts[2:])


def _load_locked() -> None:
    global _LOADED_FROM
    path = cache_path()
    if _LOADED_FROM == path:
        return
    _MEM.clear()
    _LOADED_FROM = path
    try:
        with open(path) as f:
            raw = json.load(f)
        legacy = {}
        for key, blk in raw.items():
            if not (isinstance(blk, list) and len(blk) == 3):
                continue
            if key.startswith(f"{_LEGACY_VERSION}|"):
                legacy[_migrate_key(key)] = tuple(int(b) for b in blk)
            else:
                _MEM[key] = tuple(int(b) for b in blk)
        # one-time v1 -> v2 migration: a native v2 entry for the same
        # problem wins over the migrated legacy one.
        for key, blk in legacy.items():
            if not key.startswith(f"{_LEGACY_VERSION}|"):
                _MEM.setdefault(key, blk)
    except (OSError, ValueError):
        pass  # missing/corrupt cache == empty cache


def _save_locked() -> None:
    path = cache_path()
    try:
        # merge-on-save: another process may have persisted entries since
        # our load — re-read and overlay so concurrent tuners append
        # rather than erase each other's winners
        try:
            with open(path) as f:
                for key, blk in json.load(f).items():
                    if key not in _MEM and isinstance(blk, list) and len(blk) == 3:
                        _MEM[key] = tuple(int(b) for b in blk)
        except (OSError, ValueError):
            pass
        os.makedirs(os.path.dirname(path), exist_ok=True)
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump({k: list(v) for k, v in sorted(_MEM.items())}, f, indent=1)
        os.replace(tmp, path)
    except OSError:
        pass  # read-only FS: keep the in-memory cache only


def clear_memory_cache() -> None:
    """Forget loaded entries (tests repoint REPRO_AUTOTUNE_CACHE)."""
    global _LOADED_FROM
    with _LOCK:
        _MEM.clear()
        _LOADED_FROM = None


def cached_block(m: int, n: int, k: int, cfg: NMConfig, dtype,
                 family: str = "", backend: str = "tpu") -> Optional[tuple]:
    platform = jax.default_backend()
    with _LOCK:
        _load_locked()
        hit = _MEM.get(_key(m, n, k, cfg, dtype, platform, backend, family))
    bundle = _obs.get_obs()
    if bundle is not None:
        bundle.metrics.inc(
            "autotune_cache_hits_total" if hit is not None
            else "autotune_cache_misses_total",
            family=family or "default")
    return hit


def candidate_blocks(m: int, n: int, k: int, cfg: NMConfig,
                     family: str = "", backend: str = "tpu") -> list[tuple]:
    """Plan-feasible, deduplicated candidate triples for this problem.

    On CPU the kernel runs in interpret mode (each probe is orders of
    magnitude slower than compiled code), so the grid is trimmed — the
    cache key carries the platform, so a CPU-tuned entry never shadows a
    compiled sweep. The decode family pins block_m to one sublane (its M
    is always 8) and sweeps only the streaming (n, k) tiles. The GPU
    kernel family sweeps smaller output tiles (one Triton program
    instance per tile; its block_k only sizes the in-kernel reduction
    chunks)."""
    interp = interpret_for(backend)
    if backend == "gpu":
        if family == "decode":
            grid_m = (8,)
            grid_n, grid_k = ((128,), (512,)) if interp else (
                (64, 128, 256), (256, 512, 1024))
        else:
            if interp:
                grid_m, grid_n, grid_k = (32, 64), (128,), (512,)
            else:
                grid_m, grid_n, grid_k = (32, 64, 128), (64, 128, 256), (
                    256, 512, 1024)
    elif family == "decode":
        grid_m = (8,)
        if jax.default_backend() == "cpu":
            grid_n, grid_k = (128, 256), (256, 1024)
        else:
            grid_n, grid_k = (128, 256, 512), (256, 512, 1024, 2048)
    elif jax.default_backend() == "cpu":
        grid_m, grid_n, grid_k = (8, 128), (128, 256), (256, 1024)
    else:
        grid_m, grid_n, grid_k = (8, 64, 128, 256), (128, 256, 512), (
            256, 512, 1024, 2048)
    seen, out = set(), []
    for bm in grid_m:
        for bn in grid_n:
            for bk in grid_k:
                plan = plan_nm_matmul(m, n, k, cfg, (bm, bn, bk))
                if plan is None or plan.block in seen:
                    continue
                seen.add(plan.block)
                out.append(plan.block)
    return out


def default_block(family: str = "", backend: str = "tpu") -> tuple:
    """The fallback triple for a (family, kernel-backend) pair."""
    if backend == "gpu":
        return DEFAULT_GPU_DECODE_BLOCK if family == "decode" \
            else DEFAULT_GPU_BLOCK
    return DEFAULT_DECODE_BLOCK if family == "decode" else DEFAULT_BLOCK


def tune(
    m: int,
    n: int,
    k: int,
    cfg: NMConfig,
    dtype=jnp.float32,
    candidates: Optional[Sequence[tuple]] = None,
    repeats: int = 3,
    family: str = "",
    backend: str = "tpu",
) -> tuple:
    """Time every candidate on real operands; persist and return the winner.

    ``dtype`` is the *value* dtype of the compressed operand and selects
    the quantization family: a float dtype sweeps the float kernel on
    float operands; ``int8`` sweeps the dequantizing kernel on int8
    values + per-column scales — the int8 family has its own cache keys
    (the dtype is part of the key), so its winners never shadow the
    float sweep's. ``family="decode"`` sweeps the skinny-M decode
    kernels instead, under their own ``|decode``-suffixed keys;
    ``backend`` selects the kernel lowering (tpu/gpu) being swept, each
    under its own key namespace.
    """
    from repro.core.sparsity import compress_nm, random_nm_matrix
    from repro.kernels.indexmac.ops import (
        run_pallas_decode,
        run_pallas_decode_q,
        run_pallas_padded,
        run_pallas_padded_q,
    )
    from repro.kernels.indexmac_gpu.ops import (
        run_gpu_decode,
        run_gpu_decode_q,
        run_gpu_padded,
        run_gpu_padded_q,
    )

    platform = jax.default_backend()
    interpret = interpret_for(backend)
    quantized = jnp.dtype(dtype) == jnp.dtype(jnp.int8)
    decode = family == "decode"
    gpu = backend == "gpu"
    t_sweep0 = time.perf_counter()
    kk = -(-k // cfg.m) * cfg.m  # operand K must hold whole blocks
    w = random_nm_matrix(jax.random.PRNGKey(0), (kk, n), cfg, axis=0)
    vals, idx = compress_nm(w, cfg, axis=0)
    if quantized:
        # representative int8 operands; activations stay float.
        vals = jnp.clip(jnp.round(vals * 64.0), -127, 127).astype(jnp.int8)
        scales = jnp.full((n,), 1.0 / 64.0, jnp.float32)
        x = jax.random.normal(jax.random.PRNGKey(1), (m, kk))

        if decode:
            run_q_decode = run_gpu_decode_q if gpu else run_pallas_decode_q

            def run(x, vals, idx, *, cfg, plan, interpret):
                return run_q_decode(
                    x, vals, idx, scales, None, cfg=cfg, plan=plan,
                    activation=None, interpret=interpret)
        else:
            run_q_padded = run_gpu_padded_q if gpu else run_pallas_padded_q

            def run(x, vals, idx, *, cfg, plan, interpret):
                return run_q_padded(
                    x, vals, idx, scales, cfg=cfg, plan=plan,
                    interpret=interpret)
    else:
        x = jax.random.normal(jax.random.PRNGKey(1), (m, kk)).astype(dtype)
        vals = vals.astype(dtype)
        if decode:
            run_f_decode = run_gpu_decode if gpu else run_pallas_decode

            def run(x, vals, idx, *, cfg, plan, interpret):
                return run_f_decode(
                    x, vals, idx, None, cfg=cfg, plan=plan,
                    activation=None, interpret=interpret)
        else:
            run = run_gpu_padded if gpu else run_pallas_padded

    best, best_t = None, float("inf")
    failures.clear()
    for block in candidates or candidate_blocks(m, n, kk, cfg, family,
                                                backend):
        plan = plan_nm_matmul(m, n, kk, cfg, block)
        if plan is None:
            failures[tuple(block)] = "shape does not normalize to this block"
            continue
        try:
            run(
                x, vals, idx, cfg=cfg, plan=plan, interpret=interpret
            ).block_until_ready()  # compile / warm up
            t = min(
                _time_once(run, x, vals, idx, cfg, plan, interpret)
                for _ in range(repeats)
            )
        except Exception as e:  # noqa: BLE001 — e.g. Mosaic refuses it
            failures[tuple(block)] = f"{type(e).__name__}: {e}"
            continue
        if t < best_t:
            best, best_t = plan.block, t
    if best is None:
        raise RuntimeError(
            f"autotune: no candidate block ran for M={m} K={k} N={n} "
            f"{cfg.tag} {jnp.dtype(dtype).name} family={family or 'default'}"
            f" backend={backend}: "
            + "; ".join(f"{b}: {why}" for b, why in failures.items()))
    with _LOCK:
        _load_locked()
        _MEM[_key(m, n, k, cfg, dtype, platform, backend, family)] = best
        _save_locked()
    bundle = _obs.get_obs()
    if bundle is not None:
        bundle.metrics.inc("autotune_sweeps_total",
                           family=family or "default")
        bundle.metrics.observe("autotune_sweep_seconds",
                               time.perf_counter() - t_sweep0)
    return best


def _time_once(fn, x, vals, idx, cfg, plan, interpret) -> float:
    t0 = time.perf_counter()
    fn(x, vals, idx, cfg=cfg, plan=plan, interpret=interpret).block_until_ready()
    return time.perf_counter() - t0


def best_block(
    m: int, n: int, k: int, cfg: NMConfig, dtype=jnp.float32,
    family: str = "", backend: str = "tpu",
) -> tuple:
    """Hot-path lookup: cache hit, else sweep iff REPRO_AUTOTUNE=1, else
    the (family, backend) default triple (clamped later by the plan)."""
    hit = cached_block(m, n, k, cfg, dtype, family, backend)
    if hit is not None:
        return hit
    if os.environ.get("REPRO_AUTOTUNE") == "1":
        return tune(m, n, k, cfg, dtype, family=family, backend=backend)
    return default_block(family, backend)


def ensure_tuned(
    m: int, n: int, k: int, cfg: NMConfig, dtype=jnp.float32,
    family: str = "", backend: str = "tpu",
) -> tuple:
    """Sweep-if-missing, for callers that want to pre-pay (serving warmup,
    benchmarks) regardless of REPRO_AUTOTUNE."""
    return cached_block(m, n, k, cfg, dtype, family, backend) or tune(
        m, n, k, cfg, dtype, family=family, backend=backend)


# ---------------------------------------------------------------------------
# bs_attn family: (bq, bk) token tiles for the block-sparse attention
# kernels. Same cache file and key schema — the MaskSpec's ``.tag``
# duck-types NMConfig in ``_key`` and the problem key is Sq x Skv x Dk;
# entries persist as [bq, bk, 0] triples (the loader keeps len-3 lists).
# ---------------------------------------------------------------------------

_ATTN_FAMILY = "bs_attn"


def candidate_attn_tiles(spec, sq: int, skv: int,
                         backend: str = "tpu") -> list[tuple]:
    """Feasible (bq, bk) tiles: the pattern granularity and its
    sublane-aligned subdivisions (a tile above ``spec.block`` can only
    merge live and dead blocks — never swept)."""
    from repro.kernels.blocksparse_attn.mask import compile_mask

    cands = []
    for div in (1, 2, 4):
        bq = spec.block // div
        bk = spec.block // div
        if bq < 8 or bq % 8:
            continue
        if compile_mask(spec, sq, skv, (bq, bk)) is None:
            continue
        if (bq, bk) not in cands:
            cands.append((bq, bk))
    return cands


def tune_attn(sq: int, skv: int, dk: int, spec, dtype=jnp.float32,
              repeats: int = 3, backend: str = "tpu") -> tuple:
    """Time the block-gather lowering at every candidate tile on real
    operands; persist and return the winning (bq, bk). The gather
    lowering is what every backend's routing shares (tile choice moves
    its pad + gather width the same way it moves the kernels' grids),
    and it times honestly in interpret-free XLA on any host."""
    from repro.kernels.blocksparse_attn.mask import compile_mask, default_tile
    from repro.kernels.blocksparse_attn.ref import blocksparse_xla

    platform = jax.default_backend()
    t_sweep0 = time.perf_counter()
    key = jax.random.PRNGKey(0)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (1, sq, 4, dk)).astype(dtype)
    k = jax.random.normal(kk, (1, skv, 4, dk)).astype(dtype)
    v = jax.random.normal(kv, (1, skv, 4, dk)).astype(dtype)
    best, best_t = None, float("inf")
    for tile in candidate_attn_tiles(spec, sq, skv, backend):
        plan = compile_mask(spec, sq, skv, tile)
        if plan is None:
            continue
        try:
            run = jax.jit(lambda q, k, v, plan=plan: blocksparse_xla(
                q, k, v, spec=spec, plan=plan))
            run(q, k, v).block_until_ready()  # compile / warm up
            t = float("inf")
            for _ in range(repeats):
                t0 = time.perf_counter()
                run(q, k, v).block_until_ready()
                t = min(t, time.perf_counter() - t0)
        except Exception:  # noqa: BLE001 — infeasible tile
            continue
        if t < best_t:
            best, best_t = tile, t
    if best is None:
        best = default_tile(spec, sq, skv)
    with _LOCK:
        _load_locked()
        _MEM[_key(sq, dk, skv, spec, dtype, platform, backend,
                  _ATTN_FAMILY)] = (best[0], best[1], 0)
        _save_locked()
    bundle = _obs.get_obs()
    if bundle is not None:
        bundle.metrics.inc("autotune_sweeps_total", family=_ATTN_FAMILY)
        bundle.metrics.observe("autotune_sweep_seconds",
                               time.perf_counter() - t_sweep0)
    return best


def best_attn_tile(sq: int, skv: int, dk: int, spec, dtype=jnp.float32,
                   backend: str = "tpu") -> tuple:
    """Hot-path (bq, bk) lookup for the bs_attn family: cache hit, else
    sweep iff REPRO_AUTOTUNE=1, else the spec's own granularity."""
    from repro.kernels.blocksparse_attn.mask import default_tile

    hit = cached_block(sq, dk, skv, spec, dtype, _ATTN_FAMILY, backend)
    if hit is not None:
        return tuple(hit[:2])
    if os.environ.get("REPRO_AUTOTUNE") == "1":
        return tune_attn(sq, skv, dk, spec, dtype, backend=backend)
    return default_tile(spec, sq, skv)


def ensure_tuned_attn(sq: int, skv: int, dk: int, spec,
                      dtype=jnp.float32, backend: str = "tpu") -> tuple:
    """Sweep-if-missing for the bs_attn family (serving warmup)."""
    hit = cached_block(sq, dk, skv, spec, dtype, _ATTN_FAMILY, backend)
    if hit is not None:
        return tuple(hit[:2])
    return tune_attn(sq, skv, dk, spec, dtype, backend=backend)
