"""Serving throughput benchmark: dense vs 2:4 vs int8-2:4 engines on a
single device and on a forced-8-device host mesh (2 data x 4 model).

Each (variant, device-count) cell serves a fixed request queue through
the real engine (batched admissions, chunked prefill, device-side
sampling) after a warmup request has paid the two step compiles —
best-of-3 passes, so a transient contention window on a shared runner
does not masquerade as a serving regression — and reports three
schema-2 rows:

  serve_decode_{variant}_{D}dev    us per generated token   (GATED)
  serve_ttft_{variant}_{D}dev      mean time-to-first-token us
  serve_ttft_p50_{variant}_{D}dev  p50 TTFT us (info, ungated)
  serve_ttft_p99_{variant}_{D}dev  p99 TTFT us (info, ungated)
  serve_itl_{variant}_{D}dev       p50 inter-token latency us
                                   (derived carries p99)

Inter-token latency pools each finished request's own token-timestamp
gaps (``Request.t_tokens``) — not a diff over the engine's global
decode clock, which would charge admission/preemption stalls between
*other* requests' steps to every request.

The timed passes always run with observability OFF (the gated rows
measure the zero-overhead path). A final untimed pass per device count
runs with a fresh ``repro.obs`` bundle enabled and attaches its metrics
snapshot to ``BENCH_results.json`` (``serve_metrics``).

The ``serve_decode_*`` and ``serve_itl_*`` families gate in
``check_regression.py`` — us/token is inverse tokens/sec, and the
share-normalized comparison (row / sum of gated rows, new vs baseline)
cancels runner speed, so the gate fires when one engine variant slows
*relative to the others*, e.g. a sparse decode-dispatch regression that
dense serving doesn't see. The sparse variants run with
``use_kernel=True`` and preflight every compressed GEMM with
``api.explain_dispatch`` — a decode step must route to the Pallas
decode family for its timings to be admitted at all.

Every cell runs in a subprocess: the 8-device cells must set
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` before jax
initializes, and fresh processes keep cells from warming each other.

Standalone:  PYTHONPATH=src:. python benchmarks/serve_bench.py [--smoke]
Harness:     python benchmarks/run.py --serve [--smoke]
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

VARIANTS = ("dense", "nm24", "int8")
MESH_8DEV = (2, 4)  # (data, model) for the forced host mesh


# ---------------------------------------------------------------------------
# child: one (devices, smoke) cell set — runs all variants, prints ROWS json
# ---------------------------------------------------------------------------


def _child(devices: int, smoke: bool) -> None:
    import dataclasses
    import time

    import jax
    import numpy as np

    from repro import api, compat
    from repro.configs import get_reduced
    from repro.launch.compile_cache import use_compile_cache
    from repro.models.transformer import LM
    from repro.serving.engine import Request, ServeEngine, ShardedServeEngine

    use_compile_cache()

    slots, prefill_len, chunk = 4, 16, 8
    requests = 6 if smoke else 24
    max_new = 8 if smoke else 32
    mesh = None
    if devices > 1:
        mesh = compat.make_mesh(MESH_8DEV, ("data", "model"))

    def build(variant):
        cfg = get_reduced("yi-9b", sparse=variant != "dense")
        if cfg.sparsity is not None:
            # the sparse variants measure the kernel path, not the XLA
            # reference: the decode-family dispatch is what serve_itl_*
            # rows gate
            cfg = dataclasses.replace(
                cfg, sparsity=dataclasses.replace(
                    cfg.sparsity, use_kernel=True))
        lm = LM(cfg)
        params = lm.init(jax.random.PRNGKey(0))
        if variant != "dense":
            _preflight_decode_dispatch(params, variant)
        kw = dict(slots=slots, max_seq=128, prefill_len=prefill_len,
                  prefill_chunk=chunk,
                  quantize="int8" if variant == "int8" else None)
        if mesh is not None:
            return cfg, ShardedServeEngine(lm, params, mesh=mesh, **kw)
        return cfg, ServeEngine(lm, params, **kw)

    def _preflight_decode_dispatch(params, variant):
        # the public dry-run replaces record sniffing: every compressed
        # GEMM at decode shape (M = slots) must route to a Pallas
        # decode-family kernel before any timing is trusted.
        leaves = [x for x in jax.tree.leaves(
            params, is_leaf=api.is_sparse) if api.is_sparse(x)]
        for w in leaves:
            rec = api.explain_dispatch((slots, w.dense_dim), w)
            if not (rec.op.startswith("nm_matmul_decode")
                    and rec.impl.startswith("pallas")
                    and rec.backend in ("tpu", "gpu")):
                raise RuntimeError(
                    f"serve bench ({variant}) needs the Pallas decode "
                    f"dispatch for every GEMM; K={w.dense_dim} "
                    f"N={w.vals.shape[-1]} would route to "
                    f"{rec.op}/{rec.impl} on backend {rec.backend}: "
                    f"{rec.reason}")

    rows = []
    for variant in VARIANTS:
        cfg, eng = build(variant)
        rng = np.random.default_rng(0)

        def req(i):
            return Request(
                rid=i, prompt=rng.integers(
                    0, cfg.vocab_size, size=prefill_len).astype(np.int32),
                max_new=max_new)

        eng.submit(req(-1))  # warmup: pays the prefill+decode compiles
        eng.run()
        # best-of-3 measured passes (same policy as the tpu_kernel rows):
        # a transient contention window on a shared runner slows one pass,
        # the min is the steady-state the 1.5x share gate should compare
        passes = []
        for _ in range(3):
            eng.decode_times.clear()
            n_warm = len(eng.finished)
            t0 = time.perf_counter()
            for i in range(requests):
                eng.submit(req(i))
            done = eng.run()[n_warm:]  # finished is cumulative
            wall = time.perf_counter() - t0
            toks = sum(len(r.out) for r in done)
            assert len(done) == requests, (variant, len(done))
            ttfts = np.asarray(
                [r.t_first - r.t_submit for r in done])
            # per-request inter-token gaps, pooled across the pass
            itl = np.concatenate([r.itl_s() for r in done])
            passes.append((wall / toks, toks / wall, float(ttfts.mean()),
                           float(np.percentile(itl, 50)),
                           float(np.percentile(itl, 99)),
                           float(np.percentile(ttfts, 50)),
                           float(np.percentile(ttfts, 99))))
        sizes = eng.compiled_cache_sizes()
        assert sizes["prefill"] in (-1, 1) and sizes["decode"] in (-1, 1), \
            (variant, sizes)  # recompiles would poison the timings
        us_tok, toks_s, ttft, p50, p99, tp50, tp99 = min(passes)
        dev = f"{devices}dev"
        rows.append((f"serve_decode_{variant}_{dev}", us_tok * 1e6,
                     f"{toks_s:.1f}tok/s"))
        rows.append((f"serve_ttft_{variant}_{dev}", ttft * 1e6,
                     f"chunk={chunk}"))
        rows.append((f"serve_ttft_p50_{variant}_{dev}", tp50 * 1e6,
                     "info"))
        rows.append((f"serve_ttft_p99_{variant}_{dev}", tp99 * 1e6,
                     "info"))
        rows.append((f"serve_itl_{variant}_{dev}", p50 * 1e6,
                     f"p99={p99 * 1e6:.0f}us"))
    rows += _paged_cell(devices, smoke, mesh)
    rows += _blocksparse_cell(devices, smoke, mesh)
    print("ROWS" + json.dumps(rows))
    print("METRICS" + json.dumps(_metrics_pass(devices, smoke, mesh)))


def _paged_cell(devices: int, smoke: bool, mesh) -> list[tuple]:
    """High-churn paged-KV cell: mixed-length prompts with shared
    prefixes over a deliberately undersized page pool, so page
    recycling, prefix-cache reuse, and (non-smoke) preemption are all
    load-bearing in the measured number. Emits the gated
    ``serve_paged_decode`` row plus min-gated rate rows (prefix hit
    rate, pool utilization) — a drop in either means the paging
    machinery stopped doing its job even if throughput looks fine."""
    import dataclasses
    import time

    import jax
    import numpy as np

    from repro.configs import get_reduced
    from repro.models.transformer import LM
    from repro.serving.engine import Request, ServeEngine, ShardedServeEngine

    slots, prefill_len, chunk, page_size = 4, 16, 8, 8
    max_seq = 64
    pool_pages = 16  # full residency would need slots * 8 = 32
    requests = 6 if smoke else 24
    max_new = 8 if smoke else 32

    cfg = get_reduced("yi-9b")
    cfg = dataclasses.replace(
        cfg, sparsity=dataclasses.replace(cfg.sparsity, use_kernel=True))
    lm = LM(cfg)
    params = lm.init(jax.random.PRNGKey(0))
    kw = dict(slots=slots, max_seq=max_seq, prefill_len=prefill_len,
              prefill_chunk=chunk, paged=True, page_size=page_size,
              pool_pages=pool_pages)
    if mesh is not None:
        eng = ShardedServeEngine(lm, params, mesh=mesh, **kw)
    else:
        eng = ServeEngine(lm, params, **kw)

    rng = np.random.default_rng(0)
    # two prompt families, each sharing its first page (8 tokens after
    # left-padding) within the family — the prefix cache sees real reuse
    base_long = rng.integers(0, cfg.vocab_size, size=8).astype(np.int32)
    base_short = rng.integers(0, cfg.vocab_size, size=4).astype(np.int32)

    def req(i):
        if i % 2:  # short prompt: 4 zero-pad + 4 shared = shared page 0
            prompt = np.concatenate([base_short, rng.integers(
                0, cfg.vocab_size, size=8).astype(np.int32)])
        else:      # long prompt: first 8 tokens shared
            prompt = np.concatenate([base_long, rng.integers(
                0, cfg.vocab_size, size=8).astype(np.int32)])
        return Request(rid=i, prompt=prompt,
                       max_new=max_new - (i % 3) * (max_new // 4))

    eng.submit(req(-2))  # warmup: pays the prefill+decode compiles
    eng.run()
    passes = []
    for _ in range(3):
        eng.decode_times.clear()
        n_warm = len(eng.finished)
        t0 = time.perf_counter()
        for i in range(requests):
            eng.submit(req(i))
        done = eng.run()[n_warm:]
        wall = time.perf_counter() - t0
        toks = sum(len(r.out) for r in done)
        assert len(done) == requests, ("paged", len(done))
        ttft = float(np.mean([r.t_first - r.t_submit for r in done]))
        passes.append((wall / toks, toks / wall, ttft))
    sizes = eng.compiled_cache_sizes()
    assert sizes["prefill"] in (-1, 1) and sizes["decode"] in (-1, 1), \
        ("paged", sizes)  # recompiles would poison the timings
    st = eng.throughput_stats()
    assert st["prefix_hit_pages"] > 0, st  # shared pages must be reused
    us_tok, toks_s, ttft = min(passes)
    dev = f"{devices}dev"
    return [
        (f"serve_paged_decode_{dev}", us_tok * 1e6, f"{toks_s:.1f}tok/s"),
        (f"serve_paged_ttft_{dev}", ttft * 1e6,
         f"qdepth={st['queue_depth_mean']:.1f} "
         f"preempt={st['preemptions']}"),
        (f"serve_paged_hitrate_{dev}", st["prefix_hit_rate"],
         f"{st['prefix_hit_pages']}/{st['prefix_lookup_pages']}pages"),
        (f"serve_paged_util_{dev}", st["page_util_mean"],
         f"max={st['page_util_max']:.2f}"),
    ]


def _blocksparse_cell(devices: int, smoke: bool, mesh) -> list[tuple]:
    """Block-sparse prefill cell: a thin long-context GQA model serves
    FULL prefills (``prefill_chunk == prefill_len``, the shape that
    routes the ``bs_attention`` prefill family) under a local MaskSpec,
    against the dense sliding-window path with identical visibility
    semantics. Emits the gated ``serve_prefill_bs_*`` /
    ``serve_prefill_dense_*`` timing rows plus the min-gated speedup
    rate row. The cell refuses to report at all unless (a) trace-time
    dispatch counters prove a sparse lowering ran and the dense
    ``masked_reference`` fallback never did, and (b) the masked
    engine's tokens match the dense engine's exactly (f32 compute for
    the pass, so parity is bit-meaningful)."""
    import dataclasses
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import api
    from repro.configs import get_reduced
    from repro.configs.base import FFNConfig
    from repro.kernels import registry
    from repro.kernels.blocksparse_attn.mask import MaskSpec, compile_mask
    from repro.models import common
    from repro.models.transformer import LM
    from repro.serving.engine import Request, ServeEngine, ShardedServeEngine

    seq = 512 if smoke else 1024
    window, block = 192, 64
    slots, requests = 2, 4
    spec = MaskSpec("local", block=block, window=window)
    plan = compile_mask(spec, seq, seq)
    assert plan is not None and plan.density <= 0.5, plan

    prev = common.get_compute_dtype()
    common.set_compute_dtype(jnp.float32)
    try:
        base = get_reduced("yi-9b", sparse=False)

        def variant(**fields):
            def blk(b):
                # thin FFN: the cell measures the attention path, not
                # the (identical either way) projection/FFN GEMMs
                b = dataclasses.replace(b, mlp=FFNConfig(d_ff=64))
                return dataclasses.replace(
                    b, mixer=dataclasses.replace(b.mixer, **fields))

            pl = tuple((blk(e), r) for e, r in base.plan)
            return dataclasses.replace(base, plan=pl, max_seq=seq + 8)

        cfg_d = variant(mask=None, window=window)
        cfg_b = variant(mask=spec, window=None)
        lm_d, lm_b = LM(cfg_d), LM(cfg_b)
        params = lm_d.init(jax.random.PRNGKey(0))  # mask changes no params

        # preflight: the full-prefill shape must route a sparse lowering
        mx = cfg_b.plan[0][0].mixer
        rec = api.explain_dispatch_attention(
            (slots, seq, mx.q_heads, mx.head_dim),
            (slots, seq, mx.kv_heads, mx.head_dim), mask=spec,
            dtype=jnp.float32)
        if rec.impl == "masked_reference":
            raise RuntimeError(
                f"blocksparse prefill cell needs a sparse lowering; "
                f"Sq=Skv={seq} mask {spec.tag} would route to "
                f"{rec.impl}: {rec.reason}")

        rng = np.random.default_rng(0)
        prompts = [rng.integers(0, base.vocab_size,
                                size=seq).astype(np.int32)
                   for _ in range(requests)]

        def serve(lm):
            kw = dict(slots=slots, max_seq=seq + 8, prefill_len=seq)
            eng = (ShardedServeEngine(lm, params, mesh=mesh, **kw)
                   if mesh is not None else ServeEngine(lm, params, **kw))
            eng.submit(Request(rid=-1, prompt=prompts[0], max_new=1))
            eng.run()  # warmup: pays the prefill compile
            best, out = None, None
            for _ in range(3):
                n_warm = len(eng.finished)
                t0 = time.perf_counter()
                for i, p in enumerate(prompts):
                    eng.submit(Request(rid=i, prompt=p, max_new=1))
                done = eng.run()[n_warm:]
                wall = time.perf_counter() - t0
                assert len(done) == requests, len(done)
                out = {r.rid: tuple(r.out) for r in done}
                best = wall if best is None else min(best, wall)
            sizes = eng.compiled_cache_sizes()
            assert sizes["prefill"] in (-1, 1), sizes
            return best / requests, out

        dense_s, dense_out = serve(lm_d)
        registry.clear_history()
        bs_s, bs_out = serve(lm_b)
        counts = registry.dispatch_counts("bs_attention")
        sparse_n = sum(
            n for (op, impl, _), n in counts.items()
            if op == "bs_attention" and impl != "masked_reference")
        fallback_n = sum(
            n for (op, impl, _), n in counts.items()
            if op == "bs_attention" and impl == "masked_reference")
        assert sparse_n > 0 and fallback_n == 0, counts
        assert bs_out == dense_out, (dense_out, bs_out)
    finally:
        common.set_compute_dtype(prev)

    speedup = dense_s / bs_s
    assert speedup >= 1.5, (
        f"blocksparse prefill speedup {speedup:.2f}x < 1.5x at "
        f"density {plan.density:.2f}")
    dev = f"{devices}dev"
    return [
        (f"serve_prefill_bs_{dev}", bs_s * 1e6,
         f"density={plan.density:.2f} S={seq}"),
        (f"serve_prefill_dense_{dev}", dense_s * 1e6, f"window={window}"),
        (f"serve_prefill_bs_speedup_{dev}", speedup,
         f"{speedup:.2f}x vs dense"),
    ]


def _metrics_pass(devices: int, smoke: bool, mesh) -> dict:
    """One untimed paged serve with a fresh ``repro.obs`` bundle enabled.

    A *fresh* engine is built under the bundle on purpose: kernel
    dispatch and autotune-cache decisions happen at trace time, so only
    a run that pays its own compiles records the ``kernel_dispatch_*``
    and ``autotune_*`` metrics alongside the engine/scheduler/paging
    ones. Returns the ``MetricsRegistry.snapshot()`` dict that
    ``run.py`` attaches to ``BENCH_results.json``."""
    import dataclasses

    import jax
    import numpy as np

    import repro.obs as obs_mod
    from repro.configs import get_reduced
    from repro.models.transformer import LM
    from repro.serving.engine import Request, ServeEngine, ShardedServeEngine

    bundle = obs_mod.enable(obs_mod.Obs.create())
    try:
        cfg = get_reduced("yi-9b")
        cfg = dataclasses.replace(
            cfg, sparsity=dataclasses.replace(
                cfg.sparsity, use_kernel=True))
        lm = LM(cfg)
        params = lm.init(jax.random.PRNGKey(0))
        kw = dict(slots=4, max_seq=64, prefill_len=16, prefill_chunk=8,
                  paged=True, page_size=8, pool_pages=16)
        if mesh is not None:
            eng = ShardedServeEngine(lm, params, mesh=mesh, **kw)
        else:
            eng = ServeEngine(lm, params, **kw)
        rng = np.random.default_rng(0)
        for i in range(4 if smoke else 8):
            eng.submit(Request(
                rid=i, prompt=rng.integers(
                    0, cfg.vocab_size, size=16).astype(np.int32),
                max_new=4))
        eng.run()
        return bundle.metrics.snapshot()
    finally:
        obs_mod.disable()


# ---------------------------------------------------------------------------
# parent: spawn one subprocess per device count
# ---------------------------------------------------------------------------


def bench_rows_and_metrics(smoke: bool = False) -> tuple[list, dict]:
    """All serve-bench rows plus the per-device-count obs metrics
    snapshots; spawns the per-device-count subprocesses."""
    here = os.path.dirname(os.path.abspath(__file__))
    root = os.path.dirname(here)
    rows: list[tuple] = []
    metrics: dict = {}
    for devices in (1, 8):
        env = dict(os.environ)
        env["PYTHONPATH"] = os.pathsep.join(
            [os.path.join(root, "src"), root]
            + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
        # the timed cells must measure the zero-overhead path even when
        # the harness itself runs under REPRO_OBS=1
        env.pop("REPRO_OBS", None)
        if devices > 1:
            env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
            env["JAX_PLATFORMS"] = "cpu"  # host mesh is CPU by definition
        cmd = [sys.executable, os.path.join(here, "serve_bench.py"),
               "--run-child", "--devices", str(devices)]
        if smoke:
            cmd.append("--smoke")
        proc = subprocess.run(cmd, env=env, capture_output=True, text=True,
                              timeout=3600)
        if proc.returncode != 0:
            raise RuntimeError(
                f"serve bench child (devices={devices}) failed:\n"
                + proc.stderr[-4000:])
        line = [l for l in proc.stdout.splitlines()
                if l.startswith("ROWS")][0]
        rows += [tuple(r) for r in json.loads(line[len("ROWS"):])]
        mline = [l for l in proc.stdout.splitlines()
                 if l.startswith("METRICS")]
        if mline:
            metrics[f"{devices}dev"] = json.loads(mline[0][len("METRICS"):])
    return rows, metrics


def bench_rows(smoke: bool = False) -> list[tuple]:
    """All serve-bench rows; spawns the per-device-count subprocesses."""
    return bench_rows_and_metrics(smoke=smoke)[0]


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    ap.add_argument("--smoke", action="store_true",
                    help="fewer requests / shorter generations (CI)")
    ap.add_argument("--run-child", action="store_true", help=argparse.SUPPRESS)
    ap.add_argument("--devices", type=int, default=1, help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.run_child:
        _child(args.devices, args.smoke)
        return
    print("name,us_per_call,derived")
    for name, us, derived in bench_rows(smoke=args.smoke):
        print(f"{name},{us:.1f},{derived}")


if __name__ == "__main__":
    main()
