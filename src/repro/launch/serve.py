"""Serving driver: batched requests through the continuous-batching engine.

  PYTHONPATH=src python -m repro.launch.serve --arch yi-9b --requests 8

Multi-device (tensor-parallel x data-parallel) serving:

  XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
  PYTHONPATH=src python -m repro.launch.serve --mesh 2x4 --requests 8

(on real accelerators drop the XLA_FLAGS override — the mesh axes map
onto the attached devices; slots must divide the data axis).

Published widths: ``--full`` builds the arch's published config
(``get_config``) with bf16 parameters created on the device, and
``--layers N`` cuts it to its first N layers, every width unchanged:

  PYTHONPATH=src python -m repro.launch.serve --full --layers 8 --kernels \
      --slots 8 --prefill-len 128 --max-new 32 --max-seq 160

Observability: ``--trace-out trace.json`` / ``--metrics-out
metrics.prom`` enable the :mod:`repro.obs` layer for the run (same as
``REPRO_OBS=1``) and export a Perfetto-loadable Chrome trace and a
Prometheus text snapshot on exit. ``--paged`` serves through the paged
KV cache; ``--kernels`` forces the compressed GEMMs through the Pallas
kernel families so the exported metrics include kernel-dispatch and
autotune-cache activity.
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

import repro.obs as obs_mod
from repro.configs import ARCHS, cut_layers, get_config, get_reduced
from repro.launch.compile_cache import use_compile_cache
from repro.models.transformer import LM
from repro.serving.engine import Request, ServeEngine, ShardedServeEngine


def device_info() -> dict:
    """The device JAX serves on, as every result line names it."""
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def build_lm(arch: str, *, full: bool = False, layers=None,
             kernels: bool = False, seed: int = 0, mesh=None):
    """``(lm, params)`` as the serve driver builds them.

    ``full`` takes the published config with bf16 parameters created on
    the device (``LM.init`` builds them inside jit, a group at a time);
    otherwise the reduced CPU-runnable config with f32 params.
    ``layers`` cuts the decoder to its first N layers; ``kernels``
    routes the compressed GEMMs through the Pallas kernel families.
    With a ``("data", "model")`` ``mesh`` every param is created in the
    shard :class:`ShardedServeEngine` keeps it in, so no device holds
    the whole model on the way; the values are the same as without.
    """
    cfg = get_config(arch) if full else get_reduced(arch)
    if layers is not None:
        cfg = cut_layers(cfg, layers)
    if kernels:
        if cfg.sparsity is None:
            raise SystemExit(
                f"--kernels: {arch} has a dense config (no compressed "
                "GEMMs to route)")
        cfg = dataclasses.replace(
            cfg, sparsity=dataclasses.replace(cfg.sparsity, use_kernel=True))
    lm = LM(cfg)
    key = jax.random.PRNGKey(seed)
    dtype = jnp.bfloat16 if full else jnp.float32
    shardings = None
    if mesh is not None:
        from repro.parallel.sharding import (
            serve_param_shardings,
            serve_tp_plan,
        )

        plan = serve_tp_plan(cfg, dict(zip(mesh.axis_names,
                                           mesh.devices.shape))["model"])
        shardings = serve_param_shardings(
            jax.eval_shape(lambda k: lm.init(k, dtype), key), mesh, plan)
    return lm, lm.init(key, dtype, shardings=shardings)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list(ARCHS), default="yi-9b")
    ap.add_argument("--full", action="store_true",
                    help="serve the published config (bf16 params) "
                         "instead of the reduced one")
    ap.add_argument("--layers", type=int, default=None,
                    help="cut the decoder to its first N layers")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--prefill-len", type=int, default=16)
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="split prompts into chunks of this many tokens "
                         "(bounded TTFT); must divide prefill-len")
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--quantize", choices=["int8"], default=None,
                    help="int8-quantize compressed weights at load "
                         "(per-channel absmax scales)")
    ap.add_argument("--mesh", default=None, metavar="DATAxMODEL",
                    help="serve sharded on a (data, model) mesh, e.g. 2x4 "
                         "(slots shard over data, tensor parallel over "
                         "model)")
    ap.add_argument("--strict", action="store_true",
                    help="reject prompts longer than prefill-len instead "
                         "of silently truncating to the tail")
    ap.add_argument("--paged", action="store_true",
                    help="serve through the paged KV cache (page pool + "
                         "block tables + prefix cache)")
    ap.add_argument("--page-size", type=int, default=None,
                    help="tokens per KV page (paged mode; default: the "
                         "prefill chunk)")
    ap.add_argument("--pool-pages", type=int, default=None,
                    help="total pages in the KV pool (paged mode; "
                         "default: full residency for every slot)")
    ap.add_argument("--kernels", action="store_true",
                    help="route compressed GEMMs through the Pallas "
                         "kernel families (use_kernel=True)")
    ap.add_argument("--trace-out", default=None, metavar="TRACE.JSON",
                    help="enable observability and export a Chrome/"
                         "Perfetto trace here on exit")
    ap.add_argument("--metrics-out", default=None, metavar="METRICS.PROM",
                    help="enable observability and export a Prometheus "
                         "text snapshot here on exit")
    args = ap.parse_args()

    use_compile_cache()
    print("device", device_info(), flush=True)
    bundle = None
    if args.trace_out or args.metrics_out:
        bundle = obs_mod.enable()

    mesh = None
    if args.mesh:
        from repro.launch.mesh import make_serve_mesh

        data, model = (int(x) for x in args.mesh.lower().split("x"))
        mesh = make_serve_mesh(data, model)
    lm, params = build_lm(args.arch, full=args.full, layers=args.layers,
                          kernels=args.kernels, mesh=mesh)
    cfg = lm.cfg
    print(f"model {cfg.name}: {cfg.n_layers} layers, d_model "
          f"{cfg.d_model}, vocab {cfg.vocab_size}", flush=True)
    kw = dict(slots=args.slots, max_seq=args.max_seq,
              prefill_len=args.prefill_len,
              prefill_chunk=args.prefill_chunk,
              temperature=args.temperature,
              quantize=args.quantize, strict=args.strict,
              paged=args.paged, page_size=args.page_size,
              pool_pages=args.pool_pages)
    if mesh is not None:
        eng = ShardedServeEngine(lm, params, mesh=mesh, **kw)
        print(f"mesh {args.mesh}: {eng.tp_plan}")
    else:
        eng = ServeEngine(lm, params, **kw)
    rng = np.random.default_rng(0)
    t0 = time.time()
    for i in range(args.requests):
        eng.submit(Request(
            rid=i,
            prompt=rng.integers(0, cfg.vocab_size,
                                size=args.prefill_len).astype(np.int32),
            max_new=args.max_new))
    done = eng.run()
    dt = time.time() - t0
    stats = eng.throughput_stats()
    toks = stats["tokens"]
    print(f"served {len(done)} requests, {toks} tokens in {dt:.1f}s "
          f"({toks/dt:.1f} tok/s, slots={args.slots}, "
          f"ttft={stats['ttft_s']*1e3:.0f}ms, "
          f"itl p50={stats['itl_p50_s']*1e3:.0f}ms "
          f"p99={stats['itl_p99_s']*1e3:.0f}ms)")
    for r in done[:3]:
        print(f"  rid={r.rid} out[:8]={r.out[:8]}")
    assert len(done) == args.requests
    # exactly one compile per step: -1 (size unavailable) is a failure
    assert eng.compiled_cache_sizes() == {"prefill": 1, "decode": 1}, \
        eng.compiled_cache_sizes()
    if bundle is not None:
        if args.trace_out:
            n = bundle.tracer.export_chrome(args.trace_out)
            print(f"wrote {args.trace_out} ({n} events, "
                  f"{bundle.tracer.dropped} dropped)")
        if args.metrics_out:
            with open(args.metrics_out, "w") as f:
                f.write(bundle.metrics.to_prometheus())
            print(f"wrote {args.metrics_out}")


if __name__ == "__main__":
    main()
