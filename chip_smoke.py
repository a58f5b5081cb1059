#!/usr/bin/env python3
"""Bring-up smoke test: yi-9b served at published widths on a TPU through
compiled Pallas kernels.

  python chip_smoke.py             # one chip: kernel phase, then serve phase
  python chip_smoke.py --chips 4   # four chips: sharded vs single-device only

Everything runs in this one process (a chip belongs to one process). The
script exits non-zero, without a result line, when JAX sees no TPU or
when any check fails. Its last line on success is

  {"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}

Phases (one chip):

* kernel — each compressed-GEMM family on the serve path (prefill and
  decode, bf16 and int8, 2:4) at yi-9b's projection shapes, against the
  XLA reference of the same compressed weight. The dispatch record must
  name the Pallas implementation, run compiled.
* serve — yi-9b at its published widths (d_model 4096, 32 query / 4 KV
  heads of 128, d_ff 11008, vocab 64000), 2:4, bf16, as many whole layers
  as the chip holds (all 48 when they fit), built by the functions
  ``repro.launch.serve`` uses. Serves 8 requests of 128 prompt tokens and
  32 new tokens on 8 slots, then checks: every request finished, exactly
  one compile per step, only compiled Pallas GEMMs in the steps, and
  prefill logits within tolerance of the same params on the XLA path.

Tolerances, both relative to the reference's scale:

* one GEMM: max|y - y_ref| <= 2**-7 * max|y_ref|. Both paths multiply
  the same bf16 operands exactly and accumulate in f32; they differ in
  accumulation order and in the final rounding to bf16, half an ulp
  (2**-9 relative) on each side. 2**-7 leaves twice that margin.
* whole model: ||l - l_ref|| <= 5e-2 * ||l_ref|| over the prefill
  logits. Every GEMM output (7 per layer) is rounded to bf16 on both
  paths independently; taken as a random walk of 2**-9 steps over
  48 * 7 roundings that is sqrt(336) * 2**-9 = 0.036.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time

ARCH = "yi-9b"
SLOTS, PROMPT, NEW = 8, 128, 32
MAX_SEQ = PROMPT + NEW
GEMM_TOL = 2.0 ** -7
LOGIT_TOL = 5e-2
# device bytes kept free beyond params + KV cache: step temporaries
# (0.35 GB prefill, 0.53 GB reference decode in the compile rehearsal),
# the parity check's second cache, and init's per-layer f32 draws
HEADROOM = 1.5e9
# (K, N) of yi-9b's projections: w_up / w_gate, w_down, wq / wo
KERNEL_SHAPES = ((4096, 11008), (11008, 4096), (4096, 4096))


def fail(msg: str) -> None:
    print(f"FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def check(cond: bool, msg: str) -> None:
    if not cond:
        fail(msg)


# ---------------------------------------------------------------------------
# kernel phase
# ---------------------------------------------------------------------------


def kernel_phase(seed: int) -> None:
    import jax
    import jax.numpy as jnp

    from repro import api
    from repro.core.nmweight import KernelPolicy
    from repro.core.sparsity import NMConfig
    from repro.kernels import registry

    nm = NMConfig(2, 4)
    key = jax.random.PRNGKey(seed)
    for k, n in KERNEL_SHAPES:
        kw, kx, key = jax.random.split(key, 3)
        w = jax.random.normal(kw, (k, n), jnp.float32) * k ** -0.5
        wf = api.sparsify(w, nm, kernel_policy="auto")
        weights = {"bf16": wf.astype(jnp.bfloat16), "int8": api.quantize(wf)}
        del w
        for m in (SLOTS * PROMPT, SLOTS):
            x = jax.random.normal(kx, (m, k), jnp.float32).astype(jnp.bfloat16)
            for kind, wt in weights.items():
                ref_w = dataclasses.replace(
                    wt, kernel_policy=KernelPolicy("off"))
                y = jax.jit(api.nm_matmul)(x, wt)
                rec = registry.last_dispatch()
                y_ref = jax.jit(api.nm_matmul)(x, ref_w)
                y32 = jnp.asarray(y, jnp.float32)
                r32 = jnp.asarray(y_ref, jnp.float32)
                err = float(jnp.max(jnp.abs(y32 - r32)) / jnp.max(jnp.abs(r32)))
                print(f"kernel {rec.op:20s} {rec.impl:16s} M={m} K={k} N={n} "
                      f"{kind}: block={rec.block} padded={rec.padded} "
                      f"interpret={rec.interpret} err={err:.3e}", flush=True)
                check(rec.impl.startswith("pallas"),
                      f"{kind} M={m} K={k} N={n} routed to {rec.impl}: "
                      f"{rec.reason}")
                check(not rec.interpret, f"{rec.op} ran interpreted")
                check(bool(jnp.all(jnp.isfinite(y32))), "non-finite output")
                check(err <= GEMM_TOL,
                      f"{rec.op} {kind}: error {err:.3e} > {GEMM_TOL:.3e}")


# ---------------------------------------------------------------------------
# serve phase
# ---------------------------------------------------------------------------


def layers_that_fit() -> int:
    """The most whole yi-9b layers (at most its 48) whose bf16 params
    and KV cache fit the first device with HEADROOM to spare."""
    import jax
    import jax.numpy as jnp

    from repro.configs import cut_layers, get_config
    from repro.models.transformer import LM

    full = get_config(ARCH)

    def nbytes(n):
        lm = LM(cut_layers(full, n))
        tree = jax.eval_shape(lambda: (
            lm.init(jax.random.PRNGKey(0), param_dtype=jnp.bfloat16),
            lm.init_cache(SLOTS, MAX_SEQ)))
        return sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(tree))

    per_layer = nbytes(2) - nbytes(1)
    fixed = nbytes(1) - per_layer
    limit = jax.devices()[0].memory_stats()["bytes_limit"]
    fit = int((limit - HEADROOM - fixed) // per_layer)
    print(f"memory: bytes_limit={limit} per_layer={per_layer} "
          f"embed+head={fixed} -> {fit} layers fit", flush=True)
    check(fit >= 1, "not one yi-9b layer fits the device")
    return min(full.n_layers, fit)


def prompts(vocab: int, seed: int):
    import numpy as np

    rng = np.random.default_rng(seed)
    return rng.integers(0, vocab, size=(SLOTS, PROMPT)).astype(np.int32)


def serve(eng, toks) -> tuple[list, float]:
    """Serve one request per prompt row; returns (finished, seconds)."""
    from repro.serving.engine import Request

    t0 = time.perf_counter()
    for i, p in enumerate(toks):
        eng.submit(Request(rid=i, prompt=p, max_new=NEW))
    done = eng.run()
    dt = time.perf_counter() - t0
    check(len(done) == len(toks) and all(len(r.out) == NEW for r in done),
          f"{len(done)}/{len(toks)} requests finished with {NEW} tokens")
    sizes = eng.compiled_cache_sizes()
    check(sizes == {"prefill": 1, "decode": 1}, f"recompiles: {sizes}")
    return sorted(done, key=lambda r: r.rid), dt


def logit_error(a, b) -> float:
    import jax.numpy as jnp

    a = jnp.asarray(a, jnp.float32)
    b = jnp.asarray(b, jnp.float32)
    check(bool(jnp.all(jnp.isfinite(a)) & jnp.all(jnp.isfinite(b))),
          "non-finite logits")
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


def reference_params(params):
    """The same arrays with every compressed weight pinned to the XLA
    reference path (the policy is static metadata: nothing is copied)."""
    import jax

    from repro.core.nmweight import KernelPolicy, NMWeight

    return jax.tree.map(
        lambda w: dataclasses.replace(w, kernel_policy=KernelPolicy("off"))
        if isinstance(w, NMWeight) else w,
        params, is_leaf=lambda w: isinstance(w, NMWeight))


def gemm_records():
    """Dispatch records of the compressed GEMMs traced so far, by family."""
    from repro.kernels import registry

    recs = registry.dispatch_history()
    return ([r for r in recs if r.op.startswith("nm_matmul_decode")],
            [r for r in recs if not r.op.startswith("nm_matmul_decode")])


def serve_phase(seed: int) -> None:
    import jax

    from repro.kernels import registry
    from repro.launch.serve import build_lm
    from repro.serving.engine import ServeEngine

    n = layers_that_fit()
    t0 = time.perf_counter()
    lm, params = build_lm(ARCH, full=True, layers=n, kernels=True, seed=seed)
    jax.block_until_ready(params)
    cfg = lm.cfg
    print(f"serve: {ARCH} {n} of 48 layers held, d_model {cfg.d_model}, "
          f"d_ff {cfg.plan[0][0].mlp.d_ff}, vocab {cfg.vocab_size}; init "
          f"{time.perf_counter() - t0:.1f}s", flush=True)
    toks = prompts(cfg.vocab_size, seed)

    registry.clear_history()
    eng = ServeEngine(lm, params, slots=SLOTS, max_seq=MAX_SEQ,
                      prefill_len=PROMPT)
    done, dt = serve(eng, toks)
    ntok = sum(len(r.out) for r in done)
    print(f"serve: {len(done)} requests, {ntok} tokens in {dt:.2f}s wall "
          f"(compiles included); first out {done[0].out[:8]}", flush=True)
    decode, other = gemm_records()
    check(len(decode) > 0 and len(other) > 0, "no compressed GEMM traced")
    for r in decode + other:
        check(r.impl.startswith("pallas") and not r.interpret,
              f"{r.op} M,K,N={r.shape} ran {r.impl} "
              f"(interpret={r.interpret}): {r.reason}")
    counts = registry.dispatch_counts("nm_matmul_decode")
    check(all(impl.startswith("pallas") for _, impl, _ in counts),
          f"reference GEMMs in the decode step: {counts}")
    print(f"serve: dispatch counts {registry.dispatch_counts()}", flush=True)

    kern = eng.prefill_logits(toks)
    ref = ServeEngine(lm, reference_params(params), slots=SLOTS,
                      max_seq=MAX_SEQ, prefill_len=PROMPT).prefill_logits(toks)
    err = logit_error(kern, ref)
    agree = float((kern.argmax(-1) == ref.argmax(-1)).mean())
    print(f"serve: prefill logits kernel vs XLA rel err {err:.3e} "
          f"(tol {LOGIT_TOL}), top-1 agreement {agree:.3f}", flush=True)
    check(err <= LOGIT_TOL, f"prefill logits error {err:.3e} > {LOGIT_TOL}")
    peak = (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")
    print(f"serve: peak_bytes_in_use {peak}", flush=True)


# ---------------------------------------------------------------------------
# four-chip phase
# ---------------------------------------------------------------------------


def four_chip_phase(seed: int) -> None:
    import numpy as np

    from repro.launch.mesh import make_serve_mesh
    from repro.launch.serve import build_lm
    from repro.serving.engine import ServeEngine, ShardedServeEngine

    n = layers_that_fit()
    kw = dict(slots=SLOTS, max_seq=MAX_SEQ, prefill_len=PROMPT)
    lm, params = build_lm(ARCH, full=True, layers=n, kernels=True, seed=seed)
    toks = prompts(lm.cfg.vocab_size, seed)
    single = ServeEngine(lm, params, **kw)
    done1, dt1 = serve(single, toks)
    logits1 = np.asarray(single.prefill_logits(toks), np.float32)
    print(f"4-chip: single device 0, {n} layers: {dt1:.2f}s wall", flush=True)
    del single, params  # device 0 next holds only its shard
    gc.collect()

    # the same params (same seed), each created in its shard
    mesh = make_serve_mesh(1, 4)
    lm, params = build_lm(ARCH, full=True, layers=n, kernels=True,
                          seed=seed, mesh=mesh)
    sharded = ShardedServeEngine(lm, params, mesh=mesh, **kw)
    del params
    print(f"4-chip: mesh data=1 model=4: {sharded.tp_plan}", flush=True)
    done4, dt4 = serve(sharded, toks)
    logits4 = np.asarray(sharded.prefill_logits(toks), np.float32)
    err = logit_error(logits4, logits1)
    same = np.mean([np.mean(np.equal(a.out, b.out))
                    for a, b in zip(done4, done1)])
    print(f"4-chip: sharded {dt4:.2f}s wall; prefill logits sharded vs "
          f"single rel err {err:.3e} (tol {LOGIT_TOL}); greedy-token "
          f"agreement {same:.3f}; zero recompiles on both", flush=True)
    check(err <= LOGIT_TOL,
          f"sharded vs single logits error {err:.3e} > {LOGIT_TOL}")


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded-vs-single-device phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(
        __file__)), "src"))
    from repro.launch.serve import device_info

    device = device_info()
    print(f"device: {device}", flush=True)
    check(device["platform"] == "tpu",
          f"no TPU: JAX platform is {device['platform']!r}")
    check(device["count"] >= args.chips,
          f"{args.chips} chips asked, {device['count']} visible")

    from repro.launch.compile_cache import use_compile_cache

    print(f"compile cache: {use_compile_cache()}", flush=True)
    if args.chips == 4:
        four_chip_phase(args.seed)
    else:
        kernel_phase(args.seed)
        serve_phase(args.seed)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
