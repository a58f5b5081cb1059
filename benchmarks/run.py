"""Benchmark harness: one entry per paper table/figure + beyond-paper TPU
kernel roofline. Prints ``name,us_per_call,derived`` CSV rows and writes
a machine-readable ``BENCH_results.json`` next to the CSV stream:

  {"schema": 2,
   "mode":    {"measured": bool, "smoke": bool},
   "rows":    [{"name", "us_per_call", "derived"}, ...],
   "layers":  [{"layer", "fig", "nm", "family", "m", "k", "n",
                "t_pallas_us", "t_rowwise_us", "t_gather_us",
                "speedup_vs_rowwise", "analytic_speedup", ...}, ...],
   "kernels": [{"nm", "family" (bf16|int8), "gemm", "m", "k", "n",
                "hbm_bytes", "dense_hbm_bytes", "bytes_vs_dense",
                "roofline_speedup_vs_dense", "bound"}, ...]}

``--measured`` additionally runs the fig4/5/6 measured modes — the real
padded Pallas ``nm_matmul`` dispatch timed against the row-wise / gather
baselines on the paper's CNN layer shapes (``--smoke`` sub-samples the
layers for CI). ``--serve`` runs ``benchmarks/serve_bench.py`` — serving
throughput / TTFT / inter-token latency for dense vs 2:4 vs int8-2:4
engines on one device and a forced-8-device host mesh. Either flag also
emits a ``bench_calibration`` row (a fixed Pallas kernel call) that
``benchmarks/check_regression.py`` uses as the uniform-slowdown guard
when gating against ``benchmarks/BENCH_baseline.json`` (per-row gating
is share-normalized; see that script's docstring).

Refresh the checked-in baseline after an intentional perf change (cold
autotune cache — CI runs cold too, so block choices match; keep all
flags so the baseline is a superset of every CI lane's rows):

  JAX_PLATFORMS=cpu PYTHONPATH=src:. REPRO_AUTOTUNE_CACHE=$(mktemp -u) \\
      REPRO_BENCH_JSON=benchmarks/BENCH_baseline.json \\
      python benchmarks/run.py --measured --smoke --serve
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

OUT_JSON = os.environ.get("REPRO_BENCH_JSON", "BENCH_results.json")


def _dedupe_layers(layer_rows: list[dict]) -> list[dict]:
    """fig4 and fig5 share cached measurements (same layer/nm/family ->
    same numbers); keep the first record of each. fig6's records carry
    no ``family`` key, so they never collide with the timed ones."""
    seen, out = set(), []
    for r in layer_rows:
        key = (r["layer"], r["nm"], r.get("family"))
        if key not in seen:
            seen.add(key)
            out.append(r)
    return out


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    ap.add_argument("--measured", action="store_true",
                    help="also time the real Pallas dispatch on the CNN "
                         "layer GEMMs (fig4/5/6 measured modes)")
    ap.add_argument("--smoke", action="store_true",
                    help="sub-sample layers / cap the pixel dim so the "
                         "measured sweep fits the CI budget")
    ap.add_argument("--serve", action="store_true",
                    help="also run the serving throughput bench "
                         "(benchmarks/serve_bench.py; spawns 1-device and "
                         "forced-8-device subprocesses)")
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import use_compile_cache  # noqa: PLC0415

    use_compile_cache()
    serve_rows: list = []
    serve_metrics: dict = {}
    if args.serve:
        # first, while this process has not touched a device: the
        # 1-device cell runs in its own process, which on a chip host
        # must be the only one holding the chip
        from benchmarks import serve_bench  # noqa: PLC0415

        serve_rows, serve_metrics = serve_bench.bench_rows_and_metrics(
            smoke=args.smoke)

    from benchmarks import (  # noqa: PLC0415
        fig4_resnet_layers,
        fig5_cnn_totals,
        fig6_memory_traffic,
        tpu_kernel_roofline,
    )

    rows = []
    for mod in (fig4_resnet_layers, fig5_cnn_totals, fig6_memory_traffic,
                tpu_kernel_roofline):
        t0 = time.perf_counter()
        out = mod.main()
        dt = (time.perf_counter() - t0) * 1e6
        for name, us, derived in out:
            rows.append((name, us if us else dt, derived))

    layer_rows: list[dict] = []
    if args.measured or args.serve:
        from benchmarks import measured  # noqa: PLC0415

        rows.append(measured.calibration_row())
    if args.measured:
        for mod in (fig4_resnet_layers, fig5_cnn_totals,
                    fig6_memory_traffic):
            mrows, mlayers = mod.measured_main(smoke=args.smoke)
            rows += mrows
            layer_rows += mlayers
        layer_rows = _dedupe_layers(layer_rows)
    rows += serve_rows

    print("\nname,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")

    payload = {
        "schema": 2,
        "mode": {"measured": args.measured, "smoke": args.smoke,
                 "serve": args.serve},
        "rows": [{"name": n, "us_per_call": round(us, 1), "derived": d}
                 for n, us, d in rows],
        "layers": layer_rows,
        "kernels": tpu_kernel_roofline.kernel_records(),
    }
    if serve_metrics:
        # per-device-count obs metrics snapshots from the serve bench's
        # untimed obs-on pass (the timed rows stay obs-off)
        payload["serve_metrics"] = serve_metrics
    with open(OUT_JSON, "w") as f:
        json.dump(payload, f, indent=1)
    # stderr: stdout from the CSV header down is machine-consumed
    print(f"wrote {OUT_JSON} ({len(payload['rows'])} rows, "
          f"{len(payload['layers'])} layer records, "
          f"{len(payload['kernels'])} kernel records)", file=sys.stderr)


if __name__ == "__main__":
    main()
