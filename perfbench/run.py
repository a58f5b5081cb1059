"""One run of one benchmark cell on the chip(s) of this machine.

  python3 perfbench/run.py --workload yi9b-decode --seed 7 --seconds 30 --trace 0

Run from the root of a checkout. The cell's entry in BENCHMARK.json names
its configuration and traffic; their files, the cell's limits and the
per-layer metric readers are found by those names (see harness/spec.py).
With ``--trace 0`` the result line carries the cell's end-to-end metrics,
with ``--trace 1`` its per-layer metrics, read from a profiler trace of
the same window and from the program's spans.

The last line of standard output is one JSON object: correct, attempted,
failed, metrics, device (and with --trace 1 breakdown), and last the
numbers compared with their limits, which also end standard error. A run
that finds no TPU, too few chips or a chip missing from the table of
peaks exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_PROCESS0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

from harness import compare, device, spec  # noqa: E402

CACHE_DIR = spec.ROOT / ".jax_cache"
WORK_DIR = spec.ROOT / ".perfbench"


def _args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def setup_jax(jax) -> str:
    """Persistent compilation cache at a fixed path inside the checkout
    (or where JAX_COMPILATION_CACHE_DIR says); every program is cached."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def per_layer(outcome, cell) -> dict:
    out = {}
    for m in cell.per_layer:
        value = cell.metric_reader(m["name"]).read(outcome.record)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def result_line(outcome, cell, dev: dict, trace: bool) -> dict:
    correct, checked = compare.judge(outcome.compared, cell.limits)
    if trace:
        metrics = per_layer(outcome, cell)
    else:
        metrics = {}
        for m in cell.end_to_end:
            if m["name"] not in outcome.end_to_end:
                raise spec.SpecError(f"{cell.name} reports no {m['name']}")
            metrics[m["name"]] = {"value": float(outcome.end_to_end[m["name"]]),
                                  "unit": m["unit"]}
    line = {"correct": correct, "attempted": outcome.attempted,
            "failed": outcome.failed, "metrics": metrics, "device": dev}
    if trace:
        tr = outcome.record.trace
        line["device"] = dict(dev, busy_s=tr.busy_s, window_s=tr.window_s)
        line["breakdown"] = tr.breakdown()
    line["checked"] = checked
    return line


def execute(cell, seed: int, seconds: float, trace: bool, peaks: dict,
            dev: dict, control: bool = False):
    """Everything of a run after the device check: (outcome, result line)."""
    from harness.record import RunArgs

    os.makedirs(WORK_DIR, exist_ok=True)
    outcome = cell.system().run(RunArgs(
        cell=cell, seed=seed, seconds=seconds, trace=trace,
        t_process0=T_PROCESS0, peaks=peaks, work_dir=str(WORK_DIR),
        control=control))
    dev = dict(dev, memory_peak_bytes=outcome.memory_peak_bytes)
    print("setup: " + " ".join(f"{k}={v:.3f}" for k, v in
                               outcome.setup.items())
          + f" setup_s={outcome.end_to_end['setup_s']:.3f}", flush=True)
    return outcome, result_line(outcome, cell, dev, trace)


def main(argv=None) -> int:
    a = _args(argv)
    cell = spec.load_cell(a.workload)
    sys.path.insert(0, str(spec.ROOT / "src"))
    import jax

    devs = jax.devices()
    peaks = device.check(devs[0].platform, devs[0].device_kind, len(devs),
                         cell.chips, device.load_peaks())
    dev = device.describe(jax, cell.chips)
    print(f"device: {dev}; compile cache: {setup_jax(jax)}", flush=True)
    _, line = execute(cell, a.seed, a.seconds, bool(a.trace), peaks, dev)
    for name, c in line["checked"].items():
        print(f"checked {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except (device.DeviceError, spec.SpecError) as e:
        print(f"perfbench: {e}", file=sys.stderr, flush=True)
        sys.exit(2)
