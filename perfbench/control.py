"""Readings that set a cell's limits: the program's numbers over many seeds
and the control's (the reference in float8 in the program's place).

  python3 perfbench/control.py --workload yi9b-decode --seconds 30 \
      --seeds 101,102,...,112 --control-seeds 101,102,103

Each seed is one whole run of the cell (the same code as run.py: set-up,
window, check), all in this one process so that set-up is paid once;
on the control seeds the check also runs the control on the same
sequences. Not part of a benchmark run. Prints one JSON line per seed
and a summary: the lower reading is the largest number of the program,
the upper the smallest of the control.
"""
from __future__ import annotations

import argparse
import json
import sys

import run as bench
from harness import device, spec


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--out", default=None)
    a = ap.parse_args(argv)
    cell = spec.load_cell(a.workload)
    sys.path.insert(0, str(spec.ROOT / "src"))
    import jax

    devs = jax.devices()
    peaks = device.check(devs[0].platform, devs[0].device_kind, len(devs),
                         cell.chips, device.load_peaks())
    dev = device.describe(jax, cell.chips)
    bench.setup_jax(jax)
    ctrl_seeds = {int(s) for s in a.control_seeds.split(",") if s}
    rows = []
    for s in (int(x) for x in a.seeds.split(",")):
        outcome, line = bench.execute(cell, s, a.seconds, False, peaks, dev,
                                      control=s in ctrl_seeds)
        row = {"seed": s, "program": outcome.compared,
               "control": outcome.control,
               "metrics": {k: v["value"] for k, v in line["metrics"].items()},
               "memory_peak_bytes": outcome.memory_peak_bytes}
        rows.append(row)
        print(json.dumps(row), flush=True)
    summary = {"workload": a.workload, "seconds": a.seconds,
               "device": dev, "rows": rows}
    for name in rows[0]["program"]:
        summary[name] = {
            "lower": max(r["program"][name] for r in rows),
            "upper": min((r["control"][name] for r in rows if r["control"]),
                         default=None)}
    print(json.dumps({k: v for k, v in summary.items() if k != "rows"}),
          flush=True)
    if a.out:
        with open(a.out, "w") as f:
            json.dump(summary, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
