"""Drive a whole benchmark run on the CPU at a size a test can hold: the
cell's own limits and metric lists, the tiny configuration and traffic
of ``data/``, everything of run.py after its look for a chip."""
import json
import pathlib

from harness import device, spec

DATA = pathlib.Path(__file__).with_name("data")
TINY = {"lm": ("yi9b-decode", "yi-tiny.json", "tiny-backlog.json"),
        "cnn": ("resnet50-offline", "resnet-tiny.json", "tiny-batches.json")}
# the CNN cell's entries, kept out of BENCHMARK.json while the program
# hangs on some of its seeds on the chip (PERF.md, Open questions)
CNN_BENCH = DATA / "cnn-bench.json"
PEAKS = device.load_peaks()["TPU v5 lite"]
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}


def tiny_cell(kind: str):
    workload, cfg, traffic = TINY[kind]
    cell = spec.load_cell(workload,
                          bench_json=CNN_BENCH if kind == "cnn" else None)
    cell.config = json.loads((DATA / cfg).read_text())
    cell.traffic = json.loads((DATA / traffic).read_text())
    return cell


def run_tiny(kind: str, monkeypatch, seed: int = 2 ** 33 + 5,
             seconds: float = 1.0, control: bool = False):
    import run as bench

    monkeypatch.setattr(device, "memory_peak_bytes", lambda jax, chips: 0)
    return bench.execute(tiny_cell(kind), seed, seconds, False, PEAKS, CPU,
                         control=control)
