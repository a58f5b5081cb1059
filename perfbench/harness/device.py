"""The device a run is on, checked against the cell and the table of peaks.

A run that finds no TPU, fewer chips than its cell asks for, or a chip
whose ``device_kind`` is not in ``peaks.json`` stops before any result
is printed: no number from another platform is written under a device
metric's name.
"""
from __future__ import annotations

import json
import pathlib

PEAKS_FILE = pathlib.Path(__file__).with_name("peaks.json")


class DeviceError(RuntimeError):
    """The run is not on the hardware its cell asks for."""


def load_peaks(path: pathlib.Path = PEAKS_FILE) -> dict:
    with open(path) as f:
        return json.load(f)["devices"]


def check(platform: str, kind: str, count: int, chips: int,
          peaks: dict) -> dict:
    """The peaks of ``kind``; raises DeviceError where the run may not go on."""
    if platform != "tpu":
        raise DeviceError(f"no TPU: JAX platform is {platform!r}")
    if count < chips:
        raise DeviceError(f"the cell asks for {chips} chips, JAX sees {count}")
    if kind not in peaks:
        raise DeviceError(f"device kind {kind!r} is not in the table of "
                          f"peaks ({sorted(peaks)})")
    return peaks[kind]


def describe(jax_module, chips: int) -> dict:
    """``device`` entry of the result line, as JAX reports it."""
    devs = jax_module.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


def memory_peak_bytes(jax_module, chips: int) -> int:
    """Peak bytes in use on the fullest chip of the run."""
    peaks = []
    for d in jax_module.devices()[:chips]:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" not in stats:
            raise DeviceError(f"{d} reports no peak_bytes_in_use")
        peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks)
