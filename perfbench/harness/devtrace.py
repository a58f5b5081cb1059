"""Device trace of a window: capture with the JAX profiler, reduce to numbers.

The reduction works on plain event lists, so it is tested on a small
recorded trace:

* device events: ``(name, start_ns, duration_ns)`` of the operations
  that ran on a chip (the XLA Ops line of each ``/device:TPU:<i>`` plane),
* host events: the same for the Python thread's spans (the benchmark's
  ``bench.*`` annotations and JAX's own dispatch events).

The window is the ``bench.window`` host span (or, where the host tracer
dropped it, the first to the last ``bench.step`` span, or else the first
to the last device operation of the trace, which starts and stops with
the window). Busy time is the
union of the device intervals inside it, averaged over chips; an idle gap
is a stretch of the window with no device operation, named after the
innermost host span that covers its middle ("none" where no span does).
The device and host timelines of a TPU trace are offset by a few
milliseconds, so a gap's name is a guide, not a measurement.

The Python function tracer stays off: it records every Python call of
the engine's host loop, slows it, and overflows the host buffers.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import shutil
import sys
from collections import defaultdict
from typing import Optional

WINDOW_SPAN = "bench.window"
STEP_SPAN = "bench.step"
DEVICE_LINE = "XLA Ops"


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float                   # union of device intervals, mean over chips
    op_seconds: dict                # op name -> device self seconds in the window
    op_counts: dict                 # op name -> events in the window
    gaps: list                      # (seconds, host span name), longest first

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def seconds_of(self, prefix: str) -> float:
        """Device seconds of the operations whose name starts with prefix."""
        return sum(s for n, s in self.op_seconds.items() if n.startswith(prefix))

    def count_of(self, prefix: str) -> int:
        return sum(c for n, c in self.op_counts.items() if n.startswith(prefix))

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.op_seconds.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[n, s] for n, s in ops],
                "idle_gaps": [[n, s] for s, n in self.gaps[:top]]}


def _union(intervals: list) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def reduce(device: dict, host: list) -> Reduced:
    """``device``: chip id -> [(name, start_ns, dur_ns)]; ``host``: the
    Python thread's [(name, start_ns, dur_ns)] holding one bench.window."""
    if not device or not any(device.values()):
        raise ValueError("no device operation in the trace")
    wins = [(s, s + d) for n, s, d in host if n == WINDOW_SPAN]
    steps = [(s, s + d) for n, s, d in host if n == STEP_SPAN]
    ops = [(s, s + d) for ev in device.values() for _, s, d in ev]
    if len(wins) > 1:
        raise ValueError(f"{len(wins)} {WINDOW_SPAN} spans in one trace")
    if wins:
        w0, w1 = wins[0]
    elif steps:
        w0, w1 = min(s for s, _ in steps), max(e for _, e in steps)
    else:
        w0, w1 = min(s for s, _ in ops), max(e for _, e in ops)
    spans = [(s, s + d, n) for n, s, d in host if n != WINDOW_SPAN]
    op_ns = defaultdict(float)
    op_n = defaultdict(int)
    busy = []
    gaps = []
    for events in device.values():
        ivs = []
        for name, s, d in events:
            a, b = max(s, w0), min(s + d, w1)
            if b > a:
                ivs.append((a, b, name))
        for name, own in _self_times(ivs):
            op_ns[name] += own
            op_n[name] += 1
        merged = _union([(a, b) for a, b, _ in ivs])
        busy.append(sum(b - a for a, b in merged))
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                gaps.append((b - a, _host_name(spans, (a + b) / 2)))
    gaps.sort(key=lambda g: -g[0])
    n_chips = len(device)
    return Reduced(
        window_s=(w1 - w0) * 1e-9,
        busy_s=sum(busy) / n_chips * 1e-9,
        op_seconds={n: v * 1e-9 / n_chips for n, v in op_ns.items()},
        op_counts=dict(op_n),
        gaps=[(g * 1e-9, n) for g, n in gaps])


def _self_times(ivs: list) -> list:
    """(name, self time) of nested intervals [(start, end, name)]: a
    container op (the ``while`` of a layer scan) keeps only the time no
    op inside it covers, so kernels are not counted twice."""
    order = sorted(range(len(ivs)), key=lambda i: (ivs[i][0], -ivs[i][1]))
    own = [b - a for a, b, _ in ivs]
    stack = []
    for i in order:
        a, b, _ = ivs[i]
        while stack and ivs[stack[-1]][1] <= a:
            stack.pop()
        if stack and b <= ivs[stack[-1]][1]:   # contained, not overlapping
            own[stack[-1]] -= b - a
        stack.append(i)
    return [(ivs[i][2], own[i]) for i in range(len(ivs))]


def _host_name(spans: list, t: float) -> str:
    best = None
    for s, e, n in spans:
        if s <= t <= e and (best is None or e - s < best[1] - best[0]):
            best = (s, e, n)
    return best[2] if best else "none"


def op_name(text: str) -> str:
    """``%fusion.3 = bf16[8]{0} fusion(...)`` -> ``fusion.3``."""
    if text.startswith("%"):
        return text[1:].split(" ", 1)[0].split("=", 1)[0]
    return text


def load_xplane(path: str) -> tuple[dict, list]:
    """(device events by chip, host events of the thread that ran the
    window) of an xplane file. That thread's line is the one of
    ``/host:CPU`` that holds the window span, or else the most step
    spans; its name varies with the tracer's settings."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    device, lines = {}, {}
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") and plane.name[12:].isdigit():
            chip = int(plane.name[12:])
            for line in plane.lines:
                if line.name == DEVICE_LINE:
                    device.setdefault(chip, []).extend(
                        (op_name(e.name), e.start_ns, e.duration_ns)
                        for e in line.events)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                lines.setdefault(line.name, []).extend(
                    (e.name, e.start_ns, e.duration_ns) for e in line.events)

    def rank(events):
        names = [n for n, _, _ in events]
        return (names.count(WINDOW_SPAN), names.count(STEP_SPAN))

    best = max(lines.values(), key=rank, default=[])
    if rank(best) == (0, 0):
        # the window then spans the device's own first to last operation
        print("devtrace: no bench span on any host line (" + ", ".join(
            f"{k}: {len(v)} events" for k, v in lines.items()) + ")",
              file=sys.stderr, flush=True)
        return device, []
    return device, best


class Capture:
    """Profiler on for the window of a ``--trace 1`` run, off otherwise."""

    def __init__(self, enabled: bool, out_dir: str):
        self.enabled = enabled
        self.out_dir = out_dir

    def start(self) -> None:
        if self.enabled:
            import jax

            shutil.rmtree(self.out_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.out_dir, profiler_options=opts)

    def stop(self) -> None:
        if self.enabled:
            import jax

            jax.profiler.stop_trace()

    def reduce(self, chips: int) -> Optional[Reduced]:
        """Reduce the captured trace and delete it from disk."""
        if not self.enabled:
            return None
        files = glob.glob(os.path.join(self.out_dir, "**", "*.xplane.pb"),
                          recursive=True)
        if len(files) != 1:
            raise RuntimeError(f"expected one xplane file, found {files}")
        device, host = load_xplane(files[0])
        device = {c: ev for c, ev in device.items() if c < chips}
        try:
            return reduce(device, host)
        finally:
            shutil.rmtree(self.out_dir, ignore_errors=True)
