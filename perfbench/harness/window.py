"""Accounting of a measured window: what was emitted in it, and its gaps.

A window is the interval (t_start, t_end] on the host's perf_counter
clock: it starts at a step boundary after a fixed number of warm-up
steps and ends when the first step that completes at or after the
requested seconds returns. Tokens count by their emission time, so a
request still running at the close contributes what it emitted.

Inter-token latency pools every request's own gaps (the consecutive
timestamps of its tokens, as ``benchmarks/serve_bench.py`` pools them),
finished or not, for every gap that ends inside the window.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class Window:
    t_start: float
    t_end: float
    steps: int              # engine steps that ran inside the window
    warmup_steps: int       # steps before it (fixed by the traffic file)

    @property
    def seconds(self) -> float:
        return self.t_end - self.t_start

    def inside(self, t: float) -> bool:
        return self.t_start < t <= self.t_end


def token_times(token_lists) -> np.ndarray:
    """All emission times of the given per-request timestamp lists."""
    parts = [np.asarray(t, np.float64) for t in token_lists if len(t)]
    return np.concatenate(parts) if parts else np.zeros(0)


def tokens_in(win: Window, token_lists) -> int:
    t = token_times(token_lists)
    return int(np.count_nonzero((t > win.t_start) & (t <= win.t_end)))


def gaps_in(win: Window, token_lists) -> np.ndarray:
    """Every inter-token gap (seconds) that ends inside the window."""
    out = []
    for t in token_lists:
        if len(t) < 2:
            continue
        t = np.asarray(t, np.float64)
        end = t[1:]
        keep = (end > win.t_start) & (end <= win.t_end)
        out.append(np.diff(t)[keep])
    return np.concatenate(out) if out else np.zeros(0)


def percentile(values: np.ndarray, q: float) -> float:
    if values.size == 0:
        raise ValueError("no samples")
    return float(np.percentile(values, q))
