"""The program's own spans (``repro.obs`` tracer events) inside a window.

The tracer emits matched ``B``/``E`` pairs per thread, timestamps in
microseconds on its own perf_counter origin; the window's bounds are read
from the same tracer (``now_us``) at its start and end.
"""
from __future__ import annotations

from collections import defaultdict


def pair(events: list, t0_us: float, t1_us: float) -> list:
    """Completed spans [(name, start_us, dur_us, args)] that start and end
    inside [t0_us, t1_us]."""
    open_ = defaultdict(list)
    out = []
    for ev in sorted(events, key=lambda e: e["ts"]):
        key = (ev.get("tid"), ev["name"])
        if ev["ph"] == "B":
            open_[key].append(ev)
        elif ev["ph"] == "E" and open_[key]:
            b = open_[key].pop()
            if b["ts"] >= t0_us and ev["ts"] <= t1_us:
                out.append((ev["name"], b["ts"], ev["ts"] - b["ts"],
                            b.get("args", {})))
    return out


def durations_ms(spans: list, name: str) -> list:
    return [d / 1e3 for n, _, d, _ in spans if n == name]
