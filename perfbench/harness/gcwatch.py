"""Python's garbage collector around a measured window.

Set-up leaves a large heap of long-lived objects (modules, traced
programs, the runtime's caches). A full collection walks all of them and
holds the host for as long as it takes; inside a window that is a stalled
step. ``Frozen`` collects once at the end of set-up and freezes what is
left (``gc.freeze``, as servers do after their warm-up), so that
collections inside the window walk only what the window itself makes. It
records every collection made while it is open, so that each run can
print how long the collector held the host, and thaws on exit, so that
the program's state can be collected before the check.
"""
from __future__ import annotations

import gc
import time


class Frozen:
    def __init__(self):
        self.pauses = []        # (generation, seconds) of each collection
        self._t0 = None

    def _note(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            self.pauses.append((info["generation"],
                                time.perf_counter() - self._t0))
            self._t0 = None

    def __enter__(self) -> "Frozen":
        gc.collect()
        gc.freeze()
        gc.callbacks.append(self._note)
        return self

    def __exit__(self, *exc) -> bool:
        gc.callbacks.remove(self._note)
        gc.unfreeze()
        return False

    def summary(self) -> str:
        full = sum(1 for g, _ in self.pauses if g == 2)
        longest = max((s for _, s in self.pauses), default=0.0)
        total = sum(s for _, s in self.pauses)
        return (f"gc in window: {len(self.pauses)} collections ({full} full), "
                f"longest {longest * 1e3:.3f} ms, total {total * 1e3:.3f} ms")
