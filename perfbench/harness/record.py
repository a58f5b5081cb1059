"""What one run hands to the result line and to the per-layer readers."""
from __future__ import annotations

import dataclasses
from typing import Optional

from harness.devtrace import Reduced
from harness.spec import Cell
from harness.window import Window


@dataclasses.dataclass
class Record:
    """Everything a per-layer metric reader may read; a reader that finds
    nothing it needs returns None and the metric is left out."""

    cell: Cell
    peaks: dict
    window: Window
    counts: dict                      # work done in the window, by name
    work: dict                        # shape-derived work (harness.work)
    trace: Optional[Reduced] = None   # device trace of the window
    spans: Optional[list] = None      # program spans inside the window


@dataclasses.dataclass
class Outcome:
    end_to_end: dict                  # metric name -> value
    setup: dict                       # set-up split, seconds by phase
    compared: dict                    # number name -> value
    attempted: int
    failed: int
    memory_peak_bytes: int
    record: Record
    control: dict = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class RunArgs:
    """One run's inputs, as the command line and the cell give them."""

    cell: Cell
    seed: int
    seconds: float
    trace: bool
    t_process0: float                 # perf_counter at process start
    peaks: dict
    work_dir: str                     # inside the checkout, for the trace
    control: bool = False             # also read the control's numbers
