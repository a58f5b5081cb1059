"""Whole-step share (%) of the chip's bf16 peak in decode: required
operations of every token emitted in the window (kept weights, head,
attention over its real context; harness.work) over the window."""
from harness import work


def read(r):
    flops = r.work.get("window_flops")
    if not flops:
        return None
    return work.mfu_percent(flops, r.window.seconds, r.peaks)
