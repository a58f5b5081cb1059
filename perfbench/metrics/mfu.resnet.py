"""Whole-step share (%) of the chip's bf16 peak: required
operations of every image classified in the window (kept conv
weights, dense stem and head; harness.work) over the window."""
from harness import work


def read(r):
    flops = r.work.get("window_flops")
    if not flops:
        return None
    return work.mfu_percent(flops, r.window.seconds, r.peaks)
