"""Share (%) of the roofline reached by the conv GEMM kernels.

Device time: trace operations named ``nm_spmm*`` in the window (at a
batch of images every conv GEMM has far more rows than the decode
family takes). Least time: every step's N:M conv GEMMs from shapes
(harness.work), the input activation read once rather than its im2col
patches.
"""
from harness import work

KERNEL = "nm_spmm"


def read(r):
    if r.trace is None or not r.work.get("kernel_gemms"):
        return None
    return work.roofline_percent(r.work["kernel_gemms"],
                                 r.trace.seconds_of(KERNEL), r.peaks)
