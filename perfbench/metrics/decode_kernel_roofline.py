"""Share (%) of the roofline reached by the decode GEMM kernels.

Device time: trace operations named ``nm_spmm_pallas_decode*`` in the
window. Least time: the N:M GEMMs of every decode step in the window, at
the rows that step decoded, from shapes (harness.work: kept values, 2-bit
indices, activations once), each bound by FLOP/s or HBM bandwidth.
"""
from harness import work

KERNEL = "nm_spmm_pallas_decode"


def read(r):
    if r.trace is None or not r.work.get("decode_gemms"):
        return None
    return work.roofline_percent(r.work["decode_gemms"],
                                 r.trace.seconds_of(KERNEL), r.peaks)
