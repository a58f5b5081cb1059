"""Share (%) of the roofline reached by the decode kernels of the FFN
projections. Device time: trace operations named
``nm_spmm_pallas_decode_ffn*`` in the window (the program names each
kernel after its projection). Least time: the window's decode GEMMs with
the FFN width (``intermediate_size``) in their shape (harness.work)."""
from harness import work

KERNEL = "nm_spmm_pallas_decode_ffn"


def read(r):
    width = r.cell.config["intermediate_size"]
    gemms = [g for g in r.work.get("decode_gemms") or []
             if width in (g.k, g.n)]
    if r.trace is None or not gemms:
        return None
    return work.roofline_percent(gemms, r.trace.seconds_of(KERNEL), r.peaks)
