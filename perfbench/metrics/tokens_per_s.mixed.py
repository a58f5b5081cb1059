"""Tokens emitted in the window (by emission time) over the window's
length, in the mixed backlog: its throughput, set by how admissions and
the padded prefill interleave with decoding. A per-layer reading here
because a rare host stall of a second or more moves it by several
percent; its end-to-end metric is the inter-token tail."""


def read(r):
    tokens = r.counts.get("tokens")
    return tokens / r.window.seconds if tokens else None
