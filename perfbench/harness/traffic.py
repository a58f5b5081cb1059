"""The one traffic generator: reads a traffic file, gives the work.

A traffic file fixes the sequence of lengths; the seed draws only token
ids (and, elsewhere, weights). So every seed serves the same work in the
same order, and two runs differ only in the numbers inside the tensors.

Kinds:

* ``closed_backlog`` — ``requests`` (prompt, output) length pairs, served
  in a loop on ``slots`` engine slots: when a request finishes, the next
  pair of the list (cyclically) is submitted, so the queue never empties.
* ``offline_batches`` — ``batches`` input batches of ``batch`` rows,
  cycled back to back.

A length is ``{"dist": "fixed", "value": v}`` or
``{"dist": "lognormal_quantiles", "median": m, "sigma": s, "min": a,
"max": b}``: the i-th of ``requests`` values is the (i + 0.5)/requests
quantile of the lognormal, rounded and clipped, and the list is then put
in a fixed order drawn from ``order_seed`` (part of the file, not of the
run's seed).
"""
from __future__ import annotations

import statistics

import numpy as np

KINDS = ("closed_backlog", "offline_batches")


def _lengths(spec: dict, count: int, order_seed: int) -> list[int]:
    dist = spec["dist"]
    if dist == "fixed":
        return [int(spec["value"])] * count
    if dist != "lognormal_quantiles":
        raise ValueError(f"unknown length distribution {dist!r}")
    nd = statistics.NormalDist()
    vals = [spec["median"] * np.exp(spec["sigma"] * nd.inv_cdf((i + 0.5) / count))
            for i in range(count)]
    vals = [int(min(max(round(v), spec["min"]), spec["max"])) for v in vals]
    order = np.random.default_rng(order_seed).permutation(count)
    return [vals[i] for i in order]


def backlog(traffic: dict) -> list[tuple[int, int]]:
    """The (prompt_len, output_len) list of a closed backlog, seed-free."""
    if traffic["kind"] != "closed_backlog":
        raise ValueError(f"not a closed backlog: {traffic['kind']!r}")
    n = int(traffic["requests"])
    seed = int(traffic["order_seed"])
    prompts = _lengths(traffic["prompt_len"], n, seed)
    outputs = _lengths(traffic["output_len"], n, seed + 1)
    for p, o in zip(prompts, outputs):
        if p < 1 or o < 1:
            raise ValueError(f"lengths must be positive: {p}, {o}")
        if min(p, traffic["prefill_len"]) + o > traffic["max_seq"]:
            raise ValueError(f"prompt {p} + output {o} exceeds max_seq "
                             f"{traffic['max_seq']}")
    return list(zip(prompts, outputs))


def prompt_tokens(seed: int, serial: int, length: int, vocab: int) -> np.ndarray:
    """Token ids of the ``serial``-th request of a run (ids 1..vocab-1)."""
    rng = np.random.default_rng([seed, serial])
    return rng.integers(1, vocab, size=length).astype(np.int32)
