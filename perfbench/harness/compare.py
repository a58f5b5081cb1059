"""The numbers ``correct`` compares, and their limits.

A cell's limits file holds, for each number, its ``limit`` and the
readings it was set from. The run is correct when every number is at or
under its limit.

* ``max_logit_gap`` (served language models): for every compared served
  token, the reference's largest logit minus the reference's logit of
  that token, at the same position of the same sequence; the widest gap.
  Greedy decoding with the reference's arithmetic gives 0.
* ``max_rel_err`` (classifiers): for every compared image,
  ||logits - reference|| / ||reference||; the largest.
"""
from __future__ import annotations

import numpy as np


def max_logit_gap(ref_max: np.ndarray, ref_at: np.ndarray) -> float:
    return float(np.max(np.asarray(ref_max) - np.asarray(ref_at)))


def max_rel_err(y: np.ndarray, ref: np.ndarray) -> float:
    y = np.asarray(y, np.float64)
    ref = np.asarray(ref, np.float64)
    num = np.linalg.norm(y - ref, axis=-1)
    den = np.linalg.norm(ref, axis=-1)
    return float(np.max(num / den))


def judge(compared: dict, limits: dict) -> tuple[bool, dict]:
    """(correct, {name: {"value", "limit"}}) — every number against its
    own limit; a number with no limit, or a non-finite one, fails."""
    out = {}
    ok = True
    for name, value in compared.items():
        lim = limits.get(name, {}).get("limit")
        out[name] = {"value": value, "limit": lim}
        if lim is None or not np.isfinite(value) or value > lim:
            ok = False
    return ok, out
