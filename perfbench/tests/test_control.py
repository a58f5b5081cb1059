"""The control — the reference computed in float8, put in the program's
place — reads not correct by the cell's own limit, at a size a test run
holds (on the chip it was read at each cell's own size; PERF.md §2)."""
import json

import numpy as np
import pytest

from harness import compare, spec, weights
from runs import run_tiny, tiny_cell

LLAMA = spec.load_module(spec.BENCH_DIR / "reference" / "llama.py")


@pytest.mark.parametrize("workload", ["yi9b-decode", "yi9b-mixed"])
def test_llama_control_fails_the_limit(workload):
    """A yi-shaped decoder of 8 layers at width 512: at each position of
    two sequences, the gap of the token the float8 reference puts first."""
    base = json.loads((spec.BENCH_DIR / "configs" / "yi-9b-2to4.json")
                      .read_text())
    cfg = dict(base, hidden_size=512, num_hidden_layers=8,
               num_attention_heads=8, num_key_value_heads=2, head_dim=64,
               intermediate_size=1024, vocab_size=4096)
    key = weights.seed_key(2 ** 33 + 123)
    toks = np.random.default_rng(0).integers(1, 4096, (2, 128)).astype(np.int32)
    rows = np.asarray([(b, p) for b in range(2) for p in range(64, 128)],
                      np.int32)
    x = LLAMA.hidden(cfg, key, toks)
    mx, _, best = LLAMA.logit_rows(cfg, key, x, rows, toks[rows[:, 0], rows[:, 1]])
    xc = LLAMA.hidden(cfg, key, toks, fp8=True)
    _, _, picked = LLAMA.logit_rows(cfg, key, xc, rows, best, fp8=True)
    _, at, _ = LLAMA.logit_rows(cfg, key, x, rows, picked.astype(np.int32))
    ok, checked = compare.judge({"max_logit_gap": compare.max_logit_gap(mx, at)},
                                spec.load_cell(workload).limits)
    assert not ok, checked
    # the reference's own choice reads 0
    _, at_ref, _ = LLAMA.logit_rows(cfg, key, x, rows, best.astype(np.int32))
    assert compare.max_logit_gap(mx, at_ref) == 0.0


def test_cnn_control_fails_the_limit(monkeypatch):
    outcome, line = run_tiny("cnn", monkeypatch, control=True)
    assert line["correct"]
    ok, checked = compare.judge(outcome.control, tiny_cell("cnn").limits)
    assert not ok, checked
