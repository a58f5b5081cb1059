"""A window starts at a fixed warm-up step and counts tokens by emission."""
import numpy as np
import pytest

from harness import window as win


def test_tokens_count_by_emission_time():
    w = win.Window(t_start=10.0, t_end=20.0, steps=5, warmup_steps=3)
    lists = [[9.0, 11.0, 12.0],      # one token before the window
             [15.0, 19.0, 21.0],     # one after
             [], [20.0]]             # the close itself is inside
    assert win.tokens_in(w, lists) == 5
    gaps = np.sort(win.gaps_in(w, lists))
    # gaps that end inside: 9->11, 11->12, 15->19 (19->21 ends after)
    assert np.allclose(gaps, [1.0, 2.0, 4.0])
    assert w.seconds == 10.0


def test_percentile_needs_samples():
    with pytest.raises(ValueError):
        win.percentile(np.zeros(0), 95)
    assert win.percentile(np.arange(101.0), 95) == 95.0


def test_every_seed_starts_its_window_at_the_same_step(monkeypatch):
    """Two seeds: the window opens after the traffic's fixed warm-up
    steps on the same state of the same length sequence, and counts only
    the tokens emitted inside it."""
    from runs import run_tiny, tiny_cell

    outs = [run_tiny("lm", monkeypatch, seed=s, seconds=0.5)[0]
            for s in (11, 2 ** 40 + 3)]
    warm = tiny_cell("lm").traffic["warmup_steps"]
    a, b = (o.record for o in outs)
    assert a.window.warmup_steps == b.window.warmup_steps == warm
    assert a.counts["before_window"] == b.counts["before_window"]
    assert any(served for _, served in a.counts["before_window"])
    assert all(r.counts["tokens"] > 0 and r.window.steps > 0 for r in (a, b))


def test_prefill_steps_are_the_programs_spans(monkeypatch):
    """The window's prefill steps are counted from the program's own
    engine.prefill spans, in trace-0 runs too."""
    from harness import spans
    from runs import run_tiny

    rec = run_tiny("lm", monkeypatch, seconds=0.5)[0].record
    n = len(spans.durations_ms(rec.spans, "engine.prefill"))
    assert n > 0 and rec.counts["prefill_steps"] == n


def test_frozen_collector_records_and_thaws():
    import gc

    from harness import gcwatch

    with gcwatch.Frozen() as f:
        assert gc.get_freeze_count() > 0
        gc.collect()
    assert gc.get_freeze_count() == 0
    assert f.pauses and f.pauses[-1][0] == 2
    assert "(1 full)" in f.summary()
