"""Per-layer readers on hand-made records: each reads its own source and
returns nothing where that source is absent."""
import pytest

from harness import devtrace, spec, work
from harness.record import Record
from harness.window import Window

PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _record(workload, **kw):
    cell = spec.load_cell(workload)
    base = dict(cell=cell, peaks=PEAK, window=Window(0.0, 2.0, 10, 4),
                counts={}, work={})
    base.update(kw)
    return cell, Record(**base)


def _read(cell, name, r):
    return cell.metric_reader(name).read(r)


def test_decode_readers():
    g = work.Gemm(8, 4096, 4096, (2, 4))
    floor = work.floor_seconds([g], PEAK)
    trace = devtrace.Reduced(window_s=2.0, busy_s=1.5,
                             op_seconds={"nm_spmm_pallas_decode": 40 * floor,
                                         "fusion": 0.1},
                             op_counts={}, gaps=[])
    spans = [("engine.decode", 0, 250_000.0, {}),
             ("engine.decode", 0, 260_000.0, {}),
             ("engine.decode", 0, 900_000.0, {})]
    cell, r = _record("yi9b-decode", trace=trace, spans=spans,
                      work={"decode_gemms": [g] * 10,
                            "window_flops": 0.197e12})
    assert _read(cell, "decode_kernel_roofline", r) == pytest.approx(25.0)
    assert _read(cell, "idle_share.decode", r) == pytest.approx(25.0)
    assert _read(cell, "decode_step_ms.decode", r) == pytest.approx(260.0)
    assert _read(cell, "mfu.decode", r) == pytest.approx(0.05)


def test_readers_without_their_source_return_nothing():
    cell, r = _record("yi9b-decode")
    for m in cell.per_layer:
        assert _read(cell, m["name"], r) is None, m["name"]


def test_prefill_useful_share():
    cell, r = _record("yi9b-mixed", counts={
        "prefill_steps": 4, "slots": 8, "prefill_chunk": 256,
        "prefill_prompt_tokens": 512})
    assert _read(cell, "prefill_useful_share.mixed", r) == \
        pytest.approx(100 * 512 / (4 * 8 * 256))


def test_mixed_throughput_is_window_tokens_over_window():
    cell, r = _record("yi9b-mixed", counts={"tokens": 50})
    assert _read(cell, "tokens_per_s.mixed", r) == pytest.approx(25.0)
    cell, r = _record("yi9b-mixed")
    assert _read(cell, "tokens_per_s.mixed", r) is None
