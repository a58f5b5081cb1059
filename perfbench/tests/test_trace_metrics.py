"""Readers of what the program traces from inside its engine step: the
prefill row counts of its ``engine.prefill`` spans, the host's own time
per ``engine.step``, and the decode kernels named after their
projections. Each on a hand-made record, each silent without its
source, and the program's row share against the benchmark's own count
on a whole tiny run."""
import json

import pytest

from harness import devtrace, spec, work
from harness.record import Record
from harness.window import Window

PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
NEW = {"prefill_row_share.mixed": "yi9b-mixed",
       "host_ms_per_step.decode": "yi9b-decode",
       "ffn_kernel_roofline.decode": "yi9b-decode",
       "attn_kernel_roofline.decode": "yi9b-decode"}


def _record(workload, **kw):
    cell = spec.load_cell(workload)
    base = dict(cell=cell, peaks=PEAK, window=Window(0.0, 2.0, 10, 4),
                counts={}, work={})
    base.update(kw)
    return cell, Record(**base)


def _read(cell, name, r):
    return cell.metric_reader(name).read(r)


@pytest.mark.parametrize("name", sorted(NEW))
def test_benchmark_lists_the_metric_in_its_cell(name):
    bench = json.loads((spec.ROOT / "BENCHMARK.json").read_text())
    entry = {m["name"]: m for m in bench["per_layer"]}[name]
    assert entry["workloads"] == [NEW[name]]
    assert name in [m["name"] for m in spec.load_cell(NEW[name]).per_layer]


@pytest.mark.parametrize("name", sorted(NEW))
def test_reader_without_its_source_returns_nothing(name):
    cell, r = _record(NEW[name])
    assert _read(cell, name, r) is None
    # a program that traces less: spans without the row counts, an
    # engine.step instant rather than a span, kernels under the bare name
    g = work.Gemm(8, 4096, 4096, (2, 4))
    trace = devtrace.Reduced(window_s=2.0, busy_s=1.5,
                             op_seconds={"nm_spmm_pallas_decode.48": 0.5},
                             op_counts={}, gaps=[])
    cell, r = _record(NEW[name], trace=trace,
                      spans=[("engine.prefill", 0, 9.0, {"active": 2}),
                             ("engine.decode", 10, 5.0, {"active": 2})],
                      work={"decode_gemms": [g]})
    assert _read(cell, name, r) is None


def test_prefill_row_share_sums_the_programs_counts():
    spans = [("engine.prefill", 0, 9.0, {"rows": 2048, "real_rows": 100}),
             ("engine.decode", 10, 5.0, {"ctx_tokens": 900}),
             ("engine.prefill", 20, 9.0, {"rows": 1024, "real_rows": 300})]
    cell, r = _record("yi9b-mixed", spans=spans)
    assert _read(cell, "prefill_row_share.mixed", r) == \
        pytest.approx(100 * 400 / 3072)


def test_host_ms_per_step_leaves_out_the_readback():
    # three steps of 10, 12 and 30 ms; readbacks of 9, 9 and 10 ms inside
    # them, and one readback outside every step, which counts for none
    spans = [("engine.step", 0, 10_000.0, {}),
             ("engine.readback", 500, 9_000.0, {}),
             ("engine.step", 20_000, 12_000.0, {}),
             ("engine.readback", 21_000, 9_000.0, {}),
             ("engine.step", 40_000, 30_000.0, {}),
             ("engine.readback", 41_000, 10_000.0, {}),
             ("engine.readback", 80_000, 50_000.0, {})]
    cell, r = _record("yi9b-decode", spans=spans)
    assert _read(cell, "host_ms_per_step.decode", r) == pytest.approx(3.0)


def test_kernel_group_rooflines_split_the_decode_kernels():
    """The FFN and attention groups take the decode GEMMs by shape and
    the device time by name; their seconds add up to the family's."""
    cell = spec.load_cell("yi9b-decode")
    gemms = work.lm_sparse_step_gemms(cell.config, 8)
    width = cell.config["intermediate_size"]
    ffn = [g for g in gemms if width in (g.k, g.n)]
    attn = [g for g in gemms if width not in (g.k, g.n)]
    assert len(ffn) == 3 * cell.config["num_hidden_layers"]
    assert len(attn) == 4 * cell.config["num_hidden_layers"]
    ops = {"nm_spmm_pallas_decode_ffn_w_up.3": 2.0,
           "nm_spmm_pallas_decode_ffn_w_gate.4": 2.0,
           "nm_spmm_pallas_decode_ffn_w_down.5": 2.0,
           "nm_spmm_pallas_decode_attn_proj_wq.1": 0.5,
           "nm_spmm_pallas_decode_attn_proj_wo.2": 0.5,
           "dynamic-slice_bitcast_fusion.44": 0.9}
    trace = devtrace.Reduced(window_s=10.0, busy_s=9.0, op_seconds=ops,
                             op_counts={}, gaps=[])
    _, r = _record("yi9b-decode", trace=trace,
                   work={"decode_gemms": gemms})
    got_ffn = _read(cell, "ffn_kernel_roofline.decode", r)
    got_attn = _read(cell, "attn_kernel_roofline.decode", r)
    assert got_ffn == pytest.approx(
        100 * work.floor_seconds(ffn, PEAK) / 6.0)
    assert got_attn == pytest.approx(
        100 * work.floor_seconds(attn, PEAK) / 1.0)
    assert trace.seconds_of("nm_spmm_pallas_decode_ffn") + \
        trace.seconds_of("nm_spmm_pallas_decode_attn_proj") == \
        pytest.approx(trace.seconds_of("nm_spmm_pallas_decode"))
    # the whole family's roofline is the two groups', weighted by time
    whole = _read(cell, "decode_kernel_roofline", r)
    assert whole == pytest.approx((got_ffn * 6.0 + got_attn * 1.0) / 7.0)


def test_program_row_share_matches_the_benchmarks_count(monkeypatch):
    """With every prefill at the fixed (slots, prefill_len) shape, the
    program's own row counts give the share the benchmark computes from
    its requests; with chunked prefill only the program's is defined."""
    import run as bench
    from runs import CPU, PEAKS, tiny_cell

    from harness import device

    monkeypatch.setattr(device, "memory_peak_bytes", lambda jax, chips: 0)
    cell = tiny_cell("lm")
    chunked = cell.traffic["prefill_chunk"]
    assert chunked < cell.traffic["prefill_len"]
    cell.traffic = dict(cell.traffic, prefill_chunk=cell.traffic["prefill_len"])
    outcome, _ = bench.execute(cell, 2 ** 33 + 9, 0.5, False, PEAKS, CPU)
    rec = outcome.record
    assert rec.counts["prefill_steps"] > 0
    program = _read(cell, "prefill_row_share.mixed", rec)
    benchmark = _read(cell, "prefill_useful_share.mixed", rec)
    assert program is not None and 0 < program < 100
    assert program == pytest.approx(benchmark, rel=1e-9)

    cell = tiny_cell("lm")
    outcome, _ = bench.execute(cell, 2 ** 33 + 9, 0.5, False, PEAKS, CPU)
    assert _read(cell, "prefill_useful_share.mixed", outcome.record) is None
    assert 0 < _read(cell, "prefill_row_share.mixed", outcome.record) < 100
