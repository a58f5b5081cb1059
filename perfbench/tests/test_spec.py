"""Cells, mixes, limits and metric readers are found by name; adding
files (and entries) adds a cell without editing any file."""
import json
import shutil

import pytest

from harness import spec

BENCH = spec.BENCH_DIR


def _bench():
    return json.loads((spec.ROOT / "BENCHMARK.json").read_text())


@pytest.mark.parametrize("workload,bench_json", [
    (w["name"], None) for w in _bench()["workloads"]] + [
    ("resnet50-offline", BENCH / "tests" / "data" / "cnn-bench.json")])
def test_every_cell_loads_with_its_files(workload, bench_json):
    cell = spec.load_cell(workload, bench_json=bench_json)
    assert cell.system().run and cell.reference().tensors
    names = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert cell.per_layer, "every cell reports a per-layer metric"
    for m in cell.per_layer:
        assert m["moves"] in names
        assert callable(cell.metric_reader(m["name"]).read)
    assert set(cell.limits) and all("limit" in v for v in cell.limits.values())


def test_names_and_units_are_plain():
    b = _bench()
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in b[group]]
        assert len(set(names)) == len(names)
        for n in names:
            spec.check_name(n)
    for m in b["end_to_end"] + b["per_layer"]:
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")


def test_a_new_cell_is_new_files(tmp_path):
    """Copy the benchmark, add a traffic file, a limits file and a
    BENCHMARK.json entry: the new cell loads; no old file changed."""
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "perfbench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    b = _bench()
    before = {p: p.read_bytes() for p in (root / "perfbench").rglob("*")
              if p.is_file()}
    t = json.loads((BENCH / "traffic" / "mixed-backlog.json").read_text())
    t["prefill_len"] = t["prefill_chunk"] = 128
    t["prompt_len"]["max"] = 128
    (root / "perfbench" / "traffic" / "mixed-short.json").write_text(
        json.dumps(t))
    (root / "perfbench" / "limits" / "yi9b-short.json").write_text(
        (BENCH / "limits" / "yi9b-mixed.json").read_text())
    b["workloads"].append({"name": "yi9b-short", "config": "yi-9b-2to4",
                           "traffic": "mixed-short", "chips": 1, "why": "x"})
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    cell = spec.load_cell("yi9b-short", bench_dir=root / "perfbench")
    assert cell.traffic["prefill_len"] == 128
    assert cell.config["name"] == "yi-9b-2to4"
    assert [m["name"] for m in cell.end_to_end] == ["setup_s"]
    after = {p: p.read_bytes() for p in before}
    assert after == before


def test_unknown_names_fail():
    with pytest.raises(spec.SpecError):
        spec.load_cell("no-such-cell")
    with pytest.raises(spec.SpecError):
        spec.check_name("a/b")
