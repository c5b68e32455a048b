"""A new configuration, traffic mix and per-layer metric are found by
name as new files plus new BENCHMARK.json entries, with no edit to any
file the benchmark already has."""
import hashlib
import json

import bench_fixture
import pytest

from bench.lib import spec


def _digest(root):
    return {p.relative_to(root).as_posix(): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted((root / "bench").rglob("*")) if p.is_file()}


def test_new_files_are_found_by_name(tmp_path):
    root = bench_fixture.make_root(tmp_path)
    before = _digest(root)
    bench = root / "bench"
    cfg = json.loads((bench / "configs" / "tiny-dense.json").read_text())
    cfg["name"] = "tiny-dense-wide"
    cfg["d_ff"] = 192
    (bench / "configs" / "tiny-dense-wide.json").write_text(json.dumps(cfg))
    mix = {"kind": "open", "shape_seed": 9, "rate_qps": 3.0,
           "prompt": {"median": 40, "sigma": 0.3, "min": 8, "max": 64},
           "output": {"median": 4, "sigma": 0.3, "min": 2, "max": 8}}
    (bench / "traffic" / "long_only.json").write_text(json.dumps(mix))
    (bench / "metrics" / "requests_done.new.py").write_text(
        "def read(ctx):\n"
        "    return float(sum(s.status == 'completed' for s in ctx.served))\n")
    bench_fixture.add_entry(root, "configs", {
        "name": "tiny-dense-wide", "source": "https://example.org/model",
        "file": "bench/configs/tiny-dense-wide.json", "reduced": [],
        "why": "test"})
    bench_fixture.add_entry(root, "workloads", {
        "name": "tiny-dense-wide.long_only", "config": "tiny-dense-wide",
        "traffic": "long_only", "chips": 1, "why": "test"})
    bench_fixture.add_entry(root, "per_layer", {
        "name": "requests_done.new", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "runtime and admission",
        "moves": "ttft_p90_ms",
        "workloads": ["tiny-dense-wide.long_only"]})
    bench_fixture.add_entry(root, "end_to_end", {
        "name": "ttft_p90_ms", "unit": "ms", "better": "lower",
        "bound": 0.25, "source": "host_clock",
        "workloads": ["tiny-dense-wide.long_only"]})

    cell = spec.load_cell("tiny-dense-wide.long_only", root)
    assert cell.config["d_ff"] == 192
    assert cell.traffic["shape_seed"] == 9 and cell.traffic["name"] == "long_only"
    names = [m["name"] for m in cell.per_layer]
    assert names == ["requests_done.new"]
    reader = spec.load_module(cell.bench_dir, "metrics", "requests_done.new")
    ctx = type("C", (), {"served": [type("S", (), {"status": "completed"})()] * 3})
    assert reader.read(ctx) == 3.0
    # every file the benchmark had is byte-for-byte unchanged
    after = _digest(root)
    assert {k: after[k] for k in before} == before


def test_metric_without_workloads_follows_its_end_to_end_metric():
    bench = {"end_to_end": [{"name": "a"}, {"name": "b", "workloads": ["x"]}],
             "per_layer": [{"name": "m1", "moves": "a"},
                           {"name": "m2", "moves": "b"},
                           {"name": "m3", "moves": "b", "workloads": ["y"]}]}
    got = spec.metrics_of(bench, "x")
    assert [m["name"] for m in got["per_layer"]] == ["m1", "m2"]
    got = spec.metrics_of(bench, "y")
    assert [m["name"] for m in got["per_layer"]] == ["m1", "m3"]


def test_unknown_names_are_errors(tmp_path):
    root = bench_fixture.make_root(tmp_path)
    with pytest.raises(KeyError):
        spec.load_cell("no-such.cell", root)
    with pytest.raises(FileNotFoundError):
        spec.load_module(root / "bench", "metrics", "no_such_metric")
