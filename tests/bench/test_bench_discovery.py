"""A cell, a configuration, a traffic mix and a per-layer metric are
files found by their names: adding one edits no file of the harness."""

import json
import shutil

from bench import spec as S
from bench.harness import RunData


def test_new_files_are_found_by_name(tmp_path):
    bench_dir = tmp_path / "bench"
    shutil.copytree(S.BENCH_DIR, bench_dir)
    cfg = S.load_json(bench_dir / "configs" / "sdar-8b-16l.json")
    (bench_dir / "configs" / "other.json").write_text(
        json.dumps(dict(cfg, max_batch=8)))
    (bench_dir / "traffic" / "tiny-mix.json").write_text(json.dumps(
        dict(S.load_json(bench_dir / "traffic" / "sharegpt-sdar8b.json"),
             arrivals={"kind": "poisson", "rate": 0.5})))
    (bench_dir / "metrics" / "answer.py").write_text(
        "def read(run):\n    return float(len(run.ticks))\n")
    bench = S.load_benchmark()
    bench["configs"].append({"name": "other", "source": "x",
                             "file": "bench/configs/other.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "other.tiny", "config": "other",
                               "traffic": "tiny-mix", "chips": 1,
                               "why": "x"})
    bench["per_layer"].append({"name": "answer", "unit": "tokens",
                               "better": "higher", "source": "host_clock",
                               "layer": "engine", "moves": "tokens_per_s",
                               "workloads": ["other.tiny"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    fresh = S.load_benchmark(tmp_path)
    assert "other.tiny" in S.list_cells(fresh)
    cell = S.resolve_cell("other.tiny", fresh, bench_dir, tmp_path)
    assert cell.config["max_batch"] == 8
    assert cell.traffic["arrivals"]["rate"] == 0.5
    assert [m["name"] for m in cell.per_layer] == ["answer"]
    assert {m["name"] for m in cell.end_to_end} == {"tokens_per_s",
                                                   "setup_s"}
    read = S.metric_reader("answer", bench_dir)
    assert read(RunData({}, None, [], [1, 2, 3], None)) == 3.0
