"""A cell, a configuration, a traffic mix and a per-layer metric are
files found by their names: adding one edits no file of the harness,
also where the metric reads a counter the program puts on its own
spans."""

import json
import os
import shutil
import subprocess
import sys

from bench import harness as H
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


# two readers of the stats the program puts on its launch spans, the
# prefill's and the decode's: name -> (stat, unit)
SPAN_READERS = {
    "launch_rows": ("rows", "rows"),
    "launch_count": ("count", "launches"),
}
SPAN_READER = '''"""Per-layer metric `{name}`: `{stat}` summed over the traced
window's `serving.backend.prefill.launch` and `.decode.launch` spans."""


def read(run):
    if run.spans is None:
        return None
    spans = run.spans.by_name()
    launches = [spans[n] for n in ("serving.backend.prefill.launch",
                                   "serving.backend.decode.launch")
                if n in spans]
    return sum(s["{stat}"] for s in launches) if launches else None
'''


def test_span_counter_metric_is_one_new_file(tmp_path):
    """A metric that sums a stat of the program's ``serving.*`` spans is
    one file in a copied ``bench/`` and one entry in ``BENCHMARK.json``: a
    traced run of the copy, on the CPU, reports it."""
    shutil.copy(S.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(S.BENCH_DIR, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    cell = "sdar8b-sharegpt"
    bench = S.load_benchmark(tmp_path)
    for name, (stat, unit) in SPAN_READERS.items():
        (tmp_path / "bench" / "metrics" / f"{name}.py").write_text(
            SPAN_READER.format(name=name, stat=stat))
        bench["per_layer"].append({
            "name": name, "unit": unit, "better": "higher",
            "source": "program_counter", "layer": "model step",
            "moves": "ttft_p50_ms", "workloads": [cell]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    # the rehearsal warms up no shape: an untraced run of the same seed
    # first fills a compilation cache, so that no compile in the traced
    # run outlasts the traced seconds and leaves them without a launch
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(S.ROOT / "src"),
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    for trace in ("0", "1"):
        out = subprocess.run(
            [sys.executable, "bench/run.py", "--workload", cell, "--seed",
             str(2**31 + 23), "--seconds", "12", "--trace", trace,
             "--rehearse"],
            cwd=tmp_path, capture_output=True, text=True, env=env,
            timeout=600)
        assert out.returncode == 0, out.stderr[-2000:]
    line = next(ln for ln in out.stdout.splitlines()
                if ln.startswith("per-layer: "))
    readings = {k: float(v) for k, v in (
        kv.rsplit(" ", 1) for kv in line[len("per-layer: "):].split(", "))}
    rows, count = (readings[n] for n in SPAN_READERS)
    assert count >= 1 and rows == int(rows)
    assert count <= rows <= count * H.REHEARSAL["max_batch"]
