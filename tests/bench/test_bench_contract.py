"""BENCHMARK.json and the files it names keep to the benchmark's
contract: names, units, bounds, one reader per per-layer metric, every
configuration, its model module, serving options, traffic mix and cell
present and used."""

import re

from bench import harness as H
from bench import spec as S

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
B = S.load_benchmark()


def test_top_level_keys_and_command():
    assert set(B) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert B["command"][1] == "bench/run.py"
    assert (S.ROOT / B["command"][1]).is_file()
    assert isinstance(B["run_seconds"], int) and 1 <= B["run_seconds"] <= 51


def test_configs_cells_and_traffic_exist_and_are_used():
    used = {w["config"] for w in B["workloads"]}
    assert used == {c["name"] for c in B["configs"]}
    for c in B["configs"]:
        assert NAME.match(c["name"])
        cfg = S.load_json(S.ROOT / c["file"])
        assert cfg["reduced"] == c["reduced"]
        for k in c["reduced"]:
            assert NAME.match(k)
        model = S.model_module(cfg)
        for attr in ("SPEC_KEYS", "arch_kwargs", "init_weights", "prompt_kv",
                     "window_step", "decode_flops", "prefill_flops"):
            assert hasattr(model, attr), (c["name"], attr)
        S.model_spec(cfg, model=model)
        H.serving_kw(cfg)
    seen = set()
    for w in B["workloads"]:
        assert NAME.match(w["name"]) and w["chips"] in (1, 4)
        assert (w["config"], w["traffic"]) not in seen
        seen.add((w["config"], w["traffic"]))
        assert (S.BENCH_DIR / "traffic" / f"{w['traffic']}.json").is_file()
        assert len(w["why"]) <= 200


def test_metrics_have_units_readers_and_cells():
    cells = {w["name"] for w in B["workloads"]}
    e2e = {m["name"]: m for m in B["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in B["end_to_end"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
        assert set(m.get("workloads", cells)) <= cells
    for m in B["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["moves"] in e2e
        assert (S.BENCH_DIR / "metrics" / f"{m['name']}.py").is_file()
        S.metric_reader(m["name"])
        for c in m["workloads"]:
            assert c in set(e2e[m["moves"]].get("workloads", cells))
    for c in cells:
        cell = S.resolve_cell(c, B)
        assert len(cell.end_to_end) >= 2 and cell.per_layer
