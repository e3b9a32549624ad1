"""What a cell is, read from ``BENCHMARK.json`` and the files it names.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric sits in a file of its own and is found by its name:

* a configuration: the ``file`` its ``configs`` entry gives (JSON);
* its model: ``bench/models/<model>.py``, where the configuration names
  ``"model": "<model>"`` (``dense`` where it names none): the keys its
  spec carries, the program's ``ArchConfig`` arguments, the reference's
  model-specific parts and the model FLOPs (see ``bench/models/dense.py``);
* a traffic mix: ``bench/traffic/<traffic>.json``;
* a per-layer metric: ``bench/metrics/<name>.py``, a module with
  ``read(run) -> float | None``; ``run`` is ``bench.harness.RunData``.

A reader finds the program's own counts on ``run.spans`` (a traced run's
``bench.spans.SpanSummary``, None on an untraced run): a program counter
is a numeric stat on a ``serving.*`` span, summed over the traced window
by span name through ``run.spans.by_name()``.  So a new cell,
configuration, model or metric, a metric of what a new model counts among
them, is new files and entries, never an edit of the harness.
"""

from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
DEFAULT_MODEL = "dense"


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def load_json(path: Path) -> dict:
    return json.loads(Path(path).read_text())


@dataclass
class Cell:
    name: str
    chips: int
    config: dict            # the configuration file's contents
    model: object           # the configuration's model module
    traffic: dict           # the traffic file's contents
    end_to_end: list        # BENCHMARK.json entries this cell reports
    per_layer: list


def _applies(metric: dict, cell: str, e2e_names: set | None = None) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return e2e_names is None or metric["moves"] in e2e_names


def resolve_cell(name: str, bench: dict | None = None,
                 bench_dir: Path = BENCH_DIR, root: Path = ROOT) -> Cell:
    bench = load_benchmark(root) if bench is None else bench
    try:
        w = next(w for w in bench["workloads"] if w["name"] == name)
    except StopIteration:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json; cells: "
                         + ", ".join(w["name"] for w in bench["workloads"]))
    conf = next(c for c in bench["configs"] if c["name"] == w["config"])
    e2e = [m for m in bench["end_to_end"] if _applies(m, name)]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"] if _applies(m, name, names)]
    config = load_json(root / conf["file"])
    return Cell(name=name, chips=int(w["chips"]), config=config,
                model=model_module(config, bench_dir),
                traffic=load_json(bench_dir / "traffic"
                                  / f"{w['traffic']}.json"),
                end_to_end=e2e, per_layer=per_layer)


def _load(kind: str, name: str, bench_dir: Path):
    """The module ``bench/<kind>/<name>.py``."""
    path = bench_dir / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str, bench_dir: Path = BENCH_DIR):
    """The ``read`` function of ``bench/metrics/<name>.py``."""
    return _load("metrics", name, bench_dir).read


def model_module(config: dict, bench_dir: Path = BENCH_DIR):
    """The model module a configuration names."""
    return _load("models", config.get("model", DEFAULT_MODEL), bench_dir)


def list_cells(bench: dict | None = None) -> list[str]:
    bench = load_benchmark() if bench is None else bench
    return [w["name"] for w in bench["workloads"]]


# -- configuration file → the numbers the model is run with -------------------

# published (Hugging Face config.json) key → ArchConfig field, for the keys
# every configuration has
HF_KEYS = {
    "num_hidden_layers": "n_layers", "hidden_size": "d_model",
    "num_attention_heads": "n_heads", "num_key_value_heads": "n_kv_heads",
    "head_dim": "head_dim", "intermediate_size": "d_ff",
    "vocab_size": "vocab_size", "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps", "tie_word_embeddings": "tie_embeddings",
}
SERVING_KEYS = ("block_size", "confidence_threshold", "mask_token_id",
                "kv_page_size")


def model_spec(config: dict, smoke: bool = False, model=None) -> dict:
    """The numbers the model is run with: the published keys, the serving
    keys, the keys its model module adds (``model``, by default the one the
    configuration names) and the logit gain, with the file's ``smoke``
    overrides applied for a CPU rehearsal."""
    model = model_module(config) if model is None else model
    spec = {k: config[k] for k in (*HF_KEYS, *SERVING_KEYS,
                                   *model.SPEC_KEYS)}
    spec["dtype"] = config["torch_dtype"]
    spec["hidden_act"] = config["hidden_act"]
    spec["logit_gain"] = float(config["logit_gain"])
    if smoke:
        spec.update(config["smoke"])
    return spec
