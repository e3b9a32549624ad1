"""CPU rehearsal of a cell at its configuration's smoke size, and the
check seeing each fault the served path can have.

``run.py`` refuses to run without a TPU and prints no result; with
``--rehearse`` it drives the whole run on the CPU (Pallas in interpret
mode) and prints ``correct: false`` and no device metric.  In process,
the same run with the served path broken underneath must fail its
check: a token altered where it is produced, a step that commits every
open position whatever its confidence, a decode step that leaves the page
pool unchanged, a prefill that writes nothing."""

import json
import os
import shutil
import subprocess
import sys
import time

import pytest

from bench import harness as H
from bench import spec as S

CELL = "sdar8b-sharegpt"
# one cell of each configuration, to rehearse its smoke block
_B = S.load_benchmark()
CONFIG_CELLS = [next(w["name"] for w in _B["workloads"]
                     if w["config"] == c["name"]) for c in _B["configs"]]


def _run(args, cwd=S.ROOT):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "bench/run.py", *args], cwd=cwd,
                          capture_output=True, text=True, env=env,
                          timeout=600)


def test_refuses_without_a_tpu():
    out = _run(["--workload", CELL, "--seed", "1", "--seconds", "3",
                "--trace", "0"])
    assert out.returncode != 0
    assert "needs a TPU" in out.stderr
    assert '"correct"' not in out.stdout


def test_refuses_in_a_directory_of_only_the_benchmark(tmp_path):
    shutil.copy(S.ROOT / "BENCHMARK.json", tmp_path)
    for p in S.load_benchmark()["paths"]:
        shutil.copytree(S.ROOT / p, tmp_path / p)
    out = _run(["--workload", CELL, "--seed", "1", "--seconds", "3",
                "--trace", "0", "--rehearse"], cwd=tmp_path)
    assert out.returncode != 0
    assert '"correct"' not in out.stdout


@pytest.mark.parametrize("cell", CONFIG_CELLS)
def test_rehearsal_prints_correct_false_and_no_device_metric(cell):
    out = _run(["--workload", cell, "--seed", str(2**31 + 11), "--seconds",
                "3", "--trace", "0", "--rehearse"])
    assert out.returncode == 0, out.stderr[-2000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["correct"] is False and res["metrics"] == {}
    assert res["device"]["platform"] == "cpu"
    assert "memory_peak_bytes" not in res["device"]
    assert list(res)[-1] == "checks"
    assert res["checks_pass"] is True
    assert out.stderr.strip().splitlines()[-1].startswith(
        "check uncompared_tokens")


def _alter_tokens(backend):
    f = backend._decode_paged

    def g(*a, **k):
        conf, tok, pages = f(*a, **k)
        return conf, (tok + 1) % backend.cfg.vocab_size, pages
    backend._decode_paged = g


def _commit_everything(backend):
    f = backend._decode_paged

    def g(*a, **k):
        conf, tok, pages = f(*a, **k)
        return conf * 0 + 1, tok, pages   # every open position passes
    backend._decode_paged = g


def _pool_unchanged_by_decode(backend):
    f = backend._decode_paged

    def g(*a, **k):
        a = list(a)
        a[7] = a[7] * 0                 # n_adv: nothing freezes
        return f(*a, **k)
    backend._decode_paged = g


def _prefill_writes_nothing(backend):
    f = backend._prefill_chunk

    def g(*a, **k):
        a = list(a)
        a[4] = a[4] * 0                 # valid: no prompt KV lands
        return f(*a, **k)
    backend._prefill_chunk = g


@pytest.mark.parametrize("fault", [None, _alter_tokens, _commit_everything,
                                   _pool_unchanged_by_decode,
                                   _prefill_writes_nothing])
@pytest.mark.parametrize("name", S.list_cells())
def test_check_sees_each_fault(name, fault):
    cell = S.resolve_cell(name)
    lines = []
    res = H.run_cell(cell, 7, 3.0, False, t_start=time.perf_counter(),
                     rehearse=True, on_backend=fault, log=lines.append)
    assert res["correct"] is False                   # never on a CPU
    assert res["checks"]["uncompared_tokens"]["value"] == 0
    assert res["checks_pass"] is (fault is None), (res["checks"], lines)
