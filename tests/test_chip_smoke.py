"""CPU rehearsal of ``chip_smoke.py``: its phases at SMOKE size, Pallas in
interpret mode.  Only these tests bypass the script's TPU check (they call
its phase functions, not ``main``); run as a program on a host without a
TPU, the script must fail and print no result."""

import importlib.util
import os
import shutil
import subprocess
import sys
import textwrap
from pathlib import Path

from repro.configs import get_smoke_config

REPO = Path(__file__).resolve().parents[1]
SCRIPT = REPO / "chip_smoke.py"


def _load_script():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _run_script(cwd, extra_env=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(extra_env or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "chip_smoke.py"], cwd=cwd,
                          capture_output=True, text=True, env=env,
                          timeout=300)


def test_script_fails_without_a_tpu():
    out = _run_script(REPO)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "needs a TPU" in out.stderr


def test_script_fails_outside_the_repo(tmp_path):
    shutil.copy(SCRIPT, tmp_path / "chip_smoke.py")
    out = _run_script(tmp_path)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


def test_one_chip_phases_at_smoke_size():
    smoke = _load_script()
    cfg = get_smoke_config("sdar-8b")
    lines = []
    res = smoke.one_chip(cfg, n_requests=4, max_prompt=64, max_output=32,
                         log=lines.append)
    reqs = smoke.make_requests(4, 0, 64, 32)
    assert res["tokens"] == sum(r.max_new_tokens for r in reqs)
    assert {r: len(t) for r, t in res["outputs"].items()} == \
        {r.rid: r.max_new_tokens for r in reqs}
    assert res["decode_steps"] > 0
    # interpret mode on the CPU: the kernel is not a TPU custom call here
    assert res["kernel_in_hlo"] is False
    assert set(res["kernel_errs"]) == set(smoke.CHECK_SHAPES)
    for errs in res["kernel_errs"].values():
        assert max(errs.values()) < 1e-4
    assert any(line.startswith("serve: 4/4 requests finished")
               for line in lines)


def test_split_kv_phase_on_four_virtual_devices():
    code = textwrap.dedent("""
        import importlib.util
        spec = importlib.util.spec_from_file_location("cs", %r)
        cs = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(cs)
        from repro.configs import get_smoke_config
        res = cs.split_kv(get_smoke_config("sdar-8b"), kv_shards=4,
                          n_requests=3, max_prompt=48, max_output=16,
                          kv_pages=64)
        want = {r.rid: r.max_new_tokens for r in cs.make_requests(3, 0, 48, 16)}
        runs = [res["served"], res["outputs"][4], res["outputs"][1]]
        got = [{r: len(t) for r, t in out.items()} for out in runs]
        print("all finished:", got == [want] * 3)
        errs = [max(e.values()) for e in res["kernel_errs"].values()]
        print("split-KV kernel errors:", len(errs), max(errs) < 1e-4)
    """ % str(SCRIPT))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(REPO / "src"))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=400)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "committed tokens identical at kv_shards=4 and 1 (float32): " \
        "True" in out.stdout
    assert "all finished: True" in out.stdout
    assert "split-KV kernel errors: 2 True" in out.stdout
