"""A configuration's model module and serving options.

* ``sdar-8b-16l`` resolves through ``bench/models/dense.py`` to what the
  harness ran before model modules existed: the same spec, ``ArchConfig``
  arguments, serving arguments, model FLOPs and kernel work on a fixed
  tick (``data/sdar-8b-16l-resolution.json``, recorded from that harness).
* A configuration that names a new module, written into a copy of
  ``bench/`` beside a copied harness, is served, checked and counted
  through it: ``run_cell``, ``check``, ``tick_flops``, ``sweep.py`` and
  ``calibrate.py`` take it with no edit of an existing file.  The module
  is a routed-expert model, so the seam carries ``ArchConfig`` fields the
  dense module never sets.
* The ``serving`` object is a closed list of ``ModelBackend`` options.
"""

import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

from bench import flops as F
from bench import harness as H
from bench import spec as S

PINNED = S.load_json(Path(__file__).parent / "data"
                     / "sdar-8b-16l-resolution.json")
SDAR8B = S.load_json(S.ROOT / "bench/configs/sdar-8b-16l.json")


def _fixed_tick():
    tk = H.Tick(0.0)
    tk.plan = [(7, 0, 367), (9, 352, 160)]
    tk.dec = ([1, 2, 3], np.full((3, 32), 3, np.int64),
              np.array([513, 1999, 40]), np.array([32, 17, 5]),
              np.array([3, 0, 1]))
    return tk


@pytest.mark.parametrize("size", ["full", "smoke"])
def test_sdar8b_resolves_as_before_model_modules(size):
    model = S.model_module(SDAR8B)
    assert Path(model.__file__) == S.BENCH_DIR / "models" / "dense.py"
    spec = S.model_spec(SDAR8B, smoke=size == "smoke")
    assert spec == PINNED[size]["model_spec"]
    assert model.arch_kwargs(spec) == PINNED[size]["arch_kwargs"]


def test_sdar8b_serving_flops_and_kernel_work_as_before():
    assert H.serving_kw(SDAR8B) == PINNED["serving_kw"]
    model = S.model_module(SDAR8B)
    spec = S.model_spec(SDAR8B)
    assert H.tick_flops(model, spec, _fixed_tick()) == PINNED["tick_flops"]
    assert list(H.attn_work(spec, _fixed_tick(), F.peak("TPU v5 lite"))) \
        == PINNED["attn_work"]


def test_unknown_serving_option_is_an_error_naming_the_allowed():
    cfg = dict(SDAR8B, serving={"prefill_token_budget": 512,
                                "prefill_budget": 512})
    with pytest.raises(ValueError, match="prefill_budget.*allowed: "
                                         "prefill_token_budget"):
        H.serving_kw(cfg)


def test_prefill_token_budget_reaches_the_backend_and_the_warm_up():
    """A fixed budget of 512 prompt tokens a tick reaches ``ModelBackend``
    through ``model_serving``, and ``warm`` plans its prefill shapes by
    that budget, not by the adaptive one."""
    from repro.serving.request import Request
    cfg = dict(SDAR8B, serving={"prefill_token_budget": 512})
    model = S.model_module(cfg)
    spec = S.model_spec(cfg, smoke=True, model=model)
    span = Request(-1, 0.0, 2048, 1024, prompt_tokens=[])
    _, backend, sched = H.serve(cfg, model, spec, 1, [span], "elastic",
                                "sharegpt", 1)
    pf = backend._prefill
    assert pf.fixed and pf.budget == 512 and pf.target_bc is None
    assert pf.tick_budget(live_bc=10_000) == 512
    runs = H.warm_runs(backend, 8, sched.candidates,
                       {"prompt": {"min": 8, "max": 2048}})
    prefill = [(B, n) for kind, B, n in runs if kind == "prefill"]
    assert prefill == H.prefill_shapes(512, pf.align, 8, 8, 2048)
    assert max(n for _, n in prefill) == 512


# -- a new model module in a copied bench/ ------------------------------------

TOY_MOE = '''"""Model module `toy_moe`: the dense model with a routed-expert
feed-forward layer in every layer (top-k of E SwiGLU experts, gates
renormalised over the k chosen, no shared expert)."""

import functools

import numpy as np

from bench import reference as R
from bench.models import dense

SPEC_KEYS = ("num_experts", "num_experts_per_tok", "moe_intermediate_size")
CALLS = []


def arch_kwargs(spec):
    kw = dense.arch_kwargs(spec)
    kw.update(family="moe", n_experts=spec["num_experts"],
              top_k=spec["num_experts_per_tok"],
              moe_d_ff=spec["moe_intermediate_size"])
    return kw


def ffn_weights(spec, key, dt):
    d, E, f = (spec["hidden_size"], spec["num_experts"],
               spec["moe_intermediate_size"])
    out = []
    for shape, fan in (((d, E), d), ((E, d, f), d), ((E, d, f), d),
                       ((E, f, d), f)):
        key, sub = R.split(key)
        out.append(R.dense_init(sub, shape, dt, fan))
    return key, dict(zip(("router", "e_gate", "e_up", "e_down"), out))


def ffn(spec, lw, x, quant):
    jax, jnp = R.jax_modules()
    hi = jax.lax.Precision.HIGHEST
    h = R.rms(x, spec["rms_norm_eps"])
    probs = jax.nn.softmax(R.dot(h, R.mat(lw["router"], quant)), -1)
    gates, ids = jax.lax.top_k(probs, spec["num_experts_per_tok"])
    gates = gates / gates.sum(-1, keepdims=True)
    g = jnp.einsum("...d,edf->...ef", h, R.mat(lw["e_gate"], quant),
                   precision=hi)
    u = jnp.einsum("...d,edf->...ef", h, R.mat(lw["e_up"], quant),
                   precision=hi)
    o = jnp.einsum("...ef,efd->...ed", jax.nn.silu(g) * u,
                   R.mat(lw["e_down"], quant), precision=hi)
    combine = (jax.nn.one_hot(ids, spec["num_experts"])
               * gates[..., None]).sum(-2)
    return (o * combine[..., None]).sum(-2)


def init_weights(spec, seed):
    CALLS.append("init_weights")
    return dense.init_weights(spec, seed, ffn_weights=ffn_weights)


prompt_kv = functools.partial(dense.prompt_kv, ffn=ffn)
window_step = functools.partial(dense.window_step, ffn=ffn)


def _expert_flops(spec, tokens):
    d, E, k, f, L = (spec["hidden_size"], spec["num_experts"],
                     spec["num_experts_per_tok"],
                     spec["moe_intermediate_size"],
                     spec["num_hidden_layers"])
    return 2.0 * L * (d * E + 3 * k * d * f) * tokens


def decode_flops(spec, ctx, valid):
    CALLS.append("decode_flops")
    return dense.decode_flops(dict(spec, intermediate_size=0), ctx, valid) \\
        + _expert_flops(spec, float(np.sum(valid)))


def prefill_flops(spec, plan):
    return dense.prefill_flops(dict(spec, intermediate_size=0), plan) \\
        + _expert_flops(spec, float(sum(n for _, n in plan)))
'''


@pytest.fixture(scope="module")
def toy(tmp_path_factory):
    """A checkout root holding a copy of ``bench/`` and ``BENCHMARK.json``
    plus one new model module, configuration and cell."""
    root = tmp_path_factory.mktemp("toy")
    shutil.copytree(S.BENCH_DIR, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (root / "bench" / "models" / "toy_moe.py").write_text(TOY_MOE)
    cfg = dict(SDAR8B, name="toy", model="toy_moe", num_experts=128,
               num_experts_per_tok=8, moe_intermediate_size=768,
               smoke=dict(SDAR8B["smoke"], num_experts=4,
                          num_experts_per_tok=2, moe_intermediate_size=32))
    (root / "bench" / "configs" / "toy.json").write_text(json.dumps(cfg))
    bench = S.load_benchmark()
    bench["configs"].append({"name": "toy", "source": "x",
                             "file": "bench/configs/toy.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "toy.chat", "config": "toy",
                               "traffic": "sharegpt-sdar8b", "chips": 1,
                               "why": "x"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def _toy_cell(root):
    return S.resolve_cell("toy.chat", S.load_benchmark(root),
                          root / "bench", root)


def test_new_model_module_is_found_and_builds_its_model(toy):
    from repro.launch.serve import init_model
    from repro.models.common import ArchConfig
    cell = _toy_cell(toy)
    assert Path(cell.model.__file__) == toy / "bench/models/toy_moe.py"
    spec = S.model_spec(cell.config, smoke=True, model=cell.model)
    assert (spec["num_experts"], spec["num_experts_per_tok"],
            spec["moe_intermediate_size"]) == (4, 2, 32)
    kw = cell.model.arch_kwargs(spec)
    assert (kw["family"], kw["n_experts"], kw["top_k"], kw["moe_d_ff"]) \
        == ("moe", 4, 2, 32)
    lm, params = init_model(ArchConfig(**kw), 3)
    assert lm.cfg.family == "moe"
    ffn = params["blocks"]["pos0"]["ffn"]
    assert ffn["router"].shape == (2, 64, 4)
    assert ffn["w_gate"].shape == (2, 4, 64, 32)
    # the reference draws the program's weights through the module's hook
    w = cell.model.init_weights(spec, 3)
    for prog, ref in (("router", "router"), ("w_gate", "e_gate"),
                      ("w_up", "e_up"), ("w_down", "e_down")):
        np.testing.assert_array_equal(np.asarray(ffn[prog]),
                                      np.asarray(w[ref]))
    # tick_flops counts by the module: the dense count of the same spec
    # plus the experts' part, minus the dense MLP's
    tk = _fixed_tick()
    dense = S.model_module({})
    full = S.model_spec(cell.config, model=cell.model)
    d, f, L = full["hidden_size"], full["intermediate_size"], 16
    tokens = 367 + 160 + 32 + 17 + 5
    want = H.tick_flops(dense, full, tk) + 2.0 * L * tokens * (
        d * 128 + 3 * 8 * d * 768 - 3 * d * f)
    assert H.tick_flops(cell.model, full, tk) == pytest.approx(want,
                                                               rel=1e-12)


def test_new_model_module_runs_through_run_cell_and_check(toy):
    cell = _toy_cell(toy)
    res = H.run_cell(cell, 2**31 + 5, 3.0, False,
                     t_start=time.perf_counter(), rehearse=True,
                     log=lambda s: None)
    assert res["checks_pass"] is True, res["checks"]
    assert res["checks"]["uncompared_tokens"]["value"] == 0
    assert "init_weights" in cell.model.CALLS     # the check's reference
    # step_mfu's FLOP count reads the module on the run's ticks
    calls = len(cell.model.CALLS)
    H.tick_flops(cell.model, S.model_spec(cell.config, smoke=True,
                                          model=cell.model), _fixed_tick())
    assert cell.model.CALLS[calls:] == ["decode_flops"]


def _script(root, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(S.ROOT / "src"))
    return subprocess.run([sys.executable, *args], cwd=root,
                          capture_output=True, text=True, env=env,
                          timeout=600)


def test_new_model_module_runs_through_sweep(toy):
    out = _script(toy, "bench/sweep.py", "--workload", "toy.chat",
                  "--rates", "2", "--seconds", "2", "--smoke")
    assert out.returncode == 0, out.stderr[-2000:]
    summary = json.loads(out.stdout.strip().splitlines()[-1])
    assert summary["workload"] == "toy.chat"
    assert [r["rate"] for r in summary["rows"]] == [2.0]


def test_new_model_module_runs_through_calibrate(toy):
    out = _script(toy, "bench/calibrate.py", "--config",
                  "bench/configs/toy.json", "--smoke")
    assert out.returncode == 0, out.stderr[-2000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["logit_gain"] > 0 and res["tokens_per_step"] > 1
