"""Sharding-rule system, HLO analyzer, and multi-device (subprocess) tests."""

import json
import os
import subprocess
import sys
import textwrap

import pytest

from repro.distributed.sharding import (Rules, long_context_rules,
                                        serving_rules, training_rules)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# rules
# ---------------------------------------------------------------------------

def test_rules_dedup_conflicting_axes():
    r = Rules({"a": "model", "b": "model", "c": ("data", "model")})
    spec = r.spec("a", "b")                  # second use of model → None
    assert spec == type(spec)("model", None)
    spec = r.spec("a", "c")                  # tuple drops used axis
    assert spec[0] == "model" and spec[1] == "data"


def test_training_rules_fsdp():
    r = training_rules(("pod", "data"), "model")
    assert r.table["batch"] == ("pod", "data")
    assert r.table["embed_p"] == ("pod", "data")     # FSDP weights
    assert r.table["heads"] == "model"
    assert r.table["kv_seq"] is None


def test_serving_rules_split_kv():
    r = serving_rules(("data",), "model")
    assert r.table["kv_seq"] == "model"              # split-KV decode
    assert r.table["embed_p"] is None                # no FSDP at serving


def test_long_context_rules_sequence_parallel():
    r = long_context_rules(("data",), "model")
    assert r.table["kv_seq"] == "data"               # batch=1 ⇒ SP over data
    assert r.table["batch"] is None


def test_overrides():
    r = training_rules().with_overrides(heads=None, batch=("data", "model"))
    assert r.table["heads"] is None
    assert r.table["batch"] == ("data", "model")


# ---------------------------------------------------------------------------
# HLO analyzer (trip-count correctness is the roofline's foundation)
# ---------------------------------------------------------------------------

def test_hlo_analyzer_trip_counts():
    from repro.analysis.hlo import analyze
    import jax
    import jax.numpy as jnp

    def f(x, w):
        def body(c, wi):
            return jnp.tanh(c @ wi), None
        y, _ = jax.lax.scan(body, x, w)
        return y

    comp = jax.jit(f).lower(
        jax.ShapeDtypeStruct((64, 64), jnp.float32),
        jax.ShapeDtypeStruct((30, 64, 64), jnp.float32)).compile()
    res = analyze(comp.as_text())
    expected = 2 * 64 * 64 * 64 * 30
    assert abs(res["flops"] - expected) / expected < 0.01
    # xla's own cost analysis undercounts by the trip count
    xla = comp.cost_analysis()["flops"]
    assert res["flops"] > 10 * xla
    # traffic: w is consumed via per-step dynamic-slice → ≈ read once overall
    w_bytes = 30 * 64 * 64 * 4
    assert res["bytes"] < 20 * w_bytes


# ---------------------------------------------------------------------------
# multi-device semantics (subprocess: device count is locked at jax init)
# ---------------------------------------------------------------------------

def _run_subprocess(code: str):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(REPO, "src") + ":" + REPO
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=500)
    assert out.returncode == 0, out.stderr[-2000:]
    return out.stdout


@pytest.mark.slow
def test_sharded_moe_matches_dense_oracle():
    """shard_map + ragged_dot MoE == one-hot dense oracle on an 8-device
    (data×model) mesh, full capacity."""
    out = _run_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P, NamedSharding
        from repro.models.common import ArchConfig
        from repro.models.moe import init_moe, moe_block_dense, moe_block_sharded
        from repro.models.common import KeyGen
        from repro.distributed.sharding import use_rules, training_rules

        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        cfg = ArchConfig(name="m", family="moe", d_model=32, n_experts=8,
                         top_k=2, moe_d_ff=64, capacity_factor=0.0)
        params = init_moe(KeyGen(jax.random.PRNGKey(0)), cfg)
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, 32))
        want = moe_block_dense(params, cfg, x)
        with use_rules(training_rules(), mesh), jax.set_mesh(mesh):
            got = jax.jit(lambda p, x: moe_block_sharded(p, cfg, x))(params, x)
        err = float(jnp.max(jnp.abs(got - want)))
        assert err < 1e-4, err
        print("MOE_OK", err)
    """)
    assert "MOE_OK" in out


@pytest.mark.slow
def test_dryrun_cell_compiles_on_test_mesh():
    """End-to-end dry-run of one train and one decode cell on 8 devices."""
    for arch, shape in (("smollm-135m", "train_4k"),
                        ("llama3.2-1b", "decode_32k")):
        out = subprocess.run(
            [sys.executable, "-m", "repro.launch.dryrun", "--arch", arch,
             "--shape", shape, "--devices", "8", "--out",
             "/tmp/repro_test_dryrun", "--force"],
            capture_output=True, text=True, timeout=500,
            env={**os.environ,
                 "PYTHONPATH": os.path.join(REPO, "src") + ":" + REPO})
        assert out.returncode == 0, out.stdout[-1500:] + out.stderr[-1500:]
        assert "OK" in out.stdout


@pytest.mark.slow
def test_elastic_reshard_checkpoint_roundtrip():
    """Checkpoint saved under one mesh restores under a different mesh
    (elastic re-scaling)."""
    out = _run_subprocess("""
        import jax, jax.numpy as jnp, numpy as np, shutil
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.training import checkpoint as ck

        shutil.rmtree("/tmp/repro_elastic_ck", ignore_errors=True)
        mesh1 = jax.make_mesh((8,), ("data",),
                              axis_types=(jax.sharding.AxisType.Auto,))
        tree = {"w": jax.device_put(
            jnp.arange(64*32, dtype=jnp.float32).reshape(64, 32),
            NamedSharding(mesh1, P("data", None)))}
        ck.save("/tmp/repro_elastic_ck", 7, tree)

        mesh2 = jax.make_mesh((2, 4), ("data", "model"),
                              axis_types=(jax.sharding.AxisType.Auto,) * 2)
        shardings = {"w": NamedSharding(mesh2, P("model", "data"))}
        restored, step = ck.restore("/tmp/repro_elastic_ck", tree,
                                    shardings=shardings)
        assert step == 7
        np.testing.assert_array_equal(np.asarray(restored["w"]),
                                      np.asarray(tree["w"]))
        print("ELASTIC_OK")
    """)
    assert "ELASTIC_OK" in out
