import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# NOTE: the two lines above MUST run before any other import (jax locks the
# device count at first init).  --devices N overrides for fast local testing.
import sys  # noqa: E402

if "--devices" in sys.argv:
    _n = sys.argv[sys.argv.index("--devices") + 1]
    os.environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={_n}"

import argparse    # noqa: E402
import json        # noqa: E402
import re          # noqa: E402
import time        # noqa: E402
import traceback   # noqa: E402

import jax         # noqa: E402

from repro.configs import SHAPES, cells, get_config            # noqa: E402
from repro.core.latency_model import (active_param_count,      # noqa: E402
                                      kv_bytes_per_token,
                                      total_param_count)
from repro.distributed.sharding import use_rules               # noqa: E402
from repro.launch.compile_cache import configure_compile_cache  # noqa: E402
from repro.launch.mesh import make_production_mesh             # noqa: E402
from repro.launch.specs import build_cell                      # noqa: E402

from repro.analysis.hlo import analyze as hlo_analyze          # noqa: E402


def model_flops(arch: str, shape: str, meta: dict) -> float:
    """MODEL_FLOPS = 6·N(_active)·D for training, 2·N·D(+attn) for serving."""
    cfg = get_config(arch)
    spec = SHAPES[shape]
    n_active = active_param_count(cfg)
    if spec.kind == "train":
        tokens = spec.global_batch * spec.seq_len
        if cfg.family == "encdec":
            tokens = spec.global_batch * (spec.seq_len + spec.seq_len // 4)
        return 6.0 * n_active * tokens
    if spec.kind == "prefill":
        tokens = spec.global_batch * spec.seq_len
        return 2.0 * n_active * tokens
    # decode: one chunk step
    c = meta.get("chunk") or 1
    tokens = spec.global_batch * c
    n_attn = sum(1 for i in range(cfg.n_layers) if cfg.is_attn_layer(i))
    attn = 4.0 * n_attn * cfg.n_heads * cfg.hd * spec.seq_len * tokens
    return 2.0 * n_active * tokens + attn


def memory_summary(compiled) -> dict:
    try:
        ma = compiled.memory_analysis()
    except Exception as e:                                     # noqa: BLE001
        return {"error": str(e)}
    out = {}
    for k in ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "generated_code_size_in_bytes",
              "alias_size_in_bytes", "host_argument_size_in_bytes",
              "host_output_size_in_bytes", "host_temp_size_in_bytes"):
        v = getattr(ma, k, None)
        if v is not None:
            out[k] = int(v)
    if not out:
        out["repr"] = str(ma)
    return out


def cost_summary(compiled) -> dict:
    try:
        ca = compiled.cost_analysis()
    except Exception as e:                                     # noqa: BLE001
        return {"error": str(e)}
    return {k: float(v) for k, v in ca.items()
            if isinstance(v, (int, float)) and (
                "flops" in k or "bytes" in k or "utilization" in k.lower())}


# ---------------------------------------------------------------------------

def run_cell(arch: str, shape: str, mesh, mesh_name: str, out_dir: str,
             force: bool = False, rule_overrides=None, cfg_overrides=None,
             chunk=None, tag_suffix: str = "") -> dict:
    tag = f"{mesh_name}/{arch}__{shape}{tag_suffix}"
    path = os.path.join(out_dir, mesh_name,
                        f"{arch}__{shape}{tag_suffix}.json")
    if os.path.exists(path) and not force:
        with open(path) as f:
            return json.load(f)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    rec = {"arch": arch, "shape": shape, "mesh": mesh_name,
           "devices": int(mesh.devices.size), "status": "error",
           "rule_overrides": rule_overrides, "cfg_overrides": cfg_overrides,
           "chunk_override": chunk}
    t0 = time.time()
    try:
        cell = build_cell(arch, shape, mesh, rule_overrides=rule_overrides,
                          cfg_overrides=cfg_overrides, chunk=chunk)
        rec["meta"] = cell.meta
        with use_rules(cell.rules, mesh), jax.set_mesh(mesh):
            jitted = jax.jit(cell.fn, in_shardings=cell.in_shardings,
                             donate_argnums=cell.donate_argnums)
            lowered = jitted.lower(*cell.args)
            t_lower = time.time()
            compiled = lowered.compile()
            t_compile = time.time()
            rec["lower_s"] = round(t_lower - t0, 2)
            rec["compile_s"] = round(t_compile - t_lower, 2)
            rec["memory"] = memory_summary(compiled)
            rec["cost"] = cost_summary(compiled)
            hlo = compiled.as_text()
            rec["hlo_lines"] = hlo.count("\n")
            import gzip
            with gzip.open(path.replace(".json", ".hlo.gz"), "wt") as hf:
                hf.write(hlo)
            # trip-count-aware per-device accounting (see hlo_analysis.py)
            rec["hlo_analysis"] = hlo_analyze(hlo)
            rec["collectives"] = rec["hlo_analysis"]["collectives"]
            rec["model_flops"] = model_flops(arch, shape, cell.meta)
            rec["status"] = "ok"
            n_dev = int(mesh.devices.size)
            hf = rec["hlo_analysis"]["flops"] * n_dev
            print(f"[{tag}] OK lower={rec['lower_s']}s "
                  f"compile={rec['compile_s']}s "
                  f"temp={rec['memory'].get('temp_size_in_bytes', 0)/2**30:.2f}GiB")
            print(f"[{tag}] memory_analysis: {rec['memory']}")
            print(f"[{tag}] hlo(per-dev): flops={rec['hlo_analysis']['flops']:.3e} "
                  f"bytes={rec['hlo_analysis']['bytes']:.3e} "
                  f"model/hlo_flops={rec['model_flops']/max(hf,1):.3f}")
    except Exception as e:                                     # noqa: BLE001
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
        print(f"[{tag}] FAIL: {rec['error']}")
    rec["total_s"] = round(time.time() - t0, 2)
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)
    return rec


def main():
    ap = argparse.ArgumentParser(description="multi-pod dry-run")
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true", help="every runnable cell")
    ap.add_argument("--devices", type=int, default=512,
                    help="placeholder host device count (testing)")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--rules-override", default=None,
                    help='JSON, e.g. {"heads": null, "batch": ["data","model"]}')
    ap.add_argument("--cfg-override", default=None,
                    help='JSON ArchConfig field overrides (perf sweeps)')
    ap.add_argument("--chunk", type=int, default=None,
                    help="decode chunk size override")
    ap.add_argument("--tag", default="", help="suffix for output filename")
    args = ap.parse_args()
    configure_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 5)
    rule_overrides = json.loads(args.rules_override) \
        if args.rules_override else None
    cfg_overrides = json.loads(args.cfg_override) if args.cfg_override \
        else None

    n_dev = len(jax.devices())
    meshes = []
    for mp in ([False, True] if args.both_meshes
               else [args.multi_pod]):
        want = 512 if mp else 256
        if n_dev >= want:
            mesh = make_production_mesh(multi_pod=mp)
        else:  # reduced test topology
            import numpy as np
            if mp:
                shape = (2, n_dev // 4, 2)
                axes = ("pod", "data", "model")
            else:
                shape = (n_dev // 2, 2)
                axes = ("data", "model")
            mesh = jax.make_mesh(shape, axes,
                                 axis_types=(jax.sharding.AxisType.Auto,)
                                 * len(axes))
        meshes.append(("multipod_2x16x16" if mp else "pod_16x16", mesh))

    if args.all:
        todo = [(a, s) for a, s, _ in cells()]
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        todo = [(args.arch, args.shape)]

    failures = 0
    for mesh_name, mesh in meshes:
        for arch, shape in todo:
            rec = run_cell(arch, shape, mesh, mesh_name, args.out,
                           force=args.force, rule_overrides=rule_overrides,
                           cfg_overrides=cfg_overrides, chunk=args.chunk,
                           tag_suffix=args.tag)
            failures += rec["status"] != "ok"
    print(f"dry-run complete: {len(todo) * len(meshes) - failures} ok, "
          f"{failures} failed")
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
