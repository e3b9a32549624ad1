"""Serving launcher.

    PYTHONPATH=src python -m repro.launch.serve --arch sdar-8b \
        --mode elastic --dataset sharegpt --rate 2.0 --requests 100

``--backend sim`` (default) runs the virtual-clock simulation calibrated to
the chosen device; ``--backend model`` serves the real model through the
same engine on the local device: the arch's published widths with random
seeded weights, ``--layers N`` cutting depth to what one chip's HBM holds
(SDAR-8B on one v5e: ``--layers 16``), or ``--smoke`` for the arch's
reduced CPU config.  The page pool takes the HBM left after the weights.
"""

from __future__ import annotations

import argparse

import jax

from repro.configs import get_config, get_smoke_config
from repro.core.latency_model import DEVICES, AnalyticDeviceModel, device_spec
from repro.core.scheduler import ElasticScheduler, scheduler_for_mode
from repro.launch.compile_cache import configure_compile_cache
from repro.models.registry import build_model
from repro.serving import (DATASETS, ModelBackend, ServingEngine,
                           SimBackend, Tracer, chunk_distribution,
                           make_trace)
from repro.serving.backends import hbm_pool_pages


def make_scheduler(mode: str, backend, profile):
    return scheduler_for_mode(
        mode, backend.analytic if backend is not None else None,
        prior_tokens_per_step=profile.tokens_per_step_bd32)


def run_single_replica_faults(args, profile):
    """``--faults`` on the sim backend: serve the same workload through a
    one-replica cluster engine (the fault timeline lives there)."""
    from repro.cluster import build_sim_cluster
    from repro.common.faults import FaultPlan

    wl_kw = {"share_ratio": args.share_ratio} \
        if args.workload == "shared" else {}
    cluster = build_sim_cluster(
        get_config(args.arch), profile, 1, "rr",
        device=DEVICES[args.device], mode=args.mode,
        kv_pages=args.kv_pages or 1 << 16, max_batch=args.max_batch,
        seed=args.seed, kv_admission=args.kv_admission,
        prefill_mode=args.prefill_mode,
        prefill_token_budget=args.prefill_budget, kv_shards=args.kv_shards,
        prefix_cache=not args.no_prefix_cache,
        host_kv_pages=args.host_kv_pages,
        fault_plan=FaultPlan.parse(args.faults))
    wl = make_trace(profile, args.workload, args.rate, args.requests,
                    seed=args.seed, **wl_kw)
    rep = cluster.run(list(wl))
    print(f"requests: {len(rep.metrics)}")
    print(f"decode throughput: {rep.throughput:.1f} tok/s")
    print(f"P50/P90/P99 TPOT: {rep.tpot_percentile(50)*1e3:.1f} / "
          f"{rep.tpot_percentile(90)*1e3:.1f} / "
          f"{rep.tpot_percentile(99)*1e3:.1f} ms")
    kinds = {}
    for f in rep.faults:
        if f["op"] in ("crash", "stall", "oom"):
            kinds[f["op"]] = kinds.get(f["op"], 0) + 1
    print("faults applied: " +
          (" ".join(f"{k}={v}" for k, v in sorted(kinds.items())) or "none"))
    print(f"re-submissions: {rep.resubmissions}  "
          f"lost tokens: {rep.lost_tokens}")
    reasons = rep.reject_reasons()
    print("rejects: " + (" ".join(f"{k}={v}"
                                  for k, v in sorted(reasons.items()))
                         or "none"))


def init_model(cfg, seed: int, kv_shards: int = 1):
    """(model, params) with random weights from ``seed``, created inside
    one jit on the device — replicated over the ``kv`` mesh when the page
    pool is sharded, so no device holds a second copy."""
    model = build_model(cfg)
    sharding = None
    if kv_shards > 1:
        from jax.sharding import NamedSharding, PartitionSpec
        from repro.launch.mesh import make_kv_mesh
        sharding = NamedSharding(make_kv_mesh(kv_shards), PartitionSpec())
    params = jax.jit(model.init, out_shardings=sharding)(
        jax.random.PRNGKey(seed))
    return model, params


def model_serving(model, params, requests, mode: str, profile,
                  seed: int = 0, **backend_kw):
    """(ModelBackend, scheduler) for ``requests`` on the local device.
    Requests without token ids get ids drawn from ``seed``; the block
    table spans the longest request."""
    import numpy as np
    cfg = model.cfg
    rng = np.random.default_rng(seed)
    for r in requests:
        if r.prompt_tokens is None:
            r.prompt_tokens = rng.integers(
                4, cfg.vocab_size, r.prompt_len).tolist()
    max_len = max(r.prompt_len + r.max_new_tokens for r in requests)
    if backend_kw.get("kv_pages") is None and model.supports_paged():
        # this process owns the devices: the page pool takes the HBM left
        # after the weights (None on the host CPU: the backend's default)
        backend_kw["kv_pages"] = hbm_pool_pages(
            model, page_size=backend_kw.get("page_size"),
            kv_shards=backend_kw.get("kv_shards", 1))
    # attention families serve through the paged KV pool (prompt-pages-
    # only admission + incremental growth)
    backend = ModelBackend(model, params, max_len=max_len,
                           decode_mode="ar" if mode == "ar" else "elastic",
                           **backend_kw)
    if mode == "elastic":
        # wall-clock-free scheduler from the local device's analytic model
        sched = ElasticScheduler.from_analytic(
            AnalyticDeviceModel(cfg, device_spec()),
            prior_tokens_per_step=profile.tokens_per_step_bd32,
            batches=(1, 2, 4, 8), ctx=128.0)
    else:
        sched = make_scheduler(mode, None, profile)
    return backend, sched


def build_model_serving(args, profile, wl_kw):
    """``--backend model``: (ModelBackend, trace, scheduler)."""
    if args.smoke:
        cfg = get_smoke_config(args.arch)
        lengths = {"max_prompt": 64, "max_output": 64}
    else:
        cfg = get_config(args.arch)
        if args.layers is not None:
            print(f"depth cut: {args.layers} of {cfg.n_layers} layers "
                  f"of {cfg.name} at published widths")
            cfg = cfg.replace(n_layers=args.layers)
        lengths = {}
    model, params = init_model(cfg, args.seed, args.kv_shards)
    mkw = dict(wl_kw, **lengths)
    if args.workload == "shared":
        # real ids stay inside the vocab (away from the reserved mask/eos
        # ids at the top)
        mkw.update(vocab=max(cfg.vocab_size - 8, 16), prefix_len=32)
    wl = make_trace(profile, args.workload, args.rate, args.requests,
                    seed=args.seed, **mkw)
    backend, sched = model_serving(
        model, params, wl.requests, args.mode, profile, seed=args.seed,
        obs=args.obs,
        kv_pages=args.kv_pages, prefill_mode=args.prefill_mode,
        prefill_token_budget=args.prefill_budget, kv_shards=args.kv_shards,
        prefix_cache=not args.no_prefix_cache,
        host_kv_pages=args.host_kv_pages)
    return backend, wl, sched


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="sdar-8b")
    ap.add_argument("--mode", default="elastic",
                    help="elastic | ar | bd<chunk> (e.g. bd32)")
    ap.add_argument("--backend", default="sim", choices=["sim", "model"])
    ap.add_argument("--layers", type=int, default=None,
                    help="model backend: serve this many of the arch's "
                         "layers at its published widths (depth cut)")
    ap.add_argument("--smoke", action="store_true",
                    help="model backend: serve the arch's reduced SMOKE "
                         "config with prompts/outputs ≤ 64 tokens (CPU)")
    ap.add_argument("--device", default="tpu-v5e", choices=list(DEVICES))
    ap.add_argument("--dataset", default="sharegpt", choices=list(DATASETS))
    ap.add_argument("--rate", type=float, default=2.0)
    ap.add_argument("--requests", type=int, default=100)
    ap.add_argument("--max-batch", type=int, default=256)
    ap.add_argument("--obs", action="store_true",
                    help="out-block streaming for large chunks")
    ap.add_argument("--kv-pages", type=int, default=None,
                    help="KV pool pages (sim default 65536; model default "
                         "fills the HBM left after the weights)")
    ap.add_argument("--kv-shards", type=int, default=1,
                    help="split the paged KV pool across this many devices "
                         "on a 'kv' mesh axis (model backend: split-KV "
                         "paged decode with exact partial merge; sim "
                         "backend: sharded allocator bookkeeping). CPU "
                         "testing: XLA_FLAGS=--xla_force_host_platform_"
                         "device_count=8")
    ap.add_argument("--kv-admission", default="incremental",
                    choices=["incremental", "reserve"],
                    help="sim backend: incremental page growth with "
                         "preemption-on-OutOfPages (default) vs legacy "
                         "worst-case reservation at admit")
    ap.add_argument("--prefill-mode", default="chunked",
                    choices=["chunked", "wave"],
                    help="chunked: interleave budget-bounded page-aligned "
                         "prefill chunks with decode ticks (default); "
                         "wave: the monolithic whole-admission-wave "
                         "prefill baseline")
    ap.add_argument("--prefill-budget", type=int, default=None,
                    help="fixed max prompt tokens prefetched per engine "
                         "tick (default: adaptive Sarathi-style budget "
                         "target_bc - live b*c)")
    ap.add_argument("--workload", default="poisson",
                    choices=["poisson", "bursty", "diurnal", "shared"],
                    help="arrival trace: shared = multi-turn/system-prompt "
                         "trace with real token ids (exercises the prefix "
                         "cache)")
    ap.add_argument("--share-ratio", type=float, default=0.8,
                    help="shared workload: fraction of fresh requests "
                         "prepending a pooled system prompt")
    ap.add_argument("--no-prefix-cache", action="store_true",
                    help="disable cross-request KV prefix reuse")
    ap.add_argument("--host-kv-pages", type=int, default=0,
                    help="host-memory spill tier capacity in pages "
                         "(0 = disabled); preemptions spill instead of "
                         "discarding when the cost model favors the swap")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="record the telemetry timeline to PATH (JSONL) "
                         "and PATH's stem + .perfetto.json (Chrome "
                         "trace_event JSON for ui.perfetto.dev)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--faults", default=None, metavar="SPEC",
                    help="sim backend only: run through a single-replica "
                         "cluster engine with this deterministic fault "
                         "schedule (e.g. 'stall@1:r0:dur=0.5:slow=4'); "
                         "see serve_cluster for multi-replica failover")
    args = ap.parse_args()
    configure_compile_cache()

    profile = DATASETS[args.dataset]
    if args.faults:
        if args.backend != "sim":
            ap.error("--faults requires --backend sim")
        run_single_replica_faults(args, profile)
        return
    wl_kw = {"share_ratio": args.share_ratio} \
        if args.workload == "shared" else {}
    if args.backend == "sim":
        cfg = get_config(args.arch)
        backend = SimBackend(cfg, DEVICES[args.device],
                             tokens_per_step=profile.tokens_per_step_bd32,
                             decode_mode="ar" if args.mode == "ar"
                             else "elastic", obs=args.obs, seed=args.seed,
                             kv_pool_pages=args.kv_pages or 1 << 16,
                             kv_admission=args.kv_admission,
                             prefill_mode=args.prefill_mode,
                             prefill_token_budget=args.prefill_budget,
                             kv_shards=args.kv_shards,
                             prefix_cache=not args.no_prefix_cache,
                             host_kv_pages=args.host_kv_pages)
        wl = make_trace(profile, args.workload, args.rate, args.requests,
                        seed=args.seed, **wl_kw)
        sched = make_scheduler(args.mode, backend, profile)
    else:
        backend, wl, sched = build_model_serving(args, profile, wl_kw)

    tracer = Tracer() if args.trace else None
    engine = ServingEngine(backend, sched, max_batch=args.max_batch,
                           tracer=tracer)
    report = engine.run(list(wl))
    if tracer is not None:
        jsonl, perfetto = tracer.export(args.trace)
        print(f"trace: {len(tracer.events)} events "
              f"({tracer.dropped} dropped) -> {jsonl}, {perfetto}")
    print(f"requests: {len(report.metrics)}")
    print(f"decode throughput: {report.throughput:.1f} tok/s")
    print(f"P50/P90/P99 TPOT: {report.tpot_percentile(50)*1e3:.1f} / "
          f"{report.tpot_percentile(90)*1e3:.1f} / "
          f"{report.tpot_percentile(99)*1e3:.1f} ms")
    print(f"token utilization: {report.token_utilization:.3f}")
    print(f"memory preemptions: {report.preemptions}")
    print(f"runtime distributions: {chunk_distribution(report)}")


if __name__ == "__main__":
    main()
