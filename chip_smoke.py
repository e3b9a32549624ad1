#!/usr/bin/env python3
"""Chip smoke test: SDAR-8B at its published widths, served on a TPU.

    python3 chip_smoke.py                  # one chip
    python3 chip_smoke.py --kv-shards 4    # split-KV over four chips,
                                           # against the same requests on one

Everything runs in this one process (a chip belongs to one process at a
time); no phase is allowed to fail quietly.  The default run:

1. requires ``jax.devices()[0].platform == "tpu"``;
2. builds SDAR-8B (d_model 4096, 32 heads, 8 KV heads, head_dim 128,
   d_ff 12288, vocab 151936, bf16) with its depth cut to ``LAYERS``
   (random weights from ``--seed``, created on the device inside one jit)
   and a ``ModelBackend`` with ``attn_impl="kernel"`` whose page pool takes
   the HBM left after the weights;
3. compiles the fused decode step and requires ``tpu_custom_call`` in its
   HLO — the Pallas kernel was compiled, not interpreted or replaced by
   the jnp oracle;
4. checks the kernel's flash partials against
   ``kernels/ref.paged_chunk_ref`` (float32, highest matmul precision) at
   a decode shape and at a chunk that takes two query-row tiles;
5. serves seeded sharegpt-shaped requests (prompts ≤ 1024 tokens, outputs
   ≤ 256) in elastic mode through ``ServingEngine`` and requires every one
   of them to finish with all its tokens.

``--kv-shards 4`` runs only split-KV at ``kv_shards=4`` and what it is
compared with (see :func:`split_kv`): in bf16 the split-KV kernel against
one chip's within tolerance and serving in which every request finishes;
in float32 the same requests at ``kv_shards=4`` and 1 with identical
committed tokens.

The serve phase's host-clock seconds are a smoke time, not a metric.  The
last line of stdout is one JSON object naming the device.
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "sdar-8b"
# One v5e chip lets JAX use 16.91 GB of HBM.  16 of SDAR-8B's 36 layers
# are 8.66 GB of bf16 weights (6.18 GB of blocks, 2.49 GB of embedding and
# LM head); the decode step at B=8, c=32 needs 0.34 GB of temporaries
# (memory_analysis on the chip), which leaves a ~77k-token pool.  All 36
# layers are 16.38 GB and would leave 0.53 GB for pool and temporaries.
LAYERS = 16
N_REQUESTS, MAX_PROMPT, MAX_OUTPUT = 8, 1024, 256
# Split-KV comparison: one fixed pool for both shard counts (16k tokens,
# float32: 2.1 GB at 16 layers).
SPLIT_KV_POOL_PAGES = 1024
# Kernel-vs-oracle shapes (B, c) over contexts up to CHECK_PAGES pages
# (1024 tokens): a decode chunk whose G·c = 128 query rows fit one row
# tile, and an 80-token chunk of 320 rows, which takes two ROW_TILE tiles,
# the second one padded.  The first is also the compiled-HLO check's shape.
CHECK_SHAPES = ((8, 32), (4, 80))
CHECK_PAGES = 64
# bf16 tolerance of kernel vs oracle: both read the same bf16 K/V; the
# kernel feeds the MXU bf16 probabilities for P·V (a relative rounding of
# 2^-8 per term) where the oracle keeps them in fp32.
KERNEL_RTOL = 3e-2
M_ATOL = 1e-3


class CompileCounter:
    """Counts XLA programs compiled (or loaded from the persistent cache)
    in this process, from JAX's own monitoring events."""

    def __init__(self):
        self.programs = 0
        self.seconds = 0.0
        self.cache_hits = 0

    def install(self):
        import jax.monitoring
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)
        return self

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.programs += 1
            self.seconds += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def require_tpu():
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU; JAX found "
                 f"{devices[0].platform!r} ({devices[0].device_kind})")
    return devices


def tree_bytes(tree) -> int:
    import jax
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


def depth_cut_line(device) -> str:
    """The cut and its reason, from shapes alone (nothing allocated)."""
    import jax
    from repro.configs import get_config
    from repro.models.registry import build_model
    full = get_config(ARCH)
    per = {}
    for n in (1, 2):
        model = build_model(full.replace(n_layers=n))
        per[n] = tree_bytes(model.init(jax.random.PRNGKey(0), abstract=True))
    layer, fixed = per[2] - per[1], 2 * per[1] - per[2]
    limit = (device.memory_stats() or {}).get("bytes_limit", 0)
    cut, whole = fixed + LAYERS * layer, fixed + full.n_layers * layer
    return (f"depth cut: {LAYERS} of {full.n_layers} layers of {ARCH} at "
            f"published widths — weights {cut / 1e9:.2f} GB leave "
            f"{(limit - cut) / 1e9:.2f} of {limit / 1e9:.2f} GB HBM for the "
            f"KV pool and step temporaries; all {full.n_layers} layers "
            f"({whole / 1e9:.2f} GB) would leave "
            f"{(limit - whole) / 1e9:.2f} GB")


def make_requests(n: int, seed: int, max_prompt: int, max_output: int):
    """Seeded sharegpt-shaped requests, all arriving at t=0: the model
    backend reports no step latency to the engine's virtual clock, so
    staggered arrivals would be served one at a time."""
    from repro.serving import DATASETS, PoissonWorkload
    requests = list(PoissonWorkload(DATASETS["sharegpt"], rate=1.0,
                                    n_requests=n, seed=seed,
                                    max_prompt=max_prompt,
                                    max_output=max_output))
    for r in requests:
        r.arrival_time = 0.0
    return requests


def decode_hlo(backend, B: int, c: int):
    """Compile the backend's fused decode step at [B, c]; returns the
    compiled executable (HLO text + memory analysis)."""
    import jax.numpy as jnp
    W = backend._table_width
    i32 = jnp.int32
    args = (backend.params, backend._pages_cache(), jnp.zeros((B, c), i32),
            jnp.zeros(B, i32), jnp.zeros(B, i32), jnp.zeros((B, W), i32),
            jnp.zeros(B, i32), jnp.zeros(B, i32))
    return backend._decode_paged.lower(*args).compile()


def kernel_inputs(cfg, B: int, c: int, n_pages: int, seed: int):
    """Random q [B, c, H, D] and K/V pools [n_pages, ps, KVH, D] in the
    config's compute dtype, with contexts of 1 to CHECK_PAGES pages."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    shape = (n_pages, cfg.kv_page_size, cfg.n_kv_heads, cfg.hd)
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(kq, (B, c, cfg.n_heads, cfg.hd), jnp.float32)
    kp = jax.random.normal(kk, shape, jnp.float32)
    vp = jax.random.normal(kv, shape, jnp.float32)
    lens = np.random.default_rng(seed).integers(
        1, cfg.kv_page_size * CHECK_PAGES, B)
    return (q.astype(cfg.cdt), kp.astype(cfg.cdt), vp.astype(cfg.cdt),
            lens.astype(np.int32))


def partial_errors(got, want) -> dict:
    """Errors of flash partials ``(acc, m, l)`` against ``want``."""
    import numpy as np
    acc, m, l = map(np.asarray, got)
    acc_r, m_r, l_r = map(np.asarray, want)
    return {"acc_rel": float(np.max(np.abs(acc - acc_r))
                             / (np.max(np.abs(acc_r)) + 1e-9)),
            "l_rel": float(np.max(np.abs(l - l_r) / np.maximum(l_r, 1e-9))),
            "m_abs": float(np.max(np.abs(m - m_r)))}


def require_close(errs: dict, what: str):
    if not (errs["acc_rel"] < KERNEL_RTOL and errs["l_rel"] < KERNEL_RTOL
            and errs["m_abs"] < M_ATOL):
        raise AssertionError(f"{what} outside tolerance: {errs}")


def oracle(q, kp, vp, tables, lens):
    import jax
    from repro.kernels.ref import paged_chunk_ref
    with jax.default_matmul_precision("highest"):
        return jax.jit(paged_chunk_ref)(q, kp, vp, tables, lens)


def check_kernel(cfg, B: int, c: int, *, seed: int = 0):
    """Kernel flash partials vs the fp32 oracle at [B, c], with the pool
    in the config's compute dtype and pages scattered over it.  Returns
    the errors."""
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels.chunked_paged_attn import paged_chunk_attention_kernel
    from repro.kernels.ops import resolve_interpret

    P = B * CHECK_PAGES + 3
    q, kp, vp, lens = kernel_inputs(cfg, B, c, P, seed)
    tables = np.random.default_rng(seed).permutation(P)[:B * CHECK_PAGES]
    tables = jnp.asarray(tables.reshape(B, CHECK_PAGES), jnp.int32)
    lens = jnp.asarray(lens)
    kern = jax.jit(functools.partial(paged_chunk_attention_kernel,
                                     interpret=resolve_interpret()))
    got = kern(q, kp, vp, tables, lens)
    if not np.isfinite(np.asarray(got[0])).all():
        raise AssertionError(f"kernel at B={B}, c={c}: non-finite output")
    errs = partial_errors(got, oracle(q, kp, vp, tables, lens))
    require_close(errs, f"kernel vs oracle at B={B}, c={c}")
    return errs


def check_split_kv_kernel(cfg, kv_shards: int, *, seed: int = 0):
    """Split-KV attention in the config's compute dtype: each of
    ``kv_shards`` chips runs the kernel over its slice of a page-sharded
    pool (tables striped by the allocator, as served) and the partials
    merge across the chips.  Compared with the kernel over the whole pool
    on one chip and with the fp32 oracle.  Returns both errors."""
    import functools

    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec
    from repro.distributed.collectives import (KVShardSpec,
                                               split_kv_paged_partial)
    from repro.kernels.chunked_paged_attn import paged_chunk_attention_kernel
    from repro.kernels.ops import resolve_interpret
    from repro.launch.mesh import make_kv_mesh
    from repro.serving.kv_pool import PagedKVAllocator

    B, c = CHECK_SHAPES[0]
    P = B * CHECK_PAGES + kv_shards
    q, kp, vp, lens = kernel_inputs(cfg, B, c, P, seed)
    kv = PagedKVAllocator(P, cfg.kv_page_size, kv_shards=kv_shards)
    for rid, n in enumerate(lens):
        kv.allocate(rid, int(n))
    rids = list(range(B))
    tables = jnp.asarray(kv.batch_tables(rids, width=CHECK_PAGES))
    offs = jnp.asarray(kv.stripe_offsets(rids))
    lens = jnp.asarray(lens)
    mesh = make_kv_mesh(kv_shards)
    pages = NamedSharding(mesh, PartitionSpec("kv"))
    ks = KVShardSpec(mesh, kv_shards)
    interpret = resolve_interpret()
    split = jax.jit(functools.partial(split_kv_paged_partial, ks=ks,
                                      interpret=interpret))
    got = split(q, jax.device_put(kp, pages), jax.device_put(vp, pages),
                tables, lens, offs)
    one = jax.jit(functools.partial(paged_chunk_attention_kernel,
                                    interpret=interpret))(
        q, kp, vp, tables, lens)
    errs = {"one_chip": partial_errors(got, one),
            "oracle": partial_errors(got, oracle(q, kp, vp, tables, lens))}
    for what, e in errs.items():
        require_close(e, f"split-KV kernel vs {what} at B={B}, c={c}")
    return errs


def build_backend(model, params, requests, *, seed: int, **backend_kw):
    """ModelBackend (Pallas kernel attention) + elastic scheduler for
    ``requests`` — the path ``repro.launch.serve --backend model`` takes."""
    from repro.launch.serve import model_serving
    from repro.serving import DATASETS
    return model_serving(model, params, requests, "elastic",
                         DATASETS["sharegpt"], seed=seed, attn_impl="kernel",
                         **backend_kw)


def serve(backend, sched, requests):
    """Serve ``requests`` through ``ServingEngine``; every one must finish
    with all its tokens.  Returns (report, committed tokens per rid, host
    seconds)."""
    import jax
    from repro.serving import ServingEngine

    outputs = {}
    release = backend.release

    def release_recording(rid):
        outputs[rid] = backend.state(rid).output_tokens
        release(rid)

    backend.release = release_recording
    t0 = time.perf_counter()
    report = ServingEngine(backend, sched, max_batch=len(requests)) \
        .run(requests)
    jax.block_until_ready((backend.kv.k_pages, backend.kv.v_pages))
    secs = time.perf_counter() - t0
    want = {r.rid: r.max_new_tokens for r in requests}
    got = {m.rid: m.n_tokens for m in report.metrics}
    if got != want or set(outputs) != set(want):
        raise AssertionError(f"unfinished requests: want {want}, got {got}")
    return report, outputs, secs


def error_text(errs: dict) -> str:
    return (f"acc rel {errs['acc_rel']:.2e}, l rel {errs['l_rel']:.2e} "
            f"(tolerance {KERNEL_RTOL}), m abs {errs['m_abs']:.2e} "
            f"(tolerance {M_ATOL})")


def one_chip(cfg, *, seed: int = 0, n_requests: int = N_REQUESTS,
             max_prompt: int = MAX_PROMPT, max_output: int = MAX_OUTPUT,
             log=print) -> dict:
    """Phases 2–5 for ``cfg``; returns what they measured."""
    from repro.launch.serve import init_model

    model, params = init_model(cfg, seed)
    log(f"weights: {tree_bytes(params) / 1e9:.3f} GB on device")
    requests = make_requests(n_requests, seed, max_prompt, max_output)
    errs = {}
    for B, c in CHECK_SHAPES:
        errs[B, c] = check_kernel(cfg, B, c, seed=seed)
        log(f"kernel vs oracle at B={B}, c={c} ({cfg.compute_dtype}): "
            + error_text(errs[B, c]))
    backend, sched = build_backend(model, params, requests, seed=seed)
    B, c = CHECK_SHAPES[0]
    compiled = decode_hlo(backend, B, c)
    kernel_in_hlo = "tpu_custom_call" in compiled.as_text()
    temp = compiled.memory_analysis().temp_size_in_bytes
    kv = backend.kv
    log(f"decode step [B={B}, c={c}]: tpu_custom_call in HLO: "
        f"{kernel_in_hlo}; temporaries {temp / 1e9:.3f} GB "
        f"(memory_analysis); pool {kv.n_pages} pages = "
        f"{kv.n_pages * kv.page_size} tokens "
        f"({kv.n_pages * kv.page_bytes / 1e9:.3f} GB)")
    del compiled
    report, outputs, secs = serve(backend, sched, requests)
    steps = backend.decode_dispatches
    log(f"serve: {len(report.metrics)}/{len(requests)} requests finished; "
        f"{report.total_tokens} committed tokens in {steps} decode steps "
        f"({report.total_tokens / max(steps, 1):.2f} tokens/step), "
        f"{backend.prefill_dispatches} prefill dispatches")
    log(f"smoke time, host clock (not a metric): serve phase {secs:.1f} s")
    return {"kernel_in_hlo": kernel_in_hlo, "kernel_errs": errs,
            "tokens": report.total_tokens, "decode_steps": steps,
            "outputs": outputs}


def split_kv(cfg, *, kv_shards: int, seed: int = 0,
             n_requests: int = N_REQUESTS, max_prompt: int = MAX_PROMPT,
             max_output: int = MAX_OUTPUT,
             kv_pages: int = SPLIT_KV_POOL_PAGES, log=print) -> dict:
    """Split-KV over ``kv_shards`` chips against one.

    In the config's compute dtype (bf16 for SDAR-8B), as users serve it:
    the split-KV kernel's merged partials must match one chip's within the
    kernel tolerance, and every request served at ``kv_shards`` must
    finish.  Tokens are not compared there: the merge sums attention in
    another order, and one bf16 rounding step of an activation (2^-8) can
    flip a near-tie argmax so that the free-running trajectories part
    (the CPU rehearsal at smoke width shows it).

    Then the same requests at ``kv_shards`` and at 1 in float32 under the
    highest matmul precision (weights stay in their bf16 storage), where
    the reordering moves logits by ~1e-7 relative, far inside any argmax
    gap: the committed tokens must be identical.  Sharded runs come first,
    so the per-device peaks printed after them are their own."""
    import jax
    from repro.launch.serve import init_model

    errs = check_split_kv_kernel(cfg, kv_shards, seed=seed)
    B, c = CHECK_SHAPES[0]
    for what, e in errs.items():
        log(f"split-KV kernel at kv_shards={kv_shards} vs {what}, B={B}, "
            f"c={c} ({cfg.compute_dtype}): " + error_text(e))
    f32 = cfg.replace(compute_dtype="float32")
    outputs, served = {}, None
    for run_cfg, S in ((cfg, kv_shards), (f32, kv_shards), (f32, 1)):
        model, params = init_model(run_cfg, seed, S)
        requests = make_requests(n_requests, seed, max_prompt, max_output)
        backend, sched = build_backend(model, params, requests, seed=seed,
                                       kv_shards=S, kv_pages=kv_pages)
        with (jax.default_matmul_precision("highest") if run_cfg is f32
              else contextlib.nullcontext()):
            report, out, secs = serve(backend, sched, requests)
        if run_cfg is f32:
            outputs[S] = out
        else:
            served = out
        log(f"kv_shards={S} ({run_cfg.compute_dtype}): "
            f"{len(report.metrics)}/{len(requests)} requests finished, "
            f"{report.total_tokens} committed tokens in "
            f"{backend.decode_dispatches} decode steps; smoke time, host "
            f"clock (not a metric): {secs:.1f} s")
        if S > 1 and run_cfg is f32:
            for d in jax.devices()[:S]:
                peak = (d.memory_stats() or {}).get("peak_bytes_in_use")
                log(f"  device {d.id} peak_bytes_in_use: {peak}")
        del model, params, report, backend, sched
        gc.collect()
    sharded, one = outputs[kv_shards], outputs[1]
    log(f"committed tokens identical at kv_shards={kv_shards} and 1 "
        f"(float32): {sharded == one}")
    if sharded != one:
        diff = [r for r in one if one[r] != sharded[r]]
        raise AssertionError(f"split-KV changed the tokens of rids {diff}")
    return {"outputs": outputs, "served": served, "kernel_errs": errs}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--kv-shards", type=int, default=1,
                    help="run only split-KV serving over this many chips "
                         "and the same requests on one")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = require_tpu()
    from repro.configs import get_config
    from repro.launch.compile_cache import configure_compile_cache

    cache = configure_compile_cache()
    compiles = CompileCounter().install()
    dev = devices[0]
    print(f"device: {dev.device_kind} ({dev.platform}) x{len(devices)}; "
          f"compile cache {cache}")
    print(depth_cut_line(dev))
    cfg = get_config(ARCH).replace(n_layers=LAYERS)
    if args.kv_shards > 1:
        if len(devices) < args.kv_shards:
            sys.exit(f"chip_smoke: --kv-shards {args.kv_shards} needs that "
                     f"many chips; {len(devices)} visible")
        split_kv(cfg, kv_shards=args.kv_shards, seed=args.seed)
    else:
        res = one_chip(cfg, seed=args.seed)
        if not res["kernel_in_hlo"]:
            sys.exit("chip_smoke: no tpu_custom_call in the compiled "
                     "decode step — the paged kernel did not compile")
        print(f"peak_bytes_in_use: "
              f"{dev.memory_stats().get('peak_bytes_in_use')}")
    print(f"compiles: {compiles.programs} XLA programs in "
          f"{compiles.seconds:.1f} s ({compiles.cache_hits} from the "
          f"persistent cache)")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
