"""End-to-end serving driver: a real model served with batched requests
through the continuous-batching engine, Optimus elastic decoding vs AR and
fixed-block baselines (deliverable: serve a small model with batched
requests).

    PYTHONPATH=src python examples/serve_elastic.py [--requests 12]

Attention-only families serve through the unified paged KV pool (block
tables + the Pallas chunked-paged-attention kernel, interpret mode on CPU)
with **memory-elastic admission**: a request claims only its prompt's pages
at admit and grows page-by-page as chunks commit, so far more requests run
in flight than worst-case reservation would allow — and when the pool runs
dry mid-decode, the engine preempts a victim (freeing its pages) and
re-prefills it later.  ``--tight-pool`` demonstrates that preemption path.
"""

import argparse

import jax
import numpy as np

from repro.core import ElasticScheduler, FixedScheduler
from repro.core.latency_model import AnalyticDeviceModel, device_spec
from repro.models import ArchConfig, build_model
from repro.serving import (DATASETS, ModelBackend, PoissonWorkload,
                           ServingEngine, chunk_distribution)

ap = argparse.ArgumentParser()
ap.add_argument("--requests", type=int, default=10)
ap.add_argument("--prompt", type=int, default=16)
ap.add_argument("--out", type=int, default=24)
ap.add_argument("--tight-pool", action="store_true",
                help="also run with a page pool too small for everyone, "
                     "showing preemption-on-OutOfPages")
args = ap.parse_args()

N_SLOTS, MAX_LEN = 8, 128

cfg = ArchConfig(name="serve-demo", family="dense", n_layers=2, d_model=128,
                 n_heads=4, n_kv_heads=2, d_ff=256, vocab_size=512,
                 block_size=8, confidence_threshold=0.6)
model = build_model(cfg)
params = model.init(jax.random.PRNGKey(0))
prof = DATASETS["sharegpt"]
rng = np.random.default_rng(0)


def workload(simultaneous=False):
    wl = list(PoissonWorkload(prof, rate=50.0, n_requests=args.requests,
                              seed=1))
    for r in wl:
        r.prompt_len = args.prompt
        r.max_new_tokens = args.out
        r.prompt_tokens = rng.integers(4, cfg.vocab_size,
                                       args.prompt).tolist()
        if simultaneous:
            r.arrival_time = 0.0
    return wl


def run(mode, chunk=None):
    be = ModelBackend(model, params, n_slots=N_SLOTS, max_len=MAX_LEN,
                      decode_mode="ar" if mode == "ar" else "elastic")
    if mode == "elastic":
        an = AnalyticDeviceModel(cfg, device_spec())
        samples = [(b, c, an.step_latency(b, c, 64))
                   for b in [1, 2, 4, 8] for c in [1, 2, 4, 8]]
        sch = ElasticScheduler.from_profile(samples, candidates=(2, 4, 8),
                                            prior_tokens_per_step=3.0)
    else:
        sch = FixedScheduler(1 if mode == "ar" else chunk)
    eng = ServingEngine(be, sch, max_batch=8)
    rep = eng.run(workload())
    total_steps = sum(m.decode_steps for m in rep.metrics)
    print(f"{mode + (str(chunk) if chunk else ''):>10s}: "
          f"{rep.total_tokens} tokens, {total_steps} request-steps, "
          f"TU={rep.token_utilization:.3f}, "
          f"mean chunk={np.mean([c for _, _, c in rep.chunk_history]) if rep.chunk_history else 0:.1f}")
    return rep


print(f"serving {args.requests} batched requests "
      f"(prompt {args.prompt}, output {args.out}) on a real model "
      f"[paged KV pool, incremental page growth]\n")
run("ar")
run("fixed", 8)
rep = run("elastic")
print("\nelastic runtime distributions:", chunk_distribution(rep))

# Memory-elastic admission demo: requests claim prompt pages only, so the
# pool admits far more in flight than worst-case (prompt+out) reservation.
total = args.prompt + args.out
be = ModelBackend(model, params, n_slots=N_SLOTS, max_len=MAX_LEN)
fit_worst = be.kv.n_pages // be.kv.pages_for(total)
fit_prompt = be.kv.n_pages // be.kv.pages_for(args.prompt)
eng = ServingEngine(be, FixedScheduler(8), max_batch=64)
rep = eng.run(workload(simultaneous=True))
print(f"\nmemory-elastic admission: pool of {be.kv.n_pages} pages fits "
      f"{fit_prompt} prompts at admit (worst-case reservation: {fit_worst}; "
      f"dense-slot ceiling was {N_SLOTS}); peak in-flight batch = "
      f"{max(rep.batch_history)}, preemptions = {rep.preemptions}")
assert be.kv.free_pages == be.kv.n_pages      # drained: no page leaks

if args.tight_pool:
    # Pool sized so the whole workload cannot co-resident at full length:
    # mid-decode OutOfPages forces evict+requeue+re-prefill, yet everyone
    # still completes with full outputs.
    pages = max(2 * be.kv.pages_for(total), 3 * be.kv.pages_for(args.prompt))
    be = ModelBackend(model, params, max_len=MAX_LEN, kv_pages=pages)
    eng = ServingEngine(be, FixedScheduler(8), max_batch=64)
    rep = eng.run(workload(simultaneous=True))
    done = sum(1 for m in rep.metrics if m.n_tokens == args.out)
    print(f"tight pool ({pages} pages): {done}/{args.requests} requests "
          f"completed full outputs with {rep.preemptions} preemptions; "
          f"pool drained clean = {be.kv.free_pages == be.kv.n_pages}")

print("done — all requests completed through the continuous-batching engine")
