#!/usr/bin/env python3
"""Find a cell's knee on the chip: the highest offered rate at which the
number of waiting requests at the window's end is no larger than at its
start.  A request waits from its due time until its first token: queued
for admission, or admitted with its prompt not yet prefilled.

    python3 bench/sweep.py --workload <cell> --rates 2 4 8 16 --seconds 20

One process and one set-up (the cell's model by its model module, its
pool and serving options, and the warm-up); each rate then gets a fresh engine and scheduler on the same backend and
runs its lead-in and window open loop, after which every request still in
flight is released.  Prints one line per rate and a JSON summary whose
``knee`` is the highest sustained rate; a cell file takes a fixed share of
it as its ``arrivals.rate``.  ``--smoke`` rehearses the same on the CPU
at the configuration's smoke size (no warm-up, no TPU needed).
"""

import sys
import time
from pathlib import Path

T0 = time.perf_counter()
ROOT = Path(__file__).resolve().parents[1]
sys.path[0] = str(ROOT)
sys.path.insert(1, str(ROOT / "src"))

import argparse  # noqa: E402
import copy  # noqa: E402
import json  # noqa: E402


def waiting(core, now):
    """Requests due by ``now`` that have no first token yet."""
    queued = sum(1 for r in core.pending_requests() if r.arrival_time <= now)
    return queued + sum(1 for r in core.active_requests()
                        if core._metrics[r.rid].first_token_time < 0)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--smoke", action="store_true",
                    help="CPU rehearsal at the configuration's smoke size")
    args = ap.parse_args()
    from bench import harness as H
    from bench import spec as S
    from bench import stats
    from bench import traffic as T
    if not args.smoke:
        H.configure_cache()
    import jax

    from repro.serving.engine import EngineCore
    from repro.serving.request import Request
    if not args.smoke and jax.devices()[0].platform != "tpu":
        sys.exit("sweep: needs a TPU")
    cell = S.resolve_cell(args.workload)
    spec = S.model_spec(cell.config, smoke=args.smoke, model=cell.model)
    traffic = cell.traffic
    max_batch = cell.config["max_batch"]
    if args.smoke:
        traffic = H.rehearsal_traffic(traffic)
        max_batch = H.REHEARSAL["max_batch"]
    span = Request(-1, 0.0, traffic["prompt"]["max"],
                   traffic["output"]["max"], prompt_tokens=[])
    _, backend, sched0 = H.serve(cell.config, cell.model, spec,
                                 H.weight_seed(args.seed), [span], "elastic",
                                 traffic["dataset"], args.seed)
    if not args.smoke:
        H.warm(backend, spec, max_batch, sched0.candidates, traffic)
    print(f"set-up {time.perf_counter() - T0:.1f} s", flush=True)
    rows = []
    lo = traffic["lead_in_s"]
    hi = lo + args.seconds
    for i, rate in enumerate(args.rates):
        arr = T.generate(dict(traffic, drain_s=0.0), args.seed + i,
                         args.seconds, spec["vocab_size"], rate=rate)
        reqs = [Request(a.rid, a.due, a.prompt_len, a.output_len,
                        prompt_tokens=a.tokens.tolist()) for a in arr]
        clock = H.WallClock()
        core = EngineCore(backend, copy.deepcopy(sched0),
                          max_batch=max_batch, clock=clock)
        core.submit_all(reqs)
        w_lo = tokens = None
        batches = []
        while True:
            now = clock.now()
            # the window opens at the first tick boundary at or after lo
            if w_lo is None and now >= lo:
                w_lo = waiting(core, now)
                done0 = sum(m.n_tokens for m in core.report().metrics)
                act0 = sum(backend.state(r.rid).n_committed
                           for r in core.active_requests())
            if now >= hi:
                break
            core.tick()
            if now >= lo:
                batches.append(core.n_active)
        now = clock.now()
        w_hi = waiting(core, now)
        rep = core.report()
        act1 = sum(backend.state(r.rid).n_committed
                   for r in core.active_requests())
        tokens = sum(m.n_tokens for m in rep.metrics) - done0 + act1 - act0
        fin = [m for m in rep.metrics if lo <= m.arrival_time < hi]
        row = {"rate": rate, "waiting_start": w_lo, "waiting_end": w_hi,
               "tokens_per_s": tokens / args.seconds,
               "mean_batch": sum(batches) / max(len(batches), 1),
               "finished": len(fin),
               "ttft_p90_ms": 1e3 * stats.percentile(
                   [m.ttft for m in fin], 90) if fin else None,
               "tpot_p90_ms": 1e3 * stats.percentile(
                   [m.tpot for m in fin if m.n_tokens > 1], 90)
               if fin else None,
               "preemptions": core.preemptions}
        print(json.dumps(row), flush=True)
        rows.append(row)
        for r in core.active_requests():
            backend.release(r.rid)
    ok = [r["rate"] for r in rows if r["waiting_end"] <= r["waiting_start"]]
    print(json.dumps({"workload": args.workload, "knee": max(ok)
                      if ok else None, "rows": rows}))


if __name__ == "__main__":
    main()
