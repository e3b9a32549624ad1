#!/usr/bin/env python3
"""Calibrate a configuration's logit gain on the chip.

    python3 bench/calibrate.py --config bench/configs/<name>.json

With random weights the logits are nearly flat, no position ever crosses
the confidence threshold, and every diffusion step commits one token per
request.  The gain of the final norm scales the logits.  This serves
ShareGPT-shaped requests with the program's fixed-BD32 scheduler at
several gains, in one process (the model by the configuration's model
module, with its pool and serving options), and prints the committed tokens per step
per request at each, then the gain whose value is nearest the target
(5.29, ShareGPT under BD32 in the paper's Table 2).  Gains are multiples
of 1/8, exact in bfloat16.
"""

import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[0] = str(ROOT)
sys.path.insert(1, str(ROOT / "src"))

import argparse  # noqa: E402
import json  # noqa: E402

import numpy as np  # noqa: E402

TARGET = 5.29
N_REQUESTS = 16
TRAFFIC = {"prompt": {"mean": 213, "std": 508, "min": 8, "max": 512},
           "output": {"mean": 321, "std": 214, "min": 4, "max": 256},
           "arrivals": {"kind": "poisson", "rate": 1.0},
           "lead_in_s": 0.0, "drain_s": 0.0, "base_seed": 7}


def tokens_per_step(backend, sched, spec, gain, seed):
    import jax.numpy as jnp

    from bench import traffic as T
    from repro.serving import ServingEngine
    from repro.serving.request import Request
    p = backend.params["final_norm"]
    p["scale"] = jnp.full_like(p["scale"], gain)
    arr = T.generate(TRAFFIC, seed, 3.0 * N_REQUESTS,
                     spec["vocab_size"])[:N_REQUESTS]
    reqs = [Request(a.rid, 0.0, a.prompt_len, a.output_len,
                    prompt_tokens=a.tokens.tolist()) for a in arr]
    rep = ServingEngine(backend, sched, max_batch=N_REQUESTS).run(reqs)
    per = [m.n_tokens / m.decode_steps for m in rep.metrics
           if m.decode_steps]
    return float(np.mean(per))


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--smoke", action="store_true",
                    help="CPU rehearsal at the configuration's smoke size")
    args = ap.parse_args()
    from bench import harness as H
    from bench import spec as S
    if not args.smoke:
        H.configure_cache()
    import jax

    from repro.serving.request import Request
    if not args.smoke and jax.devices()[0].platform != "tpu":
        sys.exit("calibrate: needs a TPU")
    if args.smoke:
        TRAFFIC["prompt"]["max"], TRAFFIC["output"]["max"] = 48, 24
    cfg = S.load_json(ROOT / args.config)
    model = S.model_module(cfg)
    spec = S.model_spec(cfg, smoke=args.smoke, model=model)
    span = Request(-1, 0.0, TRAFFIC["prompt"]["max"],
                   TRAFFIC["output"]["max"], prompt_tokens=[])
    _, backend, sched = H.serve(cfg, model, spec, H.weight_seed(args.seed),
                                [span], "bd32", "sharegpt", args.seed)
    seen = {}

    def at(g):
        g = round(g * 8) / 8
        if g not in seen:
            t0 = time.perf_counter()
            seen[g] = tokens_per_step(backend, sched, spec, g,
                                      args.seed + len(seen))
            print(f"gain {g}: {seen[g]:.3f} tokens/step/request "
                  f"({time.perf_counter() - t0:.1f} s)", flush=True)
        return seen[g]

    lo, hi = 1.0, 1.0
    while at(hi) < TARGET and hi < 256:
        lo, hi = hi, hi * 2
    for _ in range(6):
        mid = (lo + hi) / 2
        if round(mid * 8) in (round(lo * 8), round(hi * 8)):
            break
        if at(mid) < TARGET:
            lo = mid
        else:
            hi = mid
    best = min(seen, key=lambda g: abs(seen[g] - TARGET))
    print(json.dumps({"logit_gain": best, "tokens_per_step": seen[best],
                      "target": TARGET, "tried": seen}))


if __name__ == "__main__":
    main()
