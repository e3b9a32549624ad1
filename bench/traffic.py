"""The one traffic generator: reads a traffic file and a seed, returns the
requests of one run, open loop, with their due times.

A traffic file (``bench/traffic/<name>.json``) gives:

* ``prompt`` / ``output``: log-normal lengths by ``mean`` and ``std`` (of
  the unclipped distribution), clipped to ``[min, max]``;
* ``arrivals``: ``{"kind": "poisson", "rate": r}``;
* ``lead_in_s``, ``drain_s``: arrivals before the window opens, and how
  long after it closes the run may go on to finish the window's requests;
* ``base_seed``: the seed of the arrival times and of the multiset of
  lengths.

Every run seed sees the same arrival times and the same multiset of
lengths in each phase (lead-in, window, after the window); the run seed
only permutes which length goes to which arrival, and draws the token ids.
So seeds change the order of the work and not its amount.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

# ids below this are never drawn: the mask id and other reserved ids
FIRST_TOKEN_ID = 4


@dataclass
class Arrival:
    rid: int
    due: float              # seconds after the traffic starts
    prompt_len: int
    output_len: int
    tokens: np.ndarray      # [prompt_len] int32


def lognormal_params(mean: float, std: float) -> tuple[float, float]:
    """(mu, sigma) of the log-normal with this mean and standard deviation."""
    s2 = np.log1p((std / mean) ** 2)
    return float(np.log(mean) - s2 / 2), float(np.sqrt(s2))


def lengths(rng, dist: dict, n: int) -> np.ndarray:
    mu, sigma = lognormal_params(dist["mean"], dist["std"])
    x = rng.lognormal(mu, sigma, n)
    return np.clip(np.rint(x), dist["min"], dist["max"]).astype(np.int64)


def arrival_times(arr: dict, horizon: float, rng) -> np.ndarray:
    """Poisson arrival times in ``[0, horizon)``."""
    if arr["kind"] != "poisson":
        raise ValueError(f"unknown arrival kind {arr['kind']!r}")
    out, t = [], 0.0
    while True:
        t += rng.exponential(1.0 / arr["rate"])
        if t >= horizon:
            return np.asarray(out)
        out.append(t)


def generate(traffic: dict, seed: int, seconds: float, vocab: int,
             rate: float | None = None) -> list[Arrival]:
    """The requests of one run: due times over lead-in, window and drain.

    ``rate`` overrides the file's rate (the knee sweep)."""
    arr = dict(traffic["arrivals"])
    if rate is not None:
        arr["rate"] = rate
    lead, drain = traffic["lead_in_s"], traffic["drain_s"]
    base = np.random.default_rng(traffic["base_seed"])
    due = arrival_times(arr, lead + seconds + drain, base)
    phase = np.searchsorted([lead, lead + seconds], due, side="right")
    run = np.random.default_rng(seed)
    prompt = np.empty(len(due), np.int64)
    output = np.empty(len(due), np.int64)
    for ph in range(3):
        idx = np.nonzero(phase == ph)[0]
        p = lengths(base, traffic["prompt"], len(idx))
        o = lengths(base, traffic["output"], len(idx))
        perm = run.permutation(len(idx))
        prompt[idx], output[idx] = p[perm], o[perm]
    toks = run.integers(FIRST_TOKEN_ID, vocab, int(prompt.sum()),
                        dtype=np.int32)
    cuts = np.cumsum(prompt)[:-1]
    return [Arrival(i, float(t), int(p), int(o), tk)
            for i, (t, p, o, tk) in enumerate(
                zip(due, prompt, output, np.split(toks, cuts)))]


def longest_context(traffic: dict) -> int:
    """The longest prompt plus output the mix can draw."""
    return int(traffic["prompt"]["max"] + traffic["output"]["max"])
