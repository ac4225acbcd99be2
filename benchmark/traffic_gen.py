"""The one general traffic generator. A traffic mix is a data file
(traffic/<name>.json); this module turns it and `--seed` into the work of
a run. No jax.

Steadiness rule: every seed gets the SAME schedule: the same sizes, gaps,
pairing and order, all drawn from the file's own `base_seed`. `--seed`
draws the token ids (and, in the cells, the weights). On the chip the same
multiset in another order moved the 90th percentile of time to first token
from 1.4 s to 3.1 s at 0.8 x the knee (PERF.md, PR 24): the order is part
of the work, so it is part of the mix and not of the seed.

kinds
  train        {"kind": "train", "seq_length", "sequences_per_chip",
                "data": "synthetic"}
  open_loop    arrivals on a schedule whether or not earlier requests have
               finished: {"arrivals": {"process": "poisson"|"gamma",
               "rate_per_s", "cv"?}, "prompt_tokens": DIST,
               "output_tokens": DIST, "preroll_s", "drain_s",
               "shared_prefix"?: {"pool", "tokens"}}
  closed_loop  a fixed number of clients, each sending its next request
               when the last one ended: {"clients_per_slot",
               "prompt_tokens": DIST, "output_tokens": DIST, "preroll_s"}
DIST  {"dist": "lognormal", "median", "sigma", "min", "max"}
      {"dist": "uniform", "min", "max"}   {"dist": "fixed", "value"}
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, List

import numpy as np

FIRST_TOKEN_ID = 3  # ids 0..2 are left to pad/eos-like meanings


@dataclass
class Request:
    index: int
    due_s: float          # seconds after the schedule's start
    prompt: List[int]
    max_new: int
    measured: bool        # due inside the window (not the pre-roll)


def _draw(dist: Dict[str, Any], n: int, rng: np.random.RandomState
          ) -> np.ndarray:
    kind = dist["dist"]
    if kind == "fixed":
        return np.full(n, int(dist["value"]), np.int64)
    if kind == "uniform":
        return rng.randint(int(dist["min"]), int(dist["max"]) + 1, size=n)
    if kind == "lognormal":
        x = rng.lognormal(np.log(dist["median"]), dist["sigma"], size=n)
        return np.clip(np.rint(x), dist["min"], dist["max"]).astype(np.int64)
    raise ValueError(f"unknown length distribution {kind!r}")


def _gaps(arrivals: Dict[str, Any], n: int, horizon_s: float,
          rng: np.random.RandomState) -> np.ndarray:
    """n inter-arrival gaps that sum to horizon_s exactly."""
    process = arrivals["process"]
    if process == "poisson":
        g = rng.exponential(1.0, size=n)
    elif process == "gamma":  # bursty: coefficient of variation cv > 1
        shape = 1.0 / float(arrivals["cv"]) ** 2
        g = rng.gamma(shape, 1.0 / shape, size=n)
    else:
        raise ValueError(f"unknown arrival process {process!r}")
    return g * (horizon_s / g.sum())


def _prefix_pool(mix: Dict[str, Any], vocab: int,
                 base: np.random.RandomState) -> List[np.ndarray]:
    """The shared prefixes (system prompts, documents) of a mix: part of
    the fixed multiset, so drawn from the file's own seed."""
    shared = mix.get("shared_prefix")
    if not shared:
        return []
    lo, hi = shared["tokens"]
    return [
        base.randint(FIRST_TOKEN_ID, vocab, size=base.randint(lo, hi + 1))
        for _ in range(int(shared["pool"]))
    ]


def _prompts(lengths: np.ndarray, vocab: int, pool: List[np.ndarray],
             rng: np.random.RandomState) -> List[List[int]]:
    out = []
    for n in lengths:
        body = rng.randint(FIRST_TOKEN_ID, vocab, size=int(n))
        if pool:
            body = np.concatenate([pool[rng.randint(len(pool))], body])
        out.append(body.astype(np.int32).tolist())
    return out


def _phase(mix, n, horizon_s, base: np.random.RandomState,
           rng: np.random.RandomState, vocab: int, pool):
    """One phase's requests: sizes, gaps and their order from `base`, the
    token ids from `rng`."""
    plens = _draw(mix["prompt_tokens"], n, base)
    olens = _draw(mix["output_tokens"], n, base)
    gaps = _gaps(mix["arrivals"], n, horizon_s, base)
    # A request is due at the START of its gap, so the first is due at 0.
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    return due, _prompts(plens, vocab, pool, rng), olens


def open_loop_schedule(mix: Dict[str, Any], seed: int, seconds: float,
                       vocab: int) -> List[Request]:
    rate = float(mix["arrivals"]["rate_per_s"])
    pre_s = float(mix["preroll_s"])
    base = np.random.RandomState(int(mix["base_seed"]))
    rng = np.random.RandomState(int(seed) % (2**32))
    pool = _prefix_pool(mix, vocab, base)
    reqs: List[Request] = []
    for start, span, measured in ((0.0, pre_s, False),
                                  (pre_s, float(seconds), True)):
        n = max(1, int(round(rate * span)))
        due, prompts, olens = _phase(mix, n, span, base, rng, vocab, pool)
        for d, p, o in zip(due, prompts, olens):
            reqs.append(Request(len(reqs), start + float(d), p, int(o),
                                measured))
    return reqs


def closed_loop_clients(mix: Dict[str, Any], seed: int, n_clients: int,
                        per_client: int, vocab: int) -> List[List[Request]]:
    """Each client's own queue of requests (due_s unused)."""
    base = np.random.RandomState(int(mix["base_seed"]))
    rng = np.random.RandomState(int(seed) % (2**32))
    pool = _prefix_pool(mix, vocab, base)
    n = n_clients * per_client
    plens = _draw(mix["prompt_tokens"], n, base)
    olens = _draw(mix["output_tokens"], n, base)
    prompts = _prompts(plens, vocab, pool, rng)
    reqs = [Request(i, 0.0, prompts[i], int(olens[i]), True)
            for i in range(n)]
    return [reqs[c::n_clients] for c in range(n_clients)]
