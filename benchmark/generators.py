"""The one general generator of serving traffic. A traffic mix is a data file
of parameters (`benchmark/traffic/<name>.json`); this reads it.

Open loop: requests are due on a schedule whatever the server does, and each
is timed from when it was due. Copied in outline from `tools/bench_serve.py`
(`make_trace`: seeded Poisson arrivals, random token ids, a list of dicts with
`arrival_s`, `prompt`, `max_new_tokens`, `tenant`), with two changes:

- lengths are log-normal (median, sigma, cut to [min, max]) as chat traffic
  is, not drawn from a short list;
- every seed gets the SAME multiset of arrival gaps, prompt lengths and output
  lengths (the distributions' quantiles at (i + 0.5) / n) and draws their
  order and the token ids: the same work in another order, as a training
  cell's seed draws the rows of batches of one shape. That evens out over a
  window of some hundreds of requests; with tens the order is the work, and
  such a window is too short for a tail.
"""

from __future__ import annotations

import math
import statistics

import numpy as np


def _lognormal_quantiles(n: int, spec: dict) -> np.ndarray:
    nd = statistics.NormalDist()
    z = np.array([nd.inv_cdf((i + 0.5) / n) for i in range(n)])
    x = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(int)


def make_requests(traffic: dict, vocab: int, seed: int,
                  horizon_s: float) -> list:
    """Requests due in [0, horizon_s), sorted by `arrival_s`."""
    rate = float(traffic["rate_rps"])
    n = max(1, int(math.ceil(rate * horizon_s)))
    rng = np.random.default_rng(seed)
    u = (np.arange(n) + 0.5) / n
    gaps = rng.permutation(-np.log1p(-u) / rate)  # exponential quantiles
    arrivals = np.cumsum(gaps) - gaps[0]
    prompts = rng.permutation(_lognormal_quantiles(n, traffic["prompt"]))
    outputs = rng.permutation(_lognormal_quantiles(n, traffic["output"]))
    out = []
    for i in range(n):
        if arrivals[i] >= horizon_s:
            break
        out.append({"arrival_s": float(arrivals[i]),
                    "prompt": [int(x) for x in
                               rng.integers(1, vocab, int(prompts[i]))],
                    "max_new_tokens": int(outputs[i]),
                    "tenant": "default"})
    return out
