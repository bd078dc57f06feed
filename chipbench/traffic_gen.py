"""The one general traffic generator: a traffic file's parameters in, a
request trace out. Sizes and the gaps between arrivals belong to the traffic
mix: they are drawn once from the file's ``sizes_seed``, in one order, and
every run offers them so. ``--seed`` gives the token ids (and, in the driver,
the weights). So two seeds do the same work at the same times; what is left
between them is the system's own timing. (PR 25 tried the same requests in a
rotated order per seed: completed tokens/s then moved +-1.4% and the 95th
percentile of time to first token +-3.5% with the seed alone, by what was in
flight when the window closed.)

Parameters (``chipbench/traffic/<name>.json``):
  rate              requests per second offered (open loop)
  arrival           {"process": "poisson"}
  prompt / answer   {"dist": "lognormal", "median": m, "sigma": s,
                     "min": lo, "max": hi}   token counts, clipped
  sizes_seed        seed of the sizes and gaps
Arrival gaps reproduce ``autotuning/trace.py poisson_arrivals`` (cumulative
exponential gaps), conditioned on the count: n = round(rate * seconds)
requests, all due inside the window.
"""

from __future__ import annotations

from typing import Dict, List

import numpy as np


def lengths(rng: np.random.Generator, n: int, spec: dict) -> np.ndarray:
    if spec.get("dist", "lognormal") != "lognormal":
        raise ValueError(f"unknown length distribution {spec.get('dist')!r}")
    raw = rng.lognormal(np.log(spec["median"]), spec["sigma"], size=n)
    return np.clip(np.rint(raw), spec["min"], spec["max"]).astype(int)


def gaps(rng: np.random.Generator, n: int, rate: float, spec: dict) -> np.ndarray:
    process = spec.get("process", "poisson")
    if process == "poisson":
        return rng.exponential(1.0 / rate, size=n)
    raise ValueError(f"unknown arrival process {process!r}")


def serve_trace(params: dict, seed: int, seconds: float, vocab: int,
                rate: float = None) -> Dict[str, List]:
    """``{"prompts": [[ids]], "max_new": [n], "arrivals": [s]}``: n requests
    due inside ``seconds``. ``rate`` overrides the file's (the sweep)."""
    rate = float(params["rate"] if rate is None else rate)
    n = max(1, int(round(rate * seconds)))
    # three streams of their own, so that the requests of a shorter window
    # or a lower rate are a prefix of those of a longer or higher one
    base = int(params.get("sizes_seed", 0))
    stream = [np.random.default_rng([base, i]) for i in range(3)]
    plen = lengths(stream[0], n, params["prompt"])
    alen = lengths(stream[1], n, params["answer"])
    gap = gaps(stream[2], n, rate, params.get("arrival", {}))
    # all n due inside the window, the last one just before it closes
    gap *= seconds * n / (n + 1) / gap.sum()
    own = np.random.default_rng(int(seed))
    prompts = [own.integers(1, vocab, size=int(k)).tolist() for k in plen]
    return {"prompts": prompts, "max_new": [int(k) for k in alen],
            "arrivals": np.cumsum(gap).tolist()}
