"""The one traffic generator: reads a mix's parameters and the seed, and
gives every request its due time and sizes.

A mix is a data file ``bench/traffic/<mix>.json``::

    {"kind": "lm" | "image",
     "arrivals": {"process": "closed", "clients": 8}
               | {"process": "poisson", "rate": 4.0, "ramp_s": 5}
               | {"process": "mmpp", "rates": [2, 12], "dwell_s": [8, 2],
                  "ramp_s": 5},
     "<size>": {"dist": "const", "value": 64}
             | {"dist": "uniform", "min": 1, "max": 4}
             | {"dist": "lognormal", "median": 256, "sigma": 0.8,
                "min": 32, "max": 1024},
     "serve": {...engine settings of the cell...}}

Sizes are every key whose value is a distribution (``frames`` for images,
``prompt_len`` and ``output_len`` for LMs).

Steadiness across seeds: for a given length of run, every seed gets the
same multiset of sizes and of Poisson gaps (quantiles at stratified
points), in an order the seed draws.  So two seeds differ in the order
of the work, not in its amount.  The MMPP process is drawn as in
``repro.traffic.traces.bursty_trace`` (copied here so the yardstick
cannot move with the program); its arrival count varies with the seed.

Times are seconds from the window's opening; a ramp of ``ramp_s``
seconds of arrivals runs before it, so the window opens on a loaded
system.  Those requests are served but not counted in the tails.
"""

from __future__ import annotations

import dataclasses
import json
import math
import statistics
from typing import Dict, Iterator, List, Optional

import numpy as np

_NORMAL = statistics.NormalDist()
_CLOSED_BLOCK = 1024          # sizes are stratified per block of requests
_STREAMS = {"arrivals": 1, "sizes": 2, "payload": 3, "sample": 4}


def load(path: str) -> dict:
    with open(path) as f:
        mix = json.load(f)
    if mix.get("kind") not in ("lm", "image"):
        raise ValueError(f"{path}: kind must be 'lm' or 'image'")
    if mix.get("arrivals", {}).get("process") not in (
            "closed", "poisson", "mmpp"):
        raise ValueError(f"{path}: unknown arrival process")
    return mix


def rng(seed: int, stream: str) -> np.random.Generator:
    """An independent generator per purpose, from one whole-number seed
    of any size."""
    return np.random.default_rng([int(seed), _STREAMS[stream]])


def size_keys(mix: dict) -> List[str]:
    return sorted(k for k, v in mix.items()
                  if isinstance(v, dict) and "dist" in v)


def quantile(spec: dict, u: float) -> int:
    """Inverse CDF of one size distribution at ``u`` in (0, 1)."""
    d = spec["dist"]
    if d == "const":
        return int(spec["value"])
    if d == "uniform":
        lo, hi = int(spec["min"]), int(spec["max"])
        return min(hi, lo + int(u * (hi - lo + 1)))
    if d == "lognormal":
        x = math.exp(math.log(spec["median"])
                     + spec["sigma"] * _NORMAL.inv_cdf(u))
        return int(min(max(round(x), spec["min"]), spec["max"]))
    raise ValueError(f"unknown size distribution {d!r}")


def _strata(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def stratified_sizes(mix: dict, n: int, r: np.random.Generator
                     ) -> List[Dict[str, int]]:
    """``n`` size records: each key's values are its quantiles at ``n``
    stratified points, each key permuted on its own."""
    out: List[Dict[str, int]] = [{} for _ in range(n)]
    for key in size_keys(mix):
        vals = [quantile(mix[key], u) for u in _strata(n)]
        for rec, i in zip(out, r.permutation(n)):
            rec[key] = vals[i]
    return out


@dataclasses.dataclass
class Item:
    """One request of the schedule: due time (None until a closed-loop
    client sends it), its sizes, and its index (which seeds its payload)."""

    index: int
    sizes: Dict[str, int]
    due: Optional[float] = None


@dataclasses.dataclass
class Schedule:
    process: str
    clients: int                  # closed loop only, else 0
    items: List[Item]             # open loop: all, sorted by due
    mix: dict

    def closed_items(self, seed: int) -> Iterator[Item]:
        """Unbounded request stream for closed-loop clients."""
        r = rng(seed, "sizes")
        i = 0
        while True:
            for sizes in stratified_sizes(self.mix, _CLOSED_BLOCK, r):
                yield Item(index=i, sizes=sizes)
                i += 1


def _poisson_times(rate: float, horizon: float, r: np.random.Generator
                   ) -> np.ndarray:
    n = max(1, int(round(rate * horizon)))
    gaps = -np.log1p(-_strata(n)) / rate      # exponential quantiles
    return np.cumsum(gaps[r.permutation(n)])


def _mmpp_times(rates, dwell, horizon: float, r: np.random.Generator
                ) -> np.ndarray:
    """Markov-modulated Poisson arrivals (two states alternate, more
    jump uniformly), as ``repro.traffic.traces.bursty_trace``."""
    if len(rates) != len(dwell) or len(rates) < 2:
        raise ValueError("mmpp needs >= 2 (rate, dwell) pairs")
    ts: List[float] = []
    t, state = 0.0, 0
    while t < horizon:
        seg_end = min(t + float(r.exponential(dwell[state])), horizon)
        if rates[state] > 0:
            tt = t
            while True:
                tt += float(r.exponential(1.0 / rates[state]))
                if tt >= seg_end:
                    break
                ts.append(tt)
        t = seg_end
        if len(rates) == 2:
            state = 1 - state
        else:
            state = (state + 1 + int(r.integers(0, len(rates) - 1))) \
                % len(rates)
    return np.asarray(ts)


def schedule(mix: dict, seed: int, seconds: float) -> Schedule:
    """The request schedule of one run of ``seconds`` measured seconds."""
    arr = mix["arrivals"]
    proc = arr["process"]
    if proc == "closed":
        return Schedule(proc, int(arr["clients"]), [], mix)
    ramp = float(arr.get("ramp_s", 0.0))
    horizon = ramp + float(seconds)
    r = rng(seed, "arrivals")
    if proc == "poisson":
        times = _poisson_times(float(arr["rate"]), horizon, r)
    else:
        times = _mmpp_times(arr["rates"], arr["dwell_s"], horizon, r)
    times = times[times < horizon] - ramp
    sizes = stratified_sizes(mix, len(times), rng(seed, "sizes"))
    items = [Item(index=i, sizes=s, due=float(t))
             for i, (t, s) in enumerate(zip(times, sizes))]
    return Schedule(proc, 0, items, mix)
