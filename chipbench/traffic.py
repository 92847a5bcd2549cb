"""One traffic generator for every mix file under ``mixes/``.

A mix is data: an arrival process, its rates and bursts, and the length
distributions of prompts and outputs.  Every seed gets the same set of sizes
and the same set of gaps between arrivals, in another order: lengths are the
quantiles of the mix's distributions and gaps the quantiles of a unit
exponential, permuted by the seed.  So seeds change which request comes when,
never how much work a window holds.

Arrival processes:

- ``poisson``: rate ``rate`` req/s.
- ``bursty``: piecewise constant rate, ``burst_rate`` for ``burst_s``
  seconds at the start of every ``period_s``, ``base_rate`` otherwise; the
  arrival times are a Poisson process with that intensity (time rescaling).
- ``backlog``: an offline batch; ``depth`` requests always wait beyond the
  running ones, refilled as slots free.  Requests have no due time.

Times are seconds relative to the window's opening.  Segments: ``warm``
(``[-warm_in_s, 0)``), ``window`` (``[0, seconds)``) and ``tail``
(``[seconds, seconds + grace_s)``), each holding a fixed count.
"""
from __future__ import annotations

import dataclasses
import math
import statistics

import numpy as np

WARM, WINDOW, TAIL = "warm", "window", "tail"
_NORMAL = statistics.NormalDist()


@dataclasses.dataclass(frozen=True)
class Arrival:
    rid: int
    due: float | None  # seconds from window open; None for a backlog
    prompt_len: int
    max_new: int
    segment: str


def seed_rng(seed: int, stream: int) -> np.random.Generator:
    """Independent streams from one seed of any size."""
    return np.random.default_rng([int(seed) & (2**63 - 1), stream])


def length_quantiles(dist: dict, n: int) -> np.ndarray:
    """``n`` lengths at the mid quantiles of a lognormal (``median``,
    ``sigma``), clipped to [``min``, ``max``] and, with ``snap``, rounded up
    to the next allowed length (the largest where none is larger)."""
    z = np.array([_NORMAL.inv_cdf((i + 0.5) / n) for i in range(n)])
    x = float(dist["median"]) * np.exp(float(dist["sigma"]) * z)
    x = np.clip(x, dist.get("min", 1), dist.get("max", np.inf))
    if "snap" in dist:
        allowed = np.asarray(sorted(dist["snap"]))
        idx = np.minimum(np.searchsorted(allowed, x), len(allowed) - 1)
        return allowed[idx].astype(np.int64)
    return np.ceil(x).astype(np.int64)


def lengths_used(mix: dict) -> tuple[int, ...]:
    """Every prompt length the mix can send (the shapes to warm up)."""
    if "snap" in mix["prompt"]:
        return tuple(sorted(int(x) for x in mix["prompt"]["snap"]))
    raise ValueError("a mix must snap its prompt lengths to a fixed set")


class Intensity:
    """Cumulative arrival intensity Lambda(t) of a mix and its inverse."""

    def __init__(self, arr: dict):
        self.kind = arr["process"]
        if self.kind == "poisson":
            self.hi = self.lo = float(arr["rate"])
            self.period, self.burst = 1.0, 0.0
        elif self.kind == "bursty":
            self.hi, self.lo = float(arr["burst_rate"]), float(arr["base_rate"])
            self.period, self.burst = float(arr["period_s"]), float(arr["burst_s"])
        else:
            raise ValueError(f"no intensity for process {self.kind!r}")
        self.per_period = self.hi * self.burst + self.lo * (self.period - self.burst)

    def cum(self, t: float) -> float:
        k = math.floor(t / self.period)
        r = t - k * self.period
        within = self.hi * min(r, self.burst) + self.lo * max(r - self.burst, 0.0)
        return k * self.per_period + within

    def inv(self, u: float) -> float:
        k = math.floor(u / self.per_period)
        r = u - k * self.per_period
        if r <= self.hi * self.burst:
            t = r / self.hi
        else:
            t = self.burst + (r - self.hi * self.burst) / self.lo
        return k * self.period + t


def _segment(mix: dict, rng: np.random.Generator, lam: Intensity,
             t0: float, t1: float, segment: str, rid0: int) -> list[Arrival]:
    u0, u1 = lam.cum(t0), lam.cum(t1)
    n = int(round(u1 - u0))
    if n == 0:
        return []
    p = (np.arange(n) + 0.5) / n
    gaps = rng.permutation(-np.log1p(-p))
    gaps *= (u1 - u0) / gaps.sum()
    u = u0 + np.cumsum(gaps) - 0.5 * gaps
    prompts = rng.permutation(length_quantiles(mix["prompt"], n))
    outs = rng.permutation(length_quantiles(mix["output"], n))
    return [Arrival(rid0 + i, lam.inv(float(u[i])), int(prompts[i]),
                    int(outs[i]), segment) for i in range(n)]


def schedule(mix: dict, seed: int, seconds: float) -> list[Arrival]:
    """The cell's arrivals in due order (backlogs: in submission order)."""
    rng = seed_rng(seed, 1)
    arr = mix["arrivals"]
    if arr["process"] == "backlog":
        block = int(arr.get("block", 64))
        out: list[Arrival] = []
        for _ in range(int(arr["requests"]) // block):
            prompts = rng.permutation(length_quantiles(mix["prompt"], block))
            outs = rng.permutation(length_quantiles(mix["output"], block))
            out += [Arrival(len(out) + i, None, int(prompts[i]), int(outs[i]),
                            WINDOW) for i in range(block)]
        return out
    lam = Intensity(arr)
    bounds = ((-float(mix["warm_in_s"]), 0.0, WARM), (0.0, float(seconds), WINDOW),
              (float(seconds), float(seconds) + float(mix["grace_s"]), TAIL))
    out = []
    for t0, t1, seg in bounds:
        out += _segment(mix, rng, lam, t0, t1, seg, len(out))
    return out


def prompt_tokens(seed: int, arrival: Arrival, vocab: int) -> np.ndarray:
    """Token ids of one prompt, drawn from the seed and the request id."""
    rng = np.random.default_rng([int(seed) & (2**63 - 1), 2, arrival.rid])
    return rng.integers(0, vocab, arrival.prompt_len, dtype=np.int64).astype(np.int32)
