"""Traffic generation for the benchmark: one generator, driven by a traffic
file and ``--seed``.

The samplers are copies of ``repro.data.queries`` (``QueryDist.sample``,
the ``poisson`` and ``bursty`` branches of ``ArrivalProcess``,
``zipf_indices``), kept here so that no change to the program can move
the yardstick; a family draws its payload rows
(``families/<family>.make_rows``: DLRM's copies ``dlrm_batch``).  They take a
``numpy.random.Generator``, which accepts any whole-number seed, where the
originals take a ``RandomState`` (seeds below 2**32 only); uniform rows are
drawn directly instead of hashing uniform raw ids, which gives the same
distribution.

Every seed gets the same amount of work.  The traffic file's
``base_seed`` fixes the request sizes and, for open traffic, the arrival
times and which size arrives when; ``--seed`` draws the payload rows
(and, for backlog traffic, the order of the sizes in each chunk), so two
seeds differ in which rows they send, not in how many rows or requests a
window holds or when they arrive.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Iterator, List

import numpy as np

# bursty arrivals: mean episode length, in arrivals (queries.py's value)
BURST_EPISODE_MEAN = 8.0


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """An independent generator per (seed, stream): stream 0 draws the
    payload rows, 1 where requests take them from (and the order of a
    backlog chunk's sizes), 2 the correctness sample."""
    return np.random.default_rng([int(seed) & (2 ** 64 - 1), int(stream)])


# ------------------------------------------------------------- samplers
def query_sizes(rng: np.random.Generator, n: int, mean_size: float,
                sigma: float, max_size: int) -> np.ndarray:
    """Heavy-tailed candidate counts: lognormal with the given mean,
    rounded up and clipped to [1, max_size] (``QueryDist.sample``)."""
    mu = np.log(mean_size) - 0.5 * sigma ** 2
    s = rng.lognormal(mu, sigma, size=n)
    return np.clip(np.ceil(s), 1, max_size).astype(np.int64)


class Arrivals:
    """Inter-arrival gaps of ``ArrivalProcess``: ``poisson`` draws
    exponential gaps at mean ``gap_s``; ``bursty`` alternates burst and
    lull episodes of geometric length (mean ``BURST_EPISODE_MEAN``
    arrivals) at ``gap_s / burstiness`` and ``gap_s * burstiness``."""

    def __init__(self, kind: str, gap_s: float, rng: np.random.Generator,
                 burstiness: float = 4.0):
        if kind not in ("poisson", "bursty"):
            raise ValueError(f"unknown arrival process {kind!r}")
        if burstiness < 1.0:
            raise ValueError(f"burstiness must be >= 1, got {burstiness}")
        self.kind, self.gap_s, self.rng = kind, float(gap_s), rng
        self.burstiness = float(burstiness)
        self._burst, self._left = True, 0

    def _mean_gap(self) -> float:
        if self.kind == "poisson":
            return self.gap_s
        if self._left <= 0:
            self._burst = not self._burst
            self._left = 1 + int(self.rng.geometric(1.0 / BURST_EPISODE_MEAN))
        self._left -= 1
        return (self.gap_s / self.burstiness if self._burst
                else self.gap_s * self.burstiness)

    def next_gap(self) -> float:
        return float(self.rng.exponential(self._mean_gap()))


def zipf_indices(rng: np.random.Generator, shape, num_rows: int,
                 alpha: float) -> np.ndarray:
    """Row ids with P(rank k) ~ 1/(k+1)^alpha, row id == rank."""
    w = 1.0 / np.arange(1, num_rows + 1, dtype=np.float64) ** alpha
    cdf = np.cumsum(w)
    cdf /= cdf[-1]
    return np.searchsorted(cdf, rng.uniform(size=shape),
                           side="right").astype(np.int32)


# ---------------------------------------------------------------- plans
@dataclass(frozen=True)
class Part:
    """One request: ``size`` consecutive rows of the payload pool from
    ``offset``, due ``arrival`` seconds after the window opens."""
    offset: int
    size: int
    arrival: float


def _fill(sizes: np.ndarray, rows: int) -> List[int]:
    """Sizes taken in order until they hold exactly ``rows`` rows (the
    last one cut), repeating the list if it runs out."""
    out, left, i = [], rows, 0
    while left > 0:
        s = int(min(sizes[i % len(sizes)], left))
        out.append(s)
        left -= s
        i += 1
    return out


def backlog_chunks(traffic: Dict, seed: int) -> Iterator[List[Part]]:
    """Chunks of queued requests, each of exactly ``chunk_rows`` rows, all
    due at once, without end.  The sizes of one chunk are drawn from
    ``base_seed``; each chunk takes them in an order, and its rows at
    offsets, drawn from ``seed``."""
    base = np.random.default_rng(int(traffic["base_seed"]))
    sizes = query_sizes(base, 4 * traffic["chunk_rows"], traffic["mean_size"],
                        traffic["sigma"], traffic["max_size"])
    sizes = np.asarray(_fill(sizes, traffic["chunk_rows"]))
    rng = rng_for(seed, 1)
    pool = traffic["pool_rows"]
    while True:
        order = rng.permutation(sizes)
        offs = rng.integers(0, pool - order + 1)
        yield [Part(int(o), int(s), 0.0) for o, s in zip(offs, order)]


def open_schedule(traffic: Dict, seed: int, seconds: float) -> List[Part]:
    """Open-loop requests over ``[0, seconds)``: the arrival times the
    process draws from ``base_seed`` until the window is full, and a size
    for each, the same for every seed; ``seed`` draws where in the payload
    pool each request's rows lie.  Queueing, which sets the tail, is the
    same work for every seed."""
    base = np.random.default_rng(int(traffic["base_seed"]))
    proc = Arrivals(traffic.get("arrival", "poisson"),
                    1.0 / traffic["rate_qps"], base,
                    traffic.get("burstiness", 4.0))
    arrivals, t = [], proc.next_gap()
    while t < seconds:
        arrivals.append(t)
        t += proc.next_gap()
    sizes = query_sizes(base, len(arrivals), traffic["mean_size"],
                        traffic["sigma"], traffic["max_size"])
    offs = rng_for(seed, 1).integers(0, traffic["pool_rows"] - sizes + 1)
    return [Part(int(o), int(s), float(a))
            for o, s, a in zip(offs, sizes, arrivals)]
