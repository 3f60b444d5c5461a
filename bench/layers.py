"""Per-layer quantities of one traced run (a ``harness.Reading``), shared
by the readers under ``metrics/``.  Each returns None where the run holds
nothing to read, never 0 for a share of a roofline or of a peak.
Shares are in percent."""
from __future__ import annotations

import bisect
import math
from typing import Dict, List, Optional, Sequence, Tuple

from bench import flops
from bench import trace as tr
from bench.families import family

BAG_MODULE = "jit_embedding_bag"
# the program's leaf spans, grouped by the layer whose device-idle time
# they hold; the leaves of one batch never overlap
GROUPS: Dict[str, Tuple[str, ...]] = {
    "scatter_idle_ms": ("repro.route", "repro.scatter"),
    "gather_idle_ms": ("repro.gather", "repro.dense"),
    "bookkeeping_idle_ms": ("repro.assemble", "repro.account",
                            "repro.clock", "repro.complete", "repro.stats"),
}


def _split_modules(r) -> Tuple[float, float]:
    """Device seconds of the bag modules, and of every other module of the
    window (the dense step, found by elimination)."""
    bag = dense = 0.0
    for name, s in r.modules().items():
        if name.startswith(BAG_MODULE):
            bag += s
        else:
            dense += s
    return bag, dense


def bag_roofline(r) -> Optional[float]:
    """Least time the bags' bytes take at peak HBM bandwidth, over the
    bag modules' device time."""
    bag, _ = _split_modules(r)
    if bag <= 0 or r.rows <= 0:
        return None
    cfg = r.cell.config
    need = sum(family(cfg).bag_bytes(cfg, r.rows, r.valid_slots).values())
    return 100.0 * need / r.peak["hbm_bytes_per_s"] / bag


def dense_bounds(r) -> Dict[str, float]:
    """Least seconds of the window's dense work at the peak FLOP rate and
    at peak HBM bandwidth (weights read once per batch)."""
    cfg = r.cell.config
    fam = family(cfg)
    fl = r.rows * sum(fam.dense_flops_per_row(cfg).values())
    by = (r.batches * fam.dense_weight_bytes(cfg)
          + fam.dense_activation_bytes(cfg, r.rows))
    return {"flops": fl / r.peak["bf16_flops_per_s"],
            "bytes": by / r.peak["hbm_bytes_per_s"]}


def dense_roofline(r) -> Optional[float]:
    _, dense = _split_modules(r)
    if dense <= 0 or r.rows <= 0:
        return None
    return 100.0 * max(dense_bounds(r).values()) / dense


def host_ms_per_batch(r) -> Optional[float]:
    """Milliseconds per batch inside serve() with no operation on the
    device."""
    serve = tr.merge(r.trace.span("bench.serve"))
    if not serve or r.batches <= 0:
        return None
    inside = sum(e - s for s, e in serve) / 1e9
    return 1e3 * (inside - tr.busy_within_s(r.trace, serve)) / r.batches


def idle_share(r) -> Optional[float]:
    window = (r.hi - r.lo) / 1e9
    if window <= 0 or not r.trace.busy:
        return None
    return 100.0 * (1.0 - tr.busy_s(r.trace, r.lo, r.hi) / window)


def mfu(r) -> Optional[float]:
    """Model FLOPs of the rows served over the host time inside serve(),
    as a share of the chip's peak."""
    if r.serve_s <= 0 or r.rows <= 0:
        return None
    fl = flops.model_flops(r.cell.config, r.rows, r.valid_slots)
    return 100.0 * fl / r.serve_s / r.peak["bf16_flops_per_s"]


def batch_fill(r) -> Optional[float]:
    if r.batches <= 0:
        return None
    return 100.0 * r.rows / (r.batches * r.cell.pool["batch_size"])


def nearest_rank(values: Sequence[float], q: float) -> float:
    s = sorted(values)
    return s[max(0, math.ceil(q * len(s)) - 1)]


def latency_ms(r, q: float) -> Optional[float]:
    """The ``q`` quantile (nearest rank) of the completed requests'
    latencies, from scheduled arrival to the return of their serve()
    call."""
    if not r.latency_s:
        return None
    return 1e3 * nearest_rank(r.latency_s, q)


def bag_slot_fill(r) -> Optional[float]:
    """Valid bag slots over the slots the bag kernels walked in the window
    (the engine's counters)."""
    slots = r.counters.get("bag_slots", 0)
    if slots <= 0:
        return None
    return 100.0 * r.counters["bag_slots_valid"] / slots


# ------------------------------------------------- device idle in spans
def within(merged: Sequence[tr.Interval], lo: float, hi: float
           ) -> List[tr.Interval]:
    """The parts of ``merged`` (sorted, disjoint) inside ``[lo, hi]``; a
    bisection finds the first, so many short windows over a long trace
    stay cheap."""
    out = []
    k = max(0, bisect.bisect_right(merged, (lo, float("inf"))) - 1)
    for s, e in merged[k:]:
        if s >= hi:
            break
        if e > lo:
            out.append((max(s, lo), min(e, hi)))
    return out


def covered(merged: Sequence[tr.Interval], lo: float, hi: float) -> float:
    """Length of ``[lo, hi]`` that ``merged`` (sorted, disjoint) covers."""
    return sum(e - s for s, e in within(merged, lo, hi))


def idle_s(t: tr.Trace, windows: Sequence[tr.Interval]) -> float:
    """Seconds inside the union of ``windows`` with no operation on the
    device, averaged over the devices traced (all of it without one)."""
    inside = tr.merge(windows)
    held = sum(e - s for s, e in inside)
    busy = (sum(covered(b, s, e) for b in t.busy for s, e in inside)
            / len(t.busy) if t.busy else 0.0)
    return (held - busy) / 1e9


def span_idle_ms(r, group: str) -> Optional[float]:
    """Milliseconds per batch with no operation on the device inside the
    union of the window's program spans of ``GROUPS[group]``."""
    names = GROUPS[group]
    windows = [(s, e) for n, s, e in r.spans if n in names]
    if not windows or r.batches <= 0:
        return None
    return 1e3 * idle_s(r.trace, windows) / r.batches
