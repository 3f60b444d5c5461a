"""Per-layer quantities of one traced run (a ``harness.Reading``), shared
by the readers under ``metrics/``.  Each returns None where the run holds
nothing to read, never 0 for a share of a roofline or of a peak.
Shares are in percent."""
from __future__ import annotations

from typing import Dict, Optional, Tuple

from bench import flops
from bench import trace as tr

BAG_MODULE = "jit_embedding_bag"


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
    need = sum(flops.bag_bytes(r.cell.config, r.rows, r.valid_slots).values())
    return 100.0 * need / r.peak["hbm_bytes_per_s"] / bag


def dense_bounds(r) -> Dict[str, float]:
    """Least seconds of the window's dense work at the peak FLOP rate and
    at peak HBM bandwidth (weights read once per batch)."""
    cfg = r.cell.config
    fl = r.rows * sum(flops.dense_flops_per_row(cfg).values())
    by = (r.batches * flops.dense_weight_bytes(cfg)
          + flops.dense_activation_bytes(cfg, r.rows))
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
