"""Operations and bytes of the served work, counted from shapes, and the
table of peaks (``peaks.json``) they are held against.

A configuration here is the dict of a ``configs/<name>.json`` file; the
counts that depend on its model come from its family
(``families/<family>.py``).  Counts are per candidate row unless a name
says otherwise; bytes are fp32 (4 B), the type every table, weight and
activation is served in.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict, List

from bench.families import family

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"
F32 = 4


def peaks(device_kind: str) -> Dict:
    """Published peaks of one chip of ``device_kind``; a kind that is not
    in the table is an error, never a default."""
    table = json.loads(PEAKS_FILE.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS_FILE.name} (known: {sorted(table)})")
    return table[device_kind]


def matmul_flops(dims: List[int]) -> int:
    """FLOPs of one row through an MLP of widths ``dims``."""
    return sum(2 * a * b for a, b in zip(dims[:-1], dims[1:]))


def pooling_flops(cfg: Dict, valid_slots: float) -> float:
    """Adds of the embedding bags: one D-wide add per valid slot."""
    return float(valid_slots) * cfg["embed_dim"]


def model_flops(cfg: Dict, rows: int, valid_slots: float) -> float:
    """Model FLOPs of ``rows`` served rows holding ``valid_slots`` valid
    bag slots in all: the dense tower plus the pooling adds."""
    return (rows * sum(family(cfg).dense_flops_per_row(cfg).values())
            + pooling_flops(cfg, valid_slots))
