"""Operations and bytes of the served work, counted from shapes, and the
table of peaks (``peaks.json``) they are held against.

A DLRM configuration here is the dict of a ``configs/<name>.json`` file.
Counts are per candidate row unless a name says otherwise; bytes are
fp32 (4 B), the type every table, weight and activation is served in.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict, List

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


def _features(cfg: Dict) -> int:
    """Interaction features: the bottom-MLP output and the projected
    channels."""
    return cfg["interaction_proj"] + 1


def _mlp_dims(cfg: Dict) -> Dict[str, List[int]]:
    f = _features(cfg)
    bottom = [cfg["num_dense_features"]] + list(cfg["bottom_mlp"])
    top = [cfg["bottom_mlp"][-1] + f * (f - 1) // 2] + list(cfg["top_mlp"])
    return {"bottom": bottom, "top": top}


def _matmul_flops(dims: List[int]) -> int:
    return sum(2 * a * b for a, b in zip(dims[:-1], dims[1:]))


def dense_flops_per_row(cfg: Dict) -> Dict[str, int]:
    """FLOPs of the dense tower per row, by part: the two MLPs, the
    projection of the T pooled vectors onto K channels (2 T K D), and the
    (K+1) x (K+1) interaction matrix as the tower computes it (2 F^2 D)."""
    T, D, K = cfg["num_tables"], cfg["embed_dim"], cfg["interaction_proj"]
    dims = _mlp_dims(cfg)
    return {"bottom_mlp": _matmul_flops(dims["bottom"]),
            "proj": 2 * T * K * D,
            "interaction": 2 * _features(cfg) ** 2 * D,
            "top_mlp": _matmul_flops(dims["top"])}


def pooling_flops(cfg: Dict, valid_slots: float) -> float:
    """Adds of the embedding bags: one D-wide add per valid slot."""
    return float(valid_slots) * cfg["embed_dim"]


def model_flops(cfg: Dict, rows: int, valid_slots: float) -> float:
    """Model FLOPs of ``rows`` served rows holding ``valid_slots`` valid
    bag slots in all: the dense tower plus the pooling adds."""
    return (rows * sum(dense_flops_per_row(cfg).values())
            + pooling_flops(cfg, valid_slots))


def dense_weight_bytes(cfg: Dict) -> int:
    """Bytes of the dense tower's weights: both MLPs (weights and biases)
    and the (T, K) projection."""
    n = cfg["num_tables"] * cfg["interaction_proj"]
    for dims in _mlp_dims(cfg).values():
        n += sum(a * b + b for a, b in zip(dims[:-1], dims[1:]))
    return n * F32


def dense_activation_bytes(cfg: Dict, rows: int) -> int:
    """Bytes the dense tower must read and write per call for ``rows``
    rows: dense features and pooled (T, D) vectors in, one score out."""
    per_row = (cfg["num_dense_features"]
               + cfg["num_tables"] * cfg["embed_dim"] + 1)
    return rows * per_row * F32


def bag_bytes(cfg: Dict, rows: int, valid_slots: float) -> Dict[str, float]:
    """Bytes the embedding bags must move for ``rows`` rows: every valid
    slot's table row read, the (T, P) index block read, and the pooled
    (T, D) vectors written."""
    T, P, D = cfg["num_tables"], cfg["avg_pooling"], cfg["embed_dim"]
    return {"rows": float(valid_slots) * D * F32,
            "indices": float(rows) * T * P * 4,
            "pooled": float(rows) * T * D * F32}
