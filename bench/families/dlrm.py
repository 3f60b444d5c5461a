"""DLRM with a projected dot interaction (DisaggRec Fig. 1a; Naumov et
al., arXiv 1906.00091), as ``repro.models.dlrm`` serves it.

The configuration's keys: ``num_tables`` (T) tables of
``rows_per_table`` (R) rows of ``embed_dim`` (D); bags of up to
``avg_pooling`` (P) rows each; ``num_dense_features`` (F) dense inputs
into ``bottom_mlp``; the T pooled vectors projected onto
``interaction_proj`` (K) channels; the pairwise dot products of the
bottom output and the K channels into ``top_mlp``.  Bytes are fp32
(4 B), the type every table, weight and activation is served in.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

from bench import gen
from bench.flops import F32, matmul_flops
from bench.weights import mlp_leaves


def model_config(cfg: Dict):
    from repro.configs.base import DLRMConfig, ModelConfig
    return ModelConfig(
        name=cfg["name"], family="dlrm", num_layers=0, num_heads=0,
        num_kv_heads=0, d_ff=0, vocab_size=0, d_model=cfg["embed_dim"],
        dlrm=DLRMConfig(
            num_tables=cfg["num_tables"],
            rows_per_table=cfg["rows_per_table"],
            embed_dim=cfg["embed_dim"], avg_pooling=cfg["avg_pooling"],
            num_dense_features=cfg["num_dense_features"],
            bottom_mlp=tuple(cfg["bottom_mlp"]),
            top_mlp=tuple(cfg["top_mlp"]),
            interaction_proj=cfg["interaction_proj"]))


# ---------------------------------------------------------------- weights
def _features(cfg: Dict) -> int:
    """Interaction features: the bottom-MLP output and the projected
    channels."""
    return cfg["interaction_proj"] + 1


def _mlp_dims(cfg: Dict) -> Dict[str, List[int]]:
    f = _features(cfg)
    bottom = [cfg["num_dense_features"]] + list(cfg["bottom_mlp"])
    top = [cfg["bottom_mlp"][-1] + f * (f - 1) // 2] + list(cfg["top_mlp"])
    return {"bottom": bottom, "top": top}


def leaves(cfg: Dict):
    """``embed`` (T, R, D), ``proj`` (T, K), ``bottom`` and ``top`` MLPs of
    ``w<i>``/``b<i>``.  Tables N(0, 1/P) (a pooled vector of about 0.7 P
    valid rows), projection N(0, 1/T); see ``weights`` for the MLPs."""
    T, R, D = cfg["num_tables"], cfg["rows_per_table"], cfg["embed_dim"]
    K, P = cfg["interaction_proj"], cfg["avg_pooling"]
    dims = _mlp_dims(cfg)
    return ([(("embed",), (T, R, D), P ** -0.5),
             (("proj",), (T, K), T ** -0.5)]
            + [((part, n), s, sc) for part in ("bottom", "top")
               for n, s, sc in mlp_leaves(dims[part])])


# ------------------------------------------------------------------ rows
def make_rows(cfg: Dict, n: int, rng: np.random.Generator, traffic: Dict
              ) -> Tuple[np.ndarray, np.ndarray]:
    """Dense features (n, F) fp32 and indices (n, T, P) int32, each bag
    holding a lognormal number of valid rows (median 0.7 P, sigma the
    traffic's ``pooling_sigma``) and -1 after them; rows uniform, or Zipf
    with the traffic's ``alpha`` > 0 (``dlrm_batch``)."""
    T, P, R = cfg["num_tables"], cfg["avg_pooling"], cfg["rows_per_table"]
    alpha = traffic["alpha"]
    dense = rng.standard_normal((n, cfg["num_dense_features"]),
                                dtype=np.float32)
    if alpha > 0.0:
        idx = gen.zipf_indices(rng, (n, T, P), R, alpha)
    else:
        idx = rng.integers(0, R, size=(n, T, P), dtype=np.int32)
    lens = np.clip(rng.lognormal(np.log(max(P * 0.7, 1.0)),
                                 traffic["pooling_sigma"], size=(n, T)),
                   1, P)
    idx[np.arange(P)[None, None, :] >= lens[..., None]] = -1
    return dense, idx


# ---------------------------------------------------------------- counts
def dense_flops_per_row(cfg: Dict) -> Dict[str, int]:
    """FLOPs of the dense tower per row, by part: the two MLPs, the
    projection of the T pooled vectors onto K channels (2 T K D), and the
    (K+1) x (K+1) interaction matrix as the tower computes it (2 F^2 D)."""
    T, D, K = cfg["num_tables"], cfg["embed_dim"], cfg["interaction_proj"]
    dims = _mlp_dims(cfg)
    return {"bottom_mlp": matmul_flops(dims["bottom"]),
            "proj": 2 * T * K * D,
            "interaction": 2 * _features(cfg) ** 2 * D,
            "top_mlp": matmul_flops(dims["top"])}


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
