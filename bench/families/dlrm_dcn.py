"""DLRM-DCNv2 (MLPerf ``torchrec_dlrm`` / ``dlrm_v2``; torchrec
``DLRM_DCN`` with ``LowRankCrossNet``; DCN-V2, Wang et al., arXiv
2008.13535), as ``repro.models.dlrm`` serves it with ``interaction`` "dcn".

The configuration's keys are the MLPerf run command's flags: T tables of
``num_embeddings_per_feature[t]`` rows (as held: each at most
``row_cap``) of ``embedding_dim`` (D); table t's bag is always full, with
``multi_hot_sizes[t]`` (P_t) lookups pooled by sum; ``num_dense_features``
(F) dense inputs into ``dense_arch_layer_sizes`` (ReLU after every layer);
``dcn_num_layers`` cross layers of rank ``dcn_low_rank_dim`` over x0 =
[bottom, pooled_1..T] of width W = (T + 1) D:

    x_{l+1} = x0 * (W_l (V_l x_l) + b_l) + x_l

then ``over_arch_layer_sizes`` (ReLU after all but the last) and a
sigmoid.  Bytes are fp32 (4 B).

Weights (``leaves``): table t N(0, 1/P_t), so that each pooled vector has
unit variance and a fault in any bag shows; the bottom MLP as
``weights.mlp_leaves``; the cross layers V_l N(0, 1/W) and W_l
N(0, 1/(4 r)), so that a layer's correction x0 * (W V x) is about half of
x_l's scale, biases N(0, 0.01^2); the top MLP as ``weights.mlp_leaves``
with its hidden layers at N(0, 2/fan_in), which keeps the scale through
their ReLUs.  The reference's scores then spread and do not saturate: on
512 rows of the chip configuration's widths (tables cut to 2,000 rows),
std 0.136, from 0.016 to 0.762, logits' std 0.93.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

from bench import gen
from bench.flops import F32, matmul_flops
from bench.weights import mlp_leaves


def model_config(cfg: Dict):
    from repro.configs.base import DLRMConfig, ModelConfig
    rows, bags = cfg["num_embeddings_per_feature"], cfg["multi_hot_sizes"]
    return ModelConfig(
        name=cfg["name"], family="dlrm", num_layers=0, num_heads=0,
        num_kv_heads=0, d_ff=0, vocab_size=0, d_model=cfg["embedding_dim"],
        dlrm=DLRMConfig(
            num_tables=len(rows), rows_per_table=max(rows),
            embed_dim=cfg["embedding_dim"], avg_pooling=max(bags),
            num_dense_features=cfg["num_dense_features"],
            bottom_mlp=tuple(cfg["dense_arch_layer_sizes"]),
            top_mlp=tuple(cfg["over_arch_layer_sizes"]),
            table_rows=tuple(rows), bag_lengths=tuple(bags),
            interaction="dcn", dcn_num_layers=cfg["dcn_num_layers"],
            dcn_low_rank_dim=cfg["dcn_low_rank_dim"]))


# ---------------------------------------------------------------- weights
def _width(cfg: Dict) -> int:
    """W: the cross network's width, the bottom output and T pooled
    vectors."""
    return (cfg["dense_arch_layer_sizes"][-1]
            + len(cfg["multi_hot_sizes"]) * cfg["embedding_dim"])


def _mlp_dims(cfg: Dict) -> Dict[str, List[int]]:
    return {"bottom": ([cfg["num_dense_features"]]
                       + list(cfg["dense_arch_layer_sizes"])),
            "top": [_width(cfg)] + list(cfg["over_arch_layer_sizes"])}


def leaves(cfg: Dict):
    """``embed`` ``t<t>`` (R_t, D) for each table, ``bottom`` MLP,
    ``cross`` ``v<l>`` (W, r), ``w<l>`` (r, W), ``b<l>`` (W,), ``top``
    MLP; scales in the module's docstring."""
    D, W, r = cfg["embedding_dim"], _width(cfg), cfg["dcn_low_rank_dim"]
    dims = _mlp_dims(cfg)
    hidden = {f"w{i}" for i in range(len(dims["top"]) - 2)}
    cross = []
    for i in range(cfg["dcn_num_layers"]):
        cross += [(f"v{i}", (W, r), W ** -0.5),
                  (f"w{i}", (r, W), (4 * r) ** -0.5),
                  (f"b{i}", (W,), 0.01)]
    return ([(("embed", f"t{t}"), (rows, D), p ** -0.5)
             for t, (rows, p) in enumerate(zip(
                 cfg["num_embeddings_per_feature"], cfg["multi_hot_sizes"]))]
            + [(("bottom", n), s, sc) for n, s, sc in
               mlp_leaves(dims["bottom"])]
            + [(("cross", n), s, sc) for n, s, sc in cross]
            + [(("top", n), s, sc * 2 ** 0.5 if n in hidden else sc)
               for n, s, sc in mlp_leaves(dims["top"])])


# ------------------------------------------------------------------ rows
def make_rows(cfg: Dict, n: int, rng: np.random.Generator, traffic: Dict
              ) -> Tuple[np.ndarray, np.ndarray]:
    """Dense features (n, F) fp32 and indices (n, T, max P_t) int32:
    table t's bag holds exactly P_t ids and -1 after them; ids uniform
    over the table's rows, or Zipf with the traffic's ``alpha`` > 0."""
    rows, bags = cfg["num_embeddings_per_feature"], cfg["multi_hot_sizes"]
    alpha = traffic["alpha"]
    dense = rng.standard_normal((n, cfg["num_dense_features"]),
                                dtype=np.float32)
    idx = np.full((n, len(rows), max(bags)), -1, np.int32)
    for t, (r, p) in enumerate(zip(rows, bags)):
        idx[:, t, :p] = (gen.zipf_indices(rng, (n, p), r, alpha)
                         if alpha > 0.0 else
                         rng.integers(0, r, size=(n, p), dtype=np.int32))
    return dense, idx


# ---------------------------------------------------------------- counts
def dense_flops_per_row(cfg: Dict) -> Dict[str, int]:
    """FLOPs of the dense tower per row, by part: the two MLPs and the
    cross layers' two low-rank matmuls each (4 W r a layer; the
    elementwise product and sums, 3 W a layer, are left out)."""
    dims = _mlp_dims(cfg)
    return {"bottom_mlp": matmul_flops(dims["bottom"]),
            "cross": (cfg["dcn_num_layers"] * 4 * _width(cfg)
                      * cfg["dcn_low_rank_dim"]),
            "top_mlp": matmul_flops(dims["top"])}


def dense_weight_bytes(cfg: Dict) -> int:
    """Bytes of the dense tower's weights: both MLPs and every cross
    layer (V, W and b), weights and biases."""
    W, r = _width(cfg), cfg["dcn_low_rank_dim"]
    n = cfg["dcn_num_layers"] * (2 * W * r + W)
    for dims in _mlp_dims(cfg).values():
        n += sum(a * b + b for a, b in zip(dims[:-1], dims[1:]))
    return n * F32


def dense_activation_bytes(cfg: Dict, rows: int) -> int:
    """Bytes the dense tower must read and write per call for ``rows``
    rows: dense features and pooled (T, D) vectors in, one score out."""
    per_row = (cfg["num_dense_features"]
               + len(cfg["multi_hot_sizes"]) * cfg["embedding_dim"] + 1)
    return rows * per_row * F32


def bag_bytes(cfg: Dict, rows: int, valid_slots: float) -> Dict[str, float]:
    """Bytes the embedding bags must move for ``rows`` rows: every valid
    slot's table row read, each table's P_t index slots read (not the
    block's padding), and the pooled (T, D) vectors written."""
    D, bags = cfg["embedding_dim"], cfg["multi_hot_sizes"]
    return {"rows": float(valid_slots) * D * F32,
            "indices": float(rows) * sum(bags) * 4,
            "pooled": float(rows) * len(bags) * D * F32}
