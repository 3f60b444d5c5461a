"""RM1 — the paper's memory-intensive recommendation model (Fig. 1).

SparseNet is the growth driver: model size 1.4 TB (V0) -> 7.8 TB (V5)
over six generations / three years. Dense compute grows mildly.
Sizes are synthetic-projection endpoints from the paper; intermediate
generations interpolate geometrically (x~1.41/gen).
"""
import dataclasses

from repro.configs.base import DLRMConfig, ModelConfig

_EMBED_DIM = 128
_BYTES = 4  # fp32 tables, as served in the paper's production stack

# (num_tables, mean_rows, avg_pooling) per generation V0..V5;
# chosen so tables*rows*dim*4B hits the Fig.1(b) curve 1.4 -> 7.8 TB.
_GENS = [
    (800,  3_417_969, 80),    # V0: 1.40 TB
    (900,  4_305_004, 90),    # V1: ~1.98 TB
    (1000, 5_464_438, 100),   # V2: ~2.80 TB
    (1200, 6_442_020, 110),   # V3: ~3.96 TB
    (1400, 7_812_500, 125),   # V4: ~5.60 TB
    (1600, 9_536_743, 140),   # V5: 7.81 TB
]

_BOTTOM = (512, 256, 128)
_TOP = (1024, 1024, 512, 256, 1)


def generation(v: int) -> ModelConfig:
    tables, rows, pooling = _GENS[v]
    return ModelConfig(
        name=f"rm1.v{v}",
        family="dlrm",
        num_layers=0, num_heads=0, num_kv_heads=0, d_ff=0, vocab_size=0,
        d_model=_EMBED_DIM,
        dlrm=DLRMConfig(
            num_tables=tables, rows_per_table=rows, embed_dim=_EMBED_DIM,
            avg_pooling=pooling, num_dense_features=256,
            bottom_mlp=_BOTTOM, top_mlp=_TOP,
        ),
    )


def size_bytes(v: int) -> int:
    tables, rows, _ = _GENS[v]
    return tables * rows * _EMBED_DIM * _BYTES


CONFIG = generation(0)
GENERATIONS = [generation(v) for v in range(6)]

# Chip-share RM1: RM1 V0 (source: DisaggRec Fig. 1b, V0 = 1.4 TB of fp32
# tables) at every published width — 800 tables, embed_dim 128,
# avg_pooling 80, 256 dense features, bottom MLP (512, 256, 128), top MLP
# (1024, 1024, 512, 256, 1), interaction_proj 64, fp32.
# reduced: rows_per_table 3,417,969 -> 4,096.
# Deployment: one TPU v5e (16 GB HBM) holding one chip's share of a 4-MN
# pool — the whole {CN, MN} cluster of ClusterEngine runs on that chip.
# Tables are 800 x 4,096 x 128 x 4 B = 1.68 GB; the engine keeps the
# (T, R, D) stack plus n_replicas=2 shard copies, about 5 GB in all.
CHIP_SHARE = CONFIG.replace(
    name="rm1-chip",
    dlrm=dataclasses.replace(CONFIG.dlrm, rows_per_table=4_096),
    notes="RM1 V0 (DisaggRec Fig. 1b) at published widths; reduced: "
          "rows_per_table 3,417,969 -> 4,096 (one chip's share of a "
          "4-MN pool)",
)

REDUCED = CONFIG.replace(
    name="rm1-reduced",
    dlrm=DLRMConfig(num_tables=8, rows_per_table=1000, embed_dim=16,
                    avg_pooling=10, num_dense_features=16,
                    bottom_mlp=(32, 16), top_mlp=(64, 32, 1)),
)
