"""DLRM-DCNv2 — MLPerf's recommendation benchmark model.

MLPerf Training ``recommendation_v2/torchrec_dlrm`` and MLPerf Inference
``recommendation/dlrm_v2`` on Criteo 1TB multi-hot: torchrec's
``DLRM_DCN`` with a ``LowRankCrossNet`` interaction (DCN-V2, Wang et al.,
arXiv 2008.13535).  Widths are the MLPerf run command's flags: 13 dense
features, 26 sparse features of ``embedding_dim`` 128 with the rows of
``--num_embeddings_per_feature`` and the full bags of
``--multi_hot_sizes`` (214 lookups a row, pooled by sum), bottom MLP
512-256-128, top MLP 1024-1024-512-256-1, 3 cross layers of rank 512 over
``[bottom, pooled_1..26]`` (width 27 x 128 = 3,456).
"""
import dataclasses

from repro.configs.base import DLRMConfig, ModelConfig

NUM_EMBEDDINGS = (40_000_000, 39_060, 17_295, 7_424, 20_265, 3, 7_122,
                  1_543, 63, 40_000_000, 3_067_956, 405_282, 10, 2_209,
                  11_938, 155, 4, 976, 14, 40_000_000, 40_000_000,
                  40_000_000, 590_152, 12_973, 108, 36)
MULTI_HOT = (3, 2, 1, 2, 6, 1, 1, 1, 1, 7, 3, 8, 1, 6, 9, 5, 1, 1, 1, 12,
             100, 27, 10, 3, 1, 1)


CONFIG = ModelConfig(
    name="dcnv2",
    family="dlrm",
    num_layers=0, num_heads=0, num_kv_heads=0, d_ff=0, vocab_size=0,
    d_model=128,
    dlrm=DLRMConfig(
        num_tables=len(NUM_EMBEDDINGS), rows_per_table=max(NUM_EMBEDDINGS),
        embed_dim=128, avg_pooling=max(MULTI_HOT), num_dense_features=13,
        bottom_mlp=(512, 256, 128), top_mlp=(1024, 1024, 512, 256, 1),
        table_rows=NUM_EMBEDDINGS, bag_lengths=MULTI_HOT,
        interaction="dcn", dcn_num_layers=3, dcn_low_rank_dim=512),
)

# One TPU v5e's share of a row-wise sharded MN pool: every table cut to
# at most ROW_CAP rows, ids taken mod ROW_CAP (MLPerf's own
# max_ind_range hashing): 4,196,496 rows, 2.15 GB of fp32 in all.
ROW_CAP = 2 ** 19
CHIP_SHARE = CONFIG.replace(
    name="dcnv2-chip",
    dlrm=dataclasses.replace(
        CONFIG.dlrm, rows_per_table=ROW_CAP,
        table_rows=tuple(min(r, ROW_CAP) for r in NUM_EMBEDDINGS)))

REDUCED = CONFIG.replace(
    name="dcnv2-reduced",
    d_model=16,
    dlrm=DLRMConfig(
        num_tables=6, rows_per_table=1000, embed_dim=16, avg_pooling=12,
        num_dense_features=13, bottom_mlp=(32, 16), top_mlp=(64, 32, 1),
        table_rows=(3, 1000, 40, 7, 250, 64), bag_lengths=(1, 12, 3, 8, 2, 9),
        interaction="dcn", dcn_num_layers=2, dcn_low_rank_dim=8),
)
