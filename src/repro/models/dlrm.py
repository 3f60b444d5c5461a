"""DLRM-style recommendation model (the paper's RM1/RM2).

Pipeline (paper Fig. 1a): preprocessing G_P (hashing, done in the data
layer) -> SparseNet G_S (embedding bags: gather + pooling) -> DenseNet G_D
(bottom MLP, pairwise interaction, top MLP).

DisaggRec mapping: the stacked embedding tables shard table-wise over the
``model`` mesh axis (the MN pool; assignment computed by
core/embedding_manager's greedy allocator) and — for TB-scale generations —
row-wise over ``data`` as well, since one pod's HBM per model-group is
smaller than a DRAM memory node. Pooling (the Fsum reduction) happens
*shard-local* before any cross-device traffic: only (B, T, D) pooled
vectors cross the network, never (B, T, P, D) raw rows. That is the
paper's near-memory reduction, realized on TPU as a VMEM-local reduction
(see kernels/embedding_bag for the Pallas version).
"""
from __future__ import annotations

from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, ShapeConfig
from repro.distributed import sharding as shd
from repro.models import params as pm
from repro.models.params import Spec


def _mlp_tables(dims, prefix_names=("embed", None)):
    t = {}
    for i, (a, b) in enumerate(zip(dims[:-1], dims[1:])):
        t[f"w{i}"] = Spec((a, b), (None, None))
        t[f"b{i}"] = Spec((b,), (None,), "zeros")
    return t


def _mlp_apply(t, x, n, relu_last: bool = False):
    for i in range(n):
        x = x @ t[f"w{i}"] + t[f"b{i}"]
        if i < n - 1 or relu_last:
            x = jax.nn.relu(x)
    return x


def table_key(t: int) -> str:
    """Key of table ``t`` in a per-table ``embed`` dict."""
    return f"t{t}"


def table_list(embed) -> List[jax.Array]:
    """The tables of an ``embed`` parameter as (R_t, D) arrays, in table
    order: a (T, R, D) stack, a sequence of tables, or a per-table dict
    keyed by ``table_key``."""
    if isinstance(embed, dict):
        return [embed[table_key(t)] for t in range(len(embed))]
    return list(embed)


def embedding_bag_ref(tables, idx):
    """tables: T (R_t, D) tables as ``table_list`` takes them; idx:
    (B, T, P) -> pooled (B, T, D).

    Shard-local gather+sum; -1 indices are padding (masked out).
    """
    valid = (idx >= 0)[..., None]
    safe = jnp.maximum(idx, 0)
    rows = jnp.stack([jnp.take(tb, safe[:, t], axis=0)      # (B, P, D)
                      for t, tb in enumerate(table_list(tables))], axis=1)
    return jnp.where(valid, rows, 0.0).sum(axis=2)


class DLRMModel:
    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        r = cfg.dlrm
        self.num_feats = r.interaction_proj + 1
        self.inter = self.num_feats * (self.num_feats - 1) // 2

    def _tables(self):
        r = self.cfg.dlrm
        bot = (r.num_dense_features,) + r.bottom_mlp
        if r.table_rows is None:     # RM1's uniform tables: one stack
            embed = Spec((r.num_tables, r.rows_per_table, r.embed_dim),
                         ("table_shard", "table_rows", None), "normal:0.01")
        else:
            embed = {table_key(t): Spec((rows, r.embed_dim),
                                        ("table_rows", None), "normal:0.01")
                     for t, rows in enumerate(r.rows)}
        if r.interaction == "dcn":
            W, k = r.cross_width, r.dcn_low_rank_dim
            cross = {}
            for i in range(r.dcn_num_layers):
                cross[f"v{i}"] = Spec((W, k), (None, None))
                cross[f"w{i}"] = Spec((k, W), (None, None))
                cross[f"b{i}"] = Spec((W,), (None,), "zeros")
            return {"embed": embed, "bottom": _mlp_tables(bot),
                    "cross": cross,
                    "top": _mlp_tables((W,) + r.top_mlp)}
        top = (r.bottom_mlp[-1] + self.inter,) + r.top_mlp
        return {
            "embed": embed,
            "proj": Spec((r.num_tables, r.interaction_proj), (None, None),
                         "normal:0.05"),
            "bottom": _mlp_tables(bot),
            "top": _mlp_tables(top),
        }

    def init(self, seed: int = 0):
        # DLRM tables are served fp32 (as in the paper's production stack)
        return pm.init_table(jax.random.PRNGKey(seed), self._tables(),
                             jnp.float32)

    def param_specs(self):
        return pm.table_specs(self._tables())

    def param_shapes(self, dtype=None):
        return pm.eval_shape_tree(self._tables(), dtype=dtype or jnp.float32)

    def param_count(self):
        return pm.table_size(self._tables())

    # ------------------------------------------------------------ forward
    def pool_embeddings(self, params, idx, use_kernel: bool = False):
        """SparseNet G_S: gather+pool all tables -> (B, T, D).

        `use_kernel=True` runs the fused multi-table Pallas embedding-bag
        (one call over the tables laid back to back, each walked to its
        bag length); otherwise the jnp reference.
        This is the shard-local half of the query path — the ClusterEngine
        calls it per MN shard with that shard's table subset.
        """
        if use_kernel:
            from repro.kernels import ops
            tables = table_list(params["embed"])
            offsets = np.cumsum([0] + [t.shape[0] for t in tables[:-1]])
            return ops.embedding_bag_fused_flat(
                jnp.concatenate(tables), jnp.asarray(offsets, jnp.int32),
                idx, jnp.asarray(self.cfg.dlrm.bags, jnp.int32))
        return embedding_bag_ref(params["embed"], idx)

    def dense_forward(self, params, dense, pooled):
        """DenseNet G_D on already-pooled embeddings (the CN-side half:
        what runs after the Fsum gather returns from the MN pool)."""
        r = self.cfg.dlrm
        if r.interaction == "dcn":
            # x0 = [bottom, pooled_1..T]; x_{l+1} = x0 * (W_l V_l x_l + b_l)
            # + x_l; then the top MLP
            bot = _mlp_apply(params["bottom"], dense, len(r.bottom_mlp),
                             relu_last=True)
            pooled = shd.lsc(pooled, "batch", None, None)       # Fsum gather
            x0 = jnp.concatenate(
                [bot, pooled.astype(bot.dtype).reshape(bot.shape[0], -1)],
                axis=-1)
            x, c = x0, params["cross"]
            for i in range(r.dcn_num_layers):
                x = x0 * ((x @ c[f"v{i}"]) @ c[f"w{i}"] + c[f"b{i}"]) + x
            return _mlp_apply(params["top"], x, len(r.top_mlp))[..., 0]
        bot = _mlp_apply(params["bottom"], dense, len(r.bottom_mlp))
        pooled = shd.lsc(pooled, "batch", None, None)           # Fsum gather
        pooled = jnp.einsum("btd,tk->bkd", pooled.astype(bot.dtype),
                            params["proj"])
        z = jnp.concatenate([bot[:, None, :], pooled], axis=1)  # (B,K+1,D)
        zz = jnp.einsum("bfd,bgd->bfg", z, z)
        iu = jnp.triu_indices(self.num_feats, k=1)
        inter = zz[:, iu[0], iu[1]]                             # (B, F(F-1)/2)
        x = jnp.concatenate([bot, inter], axis=-1)
        return _mlp_apply(params["top"], x, len(r.top_mlp))[..., 0]

    def forward(self, params, batch, use_kernel: bool = False):
        pooled = self.pool_embeddings(params, batch["indices"],
                                      use_kernel=use_kernel)
        return self.dense_forward(params, batch["dense"], pooled)

    def loss(self, params, batch):
        logit = self.forward(params, batch)
        y = batch["labels"].astype(jnp.float32)
        z = logit.astype(jnp.float32)
        # stable BCE-with-logits
        return jnp.mean(jnp.maximum(z, 0) - z * y + jnp.log1p(jnp.exp(-jnp.abs(z))))

    def serve_step(self, params, batch, use_kernel: bool = False):
        return jax.nn.sigmoid(self.forward(params, batch,
                                           use_kernel=use_kernel))

    # -------------------------------------------------------------- specs
    def input_specs(self, shape_or_batch):
        r = self.cfg.dlrm
        if isinstance(shape_or_batch, ShapeConfig):
            B = shape_or_batch.global_batch
            kind = shape_or_batch.kind
        else:
            B, kind = shape_or_batch, "train"
        spec = {
            "dense": jax.ShapeDtypeStruct((B, r.num_dense_features),
                                          jnp.float32),
            "indices": jax.ShapeDtypeStruct(
                (B, r.num_tables, r.bag_width), jnp.int32),
        }
        if kind == "train":
            spec["labels"] = jax.ShapeDtypeStruct((B,), jnp.int32)
        return spec

    def input_logical(self, shape=None):
        return {"dense": ("batch", None), "indices": ("batch", None, None),
                "labels": ("batch",)}
