"""DisaggRec's communication pattern as JAX collectives (C1).

`disagg_embedding_lookup` is the production-path embedding op: tables are
table-sharded over the ``model`` mesh axis (shards = memory nodes, laid
out by the greedy allocator), every shard pools **locally** (near-memory
reduction — optionally via the Pallas embedding_bag kernel), and only the
pooled Fsum crosses the interconnect via one all-gather. The indices
scatter is implicit: index tensors are replicated over the model axis
(they are tiny: P*4 bytes per bag vs P*D*4 gathered rows — the paper's
core traffic argument).
"""
from __future__ import annotations

from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import embedding_manager as em
from repro.distributed import sharding as shd


def permutation_from_assignment(shards: List[List[int]], n_tables: int):
    """Flatten per-shard table lists into a permutation + inverse."""
    perm = [t for sh in shards for t in sh]
    assert sorted(perm) == list(range(n_tables)), "not a permutation"
    inv = np.empty(n_tables, np.int32)
    for pos, t in enumerate(perm):
        inv[t] = pos
    return np.asarray(perm, np.int32), inv


def disagg_embedding_lookup(tables, idx, mesh=None, axis: str = "model",
                            use_kernel: bool = False):
    """tables: (T, R, D) sharded on T over `axis`; idx: (B, T, P) int32
    (-1 padded). Returns pooled (B, T, D), gathered over `axis`.

    Without a mesh this is the reference single-host path.
    """
    from repro.models.dlrm import embedding_bag_ref

    def pool(tbl, ix):
        if use_kernel:
            from repro.kernels import ops as kops
            return kops.embedding_bag(tbl, ix)
        return embedding_bag_ref(tbl, ix)

    if mesh is None or axis not in mesh.shape or mesh.shape[axis] == 1:
        return pool(tables, idx)

    n_shards = mesh.shape[axis]
    T = tables.shape[0]
    assert T % n_shards == 0, (T, n_shards)
    from repro.models.layers import batch_pspec_entry
    bspec = batch_pspec_entry(idx.shape[0], mesh)

    def local_fn(tbl, ix):
        # tbl: (T_loc, R, D); ix: (B_loc, T, P) -> slice own tables
        shard = jax.lax.axis_index(axis)
        t_loc = tbl.shape[0]
        ix_loc = jax.lax.dynamic_slice_in_dim(ix, shard * t_loc, t_loc, 1)
        pooled = pool(tbl, ix_loc)                     # (B_loc, T_loc, D)
        # Fsum all-gather: only pooled vectors cross the network
        return jax.lax.all_gather(pooled, axis, axis=1, tiled=True)

    return jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(axis, None, None), P(bspec, None, None)),
        out_specs=P(bspec, None, None),
        check_vma=False,
    )(tables, idx)


def greedy_table_layout(model_cfg, m: int, n_tasks: int = 1,
                        heterogeneous_seed: Optional[int] = None):
    """Run the paper's greedy allocation+routing for a DLRM config and
    return (perm, inv_perm, alloc, routing) for `m` shards."""
    r = model_cfg.dlrm
    rng = np.random.RandomState(heterogeneous_seed or 0)
    tables = []
    for t in range(r.num_tables):
        rows = r.rows_per_table
        if heterogeneous_seed is not None:
            rows = int(r.rows_per_table * float(rng.lognormal(0.0, 0.5)))
        tables.append(em.TableInfo(t, rows, r.embed_dim,
                                   r.avg_pooling, 4))
    cap = sum(t.size_bytes for t in tables)
    caps = [cap // m + cap // (4 * m)] * m     # capacity for ~1.25 replicas
    alloc = em.allocate_greedy(tables, caps)
    routing = em.route_greedy(tables, alloc, n_tasks, m)
    shards = em.shard_assignment(alloc, routing, r.num_tables, m)
    # balance shard cardinality for the stacked-array layout (pad by moving
    # tables from over-full shards — routing stays balanced by bytes)
    want = r.num_tables // m
    overflow = []
    for sh in shards:
        while len(sh) > want:
            overflow.append(sh.pop())
    for sh in shards:
        while len(sh) < want:
            sh.append(overflow.pop())
    perm, inv = permutation_from_assignment(shards, r.num_tables)
    return perm, inv, alloc, routing
