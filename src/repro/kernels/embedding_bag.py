"""Fused embedding-bag (gather + pooling) Pallas kernels.

Near-memory reduction on TPU: the table lives in HBM and only the pooled
Fsum is ever written out — the paper's NMP-DIMM insight, VMEM-local.

``embedding_bag_1table`` / ``embedding_bag`` stream one (1, D) row per
(bag, slot) grid step through a scalar-prefetch-driven BlockSpec; that
block shape is below the TPU tile, so they serve interpret mode only.

The shard kernels on the serving path (``embedding_bag_fused_flat``,
``embedding_bag_nmp_flat``) keep the flat shard in HBM
(``memory_space=pl.ANY``) and gather each bag's rows with one DMA per
valid slot into a (P, D) VMEM scratch, then sum them in ascending slot
order.  Their index block is a per-grid-step SMEM tile, and their output
block spans the full last two dimensions, so the v5e compiler accepts
them at published widths.

Padding indices are negative: their DMA is skipped and the accumulate is
predicated off (the old accumulator is selected, never ``acc + 0.0``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(idx_ref, table_blk, out_blk):
    b = pl.program_id(0)
    p = pl.program_id(1)

    @pl.when(p == 0)
    def _init():
        out_blk[...] = jnp.zeros_like(out_blk)

    @pl.when(idx_ref[b, p] >= 0)
    def _acc():
        out_blk[...] += table_blk[...].astype(out_blk.dtype)


def embedding_bag_1table(table: jax.Array, idx: jax.Array,
                         interpret: bool = True) -> jax.Array:
    """table: (R, D); idx: (B, P) int32, -1 padded -> pooled (B, D)."""
    R, D = table.shape
    B, P = idx.shape

    def table_map(b, p, idx_ref):
        # clamp padding to row 0; the accumulate is masked in the kernel
        return jnp.maximum(idx_ref[b, p], 0), 0

    def out_map(b, p, idx_ref):
        return b, 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, P),
        in_specs=[pl.BlockSpec((1, D), table_map)],
        out_specs=pl.BlockSpec((1, D), out_map),
    )
    return pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, D), jnp.float32),
        interpret=interpret,
    )(idx, table)


def embedding_bag(tables: jax.Array, idx: jax.Array,
                  interpret: bool = True) -> jax.Array:
    """tables: (T, R, D); idx: (B, T, P) -> pooled (B, T, D)."""
    f = functools.partial(embedding_bag_1table, interpret=interpret)
    out = jax.vmap(f, in_axes=(0, 1), out_axes=1)(tables,
                                                  idx)  # (B, T, D)
    return out.astype(tables.dtype)


# ------------------------------------------------------- shard bag kernels
def _gather_pool(slot_row, base, table_hbm, rows, sems):
    """Pool one bag: DMA its valid rows ``table_hbm[base + slot_row(p)]``
    into ``rows[p]`` (one semaphore per slot), then add them to a
    (1, D) fp32 accumulator in ascending slot order.  Padding slots
    (``slot_row(p) < 0``) issue no DMA and keep the old accumulator."""
    P, D = rows.shape

    def copy(p):
        src = table_hbm.at[pl.ds(base + slot_row(p), 1)]
        return pltpu.make_async_copy(src, rows.at[pl.ds(p, 1)], sems.at[p])

    def start(p, carry):
        @pl.when(slot_row(p) >= 0)
        def _():
            copy(p).start()
        return carry

    def wait_add(p, acc):
        valid = slot_row(p) >= 0

        @pl.when(valid)
        def _():
            copy(p).wait()
        row = rows[pl.ds(p, 1), :].astype(jnp.float32)
        return jnp.where(valid, acc + row, acc)

    jax.lax.fori_loop(0, P, start, 0)
    return jax.lax.fori_loop(0, P, wait_add, jnp.zeros((1, D), jnp.float32))


def _shard_call(kernel, flat_table, offsets, idx_blocked, interpret):
    """pallas_call shared by both shard kernels: grid over the leading
    axis of ``idx_blocked`` (G, N, P), its (1, N, P) tile in SMEM per
    step, the shard in HBM, a (1, N, D) fp32 output block."""
    _, D = flat_table.shape
    G, N, P = idx_blocked.shape
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(G,),
        in_specs=[pl.BlockSpec((1, N, P), lambda g, off: (g, 0, 0),
                               memory_space=pltpu.SMEM),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, N, D), lambda g, off: (g, 0, 0)),
        scratch_shapes=[pltpu.VMEM((P, D), flat_table.dtype),
                        pltpu.SemaphoreType.DMA((P,))],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((G, N, D), jnp.float32),
        interpret=interpret,
    )(offsets, idx_blocked, flat_table)


def _fused_kernel(off_ref, idx_ref, table_hbm, out_ref, rows, sems):
    # one grid step per bag b; its tables pool in turn
    def per_table(t, carry):
        out_ref[0, pl.ds(t, 1), :] = _gather_pool(
            lambda p: idx_ref[0, t, p], off_ref[t], table_hbm, rows, sems)
        return carry

    jax.lax.fori_loop(0, idx_ref.shape[1], per_table, 0)


def embedding_bag_fused_flat(flat_table: jax.Array, offsets: jax.Array,
                             idx: jax.Array,
                             interpret: bool = True) -> jax.Array:
    """One Pallas call pooling every table of a (flattened) shard.

    flat_table: (sum_t R_t, D) — all tables stacked row-wise, so tables of
    different row counts coexist in one shard buffer; it stays in HBM.
    offsets:    (T,) int32 — scalar-prefetched row offset of each table in
    flat_table.
    idx:        (B, T, P) int32, table-local rows, -1 padded.

    Returns pooled (B, T, D) fp32.  The grid walks bags: step b holds
    bag b's (T, P) indices in SMEM and its (T, D) output block in VMEM,
    and for each table DMAs the valid rows and sums them in ascending
    slot order — raw rows never return to HBM, only the pooled Fsum (the
    NMP insight, amortizing ONE kernel launch across the whole shard).
    """
    return _shard_call(_fused_kernel, flat_table, offsets, idx, interpret)


def embedding_bag_fused(tables: jax.Array, idx: jax.Array,
                        interpret: bool = True) -> jax.Array:
    """tables: (T, R, D); idx: (B, T, P) -> pooled (B, T, D) in one call."""
    T, R, D = tables.shape
    offsets = jnp.arange(T, dtype=jnp.int32) * R
    out = embedding_bag_fused_flat(tables.reshape(T * R, D), offsets, idx,
                                   interpret=interpret)
    return out.astype(tables.dtype)


# ------------------------------------------------------ near-memory pooling
def _nmp_kernel(off_ref, idx_ref, table_hbm, out_ref, rows, sems):
    # one grid step per table t (its (B, P) indices in SMEM); its bags
    # reduce in turn against the table's offset
    t = pl.program_id(0)

    def per_bag(b, carry):
        out_ref[0, pl.ds(b, 1), :] = _gather_pool(
            lambda p: idx_ref[0, b, p], off_ref[t], table_hbm, rows, sems)
        return carry

    jax.lax.fori_loop(0, idx_ref.shape[1], per_bag, 0)


def embedding_bag_nmp_flat(flat_table: jax.Array, offsets: jax.Array,
                           idx: jax.Array,
                           interpret: bool = True) -> jax.Array:
    """On-MN pooling kernel for an NMP memory node (paper §NMP, Fig. 14).

    Same contract as ``embedding_bag_fused_flat`` — flat_table
    (sum_t R_t, D) in HBM with scalar-prefetched per-table ``offsets``
    and table-local ``idx`` (B, T, P), -1 padded — but a different
    execution shape that mirrors the NMP-DIMM: the grid walks tables —
    the node scans its shard table by table — and every bag of the
    table reduces inside the kernel body, rows fetched by DMA from the
    shard (the DIMM-rank fetch; on real NMP hardware each fetch stays
    inside the rank).  Only the D-dim pooled Fsum is ever written out —
    the memory node ships ``tables x D`` bytes to the CN instead of
    ``rows x D``.  The kernel works in (T, B, ·) layout; the wrapper
    transposes the indices in and the pooled output back.

    Slots accumulate in ascending order, the same order as the fused
    CN-side bag, so fp32 results are bitwise identical to
    ``embedding_bag_fused_flat`` and to
    ``kernels.ref.embedding_bag_seq_ref`` (tests pin this).
    """
    out = _shard_call(_nmp_kernel, flat_table, offsets,
                      jnp.transpose(idx, (1, 0, 2)), interpret)
    return jnp.transpose(out, (1, 0, 2))


def embedding_bag_nmp(tables: jax.Array, idx: jax.Array,
                      interpret: bool = True) -> jax.Array:
    """tables: (T, R, D); idx: (B, T, P) -> pooled (B, T, D) on-node."""
    T, R, D = tables.shape
    offsets = jnp.arange(T, dtype=jnp.int32) * R
    out = embedding_bag_nmp_flat(tables.reshape(T * R, D), offsets, idx,
                                 interpret=interpret)
    return out.astype(tables.dtype)
