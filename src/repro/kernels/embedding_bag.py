"""Fused embedding-bag (gather + pooling) Pallas kernels.

Near-memory reduction on TPU: the table lives in HBM and only the pooled
Fsum is ever written out — the paper's NMP-DIMM insight, VMEM-local.

``embedding_bag_1table`` / ``embedding_bag`` stream one (1, D) row per
(bag, slot) grid step through a scalar-prefetch-driven BlockSpec; that
block shape is below the TPU tile, so they serve interpret mode only.

The shard kernels on the serving path (``embedding_bag_fused_flat``,
``embedding_bag_nmp_flat``) keep the flat shard in HBM
(``memory_space=pl.ANY``) and pool eight bags at once, one per sublane
of an (8, D) fp32 tile (``_pool_lanes``): a branch-free pass lists each
bag's valid slots, one DMA per listed row fills a double-buffered
(2, 8P, D) VMEM scratch, and the slots are summed in ascending order
while the next group's DMAs are in flight.  Their index block is a
per-grid-step SMEM tile, and their output block spans the full last two
dimensions, so the v5e compiler accepts them at published widths.

Padding indices are negative: they issue no DMA, and their rows are
selected to 0.0 at the add, as ``embedding_bag_seq_ref`` does.

Each ``pallas_call`` carries a ``name=`` (``embedding_bag_fused``,
``embedding_bag_nmp``, ``embedding_bag_1table``), the kernel's name in a
device trace.
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
        name="embedding_bag_1table",
    )(idx, table)


def embedding_bag(tables: jax.Array, idx: jax.Array,
                  interpret: bool = True) -> jax.Array:
    """tables: (T, R, D); idx: (B, T, P) -> pooled (B, T, D)."""
    f = functools.partial(embedding_bag_1table, interpret=interpret)
    out = jax.vmap(f, in_axes=(0, 1), out_axes=1)(tables,
                                                  idx)  # (B, T, D)
    return out.astype(tables.dtype)


# ------------------------------------------------------- shard bag kernels
LANES = 8   # fp32 sublanes of a vreg: bags pooled at once, one per sublane


def pad_to_lanes(n: int) -> int:
    """``n`` rounded up to a multiple of ``LANES``."""
    return -(-n // LANES) * LANES


def _fold(n, body, carry, unroll: int = 8):
    """``carry = body(i, carry)`` for i in [0, n), n static or traced:
    ``unroll`` calls per loop iteration, then the remainder one by one."""
    def chunk(i0, carry):
        for u in range(unroll):
            carry = body(i0 * unroll + u, carry)
        return carry

    carry = jax.lax.fori_loop(0, n // unroll, chunk, carry)
    return jax.lax.fori_loop(n // unroll * unroll, n, body, carry)


def _pool_lanes(lane_base, idx_ref, table_hbm, out_ref, rows, src, count,
                sems):
    """Pool the grid step's N bags (N a multiple of ``LANES``) in groups
    of ``LANES``, one bag per sublane of an (8, D) fp32 tile.

    Lane j of group g is bag ``n = 8g + j``.  A branch-free pass over
    its slots lists the source row ``lane_base(n) + idx`` of each valid
    slot (``idx_ref[0, n, p] >= 0``) in the buffer's SMEM list
    ``src[buf, j, k]``, k its rank among the lane's valid slots, and
    their number in ``count[buf, j]``; padding slots are listed nowhere
    and issue no DMA.  Each listed row is DMA'd to ``rows[buf, 8k + j]``,
    signalling the buffer's semaphore, which is waited eight copies at a
    time; the loops run eight steps an iteration.  Group g+1's copies
    are issued before group g is waited on and summed, so the DMA
    pipeline drains once per grid step.  The sum adds
    ``rows[buf, 8k:8k+8]`` for k ascending to a +0.0 tile, each lane's
    rows past its count selected to 0.0: per lane its valid slots in
    ascending order and +0.0 for the rest, bitwise
    ``embedding_bag_seq_ref``'s sum (an accumulator that starts at +0.0
    never reads -0.0, so where the zeros fall does not matter)."""
    _, N, P = idx_ref.shape
    D = rows.shape[-1]
    n_groups = N // LANES
    chunk = min(LANES, table_hbm.shape[0])

    def issue(g, buf):
        """List group g's valid slots in buffer ``buf`` and start their
        copies; returns the group's number of copies."""
        def per_lane(j, total):
            n = g * LANES + j
            base = lane_base(n)

            def per_slot(p, k):
                r = idx_ref[0, n, p]
                # written unconditionally: a padding slot's entry is
                # overwritten by the next one or lies past the count
                src[buf, j, k] = base + r
                return k + (r >= 0).astype(jnp.int32)

            k = _fold(P, per_slot, jnp.int32(0))
            count[buf, j] = k

            def start(i, carry):
                pltpu.make_async_copy(
                    table_hbm.at[pl.ds(src[buf, j, i], 1)],
                    rows.at[buf, pl.ds(i * LANES + j, 1)],
                    sems.at[buf]).start()
                return carry

            _fold(k, start, 0)
            return total + k

        return jax.lax.fori_loop(0, LANES, per_lane, jnp.int32(0))

    def group(g, total):
        buf = g % 2
        total_next = jax.lax.cond(g + 1 < n_groups,
                                  lambda: issue(g + 1, 1 - buf),
                                  lambda: jnp.int32(0))

        # a DMA semaphore counts bytes: a descriptor of c rows waits for
        # c one-row copies, so wait eight at a time, then the rest
        def wait(rows_per_wait):
            def body(i, carry):
                pltpu.make_async_copy(table_hbm.at[pl.ds(0, rows_per_wait)],
                                      rows.at[buf, pl.ds(0, rows_per_wait)],
                                      sems.at[buf]).wait()
                return carry
            return body

        _fold(total // chunk, wait(chunk), 0)
        jax.lax.fori_loop(0, total % chunk, wait(1), 0)
        lane = jax.lax.broadcasted_iota(jnp.int32, (LANES, D), 0)
        n_valid = jnp.zeros((LANES, D), jnp.int32)
        for j in range(LANES):
            n_valid = jnp.where(lane == j, count[buf, j], n_valid)
        acc = jnp.zeros((LANES, D), jnp.float32)
        for k in range(P):
            row = rows[buf, k * LANES:(k + 1) * LANES].astype(jnp.float32)
            acc = acc + jnp.where(k < n_valid, row, 0.0)
        out_ref[0, pl.ds(pl.multiple_of(g * LANES, LANES), LANES), :] = acc
        return total_next

    jax.lax.fori_loop(0, n_groups, group, issue(0, 0))


def _shard_call(kernel, name, flat_table, offsets, idx_blocked, interpret):
    """pallas_call shared by both shard kernels: grid over the leading
    axis of ``idx_blocked`` (G, N, P), N a multiple of ``LANES``, its
    (1, N, P) tile in SMEM per step, the shard in HBM, a (1, N, D) fp32
    output block; scratch: a double-buffered (2, 8P, D) row buffer, its
    (2, 8, P) SMEM list of source rows and (2, 8) counts, and a DMA
    semaphore per buffer."""
    _, D = flat_table.shape
    G, N, P = idx_blocked.shape
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(G,),
        in_specs=[pl.BlockSpec((1, N, P), lambda g, off: (g, 0, 0),
                               memory_space=pltpu.SMEM),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, N, D), lambda g, off: (g, 0, 0)),
        scratch_shapes=[pltpu.VMEM((2, P * LANES, D), flat_table.dtype),
                        pltpu.SMEM((2, LANES, P), jnp.int32),
                        pltpu.SMEM((2, LANES), jnp.int32),
                        pltpu.SemaphoreType.DMA((2,))],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((G, N, D), jnp.float32),
        interpret=interpret,
        name=name,
    )(offsets, idx_blocked, flat_table)


def _pad_axis(x, axis: int, value: int):
    """Pad ``x``'s ``axis`` with ``value`` up to a multiple of LANES."""
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, pad_to_lanes(x.shape[axis]) - x.shape[axis])
    return jnp.pad(x, pad, constant_values=value)


def _fused_kernel(off_ref, idx_ref, table_hbm, out_ref, *scratch):
    # one grid step per bag; its lanes are 8 consecutive tables
    _pool_lanes(lambda t: off_ref[t], idx_ref, table_hbm, out_ref, *scratch)


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
    and pools eight tables at a time, DMAing the valid rows and summing
    them in ascending slot order — raw rows never return to HBM, only
    the pooled Fsum (the NMP insight, amortizing ONE kernel launch across
    the whole shard).  T is padded to a multiple of 8 with all-padding
    tables at offset 0, sliced off the output.
    """
    T = idx.shape[1]
    out = _shard_call(_fused_kernel, "embedding_bag_fused", flat_table,
                      _pad_axis(offsets, 0, 0), _pad_axis(idx, 1, -1),
                      interpret)
    return out[:, :T]


def embedding_bag_fused(tables: jax.Array, idx: jax.Array,
                        interpret: bool = True) -> jax.Array:
    """tables: (T, R, D); idx: (B, T, P) -> pooled (B, T, D) in one call."""
    T, R, D = tables.shape
    offsets = jnp.arange(T, dtype=jnp.int32) * R
    out = embedding_bag_fused_flat(tables.reshape(T * R, D), offsets, idx,
                                   interpret=interpret)
    return out.astype(tables.dtype)


# ------------------------------------------------------ near-memory pooling
def _nmp_kernel(off_ref, idx_ref, table_hbm, out_ref, *scratch):
    # one grid step per table t (its (B, P) indices in SMEM); its lanes
    # are 8 consecutive bags, all against the table's offset
    t = pl.program_id(0)
    _pool_lanes(lambda b: off_ref[t], idx_ref, table_hbm, out_ref, *scratch)


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
    ``rows x D``.  The kernel works in (T, B, ·) layout, eight bags at
    a time; the wrapper pads B to a multiple of 8 with all-padding bags,
    transposes the indices in and the pooled output back, and slices.

    Slots accumulate in ascending order, the same order as the fused
    CN-side bag, so fp32 results are bitwise identical to
    ``embedding_bag_fused_flat`` and to
    ``kernels.ref.embedding_bag_seq_ref`` (tests pin this).
    """
    B = idx.shape[0]
    out = _shard_call(_nmp_kernel, "embedding_bag_nmp", flat_table, offsets,
                      jnp.transpose(_pad_axis(idx, 0, -1), (1, 0, 2)),
                      interpret)
    return jnp.transpose(out, (1, 0, 2))[:B]


def embedding_bag_nmp(tables: jax.Array, idx: jax.Array,
                      interpret: bool = True) -> jax.Array:
    """tables: (T, R, D); idx: (B, T, P) -> pooled (B, T, D) on-node."""
    T, R, D = tables.shape
    offsets = jnp.arange(T, dtype=jnp.int32) * R
    out = embedding_bag_nmp_flat(tables.reshape(T * R, D), offsets, idx,
                                 interpret=interpret)
    return out.astype(tables.dtype)
