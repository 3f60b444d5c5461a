"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps, interpret mode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


@pytest.mark.parametrize("T,R,D,B,P", [
    (1, 64, 8, 4, 4), (4, 100, 16, 8, 10), (3, 257, 32, 5, 7),
    (2, 128, 128, 16, 20),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_embedding_bag_sweep(T, R, D, B, P, dtype):
    rng = np.random.RandomState(0)
    tables = jnp.asarray(rng.randn(T, R, D), dtype)
    idx = rng.randint(0, R, (B, T, P)).astype(np.int32)
    idx[rng.rand(B, T, P) < 0.25] = -1
    idx = jnp.asarray(idx)
    out_k = np.asarray(ops.embedding_bag(tables, idx), np.float32)
    out_r = np.asarray(ref.embedding_bag_ref(tables, idx), np.float32)
    tol = 1e-5 if dtype == jnp.float32 else 0.1
    np.testing.assert_allclose(out_k, out_r, atol=tol, rtol=tol)


def test_embedding_bag_all_padded():
    tables = jnp.ones((2, 10, 8), jnp.float32)
    idx = -jnp.ones((3, 2, 5), jnp.int32)
    out = ops.embedding_bag(tables, idx)
    assert float(jnp.abs(out).max()) == 0.0


# ------------------------------------------------- fused multi-table bag
def _mixed_pooling_idx(rng, R, B, T, P):
    """Per-bag pooling factors from 0..P: -1 padding tails of mixed
    length, including some fully-padded bags."""
    idx = rng.randint(0, R, (B, T, P)).astype(np.int32)
    lens = rng.randint(0, P + 1, (B, T))
    mask = np.arange(P)[None, None, :] < lens[..., None]
    return np.where(mask, idx, -1).astype(np.int32)


@pytest.mark.parametrize("T,R,D,B,P", [
    (1, 64, 8, 4, 4), (4, 100, 16, 8, 10), (3, 257, 32, 5, 7),
    (2, 128, 128, 16, 20),
])
def test_embedding_bag_fused_bitwise_fp32(T, R, D, B, P):
    """One pallas_call over all tables == slot-order reference, bitwise."""
    rng = np.random.RandomState(0)
    tables = jnp.asarray(rng.randn(T, R, D), jnp.float32)
    idx = jnp.asarray(_mixed_pooling_idx(rng, R, B, T, P))
    out_f = np.asarray(ops.embedding_bag_fused(tables, idx))
    out_s = np.asarray(ref.embedding_bag_seq_ref(tables, idx))
    out_v = np.asarray(ops.embedding_bag(tables, idx))
    assert np.array_equal(out_f, out_s)          # bitwise vs order-exact ref
    assert np.array_equal(out_f, out_v)          # bitwise vs vmapped kernel
    np.testing.assert_allclose(out_f, np.asarray(
        ref.embedding_bag_ref(tables, idx)), atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_embedding_bag_fused_dtypes(dtype):
    rng = np.random.RandomState(1)
    tables = jnp.asarray(rng.randn(4, 64, 16), dtype)
    idx = jnp.asarray(_mixed_pooling_idx(rng, 64, 6, 4, 8))
    out_f = np.asarray(ops.embedding_bag_fused(tables, idx), np.float32)
    out_r = np.asarray(ref.embedding_bag_ref(tables, idx), np.float32)
    tol = 1e-5 if dtype == jnp.float32 else 0.1
    np.testing.assert_allclose(out_f, out_r, atol=tol, rtol=tol)


def test_embedding_bag_fused_all_padded():
    tables = jnp.ones((3, 10, 8), jnp.float32)
    idx = -jnp.ones((4, 3, 5), jnp.int32)
    out = ops.embedding_bag_fused(tables, idx)
    assert float(jnp.abs(out).max()) == 0.0


def test_embedding_bag_fused_flat_shard_offsets():
    """The MN-shard entry point: a flat shard buffer addressed through
    scalar-prefetched per-table offsets, in non-contiguous slot order."""
    rng = np.random.RandomState(2)
    T, R, D, B, P = 5, 40, 16, 6, 6
    tables = jnp.asarray(rng.randn(T, R, D), jnp.float32)
    flat = tables.reshape(T * R, D)
    idx = _mixed_pooling_idx(rng, R, B, T, P)
    # route a shuffled subset of tables, as a shard assignment would
    slots = np.array([3, 0, 4], np.int32)
    offsets = jnp.asarray(slots * R)
    out = np.asarray(ops.embedding_bag_fused_flat(
        flat, offsets, jnp.asarray(idx[:, slots, :])))
    want = np.asarray(ref.embedding_bag_seq_ref(
        tables[jnp.asarray(slots)], jnp.asarray(idx[:, slots, :])))
    assert np.array_equal(out, want)


# ------------------------------------------------- near-memory (NMP) bag
@pytest.mark.parametrize("T,R,D,B,P", [
    (1, 64, 8, 4, 4), (4, 100, 16, 8, 10), (3, 257, 32, 5, 7),
    (2, 128, 128, 16, 20),
    (3, 96, 13, 6, 5),        # D not a multiple of the lane width
    (2, 50, 8, 5, 1),         # single-slot bags
])
def test_embedding_bag_nmp_bitwise_fp32(T, R, D, B, P):
    """The on-MN pooling kernel (in-kernel bag reduction) must be
    bitwise-equal to the slot-order reference AND to the fused CN-side
    bag — ragged bags, empty bags, any D — so a heterogeneous cluster
    scores identically whichever node type pools a shard."""
    rng = np.random.RandomState(0)
    tables = jnp.asarray(rng.randn(T, R, D), jnp.float32)
    idx = jnp.asarray(_mixed_pooling_idx(rng, R, B, T, P))
    out_n = np.asarray(ops.embedding_bag_nmp(tables, idx))
    assert np.array_equal(out_n, np.asarray(ref.embedding_bag_seq_ref(
        tables, idx)))
    assert np.array_equal(out_n, np.asarray(ops.embedding_bag_fused(
        tables, idx)))
    np.testing.assert_allclose(out_n, np.asarray(
        ref.embedding_bag_ref(tables, idx)), atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_embedding_bag_nmp_dtypes(dtype):
    rng = np.random.RandomState(1)
    tables = jnp.asarray(rng.randn(4, 64, 16), dtype)
    idx = jnp.asarray(_mixed_pooling_idx(rng, 64, 6, 4, 8))
    out_n = np.asarray(ops.embedding_bag_nmp(tables, idx), np.float32)
    out_r = np.asarray(ref.embedding_bag_ref(tables, idx), np.float32)
    tol = 1e-5 if dtype == jnp.float32 else 0.1
    np.testing.assert_allclose(out_n, out_r, atol=tol, rtol=tol)


def test_embedding_bag_nmp_all_padded():
    tables = jnp.ones((3, 10, 8), jnp.float32)
    idx = -jnp.ones((4, 3, 5), jnp.int32)
    out = ops.embedding_bag_nmp(tables, idx)
    assert out.shape == (4, 3, 8)
    assert float(jnp.abs(out).max()) == 0.0


def test_embedding_bag_nmp_flat_shard_offsets():
    """The NMP shard entry point matches the fused CN-side shard entry
    point bitwise on the same shuffled table subset."""
    rng = np.random.RandomState(2)
    T, R, D, B, P = 5, 40, 16, 6, 6
    tables = jnp.asarray(rng.randn(T, R, D), jnp.float32)
    flat = tables.reshape(T * R, D)
    idx = _mixed_pooling_idx(rng, R, B, T, P)
    slots = np.array([3, 0, 4], np.int32)
    offsets = jnp.asarray(slots * R)
    sub = jnp.asarray(idx[:, slots, :])
    out_n = np.asarray(ops.embedding_bag_nmp_flat(flat, offsets, sub))
    out_f = np.asarray(ops.embedding_bag_fused_flat(flat, offsets, sub))
    want = np.asarray(ref.embedding_bag_seq_ref(
        tables[jnp.asarray(slots)], sub))
    assert np.array_equal(out_n, out_f)
    assert np.array_equal(out_n, want)


def _lane_axis(B, T, n):
    """Shape broadcasting a lane index over (B, T, P): the lane axis is
    T when T == n (fused call), else B (NMP call)."""
    return (1, n, 1) if T == n else (n, 1, 1)


def _stale_lane_idx(rng, R, B, T, P):
    """Lane axis of 32: groups 0 and 1 valid at every slot, groups 2 and
    3 (the same two buffers) valid at slot 0 only, so a row left stale
    from groups 0-1 would be added."""
    idx = rng.randint(0, R, (B, T, P)).astype(np.int32)
    lane = np.arange(32).reshape(_lane_axis(B, T, 32))
    return np.where((lane < 16) | (np.arange(P) == 0), idx, -1)


def _padding_group_idx(rng, R, B, T, P, padding_first):
    """Lane axis of 16: one group all padding beside a group with one
    valid lane, at slot 2 (``padding_first``: the padding group first)."""
    idx = rng.randint(0, R, (B, T, P)).astype(np.int32)
    lane = np.arange(16).reshape(_lane_axis(B, T, 16))
    one = 11 if padding_first else 3
    return np.where((lane == one) & (np.arange(P) == 2), idx, -1)


@pytest.mark.parametrize("case,T,B,P", [
    ("mixed", 7, 3, 6), ("mixed", 8, 3, 6), ("mixed", 9, 3, 6),
    ("mixed", 17, 3, 6),
    ("mixed", 2, 7, 6), ("mixed", 2, 8, 6), ("mixed", 2, 9, 6),
    ("mixed", 2, 64, 6),
    ("stale", 32, 2, 5), ("stale", 2, 32, 5),
    ("padding_group_first", 16, 2, 5), ("padding_group_last", 16, 2, 5),
    ("padding_group_first", 2, 16, 5), ("padding_group_last", 2, 16, 5),
])
def test_shard_bags_bitwise_at_lane_boundaries(case, T, B, P):
    """The shard kernels pool eight bags per (8, D) tile (tables of a
    fused call, bags of an NMP call): at lane counts around multiples
    of 8, with a lane valid at a slot in one group and padding there in
    a later group of the same buffer, and with an all-padding group,
    both match the slot-order reference and each other bitwise."""
    R, D = 48, 16
    rng = np.random.RandomState(7)
    tables = jnp.asarray(rng.randn(T, R, D), jnp.float32)
    if case == "mixed":
        idx = _mixed_pooling_idx(rng, R, B, T, P)
    elif case == "stale":
        idx = _stale_lane_idx(rng, R, B, T, P)
    else:
        idx = _padding_group_idx(rng, R, B, T, P,
                                 case == "padding_group_first")
    idx = jnp.asarray(idx)
    want = np.asarray(ref.embedding_bag_seq_ref(tables, idx))
    out_f = np.asarray(ops.embedding_bag_fused(tables, idx))
    out_n = np.asarray(ops.embedding_bag_nmp(tables, idx))
    assert out_f.shape == out_n.shape == (B, T, D)
    assert np.array_equal(out_f, want)
    assert np.array_equal(out_n, want)
    if case != "mixed":
        assert np.count_nonzero(want) > 0


@pytest.mark.parametrize("kernel", ["embedding_bag_fused_flat",
                                    "embedding_bag_nmp_flat"])
@pytest.mark.parametrize("B", [3, 9])
def test_shard_bags_walk_each_tables_length(kernel, B):
    """Tables of uneven rows and bag lengths 1, 7, 8, 9 and 100 in one
    lane group (and 2, 12, 3 in the next), -1 inside a length: the
    kernels walk each table's bags only to its length and match the
    slot-order reference bitwise, at a slot block 100 wide."""
    rows = [3, 40, 17, 256, 1000, 9, 64, 5, 120, 33, 7]
    lens = [1, 7, 8, 9, 100, 100, 1, 8, 2, 12, 3]
    T, D, P = len(rows), 16, max(lens)
    rng = np.random.RandomState(11)
    tables = [rng.randn(r, D).astype(np.float32) for r in rows]
    idx = np.full((B, T, P), -1, np.int32)
    for t, (r, n) in enumerate(zip(rows, lens)):
        idx[:, t, :n] = rng.randint(0, r, (B, n))
    idx[rng.rand(B, T, P) < 0.2] = -1
    idx[0, 4, 50:60] = -1
    stack = np.zeros((T, max(rows), D), np.float32)
    for t, tb in enumerate(tables):
        stack[t, :len(tb)] = tb
    flat = jnp.asarray(np.concatenate(tables))
    offsets = jnp.asarray(np.cumsum([0] + rows[:-1]), jnp.int32)
    # table order in the call differs from the shard's
    order = np.array([4, 0, 2, 1, 3, 5, 9, 8, 7, 6, 10])
    got = np.asarray(getattr(ops, kernel)(
        flat, offsets[order], jnp.asarray(idx[:, order]),
        jnp.asarray(np.asarray(lens)[order], jnp.int32)))
    want = np.asarray(ref.embedding_bag_seq_ref(jnp.asarray(stack[order]),
                                                jnp.asarray(idx[:, order])))
    assert np.array_equal(got, want)
    assert np.count_nonzero(want[:, 0]) > 0


@pytest.mark.parametrize("kernel", ["embedding_bag_fused_flat",
                                    "embedding_bag_nmp_flat"])
def test_shard_bags_clamp_lengths_to_block(kernel):
    """A bag length above the index block's width P reads as P: no slot
    past the block is read, so the result is the full-width walk's."""
    rng = np.random.RandomState(3)
    T, R, D, P, B = 3, 20, 16, 5, 9
    tables = jnp.asarray(rng.randn(T, R, D), jnp.float32)
    idx = jnp.asarray(_mixed_pooling_idx(rng, R, B, T, P))
    flat, offsets = tables.reshape(T * R, D), jnp.arange(T) * R
    got = np.asarray(getattr(ops, kernel)(
        flat, offsets, idx, jnp.asarray([P, 1000, P + 1], jnp.int32)))
    assert np.array_equal(got, np.asarray(ref.embedding_bag_seq_ref(
        tables, idx)))


@pytest.mark.parametrize("B", [40, 17])
def test_nmp_bag_blocks_over_grid_steps(monkeypatch, B):
    """Where a table's (B, P) index tile would not fit SMEM, the NMP
    kernel blocks the bags over grid steps (here 3 blocks of 16 and 2 of
    16, the SMEM budget cut to 16 bags); bitwise the unblocked result."""
    from repro.kernels import embedding_bag as eb
    rng = np.random.RandomState(5)
    T, R, D, P = 3, 50, 16, 9
    tables = jnp.asarray(rng.randn(T, R, D), jnp.float32)
    idx = jnp.asarray(_mixed_pooling_idx(rng, R, B, T, P))
    flat, offsets = tables.reshape(T * R, D), jnp.arange(T) * R
    whole = np.asarray(eb.embedding_bag_nmp_flat(flat, offsets, idx))
    monkeypatch.setattr(eb, "NMP_SMEM_WORDS", 16 * 128)
    assert eb.nmp_bag_block(B, P) == 16
    blocked = np.asarray(eb.embedding_bag_nmp_flat(flat, offsets, idx))
    assert np.array_equal(blocked, whole)
    assert np.array_equal(blocked, np.asarray(ref.embedding_bag_seq_ref(
        tables, idx)))


@pytest.mark.parametrize("B,H,Hkv,S,D,qb,kb", [
    (1, 4, 4, 128, 32, 64, 64),
    (2, 8, 2, 256, 32, 64, 128),
    (2, 4, 1, 128, 64, 128, 32),
])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(B, H, Hkv, S, D, qb, kb, causal, dtype):
    rng = np.random.RandomState(1)
    q = jnp.asarray(rng.randn(B, H, S, D), dtype)
    k = jnp.asarray(rng.randn(B, Hkv, S, D), dtype)
    v = jnp.asarray(rng.randn(B, Hkv, S, D), dtype)
    o_k = np.asarray(ops.flash_attention(q, k, v, causal=causal,
                                         q_block=qb, kv_block=kb), np.float32)
    o_r = np.asarray(ref.flash_attention_ref(q, k, v, causal=causal),
                     np.float32)
    tol = 2e-5 if dtype == jnp.float32 else 0.05
    np.testing.assert_allclose(o_k, o_r, atol=tol, rtol=tol)


@pytest.mark.parametrize("B,H,Hkv,T,D,kb", [
    (2, 8, 2, 128, 32, 32), (1, 4, 4, 256, 64, 64), (3, 6, 2, 96, 16, 32),
])
@pytest.mark.parametrize("pos_frac", [0.1, 0.5, 1.0])
def test_flash_decode_sweep(B, H, Hkv, T, D, kb, pos_frac):
    rng = np.random.RandomState(2)
    q = jnp.asarray(rng.randn(B, H, D), jnp.float32)
    kc = jnp.asarray(rng.randn(B, T, Hkv, D), jnp.float32)
    vc = jnp.asarray(rng.randn(B, T, Hkv, D), jnp.float32)
    pos = jnp.asarray(int(pos_frac * (T - 1)), jnp.int32)
    o1, l1, m1 = ops.flash_decode_partial(q, kc, vc, pos, kv_block=kb)
    o2, l2, m2 = ref.flash_decode_ref(q, kc, vc, pos)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l2),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(m1), np.asarray(m2),
                               atol=1e-5, rtol=1e-5)


def test_flash_decode_combine_matches_full():
    """Partial kernel + combine == normalized reference attention, and
    shard-split partials combine to the same result (the Fsum pattern)."""
    from repro.models.layers import combine_partials
    rng = np.random.RandomState(3)
    B, H, Hkv, T, D = 2, 8, 4, 128, 32
    q = jnp.asarray(rng.randn(B, H, D), jnp.float32)
    kc = jnp.asarray(rng.randn(B, T, Hkv, D), jnp.float32)
    vc = jnp.asarray(rng.randn(B, T, Hkv, D), jnp.float32)
    pos = jnp.asarray(100, jnp.int32)
    o, l, m = ops.flash_decode_partial(q, kc, vc, pos)
    full = np.asarray(o / np.maximum(np.asarray(l)[..., None], 1e-37))
    want = np.asarray(ref.decode_attention_full_ref(q, kc, vc, pos))
    np.testing.assert_allclose(full, want, atol=1e-4, rtol=1e-4)

    # split the cache in two "memory-node" shards; combine partials
    o1, l1, m1 = ops.flash_decode_partial(q, kc[:, :64], vc[:, :64], pos,
                                          kv_offset=0)
    o2, l2, m2 = ops.flash_decode_partial(q, kc[:, 64:], vc[:, 64:], pos,
                                          kv_offset=64)
    mg = np.maximum(m1, m2)
    c1, c2 = np.exp(m1 - mg), np.exp(m2 - mg)
    lg = l1 * c1 + l2 * c2
    og = (np.asarray(o1) * np.asarray(c1)[..., None]
          + np.asarray(o2) * np.asarray(c2)[..., None])
    np.testing.assert_allclose(og / np.maximum(lg, 1e-37)[..., None], want,
                               atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("backend,want", [("cpu", True), ("tpu", False),
                                          ("gpu", None)])
def test_default_interpret_follows_backend(monkeypatch, backend, want):
    """Interpret on cpu, compiled on tpu, and no silent fallback on any
    other backend."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    if want is None:
        with pytest.raises(RuntimeError, match="gpu"):
            ops._default_interpret()
    else:
        assert ops._default_interpret() is want
