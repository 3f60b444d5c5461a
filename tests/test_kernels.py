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
