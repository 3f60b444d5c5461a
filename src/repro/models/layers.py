"""Shared transformer building blocks (pure-JAX, sharding-annotated).

Attention modes
---------------
train/prefill:  flash-style chunked causal attention (online softmax over
                KV blocks via lax.scan) — O(S·block) activation memory, so
                the 32k prefill dry-run provably fits HBM without a Pallas
                dependency on the CPU backend. The Pallas kernel
                (`repro.kernels.flash_attention`) is the TPU fast path.
decode:         sequence-sharded KV cache over the ``model`` mesh axis
                ("memory-node pool"): each shard attends over its local
                cache slice and only (max, sum, partial-V) cross the
                network — DisaggRec's near-memory reduction (Fsum) applied
                to LM serving.

Weight sharding is *rule-driven* (see distributed/sharding.py): the same
logical names resolve to head-TP, FSDP-over-data, or decode contracting-dim
sharding depending on the active rule set.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.distributed import sharding as shd
from repro.models.params import Spec

# ---------------------------------------------------------------- norms


def rmsnorm(x, scale, eps: float = 1e-6):
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * (1.0 + scale.astype(jnp.float32))).astype(dt)


def norm_table(d: int) -> Spec:
    return Spec((d,), ("embed",), "zeros")   # scale stored as (1 + s)


# ---------------------------------------------------------------- rope


def rope(x, pos, theta: float):
    """x: (..., S, H, D) or (..., H, D) with pos broadcastable to S."""
    d = x.shape[-1]
    half = d // 2
    freqs = jnp.arange(0, half, dtype=jnp.float32)
    inv = theta ** (-freqs / half)
    ang = pos[..., None].astype(jnp.float32) * inv          # (..., S, half)
    ang = ang[..., None, :]                                 # broadcast heads
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------- mlp


def mlp_table(d: int, f: int) -> dict:
    return {
        "wi_gate": Spec((d, f), ("embed", "ffn")),
        "wi_up": Spec((d, f), ("embed", "ffn")),
        "wo": Spec((f, d), ("ffn", "embed")),
    }


def mlp_apply(p, x):
    gate = jnp.einsum("...d,df->...f", x, p["wi_gate"])
    up = jnp.einsum("...d,df->...f", x, p["wi_up"])
    h = jax.nn.silu(gate.astype(jnp.float32)).astype(x.dtype) * up
    h = shd.lsc(h, "batch", "seq", "ffn")
    return jnp.einsum("...f,fd->...d", h, p["wo"])


# ---------------------------------------------------------------- attention


def attn_table(cfg) -> dict:
    hd = cfg.resolved_head_dim
    Hp = cfg.padded_heads
    t = {
        "wq": Spec((cfg.d_model, Hp, hd),
                   ("attn_din", "heads", "head_dim")),
        "wk": Spec((cfg.d_model, cfg.num_kv_heads, hd),
                   ("attn_din", "kv_heads", "head_dim")),
        "wv": Spec((cfg.d_model, cfg.num_kv_heads, hd),
                   ("attn_din", "kv_heads", "head_dim")),
        "wo": Spec((Hp, hd, cfg.d_model),
                   ("heads", "head_dim", "attn_dout")),
    }
    if cfg.attn_bias:
        t["bq"] = Spec((Hp, hd), ("heads", "head_dim"), "zeros")
        t["bk"] = Spec((cfg.num_kv_heads, hd), ("kv_heads", "head_dim"), "zeros")
        t["bv"] = Spec((cfg.num_kv_heads, hd), ("kv_heads", "head_dim"), "zeros")
    if cfg.qk_norm:
        t["q_norm"] = Spec((hd,), ("head_dim",), "zeros")
        t["k_norm"] = Spec((hd,), ("head_dim",), "zeros")
    return t


def head_mask(cfg, dtype):
    """(Hp,) mask zeroing padded heads' output path (and, via the chain
    rule, their weight grads). Padding is laid out WITHIN each kv group —
    group g holds H/kv real heads then pad slots — so the GQA q->kv
    mapping of the real heads is unchanged."""
    Hp, H, kv = cfg.padded_heads, cfg.num_heads, cfg.num_kv_heads
    if Hp == H:
        return None
    gp, g = Hp // kv, H // kv
    return ((jnp.arange(Hp) % gp) < g).astype(dtype)


def _project_qkv(p, x, cfg, pos):
    q = jnp.einsum("...d,dhk->...hk", x, p["wq"])
    k = jnp.einsum("...d,dhk->...hk", x, p["wk"])
    v = jnp.einsum("...d,dhk->...hk", x, p["wv"])
    if cfg.attn_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    if cfg.qk_norm:
        q = rmsnorm(q, p["q_norm"], cfg.norm_eps)
        k = rmsnorm(k, p["k_norm"], cfg.norm_eps)
    if pos is not None:  # rope (None for whisper encoder/cross paths)
        q = rope(q, pos, cfg.rope_theta)
        k = rope(k, pos, cfg.rope_theta)
    return q, k, v


def pick_block(S: int, target: int) -> int:
    """Largest divisor of S that is <= target (block-size helper)."""
    b = min(S, target)
    while S % b:
        b -= 1
    return b


def flash_attention_jnp(q, k, v, *, causal: bool, q_offset=0,
                        q_block: int = 512, kv_block: int = 1024,
                        kv_len: Optional[jax.Array] = None):
    """Blocked online-softmax attention. q: (B,S,H,D), k/v: (B,T,Hkv,D).

    GQA via head grouping; O(block) memory; optional running-length mask
    (kv_len) for decode-style use. Returns (B,S,H,D).
    """
    B, S, H, D = q.shape
    T, Hkv = k.shape[1], k.shape[2]
    G = H // Hkv
    scale = D ** -0.5
    qb = pick_block(S, q_block)
    kb = pick_block(T, kv_block)
    nq, nk = S // qb, T // kb

    qg = q.reshape(B, nq, qb, Hkv, G, D).transpose(1, 0, 3, 4, 2, 5)
    kg = k.reshape(B, nk, kb, Hkv, D).transpose(1, 0, 3, 2, 4)
    vg = v.reshape(B, nk, kb, Hkv, D).transpose(1, 0, 3, 2, 4)

    # Block positions come from loop-CARRIED counters, not scan indices:
    # index-derived masks are pure functions of the induction variable and
    # XLA hoists them, materializing per-(i,j) penalty tensors at s's full
    # shape across all steps (GBs at 32k seq / many heads).
    def q_step(iq, qblk):                              # (B,Hkv,G,qb,D)
        q_pos = q_offset + iq * qb + jnp.arange(qb)

        def kv_step(carry, kv_blk):
            m, l, acc, jk = carry
            kblk, vblk = kv_blk                        # (B,Hkv,kb,D)
            kpos = jk * kb + jnp.arange(kb)
            s = jnp.einsum("bhgqd,bhkd->bhgqk", qblk, kblk,
                           preferred_element_type=jnp.float32) * scale
            penalty = jnp.zeros((qb, kb), jnp.float32)
            if causal:
                penalty += jnp.where(q_pos[:, None] >= kpos[None, :],
                                     0.0, -1e30)
            if kv_len is not None:
                penalty += jnp.where(kpos[None, :] < kv_len, 0.0, -1e30)
            s = s + penalty
            # clamp: keeps fully-masked blocks nan-free (p and corr -> 0)
            m_new = jnp.maximum(jnp.maximum(m, s.max(-1)), -1e30)
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bhgqk,bhkd->bhgqd", p.astype(vblk.dtype), vblk)
            return (m_new, l_new, acc_new, jk + 1), None

        m0 = jnp.full((B, Hkv, G, qb), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((B, Hkv, G, qb), jnp.float32)
        a0 = jnp.zeros((B, Hkv, G, qb, D), jnp.float32)
        (m, l, acc, _), _ = jax.lax.scan(
            kv_step, (m0, l0, a0, jnp.zeros((), jnp.int32)), (kg, vg))
        out = acc / jnp.maximum(l, 1e-37)[..., None]
        return iq + 1, out.astype(q.dtype)

    # checkpoint per q-block: AD otherwise stacks every (q,kv) block's
    # score/prob tensors across both scan levels (GBs at 32k)
    _, outs = jax.lax.scan(jax.checkpoint(q_step),
                           jnp.zeros((), jnp.int32), qg)
    # outs: (nq, B, Hkv, G, qb, D) -> (B, S, H, D)
    return outs.transpose(1, 0, 4, 2, 3, 5).reshape(B, S, H, D)


def full_attention_ref(q, k, v, *, causal: bool, q_offset=0):
    """Unblocked reference (tests only)."""
    B, S, H, D = q.shape
    Hkv = k.shape[2]
    G = H // Hkv
    qg = q.reshape(B, S, Hkv, G, D)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k,
                   preferred_element_type=jnp.float32) * D ** -0.5
    if causal:
        qp = q_offset + jnp.arange(S)
        kp = jnp.arange(k.shape[1])
        s = jnp.where(qp[:, None] >= kp[None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(v.dtype), v)
    return o.reshape(B, S, H, D)


def context_parallel_attention(q, k, v, mesh, *, causal: bool = True,
                               axis: str = "model",
                               q_block: int = 512, kv_block: int = 1024):
    """Context-parallel attention for head-counts that cannot shard over
    the model axis (smollm 9H, whisper 20H): shard the QUERY sequence over
    `axis` — each rank runs flash over its S/n q rows against full KV —
    instead of replicating the whole attention 16x (found by the roofline:
    16x duplicated FLOPs in FSDP mode). KV is replicated (it fits; a KV
    ring is the next step at longer contexts).

    q: (B,S,H,D) logically global; k/v: (B,T,Hkv,D). Returns (B,S,H,D)
    sharded on S over `axis`.
    """
    n = mesh.shape[axis]
    B, S, H, D = q.shape
    s_loc = S // n
    bspec = batch_pspec_entry(B, mesh)

    def local(q_loc, k, v):
        off = jax.lax.axis_index(axis) * s_loc
        return flash_attention_jnp(
            q_loc, k, v, causal=causal, q_offset=off,
            q_block=min(q_block, s_loc), kv_block=min(kv_block, k.shape[1]))

    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(bspec, axis, None, None), P(bspec, None, None, None),
                  P(bspec, None, None, None)),
        out_specs=P(bspec, axis, None, None),
        check_vma=False,
    )(q, k, v)


def use_context_parallel(mesh, seq_len: int, axis: str = "model") -> bool:
    """CP applies when heads are NOT sharded (FSDP mode), the mesh has a
    model axis, and the sequence divides it (train/prefill only)."""
    if mesh is None or axis not in mesh.shape or mesh.shape[axis] <= 1:
        return False
    if shd.resolve(("heads",)) != shd.resolve((None,)):
        return False
    return seq_len > 1 and seq_len % mesh.shape[axis] == 0


# ------------------------------------------------- decode (seq-sharded KV)


def batch_pspec_entry(batch: int, mesh):
    """PartitionSpec entry for the batch dim under the active 'batch' rule,
    dropping axes the batch size cannot divide (e.g. global_batch=1)."""
    entry = shd.resolve(("batch",))[0]
    if entry is None:
        return None
    axes = (entry,) if isinstance(entry, str) else tuple(entry)
    keep = []
    prod = 1
    for a in axes:
        if batch % (prod * mesh.shape[a]) == 0:
            keep.append(a)
            prod *= mesh.shape[a]
    if not keep:
        return None
    return keep[0] if len(keep) == 1 else tuple(keep)


def decode_attention_local(q, k_cache, v_cache, pos, kv_offset=0):
    """Partial attention over a local cache slice.

    q: (B,H,D); caches: (B,T_loc,Hkv,D); pos: scalar current position
    (global); kv_offset: global position of this slice's first row.
    Returns partial (o, l, m) for cross-shard combination — the Fsum
    pattern: only (B,H,D)+(B,H)+(B,H) leave the shard.
    """
    B, H, D = q.shape
    Hkv = k_cache.shape[2]
    G = H // Hkv
    qg = q.reshape(B, Hkv, G, D)
    s = jnp.einsum("bhgd,bthd->bhgt", qg, k_cache,
                   preferred_element_type=jnp.float32) * D ** -0.5
    t = kv_offset + jnp.arange(k_cache.shape[1])
    s = jnp.where((t <= pos)[None, None, None, :], s, -jnp.inf)
    m = s.max(-1)
    p = jnp.exp(s - m[..., None])
    # rows may be fully masked on non-owner shards -> p=0, l=0 (safe)
    p = jnp.where(jnp.isfinite(m)[..., None], p, 0.0)
    l = p.sum(-1)
    o = jnp.einsum("bhgt,bthd->bhgd", p.astype(v_cache.dtype), v_cache)
    return o.reshape(B, H, D), l.reshape(B, H // Hkv * Hkv), m.reshape(B, H)


def combine_partials(o, l, m, axis_name: Optional[str]):
    """Combine flash-decode partials across a mesh axis (or locally)."""
    if axis_name is None:
        return (o / jnp.maximum(l, 1e-37)[..., None]).astype(o.dtype)
    m_glob = jax.lax.pmax(m, axis_name)
    corr = jnp.exp(m - m_glob)
    l_glob = jax.lax.psum(l * corr, axis_name)
    o_glob = jax.lax.psum(o * corr[..., None].astype(o.dtype), axis_name)
    return o_glob / jnp.maximum(l_glob, 1e-37)[..., None].astype(o.dtype)


def sharded_decode_attention(q, k_cache, v_cache, k_new, v_new, pos,
                             mesh, axis: str = "model"):
    """Decode attention over a sequence-sharded KV cache.

    The new token's KV is written with a plain dynamic_update_slice on the
    sharded cache (GSPMD masks the write to the owning shard and the
    buffer aliases in place — no cache copy); the attention itself is a
    shard_map with shard-local partial softmax + one psum of (o, l, m) —
    the Fsum pattern.
    """
    T = k_cache.shape[1]
    n_shards = mesh.shape[axis]
    t_loc = T // n_shards
    bspec = batch_pspec_entry(q.shape[0], mesh)

    from jax.sharding import NamedSharding

    k_cache = jax.lax.dynamic_update_index_in_dim(k_cache, k_new, pos, 1)
    v_cache = jax.lax.dynamic_update_index_in_dim(v_cache, v_new, pos, 1)
    cspec = P(bspec, axis, None, None)
    k_cache = jax.lax.with_sharding_constraint(
        k_cache, NamedSharding(mesh, cspec))
    v_cache = jax.lax.with_sharding_constraint(
        v_cache, NamedSharding(mesh, cspec))

    def local_fn(q, kc, vc, pos):
        pos = pos.reshape(())
        off = jax.lax.axis_index(axis) * t_loc
        o, l, m = decode_attention_local(q, kc, vc, pos, kv_offset=off)
        return (combine_partials(o, l, m, axis),)

    qspec = P(bspec, None, None)
    (out,) = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(qspec, cspec, cspec, P()),
        out_specs=(qspec,),
        check_vma=False,
    )(q, k_cache, v_cache, pos)
    return out, k_cache, v_cache


def decode_attention_unsharded(q, k_cache, v_cache, k_new, v_new, pos):
    """Single-host path (tests / no-mesh)."""
    kc = jax.lax.dynamic_update_index_in_dim(k_cache, k_new, pos, 1)
    vc = jax.lax.dynamic_update_index_in_dim(v_cache, v_new, pos, 1)
    o, l, m = decode_attention_local(q, kc, vc, pos)
    return combine_partials(o, l, m, None), kc, vc


# ---------------------------------------------------------------- embed


def embed_table(vocab: int, d: int) -> Spec:
    return Spec((vocab, d), ("vocab", "embed"), "normal:0.02")


def embed_lookup(table, tokens):
    return jnp.take(table, tokens, axis=0)


def unembed(x, table_or_head, tied: bool):
    if tied:
        return jnp.einsum("...d,vd->...v", x, table_or_head)
    return jnp.einsum("...d,dv->...v", x, table_or_head)


def head_table(vocab: int, d: int) -> Spec:
    return Spec((d, vocab), ("embed", "vocab"))
