"""Plain reference of a DLRM forward pass (DisaggRec Fig. 1a; Naumov et
al., arXiv 1906.00091), in straightforward ``jax.numpy`` float32 with no
kernel, sharding or batching of its own.

    pooled[b, t] = sum of table t's rows idx[b, t, p] over valid p (>= 0)
    bot          = bottom MLP(dense)              (ReLU after all but last)
    proj[b, k]   = sum_t pooled[b, t] * P[t, k]    (K interaction channels)
    z            = [bot, proj_1 .. proj_K]         (K + 1 vectors of width D)
    x            = [bot, <z_f, z_g> for f < g]
    score        = sigmoid(top MLP(x))

Every contraction runs at ``highest`` precision (full float32).  With
``passes=3`` each contraction is computed as XLA's ``high`` precision
computes it, from three bfloat16 products (hi*hi + hi*lo + lo*hi of each
operand's split into two bfloat16 parts): the control, one step below
float32, written out so that it reads the same on every backend.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def _bf16(x):
    """``x`` rounded to bfloat16 (to nearest, ties to even) and kept as
    float32, done on its bits: a compiler may fold a float32 -> bfloat16
    -> float32 round trip away as excess precision, but not this."""
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    bits = bits + jnp.uint32(0x7FFF) + ((bits >> 16) & jnp.uint32(1))
    return jax.lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000),
                                        jnp.float32)


def _split(x):
    hi = _bf16(x)
    return hi, _bf16(x - hi)


def _einsum(spec, a, b, passes):
    if passes != 3:
        return jnp.einsum(spec, a, b, precision=HIGHEST)
    (ah, al), (bh, bl) = _split(a), _split(b)
    return (jnp.einsum(spec, ah, bh, precision=HIGHEST)
            + jnp.einsum(spec, ah, bl, precision=HIGHEST)
            + jnp.einsum(spec, al, bh, precision=HIGHEST))


def _mlp(layers, x, passes):
    n = len(layers) // 2
    for i in range(n):
        x = _einsum("bi,io->bo", x, layers[f"w{i}"], passes) + layers[f"b{i}"]
        if i < n - 1:
            x = jnp.maximum(x, 0.0)
    return x


@functools.partial(jax.jit, static_argnames=("passes",))
def scores(w, dense, idx, passes=6):
    """Scores (B,) of rows ``dense`` (B, F) and ``idx`` (B, T, P)."""
    T = idx.shape[1]
    rows = w["embed"][jnp.arange(T)[None, :, None], jnp.maximum(idx, 0)]
    pooled = jnp.where((idx >= 0)[..., None], rows, 0.0).sum(axis=2)
    bot = _mlp(w["bottom"], dense, passes)
    proj = _einsum("btd,tk->bkd", pooled, w["proj"], passes)
    z = jnp.concatenate([bot[:, None, :], proj], axis=1)
    zz = _einsum("bfd,bgd->bfg", z, z, passes)
    f, g = jnp.triu_indices(z.shape[1], k=1)
    x = jnp.concatenate([bot, zz[:, f, g]], axis=-1)
    return jax.nn.sigmoid(_mlp(w["top"], x, passes)[:, 0])
