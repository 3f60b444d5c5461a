"""Plain reference of a DLRM-DCNv2 forward pass (torchrec ``DLRM_DCN``
with ``LowRankCrossNet``; DCN-V2, Wang et al., arXiv 2008.13535), in
straightforward ``jax.numpy`` float32 with no kernel, sharding or
batching of its own.

    pooled[b, t] = sum of table t's rows idx[b, t, p] over valid p (>= 0)
    bot          = bottom MLP(dense)          (ReLU after every layer)
    x0           = [bot, pooled_1 .. pooled_T]           (width (T + 1) D)
    x_{l+1}      = x0 * (x_l V_l W_l + b_l) + x_l        (l < L)
    score        = sigmoid(top MLP(x_L))      (ReLU after all but last)

Every contraction runs at ``highest`` precision (full float32); with
``passes=3`` each is computed from three bfloat16 products, as
``references/dlrm.py`` does: the control, one step below float32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench.references.dlrm import _einsum


def _mlp(layers, x, passes, relu_last):
    n = len(layers) // 2
    for i in range(n):
        x = _einsum("bi,io->bo", x, layers[f"w{i}"], passes) + layers[f"b{i}"]
        if i < n - 1 or relu_last:
            x = jnp.maximum(x, 0.0)
    return x


@functools.partial(jax.jit, static_argnames=("passes",))
def scores(w, dense, idx, passes=6):
    """Scores (B,) of rows ``dense`` (B, F) and ``idx`` (B, T, P)."""
    pooled = []
    for t in range(idx.shape[1]):
        ix = idx[:, t]
        rows = w["embed"][f"t{t}"][jnp.maximum(ix, 0)]
        pooled.append(jnp.where((ix >= 0)[..., None], rows, 0.0).sum(axis=1))
    bot = _mlp(w["bottom"], dense, passes, relu_last=True)
    x0 = jnp.concatenate([bot] + pooled, axis=-1)
    x, c = x0, w["cross"]
    for i in range(len(c) // 3):
        low = _einsum("bw,wr->br", x, c[f"v{i}"], passes)
        x = x0 * (_einsum("br,rw->bw", low, c[f"w{i}"], passes)
                  + c[f"b{i}"]) + x
    return jax.nn.sigmoid(_mlp(w["top"], x, passes, relu_last=False)[:, 0])
