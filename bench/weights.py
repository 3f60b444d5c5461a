"""Weights of a configuration, made on the device from the seed.

One jitted call turns the seed into every parameter, fp32, in the layout
the served model takes, as the configuration's family lists them
(``families.<family>.leaves``: path, shape and scale of each, in order).
Scales keep every stage near unit variance, so that the embedding path
carries a large share of each score and a fault in it shows: MLP weights
N(0, 1/fan_in), and biases N(0, 0.01^2) so that the bias path is checked
too.  The reference calls this again after the program's state is freed,
and gets the same arrays: the function is deterministic in the seed.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.families import family


def key_data(seed: int) -> np.ndarray:
    """The seed's 64 low bits as a threefry key (any whole seed)."""
    s = int(seed) & (2 ** 64 - 1)
    return np.asarray([s >> 32, s & 0xFFFFFFFF], np.uint32)


def mlp_leaves(dims: List[int]
               ) -> List[Tuple[str, Tuple[int, ...], float]]:
    """``w<i>`` (fan_in, fan_out) and ``b<i>`` of an MLP of widths
    ``dims``, with their scales."""
    out = []
    for i, (a, b) in enumerate(zip(dims[:-1], dims[1:])):
        out += [(f"w{i}", (a, b), a ** -0.5), (f"b{i}", (b,), 0.01)]
    return out


@functools.partial(jax.jit, static_argnums=0)
def _make(leaves, kd):
    key = jax.random.wrap_key_data(kd)
    out: Dict = {}
    for i, (path, shape, scale) in enumerate(leaves):
        x = jax.random.normal(jax.random.fold_in(key, i), shape,
                              jnp.float32) * scale
        node = out
        for p in path[:-1]:
            node = node.setdefault(p, {})
        node[path[-1]] = x
    return out


def make(cfg: Dict, seed: int) -> Dict:
    """Every parameter of ``cfg`` from ``seed``, on the default device."""
    return _make(tuple(family(cfg).leaves(cfg)),
                 jnp.asarray(key_data(seed)))
