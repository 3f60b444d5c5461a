"""jit'd public wrappers for the Pallas kernels.

``interpret=None`` picks the mode from the default backend: interpret
mode on ``cpu`` (the Pallas body executes as traced JAX — the tests'
setting), compiled Mosaic kernels on ``tpu``.  Any other backend is an
error: there is no silent fallback that would hide a missing chip.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import embedding_bag as _eb
from repro.kernels import flash_attention as _fa
from repro.kernels import flash_decode as _fd


def _default_interpret() -> bool:
    backend = jax.default_backend()
    if backend not in ("cpu", "tpu"):
        raise RuntimeError(f"Pallas kernels run compiled on tpu or "
                           f"interpreted on cpu, not on {backend!r}")
    return backend == "cpu"


@functools.partial(jax.jit, static_argnames=("interpret",))
def embedding_bag(tables, idx, interpret: bool = None):
    if interpret is None:
        interpret = _default_interpret()
    return _eb.embedding_bag(tables, idx, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def embedding_bag_fused(tables, idx, interpret: bool = None):
    if interpret is None:
        interpret = _default_interpret()
    return _eb.embedding_bag_fused(tables, idx, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def embedding_bag_fused_flat(flat_table, offsets, idx, lens=None,
                             interpret: bool = None):
    if interpret is None:
        interpret = _default_interpret()
    return _eb.embedding_bag_fused_flat(flat_table, offsets, idx, lens,
                                        interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def embedding_bag_nmp(tables, idx, interpret: bool = None):
    if interpret is None:
        interpret = _default_interpret()
    return _eb.embedding_bag_nmp(tables, idx, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def embedding_bag_nmp_flat(flat_table, offsets, idx, lens=None,
                           interpret: bool = None):
    if interpret is None:
        interpret = _default_interpret()
    return _eb.embedding_bag_nmp_flat(flat_table, offsets, idx, lens,
                                      interpret=interpret)


@functools.partial(jax.jit, static_argnames=("causal", "q_block",
                                             "kv_block", "interpret"))
def flash_attention(q, k, v, causal: bool = True, q_block: int = 128,
                    kv_block: int = 128, interpret: bool = None):
    if interpret is None:
        interpret = _default_interpret()
    return _fa.flash_attention(q, k, v, causal=causal, q_block=q_block,
                               kv_block=kv_block, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("kv_offset", "kv_block",
                                             "interpret"))
def flash_decode_partial(q, k_cache, v_cache, pos, kv_offset: int = 0,
                         kv_block: int = 256, interpret: bool = None):
    if interpret is None:
        interpret = _default_interpret()
    return _fd.flash_decode_partial(q, k_cache, v_cache, pos,
                                    kv_offset=kv_offset,
                                    kv_block=kv_block, interpret=interpret)
