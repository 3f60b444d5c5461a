"""Expert-parallel MoE FFN.

DisaggRec mapping: experts are the "memory nodes" — large parameter pools
touched sparsely per token. Expert weights shard over the ``model`` mesh
axis (EP); activations stay replicated across that axis, each shard
computes only its local experts' contribution for every token, and the
combine is a single psum — the near-memory-reduction / Fsum pattern
(expert outputs are reduced *at the expert shard* before crossing the
network; only (T, d) crosses, never (T, k, d) per-expert outputs).

Routing uses capacity-bounded greedy dispatch: position-in-expert via
one-hot cumsum, drop beyond capacity — the software analogue of the
paper's MemAccess routing table balancing accesses across MNs.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.distributed import sharding as shd
from repro.models.params import Spec


def moe_table(cfg) -> dict:
    m = cfg.moe
    E = m.padded_experts
    d = cfg.d_model
    t = {
        "router": Spec((d, E), ("embed", None), "normal:0.02"),
        "wi_gate": Spec((E, d, m.d_ff_expert), ("experts", "embed", "expert_ffn")),
        "wi_up": Spec((E, d, m.d_ff_expert), ("experts", "embed", "expert_ffn")),
        "wo": Spec((E, m.d_ff_expert, d), ("experts", "expert_ffn", "embed")),
    }
    if m.num_shared_experts:
        t["shared"] = {
            "wi_gate": Spec((d, m.d_ff_shared), ("embed", "ffn")),
            "wi_up": Spec((d, m.d_ff_shared), ("embed", "ffn")),
            "wo": Spec((m.d_ff_shared, d), ("ffn", "embed")),
            "gate": Spec((d, 1), ("embed", None), "zeros"),
        }
    return t


def _route(x2d, router, cfg):
    """Router logits -> (weights, ids, aux_loss). Padding experts masked."""
    m = cfg.moe
    E, Ep = m.num_experts, m.padded_experts
    logits = jnp.einsum("td,de->te", x2d.astype(jnp.float32), router.astype(jnp.float32))
    if Ep > E:
        logits = jnp.where(jnp.arange(Ep) < E, logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    w, ids = jax.lax.top_k(probs, m.top_k)
    w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)
    # load-balancing aux loss (Switch-style) over real experts
    density = jnp.mean(jax.nn.one_hot(ids, Ep), axis=(0, 1))[:E]
    mean_prob = jnp.mean(probs[:, :E], axis=0)
    aux = E * jnp.sum(density * mean_prob)
    return w.astype(x2d.dtype), ids, aux


def _expert_compute(xbuf, wg, wu, wo):
    """xbuf: (E_loc, C, d) -> (E_loc, C, d) through SwiGLU experts."""
    g = jnp.einsum("ecd,edf->ecf", xbuf, wg)
    u = jnp.einsum("ecd,edf->ecf", xbuf, wu)
    h = jax.nn.silu(g.astype(jnp.float32)).astype(xbuf.dtype) * u
    return jnp.einsum("ecf,efd->ecd", h, wo)


def _moe_local(x2d, w, ids, wg, wu, wo, *, e_off, E_loc, capacity, cfg,
               axis: Optional[str]):
    """Dispatch local tokens to local experts, compute, combine, psum."""
    T, d = x2d.shape
    k = cfg.moe.top_k
    Ep = cfg.moe.padded_experts
    C = capacity

    fid = ids.reshape(T * k)
    fw = w.reshape(T * k)
    tok = jnp.arange(T * k, dtype=jnp.int32) // k
    # position of each (token, expert) pair within its expert's queue
    onehot = jax.nn.one_hot(fid, Ep, dtype=jnp.float32)
    pos = (jnp.cumsum(onehot, axis=0) * onehot).sum(-1).astype(jnp.int32) - 1
    keep = pos < C
    local = (fid >= e_off) & (fid < e_off + E_loc) & keep
    slot = (fid - e_off) * C + jnp.clip(pos, 0, C - 1)
    slot = jnp.where(local, slot, E_loc * C)           # dump row

    # scatter SCALAR token ids into slots, then gather rows once — the
    # payload never materializes at (T*k, d)
    tok_of = jnp.full((E_loc * C + 1,), T, jnp.int32).at[slot].set(tok)
    w_of = jnp.zeros((E_loc * C + 1,), fw.dtype).at[slot].set(fw)
    xpad = jnp.concatenate([x2d, jnp.zeros((1, d), x2d.dtype)], axis=0)
    xbuf = jnp.take(xpad, tok_of[: E_loc * C], axis=0)
    out = _expert_compute(xbuf.reshape(E_loc, C, d), wg, wu, wo)
    out = out.reshape(E_loc * C, d)

    y = jnp.zeros((T + 1, d), x2d.dtype).at[tok_of[: E_loc * C]].add(
        out * w_of[: E_loc * C, None])[:T]
    if axis is not None:
        y = jax.lax.psum(y, axis)                      # Fsum combine
    return y


def moe_apply(p, x, cfg, *, capacity_factor: Optional[float] = None):
    """MoE FFN. x: (B, S, d) (or (B, 1, d) decode). Returns (y, aux)."""
    B, S, d = x.shape
    m = cfg.moe
    if capacity_factor is None:
        capacity_factor = m.capacity_factor
    x2d = x.reshape(B * S, d)
    w, ids, aux = _route(x2d, p["router"], cfg)

    mesh = shd.current_mesh()
    ep = shd.axis_size("model") if mesh is not None else 1
    # only use EP when the experts rule actually maps to the mesh
    use_ep = (
        mesh is not None and ep > 1
        and shd.resolve(("experts",)) == P("model")
        and m.padded_experts % ep == 0
    )
    T_tok = B * S
    if use_ep:
        from repro.models.layers import batch_pspec_entry
        E_loc = m.padded_experts // ep
        bspec = batch_pspec_entry(T_tok, mesh)
        baxes = () if bspec is None else (
            (bspec,) if isinstance(bspec, str) else tuple(bspec))
        nshards = 1
        for a in baxes:
            nshards *= mesh.shape[a]
        t_loc = T_tok // nshards
        cap = max(8, int((t_loc * m.top_k / m.num_experts) * capacity_factor))
        cap = -(-cap // 8) * 8

        def f(x2d, w, ids, wg, wu, wo):
            e_off = jax.lax.axis_index("model") * E_loc
            return _moe_local(x2d, w, ids, wg, wu, wo, e_off=e_off,
                              E_loc=E_loc, capacity=cap, cfg=cfg, axis="model")

        y = jax.shard_map(
            f, mesh=mesh,
            in_specs=(P(bspec, None), P(bspec, None), P(bspec, None),
                      P("model", None, None), P("model", None, None),
                      P("model", None, None)),
            out_specs=P(bspec, None),
            check_vma=False,
        )(x2d, w, ids, p["wi_gate"], p["wi_up"], p["wo"])
    else:
        cap = max(8, int((T_tok * m.top_k / m.num_experts) * capacity_factor))
        y = _moe_local(x2d, w, ids, p["wi_gate"], p["wi_up"], p["wo"],
                       e_off=0, E_loc=m.padded_experts, capacity=cap,
                       cfg=cfg, axis=None)

    if m.num_shared_experts:
        s = p["shared"]
        g = jnp.einsum("td,df->tf", x2d, s["wi_gate"])
        u = jnp.einsum("td,df->tf", x2d, s["wi_up"])
        h = jax.nn.silu(g.astype(jnp.float32)).astype(x2d.dtype) * u
        sh = jnp.einsum("tf,fd->td", h, s["wo"])
        gate = jax.nn.sigmoid(
            jnp.einsum("td,dz->tz", x2d.astype(jnp.float32),
                       s["gate"].astype(jnp.float32)))
        y = y + sh * gate.astype(y.dtype)

    return y.reshape(B, S, d), aux
