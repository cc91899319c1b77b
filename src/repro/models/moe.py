"""Token-choice top-k MoE with capacity.

Two dispatch paths:

- **Local (single host / tests)**: scatter/gather into an (E, C, d) buffer.
- **Distributed (`moe_ctx` given)**: the dispatch and combine run inside
  ``jax.shard_map`` over the data axes — each data shard routes its local
  tokens into a *local* capacity slice (E, C_loc, d), the shards concatenate
  into the global (E, C, d) buffer along the capacity dim, and the expert
  matmuls run under pjit with expert weights sharded over 'model'
  (expert-parallel) or 2-D (d×'data', f×'model') when E doesn't divide the
  axis. GSPMD cannot shard a scatter whose indexed dim is partitioned —
  without shard_map the dispatch buffer materializes at *global* capacity
  per device (60 GiB for grok-1 train_4k), which is why this path exists.

``moe_ctx = {"mesh": Mesh, "dp": axis-or-tuple}`` is threaded from
launch/steps.py through loss_fn.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.common import activation, fan_in_init
from repro.types import MoEConfig


def init_moe_params(key, d_model: int, d_ff: int, moe: MoEConfig,
                    num_layers: int, dtype=jnp.float32):
    init = fan_in_init()
    ks = jax.random.split(key, 7)
    L, E = num_layers, moe.num_experts
    p = {
        "router": init(ks[0], (L, d_model, E), dtype),
        "wg": init(ks[1], (L, E, d_model, d_ff), dtype),
        "wi": init(ks[2], (L, E, d_model, d_ff), dtype),
        "wo": init(ks[3], (L, E, d_ff, d_model), dtype),
    }
    if moe.shared_expert:
        p["shared_wg"] = init(ks[4], (L, d_model, d_ff), dtype)
        p["shared_wi"] = init(ks[5], (L, d_model, d_ff), dtype)
        p["shared_wo"] = init(ks[6], (L, d_ff, d_model), dtype)
    return p


def capacity(num_tokens: int, moe: MoEConfig) -> int:
    # pure python shape math on the (static) token count: C is a compile-
    # time constant inside the traced dispatch, not a device sync.
    # repro-lint: disable=R2
    return int(math.ceil(num_tokens / moe.num_experts
                         * moe.capacity_factor * moe.top_k))


def _route(router_w, xt, moe: MoEConfig, C: int):
    """Local routing: returns (weights (T,k), slot (T*k,), keep (T*k,),
    frac (E,), mean_p (E,))."""
    E, k = moe.num_experts, moe.top_k
    T = xt.shape[0]
    logits = jnp.einsum("td,de->te", xt,
                        router_w.astype(xt.dtype)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    weights, expert_idx = jax.lax.top_k(probs, k)
    weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    e_flat = expert_idx.reshape(T * k)
    onehot = jax.nn.one_hot(e_flat, E, dtype=jnp.int32)
    pos = jnp.sum(jnp.cumsum(onehot, axis=0) * onehot, axis=-1) - 1
    keep = pos < C
    slot = jnp.where(keep, e_flat * C + jnp.minimum(pos, C - 1), E * C)
    frac = jnp.mean(jax.nn.one_hot(expert_idx[:, 0], E, dtype=jnp.float32),
                    axis=0)
    mean_p = jnp.mean(probs, axis=0)
    return weights, slot, keep, frac, mean_p


def _dispatch(x_rep, slot, E, C):
    """(T*k, d) token copies -> (E, C, d) buffer (extra row = drop bin)."""
    d = x_rep.shape[-1]
    buf = jnp.zeros((E * C + 1, d), x_rep.dtype).at[slot].set(x_rep)
    return buf[: E * C].reshape(E, C, d)


def _combine(out_e, slot, keep, weights, T, k):
    d = out_e.shape[-1]
    out_pad = jnp.concatenate(
        [out_e.reshape(-1, d), jnp.zeros((1, d), out_e.dtype)], 0)
    g = out_pad[slot] * keep[:, None].astype(out_e.dtype)
    return jnp.sum(g.reshape(T, k, d)
                   * weights.reshape(T, k, 1).astype(out_e.dtype), axis=1)


def _expert_ffn(p, eb, act):
    dt = eb.dtype
    g = jnp.einsum("ecd,edf->ecf", eb, p["wg"].astype(dt))
    h = jnp.einsum("ecd,edf->ecf", eb, p["wi"].astype(dt))
    y = activation(act)(g) * h
    return jnp.einsum("ecf,efd->ecd", y, p["wo"].astype(dt))


def _pmean(v, names):
    for n in (names if isinstance(names, tuple) else (names,)):
        v = jax.lax.pmean(v, n)
    return v


def moe_forward(p, x, moe: MoEConfig, act: str = "silu", moe_ctx=None,
                dropless: bool = False):
    """x: (B, S, d) -> (out (B, S, d), aux_loss scalar).

    ``dropless=True`` (inference: prefill/decode) sizes capacity at C = T
    so no token is ever dropped: top_k picks *distinct* experts per token,
    so an expert holds at most T assignments. Routing then has no
    cross-token interaction at all — each token's output depends only on
    its own router logits — which is what makes batched/bucketed serving
    prefill bit-identical to single-request runs (docs/serving.md).
    Capacity dropping stays a train-time load-balancing concern.
    """
    B, S, d = x.shape
    T = B * S
    E, k = moe.num_experts, moe.top_k
    xt = x.reshape(T, d)

    if moe_ctx is None:
        # ---- local path (tests / single host) ----
        C = T if dropless else capacity(T, moe)
        weights, slot, keep, frac, mean_p = _route(p["router"], xt, moe, C)
        x_rep = jnp.repeat(xt, k, axis=0)
        eb = _dispatch(x_rep, slot, E, C)
        out_e = _expert_ffn(p, eb, act)
        out = _combine(out_e, slot, keep, weights, T, k)
    else:
        # ---- distributed path: per-data-shard dispatch, pjit expert FFN ----
        mesh, dp = moe_ctx["mesh"], moe_ctx["dp"]

        def disp(router_w, xt_loc):
            T_loc = xt_loc.shape[0]
            C_loc = capacity(T_loc, moe)
            weights, slot, keep, frac, mean_p = _route(router_w, xt_loc,
                                                       moe, C_loc)
            x_rep = jnp.repeat(xt_loc, k, axis=0)
            eb = _dispatch(x_rep, slot, E, C_loc)
            return eb, weights, slot, keep, _pmean(frac, dp), \
                _pmean(mean_p, dp)

        eb, weights, slot, keep, frac, mean_p = jax.shard_map(
            disp, mesh=mesh,
            in_specs=(P(None, None), P(dp, None)),
            out_specs=(P(None, dp, None), P(dp, None), P(dp), P(dp),
                       P(), P()),
            check_vma=False,
        )(p["router"], xt)

        out_e = _expert_ffn(p, eb, act)

        def comb(out_loc, weights, slot, keep):
            T_loc = weights.shape[0]
            return _combine(out_loc, slot, keep, weights, T_loc, k)

        out = jax.shard_map(
            comb, mesh=mesh,
            in_specs=(P(None, dp, None), P(dp, None), P(dp), P(dp)),
            out_specs=P(dp, None),
            check_vma=False,
        )(out_e, weights, slot, keep)

    out = out.reshape(B, S, d)
    if moe.shared_expert:
        dt = x.dtype
        g = jnp.einsum("bsd,df->bsf", x, p["shared_wg"].astype(dt))
        hh = jnp.einsum("bsd,df->bsf", x, p["shared_wi"].astype(dt))
        out = out + jnp.einsum("bsf,fd->bsd", activation(act)(g) * hh,
                               p["shared_wo"].astype(dt))

    # switch-style load-balance aux loss
    aux = E * jnp.sum(frac * mean_p) * moe.router_aux_weight
    return out, aux
