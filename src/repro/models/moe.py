"""Mixture-of-Experts FFN with capacity-bounded, sort-based dispatch.

Expert parallelism ("EP") maps onto the production mesh's ``model`` axis: each
model shard owns ``E / mp`` experts; activations are replicated across the
model axis (they are data-sharded on ``data``), every shard computes only the
tokens routed to *its* experts via a sorted capacity buffer, and one
``psum`` over the model axis combines contributions — the same collective
footprint as a Megatron TP MLP, with balanced FLOPs in expectation.

Dispatch is MegaBlocks-style: flatten (token, k) assignments, rank tokens
within their expert by a sorted running count, and gather them into a dense
``(E_local, capacity, d)`` buffer so the expert matmuls are fixed-shape MXU
einsums.  Tokens beyond capacity are dropped (standard top-k MoE semantics);
tests use ``capacity_factor`` high enough for zero drops and compare against
the dense all-experts oracle.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro import scopes
from repro.models.layers import dense_init


def moe_init(key, cfg, dtype=jnp.float32):
    d, e, ff = cfg.d_model, cfg.n_experts, cfg.expert_d_ff
    ks = jax.random.split(key, 5)
    params = {
        "router": dense_init(ks[0], d, e, jnp.float32),  # router kept f32
        "wi": (jax.random.normal(ks[1], (e, d, ff)) / math.sqrt(d)).astype(dtype),
        "wg": (jax.random.normal(ks[2], (e, d, ff)) / math.sqrt(d)).astype(dtype),
        "wo": (jax.random.normal(ks[3], (e, ff, d)) / math.sqrt(ff)).astype(dtype),
    }
    if cfg.n_shared_experts:
        sff = cfg.expert_d_ff * cfg.n_shared_experts
        sk = jax.random.split(ks[4], 3)
        params["shared"] = {
            "wi": dense_init(sk[0], d, sff, dtype),
            "wg": dense_init(sk[1], d, sff, dtype),
            "wo": dense_init(sk[2], sff, d, dtype),
        }
    return params


@scopes.scoped(scopes.MOE_ROUTER)
def _route(router_w, xf, n_experts: int, k: int):
    """Top-k routing.  Returns (ids (t,k), weights (t,k), aux_loss)."""
    logits = (xf.astype(jnp.float32) @ router_w)                 # (t, E)
    probs = jax.nn.softmax(logits, axis=-1)
    w, ids = jax.lax.top_k(probs, k)                              # (t, k)
    w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)
    # Switch-style load-balance loss: E * sum_e f_e * p_e
    f = jnp.zeros((n_experts,), jnp.float32).at[ids.reshape(-1)].add(1.0)
    f = f / jnp.maximum(f.sum(), 1.0)
    p = probs.mean(0)
    aux = n_experts * jnp.sum(f * p)
    return ids, w.astype(xf.dtype), aux


def _expert_compute(xf, ids, w, wi, wg, wo, lo: int, cap: int):
    """Compute routed-expert output for experts [lo, lo + E_local).

    xf: (t, d); ids/w: (t, k); wi/wg: (E_local, d, ff); wo: (E_local, ff, d).
    Returns partial (t, d) containing only local experts' contributions.
    """
    t, d = xf.shape
    k = ids.shape[1]
    e_loc = wi.shape[0]
    with scopes.scope(scopes.MOE_DISPATCH):
        flat_ids = ids.reshape(-1)                                # (t*k,)
        flat_w = w.reshape(-1)
        local = (flat_ids >= lo) & (flat_ids < lo + e_loc)
        local_ids = jnp.where(local, flat_ids - lo, e_loc)        # sentinel e_loc
        # rank within expert group, computed on sorted order
        order = jnp.argsort(local_ids)                            # stable
        sorted_ids = local_ids[order]
        counts = jnp.zeros((e_loc + 1,), jnp.int32).at[local_ids].add(1)
        starts = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                                  jnp.cumsum(counts)[:-1]])
        rank_sorted = jnp.arange(t * k, dtype=jnp.int32) - starts[sorted_ids]
        rank = jnp.zeros((t * k,), jnp.int32).at[order].set(rank_sorted)
        keep = local & (rank < cap)
        slot = jnp.where(keep, local_ids * cap + rank, e_loc * cap)
        # scatter token rows into the capacity buffer (extra row = drop bin)
        tok_idx = jnp.arange(t * k, dtype=jnp.int32) // k
        buf_tok = jnp.full((e_loc * cap + 1,), t, jnp.int32).at[slot].set(
            jnp.where(keep, tok_idx, t))
        buf_tok = buf_tok[:-1]                                    # (e_loc*cap,)
        xpad = jnp.concatenate([xf, jnp.zeros((1, d), xf.dtype)], 0)
        xb = xpad[buf_tok].reshape(e_loc, cap, d)
    with scopes.scope(scopes.MOE_EXPERTS):
        # expert FFN (swiglu), fixed-shape einsums
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xb, wg.astype(xf.dtype)))
        h = h * jnp.einsum("ecd,edf->ecf", xb, wi.astype(xf.dtype))
        y = jnp.einsum("ecf,efd->ecd", h,
                       wo.astype(xf.dtype)).reshape(e_loc * cap, d)
    with scopes.scope(scopes.MOE_COMBINE):
        # combine back, weighted
        wpad = jnp.concatenate([flat_w, jnp.zeros((1,), xf.dtype)])
        slot_of_flat = jnp.where(keep, slot, e_loc * cap)
        ypad = jnp.concatenate([y, jnp.zeros((1, d), xf.dtype)], 0)
        contrib = (ypad[slot_of_flat]
                   * wpad[jnp.where(keep, jnp.arange(t * k), t * k)][:, None])
        return jnp.zeros((t, d), xf.dtype).at[tok_idx].add(
            jnp.where(keep[:, None], contrib, 0))


@scopes.scoped(scopes.MOE_EXPERTS)
def _shared_expert(params, x):
    h = jax.nn.silu(x @ params["wg"].astype(x.dtype)) * (x @ params["wi"].astype(x.dtype))
    return h @ params["wo"].astype(x.dtype)


def moe_ffn(params, x, cfg, *, model_axis: Optional[str] = None,
            ff_axes=None, capacity_factor: Optional[float] = 1.25):
    """MoE FFN.  x: (B, S, d) -> (out (B, S, d), aux_loss scalar).

    ``model_axis`` is set when called inside ``shard_map`` — expert weights
    arrive pre-sliced to the local shard and the combine psums over that axis.
    ``ff_axes`` (decode-path 2D expert sharding, §Perf iteration B): the
    per-expert hidden dim arrives additionally sliced over these mesh axes;
    valid only when tokens are REPLICATED across them (batch=1 decode), and
    the final psum then spans (model_axis,) + ff_axes.  Outside shard_map
    (mp=1 smoke tests) all experts are local.
    """
    b, s, d = x.shape
    xf = x.reshape(b * s, d)
    t = b * s
    k = cfg.experts_per_token
    ids, w, aux = _route(params["router"], xf, cfg.n_experts, k)
    if capacity_factor is None:
        cap = t          # no-drop: an expert can receive every token at most once
    else:
        cap = max(1, math.ceil(t * k / cfg.n_experts * capacity_factor))
    if model_axis is None:
        lo = 0
    else:
        e_loc = params["wi"].shape[0]
        lo = jax.lax.axis_index(model_axis) * e_loc
    out = _expert_compute(xf, ids, w, params["wi"], params["wg"], params["wo"],
                          lo, cap)
    if "shared" in params:
        # shared experts: d_ff sharded over the model axis when inside
        # shard_map (weights arrive pre-sliced), partial-summed by the same psum
        out = out + _shared_expert(params["shared"], xf)
    if model_axis is not None:
        axes = (model_axis,) + tuple(ff_axes or ())
        # reduce in the activation dtype: XLA upcasts the combine scatter-add
        # to f32, and psum-ing that doubles EP wire bytes (§Perf iteration C.1)
        with scopes.scope(scopes.MOE_COMBINE):
            out = jax.lax.psum(out.astype(x.dtype), axes)
        aux = jax.lax.pmean(aux, model_axis)
    return out.reshape(b, s, d), aux


def moe_ffn_dense_oracle(params, x, cfg):
    """Reference: every expert computes every token; combine by router weights."""
    b, s, d = x.shape
    xf = x.reshape(b * s, d)
    ids, w, aux = _route(params["router"], xf, cfg.n_experts, cfg.experts_per_token)
    h = jax.nn.silu(jnp.einsum("td,edf->etf", xf, params["wg"].astype(xf.dtype)))
    h = h * jnp.einsum("td,edf->etf", xf, params["wi"].astype(xf.dtype))
    y = jnp.einsum("etf,efd->etd", h, params["wo"].astype(xf.dtype))   # (E,t,d)
    comb = jnp.zeros((xf.shape[0], cfg.n_experts), xf.dtype)
    comb = comb.at[jnp.arange(xf.shape[0])[:, None], ids].set(w)
    out = jnp.einsum("te,etd->td", comb, y)
    if "shared" in params:
        out = out + _shared_expert(params["shared"], xf)
    return out.reshape(b, s, d), aux
