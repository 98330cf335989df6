"""Unified decoder stack covering the dense / moe / ssm(rwkv) / hybrid / vlm /
audio families.

Layers are *stacked* (leading L dim on every leaf) and applied with
``jax.lax.scan`` so the HLO stays one-layer-sized for the 61/96-layer archs.
Three entry points share the block code:

    forward_train   (B,S) tokens -> (B,S,V) logits           [train / prefill-bench]
    prefill         also builds the KV/state cache
    decode_step     one token against the cache               [decode shapes]

``ParallelCtx`` carries mesh info so the MoE block can run its expert-parallel
shard_map; everything else distributes via GSPMD shardings assigned by
``repro.parallel.sharding``.
"""
from __future__ import annotations

import dataclasses
import functools
import warnings
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro import scopes
from repro.parallel.jaxcompat import shard_map
from jax.sharding import PartitionSpec as P

from repro.models import layers as L
from repro.models import moe as moe_mod
from repro.models import rwkv as rwkv_mod
from repro.models import ssm as ssm_mod


@dataclasses.dataclass(frozen=True)
class ParallelCtx:
    """Mesh context handed to blocks that need manual collectives (MoE EP)."""

    mesh: Any = None
    batch_axes: tuple = ("data",)     # mesh axes the batch dim is sharded over
    model_axis: Optional[str] = None  # None => mp=1, no shard_map
    moe_ff_axes: tuple = ()           # decode: 2D expert sharding (§Perf B)
    # tensor-MP collective runtime: "gspmd" lets the partitioner insert
    # monolithic all-reduces around the Megatron matmuls; "overlapped" routes
    # them through parallel.collectives' chunked ppermute rings
    comm_runtime: str = "gspmd"
    comm_chunks: int = 1              # ring chunks per shard (overlapped)
    # context parallelism: the mesh axis carrying the sequence-sharded KV
    # ring (parallel.context).  CP shards the sequence, not the weights, so
    # it is mutually exclusive with tensor-MP compute — the model axis hosts
    # the ring and every parameter stays replicated across it.
    context_axis: Optional[str] = None

    @property
    def ep(self) -> bool:
        return self.mesh is not None and self.model_axis is not None


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _attn_init(key, cfg, dtype, cross: bool = False):
    d, hd = cfg.d_model, cfg.head_dim
    nh, nkv = cfg.n_heads, cfg.n_kv_heads
    ks = jax.random.split(key, 4)
    return {
        "wq": L.dense_init(ks[0], d, nh * hd, dtype),
        "wk": L.dense_init(ks[1], d, nkv * hd, dtype),
        "wv": L.dense_init(ks[2], d, nkv * hd, dtype),
        "wo": L.dense_init(ks[3], nh * hd, d, dtype),
    }


def layer_init(key, cfg, dtype=jnp.float32):
    d = cfg.d_model
    ks = jax.random.split(key, 8)
    if cfg.rwkv:
        return rwkv_mod.rwkv_layer_init(key, cfg, dtype)
    p = {"ln1": jnp.ones((d,), jnp.float32), "ln2": jnp.ones((d,), jnp.float32)}
    p["attn"] = _attn_init(ks[0], cfg, dtype)
    if cfg.family == "hybrid":
        p["ssm"] = ssm_mod.ssm_init(ks[1], cfg, dtype)
        p["beta_attn"] = jnp.ones((d,), jnp.float32)
        p["beta_ssm"] = jnp.ones((d,), jnp.float32)
        p["ln_attn_out"] = jnp.ones((d,), jnp.float32)
        p["ln_ssm_out"] = jnp.ones((d,), jnp.float32)
    if cfg.encoder_layers:  # whisper decoder: cross attention
        p["lnx"] = jnp.ones((d,), jnp.float32)
        p["xattn"] = _attn_init(ks[2], cfg, dtype, cross=True)
    if cfg.is_moe:
        p["moe"] = moe_mod.moe_init(ks[3], cfg, dtype)
    else:
        p["mlp"] = L.mlp_init(ks[3], d, cfg.d_ff, cfg.mlp_kind, dtype)
    return p


def _encoder_layer_init(key, cfg, dtype):
    d = cfg.d_model
    ks = jax.random.split(key, 2)
    return {
        "ln1": jnp.ones((d,), jnp.float32), "ln2": jnp.ones((d,), jnp.float32),
        "attn": _attn_init(ks[0], cfg, dtype),
        "mlp": L.mlp_init(ks[1], d, cfg.d_ff, "gelu", dtype),
    }


def model_init(key, cfg):
    dtype = jnp.dtype(cfg.param_dtype)
    d, v = cfg.d_model, cfg.vocab_padded
    ks = jax.random.split(key, 8)
    params = {
        "embed": L.embed_init(ks[0], v, d, dtype),
        "final_norm": jnp.ones((d,), jnp.float32),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = L.dense_init(ks[1], d, v, dtype)
    lkeys = jax.random.split(ks[2], cfg.n_layers)
    params["layers"] = jax.vmap(lambda k: layer_init(k, cfg, dtype))(lkeys)
    if cfg.n_prefix_embeds:       # VLM: projector for precomputed patch embeds
        params["prefix_proj"] = L.dense_init(ks[3], d, d, dtype)
    if cfg.encoder_layers:        # whisper: encoder over stub frame embeddings
        ekeys = jax.random.split(ks[4], cfg.encoder_layers)
        params["encoder"] = {
            "layers": jax.vmap(lambda k: _encoder_layer_init(k, cfg, dtype))(ekeys),
            "pos_embed": (jax.random.normal(ks[5], (cfg.encoder_seq, d)) * 0.02
                          ).astype(dtype),
            "final_norm": jnp.ones((d,), jnp.float32),
        }
    return params


# ---------------------------------------------------------------------------
# caches
# ---------------------------------------------------------------------------

def make_cache(cfg, batch: int, capacity: int, *, window: int = 0,
               dtype=jnp.bfloat16):
    """Decode cache, stacked over layers.  ``window``>0 => ring buffer of that
    size.  RWKV/SSM carry recurrent state instead of KV."""
    Lc = cfg.n_layers
    cache = {"pos": jnp.zeros((), jnp.int32)}
    if cfg.rwkv:
        d, hd = cfg.d_model, cfg.head_dim or 64
        h = d // hd
        cache["wkv_S"] = jnp.zeros((Lc, batch, h, hd, hd), jnp.float32)
        cache["tm_x"] = jnp.zeros((Lc, batch, d), dtype)
        cache["cm_x"] = jnp.zeros((Lc, batch, d), dtype)
        return cache
    length = window if window else capacity
    cache["k"] = jnp.zeros((Lc, batch, length, cfg.n_kv_heads, cfg.head_dim), dtype)
    cache["v"] = jnp.zeros_like(cache["k"])
    if cfg.family == "hybrid":
        di = cfg.ssm_expand * cfg.d_model
        cache["ssm_h"] = jnp.zeros((Lc, batch, di, cfg.ssm_state), jnp.float32)
        cache["ssm_conv"] = jnp.zeros((Lc, batch, cfg.ssm_conv - 1, di), dtype)
    if cfg.encoder_layers:
        cache["xk"] = jnp.zeros((Lc, batch, cfg.encoder_seq, cfg.n_kv_heads,
                                 cfg.head_dim), dtype)
        cache["xv"] = jnp.zeros_like(cache["xk"])
    return cache


# ---------------------------------------------------------------------------
# blocks
# ---------------------------------------------------------------------------

def _cache_seq_sharded(cfg, cache_kv, pctx) -> bool:
    """Mirror of the flash-decode engagement condition (§Perf B.2/B.3)."""
    if pctx is None or pctx.mesh is None or pctx.model_axis is None:
        return False
    clen = cache_kv["k"].shape[1]
    return (clen % pctx.mesh.shape[pctx.model_axis] == 0 and clen >= 1024
            and not cfg.attn_logit_softcap)


def _batch_div(b, pctx, baxes) -> bool:
    n = 1
    for a in baxes:
        n *= pctx.mesh.shape[a]
    return n > 1 and b % n == 0


def _attn_batch_respec(pctx, cfg, b: int, t: int = 0):
    """When the head count does not divide the model axis (e.g. smollm's 15
    heads on 16-way MP), attention cannot be head-sharded — instead of
    replicating the quadratic attention work on every model shard, reshard
    around the attention einsums.  Two fallbacks, tried in order:

      1. batch-over-(dp x model): needs B % (dp*mp) == 0 (train_4k);
      2. sequence-over-model on the QUERY dim only (§Perf iteration A):
         q and out shard their time dim on the model axis while K/V stay
         replicated — each shard computes its S/mp query rows against all
         keys, which is exactly 1/mp of the work and is mask-correct for
         causal + sliding-window (masks are elementwise on iota positions).
         Needs T % mp == 0 (prefill_32k and train_4k both qualify).

    Returns (q_spec, kv_spec, out_spec) NamedShardings or (None,)*3.
    """
    if pctx is None or pctx.mesh is None or pctx.model_axis is None or not cfg.n_heads:
        return None, None, None
    msz = pctx.mesh.shape[pctx.model_axis]
    if cfg.n_heads % msz == 0:
        return None, None, None  # head sharding works; GSPMD handles it
    baxes = tuple(a for a in pctx.batch_axes if a)
    dp = 1
    for a in baxes:
        dp *= pctx.mesh.shape[a]
    NS = jax.sharding.NamedSharding
    if b % (dp * msz) == 0:
        inner = NS(pctx.mesh, P(baxes + (pctx.model_axis,), None, None, None))
        outer = NS(pctx.mesh, P(baxes or None, None, None, None))
        return inner, inner, outer
    if t and t % msz == 0 and t > msz:
        q_spec = NS(pctx.mesh, P(baxes or None, pctx.model_axis, None, None))
        outer = NS(pctx.mesh, P(baxes or None, None, None, None))
        # K/V must be pinned REPLICATED on the model axis: otherwise GSPMD
        # propagates q's seq-sharding onto them and lowers the KV-chunk
        # slicing as per-chunk halo collective-permutes (measured: 97
        # permutes/layer, 29 GB/layer wire — §Perf iteration A.2)
        return q_spec, outer, outer
    return None, None, None


@scopes.scoped(scopes.ATTN_PROJ)
def _self_attention(p, x, cfg, *, window: int, pos0, cache_kv=None,
                    cache_len=None, pctx=None):
    """Self-attention over x (+ optional cache for decode).

    Returns (out, (k_roped, v)) — roped keys for cache insertion.
    """
    b, t, d = x.shape
    nh, nkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = (x @ p["wq"].astype(x.dtype)).reshape(b, t, nh, hd)
    k = (x @ p["wk"].astype(x.dtype)).reshape(b, t, nkv, hd)
    v = (x @ p["wv"].astype(x.dtype)).reshape(b, t, nkv, hd)
    q_spec, kv_spec, out_spec = _attn_batch_respec(pctx, cfg, b, t)
    if q_spec is not None and cache_kv is None:
        q = jax.lax.with_sharding_constraint(q, q_spec)
        if kv_spec is not None:
            k = jax.lax.with_sharding_constraint(k, kv_spec)
            v = jax.lax.with_sharding_constraint(v, kv_spec)
    if jnp.ndim(pos0):
        positions = pos0[:, None] + jnp.arange(t)[None]          # (b, t)
    else:
        positions = jnp.broadcast_to(pos0 + jnp.arange(t), (b, t))
    q = L.apply_rope(q, positions, cfg.rope_theta)
    k = L.apply_rope(k, positions, cfg.rope_theta)
    if cache_kv is None:
        out = L.attention(q, k, v, causal=True, q_start=0, window=window,
                          softcap=cfg.attn_logit_softcap)
    elif jnp.ndim(cache_len) == 1:
        # slot mode (continuous batching): per-request positions against a
        # LINEAR cache of full capacity — a sliding window is enforced by
        # mask, not by ring storage, so mid-flight requests at different
        # positions coexist in one batch.  Valid keys for query i of row r
        # (absolute position pos_r + i): filled cache slots s < pos_r, plus
        # appended chunk tokens j <= i (causal within the chunk — this is
        # what makes multi-token chunked prefill against a cache correct).
        k_all = jnp.concatenate([cache_kv["k"], k], axis=1)
        v_all = jnp.concatenate([cache_kv["v"], v], axis=1)
        clen = cache_kv["k"].shape[1]
        slot = jnp.arange(clen + t)
        in_cache = slot < clen                                   # (clen+t,)
        qpos = positions                                         # (b, t)
        kpos = jnp.where(in_cache[None], slot[None],
                         pos0[:, None] + (slot[None] - clen))    # (b, clen+t)
        valid = jnp.where(in_cache[None, None],
                          slot[None, None, :] < pos0[:, None, None],
                          kpos[:, None, :] <= qpos[:, :, None])
        if window:
            valid &= kpos[:, None, :] > qpos[:, :, None] - window
        out = L.attention(q, k_all, v_all, mask=valid,
                          softcap=cfg.attn_logit_softcap)
    elif (pctx is not None and pctx.mesh is not None
          and pctx.model_axis is not None and t == 1
          and cache_kv["k"].shape[1] % pctx.mesh.shape[pctx.model_axis] == 0
          and cache_kv["k"].shape[1] >= 1024
          and not cfg.attn_logit_softcap):
        # flash-decode: KV cache sequence-sharded over the model axis
        # (§Perf iteration B.2) — partial softmax per shard, pmax/psum merge
        clen = cache_kv["k"].shape[1]
        slot = jnp.arange(clen)
        if window:
            # seq-sharded ring writes at pos % clen (see the insert below):
            # every written slot except the one about to be overwritten
            # (holding absolute position pos - clen, outside the window)
            cvalid = (slot < cache_len) & (slot != cache_len % clen)
        else:
            cvalid = slot < cache_len
        cvalid = jnp.broadcast_to(cvalid, (b, clen))
        baxes = tuple(a for a in pctx.batch_axes if a)
        out = L.seq_sharded_decode_attention(
            q, cache_kv["k"], cache_kv["v"], cvalid, k, v,
            mesh=pctx.mesh, seq_axis=pctx.model_axis,
            batch_axes=baxes if _batch_div(b, pctx, baxes) else ())
        out = out.reshape(b, t, nh * hd)
        return out @ p["wo"].astype(x.dtype), (k, v)
    else:
        k_all = jnp.concatenate([cache_kv["k"], k], axis=1)
        v_all = jnp.concatenate([cache_kv["v"], v], axis=1)
        clen = cache_kv["k"].shape[1]
        slot = jnp.arange(clen + t)
        if window:
            # shift-left ring: the newest slots hold the most recent tokens;
            # the query (at absolute pos cache_len) sees positions in
            # (pos - window, pos], i.e. at most window-1 cache entries plus
            # itself — the oldest ring slot is always masked
            n_valid = jnp.minimum(cache_len, window - 1)
            valid = (slot >= clen - n_valid)
        else:
            # linear buffer: first cache_len slots valid + appended tokens
            valid = (slot < cache_len) | (slot >= clen)
        kv_mask = jnp.broadcast_to(valid, (b, clen + t))
        out = L.attention(q, k_all, v_all, causal=False, kv_mask=kv_mask,
                          softcap=cfg.attn_logit_softcap,
                          dense_threshold=max(8192, clen + t + 1))
    if q_spec is not None and cache_kv is None:
        out = jax.lax.with_sharding_constraint(out, out_spec)
    out = out.reshape(b, t, nh * hd)
    return out @ p["wo"].astype(x.dtype), (k, v)


def _cross_attention(p, x, enc_kv, cfg):
    b, t, d = x.shape
    nh, nkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = (x @ p["wq"].astype(x.dtype)).reshape(b, t, nh, hd)
    out = L.attention(q, enc_kv[0], enc_kv[1], causal=False,
                      dense_threshold=max(8192, enc_kv[0].shape[1] + 1))
    return out.reshape(b, t, nh * hd) @ p["wo"].astype(x.dtype)


def _enc_kv(p, enc_out, cfg):
    b, f, d = enc_out.shape
    nkv, hd = cfg.n_kv_heads, cfg.head_dim
    k = (enc_out @ p["wk"].astype(enc_out.dtype)).reshape(b, f, nkv, hd)
    v = (enc_out @ p["wv"].astype(enc_out.dtype)).reshape(b, f, nkv, hd)
    return k, v


def block_apply(cfg, p, x, *, mode: str, window: int, pos0, cache=None,
                enc_out=None, pctx: Optional[ParallelCtx] = None,
                rwkv_chunked: bool = False, capacity_factor=1.25):
    """One decoder block.  Returns (x, new_cache (or None), aux_loss)."""
    aux = jnp.zeros((), jnp.float32)
    new_cache = {}
    if cfg.rwkv:
        if mode == "decode":
            tm_out, tm_x, S = rwkv_mod.rwkv_time_mix(
                p["tm"], L.rms_norm(x, p["ln1"], cfg.norm_eps),
                cache["tm_x"], cache["wkv_S"], cfg)
            x = x + tm_out
            cm_out, cm_x = rwkv_mod.rwkv_channel_mix(
                p["cm"], L.rms_norm(x, p["ln2"], cfg.norm_eps), cache["cm_x"])
            x = x + cm_out
            new_cache = {"wkv_S": S, "tm_x": tm_x, "cm_x": cm_x}
        else:
            b, d = x.shape[0], x.shape[-1]
            zero = jnp.zeros((b, d), x.dtype)
            tm_out, tm_x, S = rwkv_mod.rwkv_time_mix(
                p["tm"], L.rms_norm(x, p["ln1"], cfg.norm_eps), zero, None, cfg,
                chunked=rwkv_chunked)
            x = x + tm_out
            cm_out, cm_x = rwkv_mod.rwkv_channel_mix(
                p["cm"], L.rms_norm(x, p["ln2"], cfg.norm_eps), zero)
            x = x + cm_out
            if mode == "prefill":
                new_cache = {"wkv_S": S, "tm_x": tm_x, "cm_x": cm_x}
        return x, new_cache, aux

    with scopes.scope(scopes.ATTN_PROJ):
        h = L.rms_norm(x, p["ln1"], cfg.norm_eps)
    if mode == "decode":
        cache_kv = {"k": cache["k"], "v": cache["v"]}
        attn_out, (k_new, v_new) = _self_attention(
            p["attn"], h, cfg, window=window, pos0=pos0, cache_kv=cache_kv,
            cache_len=pos0, pctx=pctx)
        seq_sharded = (_cache_seq_sharded(cfg, cache_kv, pctx)
                       and jnp.ndim(pos0) == 0)
        if jnp.ndim(pos0):
            # slot mode: always the per-row positional insert — the sliding
            # window (if any) was already applied as a mask above
            kv = L.cache_insert_at(cache_kv, k_new, v_new, pos0)
        elif window and not seq_sharded:
            kv = L.cache_insert_window(cache_kv, k_new, v_new)
        elif seq_sharded:
            # windowed ring caches also take the positional-insert path when
            # seq-sharded: write at pos % window (ring without the shift)
            clen = cache_kv["k"].shape[1]
            wpos = pos0 % clen if window else pos0
            baxes = tuple(a for a in pctx.batch_axes if a)
            ck, cv = L.seq_sharded_cache_insert(
                cache_kv["k"], cache_kv["v"], k_new, v_new, wpos,
                mesh=pctx.mesh, seq_axis=pctx.model_axis,
                batch_axes=baxes if _batch_div(x.shape[0], pctx, baxes) else ())
            kv = {"k": ck, "v": cv}
        else:
            kv = L.cache_insert_full(cache_kv, k_new, v_new, pos0)
        new_cache.update(kv)
    else:
        attn_out, (k_new, v_new) = _self_attention(
            p["attn"], h, cfg, window=window, pos0=pos0, pctx=pctx)
        if mode == "prefill":
            if window:
                w = window
                s_len = k_new.shape[1]
                n = min(s_len, w)
                if _cache_seq_sharded(cfg, {"k": jnp.zeros(
                        (1, w, 1, 1))}, pctx):
                    # positional ring layout (slot = pos % w) — matches the
                    # seq-sharded decode insert (§Perf B.3)
                    idx = jnp.arange(s_len - n, s_len) % w
                    ks = jnp.zeros((k_new.shape[0], w) + k_new.shape[2:],
                                   k_new.dtype).at[:, idx].set(k_new[:, -n:])
                    vs = jnp.zeros_like(ks).at[:, idx].set(v_new[:, -n:])
                else:
                    # shift-left layout (single-device serving engine)
                    pad = w - n
                    ks = jnp.pad(k_new[:, -w:],
                                 ((0, 0), (pad, 0), (0, 0), (0, 0)))
                    vs = jnp.pad(v_new[:, -w:],
                                 ((0, 0), (pad, 0), (0, 0), (0, 0)))
                new_cache.update({"k": ks, "v": vs})
            else:
                # per-layer cache slice: (B, capacity, KV, hd)
                cap = cache["k"].shape[1] if isinstance(cache, dict) else k_new.shape[1]
                ks = jnp.pad(k_new, ((0, 0), (0, cap - k_new.shape[1]), (0, 0), (0, 0)))
                vs = jnp.pad(v_new, ((0, 0), (0, cap - v_new.shape[1]), (0, 0), (0, 0)))
                new_cache.update({"k": ks, "v": vs})

    if cfg.family == "hybrid":
        ssm_state = None
        if mode == "decode":
            ssm_state = {"h": cache["ssm_h"], "conv": cache["ssm_conv"]}
        ssm_out, ssm_state_new = ssm_mod.ssm_apply(p["ssm"], h, cfg, ssm_state)
        attn_out = 0.5 * (
            L.rms_norm(attn_out, p["ln_attn_out"], cfg.norm_eps)
            * p["beta_attn"].astype(x.dtype)
            + L.rms_norm(ssm_out, p["ln_ssm_out"], cfg.norm_eps)
            * p["beta_ssm"].astype(x.dtype))
        if mode in ("decode", "prefill"):
            new_cache.update({"ssm_h": ssm_state_new["h"],
                              "ssm_conv": ssm_state_new["conv"]})
    with scopes.scope(scopes.ATTN_PROJ):
        x = x + attn_out

    if cfg.encoder_layers:
        hx = L.rms_norm(x, p["lnx"], cfg.norm_eps)
        if mode == "decode":
            enc_kv = (cache["xk"], cache["xv"])
            new_cache.update({"xk": cache["xk"], "xv": cache["xv"]})
        else:
            enc_kv = _enc_kv(p["xattn"], enc_out, cfg)
            if mode == "prefill":
                new_cache.update({"xk": enc_kv[0], "xv": enc_kv[1]})
        x = x + _cross_attention(p["xattn"], hx, enc_kv, cfg)

    # an MoE block's norm feeds the router, its residual add ends the combine
    with scopes.scope(scopes.MOE_ROUTER if cfg.is_moe else scopes.MLP):
        h2 = L.rms_norm(x, p["ln2"], cfg.norm_eps)
    if cfg.is_moe:
        # decode batches are tiny: use the no-drop capacity so cached decoding
        # is numerically identical to teacher-forced forward
        cf = None if mode == "decode" else capacity_factor
        if pctx is not None and pctx.ep:
            d, e = cfg.d_model, cfg.n_experts
            ma = pctx.model_axis
            fa = tuple(pctx.moe_ff_axes)
            fspec = fa if fa else None
            # 2D EP replicates the (tiny) decode activations across the ff
            # axes; otherwise tokens stay batch-sharded over the DP axes
            bspec = P(None, None, None) if fa else P(pctx.batch_axes, None, None)
            in_specs = (
                {"router": P(),
                 "wi": P(ma, None, fspec), "wg": P(ma, None, fspec),
                 "wo": P(ma, fspec, None),
                 **({"shared": {"wi": P(None, ma), "wg": P(None, ma),
                                "wo": P(ma, None)}} if "shared" in p["moe"] else {})},
                bspec)
            fn = functools.partial(moe_mod.moe_ffn, cfg=cfg, model_axis=ma,
                                   ff_axes=fa, capacity_factor=cf)
            mlp_out, moe_aux = shard_map(
                fn, mesh=pctx.mesh, in_specs=in_specs,
                out_specs=(bspec, P()))(p["moe"], h2)
        else:
            mlp_out, moe_aux = moe_mod.moe_ffn(p["moe"], h2, cfg,
                                               capacity_factor=cf)
        aux = aux + cfg.router_aux_loss * moe_aux
    else:
        mlp_out = L.mlp_apply(p["mlp"], h2, cfg.mlp_kind)
    with scopes.scope(scopes.MOE_COMBINE if cfg.is_moe else scopes.MLP):
        x = x + mlp_out
    return x, new_cache, aux


# ---------------------------------------------------------------------------
# overlapped tensor-MP block (comm_runtime="overlapped")
# ---------------------------------------------------------------------------

def overlapped_arch_supported(cfg) -> bool:
    """Arch classes whose decoder block the overlap-scheduled collective
    matmuls can execute: homogeneous dense blocks only (no MoE / SSM / RWKV
    / enc-dec / VLM prefix / CNN / RNN).  ONE predicate shared by the
    runtime gate below and the planner's credit gate
    (``core.planner.comm_runtime_supported``) so the two can never drift —
    the planner must not credit an overlap the runtime will not execute."""
    return not (cfg.is_moe or cfg.rwkv
                or cfg.family in ("hybrid", "ssm", "cnn", "rnn")
                or cfg.encoder_layers or cfg.n_prefix_embeds)


def overlapped_supported(cfg, pctx: Optional[ParallelCtx],
                         t: int) -> bool:
    """Can this (arch, mesh, shape) run the overlap-scheduled collective
    matmuls?  Requires ``overlapped_arch_supported``, q heads and FFN hidden
    divisible by the model axis, and the sequence divisible so the residual
    stream can stay sequence-sharded between blocks.  Anything else falls
    back to GSPMD — the ShardingRules fallback warning makes the perf cliff
    visible."""
    if (pctx is None or pctx.comm_runtime != "overlapped"
            or pctx.mesh is None or pctx.model_axis is None):
        return False
    msz = pctx.mesh.shape[pctx.model_axis]
    if msz <= 1:
        return False
    if not overlapped_arch_supported(cfg):
        return False
    return (cfg.n_heads > 0 and cfg.n_heads % msz == 0
            and cfg.d_ff % msz == 0 and t % msz == 0
            and t // msz % max(pctx.comm_chunks, 1) == 0)


@scopes.scoped(scopes.ATTN_PROJ)
def _self_attention_overlapped(p, x, cfg, *, window: int, axis: str, msz: int,
                               chunks: int):
    """Self-attention with q/k/v/o on the collective-matmul rings, for use
    inside the block shard_map.  ``x``: (B, T/m, d) sequence-sharded.  Query
    heads shard over ``axis``; KV heads shard too when divisible, otherwise
    every shard computes the full (small, GQA) KV from the gathered x —
    both cases ride the single qkv gather ring.  Output returns through a
    ``matmul_reduce_scatter`` (row-parallel wo)."""
    from repro.parallel.collectives import (all_gather_matmul,
                                            matmul_reduce_scatter)
    b, t_loc, d = x.shape
    t = t_loc * msz
    nh, nkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    hpm = nh // msz
    kv_sharded = nkv % msz == 0
    kvpm = nkv // msz if kv_sharded else nkv
    kw = dict(axis=axis, axis_size=msz, chunks=chunks)
    # one gather ring computes q (sharded) and k/v (sharded or replicated)
    w_qkv = jnp.concatenate(
        [p["wq"].astype(x.dtype), p["wk"].astype(x.dtype),
         p["wv"].astype(x.dtype)], axis=1)
    qkv = all_gather_matmul(x, w_qkv, **kw)              # (b, t, ...)
    q = qkv[..., :hpm * hd].reshape(b, t, hpm, hd)
    k = qkv[..., hpm * hd:(hpm + kvpm) * hd].reshape(b, t, kvpm, hd)
    v = qkv[..., (hpm + kvpm) * hd:].reshape(b, t, kvpm, hd)
    positions = jnp.arange(t)
    q = L.apply_rope(q, jnp.broadcast_to(positions, (b, t)), cfg.rope_theta)
    k = L.apply_rope(k, jnp.broadcast_to(positions, (b, t)), cfg.rope_theta)
    if not kv_sharded:
        # replicated KV: take the q-head-aligned slice of the repeated heads
        j = jax.lax.axis_index(axis)
        k = jax.lax.dynamic_slice_in_dim(L.repeat_kv(k, nh // nkv),
                                         j * hpm, hpm, axis=2)
        v = jax.lax.dynamic_slice_in_dim(L.repeat_kv(v, nh // nkv),
                                         j * hpm, hpm, axis=2)
    out = L.attention(q, k, v, causal=True, q_start=0, window=window,
                      softcap=cfg.attn_logit_softcap)
    out = out.reshape(b, t, hpm * hd)
    return matmul_reduce_scatter(out, p["wo"].astype(x.dtype), **kw)


def overlapped_block_apply(cfg, p, x, *, window: int,
                           pctx: ParallelCtx):
    """One dense decoder block with every Megatron matmul on the chunked
    collective rings, the residual stream sequence-sharded over the model
    axis end to end (train mode): ln1 -> qkv gather ring -> attention (full
    sequence per head shard) -> wo reduce ring -> residual -> ln2 -> MLP
    gather/reduce rings -> residual.  ``x`` enters and leaves (B, T, d)
    GSPMD-global, sharded P(batch, model, None) — stacking these blocks in
    the layer scan keeps the hot path free of monolithic collectives."""
    mesh, axis = pctx.mesh, pctx.model_axis
    msz = mesh.shape[axis]
    chunks = max(pctx.comm_chunks, 1)
    baxes = tuple(a for a in pctx.batch_axes if a)
    bspec = baxes if (baxes and _batch_div(x.shape[0], pctx, baxes)) else None
    kv_sharded = cfg.n_kv_heads % msz == 0

    def local(lp, xl):
        with scopes.scope(scopes.ATTN_PROJ):
            h = L.rms_norm(xl, lp["ln1"], cfg.norm_eps)
            xl = xl + _self_attention_overlapped(lp["attn"], h, cfg,
                                                 window=window, axis=axis,
                                                 msz=msz, chunks=chunks)
        with scopes.scope(scopes.MLP):
            h2 = L.rms_norm(xl, lp["ln2"], cfg.norm_eps)
            return xl + L.mlp_apply_overlapped(lp["mlp"], h2, cfg.mlp_kind,
                                               axis=axis, axis_size=msz,
                                               chunks=chunks)

    col, row = P(None, axis), P(axis, None)
    kv = col if kv_sharded else P(None, None)
    p_specs = {"ln1": P(None), "ln2": P(None),
               "attn": {"wq": col, "wk": kv, "wv": kv, "wo": row},
               "mlp": {k: (row if k == "wo" else col) for k in p["mlp"]}}
    xspec = P(bspec, axis, None)
    return shard_map(local, mesh=mesh, in_specs=(p_specs, xspec),
                     out_specs=xspec)(p, x)


# ---------------------------------------------------------------------------
# context-parallel block (sequence-sharded ring attention)
# ---------------------------------------------------------------------------

def cp_supported(cfg, pctx: Optional[ParallelCtx], t: int) -> bool:
    """Can this (arch, mesh, shape) run context-parallel ring attention?
    Requires a homogeneous dense decoder (same predicate as the overlapped
    runtime — ``overlapped_arch_supported``), no logit softcap (the ring's
    online-softmax fold has no capped variant), and the sequence divisible
    by the ring size so the residual stream stays sequence-sharded between
    blocks.  Anything else falls back to GSPMD."""
    if pctx is None or pctx.context_axis is None or pctx.mesh is None:
        return False
    csz = pctx.mesh.shape[pctx.context_axis]
    if csz <= 1:
        return False
    if not overlapped_arch_supported(cfg) or cfg.attn_logit_softcap:
        return False
    return cfg.n_heads > 0 and t % csz == 0


def cp_block_apply(cfg, p, x, *, window: int, pctx: ParallelCtx):
    """One dense decoder block with the residual stream SEQUENCE-sharded
    over the context axis and attention on the KV ppermute ring
    (``parallel.context.ring_attention``).  Unlike the tensor-MP overlapped
    block, every weight stays fully replicated across the ring — CP shards
    the sequence, not the parameters — so qkv/wo/MLP are plain local
    matmuls over this device's T/m rows and the ONLY communication in the
    compiled block is the ring's collective-permutes (fwd and bwd; HLO
    asserted in tests).  ``x`` enters and leaves (B, T, d) GSPMD-global,
    sharded P(batch, context, None)."""
    from repro.parallel.context import ring_attention
    mesh, axis = pctx.mesh, pctx.context_axis
    csz = mesh.shape[axis]
    baxes = tuple(a for a in pctx.batch_axes if a)
    bspec = baxes if (baxes and _batch_div(x.shape[0], pctx, baxes)) else None
    nh, nkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    t_loc = x.shape[1] // csz

    def local(lp, xl):
        b = xl.shape[0]
        with scopes.scope(scopes.ATTN_PROJ):
            h = L.rms_norm(xl, lp["ln1"], cfg.norm_eps)
            q = (h @ lp["attn"]["wq"].astype(h.dtype)).reshape(b, t_loc, nh, hd)
            k = (h @ lp["attn"]["wk"].astype(h.dtype)).reshape(b, t_loc, nkv, hd)
            v = (h @ lp["attn"]["wv"].astype(h.dtype)).reshape(b, t_loc, nkv, hd)
            j = jax.lax.axis_index(axis)
            positions = jnp.broadcast_to(j * t_loc + jnp.arange(t_loc),
                                         (b, t_loc))
            q = L.apply_rope(q, positions, cfg.rope_theta)
            k = L.apply_rope(k, positions, cfg.rope_theta)
            out = ring_attention(q, k, v, axis=axis, axis_size=csz,
                                 causal=True, window=window)
            xl = xl + (out.reshape(b, t_loc, nh * hd)
                       @ lp["attn"]["wo"].astype(xl.dtype))
        with scopes.scope(scopes.MLP):
            h2 = L.rms_norm(xl, lp["ln2"], cfg.norm_eps)
            return xl + L.mlp_apply(lp["mlp"], h2, cfg.mlp_kind)

    rp, rw = P(None), P(None, None)
    p_specs = {"ln1": rp, "ln2": rp,
               "attn": {k: rw for k in p["attn"]},
               "mlp": {k: rw for k in p["mlp"]}}
    sub = {k: p[k] for k in ("ln1", "ln2", "attn", "mlp")}
    xspec = P(bspec, axis, None)
    return shard_map(local, mesh=mesh, in_specs=(p_specs, xspec),
                     out_specs=xspec)(sub, x)


# ---------------------------------------------------------------------------
# encoder (whisper)
# ---------------------------------------------------------------------------

def encode(cfg, params, frames):
    """frames: (B, F, d) stub frontend embeddings -> (B, F, d)."""
    enc = params["encoder"]
    x = frames + enc["pos_embed"][None].astype(frames.dtype)

    def body(x, lp):
        h = L.rms_norm(x, lp["ln1"], cfg.norm_eps)
        b, f, d = h.shape
        nh, nkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
        q = (h @ lp["attn"]["wq"].astype(h.dtype)).reshape(b, f, nh, hd)
        k = (h @ lp["attn"]["wk"].astype(h.dtype)).reshape(b, f, nkv, hd)
        v = (h @ lp["attn"]["wv"].astype(h.dtype)).reshape(b, f, nkv, hd)
        o = L.attention(q, k, v, causal=False, dense_threshold=max(8192, f + 1))
        x = x + o.reshape(b, f, nh * hd) @ lp["attn"]["wo"].astype(h.dtype)
        h2 = L.rms_norm(x, lp["ln2"], cfg.norm_eps)
        x = x + L.mlp_apply(lp["mlp"], h2, "gelu")
        return x, None

    x, _ = jax.lax.scan(body, x, enc["layers"],
                        unroll=cfg.encoder_layers if L.analysis_unroll() else 1)
    return L.rms_norm(x, enc["final_norm"], cfg.norm_eps)


# ---------------------------------------------------------------------------
# top-level entry points
# ---------------------------------------------------------------------------

@scopes.scoped(scopes.EMBED)
def _embed(cfg, params, tokens):
    x = jnp.take(params["embed"], tokens, axis=0).astype(jnp.dtype(cfg.dtype))
    return x * (cfg.d_model ** 0.5 if cfg.tie_embeddings else 1.0)


@scopes.scoped(scopes.HEAD)
def _head(cfg, params, x):
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    w = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = x @ w.astype(x.dtype)
    if cfg.vocab_padded != cfg.vocab_size:
        neg = jnp.full((cfg.vocab_padded - cfg.vocab_size,), L.NEG_INF, logits.dtype)
        bias = jnp.concatenate([jnp.zeros((cfg.vocab_size,), logits.dtype), neg])
        logits = logits + bias
    return logits


def forward(cfg, params, batch, *, mode: str = "train", window_override=None,
            pctx: Optional[ParallelCtx] = None, remat: bool = True,
            rwkv_chunked: bool = False, cache_capacity: int = 0,
            capacity_factor=1.25):
    """Main entry.  batch: dict(tokens (B,S) [, prefix (B,P,d), frames (B,F,d)]).

    mode "train": returns (logits, aux).  mode "prefill": returns
    (logits, cache, aux) with a cache of ``cache_capacity``.
    """
    window = cfg.sliding_window if window_override is None else window_override
    tokens = batch["tokens"]
    x = _embed(cfg, params, tokens)
    n_prefix = 0
    if cfg.n_prefix_embeds:
        pre = batch["prefix"].astype(x.dtype) @ params["prefix_proj"].astype(x.dtype)
        x = jnp.concatenate([pre, x], axis=1)
        n_prefix = pre.shape[1]
    enc_out = None
    if cfg.encoder_layers:
        enc_out = encode(cfg, params, batch["frames"].astype(x.dtype))

    prefill = mode == "prefill"
    cache_tmpl = None
    if prefill:
        cache_tmpl = make_cache(cfg, tokens.shape[0], cache_capacity or x.shape[1],
                                window=window, dtype=jnp.dtype(cfg.dtype))

    overlapped = (not prefill
                  and overlapped_supported(cfg, pctx, x.shape[1]))
    cp = (not prefill and not overlapped
          and cp_supported(cfg, pctx, x.shape[1]))
    if (not cp and not prefill and pctx is not None
            and pctx.context_axis is not None and pctx.mesh is not None
            and pctx.mesh.shape[pctx.context_axis] > 1):
        # same perf-cliff visibility rule as the overlapped fallback below
        cpn = pctx.mesh.shape[pctx.context_axis]
        warnings.warn(
            f"[context] {cfg.name}: context parallelism requested but the "
            f"KV ring cannot engage (needs a homogeneous dense decoder "
            f"without logit softcap and seq ({x.shape[1]}) % {cpn} == 0); "
            f"falling back to GSPMD's gathered attention", stacklevel=2)
    if (not overlapped and not prefill and pctx is not None
            and pctx.comm_runtime == "overlapped"
            and pctx.mesh is not None and pctx.model_axis is not None
            and pctx.mesh.shape[pctx.model_axis] > 1):
        # an explicitly requested runtime silently running something else is
        # the same perf cliff the ShardingRules fallback warning exposes
        mp = pctx.mesh.shape[pctx.model_axis]
        warnings.warn(
            f"[collectives] {cfg.name}: comm_runtime='overlapped' requested "
            f"but the overlapped block cannot engage (needs a homogeneous "
            f"dense decoder with n_heads ({cfg.n_heads}) and d_ff "
            f"({cfg.d_ff}) divisible by the {mp}-way model axis, seq "
            f"({x.shape[1]}) % {mp} == 0 and (seq/mp) % comm_chunks "
            f"({pctx.comm_chunks}) == 0); falling back to GSPMD's "
            f"monolithic collectives", stacklevel=2)

    def body(carry, lp_and_cache):
        x, aux = carry
        if prefill:
            lp, csl = lp_and_cache
        else:
            lp, csl = lp_and_cache, None
        if overlapped:
            x = overlapped_block_apply(cfg, lp, x, window=window, pctx=pctx)
            return (x, aux), 0
        if cp:
            x = cp_block_apply(cfg, lp, x, window=window, pctx=pctx)
            return (x, aux), 0
        x, c_new, a = block_apply(cfg, lp, x, mode="prefill" if prefill else "train",
                                  window=window, pos0=0, cache=csl,
                                  enc_out=enc_out, pctx=pctx,
                                  rwkv_chunked=rwkv_chunked,
                                  capacity_factor=capacity_factor)
        return (x, aux + a), (c_new if prefill else 0)

    if remat:
        body = jax.checkpoint(body, prevent_cse=False)
    if prefill:
        xs = (params["layers"], {k: v for k, v in cache_tmpl.items() if k != "pos"})
    else:
        xs = params["layers"]
    (x, aux), caches = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)), xs,
                                    unroll=cfg.n_layers if L.analysis_unroll() else 1)
    if n_prefix:
        x = x[:, n_prefix:]
    logits = _head(cfg, params, x)
    if prefill:
        caches["pos"] = jnp.asarray(tokens.shape[1] + n_prefix, jnp.int32)
        return logits, caches, aux
    return logits, aux


def pipeline_stage_fn(cfg, *, remat: bool = True, rwkv_chunked: bool = False,
                      window_override=None):
    """One pipeline chunk of the decoder stack as a pure shape-preserving
    ``(chunk_params, x) -> y`` callable — the unit both pipeline runtimes
    place per ``WorkUnit`` and the hand-scheduled runtime ``jax.vjp``'s."""
    window = cfg.sliding_window if window_override is None else window_override

    def stage_fn(sp, x):
        def body(x, lp):
            y, _, _ = block_apply(cfg, lp, x, mode="train", window=window,
                                  pos0=0, rwkv_chunked=rwkv_chunked)
            return y, None

        if remat:
            body = jax.checkpoint(body, prevent_cse=False)
        x, _ = jax.lax.scan(body, x, sp)
        return x

    return stage_fn


def forward_pipeline(cfg, params, batch, *, mesh, axis: str, n_micro: int,
                     remat: bool = True, rwkv_chunked: bool = False,
                     window_override=None, schedule: str = "gpipe",
                     virtual_stages: int = 1, batch_axes=()):
    """Train-mode forward with the decoder stack partitioned into pipeline
    stages over mesh ``axis`` (``parallel.pipeline``), ``n_micro``
    micro-batches in flight under the requested ``schedule``; ``batch_axes``
    shards each micro-batch over the DP mesh axes.  Supported for
    homogeneous decoder-only stacks (no encoder, no prefix embeds, no MoE
    aux loss); embed and head stay replicated on every stage.  Returns
    logits only."""
    from repro.parallel.pipeline import pipeline_apply, stack_to_stages

    x = _embed(cfg, params, batch["tokens"])
    n_stages = mesh.shape[axis]
    stages = stack_to_stages(params["layers"], n_stages, virtual_stages)
    stage_fn = pipeline_stage_fn(cfg, remat=remat, rwkv_chunked=rwkv_chunked,
                                 window_override=window_override)
    x = pipeline_apply(mesh, axis, stage_fn, stages, x, n_micro=n_micro,
                       schedule=schedule, virtual_stages=virtual_stages,
                       batch_axes=batch_axes)
    return _head(cfg, params, x)


def decode_step(cfg, params, cache, batch, *, window_override=None,
                pctx: Optional[ParallelCtx] = None):
    """Decode against the cache.  batch: dict(tokens (B,t)).  Returns
    (logits (B,t,V), new_cache).

    ``cache["pos"]`` scalar: the classic static-batch one-token step (t=1).
    ``cache["pos"]`` (B,): slot mode — per-request positions in a linear
    capacity cache (continuous batching), where t >= 1 also serves as the
    chunked-prefill "extend" step (causal within the appended chunk)."""
    window = cfg.sliding_window if window_override is None else window_override
    x = _embed(cfg, params, batch["tokens"])
    pos = cache["pos"]

    def body(x, lp_cache):
        lp, csl = lp_cache
        x, c_new, _ = block_apply(cfg, lp, x, mode="decode", window=window,
                                  pos0=pos, cache=csl, pctx=pctx)
        return x, c_new

    layer_caches = {k: v for k, v in cache.items() if k != "pos"}
    x, new_caches = jax.lax.scan(body, x, (params["layers"], layer_caches),
                                 unroll=cfg.n_layers if L.analysis_unroll() else 1)
    logits = _head(cfg, params, x)
    new_caches["pos"] = pos + batch["tokens"].shape[1]
    return logits, new_caches


# ---------------------------------------------------------------------------
# tensor-MP slot decode (continuous-batching serve engine)
# ---------------------------------------------------------------------------

def decode_slots_tp_supported(cfg, mesh, model_axis, batch_axes,
                              n_slots: int, chunks: int = 1) -> bool:
    """Can the slot-ring decode step execute on this (arch, mesh, slots)?
    Mirrors ``overlapped_supported`` with the SLOT dim in the role the
    sequence dim plays in training: n_slots must divide over dp x mp x
    chunks so the residual stream can stay slot-sharded between blocks."""
    if mesh is None or model_axis is None:
        return False
    msz = mesh.shape[model_axis]
    if msz <= 1 or not overlapped_arch_supported(cfg):
        return False
    dp = 1
    for a in (batch_axes or ()):
        if a:
            dp *= mesh.shape[a]
    return (cfg.n_heads > 0 and cfg.n_heads % msz == 0
            and cfg.d_ff % msz == 0 and n_slots % (dp * msz) == 0
            and (n_slots // (dp * msz)) % max(chunks, 1) == 0)


def decode_slots_tp(cfg, params, cache, batch, *, mesh, model_axis: str,
                    batch_axes=(), comm_chunks: int = 1,
                    window_override=None):
    """One continuous-batching decode tick under a dp x tp mesh, the whole
    layer stack inside ONE shard_map with every Megatron matmul on the
    chunked collective-matmul rings (``parallel.collectives``).

    Decode has one token per request, so the training trick of sharding the
    sequence dim does not apply — instead the SLOT/batch dim is the ring row
    dim: the residual stream stays slot-sharded (B/(dp*mp), d) between
    blocks, ``all_gather_matmul`` reassembles all slots for each shard's
    head slice of qkv, attention runs per-slot against the (KV-head-sharded
    when divisible, else replicated) cache, ``matmul_reduce_scatter``
    returns the slot shard through the row-parallel wo, and the MLP rides
    the same rings.  One ``ring_all_gather`` before the (replicated) head is
    the only full reassembly — no monolithic all-gather/all-reduce appears
    in the compiled per-layer decode HLO.

    batch: dict(tokens (B, 1)); cache: slot cache with per-request
    ``pos`` (B,).  Returns (logits (B,1,V), new_cache)."""
    from repro.parallel.collectives import (all_gather_matmul,
                                            matmul_reduce_scatter,
                                            ring_all_gather)
    window = cfg.sliding_window if window_override is None else window_override
    tokens = batch["tokens"]
    pos = cache["pos"]
    msz = mesh.shape[model_axis]
    baxes = tuple(a for a in (batch_axes or ())
                  if a and mesh.shape.get(a, 1) > 1)
    bspec = baxes if baxes else None
    chunks = max(comm_chunks, 1)
    nh, nkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    hpm = nh // msz
    kv_sharded = nkv % msz == 0
    kvpm = nkv // msz if kv_sharded else nkv
    kw = dict(axis=model_axis, axis_size=msz, chunks=chunks)

    def local(p, layer_caches, tok, ps):
        # tok: (B_loc, 1) and ps: (B_loc,) per data shard, replicated over
        # the model axis; the model shard takes its slot rows of the residual
        b_loc = tok.shape[0]
        rows = b_loc // msz
        x = _embed(cfg, p, tok)[:, 0]                     # (B_loc, d)
        j = jax.lax.axis_index(model_axis)
        xl = jax.lax.dynamic_slice_in_dim(x, j * rows, rows, axis=0)
        clen = layer_caches["k"].shape[2]
        slot = jnp.arange(clen + 1)
        valid = jnp.where(slot[None] < clen, slot[None] < ps[:, None], True)
        if window:
            kpos = jnp.where(slot[None] < clen, slot[None], ps[:, None])
            valid &= kpos > ps[:, None] - window

        def body(xl, lp_cache):
            lp, csl = lp_cache
            h = L.rms_norm(xl, lp["ln1"], cfg.norm_eps)
            w_qkv = jnp.concatenate(
                [lp["attn"]["wq"], lp["attn"]["wk"], lp["attn"]["wv"]],
                axis=1).astype(xl.dtype)
            qkv = all_gather_matmul(h, w_qkv, **kw)       # (B_loc, ...)
            q = qkv[:, :hpm * hd].reshape(b_loc, 1, hpm, hd)
            k = qkv[:, hpm * hd:(hpm + kvpm) * hd].reshape(b_loc, 1, kvpm, hd)
            v = qkv[:, (hpm + kvpm) * hd:].reshape(b_loc, 1, kvpm, hd)
            q = L.apply_rope(q, ps[:, None], cfg.rope_theta)
            k = L.apply_rope(k, ps[:, None], cfg.rope_theta)
            k_all = jnp.concatenate([csl["k"], k], axis=1)
            v_all = jnp.concatenate([csl["v"], v], axis=1)
            if kv_sharded:
                k_att, v_att = k_all, v_all
            else:
                # replicated KV: q-head-aligned slice of the repeated heads
                k_att = jax.lax.dynamic_slice_in_dim(
                    L.repeat_kv(k_all, nh // nkv), j * hpm, hpm, axis=2)
                v_att = jax.lax.dynamic_slice_in_dim(
                    L.repeat_kv(v_all, nh // nkv), j * hpm, hpm, axis=2)
            out = L.attention(q, k_att, v_att, mask=valid[:, None, :],
                              softcap=cfg.attn_logit_softcap)
            xl = xl + matmul_reduce_scatter(
                out.reshape(b_loc, hpm * hd),
                lp["attn"]["wo"].astype(xl.dtype), **kw)
            h2 = L.rms_norm(xl, lp["ln2"], cfg.norm_eps)
            xl = xl + L.mlp_apply_overlapped(lp["mlp"], h2, cfg.mlp_kind,
                                             axis=model_axis, axis_size=msz,
                                             chunks=chunks)
            kv = L.cache_insert_at({"k": csl["k"], "v": csl["v"]}, k, v, ps)
            return xl, kv

        xl, new_caches = jax.lax.scan(
            body, xl, (p["layers"], layer_caches),
            unroll=cfg.n_layers if L.analysis_unroll() else 1)
        x_full = ring_all_gather(xl, **kw)                # (B_loc, d)
        logits = _head(cfg, p, x_full[:, None])
        return logits, new_caches

    col, row = P(None, None, model_axis), P(None, model_axis, None)
    kvw = col if kv_sharded else P(None, None, None)
    p_specs = {"embed": P(None, None), "final_norm": P(None),
               "layers": {"ln1": P(None, None), "ln2": P(None, None),
                          "attn": {"wq": col, "wk": kvw, "wv": kvw,
                                   "wo": row},
                          "mlp": {k: (row if k == "wo" else col)
                                  for k in params["layers"]["mlp"]}}}
    if "lm_head" in params:
        p_specs["lm_head"] = P(None, None)
    kvm = model_axis if kv_sharded else None
    c_spec = P(None, bspec, None, kvm, None)
    layer_caches = {"k": cache["k"], "v": cache["v"]}
    logits, new_caches = shard_map(
        local, mesh=mesh,
        in_specs=(p_specs, {"k": c_spec, "v": c_spec},
                  P(bspec, None), P(bspec)),
        out_specs=(P(bspec, None, None), {"k": c_spec, "v": c_spec}))(
            params, layer_caches, tokens, pos)
    new_caches["pos"] = pos + 1
    return logits, new_caches


# ---------------------------------------------------------------------------
# sharded chunked prefill (continuous-batching serve engine)
# ---------------------------------------------------------------------------

def prefill_chunk_tp_supported(cfg, mesh, model_axis, t: int,
                               chunks: int = 1) -> bool:
    """Can one slot's prefill chunk run on the collective-matmul rings?
    The chunk's SEQUENCE dim takes the ring-row role (exactly training's
    ``overlapped_supported`` conditions, with t = the chunk length)."""
    if mesh is None or model_axis is None:
        return False
    msz = mesh.shape[model_axis]
    if msz <= 1 or not overlapped_arch_supported(cfg):
        return False
    return (cfg.n_heads > 0 and cfg.n_heads % msz == 0
            and cfg.d_ff % msz == 0 and t % msz == 0
            and (t // msz) % max(chunks, 1) == 0)


def prefill_chunk_tp(cfg, params, cache, batch, *, mesh, model_axis: str,
                     comm_chunks: int = 1, window_override=None,
                     n_valid: Optional[int] = None):
    """Chunked-prefill "extend" step for ONE slot under the tensor-MP mesh:
    the whole layer stack in one shard_map with every Megatron matmul on
    the chunked collective-matmul rings — the same schedule as training's
    ``overlapped_block_apply`` (residual stream chunk-sequence-sharded,
    qkv gather ring -> slot-mode attention against the cache -> wo reduce
    ring -> MLP rings), against the slot's extracted batch-1 cache.

    ``cache``: ``models.api.cache_extract_slot`` shape — per-layer k/v
    (Lc, 1, capacity, KV, hd) + ``pos`` (1,); batch: dict(tokens (1, t)).
    Returns (last-token logits (1, 1, V), new slot cache).

    ``n_valid`` (static, default t) marks a PADDED chunk: only the first
    ``n_valid`` tokens are real — a non-divisible final chunk padded up to
    the ring grid.  Logits are taken at position ``n_valid - 1`` and ``pos``
    advances by ``n_valid``; the pad rows written past it are inert (every
    attention mask gates on ``pos``) and get overwritten by the next
    insert at ``pos``.  Causality keeps pad keys invisible to real queries
    (pad positions are strictly later), so padding never changes the real
    tokens' math."""
    from repro.parallel.collectives import (all_gather_matmul,
                                            matmul_reduce_scatter,
                                            ring_all_gather)
    window = cfg.sliding_window if window_override is None else window_override
    tokens = batch["tokens"]
    pos = cache["pos"]
    b, t = tokens.shape
    nv = t if n_valid is None else int(n_valid)
    msz = mesh.shape[model_axis]
    t_loc = t // msz
    chunks = max(comm_chunks, 1)
    nh, nkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    hpm = nh // msz
    kv_sharded = nkv % msz == 0
    kvpm = nkv // msz if kv_sharded else nkv
    kw = dict(axis=model_axis, axis_size=msz, chunks=chunks)

    def local(p, layer_caches, tok, ps):
        x = _embed(cfg, p, tok)                           # (1, t, d)
        j = jax.lax.axis_index(model_axis)
        xl = jax.lax.dynamic_slice_in_dim(x, j * t_loc, t_loc, axis=1)
        clen = layer_caches["k"].shape[2]
        slot = jnp.arange(clen + t)
        in_cache = slot < clen
        qpos = ps[:, None] + jnp.arange(t)[None]          # (1, t)
        kpos = jnp.where(in_cache[None], slot[None],
                         ps[:, None] + (slot[None] - clen))
        valid = jnp.where(in_cache[None, None],
                          slot[None, None, :] < ps[:, None, None],
                          kpos[:, None, :] <= qpos[:, :, None])
        if window:
            valid &= kpos[:, None, :] > qpos[:, :, None] - window

        def body(xl, lp_cache):
            lp, csl = lp_cache
            h = L.rms_norm(xl, lp["ln1"], cfg.norm_eps)
            w_qkv = jnp.concatenate(
                [lp["attn"]["wq"], lp["attn"]["wk"], lp["attn"]["wv"]],
                axis=1).astype(xl.dtype)
            qkv = all_gather_matmul(h, w_qkv, **kw)       # (1, t, ...)
            q = qkv[..., :hpm * hd].reshape(b, t, hpm, hd)
            k = qkv[..., hpm * hd:(hpm + kvpm) * hd].reshape(b, t, kvpm, hd)
            v = qkv[..., (hpm + kvpm) * hd:].reshape(b, t, kvpm, hd)
            q = L.apply_rope(q, qpos, cfg.rope_theta)
            k = L.apply_rope(k, qpos, cfg.rope_theta)
            k_all = jnp.concatenate([csl["k"], k], axis=1)
            v_all = jnp.concatenate([csl["v"], v], axis=1)
            if kv_sharded:
                k_att, v_att = k_all, v_all
            else:
                k_att = jax.lax.dynamic_slice_in_dim(
                    L.repeat_kv(k_all, nh // nkv), j * hpm, hpm, axis=2)
                v_att = jax.lax.dynamic_slice_in_dim(
                    L.repeat_kv(v_all, nh // nkv), j * hpm, hpm, axis=2)
            out = L.attention(q, k_att, v_att, mask=valid,
                              softcap=cfg.attn_logit_softcap)
            xl = xl + matmul_reduce_scatter(
                out.reshape(b, t, hpm * hd),
                lp["attn"]["wo"].astype(xl.dtype), **kw)
            h2 = L.rms_norm(xl, lp["ln2"], cfg.norm_eps)
            xl = xl + L.mlp_apply_overlapped(lp["mlp"], h2, cfg.mlp_kind,
                                             axis=model_axis, axis_size=msz,
                                             chunks=chunks)
            kv = L.cache_insert_at({"k": csl["k"], "v": csl["v"]}, k, v, ps)
            return xl, kv

        xl, new_caches = jax.lax.scan(
            body, xl, (p["layers"], layer_caches),
            unroll=cfg.n_layers if L.analysis_unroll() else 1)
        x_full = ring_all_gather(xl, **kw)                # (1, t, d)
        logits = _head(cfg, p, x_full[:, nv - 1:nv])      # (1, 1, V)
        return logits, new_caches

    col, row = P(None, None, model_axis), P(None, model_axis, None)
    kvw = col if kv_sharded else P(None, None, None)
    p_specs = {"embed": P(None, None), "final_norm": P(None),
               "layers": {"ln1": P(None, None), "ln2": P(None, None),
                          "attn": {"wq": col, "wk": kvw, "wv": kvw,
                                   "wo": row},
                          "mlp": {k: (row if k == "wo" else col)
                                  for k in params["layers"]["mlp"]}}}
    if "lm_head" in params:
        p_specs["lm_head"] = P(None, None)
    kvm = model_axis if kv_sharded else None
    c_spec = P(None, None, None, kvm, None)
    layer_caches = {"k": cache["k"], "v": cache["v"]}
    logits, new_caches = shard_map(
        local, mesh=mesh,
        in_specs=(p_specs, {"k": c_spec, "v": c_spec},
                  P(None, None), P(None)),
        out_specs=(P(None, None, None), {"k": c_spec, "v": c_spec}))(
            params, layer_caches, tokens, pos)
    new_caches["pos"] = pos + nv
    return logits, new_caches


def prefill_chunk_cp_supported(cfg, mesh, context_axis, t: int) -> bool:
    """Can one slot's prefill chunk run context-parallel?  Mirrors
    ``cp_supported`` with t = the chunk length; no head-divisibility
    constraint — CP shards the sequence, not the heads."""
    if mesh is None or context_axis is None:
        return False
    csz = mesh.shape[context_axis]
    if csz <= 1 or not overlapped_arch_supported(cfg) \
            or cfg.attn_logit_softcap:
        return False
    return cfg.n_heads > 0 and t % csz == 0


def prefill_chunk_cp(cfg, params, cache, batch, *, mesh, context_axis: str,
                     window_override=None, n_valid: Optional[int] = None):
    """Chunked-prefill "extend" step for ONE slot with the chunk
    CONTEXT-PARALLEL: the chunk's sequence dim shards over the ring,
    in-chunk attention rides ``parallel.context.ring_attention_stats``
    (per-request absolute offsets cancel in the causal/window masks), the
    KV-cache contribution is computed locally per device against the
    replicated slot cache and merged via ``merge_softmax_stats``, and the
    chunk's new KV rows reassemble on a ``ring_all_gather`` (ppermute-only)
    for the replicated cache insert.  Weights stay fully replicated.

    Same signature/shapes as ``prefill_chunk_tp``, including the
    ``n_valid`` padded-final-chunk contract (pad tokens land on the tail
    devices of the ring and are masked/overwritten the same way)."""
    from repro.parallel.collectives import ring_all_gather
    from repro.parallel.context import ring_attention_stats
    window = cfg.sliding_window if window_override is None else window_override
    tokens = batch["tokens"]
    pos = cache["pos"]
    b, t = tokens.shape
    nv = t if n_valid is None else int(n_valid)
    csz = mesh.shape[context_axis]
    t_loc = t // csz
    nh, nkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    n_rep = nh // nkv
    scale = 1.0 / (hd ** 0.5)
    gkw = dict(axis=context_axis, axis_size=csz)

    def local(p, layer_caches, tok, ps):
        x = _embed(cfg, p, tok)                           # (1, t, d)
        j = jax.lax.axis_index(context_axis)
        xl = jax.lax.dynamic_slice_in_dim(x, j * t_loc, t_loc, axis=1)
        clen = layer_caches["k"].shape[2]
        slot = jnp.arange(clen)                           # cache kpos == slot
        qpos = ps[:, None] + j * t_loc + jnp.arange(t_loc)[None]  # (1, t_loc)
        valid = jnp.broadcast_to(slot[None, None, :] < ps[:, None, None],
                                 (b, t_loc, clen))
        if window:
            valid = valid & (slot[None, None, :] > qpos[:, :, None] - window)

        def body(xl, lp_cache):
            lp, csl = lp_cache
            h = L.rms_norm(xl, lp["ln1"], cfg.norm_eps)
            q = (h @ lp["attn"]["wq"].astype(h.dtype)).reshape(b, t_loc, nh, hd)
            k = (h @ lp["attn"]["wk"].astype(h.dtype)).reshape(b, t_loc, nkv, hd)
            v = (h @ lp["attn"]["wv"].astype(h.dtype)).reshape(b, t_loc, nkv, hd)
            q = L.apply_rope(q, qpos, cfg.rope_theta)
            k = L.apply_rope(k, qpos, cfg.rope_theta)
            ring_stats = ring_attention_stats(q, k, v, causal=True,
                                              window=window, **gkw)
            # cache contribution: local dense partial over the replicated
            # slot cache; a fully-masked row's bogus exp(0) probs are
            # zeroed by the merge's corr factor (m stays NEG_INF)
            kr = L.repeat_kv(csl["k"], n_rep).astype(jnp.float32)
            vr = L.repeat_kv(csl["v"], n_rep).astype(jnp.float32)
            q32 = q.astype(jnp.float32).transpose(0, 2, 1, 3) * scale
            sc = jnp.einsum("bhqd,bkhd->bhqk", q32, kr)
            sc = jnp.where(valid[:, None], sc, L.NEG_INF)
            mk = sc.max(axis=-1)
            pk = jnp.exp(sc - mk[..., None])
            cache_stats = (mk, pk.sum(axis=-1),
                           jnp.einsum("bhqk,bkhd->bhqd", pk, vr))
            m, l, acc = L.merge_softmax_stats(ring_stats, cache_stats)
            out = (acc / jnp.maximum(l, 1e-30)[..., None]
                   ).transpose(0, 2, 1, 3).astype(xl.dtype)
            xl = xl + (out.reshape(b, t_loc, nh * hd)
                       @ lp["attn"]["wo"].astype(xl.dtype))
            h2 = L.rms_norm(xl, lp["ln2"], cfg.norm_eps)
            xl = xl + L.mlp_apply(lp["mlp"], h2, cfg.mlp_kind)
            kf = ring_all_gather(k.reshape(b, t_loc, nkv * hd), **gkw
                                 ).reshape(b, t, nkv, hd)
            vf = ring_all_gather(v.reshape(b, t_loc, nkv * hd), **gkw
                                 ).reshape(b, t, nkv, hd)
            kv = L.cache_insert_at({"k": csl["k"], "v": csl["v"]}, kf, vf, ps)
            return xl, kv

        xl, new_caches = jax.lax.scan(
            body, xl, (p["layers"], layer_caches),
            unroll=cfg.n_layers if L.analysis_unroll() else 1)
        x_full = ring_all_gather(xl, **gkw)               # (1, t, d)
        logits = _head(cfg, p, x_full[:, nv - 1:nv])      # (1, 1, V)
        return logits, new_caches

    p_specs = jax.tree.map(lambda a: P(*(None,) * jnp.ndim(a)), params)
    c_spec = P(None, None, None, None, None)
    layer_caches = {"k": cache["k"], "v": cache["v"]}
    logits, new_caches = shard_map(
        local, mesh=mesh,
        in_specs=(p_specs, {"k": c_spec, "v": c_spec},
                  P(None, None), P(None)),
        out_specs=(P(None, None, None), {"k": c_spec, "v": c_spec}))(
            params, layer_caches, tokens, pos)
    new_caches["pos"] = pos + nv
    return logits, new_caches
