"""Shared neural-net layers: norms, RoPE, GQA attention (full / sliding-window /
chunked-online-softmax, or the flash kernel on a TPU), KV caches, and MLP
variants.

Everything is a pure function over explicit param pytrees so that the parallel
runtime can assign `NamedSharding`s by param path and `jax.eval_shape` can
derive ShapeDtypeStructs for the multi-pod dry-run without allocating.
"""
from __future__ import annotations

import collections
import contextlib
import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro import scopes
from repro.kernels import flash_attention as flash
from repro.parallel.jaxcompat import shard_map

# ---------------------------------------------------------------------------
# initializers
# ---------------------------------------------------------------------------

def dense_init(key, d_in: int, d_out: int, dtype=jnp.float32):
    scale = 1.0 / math.sqrt(d_in)
    return (jax.random.normal(key, (d_in, d_out), dtype=jnp.float32) * scale).astype(dtype)


def embed_init(key, vocab: int, d: int, dtype=jnp.float32):
    return (jax.random.normal(key, (vocab, d), dtype=jnp.float32) * 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def rms_norm(x, gamma, eps: float = 1e-5):
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * gamma.astype(jnp.float32)).astype(dt)


def layer_norm(x, gamma, beta, eps: float = 1e-5):
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps)
    return (y * gamma.astype(jnp.float32) + beta.astype(jnp.float32)).astype(dt)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float):
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x, positions, theta: float):
    """x: (..., T, H, hd); positions: broadcastable to (..., T)."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                       # (hd/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., T, hd/2)
    sin = jnp.sin(angles)[..., None, :]                 # (..., T, 1, hd/2)
    cos = jnp.cos(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------

NEG_INF = -1e30

# Trace-time switch: the dry-run's ANALYSIS artifacts set this so every scan
# fully unrolls and XLA's cost analysis counts all iterations (the HLO cost
# model visits while-loop bodies exactly once).  Never set during real runs.
_ANALYSIS_UNROLL = False


def set_analysis_unroll(value: bool) -> None:
    global _ANALYSIS_UNROLL
    _ANALYSIS_UNROLL = bool(value)


def analysis_unroll() -> bool:
    return _ANALYSIS_UNROLL


def repeat_kv(k, n_rep: int):
    """(B, S, KV, hd) -> (B, S, KV*n_rep, hd)."""
    if n_rep == 1:
        return k
    b, s, kv, hd = k.shape
    k = jnp.broadcast_to(k[:, :, :, None, :], (b, s, kv, n_rep, hd))
    return k.reshape(b, s, kv * n_rep, hd)


def _dense_attention(q, k, v, mask, softcap: float = 0.0):
    """q: (B,Tq,H,hd) k,v: (B,Tk,H,hd) mask: (B,1,Tq,Tk) or None -> (B,Tq,H,hd)."""
    hd = q.shape[-1]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32)
    scores = scores / math.sqrt(hd)
    if softcap:
        scores = softcap * jnp.tanh(scores / softcap)
    if mask is not None:
        scores = jnp.where(mask, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v)


def _chunked_attention(q, k, v, q_start, causal: bool, window: int, kv_chunk: int):
    """Online-softmax attention scanning over KV chunks (flash-attention
    algorithm in pure jnp — memory O(Tq * kv_chunk), the oracle for the Pallas
    kernel).  q: (B,Tq,H,hd); k,v: (B,Tk,H,hd).  q position i = q_start + i.
    """
    b, tq, h, hd = q.shape
    tk = k.shape[1]
    n_chunks = (tk + kv_chunk - 1) // kv_chunk
    pad = n_chunks * kv_chunk - tk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kc = k.reshape(b, n_chunks, kv_chunk, h, hd).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(b, n_chunks, kv_chunk, h, hd).transpose(1, 0, 2, 3, 4)
    q32 = q.astype(jnp.float32) / math.sqrt(hd)
    qpos = q_start + jnp.arange(tq)

    def step(carry, xs):
        m, l, acc = carry
        ci, kb, vb = xs
        kpos = ci * kv_chunk + jnp.arange(kv_chunk)
        s = jnp.einsum("bqhd,bkhd->bhqk", q32, kb.astype(jnp.float32))
        valid = kpos[None, :] < tk
        if causal:
            valid = valid & (kpos[None, :] <= qpos[:, None])
        if window > 0:
            valid = valid & (kpos[None, :] > qpos[:, None] - window)
        s = jnp.where(valid[None, None], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p, vb.astype(jnp.float32))
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, h, tq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, tq), jnp.float32)
    acc0 = jnp.zeros((b, h, tq, hd), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        step, (m0, l0, acc0), (jnp.arange(n_chunks), kc, vc),
        unroll=n_chunks if _ANALYSIS_UNROLL else 1)
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


# the counters of the open ``count_attention_paths`` blocks
_path_counts: list = []


@contextlib.contextmanager
def count_attention_paths():
    """Counts, while open, the ``attention`` calls traced on each path:
    ``kernel`` (the flash kernel), ``masked`` (an explicit mask), ``dense``
    or ``chunked``.  Counting happens at trace time: a jitted function
    counts once per trace, a layer scan once for its body."""
    counts = collections.Counter()
    _path_counts.append(counts)
    try:
        yield counts
    finally:
        _path_counts.remove(counts)


def _operands_on_one_device() -> bool:
    """Whether this trace's arrays are whole on one device: no mesh on a
    one-device process, a one-device mesh, or a shard_map body (every mesh
    axis manual).  Under a GSPMD mesh of several devices they are not."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return jax.device_count() == 1
    return mesh.size == 1 or set(mesh.manual_axes) == set(mesh.axis_names)


def _takes_kernel(q, k, *, causal, q_start, kv_mask, mask) -> bool:
    """Causal self-attention over the whole sequence, with no explicit mask,
    a sequence the kernel's blocks tile, on operands one device holds."""
    tq, tk = q.shape[1], k.shape[1]
    return (causal and mask is None and kv_mask is None
            and isinstance(q_start, int) and q_start == 0
            and tq == tk and tq % 128 == 0 and _operands_on_one_device())


def _xla_path(tk: int, *, softcap, dense_threshold, mask) -> str:
    if mask is not None:
        return "masked"
    if tk <= dense_threshold or softcap:
        return "dense"
    return "chunked"


def _xla_attention(q, k, v, *, path, causal, q_start, window, softcap,
                   kv_chunk, kv_mask, mask):
    """Attention as XLA ops on ``path`` (``_xla_path``)."""
    hq, hkv = q.shape[2], k.shape[2]
    k = repeat_kv(k, hq // hkv)
    v = repeat_kv(v, hq // hkv)
    tq, tk = q.shape[1], k.shape[1]
    if path == "masked":
        return _dense_attention(q, k, v, mask[:, None], softcap)
    if path == "dense":
        qpos = q_start + jnp.arange(tq)
        kpos = jnp.arange(tk)
        mask = jnp.ones((tq, tk), bool)
        if causal:
            mask &= kpos[None, :] <= qpos[:, None]
        if window > 0:
            mask &= kpos[None, :] > qpos[:, None] - window
        mask = mask[None, None]
        if kv_mask is not None:
            mask = mask & kv_mask[:, None, None, :]
        return _dense_attention(q, k, v, mask, softcap)
    assert kv_mask is None, "chunked path expects a fully-valid cache"
    return _chunked_attention(q, k, v, q_start, causal, window, kv_chunk)


@scopes.scoped(scopes.ATTN_CORE)
def attention(q, k, v, *, causal: bool = True, q_start=0, window: int = 0,
              softcap: float = 0.0, kv_chunk: int = 1024,
              dense_threshold: int = 8192, kv_mask=None, mask=None):
    """GQA attention.  q: (B,Tq,Hq,hd); k,v: (B,Tk,Hkv,hd).

    ``window`` > 0 restricts key j to (i - window, i].  ``kv_mask`` is an
    optional (B, Tk) bool of valid cache slots (decode).  ``mask`` is an
    explicit (B, Tq, Tk) bool overriding all derived masking (per-request
    positions in the slotted serving cache); it forces the dense path.

    Causal self-attention from position 0 with no explicit mask, T a
    multiple of 128 and operands on one device runs as the splash flash
    kernel (forward and backward) when lowered for a TPU.  Otherwise, and
    on other platforms, it takes a dense path for short KV and the chunked
    online-softmax path (flash algorithm) for long KV.
    """
    xla_path = _xla_path(k.shape[1], softcap=softcap,
                         dense_threshold=dense_threshold, mask=mask)
    kernel = _takes_kernel(q, k, causal=causal, q_start=q_start,
                           kv_mask=kv_mask, mask=mask)
    for counts in _path_counts:
        counts["kernel" if kernel else xla_path] += 1
    xla = functools.partial(
        _xla_attention, path=xla_path, causal=causal, q_start=q_start,
        window=window, softcap=softcap, kv_chunk=kv_chunk, kv_mask=kv_mask,
        mask=mask)
    if not kernel:
        return xla(q, k, v)
    return jax.lax.platform_dependent(
        q, k, v, default=xla,
        tpu=functools.partial(flash.causal_self_attention, window=window,
                              softcap=softcap))


# ---------------------------------------------------------------------------
# KV cache (full-length buffer or sliding-window ring)
# ---------------------------------------------------------------------------

def make_kv_cache(batch: int, length: int, n_kv: int, head_dim: int, dtype):
    return {
        "k": jnp.zeros((batch, length, n_kv, head_dim), dtype),
        "v": jnp.zeros((batch, length, n_kv, head_dim), dtype),
    }


def cache_insert_full(cache, k_new, v_new, pos):
    """Write (B,1,KV,hd) at absolute position ``pos`` (scalar int)."""
    k = jax.lax.dynamic_update_slice_in_dim(cache["k"], k_new, pos, axis=1)
    v = jax.lax.dynamic_update_slice_in_dim(cache["v"], v_new, pos, axis=1)
    return {"k": k, "v": v}


def cache_insert_at(cache, k_new, v_new, pos):
    """Write (B,t,KV,hd) at per-row positions ``pos`` (B,) — one
    dynamic_update_slice per row (vmapped), the slotted-cache insert of the
    continuous-batching engine.  Scalar ``pos`` falls through to
    ``cache_insert_full``."""
    if jnp.ndim(pos) == 0:
        return cache_insert_full(cache, k_new, v_new, pos)
    upd = jax.vmap(
        lambda c, n, p: jax.lax.dynamic_update_slice_in_dim(c, n, p, axis=0))
    return {"k": upd(cache["k"], k_new, pos), "v": upd(cache["v"], v_new, pos)}


def cache_insert_window(cache, k_new, v_new):
    """Shift-left ring insert for sliding-window caches (keys stored roped)."""
    k = jnp.concatenate([cache["k"][:, 1:], k_new], axis=1)
    v = jnp.concatenate([cache["v"][:, 1:], v_new], axis=1)
    return {"k": k, "v": v}


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------

def mlp_init(key, d: int, d_ff: int, kind: str, dtype=jnp.float32):
    ks = jax.random.split(key, 3)
    if kind == "swiglu":
        return {"wi": dense_init(ks[0], d, d_ff, dtype),
                "wg": dense_init(ks[1], d, d_ff, dtype),
                "wo": dense_init(ks[2], d_ff, d, dtype)}
    return {"wi": dense_init(ks[0], d, d_ff, dtype),
            "wo": dense_init(ks[2], d_ff, d, dtype)}


@scopes.scoped(scopes.MLP)
def mlp_apply(params, x, kind: str):
    if kind == "swiglu":
        h = jax.nn.silu(x @ params["wg"].astype(x.dtype)) * (x @ params["wi"].astype(x.dtype))
    elif kind == "gelu":
        h = jax.nn.gelu(x @ params["wi"].astype(x.dtype))
    elif kind == "sqrelu":
        h = jnp.square(jax.nn.relu(x @ params["wi"].astype(x.dtype)))
    else:
        raise ValueError(kind)
    return h @ params["wo"].astype(x.dtype)


@scopes.scoped(scopes.MLP)
def mlp_apply_overlapped(params, x, kind: str, *, axis: str, axis_size: int,
                         chunks: int = 1):
    """Megatron column/row-parallel MLP on the overlap-scheduled collective
    rings (``parallel.collectives``), for use INSIDE a shard_map: ``x`` is
    (..., T/m, d) sequence-sharded over ``axis``; ``wi``/``wg`` are this
    shard's column slices, ``wo`` the row slice.  The gate and up projections
    share one gather ring (their weights are concatenated so x travels the
    ring once).  Returns (..., T/m, d) sequence-sharded."""
    from repro.parallel.collectives import (all_gather_matmul,
                                            matmul_reduce_scatter)
    kw = dict(axis=axis, axis_size=axis_size, chunks=chunks)
    if kind == "swiglu":
        ff = params["wi"].shape[1]
        w2 = jnp.concatenate([params["wg"], params["wi"]], axis=1)
        gi = all_gather_matmul(x, w2.astype(x.dtype), **kw)
        h = jax.nn.silu(gi[..., :ff]) * gi[..., ff:]
    elif kind == "gelu":
        h = jax.nn.gelu(all_gather_matmul(x, params["wi"].astype(x.dtype), **kw))
    elif kind == "sqrelu":
        h = jnp.square(jax.nn.relu(
            all_gather_matmul(x, params["wi"].astype(x.dtype), **kw)))
    else:
        raise ValueError(kind)
    return matmul_reduce_scatter(h, params["wo"].astype(x.dtype), **kw)


# ---------------------------------------------------------------------------
# sequence-sharded decode attention (flash-decode, §Perf iteration B.2)
# ---------------------------------------------------------------------------

def _partial_softmax_stats(q, k, v, valid):
    """q: (B,1,H,hd); k,v: (B,C,H,hd); valid: (B,C) -> (m, l, acc) in f32.

    m: (B,H); l: (B,H); acc: (B,H,hd) — mergeable partial softmax stats.
    """
    import math as _math
    hd = q.shape[-1]
    s = jnp.einsum("bqhd,bkhd->bhk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / _math.sqrt(hd)
    s = jnp.where(valid[:, None, :], s, NEG_INF)
    m = s.max(axis=-1)
    p = jnp.exp(s - m[..., None])
    l = p.sum(axis=-1)
    acc = jnp.einsum("bhk,bkhd->bhd", p, v.astype(jnp.float32))
    return m, l, acc


def merge_softmax_stats(stats_a, stats_b):
    """Merge two partial-softmax stats triples (flash-decode combine)."""
    ma, la, aa = stats_a
    mb, lb, ab = stats_b
    m = jnp.maximum(ma, mb)
    ca = jnp.exp(ma - m)
    cb = jnp.exp(mb - m)
    return m, la * ca + lb * cb, aa * ca[..., None] + ab * cb[..., None]


def seq_sharded_decode_attention(q, k_cache, v_cache, cache_valid, k_new,
                                 v_new, *, mesh, seq_axis: str, batch_axes):
    """One-token decode attention with the KV cache SEQUENCE-sharded over the
    model axis (flash-decode): each shard computes partial softmax stats over
    its cache chunk; pmax/psum merge them; the new token's self-attention is
    merged in afterwards.  Cuts per-chip cache memory by the axis size for
    GQA archs whose KV-head count cannot shard (8, 20 vs 16-way).

    q: (B,1,Hq,hd) replicated on seq_axis; k_cache/v_cache: (B,S,KV,hd)
    sharded on S; cache_valid: (B,S) bool sharded on S; k_new/v_new:
    (B,1,KV,hd) replicated.  Returns (B,1,Hq,hd).
    """
    from jax.sharding import PartitionSpec as P

    hq, hkv = q.shape[2], k_cache.shape[2]
    rep = hq // hkv
    baxes = tuple(a for a in (batch_axes or ()) if a)
    bspec = baxes if baxes else None

    def local(q_, k_, v_, valid_):
        m, l, acc = _partial_softmax_stats(q_, repeat_kv(k_, rep),
                                           repeat_kv(v_, rep), valid_)
        m_g = jax.lax.pmax(m, seq_axis)
        corr = jnp.exp(m - m_g)
        l_g = jax.lax.psum(l * corr, seq_axis)
        acc_g = jax.lax.psum(acc * corr[..., None], seq_axis)
        return m_g, l_g, acc_g

    in_specs = (P(bspec, None, None, None), P(bspec, seq_axis, None, None),
                P(bspec, seq_axis, None, None), P(bspec, seq_axis))
    out_specs = (P(bspec, None), P(bspec, None), P(bspec, None, None))
    stats_cache = shard_map(local, mesh=mesh, in_specs=in_specs,
                                out_specs=out_specs)(q, k_cache, v_cache,
                                                 cache_valid)
    # the new token always sees itself
    ones = jnp.ones(k_new.shape[:2], bool)
    stats_self = _partial_softmax_stats(q, repeat_kv(k_new, rep),
                                        repeat_kv(v_new, rep), ones)
    m, l, acc = merge_softmax_stats(stats_cache, stats_self)
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out[:, None].transpose(0, 1, 2, 3).astype(q.dtype).reshape(q.shape)


def seq_sharded_cache_insert(cache_k, cache_v, k_new, v_new, pos, *, mesh,
                             seq_axis: str, batch_axes):
    """Insert one token into a sequence-sharded KV cache with ZERO
    communication: each shard locally updates iff ``pos`` lands in its chunk
    (§Perf iteration B.3 — a plain dynamic_update_slice makes GSPMD
    all-gather + rewrite the whole cache every decode step).

    cache_k/v: (B, S, KV, hd) sharded on S over seq_axis; k_new/v_new:
    (B, 1, KV, hd) replicated; pos: scalar absolute position.
    """
    from jax.sharding import PartitionSpec as P

    baxes = tuple(a for a in (batch_axes or ()) if a)
    bspec = baxes if baxes else None
    n_shards = mesh.shape[seq_axis]
    chunk = cache_k.shape[1] // n_shards

    def local(ck, cv, kn, vn):
        i = jax.lax.axis_index(seq_axis)
        lo = i * chunk
        in_range = (pos >= lo) & (pos < lo + chunk)
        lp = jnp.clip(pos - lo, 0, chunk - 1)
        cur_k = jax.lax.dynamic_slice_in_dim(ck, lp, 1, axis=1)
        cur_v = jax.lax.dynamic_slice_in_dim(cv, lp, 1, axis=1)
        wk = jnp.where(in_range, kn, cur_k)
        wv = jnp.where(in_range, vn, cur_v)
        ck = jax.lax.dynamic_update_slice_in_dim(ck, wk, lp, axis=1)
        cv = jax.lax.dynamic_update_slice_in_dim(cv, wv, lp, axis=1)
        return ck, cv

    spec = P(bspec, seq_axis, None, None)
    rspec = P(bspec, None, None, None)
    return shard_map(local, mesh=mesh,
                         in_specs=(spec, spec, rspec, rspec),
                         out_specs=(spec, spec))(
                             cache_k, cache_v, k_new, v_new)
