"""Context-parallel (CP) ring attention: shard the SEQUENCE axis on
ppermute KV rings.

Tensor-MP (``parallel.collectives``) splits parameters and pipeline-MP
splits layers; neither touches the axis that actually explodes for
long-context workloads.  CP keeps the residual stream sequence-sharded
across the ring — every device holds T/m query rows for the whole layer
stack — and runs attention itself as a ring: the KV shards rotate around
a ``ppermute`` ring while each device's flash attention consumes the
in-flight block, folding it into the online-softmax (m, l, acc) state it
already keeps, exactly the merge rule of
``models.layers.merge_softmax_stats``.  No tensor of global sequence
length is ever materialized on any chip.

Ring schedule (m = 4 devices; payload at step s on device j is KV block
``src = (j - s) mod m``, sent to j+1 WHILE the local partial attention
consumes it)::

        s:    0       1       2       3
      j=0:  KV0·A   KV3·A   KV2·A   KV1·A     A = online-softmax fold
      j=1:  KV1·A   KV0·A   KV3·A   KV2·A     into (m, l, acc); step 0
      j=2:  KV2·A   KV1·A   KV0·A   KV3·A     is the diagonal block, so
      j=3:  KV3·A   KV2·A   KV1·A   KV0·A     every query is live first

Causal masking skips WHOLE remote blocks by ring distance: block ``src``
is strictly-future iff ``src > j``, so device j only computes ``j + 1``
of its m hops (the block is still forwarded on the ring — the transfer
is overlapped anyway, the matmuls are what's saved; same trick for
blocks entirely left of a sliding window).  The backward is a custom
vjp running the REVERSE ring: kb/vb rotate as in the forward while the
dK/dV accumulators ride the ring one hop per step, landing home on their
owner after m hops with every device's contribution summed.

Per-hop cost (GQA: the ring carries the UN-repeated Hkv heads; B batch,
t = T/m local rows, e bytes/elem, bw = per-hop link bandwidth, a =
per-hop latency; compare ``core.comm.cp_ring_time``)::

    ==================  ========================  =======================
    path                wire bytes per chip       exposed time
    ==================  ========================  =======================
    all-gather K,V      2 (m-1)/m * B_kv          transfer THEN attend
                                                    (nothing overlaps)
    CP ring fwd         (m-1) * 2*B*t*Hkv*hd*e    max(hop attn, hop xfer)
                                                    * (m-1) + (m-1) a
    CP ring bwd         2x fwd (dK/dV ride too)   same, ~2.5x hop flops
    ==================  ========================  =======================

Numerics: all (m, l, acc) state is f32; a fold of a fully-masked row is
exp(NEG_INF - finite) = 0 exactly, and step 0's diagonal block gives
every query a finite max before any remote block arrives, so no
NaN-producing (-inf) - (-inf) ever forms.  ``ring_attention`` is pinned
(fp32 round-off) against the unsharded flash/ref attention — loss AND
grads — in ``tests/test_context_parallel.py``.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from repro import scopes
from repro.models.layers import NEG_INF, repeat_kv
from repro.parallel.collectives import _ring_perm


def _block_skip(src, j, t_loc: int, causal: bool, window: int):
    """Traced predicate: KV block ``src`` contributes nothing to device
    ``j``'s queries, so the hop's matmuls can be skipped entirely.
    Returns None when no static reason to skip exists."""
    skip = None
    if causal:
        skip = src > j                       # strictly-future block
    if window > 0:
        # block src's newest key is (src+1)*t_loc - 1; the oldest query
        # on j is j*t_loc, which sees keys in (j*t_loc - window, j*t_loc]
        too_old = (src + 1) * t_loc - 1 + window <= j * t_loc
        skip = too_old if skip is None else jnp.logical_or(skip, too_old)
    return skip


def _hop_mask(qpos, kpos, causal: bool, window: int):
    valid = None
    if causal:
        valid = kpos[None, :] <= qpos[:, None]
    if window > 0:
        w = kpos[None, :] > qpos[:, None] - window
        valid = w if valid is None else valid & w
    return valid


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3))
def _ring_attn(axis, axis_size, causal, window, q, k, v):
    return _ring_attn_fwd(axis, axis_size, causal, window, q, k, v)[0]


def _ring_attn_fwd(axis, axis_size, causal, window, q, k, v):
    m_st, l_st, acc = _ring_fwd_stats(axis, axis_size, causal, window,
                                      q, k, v)
    l_safe = jnp.maximum(l_st, 1e-30)
    out = (acc / l_safe[..., None]).transpose(0, 2, 1, 3).astype(q.dtype)
    lse = m_st + jnp.log(l_safe)                        # (b,h,t)
    return out, (q, k, v, out, lse)


def _ring_fwd_stats(axis, axis_size, causal, window, q, k, v):
    m = axis_size
    b, t_loc, hq, hd = q.shape
    hkv = k.shape[2]
    n_rep = hq // hkv
    j = lax.axis_index(axis)
    scale = 1.0 / math.sqrt(hd)
    qt = q.astype(jnp.float32).transpose(0, 2, 1, 3) * scale  # (b,h,t,hd)
    qpos = j * t_loc + jnp.arange(t_loc)
    m_st = jnp.full((b, hq, t_loc), NEG_INF, jnp.float32)
    l_st = jnp.zeros((b, hq, t_loc), jnp.float32)
    acc = jnp.zeros((b, hq, t_loc, hd), jnp.float32)
    perm = _ring_perm(m)
    kb, vb = k, v
    for s in range(m):
        src = (j - s) % m
        nxt = ([lax.ppermute(p, axis, perm) for p in (kb, vb)]
               if s < m - 1 else None)                  # send before compute
        kpos = src * t_loc + jnp.arange(t_loc)

        def fold(carry, kb=kb, vb=vb, kpos=kpos):
            m0, l0, a0 = carry
            kr = repeat_kv(kb, n_rep).astype(jnp.float32)
            vr = repeat_kv(vb, n_rep).astype(jnp.float32)
            sc = jnp.einsum("bhqd,bkhd->bhqk", qt, kr)
            valid = _hop_mask(qpos, kpos, causal, window)
            if valid is not None:
                sc = jnp.where(valid[None, None], sc, NEG_INF)
            m1 = jnp.maximum(m0, sc.max(axis=-1))
            p = jnp.exp(sc - m1[..., None])
            corr = jnp.exp(m0 - m1)
            l1 = l0 * corr + p.sum(axis=-1)
            a1 = a0 * corr[..., None] + jnp.einsum("bhqk,bkhd->bhqd", p, vr)
            return m1, l1, a1

        skip = _block_skip(src, j, t_loc, causal, window)
        if skip is None:
            m_st, l_st, acc = fold((m_st, l_st, acc))
        else:
            m_st, l_st, acc = lax.cond(skip, lambda c: c, fold,
                                       (m_st, l_st, acc))
        if nxt is not None:
            kb, vb = nxt
    return m_st, l_st, acc


def _ring_attn_bwd(axis, axis_size, causal, window, res, dout):
    q, k, v, out, lse = res
    m = axis_size
    b, t_loc, hq, hd = q.shape
    hkv = k.shape[2]
    n_rep = hq // hkv
    j = lax.axis_index(axis)
    scale = 1.0 / math.sqrt(hd)
    qt = q.astype(jnp.float32).transpose(0, 2, 1, 3) * scale
    dot = dout.astype(jnp.float32).transpose(0, 2, 1, 3)  # (b,h,t,hd)
    # D_i = sum_d dout_i * out_i — the softmax-jacobian diagonal term
    dterm = (dot * out.astype(jnp.float32).transpose(0, 2, 1, 3)).sum(-1)
    qpos = j * t_loc + jnp.arange(t_loc)
    perm = _ring_perm(m)
    kb, vb = k, v
    dq = jnp.zeros((b, hq, t_loc, hd), jnp.float32)
    # dK/dV accumulators RIDE the ring: ppermuted after every local
    # update (m hops total) so the block-j accumulator lands back on
    # device j carrying all m devices' contributions
    dkb = jnp.zeros((b, t_loc, hkv, hd), jnp.float32)
    dvb = jnp.zeros((b, t_loc, hkv, hd), jnp.float32)
    for s in range(m):
        src = (j - s) % m
        nxt = ([lax.ppermute(p, axis, perm) for p in (kb, vb)]
               if s < m - 1 else None)                  # send before compute
        kpos = src * t_loc + jnp.arange(t_loc)

        def hop(carry, kb=kb, vb=vb, kpos=kpos):
            dq0, dk0, dv0 = carry
            kr = repeat_kv(kb, n_rep).astype(jnp.float32)
            vr = repeat_kv(vb, n_rep).astype(jnp.float32)
            sc = jnp.einsum("bhqd,bkhd->bhqk", qt, kr)
            valid = _hop_mask(qpos, kpos, causal, window)
            if valid is not None:
                sc = jnp.where(valid[None, None], sc, NEG_INF)
            p = jnp.exp(sc - lse[..., None])            # exact probs
            dv_h = jnp.einsum("bhqk,bhqd->bkhd", p, dot)
            dp = jnp.einsum("bhqd,bkhd->bhqk", dot, vr)
            ds = p * (dp - dterm[..., None])
            dq1 = dq0 + jnp.einsum("bhqk,bkhd->bhqd", ds, kr) * scale
            dk_h = jnp.einsum("bhqk,bhqd->bkhd", ds, qt)  # scale via qt
            # GQA: a kv head's grad sums over its repeat group
            dk1 = dk0 + dk_h.reshape(b, t_loc, hkv, n_rep, hd).sum(3)
            dv1 = dv0 + dv_h.reshape(b, t_loc, hkv, n_rep, hd).sum(3)
            return dq1, dk1, dv1

        skip = _block_skip(src, j, t_loc, causal, window)
        if skip is None:
            dq, dkb, dvb = hop((dq, dkb, dvb))
        else:
            dq, dkb, dvb = lax.cond(skip, lambda c: c, hop, (dq, dkb, dvb))
        dkb, dvb = [lax.ppermute(p, axis, perm) for p in (dkb, dvb)]
        if nxt is not None:
            kb, vb = nxt
    return (dq.transpose(0, 2, 1, 3).astype(q.dtype),
            dkb.astype(k.dtype), dvb.astype(v.dtype))


_ring_attn.defvjp(_ring_attn_fwd, _ring_attn_bwd)


@scopes.scoped(scopes.ATTN_CORE)
def ring_attention(q, k, v, *, axis: str, axis_size: int,
                   causal: bool = True, window: int = 0):
    """Context-parallel GQA attention over a sequence-sharded ring.

    Runs inside a shard_map.  ``q``: (B, T/m, Hq, hd) this device's query
    rows; ``k``/``v``: (B, T/m, Hkv, hd) this device's KV shard (the ring
    carries the un-repeated Hkv heads).  Returns (B, T/m, Hq, hd), this
    device's output rows.  Forward and backward are chunked ppermute
    rings — the compiled HLO carries no all-gather of K/V in either
    direction.  Loss and grads match unsharded ``layers.attention`` at
    fp32 round-off (pinned in tests).
    """
    if axis_size <= 1:
        from repro.models.layers import attention
        return attention(q, k, v, causal=causal, window=window)
    return _ring_attn(axis, axis_size, bool(causal), int(window), q, k, v)


def ring_attention_stats(q, k, v, *, axis: str, axis_size: int,
                         causal: bool = True, window: int = 0):
    """Forward-only ring returning the UNNORMALIZED online-softmax stats
    triple ``(m, l, acc)`` in f32 — shapes (B, Hq, T/m), (B, Hq, T/m),
    (B, Hq, T/m, hd) — mergeable with other partials via
    ``models.layers.merge_softmax_stats``.  This is the serve
    chunked-prefill building block: the chunk's in-chunk attention rides
    the ring (positions are chunk-relative; causal/window masks compare
    q-k DIFFERENCES so a per-request absolute offset cancels), while the
    KV-cache contribution is computed locally per device and merged in
    afterwards.  Inference-path only (no custom_vjp)."""
    return _ring_fwd_stats(axis, axis_size, bool(causal), int(window),
                           q, k, v)


def gathered_attention(q, k, v, *, axis: str, axis_size: int,
                       causal: bool = True, window: int = 0):
    """All-gather-then-attend baseline: reassemble the FULL K/V on every
    device, then run plain attention on the local query rows.  This is
    what GSPMD lowers a sequence-sharded attention to; it exists as the
    benchmark/HLO-contrast foil for ``ring_attention`` (its HLO contains
    the monolithic all-gather the ring avoids)."""
    from repro.models.layers import attention
    if axis_size <= 1:
        return attention(q, k, v, causal=causal, window=window)
    j = lax.axis_index(axis)
    kg = lax.all_gather(k, axis, axis=1, tiled=True)
    vg = lax.all_gather(v, axis, axis=1, tiled=True)
    return attention(q, kg, vg, causal=causal, q_start=j * q.shape[1],
                     window=window)
