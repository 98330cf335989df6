"""Flash attention on the TPU, as Pallas kernels.

``causal_self_attention`` is the one on the main path: the train step's
causal self-attention (``models.layers.attention``) runs through it on a
TPU.  It calls JAX's bundled splash-attention kernel, which has a forward
and a backward pass, skips the blocks its mask hides, and reads grouped
K/V heads without repeating them.

``flash_attention`` below it is a forward-only kernel that no model path
calls.

Tiling of ``flash_attention``: grid (batch*heads, n_q_blocks, n_kv_blocks);
the kv axis is the innermost (sequential) dimension so the online-softmax
state lives in VMEM scratch across kv iterations.  Block shapes are
MXU-aligned (q/kv block x head_dim, multiples of 128 where the head_dim
allows).  Causal and sliding-window masking happen on block indices first
(whole-block skip) and lane indices second.

VMEM budget per step: q (bq, hd) + k,v (bk, hd) + scores (bq, bk) f32 +
acc (bq, hd) f32 + m,l (bq,) — e.g. bq=bk=512, hd=128: ~2.4 MB, well under
the ~16 MB/core VMEM of a v5e.

The pure-jnp oracle is ``repro.models.layers._chunked_attention`` /
``ref.attention_ref``; tests sweep shapes/dtypes against it with
interpret=True.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.experimental.pallas.ops.tpu import splash_attention as splash

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  causal: bool, window: int, bq: int, bk: int, n_kv: int,
                  sm_scale: float, seq_len: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = qi * bq
    k_start = ki * bk
    # whole-block skip: block fully masked out?
    run = True
    if causal:
        run = k_start <= q_start + bq - 1
    # (windows can't whole-block skip the lower side without dynamic grids;
    # lane masking below handles it)

    def body():
        q = q_ref[0].astype(jnp.float32) * sm_scale          # (bq, hd)
        k = k_ref[0].astype(jnp.float32)                     # (bk, hd)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = kpos < seq_len
        if causal:
            mask &= kpos <= qpos
        if window > 0:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=1)
        v = v_ref[0].astype(jnp.float32)
        acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    if causal:
        pl.when(k_start <= q_start + bq - 1)(body)
    else:
        body()

    @pl.when(ki == n_kv - 1)
    def _finalize():
        out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)[:, None]
        o_ref[0] = out.astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    block_q: int = 256, block_k: int = 256,
                    interpret: bool = False):
    """q: (B, Tq, H, hd); k, v: (B, Tk, H, hd) (kv heads pre-repeated).

    Returns (B, Tq, H, hd).  Tq/Tk are padded to block multiples internally.
    """
    b, tq, h, hd = q.shape
    tk = k.shape[1]
    bq = min(block_q, max(tq, 16))
    bk = min(block_k, max(tk, 16))
    pq = (bq - tq % bq) % bq
    pk = (bk - tk % bk) % bk
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
    if pk:
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
    # (B, T, H, hd) -> (B*H, T, hd)
    qr = q.transpose(0, 2, 1, 3).reshape(b * h, tq + pq, hd)
    kr = k.transpose(0, 2, 1, 3).reshape(b * h, tk + pk, hd)
    vr = v.transpose(0, 2, 1, 3).reshape(b * h, tk + pk, hd)
    n_q = (tq + pq) // bq
    n_kv = (tk + pk) // bk
    kernel = functools.partial(
        _flash_kernel, causal=causal, window=window, bq=bq, bk=bk, n_kv=n_kv,
        sm_scale=1.0 / math.sqrt(hd), seq_len=tk)
    out = pl.pallas_call(
        kernel,
        grid=(b * h, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, bk, hd), lambda bh, qi, ki: (bh, ki, 0)),
            pl.BlockSpec((1, bk, hd), lambda bh, qi, ki: (bh, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, hd), lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, tq + pq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qr, kr, vr)
    out = out[:, :tq].reshape(b, h, tq, hd).transpose(0, 2, 1, 3)
    return out


# Blocks of the splash kernel, forward and backward: q and kv blocks of up to
# SPLASH_BLOCK rows, each kv block computed SPLASH_BLOCK_COMPUTE columns at a
# time; a sequence takes the largest such power of two that divides it.  One
# fused backward kernel computes dq with dk and dv; q and k are laid out
# sequence-minor.  Chosen by timing the kernel alone, forward and backward,
# on a TPU v5e at (8, 2048, 15 / 5 and 16 / 8 heads, 64).
SPLASH_BLOCK = 1024
SPLASH_BLOCK_COMPUTE = 512


def _splash_block_sizes(t: int) -> splash.BlockSizes:
    blk = math.gcd(t, SPLASH_BLOCK)
    compute = math.gcd(t, SPLASH_BLOCK_COMPUTE)
    seq_minor = splash.QKVLayout.SEQ_MINOR
    return splash.BlockSizes(
        block_q=blk, block_kv=blk, block_kv_compute=compute,
        block_q_dkv=blk, block_kv_dkv=blk, block_kv_dkv_compute=compute,
        use_fused_bwd_kernel=True, q_layout=seq_minor, k_layout=seq_minor)


def _splash_mask(t: int, hq: int, window: int) -> splash.MultiHeadMask:
    if window > 0:
        # key j visible from query i iff i - window < j <= i
        mask = splash.LocalMask((t, t), (window - 1, 0), 0)
    else:
        mask = splash.CausalMask((t, t))
    return splash.MultiHeadMask([mask] * hq)


def causal_self_attention(q, k, v, *, window: int = 0, softcap: float = 0.0,
                          interpret: bool = False):
    """Causal self-attention as a flash kernel with a forward and a backward.

    q: (B, T, Hq, hd); k, v: (B, T, Hkv, hd) with Hq a multiple of Hkv (GQA,
    not repeated).  ``window`` > 0 restricts key j to (i - window, i];
    ``softcap`` > 0 caps the scaled logits at softcap * tanh(s / softcap).
    T must be a multiple of 128.  Matmul operands keep the input dtype and
    accumulate in float32.  Returns (B, T, Hq, hd) in q's dtype.
    """
    b, t, hq, hd = q.shape
    # splash folds the mask into block tables on the host once per mask and
    # block shape (it caches them), and reads the K/V heads from k and v;
    # the tables become constants of the trace that builds the kernel
    kernel = splash.make_splash_mha(
        _splash_mask(t, hq, window), block_sizes=_splash_block_sizes(t),
        head_shards=1, q_seq_shards=1,
        attn_logits_soft_cap=softcap or None, interpret=interpret)
    # splash does not scale the logits
    q = (q * (1.0 / math.sqrt(hd))).astype(q.dtype)
    to_heads = lambda x: x.transpose(0, 2, 1, 3)      # (B, H, T, hd)
    out = jax.vmap(kernel)(to_heads(q), to_heads(k), to_heads(v))
    return to_heads(out)
