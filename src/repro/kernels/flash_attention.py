"""Flash attention Pallas TPU kernel (forward).

Tiling: grid (batch*heads, n_q_blocks, n_kv_blocks); the kv axis is the
innermost (sequential) dimension so the online-softmax state lives in VMEM
scratch across kv iterations.  Block shapes are MXU-aligned (q/kv block x
head_dim, multiples of 128 where the head_dim allows).  Causal and
sliding-window masking happen on block indices first (whole-block skip) and
lane indices second.

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
