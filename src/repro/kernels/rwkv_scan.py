"""RWKV6 WKV recurrence Pallas TPU kernel.

Grid: (batch*heads, n_chunks) with the chunk axis sequential; the per-head
state S (hd x hd, f32) persists in VMEM scratch across chunk iterations, so
the HBM traffic is exactly r/k/v/w in + out out — the recurrence never spills.
Within a chunk the cross-token term is a (chunk x chunk) masked matmul on the
MXU, identical math to ``repro.models.rwkv.wkv_chunked`` (the oracle via
``ref.wkv6_ref`` is the plain sequential scan).

VMEM per step (chunk=128, hd=64): 4 inputs (128, 64) f32 + S (64, 64) +
scores (128, 128) ≈ 0.3 MB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _wkv_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, o_ref, s_ref, *,
                chunk: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    r = r_ref[0].astype(jnp.float32)          # (chunk, hd)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    w = w_ref[0].astype(jnp.float32)
    u = u_ref[0].astype(jnp.float32)          # (1, hd)
    S = s_ref[...]                            # (hd_k, hd_v)

    logw = jnp.log(jnp.maximum(w, 1e-38))
    ii = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    # inclusive cumsum over the chunk as a lower-triangular matmul: Mosaic
    # has no cumsum lowering; HIGHEST keeps the f32 sum off bf16 passes
    cum = jax.lax.dot(jnp.where(ii >= jj, 1.0, 0.0), logw,
                      precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)   # (chunk, hd)
    cume = cum - logw                         # exclusive
    total = cum[-1:, :]                       # (1, hd)

    # inter-chunk: r_i decayed against carried state
    r_dec = r * jnp.exp(cume)
    inter = jax.lax.dot(r_dec, S, preferred_element_type=jnp.float32)
    # intra-chunk pairwise j < i
    a = r * jnp.exp(cume)
    bmat = k * jnp.exp(-cum)
    scores = jax.lax.dot_general(a, bmat, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    scores = jnp.where(ii > jj, scores, 0.0)
    diag = jnp.sum(r * u * k, axis=1, keepdims=True)      # (chunk, 1)
    intra = jax.lax.dot(scores, v, preferred_element_type=jnp.float32) \
        + diag * v
    o_ref[0] = (inter + intra).astype(o_ref.dtype)

    # advance state: S' = diag(exp(total)) S + sum_j exp(total - cum_j) k_j v_j
    kw = k * jnp.exp(total - cum)
    s_ref[...] = jnp.exp(total).T * S + jax.lax.dot_general(
        kw, v, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)


def wkv6(r, k, v, w, u, *, chunk: int = 128, interpret: bool = False):
    """r,k,v,w: (B, T, H, hd); u: (H, hd) -> (out (B,T,H,hd) f32, S_final).

    T must be a multiple of ``chunk`` (caller pads).  Final state is not
    returned by the kernel (train path doesn't need it); use the oracle for
    stateful decode.
    """
    b, t, h, hd = r.shape
    assert t % chunk == 0, (t, chunk)
    n_chunks = t // chunk

    def re(x):
        return x.transpose(0, 2, 1, 3).reshape(b * h, t, hd)

    rr, kr, vr, wr = re(r), re(k), re(v), re(w)
    ur = jnp.broadcast_to(u[None], (b, h, hd)).reshape(b * h, 1, hd)
    kernel = functools.partial(_wkv_kernel, chunk=chunk)
    out = pl.pallas_call(
        kernel,
        grid=(b * h, n_chunks),
        in_specs=[
            pl.BlockSpec((1, chunk, hd), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, chunk, hd), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, chunk, hd), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, chunk, hd), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, 1, hd), lambda bh, ci: (bh, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, hd), lambda bh, ci: (bh, ci, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, t, hd), jnp.float32),
        scratch_shapes=[pltpu.VMEM((hd, hd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(rr, kr, vr, wr, ur)
    return out.reshape(b, h, t, hd).transpose(0, 2, 1, 3)
