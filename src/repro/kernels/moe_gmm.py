"""Grouped (per-expert) matmul Pallas TPU kernel for the MoE capacity buffer.

Computes out[g] = x[g] @ w[g] for G experts: grid (G, C/bc, F/bf, d/bd) with
the contraction axis innermost, accumulating in an f32 VMEM scratch tile.
This is the compute hot-spot of the sorted-capacity MoE dispatch
(``repro.models.moe._expert_compute``'s einsum); blocks are MXU-aligned
(128x128 output tiles).

Oracle: ``ref.gmm_ref``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _gmm_kernel(x_ref, w_ref, o_ref, acc_ref, *, n_d: int):
    di = pl.program_id(3)

    @pl.when(di == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[0]                                  # (bc, bd)
    w = w_ref[0]                                  # (bd, bf)
    acc_ref[...] += jax.lax.dot(x, w, preferred_element_type=jnp.float32)

    @pl.when(di == n_d - 1)
    def _done():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


def gmm(x, w, *, block_c: int = 128, block_f: int = 128, block_d: int = 512,
        interpret: bool = False):
    """x: (G, C, d); w: (G, d, F) -> (G, C, F)."""
    g, c, d = x.shape
    f = w.shape[2]
    bc = min(block_c, c)
    bf = min(block_f, f)
    bd = min(block_d, d)
    pc = (bc - c % bc) % bc
    pf = (bf - f % bf) % bf
    pd = (bd - d % bd) % bd
    if pc or pd:
        x = jnp.pad(x, ((0, 0), (0, pc), (0, pd)))
    if pd or pf:
        w = jnp.pad(w, ((0, 0), (0, pd), (0, pf)))
    n_c, n_f, n_d = (c + pc) // bc, (f + pf) // bf, (d + pd) // bd
    kernel = functools.partial(_gmm_kernel, n_d=n_d)
    out = pl.pallas_call(
        kernel,
        grid=(g, n_c, n_f, n_d),
        in_specs=[
            pl.BlockSpec((1, bc, bd), lambda gi, ci, fi, di: (gi, ci, di)),
            pl.BlockSpec((1, bd, bf), lambda gi, ci, fi, di: (gi, di, fi)),
        ],
        out_specs=pl.BlockSpec((1, bc, bf), lambda gi, ci, fi, di: (gi, ci, fi)),
        out_shape=jax.ShapeDtypeStruct((g, c + pc, f + pf), x.dtype),
        scratch_shapes=[pltpu.VMEM((bc, bf), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(x, w)
    return out[:, :c, :f]
