"""Fused LSTM cell Pallas TPU kernel (the paper's BigLSTM hot-spot — its
CuDNN "fused RNN kernel" analogue, DESIGN.md §3).

One kernel computes gates = x@Wx + h@Wh + b and the elementwise cell update,
so the (B, 4H) gates never round-trip to HBM.  Weights are laid out
(d_in, 4, H) so a column block covers all four gates of the same hidden
units.  Grid (B/bb, H/bh) with full-d contraction per tile (d_in <= ~8k fits
VMEM at bh=128: x tile (bb, d) + 2 weight tiles (d, 4, bh)).

Oracle: ``ref.lstm_cell_ref``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _lstm_kernel(x_ref, h_ref, c_ref, wx_ref, wh_ref, b_ref, hn_ref, cn_ref):
    x = x_ref[...].astype(jnp.float32)            # (bb, d_in)
    h = h_ref[...].astype(jnp.float32)            # (bb, d_h_in)
    c = c_ref[...].astype(jnp.float32)            # (bb, bh)
    bb = x.shape[0]
    bh = c.shape[1]
    wx = wx_ref[...].astype(jnp.float32)          # (d_in, 4, bh)
    wh = wh_ref[...].astype(jnp.float32)          # (d_h_in, 4, bh)
    b = b_ref[...].astype(jnp.float32)            # (4, bh)
    gx = jax.lax.dot(x, wx.reshape(wx.shape[0], 4 * bh),
                     preferred_element_type=jnp.float32)
    gh = jax.lax.dot(h, wh.reshape(wh.shape[0], 4 * bh),
                     preferred_element_type=jnp.float32)
    gates = (gx + gh).reshape(bb, 4, bh) + b[None]
    i, f, g, o = gates[:, 0], gates[:, 1], gates[:, 2], gates[:, 3]
    c_new = jax.nn.sigmoid(f + 1.0) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
    h_new = jax.nn.sigmoid(o) * jnp.tanh(c_new)
    hn_ref[...] = h_new.astype(hn_ref.dtype)
    cn_ref[...] = c_new.astype(cn_ref.dtype)


def lstm_cell(x, h, c, wx, wh, b, *, block_b: int = 128, block_h: int = 128,
              interpret: bool = False):
    """x: (B, d_in); h: (B, d_h_in); c: (B, H); wx: (d_in, 4, H);
    wh: (d_h_in, 4, H); b: (4, H).  Returns (h_new (B, H), c_new (B, H))."""
    bsz, d_in = x.shape
    hh = c.shape[1]
    bb = min(block_b, bsz)
    bh = min(block_h, hh)
    pb = (bb - bsz % bb) % bb
    ph = (bh - hh % bh) % bh
    if pb:
        x = jnp.pad(x, ((0, pb), (0, 0)))
        h = jnp.pad(h, ((0, pb), (0, 0)))
    if pb or ph:
        c = jnp.pad(c, ((0, pb), (0, ph)))
    if ph:
        wx = jnp.pad(wx, ((0, 0), (0, 0), (0, ph)))
        wh = jnp.pad(wh, ((0, 0), (0, 0), (0, ph)))
        b = jnp.pad(b, ((0, 0), (0, ph)))
    nb, nh = (bsz + pb) // bb, (hh + ph) // bh
    out_shape = [jax.ShapeDtypeStruct((bsz + pb, hh + ph), h.dtype),
                 jax.ShapeDtypeStruct((bsz + pb, hh + ph), c.dtype)]
    hn, cn = pl.pallas_call(
        _lstm_kernel,
        grid=(nb, nh),
        in_specs=[
            pl.BlockSpec((bb, d_in), lambda bi, hi: (bi, 0)),
            pl.BlockSpec((bb, h.shape[1]), lambda bi, hi: (bi, 0)),
            pl.BlockSpec((bb, bh), lambda bi, hi: (bi, hi)),
            pl.BlockSpec((d_in, 4, bh), lambda bi, hi: (0, 0, hi)),
            pl.BlockSpec((h.shape[1], 4, bh), lambda bi, hi: (0, 0, hi)),
            pl.BlockSpec((4, bh), lambda bi, hi: (0, hi)),
        ],
        out_specs=[pl.BlockSpec((bb, bh), lambda bi, hi: (bi, hi))] * 2,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(x, h, c, wx, wh, b)
    return hn[:bsz, :hh], cn[:bsz, :hh]
