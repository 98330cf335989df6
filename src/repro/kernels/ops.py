"""jit'd dispatch wrappers for the Pallas kernels.

``use_pallas()`` decides per-platform: real kernels on TPU, interpret-mode
(Python-evaluated, bit-validating) on CPU when forced, jnp reference paths
otherwise.  Model code calls these wrappers so the kernel/reference choice is
a deployment flag, not a code change.
"""
from __future__ import annotations

import functools
import os

import jax

from repro.kernels import flash_attention as _fa
from repro.kernels import lstm_cell as _lstm
from repro.kernels import moe_gmm as _gmm
from repro.kernels import ref as _ref
from repro.kernels import rwkv_scan as _wkv

_FORCE = os.environ.get("REPRO_KERNELS", "")  # "pallas" | "ref" | ""


def use_pallas() -> bool:
    if _FORCE == "pallas":
        return True
    if _FORCE == "ref":
        return False
    return jax.default_backend() == "tpu"


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


@functools.partial(jax.jit, static_argnames=("causal", "window"))
def attention(q, k, v, *, causal: bool = True, window: int = 0):
    """Flash attention (kv heads must be pre-repeated to q heads)."""
    if use_pallas():
        return _fa.flash_attention(q, k, v, causal=causal, window=window,
                                   interpret=_interpret())
    return _ref.attention_ref(q, k, v, causal=causal, window=window)


@functools.partial(jax.jit, static_argnames=("chunk",))
def wkv6(r, k, v, w, u, *, chunk: int = 128):
    if use_pallas():
        return _wkv.wkv6(r, k, v, w, u, chunk=chunk, interpret=_interpret())
    out, _ = _ref.wkv6_ref(r, k, v, w, u)
    return out


@jax.jit
def gmm(x, w):
    if use_pallas():
        return _gmm.gmm(x, w, interpret=_interpret())
    return _ref.gmm_ref(x, w)


@jax.jit
def lstm_cell(x, h, c, wx, wh, b):
    if use_pallas():
        return _lstm.lstm_cell(x, h, c, wx, wh, b, interpret=_interpret())
    return _ref.lstm_cell_ref(x, h, c, wx, wh, b)
