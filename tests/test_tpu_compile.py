"""Compile the Pallas kernels and the SmolLM-360M train step for a described
TPU v5e at real widths.

Nothing runs: the TPU compiler installed with JAX compiles for a chip that
is described, not attached, and refuses what the chip would refuse
(primitives Mosaic cannot lower, misaligned blocks, a program that does not
fit the chip's 16 GiB).  The topology is described inside the module
fixture, never at import: only the worker given this file loads libtpu.
"""
import dataclasses
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

V5E_HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip can be written to the persistent
    # cache but not read back without one
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _shape(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile_kernel(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def test_flash_attention_smollm_widths(one_chip):
    from repro.kernels.flash_attention import flash_attention
    q = _shape(one_chip, (8, 2048, 15, 64), jnp.bfloat16)    # B x T x H x hd
    _compile_kernel(lambda q, k, v: flash_attention(q, k, v, causal=True),
                    q, q, q)


def test_gmm_granite_moe_widths(one_chip):
    from repro.kernels.moe_gmm import gmm
    x = _shape(one_chip, (32, 512, 1024), jnp.bfloat16)      # E x C x d
    w = _shape(one_chip, (32, 1024, 512), jnp.bfloat16)      # E x d x ff
    _compile_kernel(gmm, x, w)


def test_lstm_cell_biglstm_widths(one_chip):
    from repro.kernels.lstm_cell import lstm_cell
    b, d, hh = 128, 1024, 8192            # 1024 in and projected, 8192 cells
    args = [_shape(one_chip, s) for s in
            ((b, d), (b, d), (b, hh), (d, 4, hh), (d, 4, hh), (4, hh))]
    _compile_kernel(lstm_cell, *args)


def test_wkv6_rwkv6_7b_widths(one_chip):
    from repro.kernels.rwkv_scan import wkv6
    x = _shape(one_chip, (1, 1024, 64, 64))                  # B x T x H x hd
    u = _shape(one_chip, (64, 64))
    _compile_kernel(lambda r, k, v, w, u: wkv6(r, k, v, w, u, chunk=128),
                    x, x, x, x, u)


def test_smollm_360m_train_step_fits_one_chip(one_chip):
    """Full width (n_layers cut to 2), batch 8 x seq 2048, fp32 params and
    AdamW, default remat: the step the one-chip smoke run executes."""
    from repro.configs import get_config
    from repro.models import build_model
    from repro.optim import adamw, warmup_cosine
    from repro.train.steps import eval_train_state, make_train_step

    cfg = dataclasses.replace(get_config("smollm_360m"), n_layers=2)
    api = build_model(cfg)
    opt = adamw(warmup_cosine(3e-3, 20, 100))
    state = jax.tree.map(lambda s: _shape(one_chip, s.shape, s.dtype),
                         eval_train_state(api, opt))
    batch = {k: _shape(one_chip, (8, 2048), jnp.int32)
             for k in ("tokens", "labels")}
    compiled = jax.jit(make_train_step(api, opt),
                       donate_argnums=(0,)).lower(state, batch).compile()
    ma = compiled.memory_analysis()
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
    assert ma.alias_size_in_bytes > 0          # the state is donated
    assert total < V5E_HBM_BYTES, total
