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
def v5e():
    """A described v5e 2x2: four chips."""
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
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(v5e):
    return SingleDeviceSharding(v5e.devices[0])


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


def test_causal_self_attention_granite_widths_forward_and_backward(one_chip):
    """The splash entry point and its gradient at Granite-MoE's widths:
    16 q / 8 kv heads of 64, batch 8 x 2048."""
    from repro.kernels.flash_attention import causal_self_attention
    q = _shape(one_chip, (8, 2048, 16, 64), jnp.bfloat16)
    kv = _shape(one_chip, (8, 2048, 8, 64), jnp.bfloat16)

    def fwd_bwd(q, k, v):
        out, vjp = jax.vjp(causal_self_attention, q, k, v)
        return out, vjp(out)

    text = _compile_kernel(fwd_bwd, q, kv, kv).as_text()
    # the backward is one fused kernel: dq with dk and dv
    for kernel in ("splash_mha_fwd", "splash_mha_dkv"):
        assert kernel in text, kernel


@pytest.mark.parametrize("hq,hkv,hd,window", [
    (16, 8, 128, 0),        # InternVL2-2B's language model: head_dim 128
    (25, 5, 64, 1024),      # Hymba-1.5B's sliding-window layers
], ids=["internvl2_hd128", "hymba_window1024"])
def test_causal_self_attention_other_widths_forward_and_backward(
        one_chip, hq, hkv, hd, window):
    """The entry point's forward and backward at the widths of the other
    configs that take it: blocks of 1024 at head_dim 128 fit the kernel's
    fast memory, and a sliding window compiles as a local mask."""
    from repro.kernels.flash_attention import causal_self_attention
    q = _shape(one_chip, (8, 2048, hq, hd), jnp.bfloat16)
    kv = _shape(one_chip, (8, 2048, hkv, hd), jnp.bfloat16)

    def fwd_bwd(q, k, v):
        out, vjp = jax.vjp(lambda q, k, v: causal_self_attention(
            q, k, v, window=window), q, k, v)
        return out, vjp(out)

    text = _compile_kernel(fwd_bwd, q, kv, kv).as_text()
    for kernel in ("splash_mha_fwd", "splash_mha_dkv"):
        assert kernel in text, kernel


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
    AdamW, default remat: the step the one-chip smoke run executes.  Its
    attention runs as the splash kernel, forward and backward."""
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
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # the backward is one fused kernel: dq with dk and dv
    for kernel in ("splash_mha_fwd", "splash_mha_dkv"):
        assert kernel in text, kernel
    ma = compiled.memory_analysis()
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
    assert ma.alias_size_in_bytes > 0          # the state is donated
    assert total < V5E_HBM_BYTES, total


@pytest.mark.parametrize("spec,kernel", [
    ("pipe=2,micro=4,sched=1f1b,dp=2", True),
    ("dp=4", False),
])
def test_smollm_360m_four_chip_steps_attention(v5e, spec, kernel):
    """The four-chip layouts ``chip_smoke.py --chips 4`` compares, at full
    width with n_layers cut to 2, for a described 2x2 v5e: the pipeline
    stages attend through the splash kernel inside their shard_map bodies;
    under GSPMD's dp=4 mesh attention keeps the dense XLA path."""
    import numpy as np
    from jax.sharding import AxisType, Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.configs import get_config
    from repro.launch.train import parse_parallel
    from repro.models import build_model
    from repro.models.layers import count_attention_paths
    from repro.optim import adamw, warmup_cosine
    from repro.train.steps import (eval_train_state, make_train_step,
                                   shardings_for)

    cfg = dataclasses.replace(get_config("smollm_360m"), n_layers=2)
    plan, mp, dp = parse_parallel(spec, 4, cfg)
    plan = dataclasses.replace(plan, dp_axes=("data",), fsdp_axes=())
    mesh = Mesh(np.array(v5e.devices[:4]).reshape(dp, mp),
                ("data", "model"), axis_types=(AxisType.Auto,) * 2)
    api = build_model(cfg)
    opt = adamw(warmup_cosine(3e-3, 20, 100))
    specs = {k: jax.ShapeDtypeStruct((8, 2048), jnp.int32)
             for k in ("tokens", "labels")}
    if plan.is_pipeline:
        # as the launcher lays out dp x stages: state replicated, batch
        # over the data axis
        state_sh = jax.tree.map(lambda _: NamedSharding(mesh, P()),
                                eval_train_state(api, opt))
        batch_sh = {k: NamedSharding(mesh, P("data", None)) for k in specs}
    else:
        state_sh, batch_sh = shardings_for(api, mesh, plan, opt, specs)
    state = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        eval_train_state(api, opt), state_sh)
    batch = {k: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=batch_sh[k])
             for k, s in specs.items()}
    step = jax.jit(make_train_step(api, opt, mesh=mesh, plan=plan),
                   donate_argnums=(0,), out_shardings=(state_sh, None))
    with jax.set_mesh(mesh), count_attention_paths() as paths:
        compiled = step.lower(state, batch).compile()
    text = compiled.as_text()
    assert set(paths) == {"kernel" if kernel else "dense"}, dict(paths)
    for name in ("splash_mha_fwd", "splash_mha_dkv"):
        assert (name in text) == kernel, name
    ma = compiled.memory_analysis()
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
    assert total < V5E_HBM_BYTES, total
