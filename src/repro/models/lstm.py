"""The paper's RNN evaluation models in JAX: GNMT (4-layer LSTM enc-dec with
attention, Wu et al. 2016) and BigLSTM (Jozefowicz et al. 2016: embedding 1024,
2 LSTM layers hidden 8192 with 1024 projection, big softmax).

These are the models the paper pipelines (Table 1: GNMT 1.15x, BigLSTM 1.22x
2-way MP) — the pipeline runtime in ``repro.parallel.pipeline`` partitions
their layer stacks into stages.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.models.layers import dense_init, embed_init
from repro.parallel.pipeline import pipeline_apply, stack_to_stages


def stack_layer_params(layer_list):
    """Homogeneous per-layer param dicts -> one stacked (L, ...) pytree, the
    layout ``parallel.pipeline.stack_to_stages`` partitions into stages."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *layer_list)


def lstm_cell_init(key, d_in: int, d_h: int, d_proj: int = 0, dtype=jnp.float32):
    ks = jax.random.split(key, 3)
    p = {
        "wx": dense_init(ks[0], d_in, 4 * d_h, dtype),
        "wh": dense_init(ks[1], d_proj or d_h, 4 * d_h, dtype),
        "b": jnp.zeros((4 * d_h,), jnp.float32),
    }
    if d_proj:
        p["wp"] = dense_init(ks[2], d_h, d_proj, dtype)
    return p


def lstm_cell(p, x, state):
    """x: (B, d_in); state: (h, c).  Returns (new_state, output)."""
    h, c = state
    gates = x @ p["wx"].astype(x.dtype) + h @ p["wh"].astype(x.dtype) \
        + p["b"].astype(x.dtype)
    i, f, g, o = jnp.split(gates, 4, axis=-1)
    c = jax.nn.sigmoid(f + 1.0) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
    out = jax.nn.sigmoid(o) * jnp.tanh(c)
    if "wp" in p:
        out = out @ p["wp"].astype(x.dtype)
    return (out, c), out


def lstm_layer(p, xs, state=None):
    """xs: (B, T, d_in) -> (B, T, d_out); scan over time."""
    b = xs.shape[0]
    d_h = p["wx"].shape[1] // 4
    d_out = p["wp"].shape[1] if "wp" in p else d_h
    if state is None:
        state = (jnp.zeros((b, d_out), xs.dtype), jnp.zeros((b, d_h), xs.dtype))

    def step(st, x):
        return lstm_cell(p, x, st)

    state, ys = jax.lax.scan(step, state, xs.transpose(1, 0, 2))
    return ys.transpose(1, 0, 2), state


def lstm_layer_overlapped(p, xs, *, mesh, axis: str, batch_axes=(),
                          chunks: int = 1):
    """Megatron tensor-MP LSTM layer on the overlap-scheduled collective
    rings (``parallel.collectives``): the time-parallel input projection
    ``x @ wx`` — the layer's dominant matmul — rides an
    ``all_gather_matmul`` ring over the TIME dim with gate-major hidden
    sharding (each shard owns a dh/m slice of every gate, so the cell
    nonlinearities stay shard-local); the recurrence keeps h replicated
    (``wh`` column-sharded, no comm per step) and the cell state c sharded.
    The per-step output projection (``wp``, row-parallel) psums — the
    recurrent dependence serializes it, which is exactly the exposed-MP-comm
    term the paper measures for the RNN models; cells without a projection
    all-gather their sharded hidden instead.  xs: (B, T, d_in) with
    T % axis_size == 0.  Returns (ys, (h, c)) like ``lstm_layer``."""
    from jax.sharding import PartitionSpec as P

    from repro.parallel.collectives import all_gather_matmul
    from repro.parallel.jaxcompat import shard_map

    m = mesh.shape[axis]
    b, t, d_in = xs.shape
    d_h = p["wx"].shape[1] // 4
    have_wp = "wp" in p
    d_out = p["wp"].shape[1] if have_wp else d_h
    dhm = d_h // m
    baxes = tuple(a for a in batch_axes if a)
    dp = 1
    for a in baxes:
        dp *= mesh.shape[a]
    bspec = baxes if (baxes and dp > 1 and b % dp == 0) else None

    # gate-major view: (d, 4*dh) -> (d, 4, dh) so the model axis shards the
    # hidden dim of every gate instead of splitting whole gates apart
    wx3 = p["wx"].reshape(d_in, 4, d_h)
    wh3 = p["wh"].reshape(d_out, 4, d_h)
    b2 = p["b"].reshape(4, d_h)
    h0 = jnp.zeros((b, d_out), xs.dtype)
    c0 = jnp.zeros((b, d_h), xs.dtype)

    def local(wx_l, wh_l, b_l, wp_l, xs_l, h0_l, c0_l):
        dt = xs_l.dtype
        gates_x = all_gather_matmul(
            xs_l, wx_l.reshape(d_in, 4 * dhm).astype(dt),
            axis=axis, axis_size=m, chunks=chunks)          # (b, T, 4*dh/m)
        wh_f = wh_l.reshape(d_out, 4 * dhm).astype(dt)
        b_f = b_l.reshape(4 * dhm).astype(dt)

        def step(st, gx):
            h, c = st
            gates = gx + h @ wh_f + b_f
            i, f, g, o = jnp.split(gates, 4, axis=-1)
            c = jax.nn.sigmoid(f + 1.0) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
            out = jax.nn.sigmoid(o) * jnp.tanh(c)           # (b, dh/m)
            if wp_l is not None:
                h = jax.lax.psum(out @ wp_l.astype(dt), axis)
            else:
                h = jax.lax.all_gather(out, axis, axis=-1, tiled=True)
            return (h, c), h

        (h, c), ys = jax.lax.scan(step, (h0_l, c0_l),
                                  gates_x.transpose(1, 0, 2))
        return ys.transpose(1, 0, 2), h, c

    gate_spec = P(None, None, axis)
    specs = [gate_spec, gate_spec, P(None, axis)]
    args = [wx3, wh3, b2]
    if have_wp:
        specs.append(P(axis, None))
        args.append(p["wp"])
        fn = local
    else:
        specs.append(P())
        args.append(jnp.zeros((), xs.dtype))

        def fn(wx_l, wh_l, b_l, _unused, xs_l, h0_l, c0_l):
            return local(wx_l, wh_l, b_l, None, xs_l, h0_l, c0_l)

    specs += [P(bspec, axis, None), P(bspec, None), P(bspec, axis)]
    args += [xs, h0, c0]
    ys, h, c = shard_map(
        fn, mesh=mesh, in_specs=tuple(specs),
        out_specs=(P(bspec, None, None), P(bspec, None), P(bspec, axis)))(
            *args)
    return ys, (h, c)


def lstm_overlapped_ok(cfg, pctx, t: int) -> bool:
    """Gate for the overlapped tensor-MP LSTM path: a real model axis, the
    hidden dim divisible by it (gate-major sharding), and the time dim
    divisible (the input projection rides a time-dim gather ring)."""
    if (pctx is None or getattr(pctx, "comm_runtime", "gspmd") != "overlapped"
            or pctx.mesh is None or pctx.model_axis is None):
        return False
    m = pctx.mesh.shape[pctx.model_axis]
    if m <= 1:
        return False
    chunks = max(getattr(pctx, "comm_chunks", 1), 1)
    return (cfg.d_ff % m == 0 and t % m == 0 and (t // m) % chunks == 0)


# ---------------------------------------------------------------------------
# GNMT
# ---------------------------------------------------------------------------

def gnmt_init(key, cfg):
    dtype = jnp.dtype(cfg.param_dtype)
    d, v, n = cfg.d_model, cfg.vocab_padded, cfg.n_layers
    ks = jax.random.split(key, 4 + 2 * n)
    params = {
        "src_embed": embed_init(ks[0], v, d, dtype),
        "tgt_embed": embed_init(ks[1], v, d, dtype),
        "enc": [lstm_cell_init(ks[2 + i], d if i == 0 else d, d, 0, dtype)
                for i in range(n)],
        "dec": [lstm_cell_init(ks[2 + n + i], (2 * d) if i == 0 else d, d, 0, dtype)
                for i in range(n)],
        "attn_q": dense_init(ks[2 + 2 * n], d, d, dtype),
        "head": dense_init(ks[3 + 2 * n], d, v, dtype),
    }
    return params


def gnmt_forward(cfg, params, batch):
    """batch: dict(src (B,S), tgt (B,T)).  Returns logits (B,T,V)."""
    dt = jnp.dtype(cfg.dtype)
    src = jnp.take(params["src_embed"], batch["src"], axis=0).astype(dt)
    x = src
    for i, lp in enumerate(params["enc"]):
        y, _ = lstm_layer(lp, x)
        x = y if i == 0 else x + y                       # residual from layer 2
    enc_out = x                                          # (B, S, d)
    tgt = jnp.take(params["tgt_embed"], batch["tgt"], axis=0).astype(dt)
    # Luong attention over encoder states from the first decoder layer's
    # output; attention context fed to subsequent layers (GNMT-style).
    y0, _ = lstm_layer(params["dec"][0],
                       jnp.concatenate([tgt, jnp.zeros_like(tgt)], -1))
    q = y0 @ params["attn_q"].astype(dt)
    scores = jnp.einsum("btd,bsd->bts", q, enc_out) / math.sqrt(cfg.d_model)
    ctx = jnp.einsum("bts,bsd->btd", jax.nn.softmax(scores, -1), enc_out)
    x = y0 + ctx
    for lp in params["dec"][1:]:
        y, _ = lstm_layer(lp, x)
        x = x + y
    return x @ params["head"].astype(dt)


# ---------------------------------------------------------------------------
# BigLSTM
# ---------------------------------------------------------------------------

def biglstm_init(key, cfg):
    dtype = jnp.dtype(cfg.param_dtype)
    d, v, dh = cfg.d_model, cfg.vocab_padded, cfg.d_ff
    ks = jax.random.split(key, 2 + cfg.n_layers)
    return {
        "embed": embed_init(ks[0], v, d, dtype),
        "lstm": [lstm_cell_init(ks[1 + i], d, dh, d, dtype)
                 for i in range(cfg.n_layers)],
        "head": dense_init(ks[1 + cfg.n_layers], d, v, dtype),
    }


def biglstm_forward(cfg, params, batch, pctx=None):
    dt = jnp.dtype(cfg.dtype)
    x = jnp.take(params["embed"], batch["tokens"], axis=0).astype(dt)
    overlapped = lstm_overlapped_ok(cfg, pctx, batch["tokens"].shape[1])
    if (not overlapped and pctx is not None
            and getattr(pctx, "comm_runtime", "gspmd") == "overlapped"
            and pctx.mesh is not None and pctx.model_axis is not None
            and pctx.mesh.shape[pctx.model_axis] > 1):
        import warnings
        warnings.warn(
            f"[collectives] biglstm: comm_runtime='overlapped' requested but "
            f"the overlapped LSTM layer cannot engage (needs hidden "
            f"({cfg.d_ff}) and seq ({batch['tokens'].shape[1]}) divisible "
            f"by the model axis and (seq/mp) % comm_chunks == 0); falling "
            f"back to GSPMD's monolithic collectives", stacklevel=2)
    for lp in params["lstm"]:
        if overlapped:
            y, _ = lstm_layer_overlapped(
                lp, x, mesh=pctx.mesh, axis=pctx.model_axis,
                batch_axes=tuple(a for a in pctx.batch_axes if a),
                chunks=max(pctx.comm_chunks, 1))
        else:
            y, _ = lstm_layer(lp, x)
        x = x + y
    return x @ params["head"].astype(dt)


def biglstm_stage_fn(cfg):
    """One pipeline chunk of BigLSTM's residual LSTM stack as a pure
    shape-preserving ``(chunk_params, x) -> y`` callable — the unit the
    hand-scheduled runtime ``jax.vjp``'s per WorkUnit."""

    def stage_fn(sp, x):
        def body(x, lp):
            y, _ = lstm_layer(lp, x)
            return x + y, None

        x, _ = jax.lax.scan(body, x, sp)
        return x

    return stage_fn


def biglstm_forward_pipeline(cfg, params, batch, *, mesh, axis: str,
                             n_micro: int, schedule: str = "gpipe",
                             virtual_stages: int = 1, batch_axes=()):
    """BigLSTM forward with the residual LSTM stack partitioned into
    pipeline stages over mesh ``axis`` — the paper's §4.4 MP implementation
    for the RNN models, streaming ``n_micro`` micro-batches through the
    stages under the requested ``schedule`` while ``batch_axes`` carries the
    data parallelism.  Bit-equal (fp32) to ``biglstm_forward``;
    embed/softmax stay replicated."""
    dt = jnp.dtype(cfg.dtype)
    x = jnp.take(params["embed"], batch["tokens"], axis=0).astype(dt)
    n_stages = mesh.shape[axis]
    stages = stack_to_stages(stack_layer_params(params["lstm"]), n_stages,
                             virtual_stages)
    x = pipeline_apply(mesh, axis, biglstm_stage_fn(cfg), stages, x,
                       n_micro=n_micro, schedule=schedule,
                       virtual_stages=virtual_stages, batch_axes=batch_axes)
    return x @ params["head"].astype(dt)
