"""Unified model API: build step functions and input specs per architecture.

``build_model(cfg)`` returns a ``ModelApi`` whose members are pure functions —
the train loop, serving engine, and multi-pod dry-run all consume models only
through this interface.  ``input_specs`` returns ShapeDtypeStructs (no device
allocation) so ``jax.jit(...).lower(**specs)`` works for the production mesh.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

from repro import scopes
from repro.parallel.jaxcompat import shard_map

from repro.configs.base import InputShape, ModelConfig
from repro.models import inception as inc_mod
from repro.models import lstm as lstm_mod
from repro.models import transformer as tf_mod
from repro.models.transformer import ParallelCtx


@scopes.scoped(scopes.LOSS)
def masked_nll_sum(logits, labels):
    """Summed token NLL in f32 (labels < 0 masked) — the additive per-micro
    numerator of ``cross_entropy``.  The scheduled pipeline runtime sums one
    of these per finished micro-batch and scales by the global valid-token
    count, recovering the mean the AD path computes over the whole batch."""
    logits = logits.astype(jnp.float32)
    mask = labels >= 0
    labels = jnp.maximum(labels, 0)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return ((logz - gold) * mask).sum()


@scopes.scoped(scopes.LOSS)
def cross_entropy(logits, labels, n_valid_vocab: int):
    """Mean token NLL in f32; labels < 0 are masked out."""
    mask = labels >= 0
    return masked_nll_sum(logits, labels) / jnp.maximum(mask.sum(), 1)


@scopes.scoped(scopes.LOSS)
def vocab_parallel_cross_entropy(logits, labels, n_valid_vocab: int, *,
                                 mesh, model_axis: str, batch_axes=()):
    """Cross-entropy over vocab-sharded logits WITHOUT gathering them
    (§Perf iteration D, Megatron-style).  logits: (B, S, V) sharded on V over
    ``model_axis``; labels: (B, S).  The all-gather of (B,S,V) logits
    (~1 GB/chip at llama scale) is replaced by pmax/psum of (B,S) stats.
    """
    from jax.sharding import PartitionSpec as P

    v = logits.shape[-1]
    msz = mesh.shape[model_axis]
    v_loc = v // msz
    baxes = tuple(a for a in (batch_axes or ()) if a)
    bspec = baxes if baxes else None

    def local(lg, lb):
        lg = lg.astype(jnp.float32)
        i = jax.lax.axis_index(model_axis)
        lo = i * v_loc
        # the max is a numerics-only shift: stop_gradient keeps the exact
        # logsumexp gradient while avoiding pmax's missing VJP
        m = jax.lax.stop_gradient(
            jax.lax.pmax(jax.lax.stop_gradient(lg).max(-1), model_axis))
        z = jax.lax.psum(jnp.exp(lg - m[..., None]).sum(-1), model_axis)
        logz = m + jnp.log(z)
        mask = lb >= 0
        lb = jnp.maximum(lb, 0)
        lidx = jnp.clip(lb - lo, 0, v_loc - 1)
        mine = (lb >= lo) & (lb < lo + v_loc)
        gold_loc = jnp.take_along_axis(lg, lidx[..., None], axis=-1)[..., 0]
        gold = jax.lax.psum(jnp.where(mine, gold_loc, 0.0), model_axis)
        nll = (logz - gold) * mask
        num = jax.lax.psum(nll.sum(), baxes) if baxes else nll.sum()
        den = jax.lax.psum(mask.sum(), baxes) if baxes else mask.sum()
        return num / jnp.maximum(den, 1)

    return shard_map(
        local, mesh=mesh,
        in_specs=(P(bspec, None, model_axis), P(bspec, None)),
        out_specs=P())(logits, labels)


@dataclasses.dataclass
class ModelApi:
    cfg: ModelConfig
    init: Callable                    # key -> params
    loss_fn: Callable                 # (params, batch, pctx) -> (loss, metrics)
    prefill: Optional[Callable]       # (params, batch, pctx, capacity, window) -> (logits, cache)
    decode_fn: Optional[Callable]     # (params, cache, batch, pctx, window) -> (logits, cache)
    # (params, batch, mesh=, axis=, n_micro=, schedule=, virtual_stages=,
    # batch_axes=) -> (loss, metrics); set for the archs whose layer stack
    # the pipeline runtime can partition into stages.  This is the **ad**
    # runtime: jax.grad through pipeline_apply's forward scan.
    pipeline_loss_fn: Optional[Callable] = None
    # Same signature -> ((loss, metrics), grads); the **scheduled** runtime:
    # executes the full fwd+bwd WorkUnit table by hand
    # (parallel.pipeline.pipeline_value_and_grad), with the arch decomposed
    # into pure (params, x) -> y stage callables plus an embedding vjp'd
    # outside and a per-micro loss seeded at the emit tick.
    pipeline_value_and_grad_fn: Optional[Callable] = None

    def input_specs(self, shape: InputShape, *, reduced: bool = False) -> Dict[str, Any]:
        return make_input_specs(self.cfg, shape, reduced=reduced)

    def make_batch(self, key, shape: InputShape):
        """Materialized random batch matching input_specs (smoke tests)."""
        specs = self.input_specs(shape, reduced=True)
        out = {}
        for name, spec in specs.items():
            key, k = jax.random.split(key)
            out[name] = _random_like(k, spec)
        return out


def _random_like(key, spec):
    if isinstance(spec, dict):
        out = {}
        for n, s in spec.items():
            key, k = jax.random.split(key)
            out[n] = _random_like(k, s)
        return out
    if jnp.issubdtype(spec.dtype, jnp.integer):
        return jax.random.randint(key, spec.shape, 0, 64, dtype=spec.dtype)
    return (jax.random.normal(key, spec.shape) * 0.02).astype(spec.dtype)


# ---------------------------------------------------------------------------
# per-slot cache helpers (continuous-batching serve engine)
# ---------------------------------------------------------------------------

def make_slot_cache(cfg: ModelConfig, n_slots: int, capacity: int,
                    dtype=None):
    """A slotted KV cache for continuous batching: ``n_slots`` independent
    request slots over a LINEAR cache of ``capacity`` positions each, with
    per-slot write positions (``pos`` is (n_slots,), which is what routes
    ``decode_step`` into slot mode).  A sliding-window arch still gets full
    linear capacity — the window is enforced as an attention mask, so
    mid-flight requests at different absolute positions can share a batch."""
    if cfg.rwkv or cfg.family == "hybrid" or cfg.encoder_layers \
            or cfg.n_prefix_embeds:
        raise ValueError(
            f"slotted KV serving supports homogeneous KV-cache decoders; "
            f"{cfg.name} (family={cfg.family}) carries recurrent/cross-attn "
            f"state that has no per-position slot layout")
    dtype = jnp.dtype(cfg.dtype) if dtype is None else dtype
    cache = tf_mod.make_cache(cfg, n_slots, capacity, window=0, dtype=dtype)
    cache["pos"] = jnp.zeros((n_slots,), jnp.int32)
    return cache


def cache_extract_slot(cache, slot):
    """View one slot of a slotted cache as a batch-1 slot cache (``pos``
    (1,)) — the shape ``decode_step``'s slot-extend path takes for chunked
    prefill."""
    out = {"pos": jax.lax.dynamic_slice_in_dim(cache["pos"], slot, 1)}
    for k, v in cache.items():
        if k != "pos":
            out[k] = jax.lax.dynamic_slice_in_dim(v, slot, 1, axis=1)
    return out


def cache_insert_slot(cache, slot_cache, slot):
    """Write a batch-1 cache (``cache_extract_slot`` shape) back into
    ``slot`` of the slotted cache."""
    out = {"pos": jax.lax.dynamic_update_slice_in_dim(
        cache["pos"], slot_cache["pos"].reshape(1).astype(cache["pos"].dtype),
        slot, axis=0)}
    for k, v in cache.items():
        if k != "pos":
            out[k] = jax.lax.dynamic_update_slice_in_dim(
                v, slot_cache[k], slot, axis=1)
    return out


def cache_evict_slot(cache, slot):
    """Free a slot: zero its KV rows and reset its position so the slot can
    be re-admitted.  (Zeroing is not strictly required — ``pos`` gates what
    attention can see — but keeps evicted state from leaking into debug
    dumps and makes reuse tests exact.)"""
    out = {"pos": jax.lax.dynamic_update_slice_in_dim(
        cache["pos"], jnp.zeros((1,), cache["pos"].dtype), slot, axis=0)}
    for k, v in cache.items():
        if k != "pos":
            out[k] = jax.lax.dynamic_update_slice_in_dim(
                v, jnp.zeros(v.shape[:1] + (1,) + v.shape[2:], v.dtype),
                slot, axis=1)
    return out


# ---------------------------------------------------------------------------

def _decode_window(cfg, shape: InputShape) -> int:
    """Effective attention window for a decode shape: long_500k forces the
    sub-quadratic sliding-window variant on otherwise-full-attention archs
    (DESIGN.md §Arch-applicability)."""
    if cfg.rwkv:
        return 0
    if shape.seq_len > 65536:
        return cfg.sliding_window or cfg.long_context_window
    return cfg.sliding_window


def make_input_specs(cfg: ModelConfig, shape: InputShape, *, reduced: bool = False):
    """ShapeDtypeStruct stand-ins for every model input of this shape."""
    s, b = (shape.seq_len, shape.global_batch)
    if reduced:
        s, b = min(s, 128), min(b, 4)
    i32 = jnp.int32
    act = jnp.dtype(cfg.dtype)

    if cfg.family == "cnn":
        size = 128 if reduced else 299
        return {"images": jax.ShapeDtypeStruct((b, size, size, 3), act),
                "labels": jax.ShapeDtypeStruct((b,), i32)}
    if cfg.name == "gnmt":
        return {"src": jax.ShapeDtypeStruct((b, s), i32),
                "tgt": jax.ShapeDtypeStruct((b, s), i32),
                "labels": jax.ShapeDtypeStruct((b, s), i32)}
    if cfg.name == "biglstm":
        return {"tokens": jax.ShapeDtypeStruct((b, s), i32),
                "labels": jax.ShapeDtypeStruct((b, s), i32)}

    specs: Dict[str, Any] = {}
    if shape.kind == "decode":
        specs["tokens"] = jax.ShapeDtypeStruct((b, 1), i32)
        window = _decode_window(cfg, shape)
        capacity = min(shape.seq_len, window) if window else shape.seq_len
        if reduced:
            capacity = min(capacity, 64)
        cache = jax.eval_shape(
            lambda: tf_mod.make_cache(cfg, b, capacity, window=window, dtype=act))
        specs["cache"] = {k: v for k, v in cache.items()}
        if shape.kind == "decode" and cfg.encoder_layers:
            pass  # cross-attn K/V live inside the cache
        return specs

    n_text = s - (cfg.n_prefix_embeds if cfg.n_prefix_embeds else 0)
    specs["tokens"] = jax.ShapeDtypeStruct((b, max(n_text, 1)), i32)
    specs["labels"] = jax.ShapeDtypeStruct((b, max(n_text, 1)), i32)
    if cfg.n_prefix_embeds:
        npre = min(cfg.n_prefix_embeds, 8) if reduced else cfg.n_prefix_embeds
        specs["prefix"] = jax.ShapeDtypeStruct((b, npre, cfg.d_model), act)
        specs["tokens"] = jax.ShapeDtypeStruct((b, s - npre), i32)
        specs["labels"] = jax.ShapeDtypeStruct((b, s - npre), i32)
    if cfg.encoder_layers:
        specs["frames"] = jax.ShapeDtypeStruct((b, cfg.encoder_seq, cfg.d_model), act)
    return specs


# ---------------------------------------------------------------------------

def supports_pipeline(cfg: ModelConfig) -> bool:
    """Archs whose layer stack the pipeline runtime can partition: BigLSTM's
    residual LSTM stack and homogeneous decoder-only transformers.  GNMT's
    encoder/decoder split and the CNN block graph need stage functions the
    GPipe runtime does not model (the planner still *costs* pipeline-MP for
    GNMT; execution falls back to the best supported plan)."""
    if cfg.name == "biglstm":
        return True
    if cfg.family == "cnn" or cfg.name == "gnmt":
        return False
    return not (cfg.encoder_layers or cfg.n_prefix_embeds or cfg.is_moe)


def pipeline_applicable(cfg: ModelConfig, n_stages: int,
                        virtual_stages: int = 1) -> bool:
    """Can this arch run as ``n_stages`` pipeline stages (each holding
    ``virtual_stages`` interleaved layer chunks) at runtime?"""
    return (supports_pipeline(cfg) and n_stages > 1
            and cfg.n_layers % (n_stages * max(virtual_stages, 1)) == 0)


def _pipeline_vag_builder(cfg, stage_key: str, make_stage_fn: Callable,
                          pre_fn: Callable, head_fn: Callable,
                          to_stacked: Callable, from_stacked: Callable):
    """Compose an arch into the scheduled pipeline runtime's three pure
    parts — ``pre_fn(outer_params, batch) -> x`` (embedding, vjp'd outside
    the pipeline), ``stage_fn(chunk_params, x) -> y`` per WorkUnit, and
    ``head_fn(outer_params, y_micro) -> logits`` feeding the per-micro NLL
    seeded at each emit tick — returning a
    ``(params, batch, ...) -> ((loss, metrics), grads)`` train-step body.

    The per-micro loss is the summed NLL scaled by the *global* inverse
    valid-token count (data-dependent but parameter-independent, so it is
    computable before the pipeline runs); summed over micro-batches it
    recovers exactly the batch-mean cross entropy the ad path computes.
    Tied embeddings fall out naturally: the embed table's head-side
    cotangent (from ``head_fn``) and embedding-side cotangent (from
    ``pre_fn``'s vjp) are summed leaf-wise.
    """
    def pipe_vag_fn(params, batch, *, mesh, axis, n_micro, schedule="gpipe",
                    virtual_stages=1, batch_axes=()):
        from repro.parallel.pipeline import (make_schedule,
                                             pipeline_value_and_grad,
                                             stack_to_stages,
                                             stages_to_stack)
        n_stages = mesh.shape[axis]
        sched = (make_schedule(schedule, n_stages, n_micro, virtual_stages)
                 if isinstance(schedule, str) else schedule)
        outer = {k: p for k, p in params.items() if k != stage_key}
        labels = batch["labels"]
        inv_count = 1.0 / jnp.maximum((labels >= 0).sum(), 1).astype(
            jnp.float32)

        x, pre_vjp = jax.vjp(lambda op: pre_fn(op, batch), outer)

        def loss_fn(lpp, y_m, lbl_m):
            return masked_nll_sum(head_fn(lpp["outer"], y_m),
                                  lbl_m) * lpp["inv_count"]

        stages = stack_to_stages(to_stacked(params[stage_key]), n_stages,
                                 sched.v)
        loss, (stage_g, lp_g, dx) = pipeline_value_and_grad(
            mesh, axis, make_stage_fn(), stages, x, loss_fn=loss_fn,
            loss_params={"outer": outer, "inv_count": inv_count},
            targets=labels, n_micro=n_micro, batch_axes=batch_axes,
            schedule=sched)
        grads = jax.tree.map(jnp.add, lp_g["outer"], pre_vjp(dx)[0])
        grads[stage_key] = from_stacked(
            stages_to_stack(stage_g, n_stages, sched.v))
        return (loss, {"loss": loss}), grads

    return pipe_vag_fn


def build_model(cfg: ModelConfig, *, rwkv_chunked: bool = True,
                remat: bool = True, capacity_factor=1.25) -> ModelApi:
    if cfg.family == "cnn":
        reduced = cfg.n_layers <= 3

        def init(key):
            return inc_mod.inception_init(key, cfg, reduced=reduced)

        def loss_fn(params, batch, pctx=None):
            logits = inc_mod.inception_forward(cfg, params, batch, reduced=reduced)
            loss = cross_entropy(logits[:, None, :], batch["labels"][:, None],
                                 cfg.vocab_size)
            return loss, {"loss": loss}

        return ModelApi(cfg, init, loss_fn, None, None)

    if cfg.name == "gnmt":
        def init(key):
            return lstm_mod.gnmt_init(key, cfg)

        def loss_fn(params, batch, pctx=None):
            logits = lstm_mod.gnmt_forward(cfg, params, batch)
            loss = cross_entropy(logits, batch["labels"], cfg.vocab_size)
            return loss, {"loss": loss}

        return ModelApi(cfg, init, loss_fn, None, None)

    if cfg.name == "biglstm":
        def init(key):
            return lstm_mod.biglstm_init(key, cfg)

        def loss_fn(params, batch, pctx=None):
            logits = lstm_mod.biglstm_forward(cfg, params, batch, pctx=pctx)
            loss = cross_entropy(logits, batch["labels"], cfg.vocab_size)
            return loss, {"loss": loss}

        def pipe_loss_fn(params, batch, *, mesh, axis, n_micro,
                         schedule="gpipe", virtual_stages=1, batch_axes=()):
            logits = lstm_mod.biglstm_forward_pipeline(
                cfg, params, batch, mesh=mesh, axis=axis, n_micro=n_micro,
                schedule=schedule, virtual_stages=virtual_stages,
                batch_axes=batch_axes)
            loss = cross_entropy(logits, batch["labels"], cfg.vocab_size)
            return loss, {"loss": loss}

        dt = jnp.dtype(cfg.dtype)
        pipe_vag_fn = _pipeline_vag_builder(
            cfg, "lstm",
            make_stage_fn=lambda: lstm_mod.biglstm_stage_fn(cfg),
            pre_fn=lambda op, b: jnp.take(op["embed"], b["tokens"],
                                          axis=0).astype(dt),
            head_fn=lambda op, y: y @ op["head"].astype(y.dtype),
            to_stacked=lstm_mod.stack_layer_params,
            from_stacked=lambda st: [
                jax.tree.map(lambda a, i=i: a[i], st)
                for i in range(cfg.n_layers)])

        return ModelApi(cfg, init, loss_fn, None, None,
                        pipeline_loss_fn=pipe_loss_fn,
                        pipeline_value_and_grad_fn=pipe_vag_fn)

    # --- transformer families ---
    def init(key):
        return tf_mod.model_init(key, cfg)

    def loss_fn(params, batch, pctx=None):
        fwd_batch = {k: v for k, v in batch.items() if k != "labels"}
        logits, aux = tf_mod.forward(cfg, params, fwd_batch, mode="train",
                                     pctx=pctx, remat=remat,
                                     rwkv_chunked=rwkv_chunked,
                                     capacity_factor=capacity_factor)
        if (pctx is not None and pctx.mesh is not None
                and pctx.model_axis is not None
                and cfg.vocab_padded % pctx.mesh.shape[pctx.model_axis] == 0):
            loss = vocab_parallel_cross_entropy(
                logits, batch["labels"], cfg.vocab_size, mesh=pctx.mesh,
                model_axis=pctx.model_axis,
                batch_axes=tuple(a for a in pctx.batch_axes if a))
        else:
            loss = cross_entropy(logits, batch["labels"], cfg.vocab_size)
        return loss + aux, {"loss": loss, "aux": aux}

    def prefill(params, batch, pctx=None, capacity: int = 0, window=None):
        fwd_batch = {k: v for k, v in batch.items() if k != "labels"}
        logits, cache, _ = tf_mod.forward(cfg, params, fwd_batch, mode="prefill",
                                          window_override=window, pctx=pctx,
                                          remat=False, cache_capacity=capacity,
                                          capacity_factor=capacity_factor)
        return logits, cache

    def decode_fn(params, cache, batch, pctx=None, window=None):
        return tf_mod.decode_step(cfg, params, cache, batch,
                                  window_override=window, pctx=pctx)

    pipe_loss_fn = pipe_vag_fn = None
    if supports_pipeline(cfg):
        def pipe_loss_fn(params, batch, *, mesh, axis, n_micro,
                         schedule="gpipe", virtual_stages=1, batch_axes=()):
            fwd_batch = {k: v for k, v in batch.items() if k != "labels"}
            logits = tf_mod.forward_pipeline(
                cfg, params, fwd_batch, mesh=mesh, axis=axis, n_micro=n_micro,
                remat=remat, rwkv_chunked=rwkv_chunked, schedule=schedule,
                virtual_stages=virtual_stages, batch_axes=batch_axes)
            loss = cross_entropy(logits, batch["labels"], cfg.vocab_size)
            return loss, {"loss": loss}

        pipe_vag_fn = _pipeline_vag_builder(
            cfg, "layers",
            make_stage_fn=lambda: tf_mod.pipeline_stage_fn(
                cfg, remat=remat, rwkv_chunked=rwkv_chunked),
            pre_fn=lambda op, b: tf_mod._embed(cfg, op, b["tokens"]),
            head_fn=lambda op, y: tf_mod._head(cfg, op, y),
            to_stacked=lambda t: t, from_stacked=lambda t: t)

    return ModelApi(cfg, init, loss_fn, prefill, decode_fn,
                    pipeline_loss_fn=pipe_loss_fn,
                    pipeline_value_and_grad_fn=pipe_vag_fn)
