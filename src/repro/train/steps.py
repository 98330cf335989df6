"""Step builders: sharded train_step / prefill_step / serve_step factories.

``make_train_step`` builds the jit-able function plus its in/out shardings for
a (ModelApi, ParallelPlan, mesh); the launcher and the multi-pod dry-run both
call it.  Gradient accumulation implements the paper's §4.2 delayed-gradient
emulation of larger global batches: A micro-batches are processed per device
before one gradient exchange/update.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import scopes
from repro.models.api import ModelApi
from repro.models.transformer import ParallelCtx
from repro.optim.optimizers import Optimizer, apply_updates, clip_by_global_norm
from repro.parallel.plan import ParallelPlan
from repro.parallel.sharding import ShardingRules


@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=["params", "opt_state", "step"],
                   meta_fields=[])
@dataclasses.dataclass
class TrainState:
    params: Any
    opt_state: Any
    step: Any


def init_train_state(api: ModelApi, optimizer: Optimizer, key) -> TrainState:
    params = api.init(key)
    return TrainState(params=params, opt_state=optimizer.init(params),
                      step=jnp.zeros((), jnp.int32))


def eval_train_state(api: ModelApi, optimizer: Optimizer) -> TrainState:
    """Abstract TrainState (ShapeDtypeStruct leaves) — the ``like`` tree for
    ``checkpoint.restore_checkpoint`` without allocating a real init (works
    for the 1T-param configs on the CPU host)."""
    return jax.eval_shape(
        lambda k: init_train_state(api, optimizer, k), jax.random.PRNGKey(0))


def _make_pctx(mesh, plan: ParallelPlan, batch_shardable: bool,
               decode: bool = False) -> Optional[ParallelCtx]:
    if mesh is None or plan.model_axis is None:
        return None
    axes = tuple(plan.dp_axes) if batch_shardable else ()
    # 2D EP (§Perf iteration B): in decode, per-step activations are ~MBs
    # while the expert bank is ~TBs — replicate tokens across the DP axes and
    # slice the expert hidden dim over them instead of gathering weights.
    # Training keeps batch-sharded dispatch (tokens >> weights per step).
    ff_axes = tuple(plan.dp_axes) if (decode or not batch_shardable) else ()
    if plan.mp_kind == "context":
        # The model axis hosts the KV ring, not tensor-MP compute: params
        # stay replicated across it (ShardingRules), activations sequence-
        # shard inside transformer.cp_block_apply.
        return ParallelCtx(mesh=mesh, batch_axes=axes if axes else (None,),
                           model_axis=None, context_axis=plan.model_axis,
                           moe_ff_axes=ff_axes,
                           comm_runtime=plan.comm_runtime,
                           comm_chunks=plan.comm_chunks)
    return ParallelCtx(mesh=mesh, batch_axes=axes if axes else (None,),
                       model_axis=plan.model_axis, moe_ff_axes=ff_axes,
                       comm_runtime=plan.comm_runtime,
                       comm_chunks=plan.comm_chunks)


def make_train_step(api: ModelApi, optimizer: Optimizer, *, mesh=None,
                    plan: ParallelPlan = ParallelPlan(), clip_norm: float = 1.0,
                    pctx: Optional[ParallelCtx] = None,
                    bucket_bytes: Optional[float] = None):
    """Returns ``train_step(state, batch) -> (state, metrics)`` (pure fn).

    ``mp_kind="pipeline"`` plans route the forward/backward through the
    arch's pipeline runtime selected by ``plan.runtime``: **"scheduled"**
    (default) calls ``api.pipeline_value_and_grad_fn`` — the hand-scheduled
    executor of the full fwd+bwd WorkUnit table
    (``parallel.pipeline.pipeline_value_and_grad``), which realizes the
    schedule's activation residency (1f1b holds min(K, S) micro-batches);
    **"ad"** keeps ``jax.value_and_grad`` of ``api.pipeline_loss_fn`` ->
    ``pipeline_apply`` (GPipe-like memory, the differential-testing
    baseline).  ``plan.microbatches`` then counts in-flight pipeline
    micro-batches, not delayed-gradient accumulation steps, so the
    accumulation loop is off.
    """
    pipelined = (plan.is_pipeline and mesh is not None
                 and mesh.shape[plan.model_axis] > 1)
    micro = 1 if pipelined else plan.microbatches

    if pipelined:
        # dp x stages: the mesh's DP axes shard each micro-batch inside the
        # pipeline shard_map; the gradient psum over them is GSPMD's
        batch_axes = tuple(a for a in plan.dp_axes
                           if mesh.shape.get(a, 1) > 1)
        pipe_kw = dict(mesh=mesh, axis=plan.model_axis,
                       n_micro=max(plan.microbatches, 1),
                       schedule=plan.schedule,
                       virtual_stages=plan.virtual_stages,
                       batch_axes=batch_axes)
        runtime_fn = (api.pipeline_value_and_grad_fn
                      if plan.runtime == "scheduled"
                      else api.pipeline_loss_fn)
        if runtime_fn is None:
            raise ValueError(
                f"{api.cfg.name}: plan requests pipeline-MP "
                f"({plan.runtime} runtime) but the arch has no pipeline "
                f"runtime (models.api.supports_pipeline)")

        if plan.runtime == "scheduled":
            def grads_of(params, batch):
                (loss, metrics), grads = runtime_fn(params, batch, **pipe_kw)
                return loss, metrics, grads
        else:
            def grads_of(params, batch):
                (loss, metrics), grads = jax.value_and_grad(
                    lambda p, b: runtime_fn(p, b, **pipe_kw),
                    has_aux=True)(params, batch)
                return loss, metrics, grads
    else:
        def loss_fn(params, batch):
            return api.loss_fn(params, batch, pctx)

        def grads_of(params, batch):
            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, batch)
            return loss, metrics, grads

    def total_grads(params, batch):
        if micro > 1:
            # delayed gradient update (paper §4.2): split the per-step batch
            # into `micro` micro-batches, accumulate grads, update once
            def split(x):
                b = x.shape[0]
                return x.reshape(micro, b // micro, *x.shape[1:])

            mbatch = jax.tree.map(split, batch)

            def body(acc, mb):
                loss, metrics, grads = grads_of(params, mb)
                acc = jax.tree.map(jnp.add, acc, grads)
                return acc, loss

            zeros = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            grads, losses = jax.lax.scan(
                body, zeros, mbatch)
            grads = jax.tree.map(lambda g: g / micro, grads)
            loss = losses.mean()
            return loss, {"loss": loss}, grads
        return grads_of(params, batch)

    # Bucketed DP gradient sync (comm_runtime="overlapped", pure-DP plans):
    # run the whole fwd+bwd(+accumulation) per-shard inside a shard_map and
    # sync gradients bucket-by-bucket through the ZeRO-style reduce-scatter
    # + all-gather split instead of GSPMD's single fused all-reduce — per
    # bucket collectives are what the scheduler can overlap with the
    # backward compute still producing later buckets.  Tensor/pipeline-MP
    # and fsdp plans keep GSPMD's sync (their params are not replicated
    # over DP, so the replicated-params shard_map does not apply).
    dp_axes_live = tuple(a for a in plan.dp_axes
                         if mesh is not None and mesh.shape.get(a, 1) > 1)
    dp_degree = 1
    for a in dp_axes_live:
        dp_degree *= mesh.shape[a]
    bucketed_dp = (plan.comm_runtime == "overlapped" and not pipelined
                   and mesh is not None and not plan.fsdp_axes
                   and dp_degree > 1
                   and (plan.model_axis is None
                        or mesh.shape.get(plan.model_axis, 1) == 1))
    if bucketed_dp:
        from repro.parallel.collectives import (DEFAULT_BUCKET_BYTES,
                                                bucketed_grad_sync)

        gspmd_total_grads = total_grads
        dp_axis = dp_axes_live[-1]
        pod_axis = dp_axes_live[0] if len(dp_axes_live) > 1 else None
        bkt = DEFAULT_BUCKET_BYTES if bucket_bytes is None else bucket_bytes

        def total_grads(params, batch):
            # per-shard batch must split over DP and still divide into the
            # accumulation micro-batches; otherwise keep GSPMD's fused sync
            b = jax.tree.leaves(batch)[0].shape[0]
            if b % dp_degree or (micro > 1 and (b // dp_degree) % micro):
                return gspmd_total_grads(params, batch)

            def local(p, bt):
                loss, metrics, grads = gspmd_total_grads(p, bt)
                with scopes.scope(scopes.GRAD_SYNC):
                    grads = bucketed_grad_sync(grads, dp_axis=dp_axis,
                                               dp_size=mesh.shape[dp_axis],
                                               pod_axis=pod_axis,
                                               bucket_bytes=bkt)
                    grads = jax.tree.map(
                        lambda g: (g / dp_degree).astype(g.dtype), grads)
                loss = jax.lax.pmean(loss, dp_axes_live)
                metrics = {k: jax.lax.pmean(v, dp_axes_live)
                           for k, v in metrics.items()}
                return loss, metrics, grads

            from repro.parallel.jaxcompat import shard_map
            return shard_map(local, mesh=mesh,
                             in_specs=(P(), P(dp_axes_live)),
                             out_specs=(P(), P(), P()))(params, batch)

    def train_step(state: TrainState, batch):
        params = state.params
        loss, metrics, grads = total_grads(params, batch)
        with scopes.scope(scopes.OPTIM):
            if clip_norm:
                grads, gnorm = clip_by_global_norm(grads, clip_norm)
                metrics = dict(metrics, grad_norm=gnorm)
            updates, opt_state = optimizer.update(grads, state.opt_state,
                                                  params, state.step)
            params = apply_updates(params, updates)
        new_state = TrainState(params=params, opt_state=opt_state,
                               step=state.step + 1)
        return new_state, metrics

    return train_step


def shardings_for(api: ModelApi, mesh, plan: ParallelPlan, optimizer: Optimizer,
                  input_specs):
    """(state_shardings, batch_shardings) for jit in_shardings/out_shardings.

    Derives everything from shape-level eval_shape — no allocation, so this
    works for the 1T-param configs on the CPU host.
    """
    rules = ShardingRules(api.cfg, mesh, plan)
    key = jax.random.PRNGKey(0)
    params_shape = jax.eval_shape(api.init, key)
    p_spec = rules.params_specs(params_shape)
    p_shard = jax.tree.map(lambda s: NamedSharding(mesh, s), p_spec,
                           is_leaf=lambda x: isinstance(x, P))
    opt_shape = jax.eval_shape(optimizer.init, params_shape)
    # path-based wrapper-key resolution lives with the rule engine so the
    # elastic-resume path can derive full-state shardings too
    o_spec = rules.opt_specs(params_shape, opt_shape)
    o_shard = jax.tree.map(lambda s: NamedSharding(mesh, s), o_spec,
                           is_leaf=lambda x: isinstance(x, P))
    state_shardings = TrainState(params=p_shard, opt_state=o_shard,
                                 step=NamedSharding(mesh, P()))
    if "cache" in input_specs:
        cache_spec = rules.cache_specs(input_specs["cache"])
        rest = {k: v for k, v in input_specs.items() if k != "cache"}
        b_spec = rules.batch_specs(rest)
        b_spec["cache"] = cache_spec
    else:
        b_spec = rules.batch_specs(input_specs)
    b_shard = jax.tree.map(lambda s: NamedSharding(mesh, s), b_spec,
                           is_leaf=lambda x: isinstance(x, P))
    return state_shardings, b_shard


def _lookup(tree, path):
    node = tree
    for p in path:
        key = getattr(p, "key", getattr(p, "idx", None))
        if isinstance(node, dict):
            node = node[key]
        else:
            node = node[int(key)]
    return node


def make_serve_steps(api: ModelApi, *, pctx=None, window=None):
    """(prefill_step, decode_step) pure fns for the serving engine/dry-run."""

    def prefill_step(params, batch, capacity):
        return api.prefill(params, batch, pctx, capacity=capacity, window=window)

    def decode_step(params, batch):
        cache = batch["cache"]
        rest = {k: v for k, v in batch.items() if k != "cache"}
        logits, new_cache = api.decode_fn(params, cache, rest, pctx, window=window)
        return logits, new_cache

    return prefill_step, decode_step


def make_continuous_steps(api: ModelApi, *, n_slots: int,
                          temperature: float = 0.0, mesh=None,
                          model_axis: Optional[str] = None, batch_axes=(),
                          comm_chunks: int = 1, window=None,
                          context_axis: Optional[str] = None):
    """Jitted ``(decode_tick, prefill_chunk, prefill_grid)`` triple for the
    continuous-batching engine (``serve.continuous``).

    ``decode_tick(params, cache, tokens, active, keys)`` runs ONE token step
    for every slot of a slotted cache — sampling happens inside the jit, and
    ``pos`` only advances for ``active`` slots (an inactive slot's write at
    its frozen position is overwritten at its next admission).  When a mesh
    with a >1 model axis is given and the arch/slot-count divides
    (``transformer.decode_slots_tp_supported``), the tick executes
    ``decode_slots_tp`` — the whole layer stack in one shard_map on the
    chunked collective-matmul rings.  ``prefill_chunk(params, cache, tokens,
    slot)`` extends one slot by a token chunk (slot-mode decode with t > 1,
    causal within the chunk) and returns the chunk's last-position logits.

    The prefill chunk is sharded too: under the tensor-MP mesh it routes
    through ``transformer.prefill_chunk_tp`` (same collective-matmul rings
    as the decode tick, the chunk's sequence dim in the ring-row role);
    with ``context_axis`` set it routes through ``prefill_chunk_cp`` — the
    chunk sequence-sharded over the ppermute KV ring of
    ``parallel.context``.  Routing is static per chunk length (jit
    re-traces per shape).  A chunk that does not divide the ring —
    typically a prompt's final chunk — is PADDED up to ``prefill_grid``
    (ring size x comm chunks for TP, ring size for CP) and runs the SAME
    sharded path with ``n_valid`` marking the real length; there is no
    single-device fallback once the arch supports the sharded step.  The
    returned ``prefill_grid`` (1 when unsharded) lets the engine validate
    that the pad rows fit the slot capacity.
    """
    from repro.models import transformer as tf_mod

    cfg = api.cfg
    use_tp = (mesh is not None and model_axis is not None
              and tf_mod.decode_slots_tp_supported(
                  cfg, mesh, model_axis, batch_axes, n_slots,
                  max(comm_chunks, 1)))
    # sharded-prefill routing is arch/mesh-static; only the chunk length
    # varies per call, and padding makes every length divide the grid
    cp_grid = tp_grid = 0
    if mesh is not None and context_axis is not None:
        csz = mesh.shape[context_axis]
        if tf_mod.prefill_chunk_cp_supported(cfg, mesh, context_axis, csz):
            cp_grid = csz
    if not cp_grid and mesh is not None and model_axis is not None:
        msz = mesh.shape[model_axis]
        g = msz * max(comm_chunks, 1)
        if tf_mod.prefill_chunk_tp_supported(cfg, mesh, model_axis, g,
                                             max(comm_chunks, 1)):
            tp_grid = g

    def _sample(last, keys):
        last = last.astype(jnp.float32)
        if temperature <= 0.0:
            nxt = last.argmax(-1).astype(jnp.int32)
        else:
            nxt = jax.vmap(
                lambda lg, k: jax.random.categorical(k, lg / temperature)
            )(last, keys).astype(jnp.int32)
        lp = jnp.take_along_axis(jax.nn.log_softmax(last, axis=-1),
                                 nxt[:, None], axis=-1)[:, 0]
        return nxt, lp

    def decode_tick(params, cache, tokens, active, keys):
        if use_tp:
            logits, new_cache = tf_mod.decode_slots_tp(
                cfg, params, cache, {"tokens": tokens[:, None]}, mesh=mesh,
                model_axis=model_axis, batch_axes=batch_axes,
                comm_chunks=comm_chunks, window_override=window)
        else:
            logits, new_cache = api.decode_fn(params, cache,
                                              {"tokens": tokens[:, None]},
                                              None, window)
        nxt, lp = _sample(logits[:, -1], keys)
        new_cache["pos"] = jnp.where(active, cache["pos"] + 1, cache["pos"])
        return new_cache, nxt, lp

    def prefill_chunk(params, cache, tokens, slot):
        from repro.models.api import cache_extract_slot, cache_insert_slot
        sl = cache_extract_slot(cache, slot)
        t = tokens.shape[1]          # static per trace: routing is per-shape
        if cp_grid or tp_grid:
            grid = cp_grid or tp_grid
            t_pad = -(-t // grid) * grid
            toks = (tokens if t_pad == t else
                    jnp.pad(tokens, ((0, 0), (0, t_pad - t))))
            nv = t if t_pad != t else None
            if cp_grid:
                logits, sl = tf_mod.prefill_chunk_cp(
                    cfg, params, sl, {"tokens": toks}, mesh=mesh,
                    context_axis=context_axis, window_override=window,
                    n_valid=nv)
            else:
                logits, sl = tf_mod.prefill_chunk_tp(
                    cfg, params, sl, {"tokens": toks}, mesh=mesh,
                    model_axis=model_axis, comm_chunks=comm_chunks,
                    window_override=window, n_valid=nv)
        else:
            logits, sl = api.decode_fn(params, sl, {"tokens": tokens}, None,
                                       window)
        return cache_insert_slot(cache, sl, slot), logits[:, -1]

    return (jax.jit(decode_tick, donate_argnums=(1,)),
            jax.jit(prefill_chunk, donate_argnums=(1,)),
            cp_grid or tp_grid or 1)
