"""Training cells: the program's jitted train step, built as the training
launcher (``repro.launch.train``) builds it for the mix's ``--parallel``
spec, driven through a measured window.

Set-up makes the weights from the seed on the device, builds the train
state, and drives the step through the mix's checked steps; those steps
compile it and are read for the comparison with the reference.  The same
step and state then run the window: each step's loss is fetched, as the
training loop does, and the window ends at the first step that completes
``seconds`` after it began.  After the window the state is freed and the
reference trains from the same weights on the same batches."""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import math
import shutil
import tempfile
import time

from bench import checks, trace, weights
from bench.common import seed_key

WINDOW, STEP, FEED, FETCH = ("bench.window", "bench.step", "bench.feed",
                             "bench.fetch")
FAULTS = ("unchanged", "half_batch", "no_exchange")


@dataclasses.dataclass
class Program:
    step: object          # jitted (state, batch) -> (state, metrics)
    init: object          # jitted seed key -> TrainState
    grad_norms: object    # jitted AdamW first moment -> first-gradient norms
    change: object        # jitted (params, key) -> norms of the change
    put: object           # host batch -> device batch
    make_params: object   # seed key -> the weights the reference also takes
    mesh: object
    chips: int
    pipeline: bool        # the layers run as pipeline stages


def span(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


@contextlib.contextmanager
def exchange_left_out():
    """Every ``ppermute`` traced inside delivers zeros: the pipeline's
    exchange between stages, forward and backward, left out (a fault)."""
    import jax
    import jax.numpy as jnp

    real = jax.lax.ppermute
    jax.lax.ppermute = lambda x, *a, **k: jax.tree.map(jnp.zeros_like, x)
    try:
        yield
    finally:
        jax.lax.ppermute = real


def build(mc: dict, mix: dict, reference, fault: str = "") -> Program:
    """The program's train step for the configuration ``mc`` and the mix;
    ``reference`` is the module that ``mc`` names, which knows its family."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.launch.mesh import make_host_mesh, make_mesh
    from repro.launch.train import parse_parallel
    from repro.models.api import build_model
    from repro.optim import adamw, warmup_cosine
    from repro.train.steps import TrainState, make_train_step

    cfg = reference.program_config(mc)
    api = build_model(cfg)
    shapes = weights.flatten(jax.eval_shape(api.init, jax.random.PRNGKey(0)))
    want = weights.flatten(reference.param_shapes(mc))
    got = {k: tuple(v.shape) for k, v in shapes.items()}
    if got != {k: tuple(v) for k, v in want.items()}:
        raise ValueError(f"the program's parameters {got} differ from the "
                         f"reference's layout {want}")
    o = mix["optimizer"]
    opt = adamw(warmup_cosine(o["lr"], o["warmup_steps"], o["total_steps"]),
                b1=o["b1"], b2=o["b2"], eps=o["eps"],
                weight_decay=o["weight_decay"])
    plan, mp, dp = parse_parallel(mix["parallel"], 0, cfg)
    pipeline = plan.is_pipeline and mp > 1
    if mp > 1 and not pipeline:
        raise ValueError("tensor and context MP layouts are not driven yet")
    spmd = not pipeline and dp > 1
    batch = mix["batch"]
    if pipeline or spmd:
        if jax.device_count() < dp * mp:
            raise SystemExit(f"[bench] the mix needs {dp * mp} devices, "
                             f"JAX has {jax.device_count()}")
        mesh = make_mesh(dp=dp, mp=mp)
    else:
        mesh = make_host_mesh()
    plan = dataclasses.replace(plan, dp_axes=("data",), fsdp_axes=())
    if pipeline:
        shard_b = batch // dp
        micro = max(k for k in range(1, min(plan.microbatches, shard_b) + 1)
                    if shard_b % k == 0)
        if micro != plan.microbatches:
            raise ValueError(f"{plan.microbatches} micro-batches do not "
                             f"divide the {shard_b} rows of a DP shard")
    step = make_train_step(api, opt, mesh=mesh, plan=plan,
                           clip_norm=o["clip_norm"])
    if fault == "unchanged":
        inner = step

        def step(state, b):
            return state, inner(state, b)[1]
    elif fault == "half_batch":
        inner = step

        def step(state, b):
            return inner(state, jax.tree.map(
                lambda x: x[: x.shape[0] // 2], b))
    elif fault == "no_exchange":
        inner = step

        def step(state, b):
            with exchange_left_out():
                return inner(state, b)
    elif fault:
        raise ValueError(f"unknown fault {fault!r}; known: {FAULTS}")

    make_params = weights.make_params_fn(reference.param_shapes(mc),
                                         reference.NORMS)

    def init(key):
        params = make_params(key)
        return TrainState(params=params, opt_state=opt.init(params),
                          step=jnp.zeros((), jnp.int32))

    state_sh = batch_sh = None
    if pipeline or spmd:
        # launch/train.py's dp x stages layout (and, with one stage, plain
        # DP): params and optimizer state replicated, the batch sharded over
        # the data axis, the layout kept across steps
        state_sh = jax.tree.map(lambda _: NamedSharding(mesh, P()),
                                jax.eval_shape(init, jax.random.PRNGKey(0)))
        batch_sh = {k: NamedSharding(mesh, P("data", None))
                    for k in ("tokens", "labels")}
        jstep = jax.jit(step, donate_argnums=(0,),
                        in_shardings=(state_sh, batch_sh),
                        out_shardings=(state_sh, None))
    else:
        jstep = jax.jit(step, donate_argnums=(0,))
    b1 = o["b1"]
    return Program(
        step=jstep,
        init=jax.jit(init, out_shardings=state_sh),
        grad_norms=jax.jit(lambda m: checks.leaf_norms(
            jax.tree.map(lambda x: x / (1 - b1), m))),
        change=checks.change_norms(make_params),
        put=lambda b: jax.device_put(b, batch_sh),
        make_params=make_params, mesh=mesh, chips=mesh.devices.size,
        pipeline=pipeline)


def checked_steps(prog: Program, state, source, n: int, key) -> tuple:
    """Drive the step through the first ``n`` batches and read it."""
    losses, grad = [], None
    for i in range(n):
        state, metrics = prog.step(state, prog.put(source.batch(i)))
        losses.append(float(metrics["loss"]))
        if i == 0:
            grad = checks.expand(prog.grad_norms(state.opt_state["m"]))
    change = checks.expand(prog.change(state.params, key))
    return state, {"losses": losses, "grad": grad, "change": change}


def memory_peak(devices) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def free(tree) -> None:
    import jax
    for x in jax.tree.leaves(tree):
        if hasattr(x, "delete") and not x.is_deleted():
            x.delete()


def run(ctx) -> dict:
    import jax

    mc, mix = ctx.config, ctx.mix
    ref = ctx.bench.reference(mc["reference"])
    prog = build(mc, mix, ref, ctx.fault)
    source = ctx.bench.generator(mix["kind"]).Source(mix, mc["vocab_size"],
                                                     ctx.seed)
    key = seed_key(ctx.seed)
    n_checked = mix["checked_steps"]
    tokens_per_step = mix["batch"] * mix["seq"]

    with jax.set_mesh(prog.mesh):
        state = prog.init(key)
        state, prog_readings = checked_steps(prog, state, source, n_checked,
                                             key)
        compiled = prog.step._cache_size()
        logdir = tempfile.mkdtemp(prefix="bench_trace_") if ctx.trace else None
        # objects made in set-up are never garbage, so no collection in the
        # window walks them
        gc.collect()
        gc.freeze()
        n = failed = 0
        ends = []
        nxt = prog.put(source.batch(n_checked))
        with (trace.capture(logdir) if logdir else contextlib.nullcontext()):
            t_start = time.perf_counter()
            with span(WINDOW):
                while True:
                    with span(STEP):
                        state, metrics = prog.step(state, nxt)
                    with span(FEED):
                        nxt = prog.put(source.batch(n_checked + n + 1))
                    with span(FETCH):
                        loss = float(metrics["loss"])
                    n += 1
                    failed += not math.isfinite(loss)
                    t_end = time.perf_counter()
                    ends.append(t_end)
                    if t_end - t_start >= ctx.seconds:
                        break
        gc.unfreeze()
        compiled_in_window = prog.step._cache_size() - compiled
    peak = memory_peak(prog.mesh.devices.flat)
    free((state, nxt))
    reduced = None
    if logdir:
        reduced = trace.reduce(trace.load(logdir))
        shutil.rmtree(logdir, ignore_errors=True)

    batches = [source.batch(i) for i in range(n_checked)]
    ref_readings = checks.Reference(
        ref, mc, mix["optimizer"], ref.Numerics("float32"), prog.make_params,
        mix.get("reference_block_rows")).readings(
            key, [(b["tokens"], b["labels"]) for b in batches])
    window_s = t_end - t_start
    return {
        "driver": "train",
        "end_to_end": {"train_tokens_per_s": n * tokens_per_step / window_s,
                       "setup_s": t_start - ctx.t_process},
        "tokens_per_s": n * tokens_per_step / window_s,
        "flops_per_token": ref.train_flops_per_token(mc, mix["seq"]),
        "chips": prog.chips,
        "attempted": n,
        "failed": failed,
        "compiled_in_window": compiled_in_window,
        "step_s": [b - a for a, b in zip([t_start] + ends, ends)],
        "memory_peak_bytes": peak,
        "trace": reduced,
        "numbers": checks.compare(prog_readings, ref_readings),
        "readings": {"program": prog_readings, "reference": ref_readings},
    }
