"""Training launcher.

    python -m repro.launch.train --arch llama3_2_1b --steps 200 \
        --parallel auto --devices 256
    python -m repro.launch.train --arch biglstm --parallel auto --reduced
    python -m repro.launch.train --arch smollm_360m --parallel dp=2,mp=2 \
        --reduced --steps 100
    python -m repro.launch.train --arch biglstm --parallel pipe=2,micro=4 \
        --reduced
    python -m repro.launch.train --arch llama3_2_1b --parallel dp=2,mp=2 \
        --reduced --comm-runtime overlapped --comm-chunks 2
    python -m repro.launch.train --arch llama3_2_1b --parallel dp=2,cp=4 \
        --reduced --seq 64          # context parallelism: ppermute KV ring

``--parallel auto`` invokes the paper's HybridPlanner — the unified search
over DP x tensor-MP x pipeline-MP x schedule factorizations of the device
budget (``--devices``, default 256) — and *executes* the winning plan:
pipeline plans run through ``parallel.pipeline.pipeline_apply`` on a
**dp x stages mesh** — the model axis carries the stages, the data axis
carries as much of the projected DP degree as the local machine affords
(capped by ``--max-local-devices``, default 8), with the batch
sharded over it and the gradient all-reduce inserted by GSPMD.  On CPU the
launcher forces dp*stages host devices before jax initializes; on an
accelerator the mesh takes that many of its chips.  Explicit
``dp=/mp=/accum=``, ``pipe=/micro=/sched=/v=/dp=``, or ``dp=/cp=`` specs
override the search (``cp=`` = context parallelism: the model axis carries
the sequence-sharded ppermute KV ring of ``parallel.context`` with params
replicated across it; ``--context-parallel`` restricts ``auto`` to those
points).  ``--reduced`` shrinks the arch (2 layers, small dims) for the CPU
container.

Tensor-MP and multi-DP plans likewise execute on a real local dp x mp mesh
(chips, or forced host devices on CPU); ``--comm-runtime overlapped``
selects the overlap-scheduled collective runtime (``parallel.collectives``:
chunked collective-matmul rings for the Megatron matmuls, bucketed
reduce-scatter DP grad sync), ``gspmd`` being the monolithic-collective
escape hatch.

Fault tolerance: ``--ckpt-dir``/``--ckpt-every`` write CRC-manifested
checkpoints (``--keep-last`` retention, ``--background-save`` off the step
path) with a guaranteed final checkpoint; ``--resume`` restores the newest
*valid* one — re-sharded onto the current mesh, so a 16-way-DP run resumes
on 8 or 32 devices — and continues with exact data order.  ``--fault``
injects a deterministic failure schedule (``train.fault``), ``--max-retries``
bounds in-place step retries, ``--max-restarts`` runs the whole loop under
the checkpoint-restoring supervisor, ``--watchdog`` flags hung steps:

    python -m repro.launch.train --arch llama3_2_1b --reduced --steps 30 \\
        --ckpt-dir /tmp/ck --ckpt-every 10 --fault "kill@25"   # preempted
    python -m repro.launch.train --arch llama3_2_1b --reduced --steps 30 \\
        --ckpt-dir /tmp/ck --resume                            # recovers
"""
from __future__ import annotations

import argparse
import dataclasses
import os

from repro.configs import INPUT_SHAPES, get_config
from repro.core.planner import HybridPlanner, default_epoch_model
from repro.parallel.plan import ParallelPlan


def parse_parallel(spec: str, devices: int, cfg, comm_runtime: str = "gspmd",
                   context_parallel: bool = False):
    """Resolve a --parallel spec to (plan, mp_degree, dp_hint).

    ``dp_hint`` is the projected DP degree the launcher should realize (the
    planner's pods*dp, or an explicit ``dp=`` key); the executable mesh
    clamps it to the local machine.  Pure planning — no jax device access,
    so the launcher can still force host devices afterwards for pipeline
    execution.  ``comm_runtime`` keys the auto search's overlap terms (the
    planner stamps each point with the runtime that will actually carry it).
    ``context_parallel`` restricts the auto search to context-parallel
    points (sequence-sharded KV rings) and reinterprets an explicit ``mp=``
    degree as the ring size; ``cp=N`` in the spec selects it directly.
    """
    from repro.models.api import supports_pipeline

    if spec == "auto":
        planner = HybridPlanner(cfg, epoch_model=default_epoch_model(cfg),
                                comm_runtime=comm_runtime)
        choices = planner.choices(devices)
        if context_parallel:
            choices = [c for c in choices if c.mp_kind == "context"]
            if not choices:
                raise SystemExit(
                    f"[planner] no memory-feasible context-parallel strategy "
                    f"for {cfg.name} at {devices} devices (needs the dense "
                    f"decoder CP path and a ring that divides the sequence)")
        if not choices:
            raise SystemExit(f"[planner] no memory-feasible strategy for "
                             f"{cfg.name} at {devices} devices")
        choice = next((c for c in choices if c.mp_kind != "pipeline"
                       or supports_pipeline(cfg)), None)
        if choice is None:
            choice = choices[0]
        if choice is not choices[0]:
            print(f"[planner] best plan ({choices[0].mp_kind}) lacks runtime "
                  f"support for {cfg.name}; using next feasible choice")
        print(f"[planner] {choice.mesh_shape} kind={choice.mp_kind} "
              f"sched={choice.schedule} micro={choice.microbatches} "
              f"SU={choice.speedup:.1f} "
              f"(SU^M={choice.su_m:.2f}, SE_N={choice.se_n:.3f}, "
              f"E1/EN={choice.epochs_ratio:.3f}, "
              f"mem={choice.mem_bytes / 2**30:.2f} GiB)")
        return choice.plan, choice.mp, choice.pods * choice.dp
    kv = dict(p.split("=") for p in spec.split(","))
    pipe = int(kv.get("pipe", 0))
    cp = int(kv.get("cp", 0))
    if context_parallel and cp <= 1:
        cp = int(kv.pop("mp", 0))         # --context-parallel: mp= is the ring
    if cp > 1:
        if pipe > 1 or int(kv.get("mp", 1)) > 1:
            raise SystemExit(
                "[plan] cp= is its own model axis: it cannot combine with "
                "mp= (tensor) or pipe= (pipeline) in one spec")
        plan = ParallelPlan(dp_axes=("data",), model_axis="model",
                            mp_kind="context",
                            microbatches=int(kv.get("accum", 1)))
        return plan, cp, int(kv.get("dp", 1))
    if pipe > 1:
        sched = kv.get("sched", "gpipe")
        v = int(kv.get("v", 2 if sched == "interleaved" else 1))
        if (sched == "interleaved") != (v > 1):
            raise SystemExit(
                f"[plan] sched={sched} incompatible with v={v} "
                f"(interleaved needs v>=2; gpipe/1f1b take v=1)")
        plan = ParallelPlan(dp_axes=("data",), model_axis="model",
                            mp_kind="pipeline",
                            microbatches=int(kv.get("micro", 4)),
                            schedule=sched, virtual_stages=v)
        return plan, pipe, int(kv.get("dp", 1))
    mp = int(kv.get("mp", 1))
    plan = ParallelPlan(dp_axes=("data",),
                        model_axis="model" if mp > 1 else None,
                        microbatches=int(kv.get("accum", 1)))
    return plan, mp, int(kv.get("dp", 1))


def _ensure_host_devices(n: int):
    """Force ``n`` CPU host devices — must run before jax initializes its
    backend (which is why main() defers every jax call until after the plan
    is known).  It only shapes the CPU backend: on an accelerator the mesh
    takes its devices from the chips."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "--xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n}").strip()


def main(argv=None) -> dict:
    """Run the training CLI on ``argv`` (default ``sys.argv[1:]``) and return
    the loop's summary: ``steps``, per-step losses (``history``) and host
    seconds (``step_s``), ``final_loss``, ``wall_s``, the fault counters and
    the mesh's device ids (``devices``) — without the train state, so its
    device buffers are freed when this returns."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--parallel", default="",
                    help="'auto', 'dp=2,mp=2', 'pipe=2,micro=4', "
                         "'dp=2,cp=4', ... (default: dp=1,mp=1 — except "
                         "with --resume, where an empty spec re-runs the "
                         "planner for the CURRENT device count: an elastic "
                         "grow/shrink resume must not need the old spec "
                         "replayed)")
    ap.add_argument("--devices", type=int, default=0,
                    help="planner device budget for --parallel auto "
                         "(default: 256, the single-pod production budget)")
    ap.add_argument("--reduced", action="store_true",
                    help="2-layer small config (CPU)")
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=100,
                    help="checkpoint cadence in steps (with --ckpt-dir); a "
                         "final checkpoint at loop exit is guaranteed either "
                         "way")
    ap.add_argument("--keep-last", type=int, default=0,
                    help="retain only the N newest checkpoints (0 = all)")
    ap.add_argument("--background-save", action="store_true",
                    help="serialize + write checkpoints on a worker thread, "
                         "off the step critical path")
    ap.add_argument("--resume", action="store_true",
                    help="restore the newest VALID checkpoint under "
                         "--ckpt-dir (corrupt files are skipped with a "
                         "warning) and continue with exact data order; the "
                         "checkpoint re-shards onto the current mesh, so a "
                         "run saved at one DP degree resumes on another "
                         "(elastic grow/shrink)")
    ap.add_argument("--max-retries", type=int, default=0,
                    help="bounded in-place retries per failed step "
                         "(exponential backoff)")
    ap.add_argument("--max-restarts", type=int, default=0,
                    help="> 0: run under the fault supervisor — a crashed "
                         "attempt restarts from the newest valid checkpoint "
                         "up to N times")
    ap.add_argument("--watchdog", type=float, default=0.0,
                    help="> 0: flag (log + count) steps exceeding this many "
                         "seconds")
    ap.add_argument("--fault", default="",
                    help="deterministic fault-injection schedule, e.g. "
                         "'fail@5x2,kill@7,corrupt@10:bitflip,stall@3:0.4' "
                         "(see repro.train.fault)")
    ap.add_argument("--max-local-devices", type=int, default=8,
                    help="cap on the local devices a dp x mp or dp x "
                         "stages mesh takes (chips, or forced host devices "
                         "on CPU); the realized DP degree is clamped to it")
    ap.add_argument("--pipe-runtime", choices=["scheduled", "ad"],
                    default=None,
                    help="pipeline runtime escape hatch: 'scheduled' "
                         "(default) hand-executes the full fwd+bwd WorkUnit "
                         "table and realizes the schedule's activation "
                         "residency; 'ad' keeps jax.grad through the "
                         "forward scan (GPipe-like memory) for bit-for-bit "
                         "differential testing")
    ap.add_argument("--comm-runtime", choices=["gspmd", "overlapped"],
                    default=None,
                    help="collective runtime for tensor-MP matmuls and the "
                         "DP gradient sync: 'overlapped' routes the Megatron "
                         "row/column matmuls through the chunked "
                         "collective-matmul ppermute rings and the grad "
                         "exchange through the bucketed reduce-scatter sync "
                         "(parallel.collectives); 'gspmd' (default) leaves "
                         "both to the partitioner's monolithic collectives")
    ap.add_argument("--comm-chunks", type=int, default=None,
                    help="ring chunks per shard for --comm-runtime "
                         "overlapped (default 1; more chunks = finer "
                         "overlap, more per-hop latency)")
    ap.add_argument("--context-parallel", action="store_true",
                    help="context parallelism: shard the SEQUENCE axis over "
                         "the model axis and run attention as a ppermute KV "
                         "ring (parallel.context); with --parallel auto "
                         "restricts the search to context plans, with an "
                         "explicit spec reinterprets mp= as the ring size "
                         "(or use --parallel dp=2,cp=4 directly)")
    args = ap.parse_args(argv)

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if cfg.family == "cnn":
        # the CLI feeds the token-LM pipeline; a cnn arch would yield zero
        # batches per epoch and spin forever
        raise SystemExit(f"[data] {cfg.name}: the train CLI drives the "
                         f"token-LM data pipeline; cnn archs train through "
                         f"benchmarks/fig4_epochs.py")
    if args.resume and not args.ckpt_dir:
        raise SystemExit("[resume] --resume needs --ckpt-dir")
    spec = args.parallel
    budget = args.devices or 256
    if spec == "auto" and args.resume:
        # elastic resume replan: the checkpoint stores global leaves and
        # re-shards onto whatever mesh this process has, so the PLAN comes
        # from the planner at the CURRENT local device budget — the old
        # run's --parallel spec never needs replaying after a grow/shrink
        budget = args.devices or args.max_local_devices
        print(f"[plan] --parallel auto with --resume: re-running the "
              f"planner for the current {budget}-device budget")
    if not spec:
        # a bare --resume keeps the same default plan a fresh run gets:
        # same-topology kill/resume must stay bit-reproducible (pinned in
        # tests/test_fault.py) — elastic replanning is an explicit opt-in
        # via --parallel auto
        spec = "dp=1,mp=1"
    plan, mp, dp_hint = parse_parallel(spec, budget, cfg,
                                       comm_runtime=args.comm_runtime
                                       or "gspmd",
                                       context_parallel=args.context_parallel)
    if plan.mp_kind == "context" and mp > 1:
        if args.seq % mp:
            raise SystemExit(
                f"[plan] context parallelism shards the sequence: --seq "
                f"({args.seq}) must divide by the {mp}-way ring")
        if args.comm_runtime == "overlapped" or args.comm_chunks:
            raise SystemExit(
                "[plan] --comm-runtime/--comm-chunks do not apply to "
                "context-parallel plans (the KV ring IS the comm schedule)")
    if args.pipe_runtime:
        if not plan.is_pipeline:
            raise SystemExit("[plan] --pipe-runtime only applies to pipeline "
                             "plans (--parallel pipe=... or a planner choice "
                             "with kind=pipeline)")
        plan = dataclasses.replace(plan, runtime=args.pipe_runtime)
    if args.comm_runtime or args.comm_chunks:
        if args.comm_chunks and (args.comm_runtime or plan.comm_runtime) \
                != "overlapped":
            raise SystemExit("[plan] --comm-chunks only applies with "
                             "--comm-runtime overlapped")
        if plan.is_pipeline and mp > 1:
            if spec != "auto":
                raise SystemExit(
                    "[plan] --comm-runtime/--comm-chunks apply to tensor-MP "
                    "/ DP plans; pipeline stages exchange activations over "
                    "their own ppermute rings (see --pipe-runtime)")
            # planner chose pipeline: the collective runtime is inert there
            print("[plan] note: planner chose a pipeline plan; "
                  "--comm-runtime/--comm-chunks do not apply to it")
        else:
            # auto plans already carry the planner's per-point runtime stamp
            # (gspmd for archs the overlapped runtime cannot execute)
            plan = dataclasses.replace(
                plan,
                comm_runtime=(plan.comm_runtime if spec == "auto"
                              else (args.comm_runtime or plan.comm_runtime)),
                comm_chunks=args.comm_chunks or plan.comm_chunks)

    # Pipeline plans need a real mesh axis with one device per stage plus as
    # much of the projected DP degree as fits locally; size the executable
    # dp x stages mesh to the local machine, then (on CPU) force that many
    # host devices BEFORE any jax backend init below.  Tensor-MP / multi-DP
    # plans likewise get a real local dp x mp mesh (capped by
    # --max-local-devices) so the collective runtime selected by
    # --comm-runtime actually executes.
    pipeline = plan.is_pipeline and mp > 1
    spmd = (not pipeline) and (mp > 1 or dp_hint > 1)
    dp = 1

    def clamp_dp(what: str) -> int:
        """Realize as much of the projected DP degree as the local budget
        affords; dp must divide the batch (it is sharded over "data")."""
        dp_cap = min(max(dp_hint, 1), max(1, args.max_local_devices // mp))
        got = max(d for d in range(1, dp_cap + 1) if args.batch % d == 0)
        if got < dp_hint:
            print(f"[plan] clamped DP {dp_hint} -> {got} "
                  f"(local budget {args.max_local_devices}, {what})")
        return got

    if spmd:
        dp = clamp_dp(f"{mp}-way MP")
        _ensure_host_devices(dp * mp)
    if pipeline:
        from repro.models.api import pipeline_applicable
        if not pipeline_applicable(cfg, mp, plan.virtual_stages):
            raise SystemExit(
                f"[plan] {cfg.name}: {mp} pipeline stages (x{max(plan.virtual_stages, 1)} "
                f"chunks) need a supported arch with n_layers % (stages*v) "
                f"== 0 (n_layers={cfg.n_layers})")
        dp = clamp_dp(f"{mp} stages")
        # the planner models micro-batches against its reference batch; the
        # executed run must use a count that divides the per-dp-shard batch
        shard_b = args.batch // dp
        micro = max(k for k in range(1, min(plan.microbatches, shard_b) + 1)
                    if shard_b % k == 0)
        if micro != plan.microbatches:
            print(f"[plan] clamped micro-batches {plan.microbatches} -> "
                  f"{micro} (batch={args.batch}, dp={dp})")
            plan = dataclasses.replace(plan, microbatches=micro)
        _ensure_host_devices(dp * mp)

    import jax
    import numpy as np

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    dev = jax.devices()[0]
    print(f"[device] {dev.platform} {dev.device_kind} x{jax.device_count()}")

    from repro.data import DataPipeline, make_lm_dataset
    from repro.launch.mesh import make_host_mesh, make_mesh
    from repro.models.api import build_model
    from repro.optim import adamw, warmup_cosine
    from repro.train.loop import LoopConfig, train_loop
    from repro.train.steps import (_make_pctx, eval_train_state,
                                   init_train_state, make_train_step,
                                   shardings_for)

    if pipeline or spmd:
        if jax.device_count() < dp * mp:
            raise SystemExit(f"[mesh] plan needs {dp * mp} devices, "
                             f"have {jax.device_count()} "
                             f"(jax initialized early?)")
        mesh = make_mesh(dp=dp, mp=mp)
        # DP narrows to the local mesh's data axis: drop pod axes / fsdp
        # from the projected plan, keep stages + schedule + micro-batches
        plan = dataclasses.replace(plan, dp_axes=("data",), fsdp_axes=())
    else:
        mesh = make_host_mesh()
        plan = dataclasses.replace(plan, dp_axes=("data",), fsdp_axes=())
    print(f"[plan] {plan.describe(mesh)}")

    api = build_model(cfg)
    data = make_lm_dataset(vocab=min(cfg.vocab_size, 64), seq_len=args.seq)
    print(f"[data] markov-lm entropy floor = {data.entropy:.4f} nats/token")

    opt = adamw(warmup_cosine(args.lr, 20, args.steps))
    pctx = _make_pctx(mesh, plan, batch_shardable=dp > 1) if spmd else None
    train_step = make_train_step(api, opt, mesh=mesh, plan=plan, pctx=pctx)
    state = init_train_state(api, opt, jax.random.PRNGKey(0))
    state_sh = None
    if pipeline and dp > 1:
        # dp x stages: batch sharded over the data axis, params/opt
        # replicated — GSPMD inserts the gradient all-reduce over "data"
        from jax.sharding import NamedSharding, PartitionSpec as P
        state_sh = jax.tree.map(lambda _: NamedSharding(mesh, P()), state)
        batch_sh = {"tokens": NamedSharding(mesh, P("data", None)),
                    "labels": NamedSharding(mesh, P("data", None))}
        # the state keeps its layout across steps: left to GSPMD, stage-
        # stacked outputs come back sharded over the stage axis and the next
        # call's in_shardings reject them
        train_step = jax.jit(train_step, donate_argnums=(0,),
                             in_shardings=(state_sh, batch_sh),
                             out_shardings=(state_sh, None))
    elif spmd:
        # tensor-MP / multi-DP: params via ShardingRules (Megatron
        # column/row specs on the model axis), batch over the data axis;
        # the comm runtime selected on the plan decides whether GSPMD or
        # parallel.collectives carries the resulting collectives
        i32 = jax.numpy.int32
        specs = {"tokens": jax.ShapeDtypeStruct((args.batch, args.seq), i32),
                 "labels": jax.ShapeDtypeStruct((args.batch, args.seq), i32)}
        state_sh, batch_sh = shardings_for(api, mesh, plan, opt, specs)
        train_step = jax.jit(train_step, donate_argnums=(0,),
                             in_shardings=(state_sh, batch_sh),
                             out_shardings=(state_sh, None))
    else:
        train_step = jax.jit(train_step, donate_argnums=(0,))

    def epoch_fn(e):
        def gen():
            for b in data.epoch(e, args.batch):
                yield {"tokens": b["tokens"].astype(np.int32),
                       "labels": b["labels"].astype(np.int32)}
        return gen()

    pipeline_data = DataPipeline(
        epoch_fn, steps_per_epoch=data.steps_per_epoch(args.batch))
    loop_cfg = LoopConfig(total_steps=args.steps,
                          ckpt_every=args.ckpt_every if args.ckpt_dir else 0,
                          ckpt_dir=args.ckpt_dir,
                          keep_last=args.keep_last,
                          background_save=args.background_save,
                          max_retries=args.max_retries,
                          watchdog_timeout_s=args.watchdog)

    # fault-injection harness: wraps the (jitted) step; the on_checkpoint
    # hook lets the schedule corrupt just-written checkpoints
    on_ckpt = None
    if args.fault:
        from repro.train.fault import FaultInjector, parse_fault_schedule
        injector = FaultInjector(parse_fault_schedule(args.fault))
        train_step = injector.wrap_step(train_step)
        on_ckpt = injector.after_save

    # elastic resume: the checkpoint stores global (unsharded) leaves, so
    # device_put against the CURRENT mesh's shardings re-shards a run saved
    # at any DP degree onto this one
    if args.resume:
        from repro.checkpoint import restore_latest_valid
        restored, fname = restore_latest_valid(
            args.ckpt_dir, eval_train_state(api, opt), state_sh)
        if restored is not None:
            state = restored
            print(f"[resume] restored {os.path.basename(fname)} at step "
                  f"{int(jax.device_get(state.step))} onto {dp}-way DP "
                  f"x {mp}-way MP")
        else:
            print("[resume] no valid checkpoint found; starting fresh")

    with jax.set_mesh(mesh):
        if args.max_restarts > 0:
            from repro.train.fault import run_supervised
            summary = run_supervised(
                train_step, pipeline_data, loop_cfg,
                init_fn=lambda: init_train_state(api, opt,
                                                 jax.random.PRNGKey(0)),
                like=eval_train_state(api, opt), shardings=state_sh,
                max_restarts=args.max_restarts, on_checkpoint=on_ckpt)
        else:
            summary = train_loop(train_step, state, pipeline_data, loop_cfg,
                                 on_checkpoint=on_ckpt)
    flags = "".join(
        f" {k}={summary[k]}" for k in ("retries", "hangs", "restarts")
        if summary.get(k))
    print(f"[done] steps={summary['steps']} final_loss="
          f"{summary['final_loss']:.4f} wall={summary['wall_s']:.1f}s "
          f"(floor {data.entropy:.4f}){flags}")
    summary.pop("state", None)
    summary["devices"] = [d.id for d in mesh.devices.flat]
    return summary


if __name__ == "__main__":
    main()
