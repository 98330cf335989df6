import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run (deliverable e): lower + compile every
(architecture x input-shape x mesh) on the production meshes, prove memory
fit, and extract the roofline terms (deliverable g).

The two XLA_FLAGS lines above MUST stay first: jax locks the device count on
first init, and only the dry-run wants 512 placeholder host devices.

Per combo this produces:
  1. the REAL artifact — scan-over-layers, flash/chunked attention — whose
     ``.lower().compile()`` success is the dry-run pass and whose
     ``memory_analysis()`` proves fit;
  2. two ANALYSIS artifacts (1-layer and 2-layer configs, fully unrolled
     scans) whose cost_analysis/collective-parse delta gives exact per-layer
     FLOPs/bytes/collective traffic; totals = base + L * per-layer.  This
     sidesteps XLA's while-loop-body-counted-once limitation (DESIGN.md §5).

Usage:
  python -m repro.launch.dryrun --arch all --shape all --mesh both \
      --plan baseline --out results/dryrun
"""
import argparse
import dataclasses
import json
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs import ARCH_IDS, INPUT_SHAPES, get_config
from repro.core import roofline as rl
from repro.launch.mesh import make_production_mesh
from repro.models.api import build_model, make_input_specs
from repro.optim import adafactor, adamw, constant_lr
from repro.parallel.plan import ParallelPlan
from repro.train.steps import (TrainState, _make_pctx, make_train_step,
                               shardings_for)

# archs whose optimizer state must be factored to fit HBM (DESIGN.md §4)
ADAFACTOR_ARCHS = {"kimi_k2_1t_a32b", "nemotron_4_340b"}


def make_plan(arch: str, mesh, plan_name: str, schedule: str = "gpipe",
              pipe_runtime: str = "scheduled",
              comm_runtime: str = "gspmd",
              comm_chunks: int = 1,
              context_parallel: bool = False) -> ParallelPlan:
    multi = "pod" in mesh.axis_names
    dp_axes = ("pod", "data") if multi else ("data",)
    fsdp = dp_axes if (plan_name == "optimized" or arch in ADAFACTOR_ARCHS) else ()
    # the giant archs need params sharded over DP to fit at all — that is the
    # ZeRO-3 "fsdp" addition; paper-faithful baseline for the rest keeps
    # params replicated across DP (sharded over model only)
    if plan_name == "pipeline":
        # model axis carries pipeline stages instead of tensor shards (§4.4);
        # ShardingRules switches to stage-dim rules so memory_analysis
        # reports per-stage parameter residency
        return ParallelPlan(dp_axes=dp_axes, model_axis="model",
                            mp_kind="pipeline", microbatches=4,
                            schedule=schedule,
                            virtual_stages=2 if schedule == "interleaved" else 1,
                            runtime=pipe_runtime,
                            fsdp_axes=tuple(fsdp))
    if context_parallel:
        # model axis carries the sequence-sharded KV ring (parallel.context):
        # params replicated across it, activations 1/16 per device — the
        # long-context training lane (train shapes only; decode keeps its
        # dense cache attention)
        return ParallelPlan(dp_axes=dp_axes, model_axis="model",
                            mp_kind="context", fsdp_axes=tuple(fsdp))
    return ParallelPlan(dp_axes=dp_axes, fsdp_axes=tuple(fsdp),
                        comm_runtime=comm_runtime, comm_chunks=comm_chunks)


def make_optimizer(arch: str):
    if arch in ADAFACTOR_ARCHS:
        return adafactor(constant_lr(1e-3))
    return adamw(constant_lr(1e-3))


def build_step(cfg, shape, mesh, plan, *, unroll: bool):
    """Returns (jitted_fn, example_args_specs) for this (cfg, shape).

    ``unroll`` marks an ANALYSIS artifact: every scan fully unrolls so the
    HLO cost analysis counts all iterations (layers.set_analysis_unroll —
    the flag is consumed lazily at trace time, i.e. inside .lower()).
    """
    from repro.models import layers as _layers
    _layers.set_analysis_unroll(unroll)
    if shape.kind != "train":
        # inference deployment: bf16 weights, no f32 master copies
        cfg = dataclasses.replace(cfg, param_dtype="bfloat16")
    api = build_model(cfg, remat=plan.remat)
    specs = make_input_specs(cfg, shape)
    opt = make_optimizer(cfg.name.replace("-", "_").replace(".", "_"))
    pctx = _make_pctx(mesh, plan,
                      batch_shardable=_batch_shardable(specs, mesh, plan),
                      decode=shape.kind == "decode")
    state_sh, batch_sh = shardings_for(api, mesh, plan, opt, specs)

    if shape.kind == "decode":
        from repro.train.steps import make_serve_steps
        _, decode_step = make_serve_steps(api, pctx=pctx)

        def fn(params, batch):
            return decode_step(params, batch)

        params_shape = jax.eval_shape(api.init, jax.random.PRNGKey(0))
        args = (params_shape, specs)
        in_sh = (state_sh.params, batch_sh)
        # pin the output cache to the input cache shardings so donation
        # aliases the buffers (otherwise memory_analysis double-counts the
        # cache — §Perf iteration B.4)
        from jax.sharding import NamedSharding, PartitionSpec as P
        logits_sh = NamedSharding(mesh, P(
            plan.dp_axes if _batch_shardable(specs, mesh, plan) else None,
            None, None))
        jitted = jax.jit(fn, in_shardings=in_sh,
                         out_shardings=(logits_sh, batch_sh["cache"]),
                         donate_argnums=(1,))
        return jitted, args

    if shape.kind == "prefill":
        def fn(params, batch):
            # capacity covers the full sequence incl. VLM prefix embeds
            logits, cache = api.prefill(params, batch, pctx,
                                        capacity=shape.seq_len)
            return logits, cache

        params_shape = jax.eval_shape(api.init, jax.random.PRNGKey(0))
        args = (params_shape, specs)
        jitted = jax.jit(fn, in_shardings=(state_sh.params, batch_sh))
        return jitted, args

    # train
    train_step = make_train_step(api, opt, mesh=mesh, plan=plan, pctx=pctx)
    params_shape = jax.eval_shape(api.init, jax.random.PRNGKey(0))
    opt_shape = jax.eval_shape(opt.init, params_shape)
    state_shape = TrainState(params=params_shape, opt_state=opt_shape,
                             step=jax.ShapeDtypeStruct((), jnp.int32))
    args = (state_shape, specs)
    jitted = jax.jit(train_step, in_shardings=(state_sh, batch_sh),
                     donate_argnums=(0,))
    return jitted, args


def _batch_shardable(specs, mesh, plan) -> bool:
    # judge by the token batch dim only (cache leaves carry a stacked layer
    # dim in front and would falsely veto)
    b = specs["tokens"].shape[0] if "tokens" in specs else \
        min(v.shape[0] for v in jax.tree.leaves(specs) if v.shape)
    dp = 1
    for a in plan.dp_axes:
        dp *= mesh.shape[a]
    return b % dp == 0 and dp > 1


def _specs_seqlen(specs) -> int:
    return specs["tokens"].shape[1]


def _unrolled_variant(cfg, n_layers: int):
    kw = {"n_layers": n_layers}
    if cfg.encoder_layers:
        kw["encoder_layers"] = n_layers
    return dataclasses.replace(cfg, **kw)


def analyze_combo(arch: str, shape_name: str, *, multi_pod: bool,
                  plan_name: str = "baseline", skip_analysis: bool = False,
                  unroll_analysis: bool = True, schedule: str = "gpipe",
                  pipe_runtime: str = "scheduled",
                  comm_runtime: str = "gspmd", comm_chunks: int = 1,
                  context_parallel: bool = False):
    """Run the dry-run for one (arch, shape, mesh) and return the record."""
    cfg = get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.size
    plan = make_plan(arch, mesh, plan_name, schedule=schedule,
                     pipe_runtime=pipe_runtime, comm_runtime=comm_runtime,
                     comm_chunks=comm_chunks, context_parallel=context_parallel)
    if comm_runtime != "gspmd":
        rec_comm = {"comm_runtime": comm_runtime, "comm_chunks": comm_chunks}
        print(f"  [comm] runtime={comm_runtime} chunks={comm_chunks}",
              flush=True)
    else:
        rec_comm = None
    if plan.is_pipeline:
        # the 1-/2-layer unroll artifacts cannot be partitioned into the
        # 16-stage pipeline; per-layer cost deltas are tensor-plan-only
        skip_analysis = True
    if plan.is_context:
        t_full = _specs_seqlen(make_input_specs(cfg, shape))
        print(f"  [ctx] 16-way kv ring, seq {t_full} -> "
              f"{t_full // 16} per device", flush=True)
    rec = {"arch": arch, "shape": shape_name,
           "mesh": "2x16x16" if multi_pod else "16x16", "chips": chips,
           "plan": plan_name + ("__cp" if plan.is_context else ""),
           "plan_detail": plan.describe(mesh)}
    if rec_comm:
        rec["comm"] = rec_comm
    if plan.is_pipeline:
        # the schedule's predicted idle fraction and activation residency
        # (keyed off the runtime that will execute it), printed next to the
        # lane banner and persisted with the record
        from repro.parallel.pipeline import (make_schedule,
                                             pipeline_activation_residency)
        stages = mesh.shape["model"]
        sched_obj = make_schedule(plan.schedule, stages, plan.microbatches,
                                  plan.virtual_stages)
        resid = pipeline_activation_residency(
            plan.microbatches, stages, plan.schedule, plan.virtual_stages,
            runtime=plan.runtime)
        rec["pipeline"] = {
            "schedule": plan.schedule, "runtime": plan.runtime,
            "n_stages": stages, "n_micro": plan.microbatches,
            "virtual_stages": sched_obj.v,
            "bubble_fraction": sched_obj.bubble_fraction(),
            "activation_residency_microbatches": resid,
        }
        print(f"  [pipe] {sched_obj.describe()} runtime={plan.runtime} "
              f"resid@runtime={resid:.1f}", flush=True)

    t0 = time.time()
    with jax.set_mesh(mesh):
        jitted, args = build_step(cfg, shape, mesh, plan, unroll=False)
        lowered = jitted.lower(*args)
        compiled = lowered.compile()
    rec["compile_s"] = round(time.time() - t0, 1)
    ma = compiled.memory_analysis()
    rec["memory"] = {
        "argument_bytes": ma.argument_size_in_bytes,
        "output_bytes": ma.output_size_in_bytes,
        "temp_bytes": ma.temp_size_in_bytes,
        "alias_bytes": ma.alias_size_in_bytes,
        "peak_bytes": (ma.argument_size_in_bytes + ma.temp_size_in_bytes
                       + ma.output_size_in_bytes - ma.alias_size_in_bytes),
        "hbm_per_chip": rl.HBM_PER_CHIP,
    }
    rec["fits"] = rec["memory"]["peak_bytes"] <= rl.HBM_PER_CHIP
    ca = compiled.cost_analysis()
    rec["real_cost"] = {"flops": ca.get("flops", 0.0),
                        "bytes": ca.get("bytes accessed", 0.0)}
    coll_real = rl.parse_collectives(compiled.as_text(), default_group=chips)
    rec["real_collectives"] = coll_real.to_dict()

    if not skip_analysis:
        # per-layer-exact analysis artifacts
        costs = {}
        for nl in (1, 2):
            cfg_n = _unrolled_variant(cfg, nl)
            with jax.set_mesh(mesh):
                j, a = build_step(cfg_n, shape, mesh, plan, unroll=unroll_analysis)
                low = j.lower(*a)
                comp = low.compile()
            c = comp.cost_analysis()
            coll = rl.parse_collectives(comp.as_text(), default_group=chips)
            costs[nl] = {"flops": c.get("flops", 0.0),
                         "bytes": c.get("bytes accessed", 0.0),
                         "wire": coll.wire_bytes,
                         "ops": coll.ops}
        L = cfg.n_layers
        # clamp: XLA's collective-combiner can merge ops differently between
        # the 1L and 2L builds, occasionally making the delta slightly
        # negative — a per-layer cost is physically >= 0
        per_layer = {k: max(0.0, costs[2][k] - costs[1][k])
                     for k in ("flops", "bytes", "wire")}
        total = {k: costs[1][k] + (L - 1) * per_layer[k]
                 for k in ("flops", "bytes", "wire")}
        rec["analysis"] = {"one_layer": costs[1], "two_layer": costs[2],
                           "per_layer": per_layer, "total": total}
        flops_pc, bytes_pc, wire_pc = total["flops"], total["bytes"], total["wire"]
    else:
        flops_pc = rec["real_cost"]["flops"]
        bytes_pc = rec["real_cost"]["bytes"]
        wire_pc = coll_real.wire_bytes

    roof = rl.Roofline(
        chips=chips,
        hlo_flops_per_chip=flops_pc,
        hlo_bytes_per_chip=bytes_pc,
        collective_wire_bytes_per_chip=wire_pc,
        model_flops_total=rl.model_flops(cfg, shape),
        crosses_pod=multi_pod,
    )
    rec["roofline"] = roof.to_dict()
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--plan", default="baseline",
                    choices=["baseline", "optimized", "pipeline"])
    ap.add_argument("--sched", default=None,
                    choices=["gpipe", "1f1b", "interleaved"],
                    help="pipeline schedule for --plan pipeline "
                         "(default gpipe; interleaved implies v=2)")
    ap.add_argument("--pipe-runtime", default=None,
                    choices=["scheduled", "ad"],
                    help="pipeline runtime for --plan pipeline (default "
                         "scheduled: the hand-scheduled fwd+bwd executor)")
    ap.add_argument("--comm-runtime", default=None,
                    choices=["gspmd", "overlapped"],
                    help="collective runtime for the tensor-MP plans: "
                         "'overlapped' compiles the Megatron matmuls "
                         "through parallel.collectives' chunked ppermute "
                         "rings (train shapes); default gspmd")
    ap.add_argument("--comm-chunks", type=int, default=1,
                    help="ring chunks per shard for --comm-runtime "
                         "overlapped")
    ap.add_argument("--context-parallel", action="store_true",
                    help="swap the tensor shards for a 16-way KV ring "
                         "(mp_kind='context'): sequence sharded over the "
                         "model axis, weights replicated; train shapes "
                         "whose seq divides by 16 only")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--skip-analysis", action="store_true")
    args = ap.parse_args()

    # validate the pipeline-only knobs early: silently ignoring --sched or
    # --pipe-runtime on a non-pipeline plan would dry-run a different
    # strategy than the operator asked for
    if args.plan != "pipeline":
        for flag, val in (("--sched", args.sched),
                          ("--pipe-runtime", args.pipe_runtime)):
            if val is not None:
                raise SystemExit(
                    f"[plan] {flag} {val} only applies to --plan pipeline "
                    f"(got --plan {args.plan}); drop the flag or select the "
                    f"pipeline plan")
    elif args.comm_runtime is not None or args.comm_chunks != 1:
        raise SystemExit(
            "[plan] --comm-runtime/--comm-chunks apply to the tensor-MP "
            "plans (baseline/optimized); pipeline stages exchange "
            "activations over their own ppermute rings (see --pipe-runtime)")
    if args.comm_chunks != 1 and args.comm_runtime != "overlapped":
        raise SystemExit("[plan] --comm-chunks only applies with "
                         "--comm-runtime overlapped")
    if args.context_parallel:
        # context is its own model-axis scheme: it replaces the tensor
        # shards and already schedules its own KV ring (plan.__post_init__
        # rejects the overlapped-collectives combination too)
        if args.plan == "pipeline":
            raise SystemExit("[plan] --context-parallel replaces the model "
                             "axis' tensor shards; it cannot combine with "
                             "--plan pipeline")
        if args.comm_runtime is not None or args.comm_chunks != 1:
            raise SystemExit("[plan] --comm-runtime/--comm-chunks apply to "
                             "the tensor-MP plans; the context plan's KV "
                             "ring schedules its own ppermute collectives")
    sched = args.sched or "gpipe"
    pipe_runtime = args.pipe_runtime or "scheduled"
    comm_runtime = args.comm_runtime or "gspmd"

    archs = ARCH_IDS if args.arch == "all" else args.arch.split(",")
    shapes = list(INPUT_SHAPES) if args.shape == "all" else args.shape.split(",")
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]
    os.makedirs(args.out, exist_ok=True)

    n_ok = n_fail = 0
    for arch in archs:
        for shape in shapes:
            for multi in meshes:
                if args.plan == "pipeline":
                    # pipeline plans: train-mode only, and the 16-way model
                    # axis (x v chunks for interleaved) must evenly
                    # partition the arch's layer stack
                    from repro.models.api import pipeline_applicable
                    v = 2 if sched == "interleaved" else 1
                    if (INPUT_SHAPES[shape].kind != "train"
                            or not pipeline_applicable(get_config(arch), 16, v)):
                        print(f"[skip] {arch}__{shape} (pipeline n/a)")
                        continue
                if args.context_parallel:
                    # the KV ring shards the sequence 16 ways and only
                    # engages on the train path (decode shapes keep their
                    # dense cache attention)
                    sh = INPUT_SHAPES[shape]
                    cfg_a = get_config(arch)
                    seq = make_input_specs(cfg_a, sh)["tokens"].shape[1]
                    if sh.kind != "train" or seq % 16:
                        print(f"[skip] {arch}__{shape} (context n/a: "
                              f"kind={sh.kind} seq={seq})")
                        continue
                tag = f"{arch}__{shape}__{'multi' if multi else 'single'}__{args.plan}"
                if args.context_parallel:
                    tag += "__cp"
                if comm_runtime != "gspmd":
                    tag += f"__{comm_runtime}"
                out_path = os.path.join(args.out, tag + ".json")
                if os.path.exists(out_path):
                    print(f"[skip] {tag} (cached)")
                    n_ok += 1
                    continue
                print(f"[run ] {tag}", flush=True)
                try:
                    # analysis artifacts only needed on the single-pod mesh
                    rec = analyze_combo(arch, shape, multi_pod=multi,
                                        plan_name=args.plan,
                                        skip_analysis=args.skip_analysis or multi,
                                        schedule=sched,
                                        pipe_runtime=pipe_runtime,
                                        comm_runtime=comm_runtime,
                                        comm_chunks=args.comm_chunks,
                                        context_parallel=args.context_parallel)
                    with open(out_path, "w") as f:
                        json.dump(rec, f, indent=1)
                    r = rec["roofline"]
                    print(f"  ok {rec['compile_s']}s fit={rec['fits']} "
                          f"bottleneck={r['bottleneck']} "
                          f"t=({r['t_compute']:.3e},{r['t_memory']:.3e},"
                          f"{r['t_collective']:.3e})s mfu={r['mfu']:.2f}",
                          flush=True)
                    n_ok += 1
                except Exception as e:
                    n_fail += 1
                    print(f"  FAIL {type(e).__name__}: {e}", flush=True)
                    with open(out_path + ".err", "w") as f:
                        f.write(traceback.format_exc())
    print(f"dry-run complete: {n_ok} ok, {n_fail} failed")
    return 0 if n_fail == 0 else 1


if __name__ == "__main__":
    raise SystemExit(main())
