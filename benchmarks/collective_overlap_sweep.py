"""Collective-overlap sweep: overlapped collective-matmul + bucketed DP sync
vs the GSPMD monolithic-collective lanes, on a forced host mesh.

    PYTHONPATH=src python -m benchmarks.collective_overlap_sweep [--smoke]

Emits ``BENCH_collectives.json`` with three sections:

- **tensor_mp** — a stack of Megatron column/row-parallel MLP layers run
  fwd+bwd under (a) GSPMD shardings (monolithic all-reduce per row-parallel
  matmul) and (b) the overlap-scheduled chunked ``ppermute`` rings
  (``parallel.collectives``; ``models.layers.mlp_apply_overlapped``) over a
  chunk-count sweep.  Per lane: measured step time, collective op counts and
  per-chip wire bytes parsed from the compiled HLO — the overlapped lane's
  wire bytes are ASSERTED equal to the analytic ring model (fwd: gather(x) +
  scatter(out); bwd: gather(dy) + scatter(dx) + re-gather(x) = 5 rings of
  (m-1)/m * |x| each per layer), and its HLO must contain no monolithic
  all-gather / all-reduce on the matmul hot path (every >unit-group
  collective is a chunk-sized collective-permute).

- **dp_sync** — the same stack replicated over a pure-DP mesh: GSPMD's fused
  gradient all-reduce vs ``bucketed_grad_sync``'s per-bucket reduce-scatter
  + all-gather split, with the bucket count swept via the bucket size.

- **planner_crossover** — the ``HybridPlanner`` DP-vs-hybrid crossover
  device count under each comm runtime (the BENCH-visible form of the
  pinned golden in ``tests/test_planner_golden.py``).

``overlap_constant_proxy`` summarizes the best overlapped-vs-gspmd step-time
ratio; it seeds ``core.comm.MEASURED_OVERLAP`` but the host-mesh CPU backend
has no async collectives, so re-calibrate the constant on real ICI hardware
(the same caveat as BENCH_pipeline.json's bubble calibration).
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

MESH_M = 4          # model-axis shards (= forced host devices)
LAYERS = 4
# full-mode sizing: per-layer matmul time must dominate the host-mesh
# per-collective dispatch overhead for the overlap to be measurable
FULL = dict(d_model=512, d_ff=2048, batch=8, seq=512, chunk_sweep=(1, 2, 4),
            reps=5, warmup=1)
SMOKE = dict(d_model=128, d_ff=512, batch=4, seq=128, chunk_sweep=(1, 2),
             reps=2, warmup=1)


def _measure(cfgv):
    import time

    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core.roofline import parse_collectives
    from repro.models import layers as L
    from repro.parallel.collectives import bucketed_grad_sync
    from repro.parallel.jaxcompat import make_mesh, shard_map

    m = MESH_M
    d, ff = cfgv["d_model"], cfgv["d_ff"]
    b, t = cfgv["batch"], cfgv["seq"]
    key = jax.random.PRNGKey(0)
    params = [{"wi": jax.random.normal(jax.random.fold_in(key, i),
                                       (d, ff)) * 0.02,
               "wo": jax.random.normal(jax.random.fold_in(key, 100 + i),
                                       (ff, d)) * 0.02}
              for i in range(LAYERS)]
    x = jax.random.normal(jax.random.PRNGKey(1), (b, t, d))

    def _time(compiled, args):
        jax.block_until_ready(compiled(*args))
        for _ in range(cfgv["warmup"]):
            jax.block_until_ready(compiled(*args))
        best = float("inf")
        for _ in range(cfgv["reps"]):
            t0 = time.perf_counter()
            jax.block_until_ready(compiled(*args))
            best = min(best, time.perf_counter() - t0)
        return best

    def stack_loss(p, x, mlp):
        for lp in p:
            x = x + mlp(lp, x)
        return (x ** 2).mean()

    # ---- tensor-MP lanes -------------------------------------------------
    mesh = make_mesh((1, m), ("data", "model"))
    p_sh = [{"wi": NamedSharding(mesh, P(None, "model")),
             "wo": NamedSharding(mesh, P("model", None))}
            for _ in range(LAYERS)]
    x_sh = NamedSharding(mesh, P())

    def gspmd_mlp(lp, x):
        return jax.nn.gelu(x @ lp["wi"]) @ lp["wo"]

    def overlapped_mlp(chunks):
        def mlp(lp, x):
            def local(lp, xl):
                return L.mlp_apply_overlapped(lp, xl, "gelu", axis="model",
                                              axis_size=m, chunks=chunks)
            return shard_map(
                local, mesh=mesh,
                in_specs=({"wi": P(None, "model"), "wo": P("model", None)},
                          P(None, "model", None)),
                out_specs=P(None, "model", None))(lp, x)
        return mlp

    x_bytes = b * t * d * 4
    # fwd: gather(x) + scatter(out); bwd: gather(dy) + scatter(dx) +
    # re-gather(x) for dW — 5 rings of (m-1)/m * |x| per layer
    expected_ring_wire = LAYERS * 5 * (m - 1) / m * x_bytes
    points = []
    with jax.set_mesh(mesh):
        lanes = [("gspmd", None, lambda: gspmd_mlp)]
        lanes += [(f"overlapped", c, lambda c=c: overlapped_mlp(c))
                  for c in cfgv["chunk_sweep"]]
        for lane, chunks, mk in lanes:
            fn = jax.jit(jax.value_and_grad(
                lambda p, x, mlp=mk(): stack_loss(p, x, mlp)),
                in_shardings=(p_sh, x_sh))
            compiled = fn.lower(params, x).compile()
            stats = parse_collectives(compiled.as_text(), default_group=m)
            pt = {"lane": lane, "chunks": chunks,
                  "step_time_s": _time(compiled, (params, x)),
                  "ops": stats.ops, "wire_bytes": stats.wire_bytes}
            if lane == "overlapped":
                # Wire must match the analytic ring model: at most the 5
                # rings/layer above, at least 4 (XLA may CSE the backward
                # re-gather of x against the forward gather), plus sub-KB
                # scalar-loss psums.  And the hot path must be chunk-sized
                # permutes only: an all-gather / all-reduce carrying an
                # activation-sized payload over a real (>1) replica group
                # would be a monolithic collective GSPMD smuggled back in
                # (unit-group psums from the shard_map transpose carry zero
                # wire and are fine).
                pt["expected_wire_bytes"] = expected_ring_wire
                assert (0.75 * expected_ring_wire <= stats.wire_bytes
                        <= expected_ring_wire + 1024), \
                    (stats.wire_bytes, expected_ring_wire, stats.ops)
                from repro.core.roofline import _group_size, _tensor_bytes
                chunk_bytes = x_bytes // m
                mono = [ln for ln in stats.lines
                        if ("all-reduce" in ln or "all-gather" in ln)
                        and _group_size(ln, m) > 1
                        and _tensor_bytes(ln) >= chunk_bytes]
                assert not mono, mono
            points.append(pt)
            print(f"collective_sweep,lane={lane},chunks={chunks},"
                  f"step_s={pt['step_time_s']:.4f},"
                  f"wire={pt['wire_bytes']:.0f}", flush=True)
    t_gspmd = points[0]["step_time_s"]
    best_ov = min(p["step_time_s"] for p in points if p["lane"] == "overlapped")
    tensor_mp = {
        "points": points,
        "gspmd_step_s": t_gspmd,
        "best_overlapped_step_s": best_ov,
        "overlapped_le_gspmd": bool(best_ov <= t_gspmd),
        "overlap_constant_proxy": max(0.0, 1.0 - best_ov / t_gspmd),
    }

    # ---- DP bucketed grad-sync lanes ------------------------------------
    dmesh = make_mesh((m, 1), ("data", "model"))
    grad_bytes = sum(p.size * 4 for lp in params for p in lp.values())
    dp_points = []
    with jax.set_mesh(dmesh):
        dp_sh = [{"wi": NamedSharding(dmesh, P()),
                  "wo": NamedSharding(dmesh, P())} for _ in range(LAYERS)]
        bx_sh = NamedSharding(dmesh, P("data"))

        def mono_fn(p, xb):
            return jax.value_and_grad(
                lambda p: stack_loss(p, xb, gspmd_mlp))(p)

        def bucketed_fn(bucket_bytes):
            def fn(p, xb):
                def local(p, xl):
                    loss, g = jax.value_and_grad(
                        lambda p: stack_loss(p, xl, gspmd_mlp))(p)
                    g = bucketed_grad_sync(g, dp_axis="data", dp_size=m,
                                           bucket_bytes=bucket_bytes)
                    g = jax.tree.map(lambda v: v / m, g)
                    return jax.lax.pmean(loss, "data"), g
                return shard_map(local, mesh=dmesh,
                                 in_specs=(P(), P("data")),
                                 out_specs=(P(), P()))(p, xb)
            return fn

        # "monolithic" = the manual sync with ONE bucket — the
        # apples-to-apples baseline for bucketing (same shard_map codegen,
        # only the bucket count differs); GSPMD's fused all-reduce lane is
        # reported alongside for the cross-runtime picture
        for lane, fn, bkt in (
                [("gspmd", mono_fn, None),
                 ("monolithic", bucketed_fn(grad_bytes), float(grad_bytes))]
                + [(f"bucketed", bucketed_fn(grad_bytes / k), grad_bytes / k)
                   for k in (4, 8)]):
            compiled = jax.jit(fn, in_shardings=(dp_sh, bx_sh)) \
                .lower(params, x).compile()
            stats = parse_collectives(compiled.as_text(), default_group=m)
            dp_points.append({
                "lane": lane, "bucket_bytes": bkt,
                "n_buckets": (None if bkt is None
                              else max(1, round(grad_bytes / bkt))),
                "step_time_s": _time(compiled, (params, x)),
                "ops": stats.ops, "wire_bytes": stats.wire_bytes})
            print(f"collective_sweep,dp_lane={lane},bucket={bkt},"
                  f"step_s={dp_points[-1]['step_time_s']:.4f},"
                  f"ops={stats.ops}", flush=True)
    dp_best = min(p["step_time_s"] for p in dp_points if p["lane"] == "bucketed")
    t_mono = next(p["step_time_s"] for p in dp_points
                  if p["lane"] == "monolithic")
    dp_sync = {"points": dp_points, "grad_bytes": grad_bytes,
               "gspmd_step_s": dp_points[0]["step_time_s"],
               "monolithic_step_s": t_mono,
               "best_bucketed_step_s": dp_best,
               "bucketed_le_monolithic": bool(dp_best <= t_mono),
               "best_bucketed_over_gspmd":
                   dp_best / dp_points[0]["step_time_s"]}
    return tensor_mp, dp_sync


def _planner_crossover():
    # llama: an arch the overlapped runtime executes, so the measured
    # overlap legitimately moves its crossover (inception's CNN blocks fall
    # back to GSPMD and must not move — see test_planner_golden.py)
    from repro.configs import get_config
    from repro.core.planner import HybridPlanner, default_epoch_model
    out = {}
    cfg = get_config("llama3_2_1b")
    for rt in ("gspmd", "overlapped"):
        planner = HybridPlanner(cfg, epoch_model=default_epoch_model(cfg),
                                comm_runtime=rt)
        out[rt] = {"crossover_m2": planner.crossover(2),
                   "crossover_m4": planner.crossover(4),
                   "best_256_speedup": planner.best(256).speedup,
                   "best_256_kind": planner.best(256).mp_kind}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="BENCH_collectives.json")
    ap.add_argument("--smoke", action="store_true",
                    help="small sizes / few reps for the CI smoke lane")
    args = ap.parse_args(argv)

    flags = os.environ.get("XLA_FLAGS", "")
    if "--xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={MESH_M}"
            .strip())
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    cfgv = SMOKE if args.smoke else FULL
    tensor_mp, dp_sync = _measure(cfgv)
    rec = {
        "bench": "collective_overlap_sweep",
        "smoke": bool(args.smoke),
        "mesh_m": MESH_M, "layers": LAYERS, **{k: cfgv[k] for k in
                                               ("d_model", "d_ff", "batch",
                                                "seq")},
        "tensor_mp": tensor_mp,
        "dp_sync": dp_sync,
        "planner_crossover": _planner_crossover(),
    }
    with open(args.out, "w") as f:
        json.dump(rec, f, indent=1)
    print(f"collective_sweep,done,out={args.out},"
          f"overlapped_le_gspmd={tensor_mp['overlapped_le_gspmd']},"
          f"overlap_proxy={tensor_mp['overlap_constant_proxy']:.3f},"
          f"bucketed_le_monolithic={dp_sync['bucketed_le_monolithic']}")
    return 0


def run(out: str = "BENCH_collectives.json") -> None:
    """benchmarks.run entry: re-exec in a subprocess so the forced host
    device count does not fight the already-initialized jax here."""
    env = dict(os.environ,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={MESH_M}",
               JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, "-m", "benchmarks.collective_overlap_sweep",
         "--out", out], env=env, text=True, capture_output=True, timeout=1800)
    sys.stdout.write(r.stdout)
    if r.returncode:
        sys.stdout.write(r.stderr[-2000:])
        print("collective_sweep,failed")


if __name__ == "__main__":
    sys.exit(main())
