"""Pipeline-schedule sweep: measured step time vs the analytic bubble model.

    PYTHONPATH=src python -m benchmarks.pipeline_schedule_sweep [--out ...]

Runs a real fwd+bwd training step through ``parallel.pipeline.pipeline_apply``
on a forced S-device host mesh for every (schedule, micro-batch count) point,
and emits ``BENCH_pipeline.json`` with

- per-point measured step time (min over reps) next to the schedule's
  analytic bubble fraction / activation residency / tick counts — the perf
  trajectory seed;
- a **runtime lane** per point: the same (schedule, K) executed by both
  pipeline runtimes — ``ad`` (jax.grad through ``pipeline_apply``'s forward
  scan) and ``scheduled`` (``pipeline_value_and_grad``, the hand-scheduled
  fwd+bwd WorkUnit executor) — with measured step time, the XLA-reported
  temp bytes, and the scheduled runtime's *actual* activation-store size
  (``plan_scheduled_runtime``: min(K, S) slots for 1f1b vs K for gpipe);
- a calibration fit of the analytic model ``t = c / (1 - bubble)`` against
  the ad-lane measurements (the ROADMAP item: calibrate the bubble +
  transfer model against measured ``pipeline_apply`` step times) with
  per-point residuals;
- an **equal-memory comparison**: at the activation budget GPipe needs for
  its K (residency = K micro-batches live), 1F1B fits K' >= K (residency
  min(K', S)) and interleaved fits vK' ticks of wave — so both run a larger
  feasible micro-batch count and a smaller bubble, and their measured step
  time must come in at or under GPipe's.

On the ad runtime gpipe and 1f1b share one executable forward dataflow at
equal K (AD builds the backward), so their measured times differ only at
the *feasible* K each schedule's memory model admits.  The scheduled
runtime is where the schedules actually diverge at runtime: 1f1b's store
holds min(K, S) stage inputs vs gpipe's K at identical tick counts.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

STAGES = 4
VIRTUAL = 2
LAYERS = 8
# sized so per-tick activation compute dominates the host-mesh per-tick
# collective/dispatch overhead (~10 ms/tick on a 2-core container) — small
# d keeps param-grad accumulation cheap, the large batch carries the work
D_MODEL = 256
BATCH = 8192
MICROS = (4, 8, 16)
# equal-memory budget: gpipe@K=8 keeps 8 micro-batches of activations live
EQUAL_MEM_BUDGET = 8


def _sweep_points():
    """(schedule, K, v) grid; interleaved needs S | K for the packed wave."""
    pts = [("gpipe", k, 1) for k in MICROS]
    pts += [("1f1b", k, 1) for k in MICROS]
    pts += [("interleaved", k, VIRTUAL) for k in MICROS if k % STAGES == 0]
    return pts


def _measure(reps: int, warmup: int):
    """The timed sweep — runs in a process whose jax sees STAGES devices."""
    import time

    import jax
    import jax.numpy as jnp

    from repro.parallel.jaxcompat import make_mesh
    from repro.parallel.pipeline import (make_schedule, pipeline_apply,
                                         pipeline_value_and_grad,
                                         plan_scheduled_runtime,
                                         stack_to_stages)

    mesh = make_mesh((1, STAGES), ("data", "model"))
    key = jax.random.PRNGKey(0)
    params = {"w": jax.random.normal(key, (LAYERS, D_MODEL, D_MODEL)) * 0.02,
              "b": jnp.zeros((LAYERS, D_MODEL))}
    x = jax.random.normal(jax.random.PRNGKey(1), (BATCH, D_MODEL))

    def stage_fn(sp, x):
        y, _ = jax.lax.scan(
            lambda x, lp: (jnp.tanh(x @ lp["w"] + lp["b"]), None), x, sp)
        return y

    def _time(compiled, args):
        jax.block_until_ready(compiled(*args))
        for _ in range(warmup):
            jax.block_until_ready(compiled(*args))
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            jax.block_until_ready(compiled(*args))
            best = min(best, time.perf_counter() - t0)
        return best

    points = []
    for sched_kind, k, v in _sweep_points():
        sched = make_schedule(sched_kind, STAGES, k, v)
        stacked = stack_to_stages(params, STAGES, v)
        mb_bytes = (BATCH // k) * D_MODEL * 4          # one f32 stage input

        def ad_step(p, x):
            def loss(p, x):
                y = pipeline_apply(mesh, "model", stage_fn, p, x, n_micro=k,
                                   schedule=sched_kind, virtual_stages=v)
                return (y ** 2).mean()

            return jax.value_and_grad(loss)(p, x)

        inv = 1.0 / (BATCH * D_MODEL)

        def sched_step(p, x):
            def loss_fn(lp, y_m, t_m):
                return (y_m ** 2).sum() * inv

            l, (gs, _, _) = pipeline_value_and_grad(
                mesh, "model", stage_fn, p, x, loss_fn=loss_fn,
                loss_params={}, n_micro=k, schedule=sched_kind,
                virtual_stages=v)
            return l, gs

        rtp = plan_scheduled_runtime(sched)
        lanes = {}
        with jax.set_mesh(mesh):
            for name, fn in (("ad", ad_step), ("scheduled", sched_step)):
                compiled = jax.jit(fn).lower(stacked, x).compile()
                ma = compiled.memory_analysis()
                lanes[name] = {
                    "step_time_s": _time(compiled, (stacked, x)),
                    "xla_temp_bytes": int(ma.temp_size_in_bytes),
                }
            lanes["scheduled"].update({
                "store_slots": rtp.fwd_slots,
                "store_bytes": rtp.fwd_slots * mb_bytes,
                "cotangent_store_bytes": rtp.bwd_slots * mb_bytes,
            })
            # the ad runtime stashes every micro-batch boundary across the
            # fwd->bwd transpose regardless of schedule
            lanes["ad"].update({"store_slots": k * max(v, 1),
                                "store_bytes": k * max(v, 1) * mb_bytes})
        best = lanes["ad"]["step_time_s"]
        tbl = sched.table()
        points.append({
            "schedule": sched_kind, "n_micro": k, "virtual_stages": v,
            "step_time_s": best,
            "runtimes": lanes,
            "bubble_fraction": sched.bubble_fraction(),
            "activation_residency_microbatches":
                sched.activation_residency(),
            "fwd_ticks": sched.fwd_ticks,
            "total_ticks": tbl[-1].tick + 1,
        })
        print(f"pipeline_sweep,schedule={sched_kind},micro={k},v={v},"
              f"ad_step_s={best:.5f},"
              f"scheduled_step_s={lanes['scheduled']['step_time_s']:.5f},"
              f"bubble={sched.bubble_fraction():.4f},"
              f"resid={sched.activation_residency():.1f},"
              f"store={lanes['scheduled']['store_slots']}"
              f"/{lanes['ad']['store_slots']}", flush=True)
    return points


def _calibrate(points):
    """Least-squares fit of t = c / (1 - bubble) + o * ticks.

    The first term is the analytic bubble model (c = ideal zero-bubble step
    time; total compute is constant across the sweep, the bubble stretches
    it); the second is the substrate's per-tick collective/dispatch
    overhead (ppermute rendezvous — the ROADMAP transfer-model term).
    Residuals per point show how well the closed forms explain the
    measurements."""
    import numpy as np

    A = np.array([[1.0 / (1.0 - p["bubble_fraction"]),
                   float(p["fwd_ticks"] + STAGES - 1)] for p in points])
    t = np.array([p["step_time_s"] for p in points])
    (c, o), *_ = np.linalg.lstsq(A, t, rcond=None)
    pred = A @ np.array([c, o])
    resid = {f'{p["schedule"]}@{p["n_micro"]}':
             float(p["step_time_s"] / max(pr, 1e-12) - 1.0)
             for p, pr in zip(points, pred)}
    return {"ideal_step_s": float(c),
            "per_tick_overhead_s": float(o),
            "per_point_rel_err": resid,
            "max_abs_rel_err": max(abs(r) for r in resid.values())}


def _runtime_comparison(points):
    """Scheduled-vs-ad lane summary: per-point step-time ratio plus the
    store realization that is the scheduled runtime's point — 1f1b's
    activation store strictly under gpipe's at K > S (the ad lanes tie at
    K slots for every schedule)."""
    out = {"points": {}}
    for p in points:
        ad, sc = p["runtimes"]["ad"], p["runtimes"]["scheduled"]
        out["points"][f'{p["schedule"]}@{p["n_micro"]}'] = {
            "scheduled_over_ad_time": sc["step_time_s"] / ad["step_time_s"],
            "store_slots_scheduled": sc["store_slots"],
            "store_slots_ad": ad["store_slots"],
        }
    f = {p["n_micro"]: p for p in points if p["schedule"] == "1f1b"}
    g = {p["n_micro"]: p for p in points if p["schedule"] == "gpipe"}
    out["1f1b_store_lt_gpipe_at_K_gt_S"] = {
        str(k): f[k]["runtimes"]["scheduled"]["store_slots"]
        < g[k]["runtimes"]["scheduled"]["store_slots"]
        for k in f if k in g and k > STAGES}
    return out


def _equal_memory(points):
    """Best measured step time per schedule among points whose activation
    residency fits the EQUAL_MEM_BUDGET micro-batch budget."""
    best = {}
    for p in points:
        if p["activation_residency_microbatches"] > EQUAL_MEM_BUDGET:
            continue
        cur = best.get(p["schedule"])
        if cur is None or p["step_time_s"] < cur["step_time_s"]:
            best[p["schedule"]] = p
    out = {"budget_microbatches": EQUAL_MEM_BUDGET,
           "best_feasible": {s: {"n_micro": p["n_micro"],
                                 "step_time_s": p["step_time_s"],
                                 "bubble_fraction": p["bubble_fraction"]}
                             for s, p in best.items()}}
    g = best.get("gpipe")
    for s in ("1f1b", "interleaved"):
        if g and s in best:
            out[f"{s}_le_gpipe"] = bool(
                best[s]["step_time_s"] <= g["step_time_s"])
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="BENCH_pipeline.json")
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--warmup", type=int, default=1)
    args = ap.parse_args(argv)

    # the forced host-device count must land before jax initializes —
    # append to any pre-existing XLA_FLAGS rather than skipping it
    flags = os.environ.get("XLA_FLAGS", "")
    if "--xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={STAGES}"
            .strip())
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    points = _measure(args.reps, args.warmup)
    rec = {
        "bench": "pipeline_schedule_sweep",
        "stages": STAGES, "layers": LAYERS, "d_model": D_MODEL,
        "batch": BATCH,
        "points": points,
        "calibration": _calibrate(points),
        "equal_memory": _equal_memory(points),
        "runtime_comparison": _runtime_comparison(points),
    }
    with open(args.out, "w") as f:
        json.dump(rec, f, indent=1)
    em = rec["equal_memory"]
    print(f"pipeline_sweep,done,out={args.out},"
          f"1f1b_le_gpipe={em.get('1f1b_le_gpipe')},"
          f"interleaved_le_gpipe={em.get('interleaved_le_gpipe')}")
    return 0


def run(out: str = "BENCH_pipeline.json") -> None:
    """benchmarks.run entry: re-exec in a subprocess so the forced host
    device count does not fight the already-initialized jax here."""
    env = dict(os.environ,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={STAGES}",
               JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, "-m", "benchmarks.pipeline_schedule_sweep",
         "--out", out], env=env, text=True, capture_output=True, timeout=1800)
    sys.stdout.write(r.stdout)
    if r.returncode:
        sys.stdout.write(r.stderr[-2000:])
        print("pipeline_sweep,failed")


if __name__ == "__main__":
    sys.exit(main())
