"""Context-parallel sweep: ring attention vs the all-gather-then-attend
baseline on a forced host ring.

    PYTHONPATH=src python -m benchmarks.context_parallel_sweep [--smoke]

Emits ``BENCH_cp.json`` with two sections:

- **attention** — batched GQA attention with the sequence sharded over a
  ``MESH_M``-way ring, fwd+bwd, under (a) ``gathered_attention`` (GSPMD's
  lowering: all-gather the full K/V on every device, attend locally) and
  (b) ``ring_attention`` (``parallel.context``: ppermute the KV shard
  around the ring with online-softmax folding; causal runs skip whole
  remote blocks by ring distance).  Per lane: measured step time,
  collective op counts and per-chip wire bytes parsed from the compiled
  HLO.  The ring lane's wire bytes are ASSERTED against the analytic ring
  model (3 rotations per step — fwd KV, bwd KV, bwd dK/dV accumulators —
  of one K+V sequence shard per hop), and its HLO must contain no
  monolithic all-gather / all-reduce carrying a KV-sized payload: every
  real collective on the hot path is a shard-sized collective-permute.
  The gathered lane is the foil — its HLO carries the full-KV all-gather
  the ring exists to avoid.

- **planner** — the ``HybridPlanner`` view of the new context axis for the
  dense-decoder arch: per-ring-size ``cp_step_speedup`` and the arg-max
  kind at 64/256 devices (the BENCH-visible form of the pinned goldens in
  ``tests/test_planner_golden.py``).

The step-time ratio is host-mesh CPU timing (no async collectives, no real
ICI): treat ``ring_le_gathered`` as a sanity direction, and re-measure on
real hardware before quoting speedups — the same caveat as
BENCH_collectives.json's overlap constant.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

MESH_M = 4          # ring size (= forced host devices)
# full-mode sizing: the causal block-skip's compute saving must dominate the
# host-mesh per-collective dispatch overhead for the ring to be measurable
FULL = dict(batch=2, seq=1024, n_heads=4, n_kv_heads=2, head_dim=64,
            reps=5, warmup=1)
SMOKE = dict(batch=1, seq=256, n_heads=4, n_kv_heads=2, head_dim=32,
             reps=2, warmup=1)


def _measure(cfgv, check_time: bool):
    import functools
    import time

    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.core.roofline import (_group_size, _tensor_bytes,
                                     parse_collectives)
    from repro.parallel.context import gathered_attention, ring_attention
    from repro.parallel.jaxcompat import make_mesh, shard_map

    m = MESH_M
    b, t = cfgv["batch"], cfgv["seq"]
    hq, hkv, hd = cfgv["n_heads"], cfgv["n_kv_heads"], cfgv["head_dim"]
    key = jax.random.PRNGKey(0)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, t, hq, hd))
    k = jax.random.normal(kk, (b, t, hkv, hd))
    v = jax.random.normal(kv, (b, t, hkv, hd))
    mesh = make_mesh((1, m), ("data", "model"))
    spec = P(None, "model", None, None)

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

    def lane_loss(attn_fn):
        def loss(q, k, v):
            fn = functools.partial(attn_fn, axis="model", axis_size=m,
                                   causal=True)
            o = shard_map(fn, mesh=mesh, in_specs=(spec,) * 3,
                          out_specs=spec)(q, k, v)
            return (o.astype(jnp.float32) ** 2).sum()
        return loss

    # one K+V sequence shard per hop, f32; 3 rotations per fwd+bwd step
    # (fwd KV, bwd KV replay, bwd dK/dV accumulators riding home) — the
    # backward's accumulator ring takes m hops (the last one carries the
    # shard back to its owner), the KV rings m-1
    pair_bytes = 2 * b * (t // m) * hkv * hd * 4
    wire_lo = 3 * (m - 1) * pair_bytes
    wire_hi = (m - 1) * 2 * pair_bytes + 2 * m * pair_bytes

    points = {}
    with jax.set_mesh(mesh):
        for lane, attn in (("gathered", gathered_attention),
                           ("ring", ring_attention)):
            fn = jax.jit(jax.value_and_grad(lane_loss(attn),
                                            argnums=(0, 1, 2)))
            compiled = fn.lower(q, k, v).compile()
            stats = parse_collectives(compiled.as_text(), default_group=m)
            pt = {"lane": lane, "step_time_s": _time(compiled, (q, k, v)),
                  "ops": stats.ops, "wire_bytes": stats.wire_bytes}
            if lane == "ring":
                pt["expected_wire_bytes"] = [wire_lo, wire_hi]
                assert 0.75 * wire_lo <= stats.wire_bytes \
                    <= 1.25 * wire_hi + 1024, \
                    (stats.wire_bytes, wire_lo, wire_hi, stats.ops)
                assert stats.ops.get("collective-permute", 0) > 0, stats.ops
                # no monolithic KV gather smuggled back in: any all-gather /
                # all-reduce over a real (>1) group must be smaller than one
                # KV shard (scalar loss psums are fine)
                mono = [ln for ln in stats.lines
                        if ("all-gather" in ln or "all-reduce" in ln)
                        and _group_size(ln, m) > 1
                        and _tensor_bytes(ln) >= pair_bytes // 2]
                assert not mono, mono
            else:
                # the foil carries the full-KV all-gather by construction
                assert stats.ops.get("all-gather", 0) > 0, stats.ops
            points[lane] = pt
            print(f"cp_sweep,lane={lane},step_s={pt['step_time_s']:.4f},"
                  f"wire={pt['wire_bytes']:.0f},ops={stats.ops}", flush=True)

    ratio = points["ring"]["step_time_s"] / points["gathered"]["step_time_s"]
    if check_time:
        assert ratio <= 1.0, \
            f"ring slower than the all-gather baseline: ratio={ratio:.3f}"
    return {"mesh_m": m, "points": list(points.values()),
            "gathered_step_s": points["gathered"]["step_time_s"],
            "ring_step_s": points["ring"]["step_time_s"],
            "ring_over_gathered": ratio,
            "ring_le_gathered": bool(ratio <= 1.0)}


def _planner_view():
    from repro.configs import get_config
    from repro.core.planner import HybridPlanner, default_epoch_model
    cfg = get_config("llama3_2_1b")
    pl = HybridPlanner(cfg, epoch_model=default_epoch_model(cfg))
    out = {"cp_step_speedup": {str(m): su
                               for m, su in sorted(pl.run.cp_speedup.items())},
           "tensor_step_speedup": {str(m): su
                                   for m, su in sorted(pl.run.mp_speedup.items())}}
    for d in (64, 256):
        b = pl.best(d)
        out[f"best_{d}"] = {"kind": b.mp_kind, "dp": b.dp, "mp": b.mp,
                            "speedup": b.speedup}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="BENCH_cp.json")
    ap.add_argument("--smoke", action="store_true",
                    help="small sizes / few reps for the CI smoke lane "
                         "(records but does not assert the timing ratio)")
    args = ap.parse_args(argv)

    flags = os.environ.get("XLA_FLAGS", "")
    if "--xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={MESH_M}"
            .strip())
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    cfgv = SMOKE if args.smoke else FULL
    attention = _measure(cfgv, check_time=not args.smoke)
    rec = {
        "bench": "context_parallel_sweep",
        "smoke": bool(args.smoke),
        **{k: cfgv[k] for k in ("batch", "seq", "n_heads", "n_kv_heads",
                                "head_dim")},
        "attention": attention,
        "planner": _planner_view(),
    }
    with open(args.out, "w") as f:
        json.dump(rec, f, indent=1)
    print(f"cp_sweep,done,out={args.out},"
          f"ring_le_gathered={attention['ring_le_gathered']},"
          f"ring_over_gathered={attention['ring_over_gathered']:.3f}")
    return 0


def run(out: str = "BENCH_cp.json") -> None:
    """benchmarks.run entry: re-exec in a subprocess so the forced host
    device count does not fight the already-initialized jax here."""
    env = dict(os.environ,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={MESH_M}",
               JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, "-m", "benchmarks.context_parallel_sweep",
         "--out", out], env=env, text=True, capture_output=True, timeout=1800)
    sys.stdout.write(r.stdout)
    if r.returncode:
        sys.stdout.write(r.stderr[-2000:])
        print("cp_sweep,failed")


if __name__ == "__main__":
    sys.exit(main())
