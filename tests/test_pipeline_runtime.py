"""Hand-scheduled fwd+bwd pipeline runtime (ISSUE 3 tentpole).

Two properties make the scheduled runtime *the* runtime rather than a
curiosity, and both are pinned here:

1. **Residency realization** — the runtime's live-buffer high-water mark
   (the activation store ``plan_scheduled_runtime`` actually allocates)
   equals the closed-form ``activation_residency()`` the planner's memory
   filter assumes: min(K, S) for 1f1b vs K for gpipe, strictly fewer at
   K > S.  The ad runtime cannot realize this (AD-through-scan stashes all
   K micro-batches across the fwd->bwd transpose).
2. **Differential correctness** — loss and every gradient (stage params,
   loss params, input cotangent) match ``jax.value_and_grad`` through the
   ad runtime to fp32 round-off on the schedule x stages x micro grid.
"""
import subprocess
import sys
import os
import textwrap

import numpy as np
import pytest

from repro.parallel.pipeline import (PipelineSchedule, SCHEDULE_KINDS,
                                     make_schedule,
                                     pipeline_activation_residency,
                                     plan_scheduled_runtime, stack_to_stages,
                                     stages_to_stack)

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

GRID = [(S, K) for S in (2, 3, 4) for K in (1, 2, 4, 8, 16)]


def _run_subprocess(code: str):
    env = dict(os.environ, PYTHONPATH=SRC, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=env, timeout=900)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-3000:]}"
    return r.stdout


# ---------------------------------------------------------------------------
# 1. residency realization (pure — the store the runtime allocates)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind", SCHEDULE_KINDS)
@pytest.mark.parametrize("S,K", GRID)
def test_store_high_water_equals_residency(kind, S, K):
    """The satellite metric: the scheduled runtime's live-buffer high-water
    mark — max over (stage, tick) of concurrently-stashed stage inputs —
    equals the schedule's closed-form activation residency.  For the v=1
    schedules that is exact in micro-batches (1f1b: min(K, S); gpipe: K);
    interleaved counts chunk inputs, residency * v of them."""
    sched = make_schedule(kind, S, K)
    rtp = plan_scheduled_runtime(sched)
    assert rtp.high_water == rtp.fwd_slots  # store sized exactly at the peak
    bound = sched.activation_residency() * sched.v
    if kind == "interleaved":
        # interleaved may buffer up to v-1 in-transit wrap chunks above the
        # closed-form held-activation bound (covered by the planner's
        # ring-buffer term), and can never fall below what the exec table
        # holds
        assert sched.residency_from_table() * sched.v <= rtp.fwd_slots \
            <= round(bound) + sched.v - 1, (S, K, rtp.fwd_slots, bound)
    else:
        assert rtp.fwd_slots == round(bound), (kind, S, K, rtp.fwd_slots)
    if kind == "1f1b":
        assert rtp.fwd_slots == min(K, S)
    if kind == "gpipe":
        assert rtp.fwd_slots == K


@pytest.mark.parametrize("S,K", [(2, 4), (2, 8), (4, 8), (4, 16)])
def test_1f1b_store_strictly_smaller_than_gpipe(S, K):
    """The acceptance criterion: at K > S the scheduled runtime's 1f1b
    activation store is strictly smaller than gpipe's — the memory win the
    planner's arg-max (1f1b@K=16) banks on, now realized by the executor."""
    assert K > S
    g = plan_scheduled_runtime(make_schedule("gpipe", S, K))
    f = plan_scheduled_runtime(make_schedule("1f1b", S, K))
    assert f.fwd_slots == S < K == g.fwd_slots, (S, K, f, g)
    # total ticks are identical — 1f1b trades nothing for the memory
    assert f.n_ticks == g.n_ticks == 2 * (K + S - 1)


@pytest.mark.parametrize("kind", SCHEDULE_KINDS)
@pytest.mark.parametrize("S,K", [(2, 4), (4, 4), (4, 8)])
def test_runtime_plan_tables_consistent(kind, S, K):
    """Structural invariants of the compiled tick tables: cells mirror the
    WorkUnit table, every slot index is within the allocated store, every
    non-injected forward input arrives over the ring exactly once before
    (or at) its exec tick, and every backward reads a slot a forward
    stashed."""
    sched = make_schedule(kind, S, K)
    rtp = plan_scheduled_runtime(sched)
    t = rtp.tables
    n_fwd = int((t["op"] == 1).sum())
    n_bwd = int((t["op"] == 2).sum())
    assert n_fwd == n_bwd == K * sched.n_virtual
    assert rtp.n_ticks == t["op"].shape[0] == sched.total_ticks()
    # slot bounds
    for name in ("f_slot", "f_arr", "b_act"):
        assert t[name].max() < rtp.fwd_slots
    for name in ("b_seed", "b_arr", "b_rd"):
        assert t[name].max() < rtp.bwd_slots
    # every fwd unit has a slot; injected units own stash writes, the rest
    # match one ring arrival at an earlier-or-equal tick
    fwd_cells = np.argwhere(t["op"] == 1)
    n_inject = sum(int(t["f_inject"][tt, s]) for tt, s in fwd_cells)
    n_arrivals = int((t["f_arr"] >= 0).sum())
    assert n_arrivals == n_fwd - n_inject
    for tt, s in fwd_cells:
        assert t["f_slot"][tt, s] >= 0
        if not t["f_inject"][tt, s]:
            arr_ticks = np.argwhere(
                (t["f_arr"][:tt + 1, s] == t["f_slot"][tt, s]))
            assert arr_ticks.size >= 1, (kind, S, K, tt, s)
    # every bwd unit pops a stashed input and an incoming cotangent
    for tt, s in np.argwhere(t["op"] == 2):
        assert t["b_act"][tt, s] >= 0 and t["b_rd"][tt, s] >= 0
    # the last virtual stage emits exactly one loss seed per micro-batch
    assert int((t["b_seed"] >= 0).sum()) == K


def test_activation_residency_keyed_off_runtime():
    """The planner's memory filter input: on the ad runtime every schedule
    holds all K micro-batches (jax AD stashes the full forward before the
    backward), so 1f1b's residency edge exists only under the scheduled
    runtime."""
    for S, K in GRID:
        for kind in SCHEDULE_KINDS:
            ad = pipeline_activation_residency(K, S, kind, 2, runtime="ad")
            sc = pipeline_activation_residency(K, S, kind, 2,
                                               runtime="scheduled")
            assert ad == K
            assert sc <= ad
    assert pipeline_activation_residency(16, 4, "1f1b",
                                         runtime="scheduled") == 4


def test_planner_memory_model_follows_runtime():
    """HybridPlanner(pipe_runtime="ad") must cost 1f1b like gpipe (no
    residency discount) and stamp the runtime into the emitted plans."""
    from repro.configs import get_config
    from repro.core.planner import (HybridPlanner, default_epoch_model,
                                    per_device_mem_bytes)
    cfg = get_config("biglstm")
    kw = dict(mp=2, mp_kind="pipeline", fsdp=1, mini_batch=64, seq_len=4096,
              remat=False, microbatches=16)
    mem_ad = per_device_mem_bytes(cfg, schedule="1f1b", pipe_runtime="ad",
                                  **kw)
    mem_sc = per_device_mem_bytes(cfg, schedule="1f1b",
                                  pipe_runtime="scheduled", **kw)
    mem_gp = per_device_mem_bytes(cfg, schedule="gpipe",
                                  pipe_runtime="ad", **kw)
    assert mem_ad == mem_gp > mem_sc
    for rt in ("scheduled", "ad"):
        planner = HybridPlanner(cfg, epoch_model=default_epoch_model(cfg),
                                pipe_runtime=rt)
        best = planner.best(256)
        assert best.mp_kind == "pipeline"
        assert best.plan.runtime == rt
    with pytest.raises(ValueError):
        HybridPlanner(cfg, epoch_model=default_epoch_model(cfg),
                      pipe_runtime="bogus")


def test_plan_runtime_field_validated():
    from repro.parallel.plan import ParallelPlan
    with pytest.raises(ValueError, match="runtime"):
        ParallelPlan(runtime="bogus")
    assert ParallelPlan().runtime == "scheduled"
    assert "scheduled runtime" in ParallelPlan(
        mp_kind="pipeline", microbatches=4).describe(
            _FakeMesh({"data": 2, "model": 2}))


class _FakeMesh:
    def __init__(self, shape):
        self.shape = shape
        self.axis_names = tuple(shape)


def test_residual_store_spec_layout():
    """The scheduled runtime's activation store, viewed as a logical
    (stages, slots, mb, ...) array, is stage-local on the model axis with
    the micro-batch dim over DP — matching the in-shard_map carry."""
    from jax.sharding import PartitionSpec as P

    from repro.configs import get_config
    from repro.parallel.plan import ParallelPlan
    from repro.parallel.sharding import ShardingRules
    rules = ShardingRules(get_config("biglstm"),
                          _FakeMesh({"data": 4, "model": 4}),
                          ParallelPlan(mp_kind="pipeline", microbatches=4))
    spec = rules.residual_store_spec(4)
    assert spec == P("model", None, ("data",), None)
    with pytest.raises(ValueError):
        rules.residual_store_spec(2)


def test_stack_to_stages_shaped_error():
    """ISSUE 3 satellite: a non-divisible layer stack must raise a shaped
    error naming the offending sizes, not silently mis-reshape."""
    import jax.numpy as jnp
    params = {"w": jnp.zeros((6, 3, 3))}
    with pytest.raises(ValueError, match=r"6.*n_stages \* virtual_stages"):
        stack_to_stages(params, 4)
    with pytest.raises(ValueError, match="not\n?.*divisible|divisible"):
        stack_to_stages(params, 2, 2)
    # the inverse validates its layout too
    with pytest.raises(ValueError, match="stages_to_stack"):
        stages_to_stack({"w": jnp.zeros((2, 2, 1, 3))}, 4, 1)
    rt = stages_to_stack(stack_to_stages(params, 3), 3)
    assert rt["w"].shape == (6, 3, 3)


# ---------------------------------------------------------------------------
# 2. differential correctness vs the ad runtime
# ---------------------------------------------------------------------------

_GRID_RUNNER = """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, jax.numpy as jnp
    from repro.parallel.jaxcompat import make_mesh
    from repro.parallel.pipeline import (pipeline_apply,
                                         pipeline_value_and_grad,
                                         stack_to_stages)

    L, d, B = 8, 16, 24
    key = jax.random.PRNGKey(0)
    params = {{"w": jax.random.normal(key, (L, d, d)) * 0.1,
               "b": jnp.zeros((L, d))}}
    x = jax.random.normal(jax.random.PRNGKey(1), (B, d))
    tgt = jax.random.normal(jax.random.PRNGKey(2), (B, d))

    def stage_fn(sp, x):
        y, _ = jax.lax.scan(
            lambda x, lp: (jnp.tanh(x @ lp["w"] + lp["b"]), None), x, sp)
        return y

    def loss_fn(lp, y_m, t_m):
        return ((y_m * lp["scale"] - t_m) ** 2).sum()

    lp = {{"scale": jnp.float32(1.3)}}
    for stages in {stages_list}:
        mesh = make_mesh((1, stages), ("data", "model"))
        for sched in ("gpipe", "1f1b", "interleaved"):
            v = 2 if sched == "interleaved" else 1
            stacked = stack_to_stages(params, stages, v)
            for K in (2, 4, 8):
                def ad_loss(stk, lpp, xx):
                    y = pipeline_apply(mesh, "model", stage_fn, stk, xx,
                                       n_micro=K, schedule=sched,
                                       virtual_stages=v)
                    ym = y.reshape((K, B // K, d))
                    tm = tgt.reshape((K, B // K, d))
                    return jax.vmap(
                        lambda a, b: loss_fn(lpp, a, b))(ym, tm).sum()
                with jax.set_mesh(mesh):
                    ref_l, ref_g = jax.jit(jax.value_and_grad(
                        ad_loss, argnums=(0, 1, 2)))(stacked, lp, x)
                    out_l, out_g = jax.jit(
                        lambda stk, lpp, xx: pipeline_value_and_grad(
                            mesh, "model", stage_fn, stk, xx,
                            loss_fn=loss_fn, loss_params=lpp, targets=tgt,
                            n_micro=K, schedule=sched,
                            virtual_stages=v))(stacked, lp, x)
                rel_l = abs(float(ref_l - out_l)) / abs(float(ref_l))
                errs = jax.tree.map(
                    lambda a, b: float(jnp.abs(a - b).max()), ref_g, out_g)
                err_g = max(jax.tree.leaves(errs))
                assert rel_l < 1e-5 and err_g < 1e-5, \\
                    (stages, sched, K, rel_l, errs)
                print("OK", stages, sched, K, rel_l, err_g)
"""


def test_scheduled_matches_ad_grid_2stage():
    """Every (schedule, K) point at S=2: loss + stage-param grads +
    loss-param grads + input cotangent all match jax.value_and_grad of the
    ad runtime to fp32 round-off."""
    out = _run_subprocess(_GRID_RUNNER.format(stages_list="(2,)"))
    assert out.count("OK") == 9


@pytest.mark.slow
def test_scheduled_matches_ad_grid_4stage():
    """Same grid at S=4 (the deeper warmup/drain and wrap-ring paths)."""
    out = _run_subprocess(_GRID_RUNNER.format(stages_list="(4,)"))
    assert out.count("OK") == 9


def test_scheduled_model_grads_equal_ad_dp_stages():
    """Model-level (the train-step path): biglstm on a 2x2 dp x stages
    mesh, scheduled runtime ((loss, metrics), grads) vs jax.value_and_grad
    of the ad pipeline loss — loss and every param grad equal to fp32
    round-off, embed/head included (the vjp'd pre/post parts)."""
    _run_subprocess("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import jax, jax.numpy as jnp
        from repro.parallel.jaxcompat import make_mesh
        from repro.configs import get_config
        from repro.models.api import build_model

        for arch in ("biglstm", "smollm_360m"):
            cfg = get_config(arch).reduced()
            api = build_model(cfg, remat=False)
            key = jax.random.PRNGKey(0)
            params = api.init(key)
            batch = {"tokens": jax.random.randint(key, (8, 16), 0,
                                                  cfg.vocab_size,
                                                  dtype=jnp.int32),
                     "labels": jax.random.randint(key, (8, 16), 0,
                                                  cfg.vocab_size,
                                                  dtype=jnp.int32)}
            mesh = make_mesh((2, 2), ("data", "model"))

            def ad_loss(p, b):
                return api.pipeline_loss_fn(p, b, mesh=mesh, axis="model",
                                            n_micro=4, schedule="1f1b",
                                            batch_axes=("data",))[0]

            with jax.set_mesh(mesh):
                ref_l, ref_g = jax.jit(jax.value_and_grad(ad_loss))(params,
                                                                    batch)
                (out_l, _), out_g = jax.jit(
                    lambda p, b: api.pipeline_value_and_grad_fn(
                        p, b, mesh=mesh, axis="model", n_micro=4,
                        schedule="1f1b", batch_axes=("data",)))(params,
                                                                batch)
            err_l = abs(float(ref_l) - float(out_l))
            errs = jax.tree.map(lambda a, b: float(jnp.abs(a - b).max()),
                                ref_g, out_g)
            err_g = max(jax.tree.leaves(errs))
            assert err_l < 1e-5 and err_g < 1e-5, (arch, err_l, err_g)
            print("OK", arch, err_l, err_g)
    """)


def test_train_step_scheduled_vs_ad_runtime_bit_for_bit():
    """The full train step (grads -> clip -> adamw update) produces the
    same post-step loss under both runtimes of the same 1f1b plan — the
    ISSUE 3 differential-testing escape hatch."""
    _run_subprocess("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import dataclasses
        import jax, jax.numpy as jnp
        from repro.parallel.jaxcompat import make_mesh
        from repro.configs import get_config
        from repro.models.api import build_model
        from repro.optim import adamw, constant_lr
        from repro.parallel.plan import ParallelPlan
        from repro.train.steps import init_train_state, make_train_step

        cfg = get_config("biglstm").reduced()
        api = build_model(cfg)
        opt = adamw(constant_lr(1e-3))
        mesh = make_mesh((2, 2), ("data", "model"))
        key = jax.random.PRNGKey(0)
        batch = {"tokens": jax.random.randint(key, (8, 16), 0,
                                              cfg.vocab_size,
                                              dtype=jnp.int32),
                 "labels": jax.random.randint(key, (8, 16), 0,
                                              cfg.vocab_size,
                                              dtype=jnp.int32)}
        plan = ParallelPlan(mp_kind="pipeline", microbatches=4,
                            schedule="1f1b")
        losses = {}
        for rt in ("scheduled", "ad"):
            p = dataclasses.replace(plan, runtime=rt)
            step = make_train_step(api, opt, mesh=mesh, plan=p)
            state = init_train_state(api, opt, jax.random.PRNGKey(0))
            with jax.set_mesh(mesh):
                step = jax.jit(step)
                for _ in range(2):
                    state, metrics = step(state, batch)
            losses[rt] = float(metrics["loss"])
        diff = abs(losses["scheduled"] - losses["ad"])
        assert diff < 1e-5, losses
        print("OK", losses)
    """)
