"""Context-parallel ring attention (ISSUE 8): the sequence-sharded KV
ppermute ring vs unsharded attention at fp32 round-off (values AND custom-vjp
grads over the ring-size x mask x GQA grid), the end-to-end CP train step vs
single-device, the HLO assertion that the CP hot path carries only
collective-permutes (no monolithic all-gather of K/V), planner/plan/CLI
gating for the new ``mp_kind='context'`` axis, and the serve engine's
CP-routed chunked prefill."""
import json
import os
import subprocess
import sys
import textwrap
import warnings

import pytest

from repro.configs import get_config
from repro.core.comm import (HardwareModel, cp_ring_time,
                             load_measured_overlap)
from repro.core.planner import (HybridPlanner, context_mp_supported,
                                cp_step_speedup, default_epoch_model)
from repro.launch.train import parse_parallel
from repro.parallel.plan import ParallelPlan
from repro.parallel.sharding import ShardingRules

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run_subprocess(code: str):
    env = dict(os.environ, PYTHONPATH=SRC, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=env, timeout=900)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-3000:]}"
    return r.stdout


class FakeMesh:
    def __init__(self, shape):
        self.shape = shape
        self.axis_names = tuple(shape)


# ---------------------------------------------------------------------------
# pure (no-device) units
# ---------------------------------------------------------------------------

def test_plan_context_validation():
    p = ParallelPlan(mp_kind="context")
    assert p.is_context and not p.is_pipeline
    desc = p.describe(FakeMesh({"data": 2, "model": 4}))
    assert "kv ring" in desc, desc
    with pytest.raises(ValueError, match="mp_kind"):
        ParallelPlan(mp_kind="sequence")
    # the ring schedules its own collectives; the overlapped matmul runtime
    # has no meaning on a context axis
    with pytest.raises(ValueError, match="context"):
        ParallelPlan(mp_kind="context", comm_runtime="overlapped")


def test_sharding_rules_context_replicates_params():
    """Under a context plan the model axis hosts the KV ring, NOT tensor
    shards: every parameter spec must stay off the model axis (replicated
    across the ring), while the batch still shards over DP."""
    import jax
    from repro.models import build_model

    cfg = get_config("llama3_2_1b")
    api = build_model(cfg)
    mesh = FakeMesh({"data": 16, "model": 16})
    rules = ShardingRules(cfg, mesh, ParallelPlan(mp_kind="context"))
    specs = rules.params_specs(jax.eval_shape(api.init, jax.random.PRNGKey(0)))
    used = {a for s in jax.tree.leaves(specs, is_leaf=lambda x: x is None)
            if s is not None for a in s if a is not None}
    assert "model" not in used, used
    # tensor plan on the same mesh does shard params over the model axis
    t_specs = ShardingRules(cfg, mesh, ParallelPlan()).params_specs(
        jax.eval_shape(api.init, jax.random.PRNGKey(0)))
    t_used = {a for s in jax.tree.leaves(t_specs, is_leaf=lambda x: x is None)
              if s is not None for a in s if a is not None}
    assert "model" in t_used, t_used


def test_cp_supported_gating():
    """The ring only engages for homogeneous dense decoders with the
    sequence divisible by the ring size; everything else falls back."""
    from repro.models.transformer import ParallelCtx, cp_supported

    def ctx(m):
        return ParallelCtx(mesh=FakeMesh({"data": 2, "model": m}),
                           batch_axes=("data",), model_axis=None,
                           context_axis="model")

    dense = get_config("llama3_2_1b").reduced()
    assert cp_supported(dense, ctx(2), t=32)
    assert cp_supported(dense, ctx(4), t=32)
    assert not cp_supported(dense, ctx(1), t=32)
    assert not cp_supported(dense, ctx(4), t=30)    # seq % ring
    assert not cp_supported(dense, None, t=32)
    import dataclasses
    capped = dataclasses.replace(dense, attn_logit_softcap=30.0)
    assert not cp_supported(capped, ctx(2), t=32)   # no capped softmax fold
    assert not cp_supported(get_config("granite_moe_1b_a400m").reduced(),
                            ctx(2), t=32)
    assert not cp_supported(get_config("rwkv6_7b").reduced(), ctx(2), t=32)


def test_parse_parallel_cp_grammar():
    cfg = get_config("llama3_2_1b")
    plan, mp, dp = parse_parallel("dp=2,cp=4", 8, cfg)
    assert plan.mp_kind == "context" and mp == 4 and dp == 2
    # --context-parallel reinterprets mp= as the ring size
    plan2, mp2, _ = parse_parallel("dp=2,mp=4", 8, cfg, context_parallel=True)
    assert plan2.mp_kind == "context" and mp2 == 4
    with pytest.raises(SystemExit, match="cp="):
        parse_parallel("cp=2,mp=2", 4, cfg)
    with pytest.raises(SystemExit, match="cp="):
        parse_parallel("cp=2,pipe=2", 4, cfg)
    # without the cp key or the flag, mp= stays tensor
    plan3, _, _ = parse_parallel("dp=2,mp=4", 8, cfg)
    assert plan3.mp_kind == "tensor"


def test_planner_context_axis():
    """The planner searches context points: cp_speedup only holds ring
    sizes that divide the sequence, the context kind appears in choices,
    and its memory model replicates params (only activations shard 1/m)."""
    cfg = get_config("llama3_2_1b")
    pl = HybridPlanner(cfg, epoch_model=default_epoch_model(cfg),
                       seq_len=4096)
    assert pl.run.cp_speedup, "no context points searched"
    assert all(4096 % m == 0 for m in pl.run.cp_speedup)
    assert all(1.0 < su <= m for m, su in pl.run.cp_speedup.items()), \
        pl.run.cp_speedup
    choices = pl.choices(64)
    kinds = {c.mp_kind for c in choices}
    assert "context" in kinds, kinds
    ctx_choice = next(c for c in choices if c.mp_kind == "context")
    assert ctx_choice.mp in pl.run.cp_speedup
    # non-divisible sequence filters the ring sizes out entirely
    pl_odd = HybridPlanner(cfg, epoch_model=default_epoch_model(cfg),
                           seq_len=4097)
    assert not pl_odd.run.cp_speedup
    assert all(c.mp_kind != "context" for c in pl_odd.choices(64))
    # archs without the dense-decoder CP path never get context points
    assert not context_mp_supported(get_config("granite_moe_1b_a400m"))
    moe = HybridPlanner(get_config("granite_moe_1b_a400m"),
                        epoch_model=default_epoch_model(
                            get_config("granite_moe_1b_a400m")))
    assert not moe.run.cp_speedup


def test_cp_ring_time_and_speedup_model():
    hw = HardwareModel()
    t2 = cp_ring_time(1 << 20, 2, hw)
    t4 = cp_ring_time(1 << 20, 4, hw)
    assert 0 < t2 < t4            # more hops, more wire time
    assert cp_ring_time(1 << 20, 1, hw) == 0.0
    cfg = get_config("llama3_2_1b")
    su2 = cp_step_speedup(cfg, 2, hw)
    su4 = cp_step_speedup(cfg, 4, hw)
    assert 1.0 < su2 < 2.0 and su2 < su4 < 4.0, (su2, su4)


def test_load_measured_overlap(tmp_path, monkeypatch):
    """Satellite 1: the planner's overlap constant comes from the measured
    BENCH_collectives.json artifact when present, clamped sane, with the
    0.6 paper-era fallback when absent or malformed."""
    good = tmp_path / "bench.json"
    good.write_text(json.dumps(
        {"tensor_mp": {"overlap_constant_proxy": 0.25}}))
    assert load_measured_overlap(str(good))["overlapped"] == 0.25
    monkeypatch.setenv("REPRO_BENCH_COLLECTIVES", str(good))
    assert load_measured_overlap()["overlapped"] == 0.25
    monkeypatch.delenv("REPRO_BENCH_COLLECTIVES")
    missing = load_measured_overlap(str(tmp_path / "missing.json"))
    assert missing == {"gspmd": 0.0, "overlapped": 0.6}
    bad = tmp_path / "bad.json"
    bad.write_text("{not json")
    assert load_measured_overlap(str(bad))["overlapped"] == 0.6
    huge = tmp_path / "huge.json"
    huge.write_text(json.dumps(
        {"tensor_mp": {"overlap_constant_proxy": 7.0}}))
    assert load_measured_overlap(str(huge))["overlapped"] == 0.95  # clamped
    # the checked-in artifact (repo root) IS the session default
    from repro.core.comm import MEASURED_OVERLAP
    assert 0.0 <= MEASURED_OVERLAP["overlapped"] <= 0.95


# ---------------------------------------------------------------------------
# multi-device equivalence (subprocesses)
# ---------------------------------------------------------------------------

def test_ring_attention_matches_reference_grid():
    """Acceptance: ring values AND custom-vjp grads == unsharded attention
    at fp32 round-off over (ring size x causal/window/bidirectional x GQA),
    with rows spread across ring devices."""
    out = _run_subprocess("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import functools
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro.parallel.jaxcompat import make_mesh, shard_map
        from repro.models.layers import attention
        from repro.parallel.context import ring_attention

        B, T, HQ, HKV, HD = 2, 32, 4, 2, 8
        key = jax.random.PRNGKey(0)
        kq, kk, kv = jax.random.split(key, 3)
        q = jax.random.normal(kq, (B, T, HQ, HD))
        k = jax.random.normal(kk, (B, T, HKV, HD))
        v = jax.random.normal(kv, (B, T, HKV, HD))

        for m in (2, 4):
            mesh = make_mesh((1, m), ("data", "model"))
            for causal, window in ((True, 0), (True, 8), (False, 0)):
                def loss_ref(q, k, v):
                    o = attention(q, k, v, causal=causal, window=window)
                    return (o.astype(jnp.float32) ** 2).sum()

                def loss_ring(q, k, v):
                    fn = functools.partial(ring_attention, axis="model",
                                           axis_size=m, causal=causal,
                                           window=window)
                    o = shard_map(fn, mesh=mesh,
                                  in_specs=(P(None, "model", None, None),) * 3,
                                  out_specs=P(None, "model", None, None))(
                                      q, k, v)
                    return (o.astype(jnp.float32) ** 2).sum()

                lr, gr = jax.value_and_grad(loss_ref, argnums=(0, 1, 2))(
                    q, k, v)
                with jax.set_mesh(mesh):
                    l, g = jax.jit(jax.value_and_grad(
                        loss_ring, argnums=(0, 1, 2)))(q, k, v)
                err_l = abs(float(l) - float(lr)) / abs(float(lr))
                err_g = max(float(jnp.abs(a - b).max())
                            for a, b in zip(g, gr))
                assert err_l < 1e-5 and err_g < 1e-4, (
                    m, causal, window, err_l, err_g)
                print("OK", m, causal, window)
    """)
    assert out.count("OK") == 6


def test_cp_train_step_matches_single_device():
    """Acceptance (tentpole pin): one optimizer step on a dp x ring mesh ==
    the single-device step — loss at fp32 round-off, params at norm-relative
    round-off — through the full make_train_step path."""
    _run_subprocess("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp
        from repro.parallel.jaxcompat import make_mesh
        from repro.configs import get_config
        from repro.models import build_model
        from repro.parallel.plan import ParallelPlan
        from repro.train.steps import (_make_pctx, init_train_state,
                                       make_train_step, shardings_for)
        from repro.optim import adamw, warmup_cosine

        cfg = get_config("llama3_2_1b").reduced()
        api = build_model(cfg, remat=False)
        opt = adamw(warmup_cosine(1e-3, 2, 10))
        key = jax.random.PRNGKey(0)
        state = init_train_state(api, opt, key)
        batch = {"tokens": jax.random.randint(key, (4, 64), 0,
                          cfg.vocab_size, dtype=jnp.int32),
                 "labels": jax.random.randint(key, (4, 64), 0,
                          cfg.vocab_size, dtype=jnp.int32)}
        ref_step = make_train_step(api, opt)
        ref_state, ref_metrics = jax.jit(ref_step)(state, batch)

        mesh = make_mesh((2, 4), ("data", "model"))
        plan = ParallelPlan(mp_kind="context")
        pctx = _make_pctx(mesh, plan, batch_shardable=True)
        assert pctx.context_axis == "model" and pctx.model_axis is None
        i32 = jnp.int32
        specs = {"tokens": jax.ShapeDtypeStruct((4, 64), i32),
                 "labels": jax.ShapeDtypeStruct((4, 64), i32)}
        s_sh, b_sh = shardings_for(api, mesh, plan, opt, specs)
        step = make_train_step(api, opt, mesh=mesh, plan=plan, pctx=pctx)
        import warnings
        with jax.set_mesh(mesh), warnings.catch_warnings():
            warnings.simplefilter("error")      # the ring MUST engage
            cp_state, cp_metrics = jax.jit(
                step, in_shardings=(s_sh, b_sh))(state, batch)
        err_l = abs(float(ref_metrics["loss"]) - float(cp_metrics["loss"]))
        assert err_l < 5e-5, err_l
        def nrel(a, b):
            d = float(jnp.linalg.norm((a - b).ravel()))
            n = float(jnp.linalg.norm(a.ravel()))
            return d / max(n, 1e-8)
        err_p = max(jax.tree.leaves(jax.tree.map(
            nrel, ref_state.params, cp_state.params)))
        assert err_p < 5e-5, err_p
        print("OK", err_l, err_p)
    """)


def test_cp_hot_path_ring_only_hlo():
    """Acceptance (HLO): growing the layer count on the CP path grows only
    collective-permutes — no per-layer all-gather of K/V (the gathered
    baseline is exactly what CP exists to avoid)."""
    _run_subprocess("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import dataclasses
        import jax, jax.numpy as jnp
        from repro.parallel.jaxcompat import make_mesh
        from repro.configs import get_config
        from repro.models import build_model
        from repro.models.transformer import ParallelCtx
        from repro.parallel.plan import ParallelPlan
        from repro.parallel.sharding import ShardingRules
        from repro.core.roofline import parse_collectives

        base = get_config("llama3_2_1b").reduced()
        mesh = make_mesh((1, 4), ("data", "model"))

        def collect(n_layers):
            cfg = dataclasses.replace(base, n_layers=n_layers)
            api = build_model(cfg, remat=False)
            key = jax.random.PRNGKey(0)
            params = api.init(key)
            batch = {"tokens": jax.random.randint(key, (2, 32), 0,
                              cfg.vocab_size, dtype=jnp.int32),
                     "labels": jax.random.randint(key, (2, 32), 0,
                              cfg.vocab_size, dtype=jnp.int32)}
            pctx = ParallelCtx(mesh=mesh, batch_axes=("data",),
                               model_axis=None, context_axis="model")
            rules = ShardingRules(cfg, mesh, ParallelPlan(mp_kind="context"))
            p_sh = rules.params_shardings(jax.eval_shape(api.init, key))
            b_sh = rules.batch_shardings(jax.eval_shape(lambda: batch))
            from repro.models import layers as L
            L.set_analysis_unroll(True)
            try:
                with jax.set_mesh(mesh):
                    comp = jax.jit(jax.grad(
                        lambda p, b: api.loss_fn(p, b, pctx)[0]),
                        in_shardings=(p_sh, b_sh)).lower(
                            params, batch).compile()
            finally:
                L.set_analysis_unroll(False)
            return parse_collectives(comp.as_text(), default_group=4)

        c2, c4 = collect(2), collect(4)
        dcp = c4.ops.get("collective-permute", 0) - \\
            c2.ops.get("collective-permute", 0)
        dag = c4.ops.get("all-gather", 0) - c2.ops.get("all-gather", 0)
        assert dcp > 0, (c2.ops, c4.ops)
        assert dag == 0, (c2.ops, c4.ops)
        print("OK", c2.ops, c4.ops)
    """)


def test_cp_fallback_warns_and_matches():
    """A sequence the ring size does not divide must fall back to GSPMD's
    gathered attention WITH the '[context]' perf-cliff warning — and the
    fallback still computes the right loss."""
    _run_subprocess("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
        import warnings
        import jax, jax.numpy as jnp
        from repro.parallel.jaxcompat import make_mesh
        from repro.configs import get_config
        from repro.models import build_model
        from repro.models.transformer import ParallelCtx
        from repro.parallel.plan import ParallelPlan
        from repro.parallel.sharding import ShardingRules

        cfg = get_config("llama3_2_1b").reduced()
        api = build_model(cfg, remat=False)
        key = jax.random.PRNGKey(0)
        params = api.init(key)
        batch = {"tokens": jax.random.randint(key, (2, 33), 0,
                          cfg.vocab_size, dtype=jnp.int32),
                 "labels": jax.random.randint(key, (2, 33), 0,
                          cfg.vocab_size, dtype=jnp.int32)}
        ref = float(api.loss_fn(params, batch)[0])
        mesh = make_mesh((1, 2), ("data", "model"))
        pctx = ParallelCtx(mesh=mesh, batch_axes=("data",),
                           model_axis=None, context_axis="model")
        rules = ShardingRules(cfg, mesh, ParallelPlan(mp_kind="context"))
        p_sh = rules.params_shardings(jax.eval_shape(api.init, key))
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            with jax.set_mesh(mesh):
                l = float(jax.jit(lambda p, b: api.loss_fn(p, b, pctx)[0],
                                  in_shardings=(p_sh, None)).lower(
                    params, batch).compile()(params, batch))
            msgs = [str(x.message) for x in w
                    if "[context]" in str(x.message)]
        assert msgs, "no [context] fallback warning for seq 33 on a 2-ring"
        assert "33" in msgs[0] and "2" in msgs[0], msgs[0]
        assert abs(l - ref) < 5e-5, (l, ref)
        print("OK", l, ref)
    """)


def test_continuous_engine_cp_prefill_matches_reference():
    """Satellite 2: the continuous engine with ``context_axis`` routes its
    prefill chunks through the sequence-sharded KV ring and still produces
    exactly the single-device tokens/logprobs."""
    out = _run_subprocess("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config
        from repro.models import build_model
        from repro.models import transformer as tf_mod
        from repro.parallel.jaxcompat import make_mesh
        from repro.serve import ContinuousEngine, Request

        cfg = get_config("llama3_2_1b").reduced()
        api = build_model(cfg, remat=False)
        params = api.init(jax.random.PRNGKey(0))
        mesh = make_mesh((1, 2), ("data", "model"))
        assert tf_mod.prefill_chunk_cp_supported(cfg, mesh, "model", 4)
        assert not tf_mod.prefill_chunk_cp_supported(cfg, mesh, "model", 3)

        reqs = lambda: [
            Request(rid=0, tokens=list(range(1, 10)), max_new_tokens=5),
            Request(rid=1, tokens=list(range(11, 16)), max_new_tokens=5)]
        ref = ContinuousEngine(api, params, n_slots=2, capacity=32,
                               prefill_chunk=4).run(reqs())
        cp = ContinuousEngine(api, params, n_slots=2, capacity=32,
                              prefill_chunk=4, mesh=mesh,
                              context_axis="model",
                              batch_axes=("data",)).run(reqs())
        for a, b in zip(ref, cp):
            assert a.tokens == b.tokens, (a.tokens, b.tokens)
            np.testing.assert_allclose(a.logprobs, b.logprobs,
                                       rtol=2e-4, atol=2e-4)
        print("CP_OK")
    """)
    assert "CP_OK" in out
