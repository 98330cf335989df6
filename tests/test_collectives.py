"""Overlap-scheduled collective subsystem (ISSUE 5): chunked collective-matmul
ring primitives and their custom-vjp backward vs plain AD, the overlapped
transformer/LSTM tensor-MP paths vs the GSPMD reference at fp32 round-off
over the (chunks x mesh x arch) grid, the PR 2-style HLO assertion that the
overlapped matmul hot path carries no monolithic all-gather/all-reduce, and
the bucketed DP reduce-scatter gradient sync (bit-equal params, per-bucket
collective split in the compiled HLO)."""
import os
import subprocess
import sys
import textwrap
import warnings

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.models import build_model
from repro.parallel.collectives import grad_bucket_sizes
from repro.parallel.plan import ParallelPlan
from repro.parallel.sharding import ShardingRules

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run_subprocess(code: str):
    env = dict(os.environ, PYTHONPATH=SRC, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=env, timeout=900)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-3000:]}"
    return r.stdout


# ---------------------------------------------------------------------------
# pure (no-device) units
# ---------------------------------------------------------------------------

def test_grad_bucket_sizes_packing():
    """Reverse-traversal greedy packing: every bucket <= target unless a
    single oversized leaf owns it, all leaves covered exactly once."""
    grads = {"a": jnp.zeros((100,)), "b": jnp.zeros((10,)),
             "c": jnp.zeros((200,)), "d": jnp.zeros((5,))}
    sizes = grad_bucket_sizes(grads, bucket_bytes=480)  # 120 floats
    assert sum(sizes) == 4
    # reverse flatten order: d(5), c(200), b(10), a(100) — c overflows alone
    assert sizes == [1, 1, 2]
    # one giant bucket swallows everything
    assert grad_bucket_sizes(grads, bucket_bytes=1e9) == [4]
    # tiny target: one leaf per bucket
    assert grad_bucket_sizes(grads, bucket_bytes=1) == [1, 1, 1, 1]


def test_plan_comm_runtime_validation():
    assert ParallelPlan(comm_runtime="overlapped").comm_runtime == "overlapped"
    with pytest.raises(ValueError, match="comm runtime"):
        ParallelPlan(comm_runtime="nope")
    with pytest.raises(ValueError, match="comm_chunks"):
        ParallelPlan(comm_chunks=0)
    mesh_shape = {"data": 2, "model": 2}

    class FakeMesh:
        shape = mesh_shape
        axis_names = ("data", "model")

    desc = ParallelPlan(comm_runtime="overlapped",
                        comm_chunks=2).describe(FakeMesh())
    assert "overlapped comm c=2" in desc


def test_sharding_fallback_warns_once_per_rule():
    """ISSUE 5 satellite: the silent replication fallback on non-divisible
    dims (smollm's 15 heads on a 16-way axis) must emit a once-per-rule
    warning naming the param path and dim."""

    class FakeMesh:
        def __init__(self, shape):
            self.shape = shape
            self.axis_names = tuple(shape)

    cfg = get_config("smollm_360m")
    api = build_model(cfg)
    params_shape = jax.eval_shape(api.init, jax.random.PRNGKey(0))
    rules = ShardingRules(cfg, FakeMesh({"data": 16, "model": 16}),
                          ParallelPlan())
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        rules.params_specs(params_shape)
        msgs = [str(x.message) for x in w if "[sharding]" in str(x.message)]
    assert msgs, "no fallback warning for smollm's 15 heads on 16-way MP"
    assert any("wq" in m and "15" in m and "16-way" in m for m in msgs), msgs
    # once per rule: re-walking the same tree must not re-warn
    with warnings.catch_warnings(record=True) as w2:
        warnings.simplefilter("always")
        rules.params_specs(params_shape)
        again = [str(x.message) for x in w2 if "[sharding]" in str(x.message)]
    assert not again, again
    # a divisible arch stays silent
    cfg_ok = get_config("llama3_2_1b")
    api_ok = build_model(cfg_ok)
    rules_ok = ShardingRules(cfg_ok, FakeMesh({"data": 16, "model": 16}),
                             ParallelPlan())
    with warnings.catch_warnings(record=True) as w3:
        warnings.simplefilter("always")
        rules_ok.params_specs(jax.eval_shape(api_ok.init,
                                             jax.random.PRNGKey(0)))
        bad = [str(x.message) for x in w3 if "[sharding]" in str(x.message)
               and "head" not in str(x.message)]
    assert not bad, bad


def test_overlapped_supported_gating():
    """The overlapped block only engages for homogeneous dense decoders with
    divisible heads/ffn/seq; everything else must fall back to GSPMD."""
    from repro.models.transformer import ParallelCtx, overlapped_supported

    class FakeMesh:
        def __init__(self, m):
            self.shape = {"data": 2, "model": m}

    def ctx(m, rt="overlapped", chunks=1):
        return ParallelCtx(mesh=FakeMesh(m), batch_axes=("data",),
                           model_axis="model", comm_runtime=rt,
                           comm_chunks=chunks)

    dense = get_config("llama3_2_1b").reduced()    # 4 heads, ff 512
    assert overlapped_supported(dense, ctx(2), t=32)
    assert overlapped_supported(dense, ctx(4), t=32)
    assert not overlapped_supported(dense, ctx(4, rt="gspmd"), t=32)
    assert not overlapped_supported(dense, ctx(1), t=32)
    assert not overlapped_supported(dense, ctx(4), t=30)   # seq % m
    assert not overlapped_supported(dense, ctx(8), t=32)   # heads % m
    assert not overlapped_supported(dense, ctx(4, chunks=3), t=32)
    assert not overlapped_supported(dense, None, t=32)
    moe = get_config("granite_moe_1b_a400m").reduced()
    assert not overlapped_supported(moe, ctx(2), t=32)
    rwkv = get_config("rwkv6_7b").reduced()
    assert not overlapped_supported(rwkv, ctx(2), t=32)


# ---------------------------------------------------------------------------
# multi-device equivalence (subprocesses)
# ---------------------------------------------------------------------------

def test_collective_matmul_primitives_match_reference():
    """all_gather_matmul / matmul_reduce_scatter forward AND custom-vjp
    backward vs plain jnp reference + AD, over the chunk sweep."""
    out = _run_subprocess("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro.parallel.jaxcompat import make_mesh, shard_map
        from repro.parallel.collectives import (all_gather_matmul,
                                                matmul_reduce_scatter)

        m = 4
        mesh = make_mesh((1, m), ("data", "model"))
        key = jax.random.PRNGKey(0)
        B, T, D, F = 2, 16, 6, 12
        x = jax.random.normal(key, (B, T, D))
        w = jax.random.normal(jax.random.PRNGKey(1), (D, F)) * 0.3
        w2 = jax.random.normal(jax.random.PRNGKey(2), (F, D)) * 0.3

        def ref(x, w, w2):
            return ((jnp.tanh(x @ w) @ w2) ** 2).sum()

        lr, gr = jax.value_and_grad(ref, argnums=(0, 1, 2))(x, w, w2)
        for chunks in (1, 2, 4):
            def f(x, w, w2):
                def local(xl, wl, w2l):
                    h = all_gather_matmul(xl, wl, axis="model", axis_size=m,
                                          chunks=chunks)
                    return matmul_reduce_scatter(jnp.tanh(h), w2l,
                                                 axis="model", axis_size=m,
                                                 chunks=chunks)
                y = shard_map(local, mesh=mesh,
                              in_specs=(P(None, "model", None),
                                        P(None, "model"), P("model", None)),
                              out_specs=P(None, "model", None))(x, w, w2)
                return (y ** 2).sum()

            with jax.set_mesh(mesh):
                l, g = jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2)))(
                    x, w, w2)
            assert abs(float(l) - float(lr)) < 1e-4, (chunks, float(l),
                                                      float(lr))
            for a, b in zip(g, gr):
                err = float(jnp.abs(a - b).max())
                assert err < 1e-4, (chunks, err)
            print("OK", chunks)
    """)
    assert out.count("OK") == 3


@pytest.mark.parametrize("arch", ["llama3_2_1b", "stablelm_12b"])
def test_overlapped_transformer_matches_gspmd_grid(arch):
    """Acceptance: overlapped collective-matmul == GSPMD loss AND grads at
    fp32 round-off over the (chunks x mesh) grid, plus a non-divisible-KV
    variant exercising the replicated-KV slice path."""
    out = _run_subprocess(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import dataclasses
        import jax, jax.numpy as jnp
        from repro.parallel.jaxcompat import make_mesh
        from repro.configs import get_config
        from repro.models import build_model
        from repro.models.transformer import ParallelCtx
        from repro.parallel.plan import ParallelPlan
        from repro.parallel.sharding import ShardingRules

        cfgs = [get_config("{arch}").reduced()]
        # non-divisible KV: 4 q heads, 1 kv head on mp=2/4 (replicated KV)
        c = cfgs[0]
        if not c.is_moe:
            cfgs.append(dataclasses.replace(c, n_kv_heads=1))
        for cfg in cfgs:
            api = build_model(cfg, remat=False)
            key = jax.random.PRNGKey(0)
            params = api.init(key)
            batch = {{"tokens": jax.random.randint(key, (8, 32), 0,
                                 cfg.vocab_size, dtype=jnp.int32),
                      "labels": jax.random.randint(key, (8, 32), 0,
                                 cfg.vocab_size, dtype=jnp.int32)}}
            ref_l, ref_g = jax.value_and_grad(
                lambda p: api.loss_fn(p, batch)[0])(params)
            for dp, mp in ((2, 4), (4, 2)):
                for chunks in (1, 2):
                    mesh = make_mesh((dp, mp), ("data", "model"))
                    pctx = ParallelCtx(mesh=mesh, batch_axes=("data",),
                                       model_axis="model",
                                       comm_runtime="overlapped",
                                       comm_chunks=chunks)
                    rules = ShardingRules(cfg, mesh, ParallelPlan())
                    p_sh = rules.params_shardings(
                        jax.eval_shape(api.init, key))
                    b_sh = rules.batch_shardings(
                        jax.eval_shape(lambda: batch))
                    with jax.set_mesh(mesh):
                        l, g = jax.jit(jax.value_and_grad(
                            lambda p, b: api.loss_fn(p, b, pctx)[0]),
                            in_shardings=(p_sh, b_sh))(params, batch)
                    err_l = abs(float(ref_l) - float(l))
                    err_g = max(jax.tree.leaves(jax.tree.map(
                        lambda a, b: float(jnp.abs(a - b).max()),
                        ref_g, g)))
                    assert err_l < 5e-5 and err_g < 5e-4, (
                        cfg.n_kv_heads, dp, mp, chunks, err_l, err_g)
                    print("OK", cfg.n_kv_heads, dp, mp, chunks)
    """)
    assert out.count("OK") >= 8


def test_overlapped_hot_path_has_no_monolithic_collectives():
    """Acceptance (PR 2-style HLO assertion): growing the layer count must
    grow only the chunk-sized collective-permutes — the per-layer matmul hot
    path contains NO all-gather / all-reduce (the embed psum, pre-head
    gather, and CE stats are per-step constants, not per-layer), while the
    GSPMD lane adds monolithic all-reduces with every layer."""
    _run_subprocess("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
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
        mesh = make_mesh((2, 4), ("data", "model"))

        def collect(n_layers, rt):
            cfg = dataclasses.replace(base, n_layers=n_layers)
            api = build_model(cfg, remat=False)
            key = jax.random.PRNGKey(0)
            params = api.init(key)
            batch = {"tokens": jax.random.randint(key, (8, 32), 0,
                              cfg.vocab_size, dtype=jnp.int32),
                     "labels": jax.random.randint(key, (8, 32), 0,
                              cfg.vocab_size, dtype=jnp.int32)}
            pctx = ParallelCtx(mesh=mesh, batch_axes=("data",),
                               model_axis="model", comm_runtime=rt,
                               comm_chunks=1)
            rules = ShardingRules(cfg, mesh, ParallelPlan())
            p_sh = rules.params_shardings(jax.eval_shape(api.init, key))
            b_sh = rules.batch_shardings(jax.eval_shape(lambda: batch))
            # unroll the layer scan so per-layer collectives are visible to
            # the parser (while bodies count once otherwise)
            from repro.models import layers as L
            L.set_analysis_unroll(True)
            try:
                with jax.set_mesh(mesh):
                    comp = jax.jit(
                        lambda p, b: api.loss_fn(p, b, pctx)[0],
                        in_shardings=(p_sh, b_sh)).lower(
                            params, batch).compile()
            finally:
                L.set_analysis_unroll(False)
            return parse_collectives(comp.as_text(), default_group=4)

        o2, o4 = collect(2, "overlapped"), collect(4, "overlapped")
        g2, g4 = collect(2, "gspmd"), collect(4, "gspmd")
        dcp = o4.ops.get("collective-permute", 0) - \
            o2.ops.get("collective-permute", 0)
        dag = o4.ops.get("all-gather", 0) - o2.ops.get("all-gather", 0)
        dar = o4.ops.get("all-reduce", 0) - o2.ops.get("all-reduce", 0)
        assert dcp > 0, (o2.ops, o4.ops)
        assert dag == 0 and dar == 0, (o2.ops, o4.ops)
        # the GSPMD lane pays monolithic all-reduces per layer
        g_dar = g4.ops.get("all-reduce", 0) - g2.ops.get("all-reduce", 0)
        assert g_dar > 0, (g2.ops, g4.ops)
        print("OK", o2.ops, o4.ops, g_dar)
    """)


def test_overlapped_biglstm_matches_gspmd():
    """The overlapped tensor-MP LSTM (gate-major collective-matmul input
    projection) == the plain forward, loss and grads, across meshes/chunks."""
    out = _run_subprocess("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp
        from repro.parallel.jaxcompat import make_mesh
        from repro.configs import get_config
        from repro.models import build_model
        from repro.models.transformer import ParallelCtx
        from repro.parallel.plan import ParallelPlan
        from repro.parallel.sharding import ShardingRules

        cfg = get_config("biglstm").reduced()
        api = build_model(cfg)
        key = jax.random.PRNGKey(0)
        params = api.init(key)
        batch = {"tokens": jax.random.randint(key, (8, 16), 0,
                          cfg.vocab_size, dtype=jnp.int32),
                 "labels": jax.random.randint(key, (8, 16), 0,
                          cfg.vocab_size, dtype=jnp.int32)}
        ref_l, ref_g = jax.value_and_grad(
            lambda p: api.loss_fn(p, batch)[0])(params)
        for dp, mp in ((2, 4), (1, 2)):
            for chunks in (1, 2):
                mesh = make_mesh((dp, mp), ("data", "model"))
                pctx = ParallelCtx(mesh=mesh, batch_axes=("data",),
                                   model_axis="model",
                                   comm_runtime="overlapped",
                                   comm_chunks=chunks)
                rules = ShardingRules(cfg, mesh, ParallelPlan())
                p_sh = rules.params_shardings(jax.eval_shape(api.init, key))
                b_sh = rules.batch_shardings(jax.eval_shape(lambda: batch))
                with jax.set_mesh(mesh):
                    l, g = jax.jit(jax.value_and_grad(
                        lambda p, b: api.loss_fn(p, b, pctx)[0]),
                        in_shardings=(p_sh, b_sh))(params, batch)
                err_l = abs(float(ref_l) - float(l))
                err_g = max(jax.tree.leaves(jax.tree.map(
                    lambda a, b: float(jnp.abs(a - b).max()), ref_g, g)))
                assert err_l < 5e-5 and err_g < 1e-3, (dp, mp, chunks,
                                                      err_l, err_g)
                print("OK", dp, mp, chunks)
    """)
    assert out.count("OK") == 4


def test_bucketed_dp_train_step_bit_equal_and_split():
    """Acceptance (DP half): the bucketed reduce-scatter grad sync produces
    BIT-EQUAL updated params to GSPMD's fused all-reduce, and the compiled
    step contains the per-bucket reduce-scatter/all-gather split with no
    gradient-sized all-reduce."""
    _run_subprocess("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.parallel.jaxcompat import make_mesh
        from repro.configs import get_config
        from repro.models import build_model
        from repro.parallel.plan import ParallelPlan
        from repro.train.steps import init_train_state, make_train_step
        from repro.optim import adamw, warmup_cosine
        from repro.core.roofline import parse_collectives

        cfg = get_config("llama3_2_1b").reduced()
        api = build_model(cfg, remat=False)
        opt = adamw(warmup_cosine(1e-3, 2, 10))
        key = jax.random.PRNGKey(0)
        state = init_train_state(api, opt, key)
        batch = {"tokens": jax.random.randint(key, (8, 16), 0,
                          cfg.vocab_size, dtype=jnp.int32),
                 "labels": jax.random.randint(key, (8, 16), 0,
                          cfg.vocab_size, dtype=jnp.int32)}
        mesh = make_mesh((4, 1), ("data", "model"))
        b_sh = {k: NamedSharding(mesh, P("data", None)) for k in batch}
        s_sh = jax.tree.map(lambda _: NamedSharding(mesh, P()), state)
        outs, comps = {}, {}
        for rt in ("gspmd", "overlapped"):
            plan = ParallelPlan(model_axis=None, comm_runtime=rt)
            step = make_train_step(api, opt, mesh=mesh, plan=plan,
                                   bucket_bytes=256 * 1024)
            with jax.set_mesh(mesh):
                j = jax.jit(step, in_shardings=(s_sh, b_sh))
                comps[rt] = j.lower(state, batch).compile()
                outs[rt] = j(state, batch)
        diff = max(jax.tree.leaves(jax.tree.map(
            lambda a, b: float(jnp.abs(a - b).max()),
            outs["gspmd"][0].params, outs["overlapped"][0].params)))
        assert diff == 0.0, diff
        ov = parse_collectives(comps["overlapped"].as_text(),
                               default_group=4)
        assert ov.ops.get("reduce-scatter", 0) >= 2, ov.ops   # > 1 bucket
        assert ov.ops.get("all-gather", 0) >= 2, ov.ops
        # no gradient-sized all-reduce: any surviving AR is a scalar metric
        from repro.core.roofline import _tensor_bytes
        big_ar = [ln for ln in ov.lines if "all-reduce" in ln
                  and _tensor_bytes(ln) > 1024]
        assert not big_ar, big_ar
        gs = parse_collectives(comps["gspmd"].as_text(), default_group=4)
        assert gs.ops.get("all-reduce", 0) > ov.ops.get("all-reduce", 0)
        print("OK", diff, ov.ops)
    """)


def test_overlapped_train_step_tensor_mp():
    """End-to-end make_train_step on a dp x mp mesh with the overlapped comm
    runtime: one optimizer step must match the GSPMD comm runtime's at fp32
    round-off (same plan, same mesh, only the collective runtime differs)."""
    _run_subprocess("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
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
        batch = {"tokens": jax.random.randint(key, (8, 32), 0,
                          cfg.vocab_size, dtype=jnp.int32),
                 "labels": jax.random.randint(key, (8, 32), 0,
                          cfg.vocab_size, dtype=jnp.int32)}
        mesh = make_mesh((2, 2), ("data", "model"))
        i32 = jnp.int32
        specs = {"tokens": jax.ShapeDtypeStruct((8, 32), i32),
                 "labels": jax.ShapeDtypeStruct((8, 32), i32)}
        outs = {}
        for rt in ("gspmd", "overlapped"):
            plan = ParallelPlan(comm_runtime=rt, comm_chunks=2)
            pctx = _make_pctx(mesh, plan, batch_shardable=True)
            s_sh, b_sh = shardings_for(api, mesh, plan, opt, specs)
            step = make_train_step(api, opt, mesh=mesh, plan=plan, pctx=pctx)
            with jax.set_mesh(mesh):
                outs[rt] = jax.jit(step, in_shardings=(s_sh, b_sh))(
                    state, batch)
        diff = max(jax.tree.leaves(jax.tree.map(
            lambda a, b: float(jnp.abs(a - b).max()),
            outs["gspmd"][0].params, outs["overlapped"][0].params)))
        l0 = float(outs["gspmd"][1]["loss"])
        l1 = float(outs["overlapped"][1]["loss"])
        assert abs(l0 - l1) < 5e-5, (l0, l1)
        assert diff < 5e-4, diff
        print("OK", diff)
    """)


@pytest.mark.slow
def test_collective_overlap_sweep_smoke():
    """The benchmark's smoke lane runs end to end and its internal HLO/wire
    assertions (ring-model wire bytes, no monolithic collectives) hold."""
    out = _run_subprocess("""
        import sys
        sys.argv = ["bench", "--smoke", "--out",
                    "/tmp/BENCH_collectives_test.json"]
        from benchmarks.collective_overlap_sweep import main
        rc = main(["--smoke", "--out", "/tmp/BENCH_collectives_test.json"])
        assert rc == 0
        import json
        rec = json.load(open("/tmp/BENCH_collectives_test.json"))
        assert rec["tensor_mp"]["points"], rec
        assert "planner_crossover" in rec
        print("OK")
    """)
    assert "OK" in out
