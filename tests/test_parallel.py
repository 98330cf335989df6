"""Parallel runtime: sharding-rule invariants (pure), plus multi-device
equivalence properties (sharded loss == single-device loss; pipeline ==
sequential) run in subprocesses so only they see forced host devices."""
import os
import subprocess
import sys
import textwrap

import jax
import pytest

from repro.configs import ARCH_IDS, get_config
from repro.models import build_model
from repro.parallel.plan import ParallelPlan
from repro.parallel.sharding import ShardingRules

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


class FakeMesh:
    def __init__(self, shape):
        self.shape = shape
        self.axis_names = tuple(shape)

    @property
    def size(self):
        n = 1
        for v in self.shape.values():
            n *= v
        return n


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_sharding_specs_divisibility(arch):
    """Every assigned spec must divide its dim by the mesh axis product —
    the invariant that makes the production jit accept the shardings."""
    cfg = get_config(arch)
    mesh = FakeMesh({"data": 16, "model": 16})
    rules = ShardingRules(cfg, mesh, ParallelPlan(fsdp_axes=("data",)))
    api = build_model(cfg)
    params_shape = jax.eval_shape(api.init, jax.random.PRNGKey(0))
    specs = rules.params_specs(params_shape)

    flat_p, _ = jax.tree_util.tree_flatten_with_path(params_shape)
    flat_s = jax.tree.leaves(
        specs, is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))
    assert len(flat_p) == len(flat_s)
    sizes = {"data": 16, "model": 16}
    n_model_sharded = 0
    for (path, leaf), spec in zip(flat_p, flat_s):
        assert len(spec) <= len(leaf.shape), (path, spec, leaf.shape)
        for dim, entry in zip(leaf.shape, tuple(spec)):
            if entry is None:
                continue
            axes = entry if isinstance(entry, tuple) else (entry,)
            prod = 1
            for a in axes:
                prod *= sizes[a]
            assert dim % prod == 0, (jax.tree_util.keystr(path), leaf.shape,
                                     spec)
            if "model" in axes:
                n_model_sharded += 1
    assert n_model_sharded > 0, f"{arch}: nothing model-sharded"


@pytest.mark.parametrize("arch", ["kimi_k2_1t_a32b", "nemotron_4_340b"])
def test_giant_archs_fit_when_fully_sharded(arch):
    """Param bytes per chip under the optimized (fsdp) plan must be < HBM."""
    cfg = get_config(arch)
    mesh = FakeMesh({"pod": 2, "data": 16, "model": 16})
    rules = ShardingRules(cfg, mesh,
                          ParallelPlan(dp_axes=("pod", "data"),
                                       fsdp_axes=("pod", "data")))
    api = build_model(cfg)
    params_shape = jax.eval_shape(api.init, jax.random.PRNGKey(0))
    specs = rules.params_specs(params_shape)
    flat_p, _ = jax.tree_util.tree_flatten_with_path(params_shape)
    flat_s = jax.tree.leaves(
        specs, is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))
    sizes = {"pod": 2, "data": 16, "model": 16}
    per_chip = 0
    for (path, leaf), spec in zip(flat_p, flat_s):
        n = leaf.dtype.itemsize
        for d in leaf.shape:
            n *= d
        div = 1
        for entry in tuple(spec):
            if entry is None:
                continue
            for a in (entry if isinstance(entry, tuple) else (entry,)):
                div *= sizes[a]
        per_chip += n / div
    # f32 master params sharded over 512 chips
    assert per_chip < 16e9, f"{arch}: {per_chip/2**30:.1f} GiB/chip"


def _run_subprocess(code: str):
    # pin the subprocess to CPU: the container ships a libtpu that otherwise
    # burns ~8 minutes probing for TPU metadata before falling back, and the
    # forced host-device count only applies to the cpu platform anyway
    env = dict(os.environ, PYTHONPATH=SRC, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=env, timeout=900)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-3000:]}"
    return r.stdout


def test_sharded_loss_equals_single_device():
    """4-way DP x 2-way MP loss == single-device loss (fp32, same batch)."""
    _run_subprocess("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
        from repro.parallel.jaxcompat import make_mesh
        from repro.configs import get_config
        from repro.models import build_model
        from repro.parallel.plan import ParallelPlan
        from repro.parallel.sharding import ShardingRules

        cfg = get_config("llama3_2_1b").reduced()
        api = build_model(cfg, remat=False)
        key = jax.random.PRNGKey(0)
        params = api.init(key)
        batch = {"tokens": jax.random.randint(key, (8, 32), 0, cfg.vocab_size, dtype=jnp.int32),
                 "labels": jax.random.randint(key, (8, 32), 0, cfg.vocab_size, dtype=jnp.int32)}
        ref, _ = api.loss_fn(params, batch)

        mesh = make_mesh((4, 2), ("data", "model"))
        plan = ParallelPlan()
        rules = ShardingRules(cfg, mesh, plan)
        p_sh = rules.params_shardings(jax.eval_shape(api.init, key))
        b_sh = rules.batch_shardings(jax.eval_shape(lambda: batch))
        with jax.set_mesh(mesh):
            f = jax.jit(lambda p, b: api.loss_fn(p, b)[0],
                        in_shardings=(p_sh, b_sh))
            sharded = f(params, batch)
        err = abs(float(ref) - float(sharded))
        assert err < 1e-4, (float(ref), float(sharded))
        print("OK", float(ref), float(sharded))
    """)


def test_moe_ep_shard_map_equals_local():
    """Expert-parallel shard_map MoE == local (mp=1) MoE."""
    _run_subprocess("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp
        from repro.parallel.jaxcompat import make_mesh
        from repro.configs import get_config
        from repro.models import build_model
        from repro.models.transformer import ParallelCtx
        from repro.parallel.plan import ParallelPlan
        from repro.parallel.sharding import ShardingRules

        cfg = get_config("granite_moe_1b_a400m").reduced()
        api = build_model(cfg, remat=False, capacity_factor=None)
        key = jax.random.PRNGKey(0)
        params = api.init(key)
        batch = {"tokens": jax.random.randint(key, (8, 16), 0, cfg.vocab_size, dtype=jnp.int32),
                 "labels": jax.random.randint(key, (8, 16), 0, cfg.vocab_size, dtype=jnp.int32)}
        ref, _ = api.loss_fn(params, batch)

        mesh = make_mesh((2, 4), ("data", "model"))
        pctx = ParallelCtx(mesh=mesh, batch_axes=("data",), model_axis="model")
        rules = ShardingRules(cfg, mesh, ParallelPlan())
        p_sh = rules.params_shardings(jax.eval_shape(api.init, key))
        b_sh = rules.batch_shardings(jax.eval_shape(lambda: batch))
        with jax.set_mesh(mesh):
            f = jax.jit(lambda p, b: api.loss_fn(p, b, pctx)[0],
                        in_shardings=(p_sh, b_sh))
            ep = f(params, batch)
        # tolerance covers fp32 reduction-order drift across jax versions
        # (the EP psum tree differs between shard_map implementations)
        err = abs(float(ref) - float(ep))
        assert err < 3e-3, (float(ref), float(ep))
        print("OK", float(ref), float(ep))
    """)


@pytest.mark.parametrize("stages", [2, 4])
def test_pipeline_schedules_equal_sequential(stages):
    """Bit-exactness of the schedule-generic runtime vs sequential stacking
    (fp32) over the {gpipe, 1f1b, interleaved} x stages x {2, 4, 8} micro
    grid (ISSUE 2 satellite): every schedule must produce identical outputs
    — they reorder/replace the placement, never the math."""
    out = _run_subprocess(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp
        from repro.parallel.jaxcompat import make_mesh
        from repro.parallel.pipeline import pipeline_apply, stack_to_stages

        stages = {stages}
        mesh = make_mesh((1, stages), ("data", "model"))
        L, d, B = 8, 16, 16
        key = jax.random.PRNGKey(0)
        params = {{"w": jax.random.normal(key, (L, d, d)) * 0.1,
                   "b": jnp.zeros((L, d))}}
        x = jax.random.normal(jax.random.PRNGKey(1), (B, d))

        def layer(p, x):
            return jnp.tanh(x @ p["w"] + p["b"])

        def stage_fn(sp, x):
            y, _ = jax.lax.scan(lambda x, lp: (layer(lp, x), None), x, sp)
            return y

        y_ref, _ = jax.lax.scan(lambda x, lp: (layer(lp, x), None), x, params)
        with jax.set_mesh(mesh):
            for sched in ("gpipe", "1f1b", "interleaved"):
                v = 2 if sched == "interleaved" else 1
                for n_micro in (2, 4, 8):
                    y = pipeline_apply(mesh, "model", stage_fn,
                                       stack_to_stages(params, stages, v), x,
                                       n_micro=n_micro, schedule=sched,
                                       virtual_stages=v)
                    err = float(jnp.abs(y - y_ref).max())
                    assert err < 1e-6, (sched, stages, n_micro, err)
                    print("OK", sched, stages, n_micro, err)
    """)
    assert out.count("OK") == 9


def test_pipeline_dp_stages_grads_equal_pure_dp():
    """dp x stages execution (the ISSUE 2 tentpole wiring): a pipeline plan
    on a 2x2 host mesh — batch sharded over "data", stages over "model" —
    must reproduce pure-DP loss AND parameter gradients (fp32) exactly."""
    _run_subprocess("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.parallel.jaxcompat import make_mesh
        from repro.configs import get_config
        from repro.models.api import build_model

        cfg = get_config("biglstm").reduced()
        api = build_model(cfg)
        key = jax.random.PRNGKey(0)
        params = api.init(key)
        batch = {"tokens": jax.random.randint(key, (8, 16), 0, cfg.vocab_size, dtype=jnp.int32),
                 "labels": jax.random.randint(key, (8, 16), 0, cfg.vocab_size, dtype=jnp.int32)}
        mesh = make_mesh((2, 2), ("data", "model"))
        b_sh = {k: NamedSharding(mesh, P("data", None)) for k in batch}
        p_sh = jax.tree.map(lambda _: NamedSharding(mesh, P()), params)

        def dp_loss(p, b):
            return api.loss_fn(p, b)[0]

        def pipe_loss(p, b):
            return api.pipeline_loss_fn(p, b, mesh=mesh, axis="model",
                                        n_micro=2, schedule="1f1b",
                                        batch_axes=("data",))[0]

        with jax.set_mesh(mesh):
            ref_l, ref_g = jax.jit(jax.value_and_grad(dp_loss),
                                   in_shardings=(p_sh, b_sh))(params, batch)
            out_l, out_g = jax.jit(jax.value_and_grad(pipe_loss),
                                   in_shardings=(p_sh, b_sh))(params, batch)
        err_l = abs(float(ref_l) - float(out_l))
        errs = jax.tree.map(
            lambda a, b: float(jnp.abs(a - b).max()), ref_g, out_g)
        err_g = max(jax.tree.leaves(errs))
        assert err_l < 1e-6 and err_g < 1e-6, (err_l, err_g)
        print("OK", err_l, err_g)
    """)


def test_pipeline_output_broadcast_bytes():
    """ISSUE 2 satellite: the old runtime psum'd the FULL outs buffer over
    every stage each step; the new single-source slice must compile to
    strictly fewer collective wire bytes (and no all-reduce of outs-sized
    operands at all)."""
    _run_subprocess("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import jax, jax.numpy as jnp
        from repro.core.roofline import parse_collectives
        from repro.parallel.jaxcompat import make_mesh
        from repro.parallel.pipeline import pipeline_apply, stack_to_stages

        stages, L, d, B, n_micro = 4, 8, 32, 16, 4
        mesh = make_mesh((1, stages), ("data", "model"))
        key = jax.random.PRNGKey(0)
        params = {"w": jax.random.normal(key, (L, d, d)) * 0.1,
                  "b": jnp.zeros((L, d))}
        x = jax.random.normal(jax.random.PRNGKey(1), (B, d))

        def stage_fn(sp, x):
            y, _ = jax.lax.scan(
                lambda x, lp: (jnp.tanh(x @ lp["w"] + lp["b"]), None), x, sp)
            return y

        stacked = stack_to_stages(params, stages)

        def run(replicate_out):
            def f(p, x):
                return pipeline_apply(mesh, "model", stage_fn, p, x,
                                      n_micro=n_micro,
                                      replicate_out=replicate_out).sum()
            with jax.set_mesh(mesh):
                comp = jax.jit(f).lower(stacked, x).compile()
            return parse_collectives(comp.as_text(), default_group=stages)

        new, old = run(False), run(True)
        outs_bytes = B * d * 4
        # the legacy path all-reduces the full (n_micro, mb, d) buffer
        assert old.ops.get("all-reduce", 0) >= 1, old.ops
        assert old.wire_bytes >= outs_bytes, (old.wire_bytes, outs_bytes)
        saved = old.wire_bytes - new.wire_bytes
        assert saved > 0, (old.wire_bytes, new.wire_bytes)
        print("OK saved", saved, "of", old.wire_bytes)
    """)


def test_dryrun_pipeline_lane_stage_sharding():
    """The dryrun ``--plan pipeline`` lane (ISSUE 2 satellite): stage-dim
    sharding rules must put the stacked layer dim of every decoder-stack
    leaf on the model axis (per-stage parameter residency) and keep
    tensor-MP dims unsharded, and the lane itself must lower+compile."""
    import jax as _jax
    from repro.launch.dryrun import make_plan
    cfg = get_config("llama3_2_1b")
    mesh = FakeMesh({"data": 16, "model": 16})
    plan = ParallelPlan(dp_axes=("data",), model_axis="model",
                        mp_kind="pipeline", microbatches=4)
    rules = ShardingRules(cfg, mesh, plan)
    api = build_model(cfg)
    params_shape = _jax.eval_shape(api.init, _jax.random.PRNGKey(0))
    specs = rules.params_specs(params_shape)
    flat_p, _ = _jax.tree_util.tree_flatten_with_path(params_shape)
    flat_s = _jax.tree.leaves(
        specs, is_leaf=lambda x: isinstance(x, _jax.sharding.PartitionSpec))
    n_stage_sharded = 0
    for (path, leaf), spec in zip(flat_p, flat_s):
        keys = [getattr(p, "key", None) for p in path]
        if "layers" in keys:
            assert tuple(spec)[0] == "model", (path, spec)   # stage residency
            assert "model" not in tuple(spec)[1:], (path, spec)
            n_stage_sharded += 1
        else:
            assert "model" not in tuple(spec), (path, spec)  # replicated
    assert n_stage_sharded > 0


@pytest.mark.slow
def test_dryrun_pipeline_lane_compiles():
    """End-to-end pipeline dry-run lane on the production 16x16 mesh."""
    out = _run_subprocess("""
        import sys
        sys.argv = ["dryrun", "--arch", "llama3_2_1b", "--shape", "train_4k",
                    "--mesh", "single", "--plan", "pipeline",
                    "--sched", "1f1b", "--out", "/tmp/dryrun_pipe_test",
                    "--skip-analysis"]
        import shutil
        shutil.rmtree("/tmp/dryrun_pipe_test", ignore_errors=True)
        from repro.launch.dryrun import main
        rc = main()
        assert rc == 0
    """)
    assert "1 ok, 0 failed" in out


def test_pipeline_apply_rejects_chunk_layout_mismatch():
    """A stage-params layout stacked for a different chunk count than the
    schedule's (normalized) v must raise, not silently apply the wrong
    layers — e.g. ``sched=gpipe`` with a v=2 stack would only ever run
    chunk 0."""
    import jax.numpy as jnp
    from repro.parallel.jaxcompat import make_mesh
    from repro.parallel.pipeline import pipeline_apply, stack_to_stages

    mesh = make_mesh((1, 1), ("data", "model"))
    params = {"w": jnp.zeros((2, 3, 3))}
    x = jnp.zeros((4, 3))
    with pytest.raises(ValueError, match="stack_to_stages"):
        pipeline_apply(mesh, "model", lambda p, x: x,
                       stack_to_stages(params, 1, 2), x, n_micro=2,
                       schedule="gpipe")


def test_biglstm_pipeline_loss_equals_sequential():
    """The arch-level pipeline runtime (the one ``--parallel auto`` executes
    for biglstm) matches the plain stacked forward bit-for-bit in fp32."""
    _run_subprocess("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp
        from repro.parallel.jaxcompat import make_mesh
        from repro.configs import get_config
        from repro.models.api import build_model

        cfg = get_config("biglstm").reduced()
        api = build_model(cfg)
        key = jax.random.PRNGKey(0)
        params = api.init(key)
        batch = {"tokens": jax.random.randint(key, (8, 16), 0, cfg.vocab_size, dtype=jnp.int32),
                 "labels": jax.random.randint(key, (8, 16), 0, cfg.vocab_size, dtype=jnp.int32)}
        ref, _ = api.loss_fn(params, batch)
        mesh = make_mesh((1, 2), ("data", "model"))
        with jax.set_mesh(mesh):
            out, _ = jax.jit(lambda p, b: api.pipeline_loss_fn(
                p, b, mesh=mesh, axis="model", n_micro=4))(params, batch)
        err = abs(float(ref) - float(out))
        assert err < 1e-6, (float(ref), float(out))
        print("OK", err)
    """)


def test_dryrun_entrypoint_single_combo():
    """The deliverable-e entrypoint works end to end for one combo on the
    production 16x16 mesh (512 forced host devices)."""
    out = _run_subprocess("""
        import sys
        sys.argv = ["dryrun", "--arch", "llama3_2_1b", "--shape", "decode_32k",
                    "--mesh", "single", "--out", "/tmp/dryrun_test",
                    "--skip-analysis"]
        import shutil
        shutil.rmtree("/tmp/dryrun_test", ignore_errors=True)
        from repro.launch.dryrun import main
        rc = main()
        assert rc == 0
    """)
    assert "1 ok, 0 failed" in out


def test_plan_describe():
    mesh = FakeMesh({"pod": 2, "data": 16, "model": 16})
    p = ParallelPlan(dp_axes=("pod", "data"), model_axis="model",
                     fsdp_axes=("pod", "data"), microbatches=4)
    s = p.describe(mesh)
    assert "32-way DP" in s and "16-way" in s and "fsdp" in s and "x4" in s


@pytest.mark.slow
def test_seq_sharded_flash_decode_matches_reference():
    """Flash-decode (KV cache sequence-sharded over the model axis) must
    match single-device cached decode logits (§Perf iteration B.2)."""
    _run_subprocess("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import dataclasses
        import jax, jax.numpy as jnp
        from repro.parallel.jaxcompat import make_mesh
        from repro.configs import get_config
        from repro.models import build_model
        from repro.models.transformer import ParallelCtx

        # capacity 2048 (>= 1024 threshold), divisible by mp=4
        cfg = get_config("llama3_2_1b").reduced()
        api = build_model(cfg, remat=False)
        key = jax.random.PRNGKey(0)
        params = api.init(key)
        T = 24
        tokens = jax.random.randint(key, (2, T), 0, cfg.vocab_size, dtype=jnp.int32)
        logits, cache = api.prefill(params, {"tokens": tokens[:, :T-2]}, capacity=2048)
        # reference: single-device decode
        ref_logits, ref_cache = api.decode_fn(params, cache, {"tokens": tokens[:, T-2:T-1]})
        mesh = make_mesh((2, 4), ("data", "model"))
        pctx = ParallelCtx(mesh=mesh, batch_axes=("data",), model_axis="model")
        with jax.set_mesh(mesh):
            out, new_cache = jax.jit(
                lambda p, c, b: api.decode_fn(p, c, b, pctx))(
                    params, cache, {"tokens": tokens[:, T-2:T-1]})
        err = float(jnp.abs(out - ref_logits).max())
        assert err < 1e-3, err
        # one more step to exercise the updated cache
        out2, _ = jax.jit(lambda p, c, b: api.decode_fn(p, c, b, pctx))(
            params, new_cache, {"tokens": tokens[:, T-1:T]})
        ref2, _ = api.decode_fn(params, ref_cache, {"tokens": tokens[:, T-1:T]})
        err2 = float(jnp.abs(out2 - ref2).max())
        assert err2 < 1e-3, err2
        print("OK", err, err2)
    """)


@pytest.mark.slow
def test_seq_sharded_flash_decode_windowed():
    """Windowed ring + seq-sharded cache decode must match single-device."""
    _run_subprocess("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import dataclasses
        import jax, jax.numpy as jnp
        from repro.parallel.jaxcompat import make_mesh
        from repro.configs import get_config
        from repro.models import build_model
        from repro.models.transformer import ParallelCtx

        W = 1024
        cfg = dataclasses.replace(get_config("llama3_2_1b").reduced(),
                                  sliding_window=W)
        api = build_model(cfg, remat=False)
        key = jax.random.PRNGKey(0)
        params = api.init(key)
        T = 16
        tokens = jax.random.randint(key, (2, T), 0, cfg.vocab_size, dtype=jnp.int32)
        logits, cache = api.prefill(params, {"tokens": tokens[:, :T-3]}, capacity=W)
        mesh = make_mesh((2, 4), ("data", "model"))
        pctx = ParallelCtx(mesh=mesh, batch_axes=("data",), model_axis="model")
        # reference: full teacher-forced forward (windowed)
        from repro.models import transformer as tf_mod
        ref, _ = tf_mod.forward(cfg, params, {"tokens": tokens}, mode="train",
                                remat=False)
        # NOTE: prefill produced a shift-left ring; re-layout to positional
        # ring (slot = pos % W) for the seq-sharded path
        def relayout(c):
            pos = int(c["pos"])
            out = dict(c)
            for k in ("k", "v"):
                buf = jnp.zeros_like(c[k])
                n = min(pos, W)
                src = c[k][:, :, W - n:, :, :]
                idx = (jnp.arange(pos - n, pos) % W)
                buf = buf.at[:, :, idx].set(src)
                out[k] = buf
            return out
        cache = relayout(cache)
        errs = []
        with jax.set_mesh(mesh):
            step = jax.jit(lambda p, c, b: api.decode_fn(p, c, b, pctx))
            for t in range(T-3, T):
                out, cache = step(params, cache, {"tokens": tokens[:, t:t+1]})
                errs.append(float(jnp.abs(out[:, 0] - ref[:, t]).max()))
        assert max(errs) < 1e-3, errs
        print("OK", errs)
    """)


def test_vocab_parallel_cross_entropy_matches():
    """Vocab-parallel CE (no logits gather) == plain CE (§Perf iteration D)."""
    _run_subprocess("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp
        from repro.parallel.jaxcompat import make_mesh
        from repro.models.api import cross_entropy, vocab_parallel_cross_entropy

        key = jax.random.PRNGKey(0)
        B, S, V = 4, 16, 64
        logits = jax.random.normal(key, (B, S, V)) * 3.0
        labels = jax.random.randint(key, (B, S), -1, V, dtype=jnp.int32)
        ref = cross_entropy(logits, labels, V)
        mesh = make_mesh((2, 4), ("data", "model"))
        with jax.set_mesh(mesh):
            out = jax.jit(lambda lg, lb: vocab_parallel_cross_entropy(
                lg, lb, V, mesh=mesh, model_axis="model",
                batch_axes=("data",)))(logits, labels)
        err = abs(float(ref) - float(out))
        assert err < 1e-5, (float(ref), float(out))
        # gradient must also match (it feeds the whole backward pass)
        g_ref = jax.grad(lambda lg: cross_entropy(lg, labels, V))(logits)
        with jax.set_mesh(mesh):
            g = jax.jit(jax.grad(lambda lg: vocab_parallel_cross_entropy(
                lg, labels, V, mesh=mesh, model_axis="model",
                batch_axes=("data",))))(logits)
        gerr = float(jnp.abs(g - g_ref).max())
        assert gerr < 1e-6, gerr
        print("OK", err, gerr)
    """)
