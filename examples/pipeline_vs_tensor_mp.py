"""Pipeline-MP vs tensor-MP on 8 forced host devices: both must produce the
same loss as the single-device reference; prints the collective footprint
difference (the paper treats pipelining as an MP instance — §2).

    PYTHONPATH=src python examples/pipeline_vs_tensor_mp.py
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.core.roofline import parse_collectives  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.parallel.jaxcompat import make_mesh  # noqa: E402
from repro.parallel.pipeline import pipeline_apply, stack_to_stages  # noqa: E402
from repro.parallel.plan import ParallelPlan  # noqa: E402
from repro.parallel.sharding import ShardingRules  # noqa: E402

import dataclasses

cfg = dataclasses.replace(get_config("llama3_2_1b").reduced(), n_layers=8)
api = build_model(cfg, remat=False)
key = jax.random.PRNGKey(0)
params = api.init(key)
batch = {"tokens": jax.random.randint(key, (8, 32), 0, cfg.vocab_size,
                                      dtype=jnp.int32),
         "labels": jax.random.randint(key, (8, 32), 0, cfg.vocab_size,
                                      dtype=jnp.int32)}
ref, _ = api.loss_fn(params, batch)
print(f"single-device loss: {float(ref):.6f}")

mesh = make_mesh((2, 4), ("data", "model"))

# --- tensor MP (GSPMD) -------------------------------------------------------
rules = ShardingRules(cfg, mesh, ParallelPlan())
p_sh = rules.params_shardings(jax.eval_shape(api.init, key))
b_sh = rules.batch_shardings(jax.eval_shape(lambda: batch))
with jax.set_mesh(mesh):
    f = jax.jit(lambda p, b: api.loss_fn(p, b)[0], in_shardings=(p_sh, b_sh))
    lowered = f.lower(params, batch)
    tp_loss = f(params, batch)
coll_tp = parse_collectives(lowered.compile().as_text(), default_group=4)
print(f"tensor-MP loss:     {float(tp_loss):.6f}  "
      f"collectives={coll_tp.ops} wire={coll_tp.wire_bytes/2**20:.1f} MiB")

# --- pipeline MP over the layer stack ---------------------------------------
from repro.models import transformer as tf_mod  # noqa: E402
from repro.models import layers as L  # noqa: E402


def stage_fn(stage_params, x):
    def body(x, lp):
        y, _, _ = tf_mod.block_apply(cfg, lp, x, mode="train", window=0,
                                     pos0=0)
        return y, None
    y, _ = jax.lax.scan(body, x, stage_params)
    return y


def pipeline_loss(params, batch):
    x = tf_mod._embed(cfg, params, batch["tokens"])
    stages = stack_to_stages(params["layers"], 4)
    x = pipeline_apply(mesh, "model", stage_fn, stages, x, n_micro=4,
                       batch_axes="data")
    logits = tf_mod._head(cfg, params, x)
    from repro.models.api import cross_entropy
    return cross_entropy(logits, batch["labels"], cfg.vocab_size)


with jax.set_mesh(mesh):
    g = jax.jit(pipeline_loss)
    lowered_p = g.lower(params, batch)
    pp_loss = g(params, batch)
coll_pp = parse_collectives(lowered_p.compile().as_text(), default_group=4)
print(f"pipeline-MP loss:   {float(pp_loss):.6f}  "
      f"collectives={coll_pp.ops} wire={coll_pp.wire_bytes/2**20:.1f} MiB")
assert abs(float(pp_loss) - float(ref)) < 1e-4
assert abs(float(tp_loss) - float(ref)) < 1e-4
print("both MP implementations match the single-device reference.")
