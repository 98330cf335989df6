"""The plain float32 references against the program's forward, loss and
gradients at a small size on the CPU, and the seeded generators."""
import dataclasses
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from bench import common, weights  # noqa: E402

ref = common.load_module(ROOT / "bench" / "references" / "decoder.py",
                         "decoder")
program_config = ref.program_config
zipf = common.load_module(ROOT / "bench" / "traffic" / "zipf_tokens.py",
                          "zipf_tokens")

SMALL = {"name": "small", "reference": "decoder", "hidden_size": 64,
         "intermediate_size": 96, "num_attention_heads": 4,
         "num_key_value_heads": 2, "num_hidden_layers": 2, "vocab_size": 500,
         "rms_norm_eps": 1e-5, "rope_theta": 10000.0,
         "tie_word_embeddings": False, "vocab_pad_multiple": 256}
DENSE = dict(SMALL, arch="smollm_360m")
MOE = dict(SMALL, arch="granite_moe_1b_a400m", num_local_experts=4,
           num_experts_per_tok=2, router_aux_loss_coef=0.01,
           capacity_factor=1.25)


def program_loss_and_grads(mc, params, batch):
    from repro.models.api import build_model
    cfg = dataclasses.replace(program_config(mc), dtype="float32")
    api = build_model(cfg, capacity_factor=mc.get("capacity_factor", 1.25))
    with jax.default_matmul_precision("highest"):
        (total, metrics), grads = jax.value_and_grad(
            api.loss_fn, has_aux=True)(params, batch)
    return total, metrics["loss"], grads, api


def batch_of(mc, seed=0, b=4, t=32):
    ids = np.random.default_rng(seed).integers(
        0, mc["vocab_size"], (b, t + 1)).astype(np.int32)
    return {"tokens": ids[:, :-1], "labels": ids[:, 1:]}


@pytest.mark.parametrize("mc,capacity", [(DENSE, None), (MOE, 1.25),
                                         (MOE, 0.5)],
                         ids=["dense", "moe", "moe-dropping"])
def test_reference_matches_program_in_float32(mc, capacity):
    if capacity is not None:
        mc = dict(mc, capacity_factor=capacity)
    params = jax.jit(weights.make_params_fn(ref.param_shapes(mc),
                                            ref.NORMS))(common.seed_key(7))
    batch = batch_of(mc)
    total, ce, grads, api = program_loss_and_grads(mc, params, batch)
    (r_total, r_ce), r_grads = jax.value_and_grad(
        lambda p: ref.loss(ref.Numerics(), mc, p, batch["tokens"],
                           batch["labels"]), has_aux=True)(params)
    assert float(r_ce) == pytest.approx(float(ce), rel=1e-5)
    assert float(r_total) == pytest.approx(float(total), rel=1e-5)
    flat, r_flat = weights.flatten(grads), weights.flatten(r_grads)
    assert flat.keys() == r_flat.keys()
    for k in flat:
        scale = float(jnp.max(jnp.abs(r_flat[k]))) + 1e-12
        assert float(jnp.max(jnp.abs(flat[k] - r_flat[k]))) <= 1e-4 * scale, k


def test_moe_capacity_drops_change_the_loss():
    params = jax.jit(weights.make_params_fn(ref.param_shapes(MOE),
                                            ref.NORMS))(common.seed_key(3))
    batch = batch_of(MOE, 1)
    losses = [float(ref.loss(ref.Numerics(), dict(MOE, capacity_factor=cf),
                             params, batch["tokens"], batch["labels"])[1])
              for cf in (4.0, 0.25)]
    assert losses[0] != losses[1]


def test_layout_matches_the_program():
    from repro.models.api import build_model
    for mc in (DENSE, MOE):
        api = build_model(program_config(mc))
        got = weights.flatten(jax.eval_shape(api.init, jax.random.PRNGKey(0)))
        want = weights.flatten(ref.param_shapes(mc))
        assert {k: tuple(v.shape) for k, v in got.items()} == {
            k: tuple(v) for k, v in want.items()}


def test_float8_control_differs_from_float32():
    params = jax.jit(weights.make_params_fn(ref.param_shapes(DENSE),
                                            ref.NORMS))(common.seed_key(5))
    batch = batch_of(DENSE, 2)
    a, b = (float(ref.loss(ref.Numerics(m), DENSE, params, batch["tokens"],
                           batch["labels"])[1]) for m in ("float32", "float8"))
    assert a != b and abs(a - b) / a < 0.1


def test_cut_exchange_fault_feeds_the_second_half_zeros():
    """Two stages with no exchange: the second half of the layers starts
    from zeros, which no layer without biases moves, so the logits are zero
    (the loss is log(vocab)) and no gradient is left."""
    params = jax.jit(weights.make_params_fn(ref.param_shapes(DENSE),
                                            ref.NORMS))(common.seed_key(6))
    batch = batch_of(DENSE, 3)
    read = {}
    for cut in (False, True):
        read[cut] = jax.value_and_grad(
            lambda p: ref.loss(ref.Numerics("float32", cut_exchange=cut),
                               DENSE, p, batch["tokens"], batch["labels"]),
            has_aux=True)(params)
    (_, ce), g = read[False]
    (_, ce_cut), g_cut = read[True]
    assert float(ce_cut) == pytest.approx(np.log(DENSE["vocab_size"]))
    assert float(ce) != float(ce_cut)
    assert all(float(jnp.max(jnp.abs(x))) == 0.0
               for x in weights.flatten(g_cut).values())
    assert all(float(jnp.max(jnp.abs(x))) > 0.0
               for x in weights.flatten(g).values())


def test_learning_rate_matches_the_program_schedule():
    from repro.optim import warmup_cosine
    opt = {"lr": 3e-3, "warmup_steps": 20, "total_steps": 1000}
    sched = warmup_cosine(3e-3, 20, 1000)
    for s in (0, 1, 2, 19, 20, 500, 999, 5000):
        assert ref.learning_rate(opt, s) == pytest.approx(float(sched(s)),
                                                          rel=1e-6)


def test_weights_are_made_from_the_seed():
    make = jax.jit(weights.make_params_fn(ref.param_shapes(DENSE),
                                          ref.NORMS))
    a, b, c = (weights.flatten(make(common.seed_key(s)))
               for s in (2**33 + 1, 2**33 + 1, 1))
    for k in a:
        assert np.array_equal(a[k], b[k])
    # the seed's high bits count: 2**33 + 1 and 1 give other weights
    assert not np.array_equal(a["embed"], c["embed"])


def test_zipf_batches_are_deterministic_and_distinct():
    mix = {"batch": 4, "seq": 64, "zipf_exponent": 1.0}
    s1, s2 = (zipf.Source(mix, 1000, 2**31 + 7) for _ in range(2))
    other = zipf.Source(mix, 1000, 7)
    b0, b0_again = s1.batch(0), s2.batch(0)
    assert np.array_equal(b0["tokens"], b0_again["tokens"])
    assert not np.array_equal(b0["tokens"], s1.batch(1)["tokens"])
    assert not np.array_equal(b0["tokens"], other.batch(0)["tokens"])
    assert b0["tokens"].shape == (4, 64) and b0["tokens"].dtype == np.int32
    assert np.array_equal(b0["tokens"][:, 1:], b0["labels"][:, :-1])
    assert 0 <= b0["tokens"].min() and b0["tokens"].max() < 1000
    rows = {r.tobytes() for r in b0["tokens"]}
    assert len(rows) == 4
    # rank 0 is the commonest id
    ids = np.concatenate([s1.batch(i)["tokens"].ravel() for i in range(20)])
    assert np.bincount(ids).argmax() == 0
