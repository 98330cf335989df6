"""The per-layer metrics read from the train step's layer scopes
(``bench/metrics/``), on records built by hand."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import common  # noqa: E402

BENCH = common.Bench(ROOT)
CELLS = {"smollm-360m.train.b8x2048", "granite-moe-1b-a400m.train.b8x2048",
         "smollm-360m.train.pipe2-dp2.b32x2048"}
GRANITE_CELL = "granite-moe-1b-a400m.train.b8x2048"
SMOLLM = {"attn.core": 0.4005, "mlp": 0.31196, "attn.proj": 0.16395,
          "head": 0.05313, "optim": 0.03507, "unscoped": 0.02397}
GRANITE = {"moe.combine": 0.33972, "moe.experts": 0.18648,
           "moe.dispatch": 0.18341, "attn.core": 0.09181, "head": 0.04705,
           "moe.router": 0.04515, "attn.proj": 0.03985, "optim": 0.03774,
           "unscoped": 0.01855}
# smollm-360m at batch 8 x 2048 on one v5e: 56 steps in a 30 s window, each
# running 32 layers' forward, rematerialised forward and fused backward
SMOLLM_COST = BENCH.reference("decoder").attention_kernel_cost(
    BENCH.config("smollm-360m"), 2048)
RUN = {"driver": "train", "chips": 1, "device_kind": "TPU v5 lite",
       "attempted": 56, "tokens_per_step": 8 * 2048,
       "scopes": SMOLLM, "scopes_window_s": 30.0,
       "kernel_runs": {"attn.core": {
           "forward": {"splash_mha_fwd_residuals": 2 * 32 * 56},
           "backward": {"splash_mha_dkv_no_residuals": 32 * 56}}},
       "attention_kernel": SMOLLM_COST}


def read(metric, run):
    return BENCH.reader(metric).read(run)


def check_layer_metrics(root, cells=CELLS) -> None:
    """Every cell that a layer-scope metric lists exists, and the metrics
    list at least the cells they were measured in; a cell added later may
    come with entries of its own instead."""
    bench = common.Bench(root)
    mine = {m["name"]: m for m in bench.manifest["per_layer"]}
    every = {w["name"] for w in bench.manifest["workloads"]}
    for name in ("attention_core_share.train", "train_attention_roofline",
                 "moe_routing_share.train"):
        assert set(mine[name]["workloads"]) <= every, name
    for name in ("attention_core_share.train", "train_attention_roofline"):
        assert cells <= set(mine[name]["workloads"]), name
    assert GRANITE_CELL in mine["moe_routing_share.train"]["workloads"]


def test_the_metrics_are_in_the_manifest_with_their_cells():
    check_layer_metrics(ROOT)


@pytest.mark.parametrize("metric", [
    "attention_core_share.train", "moe_routing_share.train",
    "train_attention_roofline"])
@pytest.mark.parametrize("run", [
    dict(RUN, scopes=None),                 # an untraced run
    {k: v for k, v in RUN.items() if k != "scopes"},
    dict(RUN, scopes={"embed": 0.1, "unscoped": 0.9}),
    dict(RUN, scopes=dict(GRANITE), driver="serve"),
], ids=["untraced", "no_scopes_key", "no_such_scope", "not_training"])
def test_nothing_to_read_gives_none(metric, run):
    assert read(metric, run) is None


def test_attention_core_share():
    assert read("attention_core_share.train", RUN) == pytest.approx(40.05)
    assert read("attention_core_share.train",
                dict(RUN, scopes=GRANITE)) == pytest.approx(9.181)


def test_moe_routing_share_is_router_dispatch_and_combine():
    got = read("moe_routing_share.train", dict(RUN, scopes=GRANITE))
    assert got == pytest.approx(100 * (0.04515 + 0.18341 + 0.33972))
    assert got == pytest.approx(56.828)
    # a dense step routes nothing
    assert read("moe_routing_share.train", RUN) is None


def test_attention_kernel_cost_by_hand():
    # 32 layers x 15 heads x 64 x 2049 = 62,945,280 for one causal matmul
    assert SMOLLM_COST == {
        "forward": {"flops": 2 * 62_945_280,
                    # q, k, v, o (960, 320, 320, 960) x 2 B + 15 x 4 B
                    "bytes": 32 * (2 * 2560 + 60)},
        "backward": {"flops": 5 * 62_945_280,
                     # q, k, v, dO, dQ, dK, dV x 2 B + 2 x 15 x 4 B
                     "bytes": 32 * (2 * 4160 + 120)}}


def test_attention_roofline_by_hand():
    # 56 x 16,384 = 917,504 tokens; forward 125,890,560 and backward
    # 314,726,400 FLOPs a token, compute-bound on the v5e; two forwards a
    # backward: 917,504 x (2 x 125,890,560 + 314,726,400) / 197e12 s
    # = 2.6384412 s, over 0.4005 x 30 s = 12.015 s: 21.95956 %
    got = read("train_attention_roofline", RUN)
    assert got == pytest.approx(
        100 * 917_504 * (2 * 125_890_560 + 314_726_400) / 197e12 / 12.015)
    assert got == pytest.approx(21.95956, abs=1e-5)
    # four chips share the calls; each spends the same share in attention
    assert read("train_attention_roofline", dict(RUN, chips=4)) == (
        pytest.approx(got / 4))
    # the rematerialised forward left out: one forward a backward, and the
    # scope's time falls with it
    once = dict(RUN, scopes=dict(SMOLLM, **{"attn.core": 0.4005 * 7 / 9}),
                kernel_runs={"attn.core": {
                    "forward": {"splash_mha_fwd_residuals": 32 * 56},
                    "backward": {"splash_mha_dkv_no_residuals": 32 * 56}}})
    assert read("train_attention_roofline", once) == pytest.approx(got)
    # a backward split in two kernels is still one backward pass
    split = dict(RUN, kernel_runs={"attn.core": {
        "forward": {"splash_mha_fwd_residuals": 2 * 32 * 56},
        "backward": {"splash_mha_dq": 32 * 56, "splash_mha_dkv": 32 * 56}}})
    assert read("train_attention_roofline", split) == pytest.approx(got)


def test_attention_roofline_takes_the_bound_that_binds():
    # bytes enough that each call is bound by the HBM bandwidth
    heavy = {"forward": {"flops": 1, "bytes": 8190},
             "backward": {"flops": 1, "bytes": 16380}}
    got = read("train_attention_roofline", dict(RUN, attention_kernel=heavy))
    assert got == pytest.approx(
        100 * 917_504 * (2 * 8190 + 16380) / 819e9 / 12.015)


@pytest.mark.parametrize("change", [
    {"attention_kernel": None},             # a family that counts none
    {"scopes": {"attn.core": 0.0}},         # a scope of no time
    {"kernel_runs": {}},                    # no kernel under the scope
    {"kernel_runs": {"attn.core": {"forward": {"k": 3}, "backward": {}}}},
], ids=["no_cost", "no_time", "no_kernel", "no_backward"])
def test_attention_roofline_without_its_readings_gives_none(change):
    assert read("train_attention_roofline", dict(RUN, **change)) is None


def test_attention_roofline_needs_the_device_in_the_table():
    with pytest.raises(KeyError):
        read("train_attention_roofline", dict(RUN, device_kind="cpu"))
