"""Model FLOP counters (the decoder family's, in its reference module) and
the table of peaks, against numbers worked out by hand."""
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import common, peaks  # noqa: E402

flops = common.load_module(ROOT / "bench" / "references" / "decoder.py",
                           "decoder")


def config(name):
    return json.loads((ROOT / "bench" / "configs" / f"{name}.json").read_text())


def test_smollm_flops_per_token():
    mc = config("smollm-360m")
    # per layer: q and o 960 x 960 each, k and v 960 x 320 each, SwiGLU
    # 3 x 960 x 2560; 32 layers; head 960 x 49152
    per_layer = 2 * 960 * 960 + 2 * 960 * 320 + 3 * 960 * 2560
    assert per_layer == 9_830_400
    assert flops.matmul_params_per_token(mc) == 32 * per_layer + 960 * 49152
    assert flops.matmul_params_per_token(mc) == 361_758_720
    # causal attention at 2048: 6 * 15 heads * 64 * 2049 per layer
    assert flops.attention_flops_per_token(mc, 2048) == 32 * 6 * 960 * 2049
    assert flops.train_flops_per_token(mc, 2048) == 2_548_224_000


def test_granite_flops_count_active_experts_only():
    mc = config("granite-moe-1b-a400m")
    # per layer: q, o 1024 x 1024; k, v 1024 x 512; router 1024 x 32;
    # 8 of the 32 experts, each 3 x 1024 x 512
    per_layer = 2 * 1024 * 1024 + 2 * 1024 * 512 + 1024 * 32 + 8 * 3 * 1024 * 512
    assert per_layer == 15_761_408
    n = mc["num_hidden_layers"]
    assert flops.matmul_params_per_token(mc) == n * per_layer + 1024 * 49155
    assert flops.train_flops_per_token(mc, 2048) == (
        6 * (n * per_layer + 1024 * 49155) + n * 6 * 1024 * 2049)
    assert n == 8 and flops.train_flops_per_token(mc, 2048) == 1_159_268_352


def test_peaks_are_keyed_by_device_kind():
    assert peaks.peak("TPU v5 lite")["bf16_flops"] == 197e12
    assert peaks.peak("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peak("TPU v9 imaginary")


def test_mfu_reader():
    read = common.load_module(ROOT / "bench" / "metrics" / "train_step_mfu.py",
                              "train_step_mfu").read
    run = {"driver": "train", "chips": 1, "device_kind": "TPU v5 lite",
           "tokens_per_s": 10_000.0, "flops_per_token": 1.97e9}
    assert read(run) == pytest.approx(10.0)
    assert read(dict(run, chips=4)) == pytest.approx(2.5)
    assert read(dict(run, driver="serve")) is None
    with pytest.raises(KeyError):
        read(dict(run, device_kind="cpu"))
