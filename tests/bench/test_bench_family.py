"""A second model family added as new files alone: a reference module, and a
configuration that names it under ``reference``.  The harness takes the
family's mapping onto the program, its FLOP count and its gain leaves from
that module; no file the benchmark has is edited."""
import json
import shutil
import sys
import time
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import common, weights  # noqa: E402

CELL = "tiny-family.train.tiny"
SENTINEL = 123_456_789
# the decoder's mathematics under other names for two keys, its own FLOP
# count, and one norm leaf more, a gain after a latent projection, which its
# layout holds where the configuration states ``kv_lora_rank``; the program
# has no such leaf, so the cell that runs leaves the key out
FAMILY = f'''"""Test family: the decoder under other configuration keys."""
from pathlib import Path

from bench import common

_decoder = common.load_module(Path(__file__).with_name("decoder.py"),
                              "decoder")
Numerics, learning_rate = _decoder.Numerics, _decoder.learning_rate
NORMS = _decoder.NORMS + ("kv_norm",)
RENAMED = {{"n_layer": "num_hidden_layers", "n_embd": "hidden_size"}}


def as_decoder(mc):
    return {{RENAMED.get(k, k): v for k, v in mc.items()}}


def param_shapes(mc):
    shapes = _decoder.param_shapes(as_decoder(mc))
    if "kv_lora_rank" in mc:
        shapes["layers"]["attn"]["kv_norm"] = (mc["n_layer"],
                                               mc["kv_lora_rank"])
    return shapes


def check_fixed(mc):
    _decoder.check_fixed(as_decoder(mc))


def program_config(mc):
    return _decoder.program_config(as_decoder(mc))


def train_flops_per_token(mc, seq):
    return {SENTINEL}


def make_step(mc, opt, num, grad_norms, block_rows=None):
    return _decoder.make_step(as_decoder(mc), opt, num, grad_norms,
                              block_rows)
'''
CONFIG = {"name": "tiny-family", "arch": "smollm_360m",
          "reference": "tiny_family", "source": "test", "n_embd": 64,
          "intermediate_size": 96, "num_attention_heads": 4,
          "num_key_value_heads": 2, "n_layer": 2, "vocab_size": 500,
          "rms_norm_eps": 1e-5, "rope_theta": 10000.0,
          "tie_word_embeddings": False, "vocab_pad_multiple": 256,
          "reduced": []}
# the tiny decoder cell's limits (tests/bench/test_bench_harness.py)
LIMITS = {"loss_gap": 1e-3, "grad_gap": 1e-2, "change_gap": 5e-3}


def test_a_family_added_as_new_files_runs_through_its_own_module(tmp_path):
    import jax

    shutil.copytree(ROOT / "bench", tmp_path / "bench")
    before = {p: p.read_bytes() for p in (tmp_path / "bench").rglob("*")
              if p.is_file()}
    bench_dir = tmp_path / "bench"
    (bench_dir / "references" / "tiny_family.py").write_text(FAMILY)
    (bench_dir / "configs" / "tiny-family.json").write_text(json.dumps(CONFIG))
    mix = json.loads((bench_dir / "traffic" / "train.b8x2048.json")
                     .read_text())
    (bench_dir / "traffic" / "train.tiny.json").write_text(
        json.dumps(dict(mix, batch=4, seq=32)))
    (bench_dir / "limits" / f"{CELL}.json").write_text(json.dumps(LIMITS))
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    manifest["configs"].append({"name": "tiny-family", "source": "test",
                                "file": "bench/configs/tiny-family.json",
                                "reduced": [], "why": "test"})
    manifest["workloads"].append({"name": CELL, "config": "tiny-family",
                                  "traffic": "train.tiny", "chips": 1,
                                  "why": "test"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(manifest))
    assert all(p.read_bytes() == b for p, b in before.items())

    from bench import run
    bench = common.Bench(tmp_path)
    cell = bench.cell(CELL)
    mc = bench.config(cell["config"])
    ctx = run.Context(bench=bench, cell=cell, config=mc,
                      mix=bench.traffic(cell["traffic"]), seed=2**32 + 41,
                      seconds=0.5, trace=False, t_process=time.perf_counter())
    driver = bench.driver("train")
    record = driver.run(ctx)
    assert record["flops_per_token"] == SENTINEL
    out = run.result_line(bench, cell, record, bench.limits(CELL), False, jax)
    assert out["correct"] is True, out["checks"]

    # the weights as the driver draws them for the family's layout
    family = bench.reference(mc["reference"])
    latent = dict(mc, kv_lora_rank=512)
    params = weights.make_params_fn(family.param_shapes(latent),
                                    family.NORMS)(common.seed_key(ctx.seed))
    gain = np.asarray(params["layers"]["attn"]["kv_norm"])
    assert gain.shape == (2, 512)
    assert abs(gain.mean() - 1.0) < 0.02 and 0.05 < gain.std() < 0.15
    # a matrix beside it is drawn as one
    wq = np.asarray(params["layers"]["attn"]["wq"])
    assert abs(wq.mean()) < 0.02 and abs(wq.std() - 64 ** -0.5) < 0.02


# --- a DeepSeek-shaped configuration, cut as model-configs section 4 cuts ---

DEEPSEEK_CELL = "deepseek-v2-lite.train.b8x2048"
# the catalog's DeepSeek-V2-Lite config, as a one-chip share of a deployment
# that divides each layer over eight chips: 8 of the 64 routed experts, an
# eighth of the vocabulary, the dense layer and five expert layers
DEEPSEEK = {
    "name": "deepseek-v2-lite", "arch": "deepseek_v2_lite",
    "reference": "deepseek_shaped",
    "source": "https://huggingface.co/deepseek-ai/DeepSeek-V2-Lite/blob/"
              "main/config.json",
    "attention_bias": False, "first_k_dense_replace": 1, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 10944, "kv_lora_rank": 512,
    "max_position_embeddings": 163840, "model_type": "deepseek_v2",
    "moe_intermediate_size": 1408, "moe_layer_freq": 1, "n_group": 1,
    "n_routed_experts": 8, "n_shared_experts": 2, "norm_topk_prob": False,
    "num_attention_heads": 16, "num_experts_per_tok": 6,
    "num_hidden_layers": 6, "num_key_value_heads": 16, "q_lora_rank": None,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-6,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 0.707, "mscale_all_dim": 0.707,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "rope_theta": 10000, "routed_scaling_factor": 1,
    "scoring_func": "softmax", "seq_aux": True, "tie_word_embeddings": False,
    "topk_group": 1, "topk_method": "greedy", "v_head_dim": 128,
    "vocab_size": 12800,
    "reduced": ["num_hidden_layers", "n_routed_experts", "vocab_size"],
    "source_values": {"num_hidden_layers": 27, "n_routed_experts": 64,
                      "vocab_size": 102400},
    "assumed": {"weights": "random, from the seed"},
    "deployment": "each layer divided over 8 chips, each holding 8 of its "
                  "64 routed experts and an eighth of the vocabulary; the "
                  "layers beyond the sixth on further chips as stages",
}
DEEPSEEK_REFERENCE = '''"""Stand-in for a DeepSeek-V2 reference module: only what the manifest's
rules call."""


def check_fixed(mc):
    if mc["q_lora_rank"] is not None or mc["first_k_dense_replace"] != 1:
        raise ValueError("the family is written for no q_lora_rank and one "
                         "leading dense layer")
'''
# a per-layer metric on a scope that only the new family's program has
LATENT_METRIC = "mla_latent_share.train"
LATENT_READER = '''"""Share of a training window in the latent projections' scope."""


def read(run):
    share = (run.get("scopes") or {}).get("attn.latent")
    if run.get("driver") != "train" or share is None:
        return None
    return 100.0 * share
'''


@pytest.fixture(scope="module")
def rules():
    return common.load_module(ROOT / "tests" / "bench" /
                              "test_bench_manifest.py", "test_bench_manifest")


@pytest.fixture(scope="module")
def layer_rules():
    return common.load_module(ROOT / "tests" / "bench" /
                              "test_bench_layer_readers.py",
                              "test_bench_layer_readers")


def deepseek_checkout(root: Path, **change) -> Path:
    """A copy of the benchmark with the DeepSeek-shaped configuration, its
    cell and its per-layer metric added as new files and new entries; the
    copy's existing files are checked to be unchanged."""
    mc = dict(DEEPSEEK, **change)
    shutil.copytree(ROOT / "bench", root / "bench")
    before = {p: p.read_bytes() for p in (root / "bench").rglob("*")
              if p.is_file()}
    bench_dir = root / "bench"
    (bench_dir / "references" / "deepseek_shaped.py").write_text(
        DEEPSEEK_REFERENCE)
    (bench_dir / "configs" / "deepseek-v2-lite.json").write_text(
        json.dumps(mc))
    (bench_dir / "limits" / f"{DEEPSEEK_CELL}.json").write_text(
        json.dumps(LIMITS))
    (bench_dir / "metrics" / f"{LATENT_METRIC}.py").write_text(LATENT_READER)
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    manifest["configs"].append({
        "name": mc["name"], "source": DEEPSEEK["source"],
        "file": "bench/configs/deepseek-v2-lite.json",
        "reduced": mc["reduced"], "why": "latent attention, shared experts"})
    manifest["workloads"].append({
        "name": DEEPSEEK_CELL, "config": mc["name"],
        "traffic": "train.b8x2048", "chips": 1, "why": "test"})
    manifest["per_layer"].append({
        "name": LATENT_METRIC, "unit": "%", "better": "lower",
        "source": "device_trace", "layer": "attention projections",
        "moves": "train_tokens_per_s", "workloads": [DEEPSEEK_CELL]})
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    assert all(p.read_bytes() == b for p, b in before.items())
    return root


def test_a_deepseek_shaped_configuration_arrives_as_new_files(
        tmp_path, rules, layer_rules):
    root = deepseek_checkout(tmp_path)
    rules.check_names_units_and_keys(root)
    rules.check_cells(root)
    rules.check_configs(root)
    layer_rules.check_layer_metrics(root)

    from bench import scopes
    assert "attn.latent" not in scopes.names()
    bench = common.Bench(root)
    assert [m["name"] for m in bench.metrics_of(DEEPSEEK_CELL, "per_layer")
            if DEEPSEEK_CELL in m["workloads"]] == [LATENT_METRIC]
    read = bench.reader(LATENT_METRIC).read
    run = {"driver": "train", "scopes": {"attn.latent": 0.125,
                                         "attn.core": 0.3, "unscoped": 0.01}}
    assert read(run) == pytest.approx(12.5)
    # the decoder's step has no such scope: nothing to read
    assert read(dict(run, scopes={"attn.core": 0.4, "mlp": 0.3})) is None


@pytest.mark.parametrize("change", [
    {"vocab_size": 12_799},
    {"n_routed_experts": 4},
    {"source_values": {"num_hidden_layers": 27, "n_routed_experts": 64}},
    {"deployment": ""},
    {"num_hidden_layers": 4},
    {"kv_lora_rank": 256, "reduced": ["num_hidden_layers",
                                      "n_routed_experts", "vocab_size",
                                      "kv_lora_rank"]},
], ids=["vocab_under_an_eighth", "four_experts", "vocab_not_published",
        "no_deployment", "three_expert_layers", "a_width"])
def test_cuts_past_the_floors_are_refused(tmp_path, rules, change):
    root = deepseek_checkout(tmp_path, **change)
    with pytest.raises(AssertionError):
        rules.check_configs(root)
