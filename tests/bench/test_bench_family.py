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
