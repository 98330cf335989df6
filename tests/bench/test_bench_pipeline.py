"""A pipeline x DP cell driven on four CPU devices: a cell added from new
files, run in a child process that forces the devices before JAX starts;
and the same cell with its train step broken underneath, once for each
fault it can have, which ``correct`` must catch."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
CELL = "tiny-dense.train.pipe"
TINY = {"name": "tiny-dense", "arch": "smollm_360m", "reference": "decoder",
        "source": "test", "hidden_size": 64, "intermediate_size": 96,
        "num_attention_heads": 4, "num_key_value_heads": 2,
        "num_hidden_layers": 2, "vocab_size": 500, "rms_norm_eps": 1e-5,
        "rope_theta": 10000.0, "tie_word_embeddings": False,
        "vocab_pad_multiple": 256, "reduced": []}


def run_cell(tmp_path, fault=""):
    shutil.copytree(ROOT / "bench", tmp_path / "bench")
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    (tmp_path / "bench" / "configs" / "tiny-dense.json").write_text(
        json.dumps(TINY))
    mix = json.loads((ROOT / "bench" / "traffic" /
                      "train.pipe2-dp2.b32x2048.json").read_text())
    mix.update(batch=8, seq=32, parallel="pipe=2,micro=2,sched=1f1b,dp=2",
               reference_block_rows=2)
    (tmp_path / "bench" / "traffic" / "train.pipe.json").write_text(
        json.dumps(mix))
    # limits of the tiny one-chip cell in test_bench_harness.py
    (tmp_path / "bench" / "limits" / f"{CELL}.json").write_text(json.dumps(
        {"loss_gap": 1e-3, "grad_gap": 1e-2, "change_gap": 5e-3}))
    manifest["configs"].append({"name": "tiny-dense", "source": "test",
                                "file": "bench/configs/tiny-dense.json",
                                "reduced": [], "why": "test"})
    manifest["workloads"].append({"name": CELL, "config": "tiny-dense",
                                  "traffic": "train.pipe", "chips": 4,
                                  "why": "test"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(manifest))
    code = ("import sys; sys.path[:0] = [{root!r}, {src!r}]; "
            "from bench import run; run.main(['--workload', {cell!r}, "
            "'--seed', '4294967301', '--seconds', '0.5'], root={tmp!r}, "
            "require_chip=False, compile_cache=False, fault={fault!r})").format(
                root=str(ROOT), src=str(ROOT / "src"), cell=CELL,
                tmp=str(tmp_path), fault=fault)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_pipeline_cell_on_four_devices(tmp_path):
    out = run_cell(tmp_path)
    assert out["correct"] is True, out["checks"]
    assert out["device"]["count"] == 4
    assert out["attempted"] >= 1 and out["failed"] == 0


@pytest.mark.parametrize("fault", ["unchanged", "half_batch", "no_exchange"])
def test_broken_pipeline_step_is_not_correct(tmp_path, fault):
    out = run_cell(tmp_path, fault)
    assert out["correct"] is False
    bad = [k for k, c in out["checks"].items() if c["value"] > c["limit"]]
    assert bad, out["checks"]
