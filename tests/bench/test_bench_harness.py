"""The harness driven on the CPU at a small size: a cell added from new
files alone, the faults and the control that ``correct`` must catch, and
the runs that must fail without a chip or without the program."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import checks, common, weights  # noqa: E402

CELL = "tiny-dense.train.tiny"
# Limits for the tiny cell, set as a cell's are, from CPU readings on seeds
# 11-16 (bench/calibrate.py): the program (bfloat16) read at most 3.5e-4,
# 3.6e-3 and 1.5e-3; the float8 control at least 1.9e-3, 1.8e-2 and
# 9.2e-3; half of each batch left out at least 3.0e-3, 9.0e-2 and 0.18.
LIMITS = {"loss_gap": 1e-3, "grad_gap": 1e-2, "change_gap": 5e-3}
TINY = {"name": "tiny-dense", "arch": "smollm_360m", "reference": "decoder",
        "source": "https://huggingface.co/HuggingFaceTB/SmolLM-360M",
        "hidden_size": 64, "intermediate_size": 96, "num_attention_heads": 4,
        "num_key_value_heads": 2, "num_hidden_layers": 2, "vocab_size": 500,
        "rms_norm_eps": 1e-5, "rope_theta": 10000.0,
        "tie_word_embeddings": False, "vocab_pad_multiple": 256,
        "reduced": []}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A checkout with one cell more, added by new files and new entries:
    no file the benchmark has is edited."""
    root = tmp_path_factory.mktemp("checkout")
    shutil.copytree(ROOT / "bench", root / "bench")
    before = {p: p.read_bytes() for p in (root / "bench").rglob("*")
              if p.is_file()}
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    (root / "bench" / "configs" / "tiny-dense.json").write_text(
        json.dumps(TINY))
    mix = json.loads((ROOT / "bench" / "traffic" / "train.b8x2048.json")
                     .read_text())
    (root / "bench" / "traffic" / "train.tiny.json").write_text(
        json.dumps(dict(mix, batch=4, seq=32)))
    (root / "bench" / "limits" / f"{CELL}.json").write_text(json.dumps(LIMITS))
    manifest["configs"].append({"name": "tiny-dense", "source": "test",
                                "file": "bench/configs/tiny-dense.json",
                                "reduced": [], "why": "test"})
    manifest["workloads"].append({"name": CELL, "config": "tiny-dense",
                                  "traffic": "train.tiny", "chips": 1,
                                  "why": "test"})
    for m in manifest["per_layer"]:
        m["workloads"].append(CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    assert all(p.read_bytes() == b for p, b in before.items())
    return root


def run_tiny(root, fault="", trace=0):
    from bench import run
    return run.main(["--workload", CELL, "--seed", str(2**31 + 99),
                     "--seconds", "0.5", "--trace", str(trace)],
                    root=root, require_chip=False, compile_cache=False,
                    fault=fault)


def test_added_cell_runs_and_is_correct(root, capsys):
    out = run_tiny(root)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] >= 1
    assert set(out["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert out["metrics"]["train_tokens_per_s"]["unit"] == "tokens/s"
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(
        out["device"])
    assert list(out)[-1] == "checks"
    assert set(out["checks"]) == {"loss_gap", "grad_gap", "change_gap"}
    printed = capsys.readouterr()
    assert json.loads(printed.out.strip().splitlines()[-1]) == out
    assert printed.err.strip().splitlines()[-1].startswith("check change_gap")


@pytest.mark.parametrize("fault", ["unchanged", "half_batch"])
def test_broken_train_step_is_not_correct(root, fault):
    out = run_tiny(root, fault=fault)
    assert out["correct"] is False
    bad = [k for k, c in out["checks"].items() if c["value"] > c["limit"]]
    assert bad, out["checks"]
    if fault == "unchanged":
        assert out["checks"]["change_gap"]["value"] == pytest.approx(1.0, rel=1e-3)


def test_control_in_lower_precision_is_not_correct():
    """The reference in the program's place, with float8 matmul operands,
    against the float32 reference, at the tiny cell's size."""
    ref = common.load_module(ROOT / "bench" / "references" / "decoder.py",
                             "decoder")
    zipf = common.load_module(ROOT / "bench" / "traffic" / "zipf_tokens.py",
                              "zipf_tokens")
    mix = json.loads((ROOT / "bench" / "traffic" / "train.b8x2048.json")
                     .read_text())
    mix.update(batch=4, seq=32)
    limits = LIMITS
    make = weights.make_params_fn(ref.param_shapes(TINY), ref.NORMS)
    for seed in (11, 12, 13):
        src = zipf.Source(mix, TINY["vocab_size"], seed)
        pairs = [(b["tokens"], b["labels"])
                 for b in map(src.batch, range(mix["checked_steps"]))]
        read = {mode: checks.Reference(
            ref, TINY, mix["optimizer"], ref.Numerics(mode), make).readings(
                common.seed_key(seed), pairs)
            for mode in ("float32", "float8")}
        numbers = checks.compare(read["float8"], read["float32"])
        ok, _ = checks.judge({k: numbers[k] for k in limits}, limits)
        assert not ok, numbers


def test_traced_run_on_the_cpu_has_no_device_trace(root):
    with pytest.raises(ValueError, match="no device op"):
        run_tiny(root, trace=1)


def test_traced_run_reads_the_layer_scopes(root, monkeypatch):
    """A traced run on the CPU, with the profiler's events replaced by
    events built by hand on the instructions of the step that the run
    compiles, two of attention's taken for kernel calls (the CPU step calls
    none): a forward that runs twice and a backward that runs once.  The
    scope shares and the kernel runs reach the per-layer metrics that read
    them."""
    import jax

    from bench import peaks, scopes, trace
    bench = common.Bench(root)
    driver, ref = bench.driver("train"), bench.reference("decoder")
    mix = bench.traffic("train.tiny")
    prog = driver.build(TINY, mix, ref)
    source = bench.generator(mix["kind"]).Source(mix, TINY["vocab_size"], 0)
    state = prog.init(common.seed_key(0))
    with jax.set_mesh(prog.mesh):
        ops = scopes.op_names(scopes.step_hlo(prog, state, prog.put(
            source.batch(0))))
    by_scope = {}
    for name, op in sorted(ops.items()):
        by_scope.setdefault(scopes.scope_of(op, scopes.names()), name)
    core = [n for n, op in sorted(ops.items())
            if scopes.scope_of(op, scopes.names()) == "attn.core"]
    fwd = next(n for n in core if "transpose(" not in ops[n])
    bwd = next(n for n in core if "transpose(" in ops[n]
               and "rematted_computation" not in ops[n])
    monkeypatch.setattr(scopes, "kernels", lambda hlo: {fwd, bwd})
    ms = 1e6
    # window 0..100 ms: attention 0-30 (a forward twice, then a backward),
    # the MLP 30-50, the optimizer 50-60
    events = [trace.Event("/host:CPU", "python", "bench.window", 0, 100 * ms)]
    for name, start, dur in ((fwd, 0, 10), (fwd, 10, 10), (bwd, 20, 10),
                             (by_scope["mlp"], 30, 20),
                             (by_scope["optim"], 50, 10)):
        events.append(trace.Event("/device:TPU:0", "XLA Ops", name,
                                  start * ms, dur * ms))
    monkeypatch.setattr(trace, "load", lambda logdir: events)
    monkeypatch.setitem(peaks.PEAKS, jax.devices()[0].device_kind,
                        {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e9})
    out = run_tiny(root, trace=1)
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert set(m) == {"train_step_mfu", "device_idle_share.train",
                      "attention_core_share.train",
                      "train_attention_roofline"}
    assert m["attention_core_share.train"] == pytest.approx(30.0)
    assert m["device_idle_share.train"] == pytest.approx(40.0)
    # the window's tokens through two forwards and a backward, at the least
    # time each call could take, over attention's 30 ms
    cost = ref.attention_kernel_cost(TINY, mix["seq"])
    least = {k: max(c["flops"] / 1e12, c["bytes"] / 1e9)
             for k, c in cost.items()}
    tokens = out["attempted"] * mix["batch"] * mix["seq"]
    assert m["train_attention_roofline"] == pytest.approx(
        100 * tokens * (2 * least["forward"] + least["backward"]) / 0.03)
    assert out["device"]["busy_s"] == pytest.approx(0.06)
    assert out["correct"] is True


def _env():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return env


def test_without_a_tpu_the_run_fails_and_prints_nothing():
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "smollm-360m.train.b8x2048", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=_env(), capture_output=True,
        text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout == ""
    assert "no TPU" in p.stderr


def test_without_the_program_the_run_fails(tmp_path):
    """A directory that holds only BENCHMARK.json and the benchmark's files."""
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    for p in manifest["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p)
    code = ("import sys; sys.path.insert(0, '.'); from bench import run; "
            "run.main(['--workload', 'smollm-360m.train.b8x2048', '--seed', "
            "'1', '--seconds', '1'], root='.', require_chip=False, "
            "compile_cache=False)")
    p = subprocess.run([sys.executable, "-c", code], cwd=tmp_path, env=_env(),
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout == ""
    assert "repro" in p.stderr
