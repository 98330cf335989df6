"""``chip_smoke.py``'s phases at ``cfg.reduced()`` on the CPU, with the same
checks the chip run makes, and its refusal to run without a TPU."""
import os
import subprocess
import sys
import textwrap

import jax
import pytest

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")


def _run(code: str, **env):
    env = dict(os.environ, PYTHONPATH=ROOT, JAX_PLATFORMS="cpu", **env)
    return subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                          capture_output=True, text=True, env=env,
                          timeout=900, cwd=ROOT)


def test_one_chip_phases_reduced():
    r = _run("""
        import chip_smoke as cs
        cs.train_phase(reduced=True, batch=2, seq=32, steps=12)
        out = cs.serve_phase(reduced=True, requests=4, prompt_len=16,
                             max_new=6, slots=4, prefill_chunk=8)
        assert len(out["results"]) == 4
        print("PHASES_OK")
    """)
    assert r.returncode == 0 and "PHASES_OK" in r.stdout, r.stderr[-3000:]
    assert "[smoke] train dp=1,mp=1" in r.stdout
    # seq 32 is no multiple of the kernel's 128: the dense path
    assert "attention calls traced by path {'dense': " in r.stdout
    assert "match the full forward" in r.stdout


def test_four_chip_phase_reduced_on_virtual_devices():
    r = _run("""
        import chip_smoke as cs
        cs.four_chip_phase(reduced=True, batch=8, seq=32, steps=4,
                           requests=8, prompt_len=16, max_new=6, slots=4,
                           prefill_chunk=8)
        print("FOUR_OK")
    """, XLA_FLAGS="--xla_force_host_platform_device_count=4")
    assert r.returncode == 0 and "FOUR_OK" in r.stdout, r.stderr[-3000:]
    assert "losses agree step for step" in r.stdout
    assert "4 replicas on devices [0, 1, 2, 3]" in r.stdout


def test_main_exits_nonzero_without_tpu():
    for argv in ([], ["--chips", "4"]):
        r = subprocess.run([sys.executable, "chip_smoke.py", *argv],
                           capture_output=True, text=True, timeout=300,
                           cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"))
        assert r.returncode != 0, r.stdout
        assert '"ok"' not in r.stdout, r.stdout
        assert "needs a TPU" in r.stderr, r.stderr[-2000:]


def test_check_raises_on_failure(monkeypatch):
    monkeypatch.syspath_prepend(ROOT)
    import chip_smoke
    chip_smoke._check(True, "kept")
    with pytest.raises(RuntimeError, match="first loss out of band"):
        chip_smoke._check(False, "first loss out of band")


@pytest.mark.parametrize("logits,token,ok", [
    ([4.0, 3.5, 1.0], 0, True),             # the argmax
    ([4.0, 4.0, 1.0], 1, True),             # an exact tie at the top
    ([4.03125, 4.0, 1.0], 1, True),         # runner-up one unit below
    ([4.03125, 4.03125, 4.0], 2, True),     # runner-up below a tied top
    ([3.84375, 3.8125, 1.0], 1, False),     # two units below
    ([4.03125, 4.0, 4.0], 2, True),         # tied runner-ups
    ([4.0625, 4.03125, 4.0], 2, False),     # third
], ids=["argmax", "tie", "runner_up", "below_tied_top", "two_units",
        "tied_runner_up", "third"])
def test_greedy_pick(monkeypatch, logits, token, ok):
    """The serve check takes the reference's argmax, or its runner-up only
    where the top two lie one bf16 unit apart."""
    import numpy as np
    monkeypatch.syspath_prepend(ROOT)
    import chip_smoke
    assert chip_smoke._greedy_pick(np.array(logits, np.float32), token) == ok


def test_compile_cache_placement(monkeypatch):
    from repro.launch import compile_cache
    was = jax.config.jax_compilation_cache_dir
    try:
        jax.config.update("jax_compilation_cache_dir", None)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
        assert compile_cache.enable_compile_cache() == "/elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir is None
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = compile_cache.enable_compile_cache()
        assert path == os.path.join(os.path.realpath(ROOT), ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        ignored = open(os.path.join(ROOT, ".gitignore")).read().split()
        assert ".jax_cache/" in ignored
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
