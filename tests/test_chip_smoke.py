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
