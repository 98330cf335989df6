"""Loading the manifest and the files it names, seeds, and the compile cache."""
from __future__ import annotations

import importlib.util
import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def load_json(path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path, name: str):
    """Import a Python file by path (names may hold dots, as metric names do)."""
    path = Path(path)
    if not path.is_file():
        raise FileNotFoundError(path)
    spec = importlib.util.spec_from_file_location(
        "bench_dyn_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


class Bench:
    """``BENCHMARK.json`` at ``root`` and the files it names under ``root``."""

    def __init__(self, root=ROOT):
        self.root = Path(root)
        self.manifest = load_json(self.root / "BENCHMARK.json")

    def cell(self, name: str) -> dict:
        for w in self.manifest["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")

    def config(self, name: str) -> dict:
        for c in self.manifest["configs"]:
            if c["name"] == name:
                return load_json(self.root / c["file"])
        raise KeyError(f"no config {name!r} in BENCHMARK.json")

    def traffic(self, name: str) -> dict:
        return load_json(self.root / "bench" / "traffic" / f"{name}.json")

    def limits(self, cell: str) -> dict:
        return load_json(self.root / "bench" / "limits" / f"{cell}.json")

    def generator(self, kind: str):
        return load_module(self.root / "bench" / "traffic" / f"{kind}.py", kind)

    def driver(self, name: str):
        return load_module(self.root / "bench" / "drivers" / f"{name}.py", name)

    def reference(self, name: str):
        return load_module(self.root / "bench" / "references" / f"{name}.py",
                           name)

    def reader(self, metric: str):
        return load_module(self.root / "bench" / "metrics" / f"{metric}.py",
                           metric)

    def metrics_of(self, cell: str, section: str) -> list:
        """The ``section`` ("end_to_end" or "per_layer") metrics this cell
        reports: those without a ``workloads`` key, and those listing it."""
        return [m for m in self.manifest[section]
                if "workloads" not in m or cell in m["workloads"]]


def seed_words(seed: int) -> tuple:
    """A seed of any size as two 32-bit words (high, low)."""
    s = int(seed) % 2**64
    return (s >> 32) & 0xFFFFFFFF, s & 0xFFFFFFFF


def seed_key(seed: int):
    """A JAX key from all 64 bits of ``seed`` (``PRNGKey`` keeps only 32)."""
    import jax.numpy as jnp
    return jnp.asarray(seed_words(seed), dtype=jnp.uint32)


def host_rng(seed: int, *stream: int):
    import numpy as np
    hi, lo = seed_words(seed)
    return np.random.default_rng([hi, lo, *stream])


def use_repo_sources(root=ROOT) -> None:
    """Put the program (``<root>/src``) on the import path."""
    src = str(Path(root) / "src")
    if src not in sys.path:
        sys.path.insert(0, src)


def enable_compile_cache(root=ROOT) -> str:
    """JAX's persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR`` where
    it is set, else the fixed directory ``<root>/.jax_cache``.  Every program
    is cached, however quick its compile, so that only a checkout's first run
    compiles."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        Path(root) / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path
