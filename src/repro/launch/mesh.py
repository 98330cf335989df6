"""Production mesh builders.

Functions, not module-level constants — importing this module never touches
jax device state (the dry-run sets XLA_FLAGS before any jax init).  Meshes
come from ``parallel.jaxcompat.make_mesh`` (``Auto`` axis types).
"""
from __future__ import annotations

from repro.parallel.jaxcompat import make_mesh as _make_mesh


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 16x16 = 256 chips ("data", "model").  Multi-pod: 2 pods =
    512 chips ("pod", "data", "model"); DP spans pod x data, MP stays
    intra-pod (DESIGN.md §5)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_mesh(dp: int, mp: int, pods: int = 1):
    """Arbitrary hybrid mesh: the planner's (pod, N, M) factorization."""
    if pods > 1:
        return _make_mesh((pods, dp, mp), ("pod", "data", "model"))
    return _make_mesh((dp, mp), ("data", "model"))


def make_host_mesh():
    """1-device mesh for CPU tests."""
    return _make_mesh((1, 1), ("data", "model"))
