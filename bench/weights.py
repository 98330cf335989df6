"""The benchmark's weights: made from the seed on the device, in one jitted
call, in the layout a reference's ``param_shapes`` gives (the program's own
layout, which the harness checks), with the reference's ``NORMS`` drawn as
gains.  The program and the reference both take these weights; neither
makes its own."""
from __future__ import annotations

import zlib


def _leaf_init(path: str, key, shape, norms):
    import jax
    import jax.numpy as jnp

    name = path.rsplit("/", 1)[-1]
    if name in norms:
        # gains near one, each distinct, so that their gradients differ
        return 1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
    if name == "embed":
        return 0.02 * jax.random.normal(key, shape, jnp.float32)
    fan_in = shape[-2]
    return jax.random.normal(key, shape, jnp.float32) / fan_in ** 0.5


def flatten(tree, prefix: str = ""):
    """Nested dict -> {"a/b/c": leaf}, in sorted key order."""
    out = {}
    for k in sorted(tree):
        v = tree[k]
        p = f"{prefix}/{k}" if prefix else k
        if isinstance(v, dict):
            out.update(flatten(v, p))
        else:
            out[p] = v
    return out


def unflatten(flat: dict) -> dict:
    out = {}
    for path, v in flat.items():
        node = out
        *parents, last = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[last] = v
    return out


def make_params_fn(shapes: dict, norms):
    """``seed_key -> params`` (float32) for the nested dict of shapes; a
    leaf whose last name is in ``norms`` is a gain near one."""
    import jax

    flat = flatten(shapes)

    def make(key):
        return unflatten({
            path: _leaf_init(path, jax.random.fold_in(
                key, zlib.crc32(path.encode())), tuple(shape), norms)
            for path, shape in flat.items()})

    return make
