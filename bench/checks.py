"""The comparison that decides a training cell's ``correct``.

Three readings are taken from the program and from the reference, over the
same weights and batches: the cross entropy of each checked step, the norm
of each leaf of the first gradient as the optimizer gets it (the program's
is worked out from AdamW's first moment after one step, m / (1 - b1)), and
the norm of each leaf's change over the checked steps.  A leaf is a
parameter array, and one layer's slice of a stacked layer array.  Each gap
is the gap between the two norms, not the norm of their difference, over
the reference's norm of that leaf or of the median leaf, whichever is
larger; the worst leaf counts.  Leaves whose reference gradient is under a
thousandth of the median leaf's move by round-off alone and are left out of
the change."""
from __future__ import annotations

import statistics

import numpy as np

EXCLUDE_BELOW = 1e-3


def leaf_norms(tree) -> dict:
    """Per-leaf L2 norms, on the device; a stacked layer array (under
    ``layers/``) gives one norm per layer."""
    import jax.numpy as jnp

    from bench.weights import flatten

    out = {}
    for path, x in flatten(tree).items():
        x = x.astype(jnp.float32)
        if path.startswith("layers/"):
            out[path] = jnp.sqrt(jnp.sum(x * x, axis=tuple(range(1, x.ndim))))
        else:
            out[path] = jnp.sqrt(jnp.sum(x * x))
    return out


def expand(norms: dict) -> dict:
    """Device norms -> {leaf name: float}."""
    out = {}
    for path, v in norms.items():
        v = np.asarray(v, dtype=np.float64)
        if v.ndim:
            for i, x in enumerate(v):
                out[f"{path}[{i}]"] = float(x)
        else:
            out[path] = float(v)
    return out


def worst_gap(prog: dict, ref: dict, keys) -> tuple:
    keys = sorted(keys)
    med = statistics.median(ref[k] for k in keys)
    gaps = {k: abs(prog[k] - ref[k]) / max(ref[k], med, 1e-30) for k in keys}
    k = max(keys, key=lambda k: (not np.isfinite(gaps[k]), gaps[k]))
    return gaps[k], k


def compare(prog: dict, ref: dict) -> dict:
    """Readings {"losses": [...], "grad": {...}, "change": {...}} of the
    program and of the reference -> the numbers compared, with the worst
    leaf of each."""
    if set(prog["grad"]) != set(ref["grad"]):
        raise ValueError("the program's leaves differ from the reference's")
    loss_gap = max(abs(a - b) / abs(b)
                   for a, b in zip(prog["losses"], ref["losses"]))
    if not np.isfinite(loss_gap):
        loss_gap = float("inf")
    grad_gap, grad_leaf = worst_gap(prog["grad"], ref["grad"], ref["grad"])
    g_med = statistics.median(ref["grad"].values())
    moved = [k for k, g in ref["grad"].items() if g >= EXCLUDE_BELOW * g_med]
    change_gap, change_leaf = worst_gap(prog["change"], ref["change"], moved)
    return {"loss_gap": loss_gap, "grad_gap": grad_gap,
            "change_gap": change_gap,
            "leaves": {"grad": grad_leaf, "change": change_leaf,
                       "excluded": len(ref["grad"]) - len(moved)}}


def judge(numbers: dict, limits: dict) -> tuple:
    """(correct, {name: {"value", "limit"}}) for every number with a limit."""
    out, ok = {}, True
    for name, limit in limits.items():
        v = numbers[name]
        out[name] = {"value": v, "limit": limit}
        ok = ok and bool(np.isfinite(v)) and v <= limit
    return ok, out


class Reference:
    """The reference trained from the seed's weights and read as the
    program is; its step is compiled once for all seeds.  ``block_rows``:
    see the reference's ``grads``."""

    def __init__(self, ref, mc: dict, opt: dict, num, make_params,
                 block_rows=None):
        self.ref, self.opt, self.make_params = ref, opt, make_params
        self.step = ref.make_step(mc, opt, num, leaf_norms, block_rows)
        self.change = change_norms(make_params)

    def readings(self, key, batches) -> dict:
        """Train over ``batches`` (one (tokens, labels) pair per checked
        step) and read it; frees its state."""
        import jax
        import jax.numpy as jnp

        params = jax.jit(self.make_params)(key)
        zeros = jax.jit(lambda p: jax.tree.map(jnp.zeros_like, p))
        m, v = zeros(params), zeros(params)
        losses, grad = [], None
        for t, (tokens, labels) in enumerate(batches):
            lr = self.ref.learning_rate(self.opt, t)
            params, m, v, ce, gn = self.step(params, m, v, jnp.float32(t),
                                             jnp.float32(lr), tokens, labels)
            losses.append(float(ce))
            if grad is None:
                grad = expand(gn)
        change = expand(self.change(params, key))
        for x in jax.tree.leaves((params, m, v)):
            x.delete()
        return {"losses": losses, "grad": grad, "change": change}


def change_norms(make_params):
    """Jitted ``(params, key) -> leaf norms of params - make_params(key)``:
    the seed's weights are made anew inside, not kept."""
    import jax

    return jax.jit(lambda p, key: leaf_norms(
        jax.tree.map(lambda a, b: a - b, p, make_params(key))))
