"""Names of the train step's layers.

Each name is opened as a ``jax.named_scope`` inside the function that
computes its layer, so every instruction of the compiled step carries it in
the ``op_name`` of its metadata: in the forward, the rematerialised forward
and the backward (``transpose(jvp(...))`` wraps the same names).  A scope
changes metadata only; the compiled program is the same without it.  The
benchmark's trace reduction (``bench/scopes.py``) sums device time by these
names.

==================  =========================================================
name                what it wraps
==================  =========================================================
``embed``           the token embedding lookup
``attn.proj``       the ``ln1`` norm, q/k/v/o projections, rope, residual add
``attn.core``       scores, mask, softmax and the weighted sum of values
``mlp``             the ``ln2`` norm, the dense MLP and its residual add
``moe.router``      the ``ln2`` norm and top-k routing of an MoE block
``moe.dispatch``    sorting, ranking and gathering tokens into capacity slots
``moe.experts``     the routed experts' einsums and the shared expert
``moe.combine``     the weighted gather back, scatter-add and residual add
``head``            the final norm and the LM head
``loss``            the cross entropy
``optim``           gradient clipping and the optimizer update
``pipe.exchange``   the pipeline's inter-stage ``ppermute`` rings
``grad_sync``       the data-parallel gradient reduction
==================  =========================================================
"""
from __future__ import annotations

import functools

import jax

EMBED = "embed"
ATTN_PROJ = "attn.proj"
ATTN_CORE = "attn.core"
MLP = "mlp"
MOE_ROUTER = "moe.router"
MOE_DISPATCH = "moe.dispatch"
MOE_EXPERTS = "moe.experts"
MOE_COMBINE = "moe.combine"
HEAD = "head"
LOSS = "loss"
OPTIM = "optim"
PIPE_EXCHANGE = "pipe.exchange"
GRAD_SYNC = "grad_sync"

NAMES = (EMBED, ATTN_PROJ, ATTN_CORE, MLP, MOE_ROUTER, MOE_DISPATCH,
         MOE_EXPERTS, MOE_COMBINE, HEAD, LOSS, OPTIM, PIPE_EXCHANGE,
         GRAD_SYNC)


def scope(name: str):
    """Context manager that names the ops traced inside it ``name``."""
    return jax.named_scope(name)


def scoped(name: str):
    """Decorator form of ``scope``: each call runs inside scope ``name``."""
    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with scope(name):
                return fn(*args, **kwargs)
        return inner
    return wrap
