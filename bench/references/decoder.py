"""Plain float32 reference of a llama-style decoder trained with AdamW.

Written from the published description, in ``jax.numpy`` alone: token
embedding; per layer RMSNorm, causal grouped-query attention with rotary
positions (half-split), a residual, RMSNorm, and either a SwiGLU MLP or a
top-k mixture of SwiGLU experts; a final RMSNorm and an untied LM head; the
mean next-token cross entropy.  Departures from the published models, kept
because the program under test has them: the embedding and the LM head are
separate matrices; the vocabulary is padded (the padded logits are left
out); a mixture of experts drops the assignments beyond each expert's
capacity, ceil(tokens * k / experts * capacity_factor), in order of token
and then of choice, and adds the Switch load-balancing loss.  Every matmul
runs at ``Precision.HIGHEST``; ``Numerics("float8")`` rounds every matmul's
operands to float8 e4m3 under a per-tensor scale instead, the control;
``Numerics(cut_exchange=True)`` feeds the second half of the layers zeros
in place of the first half's output, the fault of a two-stage pipeline
whose exchange is left out.

Memory is kept to one chip: each layer is rematerialised, attention runs
one sequence at a time, the experts one at a time, and the LM head and loss
one sequence at a time.

A reference module is the one place that knows its family, so that a new
family is a new module and a configuration file that names it under
``reference``.  Beside the reference's mathematics it gives the harness:
``NORMS``, the leaves drawn as gains near one (``bench/weights.py``);
``check_fixed`` and ``program_config``, the configuration file as the
program's ``ModelConfig``; and ``train_flops_per_token``, the model FLOPs
that ``train_step_mfu`` counts.  Only ``program_config`` imports the
program, and only when it is called."""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST
E4M3_MAX = 448.0

# leaves drawn as gains near one, each distinct, so that their gradients
# differ; every other leaf is drawn as a matrix
NORMS = ("ln1", "ln2", "final_norm")

# Hugging Face key -> ModelConfig field
FIELDS = {
    "num_hidden_layers": "n_layers",
    "hidden_size": "d_model",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "vocab_size": "vocab_size",
    "rms_norm_eps": "norm_eps",
    "rope_theta": "rope_theta",
    "num_local_experts": "n_experts",
    "num_experts_per_tok": "experts_per_token",
}
# keys the program has no switch for, and the value its arithmetic implies
FIXED = {
    "tie_word_embeddings": False,
    "hidden_act": "silu",
    "attention_bias": False,
    "mlp_bias": False,
    "embedding_multiplier": 1.0,
    "residual_multiplier": 1.0,
    "logits_scaling": 1.0,
}


class Numerics:
    """How matmul operands are rounded: ``float32`` (none) or ``float8``;
    ``cut_exchange``: see the module's docstring."""

    def __init__(self, mode: str = "float32", cut_exchange: bool = False):
        if mode not in ("float32", "float8"):
            raise ValueError(mode)
        self.mode = mode
        self.cut_exchange = cut_exchange

    def _round(self, x):
        if self.mode == "float32":
            return x
        scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / E4M3_MAX
        q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
        return x + lax.stop_gradient(q - x)

    def dot(self, eq, a, b):
        return jnp.einsum(eq, self._round(a), self._round(b),
                          precision=HIGHEST,
                          preferred_element_type=jnp.float32)


def _dims(mc):
    d, nh = mc["hidden_size"], mc["num_attention_heads"]
    return d, nh, mc["num_key_value_heads"], d // nh


def is_moe(mc) -> bool:
    return mc.get("num_local_experts", 0) > 0


def padded_vocab(mc) -> int:
    m = mc["vocab_pad_multiple"]
    return -(-mc["vocab_size"] // m) * m


def check_fixed(mc) -> None:
    """Keys the program does not model must hold the value its arithmetic
    implies (a multiplier of 1, untied embeddings), so that the file states
    what is run."""
    for k, v in FIXED.items():
        if k in mc and mc[k] != v:
            raise ValueError(f"{mc['name']}: {k}={mc[k]!r} is not what the "
                             f"program runs ({v!r})")
    if "attention_multiplier" in mc and not math.isclose(
            mc["attention_multiplier"], _dims(mc)[3] ** -0.5):
        raise ValueError(f"{mc['name']}: the program scales attention by "
                         f"1/sqrt(head_dim)")


def program_config(mc):
    """The program's ``ModelConfig`` for configuration file ``mc``, whose
    ``arch`` names the program's architecture id."""
    from repro.configs import get_config
    from repro.configs.base import VOCAB_PAD_TO

    check_fixed(mc)
    if mc["vocab_pad_multiple"] != VOCAB_PAD_TO:
        raise ValueError("the program pads the vocabulary to a multiple of "
                         f"{VOCAB_PAD_TO}")
    kw = {f: mc[k] for k, f in FIELDS.items() if k in mc}
    kw["head_dim"] = _dims(mc)[3]
    kw["tie_embeddings"] = False
    if is_moe(mc):
        kw["moe_d_ff"] = kw["d_ff"] = mc["intermediate_size"]
        kw["router_aux_loss"] = mc["router_aux_loss_coef"]
    else:
        kw["d_ff"] = mc["intermediate_size"]
    cfg = dataclasses.replace(get_config(mc["arch"]), **kw)
    if cfg.vocab_padded != padded_vocab(mc):
        raise ValueError("padded vocabulary differs")
    return cfg


# Model FLOPs from the configuration's shapes: the operations that the
# forward and backward passes require, not what a program computes (no
# recomputation, no capacity padding, no masked-out attention, no vocabulary
# padding).  Training counts 6 per matmul parameter touched per token (2 for
# the forward pass, 4 for the backward), plus causal attention.

def matmul_params_per_token(mc) -> int:
    """Matmul parameters one token passes through: the attention
    projections, the feed-forward (for a mixture of experts its router and
    its ``num_experts_per_tok`` experts), and the LM head.  The embedding is
    a lookup."""
    d, nh, nkv, hd = _dims(mc)
    ff = mc["intermediate_size"]
    attn = 2 * d * nh * hd + 2 * d * nkv * hd
    if is_moe(mc):
        mlp = d * mc["num_local_experts"] + mc["num_experts_per_tok"] * 3 * d * ff
    else:
        mlp = 3 * d * ff
    return mc["num_hidden_layers"] * (attn + mlp) + d * mc["vocab_size"]


def attention_flops_per_token(mc, seq: int) -> int:
    """Causal attention of a ``seq``-long sequence, forward and backward,
    averaged over its tokens: (seq + 1) / 2 keys per query, 4 * heads *
    head_dim per key forward (scores and values), times 3."""
    _, nh, _, hd = _dims(mc)
    return mc["num_hidden_layers"] * 6 * nh * hd * (seq + 1)


def train_flops_per_token(mc, seq: int) -> int:
    return 6 * matmul_params_per_token(mc) + attention_flops_per_token(mc, seq)


def param_shapes(mc) -> dict:
    d, nh, nkv, hd = _dims(mc)
    L, ff, vp = mc["num_hidden_layers"], mc["intermediate_size"], padded_vocab(mc)
    layers = {"ln1": (L, d), "ln2": (L, d),
              "attn": {"wq": (L, d, nh * hd), "wk": (L, d, nkv * hd),
                       "wv": (L, d, nkv * hd), "wo": (L, nh * hd, d)}}
    e = mc.get("num_local_experts", 0)
    if e:
        layers["moe"] = {"router": (L, d, e), "wi": (L, e, d, ff),
                         "wg": (L, e, d, ff), "wo": (L, e, ff, d)}
    else:
        layers["mlp"] = {"wi": (L, d, ff), "wg": (L, d, ff), "wo": (L, ff, d)}
    return {"embed": (vp, d), "final_norm": (d,), "lm_head": (d, vp),
            "layers": layers}


def rms_norm(x, g, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def rope(x, theta):
    """x: (..., T, H, hd); position t rotates pair (i, i + hd/2) by
    t * theta^(-2i/hd)."""
    t, hd = x.shape[-3], x.shape[-1]
    half = hd // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2 / hd)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(num, q, k, v):
    """One sequence.  q: (T, H, hd); k, v: (T, KV, hd); query head h reads
    key/value head h // (H / KV)."""
    t, h, hd = q.shape
    rep = h // k.shape[1]
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    s = num.dot("qhd,khd->hqk", q, k) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((t, t), bool))
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    return num.dot("hqk,khd->qhd", p, v)


def swiglu(num, x, wi, wg, wo):
    h = jax.nn.silu(num.dot("td,df->tf", x, wg)) * num.dot("td,df->tf", x, wi)
    return num.dot("tf,fd->td", h, wo)


def moe(num, mc, p, x, capacity_factor):
    """x: (t, d) -> (out (t, d), Switch aux loss)."""
    t = x.shape[0]
    e, k = mc["num_local_experts"], mc["num_experts_per_tok"]
    probs = jax.nn.softmax(num.dot("td,de->te", x, p["router"]), axis=-1)
    w, ids = lax.top_k(probs, k)
    w = w / w.sum(-1, keepdims=True)
    onehot = jax.nn.one_hot(ids.reshape(-1), e, dtype=jnp.int32)   # (t*k, e)
    rank = ((jnp.cumsum(onehot, axis=0) - 1) * onehot).sum(-1)
    cap = math.ceil(t * k / e * capacity_factor)
    kept = (rank < cap).reshape(t, k)
    comb = jnp.zeros((t, e), jnp.float32).at[
        jnp.arange(t)[:, None], ids].add(jnp.where(kept, w, 0.0))
    aux = e * jnp.sum(onehot.sum(0) / (t * k) * probs.mean(0))

    @jax.checkpoint
    def expert(acc, ew):
        wi, wg, wo, c = ew
        return acc + c[:, None] * swiglu(num, x, wi, wg, wo), None

    out, _ = lax.scan(expert, jnp.zeros_like(x),
                      (p["wi"], p["wg"], p["wo"], comb.T))
    return out, aux


def layer(num, mc, lp, x):
    b, t, d = x.shape
    _, nh, nkv, hd = _dims(mc)
    eps = mc["rms_norm_eps"]
    h = rms_norm(x, lp["ln1"], eps)
    a = lp["attn"]
    q = rope(num.dot("btd,de->bte", h, a["wq"]).reshape(b, t, nh, hd),
             mc["rope_theta"])
    k = rope(num.dot("btd,de->bte", h, a["wk"]).reshape(b, t, nkv, hd),
             mc["rope_theta"])
    v = num.dot("btd,de->bte", h, a["wv"]).reshape(b, t, nkv, hd)
    o = lax.map(jax.checkpoint(lambda qkv: attention(num, *qkv)), (q, k, v))
    x = x + num.dot("bte,ed->btd", o.reshape(b, t, nh * hd), a["wo"])
    h = rms_norm(x, lp["ln2"], eps).reshape(b * t, d)
    if "moe" in lp:
        y, aux = moe(num, mc, lp["moe"], h, mc["capacity_factor"])
    else:
        m = lp["mlp"]
        y, aux = swiglu(num, h, m["wi"], m["wg"], m["wo"]), jnp.zeros(())
    return x + y.reshape(b, t, d), aux


def loss(num, mc, params, tokens, labels):
    """(cross entropy + aux loss, cross entropy); tokens, labels: (B, T)."""
    x = params["embed"][tokens]

    @jax.checkpoint
    def body(x, lp):
        return layer(num, mc, lp, x)

    if num.cut_exchange:
        half = mc["num_hidden_layers"] // 2
        first, second = (jax.tree.map(lambda a: a[s], params["layers"])
                         for s in (slice(None, half), slice(half, None)))
        x, aux1 = lax.scan(body, x, first)
        x, aux2 = lax.scan(body, jnp.zeros_like(x), second)
        aux = jnp.concatenate([aux1, aux2])
    else:
        x, aux = lax.scan(body, x, params["layers"])
    w = params["lm_head"][:, :mc["vocab_size"]]

    @jax.checkpoint
    def row(xl):
        xr, lr = xl
        logits = num.dot("td,dv->tv",
                         rms_norm(xr, params["final_norm"],
                                  mc["rms_norm_eps"]), w)
        gold = jnp.take_along_axis(logits, lr[:, None], axis=-1)[:, 0]
        return jnp.sum(jax.nn.logsumexp(logits, axis=-1) - gold)

    ce = lax.map(row, (x, labels)).sum() / labels.size
    return ce + mc.get("router_aux_loss_coef", 0.0) * aux.sum(), ce


def learning_rate(opt: dict, step: int) -> float:
    """Linear warm-up to ``lr`` over ``warmup_steps``, then cosine decay to
    a tenth of it by ``total_steps``."""
    lr, w, n = opt["lr"], opt["warmup_steps"], opt["total_steps"]
    if step < w:
        return lr * (step + 1) / max(w, 1)
    prog = min(max((step - w) / max(n - w, 1), 0.0), 1.0)
    return lr * (0.1 + 0.9 * 0.5 * (1 + math.cos(math.pi * prog)))


def grads(num, mc, params, tokens, labels, block_rows=None):
    """(cross entropy, gradients of the total loss).  ``block_rows`` splits
    the batch into blocks of that many sequences, one at a time, and
    averages their gradients: the same mean for a dense model, whose rows
    do not interact (a mixture of experts shares each expert's capacity
    across the batch, so it takes the whole batch at once)."""
    def one(p, t, lb):
        (_, ce), g = jax.value_and_grad(
            lambda q: loss(num, mc, q, t, lb), has_aux=True)(p)
        return ce, g

    b = tokens.shape[0]
    if not block_rows or block_rows >= b:
        return one(params, tokens, labels)
    if is_moe(mc) or b % block_rows:
        raise ValueError("blocks of rows need a dense model and equal blocks")
    n = b // block_rows

    def body(acc, tl):
        ce, g = one(params, *tl)
        return jax.tree.map(jnp.add, acc, g), ce

    split = lambda x: x.reshape(n, block_rows, *x.shape[1:])  # noqa: E731
    g, ces = lax.scan(body, jax.tree.map(jnp.zeros_like, params),
                      (split(tokens), split(labels)))
    return ces.mean(), jax.tree.map(lambda x: x / n, g)


def make_step(mc, opt, num, grad_norms, block_rows=None):
    """Jitted AdamW step with global-norm clipping:
    ``(params, m, v, t, lr, tokens, labels) -> (params, m, v, ce, norms)``,
    where ``norms = grad_norms(grads)`` of the gradients as the optimizer
    gets them (after clipping)."""
    b1, b2, eps = opt["b1"], opt["b2"], opt["eps"]
    wd, clip = opt["weight_decay"], opt["clip_norm"]

    def step(params, m, v, t, lr, tokens, labels):
        ce, g = grads(num, mc, params, tokens, labels, block_rows)
        norm = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g)))
        g = jax.tree.map(lambda x: x * jnp.minimum(
            1.0, clip / jnp.maximum(norm, 1e-9)), g)
        m = jax.tree.map(lambda a, x: b1 * a + (1 - b1) * x, m, g)
        v = jax.tree.map(lambda a, x: b2 * a + (1 - b2) * x * x, v, g)
        bc1, bc2 = 1 - b1 ** (t + 1.0), 1 - b2 ** (t + 1.0)
        params = jax.tree.map(
            lambda p, a, s: p - lr * (a / bc1) / (jnp.sqrt(s / bc2) + eps)
            - lr * wd * p, params, m, v)
        return params, m, v, ce, grad_norms(g)

    return jax.jit(step, donate_argnums=(0, 1, 2))
