"""Smoke run of the trainer and the continuous engine on TPU at SmolLM-360M's
full published width.

    python chip_smoke.py             # one chip: train, then serve
    python chip_smoke.py --chips 4   # four chips: the layouts compared

One chip (the default) drives the two main paths through the entry points a
user calls, in this one process (a chip belongs to one process):

1. train: ``launch.train.main`` on ``smollm_360m`` at full width, batch 8 x
   seq 2048, one device, AdamW, random init from seed 0.  Every loss is
   finite, the first lies within ``FIRST_LOSS_BAND`` of ln(vocab), and the
   last is below the first;
2. serve: ``launch.serve.main --continuous`` with 8 slots, 8 requests of 512
   prompt tokens, 64 new tokens, prefill chunks of 256, greedy.  Every
   request completes with 64 tokens, and each first generated token is an
   argmax of a plain full-sequence forward over its prompt on the same chip,
   or its runner-up where the forward's top two logits lie one bf16 unit
   apart.  That forward attends through the dense XLA path, as the engine's
   chunked prefill does, and not through the flash kernel the train step
   takes.

``--chips 4`` runs only the four-chip comparison: ``--parallel dp=4`` against
``--parallel pipe=2,micro=4,sched=1f1b,dp=2`` at the same global batch,
steps, seed and data (each mesh on 4 distinct devices, losses equal step for
step within ``LOSS_RTOL``), then four one-chip ``ReplicaRouter`` replicas
against one engine (the same greedy tokens, each replica on its own device).

Lines before the last report each phase (compile and step seconds, losses,
tokens/s, peak device memory, the attention calls the train step traced on
each path of ``models.layers.attention``); they are informational.  The
last line of stdout is ``{"ok": true, "device": {...}}``, printed only when
every check passed.  A failed check raises, and without a TPU the script
exits non-zero before any phase runs.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import os
import statistics
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ARCH = "smollm_360m"
# random init gives unit-variance logits: the first loss sits near
# ln(vocab) + 1/2
FIRST_LOSS_BAND = 1.0
# dp=4 against pipe=2 x dp=2: the same math in bf16 with other reduction
# orders (a 4-way gradient all-reduce against 4 pipelined micro-batches)
LOSS_RTOL = 1e-2
# significant bits of a bfloat16: one unit at x is 2**(exponent(x) - 8)
BF16_BITS = 8


def _check(ok: bool, what) -> None:
    """A failed check raises (and is kept under ``python -O``)."""
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {what}")


def _greedy_pick(row, token: int) -> bool:
    """Whether ``token`` is a greedy pick of the reference logits ``row``
    (bf16 values held as float32): an argmax, exact ties counting, or the
    runner-up where the top two lie one bf16 unit apart.  The reference
    attends over other shapes than the engine's prefill chunks, so such a
    near-tie may round either way."""
    top = float(row.max())
    if row[token] == top:
        return True
    below = float(row[row < top].max())
    unit = math.ldexp(1.0, math.frexp(top)[1] - BF16_BITS)
    return row[token] == below and top - below <= unit


def _peak_gib(device) -> str:
    stats = device.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "not reported" if peak is None else f"{peak / 2**30:.2f} GiB"


def train_phase(*, reduced: bool = False, batch: int = 8, seq: int = 2048,
                steps: int = 12, parallel: str = "dp=1,mp=1") -> dict:
    """Train through ``launch.train.main`` and check the losses; returns
    the loop summary (no train state: its buffers are freed on return)."""
    import jax

    from repro.configs import get_config
    from repro.launch.train import main as train_main
    from repro.models.layers import count_attention_paths

    argv = ["--arch", ARCH, "--batch", str(batch), "--seq", str(seq),
            "--steps", str(steps), "--parallel", parallel]
    with count_attention_paths() as paths:
        out = train_main(argv + (["--reduced"] if reduced else []))
    cfg = get_config(ARCH)
    vocab = (cfg.reduced() if reduced else cfg).vocab_size
    losses = out["history"]
    _check(len(losses) == steps, (len(losses), steps))
    _check(all(math.isfinite(x) for x in losses), losses)
    _check(abs(losses[0] - math.log(vocab)) < FIRST_LOSS_BAND,
           (losses[0], math.log(vocab)))
    _check(losses[-1] < losses[0], losses)
    step_s = statistics.median(out["step_s"][1:]) if steps > 1 else 0.0
    print(f"[smoke] train {parallel}: first step (compile + run) "
          f"{out['step_s'][0]:.2f}s, median step {step_s:.4f}s, "
          f"{batch * seq / step_s if step_s else 0.0:.0f} tok/s, "
          f"losses {[round(x, 4) for x in losses]}, "
          f"process peak {_peak_gib(jax.devices()[0])}", flush=True)
    print(f"[smoke] train {parallel}: attention calls traced by path "
          f"{dict(sorted(paths.items()))}", flush=True)
    return out


def _serve_argv(reduced: bool, requests: int, prompt_len: int, max_new: int,
                slots: int, prefill_chunk: int):
    return (["--arch", ARCH, "--continuous", "--slots", str(slots),
             "--batch", str(requests), "--prompt-len", str(prompt_len),
             "--max-new", str(max_new), "--prefill-chunk", str(prefill_chunk),
             "--temperature", "0"] + (["--reduced"] if reduced else []))


def _check_completed(out: dict, requests: int, max_new: int):
    results = out["results"]
    rids = [r.rid for r in results]
    _check(rids == list(range(requests)), rids)
    for r in results:
        _check(r.finished_reason == "length" and len(r.tokens) == max_new,
               (r.rid, r.finished_reason, len(r.tokens)))


def serve_phase(*, reduced: bool = False, requests: int = 8,
                prompt_len: int = 512, max_new: int = 64, slots: int = 8,
                prefill_chunk: int = 256) -> dict:
    """Serve through ``launch.serve.main --continuous`` and check each
    request against a plain full-sequence forward over its prompt."""
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.launch.serve import main as serve_main
    from repro.models.layers import count_attention_paths
    from repro.models.transformer import forward

    out = serve_main(_serve_argv(reduced, requests, prompt_len, max_new,
                                 slots, prefill_chunk))
    _check_completed(out, requests, max_new)
    cfg = get_config(ARCH)
    cfg = cfg.reduced() if reduced else cfg
    # the forward runs over the prompt and one token more: causal, so the
    # prompt's last logits do not see that token, and a length that is no
    # multiple of 128 keeps the flash kernel out of the reference
    last = jax.jit(lambda p, t: forward(cfg, p, {"tokens": t}, mode="train",
                                        remat=False)[0][:, -2])
    prompts = out["prompts"]
    with count_attention_paths() as paths:
        ref = jax.device_get(last(out["params"], jnp.concatenate(
            [prompts, prompts[:, :1]], axis=1)).astype(jnp.float32))
    _check(set(paths) == {"dense"}, dict(paths))
    picks = [(r.rid, r.tokens[0], int(row.argmax()), float(row.max()),
              float(row[r.tokens[0]])) for r, row in zip(out["results"], ref)]
    _check(all(_greedy_pick(row, r.tokens[0])
               for r, row in zip(out["results"], ref)), picks)
    ties = [p for p in picks if p[3] != p[4]]
    print(f"[smoke] serve continuous: {out['n_tokens']} tokens in "
          f"{out['wall_s']:.2f}s incl. compile "
          f"({out['n_tokens'] / out['wall_s']:.1f} tok/s), first tokens "
          f"{[r.tokens[0] for r in out['results']]} match the full forward "
          f"(runner-ups in one-unit ties, as (rid, token, argmax, max, "
          f"logit): {ties}), "
          f"process peak {_peak_gib(jax.devices()[0])}", flush=True)
    return out


def four_chip_phase(*, reduced: bool = False, batch: int = 8,
                    seq: int = 2048, steps: int = 4, requests: int = 8,
                    prompt_len: int = 512, max_new: int = 16,
                    slots: int = 8, prefill_chunk: int = 256) -> None:
    """DP alone against hybrid DP x pipeline MP on 4 devices, then 4
    one-device router replicas against one engine."""
    import jax

    from repro.launch.serve import main as serve_main

    runs = {}
    for spec in ("dp=4", "pipe=2,micro=4,sched=1f1b,dp=2"):
        out = train_phase(reduced=reduced, batch=batch, seq=seq, steps=steps,
                          parallel=spec)
        _check(len(set(out["devices"])) == 4, (spec, out["devices"]))
        runs[spec] = out["history"]
        gc.collect()
    dp, pp = runs.values()
    worst = max(abs(a - b) / abs(a) for a, b in zip(dp, pp))
    _check(worst <= LOSS_RTOL, (dp, pp))
    print(f"[smoke] dp=4 vs pipe=2 x dp=2: losses agree step for step, "
          f"worst relative gap {worst:.2e} (bound {LOSS_RTOL:.0e})",
          flush=True)

    argv = _serve_argv(reduced, requests, prompt_len, max_new, slots,
                       prefill_chunk)
    one = serve_main(argv)
    _check_completed(one, requests, max_new)
    one = [r.tokens for r in one["results"]]
    gc.collect()
    four = serve_main(argv + ["--replicas", "4"])
    _check_completed(four, requests, max_new)
    devs = [jax.tree.leaves(e.params)[0].devices() for e in four["engines"]]
    _check(all(len(d) == 1 for d in devs), devs)
    _check(len(set.union(*map(set, devs))) == 4, devs)
    _check([r.tokens for r in four["results"]] == one,
           "router tokens differ from the 1-engine run")
    print(f"[smoke] router: 4 replicas on devices "
          f"{[next(iter(d)).id for d in devs]}, greedy tokens equal the "
          f"1-engine run", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {devices[0].platform}",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, JAX found {len(devices)}", file=sys.stderr)
        return 1

    from repro.launch.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    warm = os.path.isdir(cache) and bool(os.listdir(cache))
    print(f"[smoke] compile cache {cache} ({'warm' if warm else 'cold'})",
          flush=True)
    if args.chips == 4:
        four_chip_phase()
    else:
        train_phase()
        gc.collect()      # two train states do not fit next to the server
        serve_phase()
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
