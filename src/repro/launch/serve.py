"""Serving launcher: load/initialize a model and decode batched requests.

Static batch (the classic path)::

    python -m repro.launch.serve --arch llama3_2_1b --reduced \
        --batch 4 --prompt-len 32 --max-new 16

Continuous batching over a slotted KV cache, optionally with the decode
tick on a dp x tp mesh (forced host devices work for CPU smoke runs)::

    python -m repro.launch.serve --arch llama3_2_1b --reduced \
        --continuous --slots 4 --tp 2 --prefill-chunk 8 \
        --batch 8 --prompt-len 32 --max-new 16

Multi-replica with fault injection (``serve.router.ReplicaRouter``:
least-loaded dispatch, health-checked failover, bounded queues).  Replica
``r`` runs on devices ``[r*tp, (r+1)*tp)``, so ``--replicas N --tp T``
needs N*T devices (chips, or forced host devices on CPU)::

    XLA_FLAGS=--xla_force_host_platform_device_count=2 \
    python -m repro.launch.serve --arch llama3_2_1b --reduced \
        --continuous --replicas 2 --slots 4 --max-queue 16 \
        --fault "kill@5:0" --batch 8 --prompt-len 32 --max-new 16

``main(argv)`` returns what it served (see its docstring), so a script can
drive it in-process.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models.api import build_model
from repro.serve.continuous import ContinuousEngine, Request
from repro.serve.engine import ServeEngine


def main(argv=None) -> dict:
    """Run the serving CLI on ``argv`` (default ``sys.argv[1:]``).  Returns
    ``prompts`` (int32 (batch, prompt_len)), ``params``, ``wall_s`` and
    ``n_tokens``; the continuous paths add ``results`` (``RequestResult``s
    by rid) and ``engines`` (one ``ContinuousEngine`` per replica), the
    router path its ``stats``, the static path the generated ``tokens``."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4,
                    help="requests (continuous) / batch rows (static)")
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--continuous", action="store_true",
                    help="slotted continuous-batching engine")
    ap.add_argument("--slots", type=int, default=4,
                    help="request slots (continuous engine)")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="max prompt tokens per prefill step (0 = one shot)")
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-MP ways for the decode tick (needs >= tp "
                    "devices; use XLA_FLAGS=--xla_force_host_platform_"
                    "device_count=N on CPU)")
    ap.add_argument("--replicas", type=int, default=1,
                    help="independent continuous-engine replica groups "
                    "behind the fault-tolerant router, each on its own tp "
                    "devices")
    ap.add_argument("--fault", default="",
                    help="replica-keyed fault schedule, e.g. "
                    "'kill@5:0, stall@7:1:0.5, nanlogits@9:0'")
    ap.add_argument("--max-queue", type=int, default=0,
                    help="bound on queued requests per replica; overflow "
                    "is shed (0 = unbounded)")
    ap.add_argument("--watchdog", type=float, default=0.0,
                    help="router health watchdog seconds (0 = off). Leave "
                    "off on cold CPU runs: every distinct prefill-chunk "
                    "shape retraces for seconds and reads as a stall")
    args = ap.parse_args(argv)
    enable_compile_cache()
    dev = jax.devices()[0]
    print(f"[device] {dev.platform} {dev.device_kind} x{jax.device_count()}")

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    api = build_model(cfg, remat=False)
    key = jax.random.PRNGKey(0)
    params = api.init(key)

    tokens = jax.random.randint(
        key, (args.batch, args.prompt_len), 0, cfg.vocab_size, dtype=jnp.int32)

    if args.continuous:
        capacity = args.prompt_len + args.max_new + 8
        reqs = [Request(rid=i, tokens=[int(t) for t in tokens[i]],
                        max_new_tokens=args.max_new)
                for i in range(args.batch)]
        if args.replicas > 1 or args.fault or args.max_queue:
            from repro.serve.router import ReplicaRouter, replica_meshes
            from repro.train.fault import parse_fault_schedule
            meshes = replica_meshes(args.replicas, args.tp)
            model_axis, batch_axes = (("model", ("data",)) if args.tp > 1
                                      else (None, ()))
            router = ReplicaRouter(
                api, params, replicas=args.replicas, n_slots=args.slots,
                capacity=capacity, prefill_chunk=args.prefill_chunk,
                temperature=args.temperature, meshes=meshes,
                model_axis=model_axis, batch_axes=batch_axes,
                max_queue=args.max_queue or None,
                faults=parse_fault_schedule(args.fault) if args.fault else (),
                watchdog_timeout_s=args.watchdog or None, log_fn=print)
            t0 = time.time()
            results = router.run(reqs)
            dt = time.time() - t0
            router.close()
            toks = sum(len(r.tokens) for r in results)
            done = router.stats["completed"]
            print(f"[serve] router: {toks} tokens in {dt:.2f}s "
                  f"({toks / dt:.1f} tok/s, replicas={args.replicas}, "
                  f"tp={args.tp}, completed={done}, "
                  f"shed={router.stats['shed']}, "
                  f"timed_out={router.stats['timed_out']}, "
                  f"failovers={router.stats['failovers']}, "
                  f"states={router.replica_states})")
            print("first sequence:", results[0].tokens)
            return {"prompts": tokens, "params": params, "wall_s": dt,
                    "n_tokens": toks, "results": results,
                    "engines": [r.engine for r in router.replicas],
                    "stats": dict(router.stats)}
        mesh = model_axis = None
        if args.tp > 1:
            from repro.parallel.jaxcompat import make_mesh
            n_dev = len(jax.devices())
            if n_dev % args.tp:
                raise SystemExit(f"--tp {args.tp} does not divide the "
                                 f"{n_dev} available devices")
            mesh = make_mesh((n_dev // args.tp, args.tp), ("data", "model"))
            model_axis = "model"
        engine = ContinuousEngine(
            api, params, n_slots=args.slots, capacity=capacity,
            prefill_chunk=args.prefill_chunk, temperature=args.temperature,
            mesh=mesh, model_axis=model_axis,
            batch_axes=("data",) if mesh is not None else ())
        t0 = time.time()
        results = engine.run(reqs)
        dt = time.time() - t0
        toks = sum(len(r.tokens) for r in results)
        print(f"[serve] continuous: {toks} tokens in {dt:.2f}s "
              f"({toks / dt:.1f} tok/s, slots={args.slots}, tp={args.tp})")
        print("first sequence:", results[0].tokens)
        return {"prompts": tokens, "params": params, "wall_s": dt,
                "n_tokens": toks, "results": results, "engines": [engine]}

    engine = ServeEngine(api, params, temperature=args.temperature)
    batch = {"tokens": tokens}
    if cfg.n_prefix_embeds:
        batch["prefix"] = jax.random.normal(
            key, (args.batch, min(cfg.n_prefix_embeds, 8), cfg.d_model)) * 0.02
    if cfg.encoder_layers:
        batch["frames"] = jax.random.normal(
            key, (args.batch, cfg.encoder_seq, cfg.d_model)) * 0.02

    t0 = time.time()
    res = engine.generate(batch, max_new_tokens=args.max_new, key=key)
    dt = time.time() - t0
    toks = args.batch * args.max_new
    print(f"[serve] {toks} tokens in {dt:.2f}s "
          f"({toks / dt:.1f} tok/s, batch={args.batch})")
    print("first sequence:", res.tokens[0].tolist())
    return {"prompts": tokens, "params": params, "wall_s": dt,
            "n_tokens": toks, "tokens": res.tokens}


if __name__ == "__main__":
    main()
