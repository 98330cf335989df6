"""Readings that set a training cell's limits, in one process on the chip.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,... \
        --control 1,2,3 [--out FILE]

For every seed the program's train step (built once, as a run builds it) is
driven through the mix's checked steps and compared with the float32
reference, as a run compares it.  For the seeds under ``--control`` the
reference is also put in the program's place and compared with the
float32 reference in the same way: computed with float8 matmul operands
(the control), over the first half of each batch's rows alone (the fault
of a step that leaves half of the batch out), and, where the program runs
its layers as pipeline stages, with the second half of the layers fed zeros
in place of the first half's output (the fault of the exchange between the
stages left out).  A step that returns its state unchanged reads a change
gap of 1 and needs no run.  Prints one JSON line per seed and a summary;
``--out`` also writes them to a file."""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import checks, common  # noqa: E402

NUMBERS = ("loss_gap", "grad_gap", "change_gap")


def main(argv=None, *, root=common.ROOT, compile_cache: bool = True) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", default="")
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    controls = [int(s) for s in args.control.split(",") if s]
    bench = common.Bench(root)
    cell = bench.cell(args.workload)
    mc, mix = bench.config(cell["config"]), bench.traffic(cell["traffic"])
    common.use_repo_sources(root)
    import jax
    if compile_cache:
        common.enable_compile_cache(root)
    driver = bench.driver(mix["driver"])
    ref = bench.reference(mc["reference"])
    prog = driver.build(mc, mix, ref)
    n = mix["checked_steps"]
    reference, control, cut = (
        checks.Reference(ref, mc, mix["optimizer"], num, prog.make_params,
                         mix.get("reference_block_rows"))
        for num in (ref.Numerics("float32"), ref.Numerics("float8"),
                    ref.Numerics("float32", cut_exchange=True)))
    rows = []
    for seed in sorted(set(seeds) | set(controls)):
        t0 = time.perf_counter()
        key = common.seed_key(seed)
        source = bench.generator(mix["kind"]).Source(mix, mc["vocab_size"],
                                                     seed)
        batches = [source.batch(i) for i in range(n)]
        pairs = [(b["tokens"], b["labels"]) for b in batches]
        row = {"seed": seed}
        if seed in seeds:
            with jax.set_mesh(prog.mesh):
                state = prog.init(key)
                state, pr = driver.checked_steps(prog, state, source, n, key)
                driver.free(state)
        rr = reference.readings(key, pairs)
        if seed in seeds:
            row["program"] = checks.compare(pr, rr)
            row["losses"] = {"program": pr["losses"],
                             "reference": rr["losses"]}
        if seed in controls:
            row["control"] = checks.compare(control.readings(key, pairs), rr)
            hr = reference.readings(
                key, [(t[: len(t) // 2], lb[: len(lb) // 2])
                      for t, lb in pairs])
            row["half_batch"] = checks.compare(hr, rr)
            if prog.pipeline:
                row["no_exchange"] = checks.compare(cut.readings(key, pairs),
                                                    rr)
        row["seconds"] = time.perf_counter() - t0
        rows.append(row)
        print(json.dumps(row), flush=True)
    summary = {"cell": cell["name"], "device": jax.devices()[0].device_kind,
               "seconds": time.perf_counter() - T_PROCESS}
    for kind, pick in (("program", max), ("control", min),
                       ("half_batch", min), ("no_exchange", min)):
        got = [r[kind] for r in rows if kind in r]
        if got:
            summary[kind] = {k: pick(g[k] for g in got) for k in NUMBERS}
    print(json.dumps(summary), flush=True)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps({"rows": rows,
                                              "summary": summary}, indent=1))
    return summary


if __name__ == "__main__":
    main()
