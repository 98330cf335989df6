"""Run one cell of the benchmark once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``; its
configuration, traffic mix, limits and readers are found by name (see
``bench/__init__.py``).  With ``--trace 0`` the result holds the cell's
end-to-end metrics, with ``--trace 1`` its per-layer metrics, read from a
profiler trace of the window.  The numbers that decide ``correct`` are
printed beside their limits as the last lines of standard error and under
``checks``, the last key of the result.  Without a TPU, or with fewer chips
than the cell asks for, the run exits non-zero and prints no result."""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import checks, common  # noqa: E402


@dataclasses.dataclass
class Context:
    bench: common.Bench
    cell: dict
    config: dict
    mix: dict
    seed: int
    seconds: float
    trace: bool
    t_process: float
    fault: str = ""


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def check_devices(jax, chips: int) -> None:
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"[bench] no TPU: JAX found {devs[0].platform}")
    if len(devs) < chips:
        raise SystemExit(f"[bench] the cell needs {chips} chips, JAX found "
                         f"{len(devs)}")


def result_line(bench, cell: dict, record: dict, limits: dict, trace: bool,
                jax) -> dict:
    numbers = {k: v for k, v in record["numbers"].items() if k in limits}
    correct, shown = checks.judge(numbers, limits)
    correct = correct and record["failed"] == 0
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": jax.device_count(),
              "memory_peak_bytes": record["memory_peak_bytes"]}
    metrics = {}
    if trace:
        red = record["trace"]
        device.update(busy_s=red["busy_s"], window_s=red["window_s"])
        record = dict(record, device_kind=dev.device_kind)
        for m in bench.metrics_of(cell["name"], "per_layer"):
            v = bench.reader(m["name"]).read(record)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        for m in bench.metrics_of(cell["name"], "end_to_end"):
            metrics[m["name"]] = {"value": record["end_to_end"][m["name"]],
                                  "unit": m["unit"]}
    out = {"correct": correct, "attempted": record["attempted"],
           "failed": record["failed"], "metrics": metrics, "device": device}
    if trace:
        out["breakdown"] = {"device_ops": record["trace"]["device_ops"],
                            "idle_gaps": record["trace"]["idle_gaps"]}
    out["checks"] = shown
    return out


def main(argv=None, *, root=common.ROOT, require_chip: bool = True,
         compile_cache: bool = True, fault: str = "") -> dict:
    """Run the cell; print and return the result line.  ``require_chip`` and
    ``fault`` exist for the benchmark's own tests (a run on the CPU, with
    the train step broken underneath)."""
    args = parse(argv)
    bench = common.Bench(root)
    cell = bench.cell(args.workload)
    mix = bench.traffic(cell["traffic"])
    limits = bench.limits(cell["name"])
    common.use_repo_sources(root)
    import jax
    if require_chip:
        check_devices(jax, cell["chips"])
    if compile_cache:
        common.enable_compile_cache(root)
    ctx = Context(bench=bench, cell=cell, config=bench.config(cell["config"]),
                  mix=mix, seed=args.seed, seconds=args.seconds,
                  trace=bool(args.trace), t_process=T_PROCESS, fault=fault)
    record = bench.driver(mix["driver"]).run(ctx)
    out = result_line(bench, cell, record, limits, ctx.trace, jax)
    print(f"[bench] {cell['name']} seed={args.seed} steps={record['attempted']}"
          f" compiled_in_window={record.get('compiled_in_window')} worst "
          f"leaves {record['numbers'].get('leaves')} step_s "
          f"{[round(t, 4) for t in record.get('step_s', [])]}",
          file=sys.stderr)
    for name, c in out["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return out


if __name__ == "__main__":
    main()
