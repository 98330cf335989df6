"""Device time of the train step by layer.

The program names each layer of its train step with a ``jax.named_scope``
(``repro.scopes``).  The names reach every instruction of the compiled step
as the ``op_name`` of its metadata, in the forward, the rematerialised
forward and the backward.  ``reduce`` maps each innermost device op of a
traced window to a scope through that ``op_name`` and sums, per device, the
union of each scope's intervals within the ``bench.window`` span:

- an op's ``op_name`` is read from the compiled step's HLO text, keyed by
  the instruction name the trace gives the op.  A fusion without metadata of
  its own takes the ``op_name``s of the instructions it fuses, joined by
  ``;`` as XLA joins them;
- the compiled text is read with each instruction whole: an attribute
  that spans several lines, as a Mosaic call's ``kernel_metadata`` does,
  is joined onto its instruction (``join_lines``);
- the name is split on ``;`` and each part on ``/``; ``jvp(`` and
  ``transpose(`` wrappers and closing ``)`` are stripped; the deepest
  component that is a scope wins, and an op with none is unscoped;
- innermost ops are ``trace.leaves`` of the ops that take time: the chip's
  trace records some ops (custom calls, async ends) as zero-length
  instants inside or at the end of another op, which ``trace.leaves`` on
  all ops takes for a container and drops, a few percent of a step;
- a device's unscoped time is its busy time (as ``bench/trace.py`` counts
  it) outside every scoped op, so on each device the scope shares, the
  unscoped share and the idle share sum to 1, apart from scoped ops that
  overlap each other.

``kernel_runs`` counts, for each scope, the runs of its Mosaic kernels
(``tpu_custom_call`` instructions) in the window, by kernel and by
direction: a run whose ``op_name`` lies under ``transpose(`` and outside a
``rematted_computation`` belongs to the backward pass, every other run to a
forward pass (the first or the rematerialised one).

``bench/drivers/train.py`` reduces every traced run's window this way,
for the per-layer metrics that read a scope, and records ``gap_causes``
beside it.

``gap_causes`` names, for each of the longest idle gaps, the host-runtime
event that covers most of it: an event of a runtime thread of ``/host:CPU``
(a PjRt execute, allocation or transfer), not of the Python thread, where
the harness's spans and JAX's Python-level annotations lie.

    python3 bench/scopes.py --workload <cell> --same-program

compiles the cell's step twice, with the scopes and with each scope a
no-op, and reports whether the two programs differ apart from metadata."""
from __future__ import annotations

import argparse
import collections
import contextlib
import json
import re
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import common, trace  # noqa: E402

UNSCOPED = "unscoped"
TOP_OPS = 5
TOP_GAPS = 5
PYTHON_LINE = "python"      # the host line of the Python thread's events
WRAPPERS = re.compile(r"^(?:(?:jvp|transpose)\()+")
COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) .*\{$")
INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%?([\w.\-]+) = (.*)$")
OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
CALLS = re.compile(r"calls=%?([\w.\-]+)")
METADATA = re.compile(r",? metadata=\{[^}]*\}")
MOSAIC = 'custom_call_target="tpu_custom_call"'
NUMBERED = re.compile(r"\.\d+$")
BACKWARD = "transpose("
REMAT = "rematted_computation"


def names() -> tuple:
    """The program's scope names; empty for a program that has none."""
    common.use_repo_sources()
    try:
        from repro.scopes import NAMES
    except ImportError:
        return ()
    return tuple(NAMES)


def join_lines(hlo_text: str) -> list:
    """The text's lines, each instruction whole: an attribute that spans
    several lines (a Mosaic call's ``kernel_metadata``) continues on lines
    that start with neither a space nor ``HloModule``, and that are neither
    a computation's header nor its closing ``}``; they are joined onto the
    line before."""
    out = []
    for line in hlo_text.splitlines():
        if (out and line and line != "}"
                and not line.startswith((" ", "HloModule"))
                and not COMPUTATION.match(line)):
            out[-1] += line
        else:
            out.append(line)
    return out


def op_names(hlo_text: str) -> dict:
    """Instruction name -> ``op_name`` for every instruction of an HLO
    module's text ("" where neither it nor what it fuses has one)."""
    comps, cur = {}, None
    for line in join_lines(hlo_text):
        if not line.startswith((" ", "HloModule")):
            m = COMPUTATION.match(line)
            cur = comps.setdefault(m.group(1), []) if m else None
            continue
        m = INSTRUCTION.match(line)
        if m and cur is not None:
            name, rest = m.groups()
            own = OP_NAME.search(rest)
            callee = CALLS.search(rest)
            cur.append((name, own.group(1) if own else "",
                        callee.group(1) if callee else None))
    out = {}
    for ins in comps.values():
        for name, own, callee in ins:
            if not own and callee in comps:
                own = ";".join(dict.fromkeys(
                    o for _, o, _ in comps[callee] if o))
            out[name] = own
    return out


def kernels(hlo_text: str) -> set:
    """Names of the instructions that call a Mosaic kernel."""
    return {INSTRUCTION.match(ln).group(1) for ln in join_lines(hlo_text)
            if MOSAIC in ln and INSTRUCTION.match(ln)}


def scope_of(op_name: str, scopes: tuple) -> str:
    """The deepest scope among the components of ``op_name``."""
    best, depth = UNSCOPED, -1
    for part in op_name.split(";"):
        for i, comp in enumerate(part.split("/")):
            comp = WRAPPERS.sub("", comp).rstrip(")")
            if comp in scopes and i > depth:
                best, depth = comp, i
    return best


def instruction(event_name: str) -> str:
    """A device op's instruction name: the trace names an op by its HLO
    instruction, with or without the instruction's text after it."""
    return event_name.partition(" = ")[0].strip().lstrip("%")


def leaves(evs) -> list:
    """The innermost ops that take time: an op of zero length contains
    nothing and is no sign that the op around it is a loop or a call."""
    return trace.leaves([e for e in evs if e.dur > 0])


def reduce(events, hlo_ops: dict, scopes: tuple) -> dict | None:
    """Each scope's share of the ``bench.window`` span (mean over devices),
    the unscoped and idle shares, the same per device, and the unscoped ops
    that took most time.  None where the step carries no scope (a program
    without them)."""
    if not scopes or not any(scope_of(o, scopes) != UNSCOPED
                             for o in hlo_ops.values()):
        return None
    devices = device_ops(events)
    if not devices:
        raise ValueError("the trace holds no device op")
    lo, hi = window(events, devices)
    span = hi - lo
    per_device, unscoped_ops = {}, collections.Counter()
    unmatched = 0.0
    for dev, evs in sorted(devices.items()):
        busy = trace.length(trace.union(trace.clip(
            [(e.start, e.start + e.dur) for e in evs], lo, hi)))
        by_scope = collections.defaultdict(list)
        for e in leaves(evs):
            op = hlo_ops.get(instruction(e.name))
            s = scope_of(op or "", scopes)
            by_scope[s].append((e.start, e.start + e.dur))
            d = max(0.0, min(e.start + e.dur, hi) - max(e.start, lo))
            if op is None:
                unmatched += d / len(devices)
            if s == UNSCOPED and d > 0:
                label = f"{trace.op_label(e.name)} {op or ''}".strip()
                unscoped_ops[label] += d / len(devices)
        by_scope.pop(UNSCOPED, None)
        shares = {s: trace.length(trace.union(trace.clip(iv, lo, hi))) / span
                  for s, iv in sorted(by_scope.items())}
        scoped = trace.length(trace.union(trace.clip(
            [iv for ivs in by_scope.values() for iv in ivs], lo, hi)))
        per_device[dev] = {"scopes": shares,
                           UNSCOPED: (busy - scoped) / span,
                           "idle": 1.0 - busy / span}
    n = len(per_device)
    mean = collections.Counter()
    for d in per_device.values():
        for s, v in d["scopes"].items():
            mean[s] += v / n
    return {
        "window_s": span / 1e9,
        "scopes": dict(sorted(mean.items(), key=lambda kv: -kv[1])),
        UNSCOPED: sum(d[UNSCOPED] for d in per_device.values()) / n,
        "idle": sum(d["idle"] for d in per_device.values()) / n,
        "per_device": per_device,
        "unscoped_ops": [[k, v / 1e9]
                         for k, v in unscoped_ops.most_common(TOP_OPS)],
        # time of ops whose instruction the HLO text does not hold
        "unmatched_s": unmatched / 1e9,
    }


def kernel_runs(events, hlo_ops: dict, calls: set, scopes: tuple) -> dict:
    """Scope -> {"forward": {kernel: runs}, "backward": {kernel: runs}}: the
    runs in the ``bench.window`` span, summed over devices, of each kernel
    call (an instruction of ``calls``) under the scope, by kernel (the
    instruction's name without its number) and direction."""
    devices = device_ops(events)
    if not devices:
        return {}
    lo, hi = window(events, devices)
    out = {}
    for evs in devices.values():
        for e in leaves(evs):
            name = instruction(e.name)
            if name not in calls or not lo <= e.start < hi:
                continue
            op = hlo_ops.get(name, "")
            s = scope_of(op, scopes)
            way = ("backward" if BACKWARD in op and REMAT not in op
                   else "forward")
            runs = out.setdefault(s, {"forward": {}, "backward": {}})[way]
            kernel = NUMBERED.sub("", name)
            runs[kernel] = runs.get(kernel, 0) + 1
    return out


def device_ops(events) -> dict:
    """Device index -> the events of its ops line."""
    devices = collections.defaultdict(list)
    for e in events:
        m = trace.DEVICE_PLANE.match(e.plane)
        if m and e.line == trace.OPS_LINE:
            devices[int(m.group(1))].append(e)
    return devices


def window(events, devices) -> tuple:
    """The ``bench.window`` span, or the span of the devices' ops."""
    win = [e for e in events if e.plane == trace.HOST_PLANE
           and e.name == trace.WINDOW_SPAN]
    if win:
        return win[0].start, win[0].start + win[0].dur
    return (min(e.start for evs in devices.values() for e in evs),
            max(e.start + e.dur for evs in devices.values() for e in evs))


def gap_causes(events, top: int = TOP_GAPS) -> list:
    """The ``top`` longest idle gaps of the first device within the window,
    each as [gap seconds, the host-runtime event that overlaps it most,
    seconds of overlap, the harness span that overlaps it most].  A host
    event as long as the window (a thread's lifetime) explains nothing and
    is passed over."""
    devices = device_ops(events)
    if not devices:
        return []
    lo, hi = window(events, devices)
    first = devices[min(devices)]
    busy = trace.union(trace.clip(
        [(e.start, e.start + e.dur) for e in first], lo, hi))
    host = [e for e in events if e.plane == trace.HOST_PLANE]
    spans = [e for e in host if e.name.startswith(trace.SPAN_PREFIX)
             and e.name != trace.WINDOW_SPAN]
    runtime = [e for e in host if not e.line.startswith(PYTHON_LINE)
               and e.dur < hi - lo]

    out = []
    for s, t in sorted(trace.gaps(busy, lo, hi), key=lambda g: g[0] - g[1]):
        cause, covered = covering(runtime, s, t)
        out.append([(t - s) / 1e9, cause, covered / 1e9,
                    covering(spans, s, t)[0]])
        if len(out) == top:
            break
    return out


def covering(evs, s: float, t: float) -> tuple:
    """The event that overlaps (s, t) most, the shortest of equals, and the
    overlap."""
    best = max(((min(e.start + e.dur, t) - max(e.start, s), -e.dur, e.name)
                for e in evs), default=(0.0, 0.0, ""))
    return (best[2], best[0]) if best[0] > 0 else ("no event", 0.0)


def strip_metadata(hlo_text: str) -> str:
    return METADATA.sub("", hlo_text)


# --- running a cell ---------------------------------------------------------

def abstract(tree):
    import jax
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                       sharding=getattr(x, "sharding", None)),
        tree)


def step_hlo(prog, state, batch) -> str:
    """The compiled step's HLO text, from abstract arguments."""
    return prog.step.lower(abstract(state), abstract(batch)).compile(
    ).as_text()


def same_program(build, mc, mix, ref) -> dict:
    """Compile the step with the scopes and with each scope a no-op; the two
    HLO texts without metadata, and their instruction counts."""
    import jax
    import jax.numpy as jnp

    from repro import scopes as program_scopes

    texts = []
    for patch in (False, True):
        real = program_scopes.scope
        if patch:
            program_scopes.scope = contextlib.nullcontext
        try:
            prog = build(mc, mix, ref)
            state = jax.eval_shape(prog.init, jax.random.PRNGKey(0))
            batch = {k: jax.ShapeDtypeStruct((mix["batch"], mix["seq"]),
                                             jnp.int32)
                     for k in ("tokens", "labels")}
            texts.append(strip_metadata(prog.step.lower(state, batch)
                                        .compile().as_text()))
        finally:
            program_scopes.scope = real
    counts = [sum(1 for ln in t.splitlines() if INSTRUCTION.match(ln))
              for t in texts]
    return {"same": texts[0] == texts[1], "instructions": counts}


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--same-program", action="store_true", required=True)
    return ap.parse_args(argv)


def main(argv=None, *, root=common.ROOT, require_chip: bool = True) -> dict:
    args = parse(argv)
    bench = common.Bench(root)
    cell = bench.cell(args.workload)
    mc, mix = bench.config(cell["config"]), bench.traffic(cell["traffic"])
    common.use_repo_sources(root)
    import jax
    if require_chip and jax.devices()[0].platform != "tpu":
        raise SystemExit(f"[scopes] no TPU: JAX found "
                         f"{jax.devices()[0].platform}")
    driver = bench.driver(mix["driver"])
    ref = bench.reference(mc["reference"])
    # a cached executable would hide a difference
    jax.config.update("jax_enable_compilation_cache", False)
    out = {"workload": cell["name"],
           "same_program": same_program(driver.build, mc, mix, ref)}
    print(json.dumps(out), flush=True)
    return out


if __name__ == "__main__":
    main()
