"""Roofline analysis from compiled dry-run artifacts (EXPERIMENTS.md §Roofline).

Three terms per (arch x shape x mesh), all in seconds:

    compute    = HLO_FLOPs      / (chips * PEAK_FLOPS)
    memory     = HLO_bytes      / (chips * HBM_BW)
    collective = collective_bytes / (chips * n_links * LINK_BW)

HLO_FLOPs / HLO_bytes come from ``compiled.cost_analysis()`` (per-partition
under GSPMD, so they are already per-chip — we multiply back to totals for
reporting).  collective_bytes is parsed from the HLO text: every all-gather /
all-reduce / reduce-scatter / all-to-all / collective-permute op's tensor
sizes, weighted by the ring-algorithm wire factor for its replica-group size.

Hardware constants: TPU v5e-like — 197 TFLOP/s bf16, 819 GB/s HBM,
~50 GB/s/link ICI (DESIGN.md §3).
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional

PEAK_FLOPS = 197e12          # bf16 per chip
HBM_BW = 819e9               # bytes/s per chip
LINK_BW = 50e9               # bytes/s per ICI link
ICI_LINKS = 4                # links/chip usable on the 2D torus (x+/x-/y+/y-)
DCI_BW = 25e9                # inter-pod (data-center interconnect) per chip pair
HBM_PER_CHIP = 16 * 1024**3  # v5e: 16 GiB

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"(\w+)\[([0-9,]*)\]")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")


def _tensor_bytes(type_str: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(type_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def _group_size(line: str, default: int) -> int:
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        return int(m.group(2))
    m = _GROUPS_LIST_RE.search(line)
    if m:
        return len([x for x in m.group(1).split(",") if x.strip()])
    return default


def _wire_factor(op: str, g: int) -> float:
    """Ring-algorithm bytes-on-wire per participating chip, as a multiple of
    the (per-shard) tensor bytes."""
    if g <= 1:
        return 0.0
    if op == "all-reduce":
        return 2.0 * (g - 1) / g
    if op in ("all-gather", "reduce-scatter", "all-to-all"):
        return (g - 1) / g
    return 1.0  # collective-permute: one hop


@dataclasses.dataclass
class CollectiveStats:
    ops: Dict[str, int]
    wire_bytes: float            # per-chip bytes on the wire (ring model)
    tensor_bytes: float          # raw summed tensor bytes (reported too)
    lines: List[str]             # whole HLO lines of the collectives

    def to_dict(self):
        return {"ops": self.ops, "wire_bytes": self.wire_bytes,
                "tensor_bytes": self.tensor_bytes}


def parse_collectives(hlo_text: str, default_group: int,
                      multiplier_fn=None) -> CollectiveStats:
    """Scan HLO text and sum collective traffic.

    ``multiplier_fn(computation_name) -> int`` lets callers weight while-body
    computations by trip count; by default everything counts once (the dry-run
    lowers with unrolled layer stacks so this is exact — DESIGN.md §5).
    """
    ops: Dict[str, int] = {}
    wire = 0.0
    raw = 0.0
    lines_kept: List[str] = []
    current_comp = ""
    # "%name = <type> all-reduce(...)" — capture the result type between the
    # "=" and the op mnemonic (may be a tuple for -start forms)
    inst_re = re.compile(
        r"=\s*(.*?)\s+(" + "|".join(_COLLECTIVES) + r")(-start)?\(")
    for line in hlo_text.splitlines():
        ls = line.strip()
        if (ls.startswith("%") and ls.endswith("{")) or ls.startswith("ENTRY"):
            current_comp = ls.split(" ")[0]
        m = inst_re.search(ls)
        if not m:
            continue
        type_str, op = m.group(1), m.group(2)
        if m.group(3):  # -start returns (operand, result[, scratch]) tuple:
            # halve to avoid double counting operand+result
            tb = _tensor_bytes(type_str) / 2
        else:
            tb = _tensor_bytes(type_str)
        mult = multiplier_fn(current_comp) if multiplier_fn else 1
        g = _group_size(ls, default_group)
        ops[op] = ops.get(op, 0) + mult
        raw += tb * mult
        wire += tb * _wire_factor(op, g) * mult
        lines_kept.append(ls)
    return CollectiveStats(ops=ops, wire_bytes=wire, tensor_bytes=raw,
                           lines=lines_kept)


@dataclasses.dataclass
class Roofline:
    chips: int
    hlo_flops_per_chip: float
    hlo_bytes_per_chip: float
    collective_wire_bytes_per_chip: float
    model_flops_total: float
    crosses_pod: bool = False

    @property
    def t_compute(self) -> float:
        return self.hlo_flops_per_chip / PEAK_FLOPS

    @property
    def t_memory(self) -> float:
        return self.hlo_bytes_per_chip / HBM_BW

    @property
    def t_collective(self) -> float:
        bw = ICI_LINKS * LINK_BW if not self.crosses_pod else DCI_BW
        return self.collective_wire_bytes_per_chip / bw

    @property
    def bottleneck(self) -> str:
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)

    @property
    def useful_flops_ratio(self) -> float:
        total_hlo = self.hlo_flops_per_chip * self.chips
        return self.model_flops_total / total_hlo if total_hlo else 0.0

    @property
    def step_time(self) -> float:
        """Simple max-of-terms bound (perfect overlap assumption)."""
        return max(self.t_compute, self.t_memory, self.t_collective)

    @property
    def mfu(self) -> float:
        t = self.step_time
        if not t:
            return 0.0
        return self.model_flops_total / (self.chips * PEAK_FLOPS * t)

    def to_dict(self):
        return {
            "chips": self.chips,
            "hlo_flops_per_chip": self.hlo_flops_per_chip,
            "hlo_bytes_per_chip": self.hlo_bytes_per_chip,
            "collective_wire_bytes_per_chip": self.collective_wire_bytes_per_chip,
            "model_flops_total": self.model_flops_total,
            "t_compute": self.t_compute,
            "t_memory": self.t_memory,
            "t_collective": self.t_collective,
            "bottleneck": self.bottleneck,
            "useful_flops_ratio": self.useful_flops_ratio,
            "step_time": self.step_time,
            "mfu": self.mfu,
        }


def model_flops(cfg, shape, kind: Optional[str] = None) -> float:
    """MODEL_FLOPS: 6*N*D for training (N = active params, D = tokens);
    2*N*D for inference forward; attention's quadratic term added explicitly
    (it is not in N*D)."""
    kind = kind or shape.kind
    n_active = cfg.n_active_params()
    if kind == "train":
        tokens = shape.seq_len * shape.global_batch
        base = 6.0 * n_active * tokens
        mult = 3.0
    elif kind == "prefill":
        tokens = shape.seq_len * shape.global_batch
        base = 2.0 * n_active * tokens
        mult = 1.0
    else:  # decode: one token per sequence
        tokens = shape.global_batch
        base = 2.0 * n_active * tokens
        mult = 1.0
    # attention score/value FLOPs: 2 * 2 * B * S_kv * T_q * H * hd per layer
    if cfg.n_heads and not cfg.rwkv:
        window = cfg.sliding_window
        if kind == "decode" and shape.seq_len > 65536 and not window:
            window = cfg.long_context_window
        s_kv = min(shape.seq_len, window) if window else shape.seq_len
        if kind == "decode":
            t_q = 1
            s_eff = s_kv
        else:
            t_q = shape.seq_len
            s_eff = (s_kv + 1) / 2 if not window else min(window, shape.seq_len)
        attn = (4.0 * shape.global_batch * t_q * s_eff
                * cfg.n_heads * cfg.head_dim * cfg.n_layers)
        base += mult * attn
    return base
