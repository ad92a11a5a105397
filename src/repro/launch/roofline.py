"""Roofline analysis from compiled dry-run artifacts (deliverable g).

Three terms per (arch × shape × mesh), all PER-DEVICE (the compiled
module under SPMD is the per-device program — verified against a known
matmul in tests/test_roofline.py):

    compute    = HLO_FLOPs / PEAK_FLOPS            [s]
    memory     = HLO_bytes / HBM_BW                [s]
    collective = wire_bytes / LINK_BW              [s]

``wire_bytes`` is not in cost_analysis: we parse the compiled HLO and
sum per-op estimates with ring-algorithm factors (G = group size):

    all-reduce          2·S·(G-1)/G      (reduce-scatter + all-gather)
    all-gather          S_out·(G-1)/G
    reduce-scatter      S_out·(G-1)     (input = S_out·G)
    all-to-all          S·(G-1)/G
    collective-permute  S

Hardware model: ``PEAKS``, published per-chip peaks keyed by the
``device_kind`` JAX reports.  A kind missing from the table is an
error (``peaks_for``), never a default: a roofline computed against
another chip's peaks is a wrong number, not an approximate one.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Tuple


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    """Published peaks of one chip."""

    flops: float      # dense bf16 FLOP/s
    hbm_bw: float     # HBM bytes/s
    link_bw: float    # bytes/s per chip-to-chip link
    source: str


PEAKS: Dict[str, ChipPeaks] = {
    # 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s, 1,600 Gbit/s of ICI
    # over four links (= 50 GB/s each)
    "TPU v5 lite": ChipPeaks(
        flops=197e12, hbm_bw=819e9, link_bw=50e9,
        source="Google Cloud documentation, 'TPU v5e'"),
}


def peaks_for(device_kind: str) -> ChipPeaks:
    """The published peaks of ``device_kind`` (``jax.Device.device_kind``);
    raises KeyError for a kind with no entry in ``PEAKS``."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; add an "
            f"entry with its source to repro.launch.roofline.PEAKS "
            f"(known: {sorted(PEAKS)})") from None

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_OLD_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")


def _shape_bytes(sh: str) -> int:
    m = _SHAPE_RE.match(sh)
    if not m:
        return 0
    dt, dims = m.groups()
    n = _DTYPE_BYTES.get(dt, 0)
    if n == 0:
        return 0
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n


def _result_bytes(lhs: str) -> int:
    """Bytes of an op's result type: 'f32[8,16]{...}' or a tuple."""
    lhs = lhs.strip()
    if lhs.startswith("("):
        return sum(_shape_bytes(p.strip())
                   for p in lhs[1:].split(")")[0].split(","
                   ) if "[" in p) or sum(
            _shape_bytes(s) for s in re.findall(r"\w+\[[\d,]*\]", lhs))
    return _shape_bytes(lhs)


def _group_size(line: str, world: int) -> int:
    m = _GROUPS_RE.search(line)
    if m:
        return int(m.group(2))
    m = _GROUPS_OLD_RE.search(line)
    if m:
        return len(m.group(1).split(","))
    return world


@dataclasses.dataclass
class CollectiveStats:
    wire_bytes: float
    by_op: Dict[str, float]
    count: int

    def top(self, k: int = 5) -> List[Tuple[str, float]]:
        return sorted(self.by_op.items(), key=lambda x: -x[1])[:k]


def parse_collectives(hlo_text: str, world: int = 256) -> CollectiveStats:
    total = 0.0
    by_op: Dict[str, float] = {}
    count = 0
    for line in hlo_text.splitlines():
        s = line.strip()
        if "=" not in s:
            continue
        hit = None
        for op in _COLLECTIVES:
            if re.search(rf"\b{op}(-start)?\(", s):
                hit = op
                break
        if hit is None or f"{hit}-done" in s:
            continue
        lhs = s.split("=", 1)[0]
        # async start ops return (operand, result, ...) tuples; take the
        # largest component as the payload
        sizes = [_shape_bytes(x) for x in re.findall(r"\w+\[[\d,]*\]", lhs)]
        size = max(sizes) if sizes else 0
        g = _group_size(s, world)
        ring = (g - 1) / max(g, 1)
        if hit == "all-reduce":
            wire = 2 * size * ring
        elif hit == "reduce-scatter":
            wire = size * (g - 1)
        elif hit == "collective-permute":
            wire = size
        else:  # all-gather / all-to-all: size = output (gathered) bytes
            wire = size * ring
        total += wire
        by_op[hit] = by_op.get(hit, 0.0) + wire
        count += 1
    return CollectiveStats(wire_bytes=total, by_op=by_op, count=count)


@dataclasses.dataclass
class Roofline:
    flops: float            # per device
    hbm_bytes: float        # per device
    wire_bytes: float       # per device
    model_flops: float      # analytic 6ND/2ND (global)
    chips: int
    peaks: ChipPeaks        # the chip the program was compiled for

    @property
    def t_compute(self) -> float:
        return self.flops / self.peaks.flops

    @property
    def t_memory(self) -> float:
        return self.hbm_bytes / self.peaks.hbm_bw

    @property
    def t_collective(self) -> float:
        return self.wire_bytes / self.peaks.link_bw

    @property
    def bottleneck(self) -> str:
        ts = {"compute": self.t_compute, "memory": self.t_memory,
              "collective": self.t_collective}
        return max(ts, key=ts.get)

    @property
    def step_time(self) -> float:
        """Lower bound assuming perfect overlap: max of the three."""
        return max(self.t_compute, self.t_memory, self.t_collective)

    @property
    def useful_flops_frac(self) -> float:
        """MODEL_FLOPS / (chips · HLO_FLOPs): how much compiled compute
        is 'useful' (catches remat/redundancy waste)."""
        tot = self.flops * self.chips
        return self.model_flops / tot if tot else 0.0

    @property
    def mfu_bound(self) -> float:
        """Roofline-fraction score: useful model FLOPs per chip-second at
        the step-time lower bound, vs peak."""
        t = self.step_time
        if t <= 0:
            return 0.0
        return self.model_flops / (self.chips * t) / self.peaks.flops

    def row(self) -> Dict[str, float]:
        return {
            "t_compute": self.t_compute, "t_memory": self.t_memory,
            "t_collective": self.t_collective,
            "bottleneck": self.bottleneck, "step_time": self.step_time,
            "useful_frac": self.useful_flops_frac,
            "mfu_bound": self.mfu_bound,
        }


def model_flops_for(cfg, shape) -> float:
    """Analytic MODEL_FLOPS for the cell (global, per step):
    train 6·N_active·D; prefill 2·N_active·D; decode 2·N_active·B."""
    n_active = cfg.active_param_count()
    if shape.kind == "train":
        return 6.0 * n_active * shape.global_batch * shape.seq_len
    if shape.kind == "prefill":
        return 2.0 * n_active * shape.global_batch * shape.seq_len
    return 2.0 * n_active * shape.global_batch  # decode: one token
