"""Roofline analysis from compiled dry-run artifacts (assignment §Roofline).

Three terms per (arch x shape x mesh), all in seconds:

  compute    = global_HLO_FLOPs / (chips x 197 TF/s)
  memory     = global_HLO_bytes / (chips x 819 GB/s)
  collective = per-chip collective wire bytes / 50 GB/s/link

`cost_analysis()` reports the per-device SPMD program, so global = x chips.
Collective bytes are NOT in cost_analysis: we parse the optimized HLO text
and apply standard ring-algorithm wire accounting per op:

  all-reduce        2 * size * (n-1)/n
  all-gather        out_size * (n-1)/n
  reduce-scatter    out_size * (n-1)
  all-to-all        size * (n-1)/n
  collective-permute size

where n is the replica-group size (both explicit {{...}} and iota [g,n]<=[N]
formats are parsed).  Async -start/-done pairs are counted once.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Any

from repro.roofline.hw import DTYPE_BYTES, HBM_BW, ICI_LINK_BW, PEAK_FLOPS_BF16

def cost_properties(compiled) -> dict:
    """`compiled.cost_analysis()` as a plain dict."""
    return dict(compiled.cost_analysis())


_COLL_RE = re.compile(
    r"=\s*(?P<result>\([^)]*\)|[a-z0-9]+\[[0-9,]*\]\S*)\s+"
    r"(?P<op>all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?P<start>-start)?\(",
)
_SHAPE_RE = re.compile(r"([a-z][a-z0-9]*)\[([0-9,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")
_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]<=\[\d+\]")


def _tensor_bytes(result: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(result):
        if dtype not in DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * DTYPE_BYTES[dtype]
    return total


def _group_size(line: str) -> int:
    m = _GROUPS_RE.search(line)
    if m:
        return len([x for x in m.group(1).split(",") if x.strip() != ""])
    m = _IOTA_RE.search(line)
    if m:
        return int(m.group(2))
    return 2  # conservative default


@dataclasses.dataclass
class CollectiveStats:
    counts: dict[str, int]
    wire_bytes: float  # per device
    by_op: dict[str, float]

    def total_ops(self) -> int:
        return sum(self.counts.values())


def parse_collectives(hlo_text: str) -> CollectiveStats:
    counts: dict[str, int] = {}
    by_op: dict[str, float] = {}
    total = 0.0
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        if f"{m.group('op')}-done" in line:
            continue
        op = m.group("op")
        size = _tensor_bytes(m.group("result"))
        n = _group_size(line)
        if n <= 1:
            continue
        if op == "all-reduce":
            wire = 2.0 * size * (n - 1) / n
        elif op == "all-gather":
            wire = size * (n - 1) / n
        elif op == "reduce-scatter":
            wire = size * (n - 1)
        elif op == "all-to-all":
            wire = size * (n - 1) / n
        else:  # collective-permute
            wire = float(size)
        counts[op] = counts.get(op, 0) + 1
        by_op[op] = by_op.get(op, 0.0) + wire
        total += wire
    return CollectiveStats(counts, total, by_op)


@dataclasses.dataclass
class Roofline:
    arch: str
    shape: str
    mesh: str
    chips: int
    flops_per_device: float
    bytes_per_device: float
    collective_bytes_per_device: float
    collective_counts: dict[str, int]
    collective_by_op: dict[str, float]
    model_flops: float  # 6·N_active·D (global, per step)
    memory_per_device: dict[str, float]  # from memory_analysis

    @property
    def compute_s(self) -> float:
        return self.flops_per_device / PEAK_FLOPS_BF16

    @property
    def memory_s(self) -> float:
        return self.bytes_per_device / HBM_BW

    @property
    def collective_s(self) -> float:
        return self.collective_bytes_per_device / ICI_LINK_BW

    @property
    def bottleneck(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def step_s(self) -> float:
        """Roofline step time = max of the three terms (perfect overlap)."""
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def useful_ratio(self) -> float:
        """MODEL_FLOPS / global HLO FLOPs (remat/dispatch waste detector)."""
        hlo_global = self.flops_per_device * self.chips
        return self.model_flops / hlo_global if hlo_global else 0.0

    @property
    def roofline_fraction(self) -> float:
        """Fraction of the compute roofline achieved at the bound: useful
        FLOPs / (chips x peak x step_s)."""
        denom = self.chips * PEAK_FLOPS_BF16 * self.step_s
        return self.model_flops / denom if denom else 0.0

    def to_json(self) -> dict[str, Any]:
        return {
            "arch": self.arch, "shape": self.shape, "mesh": self.mesh,
            "chips": self.chips,
            "flops_per_device": self.flops_per_device,
            "bytes_per_device": self.bytes_per_device,
            "collective_bytes_per_device": self.collective_bytes_per_device,
            "collective_counts": self.collective_counts,
            "collective_by_op": self.collective_by_op,
            "model_flops": self.model_flops,
            "memory_per_device": self.memory_per_device,
            "compute_s": self.compute_s, "memory_s": self.memory_s,
            "collective_s": self.collective_s,
            "bottleneck": self.bottleneck, "step_s": self.step_s,
            "useful_ratio": self.useful_ratio,
            "roofline_fraction": self.roofline_fraction,
        }


def from_compiled(arch: str, shape: str, mesh_name: str, chips: int,
                  compiled, model_flops: float) -> Roofline:
    cost = cost_properties(compiled)
    mem = compiled.memory_analysis()
    flops = float(cost.get("flops", 0.0))
    byts = float(cost.get("bytes accessed", 0.0))
    colls = parse_collectives(compiled.as_text())
    return Roofline(
        arch=arch, shape=shape, mesh=mesh_name, chips=chips,
        flops_per_device=flops, bytes_per_device=byts,
        collective_bytes_per_device=colls.wire_bytes,
        collective_counts=colls.counts,
        collective_by_op=colls.by_op,
        model_flops=model_flops,
        memory_per_device={
            "argument": float(mem.argument_size_in_bytes),
            "output": float(mem.output_size_in_bytes),
            "temp": float(mem.temp_size_in_bytes),
            "alias": float(mem.alias_size_in_bytes),
            "code": float(mem.generated_code_size_in_bytes),
        },
    )


def hbm_per_device(r: Roofline) -> float:
    m = r.memory_per_device
    return m["argument"] + m["output"] + m["temp"] - m["alias"]
