"""Compiled-HLO introspection: collective traffic + cost terms.

``compiled.cost_analysis()`` reports FLOPs and HBM bytes but *not* collective
bytes, so we parse the optimized HLO text and sum the operand sizes of every
``all-gather`` / ``all-reduce`` / ``reduce-scatter`` / ``all-to-all`` /
``collective-permute`` op.  This module is shared by

* the roofline harness (``benchmarks/roofline.py``, EXPERIMENTS.md terms),
* the simulator (§5.3 DeepBench-analog path builds ``KernelDesc``s from real
  compiled step functions),
* the live-runtime instrumentation (per-stream collective-byte attribution).
"""

from __future__ import annotations

import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

__all__ = [
    "CollectiveOp",
    "HloCostSummary",
    "parse_collectives",
    "summarize_compiled",
    "DTYPE_BYTES",
]

DTYPE_BYTES: Dict[str, float] = {
    "pred": 1, "s4": 0.5, "u4": 0.5,
    "s8": 1, "u8": 1, "s16": 2, "u16": 2, "s32": 4, "u32": 4, "s64": 8, "u64": 8,
    "f8e4m3fn": 1, "f8e5m2": 1, "bf16": 2, "f16": 2, "f32": 4, "f64": 8,
    "c64": 8, "c128": 16,
}

_COLLECTIVE_KINDS = (
    "all-gather-start", "all-gather",
    "all-reduce-start", "all-reduce",
    "reduce-scatter",
    "all-to-all",
    "collective-permute-start", "collective-permute",
)

# e.g.:  %all-reduce.2 = f32[8,512]{1,0} all-reduce(%dot), channel_id=1, ...
#        %ag = (bf16[4,8]{1,0}, bf16[8,8]{1,0}) all-gather-start(...)
_OP_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(?P<ret>\([^)]*\)|[\w\[\],{}: ]+?)\s+"
    r"(?P<kind>all-gather-start|all-gather-done|all-gather|all-reduce-start|all-reduce-done|"
    r"all-reduce|reduce-scatter|all-to-all|collective-permute-start|collective-permute-done|"
    r"collective-permute)\(",
)

_SHAPE_RE = re.compile(r"(?P<dtype>\w+)\[(?P<dims>[\d,]*)\]")
_REPLICA_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_REPLICA_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")


def _shape_bytes(shape_text: str) -> float:
    """Total bytes of one ``dtype[d0,d1,...]`` shape (per participating device)."""
    total = 0.0
    for m in _SHAPE_RE.finditer(shape_text):
        dtype = m.group("dtype")
        if dtype not in DTYPE_BYTES:
            continue
        dims = m.group("dims")
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * DTYPE_BYTES[dtype]
    return total


@dataclass
class CollectiveOp:
    kind: str
    result_bytes: float  # per-device result size (sum over tuple elements)
    group_size: int  # devices participating in each replica group
    line: str = ""

    @property
    def wire_bytes(self) -> float:
        """Bytes a single device moves over links for this op (ring model).

        all-gather:   each device receives (g-1)/g of the result        → r·(g-1)/g
        reduce-scatter: symmetric to all-gather on the (larger) input   → r·(g-1)
                        (result is 1/g of input; input = r·g)           = in·(g-1)/g
        all-reduce:   reduce-scatter + all-gather                       → 2·r·(g-1)/g
        all-to-all:   each device keeps 1/g, sends the rest             → r·(g-1)/g
        collective-permute: point-to-point                              → r
        """
        g = max(1, self.group_size)
        r = self.result_bytes
        k = self.kind
        if k.startswith("all-reduce"):
            return 2.0 * r * (g - 1) / g
        if k.startswith("all-gather"):
            return r * (g - 1) / g
        if k == "reduce-scatter":
            return r * (g - 1)  # expressed on the *output* (=input/g) size
        if k == "all-to-all":
            return r * (g - 1) / g
        if k.startswith("collective-permute"):
            return r
        return r


def _group_size(line: str, default: int = 1) -> int:
    m = _REPLICA_GROUPS_RE.search(line)
    if m:
        # replica_groups=[n_groups,group_size]<=[...]
        return int(m.group(2))
    m = _REPLICA_GROUPS_LIST_RE.search(line)
    if m:
        first = [x for x in m.group(1).split(",") if x.strip() != ""]
        return max(1, len(first))
    return default


def parse_collectives(hlo_text: str) -> List[CollectiveOp]:
    """All collective ops in an optimized-HLO dump (``compiled.as_text()``)."""
    ops: List[CollectiveOp] = []
    for line in hlo_text.splitlines():
        m = _OP_RE.match(line)
        if not m:
            continue
        kind = m.group("kind")
        if kind.endswith("-done"):
            continue  # counted at the -start op
        ret = m.group("ret")
        if kind.endswith("-start") and ret.startswith("("):
            # async start returns (operand, result, ...) — size the result only:
            # take the *last* sized element for all-gather (result is larger);
            # for collective-permute the elements are equal sized.
            shapes = [s for s in _SHAPE_RE.finditer(ret)]
            if kind.startswith("all-gather") and len(shapes) >= 2:
                ret = shapes[-1].group(0)
            elif len(shapes) >= 2:
                ret = shapes[-1].group(0)
        nbytes = _shape_bytes(ret)
        if nbytes <= 0:
            continue
        ops.append(CollectiveOp(kind=kind, result_bytes=nbytes, group_size=_group_size(line), line=line.strip()[:200]))
    return ops


@dataclass
class HloCostSummary:
    """Everything roofline needs, from one compiled executable."""

    flops_per_device: float
    hbm_bytes_per_device: float
    collective_wire_bytes_per_device: float
    collective_breakdown: Dict[str, float] = field(default_factory=dict)
    collective_count: int = 0
    output_bytes: float = 0.0
    argument_bytes: float = 0.0
    temp_bytes: float = 0.0
    generated_code_bytes: float = 0.0
    peak_hbm_bytes: float = 0.0  # args + outputs + temps (per device)

    def to_dict(self) -> dict:
        return {
            "flops_per_device": self.flops_per_device,
            "hbm_bytes_per_device": self.hbm_bytes_per_device,
            "collective_wire_bytes_per_device": self.collective_wire_bytes_per_device,
            "collective_breakdown": dict(self.collective_breakdown),
            "collective_count": self.collective_count,
            "output_bytes": self.output_bytes,
            "argument_bytes": self.argument_bytes,
            "temp_bytes": self.temp_bytes,
            "generated_code_bytes": self.generated_code_bytes,
            "peak_hbm_bytes": self.peak_hbm_bytes,
        }

    @classmethod
    def from_dict(cls, d: dict) -> "HloCostSummary":
        return cls(**d)


def summarize_compiled(compiled, hlo_text: Optional[str] = None) -> HloCostSummary:
    """Derive roofline terms from a ``jax`` compiled executable.

    ``cost_analysis`` flops/bytes on an SPMD executable are *per device*
    (shapes in the module are already partitioned).
    """
    ca = compiled.cost_analysis()
    text = hlo_text if hlo_text is not None else compiled.as_text()
    colls = parse_collectives(text)
    breakdown: Dict[str, float] = defaultdict(float)
    wire = 0.0
    for op in colls:
        base = op.kind.replace("-start", "")
        breakdown[base] += op.wire_bytes
        wire += op.wire_bytes

    mem = compiled.memory_analysis()
    arg_b = float(mem.argument_size_in_bytes)
    out_b = float(mem.output_size_in_bytes)
    tmp_b = float(mem.temp_size_in_bytes)
    alias_b = float(mem.alias_size_in_bytes)
    gen_b = float(mem.generated_code_size_in_bytes)

    return HloCostSummary(
        flops_per_device=float(ca.get("flops", 0.0)),
        hbm_bytes_per_device=float(ca.get("bytes accessed", 0.0)),
        collective_wire_bytes_per_device=wire,
        collective_breakdown=dict(breakdown),
        collective_count=len(colls),
        output_bytes=out_b,
        argument_bytes=arg_b,
        temp_bytes=tmp_b,
        generated_code_bytes=gen_b,
        peak_hbm_bytes=arg_b + max(out_b - alias_b, 0.0) + tmp_b,
    )
