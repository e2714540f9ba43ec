"""Compiled-HLO analysis: collective byte counts + roofline terms.

``cost_analysis()`` lacks collective traffic, so we parse the (optimized)
HLO text: every ``all-gather``/``all-reduce``/``reduce-scatter``/
``all-to-all``/``collective-permute`` op contributes its operand bytes.
Shapes are parsed from the HLO result/operand types (e.g.
``bf16[2,4096,128]{...}``).  ``unfused_instructions`` lists the ops a
program runs, each with its result type and ``op_name``.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field

DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
}

COLLECTIVE_KINDS = (
    "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
    "collective-permute",
)

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_OP_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(\([^)]*\)|[^=(]+?)\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\(",
    re.MULTILINE,
)


def shape_bytes(type_str: str) -> int:
    total = 0
    for m in _SHAPE_RE.finditer(type_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * DTYPE_BYTES[dt]
    return total


@dataclass
class CollectiveStats:
    bytes_by_kind: dict = field(default_factory=dict)
    count_by_kind: dict = field(default_factory=dict)

    @property
    def total_bytes(self) -> float:
        return float(sum(self.bytes_by_kind.values()))

    def to_dict(self):
        return {"bytes_by_kind": self.bytes_by_kind,
                "count_by_kind": self.count_by_kind,
                "total_bytes": self.total_bytes}


def collective_stats(hlo_text: str) -> CollectiveStats:
    """Sum result-shape bytes of every collective op ('-start' counted,
    '-done' skipped to avoid double counting async pairs)."""
    stats = CollectiveStats()
    for m in _OP_RE.finditer(hlo_text):
        type_str, kind = m.group(1), m.group(2)
        line = hlo_text[m.start():hlo_text.index("\n", m.start())]
        if f"{kind}-done" in line:
            continue
        b = shape_bytes(type_str)
        stats.bytes_by_kind[kind] = stats.bytes_by_kind.get(kind, 0) + b
        stats.count_by_kind[kind] = stats.count_by_kind.get(kind, 0) + 1
    return stats


# -------------------------------------------------------------- instructions

_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.+?)\s+([a-z][\w\-]*)\("
)
_COMPUTATION_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s+\(")
_OP_NAME_RE = re.compile(r'op_name="([^"]*)"')
_ARRAY_RE = re.compile(r"^\w+\[([\d,]*)\]")

# opcodes that pass an array on (or name one) without writing it
PASS_THROUGH = frozenset(
    {"parameter", "get-tuple-element", "tuple", "while", "bitcast"}
)


@dataclass(frozen=True)
class Instr:
    name: str
    opcode: str
    type: str          # result type, e.g. "bf16[16,2048,8,128]{...}"
    op_name: str       # "" where the instruction carries none

    @property
    def dims(self) -> tuple[int, ...] | None:
        """The result's dimensions; None where the result is a tuple."""
        m = _ARRAY_RE.match(self.type)
        return tuple(int(d) for d in m.group(1).split(",") if d) if m else None


def unfused_instructions(hlo_text: str) -> list[Instr]:
    """Every instruction outside the computations that fusions call: the
    ops the program runs, a fusion counted once under its own op_name."""
    fused = set(re.findall(r"calls=%?([\w.\-]+)", hlo_text))
    out, inside = [], False
    for line in hlo_text.splitlines():
        head = _COMPUTATION_RE.match(line)
        if head:
            inside = head.group(1) in fused
            continue
        m = _INSTR_RE.match(line)
        if m and not inside:
            on = _OP_NAME_RE.search(line)
            out.append(Instr(m.group(1), m.group(3), m.group(2),
                             on.group(1) if on else ""))
    return out


# ------------------------------------------------------------------ roofline

@dataclass(frozen=True)
class HwConstants:
    peak_flops: float = 197e12       # bf16 / chip
    hbm_bw: float = 819e9            # bytes/s / chip
    link_bw: float = 50e9            # bytes/s / ICI link


def roofline_terms(
    hlo_flops: float,
    hlo_bytes: float,
    collective_bytes: float,
    chips: int,
    hw: HwConstants = HwConstants(),
) -> dict:
    """The three roofline terms in seconds (per step, whole mesh).

    cost_analysis reports whole-program numbers for the SPMD module, which
    XLA gives *per partition*; we treat flops/bytes as per-chip and
    collectives as per-chip wire bytes over one link.
    """
    compute = hlo_flops / hw.peak_flops
    memory = hlo_bytes / hw.hbm_bw
    collective = collective_bytes / hw.link_bw
    dominant = max(
        ("compute", compute), ("memory", memory), ("collective", collective),
        key=lambda kv: kv[1],
    )[0]
    return {
        "compute_s": compute,
        "memory_s": memory,
        "collective_s": collective,
        "dominant": dominant,
        "bound_s": max(compute, memory, collective),
    }
