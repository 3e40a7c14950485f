"""Parse compiled HLO text for collective traffic.

cost_analysis() has no collective-bytes term, so we derive per-kind operand
bytes from each collective instruction's *result* type (compiled CPU HLO does
not print operand types inline):

    all-gather:        operand = result / group_size
    reduce-scatter:    operand = result * group_size
    all-reduce / all-to-all / collective-permute: operand = result

group_size comes from ``replica_groups=[G,N]<=...`` (iota form) or the first
explicit ``{{...}}`` group.  Tuple-typed results (variadic / -start forms)
sum their element types.

NOTE (trip counts): cost/HLO analysis sees a lax.scan body ONCE.  The
roofline driver therefore measures collectives with the G-diff method —
lowering unrolled G=1 and G=2 variants of each model: per-layer-group bytes
= (G2 - G1), outside-scan bytes = G1 - per_layer, total = outside + G * per
(see repro.roofline.report).
"""
from __future__ import annotations

import re
from collections import defaultdict
from typing import Dict

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")


_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

_TYPE_RE = re.compile(r"\b([a-z]+\d+(?:e\d+m\d+(?:fn)?)?|pred)\[([\d,]*)\]")
_INSTR_RE = re.compile(
    r"=\s*(\([^=]*?\)|[a-z0-9]+\[[\d,]*\][^\s]*)\s+([a-z0-9-]+)\(")
_IOTA_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]<=")
_EXPLICIT_GROUPS_RE = re.compile(r"replica_groups=\{\{([\d,]+)\}")


def _type_bytes(type_str: str) -> int:
    total = 0
    for m in _TYPE_RE.finditer(type_str):
        n = 1
        if m.group(2):
            for d in m.group(2).split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES.get(m.group(1), 4)
    return total


def _group_size(line: str) -> int:
    m = _IOTA_GROUPS_RE.search(line)
    if m:
        return int(m.group(2))
    m = _EXPLICIT_GROUPS_RE.search(line)
    if m:
        return len(m.group(1).split(","))
    return 1


def _classify(op: str):
    for kind in COLLECTIVES:
        if op == kind or op.startswith(kind + "-"):
            if op.endswith("-done"):       # -start carries the traffic
                return None
            return kind
    return None


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """operand bytes per collective kind (+ 'total', 'total_bf16adj').

    total_bf16adj halves f32 collective bytes: XLA:CPU legalizes bf16 dots
    by upcasting operands to f32, and the partitioner then moves the f32
    tensor — on TPU (native bf16 MXU) the same collectives are bf16.  All
    jax-level activations/weights here are bf16 (verified in §Perf), so the
    adjusted number is the TPU-equivalent traffic.
    """
    out: Dict[str, int] = defaultdict(int)
    adj = 0
    for line in hlo_text.splitlines():
        m = _INSTR_RE.search(line)
        if not m:
            continue
        kind = _classify(m.group(2))
        if kind is None:
            continue
        ty = m.group(1)
        rb = _type_bytes(ty)
        if kind == "all-gather":
            rb //= max(_group_size(line), 1)
        elif kind == "reduce-scatter":
            rb *= _group_size(line)
        out[kind] += rb
        adj += rb // 2 if "f32[" in ty else rb
    out["total"] = sum(v for k, v in out.items() if k != "total")
    out["total_bf16adj"] = adj
    return dict(out)


def collective_counts(hlo_text: str) -> Dict[str, int]:
    out: Dict[str, int] = defaultdict(int)
    for line in hlo_text.splitlines():
        m = _INSTR_RE.search(line)
        if m:
            kind = _classify(m.group(2))
            if kind:
                out[kind] += 1
    return dict(out)
