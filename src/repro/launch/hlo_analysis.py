"""Post-SPMD HLO analysis: collective bytes.

``compiled.cost_analysis()`` gives FLOPs and HBM bytes but not collective
traffic; we parse the (per-device, post-partitioning) HLO text and sum the
operand sizes of every collective op, bucketed by kind.

Compiled HLO prints operands as %names (untyped), so per-op operand bytes
are recovered from the RESULT shape + the replica-group size:
    all-gather:      operand = result / group_size
    reduce-scatter:  operand = result * group_size
    all-reduce / all-to-all / collective-permute: operand = result
Async pairs (-start/-done) are counted once via the -start op, whose tuple
result's first element is the operand.
"""

from __future__ import annotations

import re
from typing import Dict

COLLECTIVE_OPS = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                  "collective-permute")

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "s32": 4, "s16": 2, "s8": 1, "u64": 8, "u32": 4, "u16": 2,
    "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_SHAPE_RE = re.compile(r"\b([a-z0-9]+)\[([0-9,]*)\]")
_OP_RE = re.compile(
    r"=\s*(.*?)\s*"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(-start)?\(")
_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")


def _shape_bytes(dtype: str, dims: str) -> int:
    if dtype not in _DTYPE_BYTES:
        return 0
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES[dtype]


def _group_size(line: str) -> int:
    m = _GROUPS_RE.search(line)
    if m:
        return max(int(m.group(2)), 1)
    m = _GROUPS_LIST_RE.search(line)
    if m:  # explicit list form {{0,1,2,3},{...}} -> size of first group
        return max(len(m.group(1).split(",")), 1)
    return 1


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Per-device collective traffic by op kind.

    Two aggregates:
      total      — sum of operand sizes (the brief's metric).
      ring_total — ring-algorithm wire bytes per device:
                   all-reduce 2·X·(g-1)/g, all-gather/reduce-scatter
                   X·(g-1)/g on the FULL tensor X, all-to-all X·(g-1)/g,
                   collective-permute X.
    """
    out = {k: 0 for k in COLLECTIVE_OPS}
    ring = {k: 0.0 for k in COLLECTIVE_OPS}
    for line in hlo_text.splitlines():
        m = _OP_RE.search(line)
        if not m:
            continue
        if re.search(r"(all-gather|all-reduce|all-to-all|reduce-scatter|"
                     r"collective-permute)-done\(", line):
            continue
        kind = m.group(2)
        result_part = m.group(1)
        shapes = _SHAPE_RE.findall(result_part)
        if not shapes:
            continue
        g = _group_size(line)
        if m.group(3):  # async -start: tuple (operand, result, ...)
            op_bytes = _shape_bytes(*shapes[0])
            full = op_bytes * g if kind == "all-gather" else op_bytes
        else:
            res_bytes = sum(_shape_bytes(d, s) for d, s in shapes)
            if kind == "all-gather":
                op_bytes = res_bytes // g
                full = res_bytes
            elif kind == "reduce-scatter":
                op_bytes = res_bytes * g
                full = op_bytes
            else:
                op_bytes = res_bytes
                full = res_bytes
        out[kind] += op_bytes
        frac = (g - 1) / g if g > 1 else 0.0
        if kind == "all-reduce":
            ring[kind] += 2.0 * full * frac
        elif kind in ("all-gather", "reduce-scatter", "all-to-all"):
            ring[kind] += full * frac
        else:  # collective-permute
            ring[kind] += full
    out["total"] = sum(out[k] for k in COLLECTIVE_OPS)
    out["ring_total"] = int(sum(ring[k] for k in COLLECTIVE_OPS))
    return out

