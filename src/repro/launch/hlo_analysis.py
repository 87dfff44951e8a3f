"""Collective-traffic extraction from compiled HLO text.

``cost_analysis()`` has no collective-bytes entry, so we parse the
post-optimization module: every ``all-gather`` / ``all-reduce`` /
``reduce-scatter`` / ``all-to-all`` / ``collective-permute`` op contributes
per-participant *wire bytes* under the standard ring-algorithm accounting:

  all-reduce       2 * bytes * (g-1)/g      (reduce-scatter + all-gather)
  all-gather       out_bytes * (g-1)/g
  reduce-scatter   in_bytes  * (g-1)/g  = out_bytes * (g-1)
  all-to-all       bytes * (g-1)/g
  collective-permute  bytes                  (point-to-point)

where ``g`` is the replica-group size parsed from ``replica_groups=[G,S]<=``
(iota form) or ``{{...}}`` (explicit form).  Shapes are parsed from the op's
result type; for all-reduce / all-to-all the result bytes equal the input
bytes, for all-gather the result is the gathered buffer, for reduce-scatter
the result is the scattered shard.
"""

from __future__ import annotations

import dataclasses
import re
from collections import defaultdict

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1,
    "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "token": 0,
}

# result can be a plain shape or a tuple of shapes
_OP_RE = re.compile(
    r"=\s+(?P<result>\([^)]*\)|[a-z0-9]+\[[^\]]*\](?:\{[^}]*\})?)\s+"
    r"(?P<op>all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?P<start>-start)?\("
)
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([\d,]*)\]")
_IOTA_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]<=")
_EXPLICIT_GROUPS_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")


@dataclasses.dataclass
class CollectiveStats:
    #: per-participant wire bytes, summed over all collective ops
    wire_bytes: float
    #: raw buffer bytes moved through collectives (no ring scaling)
    buffer_bytes: float
    #: op-type -> (count, wire_bytes)
    by_op: dict


def _shape_bytes(text: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(text):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def _group_size(line: str, default: int) -> int:
    m = _IOTA_GROUPS_RE.search(line)
    if m:
        return int(m.group(2))
    m = _EXPLICIT_GROUPS_RE.search(line)
    if m:
        return len([x for x in m.group(1).split(",") if x.strip()])
    return default


def collective_stats(hlo_text: str, n_devices: int) -> CollectiveStats:
    wire = 0.0
    buf = 0.0
    by_op: dict[str, list[float]] = defaultdict(lambda: [0, 0.0])
    for line in hlo_text.splitlines():
        m = _OP_RE.search(line)
        if m is None:
            continue
        op = m.group("op")
        result_bytes = _shape_bytes(m.group("result"))
        g = _group_size(line, n_devices)
        if g <= 1:
            continue
        if op == "all-reduce":
            w = 2.0 * result_bytes * (g - 1) / g
        elif op == "all-gather":
            w = result_bytes * (g - 1) / g
        elif op == "reduce-scatter":
            w = result_bytes * (g - 1)
        elif op == "all-to-all":
            w = result_bytes * (g - 1) / g
        else:  # collective-permute
            w = float(result_bytes)
        wire += w
        buf += result_bytes
        by_op[op][0] += 1
        by_op[op][1] += w
    return CollectiveStats(
        wire_bytes=wire,
        buffer_bytes=buf,
        by_op={k: tuple(v) for k, v in by_op.items()},
    )


_REMAT_NAME_RE = re.compile(r"%(fusion|[a-z-]+)\.?(\d*)")


def count_ops(hlo_text: str, opcode: str) -> int:
    return len(re.findall(rf"\b{re.escape(opcode)}\(", hlo_text))
