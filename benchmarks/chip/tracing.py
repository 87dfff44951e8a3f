"""Reduction from a profiler trace to the numbers the per-layer metrics
read.

``load`` turns the ``.xplane.pb`` a traced run writes into a small list of
events ``[plane, line, name, start_ns, duration_ns, module]``: every event
of a device plane, and the host events long enough to name an idle gap.
Everything else here works on that list, so the reduction can be checked
on a recorded trace kept with the tests.

* busy: the union of the op intervals on each device, averaged over the
  devices that ran anything;
* program time: device time of the programs a metric names, matched by
  the module name (the jitted function's name);
* kernel time: device time of the ops whose name contains a kernel's name;
* idle gaps: the longest stretches with no op on a device, each named by
  the innermost host event that spans it.
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

#: device lines whose events are the operations that ran
OP_LINES = ("XLA Ops",)
MODULE_LINES = ("XLA Modules",)
#: host events shorter than this cannot name a gap worth reporting
MIN_HOST_NS = 20_000
_SUFFIX = re.compile(r"\(\d+\)$")


def _stat(event, key: str):
    try:
        for k, v in event.stats:
            if k == key:
                return v
    except (TypeError, ValueError):
        return None
    return None


def load(trace_dir: str) -> list[list]:
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    events: list[list] = []
    for path in paths:
        data = ProfileData.from_file(path)
        for plane in data.planes:
            device = plane.name.startswith("/device:")
            host = plane.name.startswith("/host:CPU")
            if not (device or host):
                continue
            for line in plane.lines:
                for e in line.events:
                    dur = float(e.duration_ns)
                    if host and dur < MIN_HOST_NS:
                        continue
                    module = _stat(e, "hlo_module") if device else None
                    events.append([
                        plane.name, line.name, e.name, float(e.start_ns), dur,
                        module,
                    ])
    return events


def device_planes(events: list[list]) -> list[str]:
    return sorted({e[0] for e in events if e[0].startswith("/device:")
                   and e[1] in OP_LINES})


def _merge(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy_intervals(events: list[list], plane: str) -> list[tuple[float, float]]:
    return _merge([
        (e[3], e[3] + e[4]) for e in events
        if e[0] == plane and e[1] in OP_LINES and e[4] > 0
    ])


def busy_seconds(events: list[list]) -> float:
    """Seconds in which an op ran, averaged over the devices that ran any."""
    planes = device_planes(events)
    if not planes:
        return 0.0
    total = sum(
        sum(b - a for a, b in busy_intervals(events, p)) for p in planes
    )
    return total / len(planes) / 1e9


def short_name(name: str) -> str:
    """``%fusion.170 = f32[...] fusion(...)`` -> ``%fusion.170``."""
    return name.split(" = ", 1)[0]


def module_name(name: str) -> str:
    return _SUFFIX.sub("", name)


def program_seconds(events: list[list], names: tuple[str, ...]) -> float:
    """Device seconds of the programs whose module is one of ``names``,
    summed over devices.  Reads the module line where the trace has one,
    and the ops' module stat where it does not."""
    modules = [e for e in events if e[1] in MODULE_LINES]
    if modules:
        return sum(e[4] for e in modules if module_name(e[2]) in names) / 1e9
    return sum(
        e[4] for e in events if e[1] in OP_LINES and e[5] in names
    ) / 1e9


def kernel_seconds(events: list[list], names: tuple[str, ...]) -> float:
    """Device seconds of the ops whose short name (``%name.N``) starts with
    one of ``names``."""
    return sum(
        e[4] for e in events
        if e[1] in OP_LINES and short_name(e[2]).startswith(names)
    ) / 1e9


#: ops that only contain other ops, whose time the trace also gives
#: op by op
CONTAINERS = ("%while", "%conditional", "%call")


def _modules_by_plane(events: list[list]) -> dict[str, tuple[list, list]]:
    out: dict[str, list] = defaultdict(list)
    for e in events:
        if e[1] in MODULE_LINES:
            out[e[0]].append((e[3], e[3] + e[4], module_name(e[2])))
    return {p: ([a for a, _, _ in sorted(v)], sorted(v)) for p, v in out.items()}


def top_ops(events: list[list], k: int = 10) -> list[list]:
    """The ``k`` ops with the most device time, summed over devices and
    over their runs, in seconds, each named ``<program>:<op>``."""
    import bisect

    mods = _modules_by_plane(events)
    by: dict[str, float] = defaultdict(float)
    for e in events:
        if e[1] not in OP_LINES:
            continue
        short = short_name(e[2])
        if short.startswith(CONTAINERS):
            continue
        module = e[5] or "?"
        if e[5] is None and e[0] in mods:
            starts, spans = mods[e[0]]
            i = bisect.bisect_right(starts, e[3]) - 1
            if i >= 0 and spans[i][1] >= e[3]:
                module = spans[i][2]
        by[f"{module}:{short}"] += e[4]
    top = sorted(by.items(), key=lambda kv: -kv[1])[:k]
    return [[name, ns / 1e9] for name, ns in top]


def idle_gaps(events: list[list], k: int = 10) -> list[list]:
    """The ``k`` longest idle stretches of the busiest device, in seconds,
    each named by the shortest host event that spans it (or, where none
    does, the one that covers most of it)."""
    planes = device_planes(events)
    if not planes:
        return []
    plane = max(planes, key=lambda p: sum(b - a for a, b in busy_intervals(events, p)))
    busy = busy_intervals(events, plane)
    gaps = sorted(
        ((busy[i + 1][0] - busy[i][1], busy[i][1], busy[i + 1][0])
         for i in range(len(busy) - 1)),
        reverse=True,
    )[:k]
    host = [e for e in events if e[0].startswith("/host:")]
    out = []
    for length, a, b in gaps:
        spanning = [e for e in host if e[3] <= a and e[3] + e[4] >= b]
        if spanning:
            name = min(spanning, key=lambda e: e[4])[2]
        else:
            def overlap(e):
                return min(b, e[3] + e[4]) - max(a, e[3])
            best = max(host, key=overlap, default=None)
            name = best[2] if best is not None and overlap(best) > 0 else "no host event"
        out.append([name, length / 1e9])
    return out

