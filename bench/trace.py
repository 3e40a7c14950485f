"""Reduce a profiler trace (``.xplane.pb``) to the benchmark's numbers.

Device planes (``/device:TPU:<n>``) carry one event per executed XLA
operation on their ``XLA Ops`` line; the host plane carries the
benchmark's own spans (``jax.profiler.TraceAnnotation``) on the thread
that made them.  From these: the union of operation intervals (busy
time) and its complement (idle), time per kernel, collective time
during which no other operation runs on the chip (exposed), the
operations that took most time, and the idle gaps labelled by the host
span they fall in.
"""
from __future__ import annotations

import glob
import os
from collections import defaultdict
from typing import Dict, Iterable, List, Sequence, Tuple

Interval = Tuple[float, float]

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute", "allgather", "allreduce",
               "reducescatter")


def merge(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, disjoint union of [start, end) intervals."""
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def total(intervals: Iterable[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def clip(intervals: Iterable[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def subtract(a: Sequence[Interval], b: Sequence[Interval]
             ) -> List[Interval]:
    """Parts of merged ``a`` not covered by merged ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def op_name(event: str) -> str:
    """An op event's own name (``%fusion.3``), without the text of its
    operands, which name other ops."""
    return event.split(" = ", 1)[0]


def short(event: str) -> str:
    """The op's name and result type, for the breakdown."""
    head = event.split(" = ", 1)
    if len(head) == 1:
        return event[:80]
    return f"{head[0]} = {head[1].split('{', 1)[0].split(' ', 1)[0][:60]}"


def is_collective(name: str) -> bool:
    n = op_name(name).lower()
    return any(c in n for c in COLLECTIVES)


def is_control(name: str) -> bool:
    """A loop or call, whose event spans the ops of its body."""
    return op_name(name).lstrip("%").split(".")[0] in (
        "while", "conditional", "call")


def self_times(events: Sequence[Tuple[str, float, float]]
               ) -> List[Tuple[str, float]]:
    """Each op's time less the time of the ops nested in it (a loop's
    event spans the events of its body)."""
    out: List[Tuple[str, float]] = []
    stack: List[list] = []            # [name, end, child time, duration]
    for n, s, e in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        while stack and s >= stack[-1][1]:
            top = stack.pop()
            out.append((top[0], top[3] - top[2]))
        if stack:
            stack[-1][2] += e - s
        stack.append([n, e, 0.0, e - s])
    out += [(top[0], top[3] - top[2]) for top in stack]
    return out


def find(trace_dir: str) -> str:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(files, key=os.path.getmtime)


def load(path: str) -> Dict[str, Dict[str, List[Tuple[str, float, float]]]]:
    """plane name -> line name -> [(event name, start s, end s)]."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    out: Dict[str, Dict[str, list]] = {}
    for plane in pd.planes:
        lines: Dict[str, list] = {}
        for line in plane.lines:
            evs = lines.setdefault(line.name, [])
            for ev in line.events:
                s = ev.start_ns * 1e-9
                evs.append((ev.name, s, s + ev.duration_ns * 1e-9))
        out[plane.name] = lines
    return out


def device_planes(planes) -> List[str]:
    return sorted(n for n in planes if n.startswith("/device:TPU:")
                  and "XLA Ops" in planes[n])


def reduce(planes, *, kernels: Sequence[str] = (),
           spans: Sequence[str] = ()) -> Dict:
    """The numbers of one trace, over its steady window: from the first
    to the last operation on the first chip.  ``kernels`` are
    substrings of op names to total; ``spans`` are the host span names
    that label idle gaps (a gap in none of them is "host")."""
    devs = device_planes(planes)
    if not devs:
        return {}
    per_dev = []
    for name in devs:
        ops = planes[name]["XLA Ops"]
        if not ops:
            continue
        per_dev.append((name, ops))
    if not per_dev:
        return {}
    _, ops0 = per_dev[0]
    lo = min(s for _, s, _ in ops0)
    hi = max(e for _, _, e in ops0)
    busy_by_dev, kern = [], defaultdict(lambda: [0.0, 0])
    top = defaultdict(float)
    for i, (_, ops) in enumerate(per_dev):
        busy = merge(clip(((s, e) for _, s, e in ops), lo, hi))
        busy_by_dev.append(total(busy))
        if i:
            continue
        inside_ops = [(n, s, e) for n, s, e in ops if lo <= s and e <= hi]
        for n, t in self_times(inside_ops):
            top[short(n)] += t
        for n, s, e in inside_ops:
            for k in kernels:
                if k in op_name(n):
                    kern[k][0] += e - s
                    kern[k][1] += 1
    inside = [(n, s, e) for n, s, e in ops0 if e > lo and s < hi]
    busy0 = merge(clip(((s, e) for _, s, e in inside), lo, hi))
    coll = merge(clip(((s, e) for n, s, e in inside if is_collective(n)),
                      lo, hi))
    compute = merge(clip(((s, e) for n, s, e in inside
                          if not is_collective(n) and not is_control(n)),
                         lo, hi))
    gaps = subtract([(lo, hi)], busy0)
    host = []
    for lines in (planes.get("/host:CPU") or {}).values():
        host += [(n, s, e) for n, s, e in lines if n in spans]
    idle = defaultdict(float)
    for s, e in gaps:
        mid = 0.5 * (s + e)
        label = next((n for n, hs, he in host if hs <= mid < he), "host")
        idle[label] += e - s
    span = hi - lo
    return {
        "window_s": span,
        "busy_s": sum(busy_by_dev) / len(busy_by_dev),
        "idle_share": 1.0 - total(busy0) / span if span > 0 else None,
        "kernel_s": {k: v[0] for k, v in kern.items()},
        "kernel_n": {k: v[1] for k, v in kern.items()},
        "collective_s": total(coll),
        "exposed_collective_s": total(subtract(coll, compute)),
        "device_ops": sorted(top.items(), key=lambda kv: -kv[1])[:10],
        "idle_gaps": sorted(idle.items(), key=lambda kv: -kv[1])[:10],
    }
