"""Read the program's own scopes and spans out of a run's profiler trace.

Device time by scope: the trace's op events carry no scope, so each op
that ran inside a step's executions (its module's events on the device's
``XLA Modules`` line) is looked up in the map the program makes from that
step's compiled text (``repro.runtime.steps.op_scopes``: op name ->
``named_scope`` path).  Where ops making up under ``COVERAGE`` of the
step's device time are in the map, the compiled text is not the program
that ran, and nothing is read.

Idle time by span: each idle gap of the device, over the window that
``bench/trace.reduce`` takes, is cut by the program's host spans
(``Registry.span``, mirrored into the trace as annotations) that it
intersects, each piece going to the innermost span covering it; time in
none of them is "host".

Every function returns None, and logs why, where the trace or the program
lacks what it reads: a program without these scopes or spans reads as
nothing, never as zero.
"""
from __future__ import annotations

import bisect
import functools
import heapq
import sys
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

from bench import trace

COVERAGE = 0.99
DECODE_STEP = "jit_paged_step"
TRAIN_STEP = "jit_train_chunk"
PROGRAM_SPANS = "serve/"           # prefix of the serving loop's spans

Op = Tuple[str, str, float]        # (op name, scope path, self seconds)


def _log(msg: str) -> None:
    print(f"[bench] scopes: {msg}", file=sys.stderr, flush=True)


@functools.lru_cache(maxsize=1)
def load(path: str):
    """The trace's planes (bench/trace.load), read once per path."""
    return trace.load(path)


def _planes(run) -> Optional[dict]:
    path = run.get("trace_path")
    if path is None:
        _log("the run has no trace")
        return None
    planes = load(path)
    if not trace.device_planes(planes):
        _log("the trace has no TPU device plane")
        return None
    return planes


def program_scopes(module: str) -> Optional[Dict[str, str]]:
    """The program's op -> scope map for ``module``, or None where the
    program makes none or making it fails."""
    try:
        from repro.runtime.steps import op_scopes
        return op_scopes(module)
    except Exception as e:           # a reading must never fail the run
        _log(f"no op scopes for {module}: {type(e).__name__}: {e}")
        return None


def step_ops(planes, module: str, scopes: Dict[str, str]
             ) -> Optional[Tuple[List[Op], int]]:
    """The ops that ran inside ``module``'s executions on the first
    device, each with its scope path and self time, and the number of
    executions; None where the module never ran or the map covers under
    ``COVERAGE`` of the ops' time."""
    lines = planes[trace.device_planes(planes)[0]]
    runs = sorted((s, e) for n, s, e in lines.get("XLA Modules", ())
                  if n == module or n.startswith(module + "("))
    if not runs:
        _log(f"{module} did not run in the trace")
        return None
    starts = [s for s, _ in runs]
    inside = []
    for n, s, e in lines.get("XLA Ops", ()):
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and e <= runs[i][1]:
            inside.append((n, s, e))
    ops, found, whole = [], 0.0, 0.0
    for event, t in trace.self_times(inside):
        name = trace.op_name(event).lstrip("%")
        whole += t
        if name in scopes:
            found += t
        ops.append((name, scopes.get(name, "?"), t))
    if whole <= 0 or found < COVERAGE * whole:
        _log(f"{module}: the op map covers {found:.6g} s of {whole:.6g} s "
             f"of device time, under {COVERAGE:.0%}")
        return None
    _log(f"{module}: the op map covers {found:.6g} s of {whole:.6g} s of "
         f"device time in {len(runs)} runs")
    return ops, len(runs)


def in_scope(path: str, names: Sequence[str]) -> bool:
    parts = path.split("/")
    return any(n in parts for n in names)


@functools.lru_cache(maxsize=4)
def _run_ops(path: str, module: str):
    scopes = program_scopes(module)
    if scopes is None:
        return None
    return step_ops(load(path), module, scopes)


def scope_ms(run, module: str, names: Sequence[str],
             exclude: Sequence[str] = (), steps_per_run: int = 1
             ) -> Optional[float]:
    """Device milliseconds per step of the ops of ``module`` under any of
    the scopes ``names``, leaving out ops whose names hold a string of
    ``exclude``; a module's execution holds ``steps_per_run`` steps."""
    if _planes(run) is None:
        return None
    got = _run_ops(run["trace_path"], module)
    if got is None:
        return None
    ops, runs = got
    secs = sum(t for name, path, t in ops if in_scope(path, names)
               and not any(x in name for x in exclude))
    return 1e3 * secs / (runs * steps_per_run)


def labelled(spans: Sequence[Tuple[str, float, float]]
             ) -> List[Tuple[float, float, str]]:
    """Disjoint, sorted pieces of time, each named for the innermost
    (shortest) of ``spans`` covering it."""
    cuts = sorted({t for _, s, e in spans for t in (s, e)})
    by_start = sorted(spans, key=lambda sp: sp[1])
    active: List[Tuple[float, float, str]] = []   # (duration, end, name)
    out, i = [], 0
    for a, b in zip(cuts, cuts[1:]):
        while i < len(by_start) and by_start[i][1] <= a:
            n, s, e = by_start[i]
            heapq.heappush(active, (e - s, e, n))
            i += 1
        while active and active[0][1] <= a:
            heapq.heappop(active)
        # spans end only at cuts, so the shortest one left covers [a, b)
        if active:
            out.append((a, b, active[0][2]))
    return out


def idle_by_span(planes, prefix: str = PROGRAM_SPANS
                 ) -> Optional[Dict[str, float]]:
    """Idle seconds of the first device over the trace's window, by the
    innermost program span (name starting with ``prefix``, on any host
    thread) that covers each piece of each gap; "host" for the rest."""
    lines = planes[trace.device_planes(planes)[0]]
    ops = lines.get("XLA Ops", ())
    if not ops:
        return None
    lo = min(s for _, s, _ in ops)
    hi = max(e for _, _, e in ops)
    gaps = trace.subtract([(lo, hi)], trace.merge(
        trace.clip(((s, e) for _, s, e in ops), lo, hi)))
    spans = [(n, s, e) for host in (planes.get("/host:CPU") or {}).values()
             for n, s, e in host if n.startswith(prefix)]
    if not spans:
        _log(f"no program span ({prefix}*) in the trace")
        return None
    pieces = labelled(spans)
    out: Dict[str, float] = defaultdict(float)
    j = 0
    for gs, ge in gaps:
        while j < len(pieces) and pieces[j][1] <= gs:
            j += 1
        covered, k = 0.0, j
        while k < len(pieces) and pieces[k][0] < ge:
            ov = min(ge, pieces[k][1]) - max(gs, pieces[k][0])
            if ov > 0:
                out[pieces[k][2]] += ov
                covered += ov
            k += 1
        out["host"] += (ge - gs) - covered
    return dict(out)


@functools.lru_cache(maxsize=4)
def _run_idle(path: str):
    idle = idle_by_span(load(path))
    if idle is not None:
        _log("idle s by span: " + ", ".join(
            f"{n}={v:.6g}" for n, v in sorted(idle.items())))
    return idle


def idle_ms(run, module: str, names: Sequence[str]) -> Optional[float]:
    """Device idle milliseconds inside the program spans ``names`` (each
    piece counted for its innermost span), per execution of
    ``module``."""
    planes = _planes(run)
    if planes is None:
        return None
    idle = _run_idle(run["trace_path"])
    runs = sum(1 for n, _, _ in planes[trace.device_planes(planes)[0]]
               .get("XLA Modules", ())
               if n == module or n.startswith(module + "("))
    if idle is None or not runs:
        return None
    return 1e3 * sum(idle.get(n, 0.0) for n in names) / runs

