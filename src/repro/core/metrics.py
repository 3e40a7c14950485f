"""Measurement layer — the Prometheus/Grafana analogue.

The paper's methodology is "constantly measuring, learning, and informing
every aspect of a machine learning workflow" (CHASE-CI §VI, Figs 3-6,
Table I).  This registry provides counters / gauges / histograms plus
timestamped series, and renders the paper's Table I (per-step resource
summary) from StepReports.

Spans (``Registry.span``) time regions of the program on
``time.monotonic`` and mirror each into the profiler's trace as a
``jax.profiler.TraceAnnotation``, so a trace holds them on the same clock
as the device's operations.  ``SlowSteps`` flags a step far slower than
its recent median and notes on the step's span what the process was
doing meanwhile.
"""
from __future__ import annotations

import collections
import gc
import resource
import statistics
import threading
import time
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

from jax.profiler import TraceAnnotation


@dataclass
class Series:
    """One metric stream.  Pod/worker threads append concurrently while
    dashboards summarize, so every read derives from ONE locked snapshot —
    the registry's dict lock alone cannot make count/mean/total agree."""
    points: List[Tuple[float, float]] = field(default_factory=list)
    _lock: threading.Lock = field(default_factory=threading.Lock,
                                  repr=False, compare=False)

    def record(self, value: float, ts: Optional[float] = None):
        with self._lock:
            self.points.append((time.time() if ts is None else ts,
                                float(value)))

    def snapshot(self) -> List[Tuple[float, float]]:
        """A consistent copy of the points at one instant."""
        with self._lock:
            return list(self.points)

    @property
    def last(self) -> float:
        with self._lock:
            return self.points[-1][1] if self.points else 0.0

    @property
    def total(self) -> float:
        return sum(v for _, v in self.snapshot())

    @property
    def mean(self) -> float:
        pts = self.snapshot()
        return sum(v for _, v in pts) / len(pts) if pts else 0.0

    @property
    def max(self) -> float:
        return max((v for _, v in self.snapshot()), default=0.0)

    def percentile(self, q: float) -> float:
        """Nearest-rank percentile of recorded values, q in [0, 100]."""
        vals = sorted(v for _, v in self.snapshot())
        if not vals:
            return 0.0
        rank = min(len(vals) - 1, max(0, int(round(q / 100 * (len(vals) - 1)))))
        return vals[rank]

    def stats(self) -> Dict[str, float]:
        """count/last/mean/max/total/p50/p99 from a SINGLE snapshot, so
        the numbers are mutually consistent even under concurrent
        ``record`` calls (count * mean == total, always)."""
        pts = self.snapshot()
        vals = sorted(v for _, v in pts)
        n = len(vals)

        def pct(q):
            if not n:
                return 0.0
            return vals[min(n - 1, max(0, int(round(q / 100 * (n - 1)))))]

        total = sum(vals)
        return {"count": n, "last": pts[-1][1] if pts else 0.0,
                "mean": total / n if n else 0.0,
                "max": vals[-1] if n else 0.0, "total": total,
                "p50": pct(50), "p99": pct(99)}


class Span:
    """One timed region: ``name``, ``start`` and ``end`` on the registry's
    clock, ``parent`` (the innermost span open on the same thread when it
    began, or None) and ``attrs``.  Made by ``Registry.span``; while
    open it is also a ``TraceAnnotation`` of the same name."""
    __slots__ = ("name", "start", "end", "parent", "attrs", "_registry",
                 "_annotation")

    def __init__(self, registry: "Registry", name: str,
                 attrs: Dict[str, Any]):
        self.name = name
        self.attrs = attrs
        self.start = self.end = None
        self.parent: Optional[Span] = None
        self._registry = registry
        self._annotation = None

    @property
    def duration(self) -> float:
        return self.end - self.start

    def __enter__(self) -> "Span":
        reg = self._registry
        stack = reg._open_spans()
        self.parent = stack[-1] if stack else None
        stack.append(self)
        self._annotation = TraceAnnotation(self.name)
        self._annotation.__enter__()
        self.start = reg.clock()
        return self

    def __exit__(self, *exc) -> None:
        reg = self._registry
        self.end = reg.clock()
        self._annotation.__exit__(*exc)
        reg._open_spans().pop()
        self._annotation = self._registry = None
        with reg._span_lock:
            reg._spans.append(self)


MAX_SPANS = 1 << 16        # finished spans a registry keeps, newest last


class Registry:
    def __init__(self, clock: Callable[[], float] = time.monotonic):
        self._lock = threading.Lock()
        self._series: Dict[str, Series] = {}
        # spans: the clock they are stamped on, the finished ones in a
        # ring (a long-lived server keeps the newest), and each thread's
        # stack of open ones (a new span's parent is the top)
        self.clock = clock
        self._spans: "collections.deque[Span]" = collections.deque(
            maxlen=MAX_SPANS)
        self._span_lock = threading.Lock()
        self._local = threading.local()
        # listeners get (name, value, ts) on every inc/gauge/timer — the
        # near-real-time monitor (repro.vcluster.monitor) taps this to
        # stream throughput gauges without polling
        self._listeners: List[Callable[[str, float, float], None]] = []

    def series(self, name: str) -> Series:
        with self._lock:
            return self._series.setdefault(name, Series())

    def add_listener(self, cb: Callable[[str, float, float], None]) -> None:
        """Register cb(name, value, ts) on every recorded point.
        Listener exceptions are swallowed: observability must never take
        down the thing it observes."""
        with self._lock:
            self._listeners.append(cb)

    def _notify(self, name: str, value: float) -> None:
        for cb in list(self._listeners):
            try:
                cb(name, value, time.time())
            except Exception:
                pass

    def inc(self, name: str, value: float = 1.0):
        self.series(name).record(value)
        self._notify(name, value)

    def gauge(self, name: str, value: float):
        self.series(name).record(value)
        self._notify(name, value)

    def span(self, name: str, **attrs) -> Span:
        """A context manager timing a region as a ``Span`` (which it
        yields, so the region can add ``attrs``); kept when it closes,
        exception or not.  Costs two clock reads and an append, and a
        profiler annotation that is a no-op unless a trace is
        recording."""
        return Span(self, name, attrs)

    def _open_spans(self) -> List[Span]:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def spans(self, name: Optional[str] = None, lo: float = float("-inf"),
              hi: float = float("inf")) -> List[Span]:
        """Finished spans (of ``name``, if given) that ended inside
        [lo, hi], oldest first; the ring keeps the newest ``MAX_SPANS``."""
        with self._span_lock:
            spans = list(self._spans)
        return [s for s in spans if (name is None or s.name == name)
                and lo <= s.end <= hi]

    @contextmanager
    def timer(self, name: str):
        """A span whose duration is also recorded in the series
        ``name``."""
        span = self.span(name)
        try:
            with span:
                yield
        finally:
            self.series(name).record(span.duration)
            self._notify(name, span.duration)

    def scrape(self) -> Dict[str, float]:
        with self._lock:
            return {k: s.last for k, s in self._series.items()}

    def summary(self) -> Dict[str, Dict[str, float]]:
        """Per-series stats (count/last/mean/max/total/p50/p99) — the
        scrape endpoint a serving dashboard (paper §VI) would poll.
        Each series is summarized from one atomic snapshot, so its stats
        are mutually consistent under concurrent recording."""
        with self._lock:
            series = dict(self._series)
        return {k: s.stats() for k, s in series.items()}

    def to_csv(self) -> str:
        lines = ["metric,count,last,mean,max,total"]
        for k, st in sorted(self.summary().items()):
            lines.append(f"{k},{st['count']},{st['last']:.6g},"
                         f"{st['mean']:.6g},{st['max']:.6g},"
                         f"{st['total']:.6g}")
        return "\n".join(lines)


# a step slower than SLOW_FACTOR times the median of the last SLOW_WINDOW
# steps is slow; no step is judged before SLOW_MIN_STEPS have been seen
SLOW_FACTOR = 3.0
SLOW_WINDOW = 64
SLOW_MIN_STEPS = 8
CPU_STAT = "/sys/fs/cgroup/cpu.stat"


class _GcPauses:
    """Seconds the interpreter has spent collecting garbage, summed by a
    ``gc.callbacks`` hook (one per process, installed on first use)."""

    def __init__(self):
        self.seconds = 0.0
        self._t0: Optional[float] = None
        self._installed = False

    def install(self) -> None:
        if not self._installed:
            self._installed = True
            gc.callbacks.append(self._on_gc)

    def _on_gc(self, phase: str, info) -> None:
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            self.seconds += time.perf_counter() - self._t0
            self._t0 = None


_GC_PAUSES = _GcPauses()


def _cpu_stat() -> Optional[Dict[str, int]]:
    """The cgroup's CPU counters (``nr_throttled``, ``throttled_usec``,
    ...), or None where the file is missing."""
    try:
        with open(CPU_STAT) as f:
            return {k: int(v) for k, v in
                    (line.split() for line in f if line.strip())}
    except (OSError, ValueError):
        return None


class SlowSteps:
    """Flags a step whose wall time exceeds ``SLOW_FACTOR`` times the
    median of the last ``SLOW_WINDOW`` steps: it increments ``counter``
    and records on the step's span what the process was doing during the
    step.  Wrap each step as::

        with registry.span("step") as span:
            before = slow.begin()
            ...
        slow.end(span, before)

    A slow step's span gains ``median_s``, the involuntary context
    switches (``invol_switches``) and user plus system CPU seconds
    (``cpu_s``) of the process over the step, the garbage collector's
    pauses inside it (``gc_s``) and, where the cgroup's ``cpu.stat``
    exists, the throttled periods and seconds since its previous read
    (``nr_throttled``, ``throttled_s``).  That file is read here once and
    then only on slow steps."""

    def __init__(self, registry: Registry, counter: str):
        self._registry = registry
        self._counter = counter
        self._walls: "collections.deque[float]" = collections.deque(
            maxlen=SLOW_WINDOW)
        self._cpu_stat = _cpu_stat()
        _GC_PAUSES.install()

    def begin(self) -> Tuple[Any, float]:
        return resource.getrusage(resource.RUSAGE_SELF), _GC_PAUSES.seconds

    def end(self, span: Span, before: Tuple[Any, float]) -> bool:
        """Judge the closed ``span``; True if the step was slow."""
        wall = span.duration
        median = (statistics.median(self._walls)
                  if len(self._walls) >= SLOW_MIN_STEPS else None)
        self._walls.append(wall)
        if median is None or wall <= SLOW_FACTOR * median:
            return False
        ru0, gc0 = before
        ru = resource.getrusage(resource.RUSAGE_SELF)
        span.attrs.update(
            median_s=median, invol_switches=ru.ru_nivcsw - ru0.ru_nivcsw,
            cpu_s=(ru.ru_utime + ru.ru_stime) - (ru0.ru_utime + ru0.ru_stime),
            gc_s=_GC_PAUSES.seconds - gc0)
        now = _cpu_stat()
        if now is not None and self._cpu_stat is not None:
            span.attrs.update(
                nr_throttled=now.get("nr_throttled", 0)
                - self._cpu_stat.get("nr_throttled", 0),
                throttled_s=1e-6 * (now.get("throttled_usec", 0)
                                    - self._cpu_stat.get("throttled_usec", 0)))
        self._cpu_stat = now
        self._registry.inc(self._counter)
        return True


@dataclass
class StepReport:
    """One column of the paper's Table I."""
    step: str
    pods: int = 0
    cpus: int = 0
    devices: int = 0          # "# of GPUs" in the paper
    data_processed_bytes: int = 0
    memory_bytes: int = 0
    total_time_s: float = 0.0
    site: str = ""            # federation site the step ran at (repro.fabric)
    extra: Dict[str, float] = field(default_factory=dict)


def table_one(reports: List[StepReport]) -> str:
    """Render the paper's Table I (Nautilus resource summary) as markdown."""
    def fmt_bytes(b):
        for unit in ("B", "KB", "MB", "GB", "TB"):
            if abs(b) < 1024:
                return f"{b:.1f}{unit}"
            b /= 1024
        return f"{b:.1f}PB"

    head = "| | " + " | ".join(r.step for r in reports) + " |"
    sep = "|---" * (len(reports) + 1) + "|"
    rows = [
        ("# of Pods", [str(r.pods) for r in reports]),
        ("# of CPUs", [str(r.cpus) for r in reports]),
        ("# of Devices", [str(r.devices) for r in reports]),
        ("Data Processed", [fmt_bytes(r.data_processed_bytes) for r in reports]),
        ("Memory", [fmt_bytes(r.memory_bytes) for r in reports]),
        ("Total Time", [f"{r.total_time_s:.1f}s" for r in reports]),
    ]
    out = [head, sep]
    # multi-site runs (repro.fabric) say where each step landed
    if any(r.site for r in reports):
        out.append("| Site | " + " | ".join(r.site or "-" for r in reports)
                   + " |")
    for name, vals in rows:
        out.append("| " + name + " | " + " | ".join(vals) + " |")
    # free-form per-step metrics (e.g. serving tokens/s, slot occupancy)
    # render as additional rows; steps missing a key show "-"
    extra_keys = sorted({k for r in reports for k in r.extra})
    for key in extra_keys:
        vals = [f"{r.extra[key]:.4g}" if key in r.extra else "-"
                for r in reports]
        out.append("| " + key + " | " + " | ".join(vals) + " |")
    return "\n".join(out)
