"""What every runner shares: the run's context, seeds, spans and tracing."""
from __future__ import annotations

import dataclasses
import shutil
import tempfile
import threading
import time
from contextlib import contextmanager
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from bench.model import Dims


@dataclasses.dataclass
class Ctx:
    """One run of one cell."""
    workload: str
    cfg: Dict[str, Any]          # configs/<config>.json
    mix: Dict[str, Any]          # traffic/<traffic>.json
    limits: Dict[str, Any]       # limits/<workload>.json
    seed: int
    seconds: float
    trace: bool
    chips: int
    t_start: float               # process start, monotonic
    peaks: Optional[Dict[str, Any]] = None

    @property
    def dims(self) -> Dims:
        return Dims.of(self.cfg)

    def seed_for(self, what: str) -> int:
        """A program seed in [0, 2**31) drawn from ``--seed``, one per
        use, so that any ``--seed`` up to 2**63 reaches the program."""
        streams = {"weights": 1, "data": 2}
        return int(np.random.default_rng(
            [int(self.seed), streams[what]]).integers(0, 2 ** 31 - 1))


class Spans:
    """Host spans of the run, kept in memory: (name, start, end, info),
    mirrored into the profiler's trace when one is recording."""

    def __init__(self, clock=time.monotonic):
        self.clock = clock
        self.items: List[Tuple[str, float, float, Any]] = []

    @contextmanager
    def span(self, name: str, info: Any = None):
        import jax
        t0 = self.clock()
        with jax.profiler.TraceAnnotation(name):
            yield
        self.items.append((name, t0, self.clock(), info))

    def of(self, name: str, lo: float = -np.inf, hi: float = np.inf):
        """Spans of ``name`` that ended inside [lo, hi]."""
        return [s for s in self.items if s[0] == name and lo <= s[2] <= hi]


class Tracer:
    """Records the profiler's trace from ``start`` to ``stop`` (monotonic
    seconds) on a thread of its own, into a temporary directory that
    ``close`` removes."""

    def __init__(self, start: float, stop: float, clock=time.monotonic):
        self.start, self.stop, self.clock = start, stop, clock
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        self.error: Optional[BaseException] = None
        self.window: Optional[Tuple[float, float]] = None
        self._cancel = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()

    def _run(self):
        import jax
        try:
            if self._cancel.wait(max(0.0, self.start - self.clock())):
                return
            jax.profiler.start_trace(self.dir)
            t0 = self.clock()
            self._cancel.wait(max(0.0, self.stop - self.clock()))
            jax.profiler.stop_trace()
            self.window = (t0, self.clock())
        except Exception as e:         # reported by the caller
            self.error = e

    def finish(self, timeout: float = 120.0) -> Optional[str]:
        """Wait for the trace to end; returns its .xplane.pb path."""
        self._thread.join(timeout)
        if self._thread.is_alive():
            raise RuntimeError("trace did not stop")
        if self.error is not None:
            raise RuntimeError(f"tracing failed: {self.error}")
        if self.window is None:
            return None
        from bench.trace import find
        return find(self.dir)

    def cancel(self):
        self._cancel.set()

    def close(self):
        self.cancel()
        self._thread.join(120.0)
        shutil.rmtree(self.dir, ignore_errors=True)


def device_info(chips: int) -> Dict[str, Any]:
    import jax
    devs = jax.devices()[:chips]
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(jax.devices()), "memory_peak_bytes": peak}


def bytes_limit() -> int:
    import jax
    return int((jax.devices()[0].memory_stats() or {}).get("bytes_limit", 0))
