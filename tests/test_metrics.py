"""Registry/Series thread-safety and listener streaming.

The regression here: ``Series.points`` used to be appended from pod
threads while ``summary()``/``scrape()`` iterated under only the
registry's dict lock — count, mean and total could be computed from
three different instants of the same series.  Now every read derives
from one per-series locked snapshot."""
import threading

from repro.core.metrics import Registry, Series


def test_series_summary_consistent_under_concurrent_records():
    """8 writer threads recording value=1.0 while the main thread
    summarizes: within any single summary draw, total == count and
    mean == 1.0 exactly — only possible if stats come from ONE
    snapshot."""
    reg = Registry()
    n_writers, per_writer = 8, 2000
    start = threading.Barrier(n_writers + 1, timeout=30)

    def writer(w):
        start.wait()
        for i in range(per_writer):
            reg.inc(f"shared/{w % 2}")          # contended series
            reg.gauge("all", 1.0)

    threads = [threading.Thread(target=writer, args=(w,))
               for w in range(n_writers)]
    for t in threads:
        t.start()
    start.wait()
    draws = 0
    while any(t.is_alive() for t in threads):
        for name, st in reg.summary().items():
            assert st["total"] == st["count"], (name, st)
            if st["count"]:
                assert st["mean"] == 1.0, (name, st)
                assert st["max"] == st["last"] == 1.0, (name, st)
        reg.scrape()
        reg.to_csv()
        draws += 1
    for t in threads:
        t.join(timeout=30)
    assert draws > 0
    s = reg.summary()
    assert s["all"]["count"] == n_writers * per_writer
    assert s["shared/0"]["count"] + s["shared/1"]["count"] == \
        n_writers * per_writer


def test_series_snapshot_is_isolated():
    s = Series()
    s.record(1.0)
    snap = s.snapshot()
    s.record(2.0)
    assert len(snap) == 1 and len(s.snapshot()) == 2
    assert s.last == 2.0 and s.total == 3.0 and s.mean == 1.5
    st = s.stats()
    assert st["count"] == 2 and st["p50"] in (1.0, 2.0)


def test_registry_listener_gets_every_record_and_survives_errors():
    reg = Registry()
    got = []
    reg.add_listener(lambda n, v, ts: got.append((n, v)))
    reg.add_listener(lambda n, v, ts: 1 / 0)     # broken observer
    reg.inc("a")
    reg.gauge("b", 2.5)
    with reg.timer("t"):
        pass
    assert got[0] == ("a", 1.0) and got[1] == ("b", 2.5)
    assert got[2][0] == "t" and got[2][1] >= 0.0
    assert reg.series("a").total == 1.0          # broken listener harmless


# ----------------------------------------------------------------- spans

class StepClock:
    """A clock that reads 0, 1, 2, ... on successive calls."""

    def __init__(self):
        self.t = -1.0

    def __call__(self):
        self.t += 1.0
        return self.t


def test_spans_nest_by_thread_on_one_clock():
    import time

    assert Registry().clock is time.monotonic
    reg = Registry(clock=StepClock())
    with reg.span("outer", tag=1) as outer:
        with reg.span("inner") as inner:
            pass
        other = {}

        def worker():
            with reg.span("elsewhere") as sp:
                other["span"] = sp

        t = threading.Thread(target=worker)
        t.start()
        t.join(timeout=30)
        assert not t.is_alive()
    assert inner.parent is outer and outer.parent is None
    assert other["span"].parent is None      # another thread's stack
    assert (outer.start, inner.start, inner.end) == (0.0, 1.0, 2.0)
    assert outer.end == 5.0 and outer.duration == 5.0
    assert outer.attrs == {"tag": 1}
    assert [s.name for s in reg.spans()] == ["inner", "elsewhere", "outer"]
    assert [s.name for s in reg.spans("inner", hi=2.0)] == ["inner"]
    assert reg.spans("inner", lo=2.5) == []


def test_span_ring_keeps_the_newest_and_closes_on_error(monkeypatch):
    from repro.core import metrics
    monkeypatch.setattr(metrics, "MAX_SPANS", 3)
    reg = Registry()
    for i in range(5):
        with reg.span(f"s{i}"):
            pass
    try:
        with reg.span("failed"):
            raise ValueError("boom")
    except ValueError:
        pass
    names = [s.name for s in reg.spans()]
    assert names == ["s3", "s4", "failed"]
    assert reg.spans("failed")[0].end >= reg.spans("failed")[0].start
    with reg.span("after") as sp:
        pass
    assert sp.parent is None                 # the failed span was popped


def test_timer_is_a_span_recorded_as_a_series():
    reg = Registry(clock=StepClock())
    got = []
    reg.add_listener(lambda n, v, ts: got.append((n, v)))
    with reg.span("outer"):
        with reg.timer("t"):
            pass
    (t_span,) = reg.spans("t")
    assert t_span.parent.name == "outer"
    assert reg.series("t").snapshot()[0][1] == t_span.duration == 1.0
    assert got == [("t", 1.0)]


def test_slow_step_counts_and_notes_the_process(tmp_path, monkeypatch):
    import gc

    from repro.core import metrics
    from repro.core.metrics import SLOW_MIN_STEPS, SlowSteps
    now = [0.0]
    reg = Registry(clock=lambda: now[0])
    stat = tmp_path / "cpu.stat"
    stat.write_text("usage_usec 10\nnr_throttled 2\nthrottled_usec 500\n")
    monkeypatch.setattr(metrics, "CPU_STAT", str(stat))
    slow = SlowSteps(reg, "slow")

    def step(wall, during=None):
        with reg.span("step") as sp:
            before = slow.begin()
            now[0] += wall
            if during:
                during()
        return slow.end(sp, before), sp

    # no step is judged before SLOW_MIN_STEPS have been seen
    assert not step(10.0)[0]
    for _ in range(SLOW_MIN_STEPS):
        assert not step(1.0)[0]
    assert not step(2.9)[0]                  # under 3x the median
    stat.write_text("usage_usec 90\nnr_throttled 5\nthrottled_usec 2500\n")
    flagged, sp = step(3.5, gc.collect)
    assert flagged and reg.series("slow").total == 1.0
    assert sp.attrs["median_s"] == 1.0
    assert sp.attrs["gc_s"] > 0
    assert sp.attrs["invol_switches"] >= 0 and sp.attrs["cpu_s"] >= 0
    assert sp.attrs["nr_throttled"] == 3
    assert abs(sp.attrs["throttled_s"] - 0.002) < 1e-12
    # without the cgroup file the other notes stay
    monkeypatch.setattr(metrics, "CPU_STAT", str(tmp_path / "missing"))
    slow2 = SlowSteps(reg, "slow")
    for _ in range(SLOW_MIN_STEPS):
        with reg.span("step") as sp:
            before = slow2.begin()
            now[0] += 1.0
        slow2.end(sp, before)
    with reg.span("step") as sp:
        before = slow2.begin()
        now[0] += 10.0
    assert slow2.end(sp, before)
    assert "nr_throttled" not in sp.attrs and "gc_s" in sp.attrs
