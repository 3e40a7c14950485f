"""Share of the window lost to stalled decode steps: over the engine's
``serve/decode`` spans that ended inside the window, the time by which
each step slower than 3 times their median exceeds the median, summed,
over the window's seconds.  What the engine noted on its slow steps
(``core.metrics.SlowSteps``: context switches, CPU, GC, throttling),
and how long each of their child spans took, goes to the log."""
import statistics
import sys

FACTOR = 3.0


def read(run, ctx):
    if run["kind"] != "serve":
        return None
    spans = getattr(run["registry"], "spans", None)
    if spans is None:
        return None
    steps = spans("serve/decode", run["t0"], run["t_end"])
    if not steps:
        return None
    walls = [s.end - s.start for s in steps]
    median = statistics.median(walls)
    for s in steps:
        if s.attrs:
            parts = [(k.name, k.end - k.start)
                     for k in spans(lo=s.start, hi=s.end) if k.parent is s]
            print(f"[bench] slow step: wall_s={s.end - s.start:.6g} "
                  + " ".join(f"{k}={v:.6g}" for k, v in parts
                             + list(s.attrs.items())),
                  file=sys.stderr, flush=True)
    lost = sum(w - median for w in walls if w > FACTOR * median)
    return 100.0 * lost / run["seconds"]
