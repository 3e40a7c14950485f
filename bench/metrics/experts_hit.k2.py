"""Held experts that some token chose, per MoE layer and decode step,
averaged over the steps inside the window: the program's counter
``serve/experts_hit``, which the paged decode step returns beside its
tokens."""

COUNTER = "serve/experts_hit"


def read(run, ctx):
    if run["kind"] != "serve":
        return None
    lo = run["wall0"]
    hi = lo + run["seconds"]
    pts = [v for ts, v in run["registry"].series(COUNTER).snapshot()
           if lo <= ts <= hi]
    return sum(pts) / len(pts) if pts else None
