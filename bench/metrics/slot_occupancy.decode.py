"""Mean share of the decode slots active at each decode step inside the
window (the engine's ``serve/slot_occupancy``)."""


def read(run, ctx):
    if run["kind"] != "serve":
        return None
    from repro.serving.report import GAUGES
    lo = run["wall0"]
    hi = lo + run["seconds"]
    pts = [v for ts, v in run["registry"].series(
        GAUGES.SLOT_OCCUPANCY).snapshot() if lo <= ts <= hi]
    if not pts:
        return None
    return 100.0 * sum(pts) / len(pts) / run["slots"]
