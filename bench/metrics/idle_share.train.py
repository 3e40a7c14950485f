"""Share of the traced window in which no operation ran on the chip."""


def read(run, ctx):
    t = run.get("trace") or {}
    share = t.get("idle_share")
    return None if share is None else 100.0 * share
