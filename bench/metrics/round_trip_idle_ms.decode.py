"""Device idle time per decode step inside the engine's per-token round
trip: its ``serve/upload``, ``serve/launch`` and ``serve/readback`` spans
(bench/scopes.py)."""
from bench import scopes

SPANS = ("serve/upload", "serve/launch", "serve/readback")


def read(run, ctx):
    if run["kind"] != "serve":
        return None
    return scopes.idle_ms(run, scopes.DECODE_STEP, SPANS)
