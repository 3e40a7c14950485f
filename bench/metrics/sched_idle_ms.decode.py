"""Device idle time per decode step inside the engine's
``serve/schedule`` spans (admission, block allocation, ``observe``,
lease renewal), less the prefills nested in them (bench/scopes.py)."""
from bench import scopes


def read(run, ctx):
    if run["kind"] != "serve":
        return None
    return scopes.idle_ms(run, scopes.DECODE_STEP, ("serve/schedule",))
