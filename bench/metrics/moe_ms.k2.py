"""Device time per decode step of the held-expert layers: the ops of the
decode step (``jit_paged_step``) under the program's ``router``,
``experts`` and ``shared_expert`` scopes (bench/scopes.py)."""
from bench import scopes

MOE_SCOPES = ("router", "experts", "shared_expert")


def read(run, ctx):
    if run["kind"] != "serve":
        return None
    return scopes.scope_ms(run, scopes.DECODE_STEP, MOE_SCOPES)
