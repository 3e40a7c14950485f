"""Device time per decode step of the paged KV passes: the ops of the
decode step (``jit_paged_step``) under the program's ``kv_gather``,
``kv_write`` and ``kv_scatter`` scopes (bench/scopes.py)."""
from bench import scopes

KV_SCOPES = ("kv_gather", "kv_write", "kv_scatter")


def read(run, ctx):
    if run["kind"] != "serve":
        return None
    return scopes.scope_ms(run, scopes.DECODE_STEP, KV_SCOPES)
