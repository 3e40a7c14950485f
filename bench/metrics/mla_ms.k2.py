"""Device time per decode step of latent attention: the ops of the
decode step (``jit_paged_step``) under the program's ``mla`` scope (the
projections and the absorbed attention over the latent views), less the
latent passes inside it (``kv_write``), which ``latent_pass_ms.k2``
reads (bench/scopes.py)."""
from bench import scopes

LATENT = ("kv_gather", "kv_write", "kv_scatter")


def read(run, ctx):
    if run["kind"] != "serve" or scopes._planes(run) is None:
        return None
    got = scopes._run_ops(run["trace_path"], scopes.DECODE_STEP)
    if got is None:
        return None
    ops, runs = got
    secs = sum(t for _, path, t in ops if scopes.in_scope(path, ("mla",))
               and not scopes.in_scope(path, LATENT))
    return 1e3 * secs / runs
