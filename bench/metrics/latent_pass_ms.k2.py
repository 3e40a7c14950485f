"""Device time per decode step of the paged latent passes: the ops of
the decode step (``jit_paged_step``) under the program's ``kv_gather``,
``kv_write`` and ``kv_scatter`` scopes, which move MLA's ``latent`` leaf.
Read as ``kv_pass_ms.decode`` reads it; a name of its own because that
entry's list of cells is pinned by bench/tests/test_scopes.py."""
from bench import spec


def read(run, ctx):
    return spec.module("metrics", "kv_pass_ms.decode").read(run, ctx)
