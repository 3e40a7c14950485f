"""``round_trip_idle_ms.decode``'s reading in Kimi K2's cell: a name of its own
because that entry's list of cells is pinned by bench/tests/test_scopes.py."""
from bench import spec


def read(run, ctx):
    return spec.module("metrics", "round_trip_idle_ms.decode").read(run, ctx)
