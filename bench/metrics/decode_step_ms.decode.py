"""Mean wall time of one decode step inside the window, from the
benchmark's span around ``ServingEngine.decode_step`` (which blocks on
the new tokens)."""


def read(run, ctx):
    if run["kind"] != "serve":
        return None
    s = run["spans"].of("decode_step", run["t0"], run["t_end"])
    return 1e3 * sum(t1 - t0 for _, t0, t1, _ in s) / len(s) if s else None
