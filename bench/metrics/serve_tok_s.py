"""Output tokens generated inside the window, over its seconds: one per
prefill and one per active slot of each decode step that ended inside
the window, requests still in flight at its end included."""


def read(run, ctx):
    if run["kind"] != "serve":
        return None
    lo, hi = run["t0"], run["t_end"]
    spans = run["spans"]
    tokens = len(spans.of("prefill", lo, hi))
    for _, _, _, positions in spans.of("decode_step", lo, hi):
        tokens += sum(1 for p in positions if p > 0)
    return tokens / run["seconds"]
