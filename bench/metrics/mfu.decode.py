"""The decode step's share of its roofline: for each step inside the
window, the least time the chip could take (the larger of the required
operations over peak and of the weights plus the active slots' live KV
rows over bandwidth), summed, over the steps' summed wall time."""
from bench import flops


def read(run, ctx):
    if run["kind"] != "serve":
        return None
    steps = run["spans"].of("decode_step", run["t0"], run["t_end"])
    if not steps:
        return None
    least = wall = 0.0
    for _, t0, t1, positions in steps:
        ctxs = [p + 1 for p in positions if p > 0]
        if not ctxs:
            continue
        w = flops.decode_step(ctx.dims, ctxs)
        least += flops.least_seconds(w["flops"], w["bytes"], ctx.peaks)
        wall += t1 - t0
    return 100.0 * least / wall if wall else None
