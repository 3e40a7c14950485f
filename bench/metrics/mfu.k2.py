"""The decode step's share of its roofline: for each step inside the
window, the least time the chip could take (the larger of the required
operations over peak and of the bytes read over bandwidth: the weights,
with only the held experts that some token chose, the active slots' live
latent rows and their embedding rows; bench/flops_mla_moe.py), summed,
over the steps' summed wall time."""
from bench import flops, flops_mla_moe
from bench.model_mla_moe import Dims


def read(run, ctx):
    if run["kind"] != "serve" or "step_hits" not in run:
        return None
    d = Dims.of(ctx.cfg)
    steps = run["spans"].of("decode_step")
    least = wall = 0.0
    for (_, t0, t1, positions), hits in zip(steps, run["step_hits"]):
        if not run["t0"] <= t1 <= run["t_end"]:
            continue
        ctxs = [p + 1 for p in positions if p > 0]
        if not ctxs:
            continue
        w = flops_mla_moe.decode_step(d, ctxs, hits)
        least += flops.least_seconds(w["flops"], w["bytes"], ctx.peaks)
        wall += t1 - t0
    return 100.0 * least / wall if wall else None
