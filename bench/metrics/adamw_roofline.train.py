"""The fused AdamW kernel's share of its roofline: over the traced
steps, the least time that updating every parameter needs
(bench/flops.adamw_elements), over the device time of the
``adamw_update`` calls."""
import math

from bench import flops
from bench.weights import leaves


def read(run, ctx):
    t = run.get("trace") or {}
    secs = t.get("kernel_s", {}).get("adamw_update")
    if not secs or not run.get("trace_steps"):
        return None
    n = sum(math.prod(shape)
            for shape, _ in leaves(ctx.dims).values()) // ctx.chips
    w = flops.adamw_elements(n)
    least = run["trace_steps"] * flops.least_seconds(w["flops"], w["bytes"],
                                                     ctx.peaks)
    return 100.0 * least / secs
