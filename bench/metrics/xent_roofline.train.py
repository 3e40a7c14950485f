"""The fused softmax cross-entropy kernels' share of their roofline:
for every ``xent_fwd`` and ``xent_bwd`` call in the trace, the least
time its bytes and operations need (bench/flops.xent_call), summed, over
their summed device time.  A call covers the rows of one chunk of the
loss: the per-chip batch times ``models/losses.chunked_cross_entropy``'s
512 positions."""
from bench import flops

CHUNK = 512


def read(run, ctx):
    t = run.get("trace") or {}
    secs = sum(t.get("kernel_s", {}).get(k, 0.0)
               for k in ("xent_fwd", "xent_bwd"))
    if not secs:
        return None
    mix, d = ctx.mix, ctx.dims
    rows = int(mix["global_batch"]) // ctx.chips * min(CHUNK,
                                                        int(mix["seq_len"]))
    least = 0.0
    for name, backward in (("xent_fwd", False), ("xent_bwd", True)):
        w = flops.xent_call(rows, d.vocab, backward)
        least += t["kernel_n"].get(name, 0) * flops.least_seconds(
            w["flops"], w["bytes"], ctx.peaks)
    return 100.0 * least / secs
