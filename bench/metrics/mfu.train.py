"""The training step's share of the chip's peak: operations the forward
and backward passes require per token (no recomputation), times the
tokens per second of the window, over the chips' bf16 peak."""
from bench import flops


def read(run, ctx):
    if run["kind"] != "train" or not run["steps_in_window"]:
        return None
    per_token = flops.train_flops_per_token(ctx.dims, int(ctx.mix["seq_len"]))
    steps = run["steps_in_window"] + run["partial_step"]
    rate = steps * run["tokens_per_step"] / run["seconds"]
    return 100.0 * per_token * rate / (ctx.chips *
                                       ctx.peaks["bf16_flops_per_s"])
