"""Tokens of the optimizer steps that completed inside the window, and
the share of the step running at its end that the window holds, over
the window's seconds."""


def read(run, ctx):
    if run["kind"] != "train":
        return None
    steps = run["steps_in_window"] + run["partial_step"]
    return steps * run["tokens_per_step"] / run["seconds"]
