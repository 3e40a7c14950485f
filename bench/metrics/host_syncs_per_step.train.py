"""Host round trips per optimizer step, as the trainer's run report
counts them (dispatches, loss flushes, the first-step probe)."""


def read(run, ctx):
    if run["kind"] != "train":
        return None
    return run["host_syncs_per_step"]
