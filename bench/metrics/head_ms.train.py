"""Device time per optimizer step of the head's products: the ops of the
train step (``jit_train_chunk``) under the program's ``lm_head`` scope,
forward, recomputed and backward, less the fused cross-entropy kernels
(``xent_fwd``, ``xent_bwd``), which ``xent_roofline.train`` reads
(bench/scopes.py)."""
from bench import scopes


def read(run, ctx):
    if run["kind"] != "train":
        return None
    return scopes.scope_ms(run, scopes.TRAIN_STEP, ("lm_head",),
                           exclude=("xent_fwd", "xent_bwd"),
                           steps_per_run=int(ctx.mix["device_steps"]))
