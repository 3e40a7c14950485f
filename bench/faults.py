"""Faults planted under the timed path, to show that ``correct`` catches
them.  Used by bench/tests and bench/tools/readings.py only; a
benchmark run never plants one.

Each is a context manager that patches the program while it is active.
"""
from __future__ import annotations

import dataclasses
from contextlib import contextmanager


@contextmanager
def _patched(obj, name, value):
    old = getattr(obj, name)
    setattr(obj, name, value)
    try:
        yield
    finally:
        setattr(obj, name, old)


def _train_step_fault(change):
    from repro.runtime import steps as steps_mod
    build = steps_mod.build_train_chunk

    def faulty(*a, **kw):
        bundle = build(*a, **kw)
        return dataclasses.replace(bundle, fn=change(bundle.fn))

    return _patched(steps_mod, "build_train_chunk", faulty)


def state_unchanged():
    """The training step returns the state it was given (its loss is
    still computed)."""
    def change(fn):
        return lambda p, o, b: (p, o, fn(p, o, b)[2])
    return _train_step_fault(change)


def half_batch():
    """The training step leaves out the second half of each batch and
    takes the mean over the rest (its rows stand in for the others)."""
    import jax.numpy as jnp

    def change(fn):
        def step(p, o, b):
            def halve(x):
                h = x.shape[1] // 2
                return jnp.concatenate([x[:, :h], x[:, :h]], axis=1)
            return fn(p, o, {k: halve(v) for k, v in b.items()})
        return step
    return _train_step_fault(change)


def token_altered():
    """The decode step returns each slot's token plus one."""
    from repro.serving import ServingEngine
    decode = ServingEngine.decode_step

    def step(self, tokens, positions):
        out = decode(self, tokens, positions)
        return (out + 1) % self.cfg.vocab_size

    return _patched(ServingEngine, "decode_step", step)


FAULTS = {"state_unchanged": state_unchanged, "half_batch": half_batch,
          "token_altered": token_altered}
