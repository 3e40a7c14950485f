"""Faults planted in the held-expert router of the DeepSeek-V3 block, to
show that ``correct`` in its cells catches them.  Used by bench/tests
and bench/tools/readings_mla_moe.py only; a benchmark run never plants
one.

Each is a context manager that patches ``repro.models.moe.sigmoid_route``
while it is active, so steps built inside it route the faulty way.
"""
from __future__ import annotations

from bench.faults import _patched


def _route_fault(change):
    from repro.models import moe
    route = moe.sigmoid_route

    def faulty(logits, bias, top_k, scaling):
        return route(*change(logits, bias), top_k, scaling)

    return _patched(moe, "sigmoid_route", faulty)


def bias_left_out():
    """The experts are chosen by the sigmoid scores alone: the correction
    bias is left out of the choice."""
    import jax.numpy as jnp
    return _route_fault(lambda logits, bias: (logits, jnp.zeros_like(bias)))


def router_logits_bf16():
    """The router's logits are rounded to bf16 before the scores."""
    import jax.numpy as jnp
    return _route_fault(lambda logits, bias: (
        logits.astype(jnp.bfloat16).astype(jnp.float32), bias))


FAULTS = {"bias_left_out": bias_left_out,
          "router_logits_bf16": router_logits_bf16}
