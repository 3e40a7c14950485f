"""Shared kernel-runtime knobs.

The backend decides how a Pallas kernel runs: compiled by Mosaic on a
TPU, interpreted (the kernel body evaluated as ordinary JAX ops, which
validates the BlockSpec/grid logic) everywhere else.  The train-hot-loop
kernels (fused xent / fused AdamW) are also gated on the TPU backend,
because interpret mode is far too slow to sit inside every CPU test's
train step; ``REPRO_FUSED_XENT=1`` / ``REPRO_FUSED_ADAMW=1`` force them
on elsewhere for debugging, and ``=0`` takes them off a TPU.
"""
from __future__ import annotations

import os

import jax

NEG_INF = -1e30


def interpret_default() -> bool:
    return jax.default_backend() != "tpu"


def _env_gate(var: str) -> bool:
    """Fused-train-kernel gate: explicit env wins, else TPU-only."""
    val = os.environ.get(var)
    if val is not None:
        return val not in ("", "0")
    return jax.default_backend() == "tpu"


def fused_xent_default() -> bool:
    return _env_gate("REPRO_FUSED_XENT")


def fused_adamw_default() -> bool:
    return _env_gate("REPRO_FUSED_ADAMW")
