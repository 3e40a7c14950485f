"""Jitted public wrappers for the Pallas kernels.

The backend decides how they run: compiled on a TPU, interpreted (the
kernel body evaluated as ordinary JAX ops) everywhere else.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.adamw_update import adamw_update
from repro.kernels.common import interpret_default as _interpret_default
from repro.kernels.flash_attention import flash_attention
from repro.kernels.moe_gmm import gmm
from repro.kernels.ssm_scan import ssd_scan
from repro.kernels.wkv6 import wkv6
from repro.kernels.xent import softmax_xent


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k"))
def flash_attention_op(q, k, v, *, causal: bool = True, block_q: int = 128,
                       block_k: int = 128):
    return flash_attention(q, k, v, causal=causal, block_q=block_q,
                           block_k=block_k, interpret=_interpret_default())


@functools.partial(jax.jit, static_argnames=("chunk",))
def ssd_scan_op(x, dt, a, B_, C, *, chunk: int = 128):
    return ssd_scan(x, dt, a, B_, C, chunk=chunk,
                    interpret=_interpret_default())


@functools.partial(jax.jit, static_argnames=("chunk",))
def wkv6_op(r, k, v, logw, u, *, chunk: int = 64):
    return wkv6(r, k, v, logw, u, chunk=chunk,
                interpret=_interpret_default())


@functools.partial(jax.jit, static_argnames=("block_c", "block_f", "block_d"))
def gmm_op(x, w, *, block_c: int = 128, block_f: int = 128,
           block_d: int = 128):
    return gmm(x, w, block_c=block_c, block_f=block_f, block_d=block_d,
               interpret=_interpret_default())


@functools.partial(jax.jit, static_argnames=("softcap", "block_r", "block_v"))
def softmax_xent_op(logits, labels, *, softcap=None, block_r: int = 128,
                    block_v: int = 512):
    return softmax_xent(logits, labels, softcap=softcap, block_r=block_r,
                        block_v=block_v, interpret=_interpret_default())


@functools.partial(jax.jit,
                   static_argnames=("b1", "b2", "eps", "weight_decay",
                                    "block_rows"))
def adamw_update_op(p, g, m, v, lr, bc1, bc2, *, b1: float, b2: float,
                    eps: float, weight_decay: float = 0.0,
                    block_rows: int = 256):
    return adamw_update(p, g, m, v, lr, bc1, bc2, b1=b1, b2=b2, eps=eps,
                        weight_decay=weight_decay, block_rows=block_rows,
                        interpret=_interpret_default())
