"""Pallas TPU fused softmax cross-entropy (per-row NLL over a tiled vocab).

TARGET: TPU v5e VPU/VMEM.  Grid = (row blocks, vocab blocks) with the
vocab axis innermost ("arbitrary"), so the online-logsumexp running
statistics (m, l) and the gold-logit accumulator live in VMEM scratch
across vocab tiles and each (row, vocab) tile of the logits is streamed
through VMEM exactly once — the full (rows, V) f32 softmax is never
materialized.  The backward pass is a second Pallas kernel with no
cross-tile state (softmax recomputed per tile from the saved lse), wired
up via ``jax.custom_vjp`` so the fused loss is trainable.

Per-row vectors (labels, nll, lse, dy and the m/l/gold scratch) ride as
(rows, 1) columns: a 1-D block has a tiling Mosaic refuses against the
XLA layout of a long vector, a 2-D column tiles like any other operand.
Both grid axes are ``cdiv``: ragged row and vocab tails are partial
blocks whose out-of-range columns are masked in the kernel and whose
out-of-range rows are dropped on write, so the logits are never padded
(a padded copy of the (rows, V) logits costs as much HBM as the logits).

Accumulation is f32 regardless of logits dtype (bf16 logits are upcast
per tile).  ``softcap`` (gemma2 final-logit cap) is folded into both
kernels, including its ``1 - tanh^2`` chain-rule factor in the backward.

Validated on CPU via interpret=True against kernels.ref.softmax_xent_ref
(tests/test_kernels.py sweeps shapes/dtypes/softcap, values and grads);
tests/test_tpu_compile.py compiles both kernels for v5e at phi4-mini's
vocabulary.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import NEG_INF


def _capped(s, softcap: Optional[float]):
    return s if softcap is None else softcap * jnp.tanh(s / softcap)


def _xent_fwd_kernel(logits_ref, labels_ref, nll_ref, lse_ref,
                     m_scr, l_scr, g_scr, *, softcap: Optional[float],
                     block_r: int, block_v: int, num_vb: int, true_v: int):
    vb = pl.program_id(1)

    @pl.when(vb == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        g_scr[...] = jnp.zeros_like(g_scr)

    s = _capped(logits_ref[...].astype(jnp.float32), softcap)
    cols = vb * block_v + jax.lax.broadcasted_iota(jnp.int32,
                                                   (block_r, block_v), 1)
    s = jnp.where(cols < true_v, s, NEG_INF)      # mask the ragged vocab tail
    lab = labels_ref[...]                          # (block_r, 1) int32
    g_scr[...] += jnp.sum(jnp.where(cols == lab, s, 0.0), axis=1,
                          keepdims=True)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * alpha + jnp.sum(jnp.exp(s - m_new), axis=1,
                                              keepdims=True)
    m_scr[...] = m_new

    @pl.when(vb == num_vb - 1)
    def _finalize():
        lse = m_scr[...] + jnp.log(jnp.maximum(l_scr[...], 1e-30))
        lse_ref[...] = lse
        nll_ref[...] = lse - g_scr[...]


def _xent_bwd_kernel(logits_ref, labels_ref, lse_ref, dy_ref, dlogits_ref, *,
                     softcap: Optional[float], block_r: int, block_v: int,
                     true_v: int):
    vb = pl.program_id(1)
    s = logits_ref[...].astype(jnp.float32)
    if softcap is None:
        sc, dsc = s, 1.0
    else:
        t = jnp.tanh(s / softcap)
        sc, dsc = softcap * t, 1.0 - t * t
    cols = vb * block_v + jax.lax.broadcasted_iota(jnp.int32,
                                                   (block_r, block_v), 1)
    valid = cols < true_v
    sc = jnp.where(valid, sc, NEG_INF)
    p = jnp.exp(sc - lse_ref[...])
    onehot = (cols == labels_ref[...]).astype(jnp.float32)
    d = dy_ref[...] * (p - onehot) * dsc
    dlogits_ref[...] = jnp.where(valid, d, 0.0).astype(dlogits_ref.dtype)


def _fwd_call(logits, labels, softcap, block_r, block_v, interpret):
    R, V = logits.shape
    grid = (pl.cdiv(R, block_r), pl.cdiv(V, block_v))
    kernel = functools.partial(
        _xent_fwd_kernel, softcap=softcap, block_r=block_r, block_v=block_v,
        num_vb=grid[1], true_v=V)
    row = pl.BlockSpec((block_r, 1), lambda i, j: (i, 0))
    col = jax.ShapeDtypeStruct((R, 1), jnp.float32)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((block_r, block_v), lambda i, j: (i, j)), row],
        out_specs=[row, row],
        out_shape=[col, col],
        scratch_shapes=[
            pltpu.VMEM((block_r, 1), jnp.float32),      # running max m
            pltpu.VMEM((block_r, 1), jnp.float32),      # running sum l
            pltpu.VMEM((block_r, 1), jnp.float32),      # gold-logit accum
        ],
        interpret=interpret,
        name="xent_fwd",
    )(logits, labels)


def _bwd_call(logits, labels, lse, dy, softcap, block_r, block_v, interpret):
    R, V = logits.shape
    kernel = functools.partial(
        _xent_bwd_kernel, softcap=softcap, block_r=block_r, block_v=block_v,
        true_v=V)
    row = pl.BlockSpec((block_r, 1), lambda i, j: (i, 0))
    tile = pl.BlockSpec((block_r, block_v), lambda i, j: (i, j))
    return pl.pallas_call(
        kernel,
        grid=(pl.cdiv(R, block_r), pl.cdiv(V, block_v)),
        in_specs=[tile, row, row, row],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct((R, V), logits.dtype),
        interpret=interpret,
        name="xent_bwd",
    )(logits, labels, lse, dy)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5))
def _xent_core(logits, labels, softcap, block_r, block_v, interpret):
    nll, _ = _fwd_call(logits, labels, softcap, block_r, block_v, interpret)
    return nll


def _xent_core_fwd(logits, labels, softcap, block_r, block_v, interpret):
    nll, lse = _fwd_call(logits, labels, softcap, block_r, block_v,
                         interpret)
    return nll, (logits, labels, lse)


def _xent_core_bwd(softcap, block_r, block_v, interpret, res, dy):
    logits, labels, lse = res
    dlogits = _bwd_call(logits, labels, lse, dy.astype(jnp.float32),
                        softcap, block_r, block_v, interpret)
    # labels are integral: their cotangent is float0 (no gradient)
    return dlogits, np.zeros(labels.shape, jax.dtypes.float0)


_xent_core.defvjp(_xent_core_fwd, _xent_core_bwd)


def softmax_xent(logits: jax.Array, labels: jax.Array, *,
                 softcap: Optional[float] = None, block_r: int = 128,
                 block_v: int = 512, interpret: bool = False) -> jax.Array:
    """Per-row softmax cross-entropy: logits (R, V), labels (R,) int32
    -> NLL (R,) f32.  Differentiable w.r.t. ``logits`` (fused Pallas
    forward + backward); caller reduces (sum/mean) as needed.

    ``block_r`` must be a multiple of 8 and ``block_v`` of 128 unless
    they cover the whole axis (they are clamped to it)."""
    R, V = logits.shape
    block_r = min(block_r, max(R, 1))
    block_v = min(block_v, max(V, 1))
    nll = _xent_core(logits, labels.astype(jnp.int32).reshape(R, 1),
                     softcap, block_r, block_v, interpret)
    return nll.reshape(R)
