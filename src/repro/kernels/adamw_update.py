"""Pallas TPU fused AdamW leaf update (one kernel, in place, no temps).

The unfused ``optim.adamw._update_leaf`` materializes several full-leaf
f32 temporaries (g32, m_new, v_hat, update) per tensor; on 1T-scale
stacked leaves that peaks at ~6x params bytes, which is why the unfused
path scans over the layer axis.  This kernel streams the four state
tensors through VMEM one tile at a time and fuses the whole elementwise
chain — moment updates, bias correction, decoupled weight decay,
parameter write — so peak temp memory is one tile and the layered scan
becomes unnecessary.

Layout: a leaf is viewed as (rows, last dim) by collapsing its LEADING
dims only.  The last dim keeps its TPU lane tiling, so the view is a
bitcast and not a relayout copy; a flatten to (n/128, 128) would be a
physical copy of every operand.  The grid is ``cdiv`` over both axes:
ragged tails are partial blocks (out-of-range elements are never
written), so nothing is padded.  p, m and v are aliased to their
outputs, so under a donating jit the update is in place.

Schedule hyperparameters that change every step (lr, bias corrections)
ride in SMEM as a tiny scalar vector; (b1, b2, eps, weight_decay) are
compile-time constants.  Math matches ``_update_leaf`` exactly: f32
accumulation regardless of param dtype, params written back in their own
dtype, moments in f32 (the fused path is only engaged for the
float32/full state recipe — quantized or factored state keeps the
unfused path).

Validated on CPU via interpret=True against kernels.ref.adamw_update_ref
(tests/test_kernels.py: dtype sweep, weight-decay on/off, ragged tails);
tests/test_tpu_compile.py compiles it for v5e and bounds its temporaries.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE = 128                     # TPU lane width
BLOCK_ELEMS = 128 * 1024       # elements per tile: ~2.8 MiB of operands


def _adamw_kernel(sc_ref, p_ref, g_ref, m_ref, v_ref,
                  np_ref, nm_ref, nv_ref, *, b1: float, b2: float,
                  eps: float, weight_decay: float):
    lr, bc1, bc2 = sc_ref[0], sc_ref[1], sc_ref[2]
    g = g_ref[...].astype(jnp.float32)
    p = p_ref[...].astype(jnp.float32)
    m_new = b1 * m_ref[...] + (1.0 - b1) * g
    v_new = b2 * v_ref[...] + (1.0 - b2) * g * g
    update = (m_new / bc1) / (jnp.sqrt(v_new / bc2) + eps)
    if weight_decay:
        update = update + weight_decay * p
    np_ref[...] = (p - lr * update).astype(np_ref.dtype)
    nm_ref[...] = m_new
    nv_ref[...] = v_new


def _block(rows: int, cols: int, block_rows: Optional[int],
           block_cols: int):
    """Tile shape for a (rows, cols) view: a dim either fits whole or is
    cut to a multiple of the (32, 128) tile that suits every dtype."""
    bc = cols if cols <= block_cols else block_cols
    if block_rows is None:
        block_rows = max(32, (BLOCK_ELEMS // bc) // 32 * 32)
    br = rows if rows <= block_rows else block_rows
    return br, bc


def adamw_update(p: jax.Array, g: jax.Array, m: jax.Array, v: jax.Array,
                 lr: jax.Array, bc1: jax.Array, bc2: jax.Array, *,
                 b1: float, b2: float, eps: float, weight_decay: float = 0.0,
                 block_rows: Optional[int] = None, block_cols: int = 2048,
                 interpret: bool = False):
    """One fused AdamW update for a leaf of any shape.

    p (param dtype), g (grad dtype), m/v (f32) all share p.shape; lr and
    the bias corrections bc1 = 1-b1^t, bc2 = 1-b2^t are traced scalars.
    Returns (new_p p.dtype, new_m f32, new_v f32) with p.shape.
    ``block_rows`` (a multiple of 32; None sizes the tile to about
    ``BLOCK_ELEMS`` elements) and ``block_cols`` (a multiple of 128) cap
    the tile; a dim smaller than its cap is taken whole.
    """
    shape = p.shape
    if p.size == 0:
        return p, m, v
    cols = shape[-1] if shape else 1
    rows = math.prod(shape[:-1]) if shape else 1
    br, bc = _block(rows, cols, block_rows, block_cols)

    def view(x, dtype=None):
        x = x.reshape(rows, cols)
        return x if dtype is None else x.astype(dtype)

    scalars = jnp.stack([jnp.asarray(lr, jnp.float32),
                         jnp.asarray(bc1, jnp.float32),
                         jnp.asarray(bc2, jnp.float32)])
    kernel = functools.partial(_adamw_kernel, b1=b1, b2=b2, eps=eps,
                               weight_decay=weight_decay)
    tile = pl.BlockSpec((br, bc), lambda i, j: (i, j))
    new_p, new_m, new_v = pl.pallas_call(
        kernel,
        grid=(pl.cdiv(rows, br), pl.cdiv(cols, bc)),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  tile, tile, tile, tile],
        out_specs=[tile, tile, tile],
        out_shape=[jax.ShapeDtypeStruct((rows, cols), p.dtype),
                   jax.ShapeDtypeStruct((rows, cols), jnp.float32),
                   jax.ShapeDtypeStruct((rows, cols), jnp.float32)],
        input_output_aliases={1: 0, 3: 1, 4: 2},     # p, m, v in place
        interpret=interpret,
        name="adamw_update",
    )(scalars, view(p), view(g), view(m, jnp.float32),
      view(v, jnp.float32))
    return new_p.reshape(shape), new_m.reshape(shape), new_v.reshape(shape)
