"""Pallas TPU one-token attention over a paged KV pool, read in place.

TARGET: TPU v5e DMA engines and VMEM.  The decode step's attention for
every slot reads that slot's live blocks of one layer straight from the
pool leaf, ``(layers, num_blocks, block_size, ...)``, through its block
table: no layer slice of the pool and no ``(slots, S, ...)`` view is
built, and no block past a slot's last history position is read.

Grid = (slots,), sequential.  Scalar prefetch carries the layer index,
the block tables and each slot's position.  A slot's blocks stream
through two VMEM buffers of ``chunk`` blocks each: while one chunk is
scored, the next one's block copies (one DMA per block and leaf, waited
for in power-of-two runs) are in flight, and the last chunk of a slot
starts the next slot's first, so a slot boundary exposes no copy
latency.  A slot with no history issues no copy.  A chunk's copies are
started in a loop bounded by its live blocks, so the kernel's size does
not grow with the buffer.  On a v5e each block copy costs about 29 ns
beyond its bytes (larger blocks are faster for the same live bytes).

The step's own row is not in the pool yet: it enters as one extra
column that starts each query head's online softmax, so the caller
writes it back alone (``models.kvcache.PagedLayer.written``).

Two layouts, told apart by the pool leaves' shapes:

* K/V attention, leaves ``k`` and ``v`` of ``(..., bs, KV, dh)``: query
  head ``h`` reads KV head ``h // (H // KV)``.  A chunk's rows are
  flattened (block, position, KV head); every query head is scored
  against every row on the MXU and rows of other KV heads are masked,
  which costs FLOPs the chip has to spare and keeps every operand a
  plain 2-D tile.
* Latent attention (MLA), one leaf of ``(..., bs, W)`` that is both key
  and value: one shared head for all absorbed query heads.  Its zero pad
  columns ride along, as in ``models.mla._absorbed_decode``.

Scores and the softmax statistics are float32 (``preferred_element_type``
on the MXU); the probabilities enter the values product in the cache's
dtype, as in the view path (``models.attention.decode_attention``).
Masked positions contribute exactly 0.  ``window`` moves the first block
read; ``logit_softcap`` caps the scores before the mask.

Off the TPU, and for head widths that are not whole 128-lane tiles, the
paged step keeps the view path (``kernel_path``); the
kernel itself runs there in interpret mode for its tests
(tests/test_paged_decode_kernel.py), and tests/test_tpu_compile.py
compiles it for v5e inside the paged step at phi4-mini's and Kimi K2's
widths.
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import NEG_INF, interpret_default

F32 = jnp.float32
CHUNK_BYTES = 1 << 20         # one buffer's blocks, all leaves together


def kernel_path(mesh, pool: Sequence[jax.Array]) -> bool:
    """Whether the paged decode step attends with this kernel: on the TPU
    backend, with the step on one device (a Mosaic kernel is not
    partitioned across a mesh), and every pool leaf's rows a whole number
    of 128-lane tiles (a block's copy is then tile-aligned)."""
    return (not interpret_default() and (mesh is None or mesh.size == 1)
            and all(x.shape[-1] % 128 == 0 for x in pool))


def history_blocks(pos, *, block_size: int, seq_len: int,
                   window: Optional[int] = None):
    """(first block, number of blocks, lo, hi) of the pool a slot at
    ``pos`` reads: history positions [lo, hi), hi = min(pos, S) (the row
    at ``pos`` is the step's own), lo = pos - window + 1 with a window."""
    hi = jnp.minimum(pos, seq_len)
    lo = 0 if window is None else jnp.maximum(pos - window + 1, 0)
    first = lo // block_size
    count = jnp.where(hi > lo, (hi + block_size - 1) // block_size - first,
                      0)
    return first, count, lo, hi


def _kernel(layer_ref, tables_ref, pos_ref, q_ref, *refs, n_leaves: int,
            kv_heads: int, block_size: int, seq_len: int, chunk: int,
            scale: float, window: Optional[int], cap: Optional[float]):
    new_refs = refs[:n_leaves]
    pool_refs = refs[n_leaves:2 * n_leaves]
    o_ref = refs[2 * n_leaves]
    bufs = refs[2 * n_leaves + 1:3 * n_leaves + 1]
    sems, cur_ref, m_ref, l_ref, acc_ref = refs[3 * n_leaves + 1:]
    b, slots = pl.program_id(0), pl.num_programs(0)
    layer = layer_ref[0]
    H, D = q_ref.shape[1:]
    group = H // kv_heads
    rows = chunk * block_size * kv_heads     # one chunk, flattened

    def chunk_span(slot, ci):
        """The first block-table column of chunk ``ci`` of ``slot``'s
        history, and how many of the chunk's blocks are history."""
        first, count, _, _ = history_blocks(
            pos_ref[slot], block_size=block_size, seq_len=seq_len,
            window=window)
        return first + ci * chunk, jnp.minimum(count - ci * chunk, chunk)

    def start(slot, ci, buf):
        """Copy chunk ``ci`` of ``slot``'s history blocks into buffer
        ``buf``: one DMA per block and leaf, none past the last."""
        col0, n = chunk_span(slot, ci)

        def one(j, carry):
            blk = tables_ref[slot, col0 + j]
            for i in range(n_leaves):
                pltpu.make_async_copy(pool_refs[i].at[layer, blk],
                                      bufs[i].at[buf, j],
                                      sems.at[i, buf]).start()
            return carry

        jax.lax.fori_loop(0, n, one, 0)

    def wait(slot, ci, buf):
        """Wait for ``start``'s copies.  A DMA semaphore counts bytes, so
        the n blocks are waited for in power-of-two runs (n's bits), not
        one by one."""
        _, n = chunk_span(slot, ci)
        for size in [1 << k for k in range(chunk.bit_length())][::-1]:
            @pl.when((n & size) != 0)
            def _():
                for i in range(n_leaves):
                    pltpu.make_async_copy(
                        pool_refs[i].at[layer, pl.ds(0, size)],
                        bufs[i].at[buf, pl.ds(0, size)],
                        sems.at[i, buf]).wait()

    @pl.when(b == 0)
    def _():
        for buf in bufs:                 # masked tails stay finite
            buf[...] = jnp.zeros(buf.shape, buf.dtype)
        cur_ref[0] = 0
        start(0, 0, 0)

    def cap_(s):
        return s if cap is None else cap * jnp.tanh(s / cap)

    def scores(q, k):
        return cap_(jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                        preferred_element_type=F32) * scale)

    def update(s, v):
        """Fold masked scores (H, n) and their value rows (n, D) into the
        running max, sum and accumulator."""
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = alpha * acc_ref[...] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=F32)
        m_ref[...] = m_new

    # the step's own row starts the softmax: one column per KV head, the
    # query head's own alone live, none past the end of the cache
    pos = pos_ref[b]
    q = q_ref[0]
    new = [r[0] for r in new_refs]                           # (KV, D)
    own = ((jax.lax.broadcasted_iota(jnp.int32, (H, kv_heads), 0) // group
            == jax.lax.broadcasted_iota(jnp.int32, (H, kv_heads), 1))
           & (pos < seq_len))
    p = jnp.where(own, 1.0, 0.0).astype(F32)
    if kv_heads == 1:               # a one-column product: on the VPU
        s = cap_(jnp.sum(q.astype(F32) * new[0].astype(F32), axis=1,
                         keepdims=True) * scale)
        acc = p * new[-1].astype(F32)
    else:
        s = scores(q, new[0])
        acc = jax.lax.dot_general(
            p.astype(new[-1].dtype), new[-1], (((1,), (0,)), ((), ())),
            preferred_element_type=F32)
    m_ref[...] = jnp.max(jnp.where(own, s, NEG_INF), axis=1, keepdims=True)
    l_ref[...] = jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc

    first, count, lo, hi = history_blocks(
        pos, block_size=block_size, seq_len=seq_len, window=window)
    n_chunks = (count + chunk - 1) // chunk
    col = jax.lax.broadcasted_iota(jnp.int32, (H, rows), 1)
    mine = (jax.lax.broadcasted_iota(jnp.int32, (H, rows), 0) // group
            == col % kv_heads)

    def flat(buf, cur):
        x = buf[cur].astype(F32)               # (chunk, bs, [KV,] D)
        return x.reshape(rows, D).astype(buf.dtype)

    def body(ci, cur):
        nxt = 1 - cur

        @pl.when(ci + 1 < n_chunks)
        def _():
            start(b, ci + 1, nxt)

        @pl.when((ci + 1 == n_chunks) & (b + 1 < slots))
        def _():
            start(b + 1, 0, nxt)

        wait(b, ci, cur)
        kpos = (first + ci * chunk) * block_size + col // kv_heads
        live = mine & (kpos >= lo) & (kpos < hi)
        k = flat(bufs[0], cur)
        v = k if n_leaves == 1 else flat(bufs[1], cur)
        update(jnp.where(live, scores(q, k), NEG_INF), v)
        return nxt

    cur = jax.lax.fori_loop(0, n_chunks, body, cur_ref[0])

    @pl.when((n_chunks == 0) & (b + 1 < slots))
    def _():
        start(b + 1, 0, cur)

    cur_ref[0] = cur
    o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(
        o_ref.dtype)


def chunk_blocks(pool: Sequence[jax.Array], max_blocks: int) -> int:
    """Blocks per buffer: ``CHUNK_BYTES`` of all leaves' blocks together,
    in whole eights (a chunk's rows then fill whole bf16 tiles), at most
    a slot's whole table."""
    block = sum(math.prod(x.shape[2:]) * x.dtype.itemsize for x in pool)
    n = CHUNK_BYTES // block
    return max(1, min(max_blocks, n // 8 * 8 if n >= 8 else n))


def paged_attention(q: jax.Array, new: Sequence[jax.Array],
                    pool: Sequence[jax.Array], layer, tables: jax.Array,
                    pos: jax.Array, *, scale: float,
                    window: Optional[int] = None,
                    logit_softcap: Optional[float] = None,
                    chunk: Optional[int] = None,
                    interpret: Optional[bool] = None) -> jax.Array:
    """One query token per slot against its live history in the pool.

    q (B, H, D): K/V attention's query heads grouped by KV head, or the
    absorbed latent queries.  ``pool``: the leaves ``[k, v]``, each
    (layers, num_blocks, bs, KV, D), or ``[latent]``, (layers,
    num_blocks, bs, D), both key and value.  ``new``: the step's own
    rows, one per leaf, (B, KV, D) (KV = 1 for the latent leaf).
    ``layer``: the layer's index in the leaves; ``tables`` (B, S // bs)
    int32; ``pos`` (B,) int32, where the step's row sits (a slot at
    ``pos >= S`` has no row of its own and reads all S positions).
    ``chunk``: blocks per buffer (default ``chunk_blocks``).  Returns
    (B, H, D) in q's dtype."""
    B, H, D = q.shape
    bs, nb = pool[0].shape[2], tables.shape[1]
    kv_heads = 1 if pool[0].ndim == 4 else pool[0].shape[3]
    if chunk is None:
        chunk = chunk_blocks(pool, nb)
    if interpret is None:
        interpret = interpret_default()
    kernel = functools.partial(
        _kernel, n_leaves=len(pool), kv_heads=kv_heads, block_size=bs,
        seq_len=nb * bs, chunk=chunk, scale=scale, window=window,
        cap=logit_softcap)
    slot = lambda b, *_: (b, 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B,),
        in_specs=([pl.BlockSpec((1, H, D), slot)]
                  + [pl.BlockSpec((1,) + r.shape[1:], slot) for r in new]
                  + [pl.BlockSpec(memory_space=pl.ANY) for _ in pool]),
        out_specs=pl.BlockSpec((1, H, D), slot),
        scratch_shapes=(
            [pltpu.VMEM((2, chunk) + x.shape[2:], x.dtype) for x in pool]
            + [pltpu.SemaphoreType.DMA((len(pool), 2)),
               pltpu.SMEM((1,), jnp.int32),
               pltpu.VMEM((H, 1), F32), pltpu.VMEM((H, 1), F32),
               pltpu.VMEM((H, D), F32)]))
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=pltpu.InterpretParams() if interpret else False,
        name="paged_decode",
    )(jnp.reshape(jnp.asarray(layer, jnp.int32), (1,)), tables, pos, q,
      *new, *pool)
