"""A decode step's per-layer KV cache: how a layer takes the step's new
rows, attends over its history, and what it hands to the write-back.

Two forms of cache reach a layer in decode:

* a dict of (B, S, ...) leaves by name: the slotted cache, or a paged
  step's views gathered from the block pool (``paged_layer_view``).  The
  step's rows are written in (``insert_kv``) and the layer attends over
  the whole view;
* a ``PagedLayer``: one layer of the block pool, which the paged
  attention kernel (``kernels.paged_decode``) reads in place through the
  block tables, with the step's rows as one extra column.

``paged_layers`` chooses between them once per layer group of a paged
step; ``attend`` is the one place a layer's attention tells them apart,
and ``written`` the one place the write-back does.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, NamedTuple

import jax
import jax.numpy as jnp

from repro.kernels import paged_decode


def insert_kv(cache, rows, pos):
    """Write this step's rows (B, 1, ...) into the cache leaves of the
    same names (B, S, ...) at ``pos``: ``{"k": k, "v": v}`` for K/V
    attention, the latent and rotary key for MLA.  Returns the written
    leaves by name.

    ``pos`` is a scalar (whole-batch decode, all rows at the same position)
    or a (B,) vector (slot-based continuous batching: every row of the
    batch is a different request at its own sequence position).  A vector
    entry >= cache length writes nothing — a free/overflowed slot is a
    no-op rather than an out-of-bounds clamp.
    """
    with jax.named_scope("kv_write"):
        pos = jnp.asarray(pos)
        if pos.ndim == 0:
            return {name: jax.lax.dynamic_update_slice_in_dim(
                cache[name], row, pos, axis=1) for name, row in rows.items()}
        S = cache[next(iter(rows))].shape[1]
        hit = jnp.arange(S)[None, :] == pos[:, None]
        hits = {}                       # by the rows' rank: one mask each
        out = {}
        for name, row in rows.items():
            if row.ndim not in hits:
                hits[row.ndim] = hit[(...,) + (None,) * (row.ndim - 2)]
            out[name] = jnp.where(hits[row.ndim], row, cache[name])
        return out


class PagedLayer(NamedTuple):
    """One layer of a paged pool on the kernel path: its block key's
    leaves (layers, num_blocks, block_size, ...), the layer's index in
    them and the block tables (B, S // block_size)."""
    pool: Dict[str, jax.Array]
    layer: Any
    tables: jax.Array

    def attend(self, q, rows, pos, **kw):
        """q (B, 1, H, D) against each slot's live history in the pool and
        the step's own ``rows`` (by leaf name, (B, KV, D) or (B, D)), with
        the Pallas kernel; ``kw`` as ``paged_decode.paged_attention``'s.
        Returns (B, 1, H, D)."""
        B, _, H, D = q.shape
        out = paged_decode.paged_attention(
            q.reshape(B, H, D), [r.reshape(B, -1, D) for r in rows.values()],
            [self.pool[name] for name in rows], self.layer, self.tables, pos,
            **kw)
        return out.reshape(B, 1, H, D)

    def written(self, rows, pos):
        """The write-back rows, by leaf, as ``written_rows`` gives them on
        the view path: the step's row (B, ...) where it is more than one
        vector; else the block holding it, fetched from the pool with the
        row set, (B, block_size, width).  A slot past S-1 writes to the
        null block."""
        def one(name, row):
            if row.ndim > 2:
                return row
            leaf, bs = self.pool[name], self.pool[name].shape[2]
            S = self.tables.shape[1] * bs
            blk = jnp.take_along_axis(self.tables, (pos // bs)[:, None],
                                      axis=1)[:, 0]
            block = leaf[self.layer, jnp.where(pos < S, blk, 0)]
            hit = jnp.arange(bs)[None, :] == (pos % bs)[:, None]
            return jnp.where(hit[..., None], row[:, None, :], block)

        with jax.named_scope("kv_scatter"):
            return {name: one(name, row) for name, row in rows.items()}


def paged_layers(mesh, pool, group, tables):
    """Layer group ``group``'s caches of a paged pool ({block key:
    leaves}): ``PagedLayer`` handles where the paged kernel runs
    (``paged_decode.kernel_path``), else the gathered views."""
    if not paged_decode.kernel_path(mesh, jax.tree.leaves(pool)):
        return paged_layer_view(pool, group, tables)
    return {key: PagedLayer(leaves, group, tables)
            for key, leaves in pool.items()}


def attend(cache, rows, pos, q, dense: Callable, **kw):
    """One decode token per slot: (attention output, the layer's new
    cache).  ``rows``: the step's rows by leaf name, (B, 1, ...).  A
    ``PagedLayer`` attends with the paged kernel (query ``q``, ``kw`` as
    ``PagedLayer.attend``'s) and its new cache is the rows, (B, ...);
    (B, S, ...) views take the rows (``insert_kv``) and ``dense(views)``
    attends over them."""
    if isinstance(cache, PagedLayer):
        rows = {name: r[:, 0] for name, r in rows.items()}
        return cache.attend(q, rows, pos, **kw), rows
    views = insert_kv(cache, rows, pos)
    return dense(views), views


def paged_layer_view(pool, group, tables):
    """Layer group ``group``'s contiguous (B, S, ...) views of a paged
    pool, whose leaves are (groups, num_blocks, block_size, ...):
    ``tables`` (B, S // block_size) lists every slot's blocks in order.
    One gather per leaf, indexed by group and block together, so the
    group's slice of the pool is never copied out first."""
    def gather(leaf):
        g = leaf[group, tables]                   # (B, nb, bs, *tail)
        return g.reshape(g.shape[0], g.shape[1] * g.shape[2], *g.shape[3:])
    with jax.named_scope("kv_gather"):
        return jax.tree.map(gather, pool)


def written_rows(views, pos, block_size):
    """What each slot's decode step wrote into its (B, S, ...) view, per
    leaf: slot i's row ``pos[i]``, (B, ...); or, for a leaf whose row is
    one vector (MLA's latent, views (B, S, width)), the ``block_size``
    rows of the block holding it, (B, block_size, width).  One vector
    fills one row of the TPU's (8, 128) memory tiles, and to write it
    alone the compiler lays the whole pool out anew every step; a block
    is whole tiles.  Positions past S-1, where the slot wrote nothing,
    are clamped to it (the write-back sends them to the null block)."""
    def written(leaf):
        B = jnp.arange(leaf.shape[0])
        if leaf.ndim > 3:
            return leaf[B, pos]
        rows = (pos // block_size * block_size)[:, None] + jnp.arange(
            block_size)
        return leaf[B[:, None], rows]

    with jax.named_scope("kv_scatter"):
        return jax.tree.map(written, views)


def written(caches, rows, pos, block_size):
    """A paged layer group's write-back rows, per block key and leaf:
    ``caches`` as ``paged_layers`` gave them, ``rows`` the layers' new
    caches as ``attend`` returned them."""
    return {key: (caches[key].written(new, pos)
                  if isinstance(caches[key], PagedLayer)
                  else written_rows(new, pos, block_size))
            for key, new in rows.items()}
