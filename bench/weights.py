"""Weights of a dense decoder, made on the device from a seed.

The layout is the program's parameter tree (``blocks/0_attn/<leaf>`` with
the layers stacked on a leading axis, ``embed``, ``final_norm``), because
the system under test is handed these arrays.  The recipe is the
program's own initialisation, written out here: one key per leaf, split
from the seed in the sorted order of the leaf paths; norm scales zero
(the norms multiply by ``1 + scale``); every other leaf a normal draw in
float32, times its standard deviation, cast to the stored type.  So the
reference, which reads these weights, takes nothing the program made,
and the trainer, which draws its own from the same seed, starts from the
same values (``bench/tests/test_weights.py``).
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from bench.model import Dims

BLOCK = "0_attn"


def leaves(d: Dims) -> Dict[str, Tuple[Tuple[int, ...], Optional[float]]]:
    """Leaf path -> (shape, standard deviation; None for a zero leaf)."""
    G, D, H, KV, dh, F, V = (d.layers, d.d_model, d.heads, d.kv_heads,
                             d.head_dim, d.d_ff, d.vocab)
    b = f"blocks/{BLOCK}/"
    return {
        b + "ln1": ((G, D), None),
        b + "ln2": ((G, D), None),
        b + "wg": ((G, D, F), D ** -0.5),
        b + "wk": ((G, D, KV, dh), D ** -0.5),
        b + "wo": ((G, H, dh, D), (H * dh) ** -0.5),
        b + "wo_mlp": ((G, F, D), F ** -0.5),
        b + "wq": ((G, D, H, dh), D ** -0.5),
        b + "wu": ((G, D, F), D ** -0.5),
        b + "wv": ((G, D, KV, dh), D ** -0.5),
        "embed": ((V, D), 0.02),
        "final_norm": ((D,), None),
    }


def _nest(flat: Dict[str, jax.Array]) -> Dict:
    out: Dict = {}
    for path, leaf in flat.items():
        node = out
        *parents, last = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[last] = leaf
    return out


@functools.partial(jax.jit, static_argnums=(1,))
def _make(key, d: Dims):
    specs = leaves(d)
    paths = sorted(specs)
    keys = jax.random.split(key, len(paths))
    dt = jnp.dtype(d.dtype)
    flat = {}
    for k, path in zip(keys, paths):
        shape, std = specs[path]
        flat[path] = (jnp.zeros(shape, dt) if std is None else
                      (jax.random.normal(k, shape, jnp.float32) * std
                       ).astype(dt))
    return _nest(flat)


def make(d: Dims, seed: int) -> Dict:
    """Every weight, in the stored type, from one jitted call."""
    return _make(jax.random.key(seed), d)
