"""Single-source-of-truth parameter schemas.

A model's parameters are described once as a nested dict of ``PSpec``
(shape + logical axes + init).  From the schema we derive, consistently:
  * materialized params            (``init_params``)
  * abstract params for dry-runs   (``abstract_params`` — no allocation)
  * logical-axis tree              (``axes_tree``)
  * NamedSharding tree             (repro.sharding.specs.shardings_for)
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


@dataclass(frozen=True)
class PSpec:
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]   # logical axis names, len == len(shape)
    init: str = "normal"              # normal | zeros | ones | scaled
    scale: Optional[float] = None     # stddev override for "normal"/"scaled"
    dtype: Optional[str] = None       # override model param dtype

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def is_pspec(x) -> bool:
    return isinstance(x, PSpec)


def _leaves(schema) -> list[tuple[str, PSpec]]:
    out: list[tuple[str, PSpec]] = []

    def rec(node, path):
        if is_pspec(node):
            out.append((path, node))
            return
        for k in sorted(node.keys()):
            rec(node[k], f"{path}/{k}" if path else k)

    rec(schema, "")
    return out


def tree_map_schema(fn, schema):
    """Map fn(path, PSpec) over a schema, preserving structure."""
    def rec(node, path):
        if is_pspec(node):
            return fn(path, node)
        return {k: rec(v, f"{path}/{k}" if path else k) for k, v in node.items()}
    return rec(schema, "")


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _normal(key, shape, std: float, dt) -> jax.Array:
    # one fused program: the f32 draw never lands in HBM whole (eagerly,
    # a (32, 3072, 8192) leaf would hold two 3.2 GB f32 temporaries)
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dt)


def _init_one(path: str, p: PSpec, key, dtype) -> jax.Array:
    dt = jnp.dtype(p.dtype or dtype)
    if p.init == "zeros":
        return jnp.zeros(p.shape, dt)
    if p.init == "ones":
        return jnp.ones(p.shape, dt)
    fan_in = p.shape[-2] if len(p.shape) >= 2 else p.shape[-1]
    std = p.scale if p.scale is not None else 1.0 / np.sqrt(max(fan_in, 1))
    return _normal(key, p.shape, float(std), dt)


def init_params(schema, key, dtype: str):
    leaves = _leaves(schema)
    keys = jax.random.split(key, max(len(leaves), 1))
    key_of = {path: keys[i] for i, (path, _) in enumerate(leaves)}
    return tree_map_schema(lambda path, p: _init_one(path, p, key_of[path], dtype), schema)


def abstract_params(schema, dtype: str):
    return tree_map_schema(
        lambda _p, p: jax.ShapeDtypeStruct(p.shape, jnp.dtype(p.dtype or dtype)),
        schema)


def axes_tree(schema):
    return tree_map_schema(lambda _p, p: p.axes, schema)


def param_count(schema) -> int:
    return int(sum(int(np.prod(p.shape)) for _, p in _leaves(schema)))


def param_bytes(schema, dtype: str) -> int:
    return int(sum(int(np.prod(p.shape)) * jnp.dtype(p.dtype or dtype).itemsize
                   for _, p in _leaves(schema)))
