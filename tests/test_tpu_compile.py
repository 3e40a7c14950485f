"""Compile the main path's Pallas kernels for a described TPU v5e chip.

Nothing runs here: the TPU compiler installed with jax compiles for a
chip that is described, not attached, so these tests catch what interpret
mode cannot (a block layout Mosaic refuses, a kernel XLA cannot
partition, temporaries that do not fit) at phi4-mini-3.8b's widths, with
no chip.  The topology and everything built from it live in fixtures of
this one file: only the worker that runs these tests loads the TPU
library.
"""
import math
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P
from jax.sharding import SingleDeviceSharding

VOCAB, D_MODEL, D_FF = 200_064, 3072, 8192     # configs/phi4_mini_3_8b.py
XENT_ROWS = 2048                               # batch x loss chunk rows
ADAMW_HP = dict(b1=0.9, b2=0.95, eps=1e-8, weight_decay=0.1)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh4(topo):
    import numpy as np
    return Mesh(np.array(topo.devices[:4]).reshape(2, 2), ("data", "model"))


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _mosaic_calls(compiled) -> int:
    return compiled.as_text().count('custom_call_target="tpu_custom_call"')


@pytest.mark.parametrize("direction", ["forward", "grad"])
def test_xent_compiles_for_v5e(one_chip, direction):
    from repro.kernels.xent import softmax_xent

    def loss(x, labels):
        return jnp.sum(softmax_xent(x, labels))

    fn = loss if direction == "forward" else jax.grad(loss)
    compiled = jax.jit(fn).lower(
        _sds((XENT_ROWS, VOCAB), jnp.float32, one_chip),
        _sds((XENT_ROWS,), jnp.int32, one_chip)).compile()
    assert _mosaic_calls(compiled) == (1 if direction == "forward" else 2)


def _adamw_leaf(p, g, m, v, scalars):
    from repro.kernels.adamw_update import adamw_update
    return adamw_update(p, g, m, v, scalars[0], scalars[1], scalars[2],
                        **ADAMW_HP)


@pytest.mark.parametrize("shape", [
    (VOCAB, D_MODEL),          # the tied embedding: the largest leaf
    (2, D_MODEL, D_FF),        # a layer-stacked MLP leaf
    (D_MODEL,),                # a 1-D norm scale
], ids=["embed", "mlp", "norm"])
def test_adamw_compiles_in_place(one_chip, shape):
    """p, m and v alias their outputs, and no leaf is relaid out: the
    temporaries stay under one f32 copy of the leaf (the flatten to
    (n/128, 128) needed 6.87 GiB of them for the embedding)."""
    leaf = [_sds(shape, dt, one_chip) for dt in
            (jnp.bfloat16, jnp.bfloat16, jnp.float32, jnp.float32)]
    compiled = jax.jit(_adamw_leaf, donate_argnums=(0, 2, 3)).lower(
        *leaf, _sds((3,), jnp.float32, one_chip)).compile()
    ma = compiled.memory_analysis()
    n = math.prod(shape)
    assert _mosaic_calls(compiled) == 1
    assert ma.temp_size_in_bytes < 4 * n                  # one f32 copy
    assert ma.alias_size_in_bytes == (2 + 4 + 4) * n      # p, m, v in place


def test_adamw_sharded_compiles_per_shard(mesh4, monkeypatch):
    """On a four-chip mesh the optimizer runs the kernel per shard
    (XLA cannot partition a Mosaic kernel itself)."""
    from repro.configs.base import OptimizerConfig, ParallelConfig
    from repro.models.params import PSpec
    from repro.optim import adamw
    from repro.sharding import specs as sh

    monkeypatch.setattr(adamw, "interpret_default", lambda: False)
    schema = {"wg": PSpec((2, D_MODEL, D_FF), ("layers", "fsdp", "tp_ff")),
              "ln": PSpec((2, D_MODEL), ("layers", None), "zeros")}
    shd = sh.shardings_for_schema(schema, mesh4,
                                  sh.logical_rules(ParallelConfig()))
    ocfg = OptimizerConfig()
    opt_schema = adamw.opt_state_schema(schema, ocfg)

    def abstract(sch, dtype):
        return {k: _sds(p.shape, jnp.dtype(p.dtype or dtype), shd[k])
                for k, p in sch.items()}

    state = {"m": abstract(opt_schema["m"], "float32"),
             "v": abstract(opt_schema["v"], "float32"),
             "count": _sds((), jnp.int32, NamedSharding(mesh4, P()))}

    def step(params, grads, state):
        return adamw.apply_updates(schema, params, grads, state, ocfg,
                                   fused=True, shardings=shd)

    compiled = jax.jit(step, donate_argnums=(0, 2)).lower(
        abstract(schema, "bfloat16"), abstract(schema, "bfloat16"),
        state).compile()
    assert _mosaic_calls(compiled) == 2
    assert shd["wg"].spec != P()
