"""Compile the main path's Pallas kernels, and the paged decode step, for
a described TPU v5e chip.

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


def _kernel_step(topo, monkeypatch, cfg, slots, S, bs, nb):
    """Compile the paged decode step as a TPU runs it: the paged
    attention kernel in every layer (steered on here, where the backend
    is the CPU), for one described v5e chip."""
    import numpy as np

    from repro.configs import registry
    from repro.configs.base import ShapeConfig
    from repro.kernels import paged_decode
    from repro.runtime import steps as steps_mod

    monkeypatch.setattr(paged_decode, "interpret_default", lambda: False)
    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("data", "model"))
    bundle = steps_mod.build_paged_decode(
        cfg, registry.get_parallel(cfg.name), mesh,
        ShapeConfig("serve", S, slots, "decode"), block_size=bs,
        num_blocks=nb)
    assert paged_decode.kernel_path(mesh,
                                    jax.tree.leaves(bundle.abstract_args[1]))
    args = [jax.tree.map(lambda a, s: _sds(a.shape, a.dtype, s), a, s)
            for a, s in zip(bundle.abstract_args, bundle.in_shardings)]
    compiled = jax.jit(
        bundle.fn, in_shardings=bundle.in_shardings,
        out_shardings=bundle.out_shardings,
        donate_argnums=bundle.donate_argnums).lower(*args).compile()
    return compiled, compiled.as_text(), steps_mod.hlo_scopes(
        compiled.as_text())


def _kernel_scopes(scopes, scope):
    """The paged attention kernel's calls, each under ``scope``."""
    calls = [n for n in scopes if n.split(".")[0] == "paged_decode"]
    assert calls and all(scope in scopes[n].split("/") for n in calls)
    return calls


def test_paged_decode_reads_the_pool_in_place(topo, monkeypatch):
    """The paged decode step at phi4-mini's widths (two layers, 18 slots x
    1,024 positions in blocks of 8), as the TPU runs it: the paged
    attention kernel compiles through Mosaic in the layer scan, under the
    ``attn`` scope, and reads the K and V leaves in place: no copy of a
    layer's pool slice, no (slots, S, ...) view or its gather, and
    temporaries under a twentieth of one layer's K view (the compile
    plans 0.023 of it: the layer's activations; the views took 2)."""
    import re

    from repro.configs import registry

    L, slots, S, bs = 2, 18, 1024, 8
    cfg = registry.get_config("phi4-mini-3.8b").replace(num_layers=L)
    nb = 1 + slots * S // bs + 32
    compiled, text, scopes = _kernel_step(topo, monkeypatch, cfg, slots, S,
                                          bs, nb)
    KV, dh = cfg.num_kv_heads, cfg.resolved_head_dim
    assert _mosaic_calls(compiled) == 1                       # in the scan
    _kernel_scopes(scopes, "attn")
    assert not re.search(rf"\[{nb},{bs},{KV},{dh}\]", text)   # slice copy
    assert not re.search(rf"\[{slots * S // bs},{L},{bs},{KV},{dh}\]|"
                         rf"\[{L},{slots},{S},{KV},{dh}\]|"
                         rf"\[{slots},{S},{KV},{dh}\]|"
                         rf"\[{slots},{S // bs},{bs},{KV},{dh}\]", text)
    view = slots * S * KV * dh * 2                            # bf16 bytes
    assert compiled.memory_analysis().temp_size_in_bytes < view / 20


def test_latent_paged_decode_keeps_the_pool_in_place(topo, monkeypatch):
    """Kimi K2's paged decode step at its cell's size (the dense layer
    and one MoE layer, 99 slots x 6,144 positions in blocks of 8): the
    kernel compiles in the lead layer and in the scan, under the ``mla``
    scope; the latent pool keeps its row-major layout, so no step copies
    a pool leaf to read or to write it (a 576-wide or 64-wide leaf, or
    one-row writes, make the compiler lay the whole pool out anew every
    step); no (slots, S, width) view is built; and the temporaries stay
    under a twentieth of one layer's view (the compile plans 0.031 of
    it, the views took up to 2.2)."""
    import re

    from bench import spec
    from bench.model_mla_moe import program_config
    from repro.models import mla

    slots, S, bs = 99, 6144, 8
    cfg = program_config(spec.config("kimi-k2-instruct-ep48")).replace(
        num_layers=2)
    nb = 1 + slots * S // bs + 256
    compiled, text, scopes = _kernel_step(topo, monkeypatch, cfg, slots, S,
                                          bs, nb)
    W = mla.latent_width(cfg)
    assert W == 640
    assert _mosaic_calls(compiled) == 2                 # lead layer, scan
    assert len(_kernel_scopes(scopes, "mla")) == 2
    pool = rf"bf16\[1,{nb},{bs},\d+\]"
    assert len(re.findall(rf"{pool}{{3,2,1,0:[^}}]*}} parameter", text)) >= 2
    # a copy into another layout (a prefetch into on-chip memory keeps it)
    assert not re.search(rf"= {pool}{{(?!3,2,1,0:)[^}}]*}} copy", text)
    assert not re.search(rf"\[{slots},{S},{W}\]|\[{slots},{S // bs},{bs},"
                         rf"{W}\]", text)
    view = slots * S * W * 2                                  # bf16 bytes
    assert compiled.memory_analysis().temp_size_in_bytes < view / 20
