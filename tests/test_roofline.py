"""Roofline machinery tests: the analytic FLOP accounting is cross-checked
against XLA's cost analysis on a small UNROLLED config (where XLA counts
everything), and the HLO collective parser against a hand-built module."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.configs.base import ModelConfig, OptimizerConfig, ParallelConfig, \
    ShapeConfig
from repro.launch.mesh import single_device_mesh
from repro.roofline import flops as flops_mod
from repro.roofline import hlo as hlo_mod
from repro.runtime import steps as steps_mod


def test_analytic_flops_vs_xla_small_dense():
    """Unrolled tiny dense model: analytic fwd+bwd flops within 2x of XLA
    (XLA counts transcendental/elementwise we deliberately exclude)."""
    cfg = ModelConfig(name="t", family="dense", num_layers=2, d_model=64,
                      num_heads=4, num_kv_heads=4, d_ff=128, vocab_size=256,
                      head_dim=16)
    par = ParallelConfig(scan_layers=False, remat=False)
    ocfg = OptimizerConfig()
    shape = ShapeConfig("t", 64, 2, "train")
    mesh = single_device_mesh()
    bundle = steps_mod.build_train(cfg, par, ocfg, mesh, shape)
    with mesh:
        compiled = bundle.lower().compile()
    xla = compiled.cost_analysis().get("flops", 0.0)
    # fwd * (1 fwd + 2 bwd) -- no remat here
    ours = flops_mod.forward_flops(cfg, shape, 1) * 3.0
    assert xla > 0
    assert 0.5 < ours / xla < 2.0, (ours, xla)


def test_model_flops_definition():
    cfg = registry.get_config("kimi-k2-1t-a32b")
    shape = ShapeConfig("t", 4096, 256, "train")
    acc = flops_mod.accounting(cfg, shape, 256)
    # ~1T total params, ~32B active
    assert 0.9e12 < acc.params < 1.3e12
    assert 25e9 < acc.active_params < 45e9
    assert acc.model_flops == pytest.approx(
        6.0 * acc.active_params * 256 * 4096)


def test_hlo_collective_parser():
    text = """
  %ag = f32[16,4096]{1,0} all-gather(%x), replica_groups=[16,16]<=[16,16]T(1,0), dimensions={0}
  %ar = bf16[8,128]{1,0} all-reduce(%y), replica_groups=[1,256]<=[256]
  %rs = f32[1,64]{1,0} reduce-scatter(%z), replica_groups=[16,16]<=[256]
  %a2a = bf16[4,32]{1,0} all-to-all(%w), replica_groups=[16,16]<=[256]
  %cp = f32[2,2]{1,0} collective-permute(%v), source_target_pairs={{0,1}}
"""
    got = hlo_mod.collective_bytes(text)
    assert got["all-gather"] == 16 * 4096 * 4 // 16
    assert got["all-reduce"] == 8 * 128 * 2
    assert got["reduce-scatter"] == 64 * 4 * 16
    assert got["all-to-all"] == 4 * 32 * 2
    assert got["collective-permute"] == 2 * 2 * 4
    assert got["total"] == sum(got[k] for k in
                               ("all-gather", "all-reduce", "reduce-scatter",
                                "all-to-all", "collective-permute"))
    # bf16 adjustment halves only the f32 entries
    f32_part = got["all-gather"] + got["reduce-scatter"] + \
        got["collective-permute"]
    assert got["total_bf16adj"] == got["total"] - f32_part // 2


def test_accounting_covers_all_archs():
    for arch in registry.ARCHS:
        cfg = registry.get_config(arch)
        for shape_name in ("train_4k", "prefill_32k", "decode_32k"):
            from repro.configs.base import SHAPES
            acc = flops_mod.accounting(cfg, SHAPES[shape_name], 256,
                                       registry.get_optimizer(arch))
            assert acc.step_flops_global > 0, (arch, shape_name)
            assert acc.model_flops > 0
            assert acc.params > 1e8
