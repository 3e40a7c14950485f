"""Per-architecture smoke tests: instantiate a REDUCED config of the same
family and run one train step + one prefill + one decode step on CPU,
asserting output shapes and finiteness (no NaNs)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.configs.base import OptimizerConfig, ShapeConfig
from repro.launch.mesh import single_device_mesh
from repro.models import params as pr
from repro.optim import adamw
from repro.runtime import steps as steps_mod

ARCHS = registry.ARCHS

B, S = 2, 32


def _build(arch):
    cfg = registry.get_smoke(arch)
    par = registry.get_parallel(arch)
    ocfg = OptimizerConfig(warmup_steps=2, decay_steps=10,
                           moment_dtype=registry.get_optimizer(arch).moment_dtype,
                           second_moment=registry.get_optimizer(arch).second_moment)
    mesh = single_device_mesh()
    return cfg, par, ocfg, mesh


def _init(cfg, mod, ocfg):
    schema = mod.lm_schema(cfg)
    params = pr.init_params(schema, jax.random.key(0), cfg.param_dtype)
    opt = pr.init_params(adamw.opt_state_schema(schema, ocfg),
                         jax.random.key(1), "float32")
    return params, opt


def _batch(cfg, shape):
    rng = np.random.RandomState(0)
    T = steps_mod.token_len(cfg, shape)
    batch = {
        "tokens": jnp.asarray(rng.randint(0, cfg.vocab_size, (B, T)), jnp.int32),
        "labels": jnp.asarray(rng.randint(0, cfg.vocab_size, (B, T)), jnp.int32),
    }
    ex_abs, _ = steps_mod.extras_specs(cfg, B)
    if ex_abs:
        batch["extras"] = {k: jnp.asarray(rng.randn(*v.shape), v.dtype)
                           for k, v in ex_abs.items()}
    return batch


@pytest.mark.parametrize("arch", ARCHS)
def test_train_step(arch):
    cfg, par, ocfg, mesh = _build(arch)
    shape = ShapeConfig("t", S, B, "train")
    cfg = steps_mod.resolve_cfg(cfg, shape)
    bundle = steps_mod.build_train(cfg, par, ocfg, mesh, shape)
    mod = steps_mod._model_module(cfg)
    params, opt = _init(cfg, mod, ocfg)
    batch = _batch(cfg, shape)
    with mesh:
        new_params, new_opt, metrics = bundle.jit()(params, opt, batch)
    loss = float(metrics["loss"])
    assert np.isfinite(loss), f"{arch}: loss={loss}"
    assert np.isfinite(float(metrics["grad_norm"]))
    # params actually changed
    before = jax.tree.leaves(params)[0]
    after = jax.tree.leaves(new_params)[0]
    assert after.shape == before.shape


@pytest.mark.parametrize("arch", ARCHS)
def test_prefill_then_decode(arch):
    cfg, par, ocfg, mesh = _build(arch)
    shape = ShapeConfig("p", S, B, "prefill")
    cfg = steps_mod.resolve_cfg(cfg, shape)
    mod = steps_mod._model_module(cfg)
    params, _ = _init(cfg, mod, ocfg)
    batch = _batch(cfg, shape)
    pb = steps_mod.build_prefill(cfg, par, mesh, shape)
    with mesh:
        args = (params, batch["tokens"]) + ((batch["extras"],)
                                            if "extras" in batch else ())
        last, caches, _ = pb.jit()(*args)
    assert last.shape == (B, cfg.vocab_size)
    assert np.isfinite(np.asarray(last, np.float32)).all(), arch

    db = steps_mod.build_decode(cfg, par, mesh,
                                ShapeConfig("d", S, B, "decode"))
    tok = jnp.argmax(last, -1).astype(jnp.int32)[:, None]
    T = steps_mod.token_len(cfg, shape)
    with mesh:
        nxt, caches2 = db.jit()(params, caches, tok, jnp.int32(T - 1))
    assert nxt.shape == (B, 1)
    assert (np.asarray(nxt) >= 0).all() and (np.asarray(nxt) < cfg.vocab_size).all()
