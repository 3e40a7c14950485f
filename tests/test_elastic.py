"""Elastic training subsystem: global-batch-invariant accumulation, churn
controller decisions, accum-equivalence of the train step, the thin train
launcher (degenerate 1-node cluster, crash auto-resume), and the end-to-end
self-healing churn run (subprocess: needs 8 forced host devices)."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.configs.base import OptimizerConfig, ShapeConfig
from repro.core.elastic import rescale_plan
from repro.core.orchestrator import Cluster
from repro.elastic import ChurnController, batch_plan
from repro.launch.mesh import single_device_mesh

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------------------- batch plans

def test_batch_plan_keeps_global_batch_constant():
    per_replica = 16 // 4                      # sized for the (4, x) mesh
    for data in (4, 2, 1):
        bp = batch_plan(16, data, per_replica=per_replica)
        assert bp.microbatch * bp.accum_steps == 16
        assert bp.per_replica == per_replica
    assert batch_plan(16, 4, per_replica=4).accum_steps == 1
    assert batch_plan(16, 2, per_replica=4).accum_steps == 2
    assert batch_plan(16, 1, per_replica=4).accum_steps == 4


def test_batch_plan_no_bound_means_no_accum():
    assert batch_plan(32, 2).accum_steps == 1


def test_batch_plan_never_overshoots_memory_bound():
    """Divisibility snapping must step accumulation UP (smaller
    microbatches), never down past the per-replica budget."""
    bp = batch_plan(20, 1, per_replica=3)
    assert bp.per_replica <= 3 and bp.accum_steps == 10
    for g, d, pr in [(24, 4, 2), (12, 2, 5), (16, 1, 3)]:
        bp = batch_plan(g, d, per_replica=pr)
        assert bp.per_replica <= pr, (g, d, pr, bp)
        assert bp.microbatch * bp.accum_steps == g


def test_batch_plan_rejects_indivisible():
    with pytest.raises(ValueError, match="not divisible"):
        batch_plan(10, 4)


def test_rescale_plan_max_data_cap():
    plan = rescale_plan(("data", "model"), (1, 1), 8, max_data=1)
    assert plan.new_shape == (1, 1)
    plan = rescale_plan(("data", "model"), (4, 2), 8, max_data=2)
    assert plan.new_shape == (2, 2)


# -------------------------------------------------------------- controller

def test_controller_decides_shrink_and_grow():
    cluster = Cluster(devices=list(range(8)))
    ctl = ChurnController(cluster, axes=("data", "model"),
                          base_shape=(4, 2), global_batch=16)
    d0 = ctl.decide(None)
    assert d0.plan.new_shape == (4, 2) and d0.batch.accum_steps == 1
    # two nodes die: replanning shrinks data axis, doubles accumulation
    cluster.fail_node(6), cluster.fail_node(7)
    d1 = ctl.decide(None)
    assert d1.plan.new_shape == (2, 2) and d1.batch.accum_steps == 2
    assert d1.batch.microbatch * d1.batch.accum_steps == 16
    # while shrunk, no grow decision is volunteered
    assert ctl.decide(d1) is None
    # nodes rejoin: grow trigger fires
    cluster.join_node(6), cluster.join_node(7)
    d2 = ctl.decide(d1)
    assert d2 is not None and d2.plan.new_shape == (4, 2)
    assert d2.batch.accum_steps == 1
    # churn events were observed via the cluster watcher hook
    assert [e.kind for e in ctl.events] == ["fail", "fail", "join", "join"]


def test_controller_caps_growth_at_batch_divisibility():
    """Spare nodes must never grow the data axis past what the global batch
    can shard evenly (8 devices, batch 4 -> data axis capped at 4)."""
    cluster = Cluster(devices=list(range(8)))
    ctl = ChurnController(cluster, axes=("data", "model"),
                          base_shape=(1, 1), global_batch=4)
    d = ctl.decide(None)
    assert d.plan.new_shape == (4, 1)
    assert d.batch.microbatch % d.plan.new_shape[0] == 0


def test_controller_wait_for_capacity_times_out():
    cluster = Cluster(devices=list(range(2)))
    ctl = ChurnController(cluster, axes=("data", "model"),
                          base_shape=(1, 2), global_batch=4)
    cluster.fail_node(0)
    with pytest.raises(RuntimeError, match="model replica"):
        ctl.wait_for_capacity(timeout=0.2, poll=0.05)


# ------------------------------------------- accum equivalence (train step)

def test_accum_step_matches_full_batch_step():
    """One optimizer step with accum_steps=2 must match accum_steps=1 on the
    same global batch (grad averaging over equal microbatches == full-batch
    gradient) — the invariant elastic rescaling rests on."""
    from repro.runtime import steps as steps_mod
    from repro.models import params as pr
    from repro.optim import adamw

    cfg = registry.get_smoke("phi4-mini-3.8b")
    par = registry.get_parallel("phi4-mini-3.8b")
    shape = ShapeConfig("t", 32, 8, "train")
    mesh = single_device_mesh()
    batch = {"tokens": jnp.ones((8, 32), jnp.int32),
             "labels": jnp.arange(8 * 32, dtype=jnp.int32).reshape(8, 32) % 7}
    outs = {}
    for accum in (1, 2, 4):
        ocfg = OptimizerConfig(warmup_steps=2, decay_steps=100,
                               accum_steps=accum)
        bundle = steps_mod.build_train(cfg, par, ocfg, mesh, shape)
        assert bundle.accum_steps == accum
        mod = steps_mod._model_module(cfg)
        schema = mod.lm_schema(cfg)
        params = pr.init_params(schema, jax.random.key(0), cfg.param_dtype)
        opt = pr.init_params(adamw.opt_state_schema(schema, ocfg),
                             jax.random.key(1), "float32")
        with mesh:
            p, o, m = bundle.jit()(params, opt, batch)
        outs[accum] = (jax.device_get(m["loss"]),
                       np.asarray(jax.device_get(
                           jax.tree.leaves(p)[0]), dtype=np.float32))
    for accum in (2, 4):
        np.testing.assert_allclose(outs[accum][0], outs[1][0],
                                   rtol=2e-2, atol=2e-2)
        np.testing.assert_allclose(outs[accum][1], outs[1][1],
                                   rtol=5e-2, atol=5e-2)


def test_build_train_rejects_indivisible_accum():
    from repro.runtime import steps as steps_mod

    cfg = registry.get_smoke("phi4-mini-3.8b")
    par = registry.get_parallel("phi4-mini-3.8b")
    ocfg = OptimizerConfig(accum_steps=3)
    with pytest.raises(ValueError, match="accum_steps"):
        steps_mod.build_train(cfg, par, ocfg, single_device_mesh(),
                              ShapeConfig("t", 32, 8, "train"))


# ------------------------------------------------- launcher (thin wrapper)

def test_train_wrapper_degenerate_cluster(tmp_path):
    from repro.launch.train import train

    out = train("phi4-mini-3.8b", steps=6, seq=32, batch=4, smoke=True,
                ckpt_dir=str(tmp_path / "ck"), ckpt_every=2, log_every=3)
    assert len(out["losses"]) == 6
    assert out["params"] is not None
    rep = out["report"]
    assert rep.global_batch_constant
    assert [s.outcome for s in rep.segments] == ["done"]


def test_train_wrapper_self_heals_injected_crash(tmp_path):
    """--fail-at crashes once mid-run; the supervisor restores from the
    latest checkpoint and finishes IN THE SAME CALL (seed: raised)."""
    from repro.launch.train import train

    out = train("phi4-mini-3.8b", steps=8, seq=32, batch=4, smoke=True,
                ckpt_dir=str(tmp_path / "ck"), ckpt_every=2, fail_at=5,
                log_every=4)
    assert len(out["losses"]) == 8               # every step accounted for
    outcomes = [s.outcome for s in out["report"].segments]
    assert outcomes[0] == "error" and outcomes[-1] == "done"


def test_trainer_unschedulable_is_bounded(tmp_path):
    """A persistently unschedulable segment (pre-created namespace with a
    too-small quota) must error out after rejoin_timeout_s, not retry
    forever."""
    from repro.elastic import ElasticTrainer, ElasticTrainSpec

    cfg = registry.get_smoke("phi4-mini-3.8b")
    par = registry.get_parallel("phi4-mini-3.8b")
    cluster = Cluster(devices=jax.devices())
    cluster.create_namespace("elastic", device_quota=0)
    spec = ElasticTrainSpec(cfg, par, OptimizerConfig(), steps=4, seq_len=32,
                            global_batch=4, base_shape=(1, 1), max_data=1,
                            rejoin_timeout_s=0.5, verbose=False)
    trainer = ElasticTrainer(cluster, spec)
    with pytest.raises(RuntimeError, match="unschedulable"):
        trainer.run()


def test_trainer_xla_error_fails_on_first_attempt(monkeypatch):
    """A compile failure or device OOM (an XLA runtime error) repeats on
    every retry: it surfaces at once, not after backoff_limit retries."""
    from repro.elastic import ElasticTrainer, ElasticTrainSpec

    attempts = []

    def oom(self, ctx, plan, bplan, graceful):
        attempts.append(plan)
        raise jax.errors.JaxRuntimeError("RESOURCE_EXHAUSTED: hbm")

    monkeypatch.setattr(ElasticTrainer, "_train_segment", oom)
    cfg = registry.get_smoke("phi4-mini-3.8b")
    par = registry.get_parallel("phi4-mini-3.8b")
    spec = ElasticTrainSpec(cfg, par, OptimizerConfig(), steps=4, seq_len=32,
                            global_batch=4, base_shape=(1, 1), max_data=1,
                            backoff_limit=2, verbose=False)
    trainer = ElasticTrainer(Cluster(devices=jax.devices()), spec)
    with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
        trainer.run()
    assert len(attempts) == 1


# ---------------------------------------------------- end-to-end churn run

@pytest.mark.slow
def test_elastic_trainer_survives_churn_e2e():
    """The acceptance scenario: 8 forced host devices, 2 killed mid-run,
    rejoin later — run continues from the latest checkpoint on the reshaped
    mesh with the global batch invariant.  Subprocess because the device
    count is an XLA flag fixed at jax init."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src") + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "examples",
                                      "elastic_failover.py"), "--fast"],
        env=env, capture_output=True, text=True, timeout=540)
    assert out.returncode == 0, f"\n{out.stdout}\n{out.stderr}"
    assert "CHURN_REPORT" in out.stdout
    assert "OK: self-healed" in out.stdout
