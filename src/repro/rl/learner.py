"""The RL learner — elastic policy-gradient training off the rollout queue.

One :class:`RLLearner` drains trajectory batches from the
:class:`~repro.rl.replay.RolloutQueue` (lease + heartbeat, staleness
filter applied at the queue), encodes them into advantage-weighted LM
batches, and dispatches fused chunks through
``runtime.steps.build_rl_train_chunk`` — the same device-resident
``lax.scan`` hot loop (donated carry, (K,)-stacked metrics, AdamW) the
supervised trainer runs, with the policy-gradient loss swapped in.

Elasticity mirrors ``repro.elastic.ElasticTrainer``'s segment contract:

  * periodic checkpoints every ``ckpt_every`` steps (snapped up to chunk
    granularity) carry (params, opt) plus the rollout queue snapshot and
    the current policy version in ``extra``;
  * ``run()`` is ONE resumable segment: restore-or-init, train until
    done / preempted / crashed.  Under a tenant it IS the preemptible
    pod body — the fair-share scheduler's checkpoint-then-evict sets
    ``should_stop``, the segment goodbye-saves and returns, the whole
    job requeues, and the next placement restores and continues;
  * ``run_supervised()`` adds the crash loop: an injected hard failure
    (``fail_at``, no goodbye save) loses at most the steps since the
    last periodic checkpoint — ``steps_lost <= ckpt_every`` is the
    acceptance bound, accounted in :class:`RLRunReport`;
  * every ``broadcast_every`` steps the learner publishes a new weight
    version through the :class:`~repro.rl.weights.PolicyStore` — the
    actors' pull-on-bump broadcast.
"""
from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

import jax
import numpy as np

from repro.checkpoint.checkpoint import Checkpointer
from repro.configs.base import (ModelConfig, OptimizerConfig, ParallelConfig,
                                ShapeConfig)
from repro.elastic.trainer import chunk_schedule, snap_cadence
from repro.models import params as pr
from repro.optim import adamw
from repro.rl.replay import RolloutQueue, Trajectory
from repro.rl.weights import PolicyStore
from repro.runtime import steps as steps_mod


class InjectedLearnerFailure(RuntimeError):
    """The deterministic hard-crash used by tests and examples: raised
    AFTER a step completes, WITHOUT a goodbye save, so the resume path
    pays the real restore-from-periodic-checkpoint cost."""


@dataclass
class RLLearnerSpec:
    cfg: ModelConfig
    par: ParallelConfig
    ocfg: OptimizerConfig
    steps: int
    seq_len: int                 # prompt_pad + max_new_tokens (S)
    batch: int                   # trajectories per optimizer step (B)
    device_steps: int = 1        # optimizer steps fused per dispatch (K)
    ckpt_every: int = 2
    broadcast_every: int = 2
    max_policy_lag: int = 2
    seed: int = 0
    keep: int = 3
    fail_at: int = -1            # inject ONE hard crash after this step
    drain_poll_s: float = 2e-3
    drain_timeout_s: float = 300.0


@dataclass
class RLRunReport:
    steps: int = 0
    steps_done: int = 0          # completed optimizer steps (monotone)
    steps_lost: int = 0          # re-executed after crash/preempt resumes
    recoveries: int = 0          # crash resumes
    preemptions: int = 0         # cooperative (goodbye-saved) stops
    publishes: int = 0
    final_version: int = 0
    host_syncs: int = 0
    losses: List[float] = field(default_factory=list)
    segments: List[Dict[str, Any]] = field(default_factory=list)

    @property
    def completed(self) -> bool:
        return self.steps_done >= self.steps > 0


class RLLearner:
    """Drain -> encode -> fused chunk step -> publish/checkpoint loop."""

    def __init__(self, spec: RLLearnerSpec, rollouts: RolloutQueue,
                 policies: PolicyStore, *, store, registry=None,
                 name: str = "learner", mesh=None):
        self.spec = spec
        self.rollouts = rollouts
        self.policies = policies
        self.metrics = registry
        self.name = name
        if mesh is None:
            from repro.launch.mesh import single_device_mesh
            mesh = single_device_mesh()
        self.mesh = mesh
        self.ckpt = Checkpointer(store, prefix=f"rl/{name}", keep=spec.keep)
        self.report = RLRunReport(steps=spec.steps)
        self.version = 0
        self._failed_once = False
        shape = ShapeConfig("rl", spec.seq_len, spec.batch, "train")
        self._shape = shape
        self._bundles: Dict[int, Any] = {}
        self._fns: Dict[int, Any] = {}
        mod = steps_mod._model_module(spec.cfg)
        self._schema = mod.lm_schema(steps_mod.resolve_cfg(spec.cfg, shape))
        self._opt_schema = adamw.opt_state_schema(self._schema, spec.ocfg)

    # ------------------------------------------------------------- jit pieces
    def _bundle(self, length: int):
        if length not in self._bundles:
            self._bundles[length] = steps_mod.build_rl_train_chunk(
                self.spec.cfg, self.spec.par, self.spec.ocfg, self.mesh,
                self._shape, length)
        return self._bundles[length]

    def _fn(self, length: int):
        if length not in self._fns:
            self._fns[length] = self._bundle(length).jit()
        return self._fns[length]

    def _abstract(self):
        return {"params": pr.abstract_params(self._schema,
                                             self.spec.cfg.param_dtype),
                "opt": pr.abstract_params(self._opt_schema, "float32")}

    def _shardings(self):
        b = self._bundle(max(self.spec.device_steps, 1))
        return {"params": b.in_shardings[0], "opt": b.in_shardings[1]}

    def _init_state(self):
        b = self._bundle(max(self.spec.device_steps, 1))
        with self.mesh:
            params = jax.jit(
                lambda k: pr.init_params(self._schema, k,
                                         self.spec.cfg.param_dtype),
                out_shardings=b.in_shardings[0])(
                    jax.random.key(self.spec.seed))
            opt = jax.jit(
                lambda k: pr.init_params(self._opt_schema, k, "float32"),
                out_shardings=b.in_shardings[1])(
                    jax.random.key(self.spec.seed + 1))
        return params, opt

    # ----------------------------------------------------------------- encode
    def encode(self, trajs: List[Trajectory]) -> Dict[str, np.ndarray]:
        """One optimizer-step batch from B trajectories.

        Row i is prompt+generation left-aligned in S positions;
        ``labels[j] = seq[j+1]`` (next-token), ``mask[j] = 1`` iff the
        label at j is a *generated* token — prompt and pad positions
        carry zero weight and therefore zero gradient.  Advantages are
        batch-normalized rewards (REINFORCE with a mean baseline)."""
        S = self.spec.seq_len
        B = len(trajs)
        tokens = np.zeros((B, S), np.int32)
        labels = np.zeros((B, S), np.int32)
        mask = np.zeros((B, S), np.float32)
        rew = np.array([t.reward for t in trajs], np.float32)
        for i, t in enumerate(trajs):
            seq = (list(t.prompt) + list(t.tokens))[:S + 1]
            L = len(seq)
            tokens[i, :L - 1] = seq[:-1]
            labels[i, :L - 1] = seq[1:]
            lo, hi = max(len(t.prompt) - 1, 0), L - 1
            mask[i, lo:hi] = 1.0
        adv = (rew - rew.mean()) / (rew.std() + 1e-6)
        return {"tokens": tokens, "labels": labels, "mask": mask,
                "advantages": adv.astype(np.float32)}

    # ------------------------------------------------------------------ drain
    def _drain(self, n: int, should_stop) -> Optional[List]:
        """Lease n fresh trajectories (heartbeating held leases while
        waiting); None if preempted mid-drain (held leases released)."""
        held: List = []
        deadline = time.monotonic() + self.spec.drain_timeout_s
        while len(held) < n:
            if should_stop is not None and should_stop():
                self.rollouts.release(held, worker=self.name)
                return None
            got = self.rollouts.take_fresh(
                n - len(held), worker=self.name,
                current_version=self.version,
                max_policy_lag=self.spec.max_policy_lag)
            held.extend(got)
            self.rollouts.renew(held, worker=self.name)
            if len(held) < n:
                if time.monotonic() > deadline:
                    self.rollouts.release(held, worker=self.name)
                    raise RuntimeError(
                        f"learner starved: {len(held)}/{n} trajectories "
                        f"after {self.spec.drain_timeout_s}s (actors dead?)")
                time.sleep(self.spec.drain_poll_s)
        return held

    # -------------------------------------------------------------- segments
    def run(self, should_stop=None) -> Dict[str, Any]:
        """One resumable segment (the preemptible pod body).  Returns
        {"done": bool, "preempted": bool, "step": last_completed}."""
        spec = self.spec
        K = max(spec.device_steps, 1)
        eff_ckpt = snap_cadence(spec.ckpt_every, K)
        eff_pub = snap_cadence(spec.broadcast_every, K)
        shardings = self._shardings()
        restored, meta = self.ckpt.restore_latest(self._abstract(), shardings)
        if restored is not None:
            params, opt = restored["params"], restored["opt"]
            start = int(meta["step"]) + 1
            self.version = int(meta.get("version", self.version))
            lost = max(0, self.report.steps_done - start)
            self.report.steps_lost += lost
        else:
            params, opt = self._init_state()
            start = 0
        seg = {"start": start, "end": start - 1, "outcome": "running"}
        self.report.segments.append(seg)

        def finish(outcome: str, step: int, *, goodbye: bool):
            seg["outcome"], seg["end"] = outcome, step
            if goodbye and step >= start:
                self.ckpt.wait()
                self.ckpt.save(step, {"params": params, "opt": opt},
                               extra=self._extra())
            self.ckpt.wait()
            return {"done": outcome == "done", "preempted":
                    outcome == "preempted", "step": step}

        step = start - 1
        with self.mesh:
            for c_start, length in chunk_schedule(start, spec.steps, K):
                if should_stop is not None and should_stop():
                    self.report.preemptions += 1
                    return finish("preempted", step, goodbye=True)
                held = self._drain(length * spec.batch, should_stop)
                if held is None:
                    self.report.preemptions += 1
                    return finish("preempted", step, goodbye=True)
                batches = [self.encode([t for _, t in
                                        held[i * spec.batch:
                                             (i + 1) * spec.batch]])
                           for i in range(length)]
                stacked = {k: np.stack([b[k] for b in batches])
                           for k in batches[0]}
                params, opt, ms = self._fn(length)(params, opt, stacked)
                losses = np.asarray(ms["loss"])      # one sync per chunk
                self.report.host_syncs += 1
                self.report.losses.extend(float(x) for x in losses)
                self.rollouts.ack_trained(held, worker=self.name,
                                          current_version=self.version)
                step = c_start + length - 1
                self.report.steps_done = max(self.report.steps_done, step + 1)
                if self.metrics is not None:
                    self.metrics.gauge("rl/learner_step", step)
                    self.metrics.gauge("rl/loss", float(losses[-1]))
                done = step + 1
                if eff_pub and done % eff_pub == 0 and done < spec.steps:
                    self.version += 1
                    self.policies.publish(self.version, params, step=done)
                    self.report.publishes += 1
                if eff_ckpt and done % eff_ckpt == 0:
                    self.ckpt.save_async(
                        step, {"params": params, "opt": opt},
                        extra=self._extra())
                if (spec.fail_at >= 0 and step >= spec.fail_at
                        and not self._failed_once):
                    self._failed_once = True
                    seg["outcome"], seg["end"] = "failed", step
                    self.ckpt.wait()     # periodic save may be in flight
                    raise InjectedLearnerFailure(
                        f"injected learner crash after step {step}")
        # final weights always published so actors converge on the last
        # version even when steps % broadcast_every != 0
        self.version += 1
        self.policies.publish(self.version, params, step=spec.steps)
        self.report.publishes += 1
        self.report.final_version = self.version
        self._params = params
        return finish("done", step, goodbye=True)

    def _extra(self) -> dict:
        return {"version": self.version,
                "steps_done": self.report.steps_done,
                "queue": self.rollouts.snapshot()}

    def run_supervised(self, should_stop=None, *,
                       max_failures: int = 3) -> Dict[str, Any]:
        """The crash loop: resume through injected hard failures until
        the segment completes or is cooperatively preempted."""
        failures = 0
        while True:
            try:
                out = self.run(should_stop)
            except InjectedLearnerFailure:
                failures += 1
                self.report.recoveries += 1
                if failures > max_failures:
                    raise
                continue
            return out
