"""ElasticTrainer — training as a supervised, self-healing Cluster Job.

The paper's §V contract ("nodes can join and leave the cluster at any time
... pods will be rescheduled ... re-spawn them if any errors occur") applied
to SPMD training, with no human in the loop:

    +-------------------- ElasticTrainer.run() ---------------------+
    |  ChurnController.wait_for_capacity()                          |
    |        |                                                      |
    |        v            submit(JobSpec(segment))                  |
    |  Decision(plan, batch) ------------------> Cluster pod        |
    |        ^                                     |                |
    |        |   supervise: poll pod + decide()    |  train steps   |
    |        |     - node joined & bigger mesh     |  ckpt every k  |
    |        |       -> graceful preempt (save)    |                |
    |        |     - fail_node drained the pod     |                |
    |        |       -> pod FAILED, lease freed    |                |
    |        +---- restore latest ckpt onto the ---+                |
    |              NEW mesh, accum rescaled so                      |
    |              batch x accum stays constant                     |
    +---------------------------------------------------------------+

Each *segment* is one pod: it builds the mesh from its leased devices,
restores the newest checkpoint onto the new shardings (the checkpointer is
mesh-agnostic), and steps until it finishes, is preempted (scale-up), or is
drained (node failure).  The data pipeline is stateless (batch i is a pure
function of the seed), so a restored segment re-sees exactly the batches the
lost one saw — the optimizer trajectory is identical across any churn
schedule, modulo re-executed steps since the last checkpoint (measured as
``steps_lost`` in the run report).
"""
from __future__ import annotations

import dataclasses
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import jax

from repro.checkpoint.checkpoint import Checkpointer
from repro.configs.base import (ModelConfig, OptimizerConfig, ParallelConfig,
                                ShapeConfig)
from repro.core.elastic import make_elastic_mesh
from repro.core.metrics import Registry
from repro.core.orchestrator import Cluster, JobSpec, Pod, PodState
from repro.data.objectstore import ObjectStore
from repro.data.tokens import ChunkPrefetcher, TokenPipeline
from repro.elastic.batch import BatchPlan
from repro.elastic.controller import ChurnController, Decision
from repro.models import params as pr
from repro.optim import adamw
from repro.runtime import steps as steps_mod


@dataclass
class ElasticTrainSpec:
    cfg: ModelConfig
    par: ParallelConfig
    ocfg: OptimizerConfig
    steps: int
    seq_len: int = 64
    global_batch: int = 16
    mesh_axes: Tuple[str, ...] = ("data", "model")
    base_shape: Tuple[int, ...] = (1, 1)   # preferred full-cluster mesh
    max_data: Optional[int] = None         # cap the data axis (launchers)
    name: str = "elastic-train"
    namespace: str = "elastic"
    ckpt_every: int = 5                    # periodic async saves (durability)
    keep: Optional[int] = 3
    log_every: int = 10
    # Device-resident hot loop: optimizer steps fused into ONE dispatch
    # (lax.scan with on-device carry — runtime.steps.build_train_chunk).
    # Host syncs per step drop to O(1/device_steps); the cost is that
    # should_stop/fail/preemption are only observed at chunk boundaries,
    # so preemption latency is bounded by one chunk.  ckpt_every and
    # log_every are snapped UP to multiples of device_steps.
    device_steps: int = 1
    prefetch_depth: int = 2                # chunks in flight beyond current
    seed: int = 0
    data_seed: int = 17
    fail_at: int = -1                      # inject ONE crash at this step
    # non-churn failures tolerated; XLA errors (compile, OOM) never retry
    backoff_limit: int = 2
    # A drained pod's node is "dead": by default it does NOT write a final
    # checkpoint (recovery cost = steps since the last periodic save, the
    # honest number).  Graceful scale-up preemptions always save.
    save_on_drain: bool = False
    rejoin_timeout_s: float = 60.0
    poll_s: float = 0.02
    join_timeout_s: float = 120.0
    verbose: bool = True


@dataclass
class SegmentRecord:
    index: int
    start: int
    end: int                  # last executed step (start-1 if none ran)
    mesh_shape: Tuple[int, ...]
    accum_steps: int
    microbatch: int
    global_batch: int
    wall_s: float
    outcome: str              # done | preempted | node-failure | error
    # seconds from segment start to the FIRST chunk's results being ready
    # (restore + compile + first dispatch): the preemption-restart latency
    # a rescale pays before producing anything
    t_first_s: float = 0.0

    @property
    def steps_run(self) -> int:
        return max(0, self.end - self.start + 1)


@dataclass
class ElasticRunReport:
    global_batch: int = 0
    seq_len: int = 0
    steps: int = 0
    segments: List[SegmentRecord] = field(default_factory=list)
    recoveries: int = 0               # node-churn induced restarts
    steps_lost: int = 0               # re-executed since last checkpoint
    recovery_s: List[float] = field(default_factory=list)
    total_wall_s: float = 0.0
    # host round-trips during training: one per chunk dispatch + one per
    # loss flush / first-chunk latency probe.  The hot-loop win the bench
    # trajectory tracks: per-step dispatch is O(steps), chunked dispatch
    # is O(steps / device_steps).
    host_syncs: int = 0

    @property
    def tokens_executed(self) -> int:
        return sum(s.steps_run for s in self.segments) * \
            self.global_batch * self.seq_len

    @property
    def tokens_useful(self) -> int:
        return self.steps * self.global_batch * self.seq_len

    @property
    def tokens_per_s(self) -> float:
        """Useful tokens/s: the trained run's throughput including every
        recovery cost (restore, recompile, re-executed steps)."""
        return self.tokens_useful / max(self.total_wall_s, 1e-9)

    @property
    def steps_executed(self) -> int:
        return sum(s.steps_run for s in self.segments)

    @property
    def host_syncs_per_step(self) -> float:
        return self.host_syncs / max(self.steps_executed, 1)

    @property
    def t_first_s(self) -> float:
        """Time-to-first-step of the run: restore + compile + first
        dispatch of the FIRST segment (later segments' t_first_s measure
        per-recovery restart latency instead)."""
        return self.segments[0].t_first_s if self.segments else 0.0

    @property
    def global_batch_constant(self) -> bool:
        return all(s.global_batch == self.global_batch and
                   s.microbatch * s.accum_steps == self.global_batch
                   for s in self.segments)

    def to_json(self) -> Dict[str, Any]:
        return {
            "steps": self.steps,
            "global_batch": self.global_batch,
            "seq_len": self.seq_len,
            "segments": [dataclasses.asdict(s) for s in self.segments],
            "recoveries": self.recoveries,
            "steps_lost": self.steps_lost,
            "recovery_s": [round(r, 3) for r in self.recovery_s],
            "total_wall_s": round(self.total_wall_s, 3),
            "tokens_per_s": round(self.tokens_per_s, 1),
            "tokens_executed": self.tokens_executed,
            "global_batch_constant": self.global_batch_constant,
            "host_syncs": self.host_syncs,
            "host_syncs_per_step": round(self.host_syncs_per_step, 4),
            "t_first_s": round(self.t_first_s, 3),
        }


class UnschedulableError(RuntimeError):
    """A segment's submit was rejected (stale plan, quota, no devices) —
    retryable by replanning, unlike other trainer RuntimeErrors."""


@dataclass
class _SegmentResult:
    start: int
    last: int                 # last executed step (start-1 if none)
    done: bool
    preempted: bool
    # perf_counter after the first CHUNK's results are ready.  One block,
    # once: blocking per step inside a chunk would serialize the scanned
    # dispatch, and blocking a second time would double-count the compile
    # that the first dispatch already paid.
    t_first_done: Optional[float]
    wall_s: float
    host_syncs: int = 0
    t_first_s: float = 0.0    # t_first_done relative to segment start


def snap_cadence(every: int, device_steps: int) -> int:
    """Snap a per-step cadence UP to chunk granularity (0 = off stays off).
    Checkpoint/log actions only happen at chunk boundaries, so the
    effective cadence is the smallest multiple of ``device_steps`` >= the
    requested one."""
    if not every:
        return 0
    k = max(device_steps, 1)
    return ((every + k - 1) // k) * k


def chunk_schedule(start: int, steps: int, device_steps: int):
    """Chunks covering [start, steps), aligned to the ABSOLUTE step grid
    (boundaries at multiples of device_steps from step 0), so snapped
    cadences fire exactly on boundaries no matter where a restore lands.
    First/last chunks may be partial."""
    k = max(device_steps, 1)
    out, i = [], start
    while i < steps:
        bound = min(steps, (i // k + 1) * k)
        out.append((i, bound - i))
        i = bound
    return out


# chunk-cadence helpers are shared with the RL learner (repro.rl.learner
# rides the same device-resident hot loop); the old private names remain
# for in-module callers
_snap = snap_cadence
_chunk_schedule = chunk_schedule


class ElasticTrainer:
    """Supervised elastic training on a Cluster.  See module docstring."""

    def __init__(self, cluster: Cluster, spec: ElasticTrainSpec, *,
                 store: Optional[ObjectStore] = None,
                 metrics: Optional[Registry] = None,
                 report: Optional[ElasticRunReport] = None,
                 stop: Optional[threading.Event] = None):
        self.cluster = cluster
        self.spec = spec
        # cooperative cancel (repro.api Handle.cancel): when set, the
        # supervisor preempt-drains the live segment (which checkpoints on
        # the way out — the hardware is healthy) and run() returns the
        # partial result instead of resubmitting
        self._stop = stop or threading.Event()
        self._ephemeral_store = store is None
        if store is None:
            import tempfile
            store = ObjectStore(tempfile.mkdtemp(prefix="elastic-ckpt-"))
        self.store = store
        self.ckpt = Checkpointer(store, keep=spec.keep)
        self.metrics = metrics or cluster.metrics
        self.controller = ChurnController(
            cluster, axes=spec.mesh_axes, base_shape=spec.base_shape,
            global_batch=spec.global_batch, max_data=spec.max_data)
        # a caller-provided report continues a run that escalated off a
        # dead cluster (repro.fabric cross-site failover): segments, losses
        # lost and wall time keep accumulating across sites
        self.report = report or ElasticRunReport(
            global_batch=spec.global_batch, seq_len=spec.seq_len,
            steps=spec.steps)
        self.shape = ShapeConfig("elastic", spec.seq_len, spec.global_batch,
                                 "train")
        self.cfg = steps_mod.resolve_cfg(spec.cfg, self.shape)
        mod = steps_mod._model_module(self.cfg)
        self.schema = mod.lm_schema(self.cfg)
        self.opt_schema = adamw.opt_state_schema(self.schema, spec.ocfg)
        self.progress = -1                # last completed step, any segment
        self._seg_start = 0               # current segment's restore point
        self._seg_last = -1               # current segment's last step
        self._losses: Dict[int, float] = {}     # step -> loss (host)
        self._injected = False
        self._fatal: Optional[BaseException] = None
        self._final: Dict[str, Any] = {}

    # ------------------------------------------------------------- segments
    def _abstract(self):
        return {"params": pr.abstract_params(self.schema,
                                             self.cfg.param_dtype),
                "opt": pr.abstract_params(self.opt_schema, "float32")}

    def _train_segment(self, ctx, plan, bplan: BatchPlan,
                       graceful: threading.Event) -> _SegmentResult:
        """One pod: mesh from leased devices, restore, dispatch CHUNKS of
        ``spec.device_steps`` optimizer steps, checkpoint at boundaries.

        The hot loop is device-resident: each dispatch scans device_steps
        optimizer steps with the (params, opt) carry never leaving the
        device, chunk k+1's batches are prefetched + device_put by a
        background thread while chunk k executes, and the host only
        syncs (loss flush, checkpoint, log, stop/fail checks) at chunk
        boundaries — so preemption latency is bounded by one chunk."""
        spec = self.spec
        t0 = time.perf_counter()
        mesh = make_elastic_mesh(plan, ctx.devices)
        ocfg = dataclasses.replace(spec.ocfg, accum_steps=bplan.accum_steps)
        K = max(spec.device_steps, 1)
        bundle = steps_mod.build_train_chunk(self.cfg, spec.par, ocfg, mesh,
                                             self.shape, K)
        # the bundle's OWN shardings, not a recompute: build_train may flip
        # the layout (e.g. pure-FSDP train) and restore must land state
        # exactly where the jitted step expects it
        shardings = {"params": bundle.in_shardings[0],
                     "opt": bundle.in_shardings[1]}
        restored, meta = self.ckpt.restore_latest(self._abstract(), shardings)
        if restored is not None:
            params, opt = restored["params"], restored["opt"]
            start = int(meta["step"]) + 1
            saved_at = int(meta["step"])
        else:
            start, saved_at = 0, -1
        self._seg_start = start       # supervisor-visible even if we crash
        self._seg_last = start - 1    # this segment's own extent, not the
        # run-global progress: a crashed record must not inherit steps an
        # earlier segment executed
        if restored is None:
            with mesh:
                params = jax.jit(
                    lambda k: pr.init_params(self.schema, k,
                                             self.cfg.param_dtype),
                    out_shardings=shardings["params"])(
                        jax.random.key(spec.seed))
                opt = jax.jit(
                    lambda: pr.init_params(self.opt_schema,
                                           jax.random.key(spec.seed + 1),
                                           "float32"),
                    out_shardings=shardings["opt"])()

        # jitted chunk fns cached by chunk length: the steady-state K
        # chunk plus (at most) a shorter head chunk after an unaligned
        # restore and a tail chunk when K doesn't divide spec.steps
        chunk_fns = {K: bundle.jit()}

        def chunk_fn(k):
            if k not in chunk_fns:
                b = steps_mod.build_train_chunk(self.cfg, spec.par, ocfg,
                                                mesh, self.shape, k)
                chunk_fns[k] = b.jit()
            return chunk_fns[k]

        eff_ckpt = _snap(spec.ckpt_every, K)
        eff_log = _snap(spec.log_every, K)
        pipe = TokenPipeline(self.cfg.vocab_size, spec.seq_len,
                             spec.global_batch, seed=spec.data_seed)
        schedule = _chunk_schedule(start, spec.steps, K)
        last = start - 1
        t_first: Optional[float] = None
        preempted = False
        host_syncs = 0
        pending: Dict[int, Any] = {}    # on-device losses since last flush

        def flush_losses():
            # bulk host transfer at points that already sync (checkpoint
            # snapshots, log prints) — pending stays small, so long runs
            # never pin one device buffer per step
            nonlocal host_syncs
            if pending:
                self._losses.update(
                    {k: float(v)
                     for k, v in jax.device_get(pending).items()})
                pending.clear()
                host_syncs += 1

        prefetch = ChunkPrefetcher(pipe, schedule,
                                   sharding=bundle.in_shardings[2],
                                   depth=spec.prefetch_depth)
        try:
            with mesh:
                for cstart, k in schedule:
                    cend = cstart + k - 1
                    if ctx.should_stop():
                        preempted = True
                        break
                    if (cstart <= spec.fail_at <= cend
                            and not self._injected):
                        self._injected = True
                        raise RuntimeError(
                            f"injected failure at step {spec.fail_at}")
                    _, batches = prefetch.get()
                    params, opt, ms = chunk_fn(k)(params, opt, batches)
                    host_syncs += 1         # one dispatch per chunk
                    # losses stay ON DEVICE: a float() here would host-sync
                    # and serialize dispatch (a wash on the synchronous CPU
                    # backend, a real stall on async TPU/GPU dispatch); the
                    # host syncs only on the ckpt/log cadences below.
                    for j in range(k):
                        pending[cstart + j] = ms["loss"][j]
                    last = cend
                    self.progress = cend
                    self._seg_last = cend
                    if t_first is None:
                        jax.block_until_ready(ms["loss"])
                        host_syncs += 1
                        t_first = time.perf_counter()
                    if eff_ckpt and (cend + 1) % eff_ckpt == 0:
                        flush_losses()  # keeps the loss log >= the restore
                        self.ckpt.save_async(cend, {"params": params,
                                                    "opt": opt})
                        saved_at = cend
                    if eff_log and (cstart % eff_log == 0 or
                                    cend == spec.steps - 1):
                        flush_losses()      # includes this chunk's losses
                        loss = self._losses[cend]
                        self.metrics.gauge("elastic/loss", loss)
                        self.metrics.gauge("elastic/step", cend)
                        if spec.verbose:
                            print(f"[elastic] step {cend} loss {loss:.4f} "
                                  f"mesh {plan.new_shape} "
                                  f"accum {bplan.accum_steps}")
            flush_losses()
        finally:
            prefetch.close()
            # count even a crashed segment's round-trips: the report's
            # host_syncs is the run's honest total, failures included
            self.report.host_syncs += host_syncs
        self.ckpt.wait()
        done = (last == spec.steps - 1 and not preempted) or \
            start >= spec.steps
        # graceful preemptions (scale-up) always persist their last step;
        # drained pods only do so when the spec pretends the node survived.
        # A scheduler preemption (ctx.preempt — fair-share eviction via
        # Cluster.preempt_pod) is checkpoint-then-evict by contract: the
        # hardware is healthy, so the goodbye save always happens.
        # A COMPLETED run skips the terminal save when nobody could ever
        # read it (checkpointing off + trainer-owned throwaway store):
        # that save is a full host transfer of params+opt for nothing.
        want_final_save = (not preempted) or graceful.is_set() \
            or ctx.preempt.is_set() or spec.save_on_drain
        if done and self._ephemeral_store and not spec.ckpt_every:
            want_final_save = False
        if last >= start and saved_at != last and want_final_save:
            self.ckpt.save(last, {"params": params, "opt": opt})
        if done:
            self._final = {"params": params, "opt": opt}
        return _SegmentResult(start=start, last=last, done=done,
                              preempted=preempted, t_first_done=t_first,
                              wall_s=time.perf_counter() - t0,
                              host_syncs=host_syncs,
                              t_first_s=(t_first - t0)
                              if t_first is not None else 0.0)

    def _supervise(self, idx: int, decision: Decision) -> Pod:
        """Submit one segment Job and watch it + the cluster until it ends."""
        spec = self.spec
        graceful = threading.Event()
        plan, bplan = decision.plan, decision.batch

        def segment_fn(ctx):
            try:
                return self._train_segment(ctx, plan, bplan, graceful)
            except jax.errors.JaxRuntimeError as e:
                # a compile failure or device OOM repeats identically on
                # every retry: surface it on the first attempt
                self._fatal = e
                raise

        # a node can die between the capacity decision and this submit; the
        # stale plan then over-asks and the caller replans on the survivors
        try:
            job = self.cluster.submit(spec.namespace, JobSpec(
                name=f"{spec.name}-seg{idx}", fn=segment_fn, replicas=1,
                devices_per_pod=plan.devices_used,
                backoff_limit=0))   # respawn is OUR job, on a new mesh
        except RuntimeError as e:
            raise UnschedulableError(str(e)) from e
        pod = job.pods[0]
        while pod.state in (PodState.PENDING, PodState.RUNNING):
            time.sleep(spec.poll_s)
            if pod.ctx.stop.is_set() or pod.ctx.preempt.is_set():
                continue        # draining already — never grow a dying pod
            if self._stop.is_set():
                # external cancel: checkpoint-then-evict the segment
                # (ctx.preempt guarantees the goodbye save), and
                # _run_segments will NOT resubmit
                self.cluster.preempt_pod(pod, reason="stop requested")
                continue
            try:
                grow = self.controller.decide(decision)
            except RuntimeError:
                # total-loss churn mid-poll (fewer devices than one model
                # replica): no grow — the drain path ends this segment and
                # run()'s wait_for_capacity rides out the outage
                grow = None
            if grow is not None:
                # nodes rejoined and a larger mesh fits: preempt gracefully
                graceful.set()
                pod.ctx.stop.set()
        # the segment thread MUST be dead before the next segment starts:
        # two live segments would race on the shared Checkpointer and the
        # trainer's progress/loss state.  A drained thread exits at its next
        # step boundary (or after the in-flight compile), so keep waiting —
        # and if it truly wedges, fail loudly rather than corrupt the run.
        if pod.thread is not None:
            for _ in range(3):
                pod.thread.join(timeout=spec.join_timeout_s)
                if not pod.thread.is_alive():
                    break
                if spec.verbose:
                    print(f"[elastic] segment {idx}: waiting for the "
                          f"drained pod thread to exit...")
            if pod.thread.is_alive():
                raise RuntimeError(
                    f"segment {idx} thread did not exit within "
                    f"{3 * spec.join_timeout_s:.0f}s of its drain — "
                    f"refusing to start a concurrent segment")
        return pod

    # ----------------------------------------------------------------- stop
    def request_stop(self) -> None:
        """Cooperative cancel: the live segment is preempt-drained (it
        checkpoints and exits), no further segment is submitted, and
        ``run()`` returns the partial result."""
        self._stop.set()

    @property
    def stopped(self) -> bool:
        return self._stop.is_set()

    # ------------------------------------------------------------------ run
    def run(self) -> Dict[str, Any]:
        """Train to ``spec.steps`` across any node-churn schedule.

        Raises ``CapacityLostError`` (from the controller) when the whole
        cluster drops below one model replica for longer than the rejoin
        window — partial progress stays in the report/store so a
        federation supervisor can resume the job on another site."""
        spec = self.spec
        if spec.namespace not in self.cluster.namespaces:
            self.cluster.create_namespace(spec.namespace)
        t_run0 = time.perf_counter()
        try:
            self._run_segments(len(self.report.segments))
        finally:
            # wall time ACCUMULATES (not assigns): a job escalated across
            # sites keeps every site's seconds on its clock
            self.report.total_wall_s += time.perf_counter() - t_run0
        assert self.report.global_batch_constant, \
            "elastic invariant violated: global batch changed across meshes"
        if self._ephemeral_store and not self._stop.is_set():
            # trainer-owned throwaway checkpoint dir: don't leak /tmp space
            # run after run (kept on error paths — raises above — and on
            # cancel, so the goodbye checkpoint survives for a resume)
            import shutil
            shutil.rmtree(self.store.root, ignore_errors=True)
        losses = dict(self._losses)
        self.metrics.gauge("elastic/tokens_per_s", self.report.tokens_per_s)
        return {"losses": [losses[i] for i in sorted(losses)],
                "loss_by_step": losses,
                "params": self._final.get("params"),
                "opt": self._final.get("opt"),
                "report": self.report}

    def _run_segments(self, seg_idx: int) -> None:
        spec = self.spec
        failures = 0
        pending_lost_from: Optional[int] = None
        t_fail: Optional[float] = None
        done = False
        unsched_since: Optional[float] = None
        while not done:
            if self._stop.is_set():
                break           # cancelled: the last segment checkpointed
            decision = self.controller.wait_for_capacity(
                spec.rejoin_timeout_s)
            try:
                pod = self._supervise(seg_idx, decision)
            except UnschedulableError as e:  # decision went stale mid-churn
                now = time.monotonic()
                if unsched_since is None:
                    unsched_since = now
                elif now - unsched_since > spec.rejoin_timeout_s:
                    # not transient churn: e.g. a too-small pre-created
                    # namespace quota would otherwise retry forever
                    raise RuntimeError(
                        f"segment unschedulable for "
                        f"{spec.rejoin_timeout_s:.0f}s: {e}") from e
                if spec.verbose:
                    print(f"[elastic] segment {seg_idx} unschedulable "
                          f"({e}) -> replan")
                self.metrics.inc("elastic/replans")
                time.sleep(0.1)     # let the churn settle; never spin hot
                seg_idx += 1
                continue
            unsched_since = None
            res: Optional[_SegmentResult] = pod.result
            if res is not None and pending_lost_from is not None:
                # steps the failure forced us to re-execute
                self.report.steps_lost += max(
                    0, pending_lost_from - res.start + 1)
                if t_fail is not None and res.t_first_done is not None:
                    self.report.recovery_s.append(res.t_first_done - t_fail)
                pending_lost_from, t_fail = None, None
            if pod.state == PodState.FAILED:
                churn = pod.error is not None and "NodeFailure" in pod.error
                if churn:
                    self.report.recoveries += 1
                    self.metrics.inc("elastic/recoveries")
                    if spec.verbose:
                        print(f"[elastic] segment {seg_idx}: {pod.error!s}"
                              .splitlines()[0] + " -> rescale + restore")
                elif self._fatal is not None:
                    raise RuntimeError(
                        f"elastic training failed: {self._fatal}") \
                        from self._fatal
                else:
                    failures += 1
                    if failures > spec.backoff_limit:
                        raise RuntimeError(
                            f"elastic training failed after {failures} "
                            f"attempts: {pod.error}")
                    if spec.verbose:
                        print(f"[elastic] segment {seg_idx} failed "
                              f"(attempt {failures}/{spec.backoff_limit}) "
                              f"-> restore + retry")
                pending_lost_from = res.last if res is not None \
                    else self._seg_last
                t_fail = time.perf_counter()
                outcome = "node-failure" if churn else "error"
            elif res is not None and res.done:
                done = True
                outcome = "done"
            else:
                # graceful scale-up preempt OR a fair-share eviction
                # (Cluster.preempt_pod): both checkpointed; the eviction
                # resumes once the vcluster scheduler re-grants devices
                outcome = "preempted"
                if pod.state == PodState.PREEMPTED:
                    self.metrics.inc("elastic/preemptions")
                    if spec.verbose:
                        print(f"[elastic] segment {seg_idx} preempted "
                              f"({pod.error}) -> awaiting re-grant")
            # a crashed pod (res None) is still one segment of history:
            # reconstruct its extent from the trainer-side progress marks
            start = res.start if res is not None else self._seg_start
            end = res.last if res is not None \
                else max(start - 1, self._seg_last)
            self.report.segments.append(SegmentRecord(
                index=seg_idx, start=start, end=end,
                mesh_shape=tuple(decision.plan.new_shape),
                accum_steps=decision.batch.accum_steps,
                microbatch=decision.batch.microbatch,
                global_batch=decision.batch.global_batch,
                wall_s=res.wall_s if res is not None else 0.0,
                outcome=outcome,
                t_first_s=res.t_first_s if res is not None else 0.0))
            seg_idx += 1
