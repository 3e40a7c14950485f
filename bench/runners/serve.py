"""Serving cells: the continuous-batching engine a ``ServeJob`` declares,
driven by the mix's requests for the window, then checked against the
reference.

The engine is built as ``repro.api.runners.build_engine`` builds it
(paged block pool, prefix cache on), with the benchmark's weights, and
driven by ``ServingEngine.run`` in this process.  The loop is closed: it
keeps ``backlog_per_slot`` requests per slot waiting in the
``WorkQueue`` by putting the next request, due at once, whenever the
engine leases one (no polling thread competes with the engine for the
interpreter).  Spans around the engine's prefill and decode calls give
the tokens made inside the window and each step's active slots.

After the window the engine is dropped, and a sample of the finished
requests, drawn from the seed and holding the longest, is run through
the float32 reference: every served token must lie within the limit of
the reference's best logit at its position.
"""
from __future__ import annotations

import gc
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from bench import gen, weights
from bench.harness import Ctx, Spans, Tracer, device_info
from bench.model import program_config

WORKER = "bench-server"


def _engine(ctx: Ctx, params, registry):
    from repro.api import ServeJob
    from repro.configs import registry as archs
    from repro.launch.mesh import single_device_mesh
    from repro.serving import ServingEngine
    mix = ctx.mix
    job = ServeJob(name=ctx.workload, arch=ctx.cfg["arch"], smoke=False,
                   slots=int(mix["slots"]), prompt_len=int(mix["prompt_len"]),
                   max_new_tokens=int(mix["cache_len"] - mix["prompt_len"]),
                   seed=ctx.seed_for("weights"), warmup=True)
    engine = ServingEngine(
        program_config(ctx.cfg), archs.get_parallel(job.arch),
        single_device_mesh(), num_slots=job.slots, prompt_len=job.prompt_len,
        max_new_tokens=job.max_new_tokens, seed=job.seed, params=params,
        registry=registry, paged=job.paged, block_size=job.block_size,
        pool_blocks=job.pool_blocks, prefix_cache=job.prefix_cache)
    if not engine.paged:
        raise RuntimeError("the engine did not page its cache")
    return engine, job


def _timed_queue(lease_timeout: float):
    from repro.core.queue import WorkQueue

    class TimedQueue(WorkQueue):
        """The work queue, with the time of each acknowledgement.  Once
        ``closed`` it leases nothing more; ``refill``, when set, is called
        after each lease (the runner puts its next request there)."""

        def __init__(self, **kw):
            super().__init__(**kw)
            self.acked: Dict[int, float] = {}
            self.closed = False
            self.refill = None

        def lease(self, worker):
            if self.closed:
                return None
            got = super().lease(worker)
            if got is not None and self.refill is not None:
                self.refill()
            return got

        def ack(self, task_id, worker):
            ok = super().ack(task_id, worker)
            if ok:
                self.acked[task_id] = self._clock()
            return ok

    return TimedQueue(lease_timeout=lease_timeout)


def window(ctx: Ctx) -> Dict[str, Any]:
    """Set up, serve the window, and return what happened as plain data
    (the engine and its device state are gone when this returns)."""
    from repro.core.metrics import Registry
    from repro.serving.report import GAUGES
    mix, d = ctx.mix, ctx.dims
    clock = time.monotonic
    requests = gen.serve_requests(mix, d.vocab, ctx.seed)
    params = weights.make(d, ctx.seed_for("weights"))
    registry = Registry()
    engine, job = _engine(ctx, params, registry)
    del params
    with engine.mesh:
        engine.warmup()
    spans = Spans(clock)
    decode, prefill = engine.decode_step, engine.prefill_into

    def decode_step(tokens, positions):
        with spans.span("decode_step", [int(p) for p in positions]):
            return decode(tokens, positions)

    def prefill_into(slot, prompt):
        with spans.span("prefill", tuple(prompt)):
            return prefill(slot, prompt)

    engine.decode_step, engine.prefill_into = decode_step, prefill_into
    queue = _timed_queue(job.lease_timeout)
    backlog = int(mix["backlog_per_slot"]) * job.slots
    drain_s = float(mix["drain_s"])
    task_of: Dict[int, int] = {}        # request id -> queue task id
    t0, wall0 = clock(), time.time()
    t_end = t0 + ctx.seconds
    setup_s = t0 - ctx.t_start
    pending = iter(requests)

    def refill():
        # a request put for each one leased, from the engine's own
        # thread, so the backlog stays at its size
        r = next(pending, None)
        now = clock()
        if r is not None and now < t_end:
            item = {"id": r["id"], "prompt": r["prompt"],
                    "max_new_tokens": r["max_new_tokens"]}
            task_of[r["id"]] = queue.put(item, enqueued_at=now)

    cap = job.max_new_tokens
    want = {r["id"]: min(r["max_new_tokens"], cap) for r in requests}
    check_tokens = int(mix["check_tokens"])

    def should_stop():
        # after the window: admit no more, and finish enough of the
        # requests in flight to check
        now = clock()
        if now < t_end:
            return False
        if now >= t_end + drain_s:
            return True
        queue.closed = True
        done = sum(want[rid] for rid, tid in task_of.items()
                   if tid in queue.acked)
        return done >= check_tokens or queue.leased == 0

    tracer = None
    if ctx.trace:
        lead = ctx.seconds / 3
        tracer = Tracer(t0 + lead, t0 + lead + float(mix["trace_seconds"]),
                        clock)
    queue.refill = refill
    for _ in range(backlog):
        refill()
    try:
        results, _ = engine.run(queue, worker=WORKER,
                                default_max_new=job.max_new_tokens,
                                exit_on_drain=False, should_stop=should_stop)
    finally:
        if tracer is not None:
            tracer.cancel()
    t_stop = clock()
    trace_path = tracer.finish() if tracer is not None else None
    device = device_info(ctx.chips)
    counts = {name: registry.series(g).total for name, g in (
        ("prefix_hits", GAUGES.PREFIX_HITS), ("preempted", GAUGES.PREEMPTED),
        ("lease_lost", GAUGES.LEASE_LOST), ("stale_ack", GAUGES.STALE_ACK))}
    engine.decode_step = engine.prefill_into = None
    del engine, decode, prefill
    gc.collect()

    return {
        "kind": "serve", "setup_s": setup_s, "t0": t0, "t_end": t_end,
        "wall0": wall0, "t_stop": t_stop, "seconds": ctx.seconds,
        "requests_put": len(task_of), "requests_done": sum(
            1 for tid in task_of.values() if tid in queue.acked),
        "spans": spans, "results": results,
        "prompts": {r["id"]: r["prompt"] for r in requests},
        "counts": counts,
        "registry": registry, "device": device, "trace_path": trace_path,
        "tracer": tracer, "slots": job.slots, "dead": len(queue.dead),
    }


def sample(ctx: Ctx, rec: Dict[str, Any]) -> List[Any]:
    """Finished requests to check, drawn from the seed: the longest,
    then others until ``check_tokens`` served tokens are in."""
    done = [rid for rid, toks in rec["results"].items() if toks]
    if not done:
        return []
    longest = max(done, key=lambda rid: (len(rec["results"][rid]), -rid))
    rest = [rid for rid in done if rid != longest]
    order = gen.run_rng(ctx.seed, 7).permutation(len(rest))
    picked, n = [longest], len(rec["results"][longest])
    for i in order:
        if n >= int(ctx.mix["check_tokens"]):
            break
        picked.append(rest[i])
        n += len(rec["results"][rest[i]])
    return picked


def gaps(ctx: Ctx, rec: Dict[str, Any], rids, control: Optional[str] = None
         ) -> List[np.ndarray]:
    from bench.reference import dense_lm
    d = ctx.dims
    params = weights.make(d, ctx.seed_for("weights"))
    out = dense_lm.served_gaps(
        d, params, [rec["prompts"][r] for r in rids],
        [rec["results"][r] for r in rids], control)
    del params
    gc.collect()
    return out


def judge(ctx: Ctx, g: List[np.ndarray]
          ) -> Tuple[Dict[str, Dict[str, Any]], bool]:
    """Each number beside its limit, and whether all are within them:
    the widest gap of the checked tokens (the program's, or those the
    control puts first), and enough tokens checked."""
    served = sum(len(x) for x in g)
    worst = max((float(x.max()) for x in g if len(x)), default=None)
    checks = {
        "max_logit_gap": {"value": worst, "limit": float(
            ctx.limits["max_logit_gap"]["limit"])},
        "checked_tokens": {"value": served,
                           "limit": int(ctx.mix["check_tokens"])},
    }
    ok = (worst is not None and worst <= checks["max_logit_gap"]["limit"]
          and served >= checks["checked_tokens"]["limit"])
    return checks, ok


def run(ctx: Ctx) -> Dict[str, Any]:
    rec = window(ctx)
    rids = sample(ctx, rec)
    rec["checks"], ok = judge(ctx, gaps(ctx, rec, rids) if rids else [])
    rec["correct"] = ok and not rec["dead"]
    rec["attempted"] = len(rec["spans"].of("prefill"))
    rec["failed"] = rec["dead"]
    return rec
