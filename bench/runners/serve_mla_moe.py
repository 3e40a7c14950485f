"""Serving cells of the DeepSeek-V3 block (Kimi K2 at one chip's share of
an expert-parallel deployment): the continuous-batching engine a
``ServeJob`` declares, driven by the mix's closed backlog, then checked
against the float32 reference (``bench/reference/mla_moe.py``).

The engine is built and run as ``runners/serve.py`` builds and runs it
(paged block pool, ``ServingEngine.run``, a ``WorkQueue`` refilled to
``backlog_per_slot`` requests per slot), and the record it returns is
``serve.py``'s, so the same readers take ``serve_tok_s`` and ``setup_s``
from it.  Three things differ, all forced by outputs of thousands of
tokens:

- The requests that fill the slots first ask for the lengths that a
  closed loop's slots have left once it has run for long
  (``steady_lengths``), so requests finish and prefills run inside the
  window at the steady state's rate.
- The window opens with the first decode step, once those admissions
  have filled every slot (their prefills, a slot count of them at once,
  are set-up), and closes ``--seconds`` later, when the engine stops at
  once.
- What is checked is what the timed path served: every request's prompt
  and the tokens it had made by the window's end (its prefill's token
  and its decode steps'), recorded as the engine made them, with the
  router's choice of experts at each of their positions (the engine's
  ``route``).  A sample drawn from the seed, holding the longest, goes
  through the reference, which takes the program's choice where it
  departs from its own by less than ``route_margin`` (a near-tie that
  rounding of the hidden state flips): every served token must lie
  within the limit of the reference's best logit at its position, the
  share of (position, MoE layer) pairs flipped under its limit, and the
  pairs whose choices depart by the margin or more under theirs.
"""
from __future__ import annotations

import gc
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from bench import gen
from bench.harness import Ctx, Spans, Tracer, device_info
from bench.model_mla_moe import Dims, make, program_config
from bench.runners.serve import WORKER, _timed_queue
# the program's held-expert layer: a tree without it fails here, at once
from repro.models import mla  # noqa: F401


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


def steady_lengths(lengths, n: int, rng) -> np.ndarray:
    """``n`` remaining output lengths, as the slots of a closed loop that
    has run for long hold them: a slot is inside a request drawn in
    proportion to its length, at a point drawn evenly along it.  The
    quantiles of that remainder at (i + 1/2) / n, dealt out in an order
    drawn from ``rng``: every seed does the same work."""
    L = np.sort(np.asarray(lengths, np.int64))
    r = np.arange(1, L[-1] + 1)
    shorter = np.searchsorted(L, r)                 # requests under r
    below = np.concatenate([[0], np.cumsum(L)])
    # P(remainder <= r) = sum_j min(r, L_j) / sum_j L_j
    cdf = (below[shorter] + r * (len(L) - shorter)) / below[-1]
    return (np.searchsorted(cdf, (np.arange(n) + 0.5) / n) + 1)[
        rng.permutation(n)]


class Served:
    """Each admitted request's prompt and the tokens the engine made for
    it, slot by slot, from the engine's own prefill and decode calls;
    and the router's choice of experts at each of its positions."""

    def __init__(self, slots: int):
        self.requests: List[Dict[str, Any]] = []
        self._at: List[Optional[Dict[str, Any]]] = [None] * slots
        self._steps: List[Any] = []     # each decode step's choice
        self._host: Dict[int, np.ndarray] = {}

    def prefilled(self, slot: int, prompt, first: int, pos: int,
                  route=None) -> None:
        r = {"prompt": list(prompt), "tokens": [int(first)], "next": pos,
             "slot": slot, "route": route, "steps": []}
        self.requests.append(r)
        self._at[slot] = r

    def decoded(self, positions, new, route=None) -> None:
        step = len(self._steps)
        self._steps.append(route)
        for i, (p, tok) in enumerate(zip(positions, new)):
            r = self._at[i]
            if r is None or p != r["next"]:
                self._at[i] = None      # parked, or another request now
                continue
            r["tokens"].append(int(tok))
            r["steps"].append(step)
            r["next"] += 1

    def routes(self, r: Dict[str, Any]) -> np.ndarray:
        """The program's choice at each position of ``r``'s prompt and
        its tokens but the last: (positions, MoE layers, top_k)."""
        rows = [np.asarray(r["route"])[0]]
        for step in r["steps"]:
            if step not in self._host:
                self._host[step] = np.asarray(self._steps[step])
            rows.append(self._host[step][r["slot"]])
        return np.concatenate(rows)


def window(ctx: Ctx) -> Dict[str, Any]:
    """Set up, serve the window, and return what happened as plain data
    (the engine and its device state are gone when this returns)."""
    from repro.core.metrics import Registry
    from repro.serving.report import GAUGES
    mix, d = ctx.mix, Dims.of(ctx.cfg)
    clock = time.monotonic
    requests = gen.serve_requests(mix, d.vocab, ctx.seed)
    first = steady_lengths([r["max_new_tokens"] for r in requests],
                           int(mix["slots"]), gen.run_rng(ctx.seed, 8))
    for r, n in zip(requests, first):
        r["max_new_tokens"] = int(n)
    params = make(d, ctx.seed_for("weights"))
    registry = Registry()
    engine, job = _engine(ctx, params, registry)
    del params
    with engine.mesh:
        engine.warmup()
    spans = Spans(clock)
    decode, prefill = engine.decode_step, engine.prefill_into
    hits = registry.series(GAUGES.EXPERTS_HIT)
    served = Served(job.slots)
    step_hits: List[float] = []
    win: Dict[str, Any] = {"t0": None, "t_end": float("inf"),
                           "tracer": None}

    def decode_step(tokens, positions):
        if win["t0"] is None:
            win["t0"] = clock()
            win["wall0"] = time.time()
            win["t_end"] = win["t0"] + ctx.seconds
            if ctx.trace:
                lead = win["t0"] + ctx.seconds / 3
                win["tracer"] = Tracer(
                    lead, lead + float(mix["trace_seconds"]), clock)
        positions = [int(p) for p in positions]
        with spans.span("decode_step", positions):
            new = decode(tokens, positions)
        step_hits.append(hits.last)
        served.decoded(positions, new, engine.route)
        return new

    def prefill_into(slot, prompt):
        with spans.span("prefill", tuple(prompt)):
            first = prefill(slot, prompt)
        served.prefilled(slot, engine._pad_prompt(prompt)[0], first,
                         engine.prompt_pad, engine.route)
        return first

    engine.decode_step, engine.prefill_into = decode_step, prefill_into
    queue = _timed_queue(job.lease_timeout)
    backlog = int(mix["backlog_per_slot"]) * job.slots
    task_of: Dict[int, int] = {}        # request id -> queue task id
    pending = iter(requests)

    def refill():
        # a request put for each one leased, from the engine's own
        # thread, so the backlog stays at its size
        r = next(pending, None)
        now = clock()
        if r is not None and now < win["t_end"]:
            item = {"id": r["id"], "prompt": r["prompt"],
                    "max_new_tokens": r["max_new_tokens"]}
            task_of[r["id"]] = queue.put(item, enqueued_at=now)

    queue.refill = refill
    for _ in range(backlog):
        refill()
    t_run = clock()
    try:
        engine.run(queue, worker=WORKER, default_max_new=job.max_new_tokens,
                   exit_on_drain=False,
                   should_stop=lambda: clock() >= win["t_end"])
    finally:
        if win["tracer"] is not None:
            win["tracer"].cancel()
    t_stop = clock()
    if win["t0"] is None:
        raise RuntimeError("the engine made no decode step")
    tracer = win["tracer"]
    trace_path = tracer.finish() if tracer is not None else None
    device = device_info(ctx.chips)
    counts = {name: registry.series(g).total for name, g in (
        ("prefix_hits", GAUGES.PREFIX_HITS), ("preempted", GAUGES.PREEMPTED),
        ("lease_lost", GAUGES.LEASE_LOST), ("stale_ack", GAUGES.STALE_ACK))}
    engine.decode_step = engine.prefill_into = None
    del engine, decode, prefill
    gc.collect()
    return {
        "kind": "serve", "setup_s": win["t0"] - ctx.t_start,
        "t0": win["t0"], "t_end": win["t_end"], "wall0": win["wall0"],
        "t_stop": t_stop, "seconds": ctx.seconds,
        "fill_s": win["t0"] - t_run,
        "requests_put": len(task_of), "requests_done": sum(
            1 for tid in task_of.values() if tid in queue.acked),
        "spans": spans, "served": served, "step_hits": step_hits,
        "counts": counts,
        "registry": registry, "device": device, "trace_path": trace_path,
        "tracer": tracer, "slots": job.slots, "dead": len(queue.dead),
    }


def sample(ctx: Ctx, rec: Dict[str, Any]) -> List[Dict[str, Any]]:
    """Served requests to check, drawn from the seed: the longest, then
    others until ``check_tokens`` served tokens are in; each with the
    program's choice of experts at its positions (``routes``)."""
    served = rec["served"]
    done = [r for r in served.requests if r["tokens"]]
    if not done:
        return []
    longest = max(range(len(done)), key=lambda i: (len(done[i]["tokens"]),
                                                    -i))
    rest = [i for i in range(len(done)) if i != longest]
    order = gen.run_rng(ctx.seed, 7).permutation(len(rest))
    picked, n = [done[longest]], len(done[longest]["tokens"])
    for j in order:
        if n >= int(ctx.mix["check_tokens"]):
            break
        picked.append(done[rest[j]])
        n += len(done[rest[j]]["tokens"])
    return [{"prompt": r["prompt"], "tokens": r["tokens"],
             "routes": served.routes(r)} for r in picked]


def gaps(ctx: Ctx, picked: List[Dict[str, Any]],
         control: Optional[str] = None
         ) -> Tuple[List[np.ndarray], List[Dict[str, float]]]:
    """Each checked token's gap under the reference (or the control's
    token's, with ``control``), and how the program's choice of experts
    stood to the reference's over each request (``served_gaps``)."""
    from bench.reference import mla_moe
    d = Dims.of(ctx.cfg)
    params = make(d, ctx.seed_for("weights"))
    out = mla_moe.served_gaps(
        d, params, [r["prompt"] for r in picked],
        [r["tokens"] for r in picked], [r["routes"] for r in picked],
        float(ctx.limits["route_margin"]["value"]), control)
    del params
    gc.collect()
    return out


def judge(ctx: Ctx, got: Tuple[List[np.ndarray], List[Dict[str, float]]]
          ) -> Tuple[Dict[str, Dict[str, Any]], bool]:
    """Each number beside its limit, and whether all are within them:
    the widest gap of the checked tokens, the share of their requests'
    (position, MoE layer) pairs where the reference took the program's
    choice at a near-tie, the choices that departed from the
    reference's by ``route_margin`` or more, and enough tokens checked."""
    lim = ctx.limits
    g = np.concatenate(got[0]) if got[0] else np.zeros(0)
    pairs = sum(s["pairs"] for s in got[1])
    checks = {
        "max_logit_gap": {"value": float(g.max()) if g.size else None,
                          "limit": float(lim["max_logit_gap"]["limit"])},
        "route_flip_share": {
            "value": (sum(s["flips"] for s in got[1]) / pairs
                      if pairs else None),
            "limit": float(lim["route_flip_share"]["limit"])},
        "route_misses": {"value": sum(s["misses"] for s in got[1]),
                         "limit": int(lim["route_misses"]["limit"])},
        "checked_tokens": {"value": int(g.size),
                           "limit": int(ctx.mix["check_tokens"])},
    }
    ok = all(c["value"] is not None and c["value"] <= c["limit"]
             for k, c in checks.items() if k != "checked_tokens")
    ok = ok and g.size >= checks["checked_tokens"]["limit"]
    return checks, ok


def run(ctx: Ctx) -> Dict[str, Any]:
    rec = window(ctx)
    picked = sample(ctx, rec)
    got = gaps(ctx, picked) if picked else ([], [])
    rec["checks"], ok = judge(ctx, got)
    rec["correct"] = ok and not rec["dead"]
    rec["attempted"] = len(rec["spans"].of("prefill"))
    rec["failed"] = rec["dead"]
    return rec
