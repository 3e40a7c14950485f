"""Benchmark harness — one function per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--fast] [--json PATH]

Outputs ``name,us_per_call,derived`` CSV rows:
  table1_*   — paper Table I: per-step resource summary of the CONNECT
               workflow (time per step; derived = data bytes processed).
  fig3_*     — paper Figs 3-4: queue-fed download job, worker scaling
               (derived = MB/s aggregate throughput).
  fig5_*     — paper Fig 5: FFN training step (derived = voxels/s).
  fig6_*     — paper Fig 6: distributed inference worker scaling
               (derived = voxels/s; speedup printed vs 1 worker).
  lm_train_* — LM substrate: one sharded train step on the smoke config
               (derived = tokens/s).
  train_*    — device-resident hot loop: per-step dispatch vs chunked
               lax.scan dispatch on the elastic trainer (derived =
               tokens/s; extras = host syncs/step, time-to-first-step).
  serve_*    — serving: prefill latency + decode steps/s.
  fabric_*   — multi-site federation: locality-aware vs data-blind
               placement (derived = bytes moved over the links).
  workflow_* — workflow programs (repro.flow): diamond-with-fan-out
               graph makespan, serial vs concurrent branches spread
               across a 3-site fabric (derived = makespan + ratio).
  vcluster_* — multi-tenant fair share: dominant-share scheduling vs
               FIFO skew, preemption/resume cost, monitor event lag.
  scenario_* — production-chaos harness: diurnal replay under site
               loss / link brown-out; per-tenant SLO scorecards
               (goodput, p99, steps lost, chargeback).

``--only SUBSTR`` runs only the benches whose name contains SUBSTR
(e.g. ``--only scenarios`` regenerates just BENCH_scenarios.json).

``--json PATH`` additionally writes the whole run as one trajectory
record: every row as an object with its structured extras (``tok_s``,
``bytes_moved``, ``transfer_s``, ...), so cross-PR tooling can track
throughput and data movement in the same file.
"""
from __future__ import annotations

import argparse
import json
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

ROWS = []
JSON_SCHEMA = "repro-bench/v1"

# The documented vocabulary of structured row extras.  Every key a bench
# passes to ``row(**extra)`` must be registered here — the committed
# BENCH_*.json files are validated against this set by
# tests/test_bench_schema.py, so cross-PR tooling can rely on the names.
KNOWN_EXTRA_KEYS = frozenset({
    # data movement / placement
    "bytes", "bytes_moved", "transfer_s", "makespan_s",
    # throughput
    "tok_s",
    # hot-loop dispatch (train_* rows)
    "host_syncs_per_step", "t_first_s", "device_steps",
    # elasticity / preemption
    "steps_lost", "preemptions", "recoveries",
    # fair share / monitoring
    "makespan_ratio", "fifo_skew", "monitor_lag_s", "monitor_events",
    # workflow fan-out (workflow_* rows)
    "width", "fanout_ratio", "branch_sites",
    # chaos scenarios
    "fairshare_skew", "chaos_applied", "windows", "horizon_s",
    "offered", "served", "goodput", "slo_pass",
    "p99_ttft_s", "p99_latency_s", "chargeback_usd",
    # serving at scale (serving_* rows)
    "prefix_hit_rate", "scale_events", "replicas_max", "stale_tokens",
    # distributed RL (rl_* rows)
    "rollout_tok_s", "learner_steps_s", "policy_lag_p99",
    "max_lag_trained", "trained", "stale_dropped", "requeued_tickets",
    "weight_syncs", "crashes",
})


def row(name: str, us_per_call: float, derived: str = "", **extra):
    """One benchmark row.  ``extra`` keys (numbers) land verbatim in the
    JSON trajectory record — bytes_moved / transfer_s / tok_s share one
    schema with the paper-figure timings."""
    ROWS.append({"name": name, "us_per_call": round(us_per_call, 1),
                 "derived": derived, **extra})
    print(f"{name},{us_per_call:.1f},{derived}")


# ---------------------------------------------------------------------------

def bench_connect_workflow(fast: bool):
    """Table I + the 4-step CONNECT workflow, measured end to end."""
    from repro.apps.connect.pipeline import ConnectConfig, run_connect_workflow
    from repro.data.volumes import VolumeSpec
    from repro.models.ffn3d import FFNConfig

    cc = ConnectConfig(
        n_chunks=2, download_workers=2, inference_workers=2,
        vol=VolumeSpec(lat=48, lon=72, frames=16, events=2),
        ffn=FFNConfig(depth=3, width=12, fov=(8, 16, 16), flood_iters=2),
        train_steps=10 if fast else 30)
    with tempfile.TemporaryDirectory() as d:
        wf, results = run_connect_workflow(d, cc)
    for rep in wf.reports:
        row(f"table1_{rep.step}", rep.total_time_s * 1e6,
            f"bytes={rep.data_processed_bytes}",
            bytes=rep.data_processed_bytes)
    return results


def bench_queue_scaling(fast: bool):
    """Figs 3-4: download throughput vs worker count (work-queue scaling)."""
    from repro.core.queue import WorkQueue, run_workers
    from repro.data import volumes
    from repro.data.objectstore import ObjectStore

    spec = volumes.VolumeSpec(lat=48, lon=72, frames=8, events=1)
    n_chunks = 4 if fast else 8
    for workers in (1, 2, 4):
        with tempfile.TemporaryDirectory() as d:
            store = ObjectStore(d)
            q = WorkQueue(list(range(n_chunks)))
            nbytes = {"n": 0}

            def fetch(cid):
                ivt, lab = volumes.generate_chunk(spec, cid)
                nbytes["n"] += store.put_array(f"c{cid}/ivt.npy", ivt)
                nbytes["n"] += store.put_array(f"c{cid}/lab.npy", lab)

            t0 = time.perf_counter()
            run_workers(q, fetch, workers)
            dt = time.perf_counter() - t0
        row(f"fig3_download_w{workers}", dt / n_chunks * 1e6,
            f"MBps={nbytes['n'] / 2**20 / dt:.1f}")


def bench_ffn_train(fast: bool):
    """Fig 5: FFN 3-D CNN training step."""
    from repro.models import ffn3d
    from repro.models.params import init_params

    cfg = ffn3d.FFNConfig(depth=3, width=12, fov=(8, 16, 16))
    params = init_params(ffn3d.ffn_schema(cfg), jax.random.key(0), "float32")
    B = 4
    x = jax.random.uniform(jax.random.key(1), (B,) + cfg.fov)
    y = (x > 0.6).astype(jnp.float32)

    @jax.jit
    def step(p, x, y):
        loss, g = jax.value_and_grad(
            lambda p: ffn3d.bce_loss(cfg, p, x, y))(p)
        return jax.tree.map(lambda a, b: a - 1e-3 * b, p, g), loss

    params, _ = step(params, x, y)          # compile
    n = 3 if fast else 10
    t0 = time.perf_counter()
    for _ in range(n):
        params, loss = step(params, x, y)
    jax.block_until_ready(loss)
    dt = (time.perf_counter() - t0) / n
    vox = B * int(np.prod(cfg.fov))
    row("fig5_ffn_train_step", dt * 1e6, f"voxels_s={vox / dt:.0f}")


def bench_inference_scaling(fast: bool):
    """Fig 6 / §III-C: flood-fill inference scaling with worker count."""
    from repro.core.queue import WorkQueue, run_workers
    from repro.models import ffn3d
    from repro.models.params import init_params

    cfg = ffn3d.FFNConfig(depth=3, width=12, fov=(8, 16, 16), flood_iters=2)
    params = init_params(ffn3d.ffn_schema(cfg), jax.random.key(0), "float32")

    @jax.jit
    def infer(x):
        return jax.nn.sigmoid(ffn3d.flood_fill(cfg, params, x)) > 0.5

    tile = jax.random.uniform(jax.random.key(1), (4,) + cfg.fov)
    np.asarray(infer(tile))                 # compile once
    n_tiles = 8 if fast else 16
    base = None
    for workers in (1, 2, 4):
        q = WorkQueue(list(range(n_tiles)))
        t0 = time.perf_counter()
        run_workers(q, lambda i: np.asarray(infer(tile)).sum(), workers)
        dt = time.perf_counter() - t0
        vox = n_tiles * tile.size
        if base is None:
            base = dt
        row(f"fig6_inference_w{workers}", dt / n_tiles * 1e6,
            f"voxels_s={vox / dt:.0f};speedup={base / dt:.2f}")


def bench_lm_train(fast: bool):
    """LM substrate: sharded train step on the reduced phi4 config."""
    from repro.configs import registry
    from repro.configs.base import OptimizerConfig, ShapeConfig
    from repro.launch.mesh import single_device_mesh
    from repro.models import params as pr
    from repro.optim import adamw
    from repro.runtime import steps as steps_mod

    cfg = registry.get_smoke("phi4-mini-3.8b")
    shape = ShapeConfig("b", 128, 4, "train")
    mesh = single_device_mesh()
    ocfg = OptimizerConfig(warmup_steps=2, decay_steps=100)
    bundle = steps_mod.build_train(cfg, registry.get_parallel("phi4-mini-3.8b"),
                                   ocfg, mesh, shape)
    mod = steps_mod._model_module(cfg)
    schema = mod.lm_schema(cfg)
    params = pr.init_params(schema, jax.random.key(0), cfg.param_dtype)
    opt = pr.init_params(adamw.opt_state_schema(schema, ocfg),
                         jax.random.key(1), "float32")
    batch = {"tokens": jnp.ones((4, 128), jnp.int32),
             "labels": jnp.ones((4, 128), jnp.int32)}
    with mesh:
        step = bundle.jit()
        params, opt, m = step(params, opt, batch)   # compile
        n = 3 if fast else 10
        t0 = time.perf_counter()
        for _ in range(n):
            params, opt, m = step(params, opt, batch)
        jax.block_until_ready(m["loss"])
        dt = (time.perf_counter() - t0) / n
    row("lm_train_step_smoke", dt * 1e6, f"tokens_s={4 * 128 / dt:.0f}")


def bench_train_hot_loop(fast: bool):
    """Device-resident hot loop: per-step vs chunked (lax.scan) dispatch.

    Runs the SAME elastic training job twice — ``device_steps=1`` (one
    host dispatch + loss bookkeeping per optimizer step) and
    ``device_steps=K`` (one dispatch per K steps, losses flushed in bulk
    at chunk boundaries, batches prefetched by a background thread) —
    and records the trajectory numbers the refactor is about: useful
    tokens/s, host round-trips per optimizer step (O(1) vs O(1/K)), and
    time-to-first-step (restore + compile + first dispatch; the chunked
    run compiles a K-step scan, so its t_first is the cost side of the
    trade).  Losses are bit-identical between the two runs (pinned by
    tests/test_train_hot_loop.py), so this is pure dispatch overhead.
    """
    import tempfile as _tf

    from repro.configs import registry
    from repro.configs.base import OptimizerConfig
    from repro.core.orchestrator import Cluster
    from repro.data.objectstore import ObjectStore
    from repro.elastic import ElasticTrainer, ElasticTrainSpec

    cfg = registry.get_smoke("phi4-mini-3.8b")
    par = registry.get_parallel("phi4-mini-3.8b")
    steps = 16 if fast else 48
    K = 4

    def run(device_steps: int):
        spec = ElasticTrainSpec(
            cfg, par, OptimizerConfig(warmup_steps=2, decay_steps=100),
            steps=steps, seq_len=64, global_batch=8, base_shape=(1, 1),
            max_data=1, ckpt_every=0, log_every=0, verbose=False,
            device_steps=device_steps)
        with _tf.TemporaryDirectory() as d:
            trainer = ElasticTrainer(Cluster(devices=jax.devices()), spec,
                                     store=ObjectStore(d))
            out = trainer.run()
        rep = out["report"]
        assert len(out["losses"]) == steps
        return rep

    base = run(1)
    for tag, rep in (("per_step", base), (f"chunked_k{K}", run(K))):
        row(f"train_{tag}", rep.total_wall_s / steps * 1e6,
            f"tok_s={rep.tokens_per_s:.0f};"
            f"syncs_per_step={rep.host_syncs_per_step:.2f};"
            f"t_first_s={rep.t_first_s:.2f}",
            tok_s=round(rep.tokens_per_s, 1),
            host_syncs_per_step=round(rep.host_syncs_per_step, 4),
            t_first_s=round(rep.t_first_s, 3),
            device_steps=1 if tag == "per_step" else K)


def bench_serve(fast: bool):
    """Serving: continuous batching vs the static drain-then-refill batcher.

    The workload is straggler-heavy on purpose (one long request per
    static batch, the rest short): the static batcher's short requests
    idle their decode slots until the long one finishes, while the
    continuous batcher evicts and refills them immediately.  Both paths
    serve identical requests, warmed up so compile time is off the clock;
    ``tok_s`` is useful generated tokens / wall seconds.
    """
    from repro.launch.serve import make_requests, serve, serve_static

    # skew is the point, so --fast keeps the long requests long: the
    # static barrier costs 2 batches x 32 fused steps vs ~33 continuous
    long_g = 32
    kw = dict(smoke=True, n_requests=8, prompt_len=16, gen=long_g,
              batch=4, gen_lens=[long_g, 2, 2, 2], warmup=True)
    reps = 2 if fast else 3

    def best(fn):
        runs = [fn("phi4-mini-3.8b", **kw)[1].scrape() for _ in range(reps)]
        return min(runs, key=lambda m: m["serve/wall_s"])

    s, c = best(serve_static), best(serve)
    row("serve_static", s["serve/wall_s"] * 1e6,
        f"tok_s={s['serve/tok_s']:.1f}", tok_s=s["serve/tok_s"])
    row("serve_continuous", c["serve/wall_s"] * 1e6,
        f"tok_s={c['serve/tok_s']:.1f};"
        f"speedup={c['serve/tok_s'] / max(s['serve/tok_s'], 1e-9):.2f}",
        tok_s=c["serve/tok_s"])


def bench_serving_scale(fast: bool):
    """Serving at scale: static batcher vs an autoscaled paged+prefix
    replica fleet on shared-prefix, straggler-skewed traffic.

    Every request shares one block-aligned system-prompt head (the radix
    prefix cache's case) and stop lengths are skewed (one straggler per
    four requests).  The baseline is the drain-then-refill static
    batcher; the challenger runs the paged-KV engines behind the
    session-affine router with the HPA-style autoscaler (1 -> 2
    replicas off the arrival burst).  Both arms report p99 TTFT measured
    from ENQUEUE and tok/s counting only acked completions — the two
    numbers the serving-loop bug burn-down corrected.  Engines are
    prebuilt+warmed so replica cold-start is process-level, not compile.

    The smoke config is scaled up (2 layers, d_model 256) so a fused
    decode step carries real device work: on the tiny smoke shapes the
    host loop dominates and neither continuous batching nor replication
    can show through.
    """
    import dataclasses
    import threading

    from repro.configs import registry as cfg_registry
    from repro.core.metrics import Registry
    from repro.launch.mesh import single_device_mesh
    from repro.launch.serve import serve_static
    from repro.serving import GAUGES, ServingEngine, serve_replicated

    arch = "phi4-mini-3.8b"
    cfg = dataclasses.replace(
        cfg_registry.get_smoke(arch), num_layers=2, d_model=256, d_ff=512,
        num_heads=8, num_kv_heads=4, head_dim=32,
        block_pattern=("attn", "attn"))
    par = cfg_registry.get_parallel(arch)
    mesh = single_device_mesh()
    Pp, G, slots, bs = 16, 32, 4, 8
    n = 16 if fast else 32
    rng = np.random.RandomState(0)
    head = rng.randint(1, cfg.vocab_size, bs).tolist()   # shared system block
    gens = [G, 2, 2, 2]
    reqs = [{"id": i, "session": f"user-{i % 4}",
             "prompt": head + rng.randint(1, cfg.vocab_size, Pp - bs).tolist(),
             "max_new_tokens": gens[i % len(gens)]}
            for i in range(n)]

    s_res, s_m = serve_static(arch, smoke=True, n_requests=n, prompt_len=Pp,
                              gen=G, batch=slots, warmup=True, requests=reqs,
                              cfg_override=cfg)
    s_tok = s_m.series(GAUGES.TOK_S).last
    s_p99 = s_m.series(GAUGES.TTFT_S).percentile(99)
    row("serving_static", s_m.series(GAUGES.WALL_S).last * 1e6,
        f"tok_s={s_tok:.1f};p99_ttft={s_p99:.3f}",
        tok_s=s_tok, p99_ttft_s=s_p99)

    fleet = Registry()
    prebuilt = [ServingEngine(cfg, par, mesh, num_slots=slots,
                              prompt_len=Pp, max_new_tokens=G, seed=0,
                              registry=fleet, paged=True, block_size=bs)
                for _ in range(2)]
    with mesh:
        for e in prebuilt:
            e.warmup()
    avail, lock = list(prebuilt), threading.Lock()

    class Pooled:
        """Checks a prebuilt engine out for one replica lifetime."""
        def __init__(self):
            with lock:
                self.engine = avail.pop()

        def run(self, *a, **kw):
            try:
                return self.engine.run(*a, **kw)
            finally:
                with lock:
                    avail.append(self.engine)

    results, m, events = serve_replicated(
        lambda name, reg: Pooled(), reqs, min_replicas=1, max_replicas=2,
        target_backlog=2.0, registry=fleet, reconcile_interval=0.01,
        timeout_s=300.0)
    assert sorted(results) == list(range(n)), "fleet dropped requests"
    tok = m.series(GAUGES.TOK_S).last
    p99 = m.series(GAUGES.TTFT_S).percentile(99)
    hits = m.series(GAUGES.PREFIX_HITS).total
    misses = m.series(GAUGES.PREFIX_MISSES).total
    hit_rate = hits / max(hits + misses, 1.0)
    row("serving_paged_autoscaled", m.series(GAUGES.WALL_S).last * 1e6,
        f"tok_s={tok:.1f};p99_ttft={p99:.3f};"
        f"speedup={tok / max(s_tok, 1e-9):.2f};prefix_hit={hit_rate:.2f}",
        tok_s=tok, p99_ttft_s=p99, prefix_hit_rate=hit_rate,
        scale_events=float(len(events)),
        replicas_max=m.series(GAUGES.REPLICAS).max,
        stale_tokens=m.series(GAUGES.STALE_TOKENS).total)


def _example_report(script: str, tag: str, fast: bool) -> dict:
    """Run ``examples/<script>`` in a child process and parse its
    ``<tag> {json}`` line.

    The examples force simulated host devices, an XLA flag that must be
    set before jax initializes, so they cannot run in this process.  The
    child is pinned to the CPU: this process already holds the chip, and
    a second process that reached for it would fail or hang."""
    import os
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = os.path.join(root, "src") + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    cmd = [sys.executable, os.path.join(root, "examples", script)]
    if fast:
        cmd.append("--fast")
    out = subprocess.run(cmd, env=env, capture_output=True, text=True,
                         timeout=900)
    if out.returncode != 0:
        raise RuntimeError(f"{script} failed:\n{out.stdout}\n{out.stderr}")
    return next(json.loads(l.split(" ", 1)[1]) for l in out.stdout.splitlines()
                if l.startswith(f"{tag} "))


def bench_elastic_churn(fast: bool):
    """Elastic recovery cost across an injected kill/rejoin schedule.

    Runs ``examples/elastic_failover.py`` (8 forced host devices, 2 killed
    mid-run, later rejoining) in a subprocess — the device count is an XLA
    flag that must be set before jax initializes, so it cannot run in this
    process — and parses its ``CHURN_REPORT`` json: overall tokens/s with
    every recovery (restore + recompile + re-executed steps) on the clock,
    steps lost to the failure, and wall-seconds from node death to the
    first step completed on the reshaped mesh.
    """
    rep = _example_report("elastic_failover.py", "CHURN_REPORT", fast)
    steps = rep["steps"]
    row("elastic_churn_train", rep["total_wall_s"] / steps * 1e6,
        f"tok_s={rep['tokens_per_s']:.1f};recoveries={rep['recoveries']}",
        tok_s=rep["tokens_per_s"])
    recovery = (sum(rep["recovery_s"]) / len(rep["recovery_s"])
                if rep["recovery_s"] else 0.0)
    overhead = rep["tokens_executed"] / max(
        steps * rep["global_batch"] * rep["seq_len"], 1) - 1.0
    row("elastic_churn_recovery", recovery * 1e6,
        f"steps_lost={rep['steps_lost']};reexec_overhead={overhead:.1%}",
        steps_lost=rep["steps_lost"])


def bench_fabric_placement(fast: bool):
    """Multi-site federation (paper §IV): locality-aware vs data-blind
    placement on a 3-site fabric with skewed data.

    Most of the dataset homes at one hub site; the spokes hang off slow
    links.  Both planners run the identical 2-step workflow (a chunk
    "stats" pass, then a reduce over its output) with ``time_scale=1.0``,
    so wall-clock IS the simulated makespan: the data-blind round-robin
    drags chunks across the slow links, the locality planner runs at the
    data.  Locality must move strictly fewer bytes at no makespan cost.
    """
    from repro.core.workflow import Step, Workflow
    from repro.fabric import Fabric, FederatedStore, PlacementPlanner

    n_chunks = 4 if fast else 6
    chunk_mb = 2 if fast else 8

    def run(data_blind: bool):
        fabric = Fabric(time_scale=1.0)
        fabric.add_site("hub", devices=list(range(4)))
        fabric.add_site("spoke-a", devices=list(range(2)))
        fabric.add_site("spoke-b", devices=list(range(1)))
        fabric.connect("hub", "spoke-a", gbps=0.2, latency_ms=10.0)
        fabric.connect("hub", "spoke-b", gbps=0.1, latency_ms=20.0)
        fabric.connect("spoke-a", "spoke-b", gbps=0.1, latency_ms=20.0)
        fed = FederatedStore(fabric)
        rng = np.random.RandomState(0)
        keys = []
        for i in range(n_chunks):
            # skew: all but one chunk homes at the hub
            site = "hub" if i % n_chunks else "spoke-a"
            key = f"chunks/c{i}.npy"
            fed.view(site).put_array(
                key, rng.rand(chunk_mb * 2**20 // 8).astype(np.float64))
            keys.append(key)
        planner = PlacementPlanner(fed, data_blind=data_blind)
        wf = Workflow("fabric-bench", planner=planner)
        for i, key in enumerate(keys):      # one measured pass per chunk
            wf.add(Step(f"stats{i}",
                        lambda ctx, k=key: {
                            "mean": float(ctx.store.get_array(k).mean())},
                        inputs=[key]))
        wf.add(Step("reduce", lambda ctx: {
            "mean": float(np.mean([v["mean"] for v in ctx.inputs.values()]))},
            deps=[f"stats{i}" for i in range(n_chunks)]))
        t0 = time.perf_counter()
        wf.run()
        makespan = time.perf_counter() - t0
        m = fabric.metrics
        return (makespan, int(m.series("fabric/bytes_moved").total),
                m.series("fabric/transfer_s").total)

    for name, blind in (("fabric_locality", False), ("fabric_blind", True)):
        makespan, moved, sim_s = run(blind)
        row(name, makespan * 1e6,
            f"bytes_moved={moved};transfer_s={sim_s:.2f}",
            bytes_moved=moved, transfer_s=round(sim_s, 4),
            makespan_s=round(makespan, 3))


def bench_workflow_fanout(fast: bool):
    """Workflow programs (repro.flow, ISSUE 8): the diamond-with-fan-out
    graph on a 3-site fabric, serial branches vs the concurrent branch
    pool.

    Each scatter branch models an I/O-bound shard (a fixed simulated
    latency — the regime where the paper's Kepler programs win by
    running independent actors at different sites at once).  The SAME
    graph runs twice: ``max_workers=1`` dispatches the branches one at a
    time, ``max_workers=8`` overlaps them across the federation, spread
    by the planner's in-flight load accounting.  The acceptance bar is
    makespan ratio < 0.6; fresh stores per run, so no marker resume
    bleeds between the two."""
    from repro.core.workflow import Workflow
    from repro.fabric import Fabric, FederatedStore, PlacementPlanner
    from repro.flow import GraphRunner
    from repro.vcluster.monitor import EventBus

    width = 8 if fast else 12
    branch_s = 0.05

    def branch(ctx):
        time.sleep(branch_s)                  # simulated shard latency
        return {"i": ctx.inputs["index"]}

    graph = {"nodes": [
        {"step": "plan", "fn": lambda ctx: {
            "chunks": [f"c{i}" for i in range(width)]}},
        {"step": "seg", "deps": ["plan"], "fn": branch,
         "scatter": {"over": "plan.chunks"}},
        {"step": "left", "deps": ["plan"], "fn": lambda ctx: {
            "n": len(ctx.inputs["plan"]["chunks"])}},
        {"step": "join", "deps": ["seg", "left"], "fn": lambda ctx: {
            "segs": len(ctx.inputs["seg"])}},
    ]}

    def run(max_workers):
        fabric = Fabric(time_scale=0.0)
        for i in range(3):
            fabric.add_site(f"s{i}", devices=list(range(2)))
        for a, b in (("s0", "s1"), ("s0", "s2"), ("s1", "s2")):
            fabric.connect(a, b, gbps=1.0, latency_ms=10.0)
        bus = EventBus()
        sub = bus.subscribe(maxlen=4096)
        wf = Workflow("fanout-bench",
                      planner=PlacementPlanner(FederatedStore(fabric)),
                      bus=bus)
        t0 = time.perf_counter()
        out = GraphRunner(wf, graph, max_workers=max_workers).run()
        makespan = time.perf_counter() - t0
        assert out["join"]["segs"] == width
        sites = {e.data["site"] for e in sub.poll()
                 if e.kind == "branch" and e.data.get("status") == "done"}
        return makespan, len(sites)

    serial, _ = run(1)
    conc, n_sites = run(8)
    ratio = conc / serial
    row("workflow_fanout_serial", serial / width * 1e6,
        f"makespan_s={serial:.2f}",
        makespan_s=round(serial, 3), width=width)
    row("workflow_fanout_concurrent", conc / width * 1e6,
        f"makespan_s={conc:.2f};ratio={ratio:.2f};sites={n_sites}",
        makespan_s=round(conc, 3), width=width,
        fanout_ratio=round(ratio, 3), branch_sites=n_sites)


def bench_vcluster_fairness(fast: bool):
    """Multi-tenant fair share (paper §I contribution 4, §IV).

    Runs ``examples/multitenant_fabric.py`` in a subprocess (it builds a
    serving engine and an elastic trainer, so it wants a fresh jax) and
    parses its ``VCLUSTER_REPORT``: two equal-share tenants on a
    saturated fabric under the dominant-share scheduler vs the FIFO
    baseline (makespan ratio vs completion skew), the trainer's
    checkpoint-then-evict preemption cost (steps lost on resume), and
    the monitor stream's end-to-end event lag.
    """
    rep = _example_report("multitenant_fabric.py", "VCLUSTER_REPORT", fast)
    fair, fifo, prem = rep["fair"], rep["fifo"], rep["preemption"]
    mk = max(fair["alice"]["makespan_s"], fair["bob"]["makespan_s"])
    row("vcluster_fair_share", mk * 1e6,
        f"makespan_ratio={fair['makespan_ratio']};"
        f"fifo_skew={fifo['completion_skew']}",
        makespan_ratio=fair["makespan_ratio"],
        fifo_skew=fifo["completion_skew"])
    mon = prem["monitor"]
    row("vcluster_preempt_resume", mon["max_lag_s"] * 1e6,
        f"steps_lost={prem['steps_lost']};"
        f"preemptions={prem['preemptions']};"
        f"monitor_lag_s={mon['max_lag_s']}",
        steps_lost=prem["steps_lost"], preemptions=prem["preemptions"],
        monitor_lag_s=mon["max_lag_s"], monitor_events=mon["received"])


def bench_scenarios(fast: bool):
    """Production-chaos scenario harness (paper §IV measurement loop).

    Runs ``examples/scenario_chaos.py`` in a subprocess (it forces 8 XLA
    host devices before jax initializes) and parses its
    ``SCENARIO_REPORT`` json: three tenants replaying diurnal traffic
    through the declarative API while a site dies, a link browns out and
    nodes churn mid-wave.  One summary row carries the fair-share skew
    and wall time; one row per tenant carries its SLO scorecard —
    goodput ratio, p99 TTFT/latency, steps lost to preemption and the
    $-chargeback total.
    """
    rep = _example_report("scenario_chaos.py", "SCENARIO_REPORT", fast)
    chaos_applied = sum(1 for c in rep["chaos"] if c.get("applied"))
    row("scenario_chaos_run", rep["wall_s"] * 1e6,
        f"skew={rep['fairshare_skew']};chaos={chaos_applied}",
        fairshare_skew=rep["fairshare_skew"], chaos_applied=chaos_applied,
        windows=rep["windows"], horizon_s=rep["horizon_s"])
    for name, g in sorted(rep["tenants"].items()):
        row(f"scenario_tenant_{name}", g["makespan_s"] * 1e6,
            f"goodput={g['goodput_ratio']};slo_pass={g['slo_pass']};"
            f"steps_lost={g['steps_lost']}",
            offered=g["offered"], served=g["served"],
            goodput=g["goodput_ratio"], slo_pass=bool(g["slo_pass"]),
            p99_ttft_s=g["p99_ttft_s"], p99_latency_s=g["p99_latency_s"],
            steps_lost=g["steps_lost"],
            chargeback_usd=g["chargeback"]["total"])


def bench_rl(fast: bool):
    """Distributed RL co-tenants (paper §I, §IV, §VI).

    Runs ``examples/rl_cotenants.py`` in a subprocess (two serving
    engines + the learner hot loop want a fresh jax) and parses its
    ``RL_REPORT``: a serving-plane actor fleet feeding the elastic
    learner through the rollout queue while the chaos controller kills
    a lease-holding actor, resizes the fleet through the fair-share
    claim, preempts the learner with a burst tenant and injects one
    hard learner crash.  One row carries rollout generation throughput,
    one the learner's step rate with the staleness audit (p99 policy
    lag, stale drops), one the chaos/recovery accounting (steps lost
    vs the checkpoint bound, tickets requeued by the killed actor).
    """
    rep = _example_report("rl_cotenants.py", "RL_REPORT", fast)
    row("rl_rollout_fleet", rep["wall_s"] * 1e6 / max(rep["trained"], 1),
        f"tok_s={rep['rollout_tok_s']};rollouts={rep['rollouts_pushed']}",
        rollout_tok_s=rep["rollout_tok_s"], trained=rep["trained"],
        bytes_moved=rep["weight_bytes_pulled"])
    row("rl_learner_steps", rep["wall_s"] * 1e6 / max(rep["steps_done"], 1),
        f"steps_s={rep['learner_steps_s']};"
        f"lag_p99={rep['policy_lag_p99']};stale={rep['stale_dropped']}",
        learner_steps_s=rep["learner_steps_s"],
        policy_lag_p99=rep["policy_lag_p99"],
        max_lag_trained=rep["max_lag_trained"],
        stale_dropped=rep["stale_dropped"],
        weight_syncs=rep["weight_syncs"])
    row("rl_chaos_recovery", rep["wall_s"] * 1e6,
        f"steps_lost={rep['steps_lost']};"
        f"preemptions={rep['preemptions']};crashes={rep['crashes']};"
        f"requeued={rep['requeued_tickets']}",
        steps_lost=rep["steps_lost"], preemptions=rep["preemptions"],
        crashes=rep["crashes"], requeued_tickets=rep["requeued_tickets"])


BENCHES = [
    ("connect_workflow", lambda fast: bench_connect_workflow(fast)),
    ("queue_scaling", lambda fast: bench_queue_scaling(fast)),
    ("ffn_train", lambda fast: bench_ffn_train(fast)),
    ("inference_scaling", lambda fast: bench_inference_scaling(fast)),
    ("lm_train", lambda fast: bench_lm_train(fast)),
    ("train_hot_loop", lambda fast: bench_train_hot_loop(fast)),
    ("serve", lambda fast: bench_serve(fast)),
    ("serving_scale", lambda fast: bench_serving_scale(fast)),
    ("elastic_churn", lambda fast: bench_elastic_churn(fast)),
    ("fabric_placement", lambda fast: bench_fabric_placement(fast)),
    ("workflow_fanout", lambda fast: bench_workflow_fanout(fast)),
    ("vcluster_fairness", lambda fast: bench_vcluster_fairness(fast)),
    ("scenarios", lambda fast: bench_scenarios(fast)),
    ("rl", lambda fast: bench_rl(fast)),
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--json", default="",
                    help="also write the rows as a JSON trajectory record")
    ap.add_argument("--only", default="",
                    help="run only benches whose name contains this substring")
    args, _ = ap.parse_known_args()
    from repro.launch.cli import enable_compile_cache
    enable_compile_cache()
    print("name,us_per_call,derived")
    for name, fn in BENCHES:
        if args.only and args.only not in name:
            continue
        fn(args.fast)
    print(f"\n# {len(ROWS)} benchmark rows")
    if args.json:
        with open(args.json, "w") as f:
            json.dump({"schema": JSON_SCHEMA, "created_unix": time.time(),
                       "fast": args.fast, "rows": ROWS}, f, indent=1)
        print(f"# json trajectory -> {args.json}")


if __name__ == "__main__":
    main()
