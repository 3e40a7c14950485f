"""Serving driver — a thin manifest CLI over the unified workload API.

    PYTHONPATH=src python -m repro.launch.serve --arch phi4-mini-3.8b \
        --smoke --requests 8 --prompt-len 32 --gen 16 --slots 4
    PYTHONPATH=src python -m repro.launch.serve --manifest serve.json

Both forms declare the SAME ``repro.api.ServeJob`` resource and apply it
through a ``Session``: requests ride a WorkQueue (the paper's Redis
job-queue pattern) into the continuous batcher (repro.serving) — a fixed
pool of decode slots, per-request prefill into a slotted KV/state cache,
one fused per-slot decode step per iteration, immediate evict/refill.

``--static`` (or ``serve_static``) keeps the legacy drain-then-refill
batcher: lease a batch, prefill together, decode until the LONGEST
request in the batch finishes, ack, repeat.  It exists as the baseline
the serving benchmark (benchmarks/run.py bench_serve) measures
continuous batching against — it stays a plain function, not an API
workload, on purpose.

``serve(...)`` is kept as a deprecated shim delegating to
``Session.apply`` (pinned by tests/test_api_equivalence.py); the
``serve/*`` gauge names and the Table-I row live in
``repro.serving.report`` now (one copy, shared with the engine and the
scheduler).
"""
from __future__ import annotations

import argparse
import time
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.api import ServeJob, Session
from repro.configs import registry
from repro.configs.base import ShapeConfig
from repro.core.metrics import Registry, table_one
from repro.core.orchestrator import Cluster
from repro.launch import cli
from repro.launch.mesh import single_device_mesh
from repro.models import params as pr
from repro.runtime import steps as steps_mod
# canonical homes are repro.serving.report; re-exported here for the
# pre-API callers (benchmarks, examples, tests)
from repro.serving.report import (GAUGES, make_requests, record_serving_totals,
                                  request_queue as _request_queue,
                                  serving_report)


def serve_job(arch: str, *, smoke: bool, n_requests: int, prompt_len: int,
              gen: int, batch: int = 4, seed: int = 0,
              gen_lens: Optional[Sequence[int]] = None,
              lease_timeout: float = 30.0, warmup: bool = False,
              requests: Optional[Sequence[dict]] = None) -> ServeJob:
    """The ServeJob resource the legacy flag surface declares."""
    return ServeJob(
        name=f"serve-{arch}", arch=arch, smoke=smoke,
        n_requests=n_requests, prompt_len=prompt_len, max_new_tokens=gen,
        slots=batch, seed=seed,
        gen_lens=tuple(gen_lens) if gen_lens is not None else None,
        lease_timeout=lease_timeout, warmup=warmup,
        requests=[dict(r) for r in requests] if requests is not None
        else None)


def apply_serve(spec: ServeJob, *, timeout: float = 3600.0):
    """Run one ServeJob on a fresh one-host cluster Session."""
    session = Session(cluster=Cluster(devices=jax.devices(),
                                      metrics=Registry()))
    return session.apply(spec).wait(timeout)


def serve(arch: str, *, smoke: bool, n_requests: int, prompt_len: int,
          gen: int, batch: int = 4, seed: int = 0,
          gen_lens: Optional[Sequence[int]] = None,
          lease_timeout: float = 30.0, warmup: bool = False,
          requests: Optional[Sequence[dict]] = None):
    """Deprecated shim — declare a ``repro.api.ServeJob`` and apply it
    through a ``Session`` instead.  Returns ``(results, metrics)`` like
    the pre-API driver."""
    out = apply_serve(serve_job(
        arch, smoke=smoke, n_requests=n_requests, prompt_len=prompt_len,
        gen=gen, batch=batch, seed=seed, gen_lens=gen_lens,
        lease_timeout=lease_timeout, warmup=warmup, requests=requests))
    return out["results"], out["metrics"]


def serve_static(arch: str, *, smoke: bool, n_requests: int, prompt_len: int,
                 gen: int, batch: int = 4, seed: int = 0,
                 gen_lens: Optional[Sequence[int]] = None,
                 lease_timeout: float = 30.0, warmup: bool = False,
                 requests: Optional[Sequence[dict]] = None,
                 cfg_override=None):
    """Legacy static batcher (benchmark baseline — see module docstring).

    Batches drain-then-refill: each leased batch decodes until its longest
    request's stop length, then every member is acked and the next batch
    forms.  Per-request stop lengths are honored by truncation.
    ``cfg_override`` substitutes an explicit ModelConfig so benchmarks can
    compare against the continuous engine on identical custom shapes.
    """
    cfg = cfg_override if cfg_override is not None else (
        registry.get_smoke(arch) if smoke else registry.get_config(arch))
    par = registry.get_parallel(arch)
    mesh = single_device_mesh()
    S = prompt_len + gen
    shape = ShapeConfig("serve", S, batch, "prefill")
    cfg = steps_mod.resolve_cfg(cfg, shape)
    mod = steps_mod._model_module(cfg)
    metrics = Registry()

    schema = mod.lm_schema(cfg)
    params = pr.init_params(schema, jax.random.key(seed), cfg.param_dtype)
    prefill = steps_mod.build_prefill(cfg, par, mesh, shape).jit()
    decode = steps_mod.build_decode(
        cfg, par, mesh, ShapeConfig("serve", S, batch, "decode")).jit()

    T = steps_mod.token_len(cfg, shape) if cfg.family == "audio" else prompt_len
    # prefill caches cover only the prompt; splice them into a full-length
    # cache so decode has real headroom (see cache_prefix_insert)
    pad_cache = jax.jit(steps_mod.cache_prefix_insert, donate_argnums=0)
    ex_abs, _ = steps_mod.extras_specs(cfg, batch)
    extras = ()
    if ex_abs:
        extras = ({k: jnp.zeros(v.shape, v.dtype)
                   for k, v in ex_abs.items()},)

    results: Dict[int, list] = {}
    t_start = time.perf_counter()
    decode_s = 0.0
    with mesh:
        if warmup:
            dummy = jnp.ones((batch, T), jnp.int32)
            last, small = prefill(params, dummy, *extras)
            caches = pad_cache(steps_mod.init_cache(cfg, batch, S), small)
            tok = jnp.argmax(last, -1).astype(jnp.int32)[:, None]
            decode(params, caches, tok, jnp.int32(T))
            t_start = time.perf_counter()
        # requests enqueue after warmup so TTFT (enqueue -> first token,
        # same accounting as the continuous engine) excludes compile time
        queue = _request_queue(requests, cfg, n_requests=n_requests,
                               prompt_len=prompt_len, gen=gen, seed=seed,
                               gen_lens=gen_lens,
                               lease_timeout=lease_timeout)
        while not queue.drained():
            # ---- batch formation (drain-then-refill barrier)
            leased = []
            while len(leased) < batch:
                got = queue.lease("server")
                if got is None:
                    break
                leased.append(got)
            if not leased:
                time.sleep(0.001)
                continue
            prompts = np.ones((batch, T), np.int32)
            want = [gen] * len(leased)
            for row, (_, req) in enumerate(leased):
                prompts[row, :len(req["prompt"][:T])] = req["prompt"][:T]
                want[row] = min(int(req.get("max_new_tokens", gen)), gen)

            # ---- prefill -> first token + cache
            t0 = time.perf_counter()
            last, small = prefill(params, jnp.asarray(prompts), *extras)
            caches = pad_cache(steps_mod.init_cache(cfg, batch, S), small)
            tok = jnp.argmax(last, -1).astype(jnp.int32)[:, None]
            metrics.gauge(GAUGES.PREFILL_S, time.perf_counter() - t0)
            now = time.monotonic()      # the queue's clock, for TTFT
            for tid, _ in leased:
                metrics.gauge(GAUGES.TTFT_S, now - queue.enqueued_at(tid))

            # ---- decode loop: the whole batch runs to max(want)
            out_tokens = [np.asarray(tok)]
            t1 = time.perf_counter()
            for g in range(max(want) - 1):
                tok, caches = decode(params, caches, tok,
                                     jnp.int32(T + g))
                out_tokens.append(np.asarray(tok))
            decode_s += time.perf_counter() - t1

            gen_tok = np.concatenate(out_tokens, axis=1)
            now = time.monotonic()
            for row, (tid, req) in enumerate(leased):
                results[req["id"]] = gen_tok[row, :want[row]].tolist()
                queue.ack(tid, "server")
                metrics.inc(GAUGES.COMPLETED)
                metrics.inc(GAUGES.TOKENS, want[row])
                metrics.gauge(GAUGES.LATENCY_S,
                              now - queue.enqueued_at(tid))
    wall = time.perf_counter() - t_start
    record_serving_totals(metrics, sum(len(v) for v in results.values()),
                          wall, decode_s)
    return results, metrics


def main():
    ap = argparse.ArgumentParser()
    cli.add_manifest(ap)
    cli.add_arch(ap)
    cli.add_smoke(ap)
    cli.add_seed(ap)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--slots", "--batch", dest="slots", type=int, default=4)
    ap.add_argument("--static", action="store_true",
                    help="legacy drain-then-refill batcher (baseline)")
    ap.add_argument("--spread", action="store_true",
                    help="heterogeneous stop lengths (gen halved 4x, "
                         "cycled) — the workload continuous batching "
                         "wins on")
    args = ap.parse_args()
    cli.enable_compile_cache()
    gen_lens = None
    if args.spread:
        gen_lens = [max(1, args.gen // (2 ** i)) for i in range(4)]
    if args.static:
        if args.manifest:
            raise SystemExit("--static is the benchmark baseline, not an "
                             "API workload: it cannot run a --manifest "
                             "declaration")
        results, metrics = serve_static(
            args.arch, smoke=args.smoke, n_requests=args.requests,
            prompt_len=args.prompt_len, gen=args.gen, batch=args.slots,
            seed=args.seed, gen_lens=gen_lens)
        mode = "static"
    else:
        spec = cli.manifest_spec(args, ServeJob.KIND)
        if spec is None:
            spec = serve_job(args.arch, smoke=args.smoke,
                             n_requests=args.requests,
                             prompt_len=args.prompt_len, gen=args.gen,
                             batch=args.slots, seed=args.seed,
                             gen_lens=gen_lens)
        out = apply_serve(spec)
        results, metrics = out["results"], out["metrics"]
        mode = "continuous"
    print(f"[serve:{mode}] completed {len(results)} requests")
    print(metrics.to_csv())
    print()
    print(table_one([serving_report(metrics, step=f"serve ({mode})")]))


if __name__ == "__main__":
    main()
