"""Serving driver — a thin manifest CLI over the unified workload API.

    PYTHONPATH=src python -m repro.launch.serve --arch phi4-mini-3.8b \
        --smoke --requests 8 --prompt-len 32 --gen 16 --slots 4
    PYTHONPATH=src python -m repro.launch.serve --manifest serve.json

Both forms declare the SAME ``repro.api.ServeJob`` resource and apply it
through a ``Session``: requests ride a WorkQueue (the paper's Redis
job-queue pattern) into the continuous batcher (repro.serving) — a fixed
pool of decode slots, per-request prefill into a slotted KV/state cache,
one fused per-slot decode step per iteration, immediate evict/refill.
"""
from __future__ import annotations

import argparse
from typing import Optional, Sequence

import jax

from repro.api import ServeJob, Session
from repro.core.metrics import Registry, table_one
from repro.core.orchestrator import Cluster
from repro.launch import cli
from repro.serving.report import serving_report


def serve_job(arch: str, *, smoke: bool, n_requests: int, prompt_len: int,
              gen: int, batch: int = 4, seed: int = 0,
              gen_lens: Optional[Sequence[int]] = None) -> ServeJob:
    """The ServeJob resource the flag surface declares."""
    return ServeJob(
        name=f"serve-{arch}", arch=arch, smoke=smoke,
        n_requests=n_requests, prompt_len=prompt_len, max_new_tokens=gen,
        slots=batch, seed=seed,
        gen_lens=tuple(gen_lens) if gen_lens is not None else None)


def apply_serve(spec: ServeJob, *, timeout: float = 3600.0):
    """Run one ServeJob on a fresh one-host cluster Session."""
    session = Session(cluster=Cluster(devices=jax.devices(),
                                      metrics=Registry()))
    return session.apply(spec).wait(timeout)


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
    ap.add_argument("--spread", action="store_true",
                    help="heterogeneous stop lengths (gen halved 4x, "
                         "cycled)")
    args = ap.parse_args()
    cli.enable_compile_cache()
    gen_lens = None
    if args.spread:
        gen_lens = [max(1, args.gen // (2 ** i)) for i in range(4)]
    spec = cli.manifest_spec(args, ServeJob.KIND)
    if spec is None:
        spec = serve_job(args.arch, smoke=args.smoke,
                         n_requests=args.requests,
                         prompt_len=args.prompt_len, gen=args.gen,
                         batch=args.slots, seed=args.seed,
                         gen_lens=gen_lens)
    out = apply_serve(spec)
    results, metrics = out["results"], out["metrics"]
    print(f"[serve] completed {len(results)} requests")
    print(metrics.to_csv())
    print()
    print(table_one([serving_report(metrics, step="serve")]))


if __name__ == "__main__":
    main()
