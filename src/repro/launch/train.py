"""Training driver — a thin manifest CLI over the unified workload API.

    PYTHONPATH=src python -m repro.launch.train --arch phi4-mini-3.8b \
        --steps 50 --batch 4 --seq 128 --smoke --ckpt-dir /tmp/run1
    PYTHONPATH=src python -m repro.launch.train --manifest train.json

Both forms declare the SAME ``repro.api.TrainJob`` resource and apply it
through a ``Session`` on a one-host cluster; ``--manifest`` is the
kubectl path (the file is the declaration), the flags are sugar that
builds the identical manifest.  A single-device run is the degenerate
case of elastic training (repro.elastic); ``--fail-at`` injects ONE
crash at that step and the supervisor restores from the latest
checkpoint within the same invocation.

``train(...)`` is kept as a deprecated shim for existing callers — it
builds the TrainJob and delegates to ``Session.apply`` (the equivalence
is pinned by tests/test_api_equivalence.py).
"""
from __future__ import annotations

import argparse

import jax

from repro.api import Session, TrainJob
from repro.core.metrics import Registry
from repro.core.orchestrator import Cluster
from repro.launch import cli
from repro.launch.mesh import PRODUCTION_MESH_SHAPE


def train_job(arch: str, *, steps: int, seq: int, batch: int, smoke: bool,
              ckpt_dir: str = "", ckpt_every: int = 0, fail_at: int = -1,
              log_every: int = 10, production_mesh: bool = False,
              cfg_override=None, seed: int = 0,
              device_steps: int = 1) -> TrainJob:
    """The TrainJob resource the legacy flag surface declares."""
    config = None
    if cfg_override is not None:
        from repro.api.runners import dataclass_kwargs
        config = dataclass_kwargs(cfg_override)
    return TrainJob(
        name=f"train-{arch}", steps=steps, arch=arch, smoke=smoke,
        seq_len=seq, global_batch=batch,
        base_shape=PRODUCTION_MESH_SHAPE if production_mesh else (1, 1),
        max_data=None if production_mesh else 1,
        ckpt_dir=ckpt_dir, ckpt_every=ckpt_every, keep=2,
        log_every=log_every, fail_at=fail_at, seed=seed, config=config,
        device_steps=device_steps)


def apply_train(spec: TrainJob, *, timeout: float = 3600.0):
    """Run one TrainJob on a fresh one-host cluster Session."""
    metrics = Registry()
    session = Session(cluster=Cluster(devices=jax.devices(),
                                      metrics=metrics))
    out = session.apply(spec).wait(timeout)
    out["metrics"] = metrics
    return out


def train(arch: str, *, steps: int, seq: int, batch: int, smoke: bool,
          ckpt_dir: str = "", ckpt_every: int = 0, fail_at: int = -1,
          log_every: int = 10, production_mesh: bool = False,
          cfg_override=None, device_steps: int = 1):
    """Deprecated shim — declare a ``repro.api.TrainJob`` and apply it
    through a ``Session`` instead.  Kept so pre-API callers (and the
    equivalence regression) keep working unchanged."""
    spec = train_job(arch, steps=steps, seq=seq, batch=batch, smoke=smoke,
                     ckpt_dir=ckpt_dir, ckpt_every=ckpt_every,
                     fail_at=fail_at, log_every=log_every,
                     production_mesh=production_mesh,
                     cfg_override=cfg_override, device_steps=device_steps)
    out = apply_train(spec)
    return {"losses": out["losses"], "params": out["params"],
            "metrics": out["metrics"], "report": out["report"]}


def main():
    ap = argparse.ArgumentParser()
    cli.add_manifest(ap)
    cli.add_arch(ap)
    cli.add_smoke(ap)
    cli.add_seed(ap)
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--fail-at", type=int, default=-1,
                    help="inject one crash at this step; the elastic "
                         "supervisor restores and finishes the run")
    ap.add_argument("--device-steps", type=int, default=1,
                    help="optimizer steps fused into one device dispatch "
                         "(lax.scan hot loop); ckpt/log cadences snap up "
                         "to multiples of this")
    args = ap.parse_args()
    cli.enable_compile_cache()
    spec = cli.manifest_spec(args, TrainJob.KIND)
    if spec is None:
        spec = train_job(args.arch, steps=args.steps, seq=args.seq,
                         batch=args.batch, smoke=args.smoke,
                         ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
                         fail_at=args.fail_at, seed=args.seed,
                         device_steps=args.device_steps)
    out = apply_train(spec)
    first, last = out["losses"][0], out["losses"][-1]
    print(f"[train] loss {first:.4f} -> {last:.4f} "
          f"({'improved' if last < first else 'NOT improved'})")


if __name__ == "__main__":
    main()
