"""Shared launcher flags + manifest loading — one place, no drift.

``launch/train.py``, ``launch/serve.py`` and ``launch/dryrun.py`` used
to each re-declare their own ``--arch``/``--smoke``/``--seed`` argparse
surface (and their defaults had already diverged); the manifest-driven
CLIs declare them here once.  ``--manifest job.json`` short-circuits the
flag surface entirely: the file IS the workload declaration
(``repro.api.resources``), exactly like ``kubectl apply -f``.
"""
from __future__ import annotations

import argparse
import os
from pathlib import Path
from typing import Optional

import jax

from repro.api.resources import WorkloadSpec, load_manifest

DEFAULT_ARCH = "phi4-mini-3.8b"
# the persistent compile cache's fixed home inside the checkout (listed in
# .gitignore): the path is part of the cache key, so it must never move
COMPILE_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is left to JAX; otherwise the
    cache lives at ``COMPILE_CACHE_DIR``, so a cold full-width compile is
    paid once per checkout rather than once per process."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(COMPILE_CACHE_DIR))
    return str(COMPILE_CACHE_DIR)


def add_arch(ap: argparse.ArgumentParser, *, default: str = DEFAULT_ARCH,
             restrict: bool = True) -> None:
    """``--arch <id>`` from the config registry.  ``restrict=False``
    (the dry-run sweep) accepts ids the registry resolves lazily."""
    from repro.configs import registry
    kw = {"choices": list(registry.ARCHS)} if restrict else {}
    ap.add_argument("--arch", default=default, **kw)


def add_smoke(ap: argparse.ArgumentParser) -> None:
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-shape config (CPU-sized)")


def add_seed(ap: argparse.ArgumentParser, *, default: int = 0) -> None:
    ap.add_argument("--seed", type=int, default=default)


def add_manifest(ap: argparse.ArgumentParser) -> None:
    ap.add_argument("--manifest", default="",
                    help="workload manifest (JSON, see docs/api.md); "
                         "when given, the other workload flags are "
                         "ignored — the file is the declaration")


def manifest_spec(args, expect_kind: str) -> Optional[WorkloadSpec]:
    """The manifest's spec (validated to ``expect_kind``), or None when
    ``--manifest`` was not passed."""
    path = getattr(args, "manifest", "")
    if not path:
        return None
    spec = load_manifest(path)
    if spec.KIND != expect_kind:
        raise SystemExit(
            f"--manifest {path}: kind {spec.KIND!r} cannot be launched "
            f"by this driver (expects {expect_kind!r})")
    return spec
