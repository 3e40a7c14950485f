"""Size a serving mix: the most decode slots whose compiled paged decode
step plans at most a share of the chip's memory.

    python3 bench/tools/size_serve.py --config phi4-mini-3.8b \\
        --prompt-len 128 --cache-len 1024 --slots 16 20 24

Compiles the engine's paged decode step (parameters, block pool sized as
the engine sizes it, and the step's temporaries) for each slot count and
prints what ``memory_analysis()`` plans, against ``--share`` of the
chip's ``bytes_limit``.  On a machine without a TPU it compiles for a
described v5e chip instead and takes the limit from ``--bytes-limit``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def plan(cfg, par, slots: int, prompt_len: int, cache_len: int,
         block_size: int, device) -> int:
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from repro.configs.base import ShapeConfig
    from repro.runtime import steps as steps_mod
    shape = ShapeConfig("serve", cache_len, slots, "decode")
    nb_total = cache_len // block_size
    pool_blocks = 1 + slots * nb_total + 2 * (prompt_len // block_size)
    mesh = Mesh(np.array([device]).reshape(1, 1),
                ("data", "model"))
    bundle = steps_mod.build_paged_decode(
        steps_mod.resolve_cfg(cfg, shape), par, mesh, shape,
        block_size=block_size, num_blocks=pool_blocks)
    args = [jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        a, s) for a, s in zip(bundle.abstract_args, bundle.in_shardings)]
    ma = bundle.jit().lower(*args).compile().memory_analysis()
    return int(ma.argument_size_in_bytes + ma.output_size_in_bytes
               - ma.alias_size_in_bytes + ma.temp_size_in_bytes)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--prompt-len", type=int, required=True)
    ap.add_argument("--cache-len", type=int, required=True)
    ap.add_argument("--slots", type=int, nargs="+", required=True)
    ap.add_argument("--block-size", type=int, default=8)
    ap.add_argument("--share", type=float, default=0.9)
    ap.add_argument("--bytes-limit", type=int, default=16_909_336_064)
    args = ap.parse_args()
    import jax

    from bench import spec
    from bench.model import program_config
    from repro.configs import registry
    c = spec.config(args.config)
    cfg = program_config(c)
    par = registry.get_parallel(c["arch"])
    if jax.default_backend() == "tpu":
        device = jax.devices()[0]
        limit = int(device.memory_stats()["bytes_limit"])
    else:
        os.environ.setdefault("TPU_LOG_DIR", "disabled")
        from jax.experimental import topologies
        device = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices[0]
        limit = args.bytes_limit
    for slots in args.slots:
        need = plan(cfg, par, slots, args.prompt_len, args.cache_len,
                    args.block_size, device)
        print(json.dumps({"slots": slots, "cache_len": args.cache_len,
                          "planned_bytes": need, "bytes_limit": limit,
                          "share": need / limit,
                          "fits": need <= args.share * limit}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
