import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512"
                           # XLA:CPU's LICM hoists per-layer f32 converts out
                           # of the update scan (whole-tree f32 temps); the
                           # TPU pipeline's memory-aware passes undo such
                           # hoists, so disable it for parity (EXPERIMENTS
                           # §Dry-run discusses the CPU-backend deltas).
                           " --xla_disable_hlo_passes=while-loop-invariant-code-motion"
                           ).strip()
"""Multi-pod dry-run: lower + compile every (arch x shape) cell on the
production meshes, with 512 placeholder host devices (set above, BEFORE any
jax import — jax locks the device count on first init).

    PYTHONPATH=src python -m repro.launch.dryrun --arch phi4-mini-3.8b \
        --shape train_4k [--multi-pod] [--collectives]
    PYTHONPATH=src python -m repro.launch.dryrun --all --out experiments/dryrun

Per cell:
  * memory_analysis()  — per-chip bytes (argument/output/temp) proving fit;
  * cost_analysis()    — recorded as-is (NOTE: XLA does not traverse while
    bodies, so scan-hidden flops are undercounted; §Roofline uses the
    analytic accounting in repro.roofline.flops instead);
  * collective bytes   — G-diff method: the same model is built UNROLLED at
    G=1 and G=2 layer-groups; per-group bytes = C(G2)-C(G1), and
    total = C(G1) + (G_full-1) * per_group.  This recovers true trip counts
    from the compiled artifact (repro.roofline.hlo parses operand bytes).
"""
import argparse          # noqa: E402
import dataclasses       # noqa: E402
import json              # noqa: E402
import time              # noqa: E402
import traceback         # noqa: E402
from pathlib import Path  # noqa: E402

import jax               # noqa: E402

from repro.configs import registry  # noqa: E402
from repro.configs.base import SHAPES  # noqa: E402
from repro.launch import cli  # noqa: E402
from repro.launch.mesh import make_production_mesh, mesh_num_chips  # noqa: E402
from repro.roofline import hlo as hlo_mod  # noqa: E402
from repro.runtime import steps as steps_mod  # noqa: E402


def _mem_dict(compiled) -> dict:
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return {}
    keys = ("argument_size_in_bytes", "output_size_in_bytes",
            "temp_size_in_bytes", "generated_code_size_in_bytes",
            "alias_size_in_bytes")
    out = {}
    for k in keys:
        v = getattr(ma, k, None)
        if v is not None:
            out[k] = int(v)
    return out


def per_device_bytes(mem: dict) -> int:
    return (mem.get("argument_size_in_bytes", 0)
            + mem.get("temp_size_in_bytes", 0)
            + mem.get("output_size_in_bytes", 0)
            - mem.get("alias_size_in_bytes", 0))


def _compile_cell(cfg, par, ocfg, mesh, shape):
    bundle = steps_mod.build_step(cfg, par, ocfg, mesh, shape)
    with mesh:
        lowered = bundle.lower()
        compiled = lowered.compile()
    return compiled


def _reduced_cfg(cfg, groups: int):
    L = len(cfg.block_pattern)
    kw = dict(num_layers=L * groups)
    if cfg.family == "audio":
        kw["encoder_layers"] = groups
    return cfg.replace(**kw)


def gdiff_collectives(cfg, par, ocfg, mesh, shape, verbose=True) -> dict:
    """True per-step collective bytes via the G-diff method (see module doc)."""
    par_u = dataclasses.replace(par, scan_layers=False)
    out = {}
    for g in (1, 2):
        compiled = _compile_cell(_reduced_cfg(cfg, g), par_u, ocfg, mesh,
                                 shape)
        out[g] = hlo_mod.collective_bytes(compiled.as_text())
    G = cfg.num_groups if cfg.family != "audio" else cfg.num_layers
    kinds = set(out[1]) | set(out[2])
    # clamp: compile-to-compile fusion noise can make tiny deltas negative
    per_group = {k: max(out[2].get(k, 0) - out[1].get(k, 0), 0)
                 for k in kinds}
    total = {k: out[1].get(k, 0) + (G - 1) * per_group[k] for k in kinds}
    total["total"] = sum(v for k, v in total.items() if k != "total")
    per_group["total"] = sum(v for k, v in per_group.items() if k != "total")
    if verbose:
        print(f"  [gdiff] per-group {per_group.get('total', 0)/2**20:.0f} MiB"
              f" -> step total {total['total']/2**30:.2f} GiB")
    return {"per_group": per_group, "step_total": total, "groups": int(G)}


def run_cell(arch: str, shape_name: str, *, multi_pod: bool,
             par_override=None, opt_override=None, verbose: bool = True,
             collectives: bool = False) -> dict:
    cfg = registry.get_config(arch)
    par = par_override or registry.get_parallel(arch)
    ocfg = opt_override or registry.get_optimizer(arch)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    bundle = steps_mod.build_step(cfg, par, ocfg, mesh, shape)
    with mesh:
        lowered = bundle.lower()
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
    mem = _mem_dict(compiled)
    cost = compiled.cost_analysis()
    text = compiled.as_text()
    coll = hlo_mod.collective_bytes(text)
    counts = hlo_mod.collective_counts(text)
    rec = {
        "arch": arch, "shape": shape_name, "kind": shape.kind,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "chips": mesh_num_chips(mesh),
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "memory": mem, "per_device_bytes": per_device_bytes(mem),
        "xla_flops_per_device": cost.get("flops", 0.0),
        "xla_bytes_accessed": cost.get("bytes accessed", 0.0),
        "module_collective_bytes": coll, "collective_counts": counts,
    }
    if collectives:
        try:
            rec["gdiff"] = gdiff_collectives(cfg, par, ocfg, mesh, shape,
                                             verbose=verbose)
        except Exception as e:
            rec["gdiff_error"] = repr(e)
            if verbose:
                print(f"  [gdiff] FAILED: {e}")
    if verbose:
        print(f"[dryrun] {arch} x {shape_name} on {rec['mesh']}: "
              f"lower {t_lower:.1f}s compile {t_compile:.1f}s "
              f"args {mem.get('argument_size_in_bytes', 0)/2**30:.2f} "
              f"temp {mem.get('temp_size_in_bytes', 0)/2**30:.2f} GiB")
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    # shared flag helper (launch/cli.py): same --arch surface as the
    # train/serve drivers, unrestricted for sweep configs
    cli.add_arch(ap, restrict=False)
    ap.add_argument("--shape", default="train_4k", choices=list(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--collectives", action="store_true",
                    help="measure true collective bytes via G-diff")
    ap.add_argument("--all", action="store_true",
                    help="sweep every assigned (arch x shape) cell")
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()
    cli.enable_compile_cache()

    out_dir = Path(args.out)
    out_dir.mkdir(parents=True, exist_ok=True)

    if args.all:
        cells = registry.cells()
    else:
        cells = [(args.arch, SHAPES[args.shape], False)]
    meshes = [False, True] if (args.both_meshes or args.all) else [args.multi_pod]

    failures = []
    for arch, shape, _ in cells:
        for mp in meshes:
            tag = f"{arch}__{shape.name}__{'2x16x16' if mp else '16x16'}"
            path = out_dir / f"{tag}.json"
            if path.exists():
                print(f"[dryrun] skip cached {tag}")
                continue
            try:
                # G-diff only on the single-pod mesh (roofline is single-pod)
                rec = run_cell(arch, shape.name, multi_pod=mp,
                               collectives=args.collectives and not mp)
                path.write_text(json.dumps(rec, indent=1))
            except Exception as e:  # a failure here is a bug in the system
                failures.append((tag, repr(e)))
                print(f"[dryrun] FAIL {tag}: {e}")
                traceback.print_exc()
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for tag, err in failures:
            print(" ", tag, err)
        raise SystemExit(1)
    print("\nall dry-run cells passed")


if __name__ == "__main__":
    main()
