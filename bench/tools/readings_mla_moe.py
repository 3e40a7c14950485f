"""Read the numbers that decide ``correct`` in a cell of the DeepSeek-V3
block (runner ``serve_mla_moe``), for setting its limits: the program's
on each seed, the control's (the reference at a lower precision in the
program's place) on the first ``--control-seeds``, and those of the
program with each of ``--faults`` planted (``bench/faults_mla_moe.py``)
on the first ``--fault-seeds``, all in one process on the chip.

    python3 bench/tools/readings_mla_moe.py \\
        --workload serve-kimi-k2-reasoning --seconds 30 --seeds 1 2 \\
        --control fp8 --control-seeds 1 \\
        --faults bias_left_out router_logits_bf16 --fault-seeds 1

Prints one JSON line per reading: the checks as the cell's limits judge
them (``--margin`` in place of the limits' ``route_margin``), the gaps'
quantiles and widest values, and the largest departure of the program's
choice of experts from the reference's.  The limits are set by hand from
these readings (see PERF.md) and written into
bench/limits/<workload>.json.
"""
from __future__ import annotations

import argparse
import contextlib
import copy
import gc
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

MARGINS = (0.0005, 0.001, 0.002, 0.005, 0.01, 0.02)


def spread(got):
    g = np.concatenate(got[0])
    out = {"widest": sorted((float(x) for x in g), reverse=True)[:10],
           "quantiles": {str(q): float(np.quantile(g, q))
                         for q in (0.5, 0.9, 0.95, 0.99)},
           "mean": float(g.mean()),
           "above": {str(t): float((g > t).mean())
                     for t in (0.05, 0.1, 0.2, 0.3, 0.5)}}
    dep = np.concatenate([s["departures"] for s in got[1]])
    out["departure"] = float(dep.max())
    out["positions_departing"] = {str(m): int((dep >= m).sum())
                                  for m in MARGINS}
    out["departure_quantiles"] = {str(q): float(np.quantile(dep[dep > 0], q))
                                  for q in (0.5, 0.9, 0.99)} if (
        dep > 0).any() else {}
    out["flips"] = sum(s["flips"] for s in got[1])
    out["pairs"] = sum(s["pairs"] for s in got[1])
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control", nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, default=1)
    ap.add_argument("--faults", nargs="*", default=[])
    ap.add_argument("--fault-seeds", type=int, default=1)
    ap.add_argument("--margin", type=float, default=None)
    ap.add_argument("--out", default="build/readings_mla_moe.jsonl")
    args = ap.parse_args()
    from bench import cell, spec
    from bench.faults_mla_moe import FAULTS
    from bench.harness import Ctx
    from bench.runners import serve_mla_moe as runner
    man = spec.manifest()
    w = spec.workload(man, args.workload)
    kind = cell.require_chips(int(w["chips"]))
    cell.enable_cache()
    Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    limits = spec.limits(w["name"])
    if args.margin is not None:
        limits = copy.deepcopy(limits)
        limits["route_margin"]["value"] = args.margin
    runs = [(seed, None) for seed in args.seeds] + [
        (seed, f) for seed in args.seeds[:args.fault_seeds]
        for f in args.faults]
    with open(args.out, "a") as fh:
        for seed, fault in runs:
            ctx = Ctx(workload=w["name"], cfg=spec.config(w["config"]),
                      mix=spec.traffic(w["traffic"]), limits=limits,
                      seed=seed, seconds=args.seconds, trace=False,
                      chips=int(w["chips"]), t_start=time.monotonic(),
                      peaks=cell.peaks_for(kind))
            with FAULTS[fault]() if fault else contextlib.nullcontext():
                rec = runner.window(ctx)
            steps = rec["spans"].of("decode_step", rec["t0"], rec["t_end"])
            timing = {"setup_s": rec["setup_s"], "fill_s": rec["fill_s"],
                      "steps": len(steps),
                      "prefills": len(rec["spans"].of(
                          "prefill", rec["t0"], rec["t_end"])),
                      "step_ms": 1e3 * sum(b - a for _, a, b, _ in steps)
                      / max(len(steps), 1),
                      "serve_tok_s": spec.module(
                          "metrics", "serve_tok_s").read(rec, ctx),
                      "device": rec["device"]}
            picked = runner.sample(ctx, rec)
            del rec
            gc.collect()
            rows = [(f"fault:{fault}" if fault else "program", None)]
            if fault is None and seed in args.seeds[:args.control_seeds]:
                rows += [(f"control:{c}", c) for c in args.control]
            for what, control in rows:
                got = runner.gaps(ctx, picked, control)
                checks, ok = runner.judge(ctx, got)
                row = dict(workload=w["name"], seed=seed, what=what,
                           correct=ok, checks={k: c["value"] for k, c
                                               in checks.items()},
                           spread=spread(got), timing=timing)
                line = json.dumps(row)
                print(line, flush=True)
                fh.write(line + "\n")
            gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
