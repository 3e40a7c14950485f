"""Read the numbers that decide ``correct``, for setting their limits:
the program's on many seeds, the control's (the reference at a lower
precision in the program's place) and planted faults' on a few, all in
one process on the chip.

    python3 bench/tools/readings.py --workload serve-phi4-decode \\
        --seconds 10 --seeds 1 2 3 --control fp8 --control-seeds 3

Prints one JSON line per reading: each number compared and whether the
cell's limits call it correct, the control judged by the same
comparison as the program.  The limits themselves are set by hand from
these readings (see PERF.md) and written into
bench/limits/<workload>.json.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def _row(what, judge, ctx, got, **extra):
    """One reading: each number compared, and whether the cell's limits
    (where the limits file has them all) call it correct."""
    try:
        checks, ok = judge(ctx, got)
    except KeyError:
        checks, ok = None, None
    if checks:
        values = {k: c["value"] for k, c in checks.items()}
    else:
        values = {k: v for k, v in dict(got).items() if isinstance(v, float)}
    return dict(what=what, correct=ok, **values, **extra)


def serve_readings(ctx, first, controls):
    from bench.runners import serve
    rec = serve.window(ctx)
    rids = serve.sample(ctx, rec)
    out = [_row("program", serve.judge, ctx, serve.gaps(ctx, rec, rids),
                dead=rec["dead"])]
    if first:
        for c in controls:
            out.append(_row(f"control:{c}", serve.judge, ctx,
                            serve.gaps(ctx, rec, rids, c)))
    return out


def train_readings(ctx, first, controls, faults, program=True):
    from bench import faults as fault_mod
    from bench.runners import train

    def run_program(what, fault=None):
        with fault() if fault else contextlib.nullcontext():
            rec = train.window(ctx)
        got = train.program(ctx, rec)
        feed = train.feed_ok(ctx, rec)
        del rec
        gc.collect()
        out = dict(train.check(ctx, got), feed_ok=feed)
        return _row(what, train.judge, ctx, out, losses=got["losses"],
                    ref_losses=out["ref_losses"])

    out = [run_program("program")] if program else []
    if first:
        for c in controls:
            low = train.reference(ctx, c, keep_grad=True)
            out.append(_row(f"control:{c}", train.judge, ctx,
                            train.check(ctx, low), losses=low["losses"]))
            del low
            gc.collect()
        for f in faults:
            out.append(run_program(f"fault:{f}", fault_mod.FAULTS[f]))
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control", nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--fault", nargs="*", default=[])
    ap.add_argument("--no-program", action="store_true",
                    help="read only the controls and faults")
    ap.add_argument("--out", default="build/readings.jsonl")
    args = ap.parse_args()
    from bench import cell, spec
    from bench.harness import Ctx
    man = spec.manifest()
    w = spec.workload(man, args.workload)
    kind = cell.require_chips(int(w["chips"]))
    cell.enable_cache()
    mix = spec.traffic(w["traffic"])
    Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    with open(args.out, "a") as fh:
        for i, seed in enumerate(args.seeds):
            ctx = Ctx(workload=w["name"], cfg=spec.config(w["config"]),
                      mix=mix, limits=spec.limits(w["name"]), seed=seed,
                      seconds=args.seconds,
                      trace=False, chips=int(w["chips"]),
                      t_start=time.monotonic(), peaks=cell.peaks_for(kind))
            first = i < args.control_seeds
            if mix["runner"] == "serve":
                rows = serve_readings(ctx, first, args.control)
            else:
                rows = train_readings(ctx, first, args.control, args.fault,
                                      not args.no_program)
            for row in rows:
                line = json.dumps(dict(workload=w["name"], seed=seed, **row))
                print(line, flush=True)
                fh.write(line + "\n")
            gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
