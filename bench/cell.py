"""Run one cell of BENCHMARK.json on the chips of this machine.

    python3 bench/cell.py --workload <name> --seed <n> --seconds <s> \\
        --trace <0|1>

Finds the cell's configuration, traffic mix and correctness limits by
name (bench/spec.py), refuses to run without a TPU holding the chips the
cell asks for, turns on JAX's compilation cache at its fixed place in
the checkout, and hands the run to the mix's runner (bench/runners/).
The runner makes the weights from the seed, warms up, measures for
``--seconds`` and checks what the timed path produced against the
reference.  Each of the cell's metrics is then read by its own reader
(bench/metrics/<name>.py): the end-to-end metrics with ``--trace 0``,
the per-layer ones, from a profiler trace of part of the window, with
``--trace 1``.

The numbers compared for ``correct`` go to standard error as its last
lines, each beside its limit; the last line of standard output is the
result as one JSON object.  Without a TPU the command prints no result
and exits with 3.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import spec  # noqa: E402

NO_DEVICE = 3


def _log(**kv) -> None:
    print("[bench] " + " ".join(f"{k}={v}" for k, v in kv.items()),
          file=sys.stderr, flush=True)


def require_chips(chips: int) -> str:
    """The device kind, when JAX's backend is a TPU with ``chips``
    devices; raises otherwise (there is no CPU fallback)."""
    import jax
    backend = jax.default_backend()
    if backend != "tpu":
        raise RuntimeError(f"no TPU: JAX's backend is {backend!r}")
    n = len(jax.devices())
    if n < chips:
        raise RuntimeError(f"the cell needs {chips} chips, JAX sees {n}")
    return jax.devices()[0].device_kind


def peaks_for(kind: str) -> dict:
    table = json.loads((spec.BENCH_DIR / "peaks.json").read_text())
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


def enable_cache() -> str:
    import jax
    from repro.launch.cli import enable_compile_cache
    path = enable_compile_cache()
    # every program, however small or quick to compile, comes from the
    # cache after the first run: set-up does the same work each time
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def reduce_trace(rec: dict, mix: dict) -> None:
    from bench import trace
    path = rec.get("trace_path")
    if path is None:
        rec["trace"] = {}
        return
    t = mix.get("trace", {})
    rec["trace"] = trace.reduce(trace.load(path),
                                kernels=t.get("kernels", ()),
                                spans=t.get("spans", ()))


def result(man: dict, ctx, rec: dict, bench_dir: Path = spec.BENCH_DIR
           ) -> dict:
    """The result line: the cell's metrics, each read by its reader."""
    metrics = {}
    for m in spec.cell_metrics(man, ctx.workload, ctx.trace):
        value = spec.module("metrics", m["name"], bench_dir).read(rec, ctx)
        if value is not None and math.isfinite(value):
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    device = dict(rec["device"])
    checks = {}
    for name, c in rec["checks"].items():
        v = c["value"]
        # a comparison that gave no finite number has failed
        checks[name] = dict(c, value=v if v is None or math.isfinite(v)
                            else None)
    correct = bool(rec["correct"]) and all(
        c["value"] is not None for c in checks.values())
    out = {"correct": correct,
           "attempted": int(rec["attempted"]),
           "failed": int(rec["failed"]), "metrics": metrics}
    out["device"] = device
    if ctx.trace:
        t = rec.get("trace") or {}
        device["busy_s"] = t.get("busy_s", 0.0)
        device["window_s"] = t.get("window_s", 0.0)
        out["breakdown"] = {
            "device_ops": [[n, s] for n, s in t.get("device_ops", [])],
            "idle_gaps": [[n, s] for n, s in t.get("idle_gaps", [])]}
    out["checks"] = checks
    return out


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             root: Path = spec.ROOT, bench_dir: Path = spec.BENCH_DIR,
             check_device: bool = True, t_start: float = T_START) -> dict:
    """One run of one cell; returns the result line as a dict.

    ``root`` holds the manifest and ``bench_dir`` the cell's files;
    ``check_device=False`` (tests on the CPU only) skips the look for a
    chip and its peaks."""
    from bench.harness import Ctx
    man = spec.manifest(root)
    w = spec.workload(man, workload)
    cfg = spec.config(w["config"], bench_dir)
    mix = spec.traffic(w["traffic"], bench_dir)
    limits = spec.limits(w["name"], bench_dir)
    chips = int(w["chips"])
    if check_device:
        kind = require_chips(chips)
    else:
        import jax
        kind = jax.devices()[0].device_kind
    peaks = peaks_for(kind) if check_device else {
        "bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    cache = enable_cache()
    _log(workload=workload, config=w["config"], traffic=w["traffic"],
         chips=chips, seed=seed, seconds=seconds, trace=int(trace),
         device_kind=kind, compile_cache=cache)
    ctx = Ctx(workload=workload, cfg=cfg, mix=mix, limits=limits,
              seed=seed, seconds=seconds, trace=trace, chips=chips,
              t_start=t_start, peaks=peaks)
    rec = spec.module("runners", mix["runner"], bench_dir).run(ctx)
    try:
        if trace:
            reduce_trace(rec, mix)
        out = result(man, ctx, rec, bench_dir)
    finally:
        if rec.get("tracer") is not None:
            rec["tracer"].close()
        if rec.get("trace_dir"):
            import shutil
            shutil.rmtree(rec["trace_dir"], ignore_errors=True)
    _summary(rec)
    return out


def _summary(rec: dict) -> None:
    if rec["kind"] == "serve":
        lo, hi = rec["t0"], rec["t_end"]
        steps = rec["spans"].of("decode_step", lo, hi)
        gaps = [b[1] - a[2] for a, b in zip(steps, steps[1:])]
        _log(requests_put=rec["requests_put"],
             requests_done=rec["requests_done"],
             prefills_in_window=len(rec["spans"].of("prefill", lo, hi)),
             decode_steps_in_window=len(steps),
             slowest_step_s=max((b - a for _, a, b, _ in steps), default=0),
             longest_gap_between_steps_s=max(gaps, default=0),
             **rec["counts"],
             setup_s=rec["setup_s"])
    else:
        _log(steps_in_window=rec["steps_in_window"],
             checked_losses=rec["losses"][:3],
             reference_losses=rec["compare"]["ref_losses"],
             leaves_left_out=rec["compare"]["left_out"],
             segments=rec["segments"], setup_s=rec["setup_s"])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0 or args.seconds <= 0:
        ap.error("--seed must be >= 0 and --seconds > 0")
    try:
        out = run_cell(args.workload, args.seed, args.seconds,
                       bool(args.trace))
    except (ImportError, spec.SpecError, RuntimeError, KeyError) as e:
        print(f"bench: {type(e).__name__}: {e}", file=sys.stderr)
        return NO_DEVICE
    for name, c in out["checks"].items():
        print(f"[check] {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
