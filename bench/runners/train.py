"""Training cells: a ``TrainJob`` applied through the elastic trainer as
``repro.api`` applies it on a cluster, at the depth the configuration
file fixes, stopped at the window's end.

The trainer builds one compiled step and its state and drives it from
the seed.  Its first ``check_steps`` steps run in set-up, through the
same call and feed as the window's; the benchmark watches that call
(``runtime.steps.build_train_chunk``'s jitted step, wrapped while the
trainer builds it) to read the optimizer's first moment after step 1,
copy the parameters after the last checked step, and note when each
step's loss is ready on the device.  The window opens when the last
checked step is done and closes ``--seconds`` later; the trainer is then
drained between steps (no goodbye checkpoint: checkpointing is off).
Set-up that has not finished the checked steps within the mix's
``setup_limit_s`` fails the run: a program that no longer builds its
step through the watched call would otherwise train on unseen.

Correctness compares the checked steps with the float32 reference run
from the benchmark's own weights and batches: each step's loss, each
leaf's first gradient as the optimizer took it (its first moment over
1 - b1), by its norm and by the norm of its difference from the
reference's, and each leaf's change over the checked steps.
"""
from __future__ import annotations

import gc
import queue
import shutil
import tempfile
import threading
import time
from typing import Any, Dict, List, Tuple

import numpy as np

from bench import gen, weights
from bench.harness import Ctx, Spans, device_info
from bench.model import program_config


def _norms_by_leaf(tree, scale: float = 1.0) -> Dict[str, float]:
    import jax
    import jax.numpy as jnp
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    sq = jax.jit(lambda xs: [jnp.sum(jnp.square(x.astype(jnp.float32)))
                             for x in xs])([x for _, x in flat])
    out = {}
    for (path, _), v in zip(flat, sq):
        key = "/".join(str(getattr(p, "key", p)) for p in path)
        out[key] = float(np.sqrt(float(v))) * scale
    return out


def _job(ctx: Ctx):
    from repro.api import TrainJob
    from repro.api.runners import dataclass_kwargs
    mix = ctx.mix
    return TrainJob(
        name=ctx.workload, arch=ctx.cfg["arch"], smoke=False,
        steps=int(mix["steps"]), seq_len=int(mix["seq_len"]),
        global_batch=int(mix["global_batch"]),
        base_shape=tuple(mix["base_shape"]), max_data=mix["max_data"],
        device_steps=int(mix["device_steps"]),
        config=dataclass_kwargs(program_config(ctx.cfg)),
        optimizer=dict(mix["optimizer"]), seed=ctx.seed_for("weights"),
        data_seed=ctx.seed_for("data"), verbose=False)


def window(ctx: Ctx) -> Dict[str, Any]:
    import jax
    from repro.api.runners import elastic_spec, train_pieces
    from repro.core.metrics import Registry
    from repro.core.orchestrator import Cluster
    from repro.elastic.trainer import ElasticTrainer
    from repro.runtime import steps as steps_mod

    mix = ctx.mix
    job = _job(ctx)
    _, _, ocfg = train_pieces(job)
    n_check = int(mix["check_steps"])
    clock = time.monotonic
    spans = Spans(clock)
    done_q: "queue.Queue" = queue.Queue()
    ready: List[float] = []          # completion time of each step
    losses: List[float] = []
    fed: List[Dict[str, np.ndarray]] = []
    seen: Dict[str, Any] = {}
    checked = threading.Event()
    trace = {"dir": None, "first": None, "last": None}
    calls = [0]

    def watch():
        while True:
            item = done_q.get()
            if item is None:
                return
            loss = item
            loss.block_until_ready()
            ready.append(clock())
            losses.append(float(np.asarray(loss)[0]))
            if len(ready) == n_check:
                checked.set()

    def call(fn, params, opt, batches):
        calls[0] += 1
        k = calls[0]
        if k <= n_check:
            fed.append({n: np.asarray(v)[0] for n, v in batches.items()})
        tracing = ctx.trace and trace["first"] is None and \
            len(ready) >= n_check and clock() >= seen.get("trace_at", np.inf)
        if tracing:
            jax.block_until_ready((params, opt))
            trace["dir"] = tempfile.mkdtemp(prefix="bench-trace-")
            jax.profiler.start_trace(trace["dir"])
            trace["first"] = k
        with spans.span("train_dispatch", k):
            out = fn(params, opt, batches)
        if k == 1:
            seen["first_grad"] = _norms_by_leaf(
                out[1]["m"], 1.0 / (1.0 - ocfg.b1))
            seen["first_m"] = {path: np.asarray(m)
                               for path, m in _flat(out[1]["m"]).items()}
        if k == n_check:
            seen["params"] = jax.tree.map(np.asarray, out[0])
        if trace["first"] is not None and trace["last"] is None and \
                k >= trace["first"] + int(mix["trace_steps"]) - 1:
            jax.block_until_ready(out)
            jax.profiler.stop_trace()
            trace["last"] = k
        done_q.put(out[2]["loss"])
        return out

    build = steps_mod.build_train_chunk

    def build_watched(*a, **kw):
        bundle = build(*a, **kw)
        compile_ = bundle.jit

        def jit():
            fn = compile_()
            return lambda p, o, b: call(fn, p, o, b)

        bundle.jit = jit
        return bundle

    metrics = Registry()
    cluster = Cluster(devices=jax.devices()[:ctx.chips], metrics=metrics)
    trainer = ElasticTrainer(cluster, elastic_spec(job), metrics=metrics,
                             stop=threading.Event())
    watcher = threading.Thread(target=watch, daemon=True)
    watcher.start()
    result: Dict[str, Any] = {}

    def train():
        try:
            result["out"] = trainer.run()
        except BaseException as e:        # re-raised in the caller
            result["error"] = e

    def stop():
        # drain between steps: stop the live segment first, so that the
        # trainer's own stop finds it draining and asks no goodbye save
        for j in cluster.jobs:
            for pod in j.pods:
                pod.ctx.stop.set()
        trainer.request_stop()
        runner.join(300.0)
        if runner.is_alive():
            raise RuntimeError("the trainer did not stop")

    steps_mod.build_train_chunk = build_watched
    runner = threading.Thread(target=train, daemon=True)
    deadline = clock() + float(mix["setup_limit_s"])
    try:
        runner.start()
        while not checked.wait(0.5):
            if not runner.is_alive():
                raise RuntimeError(f"training ended in set-up: "
                                   f"{result.get('error')}")
            if clock() > deadline:
                stop()
                raise RuntimeError(
                    f"{len(ready)} of {n_check} checked steps done in "
                    f"{mix['setup_limit_s']} s ({calls[0]} calls of the "
                    "watched step)")
        t0 = ready[n_check - 1]
        t_end = t0 + ctx.seconds
        seen["trace_at"] = t0 + ctx.seconds / 3
        time.sleep(max(0.0, t_end - clock()))
        stop()
    finally:
        steps_mod.build_train_chunk = build
        if trace["first"] is not None and trace["last"] is None:
            jax.profiler.stop_trace()
            trace["last"] = calls[0]
        done_q.put(None)
        watcher.join(120.0)
    if "error" in result:
        raise RuntimeError(f"training failed: {result['error']}") \
            from result["error"]
    shutil.rmtree(trainer.store.root, ignore_errors=True)
    report = trainer.report
    device = device_info(ctx.chips)
    del trainer, result, cluster
    gc.collect()
    in_window = [t for t in ready[n_check:] if t <= t_end]
    # the step running at the window's end counts for the share of it
    # that the window holds
    after = [t for t in ready[n_check:] if t > t_end]
    last = in_window[-1] if in_window else t0
    partial = (t_end - last) / (after[0] - last) if after else 0.0
    trace_path = None
    if trace["dir"] is not None:
        from bench.trace import find
        trace_path = find(trace["dir"])
    return {
        "kind": "train", "setup_s": t0 - ctx.t_start, "t0": t0,
        "t_end": t_end, "seconds": ctx.seconds, "ready": ready,
        "losses": losses, "fed": fed, "seen": seen, "spans": spans,
        "steps_in_window": len(in_window), "partial_step": partial,
        "tokens_per_step": job.global_batch * job.seq_len,
        "host_syncs_per_step": report.host_syncs_per_step,
        "segments": len(report.segments), "device": device,
        "trace_path": trace_path, "trace_dir": trace["dir"],
        "trace_steps": (None if trace["last"] is None else
                        trace["last"] - trace["first"] + 1),
    }


def _gap(prog: Dict[str, float], ref: Dict[str, float], keep) -> float:
    """The widest relative gap between two sets of leaf norms, each
    against the larger of its reference norm and the median leaf's."""
    med = float(np.median([ref[k] for k in keep]))
    return max(abs(prog[k] - ref[k]) / max(ref[k], med) for k in keep)


def _opt(ctx: Ctx) -> Dict[str, float]:
    from repro.api.runners import train_pieces
    _, _, o = train_pieces(_job(ctx))
    return {"lr": o.lr, "warmup_steps": o.warmup_steps,
            "decay_steps": o.decay_steps, "b1": o.b1, "b2": o.b2,
            "eps": o.eps, "weight_decay": o.weight_decay,
            "grad_clip": o.grad_clip}


def batches(ctx: Ctx) -> List[Dict[str, np.ndarray]]:
    """The benchmark's own copy of the checked steps' batches."""
    mix, d = ctx.mix, ctx.dims
    return [gen.zipf_batch(d.vocab, int(mix["seq_len"]),
                           int(mix["global_batch"]), ctx.seed_for("data"), i)
            for i in range(int(mix["check_steps"]))]


def reference(ctx: Ctx, quant=None, against=None, keep_grad=False
              ) -> Dict[str, Any]:
    """The float32 reference over the checked steps (at ``quant``, the
    control); ``against`` and ``keep_grad`` as ``dense_lm.train_steps``
    takes them."""
    from bench.reference import dense_lm
    d = ctx.dims
    return dense_lm.train_steps(d, weights.make(d, ctx.seed_for("weights")),
                                batches(ctx), _opt(ctx), quant,
                                against=against, keep_grad=keep_grad)


CHECKED = ("loss_gap", "grad_gap", "grad_err", "change_gap")


def numbers(got: Dict[str, Any], ref: Dict[str, Any]) -> Dict[str, Any]:
    """The numbers compared: the widest relative gap of the checked
    losses, of the leaves' first-gradient norms, and of the leaves'
    change norms (leaves whose reference gradient is under a thousandth
    of the median leaf's are left out of the change); and the widest
    norm of a leaf's first-gradient difference from the reference's
    (``grad_err``), which unbiased rounding moves where a norm hardly
    moves.  Each leaf counts against the larger of its reference norm
    and the median leaf's."""
    g_ref = ref["first_grad"]
    med = float(np.median(list(g_ref.values())))
    moved = [k for k in g_ref if g_ref[k] >= 1e-3 * med]
    err = ref["first_grad_err"]
    return {
        "loss_gap": max(abs(a - b) / abs(b)
                        for a, b in zip(got["losses"], ref["losses"])),
        "grad_gap": _gap(got["first_grad"], g_ref, list(g_ref)),
        "grad_err": max(err[k] / max(g_ref[k], med) for k in g_ref),
        "change_gap": _gap(got["change"], ref["change"], moved),
        "left_out": sorted(set(g_ref) - set(moved)),
    }


def program(ctx: Ctx, rec: Dict[str, Any]) -> Dict[str, Any]:
    """What the program did over the checked steps, as the reference
    reports it: losses, first-gradient norms and change norms."""
    from bench.reference import dense_lm
    n = int(ctx.mix["check_steps"])
    start = weights.make(ctx.dims, ctx.seed_for("weights"))
    change = {path: _change(after, dense_lm.get_leaf(start, path))
              for path, after in _flat(rec["seen"]["params"]).items()}
    del start
    gc.collect()
    return {"losses": rec["losses"][:n],
            "first_grad": rec["seen"]["first_grad"], "change": change,
            "first_grad_vec": (rec["seen"]["first_m"],
                               1.0 / (1.0 - _opt(ctx)["b1"]))}


def feed_ok(ctx: Ctx, rec: Dict[str, Any]) -> bool:
    """The checked steps were fed the benchmark's own batches, and no
    row repeats among them."""
    own = batches(ctx)
    same = len(rec["fed"]) == len(own) and all(
        np.array_equal(f[k], o[k]) for f, o in zip(rec["fed"], own)
        for k in ("tokens", "labels"))
    rows = np.concatenate([o["tokens"] for o in own])
    return same and len({r.tobytes() for r in rows}) == len(rows)


def check(ctx: Ctx, got: Dict[str, Any]) -> Dict[str, Any]:
    """The numbers of ``got`` (what ``program`` returns, or the control:
    ``reference`` at a lower precision with ``keep_grad``) against the
    reference."""
    ref = reference(ctx, against=got["first_grad_vec"])
    out = numbers(got, ref)
    out["ref_losses"] = ref["losses"]
    return out


def judge(ctx: Ctx, got: Dict[str, Any]
          ) -> Tuple[Dict[str, Dict[str, Any]], bool]:
    """Each number beside its limit, and whether all are within them
    (and, where ``got`` has ``feed_ok``, the feed was the benchmark's)."""
    checks = {k: {"value": got[k], "limit": float(ctx.limits[k]["limit"])}
              for k in CHECKED}
    ok = all(c["value"] <= c["limit"] for c in checks.values())
    if "feed_ok" in got:
        checks["feed"] = {"value": int(got["feed_ok"]), "limit": 1}
        ok = ok and bool(got["feed_ok"])
    return checks, ok


def _change(after: np.ndarray, before) -> float:
    import jax.numpy as jnp
    a = jnp.asarray(after).astype(jnp.float32)
    return float(jnp.sqrt(jnp.sum(jnp.square(a - before.astype(a.dtype)))))


def _flat(tree, prefix=""):
    if not isinstance(tree, dict):
        return {prefix: tree}
    out = {}
    for k, v in tree.items():
        out.update(_flat(v, f"{prefix}/{k}" if prefix else k))
    return out


def run(ctx: Ctx) -> Dict[str, Any]:
    rec = window(ctx)
    got = program(ctx, rec)
    rec["seen"].pop("first_m")
    got = dict(check(ctx, got), feed_ok=feed_ok(ctx, rec))
    rec["checks"], rec["correct"] = judge(ctx, got)
    rec["compare"] = got
    rec["attempted"] = rec["steps_in_window"]
    rec["failed"] = sum(1 for x in rec["losses"] if not np.isfinite(x))
    return rec
