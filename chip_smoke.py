"""On-chip smoke run of the main path: phi4-mini-3.8b served and trained on
a TPU through the Session API.

    python chip_smoke.py              # serve + train phases on one chip
    python chip_smoke.py --chips 4    # sharded training on four chips vs one

Serving applies a ``ServeJob`` for the whole 32-layer model at its
published config.  Training applies a ``TrainJob`` at phi4-mini's
published widths, cut in depth only as far as one chip's memory forces:
the depth comes from the compiled step's ``memory_analysis()``.  With
``--chips 4`` only the sharded training phase runs: the same cut model on
a (2, 2) mesh, checked against the same model and batch on one chip.
Weights and data are random, made from ``--seed``.

Each phase prints the device kind, the config and its cut, compile
seconds, wall times (smoke timings, not benchmark numbers) and the
process's peak device bytes.  Any failed check exits non-zero; without a
TPU the script exits non-zero before any phase.  The last line of a
passing run is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

ARCH = "phi4-mini-3.8b"
SERVE = dict(n_requests=8, prompt_len=128, max_new_tokens=32, slots=4,
             gen_lens=(32, 24, 16, 8))
TRAIN = dict(seq_len=2048, global_batch=2, steps=8)
# a 3.8B-scale peak rate with warmup: the launcher's 1e-3 with one warmup
# step spiked a fresh full-width model's loss from 13.0 to 20.7 at step 1
OPTIMIZER = dict(lr=3e-4, warmup_steps=4)
MIN_LAYERS = 2
MEMORY_HEADROOM = 0.9        # share of the chip's bytes_limit a step may plan
MIN_DECODE_AGREEMENT = 0.75  # greedy tokens the full forward must reproduce
LOSS_RTOL = 2e-2             # bf16 tolerance, four chips against one
SHARDED_STEPS = 4


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def log(phase: str, **kv) -> None:
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in kv.items()),
          flush=True)


def peak_bytes(dev) -> int:
    return int(dev.memory_stats()["peak_bytes_in_use"])


def free_device_memory() -> int:
    """Drop the finished phase's arrays; returns the bytes still live."""
    import jax
    gc.collect()
    return sum(a.nbytes for a in jax.live_arrays())


# ------------------------------------------------------------------ device
def require_tpu(chips: int):
    import jax

    from repro.kernels import common
    backend = jax.default_backend()
    if backend != "tpu":
        raise SmokeFailure(f"no TPU found: JAX backend is {backend!r}")
    check(not common.interpret_default(),
          "Pallas kernels would run interpreted on the TPU")
    check(common.fused_xent_default() and common.fused_adamw_default(),
          "fused xent/AdamW kernels are switched off (REPRO_FUSED_*)")
    devices = jax.devices()
    check(len(devices) >= chips,
          f"--chips {chips} needs {chips} devices, JAX sees {len(devices)}")
    return devices


# ------------------------------------------------------------------- serve
def serve_phase(dev, seed: int) -> None:
    from repro.api import ServeJob
    from repro.api.runners import resolve_serve_cfg, serve_requests
    from repro.launch.serve import apply_serve
    from repro.serving.report import GAUGES

    job = ServeJob(name="smoke-serve", arch=ARCH, smoke=False, warmup=True,
                   seed=seed, **SERVE)
    cfg = resolve_serve_cfg(job)
    requests = serve_requests(job)
    want = {r["id"]: r["max_new_tokens"] for r in requests}
    log("serve", device_kind=dev.device_kind, arch=ARCH,
        layers=cfg.num_layers, d_model=cfg.d_model, vocab=cfg.vocab_size,
        cut="none", requests=len(requests), prompt=job.prompt_len,
        stop_lengths=list(job.gen_lens), slots=job.slots)
    t0 = time.perf_counter()
    out = apply_serve(job)
    total_s = time.perf_counter() - t0
    m, results = out["metrics"], out["results"]
    completed = int(m.series(GAUGES.COMPLETED).total)
    tokens = int(m.series(GAUGES.TOKENS).total)
    check(completed == len(want),
          f"serve completed {completed} of {len(want)} requests")
    check(tokens == sum(want.values()),
          f"serve/tokens_generated {tokens} != sum of stop lengths "
          f"{sum(want.values())}")
    for rid, n in want.items():
        toks = results[rid]
        check(len(toks) == n, f"request {rid}: {len(toks)} tokens, want {n}")
        check(all(0 <= t < cfg.vocab_size for t in toks),
              f"request {rid}: token id outside the vocabulary")
    wall_s = m.series(GAUGES.WALL_S).last
    log("serve", completed=completed, tokens_generated=tokens,
        setup_and_compile_s=round(total_s - wall_s, 2),
        smoke_serve_wall_s=round(wall_s, 3),
        smoke_p50_request_latency_s=round(
            m.series(GAUGES.LATENCY_S).percentile(50), 3),
        peak_bytes_in_use=peak_bytes(dev))
    longest = max(requests, key=lambda r: r["max_new_tokens"])
    del out, m
    live = free_device_memory()
    agreement = decode_agreement(job, cfg, longest, results[longest["id"]])
    log("serve", check="full forward reproduces the served greedy tokens",
        request=longest["id"], agreement=round(agreement, 4),
        live_bytes_before_check=live, peak_bytes_in_use=peak_bytes(dev))
    check(agreement >= MIN_DECODE_AGREEMENT,
          f"full forward reproduces only {agreement:.2%} of the served "
          f"greedy tokens")


def decode_agreement(job, cfg, request, generated) -> float:
    """Share of the engine's greedy tokens that one causal forward over
    prompt + generation (teacher forced, no KV cache) predicts too.  The
    weights are rebuilt from the job's seed, as the engine built them."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import registry
    from repro.launch.mesh import single_device_mesh
    from repro.models import params as pr
    from repro.models import transformer as tfm
    from repro.models.layers import ModelCtx

    mesh = single_device_mesh()
    ctx = ModelCtx(cfg, registry.get_parallel(job.arch), mesh)
    params = pr.init_params(tfm.lm_schema(cfg), jax.random.key(job.seed),
                            cfg.param_dtype)
    P = job.prompt_len
    tokens = np.asarray([list(request["prompt"]) + list(generated[:-1])],
                        np.int32)

    @jax.jit
    def greedy(params, tokens):
        hidden, _, _ = tfm.forward(ctx, params, tokens, mode="prefill")
        logits = tfm.lm_logits(ctx, params, hidden[:, P - 1:, :])
        return jnp.argmax(logits[0], axis=-1)

    with mesh:
        ref = np.asarray(greedy(params, jnp.asarray(tokens)))
    del params
    free_device_memory()
    return float(np.mean(ref == np.asarray(generated)))


# ------------------------------------------------------------------- train
def train_job(cfg, seed: int, *, steps: int, name: str = "smoke-train",
              base_shape=(1, 1), max_data=1):
    from repro.api import TrainJob
    from repro.api.runners import dataclass_kwargs
    return TrainJob(name=name, arch=ARCH, smoke=False, steps=steps,
                    seq_len=TRAIN["seq_len"],
                    global_batch=TRAIN["global_batch"],
                    config=dataclass_kwargs(cfg), optimizer=OPTIMIZER,
                    base_shape=base_shape, max_data=max_data, log_every=1,
                    seed=seed)


def compile_train_step(cfg, seed: int):
    """Compile the one-chip step the trainer will run for ``cfg``; returns
    (compiled, planned peak bytes, compile seconds)."""
    from repro.api.runners import train_pieces
    from repro.configs.base import ShapeConfig
    from repro.launch.mesh import single_device_mesh
    from repro.runtime import steps as steps_mod

    job = train_job(cfg, seed, steps=TRAIN["steps"])
    mcfg, par, ocfg = train_pieces(job)
    shape = ShapeConfig("elastic", job.seq_len, job.global_batch, "train")
    mesh = single_device_mesh()
    t0 = time.perf_counter()
    with mesh:
        compiled = steps_mod.build_train_chunk(mcfg, par, ocfg, mesh, shape,
                                               1).lower().compile()
    ma = compiled.memory_analysis()
    need = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
    return compiled, need, time.perf_counter() - t0


def pick_depth(dev, seed: int):
    """The deepest cut of phi4-mini whose compiled training step plans at
    most ``MEMORY_HEADROOM`` of the chip's memory.  Step memory is affine
    in depth: two compiles give the line, a third confirms the choice."""
    from repro.configs import registry

    full = registry.get_config(ARCH)
    budget = MEMORY_HEADROOM * dev.memory_stats()["bytes_limit"]
    planned = {}

    def plan(n):
        compiled, need, secs = compile_train_step(
            full.replace(num_layers=n), seed)
        planned[n] = need
        log("train", probe_layers=n, planned_bytes=need,
            compile_s=round(secs, 2))
        return compiled

    plan(MIN_LAYERS)
    check(planned[MIN_LAYERS] <= budget,
          f"{MIN_LAYERS} layers plan {planned[MIN_LAYERS]} bytes, over the "
          f"budget of {budget:.0f}")
    plan(MIN_LAYERS + 1)
    per_layer = planned[MIN_LAYERS + 1] - planned[MIN_LAYERS]
    n = MIN_LAYERS + math.floor((budget - planned[MIN_LAYERS])
                                / max(per_layer, 1))
    n = max(MIN_LAYERS, min(full.num_layers, n))
    compiled = plan(n)
    while planned[n] > budget:
        n -= 1
        compiled = plan(n)
    log("train", chosen_layers=n, of_layers=full.num_layers,
        planned_bytes=planned[n], budget_bytes=int(budget),
        bytes_per_layer=per_layer)
    return full.replace(num_layers=n), compiled


def fused_kernels_compiled(compiled) -> dict:
    """Compiled Mosaic kernels per fused-kernel name in a step's HLO."""
    calls = [l for l in compiled.as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in l]
    return {k: sum(f"/{k}/pallas_call" in l for l in calls)
            for k in ("xent_fwd", "xent_bwd", "adamw_update")}


def train_phase(dev, seed: int) -> None:
    from repro.configs import registry
    from repro.launch.train import apply_train
    full = registry.get_config(ARCH)
    log("train", device_kind=dev.device_kind, arch=ARCH,
        d_model=full.d_model, heads=f"{full.num_heads}/{full.num_kv_heads}",
        head_dim=full.head_dim, d_ff=full.d_ff, vocab=full.vocab_size,
        tied=full.tie_embeddings, seq=TRAIN["seq_len"],
        global_batch=TRAIN["global_batch"], steps=TRAIN["steps"])
    cfg, compiled = pick_depth(dev, seed)
    kernels = fused_kernels_compiled(compiled)
    log("train", cut=f"num_layers {full.num_layers}->{cfg.num_layers}",
        compiled_fused_kernels=kernels)
    check(all(kernels.values()),
          f"fused kernels missing from the compiled step: {kernels}")
    del compiled
    out = apply_train(train_job(cfg, seed, steps=TRAIN["steps"]))
    losses = out["losses"]
    rep = out["report"]
    check(len(losses) == TRAIN["steps"],
          f"{len(losses)} losses for {TRAIN['steps']} steps")
    check(all(math.isfinite(x) for x in losses), f"non-finite loss {losses}")
    check(losses[-1] < losses[0],
          f"loss did not fall: {losses[0]} -> {losses[-1]}")
    steady = (rep.total_wall_s - rep.t_first_s) / max(TRAIN["steps"] - 1, 1)
    log("train", layers=cfg.num_layers, losses=[round(x, 4) for x in losses],
        first_step_incl_compile_s=round(rep.t_first_s, 2),
        smoke_step_wall_s=round(steady, 3),
        peak_bytes_in_use=peak_bytes(dev))
    del out
    free_device_memory()


def sharded_phase(devices, seed: int) -> None:
    """The cut model trained on a (2, 2) mesh of four chips, against the
    same model, data and seed on one chip."""
    import jax

    from repro.launch.train import apply_train
    dev = devices[0]
    cfg, compiled = pick_depth(dev, seed)
    del compiled
    log("sharded", device_kind=dev.device_kind, chips=len(devices),
        layers=cfg.num_layers, mesh="(2, 2)", steps=SHARDED_STEPS)
    out4 = apply_train(train_job(cfg, seed, steps=SHARDED_STEPS,
                               name="smoke-train-4", base_shape=(2, 2),
                               max_data=None))
    meshes = [tuple(s.mesh_shape) for s in out4["report"].segments]
    check(meshes == [(2, 2)], f"sharded run meshes {meshes}, want [(2, 2)]")
    leaves = jax.tree.leaves(out4["params"])
    mesh_devs = set().union(*(leaf.sharding.device_set for leaf in leaves))
    check(len(mesh_devs) == 4, f"mesh spans {len(mesh_devs)} devices")
    for leaf in leaves:
        shard_devs = {s.device for s in leaf.addressable_shards}
        check(shard_devs == mesh_devs,
              f"a parameter has shards on {len(shard_devs)} of 4 devices")
    embed = out4["params"]["embed"]
    check(embed.addressable_shards[0].data.size < embed.size,
          "the embedding is not sharded")
    losses4 = out4["losses"]
    log("sharded", devices=sorted(d.id for d in mesh_devs),
        embed_shard_shape=embed.addressable_shards[0].data.shape,
        losses=[round(x, 4) for x in losses4],
        first_step_incl_compile_s=round(out4["report"].t_first_s, 2),
        peak_bytes_in_use_chip0=peak_bytes(dev))
    del out4, leaves, embed
    free_device_memory()
    out1 = apply_train(train_job(cfg, seed, steps=SHARDED_STEPS,
                               name="smoke-train-1"))
    losses1 = out1["losses"]
    del out1
    free_device_memory()
    diffs = [abs(a - b) / abs(b) for a, b in zip(losses4, losses1)]
    log("sharded", one_chip_losses=[round(x, 4) for x in losses1],
        rel_diff=[round(d, 5) for d in diffs], rtol=LOSS_RTOL)
    check(len(losses4) == len(losses1) == SHARDED_STEPS,
          "loss counts differ between the runs")
    check(all(math.isfinite(x) for x in losses4 + losses1),
          "non-finite loss")
    check(max(diffs) <= LOSS_RTOL,
          f"four-chip losses differ from one chip by {max(diffs):.3%}")


# -------------------------------------------------------------------- main
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the sharded training phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    try:
        from repro.launch.cli import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: cannot import repro from {ROOT / 'src'}: {e}",
              file=sys.stderr)
        return 2
    try:
        devices = require_tpu(args.chips)
        log("device", platform=devices[0].platform,
            kind=devices[0].device_kind, count=len(devices),
            compile_cache=enable_compile_cache())
        if args.chips == 4:
            sharded_phase(devices[:4], args.seed)
        else:
            serve_phase(devices[0], args.seed)
            train_phase(devices[0], args.seed)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
