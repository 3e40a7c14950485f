"""Step functions (train / prefill / serve) + their sharding trees.

Everything the launcher, dry-run, and tests need to jit a step:
  build_train(cfg, par, ocfg, mesh)   -> StepBundle
  build_prefill(cfg, par, mesh, shape)-> StepBundle
  build_decode(cfg, par, mesh, shape) -> StepBundle

A StepBundle carries the python fn, abstract inputs, and in/out NamedShardings
so ``jax.jit(fn, in_shardings=..., out_shardings=...).lower(*abstract)`` is
one call (see launch/dryrun.py).

The step bodies name their parts with ``jax.named_scope`` (``attn``,
``mlp``, ``lm_head``, ``kv_gather``, ``kv_write``, ``kv_scatter``), which
reaches only the compiled program's metadata; ``op_scopes`` maps a built
step's compiled ops to those names, for reading a profiler trace.
"""
from __future__ import annotations

import re
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import (ModelConfig, OptimizerConfig, ParallelConfig,
                                ShapeConfig)
from repro.models import params as pr
from repro.models import transformer as tfm
from repro.models.layers import ModelCtx
from repro.optim import adamw
from repro.sharding import specs as sh


@dataclass
class StepBundle:
    fn: Callable
    abstract_args: Tuple[Any, ...]
    in_shardings: Tuple[Any, ...]
    out_shardings: Any
    donate_argnums: Tuple[int, ...] = ()
    accum_steps: int = 1      # microbatches folded into one optimizer step
    device_steps: int = 1     # optimizer steps folded into one dispatch

    @property
    def module_name(self) -> str:
        """The compiled program's name, as a profiler trace shows it."""
        return "jit_" + getattr(self.fn, "__name__", "fn")

    def jit(self):
        if _BUILT.get(self.module_name) is not self:
            _BUILT[self.module_name] = self
            _SCOPES.pop(self.module_name, None)
        return self._jit()

    def _jit(self):
        return jax.jit(self.fn, in_shardings=self.in_shardings,
                       out_shardings=self.out_shardings,
                       donate_argnums=self.donate_argnums)

    def lower(self):
        return self._jit().lower(*self.abstract_args)


# the newest bundle jitted in this process under each module name, and the
# op -> scope maps of their compiled programs, made on request
_BUILT: Dict[str, StepBundle] = {}
_SCOPES: Dict[str, Dict[str, str]] = {}


def op_scopes(module_name: str) -> Dict[str, str]:
    """{HLO op name: scope path} for the compiled program of the step last
    jitted in this process under ``module_name`` (``jit_paged_step``).

    Every instruction of the program is a key, named as a profiler trace
    names its ops (``fusion.3``, no ``%``).  Its path is the
    ``named_scope`` names its metadata carries, outermost first, with
    transforms unwrapped (``transpose(jvp(lm_head))`` is ``lm_head``):
    ``paged_step/attn/kv_write``; empty where it carries none.  Made on
    the first request by lowering and compiling the bundle again (a hit
    in a persistent compilation cache), then kept: a reader of traces
    calls it, never a step.  Raises KeyError for a name never jitted.

    The persistent cache's key leaves metadata out by default, so a hit
    could hand back a program compiled from another build's source, whose
    op names are the same and whose scopes are not: this compile keys
    the cache by the metadata too."""
    if module_name not in _SCOPES:
        bundle = _BUILT[module_name]
        mesh = jax.tree.leaves(bundle.in_shardings)[0].mesh
        flag = "jax_compilation_cache_include_metadata_in_key"
        keyed = getattr(jax.config, flag)
        jax.config.update(flag, True)
        try:
            with mesh:
                text = bundle.lower().compile().as_text()
        finally:
            jax.config.update(flag, keyed)
        _SCOPES[module_name] = hlo_scopes(text)
    return _SCOPES[module_name]


_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_WRAPPED = re.compile(r"[\w\-]+\((.*)\)")


def _scope_path(op_name: str) -> str:
    """``jit(f)/transpose(jvp(lm_head))/dot_general`` -> ``f/lm_head``:
    the scopes of an op's metadata name, without the primitive."""
    parts, depth, cur = [], 0, ""
    for ch in op_name:
        if ch == "/" and depth == 0:
            parts.append(cur)
            cur = ""
            continue
        depth += (ch == "(") - (ch == ")")
        cur += ch
    out = []
    for part in parts:                   # the last piece is the primitive
        m = _WRAPPED.fullmatch(part)
        while m:
            part = m.group(1)
            m = _WRAPPED.fullmatch(part)
        out += [q for q in part.split("/") if q]
    return "/".join(out)


def hlo_scopes(text: str) -> Dict[str, str]:
    """{instruction name: scope path} of a compiled module's text
    (``Compiled.as_text()``).  An instruction whose metadata names no op
    (a fusion, say) takes the first op name of the computation it
    calls."""
    comp_names: Dict[str, str] = {}       # computation -> first op_name
    instrs: List[Tuple[str, str, str]] = []
    comp = None
    for line in text.splitlines():
        if line and not line[0].isspace() and line.rstrip().endswith("{"):
            comp = line.split()[1 if line.startswith("ENTRY") else 0]
            comp = comp.lstrip("%")
            continue
        m = _INSTR.match(line)
        if m is None:
            continue
        op = _OP_NAME.search(line)
        calls = _CALLS.search(line)
        name = op.group(1) if op else ""
        if name and comp is not None:
            comp_names.setdefault(comp, name)
        instrs.append((m.group(1), name, calls.group(1) if calls else ""))
    return {n: _scope_path(op or comp_names.get(calls, ""))
            for n, op, calls in instrs}


def _is_axes_leaf(x):
    return isinstance(x, tuple) and all(isinstance(a, (str, type(None))) for a in x)


def _shardings(tree_abstract, tree_axes, mesh, rules):
    return jax.tree.map(
        lambda sds, ax: sh.sharding_for(sds.shape, ax, mesh, rules),
        tree_abstract, tree_axes, is_leaf=lambda x: _is_axes_leaf(x))


def _replicated(tree, mesh):
    return jax.tree.map(lambda _: NamedSharding(mesh, P()), tree)


def _model_module(cfg: ModelConfig):
    if cfg.family == "audio":
        from repro.models import encdec
        return encdec
    return tfm


def resolve_cfg(cfg: ModelConfig, shape: ShapeConfig) -> ModelConfig:
    """Bind shape-dependent stub dims (whisper frame count) into the config."""
    if cfg.family == "audio" and cfg.encoder_frames == 0:
        return cfg.replace(encoder_frames=shape.seq_len)
    return cfg


def token_len(cfg: ModelConfig, shape: ShapeConfig) -> int:
    """Token-sequence length for train/prefill (enc-dec: decoder length)."""
    return cfg.decoder_len if cfg.family == "audio" else shape.seq_len


def batch_specs(cfg: ModelConfig, shape: ShapeConfig):
    """(abstract, axes) for one global training batch."""
    B, S = shape.global_batch, token_len(cfg, shape)
    abstract = {"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32),
                "labels": jax.ShapeDtypeStruct((B, S), jnp.int32)}
    axes = {"tokens": ("batch", "seq"), "labels": ("batch", "seq")}
    ex_abs, ex_axes = extras_specs(cfg, B)
    if ex_abs:
        abstract["extras"], axes["extras"] = ex_abs, ex_axes
    return abstract, axes


def extras_specs(cfg: ModelConfig, B: int):
    """Modality-frontend stubs (precomputed embeddings), per DESIGN.md §4."""
    if cfg.family == "vlm":
        return ({"image_embeds": jax.ShapeDtypeStruct(
                    (B, cfg.num_patches, cfg.vision_dim), jnp.bfloat16)},
                {"image_embeds": ("batch", None, None)})
    if cfg.family == "audio":
        return ({"frames": jax.ShapeDtypeStruct(
                    (B, cfg.encoder_frames, cfg.d_model), jnp.bfloat16)},
                {"frames": ("batch", "seq", None)})
    return None, None


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------

@dataclass
class _TrainPieces:
    """Shared setup between build_train / build_train_chunk: ONE place
    resolves the config, accumulation plan and shardings, and ONE
    ``train_step`` body is compiled in both — the chunked dispatch is a
    ``lax.scan`` over the *identical* per-step computation, which is what
    makes the losses bit-identical between the two (pinned by
    tests/test_train_hot_loop.py)."""
    train_step: Callable
    abstract_params: Any
    abstract_opt: Any
    param_shd: Any
    opt_shd: Any
    batch_abs: Any
    batch_axes: Any
    batch_shd: Any
    metrics_abs: Any
    mesh: Mesh
    rules: Any
    accum: int


def _train_pieces(cfg: ModelConfig, par: ParallelConfig,
                  ocfg: OptimizerConfig, mesh: Mesh,
                  shape: ShapeConfig, *, loss_attr: str = "loss_fn",
                  batch_fn: Optional[Callable] = None) -> _TrainPieces:
    cfg = resolve_cfg(cfg, shape)
    accum = max(ocfg.accum_steps, 1)
    if shape.global_batch % accum:
        raise ValueError(
            f"accum_steps={accum} must divide global_batch="
            f"{shape.global_batch} (microbatches must be equal-sized "
            f"for grad averaging to equal the full-batch gradient)")
    if par.pure_fsdp_train and not par.pure_fsdp:
        import dataclasses as _dc
        import numpy as _np
        chips = int(_np.prod(list(mesh.shape.values())))
        if shape.global_batch % chips == 0:
            par = _dc.replace(par, pure_fsdp=True)
    mod = _model_module(cfg)
    ctx = ModelCtx(cfg, par, mesh)
    rules = sh.logical_rules(par)
    schema = mod.lm_schema(cfg)
    opt_schema = adamw.opt_state_schema(schema, ocfg)

    abstract_params = pr.abstract_params(schema, cfg.param_dtype)
    abstract_opt = pr.abstract_params(opt_schema, "float32")
    param_shd = sh.shardings_for_schema(schema, mesh, rules)
    opt_shd = sh.shardings_for_schema(opt_schema, mesh, rules)
    batch_abs, batch_axes = (batch_fn or batch_specs)(cfg, shape)
    batch_shd = _shardings(batch_abs, batch_axes, mesh, rules)
    loss_impl = getattr(mod, loss_attr, None)
    if loss_impl is None:
        raise ValueError(
            f"model family {cfg.family!r} does not define {loss_attr!r}")

    def train_step(params, opt_state, batch):
        def loss_of(p, b):
            return loss_impl(ctx, p, b)

        if accum == 1:
            loss, grads = jax.value_and_grad(loss_of)(params, batch)
        else:
            def micro(carry, mb):
                acc_loss, acc_g = carry
                l, g = jax.value_and_grad(loss_of)(params, mb)
                return (acc_loss + l,
                        jax.tree.map(jnp.add, acc_g, g)), None
            micro_b = jax.tree.map(
                lambda x: x.reshape((accum, x.shape[0] // accum) + x.shape[1:]),
                batch)
            zeros = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            # strongly-typed f32 loss carry: scan needs identical carry
            # avals, and grads accumulate in f32 regardless of param dtype
            (loss, grads), _ = jax.lax.scan(
                micro, (jnp.zeros((), jnp.float32), zeros), micro_b)
            loss = loss / accum
            grads = jax.tree.map(lambda g: g / accum, grads)

        new_params, new_opt, stats = adamw.apply_updates(
            schema, params, grads, opt_state, ocfg, shardings=param_shd)
        metrics = {"loss": loss.astype(jnp.float32), **stats}
        return new_params, new_opt, metrics

    metrics_abs = {"loss": jax.ShapeDtypeStruct((), jnp.float32),
                   "grad_norm": jax.ShapeDtypeStruct((), jnp.float32),
                   "lr": jax.ShapeDtypeStruct((), jnp.float32)}
    return _TrainPieces(
        train_step=train_step, abstract_params=abstract_params,
        abstract_opt=abstract_opt, param_shd=param_shd, opt_shd=opt_shd,
        batch_abs=batch_abs, batch_axes=batch_axes, batch_shd=batch_shd,
        metrics_abs=metrics_abs, mesh=mesh, rules=rules, accum=accum)


def build_train(cfg: ModelConfig, par: ParallelConfig, ocfg: OptimizerConfig,
                mesh: Mesh, shape: ShapeConfig) -> StepBundle:
    """Build one jitted optimizer step.

    Gradient accumulation contract (``ocfg.accum_steps``): the step always
    consumes the FULL ``shape.global_batch`` rows per call and splits them
    into ``accum_steps`` sequential microbatches inside the jit, so the
    global batch — and therefore the training trajectory — is independent
    of ``accum_steps``.  Elastic rescale (repro.elastic) relies on this:
    shrinking the data axis and raising ``accum_steps`` keeps batch x accum
    constant while bounding per-device microbatch memory.
    """
    tp = _train_pieces(cfg, par, ocfg, mesh, shape)
    return StepBundle(
        fn=tp.train_step,
        abstract_args=(tp.abstract_params, tp.abstract_opt, tp.batch_abs),
        in_shardings=(tp.param_shd, tp.opt_shd, tp.batch_shd),
        out_shardings=(tp.param_shd, tp.opt_shd,
                       _replicated(tp.metrics_abs, mesh)),
        donate_argnums=(0, 1),
        accum_steps=tp.accum,
    )


def chunk_batch_specs(batch_abs, batch_axes, device_steps: int):
    """Stack ``device_steps`` per-step batches along a new leading axis.

    Returns (abstract, axes) trees whose leaves are (K, ...) with an
    unsharded leading axis — the scan dimension of ``build_train_chunk``.
    """
    K = max(device_steps, 1)
    abstract = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct((K,) + a.shape, a.dtype), batch_abs)
    axes = jax.tree.map(lambda ax: (None,) + ax, batch_axes,
                        is_leaf=_is_axes_leaf)
    return abstract, axes


def build_train_chunk(cfg: ModelConfig, par: ParallelConfig,
                      ocfg: OptimizerConfig, mesh: Mesh, shape: ShapeConfig,
                      device_steps: int) -> StepBundle:
    """Build one jitted dispatch of ``device_steps`` optimizer steps.

    The device-resident hot loop: a ``lax.scan`` over K = ``device_steps``
    full optimizer steps (each still folding ``ocfg.accum_steps``
    microbatches) with the (params, opt_state) carry donated and never
    leaving the device.  The host dispatches once per chunk and receives
    per-step metrics stacked (K,), so host round-trips per optimizer step
    drop from O(1) to O(1/device_steps).

    The batch argument is the per-step batch stacked along a new leading
    K axis (see ``chunk_batch_specs`` / ``TokenPipeline.chunk``); each
    scanned step consumes the same FULL ``shape.global_batch`` rows the
    per-step ``build_train`` would, so the training trajectory is
    independent of ``device_steps`` (and bit-identical to per-step
    dispatch — the scan body IS the per-step ``train_step``).
    """
    tp = _train_pieces(cfg, par, ocfg, mesh, shape)
    return _chunk_bundle(tp, device_steps)


def _chunk_bundle(tp: _TrainPieces, device_steps: int) -> StepBundle:
    """Wrap a per-step ``train_step`` into one K-step lax.scan dispatch —
    shared by the supervised and RL chunk builders, so the RL learner
    rides the identical device-resident hot loop."""
    K = max(device_steps, 1)
    mesh = tp.mesh
    chunk_abs, chunk_axes = chunk_batch_specs(tp.batch_abs, tp.batch_axes, K)
    chunk_shd = _shardings(chunk_abs, chunk_axes, mesh, tp.rules)

    def train_chunk(params, opt_state, batches):
        def one(carry, batch):
            p, o = carry
            p, o, m = tp.train_step(p, o, batch)
            return (p, o), m

        (params, opt_state), ms = jax.lax.scan(one, (params, opt_state),
                                               batches)
        return params, opt_state, ms      # metrics leaves stacked (K,)

    chunk_metrics_abs = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct((K,) + a.shape, a.dtype),
        tp.metrics_abs)
    return StepBundle(
        fn=train_chunk,
        abstract_args=(tp.abstract_params, tp.abstract_opt, chunk_abs),
        in_shardings=(tp.param_shd, tp.opt_shd, chunk_shd),
        out_shardings=(tp.param_shd, tp.opt_shd,
                       _replicated(chunk_metrics_abs, mesh)),
        donate_argnums=(0, 1),
        accum_steps=tp.accum,
        device_steps=K,
    )


# ---------------------------------------------------------------------------
# RL policy-gradient train step (repro.rl learner)
# ---------------------------------------------------------------------------

def rl_batch_specs(cfg: ModelConfig, shape: ShapeConfig):
    """(abstract, axes) for one batch of rollout trajectories: the LM
    batch plus a per-token action mask and a per-trajectory advantage."""
    B, S = shape.global_batch, token_len(cfg, shape)
    abstract = {"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32),
                "labels": jax.ShapeDtypeStruct((B, S), jnp.int32),
                "mask": jax.ShapeDtypeStruct((B, S), jnp.float32),
                "advantages": jax.ShapeDtypeStruct((B,), jnp.float32)}
    axes = {"tokens": ("batch", "seq"), "labels": ("batch", "seq"),
            "mask": ("batch", "seq"), "advantages": ("batch",)}
    return abstract, axes


def build_rl_train_chunk(cfg: ModelConfig, par: ParallelConfig,
                         ocfg: OptimizerConfig, mesh: Mesh,
                         shape: ShapeConfig, device_steps: int) -> StepBundle:
    """The RL learner's fused dispatch: ``device_steps`` advantage-weighted
    policy-gradient optimizer steps in one ``lax.scan``, (params, opt)
    carry donated and device-resident — structurally identical to
    ``build_train_chunk`` (same AdamW update, same donation, same (K,)
    stacked metrics), differing only in the loss (``mod.rl_loss_fn``)
    and the batch schema (``rl_batch_specs``: + mask, + advantages)."""
    tp = _train_pieces(cfg, par, ocfg, mesh, shape,
                       loss_attr="rl_loss_fn", batch_fn=rl_batch_specs)
    return _chunk_bundle(tp, device_steps)


# ---------------------------------------------------------------------------
# prefill / decode
# ---------------------------------------------------------------------------

def build_prefill(cfg: ModelConfig, par: ParallelConfig, mesh: Mesh,
                  shape: ShapeConfig) -> StepBundle:
    """(params, tokens, *extras) -> (last position's logits, caches,
    route): ``route`` is a held-expert model's router choice (B, T,
    layers, top_k) (``transformer.step_route``), else None."""
    cfg = resolve_cfg(cfg, shape)
    mod = _model_module(cfg)
    ctx = ModelCtx(cfg, par, mesh)
    rules = sh.logical_rules(par)
    schema = mod.lm_schema(cfg)
    B, S = shape.global_batch, shape.seq_len
    T = token_len(cfg, shape)

    abstract_params = pr.abstract_params(schema, cfg.param_dtype)
    param_shd = sh.shardings_for_schema(schema, mesh, rules)
    tok_abs = jax.ShapeDtypeStruct((B, T), jnp.int32)
    tok_shd = sh.sharding_for((B, T), ("batch", "seq"), mesh, rules)
    cache_schema = mod.cache_schema(cfg, B, S)
    cache_shd = sh.shardings_for_schema(cache_schema, mesh, rules)
    ex_abs, ex_axes = extras_specs(cfg, B)
    extra_args, extra_shd = ((ex_abs,), (_shardings(ex_abs, ex_axes, mesh, rules),)) \
        if ex_abs else ((), ())

    def prefill_step(params, tokens, *extras):
        ex = extras[0] if extras else None
        hidden, caches, aux = mod.forward(ctx, params, tokens,
                                          mode="prefill", extras=ex)
        # unembed only the last position: (B,1,V), not (B,S,V)
        last = mod.lm_logits(ctx, params, hidden[:, -1:, :])[:, 0, :]
        return last, caches, tfm.step_route(cfg, aux)

    last_shd = sh.sharding_for((B, cfg.vocab_size), ("batch", "act_vocab"),
                               mesh, rules)
    return StepBundle(
        fn=prefill_step,
        abstract_args=(abstract_params, tok_abs) + extra_args,
        in_shardings=(param_shd, tok_shd) + extra_shd,
        out_shardings=(last_shd, cache_shd, _route_sharding(cfg, mesh)),
    )


def _route_sharding(cfg: ModelConfig, mesh: Mesh):
    """A serving step's router choice is replicated; a model without
    held-expert layers returns none."""
    return NamedSharding(mesh, P()) if tfm.held_expert_layers(cfg) else None


def build_decode(cfg: ModelConfig, par: ParallelConfig, mesh: Mesh,
                 shape: ShapeConfig, *, per_slot: bool = False) -> StepBundle:
    """One fused greedy decode step over the whole batch.

    ``per_slot=False``: classic whole-batch decode — every row sits at the
    same scalar position ``pos`` (the per-slot step's test reference).

    ``per_slot=True``: continuous-batching decode — ``pos`` is a (B,) int32
    vector, one sequence position per slot.  Cache writes, RoPE and the
    causal mask are all per-row, so a single jitted step advances B
    *independent* requests with no inter-request barrier (repro.serving).
    """
    cfg = resolve_cfg(cfg, shape)
    mod = _model_module(cfg)
    ctx = ModelCtx(cfg, par, mesh)
    rules = sh.logical_rules(par)
    schema = mod.lm_schema(cfg)
    B, S = shape.global_batch, shape.seq_len

    abstract_params = pr.abstract_params(schema, cfg.param_dtype)
    param_shd = sh.shardings_for_schema(schema, mesh, rules)
    cache_schema = mod.cache_schema(cfg, B, S)
    abstract_cache = pr.abstract_params(cache_schema, cfg.param_dtype)
    cache_shd = sh.shardings_for_schema(cache_schema, mesh, rules)
    tok_abs = jax.ShapeDtypeStruct((B, 1), jnp.int32)
    tok_shd = sh.sharding_for((B, 1), ("batch", None), mesh, rules)
    pos_abs = jax.ShapeDtypeStruct((B,) if per_slot else (), jnp.int32)
    pos_shd = NamedSharding(mesh, P())

    def serve_step(params, caches, token, pos):
        hidden, new_caches, _ = mod.forward(ctx, params, token, mode="decode",
                                            caches=caches, pos=pos)
        logits = mod.lm_logits(ctx, params, hidden)
        next_tok = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)
        return next_tok[:, None], new_caches

    return StepBundle(
        fn=serve_step,
        abstract_args=(abstract_params, abstract_cache, tok_abs, pos_abs),
        in_shardings=(param_shd, cache_shd, tok_shd, pos_shd),
        out_shardings=(tok_shd, cache_shd),
        donate_argnums=(1,),
    )


def build_slot_decode(cfg: ModelConfig, par: ParallelConfig, mesh: Mesh,
                      shape: ShapeConfig) -> StepBundle:
    """Continuous-batching decode step (see build_decode per_slot=True)."""
    return build_decode(cfg, par, mesh, shape, per_slot=True)


# ---------------------------------------------------------------------------
# slotted KV/state cache: allocation + slot insert/evict
# ---------------------------------------------------------------------------
# Every cache leaf in the repo is laid out (layers, batch, ...), so a "slot"
# is index ``i`` of axis 1 across the whole cache pytree — attention K/V,
# mamba conv/state, rwkv state and encdec self/cross caches alike.

CACHE_BATCH_AXIS = 1


def init_cache(cfg: ModelConfig, B: int, S: int):
    """Allocate an all-zeros decode cache for B slots of S positions."""
    mod = _model_module(cfg)
    abstract = pr.abstract_params(mod.cache_schema(cfg, B, S), cfg.param_dtype)
    return jax.tree.map(lambda a: jnp.zeros(a.shape, a.dtype), abstract)


def cache_batch_insert(dst, src, slot):
    """Copy a 1-slot cache pytree ``src`` into slot ``slot`` of ``dst``.

    ``src`` leaves may have a shorter sequence axis than ``dst`` (a prefill
    cache covers only the prompt); the tail of the slot is left as-is and
    relies on the decode-position mask to stay invisible.  Pure function —
    jit it with ``donate_argnums=0`` so refills are in-place.
    """
    slot = jnp.asarray(slot, jnp.int32)

    def ins(d, s):
        start = (jnp.int32(0), slot) + (jnp.int32(0),) * (d.ndim - 2)
        return jax.lax.dynamic_update_slice(d, s.astype(d.dtype), start)

    return jax.tree.map(ins, dst, src)


def cache_prefix_insert(dst, src):
    """Copy a short-sequence cache pytree into the front of a longer one.

    Prefill emits caches whose sequence axis covers only the prompt;
    decode needs headroom for the generated tokens.  (Decoding against
    the prompt-length cache would clamp every generated token's K/V write
    onto the last prompt slot, leaving generations invisible to
    attention.)
    """
    def ins(d, s):
        start = (jnp.int32(0),) * d.ndim
        return jax.lax.dynamic_update_slice(d, s.astype(d.dtype), start)

    return jax.tree.map(ins, dst, src)


def cache_batch_evict(dst, slot):
    """Zero out one slot (hygiene on eviction; correctness never needs it —
    the next insert overwrites the prompt prefix and masks hide the rest)."""
    slot = jnp.asarray(slot, jnp.int32)

    def ev(d):
        z = jnp.zeros((d.shape[0], 1) + d.shape[2:], d.dtype)
        start = (jnp.int32(0), slot) + (jnp.int32(0),) * (d.ndim - 2)
        return jax.lax.dynamic_update_slice(d, z, start)

    return jax.tree.map(ev, dst)


# ---------------------------------------------------------------------------
# paged KV pool: block tables + in-place reads (or per-layer gathers) /
# one-row scatter decode
# ---------------------------------------------------------------------------
# The slotted cache above dedicates S positions to every slot whether the
# request uses them or not; the paged layout replaces axis 1 (slots) with a
# shared pool of fixed-size blocks — leaf shape (layers, num_blocks,
# block_size, ...) — addressed through per-slot block tables (B, S//bs).
# Block 0 is the NULL block: table entries for not-yet-allocated tail
# positions point at it, and out-of-range scatter writes are clamped onto
# it, so its contents are garbage-but-finite — which the decode mask turns
# into an exact 0.0 contribution (exp(-1e30 - m) == 0.0 in f32), keeping
# the view path bit-identical to the slotted baseline.  The paged kernel
# (kernels/paged_decode.py) never reads past a slot's live blocks.

def paged_compatible(cfg: ModelConfig, S: int, block_size: int) -> bool:
    """True iff every cache leaf is a (layers, batch, cache_seq, ...) KV
    layout whose sequence axis is exactly S and divisible into blocks.
    SSM/RWKV state caches and enc-dec cross caches are not paged-able;
    callers fall back to the slotted cache."""
    if block_size < 1 or S % block_size:
        return False
    mod = _model_module(cfg)
    flags = []
    pr.tree_map_schema(
        lambda path, ps: flags.append(
            len(ps.axes) >= 3 and ps.axes[2] == "cache_seq"
            and ps.shape[2] == S),
        mod.cache_schema(cfg, 1, S))
    return bool(flags) and all(flags)


def init_paged_cache(cfg: ModelConfig, num_blocks: int, block_size: int):
    """Allocate an all-zeros block pool: the cache schema instantiated with
    batch=num_blocks, seq=block_size gives exactly the pool leaf layout
    (layers, num_blocks, block_size, ...)."""
    mod = _model_module(cfg)
    abstract = pr.abstract_params(
        mod.cache_schema(cfg, num_blocks, block_size), cfg.param_dtype)
    return jax.tree.map(lambda a: jnp.zeros(a.shape, a.dtype), abstract)


def paged_cache_scatter(pool, rows, tables, pos):
    """Write back what the decode step wrote, per slot and layer.

    ``rows`` leaves are (layers, B, ...): slot i wrote position ``pos[i]``
    (``kvcache.written_rows``); or (layers, B, block_size, ...), the
    whole block holding that position, for rows too small to write alone.
    Writes whose position is out of range (free slots parked at 0 with an
    all-null table, or finished slots past S-1) land on the null block,
    where duplicate writes are harmless by the masking argument above."""
    def scat(pleaf, rleaf):
        bs = pleaf.shape[2]
        S = tables.shape[1] * bs
        blk = jnp.take_along_axis(tables, (pos // bs)[:, None], axis=1)[:, 0]
        blk = jnp.where(pos < S, blk, 0)
        if rleaf.ndim == pleaf.ndim:                  # whole blocks
            return pleaf.at[:, blk].set(rleaf.astype(pleaf.dtype))
        return pleaf.at[:, blk, pos % bs].set(rleaf.astype(pleaf.dtype))

    with jax.named_scope("kv_scatter"):
        return jax.tree.map(scat, pool, rows)


def paged_prompt_insert(pool, src, blocks):
    """Splice a B=1 prefill cache (leaves (layers, 1, P, ...)) into the
    pool at the given (P // block_size,) distinct block ids."""
    def ins(pleaf, sleaf):
        bs = pleaf.shape[2]
        tail = sleaf.shape[3:]
        nb = sleaf.shape[2] // bs
        chunks = sleaf[:, 0].reshape(sleaf.shape[0], nb, bs, *tail)
        return pleaf.at[:, blocks].set(chunks.astype(pleaf.dtype))

    return jax.tree.map(ins, pool, src)


def build_paged_decode(cfg: ModelConfig, par: ParallelConfig, mesh: Mesh,
                       shape: ShapeConfig, *, block_size: int,
                       num_blocks: int) -> StepBundle:
    """One fused per-slot decode step against the paged pool.  On a TPU
    (``kernels.paged_decode.kernel_path``) each layer's attention is the
    paged kernel, which reads every slot's live blocks in place through
    the block tables; elsewhere each layer gathers its own (B, S, ...)
    views (``kvcache.paged_layer_view``) and runs the slotted layer
    on them unchanged.  Either way a layer hands out only what each slot
    wrote, and one scatter after the scan writes that into the pool.
    No views of the whole pool are built or stacked.  Signature: (params,
    pool, tables, token, pos) -> (next_token, pool, counters, route);
    donate the pool for in-place updates.  ``counters`` holds the step's
    program counters (``transformer.step_counters``: the experts hit in a
    held-expert model), and is empty for every other model; ``route`` is
    a held-expert model's router choice (``transformer.step_route``,
    (B, 1, layers, top_k)), else None."""
    cfg = resolve_cfg(cfg, shape)
    mod = _model_module(cfg)
    ctx = ModelCtx(cfg, par, mesh)
    rules = sh.logical_rules(par)
    schema = mod.lm_schema(cfg)
    B, S = shape.global_batch, shape.seq_len
    if not paged_compatible(cfg, S, block_size):
        raise ValueError(f"{cfg.family} cache is not paged-compatible "
                         f"for S={S}, block_size={block_size}")

    abstract_params = pr.abstract_params(schema, cfg.param_dtype)
    param_shd = sh.shardings_for_schema(schema, mesh, rules)
    pool_schema = mod.cache_schema(cfg, num_blocks, block_size)
    abstract_pool = pr.abstract_params(pool_schema, cfg.param_dtype)
    # the block axis is an arbitrary permutation of slots x positions —
    # keep it (and the intra-block axis) unsharded; heads/layers shard as
    # in the slotted cache
    pool_shd = pr.tree_map_schema(
        lambda path, ps: sh.sharding_for(
            ps.shape, (ps.axes[0], None, None) + tuple(ps.axes[3:]),
            mesh, rules),
        pool_schema)
    nb = S // block_size
    tab_abs = jax.ShapeDtypeStruct((B, nb), jnp.int32)
    tok_abs = jax.ShapeDtypeStruct((B, 1), jnp.int32)
    pos_abs = jax.ShapeDtypeStruct((B,), jnp.int32)
    tok_shd = sh.sharding_for((B, 1), ("batch", None), mesh, rules)
    repl = NamedSharding(mesh, P())

    def paged_step(params, pool, tables, token, pos):
        hidden, rows, aux = mod.forward(ctx, params, token, mode="decode",
                                        caches=pool, pos=pos, tables=tables)
        logits = mod.lm_logits(ctx, params, hidden)
        next_tok = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)
        pool = paged_cache_scatter(pool, rows, tables, pos)
        return (next_tok[:, None], pool, tfm.step_counters(cfg, aux),
                tfm.step_route(cfg, aux))

    counters_shd = {name: repl for name in tfm.step_counters(cfg, 0.0)}
    return StepBundle(
        fn=paged_step,
        abstract_args=(abstract_params, abstract_pool, tab_abs, tok_abs,
                       pos_abs),
        in_shardings=(param_shd, pool_shd, repl, tok_shd, repl),
        out_shardings=(tok_shd, pool_shd, counters_shd,
                       _route_sharding(cfg, mesh)),
        donate_argnums=(1,),
    )


def build_step(cfg, par, ocfg, mesh, shape: ShapeConfig) -> StepBundle:
    if shape.kind == "train":
        return build_train(cfg, par, ocfg, mesh, shape)
    if shape.kind == "prefill":
        return build_prefill(cfg, par, mesh, shape)
    return build_decode(cfg, par, mesh, shape)
