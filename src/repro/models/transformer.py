"""Decoder-only LM over heterogeneous *layer groups*.

``cfg.block_pattern`` is the repeating unit (e.g. gemma2: ("local","global"),
llama-3.2-vision: ("attn",)*4 + ("cross",)); parameters for each position are
stacked along a leading group axis and the model scans over groups — the HLO
is depth-independent, which keeps 512-way dry-run compiles tractable.

``cfg.lead_pattern`` layers (Kimi K2's leading dense layer) run once each
before the scan, with parameters and caches of their own under ``"lead"``.

Each block *kind* registers (schema, cache_schema, apply) in KINDS; dense
attention kinds live here, MoE in models.moe, latent attention (MLA) in
models.mla, Mamba2/RWKV6 in models.ssm.  ``apply`` returns an auxiliary
value beside the new cache: in training the layer's auxiliary loss; in
prefill and decode, for the held-expert kind, the pair (its count for the
serving counters, its router's choice), which ``forward`` sums and
stacks by layer (``step_counters``, ``step_route``).
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Optional

import jax
import jax.ad_checkpoint
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn_mod
from repro.models import kvcache
from repro.models import losses
from repro.models.layers import (ModelCtx, cross_entropy, embed_tokens,
                                 rms_norm, swiglu, unembed)
from repro.models.params import PSpec

# ---------------------------------------------------------------------------
# kind registry
# ---------------------------------------------------------------------------
# kind -> dict with:
#   schema(cfg, G)        -> {name: PSpec}           (leading G dim, axes[0]="layers")
#   cache(cfg, B, S)      -> {name: PSpec} or {}     (leading G dim)
#   apply(ctx, p, x, *, mode, positions, cache, pos, shared, extras)
#         -> (x, new_cache, aux_loss)
KINDS: Dict[str, Dict[str, Callable]] = {}


def register_kind(name: str, schema, cache, apply):
    KINDS[name] = {"schema": schema, "cache": cache, "apply": apply}


# ---------------------------------------------------------------------------
# dense attention block (kinds: attn / local / global)
# ---------------------------------------------------------------------------

def _attn_mlp_schema(cfg: ModelConfig, G: int) -> Dict[str, PSpec]:
    D, H, KV, dh, F = (cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
                       cfg.resolved_head_dim, cfg.d_ff)
    # When the head count cannot divide the production model axis (phi4: 24,
    # whisper: 12), TP-shard attention weights along head_dim instead so they
    # are not merely 16-way (FSDP-only) sharded.
    heads_div = H % 16 == 0
    hq = "tp_heads" if heads_div else None
    hd = "head_dim" if heads_div else "tp_head_dim"
    # the projections contract over D (q/k/v) and H*dh (out), not over
    # their second-to-last dim: state the init scale, or the scores of a
    # fresh model saturate (std 1/sqrt(H) instead of 1/sqrt(D))
    sd, so = D ** -0.5, (H * dh) ** -0.5
    s: Dict[str, PSpec] = {
        "ln1": PSpec((G, D), ("layers", None), "zeros"),
        "wq": PSpec((G, D, H, dh), ("layers", "fsdp", hq, hd), scale=sd),
        "wk": PSpec((G, D, KV, dh), ("layers", "fsdp", "tp_kv_heads", hd),
                    scale=sd),
        "wv": PSpec((G, D, KV, dh), ("layers", "fsdp", "tp_kv_heads", hd),
                    scale=sd),
        "wo": PSpec((G, H, dh, D), ("layers", hq, hd, "fsdp"), scale=so),
        "ln2": PSpec((G, D), ("layers", None), "zeros"),
        "wg": PSpec((G, D, F), ("layers", "fsdp", "tp_ff")),
        "wu": PSpec((G, D, F), ("layers", "fsdp", "tp_ff")),
        "wo_mlp": PSpec((G, F, D), ("layers", "tp_ff", "fsdp")),
    }
    if cfg.attn.qkv_bias:
        s["bq"] = PSpec((G, H, dh), ("layers", "tp_heads", "head_dim"), "zeros")
        s["bk"] = PSpec((G, KV, dh), ("layers", "tp_kv_heads", "head_dim"), "zeros")
        s["bv"] = PSpec((G, KV, dh), ("layers", "tp_kv_heads", "head_dim"), "zeros")
    if cfg.post_norm:
        s["ln1_post"] = PSpec((G, D), ("layers", None), "zeros")
        s["ln2_post"] = PSpec((G, D), ("layers", None), "zeros")
    return s


def _attn_cache_schema(cfg: ModelConfig, B: int, S: int, G: int) -> Dict[str, PSpec]:
    KV, dh = cfg.num_kv_heads, cfg.resolved_head_dim
    ax = ("layers", "batch", "cache_seq", "kv_heads", "head_dim")
    return {"k": PSpec((G, B, S, KV, dh), ax, "zeros"),
            "v": PSpec((G, B, S, KV, dh), ax, "zeros")}


def _tp_boundary(ctx: ModelCtx, h, mode: str, tag: str):
    """Make the Megatron-SP all-gather an explicit, NAMED value so the
    remat policy (save_only_these_names) can keep it for backward instead
    of re-gathering 3x (remat recompute + two transposes)."""
    if (mode == "train" and ctx.par.sequence_parallel
            and ctx.par.remat_save_gathered):
        h = ctx.cons(h, ("batch", "seq", None))
        h = jax.ad_checkpoint.checkpoint_name(h, "tp_gather")
    return h


def attention_part(ctx: ModelCtx, p, x, *, window, mode, positions, cache, pos):
    """Pre-norm attention sub-block shared by dense/moe/hybrid kinds."""
    with jax.named_scope("attn"):
        return _attention_part(ctx, p, x, window=window, mode=mode,
                               positions=positions, cache=cache, pos=pos)


def _attention_part(ctx: ModelCtx, p, x, *, window, mode, positions, cache,
                    pos):
    cfg = ctx.cfg
    strategy = attn_mod.attn_strategy(ctx)
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    if strategy == "heads":
        h = _tp_boundary(ctx, h, mode, "attn_in")
    q, k, v = attn_mod.qkv_proj(ctx, p, h, positions, strategy)
    new_cache = {}
    if mode == "decode":
        out, new_cache = kvcache.attend(
            cache, {"k": k, "v": v}, pos, q,
            lambda kv: attn_mod.decode_attention(
                ctx, q, kv["k"], kv["v"], pos, window=window,
                logit_softcap=cfg.attn.logit_softcap),
            scale=q.shape[-1] ** -0.5, window=window,
            logit_softcap=cfg.attn.logit_softcap)
    else:
        out = attn_mod.causal_attention(
            ctx, q, k, v, window=window, logit_softcap=cfg.attn.logit_softcap,
            strategy=strategy, mode=mode)
        if mode == "prefill":
            cax = ("batch", "cache_seq", "kv_heads", "head_dim")
            new_cache = {"k": ctx.cons(k, cax), "v": ctx.cons(v, cax)}
    out = attn_mod.attn_out(ctx, p, out)
    # NOTE: an explicit seq-sharded constraint on this output was tried to
    # convert the combine AR into a reduce-scatter — REFUTED: GSPMD added a
    # resharding pair instead (+53% collective bytes); see EXPERIMENTS §Perf.
    if cfg.post_norm:
        out = rms_norm(out, p["ln1_post"], cfg.norm_eps)
    return x + out, new_cache


def mlp_part(ctx: ModelCtx, p, x, mode: str = "train"):
    cfg = ctx.cfg
    with jax.named_scope("mlp"):
        h = rms_norm(x, p["ln2"], cfg.norm_eps)
        h = _tp_boundary(ctx, h, mode, "mlp_in")
        out = swiglu(ctx, {"wg": p["wg"], "wu": p["wu"], "wo": p["wo_mlp"]},
                     h)
        if cfg.post_norm:
            out = rms_norm(out, p["ln2_post"], cfg.norm_eps)
        return x + out


def _make_attn_apply(window_of: Callable[[ModelConfig], Optional[int]]):
    def apply(ctx, p, x, *, mode, positions, cache, pos, shared, extras):
        x, new_cache = attention_part(
            ctx, p, x, window=window_of(ctx.cfg), mode=mode,
            positions=positions, cache=cache, pos=pos)
        x = mlp_part(ctx, p, x, mode)
        return x, new_cache, 0.0
    return apply


register_kind(
    "attn",
    schema=_attn_mlp_schema,
    cache=lambda cfg, B, S, G: _attn_cache_schema(cfg, B, S, G),
    apply=_make_attn_apply(lambda cfg: None),
)
register_kind(
    "global",
    schema=_attn_mlp_schema,
    cache=lambda cfg, B, S, G: _attn_cache_schema(cfg, B, S, G),
    apply=_make_attn_apply(lambda cfg: None),
)
register_kind(
    "local",
    schema=_attn_mlp_schema,
    cache=lambda cfg, B, S, G: _attn_cache_schema(cfg, B, S, G),
    apply=_make_attn_apply(lambda cfg: cfg.attn.window),
)


# ---------------------------------------------------------------------------
# model schema / caches
# ---------------------------------------------------------------------------

def lm_schema(cfg: ModelConfig) -> Dict[str, Any]:
    G = cfg.num_groups
    blocks = {f"{i}_{kind}": KINDS[kind]["schema"](cfg, G)
              for i, kind in enumerate(cfg.block_pattern)}
    schema: Dict[str, Any] = {
        "embed": PSpec((cfg.vocab_size, cfg.d_model), ("tp_vocab", "fsdp"),
                       scale=0.02),
        "blocks": blocks,
        "final_norm": PSpec((cfg.d_model,), (None,), "zeros"),
    }
    if cfg.lead_pattern:
        schema["lead"] = {f"{i}_{kind}": KINDS[kind]["schema"](cfg, 1)
                          for i, kind in enumerate(cfg.lead_pattern)}
    if not cfg.tie_embeddings:
        schema["lm_head"] = PSpec((cfg.vocab_size, cfg.d_model),
                                  ("tp_vocab", "fsdp"))
    if "mamba_attn" in cfg.block_pattern:   # zamba2 shared attention weights
        from repro.models import ssm
        schema["shared_attn"] = ssm.shared_attn_schema(cfg)
    if "cross" in cfg.block_pattern:        # vlm: vision projection is in-block
        pass
    return schema


def cache_schema(cfg: ModelConfig, B: int, S: int) -> Dict[str, Any]:
    """The decode cache: per block key, leaves (layers, B, S, ...); the
    lead layers' under ``"lead"``, each with a leading axis of 1."""
    G = cfg.num_groups
    out = {f"{i}_{kind}": KINDS[kind]["cache"](cfg, B, S, G)
           for i, kind in enumerate(cfg.block_pattern)}
    if cfg.lead_pattern:
        out["lead"] = {f"{i}_{kind}": KINDS[kind]["cache"](cfg, B, S, 1)
                       for i, kind in enumerate(cfg.lead_pattern)}
    return out


# ---------------------------------------------------------------------------
# forward passes
# ---------------------------------------------------------------------------

def _scan_groups(ctx: ModelCtx, params, x, *, mode, positions, caches, pos,
                 extras, tables=None):
    """Scan (or unrolled loop) over layer groups; returns (x, new_caches).

    With ``tables`` (paged decode) ``caches`` is the block pool and the
    scan runs over group indices: each group's layers read the pool
    through ``kvcache.paged_layers`` (the paged kernel's handles on a
    TPU, else the gathered views), and its scan output is only what each
    slot wrote (``kvcache.written``), (G, B, ...) per leaf.
    (A group's slice of the pool taken as ``xs`` is copied out before
    the gather reads it.)"""
    cfg, par = ctx.cfg, ctx.par
    shared = params.get("shared_attn")
    blocks = params["blocks"]

    multi = len(cfg.block_pattern) > 1
    policy = (jax.checkpoint_policies.save_only_these_names("tp_gather")
              if par.remat_save_gathered else None)

    def one_layer(kind):
        def fn(x, p, cache):
            return KINDS[kind]["apply"](
                ctx, p, x, mode=mode, positions=positions, cache=cache,
                pos=pos, shared=shared, extras=extras)
        if mode == "train" and par.remat and multi:
            # nested remat (multi-layer groups only): backward holds ONE
            # layer's activations, not a whole pattern-group's (the vlm
            # group is 5 layers).  Costs one extra fwd (3 fwd + 2 bwd);
            # len-1 patterns use just the outer body checkpoint (2 fwd).
            fn = jax.checkpoint(fn, prevent_cse=False, policy=policy)
        return fn

    layer_fns = {f"{i}_{kind}": one_layer(kind)
                 for i, kind in enumerate(cfg.block_pattern)}

    def body(carry, xs):
        x, aux = carry
        gp, gc = xs
        if tables is not None:          # gc is the group's index
            gc = kvcache.paged_layers(ctx.mesh, caches, gc, tables)
        new_gc, outs = {}, {}
        for i, kind in enumerate(cfg.block_pattern):
            key = f"{i}_{kind}"
            x, nc, a = layer_fns[key](
                x, gp[key], None if gc is None else gc[key])
            new_gc[key] = nc
            a, out = _split_aux(a)
            if out is not None:
                outs[key] = out
            aux = aux + a
            if mode == "train" and par.sequence_parallel:
                # saved per-layer inputs stay seq-sharded under remat
                x = ctx.cons(x, ("batch", "act_seq_sharded", None))
        if tables is not None:
            new_gc = kvcache.written(gc, new_gc, pos, block_size)
        return (x, aux), (new_gc, outs)

    if mode == "train" and par.remat:
        body = jax.checkpoint(body, prevent_cse=False, policy=policy)

    block_size = (None if tables is None
                  else jax.tree.leaves(caches)[0].shape[2])
    aux0 = jnp.zeros((), jnp.float32)
    G = cfg.num_groups
    per_group = caches if tables is None else jnp.arange(G)
    if par.scan_layers:
        xs = (blocks, per_group)
        (x, aux), (new_caches, outs) = jax.lax.scan(body, (x, aux0), xs)
    else:
        ncs, outs = [], []
        aux = aux0
        for gi in range(G):
            gp = jax.tree.map(lambda a: a[gi], blocks)
            gc = (None if per_group is None
                  else jax.tree.map(lambda a: a[gi], per_group))
            (x, aux), (nc, out) = body((x, aux), (gp, gc))
            ncs.append(nc)
            outs.append(out)
        new_caches = (jax.tree.map(lambda *a: jnp.stack(a), *ncs)
                      if ncs and ncs[0] else None)
        outs = jax.tree.map(lambda *a: jnp.stack(a), *outs)
    return x, new_caches, aux, outs


def _split_aux(a):
    """A layer's auxiliary value, and its per-layer outputs or None: the
    held-expert kind gives (count, router's choice) while serving."""
    return a if isinstance(a, tuple) else (a, None)


def _lead_layers(ctx: ModelCtx, params, x, *, mode, positions, caches, pos,
                 extras, tables=None):
    """The lead layers, once each and in order, before the scanned groups.
    Their parameters and caches carry a leading axis of 1; the new caches
    keep it (with ``tables``, only the written rows, as the scan's)."""
    cfg, par = ctx.cfg, ctx.par
    aux = jnp.zeros((), jnp.float32)
    new, outs = {}, {}
    for i, kind in enumerate(cfg.lead_pattern):
        key = f"{i}_{kind}"
        p = jax.tree.map(lambda a: a[0], params[key])
        if caches is None:
            cache = None
        elif tables is not None:
            cache = kvcache.paged_layers(ctx.mesh, {key: caches[key]}, 0,
                                         tables)[key]
        else:
            cache = jax.tree.map(lambda a: a[0], caches[key])

        def fn(x, p, cache, kind=kind):
            return KINDS[kind]["apply"](
                ctx, p, x, mode=mode, positions=positions, cache=cache,
                pos=pos, shared=None, extras=extras)
        if mode == "train" and par.remat:
            fn = jax.checkpoint(fn, prevent_cse=False)
        x, nc, a = fn(x, p, cache)
        if tables is not None:
            nc = kvcache.written({key: cache}, {key: nc}, pos,
                                 jax.tree.leaves(caches)[0].shape[2])[key]
        new[key] = jax.tree.map(lambda a: a[None], nc)
        a, out = _split_aux(a)
        if out is not None:
            outs[key] = out[None]
        aux = aux + a
    return x, new, aux, outs


def forward(ctx: ModelCtx, params, tokens, *, mode: str = "train",
            caches=None, pos=None, extras=None, tables=None):
    """tokens (B,St) int32.  mode train|prefill: St=S; decode: St=1.

    Returns (final hidden states (B,St,D), new_caches, aux) — callers
    pick the head: chunked xent for training, last-token logits for serving.
    ``aux`` is the layers' auxiliary values summed (the training loss
    term); where layers also give per-layer outputs (the held-expert
    router's choice, in prefill and decode) it is the pair (that sum,
    {block key: outputs stacked by layer, the lead layers' under
    "lead"}).  Decode with block ``tables`` (B, S // block_size) reads a
    paged pool as ``caches`` and returns, as ``new_caches``, only the rows
    written (layers, B, ...) for the caller to scatter into the pool.
    """
    cfg = ctx.cfg
    x = embed_tokens(ctx, params["embed"], tokens)
    if mode == "train" and ctx.par.sequence_parallel:
        x = ctx.cons(x, ("batch", "act_seq_sharded", None))
    if mode == "decode":
        # pos: scalar (whole-batch) or (B,) per-slot positions (continuous
        # batching) — rope() takes (S,) or (B,S) position grids.
        p = jnp.asarray(pos)
        positions = p[:, None] if p.ndim == 1 else jnp.reshape(p, (1,))
    else:
        positions = jnp.arange(tokens.shape[1], dtype=jnp.int32)
    if cfg.lead_pattern:
        lead = None if caches is None else caches["lead"]
        x, lead_new, lead_aux, lead_outs = _lead_layers(
            ctx, params["lead"], x, mode=mode, positions=positions,
            caches=lead, pos=pos, extras=extras, tables=tables)
        if caches is not None:
            caches = {k: v for k, v in caches.items() if k != "lead"}
    x, new_caches, aux, outs = _scan_groups(
        ctx, params, x, mode=mode, positions=positions, caches=caches,
        pos=pos, extras=extras, tables=tables)
    if cfg.lead_pattern:
        new_caches = dict(new_caches or {}, lead=lead_new)
        aux = aux + lead_aux
        if lead_outs:
            outs = dict(outs, lead=lead_outs)
    if outs:
        aux = (aux, outs)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    x = ctx.cons(x, ("batch", "act_seq_sharded" if mode == "train"
                     and ctx.par.sequence_parallel else "seq", None))
    return x, new_caches, aux


def lm_head(cfg: ModelConfig, params):
    return params["embed"] if cfg.tie_embeddings else params["lm_head"]


def lm_logits(ctx: ModelCtx, params, x) -> jax.Array:
    """Logits for a few positions (serving) — NOT for full-seq training."""
    with jax.named_scope("lm_head"):
        return unembed(ctx, lm_head(ctx.cfg, params), x, transpose=True)


def loss_fn(ctx: ModelCtx, params, batch) -> jax.Array:
    x, _, aux = forward(ctx, params, batch["tokens"], mode="train",
                        extras=batch.get("extras"))
    head = lm_head(ctx.cfg, params).astype(ctx.compute_dtype)
    S = x.shape[1]
    # sharded xent needs the vocab on the model axis; under pure-FSDP the
    # model axis carries batch, so chunked (per-chunk remat) is the
    # memory-safe head there and for non-divisible vocabs
    if (ctx.cfg.vocab_size % 16 == 0 and S % 16 == 0
            and not ctx.par.pure_fsdp):
        nll = losses.sharded_cross_entropy(
            ctx, x, batch["labels"], head,
            softcap=ctx.cfg.final_logit_softcap)
    else:
        nll = losses.chunked_cross_entropy(
            x, batch["labels"], head, softcap=ctx.cfg.final_logit_softcap)
    return nll + aux


def rl_loss_fn(ctx: ModelCtx, params, batch) -> jax.Array:
    """Advantage-weighted policy-gradient loss (the repro.rl learner).

    batch: tokens/labels (B,S) int32 as in ``loss_fn``, plus
    mask (B,S) f32 — 1.0 on generated (action) label positions — and
    advantages (B,) f32, one normalized return per trajectory.  The
    surrogate sum_t A * -log pi(label_t) / sum(mask) is exactly
    mask*advantage-weighted cross entropy, so the chunked/fused xent
    path is reused unchanged; prompt and pad positions get weight 0 and
    contribute no gradient.
    """
    x, _, aux = forward(ctx, params, batch["tokens"], mode="train",
                        extras=batch.get("extras"))
    head = lm_head(ctx.cfg, params).astype(ctx.compute_dtype)
    w = batch["mask"] * batch["advantages"][:, None]
    denom = jnp.maximum(jnp.sum(batch["mask"]), 1.0)
    pg = losses.weighted_cross_entropy(
        x, batch["labels"], head, w, denom=denom,
        softcap=ctx.cfg.final_logit_softcap)
    return pg + aux


# register the MoE kind (module import avoids a cycle at definition time)
from repro.models import moe as _moe  # noqa: E402

register_kind("moe", schema=_moe.moe_block_schema,
              cache=lambda cfg, B, S, G: _attn_cache_schema(cfg, B, S, G),
              apply=_moe.apply_moe_block)

from repro.models import ssm as _ssm  # noqa: E402

register_kind("mamba", schema=_ssm.mamba_schema, cache=_ssm.mamba_cache_schema,
              apply=_ssm.apply_mamba)
register_kind("mamba_attn", schema=_ssm.mamba_attn_schema,
              cache=_ssm.mamba_attn_cache_schema, apply=_ssm.apply_mamba_attn)
register_kind("rwkv", schema=_ssm.rwkv_schema, cache=_ssm.rwkv_cache_schema,
              apply=_ssm.apply_rwkv)

from repro.models import vlm as _vlm  # noqa: E402

register_kind("cross", schema=_vlm.cross_schema, cache=_vlm.cross_cache_schema,
              apply=_vlm.apply_cross)

from repro.models import mla as _mla  # noqa: E402

register_kind("mla_dense", schema=_mla.dense_schema,
              cache=_mla.mla_cache_schema, apply=_mla.apply_dense)
register_kind("mla_moe", schema=_mla.moe_schema,
              cache=_mla.mla_cache_schema, apply=_mla.apply_moe)


def held_expert_layers(cfg: ModelConfig) -> int:
    """The number of held-expert (``mla_moe``) layers."""
    return (cfg.num_groups * cfg.block_pattern.count("mla_moe")
            + cfg.lead_pattern.count("mla_moe"))


def step_counters(cfg: ModelConfig, aux) -> Dict[str, jax.Array]:
    """The decode step's program counters from ``forward``'s ``aux``:
    ``experts_hit``, the held experts that some token of the step chose,
    averaged over the held-expert layers; none for a model without
    them."""
    n = held_expert_layers(cfg)
    return {"experts_hit": _split_aux(aux)[0] / n} if n else {}


def step_route(cfg: ModelConfig, aux) -> Optional[jax.Array]:
    """The held-expert router's choice from ``forward``'s ``aux`` in
    prefill or decode: (B, T, held-expert layers in order, top_k) int32
    expert ids over all the router's experts; None for a model without
    held-expert layers."""
    outs = _split_aux(aux)[1]
    if not held_expert_layers(cfg) or outs is None:
        return None
    lead = outs.get("lead", {})
    parts = [lead[f"{i}_{kind}"] for i, kind in enumerate(cfg.lead_pattern)
             if f"{i}_{kind}" in lead]
    keys = [f"{i}_{kind}" for i, kind in enumerate(cfg.block_pattern)
            if f"{i}_{kind}" in outs]
    if keys:                # (G, keys, B, T, k): layer g * keys + i
        groups = jnp.stack([outs[k] for k in keys], axis=1)
        parts.append(groups.reshape((-1,) + groups.shape[2:]))
    return jnp.moveaxis(jnp.concatenate(parts), 0, 2)
