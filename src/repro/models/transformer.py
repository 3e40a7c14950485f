"""Decoder-only LM over heterogeneous *layer groups*.

``cfg.block_pattern`` is the repeating unit (e.g. gemma2: ("local","global"),
llama-3.2-vision: ("attn",)*4 + ("cross",)); parameters for each position are
stacked along a leading group axis and the model scans over groups — the HLO
is depth-independent, which keeps 512-way dry-run compiles tractable.

Each block *kind* registers (schema, cache_schema, apply) in KINDS; dense
attention kinds live here, MoE in models.moe, Mamba2/RWKV6 in models.ssm.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Optional

import jax
import jax.ad_checkpoint
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn_mod
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


def insert_kv(cache, k, v, pos):
    """Write this step's k/v (B,1,KV,dh) into the cache at ``pos``.

    ``pos`` is a scalar (whole-batch decode, all rows at the same position)
    or a (B,) vector (slot-based continuous batching: every row of the
    batch is a different request at its own sequence position).  A vector
    entry >= cache length writes nothing — a free/overflowed slot is a
    no-op rather than an out-of-bounds clamp.
    """
    with jax.named_scope("kv_write"):
        pos = jnp.asarray(pos)
        if pos.ndim == 0:
            k_cache = jax.lax.dynamic_update_slice_in_dim(cache["k"], k, pos,
                                                          axis=1)
            v_cache = jax.lax.dynamic_update_slice_in_dim(cache["v"], v, pos,
                                                          axis=1)
            return k_cache, v_cache
        S = cache["k"].shape[1]
        hit = (jnp.arange(S)[None, :] == pos[:, None])[..., None, None]
        return (jnp.where(hit, k, cache["k"]),
                jnp.where(hit, v, cache["v"]))


def paged_layer_view(pool, group, tables):
    """Layer group ``group``'s contiguous (B, S, ...) views of a paged
    pool, whose leaves are (groups, num_blocks, block_size, ...):
    ``tables`` (B, S // block_size) lists every slot's blocks in order.
    One gather per leaf, indexed by group and block together, so the
    group's slice of the pool is never copied out first."""
    def gather(leaf):
        g = leaf[group, tables]                   # (B, nb, bs, *tail)
        return g.reshape(g.shape[0], g.shape[1] * g.shape[2], *g.shape[3:])
    with jax.named_scope("kv_gather"):
        return jax.tree.map(gather, pool)


def written_rows(views, pos):
    """The row each slot's decode step wrote into its (B, S, ...) view,
    (B, ...) per leaf: slot i's row ``pos[i]``, clamped to S-1 where the
    slot wrote nothing (the write-back sends those rows to the null
    block)."""
    with jax.named_scope("kv_scatter"):
        return jax.tree.map(
            lambda leaf: leaf[jnp.arange(leaf.shape[0]), pos], views)


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
        k_cache, v_cache = insert_kv(cache, k, v, pos)
        out = attn_mod.decode_attention(
            ctx, q, k_cache, v_cache, pos, window=window,
            logit_softcap=cfg.attn.logit_softcap)
        new_cache = {"k": k_cache, "v": v_cache}
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
    G = cfg.num_groups
    return {f"{i}_{kind}": KINDS[kind]["cache"](cfg, B, S, G)
            for i, kind in enumerate(cfg.block_pattern)}


# ---------------------------------------------------------------------------
# forward passes
# ---------------------------------------------------------------------------

def _scan_groups(ctx: ModelCtx, params, x, *, mode, positions, caches, pos,
                 extras, tables=None):
    """Scan (or unrolled loop) over layer groups; returns (x, new_caches).

    With ``tables`` (paged decode) ``caches`` is the block pool and the
    scan runs over group indices: each group gathers its own layers' views
    from the pool, and its scan output is only the row each slot wrote,
    (G, B, ...) per leaf.  (A group's slice of the pool taken as ``xs``
    is copied out before the gather reads it.)"""
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
            gc = paged_layer_view(caches, gc, tables)
        new_gc = {}
        for i, kind in enumerate(cfg.block_pattern):
            key = f"{i}_{kind}"
            x, nc, a = layer_fns[key](
                x, gp[key], None if gc is None else gc[key])
            new_gc[key] = nc
            aux = aux + a
            if mode == "train" and par.sequence_parallel:
                # saved per-layer inputs stay seq-sharded under remat
                x = ctx.cons(x, ("batch", "act_seq_sharded", None))
        if tables is not None:
            new_gc = written_rows(new_gc, pos)
        return (x, aux), new_gc

    if mode == "train" and par.remat:
        body = jax.checkpoint(body, prevent_cse=False, policy=policy)

    aux0 = jnp.zeros((), jnp.float32)
    G = cfg.num_groups
    per_group = caches if tables is None else jnp.arange(G)
    if par.scan_layers:
        xs = (blocks, per_group)
        (x, aux), new_caches = jax.lax.scan(body, (x, aux0), xs)
    else:
        ncs = []
        aux = aux0
        for gi in range(G):
            gp = jax.tree.map(lambda a: a[gi], blocks)
            gc = (None if per_group is None
                  else jax.tree.map(lambda a: a[gi], per_group))
            (x, aux), nc = body((x, aux), (gp, gc))
            ncs.append(nc)
        new_caches = (jax.tree.map(lambda *a: jnp.stack(a), *ncs)
                      if ncs and ncs[0] else None)
    return x, new_caches, aux


def forward(ctx: ModelCtx, params, tokens, *, mode: str = "train",
            caches=None, pos=None, extras=None, tables=None):
    """tokens (B,St) int32.  mode train|prefill: St=S; decode: St=1.

    Returns (final hidden states (B,St,D), new_caches, aux_loss) — callers
    pick the head: chunked xent for training, last-token logits for serving.
    Decode with block ``tables`` (B, S // block_size) reads a paged pool
    as ``caches`` and returns, as ``new_caches``, only the rows written
    (layers, B, ...) for the caller to scatter into the pool.
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
    x, new_caches, aux = _scan_groups(ctx, params, x, mode=mode,
                                      positions=positions, caches=caches,
                                      pos=pos, extras=extras, tables=tables)
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
