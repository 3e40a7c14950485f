"""Whisper-style encoder-decoder backbone (family="audio").

The conv/mel frontend is a STUB per the assignment: ``extras["frames"]``
supplies precomputed frame embeddings (B, T_enc, d_model).  The encoder is
bidirectional; the decoder is causal self-attn + cross-attn to the encoder
output.  Shape semantics for the assigned cells:

  train_4k / prefill_32k : T_enc = shape.seq_len frames, decoder_len tokens
  decode_32k             : 1 new decoder token vs a cross K/V cache of
                           T_enc = seq_len (the seq_len-sized cache) plus a
                           decoder self cache of decoder_len.

This module mirrors repro.models.transformer's API (lm_schema, cache_schema,
forward, lm_logits, loss_fn) so runtime/steps.py can dispatch by family.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn_mod
from repro.models import losses
from repro.models.kvcache import insert_kv
from repro.models.layers import ModelCtx, rms_norm, swiglu, unembed
from repro.models.params import PSpec
from repro.models.transformer import _attn_mlp_schema


def _enc_frames(cfg: ModelConfig) -> int:
    assert cfg.encoder_frames > 0, "set encoder_frames from shape.seq_len"
    return cfg.encoder_frames


def lm_schema(cfg: ModelConfig) -> Dict[str, Any]:
    Ge = cfg.encoder_layers
    Gd = cfg.num_layers
    dec_blocks = _attn_mlp_schema(cfg, Gd)
    D, KV, dh = cfg.d_model, cfg.num_kv_heads, cfg.resolved_head_dim
    H = cfg.num_heads
    heads_div = H % 16 == 0
    hq = "tp_heads" if heads_div else None
    hd_ax = "head_dim" if heads_div else "tp_head_dim"
    dec_blocks.update({
        "ln_x": PSpec((Gd, D), ("layers", None), "zeros"),
        "xwq": PSpec((Gd, D, H, dh), ("layers", "fsdp", hq, hd_ax)),
        "xwk": PSpec((Gd, D, KV, dh), ("layers", "fsdp", "tp_kv_heads", hd_ax)),
        "xwv": PSpec((Gd, D, KV, dh), ("layers", "fsdp", "tp_kv_heads", hd_ax)),
        "xwo": PSpec((Gd, H, dh, D), ("layers", hq, hd_ax, "fsdp")),
    })
    return {
        "embed": PSpec((cfg.vocab_size, D), ("tp_vocab", "fsdp"), scale=0.02),
        "pos_dec": PSpec((cfg.decoder_len, D), (None, None), scale=0.02),
        "enc_blocks": _attn_mlp_schema(cfg, Ge),
        "enc_norm": PSpec((D,), (None,), "zeros"),
        "dec_blocks": dec_blocks,
        "final_norm": PSpec((D,), (None,), "zeros"),
    }


def cache_schema(cfg: ModelConfig, B: int, S: int) -> Dict[str, Any]:
    G = cfg.num_layers
    KV, dh = cfg.num_kv_heads, cfg.resolved_head_dim
    ax = ("layers", "batch", "cache_seq", "kv_heads", "head_dim")
    return {
        "self": {"k": PSpec((G, B, cfg.decoder_len, KV, dh),
                            ("layers", "batch", None, "kv_heads", "head_dim"),
                            "zeros"),
                 "v": PSpec((G, B, cfg.decoder_len, KV, dh),
                            ("layers", "batch", None, "kv_heads", "head_dim"),
                            "zeros")},
        "cross": {"ck": PSpec((G, B, S, KV, dh), ax, "zeros"),
                  "cv": PSpec((G, B, S, KV, dh), ax, "zeros")},
    }


def _sinusoid(S: int, D: int, dtype) -> jax.Array:
    pos = jnp.arange(S)[:, None].astype(jnp.float32)
    dim = jnp.arange(D // 2)[None, :].astype(jnp.float32)
    ang = pos / jnp.power(10000.0, 2 * dim / D)
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], -1).astype(dtype)


def _self_block(ctx, p, x, *, causal, mode="train", cache=None, pos=None):
    cfg = ctx.cfg
    strategy = attn_mod.attn_strategy(ctx)
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    q, k, v = attn_mod.qkv_proj(ctx, p, h, jnp.arange(x.shape[1]), strategy)
    new_cache = {}
    if mode == "decode":
        new_cache = insert_kv(cache, {"k": k, "v": v}, pos)
        out = attn_mod.decode_attention(ctx, q, new_cache["k"],
                                        new_cache["v"], pos)
    else:
        out = attn_mod.causal_attention(ctx, q, k, v, strategy=strategy,
                                        mode=mode, causal=causal)
        if mode == "prefill":
            new_cache = {"k": k, "v": v}
    x = x + attn_mod.attn_out(ctx, p, out)
    h2 = rms_norm(x, p["ln2"], cfg.norm_eps)
    x = x + swiglu(ctx, {"wg": p["wg"], "wu": p["wu"], "wo": p["wo_mlp"]}, h2)
    return x, new_cache


def _cross_part(ctx, p, x, enc_out=None, cache=None, mode="train"):
    """Decoder cross-attention vs encoder output (or its cached K/V)."""
    cfg = ctx.cfg
    cd = ctx.compute_dtype
    strategy = attn_mod.attn_strategy(ctx)
    h = rms_norm(x, p["ln_x"], cfg.norm_eps)
    q = jnp.einsum("bsd,dhk->bshk", h, p["xwq"].astype(cd))
    if mode == "decode":
        k, v = cache["ck"].astype(cd), cache["cv"].astype(cd)
        out = attn_mod.decode_attention(ctx, q, k, v, jnp.int32(0),
                                        causal=False)
        new_cache = {"ck": cache["ck"], "cv": cache["cv"]}
    else:
        k = jnp.einsum("bsd,dhk->bshk", enc_out, p["xwk"].astype(cd))
        v = jnp.einsum("bsd,dhk->bshk", enc_out, p["xwv"].astype(cd))
        out = attn_mod.causal_attention(ctx, q, k, v, strategy=strategy,
                                        mode=mode, causal=False)
        cax = ("batch", "cache_seq", "kv_heads", "head_dim")
        new_cache = {"ck": ctx.cons(k, cax), "cv": ctx.cons(v, cax)} \
            if mode == "prefill" else {}
    out = jnp.einsum("bshk,hkd->bsd", out, p["xwo"].astype(cd))
    return x + out, new_cache


def _encode(ctx: ModelCtx, params, frames: jax.Array,
            mode: str = "train") -> jax.Array:
    cfg = ctx.cfg
    x = frames.astype(ctx.compute_dtype)
    x = x + _sinusoid(x.shape[1], cfg.d_model, x.dtype)[None]
    x = ctx.cons(x, ("batch", "act_seq_sharded", None))

    def body(carry, gp):
        y, _ = _self_block(ctx, gp, carry, causal=False, mode=mode)
        y = ctx.cons(y, ("batch", "act_seq_sharded", None))
        return y, None

    if mode == "train" and ctx.par.remat:
        body = jax.checkpoint(body, prevent_cse=False)
    x, _ = jax.lax.scan(body, x, params["enc_blocks"])
    return rms_norm(x, params["enc_norm"], cfg.norm_eps)


def forward(ctx: ModelCtx, params, tokens, *, mode: str = "train",
            caches=None, pos=None, extras=None):
    """tokens: decoder tokens (B, Td) (Td=1 for decode).

    Returns (decoder hidden states, new caches, aux=0).
    """
    cfg = ctx.cfg
    cd = ctx.compute_dtype
    B, Td = tokens.shape
    x = jnp.take(params["embed"].astype(cd), tokens, axis=0)
    if mode == "decode":
        p = jnp.asarray(pos)
        if p.ndim == 1:        # per-slot positions (continuous batching)
            pvec = jnp.take(params["pos_dec"], p, axis=0)[:, None]   # (B,1,D)
            x = x + pvec.astype(cd)
        else:
            pvec = jax.lax.dynamic_slice_in_dim(params["pos_dec"], pos, 1, 0)
            x = x + pvec.astype(cd)[None]
        enc_out = None
    else:
        x = x + params["pos_dec"].astype(cd)[None, :Td]
        enc_out = _encode(ctx, params, extras["frames"], mode=mode)

    def body(carry, xs):
        h = carry
        gp, gc = xs
        h, nc_self = _self_block(ctx, gp, h, causal=True, mode=mode,
                                 cache=None if gc is None else gc["self"],
                                 pos=pos)
        h, nc_cross = _cross_part(ctx, gp, h, enc_out=enc_out,
                                  cache=None if gc is None else gc["cross"],
                                  mode=mode)
        return h, {"self": nc_self, "cross": nc_cross}

    if mode == "train" and ctx.par.remat:
        body = jax.checkpoint(body, prevent_cse=False)
    x, new_caches = jax.lax.scan(body, x, (params["dec_blocks"], caches))
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return x, new_caches, jnp.zeros((), jnp.float32)


def lm_head(cfg: ModelConfig, params):
    return params["embed"]


def lm_logits(ctx: ModelCtx, params, x) -> jax.Array:
    return unembed(ctx, params["embed"], x, transpose=True)


def loss_fn(ctx: ModelCtx, params, batch) -> jax.Array:
    x, _, _ = forward(ctx, params, batch["tokens"], mode="train",
                      extras=batch["extras"])
    head = params["embed"].astype(ctx.compute_dtype)
    return losses.chunked_cross_entropy(x, batch["labels"], head)
