"""Gated cross-attention image blocks (llama-3.2-vision style).

The vision tower is a STUB per the assignment: ``input_specs()`` provides
precomputed patch embeddings (B, P, vision_dim); this block projects them to
K/V and cross-attends with tanh-gated residuals.  During decode the cross
K/V are constants — they live in the cache (built at prefill or supplied as
an input spec for decode-only cells).
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn_mod
from repro.models.layers import ModelCtx, rms_norm, swiglu
from repro.models.params import PSpec


def cross_schema(cfg: ModelConfig, G: int) -> Dict[str, PSpec]:
    D, H, KV, dh, F = (cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
                       cfg.resolved_head_dim, cfg.d_ff)
    Vd = cfg.vision_dim
    heads_div = H % 16 == 0
    hq = "tp_heads" if heads_div else None
    hd_ax = "head_dim" if heads_div else "tp_head_dim"
    return {
        "ln1": PSpec((G, D), ("layers", None), "zeros"),
        # fan-in is the contracted dim (see transformer._attn_mlp_schema)
        "wq": PSpec((G, D, H, dh), ("layers", "fsdp", hq, hd_ax),
                    scale=D ** -0.5),
        "wk": PSpec((G, Vd, KV, dh), ("layers", None, "tp_kv_heads", hd_ax),
                    scale=Vd ** -0.5),
        "wv": PSpec((G, Vd, KV, dh), ("layers", None, "tp_kv_heads", hd_ax),
                    scale=Vd ** -0.5),
        "k_norm": PSpec((G, dh), ("layers", None), "zeros"),
        "q_norm": PSpec((G, dh), ("layers", None), "zeros"),
        "wo": PSpec((G, H, dh, D), ("layers", hq, hd_ax, "fsdp"),
                    scale=(H * dh) ** -0.5),
        "gate_attn": PSpec((G,), ("layers",), "zeros"),
        "ln2": PSpec((G, D), ("layers", None), "zeros"),
        "wg": PSpec((G, D, F), ("layers", "fsdp", "tp_ff")),
        "wu": PSpec((G, D, F), ("layers", "fsdp", "tp_ff")),
        "wo_mlp": PSpec((G, F, D), ("layers", "tp_ff", "fsdp")),
        "gate_mlp": PSpec((G,), ("layers",), "zeros"),
    }


def cross_cache_schema(cfg: ModelConfig, B: int, S: int, G: int):
    KV, dh, P = cfg.num_kv_heads, cfg.resolved_head_dim, cfg.num_patches
    ax = ("layers", "batch", "cache_seq", "kv_heads", "head_dim")
    return {"ck": PSpec((G, B, P, KV, dh), ax, "zeros"),
            "cv": PSpec((G, B, P, KV, dh), ax, "zeros")}


def _cross_attention(ctx: ModelCtx, q, k, v):
    """Full (unmasked) attention over patches.  q (B,S,H,dh); k/v (B,P,KV,dh).

    Same GQA-sharding note as models.attention: KV < tp would replicate, so
    repeat K/V to H heads (patch count is small; the repeat is sharded)."""
    B, S, H, dh = q.shape
    KV = k.shape[2]
    tp = ctx.mesh.shape.get("model", 1) if ctx.mesh is not None else 1
    hax = ("batch", "seq", "heads", "head_dim")
    q = ctx.cons(q, hax)
    if 1 < KV < tp and H % tp == 0:
        k = ctx.cons(jnp.repeat(k, H // KV, axis=2), ("batch", None, "heads",
                                                      "head_dim"))
        v = ctx.cons(jnp.repeat(v, H // KV, axis=2), ("batch", None, "heads",
                                                      "head_dim"))
        KV = H
    g = H // KV
    qr = q.reshape(B, S, KV, g, dh)
    # q-chunked (non-causal) so per-chunk (c, P) scores bound live memory
    out = attn_mod._qchunk_attention(
        qr, k, v, scale=dh ** -0.5, window=None, cap=None, chunk=512,
        causal=False)
    return out.reshape(B, S, H, dh)


def apply_cross(ctx: ModelCtx, p, x, *, mode, positions, cache, pos, shared,
                extras):
    cfg = ctx.cfg
    cd = ctx.compute_dtype
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    q = jnp.einsum("bsd,dhk->bshk", h, p["wq"].astype(cd))
    q = rms_norm(q, p["q_norm"], cfg.norm_eps)

    if mode == "decode":
        k, v = cache["ck"].astype(cd), cache["cv"].astype(cd)
        new_cache = {"ck": cache["ck"], "cv": cache["cv"]}
    else:
        img = extras["image_embeds"].astype(cd)      # (B, P, Vd)
        k = jnp.einsum("bpv,vhk->bphk", img, p["wk"].astype(cd))
        v = jnp.einsum("bpv,vhk->bphk", img, p["wv"].astype(cd))
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
        cax = ("batch", "cache_seq", "kv_heads", "head_dim")
        new_cache = {"ck": ctx.cons(k, cax), "cv": ctx.cons(v, cax)} \
            if mode == "prefill" else {}

    out = _cross_attention(ctx, q, k, v)
    out = attn_mod.attn_out(ctx, p, out)
    x = x + jnp.tanh(p["gate_attn"].astype(jnp.float32)).astype(cd) * out

    h2 = rms_norm(x, p["ln2"], cfg.norm_eps)
    mlp = swiglu(ctx, {"wg": p["wg"], "wu": p["wu"], "wo": p["wo_mlp"]}, h2)
    x = x + jnp.tanh(p["gate_mlp"].astype(jnp.float32)).astype(cd) * mlp
    return x, new_cache, 0.0
