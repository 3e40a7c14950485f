"""Multi-head latent attention (MLA) and the DeepSeek-V3 / Kimi K2 blocks.

Per token, with H heads (Kimi K2: 64):

    q      = W_qb · norm(W_qa · h)          (H, nope + rope) = (H, 128 + 64)
    c, kr  = split(W_kva · h)               latent (512), rotary key (64)
    c      = norm(c)
    k_h    = [W_uk_h · c, rope(kr)]         one rotary key shared by heads
    v_h    = W_uv_h · c
    out    = W_o · concat_h softmax(q_h · k_h · scale) v_h

with yarn rope frequencies and ``scale = mscale² / sqrt(nope + rope)``
(``mscale = 0.1 ln(factor) + 1``).  Prefill and training run these
expanded heads.  Decode keeps only ``[c, rope(kr)]`` per token (the
cache leaf ``latent``, 512 + 64 wide) and absorbs the up-projections:
the scores are ``[W_uk_hᵀ q_nope_h, q_rope_h] · latent`` and the values
``W_uv_hᵀ (Σ p_h c)``, so a step reads 576 numbers per cached token and
never expands the heads.  The leaf is padded with zeros to a multiple of
the TPU's 128 lanes (640): a 576- or 64-wide pool is laid out by the
chip with its block axis innermost, and the decode step then copies the
whole pool to gather and to write it.

Kinds: ``mla_dense`` (MLA + a SwiGLU MLP, the leading dense layer) and
``mla_moe`` (MLA + the held-expert layer of ``models.moe`` beside its
shared expert).  Rotary halves are rotated as the rest of the program
rotates them (first half against second half), not interleaved as the
published checkpoint stores them: with weights drawn at random this is a
relabelling of the rotary columns.
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, YarnConfig
from repro.models import attention as attn_mod
from repro.models import kvcache
from repro.models.layers import ModelCtx, rms_norm, rope
from repro.models.params import PSpec

F32 = jnp.float32
LANES = 128


# ---------------------------------------------------------------- yarn rope
def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    """YaRN's attention temperature factor, as DeepSeek-V3 computes it."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def _correction_dim(rotations: float, dim: int, base: float,
                    max_pos: int) -> float:
    return (dim * math.log(max_pos / (rotations * 2 * math.pi))
            / (2 * math.log(base)))


def yarn_inv_freq(dim: int, base: float, y: YarnConfig) -> np.ndarray:
    """(dim // 2,) float32 inverse frequencies: the original ones below
    the low correction dim, the ones divided by ``factor`` above the
    high one, a linear blend between."""
    exps = np.arange(0, dim, 2, dtype=np.float64) / dim
    extra = 1.0 / base ** exps
    inter = 1.0 / (y.factor * base ** exps)
    orig = y.original_max_position_embeddings
    low = max(math.floor(_correction_dim(y.beta_fast, dim, base, orig)), 0)
    high = min(math.ceil(_correction_dim(y.beta_slow, dim, base, orig)),
               dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp                   # 1: the original frequency
    return (inter * (1.0 - keep) + extra * keep).astype(np.float32)


def rope_inv_freq(cfg: ModelConfig) -> np.ndarray:
    dim, base = cfg.mla.qk_rope_head_dim, cfg.attn.rope_theta
    y = cfg.attn.rope_scaling
    if y is None:
        return (1.0 / base ** (np.arange(0, dim, 2) / dim)).astype(np.float32)
    return yarn_inv_freq(dim, base, y)


def rope_mscale(cfg: ModelConfig) -> float:
    """The factor on yarn's cos and sin (1 where mscale equals
    mscale_all_dim, as in Kimi K2)."""
    y = cfg.attn.rope_scaling
    if y is None:
        return 1.0
    return yarn_mscale(y.factor, y.mscale) / yarn_mscale(y.factor,
                                                         y.mscale_all_dim)


def softmax_scale(cfg: ModelConfig) -> float:
    m = cfg.mla
    scale = (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5
    y = cfg.attn.rope_scaling
    if y is not None and y.mscale_all_dim:
        scale *= yarn_mscale(y.factor, y.mscale_all_dim) ** 2
    return scale


def _rope(cfg: ModelConfig, x, positions):
    out = rope(x, positions, cfg.attn.rope_theta,
               jnp.asarray(rope_inv_freq(cfg)))
    m = rope_mscale(cfg)
    return out if m == 1.0 else (out * m).astype(x.dtype)


# ------------------------------------------------------------------ schemas
def mla_schema(cfg: ModelConfig, G: int) -> Dict[str, PSpec]:
    m, D, H = cfg.mla, cfg.d_model, cfg.num_heads
    Rq, R = m.q_lora_rank, m.kv_lora_rank
    dn, dr, dv = m.qk_nope_head_dim, m.qk_rope_head_dim, m.v_head_dim
    return {
        "ln1": PSpec((G, D), ("layers", None), "zeros"),
        "wq_a": PSpec((G, D, Rq), ("layers", "fsdp", None), scale=D ** -0.5),
        "q_norm": PSpec((G, Rq), ("layers", None), "zeros"),
        "wq_b": PSpec((G, Rq, H, dn + dr), ("layers", None, "tp_heads", None),
                      scale=Rq ** -0.5),
        "wkv_a": PSpec((G, D, R + dr), ("layers", "fsdp", None),
                       scale=D ** -0.5),
        "kv_norm": PSpec((G, R), ("layers", None), "zeros"),
        "w_uk": PSpec((G, R, H, dn), ("layers", None, "tp_heads", None),
                      scale=R ** -0.5),
        "w_uv": PSpec((G, R, H, dv), ("layers", None, "tp_heads", None),
                      scale=R ** -0.5),
        "wo": PSpec((G, H, dv, D), ("layers", "tp_heads", None, "fsdp"),
                    scale=(H * dv) ** -0.5),
        "ln2": PSpec((G, D), ("layers", None), "zeros"),
    }


def latent_width(cfg: ModelConfig) -> int:
    """The cached row: the latent and the rotary key, padded to lanes."""
    m = cfg.mla
    return -(-(m.kv_lora_rank + m.qk_rope_head_dim) // LANES) * LANES


def mla_cache_schema(cfg: ModelConfig, B: int, S: int, G: int
                     ) -> Dict[str, PSpec]:
    """The latent cache: per token the normed latent (R) and the rotated
    key (dr), side by side, then zeros to ``latent_width``."""
    return {"latent": PSpec((G, B, S, latent_width(cfg)),
                            ("layers", "batch", "cache_seq", None), "zeros")}


def _row(cfg: ModelConfig, c, k_pe):
    """The cached rows of (B, T, R) latents and (B, T, dr) rotary keys."""
    pad = latent_width(cfg) - c.shape[-1] - k_pe.shape[-1]
    return jnp.concatenate(
        [c, k_pe, jnp.zeros(c.shape[:-1] + (pad,), c.dtype)], axis=-1)


def dense_schema(cfg: ModelConfig, G: int) -> Dict[str, PSpec]:
    D, F = cfg.d_model, cfg.d_ff
    return dict(mla_schema(cfg, G),
                wg=PSpec((G, D, F), ("layers", "fsdp", "tp_ff")),
                wu=PSpec((G, D, F), ("layers", "fsdp", "tp_ff")),
                wo_mlp=PSpec((G, F, D), ("layers", "tp_ff", "fsdp")))


def moe_schema(cfg: ModelConfig, G: int) -> Dict[str, PSpec]:
    from repro.models import moe
    return dict(mla_schema(cfg, G), **moe.held_expert_schema(cfg, G))


# ---------------------------------------------------------------- attention
def _absorbed_decode(ctx: ModelCtx, p, q_nope, q_pe, cache, row, pos,
                     scale):
    """One token per slot against its cache with the step's own ``row``
    (B, 1, latent_width) (``kvcache.attend``); returns (B, 1, H, dv) and
    the layer's new cache."""
    cd = ctx.compute_dtype
    R = p["w_uk"].shape[0]
    q_lat = jnp.einsum("bthn,rhn->bthr", q_nope, p["w_uk"].astype(cd))
    pad = latent_width(ctx.cfg) - R - q_pe.shape[-1]
    q = jnp.concatenate(
        [q_lat, q_pe, jnp.zeros(q_pe.shape[:-1] + (pad,), cd)], axis=-1)

    def dense(views):
        latent = views["latent"]
        s = jnp.einsum("bthr,bsr->bhts", q, latent).astype(F32) * scale
        S = latent.shape[1]
        mask = jnp.arange(S)[None] <= jnp.reshape(pos, (-1, 1))  # (1|B, S)
        s = jnp.where(mask[:, None, None], s, attn_mod.NEG_INF)
        m = jnp.max(s, axis=-1, keepdims=True)
        e = jnp.exp(s - m)
        # the rotary and pad columns ride along rather than cutting the view
        lat = jnp.einsum("bhts,bsr->bthr", e.astype(cd), latent).astype(F32)
        den = jnp.moveaxis(jnp.sum(e, axis=-1), 1, 2)[..., None]
        return (lat[..., :R] / den).astype(cd)

    lat, new_cache = kvcache.attend(cache, {"latent": row}, pos, q, dense,
                                    scale=scale)
    return jnp.einsum("bthr,rhv->bthv", lat[..., :R],
                      p["w_uv"].astype(cd)), new_cache


def _expanded(ctx: ModelCtx, p, q_nope, q_pe, c, k_pe, scale):
    """Causal attention over the expanded heads (prefill, training)."""
    cd = ctx.compute_dtype
    k_nope = jnp.einsum("btr,rhn->bthn", c, p["w_uk"].astype(cd))
    v = jnp.einsum("btr,rhv->bthv", c, p["w_uv"].astype(cd))
    k_pe = jnp.broadcast_to(k_pe[:, :, None, :],
                            k_nope.shape[:3] + (k_pe.shape[-1],))
    q = jnp.concatenate([q_nope, q_pe], axis=-1)
    k = jnp.concatenate([k_nope, k_pe], axis=-1)
    out = attn_mod._qchunk_attention(q[:, :, :, None, :], k, v, scale=scale,
                                     window=None, cap=None, chunk=512)
    return out[:, :, :, 0, :]


def mla_part(ctx: ModelCtx, p, x, *, mode, positions, cache, pos):
    """Pre-norm MLA sub-block: x + attention, and the new cache (decode:
    the written views, or on the paged kernel path the step's row;
    prefill: the prompt's latents and rotary keys)."""
    cfg, m = ctx.cfg, ctx.cfg.mla
    cd = ctx.compute_dtype
    eps = cfg.norm_eps
    dn, R = m.qk_nope_head_dim, m.kv_lora_rank
    scale = softmax_scale(cfg)
    with jax.named_scope("mla"):
        h = rms_norm(x, p["ln1"], eps)
        cq = rms_norm(jnp.einsum("btd,dr->btr", h, p["wq_a"].astype(cd)),
                      p["q_norm"], eps)
        q = jnp.einsum("btr,rhk->bthk", cq, p["wq_b"].astype(cd))
        q_nope, q_pe = q[..., :dn], _rope(cfg, q[..., dn:], positions)
        kv = jnp.einsum("btd,dc->btc", h, p["wkv_a"].astype(cd))
        c = rms_norm(kv[..., :R], p["kv_norm"], eps)
        k_pe = _rope(cfg, kv[..., None, R:], positions)[:, :, 0, :]
        new_cache = {}
        if mode == "decode":
            out, new_cache = _absorbed_decode(
                ctx, p, q_nope, q_pe, cache, _row(cfg, c, k_pe), pos, scale)
        else:
            out = _expanded(ctx, p, q_nope, q_pe, c, k_pe, scale)
            if mode == "prefill":
                new_cache = {"latent": _row(cfg, c, k_pe)}
        out = jnp.einsum("bthv,hvd->btd", out, p["wo"].astype(cd))
    return x + out, new_cache


# -------------------------------------------------------------------- kinds
def apply_dense(ctx, p, x, *, mode, positions, cache, pos, shared, extras):
    from repro.models.transformer import mlp_part
    x, new_cache = mla_part(ctx, p, x, mode=mode, positions=positions,
                            cache=cache, pos=pos)
    return mlp_part(ctx, p, x, mode), new_cache, 0.0


def apply_moe(ctx, p, x, *, mode, positions, cache, pos, shared, extras):
    """MLA, then the held experts beside the shared expert.  In prefill
    and decode the auxiliary value is the pair (the number of held
    experts the tokens reached, the router's choice (B, T, top_k)); in
    training 0 (the bias-balanced router has no loss term)."""
    from repro.models import moe
    x, new_cache = mla_part(ctx, p, x, mode=mode, positions=positions,
                            cache=cache, pos=pos)
    h = rms_norm(x, p["ln2"], ctx.cfg.norm_eps)
    out, hits, ids = moe.held_expert_mlp(ctx, p, h)
    return x + out, new_cache, (0.0 if mode == "train" else (hits, ids))
