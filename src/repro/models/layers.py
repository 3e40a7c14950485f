"""Shared layers: RMSNorm, RoPE, SwiGLU MLP, embeddings + a ModelCtx carrying
mesh/rules so every module can place activations with logical axes."""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from repro.configs.base import ModelConfig, ParallelConfig
from repro.sharding import specs as sh


@dataclass(frozen=True)
class ModelCtx:
    cfg: ModelConfig
    par: ParallelConfig
    mesh: Optional[Mesh] = None

    @property
    def rules(self):
        return sh.logical_rules(self.par)

    def cons(self, x: jax.Array, axes) -> jax.Array:
        """with_sharding_constraint by logical axes (no-op without a mesh)."""
        if self.mesh is None:
            return x
        return sh.constrain(x, axes, self.mesh, self.rules)

    @property
    def compute_dtype(self):
        return jnp.dtype(self.cfg.compute_dtype)


def rms_norm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    """RMSNorm with f32 *accumulation* but no f32 copy of x.

    x.astype(f32) materializes a 2x-bytes activation copy per call (and its
    backward another) — measured as the dominant per-layer temp at 90B
    scale.  An einsum with preferred_element_type=f32 accumulates the
    variance in f32 while reading bf16, and the scale-multiply stays in the
    input dtype.
    """
    var = jnp.einsum("...d,...d->...", x, x,
                     preferred_element_type=jnp.float32) / x.shape[-1]
    inv = jax.lax.rsqrt(var + eps)[..., None].astype(x.dtype)
    return x * inv * (1.0 + scale.astype(x.dtype))


def rope(x: jax.Array, positions: jax.Array, theta: float,
         inv_freq: Optional[jax.Array] = None) -> jax.Array:
    """Rotary embedding.  x: (B, S, N, dh); positions: (S,) or (B, S).
    ``inv_freq`` (dh // 2,) replaces theta's frequencies (yarn)."""
    dh = x.shape[-1]
    half = dh // 2
    freqs = (theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
             if inv_freq is None else inv_freq)                     # (half,)
    if positions.ndim == 1:
        ang = positions[None, :, None].astype(jnp.float32) * freqs  # (1,S,half)
    else:
        ang = positions[:, :, None].astype(jnp.float32) * freqs     # (B,S,half)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    out = jnp.concatenate([xf1 * cos - xf2 * sin, xf1 * sin + xf2 * cos], axis=-1)
    return out.astype(x.dtype)


def softcap(x: jax.Array, cap: Optional[float]) -> jax.Array:
    if cap is None:
        return x
    return (cap * jnp.tanh(x.astype(jnp.float32) / cap)).astype(x.dtype)


def swiglu(ctx: ModelCtx, p, x: jax.Array) -> jax.Array:
    """p: {"wg","wu": (D, F), "wo": (F, D)} (leading layer dims indexed).

    Gate/up are separate tensors (not a fused (D,2,F)): fused layouts either
    break TP-sharding of F on a slice or degenerate Adafactor row/col
    factoring (observed on the 1T MoE — see DESIGN.md §5).
    """
    cd = ctx.compute_dtype
    gate = jnp.einsum("bsd,df->bsf", x, p["wg"].astype(cd))
    up = jnp.einsum("bsd,df->bsf", x, p["wu"].astype(cd))
    gate = ctx.cons(gate, ("batch", "seq", "act_ff"))
    up = ctx.cons(up, ("batch", "seq", "act_ff"))
    h = jax.nn.silu(gate.astype(jnp.float32)).astype(cd) * up
    return jnp.einsum("bsf,fd->bsd", h, p["wo"].astype(cd))


def embed_tokens(ctx: ModelCtx, embed: jax.Array, tokens: jax.Array) -> jax.Array:
    x = jnp.take(embed.astype(ctx.compute_dtype), tokens, axis=0)
    if getattr(ctx.cfg, "embed_scale", False):
        x = x * jnp.asarray(ctx.cfg.d_model ** 0.5, ctx.compute_dtype)
    return ctx.cons(x, ("batch", "seq", None))


def unembed(ctx: ModelCtx, embed_or_head: jax.Array, x: jax.Array,
            transpose: bool) -> jax.Array:
    """Logits, sharded on vocab (model axis) to avoid replicated (B,S,V)."""
    w = embed_or_head.astype(ctx.compute_dtype)
    eq = "bsd,vd->bsv" if transpose else "bsd,dv->bsv"
    logits = jnp.einsum(eq, x, w)
    logits = ctx.cons(logits, ("batch", "seq", "act_vocab"))
    return softcap(logits, ctx.cfg.final_logit_softcap)


def cross_entropy(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Mean token NLL; stable in f32.  logits (B,S,V) may be vocab-sharded."""
    lf = logits.astype(jnp.float32)
    m = jnp.max(lf, axis=-1, keepdims=True)
    lse = m[..., 0] + jnp.log(jnp.sum(jnp.exp(lf - m), axis=-1))
    gold = jnp.take_along_axis(lf, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - gold)
