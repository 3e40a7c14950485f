"""GQA attention with two TP strategies + context-parallel decode.

Strategies (picked per arch by head divisibility vs the model axis):

  "heads" — Megatron-style: q/k/v head-sharded on "model"; q-chunked causal
            scan (never materializes (S,S) scores).  Needs H % tp == 0.
  "seq"   — sequence-sharded attention for archs whose head count does not
            divide the model axis (phi4: 24 heads, whisper: 12).  q stays
            seq-sharded; the small GQA k/v are all-gathered (2*KV*dh ≪ D
            bytes/token).  Training uses one full-scores block per layer
            (transient, remat'd); no-grad prefill uses a k-chunked
            online-softmax scan (flash recurrence) to bound live memory.

  decode  — one token vs a seq-sharded KV cache: explicit partial-max /
            partial-sum reductions (flash-decode) so GSPMD emits tiny stat
            all-reduces, never an all-gather of the cache.

The Pallas flash kernel (repro.kernels.flash_attention) is the TPU hot-spot
implementation validated against repro.kernels.ref; these XLA paths are what
the dry-run lowers (DESIGN.md §2).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.models.layers import ModelCtx, rope, softcap

NEG_INF = -1e30


def attn_strategy(ctx: ModelCtx) -> str:
    tp = ctx.mesh.shape.get("model", 1) if ctx.mesh is not None else 1
    return "heads" if ctx.cfg.num_heads % tp == 0 else "seq"


def qkv_proj(ctx: ModelCtx, p, x: jax.Array, positions: jax.Array,
             strategy: str = "heads"):
    """x (B,S,D) -> q (B,S,H,dh), k/v (B,S,KV,dh), RoPE'd, strategy-placed."""
    cd = ctx.compute_dtype
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(cd))
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(cd))
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(cd))
    if "bq" in p:
        q = q + p["bq"].astype(cd)
        k = k + p["bk"].astype(cd)
        v = v + p["bv"].astype(cd)
    if ctx.cfg.attn.use_rope:
        q = rope(q, positions, ctx.cfg.attn.rope_theta)
        k = rope(k, positions, ctx.cfg.attn.rope_theta)
    if strategy == "seq":
        q = ctx.cons(q, ("batch", "act_seq_sharded", None, None))
        k = ctx.cons(k, ("batch", None, None, None))   # replicated == AG(kv)
        v = ctx.cons(v, ("batch", None, None, None))
    else:
        q = ctx.cons(q, ("batch", "seq", "heads", "head_dim"))
        k = ctx.cons(k, ("batch", "seq", "kv_heads", "head_dim"))
        v = ctx.cons(v, ("batch", "seq", "kv_heads", "head_dim"))
    return q, k, v


def _mask(qpos, kpos, window, causal=True):
    if not causal:
        return jnp.ones((qpos.shape[0], kpos.shape[0]), bool)
    m = kpos[None, :] <= qpos[:, None]
    if window is not None:
        m &= kpos[None, :] > qpos[:, None] - window
    return m


def _qchunk_attention(q, k, v, *, scale, window, cap, chunk, causal=True):
    """Scan over q chunks; q seq dim unsharded ("heads" strategy).

    The per-chunk fn is checkpointed so backward recomputes each chunk's
    probabilities instead of saving (Sq, Sk)-worth of residuals.  v's
    head size may differ from q's and k's (MLA: 192 against 128).
    """
    B, Sq, KV, g, dh = q.shape
    Sk = k.shape[1]
    chunk = min(chunk, Sq)
    if Sq % chunk:
        chunk = Sq
    nq = Sq // chunk
    kpos = jnp.arange(Sk)

    @jax.checkpoint
    def one(i):
        qs = jax.lax.dynamic_slice_in_dim(q, i * chunk, chunk, axis=1)
        s = jnp.einsum("bckgd,bskd->bkgcs", qs, k).astype(jnp.float32) * scale
        s = softcap(s, cap)
        qpos = i * chunk + jnp.arange(chunk)
        s = jnp.where(_mask(qpos, kpos, window, causal)[None, None, None],
                      s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
        return jnp.einsum("bkgcs,bskd->bckgd", p, v)

    if nq == 1:
        return one(jnp.int32(0))
    _, ys = jax.lax.scan(lambda c, i: (c, one(i)), None, jnp.arange(nq))
    return jnp.moveaxis(ys, 0, 1).reshape(B, Sq, KV, g, v.shape[-1])


def _full_attention(q, k, v, *, scale, window, cap, causal=True):
    """One scores block — used when q's seq dim is sharded (training)."""
    B, Sq, KV, g, dh = q.shape
    Sk = k.shape[1]
    s = jnp.einsum("bqkgd,bskd->bkgqs", q, k).astype(jnp.float32) * scale
    s = softcap(s, cap)
    m = _mask(jnp.arange(Sq), jnp.arange(Sk), window, causal)
    s = jnp.where(m[None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    return jnp.einsum("bkgqs,bskd->bqkgd", p, v)


def _kchunk_flash(q, k, v, *, scale, window, cap, chunk, causal=True):
    """Online-softmax scan over k chunks (no-grad prefill, seq-sharded q)."""
    B, Sq, KV, g, dh = q.shape
    Sk = k.shape[1]
    chunk = min(chunk, Sk)
    if Sk % chunk:
        chunk = Sk
    nk = Sk // chunk
    kr = jnp.moveaxis(k.reshape(B, nk, chunk, KV, dh), 1, 0)
    vr = jnp.moveaxis(v.reshape(B, nk, chunk, KV, dh), 1, 0)
    qpos = jnp.arange(Sq)

    m0 = jnp.full((B, KV, g, Sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, KV, g, Sq), jnp.float32)
    a0 = jnp.zeros((B, KV, g, Sq, dh), jnp.float32)

    def step(carry, xs):
        m, l, acc = carry
        kc, vc, i = xs
        s = jnp.einsum("bqkgd,bskd->bkgqs", q, kc).astype(jnp.float32) * scale
        s = softcap(s, cap)
        kpos = i * chunk + jnp.arange(chunk)
        s = jnp.where(_mask(qpos, kpos, window, causal)[None, None, None],
                      s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        l_new = l * alpha + jnp.sum(p, axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bkgqs,bskd->bkgqd", p.astype(v.dtype), vc).astype(jnp.float32)
        return (m_new, l_new, acc_new), None

    (m, l, acc), _ = jax.lax.scan(step, (m0, l0, a0),
                                  (kr, vr, jnp.arange(nk)))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return jnp.moveaxis(out, -2, 1).astype(q.dtype)   # (B,Sq,KV,g,dh)


def causal_attention(ctx: ModelCtx, q: jax.Array, k: jax.Array, v: jax.Array,
                     *, window: Optional[int] = None,
                     logit_softcap: Optional[float] = None,
                     strategy: str = "heads", mode: str = "train",
                     chunk: int = 512, causal: bool = True) -> jax.Array:
    """Chunked (optionally causal) GQA.
    q (B,Sq,H,dh); k,v (B,Sk,KV,dh) -> (B,Sq,H,dh).

    GQA sharding note: reshaping H -> (KV, g) makes BOTH factors too small to
    shard on a 16-way model axis when KV < 16 (gemma2/kimi/vlm: KV=8), which
    would force GSPMD to replicate attention.  When KV < tp we instead repeat
    K/V up to H heads (repeat is sharded, (B,S,H/tp,dh) per chip) and run
    plain MHA einsums sharded on H.
    """
    B, Sq, H, dh = q.shape
    KV = k.shape[2]
    tp = ctx.mesh.shape.get("model", 1) if ctx.mesh is not None else 1
    if strategy == "heads" and 1 < KV < tp and H % tp == 0:
        g = H // KV
        k = ctx.cons(jnp.repeat(k, g, axis=2),
                     ("batch", "seq", "heads", "head_dim"))
        v = ctx.cons(jnp.repeat(v, g, axis=2),
                     ("batch", "seq", "heads", "head_dim"))
        KV = H
    qr = q.reshape(B, Sq, KV, H // KV, dh)
    scale = dh ** -0.5
    if strategy == "seq":
        if mode == "train":
            out = _full_attention(qr, k, v, scale=scale, window=window,
                                  cap=logit_softcap, causal=causal)
        else:
            out = _kchunk_flash(qr, k, v, scale=scale, window=window,
                                cap=logit_softcap, chunk=max(chunk, 1024),
                                causal=causal)
        out = out.reshape(B, Sq, H, dh)
        return ctx.cons(out, ("batch", "act_seq_sharded", None, None))
    out = _qchunk_attention(qr, k, v, scale=scale, window=window,
                            cap=logit_softcap, chunk=chunk, causal=causal)
    out = out.reshape(B, Sq, H, dh)
    return ctx.cons(out, ("batch", "seq", "heads", "head_dim"))


def decode_attention(ctx: ModelCtx, q: jax.Array, k_cache: jax.Array,
                     v_cache: jax.Array, pos: jax.Array,
                     *, window: Optional[int] = None,
                     logit_softcap: Optional[float] = None,
                     causal: bool = True) -> jax.Array:
    """One-token attention vs a (possibly seq-sharded) KV cache — flash-decode
    via explicit partial reductions; see module docstring."""
    B, _, H, dh = q.shape
    S, KV = k_cache.shape[1], k_cache.shape[2]
    g = H // KV
    scale = dh ** -0.5
    qr = q.reshape(B, KV, g, dh)
    s = jnp.einsum("bkgd,bskd->bkgs", qr, k_cache).astype(jnp.float32) * scale
    s = softcap(s, logit_softcap)
    kpos = jnp.arange(S)
    pos_col = jnp.reshape(pos, (-1, 1))              # scalar or (B,) position
    mask = kpos[None] <= pos_col                     # (1|B, S) valid history
    if not causal:
        mask = jnp.ones_like(mask)
    if window is not None and causal:
        mask &= kpos[None] > pos_col - window
    s = jnp.where(mask[:, None, None], s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)           # reduce over S -> AR(max)
    p = jnp.exp(s - jax.lax.stop_gradient(m))
    num = jnp.einsum("bkgs,bskd->bkgd", p.astype(q.dtype), v_cache)
    den = jnp.sum(p, axis=-1)                        # (B,KV,g) -> AR(sum)
    out = num.astype(jnp.float32) / den[..., None]
    return out.reshape(B, 1, H, dh).astype(q.dtype)


def attn_out(ctx: ModelCtx, p, attn: jax.Array) -> jax.Array:
    return jnp.einsum("bshk,hkd->bsd", attn, p["wo"].astype(ctx.compute_dtype))
