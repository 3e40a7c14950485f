"""Memory-aware LM loss.

Full logits are (B, S, V) — replicated f32 copies dominate training HBM
(gemma2: 256k vocab).  Two strategies:

  * ``sharded_cross_entropy`` (preferred when vocab AND seq divide the model
    axis): logits stay (B, S/tp, V/tp) — both dims sharded — and the
    softmax/gold reductions are tiny all-reduces.  No gather of logits, no
    scan machinery; peak is one small f32 block per chip.
  * ``chunked_cross_entropy`` (fallback for non-divisible vocabs, e.g.
    granite's 49155): scan over sequence chunks with per-chunk remat.

The chunked path can route its softmax/gold math through the fused Pallas
kernel (``kernels.xent.softmax_xent``: online-logsumexp over vocab tiles,
fused backward, never materializes the f32 softmax).  Gate: ``fused=None``
defaults to on for the TPU backend and off elsewhere; set
``REPRO_FUSED_XENT=1`` to force it on CPU, where it runs under Pallas
interpret mode (correct but slow — parity is pinned by tests/test_kernels.py
against kernels.ref.softmax_xent_ref).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.common import fused_xent_default, interpret_default


def sharded_cross_entropy(ctx, x, labels, head, *, softcap=None):
    """Mean token NLL with (seq x vocab)-sharded logits.

    x (B,S,D) hidden states; labels (B,S) int32; head (V,D).
    The gold logit is extracted with a one-hot einsum (elementwise + reduce
    partitions cleanly; a gather over a sharded vocab would not).
    """
    with jax.named_scope("lm_head"):
        logits = jnp.einsum("bsd,vd->bsv", x, head)        # bf16 compute
        logits = ctx.cons(logits, ("batch", "act_seq_sharded", "act_vocab"))
    lf = logits.astype(jnp.float32)
    if softcap is not None:
        lf = softcap * jnp.tanh(lf / softcap)
    m = jnp.max(lf, axis=-1, keepdims=True)                # AR(max) over V
    lse = m[..., 0] + jnp.log(jnp.sum(jnp.exp(lf - m), axis=-1))
    # gather over the sharded vocab dim -> local masked take + tiny AR
    gold = jnp.take_along_axis(lf, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - gold)


def weighted_cross_entropy(x, labels, head, weights, *, denom=None,
                           softcap=None, chunk: int = 512,
                           fused: Optional[bool] = None):
    """Per-token-weighted NLL — the policy-gradient form of the LM loss.

    x (B,S,D) final hidden states; labels (B,S) int32; head (V,D);
    weights (B,S) f32 — each token's NLL is scaled by its weight before
    the reduction.  RL callers (repro.rl) fold ``mask * advantage`` into
    ``weights``: REINFORCE's surrogate sum_t A_t * -log pi(a_t|s_<t) IS
    advantage-weighted cross entropy, so the same chunked scan (and the
    same fused Pallas softmax-xent kernel, which already returns
    per-token NLL) serves both supervised and RL training.

    ``denom`` normalizes the weighted sum (default: token count B*S;
    RL passes the action-token count sum(mask)).  Zero weights make a
    token's contribution — and its gradient — exactly zero, so padding
    and prompt positions never train.
    """
    B, S, D = x.shape
    if fused is None:
        fused = fused_xent_default()

    def fn(xc, lc, wc):
        logits = _head_logits(xc, head)
        if fused:
            from repro.kernels.xent import softmax_xent
            V = head.shape[0]
            nll = softmax_xent(logits.reshape(-1, V), lc.reshape(-1),
                               softcap=softcap,
                               interpret=interpret_default())
            return jnp.sum(nll * wc.reshape(-1))
        if softcap is not None:
            logits = softcap * jnp.tanh(logits / softcap)
        m = jnp.max(logits, axis=-1)
        lse = m + jnp.log(jnp.sum(jnp.exp(logits - m[..., None]), axis=-1))
        gold = jnp.take_along_axis(logits, lc[..., None], axis=-1)[..., 0]
        return jnp.sum((lse - gold) * wc)

    fn = jax.checkpoint(fn, prevent_cse=False)
    weights = weights.astype(jnp.float32)
    chunk = min(chunk, S)
    if S % chunk:
        chunk = S
    n = S // chunk
    if n == 1:
        total = fn(x, labels, weights)
    else:
        xr = x.reshape(B, n, chunk, D).swapaxes(0, 1)          # (n,B,c,D)
        lr = labels.reshape(B, n, chunk).swapaxes(0, 1)        # (n,B,c)
        wr = weights.reshape(B, n, chunk).swapaxes(0, 1)       # (n,B,c)
        total, _ = jax.lax.scan(
            lambda acc, xs: (acc + fn(*xs), None), 0.0, (xr, lr, wr))
    if denom is None:
        denom = jnp.float32(B * S)
    return total / denom


def _head_logits(x_chunk, head):
    """x (B,c,D) @ head (V,D) -> f32 logits (B,c,V), under the ``lm_head``
    scope (the backward's ops inherit it)."""
    with jax.named_scope("lm_head"):
        return jnp.einsum("bcd,vd->bcv", x_chunk, head).astype(jnp.float32)


def _chunk_nll(x_chunk, labels_chunk, head):
    """x (B,c,D) @ head (V,D) -> mean-able NLL terms for one chunk (f32)."""
    logits = jnp.einsum("bcd,vd->bcv", x_chunk, head).astype(jnp.float32)
    m = jnp.max(logits, axis=-1)
    lse = m + jnp.log(jnp.sum(jnp.exp(logits - m[..., None]), axis=-1))
    gold = jnp.take_along_axis(logits, labels_chunk[..., None], axis=-1)[..., 0]
    return jnp.sum(lse - gold)


def chunked_cross_entropy(x, labels, head, *, softcap=None, chunk: int = 512,
                          fused: Optional[bool] = None):
    """Mean token NLL from final hidden states, seq-chunked.

    x (B,S,D) final hidden states; labels (B,S) int32; head (V,D).
    softcap: final-logit softcap (gemma2) — folded into the chunk fn.
    fused: route the per-chunk softmax/gold math through the fused Pallas
    kernel (None = backend default, see module docstring).
    """
    B, S, D = x.shape
    if fused is None:
        fused = fused_xent_default()

    def fn(xc, lc):
        logits = _head_logits(xc, head)
        if fused:
            from repro.kernels.xent import softmax_xent
            nll = softmax_xent(logits.reshape(-1, head.shape[0]),
                               lc.reshape(-1), softcap=softcap,
                               interpret=interpret_default())
            return jnp.sum(nll)
        if softcap is not None:
            logits = softcap * jnp.tanh(logits / softcap)
        m = jnp.max(logits, axis=-1)
        lse = m + jnp.log(jnp.sum(jnp.exp(logits - m[..., None]), axis=-1))
        gold = jnp.take_along_axis(logits, lc[..., None], axis=-1)[..., 0]
        return jnp.sum(lse - gold)

    fn = jax.checkpoint(fn, prevent_cse=False)
    chunk = min(chunk, S)
    if S % chunk:
        chunk = S
    n = S // chunk
    if n == 1:
        total = fn(x, labels)
    else:
        xr = x.reshape(B, n, chunk, D).swapaxes(0, 1)          # (n,B,c,D)
        lr = labels.reshape(B, n, chunk).swapaxes(0, 1)        # (n,B,c)
        total, _ = jax.lax.scan(
            lambda acc, xs: (acc + fn(xs[0], xs[1]), None), 0.0, (xr, lr))
    return total / (B * S)
