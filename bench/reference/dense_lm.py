"""Plain reference of a dense decoder (phi4-mini's block), in float32.

Pre-norm blocks: RMSNorm, grouped-query attention with rotary positions
(the first ``rotary_dims`` of each head, halves rotated), a causal
softmax, an output projection, RMSNorm, SwiGLU; a final RMSNorm and the
tied embedding as the head.  Every matrix product runs at
``Precision.HIGHEST``.  Weights are the benchmark's own
(``bench/weights.py``), held in their stored type and widened per layer.

Departures from the published Phi-4-mini, all shared with the program
that is checked: the norms multiply by ``1 + scale`` (the parameters are
stored as the offset from one), the rotary embedding covers the whole
head with no rope scaling, and AdamW decays every leaf of two or more
dimensions, the stacked norm scales among them.

``quant="fp8"`` computes the same function with both operands of each
product with a weight rounded to 4 exponent and 3 mantissa bits (one
scale per output channel of a weight and per token of an activation),
and in training stores the parameters at that precision after each
update (one scale per row).  That is the control: the comparisons that
decide ``correct`` must tell it from the program.

Every rounding is made by ``lax.reduce_precision``: the TPU compiler may
drop a round trip through a narrower type as excess precision, which
would leave the value unrounded.

Nothing here imports the program.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.model import Dims

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32

# contraction axes of each matrix weight, as stored per layer
_CONTRACT = {"wq": (0,), "wk": (0,), "wv": (0,), "wo": (0, 1), "wg": (0,),
             "wu": (0,), "wo_mlp": (0,)}


def quantize(w: jax.Array, axes: Tuple[int, ...], quant: Optional[str]
             ) -> jax.Array:
    """``w`` in float32, optionally rounded to ``quant`` (``"fp8"``: e4m3,
    whose largest finite value under ``reduce_precision`` is 240, scaled
    by the largest magnitude over ``axes``).  The gradient passes the
    rounding straight through to ``w``, as training with low-precision
    products does."""
    w = w.astype(F32)
    if quant is None:
        return w
    if quant != "fp8":
        raise ValueError(f"unknown quant {quant!r}")
    s = jnp.maximum(jnp.max(jnp.abs(w), axis=axes, keepdims=True),
                    1e-30) / 240.0
    q = jax.lax.reduce_precision(w / s, exponent_bits=4, mantissa_bits=3) * s
    return w + jax.lax.stop_gradient(q - w)


# (exponent, mantissa) bits of each stored type narrower than float32
_STORED_BITS = {"bfloat16": (8, 7), "float16": (5, 10)}


def store(p: jax.Array, dtype: str, quant: Optional[str]) -> jax.Array:
    """Float32 ``p`` rounded to what its stored type ``dtype`` holds, or
    to the control's precision with one scale per row."""
    if quant is not None:
        return quantize(p, (-1,), quant)
    bits = _STORED_BITS.get(str(dtype))
    return p if bits is None else jax.lax.reduce_precision(p, *bits)


def act(x: jax.Array, quant: Optional[str]) -> jax.Array:
    """An activation entering a product with a weight, rounded per token
    through ``quant``."""
    return quantize(x, (-1,), quant)


def rms_norm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + scale.astype(F32))


def rope(x, positions, theta, rotary_dims):
    """x (B, T, N, dh) float32; positions (T,)."""
    half = rotary_dims // 2
    freqs = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = positions[:, None].astype(F32) * freqs
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:rotary_dims]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos, x[..., rotary_dims:]],
        axis=-1)


def block(d: Dims, lw: Dict[str, jax.Array], x: jax.Array,
          quant: Optional[str] = None) -> jax.Array:
    """One decoder layer over x (B, T, D) float32, causal from position 0."""
    B, T, _ = x.shape
    H, KV, dh = d.heads, d.kv_heads, d.head_dim
    W = {k: quantize(lw[k], _CONTRACT[k], quant) for k in _CONTRACT}
    pos = jnp.arange(T)
    h = act(rms_norm(x, lw["ln1"], d.norm_eps), quant)
    q = jnp.einsum("btd,dhk->bthk", h, W["wq"], precision=HI)
    k = jnp.einsum("btd,dhk->bthk", h, W["wk"], precision=HI)
    v = jnp.einsum("btd,dhk->bthk", h, W["wv"], precision=HI)
    q = rope(q, pos, d.rope_theta, d.rotary_dims)
    k = rope(k, pos, d.rope_theta, d.rotary_dims)
    q = q.reshape(B, T, KV, H // KV, dh)
    s = jnp.einsum("btkgd,bskd->bkgts", q, k, precision=HI) / math.sqrt(dh)
    causal = pos[None, :] <= pos[:, None]
    s = jnp.where(causal, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgts,bskd->btkgd", p, v, precision=HI)
    o = quantize(o.reshape(B, T, H, dh), (-2, -1), quant)
    x = x + jnp.einsum("bthk,hkd->btd", o, W["wo"], precision=HI)
    h = act(rms_norm(x, lw["ln2"], d.norm_eps), quant)
    g = jnp.einsum("btd,df->btf", h, W["wg"], precision=HI)
    u = jnp.einsum("btd,df->btf", h, W["wu"], precision=HI)
    return x + jnp.einsum("btf,fd->btd", act(jax.nn.silu(g) * u, quant),
                          W["wo_mlp"], precision=HI)


def _layer_weights(blocks, layer):
    return {k: v[layer] for k, v in blocks.items()}


def head_weight(params, quant: Optional[str] = None) -> jax.Array:
    return quantize(params["embed"], (1,), quant)


# ----------------------------------------------------------------- serving
@functools.partial(jax.jit, static_argnums=(0,))
def _embed(d: Dims, embed, tokens):
    return jnp.take(embed, tokens, axis=0).astype(F32)


@functools.partial(jax.jit, static_argnums=(0, 4))
def _layer_step(d: Dims, blocks, layer, x, quant):
    return block(d, _layer_weights(blocks, layer), x, quant)


@functools.partial(jax.jit, static_argnums=(0, 3))
def _final(d: Dims, final_norm, x, quant):
    del quant
    return rms_norm(x, final_norm, d.norm_eps)


def hidden(d: Dims, params, tokens: np.ndarray,
           quant: Optional[str] = None) -> jax.Array:
    """Final normed hidden states (B, T, D) float32 for token rows
    (B, T), one jitted call per layer."""
    blocks = params["blocks"]["0_attn"]
    x = _embed(d, params["embed"], jnp.asarray(tokens))
    for layer in range(d.layers):
        x = _layer_step(d, blocks, jnp.int32(layer), x, quant)
    return _final(d, params["final_norm"], x, quant)


@functools.partial(jax.jit, static_argnums=(3,))
def _gaps(x, embed, other, control):
    """At every position of one row x (T, D): how far the float32
    reference's logit of a token lies below its best logit (0 where it is
    the best).  The token is ``other`` (T,), or, with ``control``, the one
    that the reference computed at that lower precision puts first, from
    its hidden states ``other`` (T, D)."""
    ref = jnp.einsum("td,vd->tv", x, embed.astype(F32), precision=HI)
    if control is None:
        picked = other
    else:
        low = jnp.einsum("td,vd->tv", act(other, control),
                         quantize(embed, (1,), control), precision=HI)
        picked = jnp.argmax(low, axis=-1)
    got = jnp.take_along_axis(ref, picked[:, None], axis=-1)[:, 0]
    return jnp.max(ref, axis=-1) - got


def served_gaps(d: Dims, params, prompts: Sequence[Sequence[int]],
                served: Sequence[Sequence[int]],
                control: Optional[str] = None) -> List[np.ndarray]:
    """For each request, the gap of every served token (position P-1+i
    predicts served[i]) under the float32 reference.  With ``control``
    the gaps are of the token that the reference computed at that lower
    precision puts first, at the same positions of the same tokens."""
    rows = [list(p) + list(s[:-1]) for p, s in zip(prompts, served)]
    T = max(len(r) for r in rows)
    toks = np.zeros((len(rows), T), np.int32)
    for i, r in enumerate(rows):
        toks[i, :len(r)] = r
    ref = hidden(d, params, toks)
    low = hidden(d, params, toks, control) if control else None
    out = []
    for i, (p, s) in enumerate(zip(prompts, served)):
        lo = len(p) - 1
        if low is None:
            picked = np.zeros(T, np.int32)
            picked[lo:lo + len(s)] = s
            gaps = _gaps(ref[i], params["embed"], jnp.asarray(picked), None)
        else:
            gaps = _gaps(ref[i], params["embed"], low[i], control)
        out.append(np.asarray(gaps)[lo:lo + len(s)])
    return out


# ---------------------------------------------------------------- training
def loss(d: Dims, params32, tokens, labels, quant: Optional[str] = None,
         chunk: int = 512):
    """Mean next-token negative log-likelihood of a (B, S) batch."""
    blocks = params32["blocks"]["0_attn"]
    x = jnp.take(params32["embed"], tokens, axis=0)

    @jax.checkpoint
    def layer(x, lw):
        return block(d, lw, x, quant), None

    x, _ = jax.lax.scan(layer, x, blocks)
    x = rms_norm(x, params32["final_norm"], d.norm_eps)
    w = head_weight(params32, quant)
    B, S, D = x.shape
    c = min(chunk, S)
    xs = x.reshape(B, S // c, c, D).swapaxes(0, 1)
    ls = labels.reshape(B, S // c, c).swapaxes(0, 1)

    @jax.checkpoint
    def nll(total, xl):
        xc, lc = xl
        logits = jnp.einsum("bcd,vd->bcv", act(xc, quant), w, precision=HI)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, lc[..., None], axis=-1)[..., 0]
        return total + jnp.sum(lse - gold), None

    total, _ = jax.lax.scan(nll, jnp.zeros((), F32), (xs, ls))
    return total / (B * S)


@functools.partial(jax.jit, static_argnums=(0, 4))
def loss_and_grad(d: Dims, params32, tokens, labels, quant=None):
    return jax.value_and_grad(lambda p: loss(d, p, tokens, labels, quant))(
        params32)


def learning_rate(opt: Dict, step: int) -> float:
    """The schedule the trainer states: linear warm-up, then cosine."""
    warm = max(int(opt["warmup_steps"]), 1)
    if step < warm:
        return opt["lr"] * step / warm
    t = min(max((step - warm) / max(opt["decay_steps"] - warm, 1), 0.0), 1.0)
    return opt["lr"] * 0.5 * (1.0 + math.cos(math.pi * t))


@functools.partial(jax.jit, static_argnums=(0,), donate_argnums=(1, 3, 4))
def _adamw_leaf(hp, p, g, m, v, lr, bc1, bc2, scale):
    b1, b2, eps, wd, dtype, quant = hp
    g = g * scale
    m = b1 * m + (1.0 - b1) * g
    v = b2 * v + (1.0 - b2) * g * g
    u = (m / bc1) / (jnp.sqrt(v / bc2) + eps) + wd * p
    return store(p - lr * u, dtype, quant), m, v


@jax.jit
def _diff_norm(a, a_scale, g, g_scale):
    return jnp.sqrt(jnp.sum(jnp.square(a * a_scale - g * g_scale)))


def _leaf_paths(tree, prefix=""):
    if not isinstance(tree, dict):
        return [prefix]
    out = []
    for k in sorted(tree):
        out += _leaf_paths(tree[k], f"{prefix}/{k}" if prefix else k)
    return out


def get_leaf(tree, path):
    for k in path.split("/"):
        tree = tree[k]
    return tree


def set_leaf(tree, path, value):
    *parents, last = path.split("/")
    for k in parents:
        tree = tree[k]
    tree[last] = value


def leaf_norms(tree) -> Dict[str, float]:
    return {p: float(jnp.sqrt(jnp.sum(jnp.square(
        get_leaf(tree, p).astype(F32))))) for p in _leaf_paths(tree)}


Grad = Tuple[Dict[str, np.ndarray], float]     # leaf arrays, and a scale


def train_steps(d: Dims, params, batches: Sequence[Dict[str, np.ndarray]],
                opt: Dict, quant: Optional[str] = None,
                against: Optional[Grad] = None, keep_grad: bool = False
                ) -> Dict:
    """AdamW steps from ``params`` over ``batches``, in float32 with the
    parameters stored in their configured type (or at ``quant``) after
    every update.

    Returns the loss of each step, the norm of each leaf's first
    gradient as the optimizer takes it (after clipping), and the norm of
    each leaf's change over all the steps.  With ``against``, another
    run's first gradient (host arrays by leaf path, times a scale), also
    the norm of each leaf's difference from this one's
    (``first_grad_err``); with ``keep_grad``, this run's first gradient
    in that form (``first_grad_vec``).  The moments live on the host
    between steps, so that one leaf's at a time shares the device with
    the parameters and the gradients."""
    p = jax.tree.map(lambda a: a.astype(F32), params)
    paths = _leaf_paths(p)
    start = {path: np.asarray(get_leaf(params, path)) for path in paths}
    del params
    moments: Dict[str, Tuple[np.ndarray, np.ndarray]] = {}
    hp = (float(opt["b1"]), float(opt["b2"]), float(opt["eps"]),
          float(opt["weight_decay"]), d.dtype, quant)
    losses, first_grad, errs, kept = [], {}, {}, None
    for t, batch in enumerate(batches, start=1):
        lval, grads = loss_and_grad(d, p, jnp.asarray(batch["tokens"]),
                                    jnp.asarray(batch["labels"]), quant)
        losses.append(float(lval))
        gnorm = float(jnp.sqrt(sum(jnp.sum(jnp.square(g))
                                   for g in jax.tree.leaves(grads))))
        clip = float(opt["grad_clip"])
        scale = min(1.0, clip / max(gnorm, 1e-12)) if clip else 1.0
        if t == 1:
            first_grad = {k: v * scale for k, v in leaf_norms(grads).items()}
            if against is not None:
                vecs, a_scale = against
                errs = {path: float(_diff_norm(
                    jnp.asarray(vecs[path]), a_scale, get_leaf(grads, path),
                    scale)) for path in paths}
            if keep_grad:
                kept = ({path: np.asarray(get_leaf(grads, path))
                         for path in paths}, scale)
        lr = learning_rate(opt, t)
        bc1, bc2 = 1.0 - hp[0] ** t, 1.0 - hp[1] ** t
        for path in paths:
            g = get_leaf(grads, path)
            if path in moments:
                m, v = (jnp.asarray(a) for a in moments[path])
            else:
                m, v = jnp.zeros_like(g), jnp.zeros_like(g)
            leaf_hp = hp if g.ndim >= 2 else hp[:3] + (0.0,) + hp[4:]
            new_p, m, v = _adamw_leaf(leaf_hp, get_leaf(p, path), g, m, v,
                                      lr, bc1, bc2, scale)
            set_leaf(p, path, new_p)
            if t < len(batches):
                moments[path] = (np.asarray(m), np.asarray(v))
            del m, v
        del grads
    change = {path: float(jnp.sqrt(jnp.sum(jnp.square(
        get_leaf(p, path) - jnp.asarray(start[path]).astype(F32)))))
        for path in paths}
    return {"losses": losses, "first_grad": first_grad, "change": change,
            "first_grad_err": errs, "first_grad_vec": kept}
