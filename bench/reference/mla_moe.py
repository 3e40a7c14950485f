"""Plain reference of the DeepSeek-V3 block as Kimi K2 configures it, in
float32, for one chip's share of a 48-way expert-parallel layer.

Per layer, from the published modelling code's description:
``h = x + o_proj(attn(norm(x)))``, then ``h + mlp(norm(h))``.

- Attention (MLA, expanded heads): ``q = q_b(norm(q_a(x)))`` split into
  128 un-rotated and 64 rotated dims per head; ``kv_a(x)`` split into the
  512-wide latent ``c`` (normed) and one 64-wide rotated key shared by
  every head; keys ``[W_uk c, rope(k)]``, values ``W_uv c``; causal
  softmax in float32 at scale ``mscale(32, 1)² / sqrt(192)``; yarn
  frequencies (factor 32 over 4,096 original positions, beta 1 and 1,
  theta 50,000).
- The first layer's MLP is dense SwiGLU (18,432); the others' is the
  routed layer: a float32 router product, sigmoid scores, the top 8 by
  score plus the correction bias (or the program's 8, where they differ
  from these only at a near-tie: ``choose``), weights the unbiased
  scores normalised and times 2.827; the held experts' part, every held
  expert over every token times that token's weight for it (zero where
  it chose another expert); plus the shared expert (2,048).
- A final norm and the untied head.

Every matrix product runs at ``Precision.HIGHEST`` under
``default_matmul_precision("highest")``; there is no cache, kernel or
batching of requests: each checked request's whole sequence goes through
every layer, one jitted call per layer, queries in blocks of
``Q_BLOCK`` and the head in blocks of ``V_BLOCK`` rows so that it fits
beside the stored weights on one chip.

Departures from the published model, all shared with the program that is
checked: the norms multiply by ``1 + scale`` (the parameters are the
offset from one); the rotary dims are rotated half against half, not
interleaved (a relabelling of those columns of random weights); only
this chip's 8 of 384 experts add their part, as the deployment's other
47 chips would add theirs; the weights are drawn at random in bf16.

``quant="fp8"`` is the control of ``dense_lm``: both operands of each
product with a weight rounded to e4m3 (one scale per output channel and
per token).

Nothing here imports the program.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.model_mla_moe import Dims
from bench.reference.dense_lm import _embed, _final, act, quantize, rms_norm

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32
Q_BLOCK = 256
V_BLOCK = 16384
NEG = -1e30

# contraction axes of each matrix weight, as stored per layer
_CONTRACT = {"wq_a": (0,), "wq_b": (0,), "wkv_a": (0,), "w_uk": (0,),
             "w_uv": (0,), "wo": (0, 1), "wg": (0,), "wu": (0,),
             "wo_mlp": (0,), "router": (0,), "moe_wg": (1,), "moe_wu": (1,),
             "moe_wo": (1,), "sh_wg": (0,), "sh_wu": (0,), "sh_wo": (0,)}


def yarn_mscale(scale: float, mscale: float) -> float:
    if scale <= 1:
        return 1.0
    return 0.1 * mscale * math.log(scale) + 1.0


def inv_freq(d: Dims) -> np.ndarray:
    """DeepSeek-V3's yarn inverse frequencies for the rotary dims."""
    factor, beta_fast, beta_slow, _, _, orig = d.yarn
    dim, base = d.rope, d.rope_theta

    def correction_dim(rot):
        return (dim * math.log(orig / (rot * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    mask = 1.0 - ramp
    pos_freqs = base ** (np.arange(0, dim, 2) / dim)
    extra, inter = 1.0 / pos_freqs, 1.0 / (factor * pos_freqs)
    return inter * (1 - mask) + extra * mask


def softmax_scale(d: Dims) -> float:
    factor, _, _, _, mscale_all_dim, _ = d.yarn
    return (d.nope + d.rope) ** -0.5 * yarn_mscale(factor,
                                                   mscale_all_dim) ** 2


def rope(x, positions, d: Dims):
    """x (..., T, N, rope) float32 rotated half against half."""
    factor, _, _, mscale, mscale_all_dim, _ = d.yarn
    m = yarn_mscale(factor, mscale) / yarn_mscale(factor, mscale_all_dim)
    ang = positions[:, None].astype(F32) * jnp.asarray(inv_freq(d), F32)
    cos, sin = jnp.cos(ang)[:, None, :] * m, jnp.sin(ang)[:, None, :] * m
    half = d.rope // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(d: Dims, W, x, quant):
    """MLA over x (B, T, D), expanded heads, causal from position 0;
    T is a multiple of ``Q_BLOCK``."""
    B, T, _ = x.shape
    pos = jnp.arange(T)
    cq = act(rms_norm(jnp.einsum("btd,dr->btr", x, W["wq_a"], precision=HI),
                      W["q_norm"], d.norm_eps), quant)
    q = jnp.einsum("btr,rhk->bthk", cq, W["wq_b"], precision=HI)
    kv = jnp.einsum("btd,dc->btc", x, W["wkv_a"], precision=HI)
    c = act(rms_norm(kv[..., :d.kv_rank], W["kv_norm"], d.norm_eps), quant)
    k_rope = rope(kv[..., None, d.kv_rank:], pos, d)
    k_nope = jnp.einsum("btr,rhn->bthn", c, W["w_uk"], precision=HI)
    v = jnp.einsum("btr,rhv->bthv", c, W["w_uv"], precision=HI)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope, k_nope.shape[:3] + (d.rope,))], -1)
    q = jnp.concatenate([q[..., :d.nope], rope(q[..., d.nope:], pos, d)], -1)
    scale = softmax_scale(d)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * Q_BLOCK, Q_BLOCK, axis=1)
        s = jnp.einsum("bqhk,bshk->bhqs", qb, k, precision=HI) * scale
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.where(pos[None, :] <= qpos[:, None], s, NEG)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhqs,bshv->bqhv", p, v, precision=HI)

    o = jax.lax.map(block, jnp.arange(T // Q_BLOCK))      # (nq, B, qb, H, v)
    o = jnp.moveaxis(o, 0, 1).reshape(B, T, d.heads, d.v_dim)
    o = quantize(o, (-2, -1), quant)
    return jnp.einsum("bthv,hvd->btd", o, W["wo"], precision=HI)


def swiglu(x, wg, wu, wo, quant):
    g = jnp.einsum("btd,df->btf", x, wg, precision=HI)
    u = jnp.einsum("btd,df->btf", x, wu, precision=HI)
    return jnp.einsum("btf,fd->btd", act(jax.nn.silu(g) * u, quant), wo,
                      precision=HI)


def route(d: Dims, logits, bias):
    """(ids, weights) of the top experts: chosen by sigmoid score plus
    the bias; weighted by the unbiased scores, normalised, scaled."""
    scores = jax.nn.sigmoid(logits)
    _, ids = jax.lax.top_k(scores + bias, d.top_k)
    w = jnp.take_along_axis(scores, ids, axis=-1)
    return ids, w / (jnp.sum(w, -1, keepdims=True) + 1e-20) * d.scaling


def choose(d: Dims, logits, bias, program, margin: float):
    """The reference's choice of experts where the program's (``program``,
    (..., top_k) ids, -1 where there is none) departs from it only at a
    near-tie, else its own; and how the two stand.

    The departure of the program's choice is the largest distance, in
    score plus bias, of an expert in one choice and not the other from
    the other side of the reference's boundary: a program-chosen expert's
    below the reference's last chosen, a reference-chosen one's above its
    first left out.  A swap of the last chosen and the first left out
    departs by their difference.  Rounding of the hidden state moves
    scores by far less than ``margin``; where the departure is under it
    the reference takes the program's choice (a flip).

    Returns (ids, weights, flip, miss, departure): ``miss`` where the
    choices differ by ``margin`` or more (the reference keeps its own)."""
    scores = jax.nn.sigmoid(logits)
    biased = scores + bias
    k = d.top_k
    vals, top = jax.lax.top_k(biased, k + 1)
    own, last, first_out = top[..., :k], vals[..., k - 1:k], vals[..., k:]
    if program is None:
        program = jnp.full(own.shape, -1, own.dtype)
    given = program[..., :1] >= 0
    prog = jnp.where(given, program, own)
    prog_b = jnp.take_along_axis(biased, prog, axis=-1)
    kept = jnp.any(own[..., :, None] == prog[..., None, :], axis=-1)
    departure = jnp.maximum(
        jnp.max(jnp.maximum(last - prog_b, 0.0), axis=-1),
        jnp.max(jnp.where(kept, 0.0, vals[..., :k] - first_out), axis=-1))
    differ = ~jnp.all(kept, axis=-1)
    flip = differ & (departure < margin)
    ids = jnp.where(flip[..., None], prog, own)
    w = jnp.take_along_axis(scores, ids, axis=-1)
    w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20) * d.scaling
    return ids, w, flip, differ & ~flip, departure


def experts(d: Dims, W, x, bias, quant, program=None, margin=0.0):
    """This chip's part of the routed layer plus the shared expert, and
    ``choose``'s flip, miss and departure per token."""
    logits = jnp.einsum("btd,de->bte", x, W["router"], precision=HI)
    ids, w, flip, miss, departure = choose(d, logits, bias, program, margin)
    held = d.first_held + jnp.arange(d.held)
    gate = jnp.sum(jnp.where(ids[..., None] == held, w[..., None], 0.0),
                   axis=-2)                                   # (B, T, n)
    g = jnp.einsum("btd,edf->btef", x, W["moe_wg"], precision=HI)
    u = jnp.einsum("btd,edf->btef", x, W["moe_wu"], precision=HI)
    y = jnp.einsum("btef,efd->bted", act(jax.nn.silu(g) * u, quant),
                   W["moe_wo"], precision=HI)
    routed = jnp.einsum("bted,bte->btd", y, gate, precision=HI)
    return (routed + swiglu(x, W["sh_wg"], W["sh_wu"], W["sh_wo"], quant),
            (flip, miss, departure))


@functools.partial(jax.jit, static_argnums=(0, 3, 4, 6))
def _layer(d: Dims, lw, x, dense: bool, quant, program, margin):
    W = {k: quantize(v, _CONTRACT[k], quant) if k in _CONTRACT
         else v.astype(F32) for k, v in lw.items()}
    with jax.default_matmul_precision("highest"):
        h = act(rms_norm(x, lw["ln1"], d.norm_eps), quant)
        x = x + attention(d, W, h, quant)
        h = act(rms_norm(x, lw["ln2"], d.norm_eps), quant)
        if dense:
            return x + swiglu(h, W["wg"], W["wu"], W["wo_mlp"], quant), None
        out, how = experts(d, W, h, W["router_bias"], quant, program,
                           margin)
        return x + out, how


def hidden(d: Dims, params, tokens: np.ndarray,
           quant: Optional[str] = None, program=None, margin: float = 0.0
           ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Final normed hidden states (B, T, D) float32 of token rows (B, T),
    T a multiple of ``Q_BLOCK``, one jitted call per layer; with
    ``program``, the program's choice of experts (B, T, MoE layers,
    top_k), -1 where there is none, taken at near-ties under ``margin``
    (``choose``).  Also per position, over the MoE layers: the flips and
    misses counted, and the largest departure."""
    x = _embed(d, params["embed"], jnp.asarray(tokens))
    how = {"flips": jnp.zeros(x.shape[:2], jnp.int32),
           "misses": jnp.zeros(x.shape[:2], jnp.int32),
           "departure": jnp.zeros(x.shape[:2], F32)}
    moe = 0
    for group, dense in ((params["lead"]["0_mla_dense"], True),
                         (params["blocks"]["0_mla_moe"], False)):
        n = jax.tree.leaves(group)[0].shape[0]
        for i in range(n):
            prog = None
            if not dense and program is not None:
                prog = jnp.asarray(program[:, :, moe])
            x, got = _layer(d, {k: v[i] for k, v in group.items()}, x, dense,
                            quant, prog, margin)
            if got is not None:
                moe += 1
                flip, miss, departure = got
                how = {"flips": how["flips"] + flip,
                       "misses": how["misses"] + miss,
                       "departure": jnp.maximum(how["departure"], departure)}
    return _final(d, params["final_norm"], x, None), how


@functools.partial(jax.jit, static_argnums=(4,))
def _head_block(x, low, head, picked, control):
    """One block of the head's rows for checked positions x (N, D): the
    block's best reference logit, the logit of ``picked`` where it falls
    in the block (else -inf), and with ``control`` the block's best
    logit and its row under the control (from its hidden states ``low``)."""
    with jax.default_matmul_precision("highest"):
        ref = jnp.einsum("nd,vd->nv", x, head.astype(F32), precision=HI)
        V = head.shape[0]
        inside = (picked >= 0) & (picked < V)
        got = jnp.where(inside, jnp.take_along_axis(
            ref, jnp.clip(picked, 0, V - 1)[:, None], axis=-1)[:, 0], NEG)
        if control is None:
            return jnp.max(ref, -1), got, None, None
        lo = jnp.einsum("nd,vd->nv", act(low, control),
                        quantize(head, (1,), control), precision=HI)
        return jnp.max(ref, -1), got, jnp.max(lo, -1), jnp.argmax(lo, -1)


def _gaps(x, low, head, picked, control):
    """Per checked position: the reference's best logit less its logit of
    the served token (``picked``), or of the token the control puts first."""
    starts = range(0, head.shape[0], V_BLOCK)

    def blocks(picked, control):
        return [_head_block(x, low, head[v0:v0 + V_BLOCK], picked - v0,
                            control) for v0 in starts]

    got = blocks(picked, control)
    best = functools.reduce(jnp.maximum, [g[0] for g in got])
    if control is not None:
        lo_best = jnp.stack([g[2] for g in got])            # (blocks, N)
        lo_tok = jnp.stack([g[3] + v0 for g, v0 in zip(got, starts)])
        picked = jnp.take_along_axis(
            lo_tok, jnp.argmax(lo_best, 0)[None], axis=0)[0]
        got = blocks(picked, None)
    return np.asarray(best - functools.reduce(jnp.maximum,
                                              [g[1] for g in got]))


def served_gaps(d: Dims, params, prompts: Sequence[Sequence[int]],
                served: Sequence[Sequence[int]],
                routes: Optional[Sequence[np.ndarray]] = None,
                margin: float = 0.0, control: Optional[str] = None
                ) -> Tuple[List[np.ndarray], List[Dict[str, float]]]:
    """For each request, the gap of every served token (position P-1+i
    predicts served[i]) under the float32 reference, and how the
    program's choice of experts stood to the reference's over the
    request's positions (``choose``): flips, misses, (position, MoE
    layer) pairs, each position's largest departure over the layers and
    the largest of all.  ``routes`` holds each
    request's program choice per position of its prompt and served
    tokens but the last, (P + len(served) - 1, MoE layers, top_k).  With
    ``control``, the gaps of the tokens the reference at that precision,
    routing by its own choice, puts first."""
    rows = [list(p) + list(s[:-1]) for p, s in zip(prompts, served)]
    T = -(-max(len(r) for r in rows) // Q_BLOCK) * Q_BLOCK
    toks = np.ones((len(rows), T), np.int32)
    program = None
    if routes is not None:
        program = np.full((len(rows), T, d.moe_layers, d.top_k), -1,
                          np.int32)
    for i, r in enumerate(rows):
        toks[i, :len(r)] = r
        if program is not None:
            program[i, :len(r)] = routes[i]
    ref, how = hidden(d, params, toks, program=program, margin=margin)
    low = hidden(d, params, toks, control)[0] if control else None
    head = params["lm_head"]
    how = {k: np.asarray(v) for k, v in how.items()}
    gaps, stood = [], []
    for i, (p, s) in enumerate(zip(prompts, served)):
        at = slice(len(p) - 1, len(p) - 1 + len(s))
        picked = jnp.asarray(np.asarray(s, np.int32))
        gaps.append(_gaps(ref[i, at], None if low is None else low[i, at],
                          head, picked, control))
        n = len(rows[i])
        stood.append({"flips": int(how["flips"][i, :n].sum()),
                      "misses": int(how["misses"][i, :n].sum()),
                      "pairs": n * d.moe_layers,
                      "departures": how["departure"][i, :n],
                      "departure": float(how["departure"][i, :n].max())})
    return gaps, stood
