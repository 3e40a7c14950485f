"""Top-k MoE with expert parallelism (EP): sort-based dispatch + all_to_all.

The routed-MLP pipeline per model-axis rank (megablocks/MaxText-style):

  router + top-k (outside shard_map, GSPMD-parallel)
  -> shard_map over the full mesh:
       sort assignments by destination rank -> capacity-bounded send buffer
       all_to_all (model axis)  [tokens -> their experts' ranks]
       sort received slots by local expert -> (E_local, cap_e, D) buckets
       grouped matmul (repro.kernels.moe_gmm is the Pallas hot-spot;
       this XLA einsum path is what the dry-run lowers)
       inverse all_to_all -> weighted combine at the source rank

Capacity-dropped tokens fall back to identity (standard Switch behavior);
the load-balance aux loss (Shazeer et al.) discourages the imbalance that
causes drops.

The held-expert layer at the end (``held_expert_mlp``, kind ``mla_moe``)
is the dropless one: DeepSeek-V3's sigmoid router over all experts, one
chip's held experts computed exactly, and a shared expert.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models.layers import ModelCtx, rms_norm
from repro.models.params import PSpec


def moe_schema(cfg: ModelConfig, G: int) -> Dict[str, PSpec]:
    D, F, E = cfg.d_model, cfg.d_ff, cfg.moe.num_experts
    return {
        "router": PSpec((G, D, E), ("layers", "fsdp", None), scale=0.02),
        "moe_wg": PSpec((G, E, D, F), ("layers", "expert", "fsdp", None)),
        "moe_wu": PSpec((G, E, D, F), ("layers", "expert", "fsdp", None)),
        "moe_wo": PSpec((G, E, F, D), ("layers", "expert", None, "fsdp")),
    }


def _local_mesh_size(ctx: ModelCtx, axis: str) -> int:
    return ctx.mesh.shape.get(axis, 1) if ctx.mesh is not None else 1


def _dispatch_compute_combine(x2d, top_idx, top_w, wg, wu, wo, *, E: int,
                              tp: int, cf: float, compute_dtype):
    """Per-rank routed MLP.  Runs inside shard_map (axis 'model' manual).

    x2d (T, D) local tokens; top_idx/top_w (T, K); wg/wu (E_local, D, F),
    wo (E_local, F, D).  Returns (T, D).
    """
    T, D = x2d.shape
    K = top_idx.shape[-1]
    E_local = E // tp
    TK = T * K

    flat_e = top_idx.reshape(TK)                       # global expert id
    flat_w = top_w.reshape(TK)
    flat_tok = jnp.repeat(jnp.arange(T), K)

    # ---- sort by destination rank, scatter into (tp, cap, D) send buffer
    dst = flat_e // E_local
    order = jnp.argsort(dst)                           # stable
    cap = int(-(-TK // tp) * cf)
    sorted_dst = dst[order]
    counts = jnp.bincount(dst, length=tp)
    seg_start = jnp.cumsum(counts) - counts
    pos = jnp.arange(TK) - seg_start[sorted_dst]       # position within segment
    keep = pos < cap
    slot_r = jnp.where(keep, sorted_dst, tp - 1)
    slot_c = jnp.where(keep, pos, cap - 1)

    src_tok = flat_tok[order]
    src_w = flat_w[order]
    local_e = (flat_e % E_local)[order]

    send = jnp.zeros((tp, cap, D), compute_dtype)
    send = send.at[slot_r, slot_c].set(
        jnp.where(keep[:, None], x2d[src_tok], 0), mode="drop")
    send_e = jnp.full((tp, cap), E_local, jnp.int32)   # E_local = invalid
    send_e = send_e.at[slot_r, slot_c].set(
        jnp.where(keep, local_e, E_local), mode="drop")

    # ---- exchange: rows -> their experts' ranks
    if tp > 1:
        recv = jax.lax.all_to_all(send, "model", 0, 0, tiled=False)
        recv_e = jax.lax.all_to_all(send_e, "model", 0, 0, tiled=False)
    else:
        recv, recv_e = send, send_e
    recv = recv.reshape(tp * cap, D)
    recv_e = recv_e.reshape(tp * cap)

    # ---- bucket received slots by local expert
    cap_e = int(-(-tp * cap // E_local) * cf)
    order2 = jnp.argsort(recv_e)
    sorted_e = recv_e[order2]
    counts_e = jnp.bincount(recv_e, length=E_local + 1)[:E_local]
    seg2 = jnp.cumsum(counts_e) - counts_e
    pos2 = jnp.arange(tp * cap) - jnp.concatenate(
        [seg2, jnp.zeros((1,), seg2.dtype)])[jnp.minimum(sorted_e, E_local)]
    keep2 = (pos2 < cap_e) & (sorted_e < E_local)
    be = jnp.where(keep2, sorted_e, 0)
    bc = jnp.where(keep2, pos2, cap_e - 1)

    bucket = jnp.zeros((E_local, cap_e, D), compute_dtype)
    bucket = bucket.at[be, bc].set(
        jnp.where(keep2[:, None], recv[order2], 0), mode="drop")

    # ---- grouped expert MLP (XLA batched matmul == kernels/moe_gmm oracle)
    gate = jnp.einsum("ecd,edf->ecf", bucket, wg.astype(compute_dtype))
    up = jnp.einsum("ecd,edf->ecf", bucket, wu.astype(compute_dtype))
    h = jax.nn.silu(gate.astype(jnp.float32)).astype(compute_dtype) * up
    y = jnp.einsum("ecf,efd->ecd", h, wo.astype(compute_dtype))

    # ---- un-bucket -> slots -> inverse exchange -> weighted combine
    slots_y = jnp.zeros((tp * cap, D), compute_dtype)
    slots_y = slots_y.at[order2].set(
        jnp.where(keep2[:, None], y[be, bc], 0))
    if tp > 1:
        back = jax.lax.all_to_all(slots_y.reshape(tp, cap, D), "model", 0, 0,
                                  tiled=False)
    else:
        back = slots_y.reshape(tp, cap, D)
    out = jnp.zeros((T, D), jnp.float32)
    gathered = back[slot_r, slot_c]                    # (TK, D) in sorted order
    out = out.at[src_tok].add(
        jnp.where(keep[:, None], gathered.astype(jnp.float32)
                  * src_w[:, None].astype(jnp.float32), 0))
    return out.astype(compute_dtype)


def moe_mlp(ctx: ModelCtx, p, x: jax.Array):
    """x (B,S,D) -> (B,S,D), plus the load-balance aux loss (f32 scalar)."""
    cfg = ctx.cfg
    mcfg = cfg.moe
    E, K = mcfg.num_experts, mcfg.top_k
    cd = ctx.compute_dtype
    B, S, D = x.shape

    logits = jnp.einsum("bsd,de->bse", x, p["router"].astype(cd))
    logits = logits.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_idx = jax.lax.top_k(probs, K)
    top_w = top_w / jnp.maximum(jnp.sum(top_w, -1, keepdims=True), 1e-9)

    # load-balance aux loss: E * sum_e f_e * p_e
    ass = jax.nn.one_hot(top_idx, E, dtype=jnp.float32).sum(2)   # (B,S,E)
    f = jnp.mean(ass, axis=(0, 1))
    pbar = jnp.mean(probs, axis=(0, 1))
    aux = mcfg.router_aux_weight * E * jnp.sum(f * pbar)

    tp = _local_mesh_size(ctx, "model")
    if ctx.mesh is None or tp == 1:
        # single-rank path (smoke tests / reference): dense gather per expert
        out = _dispatch_compute_combine(
            x.reshape(B * S, D), top_idx.reshape(B * S, K),
            top_w.reshape(B * S, K), p["moe_wg"], p["moe_wu"], p["moe_wo"],
            E=E, tp=1, cf=mcfg.capacity_factor, compute_dtype=cd)
        return out.reshape(B, S, D), aux

    mesh = ctx.mesh
    dp_axes = tuple(a for a in ("pod", "data") if a in mesh.shape)

    if S % tp == 0:
        # main path: tokens seq-sharded across the model axis, sort-based
        # dispatch + all_to_all (training / prefill volumes)
        xspec = P(dp_axes, "model", None)

        def ranked(x_, ti, tw, wg, wu, wo):
            b, s, _ = x_.shape
            out = _dispatch_compute_combine(
                x_.reshape(b * s, D), ti.reshape(b * s, K),
                tw.reshape(b * s, K), wg, wu, wo, E=E, tp=tp,
                cf=mcfg.capacity_factor, compute_dtype=cd)
            return out.reshape(b, s, D)

        out = jax.shard_map(
            ranked, mesh=mesh,
            in_specs=(xspec, P(dp_axes, "model", None),
                      P(dp_axes, "model", None),
                      P("model", None, None), P("model", None, None),
                      P("model", None, None)),
            out_specs=xspec, check_vma=False,
        )(x, top_idx, top_w, p["moe_wg"], p["moe_wu"], p["moe_wo"])
        return out, aux

    # decode path (S == 1): token count per chip is tiny, so each model
    # rank runs ALL its local tokens through ALL its local experts densely,
    # weight-masks non-selected experts, and psums across ranks — exact
    # (no capacity drops), no sort/a2a, negligible overcompute at S=1.
    xspec = P(dp_axes, None, None)

    def local_experts(x_, ti, tw, wg, wu, wo):
        b, s, _ = x_.shape
        T = b * s
        x2 = x_.reshape(T, D)
        e_local = E // tp
        rank = jax.lax.axis_index("model")
        base = rank * e_local
        gate = jnp.einsum("td,edf->tef", x2, wg.astype(cd))
        up = jnp.einsum("td,edf->tef", x2, wu.astype(cd))
        h = jax.nn.silu(gate.astype(jnp.float32)).astype(cd) * up
        y = jnp.einsum("tef,efd->ted", h, wo.astype(cd))
        eids = base + jnp.arange(e_local)                   # (e,)
        w_te = jnp.sum(jnp.where(ti.reshape(T, K, 1) == eids[None, None],
                                 tw.reshape(T, K, 1), 0.0), axis=1)  # (T,e)
        out = jnp.einsum("ted,te->td", y.astype(jnp.float32), w_te)
        out = jax.lax.psum(out, "model")
        return out.reshape(b, s, D).astype(cd)

    out = jax.shard_map(
        local_experts, mesh=mesh,
        in_specs=(xspec, P(dp_axes, None, None), P(dp_axes, None, None),
                  P("model", None, None), P("model", None, None),
                  P("model", None, None)),
        out_specs=xspec, check_vma=False,
    )(x, top_idx, top_w, p["moe_wg"], p["moe_wu"], p["moe_wo"])
    return out, aux


def moe_block_schema(cfg: ModelConfig, G: int) -> Dict[str, PSpec]:
    from repro.models.transformer import _attn_mlp_schema
    s = _attn_mlp_schema(cfg, G)
    del s["wg"], s["wu"], s["wo_mlp"]  # replaced by routed experts
    s.update(moe_schema(cfg, G))
    return s


def apply_moe_block(ctx, p, x, *, mode, positions, cache, pos, shared, extras):
    from repro.models.transformer import attention_part
    cfg = ctx.cfg
    x, new_cache = attention_part(ctx, p, x, window=None, mode=mode,
                                  positions=positions, cache=cache, pos=pos)
    h = rms_norm(x, p["ln2"], cfg.norm_eps)
    if ctx.par.sequence_parallel and mode == "train":
        h = ctx.cons(h, ("batch", "act_seq_sharded", None))
    out, aux = moe_mlp(ctx, p, h)
    if cfg.post_norm:
        out = rms_norm(out, p["ln2_post"], cfg.norm_eps)
    return x + out, new_cache, aux


# ---------------------------------------------------------------------------
# the held-expert layer (kind "mla_moe": DeepSeek-V3 / Kimi K2 routing)
# ---------------------------------------------------------------------------
# One chip's share of an expert-parallel layer: the router keeps its full
# width (all E experts) and its experts per token, and the chip computes
# the part of the result that the experts it holds give, exactly: each of
# its tokens through each held expert, weighted by that token's gate for
# it (zero where the token chose another).  Nothing is dropped.  The other
# chips' parts are theirs: on one chip there is no exchange.

def held_expert_schema(cfg: ModelConfig, G: int) -> Dict[str, PSpec]:
    m, D = cfg.moe, cfg.d_model
    F = m.expert_ff or cfg.d_ff
    Fs = F * m.num_shared_experts
    _, n = m.held_range
    s = {
        "router": PSpec((G, D, m.num_experts), ("layers", "fsdp", None),
                        scale=D ** -0.5),
        # the correction bias (noaux_tc): used for the choice only
        "router_bias": PSpec((G, m.num_experts), ("layers", None), "zeros",
                             dtype="float32"),
        "moe_wg": PSpec((G, n, D, F), ("layers", "expert", "fsdp", None)),
        "moe_wu": PSpec((G, n, D, F), ("layers", "expert", "fsdp", None)),
        "moe_wo": PSpec((G, n, F, D), ("layers", "expert", None, "fsdp")),
    }
    if Fs:
        s["sh_wg"] = PSpec((G, D, Fs), ("layers", "fsdp", "tp_ff"))
        s["sh_wu"] = PSpec((G, D, Fs), ("layers", "fsdp", "tp_ff"))
        s["sh_wo"] = PSpec((G, Fs, D), ("layers", "tp_ff", "fsdp"))
    return s


def sigmoid_route(logits: jax.Array, bias: jax.Array, top_k: int,
                  scaling: float):
    """DeepSeek-V3's noaux_tc gate on float32 ``logits`` (..., E): the
    ``top_k`` experts by sigmoid score plus ``bias``; their weights are
    the unbiased scores, normalised to sum to one, times ``scaling``.
    Returns (ids, weights), each (..., top_k)."""
    scores = jax.nn.sigmoid(logits)
    _, ids = jax.lax.top_k(scores + bias, top_k)
    w = jnp.take_along_axis(scores, ids, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return ids, w * scaling


def held_expert_mlp(ctx: ModelCtx, p, x: jax.Array):
    """x (B, T, D) -> (this chip's routed part plus the shared expert,
    (B, T, D); the number of held experts that some token chose; the
    router's choice, (B, T, top_k) expert ids)."""
    cfg = ctx.cfg
    m = cfg.moe
    cd = ctx.compute_dtype
    first, n = m.held_range
    with jax.named_scope("router"):
        logits = jnp.einsum("btd,de->bte", x.astype(jnp.float32),
                            p["router"].astype(jnp.float32),
                            precision=jax.lax.Precision.HIGHEST)
        ids, w = sigmoid_route(logits, p["router_bias"].astype(jnp.float32),
                               m.top_k, m.routed_scaling)
        chose = ids[..., None] == first + jnp.arange(n)       # (B,T,k,n)
        gate = jnp.sum(jnp.where(chose, w[..., None], 0.0), axis=-2)
        hits = jnp.sum(jnp.any(chose, axis=(0, 1, 2))).astype(jnp.float32)
    with jax.named_scope("experts"):
        g = jnp.einsum("btd,edf->btef", x, p["moe_wg"].astype(cd))
        u = jnp.einsum("btd,edf->btef", x, p["moe_wu"].astype(cd))
        h = jax.nn.silu(g.astype(jnp.float32)).astype(cd) * u
        y = jnp.einsum("btef,efd->bted", h, p["moe_wo"].astype(cd))
        out = jnp.einsum("bted,bte->btd", y.astype(jnp.float32), gate)
    if "sh_wg" in p:
        with jax.named_scope("shared_expert"):
            g = jnp.einsum("btd,df->btf", x, p["sh_wg"].astype(cd))
            u = jnp.einsum("btd,df->btf", x, p["sh_wu"].astype(cd))
            h = jax.nn.silu(g.astype(jnp.float32)).astype(cd) * u
            out = out + jnp.einsum("btf,fd->btd", h,
                                   p["sh_wo"].astype(cd)).astype(jnp.float32)
    return out.astype(cd), hits, ids
