"""Operations and bytes a decode step of the DeepSeek-V3 block requires,
from the shapes alone (two operations per multiply-add), for the cut
model of ``bench/model_mla_moe.Dims``.

A decode step needs, per token: the MLA projections and the absorbed
up-projections (``q_nope`` into the latent space, the latent context out
of it), the scores and values against its live latent rows, the dense
layer's MLP, per MoE layer the router, the shared expert and the held
experts it chose, and the head.  It reads every weight once except the
held experts that no token chose, the live latent rows (``c_kv`` and
``k_rope``) of every layer, and the embedding rows of its tokens.
"""
from __future__ import annotations

from typing import Dict, Iterable

from bench.model_mla_moe import Dims

BF16 = 2
F32 = 4


def mla_params(d: Dims) -> int:
    """Matrix weights of one MLA sub-block."""
    D, H = d.d_model, d.heads
    return (D * d.q_rank + d.q_rank * H * (d.nope + d.rope)
            + D * (d.kv_rank + d.rope) + d.kv_rank * H * (d.nope + d.v_dim)
            + H * d.v_dim * D)


def expert_params(d: Dims) -> int:
    return 3 * d.d_model * d.expert_ff


def fixed_params(d: Dims) -> int:
    """Matrix weights every decode step reads whatever the routing: each
    layer's MLA, the dense MLP, each MoE layer's router and shared
    experts, and the head."""
    D = d.d_model
    moe = D * d.experts + d.shared * expert_params(d)
    return (d.layers * mla_params(d) + d.dense_layers * 3 * D * d.d_ff
            + d.moe_layers * moe + d.vocab * D)


def latent_bytes_per_token(d: Dims) -> int:
    return d.layers * (d.kv_rank + d.rope) * BF16


def weight_bytes(d: Dims, hits: float) -> float:
    """Bytes of the weights a step reads when ``hits`` held experts per
    MoE layer received a token: the norms, the router biases (float32)
    and the matrices above, with those experts."""
    D = d.d_model
    norms = (d.layers * (2 * D + d.q_rank + d.kv_rank) + D) * BF16
    return (BF16 * (fixed_params(d) + d.moe_layers * hits * expert_params(d))
            + norms + d.moe_layers * d.experts * F32)


def token_flops(d: Dims, context: int) -> float:
    """One token's operations with ``context`` live positions; its held
    experts counted as the router's expected share of its choices."""
    H, R = d.heads, d.kv_rank
    # the up-projections' weights serve the absorbed products: q_nope
    # into the latent space and the latent context out of it
    attend = 2 * context * H * (R + d.rope) + 2 * context * H * R
    per_layer = 2 * mla_params(d) + attend
    routed = d.top_k * d.held / d.experts * 2 * expert_params(d)
    moe = 2 * d.d_model * d.experts + 2 * d.shared * expert_params(d) + routed
    return (d.layers * per_layer + d.dense_layers * 6 * d.d_model * d.d_ff
            + d.moe_layers * moe + 2 * d.vocab * d.d_model)


def decode_step(d: Dims, contexts: Iterable[int], hits: float
                ) -> Dict[str, float]:
    """One decode step of the active slots, each attending to its
    ``context`` positions, with ``hits`` held experts per MoE layer
    reached: operations, and bytes of the weights read, the live latent
    rows and the tokens' embedding rows."""
    ctx = list(contexts)
    flops = sum(token_flops(d, c) for c in ctx)
    byts = (weight_bytes(d, hits) + latent_bytes_per_token(d) * sum(ctx)
            + len(ctx) * d.d_model * BF16)
    return {"flops": float(flops), "bytes": float(byts)}
