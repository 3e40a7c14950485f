"""Operations and bytes the work requires, from the shapes alone.

The dense counts follow ``repro.roofline.flops`` (two operations per
multiply-add, the attention products per layer), recounted for the
chips of one cell: what the algorithm needs, with no recomputation and
no masked waste (a causal token attends to the positions up to its own).
"""
from __future__ import annotations

from typing import Dict, Iterable

from bench.model import Dims

BF16 = 2
F32 = 4


def layer_matmul_params(d: Dims) -> int:
    """Weights of one layer that enter a matrix product."""
    D, H, KV, dh, F = d.d_model, d.heads, d.kv_heads, d.head_dim, d.d_ff
    return D * dh * (H + 2 * KV) + H * dh * D + 3 * D * F


def matmul_params(d: Dims) -> int:
    """Matrix-product weights of a token's pass: every layer and the head
    (the tied embedding, read as the head; the lookup is no product)."""
    return d.layers * layer_matmul_params(d) + d.vocab * d.d_model


def weight_bytes(d: Dims) -> int:
    """Bytes of every stored weight (the embedding once)."""
    D = d.d_model
    per_layer = layer_matmul_params(d) + 2 * D
    return BF16 * (d.layers * per_layer + d.vocab * D + D)


def kv_bytes_per_token(d: Dims) -> int:
    return 2 * d.layers * d.kv_heads * d.head_dim * BF16


def attention_flops(d: Dims, context: int) -> int:
    """Scores and weighted values of one token against ``context``
    positions, over all layers."""
    return 4 * d.layers * d.heads * d.head_dim * context


def train_flops_per_token(d: Dims, seq: int) -> float:
    """Forward and backward (three times the forward) per token of a
    causal sequence of ``seq``: the mean context is (seq + 1) / 2."""
    fwd = 2 * matmul_params(d) + attention_flops(d, 1) * (seq + 1) / 2
    return 3 * fwd


def prefill_flops(d: Dims, prompt: int) -> float:
    """A prompt through every layer, and the head for its last token."""
    body = 2 * (matmul_params(d) - d.vocab * d.d_model) * prompt
    attn = attention_flops(d, 1) * prompt * (prompt + 1) / 2
    return body + attn + 2 * d.vocab * d.d_model


def decode_step(d: Dims, contexts: Iterable[int]) -> Dict[str, float]:
    """One decode step of the active slots, each attending to its
    ``context`` positions: operations, and bytes of the weights plus the
    live KV rows it reads."""
    ctx = list(contexts)
    flops = sum(2 * matmul_params(d) + attention_flops(d, c) for c in ctx)
    byts = weight_bytes(d) + kv_bytes_per_token(d) * sum(ctx)
    return {"flops": float(flops), "bytes": float(byts)}


def least_seconds(flops: float, byts: float, peaks: Dict) -> float:
    """The roofline: the larger of operations over peak and bytes over
    bandwidth."""
    return max(flops / peaks["bf16_flops_per_s"],
               byts / peaks["hbm_bytes_per_s"])


def xent_call(rows: int, vocab: int, backward: bool) -> Dict[str, float]:
    """The fused softmax cross-entropy kernel on (rows, vocab) float32
    logits.  Forward: reads the logits once (max, exp, sum and the gold
    logit: about 5 operations an element).  Backward: reads them and
    writes the gradient (about 6 an element)."""
    n = rows * vocab
    if backward:
        return {"flops": 6.0 * n, "bytes": 2.0 * F32 * n}
    return {"flops": 5.0 * n, "bytes": 1.0 * F32 * n}


def adamw_elements(n: int, param_bytes: int = BF16,
                   grad_bytes: int = BF16) -> Dict[str, float]:
    """The fused AdamW update of ``n`` elements: reads the parameter,
    gradient and both float32 moments, writes the parameter and moments
    (about 14 operations an element)."""
    per = 2 * param_bytes + grad_bytes + 4 * F32
    return {"flops": 14.0 * n, "bytes": float(per * n)}
