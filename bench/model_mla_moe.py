"""A DeepSeek-V3-block configuration file (Kimi K2), read two ways: as
the program's ``ModelConfig`` and as plain sizes for the weights the
benchmark makes and the reference it computes; and the weights
themselves, made on the device from a seed.

The file keeps the published ``config.json`` keys.  ``n_routed_experts``
is the experts this chip holds, ``published.n_routed_experts`` the
router's width, and ``first_held_expert`` where the held ones start.  A
key whose value the program cannot run is refused.

The weights are laid out as the program's parameter tree
(``lead/0_mla_dense/<leaf>``, ``blocks/0_mla_moe/<leaf>`` with the MoE
layers stacked, ``embed``, ``lm_head``, ``final_norm``), one key per leaf
split from the seed in the sorted order of the leaf paths; norm scales
zero, every other leaf a float32 normal draw times its standard
deviation, cast to its stored type.  The reference reads these arrays
and nothing the program made.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from bench.weights import _nest

BIAS_STD = 0.01       # the correction bias's draw (the file's "assumed")

# published values the program has exactly one way to run
_FIXED = {"hidden_act": "silu", "attention_bias": False, "moe_layer_freq": 1,
          "n_group": 1, "topk_group": 1, "scoring_func": "sigmoid",
          "topk_method": "noaux_tc", "norm_topk_prob": True,
          "num_nextn_predict_layers": 0, "tie_word_embeddings": False}


@dataclasses.dataclass(frozen=True)
class Dims:
    """The sizes of the cut model."""
    dense_layers: int          # leading dense layers
    moe_layers: int
    d_model: int
    heads: int
    q_rank: int
    kv_rank: int
    nope: int
    rope: int
    v_dim: int
    d_ff: int                  # the dense layer's width
    expert_ff: int
    experts: int               # the router's width
    first_held: int
    held: int
    top_k: int
    shared: int
    scaling: float
    vocab: int
    rope_theta: float
    yarn: Tuple[float, float, float, float, float, int]
    norm_eps: float
    dtype: str

    @property
    def layers(self) -> int:
        return self.dense_layers + self.moe_layers

    @classmethod
    def of(cls, c: Dict[str, Any]) -> "Dims":
        for key, want in _FIXED.items():
            if c.get(key) != want:
                raise ValueError(f"{key} must be {want!r}, not {c.get(key)!r}")
        y = c["rope_scaling"]
        if y.get("type") != "yarn":
            raise ValueError("rope_scaling must be yarn")
        experts = int(c["published"]["n_routed_experts"])
        held = int(c["n_routed_experts"])
        if held * int(c["expert_parallel"]) != experts:
            raise ValueError("expert_parallel chips must hold every expert")
        dense = int(c["first_k_dense_replace"])
        return cls(
            dense_layers=dense,
            moe_layers=int(c["num_hidden_layers"]) - dense,
            d_model=int(c["hidden_size"]),
            heads=int(c["num_attention_heads"]),
            q_rank=int(c["q_lora_rank"]), kv_rank=int(c["kv_lora_rank"]),
            nope=int(c["qk_nope_head_dim"]), rope=int(c["qk_rope_head_dim"]),
            v_dim=int(c["v_head_dim"]), d_ff=int(c["intermediate_size"]),
            expert_ff=int(c["moe_intermediate_size"]), experts=experts,
            first_held=int(c["first_held_expert"]), held=held,
            top_k=int(c["num_experts_per_tok"]),
            shared=int(c["n_shared_experts"]),
            scaling=float(c["routed_scaling_factor"]),
            vocab=int(c["vocab_size"]), rope_theta=float(c["rope_theta"]),
            yarn=(float(y["factor"]), float(y["beta_fast"]),
                  float(y["beta_slow"]), float(y["mscale"]),
                  float(y["mscale_all_dim"]),
                  int(y["original_max_position_embeddings"])),
            norm_eps=float(c["rms_norm_eps"]), dtype=str(c["torch_dtype"]))


def program_config(c: Dict[str, Any]):
    """The program's ModelConfig for configuration ``c``: its ``arch``
    entry with every size set from the file."""
    from repro.configs import registry
    from repro.configs.base import (AttnConfig, MLAConfig, MoEConfig,
                                    YarnConfig)
    d = Dims.of(c)
    base = registry.get_config(c["arch"])
    if base.block_pattern != ("mla_moe",):
        raise ValueError(f"{c['arch']} is not the MLA + MoE block")
    f, bf, bs, ms, msa, orig = d.yarn
    return base.replace(
        num_layers=d.layers, d_model=d.d_model, num_heads=d.heads,
        num_kv_heads=int(c["num_key_value_heads"]), d_ff=d.d_ff,
        vocab_size=d.vocab, tie_embeddings=False, norm_eps=d.norm_eps,
        lead_pattern=("mla_dense",) * d.dense_layers,
        attn=AttnConfig(rope_theta=d.rope_theta, rope_scaling=YarnConfig(
            factor=f, beta_fast=bf, beta_slow=bs, mscale=ms,
            mscale_all_dim=msa, original_max_position_embeddings=orig)),
        mla=MLAConfig(q_lora_rank=d.q_rank, kv_lora_rank=d.kv_rank,
                      qk_nope_head_dim=d.nope, qk_rope_head_dim=d.rope,
                      v_head_dim=d.v_dim),
        moe=MoEConfig(num_experts=d.experts, top_k=d.top_k,
                      expert_ff=d.expert_ff, num_shared_experts=d.shared,
                      routed_scaling=d.scaling,
                      held=(d.first_held, d.held)),
        param_dtype=d.dtype, compute_dtype=d.dtype)


# ------------------------------------------------------------------ weights
DENSE, MOE = "lead/0_mla_dense/", "blocks/0_mla_moe/"


def _mla_leaves(d: Dims, G: int):
    D, H, Rq, R = d.d_model, d.heads, d.q_rank, d.kv_rank
    return {
        "ln1": ((G, D), None), "ln2": ((G, D), None),
        "wq_a": ((G, D, Rq), D ** -0.5), "q_norm": ((G, Rq), None),
        "wq_b": ((G, Rq, H, d.nope + d.rope), Rq ** -0.5),
        "wkv_a": ((G, D, R + d.rope), D ** -0.5), "kv_norm": ((G, R), None),
        "w_uk": ((G, R, H, d.nope), R ** -0.5),
        "w_uv": ((G, R, H, d.v_dim), R ** -0.5),
        "wo": ((G, H, d.v_dim, D), (H * d.v_dim) ** -0.5),
    }


def leaves(d: Dims) -> Dict[str, Tuple[Tuple[int, ...], Optional[float],
                                       Optional[str]]]:
    """Leaf path -> (shape, standard deviation or None for a zero leaf,
    stored type or None for the configuration's)."""
    D, F, Fe, G, n = d.d_model, d.d_ff, d.expert_ff, d.moe_layers, d.held
    Fs = Fe * d.shared
    out = {DENSE + k: v + (None,)
           for k, v in _mla_leaves(d, d.dense_layers).items()}
    out.update({
        DENSE + "wg": ((d.dense_layers, D, F), D ** -0.5, None),
        DENSE + "wu": ((d.dense_layers, D, F), D ** -0.5, None),
        DENSE + "wo_mlp": ((d.dense_layers, F, D), F ** -0.5, None)})
    out.update({MOE + k: v + (None,) for k, v in _mla_leaves(d, G).items()})
    out.update({
        MOE + "router": ((G, D, d.experts), D ** -0.5, None),
        MOE + "router_bias": ((G, d.experts), BIAS_STD, "float32"),
        MOE + "moe_wg": ((G, n, D, Fe), D ** -0.5, None),
        MOE + "moe_wu": ((G, n, D, Fe), D ** -0.5, None),
        MOE + "moe_wo": ((G, n, Fe, D), Fe ** -0.5, None),
        MOE + "sh_wg": ((G, D, Fs), D ** -0.5, None),
        MOE + "sh_wu": ((G, D, Fs), D ** -0.5, None),
        MOE + "sh_wo": ((G, Fs, D), Fs ** -0.5, None),
        "embed": ((d.vocab, D), 0.02, None),
        "lm_head": ((d.vocab, D), D ** -0.5, None),
        "final_norm": ((D,), None, None),
    })
    return out


@functools.partial(jax.jit, static_argnums=(1,))
def _make(key, d: Dims):
    specs = leaves(d)
    paths = sorted(specs)
    keys = jax.random.split(key, len(paths))
    flat = {}
    for k, path in zip(keys, paths):
        shape, std, dtype = specs[path]
        dt = jnp.dtype(dtype or d.dtype)
        flat[path] = (jnp.zeros(shape, dt) if std is None else
                      (jax.random.normal(k, shape, jnp.float32) * std
                       ).astype(dt))
    return _nest(flat)


def make(d: Dims, seed: int) -> Dict:
    """Every weight, in its stored type, from one jitted call."""
    return _make(jax.random.key(seed), d)
