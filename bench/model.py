"""A configuration file, read two ways: as the program's ``ModelConfig``
(the system under test) and as plain sizes (the weights the benchmark
makes and the reference it computes).

Configuration files keep the published ``config.json`` key names; the
program's names for them are mapped here.  A key whose value the program
cannot run is refused, never silently dropped.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict

# published key -> the program's ModelConfig field
PROGRAM_NAMES = {
    "hidden_size": "d_model",
    "intermediate_size": "d_ff",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "head_dim": "head_dim",
    "num_hidden_layers": "num_layers",
    "vocab_size": "vocab_size",
    "tie_word_embeddings": "tie_embeddings",
    "rms_norm_eps": "norm_eps",
}


@dataclasses.dataclass(frozen=True)
class Dims:
    """The sizes of a dense decoder the weights and the reference need."""
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    rope_theta: float
    rotary_dims: int
    norm_eps: float
    dtype: str

    @classmethod
    def of(cls, c: Dict[str, Any]) -> "Dims":
        hd = int(c.get("head_dim") or
                 c["hidden_size"] // c["num_attention_heads"])
        return cls(layers=int(c["num_hidden_layers"]),
                   d_model=int(c["hidden_size"]),
                   heads=int(c["num_attention_heads"]),
                   kv_heads=int(c["num_key_value_heads"]), head_dim=hd,
                   d_ff=int(c["intermediate_size"]),
                   vocab=int(c["vocab_size"]),
                   rope_theta=float(c["rope_theta"]),
                   rotary_dims=int(round(hd * float(
                       c.get("partial_rotary_factor", 1.0)))),
                   norm_eps=float(c["rms_norm_eps"]),
                   dtype=str(c["torch_dtype"]))


def program_config(c: Dict[str, Any]):
    """The program's ModelConfig for configuration ``c`` (its ``arch``
    entry with every mapped size set from the file)."""
    from repro.configs import registry
    if float(c.get("partial_rotary_factor", 1.0)) != 1.0:
        raise ValueError("the program rotates the whole head: "
                         "partial_rotary_factor must be 1.0")
    if c.get("rope_scaling") is not None:
        raise ValueError("the program has no rope_scaling: it must be null")
    if not c.get("tie_word_embeddings", False):
        raise ValueError("the reference serves tied embeddings only")
    base = registry.get_config(c["arch"])
    a = base.attn
    if (base.block_pattern != ("attn",) or base.post_norm
            or base.embed_scale or base.final_logit_softcap is not None
            or a.window is not None or a.logit_softcap is not None
            or a.qkv_bias or not a.use_rope):
        raise ValueError(f"{c['arch']} is not the plain dense decoder "
                         "the reference computes")
    kw ={field: c[key] for key, field in PROGRAM_NAMES.items() if key in c}
    return base.replace(
        attn=dataclasses.replace(base.attn, rope_theta=float(c["rope_theta"])),
        param_dtype=c["torch_dtype"], compute_dtype=c["torch_dtype"], **kw)
