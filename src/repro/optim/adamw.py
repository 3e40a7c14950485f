"""AdamW with memory recipes for 1T-scale state (see DESIGN.md):

  moment_dtype:  float32 | bfloat16 | int8 (blockwise-quantized, bnb-style)
  second_moment: full | factored (Adafactor-style row/col factorization)

Optimizer state is schema-described (like params), so the dry-run can derive
abstract state + NamedShardings without allocating anything; ZeRO sharding is
inherited from the param logical axes.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import OptimizerConfig
from repro.kernels.common import fused_adamw_default, interpret_default
from repro.models.params import PSpec, is_pspec
from repro.optim import quant
from repro.optim.schedule import learning_rate


# ---------------------------------------------------------------------------
# state schema
# ---------------------------------------------------------------------------

def _moment_schema(p: PSpec, ocfg: OptimizerConfig):
    if ocfg.moment_dtype == "int8":
        _, s_shape = quant.quantized_shapes(p.shape)
        s_axes = p.axes[:-1] + (None,) if p.shape else p.axes
        return {"q": PSpec(p.shape, p.axes, "zeros", dtype="int8"),
                "s": PSpec(s_shape, s_axes[:len(s_shape)], "zeros",
                           dtype="float32")}
    return PSpec(p.shape, p.axes, "zeros", dtype=ocfg.moment_dtype)


def _second_moment_schema(p: PSpec, ocfg: OptimizerConfig):
    # Factor the last two dims (Adafactor) — but only when the PER-LAYER
    # slice is >= 2-D (a stacked (G, D) norm scale is effectively 1-D; its
    # "vc" would have a non-layer leading dim and break the layered update
    # scan) and the tensor is big enough to be worth it.
    layered = bool(p.axes) and p.axes[0] == "layers"
    eff_ndim = len(p.shape) - (1 if layered else 0)
    import numpy as _np
    if (ocfg.second_moment == "factored" and eff_ndim >= 2
            and int(_np.prod(p.shape)) >= (1 << 16)):
        return {"vr": PSpec(p.shape[:-1], p.axes[:-1], "zeros", dtype="float32"),
                "vc": PSpec(p.shape[:-2] + (p.shape[-1],),
                            p.axes[:-2] + (p.axes[-1],), "zeros",
                            dtype="float32")}
    return _moment_schema(p, ocfg)


def opt_state_schema(param_schema, ocfg: OptimizerConfig) -> Dict[str, Any]:
    def rec(node, fn):
        if is_pspec(node):
            return fn(node)
        return {k: rec(v, fn) for k, v in node.items()}

    return {
        "m": rec(param_schema, lambda p: _moment_schema(p, ocfg)),
        "v": rec(param_schema, lambda p: _second_moment_schema(p, ocfg)),
        "count": PSpec((), (), "zeros", dtype="int32"),
    }


# ---------------------------------------------------------------------------
# leaf math
# ---------------------------------------------------------------------------

def _load_moment(m):
    return quant.dequantize(m) if isinstance(m, dict) and "q" in m else \
        m.astype(jnp.float32)


def _store_moment(val, like):
    if isinstance(like, dict) and "q" in like:
        return quant.quantize(val)
    return val.astype(like.dtype)


def _update_leaf(pspec: PSpec, param, grad, m, v, lr, ocfg: OptimizerConfig,
                 bc1, bc2):
    g = grad.astype(jnp.float32)
    m_f = _load_moment(m)
    m_new = ocfg.b1 * m_f + (1.0 - ocfg.b1) * g

    factored = isinstance(v, dict) and "vr" in v
    if factored:
        g2 = jnp.square(g) + 1e-30
        vr = ocfg.b2 * v["vr"] + (1.0 - ocfg.b2) * jnp.mean(g2, axis=-1)
        vc = ocfg.b2 * v["vc"] + (1.0 - ocfg.b2) * jnp.mean(g2, axis=-2)
        r = vr / jnp.maximum(jnp.mean(vr, axis=-1, keepdims=True), 1e-30)
        v_hat = r[..., None] * vc[..., None, :]
        v_new = {"vr": vr, "vc": vc}
    else:
        v_f = _load_moment(v)
        v_hat = ocfg.b2 * v_f + (1.0 - ocfg.b2) * jnp.square(g)
        v_new = _store_moment(v_hat, v)

    update = (m_new / bc1) / (jnp.sqrt(v_hat / bc2) + ocfg.eps)
    if ocfg.weight_decay and len(pspec.shape) >= 2:
        update = update + ocfg.weight_decay * param.astype(jnp.float32)
    new_param = (param.astype(jnp.float32) - lr * update).astype(param.dtype)
    return new_param, _store_moment(m_new, m), v_new


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------

def global_norm(tree) -> jax.Array:
    """sqrt(sum of squares), f32-ACCUMULATED without materializing f32
    copies of the leaves, and WITHOUT reshaping (a reshape-to-1D of a
    multi-axis-sharded tensor forces GSPMD to all-gather it; an all-axes
    einsum contraction keeps the shards in place and all-reduces a scalar)."""
    def sumsq(x):
        letters = "abcdefghij"[:x.ndim]
        return jnp.einsum(f"{letters},{letters}->", x, x,
                          preferred_element_type=jnp.float32)
    return jnp.sqrt(sum(sumsq(x) for x in jax.tree.leaves(tree)))


def clip_by_global_norm(grads, max_norm: float):
    norm = global_norm(grads)
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-12))
    # multiply in the grad's own dtype: no whole-tree f32 copies
    return jax.tree.map(lambda g: g * scale.astype(g.dtype), grads), norm


def _fused_leaf(p, g, m, v, lr, bc1, bc2, shd, **hp):
    """The fused kernel on one leaf.  A Mosaic kernel cannot be
    partitioned by XLA, so on a multi-device mesh it runs per shard under
    ``shard_map`` (the update is elementwise: shards never talk)."""
    from repro.kernels.adamw_update import adamw_update
    fn = functools.partial(adamw_update, interpret=interpret_default(), **hp)
    if shd is None or shd.mesh.size == 1:
        return fn(p, g, m, v, lr, bc1, bc2)
    spec, rep = shd.spec, P()
    return jax.shard_map(fn, mesh=shd.mesh, in_specs=(spec,) * 4 + (rep,) * 3,
                         out_specs=(spec,) * 3, check_vma=False)(
                             p, g, m, v, lr, bc1, bc2)


def apply_updates(param_schema, params, grads, state, ocfg: OptimizerConfig,
                  *, fused: Optional[bool] = None, shardings=None):
    """One AdamW step.  Returns (new_params, new_state, stats).

    Memory: the elementwise update math runs in f32, so applying it to a
    whole 61-layer-stacked tensor materializes several full-tree f32 temps
    (observed: ~6x params bytes on the 1T arch).  Leaves whose leading axis
    is the stacked "layers" dim are therefore updated with a lax.scan over
    that axis — peak update temps shrink by num_groups.

    ``fused``: route plain float32/full-state leaves through the fused
    Pallas update kernel (``kernels.adamw_update``) — one elementwise
    kernel per leaf, no f32 temp trees AND no layered scan needed.
    None = backend default (TPU on, CPU off; ``REPRO_FUSED_ADAMW=1``
    forces it on CPU under interpret mode).  Quantized / factored state
    always keeps the unfused path.  ``shardings`` (the params' NamedSharding
    tree) lets the fused kernel run per shard on a multi-device mesh.
    """
    if fused is None:
        fused = fused_adamw_default()
    if ocfg.grad_clip:
        grads, gnorm = clip_by_global_norm(grads, ocfg.grad_clip)
    else:
        gnorm = global_norm(grads)
    count = state["count"] + 1
    lr = learning_rate(ocfg, count)
    t = count.astype(jnp.float32)
    bc1 = 1.0 - ocfg.b1 ** t
    bc2 = 1.0 - ocfg.b2 ** t

    def leaf(sch, p, g, m, v, shd):
        # the fused kernel streams tiles through VMEM, so even stacked
        # "layers" leaves go through whole (no scan, no temp blowup)
        if (fused and not isinstance(m, dict) and not isinstance(v, dict)
                and m.dtype == jnp.float32 and v.dtype == jnp.float32):
            wd = ocfg.weight_decay if len(sch.shape) >= 2 else 0.0
            return _fused_leaf(p, g, m, v, lr, bc1, bc2, shd, b1=ocfg.b1,
                               b2=ocfg.b2, eps=ocfg.eps, weight_decay=wd)
        layered = (sch.axes and sch.axes[0] == "layers"
                   and len(sch.shape) >= 2 and sch.shape[0] > 1)
        if not layered:
            return _update_leaf(sch, p, g, m, v, lr, ocfg, bc1, bc2)
        inner = PSpec(sch.shape[1:], sch.axes[1:], sch.init, sch.scale,
                      sch.dtype)

        def step(_, xs):
            return None, _update_leaf(inner, *xs, lr, ocfg, bc1, bc2)

        _, (np_, nm, nv) = jax.lax.scan(step, None, (p, g, m, v))
        return np_, nm, nv

    def rec(sch, p, g, m, v, shd):
        if is_pspec(sch):
            return leaf(sch, p, g, m, v, shd)
        out = {k: rec(sch[k], p[k], g[k], m[k], v[k],
                      None if shd is None else shd[k]) for k in sch}
        new_p = {k: out[k][0] for k in out}
        new_m = {k: out[k][1] for k in out}
        new_v = {k: out[k][2] for k in out}
        return new_p, new_m, new_v

    new_params, new_m, new_v = rec(param_schema, params, grads,
                                   state["m"], state["v"], shardings)
    new_state = {"m": new_m, "v": new_v, "count": count}
    return new_params, new_state, {"grad_norm": gnorm, "lr": lr}
