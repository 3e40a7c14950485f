"""Kimi K2's block (MLA, sigmoid-routed held experts beside a shared
expert, a leading dense layer) against the plain float32 reference in
``bench/reference/mla_moe.py``, at a small size on seeded random
weights; and the published formulas it rests on."""
import copy
import dataclasses
import hashlib
import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import spec
from bench import model_mla_moe as mm
from bench.reference import mla_moe as ref
from repro.configs import registry
from repro.configs.base import OptimizerConfig, ShapeConfig
from repro.launch.mesh import single_device_mesh
from repro.models import mla, moe
from repro.models import transformer as tfm
from repro.models.layers import ModelCtx
from repro.runtime import steps as steps_mod

TINY = {"hidden_size": 64, "num_attention_heads": 4,
        "num_key_value_heads": 4, "q_lora_rank": 32, "kv_lora_rank": 16,
        "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
        "intermediate_size": 96, "moe_intermediate_size": 32,
        "num_hidden_layers": 3, "n_routed_experts": 4, "expert_parallel": 4,
        "num_experts_per_tok": 4, "vocab_size": 256,
        "published": {"num_hidden_layers": 61, "n_routed_experts": 16}}


def tiny_config(dtype="float32", **kw):
    c = spec.config("kimi-k2-instruct-ep48")
    c.update(copy.deepcopy(TINY), torch_dtype=dtype, **kw)
    return c


@pytest.fixture(scope="module")
def tiny():
    c = tiny_config()
    d = mm.Dims.of(c)
    cfg = mm.program_config(c)
    return dict(c=c, d=d, cfg=cfg, params=mm.make(d, 7),
                par=registry.get_parallel(c["arch"]),
                mesh=single_device_mesh())


def _reference_logits(d, params, rows):
    T = -(-max(len(r) for r in rows) // ref.Q_BLOCK) * ref.Q_BLOCK
    toks = np.ones((len(rows), T), np.int32)
    for i, r in enumerate(rows):
        toks[i, :len(r)] = r
    x, _ = ref.hidden(d, params, toks)
    return np.asarray(jnp.einsum("btd,vd->btv", x,
                                 params["lm_head"].astype(jnp.float32),
                                 precision=ref.HI))


def _served_logits(s, prompts, steps, bs=4):
    """Prefill each prompt alone into its blocks of a paged pool, then
    decode ``steps`` tokens for all of them at once through the paged
    decode path (per-layer views, the absorbed latent attention, the
    written rows scattered back), feeding each slot its own greedy
    token.  Returns each slot's token row and logits of every served
    position, the steps' experts_hit counters, and each slot's router
    choice per position (positions, MoE layers, top_k)."""
    cfg, par, mesh, params = s["cfg"], s["par"], s["mesh"], s["params"]
    P, B = len(prompts[0]), len(prompts)
    S = P + steps + bs
    nb = S // bs
    ctx = ModelCtx(cfg, par, mesh)
    prefill = jax.jit(steps_mod.build_prefill(
        cfg, par, mesh, ShapeConfig("s", S, 1, "prefill")).fn)
    pool = steps_mod.init_paged_cache(cfg, 1 + B * nb, bs)
    tables = np.zeros((B, nb), np.int32)
    rows, logits, routes = [], [], []
    for i, prompt in enumerate(prompts):
        tables[i] = 1 + i * nb + np.arange(nb)
        last, small, route = prefill(params,
                                     jnp.asarray([prompt], jnp.int32))
        pool = steps_mod.paged_prompt_insert(pool, small,
                                             jnp.asarray(tables[i, :P // bs]))
        logits.append([np.asarray(last[0])])
        rows.append(list(prompt) + [int(jnp.argmax(last[0]))])
        routes.append([np.asarray(route[0])])

    @jax.jit
    def step(pool, tables, token, pos):
        h, new, aux = tfm.forward(ctx, params, token, mode="decode",
                                  caches=pool, pos=pos, tables=tables)
        out = tfm.lm_logits(ctx, params, h)[:, 0]
        pool = steps_mod.paged_cache_scatter(pool, new, tables, pos)
        return (out, pool, tfm.step_counters(cfg, aux),
                tfm.step_route(cfg, aux))

    hits = []
    for t in range(steps):
        token = jnp.asarray([[r[-1]] for r in rows], jnp.int32)
        pos = jnp.full((B,), P + t, jnp.int32)
        out, pool, counters, route = step(pool, jnp.asarray(tables), token,
                                          pos)
        hits.append(float(counters["experts_hit"]))
        for i in range(B):
            logits[i].append(np.asarray(out[i]))
            rows[i].append(int(jnp.argmax(out[i])))
            routes[i].append(np.asarray(route[i]))
    return (rows, [np.stack(x) for x in logits], hits,
            [np.concatenate(x) for x in routes])


def test_prefill_and_paged_decode_match_the_reference(tiny):
    """Logits of the prompt's last position (prefill) and of every
    decoded position (paged decode, absorbed MLA) agree with the float32
    reference's full forward pass, for two slots decoding at once."""
    rng = np.random.RandomState(3)
    P, steps = 12, 9
    prompts = [rng.randint(1, 256, P).tolist() for _ in range(2)]
    rows, got, hits, routes = _served_logits(tiny, prompts, steps)
    want = _reference_logits(tiny["d"], tiny["params"], rows)
    for i in range(2):
        w = want[i, P - 1:P + steps]
        assert np.abs(got[i] - w).max() < 2e-4 * np.abs(w).max()
    assert all(0 <= h <= tiny["d"].held for h in hits)
    d = tiny["d"]
    assert routes[0].shape == (P + steps, d.moe_layers, d.top_k)


@pytest.mark.parametrize("seed", [7, 8, 9])
def test_bf16_serving_stays_near_the_reference(tiny, seed):
    """The stored-precision path: with bf16 weights and caches every
    served token's logit lies within a bf16-sized gap of the float32
    reference's best (computed from the same bf16 weights).  Rounding of
    the hidden state flips the router's choice where two experts score
    within a near-tie; there the reference takes the program's choice
    (``choose``), and no choice departs from the reference's by 0.005 or
    more.  Without the program's choice seeds 7 and 8 read 0.38 and
    0.20 at such a flip."""
    c = tiny_config("bfloat16")
    d = mm.Dims.of(c)
    s = dict(tiny, c=c, d=d, cfg=mm.program_config(c),
             params=mm.make(d, seed))
    rng = np.random.RandomState(4)
    prompts = [rng.randint(1, 256, 8).tolist() for _ in range(2)]
    rows, _, _, routes = _served_logits(s, prompts, 6)
    gaps, stood = ref.served_gaps(d, s["params"], prompts,
                                  [r[8:] for r in rows], routes, 0.005)
    assert np.concatenate(gaps).max() < 0.05
    assert sum(x["misses"] for x in stood) == 0
    assert sum(x["flips"] for x in stood) < 0.25 * sum(
        x["pairs"] for x in stood)


def test_reference_takes_the_programs_choice_only_at_a_near_tie():
    """``choose``: the reference's own top 2 of 4 by score plus bias, or
    the program's where it swaps experts scoring within the margin; a
    departure of the margin or more is a miss, and keeps the reference's
    own choice; weights are the unbiased scores of the choice."""
    d = dataclasses.replace(mm.Dims.of(tiny_config()), top_k=2, scaling=1.0)
    logits = jnp.asarray([[2.0, 1.0, 0.999, -1.0]] * 3)
    bias = jnp.zeros(4)
    program = jnp.asarray([[0, 2], [0, 3], [-1, -1]])
    ids, w, flip, miss, departure = ref.choose(d, logits, bias, program,
                                               0.005)
    assert np.asarray(ids).tolist() == [[0, 2], [0, 1], [0, 1]]
    assert np.asarray(flip).tolist() == [True, False, False]
    assert np.asarray(miss).tolist() == [False, True, False]
    s = 1 / (1 + np.exp(-np.array([2.0, 1.0, 0.999, -1.0])))
    np.testing.assert_allclose(departure, [s[1] - s[2], s[1] - s[3], 0],
                               atol=1e-7)            # float32 scores
    np.testing.assert_allclose(np.asarray(w)[0], s[[0, 2]] / s[[0, 2]].sum(),
                               rtol=1e-6)


def test_experts_hit_counts_the_held_experts_chosen(tiny):
    """The decode step's counter is the number of held experts that some
    token chose, averaged over the MoE layers."""
    cfg, params = tiny["cfg"], tiny["params"]
    ctx = ModelCtx(cfg, tiny["par"], None)
    x = jax.random.normal(jax.random.key(0), (5, 1, cfg.d_model))
    lw = jax.tree.map(lambda a: a[0], params["blocks"]["0_mla_moe"])
    _, hits, route = moe.held_expert_mlp(ctx, lw, x)
    logits = jnp.einsum("btd,de->bte", x, lw["router"])
    ids, _ = moe.sigmoid_route(logits, lw["router_bias"], cfg.moe.top_k,
                               cfg.moe.routed_scaling)
    np.testing.assert_array_equal(route, ids)
    first, n = cfg.moe.held_range
    want = len({int(e) for e in np.asarray(ids).ravel()
                if first <= e < first + n})
    assert float(hits) == want


def test_routing_sigmoid_bias_normalised_scaled():
    """The bias moves the choice and only the choice; the weights are the
    chosen experts' sigmoid scores, normalised to one, times the scale."""
    logits = jnp.asarray([[2.0, 1.9, 0.0, -1.0, 1.0, 0.5]])
    bias = jnp.asarray([0.0, 0.0, 0.0, 0.0, 0.6, 0.0])
    ids, w = moe.sigmoid_route(logits, bias, 2, 2.827)
    assert sorted(np.asarray(ids)[0].tolist()) == [0, 4]    # bias picked 4
    scores = 1 / (1 + np.exp(-np.asarray(logits)[0]))
    want = scores[np.asarray(ids)[0]]
    np.testing.assert_allclose(np.asarray(w)[0],
                               want / want.sum() * 2.827, rtol=1e-6)
    np.testing.assert_allclose(float(w.sum()), 2.827, rtol=1e-6)
    d = dataclasses.replace(mm.Dims.of(tiny_config()), top_k=2,
                            scaling=2.827)
    r_ids, r_w = ref.route(d, logits, bias)
    np.testing.assert_array_equal(np.sort(r_ids), np.sort(ids))
    np.testing.assert_allclose(np.sort(r_w), np.sort(w), rtol=1e-6)


def test_yarn_against_the_published_formulas():
    """Kimi K2's yarn: correction dims floor/ceil of
    dim ln(L / (2 pi beta)) / (2 ln theta) for beta 1 and 1 give dims 19
    and 20 of the 32 frequencies, so the first 20 keep theta's
    frequencies and the rest are divided by 32; the softmax scale is
    (0.1 ln 32 + 1)^2 / sqrt(192) and yarn's cos/sin factor is 1."""
    cfg = registry.get_config("kimi-k2-1t-a32b")
    dim, base, L = 64, 50_000.0, 4096
    cd = dim * math.log(L / (2 * math.pi)) / (2 * math.log(base))
    assert (math.floor(cd), math.ceil(cd)) == (19, 20)
    orig = base ** (-np.arange(0, dim, 2) / dim)
    want = np.where(np.arange(dim // 2) <= 19, orig, orig / 32)
    np.testing.assert_allclose(mla.rope_inv_freq(cfg), want, rtol=1e-6)
    d = mm.Dims.of(spec.config("kimi-k2-instruct-ep48"))
    np.testing.assert_allclose(ref.inv_freq(d), want, rtol=1e-12)
    mscale = 0.1 * math.log(32) + 1
    assert mla.softmax_scale(cfg) == pytest.approx(mscale ** 2 / 192 ** 0.5)
    assert ref.softmax_scale(d) == pytest.approx(mscale ** 2 / 192 ** 0.5)
    assert mla.rope_mscale(cfg) == 1.0


def test_expert_shares_sum_to_the_uncut_layer(tiny):
    """Guide's share test: 16 experts in 4 shares of 4.  Each share's
    layer (its 4 experts, the router over all 16, the shared expert) is
    computed alone; their outputs, with the shared expert counted once,
    sum to the uncut layer's, and the uncut layer is the reference's."""
    c = tiny_config(n_routed_experts=16, expert_parallel=1)
    d_full = mm.Dims.of(c)
    cfg_full = mm.program_config(c)
    p_full = jax.tree.map(lambda a: a[0],
                          mm.make(d_full, 11)["blocks"]["0_mla_moe"])
    x = jax.random.normal(jax.random.key(1), (2, 5, cfg_full.d_model))
    whole = moe.held_expert_mlp(ModelCtx(cfg_full, tiny["par"]), p_full,
                                x)[0]

    def share(first, n):
        p = dict(p_full, **{k: p_full[k][first:first + n]
                            for k in ("moe_wg", "moe_wu", "moe_wo")})
        cfg = cfg_full.replace(moe=dataclasses.replace(cfg_full.moe,
                                                       held=(first, n)))
        return np.asarray(moe.held_expert_mlp(ModelCtx(cfg, tiny["par"]),
                                              p, x)[0])

    shared = share(0, 0)                # no expert held: the shared expert
    total = -3 * np.asarray(shared)
    for first in range(0, 16, 4):
        total = total + share(first, 4)
    np.testing.assert_allclose(total, np.asarray(whole), atol=2e-5)
    W = {k: v.astype(jnp.float32) for k, v in p_full.items()}
    want, _ = ref.experts(d_full, W, x, W["router_bias"], None)
    np.testing.assert_allclose(np.asarray(whole), np.asarray(want),
                               atol=2e-5)


# sha256 of the compiled text of phi4-mini's paged decode step and train
# step, at the smoke size and at its widths (2 layers), without metadata
# and debug sections, as the tree before the MLA and held-expert kinds
# compiled them (regenerate only for a change meant to alter these steps)
UNCHANGED = {
    ("paged_step", "smoke"):
        "c94374e7886a74e7c0142026b3e0d17a5f020e8948087340221c652e95b13ac6",
    ("train_chunk", "smoke"):
        "cb827fa544d6d96f82cb772ec5847b26c842b01530877f672bc60032265cdde9",
    ("paged_step", "widths"):
        "39370d411e88af5abf1d4e45957f30c1cb0c7485e007aa7dfc5b3a2fabfc3e6d",
    ("train_chunk", "widths"):
        "2a25622e2f78438c9230f440a2f1e8e937e1663d2d5167acafefd0a1b15c7153",
}
DEBUG = ("FileNames", "FunctionNames", "FileLocations", "StackFrames")


def _program(bundle, mesh):
    with mesh:
        text = bundle.lower().compile().as_text()
    text = re.sub(r",? metadata=\{[^}]*\}", "", text)
    return "\n\n".join(b for b in text.split("\n\n")
                       if not b.startswith(DEBUG))


@pytest.mark.parametrize("step, size", sorted(UNCHANGED))
def test_phi4_steps_lower_to_the_same_text(step, size):
    """serve-phi4-decode's paged step and the train step compile to the
    program they had before the new kinds (metadata aside)."""
    cfg = (registry.get_smoke("phi4-mini-3.8b") if size == "smoke" else
           registry.get_config("phi4-mini-3.8b").replace(num_layers=2))
    par = registry.get_parallel("phi4-mini-3.8b")
    mesh = single_device_mesh()
    if step == "paged_step":
        bundle = steps_mod.build_paged_decode(
            cfg, par, mesh, ShapeConfig("serve", 64, 2, "decode"),
            block_size=8, num_blocks=20)
    else:
        bundle = steps_mod.build_train_chunk(
            cfg, par, OptimizerConfig(warmup_steps=1, decay_steps=10), mesh,
            ShapeConfig("t", 32, 2, "train"), 1)
    text = _program(bundle, mesh)
    assert len(text) > 50_000          # the computations, not a header
    assert hashlib.sha256(text.encode()).hexdigest() == UNCHANGED[step, size]
