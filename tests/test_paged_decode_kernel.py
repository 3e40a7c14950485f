"""The paged decode attention kernel (``kernels/paged_decode.py``), run in
interpret mode, against the view path it replaces on the TPU: the layer's
views gathered from the pool (``paged_layer_view``), the step's rows
inserted (``insert_kv``) and attention over the whole view
(``decode_attention``, MLA's ``_absorbed_decode``).

Tolerances.  Inputs are unit-variance bf16.  Both paths round their
output to bf16: half an ulp each, 2^-9 of |out| <= ~3, so up to 0.012
apart.  The view path also rounds each score's dot product to bf16
before the f32 softmax (2^-9 of |q.k| <= ~4 sqrt(D), times the scale
D^-0.5: under 0.01 in a score, which moves a probability by under 1%)
and the values product to bf16 (another 2^-9); the kernel keeps both in
f32.  Together under 0.03 absolute; softcap only shrinks scores.  The
rows each path writes back are the same numbers, so those are compared
bit for bit.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.kernels import paged_decode
from repro.models import attention as attn_mod
from repro.models import kvcache
from repro.models import transformer as tfm
from test_mla_moe import tiny_config

BS, NB, L, LAYER = 4, 4, 2, 1        # blocks of 4, 16 positions, layer 1
S = BS * NB
ATOL = 3e-2
# slot: parked free slot (all-null table), a first token at 0, the last
# row of block 0, the first of block 1, the last position S-1, a slot
# sharing slot 4's first two blocks, one past S-1 (reads all S rows)
POS = [0, 0, BS - 1, BS, S - 1, 10, S + 1]
SHARED = (4, 5, 2)                   # slot 5 shares slot 4's first 2


def _tables(rng):
    free = list(rng.permutation(np.arange(1, 1 + 4 * NB * len(POS))))
    tables = np.zeros((len(POS), NB), np.int32)
    for i, p in enumerate(POS):
        if i == 0:
            continue                                # parked
        n = min(p // BS + 1, NB)
        tables[i, :n] = [free.pop() for _ in range(n)]
    src, dst, n = SHARED
    tables[dst, :n] = tables[src, :n]
    return jnp.asarray(tables)


def _case(layout, seed=0):
    """Random pool leaves, block tables, the step's rows and queries for
    the ``gqa`` layout (KV = 2 heads of 128, 3 query heads each) or the
    ``latent`` one (one 128-wide row shared by 8 query heads)."""
    rng = np.random.RandomState(seed)
    B = len(POS)
    nblocks = 1 + 4 * NB * len(POS)

    def normal(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)

    if layout == "gqa":
        KV, H, D = 2, 6, 128
        pool = {"k": normal(L, nblocks, BS, KV, D),
                "v": normal(L, nblocks, BS, KV, D)}
        rows = {"k": normal(B, KV, D), "v": normal(B, KV, D)}
    else:
        H, D = 8, 128
        pool = {"latent": normal(L, nblocks, BS, D)}
        rows = {"latent": normal(B, D)}
    return dict(pool=pool, rows=rows, q=normal(B, 1, H, D),
                tables=_tables(rng), pos=jnp.asarray(POS, jnp.int32))


def _views(c):
    """The view path's caches after the step's rows are inserted."""
    views = kvcache.paged_layer_view(c["pool"], LAYER, c["tables"])
    rows = {k: r[:, None] for k, r in c["rows"].items()}
    return kvcache.insert_kv(views, rows, c["pos"])


def _view_attention(c, **kw):
    views = _views(c)
    if "latent" in views:                  # one K = V head
        k = v = views["latent"][:, :, None, :]
    else:
        k, v = views["k"], views["v"]
    return attn_mod.decode_attention(None, c["q"], k, v, c["pos"], **kw)


def _kernel_attention(c, **kw):
    cache = kvcache.PagedLayer(c["pool"], jnp.int32(LAYER), c["tables"])
    return cache.attend(c["q"], c["rows"], c["pos"],
                        scale=c["q"].shape[-1] ** -0.5, **kw)


@pytest.mark.parametrize("layout", ["gqa", "latent"])
@pytest.mark.parametrize("window, cap", [(None, None), (6, 2.0)],
                         ids=["plain", "window_softcap"])
@pytest.mark.parametrize("chunk", [None, 1, 3], ids=["table", "1", "3"])
def test_kernel_matches_the_view_path(layout, window, cap, chunk):
    """Against the view path, with a slot's history in one buffer or
    streamed through both in chunks of 1 or 3 blocks (whole and partial
    chunks, waits of one and two blocks)."""
    c = _case(layout)
    kw = dict(window=window, logit_softcap=cap, chunk=chunk)
    view_kw = dict(window=window, logit_softcap=cap)
    got = np.asarray(_kernel_attention(c, **kw), np.float32)
    want = np.asarray(_view_attention(c, **view_kw), np.float32)
    assert got.shape == want.shape
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, atol=ATOL, rtol=0)


@pytest.mark.parametrize("layout", ["gqa", "latent"])
@pytest.mark.parametrize("chunk", [None, 3], ids=["table", "3"])
def test_kernel_reads_only_live_blocks(layout, chunk):
    """Blocks past each slot's history (and the null block) filled with
    NaN change nothing: the kernel never reads them."""
    c = _case(layout, seed=1)
    want = np.asarray(_kernel_attention(c, chunk=chunk))
    tables, live = np.asarray(c["tables"]), set()
    for i, p in enumerate(POS):
        n = -(-min(p, S) // BS)
        live |= set(tables[i, :n].tolist())
    dead = np.array(sorted(set(range(c["pool"][next(iter(c["pool"]))]
                                     .shape[1])) - live))
    c["pool"] = {k: v.at[:, dead].set(jnp.nan) for k, v in c["pool"].items()}
    got = np.asarray(_kernel_attention(c, chunk=chunk))
    np.testing.assert_array_equal(got.view(np.uint16), want.view(np.uint16))


@pytest.mark.parametrize("layout", ["gqa", "latent"])
def test_written_rows_equal_the_view_paths(layout):
    """The rows handed to the write-back are the view path's, bit for
    bit: K/V rows alone, the latent row inside its block (for every slot
    with a row of its own, pos < S)."""
    c = _case(layout, seed=2)
    key = "0_attn"
    cache = kvcache.PagedLayer(c["pool"], jnp.int32(LAYER), c["tables"])
    got = kvcache.written({key: cache}, {key: c["rows"]}, c["pos"], BS)[key]
    want = kvcache.written_rows(_views(c), c["pos"], BS)
    inside = np.asarray(POS) < S
    for name in c["rows"]:
        g, w = np.asarray(got[name]), np.asarray(want[name])
        assert g.shape == w.shape
        np.testing.assert_array_equal(g[inside].view(np.uint16),
                                      w[inside].view(np.uint16))


def test_absorbed_latent_decode_matches_the_view_path():
    """MLA's absorbed decode (the w_uk and w_uv products outside the
    kernel) through the kernel against the same over the latent views,
    at Kimi K2's block cut to a toy size."""
    from bench import model_mla_moe as mm
    from repro.configs import registry
    from repro.launch.mesh import single_device_mesh
    from repro.models import mla
    from repro.models.layers import ModelCtx

    c = tiny_config("bfloat16", num_attention_heads=8, num_key_value_heads=8,
                    num_hidden_layers=2)
    cfg = mm.program_config(c)
    ctx = ModelCtx(cfg, registry.get_parallel(c["arch"]),
                   single_device_mesh())
    assert mla.latent_width(cfg) == 128         # 16 + 8, then zero pad
    case = _case("latent", seed=3)
    rng = np.random.RandomState(4)
    H, R = cfg.num_heads, cfg.mla.kv_lora_rank
    p = {w: jnp.asarray(rng.standard_normal((R, H, 16)) * R ** -0.5,
                        jnp.bfloat16) for w in ("w_uk", "w_uv")}
    q_nope, q_pe = case["q"][..., :16], case["q"][..., 16:24]
    scale = mla.softmax_scale(cfg)
    row = case["rows"]["latent"][:, None]
    views = kvcache.paged_layer_view(case["pool"], LAYER, case["tables"])
    view, _ = mla._absorbed_decode(ctx, p, q_nope, q_pe, views, row,
                                   case["pos"], scale)
    cache = kvcache.PagedLayer(case["pool"], jnp.int32(LAYER), case["tables"])
    got, _ = mla._absorbed_decode(ctx, p, q_nope, q_pe, cache, row,
                                  case["pos"], scale)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(view, np.float32), atol=ATOL,
                               rtol=0)


def test_kernel_path_needs_a_tpu_one_device_and_whole_lane_tiles(
        monkeypatch):
    """The paged step takes the kernel on the TPU backend only, with the
    step on one device, for pool rows of whole 128-lane tiles (64-wide
    heads keep the view path)."""
    import types

    def leaf(*tail):
        return jax.ShapeDtypeStruct((2, 9, 8) + tail, jnp.bfloat16)

    assert not paged_decode.kernel_path(None, [leaf(8, 128)])   # the CPU
    monkeypatch.setattr(paged_decode, "interpret_default", lambda: False)
    assert paged_decode.kernel_path(None, [leaf(8, 128), leaf(8, 128)])
    assert paged_decode.kernel_path(types.SimpleNamespace(size=1),
                                    [leaf(640)])
    assert not paged_decode.kernel_path(None, [leaf(8, 64), leaf(8, 64)])
    assert not paged_decode.kernel_path(types.SimpleNamespace(size=4),
                                        [leaf(8, 128)])


def test_chunk_holds_whole_tiles_and_stays_in_the_table():
    """A buffer holds about ``CHUNK_BYTES`` of blocks, in eights, never
    more than a slot's table."""
    phi4 = [jax.ShapeDtypeStruct((32, 9, 8, 8, 128), jnp.bfloat16)] * 2
    k2 = [jax.ShapeDtypeStruct((4, 9, 8, 640), jnp.bfloat16)]
    assert paged_decode.chunk_blocks(phi4, 128) == 32
    assert paged_decode.chunk_blocks(k2, 768) == 96
    assert paged_decode.chunk_blocks(k2, 4) == 4


@pytest.mark.parametrize("window", [None, 6])
def test_history_blocks(window):
    """First block and count of each slot's read, against a count of the
    positions the view path's mask keeps (less the step's own)."""
    for p in range(S + 3):
        first, count, lo, hi = paged_decode.history_blocks(
            jnp.int32(p), block_size=BS, seq_len=S, window=window)
        keep = [k for k in range(S) if k < p
                and (window is None or k > p - window)]
        want = sorted({k // BS for k in keep})
        got = list(range(int(first), int(first) + int(count)))
        assert got == want, (p, got, want)


def _step_model(arch):
    """A config with a lead layer or several layer groups, and its
    params: phi4-mini and gemma2 (local layers windowed to 6, scores
    softcapped) at smoke size, Kimi K2's block at a toy size."""
    import dataclasses

    from repro.configs import registry
    from repro.models import params as pr
    if arch == "kimi-k2":
        from bench import model_mla_moe as mm
        c = tiny_config("bfloat16")
        return mm.program_config(c), mm.make(mm.Dims.of(c), 7)
    cfg = registry.get_smoke(arch)
    cfg = cfg.replace(num_layers=2 * len(cfg.block_pattern))
    if "local" in cfg.block_pattern:
        cfg = cfg.replace(attn=dataclasses.replace(
            cfg.attn, window=6, logit_softcap=2.0))
    return cfg, pr.init_params(tfm.lm_schema(cfg), jax.random.key(1),
                               cfg.param_dtype)


# each layer's attention output moves by up to a bf16 ulp (above); through
# the later layers and the head (and Kimi K2's router, where a near-tie can
# take another expert) the logits moved by 1.5-2.3% of their largest
# magnitude in these cases
LOGIT_TOL = 0.05


@pytest.mark.parametrize("arch", ["phi4-mini-3.8b", "gemma2-9b", "kimi-k2"])
def test_decode_step_on_the_kernel_path(arch, monkeypatch):
    """The paged decode forward with the kernel (interpreted) in every
    layer against the view path: the logits within the tolerance above,
    and the first layer's written rows in the pool bit for bit (later
    layers' rows follow from attention outputs, which differ by
    rounding)."""
    from repro.configs import registry
    from repro.launch.mesh import single_device_mesh
    from repro.models.layers import ModelCtx
    from repro.runtime import steps as steps_mod

    cfg, params = _step_model(arch)
    par = registry.get_parallel("phi4-mini-3.8b")
    c = _case("gqa")
    tables, pos = c["tables"], c["pos"]
    B = len(POS)
    rng = np.random.RandomState(5)
    pool = jax.tree.map(
        lambda a: jnp.asarray(rng.standard_normal(a.shape), a.dtype),
        steps_mod.init_paged_cache(cfg, 1 + 4 * NB * B, BS))
    token = jnp.asarray(rng.randint(1, cfg.vocab_size, (B, 1)), jnp.int32)
    ctx = ModelCtx(cfg, par, single_device_mesh())

    def step(kernel):
        monkeypatch.setattr(paged_decode, "kernel_path",
                            lambda *a: kernel)

        @jax.jit
        def run(pool):
            h, rows, _ = tfm.forward(ctx, params, token, mode="decode",
                                     caches=pool, pos=pos, tables=tables)
            return (tfm.lm_logits(ctx, params, h)[:, 0],
                    steps_mod.paged_cache_scatter(pool, rows, tables, pos))
        return run(pool)

    want_logits, want_pool = step(False)
    got_logits, got_pool = step(True)
    want = np.asarray(want_logits, np.float32)
    np.testing.assert_allclose(np.asarray(got_logits, np.float32), want,
                               atol=LOGIT_TOL * np.abs(want).max(), rtol=0)
    first = jax.tree.leaves(want_pool["lead"] if "lead" in want_pool
                            else want_pool)[0]
    got = jax.tree.leaves(got_pool["lead"] if "lead" in got_pool
                          else got_pool)[0]
    np.testing.assert_array_equal(np.asarray(got)[0, 1:].view(np.uint16),
                                  np.asarray(first)[0, 1:].view(np.uint16))
