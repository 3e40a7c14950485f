"""Per-kernel correctness: sweep shapes/dtypes, interpret=True vs ref oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.moe_gmm import gmm
from repro.kernels.ssm_scan import ssd_scan
from repro.kernels.wkv6 import wkv6


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else \
        dict(rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("B,H,Sq,Sk,dh", [
    (1, 1, 128, 128, 64),
    (2, 3, 256, 256, 64),
    (1, 2, 128, 384, 32),     # rectangular (prefill-like), Sq < Sk
    (2, 1, 512, 512, 128),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention(B, H, Sq, Sk, dh, dtype, causal):
    if causal and Sq != Sk:
        pytest.skip("causal offset variant covered by equal-length cases")
    k1, k2, k3 = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(k1, (B, H, Sq, dh), dtype)
    k = jax.random.normal(k2, (B, H, Sk, dh), dtype)
    v = jax.random.normal(k3, (B, H, Sk, dh), dtype)
    out = flash_attention(q, k, v, causal=causal, block_q=128, block_k=128,
                          interpret=True)
    want = ref.attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


@pytest.mark.parametrize("B,S,H,hd,N,chunk", [
    (1, 64, 1, 16, 8, 16),
    (2, 128, 3, 32, 16, 32),
    (1, 256, 2, 64, 64, 128),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_scan(B, S, H, hd, N, chunk, dtype):
    ks = jax.random.split(jax.random.key(1), 5)
    x = jax.random.normal(ks[0], (B, S, H, hd), dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H))).astype(jnp.float32)
    a = -jnp.exp(jax.random.normal(ks[2], (H,))).astype(jnp.float32)
    B_ = jax.random.normal(ks[3], (B, S, N), dtype)
    C = jax.random.normal(ks[4], (B, S, N), dtype)
    out = ssd_scan(x, dt, a, B_, C, chunk=chunk, interpret=True)
    want, _ = ref.ssd_ref(x, dt, a, B_, C,
                          jnp.zeros((B, H, hd, N), jnp.float32))
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=4e-2 if dtype == jnp.bfloat16 else 1e-4,
                               atol=4e-2 if dtype == jnp.bfloat16 else 1e-4)


@pytest.mark.parametrize("B,S,H,hd,chunk", [
    (1, 64, 1, 16, 16),
    (2, 128, 2, 32, 32),
    (1, 128, 4, 64, 64),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_wkv6(B, S, H, hd, chunk, dtype):
    ks = jax.random.split(jax.random.key(2), 5)
    r = jax.random.normal(ks[0], (B, S, H, hd), dtype)
    k = jax.random.normal(ks[1], (B, S, H, hd), dtype)
    v = jax.random.normal(ks[2], (B, S, H, hd), dtype)
    logw = -jnp.exp(jax.random.normal(ks[3], (B, S, H, hd))).astype(jnp.float32)
    logw = jnp.maximum(logw, -8.0)
    u = jax.random.normal(ks[4], (H, hd), jnp.float32)
    out = wkv6(r, k, v, logw.astype(dtype), u, chunk=chunk, interpret=True)
    want, _ = ref.wkv6_ref(r, k, v, logw.astype(dtype), u,
                           jnp.zeros((B, H, hd, hd), jnp.float32))
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=5e-2 if dtype == jnp.bfloat16 else 2e-3,
                               atol=5e-2 if dtype == jnp.bfloat16 else 2e-3)


@pytest.mark.parametrize("E,C,D,F,bc,bf,bd", [
    (2, 128, 64, 128, 128, 128, 64),
    (4, 256, 128, 256, 128, 128, 128),
    (1, 128, 256, 128, 64, 64, 128),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gmm(E, C, D, F, bc, bf, bd, dtype):
    k1, k2 = jax.random.split(jax.random.key(3))
    x = jax.random.normal(k1, (E, C, D), dtype)
    w = jax.random.normal(k2, (E, D, F), dtype)
    out = gmm(x, w, block_c=bc, block_f=bf, block_d=bd, interpret=True)
    want = ref.gmm_ref(x, w)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


# ---------------------------------------------------------------------------
# fused softmax cross-entropy
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("R,V,br,bv", [
    (128, 512, 128, 512),      # single tile both ways
    (256, 1024, 128, 256),     # multi-tile vocab sweep
    (100, 777, 64, 256),       # ragged rows AND vocab (padding paths)
    (32, 50, 32, 128),         # vocab smaller than one tile
])
@pytest.mark.parametrize("softcap", [None, 30.0])
def test_softmax_xent(R, V, br, bv, softcap):
    from repro.kernels.xent import softmax_xent
    k1, k2 = jax.random.split(jax.random.key(4))
    logits = 4.0 * jax.random.normal(k1, (R, V), jnp.float32)
    labels = jax.random.randint(k2, (R,), 0, V)
    out = softmax_xent(logits, labels, softcap=softcap, block_r=br,
                       block_v=bv, interpret=True)
    want = ref.softmax_xent_ref(logits, labels, softcap=softcap)
    assert out.shape == (R,) and out.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("softcap", [None, 30.0])
def test_softmax_xent_grad(softcap):
    from repro.kernels.xent import softmax_xent
    k1, k2 = jax.random.split(jax.random.key(5))
    logits = 4.0 * jax.random.normal(k1, (96, 300), jnp.float32)
    labels = jax.random.randint(k2, (96,), 0, 300)

    def mean_nll(fn):
        return lambda x: jnp.mean(fn(x))

    g = jax.grad(mean_nll(lambda x: softmax_xent(
        x, labels, softcap=softcap, block_r=64, block_v=128,
        interpret=True)))(logits)
    g_ref = jax.grad(mean_nll(lambda x: ref.softmax_xent_ref(
        x, labels, softcap=softcap)))(logits)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("R,V,br,bv", [
    (200, 1000, 64, 384),      # 4 row blocks (last 8 rows), 3 vocab (232)
    (72, 640, 64, 128),        # vocab a whole number of tiles, rows not
])
def test_softmax_xent_bf16_ragged_tiles(R, V, br, bv):
    """bf16 logits over partial row AND vocab blocks: the cdiv grid's
    out-of-range tails are masked (forward) and dropped (backward), with
    no padded copy of the logits."""
    from repro.kernels.xent import softmax_xent
    k1, k2 = jax.random.split(jax.random.key(9))
    logits = (4.0 * jax.random.normal(k1, (R, V))).astype(jnp.bfloat16)
    labels = jax.random.randint(k2, (R,), 0, V)
    out = softmax_xent(logits, labels, block_r=br, block_v=bv,
                       interpret=True)
    want = ref.softmax_xent_ref(logits, labels)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    g = jax.grad(lambda x: jnp.mean(softmax_xent(
        x, labels, block_r=br, block_v=bv, interpret=True)))(logits)
    g_ref = jax.grad(lambda x: jnp.mean(ref.softmax_xent_ref(x, labels)))(
        logits)
    assert g.shape == (R, V) and g.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(g, np.float32),
                               np.asarray(g_ref, np.float32),
                               rtol=2e-2, atol=1e-6)


def test_softmax_xent_extreme_logits():
    """Online logsumexp must not overflow where naive exp would."""
    from repro.kernels.xent import softmax_xent
    logits = jnp.array([[1000.0, 0.0, -1000.0, 500.0]] * 8, jnp.float32)
    labels = jnp.array([0, 1, 2, 3, 0, 1, 2, 3])
    out = softmax_xent(logits, labels, block_r=8, block_v=128,
                       interpret=True)
    want = ref.softmax_xent_ref(logits, labels)
    assert np.all(np.isfinite(np.asarray(out)))
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# fused AdamW update
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape", [
    (256, 128),                # exact tiles
    (3, 100, 37),              # 300 rows over 64-row tiles, last dim whole
    (5,),                      # tiny 1-D leaf: one tile, mostly out of range
    (3, 100, 300),             # rows and last dim both cut, both ragged
    (1000,),                   # 1-D leaf: one row, ragged lane tail
    (7, 5, 3, 260),            # leading dims collapse to 105 rows
])
@pytest.mark.parametrize("pdtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("weight_decay", [0.0, 0.1])
def test_adamw_update(shape, pdtype, weight_decay):
    from repro.kernels.adamw_update import adamw_update
    ks = jax.random.split(jax.random.key(6), 4)
    p = jax.random.normal(ks[0], shape, pdtype)
    g = jax.random.normal(ks[1], shape, jnp.float32)
    m = 0.1 * jax.random.normal(ks[2], shape, jnp.float32)
    v = jnp.abs(jax.random.normal(ks[3], shape)).astype(jnp.float32)
    lr, bc1, bc2 = jnp.float32(3e-4), jnp.float32(0.271), jnp.float32(0.0297)
    hp = dict(b1=0.9, b2=0.95, eps=1e-8, weight_decay=weight_decay)
    # (64, 128) tiles: the leaf's (leading dims, last dim) view is cut
    # into partial edge tiles, whose out-of-range part is never written
    new_p, new_m, new_v = adamw_update(p, g, m, v, lr, bc1, bc2,
                                       block_rows=64, block_cols=128,
                                       interpret=True, **hp)
    want_p, want_m, want_v = ref.adamw_update_ref(p, g, m, v, lr, bc1, bc2,
                                                  **hp)
    assert new_p.shape == shape and new_p.dtype == pdtype
    assert new_m.dtype == jnp.float32 and new_v.dtype == jnp.float32
    # a couple ulp of slack: XLA fuses the ref's multiply-add chains with
    # FMA, the interpreted kernel evaluates them unfused
    np.testing.assert_allclose(np.asarray(new_m), np.asarray(want_m),
                               rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(np.asarray(new_v), np.asarray(want_v),
                               rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(np.asarray(new_p, np.float32),
                               np.asarray(want_p, np.float32),
                               rtol=1e-6, atol=1e-6)


def test_adamw_update_f32_accumulation():
    """bf16 params must be updated in f32: a tiny lr*update that underflows
    a pure-bf16 subtract must still match the f32-accumulated ref."""
    from repro.kernels.adamw_update import adamw_update
    p = jnp.full((128,), 1.0, jnp.bfloat16)
    g = jnp.full((128,), 1e-3, jnp.float32)
    m = jnp.zeros((128,), jnp.float32)
    v = jnp.zeros((128,), jnp.float32)
    lr, bc1, bc2 = jnp.float32(1e-5), jnp.float32(0.1), jnp.float32(0.05)
    hp = dict(b1=0.9, b2=0.95, eps=1e-8)
    new_p, _, _ = adamw_update(p, g, m, v, lr, bc1, bc2, block_rows=8,
                               interpret=True, **hp)
    want_p, _, _ = ref.adamw_update_ref(p, g, m, v, lr, bc1, bc2, **hp)
    np.testing.assert_array_equal(np.asarray(new_p, np.float32),
                                  np.asarray(want_p, np.float32))


def test_apply_updates_fused_matches_unfused():
    """The optimizer-level fused gate: full schema tree, stacked layers
    leaf included (fused skips the layered scan entirely)."""
    from repro.configs.base import OptimizerConfig
    from repro.models.params import PSpec
    from repro.optim import adamw as A

    schema = {"w": PSpec((8, 64), (None, None), "normal"),
              "b": PSpec((64,), (None,), "zeros"),
              "stack": PSpec((3, 16, 16), ("layers", None, None), "normal")}
    ks = jax.random.split(jax.random.key(7), 6)
    params = {"w": jax.random.normal(ks[0], (8, 64), jnp.bfloat16),
              "b": jax.random.normal(ks[1], (64,), jnp.bfloat16),
              "stack": jax.random.normal(ks[2], (3, 16, 16), jnp.bfloat16)}
    grads = {"w": jax.random.normal(ks[3], (8, 64), jnp.float32),
             "b": jax.random.normal(ks[4], (64,), jnp.float32),
             "stack": jax.random.normal(ks[5], (3, 16, 16), jnp.float32)}
    state = {"m": jax.tree.map(jnp.zeros_like, grads),
             "v": jax.tree.map(jnp.zeros_like, grads),
             "count": jnp.zeros((), jnp.int32)}
    ocfg = OptimizerConfig(warmup_steps=2, decay_steps=10)
    p_u, s_u, _ = A.apply_updates(schema, params, grads, state, ocfg,
                                  fused=False)
    p_f, s_f, _ = A.apply_updates(schema, params, grads, state, ocfg,
                                  fused=True)
    for k in p_u:
        np.testing.assert_allclose(np.asarray(p_u[k], np.float32),
                                   np.asarray(p_f[k], np.float32),
                                   rtol=1e-2, atol=1e-2)   # bf16 rounding
        np.testing.assert_allclose(np.asarray(s_u["m"][k]),
                                   np.asarray(s_f["m"][k]),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(s_u["v"][k]),
                                   np.asarray(s_f["v"][k]),
                                   rtol=1e-5, atol=1e-6)


def test_chunked_cross_entropy_fused_matches_unfused():
    from repro.models import losses
    ks = jax.random.split(jax.random.key(8), 3)
    x = jax.random.normal(ks[0], (2, 32, 16), jnp.float32)
    lab = jax.random.randint(ks[1], (2, 32), 0, 100)
    head = jax.random.normal(ks[2], (100, 16), jnp.float32)
    for cap in (None, 20.0):
        a = losses.chunked_cross_entropy(x, lab, head, softcap=cap,
                                         chunk=16, fused=False)
        b = losses.chunked_cross_entropy(x, lab, head, softcap=cap,
                                         chunk=16, fused=True)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)
        ga = jax.grad(lambda t: losses.chunked_cross_entropy(
            t, lab, head, softcap=cap, chunk=16, fused=False))(x)
        gb = jax.grad(lambda t: losses.chunked_cross_entropy(
            t, lab, head, softcap=cap, chunk=16, fused=True))(x)
        np.testing.assert_allclose(np.asarray(ga), np.asarray(gb),
                                   rtol=1e-4, atol=1e-5)
