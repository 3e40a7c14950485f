"""The benchmark's weights are the program's initialisation, made
independently: the trainer, which draws its own from the seed, starts
where the reference starts."""
import jax
import numpy as np

from bench import weights
from bench.model import Dims, program_config
from bench.tests import tiny


def test_weights_equal_the_program_init():
    from repro.models import params as pr
    from repro.models import transformer as tfm
    from bench import spec
    cfg = spec.config("phi4-mini-3.8b")
    cfg.update(tiny.CONFIG)
    d = Dims.of(cfg)
    ours = weights.make(d, 1234)
    mcfg = program_config(cfg)
    theirs = pr.init_params(tfm.lm_schema(mcfg), jax.random.key(1234),
                            mcfg.param_dtype)
    a, ta = jax.tree.flatten(ours)
    b, tb = jax.tree.flatten(theirs)
    assert ta == tb
    for x, y in zip(a, b):
        assert x.dtype == y.dtype and x.shape == y.shape
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_configuration_maps_onto_the_program():
    from bench import spec
    for name in ("phi4-mini-3.8b", "phi4-mini-3.8b-train1"):
        c = spec.config(name)
        m = program_config(c)
        d = Dims.of(c)
        assert (m.num_layers, m.d_model, m.num_heads, m.num_kv_heads,
                m.resolved_head_dim, m.d_ff, m.vocab_size) == \
            (d.layers, d.d_model, d.heads, d.kv_heads, d.head_dim, d.d_ff,
             d.vocab)
        assert m.norm_eps == d.norm_eps and m.attn.rope_theta == d.rope_theta
        assert set(c["reduced"]) <= set(c) | set(c["published"])
