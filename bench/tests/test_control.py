"""The control, the reference computed at a lower precision in the
program's place, comes out not correct under the cell's own comparison,
at a size a test can hold (the chip readings at the cells' own sizes
are in PERF.md and bench/limits/)."""
import time

import jax.numpy as jnp
import numpy as np
import pytest

from bench import spec
from bench.harness import Ctx
from bench.reference import dense_lm
from bench.runners import serve, train
from bench.tests import tiny


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("bench")
    return tiny.make(tmp)


def _ctx(bench, workload, mix_name, seed=11):
    return Ctx(workload=workload, cfg=spec.config("tiny", bench),
               mix=spec.traffic(mix_name, bench),
               limits=spec.limits(workload, bench), seed=seed,
               seconds=2.0, trace=False, chips=1, t_start=time.monotonic(),
               peaks=None)


def test_serving_control_reads_wider_gaps(copy):
    ctx = _ctx(copy, "tiny-decode", "tiny-decode")
    rec = serve.window(ctx)
    rids = serve.sample(ctx, rec)
    checks, ok = serve.judge(ctx, serve.gaps(ctx, rec, rids))
    low_checks, low_ok = serve.judge(ctx, serve.gaps(ctx, rec, rids, "fp8"))
    assert ok and not low_ok
    assert low_checks["max_logit_gap"]["value"] > \
        3 * checks["max_logit_gap"]["value"]


def test_training_control_reads_wider_gaps(copy):
    ctx = _ctx(copy, "tiny-train", "tiny-train")
    rec = train.window(ctx)
    got = train.check(ctx, train.program(ctx, rec))
    low = train.check(ctx, train.reference(ctx, "fp8", keep_grad=True))
    assert train.judge(ctx, got)[1] is True
    assert train.judge(ctx, low)[1] is False
    # rounding in the products moves the first gradient itself, which
    # its norm hardly shows
    assert low["grad_err"] > 3 * got["grad_err"]
    assert low["grad_err"] > ctx.limits["grad_err"]["limit"]


def test_parameters_are_stored_at_their_type():
    x = jnp.asarray(np.random.default_rng(0).normal(size=(64, 32)),
                    jnp.float32)
    np.testing.assert_array_equal(
        dense_lm.store(x, "bfloat16", None),
        x.astype(jnp.bfloat16).astype(jnp.float32))
    np.testing.assert_array_equal(dense_lm.store(x, "float32", None), x)
    q = dense_lm.store(x, "bfloat16", "fp8")
    # e4m3 keeps 3 mantissa bits: at most 16 values per binade
    assert len(np.unique(np.asarray(q[0]))) < len(np.unique(np.asarray(x[0])))
