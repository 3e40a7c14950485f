"""A serving cell end to end on the CPU at a tiny size: the result line
as the contract has it, ``correct`` from the reference, and a token
altered where the decode step makes it turning ``correct`` false."""
import time

import numpy as np
import pytest

from bench import faults, spec
from bench.harness import Ctx
from bench.runners import serve
from bench.tests import tiny


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("bench")
    tiny.make(tmp)
    return tmp


def test_decode_line_matches_the_contract(copy):
    out = tiny.run(copy, "tiny-decode")
    assert list(out)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(out)[-1] == "checks"
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"setup_s", "serve_tok_s"}
    assert out["metrics"]["serve_tok_s"]["unit"] == "tokens/s"
    assert out["metrics"]["serve_tok_s"]["value"] > 0
    assert set(out["device"]) >= {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    gap = out["checks"]["max_logit_gap"]
    assert 0 <= gap["value"] <= gap["limit"]


def test_traced_decode_reports_per_layer_metrics(copy):
    out = tiny.run(copy, "tiny-decode", trace=True)
    assert out["correct"] is True
    assert {"slot_occupancy.decode", "decode_step_ms.decode",
            "mfu.decode"} <= set(out["metrics"])
    assert 0 < out["metrics"]["mfu.decode"]["value"] <= 100
    assert {"busy_s", "window_s"} <= set(out["device"])
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}


def test_altered_token_is_not_correct(copy):
    with faults.token_altered():
        out = tiny.run(copy, "tiny-decode")
    assert out["correct"] is False
    gap = out["checks"]["max_logit_gap"]
    assert gap["value"] > gap["limit"]


def test_too_few_checked_tokens_is_not_correct(copy):
    bench = copy / "bench"
    ctx = Ctx(workload="tiny-decode", cfg=spec.config("tiny", bench),
              mix=spec.traffic("tiny-decode", bench),
              limits=spec.limits("tiny-decode", bench), seed=1, seconds=1.0,
              trace=False, chips=1, t_start=time.monotonic())
    need = ctx.mix["check_tokens"]
    assert serve.judge(ctx, [np.zeros(need)])[1] is True
    checks, ok = serve.judge(ctx, [np.zeros(need - 1)])
    assert ok is False and checks["checked_tokens"]["value"] == need - 1
    assert serve.judge(ctx, [])[1] is False

