"""A training cell end to end on the CPU at a tiny size: ``correct``
from the reference over the checked steps, and each fault a training
cell can have turning it false; set-up that outlasts its limit fails."""
import json
import threading

import pytest

from bench import faults
from bench.tests import tiny


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("bench")
    tiny.make(tmp)
    return tmp


def test_train_cell_is_correct(copy):
    out = tiny.run(copy, "tiny-train")
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"setup_s", "train_tok_s"}
    assert out["checks"]["feed"]["value"] == 1
    for name in ("loss_gap", "grad_gap", "grad_err", "change_gap"):
        c = out["checks"][name]
        assert 0 <= c["value"] <= c["limit"], name


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_fault_is_not_correct(copy, fault):
    with faults.FAULTS[fault]():
        out = tiny.run(copy, "tiny-train")
    assert out["correct"] is False
    assert any(c["value"] > c["limit"] for c in out["checks"].values())


def test_setup_past_its_limit_fails_and_stops_the_trainer(copy):
    m = json.loads((copy / "bench" / "traffic" / "tiny-train.json").read_text())
    (copy / "bench" / "traffic" / "tiny-slow.json").write_text(
        json.dumps(dict(m, setup_limit_s=0.01)))
    man = json.loads((copy / "BENCHMARK.json").read_text())
    man["workloads"].append({"name": "tiny-slow", "config": "tiny",
                             "traffic": "tiny-slow", "chips": 1,
                             "why": "test"})
    (copy / "BENCHMARK.json").write_text(json.dumps(man))
    (copy / "bench" / "limits" / "tiny-slow.json").write_text(
        (copy / "bench" / "limits" / "tiny-train.json").read_text())
    before = threading.active_count()
    with pytest.raises(RuntimeError, match="checked steps done"):
        tiny.run(copy, "tiny-slow")
    assert threading.active_count() <= before
