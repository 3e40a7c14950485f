"""The harness finds a cell's files by name, picks up new ones without
an edit to any file already there, makes traffic from the seed alone,
and refuses to run without a TPU."""
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from bench import gen, spec
from bench.tests import tiny

ROOT = spec.ROOT


def test_every_manifest_name_has_its_files():
    man = spec.manifest()
    for c in man["configs"]:
        assert (ROOT / c["file"]).is_file()
        assert spec.config(c["name"])["source"] == c["source"]
    for w in man["workloads"]:
        mix = spec.traffic(w["traffic"])
        assert (spec.BENCH_DIR / "runners" / f"{mix['runner']}.py").is_file()
        assert spec.limits(w["name"])
    for m in man["end_to_end"] + man["per_layer"]:
        assert callable(spec.module("metrics", m["name"]).read)


def test_new_files_are_picked_up_without_edits(tmp_path):
    bench = tiny.make(tmp_path)
    before = {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}
    (bench / "configs" / "tiny2.json").write_text(
        (bench / "configs" / "tiny.json").read_text())
    (bench / "traffic" / "tiny2-mix.json").write_text(
        (bench / "traffic" / "tiny-decode.json").read_text())
    (bench / "limits" / "tiny2-cell.json").write_text(
        (bench / "limits" / "tiny-decode.json").read_text())
    (bench / "metrics" / "answer.tiny2.py").write_text(
        "def read(run, ctx):\n    return 42.0\n")
    man = json.loads((tmp_path / "BENCHMARK.json").read_text())
    man["workloads"].append({"name": "tiny2-cell", "config": "tiny2",
                             "traffic": "tiny2-mix", "chips": 1,
                             "why": "test"})
    man["per_layer"].append({"name": "answer.tiny2", "unit": "count",
                             "better": "higher", "source": "host_clock",
                             "layer": "test", "moves": "serve_tok_s",
                             "workloads": ["tiny2-cell"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(man))
    man = spec.manifest(tmp_path)
    w = spec.workload(man, "tiny2-cell")
    assert spec.config(w["config"], bench)["num_hidden_layers"] == 2
    assert spec.traffic(w["traffic"], bench)["runner"] == "serve"
    assert spec.limits("tiny2-cell", bench)
    names = [m["name"] for m in spec.cell_metrics(man, "tiny2-cell", True)]
    assert "answer.tiny2" in names
    assert spec.module("metrics", "answer.tiny2", bench).read(None, None) \
        == 42.0
    for p, data in before.items():
        assert p.read_bytes() == data, f"{p} was edited"


def test_missing_file_is_named():
    with pytest.raises(spec.SpecError, match="no-such-mix"):
        spec.traffic("no-such-mix")


@pytest.mark.parametrize("name", ["decode-batch"])
def test_traffic_is_a_function_of_the_seed(name):
    mix = spec.traffic(name)
    mix["pool"] = 256
    a = gen.serve_requests(mix, 1000, 2 ** 40 + 3)
    b = gen.serve_requests(mix, 1000, 2 ** 40 + 3)
    c = gen.serve_requests(mix, 1000, 7)
    assert a == b
    assert [r["prompt"] for r in a] != [r["prompt"] for r in c]
    block = mix["output"]["block"]
    n = len(a) // block * block
    for i in range(0, n, block):
        assert sorted(r["max_new_tokens"] for r in a[i:i + block]) == \
            sorted(r["max_new_tokens"] for r in c[i:i + block])
    lo, hi = mix["output"]["min"], mix["output"]["max"]
    assert all(lo <= r["max_new_tokens"] <= hi for r in a)
    assert len({tuple(r["prompt"]) for r in a}) == len(a)


def test_zipf_batches_match_the_program_pipeline():
    from repro.data.tokens import TokenPipeline
    pipe = TokenPipeline(512, 32, 2, seed=99)
    for i in range(3):
        ours = gen.zipf_batch(512, 32, 2, 99, i)
        theirs = pipe._host_batch(i)
        for k in ("tokens", "labels"):
            np.testing.assert_array_equal(ours[k], theirs[k])


def _cell(cwd, env):
    return subprocess.run(
        [sys.executable, "bench/cell.py", "--workload", "serve-phi4-decode",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = _cell(ROOT, env)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "no TPU" in r.stderr


def test_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(spec.BENCH_DIR, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    r = _cell(tmp_path, env)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
