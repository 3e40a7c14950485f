"""A copy of the benchmark at a size a CPU test can run.

``make(tmp)`` copies ``bench/`` into ``tmp/bench`` and writes a tiny
configuration, tiny mixes, limits for them and a manifest naming two
cells, ``tiny-decode`` and ``tiny-train``, in ``tmp``.  Widths are
small; the code paths are the cells' own.
"""
from __future__ import annotations

import json
import shutil
from pathlib import Path

from bench import spec

CONFIG = {"hidden_size": 64, "intermediate_size": 128,
          "num_attention_heads": 4, "num_key_value_heads": 2,
          "head_dim": 16, "num_hidden_layers": 2, "vocab_size": 512}

# limits for this size, read from its own runs: the program's widest
# logit gap 0-0.011 (the fp8 control's 0.041-0.080), its loss gap about
# 3e-5, leaf gaps about 5e-3, its first gradient's widest leaf error
# 0.021-0.023 (the fp8 control's 0.19-0.24)
LIMITS = {
    "tiny-decode": {"max_logit_gap": {"limit": 0.025}},
    "tiny-train": {"loss_gap": {"limit": 1e-3}, "grad_gap": {"limit": 0.05},
                   "grad_err": {"limit": 0.08},
                   "change_gap": {"limit": 0.05}},
}


def _write(path: Path, obj) -> None:
    path.write_text(json.dumps(obj, indent=1))


def make(tmp: Path) -> Path:
    """Returns the bench directory of the copy (the manifest is beside
    it)."""
    bench = tmp / "bench"
    shutil.copytree(spec.BENCH_DIR, bench,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    cfg = spec.config("phi4-mini-3.8b")
    cfg.update(CONFIG)
    _write(bench / "configs" / "tiny.json", cfg)
    mix = spec.traffic("decode-batch")
    mix.update(pool=512, prompt_len=16, cache_len=64, slots=4,
               check_tokens=60, trace_seconds=1)
    mix["output"].update(median=20, min=4, max=48, block=16)
    _write(bench / "traffic" / "tiny-decode.json", mix)
    train = spec.traffic("train-seq2048")
    train.update(seq_len=64, trace_steps=3)
    _write(bench / "traffic" / "tiny-train.json", train)
    for name, lim in LIMITS.items():
        _write(bench / "limits" / f"{name}.json", lim)
    man = spec.manifest()
    man["configs"] = [{"name": "tiny", "source": "test",
                       "file": "bench/configs/tiny.json", "reduced": [],
                       "why": "test"}]
    man["workloads"] = [
        {"name": "tiny-decode", "config": "tiny", "traffic": "tiny-decode",
         "chips": 1, "why": "test"},
        {"name": "tiny-train", "config": "tiny", "traffic": "tiny-train",
         "chips": 1, "why": "test"}]
    for group in ("end_to_end", "per_layer"):
        for m in man[group]:
            if "workloads" in m:
                m["workloads"] = ["tiny-decode" if "decode" in w
                                  else "tiny-train" for w in m["workloads"]
                                  if "decode" in w or "train" in w]
    _write(tmp / "BENCHMARK.json", man)
    return bench


def run(tmp: Path, workload: str, seconds: float = 2.0, trace=False,
        seed: int = 2 ** 33 + 5):
    """One run of a tiny cell on the CPU, with the compilation cache
    left as the test process has it."""
    import time
    from unittest import mock

    from bench import cell
    with mock.patch.object(cell, "enable_cache", lambda: "off"):
        return cell.run_cell(workload, seed, seconds, trace, root=tmp,
                             bench_dir=tmp / "bench", check_device=False,
                             t_start=time.monotonic())
