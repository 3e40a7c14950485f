"""The one traffic generator: reads a mix's parameters, makes requests.

Output lengths come from the mix's own ``shape_seed``, so every run seed
asks for the same set of lengths; the run's ``--seed`` only shuffles
which request gets which length and draws the prompt tokens.  Runs of
different seeds therefore do the same work in another order, and their
spread measures the system, not the draw.

The lognormal lengths follow ``repro.scenarios.traffic``; they are
copied here so that the yardstick cannot move with the program.
"""
from __future__ import annotations

import math
from typing import Any, Dict, List

import numpy as np

_PROMPTS, _LENGTHS = 101, 401


def _rng(seed: int, stream: int) -> np.random.RandomState:
    return np.random.RandomState((seed * 1_000_003 + stream) % (2 ** 31 - 1))


def run_rng(seed: int, stream: int) -> np.random.Generator:
    """A stream of the run's own ``--seed`` (any non-negative integer)."""
    return np.random.default_rng([int(seed), stream])


def lognormal_lengths(n: int, median: float, sigma: float, lo: int,
                      hi: int, seed: int) -> np.ndarray:
    """``n`` lognormal lengths of the given median, clipped to [lo, hi]."""
    draws = _rng(seed, _LENGTHS).lognormal(math.log(median), sigma, size=n)
    return np.clip(draws.astype(np.int64), lo, hi)


def prompts(n: int, length: int, vocab: int, seed: int) -> np.ndarray:
    """``n`` distinct random prompts of ``length`` tokens, ids in
    [1, vocab): distinct so that no prompt shares a cached prefix."""
    rng = run_rng(seed, _PROMPTS)
    out = rng.integers(1, vocab, size=(n, length), dtype=np.int64)
    if len({row.tobytes() for row in out}) != n:
        raise ValueError("prompt draw repeated a prompt")
    return out.astype(np.int32)


def serve_requests(mix: Dict[str, Any], vocab: int, seed: int
                   ) -> List[Dict[str, Any]]:
    """The run's ``pool`` requests, each with its prompt and stop length,
    in the order the runner releases them as its backlog drains."""
    out_len = mix["output"]
    n = int(mix["pool"])
    lengths = block_lengths(n, int(out_len["block"]), out_len,
                            int(mix["shape_seed"]), seed)
    toks = prompts(n, int(mix["prompt_len"]), vocab, seed)
    return [{"id": i, "prompt": toks[i].tolist(),
             "max_new_tokens": int(lengths[i])} for i in range(n)]


def block_lengths(n: int, block: int, dist: Dict[str, Any], shape_seed: int,
                  seed: int) -> np.ndarray:
    """``n`` output lengths: one lognormal set of ``block`` lengths from
    ``shape_seed``, dealt out again in each run of ``block`` requests in
    an order drawn from ``seed``.  Any ``block`` consecutive requests ask
    for the same lengths, whatever the seed."""
    base = lognormal_lengths(block, dist["median"], dist["sigma"],
                             dist["min"], dist["max"], shape_seed)
    rng = run_rng(seed, _LENGTHS)
    reps = -(-n // block)
    return np.concatenate([base[rng.permutation(block)]
                           for _ in range(reps)])[:n]


def zipf_batch(vocab: int, seq: int, batch: int, seed: int, index: int
               ) -> Dict[str, np.ndarray]:
    """Training batch ``index`` of the synthetic token stream: Zipf
    unigrams with first-order structure, as ``repro.data.tokens``
    generates them.  The benchmark makes its own copy to check what the
    trainer was fed, and to give the reference the same rows."""
    rng = np.random.RandomState((seed * 1_000_003 + index) % 2 ** 31)
    base = rng.zipf(1.3, size=(batch, seq + 1)).astype(np.int64)
    tok = (base + 7919 * np.roll(base, 1, axis=1)) % max(vocab - 2, 1) + 1
    tok = tok.astype(np.int32)
    return {"tokens": tok[:, :seq], "labels": tok[:, 1:seq + 1]}
