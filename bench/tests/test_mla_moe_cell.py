"""The Kimi K2 serving cell end to end on the CPU at a tiny size (the
code paths are the cell's own): its files found by the names in the
manifest, the result line with ``correct`` from the reference, the
traced per-layer metrics, a token altered where the decode step makes it
turning ``correct`` false; and the step's counts of operations and bytes
against hand counts."""
import copy
import dataclasses
import json

import numpy as np
import pytest

from bench import faults, faults_mla_moe, flops_mla_moe, spec
from bench.model_mla_moe import Dims
from bench.runners import serve_mla_moe
from bench.tests import tiny

CELL, CONFIG, MIX = ("serve-kimi-k2-reasoning", "kimi-k2-instruct-ep48",
                     "reasoning-batch")
K2_METRICS = {"mfu.k2", "moe_ms.k2", "mla_ms.k2", "experts_hit.k2",
              "latent_pass_ms.k2", "round_trip_idle_ms.k2",
              "sched_idle_ms.k2", "stall_share.k2"}
# the serving loop's and the step's metrics, read as serve-phi4-decode's
DECODE_METRICS = {"decode_step_ms.decode", "idle_share.decode",
                  "slot_occupancy.decode"}

TINY = {"hidden_size": 64, "num_attention_heads": 4,
        "num_key_value_heads": 4, "q_lora_rank": 32, "kv_lora_rank": 16,
        "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
        "intermediate_size": 96, "moe_intermediate_size": 32,
        "num_hidden_layers": 3, "n_routed_experts": 4,
        "expert_parallel": 16, "num_experts_per_tok": 4, "vocab_size": 512,
        "published": {"num_hidden_layers": 61, "n_routed_experts": 64},
        "torch_dtype": "float32"}

# limits for this size, in float32, where the program serves the
# reference's best token and routes as it does (seeds 1-3: widest gap 0,
# no flip; the fp8 control's gap 0.47-1.67).  The bias left out of the
# choice departs by the margin or more (36-47 misses, widest gap
# 0.40-0.89); logits rounded to bf16 depart by less and flip 0.4-1.4% of
# the pairs
LIMITS = {"max_logit_gap": {"limit": 0.01}, "route_margin": {"value": 0.005},
          "route_flip_share": {"limit": 0.002}, "route_misses": {"limit": 0}}


def make(tmp):
    """tiny.make's copy, plus a tiny cell of this configuration and mix
    under the name tiny-k2, which every k2 metric lists."""
    bench = tiny.make(tmp)
    cfg = spec.config(CONFIG)
    cfg.update(copy.deepcopy(TINY))
    (bench / "configs" / "tiny-k2.json").write_text(json.dumps(cfg))
    mix = spec.traffic(MIX)
    mix.update(pool=256, prompt_len=16, cache_len=64, slots=4,
               check_tokens=60, trace_seconds=1)
    mix["output"].update(median=20, min=4, max=48, block=16)
    (bench / "traffic" / "tiny-k2.json").write_text(json.dumps(mix))
    (bench / "limits" / "tiny-k2.json").write_text(json.dumps(LIMITS))
    man = json.loads((tmp / "BENCHMARK.json").read_text())
    man["workloads"].append({"name": "tiny-k2", "config": "tiny-k2",
                             "traffic": "tiny-k2", "chips": 1, "why": "test"})
    real = spec.manifest()
    for group in ("end_to_end", "per_layer"):
        names = {m["name"] for m in man[group]}
        for m in real[group]:
            if CELL in m.get("workloads", ()):
                if m["name"] not in names:
                    man[group].append(dict(m, workloads=[]))
                for n in man[group]:
                    if n["name"] == m["name"]:
                        n["workloads"].append("tiny-k2")
    (tmp / "BENCHMARK.json").write_text(json.dumps(man))
    return bench


@pytest.fixture(scope="module")
def copy_dir(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("bench-k2")
    make(tmp)
    return tmp


def test_the_cell_is_found_by_its_names():
    man = spec.manifest()
    w = spec.workload(man, CELL)
    assert (w["config"], w["traffic"], w["chips"]) == (CONFIG, MIX, 1)
    mix = spec.traffic(w["traffic"])
    assert spec.module("runners", mix["runner"]).run
    cfg = spec.config(w["config"])
    assert {"num_hidden_layers", "n_routed_experts"} == set(cfg["reduced"])
    names = {m["name"] for m in spec.cell_metrics(man, CELL, True)}
    assert names == K2_METRICS | DECODE_METRICS
    assert {m["name"] for m in spec.cell_metrics(man, CELL, False)} == \
        {"setup_s", "serve_tok_s"}
    assert set(spec.limits(CELL)) == set(LIMITS)


def test_the_configuration_keeps_the_published_widths():
    """Every key of the published config.json is in the file with its
    published value, but the two cuts, whose published values stand
    beside them; the router keeps its 384 outputs."""
    c = spec.config(CONFIG)
    d = Dims.of(c)
    assert (d.d_model, d.heads, d.q_rank, d.kv_rank, d.nope, d.rope,
            d.v_dim) == (7168, 64, 1536, 512, 128, 64, 128)
    assert (d.d_ff, d.expert_ff, d.experts, d.top_k, d.shared,
            d.vocab) == (18432, 2048, 384, 8, 1, 163840)
    assert (d.dense_layers, d.moe_layers, d.held) == (1, 4, 8)
    assert c["published"] == {"num_hidden_layers": 61,
                              "n_routed_experts": 384}
    assert c["expert_parallel"] * c["n_routed_experts"] == 384


def test_decode_line_matches_the_contract(copy_dir):
    out = tiny.run(copy_dir, "tiny-k2")
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"setup_s", "serve_tok_s"}
    assert out["metrics"]["serve_tok_s"]["value"] > 0
    checks = out["checks"]
    assert set(checks) == {"max_logit_gap", "route_flip_share",
                           "route_misses", "checked_tokens"}
    assert 0 <= checks["max_logit_gap"]["value"] <= LIMITS[
        "max_logit_gap"]["limit"]
    assert checks["route_misses"]["value"] == 0
    assert checks["checked_tokens"]["value"] >= 60


def test_traced_decode_reports_the_k2_metrics(copy_dir):
    out = tiny.run(copy_dir, "tiny-k2", trace=True)
    assert out["correct"] is True, out["checks"]
    got = out["metrics"]
    # no TPU plane in a CPU trace: the device readers read nothing
    assert {"decode_step_ms.decode", "mfu.k2", "experts_hit.k2",
            "slot_occupancy.decode", "stall_share.k2"} <= set(got)
    assert 0 < got["mfu.k2"]["value"] <= 100
    assert 0 < got["experts_hit.k2"]["value"] <= 4          # 4 held


def test_altered_token_is_not_correct(copy_dir):
    with faults.token_altered():
        out = tiny.run(copy_dir, "tiny-k2")
    assert out["correct"] is False
    gap = out["checks"]["max_logit_gap"]
    assert gap["value"] > gap["limit"]


@pytest.mark.parametrize("fault", sorted(faults_mla_moe.FAULTS))
def test_router_fault_is_not_correct(copy_dir, fault):
    """The router choosing without the correction bias departs from the
    reference's choice by the margin or more; from logits rounded to
    bf16 it flips choices at near-ties that the float32 program never
    flips."""
    with faults_mla_moe.FAULTS[fault]():
        out = tiny.run(copy_dir, "tiny-k2")
    assert out["correct"] is False
    checks = out["checks"]
    if fault == "bias_left_out":
        assert checks["route_misses"]["value"] > 0
    else:
        flips = checks["route_flip_share"]
        assert flips["value"] > flips["limit"]


def test_served_records_follow_each_slot():
    """Tokens are kept per request, in the order the slot made them, with
    the router's choice at each position; a slot parked or taken by
    another request stops its record."""
    s = serve_mla_moe.Served(2)
    pre = np.arange(2 * 2 * 1 * 2).reshape(1, 2 * 2, 1, 2)  # (1, P, L, k)
    s.prefilled(0, [5, 6], 9, 2, pre[:, :2])
    s.prefilled(1, [7, 8], 3, 2, pre[:, 2:])
    steps = [100 + 10 * t + np.arange(2 * 2).reshape(2, 1, 1, 2)
             for t in range(3)]                           # (B, 1, L, k)
    s.decoded([2, 2], np.array([10, 11]), steps[0])
    s.decoded([3, 0], np.array([12, 13]), steps[1])      # slot 1 parked
    s.decoded([4, 3], np.array([14, 15]), steps[2])
    assert [r["tokens"] for r in s.requests] == [[9, 10, 12, 14], [3, 11]]
    got = s.routes(s.requests[0])
    assert got.shape == (5, 1, 2)       # 2 prompt positions, 3 decoded
    assert got[:, 0].tolist() == [[0, 1], [2, 3], [100, 101], [110, 111],
                                  [120, 121]]
    assert s.routes(s.requests[1])[:, 0].tolist() == [[4, 5], [6, 7],
                                                      [102, 103]]


def test_steady_lengths_follow_the_remainder():
    """Remaining lengths of slots in a long-run closed loop: a request is
    met in proportion to its length, at an even point along it."""
    rng = np.random.default_rng(0)
    got = serve_mla_moe.steady_lengths([4, 4, 4, 4], 8, rng)
    assert sorted(got.tolist()) == [1, 1, 2, 2, 3, 3, 4, 4]
    # lengths 1 and 3: 3 of the 4 slot-steps lie in the long one
    got = serve_mla_moe.steady_lengths([1, 3], 4, rng)
    assert sorted(got.tolist()) == [1, 1, 2, 3]


def test_judge_holds_flips_and_misses():
    ctx = dataclasses.make_dataclass("C", ["limits", "mix"])(
        LIMITS, {"check_tokens": 4})
    gaps = [np.array([0.001, 0.009, 0.002]), np.array([0.0, 0.003])]
    stood = [{"flips": 1, "misses": 0, "pairs": 600, "departure": 0.001},
             {"flips": 0, "misses": 0, "pairs": 400, "departure": 0.0}]
    checks, ok = serve_mla_moe.judge(ctx, (gaps, stood))
    assert ok and checks["max_logit_gap"]["value"] == pytest.approx(0.009)
    assert checks["route_flip_share"]["value"] == pytest.approx(1 / 1000)
    assert checks["route_misses"]["value"] == 0
    assert not serve_mla_moe.judge(
        ctx, (gaps, [dict(stood[0], misses=1), stood[1]]))[1]
    assert not serve_mla_moe.judge(
        ctx, (gaps, [dict(stood[0], flips=3), stood[1]]))[1]
    gaps[0][1] = 0.02
    assert not serve_mla_moe.judge(ctx, (gaps, stood))[1]


def test_decode_step_counts_by_hand():
    """Kimi K2's cut model, one slot at 1,000 live positions, 8 held
    experts reached per layer."""
    d = Dims.of(spec.config(CONFIG))
    mla = (7168 * 1536 + 1536 * 64 * 192 + 7168 * 576 + 512 * 64 * 256
           + 64 * 128 * 7168)
    assert flops_mla_moe.mla_params(d) == mla == 101_122_048
    expert = 3 * 7168 * 2048
    fixed = (5 * mla + 3 * 7168 * 18432 + 4 * (7168 * 384 + expert)
             + 163840 * 7168)
    assert flops_mla_moe.fixed_params(d) == fixed
    w = flops_mla_moe.decode_step(d, [1000], 8)
    norms = (5 * (2 * 7168 + 1536 + 512) + 7168) * 2
    weights = 2 * (fixed + 4 * 8 * expert) + norms + 4 * 384 * 4
    assert w["bytes"] == weights + 1000 * 5 * 576 * 2 + 7168 * 2
    attend = 2 * 1000 * 64 * 576 + 2 * 1000 * 64 * 512
    per_token = (5 * (2 * mla + attend) + 6 * 7168 * 18432
                 + 4 * (2 * 7168 * 384 + 2 * expert + 8 * 8 / 384 * 2 * expert)
                 + 2 * 163840 * 7168)
    assert w["flops"] == pytest.approx(per_token)
    # 9.7 GB of bf16 weights stored; a step reads all but the embedding
    assert 9.6e9 < weights + 2 * 163840 * 7168 < 9.8e9


def test_scope_readers_split_the_step(monkeypatch):
    """The device readers sum the step's ops by scope: the latent passes
    (``kv_*``, also inside ``mla``), MLA less them, and the expert
    layers; per execution of the step."""
    from bench import scopes
    ops = [("a", "paged_step/while/body/mla/kv_write", 2e-3),
           ("b", "paged_step/while/body/mla", 5e-3),
           ("c", "paged_step/while/body/kv_gather", 3e-3),
           ("d", "paged_step/kv_scatter", 1e-3),
           ("e", "paged_step/while/body/router", 1e-3),
           ("f", "paged_step/while/body/experts", 4e-3),
           ("g", "paged_step/while/body/shared_expert", 2e-3),
           ("h", "paged_step/lm_head", 6e-3)]
    monkeypatch.setattr(scopes, "_planes", lambda run: {})
    monkeypatch.setattr(scopes, "_run_ops", lambda path, module: (ops, 2))
    run = {"kind": "serve", "trace_path": "t"}
    got = {name: spec.module("metrics", name).read(run, None)
           for name in ("latent_pass_ms.k2", "mla_ms.k2", "moe_ms.k2")}
    assert got == pytest.approx({"latent_pass_ms.k2": 3.0,
                                 "mla_ms.k2": 2.5, "moe_ms.k2": 3.5})
