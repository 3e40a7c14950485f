"""Reading the program's scopes and spans from hand-made trace planes:
idle gaps cut by the spans they intersect (innermost first), device time
summed by scope, nothing read where the op map covers too little, and
each new reader silent for the other kind of cell."""
import time
from types import SimpleNamespace

import pytest

from bench import scopes, spec

MODULE = scopes.DECODE_STEP


def _planes(ops, host=(), modules=((0.0, 10.0),)):
    return {"/device:TPU:0": {
                "XLA Modules": [(f"{MODULE}(123)", s, e) for s, e in modules],
                "XLA Ops": list(ops)},
            "/host:CPU": {"python3": list(host)}}


def test_gap_is_cut_by_intersecting_spans_not_by_midpoint():
    ops = [("%fusion.1", 0.0, 2.0), ("%fusion.2", 4.0, 5.0),
           ("%fusion.3", 9.0, 10.0)]
    host = [("serve/decode", 0.5, 4.5), ("serve/upload", 1.0, 3.0),
            ("serve/readback", 3.0, 4.5), ("serve/schedule", 5.0, 8.5),
            ("serve/prefill", 6.0, 7.0), ("decode_step", 0.0, 5.0)]
    idle = scopes.idle_by_span(_planes(ops, host))
    # gap [2, 4): 1 s in upload, 1 s in readback (its midpoint, 3, would
    # have given both to one); gap [5, 9): prefill 1 s inside schedule's
    # 2.5 s, 0.5 s in no program span
    assert idle == pytest.approx({"serve/upload": 1.0,
                                  "serve/readback": 1.0,
                                  "serve/prefill": 1.0,
                                  "serve/schedule": 2.5, "host": 0.5})
    assert scopes.labelled([("a", 0.0, 4.0), ("b", 1.0, 2.0)]) == [
        (0.0, 1.0, "a"), (1.0, 2.0, "b"), (2.0, 4.0, "a")]


def test_no_program_span_reads_nothing():
    assert scopes.idle_by_span(_planes([("%fusion.1", 0.0, 1.0)],
                                       [("decode_step", 0.0, 1.0)])) is None


OPS = [("%fusion.1 = bf16[2] fusion(%p)", 0.0, 2.0),      # kv_gather
       ("%fusion.2 = bf16[2] fusion(%q)", 2.0, 3.0),      # kv_write
       ("%while.1 = (s32[]) while(%t)", 3.0, 6.0),        # spans its body
       ("%dot.1 = bf16[2] dot(%a, %b)", 3.0, 5.0),        # attn, in the loop
       ("%fusion.3 = bf16[2] fusion(%r)", 6.0, 7.0),      # kv_scatter
       ("%xent_fwd.1 = f32[2] custom-call(%s)", 7.0, 8.0),
       ("%fusion.4 = f32[2] fusion(%u)", 8.0, 9.5),       # lm_head
       ("%fusion.9 = f32[2] fusion(%v)", 11.0, 12.0)]     # another module
SCOPES = {"fusion.1": "paged_step/kv_gather",
          "fusion.2": "paged_step/while/body/attn/kv_write",
          "while.1": "paged_step", "dot.1": "paged_step/while/body/attn",
          "fusion.3": "paged_step/kv_scatter",
          "xent_fwd.1": "paged_step/lm_head",
          "fusion.4": "paged_step/lm_head"}


def test_device_time_by_scope():
    ops, runs = scopes.step_ops(_planes(OPS), MODULE, SCOPES)
    assert runs == 1
    by = {}
    for name, path, t in ops:
        by[name] = (path, t)
    assert "fusion.9" not in by                # outside the module's run
    assert by["while.1"][1] == pytest.approx(1.0)     # self time only
    kv = sum(t for _, path, t in ops
             if scopes.in_scope(path, ("kv_gather", "kv_write",
                                       "kv_scatter")))
    assert kv == pytest.approx(4.0)
    assert not scopes.in_scope("paged_step/attn", ("at",))


def test_under_covered_module_reads_nothing():
    partial = {k: v for k, v in SCOPES.items() if k != "fusion.4"}
    # fusion.4 is 1.5 of 9.5 s: the map is not the program that ran
    assert scopes.step_ops(_planes(OPS), MODULE, partial) is None
    almost = dict(SCOPES)
    del almost["fusion.4"]
    ops = OPS[:-2] + [("%fusion.4 = f32[2] fusion(%u)", 8.0, 8.05)]
    assert scopes.step_ops(_planes(ops), MODULE, almost) is not None
    assert scopes.step_ops(_planes(OPS, modules=()), MODULE, SCOPES) is None


def _reader(name):
    return spec.module("metrics", name)


def test_readers_sum_by_scope_and_span_per_step(monkeypatch):
    ops = OPS[:-1] + [("%fusion.5 = f32[2] fusion(%w)", 10.5, 11.0)]
    host = [("serve/upload", 9.5, 10.0), ("serve/schedule", 10.0, 10.5)]
    planes = _planes(ops, host, modules=((0.0, 9.5), (10.5, 11.0)))
    monkeypatch.setattr(scopes, "load", lambda path: planes)
    monkeypatch.setattr(scopes, "program_scopes",
                        lambda module: dict(SCOPES, **{"fusion.5": ""}))
    run = {"kind": "serve", "trace_path": f"hand-made-{time.monotonic()}"}
    assert _reader("kv_pass_ms.decode").read(run, None) == \
        pytest.approx(1e3 * 4.0 / 2)
    assert _reader("round_trip_idle_ms.decode").read(run, None) == \
        pytest.approx(1e3 * 0.5 / 2)
    assert _reader("sched_idle_ms.decode").read(run, None) == \
        pytest.approx(1e3 * 0.5 / 2)


def test_head_reader_leaves_out_the_xent_kernels(monkeypatch):
    planes = _planes(OPS, modules=())
    planes["/device:TPU:0"]["XLA Modules"] = [
        (f"{scopes.TRAIN_STEP}(7)", 0.0, 10.0)]
    monkeypatch.setattr(scopes, "load", lambda path: planes)
    monkeypatch.setattr(scopes, "program_scopes", lambda module: SCOPES)
    run = {"kind": "train", "trace_path": f"hand-made-{time.monotonic()}"}
    ctx = SimpleNamespace(mix={"device_steps": 1})
    assert _reader("head_ms.train").read(run, ctx) == pytest.approx(1.5e3)


def test_stall_share_from_the_engine_step_spans(capsys):
    from repro.core.metrics import Registry
    now = [0.0]
    reg = Registry(clock=lambda: now[0])
    for wall in [1.0] * 9:
        with reg.span("serve/decode"):
            now[0] += wall
    with reg.span("serve/decode", gc_s=0.5):     # as SlowSteps marks it
        with reg.span("serve/readback"):
            now[0] += 11.0
    run = {"kind": "serve", "registry": reg, "t0": 0.0, "t_end": 100.0,
           "seconds": 100.0}
    # one step 10 s over the median of 1 s, in a 100 s window
    assert _reader("stall_share.decode").read(run, None) == \
        pytest.approx(10.0)
    assert "slow step: wall_s=11 serve/readback=11 gc_s=0.5" in \
        capsys.readouterr().err
    run["registry"] = object()                # a program without spans
    assert _reader("stall_share.decode").read(run, None) is None


@pytest.mark.parametrize("name, other", [
    ("kv_pass_ms.decode", "train"), ("round_trip_idle_ms.decode", "train"),
    ("sched_idle_ms.decode", "train"), ("stall_share.decode", "train"),
    ("head_ms.train", "serve")])
def test_reader_is_silent_for_the_other_kind(name, other):
    assert _reader(name).read({"kind": other, "trace_path": "x"},
                              None) is None


def test_new_metrics_are_in_the_manifest():
    man = spec.manifest()
    per_layer = {m["name"]: m for m in man["per_layer"]}
    for name, cell in [("kv_pass_ms.decode", "serve-phi4-decode"),
                       ("round_trip_idle_ms.decode", "serve-phi4-decode"),
                       ("sched_idle_ms.decode", "serve-phi4-decode"),
                       ("stall_share.decode", "serve-phi4-decode"),
                       ("head_ms.train", "train-phi4-1chip")]:
        assert per_layer[name]["workloads"] == [cell]
        assert (spec.BENCH_DIR / "metrics" / f"{name}.py").is_file()
