"""Trace reduction on hand-made planes and on a recorded trace, and the
peaks table."""
import json
from pathlib import Path

import pytest

from bench import cell, trace

DATA = Path(__file__).parent / "data"


def _planes():
    ops = [("fusion.1", 0.0, 1.0), ("all-gather.2", 0.5, 2.0),
           ("xent_fwd", 3.0, 4.0), ("all-reduce.3", 5.0, 6.0),
           ("adamw_update", 6.0, 8.0)]
    host = {"python": [("decode_step", 2.0, 3.0), ("prefill", 4.0, 5.0)]}
    return {"/device:TPU:0": {"XLA Ops": ops},
            "/host:CPU": host}


def test_reduce_hand_made_planes():
    r = trace.reduce(_planes(), kernels=["xent_fwd", "adamw_update"],
                     spans=["decode_step", "prefill"])
    assert r["window_s"] == 8.0
    # busy: [0, 2] + [3, 4] + [5, 8] = 6 of 8 seconds
    assert r["busy_s"] == pytest.approx(6.0)
    assert r["idle_share"] == pytest.approx(0.25)
    assert r["kernel_s"] == {"xent_fwd": 1.0, "adamw_update": 2.0}
    # collectives 1.5 + 1.0 s; the all-gather overlaps compute for 0.5 s
    assert r["collective_s"] == pytest.approx(2.5)
    assert r["exposed_collective_s"] == pytest.approx(2.0)
    assert dict(r["idle_gaps"]) == {"decode_step": 1.0, "prefill": 1.0}
    assert r["device_ops"][0] == ("adamw_update", 2.0)


def test_interval_algebra():
    assert trace.merge([(3, 4), (0, 2), (1, 3)]) == [(0, 4)]
    assert trace.subtract([(0, 10)], [(2, 3), (5, 7)]) == \
        [(0, 2), (3, 5), (7, 10)]
    assert trace.clip([(0, 5), (6, 9)], 1, 7) == [(1, 5), (6, 7)]


def test_recorded_trace():
    path = DATA / "decode.xplane.pb"
    want = json.loads((DATA / "decode.expected.json").read_text())
    planes = trace.load(str(path))
    r = trace.reduce(planes, spans=["decode_step"])
    assert trace.device_planes(planes) == want["devices"]
    for key in ("window_s", "busy_s", "idle_share"):
        assert r[key] == pytest.approx(want[key], rel=1e-9)
    assert 0 < r["busy_s"] <= r["window_s"]


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no peaks"):
        cell.peaks_for("TPU v99")
    assert cell.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
