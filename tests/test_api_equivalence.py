"""A CLI's flag form and its ``--manifest`` form declare the same job and
give the same results — same losses, same generations, same Table-I rows
(modulo wall-clock fields) — on the CPU smoke configs.  Both CLIs run
end to end through ``main()``; an arch the registry does not know is
refused.  Plus the serving-report regression: zero-completed-request
runs still render a row instead of raising."""
import json
import math
import re
import sys

import pytest

from repro.api import ServeJob, TrainJob, from_manifest
from repro.core.metrics import Registry, table_one
from repro.launch import cli
from repro.launch import serve as serve_cli
from repro.launch import train as train_cli
from repro.launch.serve import apply_serve, serve_job
from repro.launch.train import apply_train, train_job
from repro.serving.report import serving_report

ARCH = "phi4-mini-3.8b"

# Table-I fields that are a pure function of the workload (not of the
# wall clock): the equivalence contract compares exactly these.
DETERMINISTIC_ROW_FIELDS = ("requests", "tokens")


def test_train_flag_form_matches_manifest_form():
    flags = train_job(ARCH, steps=6, seq=16, batch=2, smoke=True,
                      log_every=1)
    declared = from_manifest(flags.to_manifest())
    assert isinstance(declared, TrainJob) and declared == flags
    a, b = apply_train(flags), apply_train(declared)
    assert a["losses"] == b["losses"], "identical optimizer trajectory"
    for field in ("steps", "global_batch", "seq_len"):
        assert getattr(a["report"], field) == getattr(b["report"], field)
    assert [s.mesh_shape for s in a["report"].segments] == \
        [s.mesh_shape for s in b["report"].segments]


def test_serve_flag_form_matches_manifest_form():
    flags = serve_job(ARCH, smoke=True, n_requests=4, prompt_len=8, gen=4,
                      batch=2, gen_lens=[4, 2])
    declared = from_manifest(flags.to_manifest())
    assert isinstance(declared, ServeJob) and declared == flags
    a, b = apply_serve(flags), apply_serve(declared)
    assert a["results"] == b["results"], "identical generations"
    for field in DETERMINISTIC_ROW_FIELDS:
        assert a["report"].extra[field] == b["report"].extra[field], field
    # both rows render through the same Table-I machinery
    assert table_one([a["report"]]).splitlines()[0]
    assert table_one([b["report"]]).splitlines()[0]


def test_unregistered_arch_is_refused_by_name():
    """A config does not stand in for the registry: the job's arch names
    the parallelism too, so an arch the registry does not know is
    refused with the registry's error, which names it."""
    from repro.api.runners import train_pieces
    job = TrainJob(name="t", steps=4, arch="lm-20m",
                   config=dict(name="lm-20m", family="dense", num_layers=2,
                               d_model=32, num_heads=2, num_kv_heads=2,
                               d_ff=64, vocab_size=128, head_dim=16))
    with pytest.raises(KeyError, match="lm-20m"):
        train_pieces(job)


SERVE_FLAGS = ["--arch", ARCH, "--smoke", "--requests", "5",
               "--prompt-len", "8", "--gen", "4", "--slots", "2",
               "--spread"]
TRAIN_FLAGS = ["--arch", ARCH, "--smoke", "--steps", "4", "--seq", "16",
               "--batch", "2"]


def _serve_manifest_job():
    # what --spread declares: stop lengths 4, 2, 1, 1, cycled
    return serve_job(ARCH, smoke=True, n_requests=5, prompt_len=8, gen=4,
                     batch=2, gen_lens=[4, 2, 1, 1])


def _train_manifest_job():
    return train_job(ARCH, steps=4, seq=16, batch=2, smoke=True)


def _check_serve_output(out):
    assert "[serve] completed 5 requests" in out
    totals = {line.split(",")[0]: float(line.split(",")[5])
              for line in out.splitlines() if line.startswith("serve/")}
    assert totals["serve/completed"] == 5
    assert totals["serve/tokens_generated"] == 4 + 2 + 1 + 1 + 4
    assert "| requests |" in out.replace("  ", " ")


def _check_train_output(out):
    m = re.search(r"\[train\] loss (\S+) -> (\S+)", out)
    assert m, out
    assert all(math.isfinite(float(x)) for x in m.groups())


@pytest.mark.parametrize("launcher, argv, check", [
    (serve_cli, SERVE_FLAGS, _check_serve_output),
    (serve_cli, _serve_manifest_job, _check_serve_output),
    (train_cli, TRAIN_FLAGS, _check_train_output),
    (train_cli, _train_manifest_job, _check_train_output),
], ids=["serve-flags", "serve-manifest", "train-flags", "train-manifest"])
def test_cli_main_runs_the_smoke_job(launcher, argv, check, tmp_path,
                                     monkeypatch, capsys):
    """Each launcher's ``main()`` runs its job end to end, from flags or
    from a manifest file."""
    monkeypatch.setattr(cli, "enable_compile_cache", lambda: "")
    if callable(argv):
        path = tmp_path / "job.json"
        path.write_text(json.dumps(argv().to_manifest()))
        argv = ["--manifest", str(path)]
    monkeypatch.setattr(sys, "argv", [launcher.__name__] + argv)
    launcher.main()
    check(capsys.readouterr().out)


def test_serving_summary_only_contains_gauge_names():
    from repro.serving.report import serving_summary
    keys = set(serving_summary(Registry()))
    assert all(k.startswith("serve/") for k in keys), keys


def test_serving_report_tolerates_never_recorded_stats():
    """A smoke run with 0 completed requests (or a metrics registry that
    never saw a single serve gauge) still reports a row of zeros."""
    empty = Registry()
    row = serving_report(empty)
    assert row.total_time_s == 0.0
    assert row.extra["requests"] == 0.0
    assert row.extra["p99 latency (s)"] == 0.0
    assert "| requests |" in table_one([row]).replace("  ", " ")

    partial = Registry()                 # wall recorded, nothing completed
    partial.gauge("serve/wall_s", 1.5)
    row2 = serving_report(partial)
    assert row2.total_time_s == 1.5
    assert row2.extra["tokens"] == 0.0
