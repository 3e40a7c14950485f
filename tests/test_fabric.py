"""Multi-site federation tests: topology/link cost model, federated store
replication (dedupe/batching/site loss), locality-aware placement,
federated workflows, and cross-site elastic failover."""
import threading

import numpy as np
import pytest

from repro.core.metrics import table_one
from repro.core.orchestrator import Cluster, JobSpec, PodState
from repro.core.workflow import Step, Workflow
from repro.fabric import Fabric, FederatedStore, PlacementPlanner


def mk_fabric(tmp_path, time_scale=0.0, devs=(2, 1)):
    fabric = Fabric(time_scale=time_scale)
    for i, n in enumerate(devs):
        name = f"s{i}"
        fabric.add_site(name, devices=list(range(n)),
                        store_root=str(tmp_path / name))
    names = list(fabric.sites)
    for i, a in enumerate(names):
        for b in names[i + 1:]:
            fabric.connect(a, b, gbps=1.0, latency_ms=10.0)
    return fabric


# ---------------------------------------------------------------- topology

def test_link_cost_model():
    from repro.fabric import Link
    link = Link("a", "b", gbps=1.0, latency_s=0.01)
    assert link.bytes_per_s == 1e9 / 8
    # 125 MB over 1 Gbps = 1s + latency; batching pays latency once
    assert link.transfer_s(125_000_000) == pytest.approx(1.01)
    assert link.transfer_s(125_000_000, transfers=5) == pytest.approx(1.05)


def test_fabric_transfer_metering(tmp_path):
    fabric = mk_fabric(tmp_path)
    sim = fabric.transfer("s0", "s1", 125_000_000)
    assert sim == pytest.approx(1.01)
    assert fabric.metrics.series("fabric/bytes_moved").total == 125_000_000
    assert fabric.metrics.series("fabric/transfer_s").total == \
        pytest.approx(1.01)
    # same-site moves are free and unmetered
    assert fabric.transfer("s0", "s0", 10**9) == 0.0
    assert fabric.metrics.series("fabric/bytes_moved").total == 125_000_000


def test_fabric_site_tags_and_cross_site_submit(tmp_path):
    fabric = mk_fabric(tmp_path, devs=(2, 1))
    assert fabric.sites["s0"].cluster.site == "s0"
    site, job = fabric.submit("default", JobSpec(
        "probe", lambda ctx: ctx.site, replicas=1, devices_per_pod=2))
    site.cluster.wait(job, timeout=30)
    assert site.name == "s0"            # only s0 has 2 devices
    assert job.results() == ["s0"]      # pods know their site


def test_fail_site_drains_cluster(tmp_path):
    fabric = mk_fabric(tmp_path)
    release = threading.Event()
    site, job = fabric.submit("default", JobSpec(
        "hold", lambda ctx: release.wait(5), replicas=1, devices_per_pod=1))
    fabric.fail_site(site.name)
    release.set()
    assert job.pods[0].state == PodState.FAILED
    assert site.capacity == 0
    with pytest.raises(RuntimeError, match="no live site"):
        fabric.submit("default", JobSpec("x", lambda ctx: 1,
                                         devices_per_pod=2))


def test_fail_site_drains_deviceless_pods(tmp_path):
    """A whole-site outage must drain CPU-only pods too — fail_node's
    per-device drain never sees them."""
    fabric = mk_fabric(tmp_path)
    release = threading.Event()
    site, job = fabric.submit("default", JobSpec(
        "cpu-only", lambda ctx: release.wait(5), replicas=1,
        devices_per_pod=0))
    for _ in range(200):
        if job.pods[0].state == PodState.RUNNING:
            break
        threading.Event().wait(0.01)
    fabric.fail_site(site.name)
    assert job.pods[0].state == PodState.FAILED
    assert job.pods[0].ctx.should_stop()
    release.set()


def test_degrade_link_scales_transfer_cost(tmp_path):
    """A brown-out is live immediately: transfer_s reflects the reduced
    bandwidth in both directions, and restore returns the CONFIGURED
    link exactly."""
    fabric = mk_fabric(tmp_path)
    nbytes = 125_000_000                       # 1s at the configured 1 Gbps
    base = fabric.transfer_s("s0", "s1", nbytes)
    assert base == pytest.approx(1.01)
    fabric.degrade_link("s0", "s1", gbps=0.1)
    assert fabric.transfer_s("s0", "s1", nbytes) == pytest.approx(10.01)
    assert fabric.transfer_s("s1", "s0", nbytes) == pytest.approx(10.01)
    assert fabric.degraded_links() == [("s0", "s1"), ("s1", "s0")]
    assert fabric.metrics.series("fabric/link_degradations").total == 1
    assert fabric.restore_link("s0", "s1") is True
    assert fabric.transfer_s("s0", "s1", nbytes) == pytest.approx(base)
    assert fabric.degraded_links() == []
    assert fabric.restore_link("s0", "s1") is False    # nothing degraded


def test_degrade_link_latency_override_and_validation(tmp_path):
    fabric = mk_fabric(tmp_path)
    fabric.degrade_link("s0", "s1", gbps=1.0, latency_ms=500.0)
    assert fabric.transfer_s("s0", "s1", 0) == pytest.approx(0.5)
    fabric.restore_link("s0", "s1")
    assert fabric.transfer_s("s0", "s1", 0) == pytest.approx(0.01)
    with pytest.raises(ValueError, match="gbps"):
        fabric.degrade_link("s0", "s1", gbps=0.0)
    with pytest.raises(ValueError, match="no link"):
        fabric.degrade_link("s0", "nope", gbps=0.5)
    assert fabric.degraded_links() == []       # failed calls left no residue


def test_double_degrade_restores_first_original(tmp_path):
    fabric = mk_fabric(tmp_path)
    base = fabric.transfer_s("s0", "s1", 125_000_000)
    fabric.degrade_link("s0", "s1", gbps=0.5)
    fabric.degrade_link("s0", "s1", gbps=0.05)  # brown-out worsens
    assert fabric.transfer_s("s0", "s1", 125_000_000) == \
        pytest.approx(20.01)
    fabric.restore_link("s0", "s1")
    # one restore undoes the stack: back to the CONFIGURED gbps
    assert fabric.transfer_s("s0", "s1", 125_000_000) == \
        pytest.approx(base)


def test_restore_site_clears_degraded_links(tmp_path):
    """A site restore is a power-cycle: every degraded link touching the
    site comes back at configured bandwidth."""
    fabric = mk_fabric(tmp_path)
    base = fabric.transfer_s("s0", "s1", 125_000_000)
    fabric.degrade_link("s0", "s1", gbps=0.1)
    fabric.fail_site("s1")
    fabric.restore_site("s1")
    assert fabric.degraded_links() == []
    assert fabric.transfer_s("s0", "s1", 125_000_000) == \
        pytest.approx(base)


def test_planner_routes_around_browned_out_link(tmp_path):
    """The §IV question under chaos: with the data home unable to host,
    a brown-out on one staging route must shift placement to the
    healthy route — and the restore must make both routes equal again."""
    fabric = Fabric()
    fabric.add_site("home", devices=[0], store_root=str(tmp_path / "h"))
    fabric.add_site("s1", devices=[0, 1], store_root=str(tmp_path / "s1"))
    fabric.add_site("s2", devices=[0, 1], store_root=str(tmp_path / "s2"))
    fabric.connect("home", "s1", gbps=10.0, latency_ms=1.0)
    fabric.connect("home", "s2", gbps=10.0, latency_ms=1.0)
    fed = FederatedStore(fabric)
    fed.put("d/x", b"z" * 10_000_000, "home")
    planner = PlacementPlanner(fed)
    # the step needs 2 devices: home can't host, s1/s2 tie on cost
    scores0 = planner.place(["d/x"], devices=2).scores
    assert scores0["s1"] == pytest.approx(scores0["s2"])
    fabric.degrade_link("home", "s1", gbps=0.001)
    p = planner.place(["d/x"], devices=2)
    assert p.site == "s2", f"placed over the browned-out link: {p.scores}"
    assert p.scores["s1"] > p.scores["s2"]
    fabric.restore_link("home", "s1")
    scores2 = planner.place(["d/x"], devices=2).scores
    assert scores2["s1"] == pytest.approx(scores2["s2"])


# ---------------------------------------------------------- federated store

def test_federated_namespace_and_replicate(tmp_path):
    fabric = mk_fabric(tmp_path)
    fed = FederatedStore(fabric)
    fed.put("a/x", b"hello", "s0")
    assert fed.exists("a/x") and fed.where("a/x") == ["s0"]
    assert fed.list("a") == ["a/x"]
    assert not fabric.sites["s1"].store.exists("a/x")
    sim = fed.replicate("a/x", "s1")
    assert sim > 0
    assert fabric.sites["s1"].store.get("a/x") == b"hello"
    assert fed.replicate("a/x", "s1") == 0.0        # already there
    assert fed.where("a/x") == ["s0", "s1"]


def test_replicate_dedupes_inflight(tmp_path):
    """N concurrent replications of one (key, dst) move the bytes ONCE."""
    fabric = mk_fabric(tmp_path)
    fed = FederatedStore(fabric)
    fed.put("big", b"z" * 1000, "s0")
    start = threading.Barrier(4, timeout=10)

    def pull():
        start.wait()
        fed.replicate("big", "s1")

    threads = [threading.Thread(target=pull) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=10)
    assert fabric.metrics.series("fabric/bytes_moved").total == 1000
    assert fed.where("big") == ["s0", "s1"]


def test_replicate_concurrent_billing_exactly_once(tmp_path):
    """Regression (in-flight dedup accounting): N threads replicating the
    same (key, dst) must bill exactly ONE transfer — one bytes_moved
    increment, one link transfer — and EVERY caller must observe the
    replica at dst by the time its replicate() returns."""
    fabric = mk_fabric(tmp_path, time_scale=0.001)   # widen the race window
    fed = FederatedStore(fabric)
    fed.put("hot", b"z" * 100_000, "s0")
    n = 8
    start = threading.Barrier(n, timeout=10)
    observed, errors = [], []

    def pull():
        try:
            start.wait()
            fed.replicate("hot", "s1")
            # the caller's contract: after return, the replica exists
            observed.append("s1" in fed.where("hot") and
                            fabric.sites["s1"].store.exists("hot"))
        except Exception as e:          # pragma: no cover - failure capture
            errors.append(e)

    threads = [threading.Thread(target=pull) for _ in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    assert not errors, errors[:1]
    assert all(observed) and len(observed) == n
    m = fabric.metrics
    assert m.series("fabric/bytes_moved").total == 100_000   # billed once
    assert m.series("fabric/transfers").total == 1
    assert m.series("fabric/link/s0->s1/bytes").total == 100_000
    # the losers of the race were deduped, not re-transferred
    assert m.series("fabric/replicate_dedup").total <= n - 1


def test_replicate_many_batches_latency(tmp_path):
    fabric = mk_fabric(tmp_path)
    fed = FederatedStore(fabric)
    for i in range(8):
        fed.put(f"d/{i}", b"x" * 100, "s0")
    moved, sim = fed.replicate_many([f"d/{i}" for i in range(8)], "s1")
    assert moved == 800
    # one link latency (10ms) for the whole batch, not 8
    per_key = sum(fabric.transfer_s("s0", "s1", 100) for _ in range(8))
    assert sim < per_key
    assert sim == pytest.approx(fabric.transfer_s("s0", "s1", 800))
    # unknown keys (outputs not yet produced) are skipped, not fatal
    assert fed.replicate_many(["nope"], "s1") == (0, 0.0)


def test_put_to_down_site_fails_loudly(tmp_path):
    """Writing at a dead site would be a black hole (its replicas are
    unreadable) — the write must raise, not silently 'succeed'."""
    fabric = mk_fabric(tmp_path)
    fed = FederatedStore(fabric)
    fed.put("a", b"x", "s0")
    fabric.fail_site("s1")
    with pytest.raises(RuntimeError, match="down"):
        fed.put("k", b"x", "s1")
    with pytest.raises(RuntimeError, match="down"):
        fed.replicate("a", "s1")
    with pytest.raises(RuntimeError, match="down"):
        fed.replicate_many(["a"], "s1")


def test_partial_topology_scores_instead_of_crashing(tmp_path):
    """Hub-and-spoke with NO spoke-spoke link: data at one spoke must
    make the other spoke infinitely expensive, not crash place()."""
    fabric = Fabric()
    for name, n in (("hub", 2), ("s1", 2), ("s2", 2)):
        fabric.add_site(name, devices=list(range(n)),
                        store_root=str(tmp_path / name))
    fabric.connect("hub", "s1", gbps=1.0)
    fabric.connect("hub", "s2", gbps=1.0)      # s1 <-> s2: no route
    fed = FederatedStore(fabric)
    fed.put("d/x", b"z" * 1_000_000, "s1")
    planner = PlacementPlanner(fed)
    assert fed.best_src("d/x", "s2") is None   # unreachable, not an error
    p = planner.place(["d/x"])
    assert p.site == "s1"                      # at the data
    assert planner.score(["d/x"], fabric.sites["s2"]) == float("inf")
    # replicate_many skips the stranded key instead of crashing
    assert fed.replicate_many(["d/x"], "s2") == (0, 0.0)
    assert fabric.metrics.series("fabric/missing_key").points


def test_site_loss_hides_replicas_until_restore(tmp_path):
    fabric = mk_fabric(tmp_path)
    fed = FederatedStore(fabric)
    fed.put("only-s1", b"data", "s1")
    fabric.fail_site("s1")
    assert not fed.exists("only-s1")
    assert fed.list() == []
    with pytest.raises(FileNotFoundError):
        fed.get("only-s1")
    fabric.restore_site("s1")
    assert fed.exists("only-s1")


def test_sitestore_pull_through_and_mirror(tmp_path):
    fabric = mk_fabric(tmp_path)
    fed = FederatedStore(fabric)
    fed.view("s0").put_array("data/a.npy", np.arange(4))
    # read at s1 pulls the bytes over the link (metered), then caches
    view1 = fed.view("s1")
    np.testing.assert_array_equal(view1.get_array("data/a.npy"),
                                  np.arange(4))
    moved = fabric.metrics.series("fabric/bytes_moved").total
    assert moved > 0
    view1.get_array("data/a.npy")                   # cached: no new bytes
    assert fabric.metrics.series("fabric/bytes_moved").total == moved
    # mirrored writes replicate matching prefixes synchronously
    mirrored = fed.view("s0", mirror="s1", mirror_prefixes=("checkpoints/",))
    mirrored.put("checkpoints/c1", b"ck")
    mirrored.put("scratch/tmp", b"no")
    assert fed.where("checkpoints/c1") == ["s0", "s1"]
    assert fed.where("scratch/tmp") == ["s0"]
    # namespace-wide delete drops every replica (checkpoint GC contract)
    assert mirrored.delete("checkpoints/c1")
    assert not fed.exists("checkpoints/c1")
    assert not fabric.sites["s1"].store.exists("checkpoints/c1")


# ---------------------------------------------------------------- placement

def test_planner_places_at_the_data(tmp_path):
    fabric = mk_fabric(tmp_path, devs=(2, 2))
    fed = FederatedStore(fabric)
    fed.put("big/blob", b"z" * 10_000_000, "s1")
    p = PlacementPlanner(fed).place(["big/blob"])
    assert p.site == "s1" and p.mode == "data-local"
    assert p.bytes_to_move == 0 and not p.migrated


def test_planner_prestages_when_data_site_lacks_devices(tmp_path):
    fabric = mk_fabric(tmp_path, devs=(4, 1))
    fed = FederatedStore(fabric)
    fed.put("big/blob", b"z" * 10_000_000, "s1")
    p = PlacementPlanner(fed).place(["big/blob"], devices=2)
    assert p.site == "s0" and p.mode == "pre-stage"
    assert p.bytes_to_move == 10_000_000
    assert p.migrated_from == "s1"      # the data home could not host it
    planner = PlacementPlanner(fed)
    moved, sim = planner.prestage(["big/blob"], "s0")
    assert moved == 10_000_000 and sim > 0


def test_planner_queue_depth_breaks_ties(tmp_path):
    fabric = mk_fabric(tmp_path, devs=(2, 2))
    fed = FederatedStore(fabric)
    hold = threading.Event()
    site, job = fabric.submit("default", JobSpec(
        "busy", lambda ctx: hold.wait(5), replicas=2, devices_per_pod=1))
    assert site.name == "s0"
    try:
        p = PlacementPlanner(fed).place([])     # no data: load decides
        assert p.site == "s1"
    finally:
        hold.set()
        site.cluster.wait(job, timeout=30)


def test_planner_data_blind_round_robin(tmp_path):
    fabric = mk_fabric(tmp_path, devs=(2, 2))
    fed = FederatedStore(fabric)
    fed.put("d/x", b"z" * 1000, "s0")
    planner = PlacementPlanner(fed, data_blind=True)
    assert [planner.place(["d/x"]).site for _ in range(3)] == \
        ["s0", "s1", "s0"]


def test_locality_moves_fewer_bytes_than_data_blind(tmp_path):
    """The same two-step workflow on a hub-and-spokes fabric whose data
    mostly homes at the hub: locality placement runs at the data, the
    data-blind round robin drags chunks over the slow links, so it moves
    strictly more bytes and spends more simulated link time for the same
    answer."""
    n_chunks = 4

    def run(data_blind):
        root = tmp_path / ("blind" if data_blind else "local")
        fabric = Fabric(time_scale=0.0)
        for name, n in (("hub", 4), ("spoke-a", 2), ("spoke-b", 1)):
            fabric.add_site(name, devices=list(range(n)),
                            store_root=str(root / name))
        fabric.connect("hub", "spoke-a", gbps=0.2, latency_ms=10.0)
        fabric.connect("hub", "spoke-b", gbps=0.1, latency_ms=20.0)
        fabric.connect("spoke-a", "spoke-b", gbps=0.1, latency_ms=20.0)
        fed = FederatedStore(fabric)
        rng = np.random.RandomState(0)
        keys = [f"chunks/c{i}.npy" for i in range(n_chunks)]
        for i, key in enumerate(keys):
            # all but chunk 0 home at the hub
            fed.view("hub" if i else "spoke-a").put_array(
                key, rng.rand(1 << 15))
        wf = Workflow("placement",
                      planner=PlacementPlanner(fed, data_blind=data_blind))
        for i, key in enumerate(keys):
            wf.add(Step(f"stats{i}", lambda ctx, k=key: {
                "mean": float(ctx.store.get_array(k).mean())},
                inputs=[key]))
        wf.add(Step("reduce", lambda ctx: {"mean": float(np.mean(
            [v["mean"] for v in ctx.inputs.values()]))},
            deps=[f"stats{i}" for i in range(n_chunks)]))
        out = wf.run()
        m = fabric.metrics
        return (out["reduce"]["mean"], m.series("fabric/bytes_moved").total,
                m.series("fabric/transfer_s").total)

    local, blind = run(False), run(True)
    assert local[0] == blind[0], "placement must not change the answer"
    assert local[1] < blind[1], (local, blind)
    assert local[2] < blind[2], (local, blind)


def test_planner_glob_expansion(tmp_path):
    fabric = mk_fabric(tmp_path)
    fed = FederatedStore(fabric)
    fed.put("models/ffn/w0", b"a" * 100, "s1")
    fed.put("models/ffn/w1", b"b" * 100, "s1")
    planner = PlacementPlanner(fed)
    assert planner.expand(["models/ffn/*", "k"]) == \
        ["models/ffn/w0", "models/ffn/w1", "k"]
    missing, _ = planner.bytes_missing(planner.expand(["models/ffn/*"]), "s0")
    assert missing == 200


def test_planner_never_places_on_zero_capacity_site(tmp_path):
    """A site whose nodes are ALL offline (but which is not formally
    down) must not attract even device-less steps: its cluster would
    drain any pod instantly."""
    fabric = mk_fabric(tmp_path, devs=(2, 1))
    fed = FederatedStore(fabric)
    fed.put("d/x", b"z" * 1000, "s1")            # the data homes at s1
    for d in list(fabric.sites["s1"].cluster.devices):
        fabric.sites["s1"].cluster.fail_node(d)  # s1: up, 0 online devices
    planner = PlacementPlanner(fed)
    assert all(s.name != "s1" for s in planner.candidates(0))
    p = planner.place(["d/x"])                   # pays the link instead
    assert p.site == "s0" and p.mode == "pre-stage"


def test_planner_skips_dead_sites_and_records_migration(tmp_path):
    fabric = mk_fabric(tmp_path, devs=(2, 2))
    fed = FederatedStore(fabric)
    fed.put("d/x", b"z" * 1000, "s0")
    fed.replicate("d/x", "s1")
    fabric.fail_site("s0")
    p = PlacementPlanner(fed).place(["d/x"])
    assert p.site == "s1"
    assert p.migrated_from == "s0"      # home (bigger, had the data) is down


# ------------------------------------------------------- federated workflow

def test_federated_workflow_places_and_reports(tmp_path):
    fabric = mk_fabric(tmp_path, devs=(2, 2))
    fed = FederatedStore(fabric)
    fed.view("s1").put_array("in/x.npy", np.arange(8).astype(np.float64))
    wf = Workflow("w", planner=PlacementPlanner(fed))
    wf.add(Step("sum", lambda ctx: {
        "s": float(ctx.store.get_array("in/x.npy").sum())},
        inputs=["in/x.npy"], outputs=["out/s"]))
    out = wf.run()
    assert out["sum"]["s"] == 28.0
    rep = wf.reports[0]
    assert rep.site == "s1"                       # ran at the data
    assert "bytes_moved" in rep.extra and "transfer_s" in rep.extra
    assert "Site" in wf.table_one()
    # undeclared outputs are surfaced as a metric, not an error
    assert wf.metrics.series("workflow/w/sum/missing_output").points


def test_federated_workflow_resume_skips_across_sites(tmp_path):
    fabric = mk_fabric(tmp_path, devs=(2, 2))
    fed = FederatedStore(fabric)
    calls = {"n": 0}

    def mk_wf():
        wf = Workflow("w", planner=PlacementPlanner(fed))
        def fn(ctx):
            calls["n"] += 1
            return {"ok": True}
        wf.add(Step("a", fn))
        return wf

    mk_wf().run()
    out = mk_wf().run()                 # fresh workflow object: marker skips
    assert calls["n"] == 1 and out["a"]["ok"] is True


def test_federated_workflow_survives_site_kill_between_steps(tmp_path):
    fabric = mk_fabric(tmp_path, devs=(4, 2))
    fed = FederatedStore(fabric)

    def mk_wf():
        wf = Workflow("w", planner=PlacementPlanner(fed))
        wf.add(Step("produce", lambda ctx: (
            ctx.store.put("d/x", b"z" * 1000),
            fed.replicate("d/x", "s1"), {"done": 1})[-1],
            outputs=["d/x"]))
        wf.add(Step("consume", lambda ctx: {
            "n": len(ctx.store.get("d/x"))}, deps=["produce"],
            inputs=["d/x"]))
        return wf

    mk_wf().run(only="produce")
    fabric.fail_site("s0")              # produce ran (and homed) at s0
    wf = mk_wf()
    out = wf.run()
    assert out["consume"]["n"] == 1000
    rep = next(r for r in wf.reports if r.step == "consume")
    assert rep.site == "s1" and rep.extra.get("migrated") == 1.0


# ------------------------------------------------- cross-site elastic train

def test_elastic_federated_failover(tmp_path):
    """Kill the training site mid-run: the churn controller escalates
    CapacityLostError, the supervisor replicates the mirrored checkpoints
    to the survivor, and the run completes there — one migration, every
    step's loss accounted for, wall/segment history spanning both sites."""
    import jax
    from repro.configs import registry
    from repro.configs.base import OptimizerConfig
    from repro.elastic.trainer import ElasticTrainSpec
    from repro.fabric import run_elastic_federated

    fabric = Fabric(time_scale=0.0)
    dev = jax.devices()[0]
    fabric.add_site("alpha", cluster=Cluster(devices=[dev]),
                    store_root=str(tmp_path / "alpha"))
    fabric.add_site("beta", cluster=Cluster(devices=[dev]),
                    store_root=str(tmp_path / "beta"))
    fabric.connect("alpha", "beta", gbps=10.0, latency_ms=1.0)
    fed = FederatedStore(fabric)
    planner = PlacementPlanner(fed)

    steps = 8
    spec = ElasticTrainSpec(
        registry.get_smoke("phi4-mini-3.8b"),
        registry.get_parallel("phi4-mini-3.8b"),
        OptimizerConfig(warmup_steps=2, decay_steps=100),
        steps=steps, seq_len=32, global_batch=4, base_shape=(1, 1),
        max_data=1, ckpt_every=2, log_every=4, rejoin_timeout_s=0.5,
        verbose=False)

    killed = {"done": False}

    def kill_when_halfway():
        import time as _t
        while True:
            prog = fabric.metrics.series("elastic/step").last
            if prog >= steps // 2:
                fabric.fail_site("alpha")
                killed["done"] = True
                return
            _t.sleep(0.01)

    killer = threading.Thread(target=kill_when_halfway, daemon=True)
    killer.start()
    result = run_elastic_federated(planner, spec)
    killer.join(timeout=5)

    assert killed["done"]
    assert result.sites[0] == "alpha" and result.sites[-1] == "beta"
    assert len(result.migrations) == 1
    mig = result.migrations[0]
    assert mig.from_site == "alpha" and mig.to_site == "beta"
    rep = result.report
    assert rep.segments[-1].end == steps - 1            # finished
    losses = result.out["loss_by_step"]
    assert sorted(losses) == list(range(steps))
    assert rep.recoveries >= 0 and rep.total_wall_s > 0
