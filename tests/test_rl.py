"""The distributed RL workload: replay staleness, policy store, manifest
surface, and one end-to-end RLJob on a bare cluster.

The load-bearing contract is bounded staleness: a learner at version v
draining rollouts generated at versions v-k must NEVER train on one with
k > max_policy_lag — stale rollouts are acked-and-dropped and metered
on a separate counter (property-tested below), and the lag of every
trained-on rollout is recorded so the bound is auditable after the run.
"""
import numpy as np
import pytest

from repro.api import RLJob, from_manifest
from repro.api.resources import ManifestError
from repro.core.metrics import Registry
from repro.data.objectstore import ObjectStore
from repro.rl import (PolicyStore, RolloutQueue, Trajectory, is_stale,
                      split_stale)


def traj(version: int, *, ticket="t0", reward=1.0) -> Trajectory:
    return Trajectory(ticket=ticket, prompt=(1, 2), tokens=(3, 4),
                      reward=reward, policy_version=version, actor="a")


# ------------------------------------------------------------- staleness
def test_is_stale_boundary():
    assert not is_stale(3, 5, max_policy_lag=2)     # gap == lag: trainable
    assert is_stale(2, 5, max_policy_lag=2)         # gap > lag: stale
    assert not is_stale(5, 5, max_policy_lag=0)


def test_split_stale():
    ts = [traj(0), traj(1), traj(2)]
    fresh, stale = split_stale(ts, current_version=2, max_policy_lag=1)
    assert [t.policy_version for t in fresh] == [1, 2]
    assert [t.policy_version for t in stale] == [0]


def test_take_fresh_drops_and_meters_stale():
    reg = Registry()
    q = RolloutQueue(registry=reg)
    for v in (0, 0, 2, 1):
        q.push(traj(v, ticket=f"t{v}"))
    got = q.take_fresh(10, worker="learner", current_version=2,
                       max_policy_lag=1)
    assert [t.policy_version for _, t in got] == [2, 1]
    assert q.stale_dropped == 2
    assert reg.series("rl/stale_dropped").total == 2
    q.ack_trained(got, worker="learner", current_version=2)
    assert q.trained == 2
    assert q.max_lag_trained() == 1
    assert q.pending == 0                           # stale ones consumed


def test_release_returns_batch_to_pending():
    q = RolloutQueue()
    q.push(traj(0))
    held = q.take_fresh(1, worker="learner", current_version=0,
                        max_policy_lag=2)
    assert len(held) == 1 and q.pending == 0
    q.release(held, worker="learner")               # preempted mid-drain
    assert q.pending == 1
    again = q.take_fresh(1, worker="learner", current_version=0,
                         max_policy_lag=2)
    assert len(again) == 1                          # at-least-once


def test_rollout_queue_snapshot_restore_roundtrip():
    q = RolloutQueue()
    for v in (0, 0, 1):
        q.push(traj(v))
    got = q.take_fresh(1, worker="learner", current_version=1,
                       max_policy_lag=0)            # drops the two v=0
    q.ack_trained(got, worker="learner", current_version=1)
    q.push(traj(1))
    snap = q.snapshot()
    clone = RolloutQueue()
    clone.restore(snap)
    assert clone.pushed == q.pushed == 4
    assert clone.trained == q.trained == 1
    assert clone.stale_dropped == q.stale_dropped == 2
    assert clone.lag_trained == q.lag_trained == [0]
    assert clone.pending == q.pending == 1
    got2 = clone.take_fresh(1, worker="learner", current_version=1,
                            max_policy_lag=0)
    assert [t.policy_version for _, t in got2] == [1]


def test_trajectory_item_roundtrip_is_jsonable():
    import json
    t = Trajectory(ticket="r1", prompt=(np.int32(1), 2),
                   tokens=(np.int32(7),), reward=np.float32(0.5),
                   policy_version=3, actor="a0")
    item = t.to_item()
    json.dumps(item)                                # checkpoint-manifest safe
    assert Trajectory.from_item(item) == Trajectory(
        ticket="r1", prompt=(1, 2), tokens=(7,), reward=0.5,
        policy_version=3, actor="a0")


# -------------------------------------------- queue timestamp preservation
# The rollout queue's wait accounting depends on the WorkQueue invariant
# that implicit requeues (nack on actor kill, lease expiry on actor
# crash) keep the ORIGINAL enqueued_at — a retried trajectory charges
# its queue wait from the first enqueue, never from the requeue.

class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


def test_nack_preserves_enqueued_at():
    from repro.core.queue import WorkQueue
    clock = FakeClock()
    q = WorkQueue(lease_timeout=10.0, clock=clock)
    clock.advance(5.0)
    tid = q.put("traj")
    assert q.enqueued_at(tid) == 5.0
    clock.advance(1.0)
    got_tid, _ = q.lease("w1")
    assert got_tid == tid
    clock.advance(2.0)
    assert q.nack(tid, "w1")                    # early return at t=8
    assert q.enqueued_at(tid) == 5.0            # NOT reset to nack time
    got_tid, _ = q.lease("w2")                  # re-leased by a survivor
    assert got_tid == tid
    assert q.enqueued_at(tid) == 5.0


def test_lease_expiry_reclaim_preserves_enqueued_at():
    from repro.core.queue import WorkQueue
    clock = FakeClock()
    q = WorkQueue(lease_timeout=10.0, clock=clock)
    clock.advance(3.0)
    tid = q.put("traj")
    q.lease("w1")
    clock.advance(11.0)                         # w1 died; lease expired
    got = q.lease("w2")                         # reclaim happens here
    assert got is not None and got[0] == tid
    assert q.enqueued_at(tid) == 3.0            # survives the reclaim


def test_leased_by_counts_live_leases_only():
    from repro.core.queue import WorkQueue
    clock = FakeClock()
    q = WorkQueue(["a", "b", "c"], lease_timeout=10.0, clock=clock)
    q.lease("w1")
    q.lease("w1")
    q.lease("w2")
    assert q.leased_by("w1") == 2 and q.leased_by("w2") == 1
    clock.advance(11.0)                         # everything expired
    assert q.leased_by("w1") == 0


# --------------------------------------------------- staleness (property)
def test_staleness_bound_property():
    """Actors holding versions v-k feed a learner at version v: whatever
    the push/bump interleaving, nothing older than max_policy_lag is
    ever trained on, and every drop lands on the stale meter."""
    hypothesis = pytest.importorskip(
        "hypothesis", reason="optional dev dependency")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=60, deadline=None)
    @given(lag=st.integers(min_value=0, max_value=3),
           events=st.lists(
               st.one_of(st.tuples(st.just("push"),
                                   st.integers(min_value=0, max_value=5)),
                         st.tuples(st.just("bump"), st.just(0))),
               min_size=1, max_size=40))
    def prop(lag, events):
        reg = Registry()
        q = RolloutQueue(registry=reg)
        version = 0
        pushed = []
        for kind, k in events:
            if kind == "bump":
                version += 1
            else:                       # an actor holding version - k
                v = max(version - k, 0)
                pushed.append(v)
                q.push(traj(v, ticket=f"t{len(pushed)}"))
        held = q.take_fresh(len(pushed) + 1, worker="learner",
                            current_version=version, max_policy_lag=lag)
        q.ack_trained(held, worker="learner", current_version=version)
        expect_stale = sum(1 for v in pushed if version - v > lag)
        assert q.max_lag_trained() <= lag
        assert all(version - t.policy_version <= lag for _, t in held)
        assert q.stale_dropped == expect_stale
        assert q.trained == len(pushed) - expect_stale
        assert reg.series("rl/stale_dropped").total == expect_stale
        assert reg.series("rl/trained_rollouts").total == q.trained

    prop()


# ----------------------------------------------------------- policy store
def test_policy_store_roundtrip(tmp_path):
    reg = Registry()
    store = ObjectStore(str(tmp_path))
    pub = PolicyStore(store, registry=reg)
    assert pub.latest_version() == -1
    tree = {"w": np.arange(6, dtype=np.float32).reshape(2, 3),
            "b": np.float32(2.5)}
    pub.publish(1, tree, step=4)
    pub.publish(2, {"w": tree["w"] * 2, "b": tree["b"]}, step=8)
    sub = PolicyStore(store)                # a separate subscriber view
    assert sub.latest_version() == 2
    abstract = {"w": np.zeros((2, 3), np.float32), "b": np.zeros((), np.float32)}
    got, version = sub.fetch(abstract)
    assert version == 2                     # learner_step must NOT clobber it
    np.testing.assert_array_equal(np.asarray(got["w"]), tree["w"] * 2)
    assert reg.series("rl/weights_published").total == 2


def test_policy_store_empty_fetch(tmp_path):
    sub = PolicyStore(ObjectStore(str(tmp_path)))
    got, version = sub.fetch({"w": np.zeros((1,), np.float32)})
    assert got is None and version == -1


# ---------------------------------------------------------- RLJob surface
def test_rljob_manifest_roundtrip():
    job = RLJob(name="rl", learner_steps=6, actors=3, max_policy_lag=1,
                site="serve", learner_site="train",
                optimizer={"lr": 1e-4})
    man = job.to_manifest()
    assert man["kind"] == "RLJob"
    assert from_manifest(man) == job


def test_rljob_validation_names_fields():
    with pytest.raises(ManifestError) as e:
        RLJob(name="rl", learner_steps=0)
    assert e.value.field == "spec.learner_steps"
    with pytest.raises(ManifestError) as e:
        RLJob(name="rl", learner_steps=1, max_policy_lag=-1)
    assert e.value.field == "spec.max_policy_lag"
    with pytest.raises(ManifestError) as e:
        RLJob(name="rl", learner_steps=1, actors=0)
    assert e.value.field == "spec.actors"
    with pytest.raises(ManifestError) as e:
        from_manifest({"apiVersion": "repro/v1", "kind": "RLJob",
                       "metadata": {"name": "rl"},
                       "spec": {"learner_steps": 2, "bogus": 1}})
    assert e.value.field == "spec.bogus"


def test_rl_smoke_manifest_parses():
    from repro.api import load_manifest
    spec = load_manifest("examples/manifests/rl_smoke.json")
    assert isinstance(spec, RLJob)
    assert spec.learner_steps == 4 and spec.actors == 2


# ------------------------------------------------------------- end to end
def test_rljob_end_to_end_on_cluster():
    """Two actors + learner on a bare cluster Session: completes, stays
    inside the staleness bound, and every actor observes >= 1 published
    weight version."""
    from repro.api import Session
    from repro.core.orchestrator import Cluster

    job = RLJob(name="rl-e2e", learner_steps=2, actors=2,
                rollouts_per_step=2, prompt_len=4, max_new_tokens=4,
                seq_len=12, slots=2, max_policy_lag=2, broadcast_every=1,
                ckpt_every=2)
    out = Session(cluster=Cluster()).apply(job).wait(timeout=540)
    assert out["done"] and out["steps_done"] == 2
    assert out["trained"] == 4
    assert out["max_lag_trained"] <= job.max_policy_lag
    assert out["min_actor_syncs"] >= 1
    assert out["final_version"] >= 1
    assert out["steps_lost"] == 0


# -------------------------------------------------- learner and actor chaos
RL_UNIT = RLJob(name="rl-unit", learner_steps=6, actors=1,
                rollouts_per_step=2, prompt_len=4, max_new_tokens=4,
                seq_len=12, slots=2, max_policy_lag=2, ckpt_every=2)


def mk_learner(tmp_path, *, fail_at=-1):
    """A learner on the smoke config over a pre-filled rollout queue (all
    version 0; no mid-run publish, so nothing goes stale)."""
    from repro.api.runners import rl_pieces
    from repro.rl import RLLearner, RLLearnerSpec
    cfg, par, ocfg = rl_pieces(RL_UNIT)
    spec = RLLearnerSpec(cfg, par, ocfg, steps=RL_UNIT.learner_steps,
                         seq_len=RL_UNIT.seq_len,
                         batch=RL_UNIT.rollouts_per_step,
                         ckpt_every=RL_UNIT.ckpt_every,
                         broadcast_every=100, fail_at=fail_at)
    rollouts = RolloutQueue()
    rng = np.random.RandomState(0)
    for i in range(24):
        rollouts.push(Trajectory(
            ticket=f"t{i}", prompt=tuple(rng.randint(1, 64, 4).tolist()),
            tokens=tuple(rng.randint(1, 64, 4).tolist()),
            reward=float(i % 3), policy_version=0, actor="a"))
    policies = PolicyStore(ObjectStore(str(tmp_path / "policy")))
    return RLLearner(spec, rollouts, policies,
                     store=ObjectStore(str(tmp_path / "ckpt")))


def test_learner_crash_resumes_from_the_periodic_checkpoint(tmp_path):
    """A hard crash after step 4 (no goodbye save) restores from the
    checkpoint at step 3: one step re-executed, within ckpt_every."""
    learner = mk_learner(tmp_path, fail_at=4)
    out = learner.run_supervised()
    rep = learner.report
    assert out["done"] and rep.steps_done == RL_UNIT.learner_steps
    assert [s["outcome"] for s in rep.segments] == ["failed", "done"]
    assert rep.recoveries == 1
    assert rep.segments[1]["start"] == 4        # not a restart from 0
    assert rep.steps_lost == 1 <= RL_UNIT.ckpt_every
    # the re-executed step trained on fresh rollouts, once each
    assert learner.rollouts.trained == 2 * (RL_UNIT.learner_steps + 1)


def test_learner_preemption_resumes_with_zero_lost_steps(tmp_path):
    """A cooperative stop between periodic checkpoints goodbye-saves the
    last completed step, so the next placement resumes right after it."""
    learner = mk_learner(tmp_path)
    first = learner.run(should_stop=lambda: learner.report.steps_done >= 3)
    assert first["preempted"] and first["step"] == 2
    out = learner.run()
    rep = learner.report
    assert out["done"] and rep.steps_done == RL_UNIT.learner_steps
    assert [s["outcome"] for s in rep.segments] == ["preempted", "done"]
    assert rep.preemptions == 1
    assert rep.segments[1]["start"] == 3
    assert rep.steps_lost == 0
    assert learner.rollouts.trained == 2 * RL_UNIT.learner_steps


def test_killed_actor_leases_requeue_to_the_survivor(tmp_path):
    """An actor killed while it holds ticket leases nacks them at once
    (not a lease timeout later); its replacement serves them, and every
    ticket yields exactly one trajectory."""
    import time

    from repro.api.runners import build_rl_engine, rl_pieces
    from repro.rl import ActorFleet, RolloutActor, ticket_queue

    cfg, par, _ = rl_pieces(RL_UNIT)
    tickets = ticket_queue(lease_timeout=300.0)
    rollouts = RolloutQueue()
    policies = PolicyStore(ObjectStore(str(tmp_path)))
    prompts = {}
    rng = np.random.RandomState(1)
    n = 12
    for i in range(n):
        prompts[f"t{i}"] = rng.randint(1, cfg.vocab_size, 4).tolist()
        tickets.put({"id": f"t{i}", "prompt": prompts[f"t{i}"],
                     "max_new_tokens": RL_UNIT.max_new_tokens})
    fleet = ActorFleet(
        lambda name: RolloutActor(name, build_rl_engine(RL_UNIT, cfg, par),
                                  tickets, rollouts, policies,
                                  prompts=prompts),
        width=1)
    fleet.start()
    try:
        deadline = time.monotonic() + 120
        while tickets.leased_by("actor-0") == 0:
            assert time.monotonic() < deadline, "actor-0 never leased"
            time.sleep(1e-3)
        fleet.kill("actor-0")
        assert tickets.leased == 0, "the kill must nack, not strand, leases"
        assert fleet.resize(1) == 1             # spawns actor-1
        while rollouts.pushed < n:
            assert time.monotonic() < deadline, rollouts.pushed
            time.sleep(5e-3)
    finally:
        fleet.stop_all()
    got = rollouts.take_fresh(2 * n, worker="audit", current_version=0,
                              max_policy_lag=0)
    assert sorted(t.ticket for _, t in got) == sorted(prompts)
    attempts = [a for _, _, a, _, _ in tickets.snapshot()["tasks"]]
    assert max(attempts) > 1, "no ticket was requeued"
    assert not tickets.dead


def test_actor_pulls_published_weights_across_the_fabric():
    """The learner publishes at its site; an actor polling through its
    own site's view pulls on a version bump only, and each pull is a
    metered cross-link transfer."""
    import jax

    from repro.api.runners import build_rl_engine, rl_pieces
    from repro.fabric import Fabric, FederatedStore
    from repro.rl import RolloutActor, ticket_queue

    fabric = Fabric()
    fabric.add_site("serve", devices=[0])
    fabric.add_site("train", devices=[0])
    fabric.connect("serve", "train", gbps=10.0, latency_ms=1.0)
    fed = FederatedStore(fabric)
    reg = Registry()
    cfg, par, _ = rl_pieces(RL_UNIT)
    engine = build_rl_engine(RL_UNIT, cfg, par)
    actor = RolloutActor("a", engine, ticket_queue(), RolloutQueue(),
                         PolicyStore(fed.view("serve"), registry=reg),
                         prompts={})
    publish = PolicyStore(fed.view("train"))
    moved = fabric.metrics.series("fabric/bytes_moved")

    assert not actor.maybe_sync()               # nothing published yet
    new = jax.tree.map(lambda x: x + 1, engine.params)
    publish.publish(1, new, step=2)
    assert actor.maybe_sync()
    assert actor.version == 1 and actor.syncs == 1
    pulled = moved.total
    assert pulled > 0
    leaf = jax.tree.leaves(new)[0]
    np.testing.assert_array_equal(np.asarray(jax.tree.leaves(engine.params)[0]),
                                  np.asarray(leaf))
    assert not actor.maybe_sync()               # no bump, no pull
    assert moved.total == pulled
    assert reg.series("rl/weight_syncs").total == 1
