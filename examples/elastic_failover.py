"""Self-healing elastic training demo (paper §V: "nodes can join and leave
the cluster at any time").

ALL the control lives in the platform: a ``TrainJob`` declared through
``repro.api.Session`` runs as a supervised elastic workload, and this
script only injects a churn schedule against the cluster, exactly like
an unplugged appliance would:

  1. training starts on a (4 data, 2 model) mesh over 8 simulated nodes;
  2. two nodes FAIL mid-run: the cluster drains their pods, the trainer
     restores the latest checkpoint onto a (2, 2) mesh and DOUBLES gradient
     accumulation so the global batch is unchanged;
  3. the nodes REJOIN: the trainer preempts gracefully (checkpointing) and
     scales back up to (4, 2), accumulation relaxing to 1.

Asserts, with no manual intervention anywhere: the run reaches its final
step, every mesh shape kept batch x accum constant, there is a loss value
for every step, and the loss improved end-to-end.  Emits a
``CHURN_REPORT {json}`` line (recovery cost in tokens/s and steps lost
is *measured*, not asserted).

    PYTHONPATH=src python examples/elastic_failover.py [--fast]
"""
import os

if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               " --xla_force_host_platform_device_count=8").strip()

import argparse   # noqa: E402
import json       # noqa: E402
import threading  # noqa: E402
import time       # noqa: E402

import jax        # noqa: E402

from repro.api import Session, TrainJob                  # noqa: E402
from repro.core.orchestrator import Cluster              # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="shorter run (CI churn smoke / benchmark)")
    ap.add_argument("--steps", type=int, default=0)
    args = ap.parse_args()
    steps = args.steps or (24 if args.fast else 45)
    fail_after = steps // 4          # churn points, in completed steps
    rejoin_after = steps // 2

    cluster = Cluster(devices=jax.devices())
    assert len(cluster.devices) == 8, "expected 8 forced host devices"
    session = Session(cluster=cluster)
    handle = session.apply(TrainJob(
        name="elastic-demo", steps=steps, seq_len=64, global_batch=16,
        base_shape=(4, 2), max_data=None,
        ckpt_every=3 if args.fast else 5, log_every=5,
        rejoin_timeout_s=120.0,
        optimizer={"lr": 1e-3, "warmup_steps": 2, "decay_steps": 200}))

    victims = jax.devices()[6:]

    def progress() -> int:
        return handle.status().observed.get("step", -1)

    def inject_churn():
        """The outside world: two nodes die, then come back."""
        while progress() < fail_after:
            time.sleep(0.02)
        print(f">>> churn: unplugging {len(victims)} nodes")
        for d in victims:
            cluster.fail_node(d)
        while progress() < rejoin_after:
            time.sleep(0.02)
        print(f">>> churn: {len(victims)} nodes rejoin")
        for d in victims:
            cluster.join_node(d)

    churn = threading.Thread(target=inject_churn, daemon=True)
    churn.start()
    out = handle.wait(timeout=3600)
    churn.join(timeout=10)
    report = out["report"]

    # --- the §V contract, checked end to end -----------------------------
    losses = out["loss_by_step"]
    assert sorted(losses) == list(range(steps)), "missing per-step losses"
    assert report.global_batch_constant, \
        "global batch (batch x accum) changed across mesh shapes"
    shapes = [s.mesh_shape for s in report.segments]
    assert (2, 2) in shapes, f"never trained on the shrunk mesh: {shapes}"
    assert shapes[-1] == (4, 2), f"never scaled back up: {shapes}"
    assert report.recoveries >= 1, "node failure was not recovered"
    accums = {s.mesh_shape: s.accum_steps for s in report.segments}
    assert accums[(2, 2)] == 2 * accums[(4, 2)], accums
    assert out["losses"][-1] < out["losses"][0], "loss did not improve"
    assert handle.state.value == "Succeeded", handle.state

    print("CHURN_REPORT " + json.dumps(report.to_json()))
    print(f"OK: self-healed across fail({fail_after})/rejoin({rejoin_after}) "
          f"churn — {report.recoveries} recovery, "
          f"{report.steps_lost} steps lost, "
          f"{report.tokens_per_s:,.0f} tokens/s overall "
          f"(final step {steps - 1}, mesh history {shapes})")


if __name__ == "__main__":
    main()
