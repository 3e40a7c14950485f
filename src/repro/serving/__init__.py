"""Continuous-batching distributed inference (paper §III/§V serving step).

Split host/device: ``scheduler`` is the deterministic slot/lease policy
(no jax — testable with a fake clock), ``engine`` owns the jitted prefill,
the KV cache (slotted, or a paged block pool with radix-style prefix
reuse — ``pool``) and the fused per-slot decode step, ``router`` runs N
replicas behind a session-affine load-aware router with an HPA-style
autoscaler, ``report`` holds the ``serve/*`` gauge namespace, synthetic
request streams and the Table-I row.  ``repro.launch.serve`` is the CLI
driver; docs/serving.md is the usage guide.
"""
from repro.serving.engine import ServingEngine
from repro.serving.pool import BlockPool
from repro.serving.report import (GAUGES, make_requests, record_serving_totals,
                                  serving_report, serving_summary)
from repro.serving.router import (Autoscaler, Replica, ReplicaSet,
                                  serve_replicated)
from repro.serving.scheduler import ContinuousScheduler, Request, Slot

__all__ = ["ServingEngine", "ContinuousScheduler", "Request", "Slot",
           "BlockPool", "Autoscaler", "Replica", "ReplicaSet",
           "serve_replicated",
           "GAUGES", "make_requests", "record_serving_totals",
           "serving_report", "serving_summary"]
