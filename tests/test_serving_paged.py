"""Paged KV pool, prefix reuse, replicas — and the serving-loop fixes.

Host-side policy (BlockPool refcounts/eviction, router/autoscaler,
scheduler accounting) is tested with fake clocks and fake engines — no
devices.  The paged decode path is pinned against the slotted baseline
bit for bit on the smoke config, and the pool-pressure preemption path
runs through the real engine.
"""
import math
import time

import numpy as np
import pytest

from repro.core.metrics import Registry
from repro.core.queue import WorkQueue
from repro.serving.pool import BlockPool
from repro.serving.report import GAUGES
from repro.serving.router import Autoscaler, ReplicaSet, serve_replicated
from repro.serving.scheduler import ContinuousScheduler


class FakeClock:
    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


def mk_requests(gens, prompt=(5, 6, 7)):
    return [{"id": i, "prompt": list(prompt), "max_new_tokens": g}
            for i, g in enumerate(gens)]


# -------------------------------------------------------------- block pool

def test_pool_alloc_release_refcount():
    pool = BlockPool(6, 4)              # block 0 reserved: 5 usable
    assert pool.free_blocks == 5 and pool.in_use == 0
    blocks = pool.alloc(3)
    assert len(blocks) == 3 and 0 not in blocks
    assert all(pool.ref(b) == 1 for b in blocks)
    assert pool.in_use == 3 and pool.free_blocks == 2
    pool.release(blocks)
    assert pool.in_use == 0 and pool.free_blocks == 5
    with pytest.raises(ValueError, match="refcount"):
        pool.release([blocks[0]])       # double free


def test_pool_in_use_follows_refcounts():
    """The in-use count, kept as refcounts change, equals the count of
    blocks held after any sequence of allocations, releases, prefix
    caching and prefix hits."""
    rng = np.random.RandomState(0)
    pool = BlockPool(40, 2)
    held = []
    for step in range(400):
        op = rng.randint(4)
        if op == 0:
            got = pool.alloc(rng.randint(1, 4))
            held += [got] if got else []
        elif op == 1 and held:
            pool.release(held.pop(rng.randint(len(held))))
        elif op == 2 and held:
            blocks = held[rng.randint(len(held))]
            pool.cache_prefix([step % 3] * (2 * len(blocks)), blocks)
        else:
            got = pool.match([rng.randint(3)] * 6)
            held += [got] if got else []
        assert pool.in_use == sum(pool.ref(b) > 0 for b in range(1, 40))


def test_pool_exhaustion_allocates_nothing():
    pool = BlockPool(4, 4)
    assert pool.alloc(4) is None        # only 3 usable: all-or-nothing
    assert pool.in_use == 0
    got = pool.alloc(3)
    assert pool.alloc(1) is None
    pool.release(got[:1])
    assert pool.alloc(1) is not None


def test_pool_prefix_match_is_content_exact():
    pool = BlockPool(8, 2)
    prompt = [1, 2, 3, 4, 5, 6]
    blocks = pool.alloc(3)
    assert pool.cache_prefix(prompt, blocks) == 3
    pool.release(blocks)                # cached: stay resident at ref 0
    assert pool.cached_blocks == 3 and pool.in_use == 0

    hit = pool.match(prompt, max_blocks=2)
    assert hit == blocks[:2]            # capped below the full prompt
    assert all(pool.ref(b) == 1 for b in hit)
    # same length, different content: chain key misses at block 0
    assert pool.match([9, 9, 3, 4, 5, 6], max_blocks=2) == []
    # shared first block only: chain stops after one
    assert pool.match([1, 2, 9, 9], max_blocks=2) == blocks[:1]

    reg = pool.metrics.summary()
    assert reg["serve/prefix_hits"]["total"] == 3
    assert reg["serve/prefix_misses"]["total"] >= 2


def test_pool_lru_eviction_only_under_pressure():
    pool = BlockPool(4, 2)              # 3 usable
    a = pool.alloc(2)
    pool.cache_prefix([1, 2, 3, 4], a)
    pool.release(a)                     # both cached at ref 0
    b = pool.alloc(1)                   # free list still has one: no evict
    assert pool.cached_blocks == 2
    c = pool.alloc(2)                   # pressure: evicts LRU cached pair
    assert c is not None and pool.cached_blocks == 0
    assert pool.match([1, 2, 3, 4]) == []
    pool.release(b + c)


def test_pool_cached_block_with_live_ref_is_not_evictable():
    pool = BlockPool(3, 2)              # 2 usable
    a = pool.alloc(2)
    pool.cache_prefix([1, 2, 3, 4], a)
    pool.release(a[1:])                 # a[0] still referenced
    assert pool.alloc(2) is None        # only a[1] is reclaimable
    got = pool.alloc(1)
    assert got == [a[1]]
    pool.release(got + a[:1])


# --------------------------------------------------- serving-loop bug fixes

def test_ttft_measured_from_enqueue_not_admit():
    """With one slot, the second request's TTFT must include its queue
    wait; service TTFT (admit -> first token) stays small for both."""
    clock = FakeClock()
    reg = Registry()
    q = WorkQueue(mk_requests([3, 3]), clock=clock)
    sched = ContinuousScheduler(q, 1, registry=reg, clock=clock)
    while not sched.finished():
        for slot in sched.admit():
            clock.advance(0.5)          # prefill cost
            sched.start(slot, 100, 8)
        if sched.active():
            clock.advance(1.0)          # fused decode step
            sched.observe([101])
    ttft = [v for _, v in reg.series(GAUGES.TTFT_S).points]
    service = [v for _, v in reg.series(GAUGES.SERVICE_TTFT_S).points]
    assert ttft[0] == pytest.approx(0.5)         # admitted instantly
    assert service[0] == pytest.approx(0.5)
    # request 1 waited for request 0's 2 decode steps before admission
    assert ttft[1] == pytest.approx(2.5 + 0.5)
    assert service[1] == pytest.approx(0.5)
    assert ttft[1] > service[1]


def test_stale_ack_tokens_are_not_useful_throughput():
    clock = FakeClock()
    reg = Registry()
    q = WorkQueue(mk_requests([3]), lease_timeout=10.0, clock=clock)
    sched = ContinuousScheduler(q, 1, registry=reg, clock=clock)
    [slot] = sched.admit()
    sched.start(slot, 100, 8)
    clock.advance(11.0)                 # lease expires mid-decode
    tid, _ = q.lease("thief")
    assert q.ack(tid, "thief")          # the reclaimer finishes first
    sched.observe([101])
    done = sched.observe([102])         # original completes -> stale ack
    assert done and done[0][1] == [100, 101, 102]
    s = reg.summary()
    assert s["serve/stale_ack"]["total"] == 1
    assert s["serve/stale_tokens"]["total"] == 3
    assert "serve/tokens_generated" not in s     # nothing counted useful
    assert sched.useful_tokens == 0 and sched.stale_tokens == 3


def test_release_all_nacks_inflight_leases():
    clock = FakeClock()
    q = WorkQueue(mk_requests([5, 5, 5]), lease_timeout=1000.0, clock=clock)
    sched = ContinuousScheduler(q, 2, clock=clock)
    for slot in sched.admit():
        sched.start(slot, 100, 8)
    assert q.pending == 1 and q.leased == 2
    assert sched.release_all() == 2
    # nacked, not abandoned: pending again NOW, not one timeout later
    assert q.pending == 3 and q.leased == 0
    assert sched.occupancy == 0


def test_queue_snapshot_restore_preserves_fifo_order():
    clock = FakeClock()
    q = WorkQueue([], lease_timeout=5.0, clock=clock)
    for name in "abcd":
        q.put({"id": name})
    ta, _ = q.lease("w")                # a in flight at snapshot time
    tb, _ = q.lease("w")
    assert q.nack(tb, "w")              # b requeued behind c, d
    snap = q.snapshot()

    q2 = WorkQueue.restore(snap, clock=clock)
    order = []
    while True:
        got = q2.lease("w2")
        if got is None:
            break
        order.append(got[1]["id"])
    # snapshotted pending order first (c, d, b), then the task that was
    # leased at snapshot time (a) — never re-sorted into id order
    assert order == ["c", "d", "b", "a"]

    legacy = dict(snap)
    del legacy["pending"]               # old snapshot: degrades to id order
    q3 = WorkQueue.restore(legacy, clock=clock)
    assert [q3.lease("w")[1]["id"] for _ in range(4)] == list("abcd")


def test_queue_put_preserves_original_enqueue_time():
    clock = FakeClock(t=7.0)
    q = WorkQueue(clock=clock)
    tid = q.put({"id": 0}, enqueued_at=2.0)
    assert q.enqueued_at(tid) == 2.0
    assert q.enqueued_at(q.put({"id": 1})) == 7.0


# ------------------------------------------------------ router / autoscaler

class FakeEngine:
    """Queue-draining stand-in for ServingEngine: acks instantly, nacks
    in-flight work on stop, records the fleet-shared serve gauges."""

    def __init__(self, registry, delay=0.0):
        self.metrics = registry
        self.delay = delay

    def run(self, queue, *, worker="server", should_stop=None,
            exit_on_drain=False, **_):
        results = {}
        while not (should_stop is not None and should_stop()):
            got = queue.lease(worker)
            if got is None:
                if exit_on_drain and queue.drained():
                    break
                time.sleep(0.001)
                continue
            tid, item = got
            if self.delay:
                time.sleep(self.delay)
            if should_stop is not None and should_stop():
                queue.nack(tid, worker)
                break
            queue.ack(tid, worker)
            n = int(item.get("max_new_tokens", 1))
            results[item["id"]] = [7] * n
            self.metrics.inc(GAUGES.COMPLETED)
            self.metrics.inc(GAUGES.TOKENS, n)
        return results, self.metrics


class IdleEngine:
    """Never consumes; exists so routing/draining can be observed."""

    def __init__(self, registry):
        self.metrics = registry

    def run(self, queue, *, worker="server", should_stop=None, **_):
        while not (should_stop is not None and should_stop()):
            time.sleep(0.001)
        return {}, self.metrics


def test_serve_replicated_scales_up_and_serves_everything():
    reg = Registry()
    reqs = mk_requests([2] * 24)
    results, metrics, events = serve_replicated(
        lambda name, r: FakeEngine(r, delay=0.01), reqs,
        min_replicas=1, max_replicas=3, target_backlog=2.0,
        registry=reg, reconcile_interval=0.005, timeout_s=30.0)
    assert sorted(results) == list(range(24))
    assert all(v == [7, 7] for v in results.values())
    reasons = [e[3] for e in events]
    assert reasons[0] == "startup" and "shutdown" in reasons
    # the 24-deep backlog over target 2 forced a scale-up past 1 replica
    assert metrics.series(GAUGES.REPLICAS).max >= 2
    assert metrics.series(GAUGES.SCALE_EVENTS).total == len(events)
    assert metrics.series(GAUGES.TOK_S).last > 0


def test_router_session_affinity_and_least_loaded():
    rset = ReplicaSet(lambda name, r: IdleEngine(r))
    rset.scale_to(2)
    a1 = rset.submit({"id": 0, "prompt": [1], "session": "alice"})
    a2 = rset.submit({"id": 1, "prompt": [1], "session": "alice"})
    assert a1 == a2                     # pinned: the replica's prefix
    b = rset.submit({"id": 2, "prompt": [1], "session": "bob"})
    assert b != a1                      # least-loaded breaks the tie
    rset.stop_all()


def test_scale_down_drains_queue_with_enqueue_time_preserved():
    clock = FakeClock(t=5.0)
    rset = ReplicaSet(lambda name, r: IdleEngine(r), clock=clock)
    rset.scale_to(2)
    for i in range(4):
        rset.submit({"id": i, "prompt": [1]})
    clock.advance(40.0)                 # well past any lease window
    rset.scale_to(1, reason="drain-test")
    [survivor] = rset._replicas
    assert survivor.queue.pending == 4  # nothing lost in the retirement
    order = []
    while True:
        got = survivor.queue.lease("w")
        if got is None:
            break
        tid, item = got
        # migrated requests keep charging TTFT from the FIRST enqueue
        assert survivor.queue.enqueued_at(tid) == 5.0
        order.append(item["id"])
    assert sorted(order) == [0, 1, 2, 3]
    rset.stop_all()


def test_autoscaler_recommend_clamps_and_slo_bump():
    class StubSet:
        def __init__(self):
            self.metrics = Registry()
            self.backlog = 0
            self.n = 1

        def total_backlog(self):
            return self.backlog

        def observed(self):
            return self.n

    stub = StubSet()
    sc = Autoscaler(stub, min_replicas=1, max_replicas=4,
                    target_backlog=4.0, ttft_slo_s=0.5)
    assert sc.recommend() == 1          # empty backlog, SLO series empty
    stub.backlog = 9
    assert sc.recommend() == math.ceil(9 / 4.0)
    stub.backlog = 100
    assert sc.recommend() == 4          # max clamp
    stub.backlog = 0
    stub.metrics.gauge(GAUGES.SERVICE_TTFT_S, 2.0)
    assert sc.recommend() == stub.n + 1     # latency breach: +1
    with pytest.raises(ValueError, match="min_replicas"):
        Autoscaler(stub, min_replicas=3, max_replicas=2)


def test_replicaset_capacity_gates_scale_up():
    granted = []

    def capacity(want):
        granted.append(want)
        return min(want, 2)             # the fair share caps the fleet

    rset = ReplicaSet(lambda name, r: IdleEngine(r), capacity=capacity)
    rset.scale_to(4)
    assert rset.observed() == 2
    rset.scale_to(0)
    assert granted == [4]               # scale-down never asks


def test_resize_claim_respects_fair_share():
    from repro.fabric import Fabric
    from repro.vcluster import FairShareScheduler, TenantSpec

    fabric = Fabric()
    fabric.add_site("s0", devices=list(range(4)))
    sched = FairShareScheduler(fabric)
    a = sched.create_tenant(TenantSpec("a", site_quota=4))
    sched.create_tenant(TenantSpec("b", site_quota=4))
    ca = a.claim("s0", 1, min_devices=1)
    cb = sched.claim("b", "s0", want=2)
    assert ca.granted == 1 and cb.granted == 2
    # growth clamps at what b's reservation leaves free
    assert sched.resize_claim(ca, 4) == 2
    sched.release_claim(cb)
    assert sched.resize_claim(ca, 4) == 4       # b's share returned
    assert sched.resize_claim(ca, 1) == 1       # shrink always succeeds
    ca.release()
    with pytest.raises(ValueError, match="released"):
        sched.resize_claim(ca, 2)


# ------------------------------------------------- paged engine (smoke cfg)

@pytest.fixture(scope="module")
def serve_setup():
    from repro.configs import registry as cfg_registry
    from repro.launch.mesh import single_device_mesh

    return dict(cfg=cfg_registry.get_smoke("phi4-mini-3.8b"),
                par=cfg_registry.get_parallel("phi4-mini-3.8b"),
                mesh=single_device_mesh())


def mk_engine(s, **kw):
    from repro.serving import ServingEngine
    kw.setdefault("num_slots", 2)
    kw.setdefault("prompt_len", 8)
    kw.setdefault("max_new_tokens", 8)
    return ServingEngine(s["cfg"], s["par"], s["mesh"], **kw)


def prompt_requests(cfg, gens, *, seed=0, shared_prefix=0):
    rng = np.random.RandomState(seed)
    head = rng.randint(1, cfg.vocab_size, shared_prefix).tolist()
    return [{"id": i,
             "prompt": head + rng.randint(1, cfg.vocab_size,
                                          8 - shared_prefix).tolist(),
             "max_new_tokens": g}
            for i, g in enumerate(gens)]


def test_paged_decode_bit_identical_to_slotted(serve_setup):
    """The acceptance pin: gather/scatter block addressing must produce
    the SAME tokens as the contiguous slotted cache on an identical
    trace — the null block's garbage is exactly masked out."""
    s = serve_setup
    gens = [8, 3, 5, 8, 2]
    e_slot = mk_engine(s, paged=False, seed=0)
    e_page = mk_engine(s, paged=True, block_size=4, prefix_cache=False,
                       seed=0, params=e_slot.params)
    assert not e_slot.paged and e_page.paged
    reqs = prompt_requests(s["cfg"], gens)
    r_slot, _ = e_slot.run(WorkQueue(reqs))
    r_page, m = e_page.run(WorkQueue(reqs))
    assert r_page == r_slot
    assert [len(r_page[i]) for i in range(5)] == gens
    assert m.summary()["serve/tokens_generated"]["total"] == sum(gens)


# paged decode step against the whole-view formulation it replaced

S_STEP, BS_STEP, NB_POOL = 16, 4, 12     # 4 blocks per slot, 12 in the pool


def _step_cfg(s, arch):
    """A smoke config with more than one layer group; the windowed kind's
    window is shorter than the cache, so its mask cuts history."""
    import dataclasses

    from repro.configs import registry as cfg_registry
    cfg = cfg_registry.get_smoke(arch)
    cfg = cfg.replace(num_layers=3 * len(cfg.block_pattern))
    if "local" in cfg.block_pattern:
        cfg = cfg.replace(attn=dataclasses.replace(cfg.attn, window=5))
    return cfg


def _whole_view_step(cfg, par, mesh):
    """The paged decode step as it was before each layer gathered its own
    view: gather every layer's (layers, B, S, ...) view before the layer
    scan, run the forward on it, then scatter each slot's written row."""
    import jax
    import jax.numpy as jnp

    from repro.models import transformer as tfm
    from repro.models.layers import ModelCtx
    ctx = ModelCtx(cfg, par, mesh)

    def step(params, pool, tables, token, pos):
        def gather(leaf):
            g = leaf[:, tables]                   # (G, B, nb, bs, *tail)
            return g.reshape(g.shape[0], g.shape[1], g.shape[2] * g.shape[3],
                             *g.shape[4:])

        views = jax.tree.map(gather, pool)
        hidden, new_views, _ = tfm.forward(ctx, params, token, mode="decode",
                                           caches=views, pos=pos)
        logits = tfm.lm_logits(ctx, params, hidden)
        next_tok = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)
        B = tables.shape[0]

        def scat(pleaf, vleaf):
            bs, S = pleaf.shape[2], vleaf.shape[2]
            rows = vleaf[:, jnp.arange(B), pos]   # (G, B, *tail)
            blk = jnp.take_along_axis(tables, (pos // bs)[:, None],
                                      axis=1)[:, 0]
            blk = jnp.where(pos < S, blk, 0)
            return pleaf.at[:, blk, pos % bs].set(rows.astype(pleaf.dtype))

        return next_tok[:, None], jax.tree.map(scat, pool, new_views)

    return jax.jit(step)


def _paged_step(cfg, s, B):
    from repro.configs.base import ShapeConfig
    from repro.runtime import steps as steps_mod
    return steps_mod.build_paged_decode(
        cfg, s["par"], s["mesh"], ShapeConfig("serve", S_STEP, B, "decode"),
        block_size=BS_STEP, num_blocks=NB_POOL)


def _bits(tree):
    import jax
    return [np.asarray(a).view(np.uint16) for a in jax.tree.leaves(tree)]


@pytest.mark.parametrize("arch, positions, scan", [
    ("phi4-mini-3.8b", [0, 7, 13], True),    # slot 0 parked: all-null table
    ("phi4-mini-3.8b", [S_STEP + 1, 5, 11], True),  # slot 0 past S-1
    ("phi4-mini-3.8b", [8, 4, 15], True),    # first rows of blocks 2 and 1
    ("gemma2-9b", [9, 14, 3], True),         # local + global, window 5
    ("phi4-mini-3.8b", [0, 6, S_STEP], False),  # layers unrolled, no scan
], ids=["parked", "past_end", "block_boundary", "local", "unrolled"])
def test_paged_step_matches_whole_view_step(serve_setup, arch, positions,
                                            scan):
    """On a random pool and block tables, the step that gathers each
    layer's view inside the layer scan and writes back only the new rows
    gives the tokens and the pool, bit for bit, of the step that gathered
    the whole pool's view first."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from repro.models import params as pr
    from repro.models import transformer as tfm
    from repro.runtime import steps as steps_mod
    s = dict(serve_setup,
             par=dataclasses.replace(serve_setup["par"], scan_layers=scan))
    cfg = _step_cfg(s, arch)
    B, nb = len(positions), S_STEP // BS_STEP
    rng = np.random.RandomState(sum(positions))
    params = pr.init_params(tfm.lm_schema(cfg), jax.random.key(1),
                            cfg.param_dtype)
    pool = jax.tree.map(
        lambda a: jnp.asarray(rng.standard_normal(a.shape), a.dtype),
        steps_mod.init_paged_cache(cfg, NB_POOL, BS_STEP))
    # each slot owns the blocks up to its write position; the tail is null
    owned = rng.permutation(np.arange(1, NB_POOL))
    tables = np.zeros((B, nb), np.int32)
    for i, p in enumerate(positions):
        if i == 0 and p == 0:
            continue                              # parked free slot
        n = min(p // BS_STEP + 1, nb)
        tables[i, :n] = owned[:n]
        owned = owned[n:]
    token = jnp.asarray(rng.randint(1, cfg.vocab_size, (B, 1)), jnp.int32)
    pos = jnp.asarray(positions, jnp.int32)
    tables = jnp.asarray(tables)

    before = _bits(pool)
    want_tok, want_pool = _whole_view_step(cfg, s["par"], s["mesh"])(
        params, pool, tables, token, pos)
    want = _bits(want_pool)
    got_tok, got_pool, counters, route = _paged_step(cfg, s, B).jit()(
        params, pool, tables, token, pos)           # donates the pool
    assert counters == {} and route is None
    np.testing.assert_array_equal(np.asarray(got_tok), np.asarray(want_tok))
    for g, w, b in zip(_bits(got_pool), want, before):
        np.testing.assert_array_equal(g, w)
        assert not np.array_equal(w, b)          # the step wrote rows


def test_paged_step_holds_no_whole_pool_view(serve_setup):
    """The compiled paged step holds one layer's (B, S, KV, dh) view at a
    time: no (layers, B, S, KV, dh) stack of views and no whole-pool
    gather, (B * nb, layers, bs, KV, dh) or (layers, B, nb, bs, KV, dh).
    The whole-view step shows them, so the shapes are the right ones to
    look for."""
    import re
    s = serve_setup
    cfg = _step_cfg(s, "phi4-mini-3.8b")
    G, B, nb = cfg.num_groups, 2, S_STEP // BS_STEP
    KV, dh = cfg.num_kv_heads, cfg.resolved_head_dim
    whole = [(G, B, S_STEP, KV, dh), (B * nb, G, BS_STEP, KV, dh),
             (G, B, nb, BS_STEP, KV, dh)]
    pattern = "|".join(re.escape("[" + ",".join(map(str, sh)) + "]")
                       for sh in whole)
    layer_view = re.escape(f"[{B},{S_STEP},{KV},{dh}]")
    bundle = _paged_step(cfg, s, B)
    text = bundle.lower().compile().as_text()
    assert not re.findall(pattern, text)
    assert re.search(layer_view, text)
    old = _whole_view_step(cfg, s["par"], s["mesh"]).lower(
        *bundle.abstract_args).compile().as_text()
    assert re.search(pattern, old)


def test_paged_prefix_reuse_hits_and_refcounts(serve_setup):
    """Identical prompts through one slot: the first request prefills and
    caches its prompt blocks, every later one retains them (hit) and
    replays only the uncached suffix through the decode step."""
    s = serve_setup
    engine = mk_engine(s, num_slots=1, paged=True, block_size=4,
                       prefix_cache=True, seed=0)
    reqs = prompt_requests(s["cfg"], [4, 4, 4], seed=3)
    same = reqs[0]["prompt"]
    for r in reqs:
        r["prompt"] = list(same)
    results, metrics = engine.run(WorkQueue(reqs))
    assert [len(results[i]) for i in range(3)] == [4, 4, 4]
    sm = metrics.summary()
    # nb_prompt=2, shareable capped at 1 block: requests 1 and 2 hit it
    assert sm["serve/prefix_hits"]["total"] == 2
    assert sm["serve/prefix_bytes_saved"]["total"] > 0
    # all slots drained: every block released back (cached ones at ref 0)
    assert engine.block_pool.in_use == 0
    assert engine.block_pool.cached_blocks >= 1


def test_paged_prefix_fleet_serves_everything_with_prefix_hits(serve_setup):
    """Real paged engines behind the session-affine router: a stream
    whose prompts share one block-aligned head is served whole, each
    request at its own stop length, and the replicas' prefix caches hit
    on the shared head."""
    s = serve_setup
    gens = [4, 1, 2, 1] * 4
    reqs = prompt_requests(s["cfg"], gens, seed=5, shared_prefix=4)
    for r in reqs:
        r["session"] = f"user-{r['id'] % 4}"
    results, metrics, _ = serve_replicated(
        lambda name, reg: mk_engine(s, paged=True, block_size=4,
                                    prefix_cache=True, registry=reg,
                                    seed=0),
        reqs, min_replicas=2, max_replicas=2, registry=Registry(),
        reconcile_interval=0.005, timeout_s=300.0)
    assert sorted(results) == list(range(len(gens)))
    assert [len(results[i]) for i in range(len(gens))] == gens
    assert metrics.series(GAUGES.COMPLETED).total == len(gens)
    assert metrics.series(GAUGES.PREFIX_HITS).total > 0
    assert metrics.series(GAUGES.TOK_S).last > 0


def test_paged_pool_pressure_preempts_youngest_and_recovers(serve_setup):
    """A pool too small for two full generations: the youngest slot is
    nacked back to the queue when the elder needs its next block, and
    every request still completes exactly."""
    s = serve_setup
    # nb_prompt=2, nb_total=4: each request needs up to 5 blocks; 6
    # usable blocks cannot hold two full generations at once
    engine = mk_engine(s, paged=True, block_size=4, prefix_cache=False,
                       pool_blocks=7, seed=0)
    reqs = prompt_requests(s["cfg"], [8, 8, 8], seed=1)
    queue = WorkQueue(reqs, max_attempts=100)
    results, metrics = engine.run(queue)
    assert sorted(results) == [0, 1, 2]
    assert all(len(v) == 8 for v in results.values())
    assert metrics.summary()["serve/preempted"]["total"] >= 1
    assert queue.drained() and not queue.dead
    assert engine.block_pool.in_use == 0


def test_engine_stop_nacks_within_one_step_not_one_timeout(serve_setup):
    """Cooperative stop with a huge visibility timeout: the in-flight
    requests must be re-servable immediately (nack), not after the
    lease expires — the preempted-replica acceptance bound."""
    s = serve_setup
    reqs = [{"id": i, "prompt": [1 + i] * 4, "max_new_tokens": 3}
            for i in range(4)]
    queue = WorkQueue(reqs, lease_timeout=1000.0)
    engine = mk_engine(s, prompt_len=4, max_new_tokens=3)
    calls = {"n": 0}

    def stop_after_two():
        calls["n"] += 1
        return calls["n"] > 2

    results, metrics = engine.run(queue, should_stop=stop_after_two)
    assert len(results) < 4
    assert queue.leased == 0            # nacked, not left to expire
    assert queue.pending == 4 - queue.completed
    # a replacement engine re-serves them NOW — no sleep, no timeout wait
    engine2 = mk_engine(s, prompt_len=4, max_new_tokens=3,
                        params=engine.params)
    results2, _ = engine2.run(queue)
    done = dict(results)
    done.update(results2)
    assert sorted(done) == [0, 1, 2, 3]
    assert queue.drained()


def test_engine_timing_rides_the_injected_clock(serve_setup):
    """All engine timing flows through self.clock: under a never-
    advancing fake clock every wall/TTFT stat is exactly zero even
    though real seconds elapsed."""
    s = serve_setup
    clock = FakeClock()
    engine = mk_engine(s, prompt_len=4, max_new_tokens=2, clock=clock)
    results, metrics = engine.run(
        WorkQueue([{"id": 0, "prompt": [1, 2], "max_new_tokens": 2}],
                  clock=clock))
    assert len(results[0]) == 2
    sm = metrics.summary()
    assert sm["serve/wall_s"]["last"] == 0.0
    assert sm["serve/ttft_s"]["last"] == 0.0
    assert sm["serve/request_latency_s"]["last"] == 0.0
    assert sm["serve/prefill_s"]["max"] == 0.0


def test_engine_spans_each_loop_iteration(serve_setup):
    """serve/schedule holds each admission's serve/prefill (whose span
    the serve/prefill_s gauge reads); each decode step is a serve/decode
    span split into upload -> launch -> readback."""
    from repro.serving.report import SPANS
    s = serve_setup
    engine = mk_engine(s, paged=True, block_size=4, prefix_cache=False)
    _, m = engine.run(WorkQueue(prompt_requests(s["cfg"], [4, 6, 3])))
    prefills = m.spans(SPANS.PREFILL)
    assert len(prefills) == 3
    assert all(p.parent.name == SPANS.SCHEDULE for p in prefills)
    assert [v for _, v in m.series(GAUGES.PREFILL_S).snapshot()] == \
        [p.duration for p in prefills]
    decodes = m.spans(SPANS.DECODE)
    assert len(decodes) == m.series(GAUGES.DECODE_STEPS).total > 0
    kids = [sp for sp in m.spans() if sp.parent is not None
            and sp.parent.name == SPANS.DECODE]
    assert len(kids) == 3 * len(decodes)
    for step in decodes:
        up, launch, back = [k for k in kids if k.parent is step]
        assert [up.name, launch.name, back.name] == [
            SPANS.UPLOAD, SPANS.LAUNCH, SPANS.READBACK]
        assert step.start <= up.start <= up.end <= launch.start \
            <= launch.end <= back.start <= back.end <= step.end
    assert all(sp.parent is None for sp in m.spans(SPANS.SCHEDULE))
    assert not set(SPANS.__dict__.values()) & {"decode_step", "prefill",
                                                "train_dispatch"}


def test_engine_gauges_the_live_share_of_the_views(serve_setup):
    """Each paged decode step gauges serve/kv_live_share: the blocks
    holding history below each slot's position (all S past the end),
    over the blocks of all the slots' views."""
    s = serve_setup
    engine = mk_engine(s, paged=True, block_size=4, prefix_cache=False)
    S, nb = engine.cache_len, engine.cache_len // 4
    share = engine.metrics.series(GAUGES.KV_LIVE_SHARE)
    engine.decode_step([1, 1], [5, 0])
    engine.decode_step([1, 1], [S + 3, 4])
    got = [v for _, v in share.snapshot()]
    assert got == [2 / (2 * nb), (nb + 1) / (2 * nb)]
    _, m = engine.run(WorkQueue(prompt_requests(s["cfg"], [4, 6, 3])))
    assert m is engine.metrics          # the two steps above, then the run's
    assert len(share.snapshot()) == 2 + m.series(GAUGES.DECODE_STEPS).total


def test_forced_slow_step_counts_and_carries_notes(serve_setup):
    """A decode step that the (injected) clock makes far slower than the
    running median counts in serve/slow_steps, and its span carries the
    process's context switches, CPU and garbage-collection time."""
    import gc
    import time as _time

    from repro.core.metrics import SLOW_MIN_STEPS
    from repro.serving.report import SPANS
    s = serve_setup
    skew = [0.0]
    reg = Registry(clock=lambda: _time.monotonic() + skew[0])
    engine = mk_engine(s, paged=True, block_size=4, prefix_cache=False,
                       num_slots=1, max_new_tokens=16, registry=reg)
    decode, calls = engine._paged_decode, [0]
    stall_at = SLOW_MIN_STEPS + 3

    def stalled(*a):
        calls[0] += 1
        if calls[0] == stall_at:
            skew[0] += 100.0
            gc.collect()
        return decode(*a)

    engine._paged_decode = stalled
    engine.run(WorkQueue(prompt_requests(s["cfg"], [16])))
    steps = reg.spans(SPANS.DECODE)
    assert len(steps) > stall_at
    stall = steps[stall_at - 1]
    assert stall.duration >= 100.0
    assert reg.series(GAUGES.SLOW_STEPS).total >= 1
    assert {"median_s", "invol_switches", "cpu_s", "gc_s"} <= \
        set(stall.attrs)
    assert stall.attrs["gc_s"] > 0 and stall.attrs["median_s"] < 100.0
