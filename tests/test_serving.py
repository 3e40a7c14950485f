"""Continuous-batching serving tests.

Scheduler policy (admission order, slot reuse, lease renewal, metrics) is
exercised with a fake clock and a fake engine — fully deterministic, no
devices.  The per-slot decode step and slotted-cache plumbing are checked
numerically on the smoke config, and one end-to-end serve run compares
continuous results against the engine-level invariants.
"""
import numpy as np
import pytest

from repro.core.metrics import Registry
from repro.core.queue import WorkQueue
from repro.serving.scheduler import ContinuousScheduler, Request


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


def mk_requests(gens, prompt=(5, 6, 7)):
    return [{"id": i, "prompt": list(prompt), "max_new_tokens": g}
            for i, g in enumerate(gens)]


def fake_serve(queue, num_slots, *, clock, step_cost=1.0, prefill_pos=8,
               renew=True, registry=None):
    """Drive a scheduler with a fake engine: token ids are synthesized,
    every fused decode step advances the fake clock by ``step_cost``."""
    sched = ContinuousScheduler(queue, num_slots, registry=registry,
                                clock=clock)
    trace = {"admitted": [], "completed": []}
    while True:
        for slot in sched.admit():
            trace["admitted"].append((slot.request.rid, slot.index))
            done = sched.start(slot, 1000 + slot.request.rid, prefill_pos)
            trace["completed"] += [rid for rid, _ in done]
        if not sched.active():
            if sched.finished():
                break
            clock.advance(step_cost)
            continue
        clock.advance(step_cost)
        toks = [1000 + s.request.rid if not s.free else 0
                for s in sched.slots]
        done = sched.observe(toks)
        trace["completed"] += [rid for rid, _ in done]
        if renew:
            sched.renew_leases()
    return sched, trace


# ------------------------------------------------------------- scheduler

def test_admission_is_fifo_and_fills_free_slots():
    clock = FakeClock()
    q = WorkQueue(mk_requests([3] * 5), clock=clock)
    sched, trace = fake_serve(q, 2, clock=clock)
    # requests admitted in queue order
    assert [rid for rid, _ in trace["admitted"]] == [0, 1, 2, 3, 4]
    assert len(sched.results()) == 5
    assert q.completed == 5 and q.drained()


def test_slot_reuse_after_early_stop():
    """A short request frees its slot, which the next queued request
    reuses immediately — while the long request keeps decoding."""
    clock = FakeClock()
    q = WorkQueue(mk_requests([10, 2, 2, 2]), clock=clock)
    sched, trace = fake_serve(q, 2, clock=clock)
    admitted = dict(trace["admitted"])           # rid -> slot index
    # r0 holds slot 0 throughout; r1, r2, r3 cycle through slot 1
    assert admitted[0] == 0
    assert admitted[1] == admitted[2] == admitted[3] == 1
    # short requests complete long before the straggler
    assert trace["completed"][:3] == [1, 2, 3]
    assert trace["completed"][-1] == 0
    # every request got exactly its stop length
    assert {rid: len(t) for rid, t in sched.results().items()} == \
        {0: 10, 1: 2, 2: 2, 3: 2}


def test_stop_length_one_completes_at_prefill():
    clock = FakeClock()
    q = WorkQueue(mk_requests([1, 1, 3]), clock=clock)
    sched, trace = fake_serve(q, 2, clock=clock)
    assert sched.results()[0] == [1000]
    assert sched.results()[1] == [1001]
    assert len(sched.results()[2]) == 3


def test_lease_renewal_keeps_slow_decode_leased():
    """A request that decodes longer than the visibility timeout survives
    because the scheduler heartbeats the lease between steps."""
    clock = FakeClock()
    q = WorkQueue(mk_requests([50]), lease_timeout=10.0, clock=clock)
    sched, _ = fake_serve(q, 1, clock=clock, step_cost=1.0)
    # 50 steps at 1s each >> 10s timeout; renewals must have happened and
    # the task must have completed on the FIRST attempt (never reclaimed)
    assert q.completed == 1
    assert len(sched.results()[0]) == 50
    s = sched.metrics.summary()
    assert s["serve/lease_renewals"]["total"] >= 4
    assert "serve/lease_lost" not in s
    assert "serve/stale_ack" not in s


def test_without_renewal_lease_expires_and_slot_dropped():
    clock = FakeClock()
    q = WorkQueue(mk_requests([50]), lease_timeout=10.0, clock=clock)
    sched = ContinuousScheduler(q, 1, clock=clock)
    [slot] = sched.admit()
    sched.start(slot, 1000, 8)
    clock.advance(11.0)                 # lease expires, never renewed
    assert q.lease("thief") is not None  # another worker reclaims the task
    assert sched.renew_leases() == 0     # renewal fails...
    assert sched.slots[0].free           # ...and the slot is dropped un-acked
    assert sched.metrics.summary()["serve/lease_lost"]["total"] == 1


def test_queue_renew_semantics():
    clock = FakeClock()
    q = WorkQueue([{"id": 0, "prompt": [1]}], lease_timeout=10.0, clock=clock)
    tid, _ = q.lease("w")
    assert not q.renew(tid, "other")        # wrong worker
    assert not q.renew(99, "w")             # unknown task
    clock.advance(8.0)
    assert q.renew(tid, "w")                # extends to t=18
    clock.advance(8.0)                      # t=16 < 18: still leased
    assert q.lease("thief") is None
    assert q.ack(tid, "w")
    clock.advance(100.0)
    assert q.drained()
    assert not q.renew(tid, "w")            # done tasks can't renew


def test_metrics_totals_under_fake_clock():
    clock = FakeClock()
    reg = Registry()
    gens = [4, 2, 3, 1]
    q = WorkQueue(mk_requests(gens), clock=clock)
    sched, _ = fake_serve(q, 2, clock=clock, step_cost=1.0, registry=reg)
    s = reg.summary()
    assert s["serve/admitted"]["total"] == 4
    assert s["serve/completed"]["total"] == 4
    assert s["serve/tokens_generated"]["total"] == sum(gens)
    # fused steps: slots {r0:4, r2:3} and {r1:2, r3:1} -> longest chain
    # drives the step count; occupancy is per-step active slots
    assert s["serve/decode_steps"]["total"] == s["serve/slot_occupancy"]["count"]
    assert s["serve/slot_occupancy"]["max"] <= 2
    # latency = admit -> completion on the same fake clock: r1 (2 tokens,
    # admitted at t=0, completes after its 1 decode step at t=1)
    assert s["serve/request_latency_s"]["p50"] >= 1.0
    assert s["serve/ttft_s"]["count"] == 4


def test_request_from_item_defaults():
    r = Request.from_item(7, {"prompt": [1, 2]}, default_max_new=5)
    assert r.rid == 7 and r.max_new_tokens == 5
    r2 = Request.from_item(0, Request(rid="x", prompt=(1,), max_new_tokens=2))
    assert r2.rid == "x"


# ------------------------------------------------- slotted cache / decode

@pytest.fixture(scope="module")
def smoke_setup():
    import jax
    from repro.configs import registry
    from repro.configs.base import ShapeConfig
    from repro.launch.mesh import single_device_mesh
    from repro.models import params as pr
    from repro.runtime import steps as steps_mod

    arch = "phi4-mini-3.8b"
    cfg = registry.get_smoke(arch)
    par = registry.get_parallel(arch)
    mesh = single_device_mesh()
    Pp, G, B = 8, 4, 2
    S = Pp + G
    cfg = steps_mod.resolve_cfg(cfg, ShapeConfig("s", S, B, "prefill"))
    mod = steps_mod._model_module(cfg)
    params = pr.init_params(mod.lm_schema(cfg), jax.random.key(0),
                            cfg.param_dtype)
    return dict(cfg=cfg, par=par, mesh=mesh, params=params,
                Pp=Pp, G=G, B=B, S=S)


def test_slot_decode_matches_scalar_decode(smoke_setup):
    """Vector-position decode with all rows at the same position must equal
    the classic scalar-position whole-batch decode, token for token."""
    import jax
    import jax.numpy as jnp
    from repro.configs.base import ShapeConfig
    from repro.runtime import steps as steps_mod

    s = smoke_setup
    cfg, par, mesh, params = s["cfg"], s["par"], s["mesh"], s["params"]
    Pp, G, B, S = s["Pp"], s["G"], s["B"], s["S"]
    prefill = steps_mod.build_prefill(
        cfg, par, mesh, ShapeConfig("s", S, B, "prefill")).jit()
    dec_s = steps_mod.build_decode(
        cfg, par, mesh, ShapeConfig("s", S, B, "decode")).jit()
    dec_v = steps_mod.build_slot_decode(
        cfg, par, mesh, ShapeConfig("s", S, B, "decode")).jit()

    rng = np.random.RandomState(0)
    prompts = rng.randint(1, cfg.vocab_size, (B, Pp)).astype(np.int32)
    with mesh:
        last, small, _ = prefill(params, jnp.asarray(prompts))
        tok = jnp.argmax(last, -1).astype(jnp.int32)[:, None]
        pad = jax.jit(steps_mod.cache_prefix_insert)
        cache_a = pad(steps_mod.init_cache(cfg, B, S), small)
        cache_b = pad(steps_mod.init_cache(cfg, B, S), small)
        ta, tb = tok, tok
        for g in range(G):
            ta, cache_a = dec_s(params, cache_a, ta, jnp.int32(Pp + g))
            tb, cache_b = dec_v(params, cache_b, tb,
                                jnp.full((B,), Pp + g, jnp.int32))
            np.testing.assert_array_equal(np.asarray(ta), np.asarray(tb))


def test_slot_isolation_and_reuse(smoke_setup):
    """A request decoded alone in slot 1 (slot 0 idle, then refilled with a
    different request mid-flight) produces the same tokens as in the
    all-rows-equal batched run — slots are independent."""
    import jax
    import jax.numpy as jnp
    from repro.configs.base import ShapeConfig
    from repro.runtime import steps as steps_mod

    s = smoke_setup
    cfg, par, mesh, params = s["cfg"], s["par"], s["mesh"], s["params"]
    Pp, G, B, S = s["Pp"], s["G"], s["B"], s["S"]
    prefill1 = steps_mod.build_prefill(
        cfg, par, mesh, ShapeConfig("s", S, 1, "prefill")).jit()
    dec_v = steps_mod.build_slot_decode(
        cfg, par, mesh, ShapeConfig("s", S, B, "decode")).jit()

    rng = np.random.RandomState(0)
    p0 = rng.randint(1, cfg.vocab_size, (1, Pp)).astype(np.int32)
    p1 = rng.randint(1, cfg.vocab_size, (1, Pp)).astype(np.int32)

    def solo_reference(prompt):
        last, small, _ = prefill1(params, jnp.asarray(prompt))
        tok = jnp.argmax(last, -1).astype(jnp.int32)[:, None]
        cache = steps_mod.cache_batch_insert(
            steps_mod.init_cache(cfg, B, S), small, 0)
        toks, pos = [int(tok[0, 0])], np.array([Pp, 0], np.int32)
        t = jnp.concatenate([tok, jnp.zeros((B - 1, 1), jnp.int32)])
        for _ in range(G - 1):
            t, cache = dec_v(params, cache, t, jnp.asarray(pos))
            toks.append(int(t[0, 0]))
            pos[0] += 1
        return toks

    with mesh:
        ref0 = solo_reference(p0)
        ref1 = solo_reference(p1)

        # now interleave: r0 in slot 0; after 2 steps admit r1 into slot 1
        last, small, _ = prefill1(params, jnp.asarray(p0))
        cache = steps_mod.cache_batch_insert(
            steps_mod.init_cache(cfg, B, S), small, 0)
        t = jnp.concatenate(
            [jnp.argmax(last, -1).astype(jnp.int32)[:, None],
             jnp.zeros((B - 1, 1), jnp.int32)])
        pos = np.array([Pp, 0], np.int32)
        got0 = [int(t[0, 0])]
        for _ in range(2):
            t, cache = dec_v(params, cache, t, jnp.asarray(pos))
            got0.append(int(t[0, 0]))
            pos[0] += 1
        # admit r1 into slot 1 mid-flight
        last1, small1, _ = prefill1(params, jnp.asarray(p1))
        cache = steps_mod.cache_batch_insert(cache, small1, 1)
        t = jnp.stack([t[0], jnp.argmax(last1[0], -1).astype(jnp.int32)[None]])
        pos[1] = Pp
        got1 = [int(t[1, 0])]
        for _ in range(G - 1):
            t, cache = dec_v(params, cache, t, jnp.asarray(pos))
            if len(got0) < G:
                got0.append(int(t[0, 0]))
            got1.append(int(t[1, 0]))
            pos += 1
    assert got0 == ref0          # r0 unaffected by the mid-flight admission
    assert got1 == ref1          # r1 unaffected by r0's occupancy


def test_cache_insert_evict_roundtrip():
    import jax.numpy as jnp
    from repro.runtime import steps as steps_mod

    big = {"k": jnp.zeros((2, 3, 4, 2, 2)), "s": jnp.zeros((2, 3, 5))}
    small = {"k": jnp.ones((2, 1, 2, 2, 2)),   # shorter seq axis than dst
             "s": jnp.ones((2, 1, 5))}
    out = steps_mod.cache_batch_insert(big, small, 1)
    assert float(out["k"][:, 1, :2].min()) == 1.0
    assert float(out["k"][:, 1, 2:].max()) == 0.0   # tail untouched
    assert float(out["k"][:, 0].max()) == 0.0       # other slots untouched
    assert float(out["s"][:, 1].min()) == 1.0
    out = steps_mod.cache_batch_evict(out, 1)
    assert float(out["k"].max()) == 0.0 and float(out["s"].max()) == 0.0


# ------------------------------------------------------------ end to end

def test_continuous_serve_end_to_end():
    """Heterogeneous stop lengths through the real engine on the smoke
    config: every request completes at exactly its stop length and the
    metrics totals agree with the results."""
    from repro.launch.serve import apply_serve, serve_job

    gens = [6, 2, 4, 1, 6]
    out = apply_serve(serve_job("phi4-mini-3.8b", smoke=True, n_requests=5,
                                prompt_len=8, gen=6, batch=2,
                                gen_lens=gens))
    results, metrics = out["results"], out["metrics"]
    assert sorted(results) == [0, 1, 2, 3, 4]
    assert [len(results[i]) for i in range(5)] == gens
    s = metrics.summary()
    assert s["serve/completed"]["total"] == 5
    assert s["serve/tokens_generated"]["total"] == sum(gens)
    assert s["serve/slot_occupancy"]["max"] <= 2
    assert s["serve/request_latency_s"]["count"] == 5


def test_engine_preemption_stops_cleanly_and_requeues():
    """A preempted serving pod (repro.vcluster) exits between fused
    steps without acking in-flight work: those leases expire back to the
    queue, and a re-placed engine serves every request to completion."""
    from repro.configs import registry
    from repro.core.queue import WorkQueue
    from repro.launch.mesh import single_device_mesh
    from repro.serving import ServingEngine

    cfg = registry.get_smoke("phi4-mini-3.8b")
    par = registry.get_parallel("phi4-mini-3.8b")
    mesh = single_device_mesh()
    reqs = [{"id": i, "prompt": [1 + i] * 4, "max_new_tokens": 3}
            for i in range(4)]
    queue = WorkQueue(reqs, lease_timeout=0.05)

    engine = ServingEngine(cfg, par, mesh, num_slots=2, prompt_len=4,
                           max_new_tokens=3)
    calls = {"n": 0}

    def stop_after_two():
        calls["n"] += 1
        return calls["n"] > 2           # a couple of steps, then evicted

    results, metrics = engine.run(queue, should_stop=stop_after_two)
    assert metrics.series("serve/preempted").points
    assert len(results) < 4             # interrupted mid-stream
    assert not queue.drained()
    # "re-placed" engine (fresh slots/caches) picks up the expired leases
    import time as _t
    _t.sleep(0.06)                      # let the in-flight leases expire
    engine2 = ServingEngine(cfg, par, mesh, num_slots=2, prompt_len=4,
                            max_new_tokens=3)
    results2, _ = engine2.run(queue)
    done = dict(results)
    done.update(results2)
    assert sorted(done) == [0, 1, 2, 3]
    assert all(len(v) == 3 for v in done.values())
    assert queue.drained()


def test_continuous_serve_audio_family():
    """Enc-dec (whisper) serving: the decoder-position table is the self
    cache, so the engine must budget prompt + generation inside
    decoder_len — a regression here silently no-ops every generated
    token's K/V write."""
    from repro.launch.serve import apply_serve, serve_job

    gens = [4, 2, 1]
    out = apply_serve(serve_job("whisper-small", smoke=True, n_requests=3,
                                prompt_len=8, gen=4, batch=2,
                                gen_lens=gens))
    results, metrics = out["results"], out["metrics"]
    assert [len(results[i]) for i in range(3)] == gens
    assert metrics.summary()["serve/tokens_generated"]["total"] == sum(gens)
