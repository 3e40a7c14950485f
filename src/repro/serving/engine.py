"""Continuous-batching serving engine (the device side).

The ContinuousScheduler (scheduler.py) decides *which* requests occupy
which decode slots; this engine owns everything jitted:

  * a B=1 prefill step — each admitted request is prefilled alone and its
    prompt-length KV/state cache is spliced into its slot of the big cache
    (runtime.steps.cache_batch_insert, donated so the splice is in-place);
  * one fused per-slot decode step (runtime.steps.build_slot_decode) that
    advances ALL active slots one token per call, each at its own sequence
    position — a request admitted mid-flight rides the very next step;
  * the cache itself — either the classic slotted layout (every cache
    leaf is (layers, slots, ...), slot i = row i of axis 1) or, when the
    model family supports it, a paged block pool (runtime.steps
    ``build_paged_decode``): slots address fixed-size blocks through
    per-slot block tables, a host-side ``BlockPool`` refcounts them, and
    a radix-style prefix cache lets requests sharing a system prompt skip
    re-prefilling shared blocks (the suffix replays through the fused
    decode step — chunked prefill).  Paged decode is bit-identical to the
    slotted baseline (tests/test_serving_paged.py).

Request lifecycle (see docs/architecture.md for the full diagram):

    queue --lease--> slot --prefill+insert--> decode step xN --evict/ack-->
      ^                                                          |
      '----------------- slot freed, next request refills <------'

The engine is deterministic given a queue and a clock; ``smoke``-size
configs run it on CPU in seconds (tests/test_serving.py).

Each loop iteration is timed by spans in the metrics registry (names in
``report.SPANS``, meanings in docs/serving.md): ``serve/schedule`` around
the host's policy work, with ``serve/prefill`` inside it for each
admission, and ``serve/decode`` around the fused step, split into
``serve/upload``, ``serve/launch`` and ``serve/readback``.  A decode step
far slower than its recent median counts in ``serve/slow_steps``
(``core.metrics.SlowSteps``).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, ParallelConfig, ShapeConfig
from repro.core.metrics import Registry, SlowSteps
from repro.core.queue import WorkQueue
from repro.serving.report import GAUGES, SPANS, record_serving_totals
from repro.models import params as pr
from repro.runtime import steps as steps_mod
from repro.serving.pool import BlockPool
from repro.serving.scheduler import ContinuousScheduler, Slot


class ServingEngine:
    """Owns params, jitted steps and the slotted cache for one model.

    Parameters
    ----------
    cfg, par, mesh:
        Model / parallelism config and the device mesh to serve on.
    num_slots:
        Decode-slot pool size == batch dim of the fused decode step.
    prompt_len:
        Fixed prompt pad length.  Prompts shorter than this are padded
        (token id 1), longer ones truncated — one prefill compilation.
    max_new_tokens:
        Cache headroom per slot: a slot can decode at most this many
        tokens (requests asking for more are clamped at admission).
    params:
        Optional pre-initialised params (e.g. restored from a
        checkpoint); randomly initialised from ``seed`` if omitted.
    paged:
        True forces the paged block pool (raises if the model family's
        cache is not paged-able), False forces the slotted cache, None
        (default) auto-selects paged whenever compatible.
    block_size:
        Tokens per KV block; must divide both the padded prompt length
        and the cache length for paged mode.
    pool_blocks:
        Total blocks in the pool (incl. the reserved null block).  The
        default sizes for all slots fully generated plus prefix-cache
        headroom; shrink it to exercise pressure eviction/preemption.
    prefix_cache:
        Enable radix-style prefix reuse across requests (paged only).

    After each prefill or decode step ``route`` holds a held-expert
    model's router choice there (``runtime.steps``: (B, T, layers,
    top_k) expert ids, on the device), and None for other models.
    """

    def __init__(self, cfg: ModelConfig, par: ParallelConfig, mesh, *,
                 num_slots: int = 4, prompt_len: int = 32,
                 max_new_tokens: int = 16, seed: int = 0, params=None,
                 registry: Optional[Registry] = None, clock=time.monotonic,
                 paged: Optional[bool] = None, block_size: int = 8,
                 pool_blocks: Optional[int] = None,
                 prefix_cache: bool = True):
        self.mesh = mesh
        self.num_slots = num_slots
        self.max_new_tokens = max_new_tokens
        # spans ride the registry's clock: the engine's own, unless the
        # caller brings a registry
        self.metrics = (registry if registry is not None
                        else Registry(clock=clock))
        self.clock = clock
        self._slow = SlowSteps(self.metrics, GAUGES.SLOW_STEPS)
        self.route = None

        S = prompt_len + max_new_tokens
        shape = ShapeConfig("serve", S, num_slots, "decode")
        self.cfg = cfg = steps_mod.resolve_cfg(cfg, shape)
        if cfg.family == "audio":
            # enc-dec: the decoder-position table IS the self-attn cache
            # (cache_schema sizes it to decoder_len regardless of S), so
            # prompt + generation must fit inside decoder_len — pad the
            # prompt short enough to leave max_new_tokens of headroom
            self.prompt_pad = max(1, min(prompt_len,
                                         cfg.decoder_len - max_new_tokens))
            self.cache_len = cfg.decoder_len
        else:
            self.prompt_pad = prompt_len
            self.cache_len = S

        mod = steps_mod._model_module(cfg)
        if params is None:
            params = pr.init_params(mod.lm_schema(cfg), jax.random.key(seed),
                                    cfg.param_dtype)
        self.params = params
        prefill_fn = steps_mod.build_prefill(
            cfg, par, mesh, ShapeConfig("serve", S, 1, "prefill")).fn

        # prefill + slot splice + argmax fused into ONE dispatch per
        # admission — admission cost is on the serving critical path
        # (every refill happens between fused decode steps)
        def prefill_insert(params, caches, prompt, slot, *extras):
            last, small, route = prefill_fn(params, prompt, *extras)
            caches = steps_mod.cache_batch_insert(caches, small, slot)
            return jnp.argmax(last[0], -1).astype(jnp.int32), caches, route

        self._prefill_insert = jax.jit(prefill_insert, donate_argnums=1)
        ex_abs, _ = steps_mod.extras_specs(cfg, 1)
        self._extras = (({k: jnp.zeros(v.shape, v.dtype)
                          for k, v in ex_abs.items()},) if ex_abs else ())

        compatible = (steps_mod.paged_compatible(cfg, self.cache_len,
                                                 block_size)
                      and self.prompt_pad % block_size == 0
                      and self.prompt_pad >= block_size)
        if paged and not compatible:
            raise ValueError(
                f"{cfg.family} cache cannot be paged with "
                f"block_size={block_size} (prompt_pad={self.prompt_pad}, "
                f"cache_len={self.cache_len})")
        self.paged = compatible if paged is None else bool(paged)
        self.block_size = block_size
        self.prefix_cache = bool(prefix_cache) and self.paged

        if self.paged:
            nb_total = self.cache_len // block_size
            nb_prompt = self.prompt_pad // block_size
            if pool_blocks is None:
                # all slots fully generated + prefix-cache headroom + null
                pool_blocks = 1 + num_slots * nb_total + 2 * nb_prompt
            if pool_blocks < 1 + nb_prompt + 1:
                raise ValueError(
                    f"pool_blocks={pool_blocks} cannot admit one request "
                    f"(needs {nb_prompt} prompt blocks + 1 gen + null)")
            self._nb_total = nb_total
            self._nb_prompt = nb_prompt
            self._pool = steps_mod.init_paged_cache(cfg, pool_blocks,
                                                    block_size)
            self._tables = np.zeros((num_slots, nb_total), np.int32)
            bytes_per_block = int(sum(
                leaf.size // pool_blocks * leaf.dtype.itemsize
                for leaf in jax.tree.leaves(self._pool)))
            self.block_pool = BlockPool(pool_blocks, block_size,
                                        bytes_per_block=bytes_per_block,
                                        registry=self.metrics)
            self._slot_meta = [None] * num_slots

            def paged_prefill_insert(params, pool, prompt, blocks, *extras):
                last, small, route = prefill_fn(params, prompt, *extras)
                pool = steps_mod.paged_prompt_insert(pool, small, blocks)
                return jnp.argmax(last[0], -1).astype(jnp.int32), pool, route

            self._paged_prefill = jax.jit(paged_prefill_insert,
                                          donate_argnums=1)
            self._paged_decode = steps_mod.build_paged_decode(
                cfg, par, mesh, shape, block_size=block_size,
                num_blocks=pool_blocks).jit()
            self._caches = None
        else:
            self.block_pool = None
            self._decode = steps_mod.build_slot_decode(
                cfg, par, mesh, shape).jit()
            self._caches = steps_mod.init_cache(cfg, num_slots, S)

    # ----------------------------------------------------------- jit steps
    def _pad_prompt(self, prompt) -> np.ndarray:
        row = np.ones((1, self.prompt_pad), np.int32)
        toks = list(prompt)[:self.prompt_pad]
        row[0, :len(toks)] = toks
        return row

    def prefill_into(self, slot_index: int, prompt) -> int:
        """Prefill one request alone and splice its cache into the slot
        (slotted) or its prompt blocks (paged).  Returns the first
        generated token."""
        with self.metrics.span(SPANS.PREFILL) as span:
            if self.paged:
                blocks = self._tables[slot_index, :self._nb_prompt]
                first, self._pool, self.route = self._paged_prefill(
                    self.params, self._pool,
                    jnp.asarray(self._pad_prompt(prompt)),
                    jnp.asarray(blocks), *self._extras)
            else:
                first, self._caches, self.route = self._prefill_insert(
                    self.params, self._caches,
                    jnp.asarray(self._pad_prompt(prompt)),
                    jnp.int32(slot_index), *self._extras)
            first = int(first)
        self.metrics.gauge(GAUGES.PREFILL_S, span.duration)
        return first

    def decode_step(self, tokens, positions) -> np.ndarray:
        """One fused greedy step over all slots.  ``tokens``/``positions``
        are per-slot (num_slots,) host lists; returns the new tokens."""
        m = self.metrics
        with m.span(SPANS.DECODE) as step:
            before = self._slow.begin()
            with m.span(SPANS.UPLOAD):
                tok = jnp.asarray(np.asarray(tokens, np.int32)[:, None])
                pos = jnp.asarray(np.asarray(positions, np.int32))
                tables = jnp.asarray(self._tables) if self.paged else None
            if self.paged:
                m.gauge(GAUGES.KV_LIVE_SHARE, self._live_share(positions))
            counters = {}
            with m.span(SPANS.LAUNCH):
                if self.paged:
                    out, self._pool, counters, self.route = (
                        self._paged_decode(self.params, self._pool, tables,
                                           tok, pos))
                else:
                    out, self._caches = self._decode(self.params,
                                                     self._caches, tok, pos)
                    self.route = None
            with m.span(SPANS.READBACK):
                new = np.asarray(out)[:, 0]
                for name, value in counters.items():
                    m.gauge(f"serve/{name}", float(value))
        self._slow.end(step, before)
        return new

    def warmup(self) -> None:
        """Compile the jitted paths (prefill+insert, decode) off the
        clock.  Two rounds: the first insert sees the freshly allocated
        (uncommitted) cache/pool, every later one sees a jit-output
        array — a different sharding signature, so one round would leave
        the second compile on the serving clock.  Paged warmup writes
        into blocks 1..nb_prompt with all-null tables — content that is
        either overwritten by the block's first owner or masked."""
        if self.paged:
            self._tables[0, :self._nb_prompt] = np.arange(
                1, 1 + self._nb_prompt)
        for _ in range(2):
            self.prefill_into(0, [1] * self.prompt_pad)
            self.decode_step([0] * self.num_slots, [0] * self.num_slots)
        if self.paged:
            self._tables[0] = 0

    # ------------------------------------------------------- paged plumbing
    def _live_share(self, positions) -> float:
        """The blocks of the slots' views that hold history (positions
        below each slot's own; ``kernels.paged_decode.history_blocks``
        with no window), over all the views' blocks: what the paged
        attention kernel reads of a layer, as a share of the views."""
        hist = np.minimum(np.asarray(positions), self.cache_len)
        return float(np.sum(-(-hist // self.block_size))) / self._tables.size

    def _admit_paged(self, sched: ContinuousScheduler, slot: Slot) -> bool:
        """Allocate blocks for an admitted request: retain cached shared
        prefix blocks, alloc fresh ones for the rest of the prompt plus
        the first generation block.  On a prefix hit the non-shared
        suffix replays through the fused decode step instead of a full
        prefill.  Returns False (and nacks the request) when the pool
        cannot satisfy the admission."""
        row = self._pad_prompt(slot.request.prompt)[0].tolist()
        shared = []
        if self.prefix_cache:
            # cap the shared prefix one block short of the full prompt so
            # the replay suffix is never empty — and so shared blocks are
            # strictly before every write position (no copy-on-write)
            shared = self.block_pool.match(
                row, max_blocks=self._nb_prompt - 1)
        fresh = self.block_pool.alloc(self._nb_prompt - len(shared) + 1)
        if fresh is None:
            self.block_pool.release(shared)
            sched.release_slot(slot)     # nack: retry when capacity frees
            return False
        blocks = shared + fresh
        self._slot_meta[slot.index] = {
            "blocks": blocks, "n_prompt": self._nb_prompt, "prompt": row}
        trow = np.zeros(self._nb_total, np.int32)
        trow[:len(blocks)] = blocks
        self._tables[slot.index] = trow
        if shared:
            sched.start_replay(slot, row[len(shared) * self.block_size:],
                               len(shared) * self.block_size)
        else:
            first = self.prefill_into(slot.index, slot.request.prompt)
            sched.start(slot, first, self.prompt_pad)
        return True

    def _ensure_paged_capacity(self, sched: ContinuousScheduler) -> None:
        """Lazily allocate each active slot's next generation block at a
        block boundary; under pool exhaustion preempt the *youngest* slot
        (nack — the request requeues) until the write fits."""
        for slot in sorted(sched.active(), key=lambda s: s.admitted_at):
            if slot.free:               # preempted earlier in this sweep
                continue
            bi = slot.pos // self.block_size
            if bi >= self._nb_total or self._tables[slot.index, bi] != 0:
                continue
            got = self.block_pool.alloc(1)
            while got is None:
                victims = [s for s in sched.active() if s is not slot]
                if not victims:
                    break
                sched.release_slot(max(victims, key=lambda s: s.admitted_at))
                got = self.block_pool.alloc(1)
            if got is None:
                sched.release_slot(slot)   # lone slot starved: requeue it
                continue
            self._tables[slot.index, bi] = got[0]
            self._slot_meta[slot.index]["blocks"].append(got[0])

    def _on_slot_release(self, slot: Slot, reason: str) -> None:
        """Scheduler release hook: free the slot's blocks; a completed
        request's prompt blocks go into the prefix cache first."""
        meta = self._slot_meta[slot.index]
        if meta is None:
            return
        self._slot_meta[slot.index] = None
        if reason == "completed" and self.prefix_cache:
            self.block_pool.cache_prefix(meta["prompt"],
                                         meta["blocks"][:meta["n_prompt"]])
        self.block_pool.release(meta["blocks"])
        self._tables[slot.index] = 0

    # ----------------------------------------------------------- main loop
    def run(self, queue: WorkQueue, *, worker: str = "server",
            default_max_new: Optional[int] = None, idle_wait: float = 1e-3,
            should_stop=None, exit_on_drain: bool = True
            ) -> Tuple[Dict[Any, list], Registry]:
        """Serve the queue to exhaustion with continuous batching.

        Admission, eviction and lease heartbeats happen between fused
        decode steps; a request that finishes early frees its slot for the
        next queued request immediately (no drain-then-refill barrier).
        Returns ``(results, metrics)`` with ``results[rid]`` the generated
        tokens (length == the request's stop length).

        ``should_stop`` (a zero-arg callable, e.g. ``PodCtx.should_stop``
        when the engine runs as a preemptible tenant pod under
        repro.vcluster) is polled between fused steps: when it goes true
        the loop nacks every in-flight request back to the queue and
        exits cleanly, so a re-placed engine resumes them immediately
        instead of waiting out the visibility timeout.
        """
        cap = self.cache_len - self.prompt_pad
        sched = ContinuousScheduler(
            queue, self.num_slots, worker=worker, registry=self.metrics,
            clock=self.clock,
            default_max_new=min(default_max_new or self.max_new_tokens, cap))
        if self.paged:
            sched.on_release = self._on_slot_release
        t_start = self.clock()
        decode_s = 0.0
        span = self.metrics.span
        with self.mesh:
            while True:
                with span(SPANS.SCHEDULE):
                    if should_stop is not None and should_stop():
                        # preempted between steps: nack every in-flight
                        # slot so a replacement engine re-serves them
                        # after one decode step, not one visibility timeout
                        self.metrics.inc(GAUGES.PREEMPTED)
                        sched.release_all()
                        break
                    for slot in sched.admit():
                        # engine capacity bounds the stop length: past
                        # prompt_pad+cap the cache has no row to write
                        if slot.request.max_new_tokens > cap:
                            slot.request = dataclasses.replace(
                                slot.request, max_new_tokens=cap)
                        if self.paged:
                            self._admit_paged(sched, slot)
                        else:
                            first = self.prefill_into(slot.index,
                                                      slot.request.prompt)
                            sched.start(slot, first, self.prompt_pad)
                    if not sched.active():
                        if sched.finished() and exit_on_drain:
                            break
                        # queue momentarily empty — a long-lived replica
                        # (exit_on_drain=False) idles here until its
                        # router feeds it more work or stops it
                        time.sleep(idle_wait)
                        continue
                    if self.paged:
                        self._ensure_paged_capacity(sched)
                        if not sched.active():
                            continue
                    tokens, positions = sched.last_tokens(), sched.positions()
                t0 = self.clock()
                toks = self.decode_step(tokens, positions)
                decode_s += self.clock() - t0
                with span(SPANS.SCHEDULE):
                    sched.observe(toks)
                    sched.renew_leases()
        wall = self.clock() - t_start
        results = sched.results()
        # useful throughput counts only acked completions; a stale-acked
        # duplicate's tokens are surfaced separately (serve/stale_tokens)
        record_serving_totals(self.metrics, sched.useful_tokens,
                              wall, decode_s)
        return results, self.metrics
