"""Continuous batching over the DecodeStep contract, built for traffic.

ESE/Spartus-style request-level serving, rebuilt around `repro.traffic`:
the scheduler owns a preallocated pool of decode *slots* over one shared
cache (`traffic.pool.SlotPool` — recurrent O(1) state makes hundreds of
slots cheap), a priority/deadline admission queue with overload shedding
(`traffic.admission.AdmissionQueue`), and a dispatch-ahead chunk pipeline
(`traffic.dispatch.DispatchQueue`):

  submit → admission queue → (slots free?) bucketed/batched prefill →
  join: the prefilled cache rows, last logits, positions, done flags and
  token budgets are scattered into the shared device state at the slots →
  decode: all slots step together in on-device scan chunks; ``done`` and
  ``budget`` live ON DEVICE and chain across chunks, so chunk N+1 can be
  dispatched before chunk N's tokens ever reach the host →
  harvest: the oldest in-flight chunk's tokens sync (the one host round
  trip), stream out through per-token callbacks/events, and finished or
  past-deadline slots are evicted back to the pool.

With ``dispatch_depth`` ≥ 2 (the default) the host enqueues the next
chunk — admissions included — while the device runs the current one
(donated-buffer double buffering), the TPU analogue of the paper's
computation overlapping. Depth 1 reproduces the synchronous
chunk-per-sync baseline; both schedules decode every request
bit-identically under greedy sampling (the device-resident done/budget
vectors freeze finished slots regardless of when the host notices).

Prefill compiles once per power-of-two length bucket, not once per
distinct prompt length: prompts are right-padded to the bucket and the
model's ``length=``-aware prefill masks the padded tail out of the state
(bitwise-exact; models without a ``length`` parameter fall back to
exact-length prefill). Same-bucket requests prefill together in one
batched call (``prefill_batch``).
"""
from __future__ import annotations

import dataclasses
import inspect
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from . import runtime
from .sampling import SamplingConfig
from ..obs import counters as obs_counters
from ..obs import trace as obs_trace
from ..traffic import (AdmissionQueue, DispatchQueue, QueuedRequest,
                       SlotInfo, SlotPool)

__all__ = ["Request", "Finished", "TokenEvent", "ContinuousBatchingEngine"]


@dataclasses.dataclass
class Request:
    uid: int
    prompt: Any                 # (1, S) int32 tokens
    max_new: int
    extra: Any = None           # family-specific conditioning (frames, ...)
    deadline: float | None = None
    priority: int = 0


@dataclasses.dataclass
class Finished:
    uid: int
    tokens: np.ndarray          # emitted ids, EOS included if hit
    prompt_len: int
    reason: str = "done"        # done | expired | rejected


@dataclasses.dataclass
class TokenEvent:
    """Incremental output: tokens harvested for ``uid`` this chunk."""
    uid: int
    tokens: list
    first: bool                 # True on the request's first emitted tokens


def _bucket(n: int, cap: int) -> int:
    """Next power of two ≥ n, capped at ``cap``."""
    b = 1
    while b < n:
        b <<= 1
    return min(b, cap)


class ContinuousBatchingEngine:
    """Continuous batching for any DecodeStep model.

    ``params`` may be dense, pruned, or SparsityPlan.pack'd — the model's
    decode_step dispatches (the BRDS LSTM runs rb_dual_spmv + lstm_gates
    on packed params).

    ``mesh`` turns on sharded serving (repro.dist): the slot batch runs
    data-parallel over the mesh's ``data`` axis with model-parallel row
    shards inside each replica group; ``params`` must then be
    ``repro.dist.partition_lstm_params``' layout.

    Traffic controls (all keyword-only):

    - ``slots``: pool size. Recurrent models keep O(1) state per slot, so
      hundreds are cheap.
    - ``dispatch_depth``: in-flight decode chunks (1 = synchronous
      baseline, 2 = dispatch-ahead double buffering, the default).
    - ``prefill_batch``: same-bucket admissions prefilled per call.
      Keep 1 when serving uncalibrated q8 params (their dynamic max-abs
      fallback reduces over the prefill batch; calibrated plans — the
      real serving path — are exact at any batch).
    - ``bucket_prompts``: pad prompts to power-of-two buckets when the
      model's prefill is ``length``-aware (one compile per bucket).
    - ``max_queue``: bound the admission queue; overload sheds the worst
      waiting request (reason ``"rejected"``) instead of queueing
      unboundedly.
    - ``clock``: time source for deadlines/admission (default
      ``time.perf_counter``; tests inject virtual clocks).
    - ``on_token``: per-token streaming callback
      ``(uid, tokens: list[int], first: bool)`` invoked at harvest.
    - ``counters``: thread the ``repro.obs`` on-device counter vector
      (decode steps, emitted tokens, spec acceptance, delta fired-column
      gauges) through every chunk dispatch. The vector rides the dispatch
      queue next to each chunk's token future and is read at the chunk's
      EXISTING harvest sync — zero extra device→host transfers, zero new
      sync points. ``counters()`` returns the harvested dict. Off (the
      default) compiles exactly the uninstrumented chunk function.
    - ``draft``: a ``repro.spec.DraftModel`` switches every decode chunk
      to speculative rounds (``spec_k`` proposals per round): each slot
      carries the draft's recurrent state alongside its cache rows, a
      partial acceptance rolls both back, and chunks chain through the
      carried next-token distribution exactly as plain chunks chain
      through logits. Greedy token streams are bitwise identical to
      ``draft=None``.
    """

    def __init__(self, model, params, *, slots: int = 4, max_len: int = 256,
                 sampling: SamplingConfig = SamplingConfig(),
                 chunk: int = 8, seed: int = 0, mesh=None,
                 dispatch_depth: int = 2, prefill_batch: int = 1,
                 bucket_prompts: bool = True, max_queue: int | None = None,
                 clock: Callable[[], float] | None = None,
                 on_token: Callable[[int, list, bool], None] | None = None,
                 draft=None, spec_k: int = 4, counters: bool = False):
        if not runtime.conforms(model):
            raise TypeError(
                f"{type(model).__name__} does not implement the DecodeStep "
                "serving contract (cache_defs / prefill / decode_step)")
        if mesh is not None and getattr(model, "mesh", None) is None:
            if not hasattr(model, "with_mesh"):
                raise TypeError(f"{type(model).__name__} has no sharded "
                                "decode path (with_mesh)")
            model = model.with_mesh(mesh)
        if getattr(model, "mesh", None) is not None:
            # the permuted dist layout is invisible in the tree structure;
            # reject packed-but-unpartitioned params before they decode
            # garbage silently
            from ..dist import check_partitioned
            check_partitioned(params, model.mesh)
        self.model = model
        self.params = params
        self.slots = slots
        self.max_len = max_len
        self.sampling = sampling
        self.chunk = chunk
        self.prefill_batch = max(1, prefill_batch)
        self.bucket_prompts = bucket_prompts
        self.on_token = on_token
        self._clock = clock or time.perf_counter
        if draft is not None and mesh is not None:
            raise ValueError("speculative decoding does not compose with "
                             "sharded serving (mesh) yet")
        self.draft = draft
        self.spec_k = spec_k
        # bucketed joint prefill needs BOTH models' length-masked paths
        self._length_aware = runtime.prefill_accepts_length(model) and (
            draft is None or runtime.prefill_accepts_length(draft.model))

        # ----- device-resident shared state (chained across dispatches)
        self.cache = model.init_cache(slots, max_len)
        # per-leaf batch axis: cache leaves may be layer-stacked (scanned
        # blocks put 'layers' ahead of 'batch'), so the slot join can't
        # assume axis 0 — the cache defs carry the logical axis names.
        from ..models import layers as L
        self._batch_axes = jax.tree.map(
            lambda d: d.axes.index("batch"),
            model.cache_defs(slots, max_len), is_leaf=L.is_pspec)
        self.pos = jnp.zeros((slots,), jnp.int32)
        self.logits = None                      # (slots, 1, V), lazy init
        self.rng = jax.random.key(seed)
        self.done = jnp.ones((slots,), bool)    # idle slots sit done
        self.budget = jnp.zeros((slots,), jnp.int32)

        # ----- host-side traffic machinery
        self.pool = SlotPool(slots)
        self._aq = AdmissionQueue(max_queue)
        self._dq = DispatchQueue(dispatch_depth)
        self._live: dict[int, SlotInfo] = {}    # uid → seated record
        self._collected: dict[int, list[int]] = {}
        self._drops: list[Finished] = []        # shed at submit time
        self._next_uid = 0
        self.steps_dispatched = 0               # device dispatches (chunks)
        # steps the current occupant's cache has accumulated (prefill +
        # chunk decodes) — the divisor for per-slot occupancy accounting
        self.slot_steps = np.zeros(slots, np.int64)

        # ----- on-device observability counters (repro.obs): a small
        # named vector chained across dispatches exactly like done/budget;
        # disabled (None) keeps the jitted chunk fn byte-identical
        self._counter_names = (obs_counters.counter_names(model)
                               if counters else None)
        self.counters_dev = (obs_counters.zeros(self._counter_names)
                             if counters else None)
        self._counters_host: dict | None = None

        self._prefill = jax.jit(model.prefill, static_argnames=("max_len",))
        self._join = jax.jit(self._join_impl, donate_argnums=(0, 1, 2, 3, 4))
        self._chunk_fn = jax.jit(
            self._chunk_obs_impl if counters else self._chunk_impl,
            donate_argnums=(1,))
        self._evict_fn = jax.jit(
            lambda done, s: done.at[s].set(True), donate_argnums=(0,))

        # ----- speculative-decode state (per-slot draft state + the
        # carried next-token distribution replacing chained logits)
        if draft is not None:
            from ..models import layers as L2
            self.dstate = draft.init_cache(slots, max_len)
            self._d_batch_axes = jax.tree.map(
                lambda d: d.axes.index("batch"),
                draft.model.cache_defs(slots, max_len), is_leaf=L2.is_pspec)
            self.probs = None                   # (slots, V) fp32, lazy init
            self._rounds = jnp.zeros((slots,), jnp.int32)
            self._drafted = jnp.zeros((slots,), jnp.int32)
            self._accepted = jnp.zeros((slots,), jnp.int32)
            self._dprefill = jax.jit(draft.prefill,
                                     static_argnames=("max_len",))
            self._join_spec = jax.jit(self._join_spec_impl,
                                      donate_argnums=(0, 1, 2, 3, 4, 5))
            self._chunk_spec_fn = jax.jit(
                self._chunk_spec_obs_impl if counters
                else self._chunk_spec_impl, donate_argnums=(2, 3))

    # ------------------------------------------------------------- device
    def _join_impl(self, cache, logits, pos, done, budget, pre_cache,
                   pre_logits, slots_v, lengths_v, budgets_v):
        """Scatter a batch of prefill results into the shared state at
        ``slots_v`` and arm those slots (done=False, fresh budget)."""
        def upd(c, p, ax):
            cm = jnp.moveaxis(c, ax, 0)
            pm = jnp.moveaxis(p.astype(c.dtype), ax, 0)
            return jnp.moveaxis(cm.at[slots_v].set(pm), 0, ax)

        cache = jax.tree.map(upd, cache, pre_cache, self._batch_axes)
        logits = logits.at[slots_v].set(pre_logits.astype(logits.dtype))
        pos = pos.at[slots_v].set(lengths_v)
        done = done.at[slots_v].set(False)
        budget = budget.at[slots_v].set(budgets_v)
        return cache, logits, pos, done, budget

    def _chunk_impl(self, params, cache, logits, pos, rng, done, budget):
        toks, st = runtime.decode_loop(
            self.model, params, cache, logits, pos, rng, self.chunk,
            self.sampling, done=done, budget=budget, limit=self.max_len)
        # budget lives on device so the next chunk can dispatch before
        # this one's tokens reach the host
        st["budget"] = jnp.maximum(budget - st["emitted"], 0)
        return toks, st

    def _chunk_obs_impl(self, params, cache, logits, pos, rng, done,
                        budget, counters):
        """The counter-threaded chunk: the plain chunk body plus in-graph
        counter folds (pure extra adds — same dispatch, same sync)."""
        toks, st = self._chunk_impl(params, cache, logits, pos, rng, done,
                                    budget)
        st["counters"] = obs_counters.chunk_update(
            self._counter_names, counters, st, self.chunk)
        return toks, st

    def _join_spec_impl(self, cache, dstate, probs, pos, done, budget,
                        pre_cache, pre_dstate, pre_logits, slots_v,
                        lengths_v, budgets_v):
        """The speculative join: scatter target cache rows AND draft state
        rows at ``slots_v``, and seed the carried distribution from the
        prefill logits (the spec loop's analogue of chained logits)."""
        from .sampling import sample_dist

        def upd(c, p, ax):
            cm = jnp.moveaxis(c, ax, 0)
            pm = jnp.moveaxis(p.astype(c.dtype), ax, 0)
            return jnp.moveaxis(cm.at[slots_v].set(pm), 0, ax)

        cache = jax.tree.map(upd, cache, pre_cache, self._batch_axes)
        dstate = jax.tree.map(upd, dstate, pre_dstate, self._d_batch_axes)
        probs = probs.at[slots_v].set(
            sample_dist(pre_logits[:, -1], self.sampling))
        pos = pos.at[slots_v].set(lengths_v)
        done = done.at[slots_v].set(False)
        budget = budget.at[slots_v].set(budgets_v)
        return cache, dstate, probs, pos, done, budget

    def _chunk_spec_impl(self, params, dparams, cache, dstate, probs, pos,
                         rng, done, budget):
        from ..spec import spec_decode_loop
        toks, st = spec_decode_loop(
            self.model, self.draft, params, dparams, cache, dstate, probs,
            pos, rng, self.chunk, self.spec_k, self.sampling, done=done,
            budget=budget, limit=self.max_len)
        st["budget"] = jnp.maximum(budget - st["emitted"], 0)
        return toks, st

    def _chunk_spec_obs_impl(self, params, dparams, cache, dstate, probs,
                             pos, rng, done, budget, counters):
        toks, st = self._chunk_spec_impl(params, dparams, cache, dstate,
                                         probs, pos, rng, done, budget)
        st["counters"] = obs_counters.chunk_update(
            self._counter_names, counters, st, self.chunk)
        return toks, st

    # -------------------------------------------------------------- admit
    def submit(self, prompt, max_new: int, extra=None, *,
               deadline: float | None = None, priority: int = 0) -> int:
        """Queue one request. prompt: (S,) or (1, S) int tokens.

        ``deadline`` is an absolute clock() time — past-deadline requests
        are shed from the queue and evicted from slots; ``priority``
        orders admission (higher first). Overload (a full ``max_queue``)
        sheds the worst waiting request with reason ``"rejected"``.
        """
        prompt = np.asarray(prompt, np.int32)
        if prompt.ndim == 1:
            prompt = prompt[None, :]
        if prompt.shape[1] >= self.max_len:
            raise ValueError(f"prompt length {prompt.shape[1]} ≥ max_len "
                             f"{self.max_len}")
        uid = self._next_uid
        self._next_uid += 1
        shed = self._aq.push(QueuedRequest(
            uid, prompt, prompt.shape[1], max_new, extra, deadline,
            priority, self._clock()))
        if shed is not None:
            self._drops.append(Finished(shed.uid, np.zeros(0, np.int32),
                                        shed.prompt_len, "rejected"))
        return uid

    @property
    def active_slots(self) -> list[int]:
        return self.pool.active()

    @property
    def _slot_uid(self) -> list[int | None]:
        return self.pool.owners()

    @property
    def pending(self) -> int:
        return len(self._aq)

    @property
    def busy(self) -> bool:
        """Whether step() still has work (queued, decoding, in flight, or
        undelivered shed notices)."""
        return bool(self._aq or self._live or self._dq or self._drops)

    def _admit(self, now: float) -> list[Finished]:
        """Admit queued requests into free slots: expire stale ones, group
        by prefill bucket, prefill (batched where exact), join."""
        events = [Finished(r.uid, np.zeros(0, np.int32), r.prompt_len,
                           "expired") for r in self._aq.expire(now)]
        if not (self.pool.free_count and self._aq):
            return events
        with obs_trace.span("sched.admit", queued=len(self._aq),
                            free=self.pool.free_count):
            while self.pool.free_count and self._aq:
                batch = self._aq.pop(min(self.pool.free_count,
                                         self.prefill_batch))
                for group in self._group(batch):
                    self._prefill_join(group, now)
        return events

    def _group(self, batch: list[QueuedRequest]):
        """Split admitted requests into joint-prefill groups: same padded
        bucket, no extra conditioning. Models without length-aware
        prefill (or with bucketing off) prefill one by one at exact
        length — batching would change their prefill numerics."""
        if not (self._length_aware and self.bucket_prompts):
            return [[r] for r in batch]
        groups: dict[int, list] = {}
        singles: list[list] = []
        for r in batch:
            if r.extra is not None:
                singles.append([r])
            else:
                key = _bucket(r.prompt_len, self.max_len - 1)
                groups.setdefault(key, []).append(r)
        return list(groups.values()) + singles

    def _prefill_span(self, group: list[QueuedRequest], width: int,
                      lengths: list[int], now: float):
        """The group's ``sched.prefill`` span; its per-request lists are
        built only while the tracer records."""
        if not obs_trace.enabled():
            return obs_trace.NULL
        return obs_trace.span(
            "sched.prefill", bucket=width, batch=len(group),
            uids=[r.uid for r in group], lengths=lengths,
            waited_ms=[1e3 * (now - r.arrival) for r in group])

    def _prefill_join(self, group: list[QueuedRequest], now: float):
        k = len(group)
        lengths = [r.prompt_len for r in group]
        budgets = [min(r.max_new, self.max_len - r.prompt_len)
                   for r in group]
        slots = self.pool.alloc_many(k)
        assert len(slots) == k      # _admit popped at most free_count
        bucketed = self._length_aware and self.bucket_prompts
        width = (_bucket(max(lengths), self.max_len - 1) if bucketed
                 else lengths[0])
        lengths_v = jnp.asarray(lengths, jnp.int32)
        with self._prefill_span(group, width, lengths, now):
            if bucketed:
                padded = np.zeros((k, width), np.int32)
                for i, r in enumerate(group):
                    padded[i, :r.prompt_len] = r.prompt[0]
                padded = jnp.asarray(padded)
                lp, pre_cache = self._prefill(
                    self.params, padded, max_len=self.max_len,
                    extra=group[0].extra, length=lengths_v)
            else:
                lp, pre_cache = self._prefill(
                    self.params, jnp.asarray(group[0].prompt),
                    max_len=self.max_len, extra=group[0].extra)
            if self.draft is not None:
                if bucketed:
                    _, pre_d = self._dprefill(
                        self.draft.params, padded, max_len=self.max_len,
                        length=lengths_v)
                else:
                    _, pre_d = self._dprefill(
                        self.draft.params, jnp.asarray(group[0].prompt),
                        max_len=self.max_len)
        slots_v = jnp.asarray(slots, jnp.int32)
        budgets_v = jnp.asarray(budgets, jnp.int32)
        if self.draft is not None:
            if self.probs is None:
                self.probs = jnp.zeros((self.slots, lp.shape[-1]),
                                       jnp.float32)
            (self.cache, self.dstate, self.probs, self.pos, self.done,
             self.budget) = self._join_spec(
                self.cache, self.dstate, self.probs, self.pos, self.done,
                self.budget, pre_cache, pre_d, lp, slots_v, lengths_v,
                budgets_v)
        else:
            if self.logits is None:
                self.logits = jnp.zeros((self.slots,) + lp.shape[1:],
                                        lp.dtype)
            self.cache, self.logits, self.pos, self.done, self.budget = \
                self._join(self.cache, self.logits, self.pos, self.done,
                           self.budget, pre_cache, lp, slots_v, lengths_v,
                           budgets_v)
        for r, slot, budget in zip(group, slots, budgets):
            info = SlotInfo(r.uid, r.prompt_len, budget, r.deadline,
                            r.priority, admitted_at=now, extra=r.extra)
            self.pool.seat(slot, info)
            self._live[r.uid] = info
            self._collected[r.uid] = []
            self.slot_steps[slot] = r.prompt_len    # join reset the cache

    # ------------------------------------------------------------- decode
    def _dispatch(self):
        """Enqueue one decode chunk on the chained device state. Returns
        immediately — tokens are a future harvested later."""
        owners = self.pool.owners()
        obs = self._counter_names is not None
        with obs_trace.span("sched.dispatch", seq=self.steps_dispatched,
                            active=len(self._live)):
            if self.draft is not None:
                args = (self.params, self.draft.params, self.cache,
                        self.dstate, self.probs, self.pos, self.rng,
                        self.done, self.budget)
                toks, st = self._chunk_spec_fn(
                    *(args + (self.counters_dev,) if obs else args))
                self.cache, self.dstate = st["cache"], st["dstate"]
                self.probs = st["probs"]
                self._rounds = self._rounds + st["rounds"]
                self._drafted = self._drafted + st["drafted"]
                self._accepted = self._accepted + st["accepted"]
            else:
                args = (self.params, self.cache, self.logits, self.pos,
                        self.rng, self.done, self.budget)
                toks, st = self._chunk_fn(
                    *(args + (self.counters_dev,) if obs else args))
                self.cache, self.logits = st["cache"], st["logits"]
            if obs:
                self.counters_dev = st["counters"]
            self.pos, self.rng = st["pos"], st["rng"]
            self.done, self.budget = st["done"], st["budget"]
            self.steps_dispatched += 1
            # every slot steps through decode_step each chunk (done slots
            # included — lockstep semantics), so all caches advance
            self.slot_steps += self.chunk
            self._dq.push(toks, owners,
                          counters=self.counters_dev if obs else None)

    def _harvest(self, now: float) -> list:
        """Sync the oldest in-flight chunk's tokens and account them to
        the requests that owned each slot at ITS dispatch time."""
        inflight = self._dq.harvest()
        if inflight is None:
            return []
        with obs_trace.span("sched.harvest", seq=inflight.seq):
            with obs_trace.span("sched.sync"):
                toks_np = np.asarray(inflight.tokens)   # the one host sync
            if inflight.counters is not None:
                # the chunk is host-materialized by the sync above; its
                # counter snapshot reads out with no extra sync point
                self._counters_host = obs_counters.harvest(
                    self._counter_names, inflight.counters)
            events: list = []
            evictions: list[int] = []
            for slot, uid in enumerate(inflight.owners):
                info = self._live.get(uid) if uid is not None else None
                if info is None:    # idle, or finished before this sync
                    continue
                fresh: list[int] = []
                for t in toks_np[slot]:
                    if info.remaining <= 0:
                        break
                    t = int(t)
                    fresh.append(t)
                    info.remaining -= 1
                    info.emitted += 1
                    if self.sampling.stops and t == self.sampling.eos_id:
                        info.remaining = 0
                if fresh:
                    out = self._collected[uid]
                    first = not out
                    out.extend(fresh)
                    if self.on_token is not None:
                        self.on_token(uid, fresh, first)
                    events.append(TokenEvent(uid, fresh, first))
                if info.remaining <= 0:
                    events.append(self._finish(uid, "done"))
                elif info.deadline is not None and now > info.deadline:
                    # past-deadline occupant: free the slot, freeze it on
                    # device so chunks dispatched from here on skip it
                    evictions.append(info.slot)
                    events.append(self._finish(uid, "expired"))
            if evictions:
                with obs_trace.span("sched.evict", slots=len(evictions)):
                    self.done = self._evict_fn(
                        self.done, jnp.asarray(evictions, jnp.int32))
        return events

    def _finish(self, uid: int, reason: str) -> Finished:
        info = self._live.pop(uid)
        self.pool.free(info.slot)
        toks = np.asarray(self._collected.pop(uid), np.int32)
        return Finished(uid, toks, info.prompt_len, reason)

    # -------------------------------------------------------------- drive
    def _step_events(self) -> list:
        """One scheduler iteration: deliver shed notices, admit, keep the
        dispatch pipeline full, harvest the oldest chunk. Returns the
        step's TokenEvent/Finished stream."""
        events: list = self._drops
        self._drops = []
        events += self._admit(self._clock())
        if self._live:
            while self._dq.want_dispatch:
                self._dispatch()
        if self._dq:
            events += self._harvest(self._clock())
        return events

    def step(self) -> list[Finished]:
        """Admit, decode one chunk, harvest, evict. Returns the requests
        that completed (or were shed/expired) this step; per-token output
        flows through ``on_token`` / ``events()``."""
        return [e for e in self._step_events() if isinstance(e, Finished)]

    def events(self):
        """Incremental-results iterator: yields ``TokenEvent``s as chunks
        are harvested and ``Finished`` as requests complete, until the
        engine drains."""
        while self.busy:
            yield from self._step_events()

    def run(self) -> dict[int, np.ndarray]:
        """Drive until queue, slots, and the dispatch pipeline drain.
        Returns {uid: tokens} (shed/expired requests included, with
        whatever prefix they produced)."""
        results: dict[int, np.ndarray] = {}
        for ev in self.events():
            if isinstance(ev, Finished):
                results[ev.uid] = ev.tokens
        return results

    def spec_stats(self) -> dict | None:
        """Cumulative speculative-round accounting (one host sync):
        ``rounds``/``drafted``/``accepted`` totals plus the aggregate
        ``acceptance_rate`` = accepted / drafted. None without a draft."""
        if self.draft is None:
            return None
        rounds = int(np.sum(np.asarray(self._rounds)))
        drafted = int(np.sum(np.asarray(self._drafted)))
        accepted = int(np.sum(np.asarray(self._accepted)))
        return dict(rounds=rounds, drafted=drafted, accepted=accepted,
                    acceptance_rate=accepted / max(drafted, 1))

    def counters(self) -> dict | None:
        """The harvested on-device counter dict (None when the engine was
        built without ``counters=True``).

        While chunks are in flight this returns the snapshot read at the
        last harvest (no sync). Once the pipeline drains — the normal
        read point, after ``run()`` — the chained vector's final value is
        identical to the last harvested snapshot, and reading it forces
        nothing new (every feeding dispatch already synced)."""
        if self._counter_names is None:
            return None
        if self._dq and self._counters_host is not None:
            return dict(self._counters_host)
        return obs_counters.harvest(self._counter_names, self.counters_dev)
