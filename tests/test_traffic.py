"""repro.traffic: pool/admission/dispatch machinery, the load generator,
bucketed prefill parity, streaming, deadlines, and the scheduler fuzz.

The parity bar throughout: the dispatch-ahead scheduler must reproduce
the batch=1 lockstep ``ServeEngine`` trajectory token for token under
greedy sampling — for dense weights, packed BRDS weights, Θ=0 temporal
delta, and calibrated-int8 packed weights — regardless of pipeline
depth, prompt bucketing, arrival interleave, or forced evictions.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import LSTMModel, LSTMConfig
from repro.serving import (ContinuousBatchingEngine, SamplingConfig,
                           ServeEngine, prefill_accepts_length)
from repro.sparse import (DeltaGateConfig, QuantConfig, lstm_policy,
                          use_backend)
from repro.traffic import (AdmissionQueue, Arrival, DispatchQueue,
                           LoadConfig, QueuedRequest, RequestRecord,
                           SlotInfo, SlotPool, make_prompts, percentile,
                           poisson_trace, serve_trace, summarize)


@pytest.fixture(scope="module")
def lstm():
    cfg = LSTMConfig("t", input_size=8, hidden=16, num_layers=2,
                     vocab_size=32)
    model = LSTMModel(cfg)
    params = model.init(jax.random.key(0))
    return cfg, model, params


# ---------------------------------------------------------------- loadgen
def test_poisson_trace_deterministic():
    lc = LoadConfig(rate=10.0, num_requests=40, deadline=1.5,
                    priorities=(0, 1), seed=3)
    a, b = poisson_trace(lc), poisson_trace(lc)
    assert a == b                       # same seed → identical schedule
    c = poisson_trace(LoadConfig(rate=10.0, num_requests=40, deadline=1.5,
                                 priorities=(0, 1), seed=4))
    assert a != c                       # seed actually drives the draw
    ts = [x.t for x in a]
    assert ts == sorted(ts) and ts[0] > 0
    for x in a:
        assert lc.prompt_short[0] <= x.prompt_len <= lc.prompt_long[1]
        assert lc.output_lens[0] <= x.max_new <= lc.output_lens[1]
        assert x.deadline == 1.5 and x.priority in (0, 1)
    p1, p2 = make_prompts(a, vocab=32, seed=3), make_prompts(a, 32, seed=3)
    assert all(np.array_equal(x, y) for x, y in zip(p1, p2))
    with pytest.raises(ValueError):
        poisson_trace(LoadConfig(rate=0.0, num_requests=1))


# ------------------------------------------------------------------- pool
def test_slot_pool_lifecycle():
    pool = SlotPool(3)
    assert pool.free_count == 3 and len(pool) == 0
    s0, s1 = pool.alloc(), pool.alloc()
    pool.seat(s0, SlotInfo(uid=7, prompt_len=4, remaining=2))
    pool.seat(s1, SlotInfo(uid=8, prompt_len=5, remaining=3))
    assert pool.owner(s0) == 7 and pool.info(s0).slot == s0
    assert sorted(pool.active()) == sorted([s0, s1])
    snapshot = pool.owners()
    with pytest.raises(RuntimeError):   # double-seat is a bug
        pool.seat(s0, SlotInfo(uid=9, prompt_len=1, remaining=1))
    freed = pool.free(s0)
    assert freed.uid == 7 and pool.owner(s0) is None
    assert snapshot[s0] == 7            # snapshots don't mutate
    with pytest.raises(RuntimeError):
        pool.free(s0)
    assert pool.alloc() == s0           # LIFO: freed slot reused first
    pool.release_unseated(s0)
    got = pool.alloc_many(5)            # capped at what's free
    assert len(got) == 2 and pool.alloc() is None
    with pytest.raises(ValueError):
        SlotPool(0)


# -------------------------------------------------------------- admission
def test_admission_queue_ordering_and_shedding():
    q = AdmissionQueue(max_queue=3)

    def req(uid, *, deadline=None, priority=0, arrival=0.0):
        return QueuedRequest(uid, None, 4, 4, deadline=deadline,
                             priority=priority, arrival=arrival)

    assert q.push(req(0, deadline=9.0, arrival=0.0)) is None
    assert q.push(req(1, deadline=2.0, arrival=0.1)) is None
    assert q.push(req(2, priority=1, arrival=0.2)) is None
    # full: worst = lowest priority, latest deadline → uid 0 is shed
    shed = q.push(req(3, deadline=1.0, arrival=0.3))
    assert shed.uid == 0
    # priority band first, then deadline-monotonic
    assert [r.uid for r in q.pop(3)] == [2, 3, 1]
    # an incoming request that is itself the worst bounces straight back
    q2 = AdmissionQueue(max_queue=1)
    q2.push(req(5, priority=5))
    assert q2.push(req(6, priority=0)).uid == 6
    # queued expiry
    q3 = AdmissionQueue()
    q3.push(req(7, deadline=1.0))
    q3.push(req(8, deadline=5.0))
    q3.push(req(9))
    gone = q3.expire(now=2.0)
    assert [r.uid for r in gone] == [7] and len(q3) == 2
    with pytest.raises(ValueError):
        AdmissionQueue(max_queue=0)


# ---------------------------------------------------------------- metrics
def test_metrics_records_and_summary():
    recs = [
        RequestRecord(0, scheduled=0.0, deadline=2.0, first_token=0.5,
                      finished=1.0, tokens=6, reason="done"),
        RequestRecord(1, scheduled=0.0, deadline=0.8, first_token=0.4,
                      finished=1.0, tokens=4, reason="done"),   # late
        RequestRecord(2, scheduled=0.1, tokens=0, reason="expired"),
        RequestRecord(3, scheduled=0.2, tokens=0, reason="rejected"),
    ]
    assert recs[0].ttft == 0.5
    assert recs[0].tpot == pytest.approx(0.1)    # (1.0-0.5)/(6-1)
    assert recs[2].ttft is None and recs[2].tpot is None
    assert recs[0].in_deadline and not recs[1].in_deadline
    s = summarize(recs, wall=2.0, offered_rps=5.0)
    assert s["requests"] == 4 and s["completed"] == 2
    assert s["expired"] == 1 and s["rejected"] == 1
    assert s["tokens"] == 10 and s["offered_rps"] == 5.0
    assert s["toks_per_s"] == pytest.approx(5.0)
    assert s["goodput_tps"] == pytest.approx(3.0)   # late tokens excluded
    assert s["p50_ttft_ms"] == pytest.approx(450.0)
    assert math.isnan(percentile([], 50))


# ------------------------------------------------- bucketed prefill parity
def test_bucketed_prefill_bitwise(lstm):
    """Padded-to-bucket prefill with length= is BITWISE the unpadded
    prefill — logits and every cache leaf — for dense, packed, and Θ=0
    delta params (the one compiled scan body serves all widths)."""
    cfg, model, params = lstm
    plan = lstm_policy(0.75, 0.5, backend="ref").compile(params)
    pruned, masks = plan.prune(params)
    packed, _ = plan.pack(pruned, masks)
    dmodel = model.with_delta(DeltaGateConfig(theta_x=0.0, theta_h=0.0))
    cases = [(model, params), (model, packed), (dmodel, packed)]
    rng = np.random.default_rng(0)
    with use_backend("ref"):
        for m, p in cases:
            assert prefill_accepts_length(m)
            for L, W in ((3, 4), (5, 8), (6, 16)):
                toks = np.zeros((1, W), np.int32)
                toks[0, :L] = rng.integers(0, cfg.vocab_size, size=L)
                lgp, cp = m.prefill(p, jnp.asarray(toks), max_len=24,
                                    length=jnp.asarray([L], jnp.int32))
                lgr, cr = m.prefill(p, jnp.asarray(toks[:, :L]), max_len=24)
                np.testing.assert_array_equal(np.asarray(lgp),
                                              np.asarray(lgr))
                eq = jax.tree.map(
                    lambda a, b: np.array_equal(np.asarray(a),
                                                np.asarray(b)), cp, cr)
                assert all(jax.tree.leaves(eq))


def test_bucketing_compiles_once_per_bucket(lstm):
    """Distinct prompt lengths inside one bucket share a single prefill
    trace; only new bucket widths retrace (the recompile hazard the
    pow-2 padding removes)."""
    cfg, model, params = lstm
    calls = []
    real = model.prefill

    class Probe:
        # the DecodeStep contract, spelled out: a runtime-checkable
        # Protocol finds members statically (Python >= 3.12), so
        # __getattr__ delegation alone would not conform
        def cache_defs(self, batch, max_len):
            return model.cache_defs(batch, max_len)

        def init_cache(self, batch, max_len):
            return model.init_cache(batch, max_len)

        def decode_step(self, p, cache, tokens, pos):
            return model.decode_step(p, cache, tokens, pos)

        def __getattr__(self, name):
            return getattr(model, name)

        def prefill(self, p, toks, max_len, extra=None, length=None):
            calls.append(toks.shape[1])
            return real(p, toks, max_len, extra=extra, length=length)

    sched = ContinuousBatchingEngine(Probe(), params, slots=2, max_len=32,
                                     chunk=4)
    rng = np.random.default_rng(1)
    for plen in (3, 4, 5, 6, 7, 8, 9):   # buckets: 4, 8, 16
        sched.submit(rng.integers(0, cfg.vocab_size, size=(1, plen)), 2)
        sched.run()
    assert sorted(set(calls)) == [4, 8, 16]
    # jit retraces once per shape: 3 bucket widths → 3 traced widths,
    # even though 7 distinct prompt lengths were served
    assert len(set(calls)) == 3


def test_unbucketed_fallback_without_length_support(lstm):
    """A DecodeStep model whose prefill has no ``length`` parameter still
    serves — at exact-length batch=1 prefill (old numerics)."""
    cfg, model, params = lstm
    widths = []

    class NoLen:
        def cache_defs(self, b, m):
            return model.cache_defs(b, m)

        def init_cache(self, b, m):
            return model.init_cache(b, m)

        def prefill(self, p, toks, max_len, extra=None):
            widths.append(toks.shape[1])
            return model.prefill(p, toks, max_len, extra=extra)

        def decode_step(self, p, c, t, pos):
            return model.decode_step(p, c, t, pos)

    nl = NoLen()
    assert not prefill_accepts_length(nl)
    sched = ContinuousBatchingEngine(nl, params, slots=2, max_len=32,
                                     chunk=4)
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, cfg.vocab_size, size=(1, n))
               for n in (3, 5, 6)]
    uids = [sched.submit(p, 4) for p in prompts]
    got = sched.run()
    assert widths == [3, 5, 6]          # exact lengths, one per request
    eng = ServeEngine(model, cfg, max_len=32, batch=1)
    for uid, p in zip(uids, prompts):
        np.testing.assert_array_equal(
            got[uid], np.asarray(eng.generate(params, jnp.asarray(p), 4))[0])
    # the ragged lockstep engine path refuses outright instead of
    # silently changing numerics
    eng_nl = ServeEngine(nl, cfg, max_len=32, batch=2)
    with pytest.raises(TypeError):
        eng_nl.generate(params, jnp.zeros((2, 4), jnp.int32), 2,
                        lengths=[3, 4])


def test_ragged_lockstep_generate(lstm):
    """ServeEngine.generate(lengths=) serves a ragged batch in ONE
    lockstep call, each row matching its unpadded batch=1 decode."""
    cfg, model, params = lstm
    rng = np.random.default_rng(3)
    lens = [3, 7, 5, 8]
    toks = np.zeros((4, 8), np.int32)
    for i, L in enumerate(lens):
        toks[i, :L] = rng.integers(0, cfg.vocab_size, size=L)
    eng = ServeEngine(model, cfg, max_len=32, batch=4)
    out = np.asarray(eng.generate(params, jnp.asarray(toks), 6,
                                  lengths=np.asarray(lens)))
    for i, L in enumerate(lens):
        ref = np.asarray(eng.generate(params, jnp.asarray(toks[i:i+1, :L]),
                                      6))[0]
        np.testing.assert_array_equal(out[i], ref)


# -------------------------------------------------- streaming + deadlines
def test_streaming_callbacks_and_events(lstm):
    cfg, model, params = lstm
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, cfg.vocab_size, size=(1, n))
               for n in (3, 6, 4)]
    streamed: dict[int, list] = {}
    firsts: dict[int, int] = {}

    def on_token(uid, toks, first):
        streamed.setdefault(uid, []).extend(toks)
        firsts[uid] = firsts.get(uid, 0) + bool(first)

    sched = ContinuousBatchingEngine(model, params, slots=2, max_len=32,
                                     chunk=3, on_token=on_token)
    uids = [sched.submit(p, 7) for p in prompts]
    finished = {}
    from repro.serving import TokenEvent, Finished
    for ev in sched.events():
        if isinstance(ev, TokenEvent):
            assert ev.tokens                 # no empty events
        elif isinstance(ev, Finished):
            finished[ev.uid] = ev
    eng = ServeEngine(model, cfg, max_len=32, batch=1)
    for uid, p in zip(uids, prompts):
        ref = np.asarray(eng.generate(params, jnp.asarray(p), 7))[0]
        np.testing.assert_array_equal(np.asarray(streamed[uid], np.int32),
                                      ref)
        np.testing.assert_array_equal(finished[uid].tokens, ref)
        assert firsts[uid] == 1              # exactly one first=True
    # run() stays the thin wrapper over the same event stream
    sched2 = ContinuousBatchingEngine(model, params, slots=2, max_len=32,
                                      chunk=3)
    uids2 = [sched2.submit(p, 7) for p in prompts]
    got = sched2.run()
    for uid, uid2 in zip(uids, uids2):
        np.testing.assert_array_equal(got[uid2], finished[uid].tokens)


def test_deadlines_expire_evict_and_shed(lstm):
    """The three overload outcomes: queued requests past deadline expire
    un-prefilled; in-slot overruns are evicted (tokens so far kept, a
    prefix of the reference); a bounded queue sheds the worst request."""
    cfg, model, params = lstm
    rng = np.random.default_rng(5)
    clk = [0.0]
    sched = ContinuousBatchingEngine(model, params, slots=1, max_len=64,
                                     chunk=4, clock=lambda: clk[0],
                                     max_queue=2)
    p_hog = rng.integers(0, cfg.vocab_size, size=(1, 4))
    p_exp = rng.integers(0, cfg.vocab_size, size=(1, 5))
    # priority 1 → admitted first despite the later deadline; holds the
    # one slot until evicted at clk > 9
    hog = sched.submit(p_hog, 40, deadline=9.0, priority=1)
    fin = {}
    for f in sched.step():                # admit the hog into the slot
        fin[f.uid] = f
    exp = sched.submit(p_exp, 4, deadline=5.0)        # rots behind the hog
    filler = sched.submit(rng.integers(0, cfg.vocab_size, size=(1, 3)), 2)
    # queue full (exp + filler): pushing a better request sheds the worst
    vip = sched.submit(rng.integers(0, cfg.vocab_size, size=(1, 3)), 2,
                       priority=1)
    while sched.busy:
        for f in sched.step():
            fin[f.uid] = f
        clk[0] += 2.0
    assert fin[filler].reason == "rejected" and not len(fin[filler].tokens)
    assert fin[exp].reason == "expired" and not len(fin[exp].tokens)
    assert fin[hog].reason == "expired"       # evicted mid-decode
    eng = ServeEngine(model, cfg, max_len=64, batch=1)
    ref = np.asarray(eng.generate(params, jnp.asarray(p_hog), 40))[0]
    n = len(fin[hog].tokens)
    assert 0 < n < 40
    np.testing.assert_array_equal(fin[hog].tokens, ref[:n])
    # the evicted slot was re-armed cleanly for the VIP (fresh EOS/budget)
    assert fin[vip].reason == "done" and len(fin[vip].tokens) == 2


# ------------------------------------------------------------------- fuzz
def _fuzz_round(model, params, ref_model, ref_params, cfg, *, seed, slots,
                chunk, depth, n_req, prefill_batch=1):
    """Random arrival interleave + ragged lengths through a small pool;
    returns ({uid: tokens}, {uid: (prompt, budget, reason)})."""
    rng = np.random.default_rng(seed)
    max_len = 48
    sched = ContinuousBatchingEngine(
        model, params, slots=slots, max_len=max_len, chunk=chunk,
        dispatch_depth=depth, prefill_batch=prefill_batch,
        clock=lambda: 0.0)
    reqs, fin = {}, {}
    submitted = 0
    while submitted < n_req or sched.busy:
        # bursty arrivals interleaved with decode steps
        for _ in range(int(rng.integers(0, 3))):
            if submitted >= n_req:
                break
            plen = int(rng.integers(2, 12))
            budget = int(rng.integers(1, 9))
            prompt = rng.integers(0, cfg.vocab_size,
                                  size=(1, plen)).astype(np.int32)
            uid = sched.submit(prompt, budget)
            reqs[uid] = (prompt, budget)
            submitted += 1
        for f in sched.step():
            fin[f.uid] = f
    eng = ServeEngine(ref_model, cfg, max_len=max_len, batch=1)
    for uid, (prompt, budget) in reqs.items():
        assert fin[uid].reason == "done"
        ref = np.asarray(eng.generate(ref_params, jnp.asarray(prompt),
                                      budget))[0]
        np.testing.assert_array_equal(
            fin[uid].tokens, ref,
            err_msg=f"uid {uid} (plen={prompt.shape[1]}, gen={budget}, "
                    f"slots={slots}, chunk={chunk}, depth={depth})")


def test_scheduler_fuzz_dense_and_packed(lstm):
    """Random arrivals, ragged prompts, tiny pools (forced queueing and
    slot reuse), dispatch depths 1-3: every request reproduces its
    batch=1 lockstep decode exactly — dense and packed BRDS weights."""
    cfg, model, params = lstm
    plan = lstm_policy(0.75, 0.5, backend="ref").compile(params)
    pruned, masks = plan.prune(params)
    packed, _ = plan.pack(pruned, masks)
    with use_backend("ref"):
        for seed, slots, chunk, depth in ((0, 2, 4, 2), (1, 3, 5, 1),
                                          (2, 2, 3, 3)):
            _fuzz_round(model, params, model, params, cfg, seed=seed,
                        slots=slots, chunk=chunk, depth=depth, n_req=8)
        _fuzz_round(model, packed, model, packed, cfg, seed=3, slots=2,
                    chunk=4, depth=2, n_req=8, prefill_batch=2)


def test_scheduler_fuzz_delta_and_quant(lstm):
    """Θ=0 temporal delta and calibrated-int8 packed params hold the same
    parity bar under the dispatch-ahead fuzz."""
    cfg, model, params = lstm
    with use_backend("ref"):
        # Θ=0 delta over packed weights
        deng = ServeEngine(model, cfg, max_len=48, batch=1,
                           sparsity=lstm_policy(
                               0.75, 0.5,
                               delta=DeltaGateConfig(theta_x=0.0,
                                                     theta_h=0.0)))
        dpacked, _ = deng.prepare(params)
        _fuzz_round(deng.model, dpacked, deng.model, dpacked, cfg, seed=4,
                    slots=2, chunk=4, depth=2, n_req=6)
        # calibrated int8 (static scales: exact at any prefill batch)
        calib = jax.random.randint(jax.random.key(9), (2, 12), 0,
                                   cfg.vocab_size)
        qeng = ServeEngine(model, cfg, max_len=48, batch=1,
                           sparsity=lstm_policy(0.75, 0.5,
                                                quant=QuantConfig("int8")))
        qpacked, _ = qeng.prepare(params, calib=calib)
        _fuzz_round(qeng.model, qpacked, qeng.model, qpacked, cfg, seed=5,
                    slots=2, chunk=4, depth=2, n_req=6, prefill_batch=2)


# ------------------------------------------------------------ serve_trace
def test_serve_trace_closed_loop_deterministic(lstm):
    """Closed-loop trace serving: every request completes, token outputs
    are reproducible, and the summary counts add up."""
    cfg, model, params = lstm
    lc = LoadConfig(rate=100.0, num_requests=9, prompt_short=(2, 5),
                    prompt_long=(6, 10), output_lens=(2, 6), seed=11)
    trace = poisson_trace(lc)
    prompts = make_prompts(trace, cfg.vocab_size, seed=11)
    outs = []
    for _ in range(2):
        sched = ContinuousBatchingEngine(model, params, slots=3,
                                         max_len=32, chunk=4)
        collected = {}
        sched.on_token = (lambda uid, t, f:
                          collected.setdefault(uid, []).extend(t))
        recs, s = serve_trace(sched, trace, prompts, realtime=False,
                              offered_rps=lc.rate)
        assert s["requests"] == 9 and s["completed"] == 9
        assert s["expired"] == 0 and s["rejected"] == 0
        assert s["tokens"] == sum(r.tokens for r in recs)
        assert s["offered_rps"] == 100.0
        for r in recs:
            assert r.first_token is not None and r.finished is not None
            assert r.ttft >= 0
        outs.append({u: list(v) for u, v in collected.items()})
    assert outs[0] == outs[1]           # same trace → same tokens
