"""repro.obs: span tracer, on-device counters, metrics registry,
effective-GOPS scorecard, and the collective inventory.

The load-bearing invariants:

- DISABLED IS EXACT: with ``counters=False`` the scheduler jits the
  unmodified chunk functions and the disabled tracer hands back one
  shared no-op span — trajectories are bitwise those of the
  uninstrumented stack (and with counters ON they must not change
  either: the counter folds only read the chunk state).
- PARITY: harvested on-device counters equal the offline reductions the
  repo already trusts — fired-column gauges == the delta cache's
  ``nx``/``nh`` sums (``occupancy_report``'s input), spec counters ==
  ``spec_stats()``, scorecard executed MACs == ``occupancy_report``'s
  ``effective_macs`` on the same cache.
- ONE ALL-GATHER per layer per decode step on a sharded mesh
  (docs/architecture.md's repro.dist table), measured from compiled HLO.
"""
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import LSTMModel, LSTMConfig
from repro.obs import collectives as OC
from repro.obs import counters as C
from repro.obs import metrics as M
from repro.obs import scorecard as S
from repro.obs import trace as T
from repro.serving import (ContinuousBatchingEngine, SamplingConfig,
                           ServeEngine)
from repro.sparse import DeltaGateConfig, lstm_policy, occupancy_report
from repro.spec import DraftModel
from repro.traffic import RequestRecord, summarize

CFG = LSTMConfig("t", input_size=16, hidden=32, num_layers=2,
                 vocab_size=48)
GREEDY = SamplingConfig(eos_id=-1)


def _prep(theta):
    """Delta-gated packed LSTM serving variant (ref backend)."""
    model = LSTMModel(CFG)
    params = model.init(jax.random.key(0))
    pol = lstm_policy(0.5, 0.5, backend="ref",
                      delta=DeltaGateConfig(theta_x=theta, theta_h=theta))
    eng = ServeEngine(model, CFG, max_len=32, batch=3, sparsity=pol)
    packed, _ = eng.prepare(params)
    return eng, packed


def _submit_all(sched, lens, gen=8):
    for i, plen in enumerate(lens):
        prompt = jax.random.randint(jax.random.fold_in(jax.random.key(1), i),
                                    (1, plen), 0, CFG.vocab_size)
        sched.submit(prompt, gen)


# ----------------------------------------------------------------- tracer
def test_disabled_tracer_is_one_shared_null_span():
    T.disable()
    s1, s2 = T.span("a"), T.span("b", cat="x", k=3)
    assert s1 is s2                     # no per-call allocation
    with s1:
        pass
    assert T.get_tracer().events == []


def test_tracer_spans_nest_and_export_validates(tmp_path):
    T.enable()
    try:
        with T.span("outer", phase="p"):
            with T.span("inner"):
                pass
        T.instant("mark", note=1)

        @T.traced("decorated")
        def f(x):
            return x + 1

        assert f(1) == 2
    finally:
        T.disable()
    payload = T.get_tracer().export()
    assert T.validate(payload) == []
    names = [e["name"] for e in payload["traceEvents"]]
    assert set(names) == {"outer", "inner", "mark", "decorated"}
    evs = {e["name"]: e for e in payload["traceEvents"]}
    # inner nests inside outer: starts later, ends no later
    assert evs["inner"]["ts"] >= evs["outer"]["ts"]
    assert (evs["inner"]["ts"] + evs["inner"]["dur"]
            <= evs["outer"]["ts"] + evs["outer"]["dur"] + 1e-6)
    assert evs["outer"]["args"] == {"phase": "p"}
    # export is ts-sorted, survives a save/validate-file round trip + CLI
    ts = [e["ts"] for e in payload["traceEvents"]]
    assert ts == sorted(ts)
    path = tmp_path / "trace.json"
    T.get_tracer().save(str(path))
    assert T.validate_file(str(path)) == []
    assert T.main([str(path)]) == 0
    T.get_tracer().clear()


def test_trace_validator_catches_malformed(tmp_path):
    ev = dict(name="a", ph="X", ts=1.0, dur=1.0, pid=1, tid=1)
    assert T.validate([ev]) == []
    assert T.validate({"traceEvents": "nope"})
    assert T.validate([dict(ev, ph="Q")])            # unknown phase
    assert T.validate([dict(ev, dur=-2.0)])          # negative dur
    assert T.validate([{k: v for k, v in ev.items() if k != "ts"}])
    assert T.validate([dict(ev, ts=5.0), dict(ev, ts=1.0)])  # unsorted
    b = dict(name="a", ph="B", ts=1.0, pid=1, tid=1)
    e = dict(name="a", ph="E", ts=2.0, pid=1, tid=1)
    assert T.validate([b, e]) == []
    assert T.validate([b])                           # unclosed B
    assert T.validate([e])                           # E without B
    # CLI: empty trace and unreadable file both fail the gate
    empty = tmp_path / "empty.json"
    empty.write_text('{"traceEvents": []}')
    assert T.main([str(empty)]) != 0
    assert T.main([str(tmp_path / "missing.json")]) != 0


def _xplane_host_events(path) -> list:
    """(name, start ns, dur ns, stats) of every ``/host:`` event of a
    profiler trace directory's ``.xplane.pb``."""
    import glob
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                      recursive=True)
    assert len(files) == 1, files
    out = []
    for plane in ProfileData.from_file(files[0]).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(e.name, e.start_ns, e.duration_ns, dict(e.stats))
                        for e in line.events]
    return out


def test_enabled_span_lands_in_the_profiler_trace(tmp_path):
    """An enabled span opens a profiler annotation of its name: its
    scalar args become event statistics, its list args stay in the
    tracer's own event."""
    T.enable()
    try:
        with jax.profiler.trace(str(tmp_path)):
            with T.span("sched.prefill", bucket=16, batch=2, tag="b",
                        uids=[3, 4]):
                pass
    finally:
        T.disable()
    evs = [e for e in _xplane_host_events(tmp_path)
           if e[0] == "sched.prefill"]
    assert len(evs) == 1
    _, _, dur, stats = evs[0]
    assert dur > 0
    assert stats == {"bucket": 16, "batch": 2, "tag": "b"}
    (ev,) = T.get_tracer().events
    assert ev["args"] == {"bucket": 16, "batch": 2, "tag": "b",
                          "uids": [3, 4]}
    T.get_tracer().clear()


def test_disabled_tracer_makes_no_annotation(monkeypatch):
    made, annotation = [], T._annotation
    monkeypatch.setattr(T, "_annotation",
                        lambda *a: made.append(a) or annotation(*a))
    T.disable()
    sp = T.span("sched.harvest", seq=1)
    assert sp is T.NULL and sp is T.span("other")
    with sp:
        pass
    assert made == [] and T.get_tracer().events == []
    assert not T.enabled()


def _virtual_sched(traced: bool, on_token=None):
    """A tiny scheduler on a virtual clock: 2 slots, 5 requests submitted
    a second apart, then driven with the clock 0.25 s further per step.
    Returns (results, tracer events, {uid: arrival}, [(clock at step,
    events recorded in it)])."""
    model = LSTMModel(CFG)
    params = model.init(jax.random.key(0))
    now = [0.0]
    sched = ContinuousBatchingEngine(model, params, slots=2, max_len=32,
                                     sampling=GREEDY, chunk=4,
                                     clock=lambda: now[0],
                                     on_token=on_token)
    arrival = {}
    for i, plen in enumerate([3, 5, 9, 2, 6]):
        now[0] = float(i)
        prompt = jax.random.randint(jax.random.fold_in(jax.random.key(1),
                                                       i),
                                    (1, plen), 0, CFG.vocab_size)
        arrival[sched.submit(prompt, 6 + i)] = now[0]
    if traced:
        T.enable()
    steps, results = [], {}
    try:
        while sched.busy:
            now[0] += 0.25
            seen = len(T.get_tracer().events)
            for fin in sched.step():
                results[fin.uid] = fin.tokens
            steps.append((now[0], T.get_tracer().events[seen:]))
    finally:
        T.disable()
    return results, list(T.get_tracer().events), arrival, steps


def test_prefill_spans_carry_uids_and_queue_wait():
    results, events, arrival, steps = _virtual_sched(True)
    assert set(results) == set(arrival)
    uids = []
    for t_step, evs in steps:
        for ev in evs:
            if ev["name"] != "sched.prefill":
                continue
            a = ev["args"]
            assert a["batch"] == len(a["uids"]) == len(a["lengths"]) \
                == len(a["waited_ms"])
            assert a["bucket"] >= max(a["lengths"])
            for uid, waited in zip(a["uids"], a["waited_ms"]):
                assert waited == pytest.approx(1e3 * (t_step - arrival[uid]))
            uids += a["uids"]
    # every admitted request appears in exactly one prefill span
    assert sorted(uids) == sorted(arrival)
    names = {e["name"] for e in events}
    assert {"sched.admit", "sched.prefill", "sched.dispatch",
            "sched.harvest", "sched.sync"} <= names


def _inside(child, parent) -> bool:
    return (parent["ts"] <= child["ts"] and child["ts"] + child["dur"]
            <= parent["ts"] + parent["dur"] + 1e-6)


def test_sync_and_prefill_nest_in_their_parents():
    streamed = []       # tracer clock at each on_token callback

    def on_token(uid, toks, first):
        streamed.append(T.get_tracer()._now_us())

    results, events, _, _ = _virtual_sched(True, on_token)
    by = lambda name: [e for e in events if e["name"] == name]
    harvests, admits = by("sched.harvest"), by("sched.admit")
    assert by("sched.sync") and len(by("sched.sync")) == len(harvests)
    for name, parents in (("sched.sync", harvests),
                          ("sched.prefill", admits)):
        for ev in by(name):
            assert sum(_inside(ev, p) for p in parents) == 1, name
    # the harvest covers its token loop: every callback falls inside one
    assert streamed
    for t in streamed:
        assert sum(_inside({"ts": t, "dur": 0}, h) for h in harvests) == 1
    assert T.validate(T.get_tracer().export()) == []
    T.get_tracer().clear()


def test_tracing_does_not_change_tokens():
    off, _, _, _ = _virtual_sched(False)
    on, _, _, _ = _virtual_sched(True)
    T.get_tracer().clear()
    assert off.keys() == on.keys()
    for uid in off:
        assert np.array_equal(off[uid], on[uid])


def test_scheduler_spans_reach_the_host_plane(tmp_path):
    """Under the profiler the scheduler's spans land on the host plane,
    one event per tracer span, each harvest with its chunk's ``seq``."""
    with jax.profiler.trace(str(tmp_path)):
        _, events, _, _ = _virtual_sched(True)
    T.get_tracer().clear()
    evs = _xplane_host_events(tmp_path)
    names = ("sched.admit", "sched.prefill", "sched.dispatch",
             "sched.harvest", "sched.sync")
    for name in names:
        host = [e for e in evs if e[0] == name]
        assert host and len(host) == sum(e["name"] == name for e in events)
    seqs = [st["seq"] for n, _, _, st in evs if n == "sched.harvest"]
    assert sorted(seqs) == [e["args"]["seq"] for e in events
                            if e["name"] == "sched.dispatch"]


# ---------------------------------------------------------------- metrics
def test_metrics_registry_kinds_and_exports(tmp_path):
    reg = M.MetricsRegistry()
    reg.counter("req_total", "requests").inc()
    reg.counter("req_total").inc(2)
    with pytest.raises(ValueError):
        reg.counter("req_total").inc(-1)
    with pytest.raises(TypeError):
        reg.gauge("req_total")                       # kind clash
    reg.gauge("depth").set(2.5)
    h = reg.histogram("lat_ms", buckets=(1, 10))
    h.observe(0.5)
    h.observe(5.0)
    h.observe(50.0)
    h.observe(float("nan"))                          # dropped, not summed
    assert h.count == 3 and h.sum == 55.5
    text = reg.to_prometheus()
    assert "# TYPE req_total counter" in text and "req_total 3" in text
    assert 'lat_ms_bucket{le="+Inf"} 3' in text
    assert "nan" not in text.lower()
    js = reg.to_json()
    assert js["req_total"]["value"] == 3
    assert js["lat_ms"]["buckets"][-1] == {"le": "+Inf", "count": 3}
    # both dump formats land on disk; JSON is strict (allow_nan=False)
    reg.dump(str(tmp_path / "m.prom"))
    reg.dump(str(tmp_path / "m.json"))
    assert json.load(open(tmp_path / "m.json"))["depth"]["value"] == 2.5


def test_metrics_absorbers():
    recs = [RequestRecord(0, scheduled=0.0, first_token=0.5, finished=1.0,
                          tokens=6, reason="done"),
            RequestRecord(1, scheduled=0.0, tokens=0, reason="rejected")]
    summary = summarize(recs, wall=2.0, offered_rps=4.0)
    reg = M.MetricsRegistry()
    reg.absorb_traffic(recs, summary)
    reg.absorb_spec({"rounds": 3, "drafted": 9, "accepted": 6,
                     "acceptance_rate": 2 / 3})
    reg.absorb_counters({"tokens": 6.0, "fired_x_l0": 11.0})
    js = reg.to_json()
    assert js["serve_requests_done"]["value"] == 1
    assert js["serve_requests_rejected"]["value"] == 1
    assert js["serve_tokens_total"]["value"] == 6
    assert js["spec_accepted_total"]["value"] == 6
    assert js["dev_fired_x_l0"]["value"] == 11.0
    # absorb is total-function on empty/None inputs
    reg2 = M.MetricsRegistry()
    reg2.absorb_traffic([], summarize([], wall=0.0))
    reg2.absorb_spec(None)
    reg2.absorb_counters(None)
    json.dumps(reg2.to_json(), allow_nan=False)


# --------------------------------------------- traffic summary edge cases
def test_summarize_empty_and_one_token_have_no_nan():
    s = summarize([], wall=0.0)
    assert s["requests"] == 0 and s["toks_per_s"] == 0.0
    for key in ("p50_ttft_ms", "p90_ttft_ms", "p99_ttft_ms",
                "p50_tpot_ms", "p99_tpot_ms"):
        assert s[key] is None
    json.dumps(s, allow_nan=False)      # NaN would corrupt BENCH records
    # a 1-token completion has no inter-token gap: tpot is None, and a
    # batch of only such requests must not push NaN into the summary
    one = RequestRecord(0, scheduled=0.0, first_token=0.25, finished=0.25,
                        tokens=1, reason="done")
    assert one.tpot is None and one.ttft == 0.25
    s1 = summarize([one], wall=1.0)
    assert s1["p50_tpot_ms"] is None
    assert s1["p50_ttft_ms"] == pytest.approx(250.0)
    json.dumps(s1, allow_nan=False)


# ----------------------------------------------------- on-device counters
def test_counter_names_and_layout():
    model = LSTMModel(CFG)                              # no delta
    assert C.counter_names(model) == C.BASE_COUNTERS
    eng, _ = _prep(0.1)
    names = C.counter_names(eng.model)
    assert names[:len(C.BASE_COUNTERS)] == C.BASE_COUNTERS
    assert names[len(C.BASE_COUNTERS):] == ("fired_x_l0", "fired_h_l0",
                                            "fired_x_l1", "fired_h_l1")
    vec = C.zeros(names)
    assert vec.shape == (len(names),) and vec.dtype == jnp.float32
    d = C.harvest(names, vec)
    assert set(d) == set(names) and all(v == 0.0 for v in d.values())
    assert C.fired_totals(d) == ([0.0, 0.0], [0.0, 0.0])


@pytest.mark.parametrize("theta", [0.0, 0.1])
def test_scheduler_counters_match_occupancy_report(theta):
    """The tentpole parity: counters harvested at the scheduler's own
    syncs == the offline reductions on the drained cache, exactly."""
    eng, packed = _prep(theta)
    sched = ContinuousBatchingEngine(eng.model, packed, slots=3,
                                     max_len=32, sampling=GREEDY, chunk=4,
                                     counters=True)
    _submit_all(sched, [5, 7, 9], gen=8)
    results = sched.run()
    c = sched.counters()
    assert c is not None
    # fired gauges == the cache sums occupancy_report reads
    for i, lp in enumerate(sched.cache["layers"]):
        assert c[f"fired_x_l{i}"] == float(np.asarray(jnp.sum(lp["nx"])))
        assert c[f"fired_h_l{i}"] == float(np.asarray(jnp.sum(lp["nh"])))
    # emitted-token and step counters match the scheduler's own books
    assert c["tokens"] == sum(len(v) for v in results.values())
    assert c["decode_steps"] == sched.steps_dispatched * sched.chunk
    # scorecard's fired-weighted MACs == occupancy_report, same cache
    occ = occupancy_report(sched.cache, steps=sched.slot_steps,
                           packed=packed)
    card = S.build(packed, c, 1.0, batch=3,
                   step_sum=float(np.sum(sched.slot_steps)))
    assert card["executed_macs"] == pytest.approx(occ["effective_macs"])
    assert card["occupancy_x"] == pytest.approx(occ["occupancy_x"],
                                                abs=1e-4)
    assert card["occupancy_h"] == pytest.approx(occ["occupancy_h"],
                                                abs=1e-4)
    # (Θ=0 makes the TRAJECTORY exact, not occupancy 1.0 — exact-zero
    # deltas, e.g. repeated tokens, legitimately never fire)


def test_counters_do_not_change_tokens():
    """Instrumented and uninstrumented schedulers serve identical tokens
    (counters only read the chunk state; disabled jits the original
    chunk fn, so golden trajectories stay bitwise untouched)."""
    eng, packed = _prep(0.1)
    outs = []
    for flag in (False, True):
        sched = ContinuousBatchingEngine(eng.model, packed, slots=3,
                                         max_len=32, sampling=GREEDY,
                                         chunk=4, counters=flag)
        _submit_all(sched, [5, 7, 9], gen=8)
        outs.append(sched.run())
    assert outs[0].keys() == outs[1].keys()
    for uid in outs[0]:
        assert np.array_equal(np.asarray(outs[0][uid]),
                              np.asarray(outs[1][uid]))
    # and the uninstrumented scheduler reports no counters
    assert ContinuousBatchingEngine(
        eng.model, packed, slots=2, max_len=32).counters() is None


def test_spec_counters_match_spec_stats():
    model = LSTMModel(CFG)
    params = model.init(jax.random.key(0))
    draft = DraftModel(model, params)   # the target drafts for itself
    sched = ContinuousBatchingEngine(model, params, slots=2, max_len=32,
                                     sampling=GREEDY, chunk=4,
                                     draft=draft, spec_k=3, counters=True)
    _submit_all(sched, [5, 8], gen=8)
    results = sched.run()
    st = sched.spec_stats()
    c = sched.counters()
    assert st["drafted"] > 0
    assert c["spec_rounds"] == st["rounds"]
    assert c["spec_drafted"] == st["drafted"]
    assert c["spec_accepted"] == st["accepted"]
    assert c["tokens"] == sum(len(v) for v in results.values())


def test_lockstep_from_state_matches_occupancy_report():
    eng, packed = _prep(0.1)
    prompt = jax.random.randint(jax.random.key(2), (3, 6), 0,
                                CFG.vocab_size)
    out, st = eng.generate(packed, prompt, 8, sampling=GREEDY,
                           rng=jax.random.key(3), return_state=True)
    c = C.from_state(eng.model, st, steps=8)
    assert c["tokens"] == float(np.sum(np.asarray(st["emitted"]))) == 24.0
    for i, lp in enumerate(st["cache"]["layers"]):
        assert c[f"fired_x_l{i}"] == float(np.asarray(jnp.sum(lp["nx"])))
        assert c[f"fired_h_l{i}"] == float(np.asarray(jnp.sum(lp["nh"])))
    occ = occupancy_report(st["cache"], steps=6 + 8, packed=packed)
    card = S.build(packed, c, 1.0, batch=3, step_sum=3.0 * (6 + 8))
    assert card["executed_macs"] == pytest.approx(occ["effective_macs"])
    assert card["occupancy_x"] == pytest.approx(occ["occupancy_x"],
                                                abs=1e-4)


# -------------------------------------------------------------- scorecard
def test_scorecard_geometry_and_bounds_dense():
    from repro import hw
    kind = "TPU v5 lite"                    # checks the formula on any host
    model = LSTMModel(CFG)
    params = model.init(jax.random.key(0))
    geo = S.layer_geometry(params)
    assert len(geo) == CFG.num_layers
    assert geo[0]["ncols_x"] == CFG.input_size
    assert geo[1]["ncols_x"] == CFG.hidden          # stacked layers
    dense = sum(g["dense_macs"] for g in geo)
    assert dense == sum(g["packed_macs"] for g in geo)  # dense: K = ncols
    nbytes = S.weight_stream_bytes(params)
    assert nbytes == sum(params["layers"][i][k].nbytes
                         for i in range(CFG.num_layers)
                         for k in ("w_x", "w_h"))
    card = S.build(params, {"tokens": 100.0, "decode_steps": 100.0},
                   wall_s=2.0, batch=4, device_kind=kind)
    assert card["toks_per_s"] == 50.0
    assert card["executed_macs"] == 100.0 * dense   # no fired gauges
    assert card["effective_gops"] == pytest.approx(
        2.0 * dense * 50.0 / 1e9, abs=1e-6)       # card rounds to 6 dp
    assert card["bound_toks_per_s"] == pytest.approx(
        4 * hw.peaks(kind).hbm_bytes_per_s / nbytes, rel=1e-3)
    assert "occupancy_x" not in card                # needs step_sum
    text = S.render(card)
    assert "effective GOPS" in text and "roofline bound" in text


def test_scorecard_packed_counts_packed_bytes():
    eng, packed = _prep(0.0)
    geo = S.layer_geometry(packed)
    assert all(g["k_x"] < g["ncols_x"] for g in geo)    # actually pruned
    nbytes = S.weight_stream_bytes(packed)
    expect = sum(int(packed["layers"][i][k].memory_bytes()["total"])
                 for i in range(CFG.num_layers) for k in ("w_x", "w_h"))
    assert nbytes == expect


# ------------------------------------------------------------ collectives
def test_collective_inventory_summarize():
    items = [{"kind": "all-gather", "mult": 2, "bytes": 64,
              "wire_bytes": 128, "where": "a"},
             {"kind": "all-gather", "mult": 1, "bytes": 32,
              "wire_bytes": 32, "where": "b"},
             {"kind": "all-reduce", "mult": 1, "bytes": 8,
              "wire_bytes": 8, "where": "c"}]
    s = OC.summarize_inventory(items)
    assert s == {"counts": {"all-gather": 3, "all-reduce": 1},
                 "wire_bytes": 168}
    with pytest.raises(ValueError):
        OC.inventory_from_text("no entry computation here")


REPO_SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run(code: str, devices: int = 8, timeout: int = 420) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = REPO_SRC
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, timeout=timeout,
                         env=env)
    assert out.returncode == 0, f"stderr:\n{out.stderr[-3000:]}"
    return out.stdout


def test_decode_step_has_one_allgather_per_layer():
    """docs/architecture.md's repro.dist table, measured: a sharded
    decode step's compiled HLO contains exactly ``num_layers``
    all-gathers (of h over the model axis) and no other collective."""
    out = _run("""
    import jax, jax.numpy as jnp
    from repro.models import LSTMModel, LSTMConfig
    from repro.serving import ServeEngine
    from repro.sparse import lstm_policy
    from repro.launch.mesh import make_host_mesh
    from repro.obs import collectives as OC

    cfg = LSTMConfig('t', input_size=16, hidden=64, num_layers=2,
                     vocab_size=50)
    model = LSTMModel(cfg)
    params = model.init(jax.random.key(0))
    mesh = make_host_mesh(1, 8)
    eng = ServeEngine(model, cfg, max_len=20, batch=4,
                      sparsity=lstm_policy(0.75, 0.5, backend='ref'),
                      mesh=mesh)
    p, _ = eng.prepare(params)
    assert eng._dist, 'engine did not take the repro.dist path'
    prompt = jax.random.randint(jax.random.key(1), (4, 7), 0,
                                cfg.vocab_size)
    logits, cache = eng.model.prefill(p, prompt, 20)
    tok = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)[:, None]
    pos = jnp.full((4,), 7, jnp.int32)
    items = OC.decode_step_inventory(eng.model, p, cache, tok, pos)
    s = OC.summarize_inventory(items)
    print('COUNTS', s['counts'])
    assert s['counts'] == {'all-gather': cfg.num_layers}, s
    """)
    assert "COUNTS" in out
