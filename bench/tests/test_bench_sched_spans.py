"""The scheduler layer's readers on hand-built contexts: host share, queue
wait, prefill padding and the device idle the scheduler's host work
caused; each returns None where its spans are missing, as on a program
that does not record them."""
import types

import pytest

import bench_tiny
from bench.lib import readers, registry, sched_spans, xtrace


def _reader(name):
    return registry.metric_reader(name).read


def _span(name, t, dur, **args):
    return {"name": name, "t": t, "dur": dur, "args": args}


def _ctx(spans=(), trace=None):
    return types.SimpleNamespace(spans=list(spans), trace=trace, facts={})


# the tracer's list in a window: two admissions, each with its prefill
# group, among the other spans, which the list readers pass over
SPANS = [
    _span("sched.sync", 0.000, 0.004),
    _span("sched.prefill", 0.011, 0.003, bucket=16, batch=1, uids=[7],
          lengths=[12], waited_ms=[900.0]),
    _span("sched.admit", 0.010, 0.010, queued=3, free=1),
    _span("sched.dispatch", 0.020, 0.002, seq=4, active=2),
    _span("sched.sync", 0.023, 0.045),
    _span("sched.evict", 0.069, 0.001, slots=1),
    _span("sched.harvest", 0.022, 0.050, seq=3),
    _span("sched.prefill", 0.080, 0.004, bucket=8, batch=2, uids=[8, 9],
          lengths=[5, 8], waited_ms=[100.0, 300.0]),
    _span("sched.admit", 0.079, 0.006, queued=2, free=2),
]


def test_queue_wait_p50():
    assert _reader("queue_wait_p50_ms.decode")(_ctx(SPANS)) == 300.0


def test_prefill_pad_share():
    # (16 + 16) positions run, 12 + 5 + 8 of them prompt
    assert _reader("prefill_pad_share.decode")(_ctx(SPANS)) == \
        pytest.approx(100.0 * 7 / 32)


def _reduced(host):
    # window 0..1000 ns; the first device idle at 100..200, 400..600 and
    # 800..850
    return xtrace.Reduced(t0=0, t1=1000, devices=["/device:TPU:0"], ops=[],
                          busy={"/device:TPU:0": 650},
                          gaps=[(100, 100), (400, 200), (800, 50)],
                          host=host)


HOST = [("bench.window", 0, 1000), ("sched.step", 0, 1000),
        ("sched.admit", 50, 100),       # covers 100..150 of the first gap
        ("sched.prefill", 60, 80),
        ("sched.harvest", 350, 300),    # covers 400..600 ...
        ("sched.sync", 450, 150),       # ... but waits on the device 450..600
        ("_value", 460, 100)]


def test_sched_host_share():
    # 50..150 in the admission, 350..450 and 600..650 in the harvest
    # around its sync
    ctx = _ctx(trace=_reduced(HOST))
    assert _reader("sched_host_share.decode")(ctx) == pytest.approx(25.0)
    # a harvest open at the window's opening counts from there, less its
    # sync's part in the window: 30..50
    edge = HOST + [("sched.harvest", -100, 150), ("sched.sync", -60, 90)]
    assert _reader("sched_host_share.decode")(_ctx(trace=_reduced(edge))) \
        == pytest.approx(27.0)


def test_idle_in_host_counts_work_not_the_wait():
    ctx = _ctx(trace=_reduced(HOST))
    got = _reader("idle_in_host.decode")(ctx)
    # 100..150 in the admission, 400..450 in the harvest before its sync
    assert got == pytest.approx(100.0 * 100 / 1000)
    assert got <= readers.idle_share(ctx) == pytest.approx(35.0)


def test_interval_arithmetic():
    assert sched_spans.subtract([(0, 10), (20, 30)], [(2, 4), (8, 22)]) \
        == [(0, 2), (4, 8), (22, 30)]
    assert sched_spans.subtract([(0, 10)], []) == [(0, 10)]
    assert sched_spans.overlap_ns([(0, 10), (20, 30)], [(5, 25)]) == 10
    assert sched_spans.overlap_ns([], [(5, 25)]) == 0


# what a program without these spans records: the dispatch and a
# harvest around the sync alone, no sync, prefill or args of them
OLD = [_span("sched.admit", 0.01, 0.01, queued=3, free=1),
       _span("sched.dispatch", 0.02, 0.002, seq=4, active=2),
       _span("sched.harvest", 0.022, 0.045, seq=3)]


@pytest.mark.parametrize("name", ["sched_host_share.decode",
                                  "queue_wait_p50_ms.decode",
                                  "prefill_pad_share.decode"])
@pytest.mark.parametrize("spans", [[], OLD], ids=["none", "old_program"])
def test_span_readers_read_nothing_without_their_spans(name, spans):
    assert _reader(name)(_ctx(spans)) is None


def test_host_share_needs_a_window():
    empty = xtrace.Reduced(500, 500, [], [], {}, [], HOST)
    assert _reader("sched_host_share.decode")(_ctx(trace=empty)) is None


@pytest.mark.parametrize("host", [
    [("bench.window", 0, 1000), ("sched.step", 0, 1000)],
    [("sched.admit", 50, 100), ("sched.dispatch", 200, 10),
     ("sched.harvest", 350, 300)],
], ids=["no_program_spans", "no_sync"])
@pytest.mark.parametrize("name", ["sched_host_share.decode",
                                  "idle_in_host.decode"])
def test_host_work_readers_need_the_sync(name, host):
    """Without ``sched.sync`` a harvest's wait on the device cannot be told
    from its work, so neither reader reads."""
    assert _reader(name)(_ctx(trace=_reduced(host))) is None


@pytest.mark.parametrize("trace", [
    None,
    _reduced([("bench.window", 0, 1000), ("sched.step", 0, 1000),
              ("_value", 460, 100)]),
    xtrace.Reduced(0, 1000, [], [], {}, [], HOST),
], ids=["untraced", "no_program_spans", "no_device"])
def test_idle_in_host_reads_nothing_without_spans_or_device(trace):
    assert _reader("idle_in_host.decode")(_ctx(trace=trace)) is None


def test_traced_tiny_run_reads_the_scheduler_spans():
    """A traced ptb_decode run at tiny width on the CPU (no device plane
    there, so the device-trace readers stay silent) reports the three
    span readers, each within its range."""
    result, run = bench_tiny.run("ptb_decode", trace=True)
    m = {k: v["value"] for k, v in result["metrics"].items()}
    assert 0 < m["sched_host_share.decode"] <= 100
    assert m["queue_wait_p50_ms.decode"] > 0
    assert 0 <= m["prefill_pad_share.decode"] < 100
    names = {s["name"] for s in run.spans}
    assert {"sched.admit", "sched.prefill", "sched.dispatch",
            "sched.harvest", "sched.sync"} <= names
