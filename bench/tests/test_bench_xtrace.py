"""The trace reduction: busy share, time per kernel and per program, and
idle gaps named by the host span that covered them."""
import collections
import types
from pathlib import Path

import pytest

import bench_tiny  # noqa: F401
from bench.lib import xtrace


def _raw():
    # window 0..1000 ns; ops overlap at 300..400; idle 0..100, 500..800
    ops = [("fusion.1", 100, 300), ("custom-call.7", 300, 200),
           ("fusion.2", 800, 150), ("fusion.3", 990, 100)]
    mods = [("jit_prefill(1)", 90, 420), ("jit__chunk_impl(2)", 790, 400)]
    host = [("bench.window", 0, 1000), ("sched.step", 0, 700),
            ("driver.wait", 450, 400)]
    return {"host": host, "devices": {"/device:TPU:0": {"ops": ops,
                                                        "modules": mods}}}


def test_busy_ops_modules_and_gaps():
    r = xtrace.reduce(_raw())
    assert r.window_s == pytest.approx(1000e-9)
    # union of [100,500) [800,950) [990,1000) clipped to the window
    assert r.busy["/device:TPU:0"] == 400 + 150 + 10
    assert [o.name for o in r.ops] == ["fusion.1", "custom-call.7",
                                       "fusion.2"]
    assert [o.module for o in r.ops] == ["jit_prefill(1)"] * 2 + \
        ["jit__chunk_impl(2)"]
    assert r.op_seconds(lambda n: n.startswith("custom")) == \
        pytest.approx(200e-9)
    assert r.gaps == [(0, 100), (500, 300), (950, 40)]
    top = r.top_gaps(2)
    assert [g[0].split(" @ ")[0] for g in top] == ["driver.wait",
                                                   "sched.step"]
    assert top[0][1] == pytest.approx(300e-9)


def test_union_and_clip():
    assert xtrace.union([(5, 7), (1, 3), (2, 4)]) == [[1, 4], [5, 7]]
    assert xtrace.clip([(0, 5), (8, 12), (20, 30)], 2, 10) == [(2, 5),
                                                               (8, 10)]


# A quarter second of a traced ptb_decode run on one TPU v5e chip (seed
# 3000000901): two decode chunks and a prefill of the packed two-layer
# LSTM at 2 x 1500. Its run reported rb_step_roofline.decode
# 0.9949582464241816 and idle_share.decode 0.464121972347753.
RECORDED = Path(__file__).with_name("data") / "ptb_decode_short.xplane.pb"


def test_recorded_chip_trace():
    from bench.lib import peaks, readers, registry
    r = xtrace.reduce(xtrace.read(RECORDED))
    assert r.devices == ["/device:TPU:0"]
    assert r.window_s == pytest.approx(0.253625355, rel=1e-9)
    assert r.busy_s == pytest.approx(0.252448224, rel=1e-9)
    step = readers.matcher(readers.names()["kernels"]["rb_step"])
    calls = collections.Counter(readers.module_class(o.module)
                                for o in r.ops if step(o.label))
    # one call per layer and step, whole calls inside the window only
    assert calls == {"decode": 43, "prefill": 4}
    assert r.op_seconds(step) == pytest.approx(0.244423909, rel=1e-9)
    gap = r.top_gaps(1)[0]
    assert gap[0].startswith("$<unknown> append @ ")
    assert gap[1] == pytest.approx(1.160291e-3, rel=1e-9)
    ctx = types.SimpleNamespace(
        trace=r, facts={"batch": {"prefill": 1, "decode": 32}},
        cfg=registry.config(registry.benchmark(), "lstm_ptb_large"),
        peaks=peaks.PEAKS["TPU v5 lite"])
    assert readers.kernel_roofline(ctx, "rb_step") == \
        pytest.approx(0.9949582464241816, rel=1e-9)
    assert readers.idle_share(ctx) == pytest.approx(0.464121972347753,
                                                    rel=1e-9)
