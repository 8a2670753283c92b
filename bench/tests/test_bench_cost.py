"""The shape-computed bytes and operations, against counts made by hand
for the two configurations."""
import bench_tiny  # noqa: F401
from bench.lib import cost, registry

BENCH = registry.benchmark()


def test_ptb_large_counts():
    cfg = registry.config(BENCH, "lstm_ptb_large")
    dims = cost.layer_dims(cfg)
    assert len(dims) == 2
    for d in dims:
        assert (d["kx"], d["kh"], d["padded_rows"]) == (375, 750, 6144)
        weights = cost.step_call_bytes(d, 0) - 6144 * 4
        assert weights == 41_472_000
    assert cost.lstm_flops_per_step(cfg) == 2 * 2 * (6000 * 375 + 6000 * 750)
    assert cost.head_flops(cfg) == 2 * 1500 * 10000


def test_timit_counts():
    cfg = registry.config(BENCH, "lstm_timit")
    (d,) = cost.layer_dims(cfg)
    assert (d["kx"], d["kh"], d["padded_rows"]) == (38, 512, 4096)
    assert cost.step_call_bytes(d, 0) - 4096 * 4 == 4096 * (38 + 512) * 6
    assert cost.step_call_bytes(d, 64) - cost.step_call_bytes(d, 0) == \
        64 * (153 + 2 * 1024 + 2 * 1024) * 4
    assert cost.step_call_flops(d, 64) == 2 * 64 * 4096 * 550
    assert cost.head_flops(cfg) == 2 * 1024 * 61
